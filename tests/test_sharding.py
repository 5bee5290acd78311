"""Distribution correctness on a real (forced 8-device CPU) mesh, run in a
subprocess so the main test process keeps its single device."""
import pytest

from repro.launch.subproc import run_forced_devices

SCRIPT = r"""
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import smoke_config
from repro.configs.base import MeshConfig, TrainConfig
from repro.data import synthetic_stream, calibration_batches
from repro.distributed.activation import set_activation_context
from repro.distributed.sharding import (batch_sharding, cache_shardings,
                                        make_mesh, param_shardings)
from repro.models import model_init, make_batch
from repro.optim.compression import int8_ef_compress, int8_ef_init
from repro.train.train_step import (TrainState, make_train_state,
                                    make_train_step, state_shardings)
from repro.checkpoint.manager import CheckpointManager

out = {}
mc = MeshConfig((4, 2), ("data", "model"))
mesh = make_mesh((4, 2), ("data", "model"))
set_activation_context(mesh, ("data",))

cfg = smoke_config("qwen2-72b").replace(dtype="float32", num_kv_heads=2)
params, specs = model_init(cfg, jax.random.key(0))
tcfg = TrainConfig(learning_rate=3e-3, microbatches=2, total_steps=20)
state = make_train_state(cfg, params, tcfg)
st_sh = state_shardings(mesh, mc, state, specs)
state = jax.device_put(state, st_sh)
step = jax.jit(make_train_step(cfg, tcfg, mesh=mesh, mc=mc,
                               grad_shardings=st_sh.params),
               in_shardings=(st_sh, None), out_shardings=(st_sh, None))
data = synthetic_stream(cfg, 8, 64, seed=1)
losses = []
for _ in range(14):
    state, m = step(state, next(data))
    losses.append(float(m["loss"]))
out["losses"] = losses
import numpy as _np
out["loss_decreased"] = float(_np.mean(losses[-3:])) < float(
    _np.mean(losses[:3]))

# sharded-vs-single-device equivalence for one step
state1 = make_train_state(cfg, params, tcfg)
step1 = jax.jit(make_train_step(cfg, tcfg))
b = next(synthetic_stream(cfg, 8, 64, seed=1))
s1, m1 = step1(state1, b)
state2 = jax.device_put(make_train_state(cfg, params, tcfg), st_sh)
s2, m2 = step(state2, b)
out["loss_match"] = abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4

# int8 error-feedback compressed psum: mean of per-shard values
g = jax.random.normal(jax.random.key(2), (4, 16), jnp.float32)
err0 = jnp.zeros((4, 16), jnp.float32)  # per-shard err: (1,16) inside

def comp(gl, el):
    avg, e = int8_ef_compress({"g": gl}, {"g": el}, ("data",))
    return avg["g"], e["g"]

f = jax.shard_map(comp, mesh=mesh, in_specs=(P("data"), P("data")),
                  out_specs=(P(None), P("data")))
avg, err = f(g, err0)
true_mean = jnp.mean(g.reshape(4, 1, 16), axis=0)
rel = float(jnp.max(jnp.abs(avg[:1] - true_mean)) /
            (jnp.max(jnp.abs(true_mean)) + 1e-9))
out["compress_rel_err"] = rel
out["compress_ok"] = rel < 0.05
# error feedback: residual equals quantization error
out["ef_nonzero"] = bool(jnp.any(err != 0))

# mesh-agnostic restore: save on (4,2), restore on (2,4)
ck = CheckpointManager("/tmp/shard_ck", keep=1, async_save=False)
ck.save(int(state.step), state)
mc2 = MeshConfig((2, 4), ("data", "model"))
mesh2 = make_mesh((2, 4), ("data", "model"))
st_sh2 = state_shardings(mesh2, mc2, state, specs)
restored = ck.restore(jax.tree.map(lambda x: x, state), shardings=st_sh2)
out["elastic_restore_ok"] = bool(jnp.allclose(
    jax.device_get(restored.params["embed"]["table"]),
    jax.device_get(state.params["embed"]["table"])))

# decode cache shardings valid
from repro.models.model import input_specs
from repro.configs.base import ShapeConfig
sc = ShapeConfig("d", 256, 8, "decode")
cache = input_specs(cfg, sc)["cache"]
csh = cache_shardings(cfg, mesh, mc, cache)
out["cache_shardings_ok"] = True

print("RESULT" + json.dumps(out))
"""


@pytest.mark.tier2
@pytest.mark.slow
def test_distributed_8dev():
    out = run_forced_devices(SCRIPT, 8)
    assert out["loss_decreased"], out["losses"]
    assert out["loss_match"]
    assert out["compress_ok"], out["compress_rel_err"]
    assert out["ef_nonzero"]
    assert out["elastic_restore_ok"]
    assert out["cache_shardings_ok"]
