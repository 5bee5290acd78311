"""pjit train step: microbatch gradient accumulation (scan) + remat +
optional distillation + optional int8 error-feedback gradient compression.

State layout keeps fp32 master params; compute casts to cfg.dtype at use.
Under FSDP sharding rules everything (params / grads / m / v / EF error)
is fully sharded — ZeRO-3 semantics from sharding alone.

Gradient paths:

* default — gradients come out of a global-view ``value_and_grad`` (XLA
  inserts the data all-reduce); ``grad_shardings`` pins the microbatch
  accumulation carry to the FSDP param shardings so the carry is
  reduce-scattered instead of replicated.
* ``grad_compression="int8_ef"`` — the loss/grad computation runs inside a
  ``shard_map`` over the mesh data axes: each shard takes grads on its
  local batch slice, quantizes them to int8 against a psum-max consensus
  scale, and the int8 ``psum`` IS the data all-reduce (4x fewer bytes than
  fp32); the quantization residual is carried per shard in
  ``TrainState.ef_err`` (leading shard axis, sharded over the data axes).
  Configuring compression without a mesh/data axes raises — there is no
  all-reduce to compress on one device.

The aux metrics of ``distillation_loss`` (task_loss / logit_kl / token_l2)
ride through ``value_and_grad(..., has_aux=True)`` into the returned
metrics dict, so distillation runs can log them without a second forward.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..configs.base import MeshConfig, TrainConfig
from ..distill.losses import distillation_loss
from ..distributed.activation import activation_context
from ..distributed.sharding import (axis_size, batch_sharding,
                                    param_shardings)
from ..optim.adamw import adamw_init, adamw_update, clip_by_global_norm
from ..optim.compression import int8_ef_compress, int8_ef_init
from ..optim.schedule import make_schedule


class TrainState(NamedTuple):
    params: Any
    opt: Any
    step: jnp.ndarray
    ef_err: Any = None          # int8 error-feedback residuals (optional)


def _ef_nshards(tcfg: TrainConfig, mesh, mc: Optional[MeshConfig]) -> int:
    """Shard count the EF residual is carried over; raises on a
    misconfigured (no data axes) compression setup."""
    if tcfg.grad_compression != "int8_ef":
        return 0
    if mesh is None or mc is None or not tuple(mc.data_axes):
        raise ValueError(
            "grad_compression='int8_ef' compresses the data-parallel "
            "all-reduce and needs a mesh with data axes (pass mesh= and "
            "mc= / MeshConfig with non-empty data_axes); without them the "
            "configuration would silently train uncompressed")
    return axis_size(mesh, tuple(mc.data_axes))


def make_train_state(cfg, params, tcfg: TrainConfig, *, mesh=None,
                     mc: Optional[MeshConfig] = None) -> TrainState:
    n = _ef_nshards(tcfg, mesh, mc)
    ef = int8_ef_init(params, n) if n else None
    return TrainState(params=params, opt=adamw_init(params),
                      step=jnp.zeros((), jnp.int32), ef_err=ef)


def state_shardings(mesh, mc: MeshConfig, state: TrainState, specs):
    pshard = param_shardings(mesh, mc, state.params, specs)
    ef = None
    if state.ef_err is not None:
        # EF leaves are (nshards, *param_shape): one residual slice per
        # data shard, so only the leading axis shards
        ef_sh = NamedSharding(mesh, P(tuple(mc.data_axes)))
        ef = jax.tree.map(lambda _: ef_sh, state.ef_err)
    return TrainState(
        params=pshard,
        opt={"m": pshard, "v": pshard,
             "count": NamedSharding(mesh, P())},
        step=NamedSharding(mesh, P()),
        ef_err=ef)


def _split_microbatches(batch: Dict, n: int, mesh=None,
                        mc: Optional[MeshConfig] = None) -> Dict:
    """(B, ...) -> (n_micro, B/n, ...). Without an explicit constraint XLA
    may shard the *microbatch* dim over data (replicating the batch inside
    the loop -> n x activation memory), so pin dim0=None, dim1=data."""
    def split(x):
        y = x.reshape(n, x.shape[0] // n, *x.shape[1:])
        if mesh is not None and mc is not None:
            from ..distributed.sharding import batch_axes
            ba = batch_axes(mesh, mc, x.shape[0] // n)
            spec = P(None, ba, *([None] * (y.ndim - 2)))
            y = jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, spec))
        return y

    return jax.tree.map(split, batch)


def make_train_step(cfg, tcfg: TrainConfig, *, teacher_params=None,
                    masks=None, mesh=None, mc: Optional[MeshConfig] = None,
                    grad_shardings=None):
    """Build the train step. masks: optional params-shaped {0,1} pytree
    multiplied into params after each update (gradual pruning keeps pruned
    structures at zero). grad_shardings: pin the microbatch grad-accum
    carry to the FSDP param shardings — without it XLA all-reduces full
    gradients every microbatch instead of reduce-scattering to the shard.
    """
    schedule = make_schedule(tcfg.learning_rate, tcfg.warmup_steps,
                             tcfg.total_steps)
    compress = _ef_nshards(tcfg, mesh, mc) > 0
    data_axes = tuple(mc.data_axes) if mc is not None else ()

    def _pin(tree):
        if grad_shardings is None:
            return tree
        return jax.tree.map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s),
            tree, grad_shardings)

    def loss_for(params, mb):
        return distillation_loss(
            cfg, params, teacher_params, mb, l_task=tcfg.distill_task,
            l_logit=tcfg.distill_logit, l_token=tcfg.distill_token)

    grad_fn = jax.value_and_grad(loss_for, has_aux=True)

    def accum_grads(params, batch, *, constrain: bool):
        """(aux_metrics, grads) on ``batch``, microbatch-accumulated.
        ``constrain=False`` inside shard_map (global-view sharding
        constraints are illegal there)."""
        n_micro = tcfg.microbatches
        pin = _pin if constrain else (lambda t: t)
        if n_micro > 1:
            mbs = _split_microbatches(batch, n_micro,
                                      mesh if constrain else None, mc)

            def acc_body(g_acc, mb):
                (_, aux), g = grad_fn(params, mb)
                g_acc = pin(jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, pin(g)))
                return g_acc, aux

            zeros = pin(jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params))
            grads, aux_stack = jax.lax.scan(acc_body, zeros, mbs)
            aux = jax.tree.map(lambda a: jnp.mean(a, axis=0), aux_stack)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
        else:
            (_, aux), grads = grad_fn(params, batch)
        return aux, grads

    if compress:
        def _sharded_grads(params, batch, ef):
            # per-shard body: batch is this shard's slice, ef is its
            # (1, *shape) residual slice
            ef = jax.tree.map(lambda e: e[0], ef)
            aux, grads = accum_grads(params, batch, constrain=False)
            grads, new_ef = int8_ef_compress(grads, ef, data_axes)
            aux = jax.tree.map(lambda a: jax.lax.pmean(a, data_axes), aux)
            return aux, grads, jax.tree.map(lambda e: e[None], new_ef)

        sharded_grads = jax.shard_map(
            _sharded_grads, mesh=mesh,
            in_specs=(P(), P(data_axes), P(data_axes)),
            out_specs=(P(), P(), P(data_axes)),
            check_vma=False)

    def train_step(state: TrainState, batch: Dict):
        params = state.params
        if compress:
            # the activation-context constraint hooks inside the model
            # forward are global-view ops; they must stay no-ops while the
            # shard_map body traces
            with activation_context(None, None):
                aux, grads, new_ef = sharded_grads(params, batch,
                                                   state.ef_err)
        else:
            aux, grads = accum_grads(params, batch, constrain=True)
            new_ef = state.ef_err

        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        lr = schedule(state.step)
        new_params, new_opt = adamw_update(
            grads, state.opt, params, lr=lr, b1=tcfg.beta1, b2=tcfg.beta2,
            weight_decay=tcfg.weight_decay)
        if masks is not None:
            new_params = jax.tree.map(
                lambda p, m: p * m.astype(p.dtype), new_params, masks)
        metrics = {**aux, "grad_norm": gnorm, "lr": lr}
        return TrainState(params=new_params, opt=new_opt,
                          step=state.step + 1, ef_err=new_ef), metrics

    return train_step


def make_eval_step(cfg):
    def eval_step(params, batch):
        from ..models.model import loss_fn
        return loss_fn(cfg, params, batch)["loss"]

    return eval_step


def jit_train_step(cfg, tcfg, mesh, mc: MeshConfig, state, specs, batch,
                   **kw):
    """jit with explicit in/out shardings and donated state.

    ``batch`` is an example batch (pytree of arrays or ShapeDtypeStructs);
    each leaf's leading dim shards over the mesh data axes. Unless
    overridden, the microbatch grad-accum carry is pinned to the FSDP
    param shardings (``grad_shardings``). Donation is skipped on CPU where
    it is a no-op that only emits warnings.
    """
    st_sh = state_shardings(mesh, mc, state, specs)
    kw.setdefault("grad_shardings", st_sh.params)
    step_fn = make_train_step(cfg, tcfg, mesh=mesh, mc=mc, **kw)
    b_sh = jax.tree.map(
        lambda x: batch_sharding(mesh, mc, x.shape[0]), batch)
    donate = mc.donate and jax.default_backend() != "cpu"
    return jax.jit(step_fn,
                   in_shardings=(st_sh, b_sh),
                   out_shardings=(st_sh, None),
                   donate_argnums=(0,) if donate else ())
