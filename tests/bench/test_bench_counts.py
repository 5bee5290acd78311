"""Operation and byte counts against hand counts for one GPT-2 layer, dense
and pruned, and GPT-2's counts of both configurations pinned on a fixed
admissions record."""
import numpy as np
import pytest

import harness
import serve_job
from conftest import BENCH

counts = harness.load_module(BENCH, "counts", "gpt2")

LAYER = {"n_layer": 1, "n_embd": 768, "n_head": 12, "head_dim": 64,
         "n_inner": 3072, "vocab_size": 50257}


def test_matmul_and_attention_operations_of_one_layer():
    d, f, v = 768, 3072, 50257
    qkv, out, mlp = 2 * d * 3 * d, 2 * d * d, 2 * (2 * d * f)
    assert counts.matmul_flops_per_token(LAYER) == qkv + out + mlp
    # scores and weighted values over 100 keys, 12 heads of 64
    assert counts.attn_flops(LAYER, 100) == 2 * (2 * 100 * 12 * 64)
    assert counts.head_flops(LAYER) == 2 * d * v
    s = 5   # causal prompt: query i attends i + 1 keys; head at the end
    assert counts.prefill_flops(LAYER, s) == (
        s * (qkv + out + mlp) + sum(4 * 768 * (i + 1) for i in range(s))
        + 2 * d * v)


def test_decode_steps_of_one_layer():
    d, f, v = 768, 3072, 50257
    weights = (v * d + 2 * d                       # table, final norm
               + 4 * d * d + 2 * d                 # q, k, v, o; norm
               + 2 * d * f + f + d + 2 * d)        # MLP, biases; norm
    kv_pos = 2 * d * 2                             # K and V in bf16
    qkv, out, mlp = 2 * d * 3 * d, 2 * d * d, 2 * (2 * d * f)
    # one step whose two active slots (of three) feed positions 10 and 20,
    # and an empty step
    flops, nbytes = counts.decode_steps(LAYER, np.array([[10, -1, 20],
                                                         [-1, -1, -1]]))
    assert nbytes[0] == (2 * weights + 2 * 2 * d   # weights, position rows
                         + (10 + 20) * kv_pos      # K/V held
                         + 2 * kv_pos)             # one new position each
    assert flops[0] == 2 * (qkv + out + mlp + 2 * d * v) + sum(
        4 * 768 * (p + 1) for p in (10, 20))
    assert (flops[1], nbytes[1]) == (0, 2 * weights)


def test_pruned_layer_counts_only_what_it_keeps():
    member = dict(LAYER, layers=[{"heads": 1, "n_inner": 0}])
    d = 768
    assert counts.matmul_flops_per_token(member) == 8 * d * 64
    assert counts.kv_bytes_per_position(member) == 2 * 64 * 2
    dropped = dict(LAYER, layers=[{"heads": 0, "n_inner": 0}])
    assert counts.matmul_flops_per_token(dropped) == 0
    assert counts.weight_elems(dropped) == 50257 * d + 2 * d


# A tiny seeded backlog (stream 0 at seed 7 of a mix of 6 requests through
# 3 slots) as the engine admitted it: (prompt_len, steps) per request, and
# (decode steps before, prompt_len, slot) per admission, 11 decode steps.
REQUESTS = [(16, 3), (7, 11), (4, 6), (6, 4), (11, 2), (9, 7)]
ADMISSIONS = [(0, 16, 0), (0, 7, 1), (0, 4, 2), (2, 6, 0), (5, 11, 2),
              (5, 9, 0)]
NSTEPS, SLOTS = 11, 3

# Per-step operations and bytes, and each prompt's prefill operations, as
# the counts gave them when they saw a step as (active slots, sum of
# positions): moving to per-slot positions leaves every integer as it was.
PINNED = {
    "gpt2-small": {
        "flops": [742298112, 742408704, 742076928, 742187520, 742298112,
                  742482432, 495049728, 495123456, 495197184, 495270912,
                  247617024],
        "bytes": [248343552, 248454144, 248122368, 248232960, 248343552,
                  248527872, 248157696, 248231424, 248305152, 248378880,
                  247787520],
        "prefill": [2800117248, 1267312128, 757040640, 1097184768,
                    1948190208, 1607677440]},
    "gpt2-small-zip2x": {
        "flops": [414698496, 414725376, 414644736, 414671616, 414698496,
                  414743296, 276510464, 276528384, 276546304, 276564224,
                  138277632],
        "bytes": [138510336, 138537216, 138456576, 138483456, 138510336,
                  138555136, 138464000, 138481920, 138499840, 138517760,
                  138372864],
        "prefill": [1053588992, 504084992, 321078272, 443073792, 748219392,
                    626134272]},
}


class _Req:
    def __init__(self, prompt_len, steps):
        self.prompt_len, self.steps = prompt_len, steps


@pytest.mark.parametrize("name", sorted(PINNED))
def test_decode_steps_pinned(name):
    cfg = harness.load_json(BENCH, "configs", name)
    reqs = [_Req(*r) for r in REQUESTS]
    positions = serve_job.step_positions(ADMISSIONS, reqs, NSTEPS, SLOTS)
    flops, nbytes = counts.decode_steps(cfg, positions)
    assert [int(x) for x in flops] == PINNED[name]["flops"]
    assert [int(x) for x in nbytes] == PINNED[name]["bytes"]


@pytest.mark.parametrize("name", sorted(PINNED))
def test_prefill_flops_pinned(name):
    cfg = harness.load_json(BENCH, "configs", name)
    got = [counts.prefill_flops(cfg, s) for _, s, _ in ADMISSIONS]
    assert got == PINNED[name]["prefill"]


def test_step_positions_of_the_record():
    """Each request fills its slot's column from its admission step on,
    one position a step from its prompt length, and nothing else is set;
    the active slots and position sums per step are those the sum-based
    rebuild gave."""
    reqs = [_Req(*r) for r in REQUESTS]
    positions = serve_job.step_positions(ADMISSIONS, reqs, NSTEPS, SLOTS)
    assert positions.shape == (NSTEPS, SLOTS)
    want = np.full((NSTEPS, SLOTS), -1)
    for (a, s, c), r in zip(ADMISSIONS, reqs):
        for j in range(r.steps - 1):
            assert want[a + j, c] == -1     # no two requests share a place
            want[a + j, c] = s + j
    assert (positions == want).all()
    active = positions >= 0
    assert active.sum(1).tolist() == [3, 3, 3, 3, 3, 3, 2, 2, 2, 2, 1]
    assert np.where(active, positions, 0).sum(1).tolist() == [
        27, 30, 21, 24, 27, 32, 23, 25, 27, 29, 14]
