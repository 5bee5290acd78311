"""The traffic generator: deterministic for a seed, lengths in their clips,
prompt plus output within max_len, the same work in the same random order
for every seed, and a mix that states what it does not implement is
refused."""
import json
import os

import numpy as np
import pytest

import harness
import traffic
from conftest import BENCH

MIXES = ["decode-heavy", "prompt-heavy"]


def _mix(name):
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", MIXES)
def test_stream_is_deterministic_for_a_seed(name):
    mix = _mix(name)
    a = traffic.stream(mix, 50257, 2**31 + 77, 3)
    b = traffic.stream(mix, 50257, 2**31 + 77, 3)
    assert [r.steps for r in a] == [r.steps for r in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    c = traffic.stream(mix, 50257, 2**31 + 78, 3)
    assert any(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, c))


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_in_their_clips(name):
    mix = _mix(name)
    reqs = traffic.stream(mix, 50257, 5, 0)
    p, o = mix["prompt"], mix["output"]
    assert len(reqs) == mix["requests_per_stream"]
    for r in reqs:
        assert p["min"] <= r.prompt_len <= p["max"]
        assert o["min"] <= r.steps <= o["max"]
        assert r.prompt_len + r.steps <= mix["max_len"]
        assert r.tokens.min() >= 0 and r.tokens.max() < 50257


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_serves_the_same_work(name):
    mix = _mix(name)
    a, b, c = (traffic.stream(mix, 50257, seed, i)
               for seed, i in ((1, 0), (2**32 + 9, 0), (1, 4)))
    pa, pb, pc = ([(r.prompt_len, r.steps) for r in x] for x in (a, b, c))
    # the same order of the same pairs for every seed
    assert pa == pb
    assert any(not np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    # another stream queues the same pairs in another order, neither of
    # them by length
    assert sorted(pa) == sorted(pc) and pa != pc
    for steps in ([o for _, o in pa], [o for _, o in pc]):
        assert steps != sorted(steps, reverse=True) and steps != sorted(steps)


@pytest.mark.parametrize("key,value", [
    ("kind", "open_loop"), ("sampling", "top_p"), ("prompt.dist", "gamma"),
    ("output.dist", "uniform"), ("kind", None), ("output.dist", None)])
def test_a_mix_stating_what_is_not_implemented_is_refused(key, value):
    mix = _mix("decode-heavy")
    traffic.check_mix(mix)
    part, _, field = key.rpartition(".")
    where = dict(mix[part]) if part else mix
    if value is None:
        where.pop(field)
    else:
        where[field] = value
    if part:
        mix[part] = where
    with pytest.raises(harness.Refused, match="not implemented"):
        traffic.stream(mix, 50257, 3, 0)


def test_lognormal_quantiles_follow_the_median():
    x = traffic.lognormal_quantiles(1001, 48, 0.5, 1, 10**6)
    assert x[500] == 48 and np.all(np.diff(x) >= 0)
    clipped = traffic.lognormal_quantiles(1001, 48, 0.5, 16, 128)
    assert clipped.min() == 16 and clipped.max() == 128
