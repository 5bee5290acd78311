"""The serving job's check at tiny size on the CPU: a sound run is correct,
the fp8 control in the program's place is not, and each fault the cell can
have, planted under the timed path, turns ``correct`` false."""
import pytest


class Faulty:
    """The engine's model adapter with one fault planted."""

    def __init__(self, model, fault):
        self.model, self.fault = model, fault
        self.max_len = model.max_len
        self.calls = 0

    def init_slots(self, n):
        return self.model.init_slots(n)

    def prefill(self, tokens):
        return self.model.prefill(tokens)

    def insert(self, cache, row, slot, pos):
        return self.model.insert(cache, row, slot, pos)

    def step(self, cache, tokens):
        import jax.numpy as jnp
        self.calls += 1
        logits, new = self.model.step(cache, tokens)
        if self.fault == "state_unchanged":
            return logits, cache
        if self.fault == "half_batch":
            half = logits.shape[0] // 2
            return jnp.concatenate([logits[:half], logits[:half]]), new
        if self.fault == "token_altered" and self.calls % 3 == 0:
            top = jnp.argmax(logits, -1, keepdims=True)
            bump = jnp.max(logits, -1, keepdims=True) + 1.0
            alt = (top + 1) % logits.shape[-1]
            return jnp.put_along_axis(logits, alt, bump, -1,
                                      inplace=False), new
        return logits, new


def _run(ctx, fault=None):
    if fault:
        serve_model = ctx.family.serve_model

        def planted(cfg, seed, max_len):
            model, plain = serve_model(cfg, seed, max_len)
            return Faulty(model, fault), plain

        ctx.family.serve_model = planted
    return ctx.job.run(ctx)


@pytest.mark.parametrize("seed", [1, 4, 2**33 + 5])
def test_sound_run_is_correct_and_control_fails(make_tiny_ctx, seed):
    result, checks = _run(make_tiny_ctx(seed=seed))
    assert result["correct"], checks
    assert result["failed"] == 0 and result["attempted"] > 0
    sound = checks["max_logit_gap"]["value"]
    assert sound < checks["max_logit_gap"]["limit"]
    result, checks = _run(make_tiny_ctx(seed=seed, control=True))
    assert not result["correct"], checks
    assert checks["max_logit_gap"]["value"] > checks["max_logit_gap"]["limit"]
    assert result["failed"] > 0
    # only the control failed: the program's own gap, kept in the notes, holds
    assert result["notes"]["program_max_logit_gap"] < \
        checks["max_logit_gap"]["limit"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "token_altered"])
def test_planted_fault_is_not_correct(make_tiny_ctx, fault):
    result, checks = _run(make_tiny_ctx(), fault)
    assert not result["correct"], (fault, checks)
    assert checks["max_logit_gap"]["value"] > checks["max_logit_gap"]["limit"]
