"""Seeded traffic for the benchmark's cells.

One general generator reads a mix's parameters from ``bench/traffic/<mix>.json``.
A serving mix is an offline backlog: streams of requests, every request of a
stream queued when the stream starts. Its prompt and output lengths are the
quantiles of two clipped lognormals, paired the same way for every seed.
Each stream queues them in a random order, as users send them, not sorted
by length: admission order is the engine's policy to choose. The order is
drawn from the stream's index and is the same for every seed, because the
order sets how many decode steps a stream takes; the seed draws the prompt
tokens. So every seed serves the same work.

A mix states its kind, each length's distribution and its sampling; a value
that this generator does not implement is refused (``check_mix``), so that a
mix file never declares traffic other than what runs.

The prompt source is a copy of the program's Markov-Zipf token stream
(``repro.data.synthetic.synthetic_tokens``): a fixed sparse transition table
(the "corpus") and a Zipf choice among each token's successors.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Dict, List

import numpy as np

from harness import Refused

CORPUS_SEED = 0
ORDER_SEED = 0
BRANCH = 8
RESTART_P = 0.02


def seed_words(seed: int, *salt: int) -> np.random.SeedSequence:
    """A seed sequence from a run's ``--seed`` (any size) and salts."""
    return np.random.SeedSequence([int(seed) & (2**64 - 1), *salt])


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles (i + 0.5) / n of a lognormal,
    rounded and clipped to [lo, hi]."""
    nd = NormalDist()
    z = np.array([nd.inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.rint(np.exp(math.log(median) + sigma * z)).astype(np.int64)
    return np.clip(x, lo, hi)


def markov_table(vocab: int) -> np.ndarray:
    return np.random.default_rng(CORPUS_SEED).integers(0, vocab,
                                                       size=(vocab, BRANCH))


def markov_zipf(vocab: int, lengths: np.ndarray, rng: np.random.Generator,
                table: np.ndarray = None) -> List[np.ndarray]:
    """One Markov-Zipf token sequence per entry of ``lengths``."""
    table = markov_table(vocab) if table is None else table
    p = 1.0 / np.arange(1, BRANCH + 1)
    p /= p.sum()
    n, s = len(lengths), int(max(lengths))
    out = np.empty((n, s), np.int64)
    cur = rng.integers(0, vocab, size=n)
    for t in range(s):
        out[:, t] = cur
        cur = table[cur, rng.choice(BRANCH, size=n, p=p)]
        restart = rng.random(n) < RESTART_P
        cur[restart] = rng.integers(0, vocab, size=int(restart.sum()))
    return [out[i, :int(l)] for i, l in enumerate(lengths)]


@dataclass
class Req:
    rid: int
    tokens: np.ndarray
    steps: int          # output tokens, the first one from the prefill

    @property
    def prompt_len(self) -> int:
        return int(self.tokens.shape[0])


IMPLEMENTED = {"kind": ("offline_backlog",), "dist": ("lognormal",),
               "sampling": ("greedy",)}


def check_mix(mix: Dict) -> None:
    """Refuses a mix whose kind, length distributions or sampling this
    generator and the serving job do not implement, or leave unstated."""
    stated = {"kind": [mix.get("kind")], "sampling": [mix.get("sampling")],
              "dist": [mix["prompt"].get("dist"), mix["output"].get("dist")]}
    for key, values in stated.items():
        for v in values:
            if v not in IMPLEMENTED[key]:
                raise Refused(f"traffic {key} {v!r} is not implemented "
                              f"(implemented: {', '.join(IMPLEMENTED[key])})")


def length_pairs(mix: Dict) -> np.ndarray:
    """The mix's fixed (prompt, output) pairs, shape (n, 2).

    Both marginals are quantile grids; they are paired by one fixed
    permutation, the same for every seed."""
    check_mix(mix)
    n = int(mix["requests_per_stream"])
    p, o = mix["prompt"], mix["output"]
    prompts = lognormal_quantiles(n, p["median"], p["sigma"], p["min"],
                                  p["max"])
    outputs = lognormal_quantiles(n, o["median"], o["sigma"], o["min"],
                                  o["max"])
    perm = np.random.default_rng(CORPUS_SEED).permutation(n)
    pairs = np.stack([prompts, outputs[perm]], axis=1)
    if (pairs.sum(1) > int(mix["max_len"])).any():
        raise ValueError("a (prompt, output) pair exceeds the mix's max_len")
    return pairs


def stream(mix: Dict, vocab: int, seed: int, index: int,
           table: np.ndarray = None) -> List[Req]:
    """Stream ``index`` of a run: the mix's pairs in a random order drawn
    from ``index`` alone, with Markov-Zipf prompts drawn from ``seed``. The
    engine admits them in this order. Request ids are unique within the
    run."""
    pairs = length_pairs(mix)
    order = np.random.default_rng(seed_words(ORDER_SEED, 2, index))
    pairs = pairs[order.permutation(len(pairs))]
    rng = np.random.default_rng(seed_words(seed, 1, index))
    prompts = markov_zipf(vocab, pairs[:, 0], rng, table)
    base = index * len(pairs)
    return [Req(base + i, tok, int(steps))
            for i, (tok, steps) in enumerate(zip(prompts, pairs[:, 1]))]


def prompt_lengths(mix: Dict) -> List[int]:
    """Every distinct prompt length of the mix: warming each of them covers
    whatever shapes the engine derives from a prompt's length."""
    return sorted({int(s) for s in length_pairs(mix)[:, 0]})
