"""The seeded generator: everything a run is fed comes from ``--seed``.

One general generator reads a traffic file's parameters; a new mix is a
new data file.  The program sees only what is generated: token batches
for training, (due time, prompt ids, output budget) for serving.

Serving lengths are drawn so that every seed does the same work.  A
length distribution is a clipped lognormal cut into ``levels`` quantile
levels (its distinct lengths: also the shapes set-up has to warm).  The
schedule is built in blocks of ``block`` requests; every block holds each
stratum of prompt length and of output length equally often, so any
prefix longer than a block or two holds nearly the same mix.  The order
inside the blocks and the pairing of prompts with outputs come from the
mix's own ``order_seed``: the sequence of sizes is part of the mix, a
trace that every run replays, arrival gaps included.  ``--seed`` gives
the token ids and (in the drivers) the weights.  The sizes' order is not
the seed's because order alone moves a 51 s window's rate: re-ordering the
same 800 requests spread the backlog cell's tokens per second by 0.7 to
1.2% between the quartiles (a slot simulation, and the ledger's PR 22),
more than half of any bound worth having.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, *stream) -> np.random.Generator:
    """Independent streams of one seed (seeds may pass 2**31)."""
    return np.random.default_rng([int(seed), *(int(s) for s in stream)])


class TokenBatches:
    """Training batches: ``batch(i)`` is step i's int32 [rows, width] token
    array, all rows different, the same for the same seed and step."""

    def __init__(self, seed: int, rows: int, width: int, vocab: int) -> None:
        self.seed, self.rows, self.width, self.vocab = seed, rows, width, vocab

    def batch(self, i: int) -> np.ndarray:
        return rng_for(self.seed, 1, i).integers(
            0, self.vocab, size=(self.rows, self.width), dtype=np.int32)


def levels(spec: dict) -> list[int]:
    """The distinct lengths of a clipped lognormal: the midpoints of
    ``levels`` equal-probability slices, clipped to [lo, hi]."""
    n = int(spec["levels"])
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    nd = NormalDist()
    out = []
    for j in range(n):
        x = math.exp(mu + sigma * nd.inv_cdf((j + 0.5) / n))
        out.append(int(min(max(round(x), spec["lo"]), spec["hi"])))
    return out


def _strata(rng: np.random.Generator, lens: list[int], block: int,
            n_blocks: int) -> np.ndarray:
    """[n_blocks, block] lengths: each block takes one level from each of
    ``block`` strata of the sorted levels, in a seeded order."""
    per = len(lens) // block
    assert per * block == len(lens), "levels must be a multiple of block"
    table = np.sort(np.asarray(lens)).reshape(block, per)
    picks = rng.integers(0, per, size=(n_blocks, block))
    out = table[np.arange(block)[None, :], picks]
    return rng.permuted(out, axis=1)


def schedule(traffic: dict, seed: int, vocab: int, horizon_s: float) -> list:
    """Requests as dicts ``{due_s, prompt, max_tokens}`` in due order.

    ``arrivals.kind``: ``backlog`` (all due at 0; ``requests`` of them) or
    ``poisson`` (``rate_per_s``, from ``-ramp_s`` until ``horizon_s``; a
    driver that needs arrivals beyond the horizon asks for a longer one,
    which extends the same sequence)."""
    arr = traffic["arrivals"]
    block = int(traffic["block"])
    if arr["kind"] == "backlog":
        n = int(arr["requests"])
        due = np.zeros(n)
    elif arr["kind"] == "poisson":
        rate, ramp = float(arr["rate_per_s"]), float(arr.get("ramp_s", 0))
        n = int((horizon_s + ramp) * rate * 1.5) + 4 * block
        gaps = rng_for(traffic["order_seed"], 2).exponential(
            1.0 / rate, size=n)
        due = np.cumsum(gaps) - ramp
        n = int(np.searchsorted(due, horizon_s))
        due = due[:n]
    else:
        raise SystemExit(f"chipbench: unknown arrivals {arr['kind']!r}")
    n_blocks = -(-n // block)
    order = traffic["order_seed"]
    prompts = _strata(rng_for(order, 3), levels(traffic["prompt"]), block,
                      n_blocks).reshape(-1)[:n]
    outputs = _strata(rng_for(order, 4), levels(traffic["output"]), block,
                      n_blocks).reshape(-1)[:n]
    ids = rng_for(seed, 5)
    return [{"due_s": float(d), "max_tokens": int(o),
             "prompt": ids.integers(0, vocab, size=int(p), dtype=np.int32)}
            for d, p, o in zip(due, prompts, outputs)]


def warm_prompt_lengths(traffic: dict) -> list[int]:
    """Every distinct prompt length the mix can send."""
    return sorted(set(levels(traffic["prompt"])))
