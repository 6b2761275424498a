"""Traffic from the seed: training batches and open-loop arrivals.

One generator per kind, driven by the parameters in a traffic mix file
(``bench/mixes/``), so a new traffic mix is a new data file.  The seed
picks token ids; the sizes and times of the work are the same for every
seed, so runs differ in content, not in load.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np

from harness import seed_u64

def _mix(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer (the hash of ``repro.data.SyntheticTokens``)."""
    x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def hashed_tokens(seed: int, salt: int, start: int, n: int,
                  vocab: int) -> np.ndarray:
    """``n`` token ids at hash positions ``start..start+n-1``."""
    base = np.uint64(seed_u64(seed, salt))
    idx = np.arange(start, start + n, dtype=np.uint64)
    with np.errstate(over="ignore"):
        return (_mix(idx ^ base) % np.uint64(vocab)).astype(np.int32)


class TrainBatches:
    """``global_batch_at_step(step)`` -> ``[batch, seq]`` int32 rows that
    differ in every step, O(1) random access (the interface the program's
    ``Trainer`` reads).  ``span`` wraps each build in a host span."""

    def __init__(self, *, vocab: int, seq: int, batch: int, seed: int,
                 span=None):
        self.vocab, self.seq, self.batch, self.seed = vocab, seq, batch, seed
        self.span = span

    def global_batch_at_step(self, step: int) -> np.ndarray:
        if self.span is None:
            return self._build(step)
        with self.span("batch_build"):
            return self._build(step)

    def _build(self, step: int) -> np.ndarray:
        n = self.batch * self.seq
        toks = hashed_tokens(self.seed, 1, step * n, n, self.vocab)
        return toks.reshape(self.batch, self.seq)


@dataclasses.dataclass
class Request:
    uid: int
    due: float            # seconds after the start of the arrival clock
    prompt: np.ndarray    # [P] int32
    max_new: int
    segment: int = 0      # index into the ``segments`` it was made for


def _quantiles_exp(n: int, rate: float) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def _quantiles_lognormal(n: int, median: float, sigma: float) -> np.ndarray:
    nd = NormalDist()
    return np.array([median * math.exp(sigma * nd.inv_cdf((i + 0.5) / n))
                     for i in range(n)])


def arrivals(traffic: dict, seed: int, segments, vocab: int):
    """Open-loop Poisson arrivals over consecutive segments of
    ``segments`` seconds (warm-up, window, drain).

    ``traffic``: ``rate_per_s``; ``prompt_lengths`` and ``prompt_weights``
    (lengths from a fixed set); ``output_median``, ``output_sigma``,
    ``output_min``, ``output_max`` (clipped lognormal); ``schedule_seed``.
    Each segment holds ``round(rate * seconds)`` requests whose gaps,
    prompt lengths and output lengths are the quantiles of their
    distributions (exact proportions), put in an order drawn from
    ``schedule_seed``: every run offers the same requests at the same
    times, and ``seed`` picks the prompts' token ids.  (A window holds a
    few dozen requests of heavy-tailed lengths, so an order drawn from
    ``seed`` would change the work in it by a third.)"""
    rate = float(traffic["rate_per_s"])
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    lens = np.asarray(traffic["prompt_lengths"], np.int64)
    w = np.asarray(traffic["prompt_weights"], np.float64)
    reqs, start, pos = [], 0.0, 0
    for k, seconds in enumerate(segments):
        n = int(round(rate * seconds))
        if n:
            gaps = _quantiles_exp(n, rate)
            gaps = rng.permutation(gaps * (seconds / gaps.sum()))
            counts = np.floor(w / w.sum() * n).astype(np.int64)
            rest = w / w.sum() * n - counts
            counts[np.argsort(-rest)[: n - counts.sum()]] += 1
            plen = rng.permutation(np.repeat(lens, counts))
            out = rng.permutation(np.clip(np.rint(_quantiles_lognormal(
                n, traffic["output_median"], traffic["output_sigma"])),
                traffic["output_min"], traffic["output_max"]).astype(np.int64))
            due = start + np.cumsum(gaps) - gaps[0]
            for i in range(n):
                p = int(plen[i])
                reqs.append(Request(len(reqs), float(due[i]),
                                    hashed_tokens(seed, 3, pos, p, vocab),
                                    int(out[i]), k))
                pos += p
        start += seconds
    return reqs
