"""The one traffic generator: a mix's JSON parameters in, requests out.

Every seed draws the same multiset of sizes and gaps, in another order:
lengths and gaps are the quantiles of their distribution on a grid of
``(i + 0.5) / n`` (stratified sampling), and the seed permutes them and
fills the prompts with token ids.  So two seeds put the same work in a
window, and a spread between seeds is the system's, not the draw's.

Requests are independent: open-loop Poisson arrivals at the cell's
``rate_rps``, or, with the cell's ``mode: offline``, a stream the
harness takes from as fast as the queue drains.  An open mix can also
draw a ramp: ``warm`` seconds of the same load, due before the window
opens, which bring the engine to the occupancy the load holds.

Lengths are ``{"dist": "lognormal", "median", "sigma", "min", "max"}``.
"""
from __future__ import annotations

import dataclasses
import heapq
import statistics
from typing import Dict, Iterator, List, Optional

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class ReqSpec:
    rid: int
    due: Optional[float]          # seconds after the window opens; None:
    prompt: np.ndarray            # offline (due when the harness takes it)
    max_new: int


def _grid(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: Dict, n: int) -> np.ndarray:
    """``n`` lengths on the quantile grid of ``dist``, ascending."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([_NORMAL.inv_cdf(u) for u in _grid(n)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def gaps(rate: float, n: int, span: float) -> np.ndarray:
    """``n`` Poisson inter-arrival gaps on the quantile grid at
    ``rate``, scaled to sum to ``span`` exactly."""
    g = -np.log1p(-_grid(n)) / rate
    return g * (span / g.sum())


class Traffic:
    """Requests of one mix for one seed, in due order.

    ``block`` requests are stratified together; an open mix makes one
    block per window of ``seconds`` at ``rate``, so the window holds
    exactly ``round(rate * seconds)`` arrivals, and one block of
    ``round(rate * warm)`` over the ``warm`` seconds before it."""

    def __init__(self, params: Dict, *, seed: int, vocab: int,
                 seconds: float, rate: float = 0.0, warm: float = 0.0,
                 block: int = 0):
        process = params.get("arrivals", {}).get("process", "poisson")
        if process != "poisson":
            raise ValueError(f"unknown arrival process {process!r}")
        self.p = params
        self.seed = int(seed)
        self.vocab = int(vocab)
        self.seconds = float(seconds)
        self.rate = float(rate)
        self.warm = float(warm)
        if rate > 0:
            self.block = max(1, int(round(rate * seconds)))
        else:
            self.block = int(block or params.get("block", 512))
        self._rid = 0

    def rng(self, *stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed % (1 << 63), *stream])

    def _block(self, rng, n: int, start: float,
               span: float) -> List[ReqSpec]:
        """``n`` requests due from ``start`` over ``span`` seconds (or
        offline, with ``span`` 0)."""
        p_len = rng.permutation(lengths(self.p["prompt"], n))
        o_len = rng.permutation(lengths(self.p["output"], n))
        if span > 0:
            g = rng.permutation(gaps(self.rate, n, span))
            due = start + np.concatenate([[0.0], np.cumsum(g)[:-1]])
        out = []
        for i in range(n):
            rid, self._rid = self._rid, self._rid + 1
            prompt = self.rng(2, rid).integers(0, self.vocab,
                                               size=int(p_len[i]),
                                               dtype=np.int32)
            out.append(ReqSpec(rid, float(due[i]) if span > 0 else None,
                               prompt, int(o_len[i])))
        return out

    def __iter__(self) -> Iterator[ReqSpec]:
        if self.rate <= 0:
            k = 0
            while True:
                yield from self._block(self.rng(1, k), self.block, 0.0, 0.0)
                k += 1
        heap: list = []
        n_warm = int(round(self.rate * self.warm))
        if n_warm:
            for r in self._block(self.rng(3), n_warm, -self.warm, self.warm):
                heapq.heappush(heap, (r.due, r.rid, r))
        k = 0
        while True:
            # block k's requests are all due at or after k * seconds
            while not heap or heap[0][0] >= k * self.seconds:
                for r in self._block(self.rng(1, k), self.block,
                                     k * self.seconds, self.seconds):
                    heapq.heappush(heap, (r.due, r.rid, r))
                k += 1
            yield heapq.heappop(heap)[2]


def describe(specs: List[ReqSpec]) -> Dict[str, List[int]]:
    """Quartiles and extremes of the drawn prompt and output lengths."""
    out = {}
    for key, vals in (("prompt", [len(s.prompt) for s in specs]),
                      ("output", [s.max_new for s in specs])):
        if vals:
            q = np.percentile(vals, [0, 25, 50, 75, 100])
            out[key] = [int(round(v)) for v in q]
    return out


def prompt_bound(params: Dict) -> int:
    """Longest prompt one request of the mix can have."""
    return int(params["prompt"]["max"])


def tokens_bound(params: Dict) -> int:
    """Longest prompt + output one request of the mix can have."""
    return int(params["prompt"]["max"]) + int(params["output"]["max"])
