"""Operations and bytes one serve dispatch needs, worked out from shapes.

The yardstick for ``step_roofline`` and ``step.mfu``: it counts what
the work needs, not what the program moves, so a kernel that skips
dead pages, padded prefill rows or inactive batch rows reads as a
higher share.  Per dispatch:

* weights are read once (every parameter, the tied table included);
* a decode row at context ``n`` reads its ``n`` live tokens of K and V
  and writes one, and its query attends ``n + 1`` keys;
* a prefill row of ``v`` valid tokens from ``start`` reads ``start``
  tokens of K and V, writes ``v``, and token ``t`` attends
  ``start + t + 1`` keys;
* each row that yields logits pays one unembedding.

Operations count a multiply-add as two.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterable, Sequence

import jax

from .weights import Dims, shapes


@dataclasses.dataclass
class Work:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, o: "Work") -> "Work":
        return Work(self.flops + o.flops, self.bytes + o.bytes)


def weight_bytes(k: Dims, dtype_bytes: int = 2) -> int:
    """Every parameter once: leaves of two or more dims in the serving
    dtype, 1-D leaves in float32."""
    leaves = jax.tree.leaves(shapes(k), is_leaf=lambda x: isinstance(x, tuple))
    return sum(math.prod(s) * (dtype_bytes if len(s) >= 2 else 4)
               for s in leaves)


def linear_flops(k: Dims) -> int:
    """Projections and MLP of one token through every layer."""
    per_layer = (k.d * k.H * k.Dh + 2 * k.d * k.KVH * k.Dh
                 + k.H * k.Dh * k.d + 3 * k.d * k.F)
    return 2 * k.L * per_layer


def attention_flops(k: Dims, keys: int) -> int:
    """Scores and weighted values of one query over ``keys`` keys."""
    return 4 * k.L * k.H * k.Dh * keys


def unembed_flops(k: Dims) -> int:
    return 2 * k.d * k.V


def decode_rows(k: Dims, contexts: Iterable[int], T: int = 1) -> Work:
    """Rows decoding ``T`` tokens each after ``contexts`` live tokens."""
    w = Work()
    kvb = k.kv_bytes_per_token
    for n in contexts:
        n = int(n)
        keys = T * (n + 1) + T * (T - 1) // 2
        w += Work(T * (linear_flops(k) + unembed_flops(k))
                  + attention_flops(k, keys), (n + T) * kvb)
    return w


def prefill_rows(k: Dims, starts: Sequence[int], valid: Sequence[int]) -> Work:
    """Chunk rows of ``valid`` tokens from ``starts`` (rows with
    ``valid == 0`` are padding and cost nothing)."""
    w = Work()
    kvb = k.kv_bytes_per_token
    for s, v in zip(starts, valid):
        s, v = int(s), int(v)
        if v <= 0:
            continue
        keys = v * s + v * (v + 1) // 2
        w += Work(v * linear_flops(k) + attention_flops(k, keys)
                  + unembed_flops(k), (s + v) * kvb)
    return w


def least_seconds(w: Work, weights_bytes: int, peak: Dict) -> float:
    """The larger of the compute bound and the memory bound."""
    return max(w.flops / peak["bf16_flops"],
               (w.bytes + weights_bytes) / peak["hbm_bytes_s"])
