"""Paged decode attention: gather non-contiguous KV pages via
scalar-prefetched page tables.

The page table IS the paper's programmable LD stage: instead of a
fixed-function contiguous DMA, each (batch, page-slot) grid step
computes its own source address from the prefetched table
(``tbl[b, i]``) and stages exactly one resident page, all its KV heads,
into VMEM.  CAL is the usual online-softmax pair of MACs, run for every
query head at once against the page's token-major (ps * KVH, Dh) rows,
with each query head masked to the rows of its own kv head; FLOW
carries (m, l, acc) across the page sweep in VMEM scratch; ST writes
the normalized output once — output-stationary, like All-Reuse.

A page block spans the array's full (KVH, Dh) trailing pair because the
TPU lowering tiles the last two dims of every block by (8, 128) unless
they equal the array's own: a one-kv-head block is refused on the chip.
The masked GQA columns cost KVH x the score MACs, which decode (bound by
the page reads) does not notice.

Pages beyond a sequence's length are skipped entirely (``pl.when``),
the paged analogue of Sparse PC Inc: work that is not addressed is
never issued.

Scalar-prefetch layout invariants (the contract with
serve/kv_cache.py — also see docs/ARCHITECTURE.md):

* ``page_tables`` and ``lengths`` ride in SMEM via
  ``PrefetchScalarGridSpec(num_scalar_prefetch=2)``: they are read at
  *grid-index-map time* to compute each step's page address, so they
  must be int32 and host-final before the call — the kernel never
  validates them.
* Every table entry must name a real page or the null page 0; the
  index map DMAs whatever page it is told.  Slots past a sequence's
  last page may contain anything (the ``i * ps < length`` guard skips
  them), but must still be in-range.
* ``lengths[b]`` counts *attendable* tokens including the one just
  written.  Tokens past ``length`` inside the final page are masked to
  -1e30 before the running max, so stale lanes contribute exact zeros
  — the same invariant the jnp reference (ref.py) and the engine's
  token-parity guarantee rely on.
* (m, l, acc) scratch lives in VMEM across the page sweep
  (output-stationary, All-Reuse in the paper's terms); the output is
  written once on the last grid step.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            m_ref, l_ref, acc_ref, *, ps: int, kvh: int, n_slots: int,
            scale: float):
    b = pl.program_id(0)
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    @pl.when(i * ps < length)
    def _page():
        q = q_ref[0].astype(jnp.float32) * scale             # (H, Dh)
        H, Dh = q.shape
        # the page's (ps, KVH, Dh) block flattened token-major: row j
        # is token j // kvh of kv head j % kvh
        k = k_ref[0].reshape(ps * kvh, Dh).astype(jnp.float32)
        v = v_ref[0].reshape(ps * kvh, Dh).astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        # query head h reads kv head h // G only, and tokens < length
        own = (col % kvh) == row // (H // kvh)
        s = jnp.where(own & (i * ps + col // kvh < length), s, NEG_INF)

        m_prev = m_ref[...]                                  # (H, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(i == n_slots - 1)
    def _flush():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention_kernel(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, page_tables: jax.Array,
                           lengths: jax.Array, *,
                           interpret: bool = False) -> jax.Array:
    """q: (B, H, Dh); k/v_pages: (P, ps, KVH, Dh);
    page_tables: (B, n_slots) int32; lengths: (B,) int32.
    Returns (B, H, Dh)."""
    B, H, Dh = q.shape
    _, ps, KVH, _ = k_pages.shape
    n_slots = page_tables.shape[1]
    scale = 1.0 / math.sqrt(Dh)
    # every block spans its array's full trailing dims: (H, Dh) for q
    # and the output, (KVH, Dh) for a page, as the TPU tiling requires
    page_spec = pl.BlockSpec((1, ps, KVH, Dh),
                             lambda b, i, tbl, ln: (tbl[b, i], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_slots),
        in_specs=[
            pl.BlockSpec((1, H, Dh), lambda b, i, tbl, ln: (b, 0, 0)),
            page_spec,
            page_spec,
        ],
        out_specs=pl.BlockSpec((1, H, Dh),
                               lambda b, i, tbl, ln: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((H, 1), jnp.float32),      # running max
            pltpu.VMEM((H, 1), jnp.float32),      # running denom
            pltpu.VMEM((H, Dh), jnp.float32),     # accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, ps=ps, kvh=KVH, n_slots=n_slots,
                          scale=scale),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Dh), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(page_tables, lengths, q, k_pages, v_pages)
