"""jit'd entry point of the paged decode attention kernel."""
from __future__ import annotations

import functools

import jax

from .kernel import paged_attention_kernel
from .ref import paged_attention_ref  # noqa: F401

__all__ = ["paged_attention"]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, page_tables, lengths, *,
                    interpret: bool = False):
    """Single-token decode attention over paged KV.

    q: (B, H, Dh); k/v_pages: (P, ps, KVH, Dh); page_tables: (B, n)
    int32 page ids; lengths: (B,) attendable tokens.  Returns
    (B, H, Dh).  Each page is staged once, all its kv heads together,
    and reused by every query head of the GQA group.
    """
    return paged_attention_kernel(q, k_pages, v_pages, page_tables,
                                  lengths, interpret=interpret)
