"""Oracle: decode attention over paged KV via explicit gather.

Op-for-op the same math as ``components.decode_attention`` (bf16
operands, f32 MXU accumulation, -1e30 masking) so the continuous-
batching decode path stays token-exact against the contiguous-cache
greedy oracle: gathered padding positions contribute exact zeros.

Named scopes split the device time of the paged step programs
(models/lm.py): the page gather is ``attn.gather``, scores, softmax and
values ``attn.core``.
"""
import math

import jax
import jax.numpy as jnp


def gather_pages(pages, page_tables, layer=None):
    """(P, ps, KVH, Dh) + (B, n) -> (B, n * ps, KVH, Dh).

    With ``layer`` (a traced int), ``pages`` is the stacked pool
    (L, P, ps, KVH, Dh) and the pages of that layer are read straight
    from it: one gather from the pool viewed as (L * P, ps, KVH, Dh) at
    ``layer * P + page_tables``, so no layer's pool is ever sliced out
    of the stack."""
    B, n = page_tables.shape
    with jax.named_scope("attn.gather"):
        if layer is not None:
            L, P = pages.shape[:2]
            pages = pages.reshape(L * P, *pages.shape[2:])
            page_tables = layer * P + page_tables
        g = pages[page_tables]                   # (B, n, ps, KVH, Dh)
        return g.reshape(B, n * pages.shape[1], *pages.shape[2:])


def paged_attention_ref(q, k_pages, v_pages, page_tables, lengths):
    """q: (B, H, Dh); k/v_pages: (P, ps, KVH, Dh); page_tables:
    (B, n) int32; lengths: (B,) attendable tokens per sequence
    (including the one just written).  Returns (B, H, Dh)."""
    return decode_attention_core(q, gather_pages(k_pages, page_tables),
                                 gather_pages(v_pages, page_tables),
                                 lengths)


def decode_attention_core(q, k, v, lengths):
    """``paged_attention_ref`` past the gather: q (B, H, Dh) against
    the gathered buffers k/v (B, S, KVH, Dh), positions j < lengths[b]
    attendable.  Returns (B, H, Dh)."""
    B, H, Dh = q.shape
    KVH = k.shape[2]
    G = H // KVH
    with jax.named_scope("attn.core"):
        qh = q.reshape(B, KVH, G, Dh)
        s = jnp.einsum("bkgd,bjkd->bkgj", qh, k,
                       preferred_element_type=jnp.float32)
        s = s * (1.0 / math.sqrt(Dh))
        idx = jnp.arange(k.shape[1])
        valid = idx[None, :] < lengths[:, None]  # (B, S)
        s = jnp.where(valid[:, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bkgj,bjkd->bkgd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, H, Dh).astype(q.dtype)


def paged_verify_attention_ref(q, k_pages, v_pages, page_tables, lengths):
    """Multi-token verification attention over paged KV (speculative
    decode).  Op-for-op ``paged_attention_ref`` with a query-time axis:
    query t of sequence b sits at absolute position ``lengths[b] + t``
    and attends to gathered positions j < lengths[b] + t + 1 — i.e.
    everything already resident plus the speculated tokens written at or
    before its own position.  Per (b, t) the score vector, the softmax
    reductions, and the value contraction run over the same gathered
    buffer length as the single-token path, so the t-th verify query is
    bit-identical to the decode step the target model would have run at
    that position (the engine's token-exactness rests on this — see
    docs/speculative.md).

    q: (B, T, H, Dh); k/v_pages: (P, ps, KVH, Dh); page_tables: (B, n)
    int32; lengths: (B,) tokens resident *before* this verify call's T
    writes.  Returns (B, T, H, Dh)."""
    return verify_attention_core(q, gather_pages(k_pages, page_tables),
                                 gather_pages(v_pages, page_tables),
                                 lengths)


def verify_attention_core(q, k, v, lengths):
    """``paged_verify_attention_ref`` past the gather: q (B, T, H, Dh)
    against the gathered buffers k/v (B, S, KVH, Dh).  Returns
    (B, T, H, Dh)."""
    B, T, H, Dh = q.shape
    KVH = k.shape[2]
    G = H // KVH
    with jax.named_scope("attn.core"):
        qh = q.reshape(B, T, KVH, G, Dh)
        s = jnp.einsum("btkgd,bjkd->btkgj", qh, k,
                       preferred_element_type=jnp.float32)
        s = s * (1.0 / math.sqrt(Dh))
        idx = jnp.arange(k.shape[1])
        q_pos = lengths[:, None] + jnp.arange(T)[None, :]    # (B, T)
        valid = idx[None, None, :] < (q_pos + 1)[:, :, None]  # (B, T, S)
        s = jnp.where(valid[:, :, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("btkgj,bjkd->btkgd", p.astype(v.dtype), v,
                         preferred_element_type=jnp.float32)
        return out.reshape(B, T, H, Dh).astype(q.dtype)
