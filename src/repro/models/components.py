"""Shared neural-net primitives for the model zoo (pure JAX).

Everything here is a *function of (params, inputs, cfg)* — no classes
hold state.  Attention is implemented flash-style (online-softmax over
KV chunks) so training memory is O(S * chunk), which is what lets the
32k-prefill and 4k-train shapes fit the dry-run HBM budget.  The same
decomposition is what the RISC-NN paper calls decoupled LD/CAL staging:
each KV chunk is one "ExeBlock" whose operands are staged (VMEM / here
registers of the scan carry) before the MAC burst.
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ..sharding import constrain
from .base import ParamSpec, normal, zeros, ones, const

# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x, scale, bias, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(dt)


def norm_specs(d: int, kind: str) -> dict:
    if kind == "rmsnorm":
        return {"scale": ParamSpec((d,), ("stats",), init=zeros)}
    return {"scale": ParamSpec((d,), ("stats",), init=ones),
            "bias": ParamSpec((d,), ("stats",), init=zeros)}


def apply_norm(p: dict, x, kind: str, eps: float):
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"], eps)
    return layer_norm(x, p["scale"], p.get("bias"), eps)


def group_norm_heads(x, scale, bias, eps):
    """GroupNorm with one group per head; x: (B, S, H, Dh)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    y = y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
    return y.astype(dt)


# ---------------------------------------------------------------------------
# rotary embeddings (standard / partial / M-RoPE)
# ---------------------------------------------------------------------------


def rope_freqs(rot_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, rot_dim, 2, dtype=jnp.float32)
                            / rot_dim))


def apply_rope(x, positions, *, theta: float = 1e4, fraction: float = 1.0):
    """x: (B, S, H, Dh); positions: (B, S) int32.  ``fraction`` < 1 rotates
    only the leading slice of Dh (StableLM-style partial rotary)."""
    dh = x.shape[-1]
    rot = int(dh * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    xr, xp = x[..., :rot], x[..., rot:]
    freqs = rope_freqs(rot, theta)                        # (rot/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B,S,rot/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(xr.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([out.astype(x.dtype), xp], -1)


def apply_mrope(x, positions, *, theta: float, sections: tuple):
    """Multimodal RoPE (Qwen2-VL §3): positions (B, 3, S) carry the
    (temporal, height, width) ids; the Dh/2 frequency slots are split into
    ``sections`` (e.g. 16/24/24), each rotated by its own position id."""
    dh = x.shape[-1]
    half = dh // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(dh, theta)                        # (half,)
    # pick the section-owner position per frequency slot
    owner = jnp.repeat(jnp.arange(3), jnp.array(sections),
                       total_repeat_length=half)          # (half,)
    pos = positions.astype(jnp.float32)                   # (B,3,S)
    ang = jnp.take(pos, owner, axis=1)                    # (B,half,S)
    ang = jnp.moveaxis(ang, 1, -1) * freqs                # (B,S,half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def sinusoid_pos_at(pos: jax.Array, d: int) -> jax.Array:
    """Sinusoidal embedding at dynamic positions; pos: (B,) -> (B, d)."""
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32)
                  * (-math.log(10000.0) / d))
    ang = pos.astype(jnp.float32)[:, None] * div
    pe = jnp.zeros((pos.shape[0], d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(ang))
    pe = pe.at[:, 1::2].set(jnp.cos(ang))
    return pe


def sinusoid_pos(seq: int, d: int, offset: int = 0) -> jax.Array:
    pos = jnp.arange(offset, offset + seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32)
                  * (-math.log(10000.0) / d))
    pe = jnp.zeros((seq, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attn_specs(cfg) -> dict:
    d, hd = cfg.d_model, cfg.head_dim
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    p = {
        "wq": ParamSpec((d, h * hd), ("embed", "q_heads")),
        "wk": ParamSpec((d, kvh * hd), ("embed", "kv_heads")),
        "wv": ParamSpec((d, kvh * hd), ("embed", "kv_heads")),
        "wo": ParamSpec((h * hd, d), ("q_heads", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = ParamSpec((h * hd,), ("stats",), init=zeros)
        p["bk"] = ParamSpec((kvh * hd,), ("stats",), init=zeros)
        p["bv"] = ParamSpec((kvh * hd,), ("stats",), init=zeros)
    if cfg.qk_norm:
        p["q_norm"] = ParamSpec((hd,), ("stats",), init=zeros)
        p["k_norm"] = ParamSpec((hd,), ("stats",), init=zeros)
    return p


def _project_qkv(p, x, cfg, positions, mrope_positions=None):
    """x: (B,S,D) -> q (B,S,H,Dh), k/v (B,S,KVH,Dh), RoPE applied."""
    B, S, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = x @ p["wq"].astype(x.dtype)
    k = x @ p["wk"].astype(x.dtype)
    v = x @ p["wv"].astype(x.dtype)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(x.dtype)
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    q = q.reshape(B, S, h, hd)
    k = k.reshape(B, S, kvh, hd)
    v = v.reshape(B, S, kvh, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if cfg.rope_kind == "mrope":
        q = apply_mrope(q, mrope_positions, theta=cfg.rope_theta,
                        sections=cfg.mrope_sections)
        k = apply_mrope(k, mrope_positions, theta=cfg.rope_theta,
                        sections=cfg.mrope_sections)
    elif cfg.rope_kind == "rope":
        q = apply_rope(q, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
        k = apply_rope(k, positions, theta=cfg.rope_theta,
                       fraction=cfg.rope_fraction)
    # rope_kind == "none": positions handled by additive embeddings.
    # seq deliberately unnamed: under SP rules the residual stream is
    # seq-sharded but attention internals run gathered-seq/sharded-heads
    # (Megatron-SP boundary).
    q = constrain(q, ("batch", None, "act_heads", None))
    k = constrain(k, ("batch", None, "act_heads", None))
    v = constrain(v, ("batch", None, "act_heads", None))
    return q, k, v


def flash_attention(q, k, v, *, causal: bool, window: Optional[int] = None,
                    kv_chunk: int = 1024, q_offset=0):
    """Online-softmax attention over KV chunks (pure jnp).

    q: (B,Sq,H,Dh); k/v: (B,Skv,KVH,Dh) with H = KVH * G (GQA grouping is
    kept factored — KV is never materialized per Q head).  Memory is
    O(Sq * kv_chunk) per head instead of O(Sq * Skv).

    ``q_offset`` is the absolute position of q[0] (decode / chunked use);
    a (B,)-shaped ``q_offset`` gives every batch row its own offset (the
    batched chunked-prefill shape, where co-ingested requests sit at
    different prompt depths) — masking is then per (row, q, k) but the
    arithmetic is unchanged, so a row's output depends only on its own
    offset and buffer.  Returns (B,Sq,H,Dh).

    The KV chunk partition is *anchored at absolute position 0 with a
    fixed chunk size*: a ragged Skv is padded up to a multiple of
    ``kv_chunk`` and the padded lanes are masked, rather than shrinking
    the chunk size to a divisor of Skv.  Fully-masked lanes are exact
    no-ops for the online-softmax recurrence (max against -1e30 cannot
    win, exp underflows to +0.0, and x+0.0 == x bitwise), so attention
    over a *longer* buffer with the same leading keys produces
    bit-identical outputs.  The serve engine's chunked prefill
    (models/lm.py:prefill_chunk_paged) leans on exactly this: it runs
    the same partition over a gathered page buffer and stays token-exact
    against whole-prompt prefill.
    """
    B, Sq, H, Dh = q.shape
    Skv, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    qh = q.reshape(B, Sq, KVH, G, Dh)          # keep storage dtype
    pad = -Skv % kv_chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (Skv + pad) // kv_chunk
    q_offset = jnp.asarray(q_offset)
    # (Sq,) for a shared offset, (B, Sq) for per-row offsets
    q_pos = (q_offset[:, None] if q_offset.ndim else q_offset) \
        + jnp.arange(Sq)
    scale = 1.0 / math.sqrt(Dh)

    kc = k.reshape(B, n_chunks, kv_chunk, KVH, Dh)
    vc = v.reshape(B, n_chunks, kv_chunk, KVH, Dh)

    def body(carry, inputs):
        m, l, acc = carry
        ci, ks, vs = inputs
        # bf16 operands, f32 MXU accumulation: upcasting K/V chunks
        # would double the LD-stage traffic (§Perf iteration log)
        s = jnp.einsum("bqkgd,bjkd->bkgqj", qh, ks,
                       preferred_element_type=jnp.float32) * scale
        k_pos = ci * kv_chunk + jnp.arange(kv_chunk)
        mask = (k_pos < Skv) & jnp.ones((Sq, 1), bool)
        if causal:
            mask = mask & (k_pos <= q_pos[..., None])
        if window is not None:
            mask = mask & (k_pos > q_pos[..., None] - window)
        # (Sq, j) shared mask vs (B, Sq, j) per-row mask
        bmask = mask[:, None, None] if mask.ndim == 3 \
            else mask[None, None, None]
        s = jnp.where(bmask, s, -1e30)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgqj,bjkd->bkgqd", p.astype(vs.dtype), vs,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, KVH, G, Sq), -1e30, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, KVH, G, Sq, Dh), jnp.float32)
    (m, l, acc), _ = lax.scan(
        body, (m0, l0, a0),
        (jnp.arange(n_chunks), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = jnp.moveaxis(out, 3, 1).reshape(B, Sq, H, Dh)  # (B,Sq,KVH,G,Dh)->
    return out.astype(q.dtype)


def local_attention(q, k, v, *, window: int, q_block: int = 256):
    """Banded causal attention: each chunk of ``window`` queries attends
    to its own chunk (causal) and the previous chunk — O(S*W) exactly,
    the sub-quadratic path required for long-context shapes.

    Queries are processed in ``q_block`` sub-blocks through ``lax.map``
    so the live score tensor is (…, q_block, 2W), not (…, W, 2W) —
    1/8th the peak memory at the default block size."""
    B, S, H, Dh = q.shape
    KVH = k.shape[2]
    G = H // KVH
    assert S % window == 0, (S, window)
    n = S // window
    q_block = min(q_block, window)
    nsq = window // q_block
    qh = q.reshape(B, n, window, KVH, G, Dh)
    kc = k.reshape(B, n, window, KVH, Dh)
    vc = v.reshape(B, n, window, KVH, Dh)
    scale = 1.0 / math.sqrt(Dh)
    # previous chunk (zero-padded for chunk 0)
    kp = jnp.pad(kc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    vp = jnp.pad(vc, ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))[:, :-1]
    k2 = jnp.concatenate([kp, kc], axis=2)                 # (B,n,2W,KVH,Dh)
    v2 = jnp.concatenate([vp, vc], axis=2)
    jpos = jnp.arange(2 * window) - window                 # rel. to chunk
    has_prev = (jnp.arange(n) > 0)                         # chunk0: no prev

    def one_block(sq_i):
        qs = lax.dynamic_slice_in_dim(qh, sq_i * q_block, q_block, axis=2)
        s = jnp.einsum("bnqkgd,bnjkd->bnkgqj", qs, k2,
                       preferred_element_type=jnp.float32) * scale
        qpos = sq_i * q_block + jnp.arange(q_block)
        mask = (jpos[None, :] <= qpos[:, None]) & \
               (jpos[None, :] > qpos[:, None] - window)    # (qb,2W)
        mask = mask[None] & (has_prev[:, None, None]
                             | (jpos >= 0)[None, None])    # (n,qb,2W)
        s = jnp.where(mask[None, :, None, None], s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bnkgqj,bnjkd->bnqkgd", p.astype(v2.dtype), v2,
                          preferred_element_type=jnp.float32)

    outs = lax.map(one_block, jnp.arange(nsq))             # (nsq,B,n,qb,...)
    out = jnp.moveaxis(outs, 0, 2)                         # (B,n,nsq,qb,...)
    return out.reshape(B, S, H, Dh).astype(q.dtype)


def decode_attention(q, k_cache, v_cache, pos, *, window: Optional[int]):
    """Single-token attention against a cache.

    q: (B,1,H,Dh); caches: (B,S_max,KVH,Dh); ``pos``: scalar int — the
    number of tokens already in the cache (batched decode advances in
    lockstep, which keeps the cache update a dynamic_update_slice that
    GSPMD partitions cleanly instead of a scatter it replicates)."""
    B, _, H, Dh = q.shape
    S, KVH = k_cache.shape[1], k_cache.shape[2]
    G = H // KVH
    qh = q.reshape(B, KVH, G, Dh)
    # keep K/V in their bf16 storage dtype; accumulate in f32 on the MXU
    # (upcasting the cache would double its HBM traffic — measured in
    # EXPERIMENTS.md §Perf, stablelm decode iteration)
    s = jnp.einsum("bkgd,bjkd->bkgj", qh, k_cache,
                   preferred_element_type=jnp.float32)     # (B,KVH,G,S)
    s = s * (1.0 / math.sqrt(Dh))
    idx = jnp.arange(S)                                    # (S,)
    valid = idx < pos
    if window is not None:
        valid &= idx >= (pos - window)
    s = jnp.where(valid[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgj,bjkd->bkgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, Dh).astype(q.dtype)


def update_kv_cache(cache, new, slot):
    """Write one token into a (B, S, KVH, Dh) cache at scalar ``slot``.

    When the cache's sequence dim is sharded (flash-decoding layout,
    ``kv_seq -> model``), a plain dynamic_update_slice at a dynamic
    index forces GSPMD to rematerialize the whole buffer (measured: the
    dominant decode cost).  Instead each seq-shard computes its local
    offset and only the owning shard writes — a shard-local ring write
    with zero collective traffic.
    """
    from ..sharding.rules import _current_mesh, active_rules, logical_spec
    mesh = _current_mesh()
    if mesh is None or mesh.empty:
        return lax.dynamic_update_slice(cache, new.astype(cache.dtype),
                                        (0, slot, 0, 0))
    # the decode cache's canonical logical layout (see cache_specs)
    full = logical_spec(("batch", "kv_seq", "act_heads", None),
                        cache.shape, mesh, active_rules())
    entries = tuple(full) + (None,) * (4 - len(tuple(full)))
    batch_axes, seq_axis = entries[0], entries[1]
    if not isinstance(seq_axis, str) or seq_axis not in mesh.axis_names:
        return lax.dynamic_update_slice(cache, new.astype(cache.dtype),
                                        (0, slot, 0, 0))

    from jax.sharding import PartitionSpec as P
    n_shards = mesh.shape[seq_axis]
    s_loc = cache.shape[1] // n_shards
    spec = P(*entries)
    new_spec = P(batch_axes, *([None] * (new.ndim - 1)))

    def local(c, n, p):
        my = lax.axis_index(seq_axis)
        off = p - my * s_loc
        in_range = jnp.logical_and(off >= 0, off < s_loc)

        def write(c):
            return lax.dynamic_update_slice(
                c, n.astype(c.dtype), (0, jnp.clip(off, 0, s_loc - 1),
                                       0, 0))
        return lax.cond(in_range, write, lambda c: c, c)

    return jax.shard_map(local, mesh=mesh, in_specs=(spec, new_spec, P()),
                         out_specs=spec, check_vma=False)(cache, new, slot)


def _gqa_expand_factor(cfg) -> int:
    """Expand K/V heads to the full Q-head count when the mesh's model
    axis divides H but not KVH.

    Measured motivation (EXPERIMENTS.md §Perf, qwen1.5-110b): with
    KVH=8 on a 16-way model axis GSPMD cannot reshard the 8-way KV
    layout and falls back to "involuntary full rematerialization" —
    replicate + repartition — per layer per microbatch.  Repeating KV
    G-fold makes every head tensor cleanly 16-way shardable; the
    repeated copies are *sharded*, so per-device KV bytes actually
    shrink versus the replicated fallback.
    """
    from ..sharding.rules import _current_mesh
    mesh = _current_mesh()
    if mesh is None or mesh.empty or "model" not in mesh.axis_names:
        return 1
    m = mesh.shape["model"]
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    if m > 1 and h % m == 0 and kvh % m and kvh < h:
        return h // kvh
    return 1


def attention_block(p, x, cfg, *, positions, causal=True,
                    window=None, mrope_positions=None,
                    cache=None, cache_pos=None):
    """Full attention sub-layer.  With ``cache`` given, runs one decode
    step (x: (B,1,D)) updating the cache in place (functionally)."""
    B, S, D = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions, mrope_positions)
    k0, v0 = k, v                    # pre-expansion (cache layout)
    if cache is None:
        g = _gqa_expand_factor(cfg)
        if g > 1:
            k = constrain(jnp.repeat(k, g, axis=2),
                          ("batch", None, "act_heads", None))
            v = constrain(jnp.repeat(v, g, axis=2),
                          ("batch", None, "act_heads", None))
    if cache is not None:
        k_cache, v_cache = cache["k"], cache["v"]
        Smax = k_cache.shape[1]
        ring = window is not None and Smax == window
        slot = (cache_pos % window) if ring else cache_pos   # scalar
        k_cache = update_kv_cache(k_cache, k, slot)
        v_cache = update_kv_cache(v_cache, v, slot)
        if ring:
            # a full ring holds exactly the last `window` tokens: all
            # written slots are attendable, none is out-of-window.
            out = decode_attention(q, k_cache, v_cache,
                                   jnp.minimum(cache_pos + 1, window),
                                   window=None)
        else:
            out = decode_attention(q, k_cache, v_cache, cache_pos + 1,
                                   window=window)
        new_cache = {"k": k_cache, "v": v_cache}
    else:
        if window is not None:
            out = local_attention(q, k, v, window=window)
        elif causal:
            out = flash_attention(q, k, v, causal=True,
                                  kv_chunk=cfg.attn_kv_chunk)
        else:
            out = flash_attention(q, k, v, causal=False,
                                  kv_chunk=cfg.attn_kv_chunk)
        # prefill: expose this layer's K/V so the caller can build a
        # decode cache (DCE'd when unused, e.g. during training);
        # stored in the *unexpanded* GQA layout.
        new_cache = {"k": k0, "v": v0}
    out = out.reshape(B, S, cfg.n_heads * cfg.head_dim)
    out = out @ p["wo"].astype(out.dtype)
    return constrain(out, ("batch", "seq", "act_embed")), new_cache


def _tp_gather_heads(x, tp_axis, axis: int):
    """Re-assemble a head-sharded activation inside a ``shard_map``
    tensor-parallel program (serve/parallel.py): an all-gather is a
    pure concatenation in mesh-axis order — no cross-shard *reduction*
    ever runs, which is what keeps the sharded program bit-identical
    to the single-device one (shard i computes exactly the slice of
    every op the single device would have computed for its heads).
    ``tp_axis=None`` (the single-device path) is a no-op."""
    if tp_axis is None:
        return x
    return lax.all_gather(x, tp_axis, axis=axis, tiled=True)


def chunk_scatter_targets(starts, n_valid, table_rows, n_tokens,
                          page_size):
    """Pure masking math for the chunked-prefill KV scatter: map token
    t of row b (absolute position ``starts[b] + t``) to its
    (page id, slot) write target.

    Padding tokens (``t >= n_valid[b]``) and positions whose page index
    falls past the row's table width are routed to the null page (page
    0, see serve/kv_cache.py), so a fixed-shape scatter never touches
    live data for lanes the scheduler didn't fill.  Returns
    (pid, slot), both (B, n_tokens) int32.
    """
    t = jnp.arange(n_tokens)[None, :]                      # (1, T)
    abs_pos = starts[:, None] + t                          # (B, T)
    nb = table_rows.shape[1]
    idx = jnp.minimum(abs_pos // page_size, nb - 1)
    pid = jnp.where(t < n_valid[:, None],
                    jnp.take_along_axis(table_rows, idx, axis=1), 0)
    slot = abs_pos % page_size
    return pid.astype(jnp.int32), slot.astype(jnp.int32)


def verify_scatter_targets(lengths, page_table, n_tokens, page_size):
    """Pure masking math for the decode/verify KV scatter: token t of
    row b sits at absolute position ``lengths[b] + t`` and writes to
    ``page_table[b, pos // page_size]`` at slot ``pos % page_size``.

    A position past the end of the page table must land on the null
    page — the default clamping gather would alias it onto the row's
    *last* live page and corrupt confirmed history.  Inactive rows
    carry all-zero tables, so their writes also fall on the null page.
    Returns (pid, slot), both (B, n_tokens) int32.
    """
    B = lengths.shape[0]
    nb = page_table.shape[1]
    abs_pos = lengths[:, None] + jnp.arange(n_tokens)[None, :]  # (B, T)
    bidx = jnp.arange(B)[:, None]
    idx = abs_pos // page_size
    pid = jnp.where(idx < nb,
                    page_table[bidx, jnp.minimum(idx, nb - 1)], 0)
    slot = abs_pos % page_size
    return pid.astype(jnp.int32), slot.astype(jnp.int32)


def _gather_layer_pages(pages, table, layer, like):
    """Layer ``layer``'s pages of ``table`` from the stacked pool (a
    token per (KVH, Dh) slab or KVH * Dh row, see
    DecoderLM.paged_state_specs), as a (B, n * ps, KVH, Dh) buffer with
    the head split of ``like`` (B, T, KVH, Dh)."""
    from ..kernels.paged_attention.ref import gather_pages
    return gather_pages(pages, table, layer).reshape(
        table.shape[0], -1, *like.shape[2:])


def overlay_tokens(buf, new, lengths):
    """Write a step's own tokens into a row's gathered context: token t
    of row b (absolute position ``lengths[b] + t``) lands at
    ``buf[b, lengths[b] + t]``.  ``buf``: (B, S, KVH, Dh) gathered from
    the incoming pages; ``new``: (B, T, KVH, Dh).  A position past the
    buffer's end is dropped, never clamped onto the row's last slot —
    what padding the buffer by T lanes and slicing them back would
    give, without copying the buffer.  Pure data movement: the buffer
    holds the bits a write into the pages followed by the gather would
    have given at every position the row owns."""
    B, T = new.shape[:2]
    pos = lengths[:, None] + jnp.arange(T, dtype=lengths.dtype)[None]
    return buf.at[jnp.arange(B)[:, None], pos].set(
        new.astype(buf.dtype), mode="drop")


def paged_attention_block(p, x, cfg, *, positions, k_pages, v_pages,
                          layer, page_table, lengths, tp_axis=None):
    """Paged decode attention sub-layer (continuous batching).

    x: (B, 1, D) with *per-request* positions (B, 1) — unlike
    ``attention_block``'s lockstep scalar ``cache_pos``, every sequence
    in the batch sits at its own depth.  ``k_pages``/``v_pages`` are the
    stacked pools (L, P, ps, ...), read-only: attention gathers
    layer ``layer``'s pages of ``page_table`` from them, overlays the
    new token's K/V at position ``lengths[b]`` (``overlay_tokens``) and
    attends over the buffer.  Returns (out, k, v) with the new token's
    K/V (B, 1, KVH, Dh) in the page dtype: *the caller owns page
    persistence* (DecoderLM.write_pages, which routes inactive rows'
    all-zero tables to the reserved null page, see serve/kv_cache.py).

    Under tensor parallelism (``tp_axis`` set, see serve/parallel.py)
    ``cfg`` is the *local* per-shard view: q/k/v carry this shard's
    heads, the page buffers hold this shard's KV-head slice, and the
    heads are re-gathered (concatenation, never reduction) before the
    replicated output projection.
    """
    from ..kernels.paged_attention.ref import decode_attention_core
    B, S, D = x.shape
    assert S == 1, "paged path is decode-only"
    q, k, v = _project_qkv(p, x, cfg, positions)
    k, v = k.astype(k_pages.dtype), v.astype(v_pages.dtype)
    kb = _gather_layer_pages(k_pages, page_table, layer, k)
    vb = _gather_layer_pages(v_pages, page_table, layer, v)
    with jax.named_scope("attn.kv_write"):
        kb = overlay_tokens(kb, k, lengths)
        vb = overlay_tokens(vb, v, lengths)
    out = decode_attention_core(q[:, 0], kb, vb, lengths + 1)
    out = _tp_gather_heads(out, tp_axis, axis=1)       # (B, H, Dh)
    out = out.reshape(B, 1, -1)
    out = out @ p["wo"].astype(out.dtype)
    return out, k, v


def paged_verify_attention_block(p, x, cfg, *, positions, k_pages,
                                 v_pages, layer, page_table, lengths,
                                 tp_axis=None):
    """Speculative-verification attention sub-layer (paged decode with a
    query-time axis).

    x: (B, T, D) — token 0 of row b is the request's last confirmed
    token, tokens 1..T-1 its draft continuation, token t sitting at
    absolute position ``lengths[b] + t`` (per-request positions, like
    ``paged_attention_block``).  All T tokens' K/V are overlaid on the
    row's gathered pages (``overlay_tokens``; positions past the
    table's end are dropped), then attention runs with per-(row, t)
    causal masking, so query t sees exactly the context the
    single-token decode step at its position would have seen: every
    position the row can confirm lies on a page it owns (copy-on-write
    / headroom happen host-side *before* the program runs; see
    serve/kv_cache.ensure_headroom).  Verifying T = 1 tokens *is* the
    decode step, bit for bit.

    Returns (out, k, v), the T tokens' K/V (B, T, KVH, Dh) in the page
    dtype for the caller's page write.
    """
    from ..kernels.paged_attention.ref import verify_attention_core
    B, T, D = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions)
    k, v = k.astype(k_pages.dtype), v.astype(v_pages.dtype)
    kb = _gather_layer_pages(k_pages, page_table, layer, k)
    vb = _gather_layer_pages(v_pages, page_table, layer, v)
    with jax.named_scope("attn.kv_write"):
        kb = overlay_tokens(kb, k, lengths)
        vb = overlay_tokens(vb, v, lengths)
    out = verify_attention_core(q, kb, vb, lengths)
    out = _tp_gather_heads(out, tp_axis, axis=2)       # (B, T, H, Dh)
    out = out.reshape(B, T, -1)
    out = out @ p["wo"].astype(out.dtype)
    return out, k, v


def paged_chunk_attention_block(p, x, cfg, *, positions, starts, n_valid,
                                k_pages, v_pages, layer, table_rows,
                                tp_axis=None):
    """Batched chunked-prefill attention sub-layer over a paged KV
    cache: one chunk each for up to B co-ingesting requests.

    x: (B, C, D) — row b is one request's next prompt chunk, token t
    sitting at absolute position ``starts[b] + t``; tokens with
    t >= ``n_valid[b]`` are padding, and rows with ``n_valid[b] == 0``
    are wholly inactive (fixed (B, C) shape -> one jit compile per
    context bucket regardless of how many requests co-ingest).
    ``table_rows``: (B, nb) int32 — each request's page table truncated
    to the dispatch's shared context bucket, covering every position
    < starts[b] + n_valid[b]; inactive rows and entries past a row's
    own allocation carry the null page.  ``k_pages``/``v_pages``: the
    stacked pools, read at layer ``layer``.

    Per row, earlier chunks' context is gathered from pages into a
    contiguous (nb * ps) buffer and the current chunk's K/V is overlaid
    at the row's absolute offset (vmapped dynamic_update_slice — pure
    data movement; the buffer is padded by C lanes so the last, partial
    chunk never clamps; overlaid padding tokens land past ``n_valid``
    where causal masking hides them).  Attention then runs through
    ``flash_attention`` with per-row ``q_offset``.  Every op here is
    row-independent (matmuls contract over feature dims, masks and the
    softmax recurrence are per row), the flash partition stays anchored
    at absolute position 0, and fully-masked lanes are exact no-ops —
    so each row is bit-identical to whole-prompt prefill attention *and*
    to the same chunk dispatched alone, whatever else shares the batch.
    The serve engine's token-parity guarantee rests on both.

    Returns (out, k, v) with the chunk's K/V in the page dtype; *the
    caller owns page persistence* — one stacked scatter over all layers
    (DecoderLM.write_pages).
    """
    B, C, D = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions)
    k, v = k.astype(k_pages.dtype), v.astype(v_pages.dtype)
    kc = _gather_layer_pages(k_pages, table_rows, layer, k)
    vc = _gather_layer_pages(v_pages, table_rows, layer, v)
    with jax.named_scope("attn.gather"):
        kc = jnp.pad(kc, ((0, 0), (0, C), (0, 0), (0, 0)))
        vc = jnp.pad(vc, ((0, 0), (0, C), (0, 0), (0, 0)))
        overlay = jax.vmap(lambda buf, new, s:
                           lax.dynamic_update_slice(buf, new, (s, 0, 0)))
        kc = overlay(kc, k, starts)
        vc = overlay(vc, v, starts)
    with jax.named_scope("attn.core"):
        out = flash_attention(q, kc, vc, causal=True,
                              kv_chunk=cfg.attn_kv_chunk, q_offset=starts)
    out = _tp_gather_heads(out, tp_axis, axis=2)       # (B, C, H, Dh)
    out = out.reshape(B, C, -1)
    out = out @ p["wo"].astype(out.dtype)
    return out, k, v


def cross_attention_block(p, x, enc_kv, cfg):
    """Decoder cross-attention; ``enc_kv`` = (k, v) precomputed from the
    encoder output: (B, Senc, KVH, Dh) each."""
    B, S, D = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    q = (x @ p["wq"].astype(x.dtype)).reshape(B, S, h, hd)
    k, v = enc_kv
    out = flash_attention(q, k, v, causal=False,
                          kv_chunk=min(cfg.attn_kv_chunk, k.shape[1]))
    out = out.reshape(B, S, h * hd) @ p["wo"].astype(x.dtype)
    return constrain(out, ("batch", "seq", "act_embed"))


def encode_cross_kv(p, enc_out, cfg):
    B, Senc, _ = enc_out.shape
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    k = (enc_out @ p["wk"].astype(enc_out.dtype)).reshape(B, Senc, kvh, hd)
    v = (enc_out @ p["wv"].astype(enc_out.dtype)).reshape(B, Senc, kvh, hd)
    return k, v


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def mlp_specs(cfg, d_ff: Optional[int] = None) -> dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    if cfg.mlp_kind == "gelu":
        return {
            "w1": ParamSpec((d, f), ("embed", "ff")),
            "b1": ParamSpec((f,), ("stats",), init=zeros),
            "w2": ParamSpec((f, d), ("ff", "embed")),
            "b2": ParamSpec((d,), ("stats",), init=zeros),
        }
    return {
        "wg": ParamSpec((d, f), ("embed", "ff")),
        "wu": ParamSpec((d, f), ("embed", "ff")),
        "wd": ParamSpec((f, d), ("ff", "embed")),
    }


def mlp_block(p, x, cfg, tp_axis=None):
    """Dense FFN.  Under tensor parallelism (``tp_axis`` set, see
    serve/parallel.py) the up projections are sharded over the hidden
    dim and the hidden activation is re-gathered (concatenation, no
    reduction) before the replicated down projection — the same
    bitwise-preserving split as the attention head gather."""
    if cfg.mlp_kind == "gelu":
        h = x @ p["w1"].astype(x.dtype) + p["b1"].astype(x.dtype)
        h = jax.nn.gelu(h)
        h = constrain(h, ("batch", None, "act_ff"))
        h = _tp_gather_heads(h, tp_axis, axis=2)
        return h @ p["w2"].astype(x.dtype) + p["b2"].astype(x.dtype)
    g = jax.nn.silu(x @ p["wg"].astype(x.dtype))
    u = x @ p["wu"].astype(x.dtype)
    h = constrain(g * u, ("batch", None, "act_ff"))
    h = _tp_gather_heads(h, tp_axis, axis=2)
    out = h @ p["wd"].astype(x.dtype)
    return constrain(out, ("batch", "seq", "act_embed"))


# ---------------------------------------------------------------------------
# Mixture of Experts
# ---------------------------------------------------------------------------


def moe_specs(cfg) -> dict:
    m = cfg.moe
    d, fe = cfg.d_model, m.d_expert_ff
    e = m.n_experts
    p = {
        "router": ParamSpec((d, e), ("embed", None), init=normal(0.02)),
        "wg": ParamSpec((e, d, fe), ("expert", "embed", "expert_ff")),
        "wu": ParamSpec((e, d, fe), ("expert", "embed", "expert_ff")),
        "wd": ParamSpec((e, fe, d), ("expert", "expert_ff", "embed")),
    }
    if m.n_shared:
        fs = m.d_expert_ff * m.n_shared
        p["shared"] = {
            "wg": ParamSpec((d, fs), ("embed", "ff")),
            "wu": ParamSpec((d, fs), ("embed", "ff")),
            "wd": ParamSpec((fs, d), ("ff", "embed")),
        }
    return p


def _moe_compute(p, x, cfg, ep_size: int, ep_index):
    """Local MoE shard: route this token shard, dispatch only to the
    ``E/ep_size`` experts this shard owns, run their FFNs, and return the
    *partial* output (summed over expert shards by the caller).

    RISC-NN mapping: expert routing is *task-level sparsity* — the router
    output is the "sparse vector" and the (E_loc, C) dispatch table is the
    compacted jump table (Sparse PC Inc): work that is not routed is never
    materialized, exactly like skipped CAL instructions (paper §5.4).
    """
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    E, K = m.n_experts, m.top_k
    e_loc = E // ep_size
    C = int(T * K / E * m.capacity_factor)
    C = max(1, min(C, T))
    xf = x.reshape(T, D)

    logits = (xf.astype(jnp.float32)
              @ p["router"].astype(jnp.float32))            # (T,E)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = lax.top_k(probs, K)                        # (T,K)
    if m.normalize_router:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    eid = topi.reshape(-1)                                  # (T*K,)
    gate = topw.reshape(-1)
    tok = jnp.arange(T * K) // K
    # dispatch table for the experts THIS shard owns
    lid = eid - ep_index * e_loc
    mine = (lid >= 0) & (lid < e_loc)
    lid_c = jnp.where(mine, lid, 0)
    onehot = jax.nn.one_hot(lid_c, e_loc, dtype=jnp.int32) \
        * mine[:, None].astype(jnp.int32)                   # (T*K,E_loc)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos = jnp.take_along_axis(pos, lid_c[:, None], axis=1)[:, 0]
    pos = jnp.where(mine & (pos < C), pos, C)               # OOB -> dropped

    x_e = jnp.zeros((e_loc, C, D), x.dtype)
    x_e = x_e.at[lid_c, pos].set(xf[tok], mode="drop")

    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x_e, p["wg"].astype(x.dtype)))
    u = jnp.einsum("ecd,edf->ecf", x_e, p["wu"].astype(x.dtype))
    y_e = jnp.einsum("ecf,efd->ecd", g * u, p["wd"].astype(x.dtype))

    y_tok = y_e.at[lid_c, pos].get(mode="fill", fill_value=0)  # (T*K,D)
    y = jnp.zeros((T, D), x.dtype)
    y = y.at[tok].add(y_tok * gate[:, None].astype(x.dtype), mode="drop")

    if m.n_shared:
        # shared expert(s): dense FFN, tensor-parallel over the hidden dim
        sp = p["shared"]
        sg = jax.nn.silu(xf @ sp["wg"].astype(x.dtype))
        su = xf @ sp["wu"].astype(x.dtype)
        y = y + (sg * su) @ sp["wd"].astype(x.dtype)

    # load-balance aux loss (Switch-style) over this token shard
    me = probs.mean(axis=0)                                 # (E,)
    ce = jax.nn.one_hot(topi[:, 0], E).mean(axis=0)
    aux = E * jnp.sum(me * ce)
    return y.reshape(B, S, D), aux


def moe_block(p, x, cfg):
    """Expert-parallel MoE via shard_map.

    GSPMD partitions the global scatter/gather dispatch by replicating
    the (T, D) token tensor ("involuntary full rematerialization"),
    which is both the memory and the collective bottleneck at 1M-token
    batches.  shard_map makes the efficient schedule explicit instead:
    the residual stream is already batch-sharded and model-replicated,
    so every (data, model) device routes *its own* token shard to *its
    own* experts — dispatch is entirely local, and the only collective
    is the same psum-over-model the dense FFN pays.
    """
    from ..sharding.rules import _current_mesh
    mesh = _current_mesh()
    m = cfg.moe
    usable = (mesh is not None and not mesh.empty
              and "model" in mesh.axis_names
              and m.n_experts % mesh.shape["model"] == 0)
    if not usable:
        y, aux = _moe_compute(p, x, cfg, 1, 0)
        return constrain(y, ("batch", "seq", "act_embed")), aux

    from jax.sharding import PartitionSpec as P
    ep = mesh.shape["model"]
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    xspec = P(dp_axes if len(dp_axes) > 1 else dp_axes[0], None, None) \
        if dp_axes else P(None, None, None)
    pspec = {
        "router": P(None, None),
        "wg": P("model", None, None),
        "wu": P("model", None, None),
        "wd": P("model", None, None),
    }
    if m.n_shared:
        pspec["shared"] = {"wg": P(None, "model"), "wu": P(None, "model"),
                           "wd": P("model", None)}

    def local(pl, xl):
        y_part, aux = _moe_compute(pl, xl, cfg, ep,
                                   lax.axis_index("model"))
        y = lax.psum(y_part, "model")
        if dp_axes:
            aux = lax.pmean(aux, dp_axes)
        aux = lax.pmean(aux, "model")   # identical per model shard
        return y, aux

    y, aux = jax.shard_map(local, mesh=mesh, in_specs=(pspec, xspec),
                           out_specs=(xspec, P()), check_vma=False)(p, x)
    return constrain(y, ("batch", "seq", "act_embed")), aux


# ---------------------------------------------------------------------------
# embeddings / unembedding
# ---------------------------------------------------------------------------


def embed_specs(cfg) -> dict:
    p = {"tok": ParamSpec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                          init=normal(0.02))}
    if not cfg.tie_embeddings:
        p["head"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                              ("embed", "vocab"))
    return p


def embed_tokens(p, tokens, cfg, dtype):
    x = jnp.take(p["tok"], tokens, axis=0).astype(dtype)
    if cfg.scale_embeddings:
        x = x * math.sqrt(cfg.d_model)
    return constrain(x, ("batch", "seq", "act_embed"))


def unembed(p, x, cfg):
    w = p["tok"].T if cfg.tie_embeddings else p["head"]
    logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = c * jnp.tanh(logits / c)
    return constrain(logits, ("batch", "seq", "act_vocab"))
