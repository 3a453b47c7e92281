"""Plain float32 reference of a dense GQA decoder (Qwen2 / Qwen3).

Written from the published architecture, in straightforward
``jax.numpy`` at ``highest`` matmul precision, with no cache, paging,
batching or kernel, and importing nothing of the program.  Per layer:

    h = rms(x) * (1 + ln1)
    q, k, v = h Wq (+ bq), h Wk (+ bk), h Wv (+ bv)       split in heads
    q, k = rms(q) * (1 + q_norm), rms(k) * (1 + k_norm)    (Qwen3 only)
    q, k = rope(q), rope(k)          rotate-half, theta from the config
    x += softmax(q k^T / sqrt(Dh), causal) v Wo    query head i reads
                                                   kv head i // (H / KVH)
    h = rms(x) * (1 + ln2)
    x += (silu(h Wg) * h Wu) Wd
    logits = (rms(x) * (1 + final_norm)) E^T        tied table E

It runs one sequence at a time, layer by layer, with attention in
blocks of queries, so a long context fits beside the weights.

``quant="int8"`` (or ``"fp8"``, e4m3) is the control: every matmul
operand is rounded to that format first (weights per output channel,
activations per token, both with a symmetric scale), which is what
serving the model at the precision below bfloat16 would compute.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .weights import Dims

#: queries per attention block
Q_BLOCK = 512
#: sequences are padded to a multiple of this (one compile per length)
PAD = 1024


def _fake_quant(x, axis, quant):
    """``x`` rounded to ``quant`` with a symmetric scale per slice along
    ``axis`` (the slice's largest magnitude maps to the format's)."""
    top = 127.0 if quant == "int8" else 448.0
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if quant == "int8":
        return jnp.round(x / s) * s
    if quant == "fp8":
        return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    raise ValueError(f"unknown control precision {quant!r}")


def _mm(a, w, quant):
    """a (S, n) @ w (n, m) in float32."""
    if quant is not None:
        a = _fake_quant(a, -1, quant)      # per token
        w = _fake_quant(w, 0, quant)       # per output channel
    return jnp.matmul(a, w, precision="highest")


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def _rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None, None].astype(jnp.float32) * inv      # (S, 1, dh/2)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("k", "quant"))
def _layer(lw, x, *, k: Dims, quant: Optional[str]):
    f32 = lambda a: a.astype(jnp.float32)          # noqa: E731
    S = x.shape[0]
    G = k.H // k.KVH
    pos = jnp.arange(S)
    h = _rms(x, f32(lw["ln1"]["scale"]), k.eps)
    m = lw["mix"]
    q = _mm(h, f32(m["wq"]), quant)
    kk = _mm(h, f32(m["wk"]), quant)
    v = _mm(h, f32(m["wv"]), quant)
    if k.bias:
        q, kk, v = q + f32(m["bq"]), kk + f32(m["bk"]), v + f32(m["bv"])
    q = q.reshape(S, k.H, k.Dh)
    kk = kk.reshape(S, k.KVH, k.Dh)
    v = v.reshape(S, k.KVH, k.Dh)
    if k.qk_norm:
        q = _rms(q, f32(m["q_norm"]), k.eps)
        kk = _rms(kk, f32(m["k_norm"]), k.eps)
    q, kk = _rope(q, pos, k.theta), _rope(kk, pos, k.theta)
    kk = jnp.repeat(kk, G, axis=1)                 # head i -> kv i // G
    v = jnp.repeat(v, G, axis=1)

    def block(qb, start):
        s = jnp.einsum("qhd,khd->hqk", qb, kk, precision="highest")
        s = s / np.sqrt(k.Dh)
        causal = (jnp.arange(S)[None, :]
                  <= start + jnp.arange(qb.shape[0])[:, None])
        s = jnp.where(causal[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision="highest")

    nb = S // Q_BLOCK
    out = jax.lax.map(lambda i: block(
        jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK), i * Q_BLOCK),
        jnp.arange(nb))
    out = out.reshape(S, k.H * k.Dh)
    x = x + _mm(out, f32(m["wo"]), quant)
    f = lw["ffn"]
    h = _rms(x, f32(lw["ln2"]["scale"]), k.eps)
    g = jax.nn.silu(_mm(h, f32(f["wg"]), quant))
    u = _mm(h, f32(f["wu"]), quant)
    return x + _mm(g * u, f32(f["wd"]), quant)


@functools.partial(jax.jit, static_argnames=("k", "quant"))
def _head(w, x, rows, *, k: Dims, quant: Optional[str]):
    h = _rms(x[rows], w["final_norm"]["scale"].astype(jnp.float32), k.eps)
    return _mm(h, w["embed"]["tok"].astype(jnp.float32).T, quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _embed(table, tokens, *, quant: Optional[str]):
    e = table.astype(jnp.float32)
    if quant is not None:
        e = _fake_quant(e, -1, quant)   # per row: the unembedding's channels
    return e[tokens]


def _pad(a: np.ndarray, to: int) -> np.ndarray:
    out = np.zeros(-(-len(a) // to) * to, np.int32)
    out[:len(a)] = a
    return out


def logits(w, k: Dims, tokens: np.ndarray, rows: np.ndarray,
           quant: Optional[str] = None) -> jax.Array:
    """Float32 logits of ``tokens`` at positions ``rows`` (each row
    predicts the token after it): shape (R, V) with R >= len(rows), the
    rows past ``len(rows)`` padding."""
    x = _embed(w["embed"]["tok"], jnp.asarray(_pad(tokens, PAD)),
               quant=quant)
    with jax.default_matmul_precision("highest"):
        for i in range(k.L):
            lw = jax.tree.map(lambda a: a[i], w["layers"])
            x = _layer(lw, x, k=k, quant=quant)
        return _head(w, x, jnp.asarray(_pad(rows, 128)), k=k, quant=quant)
