"""Dense GQA decoders (Qwen2, Qwen3) with a tied embedding table: the
layout of their weights, the work a serve dispatch needs, and their
plain reference.

An architecture module
----------------------

Everything the benchmark knows of one architecture is in one module
under ``bench/arch/``, named by the ``"reference"`` key of each
configuration file that uses it and loaded by path
(``bench/lib/spec.py``).  ``bench/lib/`` is shared by every
architecture and names none.  Each module exports:

    Dims, dims(config) -> Dims
        the sizes the other names read, from the configuration file;
        hashable.  ``dims(config).V`` is the vocabulary the traffic
        draws from and ``correct`` judges over.
    program_config(config)
        the program's ``ModelConfig`` for the configuration.
    shapes(dims)
        the parameter tree, a shape tuple at each leaf: the program's
        tree leaf for leaf (``weights.check_layout``).
    std(path, shape)
        the standard deviation of a leaf's normal draw
        (``weights.make_weights``).
    logits(weights, dims, tokens, rows, quant=None)
        the plain float32 reference: logits of ``tokens`` at positions
        ``rows``, each row predicting the token after it, shape (R, V)
        with R >= len(rows) (rows past ``len(rows)`` are padding).
        ``quant`` ("int8" or "fp8") is the control: the reference at
        the precision below the configuration's.
    dispatch_work(dims, *, decode=None, prefill=None) -> work.Work
        what one dispatch of a step program needs, counted from
        shapes: FLOPs (a multiply-add is two), K/V bytes, and the
        weight bytes this one dispatch reads.  ``decode=(contexts, T)``:
        rows decoding ``T`` tokens each after ``contexts`` live tokens;
        ``prefill=(starts, valid)``: chunk rows of ``valid`` tokens from
        ``starts`` (``valid == 0`` is padding).  A fused dispatch
        passes both.

A new architecture is a new module beside this one, with nothing
edited in ``bench/lib/``; it may load this one (``spec.load_arch``) and
use its building blocks (``mm``, ``rms``, ``rope``, ``layer``, ``embed``,
``pad``, the work counts).

This architecture
-----------------

Layout (the program's parameter tree; ``L`` layers stacked):

    embed/tok (V, d)                      tied input and output table
    final_norm/scale (d,)                 multiplier is 1 + scale
    layers/ln1/scale, layers/ln2/scale (L, d)
    layers/mix/wq (L, d, H*Dh)   wk, wv (L, d, KVH*Dh)   wo (L, H*Dh, d)
    layers/mix/bq, bk, bv (L, n)          when ``attention_bias``
    layers/mix/q_norm, k_norm (L, Dh)     when ``qk_norm``
    layers/ffn/wg, wu (L, d, F)   wd (L, F, d)

Leaves of two or more dims are stored in the serving dtype; 1-D leaves
stay float32.  Matrices are drawn with std 1/sqrt(fan_in), the table
with 0.02, norm scales and biases with ``SMALL_STD``.

The reference is written from the published architecture, in
straightforward ``jax.numpy`` at ``highest`` matmul precision, with no
cache, paging, batching or kernel, and imports nothing of the program.
Per layer:

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
blocks of queries, so a long context fits beside the weights.  The
control rounds every matmul operand to int8 or fp8 (e4m3) first:
weights per output channel, activations per token, both with a
symmetric scale, which is what serving the model at the precision below
bfloat16 would compute.

Work: every parameter is read once per dispatch (the tied table
included); a decode row at context ``n`` reads its ``n`` live tokens
of K and V and writes one, and its query attends ``n + 1`` keys; a
prefill row of ``v`` valid tokens from ``start`` reads ``start`` tokens
of K and V, writes ``v``, and token ``t`` attends ``start + t + 1``
keys; each row that yields logits pays one unembedding.  The count is
of what the work needs, not of what the program moves, so a kernel
that skips dead pages, padded prefill rows or inactive batch rows reads
as a higher share of its roofline.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, Iterable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.weights import nbytes
from bench.lib.work import Work

#: standard deviation of the norm scales and biases around their
#: neutral value; matrices use 1/sqrt(fan_in), the table 0.02
SMALL_STD = 0.1
#: queries per attention block
Q_BLOCK = 512
#: sequences are padded to a multiple of this (one compile per length)
PAD = 1024


@dataclasses.dataclass(frozen=True)
class Dims:
    L: int
    d: int
    H: int
    KVH: int
    Dh: int
    F: int
    V: int
    qk_norm: bool
    bias: bool
    theta: float
    eps: float
    tied: bool

    @property
    def kv_bytes_per_token(self) -> int:
        """bf16 K and V of one token over all layers."""
        return self.L * 2 * self.KVH * self.Dh * 2


def dims(config: Dict[str, Any]) -> Dims:
    m = config["model"]
    return Dims(L=m["num_hidden_layers"], d=m["hidden_size"],
                H=m["num_attention_heads"], KVH=m["num_key_value_heads"],
                Dh=m.get("head_dim")
                or m["hidden_size"] // m["num_attention_heads"],
                F=m["intermediate_size"], V=m["vocab_size"],
                qk_norm=bool(config["arch"]["qk_norm"]),
                bias=bool(config["arch"]["attention_bias"]),
                theta=float(m["rope_theta"]), eps=float(m["rms_norm_eps"]),
                tied=bool(m["tie_word_embeddings"]))


def program_config(config: Dict[str, Any]):
    """The program's ``ModelConfig`` for this configuration file."""
    from repro.configs import ModelConfig
    k = dims(config)
    if not k.tied:
        raise ValueError("only tied embeddings are laid out here")
    return ModelConfig(
        name=config["name"], family="dense", n_layers=k.L, d_model=k.d,
        n_heads=k.H, n_kv_heads=k.KVH, head_dim=k.Dh, d_ff=k.F,
        vocab_size=k.V, qkv_bias=k.bias, qk_norm=k.qk_norm,
        rope_theta=k.theta, norm_eps=k.eps, tie_embeddings=True,
        param_dtype=config["dtype"], compute_dtype=config["dtype"],
        **config.get("program", {}))


def shapes(k: Dims) -> Dict[str, Any]:
    L, d, H, KVH, Dh, F = k.L, k.d, k.H, k.KVH, k.Dh, k.F
    mix = {"wq": (L, d, H * Dh), "wk": (L, d, KVH * Dh),
           "wv": (L, d, KVH * Dh), "wo": (L, H * Dh, d)}
    if k.bias:
        mix.update(bq=(L, H * Dh), bk=(L, KVH * Dh), bv=(L, KVH * Dh))
    if k.qk_norm:
        mix.update(q_norm=(L, Dh), k_norm=(L, Dh))
    return {"embed": {"tok": (k.V, d)}, "final_norm": {"scale": (d,)},
            "layers": {"ln1": {"scale": (L, d)}, "ln2": {"scale": (L, d)},
                       "mix": mix,
                       "ffn": {"wg": (L, d, F), "wu": (L, d, F),
                               "wd": (L, F, d)}}}


def std(path: Tuple[str, ...], shape) -> float:
    name = path[-1]
    if name == "tok":
        return 0.02
    if name.startswith("w"):
        return 1.0 / math.sqrt(shape[-2])
    return SMALL_STD                       # norm scales, biases


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------


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


def mm(a, w, quant):
    """a (S, n) @ w (n, m) in float32."""
    if quant is not None:
        a = _fake_quant(a, -1, quant)      # per token
        w = _fake_quant(w, 0, quant)       # per output channel
    return jnp.matmul(a, w, precision="highest")


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + scale)


def rope(x, pos, theta):
    dh = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None, None].astype(jnp.float32) * inv      # (S, 1, dh/2)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@functools.partial(jax.jit, static_argnames=("k", "quant"))
def layer(lw, x, *, k: Dims, quant: Optional[str]):
    """One decoder layer over the whole sequence ``x`` (S, d)."""
    f32 = lambda a: a.astype(jnp.float32)          # noqa: E731
    S = x.shape[0]
    G = k.H // k.KVH
    pos = jnp.arange(S)
    h = rms(x, f32(lw["ln1"]["scale"]), k.eps)
    m = lw["mix"]
    q = mm(h, f32(m["wq"]), quant)
    kk = mm(h, f32(m["wk"]), quant)
    v = mm(h, f32(m["wv"]), quant)
    if k.bias:
        q, kk, v = q + f32(m["bq"]), kk + f32(m["bk"]), v + f32(m["bv"])
    q = q.reshape(S, k.H, k.Dh)
    kk = kk.reshape(S, k.KVH, k.Dh)
    v = v.reshape(S, k.KVH, k.Dh)
    if k.qk_norm:
        q = rms(q, f32(m["q_norm"]), k.eps)
        kk = rms(kk, f32(m["k_norm"]), k.eps)
    q, kk = rope(q, pos, k.theta), rope(kk, pos, k.theta)
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
    x = x + mm(out, f32(m["wo"]), quant)
    f = lw["ffn"]
    h = rms(x, f32(lw["ln2"]["scale"]), k.eps)
    g = jax.nn.silu(mm(h, f32(f["wg"]), quant))
    u = mm(h, f32(f["wu"]), quant)
    return x + mm(g * u, f32(f["wd"]), quant)


@functools.partial(jax.jit, static_argnames=("k", "quant"))
def _head(w, x, rows, *, k: Dims, quant: Optional[str]):
    h = rms(x[rows], w["final_norm"]["scale"].astype(jnp.float32), k.eps)
    return mm(h, w["embed"]["tok"].astype(jnp.float32).T, quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def embed(table, tokens, *, quant: Optional[str]):
    """Rows of ``table`` (V, d) in float32; with ``quant`` the table is
    rounded per row first."""
    e = table.astype(jnp.float32)
    if quant is not None:
        e = _fake_quant(e, -1, quant)   # per row: the unembedding's channels
    return e[tokens]


def pad(a: np.ndarray, to: int) -> np.ndarray:
    """``a`` as int32, zero-padded to a multiple of ``to``."""
    out = np.zeros(-(-len(a) // to) * to, np.int32)
    out[:len(a)] = a
    return out


def logits(w, k: Dims, tokens: np.ndarray, rows: np.ndarray,
           quant: Optional[str] = None) -> jax.Array:
    """Float32 logits of ``tokens`` at positions ``rows`` (each row
    predicts the token after it): shape (R, V) with R >= len(rows), the
    rows past ``len(rows)`` padding."""
    x = embed(w["embed"]["tok"], jnp.asarray(pad(tokens, PAD)), quant=quant)
    with jax.default_matmul_precision("highest"):
        for i in range(k.L):
            lw = jax.tree.map(lambda a: a[i], w["layers"])
            x = layer(lw, x, k=k, quant=quant)
        return _head(w, x, jnp.asarray(pad(rows, 128)), k=k, quant=quant)


# ---------------------------------------------------------------------------
# work
# ---------------------------------------------------------------------------


def weight_bytes(k: Dims) -> int:
    """Every parameter once, as ``weights.make_weights`` stores it."""
    return nbytes(shapes(k))


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


def dispatch_work(k: Dims, *, decode=None, prefill=None) -> Work:
    """One dispatch: its decode rows, its prefill rows, and every weight
    read once."""
    w = Work(weight_bytes=weight_bytes(k))
    if decode is not None:
        w = w + decode_rows(k, *decode)
    if prefill is not None:
        w = w + prefill_rows(k, *prefill)
    return w
