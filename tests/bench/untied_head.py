"""A dense GQA decoder with an untied output head: an architecture
module (``bench/arch/dense_gqa.py`` says what one exports) written for
the tests, placed in a checkout's ``bench/arch/`` beside
``dense_gqa.py``.  Its layout is that one's plus

    embed/head (d, V)                     the output head, std 1/sqrt(d)

and its logits are ``(rms(x) * (1 + final_norm)) W_head``."""
from __future__ import annotations

import dataclasses
import functools
import pathlib
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from bench.lib.spec import load_arch
from bench.lib.weights import nbytes

dense = load_arch(pathlib.Path(__file__).with_name("dense_gqa.py"))
Dims, dims = dense.Dims, dense.dims


def program_config(config: Dict[str, Any]):
    from repro.configs import ModelConfig
    k = dims(config)
    return ModelConfig(
        name=config["name"], family="dense", n_layers=k.L, d_model=k.d,
        n_heads=k.H, n_kv_heads=k.KVH, head_dim=k.Dh, d_ff=k.F,
        vocab_size=k.V, qkv_bias=k.bias, qk_norm=k.qk_norm,
        rope_theta=k.theta, norm_eps=k.eps, tie_embeddings=False,
        param_dtype=config["dtype"], compute_dtype=config["dtype"],
        **config.get("program", {}))


def shapes(k: Dims) -> Dict[str, Any]:
    tree = dense.shapes(k)
    tree["embed"]["head"] = (k.d, k.V)
    return tree


def std(path, shape) -> float:
    return dense.std(("w",) if path[-1] == "head" else path, shape)


@functools.partial(jax.jit, static_argnames=("k", "quant"))
def _head(w, x, rows, *, k: Dims, quant: Optional[str]):
    h = dense.rms(x[rows], w["final_norm"]["scale"].astype(jnp.float32),
                  k.eps)
    return dense.mm(h, w["embed"]["head"].astype(jnp.float32), quant)


def logits(w, k: Dims, tokens, rows, quant: Optional[str] = None):
    x = dense.embed(w["embed"]["tok"],
                    jnp.asarray(dense.pad(tokens, dense.PAD)), quant=quant)
    with jax.default_matmul_precision("highest"):
        for i in range(k.L):
            lw = jax.tree.map(lambda a: a[i], w["layers"])
            x = dense.layer(lw, x, k=k, quant=quant)
        return _head(w, x, jnp.asarray(dense.pad(rows, 128)), k=k,
                     quant=quant)


def dispatch_work(k: Dims, *, decode=None, prefill=None):
    w = dense.dispatch_work(k, decode=decode, prefill=prefill)
    return dataclasses.replace(w, weight_bytes=nbytes(shapes(k)))
