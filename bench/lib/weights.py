"""Model shapes from a configuration file, and weights from the seed.

The configuration file holds the published ``config.json`` keys.  From
them this module builds the program's model (through its public
``ModelConfig``) and makes every weight on the device in one jitted
call, in the dtype the checkpoint is served in.  The weights are the
benchmark's, not the program's: the plain reference reads the same
arrays, so neither side takes anything the other made.

Layout (the program's parameter tree; ``L`` layers stacked):

    embed/tok (V, d)                      tied input and output table
    final_norm/scale (d,)                 multiplier is 1 + scale
    layers/ln1/scale, layers/ln2/scale (L, d)
    layers/mix/wq (L, d, H*Dh)   wk, wv (L, d, KVH*Dh)   wo (L, H*Dh, d)
    layers/mix/bq, bk, bv (L, n)          when ``attention_bias``
    layers/mix/q_norm, k_norm (L, Dh)     when ``qk_norm``
    layers/ffn/wg, wu (L, d, F)   wd (L, F, d)

Leaves of two or more dims are stored in the serving dtype; 1-D leaves
stay float32.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

#: standard deviation of the norm scales and biases around their
#: neutral value; matrices use 1/sqrt(fan_in), the table 0.02
SMALL_STD = 0.1


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


def _std(path: Tuple[str, ...], shape) -> float:
    name = path[-1]
    if name == "tok":
        return 0.02
    if name.startswith("w"):
        return 1.0 / math.sqrt(shape[-2])
    return SMALL_STD                       # norm scales, biases


def make_weights(k: Dims, seed: int, dtype: str, device=None):
    """Every weight of the model, on ``device``, from ``seed``: one
    jitted call."""
    tree = shapes(k)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, tuple))
    paths = [tuple(p.key for p in path) for path, _ in flat]
    shp = [s for _, s in flat]
    dt = jnp.dtype(dtype)

    def gen(key):
        keys = jax.random.split(key, len(shp))
        out = []
        for kk, path, s in zip(keys, paths, shp):
            x = _std(path, s) * jax.random.normal(kk, s, jnp.float32)
            out.append(x.astype(dt if len(s) >= 2 else jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.key(seed % (1 << 63))
    fn = jax.jit(gen, out_shardings=(
        None if device is None
        else jax.sharding.SingleDeviceSharding(device)))
    return fn(key)


def check_layout(weights, abstract) -> None:
    """The benchmark's tree has the program's structure, shapes and
    dtypes, leaf for leaf."""
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), weights)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), abstract)
    if got != want:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"benchmark {got}\nprogram   {want}")
