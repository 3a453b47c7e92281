"""Weights from the seed, for any architecture.

An architecture module (``bench/arch/``) gives the parameter tree of
shapes and the standard deviation of each leaf; this module makes every
weight on the device in one jitted call, in the dtype the checkpoint is
served in.  The weights are the benchmark's, not the program's: the
plain reference reads the same arrays, so neither side takes anything
the other made.

Leaves of two or more dims are stored in the serving dtype; 1-D leaves
stay float32.
"""
from __future__ import annotations

import math
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def make_weights(tree: Any, std: Callable[[Tuple[str, ...], tuple], float],
                 seed: int, dtype: str, device=None):
    """Every leaf of ``tree`` (a shape tuple at each leaf), drawn from
    ``seed`` as ``std(path, shape)`` times a standard normal, on
    ``device``: one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree,
                                                         is_leaf=_is_shape)
    paths = [tuple(p.key for p in path) for path, _ in flat]
    shp = [s for _, s in flat]
    dt = jnp.dtype(dtype)

    def gen(key):
        keys = jax.random.split(key, len(shp))
        out = []
        for kk, path, s in zip(keys, paths, shp):
            x = std(path, s) * jax.random.normal(kk, s, jnp.float32)
            out.append(x.astype(dt if len(s) >= 2 else jnp.float32))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.key(seed % (1 << 63))
    fn = jax.jit(gen, out_shardings=(
        None if device is None
        else jax.sharding.SingleDeviceSharding(device)))
    return fn(key)


def nbytes(tree: Any, itemsize: int = 2) -> int:
    """Bytes of ``tree`` (shapes) as ``make_weights`` stores it: leaves
    of two or more dims at ``itemsize`` bytes (the serving dtype's,
    bfloat16 by default), 1-D leaves in float32."""
    return sum(math.prod(s) * (itemsize if len(s) >= 2 else 4)
               for s in jax.tree.leaves(tree, is_leaf=_is_shape))


def check_layout(weights, abstract) -> None:
    """The benchmark's tree has the program's structure, shapes and
    dtypes, leaf for leaf."""
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), weights)
    want = jax.tree.map(lambda a: (tuple(a.shape), str(a.dtype)), abstract)
    if got != want:
        raise ValueError(f"weight layout differs from the program's:\n"
                         f"benchmark {got}\nprogram   {want}")
