"""Production mesh definitions + TPU v5e hardware constants.

``make_production_mesh`` is a function (not a module-level constant) so
importing this module never touches jax device state — the dry-run
driver sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
*before* any jax initialization; everything else sees 1 CPU device.
"""
from __future__ import annotations

import dataclasses

import jax

__all__ = ["make_mesh", "make_production_mesh", "make_local_mesh", "HW",
           "Hardware"]


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto: the sharding rules place
    arrays and the compiler propagates, so no axis is Explicit."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model: int = 1):
    """Tiny mesh over whatever devices exist (tests / examples)."""
    n = len(jax.devices())
    assert n % model == 0
    return make_mesh((n // model, model), ("data", "model"))


@dataclasses.dataclass(frozen=True)
class Hardware:
    """TPU v5e-class chip (brief-provided constants)."""
    peak_flops_bf16: float = 197e12       # per chip
    hbm_bw: float = 819e9                  # B/s
    ici_link_bw: float = 50e9              # B/s per link
    hbm_bytes: float = 16e9                # capacity per chip
    ici_links_per_chip: int = 4            # 2-D torus (v5e)


HW = Hardware()
