"""Production mesh + TPU v5e hardware model.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — the dry-run sets the
512-placeholder-device flag before any jax import, and everything else
(tests, benches) sees the real single CPU device.
"""

from __future__ import annotations

import dataclasses

import jax


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis Auto-typed (sharding propagated
    by the compiler, as the model code assumes)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


@dataclasses.dataclass(frozen=True)
class Hardware:
    """TPU v5e chip model (the lowering TARGET; this container is CPU)."""
    name: str = "tpu-v5e"
    peak_flops: float = 197e12       # bf16 FLOP/s per chip
    hbm_bw: float = 819e9            # bytes/s per chip
    ici_bw: float = 50e9             # bytes/s per link (~per-chip budget)
    hbm_bytes: float = 16e9


V5E = Hardware()
