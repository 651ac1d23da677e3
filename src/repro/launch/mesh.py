"""Production mesh construction (assignment brief: MULTI-POD DRY-RUN §1).

A function, not a module-level constant — importing this module never
touches jax device state.  Hardware model: TPU v5e pods, 256 chips/pod,
(data, model) = (16, 16) per pod; the multi-pod mesh adds a leading "pod"
axis across the (slow) DCN/inter-pod links.
"""
from __future__ import annotations

import jax

__all__ = ["make_production_mesh", "make_smoke_mesh", "HARDWARE"]

# TPU v5e constants for the roofline (assignment brief §ROOFLINE ANALYSIS)
HARDWARE = {
    "peak_flops_bf16": 197e12,     # FLOP/s per chip
    "hbm_bw": 819e9,               # B/s per chip
    "ici_bw": 50e9,                # B/s per link
    "hbm_per_chip": 16e9,          # bytes
}


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_smoke_mesh():
    """1-device mesh with the production axis names (CPU tests)."""
    return jax.make_mesh((1, 1), ("data", "model"), axis_types=_auto(2))


def _auto(n: int) -> tuple:
    # sharding rules here rely on Auto propagation (P.UNCONSTRAINED specs,
    # vocab-sharded gathers); jax.make_mesh defaults to Explicit axes
    return (jax.sharding.AxisType.Auto,) * n
