"""Pallas TPU kernel: fused prequant + 3D Lorenzo delta (and its inverse).

This is the compression hot loop of the SZ pipeline (paper §II-A steps 1–2)
in its dual-quantization form (DESIGN.md §3): per element
``q = round(x·(1/2eb))`` followed by the integer 3D Lorenzo delta.  On the
TPU this is pure VPU element-wise work; the whole tile lives in VMEM.

Tiling contract: the kernel's grid tiles are *bricks* — each tile computes
a self-contained zero-halo Lorenzo, which is exactly the per-sub-block
independence the SHE pipeline requires (each partition sub-block predicted
on its own, paper Alg. 4 line 4).  Tile shape must therefore match the
brick shape the caller compresses; the default (8, 128, 128) fits
8·128·128·4 B · 3 buffers ≈ 1.6 MB of VMEM.

The inverse kernel reconstructs ``x̂ = 2eb · cumsum³(codes)`` — exact in
integers, so the error bound is the prequant bound.  Mosaic has no
``cumsum`` lowering, so each inclusive scan is a log-step shift-and-add
(the same zero-fill shifts the forward kernel subtracts).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["lorenzo3d_codes_kernel", "lorenzo3d_recon_kernel",
           "lorenzo3d_codes", "lorenzo3d_recon",
           "lorenzo3d_codes_batched", "lorenzo3d_recon_batched"]


def _shift(c, ax: int, s: int = 1):
    """``c`` shifted by ``s`` toward higher indices along ``ax``, zero-filled
    at the low face."""
    n = c.shape[ax]
    return jnp.pad(c, [(s, 0) if a == ax else (0, 0)
                       for a in range(c.ndim)])[
        tuple(slice(0, n) if a == ax else slice(None)
              for a in range(c.ndim))]


def _inclusive_scan(q, ax: int):
    """Integer inclusive prefix sum along ``ax`` by log-step shift-and-add
    (Hillis–Steele): exact in int32, VPU-only."""
    s = 1
    while s < q.shape[ax]:
        q = q + _shift(q, ax, s)
        s *= 2
    return q


def lorenzo3d_codes_kernel(x_ref, codes_ref, *, inv_2eb: float):
    """One VMEM tile: prequant then zero-halo 3D Lorenzo delta."""
    x = x_ref[...]
    q = jnp.rint(x * inv_2eb).astype(jnp.int32)
    # alternating first differences with a zero halo on the low faces;
    # implemented as shift-and-subtract (VPU-only, no gathers)
    c = q
    for ax in range(3):
        c = c - _shift(c, ax)
    codes_ref[...] = c


def lorenzo3d_recon_kernel(codes_ref, x_ref, *, two_eb: float):
    """Inverse tile: integer 3D inclusive scan, then dequantize."""
    q = codes_ref[...].astype(jnp.int32)
    for ax in range(3):
        q = _inclusive_scan(q, ax)
    x_ref[...] = q.astype(jnp.float32) * two_eb


def _grid_and_specs(shape, tile):
    tile = tuple(min(t, s) for t, s in zip(tile, shape))
    if any(s % t for s, t in zip(shape, tile)):
        raise ValueError(f"shape {shape} not divisible by tile {tile}")
    grid = tuple(s // t for s, t in zip(shape, tile))
    spec = pl.BlockSpec(tile, lambda i, j, k: (i, j, k))
    return grid, spec, tile


@functools.partial(jax.jit, static_argnames=("eb", "tile", "interpret"))
def lorenzo3d_codes(x: jnp.ndarray, *, eb: float,
                    tile: tuple[int, int, int] = (8, 128, 128),
                    interpret: bool = True) -> jnp.ndarray:
    """Fused prequant + 3D Lorenzo codes for a 3D array (tile = brick)."""
    grid, spec, tile = _grid_and_specs(x.shape, tile)
    kernel = functools.partial(lorenzo3d_codes_kernel,
                               inv_2eb=float(1.0 / (2.0 * eb)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32),
        interpret=interpret,
    )(x.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("eb", "tile", "interpret"))
def lorenzo3d_recon(codes: jnp.ndarray, *, eb: float,
                    tile: tuple[int, int, int] = (8, 128, 128),
                    interpret: bool = True) -> jnp.ndarray:
    grid, spec, tile = _grid_and_specs(codes.shape, tile)
    kernel = functools.partial(lorenzo3d_recon_kernel,
                               two_eb=float(2.0 * eb))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(codes.shape, jnp.float32),
        interpret=interpret,
    )(codes.astype(jnp.int32))


# ------------------------- batched (SHE) variants ---------------------------
#
# SHE stacks same-shape sub-blocks into a (N, X, Y, Z) batch and compresses
# the whole batch in one launch.  The grid grows a leading batch axis with
# block size 1 and each spatial tile keeps its own zero halo, so every
# (brick, tile) cell is predicted fully independently — the per-sub-block
# independence of Alg. 4 line 4 is preserved *by the tiling contract*, not
# by the kernel body (which is the 3D body on a leading-singleton block).


def lorenzo3d_codes_batched_kernel(x_ref, codes_ref, *, inv_2eb: float):
    """One (1, tx, ty, tz) VMEM tile: prequant + zero-halo Lorenzo delta."""
    x = x_ref[...]
    c = jnp.rint(x * inv_2eb).astype(jnp.int32)
    for ax in (1, 2, 3):
        c = c - _shift(c, ax)
    codes_ref[...] = c


def lorenzo3d_recon_batched_kernel(codes_ref, x_ref, *, two_eb: float):
    q = codes_ref[...].astype(jnp.int32)
    for ax in (1, 2, 3):
        q = _inclusive_scan(q, ax)
    x_ref[...] = q.astype(jnp.float32) * two_eb


def _batched_grid_and_specs(shape, tile):
    if len(shape) != 4:
        raise ValueError(f"expected (N, X, Y, Z) batch, got shape {shape}")
    tile = (1,) + tuple(min(t, s) for t, s in zip(tile, shape[1:]))
    if any(s % t for s, t in zip(shape, tile)):
        raise ValueError(f"shape {shape} not divisible by tile {tile}")
    grid = tuple(s // t for s, t in zip(shape, tile))
    spec = pl.BlockSpec(tile, lambda n, i, j, k: (n, i, j, k))
    return grid, spec, tile


@functools.partial(jax.jit, static_argnames=("eb", "tile", "interpret"))
def lorenzo3d_codes_batched(x: jnp.ndarray, *, eb: float,
                            tile: tuple[int, int, int] = (8, 128, 128),
                            interpret: bool = True) -> jnp.ndarray:
    """Fused prequant + Lorenzo codes for a (N, X, Y, Z) batch of bricks."""
    grid, spec, tile = _batched_grid_and_specs(x.shape, tile)
    kernel = functools.partial(lorenzo3d_codes_batched_kernel,
                               inv_2eb=float(1.0 / (2.0 * eb)))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.int32),
        interpret=interpret,
    )(x.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("eb", "tile", "interpret"))
def lorenzo3d_recon_batched(codes: jnp.ndarray, *, eb: float,
                            tile: tuple[int, int, int] = (8, 128, 128),
                            interpret: bool = True) -> jnp.ndarray:
    grid, spec, tile = _batched_grid_and_specs(codes.shape, tile)
    kernel = functools.partial(lorenzo3d_recon_batched_kernel,
                               two_eb=float(2.0 * eb))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(codes.shape, jnp.float32),
        interpret=interpret,
    )(codes.astype(jnp.int32))
