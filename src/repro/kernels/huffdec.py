"""Pallas TPU kernel + jitted scan for batched canonical-Huffman decode.

The batched decoder (``repro.core.entropy``) splits into two device
stages over a stack of payload bitstreams sharing one codebook:

  windows   ``W[a, t]`` = the ``maxlen``-bit window of stream ``a``
            starting at bit ``t`` — ``maxlen`` shift-or passes over the
            stacked 0/1 bit matrix (elementwise VPU work, gridded over
            row tiles × bit-axis tiles);
  walk      a ``lax.scan`` advancing every stream in lockstep: gather
            each live stream's current window, one ``searchsorted`` over
            the left-justified canonical interval uppers yields the code
            length, then a table gather yields the codebook row index.

VMEM is bounded independently of the payload length: the bit axis is cut
into ``col_tile``-wide blocks, and each block reads the first 128 bits of
the next block as its halo (``maxlen`` ≤ 30 < 128), so one grid step
holds ``row_tile × (col_tile + 128)`` int32 of input.  The bit matrix
must therefore be ``(A, n·col_tile + 128)`` with ``A`` a multiple of
``row_tile`` — :func:`padded_shape` gives the shapes, and rounds them to
a few buckets per octave so that a serving process compiles a bounded
number of programs rather than one per request batch.

Windows are int32, so ``maxlen`` must stay ≤ 30 (the host engine guards
and falls back to the vectorized-numpy path).  The walk returns codebook
*row indices*, not symbol values — symbols are int64 and stay on the
host.  Error flags replicate the serial oracle exactly: 1 = truncated
(stream ends mid-codeword, or the codeword-free gap is hit with fewer
than ``maxlen + 1`` bits left), 2 = corrupt (gap hit with enough bits
left for the oracle's ``l > maxlen`` check to fire).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["windows_kernel", "huffdec_windows", "decode_walk", "bucket",
           "padded_shape", "ROW_TILE", "COL_TILE", "HALO"]

ROW_TILE = 8
COL_TILE = 2048
HALO = 128


def bucket(n: int, floor: int) -> int:
    """``n`` rounded up to one of four steps per octave (≥ ``floor``):
    at most 25 % padding, and O(log n) distinct sizes."""
    n = max(int(n), int(floor))
    step = max(int(floor), 1 << max(n.bit_length() - 3, 0))
    return -(-n // step) * step


def padded_shape(rows: int, width: int) -> tuple[int, int, int]:
    """``(rows, out_width, col_tile)`` for ``rows`` streams whose windows
    are needed at bit positions ``[0, width)``; the bit matrix is then
    ``(rows, out_width + HALO)``."""
    rows = bucket(rows, ROW_TILE)
    rows = -(-rows // ROW_TILE) * ROW_TILE
    w = bucket(width, HALO)
    if w <= COL_TILE:
        return rows, w, w
    return rows, bucket(-(-width // COL_TILE), 1) * COL_TILE, COL_TILE


def windows_kernel(cur_ref, halo_ref, out_ref, *, maxlen: int):
    b = jnp.concatenate([cur_ref[...], halo_ref[...]], axis=1)
    out_w = out_ref.shape[1]
    w = jnp.zeros(out_ref.shape, jnp.int32)
    for j in range(maxlen):
        w = (w << 1) | b[:, j:j + out_w]
    out_ref[...] = w


@functools.partial(jax.jit,
                   static_argnames=("maxlen", "col_tile", "row_tile",
                                    "interpret"))
def huffdec_windows(bits: jnp.ndarray, *, maxlen: int, col_tile: int,
                    row_tile: int = ROW_TILE, interpret: bool = True
                    ) -> jnp.ndarray:
    """All ``maxlen``-bit windows of a stacked 0/1 bit matrix.

    ``bits`` is ``(A, n·col_tile + HALO)`` uint8/int32 with zeros past
    each row's real bits and ``A`` a multiple of ``row_tile``; returns
    ``(A, n·col_tile)`` int32 windows.
    """
    a, in_w = bits.shape
    n_cols = (in_w - HALO) // col_tile
    if (a % row_tile or n_cols < 1 or in_w != n_cols * col_tile + HALO
            or col_tile % HALO or maxlen > HALO):
        raise ValueError(f"bit matrix {bits.shape} does not tile as "
                         f"({row_tile}, {col_tile}) + {HALO} halo")
    halo_step = col_tile // HALO
    return pl.pallas_call(
        functools.partial(windows_kernel, maxlen=maxlen),
        grid=(a // row_tile, n_cols),
        in_specs=[pl.BlockSpec((row_tile, col_tile), lambda i, j: (i, j)),
                  pl.BlockSpec((row_tile, HALO),
                               lambda i, j: (i, (j + 1) * halo_step))],
        out_specs=pl.BlockSpec((row_tile, col_tile), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((a, n_cols * col_tile), jnp.int32),
        interpret=interpret,
        name="huffdec_windows",
    )(bits.astype(jnp.int32), bits.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("maxlen", "steps"))
def decode_walk(wm: jnp.ndarray, nbits: jnp.ndarray, ncodes: jnp.ndarray,
                uppers: jnp.ndarray, n_lens: jnp.ndarray,
                lens_tab: jnp.ndarray, fc_tab: jnp.ndarray,
                fi_tab: jnp.ndarray, *, maxlen: int, steps: int):
    """Lockstep canonical walk over precomputed windows.

    ``uppers``/``lens_tab`` hold ``n_lens`` live entries; ``uppers`` is
    padded past them with values above any window, so one table shape
    serves every codebook.  Returns ``(sidx, err)``: (A, steps) int32
    codebook row indices (0 in dead/error lanes) and the (A,) int32
    per-stream error kind.
    """
    a = wm.shape[0]

    def step(carry, k):
        pos, err = carry
        act = (k < ncodes) & (err == 0)
        w = jnp.take_along_axis(wm, pos[:, None], axis=1)[:, 0]
        ii = jnp.searchsorted(uppers, w, side="right")
        valid = ii < n_lens
        l = lens_tab[jnp.minimum(ii, n_lens - 1)]
        rem = nbits - pos
        ok = act & valid & (l <= rem)
        corrupt = act & ~valid & (rem >= maxlen + 1)
        failed = act & ~ok
        sidx = fi_tab[l] + (w >> (maxlen - l)) - fc_tab[l]
        outk = jnp.where(ok, sidx, 0)
        err = jnp.where(failed, jnp.where(corrupt, 2, 1), err)
        pos = jnp.where(ok, pos + l, pos)
        return (pos, err), outk

    init = (jnp.zeros(a, jnp.int32), jnp.zeros(a, jnp.int32))
    (_, err), outs = jax.lax.scan(step, init, jnp.arange(steps))
    return outs.T, err
