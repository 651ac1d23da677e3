"""Public jit'd entry points for the Pallas kernels.

``interpret`` defaults to True when no TPU is attached (this container is
CPU-only; the kernels target TPU v5e), and to False on real TPU backends.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import hist as _hist
from . import huffdec as _huffdec
from . import lorenzo3d as _lorenzo3d
from . import qdq as _qdq

__all__ = ["lorenzo3d_codes", "lorenzo3d_recon",
           "lorenzo3d_codes_batched", "lorenzo3d_recon_batched", "hist",
           "huffdec_windows", "group_quant", "group_dequant",
           "default_interpret"]


def default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def lorenzo3d_codes(x, *, eb: float, tile=(8, 128, 128),
                    interpret: bool | None = None):
    return _lorenzo3d.lorenzo3d_codes(
        x, eb=eb, tile=tile,
        interpret=default_interpret() if interpret is None else interpret)


def lorenzo3d_recon(codes, *, eb: float, tile=(8, 128, 128),
                    interpret: bool | None = None):
    return _lorenzo3d.lorenzo3d_recon(
        codes, eb=eb, tile=tile,
        interpret=default_interpret() if interpret is None else interpret)


def lorenzo3d_codes_batched(x, *, eb: float, tile=(8, 128, 128),
                            interpret: bool | None = None):
    """Batched (N, X, Y, Z) fused prequant+Lorenzo — the SHE hot path."""
    return _lorenzo3d.lorenzo3d_codes_batched(
        x, eb=eb, tile=tile,
        interpret=default_interpret() if interpret is None else interpret)


def lorenzo3d_recon_batched(codes, *, eb: float, tile=(8, 128, 128),
                            interpret: bool | None = None):
    return _lorenzo3d.lorenzo3d_recon_batched(
        codes, eb=eb, tile=tile,
        interpret=default_interpret() if interpret is None else interpret)


def hist(codes, *, n_bins: int = 1024, chunk: int = 8192,
         interpret: bool | None = None):
    return _hist.hist(
        codes, n_bins=n_bins, chunk=chunk,
        interpret=default_interpret() if interpret is None else interpret)


def huffdec_windows(bits, *, maxlen: int, col_tile: int,
                    row_tile: int = _huffdec.ROW_TILE,
                    interpret: bool | None = None):
    """Stacked maxlen-bit windows for batched canonical-Huffman decode
    (``bits`` shaped by ``huffdec.padded_shape``)."""
    return _huffdec.huffdec_windows(
        bits, maxlen=maxlen, col_tile=col_tile, row_tile=row_tile,
        interpret=default_interpret() if interpret is None else interpret)


def group_quant(x, *, group: int = 128, row_tile: int = 256,
                interpret: bool | None = None):
    return _qdq.group_quant(
        x, group=group, row_tile=row_tile,
        interpret=default_interpret() if interpret is None else interpret)


def group_dequant(q, scale, *, group: int = 128, row_tile: int = 256,
                  interpret: bool | None = None):
    return _qdq.group_dequant(
        q, scale, group=group, row_tile=row_tile,
        interpret=default_interpret() if interpret is None else interpret)
