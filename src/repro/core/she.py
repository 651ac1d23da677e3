"""Shared Huffman Encoding (SHE) — paper §III-D, Algorithm 4.

The partition strategies can emit thousands of small sub-blocks.  Vanilla
SZ must then either (a) merge them into 4D arrays — prediction crosses
non-adjacent block boundaries and collapses (TAC's weakness) — or
(b) compress each block separately — one Huffman tree *per block*, whose
serialized codebooks dominate the output.

SHE does the paper's third thing: **predict and quantize every block
independently** (restoring Lorenzo/regression locality), then aggregate all
blocks' quantization codes and regression coefficients and encode them with
**one shared Huffman tree**.

Batched pipeline (the default, ``batched=True``): sub-blocks are grouped by
shape, each group stacked into a 4D batch and run through the vectorized
Lor/Reg compressor (:func:`repro.core.sz.compress_lor_reg_batched` — one
fused prequant+Lorenzo + one batched plane-fit per group instead of one
Python-level compressor call per brick), then a **single aggregated
histogram** over all bricks' codes feeds one shared codebook build.  The
sequential per-brick loop is kept as the reference oracle (``batched=False``)
and the two paths are bit-identical — same codes, same reconstructions,
same size accounting (property-tested in ``tests/test_she_batched.py``).

``she_encode`` returns exact bit accounting for all three variants so the
benchmarks can reproduce Figs. 15/16:

  * ``shared``    — SHE (one codebook, per-block payload bits summed)
  * ``per_block`` — one codebook per block (the overhead SHE removes)
  * the caller gets per-block code streams back for the merged-4D
    comparison.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from . import entropy, huffman
from .compat import HAVE_ZSTD, zstd_size_bits
from .sz import SZResult, compress_lor_reg, compress_lor_reg_batched
from ..obs import metrics as obsm

__all__ = ["SHEResult", "she_encode", "aggregate_histogram",
           "encode_brick_payloads", "decode_brick_payloads"]

# Above this code span the dense histogram would be larger than the unique
# pass it replaces; fall back to np.unique (outlier-heavy streams only).
_MAX_HIST_SPAN = 1 << 22
# The one-hot-matmul kernel materializes (chunk, span) tiles, so its span
# budget is far smaller than the dense bincount's; wider streams fall back.
_MAX_PALLAS_SPAN = 1 << 14


@dataclass
class SHEResult:
    results: list[SZResult]       # per-brick prediction results (recon etc.)
    payload_bits: int             # Σ per-brick payloads under the codebook
    codebook_bits: int
    meta_bits: int                # per-brick prediction side info + counts
    codebook: huffman.Codebook

    @property
    def total_bits(self) -> int:
        return int(self.payload_bits + self.codebook_bits + self.meta_bits)


def aggregate_histogram(codes: np.ndarray, *, engine: str = "numpy",
                        ) -> tuple[np.ndarray, np.ndarray]:
    """(symbols, freqs) of the pooled code stream — Alg. 4's one histogram.

    ``engine="numpy"`` uses a dense ``bincount`` over the shifted code range
    (host path).  ``engine="pallas"`` routes the counting through the
    one-hot-matmul histogram kernel (``repro.kernels.hist``) — the on-device
    formulation used when the prediction stage already ran on the TPU.
    Both return exactly what ``np.unique(codes, return_counts=True)`` would,
    so the downstream codebook is independent of the engine.
    """
    if engine not in ("numpy", "pallas"):
        raise ValueError(f"unknown histogram engine {engine!r}")
    codes = np.asarray(codes).ravel()
    if codes.size == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    lo = int(codes.min())
    span = int(codes.max()) - lo + 1
    if span > _MAX_HIST_SPAN:
        return np.unique(codes, return_counts=True)
    if engine == "pallas" and span <= _MAX_PALLAS_SPAN:
        from repro.kernels import ops

        n_bins = -(-span // 128) * 128  # pad: hist tiles are 128-lane wide
        counts = np.asarray(ops.hist((codes - lo).astype(np.int32),
                                     n_bins=n_bins)).astype(np.int64)
    else:
        counts = np.bincount(codes - lo, minlength=span)
    nz = np.flatnonzero(counts)
    return nz + lo, counts[nz]


def _shared_entropy_stage(results: list[SZResult], *, use_zstd: bool,
                          engine: str, entropy_engine: str = "auto",
                          ) -> tuple[int, int, huffman.Codebook]:
    """One histogram → one codebook → one encoder launch → one zstd pass.

    The Huffman payload is priced exactly from the per-occurrence code
    lengths (``sum == encode(...)[1]``); the packed bitstream is only
    materialized when a zstd pass will actually consume it.
    """
    with obsm.timed(obsm.COMPRESS_STAGE_SECONDS.labels("entropy"),
                    "entropy", "layer.compress.entropy"):
        all_codes = (np.concatenate([r.codes for r in results])
                     if results else np.zeros(0, dtype=np.int64))
        symbols, freqs = aggregate_histogram(all_codes, engine=engine)
        cb = huffman.build_codebook(symbols=symbols, freqs=freqs)
        # one symbol-index pass prices the stream AND feeds the encoder
        idx = (huffman.symbol_indices(cb, all_codes.astype(np.int64))
               if all_codes.size else np.zeros(0, np.int64))
        lengths = cb.lengths[idx]
        payload = int(lengths.sum())
        if use_zstd and HAVE_ZSTD and payload:
            (blob, _), = entropy.get_engine(entropy_engine).encode_payloads(
                cb, [all_codes])
            zbits = zstd_size_bits(blob)
            if zbits is not None:
                payload = min(payload, zbits)
        # per-brick payloads (diagnostics only; totals use the shared
        # stream) — priced via the same vectorized lookup, split at brick
        # boundaries
        splits = np.cumsum([r.codes.size for r in results])[:-1]
        for r, chunk in zip(results, np.split(lengths, splits)):
            r.payload_bits = int(chunk.sum())
        return int(payload), huffman.codebook_size_bits(cb), cb


def encode_brick_payloads(cb: huffman.Codebook,
                          codes_list: list[np.ndarray], *,
                          engine: str = "auto") -> list[tuple[bytes, int]]:
    """One byte-aligned packed bitstream per brick under the shared codebook.

    This is the TACZ container's payload framing: every sub-block's code
    stream is encoded (and byte-padded) *separately* so any sub-block can be
    decoded without touching its neighbors — the random-access property the
    ROI reader builds on.  Returns ``(payload bytes, nbits)`` per brick;
    ``nbits`` is exactly ``code_lengths_for(cb, codes).sum()``.

    Thin wrapper (kept for compatibility) over
    ``repro.core.entropy.EntropyEngine.encode_payloads`` — the batched
    engines pack the whole brick list in one offset-scatter pass; output
    bytes are identical for every ``engine``.
    """
    return entropy.get_engine(engine).encode_payloads(cb, codes_list)


def decode_brick_payloads(cb: huffman.Codebook,
                          payloads: list[tuple[bytes, int, int]], *,
                          engine: str = "auto") -> list[np.ndarray]:
    """Inverse of :func:`encode_brick_payloads` for a batch of bricks.

    ``payloads`` is a list of ``(payload bytes, nbits, n_codes)`` triples,
    all under the same shared codebook; returns the int64 code stream per
    brick; pair the recovered streams with ``sz.decode_codes_batched`` for
    vectorized reconstruction.

    Thin wrapper (kept for compatibility) over
    ``repro.core.entropy.EntropyEngine.decode_payloads`` — the batched
    engines replace the per-brick serial bit-walk with one lockstep
    canonical decode; outputs and error behavior match the serial oracle
    exactly for every ``engine``.
    """
    return entropy.get_engine(engine).decode_payloads(cb, payloads)


def she_encode(bricks: list[np.ndarray], eb: float, *, block: int = 6,
               shared: bool = True, use_zstd: bool = True,
               batched: bool = True, hist_engine: str = "numpy",
               lorenzo_engine: str = "auto",
               entropy_engine: str = "auto") -> SHEResult:
    """Compress a list of 3D/4D bricks with per-brick Lor/Reg prediction.

    ``shared=True``  → Algorithm 4: one Huffman tree over all bricks, one
    encoder launch, one lossless (zstd) pass over the whole bitstream.
    ``shared=False`` → the per-block baseline SHE replaces: one tree, one
    bitstream, one lossless pass *per brick* (the per-launch overhead the
    paper measures against).

    ``batched=True`` (default) vectorizes the prediction stage over
    same-shape groups of bricks and builds the shared codebook from one
    aggregated histogram; ``batched=False`` is the sequential per-brick
    reference path.  Outputs are bit-identical either way *on the numpy
    Lorenzo engine* (the CPU default).  ``lorenzo_engine="auto"`` routes
    the batched Lorenzo branch through the float32 Pallas kernel when a
    TPU is attached — codes there may differ from the float64 oracle in
    half-integer rounding; pass ``lorenzo_engine="numpy"`` to force
    bit-exactness on any backend.  ``entropy_engine`` selects the
    :mod:`repro.core.entropy` engine used when the zstd pass sizes the
    pooled bitstream — all entropy engines are bit-identical, so this
    only affects speed.
    """
    if batched:
        results: list[SZResult | None] = [None] * len(bricks)
        groups: dict[tuple[int, ...], list[int]] = {}
        with obsm.timed(obsm.COMPRESS_STAGE_SECONDS.labels("gather"),
                        "gather", "layer.compress.gather"):
            for i, brk in enumerate(bricks):
                brk = np.asarray(brk)
                if brk.ndim == 3:
                    groups.setdefault(brk.shape, []).append(i)
                else:  # rare 4D bricks keep the reference per-brick path
                    results[i] = compress_lor_reg(brk, eb, block=block,
                                                  count_entropy=False)
        for shape, idxs in groups.items():
            with obsm.timed(obsm.COMPRESS_STAGE_SECONDS.labels("gather"),
                            "gather", "layer.compress.gather"):
                stack = np.stack([np.asarray(bricks[i]) for i in idxs])
            for i, r in zip(idxs, compress_lor_reg_batched(
                    stack, eb, block=block, engine=lorenzo_engine)):
                results[i] = r
    else:
        results = [compress_lor_reg(b, eb, block=block, count_entropy=False)
                   for b in bricks]
    meta = sum(r.meta_bits for r in results)
    # stream-splitting info: #codes per brick (32 bit each)
    meta += 32 * len(results)
    if shared:
        payload, cb_bits, cb = _shared_entropy_stage(
            results, use_zstd=use_zstd, engine=hist_engine,
            entropy_engine=entropy_engine)
    else:
        payload = 0
        cb_bits = 0
        cb = None
        for r in results:
            # per-block baseline: one codebook per brick, so there is no
            # shared-codebook batch to form — the single-stream surface
            # is the right one here
            rcb = huffman.build_codebook(r.codes)
            packed, nbits = entropy.encode_stream(rcb, r.codes)
            bits = nbits
            if use_zstd and nbits:
                zbits = zstd_size_bits(packed.tobytes())
                if zbits is not None:
                    bits = min(bits, zbits)
            payload += bits
            cb_bits += huffman.codebook_size_bits(rcb)
            r.payload_bits = bits
            r.codebook_bits = huffman.codebook_size_bits(rcb)
    return SHEResult(results=results, payload_bits=int(payload),
                     codebook_bits=int(cb_bits), meta_bits=int(meta),
                     codebook=cb)
