"""SZ error-bounded lossy compression core (paper §II-A), TPU-adapted.

The paper builds on two SZ algorithm families:

  * **Lor/Reg** (SZ2, [15]): block the data into 6³ blocks, per block pick a
    Lorenzo predictor or a linear-regression (plane-fit) predictor, quantize
    the prediction residual against the user error bound, Huffman-encode.
  * **Interp** (SZ3, [34]): global multi-level interpolation across the
    whole array, residual quantization, Huffman.

Hardware adaptation (DESIGN.md §3): classic SZ predicts from previously
*reconstructed* values — a loop-carried dependency in all three dims that
cannot be vectorized on the TPU VPU/MXU.  We use the established
**dual-quantization** parallelization (cuSZ): first pre-quantize
``q = round(x / (2·eb))`` element-wise (so ``|x − 2·eb·q| ≤ eb`` is already
guaranteed), then predict on the *exact integer grid* ``q`` — Lorenzo deltas
and interpolation residuals on integers are lossless, so the final error
bound is exactly the pre-quantization bound.  Every stage is now
embarrassingly parallel; the Pallas kernel in ``repro.kernels.lorenzo3d``
implements the fused prequant+delta hot loop for TPU.

Three compressors, one result type:

  * :func:`compress_lorenzo`   — global N-D Lorenzo on the integer grid
    (used on GSP-padded full grids and on TAC's merged 4D arrays, where the
    paper's cross-block-boundary artifact appears *by construction*).
  * :func:`compress_lor_reg`   — per-block self-contained Lorenzo-vs-
    regression with per-block choice: the faithful SZ2 analogue and the
    prediction stage of SHE (each block predicted independently, paper
    Alg. 4 line 4).
  * :func:`compress_interp`    — global multi-level linear interpolation on
    the integer grid: the faithful SZ3 "Interp" analogue.

Entropy stage: canonical Huffman (``repro.core.huffman``) + optional
Zstandard pass over the packed bitstream, exactly SZ's huffman+lossless
pipeline.  All sizes are measured from materialized bitstreams — no
estimated compression ratios.
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from . import huffman
from .compat import zstd_size_bits
from ..obs import metrics as obsm

__all__ = [
    "SZResult",
    "prequant",
    "lorenzo_nd_codes",
    "lorenzo_nd_recon",
    "interp_nd_codes",
    "interp_nd_recon",
    "compress_lorenzo",
    "compress_lor_reg",
    "compress_lor_reg_batched",
    "compress_interp",
    "decode_codes",
    "decode_codes_batched",
    "entropy_bits",
    "entropy_stage",
    "reg_block_grid",
]

# --------------------------------------------------------------------------
# result container
# --------------------------------------------------------------------------


@dataclass
class SZResult:
    """One compressed array + exact storage accounting (bits)."""

    recon: np.ndarray          # reconstructed values (same shape as input)
    codes: np.ndarray          # int64 quantization-code stream (flattened)
    payload_bits: int          # entropy-coded code stream
    codebook_bits: int         # serialized Huffman codebook(s)
    meta_bits: int             # side info: coeffs, choices, dims, eb, ...
    eb: float
    method: str
    extras: dict = field(default_factory=dict)

    @property
    def total_bits(self) -> int:
        return int(self.payload_bits + self.codebook_bits + self.meta_bits)

    def compression_ratio(self, n_values: int | None = None,
                          dtype_bits: int = 32) -> float:
        n = int(np.prod(self.recon.shape)) if n_values is None else n_values
        return n * dtype_bits / max(self.total_bits, 1)


# --------------------------------------------------------------------------
# dual quantization
# --------------------------------------------------------------------------


def prequant(x: np.ndarray, eb: float) -> np.ndarray:
    """``q = round(x / (2 eb))`` — guarantees ``|x − 2 eb q| ≤ eb``.

    Precision note: the guarantee is exact in real arithmetic; the float32
    reconstruction adds at most one ulp of the value (≈ 2⁻²⁴·|x|), the same
    machine-precision caveat every SZ-family implementation carries for
    float32 outputs.  Tests assert ``err ≤ eb + 2⁻²²·max|x|``.
    """
    if eb <= 0:
        raise ValueError("error bound must be positive")
    return np.rint(np.asarray(x, dtype=np.float64) / (2.0 * eb)).astype(np.int64)


def dequant(q: np.ndarray, eb: float) -> np.ndarray:
    return (np.asarray(q, dtype=np.float64) * (2.0 * eb)).astype(np.float32)


# --------------------------------------------------------------------------
# N-D Lorenzo on the integer grid
# --------------------------------------------------------------------------


def lorenzo_nd_codes(q: np.ndarray, axes: tuple[int, ...] | None = None) -> np.ndarray:
    """Exact integer N-D Lorenzo delta: alternating first differences.

    Composing ``diff`` with zero-prepend along each axis yields the
    (-1)^(a+b+c) corner formula of the 3D Lorenzo predictor; it is its own
    generalization in any rank (the paper's 4D merged arrays included).
    """
    c = np.asarray(q, dtype=np.int64)
    axes = tuple(range(c.ndim)) if axes is None else axes
    for ax in axes:
        c = np.diff(c, axis=ax, prepend=0)
    return c


def lorenzo_nd_recon(codes: np.ndarray, axes: tuple[int, ...] | None = None) -> np.ndarray:
    """Inverse Lorenzo: N-D inclusive prefix sum (exact in integers)."""
    qr = np.asarray(codes, dtype=np.int64)
    axes = tuple(range(qr.ndim)) if axes is None else axes
    for ax in axes:
        qr = np.cumsum(qr, axis=ax)
    return qr


# --------------------------------------------------------------------------
# N-D multi-level interpolation on the integer grid (SZ3 "Interp")
# --------------------------------------------------------------------------


def _interp_schedule(shape: tuple[int, ...]) -> list[tuple[int, int]]:
    """(axis, stride) stages, coarsest level first, mirroring SZ3's
    level-by-level, axis-by-axis interpolation order."""
    max_dim = max(shape)
    s = 1
    while s < max_dim:
        s *= 2
    stages = []
    while s >= 2:
        for ax in range(len(shape)):
            stages.append((ax, s))
        s //= 2
    return stages


def _interp_stage_indices(dim: int, stride: int):
    """Midpoint + 4-point stencil indices for one axis stage.

    Returns (mids, left, right, left2, right2, cubic_ok): interior
    midpoints use SZ3's cubic spline stencil (−a + 9b + 9c − d)/16 over the
    known lattice at ±s/2 and ±3s/2; boundary midpoints degrade to linear,
    and a missing right neighbor degrades to copy-left.
    """
    half = stride // 2
    mids = np.arange(half, dim, stride)
    left = mids - half
    right_raw = mids + half
    has_right = right_raw < dim
    right = np.where(has_right, np.minimum(right_raw, dim - 1), left)
    left2_raw = mids - 3 * half
    right2_raw = mids + 3 * half
    cubic_ok = (left2_raw >= 0) & (right2_raw < dim) & has_right
    left2 = np.where(cubic_ok, np.maximum(left2_raw, 0), left)
    right2 = np.where(cubic_ok, np.minimum(right2_raw, dim - 1), right)
    return mids, left, right, left2, right2, cubic_ok


def _interp_predict(q: np.ndarray, ax: int, left, right, left2, right2,
                    cubic_ok) -> np.ndarray:
    """Replayable integer prediction: cubic where the stencil fits, else
    linear (floor averages — identical on encoder and decoder)."""
    ql = np.take(q, left, axis=ax)
    qr = np.take(q, right, axis=ax)
    lin = (ql + qr) >> 1
    qa = np.take(q, left2, axis=ax)
    qd = np.take(q, right2, axis=ax)
    # round-to-nearest of (−a + 9b + 9c − d)/16, exact in integers
    cub = (-qa + 9 * ql + 9 * qr - qd + 8) >> 4
    shape = [1] * q.ndim
    shape[ax] = len(cubic_ok)
    sel = cubic_ok.reshape(shape)
    return np.where(sel, cub, lin)


def interp_nd_codes(q: np.ndarray) -> np.ndarray:
    """Residual codes for global multi-level linear interpolation.

    Because prediction happens on the exact integer grid (dual-quant), the
    encoder needs no sequential reconstruction: every stage's predictors are
    true ``q`` values that the decoder will have recovered exactly.
    """
    q = np.asarray(q, dtype=np.int64)
    codes = q.copy()  # anchor points keep code == q (pred 0)
    # strides per axis, tracking the known lattice
    for ax, stride in _interp_schedule(q.shape):
        mids, left, right, left2, right2, cubic_ok = _interp_stage_indices(
            q.shape[ax], stride)
        if mids.size == 0:
            continue
        qm = np.take(q, mids, axis=ax)
        pred = _interp_predict(q, ax, left, right, left2, right2, cubic_ok)
        # write residuals at the midpoints; *but only at positions whose
        # other-axis indices are on the currently-known lattice* — handled
        # implicitly: stages for other axes overwrite later at finer strides,
        # and the final value each cell keeps is from the unique stage that
        # defines it (odd-multiple decomposition is unique).
        idx = [slice(None)] * q.ndim
        idx[ax] = mids
        codes[tuple(idx)] = qm - pred
    return codes


def interp_nd_recon(codes: np.ndarray) -> np.ndarray:
    """Decoder replay of :func:`interp_nd_codes` (exact)."""
    codes = np.asarray(codes, dtype=np.int64)
    q = codes.copy()  # anchors are already correct
    for ax, stride in _interp_schedule(codes.shape):
        mids, left, right, left2, right2, cubic_ok = _interp_stage_indices(
            codes.shape[ax], stride)
        if mids.size == 0:
            continue
        pred = _interp_predict(q, ax, left, right, left2, right2, cubic_ok)
        idx = [slice(None)] * codes.ndim
        idx[ax] = mids
        q[tuple(idx)] = pred + codes[tuple(idx)]
    return q


# --------------------------------------------------------------------------
# entropy stage: Huffman (+ optional zstd), real bitstreams
# --------------------------------------------------------------------------


def entropy_stage(codes: np.ndarray, *, use_zstd: bool = True,
                  codebook: huffman.Codebook | None = None,
                  engine: str = "auto") -> tuple[int, int, dict]:
    """(payload_bits, codebook_bits, artifacts) from a materialized bitstream.

    ``artifacts`` carries the codebook and the packed Huffman payload
    (``{"codebook", "packed", "nbits"}``) that pricing already materialized.
    The compressor front-ends stash it on ``SZResult.extras["entropy"]`` so
    the TACZ write path (``repro.io.writer.pack_level``) can serialize
    GSP/global levels without re-building the codebook and re-encoding the
    exact same payload (ROADMAP memoization item).  Retention note: the
    payload bytes are a small fraction of the ``codes`` array every
    SZResult already pins (int64 per value vs the entropy-coded stream),
    so accounting-only sweeps are not meaningfully taxed.

    Thin wrapper (kept for compatibility) over
    ``repro.core.entropy.EntropyEngine.encode_payloads`` for its single
    pooled stream; all engines produce identical bytes, so ``engine``
    only affects speed.
    """
    from . import entropy as _entropy

    codes = np.asarray(codes).ravel()
    if codes.size == 0:
        return 0, 0, {"codebook": None, "packed": b"", "nbits": 0}
    cb = codebook if codebook is not None else huffman.build_codebook(codes)
    (blob, nbits), = _entropy.get_engine(engine).encode_payloads(cb, [codes])
    payload = nbits
    if use_zstd:
        zbits = zstd_size_bits(blob)
        if zbits is not None:
            payload = min(payload, zbits)
    cb_bits = 0 if codebook is not None else huffman.codebook_size_bits(cb)
    return int(payload), int(cb_bits), {"codebook": cb, "packed": blob,
                                        "nbits": int(nbits)}


def entropy_bits(codes: np.ndarray, *, use_zstd: bool = True,
                 codebook: huffman.Codebook | None = None) -> tuple[int, int]:
    """(payload_bits, codebook_bits) from a materialized bitstream."""
    payload, cb_bits, _ = entropy_stage(codes, use_zstd=use_zstd,
                                        codebook=codebook)
    return payload, cb_bits


_DIM_META_BITS = 3 * 32 + 64  # dims + eb


# --------------------------------------------------------------------------
# compressor front-ends
# --------------------------------------------------------------------------


def compress_lorenzo(x: np.ndarray, eb: float, *, use_zstd: bool = True,
                     codebook: huffman.Codebook | None = None,
                     entropy_engine: str = "auto") -> SZResult:
    """Global N-D dual-quant Lorenzo (the TPU-kernel-backed path)."""
    x = np.asarray(x)
    q = prequant(x, eb)
    codes = lorenzo_nd_codes(q)
    payload, cb_bits, ent = entropy_stage(codes, use_zstd=use_zstd,
                                          codebook=codebook,
                                          engine=entropy_engine)
    recon = dequant(lorenzo_nd_recon(codes), eb).reshape(x.shape)
    return SZResult(recon=recon, codes=codes.ravel(), payload_bits=payload,
                    codebook_bits=cb_bits, meta_bits=_DIM_META_BITS, eb=eb,
                    method="lorenzo", extras={"entropy": ent})


def compress_interp(x: np.ndarray, eb: float, *, use_zstd: bool = True,
                    codebook: huffman.Codebook | None = None,
                    entropy_engine: str = "auto") -> SZResult:
    """Global multi-level interpolation (faithful SZ3 'Interp' analogue)."""
    x = np.asarray(x)
    q = prequant(x, eb)
    codes = interp_nd_codes(q)
    payload, cb_bits, ent = entropy_stage(codes, use_zstd=use_zstd,
                                          codebook=codebook,
                                          engine=entropy_engine)
    recon = dequant(interp_nd_recon(codes), eb).reshape(x.shape)
    return SZResult(recon=recon, codes=codes.ravel(), payload_bits=payload,
                    codebook_bits=cb_bits, meta_bits=_DIM_META_BITS, eb=eb,
                    method="interp", extras={"entropy": ent})


# ---------------------------- Lor/Reg (SZ2) --------------------------------


def _block_view(a: np.ndarray, b: int) -> np.ndarray:
    """(X,Y,Z) → (bx,by,bz, b,b,b) view after edge-replication padding."""
    pads = [(0, (-s) % b) for s in a.shape]
    if any(p[1] for p in pads):
        a = np.pad(a, pads, mode="edge")
    bx, by, bz = (s // b for s in a.shape)
    return (a.reshape(bx, b, by, b, bz, b)
             .transpose(0, 2, 4, 1, 3, 5)), (bx, by, bz)


def reg_block_grid(shape: tuple[int, ...], block: int
                   ) -> tuple[int, tuple[int, ...]]:
    """(block edge b, blocked-grid shape) for a brick's regression branch.

    This derivation is load-bearing for serialized data: the encoder's
    code layout, :func:`decode_codes`, and the TACZ reader's betas/prefix
    arithmetic must all agree on it, so it lives in exactly one place.
    """
    b = min(block, min(shape)) if min(shape) >= 2 else 1
    return b, tuple(-(-s // b) for s in shape)


def _fit_from_betas(betas: np.ndarray, b: int) -> np.ndarray:
    """Replay the plane fit from stored float32 betas (exact float64 eval).

    Shared by the encoder and :func:`decode_codes`, so a regression brick
    reconstructed from serialized (betas, codes) is bit-identical to the
    encoder-side recon.
    """
    coord = np.arange(b, dtype=np.float64) - (b - 1) / 2.0
    bf = np.asarray(betas).astype(np.float64)
    return (bf[..., 0, None, None, None]
            + bf[..., 1, None, None, None] * coord[:, None, None]
            + bf[..., 2, None, None, None] * coord[None, :, None]
            + bf[..., 3, None, None, None] * coord[None, None, :])


def _regression_fit(xb: np.ndarray, b: int) -> tuple[np.ndarray, np.ndarray]:
    """Closed-form per-block plane fit f = β0 + β1 i + β2 j + β3 k.

    ``xb``: (..., b, b, b) blocks.  Returns (betas float32 (...,4), fit).
    Coordinates are centered so the normal equations are diagonal — this is
    a pure batched-``einsum`` computation (MXU-friendly, DESIGN.md §3).
    The fit is evaluated from the *float32-cast* betas so the decoder can
    replay it exactly from the serialized coefficients.
    """
    coord = np.arange(b, dtype=np.float64) - (b - 1) / 2.0
    var = float((coord ** 2).sum()) * b * b  # Σ over block of (i-ī)²
    mean = xb.mean(axis=(-3, -2, -1), keepdims=True)
    xc = xb.astype(np.float64) - mean
    b1 = np.einsum("...ijk,i->...", xc, coord) / var
    b2 = np.einsum("...ijk,j->...", xc, coord) / var
    b3 = np.einsum("...ijk,k->...", xc, coord) / var
    betas = np.stack([mean[..., 0, 0, 0], b1, b2, b3], axis=-1).astype(np.float32)
    return betas, _fit_from_betas(betas, b)


def _reg_recon(betas: np.ndarray, codes_reg: np.ndarray, b: int,
               bgrid: tuple[int, int, int], orig_shape: tuple[int, ...],
               eb: float) -> np.ndarray:
    """Regression-branch reconstruction from (betas, codes) — the decode
    path of the serialized container, and the exact recon the encoder uses."""
    bx, by, bz = bgrid
    fit = _fit_from_betas(betas, b)
    recon_b = (fit + 2.0 * eb * np.asarray(codes_reg, dtype=np.int64)
               ).astype(np.float32)
    recon = (recon_b.reshape(bx, by, bz, b, b, b)
                    .transpose(0, 3, 1, 4, 2, 5)
                    .reshape(bx * b, by * b, bz * b))
    return recon[tuple(slice(0, s) for s in orig_shape)]


def _code_cost_bits(codes: np.ndarray, axis) -> np.ndarray:
    """Cheap per-block Huffman-size proxy: Elias-gamma-like magnitude bits."""
    return np.log2(1.0 + 2.0 * np.abs(codes.astype(np.float64))).sum(axis=axis) + 1.0


def compress_lor_reg(x: np.ndarray, eb: float, *, block: int = 6,
                     use_zstd: bool = True,
                     codebook: huffman.Codebook | None = None,
                     count_entropy: bool = True,
                     entropy_engine: str = "auto") -> SZResult:
    """SZ2 "Lor/Reg" analogue: Lorenzo vs. linear regression, chosen
    adaptively — at *brick* granularity.

    Faithfulness note (DESIGN.md §3): SZ2 chooses Lorenzo-vs-regression per
    6³ block, with Lorenzo crossing block borders through previously
    *reconstructed* values.  Under dual-quantization a per-6³ mixed choice
    would make Lorenzo neighbors of regression blocks decoder-inexact (the
    reason cuSZ dropped the regression branch entirely on GPUs).  We keep
    both predictors but hoist the choice to the whole brick:

      * **Lorenzo branch** — global dual-quant Lorenzo over the brick
        (boundary cost only at the brick's own faces, which is exactly the
        independence SHE requires per partition sub-block);
      * **Regression branch** — per-``block³`` closed-form plane fits with
        residual quantization (self-contained, decoder-exact, batched
        einsum → MXU-friendly).

    The cheaper branch (estimated bits, regression pays 4×32-bit
    coefficients per block) wins; 1 branch bit per brick.

    With ``count_entropy=False`` the entropy stage is skipped (payload left
    at 0) so SHE can pool this brick's codes into a shared codebook.
    """
    x = np.asarray(x)
    orig_shape = x.shape
    if x.ndim != 3:
        # operate on trailing 3D bricks (merged 4D arrays supported)
        lead = int(np.prod(x.shape[:-3]))
        x3 = x.reshape((lead,) + x.shape[-3:])
        parts = [compress_lor_reg(x3[i], eb, block=block, use_zstd=False,
                                  codebook=codebook, count_entropy=False)
                 for i in range(lead)]
        codes = np.concatenate([p.codes for p in parts])
        meta = sum(p.meta_bits for p in parts)
        payload = cb_bits = 0
        extras4: dict = {}
        if count_entropy:
            payload, cb_bits, ent = entropy_stage(codes, use_zstd=use_zstd,
                                                  codebook=codebook,
                                                  engine=entropy_engine)
            extras4["entropy"] = ent
        recon = np.stack([p.recon for p in parts]).reshape(orig_shape)
        return SZResult(recon=recon, codes=codes, payload_bits=payload,
                        codebook_bits=cb_bits, meta_bits=meta, eb=eb,
                        method="lor_reg", extras=extras4)

    b, _ = reg_block_grid(x.shape, block)
    # --- Lorenzo branch: global dual-quant Lorenzo over the brick ----------
    q = prequant(x, eb)
    codes_lor = lorenzo_nd_codes(q)
    cost_lor = float(_code_cost_bits(codes_lor, axis=None))

    # --- Regression branch: per-block plane fits ----------------------------
    # A 1³ "plane fit" is degenerate (zero coordinate variance → NaN betas),
    # so Lorenzo wins by construction; skip the wasted fit entirely.
    use_reg = False
    if b >= 2:
        xb, bgrid = _block_view(x, b)
        betas, fit = _regression_fit(xb, b)
        codes_reg = np.rint((xb - fit) / (2.0 * eb)).astype(np.int64)
        n_blocks = int(np.prod(bgrid))
        cost_reg = (float(_code_cost_bits(codes_reg, axis=None))
                    + n_blocks * 4 * 32)
        use_reg = cost_reg < cost_lor

    if use_reg:
        recon = _reg_recon(betas, codes_reg, b, bgrid, orig_shape, eb)
        codes = codes_reg
        meta = _DIM_META_BITS + 1 + n_blocks * 4 * 32
        method = "lor_reg/reg"
        extras = {"betas": betas, "branch": "reg"}
    else:
        recon = dequant(lorenzo_nd_recon(codes_lor), eb).reshape(orig_shape)
        codes = codes_lor
        meta = _DIM_META_BITS + 1
        method = "lor_reg/lorenzo"
        extras = {"branch": "lorenzo"}

    payload = cb_bits = 0
    if count_entropy:
        payload, cb_bits, ent = entropy_stage(codes, use_zstd=use_zstd,
                                              codebook=codebook,
                                              engine=entropy_engine)
        extras["entropy"] = ent
    return SZResult(recon=recon, codes=codes.ravel(), payload_bits=payload,
                    codebook_bits=cb_bits, meta_bits=meta, eb=eb,
                    method=method, extras=extras)


# ------------------------- decode from serialized codes ---------------------


def decode_codes(codes: np.ndarray, shape: tuple[int, ...], eb: float, *,
                 branch: str, block: int = 6,
                 betas: np.ndarray | None = None) -> np.ndarray:
    """Reconstruct an array from its quantization-code stream.

    This is the read path of the TACZ container: given the codes a
    ``compress_*`` front-end produced (plus the regression betas for the
    ``reg`` branch), replay the reconstruction **bit-identically** to the
    ``recon`` the compressor returned.

      * ``branch="lorenzo"`` — inverse of the global N-D Lorenzo codes
        (:func:`compress_lorenzo` and the Lorenzo branch of
        :func:`compress_lor_reg`), any rank.
      * ``branch="interp"``  — inverse of :func:`compress_interp`.
      * ``branch="reg"``     — regression branch of
        :func:`compress_lor_reg`; ``codes`` are the blocked residuals and
        ``betas`` the per-``block³`` plane coefficients (float32, shape
        ``(bx, by, bz, 4)``).
    """
    shape = tuple(int(s) for s in shape)
    codes = np.asarray(codes, dtype=np.int64)
    if branch == "lorenzo":
        return dequant(lorenzo_nd_recon(codes.reshape(shape)), eb)
    if branch == "interp":
        return dequant(interp_nd_recon(codes.reshape(shape)), eb)
    if branch == "reg":
        if betas is None:
            raise ValueError("regression branch needs betas")
        if len(shape) != 3:
            raise ValueError("regression branch decodes 3D bricks only")
        b, bgrid = reg_block_grid(shape, block)
        codes_reg = codes.reshape(tuple(bgrid) + (b, b, b))
        return _reg_recon(betas, codes_reg, b, bgrid, shape, eb)
    raise ValueError(f"unknown branch {branch!r}")


def decode_codes_batched(codes: np.ndarray, shape: tuple[int, ...],
                         eb: float, *, branch: str, block: int = 6,
                         betas: np.ndarray | None = None) -> np.ndarray:
    """Vectorized :func:`decode_codes` over a stack of same-shape bricks.

    ``codes``: (N, n_codes) — N bricks that share ``shape``, ``branch``,
    and ``eb`` (the grouping the serving-side decode planner produces);
    for ``branch="reg"``, ``betas`` is the matching (N, bx, by, bz, 4)
    coefficient stack.  Returns an (N, \\*shape) float32 reconstruction
    whose every slice is **bit-identical** to
    ``decode_codes(codes[i], shape, eb, ...)`` — the Lorenzo prefix sums
    and the regression replay run once across the batch axis instead of
    once per brick (the same vectorization the encode side got in PR 1).
    The interp branch keeps a per-item loop: its stage schedule is a
    function of the array rank, and interp only ever appears as a single
    global payload per level.
    """
    shape = tuple(int(s) for s in shape)
    codes = np.ascontiguousarray(codes, dtype=np.int64)
    if codes.ndim != 2:
        raise ValueError("expected a (N, n_codes) stack of code streams")
    n = codes.shape[0]
    if branch == "lorenzo":
        stacked = codes.reshape((n,) + shape)
        axes = tuple(range(1, len(shape) + 1))
        return dequant(lorenzo_nd_recon(stacked, axes=axes), eb)
    if branch == "interp":
        if n == 0:
            return np.zeros((0,) + shape, dtype=np.float32)
        return np.stack([dequant(interp_nd_recon(codes[i].reshape(shape)),
                                 eb) for i in range(n)])
    if branch == "reg":
        if betas is None:
            raise ValueError("regression branch needs betas")
        if len(shape) != 3:
            raise ValueError("regression branch decodes 3D bricks only")
        b, bgrid = reg_block_grid(shape, block)
        bx, by, bz = bgrid
        codes_reg = codes.reshape((n,) + tuple(bgrid) + (b, b, b))
        fit = _fit_from_betas(np.asarray(betas), b)
        rr = (fit + 2.0 * eb * codes_reg).astype(np.float32)
        rr = (rr.reshape(n, bx, by, bz, b, b, b)
                .transpose(0, 1, 4, 2, 5, 3, 6)
                .reshape(n, bx * b, by * b, bz * b))
        return rr[(slice(None),) + tuple(slice(0, s) for s in shape)]
    raise ValueError(f"unknown branch {branch!r}")


# ----------------------- batched Lor/Reg (SHE hot path) ---------------------


def _block_view_batched(a: np.ndarray, b: int) -> tuple[np.ndarray, tuple[int, int, int]]:
    """(N,X,Y,Z) → (N, bx,by,bz, b,b,b) view after per-brick edge padding.

    Per-brick this is exactly :func:`_block_view`; the padding and the
    transpose never mix values across the leading batch axis.
    """
    pads = [(0, 0)] + [(0, (-s) % b) for s in a.shape[1:]]
    if any(p[1] for p in pads):
        a = np.pad(a, pads, mode="edge")
    n = a.shape[0]
    bx, by, bz = (s // b for s in a.shape[1:])
    return (a.reshape(n, bx, b, by, b, bz, b)
             .transpose(0, 1, 3, 5, 2, 4, 6)), (bx, by, bz)


def _code_cost_bits_rows(codes: np.ndarray) -> np.ndarray:
    """Per-brick :func:`_code_cost_bits`: sum over everything but axis 0.

    ``codes`` must be C-contiguous so each brick's row reduction adds the
    same values in the same (pairwise) order as the sequential per-brick
    ``sum(axis=None)`` — keeping the batched branch scores bit-identical.
    """
    mag = np.log2(1.0 + 2.0 * np.abs(np.ascontiguousarray(codes)
                                     .astype(np.float64)))
    return mag.reshape(mag.shape[0], -1).sum(axis=1) + 1.0


# One brick must fit in one VMEM tile (the kernel's zero-halo is per tile,
# so tile == brick is the independence contract); this is the default tile's
# footprint budget from repro.kernels.lorenzo3d.
_MAX_PALLAS_BRICK = 8 * 128 * 128
# The kernel quantizes as rint(x · float32(1/2eb)) in float32 and stores
# int32 codes; the error-bound guarantee needs the quantized integers to be
# float32-exact, i.e. |x|/(2eb) < 2^24 (one bit of margin kept).
_MAX_PALLAS_Q = float(2 ** 23)


def _lorenzo_codes_batched_pallas(x: np.ndarray, eb: float) -> np.ndarray | None:
    """Fused prequant+Lorenzo via ``repro.kernels.lorenzo3d`` (batched).

    The tile is the whole brick — the kernel computes a zero-halo Lorenzo
    per tile, so tile == brick is what makes each sub-block's prediction
    self-contained (Alg. 4 line 4).  Returns None (callers fall back to
    the numpy oracle) when a brick exceeds the VMEM tile budget or when
    the quantized magnitudes exceed the float32-exact integer range — past
    that the kernel's float32/int32 arithmetic would break the error
    bound rather than merely differ in last-ulp rounding.  The numpy host
    path stays the bit-exact float64/int64 oracle.  Bricks go to
    ``tacz_device_items_total`` or, with the guard that stopped them, to
    ``tacz_host_fallbacks_total``.
    """
    shape = tuple(int(s) for s in x.shape[1:])
    reason = None
    if int(np.prod(shape)) > _MAX_PALLAS_BRICK:
        reason = "brick_size"
    elif float(np.abs(x).max(initial=0.0)) / (2.0 * eb) >= _MAX_PALLAS_Q:
        reason = "quant_range"
    if reason is not None:
        obsm.HOST_FALLBACKS.labels("lorenzo", reason).inc(x.shape[0])
        return None
    from repro.device import device_label
    from repro.kernels import ops

    codes = ops.lorenzo3d_codes_batched(x.astype(np.float32), eb=float(eb),
                                        tile=shape)
    obsm.DEVICE_ITEMS.labels("lorenzo", device_label(codes)).inc(x.shape[0])
    return np.asarray(codes).astype(np.int64)


def compress_lor_reg_batched(x: np.ndarray, eb: float, *, block: int = 6,
                             engine: str = "auto") -> list[SZResult]:
    """Batched :func:`compress_lor_reg` over a stack of same-shape bricks.

    ``x``: (N, X, Y, Z) — N independent 3D bricks (e.g. one padded-shape
    group of SHE sub-blocks).  Every stage of the per-brick compressor is
    vectorized across the leading axis with identical arithmetic, so each
    returned :class:`SZResult` is bit-identical (codes, recon, meta, branch
    choice) to ``compress_lor_reg(x[i], eb, block=block,
    count_entropy=False)`` — the sequential path stays the oracle.

    ``engine`` selects the Lorenzo-branch *codes* backend: ``"numpy"`` is
    the bit-exact host oracle; ``"pallas"`` routes the fused
    prequant+Lorenzo through the batched Pallas kernel — float32/int32
    on-device arithmetic, falling back to numpy when a brick exceeds the
    VMEM tile budget or the float32-exact quantization range.  ``"auto"``
    (default) picks ``"pallas"`` when a TPU backend is attached and
    ``"numpy"`` otherwise.  Reconstruction always uses the float64 host
    dequant (the same arithmetic ``decode_codes`` replays), so serialized
    codes round-trip bit-identically to ``recon`` on every backend.

    The entropy stage is intentionally left to the caller (payloads are 0):
    SHE pools all bricks' codes under one shared codebook (paper Alg. 4),
    so pricing them here would be wasted work.
    """
    x = np.asarray(x)
    if x.ndim != 4:
        raise ValueError("expected a (N, X, Y, Z) stack of 3D bricks")
    if engine not in ("auto", "numpy", "pallas"):
        raise ValueError(f"unknown engine {engine!r}")
    n = x.shape[0]
    if n == 0:
        return []
    bshape = x.shape[1:]
    b, _ = reg_block_grid(bshape, block)

    # --- Lorenzo branch: zero-halo dual-quant Lorenzo per brick ------------
    with obsm.timed(obsm.COMPRESS_STAGE_SECONDS.labels("prequant"),
                    "prequant"):
        if engine == "auto":
            from repro.device import tpu_attached
            engine = "pallas" if tpu_attached() else "numpy"
        codes_lor = None
        if engine == "pallas":
            codes_lor = _lorenzo_codes_batched_pallas(x, eb)
            if codes_lor is None:
                engine = "numpy"
        if codes_lor is None:
            codes_lor = lorenzo_nd_codes(prequant(x, eb), axes=(1, 2, 3))

    # --- Regression branch: per-block plane fits + branch scoring ----------
    # Degenerate b == 1 (zero coordinate variance → NaN betas) can never
    # beat Lorenzo; skip the fit, matching the sequential path.
    with obsm.timed(obsm.COMPRESS_STAGE_SECONDS.labels("branch_score"),
                    "branch_score"):
        cost_lor = _code_cost_bits_rows(codes_lor)
        n_blocks = 0
        if b >= 2:
            xb, bgrid = _block_view_batched(x, b)
            betas, fit = _regression_fit(xb, b)
            codes_reg = np.rint((xb - fit) / (2.0 * eb)).astype(np.int64)
            n_blocks = int(np.prod(bgrid))
            cost_reg = _code_cost_bits_rows(codes_reg) + n_blocks * 4 * 32
            use_reg = cost_reg < cost_lor
        else:
            use_reg = np.zeros(n, dtype=bool)

    # --- per-brick branch choice: reconstruct only the winning branch ------
    recon = np.empty(x.shape, dtype=np.float32)
    lor_idx = np.flatnonzero(~use_reg)
    reg_idx = np.flatnonzero(use_reg)
    if lor_idx.size:
        # recon always goes through the float64 host dequant — the same
        # arithmetic decode_codes replays — so a container written from
        # kernel-produced codes round-trips bit-identically on any backend
        # (the kernel accelerates the codes hot loop; dequant is cheap)
        recon[lor_idx] = dequant(
            lorenzo_nd_recon(codes_lor[lor_idx], axes=(1, 2, 3)), eb)
    if reg_idx.size:
        bx, by, bz = bgrid
        rr = (fit[reg_idx] + 2.0 * eb * codes_reg[reg_idx]).astype(np.float32)
        rr = (rr.reshape(len(reg_idx), bx, by, bz, b, b, b)
                .transpose(0, 1, 4, 2, 5, 3, 6)
                .reshape(len(reg_idx), bx * b, by * b, bz * b))
        recon[reg_idx] = rr[(slice(None),)
                            + tuple(slice(0, s) for s in bshape)]

    out: list[SZResult] = []
    for i in range(n):
        if use_reg[i]:
            out.append(SZResult(
                recon=recon[i], codes=codes_reg[i].ravel().copy(),
                payload_bits=0, codebook_bits=0,
                meta_bits=_DIM_META_BITS + 1 + n_blocks * 4 * 32, eb=eb,
                method="lor_reg/reg",
                extras={"betas": betas[i], "branch": "reg"}))
        else:
            out.append(SZResult(
                recon=recon[i], codes=codes_lor[i].ravel().copy(),
                payload_bits=0, codebook_bits=0,
                meta_bits=_DIM_META_BITS + 1, eb=eb,
                method="lor_reg/lorenzo", extras={"branch": "lorenzo"}))
    return out
