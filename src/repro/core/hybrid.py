"""Hybrid level-wise AMR compression — the TAC / TAC+ drivers (paper §III-E).

Per AMR level, pick the pre-process strategy from the level's unit-block
density:

  * **Lor/Reg + SHE (= TAC+)**: OpST+ below T0 = 50 %, AKDTree+ above.
    (GSP is dominated once SHE removes the partitioning penalty, Fig. 12.)
  * **Interp (= TAC)**:  OpST < T1 = 50 % ≤ AKDTree < T2 = 85 % ≤ GSP.
  * **Lor/Reg without SHE (= TAC)**: same thresholds as Interp.

The strategy output feeds the matching SZ path:

  * GSP        → padded full grid → one global compression.
  * OpST/AKD   → sub-blocks; with SHE: per-block Lor/Reg prediction + one
    shared Huffman tree; without SHE: same-size blocks merged into 4D
    arrays, each compressed globally (prediction crosses block boundaries —
    exactly the artifact the paper's Figs. 15/16 show SHE removing).

Level reconstructions are scattered back; empty regions are exact zeros.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import numpy as np

from .. import obs
from ..obs import metrics as obsm
from . import huffman
from .akdtree import akdtree_partition
from .amr import AMRDataset
from .blocks import BlockGrid, SubBlock, make_block_grid, extract_subblock
from .gsp import gsp_meta_bits, gsp_pad, gsp_unpad
from .opst import opst_partition
from .she import she_encode
from .sz import SZResult, compress_interp, compress_lorenzo, compress_lor_reg

__all__ = ["LevelArtifacts", "LevelResult", "AMRCompressionResult",
           "compress_level", "compress_amr", "choose_strategy",
           "partition_level", "T0", "T1", "T2"]

T0 = 0.50   # Lor/Reg+SHE: OpST+ vs AKDTree+ (Fig. 12 / Fig. 14)
T1 = 0.50   # Interp: OpST vs AKDTree (Fig. 13)
T2 = 0.85   # Interp: AKDTree vs GSP (Fig. 13)


@dataclass
class LevelArtifacts:
    """Serialization-grade level state the aggregate accounting drops.

    ``LevelResult`` carries bit totals and the reconstructed grid; the TACZ
    container (``repro.io``) additionally needs the raw code streams, the
    sub-block placement, and the shared codebook to emit real byte streams
    and decode them back.  Kept by default (the arrays referenced here were
    already materialized by the compressor — this holds references, it does
    not copy).
    """

    mask: np.ndarray              # validity mask at the level's orig shape
    orig_shape: tuple[int, ...]   # level shape before unit-block padding
    grid_shape: tuple[int, ...]   # padded block-grid data shape
    unit: int                     # unit-block edge (cells)
    sz_block: int                 # Lor/Reg regression block edge
    subblocks: list[SubBlock]     # placement (empty for gsp/global levels)
    results: list[SZResult]       # per-sub-block codes/branch/betas
    codebook: huffman.Codebook | None  # shared Huffman codebook (SHE levels)


@dataclass
class LevelResult:
    strategy: str
    algorithm: str
    she: bool
    payload_bits: int
    codebook_bits: int
    meta_bits: int
    recon: np.ndarray            # reconstructed level grid (exact zeros outside)
    n_values: int                # stored values at this level
    density: float
    eb: float
    n_subblocks: int = 0
    ratio: int = 1               # coarsening ratio vs the finest grid
    artifacts: LevelArtifacts | None = field(default=None, repr=False)

    @property
    def total_bits(self) -> int:
        return int(self.payload_bits + self.codebook_bits + self.meta_bits)


@dataclass
class AMRCompressionResult:
    levels: list[LevelResult]
    method: str

    @property
    def total_bits(self) -> int:
        return sum(l.total_bits for l in self.levels)

    @property
    def n_values(self) -> int:
        return sum(l.n_values for l in self.levels)

    def compression_ratio(self, dtype_bits: int = 32) -> float:
        return self.n_values * dtype_bits / max(self.total_bits, 1)

    def bit_rate(self, dtype_bits: int = 32) -> float:
        return self.total_bits / max(self.n_values, 1)


def choose_strategy(density: float, *, algorithm: str, she: bool) -> str:
    """§III-E hybrid policy on unit-block density."""
    if she and algorithm == "lor_reg":
        return "opst" if density < T0 else "akdtree"
    if density < T1:
        return "opst"
    if density < T2:
        return "akdtree"
    return "gsp"


def _global_compress(x: np.ndarray, eb: float, algorithm: str,
                     sz_block: int = 6,
                     entropy_engine: str = "auto") -> SZResult:
    if algorithm == "interp":
        return compress_interp(x, eb, entropy_engine=entropy_engine)
    if algorithm == "lorenzo":
        return compress_lorenzo(x, eb, entropy_engine=entropy_engine)
    if algorithm == "lor_reg":
        # the block edge must match what the level records (the TACZ index
        # stores sz_block and the decoder rebuilds the betas grid from it)
        return compress_lor_reg(x, eb, block=sz_block,
                                entropy_engine=entropy_engine)
    raise ValueError(f"unknown algorithm {algorithm!r}")


def _merged_compress(groups: dict[tuple[int, ...], np.ndarray], eb: float,
                     algorithm: str) -> tuple[list[SZResult], dict[tuple[int, ...], np.ndarray]]:
    """TAC path: one global compression per same-size 4D group.

    For Lor/Reg-without-SHE the merged 4D array is compressed with the
    global (Lorenzo-branch) predictor — prediction runs across the block-
    stacking axis, reproducing the paper's boundary artifact.
    """
    results, recon = [], {}
    for shape, arr in groups.items():
        alg = "lorenzo" if algorithm == "lor_reg" else algorithm
        r = _global_compress(arr, eb, alg)
        results.append(r)
        recon[shape] = r.recon
    return results, recon


def partition_level(data: np.ndarray, mask: np.ndarray, *, unit: int = 8,
                    algorithm: str = "lor_reg", she: bool = True,
                    strategy: str | None = None,
                    ) -> tuple[BlockGrid, str, float, list[SubBlock]]:
    """Resolve one level's strategy and sub-block placement — without
    compressing anything.

    This is the global, deterministic prefix of :func:`compress_level`:
    the unit-block grid, the density-driven strategy choice, and (for
    SHE-style strategies) the partition into sub-blocks.  A parallel
    writer (``repro.io.parallel``) runs it once per level so N workers
    can compress disjoint slices of the *same* placement — every brick's
    codes are then bit-identical to the single-writer path, because the
    batched compressor is per-brick independent.

    :returns: ``(grid, strategy, density, subblocks)`` — ``subblocks``
        is empty for ``"gsp"`` (single global payload).
    :raises ValueError: on an unknown ``strategy``.
    """
    grid = make_block_grid(data, mask, unit=unit)
    density = grid.block_density
    if strategy is None:
        strategy = choose_strategy(density, algorithm=algorithm, she=she)
    if strategy == "gsp":
        return grid, "gsp", density, []
    if strategy == "opst":
        subblocks = opst_partition(grid)
    elif strategy == "akdtree":
        subblocks = akdtree_partition(grid)
    elif strategy == "nast":
        subblocks = [SubBlock(origin=tuple(c), bsize=(1, 1, 1))
                     for c in np.argwhere(grid.occ)]
    else:
        raise ValueError(f"unknown strategy {strategy!r}")
    return grid, strategy, density, subblocks


def compress_level(data: np.ndarray, mask: np.ndarray, *, eb: float,
                   unit: int = 8, algorithm: str = "lor_reg",
                   she: bool = True, strategy: str | None = None,
                   sz_block: int = 6, batched: bool = True,
                   ratio: int = 1, keep_artifacts: bool = True,
                   lorenzo_engine: str = "auto",
                   entropy_engine: str = "auto") -> LevelResult:
    """One level end to end; records per-strategy wall time into
    ``tacz_compress_level_seconds``.  Stage timings
    (``tacz_compress_stage_seconds``) are recorded where each stage runs:
    partition, the brick gather and the scatter back into the level grid
    here; brick stacking and entropy in she; prequant, branch_score and
    the branch reconstruction in sz."""
    with obs.trace("compress_level", "layer.compress.level"):
        t0 = time.perf_counter()
        res = _compress_level(
            data, mask, eb=eb, unit=unit, algorithm=algorithm, she=she,
            strategy=strategy, sz_block=sz_block, batched=batched,
            ratio=ratio, keep_artifacts=keep_artifacts,
            lorenzo_engine=lorenzo_engine, entropy_engine=entropy_engine)
        obsm.COMPRESS_LEVEL_SECONDS.labels(res.strategy).observe(
            time.perf_counter() - t0)
        return res


def _compress_level(data: np.ndarray, mask: np.ndarray, *, eb: float,
                    unit: int = 8, algorithm: str = "lor_reg",
                    she: bool = True, strategy: str | None = None,
                    sz_block: int = 6, batched: bool = True,
                    ratio: int = 1, keep_artifacts: bool = True,
                    lorenzo_engine: str = "auto",
                    entropy_engine: str = "auto") -> LevelResult:
    with obsm.timed(obsm.COMPRESS_STAGE_SECONDS.labels("partition"),
                    "partition", "layer.compress.partition"):
        grid, strategy, density, subblocks = partition_level(
            data, mask, unit=unit, algorithm=algorithm, she=she,
            strategy=strategy)
        sb_meta = sum(sb.meta_bits() for sb in subblocks)
    obsm.COMPRESS_SUBBLOCKS.labels(strategy).inc(len(subblocks))

    orig_shape = data.shape

    if strategy == "gsp":
        padded, grid = gsp_pad(data, mask, unit=unit)
        r = _global_compress(padded, eb, algorithm, sz_block, entropy_engine)
        recon = gsp_unpad(r.recon, grid)[
            tuple(slice(0, s) for s in orig_shape)]
        art = None
        if keep_artifacts:
            art = LevelArtifacts(mask=np.asarray(mask, dtype=bool),
                                 orig_shape=tuple(orig_shape),
                                 grid_shape=tuple(grid.data.shape),
                                 unit=unit, sz_block=sz_block,
                                 subblocks=[], results=[r], codebook=None)
        return LevelResult(strategy="gsp", algorithm=algorithm, she=False,
                           payload_bits=r.payload_bits,
                           codebook_bits=r.codebook_bits,
                           meta_bits=r.meta_bits + gsp_meta_bits(grid),
                           recon=recon, n_values=int(mask.sum()),
                           density=density, eb=eb, ratio=ratio,
                           artifacts=art)

    u = grid.unit

    if she and algorithm == "lor_reg":
        with obsm.timed(obsm.COMPRESS_STAGE_SECONDS.labels("gather"),
                        "gather", "layer.compress.gather"):
            bricks = [extract_subblock(grid, sb) for sb in subblocks]
        enc = she_encode(bricks, eb, block=sz_block, shared=True,
                         batched=batched, lorenzo_engine=lorenzo_engine,
                         entropy_engine=entropy_engine)
        with obsm.timed(obsm.COMPRESS_STAGE_SECONDS.labels("recon"),
                        "recon", "layer.compress.recon"):
            recon = np.zeros(grid.data.shape, dtype=np.float32)
            for sb, r in zip(subblocks, enc.results):
                ox, oy, oz = sb.cell_origin(u)
                sx, sy, sz = sb.cell_size(u)
                recon[ox:ox + sx, oy:oy + sy, oz:oz + sz] = r.recon
            recon = recon[tuple(slice(0, s) for s in orig_shape)]
            recon = np.where(mask, recon, 0.0).astype(np.float32)
            art = None
            if keep_artifacts:
                art = LevelArtifacts(mask=np.asarray(mask, dtype=bool),
                                     orig_shape=tuple(orig_shape),
                                     grid_shape=tuple(grid.data.shape),
                                     unit=grid.unit, sz_block=sz_block,
                                     subblocks=subblocks,
                                     results=enc.results,
                                     codebook=enc.codebook)
        return LevelResult(strategy=strategy, algorithm=algorithm, she=True,
                           payload_bits=enc.payload_bits,
                           codebook_bits=enc.codebook_bits,
                           meta_bits=enc.meta_bits + sb_meta,
                           recon=recon, n_values=int(mask.sum()),
                           density=density, eb=eb,
                           n_subblocks=len(subblocks), ratio=ratio,
                           artifacts=art)

    # TAC path: merge same-size blocks into 4D arrays, compress each group
    groups: dict[tuple[int, ...], list[tuple[SubBlock, np.ndarray]]] = {}
    for sb in subblocks:
        brick = extract_subblock(grid, sb)
        order = tuple(np.argsort(brick.shape)[::-1])
        brick_t = np.transpose(brick, order)
        groups.setdefault(brick_t.shape, []).append((sb, order, brick_t))
    payload = cb_bits = 0
    recon = np.zeros(grid.data.shape, dtype=np.float32)
    n_groups = 0
    for shape, items in groups.items():
        arr = np.stack([b for _, _, b in items])
        alg = "lorenzo" if algorithm == "lor_reg" else algorithm
        r = _global_compress(arr, eb, alg, entropy_engine=entropy_engine)
        payload += r.payload_bits
        cb_bits += r.codebook_bits
        n_groups += 1
        for i, (sb, order, _) in enumerate(items):
            inv_order = tuple(np.argsort(order))
            back = np.transpose(r.recon[i], inv_order)
            ox, oy, oz = sb.cell_origin(u)
            sx, sy, sz = sb.cell_size(u)
            recon[ox:ox + sx, oy:oy + sy, oz:oz + sz] = back
    recon = recon[tuple(slice(0, s) for s in orig_shape)]
    recon = np.where(mask, recon, 0.0).astype(np.float32)
    # merged-4D (non-SHE) groups interleave many sub-blocks into one code
    # stream — no per-sub-block payload exists, so no TACZ artifacts.
    return LevelResult(strategy=strategy, algorithm=algorithm, she=False,
                       payload_bits=payload, codebook_bits=cb_bits,
                       meta_bits=sb_meta + n_groups * 64,
                       recon=recon, n_values=int(mask.sum()),
                       density=density, eb=eb, n_subblocks=len(subblocks),
                       ratio=ratio)


def compress_amr(ds: AMRDataset, *, eb: float | list[float],
                 unit: int = 8, algorithm: str = "lor_reg",
                 she: bool = True, strategy: str | None = None,
                 sz_block: int = 6, batched: bool = True,
                 keep_artifacts: bool = True,
                 lorenzo_engine: str = "auto",
                 entropy_engine: str = "auto") -> AMRCompressionResult:
    """Level-wise TAC/TAC+ over a whole AMR dataset.

    ``eb`` may be a scalar (uniform bound) or per-level list — the paper's
    adaptive-error-bound mode (§IV-F).  ``unit`` is the finest level's unit
    block edge; coarser levels use ``max(2, unit / ratio)`` so the unit
    block tracks the refinement granularity (the paper's 16³ unit blocks
    are likewise fixed in *domain* units, not in per-level cells).

    ``keep_artifacts=True`` (default) retains the per-sub-block code
    streams, placement, and shared codebook on each level so the result
    can be serialized to a TACZ container (``repro.io.write``).  That
    pins roughly 3× the level data in memory (int64 codes dominate) —
    accounting-only callers that never serialize should pass
    ``keep_artifacts=False``.

    ``lorenzo_engine`` is forwarded to the batched Lor/Reg compressor:
    ``"auto"`` uses the Pallas kernel on TPU (float32 fast path),
    ``"numpy"`` forces the bit-exact float64 host oracle on any backend.
    ``entropy_engine`` is forwarded to the :mod:`repro.core.entropy`
    stage the same way; entropy engines are bit-identical, so it only
    affects speed.
    """
    ebs = eb if isinstance(eb, (list, tuple)) else [eb] * ds.n_levels
    if len(ebs) != ds.n_levels:
        raise ValueError("need one error bound per level")
    levels = []
    for lvl, e in zip(ds.levels, ebs):
        lvl_unit = max(2, unit // lvl.ratio)
        levels.append(compress_level(lvl.data, lvl.mask, eb=float(e),
                                     unit=lvl_unit, algorithm=algorithm,
                                     she=she, strategy=strategy,
                                     sz_block=sz_block, batched=batched,
                                     ratio=lvl.ratio,
                                     keep_artifacts=keep_artifacts,
                                     lorenzo_engine=lorenzo_engine,
                                     entropy_engine=entropy_engine))
    name = "tac+" if (she and algorithm == "lor_reg") else "tac"
    return AMRCompressionResult(levels=levels, method=f"{name}/{algorithm}")
