"""TACZ reader: full decode, region-of-interest decode, corruption checks.

The reader never scans the file: it parses the footer + CRC'd index, then
seeks straight to the byte ranges it needs.  Full decode touches every
payload; :meth:`TACZReader.read_roi` touches only the sub-blocks whose
cuboids intersect the query box — on partition-heavy TAC+ levels that is
the difference between decoding the whole snapshot and decoding a few
bricks (the access pattern AMR visualization/analysis consumers actually
have).  Both paths reproduce the in-memory ``compress_amr`` reconstruction
bit-identically.
"""
from __future__ import annotations

import io as _stdio
import os
import threading
import zlib
from dataclasses import dataclass

import numpy as np

from repro.core import entropy, huffman, sz
from repro.core.blocks import make_block_grid
from repro.core.compat import HAVE_ZSTD, zstd_decompress
from repro.obs import metrics as obsm
from repro.core.gsp import gsp_unpad

from . import format as fmt
from . import frontier as frt

__all__ = ["ROILevel", "TACZReader", "WHOLE_LEVEL", "open_snapshot",
           "probe_index_crc", "read", "read_roi"]

Box = tuple[tuple[int, int], tuple[int, int], tuple[int, int]]

#: Sub-block index standing in for the single payload of a gsp/global
#: level in a ``(level, sub_block)`` key.  SHE levels use real indices
#: (``0..n_subblocks-1``); single-payload levels are addressed as one
#: unit because their reconstruction is not block-local.  The serving
#: layer (cache keys, shard placement) uses the same convention.
WHOLE_LEVEL = -1


@dataclass
class ROILevel:
    """One level's crop of a region-of-interest read."""

    level: int                    # level index in the file
    ratio: int                    # coarsening ratio vs the finest grid
    box: Box                      # the decoded box, in *level* cells
    data: np.ndarray              # recon crop, shape = box extents

    @property
    def shape(self) -> tuple[int, ...]:
        """Extent of the crop per dim (``hi - lo`` of each box range)."""
        return tuple(hi - lo for lo, hi in self.box)


def _decompress(buf: bytes, compressor: int) -> bytes:
    if compressor == fmt.COMPRESSOR_NONE:
        return buf
    if compressor == fmt.COMPRESSOR_ZLIB:
        return zlib.decompress(buf)
    if compressor == fmt.COMPRESSOR_ZSTD:
        if not HAVE_ZSTD:
            raise ModuleNotFoundError(
                "this TACZ file was written with zstd payloads but "
                "zstandard is not installed")
        return zstd_decompress(buf)
    raise ValueError(f"unknown compressor {compressor}")


class TACZReader:
    """Random-access reader over a TACZ container.

    The constructor validates framing eagerly: header magic/version,
    footer, index bounds, and the index CRC — a truncated or corrupt
    file fails at open time, never as silent garbage mid-decode.  One
    reader may serve many threads (the seek+read pair is lock-guarded).

    :param src: file path, raw ``bytes``/``bytearray``, or a seekable
        binary file object (not closed on :meth:`close`).
    :param entropy_engine: :mod:`repro.core.entropy` engine for payload
        decode (``"auto"`` picks the batched path; every engine is
        bit-identical, so this only affects speed).
    :raises ValueError: if the bytes are not a valid TACZ container
        (bad magic, unsupported version, truncation, index CRC mismatch).
    :raises OSError: if a path cannot be opened.
    """

    _SHE_STRATEGIES = (fmt.STRATEGY_OPST, fmt.STRATEGY_AKDTREE,
                       fmt.STRATEGY_NAST)

    def __init__(self, src, *, entropy_engine: str = "auto"):
        entropy.check_engine_name(entropy_engine)
        self._entropy_engine = entropy_engine
        if isinstance(src, (bytes, bytearray)):
            self._f = _stdio.BytesIO(bytes(src))
            self._own = True
        elif hasattr(src, "seek"):
            self._f = src
            self._own = False
        else:
            self._f = open(src, "rb")
            self._own = True
        self._io_lock = threading.Lock()   # seek+read must be atomic
        try:
            self._f.seek(0, 2)
            self._size = self._f.tell()
            self.version = fmt.parse_header(
                self._read_at(0, min(fmt.HEADER_SIZE, self._size)))
            idx_off, idx_len, idx_crc = fmt.parse_footer(
                self._read_at(max(0, self._size - fmt.FOOTER_SIZE),
                              min(fmt.FOOTER_SIZE, self._size)))
            if idx_off + idx_len + fmt.FOOTER_SIZE > self._size:
                raise ValueError("truncated TACZ file: index out of bounds")
            index = self._read_at(idx_off, idx_len)
            if fmt.index_crc(index) != idx_crc:
                raise ValueError("corrupt TACZ file: index CRC mismatch")
            # the index CRC uniquely identifies the snapshot's content —
            # the serving layer's hot-swap check compares it footer-to-footer
            self.index_crc = idx_crc & 0xFFFFFFFF
            self.levels: list[fmt.LevelEntry] = fmt.parse_index(
                index, version=self.version)
            # optional TACF frontier section between index and footer:
            # absent (zero gap) or corrupt → frontier=None, never raise
            # (a pre-frontier file must keep opening, and a damaged
            # section must degrade to default-variant serving)
            self.frontier: frt.Frontier | None = None
            self.frontier_error: str | None = None
            gap = (self._size - fmt.FOOTER_SIZE) - (idx_off + idx_len)
            if gap > 0:
                try:
                    self.frontier = frt.parse_section(
                        self._read_at(idx_off + idx_len, gap))
                except ValueError as exc:
                    self.frontier_error = str(exc)
        except BaseException:
            # validation raises for exactly the files callers probe with
            # (truncated/corrupt/non-TACZ) — don't leak the fd until GC
            self.close()
            raise
        self._codebooks: dict[int, huffman.Codebook] = {}
        self._masks: dict[int, np.ndarray | None] = {}
        self._bounds = self._subblock_bounds()

    # ------------------------------ plumbing -------------------------------

    def _subblock_bounds(self) -> list[tuple[np.ndarray, np.ndarray]]:
        """Per level, read-only ``(lo, hi)`` int64 arrays of shape
        ``(N, 3)``: each sub-block's ``origin`` and ``origin + size``, in
        index order.  They are column-major, so the per-dimension tests
        of :meth:`intersecting_subblocks` run over contiguous columns."""
        out = []
        for e in self.levels:
            geo = np.array([sb.origin + sb.size for sb in e.subblocks],
                           dtype=np.int64).reshape(-1, 6)
            lo = np.asfortranarray(geo[:, :3])
            hi = np.asfortranarray(geo[:, :3] + geo[:, 3:])
            lo.flags.writeable = hi.flags.writeable = False
            out.append((lo, hi))
        return out

    def close(self) -> None:
        """Close the underlying handle (no-op for caller-owned files)."""
        if self._own:
            self._f.close()

    def __enter__(self) -> "TACZReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def n_levels(self) -> int:
        """Number of levels (or tensors) in the container."""
        return len(self.levels)

    def _read_at(self, off: int, length: int) -> bytes:
        # one reader may serve many threads (RegionServer, ThreadingHTTP):
        # the shared handle's seek+read pair must not interleave
        with self._io_lock:
            self._f.seek(off)
            buf = self._f.read(length)
        if len(buf) != length:
            raise ValueError("truncated TACZ file: unexpected EOF")
        return buf

    def _section(self, off: int, length: int, crc: int, what: str,
                 li: int) -> bytes:
        buf = self._read_at(off, length)
        if (zlib.crc32(buf) & 0xFFFFFFFF) != (crc & 0xFFFFFFFF):
            raise IOError(f"TACZ corruption: {what} section CRC mismatch "
                          f"(level {li})")
        return buf

    def _codebook(self, li: int) -> huffman.Codebook:
        if li not in self._codebooks:
            e = self.levels[li]
            self._codebooks[li] = huffman.deserialize_codebook(
                self._section(e.codebook_off, e.codebook_len,
                              e.codebook_crc, "codebook", li))
        return self._codebooks[li]

    def _mask(self, li: int) -> np.ndarray | None:
        """Level validity mask at its original shape, or None (all-True)."""
        if li not in self._masks:
            e = self.levels[li]
            if e.mask_len == 0:
                self._masks[li] = None
            else:
                raw = _decompress(
                    self._section(e.mask_off, e.mask_len, e.mask_crc,
                                  "mask", li),
                    e.mask_compressor)
                n = int(np.prod(e.shape))
                bits = np.unpackbits(np.frombuffer(raw, dtype=np.uint8),
                                     count=n)
                self._masks[li] = bits.astype(bool).reshape(e.shape)
        return self._masks[li]

    # ------------------------------ decoding -------------------------------

    @staticmethod
    def _prefix_limit(sb: fmt.SubBlockEntry, shape: tuple[int, ...],
                      sz_block: int, hi: tuple[int, int, int]) -> int:
        """Number of leading codes needed to reconstruct every cell with
        brick-local index < ``hi`` (exclusive per dim).

        Lorenzo recon of cell (i,j,k) sums the rectangular code prefix
        [0..i]×[0..j]×[0..k], and every cell of that rectangle has a
        C-order flat index ≤ flat(i,j,k) — so decoding the C-order prefix
        up to the box's high corner is sufficient.  The regression branch
        is block-local with blocks stored in C order, so the same argument
        applies at block granularity.  Entropy decode is bit-serial — this
        prefix stop is what makes partially-overlapped bricks cheap.
        """
        corner = tuple(h - 1 for h in hi)
        if sb.branch == fmt.BRANCH_REG:
            b, bgrid = sz.reg_block_grid(shape, sz_block)
            bc = tuple(c // b for c in corner)
            flat = (bc[0] * bgrid[1] + bc[1]) * bgrid[2] + bc[2]
            return (flat + 1) * b ** 3
        if sb.branch == fmt.BRANCH_LORENZO:
            flat = (corner[0] * shape[1] + corner[1]) * shape[2] + corner[2]
            return flat + 1
        return sb.n_codes   # interp is global — no partial decode

    def _payload_parts(self, li: int, sb: fmt.SubBlockEntry,
                       shape: tuple[int, ...],
                       ) -> tuple[bytes, np.ndarray | None]:
        """Fetch + CRC-check one payload → (decompressed code bytes, betas).

        This is the I/O half of the payload path; entropy decode happens
        in :meth:`_decode_payloads` so many payloads can share one
        batched engine launch.
        """
        e = self.levels[li]
        payload = self._read_at(sb.payload_off, sb.payload_len)
        if (zlib.crc32(payload) & 0xFFFFFFFF) != sb.crc:
            raise IOError(f"TACZ corruption: sub-block payload CRC mismatch "
                          f"(level {li}, offset {sb.payload_off})")
        betas = None
        if sb.betas_len:
            _, bgrid = sz.reg_block_grid(shape, e.sz_block)
            betas = np.frombuffer(payload, dtype="<f4",
                                  count=int(np.prod(bgrid)) * 4,
                                  offset=0).reshape(bgrid + (4,))
        code_bytes = _decompress(payload[sb.betas_len:], sb.compressor)
        return code_bytes, betas

    def _decode_payloads(self, li: int, jobs,
                         ) -> list[tuple[np.ndarray, np.ndarray | None]]:
        """(codes, betas) per ``(sub-block entry, shape, limit)`` job.

        All CODEC_HUFFMAN payloads of the batch go through **one**
        ``EntropyEngine.decode_payloads`` launch (the level's shared
        codebook covers them all); RAW_I16/I32 payloads decode directly.
        Each codes array has ``sb.n_codes`` entries; with a ``limit``
        only the leading ``limit`` are decoded (the rest are zeros and
        unspecified for reconstruction purposes).
        """
        out: list[tuple[np.ndarray, np.ndarray | None] | None] = \
            [None] * len(jobs)
        huff: list[tuple[int, tuple[bytes, int, int]]] = []
        metas: list[tuple[fmt.SubBlockEntry, int, np.ndarray | None]] = []
        for pos, (sb, shape, limit) in enumerate(jobs):
            code_bytes, betas = self._payload_parts(li, sb, shape)
            n_decode = (sb.n_codes if limit is None
                        else min(int(limit), sb.n_codes))
            metas.append((sb, n_decode, betas))
            if sb.codec == fmt.CODEC_HUFFMAN:
                huff.append((pos, (code_bytes, sb.nbits, n_decode)))
            elif sb.codec == fmt.CODEC_RAW_I16:
                out[pos] = (np.frombuffer(code_bytes, dtype="<i2",
                                          count=n_decode).astype(np.int64),
                            betas)
            elif sb.codec == fmt.CODEC_RAW_I32:
                out[pos] = (np.frombuffer(code_bytes, dtype="<i4",
                                          count=n_decode).astype(np.int64),
                            betas)
            else:
                raise ValueError(f"unknown payload codec {sb.codec}")
        if huff:
            with obsm.timed(obsm.ENTROPY_DECODE_SECONDS.labels(),
                            "entropy_decode", "layer.reader.entropy_decode"):
                decoded = entropy.get_engine(self._entropy_engine). \
                    decode_payloads(self._codebook(li),
                                    [payload for _, payload in huff])
            for (pos, _), codes in zip(huff, decoded):
                out[pos] = (codes, metas[pos][2])
        for pos, (sb, n_decode, _) in enumerate(metas):
            codes, betas = out[pos]
            if n_decode < sb.n_codes:
                full = np.zeros(sb.n_codes, dtype=np.int64)
                full[:n_decode] = codes
                out[pos] = (full, betas)
        return out

    def _subblock_codes(self, li: int, sb: fmt.SubBlockEntry,
                        shape: tuple[int, ...], limit: int | None = None,
                        ) -> tuple[np.ndarray, np.ndarray | None]:
        """Entropy-decode one payload → (codes, betas), no prediction
        replay — the single-payload case of :meth:`_decode_payloads`."""
        return self._decode_payloads(li, [(sb, shape, limit)])[0]

    def decode_subblocks(self, li: int, sbis, limits=None,
                         ) -> list[tuple[np.ndarray, np.ndarray | None]]:
        """(codes, betas) for many sub-blocks of one level — the batched
        form of :meth:`subblock_codes`, and the serving planner's entry
        point: every Huffman payload of the batch decodes in one
        ``EntropyEngine`` launch instead of one serial bit-walk each.

        :param li: level index.
        :param sbis: sub-block indices (any order, duplicates allowed).
        :param limits: optional per-entry prefix limits (None = full).
        :returns: one ``(codes, betas)`` pair per entry of ``sbis``, in
            input order, each identical to ``subblock_codes(li, sbi)``.
        """
        e = self.levels[li]
        jobs = []
        for pos, sbi in enumerate(sbis):
            limit = None if limits is None else limits[pos]
            jobs.append((e.subblocks[sbi], self.subblock_shape(li, sbi),
                         limit))
        return self._decode_payloads(li, jobs)

    def subblock_shape(self, li: int, sbi: int) -> tuple[int, ...]:
        """Decode shape of one sub-block payload (brick shape for SHE
        levels, the padded/original grid for gsp/global single payloads)."""
        e = self.levels[li]
        if e.strategy in self._SHE_STRATEGIES:
            return tuple(int(s) for s in e.subblocks[sbi].size)
        if e.strategy == fmt.STRATEGY_GSP:
            return tuple(int(s) for s in e.grid_shape)
        return tuple(int(s) for s in e.shape)

    def subblock_codes(self, li: int, sbi: int, limit: int | None = None,
                       ) -> tuple[np.ndarray, np.ndarray | None]:
        """(codes, betas) of sub-block ``sbi`` of level ``li`` — the
        planner's entry point for batched reconstruction."""
        e = self.levels[li]
        return self._subblock_codes(li, e.subblocks[sbi],
                                    self.subblock_shape(li, sbi), limit)

    def _decode_subblock(self, li: int, sb: fmt.SubBlockEntry,
                         shape: tuple[int, ...],
                         limit: int | None = None) -> np.ndarray:
        """Decode one payload into its reconstructed brick (bit-identical
        to the encoder-side recon).

        ``limit`` (from :meth:`_prefix_limit`) stops the entropy decode
        after the first ``limit`` codes: cells whose code rectangle lies
        inside the prefix reconstruct bit-identically, later cells are
        unspecified — only the ROI path passes it, and it never reads
        those cells.
        """
        e = self.levels[li]
        codes, betas = self._subblock_codes(li, sb, shape, limit)
        return sz.decode_codes(codes, shape, e.eb,
                               branch=fmt.BRANCH_NAMES[sb.branch],
                               block=e.sz_block, betas=betas)

    def _decode_bricks(self, li: int, jobs) -> list[np.ndarray]:
        """Reconstructed bricks for many ``(sbi, limit)`` jobs of one
        SHE level — the fully batched cold path: one entropy-engine
        launch over every payload, then one ``sz.decode_codes_batched``
        per (shape, branch) group.  Each brick is bit-identical to
        ``_decode_subblock`` on the same (sub-block, limit).
        """
        e = self.levels[li]
        sbis = [sbi for sbi, _ in jobs]
        decoded = self.decode_subblocks(li, sbis,
                                        [lim for _, lim in jobs])
        groups: dict[tuple[tuple[int, ...], int], list[int]] = {}
        for pos, sbi in enumerate(sbis):
            key = (self.subblock_shape(li, sbi), e.subblocks[sbi].branch)
            groups.setdefault(key, []).append(pos)
        out: list[np.ndarray | None] = [None] * len(jobs)
        for (shape, branch), poss in groups.items():
            codes = np.stack([decoded[p][0] for p in poss])
            betas = (np.stack([decoded[p][1] for p in poss])
                     if branch == fmt.BRANCH_REG else None)
            recon = sz.decode_codes_batched(
                codes, shape, e.eb, branch=fmt.BRANCH_NAMES[branch],
                block=e.sz_block, betas=betas)
            for p, brick in zip(poss, recon):
                out[p] = np.ascontiguousarray(brick)
        return out

    def read_level(self, li: int) -> np.ndarray:
        """Full decode of one level.

        :param li: level index (file order).
        :returns: float32 reconstruction at the level's original shape,
            bit-identical to the in-memory ``compress_amr`` recon.
        :raises IndexError: if ``li`` is out of range.
        :raises IOError: if a section or payload fails its CRC check.
        """
        e = self.levels[li]
        mask = self._mask(li)
        if e.strategy in self._SHE_STRATEGIES:
            acc = np.zeros(e.grid_shape, dtype=np.float32)
            bricks = self._decode_bricks(
                li, [(sbi, None) for sbi in range(len(e.subblocks))])
            for sb, brick in zip(e.subblocks, bricks):
                sl = tuple(slice(o, o + s) for o, s in zip(sb.origin, sb.size))
                acc[sl] = brick
            recon = acc[tuple(slice(0, s) for s in e.shape)]
            if mask is not None:
                recon = np.where(mask, recon, 0.0)
            return recon.astype(np.float32)
        if e.strategy == fmt.STRATEGY_GSP:
            padded = self._decode_subblock(li, e.subblocks[0], e.grid_shape)
            m = mask if mask is not None else np.ones(e.shape, dtype=bool)
            grid = make_block_grid(np.zeros(e.shape, dtype=np.float32), m,
                                   unit=e.unit)
            return gsp_unpad(padded, grid)[
                tuple(slice(0, s) for s in e.shape)]
        if e.strategy == fmt.STRATEGY_GLOBAL:
            recon = self._decode_subblock(li, e.subblocks[0], e.shape)
            if mask is not None:
                recon = np.where(mask, recon, 0.0).astype(np.float32)
            return recon
        raise ValueError(f"unknown strategy {e.strategy}")

    def read(self) -> list[np.ndarray]:
        """Full decode of every level.

        :returns: one float32 reconstruction per level, in file order.
        :raises IOError: if a section or payload fails its CRC check.
        """
        return [self.read_level(i) for i in range(self.n_levels)]

    # ----------------------- ROI machinery (shared) ------------------------
    # read_roi and the serving layer (repro.serving.regions) are the same
    # code path: box mapping, sub-block intersection, and crop assembly live
    # here; only *where the decoded brick comes from* differs (prefix-stop
    # entropy decode here, the byte-budgeted sub-block cache there).

    def level_box(self, li: int, box: Box) -> Box:
        """Map a finest-grid box into level ``li`` cells.

        :param li: level index.
        :param box: three half-open ``(lo, hi)`` ranges in finest cells.
        :returns: the box in level cells — lows floored, highs ceiled
            through the coarsening ratio, both clipped to the level
            extent (may be empty, ``hi <= lo``).
        :raises ValueError: if the level is not 3-D.
        """
        e = self.levels[li]
        if e.rank != 3:
            raise ValueError("ROI reads need 3D levels")
        r = max(int(e.ratio), 1)
        return tuple(
            (min(max(lo // r, 0), s), min(-(-hi // r), s))
            for (lo, hi), s in zip(box, e.shape))

    def intersecting_subblocks(self, li: int, lbox: Box,
                               ) -> list[tuple[int, Box]]:
        """Sub-blocks of level ``li`` whose cuboids overlap ``lbox``.

        The lookup is a vectorised overlap test over the level's
        sub-block bounds: two read-only ``int64`` arrays of shape
        ``(N, 3)``, each sub-block's ``origin`` and ``origin + size``,
        built once from the parsed index when the reader opens and kept
        for the reader's lifetime (a hot-swapped snapshot's new reader
        builds its own).  Only the hits come back to Python, so the work
        past the test is O(hits), not O(N).

        :param li: level index.
        :param lbox: three half-open ranges in *level* cells.
        :returns: ``(sub_block_index, intersection_box)`` pairs in index
            order, as plain ``int``; the intersection is again in level
            cells and is non-empty in every dimension.
        """
        lo, hi = self._bounds[li]
        hit = np.ones(len(lo), dtype=bool)
        for d, (blo, bhi) in enumerate(lbox):
            hit &= lo[:, d] < bhi
            hit &= hi[:, d] > blo
        idx = np.flatnonzero(hit)
        box = np.array(lbox, dtype=np.int64)
        ilo = np.maximum(lo[idx], box[:, 0])
        ihi = np.minimum(hi[idx], box[:, 1])
        # the overlap test passes an empty box or a zero-size sub-block;
        # the intersection itself must be non-empty in every dimension
        keep = np.all(ilo < ihi, axis=1)
        return [(i, ((a0, b0), (a1, b1), (a2, b2)))
                for i, (a0, a1, a2), (b0, b1, b2)
                in zip(idx[keep].tolist(), ilo[keep].tolist(),
                       ihi[keep].tolist())]

    def subblock_keys(self, levels: list[int] | None = None,
                      ) -> list[tuple[int, int]]:
        """Enumerate every ``(level, sub_block)`` key in the container.

        SHE levels contribute one key per partition sub-block; gsp/global
        levels contribute a single ``(level, WHOLE_LEVEL)`` key (their one
        payload decodes as a unit).  This is the key universe that cache
        entries and consistent-hash shard placement range over — a shard
        filter intersects it with a shard map to learn which payloads it
        owns.

        :param levels: restrict enumeration to these level indices
            (default: every level, in file order).
        :returns: list of ``(level_index, sub_block_index)`` tuples, file
            order; ``sub_block_index`` is :data:`WHOLE_LEVEL` for
            single-payload levels.
        :raises IndexError: if ``levels`` names an out-of-range level.
        """
        lis = range(self.n_levels) if levels is None else levels
        out: list[tuple[int, int]] = []
        for li in lis:
            e = self.levels[li]
            if e.strategy in self._SHE_STRATEGIES:
                out.extend((li, sbi) for sbi in range(len(e.subblocks)))
            else:
                out.append((li, WHOLE_LEVEL))
        return out

    def level_signature(self, li: int) -> tuple:
        """Content signature of one level, independent of byte placement.

        Two snapshots whose signatures match for a level reconstruct that
        level bit-identically: the signature covers the decode-relevant
        index fields (shape, strategy, error bound, per-sub-block
        geometry/branch/codec) plus the CRC32 of every stored section —
        codebook, mask, and each payload — but **not** file offsets, so a
        level whose bytes merely moved (an earlier level changed size on
        republish) still matches.  The serving layer uses this to carry
        decoded-brick cache entries across snapshot hot-swaps.

        :param li: level index.
        :returns: an opaque hashable tuple; compare with ``==`` only.
        :raises IndexError: if ``li`` is out of range.
        """
        e = self.levels[li]
        return (e.shape, e.grid_shape, e.strategy, e.algorithm, e.unit,
                e.sz_block, e.ratio, e.eb, e.n_values,
                e.codebook_crc & 0xFFFFFFFF, e.mask_len,
                e.mask_crc & 0xFFFFFFFF, e.mask_compressor,
                tuple((sb.origin, sb.size, sb.branch, sb.codec,
                       sb.payload_len, sb.nbits, sb.n_codes, sb.betas_len,
                       sb.crc & 0xFFFFFFFF) for sb in e.subblocks))

    def read_level_box(self, li: int, lbox: Box) -> np.ndarray:
        """Decode one level's crop of a box given in *level* cells.

        Unlike :meth:`read_roi` (whose box is in finest-grid cells and is
        mapped through every level's ratio), this takes a single level and
        a box already expressed in that level's own cells — the shape the
        sharded router's local-fallback path works in.  The box is clipped
        to the level extent; only intersecting sub-blocks are decoded,
        with the same prefix-stop entropy decode as ``read_roi``.

        :param li: level index.
        :param lbox: three half-open ``(lo, hi)`` ranges in level cells.
        :returns: float32 crop of shape ``(hi-lo, ...)`` after clipping —
            bit-identical to slicing the full level reconstruction.
        :raises IndexError: if ``li`` is out of range.
        :raises ValueError: if ``lbox`` is not three ranges.
        """
        if len(lbox) != 3:
            raise ValueError("box must be ((x0,x1),(y0,y1),(z0,z1))")
        e = self.levels[li]
        clipped = tuple((min(max(int(lo), 0), s), min(max(int(hi), 0), s))
                        for (lo, hi), s in zip(lbox, e.shape))
        return self.assemble_level_roi(li, clipped,
                                       self._fetch_brick_prefix,
                                       self.read_level,
                                       fetch_bricks=self._fetch_bricks_prefix)

    def assemble_level_roi(self, li: int, lbox: Box, fetch_brick,
                           fetch_level, tasks=None,
                           fetch_bricks=None) -> np.ndarray:
        """Assemble one level's crop from decoded bricks.

        ``fetch_brick(li, sbi, local_hi)`` must return sub-block ``sbi``'s
        reconstruction, valid at least on brick-local cells below
        ``local_hi`` (exclusive); ``fetch_level(li)`` must return the full
        level reconstruction (gsp/global levels — their single payload is
        not block-local).  ``tasks`` may carry a precomputed
        ``intersecting_subblocks(li, lbox)`` result (the serving planner
        already ran the lookup).  ``fetch_bricks(li, [(sbi, local_hi)])``,
        when given, replaces the per-brick calls with one batched fetch
        for the whole SHE task list (the cold ROI path routes this at the
        batched entropy engine).  Masking and crop placement are
        identical for every caller, which is what keeps cached serving
        bit-identical to :meth:`read_roi`.
        """
        e = self.levels[li]
        bshape = tuple(max(hi - lo, 0) for lo, hi in lbox)
        if 0 in bshape:
            return np.zeros(bshape, dtype=np.float32)
        if e.strategy in self._SHE_STRATEGIES:
            if tasks is None:
                tasks = self.intersecting_subblocks(li, lbox)
            acc = np.zeros(bshape, dtype=np.float32)
            if not tasks:      # nothing decoded → all zeros; masking is a
                return acc     # no-op, so skip the mask-section read
            jobs = [(sbi, tuple(hi - o for (_, hi), o
                                in zip(isect, e.subblocks[sbi].origin)))
                    for sbi, isect in tasks]
            bricks = (fetch_bricks(li, jobs) if fetch_bricks is not None
                      else [fetch_brick(li, sbi, hi) for sbi, hi in jobs])
            for (sbi, isect), brick in zip(tasks, bricks):
                sb = e.subblocks[sbi]
                src = tuple(slice(lo - o, hi - o) for (lo, hi), o
                            in zip(isect, sb.origin))
                dst = tuple(slice(lo - b0, hi - b0) for (lo, hi), (b0, _)
                            in zip(isect, lbox))
                acc[dst] = brick[src]
            mask = self._mask(li)
            if mask is not None:
                mcrop = mask[tuple(slice(lo, hi) for lo, hi in lbox)]
                acc = np.where(mcrop, acc, 0.0).astype(np.float32)
            return acc
        # gsp/global levels have one global payload — decode fully,
        # then crop (interpolation/padding are not block-local)
        return fetch_level(li)[tuple(slice(lo, hi) for lo, hi in lbox)]

    def _fetch_brick_prefix(self, li: int, sbi: int,
                            local_hi: tuple[int, int, int]) -> np.ndarray:
        """read_roi's brick source: prefix-stop entropy decode up to the
        box's high corner (C-order prefix ⊇ Lorenzo code rectangle)."""
        e = self.levels[li]
        sb = e.subblocks[sbi]
        limit = self._prefix_limit(sb, sb.size, e.sz_block, local_hi)
        return self._decode_subblock(li, sb, sb.size, limit=limit)

    def _fetch_bricks_prefix(self, li: int, jobs) -> list[np.ndarray]:
        """Batched :meth:`_fetch_brick_prefix`: same prefix limits, one
        entropy launch + one batched recon per (shape, branch) group."""
        e = self.levels[li]
        return self._decode_bricks(
            li, [(sbi, self._prefix_limit(e.subblocks[sbi],
                                          e.subblocks[sbi].size,
                                          e.sz_block, local_hi))
                 for sbi, local_hi in jobs])

    def read_roi(self, box: Box) -> list[ROILevel]:
        """Decode only the region of interest.

        ``box`` is three half-open ``(lo, hi)`` ranges in *finest-grid*
        cells.  Per level the box is mapped through the coarsening ratio
        (floor/ceil, then clipped to the level extent) and only sub-blocks
        intersecting it are decoded.  Each returned crop is bit-identical
        to slicing that level's full reconstruction with the same box.
        """
        if len(box) != 3:
            raise ValueError("box must be ((x0,x1),(y0,y1),(z0,z1))")
        out: list[ROILevel] = []
        for li, e in enumerate(self.levels):
            lbox = self.level_box(li, box)
            data = self.assemble_level_roi(
                li, lbox, self._fetch_brick_prefix, self.read_level,
                fetch_bricks=self._fetch_bricks_prefix)
            out.append(ROILevel(level=li, ratio=max(int(e.ratio), 1),
                                box=lbox, data=data))
        return out

    def verify(self) -> bool:
        """Check every section and payload CRC (the index CRC was checked
        at open).

        :returns: True when every stored byte range checks out.
        :raises IOError: at the first corrupt byte range, naming the
            level and section.
        """
        for li, e in enumerate(self.levels):
            if e.codebook_len:
                self._section(e.codebook_off, e.codebook_len,
                              e.codebook_crc, "codebook", li)
            if e.mask_len:
                self._section(e.mask_off, e.mask_len, e.mask_crc, "mask", li)
            for sb in e.subblocks:
                payload = self._read_at(sb.payload_off, sb.payload_len)
                if (zlib.crc32(payload) & 0xFFFFFFFF) != sb.crc:
                    raise IOError(
                        f"TACZ corruption: sub-block payload CRC mismatch "
                        f"(level {li}, offset {sb.payload_off})")
        return True


def probe_index_crc(path) -> int | None:
    """Read a snapshot's identity CRC — nothing else.

    The cheap snapshot-identity probe the serving layer's hot-swap checks
    run per request: the CRC uniquely identifies a published snapshot's
    content, so comparing it against an open reader's ``index_crc`` tells
    whether the file was atomically republished.  For a single-file
    snapshot that is the 20-byte footer's index CRC; for a multi-part
    snapshot directory it is the manifest's own CRC (``manifest.json``
    is the commit point — part files only count once it names them).

    :param path: ``.tacz`` file path or multi-part snapshot directory.
    :returns: the CRC as an unsigned 32-bit int, or None when the file is
        missing, truncated, or not a TACZ container (a half-written state
        is never adopted — the writer publishes atomically).
    """
    from . import manifest as _manifest
    if _manifest.is_multipart(path):
        return _manifest.probe_crc(path)
    try:
        with open(path, "rb") as f:
            f.seek(-fmt.FOOTER_SIZE, os.SEEK_END)
            _, _, crc = fmt.parse_footer(f.read(fmt.FOOTER_SIZE))
    except (OSError, ValueError):
        return None
    return crc & 0xFFFFFFFF


def open_snapshot(src, *, entropy_engine: str = "auto") -> TACZReader:
    """Open a snapshot — single-file or multi-part — behind one surface.

    A multi-part snapshot directory (or its ``manifest.json``) yields a
    :class:`repro.io.parallel.MultiPartReader`; anything else — a
    ``.tacz`` path, raw bytes, or a seekable file object — yields a
    plain :class:`TACZReader`.  Both expose the same read surface
    (``read``/``read_roi``/``subblock_keys``/``level_signature``/...),
    which is what lets the serving stack treat them interchangeably.

    :param src: snapshot path (file or directory), bytes, or file object.
    :param entropy_engine: payload-decode engine, forwarded to the reader
        (see :class:`TACZReader`).
    :returns: an open reader; the caller owns :meth:`TACZReader.close`.
    :raises ValueError: if the snapshot fails validation.
    :raises OSError: if the path cannot be opened.
    """
    from . import manifest as _manifest
    if _manifest.is_multipart(src):
        from .parallel import MultiPartReader
        return MultiPartReader(src, entropy_engine=entropy_engine)
    return TACZReader(src, entropy_engine=entropy_engine)


def read(path) -> list[np.ndarray]:
    """Decode every level of ``path``.

    :param path: file path or container bytes.
    :returns: one float32 reconstruction per level, file order.
    :raises ValueError: if the bytes are not a valid TACZ container.
    :raises IOError: if a section or payload fails its CRC check.
    """
    with TACZReader(path) as rd:
        return rd.read()


def read_roi(path, box: Box) -> list[ROILevel]:
    """ROI decode of ``path`` — see :meth:`TACZReader.read_roi`.

    :param path: file path or container bytes.
    :param box: three half-open ``(lo, hi)`` ranges in finest-grid cells.
    :returns: one :class:`ROILevel` crop per level, finest first.
    :raises ValueError: if the container or box is malformed.
    :raises IOError: if a touched payload fails its CRC check.
    """
    with TACZReader(path) as rd:
        return rd.read_roi(box)
