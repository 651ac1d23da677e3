"""Plain reference reader of a published TACZ snapshot.

This is the benchmark's yardstick for ``correct``: it decodes a ``.tacz``
file from the container layout alone (``docs/tacz_format.md``) and imports
nothing of the program under test.  Only what the compressor's TAC+ path
writes is supported: SHE levels (OpST / AKDTree / NaST sub-blocks) whose
payloads are canonical-Huffman code streams, optionally behind a zlib or
zstd byte pass, with the Lorenzo or regression prediction branch.  Any
other layout raises, so a file the reference cannot read is never passed.

The decode is written for clarity first and speed second: canonical
Huffman codeword lengths are found for every bit position with numpy,
and one Python pass walks the codeword starts.
"""
from __future__ import annotations

import struct
import zlib
from dataclasses import dataclass, field

import numpy as np

MAGIC = b"TACZ"
_HEADER = struct.Struct("<4sHHQ")
_FOOTER = struct.Struct("<QII4s")
_LEVEL_HEAD_V1 = struct.Struct("<BBBBBHHdQd")
_LEVEL_HEAD_V2 = struct.Struct("<BBBBBBHHdQd")
_SECTIONS = struct.Struct("<QIIQIII")
_SUBBLOCK = struct.Struct("<6I3BQIQQII")

SHE_STRATEGIES = (0, 1, 4)          # opst, akdtree, nast
BRANCH_LORENZO, BRANCH_REG = 0, 1
CODEC_HUFFMAN = 0
COMP_NONE, COMP_ZLIB, COMP_ZSTD = 0, 1, 2


@dataclass
class SubBlock:
    origin: tuple
    size: tuple
    branch: int
    codec: int
    compressor: int
    off: int
    length: int
    nbits: int
    n_codes: int
    betas_len: int
    crc: int


@dataclass
class Level:
    shape: tuple
    grid_shape: tuple
    strategy: int
    sz_block: int
    ratio: int
    eb: float
    n_values: int
    cb: tuple = (0, 0, 0)           # off, len, crc
    mask: tuple = (0, 0, 0)
    mask_compressor: int = COMP_ZLIB
    subblocks: list = field(default_factory=list)


class RefError(ValueError):
    """The file does not decode under the container's rules."""


def _decompress(buf: bytes, comp: int) -> bytes:
    if comp == COMP_NONE:
        return buf
    if comp == COMP_ZLIB:
        return zlib.decompress(buf)
    if comp == COMP_ZSTD:
        import zstandard
        return zstandard.ZstdDecompressor().decompress(buf)
    raise RefError(f"unknown byte pass {comp}")


def _checked(buf: bytes, crc: int, what: str) -> bytes:
    if zlib.crc32(buf) & 0xFFFFFFFF != crc & 0xFFFFFFFF:
        raise RefError(f"{what}: CRC mismatch")
    return buf


class Canonical:
    """Canonical Huffman code from (symbol, length) pairs: codes are
    assigned in (length, symbol) order, each length's first code being
    ``(previous first code + previous count) << 1``."""

    def __init__(self, symbols: np.ndarray, lengths: np.ndarray):
        order = np.lexsort((symbols, lengths))
        self.symbols = symbols[order]
        self.lengths = lengths[order]
        self.maxlen = int(self.lengths.max(initial=0))
        self.first_code = [0] * (self.maxlen + 1)
        self.first_index = [0] * (self.maxlen + 1)
        self.count = [0] * (self.maxlen + 1)
        for length in self.lengths.tolist():
            self.count[length] += 1
        code = idx = 0
        for length in range(1, self.maxlen + 1):
            self.first_code[length] = code
            self.first_index[length] = idx
            code = (code + self.count[length]) << 1
            idx += self.count[length]

    @classmethod
    def parse(cls, buf: bytes) -> "Canonical":
        """u32 count, u8 symbol width (4 or 8), symbols, u8 lengths."""
        n, width = struct.unpack_from("<IB", buf, 0)
        if width not in (4, 8) or len(buf) < 5 + n * (width + 1):
            raise RefError("malformed codebook")
        sym = np.frombuffer(buf, f"<i{width}", n, 5).astype(np.int64)
        lens = np.frombuffer(buf, np.uint8, n, 5 + width * n).astype(np.int64)
        return cls(sym, lens)

    def decode(self, packed: bytes, nbits: int, n: int) -> np.ndarray:
        """``n`` symbols from an MSB-first packed bit stream."""
        if n == 0:
            return np.zeros(0, np.int64)
        if self.maxlen == 0:
            raise RefError("symbols with an empty codebook")
        bits = np.unpackbits(np.frombuffer(packed, np.uint8))[:nbits]
        if bits.size < nbits:
            raise RefError("payload shorter than its bit count")
        ext = np.concatenate([bits, np.zeros(self.maxlen, np.uint8)])
        length_at = np.zeros(nbits, np.int64)
        rank_at = np.zeros(nbits, np.int64)
        value = np.zeros(nbits, np.int64)
        open_ = np.ones(nbits, bool)
        for length in range(1, self.maxlen + 1):
            value = (value << 1) | ext[length - 1:length - 1 + nbits]
            if not self.count[length]:
                continue
            rel = value - self.first_code[length]
            hit = open_ & (rel >= 0) & (rel < self.count[length])
            length_at[hit] = length
            rank_at[hit] = self.first_index[length] + rel[hit]
            open_ &= ~hit
        lens = length_at.tolist()
        starts = [0] * n
        pos = 0
        for k in range(n):
            if pos >= nbits:
                raise RefError("bit stream ends before its last code")
            step = lens[pos]
            if step == 0 or pos + step > nbits:
                raise RefError("no codeword at a code start")
            starts[k] = pos
            pos += step
        return self.symbols[rank_at[np.asarray(starts, np.int64)]]


class RefReader:
    """Index, sections and per-brick decode of one ``.tacz`` file."""

    def __init__(self, path):
        with open(path, "rb") as f:
            self.buf = f.read()
        buf = self.buf
        magic, self.version, _, _ = _HEADER.unpack_from(buf, 0)
        if magic != MAGIC or self.version not in (1, 2):
            raise RefError("not a TACZ v1/v2 file")
        off, length, crc, magic = _FOOTER.unpack_from(buf, len(buf) - 20)
        if magic != MAGIC:
            raise RefError("bad footer")
        index = _checked(buf[off:off + length], crc, "index")
        self.levels = self._parse_index(index)
        self._cb: dict = {}

    def _parse_index(self, idx: bytes) -> list[Level]:
        (n_levels,) = struct.unpack_from("<I", idx, 0)
        pos, out = 4, []
        head = _LEVEL_HEAD_V2 if self.version >= 2 else _LEVEL_HEAD_V1
        for _ in range(n_levels):
            vals = head.unpack_from(idx, pos)
            pos += head.size
            rank, strategy = vals[0], vals[1]
            mask_comp = vals[3]
            sz_block, _unit, ratio, eb, n_values, _dens = vals[-6:]
            if rank != 3:
                raise RefError(f"rank {rank} level")
            shape = struct.unpack_from("<3I", idx, pos)
            grid = struct.unpack_from("<3I", idx, pos + 12)
            pos += 24
            cbo, cbl, cbc, mo, ml, mc, n_sb = _SECTIONS.unpack_from(idx, pos)
            pos += _SECTIONS.size
            lvl = Level(shape=tuple(shape), grid_shape=tuple(grid),
                        strategy=strategy, sz_block=sz_block, ratio=ratio,
                        eb=eb, n_values=n_values, cb=(cbo, cbl, cbc),
                        mask=(mo, ml, mc), mask_compressor=mask_comp)
            for _ in range(n_sb):
                v = _SUBBLOCK.unpack_from(idx, pos)
                pos += _SUBBLOCK.size
                lvl.subblocks.append(SubBlock(tuple(v[0:3]), tuple(v[3:6]),
                                              *v[6:]))
            if n_sb and strategy not in SHE_STRATEGIES:
                raise RefError(f"strategy {strategy} is not a SHE level")
            out.append(lvl)
        return out

    # ------------------------------------------------------------------

    def codebook(self, li: int) -> Canonical:
        if li not in self._cb:
            o, n, c = self.levels[li].cb
            self._cb[li] = Canonical.parse(
                _checked(self.buf[o:o + n], c, "codebook"))
        return self._cb[li]

    def mask(self, li: int) -> np.ndarray:
        lvl = self.levels[li]
        o, n, c = lvl.mask
        if n == 0:
            return np.ones(lvl.shape, bool)
        raw = _decompress(_checked(self.buf[o:o + n], c, "mask"),
                          lvl.mask_compressor)
        count = int(np.prod(lvl.shape))
        return np.unpackbits(np.frombuffer(raw, np.uint8),
                             count=count).astype(bool).reshape(lvl.shape)

    def brick(self, li: int, sbi: int) -> np.ndarray:
        """float32 reconstruction of one sub-block."""
        lvl = self.levels[li]
        sb = lvl.subblocks[sbi]
        if sb.codec != CODEC_HUFFMAN:
            raise RefError(f"payload codec {sb.codec}")
        payload = _checked(self.buf[sb.off:sb.off + sb.length], sb.crc,
                           "payload")
        shape = tuple(int(s) for s in sb.size)
        body = _decompress(payload[sb.betas_len:], sb.compressor)
        codes = self.codebook(li).decode(body, sb.nbits, sb.n_codes)
        if codes.size != int(np.prod(shape)):
            raise RefError("code count does not match the brick")
        two_eb = 2.0 * lvl.eb
        if sb.branch == BRANCH_LORENZO:
            q = codes.reshape(shape)
            for ax in range(3):
                q = np.cumsum(q, axis=ax)
            return (q.astype(np.float64) * two_eb).astype(np.float32)
        if sb.branch == BRANCH_REG:
            return _reg_brick(payload, sb, shape, lvl.sz_block, codes,
                              two_eb)
        raise RefError(f"prediction branch {sb.branch}")

    def level_crop(self, li: int, lbox) -> np.ndarray:
        """The level's reconstruction inside ``lbox`` (level cells), zero
        where the level stores nothing."""
        lvl = self.levels[li]
        ext = tuple(max(hi - lo, 0) for lo, hi in lbox)
        out = np.zeros(ext, np.float32)
        if 0 in ext:
            return out
        for sbi, sb in enumerate(lvl.subblocks):
            isect = [(max(lo, o), min(hi, o + s))
                     for (lo, hi), o, s in zip(lbox, sb.origin, sb.size)]
            if any(hi <= lo for lo, hi in isect):
                continue
            b = self.brick(li, sbi)
            src = tuple(slice(lo - o, hi - o)
                        for (lo, hi), o in zip(isect, sb.origin))
            dst = tuple(slice(lo - b0, hi - b0)
                        for (lo, hi), (b0, _) in zip(isect, lbox))
            out[dst] = b[src]
        m = self.mask(li)[tuple(slice(lo, hi) for lo, hi in lbox)]
        return np.where(m, out, np.float32(0))

    def level(self, li: int) -> np.ndarray:
        return self.level_crop(li, tuple((0, s) for s in
                                         self.levels[li].shape))

    def level_box(self, li: int, box) -> tuple:
        """A finest-grid box in level cells: lows floored, highs ceiled
        through the ratio, both clipped to the level."""
        lvl = self.levels[li]
        r = max(int(lvl.ratio), 1)
        return tuple((min(max(lo // r, 0), s), min(-(-hi // r), s))
                     for (lo, hi), s in zip(box, lvl.shape))


def _reg_brick(payload, sb, shape, sz_block, codes, two_eb):
    """Regression branch: per ``b³`` block a plane ``β0 + β1 i + β2 j +
    β3 k`` over centred coordinates, from float32 betas evaluated in
    float64, plus ``2 eb`` times the residual code."""
    b = min(sz_block, min(shape)) if min(shape) >= 2 else 1
    bgrid = tuple(-(-s // b) for s in shape)
    nb = int(np.prod(bgrid))
    if sb.betas_len != nb * 16:
        raise RefError("betas do not match the block grid")
    betas = np.frombuffer(payload, "<f4", nb * 4, 0).reshape(bgrid + (4,))
    c = np.arange(b, dtype=np.float64) - (b - 1) / 2.0
    bf = betas.astype(np.float64)
    fit = (bf[..., 0, None, None, None]
           + bf[..., 1, None, None, None] * c[:, None, None]
           + bf[..., 2, None, None, None] * c[None, :, None]
           + bf[..., 3, None, None, None] * c[None, None, :])
    res = codes.reshape(bgrid + (b, b, b))
    vals = (fit + two_eb * res).astype(np.float32)
    full = vals.transpose(0, 3, 1, 4, 2, 5).reshape(
        bgrid[0] * b, bgrid[1] * b, bgrid[2] * b)
    return full[:shape[0], :shape[1], :shape[2]]


def eb_gap(recon: np.ndarray, orig: np.ndarray, mask: np.ndarray) -> float:
    """Largest ``|recon − orig|`` over the stored cells (0 if none)."""
    if not mask.any():
        return 0.0
    return float(np.abs(recon[mask].astype(np.float64)
                        - orig[mask].astype(np.float64)).max())
