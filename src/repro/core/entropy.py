"""Batched entropy stage: one engine surface over Huffman encode/decode.

Entropy coding used to be reachable through three divergent ad-hoc
surfaces (``huffman.encode/decode``, ``she.encode_brick_payloads/
decode_brick_payloads``, ``sz.entropy_stage``), each looping Python-level
per payload — the last stage of the pipeline still bit-serial after the
Lorenzo/regression engines were batched.  This module consolidates them
behind one :class:`EntropyEngine` protocol with the same engine pattern
as ``sz.compress_lor_reg_batched``:

  * ``"numpy"``   — the serial, bit-exact oracle (:func:`encode_stream` /
    :func:`decode_stream`, the bodies that used to live in
    ``repro.core.huffman``);
  * ``"batched"`` — vectorized numpy: encode packs ALL payloads in one
    offset-scatter pass over the pooled symbol stream, decode runs a
    canonical-Huffman interval walk over a stacked window matrix
    (symbols within a stream stay sequential, streams advance in
    lockstep);
  * ``"pallas"``  — the window matrix is built by the
    ``repro.kernels.huffdec`` Pallas kernel and the decode walk runs as
    a jitted ``lax.scan`` on the accelerator; encode shares the batched
    host path (bit packing is memory-bound scatter, not FLOPs).  What
    its guards send to the host is counted in
    ``tacz_host_fallbacks_total``, what the device decoded in
    ``tacz_device_items_total``;
  * ``"auto"``    — ``"pallas"`` when a TPU backend is attached,
    ``"batched"`` otherwise.

Every engine is bit-identical to the oracle: encoded payload bytes match
``huffman.encode`` byte-for-byte (each payload is laid out at its own
byte-aligned offset of the pooled bitstream, so per-payload ``packbits``
padding is reproduced exactly), and the batched decoder reproduces the
oracle's outputs *and errors* — including the degenerate empty/
single-symbol codebooks and the exact truncated-vs-corrupt distinction
of the serial bit walk (see :func:`_decode_batched`).
"""
from __future__ import annotations

import contextlib

import numpy as np

from ..obs import metrics as obsm
from . import huffman

__all__ = [
    "ENGINE_NAMES",
    "EntropyEngine",
    "NumpyEngine",
    "BatchedEngine",
    "PallasEngine",
    "get_engine",
    "encode_stream",
    "decode_stream",
]

ENGINE_NAMES = ("auto", "numpy", "batched", "pallas")

# Batched-decode guards: below _MIN_BATCH payloads the per-step numpy
# dispatch overhead loses to the serial walk (results are identical either
# way, so this is purely a heuristic); window values are built in int64,
# so code lengths must leave headroom for the shift-or; the window matrix
# is (payloads, max_bits) int64 — past the element budget fall back to the
# serial walk rather than blow memory (single huge gsp payloads take this
# path, and they are exactly the A=1 case batching cannot help anyway).
_MIN_BATCH = 4
_MAX_BATCH_MAXLEN = 57
_MAX_WINDOW_ELEMS = 1 << 27
# Pallas windows are int32 (maxlen ≤ 30).  One device launch holds its bit
# and window matrices in HBM, each padded to at most this many elements;
# a larger batch runs as several launches, and only a payload too long
# for a launch of its own goes to the host.
_MAX_PALLAS_MAXLEN = 30
_MAX_PALLAS_WINDOW_ELEMS = 1 << 24
_HUFF_STAGE = "huffman_decode"


def _decode_stage(stage: str):
    """Time one stage of a payload decode (``pack``, ``device``,
    ``unpack`` or ``host``) into ``tacz_entropy_decode_stage_seconds``
    and the profiler's ``layer.decode.<stage>``."""
    return obsm.timed(obsm.ENTROPY_DECODE_STAGE_SECONDS.labels(stage),
                      layer="layer.decode." + stage)


# --------------------------------------------------------------------------
# serial primitives — the bit-exact oracle (moved from repro.core.huffman)
# --------------------------------------------------------------------------


def encode_stream(cb: huffman.Codebook, data: np.ndarray, *,
                  indices: np.ndarray | None = None,
                  ) -> tuple[np.ndarray, int]:
    """Encode one symbol stream.  Returns (packed uint8 bitstream, nbits).

    This is the oracle ``huffman.encode`` wraps: offset-scatter bit
    packing — codeword i occupies ``[start_i, start_i + len_i)`` and one
    vectorized pass per bit position fills the dense bitstream.
    ``indices`` may carry a precomputed ``huffman.symbol_indices`` result.
    """
    data = np.asarray(data, dtype=np.int64).ravel()
    if data.size == 0:
        return np.zeros(0, dtype=np.uint8), 0
    idx = huffman.symbol_indices(cb, data) if indices is None else indices
    codes = cb.codes[idx]
    lens = cb.lengths[idx]
    maxlen = int(lens.max())
    ends = np.cumsum(lens)
    starts = ends - lens
    nbits = int(ends[-1])
    bitstream = np.zeros(nbits, dtype=np.uint8)
    sel = np.ones(data.size, dtype=bool)
    for j in range(maxlen):
        if j > 0:
            sel = lens > j
            if not sel.any():
                break
        c, l, s = codes[sel], lens[sel], starts[sel]
        bitstream[s + j] = (c >> (l - 1 - j)) & 1
    packed = np.packbits(bitstream)
    return packed, nbits


def decode_stream(cb: huffman.Codebook, packed: np.ndarray, nbits: int,
                  n_symbols: int) -> np.ndarray:
    """Decode ``n_symbols`` from one packed bitstream (canonical walk).

    This is the oracle ``huffman.decode`` wraps and every batched engine
    is pinned against — its exact error behavior (``"truncated
    bitstream"`` when the stream ends mid-codeword, ``"corrupt
    bitstream"`` when ``maxlen`` bits match nothing, empty/single-symbol
    degenerate codebooks) is part of the engine contract.
    """
    if n_symbols == 0:
        return np.zeros(0, dtype=np.int64)
    symbols = cb.symbols
    if len(symbols) == 0:
        raise ValueError("cannot decode symbols with an empty codebook")
    bits = np.unpackbits(np.asarray(packed, dtype=np.uint8))[:nbits]
    nbits = min(int(nbits), bits.size)
    out = np.empty(n_symbols, dtype=np.int64)
    if len(symbols) == 1:
        # degenerate: single-symbol alphabet, 1 bit per symbol on the wire
        if nbits < n_symbols:
            raise ValueError("truncated bitstream")
        out[:] = symbols[0]
        return out
    maxlen = cb.max_length
    first_code = cb.first_code
    first_index = cb.first_index
    count = cb.count
    i = 0
    bl = bits.tolist()  # python ints — much faster to index than np scalars
    for k in range(n_symbols):
        code = 0
        l = 0
        while True:
            if i >= nbits:
                raise ValueError("truncated bitstream")
            code = (code << 1) | bl[i]
            i += 1
            l += 1
            if l > maxlen:
                raise ValueError("corrupt bitstream")
            c0 = first_code[l]
            if count[l] and code - c0 < count[l] and code >= c0:
                out[k] = symbols[first_index[l] + (code - c0)]
                break
    return out


# --------------------------------------------------------------------------
# payload plumbing
# --------------------------------------------------------------------------


def _as_u8(buf) -> np.ndarray:
    if isinstance(buf, (bytes, bytearray, memoryview)):
        return np.frombuffer(buf, dtype=np.uint8)
    return np.asarray(buf, dtype=np.uint8).ravel()


def _triples(payloads, n_codes) -> list[tuple[np.ndarray, int, int]]:
    """Normalize decode inputs to ``(uint8 buf, nbits, n_codes)`` triples.

    ``payloads`` may be ``(buf, nbits, n_codes)`` triples (the
    ``she.decode_brick_payloads`` shape) or ``(buf, nbits)`` pairs with a
    separate per-payload ``n_codes`` sequence.
    """
    out = []
    if n_codes is None:
        for buf, nbits, nc in payloads:
            out.append((_as_u8(buf), int(nbits), int(nc)))
    else:
        for (buf, nbits), nc in zip(payloads, n_codes, strict=True):
            out.append((_as_u8(buf), int(nbits), int(nc)))
    return out


def _decode_tables(cb: huffman.Codebook):
    """(present lengths, interval uppers, maxlen) — the canonical-decode
    acceleration tables of the batched interval walk.

    Left-justified to ``maxlen`` bits, the windows starting with a
    length-``l`` codeword occupy the half-open interval
    ``[fc_l << (maxlen-l), (fc_l + count_l) << (maxlen-l))``; canonical
    code assignment makes consecutive intervals adjacent and the first
    start at 0, so a single ``searchsorted`` over the interval uppers
    finds the (unique, prefix-free) code length of any window — or lands
    past the last upper for the codeword-free gap an incomplete
    (deserialized) codebook leaves at the top of the range.
    """
    maxlen = cb.max_length
    ls = np.flatnonzero(cb.count[1:maxlen + 1]) + 1
    uppers = ((cb.first_code[ls] + cb.count[ls]).astype(np.int64)
              << (maxlen - ls))
    return ls.astype(np.int64), uppers, maxlen


def _bit_matrix(triples, maxlen: int, pad: int = 0,
                ) -> tuple[np.ndarray, np.ndarray]:
    """(A, max_nbits + maxlen + pad) 0/1 matrix + effective nbits per row.

    Row ``a`` holds payload ``a``'s first ``nbits_a`` bits; everything
    past them is zero (the serial oracle never reads those positions, so
    the zero padding only has to keep the window math in range — the
    walk's error rules make padded windows reproduce the oracle's
    truncation errors, see :func:`_decode_batched`).
    """
    nbits_eff = np.array([min(nb, 8 * buf.size) for buf, nb, _ in triples],
                         dtype=np.int64)
    width = int(nbits_eff.max(initial=0)) + maxlen + pad
    bits = np.zeros((len(triples), width), dtype=np.uint8)
    for a, (buf, _, _) in enumerate(triples):
        nb = int(nbits_eff[a])
        if nb:
            bits[a, :nb] = np.unpackbits(buf, count=nb)
    return bits, nbits_eff


def _window_matrix(bits: np.ndarray, maxlen: int, width: int) -> np.ndarray:
    """``W[a, t]`` = the ``maxlen``-bit window of row ``a`` at bit ``t``,
    as an int64 — ``maxlen`` shift-or passes over the bit matrix."""
    w = np.zeros((bits.shape[0], width), dtype=np.int64)
    for j in range(maxlen):
        w = (w << 1) | bits[:, j:j + width]
    return w


def _raise_payload_error(err_kind: np.ndarray) -> None:
    """Raise the oracle's error for the lowest-index failed payload."""
    bad = np.flatnonzero(err_kind)
    if bad.size:
        kind = int(err_kind[bad[0]])
        raise ValueError("corrupt bitstream" if kind == 2
                         else "truncated bitstream")


def _decode_batched(cb: huffman.Codebook, triples) -> list[np.ndarray]:
    """Vectorized canonical decode of many payloads under one codebook.

    Streams advance in lockstep: each step gathers every live stream's
    current ``maxlen``-bit window, finds its code length with one
    ``searchsorted`` over the interval uppers, and emits one symbol per
    stream.  Error parity with the serial oracle:

      * an accepted codeword that would consume bits past the payload's
        ``nbits`` → ``"truncated bitstream"`` (the oracle hits its
        ``i >= nbits`` check mid-codeword);
      * a window in the codeword-free gap → ``"corrupt bitstream"`` only
        when ``nbits - pos >= maxlen + 1`` (the oracle must successfully
        read ``maxlen + 1`` bits to trip its ``l > maxlen`` check),
        otherwise ``"truncated bitstream"`` — this is what makes the
        zero-padded windows of the stacked matrix safe;
      * with several failing payloads, the error raised is the
        lowest-index one (the oracle iterates payloads in list order).
    """
    ls, uppers, maxlen = _decode_tables(cb)
    symbols = cb.symbols
    first_code = cb.first_code.astype(np.int64)
    first_index = cb.first_index.astype(np.int64)

    bits, nbits_arr = _bit_matrix(triples, maxlen, pad=1)
    width = int(nbits_arr.max(initial=0)) + 1
    wm = _window_matrix(bits, maxlen, width)

    A = len(triples)
    ncodes_arr = np.array([nc for _, _, nc in triples], dtype=np.int64)
    out = np.zeros((A, int(ncodes_arr.max(initial=0))), dtype=np.int64)
    pos = np.zeros(A, dtype=np.int64)
    err_kind = np.zeros(A, dtype=np.int8)     # 0 ok, 1 truncated, 2 corrupt
    rows = np.arange(A)
    for k in range(out.shape[1]):
        act = (k < ncodes_arr) & (err_kind == 0)
        if not act.any():
            break
        r = rows[act]
        w = wm[r, pos[act]]
        ii = np.searchsorted(uppers, w, side="right")
        valid = ii < len(ls)
        l = ls[np.minimum(ii, len(ls) - 1)]
        rem = nbits_arr[r] - pos[r]
        fits = l <= rem
        ok = valid & fits
        corrupt = ~valid & (rem >= maxlen + 1)
        err_kind[r] = np.where(corrupt, 2,
                               np.where(ok, 0, 1)).astype(np.int8)
        okr, lok, wok = r[ok], l[ok], w[ok]
        sym_idx = first_index[lok] + (wok >> (maxlen - lok)) - first_code[lok]
        out[okr, k] = symbols[sym_idx]
        pos[okr] += lok
    _raise_payload_error(err_kind)
    return [out[a, :ncodes_arr[a]].copy() for a in range(A)]


# --------------------------------------------------------------------------
# engines
# --------------------------------------------------------------------------


class EntropyEngine:
    """Protocol: batch entropy coding under one shared codebook.

    ``encode_payloads(cb, streams)`` → one ``(payload bytes, nbits)``
    pair per symbol stream, byte-identical to per-stream
    ``huffman.encode`` + ``packbits`` padding (the TACZ payload framing).
    ``decode_payloads(cb, payloads, n_codes=None)`` → one int64 code
    array per payload; ``payloads`` are ``(buf, nbits, n_codes)``
    triples, or ``(buf, nbits)`` pairs with ``n_codes`` given separately.
    Implementations must match the serial oracle bit-for-bit, errors
    included.
    """

    name = "abstract"

    def encode_payloads(self, cb: huffman.Codebook,
                        streams) -> list[tuple[bytes, int]]:
        raise NotImplementedError

    def decode_payloads(self, cb: huffman.Codebook, payloads,
                        n_codes=None) -> list[np.ndarray]:
        raise NotImplementedError


class NumpyEngine(EntropyEngine):
    """The serial reference: one oracle call per payload."""

    name = "numpy"

    def encode_payloads(self, cb, streams):
        out = []
        for s in streams:
            packed, nbits = encode_stream(cb, np.asarray(s, dtype=np.int64))
            out.append((packed.tobytes(), int(nbits)))
        return out

    def decode_payloads(self, cb, payloads, n_codes=None):
        with _decode_stage("host"):
            return [decode_stream(cb, buf, nbits, nc)
                    for buf, nbits, nc in _triples(payloads, n_codes)]


class BatchedEngine(EntropyEngine):
    """Vectorized numpy: whole-batch encode scatter + lockstep decode."""

    name = "batched"

    def encode_payloads(self, cb, streams):
        streams = [np.asarray(s, dtype=np.int64).ravel() for s in streams]
        sizes = np.array([s.size for s in streams], dtype=np.int64)
        pooled = (np.concatenate(streams) if streams
                  else np.zeros(0, dtype=np.int64))
        if pooled.size == 0:
            return [(b"", 0)] * len(streams)
        # one lookup pass over the pooled stream (the codebook sort inside
        # symbol_indices is paid once, not once per payload)
        idx = huffman.symbol_indices(cb, pooled)
        lens = cb.lengths[idx]
        codes = cb.codes[idx]
        maxlen = int(lens.max())
        cum_bits = np.concatenate(([0], np.cumsum(lens)))
        bounds = np.cumsum(sizes)
        start_sym = bounds - sizes
        nbits_p = cum_bits[bounds] - cum_bits[start_sym]
        bytelen_p = (nbits_p + 7) // 8
        base_bits = 8 * np.concatenate(([0], np.cumsum(bytelen_p)))[:-1]
        # global bit offset of every codeword: its offset inside its own
        # payload's bitstream, shifted to the payload's byte-aligned base —
        # the inter-payload gap bits stay 0, exactly the zero padding
        # per-payload packbits would have emitted, so the sliced bytes are
        # identical to the serial framing
        stream_of = np.repeat(np.arange(len(streams)), sizes)
        starts = (cum_bits[:-1] - cum_bits[start_sym][stream_of]
                  + base_bits[stream_of])
        total_bytes = int(bytelen_p.sum())
        bitstream = np.zeros(total_bytes * 8, dtype=np.uint8)
        sel = np.ones(pooled.size, dtype=bool)
        for j in range(maxlen):
            if j > 0:
                sel = lens > j
                if not sel.any():
                    break
            c, l, s = codes[sel], lens[sel], starts[sel]
            bitstream[s + j] = (c >> (l - 1 - j)) & 1
        packed = np.packbits(bitstream)
        out = []
        for p in range(len(streams)):
            b0 = int(base_bits[p]) // 8
            out.append((packed[b0:b0 + int(bytelen_p[p])].tobytes(),
                        int(nbits_p[p])))
        return out

    def decode_payloads(self, cb, payloads, n_codes=None):
        with _decode_stage("host"):
            triples = self._serial_or_none(cb, _triples(payloads, n_codes))
            if isinstance(triples, list) and triples and \
                    isinstance(triples[0], np.ndarray):
                return triples
            return _decode_batched(cb, triples)

    def _serial_or_none(self, cb, triples):
        """Serial fallback (identical results) for the cases batching
        cannot help: degenerate codebooks, tiny batches, over-deep codes,
        or a window matrix past the memory budget."""
        if self._use_serial(cb, triples):
            return [decode_stream(cb, buf, nbits, nc)
                    for buf, nbits, nc in triples]
        return triples

    @staticmethod
    def _use_serial(cb, triples, *, min_batch: int = _MIN_BATCH,
                    max_maxlen: int = _MAX_BATCH_MAXLEN,
                    max_elems: int = _MAX_WINDOW_ELEMS) -> bool:
        if len(cb.symbols) <= 1 or len(triples) < min_batch:
            return True
        if cb.max_length > max_maxlen:
            return True
        max_bits = max((min(nb, 8 * buf.size) for buf, nb, _ in triples),
                       default=0)
        return len(triples) * (max_bits + cb.max_length + 1) > max_elems


class PallasEngine(BatchedEngine):
    """Decode through the ``repro.kernels.huffdec`` window kernel + jitted
    scan walk; encode shares the batched host scatter (bit packing is a
    memory-bound byte shuffle — there is no FLOP win to move).

    Payloads are sorted by length and cut into launches of bucketed
    shapes (see ``huffdec.padded_shape``), so a process compiles a few
    programs per octave of batch size, not one per batch.  ``device``
    pins the launches to one jax device (default: JAX's default device).
    """

    name = "pallas"

    def __init__(self, device=None):
        self.device = device

    @staticmethod
    def _host_reason(cb, triples) -> str | None:
        """Why the whole batch goes to the host decoders, or None."""
        if len(cb.symbols) <= 1:
            return "degenerate_codebook"
        if len(triples) < _MIN_BATCH:
            return "tiny_batch"
        if cb.max_length > _MAX_PALLAS_MAXLEN:
            return "maxlen"
        return None

    def decode_payloads(self, cb, payloads, n_codes=None):
        triples = _triples(payloads, n_codes)
        if not triples:
            return []
        reason = self._host_reason(cb, triples)
        if reason is not None:
            obsm.HOST_FALLBACKS.labels(_HUFF_STAGE, reason).inc(len(triples))
            return BatchedEngine.decode_payloads(self, cb, triples)
        with _decode_stage("pack"):
            nbits = np.array([min(nb, 8 * buf.size)
                              for buf, nb, _ in triples], dtype=np.int64)
            order = np.argsort(nbits, kind="stable")
            out: list[np.ndarray | None] = [None] * len(triples)
            launches, host = [], []
            chunk: list[int] = []
            for i in order.tolist():
                if self._launch_elems(1, nbits[i]) > _MAX_PALLAS_WINDOW_ELEMS:
                    host.append(i)
                elif self._launch_elems(len(chunk) + 1, nbits[i]) \
                        > _MAX_PALLAS_WINDOW_ELEMS:
                    launches.append(chunk)
                    chunk = [i]
                else:
                    chunk.append(i)
            if chunk:
                launches.append(chunk)
            tables = _device_tables(cb) if launches else None
        ok = True
        if host:
            obsm.HOST_FALLBACKS.labels(_HUFF_STAGE, "window_budget").inc(
                len(host))
            try:
                for i, codes in zip(host, BatchedEngine.decode_payloads(
                        self, cb, [triples[i] for i in host])):
                    out[i] = codes
            except ValueError:
                ok = False
        for idx in launches if ok else ():
            ok = self._launch(cb, tables, triples, nbits, idx, out)
            if not ok:
                break
        if not ok:
            # error path: replay on the host so the raised error is the
            # oracle's, for the lowest-index broken payload
            BatchedEngine.decode_payloads(self, cb, triples)
            raise RuntimeError("device Huffman decode reported a broken "
                               "payload that the host decoders accept")
        return out

    @staticmethod
    def _launch_elems(rows: int, max_nbits: int) -> int:
        from repro.kernels import huffdec

        r, w, _ = huffdec.padded_shape(rows, int(max_nbits) + 1)
        return r * (w + huffdec.HALO)

    def _launch(self, cb, tables, triples, nbits, idx, out) -> bool:
        """Decode payloads ``idx`` (sorted by length) in one device
        launch into ``out``; False when any of them is broken."""
        import jax

        from repro import device
        from repro.kernels import huffdec, ops

        with _decode_stage("pack"):
            maxlen = cb.max_length
            rows, width, col_tile = huffdec.padded_shape(
                len(idx), int(nbits[idx[-1]]) + 1)
            ncodes = np.zeros(rows, dtype=np.int32)
            ncodes[:len(idx)] = [triples[i][2] for i in idx]
            steps = huffdec.bucket(int(ncodes.max()), 8)
            bits = np.zeros((rows, width + huffdec.HALO), dtype=np.uint8)
            nb = np.zeros(rows, dtype=np.int32)
            for r, i in enumerate(idx):
                nb[r] = nbits[i]
                if nb[r]:
                    bits[r, :nb[r]] = np.unpackbits(triples[i][0],
                                                    count=nb[r])
        with _decode_stage("device"):
            with (jax.default_device(self.device) if self.device is not None
                  else contextlib.nullcontext()):
                wm = ops.huffdec_windows(bits, maxlen=maxlen,
                                         col_tile=col_tile)
                sidx, err = huffdec.decode_walk(wm, nb, ncodes, *tables,
                                                maxlen=maxlen, steps=steps)
            err = np.asarray(err)
            sidx = np.asarray(sidx)
            obsm.DEVICE_ITEMS.labels(_HUFF_STAGE,
                                     device.device_label(wm)).inc(len(idx))
        if err.any():
            return False
        with _decode_stage("unpack"):
            # symbol values stay int64 on the host: the walk returns
            # codebook row indices, which always fit the device's int32
            # lanes
            sym = cb.symbols[np.clip(sidx, 0, len(cb.symbols) - 1)]
            for r, i in enumerate(idx):
                out[i] = sym[r, :ncodes[r]].astype(np.int64)
        return True


def _device_tables(cb: huffman.Codebook) -> tuple[np.ndarray, ...]:
    """``(uppers, n_lens, lens, first_code, first_index)`` as int32, padded
    to fixed lengths so every codebook shares one walk program."""
    ls, uppers, _ = _decode_tables(cb)
    n = _MAX_PALLAS_MAXLEN + 2
    up = np.full(n, np.iinfo(np.int32).max, dtype=np.int32)
    up[:uppers.size] = uppers
    lens = np.zeros(n, dtype=np.int32)
    lens[:ls.size] = ls
    fc = np.zeros(n, dtype=np.int32)
    fc[:cb.first_code.size] = cb.first_code
    fi = np.zeros(n, dtype=np.int32)
    fi[:cb.first_index.size] = cb.first_index
    return up, np.int32(ls.size), lens, fc, fi


_ENGINES: dict[str, EntropyEngine] = {}


def get_engine(name: str | EntropyEngine = "auto") -> EntropyEngine:
    """Resolve an entropy engine, mirroring the Lorenzo engine selection.

    ``"auto"`` picks ``"pallas"`` when a TPU backend is attached (same
    probe as ``sz.compress_lor_reg_batched``) and ``"batched"``
    otherwise; an :class:`EntropyEngine` instance passes through
    unchanged.  Instances are cached — engines are stateless.
    """
    if isinstance(name, EntropyEngine):
        return name
    if name not in ENGINE_NAMES:
        raise ValueError(f"unknown entropy engine {name!r} "
                         f"(expected one of {ENGINE_NAMES})")
    if name == "auto":
        from repro.device import tpu_attached
        name = "pallas" if tpu_attached() else "batched"
    eng = _ENGINES.get(name)
    if eng is None:
        eng = _ENGINES.setdefault(
            name, {"numpy": NumpyEngine, "batched": BatchedEngine,
                   "pallas": PallasEngine}[name]())
    return eng


def check_engine_name(name: str | EntropyEngine) -> None:
    """Fail-fast name validation without resolving ``"auto"`` (resolution
    may probe accelerator backends — writers validate at construction but
    resolve lazily, the ``ParallelTACZWriter`` fork-safety pattern)."""
    if not isinstance(name, EntropyEngine) and name not in ENGINE_NAMES:
        raise ValueError(f"unknown entropy engine {name!r} "
                         f"(expected one of {ENGINE_NAMES})")
