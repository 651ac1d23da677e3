"""TACZ writer: level serialization + a streaming, double-buffered writer.

Two entry points:

  * :func:`write` — one-shot: serialize an ``AMRCompressionResult`` (the
    output of ``repro.core.hybrid.compress_amr``) or compress-and-write an
    ``AMRDataset`` directly.
  * :class:`TACZWriter` — streaming: ``add_level(data, mask)`` hands raw
    levels to a background encoder thread (bounded queue → double
    buffering: the simulation produces level *i+1* while level *i* is
    being SHE-encoded and appended), and ``close()`` finalizes the index
    and publishes the file atomically via the checkpoint manager's
    tmp + ``os.replace`` pattern — a crashed write never leaves a
    half-valid ``.tacz`` behind.

Serializable levels are the TAC+ SHE path (per-sub-block payloads under
one shared-Huffman codebook per level — the random-access case), GSP /
global single-payload levels, and raw-code "global" tensor levels (see
``repro.io.tensor``).  The merged-4D non-SHE path interleaves sub-blocks
inside shared code streams, so it has no per-sub-block payload to index;
asking to serialize it raises with a pointer at ``she=True``.
"""
from __future__ import annotations

import os
import queue
import threading
import time
import weakref
import zlib

import numpy as np

from repro.core import entropy, huffman
from repro.obs import metrics as obsm
from repro.core.amr import AMRDataset
from repro.core.compat import HAVE_ZSTD, zstd_compress
from repro.core.hybrid import (AMRCompressionResult, LevelResult,
                               compress_level)
from repro.core.sz import SZResult

from . import format as fmt
from . import frontier as frt

__all__ = ["TACZWriter", "pack_level", "write"]


def resolve_payload_codec(codec: str) -> int:
    """Map a payload-codec name to its COMPRESSOR_* wire code.

    ``"auto"`` (the default everywhere) picks zstd when the optional
    ``zstandard`` module is importable and degrades to stdlib zlib
    otherwise (``repro.core.compat``); ``"none"`` disables the v2
    lossless pass, reproducing v1's raw packed-bits payloads.
    """
    if codec == "none":
        return fmt.COMPRESSOR_NONE
    if codec == "zlib":
        return fmt.COMPRESSOR_ZLIB
    if codec == "zstd":
        if not HAVE_ZSTD:
            raise ModuleNotFoundError(
                "payload_codec='zstd' but zstandard is not installed "
                "(use 'auto' to fall back to zlib)")
        return fmt.COMPRESSOR_ZSTD
    if codec == "auto":
        return fmt.COMPRESSOR_ZSTD if HAVE_ZSTD else fmt.COMPRESSOR_ZLIB
    raise ValueError(f"unknown payload codec {codec!r}")


def _lossless_pass(buf: bytes, compressor: int) -> tuple[bytes, int]:
    """Apply the configured byte pass to one payload's code bytes.

    Size-reducing only: the compressed form is kept when strictly smaller,
    otherwise the raw bytes go to the wire as ``COMPRESSOR_NONE`` — the
    per-sub-block compressor field records what actually happened, so the
    reader never pays an inflate for a pass that lost.
    """
    if compressor == fmt.COMPRESSOR_NONE or len(buf) < 16:
        return buf, fmt.COMPRESSOR_NONE
    if compressor == fmt.COMPRESSOR_ZSTD:
        comp = zstd_compress(buf)
    else:
        comp = zlib.compress(buf, 6)
    if len(comp) < len(buf):
        return comp, compressor
    return buf, fmt.COMPRESSOR_NONE


def _branch_code(r: SZResult) -> int:
    b = (r.extras or {}).get("branch")
    if b == "reg":
        return fmt.BRANCH_REG
    if b == "lorenzo" or r.method == "lorenzo":
        return fmt.BRANCH_LORENZO
    if r.method == "interp":
        return fmt.BRANCH_INTERP
    raise ValueError(f"cannot serialize SZ method {r.method!r}")


def _betas_bytes(r: SZResult) -> bytes:
    if (r.extras or {}).get("branch") != "reg":
        return b""
    return np.ascontiguousarray(r.extras["betas"], dtype="<f4").tobytes()


def pack_level(lr: LevelResult, *, payload_codec: str = "auto",
               entropy_engine: str = "auto",
               ) -> tuple[bytes, fmt.LevelEntry]:
    """Serialize one compressed level into (section blob, index entry).

    Offsets inside the returned entry are blob-relative; the caller places
    the blob in the file and calls ``entry.shift_offsets(base)``.

    ``payload_codec`` selects the v2 lossless byte pass over each
    payload's packed-Huffman code bytes (betas prefixes stay raw):
    ``"auto"`` → zstd, or zlib when zstandard is missing; ``"none"``
    reproduces v1's raw payloads.  The pass is recorded per level
    (``payload_compressor``) and per sub-block (only where it shrank).

    GSP/global levels reuse the codebook and packed payload the
    compress-time entropy stage already materialized
    (``SZResult.extras["entropy"]``) instead of re-encoding the same
    single stream — the write-path memoization the ROADMAP tracked.

    Artifacts with an *empty* result list (a parallel part writer's stub
    for a level whose every sub-block lives in other parts) serialize to
    a head + mask section only: no codebook, no payloads.

    ``entropy_engine`` selects the :mod:`repro.core.entropy` engine that
    packs the level's payloads (one batched launch instead of one encode
    per sub-block); every engine emits byte-identical payloads.
    """
    art = lr.artifacts
    if art is None:
        raise ValueError(
            "level has no serialization artifacts — the merged-4D non-SHE "
            "path is not indexable; compress with she=True (TAC+) or "
            "strategy='gsp', and keep_artifacts=True")
    if lr.strategy not in fmt.STRATEGY_CODES:
        raise ValueError(f"unknown strategy {lr.strategy!r}")

    blob = bytearray()

    def append(section: bytes) -> tuple[int, int]:
        off = len(blob)
        blob.extend(section)
        return off, len(section)

    entry = fmt.LevelEntry(
        shape=tuple(int(s) for s in art.orig_shape),
        grid_shape=tuple(int(s) for s in art.grid_shape),
        strategy=fmt.STRATEGY_CODES[lr.strategy],
        algorithm=fmt.ALGO_CODES[lr.algorithm],
        unit=int(art.unit), sz_block=int(art.sz_block), ratio=int(lr.ratio),
        eb=float(lr.eb), n_values=int(lr.n_values), density=float(lr.density))

    # --- shared codebook section (one per level, paper Alg. 4) -------------
    # (omitted, codebook_len = 0, when this part holds no payloads at all)
    memo = None
    if not art.results:
        cb = None
    elif lr.she:
        cb = art.codebook
    else:
        # gsp/global levels: one payload.  The compress-time entropy stage
        # already built the (deterministic) codebook and packed bitstream —
        # reuse both when present; rebuild only for artifacts produced
        # without entropy accounting.
        r0 = art.results[0]
        ent = (r0.extras or {}).get("entropy")
        if (len(art.results) == 1 and ent is not None
                and ent.get("codebook") is not None):
            memo = ent
            cb = ent["codebook"]
        else:
            cb = huffman.build_codebook(np.asarray(r0.codes,
                                                   dtype=np.int64))
    if art.results:
        cb_bytes = huffman.serialize_codebook(cb)
        entry.codebook_off, entry.codebook_len = append(cb_bytes)
        entry.codebook_crc = zlib.crc32(cb_bytes)

    # --- validity mask section (packbits + zlib; omitted when all-True) ----
    mask = np.asarray(art.mask, dtype=bool)
    if not mask.all():
        mask_bytes = zlib.compress(np.packbits(mask.ravel()).tobytes(), 6)
        entry.mask_off, entry.mask_len = append(mask_bytes)
        entry.mask_crc = zlib.crc32(mask_bytes)
        entry.mask_compressor = fmt.COMPRESSOR_ZLIB

    # --- sub-block payloads (byte-aligned, independently decodable) --------
    level_comp = resolve_payload_codec(payload_codec)
    entry.payload_compressor = level_comp
    if not art.results:
        return bytes(blob), entry
    if art.subblocks:
        subblocks, results = art.subblocks, art.results
        origins = [sb.cell_origin(art.unit) for sb in subblocks]
        sizes = [sb.cell_size(art.unit) for sb in subblocks]
    else:
        # single payload covering the whole (padded) grid; origin/size are
        # informative for 3D levels only (higher ranks decode via shape)
        results = art.results
        origins = [(0, 0, 0)]
        gs = tuple(int(s) for s in art.grid_shape[:3])
        sizes = [gs + (1,) * (3 - len(gs))]
    if memo is not None:
        payloads = [(memo["packed"], memo["nbits"])]
    else:
        # one engine launch packs every sub-block payload of the level
        # (byte-identical framing to per-payload encode, any engine)
        payloads = entropy.get_engine(entropy_engine).encode_payloads(
            cb, [np.asarray(r.codes, dtype=np.int64) for r in results])
    for r, (packed, nbits), origin, size in zip(results, payloads,
                                                origins, sizes):
        betas = _betas_bytes(r)
        stored, comp = _lossless_pass(packed, level_comp)
        payload = betas + stored
        off, length = append(payload)
        entry.subblocks.append(fmt.SubBlockEntry(
            origin=tuple(int(o) for o in origin),
            size=tuple(int(s) for s in size),
            branch=_branch_code(r), codec=fmt.CODEC_HUFFMAN,
            compressor=comp,
            payload_off=off, payload_len=length, nbits=int(nbits),
            n_codes=int(np.asarray(r.codes).size), betas_len=len(betas),
            crc=zlib.crc32(payload)))
    return bytes(blob), entry


def build_container(packed: list[tuple[bytes, fmt.LevelEntry]], *,
                    version: int = fmt.TACZ_VERSION) -> bytes:
    """Assemble header + level blobs + index + footer into one buffer
    (the in-memory path used for checkpoint tensor blobs).  ``version``
    exists for back-compat tooling/tests that emit v1 indexes; payloads
    must then not rely on v2-only index fields."""
    out = bytearray(fmt.pack_header(version=version))
    entries = []
    for blob, entry in packed:
        entry.shift_offsets(len(out))
        out.extend(blob)
        entries.append(entry)
    index = fmt.pack_index(entries, version=version)
    index_off = len(out)
    out.extend(index)
    out.extend(fmt.pack_footer(index_off, len(index), fmt.index_crc(index)))
    return bytes(out)


_SENTINEL = object()


def _nudge(q: queue.Queue) -> None:
    """GC finalizer: wake the encoder thread of an abandoned writer."""
    try:
        q.put_nowait(_SENTINEL)
    except queue.Full:   # worker is mid-item; it re-checks liveness next get
        pass


def _reap_sync(f, tmp: str) -> None:
    """GC finalizer for a ``background=False`` writer abandoned without
    close()/abort(): close the fd and drop the never-published tmp."""
    try:
        f.close()
    except OSError:      # pragma: no cover - already closed
        pass
    try:
        os.remove(tmp)
    except OSError:
        pass


def _worker_loop(wref, q: queue.Queue, f, tmp: str) -> None:
    """Encoder-thread body.  Holds only a weakref to the writer so an
    abandoned ``TACZWriter`` (never ``close()``d) can be collected; on
    collection the thread wakes (via the ``weakref.finalize`` nudge or the
    next queued item), closes the fd, unlinks the tmp file, and exits —
    no thread/fd/tmp leak per failed write."""
    while True:
        item = q.get()
        w = wref()
        try:
            if item is _SENTINEL or w is None:
                if w is None:   # abandoned without close()/abort()
                    try:
                        f.close()
                    except OSError:  # pragma: no cover
                        pass
                    try:
                        os.remove(tmp)
                    except OSError:
                        pass
                return
            if w._err is None and not w._aborted:
                w._append_level(w._encode(item))
        except BaseException as exc:  # propagate to the producer thread
            if w is not None:
                w._err = exc
        finally:
            del w
            q.task_done()


class TACZWriter:
    """Streaming TACZ writer with a background encoder thread.

    ``add_level`` enqueues a snapshot of the level and returns immediately;
    a worker thread runs the batched SHE pipeline and appends the encoded
    sections.  The queue is bounded: with the default ``queue_depth=2``
    the producer can hold two snapshots queued while a third encodes
    (peak three in-flight levels); pass ``queue_depth=1`` for strict
    double buffering (one queued + one encoding).

    The file is written to ``<path>.tmp`` and moved into place by
    ``close()`` via ``os.replace`` — readers never observe a partial file.
    Use as a context manager; a writer dropped without ``close()`` /
    ``abort()`` is still reaped at GC time (encoder thread exits, fd
    closed, tmp unlinked) but the file is never published.

    :param path: destination ``.tacz`` path.
    :param eb: default absolute error bound for :meth:`add_level` (may
        also be passed per level).
    :param unit: default unit-block edge in cells; per-level units follow
        the ``compress_amr`` domain-tracking rule (see :meth:`add_level`).
    :param algorithm: prediction algorithm (``"lor_reg"``/``"lorenzo"``/
        ``"interp"``).
    :param she: encode SHE (per-sub-block payload) levels — required for
        random access; ``False`` only makes sense with ``strategy="gsp"``.
    :param strategy: partitioning strategy override (default: per-level
        auto selection).
    :param sz_block: Lorenzo/regression block edge in cells.
    :param batched: run the batched SHE pipeline (bit-identical, faster).
    :param lorenzo_engine: ``"auto"``/``"numpy"``/``"pallas"`` for the
        Lorenzo branch.
    :param payload_codec: v2 lossless byte pass — ``"auto"`` (zstd, zlib
        fallback), ``"zstd"``, ``"zlib"``, or ``"none"`` (v1 payloads).
    :param queue_depth: bounded encode queue length (≥1).
    :param background: run the encoder on a background thread (the
        double-buffering default).  ``background=False`` encodes inline
        in the calling thread — ``add_level`` then blocks but never
        contends for the GIL with a second thread, which is what a
        caller that *is already* a dedicated worker wants (each part
        worker of ``repro.io.parallel`` writes this way).
    :raises ValueError: on an unknown ``payload_codec`` name.
    :raises OSError: if the tmp file cannot be created.
    """

    def __init__(self, path: str, *, eb: float | None = None, unit: int = 8,
                 algorithm: str = "lor_reg", she: bool = True,
                 strategy: str | None = None, sz_block: int = 6,
                 batched: bool = True, lorenzo_engine: str = "auto",
                 entropy_engine: str = "auto",
                 payload_codec: str = "auto", queue_depth: int = 2,
                 background: bool = True):
        self.path = str(path)
        self._tmp = self.path + ".tmp"
        resolve_payload_codec(payload_codec)   # fail fast on bad names
        entropy.check_engine_name(entropy_engine)
        self._payload_codec = payload_codec
        self._entropy_engine = entropy_engine
        self._defaults = dict(eb=eb, unit=unit, algorithm=algorithm, she=she,
                              strategy=strategy, sz_block=sz_block,
                              batched=batched, lorenzo_engine=lorenzo_engine,
                              entropy_engine=entropy_engine)
        self._f = open(self._tmp, "wb")
        self._f.write(fmt.pack_header())
        self._off = fmt.HEADER_SIZE
        self._entries: list[fmt.LevelEntry] = []
        self._frontier: frt.Frontier | None = None
        #: index CRC of the published file (set by :meth:`close` — the
        #: same value ``probe_index_crc`` reads back from the footer)
        self.index_crc: int | None = None
        self._err: BaseException | None = None
        # plain per-writer stage totals (no registry round-trip): the
        # process-mode parallel writer ships these back over the result
        # queue so the producer can merge them into its own registry
        self._obs = {"levels": 0, "encode_seconds": 0.0,
                     "pack_seconds": 0.0, "publish_seconds": 0.0,
                     "bytes": 0}
        self._background = bool(background)
        self._finalized = False          # close() published the file
        self._aborted = False            # tmp dropped; writer unusable
        self._sentinel_sent = False
        if self._background:
            self._queue: queue.Queue = queue.Queue(
                maxsize=max(1, queue_depth))
            self._thread = threading.Thread(
                target=_worker_loop,
                args=(weakref.ref(self), self._queue, self._f, self._tmp),
                daemon=True)
            self._thread.start()
            self._reaper = weakref.finalize(self, _nudge, self._queue)
        else:
            self._queue = None
            self._thread = None
            # still reap an abandoned writer: close the fd, drop the tmp
            self._reaper = weakref.finalize(self, _reap_sync, self._f,
                                            self._tmp)

    # ------------------------------ producer -------------------------------

    def add_level(self, data: np.ndarray, mask: np.ndarray | None = None, *,
                  eb: float | None = None, ratio: int = 1,
                  unit: int | None = None) -> None:
        """Queue one raw level for encoding (snapshot taken immediately).

        ``unit`` defaults to ``max(2, default_unit // ratio)`` — the same
        domain-tracking rule ``compress_amr`` applies, so a streamed file
        decodes bit-identically to the one-shot path.
        """
        self._check_live()
        eb = self._defaults["eb"] if eb is None else eb
        if eb is None:
            raise ValueError("no error bound: pass eb= here or to the writer")
        if unit is None:
            unit = max(2, int(self._defaults["unit"]) // max(int(ratio), 1))
        data = np.array(data, dtype=np.float32, copy=True)
        mask = (data != 0) if mask is None else np.array(mask, dtype=bool,
                                                         copy=True)
        self._put(("raw", data, mask, float(eb), int(ratio), int(unit)))

    def add_compressed(self, lr: LevelResult) -> None:
        """Queue an already-compressed level (needs ``artifacts``)."""
        self._check_live()
        if lr.artifacts is None:
            raise ValueError(
                "LevelResult has no serialization artifacts — the merged-4D "
                "non-SHE path is not indexable (compress with she=True or "
                "strategy='gsp'), and compression must run with "
                "keep_artifacts=True")
        self._put(("level", lr))

    def set_frontier(self, frontier: frt.Frontier | None) -> None:
        """Attach a rate–distortion frontier (``repro.io.frontier``) to
        this snapshot.  ``close()`` then writes it as the optional
        ``TACF`` section between the index and the footer — the footer
        keeps framing only the index, so readers that predate the
        section skip it untouched."""
        self._check_live()
        self._frontier = frontier

    def close(self, *, publish: bool = True) -> str:
        """Drain the queue, write index + footer, publish atomically.

        Raises the background encoder's error (if any) — even when that
        error already surfaced through ``add_level`` — after dropping the
        tmp file; the destination path is never reported as written
        unless it actually was.

        ``publish=False`` finalizes the file completely (index, footer,
        fsync, fd closed) but leaves it at ``<path>.tmp`` and returns
        that tmp path — the multi-part writer's two-phase commit: every
        part finalizes first, and only when all of them succeeded are
        they renamed into place, so a failing sibling can never leave a
        previously published snapshot half-replaced.
        """
        if self._finalized:
            return self.path
        self._stop_worker()
        if self._aborted:
            raise ValueError("writer was aborted")
        try:
            if self._err is not None:
                raise self._err
            with obsm.timed(obsm.WRITER_LEVEL_SECONDS.labels("publish"),
                            "publish", "layer.writer.publish"):
                t0 = time.perf_counter()
                index = fmt.pack_index(self._entries)
                self._f.write(index)
                self.index_crc = fmt.index_crc(index)
                if self._frontier is not None:
                    # optional TACF section between index and footer —
                    # the footer frames only the index, so pre-frontier
                    # readers skip these bytes without noticing
                    self._f.write(frt.pack_section(self._frontier))
                self._f.write(fmt.pack_footer(self._off, len(index),
                                              self.index_crc))
                self._f.flush()
                os.fsync(self._f.fileno())
                self._f.close()
                if publish:
                    os.replace(self._tmp, self.path)
                self._obs["publish_seconds"] += time.perf_counter() - t0
        except BaseException:
            self.abort()
            raise
        self._finalized = True
        return self.path if publish else self._tmp

    def abort(self) -> None:
        """Drop the partial file (used on error paths)."""
        self._aborted = True
        self._stop_worker()
        try:
            self._f.close()
        except OSError:  # pragma: no cover - double close
            pass
        try:
            os.remove(self._tmp)
        except OSError:
            pass

    def __enter__(self) -> "TACZWriter":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.close()
        else:
            self.abort()

    # ------------------------------ worker ---------------------------------

    def _stop_worker(self) -> None:
        if not self._sentinel_sent:
            self._sentinel_sent = True
            self._reaper.detach()   # orderly shutdown owns cleanup now
            if self._background:
                self._queue.put(_SENTINEL)
        if self._thread is not None:
            self._thread.join()

    def _check_live(self) -> None:
        if self._finalized or self._aborted or self._sentinel_sent:
            raise ValueError("writer is closed")
        if self._err is not None:
            raise self._err

    def _put(self, item) -> None:
        if self._background:
            self._queue.put(item)
            return
        try:                  # inline encode: errors surface immediately
            self._append_level(self._encode(item))
        except BaseException as exc:
            self._err = exc   # close() must keep refusing to publish
            raise

    def _encode(self, item) -> LevelResult:
        if item[0] == "level":
            return item[1]
        _, data, mask, eb, ratio, unit = item
        d = self._defaults
        with obsm.timed(obsm.WRITER_LEVEL_SECONDS.labels("encode"),
                        "encode", "layer.writer.encode"):
            t0 = time.perf_counter()
            lr = compress_level(data, mask, eb=eb, unit=unit,
                                algorithm=d["algorithm"], she=d["she"],
                                strategy=d["strategy"],
                                sz_block=d["sz_block"],
                                batched=d["batched"],
                                lorenzo_engine=d["lorenzo_engine"],
                                entropy_engine=d["entropy_engine"],
                                ratio=ratio, keep_artifacts=True)
            self._obs["encode_seconds"] += time.perf_counter() - t0
            return lr

    def _append_level(self, lr: LevelResult) -> None:
        with obsm.timed(obsm.WRITER_LEVEL_SECONDS.labels("pack"), "pack",
                        "layer.writer.pack"):
            t0 = time.perf_counter()
            blob, entry = pack_level(lr, payload_codec=self._payload_codec,
                                     entropy_engine=self._entropy_engine)
            entry.shift_offsets(self._off)
            self._f.write(blob)
            self._off += len(blob)
            self._entries.append(entry)
            self._obs["pack_seconds"] += time.perf_counter() - t0
            self._obs["levels"] += 1
            self._obs["bytes"] += len(blob)
        obsm.WRITER_LEVELS.inc()
        obsm.WRITER_BYTES.inc(len(blob))

    def obs_summary(self) -> dict:
        """Plain-dict stage totals for this writer (levels appended,
        encode/pack/publish seconds, payload bytes).  Process-mode part
        workers return this through the result queue so the producer can
        fold worker time into its own registry — worker processes have
        their own (unscraped) ``repro.obs`` registry."""
        return dict(self._obs)


def write(path: str, obj, *, eb: float | list[float] | None = None,
          frontier: frt.Frontier | None = None, **kwargs) -> str:
    """Write ``obj`` to a TACZ container at ``path``.

    ``obj`` may be an ``AMRCompressionResult`` (already compressed with
    ``keep_artifacts=True`` — the default) or an ``AMRDataset`` (compressed
    here, level by level, through the streaming writer; ``eb`` is required
    and may be per-level).  ``frontier`` attaches an optional rate–
    distortion frontier (``TACF`` section).  Returns ``path``.
    """
    if isinstance(obj, AMRCompressionResult):
        with TACZWriter(path, **kwargs) as w:
            for lr in obj.levels:
                w.add_compressed(lr)
            if frontier is not None:
                w.set_frontier(frontier)
        return path
    if isinstance(obj, AMRDataset):
        if eb is None:
            raise ValueError("writing a raw AMRDataset needs eb=")
        ebs = eb if isinstance(eb, (list, tuple)) else [eb] * obj.n_levels
        if len(ebs) != obj.n_levels:
            raise ValueError("need one error bound per level")
        with TACZWriter(path, **kwargs) as w:
            for lvl, e in zip(obj.levels, ebs):
                w.add_level(lvl.data, lvl.mask, eb=float(e), ratio=lvl.ratio)
            if frontier is not None:
                w.set_frontier(frontier)
        return path
    raise TypeError(f"cannot write {type(obj).__name__} as TACZ")
