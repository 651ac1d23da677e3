"""``repro.serving.regions`` — region serving over a TACZ container.

The canonical read workload against a compressed AMR snapshot is many
overlapping region queries (AMReX visualization study, arXiv:2309.16980),
where repeated sub-block entropy decodes dominate: the Huffman walk is
bit-serial, so decoding the same hot brick for every query that touches it
wastes almost all of the serving budget.  This module turns a ``.tacz``
file into a queryable region service in three layers:

  * :class:`SubBlockCache` — byte-budgeted LRU over *decoded* bricks,
    keyed on (level, sub-block index), with hit/miss/eviction counters.
    Overlapping queries pay each brick's entropy decode once.
  * :class:`DecodePlanner` — maps a batch of ROI boxes to the minimal set
    of *uncached* sub-blocks, entropy-decodes each level's in one batched
    launch, and reconstructs each (level, shape, branch) group through
    one vectorized
    ``sz.decode_codes_batched`` launch instead of PR 2's per-brick serial
    ``decode_codes`` walk.
  * :class:`RegionServer` — ``get_region(level, box)`` /
    ``get_regions(boxes)`` over one reader + cache + planner, with
    snapshot hot-swap keyed on the TACZ footer's index CRC (an atomically
    republished file is detected by a 20-byte footer read, the cache is
    dropped, queries continue against the new snapshot).

Assembly (box mapping, intersection, mask crop) is the reader's own code
path (``TACZReader.assemble_level_roi``), so every served crop is
bit-identical to ``TACZReader.read_roi`` — cold or warm.  The HTTP
endpoint lives in ``repro.serving.http_api``; the matching client in
``repro.serving.client``.
"""
from __future__ import annotations

import json
import struct
import threading
import time
import zlib
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np

from repro import obs
from repro.core import entropy, sz
from repro.io import format as fmt
from repro.io import frontier as frt
from repro.io.reader import (WHOLE_LEVEL, Box, ROILevel, TACZReader,
                             open_snapshot, probe_index_crc)
from repro.obs import metrics as obsm

__all__ = ["CacheKey", "SubBlockCache", "DecodePlanner", "PlannedLevel",
           "RegionServer", "WHOLE_LEVEL", "resolve_single_target"]


def resolve_single_target(reader, target) -> str:
    """Validate a distortion target against a *single* snapshot — the
    serving rule for servers/routers that hold one eb variant only.

    The snapshot's recorded frontier (``reader.frontier``) names the
    metrics of the point it was written at; the request is admitted when
    that default point satisfies the target.  A snapshot with no frontier
    — pre-frontier files, or a corrupt ``TACF`` section the reader
    degraded on — cannot prove anything either way, so the request is
    served as-is and counted in ``tacz_variant_fallbacks_total`` (the
    operator's signal that targets are being ignored, not enforced).

    :param reader: an open snapshot reader (``frontier`` attribute
        optional).
    :param target: a :class:`repro.io.frontier.Target` or its string
        form, e.g. ``"psnr>=60"``.
    :returns: the serving variant name — always ``"default"`` here.
    :raises ValueError: on a malformed target spec.
    :raises repro.io.frontier.TargetUnsatisfiable: when the frontier is
        present and the snapshot's own point misses the target (counted
        in ``tacz_variant_unsatisfied_total``).
    """
    if isinstance(target, str):
        target = frt.parse_target(target)
    fr = getattr(reader, "frontier", None)
    point = fr.default_point if fr is not None else None
    if point is None:
        obsm.VARIANT_FALLBACKS.inc()
    elif not target.satisfies(point.metrics):
        obsm.VARIANT_UNSATISFIED.inc()
        raise frt.TargetUnsatisfiable(target, fr.best_value(target.metric))
    obsm.VARIANT_REQUESTS.labels("default").inc()
    return "default"

# planner key: (level index, sub-block index); WHOLE_LEVEL (re-exported
# from repro.io.reader) marks the full reconstruction of a gsp/global
# level (their payload is not block-local).  In the cache itself keys
# carry a leading snapshot-CRC generation tag — see DecodePlanner.fetch.
CacheKey = tuple[int, int]


class SubBlockCache:
    """Thread-safe byte-budgeted LRU of decoded bricks.

    Keys are hashable tuples (the planner uses
    ``(snapshot_crc, level, sub-block index)``); values are float32
    reconstructions (marked read-only — they are shared across requests).
    Insertion evicts least-recently-used entries until the budget holds
    again; an entry larger than the whole budget is not inserted at all —
    it could never be held, and admitting it would flush the hot set.
    """

    def __init__(self, budget_bytes: int = 256 << 20):
        if budget_bytes <= 0:
            raise ValueError("cache budget must be positive")
        self.budget_bytes = int(budget_bytes)
        self._od: OrderedDict[tuple, np.ndarray] = OrderedDict()
        self._bytes = 0
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get(self, key: tuple) -> np.ndarray | None:
        """Look one brick up, counting a hit (entry becomes MRU) or miss.

        :param key: hashable tuple, e.g. ``(gen, level, sub_block)``.
        :returns: the cached read-only array, or None on a miss.
        """
        with self._lock:
            arr = self._od.get(key)
            if arr is None:
                self.misses += 1
                return None
            self._od.move_to_end(key)
            self.hits += 1
            return arr

    def put(self, key: tuple, brick: np.ndarray) -> None:
        """Insert (or replace) one decoded brick, evicting LRU entries
        until the byte budget holds.

        :param key: hashable tuple, e.g. ``(gen, level, sub_block)``.
        :param brick: decoded array; stored C-contiguous and marked
            read-only (it is shared across requests).  A brick larger
            than the whole budget is silently not inserted.
        """
        brick = np.ascontiguousarray(brick)
        brick.setflags(write=False)
        with self._lock:
            old = self._od.pop(key, None)
            if old is not None:
                self._bytes -= old.nbytes
            if brick.nbytes > self.budget_bytes:
                return   # can never be held — don't flush the hot set
            self._od[key] = brick
            self._bytes += brick.nbytes
            while self._bytes > self.budget_bytes and self._od:
                _, victim = self._od.popitem(last=False)
                self._bytes -= victim.nbytes
                self.evictions += 1

    def peek(self, key: tuple) -> np.ndarray | None:
        """Look one brick up *without* touching counters or LRU order.

        The cache-handoff exporter uses this: serializing a shard's hot
        set for a peer must not skew the hit/miss statistics or promote
        entries the serving workload is not actually using.
        """
        with self._lock:
            return self._od.get(key)

    def drop(self, pred) -> int:
        """Remove every entry whose key matches ``pred(key)``.

        :param pred: predicate over full cache keys (e.g. the 3-tuple
            ``(gen, level, sub_block)`` form the planner uses).
        :returns: number of entries removed.
        """
        with self._lock:
            victims = [k for k in self._od if pred(k)]
            for k in victims:
                self._bytes -= self._od.pop(k).nbytes
            return len(victims)

    def clear(self) -> None:
        """Drop every entry (counters are kept — they are lifetime totals)."""
        with self._lock:
            self._od.clear()
            self._bytes = 0

    def swap_generation(self, old_gen: int, new_gen: int,
                        keep_levels: set) -> int:
        """Carry entries across a snapshot hot-swap, dropping the rest.

        Entries keyed ``(old_gen, level, sub_block)`` whose ``level`` is in
        ``keep_levels`` are re-tagged to ``new_gen`` (LRU order preserved);
        every other entry — changed levels, stale generations from raced
        requests — is dropped.  ``swap_generation(g, g', set())`` is
        :meth:`clear`.  The server calls this with the set of levels whose
        :meth:`repro.io.TACZReader.level_signature` did not change, so a
        republish that only touched some levels keeps the others warm.

        :param old_gen: generation tag (snapshot index CRC) to carry from.
        :param new_gen: generation tag of the newly adopted snapshot.
        :param keep_levels: level indices whose decoded bricks stay valid.
        :returns: number of entries carried over.
        """
        with self._lock:
            od: OrderedDict[tuple, np.ndarray] = OrderedDict()
            nbytes = 0
            for key, arr in self._od.items():
                if (len(key) == 3 and key[0] == old_gen
                        and key[1] in keep_levels):
                    od[(new_gen, key[1], key[2])] = arr
                    nbytes += arr.nbytes
            kept = len(od)
            self._od = od
            self._bytes = nbytes
            return kept

    def __len__(self) -> int:
        with self._lock:
            return len(self._od)

    def __contains__(self, key: tuple) -> bool:
        with self._lock:
            return key in self._od

    @property
    def nbytes(self) -> int:
        """Decoded bytes currently held (always ≤ ``budget_bytes``)."""
        with self._lock:
            return self._bytes

    def stats(self) -> dict:
        """Lifetime counters and current occupancy.

        :returns: dict with ``hits``, ``misses``, ``evictions``,
            ``entries``, ``bytes``, ``budget_bytes``.
        """
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "evictions": self.evictions, "entries": len(self._od),
                    "bytes": self._bytes,
                    "budget_bytes": self.budget_bytes}


@dataclass(frozen=True)
class PlannedLevel:
    """One (level, box) query resolved against the index: which sub-blocks
    the box touches, or whether the whole level must be materialized.

    On a shard-filtered server ``tasks`` holds only *owned* sub-blocks and
    ``owned`` is False for a whole-level plan whose key belongs to another
    shard — such a plan decodes nothing and assembles to zeros (the router
    overlays the owning shard's crop in its place).
    """

    level: int
    lbox: Box
    tasks: tuple[tuple[int, Box], ...]   # (sub-block index, intersection)
    whole_level: bool                    # gsp/global single-payload level
    owned: bool = True                   # False → serve zeros (shard filter)

    def keys(self) -> list[CacheKey]:
        """Cache/placement keys this plan needs decoded.

        :returns: ``[(level, WHOLE_LEVEL)]`` for an owned whole-level
            plan, one ``(level, sub_block)`` key per task otherwise —
            empty for non-owned or empty-box plans.
        """
        if self.whole_level:
            return [(self.level, WHOLE_LEVEL)] if self.owned else []
        return [(self.level, sbi) for sbi, _ in self.tasks]


class DecodePlanner:
    """Batch ROI queries into minimal, grouped decode work.

    ``plan`` resolves (level, box) queries against the reader's index;
    ``fetch`` dedupes the union of needed sub-blocks, consults the cache
    once per unique key, entropy-decodes only the misses, and reconstructs
    them per (level, shape, branch) group through
    ``sz.decode_codes_batched`` — the decode-side analogue of the batched
    SHE encode pipeline.

    :param reader: the open :class:`~repro.io.TACZReader` to plan against.
    :param owned: optional set of ``(level, sub_block)`` keys this planner
        may decode (a shard's slice of ``reader.subblock_keys()``).  When
        given, plans are restricted to owned keys: foreign sub-blocks are
        dropped from ``tasks`` and foreign whole-level plans are marked
        ``owned=False``.  ``None`` (the default) plans everything.
    """

    def __init__(self, reader: TACZReader,
                 owned: set[CacheKey] | None = None):
        self._rd = reader
        self._owned = owned

    def plan(self, queries: list[tuple[int, Box]]) -> list[PlannedLevel]:
        """Resolve ``(level, box)`` queries against the reader's index.

        :param queries: pairs of level index and finest-grid box.
        :returns: one :class:`PlannedLevel` per query, in order.
        :raises ValueError: if a box is not three ``(lo, hi)`` ranges.
        :raises IndexError: if a level index is out of range.
        """
        rd, owned = self._rd, self._owned
        out: list[PlannedLevel] = []
        for li, box in queries:
            if len(box) != 3:
                raise ValueError("box must be ((x0,x1),(y0,y1),(z0,z1))")
            lbox = rd.level_box(li, box)
            if any(hi <= lo for lo, hi in lbox):
                out.append(PlannedLevel(li, lbox, (), False))
            elif rd.levels[li].strategy in TACZReader._SHE_STRATEGIES:
                tasks = rd.intersecting_subblocks(li, lbox)
                if owned is not None:
                    tasks = [t for t in tasks if (li, t[0]) in owned]
                out.append(PlannedLevel(li, lbox, tuple(tasks), False))
            else:
                out.append(PlannedLevel(
                    li, lbox, (), True,
                    owned=owned is None or (li, WHOLE_LEVEL) in owned))
        return out

    def fetch(self, plans: list[PlannedLevel], cache: SubBlockCache,
              ) -> dict[CacheKey, np.ndarray]:
        """Bricks for every key the plans need, decoding only cache misses.

        Each unique key touches the cache exactly once per call, so the
        hit/miss counters reflect unique sub-blocks per request batch, not
        per overlapping box.

        Cache entries are tagged with the snapshot's index CRC: a request
        that raced a hot-swap (old reader, freshly cleared cache) can only
        insert under the *old* generation, which no post-swap request will
        ever look up — stale bricks age out through normal LRU eviction
        instead of being served.

        :param plans: output of :meth:`plan`.
        :param cache: the server's :class:`SubBlockCache`.
        :returns: ``{(level, sub_block): decoded brick}`` covering every
            key of every plan.
        :raises IOError: if a payload fails its CRC check.
        """
        rd = self._rd
        gen = rd.index_crc
        out: dict[CacheKey, np.ndarray] = {}
        missing: list[CacheKey] = []
        missing_set: set[CacheKey] = set()
        for p in plans:
            for key in p.keys():
                if key in out or key in missing_set:
                    continue
                arr = cache.get((gen,) + key)
                if arr is None:
                    missing.append(key)
                    missing_set.add(key)
                else:
                    out[key] = arr
        obsm.PLANNER_SUBBLOCKS.labels("cached").inc(len(out))
        obsm.PLANNER_SUBBLOCKS.labels("decoded").inc(len(missing))
        decoded_bytes = 0
        with obsm.timed(obsm.PLANNER_DECODE_SECONDS.labels(), "decode",
                        "layer.planner.decode"):
            # gsp/global levels: single global payload each — decode
            # serially
            by_level: dict[int, list[int]] = {}
            for li, sbi in missing:
                if sbi == WHOLE_LEVEL:
                    full = rd.read_level(li)
                    cache.put((gen, li, sbi), full)
                    out[(li, sbi)] = full
                    decoded_bytes += full.nbytes
                else:
                    by_level.setdefault(li, []).append(sbi)
            # SHE sub-blocks: one batched EntropyEngine launch per level
            # (its payloads share one codebook), then one vectorized
            # reconstruction per (shape, branch) group — no per-payload
            # serial bit-walk anywhere
            for li, sbis in by_level.items():
                e = rd.levels[li]
                decoded = dict(zip(sbis, rd.decode_subblocks(li, sbis)))
                groups: dict[tuple[tuple[int, ...], int], list[int]] = {}
                for sbi in sbis:
                    groups.setdefault((rd.subblock_shape(li, sbi),
                                       e.subblocks[sbi].branch),
                                      []).append(sbi)
                with obsm.timed(obsm.SERVER_STAGE_SECONDS.labels("recon"),
                                layer="layer.server.recon"):
                    for (shape, branch), g in groups.items():
                        codes = np.stack([decoded[sbi][0] for sbi in g])
                        betas = (np.stack([decoded[sbi][1] for sbi in g])
                                 if branch == fmt.BRANCH_REG else None)
                        recon = sz.decode_codes_batched(
                            codes, shape, e.eb,
                            branch=fmt.BRANCH_NAMES[branch],
                            block=e.sz_block, betas=betas)
                        for sbi, brick in zip(g, recon):
                            # detach from the stacked batch
                            brick = brick.copy()
                            cache.put((gen, li, sbi), brick)
                            out[(li, sbi)] = brick
                            decoded_bytes += brick.nbytes
        obsm.PLANNER_DECODED_BYTES.inc(decoded_bytes)
        return out


def _shard_engine(name, shard_map, shard_id):
    """The engine a shard server decodes with: a device engine is pinned
    to the shard's own local device (see :class:`RegionServer`)."""
    eng = entropy.get_engine(name)
    shards = list(getattr(shard_map, "shards", ()))
    if not isinstance(eng, entropy.PallasEngine) or shard_id not in shards:
        return name
    import jax

    devs = jax.local_devices()
    return entropy.PallasEngine(
        device=devs[shards.index(shard_id) % len(devs)])


class RegionServer:
    """Serve ROI queries from one TACZ snapshot with a hot sub-block cache.

    ``box`` semantics are exactly :meth:`TACZReader.read_roi`'s: half-open
    ranges in finest-grid cells, mapped through each level's coarsening
    ratio.  ``get_region(level, box)`` returns one level's
    :class:`~repro.io.reader.ROILevel`; ``get_regions(boxes)`` plans a
    whole batch at once (one cache pass + one batched decode per group);
    ``get_roi(box)`` mirrors ``read_roi`` (every level, finest first).

    Hot swap: :meth:`maybe_reload` re-reads the file's 20-byte footer and
    compares the index CRC with the serving snapshot's; on change (the
    writer republished via atomic ``os.replace``) the reader is reopened.
    Cache entries for levels whose content signature
    (:meth:`~repro.io.TACZReader.level_signature` — section CRCs, not byte
    offsets) is unchanged are carried over to the new snapshot; the rest
    are dropped.  Pass ``auto_reload=True`` to run the check at the start
    of every request batch (what the HTTP layer does).

    Sharding: pass ``shard_map``/``shard_id`` to restrict the server to
    the sub-blocks the map assigns to that shard.  Foreign sub-blocks are
    never decoded or cached (crops cover them with zeros), so N shard
    servers hold N disjoint cache slices — aggregate cache capacity grows
    ~linearly with N.  The :class:`repro.serving.sharded.ShardedRegionRouter`
    scatter-gathers such servers back into full, bit-identical crops.

    :param path: path of the snapshot to serve — a ``.tacz`` file or a
        multi-part snapshot directory (opened via
        :func:`repro.io.open_snapshot`; the reader surface is the same).
    :param cache_bytes: :class:`SubBlockCache` byte budget (~25 % of the
        decoded level bytes is a good default for overlapping workloads).
    :param auto_reload: run :meth:`maybe_reload` before every batch.
    :param shard_map: an object with ``owner(key) -> shard_id`` (normally
        :class:`repro.serving.sharded.ShardMap`); requires ``shard_id``.
    :param shard_id: this server's shard in ``shard_map``.
    :param entropy_engine: :mod:`repro.core.entropy` engine the reader
        decodes Huffman payloads with on cache misses (``"auto"``/
        ``"numpy"``/``"batched"``/``"pallas"``).  Engines are
        bit-identical, so served crops never depend on the choice;
        hot-swapped readers keep the same engine.  A shard server whose
        engine is ``"pallas"`` decodes on its own device — shard ``i`` of
        the map's sorted shard list on local device ``i mod n`` — so
        shard servers in one process spread over the host's chips.
    :raises ValueError: if only one of ``shard_map``/``shard_id`` is given,
        or the file fails TACZ validation.
    :raises OSError: if the file cannot be opened.
    """

    def __init__(self, path, *, cache_bytes: int = 256 << 20,
                 auto_reload: bool = False, shard_map=None,
                 shard_id: str | None = None,
                 entropy_engine: str = "auto"):
        if (shard_map is None) != (shard_id is None):
            raise ValueError("shard_map and shard_id go together")
        self.path = str(path)
        if shard_map is not None:
            entropy_engine = _shard_engine(entropy_engine, shard_map,
                                           shard_id)
        self.entropy_engine = entropy_engine
        self.auto_reload = bool(auto_reload)
        self.shard_map = shard_map
        self.shard_id = shard_id
        #: optional zero-arg callable invoked at the top of every batch —
        #: a fault-injection point for tests/benchmarks (e.g. a
        #: ``time.sleep`` that makes an SLO latency rule fire on demand).
        #: Exceptions it raises surface as request failures.
        self.fault_hook = None
        self.cache = SubBlockCache(cache_bytes)
        self._lock = threading.Lock()
        # readers displaced by a hot swap, with in-flight request counts:
        # a retired reader closes as soon as its last request drains (or
        # immediately when idle), so republishing never accumulates fds
        self._inflight: dict[int, int] = {}
        self._retired: dict[int, TACZReader] = {}
        self._reader = open_snapshot(self.path,
                                     entropy_engine=entropy_engine)
        self._owned = self._compute_owned(self._reader)
        self._planner = DecodePlanner(self._reader, self._owned)

    def _compute_owned(self, reader: TACZReader) -> set[CacheKey] | None:
        if self.shard_map is None:
            return None
        return {k for k in reader.subblock_keys()
                if self.shard_map.owner(k) == self.shard_id}

    # ------------------------------ lifecycle ------------------------------

    def close(self) -> None:
        """Close the current reader and any hot-swap-retired readers."""
        with self._lock:
            self._reader.close()
            for rd in self._retired.values():
                rd.close()
            self._retired.clear()
            self._inflight.clear()

    def __enter__(self) -> "RegionServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    @property
    def reader(self) -> TACZReader:
        """The reader of the snapshot currently being served."""
        return self._reader

    @property
    def n_levels(self) -> int:
        """Level count of the serving snapshot."""
        return self._reader.n_levels

    @property
    def snapshot_crc(self) -> int:
        """Index CRC of the snapshot currently being served."""
        return self._reader.index_crc

    def maybe_reload(self) -> bool:
        """Swap to a republished snapshot; True when a swap happened.

        Cheap (one footer read) and safe to call per request.  A missing
        or truncated file keeps the current snapshot serving — the writer
        publishes atomically, so a half-written state is never adopted.

        Cache entries are carried over for every level whose content
        signature (section/payload CRCs — see
        :meth:`repro.io.TACZReader.level_signature`) matches the new
        snapshot: a republish that recompressed only some levels keeps the
        other levels' decoded bricks warm.  Entries for changed levels are
        dropped.

        :returns: True when a new snapshot was adopted.
        """
        crc = probe_index_crc(self.path)
        if crc is None or crc == self.snapshot_crc:
            return False
        with self._lock:
            if crc == self.snapshot_crc:                  # raced reload
                return False
            try:
                reader = open_snapshot(self.path,
                                       entropy_engine=self.entropy_engine)
            except (OSError, ValueError):
                return False
            # in-flight requests may still hold the old reader — close it
            # when idle, else park it until its last request drains
            old = self._reader
            keep = {li for li in range(min(old.n_levels, reader.n_levels))
                    if old.level_signature(li) == reader.level_signature(li)}
            if self._inflight.get(id(old), 0) == 0:
                old.close()
            else:
                self._retired[id(old)] = old
            self._reader = reader
            self._owned = self._compute_owned(reader)
            self._planner = DecodePlanner(reader, self._owned)
            self.cache.swap_generation(old.index_crc, reader.index_crc,
                                       keep)
        return True

    # ------------------------------- queries -------------------------------

    def get_regions(self, boxes: list[Box],
                    levels: list[int] | None = None,
                    ) -> list[list[ROILevel]]:
        """Serve a batch of boxes; one list of per-level crops per box.

        The whole batch is planned as one unit: overlapping boxes decode
        each hot sub-block once, and cache misses reconstruct in
        vectorized ``(level, shape, branch)`` groups.  On a shard-filtered
        server, cells belonging to foreign sub-blocks come back as zeros.

        :param boxes: half-open boxes in finest-grid cells.
        :param levels: restrict crops to these level indices (default:
            every level, finest first).
        :returns: ``out[b][l]`` = crop of ``boxes[b]`` at ``levels[l]``.
        :raises ValueError: if a level is out of range or a box malformed.
        :raises IOError: if a payload fails its CRC check.
        """
        return self.get_regions_with_crc(boxes, levels)[1]

    def get_regions_with_crc(self, boxes: list[Box],
                             levels: list[int] | None = None,
                             ) -> tuple[int, list[list[ROILevel]]]:
        """:meth:`get_regions` plus the identity of the snapshot that
        actually served the batch.

        A hot-swap can land *while* a batch is decoding against the
        previous reader; ``self.snapshot_crc`` read afterwards would then
        name the new generation for old data.  Callers that publish the
        CRC next to the payload (the HTTP layer, whose CRC the sharded
        router trusts for its generation check) must use this method.

        :returns: ``(index_crc_of_serving_snapshot, results)``.
        """
        if self.auto_reload:
            self.maybe_reload()
        with self._lock:
            rd, planner = self._reader, self._planner
            self._inflight[id(rd)] = self._inflight.get(id(rd), 0) + 1
        span = obs.trace("get_regions", "layer.server.get_regions")
        span.__enter__()
        t0 = time.perf_counter()
        try:
            hook = self.fault_hook
            if hook is not None:
                hook()
            obsm.SERVER_REGIONS.inc(len(boxes))
            lis = list(range(rd.n_levels)) if levels is None else \
                [int(li) for li in levels]
            for li in lis:
                if not 0 <= li < rd.n_levels:
                    raise ValueError(f"level {li} out of range "
                                     f"(0..{rd.n_levels - 1})")
            queries = [(li, box) for box in boxes for li in lis]
            with obsm.timed(obsm.SERVER_STAGE_SECONDS.labels("plan"), "plan",
                            "layer.server.plan"):
                plans = planner.plan(queries)
            bricks = planner.fetch(plans, self.cache)

            def fetch_brick(li, sbi, _local_hi):
                return bricks[(li, sbi)]

            def fetch_level(li):
                return bricks[(li, WHOLE_LEVEL)]

            out: list[list[ROILevel]] = []
            it = iter(plans)
            with obsm.timed(obsm.SERVER_STAGE_SECONDS.labels("assemble"),
                            layer="layer.server.assemble"):
                for _ in boxes:
                    per_box: list[ROILevel] = []
                    for li in lis:
                        p = next(it)
                        if not p.owned:   # foreign whole-level key: zeros
                            # — the router overlays the owning shard's crop
                            data = np.zeros(tuple(max(hi - lo, 0)
                                                  for lo, hi in p.lbox),
                                            dtype=np.float32)
                        else:
                            data = rd.assemble_level_roi(p.level, p.lbox,
                                                         fetch_brick,
                                                         fetch_level,
                                                         tasks=p.tasks)
                        per_box.append(ROILevel(
                            level=p.level,
                            ratio=max(int(rd.levels[p.level].ratio), 1),
                            box=p.lbox, data=data))
                    out.append(per_box)
            return rd.index_crc, out
        finally:
            span.__exit__(None, None, None)
            obsm.SERVER_REQUEST_SECONDS.labels().observe(
                time.perf_counter() - t0)
            with self._lock:
                n = self._inflight.get(id(rd), 1) - 1
                if n:
                    self._inflight[id(rd)] = n
                else:
                    self._inflight.pop(id(rd), None)
                    retired = self._retired.pop(id(rd), None)
                    if retired is not None:   # last request drained
                        retired.close()

    def get_regions_ex(self, boxes: list[Box],
                       levels: list[int] | None = None, *,
                       target=None, variant: str | None = None,
                       ) -> tuple[int, str | None, list[list[ROILevel]]]:
        """:meth:`get_regions_with_crc` plus distortion-target admission.

        A single-snapshot server holds exactly one eb variant, so the
        only question a ``target`` can ask is whether *this* snapshot's
        recorded frontier point satisfies it (see
        :func:`resolve_single_target`); :class:`repro.serving.variants.
        VariantServer` overrides the surface with real multi-variant
        selection.  This is the method the HTTP layer binds ``target``/
        ``variant`` request fields to.

        :param target: optional distortion target (string or
            :class:`repro.io.frontier.Target`), e.g. ``"psnr>=60"``.
        :param variant: optional explicit variant name — rejected here
            (a single snapshot has no named variants).
        :returns: ``(snapshot_crc, variant_name, results)`` —
            ``variant_name`` is None when no target/variant was given.
        :raises ValueError: on a malformed target or a ``variant`` name.
        :raises repro.io.frontier.TargetUnsatisfiable: when the target
            cannot be met (the HTTP layer maps this to a 400).
        """
        name = None
        if variant is not None:
            raise ValueError(
                f"unknown variant {variant!r}: this endpoint serves a "
                f"single snapshot, not a variant set")
        if target is not None:
            name = resolve_single_target(self._reader, target)
        crc, out = self.get_regions_with_crc(boxes, levels)
        return crc, name, out

    def get_region(self, level: int, box: Box) -> ROILevel:
        """One level's crop of ``box`` (finest-grid cells).

        :param level: level index.
        :param box: three half-open ``(lo, hi)`` ranges in finest cells.
        :returns: the :class:`~repro.io.reader.ROILevel` crop.
        :raises ValueError: if ``level`` is out of range or ``box``
            malformed.
        """
        return self.get_regions([box], levels=[level])[0][0]

    def get_roi(self, box: Box) -> list[ROILevel]:
        """All levels' crops — the cached mirror of ``read_roi(box)``.

        :param box: three half-open ``(lo, hi)`` ranges in finest cells.
        :returns: one crop per level, finest first (file order).
        """
        return self.get_regions([box])[0]

    # --------------------------- cache handoff -----------------------------
    #
    # Live resharding moves sub-block ownership between shard servers.
    # The handoff protocol lets the *new* owner start warm: the old owner
    # serializes its decoded bricks for the moved keys (`cache_export`),
    # the new owner ingests them (`cache_import`), and only then does the
    # old owner adopt the new shard map (`reshard`) and drop the keys.
    # The blob mirrors the /v1/regions framing (u32 header length + JSON
    # header + raw <f4 frames) with two integrity gates: a per-entry
    # zlib.crc32 over the frame bytes, and the exporter's snapshot CRC —
    # bricks from a different snapshot generation are skipped wholesale.

    def cache_export(self, keys: list[CacheKey]) -> bytes:
        """Serialize cached decoded bricks for ``keys`` into a handoff blob.

        Keys not currently cached are silently omitted (the importer's
        peer decodes them cold on first touch); lookups bypass the LRU
        and hit/miss counters.  Exported volume is counted in
        ``tacz_cache_handoff_keys_total`` / ``..._bytes_total``
        (``direction="export"``).

        :param keys: ``(level, sub_block)`` pairs to export.
        :returns: the blob — u32 header length, JSON header
            (``snapshot_crc`` + per-entry ``level/sub_block/shape/offset/
            nbytes/crc32``), then the concatenated ``<f4`` frames.
        """
        if self.auto_reload:
            self.maybe_reload()
        gen = self.snapshot_crc
        entries = []
        frames: list[memoryview] = []
        total = 0
        for li, sbi in keys:
            arr = self.cache.peek((gen, int(li), int(sbi)))
            if arr is None:
                continue
            mv = memoryview(np.ascontiguousarray(arr, dtype="<f4")).cast("B")
            entries.append({"level": int(li), "sub_block": int(sbi),
                            "shape": list(arr.shape),
                            "offset": total, "nbytes": len(mv),
                            "crc32": zlib.crc32(mv) & 0xFFFFFFFF})
            frames.append(mv)
            total += len(mv)
        hdr = json.dumps({"snapshot_crc": gen, "entries": entries},
                         sort_keys=True).encode()
        obsm.HANDOFF_KEYS.labels("export").inc(len(entries))
        obsm.HANDOFF_BYTES.labels("export").inc(total)
        return struct.pack("<I", len(hdr)) + hdr + b"".join(frames)

    def cache_import(self, blob: bytes) -> dict:
        """Ingest a :meth:`cache_export` blob into this server's cache.

        Three per-entry gates, in order: entries from a *different
        snapshot generation* than this server currently serves are
        counted ``skipped_stale`` (a hot-swap between export and import
        invalidates the bricks — not an error); entries this server does
        not *own* under its shard map are counted ``skipped_foreign``;
        a truncated frame or a ``crc32`` mismatch raises — corruption in
        a handoff must never seed the cache with wrong data.  Ingest is
        all-or-nothing: every frame is CRC-verified *before* the first
        one touches the cache, so a corrupt blob leaves it untouched.

        :param blob: bytes produced by a peer's :meth:`cache_export`.
        :returns: summary dict — ``imported``, ``skipped_foreign``,
            ``skipped_stale``, ``bytes``, ``snapshot_crc``.
        :raises ValueError: malformed blob, truncated frame, or CRC
            mismatch.
        """
        if self.auto_reload:
            self.maybe_reload()
        if len(blob) < 4:
            raise ValueError("handoff blob shorter than its length prefix")
        hlen = struct.unpack_from("<I", blob)[0]
        if 4 + hlen > len(blob):
            raise ValueError("handoff blob truncated inside its header")
        try:
            head = json.loads(blob[4:4 + hlen])
            src_crc = int(head["snapshot_crc"])
            entries = head["entries"]
        except (KeyError, TypeError, ValueError) as exc:
            raise ValueError(f"malformed handoff header: {exc}") from None
        gen = self.snapshot_crc
        stale = (src_crc & 0xFFFFFFFF) != (gen & 0xFFFFFFFF)
        base = 4 + hlen
        imported = skipped_foreign = skipped_stale = nbytes = 0
        owned = self._owned
        admitted = []                       # verified (key, frame, shape)
        for e in entries:
            li, sbi = int(e["level"]), int(e["sub_block"])
            if stale:
                skipped_stale += 1
                continue
            if owned is not None and (li, sbi) not in owned:
                skipped_foreign += 1
                continue
            off, n = base + int(e["offset"]), int(e["nbytes"])
            frame = blob[off:off + n]
            if len(frame) != n:
                raise ValueError(
                    f"handoff frame truncated for ({li}, {sbi})")
            if zlib.crc32(frame) & 0xFFFFFFFF != int(e["crc32"]):
                raise ValueError(
                    f"handoff CRC mismatch for ({li}, {sbi})")
            admitted.append(((gen, li, sbi), frame,
                             tuple(int(s) for s in e["shape"])))
        for key, frame, shape in admitted:
            arr = np.frombuffer(frame, dtype="<f4").reshape(shape).copy()
            self.cache.put(key, arr)
            imported += 1
            nbytes += len(frame)
        obsm.HANDOFF_KEYS.labels("import").inc(imported)
        obsm.HANDOFF_BYTES.labels("import").inc(nbytes)
        return {"imported": imported, "skipped_foreign": skipped_foreign,
                "skipped_stale": skipped_stale, "bytes": nbytes,
                "snapshot_crc": gen}

    def reshard(self, shard_map, shard_id: str | None = None) -> int:
        """Adopt a new shard map, dropping cache entries for keys this
        server no longer owns.

        Ordering matters for a live fleet: the *router* must adopt the
        new map (and the new owner must import the moved bricks) before
        old owners call this — a server that reshards early serves zeros
        for its moved keys while the router still queries it for them.

        :param shard_map: the new map (``owner(key) -> shard_id``).
        :param shard_id: this server's shard in the new map (defaults to
            its current ``shard_id``).
        :returns: number of cache entries dropped (now-foreign keys).
        """
        with self._lock:
            self.shard_map = shard_map
            if shard_id is not None:
                self.shard_id = shard_id
            self._owned = self._compute_owned(self._reader)
            self._planner = DecodePlanner(self._reader, self._owned)
            owned = self._owned
        if owned is None:
            return 0
        return self.cache.drop(
            lambda k: len(k) == 3 and (k[1], k[2]) not in owned)

    def stats(self) -> dict:
        """Cache counters plus snapshot identity (and shard info when
        shard-filtered).

        Also refreshes the ``tacz_cache_*`` gauges of the default obs
        registry and reports ``latency`` — request-count plus
        p50/p90/p99 estimates (milliseconds) derived from the
        ``tacz_server_request_seconds`` histogram's buckets.  The
        histogram is process-wide and lifetime (it survives hot swaps,
        like the cache counters).

        :returns: dict with ``hits/misses/evictions/entries/bytes/
            budget_bytes/snapshot_crc/n_levels/latency`` and, on a shard,
            ``shard`` = ``{shard_id, n_shards, owned_keys}``.
        """
        s = self.cache.stats()
        obsm.refresh_cache_gauges(s)
        s["snapshot_crc"] = self.snapshot_crc
        s["n_levels"] = self.n_levels
        hist = obsm.SERVER_REQUEST_SECONDS.labels()
        lat = {"count": hist.count}
        for q, key in ((0.5, "p50_ms"), (0.9, "p90_ms"), (0.99, "p99_ms")):
            est = hist.quantile(q)
            lat[key] = None if est is None else round(est * 1000.0, 3)
        mean = hist.mean()
        lat["mean_ms"] = None if mean is None else round(mean * 1000.0, 3)
        s["latency"] = lat
        if self.shard_map is not None:
            s["shard"] = {"shard_id": self.shard_id,
                          "n_shards": len(self.shard_map),
                          "owned_keys": len(self._owned or ())}
        return s

    def health(self) -> dict:
        """Liveness/readiness report (the body of ``GET /v1/health``).

        Three checks:

        * ``snapshot`` — the published file's footer CRC is readable
          (probe failure ⇒ ``down``: the server could not adopt a
          republish and a restart would not come back), and matches the
          serving snapshot (mismatch ⇒ ``degraded``: an atomic republish
          landed but has not been adopted yet — with ``auto_reload`` the
          next request heals it).
        * ``cache`` — byte-budget headroom (informational: a full cache
          evicting is normal steady state, never unhealthy by itself).
        * ``shard`` — present on a shard-filtered server: this shard's
          identity and owned-key count, so a fleet collector can see a
          shard serving zero keys after a resharding bug.

        :returns: dict with ``status`` (``"ok"`` | ``"degraded"`` |
            ``"down"``), ``snapshot_crc``, and per-check detail under
            ``checks``.  Never raises — a broken server must still be
            able to say *how* it is broken.
        """
        checks: dict = {}
        status = "ok"
        try:
            probe = probe_index_crc(self.path)
        except Exception:   # unreadable path: treat like a failed probe
            probe = None
        if probe is None:
            status = "down"
        elif probe != self.snapshot_crc:
            status = "degraded"
        checks["snapshot"] = {"ok": probe is not None,
                              "serving_crc": self.snapshot_crc,
                              "file_crc": probe,
                              "stale": (None if probe is None
                                        else probe != self.snapshot_crc)}
        cs = self.cache.stats()
        headroom = 1.0 - cs["bytes"] / cs["budget_bytes"]
        checks["cache"] = {"ok": True,
                           "budget_bytes": cs["budget_bytes"],
                           "bytes": cs["bytes"],
                           "headroom": round(headroom, 4)}
        if self.shard_map is not None:
            owned = len(self._owned or ())
            checks["shard"] = {"ok": owned > 0,
                               "shard_id": self.shard_id,
                               "n_shards": len(self.shard_map),
                               "owned_keys": owned}
        return {"status": status, "role": "server",
                "snapshot_crc": self.snapshot_crc, "checks": checks}
