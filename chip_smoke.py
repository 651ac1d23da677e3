"""Bring-up smoke: compress → publish → serve on TPU chips.

    python chip_smoke.py                # one chip, 512³ finest grid
    python chip_smoke.py --chips 4      # four shard servers, one per chip
    python chip_smoke.py --grid 256     # a smaller finest grid

One chip: builds a Nyx Run1-like two-level AMR snapshot (``run1_z10``
densities and contrast) at the finest grid given, compresses it with
TAC+ at a relative error bound of 1e-3, writes it as a TACZ file, serves
it over HTTP from this process and fetches ROI batches cold, warm and
half-warm through ``RegionClient``.  It checks ``max|recon − orig| ≤ eb``
on the compressor's reconstruction and on every served crop, and that
every served crop equals ``TACZReader(path, entropy_engine="numpy")
.read_roi`` bit for bit.

``--chips 4``: the same snapshot served by four shard ``RegionServer``
endpoints — shard i decodes on ``jax.devices()[i]`` — behind a
``ShardedRegionRouter`` endpoint, checked bit for bit against one
unsharded server on the same batches.  Only this path runs.

Earlier lines report stage times (host clock; every stage ends in host
arrays, which waits for the device), the compression ratio, how many
Lorenzo bricks and Huffman payloads each device processed and how many
each host-fallback guard took, XLA compile requests per phase and the
persistent compile-cache hits among them.  The last line is one JSON
object naming the device.  The script exits non-zero, with no JSON line,
when the default JAX backend is not a TPU with enough chips, when a
kernel would run in interpret mode, when the device processed no Lorenzo
brick or no Huffman payload, or when any check fails.  Everything runs
in this one process, which holds the chips.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

PRESET = "run1_z10"
EB_REL = 1e-3


class _Compiles:
    """XLA compile requests and persistent-cache hits, read per phase."""

    def __init__(self):
        import jax

        self.requests = 0
        self.hits = 0

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.requests += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)
        self._mark = (0, 0)

    def phase(self) -> str:
        r, h = self.requests - self._mark[0], self.hits - self._mark[1]
        self._mark = (self.requests, self.hits)
        return f"xla_compiles={r} cache_hits={h}"


def _say(*parts) -> None:
    print(*parts, flush=True)


def _fail(msg: str) -> None:
    raise SystemExit(f"chip_smoke: FAIL: {msg}")


def require_tpu(n_chips: int):
    """The TPU devices to run on; fails unless JAX's default backend is a
    TPU with at least ``n_chips`` chips and kernels compile for it."""
    import jax

    from repro.kernels import ops

    devs = jax.devices()
    if devs[0].platform != "tpu":
        _fail(f"default JAX backend is {devs[0].platform!r}, not a TPU")
    if len(devs) < n_chips:
        _fail(f"{n_chips} chips needed, JAX sees {len(devs)}")
    if ops.default_interpret():
        _fail("Pallas kernels would run in interpret mode")
    return devs[:n_chips]


def _dataset(grid: int):
    from repro.core import amr

    kw = dict(amr.NYX_LIKE_PRESETS[PRESET], finest_shape=(grid,) * 3)
    ds = amr.synthetic_amr(name=f"{PRESET}@{grid}", **kw)
    rng = max(float(lvl.data.max()) for lvl in ds.levels)
    eb = EB_REL * rng
    # float32 round-off on top of eb, as examples/quickstart.py allows
    return ds, eb, eb * (1 + 1e-4) + rng * 2 ** -22


def _check_eb(ds, crops_or_levels, tol, what: str) -> float:
    """Worst |recon − orig| over covered cells; fails past ``tol``."""
    import numpy as np

    worst = 0.0
    for li, lbox, data in crops_or_levels:
        lvl = ds.levels[li]
        sl = tuple(slice(lo, hi) for lo, hi in lbox)
        m = lvl.mask[sl]
        if m.any():
            worst = max(worst, float(np.abs(data[m] - lvl.data[sl][m]).max()))
    if worst > tol:
        _fail(f"{what}: max|recon-orig| = {worst!r} > {tol!r}")
    return worst


def _boxes(grid: int, k: int, shift: int = 0):
    """``k`` cubes of edge grid/8 spread along the main diagonal, moved
    ``shift`` cells up it (clipped to the grid)."""
    e = max(grid // 8, 4)
    step = (grid - e) // max(k - 1, 1)
    return [tuple((lo, lo + e) for _ in range(3))
            for lo in (min(i * step + shift, grid - e) for i in range(k))]


def _crops(results):
    return [(roi.level, roi.box, roi.data) for per_box in results
            for roi in per_box]


def _routing_counts() -> tuple[dict, dict]:
    from repro.obs import metrics as obsm

    dev = {k: int(c.value) for k, c in obsm.DEVICE_ITEMS.children().items()}
    host = {k: int(c.value)
            for k, c in obsm.HOST_FALLBACKS.children().items()}
    return dev, host


def _report_routing(platform: str, since=None) -> dict:
    """Print device items and host fallbacks (deltas since ``since``)."""
    dev, host = _routing_counts()
    if since is not None:
        dev = {k: v - since[0].get(k, 0) for k, v in dev.items()}
        host = {k: v - since[1].get(k, 0) for k, v in host.items()}
    for (stage, label), n in sorted(dev.items()):
        _say(f"  device  {stage:15s} {label:8s} {n}")
        if not label.startswith(platform + ":"):
            _fail(f"{stage} ran {n} items on {label}, not the {platform}")
    for (stage, reason), n in sorted(host.items()):
        if n:
            _say(f"  host    {stage:15s} {reason:20s} {n}")
    return dev


def _report_layers() -> None:
    """Host-clock seconds per instrumented layer (sum / count of each
    ``repro.obs`` histogram series this process recorded)."""
    from repro.obs import metrics as obsm

    _say("layers (host seconds, calls):")
    for fam in (obsm.COMPRESS_STAGE_SECONDS, obsm.COMPRESS_LEVEL_SECONDS,
                obsm.WRITER_LEVEL_SECONDS, obsm.PLANNER_DECODE_SECONDS,
                obsm.ENTROPY_DECODE_SECONDS, obsm.SERVER_REQUEST_SECONDS):
        for labels, h in sorted(fam.children().items()):
            _, total, n = h.snapshot()
            if n:
                name = fam.name + (f"{{{','.join(labels)}}}" if labels
                                   else "")
                _say(f"  {name:48s} {total:.3f} s  {n}")


def _serve(src, **kw):
    from repro.serving import serve

    httpd = serve(src, port=0, **kw)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, f"http://127.0.0.1:{httpd.server_address[1]}"


def _publish(ds, eb, tol, path, comp) -> None:
    """compress_amr → eb check → TACZWriter; prints stage lines."""
    from repro.core import hybrid
    from repro.io import TACZWriter

    t0 = time.perf_counter()
    res = hybrid.compress_amr(ds, eb=eb, unit=8)
    t_comp = time.perf_counter() - t0
    worst = _check_eb(ds, [(li, tuple((0, s) for s in lvl.data.shape),
                            lr.recon)
                           for li, (lvl, lr) in enumerate(zip(ds.levels,
                                                              res.levels))],
                      tol, "compress recon")
    raw = 4 * ds.total_values()          # float32 values the levels store
    _say(f"compress: {t_comp:.3f} s  {raw / t_comp / 1e6:.1f} MB/s raw  "
         f"CR={res.compression_ratio():.3f}  "
         f"subblocks={[lr.n_subblocks for lr in res.levels]}  "
         f"max_err={worst!r} (eb={eb!r})  {comp.phase()}")
    t0 = time.perf_counter()
    with TACZWriter(path, eb=eb) as w:
        for lr in res.levels:
            w.add_compressed(lr)
    t_w = time.perf_counter() - t0
    size = os.path.getsize(path)
    _say(f"write:    {t_w:.3f} s  {size} bytes  file CR={raw / size:.3f}  "
         f"{comp.phase()}")


def one_chip(grid: int) -> dict:
    """compress → publish → serve on one chip; returns the device dict."""
    import numpy as np

    from repro.io import TACZReader
    from repro.serving import RegionClient

    comp = _Compiles()
    dev = require_tpu(1)[0]
    platform = dev.platform
    _say(f"device: {dev.platform}:{dev.id} {dev.device_kind}")
    t0 = time.perf_counter()
    ds, eb, tol = _dataset(grid)
    _say(f"dataset: {ds.name} finest={grid}^3 levels={ds.n_levels} "
         f"densities={[round(d, 4) for d in ds.densities()]} "
         f"values={ds.total_values()}  {time.perf_counter() - t0:.3f} s  "
         f"{comp.phase()}")
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "snapshot.tacz")
        _publish(ds, eb, tol, path, comp)
        budget = sum(lvl.data.nbytes for lvl in ds.levels) // 4
        httpd, url = _serve(path, cache_bytes=budget)
        client = RegionClient(url)
        boxes = _boxes(grid, 4)
        served = {}
        for name, bx in (("cold", boxes), ("warm", boxes),
                         ("half-warm", _boxes(grid, 4, grid // 16))):
            t0 = time.perf_counter()
            out = client.regions(bx)
            dt = time.perf_counter() - t0
            served[name] = (bx, out)
            st = client.stats()
            _say(f"serve {name:9s}: {dt:.3f} s  {len(bx)} boxes  "
                 f"hits={st['hits']} misses={st['misses']}  "
                 f"{comp.phase()}")
        httpd.shutdown()
        httpd.server_close()
        httpd.region_server.close()
        _report_layers()      # before the oracle reads add to them
        t0 = time.perf_counter()
        differ = n = 0
        worst = 0.0
        with TACZReader(path, entropy_engine="numpy") as rd:
            for bx, out in served.values():
                for box, got in zip(bx, out):
                    ref = rd.read_roi(box)
                    for g, r in zip(got, ref):
                        n += 1
                        differ += not (g.box == r.box and
                                       np.array_equal(g.data, r.data))
                worst = max(worst, _check_eb(ds, _crops(out), tol,
                                             "served crop"))
        _say(f"oracle:   {time.perf_counter() - t0:.3f} s  {n} served "
             f"regions, {differ} differ from numpy read_roi  "
             f"max_err={worst!r}")
        if differ:
            _fail(f"{differ} of {n} served regions differ from the oracle")
    _say("routing (items per device, host fallbacks by guard):")
    dev_items = _report_routing(platform)
    for stage in ("lorenzo", "huffman_decode"):
        if not sum(v for (s, _), v in dev_items.items() if s == stage):
            _fail(f"the device processed no {stage} items")
    return dict(platform=platform, kind=dev.device_kind, count=1)


def four_chips(grid: int) -> dict:
    """Four shard servers, one per chip, behind the router, against one
    unsharded server; returns the device dict."""
    import numpy as np

    from repro.serving import (RegionClient, RegionServer, ShardMap,
                               ShardedRegionRouter)

    comp = _Compiles()
    devs = require_tpu(4)
    platform = devs[0].platform
    _say(f"devices: {[f'{d.platform}:{d.id} {d.device_kind}' for d in devs]}")
    ds, eb, tol = _dataset(grid)
    _say(f"dataset: {ds.name} finest={grid}^3 levels={ds.n_levels}  "
         f"{comp.phase()}")
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "snapshot.tacz")
        _publish(ds, eb, tol, path, comp)
        smap = ShardMap([f"s{i}" for i in range(4)])
        shards = [_serve(path, shard_map=smap, shard_id=sid)
                  for sid in smap.shards]
        for (httpd, _), want in zip(shards, devs):
            got = httpd.region_server.entropy_engine.device
            if got != want:
                _fail(f"shard on {got}, expected {want}")
        router = ShardedRegionRouter(path, smap,
                                     {sid: url for sid, (_, url)
                                      in zip(smap.shards, shards)})
        rhttpd, rurl = _serve(router)
        client = RegionClient(rurl)
        batches = [_boxes(grid, 4), _boxes(grid, 3)]
        before = _routing_counts()
        fleet = []
        for i, bx in enumerate(batches):
            t0 = time.perf_counter()
            fleet.append(client.regions(bx))
            _say(f"fleet batch {i}: {time.perf_counter() - t0:.3f} s  "
                 f"{len(bx)} boxes  {comp.phase()}")
        _say("fleet routing (items per device, host fallbacks by guard):")
        dev_items = _report_routing(platform, since=before)
        per_dev = {lab: n for (s, lab), n in dev_items.items()
                   if s == "huffman_decode"}
        idle = [f"{platform}:{x.id}" for x in devs
                if not per_dev.get(f"{platform}:{x.id}")]
        if idle:
            _fail(f"devices {idle} decoded nothing")
        if router.counters["local_fallbacks"]:
            _fail(f"router fell back locally "
                  f"{router.counters['local_fallbacks']} times")
        differ = n = 0
        with RegionServer(path) as single:
            for bx, got in zip(batches, fleet):
                t0 = time.perf_counter()
                ref = single.get_regions(bx)
                _say(f"single server: {time.perf_counter() - t0:.3f} s  "
                     f"{len(bx)} boxes  {comp.phase()}")
                for g_box, r_box in zip(got, ref):
                    for g, r in zip(g_box, r_box):
                        n += 1
                        differ += not np.array_equal(g.data, r.data)
                _check_eb(ds, _crops(got), tol, "fleet crop")
        _say(f"fleet vs single server: {n} regions, {differ} differ")
        if differ:
            _fail(f"{differ} of {n} fleet regions differ from one server")
        rhttpd.shutdown()
        rhttpd.server_close()
        router.close()
        for httpd, _ in shards:
            httpd.shutdown()
            httpd.server_close()
            httpd.region_server.close()
    return dict(platform=platform, kind=devs[0].device_kind, count=4)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--grid", type=int, default=None,
                    help="finest grid edge (default 512 on one chip, "
                         "256 with --chips 4)")
    args = ap.parse_args(argv)
    grid = args.grid or (512 if args.chips == 1 else 256)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.device import enable_compile_cache

    _say(f"compile cache: {enable_compile_cache(HERE)}")
    if grid != 512:
        _say(f"finest grid cut to {grid}^3 (the reference size is 512^3)")
    t0 = time.perf_counter()
    device = one_chip(grid) if args.chips == 1 else four_chips(grid)
    _say(f"total: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
