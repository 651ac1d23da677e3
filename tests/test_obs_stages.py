"""Stage instrumentation: the compress, decode and serving stages tile
their parents' time, name themselves in a JAX profiler trace as
``layer.<component>.<stage>``, and the collector's pauses are timed.

The profiler half is read back with the on-chip benchmark's own trace
reduction (``benchmarks/chip/tracered.py``), which labels each device
idle gap with the innermost ``layer.*`` annotation covering it."""
import gc
import importlib.util
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro import obs
from repro import io as tacz
from repro.core import amr, hybrid
from repro.obs import metrics as obsm
from repro.serving import AsyncServingCore, RegionServer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPRESS_STAGES = ("partition", "gather", "prequant", "branch_score",
                   "recon", "entropy")


def _tracered():
    name = "bench_tracered"
    if name not in sys.modules:
        path = os.path.join(REPO, "benchmarks", "chip", "tracered.py")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod       # its dataclasses look themselves up
        spec.loader.exec_module(mod)
    return sys.modules[name]


@pytest.fixture(autouse=True)
def metrics_enabled():
    was = obs.is_enabled()
    obs.set_enabled(True)
    yield
    obs.set_enabled(was)


@pytest.fixture(scope="module")
def z10():
    ds = amr.load_preset("run1_z10")
    eb = 1e-3 * float(max(lv.data.max() for lv in ds.levels))
    return ds, eb


def _hist(family) -> tuple[float, int]:
    """(sum, count) over all of a family's series."""
    total, n = 0.0, 0
    for child in family.children().values():
        _, s, c = child.snapshot()
        total += s
        n += c
    return total, n


def _delta(fn, *families):
    before = [_hist(f) for f in families]
    fn()
    return [(s1 - s0, n1 - n0)
            for (s0, n0), (s1, n1) in zip(before, [_hist(f)
                                                   for f in families])]


# ------------------------------- compress -------------------------------


def test_compress_stages_cover_the_level_time(z10):
    ds, eb = z10
    hybrid.compress_amr(ds, eb=eb)          # compile and warm first
    (stage_s, _), (level_s, levels) = _delta(
        lambda: [hybrid.compress_amr(ds, eb=eb) for _ in range(2)],
        obsm.COMPRESS_STAGE_SECONDS, obsm.COMPRESS_LEVEL_SECONDS)
    assert levels == 2 * ds.n_levels
    assert 0.95 * level_s <= stage_s <= level_s
    for stage in COMPRESS_STAGES:
        assert obsm.COMPRESS_STAGE_SECONDS.labels(stage).count > 0, stage


def test_compress_stages_label_the_device_trace(z10, tmp_path):
    tracered = _tracered()
    ds, eb = z10
    hybrid.compress_amr(ds, eb=eb)
    gc.disable()            # no collection may land inside a stage here
    try:
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation(tracered.WINDOW_SPAN):
                hybrid.compress_amr(ds, eb=eb)
    finally:
        gc.enable()
    planes = tracered.flatten(str(tmp_path))
    events = [ev for p in planes for ln in p["lines"] for ev in ln["events"]]
    names = {name for name, _, _ in events}
    window = next((s, s + d) for name, s, d in events
                  if name == tracered.WINDOW_SPAN)
    for stage in COMPRESS_STAGES:
        layer = f"layer.compress.{stage}"
        assert layer in names, layer
        # a device busy up to the stage's start and again from its end:
        # the idle gap between carries the stage's name, not the longer
        # level annotation around it
        _, s, d = max((ev for ev in events if ev[0] == layer),
                      key=lambda ev: ev[2])
        device = {"name": "/device:TPU:0", "lines": [{
            "name": tracered.OPS_LINE,
            "events": [["op", window[0], s - window[0]],
                       ["op", s + d, window[1] - s - d]]}]}
        red = tracered.reduce(planes + [device])
        assert red.gaps[0][0] == layer
    assert "layer.compress.level" in names


# -------------------------------- decode --------------------------------


@pytest.fixture(scope="module")
def snapshot(z10, tmp_path_factory):
    ds, eb = z10
    path = str(tmp_path_factory.mktemp("stages") / "z10.tacz")
    tacz.write(path, hybrid.compress_amr(ds, eb=eb))
    return path


def test_decode_stages_cover_entropy_decode_under_pallas(snapshot):
    with tacz.TACZReader(snapshot, entropy_engine="pallas") as rd:
        li = max(range(rd.n_levels), key=lambda i: len(rd.levels[i]
                                                       .subblocks))
        many = list(range(min(24, len(rd.levels[li].subblocks))))

        def decode():
            rd.decode_subblocks(li, many)       # device launches
            rd.decode_subblocks(li, many[:2])   # tiny_batch: host

        decode()                                # compile first
        (stages_s, _), (decode_s, calls) = _delta(
            decode, obsm.ENTROPY_DECODE_STAGE_SECONDS,
            obsm.ENTROPY_DECODE_SECONDS)
    assert calls == 2
    assert 0.95 * decode_s <= stages_s <= decode_s
    for stage in ("pack", "device", "unpack", "host"):
        assert obsm.ENTROPY_DECODE_STAGE_SECONDS.labels(stage).count > 0


# ------------------------------- serving --------------------------------


class _Levels:
    n_levels = 3

    def get_regions_with_crc(self, boxes, levels=None):
        return 7, [[li for li in levels] for _ in boxes]


def test_queue_wait_is_observed_once_per_decode_unit():
    waits = obsm.SERVER_STAGE_SECONDS.labels("queue_wait")
    units = obsm.SERVER_DECODE_UNITS.labels()
    w0, u0 = waits.count, units.value
    core = AsyncServingCore(_Levels(), decode_workers=1)
    try:
        for _ in range(2):
            core.execute([0], levels=[0, 1, 2])
    finally:
        core.close()
    assert waits.count - w0 == units.value - u0 == 6


def test_server_stages_and_annotations(snapshot, tmp_path):
    tracered = _tracered()
    srv = RegionServer(snapshot, cache_bytes=1 << 20)
    box = ((0, 16), (8, 40), (4, 20))
    try:
        with jax.profiler.trace(str(tmp_path)):
            with jax.profiler.TraceAnnotation(tracered.WINDOW_SPAN):
                srv.get_regions([box])
    finally:
        srv.close()
    for stage in ("plan", "recon", "assemble"):
        assert obsm.SERVER_STAGE_SECONDS.labels(stage).count > 0, stage
    names = {ev[0] for p in tracered.flatten(str(tmp_path))
             for ln in p["lines"] for ev in ln["events"]}
    assert {"layer.server.get_regions", "layer.server.plan",
            "layer.server.recon", "layer.server.assemble",
            "layer.planner.decode", "layer.reader.entropy_decode",
            "layer.decode.host"} <= names


# ---------------------------- collector pauses ----------------------------


def test_forced_collection_lands_in_gc_pause_seconds():
    child = obsm.GC_PAUSE_SECONDS.labels("2")
    before, seconds = child.count, child.sum
    gc.collect()
    assert child.count >= before + 1 and child.sum > seconds


def test_a_pause_the_lock_turns_away_is_recorded_later():
    """A collection that starts while the histogram's lock is held (a
    scrape copying the series on this very thread) must not block."""
    child = obsm.GC_PAUSE_SECONDS.labels("2")
    before = child.count
    with child._lock:
        gc.collect()                    # deferred, not deadlocked
    assert child.count == before
    gc.collect()
    assert child.count == before + 2


def test_gc_pauses_under_concurrent_scrapes_are_all_counted():
    """Threads collecting while others copy the series (holding the
    family's lock): nothing blocks, and once the lock is free every pause
    is in the histogram."""
    import threading

    stops = []

    def count(phase, info):
        if phase == "stop":
            stops.append(info["generation"])

    def total():
        return sum(c.count for c in obsm.GC_PAUSE_SECONDS.children()
                   .values())

    before = total()
    gc.callbacks.append(count)
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        def work():
            for _ in range(20):
                gc.collect(0)
                obsm.REGISTRY.snapshot()

        threads = [threading.Thread(target=work) for _ in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(switch)
        gc.callbacks.remove(count)
    gc.collect()                        # drains what a lock turned away
    assert total() >= before + len(stops) + 1


def test_gc_collection_is_annotated_while_profiling(tmp_path):
    tracered = _tracered()
    with jax.profiler.trace(str(tmp_path)):
        gc.collect()
    names = {ev[0] for p in tracered.flatten(str(tmp_path))
             for ln in p["lines"] for ev in ln["events"]}
    assert "layer.gc.collect" in names


# ------------------------------ annotations ------------------------------


def test_annotate_is_a_no_op_unless_a_profiler_records(tmp_path):
    # outside a root span trace() is the shared no-op too
    assert obs.annotate("layer.test.off") is obs.trace("x")
    with jax.profiler.trace(str(tmp_path)):
        ann = obs.annotate("layer.test.on")
        assert isinstance(ann, jax.profiler.TraceAnnotation)
        with ann:
            pass
        with obsm.timed(obsm.SERVER_STAGE_SECONDS.labels("plan"),
                        layer="layer.test.timed"):
            pass
    names = {ev[0] for p in _tracered().flatten(str(tmp_path))
             for ln in p["lines"] for ev in ln["events"]}
    assert {"layer.test.on", "layer.test.timed"} <= names


def test_timed_layer_adds_no_response_span():
    """A stage timed with only a profiler name leaves the request's span
    tree as it was."""
    with obs.root_span("batch") as root:
        with obsm.timed(obsm.SERVER_STAGE_SECONDS.labels("assemble"),
                        layer="layer.server.assemble"):
            pass
        with obsm.timed(obsm.SERVER_STAGE_SECONDS.labels("plan"), "plan",
                        "layer.server.plan"):
            pass
    assert [s["name"] for s in root.summary()["stages"]] == ["plan"]


def test_repro_obs_imports_without_jax():
    code = ("import sys; sys.modules['jax'] = None\n"
            "import gc, repro.obs as obs\n"
            "from repro.obs import metrics as obsm\n"
            "with obsm.timed(obsm.SERVER_STAGE_SECONDS.labels('plan'),"
            " 'plan', 'layer.server.plan'):\n"
            "    gc.collect()\n"
            "assert obs.annotate('layer.x.y') is obs.trace('z')\n"
            "assert sys.modules['jax'] is None\n"
            "assert obsm.GC_PAUSE_SECONDS.labels('2').count >= 1\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr


def test_stage_timing_leaves_outputs_unchanged(z10):
    """Instrumentation is observe-only: the same codes with the registry
    on and off."""
    ds, eb = z10
    on = hybrid.compress_amr(ds, eb=eb)
    obs.set_enabled(False)
    off = hybrid.compress_amr(ds, eb=eb)
    for a, b in zip(on.levels, off.levels):
        assert a.total_bits == b.total_bits
        np.testing.assert_array_equal(a.recon, b.recon)
        for ra, rb in zip(a.artifacts.results, b.artifacts.results):
            np.testing.assert_array_equal(ra.codes, rb.codes)
