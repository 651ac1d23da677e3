"""On-chip benchmark of TAC+ compression and ROI serving.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

One process: it finds the cell in ``BENCHMARK.json``, loads its
configuration (``configs/``) and traffic mix (``traffic/``), and hands both
to the driver the mix names (``drivers/<kind>.py``).  The driver sets up
(data from the seed, compiles, warm caches), the harness measures for
``--seconds``, and the driver then checks what the timed path produced
against the plain reference reader.  With ``--trace 1`` the cell's
per-layer metrics are read by one reader each
(``layer_metrics/<metric>.py``): spans and counters over the whole
window, the device trace over the window or, for a mix that sets
``trace_seconds``, over its last seconds; otherwise the cell's
end-to-end metrics are reported.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), then ``checks``, each compared number beside its limit.  The run
exits non-zero and prints no result when JAX finds no TPU, too few chips,
or Pallas kernels that would run in interpret mode.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402
import peaks  # noqa: E402
import tracered  # noqa: E402


def _log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_START:8.3f}s] {msg}", file=sys.stderr,
          flush=True)


def _layer_metrics(cell, win) -> dict:
    out = {}
    for m in cell.per_layer:
        reader = harness.load_module(
            os.path.join(HERE, "layer_metrics", f"{m['name']}.py"), m["name"])
        value = reader.read(win)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


class _Profiler:
    """The JAX profiler over part of the window: ``start`` may run on a
    timer thread while the window goes on.  The traced part lies between
    the marks ``tracered.FROM_MARK`` and ``tracered.TO_MARK``, and the
    program's registry is read at both."""

    def __init__(self, trace_dir: str):
        self.dir = trace_dir
        self.before = self.after = None
        self._lock = threading.Lock()
        self._on = False

    def start(self) -> None:
        import jax

        with self._lock:
            if self._on:
                return
            shutil.rmtree(self.dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0      # host spans only: no call trace
            self.before = harness.registry_snapshot()
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(tracered.FROM_MARK):
                pass
            self._on = True

    def stop(self) -> None:
        import jax

        self.start()          # a window shorter than its timer: trace its end
        with self._lock:
            with jax.profiler.TraceAnnotation(tracered.TO_MARK):
                pass
            self.after = harness.registry_snapshot()
            jax.profiler.stop_trace()
            self._on = False


def run(cell, seed: int, seconds: float, trace: bool, *,
        driver_cls=None, control: bool = False, check_device=True) -> dict:
    """One run of ``cell``; returns ``(result, checks)``.  Tests pass
    ``check_device=False`` to rehearse on the CPU and ``driver_cls`` to
    plant a fault."""
    harness.enable_compile_cache()
    import jax

    if check_device:
        devs = harness.require_accelerator(cell.chips)
    else:
        sys.path.insert(0, os.path.join(harness.ROOT, "src"))
        devs = jax.devices()[:cell.chips]
    dev = devs[0]
    _log(f"device {dev.platform} {dev.device_kind} x{len(devs)}")
    compiles = harness.CompileCounter()
    if driver_cls is None:
        driver_cls = harness.load_module(cell.driver_path,
                                         cell.traffic["kind"]).Driver
    drv = driver_cls(cell, seed, seconds, control=control)
    drv.setup()
    _log(f"set-up done ({compiles.compiles} compiles, {compiles.hits} "
         f"persistent-cache hits)")
    before = harness.registry_snapshot()
    c0, h0 = compiles.compiles, compiles.hits
    profiler = _Profiler(os.path.join(harness.STATE, "trace", cell.name))
    # a mix whose device trace is dense (one event per scan step) traces
    # only the last ``trace_seconds`` of its window, so the trace stays
    # readable; spans and counters are read over the whole window
    trace_s = float(cell.traffic.get("trace_seconds", seconds))
    timer = None
    if trace and trace_s < seconds:
        timer = threading.Timer(seconds - trace_s, profiler.start)
    elif trace:
        profiler.start()
    t_window = time.perf_counter()
    if timer is not None:
        timer.start()
    with jax.profiler.TraceAnnotation("bench.window"):
        drv.measure()
    window_s = time.perf_counter() - t_window
    if timer is not None:
        timer.cancel()
        timer.join()
    if trace:
        profiler.stop()
    setup_s = t_window - T_START
    after = harness.registry_snapshot()
    in_window = (compiles.compiles - c0, compiles.hits - h0)
    _log(f"window {window_s:.3f} s; compiles in window {in_window[0]} "
         f"(persistent-cache hits {in_window[1]})")
    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}
    drv.release()
    gc.collect()
    attempted, failed = drv.counts()
    result = {"correct": False, "attempted": attempted, "failed": failed,
              "metrics": {}, "device": device}
    if trace:
        reduced = tracered.reduce(tracered.flatten(profiler.dir))
        shutil.rmtree(profiler.dir, ignore_errors=True)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        win = harness.Window(before, after, facts=drv.facts(),
                             trace=reduced,
                             peaks=peaks.for_device(dev.device_kind),
                             traced=(profiler.before, profiler.after)
                             if timer is not None else None)
        result["metrics"] = _layer_metrics(cell, win)
        result["breakdown"] = reduced.breakdown()
    else:
        e2e = drv.end_to_end()
        e2e["setup_s"] = setup_s
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}
    extra = getattr(drv, "client_summary", None)
    if extra is not None:
        _log(f"client: {extra()}")
    t_check = time.perf_counter()
    checks = drv.check()
    _log(f"reference check {time.perf_counter() - t_check:.3f} s")
    result["correct"] = all(c["ok"] for c in checks.values())
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = harness.find_cell(harness.benchmark(), args.workload)
    result, checks = run(cell, args.seed, args.seconds, bool(args.trace))
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
