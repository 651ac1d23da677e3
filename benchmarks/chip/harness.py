"""What every cell of the on-chip benchmark shares: finding a cell's
pieces by name, the accelerator guard, the compile cache, compile
counting, registry deltas over the window, and the result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
``BENCHMARK.json`` names them and this module finds the matching files.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: run-time state of this checkout (compile cache, published snapshots,
#: traces); listed in this directory's ``.gitignore``
STATE = os.path.join(HERE, ".state")


class NoAccelerator(SystemExit):
    """The run cannot measure: it exits non-zero and prints no result."""

    def __init__(self, msg: str):
        super().__init__(f"benchmark: {msg}")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        raise NoAccelerator(f"no BENCHMARK.json at {ROOT}")
    return load_json(path)


def load_module(path: str, name: str):
    """Import one file of the benchmark by path (names may hold dots)."""
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    spec = importlib.util.spec_from_file_location(
        "bench_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its pieces loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    @property
    def driver_path(self) -> str:
        return os.path.join(HERE, "drivers", f"{self.traffic['kind']}.py")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(bench: dict, name: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"benchmark: unknown workload {name!r}; "
                         f"have {sorted(cells)}")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", f"{w['traffic']}.json"))
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)])


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    when the environment sets it, else a fixed directory in this checkout
    (the path is part of the cache key, so it never moves).  Every
    program is cached, however short its compile."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(STATE, "jax_cache")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def require_accelerator(chips: int):
    """The devices to run on.  Exits (no result) unless JAX's default
    backend is a TPU with ``chips`` chips and Pallas kernels compile for
    it rather than run in interpret mode."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"default JAX backend is {devs[0].platform!r}, "
                            "not a TPU")
    if len(devs) < chips:
        raise NoAccelerator(f"{chips} chips needed, JAX sees {len(devs)}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.kernels import ops

    if ops.default_interpret():
        raise NoAccelerator("Pallas kernels would run in interpret mode")
    return devs[:chips]


class CompileCounter:
    """XLA backend compiles and persistent-cache hits since a mark."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.hits = 0

        def on_duration(event, _secs, **_kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **_kw):
            if event == "/jax/compilation_cache/cache_hits":
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def registry_snapshot() -> dict:
    """The program's metric registry (``repro.obs``), flattened to
    ``{(family, labels): value or (sum, count)}``."""
    from repro.obs.metrics import REGISTRY

    out = {}
    for fam, body in REGISTRY.snapshot().items():
        for labels, v in body["series"].items():
            out[(fam, labels)] = ((v["sum"], v["count"])
                                  if isinstance(v, dict) else v)
    return out


class Window:
    """What the per-layer readers get: registry deltas over the measured
    window, the reduced trace (traced runs only), the chip's peaks, and
    facts the driver worked out from the work it did.

    ``traced``: the registry at the start and end of the traced part when
    the trace covers only part of the window.  ``win.traced`` is then a
    window over that part, for readers that set counters against device
    time; otherwise it is the window itself."""

    def __init__(self, before: dict, after: dict, *, facts=None,
                 trace=None, peaks=None, traced=None):
        self._before, self._after = before, after
        self.facts = dict(facts or {})
        self.trace = trace
        self.peaks = peaks
        #: whether the trace covers the whole window (facts are counted
        #: over the whole window)
        self.whole_window_traced = traced is None
        self.traced = self if traced is None else Window(
            *traced, facts=facts, trace=trace, peaks=peaks)
        self.traced.whole_window_traced = self.whole_window_traced

    def counter(self, family: str, **labels) -> float:
        """Sum of the family's series matching ``labels`` (delta)."""
        return sum(self._delta(k) for k in self._match(family, labels)
                   if not isinstance(self._after[k], tuple))

    def hist(self, family: str, **labels) -> tuple[float, int]:
        """(seconds, observations) of a histogram family (delta)."""
        s = n = 0
        for k in self._match(family, labels):
            ds, dn = self._delta(k)
            s += ds
            n += dn
        return s, n

    def _match(self, family, labels):
        want = {f"{k}={v}" for k, v in labels.items()}
        for fam, lab in self._after:
            if fam == family and want <= set(lab.split(",")):
                yield (fam, lab)

    def _delta(self, key):
        a = self._after[key]
        b = self._before.get(key)
        if isinstance(a, tuple):
            b = b or (0.0, 0)
            return a[0] - b[0], a[1] - b[1]
        return a - (b or 0)


def quantile(sorted_vals: list, q: float):
    """Linear-interpolated quantile of a sorted list (None if empty)."""
    if not sorted_vals:
        return None
    pos = q * (len(sorted_vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(sorted_vals) - 1)
    return sorted_vals[lo] + (sorted_vals[hi] - sorted_vals[lo]) * (pos - lo)


def emit(result: dict, checks: dict) -> None:
    """Print each compared number beside its limit as the last lines of
    standard error, then the result as the last line of standard output
    with the checks under the last key."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r} "
              f"({'ok' if c['ok'] else 'FAIL'})", file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                      for k, c in checks.items()}
    print(json.dumps(line), flush=True)
