"""Reduce a JAX profiler trace to the numbers the benchmark reports.

A trace is first flattened to plain data — planes, their lines, and
events as ``(name, start_ns, duration_ns)`` — so the reduction can be
checked on a small recorded trace (``tests/data``) without a chip.

* Device planes are those named ``/device:TPU:<n>``.  The device is busy
  while any event of its ``XLA Ops`` line runs; busy time is the union of
  those intervals inside the measured window, averaged over the chips.
* The window is what was traced of the measured part of a run: from
  the mark ``bench.trace_from`` to the mark ``bench.trace_to`` that the
  harness sets when it starts and stops the profiler, or else the host
  span ``bench.window`` that it opens around the measured part.
* A kernel's device time is the summed duration of the events of the
  ``XLA Modules`` line whose name contains the kernel's jitted function
  name (stable across refactors of the kernel body).
* Device ops are grouped by instruction name (``op_kind``) for the
  breakdown.
* Idle gaps between device ops are labelled with the innermost host
  annotation (``jax.profiler.TraceAnnotation``) that covers the gap's
  midpoint; the harness and drivers open those around each layer call.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

WINDOW_SPAN = "bench.window"
FROM_MARK = "bench.trace_from"
TO_MARK = "bench.trace_to"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def flatten(trace_dir: str) -> list[dict]:
    """Planes of the newest ``.xplane.pb`` under ``trace_dir`` as plain
    data: ``[{name, lines: [{name, events: [[name, start, dur]]}]}]``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    prof = ProfileData.from_file(files[-1])
    planes = []
    for plane in prof.planes:
        lines = []
        for line in plane.lines:
            evs = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                   for ev in line.events]
            lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def op_kind(name: str) -> str:
    """An HLO op's instruction name without its shapes and numbering:
    ``%copy.1 = s32[..] copy(..)`` → ``copy``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def _is_device(plane: dict) -> bool:
    return plane["name"].startswith("/device:TPU:")


def _union(intervals) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    n_devices: int
    ops: list             # [(name, seconds)] device ops, most time first
    gaps: list            # [(label, seconds)] longest idle gaps
    modules: dict         # module name -> device seconds in the window

    def kernel_s(self, *names: str) -> float:
        """Device seconds of the programs whose name holds any of
        ``names``."""
        return sum(t for m, t in self.modules.items()
                   if any(n in m for n in names))

    def breakdown(self) -> dict:
        return {"device_ops": [[n, t] for n, t in self.ops[:10]],
                "idle_gaps": [[n, t] for n, t in self.gaps[:10]]}


def window(planes: list[dict]) -> tuple[int, int]:
    """``(start_ns, end_ns)`` of the traced part of the measured window."""
    span = None
    marks = {}
    for plane in planes:
        if _is_device(plane):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name == WINDOW_SPAN:
                    span = (s, s + d)
                elif name in (FROM_MARK, TO_MARK):
                    marks[name] = (s, s + d)
    if FROM_MARK in marks and TO_MARK in marks:
        return marks[FROM_MARK][0], marks[TO_MARK][1]
    if span is None:
        raise ValueError(f"trace holds neither the marks {FROM_MARK!r} and "
                         f"{TO_MARK!r} nor a {WINDOW_SPAN!r} span")
    return span


def reduce(planes: list[dict]) -> Reduced:
    """Busy time, top ops, idle gaps and per-program time in the window."""
    w0, w1 = window(planes)
    host_spans = []
    for plane in planes:
        if _is_device(plane):
            continue
        for line in plane["lines"]:
            for name, s, d in line["events"]:
                if name.startswith(("bench.", "layer.")) and name not in (
                        WINDOW_SPAN, FROM_MARK, TO_MARK):
                    host_spans.append((s, s + d, name))
    devices = [p for p in planes if _is_device(p)]
    busy = 0
    op_time: dict = {}
    mod_time: dict = {}
    gaps = []
    for plane in devices:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get(OPS_LINE, [])
        clipped = []
        for name, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                key = op_kind(name)
                op_time[key] = op_time.get(key, 0) + (b - a)
        merged = _union(clipped)
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
        for name, s, d in lines.get(MODULES_LINE, []):
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                mod_time[name] = mod_time.get(name, 0) + (b - a)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    labelled = [(_label(host_spans, (a + b) // 2), (b - a) / 1e9)
                for a, b in gaps[:10]]
    n = max(len(devices), 1)
    return Reduced(window_s=(w1 - w0) / 1e9, busy_s=busy / n / 1e9,
                   n_devices=len(devices),
                   ops=sorted(((k, v / 1e9) for k, v in op_time.items()),
                              key=lambda kv: kv[1], reverse=True),
                   gaps=labelled,
                   modules={k: v / 1e9 for k, v in mod_time.items()})


def _label(host_spans, t: int) -> str:
    """Innermost benchmark annotation covering ``t`` (shortest span)."""
    best = None
    for s, e, name in host_spans:
        if s <= t < e:
            if best is None or e - s < best[1] - best[0]:
                best = (s, e, name)
    return best[2] if best else "host: outside any benchmark span"
