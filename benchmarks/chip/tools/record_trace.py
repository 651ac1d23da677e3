"""Record a cell's traced window as plain JSON (``tracered.flatten``) and
print what it holds: planes, their lines, and the programs that took the
most device time.  Run it on the chip to look at a trace by hand, or to
re-record the small trace the tests keep::

    python3 benchmarks/chip/tools/record_trace.py --workload <cell> \\
        --seed <n> --seconds <s> --out <file.json> [--keep 400]

The saved copy keeps the window's device events and benchmark spans,
at most ``--keep`` events per line, which keeps it small.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402
import run  # noqa: E402
import tracered  # noqa: E402


def trim(planes: list, keep: int) -> list:
    """Planes cut to the traced window and to the first ``keep`` events of
    each line, the window's span and marks kept whole; host lines keep only the
    benchmark's own spans."""
    w0, w1 = tracered.window(planes)
    marks = (tracered.WINDOW_SPAN, tracered.FROM_MARK, tracered.TO_MARK)
    out = []
    for p in planes:
        dev = p["name"].startswith("/device:TPU:")
        lines = []
        for ln in p["lines"]:
            evs = []
            for name, s, d in ln["events"]:
                if name in marks:
                    evs.append([name, s, d])
                elif len(evs) < keep and s < w1 and s + d > w0 and (
                        dev or name.startswith(("bench.", "layer."))):
                    evs.append([name, s, d])
            if evs:
                lines.append({"name": ln["name"], "events": evs})
        if lines:
            out.append({"name": p["name"], "lines": lines})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--keep", type=int, default=400)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    seen = {}
    real_flatten = tracered.flatten

    def capture(trace_dir):
        seen["planes"] = real_flatten(trace_dir)
        return seen["planes"]

    tracered.flatten = capture
    cell = harness.find_cell(harness.benchmark(), args.workload)
    result, checks = run.run(cell, args.seed, args.seconds, True)
    planes = seen["planes"]
    for p in planes:
        print(p["name"])
        for ln in p["lines"]:
            print(f"    {ln['name']!r}: {len(ln['events'])} events")
    red = tracered.reduce(planes)
    print("busy_s", red.busy_s, "window_s", red.window_s)
    for name, t in sorted(red.modules.items(), key=lambda kv: -kv[1])[:15]:
        print(f"  module {t:10.6f} s  {name}")
    for name, t in red.ops[:15]:
        print(f"  op     {t:10.6f} s  {name}")
    for name, t in red.gaps[:10]:
        print(f"  gap    {t:10.6f} s  {name}")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(trim(planes, args.keep), f)
    harness.emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
