"""Run a cell's control on several seeds in one process and print the
numbers its check compares.  The control breaks the guarantee the
configuration states: compress cells compress at four times the stated
bound, serve cells compare the served crops rounded to bfloat16.  The
benchmark's own runs never run it.

    python3 benchmarks/chip/tools/run_control.py --workload <cell> \\
        --seeds 1,2,3 --seconds <s>
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell = harness.find_cell(harness.benchmark(), args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        result, checks = run.run(cell, seed, args.seconds, False,
                                 control=True)
        print(json.dumps({"seed": seed, "control": True,
                          "correct": result["correct"],
                          "checks": checks}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
