"""Find a serving cell's knee: one set-up, then one open-loop window per
offered rate, each on a server with the cell's own cache state (cold
cells restart the server between rates).  For each rate it prints the
latency quantiles and the median latency of the first and last thirds of
the window; a last third well above the first is a growing backlog.

    python3 benchmarks/chip/tools/sweep.py --workload <cell> --seed <n> \\
        --seconds <s> --rates 1,2,3,4
"""
from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import harness  # noqa: E402
import openloop  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    rates = [float(r) for r in args.rates.split(",")]
    harness.enable_compile_cache()
    cell = harness.find_cell(harness.benchmark(), args.workload)
    harness.require_accelerator(cell.chips)
    cell.traffic["rate_rps"] = max(rates)
    drv = harness.load_module(cell.driver_path, "serve").Driver(
        cell, args.seed, args.seconds)
    drv.setup()
    for rate in rates:
        if cell.traffic["warm"] == "replay":
            drv._stop()
            drv._start()
        plan = openloop.schedule(dict(cell.traffic, rate_rps=rate),
                                 drv.shape, args.seed, args.seconds)
        res = drv._drive(plan["requests"], plan["levels"])
        recs = res["records"]
        lat = sorted(r[1] for r in recs if r[1] is not None)
        third = max(len(recs) // 3, 1)
        first = sorted(r[1] for r in recs[:third] if r[1] is not None)
        last = sorted(r[1] for r in recs[-third:] if r[1] is not None)
        def ms(vals, p):
            v = harness.quantile(vals, p)
            return "none" if v is None else f"{v * 1e3:.1f} ms"

        print(f"rate {rate:6.2f} rps: n={len(recs)} ok={len(lat)} "
              f"p50={ms(lat, .5)} p90={ms(lat, .9)} "
              f"first_third_p50={ms(first, .5)} "
              f"last_third_p50={ms(last, .5)} "
              f"busy_retries={sum(r[3] for r in recs)} "
              f"elapsed={res['elapsed_s']:.2f} s", flush=True)
    drv.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
