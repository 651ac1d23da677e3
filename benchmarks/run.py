"""Benchmark driver: one module per paper table/figure (DESIGN.md §7).

``python -m benchmarks.run [--quick]`` runs everything and prints a
``name,seconds,headline`` CSV summary; per-benchmark CSVs land in
``results/bench/``.
"""
from __future__ import annotations

import argparse
import json
import os
import time

from . import (bench_autotune, bench_density_sweep, bench_distributed,
               bench_entropy,
               bench_grad_compress, bench_halo, bench_kernels,
               bench_loadgen, bench_nast_opst, bench_parallel_write,
               bench_partition_time, bench_power_spectrum,
               bench_rate_distortion, bench_region_serving,
               bench_roi_decode, bench_sharded_serving, bench_she,
               bench_throughput)
from .common import record_summary

BENCHES = [
    ("rate_distortion (Figs 20-27)", bench_rate_distortion),
    ("density_sweep (Figs 12-13)", bench_density_sweep),
    ("partition_time (Fig 14)", bench_partition_time),
    ("she_ablation (Figs 15-16)", bench_she),
    ("nast_vs_opst (Fig 9)", bench_nast_opst),
    ("throughput (Tables III-V)", bench_throughput),
    ("power_spectrum (Fig 30)", bench_power_spectrum),
    ("halo_finder (Table II)", bench_halo),
    ("distributed (SIII-F)", bench_distributed),
    ("grad_compress (beyond-paper)", bench_grad_compress),
    ("kernels (beyond-paper)", bench_kernels),
    ("roi_decode (TACZ container)", bench_roi_decode),
    ("region_serving (TACZ serving)", bench_region_serving),
    ("sharded_serving (TACZ serving)", bench_sharded_serving),
    ("parallel_write (TACZ multi-part)", bench_parallel_write),
    ("entropy (batched Huffman engines)", bench_entropy),
    ("loadgen (fleet SLO harness)", bench_loadgen),
    ("autotune (TAC+ §IV-F eb tuning)", bench_autotune),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from repro.device import enable_compile_cache

    enable_compile_cache(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    print("name,seconds,headline")
    for name, mod in BENCHES:
        if args.only and args.only not in name:
            continue
        t0 = time.perf_counter()
        try:
            out = mod.run(quick=args.quick)
        except Exception as exc:
            record_summary(name, metric="error", value=str(exc)[:200],
                           passed=False)
            raise
        dt = time.perf_counter() - t0
        headline = {k: v for k, v in out.items() if k != "csv"}
        # one verdict row per benchmark: first headline metric + the
        # gate threshold when the module reports one (a raising gate is
        # recorded as failed above)
        key = next(iter(headline), None)
        record_summary(name, metric=key or "seconds",
                       value=headline.get(key, round(dt, 2)),
                       threshold=out.get("threshold"), passed=True)
        print(f"{name},{dt:.1f},\"{json.dumps(headline)[:160]}\"", flush=True)


if __name__ == "__main__":
    main()
