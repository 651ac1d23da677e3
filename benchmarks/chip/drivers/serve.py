"""ROI serving: a published snapshot served over HTTP by the program's
``serve()`` (``RegionServer`` behind the async serving core), with open-
loop viewer traffic from a client process (``openloop.py``).

Set-up makes the configuration's snapshot (``datagen``, from its fixed
``data_seed``: compressing and publishing the 512³ snapshot anew for
every seed would cost each run about a minute), compresses and publishes
it (or finds it published by an earlier run in this checkout), starts
the server and warms it.  The seed draws the requests (``openloop``):
which boxes, or in which order a fixed set of them, and which of them
the check samples.

* ``warm: "fill"`` requests the whole query population once; the cache
  then holds the working set, as it would for a viewer session under way;
* ``warm: "replay"`` sends the window's own boxes, in the window's order,
  to a throwaway server with the window's cache budget first, so every
  decode shape is compiled, then serves the window from a fresh server
  whose cache holds only one box at the domain's corner.

``correct``: a seeded sample of the requests finished in the window is
compared, crop by crop, with the plain reference reader's decode of the
same file (bit for bit), and each crop with the generated data (the error
bound plus float32 round-off of the largest value).  A sampled request
that never came back fails the run.
"""
from __future__ import annotations

import json
import os
import random
import shutil
import subprocess
import sys
import threading

import ml_dtypes
import numpy as np

import datagen
import harness
import openloop
import tacz_ref


class Driver:
    def __init__(self, cell, seed: int, seconds: float, *,
                 control: bool = False):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.cfg = cell.config
        self.traffic = cell.traffic
        self.control = control
        self.work = os.path.join(harness.STATE, "work", cell.name)
        self.httpd = None

    # ---------------------------------------------------------------- set-up
    def setup(self) -> None:
        import jax

        edge = int(self.cfg["finest_grid"])
        self.snap = datagen.generate(self.cfg["generator"], edge,
                                     int(self.cfg["data_seed"]))
        self.eb = (float(self.cfg["eb_rel"])
                   * datagen.geometry_range([self.snap]))
        self.path = self._published()
        shutil.rmtree(self.work, ignore_errors=True)
        os.makedirs(self.work)
        self.budget = int(float(self.traffic["cache_share"])
                          * self.snap.level_bytes())
        self.shape = (edge,) * 3
        self.plan = openloop.schedule(self.traffic, self.shape, self.seed,
                                      self.seconds)
        n = len(self.plan["requests"])
        rng = random.Random(self.seed * 31 + 7)
        self.sample = set(rng.sample(
            range(n), min(int(self.traffic["verify_sample"]), n)))
        self._start()
        with jax.profiler.TraceAnnotation("bench.warm"):
            if self.traffic["warm"] == "fill":
                for box in self.plan["population"]:
                    self.httpd.region_server.get_regions(
                        [tuple(map(tuple, box))], self.plan["levels"])
            else:
                self._drive(self.plan["requests"], self.plan["levels"],
                            warmup=True)
                self._stop()
                self._start()
                # the fresh server loads each level's mask on first use:
                # one request at the domain's corner pays that here
                edge = int(self.traffic["box_edge"])
                self.httpd.region_server.get_regions(
                    [((0, edge),) * 3], self.plan["levels"])

    def _published(self) -> str:
        """The snapshot's TACZ file, compressed and published once per
        configuration in this checkout."""
        from repro.core import amr, hybrid
        from repro.io import TACZWriter

        d = os.path.join(harness.STATE, "snapshots")
        os.makedirs(d, exist_ok=True)
        path = os.path.join(d, f"{self.cfg['name']}-{self.cfg['data_seed']}"
                               ".tacz")
        if not os.path.exists(path):
            ds = amr.AMRDataset([amr.AMRLevel(x, m, r) for x, m, r in
                                 zip(self.snap.data, self.snap.mask,
                                     self.snap.ratio)])
            res = hybrid.compress_amr(ds, eb=self.eb,
                                      unit=int(self.cfg["unit"]))
            with TACZWriter(path, eb=self.eb) as w:
                for lr in res.levels:
                    w.add_compressed(lr)
        return path

    def _start(self) -> None:
        from repro.serving import serve

        srv = self.cfg["server"]
        self.httpd = serve(self.path, port=0, cache_bytes=self.budget,
                           decode_workers=int(srv["decode_workers"]),
                           queue_depth=int(srv["queue_depth"]))
        _annotate(self.httpd.region_server)
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()

    def _stop(self) -> None:
        if self.httpd is None:
            return
        self.httpd.shutdown()
        self.httpd.server_close()
        self.httpd.region_server.close()
        self._thread.join(timeout=30)
        self.httpd = None

    def _drive(self, requests, levels, *, warmup=False, keep=()):
        """Run the client process over ``requests``; returns its result.
        A warm-up replay sends the same boxes as fast as the server takes
        them: one client thread per decode worker, so admission never
        turns one away, and no deadline short of the call's own."""
        threads, grace = int(self.traffic["threads"]), 60.0
        if warmup:
            requests = [[0.0, box] for _, box in requests]
            threads = int(self.cfg["server"]["decode_workers"])
            grace = 600.0
        spec = {"host": "127.0.0.1", "port": self.httpd.server_address[1],
                "levels": levels, "requests": requests,
                "seconds": max((r[0] for r in requests), default=0.0),
                "grace_s": grace, "threads": threads,
                "keep": sorted(keep), "keep_dir": self.work,
                "result_path": os.path.join(self.work, "client.json")}
        spec_path = os.path.join(self.work, "spec.json")
        with open(spec_path, "w") as f:
            json.dump(spec, f)
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [sys.executable, os.path.join(harness.HERE, "openloop.py"),
             spec_path], stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            env=env, text=True)
        try:
            if proc.stdout.readline().strip() != "ready":
                raise RuntimeError("load client did not start")
            proc.stdin.write("go\n")
            proc.stdin.flush()
            proc.wait(timeout=spec["seconds"] + spec["grace_s"] + 60)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if proc.returncode != 0:
            raise RuntimeError(f"load client exited {proc.returncode}")
        with open(spec["result_path"]) as f:
            return json.load(f)

    # ---------------------------------------------------------------- window
    def measure(self) -> None:
        self.result = self._drive(self.plan["requests"], self.plan["levels"],
                                  keep=self.sample)

    def release(self) -> None:
        self._stop()

    # ------------------------------------------------------------ results
    def _latencies(self) -> list:
        return sorted(r[1] for r in self.result["records"]
                      if r[1] is not None)

    def end_to_end(self) -> dict:
        lat = self._latencies()
        return {"roi_p50_ms": harness.quantile(lat, 0.5) * 1e3,
                "roi_p90_ms": harness.quantile(lat, 0.9) * 1e3}

    def counts(self) -> tuple[int, int]:
        recs = self.result["records"]
        return len(recs), sum(1 for r in recs if r[1] is None)

    def facts(self) -> dict:
        recs = self.result["records"]
        rd = tacz_ref.RefReader(self.path)
        bits = codes = 0
        for lvl in rd.levels:
            for sb in lvl.subblocks:
                bits += sb.nbits
                codes += sb.n_codes
        return {"requests": len(recs),
                "max_send_lag_s": max((r[4] for r in recs), default=0.0),
                "busy_retries": sum(r[3] for r in recs),
                "bits_per_code": bits / max(codes, 1)}

    def client_summary(self) -> str:
        lat = self._latencies()
        f = self.facts()
        return (f"requests={f['requests']} completed={len(lat)} "
                f"max_send_lag_s={f['max_send_lag_s']:.4f} "
                f"busy_retries={f['busy_retries']} "
                f"elapsed_s={self.result['elapsed_s']:.3f}")

    def check(self) -> dict:
        rd = tacz_ref.RefReader(self.path)
        recs = self.result["records"]
        differ = missing = 0
        worst = 0.0
        top = max(float(np.abs(d[m]).max()) for d, m in
                  zip(self.snap.data, self.snap.mask) if m.any())
        for i in sorted(self.sample):
            body_path = os.path.join(self.work, f"resp_{i}.bin")
            if recs[i][1] is None or not os.path.exists(body_path):
                missing += 1
                continue
            with open(body_path, "rb") as f:
                crops = openloop.parse_regions(f.read())
            box = self.plan["requests"][i][1]
            if len(crops) != len(self.plan["levels"]):
                missing += 1
                continue
            for li, (level, lbox, shape, raw) in zip(self.plan["levels"],
                                                     crops):
                want_box = rd.level_box(li, box)
                ref = rd.level_crop(li, want_box)
                got = np.frombuffer(raw, "<f4").reshape(shape)
                if self.control:
                    got = got.astype(ml_dtypes.bfloat16).astype(np.float32)
                if (level != li or tuple(map(tuple, lbox)) != want_box
                        or not np.array_equal(got, ref)):
                    differ += 1
                sl = tuple(slice(lo, hi) for lo, hi in want_box)
                if got.shape == ref.shape:
                    worst = max(worst, tacz_ref.eb_gap(
                        got, self.snap.data[li][sl], self.snap.mask[li][sl]))
        limit = 1.0 + 2.0 ** -22 * top / self.eb
        return {
            "crops_differ": {"value": differ, "limit": 0, "ok": differ == 0},
            "sampled_missing": {"value": missing, "limit": 0,
                                "ok": missing == 0},
            "max_err_over_eb": {"value": worst / self.eb, "limit": limit,
                                "ok": worst / self.eb <= limit},
        }


def _annotate(server) -> None:
    """Name the server's batch and decode calls in the profiler's trace
    (a no-op where the program lacks the method)."""
    import jax

    def wrap(obj, name, label):
        fn = getattr(obj, name, None)
        if fn is None:
            return

        def inner(*a, **k):
            with jax.profiler.TraceAnnotation(label):
                return fn(*a, **k)
        setattr(obj, name, inner)

    wrap(server, "get_regions_with_crc", "layer.server_batch")
    wrap(getattr(server, "reader", None), "decode_subblocks",
         "layer.entropy_decode")
