"""In-situ compress + publish: one chip's rank of a simulation that writes
a series of snapshots, each compressed with TAC+ (``compress_amr``) and
published as a TACZ file (``TACZWriter``: written, closed and renamed).

Set-up makes the configuration's snapshots (``datagen``: the levels
placed by the configuration's ``data_seed``, the stored values drawn from
the run's seed too) and compresses each once, which compiles every
Lorenzo brick shape the window will use (publishing compiles nothing).
The window then cycles through the series and ends with the first whole
pass after ``--seconds``: every run compresses each snapshot equally
often.  The absolute error bound is fixed for the series from the value
range of its ``data_seed`` fields, as an in-situ run fixes its bound, so
every seed compresses under the same bound and the same programs.

``correct``: for each distinct snapshot, one of its publishes in the
window (drawn from the seed) is read back by the plain reference reader
(``tacz_ref``) and compared with the generated data: the largest error
over the stored cells, over the bound, may not pass
``1 + 2⁻²² · max|x| / eb`` (the bound plus float32 round-off of the
largest value, as ``repro.core.sz.prequant`` states it), and each level
must store exactly the cells the snapshot stores.
"""
from __future__ import annotations

import os
import random
import shutil
import time

import numpy as np

import datagen
import harness
import tacz_ref


class Driver:
    def __init__(self, cell, seed: int, seconds: float, *,
                 control: bool = False):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.cfg = cell.config
        self.control = control
        self.out = os.path.join(harness.STATE, "work", cell.name)
        self.published: list = []

    # ---------------------------------------------------------------- set-up
    def setup(self) -> None:
        from repro.core import amr

        edge = int(self.cfg["subdomain"][0])
        k = int(self.cell.traffic["snapshots"])
        order = random.Random(self.seed).sample(range(k), k)
        self.snaps = [datagen.generate(self.cfg["generator"], edge,
                                       int(self.cfg["data_seed"]), s,
                                       value_seed=self.seed)
                      for s in order]
        self.eb = (float(self.cfg["eb_rel"])
                   * datagen.geometry_range(self.snaps))
        # the control states a bound it does not keep: it compresses at
        # four times the configuration's bound
        self.eb_used = self.eb * (4.0 if self.control else 1.0)
        self.datasets = [amr.AMRDataset(
            [amr.AMRLevel(d, m, r) for d, m, r in
             zip(s.data, s.mask, s.ratio)]) for s in self.snaps]
        shutil.rmtree(self.out, ignore_errors=True)
        os.makedirs(self.out)
        from repro.core import hybrid

        for ds in self.datasets:
            hybrid.compress_amr(ds, eb=self.eb_used,
                                unit=int(self.cfg["unit"]))

    def _publish(self, i: int, path: str) -> int:
        import jax

        from repro.core import hybrid
        from repro.io import TACZWriter

        with jax.profiler.TraceAnnotation("layer.compress_amr"):
            res = hybrid.compress_amr(self.datasets[i], eb=self.eb_used,
                                      unit=int(self.cfg["unit"]))
        with jax.profiler.TraceAnnotation("layer.publish"):
            with TACZWriter(path, eb=self.eb_used) as w:
                for lr in res.levels:
                    w.add_compressed(lr)
        return os.path.getsize(path)

    # ---------------------------------------------------------------- window
    def measure(self) -> None:
        k = len(self.snaps)
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < self.seconds or i % k:
            path = os.path.join(self.out, f"pub_{i}.tacz")
            size = self._publish(i % k, path)
            self.published.append((i % k, path, size,
                                   time.perf_counter() - t0))
            i += 1

    def release(self) -> None:
        self.datasets = None

    # ------------------------------------------------------------ results
    def end_to_end(self) -> dict:
        raw = sum(4 * self.snaps[s].n_values for s, _, _, _ in self.published)
        stored = sum(size for _, _, size, _ in self.published)
        return {"compress_MBps": raw / 1e6 / self.published[-1][3],
                "file_cr": raw / stored}

    def counts(self) -> tuple[int, int]:
        return len(self.published), 0

    def facts(self) -> dict:
        """What the per-layer readers need beyond the registry: snapshots
        in the window and the Lorenzo brick cells each compress did."""
        cells = bricks = 0
        for s, path, _, _ in self.published:
            rd = tacz_ref.RefReader(path)
            for lvl in rd.levels:
                bricks += len(lvl.subblocks)
                cells += sum(int(np.prod(sb.size)) for sb in lvl.subblocks)
        return {"snapshots": len(self.published), "lorenzo_cells": cells,
                "lorenzo_bricks": bricks}

    def sample(self) -> list:
        """One publish of each distinct snapshot, drawn from the seed."""
        rng = random.Random(self.seed + 5)
        by_snap: dict = {}
        for s, path, _, _ in self.published:
            by_snap.setdefault(s, []).append(path)
        return [(s, rng.choice(paths)) for s, paths in sorted(by_snap.items())]

    def check(self) -> dict:
        worst_ratio = 0.0
        limit = float("inf")
        cell_mismatch = 0
        checked = self.sample()
        for s, path in checked:
            snap = self.snaps[s]
            rd = tacz_ref.RefReader(path)
            if len(rd.levels) != len(snap.data):
                cell_mismatch += 1
                continue
            top = max(float(np.abs(d[m]).max()) for d, m in
                      zip(snap.data, snap.mask) if m.any())
            limit = min(limit, 1.0 + 2.0 ** -22 * top / self.eb)
            for li, (d, m) in enumerate(zip(snap.data, snap.mask)):
                if rd.levels[li].n_values != int(m.sum()) or \
                        not np.array_equal(rd.mask(li), m):
                    cell_mismatch += 1
                    continue
                gap = tacz_ref.eb_gap(rd.level(li), d, m)
                worst_ratio = max(worst_ratio, gap / self.eb)
        for _, path, _, _ in self.published:
            os.remove(path)
        return {
            "max_err_over_eb": {"value": worst_ratio, "limit": limit,
                                "ok": worst_ratio <= limit},
            "levels_with_wrong_cells": {"value": cell_mismatch, "limit": 0,
                                        "ok": cell_mismatch == 0},
            "files_checked": {"value": len(checked), "limit": 1,
                              "ok": len(checked) >= 1},
        }
