"""Cells cut to sizes a CPU test can hold."""
import os

import harness
import peaks

SEED = 2 ** 31 + 4099

#: every configuration × traffic pair the harness runs, including the
#: ones the benchmark does not list yet
PAIRS = {
    "nyx_z10.compress": ("nyx_run1_z10", "insitu_series"),
    "nyx_t4.compress": ("nyx_run2_t4", "insitu_series"),
    "nyx_z10.serve_cold": ("nyx_run1_z10", "roi_cold_uniform"),
    "nyx_z10.serve_warm": ("nyx_run1_z10", "roi_warm_zipf"),
}


def cell(name: str, tmp_path, monkeypatch):
    """The named cell with a 64³ finest grid (32³ compress sub-domain for
    z10), small boxes, and its run-time state under ``tmp_path``.  Its
    metrics are those ``BENCHMARK.json`` lists for it, or for the listed
    cell of the same traffic kind when it is not listed."""
    monkeypatch.setattr(harness, "STATE", str(tmp_path / "state"))
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    bench = harness.benchmark()
    config, traffic = PAIRS[name]
    listed = {w["name"] for w in bench["workloads"]}
    kind = traffic.split("_")[0]
    like = name if name in listed else next(
        n for n in sorted(listed) if PAIRS[n][1].split("_")[0] == kind)
    c = harness.find_cell(bench, like)
    c.name = name
    c.config = harness.load_json(os.path.join(harness.HERE, "configs",
                                              f"{config}.json"))
    c.traffic = harness.load_json(os.path.join(harness.HERE, "traffic",
                                               f"{traffic}.json"))
    c.config["finest_grid"] = 64
    c.config["subdomain"] = [32, 32, 32] if "z10" in name else [64, 64, 64]
    if c.traffic["kind"] == "serve":
        c.traffic.update(box_edge=8, box_edges=[8, 16], rate_rps=4.0,
                         verify_sample=6)
    return c
