"""A new configuration, traffic mix and per-layer metric are found by
name from their own files and new ``BENCHMARK.json`` entries, with no
edit to any file the harness already has."""
import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(os.path.dirname(BENCH))


@pytest.fixture
def tree(tmp_path):
    """A copy of the checkout's benchmark, as a later PR would extend it."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".state", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def test_new_files_are_found_by_name(tree, monkeypatch):
    chip = tree / "benchmarks" / "chip"
    bench = json.loads((tree / "BENCHMARK.json").read_text())
    cfg = json.loads((chip / "configs" / "nyx_run1_z10.json").read_text())
    cfg["name"] = "nyx_run1_z5"
    cfg["generator"]["densities"] = [0.58, 0.42]
    (chip / "configs" / "nyx_run1_z5.json").write_text(json.dumps(cfg))
    (chip / "traffic" / "roi_burst.json").write_text(json.dumps(
        {"kind": "serve", "boxes": "uniform", "box_edge": 16,
         "box_set_seed": 7, "levels": [0], "rate_rps": 9.0}))
    (chip / "layer_metrics" / "serve.new_ms.py").write_text(
        "def read(win):\n    return win.facts['x'] * 2.0\n")
    bench["configs"].append({"name": "nyx_run1_z5", "source": "x",
                             "file": "benchmarks/chip/configs/"
                                     "nyx_run1_z5.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "nyx_z5.burst",
                               "config": "nyx_run1_z5",
                               "traffic": "roi_burst", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "serve.new_ms", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "x", "moves": "roi_p90_ms",
                               "workloads": ["nyx_z5.burst"]})
    for m in bench["end_to_end"]:
        if m["name"] in ("roi_p50_ms", "roi_p90_ms"):
            m["workloads"].append("nyx_z5.burst")
    (tree / "BENCHMARK.json").write_text(json.dumps(bench))

    for mod in ("harness", "run", "peaks"):
        monkeypatch.delitem(sys.modules, mod, raising=False)
    monkeypatch.syspath_prepend(str(chip))
    import harness
    import run

    assert harness.ROOT == str(tree)
    cell = harness.find_cell(harness.benchmark(), "nyx_z5.burst")
    assert cell.config["generator"]["densities"] == [0.58, 0.42]
    assert cell.traffic["rate_rps"] == 9.0
    assert cell.driver_path == str(chip / "drivers" / "serve.py")
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "roi_p50_ms",
                                                     "roi_p90_ms"]
    win = harness.Window({}, {}, facts={"x": 1.5})
    got = run._layer_metrics(cell, win)
    assert got["serve.new_ms"] == {"value": 3.0, "unit": "ms"}
    for mod in ("harness", "run", "peaks"):
        sys.modules.pop(mod, None)


def test_a_reader_that_finds_nothing_leaves_its_metric_out():
    sys.path.insert(0, BENCH)
    import harness
    import run

    cell = harness.find_cell(harness.benchmark(), "nyx_z10.serve_cold")
    got = run._layer_metrics(cell, harness.Window({}, {}, facts={}))
    assert got == {}
