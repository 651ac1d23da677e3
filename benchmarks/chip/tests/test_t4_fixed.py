"""``nyx_t4.compress`` on its fixed series (``nyx_run2_t4_fixed``),
rehearsed on the CPU at a 64³ finest grid: every seed compresses the
same three snapshots, so ``file_cr`` is one number for every seed, and
the two readers of the compressor's sub-block and launch counters stay
silent where there is nothing to read."""
import os

import pytest

import harness
import run
import tiny

CELL = "nyx_t4.compress"
CONFIG = "nyx_run2_t4_fixed"


def _cell(tmp_path, monkeypatch):
    c = tiny.cell(CELL, tmp_path, monkeypatch)
    c.config = harness.load_json(os.path.join(harness.HERE, "configs",
                                              f"{CONFIG}.json"))
    c.config["finest_grid"] = 64
    c.config["subdomain"] = [64, 64, 64]
    return c


def _reader(name):
    return harness.load_module(
        os.path.join(harness.HERE, "layer_metrics", f"{name}.py"), name)


def test_the_cell_runs_the_fixed_configuration():
    bench = harness.benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    assert cells[CELL]["config"] == CONFIG and cells[CELL]["chips"] == 1
    cfg = harness.find_cell(bench, CELL).config
    assert cfg["name"] == CONFIG
    assert cfg["generator"]["seed_variance_share"] == 0.0


def test_rehearsal_is_correct(tmp_path, monkeypatch):
    c = _cell(tmp_path, monkeypatch)
    result, checks = run.run(c, tiny.SEED, 1.0, False, check_device=False)
    assert result["correct"], checks
    assert result["attempted"] >= 3 and result["failed"] == 0
    assert set(result["metrics"]) == {"setup_s", "compress_MBps", "file_cr"}
    assert checks["levels_with_wrong_cells"]["value"] == 0
    assert checks["files_checked"]["value"] == 3


def test_file_cr_is_the_same_for_every_seed(tmp_path, monkeypatch):
    crs = []
    for i, seed in enumerate((tiny.SEED, 2 ** 31 + 77777)):
        c = _cell(tmp_path / str(i), monkeypatch)
        result, checks = run.run(c, seed, 0.5, False, check_device=False)
        assert result["correct"], checks
        crs.append(result["metrics"]["file_cr"]["value"])
    assert crs[0] == crs[1] > 1


def test_traced_run_reports_brick_us_and_no_launches_on_the_cpu(
        tmp_path, monkeypatch):
    c = _cell(tmp_path, monkeypatch)
    want = {m["name"] for m in c.per_layer}
    assert {"compress.brick_us", "compress.bricks_per_launch"} <= want
    result, _ = run.run(c, tiny.SEED, 1.0, True, check_device=False)
    assert result["correct"]
    got = result["metrics"]
    assert got["compress.brick_us"]["value"] > 0
    assert got["compress.brick_us"]["unit"] == "us"
    # the CPU computes the Lorenzo codes on the host: no launch to count
    assert "compress.bricks_per_launch" not in got
    assert "compress.partition_s" in got and "compress.recon_s" in got


def _window():
    stage = "tacz_compress_stage_seconds"
    before = {(stage, "stage=partition"): (1.0, 4),
              (stage, "stage=gather"): (0.5, 4),
              (stage, "stage=recon"): (0.5, 8),
              (stage, "stage=entropy"): (9.0, 4),
              ("tacz_compress_subblocks_total", "strategy=opst"): 100.0,
              ("tacz_device_items_total", "device=tpu:0,stage=lorenzo"): 50.0,
              ("tacz_device_launches_total", "stage=lorenzo"): 1.0}
    after = {(stage, "stage=partition"): (1.3, 8),
             (stage, "stage=gather"): (0.6, 8),
             (stage, "stage=recon"): (0.7, 16),
             (stage, "stage=entropy"): (19.0, 8),
             ("tacz_compress_subblocks_total", "strategy=opst"): 900.0,
             ("tacz_compress_subblocks_total", "strategy=akdtree"): 200.0,
             ("tacz_device_items_total", "device=tpu:0,stage=lorenzo"): 650.0,
             ("tacz_device_launches_total", "stage=lorenzo"): 5.0}
    return harness.Window(before, after)


def test_readers_divide_the_window_deltas():
    win = _window()
    # (0.3 + 0.1 + 0.2) s over 1000 sub-blocks; entropy is not a walk
    assert _reader("compress.brick_us").read(win) == pytest.approx(600.0)
    assert _reader("compress.bricks_per_launch").read(win) == \
        pytest.approx(150.0)


@pytest.mark.parametrize("name", ["compress.brick_us",
                                  "compress.bricks_per_launch"])
def test_readers_are_silent_without_their_family_or_counts(name,
                                                           monkeypatch):
    from repro.obs import metrics as obsm
    from repro.obs.registry import MetricsRegistry

    reader = _reader(name)
    assert reader.read(harness.Window({}, {})) is None
    # a program that lacks the counters (the registry declares no family)
    monkeypatch.setattr(obsm, "REGISTRY", MetricsRegistry())
    assert reader.read(_window()) is None
