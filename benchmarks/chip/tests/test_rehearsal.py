"""A whole run of each driver on the CPU at a tiny size, with the chip
guard bypassed here only: set-up, window, metrics and the reference
check all run as on the chip."""
import pytest

import run
import tiny


@pytest.mark.parametrize("name", ["nyx_z10.compress", "nyx_t4.compress"])
def test_compress_run_is_correct(name, tmp_path, monkeypatch):
    c = tiny.cell(name, tmp_path, monkeypatch)
    result, checks = run.run(c, tiny.SEED, 1.0, False, check_device=False)
    assert result["correct"], checks
    assert result["attempted"] >= 1 and result["failed"] == 0
    m = result["metrics"]
    assert set(m) == {"setup_s", "compress_MBps", "file_cr"}
    assert m["compress_MBps"]["value"] > 0 and m["file_cr"]["value"] > 1
    assert checks["max_err_over_eb"]["value"] <= \
        checks["max_err_over_eb"]["limit"]


@pytest.mark.parametrize("name", ["nyx_z10.serve_cold",
                                  "nyx_z10.serve_warm"])
def test_serve_run_is_correct(name, tmp_path, monkeypatch):
    c = tiny.cell(name, tmp_path, monkeypatch)
    result, checks = run.run(c, tiny.SEED, 2.0, False, check_device=False)
    assert result["correct"], checks
    assert result["attempted"] == 8 and result["failed"] == 0
    m = result["metrics"]
    assert set(m) == {"setup_s", "roi_p50_ms", "roi_p90_ms"}
    assert 0 < m["roi_p50_ms"]["value"] <= m["roi_p90_ms"]["value"]


@pytest.mark.parametrize("name", ["nyx_z10.compress", "nyx_z10.serve_cold",
                                  "nyx_z10.serve_warm"])
def test_traced_run_reports_the_cells_layer_metrics(name, tmp_path,
                                                    monkeypatch):
    c = tiny.cell(name, tmp_path, monkeypatch)
    result, _ = run.run(c, tiny.SEED, 1.0, True, check_device=False)
    assert result["correct"]
    want = {m["name"] for m in c.per_layer}
    got = set(result["metrics"])
    # the CPU trace has no device plane: the device readers stay silent
    assert got <= want
    assert got >= {n for n in want if not n.endswith("roofline")
                   and not n.startswith("device_idle")
                   and n != "serve.entropy_decode_ms"}
    assert result["device"]["window_s"] > 0
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_no_accelerator_exits_without_a_result(tmp_path, monkeypatch, capsys):
    c = tiny.cell("nyx_z10.compress", tmp_path, monkeypatch)
    with pytest.raises(SystemExit) as exc:
        run.run(c, tiny.SEED, 1.0, False)
    assert "not a TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_a_partly_traced_run_reads_spans_over_the_whole_window(
        tmp_path, monkeypatch):
    """A mix with ``trace_seconds`` traces the end of its window only;
    spans and counters still cover every request of the window."""
    c = tiny.cell("nyx_z10.serve_cold", tmp_path, monkeypatch)
    c.traffic["trace_seconds"] = 0.75
    seen = {}
    real = run._layer_metrics

    def spy(cell, win):
        seen["win"] = win
        return real(cell, win)

    monkeypatch.setattr(run, "_layer_metrics", spy)
    result, _ = run.run(c, tiny.SEED, 2.0, True, check_device=False)
    assert result["correct"]
    win = seen["win"]
    _, n_all = win.hist("tacz_http_request_seconds", route="/v1/regions")
    _, n_traced = win.traced.hist("tacz_http_request_seconds",
                                  route="/v1/regions")
    assert n_all == result["attempted"] == 8
    assert n_traced < n_all
    assert 0.5 < result["device"]["window_s"] < 1.9
    assert "serve.http_ms" in result["metrics"]
