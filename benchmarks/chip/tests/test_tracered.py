"""The trace reduction, checked on a small trace recorded on a TPU v5e
chip (``data/trace_*.json``, written by ``tools/record_trace.py``) against
a brute-force count, and on hand-made planes."""
import glob
import json
import os

import pytest

import tracered

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _planes(events_by_line, host=()):
    dev = {"name": "/device:TPU:0",
           "lines": [{"name": k, "events": v}
                     for k, v in events_by_line.items()]}
    hostp = {"name": "/host:CPU",
             "lines": [{"name": "python", "events": list(host)}]}
    return [hostp, dev]


def test_busy_is_the_union_of_ops_inside_the_window():
    planes = _planes(
        {"XLA Ops": [["a", 0, 50], ["b", 40, 30], ["c", 200, 100],
                     ["d", 950, 100]],
         "XLA Modules": [["jit_decode_walk(3)", 0, 70],
                         ["jit_other(1)", 200, 100]]},
        host=[["bench.window", 10, 990], ["layer.x", 100, 50]])
    red = tracered.reduce(planes)
    assert red.window_s == pytest.approx(990e-9)
    # [10,70) + [200,300) + [950,1000) inside [10,1000)
    assert red.busy_s == pytest.approx(210e-9)
    assert red.kernel_s("decode_walk") == pytest.approx(60e-9)
    assert red.gaps[0] == ("host: outside any benchmark span",
                           pytest.approx(650e-9))
    assert ("layer.x", pytest.approx(130e-9)) in red.gaps
    assert red.breakdown()["device_ops"][0][0] == "c"


def test_the_marks_bound_a_trace_started_inside_the_window():
    planes = _planes(
        {"XLA Ops": [["a", 0, 50], ["b", 120, 30], ["c", 500, 100]]},
        host=[["bench.trace_from", 100, 1], ["layer.x", 200, 100],
              ["bench.trace_to", 399, 1]])
    red = tracered.reduce(planes)
    assert red.window_s == pytest.approx(300e-9)
    assert red.busy_s == pytest.approx(30e-9)


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tracered.reduce(_planes({"XLA Ops": []}))


def _brute(planes):
    w0 = w1 = None
    for p in planes:
        for ln in p["lines"]:
            for n, s, d in ln["events"]:
                if n == tracered.WINDOW_SPAN:
                    w0, w1 = s, s + d
    busy = 0
    devs = [p for p in planes if p["name"].startswith("/device:TPU:")]
    for p in devs:
        marks = set()
        for ln in p["lines"]:
            if ln["name"] == tracered.OPS_LINE:
                for _, s, d in ln["events"]:
                    # 1 µs resolution is enough for the comparison
                    marks.update(range(max(s, w0) // 1000,
                                       -(-min(s + d, w1) // 1000)))
        busy += len(marks) * 1000
    return (w1 - w0) / 1e9, busy / len(devs) / 1e9


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    DATA, "trace_*.json"))))
def test_recorded_chip_trace(path):
    with open(path) as f:
        planes = json.load(f)
    red = tracered.reduce(planes)
    window_s, busy_s = _brute(planes)
    assert red.n_devices == 1
    assert red.window_s == pytest.approx(window_s)
    assert red.busy_s == pytest.approx(busy_s, abs=2e-6 * 1000)
    assert 0 < red.busy_s < red.window_s
    assert red.modules
