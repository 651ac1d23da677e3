"""The open-loop client times each request from when it was due: a
stalled request raises the latency of the requests queued behind it."""
import http.server
import json
import struct
import threading
import time

import pytest

import openloop


class _Stub(http.server.BaseHTTPRequestHandler):
    calls = 0
    protocol_version = "HTTP/1.1"

    def do_POST(self):
        self.rfile.read(int(self.headers["Content-Length"]))
        _Stub.calls += 1
        if _Stub.calls == 1:
            time.sleep(1.0)
        header = json.dumps({"results": [[]]}).encode()
        body = struct.pack("<I", len(header)) + header
        self.send_response(200)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def log_message(self, *a):
        pass


@pytest.fixture
def stub():
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Stub)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    _Stub.calls = 0
    yield srv.server_address[1]
    srv.shutdown()
    srv.server_close()
    t.join(timeout=10)


def test_a_stall_counts_in_later_requests(stub):
    spec = {"host": "127.0.0.1", "port": stub, "levels": [0],
            "requests": [[0.0, [[0, 1]] * 3], [0.1, [[0, 1]] * 3],
                         [0.2, [[0, 1]] * 3]],
            "seconds": 0.3, "grace_s": 10, "threads": 1}
    out = openloop.run_client(spec)
    lat = [r[1] for r in out["records"]]
    assert lat[0] >= 1.0
    # due at 0.1 s and 0.2 s, sent after the stalled first one finished
    assert lat[1] >= 0.85 and lat[2] >= 0.75
    assert out["records"][1][4] >= 0.85          # send lag reported


def test_schedule_is_fixed_by_rate_window_and_seed():
    t = {"rate_rps": 4.0, "levels": [0, 1], "boxes": "uniform",
         "box_edge": 8, "box_set_seed": 12345}
    a = openloop.schedule(t, (64, 64, 64), 2 ** 40 + 3, 5.0)
    b = openloop.schedule(t, (64, 64, 64), 2 ** 40 + 3, 5.0)
    c = openloop.schedule(t, (64, 64, 64), 7, 5.0)
    assert a == b and a != c
    assert len(a["requests"]) == 20
    assert [r[0] for r in a["requests"]] == [i / 4.0 for i in range(20)]
    for _, box in a["requests"]:
        assert all(hi - lo == 8 and 0 <= lo and hi <= 64 for lo, hi in box)


def test_uniform_boxes_are_one_set_that_the_seed_orders():
    t = {"rate_rps": 4.0, "levels": [0], "boxes": "uniform", "box_edge": 8,
         "box_set_seed": 12345}
    a = openloop.schedule(t, (64, 64, 64), 2 ** 40 + 3, 5.0)
    b = openloop.schedule(t, (64, 64, 64), 7, 5.0)
    boxes_a = [r[1] for r in a["requests"]]
    boxes_b = [r[1] for r in b["requests"]]
    assert boxes_a != boxes_b and sorted(boxes_a) == sorted(boxes_b)
    assert [r[0] for r in a["requests"]] == [r[0] for r in b["requests"]]
