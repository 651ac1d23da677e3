"""Open-loop ROI traffic: the request schedule and the client process.

The traffic model is a copy of ``repro.serving.loadgen`` (``ZipfWorkload``:
a seeded Zipf population over per-axis box sizes) with two changes the
benchmark needs:

* every request's latency is timed from the moment it was *due*, not from
  when a client thread got round to sending it, so a stall shows in the
  latency of every request queued behind it;
* the schedule covers a fixed window (``rate × seconds`` requests due at
  ``i / rate``), not a fixed request count.

The client runs as a child process (``python openloop.py <spec.json>``)
that imports neither JAX nor the program, so its HTTP work never competes
with the server for the parent's interpreter lock.  It speaks the region
wire protocol itself: ``POST /v1/regions`` with a JSON body, answered by a
u32 header length, a JSON header and raw little-endian float32 crops.  A
429/503 with ``Retry-After`` means busy: the client waits as told (at most
2 s) and sends again, and the wait counts in the latency.
"""
from __future__ import annotations

import http.client
import json
import random
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor


# ---------------------------------------------------------------- schedule


def uniform_boxes(rng: random.Random, n: int, shape, edge: int):
    """``n`` cubes of ``edge`` finest cells, origins uniform over the
    domain."""
    return [tuple((lo, lo + edge) for lo in
                  (rng.randrange(0, dim - edge + 1) for dim in shape))
            for _ in range(n)]


def zipf_population(rng: random.Random, shape, population: int,
                    edges) -> list:
    """The query population of ``ZipfWorkload``: rank ``r`` has a cube of
    ``edges[r % len(edges)]`` cells at a uniform origin."""
    return [uniform_boxes(rng, 1, shape, edges[r % len(edges)])[0]
            for r in range(population)]


def schedule(traffic: dict, shape, seed: int, seconds: float) -> dict:
    """Requests due in ``[0, seconds)`` at the traffic's fixed rate.

    ``traffic["boxes"]`` is ``"uniform"`` (one box per request, drawn
    from the mix's ``box_set_seed``; the run's seed only orders them, so
    every seed asks for the same work) or ``"zipf"`` (draws from a seeded
    population, probability of rank ``r`` ∝ ``1/(r+1)**s``).  Returns
    ``{"requests": [[due_s, box]], "levels", "population"}``; the
    population (zipf only) is what set-up warms.
    """
    rate = float(traffic["rate_rps"])
    n = int(round(rate * seconds))
    rng = random.Random(int(seed) * 7919 + 17)
    levels = [int(li) for li in traffic["levels"]]
    population = None
    if traffic["boxes"] == "uniform":
        boxes = uniform_boxes(random.Random(int(traffic["box_set_seed"])),
                              n, shape, int(traffic["box_edge"]))
        rng.shuffle(boxes)
    elif traffic["boxes"] == "zipf":
        population = zipf_population(rng, shape, int(traffic["population"]),
                                     [int(e) for e in traffic["box_edges"]])
        s = float(traffic["zipf_s"])
        w = [1.0 / (r + 1) ** s for r in range(len(population))]
        boxes = rng.choices(population, w, k=n)
    else:
        raise ValueError(f"unknown box model {traffic['boxes']!r}")
    return {"requests": [[i / rate, [list(r) for r in b]]
                         for i, b in enumerate(boxes)],
            "levels": levels,
            "population": population}


def parse_regions(blob: bytes):
    """``[(level, box, shape, float32 bytes)]`` of a one-box response."""
    import struct

    (hlen,) = struct.unpack_from("<I", blob, 0)
    header = json.loads(blob[4:4 + hlen])
    payload = blob[4 + hlen:]
    out = []
    for row in header["results"]:
        for r in row:
            n = 4
            for d in r["shape"]:
                n *= int(d)
            out.append((int(r["level"]), [tuple(b) for b in r["box"]],
                        tuple(int(d) for d in r["shape"]),
                        payload[r["offset"]:r["offset"] + n]))
    return out


# ------------------------------------------------------------------ client


class _Conn(threading.local):
    conn = None


def _post(local: _Conn, host: str, port: int, body: bytes, timeout: float):
    """One POST; returns (status, retry_after or None, body)."""
    for attempt in range(2):
        if local.conn is None:
            local.conn = http.client.HTTPConnection(host, port,
                                                    timeout=timeout)
        try:
            local.conn.request("POST", "/v1/regions", body,
                               {"Content-Type": "application/json"})
            resp = local.conn.getresponse()
            data = resp.read()
            return resp.status, resp.getheader("Retry-After"), data
        except (http.client.HTTPException, OSError):
            local.conn.close()
            local.conn = None
            if attempt:
                raise
    raise AssertionError("unreachable")


def run_client(spec: dict, go=None) -> dict:
    """Send ``spec["requests"]`` on schedule; returns per-request records.

    ``go``: optional callable that blocks until the window opens (the
    child process waits for a line on stdin).  Each record is ``[index,
    latency_s or None, status, busy_retries, send_lag_s]``; bodies of the
    indices in ``spec["keep"]`` are written to ``spec["keep_dir"]``.
    """
    host, port = spec["host"], int(spec["port"])
    levels = spec["levels"]
    reqs = spec["requests"]
    keep = set(spec.get("keep", []))
    grace = float(spec.get("grace_s", 60.0))
    window = float(spec["seconds"])
    local = _Conn()
    records: list = [None] * len(reqs)
    if go is not None:
        go()
    t0 = time.perf_counter()
    deadline = t0 + window + grace

    def one(i: int) -> None:
        due, box = reqs[i]
        lag = time.perf_counter() - (t0 + due)
        body = json.dumps({"boxes": [box], "levels": levels}).encode()
        retries = 0
        status = None
        try:
            while True:
                left = deadline - time.perf_counter()
                if left <= 0:
                    break
                status, retry_after, data = _post(local, host, port, body,
                                                  left)
                if status in (429, 503) and retry_after is not None:
                    retries += 1
                    time.sleep(min(float(retry_after), 2.0,
                                   max(deadline - time.perf_counter(), 0)))
                    continue
                break
        except (http.client.HTTPException, OSError) as exc:
            status = f"{type(exc).__name__}: {exc}"
        done = time.perf_counter()
        ok = status == 200 and done <= deadline
        if ok and i in keep:
            with open(f"{spec['keep_dir']}/resp_{i}.bin", "wb") as f:
                f.write(data)
        records[i] = [i, (done - (t0 + due)) if ok else None, status,
                      retries, max(lag, 0.0)]

    with ThreadPoolExecutor(max_workers=int(spec.get("threads", 16))) as ex:
        futs = []
        for i, (due, _) in enumerate(reqs):
            wait = t0 + due - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            futs.append(ex.submit(one, i))
        for f in futs:
            f.result()
    return {"records": records, "elapsed_s": time.perf_counter() - t0}


def main(argv) -> int:
    spec_path = argv[1]
    with open(spec_path) as f:
        spec = json.load(f)
    print("ready", flush=True)

    def go():
        sys.stdin.readline()

    out = run_client(spec, go)
    with open(spec["result_path"], "w") as f:
        json.dump(out, f)
    print("done", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
