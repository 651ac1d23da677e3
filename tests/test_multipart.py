"""Multi-part parallel TACZ snapshots (ISSUE 5): writer, reader, serving
conformance, crash consistency.

The contract:

  * a multi-part snapshot reads **bit-identically** to the equivalent
    single-file snapshot — ``read``, ``read_roi``, cold/warm
    ``RegionServer``, and the sharded router — across part counts 1–4
    and across v1/v2 payload codecs (property-tested);
  * the write-side partition is the serving-side ``ShardMap``'s
    rendezvous hashing: a shard aligned with its part never opens other
    parts' files;
  * the manifest is the atomic commit point: a killed/failed part writer
    never publishes one, stale ``part-*.tmp`` litter is detected, a
    previously published snapshot stays valid, and a re-run converges.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

from repro import io as tacz
from repro.io import manifest as mfst
from repro.io.parallel import (MultiPartReader, ParallelTACZWriter,
                               write_multipart)
from repro.io.reader import probe_index_crc
from repro.serving import (RegionServer, ShardMap, ShardedRegionRouter,
                           serve)

BOXES = [((0, 8), (0, 8), (0, 8)),
         ((5, 23), (11, 30), (2, 9)),
         ((0, 32), (0, 32), (0, 32)),
         ((14, 18), (14, 18), (14, 18)),
         ((40, 50), (0, 4), (0, 4))]          # beyond the extent


def _assert_identical_reads(single_path, multi_path, res, boxes=BOXES):
    """read()/read_roi() of the multi-part snapshot == single-file."""
    with tacz.TACZReader(single_path) as srd, \
            MultiPartReader(multi_path) as mrd:
        assert mrd.n_levels == srd.n_levels
        assert mrd.subblock_keys() == srd.subblock_keys()
        for a, b in zip(srd.read(), mrd.read()):
            np.testing.assert_array_equal(a, b)
        for box in boxes:
            for a, b in zip(srd.read_roi(box), mrd.read_roi(box)):
                assert (a.level, a.ratio, a.box) == (b.level, b.ratio, b.box)
                np.testing.assert_array_equal(a.data, b.data)
        for lr, rec in zip(res.levels, mrd.read()):
            np.testing.assert_array_equal(lr.recon, rec)


# ----------------------------- deterministic --------------------------------


@pytest.mark.parametrize("parts", [1, 2, 3, 4])
@pytest.mark.parametrize("codec", ["none", "zlib"])
def test_multipart_matches_single_file(make_amr_snapshot, parts, codec):
    """Payload-slice fan-out (shared codebook): bit-identical reads AND
    matching level signatures — part payload bytes equal the single
    file's, so cache carry-over works across single↔multi republish."""
    single = make_amr_snapshot(codec=codec, name="single")
    multi = make_amr_snapshot(codec=codec, parts=parts, name="multi")
    _assert_identical_reads(single.path, multi.path, single.res)
    with tacz.TACZReader(single.path) as srd, \
            MultiPartReader(multi.path) as mrd:
        for li in range(srd.n_levels):
            assert mrd.level_signature(li) == srd.level_signature(li)
        assert mrd.n_parts == parts
        assert mrd.version == srd.version


@pytest.mark.parametrize("mode", ["thread", "process"])
def test_parallel_writer_compresses_raw_levels(tmp_path, make_amr_snapshot,
                                               mode):
    """Mode-B fan-out: each worker compresses its own brick partition —
    per-part codebooks, but decoded values bit-identical to the
    single-writer path."""
    snap = make_amr_snapshot(densities=[0.35, 0.65], seed=5)
    path = os.path.join(str(tmp_path), "raw.taczd")
    with ParallelTACZWriter(path, parts=3, mode=mode, eb=snap.eb) as w:
        for lvl in snap.ds.levels:
            w.add_level(lvl.data, lvl.mask, ratio=lvl.ratio)
    _assert_identical_reads(snap.path, path, snap.res)


def test_gsp_level_owned_by_one_part(tmp_path):
    """A single-payload (gsp) level lands whole in its owner part; the
    other parts carry head+mask stubs, and the merged read matches."""
    from repro.core import amr, hybrid
    ds = amr.synthetic_amr((32, 32, 32), densities=[0.9, 0.1],
                           refine_block=4, seed=7)
    lvl = ds.levels[0]
    lr = hybrid.compress_level(lvl.data, lvl.mask, eb=0.01, unit=4,
                               strategy="gsp")
    path = os.path.join(str(tmp_path), "gsp.taczd")
    with ParallelTACZWriter(path, parts=3) as w:
        w.add_compressed(lr)
    body = mfst.load(path)
    owners = [p["levels"][0] for p in body["parts"]]
    assert sorted(sum(owners, [])) == [0]       # exactly one owner
    with MultiPartReader(path) as rd:
        [rec] = rd.read()
        np.testing.assert_array_equal(lr.recon, rec)
    # streaming a raw gsp level through worker-side compression too
    path2 = os.path.join(str(tmp_path), "gsp2.taczd")
    with ParallelTACZWriter(path2, parts=3, eb=0.01, unit=4,
                            strategy="gsp") as w:
        w.add_level(lvl.data, lvl.mask)
    with MultiPartReader(path2) as rd:
        [rec] = rd.read()
        np.testing.assert_array_equal(lr.recon, rec)


def test_region_server_and_router_serve_multipart(make_amr_snapshot):
    """The serving stack works over a snapshot *directory* unchanged:
    cold==warm==single-server, and a part-aligned shard fleet touches
    only its own parts."""
    single = make_amr_snapshot(densities=[0.35, 0.65], seed=5,
                               name="single")
    multi = make_amr_snapshot(densities=[0.35, 0.65], seed=5, parts=3,
                              name="multi")
    with tacz.TACZReader(single.path) as rd, \
            RegionServer(multi.path, cache_bytes=32 << 20) as srv:
        for box in BOXES:
            ref = rd.read_roi(box)
            for g, r in zip(srv.get_roi(box), ref):        # cold
                np.testing.assert_array_equal(g.data, r.data)
            for g, r in zip(srv.get_roi(box), ref):        # warm
                np.testing.assert_array_equal(g.data, r.data)

    # part-aligned fleet: shard ids from the manifest's partition config
    with MultiPartReader(multi.path) as mrd:
        m = ShardMap.from_dict(mrd.partition)
        assert set(m.shards) == {f"part-{i:04d}" for i in range(3)}
    servers, urls = {}, {}
    try:
        for sid in m.shards:
            httpd = serve(multi.path, port=0, cache_bytes=16 << 20,
                          shard_map=m, shard_id=sid)
            threading.Thread(target=httpd.serve_forever,
                             daemon=True).start()
            servers[sid] = httpd
            urls[sid] = f"http://127.0.0.1:{httpd.server_address[1]}"
        with RegionServer(single.path) as baseline, \
                ShardedRegionRouter(multi.path, m, urls) as router:
            ref = baseline.get_regions(BOXES)
            got = router.get_regions(BOXES)
            for per_got, per_ref in zip(got, ref):
                for g, r in zip(per_got, per_ref):
                    assert (g.level, g.ratio, g.box) == \
                        (r.level, r.ratio, r.box)
                    np.testing.assert_array_equal(g.data, r.data)
            assert router.counters["local_fallbacks"] == 0
        # the locality guarantee: each shard opened ONLY its own part
        for pi, sid in enumerate(sorted(m.shards)):
            reader = servers[sid].region_server.reader
            assert reader.open_parts in ([], [pi]), \
                f"shard {sid} opened foreign parts: {reader.open_parts}"
    finally:
        for httpd in servers.values():
            httpd.shutdown()
            httpd.server_close()
            httpd.region_server.close()


def test_multipart_hot_swap_through_server(tmp_path, make_amr_snapshot):
    """Republishing a multi-part snapshot (even with a different part
    count) hot-swaps through the footer/manifest CRC like a single file,
    and unreferenced old parts are cleaned up."""
    a = make_amr_snapshot(densities=[0.35, 0.65], seed=5)
    b = make_amr_snapshot(densities=[0.5, 0.5], seed=9)
    path = os.path.join(str(tmp_path), "hot.taczd")
    write_multipart(path, a.res, parts=3)
    box = ((0, 32), (0, 32), (0, 32))
    with RegionServer(path, cache_bytes=32 << 20) as srv:
        np.testing.assert_array_equal(srv.get_roi(box)[0].data,
                                      a.res.levels[0].recon)
        old = srv.snapshot_crc
        assert probe_index_crc(path) == old
        write_multipart(path, b.res, parts=2)          # atomic republish
        assert srv.maybe_reload() is True
        assert srv.snapshot_crc != old
        np.testing.assert_array_equal(srv.get_roi(box)[0].data,
                                      b.res.levels[0].recon)
    assert sorted(n for n in os.listdir(path) if n.endswith(".tacz")) == \
        ["part-0000.tacz", "part-0001.tacz"]


# --------------------------- manifest validation ----------------------------


def test_manifest_crc_and_part_binding(make_amr_snapshot):
    multi = make_amr_snapshot(parts=2, name="m")
    mpath = os.path.join(multi.path, mfst.MANIFEST_NAME)

    # CRC mismatch: hand-edited manifest fails loudly
    with open(mpath) as f:
        body = json.load(f)
    body["n_levels"] = 99
    with open(mpath, "w") as f:
        json.dump(body, f)
    with pytest.raises(ValueError, match="CRC"):
        MultiPartReader(multi.path)
    assert probe_index_crc(multi.path) is None

    # truncate a part: fails at open (torn republish — the part no
    # longer matches the manifest's binding)
    multi2 = make_amr_snapshot(parts=2, name="m2")
    part = os.path.join(multi2.path, "part-0001.tacz")
    with open(part, "rb") as f:
        blob = f.read()
    with open(part, "wb") as f:
        f.write(blob[:len(blob) // 2])
    with pytest.raises(ValueError):
        MultiPartReader(multi2.path)

    # a *stale* part (valid TACZ, wrong generation) is caught by the
    # manifest's recorded index_crc
    multi3 = make_amr_snapshot(parts=2, name="m3")
    other = make_amr_snapshot(densities=[0.5, 0.5], seed=9, name="other")
    import shutil
    shutil.copy(other.path, os.path.join(multi3.path, "part-0001.tacz"))
    with pytest.raises(ValueError, match="CRC"):
        MultiPartReader(multi3.path)

    # flipped payload bytes inside a part are localized like the
    # single-file case: open succeeds, verify()/reads fail loudly
    multi4 = make_amr_snapshot(parts=2, name="m4")
    part = os.path.join(multi4.path, "part-0000.tacz")
    with open(part, "rb") as f:
        blob = bytearray(f.read())
    with tacz.TACZReader(part) as prd:
        sb = next(sb for e in prd.levels for sb in e.subblocks)
    blob[sb.payload_off + sb.payload_len - 1] ^= 0xFF
    # keep the footer/index intact: only payload bytes changed, so the
    # index CRC still matches and open succeeds
    with open(part, "wb") as f:
        f.write(bytes(blob))
    with MultiPartReader(multi4.path) as rd:
        with pytest.raises(IOError, match="CRC"):
            rd.verify()

    # missing part file
    multi3 = make_amr_snapshot(parts=2, name="m3")
    os.remove(os.path.join(multi3.path, "part-0000.tacz"))
    with pytest.raises(OSError):
        MultiPartReader(multi3.path)


# --------------------------- crash consistency ------------------------------


def test_process_workers_never_get_a_device_engine(tmp_path, monkeypatch,
                                                   make_amr_snapshot):
    """One process per chip: even when the backend probe reports a TPU,
    process-mode workers are handed host engines (and an explicit device
    engine is refused), while thread workers follow the probe."""
    import repro.device

    monkeypatch.setattr(repro.device, "tpu_attached", lambda: True)
    snap = make_amr_snapshot(densities=[0.35, 0.65], seed=5)
    path = os.path.join(str(tmp_path), "proc.taczd")
    heads = []
    with ParallelTACZWriter(path, parts=2, mode="process",
                            eb=snap.eb) as w:
        # the part writers were started with this engine
        assert w._entropy_eng == "batched"
        dispatch = w._dispatch

        def spy(pi, task, check=True):
            if isinstance(task, dict) and "head" in task:
                heads.append(task["head"])
            return dispatch(pi, task, check)

        w._dispatch = spy
        for lvl in snap.ds.levels:
            w.add_level(lvl.data, lvl.mask, ratio=lvl.ratio)
    assert heads and all(h["lorenzo_engine"] == "numpy"
                         and h["entropy_engine"] == "batched"
                         for h in heads)
    with MultiPartReader(path) as rd:
        for lr, rec in zip(snap.res.levels, rd.read()):
            assert rec.shape == lr.recon.shape
    for knob in ("lorenzo_engine", "entropy_engine"):
        with pytest.raises(ValueError, match="process workers"):
            ParallelTACZWriter(os.path.join(str(tmp_path), knob),
                               mode="process", **{knob: "pallas"})
    w = ParallelTACZWriter(os.path.join(str(tmp_path), "thr"), parts=1,
                           mode="thread", eb=snap.eb)
    try:
        assert (w._engine, w._entropy_eng) == ("pallas", "pallas")
    finally:
        w.abort()


def test_killed_part_worker_never_publishes(tmp_path, make_amr_snapshot):
    """Kill one part worker mid-republish: close() must fail, the new
    manifest must not appear, the victim's tmp litter is detected — and
    the previously published snapshot must survive *byte-intact* (the
    two-phase commit: no part is renamed until every worker reported)."""
    snap = make_amr_snapshot(densities=[0.35, 0.65], seed=5)
    prior = make_amr_snapshot(densities=[0.5, 0.5], seed=9)
    path = os.path.join(str(tmp_path), "killed.taczd")
    write_multipart(path, prior.res, parts=3)      # snapshot A, published
    crc_a = probe_index_crc(path)
    w = ParallelTACZWriter(path, parts=3, mode="process", eb=snap.eb)
    try:
        w.add_level(snap.ds.levels[0].data, snap.ds.levels[0].mask, ratio=1)
        victim = w._workers[1]
        victim_tmp = os.path.join(path, "part-0001.tacz.tmp")
        deadline = time.time() + 60
        while not os.path.exists(victim_tmp):   # wait for the worker to
            assert time.time() < deadline       # actually be mid-stream
            time.sleep(0.02)
        victim.terminate()
        victim.join()
        with pytest.raises(RuntimeError, match="manifest not published"):
            for _ in range(50):   # the dead worker surfaces on add or close
                w.add_level(snap.ds.levels[1].data, snap.ds.levels[1].mask,
                            ratio=2)
            w.close()
    finally:
        w.abort()                 # what a with-block would do on the raise
    # the surviving workers' tmps were aborted away; the killed worker had
    # no chance to clean its own — detected as stale litter
    assert mfst.stale_parts(path) == ["part-0001.tacz.tmp"]
    # snapshot A is untouched: same generation, bit-identical reads
    assert probe_index_crc(path) == crc_a
    with MultiPartReader(path) as rd:
        for lr, rec in zip(prior.res.levels, rd.read()):
            np.testing.assert_array_equal(lr.recon, rec)


def test_worker_error_aborts_all_parts(tmp_path):
    """A failing encode in any worker surfaces to the producer; no
    manifest, no part files, no tmp litter (orderly abort)."""
    path = os.path.join(str(tmp_path), "err.taczd")
    w = ParallelTACZWriter(path, parts=2, eb=-1.0)   # invalid bound
    with pytest.raises((RuntimeError, ValueError)):
        for _ in range(50):
            w.add_level(np.ones((8, 8, 8), np.float32))
        w.close()
    w.abort()
    assert not os.path.exists(os.path.join(path, mfst.MANIFEST_NAME))
    assert mfst.stale_parts(path) == []
    assert not any(n.endswith(".tacz") for n in os.listdir(path))


def test_crash_rerun_converges_and_keeps_old_snapshot(tmp_path,
                                                      make_amr_snapshot):
    """Kill-style litter (stale tmps, no new manifest) must leave a
    previously published snapshot serving, be detected, and disappear
    after a successful re-run."""
    a = make_amr_snapshot(densities=[0.35, 0.65], seed=5)
    b = make_amr_snapshot(densities=[0.5, 0.5], seed=9)
    path = os.path.join(str(tmp_path), "conv.taczd")
    write_multipart(path, a.res, parts=2)
    crc_a = probe_index_crc(path)

    # simulate a writer killed before publishing snapshot B
    for i in range(2):
        with open(os.path.join(path, mfst.part_name(i) + ".tmp"),
                  "wb") as f:
            f.write(b"half-written garbage")
    assert mfst.stale_parts(path) == ["part-0000.tacz.tmp",
                                      "part-0001.tacz.tmp"]
    # old snapshot still fully valid
    assert probe_index_crc(path) == crc_a
    with MultiPartReader(path) as rd:
        for lr, rec in zip(a.res.levels, rd.read()):
            np.testing.assert_array_equal(lr.recon, rec)

    # re-run converges: new snapshot publishes, litter is gone
    write_multipart(path, b.res, parts=2)
    assert mfst.stale_parts(path) == []
    with MultiPartReader(path) as rd:
        for lr, rec in zip(b.res.levels, rd.read()):
            np.testing.assert_array_equal(lr.recon, rec)


def test_abort_leaves_no_trace(tmp_path, make_amr_snapshot):
    snap = make_amr_snapshot(densities=[0.35, 0.65], seed=5)
    path = os.path.join(str(tmp_path), "abort.taczd")
    w = ParallelTACZWriter(path, parts=2, eb=snap.eb)
    w.add_level(snap.ds.levels[0].data, snap.ds.levels[0].mask, ratio=1)
    w.abort()
    assert not os.path.exists(os.path.join(path, mfst.MANIFEST_NAME))
    assert mfst.stale_parts(path) == []
    with pytest.raises(ValueError):
        w.add_level(snap.ds.levels[0].data, snap.ds.levels[0].mask)


# --------------------------- hypothesis sweeps ------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
    settings.register_profile("multipart", max_examples=6, deadline=None)
    settings.load_profile("multipart")
except ImportError:        # pragma: no cover - environment dependent
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @given(seed=st.integers(0, 40), fine=st.floats(0.1, 0.9),
           parts=st.integers(1, 4), codec=st.sampled_from(["none", "zlib"]),
           lo=st.tuples(st.integers(0, 28), st.integers(0, 28),
                        st.integers(0, 28)),
           ext=st.tuples(st.integers(1, 32), st.integers(1, 32),
                         st.integers(1, 32)))
    def test_property_multipart_reads_bit_identical(make_amr_snapshot, seed,
                                                    fine, parts, codec,
                                                    lo, ext):
        """Random datasets × part counts 1–4 × v1-style/v2 codecs: read,
        read_roi, and a cold+warm RegionServer agree with the single
        file bit for bit."""
        dens = [fine, 1.0 - fine]
        single = make_amr_snapshot(seed=seed, densities=dens, codec=codec,
                                   name="single")
        multi = make_amr_snapshot(seed=seed, densities=dens, codec=codec,
                                  parts=parts, name="multi")
        box = tuple((int(l), int(l + e)) for l, e in zip(lo, ext))
        _assert_identical_reads(single.path, multi.path, single.res,
                                boxes=[box])
        with tacz.TACZReader(single.path) as rd, \
                RegionServer(multi.path, cache_bytes=16 << 20) as srv:
            ref = rd.read_roi(box)
            for pass_ in range(2):              # cold, then warm
                for g, r in zip(srv.get_roi(box), ref):
                    np.testing.assert_array_equal(g.data, r.data)

    @given(seed=st.integers(0, 10),
           lo=st.tuples(st.integers(0, 28), st.integers(0, 28),
                        st.integers(0, 28)),
           ext=st.tuples(st.integers(1, 32), st.integers(1, 32),
                         st.integers(1, 32)))
    @settings(max_examples=5, deadline=None)
    def test_property_router_over_multipart(make_amr_snapshot,
                                            router_fleet, seed, lo, ext):
        """A 2-shard part-aligned router over a multi-part snapshot is
        bit-identical to a single unsharded server on random boxes."""
        single_srv, router = router_fleet
        box = tuple((int(l), int(l + e)) for l, e in zip(lo, ext))
        ref = single_srv.get_regions([box])
        got = router.get_regions([box])
        for per_got, per_ref in zip(got, ref):
            for g, r in zip(per_got, per_ref):
                assert (g.level, g.ratio, g.box) == (r.level, r.ratio, r.box)
                np.testing.assert_array_equal(g.data, r.data)

    @pytest.fixture(scope="module")
    def router_fleet(make_amr_snapshot):
        single = make_amr_snapshot(densities=[0.35, 0.65], seed=5,
                                   name="single")
        multi = make_amr_snapshot(densities=[0.35, 0.65], seed=5, parts=2,
                                  name="multi")
        with MultiPartReader(multi.path) as mrd:
            m = ShardMap.from_dict(mrd.partition)
        servers, urls = {}, {}
        try:
            for sid in m.shards:
                httpd = serve(multi.path, port=0, cache_bytes=16 << 20,
                              shard_map=m, shard_id=sid)
                threading.Thread(target=httpd.serve_forever,
                                 daemon=True).start()
                servers[sid] = httpd
                urls[sid] = f"http://127.0.0.1:{httpd.server_address[1]}"
            with RegionServer(single.path) as baseline, \
                    ShardedRegionRouter(multi.path, m, urls) as router:
                yield baseline, router
        finally:
            for httpd in servers.values():
                httpd.shutdown()
                httpd.server_close()
                httpd.region_server.close()
