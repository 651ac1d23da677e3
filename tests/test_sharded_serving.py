"""Sharded region serving (ISSUE 4): shard map + scatter-gather router.

The contract:

  * :class:`ShardMap` is a pure function of its serialized config — the
    router ("client side") and the shard servers ("server side") compute
    identical owners from the same JSON, independent of shard-list order;
  * resizing moves the minimum: adding a shard only moves keys *to* it,
    removing one only moves the keys it owned;
  * ``ShardedRegionRouter.get_regions`` is bit-identical to a single
    unsharded ``RegionServer.get_regions`` across shard counts 1–4,
    including with one shard unreachable (replica retry and direct local
    ``TACZReader`` fallback);
  * shard-filtered servers decode/cache only owned sub-blocks;
  * a shard serving a stale snapshot generation is detected via the
    footer ``index_crc`` and routed around, never mixed in.
"""
import contextlib
import os
import socket
import threading

import numpy as np
import pytest

from repro import io as tacz
from repro.core import amr, hybrid
from repro.io.reader import WHOLE_LEVEL
from repro.serving import RegionServer, ShardMap, ShardedRegionRouter, serve

BOXES = [((0, 8), (0, 8), (0, 8)),
         ((5, 23), (11, 30), (2, 9)),
         ((24, 32), (16, 32), (0, 32)),
         ((0, 32), (0, 32), (0, 32)),
         ((14, 18), (14, 18), (14, 18)),
         ((40, 50), (0, 4), (0, 4))]          # beyond the extent


@pytest.fixture(scope="module")
def snapshot(make_amr_snapshot):
    snap = make_amr_snapshot(densities=[0.35, 0.65], seed=5, name="s")
    return snap.path, snap.res


@pytest.fixture(scope="module")
def file_keys(snapshot):
    path, _ = snapshot
    with tacz.TACZReader(path) as rd:
        return rd.subblock_keys()


@contextlib.contextmanager
def shard_fleet(path, shard_map, *, cache_bytes=16 << 20, auto_reload=True):
    """Launch one HTTP endpoint per shard; yields {shard_id: url} plus the
    raw servers (for fault injection)."""
    servers, urls = {}, {}
    try:
        for sid in shard_map.shards:
            httpd = serve(path, port=0, cache_bytes=cache_bytes,
                          auto_reload=auto_reload, shard_map=shard_map,
                          shard_id=sid)
            threading.Thread(target=httpd.serve_forever,
                             daemon=True).start()
            servers[sid] = httpd
            urls[sid] = f"http://127.0.0.1:{httpd.server_address[1]}"
        yield urls, servers
    finally:
        for httpd in servers.values():
            httpd.shutdown()
            httpd.server_close()
            httpd.region_server.close()


def dead_url() -> str:
    """An endpoint URL that refuses connections immediately."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    return f"http://127.0.0.1:{port}"


def _assert_same_regions(got, ref):
    assert len(got) == len(ref)
    for per_got, per_ref in zip(got, ref):
        assert len(per_got) == len(per_ref)
        for g, r in zip(per_got, per_ref):
            assert (g.level, g.ratio, g.box) == (r.level, r.ratio, r.box)
            np.testing.assert_array_equal(g.data, r.data)


# ------------------------------- shard map ----------------------------------


def test_shard_map_client_server_agreement(file_keys):
    """The router and a shard server built from the same serialized config
    must compute identical owners — the whole scheme rests on this."""
    server_side = ShardMap(["s0", "s1", "s2"], seed=11)
    client_side = ShardMap.from_json(server_side.to_json())
    assert client_side == server_side
    for key in file_keys:
        assert client_side.owner(key) == server_side.owner(key)
    # dict round-trip too (what a deployment config file would hold)
    assert ShardMap.from_dict(server_side.to_dict()) == server_side


def test_shard_map_order_and_process_independence(file_keys):
    a = ShardMap(["x", "y", "z"], seed=3)
    b = ShardMap(["z", "x", "y"], seed=3)
    assert a == b
    assert all(a.owner(k) == b.owner(k) for k in file_keys)
    # seed reshuffles; different seeds give (almost surely) different maps
    c = ShardMap(["x", "y", "z"], seed=4)
    keys = [(li, sbi) for li in range(4) for sbi in range(64)]
    assert any(a.owner(k) != c.owner(k) for k in keys)


def test_shard_map_covers_whole_level_keys():
    m = ShardMap(["a", "b"])
    assert m.owner((2, WHOLE_LEVEL)) in m.shards


def test_shard_map_minimal_movement_on_add():
    m = ShardMap([f"s{i}" for i in range(3)], seed=0)
    keys = [(li, sbi) for li in range(4) for sbi in range(128)]
    grown = m.with_shard("s3")
    moved = [k for k in keys if m.owner(k) != grown.owner(k)]
    # rendezvous: every moved key lands on the NEW shard only
    assert all(grown.owner(k) == "s3" for k in moved)
    # and roughly 1/(N+1) of the keys move (generous bounds, 512 keys)
    assert 0.10 * len(keys) < len(moved) < 0.45 * len(keys)


def test_shard_map_minimal_movement_on_remove():
    m = ShardMap([f"s{i}" for i in range(4)], seed=0)
    keys = [(li, sbi) for li in range(4) for sbi in range(128)]
    shrunk = m.without_shard("s1")
    for k in keys:
        if m.owner(k) != "s1":          # survivors keep every key
            assert shrunk.owner(k) == m.owner(k)
        else:
            assert shrunk.owner(k) in shrunk.shards


def test_shard_map_partition_is_total_and_disjoint(file_keys):
    m = ShardMap(["a", "b", "c"], seed=1)
    part = m.partition(file_keys)
    flat = [k for keys in part.values() for k in keys]
    assert sorted(flat) == sorted(file_keys)
    assert set(part) <= set(m.shards)


def test_shard_map_validation():
    with pytest.raises(ValueError):
        ShardMap([])
    with pytest.raises(ValueError):
        ShardMap(["a", "a"])
    with pytest.raises(ValueError):
        ShardMap(["a", ""])
    with pytest.raises(ValueError):
        ShardMap(["a"]).with_shard("a")
    with pytest.raises(ValueError):
        ShardMap(["a", "b"]).without_shard("nope")
    with pytest.raises(ValueError, match="algorithm"):
        ShardMap.from_dict({"algorithm": "ring-md5", "shards": ["a"]})


# --------------------------- router vs single server ------------------------


@pytest.mark.parametrize("n_shards", [1, 2, 3, 4])
def test_router_bit_identical_to_single_server(snapshot, n_shards):
    path, _ = snapshot
    m = ShardMap([f"s{i}" for i in range(n_shards)], seed=2)
    with RegionServer(path) as single, \
            shard_fleet(path, m) as (urls, _servers), \
            ShardedRegionRouter(path, m, urls) as router:
        ref = single.get_regions(BOXES)
        _assert_same_regions(router.get_regions(BOXES), ref)
        # repeat batch (shard caches warm now) — still identical
        _assert_same_regions(router.get_regions(BOXES), ref)
        assert router.counters["local_fallbacks"] == 0
        # level-filtered and single-region forms route the same way
        np.testing.assert_array_equal(
            router.get_region(1, BOXES[1]).data,
            single.get_region(1, BOXES[1]).data)


@pytest.mark.parametrize("n_shards", [2, 3, 4])
def test_router_with_one_shard_unreachable(snapshot, n_shards):
    """Killing one shard must cost throughput only: the router decodes
    that shard's rectangles from the local file, bit-identically."""
    path, _ = snapshot
    m = ShardMap([f"s{i}" for i in range(n_shards)], seed=2)
    with RegionServer(path) as single, shard_fleet(path, m) as (urls, _):
        down = m.shards[0]
        urls = dict(urls, **{down: dead_url()})
        with ShardedRegionRouter(path, m, urls) as router:
            ref = single.get_regions(BOXES)
            _assert_same_regions(router.get_regions(BOXES), ref)
            assert router.counters["local_fallbacks"] > 0
            assert router.counters["endpoint_failures"] > 0


def test_router_replica_retry_avoids_fallback(snapshot):
    """A dead primary with a live replica must be absorbed by the retry,
    never reaching the local-fallback path."""
    path, _ = snapshot
    m = ShardMap(["s0", "s1"], seed=2)
    with RegionServer(path) as single, shard_fleet(path, m) as (urls, _):
        routed = {m.shards[0]: [dead_url(), urls[m.shards[0]]],
                  m.shards[1]: urls[m.shards[1]]}
        with ShardedRegionRouter(path, m, routed) as router:
            _assert_same_regions(router.get_regions(BOXES),
                                 single.get_regions(BOXES))
            assert router.counters["endpoint_failures"] > 0
            assert router.counters["local_fallbacks"] == 0


def test_router_load_balances_across_replicas(snapshot):
    """With load_balance=True, a shard's read traffic must spread across
    its healthy replica endpoints (both see work) — and the reassembled
    bytes must be unchanged."""
    path, _ = snapshot
    m = ShardMap(["s0", "s1"], seed=2)
    with RegionServer(path) as single, shard_fleet(path, m) as (urls, _):
        # a second full replica endpoint for shard s0
        with shard_fleet(path, ShardMap(["s0"], seed=2)) as (r_urls,
                                                            r_servers):
            routed = {"s0": [urls["s0"], r_urls["s0"]], "s1": urls["s1"]}
            with ShardedRegionRouter(path, m, routed,
                                     load_balance=True) as router:
                ref = single.get_regions(BOXES)
                for _ in range(4):             # several batches → rotation
                    _assert_same_regions(router.get_regions(BOXES), ref)
                assert router.counters["local_fallbacks"] == 0
                assert router.counters["endpoint_failures"] == 0
                assert router.stats()["unhealthy_endpoints"] == []
            replica = r_servers["s0"].region_server
            s = replica.cache.stats()
            assert s["hits"] + s["misses"] > 0     # the replica saw reads


def test_router_load_balance_demotes_dead_endpoint(snapshot):
    """A dead replica in the rotation is demoted after its first failure:
    batches keep succeeding off the healthy endpoint, bytes unchanged."""
    path, _ = snapshot
    m = ShardMap(["s0", "s1"], seed=2)
    with RegionServer(path) as single, shard_fleet(path, m) as (urls, _):
        routed = {"s0": [dead_url(), urls["s0"]], "s1": urls["s1"]}
        with ShardedRegionRouter(path, m, routed,
                                 load_balance=True) as router:
            ref = single.get_regions(BOXES)
            for _ in range(3):
                _assert_same_regions(router.get_regions(BOXES), ref)
            assert router.counters["local_fallbacks"] == 0
            assert router.counters["endpoint_failures"] > 0
            assert router.stats()["unhealthy_endpoints"] == \
                [routed["s0"][0]]


def test_router_missing_endpoint_uses_local_fallback(snapshot):
    path, _ = snapshot
    m = ShardMap(["s0", "s1"], seed=2)
    with RegionServer(path) as single, shard_fleet(path, m) as (urls, _):
        partial = {m.shards[0]: urls[m.shards[0]]}   # s1 not deployed yet
        with ShardedRegionRouter(path, m, partial) as router:
            _assert_same_regions(router.get_regions(BOXES),
                                 single.get_regions(BOXES))
            assert router.counters["local_fallbacks"] > 0


def test_router_without_local_fallback_raises(snapshot):
    path, _ = snapshot
    m = ShardMap(["s0", "s1"], seed=2)
    with shard_fleet(path, m) as (urls, _):
        bad = dict(urls, **{m.shards[0]: dead_url()})
        with ShardedRegionRouter(path, m, bad,
                                 local_fallback=False) as router:
            with pytest.raises(RuntimeError, match="unreachable"):
                router.get_regions([((0, 32), (0, 32), (0, 32))])


def test_router_rejects_bad_levels(snapshot):
    path, _ = snapshot
    m = ShardMap(["s0"], seed=0)
    with shard_fleet(path, m) as (urls, _), \
            ShardedRegionRouter(path, m, urls) as router:
        with pytest.raises(ValueError, match="out of range"):
            router.get_regions([BOXES[0]], levels=[99])


# ------------------------------ shard filter --------------------------------


def test_shard_servers_cache_only_owned_keys(snapshot):
    path, _ = snapshot
    m = ShardMap(["s0", "s1", "s2"], seed=9)
    with tacz.TACZReader(path) as rd:
        owned = {sid: {k for k in rd.subblock_keys() if m.owner(k) == sid}
                 for sid in m.shards}
    with shard_fleet(path, m) as (urls, servers), \
            ShardedRegionRouter(path, m, urls) as router:
        router.get_regions(BOXES)
        total = 0
        for sid, httpd in servers.items():
            rs = httpd.region_server
            for key in list(rs.cache._od):
                assert (key[1], key[2]) in owned[sid], \
                    f"shard {sid} cached foreign sub-block {key}"
            total += len(rs.cache._od)
        assert total > 0                      # the fleet did cache work
        # disjointness: every decoded key sits in exactly one shard cache
        all_cached = [(key[1], key[2]) for httpd in servers.values()
                      for key in httpd.region_server.cache._od]
        assert len(all_cached) == len(set(all_cached))


def test_shard_filtered_server_zeros_foreign_cells(snapshot):
    """A lone shard server queried directly serves zeros where it does not
    own the sub-block — the router's overlay relies on exactly that."""
    path, _ = snapshot
    m = ShardMap(["s0", "s1"], seed=9)
    box = ((0, 32), (0, 32), (0, 32))
    with RegionServer(path) as full, \
            RegionServer(path, shard_map=m, shard_id="s0") as s0, \
            RegionServer(path, shard_map=m, shard_id="s1") as s1:
        ref = full.get_roi(box)
        a, b = s0.get_roi(box), s1.get_roi(box)
        for r, ga, gb in zip(ref, a, b):
            # each cell comes from exactly one owner; the other is zero,
            # so overlaying the two shard crops rebuilds the full crop
            overlay = np.where(ga.data != 0, ga.data, gb.data)
            np.testing.assert_array_equal(overlay, r.data)
    with pytest.raises(ValueError, match="go together"):
        RegionServer(path, shard_map=m)


def test_shard_meta_reports_shard_info(snapshot):
    from repro.serving import RegionClient
    path, _ = snapshot
    m = ShardMap(["s0", "s1"], seed=4)
    with shard_fleet(path, m) as (urls, _):
        meta = RegionClient(urls["s0"]).meta()
        assert meta["shard"]["shard_id"] == "s0"
        assert meta["shard"]["n_shards"] == 2
        assert ShardMap.from_dict(meta["shard"]["shard_map"]) == m


# ------------------------------- hot swap -----------------------------------


def test_hot_swap_propagates_through_router(tmp_path):
    ds_a = amr.synthetic_amr((16, 16, 16), densities=[1.0], refine_block=4,
                             seed=3)
    ds_b = amr.synthetic_amr((16, 16, 16), densities=[1.0], refine_block=4,
                             seed=4)
    res_a = hybrid.compress_amr(ds_a, eb=1e-2)
    res_b = hybrid.compress_amr(ds_b, eb=1e-2)
    path = os.path.join(str(tmp_path), "hot.tacz")
    tacz.write(path, res_a)
    box = ((0, 16), (0, 16), (0, 16))
    m = ShardMap(["s0", "s1"], seed=0)
    with shard_fleet(path, m) as (urls, _), \
            ShardedRegionRouter(path, m, urls) as router:
        np.testing.assert_array_equal(
            router.get_roi(box)[0].data, res_a.levels[0].recon)
        old_crc = router.snapshot_crc
        tacz.write(path, res_b)               # atomic republish
        np.testing.assert_array_equal(        # next batch serves the new one
            router.get_roi(box)[0].data, res_b.levels[0].recon)
        assert router.snapshot_crc != old_crc
        assert router.counters["local_fallbacks"] == 0


def test_stale_shard_generation_is_routed_around(tmp_path):
    """A shard that has not adopted a republish yet (auto_reload off here,
    file-distribution lag in real deployments) answers with the old index
    CRC — the router must treat it as failed, not mix generations."""
    ds_a = amr.synthetic_amr((16, 16, 16), densities=[1.0], refine_block=4,
                             seed=3)
    ds_b = amr.synthetic_amr((16, 16, 16), densities=[1.0], refine_block=4,
                             seed=4)
    res_a = hybrid.compress_amr(ds_a, eb=1e-2)
    res_b = hybrid.compress_amr(ds_b, eb=1e-2)
    path = os.path.join(str(tmp_path), "lag.tacz")
    tacz.write(path, res_a)
    box = ((0, 16), (0, 16), (0, 16))
    m = ShardMap(["s0"], seed=0)
    with shard_fleet(path, m, auto_reload=False) as (urls, _), \
            ShardedRegionRouter(path, m, urls) as router:
        router.get_roi(box)                   # both sides on snapshot A
        tacz.write(path, res_b)
        roi = router.get_roi(box)[0]          # router reloads; shard lags
        np.testing.assert_array_equal(roi.data, res_b.levels[0].recon)
        assert router.counters["local_fallbacks"] > 0


# --------------------------- hypothesis sweeps ------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
    settings.register_profile("sharded", max_examples=10, deadline=None)
    settings.load_profile("sharded")
except ImportError:        # pragma: no cover - environment dependent
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @pytest.fixture(scope="module")
    def fleet3(snapshot):
        path, _ = snapshot
        m = ShardMap(["s0", "s1", "s2"], seed=6)
        with shard_fleet(path, m) as (urls, servers):
            # s2 is permanently down: every example also exercises the
            # local-fallback path alongside the two live shards
            urls = dict(urls, **{"s2": dead_url()})
            with RegionServer(path) as single, \
                    ShardedRegionRouter(path, m, urls) as router:
                yield single, router

    @given(lo=st.tuples(st.integers(0, 28), st.integers(0, 28),
                        st.integers(0, 28)),
           ext=st.tuples(st.integers(1, 32), st.integers(1, 32),
                         st.integers(1, 32)))
    def test_property_random_boxes_sharded(fleet3, lo, ext):
        single, router = fleet3
        box = tuple((int(l), int(l + e)) for l, e in zip(lo, ext))
        _assert_same_regions(router.get_regions([box]),
                             single.get_regions([box]))

    @given(seed=st.integers(0, 2 ** 31), n=st.integers(1, 9))
    def test_property_rendezvous_add_only_moves_to_new(seed, n):
        m = ShardMap([f"s{i}" for i in range(n)], seed=seed)
        grown = m.with_shard("new")
        keys = [(li, sbi) for li in range(3) for sbi in range(32)]
        for k in keys:
            before, after = m.owner(k), grown.owner(k)
            assert after == before or after == "new"


def test_shard_servers_decode_on_their_own_device(snapshot):
    """A shard server with a device engine is pinned to local device
    ``i mod n`` (i = its place in the sorted shard list) and serves the
    same crops as an unsharded host-engine server; a host engine is left
    as named."""
    import jax

    from repro.core import entropy
    path, _ = snapshot
    devs = jax.local_devices()
    smap = ShardMap(["b", "a", "c"])
    box = BOXES[3]
    with RegionServer(path, entropy_engine="numpy") as ref:
        want = ref.get_roi(box)
    got = [np.zeros_like(r.data) for r in want]
    for i, sid in enumerate(smap.shards):
        with RegionServer(path, shard_map=smap, shard_id=sid,
                          entropy_engine="pallas") as srv:
            assert isinstance(srv.entropy_engine, entropy.PallasEngine)
            assert srv.entropy_engine.device == devs[i % len(devs)]
            for acc, roi in zip(got, srv.get_roi(box)):
                acc += roi.data      # foreign cells come back as zeros
    for g, r in zip(got, want):
        np.testing.assert_array_equal(g, r.data)
    with RegionServer(path, shard_map=smap, shard_id="a",
                      entropy_engine="batched") as srv:
        assert srv.entropy_engine == "batched"
