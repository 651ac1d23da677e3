"""Sub-block lookup from per-level bound arrays.

``TACZReader.intersecting_subblocks`` answers a box from two ``(N, 3)``
int64 arrays per level.  The contract it keeps is the per-sub-block
Python scan it replaced, kept here as the reference: the same
``(sub_block_index, intersection_box)`` pairs, in index order, as plain
``int`` (they become cache keys, shard keys and JSON on the router's
wire).  The planner and the sharded router built on it serve the same
tasks and bit-identical crops, and the planner times each lookup into
``tacz_planner_intersect_seconds``.
"""
import contextlib
import os
import threading

import numpy as np
import pytest

from repro import io as tacz
from repro import obs
from repro.core import amr, hybrid
from repro.io import format as fmt
from repro.obs import metrics as obsm
from repro.serving import (DecodePlanner, RegionServer, ShardMap,
                           ShardedRegionRouter, serve)

SHE_KINDS = ("opst", "akdtree", "nast")
BOX_KINDS = ("random", "edge", "one_cell", "empty", "whole")
BOXES = [((0, 8), (0, 8), (0, 8)),
         ((5, 23), (11, 30), (2, 9)),
         ((24, 64), (16, 64), (0, 64)),
         ((0, 64), (0, 64), (0, 64)),
         ((31, 33), (31, 33), (31, 33)),
         ((70, 80), (0, 4), (0, 4))]          # beyond the extent


def _scan(e: fmt.LevelEntry, lbox):
    """The per-sub-block scan the bound arrays replaced."""
    out = []
    for i, sb in enumerate(e.subblocks):
        isect = tuple(
            (max(lo, o), min(hi, o + s))
            for (lo, hi), o, s in zip(lbox, sb.origin, sb.size))
        if all(hi > lo for lo, hi in isect):
            out.append((i, isect))
    return out


@pytest.fixture(scope="module")
def z10(make_amr_snapshot):
    return make_amr_snapshot(preset="run1_z10", name="z10")


@pytest.fixture(scope="module")
def nast_path(tmp_path_factory):
    ds = amr.synthetic_amr((32, 32, 32), densities=[0.35, 0.65],
                           refine_block=4, seed=5)
    lvl = ds.levels[1]
    lr = hybrid.compress_level(lvl.data, lvl.mask, eb=1e-2, unit=4,
                               strategy="nast")
    path = os.path.join(str(tmp_path_factory.mktemp("nast")), "n.tacz")
    with tacz.TACZWriter(path) as w:
        w.add_compressed(lr)
    return path


@pytest.fixture(scope="module")
def levels(z10, nast_path):
    """``{strategy name: (reader, level index)}`` for the three SHE
    strategies; the readers stay open for the module."""
    out = {}
    with contextlib.ExitStack() as stack:
        for path in (z10.path, nast_path):
            rd = stack.enter_context(tacz.TACZReader(path))
            for li, e in enumerate(rd.levels):
                name = fmt.STRATEGY_NAMES[e.strategy]
                if name in SHE_KINDS:
                    out.setdefault(name, (rd, li))
        assert set(out) == set(SHE_KINDS)
        yield out


def _boxes(kind: str, shape, rng):
    sx, sy, sz = shape
    if kind == "random":
        out = []
        for _ in range(40):
            lo = [int(rng.integers(-4, s)) for s in shape]
            out.append(tuple((a, a + int(rng.integers(1, max(s // 2, 2))))
                             for a, s in zip(lo, shape)))
        return out
    if kind == "edge":
        return [((sx - 4, sx), (0, 4), (sz - 4, sz)),
                ((sx - 1, sx + 6), (sy - 3, sy + 3), (-3, 2)),
                ((0, sx), (sy - 1, sy), (0, sz)),
                ((-8, 0), (0, sy), (0, sz)),            # touches lo only
                ((sx, sx + 8), (0, sy), (0, sz))]       # touches hi only
    if kind == "one_cell":
        return [tuple((a, a + 1) for a in c)
                for c in [(0, 0, 0), (sx - 1, sy - 1, sz - 1),
                          (sx // 2, sy // 3, sz // 5), (7, 8, 9)]]
    if kind == "empty":
        return [((5, 5), (0, sy), (0, sz)),
                ((0, sx), (9, 3), (0, sz)),
                ((0, 0), (0, 0), (0, 0)),
                ((sx, sx), (sy, sy), (sz, sz))]
    assert kind == "whole"
    return [((0, sx), (0, sy), (0, sz)),
            ((-10, sx + 10), (-10, sy + 10), (-10, sz + 10))]


@pytest.mark.parametrize("box_kind", BOX_KINDS)
@pytest.mark.parametrize("strategy", SHE_KINDS)
def test_lookup_equals_the_scan(levels, strategy, box_kind):
    rd, li = levels[strategy]
    e = rd.levels[li]
    assert len(e.subblocks) > 1
    rng = np.random.default_rng(20230101 + li)
    for lbox in _boxes(box_kind, e.shape, rng):
        got = rd.intersecting_subblocks(li, lbox)
        assert got == _scan(e, lbox), lbox
        idx = [i for i, _ in got]
        assert idx == sorted(set(idx))
        for i, isect in got:
            assert type(i) is int
            assert all(type(v) is int for r in isect for v in r)
        if box_kind == "empty":
            assert got == []
        if box_kind == "whole":
            assert idx == list(range(len(e.subblocks)))


def test_bound_arrays_are_read_only(levels):
    rd, li = levels["opst"]
    lo, hi = rd._bounds[li]
    n = len(rd.levels[li].subblocks)
    assert lo.shape == hi.shape == (n, 3)
    assert lo.dtype == hi.dtype == np.int64
    with pytest.raises(ValueError):
        lo[0, 0] = 1
    with pytest.raises(ValueError):
        hi[0, 0] = 1


# ------------------------------ planner -------------------------------------


def test_owned_planner_tasks_equal_the_filtered_scan(z10):
    smap = ShardMap(["s0", "s1"], seed=7)
    with tacz.TACZReader(z10.path) as rd:
        keys = rd.subblock_keys()
        owned = {k for k in keys if smap.owner(k) == "s0"}
        assert 0 < len(owned) < len(keys)
        lis = list(range(rd.n_levels))
        queries = [(li, box) for box in BOXES for li in lis]
        plans = DecodePlanner(rd, owned=owned).plan(queries)
        assert len(plans) == len(queries)
        for (li, box), p in zip(queries, plans):
            lbox = rd.level_box(li, box)
            assert (p.level, p.lbox, p.whole_level) == (li, lbox, False)
            want = () if any(hi <= lo for lo, hi in lbox) else tuple(
                t for t in _scan(rd.levels[li], lbox) if (li, t[0]) in owned)
            assert p.tasks == want
            assert all(smap.owner(k) == "s0" for k in p.keys())


@contextlib.contextmanager
def _fleet(path, smap):
    servers, urls = [], {}
    try:
        for sid in smap.shards:
            httpd = serve(path, port=0, shard_map=smap, shard_id=sid)
            threading.Thread(target=httpd.serve_forever,
                             daemon=True).start()
            servers.append(httpd)
            urls[sid] = f"http://127.0.0.1:{httpd.server_address[1]}"
        yield urls
    finally:
        for httpd in servers:
            httpd.shutdown()
            httpd.server_close()
            httpd.region_server.close()


def test_two_shard_router_crops_are_bit_identical(z10):
    smap = ShardMap(["s0", "s1"], seed=7)
    with _fleet(z10.path, smap) as urls, \
            ShardedRegionRouter(z10.path, smap, urls) as router, \
            tacz.TACZReader(z10.path) as rd:
        got = router.get_regions(BOXES)
        assert router.stats()["local_fallbacks"] == 0
        lboxes = [[rd.level_box(li, box) for li in range(rd.n_levels)]
                  for box in BOXES]
    for want, per_box in zip(lboxes, got):
        assert [roi.box for roi in per_box] == want
        for roi, lr in zip(per_box, z10.res.levels):
            crop = lr.recon[tuple(slice(lo, hi) for lo, hi in roi.box)]
            np.testing.assert_array_equal(roi.data, crop)


# ------------------------------ counters ------------------------------------


@pytest.fixture
def metrics_enabled():
    was = obs.is_enabled()
    obs.set_enabled(True)
    yield
    obs.set_enabled(was)


def test_one_observation_per_she_level_box(z10, metrics_enabled):
    fam = obsm.PLANNER_INTERSECT_SECONDS.labels()
    with RegionServer(z10.path) as server:
        plans = server._planner.plan(
            [(li, box) for box in BOXES
             for li in range(server._reader.n_levels)])
        n0, hits0 = fam.count, obsm.PLANNER_INTERSECT_SUBBLOCKS.value
        server.get_regions(BOXES)
        n1, hits1 = fam.count, obsm.PLANNER_INTERSECT_SUBBLOCKS.value
    lookups = [p for p in plans if not p.whole_level
               and all(hi > lo for lo, hi in p.lbox)]
    assert lookups and not any(p.whole_level for p in plans)
    assert n1 - n0 == len(lookups)
    assert hits1 - hits0 == sum(len(p.tasks) for p in plans) > 0
