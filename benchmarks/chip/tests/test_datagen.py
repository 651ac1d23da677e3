"""The data generator: the configuration's ``data_seed`` fixes where the
levels lie, the bound's value range and so the compressor's programs; a
run's seed changes the stored values."""
import numpy as np

import datagen
import harness

GEN = harness.load_json(f"{harness.HERE}/configs/nyx_run1_z10.json")[
    "generator"]


def _snap(value_seed, stream=0):
    return datagen.generate(GEN, 32, 20230101, stream, value_seed=value_seed)


def test_the_same_seed_gives_the_same_data():
    a, b = _snap(2 ** 31 + 11), _snap(2 ** 31 + 11)
    for x, y in zip(a.data, b.data):
        assert np.array_equal(x, y)


def test_a_run_seed_changes_the_values_and_keeps_the_levels():
    a, b, fixed = _snap(2 ** 31 + 11), _snap(2 ** 33 + 5), _snap(None)
    for x, y, z in zip(a.mask, b.mask, fixed.mask):
        assert np.array_equal(x, y) and np.array_equal(x, z)
    assert not np.array_equal(a.data[0], b.data[0])
    assert a.geometry_range == b.geometry_range == fixed.geometry_range
    # with no run seed the bound's range is that of the stored values
    vals = np.concatenate([d[m] for d, m in zip(fixed.data, fixed.mask)])
    assert fixed.geometry_range == (float(vals.min()), float(vals.max()))


def test_the_compressor_places_the_same_bricks_for_every_seed():
    """Brick shapes and counts are what the Lorenzo programs compile for:
    a new seed in a warm checkout compiles nothing."""
    from repro.core import hybrid

    def bricks(snap):
        out = []
        for d, m in zip(snap.data, snap.mask):
            _, strategy, _, subs = hybrid.partition_level(d, m, unit=8)
            out.append((strategy, sorted((s.origin, s.bsize) for s in subs)))
        return out

    assert bricks(_snap(2 ** 31 + 11)) == bricks(_snap(2 ** 33 + 5))
