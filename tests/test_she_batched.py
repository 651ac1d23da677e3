"""Batched SHE pipeline vs the sequential reference oracle.

The contract (ISSUE 1 acceptance): for any brick set, ``batched=True`` must
produce **bit-identical** sizes, code streams, and reconstructions to the
sequential per-brick path, and the error bound must hold elementwise.
Deterministic parametrized cases run everywhere; hypothesis sweeps run when
the optional dep is installed (CI always has it).
"""
import numpy as np
import pytest

from repro.core import amr, she, sz
from repro.core.akdtree import akdtree_partition
from repro.core.blocks import extract_subblock, make_block_grid
from repro.core.opst import opst_partition
from repro.core.sz import compress_lor_reg, compress_lor_reg_batched


def _bound(eb, x):
    return eb + np.abs(x).max() * 2.0 ** -22


def _assert_she_identical(a: she.SHEResult, b: she.SHEResult):
    assert a.payload_bits == b.payload_bits
    assert a.codebook_bits == b.codebook_bits
    assert a.meta_bits == b.meta_bits
    assert a.total_bits == b.total_bits
    for ra, rb in zip(a.results, b.results):
        np.testing.assert_array_equal(ra.codes, rb.codes)
        np.testing.assert_array_equal(ra.recon, rb.recon)
        assert ra.payload_bits == rb.payload_bits
        assert ra.meta_bits == rb.meta_bits
        assert ra.extras.get("branch") == rb.extras.get("branch")


def _random_bricks(seed, n, shapes, scale=10.0):
    rng = np.random.default_rng(seed)
    return [(rng.standard_normal(shapes[i % len(shapes)]) * scale)
            .astype(np.float32) for i in range(n)]


# ------------------------- preset-dataset identity --------------------------


@pytest.mark.parametrize("partition", [akdtree_partition, opst_partition])
@pytest.mark.parametrize("eb", [0.05, 1e-3])
def test_batched_matches_sequential_on_amr(partition, eb):
    ds = amr.synthetic_amr((48, 48, 48), densities=[0.23, 0.77],
                           refine_block=4, seed=10)
    lvl = ds.levels[0]
    grid = make_block_grid(lvl.data, lvl.mask, unit=4)
    bricks = [extract_subblock(grid, sb) for sb in partition(grid)]
    assert len(bricks) > 100   # the many-small-blocks regime SHE targets
    seq = she.she_encode(bricks, eb, shared=True, batched=False)
    bat = she.she_encode(bricks, eb, shared=True, batched=True)
    _assert_she_identical(seq, bat)
    for brk, r in zip(bricks, bat.results):
        assert np.abs(r.recon - brk).max() <= _bound(eb, brk)


def test_batched_mixed_shapes_and_singletons():
    """Shape groups of size 1, thin bricks, and cubes all agree."""
    bricks = _random_bricks(0, 13, [(4, 4, 4), (8, 4, 4), (4, 12, 8),
                                    (1, 4, 4), (6, 6, 6)])
    for eb in (0.5, 1e-2):
        seq = she.she_encode(bricks, eb, shared=True, batched=False)
        bat = she.she_encode(bricks, eb, shared=True, batched=True)
        _assert_she_identical(seq, bat)


def test_batched_empty_and_single_brick():
    assert she.she_encode([], 0.1, batched=True).total_bits == \
        she.she_encode([], 0.1, batched=False).total_bits
    bricks = _random_bricks(1, 1, [(6, 6, 6)])
    _assert_she_identical(she.she_encode(bricks, 0.1, batched=False),
                          she.she_encode(bricks, 0.1, batched=True))


def test_batched_4d_bricks_fall_back_to_oracle():
    rng = np.random.default_rng(2)
    bricks = [rng.standard_normal((2, 4, 4, 4)).astype(np.float32),
              rng.standard_normal((4, 4, 4)).astype(np.float32)]
    _assert_she_identical(she.she_encode(bricks, 0.05, batched=False),
                          she.she_encode(bricks, 0.05, batched=True))


def test_pallas_histogram_engine_matches_numpy():
    bricks = _random_bricks(3, 8, [(6, 6, 6)], scale=2.0)
    a = she.she_encode(bricks, 0.05, batched=True, hist_engine="numpy")
    b = she.she_encode(bricks, 0.05, batched=True, hist_engine="pallas")
    _assert_she_identical(a, b)


def test_aggregate_histogram_equals_unique():
    rng = np.random.default_rng(4)
    codes = rng.integers(-300, 300, size=5000)
    s_np, f_np = she.aggregate_histogram(codes, engine="numpy")
    s_u, f_u = np.unique(codes, return_counts=True)
    np.testing.assert_array_equal(s_np, s_u)
    np.testing.assert_array_equal(f_np, f_u)
    s_pl, f_pl = she.aggregate_histogram(codes, engine="pallas")
    np.testing.assert_array_equal(s_pl, s_u)
    np.testing.assert_array_equal(f_pl, f_u)
    # outlier-widened spans must fall back off the one-hot kernel instead
    # of materializing a (chunk, span) tile
    wide = np.concatenate([codes, [10_000_000]])
    s_w, f_w = she.aggregate_histogram(wide, engine="pallas")
    s_wu, f_wu = np.unique(wide, return_counts=True)
    np.testing.assert_array_equal(s_w, s_wu)
    np.testing.assert_array_equal(f_w, f_wu)


# -------------------- batched Lor/Reg compressor oracle ---------------------


@pytest.mark.parametrize("shape", [(4, 4, 4), (8, 8, 8), (13, 7, 9),
                                   (12, 12, 12), (2, 2, 2)])
@pytest.mark.parametrize("eb", [0.5, 1e-2])
def test_lor_reg_batched_is_bit_identical(shape, eb):
    rng = np.random.default_rng(hash((shape, eb)) % 2**31)
    # mix smooth ramps (regression-friendly) and noise (Lorenzo-friendly)
    i, j, k = np.mgrid[0:shape[0], 0:shape[1], 0:shape[2]].astype(np.float32)
    stack = np.stack(
        [3.0 * i + 2.0 * j - k + rng.normal(scale=3 * eb, size=shape)
         .astype(np.float32) for _ in range(3)]
        + [(rng.standard_normal(shape) * 10).astype(np.float32)
           for _ in range(3)])
    batched = compress_lor_reg_batched(stack, eb, block=4)
    for idx in range(stack.shape[0]):
        ref = compress_lor_reg(stack[idx], eb, block=4, count_entropy=False)
        np.testing.assert_array_equal(batched[idx].codes, ref.codes)
        np.testing.assert_array_equal(batched[idx].recon, ref.recon)
        assert batched[idx].meta_bits == ref.meta_bits
        assert batched[idx].extras["branch"] == ref.extras["branch"]
        assert np.abs(batched[idx].recon - stack[idx]).max() \
            <= _bound(eb, stack[idx])


# ------------------- Pallas-kernel Lorenzo branch (ROADMAP) -----------------
#
# On a TPU backend `engine="auto"` routes the batched Lorenzo branch through
# the Pallas kernel; on CPU the kernel runs in interpret mode, so forcing
# `engine="pallas"` here exercises the exact routing.  Inputs are chosen on
# the quantization lattice (x = k·2eb) so the kernel's float32 arithmetic
# agrees exactly with the float64 numpy oracle.


def _lattice_stack(seed, n, shape, eb):
    rng = np.random.default_rng(seed)
    return (rng.integers(-50, 50, size=(n,) + shape) * (2.0 * eb)
            ).astype(np.float32)


@pytest.mark.parametrize("shape", [(8, 8, 8), (4, 4, 4), (16, 16, 16),
                                   (13, 7, 9)])
def test_pallas_engine_matches_numpy_oracle(shape):
    """tile == brick, so any VMEM-sized brick shape routes through the
    kernel — including non-power-of-two ones like (13, 7, 9)."""
    eb = 0.25
    stack = _lattice_stack(11, 5, shape, eb)
    ref = compress_lor_reg_batched(stack, eb, block=4, engine="numpy")
    pal = compress_lor_reg_batched(stack, eb, block=4, engine="pallas")
    for r, p in zip(ref, pal):
        np.testing.assert_array_equal(r.codes, p.codes)
        np.testing.assert_array_equal(r.recon, p.recon)
        assert r.extras["branch"] == p.extras["branch"]
        assert r.meta_bits == p.meta_bits


def test_pallas_engine_falls_back_on_wide_dynamic_range():
    """|x|/(2eb) beyond float32-exact integers would break the error bound
    in the kernel's float32/int32 arithmetic — must fall back to numpy."""
    eb = 1e-4
    rng = np.random.default_rng(14)
    stack = (rng.standard_normal((2, 8, 8, 8)) * 1e4).astype(np.float32)
    assert float(np.abs(stack).max()) / (2 * eb) >= 2 ** 23
    ref = compress_lor_reg_batched(stack, eb, engine="numpy")
    pal = compress_lor_reg_batched(stack, eb, engine="pallas")
    for r, p in zip(ref, pal):
        np.testing.assert_array_equal(r.codes, p.codes)
        np.testing.assert_array_equal(r.recon, p.recon)


def test_pallas_engine_falls_back_on_oversize_brick():
    """A brick bigger than the kernel's VMEM tile budget must fall back to
    the numpy path and still match the oracle exactly."""
    eb = 0.25
    stack = _lattice_stack(12, 1, (16, 128, 128), eb)  # > 8·128·128 cells
    ref = compress_lor_reg_batched(stack, eb, engine="numpy")
    pal = compress_lor_reg_batched(stack, eb, engine="pallas")
    for r, p in zip(ref, pal):
        np.testing.assert_array_equal(r.codes, p.codes)
        np.testing.assert_array_equal(r.recon, p.recon)


def test_engine_auto_uses_numpy_off_tpu():
    """No TPU attached in CI → auto must be the bit-exact host path."""
    import jax

    assert jax.default_backend() != "tpu"
    stack = (np.random.default_rng(13).standard_normal((3, 6, 6, 6)) * 10
             ).astype(np.float32)
    auto = compress_lor_reg_batched(stack, 1e-2, engine="auto")
    ref = compress_lor_reg_batched(stack, 1e-2, engine="numpy")
    for a, r in zip(auto, ref):
        np.testing.assert_array_equal(a.codes, r.codes)
        np.testing.assert_array_equal(a.recon, r.recon)


def test_engine_rejects_unknown():
    with pytest.raises(ValueError, match="engine"):
        compress_lor_reg_batched(np.zeros((1, 4, 4, 4), np.float32), 0.1,
                                 engine="cuda")


# --------------------------- hypothesis sweeps ------------------------------
#
# Guarded (not importorskip'd at module level) so the deterministic cases
# above still run in environments without the optional hypothesis dep.

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
    settings.register_profile("ci", max_examples=25, deadline=None)
    settings.load_profile("ci")
except ImportError:        # pragma: no cover - environment dependent
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @given(seed=st.integers(0, 10_000),
           eb=st.floats(1e-3, 1.0),
           n=st.integers(1, 24),
           shapes=st.sampled_from([[(4, 4, 4)], [(8, 8, 8), (4, 4, 4)],
                                   [(5, 9, 4), (4, 4, 8), (6, 6, 6)]]))
    def test_property_batched_she_identical(seed, eb, n, shapes):
        bricks = _random_bricks(seed, n, shapes)
        seq = she.she_encode(bricks, eb, shared=True, batched=False)
        bat = she.she_encode(bricks, eb, shared=True, batched=True)
        _assert_she_identical(seq, bat)
        for brk, r in zip(bricks, bat.results):
            assert np.abs(r.recon - brk).max() <= _bound(eb, brk)

    @given(seed=st.integers(0, 10_000), eb=st.floats(1e-3, 1.0),
           shape=st.sampled_from([(4, 4, 4), (8, 8, 8), (13, 7, 9)]))
    def test_property_lor_reg_batched_identical(seed, eb, shape):
        rng = np.random.default_rng(seed)
        stack = (rng.standard_normal((4,) + shape) * 10).astype(np.float32)
        batched = compress_lor_reg_batched(stack, eb, block=4)
        for idx in range(4):
            ref = compress_lor_reg(stack[idx], eb, block=4,
                                   count_entropy=False)
            np.testing.assert_array_equal(batched[idx].codes, ref.codes)
            np.testing.assert_array_equal(batched[idx].recon, ref.recon)
            assert batched[idx].extras["branch"] == ref.extras["branch"]


def test_oversize_brick_fallback_counted():
    """A brick over the VMEM tile budget is compressed by the numpy oracle
    and counted under ``brick_size``; a brick within it is counted as
    device work — both bound-correct."""
    from repro.obs import metrics as obsm

    def count(family, *labels):
        child = family.children().get(labels)
        return 0.0 if child is None else child.value

    eb = 1e-2
    rng = np.random.default_rng(3)
    big = rng.normal(size=(1, 8, 128, 129)).astype(np.float32)
    small = rng.normal(size=(3, 8, 8, 8)).astype(np.float32)
    assert np.prod(big.shape[1:]) > sz._MAX_PALLAS_BRICK
    host = (obsm.HOST_FALLBACKS, "lorenzo", "brick_size")
    dev = (obsm.DEVICE_ITEMS, "lorenzo", "cpu:0")
    h0, d0 = count(*host), count(*dev)
    for x in (big, small):
        for r, brick in zip(sz.compress_lor_reg_batched(x, eb,
                                                        engine="pallas"), x):
            assert np.abs(r.recon - brick).max() <= eb * (1 + 1e-5)
    assert count(*host) - h0 == 1
    assert count(*dev) - d0 == 3
