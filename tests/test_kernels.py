"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode
(assignment brief deliverable (c))."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _x(shape, seed, dtype=np.float32, scale=10.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray((rng.standard_normal(shape) * scale).astype(dtype))


@pytest.mark.parametrize("shape,tile", [
    ((8, 128, 128), (8, 128, 128)),
    ((16, 128, 128), (8, 128, 128)),
    ((8, 256, 128), (8, 128, 128)),
    ((16, 256, 256), (8, 128, 128)),
    ((4, 8, 8), (4, 8, 8)),
])
@pytest.mark.parametrize("eb", [0.5, 0.01])
def test_lorenzo3d_codes_vs_ref(shape, tile, eb):
    x = _x(shape, hash((shape, eb)) % 2**31)
    codes_k = ops.lorenzo3d_codes(x, eb=eb, tile=tile)
    codes_r = ref.lorenzo3d_codes_ref(x, eb, tile=tile)
    np.testing.assert_array_equal(np.asarray(codes_k), np.asarray(codes_r))


@pytest.mark.parametrize("shape,tile", [
    ((8, 128, 128), (8, 128, 128)),
    ((16, 256, 128), (8, 128, 128)),
])
@pytest.mark.parametrize("eb", [0.1])
def test_lorenzo3d_roundtrip_error_bound(shape, tile, eb):
    x = _x(shape, 7)
    codes = ops.lorenzo3d_codes(x, eb=eb, tile=tile)
    recon_k = ops.lorenzo3d_recon(codes, eb=eb, tile=tile)
    recon_r = ref.lorenzo3d_recon_ref(
        ref.lorenzo3d_codes_ref(x, eb, tile=tile), eb, tile=tile)
    np.testing.assert_allclose(np.asarray(recon_k), np.asarray(recon_r),
                               rtol=0, atol=1e-5)
    assert float(jnp.abs(recon_k - x).max()) <= eb * (1 + 1e-5)


@pytest.mark.parametrize("shape,tile", [
    ((5, 8, 128, 128), (8, 128, 128)),
    ((3, 16, 128, 256), (8, 128, 128)),
    ((7, 4, 8, 8), (4, 8, 8)),
    ((2, 8, 8, 8), (8, 128, 128)),
])
@pytest.mark.parametrize("eb", [0.5, 0.01])
def test_lorenzo3d_batched_codes_vs_ref(shape, tile, eb):
    x = _x(shape, hash((shape, eb)) % 2**31)
    codes_k = ops.lorenzo3d_codes_batched(x, eb=eb, tile=tile)
    codes_r = ref.lorenzo3d_codes_batched_ref(x, eb, tile=tile)
    np.testing.assert_array_equal(np.asarray(codes_k), np.asarray(codes_r))


@pytest.mark.parametrize("shape,tile", [
    ((4, 8, 128, 128), (8, 128, 128)),
    ((6, 4, 8, 8), (4, 8, 8)),
])
def test_lorenzo3d_batched_matches_per_brick(shape, tile):
    """The batch axis must not leak values across bricks: the batched
    kernel equals the 3D kernel run brick-by-brick (SHE's independence)."""
    eb = 0.05
    x = _x(shape, 11)
    codes_b = np.asarray(ops.lorenzo3d_codes_batched(x, eb=eb, tile=tile))
    for i in range(shape[0]):
        codes_i = np.asarray(ops.lorenzo3d_codes(x[i], eb=eb, tile=tile))
        np.testing.assert_array_equal(codes_b[i], codes_i)
    recon_b = ops.lorenzo3d_recon_batched(jnp.asarray(codes_b), eb=eb,
                                          tile=tile)
    recon_r = ref.lorenzo3d_recon_batched_ref(jnp.asarray(codes_b), eb,
                                              tile=tile)
    np.testing.assert_allclose(np.asarray(recon_b), np.asarray(recon_r),
                               rtol=0, atol=1e-5)
    assert float(jnp.abs(recon_b - x).max()) \
        <= eb + float(jnp.abs(x).max()) * 2.0 ** -22


@pytest.mark.parametrize("rows,nbits,maxlen", [
    (3, 100, 5),            # one narrow tile
    (11, 5000, 17),         # several bit-axis tiles: windows cross the halo
    (8, 2047, 30),          # the longest codes, ending at a tile edge
])
def test_huffdec_windows_vs_numpy(rows, nbits, maxlen):
    from repro.core import entropy
    from repro.kernels import huffdec
    rng = np.random.default_rng(rows * nbits)
    r, w, col_tile = huffdec.padded_shape(rows, nbits)
    bits = np.zeros((r, w + huffdec.HALO), np.uint8)
    bits[:rows, :nbits] = rng.integers(0, 2, size=(rows, nbits))
    wm = ops.huffdec_windows(bits, maxlen=maxlen, col_tile=col_tile)
    np.testing.assert_array_equal(
        np.asarray(wm), entropy._window_matrix(bits, maxlen, w))


@pytest.mark.parametrize("n,n_bins,chunk", [
    (1000, 64, 256), (8192, 1024, 8192), (5000, 128, 1024), (10, 16, 8)])
def test_hist_vs_ref(n, n_bins, chunk):
    rng = np.random.default_rng(n)
    codes = jnp.asarray(rng.integers(-5, n_bins + 10, size=(n,)), jnp.int32)
    h_k = ops.hist(codes, n_bins=n_bins, chunk=chunk)
    h_r = ref.hist_ref(codes, n_bins)
    np.testing.assert_array_equal(np.asarray(h_k), np.asarray(h_r))
    assert int(h_k.sum()) == n


@pytest.mark.parametrize("shape,group", [
    ((256, 512), 128), ((512, 256), 128), ((64, 128), 64), ((256, 1024), 256)])
@pytest.mark.parametrize("dtype", [np.float32])
def test_group_quant_vs_ref(shape, group, dtype):
    x = _x(shape, hash((shape, group)) % 2**31, dtype=dtype, scale=3.0)
    q_k, s_k = ops.group_quant(x, group=group)
    q_r, s_r = ref.group_quant_ref(x, group)
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6)
    d_k = ops.group_dequant(q_k, s_k, group=group)
    d_r = ref.group_dequant_ref(q_r, s_r, group)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), rtol=1e-6)
    # int8 quantization error bound: |x - deq| <= scale/2 per group
    err = np.abs(np.asarray(d_k) - np.asarray(x))
    bound = np.repeat(np.asarray(s_k), group, axis=1) * 0.5 + 1e-7
    assert (err <= bound).all()


def test_group_quant_zero_group_exact():
    x = jnp.zeros((256, 256), jnp.float32)
    q, s = ops.group_quant(x, group=128)
    assert (np.asarray(q) == 0).all()
    d = ops.group_dequant(q, s, group=128)
    assert (np.asarray(d) == 0).all()


def test_kernel_codes_match_core_sz_per_brick():
    """The Pallas tile == repro.core per-brick Lorenzo semantics."""
    from repro.core import sz

    x = _x((8, 128, 128), 3)
    eb = 0.05
    codes_k = np.asarray(ops.lorenzo3d_codes(x, eb=eb, tile=(8, 128, 128)))
    codes_c = sz.lorenzo_nd_codes(sz.prequant(np.asarray(x), eb))
    np.testing.assert_array_equal(codes_k, codes_c.astype(np.int32))
