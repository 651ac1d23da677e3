"""Compile-only rehearsal of the main-path kernels for a TPU v5e chip.

Nothing here runs on a chip: each case lowers and compiles one kernel for
a *described* ``v5e:2x2`` topology, so the TPU compiler refuses here what
interpret mode lets through (unsupported primitives, VMEM overruns).
Shapes are the ones the pipeline launches: brick stacks the AMR
partition emits, a cold-serving Huffman batch, and the widest decode
launch ``PallasEngine`` admits.

The topology is described inside a module fixture — never at import — so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler library.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import entropy, sz
from repro.kernels import huffdec, lorenzo3d


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a persistent cache cannot read back programs compiled for a chip
    # that is not attached; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means "cannot"
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes, kernel=True):
    """Compile for the described chip; a Pallas kernel must come out as
    a Mosaic custom call, not as interpreted XLA."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    return compiled


@pytest.mark.parametrize("shape", [
    (512, 24, 24, 24),      # OpST bricks of the fine level
    (256, 32, 16, 32),      # AKDTree bricks of the coarse level
    (4, 8, 128, 128),       # the largest brick the VMEM guard admits
])
def test_lorenzo_codes_batched_compiles(one_chip, shape):
    assert shape[1] * shape[2] * shape[3] <= sz._MAX_PALLAS_BRICK
    fn = functools.partial(lorenzo3d.lorenzo3d_codes_batched, eb=1e-3,
                           tile=shape[1:], interpret=False)
    _compile(fn, jax.ShapeDtypeStruct(shape, jnp.float32,
                                      sharding=one_chip))


@pytest.mark.parametrize("batched", [False, True])
def test_lorenzo_recon_compiles(one_chip, batched):
    shape = (64, 16, 16, 16) if batched else (16, 128, 128)
    kern = (lorenzo3d.lorenzo3d_recon_batched if batched
            else lorenzo3d.lorenzo3d_recon)
    fn = functools.partial(kern, eb=1e-3, tile=shape[-3:], interpret=False)
    _compile(fn, jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip))


@pytest.mark.parametrize("rows,nbits", [
    (700, 12_000),          # a cold ROI batch: ~700 bricks of 16³ codes
    (1, None),              # the widest launch the window budget admits
])
def test_huffdec_windows_compiles(one_chip, rows, nbits):
    if nbits is None:       # longest payload that still gets a launch
        nbits = 1
        while entropy.PallasEngine._launch_elems(1, nbits * 2) \
                <= entropy._MAX_PALLAS_WINDOW_ELEMS:
            nbits *= 2
    r, w, col_tile = huffdec.padded_shape(rows, nbits + 1)
    assert r * (w + huffdec.HALO) <= entropy._MAX_PALLAS_WINDOW_ELEMS
    fn = functools.partial(huffdec.huffdec_windows, maxlen=24,
                           col_tile=col_tile, interpret=False)
    _compile(fn, jax.ShapeDtypeStruct((r, w + huffdec.HALO), jnp.uint8,
                                      sharding=one_chip))


def test_decode_walk_compiles(one_chip):
    r, w, _ = huffdec.padded_shape(700, 12_000 + 1)
    n = entropy._MAX_PALLAS_MAXLEN + 2

    def sds(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    fn = functools.partial(huffdec.decode_walk, maxlen=24,
                           steps=huffdec.bucket(4096, 8))
    _compile(fn, sds((r, w)), sds((r,)), sds((r,)), sds((n,)), sds(()),
             sds((n,)), sds((n,)), sds((n,)), kernel=False)
