"""The compressor's per-level and per-launch counters:
``tacz_compress_subblocks_total{strategy}`` counts the sub-blocks
``partition_level`` places, and ``tacz_device_launches_total{stage}``
counts batched Lorenzo kernel calls (bricks that a guard sends to the
host launch nothing)."""
import numpy as np
import pytest

from repro import obs
from repro.core import amr, hybrid, sz
from repro.kernels import ops, ref
from repro.obs import metrics as obsm


@pytest.fixture(autouse=True)
def metrics_enabled():
    was = obs.is_enabled()
    obs.set_enabled(True)
    yield
    obs.set_enabled(was)


def _subblocks(strategy: str) -> float:
    return obsm.COMPRESS_SUBBLOCKS.labels(strategy).value


def _launches() -> float:
    return obsm.DEVICE_LAUNCHES.labels("lorenzo").value


def _two_level_dataset(seed: int = 3) -> amr.AMRDataset:
    """A 32³ fine level refined in 5 of 64 8³ blocks (OpST) over a 16³
    coarse level that stores the rest (AKDTree)."""
    rng = np.random.default_rng(seed)
    fine_blocks = np.zeros((4, 4, 4), bool)
    fine_blocks.flat[[0, 9, 21, 42, 63]] = True
    fine_mask = fine_blocks.repeat(8, 0).repeat(8, 1).repeat(8, 2)
    coarse_mask = ~fine_blocks.repeat(4, 0).repeat(4, 1).repeat(4, 2)
    fine = np.where(fine_mask, rng.standard_normal((32,) * 3), 0)
    coarse = np.where(coarse_mask, rng.standard_normal((16,) * 3), 0)
    return amr.AMRDataset([
        amr.AMRLevel(fine.astype(np.float32), fine_mask, 1),
        amr.AMRLevel(coarse.astype(np.float32), coarse_mask, 2)])


def test_subblock_counter_adds_each_levels_partition():
    ds = _two_level_dataset()
    unit = 8
    want: dict = {}
    for lvl in ds.levels:
        _, strategy, _, sbs = hybrid.partition_level(
            lvl.data, lvl.mask, unit=max(2, unit // lvl.ratio))
        want[strategy] = want.get(strategy, 0) + len(sbs)
    assert set(want) == {"opst", "akdtree"} and all(want.values())
    before = {s: _subblocks(s) for s in want}
    res = hybrid.compress_amr(ds, eb=1e-2, unit=unit, lorenzo_engine="numpy")
    assert [lv.strategy for lv in res.levels] == ["opst", "akdtree"]
    for strategy, n in want.items():
        assert _subblocks(strategy) - before[strategy] == n


def _oracle(x, *, eb, tile):
    return ref.lorenzo3d_codes_batched_ref(x, eb, tile=tile)


@pytest.mark.parametrize("n_bricks", [1, 7])
def test_launch_counter_adds_one_per_kernel_call(monkeypatch, n_bricks):
    monkeypatch.setattr(ops, "lorenzo3d_codes_batched", _oracle)
    x = np.random.default_rng(n_bricks).standard_normal(
        (n_bricks, 4, 6, 8)).astype(np.float32)
    before = _launches()
    for calls in (1, 2):
        codes = sz._lorenzo_codes_batched_pallas(x, 1e-2)
        assert codes is not None and codes.shape == x.shape
        assert _launches() - before == calls


@pytest.mark.parametrize("reason", ["brick_size", "quant_range"])
def test_a_host_fallback_launches_nothing(monkeypatch, reason):
    def must_not_launch(*_a, **_k):
        raise AssertionError("the kernel ran past its guard")

    monkeypatch.setattr(ops, "lorenzo3d_codes_batched", must_not_launch)
    if reason == "brick_size":
        x = np.zeros((2, 16, 128, 128), np.float32)
        eb = 1e-2
    else:
        x = np.full((2, 4, 4, 4), 1e6, np.float32)
        eb = 1e-3
    before = _launches()
    fallbacks = obsm.HOST_FALLBACKS.labels("lorenzo", reason)
    fb_before = fallbacks.value
    assert sz._lorenzo_codes_batched_pallas(x, eb) is None
    assert _launches() == before
    assert fallbacks.value - fb_before == 2
