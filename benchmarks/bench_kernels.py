"""Kernel-layer benchmark: Pallas (interpret) vs pure-jnp oracle vs the
numpy host path — correctness + CPU-side call timing.

Interpret-mode timings are *functional* only (the kernels target TPU v5e);
they say nothing about device speed."""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.core import sz
from repro.kernels import ops, ref

from .common import timed, write_csv


def run(quick: bool = False):
    rows = []
    shape = (8, 128, 128)
    x = jnp.asarray(np.random.default_rng(0)
                    .standard_normal(shape).astype(np.float32) * 5)
    eb = 1e-2

    codes, t_k = timed(lambda: ops.lorenzo3d_codes(x, eb=eb).block_until_ready(),
                       repeat=3)
    _, t_r = timed(lambda: np.asarray(
        ref.lorenzo3d_codes_ref(x, eb, tile=shape)), repeat=3)
    _, t_np = timed(lambda: sz.lorenzo_nd_codes(
        sz.prequant(np.asarray(x), eb)), repeat=3)
    rows.append(("lorenzo3d_codes", round(t_k * 1e6, 1),
                 round(t_r * 1e6, 1), round(t_np * 1e6, 1)))

    codes_i = jnp.asarray(np.random.default_rng(1)
                          .integers(0, 1024, size=(65536,)), jnp.int32)
    _, t_k = timed(lambda: ops.hist(codes_i, n_bins=1024).block_until_ready(),
                   repeat=3)
    _, t_r = timed(lambda: ref.hist_ref(codes_i, 1024).block_until_ready(),
                   repeat=3)
    rows.append(("hist_1024", round(t_k * 1e6, 1), round(t_r * 1e6, 1),
                 "-"))

    g = jnp.asarray(np.random.default_rng(2)
                    .standard_normal((1024, 1024)).astype(np.float32))
    _, t_k = timed(lambda: ops.group_quant(g, group=128)[0]
                   .block_until_ready(), repeat=3)
    _, t_r = timed(lambda: ref.group_quant_ref(g, 128)[0]
                   .block_until_ready(), repeat=3)
    rows.append(("group_quant", round(t_k * 1e6, 1), round(t_r * 1e6, 1),
                 "-"))

    path = write_csv("kernels",
                     ["kernel", "pallas_interp_us", "jnp_ref_us",
                      "numpy_us"], rows)
    return {"csv": path, "n_kernels": len(rows)}


if __name__ == "__main__":
    print(run())
