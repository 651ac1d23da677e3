"""Nyx-like tree-based AMR snapshots, made in bulk from a seed.

A copy of the recipe of ``repro.core.amr.synthetic_amr`` (lognormal
Gaussian random field with a power-law spectrum, block-wise refinement by
value quantile to the level densities of TAC+ Table I), rewritten as one
jitted float32 call that makes the field, its pooled levels and the level
of each refinement block; the host only expands the block levels into
cell masks.  The program's generator takes a single-threaded float64 FFT
and a second tiling pass, about 36 s at 512³ on the v5e host.  Every
stored point lives at exactly one level (the quantile assignment tiles
the domain by construction).

The call runs on JAX's CPU backend, multi-threaded: on the TPU the same
program gave a different field for the same seed in two processes (at
256³), and the benchmark needs the same inputs from the same seed.  The
field is not bit-identical to the program's generator, which is not
needed: the benchmark compares the program's output with this
generator's data.

Geometry and values come from two seeds.  The configuration's fixed
``data_seed`` draws the field that places the refinement (and so every
brick shape the compressor compiles for) and fixes the value range from
which a series takes its error bound; a run's seed draws a second field
that is mixed into the stored values (``seed_variance_share`` of the
Gaussian field's variance), as an in-situ series evolves its values on a
grid hierarchy that changes slowly.  Every seed therefore stores
different values under the same programs and the same bound.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np


@dataclass
class Snapshot:
    """One AMR snapshot, finest level first (host arrays)."""

    data: list          # per level float32 (n/r)³, 0 where not stored
    mask: list          # per level bool, True where the level stores
    ratio: list         # per level coarsening ratio vs the finest grid
    #: (min, max) of the values the ``data_seed`` field alone stores under
    #: this geometry: the range a series fixes its error bound from
    geometry_range: tuple = (0.0, 0.0)

    @property
    def n_values(self) -> int:
        return int(sum(int(m.sum()) for m in self.mask))

    def level_bytes(self) -> int:
        """Bytes of the dense float32 level layouts (what a full decode
        of every level holds)."""
        return int(sum(d.size * 4 for d in self.data))


def geometry_range(snapshots) -> float:
    """max − min of the values the snapshots' fixed (``data_seed``) fields
    store: the same for every run seed."""
    return (max(s.geometry_range[1] for s in snapshots)
            - min(s.geometry_range[0] for s in snapshots))


def key_words(seed: int, stream: int) -> np.ndarray:
    """Threefry key data from any non-negative integer seed and a stream
    index (seeds beyond 32 bits are folded through numpy's SeedSequence)."""
    words = np.random.SeedSequence([int(seed), int(stream)]).generate_state(2)
    return words.astype(np.uint32)


@functools.lru_cache(maxsize=None)
def _program(n: int, densities: tuple, refine_block: int, beta: float,
             smooth_sigma: float, lognormal_sigma: float, share: float):
    import jax
    import jax.numpy as jnp

    n_levels = len(densities)
    ratios = [2 ** i for i in range(n_levels)]
    rb = refine_block
    nb = n // rb
    total = float(sum(densities))
    counts = [int(round(d / total * nb ** 3)) for d in densities[:-1]]
    counts.append(nb ** 3 - sum(counts))

    def pool(x, r, op, init):
        # reduce_window keeps the array's minor dimensions large: a
        # reshape to (.., r) would be padded to the device's (8, 128) tiles
        if r == 1:
            return x
        return jax.lax.reduce_window(x, init, op, (r, r, r), (r, r, r),
                                     "VALID")

    def gaussian(words):
        key = jax.random.wrap_key_data(words, impl="threefry2x32")
        white = jax.random.normal(key, (n, n, n), jnp.float32)
        fw = jnp.fft.rfftn(white)
        kx = jnp.fft.fftfreq(n)[:, None, None]
        ky = jnp.fft.fftfreq(n)[None, :, None]
        kz = jnp.fft.rfftfreq(n)[None, None, :]
        k2 = kx * kx + ky * ky + kz * kz
        k2 = k2.at[0, 0, 0].set(1.0)
        amp = k2 ** (-beta / 4.0)
        if smooth_sigma > 0:
            amp = amp * jnp.exp(-2.0 * (jnp.pi * smooth_sigma) ** 2 * k2)
        amp = amp.at[0, 0, 0].set(0.0)
        g = jnp.fft.irfftn(fw * amp, s=(n, n, n))
        return g / (g.std() + 1e-30)

    def lognormal(g):
        field = jnp.exp(lognormal_sigma * g)
        return field / field.mean()

    def levels(field):
        return [pool(field, r, jax.lax.add, 0.0) / float(r ** 3)
                for r in ratios]

    def make(geometry_words, value_words):
        g0 = gaussian(geometry_words)
        field0 = lognormal(g0)
        interest = pool(field0, rb, jax.lax.max, -jnp.inf)
        order = jnp.argsort(-interest.ravel(), stable=True)
        bounds = np.cumsum([0] + counts)
        level_of = jnp.zeros(nb ** 3, jnp.int32)
        for lvl in range(1, n_levels):
            level_of = level_of.at[order[bounds[lvl]:bounds[lvl + 1]]].set(lvl)
        level_of = level_of.reshape(nb, nb, nb)
        pooled0 = levels(field0)
        lo = hi = None
        for lvl, p in enumerate(pooled0):
            c = rb // ratios[lvl]
            here = level_of == lvl
            bmin = jnp.where(here, pool(p, c, jax.lax.min, jnp.inf), jnp.inf)
            bmax = jnp.where(here, pool(p, c, jax.lax.max, -jnp.inf),
                             -jnp.inf)
            lo = bmin.min() if lo is None else jnp.minimum(lo, bmin.min())
            hi = bmax.max() if hi is None else jnp.maximum(hi, bmax.max())
        if share > 0:
            g = (np.sqrt(1.0 - share) * g0
                 + np.sqrt(share) * gaussian(value_words))
            pooled = levels(lognormal(g / (g.std() + 1e-30)))
        else:
            pooled = pooled0
        return pooled, level_of, lo, hi

    return jax.jit(make)


def generate(gen: dict, n: int, seed: int, stream: int = 0,
             value_seed: int | None = None) -> Snapshot:
    """Snapshot of finest edge ``n`` from the configuration's generator
    settings (``densities``, ``refine_block``, ``beta``, ``smooth_sigma``,
    ``lognormal_sigma``, ``seed_variance_share``).  ``seed`` (the
    configuration's ``data_seed``) places the levels; ``value_seed``, when
    given, is mixed into the stored values."""
    rb = int(gen["refine_block"])
    dens = tuple(float(d) for d in gen["densities"])
    if n % rb or rb % 2 ** (len(dens) - 1):
        raise ValueError(f"edge {n} and refine block {rb} do not tile "
                         f"{len(dens)} levels")
    share = (float(gen.get("seed_variance_share", 0.0))
             if value_seed is not None else 0.0)
    prog = _program(int(n), dens, rb, float(gen["beta"]),
                    float(gen["smooth_sigma"]), float(gen["lognormal_sigma"]),
                    share)
    import jax

    geometry = key_words(seed, stream)
    values = geometry if value_seed is None else key_words(value_seed,
                                                           stream)
    with jax.default_device(jax.devices("cpu")[0]):
        pooled, level_of, lo, hi = prog(geometry, values)
    level_of = np.asarray(level_of)
    data, mask, ratio = [], [], []
    for lvl, p in enumerate(pooled):
        r = 2 ** lvl
        c = rb // r
        m = (level_of == lvl).repeat(c, 0).repeat(c, 1).repeat(c, 2)
        data.append(np.where(m, np.asarray(p), np.float32(0)))
        mask.append(m)
        ratio.append(r)
    return Snapshot(data=data, mask=mask, ratio=ratio,
                    geometry_range=(float(lo), float(hi)))
