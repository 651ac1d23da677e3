"""``repro.device`` — the backend probe the device engines share, and the
compile cache the chip entry points turn on.

``"auto"`` engine names resolve through :func:`tpu_attached`; a probe
that fails raises, so a broken accelerator never passes for a host
without one.  :func:`enable_compile_cache` is called by the entry points
that run on the chip (``chip_smoke.py``, ``benchmarks/run.py``), never by
library code.
"""
from __future__ import annotations

import os

__all__ = ["tpu_attached", "device_label", "enable_compile_cache",
           "CACHE_DIR_NAME"]

#: the compile cache's directory name inside a checkout
CACHE_DIR_NAME = ".jax_cache"


def tpu_attached() -> bool:
    """True when JAX's default backend is a TPU.

    Backend start-up errors propagate instead of reading as "no TPU".
    """
    import jax

    return jax.default_backend() == "tpu"


def device_label(arr) -> str:
    """``"platform:id"`` of the device holding a (single-device) array —
    the ``device`` label of ``tacz_device_items_total``."""
    d = next(iter(arr.devices()))
    return f"{d.platform}:{d.id}"


def enable_compile_cache(checkout) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads it
    itself and no other directory is set here).  Otherwise the cache is
    ``<checkout>/.jax_cache``: a fixed path, so the next run from the
    same checkout finds what this one cached (a directory named after a
    PID, a temp name or the time never hits).  Every program is cached,
    however short its compile: the serving kernels compile in well under
    JAX's default one-second threshold.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(os.fspath(checkout)),
                            CACHE_DIR_NAME)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
