"""The accelerator a measured run needs, and where its compiled programs
are kept.

Both are called from an entry point's ``main()``, never at import time:
tests and tools import this package on hosts without a chip.
"""

from __future__ import annotations

import os
from pathlib import Path
from typing import List

import jax

#: compile cache used when ``JAX_COMPILATION_CACHE_DIR`` is not set; a
#: fixed path, because the directory is part of the cache's key
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


class NoTPUError(RuntimeError):
    """JAX found no TPU, so nothing measured here would be a TPU number."""


def require_tpu() -> List[jax.Device]:
    """The TPU devices JAX sees; raises :class:`NoTPUError` otherwise.

    JAX falls back to the CPU when its TPU backend fails to start, and
    ``JAX_PLATFORMS=cpu`` hides the chip on purpose.  Either way a run
    that goes on would time the CPU, so it stops here instead.
    """
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise NoTPUError(
            f"JAX found no TPU (default platform {platform!r}, "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    return devices


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is read by JAX's own config
    and left alone.  Otherwise the cache goes to ``<repo>/.jax_cache``.
    Every compile is cached, however short, so that a second run of the
    same programs compiles nothing.  Call before the first computation.
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
