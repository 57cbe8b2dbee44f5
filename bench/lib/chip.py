"""The chip a benchmark process runs on, and its compilation cache."""
from __future__ import annotations

import os
import sys

from bench.lib.spec import ROOT

# a fixed path inside the checkout: the directory is part of the cache key
CACHE_DIR = os.path.join(str(ROOT), ".jax_cache")


def open_chip(chips: int) -> bool:
    """Point JAX's persistent compilation cache at the checkout and check
    that JAX sees a TPU with at least `chips` chips (False otherwise, with
    the reason on standard error)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench: needs {chips} TPU chip(s); JAX found {len(devs)} "
              f"{devs[0].platform} device(s)", file=sys.stderr)
        return False
    return True
