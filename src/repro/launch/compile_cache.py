"""JAX's persistent compilation cache, at one stable place per checkout.

A cold force call on the chip spends seconds to minutes compiling, so the
entry points (``chip_smoke.py``, ``examples/md_nve.py``,
``benchmarks/run.py``) turn the cache on first thing.  The directory is
part of what makes a cached program findable again, so it never moves:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads the
variable itself), otherwise ``.jax_cache`` at the root of the checkout.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / '.jax_cache'


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get('JAX_COMPILATION_CACHE_DIR')
    if env:
        return env
    jax.config.update('jax_compilation_cache_dir', str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
