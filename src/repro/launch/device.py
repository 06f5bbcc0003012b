"""Process set-up shared by the launchers and ``chip_smoke.py``: where
JAX keeps its persistent compilation cache, and which devices it runs on.

Call both before the first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

# fixed, inside the checkout (git-ignored): a cache whose directory
# moves between runs is never found again
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place JAX's persistent compilation cache. Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to :data:`CACHE_DIR`.
    Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def device_banner() -> dict:
    """Print and return the platform, kind and count of JAX's devices."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"devices: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    return info
