"""What every entry point does first: report the devices it got and keep
JAX's persistent compilation cache.

A FUNCTION each (nothing runs at import), so tests and tools can import the
entry points without touching device state.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    JAX reads ``JAX_COMPILATION_CACHE_DIR`` itself, so where it is set
    nothing is set here.  Otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``: a directory derived from tmp, the process id
    or the time would never be found again by the next run.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_report() -> dict:
    """Platform, kind and count of the devices JAX found, as JAX names them."""
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
