"""Process set-up shared by the entry points (``serve``, ``train``,
``benchmarks/run.py``, ``chip_smoke.py``): where JAX keeps its persistent
compilation cache, and which devices the process runs on.

Nothing here runs at import; each entry point calls what it needs from
its ``main``, so importing the package (as the tests do) leaves JAX's
global configuration alone.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when set.  Otherwise the cache is
    the fixed, git-ignored ``.jax_cache/`` at the checkout root: the path
    is part of what a later run must find again, so it is never derived
    from a temp name, a pid or the time."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def device_info() -> dict:
    """The devices as JAX reports them: platform, kind and count."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def device_line() -> str:
    d = device_info()
    return (f"[device] platform={d['platform']} kind={d['kind']} "
            f"count={d['count']}")
