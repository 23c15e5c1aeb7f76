"""Where JAX's persistent compilation cache lives.

The cache's path is part of what a later run must find again, so it is one
fixed place: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself, and no other directory is set), otherwise
``.jax_cache`` at the root of the checkout.  Every program is cached, however
fast it compiled: the explorer compiles hundreds of small programs (616 for
the six golden scenarios on a TPU v5e, about 66 ms each), and JAX's default
threshold of one second would cache almost none of them.  Entry points call
``enable_compile_cache()`` before their first compile (``spac`` /
``python -m repro`` through ``api.cli.main``, and ``chip_smoke.py``); it is
never called at import time, so importing ``repro`` changes no JAX setting.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

#: the fixed in-checkout default (``<repo>/.jax_cache``, git-ignored)
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
