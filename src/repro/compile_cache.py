"""JAX's persistent compilation cache, kept where the next run finds it.

CloverLeaf compiles one tile program per chain signature (the init chain,
the ``calc_dt`` chain, each timestep segment), so a cold run on the chip
pays every one of those compiles; the cache lets a later run load them.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

CHECKOUT_ROOT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> Optional[str]:
    """Turn on the persistent compilation cache on an accelerator; returns
    its directory (None when no cache is in use).

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left to JAX.  Otherwise the cache is ``.jax_cache/`` at the checkout
    root — a fixed path, because the path is part of what a later run looks
    up.  Every compile is cached, however short it was.  On the CPU (tests)
    nothing is changed: XLA:CPU logs a long machine-feature warning for each
    executable it loads back, and its compiles are cheap."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if jax.default_backend() == "cpu":
        return path
    if not path:
        path = str(CHECKOUT_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
