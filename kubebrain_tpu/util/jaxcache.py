"""Where compiled XLA/Mosaic programs persist between process starts.

Every server start otherwise recompiles every kernel, every pow2 index
bucket and every query-batch width. One rule, applied by every entry point
that uses JAX (``cli.build_endpoint``, ``__graft_entry__``,
``tests/conftest.py``) before its first backend touch: ``JAX_COMPILATION_CACHE_DIR`` decides when it is set — JAX reads it
itself — and otherwise the cache lives in ``<checkout>/.jax_cache``. The
directory is part of the cache key, so it never depends on a pid, a port,
a timestamp or a temp dir.
"""

from __future__ import annotations

import os

#: the checkout holding this package (``<checkout>/kubebrain_tpu/util/``)
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def use_compile_cache() -> str:
    """Enable JAX's persistent compilation cache; returns its directory."""
    import jax

    if ("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ
            and jax.config.jax_platforms != "cpu"):
        # this program's Pallas kernels each compile in about JAX's 1 s
        # default threshold or less, so the default would cache few of them.
        # CPU simulation keeps the default: its compiles are cheap, and
        # XLA:CPU logs a machine-feature complaint per reloaded entry.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
