"""JAX runtime configuration shared by every kernel entry point.

A time-series database computes on int64 timestamps (epoch-ms overflows
int32), so x64 must be on wherever the kernels run — including the real
TPU chip, where jax defaults to x32 and would silently truncate both the
timestamps and the int64 sentinels in the segmented kernels (an
OverflowError in ops/rate.py).  Value columns stay
float32/bfloat16 by explicit dtype choice in the kernels; this only widens
the default so int64/float64 requests mean what they say.
"""

from __future__ import annotations

import os

_done = False


def ensure_x64():
    global _done
    if _done:
        return
    import jax

    jax.config.update("jax_enable_x64", True)
    _done = True


# The one in-checkout cache path (git-ignored).  Fixed on purpose: the
# directory is part of how a deployment finds its compiled programs again,
# so it never depends on $HOME, $TMPDIR, a pid or a time.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_cache_done = False


def ensure_compilation_cache():
    """Persistent XLA compilation cache: query-plan shapes compile once per
    machine, not once per process (cold-query latency is dominated by XLA
    compilation; the reference's equivalent is DataFusion having no
    compilation step at all, so cold starts must not regress vs it).

    Placed from OUTSIDE: where `JAX_COMPILATION_CACHE_DIR` is set, jax
    reads it itself and no directory is set in code; otherwise the cache
    lives at `DEFAULT_COMPILATION_CACHE_DIR` inside the checkout."""
    global _cache_done
    if _cache_done:
        return
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(DEFAULT_COMPILATION_CACHE_DIR, exist_ok=True)
        jax.config.update(
            "jax_compilation_cache_dir", DEFAULT_COMPILATION_CACHE_DIR
        )
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _cache_done = True
