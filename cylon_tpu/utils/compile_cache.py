"""The one way to enable JAX's persistent compilation cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already caches there and
this module sets no directory in code.  Where it is not, the cache lives at
one fixed path inside the checkout, ``<checkout>/.jax_cache`` — the path is
part of the cache key, so it is never a temporary name, a pid or a time.
``CYLON_TEST_NO_COMPILE_CACHE=1`` turns every enabler off.
"""
from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_persistent_compile_cache(min_compile_secs: float = 5
                                    ) -> "str | None":
    """Turn the persistent compile cache on and return the directory in
    force (None when CYLON_TEST_NO_COMPILE_CACHE=1).  Safe to call more than
    once; call it from drivers and harnesses, never at library import."""
    if os.environ.get("CYLON_TEST_NO_COMPILE_CACHE") == "1":
        return None
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    # recorded in the observability snapshot so a trace artifact says
    # whether its compiles could have been cache hits
    from cylon_tpu.obs import metrics as _obs_metrics

    _obs_metrics.gauge_set("compile_cache.enabled", 1)
    return path
