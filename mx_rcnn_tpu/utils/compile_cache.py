"""Persistent XLA compilation cache — the one place that sets its directory.

Every entry point (train_end2end.py, test.py, train_alternate.py, demo.py,
bench.py, chip_smoke.py, the test suite) calls ``enable_persistent_cache()``
first, so a ``--resume`` restart, a second phase of the same run or a second
process on the same machine reuses the programs the first one compiled.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX itself reads it and the
cache is there: this module sets no other directory. Where it is not, the
cache goes to ``<repo>/.jax_cache`` — one fixed path inside the checkout,
never the home directory or a temporary name: a cache that moves with the
user, the pid or the time is a cache that never hits.
"""

from __future__ import annotations

import os

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_persistent_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    loc = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not loc:
        loc = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", loc)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return loc
