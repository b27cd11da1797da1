"""Test configuration: run on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; per SURVEY.md §5 the sharding
tests run on host-simulated devices. The suite never touches a chip: the
platform is forced to the CPU here, and what needs the chip's compiler
(tests/test_chip_compile.py) compiles for a DESCRIBED v5e inside its own
fixtures.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

# Persistent compilation cache: the suite's wall time is dominated by XLA
# re-compiles of the same jitted steps across test processes/runs; cache
# them on disk (<repo>/.jax_cache or $JAX_COMPILATION_CACHE_DIR —
# utils/compile_cache.py) so repeat runs pay tracing only. Threshold 0.1s
# keeps only trivial kernels out of the cache.
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from mx_rcnn_tpu.utils.compile_cache import enable_persistent_cache

enable_persistent_cache()

import numpy as np
import pytest

#: below this much free space in the tmp dir, checkpoint-writing fixtures
#: skip loudly instead of dying mid-write with a phantom FileNotFoundError
#: (PR 12's notes: a full /tmp surfaces as missing .npz shards, not ENOSPC)
_TMP_FREE_FLOOR_BYTES = 512 * 1024 * 1024


def _tmp_free_bytes() -> int:
    import shutil
    import tempfile

    try:
        return shutil.disk_usage(tempfile.gettempdir()).free
    except OSError:
        return _TMP_FREE_FLOOR_BYTES  # unknowable — don't block the run


def _require_tmp_space(what: str):
    free = _tmp_free_bytes()
    if free < _TMP_FREE_FLOOR_BYTES:
        pytest.skip(
            f"/tmp has only {free // (1024 * 1024)} MiB free "
            f"(< {_TMP_FREE_FLOOR_BYTES // (1024 * 1024)} MiB floor) — "
            f"{what} writes checkpoints there and would fail with "
            "misleading FileNotFoundErrors; free space and re-run")


@pytest.fixture(scope="session", autouse=True)
def _prune_run_tmp(tmp_path_factory):
    """Session finalizer: delete THIS run's pytest tmp tree (checkpoint
    dirs from the fit baselines and resilience tests are the bulk of it)
    so repeated runs stop accumulating toward /tmp exhaustion. pytest's
    own keep-3-runs retention never fires when a run is killed mid-way;
    this always does."""
    yield
    import shutil

    base = tmp_path_factory.getbasetemp()
    shutil.rmtree(base, ignore_errors=True)


@pytest.fixture(scope="module", autouse=True)
def _release_module_memory(request):
    """After each module of whole-model compiles (the chip-compile files,
    and those marked ``compile_heavy``: the rehearsals, the train steps):
    drop JAX's compiled-function caches and hand freed heap back to the
    system. A worker runs many modules in one process, and those compiles
    otherwise leave each worker holding several GB for the rest of the
    run; six such workers outgrew the machine's memory. Light modules
    keep their caches."""
    yield
    if (request.node.get_closest_marker("compile_heavy") is None
            and not request.module.__name__.startswith("test_chip_compile")):
        return
    import ctypes
    import gc

    jax.clear_caches()
    gc.collect()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except OSError:  # not glibc: nothing to trim
        pass


@pytest.fixture
def rng():
    return np.random.RandomState(0)


def _uninterrupted_fit(tmp_path_factory, name, **kw):
    """One chaos-clean tiny fit (tests/_resilience_driver.py::run_fit)
    whose final params serve as a shared bit-exactness baseline. Armed
    chaos must not leak into it."""
    import _resilience_driver as driver
    from mx_rcnn_tpu.resilience import chaos

    _require_tmp_space(f"the {name} baseline fit")
    old = os.environ.pop(chaos.ENV_VAR, None)
    chaos.reset()
    try:
        prefix = str(tmp_path_factory.mktemp(name) / "u")
        return driver.run_fit(prefix, **kw)
    finally:
        if old is not None:
            os.environ[chaos.ENV_VAR] = old
        chaos.reset()


@pytest.fixture(scope="session")
def bf16_baseline(tmp_path_factory):
    """Uninterrupted compute_dtype=bf16 tiny fit params — the ONE
    graftcast parity reference shared by the kill→resume gate
    (tests/test_resilience.py), the heal-carry gate (tests/test_heal.py)
    and the graftpulse nan→resume gate (tests/test_health.py). Session
    scope: all compare against the bit-identical deterministic run, so a
    single baseline fit pays for every consumer (tier-1 budget)."""
    return _uninterrupted_fit(tmp_path_factory, "bf16_base",
                              compute="bf16")


@pytest.fixture(scope="session")
def tree_f32_baseline(tmp_path_factory):
    """Uninterrupted f32 tiny fit params — shared by the SIGTERM
    kill→resume parity gate (tests/test_resilience.py) and the
    graftpulse nan→resume gate (tests/test_health.py)."""
    return _uninterrupted_fit(tmp_path_factory, "tree_base")
