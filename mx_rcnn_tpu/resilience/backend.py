"""Classified backend acquisition.

One call that classifies backend initialization failures into transient vs
permanent, retries transients with exponential backoff + jitter under a
configurable deadline, and emits ``backend_retry`` / ``backend_up``
graftscope events so an outage leaves a machine-foldable record
(``obs.report`` counts the retries and keeps the last error).

Classification is by gRPC status name in the message — a backend that is
not there yet surfaces as ``UNAVAILABLE`` (both as
``jax.errors.JaxRuntimeError`` and as the ``RuntimeError`` that
``Unable to initialize backend`` wraps it in; both are RuntimeError
subclasses). Anything not carrying a transient marker fails fast:
retrying an INVALID_ARGUMENT for hours is how a misconfigured run burns a
deadline.

Wired through train (tools/train.py::fit_detector), eval (test.py) and
bench (bench.py) behind ``resilience.backend_acquire``; knobs live in the
``resilience`` config section. Fault injection: chaos.py's
``backend_unavailable`` / ``backend_permanent``.
"""

from __future__ import annotations

import os
import random
import time
from typing import Callable, Optional

from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.resilience import chaos

#: gRPC status names that mark a failure as transient (retry).
TRANSIENT_MARKERS = ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED")


class BackendUnavailableError(RuntimeError):
    """The backend stayed transiently unavailable past the deadline."""


def classify_backend_error(exc: BaseException) -> str:
    """'transient' (retry) or 'permanent' (fail fast) for a backend
    initialization error, by gRPC status name in the message."""
    msg = str(exc)
    return ("transient" if any(m in msg for m in TRANSIENT_MARKERS)
            else "permanent")


def _default_probe():
    """One acquisition attempt: the chaos hook first (so injected outages
    work even on an already-initialized backend), then the real device
    query."""
    chaos.from_env().maybe_fail_backend()
    import jax

    return jax.devices()


def _check_platform(devices, want: str):
    """jax can come up on the CPU when the accelerator is not there —
    the probe then 'succeeds' on attempt 1 and a multi-hour 'TPU' run
    proceeds at CPU speed. With ``resilience.backend_platform`` set, a
    device list without the expected platform is a transient failure
    like any other (classified UNAVAILABLE, retried under the
    deadline)."""
    if any(getattr(d, "platform", "").lower() == want for d in devices):
        return
    got = sorted({getattr(d, "platform", "?") for d in devices})
    raise RuntimeError(
        f"UNAVAILABLE: backend came up without a {want!r} device "
        f"(got {got}) — treating as outage")


def _clear_backend_cache():
    """Drop jax's cached backend set so the next probe re-initializes —
    after a CPU fallback the wrong backend is CACHED and no amount of
    retrying would ever observe the recovered accelerator without this.
    Two callers, both of which have made live arrays expendable
    first: the platform-mismatch retry path here (before the first real
    device touch), and graftheal's teardown (resilience/heal.py — after
    the emergency capture copied everything worth keeping to host-owned
    numpy). Anywhere else, clearing would invalidate live arrays."""
    import jax.extend.backend

    jax.extend.backend.clear_backends()


def acquire_backend(rcfg, elog=None, probe: Optional[Callable] = None,
                    sleep: Callable[[float], None] = time.sleep,
                    clock: Callable[[], float] = time.monotonic,
                    rng: Optional[random.Random] = None):
    """Acquire the accelerator backend, riding out transient failures.

    Returns the device list. ``rcfg`` is the ``resilience`` config section
    (backend_deadline_s / backend_backoff_base_s / backend_backoff_max_s /
    backend_backoff_jitter). ``elog`` is an optional graftscope EventLog.
    ``probe``/``sleep``/``clock``/``rng`` are injectable for tests — the
    defaults are the real thing.

    Raises the original error immediately when it classifies permanent,
    and BackendUnavailableError when transient failures outlast
    ``backend_deadline_s``.
    """
    probe = probe or _default_probe
    # Jitter decorrelates a fleet of hosts re-probing a recovering backend;
    # seeding by pid keeps one process's schedule reproducible.
    rng = rng or random.Random(os.getpid())
    start = clock()
    deadline = start + max(0.0, rcfg.backend_deadline_s)
    delay = max(0.001, rcfg.backend_backoff_base_s)
    attempt = 0
    while True:
        attempt += 1
        try:
            devices = probe()
            want = getattr(rcfg, "backend_platform", "")
            if want:
                try:
                    _check_platform(devices, want.lower())
                except RuntimeError:
                    _clear_backend_cache()  # else retries see the cached
                    raise                   # fallback backend forever
        except RuntimeError as exc:
            waited = clock() - start
            if classify_backend_error(exc) == "permanent":
                logger.error(
                    "backend acquisition failed PERMANENTLY on attempt %d "
                    "(%s) — not retrying; fix the config/driver, the "
                    "deadline is for outages", attempt, exc)
                raise
            remaining = deadline - clock()
            if remaining <= 0:
                raise BackendUnavailableError(
                    f"backend still transiently unavailable after "
                    f"{attempt} attempts / {waited:.0f}s (deadline "
                    f"{rcfg.backend_deadline_s:.0f}s); last error: {exc}"
                ) from exc
            pause = min(delay, rcfg.backend_backoff_max_s)
            pause *= 1.0 + max(0.0, rcfg.backend_backoff_jitter) * rng.random()
            pause = min(pause, remaining)
            if elog is not None and elog.enabled:
                elog.emit("backend_retry", attempt=attempt,
                          sleep_s=round(pause, 3),
                          waited_s=round(waited, 3), error=str(exc)[:500])
            logger.warning(
                "backend unavailable (attempt %d, waited %.0fs): %s — "
                "retrying in %.1fs", attempt, waited, exc, pause)
            sleep(pause)
            delay = min(delay * 2.0, rcfg.backend_backoff_max_s)
        else:
            if elog is not None and elog.enabled:
                elog.emit("backend_up", attempts=attempt,
                          waited_s=round(clock() - start, 3),
                          device_count=len(devices))
            if attempt > 1:
                logger.info("backend up after %d attempts (%.0fs)",
                            attempt, clock() - start)
            return devices
