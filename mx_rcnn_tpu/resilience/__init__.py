"""graftguard — fault tolerance for long runs on preemptible accelerators.

The failure classes this package answers: the backend is transiently
unavailable (UNAVAILABLE at start-up or mid-step), the scheduler preempts
multi-hour runs mid-epoch, and a single hung compile can eat an entire
bench timeout. graftscope (mx_rcnn_tpu/obs) makes those failures
*visible*; graftguard makes them *survivable*:

- ``backend``  — classified backend acquisition: transient errors
  (UNAVAILABLE — the outage signature) retry with exponential backoff +
  jitter under a deadline; permanent errors fail fast. Emits
  ``backend_retry`` / ``backend_up`` graftscope events.
- ``preempt``  — SIGTERM/SIGINT handlers that request a checkpoint at the
  next step boundary and exit with ``RESUMABLE_RC`` so a supervisor knows
  to restart with ``--resume auto``.
- ``isolate``  — run a callable in a child process under a deadline (the
  bench's per-config jail: a hung compile forfeits one row, not the sweep).
- ``heal``     — graftheal: a step-time backend loss (mid-run, the part
  graftguard's startup acquisition could not reach) is recovered
  IN-PROCESS — emergency capture of the last known-good host state,
  teardown + re-acquisition under the same deadline, elastic re-shard
  when the backend returns with fewer devices. No crash, no operator.
- ``chaos``    — deterministic fault injection (raise UNAVAILABLE on the
  first N probes or mid-run at step K, SIGTERM at step K, hang one bench
  config, SIGKILL at a named site, shrink the re-acquired device list,
  kill one host of a simulated fleet, skip a quorum barrier) so every
  guarantee above is exercised by tier-1 CPU tests instead of by the
  next real outage.
- ``quorum``   — graftquorum: multi-host coordination (deadline-bounded
  barriers, propose/agree, generation-numbered heal rounds with
  exclusion) over jax.distributed's KV client or a filesystem store, so
  preemption commits ONE consistent fleet-wide save and a backend loss
  heals in lockstep across hosts instead of deadlocking the survivors.

Config: the ``resilience`` section of config.py; runbook: OUTAGES.md.
"""

from mx_rcnn_tpu.resilience.backend import (
    BackendUnavailableError,
    acquire_backend,
    classify_backend_error,
)
from mx_rcnn_tpu.resilience.heal import (
    DeferredSnapshot,
    HealCarry,
    Healer,
    compile_tree_copy,
    host_tree_copy,
)
from mx_rcnn_tpu.resilience.preempt import (
    RESUMABLE_RC,
    PreemptionExit,
    PreemptionGuard,
)
from mx_rcnn_tpu.resilience.quorum import (
    CoordinatedStop,
    FileKVStore,
    Quorum,
    QuorumError,
    QuorumExcludedError,
    QuorumOutcome,
)

__all__ = [
    "BackendUnavailableError",
    "acquire_backend",
    "classify_backend_error",
    "DeferredSnapshot",
    "HealCarry",
    "Healer",
    "compile_tree_copy",
    "host_tree_copy",
    "RESUMABLE_RC",
    "PreemptionExit",
    "PreemptionGuard",
    "CoordinatedStop",
    "FileKVStore",
    "Quorum",
    "QuorumError",
    "QuorumExcludedError",
    "QuorumOutcome",
]
