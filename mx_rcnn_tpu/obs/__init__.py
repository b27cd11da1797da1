"""graftscope — structured runtime telemetry for train/eval/bench.

The reference repo's only runtime signal is the Speedometer log line;
graftscope adds the machine-readable layer underneath it:

- ``events``:        typed append-only JSONL event stream (EventLog /
                     NullEventLog; schema = EVENT_TYPES)
- ``timing``:        StepTimer — per-iteration data-wait / dispatch /
                     step split (``place_ms`` and ``enqueue_ms`` inside
                     the dispatch), no host syncs added; owner of the
                     loop's spans on the profiler's clock
                     (``LOOP_SPANS``: ``train.next_batch``, a ``train``
                     step annotation holding ``train.key`` /
                     ``train.place`` / ``train.enqueue`` /
                     ``train.metrics``, and
                     ``train.checkpoint``)
- ``compile_track``: every XLA compile becomes a ``compile`` event with
                     the triggering batch-shape signature
- ``watchdog``:      StallWatchdog — a hung run emits a ``stall`` event
                     with stack dumps instead of dying as a bare rc=124
- ``report``:        ``python -m mx_rcnn_tpu.obs.report`` folds a run's
                     JSONL into a human summary + BENCH-compatible JSON

graftprof (this layer's profiling/cost pass) adds:

- ``costs``:         XLA ``cost_analysis``/``memory_analysis`` per
                     compiled shape bucket → ``cost`` events, computed
                     MFU, HBM footprint, padding-waste accounting
- ``profile``:       ``STAGES`` / ``stage`` — the step program's
                     ``jax.named_scope`` names (backbone … update);
                     programmatic jax.profiler capture windows
                     (``obs.trace_at_step``; stall-armed) folded by
                     stage → ``trace`` events carrying ``stages``
                     ({stage: device ms}) and ``unscoped_ms``
- ``ledger``:        ``python -m mx_rcnn_tpu.obs.ledger`` — append-only
                     cross-run perf history (bench_obs/history.jsonl) with a
                     regression-gating ``check`` subcommand

Enable on any training entry point with config overrides::

    --set obs.enabled=true --set obs.dir=runs/myrun

When disabled (the default) every surface degrades to a no-op sink and
the train hot path is unchanged. See the README's graftscope section for
the event schema.
"""

from __future__ import annotations

from mx_rcnn_tpu.obs.events import (
    EVENT_TYPES,
    EventLog,
    NullEventLog,
    env_fingerprint,
    event_log_path,
    open_event_log,
    run_meta_fields,
)
from mx_rcnn_tpu.obs.timing import StepTimer
from mx_rcnn_tpu.obs.watchdog import StallWatchdog

# NOTE: costs (CostTracker) and profile (TraceController) are NOT
# imported here — costs needs numpy, and the `python -m
# mx_rcnn_tpu.obs.report` / `...obs.ledger` CLIs promise a stdlib-only
# import chain (foldable on any machine the JSON is copied to). Import
# them from their submodules.

__all__ = [
    "EVENT_TYPES",
    "EventLog",
    "NullEventLog",
    "StallWatchdog",
    "StepTimer",
    "event_log_path",
    "obs_from_config",
    "open_event_log",
    "run_meta_fields",
]


def obs_from_config(cfg, default_dir: str = ""):
    """Config → sink: a real EventLog when ``cfg.obs.enabled`` (at
    ``cfg.obs.dir``, else ``default_dir``), the NullEventLog otherwise.
    The disabled path touches no filesystem and imports no jax."""
    if not cfg.obs.enabled:
        return NullEventLog()
    directory = cfg.obs.dir or default_dir
    if not directory:
        raise ValueError(
            "obs.enabled=true needs obs.dir (or a caller-provided run "
            "directory) to place this process's events_p<k>.jsonl")
    # Coordination identity, not raw jax: under the graftquorum
    # simulated-host tests each CPU process stamps (and names its
    # JSONL after) the host index it is standing in for, so the
    # report's per-host fold sees the fleet it would see on a pod.
    from mx_rcnn_tpu.parallel.distributed import process_index

    try:
        index = process_index()
    except RuntimeError:
        # The sink opens BEFORE backend acquisition (whose retries it
        # records): a backend that is not up yet cannot say who we are.
        index = 0
    return open_event_log(directory, process_index=index,
                          flush_every=cfg.obs.flush_every)
