"""Shared training driver — the `MutableModule.fit` analog.

Reference: the body of train_end2end.py::train_net (SURVEY.md §4.1): roidb
load → AnchorLoader → param init/resume → fit with metrics, Speedometer,
epoch checkpoints. All entry points (end2end, rpn-only, rcnn-only stages)
funnel through `fit_detector`.
"""

from __future__ import annotations

import itertools
import os
from typing import Callable, Dict, List, Optional, Union

import jax
import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.data.datasets import dataset_from_config
from mx_rcnn_tpu.data.datasets.imdb import filter_roidb, merge_roidb
from mx_rcnn_tpu.data.feedguard import FeedGuard
from mx_rcnn_tpu.data.loader import AnchorLoader
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.models.zoo import build_model, init_params
from mx_rcnn_tpu.obs import (
    StallWatchdog,
    StepTimer,
    obs_from_config,
    run_meta_fields,
)
from mx_rcnn_tpu.obs import compile_track
from mx_rcnn_tpu.obs import costs as obs_costs
from mx_rcnn_tpu.obs.costs import CostTracker
from mx_rcnn_tpu.obs.profile import TraceController
from mx_rcnn_tpu.parallel.mesh import (create_mesh, place_replicated,
                                       shard_batch)
from mx_rcnn_tpu.resilience import (
    CoordinatedStop,
    DeferredSnapshot,
    FileKVStore,
    HealCarry,
    Healer,
    PreemptionExit,
    PreemptionGuard,
    Quorum,
    QuorumExcludedError,
    acquire_backend,
    compile_tree_copy,
    host_tree_copy,
)
from mx_rcnn_tpu.resilience import chaos
from mx_rcnn_tpu.resilience import quorum as quorum_lib
from mx_rcnn_tpu.targets.rcnn_targets import fg_rois_per_image
from mx_rcnn_tpu.train.callback import Speedometer
from mx_rcnn_tpu.train.checkpoint import (
    checkpoint_meta,
    latest_checkpoint,
    latest_epoch,
    load_checkpoint,
    save_checkpoint,
)
from mx_rcnn_tpu.train.metrics import MetricBag
from mx_rcnn_tpu.train.optimizer import build_optimizer, rebase_schedule_count
from mx_rcnn_tpu.train.step import create_train_state, make_train_step


def load_gt_roidbs(cfg: Config, image_set: Optional[str] = None,
                   flip: Optional[bool] = None) -> List[Dict]:
    """'07_trainval+12_trainval'-style multi-set load (reference:
    rcnn/utils/load_data.py::load_gt_roidb + merge_roidb)."""
    image_set = image_set or cfg.dataset.image_set
    flip = cfg.train.flip if flip is None else flip
    roidbs = []
    for s in image_set.split("+"):
        ds = dataset_from_config(cfg.dataset, s)
        roidb = ds.gt_roidb()
        if flip:
            roidb = ds.append_flipped_images(roidb)
        roidbs.append(roidb)
    return filter_roidb(merge_roidb(roidbs))


def fit_detector(
    cfg: Config,
    roidb: List[Dict],
    prefix: str,
    begin_epoch: int = 0,
    end_epoch: Optional[int] = None,
    frequent: int = 20,
    resume: Union[bool, str] = False,
    pretrained_params=None,
    pretrained_npz: Optional[str] = None,
    mesh_spec: Optional[str] = None,
    seed: int = 0,
    epoch_callback: Optional[Callable] = None,
    forward_fn=None,
    loader_factory: Optional[Callable] = None,
    fixed_param_patterns=None,
    checkpoint_period: int = 1,
):
    """Train loop. Returns the final (host) params tree.

    forward_fn selects the training graph (end2end default; rpn-only /
    rcnn-only for the alternate stages); loader_factory builds the data
    iterator (AnchorLoader default, ROIIter for Fast R-CNN);
    fixed_param_patterns extends the frozen set (alternate stages 4/6 freeze
    the shared conv trunk — reference train_alternate.py).

    resume: True resumes from the latest EPOCH-BOUNDARY checkpoint under
    prefix (the pre-graftguard contract); "auto" also considers graftguard
    emergency (dispatch-tagged) saves and resumes from the most-advanced
    point, skipping the already-trained dispatch prefix of the interrupted
    epoch (the skipped batches are still loaded and discarded — host work
    only, bounded by one epoch). Resume is bit-exact vs an uninterrupted
    run: the epoch batch order is a pure function of (seed, epoch)
    (AnchorLoader.set_epoch) and each dispatch's rng key is derived from
    its global index (fold_in), not from a run-position-dependent split
    chain.

    graftguard (cfg.resilience; runbook OUTAGES.md): the backend is
    acquired through classified retry-with-backoff before the first
    device touch, and SIGTERM/SIGINT are honored at the next step
    boundary — emergency checkpoint (resilience.preempt_save), `preempt`
    event, then PreemptionExit carrying RESUMABLE_RC (75).

    graftheal (resilience/heal.py; resilience.heal, default on): a
    TRANSIENT step-time backend loss no longer kills the run — the loop
    captures the last known-good host state in memory, re-acquires the
    backend under resilience.backend_deadline_s, rebuilds the session
    (mesh, partition specs) and continues, emitting
    a `heal` event. If the backend returns with fewer devices the data
    axis is re-cut to the largest batch-divisible size — the GLOBAL
    batch is invariant, so the loader stream, LR schedule and loss
    trajectory carry straight across the shrink. Checkpoints carry a
    topology sidecar (graft_meta.json) so `--resume auto` onto a
    DIFFERENT device count recomputes the dispatch skip through the
    images-consumed invariant.

    With train.async_checkpoint (default, single-process) the epoch-end
    save is enqueued, not durable, when epoch_callback runs — a callback
    that READS the just-saved checkpoint from disk must not assume it has
    landed (it is durable by the next epoch's save and before return).

    epoch_callback(epoch, state, bag): the state is the loop's TrainState
    (`.step`, `.params`, `.opt_state`). A graftheal recovery that lands
    inside the epoch-end window REPLAYS it (event, save, callback) rather
    than dropping it — callbacks should tolerate a rare re-invocation
    for the same epoch.
    """
    from mx_rcnn_tpu.parallel.distributed import (
        is_primary,
        local_data_shards,
        process_count,
        process_index,
    )
    from mx_rcnn_tpu.train import precision

    # graftcast: resolve (and validate, loudly, before any device work)
    # the run's compute-dtype policy — threaded into run_meta and the
    # cost tracker so every MFU downstream divides by the right peak.
    policy = precision.policy_of(cfg)
    end_epoch = end_epoch or cfg.train.end_epoch
    # graftscope sink FIRST (it touches no jax): backend acquisition below
    # wants somewhere to emit backend_retry/backend_up events, so an
    # outage ridden out here leaves a structured record, not a watch log.
    obs_log = obs_from_config(cfg, default_dir=f"{prefix}.obs")
    # graftpulse flight recorder: every emitted record also lands in a
    # last-K in-memory ring, dumped to <obs dir>/flight_<reason>.json on
    # anomaly/stall/heal/preempt/crash — attached before backend
    # acquisition so even startup retries ride the ring.
    recorder = None
    if obs_log.enabled:
        from mx_rcnn_tpu.obs.health import FlightRecorder

        recorder = FlightRecorder(os.path.dirname(obs_log.path),
                                  capacity=cfg.obs.flight_events)
        obs_log.attach_ring(recorder)
    if cfg.resilience.backend_acquire:
        # Classified retry-with-backoff before the first device touch —
        # a transiently unavailable backend delays the run instead of
        # killing it (resilience/backend.py).
        acquire_backend(cfg.resilience, elog=obs_log)
    mesh = create_mesh(mesh_spec or cfg.mesh.mesh_shape)
    n_data = mesh.shape["data"]
    # Each process feeds only its own slice of the data axis (multi-host:
    # parallel/distributed.py; single-process: n_local == n_data).
    n_local = local_data_shards(mesh)
    # The run's NOMINAL footprint — what graftheal's elastic re-shard
    # derives the post-loss mesh from (parallel/partition.py).
    d0, m0 = n_data, mesh.shape["model"]
    logger.info("mesh: %s (data=%d model=%d, %d local shards)",
                mesh.devices.shape, n_data, mesh.shape["model"], n_local)

    if fixed_param_patterns is not None:
        from dataclasses import replace as _replace
        cfg = cfg.with_updates(network=_replace(
            cfg.network,
            fixed_param_patterns=tuple(cfg.network.fixed_param_patterns)
            + tuple(fixed_param_patterns)))

    model = build_model(cfg, mesh=mesh)  # mesh: ring attention for ViTDet
    params = pretrained_params or init_params(
        model, cfg, jax.random.PRNGKey(seed))
    if pretrained_npz:
        # ImageNet manifest init (reference: load_param over .params —
        # utils/pretrained.py). Trunk leaves come from the npz; the new
        # heads keep the fresh init above.
        from mx_rcnn_tpu.utils.pretrained import import_pretrained
        params, _ = import_pretrained(pretrained_npz, params)
    # Gradient accumulation: the step consumes accum x batch_images images
    # per optimizer step (train/step.py micro-step scan), so the LOADER
    # yields that much; the model/step cfg keeps the per-micro-step size.
    accum = max(1, cfg.train.grad_accum_steps)
    loader_cfg = cfg
    if accum > 1:
        from dataclasses import replace as _replace
        loader_cfg = cfg.with_updates(train=_replace(
            cfg.train, batch_images=cfg.train.batch_images * accum))
        if cfg.image.canvas_pack and not cfg.image.canvas_images:
            # graftcanvas × grad accum: planes stay one per MICRO-step
            # (images per plane = the un-accumulated batch) so the
            # step's accum reshape slices whole planes per chunk.
            loader_cfg = loader_cfg.with_updates(image=_replace(
                loader_cfg.image, canvas_images=cfg.train.batch_images))
        elif (cfg.image.canvas_pack
              and cfg.train.batch_images % cfg.image.canvas_images):
            # A user-set canvas_images that doesn't divide the MICRO
            # batch would pass the loader's validate (which sees the
            # accumulated batch) and then die as an opaque reshape error
            # inside the jitted accum split — fail loudly here instead.
            raise ValueError(
                f"image.canvas_images={cfg.image.canvas_images} must "
                f"divide the un-accumulated train.batch_images="
                f"{cfg.train.batch_images} under grad_accum_steps="
                f"{accum}: each micro-step must consume whole planes")
    if cfg.image.canvas_pack:
        # Fail fast (cfg-contract): surface a mis-sized canvas or an
        # unsupported family here, before prefetch workers spin up (the
        # loader validates too, but a worker-thread raise is noisier).
        from mx_rcnn_tpu.data.canvas import validate_canvas_pack

        validate_canvas_pack(loader_cfg)

    # graftfeed (data/feedguard.py; cfg.data): ONE guard per run, built
    # before the first loader and shared across every heal-time /
    # elastic rebuild below — the quarantine set and worker-death budget
    # are run-scoped, not loader-scoped. Chaos comes up here too (not at
    # the session loop) because the input plane has injection sites of
    # its own now.
    chaos_spec = chaos.from_env()
    feed_guard = FeedGuard(
        cfg.data, n_records=len(roidb), seed=seed,
        elog=obs_log if obs_log.enabled else None,
        quarantine_path=(os.path.join(os.path.dirname(obs_log.path),
                                      "quarantine.jsonl")
                         if obs_log.enabled else ""),
        # --resume / --resume auto re-applies the interrupted run's
        # quarantine file, so the resumed stream sees the SAME
        # substitutions at the same positions (bit-exact parity).
        resume=bool(resume),
        chaos_spec=chaos_spec if chaos_spec.active else None)

    def _build_loader(n_shards: int):
        """Loader for ``n_shards`` data shards. Factored out because the
        session loop rebuilds it under ``resilience.elastic_mode=rescale``
        (the global batch scales with the surviving fleet). Data sharding
        stays on RAW ``jax.process_count``/``process_index`` on purpose:
        the graftquorum simulated hosts override coordination identity
        only, and each sim process must load the full global batch to
        keep trajectories bit-identical (parallel/distributed.py)."""
        if loader_factory is None:
            return AnchorLoader(roidb, loader_cfg, num_shards=n_shards,
                                seed=seed,
                                process_count=jax.process_count(),
                                process_index=jax.process_index(),
                                guard=feed_guard)
        import inspect

        params_of = inspect.signature(loader_factory).parameters
        if "process_count" in params_of or any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                for p in params_of.values()):
            return loader_factory(roidb, loader_cfg, n_shards,
                                  process_count=jax.process_count(),
                                  process_index=jax.process_index())
        if jax.process_count() > 1:
            raise ValueError(
                "loader_factory must accept process_count/process_index "
                "kwargs to run multi-host")
        return loader_factory(roidb, loader_cfg, n_shards)

    loader = _build_loader(n_local)
    steps_per_epoch = max(len(loader), 1)

    # Global images per dispatch — the run's INVARIANT unit of progress.
    # graftheal keeps it fixed across an elastic shrink (the surviving
    # devices carry more rows each), and the checkpoint meta sidecar
    # records it so a resume onto a different topology can convert a
    # dispatch tag minted under another mesh (see below).
    ipd = cfg.train.batch_images * accum * n_data

    # Resume discovery BEFORE building the optimizer: a restored opt_state
    # carries optax's schedule counter; without one the LR schedule is
    # offset by begin_step instead (never both — that would double-count).
    # resume=True sees epoch-boundary checkpoints only; resume="auto"
    # (graftguard) also picks up dispatch-tagged emergency saves and
    # restarts mid-epoch from the most-advanced point.
    resume_epoch = resume_dispatch = None
    if resume == "auto":
        found = latest_checkpoint(prefix)
        if found is not None:
            resume_epoch, resume_dispatch = found
    elif resume:
        resume_epoch = latest_epoch(prefix)
    skip_dispatch = resume_dispatch or 0
    opt_state = None
    if resume_epoch is not None:
        begin_epoch = resume_epoch
        tx_tmpl = build_optimizer(cfg, params, steps_per_epoch)
        params, opt_state = load_checkpoint(
            prefix, resume_epoch, dispatch=resume_dispatch,
            template={"params": params},
            opt_state_template=tx_tmpl.init(params),
            means=cfg.train.bbox_means, stds=cfg.train.bbox_stds,
            num_classes=cfg.dataset.num_classes)
        # Elastic resume (graftheal): the meta sidecar records the SAVING
        # run's topology. When it differs from this run's, two
        # conversions apply — to boundary checkpoints and dispatch-0
        # emergency saves just as much as to mid-epoch ones:
        meta = checkpoint_meta(prefix, resume_epoch, resume_dispatch)
        old_ipd = (meta or {}).get("images_per_dispatch")
        if old_ipd and old_ipd != ipd:
            if skip_dispatch:
                # (1) A dispatch tag counts dispatches AT THE SAVING
                # RUN'S global batch — convert through the invariant,
                # images consumed, so the trained prefix of the epoch is
                # skipped exactly (floor: a non-divisible remainder
                # re-trains up to one old dispatch rather than skipping
                # unseen images).
                images_done = skip_dispatch * int(old_ipd)
                skip_dispatch = images_done // ipd
                logger.warning(
                    "elastic resume: checkpoint was saved at %d images/"
                    "dispatch (device_count=%s), this run dispatches %d — "
                    "skip recomputed to %d dispatch(es) (%d of %d images"
                    "%s)", old_ipd, (meta or {}).get("device_count", "?"),
                    ipd, skip_dispatch, skip_dispatch * ipd, images_done,
                    "" if images_done % ipd == 0 else
                    f"; {images_done % ipd} image(s) re-trained")
            if opt_state is not None:
                # (2) The restored schedule/Adam counters are in the
                # SAVING run's step units; this run counts against ITS
                # steps_per_epoch and schedule — rebase, or every
                # warmup/decay read happens at the old run's position
                # (train/optimizer.py).
                opt_state = rebase_schedule_count(
                    opt_state,
                    begin_epoch * steps_per_epoch + skip_dispatch)
                logger.warning(
                    "elastic resume: optimizer counters rebased to step "
                    "%d (this run's units)",
                    begin_epoch * steps_per_epoch + skip_dispatch)
        logger.info("resumed from %s epoch %d%s (opt_state %s)", prefix,
                    resume_epoch,
                    f" dispatch {resume_dispatch}"
                    if resume_dispatch is not None else "",
                    "restored" if opt_state is not None
                    else "reinitialized")

    # The session carry: host-side (params, opt_state, position) every
    # device-facing object is (re)built from — initially the fresh/
    # resumed state above, then whatever graftheal captured. opt_state
    # None => fresh slots, LR schedule offset by begin_step instead.
    carry = HealCarry(params=params, opt_state=opt_state,
                      epoch=begin_epoch, dispatch=skip_dispatch)

    # graftscope telemetry (mx_rcnn_tpu/obs): the sink was opened at the
    # top of this function (backend acquisition emits through it); a
    # no-op unless cfg.obs.enabled — nothing added to the hot loop.
    watchdog = tracer = cost_tracker = None
    if obs_log.enabled:
        obs_log.emit("run_meta", **run_meta_fields(
            cfg, mesh=mesh, prefix=prefix, batch_size=ipd,
            steps_per_epoch=steps_per_epoch, begin_epoch=begin_epoch,
            end_epoch=end_epoch, grad_accum=accum,
            compute_dtype=policy.short))
        if cfg.obs.track_compiles:
            compile_track.activate(obs_log)
        # graftprof: trace windows (obs.trace_at_step counts dispatches
        # completed THIS process — also stall-armed by the watchdog) and
        # per-shape-bucket XLA cost events for the computed MFU.
        tracer = TraceController(
            obs_log, os.path.join(os.path.dirname(obs_log.path), "trace"),
            trace_at_step=cfg.obs.trace_at_step,
            trace_steps=cfg.obs.trace_steps)
        if cfg.obs.cost_analysis:
            # The MFU's denominator is the published peak of THIS device
            # in THIS dtype (obs/costs.py::PEAKS); where none is
            # published (a CPU run, an f32 step) the cost events carry
            # no peak and the report no MFU.
            try:
                peak = obs_costs.peak_flops_for(
                    mesh.devices.flat[0].device_kind, policy.compute)
            except obs_costs.UnknownPeakError as exc:
                logger.info("no MFU for this run: %s", exc)
                peak = None
            cost_tracker = CostTracker(obs_log, peak_flops=peak,
                                       compute_dtype=policy.short)
        if cfg.obs.watchdog:
            watchdog = StallWatchdog(
                obs_log, stall_factor=cfg.obs.stall_factor,
                min_stall_s=cfg.obs.stall_min_s,
                poll_s=cfg.obs.watchdog_poll_s, tracer=tracer,
                recorder=recorder,
                heartbeat_every_s=cfg.obs.heartbeat_every_s)
            watchdog.start()
    timer = StepTimer(obs_log, watchdog=watchdog,
                      enrich=obs_costs.step_fields if obs_log.enabled
                      else None)
    speedometer = Speedometer(ipd, frequent, event_log=obs_log)

    # graftquorum (resilience/quorum.py): the coordination layer every
    # multi-host resilience path below rides. A preempted fleet drains to
    # ONE agreed dispatch boundary before the leader publishes; a healed
    # fleet agrees the post-heal topology and rebuilds in lockstep. The
    # store is jax.distributed's KV service on a real pod, or a shared
    # filesystem directory (resilience.quorum_store_dir) — which is also
    # how the N-process CPU tests exercise the real protocol.
    n_hosts = process_count()
    quorum = stopper = None
    if n_hosts > 1:
        if cfg.resilience.quorum_store_dir:
            store = FileKVStore(cfg.resilience.quorum_store_dir)
        else:
            client = quorum_lib.jax_kv_client()
            store = (quorum_lib.JaxKVStore(client)
                     if client is not None else None)
        if store is None:
            logger.warning(
                "graftquorum: no KV store reachable (jax.distributed not "
                "initialized and resilience.quorum_store_dir unset) — "
                "multi-host coordination disabled; preemption and heal "
                "fall back to uncoordinated per-host behavior")
        else:
            quorum = Quorum(
                store, process_index(), n_hosts,
                timeout_s=cfg.resilience.quorum_timeout_s,
                min_fraction=cfg.resilience.quorum_min_fraction,
                # grafttower: every barrier leaves a typed `barrier`
                # event in this host's stream (wait attribution + the
                # fleet fold's clock-skew correction signal). Host-side
                # only — no device work rides a barrier.
                elog=obs_log if obs_log.enabled else None)
            stopper = CoordinatedStop(quorum)
            logger.info(
                "graftquorum: host %d/%d coordinating via %s",
                process_index(), n_hosts,
                "filesystem store" if cfg.resilience.quorum_store_dir
                else "jax.distributed KV client")

    # Async epoch-end saves (train/checkpoint.py CheckpointWriter); the
    # multi-host primary-only pattern needs the synchronous path (orbax's
    # cross-process commit barrier would hang with one caller).
    writer = None
    if cfg.train.async_checkpoint and n_hosts > 1:
        # LOUD fallback (graftquorum satellite): silently dropping the
        # requested async writer made multi-host epoch ends mysteriously
        # slower than single-host. One structured `checkpoint` record
        # with fallback="sync" says what happened and why.
        logger.warning(
            "train.async_checkpoint requested but process_count()=%d: "
            "falling back to SYNCHRONOUS epoch saves (the async writer "
            "cannot satisfy orbax's cross-process commit barrier under "
            "the primary-only save pattern)", n_hosts)
        if obs_log.enabled:
            obs_log.emit("checkpoint", fallback="sync",
                         reason="multi-host: async writer incompatible "
                                "with primary-only saves",
                         hosts=n_hosts)
    elif cfg.train.async_checkpoint:
        from mx_rcnn_tpu.train.checkpoint import CheckpointWriter

        writer = CheckpointWriter()

    # graftguard preemption (resilience/preempt.py): the handlers only
    # RECORD the signal; the loop below honors it at step boundaries.
    # install() is a no-op off the main thread (the guard stays inert).
    guard = None
    if cfg.resilience.preempt_handlers:
        guard = PreemptionGuard()
        guard.install()

    # graftheal (resilience/heal.py): a transient step-time backend loss
    # is recovered IN-PROCESS — capture the last known-good host state,
    # tear down + re-acquire the backend under the deadline, rebuild the
    # session (possibly on fewer devices) and continue. The initial
    # fallback is a host-owned copy of the starting state, refreshed by
    # periodic snapshots (deferred reads: _begin_snapshot) and by every
    # successful capture.
    healer = None
    if cfg.resilience.heal and n_hosts > 1 and quorum is None:
        # Multi-host heal NEEDS the quorum: one process tearing its
        # backend down mid-collective wedges the others unless every
        # survivor re-converges on an agreed post-heal topology. Without
        # a reachable KV store, stay inert — preemption + --resume auto
        # still covers the fleet case.
        logger.warning("resilience.heal under process_count()=%d needs "
                       "graftquorum coordination but no KV store is "
                       "reachable; heal disabled", n_hosts)
    elif cfg.resilience.heal:
        healer = Healer(cfg.resilience, elog=obs_log, watchdog=watchdog,
                        recorder=recorder)
        healer.set_fallback(HealCarry(
            params=host_tree_copy(carry.params),
            opt_state=host_tree_copy(carry.opt_state),
            epoch=carry.epoch, dispatch=carry.dispatch))
        if quorum is not None:
            from mx_rcnn_tpu.parallel.partition import elastic_mesh_spec

            heal_generation = itertools.count()

            def _heal_quorum(devices):
                """graftquorum heal round, run INSIDE Healer.recover
                right after this host re-acquired its backend: survivors
                rendezvous under the deadline, the leader seals the
                post-heal topology from the MINIMUM surviving capacity,
                and a host that misses the round is excluded (it raises
                QuorumExcludedError out of recover — caught below and
                turned into a resumable exit)."""
                outcome = quorum.heal_round(
                    next(heal_generation), len(devices),
                    lambda n_dev, n_arrived: elastic_mesh_spec(
                        d0, m0, n_dev, cfg.train.batch_images * n_data,
                        mode=cfg.resilience.elastic_mode))
                if obs_log.enabled:
                    obs_log.emit("quorum", kind="heal",
                                 generation=outcome.generation,
                                 hosts=sorted(outcome.arrived),
                                 excluded=sorted(outcome.excluded),
                                 devices=outcome.devices,
                                 spec=outcome.spec)
                return outcome

            healer.quorum_hook = _heal_quorum

    # Per-session device-facing objects, (re)assigned by the session loop
    # below; declared here so the closures and the return path see them.
    state = bag = copy_state = None
    pos = (carry.epoch, carry.dispatch)
    # Coordinated-stop latch: this host has published its preemption
    # request to the quorum (at most one request per run — the agreed
    # boundary is cached by CoordinatedStop.check thereafter).
    stop_requested = False
    # One `rpn_targets` event a run, for pyramid families one `roi_levels`
    # and with the mask branch one `mask_rois` (obs.enabled): read from the
    # first dispatch's metrics, set-up's one wait for a dispatch.
    first_dispatch_due = obs_log.enabled

    def _first_dispatch_counters(epoch: int, i: int, metrics):
        """The counter events of the first dispatch's metrics (a wait for
        that dispatch: set-up's, once a run)."""
        if "RpnTargetCounts" in metrics:
            walked, padded, kept_pos, kept_neg = (
                round(float(c)) for c in metrics["RpnTargetCounts"])
            obs_log.emit("rpn_targets", epoch=epoch, dispatch=i + 1,
                         slots_walked=walked, slots_padded=padded,
                         kept_pos=kept_pos, kept_neg=kept_neg)
            logger.info("anchor labelling at dispatch %d: walked %d of %d "
                        "gt slots, kept %d positives and %d negatives",
                        i + 1, walked, padded, kept_pos, kept_neg)
        if "RoiLevelShare" in metrics:
            share = [round(float(s), 4) for s in metrics["RoiLevelShare"]]
            *canvas, poolings = (round(float(c))
                                 for c in metrics["RoiPoolingForm"])
            obs_log.emit("roi_levels", epoch=epoch, dispatch=i + 1,
                         share=share, canvas=canvas, poolings=poolings)
            logger.info("sampled rois by pyramid level (P2..P5) at "
                        "dispatch %d, each pooled %d time(s) a call from a "
                        "canvas of %dx%d cells: %s", i + 1, poolings,
                        *canvas, share)
        if "MaskRoiCounts" in metrics:
            counts = [float(c) for c in metrics["MaskRoiCounts"]]
            live = max(sum(counts[3:]), 1.0)
            mask_rois = dict(
                slots=fg_rois_per_image(cfg.train.batch_rois,
                                        cfg.train.fg_fraction),
                per_image_min=round(counts[0]),
                per_image_mean=round(counts[1], 2),
                per_image_max=round(counts[2]),
                share=[round(c / live, 4) for c in counts[3:]])
            obs_log.emit("mask_rois", epoch=epoch, dispatch=i + 1,
                         **mask_rois)
            logger.info("mask branch at dispatch %d: %s", i + 1, mask_rois)

    def _ckpt_meta(at_epoch: int, at_dispatch: Optional[int],
                   hosts=None):
        """The topology sidecar (train/checkpoint.py::META_NAME): what a
        dispatch WAS when this checkpoint was cut, so an elastic resume
        can convert the tag (see the skip recompute above). Multi-host
        runs also record the PARTICIPATING host set against the expected
        count — latest_checkpoint refuses an emergency save whose host
        set is incomplete (a torn save: some host died before reaching
        the publication barrier)."""
        meta = {"epoch": at_epoch, "dispatch": at_dispatch,
                "images_per_dispatch": ipd,
                "steps_per_epoch": steps_per_epoch,
                "device_count": int(mesh.devices.size),
                "mesh": {a: int(s) for a, s in
                         zip(mesh.axis_names, mesh.devices.shape)}}
        if n_hosts > 1:
            active = (quorum.active if quorum is not None
                      else range(n_hosts))
            meta["host_count"] = len(tuple(active))
            meta["hosts"] = sorted(hosts if hosts is not None else active)
        return meta

    def _capture() -> HealCarry:
        """graftheal's in-memory emergency capture: the live train state
        as host-OWNED copies (np.array, never device views —
        the backend they came from is about to be torn down), tagged
        with its position and the drained metric sums. BLOCKING: it waits
        for the newest dispatch, then reads leaf by leaf. For the callers
        that need the live state now (Healer.recover, HealthMonitor); the
        loop's periodic snapshot is _begin_snapshot."""
        if state is None:
            raise RuntimeError("no live state to capture yet")
        cap_params = host_tree_copy(state.params)
        cap_opt = host_tree_copy(state.opt_state)
        if sched_begin:
            # This session's optimizer was built FRESH with its schedule
            # offset by begin_step, so its counters are session-relative
            # (they started at 0 mid-run). The carry contract is
            # ABSOLUTE counters — rebuilds use begin_step=0 whenever an
            # opt_state is present — so normalize to the capture
            # position (== sched_begin + updates this session).
            cap_opt = rebase_schedule_count(
                cap_opt, pos[0] * steps_per_epoch + pos[1])
        return HealCarry(params=cap_params, opt_state=cap_opt,
                         epoch=pos[0], dispatch=pos[1],
                         bag=bag.snapshot() if bag is not None else None)

    def _begin_snapshot() -> DeferredSnapshot:
        """_capture as a deferred read, for the loop's periodic snapshot:
        ONE device program enqueued behind the step just dispatched copies
        parameters and optimizer state into buffers the next (donating)
        step does not own, their host transfers start, and the position,
        the schedule rebase and the bag as they stand NOW ride along.
        Healer.poll_snapshot installs it at a later dispatch, once the
        values are there; the loop never waits on its newest dispatch."""
        count = pos[0] * steps_per_epoch + pos[1]
        return DeferredSnapshot(
            copy_state((state.params, state.opt_state)),
            epoch=pos[0], dispatch=pos[1], bag=bag.fork(),
            # session-relative counters -> absolute, as in _capture
            rebase=((lambda opt: rebase_schedule_count(opt, count))
                    if sched_begin else None))

    def _honor_preemption(at_epoch: int, at_dispatch: Optional[int],
                          need_save: bool = True):
        """Orderly preemption exit: emergency checkpoint (sync — it must
        be durable before the process dies), `preempt` event, then
        PreemptionExit carrying the resumable rc. at_dispatch=None marks
        an epoch boundary (at_epoch epochs complete).

        Multi-host (graftquorum): every host drained to the agreed stop
        boundary before getting here, and the fleet BARRIERS before the
        leader publishes — so the one emergency save is cut from a state
        every participant reached, and its meta records exactly who was
        still alive (`hosts`). A host missing from that set marks the
        save torn; latest_checkpoint skips it on resume."""
        arrived = None
        if quorum is not None:
            arrived = quorum.barrier("preempt/stop")
            if obs_log.enabled:
                obs_log.emit("quorum", kind="preempt",
                             hosts=sorted(arrived),
                             excluded=sorted(quorum.active - arrived),
                             agreed=[at_epoch, at_dispatch])
        saved = None
        if need_save and cfg.resilience.preempt_save and is_primary():
            saved = save_checkpoint(
                prefix, at_epoch, state.params, state.opt_state,
                means=cfg.train.bbox_means, stds=cfg.train.bbox_stds,
                num_classes=cfg.dataset.num_classes, dispatch=at_dispatch,
                meta=_ckpt_meta(at_epoch, at_dispatch, hosts=arrived))
        # signum is None on a host that was never signaled itself but is
        # draining to the fleet's agreed boundary (coordinated stop).
        signum = guard.signum if guard is not None else None
        if obs_log.enabled:
            obs_log.emit("preempt", signal=signum,
                         step=at_epoch * steps_per_epoch + (at_dispatch or 0),
                         saved=saved)
        if recorder is not None:
            recorder.dump("preempt")
        logger.warning("preempted (signal %s) at epoch %d dispatch %s — "
                       "exiting rc %d; restart with --resume auto",
                       signum, at_epoch, at_dispatch,
                       PreemptionExit().code)
        raise PreemptionExit(signum)

    # graftpulse (obs/health.py + train/health.py): with obs on and
    # obs.health_every > 0 the step returns an extra in-graph numerics
    # output (same executable, no added per-step sync); the monitor
    # folds it into `health` events at the cadence and turns anomalies
    # into action — anomaly event, trace window, emergency checkpoint
    # of the last known-good state, flight dump, then NumericsAnomaly
    # under the default health_action=abort (resume with --resume auto).
    monitor = None
    health_on = obs_log.enabled and cfg.obs.health_every > 0
    if health_on:
        from mx_rcnn_tpu.obs.health import HealthMonitor

        def _save_good(good):
            """Emergency checkpoint of the monitor's known-good carry —
            the graftguard dispatch-tagged shape, so `--resume auto`
            picks it up like any preemption save."""
            if not is_primary():
                return None
            return save_checkpoint(
                prefix, good.epoch, good.params, good.opt_state,
                means=cfg.train.bbox_means, stds=cfg.train.bbox_stds,
                num_classes=cfg.dataset.num_classes,
                dispatch=good.dispatch,
                meta=_ckpt_meta(good.epoch, good.dispatch))

        monitor = HealthMonitor(
            obs_log, every=cfg.obs.health_every,
            window=cfg.obs.health_window,
            grad_factor=cfg.obs.health_grad_factor,
            loss_z=cfg.obs.health_loss_z,
            action=cfg.obs.health_action,
            tracer=tracer, recorder=recorder,
            capture=_capture if cfg.obs.health_checkpoint else None,
            save=_save_good if cfg.obs.health_checkpoint else None)

    try:
        while True:  # one iteration per backend session; graftheal re-enters
            try:
                state = bag = copy_state = None
                pos = (carry.epoch, carry.dispatch)
                if cost_tracker is not None:
                    # New session, possibly a new per-device program
                    # (elastic re-mesh keeps the GLOBAL batch shape, so
                    # the bucket key alone would dedup a now-stale cost)
                    cost_tracker.reset()
                if healer is not None:
                    if healer.devices is not None:
                        # Re-acquired backend, possibly smaller: re-cut
                        # the mesh (model axis kept, data axis re-derived
                        # — global batch invariant under the default
                        # shrink mode, so the loader and the schedule
                        # carry straight across) and re-derive everything
                        # device-facing against it.
                        from mx_rcnn_tpu.parallel.partition import (
                            elastic_mesh_spec)

                        if healer.outcome is not None:
                            # graftquorum: adopt the AGREED topology —
                            # every surviving host rebuilds in lockstep
                            # on the spec the heal round sealed (derived
                            # from the MINIMUM re-acquired capacity
                            # across the quorum), not its own local view.
                            respec = healer.outcome.spec
                        else:
                            respec = elastic_mesh_spec(
                                d0, m0, len(healer.devices),
                                cfg.train.batch_images * n_data,
                                mode=cfg.resilience.elastic_mode)
                        mesh = create_mesh(respec, devices=healer.devices)
                        model = build_model(cfg, mesh=mesh)
                        logger.info(
                            "graftheal: session rebuilt on mesh %s "
                            "(%d device(s))", dict(zip(
                                mesh.axis_names,
                                (int(s) for s in mesh.devices.shape))),
                            int(mesh.devices.size))
                        if (cfg.resilience.elastic_mode == "rescale"
                                and (cfg.train.batch_images * n_data)
                                % mesh.shape["data"]):
                            # RESCALE (elastic phase 2): the agreed data
                            # axis cannot carry the nominal global batch
                            # (not a divisor) — too-deep shrink or odd
                            # grow. Keep rows-per-device constant and let
                            # the GLOBAL batch scale with the fleet:
                            # rebuild the loader for the new shard count,
                            # re-derive the progress units, and rebase
                            # the carry position + schedule counters
                            # through the invariant (images consumed).
                            new_data = mesh.shape["data"]
                            old_ipd_live = ipd
                            if hasattr(loader, "close"):
                                loader.close()
                            n_local = local_data_shards(mesh)
                            loader = _build_loader(n_local)
                            steps_per_epoch = max(len(loader), 1)
                            ipd = (cfg.train.batch_images * accum
                                   * new_data)
                            images_done = carry.dispatch * old_ipd_live
                            carry.dispatch = images_done // ipd
                            if carry.opt_state is not None:
                                carry.opt_state = rebase_schedule_count(
                                    carry.opt_state,
                                    carry.epoch * steps_per_epoch
                                    + carry.dispatch)
                            logger.warning(
                                "elastic rescale: global batch now %d "
                                "image(s)/dispatch (was %d); LR schedule "
                                "rebased to step %d — the batch-size "
                                "change makes bit-exactness with the "
                                "nominal run impossible by construction",
                                ipd, old_ipd_live,
                                carry.epoch * steps_per_epoch
                                + carry.dispatch)
                    healer.note_devices(int(mesh.devices.size),
                                        mesh.devices.flat[0].platform)

                # Optimizer/state from the carry: a restored opt_state
                # brings optax's schedule counter; a fresh one offsets
                # the schedule by begin_step instead (never both).
                b_epoch, b_skip = carry.epoch, carry.dispatch
                sched_begin = (0 if carry.opt_state is not None
                               else b_epoch * steps_per_epoch + b_skip)
                tx = build_optimizer(cfg, carry.params, steps_per_epoch,
                                     begin_step=sched_begin)
                state = create_train_state(carry.params, tx)
                if carry.opt_state is not None:
                    state = state.replace(opt_state=carry.opt_state)
                if b_epoch or b_skip:
                    state = state.replace(
                        step=jax.numpy.asarray(
                            b_epoch * steps_per_epoch + b_skip,
                            jax.numpy.int32))

                # Partition specs are RE-DERIVED against the session's
                # mesh — after an elastic shrink the same rules bind to
                # the new model/data axes (parallel/partition.py).
                param_specs = None
                if cfg.network.tensor_parallel:
                    if ("model" in mesh.axis_names
                            and mesh.shape["model"] > 1):
                        from mx_rcnn_tpu.parallel.partition import (
                            shard_train_state, tp_param_specs)

                        param_specs = tp_param_specs(state.params)
                        state = shard_train_state(state, mesh, param_specs)
                    else:
                        logger.warning(
                            "network.tensor_parallel ignored: mesh model "
                            "axis is 1 (build the mesh as '<data>x"
                            "<model>', e.g. --tpu-mesh 4x2)")

                if param_specs is None:
                    # One compile of the train step, not two
                    # (parallel/mesh.py::place_replicated); a TP state
                    # was placed by shard_train_state above.
                    state = place_replicated(state, mesh)

                # Donation on the CPU backend is OFF: a session rebuilt
                # from HOST numpy trees (checkpoint restore, heal carry)
                # feeds numpy-backed arrays into a donating step, and CPU
                # zero-copy + donation writes into/frees memory numpy
                # owns (observed in the heal shrink gate as 1e18 losses
                # one dispatch after the heal, or a segfault). Donation
                # is an HBM-footprint optimization — on the host-memory
                # backend correctness wins. TPU keeps it.
                donate = jax.default_backend() != "cpu"
                step_fn = make_train_step(model, cfg, mesh=mesh,
                                          donate=donate,
                                          forward_fn=forward_fn,
                                          param_specs=param_specs,
                                          health=health_on)
                # Per-dispatch rng keys are derived from the dispatch's
                # GLOBAL index (fold_in), not a run-position-dependent
                # split chain — so a resumed/healed run consumes exactly
                # the keys the uninterrupted run would have (the
                # kill→resume bit-exactness gate), at O(1) resume cost.
                rng = jax.random.PRNGKey(seed + 1)

                for epoch in range(b_epoch, end_epoch):
                    if hasattr(loader, "set_epoch"):
                        # epoch order = f(seed, epoch): a resumed epoch
                        # replays exactly the order the uninterrupted run
                        # saw.
                        loader.set_epoch(epoch)
                    skip = b_skip if epoch == b_epoch else 0
                    batches = loader
                    if skip:
                        logger.info(
                            "mid-epoch resume: skipping %d already-"
                            "trained dispatch(es) of epoch %d", skip,
                            epoch)
                        batches = itertools.islice(batches, skip, None)
                    bag = MetricBag()
                    if skip and carry.bag is not None \
                            and epoch == carry.epoch:
                        # Healed mid-epoch: keep accounting for the
                        # pre-loss dispatches so the epoch log/event
                        # covers the whole epoch, not just the remainder.
                        bag.restore(carry.bag)
                    pos = (epoch, skip)
                    # start=skip keeps i the TRUE epoch-local dispatch
                    # index on a mid-epoch resume — telemetry/log batch
                    # numbers continue where the interrupted run stopped
                    # rather than restarting at 0 over indices it already
                    # recorded.
                    for i, batch in timer.iterate(epoch, batches,
                                                  start=skip):
                        # Every statement of the body lies in ONE phase
                        # of obs/timing.py::LOOP_SPANS, so that a device
                        # idle under the profiler has a named cause.
                        with timer.span("train.key"):
                            # the iteration's first device dispatch: with
                            # the device's queue full it is HERE that the
                            # loop blocks (read on the chip, PR 25)
                            k = jax.random.fold_in(  # graftlint: disable=prng-key-reuse — the root is folded with a DISTINCT global dispatch index each iteration (the resumable-key derivation; see the rng comment above)
                                rng, epoch * steps_per_epoch + i)
                        with timer.span("train.place"):
                            sharded = shard_batch(batch, mesh)
                        with timer.span("train.observe"):
                            if chaos_spec.active:
                                # chaos site "train_dispatch": the
                                # injected device loss
                                # (device_lost_at_step) fires before the
                                # dispatch that would complete optimizer
                                # step K.
                                chaos_spec.fire(
                                    "train_dispatch",
                                    step=epoch * steps_per_epoch + i + 1)
                            if cost_tracker is not None:
                                # One AOT cost capture per compiled shape
                                # bucket (dict lookup otherwise) — the
                                # `cost` event behind per-bucket MFU.
                                cost_tracker.observe(step_fn, state,
                                                     sharded, k)
                            if tracer is not None:
                                # Pre-dispatch arming: the window must
                                # INCLUDE step trace_at_step (even step 1).
                                tracer.before_step(timer.total_steps + 1)
                        with timer.span("train.enqueue"):
                            if health_on:
                                state, metrics, pulse = step_fn(
                                    state, sharded, k)
                            else:
                                state, metrics = step_fn(state, sharded, k)
                            pos = (epoch, i + 1)
                            timer.dispatched()
                        with timer.span("train.metrics"):
                            # Speedometer's line, every `frequent`
                            # dispatches, reads the means of the
                            # dispatches already done (the bag's
                            # ready-only drain): no host sync here
                            bag.update(metrics)
                            speedometer(epoch, i, bag)
                        with timer.span("train.snapshot"):
                            if healer is not None:
                                healer.note_progress()
                                if copy_state is None and healer.snapshots:
                                    # The snapshot's copy program,
                                    # compiled for the state the step
                                    # RETURNS (its types are every later
                                    # dispatch's), on the session's first
                                    # dispatch: set-up pays for it, never
                                    # the steady state (a compile at
                                    # dispatch 200 would drain the queue
                                    # the deferred read is to spare).
                                    copy_state = compile_tree_copy(
                                        (state.params, state.opt_state))
                                healer.poll_snapshot()
                                if healer.snapshot_due():
                                    healer.begin_snapshot(_begin_snapshot)
                        with timer.span("train.observe"):
                            if first_dispatch_due:
                                first_dispatch_due = False
                                _first_dispatch_counters(epoch, i, metrics)
                            if tracer is not None:
                                # timer.total_steps increments when the
                                # generator resumes — this dispatch is
                                # the (+1)th completed. A window that
                                # closes here first waits for this
                                # dispatch (the loop itself never does).
                                tracer.step_completed(
                                    timer.total_steps + 1, outputs=metrics)
                            if monitor is not None:
                                # stores a reference per dispatch; pulls
                                # to host (and runs the tripwires) only
                                # at the obs.health_every cadence. A
                                # tripped wire raises NumericsAnomaly out
                                # of the loop AFTER saving the known-good
                                # checkpoint.
                                monitor.observe(pulse, epoch=epoch,
                                                dispatch=i + 1)
                            done = i + 1  # dispatches complete this epoch
                            if chaos_spec.active:
                                chaos_spec.maybe_sigterm(
                                    epoch * steps_per_epoch + done)
                            if stopper is not None:
                                # Coordinated preemption (graftquorum):
                                # the signaled host PROPOSES its next
                                # boundary; every host folds in its own
                                # floor and ALL of them drain to the
                                # agreed max before the one
                                # barrier+publish in _honor_preemption.
                                # The un-signaled steady state costs one
                                # store read per dispatch.
                                gdone = epoch * steps_per_epoch + done
                                if (guard is not None and guard.requested
                                        and not stop_requested):
                                    stopper.request(gdone)
                                    stop_requested = True
                                agreed = stopper.check(gdone)
                                if agreed is not None and gdone >= agreed:
                                    _honor_preemption(epoch, done)
                            elif guard is not None and guard.requested:
                                _honor_preemption(epoch, done)
                    # pos stays at (epoch, <last dispatch>) until the
                    # epoch-end work below completes: a heal landing
                    # inside this window then REPLAYS the whole block
                    # (the islice skips every dispatch, the bag restores
                    # from the carry) — the epoch event, the boundary
                    # save (a re-save is atomic and idempotent) and the
                    # epoch_callback all run instead of being silently
                    # dropped. Epoch callbacks should tolerate a rare
                    # re-invocation for the same epoch.
                    logger.info("Epoch[%d] done. %s", epoch, bag.format())
                    if obs_log.enabled:
                        # bag.format() above already drained the pending
                        # device scalars — this get() re-reads host-side
                        # sums only. Pad-waste accounting rides along:
                        # cumulative real/canvas pixels from the loader
                        # (graftprof; the canvas-packing baseline).
                        pad = (loader.pad_waste_stats()
                               if hasattr(loader, "pad_waste_stats")
                               else None)
                        obs_log.emit("epoch", epoch=epoch,
                                     metrics=bag.get(),
                                     **({"pad_waste": pad["pad_waste"],
                                         "pad_real_px": pad["real_px"],
                                         "pad_canvas_px": pad["canvas_px"]}
                                        if pad else {}))
                    # checkpoint_period > 1 (long small-epoch runs, e.g.
                    # the DETR gate's 150 epochs): save every Nth epoch
                    # and always the last — resume granularity traded
                    # against orbax save time.
                    # Explicit loader shutdown at epoch end: the epoch
                    # generator's finally already STOPPED the prefetcher
                    # when the loop drained it; close() additionally
                    # joins the worker threads so none outlive the epoch
                    # (data/loader.py).
                    if hasattr(loader, "close"):
                        loader.close()
                    boundary = (epoch + 1) * steps_per_epoch
                    if stopper is not None:
                        # Stop check BEFORE the epoch barrier: a host
                        # already waiting in the barrier cannot publish
                        # its drain floor, so a stop requested by a
                        # mid-epoch peer would idle the fleet until the
                        # deadline. (The residual race — a request
                        # landing between this check and the barrier —
                        # stays bounded by quorum_timeout_s.)
                        if (guard is not None and guard.requested
                                and not stop_requested):
                            stopper.request(boundary)
                            stop_requested = True
                        agreed = stopper.check(boundary)
                        if agreed is not None and boundary >= agreed:
                            _honor_preemption(epoch + 1, None)
                    if quorum is not None:
                        # Epoch-boundary saves get the same publication
                        # discipline as emergency saves: every host has
                        # finished the epoch before the leader publishes
                        # (the unbarriered-publish lint rule's contract).
                        quorum.barrier(f"epoch/{epoch + 1}")
                    epoch_saved = False
                    if is_primary() and (
                            (epoch + 1) % max(1, checkpoint_period) == 0
                            or epoch + 1 == end_epoch):
                        save = (writer.save if writer is not None
                                else save_checkpoint)
                        with timer.span("train.checkpoint"):
                            save(prefix, epoch + 1, state.params,
                                 state.opt_state,
                                 means=cfg.train.bbox_means,
                                 stds=cfg.train.bbox_stds,
                                 num_classes=cfg.dataset.num_classes,
                                 meta=_ckpt_meta(epoch + 1, None))
                        epoch_saved = True
                        if obs_log.enabled:
                            obs_log.emit("checkpoint", epoch=epoch + 1,
                                         prefix=prefix,
                                         durable=writer is None)
                    if epoch_callback:
                        epoch_callback(epoch, state, bag)
                    if stopper is not None:
                        # Re-check after the save/callback window — a
                        # signal that landed during epoch-end work, or a
                        # peer's request that arrived after the check
                        # above.
                        if (guard is not None and guard.requested
                                and not stop_requested):
                            stopper.request(boundary)
                            stop_requested = True
                        agreed = stopper.check(boundary)
                        if agreed is not None and boundary >= agreed:
                            _honor_preemption(epoch + 1, None,
                                              need_save=not epoch_saved)
                    elif guard is not None and guard.requested:
                        # Signal landed during epoch-end work: exit at
                        # the boundary. The save just enqueued (if any)
                        # goes durable in the finally below (writer.close
                        # publishes it); otherwise (checkpoint_period
                        # skipped this epoch) write a boundary checkpoint
                        # now so nothing is lost.
                        _honor_preemption(epoch + 1, None,
                                          need_save=not epoch_saved)
                    pos = (epoch + 1, 0)
                break  # trained through end_epoch — leave the session loop
            except RuntimeError as exc:
                # Step-time device/backend loss: heal in-process when the
                # PR 5 classification says transient (and the consecutive-heal
                # cap has headroom); anything else propagates untouched.
                if healer is None or not healer.healable(exc):
                    raise
                try:
                    carry = healer.recover(exc, _capture)
                except QuorumExcludedError as qexc:
                    # The quorum sealed a heal round WITHOUT this host
                    # (it missed the rendezvous deadline): its session
                    # state is stale relative to the agreed topology.
                    # Exit resumably (rc 75, no local save — the fleet's
                    # checkpoints are authoritative) so the supervisor
                    # rejoins it via --resume auto.
                    if obs_log.enabled:
                        obs_log.emit("quorum", kind="excluded",
                                     error=str(qexc)[:300])
                    logger.warning("graftquorum: %s — exiting rc %d for "
                                   "rejoin via --resume auto", qexc,
                                   PreemptionExit().code)
                    raise PreemptionExit(None) from qexc
    except BaseException as exc:  # graftlint: disable=broad-except — crash telemetry, re-raised below
        if obs_log.enabled and not isinstance(exc, PreemptionExit):
            import traceback

            obs_log.emit("crash", error=repr(exc),
                         traceback=traceback.format_exc())
            if recorder is not None:
                # the rc!=0 artifact: the last-K events (incl. any
                # health readings) around the death, flushed even when
                # the JSONL buffer was not
                recorder.dump("crash")
        raise
    finally:
        timer.close()  # the collector hook goes with the loop
        if healer is not None:
            healer.drop_snapshot()  # the session is over: not awaited
        if guard is not None:
            guard.uninstall()
        if watchdog is not None:
            watchdog.stop()
        if tracer is not None:
            tracer.close()  # an open stall window must land on disk
        if obs_log.enabled and cfg.obs.track_compiles:
            compile_track.deactivate()
        obs_log.close()
        if writer is not None:
            writer.close()  # the last save must be durable before return
        if hasattr(loader, "close"):
            loader.close()  # crash paths must not leak worker threads
    # Host-OWNED copies, not views: on the CPU backend device_get can
    # return zero-copy numpy views of runtime buffers, and callers hold
    # the returned tree across later jax work in the same process (the
    # kill->resume parity gate compares trees from THREE runs) — a
    # reused buffer would silently corrupt the caller's copy. One
    # end-of-training copy is noise next to an epoch.
    return jax.tree_util.tree_map(np.array, jax.device_get(state.params))
