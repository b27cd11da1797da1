"""Immutable configuration tree.

Replaces the reference's global mutable ``easydict`` config
(rcnn/config.py: ``config``, ``default``, ``network``, ``dataset``,
``generate_config(net, ds)``) with a frozen dataclass tree. Numeric defaults
follow the reference's classic Faster R-CNN hyperparameters; every field that
the reference exposes has an equivalent here. ``generate_config`` keeps the
same name and role: merge per-network and per-dataset presets.

TPU delta vs the reference: shapes are static. ``TrainConfig.max_gt_boxes``
pads the gt-box tensor, ``rpn_post_nms_top_n`` / ``batch_rois`` are exact
(masked) counts, and image batches are padded to ``image_pad_shape``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field, replace
from typing import Any, Mapping, Optional


@dataclass(frozen=True)
class NetworkConfig:
    """Per-backbone structural config (reference: rcnn/config.py `network.*`)."""

    name: str = "resnet50"
    # Anchors (reference: generate_anchors(base_size=16, ratios, scales)).
    anchor_base_size: int = 16
    anchor_ratios: tuple = (0.5, 1.0, 2.0)
    anchor_scales: tuple = (8, 16, 32)
    rpn_feat_stride: int = 16
    # Backbone freezing (reference: fixed_param_prefix in train_end2end.py).
    fixed_param_patterns: tuple = ("conv0", "bn0", "stage1", "gamma", "beta")
    # Head pooling (reference: ROIPooling 7x7 VGG / 14x14 ResNet, 1/16 scale).
    roi_pool_size: int = 14
    roi_pool_type: str = "align"  # "align" | "max" — reference uses max-pool
    # Channels of the stride-16 feature map (C4): 1024 for ResNet, 512 VGG.
    feat_channels: int = 1024
    depth: int = 50  # resnet depth; unused for vgg
    # Backbone normalization: "frozen_bn" (reference parity — REQUIRES
    # pretrained statistics to be restored) or "group" (GroupNorm; the
    # stable choice for from-scratch training, see models/backbones.py).
    norm: str = "frozen_bn"
    # Stop-gradient freeze cut: 0 = none, 1 = stem, 2 = stem+stage1
    # (reference fixed_param_prefix default). Use 0 when training from
    # scratch — freezing random weights is pointless.
    freeze_at: int = 2
    # Rematerialize ResNet stage activations in the backward (jax.checkpoint
    # via nn.remat) — trades ~1/3 extra FLOPs for HBM, enabling bigger
    # images / per-chip batches (models/backbones.py).
    remat: bool = False
    # FPN (off for the classic C4 configs).
    use_fpn: bool = False
    fpn_strides: tuple = (4, 8, 16, 32, 64)
    fpn_channels: int = 256
    # Fused shared-RPN-head application: pack P2..P6 into one zero-gapped
    # canvas and run the head ONCE instead of five small-grid convs
    # (models/fpn.py::rpn_forward_packed; semantics identical — tested).
    fpn_packed_rpn_head: bool = True
    # Mask head (Mask R-CNN configs).
    use_mask: bool = False
    mask_pool_size: int = 14
    mask_resolution: int = 28
    # ViTDet (stretch config; models/vit.py).
    use_vit: bool = False
    vit_patch: int = 16
    vit_dim: int = 768
    vit_depth: int = 12
    vit_heads: int = 12
    vit_window: int = 8  # local-attention window (tokens per side)
    # Sequence-parallel attention for the global blocks (long context,
    # ops/ring_attention.py); needs a mesh at model build time.
    # use_ring_attention selects the ppermute ring; sp_mode overrides the
    # formulation: "ring" | "ulysses" (all-to-all; heads must divide by
    # the mesh model-axis size).
    use_ring_attention: bool = False
    sp_mode: str = "ring"
    # Single-device attention formulation for the ViTDet GLOBAL blocks
    # (the only dense-attention site with enough tokens to matter —
    # DETR's 640-token encoder is below any practical chunk, and its MHA
    # stays dense; windowed blocks are 64-token tiles): "dense" (one
    # (S,S) score buffer — XLA fuses well at detector sequence lengths)
    # or "streaming" (flash-style key-block scan, O(S·chunk) memory;
    # ops/ring_attention.py). Exact either way; a speed/memory knob
    # measured in PERF.md r5. Ignored (with a warning) under pp_stages.
    attn_impl: str = "dense"
    attn_kv_chunk: int = 1024
    # Tensor parallelism over the mesh `model` axis (parallel/partition.py):
    # Megatron-split transformer MLP/attention weights and the paired
    # fc6/fc7 detection heads; GSPMD inserts the collectives. Composes
    # with DP (data axis) and SP (same model axis, different tensors).
    tensor_parallel: bool = False
    # Pipeline parallelism for the ViT encoder (parallel/pipeline.py):
    # pp_stages > 0 selects the staged backbone (ViTBackbonePP) pipelined
    # over the mesh `model` axis (whose size must equal pp_stages). The
    # staged model reproduces the sequential ViTDet global-attention
    # placement EXACTLY for every buildable stage count; stage counts
    # that cannot preserve it (placement not periodic in the stage size,
    # e.g. depth 12 into 3 stages) hard-error at build time
    # (models/vit.py::_stage_global_pattern). Mutually exclusive with SP.
    # pp_microbatches=0 → one microbatch per stage.
    pp_stages: int = 0
    pp_microbatches: int = 0
    # Proposal pre-NMS top-k: "exact" (lax.top_k) or "approx"
    # (lax.approx_max_k, recall 0.95 — the TPU PartialReduce op; ~1.2 ms
    # off the FPN step, exact kept default for determinism. PERF.md).
    proposal_topk: str = "exact"
    # DETR (stretch config; models/detr.py).
    use_detr: bool = False
    detr_queries: int = 100
    detr_hidden: int = 256
    detr_heads: int = 8
    detr_enc_layers: int = 6
    detr_dec_layers: int = 6

    @property
    def num_anchors(self) -> int:
        return len(self.anchor_ratios) * len(self.anchor_scales)


@dataclass(frozen=True)
class TrainConfig:
    """Training hyperparameters (reference: rcnn/config.py `config.TRAIN`)."""

    # RPN anchor target assignment (reference: rcnn/io/rpn.py assign_anchor).
    rpn_batch_size: int = 256
    rpn_fg_fraction: float = 0.5
    rpn_positive_overlap: float = 0.7
    rpn_negative_overlap: float = 0.3
    rpn_clobber_positives: bool = False
    rpn_allowed_border: int = 0
    # Proposal op (train mode) (reference: rcnn/symbol/proposal.py).
    rpn_pre_nms_top_n: int = 12000
    rpn_post_nms_top_n: int = 2000
    rpn_nms_thresh: float = 0.7
    rpn_min_size: int = 16
    # RCNN roi sampling (reference: rcnn/io/rcnn.py sample_rois).
    batch_rois: int = 128
    fg_fraction: float = 0.25
    fg_thresh: float = 0.5
    bg_thresh_hi: float = 0.5
    # None is a SENTINEL meaning "unset": it resolves to the reference's
    # end2end default 0.0 (see bg_thresh_lo_value), while the alternate
    # Fast-RCNN path (tools/stages.py::train_rcnn) replaces it with the
    # reference's 0.1 preset. An EXPLICIT value — including 0.0, which the
    # sentinel makes expressible — is respected everywhere.
    bg_thresh_lo: Optional[float] = None
    # bbox regression target normalization (reference: config.TRAIN.BBOX_*).
    bbox_normalization_precomputed: bool = True
    bbox_means: tuple = (0.0, 0.0, 0.0, 0.0)
    bbox_stds: tuple = (0.1, 0.1, 0.2, 0.2)
    # Optimizer (reference: train_end2end.py fit kwargs).
    lr: float = 0.001
    lr_step: tuple = (7,)  # epochs at which lr is divided by lr_factor
    lr_factor: float = 0.1
    momentum: float = 0.9
    wd: float = 0.0005
    clip_gradient: float = 5.0
    # "sgd" (reference parity: SGD+momentum, elementwise clip) or "adamw"
    # (the transformer families — DETR/ViTDet train with AdamW + global-
    # norm clip per their papers; SGD barely converges there).
    optimizer: str = "sgd"
    begin_epoch: int = 0
    end_epoch: int = 10
    # Non-blocking epoch-end saves (orbax AsyncCheckpointer — the train
    # loop keeps stepping while the write lands; train/checkpoint.py
    # CheckpointWriter). Auto-falls back to synchronous saves multi-host.
    async_checkpoint: bool = True
    # Gradient accumulation: each optimizer step averages grads over this
    # many sequential micro-steps (unrolled inside the jitted step —
    # compile time and HLO size grow with the count; see train/step.py
    # for why not lax.scan), so effective batch = batch_images x
    # grad_accum_steps x data-axis size without the activation memory of
    # the big batch. The reference has no equivalent (SURVEY.md §3.2).
    # 1 = off.
    grad_accum_steps: int = 1
    # graftcast (train/precision.py): the mixed-precision policy. "bf16"
    # (default — the MXU's native dtype, ~2x the f32 peak on v5e) runs
    # the forward/backward in bfloat16 with f32 master weights, f32
    # islands (norm statistics, losses, bbox decode/encode, NMS scores)
    # and f32 gradients/optimizer updates; "f32" runs everything float32
    # (the numerics reference the bf16 parity gates compare against).
    # Checkpoints are f32 either way and interchange between the two
    # bit-for-bit at the master-weight level. The modules cast their f32
    # leaves at use (flax's per-leaf promotion). Accepts the long
    # spellings "float32"/"bfloat16" too.
    compute_dtype: str = "bf16"
    # Optimizer slot dtype: "float32" (default) or "bfloat16" — stores
    # the SGD momentum / AdamW first-moment accumulator in bf16 (halves
    # that tree's memory; the AdamW second moment always stays f32 — its
    # precision matters for the rsqrt). A MEMORY lever for big models,
    # not a speed lever: the update has no device time of its own
    # (`stage.update_ms.train` 0.0001 ms, ledger, PR 29: fused into the
    # weight-gradient convolutions).
    opt_state_dtype: str = "float32"
    # Data
    batch_images: int = 1  # images per device
    shuffle: bool = True
    flip: bool = True
    aspect_grouping: bool = True
    # Static-shape padding (TPU design decision — no reference equivalent).
    max_gt_boxes: int = 100
    # FPN proposal budget per pyramid level (Detectron convention: 2000/level
    # at train time); only read when network.use_fpn.
    fpn_rpn_pre_nms_per_level: int = 2000
    # FPN RPN NMS scope: per-level (True — the Detectron-lineage
    # semantics; measured equal in cost to one joint NMS over the
    # 10k-candidate union at v5e train sizes, PERF.md) or joint across
    # the union (False).
    fpn_nms_per_level: bool = True
    # Mask target rasterization resolution (gt instance masks are stored
    # box-frame at this size; only read when network.use_mask).
    mask_gt_resolution: int = 56
    # Loss scaling constants (reference scales smooth-L1 by 1/RPN_BATCH and
    # 1/BATCH_ROIS via grad_scale, NOT by live fg counts).
    # DETR set-loss knobs (models/detr.py; Carion et al. defaults).
    detr_eos_coef: float = 0.1
    detr_cost_class: float = 1.0
    detr_cost_l1: float = 5.0
    detr_cost_giou: float = 2.0
    # Auxiliary decoding losses: the matched set loss at EVERY decoder
    # layer through shared heads (Carion et al. §3.2).
    detr_aux_loss: bool = True
    # end2end switch retained for the alternate-training tools.
    end2end: bool = True

    @property
    def bg_thresh_lo_value(self) -> float:
        """bg_thresh_lo with the None sentinel resolved to the end2end
        default (0.0). Model forwards read this; only the Fast-RCNN stage
        driver inspects the raw sentinel."""
        return 0.0 if self.bg_thresh_lo is None else self.bg_thresh_lo


@dataclass(frozen=True)
class TestConfig:
    """Inference hyperparameters (reference: rcnn/config.py `config.TEST`)."""

    rpn_pre_nms_top_n: int = 6000
    rpn_post_nms_top_n: int = 300
    rpn_nms_thresh: float = 0.7
    rpn_min_size: int = 16
    # Final detection post-processing (reference: rcnn/core/tester.py pred_eval).
    nms_thresh: float = 0.3
    score_thresh: float = 0.05
    max_per_image: int = 100
    # Proposal-generation mode (alternate training / Fast R-CNN) — the
    # reference's TEST.PROPOSAL_* knobs: dump MORE proposals (→2000) than the
    # detection path keeps (→300).
    proposal_nms_thresh: float = 0.7
    proposal_pre_nms_top_n: int = 20000
    proposal_post_nms_top_n: int = 2000
    # FPN per-level proposal budget at test time (Detectron: 1000/level).
    fpn_rpn_pre_nms_per_level: int = 1000
    fpn_nms_per_level: bool = True  # see TrainConfig.fpn_nms_per_level


@dataclass(frozen=True)
class DatasetConfig:
    """Per-dataset config (reference: rcnn/config.py `dataset.*`)."""

    name: str = "coco"
    root_path: str = "data"
    dataset_path: str = "data/coco"
    image_set: str = "train2017"
    test_image_set: str = "val2017"
    num_classes: int = 81  # incl. background
    class_names: tuple = ()
    # Extra get_dataset(...) kwargs as (key, value) pairs — kept a tuple so
    # the frozen config stays hashable (e.g. synthetic dataset sizing:
    # (("num_images", 8), ("image_size", 128))).
    kwargs: tuple = ()


@dataclass(frozen=True)
class ImageConfig:
    """Image pipeline (reference: config.SCALES / PIXEL_MEANS, rcnn/io/image.py)."""

    scales: tuple = ((600, 1000),)  # (target short side, max long side)
    pixel_means: tuple = (123.68, 116.779, 103.939)  # RGB (reference stores BGR)
    pixel_stds: tuple = (1.0, 1.0, 1.0)
    # Static padded shape (H, W) every image batch is padded to. Must be a
    # multiple of the max feature stride. 1024 covers the (600,1000) scale.
    pad_shape: tuple = (1024, 1024)
    # Multi-scale training (BASELINE config 3): one (H, W) pad bucket per
    # entry of `scales`. Used ONLY when len(pad_shapes) == len(scales);
    # an EMPTY tuple falls back to the single pad_shape (the documented
    # override path — generate_config empties it when scales/pad_shape
    # are overridden alone), while a NON-empty length mismatch is a
    # config error (loader.pad_shape_for raises — the stale-pair trap).
    # Each bucket is its own static shape → its own jit compile of the
    # train step (documented cost: one extra compile per extra scale).
    # The loader samples one scale PER BATCH — the per-image random
    # scale of reference-lineage forks would break the single static
    # batch shape.
    pad_shapes: tuple = ()
    # graftcanvas (data/canvas.py): whole-batch canvas packing. The
    # loader shelf-packs every batch's mixed-size images into ONE fixed
    # (canvas_shape) canvas per data shard instead of padding each image
    # to its orientation x scale pad bucket — every STEP then has one
    # static shape, period (the pad-bucket compile zoo collapses to a
    # single train-step executable) and the model pays for canvas
    # pixels, not bucket pixels. Placement metadata rides im_info
    # ([h, w, scale, y0, x0] rows) through anchors/targets, proposals
    # and ROI extraction, so per-image semantics are exact: proposals
    # and ROIs never cross a placement border (gated in
    # tests/test_canvas.py). TRAIN-time only — eval/checkpoints are
    # unaffected. Default off until the on-chip A/B (bench.py
    # c4_r101_canvas / fpn_r101_canvas recipes).
    canvas_pack: bool = False
    # Fixed canvas (H, W); () derives a never-overflowing cover from
    # scales/canvas_images (data/canvas.py::resolve_canvas — the
    # conservative default; set a TIGHT canvas for the pixel win and let
    # scale-to-fit absorb the rare overflow batch).
    canvas_shape: tuple = ()
    # Minimum zero gap (px) between any two placements and alignment of
    # every placement offset; 0 derives the model family's max feature
    # stride (64 for FPN/ViTDet, 16 for C4). Must stay >= that stride:
    # alignment keeps every downsampled grid exact and the gap keeps
    # activations from leaking across images (the rpn_forward_packed
    # zero-gap argument, per-block re-masked in the backbone).
    canvas_gap: int = 0
    # Images packed per canvas plane; 0 = train.batch_images (each data
    # shard packs its whole per-device batch into one plane). Packing
    # pays off at >= 2 images per plane — mixed aspects share a canvas.
    canvas_images: int = 0


@dataclass(frozen=True)
class MeshConfig:
    """Device-mesh layout (replaces the reference's --gpus/--kvstore flags).

    The reference's only parallelism is data parallel (rcnn/core/module.py
    MutableModule over a context list + KVStore allreduce). Here a
    `jax.sharding.Mesh` with axes (data, model) covers DP and leaves room for
    model/spatial sharding; `mesh_shape="8"` or `"4x2"` style strings come
    from the `--tpu-mesh` CLI flag.
    """

    mesh_shape: str = "1"
    data_axis: str = "data"
    model_axis: str = "model"


@dataclass(frozen=True)
class ObsConfig:
    """graftscope telemetry (mx_rcnn_tpu/obs — event stream, step timing,
    compile tracking, stall watchdog). Off by default: the disabled path
    is a no-op sink and adds nothing to the train hot path."""

    enabled: bool = False
    # Event-log directory; "" derives one from the run (fit_detector uses
    # "<checkpoint-prefix>.obs"). Each process writes its own JSONL.
    dir: str = ""
    # step/compile records buffer this many lines before hitting disk
    # (other record kinds flush immediately).
    flush_every: int = 64
    # Emit a `compile` event (with shape signature) per XLA compile via
    # jax.monitoring.
    track_compiles: bool = True
    # Heartbeat watchdog: emit a `stall` event (with stack dumps) when no
    # step completes within max(stall_min_s, stall_factor x trailing
    # median step time). Before the FIRST completed step the floor is
    # COLD_GRACE (10x) x stall_min_s, so a healthy multi-minute cold
    # compile is not reported as a stall (obs/watchdog.py).
    watchdog: bool = True
    stall_factor: float = 10.0
    stall_min_s: float = 120.0
    watchdog_poll_s: float = 5.0
    # grafttower (obs/fleet.py): liveness beacon cadence — the watchdog
    # thread additionally emits a `heartbeat` event every this many
    # seconds (flushed immediately; ring-buffered into the flight
    # recorder) plus one final=True beat at clean shutdown, so the fleet
    # report tells a KILLED host (stale trail, no final beat) from a
    # slow one (fresh beats, fat step tail). 0 disables. Requires
    # obs.watchdog (the beacon shares its daemon thread).
    heartbeat_every_s: float = 15.0
    # graftprof (obs/costs.py): per-compiled-shape-bucket XLA cost/memory
    # accounting — one `cost` event per bucket (flops, HBM split), the
    # basis of the computed MFU in step/bench reports. Costs one AOT
    # trace per bucket (the XLA compile itself is a cache hit).
    cost_analysis: bool = True
    # graftprof (obs/profile.py): arm a jax.profiler capture window
    # around global step K (0 = off), N completed steps long, saved
    # under "<obs dir>/trace/stepK" and folded into a `trace` event.
    # The stall watchdog additionally auto-arms one window when it
    # fires, independent of this knob.
    trace_at_step: int = 0
    trace_steps: int = 3
    # graftpulse (train/health.py + obs/health.py): in-graph numerics
    # health. health_every=N computes per-buffer nonfinite counts and
    # grad/param/update norms INSIDE the compiled step (same executable,
    # zero added per-step host syncs) and folds them into a `health`
    # event every N dispatches; 0 = off (the step program is then
    # bit-identical to pre-graftpulse). Tripwires — any nonfinite, a
    # grad-norm explosion past health_grad_factor x the trailing median,
    # or a loss z-score beyond health_loss_z vs the health_window
    # trailing readings — emit an `anomaly` event, arm one jax.profiler
    # window, dump the flight-recorder ring and (health_checkpoint)
    # write an emergency checkpoint of the last known-good state; then
    # health_action "abort" raises NumericsAnomaly (restart with
    # --resume auto) while "warn" keeps training. Runbook: OUTAGES.md
    # "run went nonfinite".
    health_every: int = 0
    health_window: int = 64
    health_grad_factor: float = 100.0
    health_loss_z: float = 10.0
    health_action: str = "abort"
    # Refresh a host-side known-good snapshot after each CLEAN health
    # check (one device_get per interval — size health_every
    # accordingly) and save it as the emergency checkpoint on anomaly.
    health_checkpoint: bool = True
    # Flight recorder: capacity of the last-K in-memory event ring
    # dumped to <obs dir>/flight_<reason>.json on anomaly/stall/heal/
    # preempt/crash (obs/health.py FlightRecorder).
    flight_events: int = 256


@dataclass(frozen=True)
class ResilienceConfig:
    """graftguard fault tolerance (mx_rcnn_tpu/resilience — classified
    backend acquisition, preemption-safe training, deadline-isolated
    benching, chaos injection)."""

    # Acquire the backend through resilience/backend.py: transient
    # failures (UNAVAILABLE) retry with
    # exponential backoff + jitter under the deadline below; permanent
    # errors fail fast. False = raw first-touch jax behavior.
    backend_acquire: bool = True
    # Require this platform in the acquired device list ("tpu" for real
    # runs): jax can come up on the CPU when the accelerator is not
    # there — the probe then "succeeds" instantly and a multi-hour run proceeds
    # at CPU speed. When set, a fallback device list is classified as a
    # transient failure (backend cache cleared, retried under the
    # deadline). "" accepts whatever comes up (CPU tests/dev boxes).
    backend_platform: str = ""
    # Give up after this long of CONTINUOUS transient failure. Half a
    # day suits a long run on a host that is yours; on a machine charged
    # by the second pass seconds (chip_smoke.py and bench.py do).
    backend_deadline_s: float = 43200.0
    backend_backoff_base_s: float = 2.0
    backend_backoff_max_s: float = 300.0
    # Multiplicative jitter fraction on every sleep (decorrelates a fleet
    # of hosts re-probing a recovering backend).
    backend_backoff_jitter: float = 0.25
    # Install SIGTERM/SIGINT handlers that request a checkpoint at the
    # next step boundary and exit with the resumable rc (75) instead of
    # dying mid-step (resilience/preempt.py).
    preempt_handlers: bool = True
    # On preemption, write a step-granular emergency checkpoint (a
    # dispatch-tagged dir under the prefix; picked up by --resume auto).
    # False: exit resumable-rc without saving (epoch checkpoints only).
    preempt_save: bool = True
    # graftheal (resilience/heal.py): a step-time transient backend loss
    # (the TPU_OUTAGE_r5 signature, mid-run) is healed IN-PROCESS —
    # emergency capture of the last known-good host state, backend
    # teardown + re-acquisition under backend_deadline_s, resume from
    # the captured state. If the backend returns with fewer devices the
    # mesh is re-cut (model axis kept, data axis shrunk; global batch
    # invariant). False = the pre-heal behavior: the error propagates.
    heal: bool = True
    # Give up (re-raise) after this many consecutive heals with no
    # completed dispatch in between — a fault that recurs instantly is
    # not an outage.
    heal_consecutive_max: int = 3
    # Refresh the host-side fallback snapshot every N completed
    # dispatches. No sync: the snapshot is a deferred read (a device-side
    # copy of the state enqueued behind dispatch N, fetched while the
    # next steps run, installed as the fallback once it is there). 0 =
    # live capture only: fine when the post-loss state is readable (no
    # donation, or chaos injection); on real hardware with donated
    # buffers the snapshot is what bounds the deterministic replay after
    # a mid-step loss: to N + the dispatches a snapshot is in flight
    # (the depth of the device's queue, 10-12 on the chip).
    heal_snapshot_dispatches: int = 200
    # graftquorum (resilience/quorum.py): multi-host coordination for
    # preemption and heal. Deadline on every barrier / agree wait — a
    # host that misses it is excluded from the round (and exits
    # resumable when it discovers the sealed quorum moved on without
    # it).
    quorum_timeout_s: float = 60.0
    # A heal quorum below this fraction of the host set aborts the run
    # instead of limping on (half a fleet re-healing every few minutes
    # is an outage, not elasticity).
    quorum_min_fraction: float = 0.5
    # Filesystem-backed KV store directory for the quorum protocol.
    # "" = use jax.distributed's coordination-service KV client (real
    # pods); a path = FileKVStore rooted there (the N-process CPU
    # tests, or any fleet sharing a filesystem). Single-process runs
    # never construct a quorum.
    quorum_store_dir: str = ""
    # Elastic phase 2 policy when a heal re-acquires a different device
    # count (parallel/partition.py elastic_mesh_spec):
    #   "shrink"  — phase 1 behavior: shrink the data axis to the
    #               largest micro-batch divisor; never grow past the
    #               nominal footprint.
    #   "grow"    — shrink, plus GROW onto devices beyond the nominal
    #               footprint when the re-acquire returns more.
    #   "rescale" — grow, and on shrinks too deep to hold the global
    #               batch keep rows-per-device constant instead: the
    #               global batch scales with the fleet and the LR
    #               schedule position is rebased in images-seen terms
    #               via rebase_schedule_count.
    elastic_mode: str = "shrink"


@dataclass(frozen=True)
class DataConfig:
    """graftfeed input-plane fault tolerance (mx_rcnn_tpu/data/feedguard.py
    — classified record IO retry, deterministic quarantine, prefetch worker
    supervision, data-stall deadlines). Runbook: OUTAGES.md."""

    # Per-record retry window for TRANSIENT IO failures (EIO/ETIMEDOUT/
    # stale NFS handle/truncated read — the storage flake classification of
    # resilience/backend.py applied to the input plane). A record that
    # stays broken past the deadline is reclassified as permanent and
    # quarantined. 0 disables retry (first failure classifies directly).
    record_deadline_s: float = 60.0
    record_backoff_base_s: float = 0.05
    record_backoff_max_s: float = 5.0
    # PERMANENTLY corrupt records (bad JPEG, malformed roidb entry) are
    # quarantined — `data` event + <obs dir>/quarantine.jsonl append — and
    # replaced by a deterministic substitute record f(seed, epoch, index)
    # so the epoch stream (and kill->resume parity) stays bit-exact. When
    # more than this fraction of the dataset lands in quarantine the
    # dataset itself is broken: abort loudly (flight-recorder dump)
    # instead of training on a stream of substitutes.
    quarantine_max_fraction: float = 0.01
    # A crashed prefetch worker thread is resurrected in place
    # (`data_worker` event); after this many deaths within one iterator
    # the input plane is declared broken and the run fails hard.
    worker_restart_max: int = 3
    # A blocking next() on the prefetch queue that exceeds this deadline
    # raises DataStallError (classified, flight-dumped, names data-wait
    # as the culprit) instead of hanging forever on dead storage.
    # 0 disables the deadline (wait forever — pre-graftfeed behavior).
    wait_deadline_s: float = 600.0


@dataclass(frozen=True)
class Config:
    network: NetworkConfig = field(default_factory=NetworkConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    test: TestConfig = field(default_factory=TestConfig)
    dataset: DatasetConfig = field(default_factory=DatasetConfig)
    image: ImageConfig = field(default_factory=ImageConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)
    resilience: ResilienceConfig = field(default_factory=ResilienceConfig)
    data: DataConfig = field(default_factory=DataConfig)
    seed: int = 0

    def with_updates(self, **kw) -> "Config":
        return replace(self, **kw)


# ---------------------------------------------------------------------------
# Presets (reference: rcnn/config.py per-network / per-dataset dicts merged by
# generate_config)
# ---------------------------------------------------------------------------

_NETWORK_PRESETS: Mapping[str, Mapping[str, Any]] = {
    "vgg": dict(
        name="vgg",
        feat_channels=512,
        roi_pool_size=7,
        depth=16,
        fixed_param_patterns=("conv1_1", "conv1_2", "conv2_1", "conv2_2"),
    ),
    "resnet50": dict(name="resnet50", depth=50),
    "resnet101": dict(name="resnet101", depth=101),
    # FPN-family presets default proposal_topk="approx": the per-level
    # exact lax.top_k over the stride-4 level's ~123k scores costs
    # ~2.2 ms/img in situ (7% of fwd+bwd, PERF.md r4 roofline) while
    # approx_max_k (recall 0.95) only perturbs MEMBERSHIP at the pre-NMS
    # tail — score order within the kept set is preserved, so NMS
    # semantics are unchanged and the Detectron-lineage recipe is
    # insensitive to the tail. `--set network.proposal_topk=exact`
    # restores bit-deterministic selection (and stays the C4 default).
    "resnet50_fpn": dict(
        name="resnet50_fpn", depth=50, use_fpn=True, roi_pool_size=7,
        anchor_scales=(8,), proposal_topk="approx",
    ),
    "resnet101_fpn": dict(
        name="resnet101_fpn", depth=101, use_fpn=True, roi_pool_size=7,
        anchor_scales=(8,), proposal_topk="approx",
    ),
    "resnet50_fpn_mask": dict(
        name="resnet50_fpn_mask", depth=50, use_fpn=True, roi_pool_size=7,
        anchor_scales=(8,), use_mask=True, proposal_topk="approx",
    ),
    "resnet101_fpn_mask": dict(
        name="resnet101_fpn_mask", depth=101, use_fpn=True, roi_pool_size=7,
        anchor_scales=(8,), use_mask=True, proposal_topk="approx",
    ),
    "vitdet_b": dict(
        name="vitdet_b", use_vit=True, roi_pool_size=7, anchor_scales=(8,),
        vit_dim=768, vit_depth=12, vit_heads=12, vit_window=8,
        norm="group",  # detector-side norms; the ViT itself uses LayerNorm
        proposal_topk="approx",
    ),
    "vitdet_b_mask": dict(
        name="vitdet_b_mask", use_vit=True, roi_pool_size=7,
        anchor_scales=(8,), use_mask=True,
        vit_dim=768, vit_depth=12, vit_heads=12, vit_window=8,
        norm="group", proposal_topk="approx",
    ),
    "detr_r50": dict(name="detr_r50", depth=50, use_detr=True),
}

# Per-network ImageConfig presets. The FPN/Mask configs default to the
# BASELINE-config-3 multi-scale recipe: short side sampled per batch from
# {640, 800}. Buckets are stored landscape-oriented (short, long) in
# stride-32 multiples (exact FPN top-down upsample-and-add shapes); the
# loader transposes them for portrait batches and squares only the rare
# mixed-orientation seam batch (loader.resolve_pad_bucket) — square-only
# covers would waste ~60% of the conv FLOPs on landscape COCO batches.
_IMAGE_PRESETS: Mapping[str, Mapping[str, Any]] = {
    name: dict(
        scales=((640, 1066), (800, 1333)),
        pad_shapes=((672, 1088), (832, 1344)),
        pad_shape=(1344, 1344),
    )
    for name in ("resnet50_fpn", "resnet101_fpn",
                 "resnet50_fpn_mask", "resnet101_fpn_mask")
}

# Per-network TrainConfig presets: the transformer families train with
# AdamW + global-norm clip 0.1 at transformer learning rates (Carion et
# al. §4: AdamW 1e-4, clip 0.1; ViTDet likewise AdamW) — SGD+momentum at
# detector rates barely converges there. The LR schedules are the papers'
# too (DETR: 300 epochs, ÷10 at 200; ViTDet: ~100 epochs, ÷10 at 88/96) —
# inheriting the SGD default lr_step=(7,) would silently decimate the LR
# at epoch 7.
_TRAIN_PRESETS: Mapping[str, Mapping[str, Any]] = {
    "detr_r50": dict(optimizer="adamw", lr=1e-4, clip_gradient=0.1,
                     wd=1e-4, lr_step=(200,), end_epoch=300),
    **{name: dict(optimizer="adamw", lr=1e-4, clip_gradient=0.1,
                  wd=1e-4, lr_step=(88, 96), end_epoch=100)
       for name in ("vitdet_b", "vitdet_b_mask")},
}

VOC_CLASSES = (
    "__background__",
    "aeroplane", "bicycle", "bird", "boat", "bottle", "bus", "car", "cat",
    "chair", "cow", "diningtable", "dog", "horse", "motorbike", "person",
    "pottedplant", "sheep", "sofa", "train", "tvmonitor",
)

_DATASET_PRESETS: Mapping[str, Mapping[str, Any]] = {
    "PascalVOC": dict(
        name="PascalVOC",
        dataset_path="data/VOCdevkit",
        image_set="2007_trainval",
        test_image_set="2007_test",
        num_classes=21,
        class_names=VOC_CLASSES,
    ),
    "coco": dict(
        name="coco",
        dataset_path="data/coco",
        image_set="train2017",
        test_image_set="val2017",
        num_classes=81,
    ),
    "synthetic": dict(
        name="synthetic",
        dataset_path="",
        image_set="train",
        test_image_set="test",
        num_classes=4,
    ),
}


def generate_config(network: str, dataset: str, **overrides) -> Config:
    """Build a Config from a network preset + dataset preset.

    Mirrors the reference's ``generate_config(network, dataset)``
    (rcnn/config.py) which merges ``network.<net>`` and ``dataset.<ds>``
    dicts into the globals; here it returns a fresh immutable Config.
    """
    if network not in _NETWORK_PRESETS:
        raise KeyError(f"unknown network {network!r}; have {sorted(_NETWORK_PRESETS)}")
    if dataset not in _DATASET_PRESETS:
        raise KeyError(f"unknown dataset {dataset!r}; have {sorted(_DATASET_PRESETS)}")
    cfg = Config(
        network=NetworkConfig(**_NETWORK_PRESETS[network]),
        dataset=DatasetConfig(**_DATASET_PRESETS[dataset]),
        image=ImageConfig(**_IMAGE_PRESETS.get(network, {})),
        train=TrainConfig(**_TRAIN_PRESETS.get(network, {})),
    )
    if overrides:
        # Overriding scales or pad_shape without pad_shapes must not pair
        # with the preset's stale buckets: a pad_shape override would be
        # silently ignored while len(pad_shapes) == len(scales), and a
        # scales override of the same length would keep too-small buckets
        # that overflow mid-epoch. Dropping the preset buckets falls back
        # to the single pad_shape (loader.pad_shape_for).
        if (("image.scales" in overrides or "image.pad_shape" in overrides)
                and "image.pad_shapes" not in overrides):
            overrides = dict(overrides, **{"image.pad_shapes": ()})
        if ("image.pad_shape" in overrides
                and "image.scales" not in overrides):
            # pad_shape-only override: the preset scales may exceed the
            # new canvas (the FPN presets' (800,1333) against a 640-pad
            # would crash pad_image mid-epoch). The canvas IS the intent:
            # train at the pad-sized scale.
            ph, pw = overrides["image.pad_shape"]
            overrides = dict(
                overrides,
                **{"image.scales": ((min(ph, pw), max(ph, pw)),)})
        cfg = _apply_dotted_overrides(cfg, overrides)
    return cfg


def _apply_dotted_overrides(cfg: Config, overrides: Mapping[str, Any]) -> Config:
    """Apply {"train.lr": 0.002, "test.nms_thresh": 0.5}-style overrides."""
    grouped: dict = {}
    for key, value in overrides.items():
        if "." in key:
            section, leaf = key.split(".", 1)
            grouped.setdefault(section, {})[leaf] = value
        else:
            grouped[key] = value
    updates = {}
    for section, value in grouped.items():
        current = getattr(cfg, section)
        if isinstance(value, Mapping) and dataclasses.is_dataclass(current):
            for leaf, leaf_value in value.items():
                # A string landing on a bool field is always a mistake
                # (e.g. a CLI "false" that failed literal parsing would be
                # TRUTHY); fail loudly instead of silently enabling it.
                if isinstance(getattr(current, leaf, None), bool) and isinstance(
                        leaf_value, str):
                    raise ValueError(
                        f"override {section}.{leaf}={leaf_value!r}: field is "
                        f"a bool; pass True/False")
            updates[section] = replace(current, **value)
        else:
            updates[section] = value
    return replace(cfg, **updates)


def parse_cli_overrides(pairs) -> dict:
    """['a.b=1', ...] (the CLI --set flag) → {'a.b': 1}.

    Values parse as python literals; the common CLI bool spellings
    (true/false/yes/no/on/off, any case) map to real bools BEFORE the
    literal fallback so '--set network.tensor_parallel=false' can never
    come through as a truthy string; anything else unparseable stays a
    string (e.g. network.norm=group).

    Caveat: the bool coercion is unconditional (it does not consult the
    target field's type), so a STRING-typed field can never receive the
    literal strings 'true'/'false'/'yes'/'no'/'on'/'off' (or quoted
    variants — quotes survive literal_eval as str only for other values)
    through --set. No current config field has such a value domain; if
    one ever does, route it around --set or rename the value.
    """
    import ast

    out = {}
    for pair in pairs:
        key, sep, raw = pair.partition("=")
        if not sep or not key:
            raise ValueError(f"--set expects KEY=VALUE, got {pair!r}")
        low = raw.strip().lower()
        if low in ("true", "yes", "on"):
            out[key] = True
        elif low in ("false", "no", "off"):
            out[key] = False
        else:
            try:
                out[key] = ast.literal_eval(raw)
            except (ValueError, SyntaxError):
                out[key] = raw
    return out
