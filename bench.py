"""Benchmark: train-step throughput + MFU on the chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline",
"headline_config", "detail"}. The headline metric stays the C4 R-101
img/s/chip figure; "headline_config" names the recipe that produced it.
"detail" carries per-config {img_s, step_ms, mfu} for ALL FIVE BASELINE
families — C4 (configs 1-2), FPN (config 3), Mask R-CNN (config 4), ViTDet
and DETR (config 5) — each the MEDIAN of 5 timed repetitions, and every row
names the device it ran on (platform, device_kind, device_count).

One process per chip: THIS process never imports jax. Every cell runs in a
spawn child (resilience/isolate.py) that acquires the TPU, measures, reports
one row through a pipe and exits — the children own the chip one after
another, share the persistent compile cache (utils/compile_cache.py), and a
hung compile forfeits one row under the per-cell deadline
(MX_RCNN_BENCH_DEADLINE_S, default 1800) instead of the sweep. A probe child
goes first: no TPU is an error, not a row, and the sweep does not start.
A sweep in which any cell errored exits non-zero.

Timing discipline: every repetition ends by fetching the loss scalar to the
host — a barrier on any backend (the bytes cannot arrive before the step
that computes them has run), and what a training loop's logging does anyway.

`update_r101`/`update_detr` isolate the optimizer update itself
(`apply_gradients` alone at full model size).

Crash-durability: every completed config's row is flushed to
<obs_dir>/partial.json (MX_RCNN_BENCH_PARTIAL overrides) the moment it
lands — a sweep killed at its time limit keeps its finished measurements.

MFU: analytic FLOPs from XLA's own cost model for the whole compiled
program (fwd+bwd+update), divided by the published peak of the
device the cell ran on IN THE RECIPE'S COMPUTE DTYPE
(obs/costs.py::peak_flops_for — one table keyed by device_kind, unknown
devices and unpublished dtypes raise); every row carries a `compute_dtype`
field and `ledger check` only grades rows against prior rows of the SAME
dtype.

graftscope: every run also writes an event stream + folded summary to
MX_RCNN_BENCH_OBS (default ./bench_obs) — per-config `bench` events,
folded by obs/report.py into bench_obs/report.json (the printed line
carries its path). The cells' XLA compiles happen in the children; each
row carries its own `compile_s` / `n_executables`.

The reference never published throughput (BASELINE.md: Speedometer logs
only), so vs_baseline is measured against a fixed reference point of
5.0 img/s/GPU — a generous estimate of the classic implementation's
ResNet-101 COCO training speed on a 2017 P100 (README-era hardware), used
solely to make the ratio meaningful across rounds.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time
from typing import Optional

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
# Nothing imported here may import jax: the parent process of a sweep
# stays off it (module docstring). The cell functions import it inside —
# they run in the children.
from mx_rcnn_tpu.obs import compile_track
from mx_rcnn_tpu.obs import costs as obs_costs
from mx_rcnn_tpu.obs.events import _json_default

REFERENCE_IMG_S = 5.0  # estimated reference img/s/GPU (see module docstring)


def own_device(want) -> dict:
    """Child-side, first thing in every cell: turn the compile cache on,
    acquire the backend — ``want`` is (platform, seconds to wait for it);
    that platform or an error, never a fallback — and name the device
    this process now owns. The fields go into every row, so no number
    can be read without the device it came from."""
    platform, deadline_s = want
    import jax

    from mx_rcnn_tpu.config import ResilienceConfig
    from mx_rcnn_tpu.resilience import acquire_backend
    from mx_rcnn_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    devices = acquire_backend(ResilienceConfig(
        backend_platform=platform, backend_deadline_s=deadline_s))
    import jaxlib

    return {"platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
            "jax_version": jax.__version__,
            "jaxlib_version": jaxlib.__version__}


def measure_cell(job) -> dict:
    """The children's entry: ``(runner, cfg, want)`` → one row."""
    runner, cfg, want = job
    device = own_device(want)
    row = runner(cfg)
    row.update(device)
    return row


def make_batch(cfg):
    b = cfg.train.batch_images
    h, w = cfg.image.pad_shape
    g = cfg.train.max_gt_boxes
    rs = np.random.RandomState(0)
    n_boxes = min(8, g)  # tiny tier-1 configs cap max_gt_boxes below 8
    # Box span and im_info content size scale with the canvas so tiny
    # tier-1 configs stay well-formed; at the flagship 640x1024 canvas
    # these reduce EXACTLY to the historical constants (span 200, boxes
    # uniform(50,199), content 600x1000 — rounds stay comparable). The
    # content-vs-canvas gap is also the measured pad_waste baseline.
    span = max(8, min(200, h // 2, w // 2))
    content_h, content_w = h * 600 // 640, w * 1000 // 1024
    boxes = np.zeros((b, g, 4), np.float32)
    for i in range(b):
        x1 = rs.uniform(0, w - span, n_boxes)
        y1 = rs.uniform(0, h - span, n_boxes)
        boxes[i, :n_boxes] = np.stack(
            [x1, y1, x1 + rs.uniform(span // 4, span - 1, n_boxes),
             y1 + rs.uniform(span // 4, span - 1, n_boxes)], axis=1)
    valid = np.zeros((b, g), bool)
    valid[:, :n_boxes] = True
    classes = np.zeros((b, g), np.int32)
    classes[:, :n_boxes] = rs.randint(1, cfg.dataset.num_classes,
                                      (b, n_boxes))
    batch = {
        "image": rs.randn(b, h, w, 3).astype(np.float32),
        "im_info": np.asarray([[content_h, content_w, 1.0]] * b,
                              np.float32),
        "gt_boxes": boxes,
        "gt_classes": classes,
        "gt_valid": valid,
    }
    if cfg.network.use_mask:
        m = cfg.train.mask_gt_resolution
        gm = np.zeros((b, g, m, m), np.uint8)
        gm[:, :n_boxes, 2:-2, 2:-2] = 1
        batch["gt_masks"] = gm
    return batch


def make_packed_batch(cfg):
    """Synthetic PACKED batch (graftcanvas — the ops/canvas.py contract):
    orientation-PURE landscape content at the first training scale (the
    aspect-grouped common case — mixed-orientation packing is covered by
    unit tests, not benched), shelf-packed into the config's fixed
    canvas by the real planner, random pixels in the placements and
    zeros in the gaps. The reported ``pad_waste`` is then genuine canvas
    utilization for the recipe's geometry."""
    from mx_rcnn_tpu.data.canvas import (content_size, plan_batch,
                                         validate_canvas_pack)

    spec = validate_canvas_pack(cfg)
    b = cfg.train.batch_images
    g = cfg.train.max_gt_boxes
    target, max_size = cfg.image.scales[0]
    rs = np.random.RandomState(0)
    # COCO-ish landscape source dims — aspect grouping keeps real
    # batches orientation-pure, so the bench times the common case (the
    # rare mixed seam batch pays scale-to-fit, covered by unit tests).
    # At the (600,1000) C4 scale these resize to the historical 600x1000
    # content, so canvas rows stay comparable to the bucketed recipes.
    srcs = [(480, 800) for _ in range(b)]

    def sizes_at(fit):
        t = max(1, int(round(target * fit)))
        mx = max(1, int(round(max_size * fit)))
        return [content_size(h0, w0, t, mx)[:2] for h0, w0 in srcs]

    placements, fit, sizes = plan_batch(sizes_at, b, spec)
    planes = b // spec.images
    ch, cw = spec.shape
    image = np.zeros((planes, ch, cw, 3), np.float32)
    info = np.zeros((planes, spec.images, 5), np.float32)
    boxes = np.zeros((planes, spec.images, g, 4), np.float32)
    classes = np.zeros((planes, spec.images, g), np.int32)
    valid = np.zeros((planes, spec.images, g), bool)
    n_boxes = min(8, g)
    t_f = max(1, int(round(target * fit)))
    m_f = max(1, int(round(max_size * fit)))
    for k, ((pl, y0, x0), (h, w)) in enumerate(zip(placements, sizes)):
        slot = k % spec.images
        image[pl, y0:y0 + h, x0:x0 + w] = rs.randn(h, w, 3)
        scale = content_size(*srcs[k], t_f, m_f)[2]
        info[pl, slot] = (h, w, scale, y0, x0)
        span = max(8, min(200, h // 2, w // 2))
        x1 = x0 + rs.uniform(0, w - span, n_boxes)
        y1 = y0 + rs.uniform(0, h - span, n_boxes)
        boxes[pl, slot, :n_boxes] = np.stack(
            [x1, y1, x1 + rs.uniform(span // 4, span - 1, n_boxes),
             y1 + rs.uniform(span // 4, span - 1, n_boxes)], axis=1)
        classes[pl, slot, :n_boxes] = rs.randint(
            1, cfg.dataset.num_classes, n_boxes)
        valid[pl, slot, :n_boxes] = True
    batch = {"image": image, "im_info": info, "gt_boxes": boxes,
             "gt_classes": classes, "gt_valid": valid}
    if cfg.network.use_mask:
        m = cfg.train.mask_gt_resolution
        gm = np.zeros((planes, spec.images, g, m, m), np.uint8)
        gm[:, :, :n_boxes, 2:-2, 2:-2] = 1
        batch["gt_masks"] = gm
    return batch


def _peak_flops(compute_dtype: str) -> Optional[float]:
    """The MFU denominator for the device this process owns. On the chip
    an unknown device_kind or an unpublished dtype raises; a CPU
    rehearsal has no device metric at all, so its rows carry no MFU."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    return obs_costs.peak_flops_for(jax.devices()[0].device_kind,
                                    compute_dtype)


def bench_config(cfg, reps: int = 5, iters: int = 20):
    import jax

    from mx_rcnn_tpu.models.zoo import build_model, forward_train, init_params
    from mx_rcnn_tpu.parallel.mesh import create_mesh, shard_batch
    from mx_rcnn_tpu.train import precision
    from mx_rcnn_tpu.train.optimizer import build_optimizer
    from mx_rcnn_tpu.train.step import create_train_state, make_train_step

    policy = precision.policy_of(cfg)

    b = cfg.train.batch_images
    batch = (make_packed_batch(cfg) if cfg.image.canvas_pack
             else make_batch(cfg))
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    tx = build_optimizer(cfg, params, steps_per_epoch=1000)
    state = create_train_state(params, tx)
    mesh = create_mesh(str(jax.device_count()))
    step_fn = make_train_step(model, cfg, mesh=mesh, forward_fn=forward_train)
    batch = shard_batch(batch, mesh)

    rng = jax.random.PRNGKey(1)
    # AOT-compile ONCE and time the compiled executable directly: this
    # pins the donated/device layouts up front (no second trace on the
    # first donated call) and gives cost_analysis() for free — no second
    # compile just for FLOPs. The compile counter (graftprof) tallies
    # the real XLA compiles this row triggered — incl. the warmups, so
    # a donation-layout recompile shows up in compile_s too; a warm
    # persistent-cache run honestly reports 0.
    with compile_track.count() as cc:
        rng, k0 = jax.random.split(rng)
        compiled = step_fn.lower(state, batch, k0).compile()
        costs = obs_costs.executable_costs(compiled)
        flops = costs.get("flops", 0.0)

        # Warmup dispatches through the compiled executable.
        for _ in range(4):
            rng, k = jax.random.split(rng)
            state, metrics = compiled(state, batch, k)
            float(np.asarray(metrics["TotalLoss"]))

    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            rng, k = jax.random.split(rng)
            state, metrics = compiled(state, batch, k)
        # Barrier: fetch the scalar VALUE (see module docstring).
        float(np.asarray(metrics["TotalLoss"]))
        rates.append(iters * b / (time.perf_counter() - t0))
    img_s = statistics.median(rates)
    per_chip = img_s / jax.device_count()
    step_ms = 1000.0 * b / img_s  # per optimizer step

    # cost_analysis() counts the PER-DEVICE (SPMD-partitioned) program, so
    # per-device flops x steps/sec / per-chip peak is already the
    # per-chip MFU — no extra device_count factor (obs_costs.mfu_from).
    mfu = obs_costs.mfu_from(flops, img_s / b, _peak_flops(policy.compute))
    pad = obs_costs.batch_pad_waste(batch)
    return {
        "img_s_per_chip": round(per_chip, 3),
        "step_ms": round(step_ms, 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "compute_dtype": policy.short,
        # graftprof: the executable's HBM footprint (args+temps+output
        # −alias from memory_analysis) and this batch's padding waste —
        # the HBM headroom and canvas-packing numbers the ledger tracks.
        "hbm_bytes": costs.get("hbm_bytes"),
        "pad_waste": pad.get("pad_waste"),
        "compile_s": round(cc.seconds, 3),
        "n_executables": cc.n,
        "reps_img_s": [round(r, 2) for r in rates],
    }


def bench_update_config(cfg, reps: int = 5, iters: int = 50):
    """Isolated optimizer-update microbench over synthetic gradients at
    full model size. No forward/backward: the jitted program is exactly
    `apply_gradients`, donated state, barrier = materializing the step
    counter's bytes."""
    import jax

    from mx_rcnn_tpu.models.zoo import build_model, init_params
    from mx_rcnn_tpu.train import precision
    from mx_rcnn_tpu.train.optimizer import build_optimizer
    from mx_rcnn_tpu.train.step import create_train_state

    policy = precision.policy_of(cfg)
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    key = jax.random.PRNGKey(7)
    grads = jax.tree_util.tree_map(
        lambda p: (jax.random.normal(jax.random.fold_in(key, p.size),
                                     p.shape) * 1e-3).astype(p.dtype),
        params)
    tx = build_optimizer(cfg, params, steps_per_epoch=1000)
    n_leaves = len(jax.tree_util.tree_leaves(params))

    def timed(state, gr):
        fn = jax.jit(lambda s, g: s.apply_gradients(g), donate_argnums=(0,))
        state = fn(state, gr)  # compile + donated-layout warmup
        for _ in range(3):
            state = fn(state, gr)
        float(np.asarray(state.step))
        rates = []
        for _ in range(reps):
            t0 = time.perf_counter()
            for _ in range(iters):
                state = fn(state, gr)
            float(np.asarray(state.step))  # barrier (module docstring)
            rates.append(1000.0 * (time.perf_counter() - t0) / iters)
        return statistics.median(rates)

    with compile_track.count() as cc:  # graftprof compile accounting
        tree_ms = timed(create_train_state(params, tx), grads)
    return {
        "tree_ms": round(tree_ms, 3),
        "param_leaves": n_leaves,
        "optimizer": cfg.train.optimizer,
        "compute_dtype": policy.short,
        "compile_s": round(cc.seconds, 3),
        "n_executables": cc.n,
    }


def bench_eval_config(cfg, batch_size: int = 4, reps: int = 5,
                      iters: int = 10):
    """Inference-path throughput: the Predictor's fused detect program
    (backbone → proposals → box head → decode → per-class NMS → packed
    (B, M, 7) output) at the test-time proposal budget (6000→300). The
    packed output read IS the barrier — eval always fetches its bytes.
    """
    import jax

    from mx_rcnn_tpu.models.zoo import build_model, init_params
    from mx_rcnn_tpu.evaluation.tester import Predictor
    from mx_rcnn_tpu.train import precision

    policy = precision.policy_of(cfg)
    h, w = cfg.image.pad_shape
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    predictor = Predictor(model, params, cfg)
    rs = np.random.RandomState(0)
    images = rs.randn(batch_size, h, w, 3).astype(np.float32)
    im_info = np.asarray([[600, 1000, 1.0]] * batch_size, np.float32)

    with compile_track.count() as cc:
        compiled = predictor._detect.lower(params, images, im_info).compile()
        costs = obs_costs.executable_costs(compiled)
        flops = costs.get("flops", 0.0)
        for _ in range(3):
            np.asarray(compiled(params, images, im_info))  # warmup + barrier
    rates = []
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = compiled(params, images, im_info)
        np.asarray(out)
        rates.append(iters * batch_size / (time.perf_counter() - t0))
    img_s = statistics.median(rates)
    # The detect program is a plain jit on ONE device (no mesh), so the
    # measured rate already IS the per-chip rate — no device_count division
    # (unlike bench_config, whose step shards over all devices).
    mfu = obs_costs.mfu_from(flops, img_s / batch_size,
                             _peak_flops(policy.compute))
    return {
        "img_s_per_chip": round(img_s, 3),
        "batch_size": batch_size,
        "ms_per_img": round(1000.0 / img_s, 2),
        "mfu": round(mfu, 4) if mfu is not None else None,
        "compute_dtype": policy.short,
        "hbm_bytes": costs.get("hbm_bytes"),
        "compile_s": round(cc.seconds, 3),
        "n_executables": cc.n,
        "reps_img_s": [round(r, 2) for r in rates],
    }


def flush_partial(path: str, payload: dict):
    """Atomically (tmp + rename) persist the sweep's completed rows.

    Each config's result lands here the moment it completes, so a run
    killed at its time limit leaves its rows instead of losing the detail
    dict with the final print."""
    tmp = path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        # obs' last-resort coercion: an np/jnp scalar a recipe forgot to
        # round() must degrade in place, not kill the remaining sweep
        json.dump(payload, fh, indent=2, sort_keys=True,
                  default=_json_default)
        fh.write("\n")
    os.replace(tmp, path)


def run_sweep(configs: dict, runner, detail=None, elog=None,
              flush_path=None, attempts: int = 2,
              timeout_s: Optional[float] = None, on_row=None):
    """Measure each config, recording errors per-row (one failed cell
    must not lose the sweep) and flushing the accumulated detail dict to
    `flush_path` after EVERY config.

    With timeout_s (what ``main`` always passes) each config runs in a
    spawn child with a per-config deadline (resilience/isolate.py): the
    child owns the chip while it lives, and a hung compile forfeits ONE
    row (a structured timeout row) instead of the whole sweep. A timeout
    is never retried (a hung compile would just hang again); child error
    rows get the same `attempts` retry as in-process exceptions.
    timeout_s=None calls the runner in this process — the seam the unit
    tests drive with runners of their own; ``main`` never takes it."""
    detail = {} if detail is None else detail
    for name, cfg in configs.items():
        for _ in range(max(1, attempts)):
            if timeout_s is not None:
                from mx_rcnn_tpu.resilience.isolate import run_with_deadline

                detail[name] = run_with_deadline(runner, cfg, timeout_s,
                                                 label=name)
                if "timeout_s" in detail[name] or "error" not in detail[name]:
                    break
            else:
                try:
                    detail[name] = runner(cfg)
                    break
                except Exception as e:  # noqa: BLE001  # graftlint: disable=broad-except — record, don't lose the whole run
                    detail[name] = {"error": f"{type(e).__name__}: {e}"}
        if elog is not None:
            elog.emit("bench", config=name, **detail[name])
        if flush_path:
            flush_partial(flush_path, detail)
        if on_row is not None:
            # graftprof perf ledger: each completed row is appended the
            # moment it lands (same crash-durability contract as
            # flush_partial — a killed sweep keeps its ledger history).
            on_row(name, detail[name])
    return detail


def flagship_cells() -> dict:
    """name → (runner, cfg): the sweep ``main`` runs when given no other."""
    from mx_rcnn_tpu.config import generate_config

    # Flagship shapes: (600,1000)-scale COCO canvas padded to 640x1024,
    # full train proposal path. All five BASELINE families; C4 and FPN at
    # batch 1 (reference recipe, r01-r03 comparison point) and batch 2
    # (the Detectron-lineage recipe; amortizes fixed per-dispatch overhead).
    def cfg_for(net, b):
        return generate_config(net, "coco", **{
            "image.pad_shape": (640, 1024), "train.batch_images": b})

    train = {
        # BASELINE configs 1-2 (C4 lineage; headline family).
        "c4_r101": cfg_for("resnet101", 1),
        "c4_r101_b2": cfg_for("resnet101", 2),
        # BASELINE config 3 (acceptance config).
        "fpn_r101": cfg_for("resnet101_fpn", 1),
        "fpn_r101_b2": cfg_for("resnet101_fpn", 2),
        # The acceptance recipe (script/resnet101_fpn_coco.sh) pins
        # exact top-k; the preset default is approx. Bench both so the
        # recorded number matches what the recipe would run.
        "fpn_r101_b2_exact": generate_config("resnet101_fpn", "coco", **{
            "image.pad_shape": (640, 1024), "train.batch_images": 2,
            "network.proposal_topk": "exact"}),
        # BASELINE config 4 (+ b2: amortizes per-dispatch overhead and the
        # HBM-bound optimizer floor; PERF.md "batch>1 lever").
        "mask_r101_fpn": cfg_for("resnet101_fpn_mask", 1),
        "mask_r101_fpn_b2": cfg_for("resnet101_fpn_mask", 2),
        # BASELINE config 5 (stretch families) + batch-scaling recipes:
        # both are bounded at b1 by small-batch conv/matmul efficiency
        # plus the fixed ~6-7 ms AdamW update (PERF.md r4 decompositions).
        "vitdet_b": cfg_for("vitdet_b", 1),
        "vitdet_b_b2": cfg_for("vitdet_b", 2),
        "detr_r50": cfg_for("detr_r50", 1),
        "detr_r50_b4": cfg_for("detr_r50", 4),
        # BASELINE config 1 family (VGG-16; SURVEY §3 symbol_vgg.py) at
        # the VOC 600x1000 canvas. fc6 (25088x4096) dominates its head.
        "vgg16_voc": generate_config("vgg", "PascalVOC", **{
            "image.pad_shape": (608, 1024), "train.batch_images": 1}),
        "vgg16_voc_b2": generate_config("vgg", "PascalVOC", **{
            "image.pad_shape": (608, 1024), "train.batch_images": 2}),
        # graftcanvas (image.canvas_pack): whole-batch canvas packing
        # A/B against the bucketed b2 recipes above — ONE compiled
        # train-step shape regardless of scale/orientation mix, content
        # pixels instead of bucket pixels; rows land in the bench history via
        # on_row like every other recipe, and pad_waste in the row is
        # genuine canvas utilization (make_packed_batch). The C4 canvas
        # packs 2 × (600,1000)-scale landscapes with the 16px-aligned
        # gap; the FPN recipe keeps the multi-scale preset and the
        # derived never-overflow canvas (data/canvas.py) so its row
        # grades the compile-zoo collapse at the flagship recipe.
        "c4_r101_canvas": generate_config("resnet101", "coco", **{
            "train.batch_images": 2, "image.canvas_pack": True,
            "image.canvas_shape": (1248, 1024)}),
        "fpn_r101_canvas": generate_config("resnet101_fpn", "coco", **{
            "train.batch_images": 2, "image.canvas_pack": True}),
    }
    # Isolated optimizer-update microbench at full model size.
    update = {
        "update_r101": generate_config("resnet101", "coco", **{
            "image.pad_shape": (640, 1024),
            "train.compute_dtype": "f32"}),
        "update_detr": generate_config("detr_r50", "coco", **{
            "image.pad_shape": (640, 1024),
            "train.compute_dtype": "f32"}),
    }
    # Inference path (SURVEY §4.2 call stack: test.py → Predictor →
    # pred_eval): the jitted detect program at the test proposal budget.
    evals = {
        "eval_c4_r101": generate_config("resnet101", "coco", **{
            "image.pad_shape": (640, 1024)}),
        "eval_fpn_r101": generate_config("resnet101_fpn", "coco", **{
            "image.pad_shape": (640, 1024)}),
    }
    cells = {}
    for runner, group in ((bench_config, train),
                          (bench_update_config, update),
                          (bench_eval_config, evals)):
        cells.update({name: (runner, cfg) for name, cfg in group.items()})
    return cells


def main(cells: Optional[dict] = None, platform: str = "tpu",
         backend_deadline_s: float = 60.0) -> int:
    """Run the sweep; returns the process exit code (non-zero when the
    platform is not there or any cell errored). The arguments are what
    the CPU rehearsal in tests/test_bench.py passes — a tiny cell on
    "cpu" through this same default path; the command line always
    measures the flagship cells on a TPU, and a child waits a minute for
    it (a machine charged by the second is not waited for by the hour)."""
    from mx_rcnn_tpu.obs import open_event_log
    from mx_rcnn_tpu.obs import ledger as perf_ledger
    from mx_rcnn_tpu.obs import report as obs_report
    from mx_rcnn_tpu.resilience.isolate import run_with_deadline

    # Per-config deadline (resilience/isolate.py): each config runs in a
    # killable spawn child, so a hung compile forfeits one row.
    timeout_s = float(os.environ.get("MX_RCNN_BENCH_DEADLINE_S", "1800"))

    # The probe child goes first: no TPU is an error, not a row.
    want = (platform, backend_deadline_s)
    device = run_with_deadline(own_device, want, timeout_s,
                               label="device_probe")
    if "error" in device:
        print(f"bench: no {platform!r} device to measure on — "
              f"{device['error']}", file=sys.stderr)
        return 1

    # graftscope: the bench emits its measurements as events, then folds
    # them into <obs_dir>/report.json — the machine-readable artifact
    # alongside the printed JSON line (PERF.md). Override the directory
    # with MX_RCNN_BENCH_OBS.
    obs_dir = os.environ.get("MX_RCNN_BENCH_OBS", "bench_obs")
    elog = open_event_log(obs_dir, fresh=True)  # per-run artifact
    ledger_sha = perf_ledger._git_sha()
    elog.emit("run_meta", tool="bench",
              **({"git_sha": ledger_sha} if ledger_sha else {}), **device)

    cells = flagship_cells() if cells is None else cells
    # Partial-results flush: every completed row lands on disk immediately
    # (see flush_partial). The final report supersedes it.
    flush_path = os.environ.get("MX_RCNN_BENCH_PARTIAL",
                                os.path.join(obs_dir, "partial.json"))

    # graftprof perf ledger (obs/ledger.py): every completed row is also
    # appended to the repo's own cross-run history
    # (bench_obs/history.jsonl; MX_RCNN_PERF_LEDGER overrides, empty
    # disables — never the root PERF_LEDGER.jsonl, which is the
    # driver's). The round tag comes from MX_RCNN_BENCH_ROUND when set.
    ledger_path = os.environ.get("MX_RCNN_PERF_LEDGER",
                                 perf_ledger.default_path())
    bench_round = os.environ.get("MX_RCNN_BENCH_ROUND")
    if bench_round:
        bench_round = int(bench_round)
    elif ledger_path:
        # No explicit round: key this sweep as the next one after the
        # ledger's latest, so `ledger check` (which grades the latest
        # round against everything before) always sees these rows.
        prior = perf_ledger.latest_round(perf_ledger.load_rows(ledger_path))
        bench_round = (prior + 1) if prior is not None else None

    def ledger_row(name, row):
        if not ledger_path:
            return
        perf_ledger.append_rows(ledger_path, [perf_ledger.normalize_row(
            name, row, round_=bench_round, sha=ledger_sha,
            source="bench")])

    detail = run_sweep(
        {name: (runner, cfg, want)
         for name, (runner, cfg) in cells.items()},
        measure_cell, elog=elog, flush_path=flush_path,
        timeout_s=timeout_s, on_row=ledger_row)

    # Headline: best C4 recipe — same model, same shapes, same work per
    # optimizer step across recipes. None when no C4 cell has a number.
    c4 = {k: v for k, v in detail.items()
          if k.startswith("c4") and "img_s_per_chip" in v}
    headline_config = headline = headline_mfu = None
    if c4:
        headline_config = max(c4, key=lambda k: c4[k]["img_s_per_chip"])
        headline = c4[headline_config]["img_s_per_chip"]
        headline_mfu = c4[headline_config].get("mfu")
        ledger_row("headline", dict(device, img_s_per_chip=headline,
                                    mfu=headline_mfu))

    elog.close()
    # Fold the run DIR, not just this process's stream: a multi-host
    # bench leaves one events_p<k>.jsonl per host, and the blob should
    # summarize all of them (grafttower fleet_* aggregates ride in via
    # bench_blob when a --fleet fold adds them).
    summary = obs_report.summarize(obs_report.load_events(obs_dir))
    report_path = os.path.join(obs_dir, "report.json")
    with open(report_path, "w", encoding="utf-8") as fh:
        # the BENCH-compatible blob (top-level value/compile_count/...,
        # full summary under "detail") — what regression gates diff.
        json.dump(obs_report.bench_blob(summary), fh, indent=2,
                  sort_keys=True)
        fh.write("\n")

    errored = sorted(k for k, v in detail.items() if "error" in v)
    print(json.dumps({
        "metric": "faster_rcnn_r101_coco_train_img_per_sec_per_chip",
        "value": headline,
        "unit": "img/s/chip",
        # MFU is the PRIMARY efficiency number (measured against the
        # device's published bf16 peak); vs_baseline is a reconstructed
        # convenience ratio.
        "mfu": headline_mfu,
        "vs_baseline": (round(headline / REFERENCE_IMG_S, 3)
                        if headline is not None else None),
        "baseline_provenance": ("reconstructed (5.0 img/s assumed; the "
                                "reference publishes no throughput — "
                                "BASELINE.md). MFU is the measured number."),
        "headline_config": headline_config,
        "device": device,
        "errored": errored,
        # graftscope artifact: the same run folded by obs/report.py.
        "obs_report": report_path,
        "detail": detail,
    }))
    if errored:
        print(f"bench: {len(errored)} cell(s) errored: {errored}",
              file=sys.stderr)
    return 1 if errored else 0


if __name__ == "__main__":
    sys.exit(main())
