"""Train-step profiler — stage breakdown + optional jax.profiler trace.

The reference's only perf instrumentation is the Speedometer samples/sec log
(rcnn/core/callback.py); MXNet's engine profiler exists below it but is never
wired into the repo (SURVEY.md §6). This tool is the TPU build's replacement:

  python -m mx_rcnn_tpu.tools.profile --network resnet101 --dataset coco
  python -m mx_rcnn_tpu.tools.profile --trace-dir /tmp/trace  # TensorBoard

Stage timings are additive prefixes of the train forward (backbone → +rpn →
+anchor targets → +proposals/NMS → full fwd → train step), each jitted
separately, so the deltas bound each stage's cost. The absolute numbers
include per-call transfer overhead for any large outputs — the train-step row (donated state, scalar outputs) is
the honest end-to-end number; bench.py reports the same quantity.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.models import faster_rcnn as F
from mx_rcnn_tpu.models.faster_rcnn import FasterRCNN, build_model, init_params
from mx_rcnn_tpu.ops.proposal import generate_proposals


def synthetic_batch(cfg, batch_images=None):
    b = batch_images or cfg.train.batch_images
    h, w = cfg.image.pad_shape
    g = cfg.train.max_gt_boxes
    rs = np.random.RandomState(0)
    n = 8
    boxes = np.zeros((b, g, 4), np.float32)
    for i in range(b):
        x1 = rs.uniform(0, w - 200, n)
        y1 = rs.uniform(0, h - 200, n)
        boxes[i, :n] = np.stack(
            [x1, y1, x1 + rs.uniform(50, 199, n), y1 + rs.uniform(50, 199, n)],
            axis=1)
    valid = np.zeros((b, g), bool)
    valid[:, :n] = True
    classes = np.zeros((b, g), np.int32)
    classes[:, :n] = rs.randint(1, cfg.dataset.num_classes, (b, n))
    return {
        "image": rs.randn(b, h, w, 3).astype(np.float32),
        "im_info": np.asarray([[h * 0.94, w * 0.98, 1.0]] * b, np.float32),
        "gt_boxes": boxes,
        "gt_classes": classes,
        "gt_valid": valid,
    }


def _timeit(name, fn, *args, iters=5, elog=None):
    out = jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters * 1000
    print(f"{name:36s} {dt:9.2f} ms")
    if elog is not None and elog.enabled:
        elog.emit("step", label=name, step_ms=round(dt, 3), iters=iters)
    return dt


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--network", default="resnet101")
    ap.add_argument("--dataset", default="coco")
    ap.add_argument("--pad", type=int, nargs=2, default=(640, 1024),
                    metavar=("H", "W"))
    ap.add_argument("--batch-images", type=int, default=1)
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--trace-dir", default=None,
                    help="capture a jax.profiler trace of the train step "
                         "(view with TensorBoard)")
    ap.add_argument("--stages", action="store_true",
                    help="also time the additive stage prefixes (several "
                         "extra compiles)")
    ap.add_argument("--obs-dir", dest="obs_dir", default=None,
                    help="also write a graftscope event stream (one `step` "
                         "event per timed row + every compile) here; fold "
                         "with `python -m mx_rcnn_tpu.obs.report`")
    args = ap.parse_args(argv)

    cfg = generate_config(
        args.network, args.dataset,
        **{"image.pad_shape": tuple(args.pad),
           "train.batch_images": args.batch_images})
    elog = None
    if args.obs_dir:
        from mx_rcnn_tpu.obs import compile_track, open_event_log, \
            run_meta_fields

        elog = open_event_log(args.obs_dir, fresh=True)  # per-run artifact
        elog.emit("run_meta", **run_meta_fields(
            cfg, tool="profile", batch_size=args.batch_images))
        compile_track.activate(elog)
    model = build_model(cfg)
    params = init_params(model, cfg, jax.random.PRNGKey(0))
    batch = synthetic_batch(cfg)
    rng = jax.random.PRNGKey(1)

    if args.stages:
        def backbone(p, bt):
            return jnp.sum(model.apply(p, bt["image"],
                                       method=FasterRCNN.extract)
                           .astype(jnp.float32))
        _timeit("backbone fwd", jax.jit(backbone), params, batch,
                iters=args.iters, elog=elog)

        def with_rpn(p, bt):
            _, cl, bx, _ = F._backbone_rpn(model, p, bt["image"], cfg)
            return jnp.sum(cl.astype(jnp.float32)), jnp.sum(
                bx.astype(jnp.float32))
        _timeit("+rpn heads", jax.jit(with_rpn), params, batch,
                iters=args.iters, elog=elog)

        def with_targets(p, bt, r):
            _, cl, bx, anch = F._backbone_rpn(model, p, bt["image"], cfg)
            t = F._assign_anchors_batch(anch, bt["gt_boxes"],
                                        bt["gt_valid"], bt["im_info"],
                                        r, cfg)
            return jnp.sum(t.labels), jnp.sum(cl.astype(jnp.float32))
        _timeit("+anchor targets", jax.jit(with_targets), params, batch, rng,
                iters=args.iters, elog=elog)

        def with_proposals(p, bt, r):
            _, cl, bx, anch = F._backbone_rpn(model, p, bt["image"], cfg)
            prob = F._rpn_softmax(cl, model.num_anchors)
            rois, rv, _ = generate_proposals(
                prob, bx, bt["im_info"], anch,
                pre_nms_top_n=cfg.train.rpn_pre_nms_top_n,
                post_nms_top_n=cfg.train.rpn_post_nms_top_n,
                nms_thresh=cfg.train.rpn_nms_thresh,
                min_size=cfg.train.rpn_min_size,
                topk_impl=cfg.network.proposal_topk)
            return jnp.sum(rois), jnp.sum(rv)
        _timeit("+proposals (topk+nms)", jax.jit(with_proposals), params,
                batch, rng, iters=args.iters, elog=elog)

        def full_fwd(p, bt, r):
            loss, _ = F.forward_train(model, p, bt, r, cfg)
            return loss
        _timeit("full fwd (loss)", jax.jit(full_fwd), params, batch, rng,
                iters=args.iters, elog=elog)

    # The honest end-to-end number: full train step, donated state, scalar
    # metric outputs only (same quantity bench.py reports).
    from mx_rcnn_tpu.train.optimizer import build_optimizer
    from mx_rcnn_tpu.train.step import create_train_state, make_train_step
    tx = build_optimizer(cfg, params, steps_per_epoch=1000)
    state = create_train_state(params, tx)
    step_fn = make_train_step(model, cfg)

    def run_step(s, bt, r):
        return step_fn(s, bt, r)

    # Two warmups: the second sees the donated device-layout state.
    for _ in range(2):
        rng, k = jax.random.split(rng)
        state, metrics = run_step(state, batch, k)
        jax.block_until_ready(metrics["TotalLoss"])
    t0 = time.perf_counter()
    for _ in range(args.iters):
        rng, k = jax.random.split(rng)
        state, metrics = run_step(state, batch, k)
    jax.block_until_ready(metrics["TotalLoss"])
    dt = (time.perf_counter() - t0) / args.iters * 1000
    b = cfg.train.batch_images
    print(f"{'train step (donated)':36s} {dt:9.2f} ms   "
          f"{b / dt * 1000:6.2f} img/s/chip")
    if elog is not None:
        elog.emit("step", label="train step (donated)",
                  step_ms=round(dt, 3), iters=args.iters,
                  samples_per_sec=round(b / dt * 1000, 3))

    if args.trace_dir:
        with jax.profiler.trace(args.trace_dir):
            for _ in range(3):
                rng, k = jax.random.split(rng)
                state, metrics = run_step(state, batch, k)
            jax.block_until_ready(metrics["TotalLoss"])
        print(f"trace written to {args.trace_dir}")
        # graftprof: fold the capture by the step's named stages
        # (obs/profile.py) so the split is readable without TensorBoard.
        from mx_rcnn_tpu.obs.profile import summarize_trace

        summary = summarize_trace(args.trace_dir)
        if summary:
            print("trace stages (ms): "
                  + ", ".join(f"{k}={v}"
                              for k, v in summary["stages"].items())
                  + f"; unscoped={summary['unscoped_ms']} of "
                  f"{summary['total_ms']}")
            if elog is not None and elog.enabled:
                elog.emit("trace", dir=args.trace_dir, reason="manual",
                          summary=summary)

    if elog is not None:
        from mx_rcnn_tpu.obs import compile_track

        compile_track.deactivate()
        elog.close()
        print(f"graftscope events written to {elog.path} "
              "(fold with `python -m mx_rcnn_tpu.obs.report`)")


if __name__ == "__main__":
    main()
