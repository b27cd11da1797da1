"""Evaluate a trained detector on a dataset (reference entry point: test.py).

    python test.py --network resnet101 --dataset coco --image_set val2017 \
        --prefix model/e2e --epoch 10
"""

from __future__ import annotations

import argparse

import jax

from mx_rcnn_tpu.utils.compile_cache import enable_persistent_cache
from mx_rcnn_tpu.config import generate_config, parse_cli_overrides
from mx_rcnn_tpu.data.datasets import dataset_from_config
from mx_rcnn_tpu.data.loader import TestLoader
from mx_rcnn_tpu.evaluation.tester import Predictor, pred_eval
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.models.zoo import build_model, init_params
from mx_rcnn_tpu.train.checkpoint import load_checkpoint


def parse_args():
    p = argparse.ArgumentParser(description="Test a Faster R-CNN network")
    p.add_argument("--network", default="resnet101")
    p.add_argument("--dataset", default="coco")
    p.add_argument("--image_set", default=None)
    p.add_argument("--root_path", default=None)
    p.add_argument("--dataset_path", default=None)
    p.add_argument("--prefix", default="model/e2e")
    p.add_argument("--epoch", type=int, default=10)
    p.add_argument("--batch_size", type=int, default=1)
    p.add_argument("--thresh", type=float, default=1e-3)
    p.add_argument("--vis", action="store_true")
    p.add_argument("--out_json", default=None,
                   help="write COCO-format detections json")
    p.add_argument("--from-scratch", dest="from_scratch", action="store_true",
                   help="match a train_end2end.py --from-scratch checkpoint "
                        "(GroupNorm backbone)")
    p.add_argument("--set", dest="set_cfg", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="dotted config override, repeatable (must match "
                        "the training overrides that shape the graph)")
    return p.parse_args()


def main():
    enable_persistent_cache()
    args = parse_args()
    overrides = {}
    if args.root_path:
        overrides["dataset.root_path"] = args.root_path
    if args.dataset_path:
        overrides["dataset.dataset_path"] = args.dataset_path
    if args.from_scratch:
        overrides["network.norm"] = "group"
        overrides["network.freeze_at"] = 0
    overrides.update(parse_cli_overrides(args.set_cfg))
    cfg = generate_config(args.network, args.dataset, **overrides)
    image_set = args.image_set or cfg.dataset.test_image_set

    # graftscope (--set obs.enabled=true [--set obs.dir=...]): the eval
    # run gets a run_meta record and pred_eval emits the `eval` result.
    # Opened before the first device touch so graftguard backend
    # acquisition below has somewhere to emit backend_retry events.
    from mx_rcnn_tpu.obs import obs_from_config, run_meta_fields

    obs_log = obs_from_config(cfg, default_dir=f"{args.prefix}.obs")
    if cfg.resilience.backend_acquire:
        # graftguard: ride out a transiently unavailable backend instead
        # of dying on first touch (resilience/backend.py).
        from mx_rcnn_tpu.resilience import acquire_backend

        acquire_backend(cfg.resilience, elog=obs_log)

    ds = dataset_from_config(cfg.dataset, image_set)
    roidb = ds.gt_roidb()
    model = build_model(cfg)
    template = init_params(model, cfg, jax.random.PRNGKey(0))
    params, _ = load_checkpoint(
        args.prefix, args.epoch, template={"params": template},
        means=cfg.train.bbox_means, stds=cfg.train.bbox_stds,
        num_classes=cfg.dataset.num_classes)
    predictor = Predictor(model, params, cfg)
    loader = TestLoader(roidb, cfg, batch_size=args.batch_size)
    if obs_log.enabled:
        obs_log.emit("run_meta", **run_meta_fields(
            cfg, tool="test", prefix=args.prefix, epoch=args.epoch,
            image_set=image_set))
    results = pred_eval(predictor, loader, ds, vis=args.vis,
                        thresh=args.thresh, out_json=args.out_json,
                        event_log=obs_log)
    obs_log.close()
    logger.info("evaluation: %s", results)


if __name__ == "__main__":
    main()
