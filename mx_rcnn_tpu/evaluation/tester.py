"""Inference core: Predictor, im_detect, pred_eval, proposal dumping.

Reference: rcnn/core/tester.py — Predictor (module bound for test shapes),
im_detect (forward → decode → clip), pred_eval (loop over TestLoader,
per-class threshold + NMS + max_per_image, then imdb.evaluate_detections),
im_proposal/generate_proposals (RPN proposal dump for alternate training).

TPU deltas: decode + per-class NMS run INSIDE the jitted forward
(ops/detection.py::multiclass_nms); only ONE final packed
(B, max_per_image, 7) tensor reaches the host per batch. Batch > 1 inference is supported (the reference's
TestLoader is batch-1 only).
"""

from __future__ import annotations

import pickle
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.data.loader import TestLoader
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.models.zoo import forward_rpn, forward_test
from mx_rcnn_tpu.ops.detection import multiclass_nms


class Predictor:
    """Jitted test-forward + post-processing bound to one param set.

    Reference: rcnn/core/tester.py::Predictor (an mx.mod.Module bound with
    max test shapes); here binding = jit caching per input shape.
    """

    def __init__(self, model, params, cfg: Config):
        self.model = model
        self.params = params
        self.cfg = cfg
        self.use_mask = bool(getattr(model, "use_mask", False))

        def _detect(params, image, im_info):
            rois, roi_valid, scores, boxes = forward_test(
                model, params, image, im_info, cfg)
            dets = multiclass_nms(
                scores, boxes, roi_valid,
                score_thresh=cfg.test.score_thresh,
                nms_thresh=cfg.test.nms_thresh,
                max_per_image=cfg.test.max_per_image,
            )
            # Pack into ONE (B, M, 7) tensor [cls, score, x1, y1, x2, y2,
            # valid] so a single device→host read returns everything
            # (each separate read is a transfer and a sync of its own).
            return jnp.concatenate(
                [dets.classes[..., None].astype(jnp.float32),
                 dets.scores[..., None],
                 dets.boxes,
                 dets.valid[..., None].astype(jnp.float32)], axis=-1)

        def _propose(params, image, im_info):
            # RPN-only path: backbone + RPN + proposal op, no box head
            # (reference: tester.py im_proposal runs the rpn-test symbol).
            return forward_rpn(model, params, image, im_info, cfg)

        def _masks(params, image, det_boxes, det_classes, det_valid):
            from mx_rcnn_tpu.models.fpn import forward_test_masks

            return forward_test_masks(model, params, image, det_boxes,
                                      det_classes, det_valid)

        self._detect = jax.jit(_detect)
        self._propose = jax.jit(_propose)
        self._masks = jax.jit(_masks) if self.use_mask else None

    def detect(self, image: np.ndarray, im_info: np.ndarray):
        """Packed (B, M, 7) detections [cls, score, x1, y1, x2, y2, valid],
        network-input coordinates, still on device. Host numpy args go
        straight to the jitted call (one dispatch does both transfers)."""
        return self._detect(self.params, image, im_info)

    def propose(self, image: np.ndarray, im_info: np.ndarray):
        return self._propose(self.params, jnp.asarray(image), jnp.asarray(im_info))

    def mask_probs(self, image: np.ndarray, det_boxes: np.ndarray,
                   det_classes: np.ndarray, det_valid: np.ndarray):
        """(B, D, m, m) mask probabilities for NETWORK-scale detection boxes
        (the Mask R-CNN inference tail; see models/fpn.forward_test_masks)."""
        return self._masks(self.params, jnp.asarray(image),
                           jnp.asarray(det_boxes), jnp.asarray(det_classes),
                           jnp.asarray(det_valid))


def im_detect(predictor: Predictor, image: np.ndarray, im_info: np.ndarray,
              scale: float) -> List[np.ndarray]:
    """Detections for one batch, mapped back to ORIGINAL image coordinates.

    Returns per-image arrays (n, 6): [cls, score, x1, y1, x2, y2].
    """
    return _split_packed(
        np.asarray(predictor.detect(image, im_info)), scale)


def _split_packed(packed: np.ndarray, scale: float) -> List[np.ndarray]:
    """(B, M, 7) packed detections → per-image (n, 6) arrays at 1/scale."""
    out = []
    for b in range(packed.shape[0]):
        v = packed[b, :, 6] > 0.5
        arr = packed[b, v, :6]  # advanced indexing -> fresh array
        arr[:, 2:6] /= scale
        out.append(arr)
    return out


def pred_eval(predictor: Predictor, test_loader: TestLoader, imdb,
              vis: bool = False, thresh: float = 0.0,
              out_json: Optional[str] = None,
              vis_dir: str = "vis", pipeline_depth: int = 3,
              event_log=None) -> Dict[str, float]:
    """Evaluate over an imdb (reference: tester.py::pred_eval).

    Builds all_boxes[class][image] = (n, 5) [x1..y2, score] in original
    coords and hands it to imdb.evaluate_detections. vis=True writes box
    overlays (score ≥ 0.5) to vis_dir, as the reference's vis branch shows
    them interactively.

    pipeline_depth: how many batches of device work stay enqueued before
    the oldest result is read back, so the read and the host
    post-processing overlap the next batches' device work. 1 = fully serial
    (enqueue, then immediately read); 2 ≈ the previous fixed 1-in-flight
    pipeline.

    event_log: optional graftscope EventLog — the pass then ends with an
    ``eval`` event carrying the result dict and wall time (obs/report.py
    folds these into the run summary).
    """
    import time as _time

    t_start = _time.perf_counter()
    num_classes = imdb.num_classes
    num_images = len(test_loader.roidb)
    all_boxes: List[List] = [
        [np.zeros((0, 5), np.float32) for _ in range(num_images)]
        for _ in range(num_classes)
    ]
    want_masks = predictor.use_mask
    all_masks: List[List] = [
        [[] for _ in range(num_images)] for _ in range(num_classes)
    ] if want_masks else None
    done = 0

    def _process(dev_packed, batch, metas):
        nonlocal done
        # The host read happens HERE — one batch after the detect was
        # enqueued, so it overlaps the next batch's device work.
        per_image = _split_packed(np.asarray(dev_packed), metas[0]["scale"])
        if vis:
            _vis_batch(batch, metas, per_image, imdb, test_loader, vis_dir)
        if want_masks:
            per_image_rles = _batch_mask_rles(
                predictor, batch, metas, per_image, test_loader)
        # per-image scales differ; recompute per image (the packed split
        # used the first scale — fix up here for the general batch case).
        for i, meta in enumerate(metas):
            if not meta["real"]:
                continue
            dets = per_image[i]
            if metas[0]["scale"] != meta["scale"]:
                dets = dets.copy()
                dets[:, 2:6] *= metas[0]["scale"] / meta["scale"]
            img_idx = meta["index"]
            for c in range(1, num_classes):
                sel = (dets[:, 0] == c) & (dets[:, 1] >= thresh)
                cls_dets = np.concatenate(
                    [dets[sel, 2:6], dets[sel, 1:2]], axis=1)
                all_boxes[c][img_idx] = cls_dets.astype(np.float32)
                if want_masks:
                    rles = per_image_rles[i]
                    all_masks[c][img_idx] = [
                        rles[j] for j in np.nonzero(sel)[0]]
            done += 1
        if done % 100 < len(metas):
            logger.info("im_detect: %d/%d", done, num_images)

    # N-deep pipeline: keep up to pipeline_depth batches of device work
    # in flight before reading the oldest result, so host post-processing
    # and the device→host reads overlap device compute.
    from collections import deque

    pending = deque()
    for batch, metas in test_loader:
        pending.append((predictor.detect(batch["image"], batch["im_info"]),
                        batch, metas))
        if len(pending) >= max(1, pipeline_depth):
            _process(*pending.popleft())
    while pending:
        _process(*pending.popleft())
    kwargs = {}
    if out_json:
        kwargs["out_json"] = out_json
    if want_masks and hasattr(imdb, "evaluate_segmentations"):
        results = imdb.evaluate_segmentations(all_boxes, all_masks, **kwargs)
    else:
        if want_masks:
            logger.warning("%s has no segm evaluation; reporting boxes only",
                           type(imdb).__name__)
        results = imdb.evaluate_detections(all_boxes, **kwargs)
    if event_log is not None and event_log.enabled:
        event_log.emit("eval", images=num_images, results=results,
                       wall_s=round(_time.perf_counter() - t_start, 3))
    return results


def _batch_mask_rles(predictor: Predictor, batch, metas, per_image,
                     test_loader):
    """Run the mask head on one batch's final detections and paste to
    original-size RLEs. Returns per image a list of RLEs aligned with
    per_image[i]'s det rows."""
    from mx_rcnn_tpu.masks.paste import paste_masks_to_rles

    d = predictor.cfg.test.max_per_image
    b = batch["image"].shape[0]
    det_boxes = np.zeros((b, d, 4), np.float32)
    det_classes = np.zeros((b, d), np.int32)
    det_valid = np.zeros((b, d), bool)
    for i, meta in enumerate(metas):
        dets = per_image[i]
        n = min(len(dets), d)
        # per_image is at ORIGINAL scale (divided by metas[0]); map back to
        # this image's network-input coords for pooling.
        det_boxes[i, :n] = dets[:n, 2:6] * metas[0]["scale"]
        det_classes[i, :n] = dets[:n, 0]
        det_valid[i, :n] = True
    probs = np.asarray(predictor.mask_probs(
        batch["image"], det_boxes, det_classes, det_valid))
    out = []
    for i, meta in enumerate(metas):
        if not meta["real"]:
            out.append([])
            continue
        entry = test_loader.roidb[meta["index"]]
        h, w = entry["height"], entry["width"]
        dets = per_image[i]
        n = min(len(dets), d)
        # Paste with ORIGINAL-scale boxes (same rows the eval consumes).
        boxes_orig = dets[:n, 2:6] * (metas[0]["scale"] / meta["scale"])
        out.append(paste_masks_to_rles(probs[i, :n], boxes_orig, h, w))
    return out


def _vis_batch(batch, metas, per_image, imdb, test_loader, vis_dir):
    """Save detection overlays for one batch (score ≥ 0.5)."""
    from mx_rcnn_tpu.data.image import transform_inverse
    from mx_rcnn_tpu.utils.vis import save_vis

    cfg = test_loader.cfg
    class_names = getattr(imdb, "classes", ()) or tuple(
        str(i) for i in range(imdb.num_classes))
    for i, meta in enumerate(metas):
        if not meta["real"]:
            continue
        dets = per_image[i]
        dets = dets[dets[:, 1] >= 0.5].copy()
        # im_detect divided every image's boxes by metas[0]["scale"]; undo
        # exactly that to return to network-input coords.
        dets[:, 2:6] *= metas[0]["scale"]
        img = transform_inverse(batch["image"][i], cfg.image.pixel_means,
                                cfg.image.pixel_stds)
        save_vis(img, dets, class_names,
                 f"{vis_dir}/{meta['index']}.jpg")


def generate_proposals(predictor: Predictor, test_loader: TestLoader,
                       rpn_file: str) -> List[np.ndarray]:
    """Run the RPN over an imdb and dump proposals (reference:
    tester.py::generate_proposals writing *_rpn.pkl for alternate training).

    Saves a list (image order) of (n, 5) [x1,y1,x2,y2,score] proposal arrays
    at ORIGINAL scale (consumers use [:, :4]; scores kept for inspection).
    """
    num_images = len(test_loader.roidb)
    out: List[Optional[np.ndarray]] = [None] * num_images
    for batch, metas in test_loader:
        rois, roi_valid, roi_scores = predictor.propose(
            batch["image"], batch["im_info"])
        rois = np.asarray(rois)
        roi_valid = np.asarray(roi_valid)
        roi_scores = np.asarray(roi_scores)
        for i, meta in enumerate(metas):
            if not meta["real"]:
                continue
            v = roi_valid[i]
            out[meta["index"]] = np.concatenate(
                [rois[i][v] / meta["scale"], roi_scores[i][v, None]],
                axis=1).astype(np.float32)
    with open(rpn_file, "wb") as f:
        pickle.dump(out, f, pickle.HIGHEST_PROTOCOL)
    logger.info("wrote %d proposal sets to %s", num_images, rpn_file)
    return out
