"""Packed pre-decoded shard format for the input pipeline.

SURVEY.md §8 hard-part 5: host cv2 JPEG decode + resize cannot sustain a
v5e chip (~19 img/s on one thread against a chip's 70 img/s). The
reference has no equivalent (MXNet's .rec IndexedRecordIO is the closest
ancestor); this is the TPU-era replacement: decode and resize ONCE at pack
time, then train-time loading is an mmap slice + normalize + pad.

Format (one directory):
  s{j}_shard_{k:04d}_{l|p}.npy
                      (N, Hb, Wb, 3) uint8 RGB, mmap-able; every image
                      is resized to training scale j and zero-padded to
                      its ORIENTED pad bucket (landscape `_l` and
                      portrait `_p` shards are packed separately so rows
                      are uniform). One shard set per cfg.image.scales
                      entry — multi-scale training draws a scale per
                      batch and reads the matching set.
  manifest.pkl        ONE dict per image: a `packed` map
                      {scale_idx: {file, index, hw, scale}} plus the
                      original roidb gt fields (boxes in ORIGINAL
                      coordinates, gt_classes, segmentations/gt_masks...).

`load_packed_roidb(dir)` returns a normal roidb whose entries carry the
`packed` scale map; data/loader.py::_load_roidb_entry takes the mmap fast
path for them — same AnchorLoader/ROIIter API, same batches, no other
changes.
"""

from __future__ import annotations

import os
import pickle
import threading
from typing import Dict, List, Optional

import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.data._native_img import normalize_pad
from mx_rcnn_tpu.logger import logger

_GT_KEYS = ("gt_classes", "segmentations", "gt_masks")


def _oriented_bucket(cfg: Config, scale_idx: int, landscape: bool) -> tuple:
    from mx_rcnn_tpu.data.loader import pad_shape_for

    h, w = pad_shape_for(cfg, scale_idx)
    h, w = min(h, w), max(h, w)
    return (h, w) if landscape else (w, h)


def write_packed_dataset(roidb: List[Dict], cfg: Config, out_dir: str,
                         scale_idx=None,
                         shard_images: int = 512) -> str:
    """Decode every roidb image once and write packed shards for EVERY
    training scale (multi-scale configs pack one shard set per
    cfg.image.scales entry — the loader draws a scale per batch and reads
    the matching set). scale_idx: an int or list restricts the packed
    scales (single-scale fixtures, tests).

    Only UNFLIPPED entries are packed (flip is a view at load time —
    append_flipped_images after load_packed_roidb works as usual).
    """
    from mx_rcnn_tpu.data.image import load_image, resize_image

    os.makedirs(out_dir, exist_ok=True)
    if scale_idx is None:
        scale_ids = list(range(len(cfg.image.scales)))
    elif isinstance(scale_idx, int):
        scale_ids = [scale_idx]
    else:
        scale_ids = [int(s) for s in scale_idx]
    # Group by orientation so every shard has uniform row shape.
    by_orient = {True: [], False: []}
    for i, entry in enumerate(roidb):
        if entry.get("flipped"):
            raise ValueError(
                "pack the UNFLIPPED roidb; apply append_flipped_images "
                "after load_packed_roidb")
        landscape = entry.get("width", 1) >= entry.get("height", 1)
        by_orient[landscape].append(i)

    # One manifest record per image, carrying every packed scale.
    recs: Dict[int, Dict] = {}
    for i, entry in enumerate(roidb):
        rec = {
            "packed": {},
            "height": entry.get("height"),
            "width": entry.get("width"),
            "boxes": np.asarray(entry["boxes"], np.float32),
            "flipped": False,
        }
        for k in _GT_KEYS:
            if k in entry:
                rec[k] = entry[k]
        recs[i] = rec

    # Scale is the INNER loop: each image decodes ONCE and feeds every
    # per-scale shard row from that decode (JPEG decode is the cost this
    # format exists to amortize — a scale-outer loop would multiply it).
    n_shards = 0
    for landscape, idxs in by_orient.items():
        shard_id = 0
        for lo in range(0, len(idxs), shard_images):
            chunk = idxs[lo:lo + shard_images]
            arrs = {s: np.zeros(
                (len(chunk), *_oriented_bucket(cfg, s, landscape), 3),
                np.uint8) for s in scale_ids}
            fnames = {s: (f"s{s}_shard_{shard_id:04d}_"
                          f"{'l' if landscape else 'p'}.npy")
                      for s in scale_ids}  # ONE name per (scale, shard)
            for row, i in enumerate(chunk):
                entry = roidb[i]
                img = (entry["image_data"].astype(np.float32)
                       if "image_data" in entry
                       else load_image(entry["image"]))
                for s in scale_ids:
                    target, max_size = cfg.image.scales[s]
                    rimg, scale = resize_image(img, target, max_size)
                    rh, rw = rimg.shape[:2]
                    bucket = arrs[s].shape[1:3]
                    if rh > bucket[0] or rw > bucket[1]:
                        raise ValueError(
                            f"resized image ({rh},{rw}) exceeds pad "
                            f"bucket {bucket} — check image.scales/"
                            "pad_shapes")
                    arrs[s][row, :rh, :rw] = np.clip(
                        np.rint(rimg), 0, 255).astype(np.uint8)
                    recs[i]["packed"][s] = {
                        "file": fnames[s],
                        "index": row, "hw": (rh, rw),
                        "scale": float(scale),
                    }
            for s in scale_ids:
                np.save(os.path.join(out_dir, fnames[s]), arrs[s])
                n_shards += 1
            shard_id += 1
    manifest = {
        # Pack-time geometry: load_packed_roidb validates it against the
        # training config so a pack made for another network/resolution
        # fails loudly instead of training at the wrong scale.
        "meta": {
            "scales": tuple(cfg.image.scales),
            "pad_shapes": tuple(cfg.image.pad_shapes),
            "pad_shape": tuple(cfg.image.pad_shape),
            "scale_ids": scale_ids,
        },
        "records": [recs[i] for i in range(len(roidb))],
    }
    mpath = os.path.join(out_dir, "manifest.pkl")
    with open(mpath, "wb") as f:
        pickle.dump(manifest, f, pickle.HIGHEST_PROTOCOL)
    logger.info("packed %d images x %d scale(s) into %d shards under %s",
                len(recs), len(scale_ids), n_shards, out_dir)
    return mpath


def load_packed_roidb(out_dir: str, cfg: Optional[Config] = None
                      ) -> List[Dict]:
    """Manifest → roidb (entries carry the `packed` scale map; shard
    paths resolved). With ``cfg``, the pack-time image geometry is
    validated against the training config — a shard set packed for a
    different network/resolution fails here, loudly, instead of silently
    training at the wrong scale."""
    with open(os.path.join(out_dir, "manifest.pkl"), "rb") as f:
        manifest = pickle.load(f)
    if not isinstance(manifest, dict) or "records" not in manifest:
        raise ValueError(
            f"{out_dir} holds a pre-multi-scale packed manifest (or not a "
            "packed dataset); re-pack with tools/pack_dataset.py")
    if cfg is not None:
        meta = manifest["meta"]
        want = {"scales": tuple(cfg.image.scales),
                "pad_shapes": tuple(cfg.image.pad_shapes),
                "pad_shape": tuple(cfg.image.pad_shape)}
        have = {k: tuple(meta[k]) for k in want}
        if want != have:
            raise ValueError(
                f"packed dataset geometry {have} does not match the "
                f"training config {want}; re-pack with the same "
                "network/image settings (tools/pack_dataset.py)")
        missing = (set(range(len(cfg.image.scales)))
                   - set(meta["scale_ids"]))
        if missing:
            raise ValueError(
                f"packed dataset covers scale_ids {meta['scale_ids']} "
                f"but the training config draws from "
                f"{len(cfg.image.scales)} scales (missing {sorted(missing)})"
                "; re-pack without scale_idx restriction")
    records = manifest["records"]
    for rec in records:
        for s in rec["packed"].values():
            s["file"] = os.path.join(out_dir, os.path.basename(s["file"]))
    return records


# -- load-time fast path (called from data/loader.py) -----------------------

_MMAPS: Dict[str, np.ndarray] = {}
_MMAP_LOCK = threading.Lock()


def _shard_mmap(path: str) -> np.ndarray:
    arr = _MMAPS.get(path)
    if arr is None:
        with _MMAP_LOCK:
            arr = _MMAPS.get(path)
            if arr is None:
                arr = np.load(path, mmap_mode="r")
                _MMAPS[path] = arr
    return arr


def load_packed_entry(entry: Dict, cfg: Config, scale_idx: int,
                      pad: Optional[tuple],
                      out: Optional[np.ndarray] = None):
    """Packed analog of loader._load_roidb_entry: mmap slice → f32 →
    normalize → pad, written into ``out`` (the image's row of the
    loader's batch buffer) when one is given. Returns (img, im_info,
    boxes, classes)."""
    from mx_rcnn_tpu.data.loader import pad_shape_for

    ref = entry["packed"].get(scale_idx)
    if ref is None:
        raise ValueError(
            f"scale_idx {scale_idx} is not packed (have "
            f"{sorted(entry['packed'])}); re-pack with "
            "write_packed_dataset covering every training scale")
    rh, rw = ref["hw"]
    scale = ref["scale"]
    img_u8 = np.asarray(_shard_mmap(ref["file"])[ref["index"], :rh, :rw])
    boxes = entry["boxes"].astype(np.float32).copy()
    flipped = bool(entry.get("flipped"))
    if flipped:
        w0 = entry["width"]
        x1 = boxes[:, 0].copy()
        boxes[:, 0] = w0 - boxes[:, 2] - 1
        boxes[:, 2] = w0 - x1 - 1
    boxes *= scale
    pad = pad if pad is not None else pad_shape_for(cfg, scale_idx)
    img = normalize_pad(img_u8, cfg.image.pixel_means,
                        cfg.image.pixel_stds, pad, flip=flipped, out=out)
    im_info = np.asarray([rh, rw, scale], np.float32)
    return img, im_info, boxes, entry["gt_classes"].astype(np.int32)


def load_packed_content(entry: Dict, cfg: Config, scale_idx: int,
                        fit: float = 1.0):
    """graftcanvas analog of load_packed_entry: mmap slice → f32 →
    normalize, UNPADDED — the content feeds a canvas placement directly
    (data/loader.py::_make_packed_batch), so the pack-time decode+resize
    is all the geometry work the hot path pays. fit < 1 (a scale-to-fit
    batch) re-resamples the stored content to the shrunken targets —
    rare by construction; the planner logs it.

    Returns (img f32 HWC unpadded, im_info [h, w, scale], boxes,
    classes) with `scale` the ORIGINAL-image → content scale (stored
    pack scale × any fit resample)."""
    from mx_rcnn_tpu.data.image import resize_image

    ref = entry["packed"].get(scale_idx)
    if ref is None:
        raise ValueError(
            f"scale_idx {scale_idx} is not packed (have "
            f"{sorted(entry['packed'])}); re-pack with "
            "write_packed_dataset covering every training scale")
    rh, rw = ref["hw"]
    scale = ref["scale"]
    img_u8 = np.asarray(_shard_mmap(ref["file"])[ref["index"], :rh, :rw])
    boxes = entry["boxes"].astype(np.float32).copy()
    flipped = bool(entry.get("flipped"))
    if flipped:
        w0 = entry["width"]
        x1 = boxes[:, 0].copy()
        boxes[:, 0] = w0 - boxes[:, 2] - 1
        boxes[:, 2] = w0 - x1 - 1
    if fit < 1.0:
        target, max_size = cfg.image.scales[scale_idx]
        arr = (img_u8[:, ::-1] if flipped else img_u8).astype(np.float32)
        arr, s2 = resize_image(arr, max(1, int(round(target * fit))),
                               max(1, int(round(max_size * fit))))
        scale *= s2
        img = normalize_pad(np.ascontiguousarray(arr, np.float32),
                            cfg.image.pixel_means, cfg.image.pixel_stds,
                            arr.shape[:2])
    else:
        # Fused u8→f32 mirror+normalize, pad == content dims — the same
        # one-pass kernel the bucketed mmap path uses.
        img = normalize_pad(img_u8, cfg.image.pixel_means,
                            cfg.image.pixel_stds, (rh, rw), flip=flipped)
    boxes *= scale
    im_info = np.asarray([img.shape[0], img.shape[1], scale], np.float32)
    return img, im_info, boxes, entry["gt_classes"].astype(np.int32)
