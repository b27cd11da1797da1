"""The one general traffic generator: a mix is a JSON file of parameters
under ``benchmarks/traffic/`` and this module turns it, with ``--seed``, into
images and annotations. A new mix is a new data file, never new code.

Every seed gets the SAME multiset of image sizes and box counts (drawn once
from the mix's ``size_seed``) in another order and with other pixels, so the
work of a run does not depend on the seed.
"""

from __future__ import annotations

import numpy as np

from benchmarks import synth


def _size(rs, mix: dict):
    """(h, w): the generator's two draws, then ``portrait_share`` of the
    images upright (h > w) and the rest lying (h <= w). The generator's own
    ranges overlap, so its short side can come out the longer: the mix's
    share decides the orientation, not the draw."""
    a, b = synth.draw_size(rs, tuple(mix["short_side"]),
                           tuple(mix["long_side"]), portrait_share=0.0)
    lo, hi = min(a, b), max(a, b)
    return (hi, lo) if rs.rand() < float(mix["portrait_share"]) else (lo, hi)


def make_roidb(mix: dict, seed: int) -> list:
    """``mix['images']`` roidb records with the pixels embedded
    (``image_data``), boxes xyxy inclusive in original coordinates — the
    record layout the program's packer and loaders document."""
    n = int(mix["images"])
    fixed = np.random.RandomState(int(mix.get("size_seed", 0)))
    sizes = [_size(fixed, mix) for _ in range(n)]
    rs = np.random.RandomState(seed % (2 ** 32))
    order = rs.permutation(n)
    lo, hi = mix["boxes"]
    roidb = []
    for k in order:
        img, xywh, classes = synth.gen_image(
            rs, int(mix["colors"]), size=sizes[k], boxes=(lo, hi + 1))
        b = np.asarray(xywh, np.float32).reshape(-1, 4)
        boxes = np.stack([b[:, 0], b[:, 1], b[:, 0] + b[:, 2] - 1,
                          b[:, 1] + b[:, 3] - 1], axis=1)
        roidb.append({
            "image_data": img, "height": img.shape[0], "width": img.shape[1],
            "boxes": boxes.astype(np.float32),
            "gt_classes": np.asarray(classes, np.int32), "flipped": False})
    return roidb
