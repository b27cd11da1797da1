"""The benchmark's own copy of the synthetic image/annotation generator and
of the pad-waste arithmetic.

Copied from ``mx_rcnn_tpu/tools/gen_synthetic_coco.py::_gen_image`` and
``mx_rcnn_tpu/obs/costs.py::batch_pad_waste`` so that the yardstick does not
move when the program does; ``tests/benchmarks/test_bm_copies.py`` holds the
copies to the originals as they are today, so a drift is seen and not silent.
"""

from __future__ import annotations

import numpy as np

COLORS = np.asarray([
    (220, 40, 40), (40, 200, 60), (50, 80, 230), (230, 200, 40),
    (230, 40, 200), (40, 220, 220), (140, 70, 20), (120, 120, 120),
    (250, 150, 50), (90, 40, 130), (170, 220, 120), (60, 120, 90),
    (240, 120, 160), (30, 40, 90), (200, 170, 130), (100, 200, 250),
], np.float32)


def draw_size(rs: np.random.RandomState, short=(360, 640), long=(480, 800),
              portrait_share: float = 0.35):
    """(h, w) as the original draws them: short side, long side, then one
    uniform draw that decides the orientation."""
    h = int(rs.randint(*short))
    w = int(rs.randint(*long))
    if rs.rand() < portrait_share:
        h, w = w, h
    return h, w


def gen_image(rs: np.random.RandomState, n_colors: int, size=None,
              boxes=(1, 6)):
    """One image (uint8 HWC RGB), its boxes as COCO xywh and its class ids.
    ``size=None`` draws the size from ``rs`` first, which is the original's
    sequence of draws; the traffic generator passes sizes of its own."""
    h, w = size if size is not None else draw_size(rs)
    img = rs.uniform(70, 160, (h, w, 3)).astype(np.float32)
    n = int(rs.randint(*boxes))
    out_boxes, classes = [], []
    for _ in range(n):
        bw = int(rs.randint(min(h, w) // 8, min(h, w) // 2))
        bh = int(rs.randint(min(h, w) // 8, min(h, w) // 2))
        x1 = int(rs.randint(0, w - bw))
        y1 = int(rs.randint(0, h - bh))
        cls = int(rs.randint(1, n_colors + 1))
        color = COLORS[cls - 1] + rs.uniform(-12, 12, 3)
        img[y1:y1 + bh, x1:x1 + bw] = color
        out_boxes.append((x1, y1, bw, bh))
        classes.append(cls)
    return np.clip(img, 0, 255).astype(np.uint8), out_boxes, classes


def pad_waste(im_info, canvas_hw, planes: int) -> float:
    """1 - real pixels / canvas pixels; ``im_info`` rows are [h, w, ...]."""
    rows = np.asarray(im_info, np.float64).reshape(-1, np.shape(im_info)[-1])
    real = float(np.sum(rows[:, 0] * rows[:, 1]))
    canvas = float(planes * canvas_hw[0] * canvas_hw[1])
    return round(1.0 - real / canvas, 4)
