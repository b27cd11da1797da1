"""What steers the pyramid cell to a tiny size on the CPU in these tests:
R-50, a 128x192 canvas, 2 images, 64 candidates a level, 32 rois. The test
steers the run, no option of the command does."""

TINY_FPN = dict(
    overrides={"network.depth": 50, "image.scales": ((128, 192),),
               "image.pad_shape": (128, 192),
               "train.fpn_rpn_pre_nms_per_level": 64,
               "train.rpn_post_nms_top_n": 64, "train.batch_rois": 32,
               "train.max_gt_boxes": 8, "network.anchor_scales": (4,),
               "train.batch_images": 2},
    spec_overrides={"depth": 50, "scales": [128, 192], "canvas": [128, 192],
                    "max_gt_boxes": 8, "anchor_scales": [4],
                    "batch_images": 2,
                    "train": {"fpn_rpn_pre_nms_per_level": 64,
                              "rpn_post_nms_top_n": 64, "batch_rois": 32}},
    mix_overrides={"images": 12, "short_side": [80, 128],
                   "long_side": [120, 192], "warmup_steps": 1,
                   "min_step_s": 0.04, "trace_seconds": 2},
)


def tiny_fpn():
    return {k: dict(v) for k, v in TINY_FPN.items()}
