"""What steers the ViTDet cell to a tiny size on the CPU in these tests: a
ViT of width 64, 8 blocks (the global ones 1, 3, 5, 7, so windowed and
global blocks alternate), 2 heads, windows of 4 on the 8x8 token grid of a
128x128 canvas, 2 images, 64 candidates a level, 64 rois (16 the mask
branch's). The pyramid's and heads' widths are the published ones. The test
steers the run, no option of the command does."""

TINY_VITDET = dict(
    overrides={"network.vit_dim": 64, "network.vit_depth": 8,
               "network.vit_heads": 2, "network.vit_window": 4,
               "image.scales": ((128, 128),), "image.pad_shape": (128, 128),
               "train.fpn_rpn_pre_nms_per_level": 64,
               "train.rpn_post_nms_top_n": 64, "train.batch_rois": 64,
               "train.max_gt_boxes": 8, "network.anchor_scales": (4,),
               "train.batch_images": 2},
    spec_overrides={"vit_dim": 64, "vit_depth": 8, "vit_heads": 2,
                    "vit_window": 4, "vit_global_blocks": [1, 3, 5, 7],
                    "scales": [128, 128], "canvas": [128, 128],
                    "max_gt_boxes": 8, "anchor_scales": [4],
                    "batch_images": 2,
                    "train": {"fpn_rpn_pre_nms_per_level": 64,
                              "rpn_post_nms_top_n": 64, "batch_rois": 64}},
    mix_overrides={"images": 12, "short_side": [80, 128],
                   "long_side": [120, 128], "warmup_steps": 1,
                   "min_step_s": 0.04, "trace_seconds": 2},
)


def tiny_vitdet():
    return {k: dict(v) for k, v in TINY_VITDET.items()}
