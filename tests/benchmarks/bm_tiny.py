"""What steers a benchmark run to a tiny size on the CPU in these tests: the
test steers the run, no option of the command does."""

TINY = dict(
    overrides={"network.depth": 50, "image.scales": ((96, 160),),
               "image.pad_shape": (96, 160),
               "train.rpn_pre_nms_top_n": 256, "train.rpn_post_nms_top_n": 64,
               "train.batch_rois": 32, "train.max_gt_boxes": 8,
               "network.anchor_scales": (2, 4, 8), "train.batch_images": 2},
    spec_overrides={"depth": 50, "scales": [96, 160], "canvas": [96, 160],
                    "max_gt_boxes": 8, "anchor_scales": [2, 4, 8],
                    "batch_images": 2,
                    "train": {"rpn_pre_nms_top_n": 256,
                              "rpn_post_nms_top_n": 64, "batch_rois": 32}},
    mix_overrides={"images": 12, "short_side": [60, 96],
                   "long_side": [100, 160], "warmup_steps": 1,
                   "min_step_s": 0.2, "trace_seconds": 2},
)


def tiny(batch_images=2):
    t = {k: dict(v) for k, v in TINY.items()}
    t["overrides"]["train.batch_images"] = batch_images
    t["spec_overrides"]["batch_images"] = batch_images
    return t
