"""Operations Mask R-CNN's forward and backward passes REQUIRE per image
(benchmarks/flops_mask.py: the pyramid detector and the mask branch over its
foreground SLOTS, live or not: the program's shapes are static) x
images/s/chip of this run, over the chip's bf16 peak."""
from benchmarks import flops_mask, peaks


def read(run):
    spec = run["spec"]
    if spec.get("flops") != "mask_flops":
        return None  # another family's work is another reader's to count
    try:
        peak = peaks.peak(run["device_kind"])
    except KeyError:
        return None  # the CPU rehearsal: no published peak, no share of one
    need = flops_mask.mask_flops(spec, "train", spec["train"]["batch_rois"])
    return 100.0 * need * run["rate"] / peak["bf16_flops"]
