"""Operations ViTDet's Mask R-CNN REQUIRES a step (benchmarks/
flops_vitdet.py, per image x the images of a step) over the step's device
time in the traced interval (``step.device_ms.train``), over the chip's
bf16 peak. Silent for another family's configuration."""
from benchmarks import flops_vitdet, peaks


def read(run):
    spec = run["spec"]
    if spec.get("flops") != "vitdet_flops" or not run.get("trace"):
        return None
    steps = run["trace"]["step_runs"]
    try:
        peak = peaks.peak(run["device_kind"])
    except KeyError:
        return None  # the CPU rehearsal: no published peak, no share of one
    if not steps or not run["trace"]["busy_s"]:
        return None
    need = flops_vitdet.vitdet_flops(spec, "train") * spec["batch_images"]
    step_s = run["trace"]["busy_s"] / steps
    return 100.0 * need / step_s / peak["bf16_flops"]
