"""The proposal NMS kernel's share of its roofline where it is launched once
a pyramid level: the least time the chip could take for the per-level NMS the
configuration asks for (benchmarks/flops_fpn.py) over the summed device time
of the ``nms_sweep`` events at the per-level shapes. Silent where the
configuration has no levels or the trace names no such kernel."""
import re

from benchmarks import flops, flops_fpn, peaks, trace_reduce

KERNEL = "nms_sweep"  # mx_rcnn_tpu/ops/nms_pallas.py::KERNEL_NAME


def kernel_pattern(candidates) -> str:
    """``%nms_sweep.N f32[images,1,boxes padded to 128] tpu_custom_call`` at
    any of the levels' sizes."""
    padded = sorted({-(-n // 128) * 128 for n in candidates})
    sizes = "|".join(str(n) for n in padded)
    return rf"{re.escape(KERNEL)}[\w.\-]* f32\[\d+,1,(?:{sizes})\] "


def read(run):
    spec = run["spec"]
    if not run.get("trace") or "rpn_levels" not in spec:
        return None
    steps = run["trace"]["step_runs"]
    spent = trace_reduce.kernel_seconds(
        run["trace"], kernel_pattern(flops_fpn.nms_candidates(spec).values()))
    if not spent or not steps:
        return None
    # bound by compute at these sizes: 16 operations a pair, 20 bytes a box
    least, _ = flops.roofline_seconds(flops_fpn.per_level_nms_work(spec),
                                      peaks.peak(run["device_kind"]))
    return 100.0 * least * steps * spec["batch_images"] / spent
