"""The proposal NMS kernel's share of its roofline: the least time the chip
could take for the NMS the configuration asks for (benchmarks/flops.py) over
the summed device time of the kernel's events in the trace. Silent where the
trace names no such kernel."""
from benchmarks import flops, peaks, trace_reduce



def kernel_pattern(boxes_in: int) -> str:
    """The proposal NMS is the program's one Mosaic kernel; the program gives
    it no name of its own, so the trace shows it as a ``tpu_custom_call``
    whose result is (images, 1, boxes padded to 128)."""
    padded = -(-boxes_in // 128) * 128
    return rf"f32\[\d+,1,{padded}\] tpu_custom_call"


def read(run):
    if not run.get("trace"):
        return None
    steps = run["trace"]["step_runs"]
    t = run["spec"]["train"]
    spent = trace_reduce.kernel_seconds(
        run["trace"], kernel_pattern(t["rpn_pre_nms_top_n"]))
    if not spent or not steps:
        return None
    per_image = flops.nms_work(t["rpn_pre_nms_top_n"], t["rpn_post_nms_top_n"])
    # bound by compute at these sizes: 16 operations a pair, 20 bytes a box
    least, _ = flops.roofline_seconds(per_image,
                                      peaks.peak(run["device_kind"]))
    images_per_chip = steps * run["spec"]["batch_images"]
    return 100.0 * least * images_per_chip / spent
