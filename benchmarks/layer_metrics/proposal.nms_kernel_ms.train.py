"""Device ms per step of every ``nms_sweep`` launch together: the kernel's
part of ``stage.proposal_ms.train``; the rest of that stage is the top-k,
sort and scatter around it. Silent where the trace names no such kernel."""
from benchmarks import trace_reduce

KERNEL = "nms_sweep"  # mx_rcnn_tpu/ops/nms_pallas.py::KERNEL_NAME


def read(run):
    if not run.get("trace") or not run["trace"]["step_runs"]:
        return None
    spent = trace_reduce.kernel_seconds(run["trace"], rf"%?{KERNEL}[\w.\-]* ")
    return 1e3 * spent / run["trace"]["step_runs"] if spent else None
