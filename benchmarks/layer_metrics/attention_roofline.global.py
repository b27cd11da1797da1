"""The global blocks' attention's share of its roofline: the least time the
chip could take for the work counted from the shapes alone (benchmarks/
flops_vitdet.py::global_attention_work: QK^T, PV and the decomposed bias's
two products, forward and backward, against the least bytes any
implementation moves: no N x N matrix), over the device time of the
``global_attn`` scope a step (which also holds norm1, qkv and proj). Reads
the same work whatever implements the attention. Silent where the program
has no such scope or the configuration no global block."""
from benchmarks import flops, flops_vitdet, peaks, trace_scopes_vit


def read(run):
    spec = run["spec"]
    if not spec.get("vit_global_blocks"):
        return None
    ms = trace_scopes_vit.scope_ms(run, "global_attn")
    if not ms:
        return None
    try:
        peak = peaks.peak(run["device_kind"])
    except KeyError:
        return None
    work = flops_vitdet.global_attention_work(spec)
    least, _ = flops.roofline_seconds(
        {k: v * spec["batch_images"] for k, v in work.items()}, peak)
    return 100.0 * least / (ms / 1e3)
