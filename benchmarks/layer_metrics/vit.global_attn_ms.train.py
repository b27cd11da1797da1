"""Device ms a step of the ops whose scope path holds ``global_attn``
(mx_rcnn_tpu/obs/profile.py::VIT_STAGES): the global blocks' attention,
norm1 through proj, forward and backward. Silent where the program has no
such scope."""
from benchmarks import trace_scopes_vit


def read(run):
    return trace_scopes_vit.scope_ms(run, "global_attn")
