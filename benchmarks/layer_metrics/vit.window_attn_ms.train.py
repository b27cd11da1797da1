"""Device ms a step of the ops whose scope path holds ``window_attn``
(mx_rcnn_tpu/obs/profile.py::VIT_STAGES): the windowed blocks' attention,
norm1 through the window unpartition, forward and backward. Silent where
the program has no such scope."""
from benchmarks import trace_scopes_vit


def read(run):
    return trace_scopes_vit.scope_ms(run, "window_attn")
