"""Device ms a step of the ops whose scope path holds ``vit_mlp``
(mx_rcnn_tpu/obs/profile.py::VIT_STAGES): every block's MLP, norm2 through
mlp2, forward and backward. Silent where the program has no such scope."""
from benchmarks import trace_scopes_vit


def read(run):
    return trace_scopes_vit.scope_ms(run, "vit_mlp")
