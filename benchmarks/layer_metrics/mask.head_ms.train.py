"""Device ms per step of the ops whose scope path holds ``mask_head``: the
four 3x3 convolutions, the transposed convolution and the 1x1 convolution to
a map a class, forward and backward."""
from benchmarks import trace_scopes_mask


def read(run):
    return trace_scopes_mask.branch_ms(run, "head")
