"""Device ms per step of the ops whose scope path holds ``mask_align``: the
mask branch's 14x14 pooling of its foreground rois, forward and backward
(the accepted stage readers see the same ops as ``roi_align``)."""
from benchmarks import trace_scopes_mask


def read(run):
    return trace_scopes_mask.branch_ms(run, "align")
