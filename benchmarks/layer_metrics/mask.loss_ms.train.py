"""Device ms per step of the ops whose scope path holds ``mask_targets`` or
``mask_loss``: the 28x28 targets resampled from the box-frame masks, the
class's map picked out and the per-pixel sigmoid cross-entropy, forward and
backward."""
from benchmarks import trace_scopes_mask


def read(run):
    return trace_scopes_mask.branch_ms(run, "loss")
