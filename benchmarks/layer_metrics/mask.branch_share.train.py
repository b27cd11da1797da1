"""Device time of the mask branch's four scopes (``mask_align``,
``mask_head``, ``mask_targets``, ``mask_loss``) over the device's busy time
in the traced interval."""
from benchmarks import trace_scopes_mask


def read(run):
    return trace_scopes_mask.branch_share(run)
