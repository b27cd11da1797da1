"""Busy time of the ops under no stage over the device's busy time: the
pyramid cell's copy of ``stage.unscoped_share.train``, which C4's cells read.
Not listed for the mask cell: there it would count the branch's ``mask_head``
and ``mask_loss`` scopes, which are not stages, as unscoped."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.unscoped_share(run)
