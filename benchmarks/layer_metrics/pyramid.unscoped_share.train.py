"""Busy time of the ops under no stage over the device's busy time: the pyramid
cell's copy of ``stage.unscoped_share.train``, whose ``workloads``
tests/benchmarks/test_bm_trace_scopes.py pins to C4's two cells."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.unscoped_share(run)
