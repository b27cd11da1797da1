"""Device-idle time that is neither launch lag nor host idle - under the
harness's own spans or in no program span - over the traced interval: what
the trace cannot explain yet (``benchmarks/trace_idle.py``)."""
from benchmarks import trace_idle


def read(run):
    return trace_idle.unlaid_share(run)
