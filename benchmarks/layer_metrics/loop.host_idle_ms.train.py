"""The rest of the traced interval's device-idle time a step whose midpoint
lies inside a ``train.*`` span of the program (its loop phases, the step
annotation, a ``train.gc`` collection) and outside the harness's own spans:
the host held the chip (``benchmarks/trace_idle.py``)."""
from benchmarks import trace_idle


def read(run):
    return trace_idle.per_step_ms(run, "host_ns")
