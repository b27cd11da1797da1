"""Device-idle time a step of the traced interval while a step the loop had
handed over (its ``train.enqueue`` returned) had not yet started on the
device: the n-th enqueue paired with the n-th execution of the step program
from the capture's start (``benchmarks/trace_idle.py``)."""
from benchmarks import trace_idle


def read(run):
    return trace_idle.per_step_ms(run, "lag_ns")
