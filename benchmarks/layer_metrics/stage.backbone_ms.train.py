"""Device ms per step of the ops scoped ``backbone`` (and FPN's ``neck``),
forward and backward."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "backbone")
