"""Device ms per step of the optimizer's ``update``."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "update")
