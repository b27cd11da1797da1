"""Device ms per step of ``rpn_head`` + ``rpn_targets`` + ``rpn_loss``."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "rpn")
