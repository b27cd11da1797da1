"""Device ms per step of the ``proposal`` stage: softmax, decode, top-k and the
``nms_sweep`` kernel."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "proposal")
