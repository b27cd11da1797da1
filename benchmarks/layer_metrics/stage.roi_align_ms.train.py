"""Device ms per step of the ops scoped ``roi_align``, forward and backward."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "roi_align")
