"""Device ms per step of ``roi_sample`` + ``box_head`` + ``rcnn_loss``."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "box_head")
