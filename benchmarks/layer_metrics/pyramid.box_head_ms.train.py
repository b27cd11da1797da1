"""Device ms per step of ``roi_sample`` + ``box_head`` + ``rcnn_loss``: the
pyramid cells' copy of ``stage.box_head_ms.train``, which C4's cells read."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "box_head")
