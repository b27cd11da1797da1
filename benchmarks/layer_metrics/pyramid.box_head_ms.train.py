"""Device ms per step of ``roi_sample`` + ``box_head`` + ``rcnn_loss``: the
pyramid cell's copy of ``stage.box_head_ms.train``, whose ``workloads``
tests/benchmarks/test_bm_trace_scopes.py pins to C4's two cells."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "box_head")
