"""Device ms per step of the ops scoped ``backbone`` and ``neck``, forward and
backward: the pyramid cell's copy of ``stage.backbone_ms.train``, whose
``workloads`` tests/benchmarks/test_bm_trace_scopes.py pins to C4's two cells."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "backbone")
