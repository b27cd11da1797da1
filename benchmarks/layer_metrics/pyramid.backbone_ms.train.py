"""Device ms per step of the ops scoped ``backbone`` and ``neck``, forward and
backward: the pyramid cells' copy of ``stage.backbone_ms.train``, which C4's
cells read."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "backbone")
