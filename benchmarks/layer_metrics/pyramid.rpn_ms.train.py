"""Device ms per step of ``rpn_head`` + ``rpn_targets`` + ``rpn_loss``: the
pyramid cells' copy of ``stage.rpn_ms.train``, which C4's cells read."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "rpn")
