"""Device ms per step of the ``proposal`` stage (decode, top-k, the NMS
launches): the pyramid cells' copy of ``stage.proposal_ms.train``, which C4's
cells read."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "proposal")
