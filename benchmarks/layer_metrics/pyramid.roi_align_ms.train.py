"""Device ms per step of the ``roi_align`` stage, forward and backward: the
pyramid cells' copy of ``stage.roi_align_ms.train``, which C4's cells read.
On the mask cell the stage holds both poolings: the box head's and the
branch's 14x14, whose ``mask_align`` scope wraps the pooling's own
``roi_align``. There it read 44.75 ms a step, of which
``mask.align_ms.train`` was 23.69 (a v5e, the mask cell's traced run)."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.stage_ms(run, "roi_align")
