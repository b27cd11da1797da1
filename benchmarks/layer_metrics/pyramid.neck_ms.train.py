"""Device ms per step of the ops scoped ``neck`` alone (laterals, top-down
sums, output convolutions; forward and backward): the pyramid's part of
``pyramid.backbone_ms.train``. Silent where the program has no neck."""
from benchmarks import trace_scopes


def read(run):
    f = trace_scopes.of_run(run)
    if not f or not f["step_runs"] or "neck" not in f["stage_ns"]:
        return None
    return f["stage_ns"]["neck"] / 1e6 / f["step_runs"]
