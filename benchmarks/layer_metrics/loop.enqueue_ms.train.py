"""Mean duration of the loop's ``train.enqueue`` spans (the ``step_fn`` call) in
the traced interval."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.span_ms(run, "train.enqueue")
