"""Mean duration of the loop's ``train.place`` spans (``shard_batch``) in the
traced interval."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.span_ms(run, "train.place")
