"""Device busy time of the ops under no stage scope over the busy time: what
the scopes do not cover (collectives, copies, whatever the compiler hoists)."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.unscoped_share(run)
