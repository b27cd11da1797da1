"""Device-idle time in the traced interval whose midpoint lies inside a
``train.*`` span of the loop thread (and outside the harness's own stalls)
over the interval."""
from benchmarks import trace_scopes


def read(run):
    return trace_scopes.host_bound_share(run)
