"""Mean ``step_ms - key_ms - enqueue_ms`` of the program's ``step`` events in
the obs-on, profiler-off window of the traced run: the loop thread's own
work a step, outside the two calls where back-pressure lands."""
from benchmarks import trace_idle


def read(run):
    return trace_idle.host_ms(run)
