"""From a profiler trace to numbers: device busy time, the operations that
took most of it, kernel time by name, collective time not hidden behind
compute, and the longest idle gaps labelled by what the host was doing.

Reads the ``.xplane.pb`` with nothing but JAX
(``jax.profiler.ProfileData``); ``reduce_events`` works on plain tuples so
that the small recorded trace under ``benchmarks/fixtures`` checks it
without a chip.
"""

from __future__ import annotations

import glob
import os
import re

# an op line of a TPU device plane; "Steps" and "XLA Modules" cover the same
# time again and are not added to it
OP_LINES = ("XLA Ops",)
MODULE_LINE = "XLA Modules"  # one event per execution of a compiled program
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                        r"collective-permute", re.I)
HOST_SPAN = re.compile(r"^bench\.")
WINDOW_SPAN = "bench.traced"  # the harness's mark of the traced interval


class NoDeviceOps(ValueError):
    """The trace holds no device operation."""


def short_name(op: str) -> str:
    """The trace names an op by its whole HLO text. Keep what tells ops
    apart: the name, the result's shape and, for a custom call, its target."""
    m = re.match(r"(%?[\w.\-]+) = (\(?[a-z0-9]+\[[\d,]*\])", op)
    if not m:
        return op[:80]
    target = re.search(r'custom_call_target="([\w.\-]+)"', op)
    return f"{m.group(1)} {m.group(2)}" + (f" {target.group(1)}"
                                           if target else "")


def union_seconds(intervals) -> float:
    """Seconds covered by a list of (start_ns, end_ns)."""
    total, end = 0, None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def gaps_of(intervals, lo, hi):
    """Idle (start_ns, end_ns) stretches of [lo, hi] not covered."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(a, b) for a, b in out if b > a]


def main_module_runs(modules: dict, lo: int, hi: int) -> dict:
    """{device: [(start, end)]} of the executions, inside [lo, hi], of the
    program that takes most of the device's time there: the step."""
    total = {}
    for evs in modules.values():
        for n, s, d in evs:
            if s >= lo and s + d <= hi:
                total[n] = total.get(n, 0) + d
    if not total:
        return {}
    main = max(total, key=total.get)
    return {dev: sorted((s, s + d) for n, s, d in evs
                        if n == main and s >= lo and s + d <= hi)
            for dev, evs in modules.items()}


def reduce_events(devices: dict, host_spans: list, window=None,
                  modules=None) -> dict:
    """devices: {device name: [(op name, start_ns, dur_ns)]} of the op
    lines; host_spans: [(name, start_ns, dur_ns)] of the harness's own
    annotations; modules: {device name: [(program, start_ns, dur_ns)]}.

    The window (lo_ns, hi_ns) is, unless given: inside the harness's
    ``bench.traced`` span, from the start of the step program's first
    execution to the end of its last. The span opens and closes at a drained
    device, and under the profiler a drained chip takes over a second to
    start again (read on the chip, PR 24: 1.3-1.5 s at either end, none in an
    untraced window); those two stalls belong to the measurement, not to the
    loop, so the interval is taken between them, where the loop runs as it
    does in the window. Without module events: the ``bench.traced`` span."""
    all_ops = [ev for evs in devices.values() for ev in evs]
    if not all_ops:
        raise NoDeviceOps("the trace holds no device operation")
    runs = {}
    if window is None:
        marked = [(s, s + d) for n, s, d in host_spans if n == WINDOW_SPAN]
        src = host_spans or all_ops
        window = marked[0] if marked else (
            min(s for _, s, _ in src), max(s + d for _, s, d in src))
        runs = main_module_runs(modules or {}, *window)
        if any(runs.values()):
            window = (min(r[0][0] for r in runs.values() if r),
                      max(r[-1][1] for r in runs.values() if r))
    host_spans = [h for h in host_spans if h[0] != WINDOW_SPAN]
    lo, hi = window
    busy, by_name, kernel_s, exposed = [], {}, {}, []
    gaps = []
    for dev, evs in sorted(devices.items()):
        evs = [(short_name(n), max(s, lo), min(s + d, hi)) for n, s, d in evs
               if s + d > lo and s < hi]
        spans = [(s, e) for _, s, e in evs]
        busy.append(union_seconds(spans))
        for n, s, e in evs:
            by_name[n] = by_name.get(n, 0.0) + (e - s) / 1e9 / len(devices)
        compute = [(s, e) for n, s, e in evs if not COLLECTIVE.search(n)]
        coll = [(s, e) for n, s, e in evs if COLLECTIVE.search(n)]
        exposed.append(union_seconds(coll + compute) - union_seconds(compute))
        gaps += gaps_of(spans, lo, hi)
    labelled, longest = {}, []
    for a, b in gaps:
        mid = (a + b) // 2
        name = "host: outside the harness's spans"
        for n, s, d in host_spans:
            if s <= mid < s + d:
                name = n
                break
        labelled[name] = labelled.get(name, 0.0) + (b - a) / 1e9 / len(devices)
        longest.append([(a - lo) / 1e9, (b - a) / 1e9, name])
    return {
        "window_s": (hi - lo) / 1e9,
        "step_runs": max((len(r) for r in runs.values()), default=0),
        "busy_s": sum(busy) / len(busy),
        "busy_by_device_s": busy,
        "by_name": by_name,
        "device_ops": sorted(([n, t] for n, t in by_name.items()),
                             key=lambda r: -r[1]),
        "idle_gaps": sorted(([n, t] for n, t in labelled.items()),
                            key=lambda r: -r[1]),
        "longest_gaps": sorted(longest, key=lambda r: -r[1])[:12],
        "collective_exposed_s": sum(exposed) / len(exposed),
        "collective_s": sum(t for n, t in by_name.items()
                            if COLLECTIVE.search(n)),
    }


def kernel_seconds(summary: dict, pattern: str):
    """Summed device seconds of the ops whose name matches, or None where
    the trace names no such op."""
    rx = re.compile(pattern)
    hit = [t for n, t in summary["by_name"].items() if rx.search(n)]
    return sum(hit) if hit else None


def read_xplane(path: str, chips: int):
    """-> (devices, host_spans, {plane: line names}, modules);
    ``reduce_events``'s arguments and the planes' line names."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, lines, modules = {}, [], {}, {}
    for plane in data.planes:
        lines[plane.name] = [line.name for line in plane.lines][:12]
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                got = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events] if line.name in (
                           OP_LINES + (MODULE_LINE,)) else []
                if line.name in OP_LINES:
                    evs += got
                elif got:
                    modules[plane.name] = got
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if HOST_SPAN.match(e.name):
                        host.append((e.name, int(e.start_ns),
                                     int(e.duration_ns)))
    if len(devices) > chips:  # a host may show chips the cell does not use
        used = sorted(devices, key=lambda d: -len(devices[d]))[:chips]
        devices = {d: devices[d] for d in used}
    modules = {d: modules.get(d, []) for d in devices}
    return devices, host, lines, modules


def reduce_dir(trace_dir: str, chips: int) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    devices, host, lines, modules = read_xplane(
        max(paths, key=os.path.getmtime), chips)
    return dict(reduce_events(devices, host, modules=modules), planes=lines)
