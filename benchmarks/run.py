"""The benchmark's one command:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration, traffic mix, driver,
limits and per-layer readers by name (``benchmarks/manifest.py``), runs one
measured window through the program's own entry, and prints one JSON object
as the last line of standard output. ``--plan 1`` compiles the cell's step
for a DESCRIBED v5e instead (no chip, no run) and prints its memory analysis.
"""

from __future__ import annotations

import time

T0 = time.monotonic()  # process start, as near as Python lets us

import argparse
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--plan", type=int, default=0)
    return p.parse_args(argv)


def find_devices(platform: str, chips: int):
    """The accelerator, or no run: never a fallback to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != platform or len(devs) < chips:
        sys.stderr.write(
            f"benchmark: need {chips} {platform!r} device(s), found "
            f"{len(devs)} {devs[0].platform!r}; no result\n")
        raise SystemExit(3)
    return devs[:chips]


def main(argv=None, *, platform="tpu", t0=None, root=ROOT, **steer):
    """``platform``, ``root`` and ``steer`` (config overrides) exist for
    ``tests/benchmarks``: a test steers the run, no option of the command
    does."""
    args = parse(argv)
    from benchmarks import manifest

    base = os.path.join(root, "benchmarks")
    bm = manifest.load(root)
    cell = manifest.cell(bm, args.workload)
    conf = manifest.load_json("configs", cell["config"], base)
    mix = dict(manifest.load_json("traffic", cell["traffic"], base),
               **steer.pop("mix_overrides", {}))
    driver = manifest.load_module("drivers", mix["driver"], base)
    if args.plan:
        from benchmarks import plan

        return plan.run(cell, conf, mix)
    try:
        from mx_rcnn_tpu.utils.compile_cache import enable_persistent_cache
    except ImportError:
        sys.stderr.write("benchmark: the program (mx_rcnn_tpu) is not in "
                         "this directory; no result\n")
        raise SystemExit(4)
    enable_persistent_cache()  # JAX_COMPILATION_CACHE_DIR, else <root>/.jax_cache
    devices = find_devices(platform, cell["chips"])
    seconds = bm["run_seconds"] if args.seconds is None else args.seconds
    ctx = dict(cell=cell, conf=conf, mix=mix, seed=args.seed, seconds=seconds,
               trace=bool(args.trace), root=root, base=base,
               t0=T0 if t0 is None else t0, **steer)
    out = driver.run(ctx)

    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": out["memory_peak_bytes"]}
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    group = "per_layer" if args.trace else "end_to_end"
    wanted = manifest.metrics_of(bm, group, cell["name"])
    if args.trace:
        from benchmarks import trace_reduce

        try:
            summary = out.get("trace") or trace_reduce.reduce_dir(
                os.path.join(out["work"], "trace"), chips=cell["chips"])
        except trace_reduce.NoDeviceOps:
            if dev.platform == "tpu":
                raise  # a traced run in which no operation ran on the device
            summary = None  # the CPU rehearsal: no device plane, no device metric
        out["trace"], out["device_kind"] = summary, dev.device_kind
        if summary:
            device["busy_s"], device["window_s"] = (summary["busy_s"],
                                                    summary["window_s"])
        values = {}
        for m in wanted:
            v = manifest.load_module("layer_metrics", m["name"],
                                     base).read(out)
            if v is not None:
                values[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = values
        if summary:
            result["breakdown"] = {"device_ops": summary["device_ops"][:10],
                                   "idle_gaps": summary["idle_gaps"][:10]}
    else:
        e2e = {"setup_s": out["setup_s"], **out.get("end_to_end", {})}
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]} for m in wanted}
    result["device"] = device
    if args.trace and summary:
        result["trace"] = {"planes": summary.get("planes"),
                           "device_ops_top40": summary["device_ops"][:40],
                           "traced_steps": out.get("traced_steps"),
                           "step_runs": summary.get("step_runs"),
                           "longest_gaps": summary.get("longest_gaps"),
                           "collective_s": summary.get("collective_s")}
    result["run"] = {k: out[k] for k in (
        "window_s", "steps", "images", "setup_s", "reference_s", "losses",
        "where", "numbers", "memory_stats", "phases") if k in out}
    result["compared"] = out["compared"]  # each number beside its limit: last
    if out.get("work"):  # the cell's whole work directory: shards, model, trace
        shutil.rmtree(os.path.dirname(out["work"]), ignore_errors=True)
    for name, row in out["compared"].items():
        sys.stderr.write(f"compared {name}: {row['value']:.6g} "
                         f"(limit {row['limit']:.6g})\n")
    sys.stderr.write(f"correct: {out['correct']}\n")
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result


if __name__ == "__main__":
    main()
