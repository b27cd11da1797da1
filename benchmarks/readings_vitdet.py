"""The readings that ``limits/vitdet_b_mask_train.json`` is set from, all in
one process (``benchmarks/readings.py`` for the attention trunk's cell):

    python benchmarks/readings_vitdet.py --workload vitdet_b_mask_train --seeds 11,12,13 [--controls 3]

For each seed: the program's checked steps against the reference (the LOWER
reading), and for the first ``--controls`` seeds the stand-ins put in the
program's place, each against the same reference (the UPPER readings): the
fp8 control, half of the batch left out, the state left unchanged, and the
planted faults ``reference/vitdet.py`` carries (the relative-position bias
left out; windows of 8 in place of 14; the four global blocks windowed;
layer-wise decay left out; GroupNorm in place of LayerNorm in the heads).
Prints one JSON line per reading. Not part of a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def stand_ins(batch: int, faults) -> list:
    """[(name, keyword arguments of ``drivers/train.py::follow``)]."""
    out = [("control_fp8", dict(precision="fp8")),
           ("fault_state_unchanged", dict(frozen_state=True))]
    if batch > 1:
        out.insert(1, ("fault_half_batch", dict(rows=range(batch // 2))))
    return out + [("fault_" + p.split("/")[1], dict(precision=p))
                  for p in faults]


def main(argv=None, *, platform="tpu", **steer):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--seconds", type=float, default=1.0)
    p.add_argument("--only", default="", help="stand-ins to run, by name")
    args = p.parse_args(argv)
    from benchmarks import manifest, run
    from benchmarks.drivers import train as driver
    from mx_rcnn_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    bm = manifest.load()
    cell = manifest.cell(bm, args.workload)
    conf = manifest.load_json("configs", cell["config"])
    mix = dict(manifest.load_json("traffic", cell["traffic"]),
               **steer.pop("mix_overrides", {}))
    run.find_devices(platform, cell["chips"])
    rows = []
    for n, seed in enumerate(int(s) for s in args.seeds.split(",")):
        out = driver.run(dict(cell=cell, conf=conf, mix=mix, seed=seed,
                              seconds=args.seconds, trace=False, root=ROOT,
                              t0=time.monotonic(), **steer))
        c = out["checked"]
        rows.append({"seed": seed, "kind": "program", **out["numbers"],
                     "where": out["where"], "rate": out["rate"],
                     "correct": out["correct"],
                     "memory_peak_bytes": out["memory_peak_bytes"],
                     "reference_s": out["reference_s"]})
        print(json.dumps(rows[-1]), flush=True)
        if n >= args.controls:
            continue
        for kind, kw in stand_ins(len(c["batches"][0]["image"]),
                                  c["ref_module"].FAULTS):
            if args.only and kind not in args.only.split(","):
                continue
            got = driver.follow(c["ref_module"], out["spec"], seed,
                                c["prog_seed"], c["batches"], **kw)
            got["input_gap"] = 0.0
            numbers, where = driver.numbers_of(got, c["ref"])
            rows.append({"seed": seed, "kind": kind, **numbers,
                         "where": where})
            print(json.dumps(rows[-1]), flush=True)
    return rows


if __name__ == "__main__":
    main()
