"""The readings that a cell's limits are set from, all in one process:

    python benchmarks/readings.py --workload <cell> --seeds 11,12,13 [--controls 3]

For each seed: the program's checked steps against the reference (the LOWER
reading), and for the first ``--controls`` seeds the control - the reference
put in the program's place at the precision below the configuration's (fp8
operands for bf16) - its bf16 stand-in, and the planted faults (half of the
batch left out; the state left unchanged; on a mesh, the exchange left out), each against the same reference
(the UPPER readings). Prints one JSON line per reading. Not part of a run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None, *, platform="tpu", **steer):
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--seconds", type=float, default=1.0)
    p.add_argument("--only", default="", help="stand-ins to run, by name")
    args = p.parse_args(argv)
    import time

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
        row = {"seed": seed, "kind": "program",
               **out["numbers"],
               "where": out["where"], "rate": out["rate"],
               "reference_s": out["reference_s"]}
        rows.append(row)
        print(json.dumps(row), flush=True)
        if n >= args.controls:
            continue
        b = len(c["batches"][0]["image"])
        stand_ins = [("control_fp8", dict(precision="fp8")),
                     ("look_bf16_operands", dict(precision="bf16")),
                     ("look_rpn_bf16_outputs", dict(precision="f32/rpn_bf16")),
                     ("fault_half_batch", dict(rows=range(b // 2))),
                     # each chip applying its own rows' gradient: on the
                     # replicated state, the first chip's rows alone
                     ("fault_no_exchange",
                      dict(rows=range(b // cell["chips"]))),
                     ("fault_state_unchanged", dict(frozen_state=True))]
        stand_ins = [s for s in stand_ins
                     if (s[0] in args.only.split(",") if args.only
                         else s[0] != "fault_no_exchange" or cell["chips"] > 1)]
        for kind, kw in stand_ins:
            got = driver.follow(c["ref_module"], out["spec"], seed,
                                c["prog_seed"], c["batches"], **kw)
            got["input_gap"] = 0.0
            numbers, where = driver.numbers_of(got, c["ref"])
            row = {"seed": seed, "kind": kind, **numbers, "where": where}
            rows.append(row)
            print(json.dumps(row), flush=True)
    return rows


if __name__ == "__main__":
    main()
