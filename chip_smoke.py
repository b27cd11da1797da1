#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the main path starts on the chip.

    python chip_smoke.py                # one TPU chip
    python chip_smoke.py --four-chips   # data-parallel path on a 4-chip host

Drives the system's main path once, through the entry points a user calls,
at the full width of the flagship model: ``--network resnet101 --dataset
coco`` (C4 Faster R-CNN, 81 classes, the default (600, 1000) scale on the
default 1024x1024 canvas, default proposal budgets 12000->2000 train /
6000->300 test, default batch_rois and compute dtype). Weights are random
from the seed, so both entry points get ``--from-scratch`` — the repo's own
way to run without pretrained weights (GroupNorm trunk, nothing frozen).
Data comes from ``mx_rcnn_tpu.tools.gen_synthetic_coco``, from a seed.

One chip: ``train_end2end.py`` takes one epoch of optimizer steps and writes
a checkpoint; the same command runs a second time (same seed) and must
reproduce the losses from the compile cache; ``test.py`` loads the
checkpoint and evaluates the val split through Predictor/pred_eval to a COCO
result. ``--four-chips`` runs the data-parallel path and what it is compared
with, and no other phase: ``--tpu-mesh 4`` with one image per chip against
one chip with the same four images as one batch.

EVERYTHING runs in this one process: it owns the chip from its first jax
call to its exit and starts no child that needs it. No fallback hides the
device — the platform must be a TPU (``resilience.backend_platform`` says so
to the entry points too, with a deadline of seconds), a heal or a backend
retry during the run is a failure, and no phase's exception is caught and
carried past: the first one ends the run with a non-zero exit code.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``;
earlier ``smoke:`` lines carry what is worth reading, and the same report
lands in ``chiprun_out/chip_smoke.json``. Work files (dataset, checkpoints,
event streams) go under ``.chip_smoke/``; the compile cache is where
``utils/compile_cache.py`` puts it.
"""

from __future__ import annotations

import argparse
import contextlib
import glob
import importlib
import json
import math
import os
import re
import shutil
import statistics
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
WORK = os.path.join(REPO, ".chip_smoke")
REPORT = os.path.join(REPO, "chiprun_out", "chip_smoke.json")

#: what the smoke passes on — a test rehearses the phases elsewhere by
#: patching this, the command line never can
PLATFORM = "tpu"
NETWORK = "resnet101"
#: images written by the generator: enough for a couple of dozen steps
N_TRAIN, N_VAL = 12, 6
#: every entry point gets these through its existing ``--set``
BASE_SETS = ("resilience.backend_deadline_s=30", "obs.enabled=true")
#: --four-chips: how closely the mesh run's losses follow the one-chip run
#: of the same global batch. Step 1 compares the forward passes alone (same
#: weights, same images: bf16 rounding in another order); step 2 is the
#: first loss AFTER a gradient all-reduce and an update, where a wrong
#: reduction (a sum for a mean) is an error of order one, while rounding
#: alone was read at 4% on the chip (the first update takes the loss from
#: 6.3 to 1.8: a steep place) — and from there on two from-scratch
#: trajectories drift apart by themselves, so later steps are printed, not
#: gated.
FOUR_CHIP_RTOL = (1e-3, 1e-1)
#: both --four-chips runs recompute activations in the backward pass: four
#: 1024x1024 images of an R-101 with nothing frozen are 14.4 GB of
#: temporaries on ONE 16 GB chip without it, 8.5 GB with (compiled for the
#: described chip; the mesh run's per-chip share needs no such help)
FOUR_CHIP_SETS = ("network.remat=true",)
#: obs events that mean the run did not simply run
FAULT_EVENTS = ("heal", "backend_retry", "anomaly", "stall", "crash",
                "preempt")

REPORT_FIELDS: dict = {}


def say(key: str, value) -> None:
    """One ``smoke:`` line, and the same field in the written report."""
    REPORT_FIELDS[key] = value
    print(f"smoke: {key} = {json.dumps(value, sort_keys=True, default=str)}",
          flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"chip_smoke: {what}")


# ---------------------------------------------------------------------------
# what jax itself reports while the entry points run
# ---------------------------------------------------------------------------

class CacheTraffic:
    """Persistent-cache hits and misses on jax's monitoring bus; the
    compiles themselves are counted by obs/compile_track.py."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event, **kw):
        if event.endswith("/cache_hits"):
            self.hits += 1
        elif event.endswith("/cache_misses"):
            self.misses += 1


@contextlib.contextmanager
def compiles(traffic: CacheTraffic):
    """What was compiled, or fetched from the cache, inside the block.
    jax 0.9 times ``compile_or_get_cached`` as a whole, so a hit shows up
    as a (short) program too; hits and misses are counted beside it."""
    from mx_rcnn_tpu.obs import compile_track

    hits, misses, report = traffic.hits, traffic.misses, {}
    with compile_track.count() as cc:
        yield report
    report.update({
        "programs": cc.n, "seconds_total": round(cc.seconds, 1),
        # per program, whatever took a second or more
        "seconds_by_program": [[f, round(s, 1)] for f, s in cc.programs
                               if s >= 1.0],
        "cache_hits": traffic.hits - hits,
        "cache_misses": traffic.misses - misses})


def run_entry(name: str, argv: list) -> None:
    """``python <name>.py <argv>`` in THIS process: the entry point's own
    ``main()`` under its own argument parser."""
    module = importlib.import_module(name)
    print(f"smoke: $ python {name}.py {' '.join(argv)}", flush=True)
    old = sys.argv
    sys.argv = [f"{name}.py"] + argv
    try:
        module.main()
    finally:
        sys.argv = old


def read_events(prefix: str) -> list:
    events = []
    for path in sorted(glob.glob(os.path.join(f"{prefix}.obs", "events*"))):
        with open(path, encoding="utf-8") as fh:
            events += [json.loads(line) for line in fh if line.strip()]
    return events


def no_faults(events: list, where: str) -> None:
    bad = [e["type"] for e in events if e.get("type") in FAULT_EVENTS]
    check(not bad, f"{where}: the run did not simply run — {bad} event(s); "
                   "a recovery during the smoke is a failure of the smoke")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def all_sets(sets=()) -> tuple:
    return (f"resilience.backend_platform={PLATFORM}",) + BASE_SETS \
        + tuple(sets)


def common_args(sets=()) -> list:
    data = os.path.join(WORK, "data")
    args = ["--network", NETWORK, "--dataset", "coco", "--root_path", data,
            "--dataset_path", os.path.join(data, "coco"), "--from-scratch"]
    for kv in all_sets(sets):
        args += ["--set", kv]
    return args


def native_helpers() -> None:
    """cc/*.c are rebuilt from source on first use (cc/build is not in git)
    and utils/native_build.py returns None in silence when that fails."""
    from mx_rcnn_tpu.data import _native_img
    from mx_rcnn_tpu.masks import _native as _native_mask

    built = {"imgproc": _native_img.available(),
             "maskapi": _native_mask.available()}
    say("native_helpers_loaded", built)
    check(all(built.values()), f"C helpers did not build/load: {built}")


def make_dataset(n_train: int, n_val: int) -> None:
    from mx_rcnn_tpu.tools import gen_synthetic_coco

    root = os.path.join(WORK, "data", "coco")
    gen_synthetic_coco.main(["--root", root, "--train", str(n_train),
                             "--val", str(n_val), "--seed", "0"])
    say("dataset", {"root": os.path.relpath(root, REPO), "train": n_train,
                    "val": n_val, "seed": 0})


#: the train runs read every step's loss back (graftpulse's reading, one
#: per dispatch): the train loop itself waits for none of its dispatches,
#: and Speedometer's line carries the means of the steps already done, not
#: each step's own. The reading's tripwires for a grad-norm jump and a loss
#: z-score are set out of reach (a from-scratch run's first update takes
#: the loss from 6.3 to 1.8); the nonfinite one stays, and is a fault.
TRAIN_SETS = ("obs.health_every=1", "obs.health_grad_factor=1e30",
              "obs.health_loss_z=1e30")


def step_losses(events: list) -> list:
    """Per-step total loss: the ``health`` events' ``loss``, one a
    dispatch, in dispatch order."""
    return [round(e["loss"], 4) for e in events if e["type"] == "health"]


def train_phase(tag: str, traffic: CacheTraffic, mesh: str = "1",
                sets=()) -> dict:
    """One run of train_end2end.py: one epoch, a checkpoint at its end."""
    prefix = os.path.join(WORK, tag, "e2e")
    with compiles(traffic) as compiled:
        run_entry("train_end2end", common_args(TRAIN_SETS + tuple(sets)) + [
            "--image_set", "train2017", "--prefix", prefix,
            "--end_epoch", "1", "--frequent", "1", "--tpu-mesh", mesh])
    events = read_events(prefix)
    no_faults(events, f"train[{tag}]")
    meta = next(e for e in events if e["type"] == "run_meta")
    check(meta.get("backend") == PLATFORM,
          f"train[{tag}] ran on {meta.get('backend')!r}, not {PLATFORM!r}")

    losses = step_losses(events)
    steps = [e for e in events if e["type"] == "step" and "step_ms" in e]
    check(len(losses) == len(steps) > 0,
          f"train[{tag}]: {len(losses)} logged losses, {len(steps)} steps")
    check(all(math.isfinite(v) for v in losses),
          f"train[{tag}]: non-finite loss in {losses}")
    # Every iteration's loss is read back (TRAIN_SETS): each step_ms is a
    # whole step with the device drained.
    warm = [e["step_ms"] for e in steps[1:]]
    # A train-step compile once a step has completed is a recompile: the
    # canvas is one static shape, so there must be none.
    first = events.index(steps[0])
    late = [e for e in events[first:] if e["type"] == "compile"
            and e.get("phase") == "backend_compile"
            and e.get("fun") == "jit(step)"]
    check(not late, f"train[{tag}]: {len(late)} train-step compile(s) "
                    "after the first step")
    saved = [e for e in events if e["type"] == "checkpoint"]
    check(saved, f"train[{tag}]: no checkpoint written")
    out = {
        "steps": len(steps), "losses": losses,
        "step_ms_synced_p50": round(statistics.median(warm), 2)
        if warm else None,
        "first_step_ms": steps[0]["step_ms"],
        "train_step_compiles_after_first_step": len(late),
        # durable=False: enqueued on the async writer (the chip-side
        # branch of tools/train.py), durable before the run returned
        "checkpoints": [{k: e[k] for k in ("epoch", "durable", "fallback")
                         if k in e} for e in saved],
        "mesh": meta.get("mesh"), "images_per_step": meta.get("batch_size"),
        "compile": compiled,
    }
    say(f"train[{tag}]", out)
    out["prefix"] = prefix
    return out


def eval_phase(prefix: str, traffic: CacheTraffic) -> dict:
    """test.py on the checkpoint train_end2end.py wrote."""
    out_json = os.path.join(WORK, "dets.json")
    with compiles(traffic) as compiled:
        run_entry("test", common_args() + [
            "--image_set", "val2017", "--prefix", prefix, "--epoch", "1",
            "--batch_size", "1", "--out_json", out_json])
    events = read_events(prefix)
    no_faults(events, "eval")
    result = [e for e in events if e["type"] == "eval"]
    check(len(result) == 1, f"eval: {len(result)} `eval` event(s)")
    with open(out_json, encoding="utf-8") as fh:
        n_dets = len(json.load(fh))
    results = result[0]["results"]
    check(results and all(math.isfinite(v) for v in results.values()
                          if isinstance(v, (int, float))),
          f"eval: empty or non-finite result {results}")
    out = {"images": result[0]["images"], "results": results,
           "coco_detections_written": n_dets,
           "wall_s": result[0]["wall_s"], "compile": compiled}
    say("eval", out)
    return out


def lowered_train_step(mesh_spec: str = "1", sets=()):
    """The train step of the SAME config the entry points ran, lowered
    here from shapes alone; also returns what the detect program needs."""
    from mx_rcnn_tpu.config import generate_config, parse_cli_overrides
    from mx_rcnn_tpu.models.zoo import build_model
    from mx_rcnn_tpu.parallel.mesh import create_mesh
    from mx_rcnn_tpu.train.step import abstract_step_inputs, make_train_step

    cfg = generate_config(NETWORK, "coco", **{
        "network.norm": "group", "network.freeze_at": 0,  # --from-scratch
        **parse_cli_overrides(list(all_sets(sets)))})
    mesh = create_mesh(mesh_spec)
    model = build_model(cfg, mesh=mesh)
    state, batch, key = abstract_step_inputs(
        model, cfg, mesh, cfg.train.batch_images * mesh.shape["data"])
    lowered = make_train_step(model, cfg, mesh=mesh).lower(state, batch, key)
    return lowered, (model, cfg, state.params, batch)


def kernels_in_programs() -> None:
    """Is the Pallas NMS in the lowered programs (``tpu_custom_call``), or
    its jnp stand-in?"""
    from mx_rcnn_tpu.evaluation.tester import Predictor

    train, (model, cfg, params, batch) = lowered_train_step()
    detect = Predictor(model, None, cfg)._detect.lower(
        params, batch["image"], batch["im_info"])
    found = {"train_step": train.as_text().count("tpu_custom_call"),
             "detect": detect.as_text().count("tpu_custom_call")}
    say("tpu_custom_calls_in_lowered_programs", found)
    if PLATFORM == "tpu":
        check(min(found.values()) >= 1,
              f"a program runs the jnp stand-in, not the kernel: {found}")


def one_chip(traffic: CacheTraffic, cache_dir: str) -> None:
    native_helpers()
    make_dataset(N_TRAIN, N_VAL)
    first = train_phase("a", traffic)
    # The same command again, same seed: the programs come from the cache
    # the first run filled, and the losses must be the same numbers.
    again = train_phase("b", traffic)
    check(again["losses"] == first["losses"],
          f"losses differ on a rerun with the same seed: {first['losses']} "
          f"vs {again['losses']}")
    check(again["compile"]["cache_hits"] > 0
          and os.listdir(cache_dir),
          f"the second run hit nothing in the compile cache {cache_dir}: "
          f"{again['compile']}")
    say("rerun", {"losses_identical": True,
                  "cache_hits": again["compile"]["cache_hits"],
                  "cache_misses": again["compile"]["cache_misses"],
                  "first_step_ms": [first["first_step_ms"],
                                    again["first_step_ms"]]})
    kernels_in_programs()
    eval_phase(first["prefix"], traffic)


def four_chips(traffic: CacheTraffic) -> None:
    import jax

    check(jax.device_count() == 4,
          f"--four-chips needs 4 devices, jax sees {jax.device_count()}")
    make_dataset(8, 0)  # 16 roidb entries with flips: 4 steps of 4 images
    mesh_run = train_phase("mesh4", traffic, mesh="4", sets=FOUR_CHIP_SETS)
    one_run = train_phase("one_b4", traffic, mesh="1", sets=FOUR_CHIP_SETS
                          + ("train.batch_images=4",))
    check(mesh_run["images_per_step"] == one_run["images_per_step"] == 4,
          "the two runs did not see the same global batch")
    a, b = mesh_run["losses"], one_run["losses"]
    rel = [abs(x - y) / max(abs(y), 1e-12) for x, y in zip(a, b)]
    say("four_chips_vs_one", {"mesh4_losses": a, "one_chip_b4_losses": b,
                              "rel_diff": [round(r, 5) for r in rel],
                              "rtol_first_steps": FOUR_CHIP_RTOL})
    check(len(rel) >= len(FOUR_CHIP_RTOL)
          and all(r <= tol for r, tol in zip(rel, FOUR_CHIP_RTOL)),
          f"data-parallel losses left the one-chip run's: {rel}")

    # Where things sit: parameters on all four devices, the batch's shards
    # on four different ones (parallel/mesh.py::create_mesh takes
    # jax.devices()[:d*m]; make_global_batch places by the sharding).
    import numpy as np

    from mx_rcnn_tpu.parallel.mesh import (create_mesh, place_replicated,
                                           shard_batch)

    mesh = create_mesh("4")
    placed = shard_batch({"image": np.zeros((4, 8, 8, 3), np.float32)}, mesh)
    shard_devs = sorted(s.device.id for s in
                        placed["image"].addressable_shards)
    shard_rows = sorted(s.index[0].start for s in
                        placed["image"].addressable_shards)
    param_devs = sorted(d.id for d in place_replicated(
        {"w": np.ones((4, 4), np.float32)}, mesh)["w"].devices())
    say("placement", {"batch_shard_devices": shard_devs,
                      "batch_shard_rows": shard_rows,
                      "param_replica_devices": param_devs})
    check(len(set(shard_devs)) == 4 and shard_rows == [0, 1, 2, 3]
          and len(set(param_devs)) == 4,
          "batch shards / parameter replicas are not on four devices")

    # The compiled data-parallel step: the kernel is in it, and so is the
    # gradient all-reduce.
    with compiles(traffic) as compiled:
        train, _ = lowered_train_step("4", sets=FOUR_CHIP_SETS)
        hlo = train.compile().as_text()
    found = {"tpu_custom_call": hlo.count('custom_call_target="tpu_custom'),
             "all_reduce": len(re.findall(r"\ball-reduce(-start)?\(", hlo)),
             "compile": compiled}
    say("mesh4_compiled_step", found)
    if PLATFORM == "tpu":
        check(found["tpu_custom_call"] >= 1, "no Pallas NMS in the DP step")
    check(found["all_reduce"] >= 1, "no gradient all-reduce in the DP step")


# ---------------------------------------------------------------------------

def device_fields() -> dict:
    import jax

    devices = jax.devices()
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--four-chips", action="store_true",
                        help="run the data-parallel path on a 4-chip host "
                             "and what it is compared with, nothing else")
    args = parser.parse_args(argv)

    from mx_rcnn_tpu.utils.compile_cache import enable_persistent_cache

    cache_dir = enable_persistent_cache()
    device = device_fields()  # the first jax call: this process owns the chip
    ok = False
    try:
        import jax
        import jaxlib

        say("versions", {"jax": jax.__version__,
                         "jaxlib": jaxlib.__version__,
                         "libtpu": _libtpu_version(),
                         "python": sys.version.split()[0]})
        say("device", device)
        say("compile_cache_dir", cache_dir)
        check(device["platform"] == PLATFORM,
              f"jax came up on {device['platform']!r}: no {PLATFORM!r} "
              "here, and the smoke passes nowhere else")
        shutil.rmtree(WORK, ignore_errors=True)
        os.makedirs(WORK)
        traffic = CacheTraffic()
        if args.four_chips:
            four_chips(traffic)
        else:
            one_chip(traffic, cache_dir)
        stats = jax.devices()[0].memory_stats() or {}
        say("peak_bytes_in_use", stats.get("peak_bytes_in_use"))
        say("cache_files", len(os.listdir(cache_dir)))
        ok = True
    finally:
        os.makedirs(os.path.dirname(REPORT), exist_ok=True)
        with open(REPORT, "w", encoding="utf-8") as fh:
            json.dump(dict(REPORT_FIELDS, ok=ok), fh, indent=1,
                      sort_keys=True, default=str)
        print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0


def _libtpu_version():
    from importlib import metadata

    for name in ("libtpu", "libtpu-nightly"):
        try:
            return metadata.version(name)
        except metadata.PackageNotFoundError:
            continue
    return None


if __name__ == "__main__":
    sys.exit(main())
