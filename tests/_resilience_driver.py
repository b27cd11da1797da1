"""graftguard test driver — subprocess entry + picklable sweep runners.

tests/test_resilience.py uses this module two ways:

- as a SUBPROCESS entry (``python tests/_resilience_driver.py --fit ...``)
  for the gates that need a real process boundary: the preemption exit
  code (SIGTERM → rc 75 is a process-level contract) and the
  checkpoint crash window (``--crash-save`` + chaos
  ``die_at=checkpoint_finalize`` SIGKILLs mid-save — nothing in-process
  survives that by design);
- as an IMPORT for the in-process parity gates (``tiny_config`` /
  ``run_fit``) and for the module-level functions the deadline-isolation
  tests ship to spawn children (``sweep_runner`` and friends — a spawn
  child unpickles them by qualified name, so they must live in an
  importable module, and this module's top-level imports stay
  stdlib-only to keep child startup off the jax import path).
"""

from __future__ import annotations

import argparse
import os
import sys
import time

# Script execution puts tests/ (not the repo root) on sys.path.
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)


# ---------------------------------------------------------------------------
# picklable runners for resilience/isolate.py spawn children
# ---------------------------------------------------------------------------

def sweep_runner(label):
    """A well-behaved bench runner: one structured row, instantly."""
    return {"img_s_per_chip": 1.0, "which": label}


def sleepy_runner(label):
    """Stands in for the BENCH_r05 hung compile (without chaos wiring)."""
    time.sleep(60.0)
    return {"img_s_per_chip": 0.0, "which": label}


def error_runner(label):
    raise RuntimeError(f"cell dropped mid-measure ({label})")


# ---------------------------------------------------------------------------
# the tiny fit (in-process helper + --fit subprocess mode)
# ---------------------------------------------------------------------------

def tiny_config(obs_dir: str = "", compute: str = "f32",
                health_every: int = 0, over_extra=None):
    """A 64^2 f32 micro-config with power-of-two bbox stds: the kill->resume parity gates assert BIT
    exactness, and an emergency save round-trips bbox_pred through
    unnormalize (kernel*std) + renormalize (kernel/std) — exact for
    powers of two, not for the default 0.1/0.2. ``compute`` selects the
    graftcast policy (train/precision.py) — the bf16 parity gates run
    the exact same resume/heal machinery under compute_dtype=bf16
    (determinism holds: bf16 rounding is deterministic on one
    backend, so killed+resumed still matches uninterrupted bit for
    bit)."""
    from dataclasses import replace

    from mx_rcnn_tpu.config import generate_config

    over = {
        "train.rpn_pre_nms_top_n": 128,
        "train.rpn_post_nms_top_n": 32,
        "train.batch_rois": 16,
        "train.max_gt_boxes": 4,
        "train.batch_images": 1,
        "train.flip": False,
        "network.anchor_scales": (2, 4),
        "image.pad_shape": (64, 64),
        "image.scales": ((64, 64),),
    }
    if obs_dir:
        over["obs.enabled"] = True
        over["obs.dir"] = obs_dir
        # graftprof's per-bucket AOT cost capture re-traces the step once
        # per shape bucket — pure compile-time, but these gates are about
        # resilience, not attribution; keep them inside the tier-1 budget.
        over["obs.cost_analysis"] = False
        # graftpulse: in-graph health at every Nth dispatch (0 = off).
        # The nan_at_step gates run every=1 so the tripwire sees the
        # poisoned dispatch the moment it lands.
        over["obs.health_every"] = health_every
    if over_extra:
        # graftquorum gates thread resilience.quorum_* / elastic_mode
        # overrides through here (dotted config keys).
        over.update(over_extra)
    cfg = generate_config("resnet50", "synthetic", **over)
    return cfg.with_updates(
        train=replace(cfg.train, compute_dtype=compute,
                      bbox_stds=(0.5, 0.5, 0.25, 0.25)))


def run_fit(prefix: str, end_epoch: int = 2, resume=False,
            obs_dir: str = "", mesh: str = "1",
            num_images: int = 3, epoch_metrics=None, compute: str = "f32",
            health_every: int = 0, over_extra=None):
    """num_images x 64^2, seed 0 — returns the final host params.
    Deterministic end to end, so an interrupted+resumed (or graftheal-ed)
    run must match an uninterrupted one bit for bit. ``mesh`` sizes the
    data axis (the heal shrink gates run "8" on the virtual CPU mesh);
    ``epoch_metrics`` (a list) collects ``(epoch, bag.get())`` per epoch —
    the loss trajectory the elastic gates compare."""
    from mx_rcnn_tpu.data.datasets.synthetic import SyntheticDataset
    from mx_rcnn_tpu.tools.train import fit_detector

    ds = SyntheticDataset("train", num_images=num_images, image_size=64,
                          max_objects=1, min_size_frac=3, max_size_frac=2)
    cb = None
    if epoch_metrics is not None:
        def cb(epoch, state, bag):
            epoch_metrics.append((epoch, bag.get()))
    return fit_detector(tiny_config(obs_dir, compute, health_every,
                                    over_extra=over_extra),
                        ds.gt_roidb(),
                        prefix=prefix, end_epoch=end_epoch, frequent=1000,
                        seed=0, mesh_spec=mesh, resume=resume,
                        epoch_callback=cb)


def _crash_save(prefix: str, scale: float = 1.0):
    """One sync checkpoint save of a known tiny tree (``scale`` makes
    successive saves distinguishable). With chaos ``die_at=
    checkpoint_finalize`` / ``checkpoint_swap`` armed the process
    SIGKILLs inside that crash window; unarmed it publishes."""
    import numpy as np

    from mx_rcnn_tpu.train.checkpoint import save_checkpoint

    save_checkpoint(prefix, 1,
                    {"w": scale * np.arange(6, dtype=np.float32).reshape(2, 3)})


def _coerce(raw: str):
    """Literal coercion for --set values: int, float, bool, else str."""
    try:
        return int(raw)
    except ValueError:
        pass
    try:
        return float(raw)
    except ValueError:
        pass
    low = raw.strip().lower()
    if low in ("true", "false"):
        return low == "true"
    return raw


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--fit", metavar="PREFIX",
                   help="run the tiny training run under PREFIX")
    p.add_argument("--end-epoch", type=int, default=2)
    p.add_argument("--resume", nargs="?", const=True, default=False,
                   choices=[True, "auto"], metavar="auto")
    p.add_argument("--obs-dir", default="")
    p.add_argument("--mesh", default="1", help="mesh spec (data[xmodel])")
    p.add_argument("--num-images", type=int, default=3)
    p.add_argument("--compute", default="f32", choices=["f32", "bf16"],
                   help="graftcast train.compute_dtype policy")
    p.add_argument("--crash-save", metavar="PREFIX",
                   help="one sync checkpoint save (the crash-window probe)")
    p.add_argument("--scale", type=float, default=1.0,
                   help="scale factor on the --crash-save tree")
    # graftquorum simulated-host mode: N of these processes, each a full
    # replicated computation, coordinate through a shared FileKVStore as
    # if they were N pod hosts (parallel/distributed.py sim contract).
    p.add_argument("--sim-host", type=int, default=None, metavar="I",
                   help="stand in for host I of a simulated fleet")
    p.add_argument("--sim-hosts", type=int, default=None, metavar="N",
                   help="size of the simulated fleet")
    p.add_argument("--quorum-dir", default="",
                   help="resilience.quorum_store_dir (shared FileKVStore)")
    p.add_argument("--quorum-timeout", type=float, default=0.0,
                   help="resilience.quorum_timeout_s override (0 = keep)")
    p.add_argument("--elastic-mode", default="",
                   choices=["", "shrink", "grow", "rescale"],
                   help="resilience.elastic_mode override")
    # grafttower gates thread heartbeat/fleet knobs through here without
    # growing a flag per knob: repeatable dotted config overrides with
    # literal coercion (int -> float -> bool -> str).
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="extra dotted config override (repeatable), e.g. "
                        "--set obs.heartbeat_every_s=0.2")
    args = p.parse_args(argv)

    if args.sim_host is not None or args.sim_hosts is not None:
        if args.sim_host is None or args.sim_hosts is None:
            p.error("--sim-host and --sim-hosts go together")
        # Coordination identity only — jax itself stays single-process
        # (env must land before mx_rcnn_tpu reads it at call time).
        os.environ["MXRCNN_SIM_PROCESS_ID"] = str(args.sim_host)
        os.environ["MXRCNN_SIM_NUM_PROCESSES"] = str(args.sim_hosts)

    if args.mesh not in ("", "1", "1x1"):
        # Multi-device mesh in a subprocess: the virtual CPU devices must
        # be requested BEFORE jax initializes (same dance as conftest.py).
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=8").strip()

    import jax

    jax.config.update("jax_platforms", "cpu")
    from mx_rcnn_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()  # the suite's cache (utils/compile_cache.py)

    if args.crash_save:
        _crash_save(args.crash_save, scale=args.scale)
        return 0
    if args.fit:
        over_extra = {}
        if args.quorum_dir:
            over_extra["resilience.quorum_store_dir"] = args.quorum_dir
        if args.quorum_timeout:
            over_extra["resilience.quorum_timeout_s"] = args.quorum_timeout
        if args.elastic_mode:
            over_extra["resilience.elastic_mode"] = args.elastic_mode
        for pair in args.overrides:
            key, sep, raw = pair.partition("=")
            if not sep:
                p.error(f"--set expects KEY=VALUE, got {pair!r}")
            over_extra[key] = _coerce(raw)
        run_fit(args.fit, end_epoch=args.end_epoch, resume=args.resume,
                obs_dir=args.obs_dir, mesh=args.mesh,
                num_images=args.num_images, compute=args.compute,
                over_extra=over_extra or None)
        return 0
    p.error("one of --fit / --crash-save is required")


if __name__ == "__main__":
    sys.exit(main())
