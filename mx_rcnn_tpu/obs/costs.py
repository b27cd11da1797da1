"""graftprof cost accounting — FLOPs/HBM straight from XLA, per executable.

The ROADMAP's MFU push (0.28 → 0.45) was blocked on attribution: the
repo's best efficiency number was ONE hand-derived scalar (BENCH_r04's
0.2811), and nothing could say what a compiled step actually costs in
FLOPs or HBM, or how much of the conv work is burned on pad-bucket
padding. This module closes that gap at the only honest source — the
compiled executable itself:

- ``executable_costs``: wraps ``Compiled.cost_analysis()`` /
  ``Compiled.memory_analysis()`` into one flat dict
  (``flops``, ``bytes_accessed``, ``hbm_args/temps/output/alias``,
  ``hbm_bytes``) that works on every backend jax exposes the analyses on
  (CPU included — the tier-1 gate runs there).
- ``mfu_from``: measured step rate × analytic FLOPs ÷ chip peak — the
  computed MFU that replaces the hand model in bench rows and reports.
- ``batch_pad_waste``: real pixels ÷ canvas pixels for one batch, from
  ``im_info`` (the loader records the pre-pad size there) — the measured
  baseline for the canvas-packing lever (ROADMAP MFU item, lever 3).
- ``CostTracker``: the train-loop hook — one ``cost`` event per compiled
  shape bucket (FPN multi-scale runs compile one executable per pad
  bucket; their FLOPs differ, so per-bucket MFU needs per-bucket costs).

Everything here degrades, never blocks: a backend without cost analysis
yields partial dicts, and the tracker disarms itself on the first
failure (telemetry must not kill a training run).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import numpy as np

#: Published per-chip peaks, ONE table keyed by jax's ``device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s in bf16,
#: 16 GB of HBM at 819 GB/s. No f32 matmul peak is published for the
#: chip, so an f32 step has no MFU here rather than a guessed one; a
#: device that is not in the table is an error, never a default. Keeping
#: the table here keeps report folding jax-free: cost events carry the
#: peak they were computed against.
PEAKS = {
    "TPU v5 lite": {"flops": {"bfloat16": 197e12},
                    "hbm_bytes_per_s": 819e9},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]  # the same chip's other spelling


class UnknownPeakError(LookupError):
    """No published peak for this (device_kind, dtype)."""


def peak_flops_for(device_kind: str, compute_dtype: str) -> float:
    """Published per-chip peak FLOP/s of ``device_kind`` in
    ``compute_dtype`` (canonical or the "f32"/"bf16" short spellings).
    An MFU must divide by the peak of the dtype the step actually ran
    on the chip it actually ran on, so anything not in PEAKS raises."""
    name = {"f32": "float32", "bf16": "bfloat16"}.get(
        str(compute_dtype), str(compute_dtype))
    try:
        return PEAKS[device_kind]["flops"][name]
    except KeyError:
        raise UnknownPeakError(
            f"no published peak FLOP/s for device_kind={device_kind!r} "
            f"dtype={name!r} (obs/costs.py::PEAKS)") from None


def executable_costs(compiled) -> Dict[str, Any]:
    """XLA's analytic cost + memory accounting for ONE compiled executable.

    Returns a flat dict: ``flops`` / ``bytes_accessed`` from
    ``cost_analysis()`` (per-device numbers for SPMD programs — XLA
    analyzes the partitioned module), and the HBM footprint split from
    ``memory_analysis()``: ``hbm_args`` (live inputs), ``hbm_temps``
    (scratch), ``hbm_output``, ``hbm_alias`` (donated input/output
    aliasing), plus ``hbm_bytes`` = args + temps + output − alias (the
    peak working set; donated buffers must not double-count). Keys are
    omitted, not zeroed, when a backend lacks the analysis."""
    out: Dict[str, Any] = {}
    try:
        analysis = compiled.cost_analysis()
        if analysis:
            out["flops"] = float(analysis.get("flops", 0.0))
            out["bytes_accessed"] = float(
                analysis.get("bytes accessed", 0.0))
    except Exception:  # noqa: BLE001  # graftlint: disable=broad-except — backend-dependent API (unimplemented/runtime errors vary); cost accounting degrades to a partial dict, never raises into the run
        pass
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            args = float(getattr(mem, "argument_size_in_bytes", 0) or 0)
            temps = float(getattr(mem, "temp_size_in_bytes", 0) or 0)
            outb = float(getattr(mem, "output_size_in_bytes", 0) or 0)
            alias = float(getattr(mem, "alias_size_in_bytes", 0) or 0)
            out.update(hbm_args=args, hbm_temps=temps, hbm_output=outb,
                       hbm_alias=alias,
                       hbm_bytes=max(0.0, args + temps + outb - alias))
    except Exception:  # noqa: BLE001  # graftlint: disable=broad-except — same degradation contract as above
        pass
    return out


def mfu_from(flops: Optional[float], steps_per_sec: float,
             peak_flops: Optional[float]) -> Optional[float]:
    """Computed MFU: analytic per-step FLOPs × measured step rate ÷ peak.

    ``cost_analysis()`` counts the per-device (SPMD-partitioned) program,
    so per-device flops × steps/sec ÷ per-chip peak IS the per-chip MFU
    — no extra device_count factor (the bench.py convention)."""
    if not flops or steps_per_sec <= 0 or not peak_flops:
        return None
    return (flops * steps_per_sec) / peak_flops


def batch_pad_waste(batch) -> Dict[str, Any]:
    """Padding waste of one batch: real pixels ÷ canvas pixels.

    ``im_info`` rows are ``[h, w, scale]`` (or graftcanvas packed
    ``[h, w, scale, y0, x0]``) with (h, w) the content size; the canvas
    is the image tensor's static (H, W) × its PLANE count — for a
    bucketed batch that is one canvas per im_info row, for a packed
    batch one per canvas plane holding several rows, so packed rows
    honestly report canvas utilization (leading-axes flattening).
    Returns {} when the batch lacks the train contract keys (custom
    loaders)."""
    try:
        image = batch["image"]
        info = np.asarray(batch["im_info"], np.float64)
    except (KeyError, TypeError):
        return {}
    shape = getattr(image, "shape", ())
    if len(shape) < 3 or info.ndim < 1:
        return {}
    canvas_h, canvas_w = int(shape[-3]), int(shape[-2])
    planes = int(np.prod(shape[:-3], dtype=np.int64)) if len(shape) > 3 else 1
    rows = info.reshape(-1, info.shape[-1])
    real = float(np.sum(rows[:, 0] * rows[:, 1]))
    canvas = float(planes * canvas_h * canvas_w)
    if canvas <= 0:
        return {}
    return {
        "canvas": [canvas_h, canvas_w],
        "real_px": int(real),
        "canvas_px": int(canvas),
        "pad_waste": round(1.0 - real / canvas, 4),
    }


def step_fields(batch) -> Dict[str, Any]:
    """The per-step enrichment StepTimer attaches to ``step`` events:
    the batch's canvas + pad-waste fraction (host-side numpy arithmetic
    over ``im_info`` — no device touch, no added sync)."""
    pw = batch_pad_waste(batch)
    if not pw:
        return {}
    return {"canvas": pw["canvas"], "pad_waste": pw["pad_waste"]}


class CostTracker:
    """One ``cost`` event per compiled shape bucket of the train step.

    ``observe(step_fn, state, batch, key)`` is called once per dispatch
    (host-side, before the call): on a batch-shape signature it has not
    seen it AOT-lowers the step (``step_fn.lower(...).compile()``) and
    emits the executable's cost/memory accounting. The AOT compile of an
    already-jitted program is a persistent-compile-cache hit — the extra
    cost is one tracing pass per bucket, paid only with obs enabled.
    Every other dispatch is one dict lookup.

    Self-disarming: any failure (TP pre-placement quirks, a backend
    without AOT) switches the tracker off for the rest of the run —
    attribution is telemetry, not a dependency of training."""

    def __init__(self, elog, label: str = "train_step",
                 peak_flops: Optional[float] = None,
                 compute_dtype: Optional[str] = None):
        """``peak_flops``: the published peak of the device and dtype the
        step runs on (``peak_flops_for``), or None where there is none —
        a CPU run, an f32 step — and the ``cost`` events then carry
        FLOPs and HBM but nothing to compute an MFU from.
        ``compute_dtype`` (graftcast policy, canonical name) is stamped
        on every event so report/ledger folding can split rows by
        dtype."""
        self.elog = elog
        self.label = label
        self.compute_dtype = compute_dtype
        self.peak_flops = peak_flops
        self._seen: set = set()
        self._disabled = False

    def reset(self):
        """Forget seen buckets — called when the session is rebuilt
        (graftheal): an elastic re-mesh changes the PER-DEVICE program
        behind the same global batch shape, so the old cost events no
        longer describe the running executable. Re-arms the tracker too
        (a heal is a new backend; a prior AOT failure may not recur)."""
        self._seen.clear()
        self._disabled = False

    def _bucket_key(self, batch):
        try:
            return tuple(sorted(
                (k, tuple(getattr(v, "shape", ()))) for k, v in batch.items()))
        except (AttributeError, TypeError):
            return None

    def observe(self, step_fn, state, batch, key) -> None:
        if self._disabled or not self.elog.enabled:
            return
        bucket = self._bucket_key(batch)
        if bucket is None or bucket in self._seen:
            return
        self._seen.add(bucket)
        try:
            compiled = step_fn.lower(state, batch, key).compile()
            costs = executable_costs(compiled)
        except Exception as exc:  # noqa: BLE001  # graftlint: disable=broad-except — AOT support varies by backend/sharding mode; the tracker disarms instead of killing the run
            from mx_rcnn_tpu.logger import logger

            logger.warning("graftprof cost tracking disabled: %r", exc)
            self._disabled = True
            return
        shapes = {k: list(getattr(v, "shape", ())) for k, v in batch.items()}
        extra = ({"compute_dtype": self.compute_dtype}
                 if self.compute_dtype else {})
        if self.peak_flops:
            extra["peak_flops"] = self.peak_flops
        self.elog.emit("cost", label=self.label, shapes=shapes,
                       **extra, **costs)
