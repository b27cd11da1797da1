"""Driver of kind ``train``: the cell's window is driven through
``mx_rcnn_tpu.tools.train.fit_detector`` exactly as ``train_end2end.py`` calls
it for packed shards, bounded through its own ``loader_factory`` argument.

One call of ``fit_detector`` builds one compiled step with its state, takes
its first ``checked_steps`` steps one per epoch (so that the loop's
``epoch_callback`` hands over the state after each), warms up, and then runs
the measured window - the same object throughout. After the window has closed,
``memory_peak_bytes`` has been read and the program's state is gone, the plain
reference follows the checked steps and ``compare`` decides ``correct``.
"""

from __future__ import annotations

import gc
import glob
import json
import os
import shutil
import time

import numpy as np

from benchmarks import compare, manifest, traffic, weights, window


def _program_config(conf: dict, extra=None):
    from mx_rcnn_tpu.config import generate_config

    over = manifest.tuples(dict(conf["overrides"], **(extra or {})))
    return generate_config(conf["network"], conf["dataset"], **over)


def check_spec(cfg, spec: dict):
    """The reference's sizes are the file's; the program's come from its
    preset and the overrides. Where both name a size they must agree, or the
    two sides would run different recipes."""
    bad = []
    for k, v in spec["train"].items():
        have = (cfg.train.bg_thresh_lo_value if k == "bg_thresh_lo"
                else getattr(cfg.train, k))
        if manifest.tuples(v) != have and list(np.atleast_1d(v)) != list(
                np.atleast_1d(have)):
            bad.append((f"train.{k}", v, have))
    pairs = [("depth", cfg.network.depth),
             ("num_classes", cfg.dataset.num_classes),
             ("anchor_scales", cfg.network.anchor_scales),
             ("anchor_ratios", cfg.network.anchor_ratios),
             ("feat_stride", cfg.network.rpn_feat_stride),
             ("roi_pool_size", cfg.network.roi_pool_size),
             ("canvas", cfg.image.pad_shape),
             ("scales", cfg.image.scales[0]),
             ("max_gt_boxes", cfg.train.max_gt_boxes),
             ("batch_images", cfg.train.batch_images),
             ("compute_dtype", cfg.train.compute_dtype)]
    for k, have in pairs:
        if manifest.tuples(spec[k]) != have:
            bad.append((k, spec[k], have))
    if optimizer_of(spec["train"]) != cfg.train.optimizer:
        bad.append(("train.optimizer", optimizer_of(spec["train"]),
                    cfg.train.optimizer))
    if bad:
        raise SystemExit(f"configuration file and program disagree: {bad}")


def _seeded_params(cfg, seed, mesh=None):
    """The program's tree, the benchmark's values."""
    import jax
    from mx_rcnn_tpu.models.zoo import build_model, init_params

    model = build_model(cfg, mesh=mesh)
    abstract = jax.eval_shape(
        lambda k: init_params(model, cfg, k), jax.random.PRNGKey(0))
    return weights.fill_tree(seed, abstract)


def _flat(tree) -> dict:
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {weights.path_of(p): np.asarray(v) for p, v in flat}


def optimizer_of(train: dict) -> str:
    """The configuration's optimizer, ``spec.train.optimizer``: ``sgd``
    where the file names none."""
    return train.get("optimizer", "sgd")


# the slot of the state that holds the first gradient after one step
SLOT = {"sgd": "trace", "adamw": "mu"}


def _slot_leaves(opt_state, slot: str) -> dict:
    """One slot of the optimizer state, by parameter path."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(opt_state)
    out = {}
    for p, v in flat:
        keys = [str(getattr(k, "key", getattr(k, "name", k))) for k in p]
        if "params" in keys and slot in keys[:keys.index("params")]:
            out["/".join(keys[keys.index("params") + 1:])] = np.asarray(v)
    return out


def program_b1(cfg) -> float:
    """The first moment's decay of the program's own AdamW, read off one
    update of ``build_optimizer(cfg)`` over a one-leaf tree: from a zero
    state, ``mu = (1 - b1) g``. The gradient is a power of two under any
    clip, so the quotient is the program's ``1 - b1`` to float32."""
    import jax.numpy as jnp

    from mx_rcnn_tpu.train.optimizer import build_optimizer

    g = 2.0 ** -20
    params = {"params": {"first_moment_probe": jnp.zeros((1,), jnp.float32)}}
    tx = build_optimizer(cfg, params)
    _, state = tx.update({"params": {"first_moment_probe": jnp.full(
        (1,), g, jnp.float32)}}, tx.init(params), params)
    mu = _slot_leaves(state, SLOT["adamw"])["first_moment_probe"]
    return 1.0 - float(mu[0]) / g


def first_gradient(opt_state, w0: dict, spec: dict, b1=None) -> dict:
    """The first gradient as the optimizer got it (after its clip), by
    parameter path, read from its state after the first step from a zero
    state, whichever optimizer the configuration names:

    - ``sgd`` (``optax.clip``, ``add_decayed_weights``, momentum): the
      momentum trace is the clipped gradient plus the coupled decay, so
      ``trace - wd * w0``;
    - ``adamw`` (``clip_by_global_norm``, then ``adamw``): the first moment
      is ``(1 - b1)`` times the clipped gradient; the decay is decoupled and
      never enters it. ``b1`` is the program's (``program_b1``).
    """
    kind = optimizer_of(spec["train"])
    slots = _slot_leaves(opt_state, SLOT[kind])
    if not slots:
        raise RuntimeError(f"no {SLOT[kind]!r} slot in the optimizer state: "
                           f"not the {kind} the configuration names")
    if kind == "adamw":
        return {p: np.asarray(m, np.float32) / (1.0 - b1)
                for p, m in slots.items()}
    wd = spec["train"]["wd"]
    return {p: t - wd * np.asarray(w0[p]) for p, t in slots.items()}


def clip_like(grads: dict, train: dict) -> dict:
    """A gradient clipped as the configuration's optimizer clips it:
    elementwise for ``sgd`` (``optax.clip``), by the global norm of the
    trainable leaves for ``adamw`` (``optax.clip_by_global_norm``)."""
    to = train["clip_gradient"]
    if optimizer_of(train) == "adamw":
        norm = np.float32(np.sqrt(sum(np.sum(np.square(v, dtype=np.float64))
                                      for v in grads.values())))
        if norm < to:
            return dict(grads)
        return {k: v / norm * np.float32(to) for k, v in grads.items()}
    return {k: np.clip(v, -to, to) for k, v in grads.items()}


def identify(batch: dict, roidb: list):
    """Which record (and mirror) each row of a served batch is: matched on
    the ground-truth boxes, which the generator made. Returns [(index,
    flipped)]."""
    out = []
    for r in range(batch["gt_boxes"].shape[0]):
        n = int(batch["gt_valid"][r].sum())
        got = batch["gt_boxes"][r, :n]
        scale = float(batch["im_info"][r, 2])
        hit = None
        for i, rec in enumerate(roidb):
            if len(rec["boxes"]) != n:
                continue
            for flipped in (False, True):
                b = rec["boxes"].astype(np.float32).copy()
                if flipped:
                    x1 = b[:, 0].copy()
                    b[:, 0] = rec["width"] - b[:, 2] - 1
                    b[:, 2] = rec["width"] - x1 - 1
                if np.allclose(b * scale, got, atol=1e-2):
                    hit = (i, flipped)
                    break
            if hit:
                break
        if hit is None:
            raise RuntimeError(f"served row {r} matches no generated image")
        out.append(hit)
    return out


def reference_batch(ref, rows, roidb, spec):
    """The checked step's batch, built by the reference's own input plane
    from the generator's pixels."""
    imgs, infos, boxes, classes, valid = [], [], [], [], []
    g = spec["max_gt_boxes"]
    for i, flipped in rows:
        rec = roidb[i]
        img, info = ref.prepare_image(rec["image_data"], flipped,
                                      spec["scales"], spec["pixel_means"],
                                      spec["canvas"])
        b, ok = ref.prepare_boxes(rec["boxes"], rec["width"], flipped,
                                  float(info[2]), g)
        c = np.zeros((g,), np.int32)
        c[:len(rec["gt_classes"])] = rec["gt_classes"]
        imgs.append(img), infos.append(info), boxes.append(b)
        classes.append(c), valid.append(ok)
    return {"image": np.stack(imgs), "im_info": np.stack(infos),
            "gt_boxes": np.stack(boxes), "gt_classes": np.stack(classes),
            "gt_valid": np.stack(valid)}


def follow(ref, spec, seed, prog_seed, batches, precision="f32",
           rows=None, frozen_state=False):
    """The reference through the checked steps. Returns per-step losses, the
    first gradient as the optimizer gets it (clipped as the configuration's
    optimizer clips), the parameters' change after the last step, and the
    reference's raw first gradient."""
    import jax

    params = weights.make(seed, ref.param_shapes(spec))
    trainer = ref.Trainer(spec, params, precision)
    start = {k: np.asarray(v) for k, v in trainer.train.items()}
    root = jax.random.PRNGKey(prog_seed + 1)
    losses, first, raw = [], None, None
    for e, batch in enumerate(batches):
        key = jax.random.fold_in(root, e * window.EPOCH_LEN)
        loss, _, grads = trainer.grads(batch, key, rows=rows)
        losses.append(loss)
        if first is None:
            raw = {k: np.asarray(v) for k, v in grads.items()}
            first = clip_like(raw, spec["train"])
        if not frozen_state:
            trainer.update(grads)
    change = {k: np.asarray(v) - start[k] for k, v in trainer.train.items()}
    return {"loss": losses, "grad1": first, "change": change, "raw": raw}


def numbers_of(prog: dict, ref_out: dict):
    """The numbers compared, by short plain names, and where the worst
    leaves are. ``grad1``: the first gradient as the optimizer gets it;
    ``dw3``: the parameters' change after the checked steps. Of each, the
    worst leaf, the worst leaf of the RPN head (whose gradient comes from the
    anchor losses alone, with no sampled roi in it) and the median leaf."""
    out = {f"loss{e + 1}": compare.rel(lp, lr)
           for e, (lp, lr) in enumerate(zip(prog["loss"], ref_out["loss"]))}
    ref_g = compare.norms(ref_out["grad1"])
    # leaves whose gradient is nought to rounding move by round-off alone
    med = float(np.median(list(ref_g.values())))
    skip = {k for k, v in ref_g.items() if v < 1e-3 * med}
    where = {"skipped": sorted(skip)}
    for name, p, r, sk in (
            ("grad1", compare.norms(prog["grad1"]), ref_g, ()),
            ("dw3", compare.norms(prog["change"]),
             compare.norms(ref_out["change"]), skip)):
        gaps = compare.leaf_gaps(p, r, skip=sk)
        out[name], where[name] = compare.worst(gaps)
        out[name + "_rpn"], _ = compare.worst(
            {k: v for k, v in gaps.items() if k.startswith("rpn/")})
        out[name + "_med"] = float(np.median(list(gaps.values())))
    out["input"] = prog["input_gap"]
    return out, where


def run(ctx: dict) -> dict:
    """ctx: cell, conf, mix, seed, seconds, trace, root, base, t0 and, from
    tests only, overrides (the program's config) and spec_overrides (the
    reference's sizes). Returns the result's parts."""
    import jax

    from mx_rcnn_tpu.data.datasets.imdb import (append_flipped_roidb,
                                                filter_roidb)
    from mx_rcnn_tpu.data.loader import AnchorLoader
    from mx_rcnn_tpu.data.packed import (load_packed_roidb,
                                         write_packed_dataset)
    from mx_rcnn_tpu.tools.train import fit_detector

    cell, conf, mix = ctx["cell"], ctx["conf"], ctx["mix"]
    phases = {}

    def mark(name):
        phases[name] = round(time.monotonic() - ctx["t0"], 3)

    mark("imports")
    seed, chips = ctx["seed"], cell["chips"]
    steer = ctx.get("spec_overrides", {})
    spec = dict(conf["spec"], **steer)
    spec["train"] = dict(conf["spec"]["train"], **steer.get("train", {}))
    prog_seed = seed % (2 ** 31 - 2)
    # the seed is in the path: the program memory-maps shards by path, and a
    # process that reads several seeds (benchmarks/readings.py) must not meet
    # the last one's pages
    work = os.path.join(ctx["root"], ".bench_work", cell["name"], f"s{seed}")
    shutil.rmtree(os.path.dirname(work), ignore_errors=True)
    os.makedirs(work)
    extra = dict(ctx.get("overrides", {}))
    if ctx["trace"]:
        extra.update({"obs.enabled": True, "obs.dir": os.path.join(work, "obs"),
                      "obs.cost_analysis": False, "obs.watchdog": False})
    cfg = _program_config(conf, extra)
    check_spec(cfg, spec)
    base = ctx.get("base", manifest.HERE)
    ref = manifest.load_module("reference", conf["reference"], base)

    # traffic: pixels from the seed, packed through the program's packer
    raw = traffic.make_roidb(mix, seed)
    write_packed_dataset(raw, cfg, os.path.join(work, "packed"))
    roidb = load_packed_roidb(os.path.join(work, "packed"), cfg)
    if cfg.train.flip:
        roidb = append_flipped_roidb(roidb, name="bench")
    roidb = filter_roidb(roidb)
    per_step = cfg.train.batch_images * chips
    k = int(mix["checked_steps"])
    need = (k + int(mix["warmup_steps"]) + 8
            + int((ctx["seconds"] + (mix.get("trace_seconds", 0)
                                     if ctx["trace"] else 0))
                  / float(mix["min_step_s"]))) * per_step
    roidb = roidb * (-(-need // len(roidb)))
    mark("traffic_packed")

    devices = jax.devices()[:chips]
    params = _seeded_params(cfg, seed)
    holder, first = {}, {"loss": []}
    mark("weights")

    def factory(roidb_, cfg_, n_shards, **kw):
        inner = AnchorLoader(roidb_, cfg_, num_shards=n_shards,
                             seed=prog_seed, **kw)
        holder["w"] = window.WindowLoader(
            inner, checked_steps=k, warmup_steps=mix["warmup_steps"],
            seconds=ctx["seconds"], devices=devices,
            trace_dir=os.path.join(work, "trace") if ctx["trace"] else None,
            trace_seconds=mix.get("trace_seconds", 0),
            on_open=lambda: holder.update(
                setup_s=time.monotonic() - ctx["t0"]))
        return holder["w"]

    def after_epoch(epoch, state, bag):
        if epoch >= k:
            return
        first["loss"].append(float(bag.get()["TotalLoss"]))
        mark(f"checked_step_{epoch + 1}")
        if epoch == 0:
            first["state1"] = jax.device_get(state.opt_state)
        if epoch == k - 1:
            first["params"] = _flat(state.params)

    fit_detector(
        cfg, roidb, os.path.join(work, "model", "bench"), begin_epoch=0,
        end_epoch=k + 1, frequent=20, pretrained_params=params,
        mesh_spec=str(chips), seed=prog_seed, epoch_callback=after_epoch,
        loader_factory=factory, checkpoint_period=10 ** 9)
    w = holder["w"]
    mark("fit_detector_returned")
    phases["window_open"] = round(holder.get("setup_s", 0.0), 3)
    if w.t_close is None:
        raise RuntimeError("the window never closed: the loader ran dry "
                           f"after {w.steps} steps (raise min_step_s' margin)")
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max((device_peak_bytes(s) for s in stats), default=0)
    del params
    gc.collect()

    # what the timed path produced in its first steps, against the reference
    w0 = weights.make(seed, ref.param_shapes(spec))
    b1 = (program_b1(cfg) if optimizer_of(spec["train"]) == "adamw"
          else None)
    grad1 = first_gradient(first.pop("state1"), w0, spec, b1)
    prog = {"loss": first["loss"], "grad1": grad1,
            "change": {p: first["params"][p] - np.asarray(w0[p])
                       for p in grad1}}
    del w0
    rows = [identify(b, raw) for b in w.first_batches]
    batches = [reference_batch(ref, r, raw, spec) for r in rows]
    prog["input_gap"] = max(
        float(np.max(np.abs(b["image"] - s["image"])))
        for b, s in zip(batches, w.first_batches))
    t_ref = time.monotonic()
    ref_out = follow(ref, spec, seed, prog_seed, batches)
    numbers, where = numbers_of(prog, ref_out)
    limits = manifest.load_json("limits", cell["name"], base)
    correct, table = compare.verdict(numbers, limits["limits"])

    images = w.steps * per_step
    rate = images / w.window_s / chips
    out = {
        "correct": correct, "compared": table, "where": where,
        "numbers": numbers, "phases": phases,
        "attempted": images, "failed": 0,
        "window_s": w.window_s, "steps": w.steps, "images": images,
        "setup_s": holder["setup_s"], "chips": chips,
        "memory_peak_bytes": int(peak_bytes),
        "memory_stats": max(stats, key=device_peak_bytes),
        "reference_s": time.monotonic() - t_ref,
        "rate": rate, "end_to_end": {"train_img_per_s_chip": rate},
        "spec": spec, "loader": w, "work": work,
        "traced_steps": w.trace[2] if w.trace else None,
        "events": [e for e in _step_events(os.path.join(work, "obs"))
                   if w.t_open_mono <= e.get("t_mono", 0) <= w.t_close_mono],
        "losses": {"program": prog["loss"], "reference": ref_out["loss"]},
        "checked": {"prog": prog, "ref": ref_out, "batches": batches,
                    "ref_module": ref, "prog_seed": prog_seed},
    }
    return out


def device_peak_bytes(stats: dict) -> int:
    """A device's peak as its runtime reports it. ``peak_bytes_in_use``
    counts live arrays only; a running program's temporaries are held as
    RESERVED bytes (read on the chip, PR 24: 1.29 GB in use, 7.92 GB reserved,
    against 0.45 GB of arguments and 7.95 GB of temporaries compiled). Both
    peak while a step runs, so the device's peak is their sum."""
    return int(stats.get("peak_bytes_in_use", 0)
               + stats.get("peak_bytes_reserved", 0))


def _step_events(obs_dir: str) -> list:
    events = []
    for path in sorted(glob.glob(os.path.join(obs_dir, "**", "*.jsonl"),
                                 recursive=True)):
        with open(path) as f:
            for line in f:
                try:
                    ev = json.loads(line)
                except ValueError:
                    continue
                if ev.get("type") == "step":
                    events.append(ev)
    return events
