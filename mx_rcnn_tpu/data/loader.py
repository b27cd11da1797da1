"""Batch loaders with background prefetch.

Reference: rcnn/core/loader.py — AnchorLoader (training DataIter: shuffle
with aspect-ratio grouping, load+resize, host-side assign_anchor) and
TestLoader (batch-1 inference iterator).

TPU deltas:
- anchor/ROI target assignment moved on-device (targets/), so AnchorLoader
  only yields images + padded gt boxes;
- every batch has ONE static shape (config.image.pad_shape + max_gt_boxes);
- a worker-thread pool assembles batches ahead of the device (the
  reference overlaps only via MXNet's PrefetchingIter when wired,
  SURVEY.md §4.1 'hot loops'). A training batch's pixels are written ONCE,
  by the normalize kernel, into their row of a batch buffer the loader
  owns and reuses (_BufferPool). What used to cap the loader was not the
  GIL (the kernel runs outside it) but fresh pages: a 7.9 MB temporary
  per image and a np.stack into a new (32, 640, 1024, 3) float32 every
  batch meant 252 MB of pages the kernel zero-fills on first touch and
  unmaps when the batch dies, which does not scale with threads in one
  address space. Measured on the four-chip host (30 cores, PR 28,
  PERF.md §6), this loader alone at batch 32: 137.5 img/s before, 1652.3
  after with two workers (208.8 -> 2701.2 with four); at batch 8 on the
  one-chip host (13 cores) 138.7 -> 1271.8. Four chips ask for 275, so
  the default stays at 2 workers: more threads would only take cores from
  the train loop's own host work. The packed shard format
  (data/packed.py) is the throughput path;
- aspect grouping survives as a perf knob (groups portrait/landscape so the
  short-side resize wastes less canvas), not a correctness feature.
"""

from __future__ import annotations

import sys
import threading
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.logger import logger
from mx_rcnn_tpu.data.feedguard import DataStallError, DataWorkerError
from mx_rcnn_tpu.data._native_img import normalize_pad
from mx_rcnn_tpu.data.image import (
    flip_image_and_boxes,
    load_image,
    resize_image,
)


def pad_shape_for(cfg: Config, scale_idx: int) -> tuple:
    """The static pad bucket for scale `scale_idx`: image.pad_shapes when
    present (must then match image.scales entry-for-entry), else the
    single image.pad_shape.

    An EMPTY pad_shapes is the documented fallback path (generate_config
    empties it when scales/pad_shape are overridden alone). A NON-empty
    length mismatch is the stale-pair trap — scales overridden next to
    leftover buckets would silently train under-/over-padded — and is a
    loud config error (cfg-contract family), not a silent fallback.

    A pad_shapes entry is stored LANDSCAPE-oriented ((H, W), H <= W);
    resolve_pad_bucket orients it per batch."""
    n = len(cfg.image.pad_shapes)
    if n and n != len(cfg.image.scales):
        raise ValueError(
            f"image.pad_shapes has {n} entries but image.scales has "
            f"{len(cfg.image.scales)} — the lists pair entry-for-entry. "
            "Override them together, or set image.pad_shapes=() to fall "
            "back to the single image.pad_shape")
    if n:
        return tuple(cfg.image.pad_shapes[scale_idx])
    return tuple(cfg.image.pad_shape)


def resolve_pad_bucket(cfg: Config, scale_idx: int,
                       landscape_flags: Sequence[bool]) -> tuple:
    """Orientation-aware bucket for one batch.

    Square-covering both orientations pads the dominant (landscape COCO)
    batches to ~1.6x their needed pixel area — measurable MFU on the conv
    hot path. With aspect grouping, batches are orientation-pure except at
    the group seam, so: all-landscape → (H, W) as stored; all-portrait →
    transposed; mixed (the rare seam batch) → the square cover. At most 3
    static shapes per scale, compiled once each."""
    h, w = pad_shape_for(cfg, scale_idx)
    h, w = min(h, w), max(h, w)  # normalize to landscape orientation
    if all(landscape_flags):
        return (h, w)
    if not any(landscape_flags):
        return (w, h)
    return (w, w)


def _load_roidb_entry(entry: Dict, cfg: Config, scale_idx: int = 0,
                      pad: Optional[tuple] = None,
                      out: Optional[np.ndarray] = None):
    """roidb record → (padded image f32 HWC, im_info, boxes, classes) at the
    chosen training scale. Handles the `flipped` flag the imdb sets. The
    image is written into ``out`` (a (pad_h, pad_w, 3) float32 row of the
    loader's batch buffer) when one is given, into a fresh array otherwise.

    Packed entries (data/packed.py shards) take the mmap fast path: the
    decode+resize already happened at pack time."""
    if "packed" in entry:
        from mx_rcnn_tpu.data.packed import load_packed_entry

        return load_packed_entry(entry, cfg, scale_idx, pad, out)
    if "image_data" in entry:  # synthetic datasets embed pixels directly
        img = entry["image_data"].astype(np.float32)
    else:
        img = load_image(entry["image"])
    boxes = entry["boxes"].astype(np.float32).copy()
    if entry.get("flipped"):
        img, boxes = flip_image_and_boxes(img, boxes)
    target, max_size = cfg.image.scales[scale_idx]
    img, scale = resize_image(img, target, max_size)
    boxes *= scale
    h, w = img.shape[:2]
    pad = pad if pad is not None else pad_shape_for(cfg, scale_idx)
    img = normalize_pad(np.ascontiguousarray(img, np.float32),
                        cfg.image.pixel_means, cfg.image.pixel_stds, pad,
                        out=out)
    im_info = np.asarray([h, w, scale], np.float32)
    return img, im_info, boxes, entry["gt_classes"].astype(np.int32)


def _load_roidb_content(entry: Dict, cfg: Config, scale_idx: int,
                        fit: float = 1.0):
    """roidb record → (normalized UNPADDED image, im_info [h, w, scale],
    boxes, classes) at the drawn scale × the scale-to-fit factor — the
    graftcanvas packed path's load: the batch assembler places the raw
    content into a shared canvas instead of padding per image.

    Packed entries take the mmap fast path (data/packed.py
    load_packed_content): the stored content slice feeds the placement
    directly; only a fit < 1 batch pays a second resample."""
    if "packed" in entry:
        from mx_rcnn_tpu.data.packed import load_packed_content

        return load_packed_content(entry, cfg, scale_idx, fit)
    if "image_data" in entry:
        img = entry["image_data"].astype(np.float32)
    else:
        img = load_image(entry["image"])
    boxes = entry["boxes"].astype(np.float32).copy()
    if entry.get("flipped"):
        img, boxes = flip_image_and_boxes(img, boxes)
    target, max_size = cfg.image.scales[scale_idx]
    if fit < 1.0:
        target = max(1, int(round(target * fit)))
        max_size = max(1, int(round(max_size * fit)))
    img, scale = resize_image(img, target, max_size)
    boxes *= scale
    h, w = img.shape[:2]
    # pad == content dims: a no-op pad keeps the one-pass kernel
    img = normalize_pad(np.ascontiguousarray(img, np.float32),
                        cfg.image.pixel_means, cfg.image.pixel_stds, (h, w))
    im_info = np.asarray([h, w, scale], np.float32)
    return img, im_info, boxes, entry["gt_classes"].astype(np.int32)


def _pad_gt(boxes: np.ndarray, classes: np.ndarray, max_gt: int):
    g = min(len(boxes), max_gt)
    out_b = np.zeros((max_gt, 4), np.float32)
    out_c = np.zeros((max_gt,), np.int32)
    out_v = np.zeros((max_gt,), bool)
    out_b[:g] = boxes[:g]
    out_c[:g] = classes[:g]
    out_v[:g] = True
    return out_b, out_c, out_v


def _entry_gt_masks(entry: Dict, m: int, max_gt: int) -> np.ndarray:
    """Box-frame (max_gt, m, m) instance masks for one roidb entry.

    Sources, in priority order: a precomputed entry["gt_masks"] (G, m', m')
    array (synthetic dataset / caches; nearest-resampled if m' != m), or
    entry["segmentations"] polygon lists rasterized against entry["boxes"]
    (COCO). Missing masks default to all-ones (box == mask). Horizontal flip
    (entry["flipped"]) mirrors the box-frame mask content — the box coords
    were already mirrored by the imdb."""
    from mx_rcnn_tpu import masks as _masks

    boxes = entry["boxes"]
    g = min(len(boxes), max_gt)
    out = np.zeros((max_gt, m, m), np.uint8)
    pre = entry.get("gt_masks")
    segs = entry.get("segmentations")
    for i in range(g):
        if pre is not None:
            mm = pre[i]
            if mm.shape != (m, m):
                yi = (np.arange(m) * mm.shape[0] // m)
                xi = (np.arange(m) * mm.shape[1] // m)
                mm = mm[np.ix_(yi, xi)]
            out[i] = mm.astype(np.uint8)
        elif segs is not None and segs[i]:
            # roidb boxes and polygons are both stored unflipped (the loader
            # mirrors at load time), so they line up directly; the content
            # mirror below handles the flipped copies.
            out[i] = _masks.poly_box_frame_mask(segs[i], boxes[i], m)
        else:
            out[i] = 1
    if entry.get("flipped"):
        out = out[:, :, ::-1]
    return out


class _BufferPool:
    """The float32 batch buffers one loader owns, per shape, reused.

    ``take`` hands out a buffer that nothing outside the pool references
    any more, and allocates a new one when there is none: no release
    call, no ring length. A consumer that keeps a batch (the benchmark's
    window keeps its first three ``image`` arrays by reference) keeps its
    buffer out of circulation for as long; ``jax.device_put`` holds the
    host array until its transfer is done (on the CPU backend, for the
    life of the device array), so unreferenced is also what makes reuse
    safe against a copy in flight. References are counted on the OWNING
    array: numpy collapses a view of a view onto it (``buf[:][0].base is
    buf``), so a surviving row or slice of a batch counts too.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._bufs: Dict[tuple, List[np.ndarray]] = {}
        self.allocated = 0
        # what sys.getrefcount reads, in the form take() asks it, of an
        # array that only a list holds
        probe = [np.empty(0, np.float32)]
        self._idle_refs = sys.getrefcount(probe[0])

    def take(self, shape: Sequence[int]) -> np.ndarray:
        """An idle (or new) C-contiguous float32 buffer of ``shape``;
        its contents are whatever the last batch left there."""
        shape = tuple(int(d) for d in shape)
        with self._lock:
            bufs = self._bufs.setdefault(shape, [])
            for k in range(len(bufs)):
                if sys.getrefcount(bufs[k]) == self._idle_refs:
                    return bufs[k]
            bufs.append(np.empty(shape, np.float32))
            self.allocated += 1
            return bufs[-1]


class _PrefetchIterator:
    """Thread-pool prefetcher: indices → assembled batches, `depth` ahead.

    Backpressure: workers acquire a slot semaphore (depth total) before
    building a batch; the consumer releases it on yield — so at most `depth`
    batches are buffered. Worker exceptions are captured and re-raised in the
    consumer at that batch position (a dead loader must fail loudly, not
    hang the train loop).

    Lifecycle: workers are daemon threads (an abandoned iterator can never
    wedge interpreter exit), but `close()` is the REAL shutdown — it stops
    the pool, drains the buffered results, and JOINS every worker, so a
    disposed iterator leaves no thread alive (the epoch-end contract
    tools/train.py relies on; tested in tests/test_datasets.py).

    graftfeed (``guard`` — a data/feedguard.py FeedGuard): the consumer
    supervises the pool while it waits — a worker thread that died
    without a clean exit has its claimed queue position requeued and a
    replacement spawned (``data_worker`` event; DataWorkerError past
    ``data.worker_restart_max`` deaths) — and a blocking wait that
    outlasts ``data.wait_deadline_s`` raises DataStallError instead of
    hanging on dead storage. Without a guard both behaviors are off
    (wait forever, die with the worker) — the pre-graftfeed contract.
    """

    _ids = iter(range(1_000_000_000))

    def __init__(self, make_batch, batch_indices: Sequence, depth: int = 4,
                 workers: int = 4, guard=None):
        self._make = make_batch
        self._indices = list(batch_indices)
        self._slots = threading.Semaphore(max(1, depth))
        self._threads: List[threading.Thread] = []
        self._next = 0
        self._lock = threading.Lock()
        self._emitted = {}
        self._emit_cond = threading.Condition()
        self._stop = threading.Event()
        self._guard = guard
        self._claims: Dict[str, int] = {}   # thread name -> claimed pos
        self._requeue: List[int] = []       # positions lost to dead workers
        self._done: set = set()             # names that exited CLEANLY
        self._deaths = 0
        self._worker_fail: Optional[BaseException] = None
        self._closed = False
        pool = next(self._ids)
        for i in range(max(1, workers)):
            t = threading.Thread(target=self._worker, args=(i,), daemon=True,
                                 name=f"loader-worker-{pool}-{i}")
            t.start()
            self._threads.append(t)

    def _worker(self, widx: int):
        name = threading.current_thread().name
        spec = self._guard.chaos_spec if self._guard is not None else None
        while not self._stop.is_set():
            if not self._slots.acquire(timeout=0.1):
                continue  # re-check stop flag
            with self._lock:
                if self._requeue:  # a dead sibling's lost claim first
                    pos = self._requeue.pop(0)
                elif self._next < len(self._indices):
                    pos = self._next
                    self._next += 1
                else:
                    self._slots.release()
                    self._done.add(name)
                    return
                self._claims[name] = pos
            if spec is not None and spec.active:
                spec.maybe_die("data_worker_loop")
                if spec.maybe_worker_die(widx):
                    # Abrupt chaos death: claim kept, slot kept, no
                    # result — what a segfaulting decoder leaves behind;
                    # consumer-side supervision must requeue + resurrect.
                    return
            try:
                result = ("ok", self._make(self._indices[pos]))
            except BaseException as exc:  # noqa: BLE001  # graftlint: disable=broad-except — captured and re-raised in the consumer, not swallowed
                result = ("err", exc)
            with self._lock:
                self._claims.pop(name, None)
            with self._emit_cond:
                # Preserve order: the consumer pops positions sequentially.
                self._emitted[pos] = result
                self._emit_cond.notify_all()
        with self._lock:
            self._done.add(name)

    def _supervise(self):
        """Consumer-side worker supervision (runs between waits): a
        thread that died without a clean exit gets its claimed position
        requeued and — restart budget permitting — a replacement thread
        spawned; past ``data.worker_restart_max`` deaths the pool is
        declared broken (the consumer raises DataWorkerError)."""
        dead = [t for t in self._threads if not t.is_alive()]
        for t in dead:
            self._threads.remove(t)
            with self._lock:
                clean = t.name in self._done
                pos = self._claims.pop(t.name, None)
                if pos is not None:
                    self._requeue.append(pos)
            if clean:
                continue
            self._deaths += 1
            guard = self._guard
            limit = guard.worker_restart_max if guard is not None else 0
            resurrect = guard is not None and self._deaths <= limit
            logger.warning(
                "loader worker %s died (death %d/%d)%s%s", t.name,
                self._deaths, limit,
                f", requeued position {pos}" if pos is not None else "",
                " — resurrecting" if resurrect
                else " — restart budget spent")
            if guard is not None:
                guard.emit_worker_event(
                    worker=t.name, deaths=self._deaths, restart_max=limit,
                    requeued=pos if pos is not None else -1,
                    resurrected=resurrect)
            if not resurrect:
                self._worker_fail = DataWorkerError(
                    f"{self._deaths} prefetch worker death(s) exceed "
                    f"data.worker_restart_max={limit} — the input plane "
                    "itself is broken (decoder/native crash loop); last "
                    f"casualty: {t.name}")
                return
            if pos is not None:
                # The dead worker still held its backpressure slot —
                # hand it back or the pool deadlocks at depth exhaustion.
                self._slots.release()
            r = threading.Thread(target=self._worker, args=(-1,),
                                 daemon=True,
                                 name=f"{t.name}-r{self._deaths}")
            r.start()
            self._threads.append(r)

    def __iter__(self):
        deadline_s = (self._guard.wait_deadline_s
                      if self._guard is not None else 0.0)
        for pos in range(len(self._indices)):
            t0 = time.monotonic()
            while True:
                with self._emit_cond:
                    if pos in self._emitted:
                        result = self._emitted.pop(pos)
                        break
                    if self._stop.is_set():
                        result = None
                        break
                    self._emit_cond.wait(timeout=0.1)
                self._supervise()
                if self._worker_fail is not None:
                    self._stop.set()
                    raise self._worker_fail
                if deadline_s and time.monotonic() - t0 > deadline_s:
                    self._stop.set()
                    raise DataStallError(
                        f"no batch arrived at queue position {pos} within "
                        f"data.wait_deadline_s={deadline_s:.0f}s "
                        f"({len(self._threads)} worker(s) alive, "
                        f"{self._deaths} death(s)) — storage is stuck or "
                        "the input plane is wedged")
            if result is None:
                return
            kind, payload = result
            self._slots.release()
            if kind == "err":
                self._stop.set()
                raise payload
            yield payload

    def close(self):
        """Stop, drain, and JOIN the pool. Idempotent (a second close —
        or closing after a worker already crashed — is a no-op/skip, not
        a block on a thread that will never drain). Workers poll the
        stop flag every 0.1 s while waiting for a slot and exit after at
        most one in-flight batch build, so the join is bounded by one
        batch's assembly time; chaos-hung loads poll the same flag."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        for t in self._threads:
            if t.is_alive():
                t.join(timeout=30.0)
                if t.is_alive():
                    # a worker wedged inside make_batch (>30 s) breaks
                    # the no-survivor contract — say so, don't hide it
                    logger.warning(
                        "loader worker %s did not join within 30s; "
                        "leaking a daemon thread", t.name)
        with self._emit_cond:
            self._emitted.clear()
            self._emit_cond.notify_all()


class _CloseableLoader:
    """Shared shutdown surface for the batch loaders: tracks every live
    prefetcher (overlapping iterations over the same loader each get
    their own pool), so `close()` (or `with loader: ...`) joins all
    worker threads even when an epoch was abandoned mid-stream.
    Exhausting an iterator closes its prefetcher automatically; close()
    is the explicit hook for early exits (tools/train.py epoch end).

    Also hosts the graftprof pad-waste counters: batch assembly calls
    ``_note_pad(real_px, canvas_px)`` (from worker threads — locked), so
    ``pad_waste_stats()`` reports what fraction of every canvas pixel
    the run paid for padding — the measured baseline of the ROADMAP's
    canvas-packing lever. Counters are cumulative over the loader's
    lifetime (fit_detector folds them into each epoch event)."""

    _active: Tuple[_PrefetchIterator, ...] = ()
    #: shared class-level lock — _note_pad is called from prefetch WORKER
    #: threads, which start before any per-instance init could run; a
    #: lazily-created instance lock would race its own creation.
    #: Contention is a few batches/sec across all loaders — negligible.
    _pad_lock: threading.Lock = threading.Lock()
    _pad_real_px = 0
    _pad_canvas_px = 0
    _pad_batches = 0

    def _note_pad(self, real_px: float, canvas_px: float):
        with self._pad_lock:
            self._pad_real_px += int(real_px)
            self._pad_canvas_px += int(canvas_px)
            self._pad_batches += 1

    def pad_waste_stats(self) -> Optional[Dict[str, float]]:
        """Cumulative padding accounting, or None before the first
        batch. ``pad_waste`` = 1 − real/canvas pixels."""
        if not self._pad_canvas_px:
            return None
        return {
            "real_px": self._pad_real_px,
            "canvas_px": self._pad_canvas_px,
            "batches": self._pad_batches,
            "pad_waste": round(
                1.0 - self._pad_real_px / self._pad_canvas_px, 4),
        }

    #: graftfeed guard (data/feedguard.py FeedGuard) — None keeps every
    #: pre-graftfeed behavior (no retry, no quarantine, wait forever).
    _guard = None

    def _feed_cancel(self) -> bool:
        """Stop predicate threaded into the guard's cancel-aware hooks
        (chaos hang injection): True once any of this loader's live
        prefetchers has been stopped — a hung worker must release when
        the consumer gives up (DataStallError) or the loader closes."""
        return any(p._stop.is_set() for p in self._active)

    def _guarded(self, load_one, i: int):
        """Route one record load through graftfeed when armed: classified
        transient-IO retry under data.record_deadline_s, quarantine +
        deterministic substitution for permanent corruption. Returns
        ``(result, actual_index)`` — the index differs from ``i`` when a
        quarantine substituted, and per-entry side lookups (gt masks)
        must follow it."""
        if self._guard is None:
            return load_one(i), i
        return self._guard.load(load_one, i, cancel=self._feed_cancel)

    def _run_prefetch(self, it: _PrefetchIterator):
        self._active = self._active + (it,)
        try:
            yield from it
        finally:
            it.close()
            self._active = tuple(p for p in self._active if p is not it)

    def close(self):
        for it in self._active:
            it.close()
        self._active = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class AnchorLoader(_CloseableLoader):
    """Training loader: roidb → static-shape batches.

    Yields dicts with keys image (B,H,W,3) f32, im_info (B,3),
    gt_boxes (B,G,4), gt_classes (B,G), gt_valid (B,G) — the forward_train
    batch contract. B = cfg.train.batch_images × num_shards (devices).
    ``image`` is one of the loader's reused buffers (_BufferPool): it is
    the consumer's for as long as the consumer references it, and goes
    back into circulation when the last reference is dropped.

    graftcanvas (cfg.image.canvas_pack): batches are instead PACKED —
    each shard's images shelf-packed into one fixed canvas plane
    (data/canvas.py planner), yielding image (P,Hc,Wc,3) + im_info
    (P,I,5) placement rows + (P,I,G,·) canvas-coordinate gt tensors (the
    ops/canvas.py contract). Every batch of every scale draw then has
    the SAME shape — one compiled train step, period — and the pad
    counters below measure canvas utilization instead of bucket waste.
    """

    def __init__(self, roidb: List[Dict], cfg: Config, num_shards: int = 1,
                 shuffle: Optional[bool] = None, seed: int = 0,
                 prefetch_depth: int = 4, workers: int = 2,
                 process_count: int = 1, process_index: int = 0,
                 guard=None):
        """num_shards = data-axis shards THIS process feeds. Multi-host
        (process_count > 1): every process must use the SAME seed — the
        epoch order is computed over the global batch and each process
        loads its own column slice, preserving exact global-batch DP
        semantics (parallel/distributed.py).

        ``guard`` is a graftfeed FeedGuard (data/feedguard.py) — built
        once per run by fit_detector and shared across heal-time loader
        rebuilds, because the quarantine set is run-scoped state. None
        (standalone/dev iteration) keeps the pre-graftfeed behavior."""
        self.roidb = roidb
        self.cfg = cfg
        self.batch_size = cfg.train.batch_images * num_shards
        self.process_count = process_count
        self.process_index = process_index
        self.global_batch_size = self.batch_size * process_count
        self.shuffle = cfg.train.shuffle if shuffle is None else shuffle
        self.aspect_grouping = cfg.train.aspect_grouping
        self._seed = seed
        self._rng = np.random.RandomState(seed)
        self._depth = prefetch_depth
        self._workers = workers
        self._guard = guard
        self._pool = _BufferPool()
        self._canvas_spec = None
        if cfg.image.canvas_pack:
            from mx_rcnn_tpu.data.canvas import validate_canvas_pack

            self._canvas_spec = validate_canvas_pack(cfg)

    def __len__(self):
        return len(self.roidb) // self.global_batch_size

    def set_epoch(self, epoch: int):
        """Reseed the order rng as a pure function of (seed, epoch) — the
        distributed-sampler idiom. fit_detector calls this at every epoch
        start so the epoch's batch order (and scale-bucket draw) is
        reproducible in isolation: a run resumed at epoch E (or mid-epoch
        via a graftguard emergency save, which SKIPS the already-trained
        prefix) replays exactly the order the uninterrupted run saw —
        the bit-exact kill→resume parity gate depends on it. Multi-host:
        identical on every process (same seed, same epoch). Standalone
        iteration without set_epoch keeps the legacy advancing stream."""
        self._rng = np.random.RandomState(
            (self._seed * 1_000_003 + epoch) % (2 ** 32))
        if self._guard is not None:
            # graftfeed: the epoch feeds the chaos E:I keys and the
            # deterministic quarantine-replacement draw.
            self._guard.set_epoch(epoch)

    def _epoch_order(self) -> np.ndarray:
        n = len(self.roidb)
        if not self.shuffle:
            return np.arange(n)
        if self.aspect_grouping:
            # Reference: group landscape vs portrait (loader.py) so resize
            # shapes cluster; with one static pad it just improves locality.
            widths = np.array([r.get("width", 1) for r in self.roidb])
            heights = np.array([r.get("height", 1) for r in self.roidb])
            horz = np.where(widths >= heights)[0]
            vert = np.where(widths < heights)[0]
            self._rng.shuffle(horz)
            self._rng.shuffle(vert)
            inds = np.hstack([horz, vert])
            # Rotate by a random offset so the trimmed epoch tail (below)
            # doesn't always fall on the second (vert) group — without this
            # the minority orientation is dropped disproportionately every
            # epoch. Costs one extra mixed-orientation seam, same as the
            # horz/vert boundary batch already present.
            inds = np.roll(inds, int(self._rng.randint(max(n, 1))))
            # Shuffle at (global) batch granularity to keep groups together.
            gb = self.global_batch_size
            nb = n // gb
            trimmed = inds[: nb * gb].reshape(nb, gb)
            self._rng.shuffle(trimmed)
            return trimmed.reshape(-1)
        inds = np.arange(n)
        self._rng.shuffle(inds)
        return inds

    def _content_sizes_fn(self, idxs, scale_idx):
        """sizes_fn for the canvas planner: per-image content (h, w) at
        the drawn scale × fit, via the SAME arithmetic the load path
        uses (data/canvas.py::content_size; packed entries read their
        stored post-resize dims) — planned rects match loaded pixels."""
        from mx_rcnn_tpu.data.canvas import content_size

        cfg = self.cfg
        target0, max0 = cfg.image.scales[scale_idx]

        def sizes_at(fit):
            if fit < 1.0:
                t = max(1, int(round(target0 * fit)))
                mx = max(1, int(round(max0 * fit)))
            else:
                t, mx = target0, max0
            out = []
            for i in idxs:
                e = self.roidb[i]
                if "packed" in e:
                    ref = e["packed"].get(scale_idx)
                    if ref is None:
                        # Same remediation hint as load_packed_content —
                        # the planner runs BEFORE any load, so the error
                        # must be raised (descriptively) here too.
                        raise ValueError(
                            f"scale_idx {scale_idx} is not packed (have "
                            f"{sorted(e['packed'])}); re-pack with "
                            "write_packed_dataset covering every "
                            "training scale")
                    rh, rw = ref["hw"]
                    out.append((rh, rw) if fit >= 1.0
                               else content_size(rh, rw, t, mx)[:2])
                    continue
                if "image_data" in e:
                    h0, w0 = e["image_data"].shape[:2]
                else:
                    h0, w0 = e["height"], e["width"]
                out.append(content_size(h0, w0, t, mx)[:2])
            return out

        return sizes_at

    def _make_packed_batch(self, idxs, scale_idx) -> Dict[str, np.ndarray]:
        """graftcanvas batch assembly: plan placements (scale-to-fit on
        overflow), load unpadded content, place into fixed canvas
        planes, shift gt boxes to canvas coordinates."""
        from mx_rcnn_tpu.data.canvas import plan_batch

        cfg = self.cfg
        if self._guard is not None:
            # graftfeed pre-resolution: records already known quarantined
            # are substituted BEFORE the planner measures content sizes,
            # so planned rects match loaded pixels. A mid-batch DISCOVERY
            # still substitutes at load time (the slot clamp below
            # absorbs the size delta — one batch, once per record).
            idxs = [self._guard.resolve(i) for i in idxs]
        spec = self._canvas_spec
        g = cfg.train.max_gt_boxes
        with_masks = cfg.network.use_mask
        m = cfg.train.mask_gt_resolution
        ch, cw = spec.shape
        placements, fit, _ = plan_batch(
            self._content_sizes_fn(idxs, scale_idx), len(idxs), spec)
        planes = len(idxs) // spec.images
        image = self._pool.take((planes, ch, cw, 3))
        image.fill(0.0)
        info = np.zeros((planes, spec.images, 5), np.float32)
        gtb = np.zeros((planes, spec.images, g, 4), np.float32)
        gtc = np.zeros((planes, spec.images, g), np.int32)
        gtv = np.zeros((planes, spec.images, g), bool)
        gtm = (np.zeros((planes, spec.images, g, m, m), np.uint8)
               if with_masks else None)
        real_px = 0.0
        for j, i in enumerate(idxs):
            def _load_content(k, _s=scale_idx, _f=fit):
                return _load_roidb_content(self.roidb[k], cfg, _s, _f)

            (img, iminfo, boxes, classes), ri = self._guarded(
                _load_content, i)
            entry = self.roidb[ri]
            pl, y0, x0 = placements[j]
            slot = j % spec.images
            # Clamp into the canvas: a fit<1 double-resample can round a
            # pixel past the plan; the slot's gap margin absorbs it.
            h = min(img.shape[0], ch - y0)
            w = min(img.shape[1], cw - x0)
            image[pl, y0:y0 + h, x0:x0 + w] = img[:h, :w]
            if len(boxes):
                boxes = boxes + np.asarray([x0, y0, x0, y0], np.float32)
            b_, c_, v_ = _pad_gt(boxes, classes, g)
            info[pl, slot] = (h, w, iminfo[2], y0, x0)
            gtb[pl, slot] = b_
            gtc[pl, slot] = c_
            gtv[pl, slot] = v_
            if with_masks:
                gtm[pl, slot] = _entry_gt_masks(entry, m, g)
            real_px += float(h) * float(w)
        batch = {
            "image": image,
            "im_info": info,
            "gt_boxes": gtb,
            "gt_classes": gtc,
            "gt_valid": gtv,
        }
        if with_masks:
            batch["gt_masks"] = gtm
        # graftprof: in packed mode the counters measure CANVAS
        # utilization — real content pixels over compiled canvas pixels.
        self._note_pad(real_px, planes * ch * cw)
        return batch

    def _make_batch(self, item) -> Dict[str, np.ndarray]:
        idxs, scale_idx = item
        cfg = self.cfg
        if self._canvas_spec is not None:
            return self._make_packed_batch(idxs, scale_idx)
        g = cfg.train.max_gt_boxes
        with_masks = cfg.network.use_mask
        m = cfg.train.mask_gt_resolution
        if self._guard is not None:
            # graftfeed pre-resolution: known-quarantined records swap out
            # BEFORE the orientation vote below, so the pad bucket matches
            # what actually loads (a mid-batch discovery is clamped).
            idxs = [self._guard.resolve(i) for i in idxs]
        pad = resolve_pad_bucket(cfg, scale_idx, [
            self.roidb[i].get("width", 1) >= self.roidb[i].get("height", 1)
            for i in idxs])
        image = self._pool.take((len(idxs), pad[0], pad[1], 3))
        infos, gtb, gtc, gtv, gtm = [], [], [], [], []
        for j, i in enumerate(idxs):
            def _load_entry(k, _s=scale_idx, _p=pad, _row=image[j]):
                # A quarantine substitute can carry the other orientation
                # and overflow this batch's bucket: load those against the
                # square cover and let the clamp below cut them into the
                # row.
                e = self.roidb[k]
                land = e.get("width", 1) >= e.get("height", 1)
                fits = _p[1] >= _p[0] if land else _p[0] >= _p[1]
                if fits:
                    return _load_roidb_entry(e, cfg, _s, _p, out=_row)
                return _load_roidb_entry(e, cfg, _s, (max(_p), max(_p)))

            (img, info, boxes, classes), ri = self._guarded(_load_entry, i)
            entry = self.roidb[ri]
            if img.shape[:2] != tuple(pad):
                # A mid-batch quarantine substitute with the other
                # orientation — clamp its content into the row
                # (deterministic; once per discovered record).
                ch = min(img.shape[0], pad[0])
                cw = min(img.shape[1], pad[1])
                image[j].fill(0.0)
                image[j, :ch, :cw] = img[:ch, :cw]
            b, c, v = _pad_gt(boxes, classes, g)
            infos.append(info)
            gtb.append(b)
            gtc.append(c)
            gtv.append(v)
            if with_masks:
                gtm.append(_entry_gt_masks(entry, m, g))
        batch = {
            "image": image,
            "im_info": np.stack(infos),
            "gt_boxes": np.stack(gtb),
            "gt_classes": np.stack(gtc),
            "gt_valid": np.stack(gtv),
        }
        if with_masks:
            batch["gt_masks"] = np.stack(gtm)
        # graftprof pad accounting: im_info rows are [h, w, scale] with
        # (h, w) the pre-pad content size — a few adds per batch.
        self._note_pad(sum(float(i[0]) * float(i[1]) for i in infos),
                       len(idxs) * pad[0] * pad[1])
        return batch

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        order = self._epoch_order()
        gb = self.global_batch_size
        nb = len(order) // gb
        batches = order[: nb * gb].reshape(nb, gb)
        # Multi-host: this process loads only its column slice of each
        # global batch (same order on every process — same seed).
        lo = self.process_index * self.batch_size
        batches = batches[:, lo:lo + self.batch_size]
        # Multi-scale: one scale bucket per GLOBAL batch (drawn from the
        # shared-seed rng AFTER the order draw, so every host picks the
        # same buckets). Each distinct bucket is one static shape.
        n_scales = len(self.cfg.image.scales)
        scale_ids = (self._rng.randint(n_scales, size=nb) if n_scales > 1
                     else np.zeros(nb, np.int64))
        items = [(batches[i], int(scale_ids[i])) for i in range(nb)]
        yield from self._run_prefetch(
            _PrefetchIterator(self._make_batch, items,
                              depth=self._depth, workers=self._workers,
                              guard=self._guard))


class ROIIter(AnchorLoader):
    """Fast-R-CNN-stage loader over precomputed proposals.

    Reference: rcnn/core/loader.py::ROIIter (selective-search or RPN-dumped
    proposals from imdb.rpn_roidb). Adds proposals (B, P, 4) +
    proposal_valid (B, P) to the batch, padded to `max_proposals`.
    """

    def __init__(self, roidb: List[Dict], cfg: Config, num_shards: int = 1,
                 max_proposals: int = 2000, **kw):
        if cfg.image.canvas_pack:
            raise NotImplementedError(
                "image.canvas_pack is not supported by ROIIter: "
                "precomputed proposals would need placement shifting and "
                "the Fast-RCNN stage forward runs bucketed. Disable "
                "canvas_pack for alternate-stage training")
        super().__init__(roidb, cfg, num_shards, **kw)
        self.max_proposals = max_proposals

    def _make_batch(self, item) -> Dict[str, np.ndarray]:
        idxs, _scale_idx = item
        batch = super()._make_batch(item)
        p = self.max_proposals
        props = np.zeros((len(idxs), p, 4), np.float32)
        pvalid = np.zeros((len(idxs), p), bool)
        for j, i in enumerate(idxs):
            entry = self.roidb[i]
            raw = entry.get("proposals",
                            np.zeros((0, 4), np.float32)).astype(np.float32)
            if entry.get("flipped") and len(raw):
                w = entry["width"]
                raw = raw.copy()
                x1 = raw[:, 0].copy()
                raw[:, 0] = w - 1 - raw[:, 2]
                raw[:, 2] = w - 1 - x1
            scale = batch["im_info"][j, 2]
            n = min(len(raw), p)
            props[j, :n] = raw[:n] * scale
            pvalid[j, :n] = True
        batch["proposals"] = props
        batch["proposal_valid"] = pvalid
        return batch


class TestLoader(_CloseableLoader):
    """Inference loader (reference: rcnn/core/loader.py TestLoader).

    Yields (batch_dict, meta) where meta carries the per-image scale and true
    size for mapping detections back to original image coordinates.
    """

    __test__ = False  # pytest: not a test class, despite the name

    def __init__(self, roidb: List[Dict], cfg: Config, batch_size: int = 1,
                 prefetch_depth: int = 4, workers: int = 2, guard=None):
        self.roidb = roidb
        self.cfg = cfg
        self.batch_size = batch_size
        self._depth = prefetch_depth
        self._workers = workers
        self._guard = guard  # graftfeed (epoch stays 0 for inference)

    def __len__(self):
        return (len(self.roidb) + self.batch_size - 1) // self.batch_size

    def _make_batch(self, idxs):
        cfg = self.cfg
        # Inference uses ONE scale — the last (largest) entry, the
        # reference's TEST.SCALE convention under multi-scale training.
        scale_idx = len(cfg.image.scales) - 1
        real_idxs = [i if i >= 0 else len(self.roidb) - 1 for i in idxs]
        pad = resolve_pad_bucket(cfg, scale_idx, [
            self.roidb[i].get("width", 1) >= self.roidb[i].get("height", 1)
            for i in real_idxs])
        imgs, infos, metas = [], [], []
        for i in idxs:
            if i < 0:  # tail padding repeats the last real image
                i = len(self.roidb) - 1
                real = False
            else:
                real = True

            def _load_entry(k, _s=scale_idx, _p=pad):
                return _load_roidb_entry(
                    {**self.roidb[k], "boxes": np.zeros((0, 4), np.float32),
                     "gt_classes": np.zeros((0,), np.int32)}, cfg, _s, _p)

            (img, info, _, _), _ri = self._guarded(_load_entry, i)
            imgs.append(img)
            infos.append(info)
            metas.append({"index": i, "scale": float(info[2]), "real": real})
        self._note_pad(sum(float(i[0]) * float(i[1]) for i in infos),
                       len(idxs) * pad[0] * pad[1])
        return {"image": np.stack(imgs), "im_info": np.stack(infos)}, metas

    def __iter__(self):
        n = len(self.roidb)
        # Orientation-grouped order (landscape first, stable): with
        # batch_size > 1 this keeps batches orientation-pure so they take
        # the rectangular pad bucket, not the ~1.6x square mixed cover —
        # at most one mixed seam batch. metas carry the original index,
        # so detection ordering is unaffected.
        land = np.array([r.get("width", 1) >= r.get("height", 1)
                         for r in self.roidb])
        idxs = np.concatenate([np.nonzero(land)[0], np.nonzero(~land)[0]])
        pad = (-n) % self.batch_size
        if pad:
            idxs = np.concatenate([idxs, -np.ones(pad, np.int64)])
        batches = idxs.reshape(-1, self.batch_size)
        yield from self._run_prefetch(
            _PrefetchIterator(self._make_batch, batches,
                              depth=self._depth, workers=self._workers,
                              guard=self._guard))
