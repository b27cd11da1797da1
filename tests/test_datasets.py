"""PascalVOC / COCODataset against the checked-in 2-image fixtures.

VERDICT round 1 flagged both dataset classes as never-executed (offline, no
data); tests/fixtures/mini_voc and mini_coco are tiny but REAL on-disk
datasets (actual JPEGs, VOC XML, COCO instances json incl. a crowd-RLE
annotation) so the parse → roidb → loader → eval paths run in CI.
"""

import os

import numpy as np
import pytest

from mx_rcnn_tpu.config import generate_config
from mx_rcnn_tpu.data.datasets.coco import COCODataset
from mx_rcnn_tpu.data.datasets.pascal_voc import PascalVOC
from mx_rcnn_tpu.data.loader import AnchorLoader

FIXTURES = os.path.join(os.path.dirname(__file__), "fixtures")
VOC_ROOT = os.path.join(FIXTURES, "mini_voc/VOCdevkit")
COCO_ROOT = os.path.join(FIXTURES, "mini_coco")


# ---------------------------------------------------------------------------
# PASCAL VOC
# ---------------------------------------------------------------------------


@pytest.fixture
def voc():
    return PascalVOC("2007_minitest", root_path=FIXTURES,
                     dataset_path=VOC_ROOT)


def test_voc_index_and_roidb(voc):
    assert voc.image_index == ["000001", "000002"]
    roidb = voc._load_gt_roidb()
    assert len(roidb) == 2
    e1 = roidb[0]
    # Difficult person and non-VOC class are excluded from training boxes;
    # the dog stays, converted to 0-indexed coords.
    assert e1["boxes"].shape == (1, 4)
    np.testing.assert_allclose(e1["boxes"][0], [10, 8, 40, 38])
    assert voc.classes[e1["gt_classes"][0]] == "dog"
    # ... but kept for evaluation (difficult handling); the non-VOC class is
    # dropped entirely at parse.
    assert e1["all_boxes"].shape == (2, 4)
    assert e1["difficult"].tolist() == [False, True]
    assert e1["height"] == 48 and e1["width"] == 64


def test_voc_loader_reads_real_jpegs(voc):
    cfg = generate_config("resnet50", "PascalVOC", **{
        "image.pad_shape": (64, 64), "image.scales": ((48, 64),),
        "train.max_gt_boxes": 4, "train.flip": False,
    })
    roidb = voc._load_gt_roidb()
    loader = AnchorLoader(roidb, cfg, num_shards=1, shuffle=False, seed=0)
    batch = next(iter(loader))
    assert batch["image"].shape == (1, 64, 64, 3)
    assert batch["gt_valid"][0].sum() == 1
    # The dog rectangle is red-ish: the mean-subtracted red channel inside
    # the box must exceed the background's.
    img = batch["image"][0]
    assert img[20, 20, 0] > img[45, 2, 0]


def test_voc_eval_perfect_detections(voc, tmp_path):
    roidb = voc._load_gt_roidb()
    n = len(roidb)
    all_boxes = [[np.zeros((0, 5), np.float32) for _ in range(n)]
                 for _ in range(voc.num_classes)]
    dog = voc.classes.index("dog")
    cat = voc.classes.index("cat")
    all_boxes[dog][0] = np.asarray([[10, 8, 40, 38, 0.9]], np.float32)
    all_boxes[cat][1] = np.asarray([[5, 5, 30, 30, 0.8]], np.float32)
    result = voc.evaluate_detections(all_boxes)
    assert result["dog"] == pytest.approx(1.0, abs=1e-4)
    assert result["cat"] == pytest.approx(1.0, abs=1e-4)
    # comp4 result files round-trip (reference write_pascal_results).
    voc.write_results(all_boxes, str(tmp_path))
    path = tmp_path / "comp4_det_minitest_dog.txt"
    assert path.exists()
    line = path.read_text().strip().split()
    assert line[0] == "000001" and float(line[2]) == 11.0  # 1-indexed


def test_voc_eval_difficult_not_counted(voc):
    """A detection on the difficult person neither scores nor hurts."""
    roidb = voc._load_gt_roidb()
    n = len(roidb)
    all_boxes = [[np.zeros((0, 5), np.float32) for _ in range(n)]
                 for _ in range(voc.num_classes)]
    person = voc.classes.index("person")
    all_boxes[person][0] = np.asarray([[45, 5, 58, 42, 0.95]], np.float32)
    result = voc.evaluate_detections(all_boxes)
    # No non-difficult person gt anywhere: AP must be 0 (not negative /
    # crash), and the det must have been IGNORED rather than counted FP.
    assert result["person"] == 0.0


# ---------------------------------------------------------------------------
# COCO
# ---------------------------------------------------------------------------


@pytest.fixture
def coco():
    return COCODataset("minival", root_path=FIXTURES,
                       dataset_path=COCO_ROOT)


def test_coco_roidb(coco):
    roidb = coco._load_gt_roidb()
    assert coco.classes == ("__background__", "car", "dog")
    assert len(roidb) == 2
    e1, e2 = roidb
    # Crowd annotation excluded from training boxes.
    assert e1["boxes"].shape == (1, 4)
    np.testing.assert_allclose(e1["boxes"][0], [10, 10, 40, 40])
    assert e1["gt_classes"][0] == 1  # car → contiguous id 1 (cat id 3)
    # Out-of-bounds bbox is clipped into the image.
    assert e2["boxes"].shape == (2, 4)
    np.testing.assert_allclose(e2["boxes"][1], [0, 0, 6, 5])
    # Polygon segmentations ride along for the mask pipeline.
    assert e1["segmentations"][0] is not None
    assert len(e1["segmentations"]) == 1


def test_coco_loader_with_masks(coco):
    cfg = generate_config("resnet50_fpn_mask", "coco", **{
        "image.pad_shape": (64, 64), "image.scales": ((48, 64),),
        "train.max_gt_boxes": 4, "train.flip": False,
        "train.mask_gt_resolution": 28,
    })
    roidb = coco._load_gt_roidb()
    loader = AnchorLoader(roidb, cfg, num_shards=1, shuffle=False, seed=0)
    batches = list(loader)
    assert len(batches) == 2
    b = batches[0]
    assert b["gt_masks"].shape == (1, 4, 28, 28)
    # The car's polygon fills its whole box → its box-frame mask is ~all on.
    assert b["gt_masks"][0, 0].mean() > 0.9
    # Padding gt slots carry empty masks.
    assert b["gt_masks"][0, 3].sum() == 0


def test_coco_eval_perfect_detections(coco, tmp_path):
    roidb = coco._load_gt_roidb()
    n = len(roidb)
    all_boxes = [[np.zeros((0, 5), np.float32) for _ in range(n)]
                 for _ in range(coco.num_classes)]
    # Perfect detections for all three non-crowd gts. Note the third matches
    # the ORIGINAL (unclipped) annotation bbox — COCO eval compares against
    # the json annotations, not the training-clipped roidb boxes.
    all_boxes[1][0] = np.asarray([[10, 10, 40, 40, 0.9]], np.float32)
    all_boxes[2][1] = np.asarray([[5, 20, 30, 55, 0.8]], np.float32)
    all_boxes[1][1] = np.asarray([[-3, -2, 6, 5, 0.7]], np.float32)
    out_json = str(tmp_path / "dets.json")
    stats = coco.evaluate_detections(all_boxes, out_json=out_json)
    assert stats["AP"] == pytest.approx(1.0, abs=1e-3), stats
    assert os.path.exists(out_json)


def test_coco_eval_false_positive_lowers_ap(coco):
    roidb = coco._load_gt_roidb()
    n = len(roidb)
    all_boxes = [[np.zeros((0, 5), np.float32) for _ in range(n)]
                 for _ in range(coco.num_classes)]
    all_boxes[1][0] = np.asarray(
        [[10, 10, 40, 40, 0.9],
         [50, 2, 62, 12, 0.95]],  # confident FP in open space
        np.float32)
    all_boxes[2][1] = np.asarray([[5, 20, 30, 55, 0.8]], np.float32)
    all_boxes[1][1] = np.asarray([[0, 0, 6, 5, 0.7]], np.float32)
    stats = coco.evaluate_detections(all_boxes)
    assert stats["AP"] < 1.0


def test_coco_crowd_region_detection_ignored(coco):
    """A detection inside the crowd-RLE region must be IGNORED (matched to
    the crowd gt), not counted as a false positive — the maskApi crowd-IoU
    semantics flowing through eval."""
    roidb = coco._load_gt_roidb()
    n = len(roidb)

    def boxes_with_crowd_hit():
        all_boxes = [[np.zeros((0, 5), np.float32) for _ in range(n)]
                     for _ in range(coco.num_classes)]
        all_boxes[1][0] = np.asarray([[10, 10, 40, 40, 0.9]], np.float32)
        all_boxes[2][1] = np.asarray([[5, 20, 30, 55, 0.8]], np.float32)
        all_boxes[1][1] = np.asarray([[-3, -2, 6, 5, 0.7]], np.float32)
        # dog detection fully inside the crowd block (0,30)-(19,41) @img1
        all_boxes[2][0] = np.asarray([[2, 31, 17, 40, 0.85]], np.float32)
        return all_boxes

    stats = coco.evaluate_detections(boxes_with_crowd_hit())
    assert stats["AP"] == pytest.approx(1.0, abs=1e-3), stats


def test_coco_segm_eval_perfect_masks(coco, tmp_path):
    """evaluate_segmentations with pixel-perfect masks -> segm AP == 1."""
    from mx_rcnn_tpu import masks as M

    roidb = coco._load_gt_roidb()
    n = len(roidb)
    all_boxes = [[np.zeros((0, 5), np.float32) for _ in range(n)]
                 for _ in range(coco.num_classes)]
    all_masks = [[[] for _ in range(n)] for _ in range(coco.num_classes)]

    def full_mask(poly, h, w):
        return M.fr_poly(poly, h, w)

    # img1 car: polygon rectangle (10,10)-(41,41) @ 48x64
    all_boxes[1][0] = np.asarray([[10, 10, 40, 40, 0.9]], np.float32)
    all_masks[1][0] = [full_mask(
        [[10.0, 10.0, 41.0, 10.0, 41.0, 41.0, 10.0, 41.0]], 48, 64)]
    # img2 dog: rectangle (5,20)-(31,56) @ 64x48
    all_boxes[2][1] = np.asarray([[5, 20, 30, 55, 0.8]], np.float32)
    all_masks[2][1] = [full_mask(
        [[5.0, 20.0, 31.0, 20.0, 31.0, 56.0, 5.0, 56.0]], 64, 48)]
    # img2 car: clipped corner box
    all_boxes[1][1] = np.asarray([[-3, -2, 6, 5, 0.7]], np.float32)
    all_masks[1][1] = [full_mask(
        [[0.0, 0.0, 6.0, 0.0, 6.0, 5.0, 0.0, 5.0]], 64, 48)]

    out_json = str(tmp_path / "segm.json")
    stats = coco.evaluate_segmentations(all_boxes, all_masks,
                                        out_json=out_json)
    assert stats["segm_AP"] == pytest.approx(1.0, abs=1e-3), stats
    assert stats["AP"] > 0.7  # bbox side still evaluated
    assert os.path.exists(out_json)
    # The written json is valid COCO segm results.
    import json as _json
    with open(out_json) as f:
        res = _json.load(f)
    assert all("segmentation" in r and "counts" in r["segmentation"]
               for r in res)


def test_coco_segm_eval_wrong_masks_score_low(coco):
    """Right boxes, wrong masks: bbox AP stays high, segm AP collapses."""
    from mx_rcnn_tpu import masks as M

    roidb = coco._load_gt_roidb()
    n = len(roidb)
    all_boxes = [[np.zeros((0, 5), np.float32) for _ in range(n)]
                 for _ in range(coco.num_classes)]
    all_masks = [[[] for _ in range(n)] for _ in range(coco.num_classes)]
    # Perfect boxes but masks covering only a sliver of each gt.
    sliver1 = np.zeros((48, 64), np.uint8); sliver1[10:12, 10:12] = 1
    sliver2 = np.zeros((64, 48), np.uint8); sliver2[20:22, 5:7] = 1
    sliver3 = np.zeros((64, 48), np.uint8); sliver3[0:1, 0:1] = 1
    all_boxes[1][0] = np.asarray([[10, 10, 40, 40, 0.9]], np.float32)
    all_masks[1][0] = [M.encode(sliver1)]
    all_boxes[2][1] = np.asarray([[5, 20, 30, 55, 0.8]], np.float32)
    all_masks[2][1] = [M.encode(sliver2)]
    all_boxes[1][1] = np.asarray([[-3, -2, 6, 5, 0.7]], np.float32)
    all_masks[1][1] = [M.encode(sliver3)]
    stats = coco.evaluate_segmentations(all_boxes, all_masks)
    assert stats["segm_AP"] < 0.2, stats
    assert stats["AP"] > 0.7


def test_gen_synthetic_coco_roundtrip(tmp_path):
    """tools/gen_synthetic_coco writes the documented COCO layout and the
    real COCODataset parses it (the r5 launch-rehearsal data path)."""
    pytest.importorskip("cv2")
    from mx_rcnn_tpu.tools.gen_synthetic_coco import generate_split

    root = str(tmp_path / "coco")
    info = generate_split(root, "val2017", num_images=4, seed=11)
    assert info["images"] == 4 and info["annotations"] >= 4
    ds = COCODataset("val2017", root_path=str(tmp_path), dataset_path=root)
    roidb = ds.gt_roidb()
    assert len(roidb) == 4
    assert ds.num_classes == 81  # full COCO category list declared
    for e in roidb:
        assert os.path.exists(e["image"])
        assert e["boxes"].shape[0] == e["gt_classes"].shape[0] >= 1
        assert (e["gt_classes"] >= 1).all() and (e["gt_classes"] <= 16).all()
    # Validate the RAW json (COCODataset clips boxes at parse time, so
    # roidb bounds checks would be tautological): every xywh bbox must
    # already lie within its image.
    import json as _json

    raw = _json.load(open(info["json"]))
    dims = {im["id"]: (im["width"], im["height"]) for im in raw["images"]}
    for ann in raw["annotations"]:
        w, h = dims[ann["image_id"]]
        x, y, bw, bh = ann["bbox"]
        assert 0 <= x and 0 <= y and x + bw <= w and y + bh <= h, ann


def test_gt_roidb_cache_distinguishes_dataset_paths(tmp_path):
    """Two COCO datasets sharing a split name at DIFFERENT paths must not
    reuse each other's roidb cache (r5 rehearsal bug: a small-copy set
    silently loaded the full set's pickle)."""
    pytest.importorskip("cv2")
    from mx_rcnn_tpu.tools.gen_synthetic_coco import generate_split

    a = str(tmp_path / "a"); b = str(tmp_path / "b")
    generate_split(a, "val2017", num_images=3, seed=1)
    generate_split(b, "val2017", num_images=5, seed=2)
    root = str(tmp_path)  # shared root_path -> shared cache dir
    ds_a = COCODataset("val2017", root_path=root, dataset_path=a)
    ds_b = COCODataset("val2017", root_path=root, dataset_path=b)
    assert len(ds_a.gt_roidb()) == 3
    assert len(ds_b.gt_roidb()) == 5  # not the cached 3-entry roidb
    assert len(ds_a.gt_roidb()) == 3  # both caches coexist


# ---------------------------------------------------------------------------
# loader shutdown (data/loader.py close/context-manager contract)
# ---------------------------------------------------------------------------


def _worker_threads():
    import threading

    return [t for t in threading.enumerate()
            if t.name.startswith("loader-worker") and t.is_alive()]


def _synthetic_loader(n=6):
    from mx_rcnn_tpu.data.datasets.synthetic import SyntheticDataset

    cfg = generate_config("resnet50", "synthetic", **{
        "image.pad_shape": (64, 64), "image.scales": ((64, 64),),
        "train.batch_images": 1, "train.flip": False,
        "train.max_gt_boxes": 4})
    ds = SyntheticDataset("train", num_images=n, image_size=64,
                          max_objects=1, min_size_frac=3, max_size_frac=2)
    return AnchorLoader(ds.gt_roidb(), cfg, num_shards=1, seed=0)


def test_loader_close_joins_workers():
    """close() stops AND joins the prefetch pool: no loader worker thread
    survives, even when the epoch was abandoned mid-stream."""
    loader = _synthetic_loader()
    it = iter(loader)
    next(it)
    assert _worker_threads(), "prefetch pool never started"
    loader.close()
    assert not _worker_threads(), "worker threads survived close()"
    # close() is idempotent and the loader is reusable for a fresh epoch
    loader.close()
    assert sum(1 for _ in loader) == 6
    assert not _worker_threads()


def test_loader_iterator_disposal_joins_workers():
    """Disposing the epoch generator (the for-loop breaking out, or GC)
    runs the generator's finally — which closes AND joins the pool."""
    import gc

    loader = _synthetic_loader()
    it = iter(loader)
    next(it)
    del it
    gc.collect()
    assert not _worker_threads(), "worker threads survived disposal"


def test_loader_close_joins_overlapping_iterations():
    """Two live iterations over the same loader each own a pool; close()
    must join BOTH (a single-slot tracker would orphan the first)."""
    loader = _synthetic_loader()
    it1 = iter(loader)
    next(it1)
    it2 = iter(loader)
    next(it2)
    loader.close()
    assert not _worker_threads(), "a pool survived close()"


def test_loader_context_manager():
    with _synthetic_loader() as loader:
        for i, batch in enumerate(loader):
            assert np.isfinite(batch["image"]).all()
            if i == 1:
                break  # abandon mid-epoch; __exit__ must clean up
    assert not _worker_threads()


# ---------------------------------------------------------------------------
# batch assembly into the loader's reused buffers (data/loader.py::_BufferPool)
# ---------------------------------------------------------------------------


def _pool_cfg(**over):
    base = {
        "image.scales": ((40, 64),), "image.pad_shape": (48, 64),
        "train.batch_images": 1, "train.flip": False,
        "train.max_gt_boxes": 4}
    base.update(over)
    return generate_config("resnet50", "synthetic", **base)


def _pixel_roidb(n, seed=0):
    """``image_data`` records, each with its own pixels; every fifth one
    upright, so that some batches take the square cover."""
    rs = np.random.RandomState(seed)
    roidb = []
    for i in range(n):
        h, w = (30 + i % 7, 44 + i % 11)
        if i % 5 == 4:
            h, w = w, h
        roidb.append({
            "image_data": (rs.rand(h, w, 3) * 255).astype(np.uint8),
            "height": h, "width": w,
            "boxes": np.asarray([[2 + i % 9, 3, 20 + i % 9, 25]], np.float32),
            "gt_classes": np.asarray([1 + i % 3], np.int32),
            "flipped": False})
    return roidb


def _plain_batch(entries, cfg):
    """The batch assembled the plain way: every image loaded into an array
    of its own (no ``out``), then ``np.stack``."""
    from mx_rcnn_tpu.data.loader import (_load_roidb_entry, _pad_gt,
                                         resolve_pad_bucket)

    pad = resolve_pad_bucket(cfg, 0, [e["width"] >= e["height"]
                                      for e in entries])
    loaded = [_load_roidb_entry(e, cfg, 0, pad) for e in entries]
    gt = [_pad_gt(b, c, cfg.train.max_gt_boxes) for _, _, b, c in loaded]
    return {"image": np.stack([l[0] for l in loaded]),
            "im_info": np.stack([l[1] for l in loaded]),
            "gt_boxes": np.stack([g[0] for g in gt]),
            "gt_classes": np.stack([g[1] for g in gt]),
            "gt_valid": np.stack([g[2] for g in gt])}


def _assert_batch_equal(got, want):
    assert sorted(got) == sorted(want)
    for k in want:
        assert got[k].dtype == want[k].dtype and got[k].shape == want[k].shape
        assert got[k].tobytes() == want[k].tobytes(), k


@pytest.mark.parametrize("flipped", [False, True], ids=["plain", "flipped"])
@pytest.mark.parametrize("source", ["packed", "image_data"])
@pytest.mark.parametrize("num_shards", [1, 4])
def test_loader_batches_equal_plain_assembly(tmp_path, num_shards, source,
                                             flipped):
    """Every batch of an epoch, written in place into a reused buffer, is
    bit for bit the batch of per-image arrays and np.stack. Nothing is
    kept, so the buffers go round several times in the epoch."""
    from mx_rcnn_tpu.data.datasets.imdb import append_flipped_roidb
    from mx_rcnn_tpu.data.packed import (load_packed_roidb,
                                         write_packed_dataset)

    cfg = _pool_cfg()
    roidb = _pixel_roidb(48)
    if source == "packed":
        write_packed_dataset(roidb, cfg, str(tmp_path / "pack"),
                             shard_images=16)
        roidb = load_packed_roidb(str(tmp_path / "pack"), cfg)
    if flipped:
        roidb = append_flipped_roidb(roidb, name="test")
    loader = AnchorLoader(roidb, cfg, num_shards=num_shards, shuffle=False)
    b = loader.batch_size
    n = 0
    for k, batch in enumerate(loader):
        _assert_batch_equal(batch, _plain_batch(roidb[k * b:(k + 1) * b], cfg))
        n += 1
    assert n == len(roidb) // b and n > loader._pool.allocated


def _constant_roidb(n):
    """Record i is all pixels i + 1 and exactly fills the pad shape, so a
    batch's image reads ``(i + 1 - mean) / std`` everywhere."""
    return [{"image_data": np.full((48, 64, 3), i + 1, np.uint8),
             "height": 48, "width": 64,
             "boxes": np.asarray([[1, 1, 20, 20]], np.float32),
             "gt_classes": np.asarray([1], np.int32), "flipped": False}
            for i in range(n)]


def _constant_loader(n, **kw):
    cfg = _pool_cfg(**{"image.scales": ((48, 64),)})
    return AnchorLoader(_constant_roidb(n), cfg, shuffle=False, **kw)


def _expected_constant(loader, i):
    img = np.empty((48, 64, 3), np.float32)
    img[:] = ((np.float32(i + 1)
               - np.asarray(loader.cfg.image.pixel_means, np.float32))
              / np.asarray(loader.cfg.image.pixel_stds, np.float32))
    return img


def test_pool_leaves_kept_batches_alone():
    """(a) A consumer that keeps the first three image arrays by reference,
    as benchmarks/window.py does, finds them unchanged twenty batches on."""
    loader = _constant_loader(40)
    kept, copies = [], []
    for k, batch in enumerate(loader):
        if k < 3:
            kept.append(batch["image"])
            copies.append(batch["image"].copy())
        if k == 23:
            break
    loader.close()
    for k in range(3):
        np.testing.assert_array_equal(kept[k], copies[k])
        np.testing.assert_allclose(kept[k][0], _expected_constant(loader, k),
                                   rtol=1e-6)


def test_pool_leaves_a_placed_batch_alone():
    """(b) An array placed with jax.device_put from a batch holds that
    batch's values after twenty more batches: the device array (or the
    transfer) references the host buffer, so the pool does not reuse it."""
    import jax

    loader = _constant_loader(40)
    placed = copy = None
    for k, batch in enumerate(loader):
        if k == 1:
            copy = batch["image"].copy()
            placed = jax.device_put(batch["image"])
        if k == 22:
            break
    loader.close()
    np.testing.assert_array_equal(np.asarray(placed), copy)


def test_pool_stays_small_when_nothing_is_kept():
    """(c) Over 200 batches with nothing kept, the pool never allocates
    more than prefetch_depth + workers + 2 buffers: it reuses."""
    loader = _constant_loader(200, prefetch_depth=4, workers=2)
    n = 0
    for k, batch in enumerate(loader):
        assert batch["image"][0, 0, 0, 0] == _expected_constant(
            loader, k)[0, 0, 0]
        n += 1
    assert n == 200
    assert 1 <= loader._pool.allocated <= 4 + 2 + 2


def test_pool_loader_close_joins_workers():
    """(d) close() still joins every worker mid-epoch; a batch handed out
    before it stays intact, and the next epoch reuses the same buffers."""
    loader = _constant_loader(60)
    it = iter(loader)
    first = next(it)["image"]
    copy = first.copy()
    next(it)
    assert _worker_threads(), "prefetch pool never started"
    loader.close()
    assert not _worker_threads(), "worker threads survived close()"
    assert sum(1 for _ in loader) == 60
    assert not _worker_threads()
    np.testing.assert_array_equal(first, copy)
    assert loader._pool.allocated <= 4 + 2 + 2 + 1   # `first` is still held


def test_pool_under_many_workers_and_fast_switching():
    """More workers than cores and a short switch interval: every batch
    still carries its own record's pixels in every row, whether or not the
    consumer keeps some of them (a buffer handed to two workers at once, or
    reused while kept, would mix records)."""
    import sys
    import time

    n, workers = 240, 2 * (os.cpu_count() or 4)
    cfg = _pool_cfg(**{"image.scales": ((48, 64),), "train.batch_images": 2})
    loader = AnchorLoader(_constant_roidb(n), cfg, shuffle=False,
                          workers=workers, prefetch_depth=workers)
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    t0 = time.monotonic()
    kept = {}
    try:
        for k, batch in enumerate(loader):
            for j in range(2):
                want = _expected_constant(loader, 2 * k + j)
                assert (batch["image"][j] == want).all(), (k, j)
            if k % 7 == 0:
                kept[k] = batch["image"]
            assert time.monotonic() - t0 < 120
    finally:
        sys.setswitchinterval(old)
        loader.close()
    assert k == n // 2 - 1
    for k, image in kept.items():
        assert (image[1] == _expected_constant(loader, 2 * k + 1)).all(), k


def test_substitute_of_other_orientation_is_clamped_into_its_row():
    """A quarantine substitute discovered mid-batch can be upright in a
    lying batch: it is loaded against the square cover and its content cut
    into the row of the batch buffer, the rest of the row zero."""
    from mx_rcnn_tpu.data.loader import _load_roidb_entry

    cfg = _pool_cfg()
    roidb = _pixel_roidb(10)
    upright = next(i for i, e in enumerate(roidb)
                   if e["height"] > e["width"])
    lying = [i for i, e in enumerate(roidb) if e["width"] >= e["height"]][:2]

    class Substituting:
        chaos_spec = None

        def resolve(self, i):
            return i

        def load(self, load_one, i, cancel=None):
            k = upright if i == lying[1] else i
            return load_one(k), k

    loader = AnchorLoader(roidb, cfg, num_shards=2, shuffle=False,
                          guard=Substituting())
    for _ in range(3):   # the same rows again: stale pixels must not show
        batch = loader._make_batch((lying, 0))
        assert batch["image"].shape == (2, 48, 64, 3)
        want0 = _load_roidb_entry(roidb[lying[0]], cfg, 0, (48, 64))[0]
        square, info = _load_roidb_entry(roidb[upright], cfg, 0,
                                         (64, 64))[:2]
        np.testing.assert_array_equal(batch["image"][0], want0)
        np.testing.assert_array_equal(batch["image"][1], square[:48, :64])
        np.testing.assert_array_equal(batch["im_info"][1], info)
        del batch


def test_loader_without_native_layer_takes_the_same_road(monkeypatch):
    """With no toolchain the numpy chain fills the same rows: batches
    equal the plain assembly (which then runs the numpy chain too)."""
    from mx_rcnn_tpu.data import _native_img

    monkeypatch.setattr(_native_img, "get_lib", lambda: None)
    cfg = _pool_cfg()
    roidb = _pixel_roidb(24)
    loader = AnchorLoader(roidb, cfg, num_shards=2, shuffle=False)
    for k, batch in enumerate(loader):
        _assert_batch_equal(batch, _plain_batch(roidb[2 * k:2 * k + 2], cfg))
    assert k == 11 and loader._pool.allocated < 12
