"""``benchmarks/flops_roi_align.py`` held to a count by hand at the pyramid
cells' shapes, and the reader ``roi_align_roofline.taps`` to a trace made by
hand: its value on the taps kernels' events, None where no such kernel ran
(a C4 trace, the dense form of a parent), never an exception."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmarks import flops_roi_align, manifest, trace_reduce  # noqa: E402

MS = 1_000_000
FPN = manifest.load_json("configs", "fpn_r101_coco")["spec"]
MASK = manifest.load_json("configs", "mask_r101_fpn_coco")["spec"]
C4 = manifest.load_json("configs", "c4_r101_coco")["spec"]


def _reader():
    return manifest.load_module("layer_metrics", "roi_align_roofline.taps")


def test_the_canvas_is_p2_over_a_shelf_of_p3_p4_p5():
    """832x1344: P2 208x336; P3 104x168, P4 52x84 and P5 26x42 side by
    side below it (168 + 84 + 42 = 294 <= 336): 312x336, as the program's
    ``bf16[8,312,336,256]``."""
    assert flops_roi_align.canvas(FPN) == (312, 336)
    assert flops_roi_align.canvas(MASK) == (312, 336)


@pytest.mark.parametrize("spec,want", [
    (FPN, [(7, 2, 512)]),
    (MASK, [(7, 2, 512), (14, 2, 128)]),
], ids=["fpn_r101_coco", "mask_r101_fpn_coco"])
def test_the_poolings_a_step(spec, want):
    """The box head's 7x7 pooling of the 512 sampled rois an image, and the
    mask branch's 14x14 pooling of round(0.25 x 512) = 128 slots."""
    assert flops_roi_align.poolings(spec) == want


def test_the_hand_count_at_the_pyramid_cell():
    """A roi: 49 bins x 4 samples x 4 taps x 256 channels = 200,704
    multiply-adds, 2 x that forward and again backward; 8 x 512 rois. The
    canvas 312 x 336 x 256 bfloat16 = 53,673,984 bytes an image, the pooled
    values 512 x 49 x 256 x 2 = 12,845,056: each once forward and once
    backward. Bound by the bytes: 1.0643 GB over 819 GB/s = 1.30 ms."""
    work = flops_roi_align.taps_work(FPN)
    assert work["flops"] == 4 * 200_704 * 512 * 8 == 3_288_334_336
    assert work["bytes"] == 2 * (53_673_984 + 12_845_056) * 8 == 1_064_304_640
    mask = flops_roi_align.taps_work(MASK)
    # the mask pooling: 196 bins x 16 taps x 256 x 128 slots, the same bytes
    # of canvas and 128 x 196 x 256 x 2 = 12,845,056 of pooled values
    assert mask["flops"] == work["flops"] + 4 * 196 * 16 * 256 * 128 * 8
    assert mask["bytes"] == 2 * work["bytes"]


def _run(spec, events, rate=40.0):
    dev = {"/device:TPU:0": events}
    host = [("bench.traced", 0, 200 * MS)]
    modules = {"/device:TPU:0": [("jit_step(1)", 0, 100 * MS),
                                 ("jit_step(1)", 100 * MS, 100 * MS)]}
    summary = trace_reduce.reduce_events(dev, host, modules=modules)
    return {"trace": summary, "work": "held in memory", "spec": spec,
            "device_kind": "TPU v5 lite", "rate": rate}


def _kernels(fwd_ms, bwd_ms, at=0):
    """One step's launches of the two kernels, named as the program names
    them (mx_rcnn_tpu/ops/roi_align_pallas.py)."""
    return [
        ('%roi_align_taps.1 = bf16[8,512,7,7,256]{4,3,2,1,0} custom-call(%a, '
         '%b), custom_call_target="tpu_custom_call"', at, int(fwd_ms * MS)),
        ('%roi_align_taps_grad.1 = bf16[8,104832,256]{2,1,0} custom-call(%a, '
         '%b), custom_call_target="tpu_custom_call"', at + 20 * MS,
         int(bwd_ms * MS)),
        ("%fusion.3 = bf16[8,312,336,256] x", at + 40 * MS, 50 * MS),
    ]


@pytest.mark.parametrize("spec", [FPN, MASK],
                         ids=["fpn_r101_train", "mask_r101_train"])
def test_the_share_reads_the_two_kernels_over_two_steps(spec):
    """Two steps, each 2.5 ms forward and 7.5 ms backward of the box
    pooling: 20 ms spent for 2 x 1.30 ms of least time, 13.0 %; the other
    fusion is not counted. In the mask cell the branch's 14x14 pooling is
    dense at this canvas (no launch of its shape), so it is not counted
    either."""
    run = _run(spec, _kernels(2.5, 7.5) + _kernels(2.5, 7.5, at=100 * MS))
    assert run["trace"]["step_runs"] == 2
    least = 1_064_304_640 / 819e9
    got = _reader().read(run)
    assert got == pytest.approx(100 * 2 * least / 20e-3)
    assert 0 < got < 100


def test_a_mask_pooling_by_taps_counts_too():
    """Where the branch's pooling ran by taps as well (a forward launch of
    its pooled shape, (8, 128, 14, 14, 256)), its least work joins."""
    def mask_launch(at):
        return [('%roi_align_taps.2 = bf16[8,128,14,14,256]{4,3,2,1,0} '
                 'custom-call(%a), custom_call_target="tpu_custom_call"',
                 at + 95 * MS, 5 * MS)]

    run = _run(MASK, _kernels(2.5, 7.5) + mask_launch(0)
               + _kernels(2.5, 7.5, at=100 * MS) + mask_launch(100 * MS))
    least = 2 * 1_064_304_640 / 819e9   # a step: both poolings' bytes
    assert _reader().read(run) == pytest.approx(100 * 2 * least / 30e-3)


@pytest.mark.parametrize("case", ["c4_trace", "no_trace", "dense_form"])
def test_silent_where_no_taps_kernel_ran(case):
    """A C4 run (no levels in its configuration), the CPU rehearsal (no
    trace, no published peak), a pyramid run of the dense form (the
    contractions, no kernel of this name): None, and no exception."""
    read = _reader().read
    if case == "no_trace":
        assert read({"trace": None, "spec": FPN, "device_kind": "cpu",
                     "rate": 1.0, "memory_peak_bytes": 0}) is None
    elif case == "c4_trace":
        assert read(_run(C4, _kernels(2.5, 7.5))) is None
        assert read(_run(C4, [("%fusion.1 = f32[8] x", 0, 20 * MS)])) is None
    else:
        dense = [("%convolution_convert_fusion.1 = bf16[8,512,7,336,256] x",
                  0, 13 * MS), ("%fusion.83 = bf16[8,312,336,256] x",
                                20 * MS, 10 * MS)]
        assert read(_run(FPN, dense)) is None


def the_taps_metric_holds(bm):
    """Listed for the two cells whose box pooling runs by taps, as the
    kernels' roofline share of the model stages' layer."""
    m, = [m for m in bm["per_layer"] if m["name"] == "roi_align_roofline.taps"]
    assert {"fpn_r101_train", "mask_r101_train"} <= set(m["workloads"])
    assert not {"c4_r101_train", "c4_r101_train_dp4"} & set(m["workloads"])
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "%", "higher", "device_trace", "model stages", "train_img_per_s_chip")


def test_the_manifest_lists_it_for_the_pyramid_cells():
    the_taps_metric_holds(manifest.load())


def test_the_reader_names_the_programs_kernels():
    """The reader matches the two kernels by the names the program gives
    them."""
    from mx_rcnn_tpu.ops import roi_align_pallas

    assert _reader().KERNELS == (roi_align_pallas.KERNEL_NAME,
                                 roi_align_pallas.KERNEL_NAME_GRAD)
