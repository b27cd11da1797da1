"""Operations and bytes that the configuration REQUIRES, counted from its
shapes: the same number whatever implements a stage.

Training counts the forward pass, the data gradient and the weight gradient
of every convolution and matrix product, each at 2 * MACs. A frozen layer has
no weight gradient; a layer below the recipe's stop-gradient cut (the stem and
stage 1) has neither; the first layers above the cut need no data gradient.
Recomputation is never counted, nor are elementwise ops, the losses or the
update (under 1 % of a step's operations at these widths).
"""

from __future__ import annotations

STAGE_BLOCKS = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def conv_flops(h_out, w_out, kh, kw, cin, cout):
    """Forward operations of one convolution: 2 per multiply-accumulate."""
    return 2 * h_out * w_out * kh * kw * cin * cout


def _out(size, k, stride, pad):
    return (size + 2 * pad - k) // stride + 1


def bottleneck_convs(h, w, cin, width, stride, first):
    """[(flops, at_cut)] of one v1.5 bottleneck on an (h, w, cin) input, and
    its output (h, w, c). ``at_cut`` marks the convolutions that read the
    block's input directly."""
    ho, wo = _out(h, 3, stride, 1), _out(w, 3, stride, 1)
    convs = [(conv_flops(h, w, 1, 1, cin, width), True),
             (conv_flops(ho, wo, 3, 3, width, width), False),
             (conv_flops(ho, wo, 1, 1, width, 4 * width), False)]
    if first:
        convs.append((conv_flops(ho, wo, 1, 1, cin, 4 * width), True))
    return convs, (ho, wo, 4 * width)


def stage_flops(h, w, cin, width, blocks, stride, mode, input_is_cut=False):
    """mode: 'fwd' (1x), 'train' (3x; 2x for convolutions reading a
    stop-gradient input). Returns (flops, (h, w, c))."""
    total = 0
    for b in range(blocks):
        convs, (h2, w2, c2) = bottleneck_convs(
            h, w, cin, width, stride if b == 0 else 1, b == 0)
        for f, reads_input in convs:
            if mode == "fwd":
                total += f
            elif input_is_cut and b == 0 and reads_input:
                total += 2 * f
            else:
                total += 3 * f
        h, w, cin = h2, w2, c2
    return total, (h, w, cin)


def c4_flops(spec: dict, mode: str, rois: int) -> float:
    """Per image. mode 'train': forward + required backward with ``rois``
    sampled rois through the head; 'fwd': the test forward with ``rois``
    proposals through the head."""
    blocks = STAGE_BLOCKS[spec["depth"]]
    h, w = spec["canvas"]
    a = len(spec["anchor_ratios"]) * len(spec["anchor_scales"])
    c = spec["num_classes"]
    k = 3 if mode == "train" else 1
    ho, wo = _out(h, 7, 2, 3), _out(w, 7, 2, 3)
    total = conv_flops(ho, wo, 7, 7, 3, 64)                    # stem: frozen
    ho, wo = _out(ho, 3, 2, 1), _out(wo, 3, 2, 1)              # max pool
    f, (ho, wo, ch) = stage_flops(ho, wo, 64, 64, blocks[0], 1, "fwd")
    total += f                                                 # stage 1: frozen
    f, (ho, wo, ch) = stage_flops(ho, wo, ch, 128, blocks[1], 2, mode,
                                  input_is_cut=True)
    total += f
    f, (ho, wo, ch) = stage_flops(ho, wo, ch, 256, blocks[2], 2, mode)
    total += f
    rc = spec["rpn_channels"]
    total += k * (conv_flops(ho, wo, 3, 3, ch, rc)
                  + conv_flops(ho, wo, 1, 1, rc, 2 * a)
                  + conv_flops(ho, wo, 1, 1, rc, 4 * a))
    p = spec["roi_pool_size"]
    # ROIAlign: 4 taps x (multiply + add) per sample point, 2x2 points a bin;
    # the backward scatters the same taps
    total += (2 if mode == "train" else 1) * rois * p * p * 4 * 4 * 2 * ch
    f, (hh, wh, chh) = stage_flops(p, p, ch, 512, blocks[3], 2, mode)
    total += rois * f
    total += k * rois * 2 * chh * (c + 4 * c)
    return float(total)


def nms_work(boxes_in: int, boxes_kept: int) -> dict:
    """Greedy NMS as the configuration asks for it: every kept box is held
    against every box after it (one IoU: 4 min/max, 2 subtract+1, 2 clamp,
    1 multiply for the intersection, 3 for the union, 1 divide, 1 compare =
    16 operations). Bytes: the boxes and scores read once, the kept indices
    written once."""
    return {"flops": float(boxes_kept) * boxes_in * 16,
            "bytes": float(boxes_in) * (4 + 1) * 4 + boxes_kept * 4}


def roofline_seconds(work: dict, peak: dict) -> tuple:
    """(least seconds, which bound)."""
    t_f = work["flops"] / peak["bf16_flops"]
    t_b = work["bytes"] / peak["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
