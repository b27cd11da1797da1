"""Operations the forward and backward passes REQUIRE per image
(benchmarks/flops.py) x images/s/chip of this run, over the chip's bf16
peak."""
from benchmarks import flops, peaks


def read(run):
    spec = run["spec"]
    try:
        peak = peaks.peak(run["device_kind"])
    except KeyError:
        return None  # the CPU rehearsal: no published peak, no share of one
    need = getattr(flops, spec["flops"])(spec, "train",
                                         spec["train"]["batch_rois"])
    return 100.0 * need * run["rate"] / peak["bf16_flops"]
