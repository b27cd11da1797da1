"""What steers the mask cell to a tiny size on the CPU in these tests: the
pyramid cell's steering (R-50, a 128x192 canvas, 2 images, 64 candidates a
level) with 64 rois an image, so that the branch's foreground block is 16
slots. The branch's own sizes (14x14 bins, four 256-wide convolutions, 28x28
maps, 81 classes) are the published ones. The test steers the run, no option
of the command does."""

from bm_tiny_fpn import tiny_fpn


def tiny_mask():
    t = tiny_fpn()
    t["overrides"]["train.batch_rois"] = 64
    t["spec_overrides"]["train"] = dict(t["spec_overrides"]["train"],
                                        batch_rois=64)
    return t
