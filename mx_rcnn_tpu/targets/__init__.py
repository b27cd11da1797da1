"""Training-target assignment, traceable and static-shape.

TPU-native replacement for the reference's host-side numpy target builders:
rcnn/io/rpn.py (assign_anchor — run in the AnchorLoader on CPU) and
rcnn/io/rcnn.py + rcnn/symbol/proposal_target.py (sample_rois — run inside
the graph as a Python CustomOp, serializing every training step through the
host). Here both run inside the jitted train step.
"""

from mx_rcnn_tpu.targets.rpn_targets import assign_anchor, assign_anchors
from mx_rcnn_tpu.targets.rcnn_targets import sample_rois
