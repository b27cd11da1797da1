"""Training core.

Reference layer L8 (SURVEY.md §2): rcnn/core/module.py MutableModule,
rcnn/core/metric.py (6 metrics), rcnn/core/callback.py (Speedometer,
do_checkpoint). Here: an optax optimizer with reference hyperparameters, a
pjit-able train step, host-side metric accumulators, orbax checkpoints,
and the graftcast dtype policy (precision.py).

Attribute access is lazy (PEP 562): ``train/precision.py`` must be
importable from model code (models/*.py read the compute-dtype policy),
and an eager ``from .step import ...`` here would close the cycle
models → train → step → models at import time.
"""

from __future__ import annotations

import importlib

_EXPORTS = {
    "build_optimizer": "mx_rcnn_tpu.train.optimizer",
    "trainable_mask": "mx_rcnn_tpu.train.optimizer",
    "TrainState": "mx_rcnn_tpu.train.step",
    "create_train_state": "mx_rcnn_tpu.train.step",
    "make_train_step": "mx_rcnn_tpu.train.step",
    "MetricBag": "mx_rcnn_tpu.train.metrics",
    "Speedometer": "mx_rcnn_tpu.train.callback",
}

__all__ = list(_EXPORTS)


def __getattr__(name: str):
    module = _EXPORTS.get(name)
    if module is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    return getattr(importlib.import_module(module), name)
