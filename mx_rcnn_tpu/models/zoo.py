"""Model zoo dispatch — config → (model, init, forwards).

The reference selects its graph builders by name
(train_end2end.py: ``eval('get_' + args.network + '_train')`` over
rcnn/symbol/symbol_vgg.py / symbol_resnet.py). Here the config's
``network.use_fpn`` flag routes between the two model families:

- classic C4 Faster R-CNN (models/faster_rcnn.py): VGG16 / ResNet-50/101
  stride-16 single-level models — the reference's actual graphs;
- FPN Faster/Mask R-CNN (models/fpn.py): BASELINE.json configs 3-4.

Every consumer (trainer, Predictor, bench, CLI) goes through these
functions so the two families stay drop-in interchangeable: the functional
forwards share their input/output contracts.
"""

from __future__ import annotations

import jax

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models import faster_rcnn as _c4
from mx_rcnn_tpu.models import fpn as _fpn


def _is_pyramid_model(model) -> bool:
    """FPN and ViTDet share the pyramid method surface and the fpn.py
    functional forwards (duck-typed via string method names)."""
    from mx_rcnn_tpu.models import vit as _vit

    return isinstance(model, (_fpn.FPNFasterRCNN, _vit.ViTDet))


def build_model(cfg: Config, mesh=None):
    """Config → model. For ViTDet configs, `mesh` + an SP request turn on
    sequence-parallel global attention over the mesh's model axis: either
    network.use_ring_attention=True (ring by default) or
    network.sp_mode="ulysses" (all-to-all) alone enables it."""
    if cfg.network.sp_mode not in ("ring", "ulysses"):
        raise ValueError(
            f"network.sp_mode must be 'ring' or 'ulysses', got "
            f"{cfg.network.sp_mode!r}")
    if cfg.network.attn_impl not in ("dense", "streaming"):
        raise ValueError(
            f"network.attn_impl must be 'dense' or 'streaming', got "
            f"{cfg.network.attn_impl!r}")
    # SP is requested by use_ring_attention=True (legacy knob, ring by
    # default) or by naming a non-default sp_mode outright; only the ViT
    # global-attention blocks have a sequence to shard.
    wants_sp = (cfg.network.use_ring_attention
                or cfg.network.sp_mode != "ring")
    if wants_sp and not cfg.network.use_vit:
        from mx_rcnn_tpu.logger import logger

        logger.warning(
            "sequence parallelism (use_ring_attention=%s, sp_mode=%r) has "
            "no effect on %s: only the ViTDet global-attention blocks "
            "have a token sequence to shard",
            cfg.network.use_ring_attention, cfg.network.sp_mode,
            cfg.network.name)
    if cfg.network.pp_stages and not cfg.network.use_vit:
        from mx_rcnn_tpu.logger import logger

        logger.warning(
            "network.pp_stages=%d has no effect on %s: only the ViT "
            "encoder has the homogeneous staged structure to pipeline "
            "(parallel/pipeline.py)",
            cfg.network.pp_stages, cfg.network.name)
    if cfg.network.use_detr:
        from mx_rcnn_tpu.models import detr as _detr

        return _detr.build_detr_model(cfg)
    if cfg.network.use_vit:
        from functools import partial

        from mx_rcnn_tpu.models import vit as _vit
        from mx_rcnn_tpu.ops.ring_attention import (
            ring_attention, ulysses_attention)

        attn_fn = None
        if wants_sp and mesh is not None:
            if "model" not in mesh.axis_names:
                # attn_fn shards over axis='model'; without it the failure
                # would surface later as an opaque unbound-axis error inside
                # shard_map. Fail at build time with the real cause.
                raise ValueError(
                    f"sequence parallelism (sp_mode="
                    f"{cfg.network.sp_mode!r}) needs a 'model' axis in the "
                    f"mesh; got axes {mesh.axis_names}. Build the mesh as "
                    "'<data>x<model>' (e.g. --tpu-mesh 2x4) or disable SP")
            if (cfg.network.sp_mode == "ulysses"
                    and cfg.network.vit_heads % mesh.shape["model"] != 0):
                # Fail at build time, not at first trace.
                raise ValueError(
                    f"sp_mode='ulysses' needs vit_heads "
                    f"({cfg.network.vit_heads}) divisible by the mesh "
                    f"model axis ({mesh.shape['model']}); use the ring "
                    "formulation for head-indivisible layouts")
            if cfg.network.pp_stages:
                raise ValueError(
                    "network.pp_stages and sequence parallelism both claim "
                    "the mesh 'model' axis; enable only one")
            sp = (ulysses_attention if cfg.network.sp_mode == "ulysses"
                  else ring_attention)
            attn_fn = partial(sp, mesh=mesh, axis="model")
            if cfg.network.attn_impl == "streaming":
                # Mirrors the pp_stages warning below: the knob is
                # accepted but cannot take effect on this build.
                from mx_rcnn_tpu.logger import logger

                logger.warning(
                    "network.attn_impl='streaming' superseded by "
                    "sequence-parallel attention (sp_mode=%r): the SP "
                    "kernels manage their own attention internals "
                    "(numerics unchanged)", cfg.network.sp_mode)
        elif wants_sp:
            # Not an error: SP modes are exact, so a dense build (inference
            # on one chip — no mesh passed) is mathematically identical —
            # but flag it, since the config asked for a parallel layout.
            from mx_rcnn_tpu.logger import logger

            logger.warning(
                "sequence parallelism (use_ring_attention=%s, sp_mode=%r) "
                "ignored: build_model() was called without a mesh; using "
                "dense attention (same numerics, no SP)",
                cfg.network.use_ring_attention, cfg.network.sp_mode)
        if attn_fn is None and cfg.network.attn_impl == "streaming":
            if cfg.network.pp_stages:
                # The staged encoder manages its own attention internals;
                # the knob cannot be routed through pipeline_fn.
                from mx_rcnn_tpu.logger import logger

                logger.warning(
                    "network.attn_impl='streaming' ignored under "
                    "pp_stages=%d (the staged ViT encoder uses its own "
                    "dense attention; numerics unchanged)",
                    cfg.network.pp_stages)
            else:
                # Flash-style streaming softmax for the single-device
                # dense path: O(S·chunk) score memory instead of O(S²).
                # Exact (the kernel SP uses locally); a speed/memory
                # knob, not an approximation (PERF.md r5).
                from mx_rcnn_tpu.ops.ring_attention import (
                    streaming_attention)

                attn_fn = partial(streaming_attention,
                                  kv_chunk=cfg.network.attn_kv_chunk)
        pipeline_fn = None
        if cfg.network.pp_stages and mesh is not None:
            if "model" not in mesh.axis_names or (
                    mesh.shape["model"] != cfg.network.pp_stages):
                raise ValueError(
                    f"network.pp_stages={cfg.network.pp_stages} needs a "
                    f"mesh model axis of that size; got "
                    f"{dict(zip(mesh.axis_names, mesh.devices.shape))}. "
                    "Build the mesh as '<data>x<stages>' "
                    f"(e.g. --tpu-mesh 2x{cfg.network.pp_stages})")
            from mx_rcnn_tpu.parallel.pipeline import pipeline_apply

            def pipeline_fn(stage_fn, stacked, x, _mesh=mesh):
                return pipeline_apply(
                    stage_fn, stacked, x, _mesh, axis="model",
                    microbatches=cfg.network.pp_microbatches or None)
        elif cfg.network.pp_stages:
            from mx_rcnn_tpu.logger import logger

            logger.warning(
                "network.pp_stages=%d: no mesh at build time — running the "
                "staged backbone SEQUENTIALLY (same params and numerics, "
                "no pipelining)", cfg.network.pp_stages)
        return _vit.build_vitdet_model(cfg, global_attn_fn=attn_fn,
                                       pipeline_fn=pipeline_fn)
    if cfg.network.use_fpn:
        return _fpn.build_fpn_model(cfg)
    return _c4.build_model(cfg)


def init_params(model, cfg: Config, rng, image_shape=None):
    from mx_rcnn_tpu.models import detr as _detr
    from mx_rcnn_tpu.models import vit as _vit

    if isinstance(model, _detr.DETR):
        return _detr.init_detr_params(model, cfg, rng, image_shape)
    if isinstance(model, _vit.ViTDet):
        return _vit.init_vitdet_params(model, cfg, rng, image_shape)
    if isinstance(model, _fpn.FPNFasterRCNN):
        return _fpn.init_fpn_params(model, cfg, rng, image_shape)
    return _c4.init_params(model, cfg, rng, image_shape)


def _is_detr(model) -> bool:
    from mx_rcnn_tpu.models import detr as _detr

    return isinstance(model, _detr.DETR)


def forward_train(model, params, batch, rng, cfg: Config):
    if _is_detr(model):
        from mx_rcnn_tpu.models import detr as _detr

        return _detr.forward_train(model, params, batch, rng, cfg)
    if _is_pyramid_model(model):
        return _fpn.forward_train(model, params, batch, rng, cfg)
    return _c4.forward_train(model, params, batch, rng, cfg)


def forward_test(model, params, images, im_info, cfg: Config):
    if _is_detr(model):
        from mx_rcnn_tpu.models import detr as _detr

        return _detr.forward_test(model, params, images, im_info, cfg)
    if _is_pyramid_model(model):
        return _fpn.forward_test(model, params, images, im_info, cfg)
    return _c4.forward_test(model, params, images, im_info, cfg)


def forward_rpn(model, params, images, im_info, cfg: Config):
    if _is_detr(model):
        raise NotImplementedError("DETR has no RPN / proposal path")
    if _is_pyramid_model(model):
        return _fpn.forward_rpn(model, params, images, im_info, cfg)
    return _c4.forward_rpn(model, params, images, im_info, cfg)
