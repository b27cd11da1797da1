"""ViTDet — plain ViT backbone + simple feature pyramid detector.

BASELINE.json config 5 (the stretch config; the reference repo predates
transformers entirely — SURVEY.md §3.2). Follows Li et al., "Exploring
Plain Vision Transformer Backbones for Object Detection" (ViTDet), and the
recipe of Detectron2's ``projects/ViTDet`` (``mask_rcnn_vitdet_b_100ep``):

- non-hierarchical ViT encoder at stride 16 (patch 16), windowed attention
  (14x14 windows, the grid padded to whole windows) in most blocks with a
  few global-attention blocks spread evenly (ViT-B: 2, 5, 8, 11); every
  block adds the DECOMPOSED relative-position bias of Detectron2's
  ``add_decomposed_rel_pos`` (one table an axis, sized to the window or
  to the whole grid); pre-LN blocks with LayerNorm eps 1e-6, stochastic
  depth rising linearly to ``drop_path``, and no norm after the last
  block;
- a Simple Feature Pyramid built from the LAST feature map only (stride-16
  map → deconv x4 / deconv x2 / identity / maxpool → strides 4/8/16/32),
  each level's 1x1 and 3x3 convs bias-free and LayerNorm'd,
  then the multi-level RPN + box/mask heads of models/fpn.py at the
  recipe's shapes (a two-conv RPN head, the 4conv1fc box head and the mask
  head, LN'd) — the class deliberately mirrors FPNFasterRCNN's method
  surface so fpn.forward_train / forward_test / forward_rpn drive it
  unchanged (models/zoo.py dispatch).

Long-context: the global-attention blocks can run RING ATTENTION
(ops/ring_attention.py) with the token sequence sharded over a mesh axis —
`network.use_ring_attention` + a mesh passed at construction. Window blocks
are always local (windows never cross device shards; each image row-block
is self-contained), so only the few global blocks pay ICI traffic, exactly
the ViTDet compute structure. Every backend (dense, streaming, ring,
Ulysses) adds the decomposed bias to its scores (``rel=``).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from mx_rcnn_tpu.config import Config
from mx_rcnn_tpu.models.fpn import ConvFCHead, MaskHead, RPNHead, TwoFCHead
from mx_rcnn_tpu.obs.profile import stage
from mx_rcnn_tpu.ops.ring_attention import dense_attention
from mx_rcnn_tpu.train.precision import island, model_dtype

Dtype = Any

#: every LayerNorm of the trunk and the pyramid (Detectron2's ViTDet)
LN_EPS = 1e-6


def _layer_norm(dtype, name: str) -> nn.LayerNorm:
    return nn.LayerNorm(epsilon=LN_EPS, dtype=dtype,
                        param_dtype=jnp.float32, name=name)


def rel_pos_table(table: jnp.ndarray, size: int) -> jnp.ndarray:
    """(2·L - 1, D) table -> (size, size, D): entry (i, j) is the table's
    row for the offset i - j (Detectron2's ``get_rel_pos`` with query and
    key of one size). A table of another length is resized linearly to
    2·size - 1 rows first."""
    rows = 2 * size - 1
    if table.shape[0] != rows:
        table = jax.image.resize(table, (rows, table.shape[1]), "linear")
    at = np.arange(size)[:, None] - np.arange(size)[None, :] + size - 1
    return table[at]


def decomposed_rel_pos(q: jnp.ndarray, rel_pos_h: jnp.ndarray,
                       rel_pos_w: jnp.ndarray, grid: Tuple[int, int]):
    """The decomposed bias's two factors for (B, N, H, D) UNSCALED queries
    on an (gh, gw) grid (N = gh·gw, row-major): ``rel_h`` (B, H, N, gh)
    and ``rel_w`` (B, H, N, gw), float32; the bias of query n and key
    (kh, kw) is ``rel_h[.., n, kh] + rel_w[.., n, kw]``."""
    b, n, h, d = q.shape
    gh, gw = grid
    r_h = rel_pos_table(rel_pos_h, gh).astype(q.dtype)  # (gh, gh, D)
    r_w = rel_pos_table(rel_pos_w, gw).astype(q.dtype)  # (gw, gw, D)
    r_q = q.reshape(b, gh, gw, h, d)
    rel_h = jnp.einsum("byxhd,ykd->bhyxk", r_q, r_h,
                       preferred_element_type=jnp.float32)
    rel_w = jnp.einsum("byxhd,xkd->bhyxk", r_q, r_w,
                       preferred_element_type=jnp.float32)
    return rel_h.reshape(b, h, n, gh), rel_w.reshape(b, h, n, gw)


class Attention(nn.Module):
    """Multi-head self-attention over (B, N, C) tokens of a ``grid`` with
    the decomposed relative-position bias; its tables span ``table``
    (rows, columns) positions: the window, or the whole grid."""

    dim: int
    heads: int
    table: Tuple[int, int]
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, grid: Tuple[int, int],
                 attn_fn=None) -> jnp.ndarray:
        b, n, c = x.shape
        h = self.heads
        d = self.dim // h
        qkv = nn.Dense(3 * self.dim, dtype=self.dtype,
                       param_dtype=jnp.float32, name="qkv")(x)
        q, k, v = jnp.split(qkv.reshape(b, n, 3, h, d), 3, axis=2)
        q, k, v = (t[:, :, 0] for t in (q, k, v))  # (B, N, H, D)
        rel_pos_h = self.param("rel_pos_h", nn.initializers.zeros,
                               (2 * self.table[0] - 1, d), jnp.float32)
        rel_pos_w = self.param("rel_pos_w", nn.initializers.zeros,
                               (2 * self.table[1] - 1, d), jnp.float32)
        rel = decomposed_rel_pos(q, rel_pos_h, rel_pos_w, grid)
        out = (attn_fn or dense_attention)(q, k, v, rel=rel)
        out = out.reshape(b, n, self.dim)
        return nn.Dense(self.dim, dtype=self.dtype, param_dtype=jnp.float32,
                        name="proj")(out)


def _drop(y: jnp.ndarray, keep) -> jnp.ndarray:
    """A residual branch times its per-image keep factor (0, or
    1/(1 - rate)); ``keep`` None is no stochastic depth."""
    if keep is None:
        return y
    return y * keep.astype(y.dtype)[:, None, None, None]


class Block(nn.Module):
    """Pre-LN transformer block, windowed or global spatial attention.

    Input/output (B, H, W, C). Window attention partitions the (H, W) grid
    into window x window tiles (padded if needed) and attends within each —
    the ViTDet local block. window == 0 → global attention over all H·W
    tokens (optionally ring attention when attn_fn is given), its bias
    tables sized to ``grid`` (the configured token grid; None → the
    input's). ``keep`` (2, B): the attention and MLP branches' stochastic
    depth factors, or None.
    """

    dim: int
    heads: int
    window: int = 0
    mlp_ratio: float = 4.0
    grid: Optional[Tuple[int, int]] = None
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, attn_fn=None,
                 keep=None) -> jnp.ndarray:
        b, h, w, c = x.shape
        shortcut = x
        if self.window > 0:
            ws = self.window
            with stage("window_attn"):
                y = _layer_norm(self.dtype, "norm1")(x)
                ph = (-h) % ws
                pw = (-w) % ws
                y = jnp.pad(y, ((0, 0), (0, ph), (0, pw), (0, 0)))
                hh, ww = h + ph, w + pw
                y = y.reshape(b, hh // ws, ws, ww // ws, ws, c)
                y = y.transpose(0, 1, 3, 2, 4, 5).reshape(-1, ws * ws, c)
                y = Attention(self.dim, self.heads, (ws, ws),
                              dtype=self.dtype, name="attn")(y, (ws, ws))
                y = y.reshape(b, hh // ws, ww // ws, ws, ws, c)
                y = y.transpose(0, 1, 3, 2, 4, 5).reshape(b, hh, ww, c)
                y = y[:, :h, :w]
        else:
            with stage("global_attn"):
                y = _layer_norm(self.dtype, "norm1")(x)
                y = Attention(self.dim, self.heads, self.grid or (h, w),
                              dtype=self.dtype, name="attn")(
                                  y.reshape(b, h * w, c), (h, w), attn_fn)
                y = y.reshape(b, h, w, c)
        x = shortcut + _drop(y, None if keep is None else keep[0])
        with stage("vit_mlp"):
            y = _layer_norm(self.dtype, "norm2")(x)
            y = nn.Dense(int(self.dim * self.mlp_ratio), dtype=self.dtype,
                         param_dtype=jnp.float32, name="mlp1")(y)
            y = nn.gelu(y, approximate=False)
            y = nn.Dense(self.dim, dtype=self.dtype,
                         param_dtype=jnp.float32, name="mlp2")(y)
        return x + _drop(y, None if keep is None else keep[1])


def drop_path_keep(key: jax.Array, batch: int, depth: int,
                   rate: float) -> jnp.ndarray:
    """(depth, 2, batch) stochastic-depth factors: block i drops each
    image's attention and MLP branch with probability ``rate·i/(depth-1)``
    (Detectron2's ``linspace(0, drop_path_rate, depth)``), a kept branch
    scaled by 1/(1 - its rate) (timm's ``DropPath``)."""
    rates = jnp.linspace(0.0, rate, depth)[:, None, None]
    kept = jax.random.uniform(key, (depth, 2, batch)) >= rates
    return kept.astype(jnp.float32) / (1.0 - rates)


def _global_block_indices(depth: int) -> set:
    """ViTDet global-attention placement: the depth is split into 4
    subsets, each ENDING with a global block (ViT-B depth 12 → {2, 5, 8,
    11}); degenerate small depths (< 4) make every block global. Shared
    by ViTBackbone and the staged-layout checkpoint converters."""
    blocks = {depth * k // 4 - 1 for k in range(1, 5)}
    return {i for i in blocks if i >= 0} or {depth - 1}


def _stage_global_pattern(depth: int, stages_n: int):
    """In-stage indices of the global-attention blocks for a staged split
    of the sequential backbone — the SAME tuple for every stage, so the
    stages are identically structured (what nn.scan and the GPipe ring
    need), or ValueError when no such split exists.

    The sequential placement is periodic with period depth/4, so any
    stages_n dividing 4 preserves it exactly (depth 12, 2 stages → {2, 5}
    in both halves); degenerate all-global depths support any divisor.
    Splits that would change the architecture (e.g. depth 12 into 3
    stages) hard-error instead of silently training a different model."""
    if stages_n <= 0 or depth % stages_n:
        raise ValueError(
            f"vit_depth {depth} must divide into pp_stages {stages_n}")
    per = depth // stages_n
    g = _global_block_indices(depth)
    pats = [tuple(sorted(i - s * per for i in g
                         if s * per <= i < (s + 1) * per))
            for s in range(stages_n)]
    if any(p != pats[0] for p in pats[1:]):
        raise ValueError(
            f"pp_stages={stages_n} cannot preserve the ViTDet global-"
            f"attention placement at depth {depth}: the sequential globals "
            f"{sorted(g)} split into unequal per-stage patterns {pats}; "
            "pipeline stages must be identically structured. Use a stage "
            "count that divides 4 (the placement period is depth/4).")
    return pats[0]


def _embed_patches(mdl, x: jnp.ndarray) -> jnp.ndarray:
    """Shared embed surface: patch Conv + the absolute pos-embed, a table
    of ``mdl.grid`` positions (the configured token grid; None → the
    input's), bilinearly resized where the input's grid differs. Called
    from the compact bodies of BOTH backbones (same param names —
    `patch_embed`, `pos_embed` — so the checkpoint format is identical;
    static under jit: shapes are compile-time)."""
    x = nn.Conv(mdl.dim, (mdl.patch, mdl.patch),
                strides=(mdl.patch, mdl.patch), dtype=mdl.dtype,
                param_dtype=jnp.float32, name="patch_embed")(
                    x.astype(mdl.dtype))
    h, w = x.shape[1], x.shape[2]
    gh, gw = mdl.grid or (h, w)
    pos = mdl.param("pos_embed", nn.initializers.normal(0.02),
                    (1, gh, gw, mdl.dim), jnp.float32)
    if (gh, gw) != (h, w):
        pos = jax.image.resize(pos, (1, h, w, mdl.dim), "bilinear")
    return x + pos.astype(mdl.dtype)


class ViTBackbone(nn.Module):
    """Plain ViT encoder → single stride-16 feature map (B, H/16, W/16, C),
    the last block's output (no norm after it: Detectron2's ViT).

    Global blocks at depth/4 spacing (ViTDet: 4 global blocks for ViT-B);
    the rest use `window`-sized local attention. ``grid``: the token grid
    the absolute positions and the global blocks' bias tables are sized
    to. ``drop_path``: the last block's stochastic-depth rate, applied when
    a key is given (training).
    """

    patch: int = 16
    dim: int = 768
    depth: int = 12
    heads: int = 12
    window: int = 14
    grid: Optional[Tuple[int, int]] = None
    drop_path: float = 0.0
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, attn_fn=None,
                 keep=None) -> jnp.ndarray:
        """``keep``: ``drop_path_keep``'s (depth, 2, B) factors, or None."""
        x = _embed_patches(self, x)
        global_blocks = _global_block_indices(self.depth)
        for i in range(self.depth):
            is_global = i in global_blocks
            x = Block(self.dim, self.heads,
                      window=0 if is_global else self.window,
                      grid=self.grid, dtype=self.dtype, name=f"block{i}")(
                          x, attn_fn if is_global else None,
                          None if keep is None else keep[i])
        return x


class ViTStage(nn.Module):
    """One pipeline stage: ``blocks`` Blocks, global attention at the
    static in-stage indices ``globals_idx`` (windowed elsewhere).

    The ViTDet placement is periodic in the stage size for any supported
    stage count (_stage_global_pattern), so every stage carries the SAME
    globals_idx — the encoder is a stack of IDENTICALLY-STRUCTURED stages,
    which is exactly what pipeline parallelism needs (ring-homogeneous,
    shape-preserving). nn.scan-compatible signature: (carry, None) ->
    (carry, None). Blocks are named positionally (b0..b{blocks-1}); the
    n-th block of every stage is of one kind (windowed or global), so its
    bias tables stack across the stages.
    """

    dim: int
    heads: int
    window: int
    blocks: int
    globals_idx: tuple = ()
    grid: Optional[Tuple[int, int]] = None
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, _=None):
        for i in range(self.blocks):
            is_global = i in self.globals_idx
            x = Block(self.dim, self.heads,
                      window=0 if is_global else self.window,
                      grid=self.grid, dtype=self.dtype, name=f"b{i}")(x)
        return x, None


class ViTBackbonePP(nn.Module):
    """Plain-ViT encoder with a STAGED block stack for pipeline parallelism.

    Same embed surface as ViTBackbone, but the depth is organized as
    ``stages_n`` scanned ViTStages (params stacked on a leading stage axis
    by nn.scan). Sequential execution (pipeline_fn=None) and pipelined
    execution (parallel/pipeline.py::pipeline_apply over the mesh `model`
    axis) share the SAME parameters and numerics. The global-attention
    placement matches ViTBackbone's ViTDet pattern EXACTLY for every
    supported stage count (_stage_global_pattern hard-errors on splits
    that cannot preserve it). No stochastic depth.
    """

    patch: int = 16
    dim: int = 768
    stages_n: int = 4
    blocks_per_stage: int = 3
    heads: int = 12
    window: int = 14
    grid: Optional[Tuple[int, int]] = None
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x: jnp.ndarray, pipeline_fn=None) -> jnp.ndarray:
        x = _embed_patches(self, x)
        stage_kw = dict(dim=self.dim, heads=self.heads, window=self.window,
                        blocks=self.blocks_per_stage,
                        globals_idx=_stage_global_pattern(
                            self.stages_n * self.blocks_per_stage,
                            self.stages_n),
                        grid=self.grid or tuple(x.shape[1:3]),
                        dtype=self.dtype)
        ScanStages = nn.scan(
            ViTStage, variable_axes={"params": 0},
            split_rngs={"params": True}, length=self.stages_n)
        stages = ScanStages(**stage_kw, name="stages")
        if pipeline_fn is None or self.is_initializing():
            # Sequential nn.scan — also the init path (creates the stacked
            # params the pipeline slices per stage).
            x, _ = stages(x, None)
        else:
            stacked = self.variables["params"]["stages"]
            stage_mod = ViTStage(**stage_kw)

            def stage_fn(p, h_act):
                y, _ = stage_mod.apply({"params": p}, h_act)
                return y

            x = pipeline_fn(stage_fn, stacked, x)
        return x


class SimpleFeaturePyramid(nn.Module):
    """ViTDet SFP: stride-16 map → {P2..P6} 256-channel pyramid; each
    level's 1x1 and 3x3 convs bias-free and LayerNorm'd (``norm="LN"``)."""

    channels: int = 256
    dtype: Dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, feat: jnp.ndarray) -> Dict[int, jnp.ndarray]:
        def out_convs(y, lv):
            y = nn.Conv(self.channels, (1, 1), use_bias=False,
                        dtype=self.dtype, param_dtype=jnp.float32,
                        name=f"out{lv}_1")(y)
            y = _layer_norm(self.dtype, f"out{lv}_1_ln")(y)
            y = nn.Conv(self.channels, (3, 3), padding=[(1, 1), (1, 1)],
                        use_bias=False, dtype=self.dtype,
                        param_dtype=jnp.float32, name=f"out{lv}_3")(y)
            return _layer_norm(self.dtype, f"out{lv}_3_ln")(y)

        c = feat.shape[-1]
        # stride 4: two stride-2 deconvs (with an intermediate norm+gelu).
        y4 = nn.ConvTranspose(c // 2, (2, 2), strides=(2, 2),
                              dtype=self.dtype, param_dtype=jnp.float32,
                              name="up4_1")(feat)
        y4 = nn.gelu(_layer_norm(self.dtype, "up4_ln")(y4),
                     approximate=False)
        y4 = nn.ConvTranspose(c // 4, (2, 2), strides=(2, 2),
                              dtype=self.dtype, param_dtype=jnp.float32,
                              name="up4_2")(y4)
        y8 = nn.ConvTranspose(c // 2, (2, 2), strides=(2, 2),
                              dtype=self.dtype, param_dtype=jnp.float32,
                              name="up8")(feat)
        out = {
            2: out_convs(y4, 2),
            3: out_convs(y8, 3),
            4: out_convs(feat, 4),
            5: out_convs(nn.max_pool(feat, (2, 2), strides=(2, 2)), 5),
        }
        out[6] = nn.max_pool(out[5], (1, 1), strides=(2, 2))
        return out


class ViTDet(nn.Module):
    """ViT backbone + SFP + the FPN detection heads at ViTDet's shapes.

    Mirrors models/fpn.py::FPNFasterRCNN's method surface (extract /
    rpn_forward / box_head / mask_forward and the attrs the functional
    forwards read), so fpn.forward_train/forward_test/forward_rpn drive it
    via models/zoo.py without modification.
    """

    num_classes: int = 81
    num_anchors: int = 3
    fpn_channels: int = 256
    roi_pool_size: int = 7
    use_mask: bool = False
    mask_pool_size: int = 14
    patch: int = 16
    dim: int = 768
    depth: int = 12
    heads: int = 12
    window: int = 14
    # the configured token grid (canvas / patch): the absolute positions'
    # and the global blocks' bias tables' size
    grid: Optional[Tuple[int, int]] = None
    drop_path: float = 0.0
    # the heads' norm ("layer": the recipe's LN) and shapes
    norm: str = "layer"
    rpn_convs: int = 2
    box_head_convs: int = 4
    # ROIAlignV2: the half-pixel shift (models/fpn.py::pyramid_roi_align)
    roi_aligned: bool = True
    dtype: Dtype = jnp.bfloat16
    # Optional ring-attention backend for the global blocks: a callable
    # (q, k, v, rel=) -> out, typically partial(ring_attention,
    # mesh=mesh). Static (non-pytree) module field.
    global_attn_fn: Optional[Any] = None
    # Pipeline parallelism (mutually exclusive with global_attn_fn — both
    # own the mesh `model` axis): number of encoder stages, and the
    # executor (stage_fn, stacked_params, x) -> x built over the mesh
    # (parallel/pipeline.py). pp_stages > 0 selects ViTBackbonePP.
    pp_stages: int = 0
    pipeline_fn: Optional[Any] = None

    def setup(self):
        if self.pp_stages:
            # Raises when depth doesn't divide OR the split can't preserve
            # the ViTDet global-attention placement (hard error, not a
            # warning — a silently different architecture is a trap).
            _stage_global_pattern(self.depth, self.pp_stages)
            self.features = ViTBackbonePP(
                patch=self.patch, dim=self.dim, stages_n=self.pp_stages,
                blocks_per_stage=self.depth // self.pp_stages,
                heads=self.heads, window=self.window, grid=self.grid,
                dtype=self.dtype)
        else:
            self.features = ViTBackbone(patch=self.patch, dim=self.dim,
                                        depth=self.depth, heads=self.heads,
                                        window=self.window, grid=self.grid,
                                        drop_path=self.drop_path,
                                        dtype=self.dtype)
        self.neck = SimpleFeaturePyramid(channels=self.fpn_channels,
                                         dtype=self.dtype)
        self.rpn = RPNHead(num_anchors=self.num_anchors,
                           channels=self.fpn_channels, convs=self.rpn_convs,
                           dtype=self.dtype)
        self.head = (ConvFCHead(convs=self.box_head_convs,
                                channels=self.fpn_channels, norm=self.norm,
                                dtype=self.dtype)
                     if self.box_head_convs else TwoFCHead(dtype=self.dtype))
        self.cls_score = nn.Dense(
            self.num_classes, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.01), name="cls_score")
        self.bbox_pred = nn.Dense(
            self.num_classes * 4, dtype=self.dtype, param_dtype=jnp.float32,
            kernel_init=nn.initializers.normal(0.001), name="bbox_pred")
        if self.use_mask:
            self.mask_head = MaskHead(num_classes=self.num_classes,
                                      norm=self.norm, dtype=self.dtype)

    def extract(self, images: jnp.ndarray, masks=None,
                drop_key=None) -> Dict[int, jnp.ndarray]:
        """masks (graftcanvas): packed-canvas placement masks applied to
        the SFP pyramid outputs. The ViT encoder itself attends across
        the canvas (windowed/global blocks may span placements — a
        documented approximation, unlike the conv families' exact
        re-masking); masking the pyramid keeps the RPN/ROI inputs clean
        so the proposal path stays border-exact. ``drop_key``: the
        training step's key for stochastic depth (None: none)."""
        with stage("backbone"):
            if self.pp_stages:
                feat = self.features(images, self.pipeline_fn)
            else:
                keep = None
                if drop_key is not None and self.drop_path > 0.0:
                    keep = drop_path_keep(drop_key, images.shape[0],
                                          self.depth, self.drop_path)
                feat = self.features(images, self.global_attn_fn, keep)
        with stage("neck"):
            pyramid = self.neck(feat)
            if masks:
                pyramid = {lv: (p * masks[2 ** lv].astype(p.dtype)
                                if 2 ** lv in masks else p)
                           for lv, p in pyramid.items()}
        return pyramid

    def rpn_forward(self, pyramid: Dict[int, jnp.ndarray]):
        from mx_rcnn_tpu.models.fpn import RPN_LEVELS

        return {lv: self.rpn(pyramid[lv]) for lv in RPN_LEVELS}

    def rpn_forward_packed(self, pyramid: Dict[int, jnp.ndarray]):
        """One fused head application over all levels (see
        models/fpn.py::FPNFasterRCNN.rpn_forward_packed)."""
        from mx_rcnn_tpu.models.fpn import apply_rpn_head_packed

        return apply_rpn_head_packed(self.rpn, pyramid)

    def box_head(self, pooled: jnp.ndarray):
        x = self.head(pooled)
        return (island(self.cls_score(x)),
                island(self.bbox_pred(x)))

    def mask_forward(self, pooled: jnp.ndarray):
        return self.mask_head(pooled)

    def __call__(self, images: jnp.ndarray, rois: jnp.ndarray):
        from mx_rcnn_tpu.ops.roi_align import roi_align

        pyramid = self.extract(images)
        rpn_out = self.rpn_forward(pyramid)
        pooled = roi_align(pyramid[2], rois, self.roi_pool_size, 1.0 / 4.0)[0]
        cls, box = self.box_head(pooled)
        outs = (pyramid, rpn_out, cls, box)
        if self.use_mask:
            mp = roi_align(pyramid[2], rois, self.mask_pool_size, 1.0 / 4.0)
            outs = outs + (self.mask_forward(mp[0]),)
        return outs


def build_vitdet_model(cfg: Config, global_attn_fn=None,
                       pipeline_fn=None) -> ViTDet:
    pp_stages = cfg.network.pp_stages
    if pp_stages and global_attn_fn is not None:
        raise ValueError(
            "pp_stages and sequence-parallel attention both claim the mesh "
            "'model' axis; enable one of network.pp_stages / "
            "network.use_ring_attention")
    if pp_stages and cfg.network.tensor_parallel:
        raise ValueError(
            "network.tensor_parallel and network.pp_stages both claim the "
            "mesh 'model' axis (TP rules would shard the stacked STAGE "
            "axis of the scanned stage params); enable only one")
    if pp_stages:
        # Fail fast (before init) on splits that would change the
        # architecture; every constructible staged model preserves the
        # sequential global placement exactly.
        _stage_global_pattern(cfg.network.vit_depth, pp_stages)
        if cfg.network.vit_drop_path > 0.0:
            raise ValueError(
                "network.pp_stages runs no stochastic depth: set "
                "network.vit_drop_path=0 (the staged backbone would train "
                "another recipe silently)")
    patch = cfg.network.vit_patch
    h, w = cfg.image.pad_shape
    return ViTDet(
        num_classes=cfg.dataset.num_classes,
        num_anchors=cfg.network.num_anchors,
        fpn_channels=cfg.network.fpn_channels,
        roi_pool_size=cfg.network.roi_pool_size,
        use_mask=cfg.network.use_mask,
        mask_pool_size=cfg.network.mask_pool_size,
        patch=patch,
        dim=cfg.network.vit_dim,
        depth=cfg.network.vit_depth,
        heads=cfg.network.vit_heads,
        window=cfg.network.vit_window,
        grid=(-(-h // patch), -(-w // patch)),
        drop_path=cfg.network.vit_drop_path,
        norm=cfg.network.norm,
        rpn_convs=cfg.network.rpn_convs,
        box_head_convs=cfg.network.box_head_convs,
        dtype=model_dtype(cfg),
        global_attn_fn=global_attn_fn,
        pp_stages=pp_stages,
        pipeline_fn=pipeline_fn,
    )


def sequential_to_staged(params, stages_n: int):
    """Convert a ViTDet param tree from the sequential backbone layout
    (`features/block{i}` with globals at depth/4 tails) to the staged/PP
    layout (`features/stages` with leaves stacked on a leading stage axis).

    Enables the train-small → scale-out path: fit with the default
    backbone on one chip, then resume/continue under pp_stages. Valid for
    every stage count the staged backbone itself supports — i.e. whenever
    _stage_global_pattern(depth, stages_n) exists, the staged model runs
    the IDENTICAL architecture (ValueError otherwise). Non-backbone
    leaves pass through unchanged.
    """
    feats = params["params"]["features"]
    blocks = sorted((k for k in feats if k.startswith("block")),
                    key=lambda k: int(k[5:]))
    depth = len(blocks)
    if not depth:
        raise ValueError(
            "no features/block* leaves — not a sequential-backbone param "
            "tree (already staged?)")
    _stage_global_pattern(depth, stages_n)  # architecture must be preserved
    per = depth // stages_n

    # ViTStage names its blocks positionally: b0..b{per-1}.
    def stage_tree(s):
        return {f"b{j}": feats[blocks[s * per + j]] for j in range(per)}

    stages = jax.tree.map(lambda *leaves: jnp.stack(leaves),
                          *[stage_tree(s) for s in range(stages_n)])
    new_feats = {k: v for k, v in feats.items() if not k.startswith("block")}
    new_feats["stages"] = stages
    return {**params, "params": {**params["params"], "features": new_feats}}


def staged_to_sequential(params):
    """Inverse of sequential_to_staged (stacked stages → block{i}).

    Validates the same architecture constraint as the forward direction:
    a staged layout whose (stages_n, per) split cannot preserve the
    sequential backbone's global placement would convert into params that
    LOAD cleanly (Block shapes are window-independent) but run the wrong
    attention pattern — the architectures differ, so it is rejected.
    """
    feats = params["params"]["features"]
    if "stages" not in feats:
        raise ValueError(
            "no features/stages subtree — not a staged-backbone param tree")
    stages = feats["stages"]
    stages_n = jax.tree.leaves(stages)[0].shape[0]
    names = sorted((k for k in stages
                    if k.startswith("b") and k[1:].isdigit()),
                   key=lambda k: int(k[1:]))
    if not names or len(names) != len(stages):
        raise ValueError(
            f"stage blocks {sorted(stages)} are not the positional "
            "b0..b{n} layout — a pre-round-4 staged checkpoint "
            "(win{i}/glob names) must be converted by the round that "
            "wrote it; refusing to silently drop blocks")
    per = len(names)
    depth = stages_n * per
    try:
        _stage_global_pattern(depth, stages_n)
    except ValueError as e:
        raise ValueError(f"the architectures differ: {e}") from e
    new_feats = {k: v for k, v in feats.items() if k != "stages"}
    for s in range(stages_n):
        for j, name in enumerate(names):
            new_feats[f"block{s * per + j}"] = jax.tree.map(
                lambda a: a[s], stages[name])
    return {**params, "params": {**params["params"], "features": new_feats}}


def init_vitdet_params(model: ViTDet, cfg: Config, rng: jax.Array,
                       image_shape=None):
    h, w = image_shape or (64, 64)
    images = jnp.zeros((1, h, w, 3), jnp.float32)
    rois = jnp.asarray([[[0.0, 0.0, 31.0, 31.0]]], jnp.float32)
    return model.init(rng, images, rois)
