"""Pallas TPU NMS — the blocked-bitmask greedy NMS kernel.

This is the TPU replacement for the reference's CUDA NMS
(rcnn/cython/nms_kernel.cu + gpu_nms.pyx): same algorithm family — compute
pairwise suppression in score order, then a sequential survivor scan — but
restructured for the TPU memory hierarchy instead of 64-thread warps:

- boxes are pre-sorted by score (descending) and padded to a multiple of the
  128-lane block size;
- the grid walks (set, block): for each 128-box block the kernel computes the
  IoU of the block's boxes against ALL boxes in one (128, N) VPU tile
  (recomputed per block — cheaper than materializing the N×N matrix in HBM,
  which is what caps the XLA `nms_bitmask` variant at ~6k boxes);
- suppression *within* the block is resolved by a 128-step `fori_loop` on
  (1, 128) vectors (the only inherently sequential part of greedy NMS);
- suppression of *later* blocks is propagated with one (1,128)·(128,N) MXU
  matmul into a persistent (1, N) VMEM accumulator.

Semantics match ops/nms.py exactly (strict `>` threshold, +1 inclusive box
widths, score-descending greedy order). This kernel is the production NMS for
proposal generation on TPU (ops/nms.py::nms_dispatch decides the path);
tests/test_nms.py::TestBatchedNMSPallas checks equivalence against both jnp
oracles with ``interpret=True``, and tests/test_chip_compile.py compiles it
for a described v5e at every N the presets reach.

Mosaic lowering notes: dynamic_slice on computed VALUES is unsupported — all
dynamic indexing here happens either through BlockSpec index maps (the
per-block column views) or through `pl.ds` on refs (the in-block suppression
matrix staged via VMEM scratch, the suppression-accumulator prefix).

``interpret`` is an argument, never a guess from the backend: nms_dispatch
always asks for the compiled kernel, the CPU tests ask for the interpreter.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128
#: the kernel's name in a compiled program and in a profiler trace, on one
#: chip and under a ``shard_map`` alike
KERNEL_NAME = "nms_sweep"


def _iou_tile(x1i, y1i, x2i, y2i, cols):
    """IoU of column-vector boxes (B,1 each) vs a (4, M) transposed box set."""
    x1j, y1j = cols[0:1, :], cols[1:2, :]
    x2j, y2j = cols[2:3, :], cols[3:4, :]
    iw = jnp.minimum(x2i, x2j) - jnp.maximum(x1i, x1j) + 1.0
    ih = jnp.minimum(y2i, y2j) - jnp.maximum(y1i, y1j) + 1.0
    inter = jnp.maximum(iw, 0.0) * jnp.maximum(ih, 0.0)
    area_i = (x2i - x1i + 1.0) * (y2i - y1i + 1.0)
    area_j = (x2j - x1j + 1.0) * (y2j - y1j + 1.0)
    return inter / jnp.maximum(area_i + area_j - inter, 1e-14)


def _nms_kernel(rows_ref, cols_ref, cols_blk_ref, valid_ref, valid_blk_ref,
                out_ref, supp_ref, mkk_ref, *, iou_threshold: float):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _():
        supp_ref[...] = jnp.zeros_like(supp_ref)

    blk = rows_ref[0]  # (BLOCK, 4) — this block's boxes, score-desc order
    x1i, y1i = blk[:, 0:1], blk[:, 1:2]
    x2i, y2i = blk[:, 2:3], blk[:, 3:4]

    vj = valid_ref[0]  # (1, N)
    # mask[i, j] = 1 iff box i (this block), if kept, suppresses box j.
    iou_all = _iou_tile(x1i, y1i, x2i, y2i, cols_ref[0])
    mask = ((iou_all > iou_threshold) & (vj > 0.0)).astype(jnp.float32)

    vblk = valid_blk_ref[0]  # (1, BLOCK)
    iou_kk = _iou_tile(x1i, y1i, x2i, y2i, cols_blk_ref[0])
    mkk_ref[...] = ((iou_kk > iou_threshold) & (vblk > 0.0)).astype(jnp.float32)

    base = pl.multiple_of(k * BLOCK, BLOCK)
    prefix = supp_ref[0:1, pl.ds(base, BLOCK)]  # (1, BLOCK)
    lane = lax.broadcasted_iota(jnp.int32, (1, BLOCK), 1)

    def inner(j, carry):
        kept_row, sup_row = carry  # (1, BLOCK) each
        onehot = (lane == j).astype(jnp.float32)
        supped = jnp.sum(onehot * (sup_row + prefix))
        v_j = jnp.sum(onehot * vblk)
        keep_j = (v_j > 0.0) & (supped == 0.0)
        # Row j of the in-block mask: boxes j would suppress if kept.
        mrow = mkk_ref[pl.ds(j, 1), :]
        sup_row = sup_row + jnp.where(keep_j, mrow, 0.0)
        kept_row = kept_row + jnp.where(keep_j, onehot, 0.0)
        return kept_row, sup_row

    zeros = jnp.zeros((1, BLOCK), jnp.float32)
    kept_row, _ = lax.fori_loop(0, BLOCK, inner, (zeros, zeros))

    out_ref[0] = kept_row
    # Propagate this block's survivors to every later column (earlier columns
    # are never read again, so polluting them is harmless).
    supp_ref[...] += lax.dot_general(
        kept_row, mask, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


# Mosaic's default scoped-VMEM budget on v5e; a kernel that needs more has
# to say so (v5e has 128 MiB of VMEM behind it).
_DEFAULT_SCOPED_VMEM = 16 << 20


def _vmem_limit(n_pad: int):
    """The kernel's VMEM need at ``n_pad`` columns, or None while the
    compiler's default budget covers it.

    The two live (BLOCK, n_pad) f32 tiles (IoU and mask) dominate; the
    (·, n_pad) inputs, the accumulator and their double buffers add about
    an eighth of that again. The chip's compiler reports 12.28 MiB at
    N = 12000 and 20.44 MiB at N = 20000 (tests/test_chip_compile.py keeps
    both compiles), so only the alternate-training budget
    (test.proposal_pre_nms_top_n = 20000) ever states a limit.
    """
    tile = BLOCK * n_pad * 4
    need = 2 * tile + tile // 4 + (1 << 20)
    return need if need > _DEFAULT_SCOPED_VMEM else None


def nms_keep_sorted(boxes: jnp.ndarray, valid: jnp.ndarray,
                    iou_threshold: float, interpret: bool = False
                    ) -> jnp.ndarray:
    """Greedy-NMS survivor mask over score-DESC-sorted boxes.

    Args:
      boxes: (S, N, 4) float32, sorted by descending score within each set.
      valid: (S, N) bool.
      interpret: run the kernel in the Pallas interpreter (what the CPU
        tests ask for by name); False compiles it with Mosaic.
    Returns: keep (S, N) bool.
    """
    s, n = boxes.shape[0], boxes.shape[1]
    n_pad = -(-n // BLOCK) * BLOCK
    if n_pad != n:
        boxes = jnp.pad(boxes, ((0, 0), (0, n_pad - n), (0, 0)))
        valid = jnp.pad(valid, ((0, 0), (0, n_pad - n)))
    rows = boxes.astype(jnp.float32)
    cols = jnp.transpose(rows, (0, 2, 1))  # (S, 4, N)
    vmask = valid.astype(jnp.float32)[:, None, :]  # (S, 1, N)

    grid = (s, n_pad // BLOCK)
    keep = pl.pallas_call(
        partial(_nms_kernel, iou_threshold=float(iou_threshold)),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, BLOCK, 4), lambda si, ki: (si, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 4, n_pad), lambda si, ki: (si, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 4, BLOCK), lambda si, ki: (si, 0, ki),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, n_pad), lambda si, ki: (si, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, BLOCK), lambda si, ki: (si, 0, ki),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, BLOCK), lambda si, ki: (si, 0, ki),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((s, 1, n_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, n_pad), jnp.float32),
            pltpu.VMEM((BLOCK, BLOCK), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_vmem_limit(n_pad)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(rows, cols, cols, vmask, vmask)
    return keep[:, 0, :n] > 0.0


def batched_nms(boxes: jnp.ndarray, scores: jnp.ndarray, valid: jnp.ndarray,
                iou_threshold: float, max_output: int,
                interpret: bool = False):
    """Batched greedy NMS: sort → Pallas survivor mask → top-k selection.

    Args:
      boxes: (S, N, 4); scores: (S, N); valid: (S, N) bool.
    Returns:
      keep_idx: (S, max_output) int32 indices into the ORIGINAL box order
        (0-padded), keep_valid: (S, max_output) bool.

    Same output contract as ops/nms.py::nms/nms_bitmask (score-descending
    emission order, stable ties by original index).
    """
    s, n = scores.shape
    neg = jnp.where(valid, scores.astype(jnp.float32), -jnp.inf)
    order = jnp.argsort(-neg, axis=1)  # stable: ties keep original order
    sboxes = jnp.take_along_axis(boxes, order[..., None], axis=1)
    svalid = jnp.take_along_axis(valid, order, axis=1)
    keep = nms_keep_sorted(sboxes, svalid, iou_threshold, interpret)  # (S, N)

    rank = jnp.cumsum(keep, axis=1) - 1
    take = keep & (rank < max_output)
    slot = jnp.where(take, rank, max_output)  # OOB slot drops padding rows
    out_idx = jnp.zeros((s, max_output), jnp.int32)
    out_valid = jnp.zeros((s, max_output), bool)
    out_idx = out_idx.at[jnp.arange(s)[:, None], slot].set(
        order.astype(jnp.int32), mode="drop")
    out_valid = out_valid.at[jnp.arange(s)[:, None], slot].set(
        True, mode="drop")
    return out_idx, out_valid
