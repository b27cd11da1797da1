"""Pallas TPU NMS — the blocked greedy NMS kernel.

This is the TPU replacement for the reference's CUDA NMS
(rcnn/cython/nms_kernel.cu + gpu_nms.pyx): same algorithm family — pairwise
suppression in score order, then a sequential survivor scan — but scheduled
for the TPU's vector unit instead of 64-thread warps:

- boxes are pre-sorted by score (descending) and padded to a multiple of the
  128-lane block size;
- the grid walks (set, block), and each step does only what greedy NMS needs
  of a 128-box block, in three parts:

  1. *the diagonal tile*: the IoU of the block's boxes against the block's
     own 128 columns, thresholded: who in the block overlaps whom;
  2. *the resolve*, on whole vectors: the greedy answer is the one fixed
     point of ``kept[j] = cand[j] & ~any_{i<j}(kept[i] & M[i, j])``, found by
     iterating that equation on the strictly-triangular tile until ``kept``
     stops changing (a handful of passes on real boxes, 128 on a chain where
     each box suppresses the next). No box is visited alone and nothing but
     the loop's own "changed" bit goes through a scalar. IoU is symmetric, so
     the one tile serves both layouts and the loop carries ``kept`` along the
     lanes (the output) and along the sublanes (what the sweep needs)
     without a transpose per pass;
  3. *the sweep*, fused and triangular: only the columns AFTER the block, in
     chunks of ``CHUNK``, each (8 boxes × 128 columns) register of IoU
     compared and folded straight into a persistent (8, N) VMEM flag
     accumulator — no (128, N) tile, no matrix product. A box that is not
     kept compares against +inf, so the kept mask costs no operation of its
     own. Block ``k`` reads its own columns' flags (an 8→1 sublane reduce,
     once a block) before it resolves.

Semantics match ops/nms.py exactly (strict ``>`` threshold on
``inter / max(union, 1e-14)`` in float32, +1 inclusive box widths,
score-descending greedy order; padded and invalid boxes are never kept and
never suppress — a column's validity needs no test, since its flag is only
read through ``cand``). This kernel is the production NMS for proposal
generation on TPU (ops/nms.py::nms_dispatch decides the path);
tests/test_nms.py::TestBatchedNMSPallas checks equivalence against both jnp
oracles with ``interpret=True``, and tests/test_chip_compile.py compiles it
for a described v5e at every N the presets reach.

Mosaic lowering notes: dynamic_slice on computed VALUES is unsupported — all
dynamic indexing here happens either through BlockSpec index maps or through
128-aligned ``pl.ds`` windows on refs (the flags, the spread columns). What
the sweep reads many times is spread once into whole registers: the set's
columns along the sublanes (once a set, into VMEM scratch), the block's boxes
along the lanes (once a block, as values the compiler keeps in VMEM).
The kernel needs about 220 bytes of VMEM a column (the chip's compiler:
2.6 MiB at N = 12000, 4.2 MiB at 20000), so no preset states a limit.

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
#: sublanes of a float32 vector register: the sweep walks the block's boxes
#: eight at a time, one register of each quantity per step
SUBLANES = 8
#: columns one pass of the sweep's loop covers: two blocks, and no more. The
#: chunks are counted back from the last column, so the farthest one starts
#: at most one block before the columns it is for (at the block's own first
#: column, never before column 0); wider chunks would need a clamped start.
#: Two blocks also read fastest on the chip (PERF.md section 6, PR 29).
CHUNK = 2 * BLOCK


def _suppresses(row, col, thr):
    """(SUBLANES, BLOCK) mask: box ``i`` of ``row`` overlaps column ``j`` of
    ``col`` by more than ``thr`` (a float, or a register of per-box
    thresholds).

    ``row`` is x1, y1, x2, y2 and the area of SUBLANES boxes, each spread
    along the lanes; ``col`` the same five of BLOCK boxes, each spread along
    the sublanes: every operand is one whole register.
    """
    x1i, y1i, x2i, y2i, area_i = row
    x1j, y1j, x2j, y2j, area_j = col
    iw = jnp.minimum(x2i, x2j) - jnp.maximum(x1i, x1j) + 1.0
    ih = jnp.minimum(y2i, y2j) - jnp.maximum(y1i, y1j) + 1.0
    inter = jnp.maximum(iw, 0.0) * jnp.maximum(ih, 0.0)
    return inter / jnp.maximum(area_i + area_j - inter, 1e-14) > thr


def _nms_kernel(rows_ref, cols_ref, valid_blk_ref, out_ref,
                colb_ref, supp_ref, *, iou_threshold: float):
    k = pl.program_id(1)
    n_pad = cols_ref.shape[2]
    groups = [slice(g, g + SUBLANES) for g in range(0, BLOCK, SUBLANES)]

    @pl.when(k == 0)
    def _():
        # A new image: its columns (and their areas) spread along the
        # sublanes once, for every block's sweep to load whole registers.
        c = cols_ref[0]  # (4, n_pad)
        area = (c[2:3] - c[0:1] + 1.0) * (c[3:4] - c[1:2] + 1.0)
        for q, v in enumerate((c[0:1], c[1:2], c[2:3], c[3:4], area)):
            colb_ref[q] = jnp.broadcast_to(v, (SUBLANES, n_pad))
        supp_ref[...] = jnp.zeros_like(supp_ref)

    # This block's boxes, score-descending, spread along the lanes.
    blk = rows_ref[0]  # (BLOCK, 4)
    x1, y1, x2, y2 = (blk[:, q:q + 1] for q in range(4))
    rows = [jnp.broadcast_to(v, (BLOCK, BLOCK)) for v in (
        x1, y1, x2, y2, (x2 - x1 + 1.0) * (y2 - y1 + 1.0))]

    # 1. The diagonal tile: who in the block overlaps whom.
    base = pl.multiple_of(k * BLOCK, BLOCK)
    own = [colb_ref[q, :, pl.ds(base, BLOCK)] for q in range(5)]
    m = jnp.concatenate(
        [_suppresses([r[g] for r in rows], own, iou_threshold)
         for g in groups]).astype(jnp.float32)
    i_id = lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 0)
    j_id = lax.broadcasted_iota(jnp.int32, (BLOCK, BLOCK), 1)
    # IoU is symmetric to the last bit (min, max and + commute), so the one
    # tile serves both layouts of the block: ``upper[i, j]`` lets an earlier
    # box i on the sublanes suppress j on the lanes, ``lower[i, j]`` lets an
    # earlier box j on the lanes suppress i on the sublanes.
    upper = jnp.where(i_id < j_id, m, 0.0)
    lower = jnp.where(j_id < i_id, m, 0.0)

    # 2. Resolve the block on whole vectors. A box can be kept if it is
    # valid and no earlier block suppressed it; the greedy answer is the one
    # fixed point of kept[j] = cand[j] & ~any_{i<j}(kept[i] & M[i, j]).
    # After t passes the first t boxes are final, so the loop ends within
    # BLOCK passes (a chain where each box suppresses the next) and in a
    # handful on real boxes. It carries the answer in both layouts: along
    # the lanes for the output, along the sublanes for the sweep.
    prefix = jnp.max(supp_ref[:, pl.ds(base, BLOCK)], axis=0, keepdims=True)
    cand_row = jnp.where((valid_blk_ref[0] > 0.0) & (prefix == 0.0), 1.0, 0.0)
    cand_col = jnp.max(jnp.where(i_id == j_id, cand_row, 0.0),
                       axis=1, keepdims=True)  # (BLOCK, 1): the transpose

    def settle(carry):
        kept_row, kept_col, _ = carry
        sup_row = jnp.max(upper * kept_col, axis=0, keepdims=True)
        sup_col = jnp.max(lower * kept_row, axis=1, keepdims=True)
        new_row = cand_row * (1.0 - sup_row)
        new_col = cand_col * (1.0 - sup_col)
        return new_row, new_col, jnp.max(jnp.abs(new_row - kept_row)) > 0.0

    kept_row, kept_col, _ = lax.while_loop(
        lambda carry: carry[2], settle, (cand_row, cand_col, True))
    out_ref[0] = kept_row

    # 3. Sweep the columns after the block, a chunk at a time: a kept box
    # compares against the threshold, any other against +inf (never above),
    # and a hit sets the column's flag on the sublane it was found on. The
    # chunks are counted back from the last column, so the farthest one may
    # reach into the block itself: its columns are never read again.
    thr = jnp.broadcast_to(
        jnp.where(kept_col > 0.0, iou_threshold, jnp.inf), (BLOCK, BLOCK))

    def sweep(c, _):
        start = pl.multiple_of(n_pad - (c + 1) * CHUNK, BLOCK)
        for t in range(0, CHUNK, BLOCK):
            at = pl.ds(start + t, BLOCK)
            col = [colb_ref[q, :, at] for q in range(5)]
            flags = supp_ref[:, at]
            for g in groups:
                flags = jnp.where(
                    _suppresses([r[g] for r in rows], col, thr[g]),
                    1.0, flags)
            supp_ref[:, at] = flags

    lax.fori_loop(0, pl.cdiv(n_pad - (k + 1) * BLOCK, CHUNK), sweep, None)


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

    keep = pl.pallas_call(
        partial(_nms_kernel, iou_threshold=float(iou_threshold)),
        grid=(s, n_pad // BLOCK),
        in_specs=[
            pl.BlockSpec((1, BLOCK, 4), lambda si, ki: (si, ki, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 4, n_pad), lambda si, ki: (si, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, BLOCK), lambda si, ki: (si, 0, ki),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, BLOCK), lambda si, ki: (si, 0, ki),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((s, 1, n_pad), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((5, SUBLANES, n_pad), jnp.float32),  # columns
            pltpu.VMEM((SUBLANES, n_pad), jnp.float32),     # flags
        ],
        interpret=interpret,
        name=KERNEL_NAME,
    )(rows, cols, vmask)
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
