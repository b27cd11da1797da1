"""Sequence-parallel exact attention over a mesh axis — ring and all-to-all.

The reference has no attention at all (SURVEY.md §3.2 / §6: "no reference
parity needed ... if the ViTDet/DETR stretch config lands, sequence = image
patches — plan a shard_map ring-attention option over the ICI mesh"). This
module provides BOTH standard sequence-parallel formulations:

- **Ring** (`ring_attention`, Liu et al.): key/value blocks rotate around
  the ring with `jax.lax.ppermute`, overlapping compute with ICI
  transfers; streaming-softmax accumulation. Memory per device is
  O(S/P · d) instead of O(S · d), so context length scales linearly with
  the ring size. No constraint on head count.
- **All-to-all** (`ulysses_attention`, DeepSpeed-Ulysses): one
  re-partition step before attention (an `all_to_all` on each of q/k/v)
  and one after (on the output) — 4 tensor collectives per call —
  exchange sequence sharding for head sharding; streaming-softmax
  (flash-style) attention runs locally without materializing the (S, S)
  score matrix. Constant collective count instead of the ring's P−1
  hops per tensor; requires heads divisible by the axis size.

Algorithm (Liu et al., Ring Attention; numerics = flash attention's
streaming softmax): each device keeps its query shard fixed and accumulates

    m_new = max(m, rowmax(q k_blk^T))
    acc   = acc · e^{m−m_new} + e^{s−m_new} v_blk
    l     = l · e^{m−m_new} + rowsum(e^{s−m_new})

over all P key/value blocks, permuting (k, v) one step around the ring per
iteration. The final output acc / l is bitwise-independent of the block
order up to float addition reordering, so it matches dense softmax
attention to numerical tolerance (tests/test_ring_attention.py).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _block_attn_update(carry, kv, q, scale, key_mask=None):
    """One streaming-softmax update with a (k, v) block.

    key_mask: optional (block,) bool — False keys are excluded (their
    scores forced to −inf before the max/exp), used for the padded tail
    block of streaming_attention.
    """
    acc, m, l = carry
    k, v = kv
    s = jnp.einsum("...qhd,...khd->...hqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if key_mask is not None:
        s = jnp.where(key_mask, s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)  # (..., h, q)
    m_new = jnp.maximum(m, m_blk)
    p = jnp.exp(s - m_new[..., None])  # (..., h, q, k)
    corr = jnp.exp(m - m_new)
    acc = acc * corr[..., None] + jnp.einsum(
        "...hqk,...khd->...hqd", p, v, preferred_element_type=jnp.float32)
    l = l * corr + jnp.sum(p, axis=-1)
    return acc, m_new, l


def _mark_varying(x, axes):
    """Mark ``x`` as varying over the shard_map manual ``axes`` (varying-
    type tracking: a carry that starts replicated and becomes per-shard
    inside the loop must say so up front)."""
    return lax.pcast(x, axes, to="varying")


def _streaming_init(q, vary_axes=()):
    """(acc, m, l) carry for the streaming softmax, (..., h, q_len, d/·),
    marked varying over `vary_axes` (the carry mixes with sharded operands
    inside shard_map loops, so the types must agree)."""
    h, d, q_len = q.shape[-2], q.shape[-1], q.shape[-3]
    batch_shape = q.shape[:-3]
    acc = jnp.zeros(batch_shape + (h, q_len, d), jnp.float32)
    m = jnp.full(batch_shape + (h, q_len), -jnp.inf, jnp.float32)
    l = jnp.zeros(batch_shape + (h, q_len), jnp.float32)
    if vary_axes:
        acc, m, l = (_mark_varying(x, tuple(vary_axes))
                     for x in (acc, m, l))
    return acc, m, l


def _streaming_finalize(acc, l, dtype):
    """acc / l with the (..., h, q, d) -> (..., q, h, d) layout restore."""
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return jnp.moveaxis(out, -3, -2).astype(dtype)


def ring_attention_sharded(q, k, v, axis_name: str, scale=None,
                           vary_axes=None):
    """Attention with the SEQUENCE axis sharded over `axis_name`.

    To be called inside shard_map (or pmapped code): q/k/v are the LOCAL
    shards, shape (..., s_local, h, d). Returns the local output shard,
    (..., s_local, h, d), float32 accumulation cast back to q.dtype.

    vary_axes: all mesh axes the q/k/v shards vary over (defaults to just
    the ring axis). When the caller also shards the batch dim over another
    axis (DP×SP), that axis must be included so the fori_loop carry's
    varying-axes type matches the loop body's output.
    """
    p_size = lax.psum(1, axis_name)
    vary = tuple(vary_axes) if vary_axes is not None else (axis_name,)
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    acc, m, l = _streaming_init(q, vary)

    def body(i, carry):
        acc, m, l, k_cur, v_cur = carry
        acc, m, l = _block_attn_update((acc, m, l), (k_cur, v_cur), q, scale)
        perm = [(j, (j + 1) % p_size) for j in range(p_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return acc, m, l, k_nxt, v_nxt

    # p_size - 1 rotate-and-update steps, then the final block WITHOUT the
    # trailing ppermute (its result would be discarded — one full k/v shard
    # of ICI traffic saved per call).
    acc, m, l, k_last, v_last = lax.fori_loop(
        0, p_size - 1, body, (acc, m, l, k, v))
    acc, m, l = _block_attn_update((acc, m, l), (k_last, v_last), q, scale)
    return _streaming_finalize(acc, l, q.dtype)


def _sp_layout(q, mesh: Mesh, axis: str):
    """(spec, vary) for a (B, S, H, D) array with S sharded over `axis`.

    The BATCH axis stays sharded over the mesh's data axis when one exists
    (and isn't the sequence axis itself) — in the DP×SP layout the batch
    must not be allgathered onto every data-axis device. Batch sharding is
    skipped when the batch doesn't tile the data axis — notably the
    batch-1 dummy of init_vitdet_params; the real train step always passes
    a data-divisible global batch.
    """
    batch_axis = None
    if "data" in mesh.axis_names and axis != "data" \
            and mesh.shape["data"] > 1 \
            and q.shape[0] % mesh.shape["data"] == 0:
        batch_axis = "data"
    spec = P(batch_axis, axis, None, None)
    vary = (axis,) if batch_axis is None else (axis, batch_axis)
    return spec, vary


def _sp_entry(make_sharded_fn, q, k, v, mesh: Mesh, axis: str):
    """Shared full-array entry: shard the sequence axis over `mesh[axis]`,
    run the per-shard attention under shard_map, return the full array.

    make_sharded_fn(vary) -> the per-shard callable; the layout (spec and
    varying axes) is computed ONCE here so the two can't diverge."""
    spec, vary = _sp_layout(q, mesh, axis)
    fn = shard_map(
        make_sharded_fn(vary),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    sh = NamedSharding(mesh, spec)
    return fn(jax.device_put(q, sh), jax.device_put(k, sh),
              jax.device_put(v, sh))


def ring_attention(q, k, v, mesh: Mesh, axis: str = "data", scale=None):
    """Full-array entry point: shards the sequence axis over `mesh[axis]`.

    q/k/v: (B, S, H, D) with S divisible by the axis size. Output (B, S, H,
    D). This is the module attention backend for long-context configs
    (models/vit.py global blocks with network.use_ring_attention).
    """
    return _sp_entry(
        lambda vary: partial(ring_attention_sharded, axis_name=axis,
                             scale=scale, vary_axes=vary),
        q, k, v, mesh, axis)


def streaming_attention(q, k, v, scale=None, kv_chunk=1024, vary_axes=()):
    """Exact attention with flash-style streaming softmax over key blocks.

    (B, S, H, D) layout, same contract as dense_attention, but the score
    buffer is (..., H, S, chunk) instead of (..., H, S, S) — O(S·chunk)
    memory, so long sequences never materialize a quadratic tensor. A
    non-divisible S is padded up to a whole number of chunks with the
    padded keys masked to −inf, so the bound holds for every length. Used
    as the LOCAL attention inside ulysses_attention (which would otherwise
    undercut the module's long-context memory claim) and usable standalone.

    vary_axes: mesh axes the operands vary over when called inside
    shard_map (the scan carry must carry the same varying-axes type).
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = k.shape[-3]
    c = min(kv_chunk, s)
    n = -(-s // c)
    if n <= 1:
        # One block: the streaming pass degenerates to a single (S, S)
        # score buffer anyway — dense is the same memory, fewer ops.
        return dense_attention(q, k, v, scale=scale)
    h, d = q.shape[-2], q.shape[-1]
    batch_shape = q.shape[:-3]
    pad = n * c - s
    if pad:
        widths = [(0, 0)] * (k.ndim - 3) + [(0, pad), (0, 0), (0, 0)]
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
    acc, m, l = _streaming_init(q, vary_axes)
    # (..., n·c, h, d) -> (n, ..., c, h, d): chunk axis leading for scan.
    nd = k.ndim
    km = jnp.moveaxis(k.reshape(batch_shape + (n, c, h, d)), nd - 3, 0)
    vm = jnp.moveaxis(v.reshape(batch_shape + (n, c, h, d)), nd - 3, 0)

    def body(carry, xs):
        return _block_attn_update(carry, xs, q, scale), None

    if pad:
        # Only the final block holds padded keys: scan the full blocks
        # unmasked (no per-block where in the hot path), then one masked
        # tail update.
        (acc, m, l), _ = lax.scan(body, (acc, m, l), (km[:-1], vm[:-1]))
        tail_mask = jnp.arange(c) < (c - pad)
        acc, m, l = _block_attn_update((acc, m, l), (km[-1], vm[-1]), q,
                                       scale, key_mask=tail_mask)
    else:
        (acc, m, l), _ = lax.scan(body, (acc, m, l), (km, vm))
    return _streaming_finalize(acc, l, q.dtype)


def ulysses_attention_sharded(q, k, v, axis_name: str, scale=None,
                              vary_axes=None, kv_chunk=1024):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses layout).

    Local shards (..., s_local, h, d) with the SEQUENCE sharded over
    `axis_name`. all_to_alls on q/k/v re-partition to full-sequence ×
    h/P heads (3 collectives), exact streaming-softmax attention runs
    locally (no (S, S) buffer), and one all_to_all re-partitions the
    output back — 4 tensor collectives per call, independent of P, vs
    the ring's P−1 ppermutes each for k and v; cheaper when h ≥ P and
    the per-step latency of the ring hops would dominate. Requires h
    divisible by the axis size (ring has no such constraint).
    """
    p_size = lax.psum(1, axis_name)
    h = q.shape[-2]
    # h % p_size == 0 is a static requirement; jit-traced shapes make this
    # checkable at trace time.
    if h % p_size != 0:
        raise ValueError(
            f"ulysses_attention needs heads ({h}) divisible by the "
            f"sequence-parallel axis size ({p_size}); use ring_attention "
            "for head-indivisible layouts")
    # (..., s_local, h, d) -> (..., s_full, h/P, d): split heads, gather seq.
    q, k, v = (
        lax.all_to_all(x, axis_name, split_axis=x.ndim - 2,
                       concat_axis=x.ndim - 3, tiled=True)
        for x in (q, k, v))
    vary = tuple(vary_axes) if vary_axes is not None else (axis_name,)
    out = streaming_attention(q, k, v, scale=scale, vary_axes=vary,
                              kv_chunk=kv_chunk)
    # (..., s_full, h/P, d) -> (..., s_local, h, d).
    return lax.all_to_all(out, axis_name, split_axis=out.ndim - 3,
                          concat_axis=out.ndim - 2, tiled=True)


def ulysses_attention(q, k, v, mesh: Mesh, axis: str = "data", scale=None,
                      kv_chunk=1024):
    """Full-array entry point for the all-to-all SP formulation; same
    contract as ring_attention (q/k/v (B, S, H, D), S divisible by the
    axis size, plus H divisible by the axis size). kv_chunk sets the local
    streaming-softmax key-block size (the (S, S/chunks) memory knob)."""
    return _sp_entry(
        lambda vary: partial(ulysses_attention_sharded, axis_name=axis,
                             scale=scale, vary_axes=vary,
                             kv_chunk=kv_chunk),
        q, k, v, mesh, axis)


def dense_attention(q, k, v, scale=None):
    """Reference dense softmax attention, (B, S, H, D) layout — the oracle
    for the ring formulation and the single-device fallback."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    s = jnp.einsum("...qhd,...khd->...hqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("...hqk,...khd->...qhd", p, v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)
