"""Ring attention (sequence-parallel) vs dense softmax attention."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mx_rcnn_tpu.ops.ring_attention import (
    dense_attention,
    ring_attention,
    ring_attention_sharded,
)
from mx_rcnn_tpu.parallel.mesh import create_mesh


def _qkv(rng, b=2, s=32, h=4, d=8):
    q = rng.randn(b, s, h, d).astype(np.float32)
    k = rng.randn(b, s, h, d).astype(np.float32)
    v = rng.randn(b, s, h, d).astype(np.float32)
    return jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)


def test_single_block_equals_dense(rng):
    """Ring of size 1 degenerates to dense attention exactly."""
    q, k, v = _qkv(rng)
    mesh = create_mesh("1")
    out = ring_attention(q, k, v, mesh, axis="data")
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ring", [2, 4, 8])
def test_ring_matches_dense(rng, ring):
    if jax.device_count() < ring:
        pytest.skip(f"needs {ring} devices")
    q, k, v = _qkv(rng, s=8 * ring)
    mesh = create_mesh(str(ring))
    out = ring_attention(q, k, v, mesh, axis="data")
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ring_under_jit(rng):
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    q, k, v = _qkv(rng, s=32)
    mesh = create_mesh("4")
    out = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh))(q, k, v)
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_extreme_logits_stable(rng):
    """Streaming softmax must survive large-magnitude scores."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    q, k, v = _qkv(rng, s=16, d=4)
    q = q * 30.0  # logits ~ hundreds
    mesh = create_mesh("4")
    out = ring_attention(q, k, v, mesh)
    want = dense_attention(q, k, v)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_bf16_io(rng):
    if jax.device_count() < 2:
        pytest.skip("needs 2 devices")
    q, k, v = _qkv(rng, s=16)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    mesh = create_mesh("2")
    out = ring_attention(qb, kb, vb, mesh)
    assert out.dtype == jnp.bfloat16
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=0.05, atol=0.05)


def test_ring_dp_sp_batch_sharded(rng):
    """DP x SP layout: ring over 'model' with the batch sharded over
    'data' (the ViTDet use_ring_attention layout). Regression: the
    fori_loop carry must be marked varying over BOTH axes, and a batch
    not divisible by the data axis (init_vitdet_params' batch-1 dummy)
    must fall back to a replicated batch instead of failing device_put."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    mesh = create_mesh("4x2")
    q, k, v = _qkv(rng, b=4, s=16)
    out = ring_attention(q, k, v, mesh, axis="model")
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # batch 1 (not divisible by data=4): replicated-batch fallback.
    q1, k1, v1 = _qkv(rng, b=1, s=16)
    out1 = ring_attention(q1, k1, v1, mesh, axis="model")
    np.testing.assert_allclose(np.asarray(out1),
                               np.asarray(dense_attention(q1, k1, v1)),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_matches_dense(rng):
    """All-to-all SP formulation == dense attention (heads divisible)."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    from mx_rcnn_tpu.ops.ring_attention import ulysses_attention
    q, k, v = _qkv(rng, b=2, s=32, h=4, d=8)
    mesh = create_mesh("4")
    out = ulysses_attention(q, k, v, mesh, axis="data")
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_dp_sp_and_grad(rng):
    """Ulysses under the DP x SP (4x2) layout, and its gradient flows."""
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices")
    from mx_rcnn_tpu.ops.ring_attention import ulysses_attention
    mesh = create_mesh("4x2")
    q, k, v = _qkv(rng, b=4, s=16, h=4, d=8)
    out = ulysses_attention(q, k, v, mesh, axis="model")
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)

    def loss(fn):
        return lambda a, b, c: jnp.sum(fn(a, b, c) ** 2)

    g_sp = jax.grad(loss(lambda a, b, c: ulysses_attention(
        a, b, c, mesh, axis="model")))(q, k, v)
    g_dense = jax.grad(loss(dense_attention))(q, k, v)
    np.testing.assert_allclose(np.asarray(g_sp), np.asarray(g_dense),
                               rtol=5e-4, atol=5e-4)


def test_ulysses_head_divisibility_error(rng):
    """heads not divisible by the SP axis -> clear error, not garbage."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    from mx_rcnn_tpu.ops.ring_attention import ulysses_attention
    q, k, v = _qkv(rng, b=1, s=16, h=3, d=8)
    mesh = create_mesh("4")
    with pytest.raises(ValueError, match="divisible"):
        ulysses_attention(q, k, v, mesh, axis="data")


def test_streaming_attention_matches_dense(rng):
    """Flash-style streaming softmax (the Ulysses local attention) ==
    dense, exercised with >1 key chunk."""
    from mx_rcnn_tpu.ops.ring_attention import streaming_attention
    q, k, v = _qkv(rng, b=2, s=256, h=2, d=8)
    out = streaming_attention(q, k, v, kv_chunk=64)  # 4 chunks
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # auto chunk selection at a chunking length (2048 -> 2x1024).
    q2, k2, v2 = _qkv(rng, b=1, s=2048, h=1, d=4)
    out2 = streaming_attention(q2, k2, v2)
    np.testing.assert_allclose(np.asarray(out2),
                               np.asarray(dense_attention(q2, k2, v2)),
                               rtol=2e-5, atol=2e-5)
    # non-divisible length: padded tail chunk, masked keys (s=300 with
    # chunk 128 -> 3 chunks, 84 padded keys).
    q3, k3, v3 = _qkv(rng, b=1, s=300, h=2, d=4)
    out3 = streaming_attention(q3, k3, v3, kv_chunk=128)
    np.testing.assert_allclose(np.asarray(out3),
                               np.asarray(dense_attention(q3, k3, v3)),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_streaming_under_sp(rng):
    """The chunked streaming path INSIDE shard_map: small kv_chunk forces
    >1 key block (incl. a padded tail) under the SP re-partition."""
    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    from mx_rcnn_tpu.ops.ring_attention import ulysses_attention
    mesh = create_mesh("4")
    q, k, v = _qkv(rng, b=2, s=48, h=4, d=8)  # S_full=48, 16/chunk -> 3
    out = ulysses_attention(q, k, v, mesh, axis="data", kv_chunk=16)
    want = dense_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # padded tail inside shard_map: S_full=40, chunk 16 -> 3 chunks, 8 pad.
    q2, k2, v2 = _qkv(rng, b=1, s=40, h=4, d=8)
    out2 = ulysses_attention(q2, k2, v2, mesh, axis="data", kv_chunk=16)
    np.testing.assert_allclose(np.asarray(out2),
                               np.asarray(dense_attention(q2, k2, v2)),
                               rtol=2e-5, atol=2e-5)


def _rel(rng, b, h, s, grid):
    """Decomposed bias factors for (b, s, h, d) queries on a grid."""
    gh, gw = grid
    assert gh * gw == s
    return (jnp.asarray(rng.randn(b, h, s, gh), jnp.float32),
            jnp.asarray(rng.randn(b, h, s, gw), jnp.float32))


@pytest.mark.parametrize("form", ["ring", "ulysses", "streaming"])
def test_decomposed_bias_in_every_form(rng, form):
    """Each form adds the decomposed bias to the scores of the keys it
    holds, forward and gradient, as dense attention does: ring and Ulysses
    over 4 devices (each holds a quarter of the grid's rows of keys), the
    streaming form over key blocks of 12 (a padded tail). 2e-5 forward
    and 5e-4 gradient, the module's own tolerances for these forms."""
    from mx_rcnn_tpu.ops.ring_attention import (streaming_attention,
                                                ulysses_attention)
    if form != "streaming" and jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    q, k, v = _qkv(rng, b=2, s=32, h=4, d=8)
    rel = _rel(rng, 2, 4, 32, (4, 8))
    mesh = create_mesh("4")
    fn = {"ring": lambda *a, rel: ring_attention(*a, mesh, rel=rel),
          "ulysses": lambda *a, rel: ulysses_attention(*a, mesh, rel=rel),
          "streaming": lambda *a, rel: streaming_attention(
              *a, kv_chunk=12, rel=rel)}[form]
    want = dense_attention(q, k, v, rel=rel)
    assert np.abs(np.asarray(want)
                  - np.asarray(dense_attention(q, k, v))).max() > 1e-2
    np.testing.assert_allclose(np.asarray(fn(q, k, v, rel=rel)),
                               np.asarray(want), rtol=2e-5, atol=2e-5)

    def loss(f):
        return lambda a, b, c, r: jnp.sum(f(a, b, c, rel=r) ** 2)

    g = jax.grad(loss(fn), argnums=(0, 1, 2, 3))(q, k, v, rel)
    g_dense = jax.grad(loss(dense_attention), argnums=(0, 1, 2, 3))(
        q, k, v, rel)
    for got, ref in zip(jax.tree.leaves(g), jax.tree.leaves(g_dense)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=5e-4, atol=5e-4)
