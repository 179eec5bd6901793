"""Pallas flash attention vs XLA reference (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops.pallas import flash_attention as fa


def _make_qkv(key, b=2, s=256, h=4, kvh=2, d=64, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b, s, h, d), dtype=dtype)
    k = jax.random.normal(kk, (b, s, kvh, d), dtype=dtype)
    v = jax.random.normal(kv, (b, s, kvh, d), dtype=dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_reference(causal):
    q, k, v = _make_qkv(jax.random.key(0))
    out = fa.flash_attention(q, k, v, causal=causal, block_q=128,
                             block_k=128)
    ref = attention_ops._reference_attention(q, k, v, causal=causal,
                                             scale=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_gradients_match_reference():
    q, k, v = _make_qkv(jax.random.key(1), s=128)

    def loss_flash(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True,
                                          block_q=64, block_k=64) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_ops._reference_attention(
            q, k, v, causal=True, scale=None) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_flash_irregular_shape_falls_back():
    # seq not divisible by block -> reference fallback, still correct.
    q, k, v = _make_qkv(jax.random.key(2), s=100)
    out = fa.flash_attention(q, k, v, causal=True, block_q=64, block_k=64)
    ref = attention_ops._reference_attention(q, k, v, causal=True,
                                             scale=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_streamed_kernels_match_resident(monkeypatch):
    """Long-context (streamed) kernel family vs the resident-KV family:
    same math, different VMEM strategy — outputs and grads must agree."""
    q, k, v = _make_qkv(jax.random.key(3), s=256)

    def run(use_resident):
        monkeypatch.setattr(fa, "_use_resident",
                            lambda s, d: use_resident)

        def loss(q, k, v):
            return jnp.sum(fa.flash_attention(
                q, k, v, causal=True, block_q=64, block_k=64) ** 2)
        out = fa.flash_attention(q, k, v, causal=True, block_q=64,
                                 block_k=64)
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return out, grads

    o_r, g_r = run(True)
    o_s, g_s = run(False)
    np.testing.assert_allclose(np.asarray(o_s), np.asarray(o_r),
                               rtol=2e-3, atol=2e-3)
    for a, b in zip(g_s, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_tri_family_unequal_blocks(monkeypatch):
    """Triangular causal family with block_q != block_k (bound / lo
    arithmetic is exercised off the square-block fast path)."""
    q, k, v = _make_qkv(jax.random.key(4), s=256)
    monkeypatch.setattr(fa, "_use_resident", lambda s, d: False)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(
            q, k, v, causal=True, block_q=128, block_k=64) ** 2)

    out = fa.flash_attention(q, k, v, causal=True, block_q=128,
                             block_k=64)
    ref = attention_ops._reference_attention(q, k, v, causal=True,
                                             scale=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    gf = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        attention_ops._reference_attention(q, k, v, causal=True,
                                           scale=None) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_tri_family_unequal_blocks_kq(monkeypatch):
    """block_k > block_q: diagonal-straddle predicate must still mask,
    fwd AND bwd (the dkv kernel's lo/diag arithmetic runs in the
    wide-KV regime only here)."""
    q, k, v = _make_qkv(jax.random.key(5), s=256)
    monkeypatch.setattr(fa, "_use_resident", lambda s, d: False)
    out = fa.flash_attention(q, k, v, causal=True, block_q=64,
                             block_k=128)
    ref = attention_ops._reference_attention(q, k, v, causal=True,
                                             scale=None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    gf = jax.grad(lambda q, k, v: jnp.sum(fa.flash_attention(
        q, k, v, causal=True, block_q=64, block_k=128) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda q, k, v: jnp.sum(
        attention_ops._reference_attention(q, k, v, causal=True,
                                           scale=None) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)


def test_irregular_shape_fallback_is_counted():
    """The shape fallback stays a correctness path but must be visible:
    the trace counter says which implementation a call compiled to."""
    before = attention_ops.trace_counts()
    # Shapes no other test uses, so each call is a fresh trace.
    q, k, v = _make_qkv(jax.random.key(4), s=104)   # 8-aligned: kernel
    fa.flash_attention(q, k, v, causal=True)
    q, k, v = _make_qkv(jax.random.key(4), s=136)   # 8-aligned: kernel
    fa.flash_attention(q, k, v, causal=True)
    q, k, v = _make_qkv(jax.random.key(4), s=100)   # not 8-aligned
    fa.flash_attention(q, k, v, causal=True)
    after = attention_ops.trace_counts()
    assert after["kernel"] - before["kernel"] == 2
    assert after["reference"] - before["reference"] == 1


@pytest.mark.parametrize("axes,b,h,kvh,replicated", [
    ({"fsdp": 2, "tp": 2}, 4, 4, 2, False),  # batch and heads both split
    ({"fsdp": 4}, 4, 4, 1, False),           # the LoRA recipe's mesh, MQA
    ({"dp": 1, "tp": 4}, 4, 4, 1, False),    # MQA: one KV head, shared
    ({"dp": 1, "tp": 4}, 4, 8, 2, True),     # ratio breaks groups: whole
    ({"fsdp": 4}, 2, 4, 1, True),            # batch 2 over 4: whole
])
def test_kernel_under_mesh_matches_reference(axes, b, h, kvh, replicated):
    """A Mosaic kernel is not partitioned by the compiler: under an
    ambient mesh the model's entry point wraps it in a shard_map over
    the batch and heads axes, forward and backward. An axis that does
    not divide is left out, and counted: every device of it then
    computes the whole dimension."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.parallel import mesh_attention
    mesh = mesh_lib.make_mesh(axes, devices=jax.devices()[:4])
    rules = mesh_lib.DEFAULT_RULES
    q, k, v = _make_qkv(jax.random.key(5), b=b, s=128, h=h, kvh=kvh)

    def loss(impl):
        def f(q, k, v):
            with mesh_lib.use_mesh(mesh, rules):
                return jnp.sum(mesh_attention.attention_from_context(
                    q, k, v, causal=True, impl=impl) ** 2)
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2)))

    before = attention_ops.trace_counts()
    (lk, gk), (lr, gr) = loss("pallas")(q, k, v), loss("reference")(q, k, v)
    after = attention_ops.trace_counts()
    assert after["kernel"] > before["kernel"]
    assert (after["kernel_replicated"] >
            before["kernel_replicated"]) == replicated
    np.testing.assert_allclose(float(lk), float(lr), rtol=2e-3)
    for a, b in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-3)
