"""Attention dispatch: Pallas flash kernel on TPU, XLA reference on the CPU.

The hot op of every transformer recipe. The Pallas kernel keeps the working
set in VMEM with online softmax (blockwise), so HBM traffic is O(S*D) instead
of O(S^2); the reference path is a plain einsum that XLA fuses well enough on
CPU for tests.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from skypilot_tpu.observability import metrics

_NEG_INF = -1e30

# Which implementation each attention call site was traced into. Two
# fallbacks cost speed and not correctness; this is what keeps them
# visible (chip_smoke's train phase and the tests read it): the shape
# fallback in flash_attention ('reference'), and a kernel under a mesh
# one of whose axes does not divide the batch or the heads, so that
# every device of that axis computes the whole dimension
# ('kernel_replicated', counted beside 'kernel' by
# parallel/mesh_attention.py). A trace served from jit's cache is not
# counted again.
TRACES = metrics.counter(
    "stpu_attention_traces_total",
    "Attention call sites traced, by the implementation compiled in: "
    "'kernel' is the Pallas flash kernel, 'reference' the O(S^2) XLA "
    "path, 'kernel_replicated' a kernel (also counted as 'kernel') "
    "that a mesh axis computes redundantly.", ("impl",))


def trace_counts() -> dict:
    return {impl: int(TRACES.labels(impl=impl).get())
            for impl in ("kernel", "reference", "kernel_replicated")}


def _reference_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool,
                         scale: Optional[float]) -> jax.Array:
    # q: (B, S, H, D); k/v: (B, S, KVH, D) with H % KVH == 0 (GQA).
    b, sq, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    scale = scale if scale is not None else d ** -0.5
    qr = q.reshape(b, sq, kvh, groups, d)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qr.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        sk = k.shape[1]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        logits = jnp.where(mask[None, None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, h, d).astype(q.dtype)


def resolve_impl(impl: str) -> str:
    """'auto' by platform only: the kernel on 'tpu', the reference on
    'cpu' (the tests); any other platform has to say which."""
    if impl != "auto":
        return impl
    platform = jax.default_backend()
    if platform not in ("tpu", "cpu"):
        raise ValueError(
            f"attention impl='auto' knows the platforms 'tpu' "
            f"(kernel) and 'cpu' (reference), not {platform!r}; "
            f"pass impl explicitly")
    return "pallas" if platform == "tpu" else "reference"


@functools.partial(jax.jit, static_argnames=("causal", "impl", "scale"))
def _attention(q, k, v, *, causal, scale, impl):
    if impl == "pallas":
        from skypilot_tpu.ops.pallas import flash_attention
        return flash_attention.flash_attention(
            q, k, v, causal=causal, scale=scale)
    TRACES.labels(impl="reference").inc()
    return _reference_attention(q, k, v, causal=causal, scale=scale)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True,
              scale: Optional[float] = None,
              impl: str = "auto") -> jax.Array:
    """Multi-head / grouped-query attention.

    Args:
      q: (batch, q_seq, n_heads, head_dim)
      k, v: (batch, kv_seq, n_kv_heads, head_dim)
      causal: apply causal mask (offset so q is the trailing window of kv).
      impl: 'auto' | 'pallas' | 'reference' (see :func:`resolve_impl`).

    The op itself knows no mesh. A Mosaic kernel is not partitioned by
    the compiler, so a model that runs under a multi-device mesh calls
    ``parallel.mesh_attention.attention_from_context`` instead.
    """
    return _attention(q, k, v, causal=causal, scale=scale,
                      impl=resolve_impl(impl))
