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
from jax.sharding import PartitionSpec as P

from skypilot_tpu.observability import metrics
from skypilot_tpu.parallel import mesh as mesh_lib

_NEG_INF = -1e30

# Which implementation each attention call site was traced into. The
# shape fallback in flash_attention is a correctness path; this is what
# keeps it visible (chip_smoke's train phase and the tests read it). A
# trace served from jit's cache is not counted again.
TRACES = metrics.counter(
    "stpu_attention_traces_total",
    "Attention call sites traced, by the implementation compiled in: "
    "'kernel' is the Pallas flash kernel, 'reference' the O(S^2) XLA "
    "path.", ("impl",))


def trace_counts() -> dict:
    return {impl: int(TRACES.labels(impl=impl).get())
            for impl in ("kernel", "reference")}


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


def _kernel_partition(mesh, rules, q_shape, kv_heads: int):
    """(q_spec, kv_spec) that split the kernel's work over ``mesh``.

    A Mosaic kernel is not partitioned by the compiler, so under a mesh
    it runs inside a shard_map: batch over the rule table's batch axes,
    heads over its heads axis, sequence and head_dim whole. An axis
    that does not divide its dimension is left out (that dimension is
    then computed in full on each device of the axis). KV heads follow
    the query heads when they divide too; a single KV head (MQA) is
    shared by every shard; any other ratio would break the group
    mapping, so heads then stay whole."""
    b, _, h, _ = q_shape
    batch = rules.resolve_axis("batch", mesh)
    if b % rules.axis_size("batch", mesh):
        batch = None
    heads = rules.resolve_axis("heads", mesh)
    tp = rules.axis_size("heads", mesh)
    if h % tp or (kv_heads % tp and kv_heads != 1):
        heads = None
    kv = heads if kv_heads % tp == 0 else None
    return P(batch, None, heads, None), P(batch, None, kv, None)


@functools.partial(jax.jit, static_argnames=("causal", "impl", "scale",
                                             "mesh", "specs"))
def _attention(q, k, v, *, causal, scale, impl, mesh, specs):
    if impl == "reference":
        TRACES.labels(impl="reference").inc()
        return _reference_attention(q, k, v, causal=causal, scale=scale)
    from skypilot_tpu.ops.pallas import flash_attention
    kernel = functools.partial(flash_attention.flash_attention,
                               causal=causal, scale=scale)
    if mesh is not None:
        q_spec, kv_spec = specs
        kernel = jax.shard_map(kernel, mesh=mesh,
                               in_specs=(q_spec, kv_spec, kv_spec),
                               out_specs=q_spec, check_vma=False)
    return kernel(q, k, v)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
              causal: bool = True,
              scale: Optional[float] = None,
              impl: str = "auto") -> jax.Array:
    """Multi-head / grouped-query attention.

    Args:
      q: (batch, q_seq, n_heads, head_dim)
      k, v: (batch, kv_seq, n_kv_heads, head_dim)
      causal: apply causal mask (offset so q is the trailing window of kv).
      impl: 'auto' | 'pallas' | 'reference'. 'auto' is the kernel on
        platform 'tpu' and the reference on 'cpu' (the tests).

    Under an ambient multi-device mesh (``mesh_lib.use_mesh``) the
    kernel runs inside a shard_map (see :func:`_kernel_partition`).
    The mesh is resolved here, outside the jit, so that it is part of
    the trace's cache key.
    """
    if impl == "auto":
        platform = jax.default_backend()
        if platform not in ("tpu", "cpu"):
            raise ValueError(
                f"attention impl='auto' knows the platforms 'tpu' "
                f"(kernel) and 'cpu' (reference), not {platform!r}; "
                f"pass impl explicitly")
        impl = "pallas" if platform == "tpu" else "reference"
    mesh = specs = None
    pair = mesh_lib.current_mesh_rules()
    if impl == "pallas" and pair is not None and pair[0].size > 1:
        mesh, rules = pair
        specs = _kernel_partition(mesh, rules, q.shape, k.shape[2])
    return _attention(q, k, v, causal=causal, scale=scale, impl=impl,
                      mesh=mesh, specs=specs)
