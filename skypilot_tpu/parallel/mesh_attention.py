"""The attention kernel under a mesh.

The compiler does not partition a Mosaic kernel ("Mosaic kernels cannot
be automatically partitioned. Please wrap the call in a shard_map"): it
compiles only where every axis of the mesh is manual. So under a
multi-device mesh the kernel runs inside a `jax.shard_map` that splits
the batch over the rule table's batch axes and the heads over its heads
axis, sequence and head_dim whole.

Inside a region that is already manual over some axes — a pipeline
stage is, over `pp` (parallel/pipeline.py) — the shard_map is nested:
it takes the context's own mesh and makes manual only the axes that are
still automatic. The XLA reference needs none of this and is called as
it is.
"""
from __future__ import annotations

import functools
import logging
import math
from typing import Optional

import jax
from jax.sharding import PartitionSpec as P

from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.parallel import mesh as mesh_lib

logger = logging.getLogger(__name__)


def _partition(mesh, rules, free, q_shape, kv_heads: int):
    """(q_spec, kv_spec, left_out) splitting the kernel's work over the
    axes of ``mesh`` in ``free`` (the ones not manual yet).

    An axis that does not divide its dimension is left out: that
    dimension is then computed in full on every device of the axis,
    and the caller makes that visible. KV heads follow the query heads
    when they divide too; a single KV head (MQA) is shared by every
    shard; any other ratio would break the group mapping, so heads
    then stay whole."""
    def axes(logical):
        axis = rules.resolve_axis(logical, mesh)
        names = (axis,) if isinstance(axis, str) else tuple(axis or ())
        names = tuple(a for a in names if a in free and mesh.shape[a] > 1)
        return names, math.prod(mesh.shape[a] for a in names)

    b, _, h, _ = q_shape
    left_out = []
    batch, ways = axes("batch")
    if b % ways:
        left_out.append(f"batch {b} over {batch}={ways}")
        batch = ()
    heads, tp = axes("heads")
    if h % tp or (kv_heads % tp and kv_heads != 1):
        left_out.append(f"heads {h}/{kv_heads} over {heads}={tp}")
        heads = ()
    kv = heads if kv_heads % tp == 0 else ()
    return (P(batch or None, None, heads or None, None),
            P(batch or None, None, kv or None, None), left_out)


def attention_from_context(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True,
                           scale: Optional[float] = None,
                           impl: str = "auto") -> jax.Array:
    """Model-side entry point: `ops.attention.attention` when there is
    nothing to partition, else the kernel in a shard_map over the
    ambient mesh (`mesh_lib.use_mesh`; make_train_step and
    forward_pipelined install it)."""
    impl = attention_ops.resolve_impl(impl)
    op = functools.partial(attention_ops.attention, causal=causal,
                           scale=scale, impl=impl)
    pair = mesh_lib.current_mesh_rules()
    if impl != "pallas" or pair is None or pair[0].size == 1:
        return op(q, k, v)
    mesh, rules = pair
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty:
        mesh = ctx      # nested: shard_map wants the context's own mesh
    free = frozenset(mesh.axis_names) - frozenset(ctx.manual_axes)
    if not free:
        return op(q, k, v)
    q_spec, kv_spec, left_out = _partition(mesh, rules, free, q.shape,
                                           k.shape[2])
    if left_out:
        attention_ops.TRACES.labels(impl="kernel_replicated").inc()
        logger.warning(
            "attention kernel computed redundantly under mesh %s: %s "
            "does not divide", dict(mesh.shape), "; ".join(left_out))
    return jax.shard_map(op, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, axis_names=free,
                         check_vma=False)(q, k, v)
