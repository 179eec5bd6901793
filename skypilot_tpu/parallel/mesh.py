"""Device-mesh construction and logical-axis sharding rules.

TPU-first design: parallelism is expressed as a `jax.sharding.Mesh` with
named axes plus a table of rules mapping *logical* tensor axes (batch, seq,
embed, heads, ...) onto mesh axes. XLA inserts the collectives; recipes pick
rules, not collectives.

The reference framework has no parallelism math of its own -- it only ships
the env-var scaffolding for torch DDP (reference:
sky/backends/cloud_vm_ray_backend.py:570-636). Here the mesh/rules layer IS
the native equivalent: dp/fsdp/tp/sp/ep/pp are all axis assignments over one
mesh.

Canonical mesh axes:
  dp    data parallel (pure replication of params, batch-sharded)
  fsdp  fully-sharded data parallel (batch- AND param-sharded)
  pp    pipeline stage axis
  tp    tensor (model) parallel axis; also hosts Megatron-style sequence
        parallelism of activations outside attention/mlp blocks
  sp    context/sequence parallelism for ring attention (long context)
  ep    expert parallel axis for MoE (may alias onto dp/fsdp via rules)
"""
from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any, Mapping, Optional, Sequence, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisName = Union[str, Sequence[str], None]

DP = "dp"
FSDP = "fsdp"
PP = "pp"
TP = "tp"
SP = "sp"
EP = "ep"


def _resolve_axis_sizes(axes: Mapping[str, int], n: int,
                        what: str = "device count") -> dict:
    """Resolve one optional -1 axis against `n` and validate the product
    (shared by the flat and hybrid mesh builders)."""
    sizes = dict(axes)
    unknown = [k for k, v in sizes.items() if v == -1]
    if len(unknown) > 1:
        raise ValueError(f"At most one axis may be -1, got {unknown}")
    known = math.prod(v for v in sizes.values() if v != -1)
    if unknown:
        if n % known:
            raise ValueError(
                f"{what} {n} not divisible by fixed axes {sizes}")
        sizes[unknown[0]] = n // known
    if math.prod(sizes.values()) != n:
        raise ValueError(
            f"Mesh axes {sizes} do not multiply to {what} {n}")
    return sizes


def make_mesh(axes: Mapping[str, int],
              devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """Build a Mesh with the given named axis sizes.

    Axis sizes must multiply to the device count; an axis size of -1 is
    inferred. Axis order follows insertion order of `axes`, which also
    controls physical layout: put the fastest-communicating axis (tp/sp)
    last so it lands on adjacent devices (ICI neighbors on a real slice).
    """
    if devices is None:
        devices = jax.devices()
    sizes = _resolve_axis_sizes(axes, len(devices))
    dev_array = np.asarray(devices).reshape(tuple(sizes.values()))
    return Mesh(dev_array, tuple(sizes.keys()))


def make_multislice_mesh(ici_axes: Mapping[str, int], num_slices: int,
                         dcn_axis: str = DP,
                         devices: Optional[Sequence[jax.Device]] = None
                         ) -> Mesh:
    """Hybrid DCN x ICI mesh for multi-slice (pod-to-pod) training.

    The leading ``dcn_axis`` spans slices — collectives on it ride the
    data-center network — while ``ici_axes`` live inside one slice's ICI
    domain. Standard layout: data parallelism over DCN, fsdp/tp/sp over
    ICI (the "How to Scale Your Model" recipe; the env contract's
    MEGASCALE_* variables bring up the DCN transport).

    On real multislice hardware devices carry ``slice_index`` and are
    grouped by it so the leading axis truly crosses slices; on virtual
    or single-slice platforms devices are split evenly (same program,
    simulated topology).
    """
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    if num_slices < 1 or n % num_slices:
        raise ValueError(
            f"{n} devices not divisible into {num_slices} slices")
    per_slice = n // num_slices
    sizes = _resolve_axis_sizes(ici_axes, per_slice,
                                "per-slice device count")
    if dcn_axis in sizes:
        raise ValueError(f"dcn axis {dcn_axis!r} also named in ici_axes")
    # Group by slice: real multislice devices expose slice_index, and
    # then the claimed num_slices MUST match the physical topology —
    # a silent mismatch would put the "DCN" axis inside a slice (and an
    # ICI axis across DCN), inverting the layout with no error.
    slice_ids = {getattr(d, "slice_index", None) for d in devices}
    if slice_ids != {None} and None not in slice_ids:
        counts: dict = {}
        for d in devices:
            counts[d.slice_index] = counts.get(d.slice_index, 0) + 1
        if len(counts) != num_slices or set(counts.values()) != {per_slice}:
            raise ValueError(
                f"devices span {len(counts)} physical slice(s) "
                f"{dict(sorted(counts.items()))}, but num_slices="
                f"{num_slices} x {per_slice} was requested — the DCN "
                f"axis would not align with slice boundaries.")
    # Intra-slice order follows PHYSICAL coordinates when the platform
    # exposes them: raw device ids need not walk the ICI torus, and an
    # id-ordered reshape can land a "fast" axis on non-adjacent chips
    # (correct results, degraded collective bandwidth). Virtual/CPU
    # devices have no coords and keep the id order.
    def _physical_key(d):
        coords = getattr(d, "coords", None)
        core = getattr(d, "core_on_chip", 0)
        if coords is not None:
            return (getattr(d, "slice_index", 0) or 0, tuple(coords),
                    core)
        return (getattr(d, "slice_index", 0) or 0, (),
                getattr(d, "id", 0))

    order = sorted(devices, key=_physical_key)
    dev_array = np.asarray(order).reshape(
        (num_slices,) + tuple(sizes.values()))
    return Mesh(dev_array, (dcn_axis,) + tuple(sizes.keys()))


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    """Logical-axis -> mesh-axis mapping.

    Any logical axis not listed resolves to None (replicated). A mesh axis
    named in a rule but absent from the mesh is dropped at resolution time,
    so one rule set works across meshes of different shapes (e.g. the same
    FSDP+TP rules on a ('dp','tp') mesh simply ignore 'fsdp').
    """
    rules: Mapping[str, AxisName]

    def resolve_axis(self, logical: Optional[str],
                     mesh: Mesh) -> AxisName:
        if logical is None:
            return None
        axis = self.rules.get(logical)
        if axis is None:
            return None
        names = (axis,) if isinstance(axis, str) else tuple(axis)
        present = tuple(a for a in names if a in mesh.axis_names)
        if not present:
            return None
        return present if len(present) > 1 else present[0]

    def axis_size(self, logical: Optional[str], mesh: Mesh) -> int:
        """How many ways ``logical`` is split on ``mesh`` (1 = whole)."""
        axis = self.resolve_axis(logical, mesh)
        names = (axis,) if isinstance(axis, str) else tuple(axis or ())
        return math.prod(mesh.shape[a] for a in names)

    def spec(self, logical_axes: Sequence[Optional[str]],
             mesh: Mesh) -> P:
        resolved = []
        used: set = set()
        for la in logical_axes:
            axis = self.resolve_axis(la, mesh)
            # A mesh axis can shard at most one tensor dim; later dims fall
            # back to replicated rather than erroring (matches t5x behavior).
            flat = ((axis,) if isinstance(axis, str) else
                    tuple(axis) if axis else ())
            if any(a in used for a in flat):
                axis = None
                flat = ()
            used.update(flat)
            resolved.append(axis)
        while resolved and resolved[-1] is None:
            resolved.pop()
        return P(*resolved)

    def sharding(self, logical_axes: Sequence[Optional[str]],
                 mesh: Mesh) -> NamedSharding:
        return NamedSharding(mesh, self.spec(logical_axes, mesh))


# Preset rule tables ---------------------------------------------------------

# Llama-class dense model, DP/FSDP/TP (+ megatron-SP via 'act_seq').
DEFAULT_RULES = ShardingRules(rules={
    # activations
    "batch": (DP, FSDP),
    "act_seq": SP,          # ring/context parallel shards the sequence
    "act_embed": None,
    "heads": TP,
    "kv_heads": TP,
    # params
    "embed": FSDP,
    "mlp": TP,
    "q_heads_x_dim": TP,
    "kv_heads_x_dim": TP,
    "vocab": TP,
    # MoE
    "expert": EP,
    # pipeline: leading stacked-layer axis of stage-stacked params
    "stage": PP,
    "layers": None,
})

# Pipelined runs shard the stored (L, ...) layer stack over pp so the
# in-jit reshape to (P, L/P, ...) is a purely local view change.
PIPELINE_RULES = ShardingRules(rules={**DEFAULT_RULES.rules, "layers": PP})


def resolve(rules: ShardingRules, mesh: Mesh,
            logical_axes: Sequence[Optional[str]]) -> NamedSharding:
    return rules.sharding(logical_axes, mesh)


def constrain(x: jax.Array, mesh: Mesh, rules: ShardingRules,
              logical_axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint by logical axis names."""
    return jax.lax.with_sharding_constraint(
        x, rules.sharding(logical_axes, mesh))


_AMBIENT = threading.local()


class use_mesh:
    """Context manager installing (mesh, rules) as the ambient pair.

    Trainers enter this around model forward so ops that need the concrete
    mesh at trace time (ring attention's shard_map, MoE dispatch) can find
    it without threading it through every model signature. Thread-local so
    concurrent traces for different meshes don't cross-talk.
    """

    def __init__(self, mesh: Mesh, rules: ShardingRules):
        self.pair = (mesh, rules)

    def __enter__(self):
        if not hasattr(_AMBIENT, "stack"):
            _AMBIENT.stack = []
        _AMBIENT.stack.append(self.pair)
        return self.pair

    def __exit__(self, *exc):
        _AMBIENT.stack.pop()
        return False


def current_mesh_rules() -> Optional[tuple]:
    stack = getattr(_AMBIENT, "stack", None)
    return stack[-1] if stack else None


def tree_shardings(mesh: Mesh, rules: ShardingRules,
                   specs_tree: Any) -> Any:
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda spec: rules.sharding(spec, mesh),
        specs_tree,
        is_leaf=lambda s: isinstance(s, tuple) and all(
            a is None or isinstance(a, str) for a in s))


def device_info() -> dict:
    """What this process runs on, as JAX reports it — the statement
    every result and every server makes for itself, so that a number
    from a CPU run is never read as the chip's."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def bytes_per_device(tree: Any) -> dict:
    """Device id -> bytes of ``tree`` resident on that device, from the
    leaves' addressable shards (a replicated leaf counts on each device
    that holds a copy). How the serving and training recipes show that
    a sharded model is spread over its mesh and not held by one chip."""
    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            out[shard.device.id] = (out.get(shard.device.id, 0) +
                                    shard.data.nbytes)
    return out
