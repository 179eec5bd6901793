"""Ring attention: context parallelism over the `sp` mesh axis.

Long-context capability the reference framework lacks entirely (SURVEY.md
§2.6: no sequence/context parallelism anywhere in the reference). Native
here: the sequence axis of q/k/v is sharded over `sp`; each device computes
blockwise attention of its local queries against the KV chunk it currently
holds, accumulates with online softmax, and passes KV around the ring with
`lax.ppermute` — collectives ride the ICI torus, overlap comes from XLA
scheduling the permute against the chunk matmuls.

Only the `sp` axis is manual (`jax.shard_map(..., axis_names={'sp'})`);
dp/fsdp/tp stay automatic, so the same rule table governs the rest of the
model around this op.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.parallel import mesh_attention

_NEG_INF = -1e30


# KV sub-block width inside one ring chunk: bounds the live score
# matrix to (B, H, Sl, _KV_BLOCK) regardless of per-shard length.
_KV_BLOCK = 512
# Below this block width the scan's per-step cost dominates the einsum;
# chunk lengths with no divisor >= the floor take the pad-and-mask path.
_KV_BLOCK_FLOOR = 128


def _chunk_update(q, kc, vc, qpos, kpos0, m, l, acc, *, causal, scale):
    """One online-softmax update of local queries against one KV chunk,
    BLOCKWISE over the chunk's KV axis.

    q: (B, Sl, H, D) bf16; kc/vc: (B, Sl, KVH, D) bf16; m/l:
    (B, H, Sl, 1) f32; acc: (B, H, Sl, D) f32. kpos0 is the chunk's
    absolute start position (chunk positions are contiguous).

    Two properties real context lengths need: matmuls take bf16 INPUTS
    with f32 accumulation (fp32 inputs run the MXU ~4x below peak), and
    scores exist only one (Sl x _KV_BLOCK) sub-block at a time — a full
    (Sl x Sl) chunk score matrix is gigabytes at 8k+ per shard.
    """
    b, sl, h, d = q.shape
    kvh = kc.shape[2]
    groups = h // kvh
    # Largest divisor of the chunk length <= _KV_BLOCK (any divisor,
    # not only powers of two): halving alone degenerates to 1-2-wide
    # blocks for lengths with small odd factors, wrecking the MXU.
    n = kc.shape[1]
    block = max(dv for dv in range(1, min(_KV_BLOCK, n) + 1)
                if n % dv == 0)
    if block < _KV_BLOCK_FLOOR and n > block:
        # Prime / small-odd-factor chunk lengths have no decent
        # divisor: the exact-divisor path would scan thousands of
        # 1-2-wide einsum steps (ADVICE r3 #2). Pad the chunk to a
        # multiple of _KV_BLOCK instead and mask the tail slots out of
        # the softmax below.
        block = min(_KV_BLOCK, n)
        pad = (-n) % block
        if pad:
            kc = jnp.pad(kc, ((0, 0), (0, pad), (0, 0), (0, 0)))
            vc = jnp.pad(vc, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_blocks = kc.shape[1] // block
    padded = kc.shape[1] != n
    # Grouped-query form: keep K/V at KVH heads and fold the group axis
    # into the einsum instead of materializing repeated K/V.
    qg = q.reshape(b, sl, kvh, groups, d)

    def body(carry, j):
        m, l, acc = carry
        # Slice in place: staging a blocks-leading copy of the chunk
        # would re-write (B, Sl, KVH, D) every ring step (twice with
        # the checkpoint recompute) — real HBM traffic at long context.
        kcj = lax.dynamic_slice_in_dim(kc, j * block, block, axis=1)
        vcj = lax.dynamic_slice_in_dim(vc, j * block, block, axis=1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kcj,
                       preferred_element_type=jnp.float32) * scale
        s = s.reshape(b, h, sl, block)
        idx = j * block + jnp.arange(block)
        if causal:
            kpos = kpos0 + idx
            mask = qpos[:, None] >= kpos[None, :]
            if padded:
                # Zero-padded tail slots would score s=0 and leak
                # exp(-m) weight into the softmax: mask them too.
                mask = mask & (idx < n)[None, :]
            s = jnp.where(mask[None, None], s, _NEG_INF)
        elif padded:
            s = jnp.where((idx < n)[None, None, None, :], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        # Guard fully-masked rows: exp(-inf - (-inf)) -> stable max.
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pg = p.reshape(b, kvh, groups, sl, block)
        av = jnp.einsum("bkgqs,bskd->bkgqd", pg.astype(vcj.dtype), vcj,
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha + av.reshape(b, h, sl, d)
        return (m_new, l_new, acc_new), None

    (m, l, acc), _ = lax.scan(body, (m, l, acc),
                              jnp.arange(n_blocks))
    return m, l, acc


def _ring_local(q, k, v, *, axis_name: str, causal: bool,
                scale: float, axis_size: int):
    idx = lax.axis_index(axis_name)
    b, sl, h, d = q.shape
    qpos = idx * sl + jnp.arange(sl)
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    def body(carry, step):
        m, l, acc, kc, vc = carry
        chunk_idx = (idx - step) % axis_size
        m, l, acc = _chunk_update(q, kc, vc, qpos, chunk_idx * sl,
                                  m, l, acc, causal=causal, scale=scale)
        kc = lax.ppermute(kc, axis_name, perm)
        vc = lax.ppermute(vc, axis_name, perm)
        return (m, l, acc, kc, vc), None

    m0 = jnp.full((b, h, sl, 1), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((b, h, sl, 1), dtype=jnp.float32)
    acc0 = jnp.zeros((b, h, sl, d), dtype=jnp.float32)
    body = jax.checkpoint(body, prevent_cse=False)
    (m, l, acc, _, _), _ = lax.scan(body, (m0, l0, acc0, k, v),
                                    jnp.arange(axis_size))
    out = acc / jnp.maximum(l, 1e-30)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)  # (B, Sl, H, D)


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   mesh, sp_axis: str = mesh_lib.SP,
                   causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Context-parallel causal attention.

    q: (B, S, H, D); k/v: (B, S, KVH, D), S sharded over `sp_axis`.
    Falls back to single-chunk local attention when the mesh has no sp axis.
    """
    d = q.shape[-1]
    scale = scale if scale is not None else d ** -0.5
    if sp_axis not in mesh.axis_names or mesh.shape[sp_axis] == 1:
        return mesh_attention.attention_from_context(
            q, k, v, causal=causal, scale=scale)
    axis_size = mesh.shape[sp_axis]
    spec = P(None, sp_axis, None, None)
    inner = jax.shard_map(
        functools.partial(_ring_local, axis_name=sp_axis, causal=causal,
                          scale=scale, axis_size=axis_size),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        axis_names={sp_axis},
        check_vma=False,
    )
    return inner(q, k, v)


def ring_attention_from_context(q: jax.Array, k: jax.Array,
                                v: jax.Array) -> jax.Array:
    """Model-side entrypoint: resolve the mesh from the ambient context
    installed by the trainer (`mesh_lib.use_mesh`)."""
    pair = mesh_lib.current_mesh_rules()
    if pair is None:
        raise RuntimeError(
            "attention_impl='ring' requires an ambient mesh: wrap the "
            "forward call in `with mesh_lib.use_mesh(mesh, rules): ...` "
            "(make_train_step does this automatically).")
    mesh, _ = pair
    return ring_attention(q, k, v, mesh=mesh)
