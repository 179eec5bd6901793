"""Blockwise (flash) attention as Pallas TPU kernels, fwd and bwd.

Forward: online-softmax with the KV loop as a *grid dimension* — each
step stages one (block_k, d) tile into VMEM and carries (m, l, acc) in
VMEM scratch, so the working set is O(block) regardless of sequence
length (64k+ sequences compile; an in-kernel full-K load would blow VMEM
past ~8k). Logits never touch HBM.

Backward: two kernels from the saved (q, k, v, o, lse) — a dq kernel
gridded (batch, head, q_block, kv_block) and a dk/dv kernel gridded
(batch, kv_block, head, q_block), the flash-attention-2 split so each
output block has a single writer. delta = rowsum(do*o) is recomputed
in-kernel. Causal runs skip fully-masked block pairs via predicated
compute on the grid.

GQA (n_heads % n_kv_heads == 0): the dk/dv kernel orders the grid so one
KV head's query-head group and all q blocks are consecutive steps; the
group-sum accumulates in VMEM scratch and writes (B, KVH, S, D) once —
no per-query-head gradient reaches HBM.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skypilot_tpu.ops import attention as attention_ops

_NEG_INF = -1e30
# Softmax runs in the exp2 domain: log2(e) is folded into the logit
# scale once, so every per-element transcendental is exp2 (cheaper on
# the VPU than exp) and the lse carries base-2 values end-to-end
# (fwd and bwd agree; nothing outside the kernel pair reads lse).
_LOG2E = 1.4426950408889634
# Measured on v5e (16L, GQA 16/8, d=128, seq 8k): 1024x1024 blocks run
# fwd+bwd 2.7x faster than 256x256 — the streamed grid's per-step cost
# dominates at small blocks. 2048-wide q blocks blow VMEM (scores are
# block_q x block_k f32).
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024

LSE_PAD = 8    # trailing tile dim for the lse output (tiling constraint)
_STAT = 128    # lane width for the (m, l) scratch carries


def _interpret() -> bool:
    """Pallas interpret mode: only where the process runs on the CPU
    platform (the tests). One function, so a test that compiles the
    kernels for a described chip steers exactly this."""
    return jax.default_backend() == "cpu"


def _causal_mask(s, q_start, k_start):
    bq, bk = s.shape
    qpos = q_start + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k_start + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(qpos >= kpos, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale: float, causal: bool):
    # Blocks: q/o (bq, d); k/v (bk, d); lse (bq, LSE_PAD).
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # Causal: a KV block right of the Q block's last row contributes
    # nothing — skip its compute (the fetch already happened).
    run = (k_start <= q_start + bq - 1) if causal else True

    @pl.when(run)
    def _step():
        # MXU dots take bf16 INPUTS (f32 accumulate via
        # preferred_element_type): casting inputs to f32 first would run
        # the matmuls at the fp32 rate, ~4x below bf16 peak on v5e.
        # Scale applies after the dot, in f32.
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if causal:
            s = _causal_mask(s, q_start, k_start)
        m_prev = m_scr[...][:, 0:1]
        l_prev = l_scr[...][:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_scr[...][:, 0:1], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[...] = jnp.broadcast_to(
            m_scr[...][:, 0:1] + jnp.log(l), lse_ref.shape)


def _flash_fwd_streamed(q: jax.Array, k: jax.Array, v: jax.Array, *,
               causal: bool, scale: float,
               block_q: int, block_k: int,
               keep_lse_pad: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    b, s, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    grid = (b, h, s // block_q, s // block_k)

    # Kernel operates in (B, H, S, D) layout so the last two dims of every
    # block are MXU/VPU-tileable (S and D); XLA fuses the transposes into
    # the surrounding projections.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    # Causal DMA elision: a KV block fully right of the Q block is
    # skipped by the kernel's pl.when — clamping its index to the causal
    # bound makes the "fetch" re-reference the previous block, which
    # Pallas elides (same index => no copy), so masked grid steps cost
    # neither compute nor HBM traffic.
    if causal:
        def _kv_idx(bi, hi, qi, ki):
            bound = (qi * block_q + block_q - 1) // block_k
            return (bi, hi // groups, jnp.minimum(ki, bound), 0)
    else:
        def _kv_idx(bi, hi, qi, ki):
            return (bi, hi // groups, ki, 0)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_k, d), _kv_idx),
            pl.BlockSpec((None, None, block_k, d), _kv_idx),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, LSE_PAD),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, s, LSE_PAD), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _STAT), jnp.float32),
            pltpu.VMEM((block_q, _STAT), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret(),
        name="stpu_flash_fwd",
    )(qt, kt, vt)
    # keep_lse_pad: the (B,H,S,LSE_PAD) layout feeds the bwd kernels
    # directly (already lane-tileable); [..., 0] is the logical value.
    return out.transpose(0, 2, 1, 3), (lse if keep_lse_pad
                                       else lse[..., 0])


def _dq_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
               dq_scr, delta_scr, *, scale: float, causal: bool):
    # Blocks: q/o/do/dq (bq, d); k/v (bk, d); lse (bq, LSE_PAD).
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    q_start = qi * bq
    k_start = ki * bk

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        # delta depends only on the q block — compute once, not per
        # KV step (nk can be 256+ on the long-context path).
        do = do_ref[...].astype(jnp.float32)
        o = o_ref[...].astype(jnp.float32)
        delta_scr[...] = jnp.broadcast_to(
            jnp.sum(do * o, axis=-1, keepdims=True), delta_scr.shape)

    run = (k_start <= q_start + bq - 1) if causal else True

    @pl.when(run)
    def _step():
        # bf16 MXU inputs, f32 accumulate (see _fwd_kernel).
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...][:, 0:1]
        delta = delta_scr[...][:, 0:1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if causal:
            s = _causal_mask(s, q_start, k_start)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale: float,
                causal: bool, groups: int):
    # Grid (batch, kv_block, head, q_block): for one KV-head group the
    # `groups * nq` innermost steps hit the same (bi, hi//groups, ki)
    # output block; dk/dv accumulate in scratch (the GQA group-sum) and
    # write once at the group's final step.
    ki, hi, qi = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    q_start = qi * bq
    k_start = ki * bk

    first = jnp.logical_and(hi % groups == 0, qi == 0)

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    run = (q_start + bq - 1 >= k_start) if causal else True

    @pl.when(run)
    def _step():
        # bf16 MXU inputs, f32 accumulate (see _fwd_kernel).
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        o = o_ref[...].astype(jnp.float32)
        lse = lse_ref[...][:, 0:1]
        delta = jnp.sum(do.astype(jnp.float32) * o, axis=-1,
                        keepdims=True)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if causal:
            s = _causal_mask(s, q_start, k_start)
        p = jnp.exp(s - lse)
        # dv += p^T @ do ; dk += ds^T @ q (scale folded in at _finish)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    last = jnp.logical_and(hi % groups == groups - 1, qi == nq - 1)

    @pl.when(last)
    def _finish():
        dk_ref[...] = (dk_scr[...] * scale).astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_streamed(res, do, *, causal: bool, scale: float,
               block_q: int, block_k: int):
    q, k, v, o, lse_pad = res
    b, s, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq, nk = s // block_q, s // block_k
    interpret = _interpret()

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    ot = o.transpose(0, 2, 1, 3)
    dot_ = do.transpose(0, 2, 1, 3)

    qspec = pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0))
    if causal:
        # Same DMA elision as the forward: skipped KV blocks re-fetch
        # the previous index (no copy) instead of staging dead data.
        def _kv_idx(bi, hi, qi, ki):
            bound = (qi * block_q + block_q - 1) // block_k
            return (bi, hi // groups, jnp.minimum(ki, bound), 0)
        kvspec = pl.BlockSpec((None, None, block_k, d), _kv_idx)
    else:
        kvspec = pl.BlockSpec(
            (None, None, block_k, d),
            lambda bi, hi, qi, ki: (bi, hi // groups, ki, 0))
    lse_q = pl.BlockSpec((None, None, block_q, LSE_PAD),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0))

    dqt = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal),
        grid=(b, h, nq, nk),
        in_specs=[qspec, kvspec, kvspec, qspec, qspec, lse_q],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, _STAT), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="stpu_flash_dq",
    )(qt, kt, vt, ot, dot_, lse_pad)

    # Grid (batch, kv_block, head, q_block): head × q_block innermost so
    # one KV head's whole group accumulates into the resident output.
    if causal:
        # Mirror-image elision: Q blocks BEFORE the KV block are masked;
        # clamp from below so they re-fetch instead of staging dead
        # data. One index fn serves q/o/do AND lse so their blocks can
        # never desynchronize.
        def _q_idx(bi, ki, hi, qi):
            lo = (ki * block_k) // block_q
            return (bi, hi, jnp.maximum(qi, lo), 0)
    else:
        def _q_idx(bi, ki, hi, qi):
            return (bi, hi, qi, 0)
    q_h = pl.BlockSpec((None, None, block_q, d), _q_idx)
    kv_h = pl.BlockSpec((None, None, block_k, d),
                        lambda bi, ki, hi, qi: (bi, hi // groups, ki, 0))
    lse_h = pl.BlockSpec((None, None, block_q, LSE_PAD), _q_idx)
    dkt, dvt = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          groups=groups),
        grid=(b, nk, h, nq),
        in_specs=[q_h, kv_h, kv_h, q_h, q_h, lse_h],
        out_specs=[kv_h, kv_h],
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, s, d), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
        name="stpu_flash_dkv",
    )(qt, kt, vt, ot, dot_, lse_pad)

    dq = dqt.transpose(0, 2, 1, 3)
    dk = dkt.transpose(0, 2, 1, 3)
    dv = dvt.transpose(0, 2, 1, 3)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)



# --------------------------------------------------------------------------
# Triangular-grid causal family (streamed): the (q_block, kv_block) pairs
# above the causal diagonal are NEVER SCHEDULED — the grid's last dim
# enumerates only the lower-triangle pairs, with the (qi, ki) coordinates
# delivered through scalar prefetch (splash-attention style). Two wins
# over predicating a rectangular grid: masked pairs cost zero grid steps,
# and interior (fully-unmasked) pairs skip the iota/compare/select mask
# entirely — only diagonal-straddling blocks pay it.
# --------------------------------------------------------------------------


def _tri_maps_row(nq: int, nk: int, block_q: int, block_k: int):
    """Row-major (qi, ki) pairs with any unmasked element:
    k_start <= q_start + block_q - 1."""
    qs, ks = [], []
    for qi in range(nq):
        bound = min(nk - 1, (qi * block_q + block_q - 1) // block_k)
        for ki in range(bound + 1):
            qs.append(qi)
            ks.append(ki)
    return (np.asarray(qs, np.int32), np.asarray(ks, np.int32))


def _tri_maps_col(nq: int, nk: int, block_q: int, block_k: int,
                  n_heads: int):
    """Column-major (ki, hi, qi) triples for the dk/dv kernel: for each
    KV block, every query head's unmasked q blocks are consecutive so
    the GQA group-sum accumulates in resident scratch."""
    kks, hhs, qqs = [], [], []
    for ki in range(nk):
        lo = (ki * block_k) // block_q
        for hi in range(n_heads):
            for qi in range(lo, nq):
                kks.append(ki)
                hhs.append(hi)
                qqs.append(qi)
    return (np.asarray(kks, np.int32), np.asarray(hhs, np.int32),
            np.asarray(qqs, np.int32))


def _fwd_kernel_tri(qmap, kmap, q_ref, k_ref, v_ref, o_ref, lse_ref,
                    m_scr, l_scr, acc_scr, *, scale: float,
                    block_q: int, block_k: int, nk: int):
    t = pl.program_id(2)
    qi = qmap[t]
    ki = kmap[t]
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    q_start = qi * bq
    k_start = ki * bk
    bound = jnp.minimum(nk - 1, lax.div(q_start + bq - 1, block_k))

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _step(masked: bool):
        # bf16 MXU inputs, f32 accumulate (preferred_element_type).
        # q arrives PRE-SCALED by scale*log2e (folded in outside the
        # kernel): the per-element s*scale pass over the (bq, bk) score
        # tile — a full VPU/VMEM sweep per grid step — disappears.
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            s = _causal_mask(s, q_start, k_start)
        m_prev = m_scr[...][:, 0:1]
        l_prev = l_scr[...][:, 0:1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        alpha = jnp.exp2(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    # Only a block straddling the diagonal needs the mask; interior
    # blocks (k_end - 1 <= q_start) skip the iota/compare/select.
    diag = k_start + bk - 1 > q_start

    @pl.when(diag)
    def _():
        _step(masked=True)

    @pl.when(jnp.logical_not(diag))
    def _():
        _step(masked=False)

    @pl.when(ki == bound)
    def _finish():
        l = jnp.maximum(l_scr[...][:, 0:1], 1e-30)
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        # lse in BASE-2 domain (matches the exp2 softmax above; the bwd
        # kernels below consume the same convention).
        lse_ref[...] = jnp.broadcast_to(
            m_scr[...][:, 0:1] + jnp.log2(l), lse_ref.shape)


def _flash_fwd_tri(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   scale: float, block_q: int, block_k: int,
                   keep_lse_pad: bool = False
                   ) -> Tuple[jax.Array, jax.Array]:
    b, s, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq, nk = s // block_q, s // block_k
    qmap, kmap = _tri_maps_row(nq, nk, block_q, block_k)

    # Logit scale (and the exp2-domain log2e) folded into q ONCE here —
    # XLA fuses the scalar mul into the transpose — instead of a
    # per-step elementwise pass over every (bq, bk) score tile.
    qt = (q * (scale * _LOG2E)).astype(q.dtype).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, h, len(qmap))

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_tri, scale=scale, block_q=block_q,
                          block_k=block_k, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((None, None, block_q, d),
                             lambda bi, hi, t, qm, km: (bi, hi, qm[t], 0)),
                pl.BlockSpec(
                    (None, None, block_k, d),
                    lambda bi, hi, t, qm, km: (bi, hi // groups,
                                               km[t], 0)),
                pl.BlockSpec(
                    (None, None, block_k, d),
                    lambda bi, hi, t, qm, km: (bi, hi // groups,
                                               km[t], 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, None, block_q, d),
                             lambda bi, hi, t, qm, km: (bi, hi, qm[t], 0)),
                pl.BlockSpec((None, None, block_q, LSE_PAD),
                             lambda bi, hi, t, qm, km: (bi, hi, qm[t], 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, _STAT), jnp.float32),
                pltpu.VMEM((block_q, _STAT), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, s, LSE_PAD), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="stpu_flash_fwd_tri",
    )(jnp.asarray(qmap), jnp.asarray(kmap), qt, kt, vt)
    return out.transpose(0, 2, 1, 3), (lse if keep_lse_pad
                                       else lse[..., 0])


def _dq_kernel_tri(qmap, kmap, q_ref, k_ref, v_ref, o_ref, do_ref,
                   lse_ref, dq_ref, dq_scr, delta_scr, *, scale: float,
                   block_q: int, block_k: int, nk: int):
    t = pl.program_id(2)
    qi = qmap[t]
    ki = kmap[t]
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    q_start = qi * bq
    k_start = ki * bk
    bound = jnp.minimum(nk - 1, lax.div(q_start + bq - 1, block_k))

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)
        do = do_ref[...].astype(jnp.float32)
        o = o_ref[...].astype(jnp.float32)
        delta_scr[...] = jnp.broadcast_to(
            jnp.sum(do * o, axis=-1, keepdims=True), delta_scr.shape)

    def _step(masked: bool):
        # q arrives pre-scaled by scale*log2e (see _flash_bwd_tri).
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        lse = lse_ref[...][:, 0:1]
        delta = delta_scr[...][:, 0:1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            s = _causal_mask(s, q_start, k_start)
        p = jnp.exp2(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dq_scr[...] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    diag = k_start + bk - 1 > q_start

    @pl.when(diag)
    def _():
        _step(masked=True)

    @pl.when(jnp.logical_not(diag))
    def _():
        _step(masked=False)

    @pl.when(ki == bound)
    def _finish():
        dq_ref[...] = (dq_scr[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel_tri(kmap, hmap, qmap, q_ref, k_ref, v_ref, o_ref,
                    do_ref, lse_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    scale: float, block_q: int, block_k: int, nq: int,
                    groups: int):
    t = pl.program_id(1)
    ki = kmap[t]
    hi = hmap[t]
    qi = qmap[t]
    bq, d = q_ref.shape
    bk = k_ref.shape[0]
    q_start = qi * bq
    k_start = ki * bk
    lo = lax.div(k_start, block_q)

    first = jnp.logical_and(hi % groups == 0, qi == lo)

    @pl.when(first)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _step(masked: bool):
        # q arrives pre-scaled by c = scale*log2e; dk accumulates
        # ds^T @ (c*q), so _finish divides the c back out and applies
        # the true logit scale in one constant.
        q = q_ref[...]
        k = k_ref[...]
        v = v_ref[...]
        do = do_ref[...]
        o = o_ref[...].astype(jnp.float32)
        lse = lse_ref[...][:, 0:1]
        delta = jnp.sum(do.astype(jnp.float32) * o, axis=-1,
                        keepdims=True)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if masked:
            s = _causal_mask(s, q_start, k_start)
        p = jnp.exp2(s - lse)
        dv_scr[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[...] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    # Mask needed while the q block's first row precedes the KV block's
    # last column.
    diag = q_start < k_start + bk - 1

    @pl.when(diag)
    def _():
        _step(masked=True)

    @pl.when(jnp.logical_not(diag))
    def _():
        _step(masked=False)

    last = jnp.logical_and(hi % groups == groups - 1, qi == nq - 1)

    @pl.when(last)
    def _finish():
        # scale / (scale*log2e) = 1/log2e: undo the q pre-scale, apply
        # the logit scale.
        dk_ref[...] = (dk_scr[...] * (1.0 / _LOG2E)).astype(
            dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd_tri(res, do, *, scale: float, block_q: int,
                   block_k: int):
    q, k, v, o, lse_pad = res
    b, s, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    nq, nk = s // block_q, s // block_k
    interpret = _interpret()

    # Same q pre-scale as the tri forward (kills the per-step s*scale
    # pass); the dkv kernel's _finish divides the factor back out of dk.
    qt = (q * (scale * _LOG2E)).astype(q.dtype).transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    ot = o.transpose(0, 2, 1, 3)
    dot_ = do.transpose(0, 2, 1, 3)

    qmap, kmap = _tri_maps_row(nq, nk, block_q, block_k)
    qspec = pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, t, qm, km: (bi, hi, qm[t], 0))
    kvspec = pl.BlockSpec(
        (None, None, block_k, d),
        lambda bi, hi, t, qm, km: (bi, hi // groups, km[t], 0))
    lse_q = pl.BlockSpec((None, None, block_q, LSE_PAD),
                         lambda bi, hi, t, qm, km: (bi, hi, qm[t], 0))

    dqt = pl.pallas_call(
        functools.partial(_dq_kernel_tri, scale=scale, block_q=block_q,
                          block_k=block_k, nk=nk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h, len(qmap)),
            in_specs=[qspec, kvspec, kvspec, qspec, qspec, lse_q],
            out_specs=qspec,
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                            pltpu.VMEM((block_q, _STAT), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="stpu_flash_dq_tri",
    )(jnp.asarray(qmap), jnp.asarray(kmap), qt, kt, vt, ot, dot_,
      lse_pad)

    kmap3, hmap3, qmap3 = _tri_maps_col(nq, nk, block_q, block_k, h)
    q_h = pl.BlockSpec(
        (None, None, block_q, d),
        lambda bi, t, km, hm, qm: (bi, hm[t], qm[t], 0))
    kv_h = pl.BlockSpec(
        (None, None, block_k, d),
        lambda bi, t, km, hm, qm: (bi, hm[t] // groups, km[t], 0))
    lse_h = pl.BlockSpec(
        (None, None, block_q, LSE_PAD),
        lambda bi, t, km, hm, qm: (bi, hm[t], qm[t], 0))
    dkt, dvt = pl.pallas_call(
        functools.partial(_dkv_kernel_tri, scale=scale, block_q=block_q,
                          block_k=block_k, nq=nq, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, len(kmap3)),
            in_specs=[q_h, kv_h, kv_h, q_h, q_h, lse_h],
            out_specs=[kv_h, kv_h],
            scratch_shapes=[
                pltpu.VMEM((block_k, d), jnp.float32),
                pltpu.VMEM((block_k, d), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, s, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="stpu_flash_dkv_tri",
    )(jnp.asarray(kmap3), jnp.asarray(hmap3), jnp.asarray(qmap3),
      qt, kt, vt, ot, dot_, lse_pad)

    dq = dqt.transpose(0, 2, 1, 3)
    dk = dkt.transpose(0, 2, 1, 3)
    dv = dvt.transpose(0, 2, 1, 3)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


# --------------------------------------------------------------------------
# Resident-KV kernel family: K/V (fwd, dq) and Q/O/dO (dkv) are staged into
# VMEM once per head and reused across the in-kernel block loop — fastest
# for short/medium sequences, but the full-sequence staging caps length.
# The streamed family above keeps O(block) VMEM and scales to 64k+.
# --------------------------------------------------------------------------

# Scoped-VMEM limit for the resident kernels. At the top of the resident
# range (S 2048, D 256) the dk/dv kernel stages 16.6 MiB, which the
# v5e compiler refuses under its 16 MiB default once the batch is 3 or
# more; the chip has 128 MiB.
_RESIDENT_VMEM_LIMIT = 32 * 1024 * 1024


def _fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale: float, block_k: int, causal: bool, seq_len: int):
    # Refs are rank-reduced by the None dims in the BlockSpecs:
    # q_ref/o_ref: (block_q, d); k_ref/v_ref: (seq_len, d);
    # lse_ref: (block_q, LSE_PAD)
    qi = pl.program_id(2)
    q = q_ref[...]                              # (bq, D), bf16 into MXU
    bq, d = q.shape
    q_start = qi * bq

    if causal:
        # Only KV blocks at or before the end of this Q block contribute.
        n_blocks = lax.div(q_start + bq + block_k - 1, block_k)
    else:
        n_blocks = seq_len // block_k

    def body(j, carry):
        m, l, acc = carry
        # bf16 MXU inputs, f32 accumulate; scale after the dot (casting
        # inputs to f32 would run the matmuls at the fp32 rate, ~4x
        # below bf16 peak).
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if causal:
            qpos = q_start + lax.broadcasted_iota(jnp.int32, (bq, block_k), 0)
            kpos = j * block_k + lax.broadcasted_iota(jnp.int32,
                                                      (bq, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    m0 = jnp.full((bq, 1), _NEG_INF, dtype=jnp.float32)
    l0 = jnp.zeros((bq, 1), dtype=jnp.float32)
    acc0 = jnp.zeros((bq, d), dtype=jnp.float32)
    m, l, acc = lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    l = jnp.maximum(l, 1e-30)
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    # lse block is (block_q, LSE_PAD): broadcast across the pad dim, which
    # exists only to satisfy the (8,128)-ish tiling constraint on outputs.
    lse_ref[...] = jnp.broadcast_to(m + jnp.log(l),
                                    (bq, lse_ref.shape[-1]))


def _flash_fwd_resident(q: jax.Array, k: jax.Array, v: jax.Array, *,
               causal: bool, scale: float,
               block_q: int, block_k: int,
               keep_lse_pad: bool = False
               ) -> Tuple[jax.Array, jax.Array]:
    b, s, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    grid = (b, h, s // block_q)

    # Kernel operates in (B, H, S, D) layout so the last two dims of every
    # block are MXU/VPU-tileable (S and D); XLA fuses the transposes into
    # the surrounding projections.
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_resident, scale=scale, block_k=block_k,
                          causal=causal, seq_len=s),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, s, d),
                         lambda bi, hi, qi: (bi, hi // groups, 0, 0)),
            pl.BlockSpec((None, None, s, d),
                         lambda bi, hi, qi: (bi, hi // groups, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((None, None, block_q, LSE_PAD),
                         lambda bi, hi, qi: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qt.shape, q.dtype),
            jax.ShapeDtypeStruct((b, h, s, LSE_PAD), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_RESIDENT_VMEM_LIMIT),
        interpret=_interpret(),
        name="stpu_flash_fwd_resident",
    )(qt, kt, vt)
    # keep_lse_pad: the (B,H,S,LSE_PAD) layout feeds the bwd kernels
    # directly (already lane-tileable); [..., 0] is the logical value.
    return out.transpose(0, 2, 1, 3), (lse if keep_lse_pad
                                       else lse[..., 0])


def _dq_kernel_resident(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref, *,
               scale: float, block_k: int, causal: bool, seq_len: int):
    # q/o/do/dq_ref: (block_q, d); k/v_ref: (seq_len, d);
    # lse_ref: (block_q, LSE_PAD)
    qi = pl.program_id(2)
    q = q_ref[...]                                   # bf16 into MXU
    do = do_ref[...]
    o = o_ref[...].astype(jnp.float32)
    lse = lse_ref[...][:, 0:1]                       # (bq, 1)
    delta = jnp.sum(do.astype(jnp.float32) * o, axis=-1,
                    keepdims=True)                   # (bq, 1)
    bq, d = q.shape
    q_start = qi * bq
    if causal:
        n_blocks = lax.div(q_start + bq + block_k - 1, block_k)
    else:
        n_blocks = seq_len // block_k

    def body(j, dq):
        # bf16 MXU inputs, f32 accumulate; scale after the dot.
        k = k_ref[pl.ds(j * block_k, block_k), :]
        v = v_ref[pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if causal:
            qpos = q_start + lax.broadcasted_iota(jnp.int32, (bq, block_k),
                                                  0)
            kpos = j * block_k + lax.broadcasted_iota(jnp.int32,
                                                      (bq, block_k), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq0 = jnp.zeros((bq, d), dtype=jnp.float32)
    dq = lax.fori_loop(0, n_blocks, body, dq0)
    dq_ref[...] = (dq * scale).astype(dq_ref.dtype)


def _dkv_kernel_resident(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref,
                dk_ref, dv_ref, *, scale: float, block_q: int,
                causal: bool, seq_len: int, groups: int):
    # k/v/dk/dv_ref: (block_k, d); q/o/do_ref: (seq_len, d);
    # lse_ref: (seq_len, LSE_PAD). Grid is (batch, kv_block, head) with
    # head fastest, so the `groups` query heads of one KV head hit the
    # same (bi, hi // groups, ki) output block on consecutive steps and
    # the GQA group-sum happens by accumulating into the resident block
    # — no per-query-head (B,H,S,D) gradient ever reaches HBM.
    ki = pl.program_id(1)
    hi = pl.program_id(2)
    k = k_ref[...]                                   # bf16 into MXU
    v = v_ref[...]
    bk, d = k.shape
    k_start = ki * bk
    nq = seq_len // block_q
    i0 = lax.div(k_start, block_q) if causal else 0

    def body(i, carry):
        dk, dv = carry
        # bf16 MXU inputs, f32 accumulate; scale folded in after the
        # loop (dk) / after the dot (s).
        q = q_ref[pl.ds(i * block_q, block_q), :]
        do = do_ref[pl.ds(i * block_q, block_q), :]
        o = o_ref[pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[pl.ds(i * block_q, block_q), :][:, 0:1]
        delta = jnp.sum(do.astype(jnp.float32) * o, axis=-1,
                        keepdims=True)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        if causal:
            qpos = i * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, bk), 0)
            kpos = k_start + lax.broadcasted_iota(jnp.int32,
                                                  (block_q, bk), 1)
            s = jnp.where(qpos >= kpos, s, _NEG_INF)
        p = jnp.exp(s - lse)
        # dv += p^T @ do ; dk += ds^T @ q (scale applied after loop)
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk = dk + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros((bk, d), dtype=jnp.float32)
    dk, dv = lax.fori_loop(i0, nq, body, (z, z))
    dk = dk * scale

    first_in_group = hi % groups == 0

    @pl.when(first_in_group)
    def _():
        dk_ref[...] = dk
        dv_ref[...] = dv

    @pl.when(jnp.logical_not(first_in_group))
    def _():
        dk_ref[...] += dk
        dv_ref[...] += dv


def _flash_bwd_resident(res, do, *, causal: bool, scale: float,
               block_q: int, block_k: int):
    q, k, v, o, lse_pad = res
    b, s, h, d = q.shape
    kvh = k.shape[2]
    groups = h // kvh
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    interpret = _interpret()

    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    ot = o.transpose(0, 2, 1, 3)
    dot_ = do.transpose(0, 2, 1, 3)

    qspec = pl.BlockSpec((None, None, block_q, d),
                         lambda bi, hi, i: (bi, hi, i, 0))
    kv_full = pl.BlockSpec((None, None, s, d),
                           lambda bi, hi, i: (bi, hi // groups, 0, 0))
    lse_q = pl.BlockSpec((None, None, block_q, LSE_PAD),
                         lambda bi, hi, i: (bi, hi, i, 0))

    dqt = pl.pallas_call(
        functools.partial(_dq_kernel_resident, scale=scale, block_k=block_k,
                          causal=causal, seq_len=s),
        grid=(b, h, s // block_q),
        in_specs=[qspec, kv_full, kv_full, qspec, qspec, lse_q],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_RESIDENT_VMEM_LIMIT),
        interpret=interpret,
        name="stpu_flash_dq_resident",
    )(qt, kt, vt, ot, dot_, lse_pad)

    # Grid (batch, kv_block, head), head fastest: the group's heads
    # accumulate into the same resident (B,KVH,S,D) output block.
    kvspec = pl.BlockSpec((None, None, block_k, d),
                          lambda bi, i, hi: (bi, hi // groups, i, 0))
    fullq_h = pl.BlockSpec((None, None, s, d),
                           lambda bi, i, hi: (bi, hi, 0, 0))
    lse_h = pl.BlockSpec((None, None, s, LSE_PAD),
                         lambda bi, i, hi: (bi, hi, 0, 0))
    dkt, dvt = pl.pallas_call(
        functools.partial(_dkv_kernel_resident, scale=scale, block_q=block_q,
                          causal=causal, seq_len=s, groups=groups),
        grid=(b, s // block_k, h),
        in_specs=[fullq_h, kvspec, kvspec, fullq_h, fullq_h, lse_h],
        out_specs=[kvspec, kvspec],
        out_shape=[
            jax.ShapeDtypeStruct((b, kvh, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b, kvh, s, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_RESIDENT_VMEM_LIMIT),
        interpret=interpret,
        name="stpu_flash_dkv_resident",
    )(qt, kt, vt, ot, dot_, lse_pad)

    dq = dqt.transpose(0, 2, 1, 3)
    dk = dkt.transpose(0, 2, 1, 3)
    dv = dvt.transpose(0, 2, 1, 3)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)




# Streamed kernels stage 3 full-seq fp32 tensors at most in the resident
# family; past this budget Mosaic runs out of VMEM, so dispatch by size.
_RESIDENT_MAX_BYTES = 6 * 1024 * 1024


def _use_resident(s: int, d: int) -> bool:
    return 3 * s * d * 4 <= _RESIDENT_MAX_BYTES


def _flash_fwd(q, k, v, *, causal, scale, block_q, block_k,
               keep_lse_pad: bool = False):
    if _use_resident(q.shape[1], q.shape[3]):
        return _flash_fwd_resident(q, k, v, causal=causal, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   keep_lse_pad=keep_lse_pad)
    if causal:
        # Long causal sequences: triangular grid — masked block pairs
        # are never scheduled. (lse is base-2 here; the tri bwd pairs
        # with it, and family dispatch is shape-deterministic so fwd
        # and bwd always agree.)
        return _flash_fwd_tri(q, k, v, scale=scale, block_q=block_q,
                              block_k=block_k,
                              keep_lse_pad=keep_lse_pad)
    return _flash_fwd_streamed(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               keep_lse_pad=keep_lse_pad)


def _flash_bwd(res, do, *, causal, scale, block_q, block_k):
    q = res[0]
    if _use_resident(q.shape[1], q.shape[3]):
        return _flash_bwd_resident(res, do, causal=causal, scale=scale,
                                   block_q=block_q, block_k=block_k)
    if causal:
        return _flash_bwd_tri(res, do, scale=scale, block_q=block_q,
                              block_k=block_k)
    return _flash_bwd_streamed(res, do, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, causal, scale, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, causal=causal, scale=scale,
                        block_q=block_q, block_k=block_k)
    return out


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k):
    out, lse_pad = _flash_fwd(q, k, v, causal=causal, scale=scale,
                              block_q=block_q, block_k=block_k,
                              keep_lse_pad=True)
    # Named so a remat policy can pin EXACTLY the kernel's outputs:
    # jax.checkpoint_policies.save_only_these_names("flash_out",
    # "flash_lse") makes layer-remat recompute the cheap projections but
    # never re-run the quadratic kernel itself (the bwd residuals q/k/v
    # come from the recomputed projections; o/lse from here). See
    # models/llama.py remat_policy="save_flash".
    from jax.ad_checkpoint import checkpoint_name
    out = checkpoint_name(out, "flash_out")
    lse_pad = checkpoint_name(lse_pad, "flash_lse")
    # q/k/v names let a larger policy tier also skip the qkv-projection
    # recompute (models/llama.py remat_policy="save_flash_qkv").
    q = checkpoint_name(q, "flash_q")
    k = checkpoint_name(k, "flash_k")
    v = checkpoint_name(v, "flash_v")
    return out, (q, k, v, out, lse_pad)


def _flash_vjp_bwd(causal, scale, block_q, block_k, res, do):
    return _flash_bwd(res, do, causal=causal, scale=scale,
                      block_q=block_q, block_k=block_k)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K) -> jax.Array:
    """Flash attention. q: (B,S,H,D); k,v: (B,S,KVH,D)."""
    b, s, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    block_q = min(block_q, s)
    block_k = min(block_k, s)
    # Halve blocks until they divide the sequence: a seq like 1536 must
    # run the kernel at 512, not fall back to the O(S^2) reference.
    while block_q > 8 and s % block_q:
        block_q //= 2
    while block_k > 8 and s % block_k:
        block_k //= 2
    if (k.shape[1] != s or s % block_q or s % block_k or h % k.shape[2] or
            block_q % 8 or block_k % 8 or d % 8):
        # Irregular/misaligned shapes: fall back to the XLA reference path
        # (Mosaic requires 8-sublane-aligned blocks).
        attention_ops.TRACES.labels(impl="reference").inc()
        return attention_ops._reference_attention(q, k, v, causal=causal,
                                                  scale=scale)
    attention_ops.TRACES.labels(impl="kernel").inc()
    return _flash(q, k, v, causal, scale, block_q, block_k)
