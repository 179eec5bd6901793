"""Decode-step latent attention (MLA, absorbed form) over a paged pool,
read where it lies.

The decode step of models/deepseek.py attends with ``W_UK`` absorbed
into the query: every head scores the cached LATENT rows (``c_kv``, 512
wide) and the roped key all heads share (``k_r``, 64 wide), and the
output is a weighted sum of the latent rows that ``W_UV`` takes
afterwards. This kernel walks the list of blocks the step needs
(:func:`paged_attention.step_reads`: each decoding slot's blocks from
its first to the one that holds its query, ``FOLD`` an entry), copies
both leaves of each block out of the pool in HBM with DMAs of its own, a
few entries in flight, and folds an entry into the slot's running
softmax over all heads at once. A slot that does not decode has no entry:
nothing of it is read and its output is zeros.

The pool's layout is ``deepseek.init_paged_cache``'s and is not
changed: ``c_kv`` (layers, blocks, block_tokens, kv_lora_rank) and
``k_r`` the roped key's PLANAR bytes (layers, blocks, block_tokens,
itemsize * rope): every value's low byte, then every value's next one.
The kernel rebuilds the key from the bytes it copied: lane ``i`` of the
row and lane ``i + rope`` (rolled onto it) are a value's two bytes, and a
bfloat16 is the upper half of the float32 with the same bits. The lanes
past ``rope`` hold no value and are zeroed; the queries' rope part comes
padded with zeros to the leaf's width.

Precision is ``deepseek._attend``'s absorbed form: queries and latent
rows in the pool's dtype, scores, maxima, sums and the accumulator in
float32, the probabilities cast to the rows' dtype for their product
(what the TPU's one bf16 pass does to the loop's float32 operands); the
softmax over blocks is the online one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skypilot_tpu.ops.pallas import flash_attention
from skypilot_tpu.ops.pallas.paged_attention import FOLD, Reads

F32 = jnp.float32
_LANES = 128
_MASKED = -1e30
# Entries in flight or in use: the one being folded and the next ones'
# copies.
_BUFFERS = 3


def _roped_key(raw: jax.Array, dtype) -> jax.Array:
    """(keys, size * rope) planar bytes -> (keys, size * rope) keys of
    ``dtype``: lane ``i < rope`` the value whose bytes lie at lanes
    ``i, i + rope, ...``, the other lanes 0."""
    size = jnp.dtype(dtype).itemsize
    lanes = raw.shape[-1]
    rope = lanes // size
    x = raw.astype(jnp.int32)
    word = x
    for k in range(1, size):
        word = word | (pltpu.roll(x, lanes - k * rope, 1) << (8 * k))
    # The value's bits at the top of a 32-bit word: a float32 equal to
    # the bfloat16 (or the float32 itself).
    value = pltpu.bitcast(word << (8 * (4 - size)), F32)
    lane = lax.broadcasted_iota(jnp.int32, raw.shape, 1)
    return jnp.where(lane < rope, value, 0.0).astype(dtype)


def _kernel(layer_ref, total_ref, slot_ref, phys_ref, kbase_ref, lo_ref,
            hi_ref, qc_ref, qr_ref, c_hbm, k_hbm, o_ref, c_buf, k_buf,
            sems, m_ref, l_ref, acc_ref, *, scale: float):
    """The whole list in one loop: entry ``n`` waits for its blocks,
    starts the copies of entry ``n + buffers - 1`` and folds its keys
    into its slot's running maximum, sum and (heads, latent)
    accumulator; a slot's first entry starts those anew (a select: what
    the scratch holds before the first slot is anything) and its last
    one writes the slot's output."""
    layer, total = layer_ref[0], total_ref[0]
    buffers, keys, _ = c_buf.shape
    bt = keys // FOLD
    dtype = qc_ref.dtype
    o_ref[...] = jnp.zeros_like(o_ref)
    # A copy moves bits: the latent rows travel as unsigned integers of
    # their width and are values again where they are multiplied.
    c_bits = c_hbm.bitcast(c_buf.dtype)

    def copies(n, buf):
        out = []
        for g in range(FOLD):
            block = phys_ref[n * FOLD + g]
            rows = (buf, pl.ds(g * bt, bt))
            out += [pltpu.make_async_copy(c_bits.at[layer, block],
                                          c_buf.at[rows], sems.at[0, buf]),
                    pltpu.make_async_copy(k_hbm.at[layer, block],
                                          k_buf.at[rows], sems.at[1, buf])]
        return out

    def start(n):
        @pl.when(n < total)
        def _():
            for copy in copies(n, lax.rem(n, buffers)):
                copy.start()

    for n in range(buffers - 1):
        start(n)

    def fold(n, _):
        start(n + buffers - 1)
        buf = lax.rem(n, buffers)
        b = slot_ref[n]
        first = (n == 0) | (slot_ref[jnp.maximum(n - 1, 0)] != b)
        last = (n + 1 == total) | (
            slot_ref[jnp.minimum(n + 1, slot_ref.shape[0] - 1)] != b)
        kpos = kbase_ref[n] + lax.broadcasted_iota(jnp.int32, (1, keys), 1)
        seen = (kpos >= lo_ref[b]) & (kpos <= hi_ref[b])
        m_old = jnp.where(first, _MASKED, m_ref[...])
        for copy in copies(n, buf):
            copy.wait()
        latent = pltpu.bitcast(c_buf[buf], dtype)
        nt = (((1,), (1,)), ((), ()))
        s = (lax.dot_general(qc_ref[b], latent, nt,
                             preferred_element_type=F32)
             + lax.dot_general(qr_ref[b], _roped_key(k_buf[buf], dtype),
                               nt, preferred_element_type=F32)) * scale
        s = jnp.where(seen, s, _MASKED)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=1, keepdims=True))
        e = jnp.where(seen, jnp.exp(s - m_new[:, :1]), 0.0)
        alpha = jnp.exp(m_old - m_new)
        m_ref[...] = m_new
        l_ref[...] = (alpha * jnp.where(first, 0.0, l_ref[...])
                      + jnp.sum(e, axis=1, keepdims=True))
        acc_ref[...] = (
            alpha[:, :1] * jnp.where(first, 0.0, acc_ref[...])
            + lax.dot_general(e.astype(dtype), latent,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=F32))

        @pl.when(last)
        def _():
            den = jnp.maximum(l_ref[...], 1e-30)
            o_ref[b] = (acc_ref[...] / den[:, :1]).astype(o_ref.dtype)

    lax.fori_loop(0, total, fold, None)


def attend(q_latent: jax.Array, q_rope: jax.Array, pool_c: jax.Array,
           pool_k: jax.Array, layer, reads: Reads,
           scale: float) -> jax.Array:
    """Absorbed latent attention of one query a slot: ``q_latent`` (B,
    heads, kv_lora_rank), ``q_nope`` times ``W_UK``, and ``q_rope`` (B,
    heads, rope), both in the pool's dtype, against layer ``layer`` of
    ``pool_c`` (layers, blocks, block_tokens, kv_lora_rank) and
    ``pool_k`` (layers, blocks, block_tokens, itemsize * rope: planar
    bytes), the blocks and the visible positions as ``reads`` names
    them. Returns (B, heads, kv_lora_rank) in the queries' dtype: the
    softmax-weighted latent rows ahead of ``W_UV``, zeros for a slot with
    nothing to read. The pool stays where it is: the kernel copies
    single blocks out of it."""
    b, heads, rank = q_latent.shape
    bt, lanes = pool_c.shape[2], pool_k.shape[3]
    dtype = pool_c.dtype
    q_latent = q_latent.astype(dtype)
    # The rope part over the key leaf's lanes, zeros past ``rope``: the
    # rebuilt key is zero there too.
    q_rope = jnp.pad(q_rope.astype(dtype),
                     ((0, 0), (0, 0), (0, lanes - q_rope.shape[-1])))
    keys = FOLD * bt
    bits = jnp.dtype(f"uint{8 * jnp.dtype(dtype).itemsize}")
    item = jnp.dtype(dtype).itemsize
    scratch = [pltpu.VMEM((_BUFFERS, keys, rank), bits),
               pltpu.VMEM((_BUFFERS, keys, lanes), pool_k.dtype),
               pltpu.SemaphoreType.DMA((2, _BUFFERS)),
               pltpu.VMEM((heads, _LANES), F32),
               pltpu.VMEM((heads, _LANES), F32),
               pltpu.VMEM((heads, rank), F32)]
    # Queries and output lie whole in VMEM (64 slots: 8 + 2 + 8 MiB at
    # DeepSeek-V3's widths), more than the 16 MiB a kernel gets by
    # default: the limit is what they and the scratch take, and room
    # for the body's tiles.
    resident = (2 * b * heads * rank + b * heads * lanes) * item
    buffers = _BUFFERS * keys * (rank * item + lanes) \
        + heads * (2 * _LANES + rank) * 4
    vmem = resident + buffers + 8 * 2 ** 20
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=scratch),
        out_shape=jax.ShapeDtypeStruct((b, heads, rank), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(vmem, 16 * 2 ** 20)),
        interpret=flash_attention._interpret(),
        name="stpu_paged_mla_attn",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *reads, q_latent,
      q_rope, pool_c, pool_k)
