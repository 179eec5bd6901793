"""Decode-step attention over a paged pool, read where it lies.

A decode step has one query position a slot. The XLA form gathers every
slot's blocks into a dense array (to the table's end, for slots that do
not decode too) and contracts over the copy. This kernel walks ONE list
of the blocks the step needs (:func:`step_reads`: for each decoding
slot, the blocks that hold keys its query may see, ``FOLD`` consecutive
ones an entry), copies each block from the pool in HBM into VMEM with a
DMA of its own, a few entries in flight, and folds an entry into the
slot's running softmax. A slot that does not decode has no entry in the
list: nothing of it is read and its output is zeros.

The pool's layout is models/phi4flash.py's: a leaf ``(layers, blocks,
pairs, block_tokens, lanes)`` whose row ``[k1; k2]`` of a key/value pair
fills the lanes, so that differential attention is plain grouped
attention with four "heads" a pair (``phi4flash._pad_queries``); the
kernel knows nothing of the difference, which ``phi4flash._diff_out``
takes afterwards.

Precision is the one-pass form's (``phi4flash._attend``): keys, values
and queries in the pool's dtype, scores, maxima, sums and the
accumulator in float32, the probabilities cast to the values' dtype for
their product; the softmax over blocks is the online one.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skypilot_tpu.ops.pallas import flash_attention

F32 = jnp.float32
_LANES = 128
_MASKED = -1e30
# Consecutive blocks of a slot folded at once: two blocks of 64 keys
# fill the 128 lanes of a score tile and the MXU's 128 rows, which a
# block at a time leaves half empty (my chip runs, PERF.md PR 37, the
# kernel alone at 25 decoding slots, a host clock around chained calls:
# 0.60-0.62 us a block and 530-544 GB/s at 1, 0.48-0.52 and 692-706 at
# 2; at 4 the copies run no faster and a window's 9 blocks are read as
# 12). On the device's clock two blocks an entry copy 737-751 GB/s. A
# slot with an odd count reads its last entry's first block twice: the
# second copy's keys lie past ``hi``.
FOLD = 2
# Entries in flight or in use: the one being folded and the next ones'
# copies (host clock; 2: 665-681 GB/s, 4: as 3).
_BUFFERS = 3


class Reads(NamedTuple):
    """The blocks one decode step reads of one kind of pool, in slot
    order, as the kernel's prefetched scalars. ``total`` (1,) entries
    count; entry ``n`` is the ``FOLD`` blocks ``phys[FOLD * n ..]`` of
    slot ``slot[n]``, which hold the keys at positions ``kbase[n] ..``
    in order; a query of slot ``b`` sees the keys at ``lo[b] <=
    position <= hi[b]``."""
    total: jax.Array
    slot: jax.Array
    phys: jax.Array
    kbase: jax.Array
    lo: jax.Array
    hi: jax.Array

    def fetched(self) -> jax.Array:
        """Blocks the kernel copies out of one pool leaf in one call."""
        return self.total[0] * FOLD


def window_blocks(window: int, block_tokens: int) -> int:
    """The most blocks that hold a window's keys: ``window`` keys end
    in the query's block and start at most ``window - 1`` rows before
    it."""
    return -(-window // block_tokens) + 1


def step_reads(table: jax.Array, live: jax.Array, pos: jax.Array,
               valid_len: jax.Array, block_tokens: int,
               window: int = 0) -> Reads:
    """What a decode step reads through ``table`` (B, span; entry j the
    block of positions ``j * block_tokens ..``): for each slot that
    ``live`` (B,) marks, the blocks from the one that holds its oldest
    visible key (position 0, or ``pos - window + 1`` under a window) to
    the one that holds its newest (``pos``, and under ``valid_len``).
    A slot that is not live reads nothing."""
    b, span = table.shape
    bt = block_tokens
    hi = jnp.minimum(pos, valid_len - 1).astype(jnp.int32)
    lo = (jnp.maximum(pos - window + 1, 0) if window
          else jnp.zeros_like(pos)).astype(jnp.int32)
    first, newest = lo // bt, jnp.minimum(hi // bt, span - 1)
    count = jnp.where(live & (hi >= lo), newest - first + 1, 0)
    count = -(-jnp.maximum(count, 0).astype(jnp.int32) // FOLD)
    most = min(span, window_blocks(window, bt)) if window else span
    ends = jnp.cumsum(count)
    n = jnp.arange(b * -(-most // FOLD), dtype=jnp.int32)
    slot = jnp.minimum(jnp.searchsorted(ends, n, side="right",
                                        method="compare_all"),
                       b - 1).astype(jnp.int32)
    oldest = first[slot] + (n - (ends - count)[slot]) * FOLD
    logical = oldest[:, None] + jnp.arange(FOLD, dtype=jnp.int32)
    logical = jnp.where(logical <= newest[slot][:, None], logical,
                        oldest[:, None])
    phys = table[slot[:, None], jnp.clip(logical, 0, span - 1)]
    return Reads(ends[-1:].astype(jnp.int32), slot,
                 phys.reshape(-1).astype(jnp.int32), oldest * bt, lo, hi)


def _kernel(layer_ref, total_ref, slot_ref, phys_ref, kbase_ref, lo_ref,
            hi_ref, q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_ref,
            l_ref, acc_ref, *, scale: float):
    """The whole list in one loop: entry ``n`` waits for its blocks,
    starts the copies of entry ``n + buffers - 1`` and folds its keys
    into its slot's running maximum, sum and accumulator; a slot's
    first entry starts those anew (a select, not a product: what the
    scratch holds before the first slot is anything) and its last one
    writes the slot's output."""
    layer, total = layer_ref[0], total_ref[0]
    buffers, _, keys, d = k_buf.shape
    bt = keys // FOLD
    o_ref[...] = jnp.zeros_like(o_ref)
    # A copy moves bits: the blocks travel as the buffers' unsigned
    # integers (see ``attend``) and are keys and values again where
    # they are multiplied.
    k_bits, v_bits = k_hbm.bitcast(k_buf.dtype), v_hbm.bitcast(v_buf.dtype)

    def copies(n, buf):
        out = []
        for g in range(FOLD):
            block = phys_ref[n * FOLD + g]
            at = (buf, slice(None), pl.ds(g * bt, bt))
            out += [pltpu.make_async_copy(k_bits.at[layer, block],
                                          k_buf.at[at], sems.at[0, buf]),
                    pltpu.make_async_copy(v_bits.at[layer, block],
                                          v_buf.at[at], sems.at[1, buf])]
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
        kpos = kbase_ref[n] + lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], keys), 1)
        seen = ((kpos >= lo_ref[b]) & (kpos <= hi_ref[b]))[None]
        m_old = jnp.where(first, _MASKED, m_ref[...])
        landed = copies(n, buf)
        for copy in landed[::2]:
            copy.wait()
        # All pairs in one batched product: (pairs, rows, keys).
        s = lax.dot_general(q_ref[b],
                            pltpu.bitcast(k_buf[buf], q_ref.dtype),
                            (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=F32) * scale
        s = jnp.where(seen, s, _MASKED)
        m_new = jnp.maximum(m_old, jnp.max(s, axis=2, keepdims=True))
        e = jnp.where(seen, jnp.exp(s - m_new[:, :, :1]), 0.0)
        alpha = jnp.exp(m_old - m_new)
        m_ref[...] = m_new
        l_ref[...] = (alpha * jnp.where(first, 0.0, l_ref[...])
                      + jnp.sum(e, axis=2, keepdims=True))
        for copy in landed[1::2]:
            copy.wait()
        acc_ref[...] = (
            alpha[:, :, :d] * jnp.where(first, 0.0, acc_ref[...])
            + lax.dot_general(e.astype(q_ref.dtype),
                              pltpu.bitcast(v_buf[buf], q_ref.dtype),
                              (((2,), (1,)), ((0,), (0,))),
                              preferred_element_type=F32))

        @pl.when(last)
        def _():
            den = jnp.maximum(l_ref[...], 1e-30)
            o_ref[b] = acc_ref[...] / den[:, :, :d]

    lax.fori_loop(0, total, fold, None)


def attend(q: jax.Array, pool_k: jax.Array, pool_v: jax.Array, layer,
           reads: Reads, scale: float) -> jax.Array:
    """Attention of one query a slot: q (B, pairs, G, lanes) in the
    pool's dtype against layer ``layer`` of ``pool_k`` / ``pool_v``
    (layers, blocks, pairs, block_tokens, lanes), the blocks and the
    visible positions as ``reads`` names them. Returns float32 (B,
    pairs, G, lanes): zeros for a slot with nothing to read. The pools
    stay where they are: the kernel copies single blocks out of them."""
    b, pairs, group, d = q.shape
    bt = pool_k.shape[3]
    if d > _LANES:
        raise ValueError(f"a key/value row of {d} lanes: at most {_LANES}")
    # The queries of a pair as whole sublane tiles: rows of zeros score
    # 0 everywhere and are cut off below.
    rows = -(-group // 8) * 8
    q = jnp.pad(q.astype(pool_k.dtype),
                ((0, 0), (0, 0), (0, rows - group), (0, 0)))
    # The buffers hold a block's bits as unsigned integers of the pool's
    # width. On the chip that is free. Interpreted on a CPU a copy is an
    # update of part of a buffer, which XLA's CPU compiler does in place
    # on integers and, on bfloat16, by converting the WHOLE buffer to
    # float32 and back: 120 us a copy for 15, and the benchmark's tiny
    # rehearsal of this family a step of 17 ms for 9.9 (the gathering
    # program's: 6.9), too slow for its clients' deadline.
    bits = jnp.dtype(f"uint{8 * pool_k.dtype.itemsize}")
    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=7,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((_BUFFERS, pairs, FOLD * bt, d), bits),
                pltpu.VMEM((_BUFFERS, pairs, FOLD * bt, d), bits),
                pltpu.SemaphoreType.DMA((2, _BUFFERS)),
                pltpu.VMEM((pairs, rows, _LANES), F32),
                pltpu.VMEM((pairs, rows, _LANES), F32),
                pltpu.VMEM((pairs, rows, d), F32)]),
        out_shape=jax.ShapeDtypeStruct((b, pairs, rows, d), F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=flash_attention._interpret(),
        name="stpu_paged_diff_attn",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *reads, q, pool_k, pool_v)
    return out[:, :, :group]
