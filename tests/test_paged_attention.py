"""The decode steps' paged attention kernels (interpret mode on the CPU)
against the forms they replace in the step, at tiny widths and the served
geometry (blocks of 64 tokens, a table of 20 blocks): phi4flash's
(ops/pallas/paged_attention.py: 2 key/value pairs of 32 lanes, a window
of 512) against ``phi4flash._attend`` over ``phi4flash._gather``'s
blocks, and deepseek's (ops/pallas/paged_mla_attention.py: 4 heads over
latent rows of 32 and a roped key of 8) against the absorbed tile loop
``deepseek._attend``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import deepseek, phi4flash
from skypilot_tpu.ops.pallas import paged_attention, paged_mla_attention

BT, SPAN, WINDOW = 64, 20, 512
CFG = dataclasses.replace(phi4flash.Phi4FlashConfig.tiny(),
                          sliding_window=WINDOW, dtype=jnp.float32)
# Float32 leaves rounding of about 1e-6 a product (tests/test_phi4flash.py
# holds logits to 3e-4); in bfloat16 the two forms round the
# probabilities at different scales: one bf16 step of an output near 1.
TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}

# (positions of the slots, None = a slot that does not decode; windowed)
CASES = {
    "ragged lengths in one batch": ([5, 700, 64, 1279, 130], False),
    "a dead slot between two live ones": ([300, None, 90], False),
    "dead slots first and last": ([None, 300, None], True),
    "no slot decodes": ([None, None], False),
    "pos % 64 == 0": ([0, 64, 640], False),
    "pos % 64 == 1": ([1, 65, 641], True),
    "pos % 64 == 63": ([63, 127, 703], True),
    "a window before, at and after the 512th token":
        ([510, 511, 512, 513, 575, 576, 1100], True),
    "the full layer at the cap": ([1279, 1278], False),
    "the window at the cap": ([1279, 1216], True),
}


def _pools(dtype, key, blocks=80):
    """Two layers of seeded keys and values; block 0 (the scratch block)
    is zeros here and NaN in the copy the kernel reads."""
    shape = (2, blocks, CFG.kv_pairs, BT, 2 * CFG.head_dim)
    k, v = (jax.random.normal(s, shape, jnp.float32).astype(dtype)
            for s in jax.random.split(key))
    clean = (k.at[:, 0].set(0), v.at[:, 0].set(0))
    return clean, tuple(a.at[:, 0].set(jnp.nan) for a in clean)


def _table(positions, windowed):
    """Each decoding slot's blocks, ids of its own, as the engine keeps
    them: every block up to the query's, and under a window 0 where a
    block lies wholly behind it (released). A slot that does not decode
    has a row of zeros."""
    table = np.zeros((len(positions), SPAN), np.int32)
    nxt = 1
    for b, pos in enumerate(positions):
        if pos is None:
            continue
        oldest = max(pos - WINDOW + 1, 0) // BT if windowed else 0
        for j in range(oldest, pos // BT + 1):
            table[b, j] = nxt
            nxt += 1
    return table


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", CASES)
def test_the_kernel_equals_the_one_pass_form_on_the_blocks_it_reads(
        case, dtype):
    positions, windowed = CASES[case]
    window = WINDOW if windowed else 0
    b = len(positions)
    table = _table(positions, windowed)
    live = jnp.asarray([p is not None for p in positions])
    pos = jnp.asarray([p or 0 for p in positions], jnp.int32)
    clean, poisoned = _pools(dtype, jax.random.key(len(case)))
    q = phi4flash._pad_queries(CFG, jax.random.normal(
        jax.random.key(7), (b, 1, CFG.n_heads * CFG.head_dim),
        jnp.float32).astype(dtype))
    layer = 1

    reads = paged_attention.step_reads(jnp.asarray(table), live, pos,
                                       pos + 1, BT, window)
    got = np.asarray(jax.jit(
        lambda q, k, v, reads: paged_attention.attend(
            q[:, 0], k, v, jnp.int32(layer), reads,
            CFG.head_dim ** -0.5))(q, *poisoned, reads))

    # The count is the sum a plain count gives: a decoding slot's blocks
    # from its oldest visible key's to its query's, FOLD to an entry (an
    # odd count's last block is read twice).
    fold = paged_attention.FOLD
    by_hand = [0 if p is None else
               p // BT - (max(p - WINDOW + 1, 0) // BT if windowed else 0)
               + 1 for p in positions]
    entries = sum(-(-c // fold) for c in by_hand)
    assert int(reads.total[0]) == entries
    assert int(reads.fetched()) == entries * fold
    assert sum(by_hand) <= entries * fold <= sum(by_hand) + sum(
        p is not None for p in positions) * (fold - 1)
    listed = np.asarray(reads.phys)[:entries * fold]
    assert set(listed) == set(table[table > 0])          # never block 0

    count = 9 if windowed else SPAN
    first = jnp.maximum(pos - window + 1, 0) // BT if windowed \
        else jnp.zeros((b,), jnp.int32)
    kb, vb, kpos = phi4flash._gather(*clean, layer, jnp.asarray(table),
                                     first, count)
    want = np.asarray(phi4flash._attend(CFG, q, kb, vb, phi4flash._paged_mask(
        kpos, pos[:, None], pos + 1, window)))[:, 0]
    assert got.shape == want.shape == (b, CFG.kv_pairs, 4, 2 * CFG.head_dim)
    assert np.isfinite(got).all()          # nothing of block 0 was read
    for i, p in enumerate(positions):
        if p is None:
            assert not got[i].any()
        else:
            assert np.abs(want[i]).max() > 1e-2
            err = np.abs(got[i] - want[i]).max() / np.abs(want[i]).max()
            assert err < MLA_TOL[dtype], (i, p, err)


# ------------------------------------------------- deepseek's latent rows
# nope = v = the latent's 32: W_UK and W_UV as identities make the tile
# loop's output the softmax-weighted latent rows the kernel returns.
MLA = dataclasses.replace(deepseek.DeepseekV3Config.tiny(),
                          qk_nope_head_dim=32, v_head_dim=32)
# A slot's first block (0, 63), a second that starts (64, 65), an odd
# count (130: three blocks), the table's end (1279), one slot that does
# not decode.
MLA_POSITIONS = [0, 63, None, 64, 65, 130, 1279]
# Relative to a slot's largest output. Float32: the two forms' sums in
# another order (3e-7 seen). Bfloat16: both round the output once, from
# float32 values that differ by the kernel's rounding of the
# probabilities: an ulp or two of the output (2^-8 of its size).
MLA_TOL = {jnp.float32: 1e-5, jnp.bfloat16: 2.0 ** -7}


def _latent_pools(dtype, key, table, layer, blocks=80):
    """Two layers of seeded latent rows and roped keys (the pool's planar
    bytes). In the copy the kernel reads, every block that no decoding
    slot of layer ``layer`` lists is NaN, the other layer's too (bytes
    0xff make a NaN of either width)."""
    shape = (2, blocks, BT)
    kc, kr = jax.random.split(key)
    c = jax.random.normal(kc, shape + (MLA.kv_lora_rank,),
                          jnp.float32).astype(dtype)
    k = deepseek._to_bytes(jax.random.normal(
        kr, shape + (MLA.qk_rope_head_dim,), jnp.float32).astype(dtype))
    listed = np.zeros((2, blocks), bool)
    listed[layer, table[table[:, 0] > 0]] = True
    listed = jnp.asarray(listed)[..., None, None]
    return (c, k), (jnp.where(listed, c, jnp.nan),
                    jnp.where(listed, k, jnp.uint8(0xff)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_the_mla_kernel_equals_the_tile_loop_on_the_blocks_it_reads(dtype):
    positions = MLA_POSITIONS
    b, layer = len(positions), 1
    table = _table(positions, False)
    live = jnp.asarray([p is not None for p in positions])
    pos = jnp.asarray([p or 0 for p in positions], jnp.int32)
    (c, k), (c_bad, k_bad) = _latent_pools(dtype, jax.random.key(3),
                                           table, layer)
    heads, rank = MLA.n_heads, MLA.kv_lora_rank
    kq, kr = jax.random.split(jax.random.key(11))
    q_latent = jax.random.normal(kq, (b, 1, heads, rank),
                                 jnp.float32).astype(dtype)
    q_rope = jax.random.normal(kr, (b, 1, heads, MLA.qk_rope_head_dim),
                               jnp.float32).astype(dtype)
    scale = deepseek.softmax_scale(MLA)

    reads = paged_attention.step_reads(jnp.asarray(table), live, pos,
                                       pos + 1, BT)
    got = np.asarray(jax.jit(
        lambda ql, qr, c, k, reads: paged_mla_attention.attend(
            ql[:, 0], qr[:, 0], c, k, jnp.int32(layer), reads, scale))(
        q_latent, q_rope, c_bad, k_bad, reads), np.float32)
    blocks = [0 if p is None else p // BT + 1 for p in positions]
    assert int(reads.fetched()) == sum(-(-n // 2) * 2 for n in blocks)

    eye = jnp.broadcast_to(jnp.eye(rank, dtype=dtype), (heads, rank, rank))
    window = 4 * BT

    def tiles(s0):
        phys = jax.lax.dynamic_slice(jnp.asarray(table), (0, s0 // BT),
                                     (b, window // BT))
        kpos = s0 + jnp.arange(window)
        msk = kpos[None, None, :] <= pos[:, None, None]
        return (c[layer, phys].reshape(b, window, -1),
                deepseek._from_bytes(k[layer, phys].reshape(b, window, -1),
                                     dtype), msk)

    want = np.asarray(deepseek._attend(
        MLA, q_latent, q_rope, {"w_uk": eye, "w_uv": eye}, tiles,
        jnp.int32(SPAN * BT), absorbed=True), np.float32).reshape(
            b, heads, rank)
    assert got.shape == want.shape
    assert np.isfinite(got).all()          # no block outside the list
    for i, p in enumerate(positions):
        if p is None:
            assert not got[i].any()
        else:
            assert np.abs(want[i]).max() > 1e-2
            err = np.abs(got[i] - want[i]).max() / np.abs(want[i]).max()
            assert err < MLA_TOL[dtype], (i, p, err)
