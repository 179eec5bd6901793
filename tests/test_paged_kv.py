"""Paged KV-cache block pool: one device-resident pool for slots +
prefix cache, zero-copy shared-prefix aliasing.

The contract under test, strongest first:

  * the engine's decode is BIT-IDENTICAL to the row-cache reference
    (``models.<family>.decode`` / ``forward_with_cache``) — greedy and
    seeded sampling, all three families, across slot reuse and chunked
    prefill (the block-table gather feeds the same online-softmax tile
    as the reference's slice, so aligned tiles produce the same
    floats);
  * a prefix hit is a block-table entry write: zero splice copies on
    the hot path (the dense splice entry points no longer exist), and
    publish-on-free is a refcount transfer;
  * block refcount/aliasing lifecycle: shared blocks survive a
    mid-stream cancel, eviction never frees a pinned block, and 500
    seeded admit/cancel cycles leak nothing;
  * admission is pool-capacity based — a request longer than max_seq
    is admitted when its blocks fit — and a budget of two max_seq
    rows sustains strictly more than two concurrent slots for
    mixed-length traffic;
  * KV-cache donation is preserved through both paged jitted entry
    points (single-device and TP-sharded), and the same admission
    sequence reproduces the same block tables on every gang host.
"""
import dataclasses
import random
import socket
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import deepseek, gemma, llama, mixtral
from skypilot_tpu.serve import decode_engine
from skypilot_tpu.serve import gang_replica
from skypilot_tpu.serve import kv_pool
from skypilot_tpu.serve.decode_engine import DecodeEngine, EngineError


def _tiny(family="llama"):
    if family == "mixtral":
        return mixtral, mixtral.MixtralConfig.tiny()
    if family == "gemma":
        return gemma, gemma.GemmaConfig.tiny(vocab_size=128)
    if family == "deepseek":
        return deepseek, deepseek.DeepseekV3Config.tiny(vocab_size=128)
    return llama, llama.LlamaConfig.tiny(vocab_size=128)


def _drive(engine, rounds=200):
    """Step an UNSTARTED engine deterministically until idle."""
    for _ in range(rounds):
        engine._admit()
        did = engine._prefill_one()
        did = engine._decode_step() or did
        if not did and not engine._waiting:
            return
    raise AssertionError("engine did not quiesce")


# ==================================================== pool accounting
def test_block_pool_accounting_and_errors():
    pool = kv_pool.BlockPool(6, 8)           # block 0 scratch, 5 usable
    assert pool.usable_blocks == 5
    assert pool.blocks_for(1) == 1 and pool.blocks_for(17) == 3
    pool.reserve(3)
    assert pool.available() == 2
    blocks = [pool.alloc() for _ in range(3)]
    assert 0 not in blocks                   # scratch never allocated
    assert pool.available() == 2             # reservation consumed
    pool.retain(blocks[0])
    pool.release(blocks[0])
    assert pool.refcount(blocks[0]) == 1     # still one owner
    pool.release(blocks[0])
    assert pool.refcount(blocks[0]) == 0     # freed
    with pytest.raises(RuntimeError, match="double-release"):
        pool.release(blocks[0])
    with pytest.raises(RuntimeError, match="available"):
        pool.reserve(5)
    pool.release(blocks[1])
    pool.release(blocks[2])
    assert pool.free_blocks() == 5


def test_paged_trie_lru_refcount_and_interior_protection():
    """Paged eviction contract, mirroring the dense pool test: LRU
    leaves go first, pinned nodes are never evicted, and an interior
    chunk outlives fresher leaves until its children are gone."""
    pool = kv_pool.BlockPool(8, 4)
    trie = kv_pool.PagedPrefixCache(pool, chunk=4)
    a, b = list(range(10, 14)), list(range(20, 24))

    def adopt(prompt, n_tokens):
        owned = [pool.alloc(reserved=False)
                 for _ in range(n_tokens // 4)]
        trie.publish(prompt, n_tokens, lambda j: owned[j])
        for blk in owned:                    # slot's own ref drops
            pool.release(blk)

    adopt(a + b + [1], 8)                    # chain a -> b
    adopt(list(range(30, 34)) + [1], 4)      # c
    assert trie.stats()["chunks"] == 3
    assert pool.free_blocks() == 7 - 3

    held = trie.match(a + b + [1])
    assert len(held) == 2
    trie.pin(held)
    assert all(n.refs == 1 for n in held)

    # Evict: the unpinned LRU leaf (c) goes; the pinned chain and the
    # interior node survive any number of attempts.
    assert trie.evict_one()
    keys = {n.key for n in trie.nodes()}
    assert tuple(a) in keys and tuple(b) in keys
    assert tuple(range(30, 34)) not in keys
    assert not trie.evict_one()              # only pinned/interior left
    assert {n.key for n in trie.nodes()} == {tuple(a), tuple(b)}

    trie.unpin(held)
    assert trie.evict_one()                  # leaf b first
    assert {n.key for n in trie.nodes()} == {tuple(a)}
    assert trie.evict_one()                  # then a, now a leaf
    assert pool.free_blocks() == 7


# ================================================= bit-parity: engine
def test_paged_engine_matches_dense_and_reference():
    """5 ragged greedy requests through 2 slots: the engine's streams
    equal the row-cache decode token-for-token — slot reuse, chunked
    prefill, and the block-table gather all covered by one
    workload."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    rng = random.Random(0)
    specs = [([rng.randint(1, 127) for _ in range(rng.randint(1, 19))],
              rng.randint(1, 8)) for _ in range(5)]

    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8).start()
    try:
        reqs = [eng.submit(p, max_tokens=mt) for p, mt in specs]
        served = [r.result(timeout=300.0) for r in reqs]
    finally:
        eng.shutdown()
    for (p, mt), got in zip(specs, served):
        ref = mdl.decode(cfg, params, jnp.asarray([p], jnp.int32),
                         jnp.int32(len(p)), mt, len(p) + mt)
        assert got == [int(t) for t in ref[0]], (p, mt)


@pytest.mark.parametrize("family", ["mixtral", "gemma"])
def test_paged_parity_other_families(family):
    """The block-table decode path holds bit-identically for the MoE
    (dense-routed) and MQA/tied-head families too."""
    mdl, cfg = _tiny(family)
    params = mdl.init(cfg, jax.random.key(0))
    rng = random.Random(3)
    specs = [([rng.randint(1, cfg.vocab_size - 1)
               for _ in range(rng.randint(2, 18))],
              rng.randint(1, 6)) for _ in range(3)]

    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8).start()
    try:
        reqs = [eng.submit(p, max_tokens=mt) for p, mt in specs]
        served = [r.result(timeout=300.0) for r in reqs]
    finally:
        eng.shutdown()
    for (p, mt), got in zip(specs, served):
        ref = mdl.decode(cfg, params, jnp.asarray([p], jnp.int32),
                         jnp.int32(len(p)), mt, len(p) + mt)
        assert got == [int(t) for t in ref[0]], (p, mt)


def test_cached_and_paged_blocks_share_one_projection(monkeypatch):
    """The row-cache reference and the paged forward reach q, k and v
    through ONE function of the module, llama.cached_qkv_proj (the
    products finished before the reshape to heads, PR 32), and never
    through the training forward's llama.qkv_proj — so a test that
    holds the engine to ``decode`` / ``forward_with_cache`` compares
    two callers of the same arithmetic. The training forward is the
    other way round."""
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    calls = []

    def recorded(name):
        real = getattr(llama, name)

        def fn(*args):
            calls.append(name)
            return real(*args)
        return fn

    for name in ("qkv_proj", "cached_qkv_proj"):
        monkeypatch.setattr(llama, name, recorded(name))
    params = jax.eval_shape(lambda: llama.init(cfg, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((1, 8), jnp.int32)
    table = jax.ShapeDtypeStruct((1, 4), jnp.int32)
    rows = jax.eval_shape(lambda: llama.init_cache(cfg, 1, 32))
    pool = jax.eval_shape(lambda: llama.init_paged_cache(cfg, 5, 8))
    # One trace of the scan's body each: one call each.
    jax.eval_shape(lambda p, t, c: llama.forward_with_cache(
        cfg, p, t, c, 0), params, tokens, rows)
    assert calls == ["cached_qkv_proj"]
    jax.eval_shape(lambda p, t, c, tb: llama.forward_with_paged_cache(
        cfg, p, t, c, tb, 0, window=32, write_block=jnp.int32(1)),
        params, tokens, pool, table)
    assert calls == ["cached_qkv_proj"] * 2
    jax.eval_shape(lambda p, t: llama.forward(cfg, p, t), params, tokens)
    assert calls == ["cached_qkv_proj"] * 2 + ["qkv_proj"]


@pytest.mark.parametrize("family", ["llama", "mixtral", "gemma"])
def test_paged_forward_logits_equal_the_row_cache_forward(family):
    """Below the engine: a prompt's chunk and three decode steps through
    ``forward_with_paged_cache`` give the logits of
    ``forward_with_cache`` to the bit (one projection, one attention
    tile, tiles aligned: window == max_seq == 32)."""
    mdl, cfg = _tiny(family)
    params = mdl.init(cfg, jax.random.key(0))
    bt, max_seq = 8, 32
    prompt = jax.random.randint(jax.random.key(1), (1, bt), 1,
                                cfg.vocab_size)
    rows = mdl.init_cache(cfg, 1, max_seq)
    pool = mdl.init_paged_cache(cfg, 1 + max_seq // bt, bt)
    table = jnp.arange(1, 1 + max_seq // bt, dtype=jnp.int32)[None]
    want, rows = mdl.forward_with_cache(cfg, params, prompt, rows, 0)
    got, pool = mdl.forward_with_paged_cache(
        cfg, params, prompt, pool, table, 0, window=max_seq,
        write_block=jnp.int32(1))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for pos in range(bt, bt + 3):
        tok = jnp.argmax(want[:, -1:], axis=-1).astype(jnp.int32)
        want, rows = mdl.forward_with_cache(cfg, params, tok, rows, pos)
        got, pool = mdl.forward_with_paged_cache(
            cfg, params, tok, pool, table, pos, window=max_seq)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_paged_seeded_sampling_parity_and_zero_copy_hit(
        reference_stream):
    """A temperature > 0 stream is the row-cache reference's, sampled
    by the same per-position keys, AND the repeat of the same prompt —
    a zero-copy aliased hit — still samples the identical stream (the
    aliased blocks hold the exact rows prefill would recompute)."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    prompt = [int(t) for t in jax.random.randint(
        jax.random.key(3), (21,), 1, 128)]

    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8).start()
    try:
        first = eng.submit(prompt, max_tokens=6, temperature=0.9,
                           seed=17).result(timeout=300.0)
        second_req = eng.submit(prompt, max_tokens=6,
                                temperature=0.9, seed=17)
        second = second_req.result(timeout=300.0)
    finally:
        eng.shutdown()
    assert first == second == reference_stream(
        mdl, cfg, params, prompt, 6, temperature=0.9, seed=17, chunk=8)
    assert second_req.cached_prompt_tokens == 16   # 2 aliased blocks


# ========================================== zero-copy on the hot path
def test_paged_prefix_hit_zero_copies_on_hot_path():
    """A prefix hit performs NO splice work: the row
    splice entry points (_insert_chunk/_gather_chunk and the per-model
    gather/insert_cache_rows) are RETIRED — asserted gone, so nothing
    can quietly reintroduce a copy path — and the warm request must
    still restore its prefix (table aliasing) and publish on free
    (refcount transfer)."""
    for retired in ("_insert_chunk", "_gather_chunk", "PrefixCache"):
        assert not hasattr(decode_engine, retired), retired
    for mod in (llama, mixtral, gemma):
        for retired in ("gather_cache_rows", "insert_cache_rows"):
            assert not hasattr(mod, retired), (mod.__name__, retired)
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8).start()
    try:
        shared = [int(t) for t in jax.random.randint(
            jax.random.key(11), (17,), 1, 128)]
        cold = eng.submit(shared + [5, 6], max_tokens=4)
        cold_toks = cold.result(timeout=300.0)
        warm = eng.submit(shared + [7, 8, 9], max_tokens=4)
        warm_toks = warm.result(timeout=300.0)
        for prompt, got in ((shared + [5, 6], cold_toks),
                            (shared + [7, 8, 9], warm_toks)):
            ref = mdl.decode(cfg, params, jnp.asarray([prompt]),
                             jnp.int32(len(prompt)), 4,
                             len(prompt) + 4)
            assert got == [int(t) for t in ref[0]]
        assert cold.cached_prompt_tokens == 0
        assert warm.cached_prompt_tokens == 16
        assert warm.prefill_chunks < cold.prefill_chunks
        stats = eng.prefix_cache.stats()
        assert stats["zero_copy_hits"] >= 1
        assert stats["tokens_saved"] >= 16
    finally:
        eng.shutdown()


# ======================================== admission: pool, not row
def test_paged_admission_pool_bound_not_row_length():
    """The admission bound is POOL capacity, not max_seq: a request
    with len(prompt) + max_tokens > max_seq is admitted when its
    blocks fit (and still decodes correctly), while a request bigger
    than the whole pool gets the pool-bound error."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    prompt = [int(t) for t in jax.random.randint(
        jax.random.key(5), (70,), 1, 128)]

    # 32 usable blocks x 8 tokens = 256 logical tokens per request.
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8,
                       kv_pool_blocks=33).start()
    try:
        got = eng.submit(prompt, max_tokens=8).result(timeout=300.0)
        ref = mdl.decode(cfg, params, jnp.asarray([prompt], jnp.int32),
                         jnp.int32(70), 8, 78)
        assert got == [int(t) for t in ref[0]]
        with pytest.raises(EngineError, match="exceeds the KV pool"):
            eng.submit(list(range(1, 260)), max_tokens=16)
    finally:
        eng.shutdown()


# =============================================== aliasing lifecycle
def test_paged_aliasing_cancel_mid_stream_blocks_survive():
    """Two slots aliasing one cached prefix; one cancels mid-stream.
    The shared blocks must survive (the other slot still reads them
    through its table), eviction must refuse to touch them while
    pinned, and the survivor's stream stays token-identical."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8)
    shared = [int(t) for t in jax.random.randint(
        jax.random.key(9), (17,), 1, 128)]
    # Cold leg publishes the two full prompt chunks on free.
    first = eng.submit(shared, max_tokens=1)
    _drive(eng)
    assert first.result(timeout=5.0)
    assert eng.prefix_cache.stats()["chunks"] == 2

    a = eng.submit(shared + [3, 4, 5], max_tokens=6)
    b = eng.submit(shared + [6, 7, 8], max_tokens=6)
    eng._admit()
    pinned = [n for n in eng.prefix_cache.nodes() if n.refs > 0]
    assert len(pinned) == 2 and all(n.refs == 2 for n in pinned)
    shared_blocks = {n.block for n in pinned}
    assert all(eng._pool.refcount(blk) == 3 for blk in shared_blocks)

    # A few interleaved steps so both are mid-stream, then cancel one.
    for _ in range(4):
        eng._prefill_one()
        eng._decode_step()
    a.cancel()
    _drive(eng)
    try:
        a.result(timeout=5.0)
    except EngineError:
        pass                                # cancelled is clean either way
    # Shared blocks survived the cancel and pinning blocked eviction
    # throughout; the survivor's stream equals the fixed path.
    keys = {n.key for n in eng.prefix_cache.nodes()}
    assert {n.key for n in pinned} <= keys
    got = b.result(timeout=5.0)
    ref = mdl.decode(cfg, params, jnp.asarray([shared + [6, 7, 8]]),
                     jnp.int32(20), 6, 26)
    assert got == [int(t) for t in ref[0]]
    assert all(n.refs == 0 for n in eng.prefix_cache.nodes())


def test_paged_release_idempotent_500_cycle_churn():
    """500 seeded admit/cancel cycles (cancel at random prefill/decode
    depth): slot-level release is idempotent under refcounted blocks,
    so the accounting identity free + trie == usable holds at the end
    with zero reservations and zero pins outstanding."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8)
    rng = random.Random(7)
    for _ in range(500):
        prompt = [rng.randint(1, 127)
                  for _ in range(rng.randint(9, 30))]
        req = eng.submit(prompt, max_tokens=rng.randint(1, 4))
        eng._admit()
        for _ in range(rng.randint(0, 5)):
            did = eng._prefill_one()
            did = eng._decode_step() or did
            if not did:
                break
        req.cancel()
        _drive(eng)
    pool = eng._pool
    trie_blocks = len(eng.prefix_cache.nodes())
    assert all(s.request is None for s in eng._slots)
    assert pool.free_blocks() + trie_blocks == pool.usable_blocks
    assert pool._reserved == 0
    assert all(n.refs == 0 for n in eng.prefix_cache.nodes())


# ============================================== capacity per KV byte
def test_paged_more_live_slots_than_dense_same_budget():
    """A KV budget of 128 cache-token rows holds 2 whole max_seq=64
    rows (slots x max_seq, the budget's arithmetic); the pool runs 6
    slots over those bytes and admission packs by ACTUAL length — a
    mixed short-request burst sustains strictly more concurrent
    slots than whole rows would."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    rng = random.Random(4)
    specs = [([rng.randint(1, 127) for _ in range(8)], 4)
             for _ in range(6)]

    budget_tokens, max_seq = 128, 64
    whole_rows = budget_tokens // max_seq
    # The default pool IS that arithmetic: slots x max_seq tokens.
    assert decode_engine.resolve_kv_geometry(
        slots=whole_rows, max_seq=max_seq, prefill_chunk=8
    )["pool_blocks"] == budget_tokens // 8 + 1

    paged = DecodeEngine(cfg, params, slots=6, max_seq=max_seq,
                         prefill_chunk=8,
                         kv_pool_blocks=budget_tokens // 8 + 1)
    reqs = [paged.submit(p, max_tokens=mt) for p, mt in specs]
    _drive(paged)

    assert whole_rows == 2
    assert paged.peak_live_slots == 6 > whole_rows
    assert all(len(r.result(timeout=5.0)) == 4 for r in reqs)


# ===================================================== donation + TP
def test_paged_entry_points_keep_donation_sharded_and_single():
    """The pool stays donated through BOTH paged jitted entry points —
    single-device and TP-sharded (cache_shardings applies unchanged to
    the pool layout): the buffer handed in is consumed. That is all a
    consumed donation shows. A program can take the donated buffer and
    still build a second pool beside it (up to PR 26 both programs
    did: one layer's pool sliced out, written back into a second
    stack, copied over the donated one); that the pool is ONE buffer
    inside the program is held by
    test_paged_entry_points_hold_one_pool_buffer below. Pinned per
    family."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    mesh = mesh_lib.make_mesh({"tp": 2}, devices=jax.devices()[:2])
    rules = mesh_lib.DEFAULT_RULES
    for family in ("llama", "mixtral", "gemma"):
        mdl, cfg = _tiny(family)
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        for shard in (False, True):
            params = mdl.init(cfg, jax.random.key(0))
            pool = mdl.init_paged_cache(cfg, 8, 8)
            if shard:
                params = gang_replica.shard_params(cfg, params, mesh,
                                                   rules)
                shardings = gang_replica.cache_shardings(cfg, mesh,
                                                         rules)
                # shardings also carries k_scale/v_scale for the int8
                # pool; a bf16 pool has no such leaves — filter like
                # the engine does.
                pool = jax.device_put(
                    pool, {k: shardings[k] for k in pool})
            table = jnp.ones((2, 8), jnp.int32)
            old_k, old_v = pool["k"], pool["v"]
            buf = jnp.zeros((8,), jnp.int32).at[:4].set(
                jnp.asarray([1, 2, 3, 4]))
            toks, pool = decode_engine._paged_prefill_chunk(
                cfg, params, pool, buf, table[0], jnp.int32(0),
                jnp.int32(4), jnp.int32(1), 64,
                jnp.zeros((2,), jnp.int32), jnp.int32(0),
                jnp.uint32(0), jnp.float32(0.0))
            assert old_k.is_deleted() and old_v.is_deleted(), \
                f"{family} shard={shard}: prefill dropped donation"
            old_k, old_v = pool["k"], pool["v"]
            _nxt, pool = decode_engine._paged_step(
                cfg, params, pool, toks,
                jnp.asarray([4, 0], jnp.int32), table, 64,
                jnp.zeros((2,), jnp.float32),
                jnp.zeros((2,), jnp.uint32))
            assert old_k.is_deleted() and old_v.is_deleted(), \
                f"{family} shard={shard}: step dropped donation"


# A pool far larger than everything else a program touches, so that a
# second copy of it (or of one layer of it) cannot hide among the
# temporaries: 16384 blocks of 64 rows, never allocated — the entry
# points are lowered from shapes.
_ONE_BUFFER_BLOCKS, _ONE_BUFFER_BT, _ONE_BUFFER_WINDOW = 16384, 64, 256


def _compile_paged_entry(entry, family, quantized, tp):
    """Compile one paged entry point from shapes alone (float32; the
    CPU backend widens a bf16 pool for its scatter, which says nothing
    about the program). Returns (compiled, pool bytes on one device,
    parameter bytes, the first pool leaf's shape on one device)."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    mdl, cfg = _tiny(family)
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = jax.eval_shape(lambda: mdl.init(cfg, jax.random.key(0)))
    pool = jax.eval_shape(lambda: mdl.init_paged_cache(
        cfg, _ONE_BUFFER_BLOCKS, _ONE_BUFFER_BT, quantized=quantized))
    first = next(iter(pool))
    shard_shape = pool[first].shape
    if tp > 1:
        mesh = mesh_lib.make_mesh({"tp": tp},
                                  devices=jax.devices()[:tp])
        rules = mesh_lib.DEFAULT_RULES
        params = jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=sh),
            params,
            mesh_lib.tree_shardings(mesh, rules, mdl.param_specs(cfg)))
        shardings = gang_replica.cache_shardings(cfg, mesh, rules)
        pool = {k: jax.ShapeDtypeStruct(v.shape, v.dtype,
                                        sharding=shardings[k])
                for k, v in pool.items()}
        shard_shape = shardings[first].shard_shape(pool[first].shape)
    slots, table_len = 2, 8
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    temps = jax.ShapeDtypeStruct((slots,), jnp.float32)
    seeds = jax.ShapeDtypeStruct((slots,), jnp.uint32)
    if entry == "_paged_step":
        args = (i32(slots), i32(slots), i32(slots, table_len),
                _ONE_BUFFER_WINDOW, temps, seeds)
    elif entry == "_paged_prefill_chunk":
        args = (i32(_ONE_BUFFER_BT), i32(table_len), i32(), i32(),
                i32(), _ONE_BUFFER_WINDOW, i32(slots), i32(),
                jax.ShapeDtypeStruct((), jnp.uint32),
                jax.ShapeDtypeStruct((), jnp.float32))
    else:
        args = (i32(slots), i32(slots, 3), i32(slots), i32(slots),
                i32(slots, table_len), _ONE_BUFFER_WINDOW, temps, seeds)
    compiled = getattr(decode_engine, entry).lower(
        cfg, params, pool, *args).compile()
    nbytes = lambda tree: sum(a.size * a.dtype.itemsize
                              for a in jax.tree.leaves(tree))
    return compiled, nbytes(pool) // tp, nbytes(params), shard_shape


@pytest.mark.parametrize("family,entry,quantized,tp", [
    (family, entry, quantized, 1)
    for family in ("llama", "mixtral", "gemma")
    for entry in ("_paged_step", "_paged_prefill_chunk",
                  "_paged_spec_step")
    for quantized in (False, True)
] + [
    # The latent pool (two leaves, two scans carrying them; no int8
    # form, no tp).
    ("deepseek", entry, False, 1)
    for entry in ("_paged_step", "_paged_prefill_chunk",
                  "_paged_spec_step")
] + [
    # The sharded pool (kv_heads over tp; head_dim for gemma's single
    # KV head): the form a tp=4 replica runs.
    ("llama", "_paged_step", False, 2),
    ("llama", "_paged_prefill_chunk", False, 2),
    ("llama", "_paged_spec_step", False, 2),
    ("llama", "_paged_step", True, 2),
    ("gemma", "_paged_step", False, 2),
    ("mixtral", "_paged_prefill_chunk", False, 2),
])
def test_paged_entry_points_hold_one_pool_buffer(family, entry,
                                                 quantized, tp):
    """The pool is ONE buffer from entry to exit of every paged
    program: the layer scan carries the stack and each layer scatters
    into and gathers from ``[li, ...]`` of it. What shows it is the
    compiled program, not the donation: its temporaries stay under a
    quarter of the pool (of one shard's pool under tp), and its text
    holds no copy, dynamic-slice or dynamic-update-slice whose result
    has the pool's or one layer's pool's shape. The scanned-in,
    stacked-out form this replaced read 1.25-1.78 times the pool in
    temporaries here (PERF.md, PR 27)."""
    import re
    compiled, pool_bytes, param_bytes, kshape = _compile_paged_entry(
        entry, family, quantized, tp)
    assert pool_bytes >= 50 * param_bytes
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.25 * pool_bytes, (
        f"temporaries {temp} of a {pool_bytes}-byte pool")
    dims = {",".join(map(str, kshape)), ",".join(map(str, kshape[1:]))}
    moved = [m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?(\S+ = \w+\[([\d,]*)\]\S* "
        r"(?:copy|dynamic-slice|dynamic-update-slice))\(",
        compiled.as_text(), re.M) if m.group(2) in dims]
    assert not moved, moved


def test_paged_tp_engine_bit_identical_to_dense_single(
        reference_stream):
    """The TP engine (params by param_specs, POOL by the same
    cache_specs sharding, tp=2 mesh) reproduces the unsharded
    row-cache reference bit-identically in f32 — the full parity chain
    paged+sharded == row cache+unsharded, greedy and seeded."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=128),
                              dtype=jnp.float32)
    params = llama.init(cfg, jax.random.key(0))
    topo = gang_replica.ReplicaTopology(hosts=1, ici_axes={"tp": 2})
    mesh, rules = gang_replica.build_mesh(topo)
    sparams = gang_replica.shard_params(cfg, params, mesh, rules)
    reqs = [([1, 2, 3, 4, 5], 8, 0.0, 0),
            ([7, 9, 11], 10, 0.8, 123),
            ([4] * 70, 6, 0.0, 0),          # chunked prefill path
            ([5, 6], 8, 1.1, 7)]

    def run(engine):
        out = []
        try:
            handles = [engine.submit(p, max_tokens=mt,
                                     temperature=t, seed=s)
                       for p, mt, t, s in reqs]
            for h in handles:
                out.append(h.result(timeout=600.0))
        finally:
            engine.shutdown()
        return out

    tp_paged = run(DecodeEngine(cfg, sparams, slots=2, max_seq=128,
                                mesh=mesh, rules=rules).start())
    assert tp_paged == [
        reference_stream(llama, cfg, params, p, mt, temperature=t,
                         seed=s, max_seq=128)
        for p, mt, t, s in reqs]


# ============================================ gang lockstep + config
def test_paged_same_admission_sequence_same_block_tables():
    """The follower-mirror property paging adds to the gang contract:
    two engines fed the identical admission sequence step-for-step
    allocate identical block tables AND produce identical streams —
    pool state is a pure function of the (mirrored) request order."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    rng = random.Random(6)
    seq = [([rng.randint(1, 127) for _ in range(rng.randint(4, 20))],
            rng.randint(1, 5)) for _ in range(8)]

    def run():
        eng = DecodeEngine(cfg, params, slots=3, max_seq=64,
                           prefill_chunk=8)
        reqs = [eng.submit(p, max_tokens=mt) for p, mt in seq]
        tables = []
        for _ in range(400):
            eng._admit()
            tables.append(eng._table.copy())
            did = eng._prefill_one()
            did = eng._decode_step() or did
            if not did and not eng._waiting:
                break
        return [r.result(timeout=5.0) for r in reqs], tables

    toks_a, tables_a = run()
    toks_b, tables_b = run()
    assert toks_a == toks_b
    assert len(tables_a) == len(tables_b)
    for ta, tb in zip(tables_a, tables_b):
        np.testing.assert_array_equal(ta, tb)


def test_kv_geometry_single_derivation_no_drift():
    """resolve_kv_geometry IS what the engine runs: the handshake dict
    serve_llm computes equals DecodeEngine.kv_config() for the same
    inputs — including the auto-sized pool, which raw STPU_KV_* knobs
    cannot express (two hosts with identical knobs but different slot
    counts auto-size DIFFERENT pools; the effective dict catches it)."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, params, slots=3, max_seq=64,
                       prefill_chunk=8)
    geo = decode_engine.resolve_kv_geometry(
        slots=3, max_seq=64, prefill_chunk=8)
    assert eng.kv_config() == geo
    assert geo["pool_blocks"] == 3 * (64 // 8) + 1
    # Same knobs, different slot count -> different effective pool.
    other = decode_engine.resolve_kv_geometry(
        slots=4, max_seq=64, prefill_chunk=8)
    assert other != geo


def test_gang_welcome_carries_kv_config_and_mismatch_kills_follower():
    """The leader stamps its EFFECTIVE KV geometry into every
    follower's welcome and a disagreeing follower dies at join (rc 1)
    instead of silently running a differently-sized pool out of
    lockstep."""
    topo = gang_replica.ReplicaTopology(hosts=2)
    kv = decode_engine.resolve_kv_geometry(
        slots=4, max_seq=64, prefill_chunk=8)
    leader = gang_replica.GangLeader(topo, port=0, kv_config=kv)
    try:
        # Raw peek: welcome carries the kv block verbatim.
        import json as json_lib
        sock = socket.create_connection(("127.0.0.1", leader.port),
                                        timeout=5.0)
        wf, rf = sock.makefile("wb"), sock.makefile("rb")
        gang_replica._send_line(wf, {"op": "hello", "rank": 1,
                                     "pid": 1})
        welcome = json_lib.loads(rf.readline())
        assert welcome["kv"] == kv
        sock.close()

        class _StubEngine:
            def start(self):
                return self

            def shutdown(self):
                pass

        rc_box = []

        def follower():
            # Identical raw knobs, different slot count: the effective
            # geometry differs (auto-sized pool), and must be fatal.
            rc_box.append(gang_replica.follower_serve(
                _StubEngine, topo, f"127.0.0.1:{leader.port}", rank=1,
                kv_config=decode_engine.resolve_kv_geometry(
                    slots=8, max_seq=64, prefill_chunk=8)))

        t = threading.Thread(target=follower, daemon=True)
        t.start()
        t.join(timeout=30.0)
        assert rc_box == [1]
    finally:
        leader.shutdown()


# ==================================================== metrics surface
def test_paged_pool_metrics_exposed():
    """Pool gauges and the zero-copy counter land in the process
    registry (and therefore the replica /metrics + LB merge)."""
    from skypilot_tpu.observability import metrics as metrics_lib
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    zero_before = metrics_lib.REGISTRY.counter(
        "stpu_engine_prefix_zero_copy_hits_total").get()
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8).start()
    try:
        shared = list(range(1, 18))
        eng.submit(shared, max_tokens=2).result(timeout=300.0)
        eng.submit(shared + [19], max_tokens=2).result(timeout=300.0)
    finally:
        eng.shutdown()
    assert metrics_lib.REGISTRY.counter(
        "stpu_engine_prefix_zero_copy_hits_total").get() > zero_before
    text = metrics_lib.render()
    assert "stpu_engine_kv_pool_blocks_total" in text
    assert "stpu_engine_kv_pool_blocks_free" in text
    assert "stpu_engine_kv_pool_blocks_pinned" in text
