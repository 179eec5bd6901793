"""Tiered KV memory: the host-RAM spill tier under the paged trie.

The contract under test, strongest first:

  * re-admitted blocks are BIT-IDENTICAL to cold prefill — greedy and
    seeded sampling, bf16 and int8 KV, single-device and tp=2, all
    three families (the H2D restore writes back the exact rows the
    D2H spill took out, so the block-table gather sees the same
    floats either way);
  * eviction never stalls decode: the spill is an async D2H handoff
    to a background drain, and a wedged drain degrades evictions to
    drop-on-evict (bounded queue) while every stream still finishes;
  * an injected D2H fault ("engine.spill") degrades that one
    eviction to a plain drop — counter bumped, serving uninterrupted,
    never a crashed engine;
  * N-cycle spill/re-admit churn leaks nothing: host-pool bytes,
    device-pool accounting, refcounts and reservations all return to
    baseline;
  * the tier budget is part of the effective KV geometry, so a gang
    follower with a drifted budget fails the welcome comparison.
"""
import dataclasses
import random
import threading
import time
import queue as queue_lib

import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.models import gemma, llama, mixtral
from skypilot_tpu.serve import decode_engine
from skypilot_tpu.serve import gang_replica
from skypilot_tpu.serve import kv_pool
from skypilot_tpu.serve.decode_engine import DecodeEngine
from skypilot_tpu.utils import fault_injection


def _tiny(family="llama"):
    if family == "mixtral":
        return mixtral, mixtral.MixtralConfig.tiny()
    if family == "gemma":
        return gemma, gemma.GemmaConfig.tiny(vocab_size=128)
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


def _drain_to_host(eng, timeout=30.0):
    """Force every evictable device block into the host tier (each
    eviction must SPILL, not drop) and wait for the D2H drains to
    land so the next match is a pure host-tier hit."""
    while True:
        out = eng.prefix_cache.evict_one()
        if not out:
            break
        assert out == "spilled", out
    deadline = time.monotonic() + timeout
    while eng.spill_in_flight() > 0:
        assert time.monotonic() < deadline, "spill drain never landed"
        time.sleep(0.005)


# ================================================ host pool accounting
def test_host_block_pool_accounting_budget_and_inflight():
    import numpy as np
    pool = kv_pool.HostBlockPool(budget_bytes=3 * 64)
    blk = {"k": np.zeros(16, np.float32)}       # 64 bytes per entry

    # In-flight protocol: has() counts a kicked-but-unlanded spill
    # (the trie must keep the node), get() does not (admission cannot
    # restore bytes that are not on host yet).
    pool.mark_inflight(("a",))
    assert pool.has(("a",)) and pool.get(("a",)) is None
    pool.put(("a",), dict(blk))
    assert pool.stats()["inflight"] == 0        # landing clears it
    assert pool.get(("a",)) is not None
    assert pool.stats()["rehits"] == 1

    # LRU within the byte budget: 3 entries fit, the 4th drops the
    # least-recently-USED (a was just rehit, so b goes first).
    pool.put(("b",), dict(blk))
    pool.put(("c",), dict(blk))
    pool.get(("a",))
    pool.put(("d",), dict(blk))
    assert not pool.has(("b",))
    assert pool.has(("a",)) and pool.has(("c",)) and pool.has(("d",))
    assert pool.stats()["lru_dropped"] == 1
    assert pool.stats()["bytes"] == 3 * 64

    # An entry bigger than the whole budget is refused outright
    # (never evict the world for one oversized block).
    assert not pool.put(("big",), {"k": np.zeros(128, np.float32)})
    assert pool.has(("a",))                     # nothing was evicted

    pool.discard(("a",))
    assert not pool.has(("a",))
    assert pool.stats()["blocks"] == 2


# ======================================= bit-parity: spill -> re-admit
@pytest.mark.parametrize("family", ["llama", "mixtral", "gemma"])
def test_tier_readmit_bit_identical_cold_prefill(family):
    """Greedy AND seeded streams after a full spill/re-admit cycle
    equal the cold streams token-for-token (and the greedy one equals
    the fixed-path reference), with the warm request measurably
    cheaper in prefill chunks."""
    mdl, cfg = _tiny(family)
    params = mdl.init(cfg, jax.random.key(0))
    rng = random.Random(1)
    pg = [rng.randint(1, cfg.vocab_size - 1) for _ in range(17)]
    ps = [rng.randint(1, cfg.vocab_size - 1) for _ in range(19)]
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8,
                       prefix_cache_mb=8).start()
    try:
        cold_g = eng.submit(pg, max_tokens=4)
        cold_s = eng.submit(ps, max_tokens=4, temperature=0.9, seed=17)
        cold_g_toks = cold_g.result(timeout=300.0)
        cold_s_toks = cold_s.result(timeout=300.0)

        _drain_to_host(eng)
        assert eng.prefix_cache.stats()["host_chunks"] >= 4

        warm_g = eng.submit(pg, max_tokens=4)
        warm_s = eng.submit(ps, max_tokens=4, temperature=0.9, seed=17)
        assert warm_g.result(timeout=300.0) == cold_g_toks
        assert warm_s.result(timeout=300.0) == cold_s_toks
        ref = mdl.decode(cfg, params, jnp.asarray([pg], jnp.int32),
                         jnp.int32(len(pg)), 4, len(pg) + 4)
        assert cold_g_toks == [int(t) for t in ref[0]]
        assert warm_g.cached_prompt_tokens == 16
        assert warm_s.cached_prompt_tokens == 16
        assert warm_g.prefill_chunks < cold_g.prefill_chunks
        tier = eng.host_tier_stats()
        assert tier["readmitted_blocks"] >= 4
        assert tier["rehits"] >= 4
    finally:
        eng.shutdown()


def test_tier_readmit_bit_identical_int8_kv():
    """The quantized pool spills int8 payloads + scale leaves and
    re-admits them bit-identically — transfers at half the bf16
    bytes, same streams."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    prompt = [int(t) for t in jax.random.randint(
        jax.random.key(2), (21,), 1, 128)]
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8, kv_quant=True,
                       prefix_cache_mb=8).start()
    try:
        cold = eng.submit(prompt, max_tokens=5)
        cold_toks = cold.result(timeout=300.0)
        seeded_cold = eng.submit(prompt, max_tokens=5,
                                 temperature=0.8,
                                 seed=3).result(timeout=300.0)
        _drain_to_host(eng)
        warm = eng.submit(prompt, max_tokens=5)
        assert warm.result(timeout=300.0) == cold_toks
        assert eng.submit(prompt, max_tokens=5, temperature=0.8,
                          seed=3).result(timeout=300.0) == seeded_cold
        assert warm.cached_prompt_tokens == 16
        assert eng.host_tier_stats()["readmitted_blocks"] >= 2
    finally:
        eng.shutdown()


def test_tier_readmit_bit_identical_tp2():
    """The tp=2 sharded engine (pool sharded by cache_specs) spills
    and re-admits through the same seam: the sharded slices land on
    host, restore into the sharded pool, and the warm stream stays
    bit-identical in f32."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=128),
                              dtype=jnp.float32)
    params = llama.init(cfg, jax.random.key(0))
    topo = gang_replica.ReplicaTopology(hosts=1, ici_axes={"tp": 2})
    mesh, rules = gang_replica.build_mesh(topo)
    sparams = gang_replica.shard_params(cfg, params, mesh, rules)
    prompt = [int(t) for t in jax.random.randint(
        jax.random.key(4), (18,), 1, 128)]
    eng = DecodeEngine(cfg, sparams, slots=2, max_seq=64,
                       prefill_chunk=8, mesh=mesh, rules=rules,
                       prefix_cache_mb=8).start()
    try:
        cold = eng.submit(prompt, max_tokens=5)
        cold_toks = cold.result(timeout=600.0)
        _drain_to_host(eng)
        warm = eng.submit(prompt, max_tokens=5)
        assert warm.result(timeout=600.0) == cold_toks
        assert warm.cached_prompt_tokens == 16
        assert eng.host_tier_stats()["readmitted_blocks"] >= 2
    finally:
        eng.shutdown()


# ============================================= churn leaks nothing
def test_tier_churn_accounting_identity():
    """20 seeded admit/evict/rehit cycles over a fixed prompt set:
    after the warm-up cycle populates the (inclusive) host tier, every
    later cycle must return host bytes/blocks, device free-list,
    reservations and refcounts to the same baseline."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8, prefix_cache_mb=8)
    rng = random.Random(11)
    prompts = [[rng.randint(1, 127) for _ in range(rng.randint(17, 25))]
               for _ in range(4)]

    def cycle():
        for p in prompts:
            eng.submit(p, max_tokens=rng.randint(1, 3))
            _drive(eng)
        _drain_to_host(eng)

    cycle()                                    # warm-up fills the tier
    base = eng.host_tier_stats()
    for _ in range(20):
        cycle()
        now = eng.host_tier_stats()
        assert now["bytes"] == base["bytes"]
        assert now["blocks"] == base["blocks"]
        assert now["lru_dropped"] == base["lru_dropped"] == 0
        assert now["evict_drops"] == 0
    pool = eng._pool
    # Everything is host-resident: the device pool is fully free, no
    # reservations or pins are outstanding, and the trie still spans
    # the full prompt set (host-side).
    assert pool.free_blocks() == pool.usable_blocks
    assert pool._reserved == 0
    assert all(n.refs == 0 for n in eng.prefix_cache.nodes())
    assert all(n.block < 0 for n in eng.prefix_cache.nodes())
    stats = eng.prefix_cache.stats()
    assert stats["host_chunks"] == stats["chunks"] == base["blocks"]
    eng.shutdown()


# ==================================== decode never blocks on a spill
def test_decode_never_blocks_on_wedged_spill_drain():
    """Monkeypatch bomb: the drain thread is frozen mid-store and the
    spill queue shrunk to 2, so in-flight spills pile up and the
    bounded queue fills. Every stream must still complete — evictions
    past the backlog degrade to drops, and the compute loop never
    waits on the host tier."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8, prefix_cache_mb=64)
    eng._spill_q = queue_lib.Queue(maxsize=2)
    unfreeze = threading.Event()
    orig_put = eng._host_pool.put

    def frozen_put(path, arrays):
        unfreeze.wait(timeout=60.0)
        return orig_put(path, arrays)

    eng._host_pool.put = frozen_put
    eng.start()
    rng = random.Random(13)
    try:
        reqs = [eng.submit([rng.randint(1, 127) for _ in range(17)],
                           max_tokens=2) for _ in range(12)]
        for r in reqs:
            assert len(r.result(timeout=120.0)) == 2
        stats = eng.prefix_cache.stats()
        assert stats["spills"] >= 1             # tier was exercised...
        assert stats["drops"] >= 1              # ...and backlog dropped
        assert eng.spill_in_flight() >= 1       # while still wedged
    finally:
        unfreeze.set()
        eng.shutdown()


# ================================================ fault seam degrades
def test_injected_spill_fault_degrades_to_drop():
    """engine.spill firing makes THAT eviction a plain drop-on-evict:
    outcome counted, the prefix re-prefills cold, the engine never
    crashes."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    prompt = list(range(1, 18))
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8,
                       prefix_cache_mb=8).start()
    try:
        cold_toks = eng.submit(prompt,
                               max_tokens=3).result(timeout=300.0)
        with fault_injection.inject("engine.spill"):
            while True:
                out = eng.prefix_cache.evict_one()
                if not out:
                    break
                assert out == "dropped"
        stats = eng.prefix_cache.stats()
        assert stats["drops"] == 2 and stats["spills"] == 0
        assert eng.host_tier_stats()["blocks"] == 0
        # Serving continues: the dropped prefix simply prefills cold
        # again (and spills cleanly once the fault is disarmed).
        again = eng.submit(prompt, max_tokens=3)
        assert again.result(timeout=300.0) == cold_toks
        assert again.cached_prompt_tokens == 0
        _drain_to_host(eng)
        assert eng.prefix_cache.stats()["spills"] >= 1
    finally:
        eng.shutdown()


# ======================================= geometry rides the handshake
def test_tier_budget_is_kv_geometry():
    """host_mb is part of the effective KV geometry dict the gang
    welcome compares — a follower with a drifted tier budget produces
    a different dict and dies at join (the comparison is pinned fatal
    by test_paged_kv's welcome test)."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    geo = decode_engine.resolve_kv_geometry(
        slots=2, max_seq=64, prefill_chunk=8,
        host_cache_mb=8.0)
    assert geo["host_mb"] == 8.0
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8, prefix_cache_mb=8)
    assert eng.kv_config() == geo
    drifted = decode_engine.resolve_kv_geometry(
        slots=2, max_seq=64, prefill_chunk=8,
        host_cache_mb=64.0)
    assert drifted != geo
    eng.shutdown()


def test_tier_off_by_zero_budget():
    """prefix_cache_mb=0 disables the tier: evictions drop like the
    pre-tier engine and the introspection surface reports empty."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8, prefix_cache_mb=0)
    eng.submit(list(range(1, 18)), max_tokens=2)
    _drive(eng)
    assert eng.prefix_cache.evict_one() == "dropped"
    assert eng.host_tier_stats() == {}
    assert eng.spill_in_flight() == 0
    assert "host_mb" in eng.kv_config()         # geometry still pinned
    assert eng.kv_config()["host_mb"] == 0.0
    eng.shutdown()


# ==================================================== metrics surface
def test_tier_metrics_exposed():
    """Eviction outcomes, tier hits and the host gauges land in the
    process registry (and therefore replica /metrics + LB merge)."""
    from skypilot_tpu.observability import metrics as metrics_lib
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    evs = metrics_lib.REGISTRY.counter(
        "stpu_engine_kv_pool_evictions_total",
        labelnames=("outcome",))
    spilled_before = evs.labels(outcome="spilled").get()
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8,
                       prefix_cache_mb=8).start()
    try:
        prompt = list(range(20, 37))
        eng.submit(prompt, max_tokens=2).result(timeout=300.0)
        _drain_to_host(eng)
        eng.submit(prompt, max_tokens=2).result(timeout=300.0)
    finally:
        eng.shutdown()
    assert evs.labels(outcome="spilled").get() >= spilled_before + 2
    text = metrics_lib.render()
    assert "stpu_engine_kv_host_bytes" in text
    assert "stpu_engine_kv_host_blocks" in text
    assert 'stpu_engine_kv_tier_hits_total{tier="host"}' in text
    assert "stpu_engine_kv_host_readmitted_blocks_total" in text
