"""int8 quantized serving: KV blocks in the paged pool + weight path.

The contract under test, strongest first:

  * the correctness gate is NOT bit-identity — quantization changes
    numerics by design. The gate is a parity suite: top-1 agreement
    with the bf16 fixed-path decode above a pinned per-family
    threshold plus a perplexity-ratio bound, single-device AND
    TP-sharded, all three families;
  * KV-cache donation (codes AND scales) survives every quantized
    paged jitted entry point — prefill, decode step, speculative
    verify — single-device and TP-sharded, all families;
  * the gang welcome handshake rejects quant-geometry drift: a
    follower whose kv_quant flag disagrees with the leader dies at
    join instead of silently running a differently-shaped pool;
  * speculative decoding composes with quantized KV: the seeded greedy
    workload's spec streams equal the same quantized engine without
    speculation, and drafts are actually accepted;
  * 500 seeded admit/cancel cycles on the quantized pool leak nothing
    (the accounting identity free + trie == usable, zero reservations,
    zero pins).
"""
import dataclasses
import math
import random
import socket
import threading

import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.models import gemma, llama, mixtral
from skypilot_tpu.serve import decode_engine
from skypilot_tpu.serve import gang_replica
from skypilot_tpu.serve.decode_engine import DecodeEngine

# Pinned per-family top-1 agreement floors for int8 KV + int8 weights
# vs the bf16 fixed path, on the seeded CPU workloads below (observed:
# llama 0.76-1.0, mixtral 0.74-0.80, gemma 1.0 — the MoE family is
# the most sensitive because near-tie router logits flip experts under
# quantized inputs, changing the whole expert mix for that token).
TOP1_FLOOR = {"llama": 0.70, "mixtral": 0.55, "gemma": 0.85}
# Quantized perplexity may exceed bf16 by at most 10% (observed ratio
# ~1.00 at tiny scale — the bound catches a broken scale path, which
# shows up as a 10-100x blowup, not a drift).
PPL_RATIO_BOUND = 1.10


def _tiny(family):
    if family == "mixtral":
        return mixtral, mixtral.MixtralConfig.tiny()
    if family == "gemma":
        return gemma, gemma.GemmaConfig.tiny(vocab_size=128)
    return llama, llama.LlamaConfig.tiny(vocab_size=128)


def _workload(cfg, n=6, seed=1):
    rng = random.Random(seed)
    return [([rng.randint(1, cfg.vocab_size - 1)
              for _ in range(rng.randint(4, 20))],
             rng.randint(4, 8)) for _ in range(n)]


def _top1_agreement(mdl, cfg, params, specs, streams):
    agree = total = 0
    for (p, mt), got in zip(specs, streams):
        ref = mdl.decode(cfg, params, jnp.asarray([p], jnp.int32),
                         jnp.int32(len(p)), mt, len(p) + mt)
        ref = [int(t) for t in ref[0]]
        agree += sum(a == b for a, b in zip(got, ref))
        total += len(ref)
    return agree / total


def _mean_nll(mdl, cfg, params, toks):
    """Mean next-token NLL over a fixed sequence through the serving
    forward (forward_with_cache handles quantized params; the trainer
    forward() is intentionally bf16-only)."""
    cache = mdl.init_cache(cfg, 1, toks.shape[1] - 1)
    logits, _ = mdl.forward_with_cache(cfg, params, toks[:, :-1],
                                       cache, jnp.int32(0))
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
    return float(-jnp.take_along_axis(lp, toks[:, 1:, None], -1).mean())


# ======================================================= parity gate
@pytest.mark.parametrize("family", ["llama", "mixtral", "gemma"])
def test_quant_parity_single_device(family):
    """int8 KV + int8 weights vs the bf16 fixed path: top-1 agreement
    above the pinned family floor, and quantized perplexity within the
    ratio bound. This is THE correctness gate for quantized serving —
    the bit-parity suites stay bf16-only on purpose."""
    mdl, cfg = _tiny(family)
    params = mdl.init(cfg, jax.random.key(0))
    specs = _workload(cfg)
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8,
                       kv_quant=True, weight_quant=True).start()
    try:
        reqs = [eng.submit(p, max_tokens=mt) for p, mt in specs]
        streams = [r.result(timeout=600.0) for r in reqs]
        assert eng.kv_config()["kv_quant"] == 1
        assert eng.kv_config()["weight_quant"] == 1
    finally:
        eng.shutdown()
    top1 = _top1_agreement(mdl, cfg, params, specs, streams)
    assert top1 >= TOP1_FLOOR[family], (family, top1)

    rng = random.Random(9)
    toks = jnp.asarray([[rng.randint(1, cfg.vocab_size - 1)
                         for _ in range(33)]], jnp.int32)
    nll_bf16 = _mean_nll(mdl, cfg, params, toks)
    nll_q8 = _mean_nll(mdl, cfg, mdl.quantize_params(cfg, params), toks)
    ratio = math.exp(nll_q8 - nll_bf16)
    assert ratio <= PPL_RATIO_BOUND, (family, ratio)


@pytest.mark.parametrize("family", ["llama", "mixtral", "gemma"])
def test_quant_parity_tp_sharded(family):
    """The same parity floor holds for the TP-sharded quantized engine
    (params sharded bf16 THEN quantized inside the engine, pool + scale
    arrays placed by cache_shardings) — the quantize-then-reshard path
    and the scale-aware collectives do not cost extra agreement."""
    topo = gang_replica.ReplicaTopology(hosts=1, ici_axes={"tp": 2})
    mesh, rules = gang_replica.build_mesh(topo)
    mdl, cfg = _tiny(family)
    params = mdl.init(cfg, jax.random.key(0))
    specs = _workload(cfg, n=4)
    sparams = gang_replica.shard_params(cfg, params, mesh, rules)
    eng = DecodeEngine(cfg, sparams, slots=2, max_seq=64,
                       prefill_chunk=8, mesh=mesh, rules=rules,
                       kv_quant=True,
                       weight_quant=True).start()
    try:
        reqs = [eng.submit(p, max_tokens=mt) for p, mt in specs]
        streams = [r.result(timeout=600.0) for r in reqs]
    finally:
        eng.shutdown()
    top1 = _top1_agreement(mdl, cfg, params, specs, streams)
    assert top1 >= TOP1_FLOOR[family], (family, top1)


# ========================================================== donation
def test_quant_entry_points_keep_donation_sharded_and_single():
    """The quantized pool — int8 codes AND f32 scales — stays donated
    through all three paged jitted entry points (prefill chunk, decode
    step, speculative verify), single-device and TP-sharded, per
    family: the O(layers * blocks) buffer updates in place instead of
    double-buffering HBM."""
    from skypilot_tpu.parallel import mesh as mesh_lib
    mesh = mesh_lib.make_mesh({"tp": 2}, devices=jax.devices()[:2])
    rules = mesh_lib.DEFAULT_RULES
    leaves = ("k", "v", "k_scale", "v_scale")
    for family in ("llama", "mixtral", "gemma"):
        mdl, cfg = _tiny(family)
        cfg = dataclasses.replace(cfg, dtype=jnp.float32)
        for shard in (False, True):
            params = mdl.quantize_params(
                cfg, mdl.init(cfg, jax.random.key(0)))
            pool = mdl.init_paged_cache(cfg, 8, 8, quantized=True)
            assert set(pool) == set(leaves)
            if shard:
                params = gang_replica.shard_params(cfg, params, mesh,
                                                   rules)
                shardings = gang_replica.cache_shardings(cfg, mesh,
                                                         rules)
                pool = jax.device_put(
                    pool, {k: shardings[k] for k in pool})
            table = jnp.ones((2, 8), jnp.int32)

            def assert_donated(old, tag):
                gone = [k for k in leaves if old[k].is_deleted()]
                assert gone == list(leaves), \
                    f"{family} shard={shard} {tag}: donated {gone}"

            old = dict(pool)
            buf = jnp.zeros((8,), jnp.int32).at[:4].set(
                jnp.asarray([1, 2, 3, 4]))
            toks, pool = decode_engine._paged_prefill_chunk(
                cfg, params, pool, buf, table[0], jnp.int32(0),
                jnp.int32(4), jnp.int32(1), 64,
                jnp.zeros((2,), jnp.int32), jnp.int32(0),
                jnp.uint32(0), jnp.float32(0.0))
            assert_donated(old, "prefill")
            old = dict(pool)
            nxt, pool = decode_engine._paged_step(
                cfg, params, pool, toks,
                jnp.asarray([4, 0], jnp.int32), table, 64,
                jnp.zeros((2,), jnp.float32),
                jnp.zeros((2,), jnp.uint32))
            assert_donated(old, "step")
            old = dict(pool)
            _t, _a, _last, pool = decode_engine._paged_spec_step(
                cfg, params, pool, nxt,
                jnp.zeros((2, 2), jnp.int32),
                jnp.asarray([5, 0], jnp.int32),
                jnp.asarray([2, 0], jnp.int32), table, 64,
                jnp.zeros((2,), jnp.float32),
                jnp.zeros((2,), jnp.uint32))
            assert_donated(old, "verify")


# ==================================================== gang handshake
def test_gang_welcome_rejects_quant_geometry_drift():
    """A follower whose kv_quant flag disagrees with the leader's
    effective geometry dies at join (rc 1) — identical raw pool knobs,
    different quant flag, caught by the same dict equality that guards
    pool-size drift (the quant flags ride resolve_kv_geometry)."""
    topo = gang_replica.ReplicaTopology(hosts=2)
    kv = decode_engine.resolve_kv_geometry(
        slots=4, max_seq=64, prefill_chunk=8,
        kv_quant=True, weight_quant=True)
    assert kv["kv_quant"] == 1 and kv["weight_quant"] == 1
    leader = gang_replica.GangLeader(topo, port=0, kv_config=kv)
    try:
        import json as json_lib
        sock = socket.create_connection(("127.0.0.1", leader.port),
                                        timeout=5.0)
        wf, rf = sock.makefile("wb"), sock.makefile("rb")
        gang_replica._send_line(wf, {"op": "hello", "rank": 1,
                                     "pid": 1})
        welcome = json_lib.loads(rf.readline())
        assert welcome["kv"] == kv          # quant flags ride verbatim
        sock.close()

        class _StubEngine:
            def start(self):
                return self

            def shutdown(self):
                pass

        rc_box = []

        def follower():
            # Same pool knobs, kv_quant off: the follower would run a
            # bf16 pool half the leader's logical capacity — fatal.
            rc_box.append(gang_replica.follower_serve(
                _StubEngine, topo, f"127.0.0.1:{leader.port}", rank=1,
                kv_config=decode_engine.resolve_kv_geometry(
                    slots=4, max_seq=64, prefill_chunk=8,
                    kv_quant=False, weight_quant=True)))

        t = threading.Thread(target=follower, daemon=True)
        t.start()
        t.join(timeout=30.0)
        assert rc_box == [1]
    finally:
        leader.shutdown()


# ================================================ speculative decode
def test_spec_decode_parity_with_quantized_kv():
    """Speculative decoding composes with int8 KV: on the seeded
    shared-prefix greedy workload the spec streams equal the SAME
    quantized engine without speculation (verify writes and sequential
    writes land identical quantized rows here), and drafts are
    actually accepted — the speed lever survives quantization."""
    mdl, cfg = _tiny("llama")
    params = mdl.init(cfg, jax.random.key(0))
    rng = random.Random(1)
    shared = [int(t) for t in jax.random.randint(
        jax.random.key(11), (17,), 1, 128)]
    specs = [(shared + [rng.randint(1, 127)
                        for _ in range(rng.randint(1, 6))],
              rng.randint(4, 10)) for _ in range(5)]

    def run(spec_k):
        eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                           prefill_chunk=8, kv_quant=True,
                           weight_quant=True, spec_k=spec_k,
                           spec_ngram=2).start()
        try:
            reqs = [eng.submit(p, max_tokens=mt) for p, mt in specs]
            out = [r.result(timeout=600.0) for r in reqs]
            accepted = sum(r.spec_accepted for r in reqs)
            return out, accepted
        finally:
            eng.shutdown()

    plain, _ = run(0)
    spec, accepted = run(4)
    assert spec == plain
    assert accepted > 0                     # drafts really accepted


# ======================================================== churn leak
def test_quant_pool_500_cycle_churn_accounting_identity():
    """500 seeded admit/cancel cycles (cancel at random prefill/decode
    depth) on the QUANTIZED pool: block release is idempotent with the
    scales array riding along, so free + trie == usable holds at the
    end with zero reservations and zero pins outstanding."""
    mdl, cfg = _tiny("llama")
    params = mdl.init(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8, kv_quant=True)
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
        for _ in range(200):
            eng._admit()
            did = eng._prefill_one()
            did = eng._decode_step() or did
            if not did and not eng._waiting:
                break
    pool = eng._pool
    assert all(s.request is None for s in eng._slots)
    assert pool.free_blocks() + len(eng.prefix_cache.nodes()) \
        == pool.usable_blocks
    assert pool._reserved == 0
    assert all(n.refs == 0 for n in eng.prefix_cache.nodes())
