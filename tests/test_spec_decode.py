"""Speculative decoding on the engine hot path — self-speculative
n-gram drafts with multi-token paged verification.

The contract under test, strongest first:

  * speculative output is BIT-IDENTICAL to non-speculative decode and
    to the row-cache reference (``models.<family>.decode``) — greedy
    AND seeded sampling, all three families (targets are re-sampled with the engine's own
    fold_in(seed, pos) keys, so rejection sampling against the
    deterministic n-gram draft degenerates to exact-match acceptance
    and the stream can never change, only its wall clock);
  * rejected-suffix rollback is safe: the grown block-table tail is
    truncated back into the pool (reservation returned), and a
    verify window clamped near a request's token budget never writes
    where it could corrupt valid rows;
  * the TP-sharded engine drafts/accepts identically to the
    single-device one, and the same admission sequence reproduces the
    same block tables under speculation (the gang lockstep property);
  * cancel-mid-verify releases every pool reference; an injected
    ``engine.verify`` fault rides the EngineSupervisor restart ladder;
  * acceptance telemetry reaches /metrics, stepstats and /perf, and
    the STPU_SPEC_* knobs are registered in the env contract and the
    gang kv-handshake geometry.
"""
import dataclasses
import random
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import gemma, llama, mixtral
from skypilot_tpu.serve import decode_engine
from skypilot_tpu.serve import gang_replica
from skypilot_tpu.serve.decode_engine import DecodeEngine, EngineError
from skypilot_tpu.utils import fault_injection


def _tiny(family="llama"):
    if family == "mixtral":
        return mixtral, mixtral.MixtralConfig.tiny()
    if family == "gemma":
        return gemma, gemma.GemmaConfig.tiny(vocab_size=128)
    return llama, llama.LlamaConfig.tiny(vocab_size=128)


def _drive(engine, rounds=400):
    """Step an UNSTARTED engine deterministically until idle."""
    for _ in range(rounds):
        engine._admit()
        did = engine._prefill_one()
        did = engine._decode_step() or did
        if not did and not engine._waiting:
            return
    raise AssertionError("engine did not quiesce")


def _mixed_specs(cfg, seed=0, n=3):
    """Ragged mix plus a repetitive prompt that guarantees drafting."""
    rng = random.Random(seed)
    specs = [([rng.randint(1, cfg.vocab_size - 1)
               for _ in range(rng.randint(2, 19))],
              rng.randint(1, 8)) for _ in range(n)]
    specs.append(([5, 6, 7] * 6, 10))
    return specs


# =========================================== bit-identity: all families
@pytest.mark.parametrize("family", ["llama", "mixtral", "gemma"])
def test_spec_greedy_bit_identical_dense_and_paged(family,
                                                   reference_stream):
    """Greedy speculative streams equal the non-speculative engine's
    AND the row-cache reference's token-for-token, with real drafting
    exercised (the repetitive prompt forces verify steps; the ragged
    ones force rejections)."""
    mdl, cfg = _tiny(family)
    params = mdl.init(cfg, jax.random.key(0))
    specs = _mixed_specs(cfg)

    def run(spec_k):
        eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                           prefill_chunk=8,
                           spec_k=spec_k, spec_ngram=2).start()
        try:
            reqs = [eng.submit(p, max_tokens=mt) for p, mt in specs]
            return ([r.result(timeout=300.0) for r in reqs],
                    sum(r.spec_drafted for r in reqs))
        finally:
            eng.shutdown()

    base, zero = run(0)
    assert zero == 0
    spec, drafted = run(4)
    assert spec == base
    assert drafted > 0
    for (p, mt), got in zip(specs, spec):
        assert got == reference_stream(mdl, cfg, params, p, mt,
                                       chunk=8), (p, mt)


def test_spec_seeded_sampling_parity(reference_stream):
    """temperature > 0 streams are bit-identical with speculation on,
    and are the row-cache reference's under the same keys:
    the verify targets are sampled with the SAME fold_in(seed, pos)
    keys the 1-token step folds, so acceptance is exact-match and the
    distribution is preserved trivially — the output IS the
    non-speculative sample stream."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    # Near-greedy temperatures settle into draftable cycles (both
    # accepts and rejections fire — probed offline); the hot one
    # exercises pure sampling parity even when nothing drafts.
    specs = [([5, 6, 7] * 6, 14, 0.2, 17),
             ([9, 9, 9, 9, 9, 9, 9, 9], 14, 0.3, 4),
             ([1, 2, 3, 4, 5], 8, 1.1, 123)]

    def run(spec_k):
        eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                           prefill_chunk=8,
                           spec_k=spec_k, spec_ngram=2).start()
        try:
            reqs = [eng.submit(p, max_tokens=mt, temperature=t,
                               seed=s) for p, mt, t, s in specs]
            return ([r.result(timeout=300.0) for r in reqs],
                    sum(r.spec_drafted for r in reqs))
        finally:
            eng.shutdown()

    base, _ = run(0)
    spec, drafted = run(4)
    assert spec == base
    assert drafted > 0
    assert spec == [reference_stream(mdl, cfg, params, p, mt,
                                     temperature=t, seed=s, chunk=8)
                    for p, mt, t, s in specs]


def test_spec_window_clamped_near_token_budget_and_row_end():
    """A request one token from its budget must not draft (k clamps to
    remaining - 1), and a long prompt decoding up to the row end still
    streams bit-identically — the verify window's out-of-bounds writes
    are DROPPED, never clamped onto valid rows (a clamped
    dynamic_update_slice would smear draft K/V over the prompt)."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    prompt = [3, 4] * 27                      # 54 tokens, max_seq 64
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8, spec_k=4, spec_ngram=2)
    one = eng.submit(prompt, max_tokens=1)    # remaining - 1 == 0
    long = eng.submit(prompt[:-1] + [9], max_tokens=9)
    _drive(eng)
    assert one.result(timeout=5.0)
    assert one.spec_drafted == 0
    got = long.result(timeout=5.0)
    assert long.spec_drafted > 0              # windows reached the end
    ref_eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                           prefill_chunk=8)
    ref = ref_eng.submit(prompt[:-1] + [9], max_tokens=9)
    _drive(ref_eng)
    assert got == ref.result(timeout=5.0)


# ==================================================== TP + determinism
def test_spec_tp_paged_engine_bit_identical_to_dense_single(
        reference_stream):
    """The TP-sharded speculative engine reproduces the unsharded
    row-cache reference bit-identically in f32 — speculation composes
    with the full sharded serving path."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=128),
                              dtype=jnp.float32)
    params = llama.init(cfg, jax.random.key(0))
    topo = gang_replica.ReplicaTopology(hosts=1, ici_axes={"tp": 2})
    mesh, rules = gang_replica.build_mesh(topo)
    sparams = gang_replica.shard_params(cfg, params, mesh, rules)
    reqs = [([5, 6, 7] * 6, 10, 0.0, 0),
            ([7, 9, 11], 8, 0.8, 123),
            ([4] * 70, 6, 0.0, 0),            # chunked prefill path
            ([9] * 8, 12, 0.7, 7)]

    def run(engine):
        out, drafted = [], 0
        try:
            handles = [engine.submit(p, max_tokens=mt,
                                     temperature=t, seed=s)
                       for p, mt, t, s in reqs]
            for h in handles:
                out.append(h.result(timeout=600.0))
            drafted = sum(h.spec_drafted for h in handles)
        finally:
            engine.shutdown()
        return out, drafted

    ref = [reference_stream(llama, cfg, params, p, mt, temperature=t,
                            seed=s, max_seq=128)
           for p, mt, t, s in reqs]
    tp_spec, drafted = run(DecodeEngine(
        cfg, sparams, slots=2, max_seq=128, mesh=mesh, rules=rules,
        spec_k=4, spec_ngram=2).start())
    assert tp_spec == ref
    assert drafted > 0


def test_spec_same_admission_sequence_same_tables_and_tokens():
    """The gang lockstep property survives speculation: drafting and
    acceptance are pure functions of the mirrored admission sequence,
    so two engines fed identical submissions step-for-step allocate
    identical block tables (including verify growth + rejected-suffix
    truncation) and emit identical streams."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    seq = _mixed_specs(cfg, seed=6, n=6)

    def run():
        eng = DecodeEngine(cfg, params, slots=3, max_seq=64,
                           prefill_chunk=8, spec_k=4,
                           spec_ngram=2)
        reqs = [eng.submit(p, max_tokens=mt) for p, mt in seq]
        tables = []
        for _ in range(400):
            eng._admit()
            tables.append(eng._table.copy())
            did = eng._prefill_one()
            did = eng._decode_step() or did
            if not did and not eng._waiting:
                break
        return ([r.result(timeout=5.0) for r in reqs],
                sum(r.spec_drafted for r in reqs), tables)

    toks_a, drafted_a, tables_a = run()
    toks_b, drafted_b, tables_b = run()
    assert toks_a == toks_b
    assert drafted_a == drafted_b > 0
    assert len(tables_a) == len(tables_b)
    for ta, tb in zip(tables_a, tables_b):
        np.testing.assert_array_equal(ta, tb)


# ======================================================= draft matcher
def test_spec_ngram_draft_lookup_and_self_match_protection():
    """The incremental index proposes the MOST RECENT earlier
    occurrence's continuation, never matches the lookup pattern
    against itself, and clamps drafts to remaining - 1."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, params, slots=1, max_seq=64,
                       prefill_chunk=8, spec_k=4, spec_ngram=2)
    slot = eng._slots[0]
    req = eng.submit(list(range(1, 9)), max_tokens=20)
    eng._admit()
    assert slot.request is req
    # Draft state seeds LAZILY on the compute path (first prefill
    # touch), never under the admission lock — an un-seeded slot
    # simply has no draft.
    assert not slot.history and eng._draft(slot) == []
    eng._spec_init(slot, req)
    # History [1..8]: trailing bigram (7, 8) has no earlier occurrence.
    assert eng._draft(slot) == []
    # Feed a repeat of an interior bigram: (3, 4) occurred at s=2, its
    # continuation is [5, 6, 7, 8] — exactly the k=4 draft.
    for tok in (3, 4):
        slot.generated += 1
        eng._spec_track(slot, tok)
    assert eng._draft(slot) == [5, 6, 7, 8]
    # Most recent occurrence wins: append (3, 4) -> 9; the trailing
    # (3, 4) now resolves to the later occurrence, whose continuation
    # starts with 9.
    for tok in (9, 3, 4):
        slot.generated += 1
        eng._spec_track(slot, tok)
    assert eng._draft(slot)[0] == 9
    # remaining - 1 clamp: 13 generated of 20 -> k = min(4, 6).
    assert len(eng._draft(slot)) <= 4
    slot.generated = 19
    assert eng._draft(slot) == []             # one token owed: no draft


def test_spec_auto_disable_below_min_accept():
    """A slot whose drafts keep getting rejected stops drafting once
    >= 16 drafted tokens fall below the acceptance floor — the verify
    window stops widening for traffic that never repeats — and the
    stream stays bit-identical throughout."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    prompt = [5, 6, 7] * 6

    def run(min_accept):
        eng = DecodeEngine(cfg, params, slots=1, max_seq=64,
                           prefill_chunk=8, spec_k=4, spec_ngram=2,
                           spec_min_accept=min_accept)
        req = eng.submit(prompt, max_tokens=40)
        _drive(eng)
        return req.result(timeout=5.0), req.spec_drafted, \
            eng._slots[0]

    # min_accept > 1 is unreachable: drafting must shut off right
    # after the 16-draft grace window instead of running forever.
    toks_off, drafted_off, _ = run(min_accept=1.5)
    toks_on, drafted_on, _ = run(min_accept=0.0)
    assert toks_off == toks_on                # parity is unconditional
    assert drafted_on > drafted_off
    assert drafted_off <= 16 + 4              # grace window + one step


# ============================================== lifecycle + pool refs
def test_spec_cancel_mid_verify_releases_pool_refs():
    """Cancel landing between verify steps of a speculating paged slot
    releases every pool reference: aliased prefix pins drop, grown
    decode blocks free, reservations return — the churn identity
    free + trie == usable holds with zero refs outstanding."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8, spec_k=4,
                       spec_ngram=2)
    shared = [5, 6, 7] * 6                    # 18 tokens: 2 full chunks
    first = eng.submit(shared, max_tokens=1)
    _drive(eng)
    assert first.result(timeout=5.0)
    assert eng.prefix_cache.stats()["chunks"] == 2

    req = eng.submit(shared + [9, 9, 9], max_tokens=16)
    eng._admit()
    assert eng._slots[0].held                 # aliased prefix pinned
    # Run prefill + a couple of verify steps so the slot is
    # mid-speculation with grown decode blocks, then cancel.
    for _ in range(6):
        eng._prefill_one()
        eng._decode_step()
    assert req.spec_drafted > 0               # really mid-verify
    req.cancel()
    _drive(eng)
    try:
        req.result(timeout=5.0)
    except EngineError:
        pass                                  # cancelled is clean either way
    pool = eng._pool
    assert all(s.request is None for s in eng._slots)
    assert pool.free_blocks() + len(eng.prefix_cache.nodes()) == \
        pool.usable_blocks
    assert pool._reserved == 0
    assert all(n.refs == 0 for n in eng.prefix_cache.nodes())


def test_spec_churn_500_cycles_accounting_clean():
    """The paged 500-cycle admit/cancel churn holds its accounting
    identity with speculation armed — verify growth, truncation and
    cancel interleave without leaking a block or a reservation."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8, spec_k=4,
                       spec_ngram=2)
    rng = random.Random(7)
    for _ in range(500):
        if rng.random() < 0.4:                # draft-friendly mix
            motif = [rng.randint(1, 127)] * 2
            prompt = motif * rng.randint(5, 12)
        else:
            prompt = [rng.randint(1, 127)
                      for _ in range(rng.randint(9, 30))]
        req = eng.submit(prompt, max_tokens=rng.randint(1, 6))
        eng._admit()
        for _ in range(rng.randint(0, 5)):
            did = eng._prefill_one()
            did = eng._decode_step() or did
            if not did:
                break
        req.cancel()
        _drive(eng)
    pool = eng._pool
    assert all(s.request is None for s in eng._slots)
    assert pool.free_blocks() + len(eng.prefix_cache.nodes()) == \
        pool.usable_blocks
    assert pool._reserved == 0
    assert all(n.refs == 0 for n in eng.prefix_cache.nodes())


# ================================================== chaos + supervisor
def test_spec_injected_verify_fault_rides_restart_ladder():
    """An injected ``engine.verify`` fault crashes the compute loop
    like any real verify-step failure; the EngineSupervisor restarts a
    fresh engine and the replacement serves bit-identical tokens."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    prompt = [5, 6, 7] * 6

    def factory():
        return DecodeEngine(cfg, params, slots=1, max_seq=64,
                            prefill_chunk=8, spec_k=4,
                            spec_ngram=2)

    sup = decode_engine.EngineSupervisor(
        factory, backoff_base=0.05, poll_interval=0.02).start()
    try:
        with fault_injection.inject("engine.verify", times=1):
            req = sup.submit(prompt, max_tokens=10)
            with pytest.raises(EngineError):
                req.result(timeout=60.0)
        deadline = 30.0
        import time
        t0 = time.monotonic()
        while not sup.healthy():
            assert time.monotonic() - t0 < deadline, \
                "supervisor never restarted the engine"
            time.sleep(0.05)
        assert sup.restarts == 1
        got = sup.submit(prompt, max_tokens=10).result(timeout=60.0)
        ref_eng = DecodeEngine(cfg, params, slots=1, max_seq=64,
                               prefill_chunk=8)
        ref = ref_eng.submit(prompt, max_tokens=10)
        _drive(ref_eng)
        assert got == ref.result(timeout=5.0)
    finally:
        sup.shutdown()


# ============================================ telemetry + env contract
def test_spec_counters_and_metrics_surface():
    """Drafted/accepted counters and the acceptance-rate histogram
    land in the process registry (and therefore the replica /metrics
    -> LB merge)."""
    from skypilot_tpu.observability import metrics as metrics_lib
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    drafted_before = metrics_lib.REGISTRY.counter(
        "stpu_engine_spec_drafted_tokens_total").get()
    eng = DecodeEngine(cfg, params, slots=1, max_seq=64,
                       prefill_chunk=8, spec_k=4,
                       spec_ngram=2).start()
    try:
        eng.submit([5, 6, 7] * 6, max_tokens=10).result(timeout=300.0)
    finally:
        eng.shutdown()
    assert metrics_lib.REGISTRY.counter(
        "stpu_engine_spec_drafted_tokens_total").get() > drafted_before
    text = metrics_lib.render()
    assert "stpu_engine_spec_drafted_tokens_total" in text
    assert "stpu_engine_spec_accepted_tokens_total" in text
    assert "stpu_engine_spec_accept_rate_count" in text


def test_spec_stepstats_and_perf_snapshot_carry_acceptance():
    """Armed stepstats records per-step drafted/accepted counts and
    snapshot() (the replica /perf document, which `stpu perf`
    renders) derives the live acceptance rate from the ring."""
    from skypilot_tpu import cli as cli_mod
    from skypilot_tpu.observability import stepstats
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    was_armed = stepstats.ENABLED
    stepstats.arm(ring=512)
    stepstats.reset()
    try:
        eng = DecodeEngine(cfg, params, slots=1, max_seq=64,
                           prefill_chunk=8, spec_k=4,
                           spec_ngram=2).start()
        try:
            req = eng.submit([5, 6, 7] * 6, max_tokens=10)
            req.result(timeout=300.0)
        finally:
            eng.shutdown()
        assert req.spec_drafted > 0
        recs = stepstats.steps_tail()
        assert sum(r.get("spec_drafted", 0) for r in recs) == \
            req.spec_drafted
        snap = stepstats.snapshot()
        assert snap["spec"]["drafted"] == req.spec_drafted
        assert snap["spec"]["accepted"] == req.spec_accepted
        assert 0.0 <= snap["spec"]["accept_rate"] <= 1.0
        rendered = "\n".join(cli_mod._perf_snapshot_lines(snap))
        assert "accept" in rendered and "drafted" in rendered
    finally:
        stepstats.reset()
        if not was_armed:
            stepstats.disarm()


def test_spec_env_knobs_registered_and_in_handshake_geometry():
    """STPU_SPEC_* are registered (stpu-env stays green), and the
    spec knobs ride the effective
    kv-handshake geometry so a gang member drafting differently fails
    the welcome comparison instead of silently diverging tokens."""
    from skypilot_tpu.utils import env_contract
    assert env_contract.get("STPU_SPEC_K").default == "0"
    assert env_contract.get("STPU_SPEC_NGRAM").default == "3"
    assert env_contract.get("STPU_SPEC_MIN_ACCEPT").default == "0.2"

    geo = decode_engine.resolve_kv_geometry(
        slots=2, max_seq=64, prefill_chunk=8, spec_k=4,
        spec_ngram=2, spec_min_accept=0.25)
    assert geo["spec_k"] == 4 and geo["spec_ngram"] == 2
    assert geo["spec_min_accept"] == 0.25
    other = decode_engine.resolve_kv_geometry(
        slots=2, max_seq=64, prefill_chunk=8, spec_k=0)
    assert other != geo                       # mismatch is fatal at join

    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    eng = DecodeEngine(cfg, params, slots=2, max_seq=64,
                       prefill_chunk=8, spec_k=4,
                       spec_ngram=2, spec_min_accept=0.25)
    assert eng.kv_config() == geo             # single derivation


def test_serve_llm_default_is_paged_with_spec_selectable():
    """Spec stays opt-in, and a spec-armed replica serves the same
    tokens over HTTP as the models' fixed path."""
    import json
    import urllib.request
    from skypilot_tpu.recipes import serve_llm
    assert serve_llm.ENGINE_SPEC_K == 0

    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    ready = threading.Event()
    httpd = serve_llm.serve(cfg, params, 0, ready_event=ready,
                            engine_slots=2, spec_k=3)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        assert ready.wait(timeout=300)
        assert httpd.engine.engine._spec_k == 3
        port = httpd.server_address[1]
        prompt = [5, 6, 7] * 6
        body = json.dumps({"prompt": prompt,
                           "max_tokens": 8}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            toks = json.loads(resp.read())["tokens"]
        # The reference is a non-speculative engine with the
        # server's own geometry (default prefill chunk, the recipe's
        # max_seq): in bf16 a near-tied argmax depends on the prefill
        # tiling, and tiling is not what this test is about.
        ref_eng = DecodeEngine(
            cfg, params, slots=2,
            max_seq=serve_llm.MAX_PROMPT_TOKENS + serve_llm.MAX_GEN_TOKENS)
        ref = ref_eng.submit(prompt, max_tokens=8)
        _drive(ref_eng)
        assert toks == ref.result(timeout=5.0)
    finally:
        httpd.shutdown()
