"""Continuous-batching decode engine + ragged KV-cache decode.

The contract under test, from strongest to weakest layer:

  * split-KV (flash-decode-style) attention == dense masked softmax;
  * batched decode with PER-EXAMPLE prompt lengths matches per-request
    sequential decode token-for-token (greedy) — batch composition
    must never change any row's tokens;
  * the engine (slot scheduling, chunked prefill interleaved with
    decode, slot reuse) reproduces the same tokens — including that
    stale K/V left in a reused slot is never attendable;
  * engine counters land in the observability registry and the replica
    /metrics surface.
"""
import json
import threading
import time
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import gemma, llama, mixtral
from skypilot_tpu.observability import metrics
from skypilot_tpu.serve.decode_engine import DecodeEngine, EngineError


def _ragged_prompts(key, lens, vocab):
    return [jax.random.randint(jax.random.key(key + i), (l,), 1, vocab)
            for i, l in enumerate(lens)]


def _pad(prompts, s_pad):
    b = len(prompts)
    out = jnp.zeros((b, s_pad), jnp.int32)
    for i, p in enumerate(prompts):
        out = out.at[i, :p.shape[0]].set(p)
    return out


@pytest.mark.parametrize("seq_len,block", [(32, 4), (30, 8)])
def test_split_kv_matches_dense_reference(seq_len, block):
    """Blocked online-softmax over the ragged cache == one dense
    masked softmax, across block boundaries — including a cache length
    the block does NOT divide (the clamped-overlap tail window)."""
    B, T, KVH, G, D = 2, 3, 2, 2, 8
    q = jax.random.normal(jax.random.key(0), (B, T, KVH, G, D))
    ck = jax.random.normal(jax.random.key(1), (B, seq_len, KVH, D))
    cv = jax.random.normal(jax.random.key(2), (B, seq_len, KVH, D))
    positions = jnp.array([[18, 19, 20], [7, 8, 9]])
    valid = jnp.array([21, 10])

    out = llama._split_kv_attention(q, ck, cv, positions, valid,
                                    block=block)

    kpos = jnp.arange(seq_len)
    mask = ((kpos[None, None, :] <= positions[..., None]) &
            (kpos[None, None, :] < valid[:, None, None]))
    scores = jnp.einsum("btkgd,bskd->bkgts", q, ck) * (D ** -0.5)
    scores = jnp.where(mask[:, None, None], scores, -1e30)
    dense = jnp.einsum("bkgts,bskd->btkgd",
                       jax.nn.softmax(scores, axis=-1), cv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               rtol=1e-5, atol=1e-6)


def test_ragged_batched_decode_matches_sequential():
    """One batched decode over heterogeneous prompt lengths must equal
    per-request decode token-for-token — the property the fixed-batch
    path enforced by REJECTING (B,) lengths."""
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    lens, mt, s_pad = [3, 7, 5], 6, 8
    prompts = _ragged_prompts(1, lens, 128)

    got = llama.decode(cfg, params, _pad(prompts, s_pad),
                       jnp.asarray(lens), mt, s_pad + mt)
    for i, p in enumerate(prompts):
        ref = llama.decode(cfg, params, p[None, :], jnp.int32(lens[i]),
                           mt, lens[i] + mt)
        np.testing.assert_array_equal(np.asarray(got[i]),
                                      np.asarray(ref[0]))


@pytest.mark.parametrize("family", ["mixtral", "gemma"])
def test_ragged_decode_other_families(family):
    """The (B,) length contract holds through the shared loop for the
    MoE (dense-routed) and MQA/tied-head families too."""
    mdl = {"mixtral": mixtral, "gemma": gemma}[family]
    cfg = mdl.MixtralConfig.tiny() if family == "mixtral" \
        else mdl.GemmaConfig.tiny(vocab_size=128)
    vocab = cfg.vocab_size
    params = mdl.init(cfg, jax.random.key(0))
    lens, mt, s_pad = [2, 5], 4, 6
    prompts = _ragged_prompts(3, lens, vocab)

    got = mdl.decode(cfg, params, _pad(prompts, s_pad),
                     jnp.asarray(lens), mt, s_pad + mt)
    for i, p in enumerate(prompts):
        ref = mdl.decode(cfg, params, p[None, :], jnp.int32(lens[i]),
                         mt, lens[i] + mt)
        np.testing.assert_array_equal(np.asarray(got[i]),
                                      np.asarray(ref[0]))


def test_decode_rejects_mismatched_length_vector():
    cfg = llama.LlamaConfig.tiny(vocab_size=64)
    params = llama.init(cfg, jax.random.key(0))
    prompt = jnp.ones((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="scalar or a"):
        llama.decode(cfg, params, prompt, jnp.asarray([1, 2, 3]), 2, 16)


def test_decode_with_donated_preallocated_cache():
    """The caller-allocated-and-donated cache path (bench + serving)
    produces the same tokens as the internal-allocation path, and the
    donation is actually USABLE (return_cache=True puts the cache in
    the jit output, so XLA can alias the donated input to it)."""
    import warnings
    cfg = llama.LlamaConfig.tiny(vocab_size=64)
    params = llama.init(cfg, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(1), (2, 5), 1, 64)
    ref = llama.decode(cfg, params, prompt, jnp.int32(5), 4, 16)

    decode_jit = jax.jit(
        lambda p, pr, cache: llama.decode(cfg, p, pr, jnp.int32(5), 4,
                                          16, cache=cache,
                                          return_cache=True),
        donate_argnums=(2,))
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "error", message=".*donated buffers were not usable.*")
        got, _ = decode_jit(params, prompt, llama.init_cache(cfg, 2, 16))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_engine_matches_decode_across_slot_reuse():
    """5 ragged greedy requests through 2 slots: every request's
    stream must equal its own fixed-path decode — requests 3..5 reuse
    slots whose rows still hold the previous request's K/V, so any
    leak of stale (masked) cache into attention breaks this."""
    import random
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    engine = DecodeEngine(cfg, params, slots=2, max_seq=64,
                          prefill_chunk=8).start()
    try:
        rng = random.Random(0)
        specs = [([rng.randint(1, 127)
                   for _ in range(rng.randint(1, 19))],
                  rng.randint(1, 8)) for _ in range(5)]
        reqs = [engine.submit(p, max_tokens=mt) for p, mt in specs]
        for (p, mt), req in zip(specs, reqs):
            got = req.result(timeout=300.0)
            ref = llama.decode(cfg, params,
                               jnp.asarray([p], jnp.int32),
                               jnp.int32(len(p)), mt, len(p) + mt)
            assert got == [int(t) for t in ref[0]], (p, mt)
    finally:
        engine.shutdown()


def test_engine_chunked_prefill_long_prompt():
    """A prompt spanning several prefill chunks (chunk 8, prompt 19)
    must decode identically to the single-pass prefill path."""
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    engine = DecodeEngine(cfg, params, slots=2, max_seq=64,
                          prefill_chunk=8).start()
    try:
        prompt = [int(t) for t in jax.random.randint(
            jax.random.key(7), (19,), 1, 128)]
        got = engine.submit(prompt, max_tokens=6).result(timeout=300.0)
        ref = llama.decode(cfg, params, jnp.asarray([prompt]),
                           jnp.int32(19), 6, 32)
        assert got == [int(t) for t in ref[0]]
    finally:
        engine.shutdown()


def test_engine_sampling_reproducible_and_limits():
    """Seeded sampling is slot- and batch-composition-independent;
    oversized and empty requests are rejected upfront."""
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    engine = DecodeEngine(cfg, params, slots=2, max_seq=32,
                          prefill_chunk=8).start()
    try:
        r1 = engine.submit([5, 6, 7], max_tokens=5, temperature=0.8,
                           seed=42).result(timeout=300.0)
        # Second run shares the batch with another live request — the
        # fold_in(seed, position) keys must not notice.
        other = engine.submit([9, 9, 9, 9], max_tokens=8)
        r2 = engine.submit([5, 6, 7], max_tokens=5, temperature=0.8,
                           seed=42).result(timeout=300.0)
        other.result(timeout=300.0)
        assert r1 == r2
        # 2 slots x 32 tokens = 8 blocks of 8: 75 tokens need 10.
        with pytest.raises(EngineError, match="exceeds the KV pool"):
            engine.submit(list(range(1, 60)), max_tokens=16)
        with pytest.raises(EngineError, match="empty"):
            engine.submit([], max_tokens=4)
    finally:
        engine.shutdown()


def test_engine_metrics_in_registry_and_replica_endpoint():
    """Slot/queue gauges and token/TTFT series reach the process
    registry, and the replica serves them on GET /metrics."""
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    tokens_before = metrics.REGISTRY.counter(
        "stpu_engine_decode_tokens_total").get()

    from skypilot_tpu.recipes import serve_llm
    ready = threading.Event()
    httpd = serve_llm.serve(cfg, params, 0, ready_event=ready,
                            engine_slots=2)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        assert ready.wait(timeout=300)
        port = httpd.server_address[1]
        body = json.dumps({"prompt": [1, 2, 3],
                           "max_tokens": 4}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert len(json.loads(resp.read())["tokens"]) == 4
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=30) as resp:
            text = resp.read().decode()
        assert "stpu_engine_slots_total 2" in text
        assert "stpu_engine_queue_depth" in text
        assert "stpu_engine_ttft_seconds_count" in text
        assert metrics.REGISTRY.counter(
            "stpu_engine_decode_tokens_total").get() >= tokens_before + 4
    finally:
        httpd.shutdown()


def test_lb_metrics_include_replica_engine_families():
    """The LB /metrics snapshot merges each ready replica's exposition
    (engine slot/queue/token families) into one scrape."""
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve.load_balancing_policies import \
        RoundRobinPolicy

    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    ready = threading.Event()
    httpd = serve_llm.serve(cfg, params, 0, ready_event=ready,
                            engine_slots=2)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    lb = None
    try:
        assert ready.wait(timeout=300)
        policy = RoundRobinPolicy()
        policy.set_ready_replicas(
            [f"http://127.0.0.1:{httpd.server_address[1]}"])
        lb = lb_lib.run_load_balancer(0, policy,
                                      lb_lib.RequestRecorder())
        with urllib.request.urlopen(
                f"http://127.0.0.1:{lb.server_address[1]}/metrics",
                timeout=30) as resp:
            text = resp.read().decode()
        assert "stpu_lb_requests_total" in text       # LB's own
        assert "stpu_engine_slots_total" in text      # replica's
    finally:
        if lb is not None:
            lb.shutdown()
        httpd.shutdown()


@pytest.mark.parametrize("how", ["call", "flag", "kv-paged"])
def test_serve_llm_refuses_the_deleted_paths(how, capsys):
    """One serving path: ``engine_slots=0`` / ``--engine-slots 0``
    (which selected the locked fixed-batch path) is refused at
    start-up with one sentence, and ``--kv-paged`` (which selected
    the row-cache engine) is no argument at all."""
    from skypilot_tpu.recipes import serve_llm
    if how == "call":
        cfg = llama.LlamaConfig.tiny(vocab_size=128)
        params = llama.init(cfg, jax.random.key(0))
        with pytest.raises(ValueError) as err:
            serve_llm.serve(cfg, params, 0, engine_slots=0)
        assert str(err.value) == serve_llm.NO_ENGINE_SLOTS
        return
    argv = {"flag": ["--engine-slots", "0"],
            "kv-paged": ["--kv-paged", "1"]}[how]
    with pytest.raises(SystemExit) as err:
        serve_llm.main(argv)
    assert err.value.code == 2
    said = capsys.readouterr().err
    assert ("unrecognized arguments: --kv-paged" if how == "kv-paged"
            else serve_llm.NO_ENGINE_SLOTS) in said


def test_one_engine_no_paged_switch_in_any_signature():
    """Nothing selects an engine: no ``paged`` / ``kv_paged`` argument
    on the class, the geometry or the recipe."""
    import inspect
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import decode_engine
    for fn in (DecodeEngine, decode_engine.resolve_kv_geometry,
               serve_llm.serve):
        names = set(inspect.signature(fn).parameters)
        assert not names & {"paged", "kv_paged"}, fn


def test_decode_engine_defines_only_the_programs_that_serve():
    """The jitted programs of decode_engine.py are the three that
    serve (found by these names in a device trace), the host tier's
    two and the sampler — the set ``warmup()`` and the donation rule
    (tests/test_static_analysis.py) have to know."""
    from skypilot_tpu.serve import decode_engine
    jitted = type(decode_engine._sample)
    assert {name for name, obj in vars(decode_engine).items()
            if isinstance(obj, jitted)} == {
        "_paged_prefill_chunk", "_paged_step", "_paged_spec_step",
        "_slice_block", "_host_restore_block", "_sample"}


# ------------------------------------------------- shared-prefix KV cache
def _tiny_cfg(family):
    if family == "mixtral":
        return mixtral, mixtral.MixtralConfig.tiny()
    if family == "gemma":
        return gemma, gemma.GemmaConfig.tiny(vocab_size=128)
    return llama, llama.LlamaConfig.tiny(vocab_size=128)


@pytest.mark.parametrize("family", ["llama", "mixtral", "gemma"])
def test_prefix_hit_token_identical_and_fewer_steps(family):
    """A prefix-cache hit must change ONLY latency: the warm stream is
    token-identical to the fixed-path (cold) decode, prefill tokens
    are actually saved, and steps-to-first-token (chunk prefills, the
    deterministic TTFT) is STRICTLY lower than the cold run's. Prefix
    caching is the paged pool's zero-copy aliasing — the only
    representation left now the dense splice cache is retired — so
    the contract is pinned per family on the paged engine."""
    mdl, cfg = _tiny_cfg(family)
    vocab = cfg.vocab_size
    params = mdl.init(cfg, jax.random.key(0))
    engine = DecodeEngine(cfg, params, slots=2, max_seq=64,
                          prefill_chunk=8).start()
    try:
        shared = [int(t) for t in jax.random.randint(
            jax.random.key(11), (17,), 1, vocab)]  # 2 full 8-chunks
        cold = engine.submit(shared + [5, 6], max_tokens=4)
        cold_toks = cold.result(timeout=300.0)
        warm = engine.submit(shared + [7, 8, 9], max_tokens=4)
        warm_toks = warm.result(timeout=300.0)

        for prompt, got in ((shared + [5, 6], cold_toks),
                            (shared + [7, 8, 9], warm_toks)):
            ref = mdl.decode(cfg, params, jnp.asarray([prompt]),
                             jnp.int32(len(prompt)), 4, len(prompt) + 4)
            assert got == [int(t) for t in ref[0]]
        assert cold.cached_prompt_tokens == 0
        assert warm.cached_prompt_tokens == 16
        assert warm.prefill_chunks < cold.prefill_chunks
        assert engine.prefix_cache.stats()["tokens_saved"] >= 16
    finally:
        engine.shutdown()


def test_prefix_hit_seeded_sampling_parity(reference_stream):
    """A temperature>0 stream is bit-identical warm vs cold: the hit
    restores the exact KV rows prefill would recompute, and the
    fold_in(seed, position) keys never see the cache. The cold
    baseline is the row-cache reference, which has no prefix cache at
    all; the warm run is the pool's always-on zero-copy trie."""
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    prompt = [int(t) for t in jax.random.randint(
        jax.random.key(3), (21,), 1, 128)]

    engine = DecodeEngine(cfg, params, slots=2, max_seq=64,
                          prefill_chunk=8).start()
    try:
        # Sequential on purpose: the second submission must see the
        # first's published chunks (cache-hit path).
        first = engine.submit(prompt, max_tokens=6,
                              temperature=0.9, seed=17)
        first_toks = first.result(timeout=300.0)
        second = engine.submit(prompt, max_tokens=6,
                               temperature=0.9, seed=17)
        second_toks = second.result(timeout=300.0)
    finally:
        engine.shutdown()
    cold = reference_stream(llama, cfg, params, prompt, 6,
                            temperature=0.9, seed=17, chunk=8)
    assert cold == first_toks == second_toks
    assert first.cached_prompt_tokens == 0
    assert second.cached_prompt_tokens > 0    # the hit really happened


# The dense splice cache (PrefixCache + _insert_chunk/_gather_chunk)
# is retired; its pool-level eviction contract lives on against the
# paged trie in test_paged_kv.py::
# test_paged_trie_lru_refcount_and_interior_protection.


def test_engine_slot_churn_respects_pool_budget_and_parity():
    """Slot churn through a SMALL block pool: every stream stays
    token-identical to the fixed path while trie eviction constantly
    recycles blocks (LRU + refcount safety under churn), and the pool
    accounting identity free + trie == usable holds after every
    request (engine driven step-by-step — no scheduler races)."""
    import random
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    # 9 usable 8-token blocks: one live request plus a couple of
    # cached chunks — publish-on-free forces constant eviction.
    engine = DecodeEngine(cfg, params, slots=2, max_seq=64,
                          prefill_chunk=8,
                          kv_pool_blocks=10)
    rng = random.Random(2)
    for _ in range(6):
        prompt = [rng.randint(1, 127)
                  for _ in range(rng.randint(9, 20))]
        req = engine.submit(prompt, max_tokens=3)
        for _ in range(200):
            engine._admit()
            did = engine._prefill_one()
            did = engine._decode_step() or did
            if not did and not engine._waiting:
                break
        got = req.result(timeout=5.0)
        ref = llama.decode(cfg, params, jnp.asarray([prompt]),
                           jnp.int32(len(prompt)), 3,
                           len(prompt) + 3)
        assert got == [int(t) for t in ref[0]]
        pool = engine._pool
        assert pool.free_blocks() + len(engine.prefix_cache.nodes()) \
            == pool.usable_blocks


def test_cancel_mid_prefill_releases_block_refcounts():
    """A request cancelled between admission and prefill completion
    must unpin every trie node it aliased and return its own blocks —
    the pool accounting identity holds afterwards (engine driven
    step-by-step on this thread — no scheduler races)."""
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    engine = DecodeEngine(cfg, params, slots=1, max_seq=64,
                          prefill_chunk=8)
    # NOT started: drive _admit/_prefill_one/_decode_step directly.
    shared = [int(t) for t in jax.random.randint(
        jax.random.key(5), (18,), 1, 128)]
    first = engine.submit(shared, max_tokens=1)
    engine._admit()
    for _ in range(8):
        if not engine._prefill_one():
            break
        engine._decode_step()
    assert first.result(timeout=5.0)          # finished + published
    assert engine.prefix_cache.stats()["chunks"] == 2

    second = engine.submit(shared + [3, 4, 5, 6, 7, 8, 9, 10, 11],
                           max_tokens=4)
    engine._admit()
    pinned = [n for n in engine.prefix_cache.nodes() if n.refs > 0]
    assert len(pinned) == 2                   # admission pinned the hit
    second.cancel()
    engine._prefill_one()                     # cancel path frees slot
    assert all(n.refs == 0 for n in engine.prefix_cache.nodes())
    pool = engine._pool
    assert pool.free_blocks() + len(engine.prefix_cache.nodes()) \
        == pool.usable_blocks
    assert pool._reserved == 0
    assert second.result(timeout=5.0) == []   # clean cancelled stream


def test_prefix_metrics_reach_replica_endpoint():
    """Hit/miss/tokens-saved counters and the split TTFT histogram are
    part of the replica's /metrics surface (and therefore of the LB's
    merged scrape) — emitted by the paged zero-copy trie, the only
    prefix-cache representation left. The quant info gauges ride the
    same surface (0 here: bf16 engine)."""
    from skypilot_tpu.observability import metrics as metrics_lib
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    saved_before = metrics_lib.REGISTRY.counter(
        "stpu_engine_prefill_tokens_saved_total").get()
    engine = DecodeEngine(cfg, params, slots=2, max_seq=64,
                          prefill_chunk=8).start()
    try:
        shared = list(range(1, 18))
        engine.submit(shared, max_tokens=2).result(timeout=300.0)
        engine.submit(shared + [19], max_tokens=2).result(timeout=300.0)
    finally:
        engine.shutdown()
    assert metrics_lib.REGISTRY.counter(
        "stpu_engine_prefill_tokens_saved_total").get() >= \
        saved_before + 16
    text = metrics_lib.render()
    assert "stpu_engine_prefix_cache_hits_total" in text
    assert 'stpu_engine_prefix_ttft_seconds_count{cache="hit"}' in text
    assert "stpu_engine_kv_quant_enabled 0" in text
    assert "stpu_engine_weight_quant_enabled 0" in text


# ------------------------------------------------- prefix-affinity LB
def test_prefix_affinity_routes_equal_prefixes_together():
    """Equal-prefix requests land on ONE replica; when that replica
    disappears they remap consistently to a surviving replica; traffic
    without a prompt falls back to least-loaded."""
    from skypilot_tpu.serve.load_balancing_policies import \
        PrefixAffinityPolicy

    policy = PrefixAffinityPolicy()
    urls = [f"http://replica-{i}" for i in range(4)]
    policy.set_ready_replicas(urls)
    body = json.dumps({"prompt": list(range(100)),
                       "max_tokens": 4}).encode()
    req = {"path": "/generate", "body": body}

    def pick():
        url = policy.select_replica(req)
        policy.report_done(url)   # request completes -> load returns
        return url

    picks = {pick() for _ in range(8)}
    assert len(picks) == 1
    target = picks.pop()

    # Replica vanishes: every equal-prefix request remaps to the SAME
    # survivor (consistent hashing), never bounces.
    policy.set_ready_replicas([u for u in urls if u != target])
    remapped = {pick() for _ in range(8)}
    assert len(remapped) == 1 and target not in remapped

    # It comes back: affinity returns to the original owner.
    policy.set_ready_replicas(urls)
    assert pick() == target

    # DIFFERENT prefixes spread: with vnodes, 20 distinct prefixes on
    # 4 replicas never all hash to one arc.
    spread = {policy.select_replica({"path": "/generate",
                                     "body": json.dumps(
                                         {"prompt": [i] * 70}).encode()})
              for i in range(20)}
    assert len(spread) > 1


def test_prefix_affinity_bounded_load_spills_deterministically():
    """One dominant prefix must NOT pin the whole fleet's traffic on
    its owner: once the owner's in-flight count crosses the bounded-
    load threshold, requests spill to the ring successor (which then
    warms too) — and the spill target is deterministic, not random."""
    from skypilot_tpu.serve.load_balancing_policies import \
        PrefixAffinityPolicy

    policy = PrefixAffinityPolicy()
    policy.set_ready_replicas([f"http://replica-{i}" for i in range(4)])
    req = {"path": "/generate",
           "body": json.dumps({"prompt": list(range(100))}).encode()}
    # No report_done: every request stays in flight (slow decodes).
    picks = [policy.select_replica(req) for _ in range(8)]
    owner = picks[0]
    assert picks[1] == owner              # under the bound: affinity
    spilled = [u for u in picks if u != owner]
    assert spilled                        # over the bound: spill
    assert len(set(spilled)) == 1         # ... to ONE successor
    # Owner still carries the larger share (affinity preserved).
    assert picks.count(owner) >= len(spilled)


def test_prefix_affinity_fallback_least_loaded_and_report_done():
    from skypilot_tpu.serve.load_balancing_policies import \
        PrefixAffinityPolicy

    policy = PrefixAffinityPolicy()
    policy.set_ready_replicas(["http://a", "http://b"])
    body = json.dumps({"prompt": list(range(80))}).encode()
    busy = policy.select_replica({"path": "/generate", "body": body})
    other = "http://a" if busy == "http://b" else "http://b"
    # No prompt -> least loaded, i.e. NOT the replica holding the
    # in-flight generate.
    assert policy.select_replica({"path": "/health",
                                  "body": None}) == other
    policy.report_done(busy)
    policy.report_done(other)
    # Unknown url must not crash the accounting.
    policy.report_done("http://gone")


def test_lb_proxies_through_prefix_affinity_policy():
    """End to end through the real LB: the proxy hands the request body
    to the policy (content-aware selection) and returns the in-flight
    slot when the response completes."""
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve.load_balancing_policies import \
        PrefixAffinityPolicy

    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    ready = threading.Event()
    httpd = serve_llm.serve(cfg, params, 0, ready_event=ready,
                            engine_slots=2)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    lb = None
    try:
        assert ready.wait(timeout=300)
        policy = PrefixAffinityPolicy()
        url = f"http://127.0.0.1:{httpd.server_address[1]}"
        policy.set_ready_replicas([url])
        lb = lb_lib.run_load_balancer(0, policy,
                                      lb_lib.RequestRecorder())
        body = json.dumps({"prompt": [1, 2, 3],
                           "max_tokens": 3}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{lb.server_address[1]}/generate",
            data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert len(json.loads(resp.read())["tokens"]) == 3
        # The LB returns the slot AFTER it has written the response,
        # so the client can be here first.
        deadline = time.monotonic() + 5.0
        while policy._inflight[url] and time.monotonic() < deadline:
            time.sleep(0.01)
        assert policy._inflight[url] == 0    # slot returned
    finally:
        if lb is not None:
            lb.shutdown()
        httpd.shutdown()


def test_engine_shutdown_fails_pending_requests():
    """shutdown() must not strand callers blocked on queues."""
    cfg = llama.LlamaConfig.tiny(vocab_size=64)
    params = llama.init(cfg, jax.random.key(0))
    engine = DecodeEngine(cfg, params, slots=1, max_seq=32,
                          prefill_chunk=8).start()
    engine.warmup()
    reqs = [engine.submit([1, 2], max_tokens=8) for _ in range(3)]
    engine.shutdown()
    for req in reqs:
        try:
            req.result(timeout=30.0)
        except EngineError:
            pass  # "engine shut down" is the expected outcome
    with pytest.raises(EngineError, match="shut down"):
        engine.submit([1], max_tokens=1)
