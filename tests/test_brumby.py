"""Brumby-14B (models/brumby.py) held to its plain reference
(benchmarks/reference/brumby_arch.py, the attention form) at tiny sizes
on the CPU, seeded random weights, float32: the feature map, the chunk
form over a whole sequence, prefill chunks and recurrent decode steps
through DecodeEngine and the state pool (logits, not tokens: a tap on
``decode_engine._sample``), prefix reuse by snapshots (to the bit), what
the pool's accounting does under pressure, and what the family
refuses."""
import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import brumby_arch as ref
from skypilot_tpu.models import (brumby, deepseek, family_name, gemma,
                                 llama, mixtral, model_api)
from skypilot_tpu.observability import metrics
from skypilot_tpu.serve import decode_engine, gang_replica, kv_pool
from skypilot_tpu.serve.decode_engine import DecodeEngine

# Float32 leaves rounding of about 1e-6 of a logit of about 4 a
# product; six orders below what a wrong gate or a lost chunk shows.
TOL = 2e-4


def _tiny(**changes):
    return dataclasses.replace(brumby.BrumbyConfig.tiny(),
                               dtype=jnp.float32, **changes)


# The programs of THIS configuration (and of no other test's) are
# traced with the tap below in place of ``_sample``.
TAP_CFG = _tiny(max_seq_len=2049)
_ROWS: dict = {}


def _keep(logits, seed, position):
    for row, sd, pos in zip(np.asarray(logits), np.asarray(seed),
                            np.asarray(position)):
        if int(sd) > 1000:
            _ROWS[(int(sd), int(pos))] = np.array(row, np.float32)


_sample = decode_engine._sample


def _tapped(logits, seed, position, temps):
    jax.debug.callback(_keep, logits, seed, position)
    return _sample(logits, seed, position, temps)


@pytest.fixture(scope="module")
def params():
    return brumby.init(TAP_CFG, jax.random.key(0))


def _tokens(n, seed=1, vocab=256):
    return [int(t) for t in jax.random.randint(
        jax.random.key(seed), (n,), 0, vocab)]


class _Tap:
    """An engine over TAP_CFG whose every sampled row is kept:
    ``rows(request)`` are the logits its served tokens were taken
    from, in order."""

    def __init__(self, params, **kwargs):
        self._patch = mock.patch.object(decode_engine, "_sample", _tapped)
        self._patch.start()
        kwargs.setdefault("slots", 3)
        kwargs.setdefault("max_seq", 512)
        self.engine = DecodeEngine(TAP_CFG, params, use_manifest=False,
                                   **kwargs).start()
        self._seed = 1000 + 100 * len(_ROWS)

    def submit(self, prompt, n):
        self._seed += 1
        req = self.engine.submit(prompt, max_tokens=n, seed=self._seed)
        req.tap_seed = self._seed
        return req

    def rows(self, req, timeout=300.0):
        tokens = req.result(timeout=timeout)
        jax.effects_barrier()
        start = len(req.prompt)
        return tokens, np.stack([_ROWS[(req.tap_seed, start + j)]
                                 for j in range(len(tokens))])

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.engine.shutdown()
        self._patch.stop()


def _decoding(req, timeout=300.0):
    """Wait until ``req`` has its first token (it decodes from here)."""
    import time
    limit = time.monotonic() + timeout
    while req.first_token_at is None:
        assert time.monotonic() < limit
        time.sleep(0.005)


def _reference_rows(params, prompt, tokens):
    seq = np.asarray(prompt + tokens)
    want = np.asarray(ref.logits(TAP_CFG, params, seq))
    return want[len(prompt) - 1:len(seq) - 1]


def _counter(name, **labels):
    want = name + ("{" + ",".join(f'{k}="{v}"' for k, v in
                                  sorted(labels.items())) + "}"
                   if labels else "")
    for line in metrics.render().splitlines():
        if line.startswith(want + " "):
            return float(line.split()[1])
    return 0.0


# ------------------------------------------------------------ the model
def test_model_api_dispatch_and_the_one_question():
    cfg = brumby.BrumbyConfig.tiny()
    assert model_api(cfg) is brumby and family_name(cfg) == "brumby"
    assert kv_pool.pool_layout(cfg) == kv_pool.PoolLayout(
        tokens=False, state_blocks=1)
    assert kv_pool.pool_layout(cfg).kinds() == ("state",)
    for other in (llama.LlamaConfig.tiny(), mixtral.MixtralConfig.tiny(),
                  gemma.GemmaConfig.tiny(),
                  deepseek.DeepseekV3Config.tiny()):
        assert kv_pool.pool_layout(other) == kv_pool.PoolLayout()
        assert kv_pool.pool_layout(other).kinds() == ("global",)


@pytest.mark.parametrize("hd,d", [(16, 256), (32, 768), (128, 9216)])
def test_feature_map_is_the_square_of_the_product(hd, d):
    a = jax.random.normal(jax.random.key(hd), (7, hd))
    b = jax.random.normal(jax.random.key(hd + 1), (7, hd))
    fa, fb = brumby.phi(a), brumby.phi(b)
    assert fa.shape == (7, d)
    want = np.sum(np.asarray(a, np.float64) * np.asarray(b, np.float64),
                  -1) ** 2
    got = np.sum(np.asarray(fa, np.float64) * np.asarray(fb, np.float64),
                 -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # bf16 values pass the one-hot products unrounded.
    a16 = a.astype(jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(brumby.phi(a16)),
        np.asarray(brumby.phi(a16.astype(jnp.float32))))


def test_published_sizes():
    cfg = brumby.BrumbyConfig.b14_6l()
    assert (cfg.dim, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.mlp_dim, cfg.vocab_size, cfg.n_layers) == (
                5120, 40, 8, 128, 17408, 151936, 6)
    assert cfg.state_dim == 9216 and brumby.BrumbyConfig().n_layers == 40
    with pytest.raises(ValueError, match="brumby"):
        brumby.BrumbyConfig(dim=100, n_heads=4)


@pytest.mark.parametrize("n", [64, 128, 100, 7, 193])
def test_chunk_form_matches_the_attention_form(params, n):
    """``forward`` (the chunk form, 64 tokens at a time, the state
    across chunks) against the reference's one quadratic pass, for
    lengths that are and are not multiples of the chunk."""
    toks = np.asarray(_tokens(n, seed=n))
    got = brumby.forward(TAP_CFG, params, jnp.asarray(toks)[None])[0]
    want = ref.logits(TAP_CFG, params, toks)
    assert float(jnp.abs(got - want).max()) < TOL


def test_recurrence_kernel_equals_the_chunk_form_and_skips_block_0():
    """One decode step through the Pallas kernel (interpret mode here)
    against the chunk form at T = 1, slot by slot; a slot on the
    scratch block reads and changes nothing."""
    cfg = _tiny()
    kvh, g, hd, d = cfg.n_kv_heads, 2, cfg.head_dim, cfg.state_dim
    ks = jax.random.split(jax.random.key(4), 6)
    q = jax.random.normal(ks[0], (3, kvh, g, hd))
    k = jax.random.normal(ks[1], (3, kvh, hd))
    v = jax.random.normal(ks[2], (3, kvh, hd))
    log_gate = jax.nn.log_sigmoid(jax.random.normal(ks[3], (3, kvh)))
    pool_s = jax.random.normal(ks[4], (2, 5, kvh, hd, d))
    pool_z = jnp.abs(jax.random.normal(ks[5], (2, 5, kvh, d)))
    pool_s, pool_z = pool_s.at[:, 0].set(0), pool_z.at[:, 0].set(0)
    blocks = jnp.asarray([3, 0, 1], jnp.int32)
    y, new_s, new_z = brumby._retention_step(
        cfg, jnp.int32(1), blocks, q, k, v, log_gate, pool_s, pool_z)
    for b, blk in enumerate([3, 0, 1]):
        want_y, want_s, want_z = brumby._chunk(
            cfg, q[b][None, None], k[b][None, None], v[b][None, None],
            log_gate[b][None, None], jnp.ones((1, 1), bool),
            pool_s[1, blk][None], pool_z[1, blk][None])
        if blk:
            np.testing.assert_allclose(y[b], want_y[0, 0], atol=1e-4,
                                       rtol=1e-4)
            np.testing.assert_allclose(new_s[1, blk], want_s[0],
                                       atol=1e-5, rtol=1e-5)
            np.testing.assert_allclose(new_z[1, blk], want_z[0],
                                       atol=1e-5, rtol=1e-5)
        else:
            assert not np.asarray(y[b]).any()
    untouched = [(0, b) for b in range(5)] + [(1, 0), (1, 2), (1, 4)]
    for li, blk in untouched:
        np.testing.assert_array_equal(new_s[li, blk], pool_s[li, blk])
        np.testing.assert_array_equal(new_z[li, blk], pool_z[li, blk])


def test_both_forms_in_bfloat16_stay_near_the_reference():
    """The program in bf16 (weights, activations; the state float32)
    against the float32 reference on the same weights, in parts of the
    logits' range: a chunk of the chunk form, then 40 steps of the
    recurrence through the kernel (its read-out multiplies in bf16, as
    the chunk form's products do). A bf16 value carries 8 bits, so each
    matmul between tokens and logits is off by about 2^-9 of its size:
    the median row within 0.015 of the range and nine in ten within
    0.03 (measured at this seed: 0.005 and 0.011; the decode rows
    alone 0.007 and 0.015); a wrong gate, a lost chunk or a state kept from another
    sequence moves every row by tenths."""
    cfg = brumby.BrumbyConfig.tiny()
    params = brumby.init(cfg, jax.random.key(0))
    toks = np.asarray(_tokens(104, seed=3))
    pool = brumby.init_paged_cache(cfg, 3, 64)
    first, pool = brumby.forward_with_paged_cache(
        cfg, params, jnp.asarray(toks[:64])[None], pool,
        jnp.zeros((1, 1), jnp.int32), jnp.int32(0), window=64,
        write_block=jnp.int32(2))
    rows = [first[0]]
    table = jnp.asarray([[0], [2]], jnp.int32)
    step = jax.jit(lambda tok, pool, pos: brumby.forward_with_paged_cache(
        cfg, params, tok, pool, table, pos, window=64))
    for i in range(64, len(toks)):
        logits, pool = step(jnp.asarray([[7], [toks[i]]]), pool,
                            jnp.asarray([0, i]))
        rows.append(logits[1])
    got = np.asarray(jnp.concatenate(rows, axis=0), np.float32)
    want = np.asarray(ref.logits(cfg, params, toks))
    err = np.abs(got - want).max(-1) / float(np.ptp(want))
    assert np.median(err) < 0.015 and np.quantile(err, 0.9) < 0.03, (
        np.median(err), np.quantile(err, 0.9))
    assert np.median(err[64:]) < 0.015


# --------------------------------------------- through the engine's pool
def test_engine_logits_match_the_reference(params):
    """Prefill chunks (chunk form) then decode steps (the recurrence)
    through DecodeEngine's own programs against the reference's full
    pass, logits and not tokens: a prompt of three chunks, one of
    exactly a chunk, a short one, and a long one that joins and
    prefills while the others decode (its state must not move under
    their steps)."""
    with _Tap(params, slots=3) as tap:
        first = [tap.submit(_tokens(n, seed=n), 12) for n in (150, 64)]
        _decoding(first[0])               # ... when the rest join
        rest = [tap.submit(_tokens(n, seed=n), 12) for n in (7, 200)]
        for req in first + rest:
            tokens, rows = tap.rows(req)
            want = _reference_rows(params, req.prompt, tokens)
            assert np.abs(rows - want).max() < TOL, len(req.prompt)
        assert tap.engine.kv_config()["seq_blocks"] == 1


def test_snapshot_hit_and_wholly_cached_prompt_equal_a_cold_prefill(
        params):
    """A prompt served cold, then again from its snapshot, then its
    first 128 tokens alone (wholly cached: restored from the node
    before its last chunk), against a fresh engine's cold passes: the
    same programs on the same bits, so the logits are equal to the
    bit."""
    prompt = _tokens(150, seed=9)
    taken = _counter("stpu_engine_state_snapshots_total", event="taken")
    restored = _counter("stpu_engine_state_snapshots_total",
                        event="restored")
    with _Tap(params, slots=2) as tap:
        cold = tap.submit(prompt, 6)
        cold_tokens, cold_rows = tap.rows(cold)
        assert cold.cached_prompt_tokens == 0
        assert _counter("stpu_engine_state_snapshots_total",
                        event="taken") == taken + 2
        warm = tap.submit(prompt, 6)
        warm_tokens, warm_rows = tap.rows(warm)
        assert warm.cached_prompt_tokens == 128
        assert warm.prefill_chunks == 1
        whole = tap.submit(prompt[:128], 6)
        whole_tokens, whole_rows = tap.rows(whole)
        assert whole.cached_prompt_tokens == 64
        assert _counter("stpu_engine_state_snapshots_total",
                        event="restored") == restored + 2
        stats = tap.engine.prefix_cache.stats()
        assert (stats["hits"], stats["misses"], stats["chunks"],
                stats["zero_copy_hits"]) == (2, 1, 2, 0)
        # A node is never written again: its block has one owner.
        for node in tap.engine.prefix_cache.nodes():
            assert tap.engine._pool.refcount(node.block) == 1
    assert warm_tokens == cold_tokens
    np.testing.assert_array_equal(warm_rows, cold_rows)
    with _Tap(params, slots=2) as tap:
        fresh_tokens, fresh_rows = tap.rows(tap.submit(prompt[:128], 6))
    assert whole_tokens == fresh_tokens
    np.testing.assert_array_equal(whole_rows, fresh_rows)
    want = _reference_rows(params, prompt, cold_tokens)
    assert np.abs(cold_rows - want).max() < TOL


def test_snapshots_are_evicted_under_a_pool_with_room_for_two(params):
    """One slot, its own block and two spare: five prompts of three
    chunks each take snapshots, LRU gives them back, no request is
    refused or served wrongly, and a full pool only costs reuse."""
    evicted = _counter("stpu_engine_state_snapshots_total",
                       event="evicted")
    with _Tap(params, slots=1, kv_pool_blocks=4) as tap:
        pool = tap.engine._pool
        assert pool.usable_blocks == 3
        for seed in range(20, 25):
            req = tap.submit(_tokens(150, seed=seed), 4)
            tokens, rows = tap.rows(req)
            want = _reference_rows(params, req.prompt, tokens)
            assert np.abs(rows - want).max() < TOL
            assert tap.engine.prefix_cache.stats()["chunks"] <= 2
        assert _counter("stpu_engine_state_snapshots_total",
                        event="evicted") >= evicted + 6
        # Everything is a snapshot or free again; nothing leaked.
        assert pool.free_blocks() + len(
            tap.engine.prefix_cache.nodes()) == pool.usable_blocks
        assert pool.available() == pool.free_blocks()
    # No spare block at all: chunks rewrite the slot's block in place.
    with _Tap(params, slots=1, kv_pool_blocks=2) as tap:
        req = tap.submit(_tokens(150, seed=20), 4)
        tokens, rows = tap.rows(req)
        assert tap.engine.prefix_cache.stats()["chunks"] == 0
        want = _reference_rows(params, req.prompt, tokens)
        assert np.abs(rows - want).max() < TOL


def test_snapshots_never_restored_from_go_before_one_that_was(params):
    """A shared prefix's snapshot that has been restored from outlives
    any number of snapshots of prompts nobody asks again, though each
    of those is younger: one spare block is enough for them."""
    shared = _tokens(128, seed=50)
    with _Tap(params, slots=1, kv_pool_blocks=5) as tap:
        tap.rows(tap.submit(shared + _tokens(20, seed=51), 2))
        again = tap.submit(shared + _tokens(30, seed=52), 2)
        tap.rows(again)
        assert again.cached_prompt_tokens == 128
        for seed in range(53, 58):
            tap.rows(tap.submit(_tokens(150, seed=seed), 2))
        last = tap.submit(shared + _tokens(9, seed=58), 2)
        tokens, rows = tap.rows(last)
        assert last.cached_prompt_tokens == 128
        want = _reference_rows(params, last.prompt, tokens)
        assert np.abs(rows - want).max() < TOL


def test_a_retired_slots_block_is_reused_without_leaking_state(params):
    """One slot and one usable block: the second request runs on the
    block the first one's state was left in, and its logits are a
    fresh engine's to the bit."""
    second = _tokens(70, seed=31)
    with _Tap(params, slots=1, kv_pool_blocks=2) as tap:
        tap.rows(tap.submit(_tokens(90, seed=30), 8))
        block = int(tap.engine._pool._free[0])
        got_tokens, got_rows = tap.rows(tap.submit(second, 8))
        assert int(tap.engine._pool._free[0]) == block
    with _Tap(params, slots=1, kv_pool_blocks=2) as tap:
        want_tokens, want_rows = tap.rows(tap.submit(second, 8))
    assert got_tokens == want_tokens
    np.testing.assert_array_equal(got_rows, want_rows)


def test_dead_and_prefilling_slots_never_change_a_live_slots_logits(
        params):
    """The same request alone in a four-slot engine, and beside
    requests that end before it, join after it and prefill while it
    decodes: equal to the bit, and the scratch block stays zero."""
    prompt = _tokens(100, seed=40)
    with _Tap(params, slots=4) as tap:
        alone_tokens, alone_rows = tap.rows(tap.submit(prompt, 24))
    with _Tap(params, slots=4) as tap:
        main = tap.submit(prompt, 24)
        short = [tap.submit(_tokens(n, seed=n), 3) for n in (5, 66)]
        _decoding(main)
        late = tap.submit(_tokens(180, seed=41), 3)
        tokens, rows = tap.rows(main)
        for req in short + [late]:
            req.result(timeout=300.0)
        cache = tap.engine._cache
        assert not np.asarray(cache["S"][:, 0]).any()
        assert not np.asarray(cache["z"][:, 0]).any()
        assert np.isfinite(np.asarray(cache["S"])).all()
    assert tokens == alone_tokens
    np.testing.assert_array_equal(rows, alone_rows)


def test_pool_accounting_and_gauges(params):
    geo = decode_engine.resolve_kv_geometry(
        slots=16, max_seq=1280, use_manifest=False,
        layout=kv_pool.PoolLayout(tokens=False, state_blocks=1))
    assert (geo["pool_blocks"], geo["snapshot_blocks"], geo["table_len"],
            geo["chunk"], geo["seq_blocks"]) == (29, 12, 1, 64, 1)
    assert geo["pools"] == {"state": 29}
    paged = decode_engine.resolve_kv_geometry(
        slots=16, max_seq=1280, use_manifest=False)
    assert (paged["pool_blocks"], paged["seq_blocks"],
            paged["snapshot_blocks"]) == (16 * 20 + 1, 0, 0)
    assert paged["pools"] == {"global": 16 * 20 + 1}
    pool = kv_pool.BlockPool(5, 64, seq_blocks=1)
    assert pool.blocks_for(1) == pool.blocks_for(100000) == 1
    assert kv_pool.BlockPool(5, 64).blocks_for(130) == 3
    cfg = _tiny()
    block = kv_pool.block_bytes_for(cfg, 64)
    assert block == (cfg.n_layers * cfg.n_kv_heads * cfg.state_dim
                     * (cfg.head_dim + 1) * 4)
    engine = DecodeEngine(cfg, params, slots=2, max_seq=256,
                          use_manifest=False)
    assert engine.kv_config() == decode_engine.resolve_kv_geometry(
        slots=2, max_seq=256, use_manifest=False,
        layout=kv_pool.pool_layout(cfg))
    assert _counter("stpu_engine_kv_pool_block_bytes") == block
    assert sum(engine.cache_bytes_per_device().values()) == 5 * block
    with pytest.raises(decode_engine.EngineError, match="max_seq"):
        engine.submit(_tokens(250), max_tokens=10)
    req = engine.submit(_tokens(70), max_tokens=3)
    for _ in range(40):
        engine._admit()
        did = engine._prefill_one()
        if not (engine._decode_step() or did):
            break
    assert len(req.result(timeout=5.0)) == 3
    engine._admit()
    kinds = {k: _counter("stpu_engine_cache_blocks", kind=k)
             for k in ("state", "snapshot")}
    assert kinds == {"state": 0.0, "snapshot": 1.0}
    assert engine._state_pool.free_blocks() == 3


def test_unknown_family_gets_the_default_tuning_silently(tmp_path,
                                                         monkeypatch,
                                                         capsys):
    """A manifest that knows nothing of a family is no fault: the
    default, no warning, however often it is asked."""
    from skypilot_tpu.tune import manifest
    path = tmp_path / "manifest.json"
    manifest.save({manifest.tuning_key("llama", 4):
                   {"chunk": 32, "parity": "pass"}},
                  {"device_kind": "cpu", "commit": "abc1234",
                   "created": "2026-10-02T00:00:00+0000"}, path=path)
    monkeypatch.setenv(manifest.ENV_MANIFEST, str(path))
    for _ in range(3):
        assert manifest.entry_for(family="brumby", slots=4) == (
            None, "default")
    assert manifest.entry_for(family="llama", slots=4)[0]["chunk"] == 32
    out = capsys.readouterr()
    assert "brumby" not in out.out + out.err


# ------------------------------------------------------------- refusals
@pytest.mark.parametrize("what,kwargs", [
    ("int8 pool", {"kv_quant": True}),
    ("int8 weights", {"weight_quant": True}),
    ("speculative decoding", {"spec_k": 2}),
    ("host spill tier", {"prefix_cache_mb": 8.0}),
])
def test_engine_options_are_refused_by_name(params, what, kwargs):
    with pytest.raises(NotImplementedError, match=f"brumby.*{what}"):
        DecodeEngine(TAP_CFG, params, slots=2, max_seq=128,
                     use_manifest=False, **kwargs)


def test_tp_lora_and_the_verify_step_are_refused_by_name(params):
    cfg = TAP_CFG
    mesh, rules = gang_replica.build_mesh(
        gang_replica.ReplicaTopology(hosts=1, ici_axes={"tp": 2}))
    with pytest.raises(NotImplementedError, match="brumby.*tp > 1"):
        DecodeEngine(cfg, params, slots=2, max_seq=128, mesh=mesh,
                     rules=rules, use_manifest=False)
    with pytest.raises(NotImplementedError, match="brumby.*tp > 1"):
        gang_replica.cache_shardings(cfg, mesh, rules)
    lora = jax.tree.map(lambda a: a, params)
    lora["layers"]["wq_lora_a"] = jnp.zeros((cfg.n_layers, cfg.dim, 2))
    with pytest.raises(NotImplementedError, match="brumby.*LoRA"):
        brumby.forward(cfg, lora, jnp.zeros((1, 4), jnp.int32))
    with pytest.raises(NotImplementedError, match="brumby.*speculative"):
        brumby.verify_step_paged(cfg, params)
    with pytest.raises(NotImplementedError, match="brumby.*int8 weights"):
        brumby.quantize_params(cfg, params)


def test_serve_llm_presets():
    from skypilot_tpu.recipes import serve_llm
    assert serve_llm.model_config("brumby-tiny") == \
        brumby.BrumbyConfig.tiny()
    assert serve_llm.model_config("brumby-14b-6l") == \
        brumby.BrumbyConfig.b14_6l()
