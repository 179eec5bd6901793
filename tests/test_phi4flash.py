"""Phi-4-mini-flash-reasoning (models/phi4flash.py) held to its plain
reference (benchmarks/reference/phi4flash_arch.py) at tiny sizes on the
CPU, seeded random weights, float32: the five mixers with no cache,
prefill chunks and decode steps through DecodeEngine and the three
kinds of pool behind one slot (logits, not tokens: a tap on
``decode_engine._sample``), a prefix hit that aliases two kinds of
block and restores a state snapshot (to the bit), what each kind's
accounting does on a served run, that the parity has teeth, and what
the family refuses."""
import dataclasses
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import phi4flash_arch as ref
from skypilot_tpu.models import family_name, model_api, phi4flash
from skypilot_tpu.observability import metrics
from skypilot_tpu.ops.pallas import paged_attention
from skypilot_tpu.serve import decode_engine, gang_replica, kv_pool
from skypilot_tpu.serve.decode_engine import DecodeEngine

# Float32 leaves rounding of about 1e-6 of a logit of about 4 a
# product; a lost window block or a snapshot one chunk off shows
# tenths (the last tests of this file).
TOL = 3e-4
CHUNK = 64


def _tiny(**changes):
    return dataclasses.replace(phi4flash.Phi4FlashConfig.tiny(),
                               **{"dtype": jnp.float32, **changes})


# The programs of THIS configuration (and of no other test's) are
# traced with the tap below in place of ``_sample``; the two tests
# that break the program on purpose trace configurations of their own.
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
    return phi4flash.init(TAP_CFG, jax.random.key(0))


def _tokens(n, seed=1, vocab=256):
    return [int(t) for t in jax.random.randint(
        jax.random.key(seed), (n,), 0, vocab)]


class _Tap:
    """An engine whose every sampled row is kept: ``rows(request)`` are
    the logits its served tokens were taken from, in order."""

    def __init__(self, params, cfg=TAP_CFG, **kwargs):
        self._patch = mock.patch.object(decode_engine, "_sample", _tapped)
        self._patch.start()
        kwargs.setdefault("slots", 3)
        kwargs.setdefault("max_seq", 512)
        self.engine = DecodeEngine(cfg, params, use_manifest=False,
                                   **kwargs).start()
        self._seed = 1000 + 100 * len(_ROWS)

    def submit(self, prompt, n):
        self._seed += 1
        req = self.engine.submit(prompt, max_tokens=n, seed=self._seed)
        req.tap_seed = self._seed
        return req

    def rows(self, req, timeout=600.0):
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


def _reference_rows(params, prompt, tokens, cfg=TAP_CFG):
    seq = np.asarray(prompt + tokens)
    want = np.asarray(ref.logits(cfg, params, seq))
    return want[len(prompt) - 1:len(seq) - 1]


def _counter(name, **labels):
    want = name + ("{" + ",".join(f'{k}="{v}"' for k, v in
                                  sorted(labels.items())) + "}"
                   if labels else "")
    for line in metrics.render().splitlines():
        if line.startswith(want + " "):
            return float(line.split()[1])
    return 0.0


def _drive(engine):
    """One iteration of the engine's loop by hand; False when idle."""
    engine._admit()
    did = engine._prefill_one()
    return bool(engine._decode_step() or did)


# ------------------------------------------------------------ the model
def test_model_api_dispatch_and_the_layout():
    cfg = phi4flash.Phi4FlashConfig.tiny()
    assert model_api(cfg) is phi4flash and family_name(cfg) == "phi4flash"
    layout = kv_pool.pool_layout(cfg)
    assert layout == kv_pool.PoolLayout(tokens=True, window=128,
                                        state_blocks=1)
    assert layout.kinds() == ("global", "window", "state")
    assert layout.window_blocks(64) == 3
    assert kv_pool.pool_layout(
        phi4flash.Phi4FlashConfig()).window_blocks(64) == 9
    with pytest.raises(ValueError, match="no kind"):
        kv_pool.PoolLayout(tokens=False)
    with pytest.raises(NotImplementedError, match="more than one"):
        kv_pool.PoolLayout(state_blocks=2)


def test_the_layer_pattern_is_the_published_one():
    kinds = phi4flash.layer_kinds(phi4flash.Phi4FlashConfig())
    assert [k for _, k, _ in kinds[:18]] == ["ssm", "attn"] * 9
    assert [k for _, k, _ in kinds[18:]] == ["gmu", "cross"] * 7
    assert [g for g, _, _ in kinds] == (["front"] * 16 + ["mid"] * 2
                                        + ["back"] * 14)
    assert kinds[14] == ("front", "ssm", 7) and kinds[31] == (
        "back", "cross", 6)
    for i in range(32):
        assert ref.where_is(32, i) == kinds[i]
    # All five mixers occur at the tiny depth too.
    tiny = phi4flash.layer_kinds(phi4flash.Phi4FlashConfig.tiny())
    assert [k for _, k, _ in tiny] == ["ssm", "attn", "ssm", "attn",
                                       "ssm", "attn", "gmu", "cross"]
    assert phi4flash.lambda_init(17) == pytest.approx(
        0.8 - 0.6 * np.exp(-5.1))


def test_the_published_tree_counts_3852_6_million_parameters():
    cfg = phi4flash.Phi4FlashConfig()
    tree = jax.eval_shape(lambda: phi4flash.init(cfg, jax.random.key(0)))
    count = {g: sum(a.size for a in jax.tree.leaves(tree[g]))
             for g in tree}
    assert count["embed"] == 200064 * 2560 == 512_163_840
    # 8 Mamba + 8 window; layers 16 and 17; 7 units + 7 cross.
    ssm, attn = 119_895_040, 98_322_304
    assert count["front"] == 8 * (ssm + attn)
    assert count["mid"] == ssm + attn
    assert count["back"] == 7 * (104_867_840 + 91_766_144)
    total = sum(count.values())
    assert total == 3_852_562_944 and round(total / 1e6, 1) == 3852.6
    # What a sequence holds, by kind (a block of 64 tokens).
    assert kv_pool.block_bytes_by_kind(cfg, 64) == {
        "global": 327_680, "window": 2_621_440, "state": 3_225_600}


@pytest.mark.parametrize("length,dtype,tol", [
    (64, jnp.float32, TOL), (300, jnp.float32, TOL), (7, jnp.float32, TOL),
    (300, jnp.bfloat16, 0.6)])
def test_forward_equals_the_reference(length, dtype, tol):
    """No cache: all five mixers, the window crossed twice at 300."""
    cfg = _tiny(dtype=dtype)
    weights = phi4flash.init(cfg, jax.random.key(3))
    tokens = jnp.asarray(_tokens(length, seed=length))
    got = np.asarray(phi4flash.forward(cfg, weights, tokens[None])[0])
    want = np.asarray(ref.logits(cfg, weights, tokens))
    assert np.abs(got - want).max() < tol
    assert np.abs(want).max() > 2.0          # logits, not zeros


def test_the_state_remembers_hundreds_of_tokens():
    """The seeded steps are Mamba's (log-uniform in [1e-3, 1e-1]): a
    change of the FIRST token still moves the logits 300 tokens on,
    through the state alone (no window reaches that far back but the
    full layer's, whose keys carry the state's trace too)."""
    cfg = _tiny()
    weights = phi4flash.init(cfg, jax.random.key(3))
    weights = {**weights, "mid": {**weights["mid"], "attn": jax.tree.map(
        jnp.zeros_like, weights["mid"]["attn"])}}
    tokens = np.asarray(_tokens(300, seed=5))
    other = tokens.copy()
    other[0] = (other[0] + 1) % 256
    a = np.asarray(ref.logits(cfg, weights, tokens))[-1]
    b = np.asarray(ref.logits(cfg, weights, other))[-1]
    assert np.abs(a - b).max() > 1e-3


# ------------------------------------------------- the paged forward alone
def _paged_prefill(cfg, weights, tokens, bt, blocks=8):
    """Prefill ``tokens`` a chunk of ``bt`` at a time into fresh pools,
    the last chunk padded; the slot's blocks are 1.. of each kind and
    its state block alternates between 1 and 2."""
    cache = phi4flash.init_paged_cache(cfg, blocks, bt)
    span = 6
    table = np.zeros((1, 1 + 2 * span), np.int32)
    n_chunks = -(-len(tokens) // bt)
    table[0, 1:1 + n_chunks] = np.arange(1, 1 + n_chunks)
    table[0, 1 + span:1 + span + n_chunks] = np.arange(1, 1 + n_chunks)
    logits = None
    for c in range(n_chunks):
        buf = np.zeros((1, bt), np.int32)
        piece = tokens[c * bt:(c + 1) * bt]
        buf[0, :len(piece)] = piece
        valid = c * bt + len(piece)
        write = 1 + c % 2
        logits, cache = phi4flash.forward_with_paged_cache(
            cfg, weights, jnp.asarray(buf), cache, jnp.asarray(table),
            jnp.int32(c * bt), valid_len=jnp.int32(valid),
            logits_at=jnp.int32(len(piece) - 1), window=bt,
            write_block=jnp.int32(write))
        table[0, 0] = write
    return logits[0, 0], cache, table


def test_a_padded_last_chunk_leaves_the_state_where_an_unpadded_run_does():
    """20 tokens as chunks of 8 (the last padded with 4 rows) and as
    chunks of 4 (none padded): rows at and beyond ``valid_len`` advance
    neither ``h`` nor the conv's tail."""
    cfg = _tiny(sliding_window=16)
    weights = phi4flash.init(cfg, jax.random.key(4))
    tokens = _tokens(20, seed=8)
    padded, cache_a, table_a = _paged_prefill(cfg, weights, tokens, 8)
    whole, cache_b, table_b = _paged_prefill(cfg, weights, tokens, 4)
    for leaf in ("state_h", "state_conv"):
        a = np.asarray(cache_a[leaf][:, table_a[0, 0]])
        b = np.asarray(cache_b[leaf][:, table_b[0, 0]])
        assert np.abs(a).max() > 1e-3
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    want = np.asarray(ref.logits(cfg, weights, np.asarray(tokens)))[-1]
    assert np.abs(np.asarray(padded) - want).max() < TOL
    assert np.abs(np.asarray(whole) - want).max() < TOL
    # The scratch block of the state stays the zero state.
    assert not np.asarray(cache_a["state_h"][:, 0]).any()
    assert not np.asarray(cache_a["state_conv"][:, 0]).any()


def test_one_key_value_layer_is_read_by_every_cross_layer():
    """Layer L/2 + 1's keys and values exist once: one layer of
    ``global_`` leaves, whatever the depth."""
    for cfg in (phi4flash.Phi4FlashConfig(), _tiny()):
        pool = jax.eval_shape(
            lambda: phi4flash.init_paged_cache(cfg, 4, 64))
        assert pool["global_k"].shape == (
            1, 4, cfg.kv_pairs, 64, 2 * cfg.head_dim)
        assert pool["window_k"].shape[0] == cfg.n_front
        assert pool["state_h"].shape == (
            cfg.n_front + 1, 4, cfg.ssm_state, cfg.ssm_inner)
        assert pool["state_h"].dtype == jnp.float32


# ------------------------------------------------------ through the engine
def test_chunks_and_steps_through_three_pools_equal_the_reference(params):
    """Requests of several lengths, one of them crossing the window of
    128 twice (200 + 100 tokens), one joining while others decode:
    every row the engine samples from against the reference's full
    forward pass over prompt + served tokens."""
    with _Tap(params, slots=3) as tap:
        first = [tap.submit(_tokens(200, seed=200), 100),
                 tap.submit(_tokens(64, seed=64), 12)]
        while first[0].first_token_at is None:
            import time
            time.sleep(0.005)
        rest = [tap.submit(_tokens(7, seed=7), 12)]
        for req in first + rest:
            tokens, rows = tap.rows(req)
            want = _reference_rows(params, req.prompt, tokens)
            assert np.abs(rows - want).max() < TOL, len(req.prompt)
        kv = tap.engine.kv_config()
        assert (kv["seq_blocks"], kv["table_len"]) == (1, 1 + 2 * 8)
        assert kv["pools"] == {"global": 3 * 8 + 2 + 1,
                               "window": 3 * 3 + 2 + 1,
                               "state": 3 + 2 + 1}


def test_a_prefix_hit_restores_all_three_kinds_to_the_bit(params):
    """A prompt served cold, then again: the second admission aliases
    the full layer's two blocks and the window layers' two, reads the
    snapshot taken after chunk 2, and serves the same logits TO THE
    BIT (the same programs on the same bits)."""
    prompt = _tokens(150, seed=9)
    taken = _counter("stpu_engine_state_snapshots_total", event="taken")
    restored = _counter("stpu_engine_state_snapshots_total",
                        event="restored")
    with _Tap(params, slots=2) as tap:
        cold = tap.submit(prompt, 40)
        cold_tokens, cold_rows = tap.rows(cold)
        assert cold.cached_prompt_tokens == 0
        assert _counter("stpu_engine_state_snapshots_total",
                        event="taken") == taken + 2
        nodes = tap.engine.prefix_cache.nodes()
        assert len(nodes) == 2
        for node in nodes:
            # One block of every kind, each held by the trie alone now.
            assert set(node.extra) == {"window", "state"}
            assert tap.engine._pool.refcount(node.block) == 1
            for kind, block in node.extra.items():
                assert tap.engine._pools[kind].refcount(block) == 1
        warm = tap.submit(prompt, 40)
        warm_tokens, warm_rows = tap.rows(warm)
        assert warm.cached_prompt_tokens == 128
        assert warm.prefill_chunks == 1
        assert _counter("stpu_engine_state_snapshots_total",
                        event="restored") == restored + 1
        stats = tap.engine.prefix_cache.stats()
        assert (stats["hits"], stats["misses"],
                stats["zero_copy_hits"]) == (1, 1, 1)
    assert warm_tokens == cold_tokens
    np.testing.assert_array_equal(warm_rows, cold_rows)
    want = _reference_rows(params, prompt, cold_tokens)
    assert np.abs(cold_rows - want).max() < TOL


def test_block_accounting_of_a_served_run(params):
    """By hand, one iteration at a time: a sequence of n tokens holds
    ceil(n / 64) blocks of the full layer, at most 3 of the window
    layers (128 / 64 + 1) and one state block; admission reserves the
    request's longest of each kind; window blocks are released behind
    the sequence; the pools are empty after the last request and an
    eviction of every node."""
    engine = DecodeEngine(TAP_CFG, params, slots=2, max_seq=512,
                          use_manifest=False)
    pools = engine._pools
    free = {k: p.available() for k, p in pools.items()}
    released = _counter("stpu_engine_window_blocks_released_total")
    prompt = _tokens(200, seed=11)
    req = engine.submit(prompt, max_tokens=120)       # 320 tokens: 5 blocks
    engine._admit()
    slot = engine._slots[0]
    assert slot.request is req
    assert {k: free[k] - p.available() for k, p in pools.items()} == {
        "global": 5, "window": 3, "state": 1}
    most_window = 0
    while _drive(engine):
        if slot.request is not req:
            continue
        n = slot.pos
        held = {"global": slot.blocks,
                "window": slot.blocks - slot.win_lo,
                "state": int(slot.own_state)}
        # Blocks back what is written and the next token's row.
        assert held["global"] in (-(-n // CHUNK), n // CHUNK + 1)
        assert held["window"] == min(
            held["global"], held["global"] - max(n - 127, 0) // CHUNK)
        assert held["window"] <= 3 and held["state"] == 1
        most_window = max(most_window, held["window"])
        row = engine._table[0]
        assert np.count_nonzero(row[1:9]) == held["global"]
        assert np.count_nonzero(row[9:17]) == held["window"]
        # Held and still promised add up to the admission's budget,
        # whatever the trie adopted meanwhile.
        assert len(slot.win_own) + slot.win_reserved == 3
        assert slot.blocks - len(slot.held) + slot.reserved == 5
    assert len(req.result(timeout=5.0)) == 120 and most_window == 3
    # 5 blocks; the token after the last (position 320) would reach
    # back to 193, block 3: blocks 0, 1 and 2 were released behind the
    # sequence, as refcount drops (the prompt's chunks are the trie's
    # too).
    assert _counter("stpu_engine_window_blocks_released_total") \
        == released + 3
    nodes = engine.prefix_cache.nodes()
    assert len(nodes) == 3                      # 200 tokens: 3 full chunks
    engine._admit()
    assert _counter("stpu_engine_cache_blocks", kind="snapshot") == 3
    assert _counter("stpu_engine_cache_blocks", kind="global") == 0
    for kind, pool in pools.items():
        assert pool.in_use() == 3 and pool.available() == free[kind] - 3
    while engine.prefix_cache.evict_one():
        pass
    for kind, pool in pools.items():
        assert pool.in_use() == 0 and pool.available() == free[kind]


def test_the_steps_counter_is_the_blocks_its_reads_fetched(params):
    """``stpu_attn_blocks_read_total`` over a served run against a plain
    count from what each dispatched step was given: for every row whose
    table names a state block of its own, the window layers' blocks from
    the one that holds position ``pos - 127`` and the full layer's from
    0, to the one that holds ``pos``, two to an entry of the kernel's
    list (``paged_attention.FOLD``; an odd count reads one block twice),
    times the layers that read them (2 window layers; the full layer
    and 1 cross layer). A request that joins while another decodes makes
    steps with one and with two decoding rows, and rows that ride with
    a table of zeros count nothing."""
    fold, calls = paged_attention.FOLD, []
    step = decode_engine._paged_step

    def spy(cfg, weights, cache, toks, pos, table, *rest):
        calls.append((np.asarray(pos), np.asarray(table)))
        return step(cfg, weights, cache, toks, pos, table, *rest)

    before = _counter("stpu_attn_blocks_read_total")
    engine = DecodeEngine(TAP_CFG, params, slots=3, max_seq=512,
                          use_manifest=False)
    with mock.patch.object(decode_engine, "_paged_step", spy):
        first = engine.submit(_tokens(150, seed=21), max_tokens=70)
        for _ in range(12):
            _drive(engine)
        second = engine.submit(_tokens(40, seed=22), max_tokens=30)
        while _drive(engine):
            pass
        engine._land(everything=True)
    assert len(first.result(timeout=5.0)) == 70
    assert len(second.result(timeout=5.0)) == 30
    want, rows_decoding = 0, set()
    for pos, table in calls:
        decoding = np.flatnonzero(table[:, 0])
        rows_decoding.add(len(decoding))
        for p in pos[decoding]:
            full = p // CHUNK + 1
            window = p // CHUNK - max(p - 127, 0) // CHUNK + 1
            want += (2 * -(-window // fold) + 2 * -(-full // fold)) * fold
    assert rows_decoding >= {1, 2} and len(calls) >= 69   # 1st: prefill
    assert _counter("stpu_attn_blocks_read_total") - before == want > 0


def test_a_hit_pins_and_aliases_by_kind_and_releases_drop_refcounts(params):
    """After a cold run of 4 full chunks, the same prompt again: the
    full layer aliases all 4 blocks, the window layers the last 2 (what
    the next token's window still reaches), the state is the deepest
    node's snapshot; a block released behind the sequence stays the
    trie's."""
    engine = DecodeEngine(TAP_CFG, params, slots=4, max_seq=512,
                          use_manifest=False)
    prompt = _tokens(260, seed=12)
    engine.submit(prompt, max_tokens=2)
    while _drive(engine):
        pass
    path = {n.key: n for n in engine.prefix_cache.nodes()}
    chain = [path[tuple(prompt[j * 64:(j + 1) * 64])] for j in range(4)]
    free = {k: p.available() for k, p in engine._pools.items()}
    req = engine.submit(prompt, max_tokens=70)        # 330 tokens: 6 blocks
    engine._admit()
    slot = engine._slots[0]
    assert req.cached_prompt_tokens == 256 and slot.win_lo == 2
    row = engine._table[0]
    assert row[0] == chain[3].extra["state"]
    assert list(row[1:5]) == [n.block for n in chain]
    assert list(row[9:13]) == [0, 0, chain[2].extra["window"],
                               chain[3].extra["window"]]
    assert {k: free[k] - p.available()
            for k, p in engine._pools.items()} == {
        "global": 2, "window": 2, "state": 1}
    window = engine._window_pool
    assert window.refcount(chain[2].extra["window"]) == 2
    assert window.refcount(chain[1].extra["window"]) == 1
    while _drive(engine):
        if slot.request is req:
            assert slot.blocks - slot.win_lo <= 3
    assert len(req.result(timeout=5.0)) == 70
    # The aliased blocks were released behind it: the trie's alone.
    for node in chain:
        assert window.refcount(node.extra["window"]) == 1
        assert engine._pool.refcount(node.block) == 1


def test_a_full_pool_costs_prefix_reuse_never_a_request(params):
    """One slot, so two spare blocks a kind: six prompts of three
    chunks each take snapshots, LRU gives them back, every request is
    served and served rightly."""
    evicted = _counter("stpu_engine_state_snapshots_total",
                       event="evicted")
    with _Tap(params, slots=1) as tap:
        pools = tap.engine._pools
        assert pools["state"].usable_blocks == 3
        for seed in range(30, 36):
            req = tap.submit(_tokens(150, seed=seed), 4)
            tokens, rows = tap.rows(req)
            want = _reference_rows(params, req.prompt, tokens)
            assert np.abs(rows - want).max() < TOL
            assert tap.engine.prefix_cache.stats()["chunks"] <= 2
        assert _counter("stpu_engine_state_snapshots_total",
                        event="evicted") >= evicted + 6
        # Everything is a node's or free again; nothing leaked.
        held = len(tap.engine.prefix_cache.nodes())
        for pool in pools.values():
            assert pool.in_use() == held and pool._reserved == 0


# ------------------------------------------------------- the parity's teeth
def test_a_snapshot_one_chunk_off_fails_the_parity():
    """The same hit as above, but the admission is handed the state
    after chunk 1 where chunk 2's belongs: the served logits leave the
    reference by far more than the tolerance."""
    cfg = _tiny(max_seq_len=2051)
    weights = phi4flash.init(cfg, jax.random.key(0))
    prompt = _tokens(150, seed=9)
    admit = DecodeEngine._try_admit_paged

    def one_off(self, i, req):
        ok = admit(self, i, req)
        held = self._slots[i].held
        if ok and len(held) == 2:
            self._table[i, 0] = held[0].extra["state"]
        return ok

    with mock.patch.object(DecodeEngine, "_try_admit_paged", one_off), \
            _Tap(weights, cfg=cfg, slots=2) as tap:
        cold_tokens, cold_rows = tap.rows(tap.submit(prompt, 8))
        want = _reference_rows(weights, prompt, cold_tokens, cfg)
        assert np.abs(cold_rows - want).max() < TOL
        warm = tap.submit(prompt, 8)
        warm_tokens, warm_rows = tap.rows(warm)
        assert warm.cached_prompt_tokens == 128
        want = _reference_rows(weights, prompt, warm_tokens, cfg)
        assert np.abs(warm_rows - want).max() > 100 * TOL


def test_a_window_one_block_off_fails_the_parity():
    """The program's window mask widened by one block of 64: rows
    beyond the window leave the reference."""
    cfg = _tiny(max_seq_len=2053)
    weights = phi4flash.init(cfg, jax.random.key(0))
    prompt = _tokens(250, seed=13)
    mask = phi4flash._paged_mask

    def wider(kpos, positions, valid_len, window=0):
        return mask(kpos, positions, valid_len, window and window + 64)

    with mock.patch.object(phi4flash, "_paged_mask", wider), \
            _Tap(weights, cfg=cfg, slots=2) as tap:
        tokens, rows = tap.rows(tap.submit(prompt, 8))
    want = _reference_rows(weights, prompt, tokens, cfg)
    assert np.abs(rows - want).max() > 100 * TOL


# ------------------------------------------------------------ the refusals
@pytest.mark.parametrize("what,call", [
    ("int8 weights", lambda cfg, p: phi4flash.quantize_params(cfg, p)),
    ("int8 pool", lambda cfg, p: phi4flash.init_paged_cache(
        cfg, 4, 64, quantized=True)),
    ("tp > 1", lambda cfg, p: phi4flash.cache_specs(cfg)),
    ("speculative", lambda cfg, p: phi4flash.verify_step_paged(cfg)),
    ("speculative", lambda cfg, p: DecodeEngine(
        cfg, p, slots=2, max_seq=256, spec_k=2, use_manifest=False)),
    ("host spill tier", lambda cfg, p: DecodeEngine(
        cfg, p, slots=2, max_seq=256, prefix_cache_mb=1.0,
        use_manifest=False)),
    ("int8 weights", lambda cfg, p: DecodeEngine(
        cfg, p, slots=2, max_seq=256, weight_quant=True,
        use_manifest=False)),
    ("int8 pool", lambda cfg, p: DecodeEngine(
        cfg, p, slots=2, max_seq=256, kv_quant=True, use_manifest=False)),
    ("LoRA", lambda cfg, p: phi4flash.forward(
        cfg, {**p, "front": {**p["front"], "attn": {
            **p["front"]["attn"], "wo_lora_a": 0}}},
        jnp.zeros((1, 4), jnp.int32))),
    ("tp > 1", lambda cfg, p: gang_replica.cache_shardings(
        cfg, None, None)),
])
def test_refusals_by_name(params, what, call):
    with pytest.raises(NotImplementedError,
                       match=r"phi4flash \(Phi-4-mini-flash") as e:
        call(TAP_CFG, params)
    assert what.split()[0] in str(e.value)


def test_a_trie_over_several_kinds_of_block_refuses_the_host_tier():
    """``evict_one``'s spill branch releases ``node.block`` alone: a
    node that also holds a window and a state block must never be
    offered to it, whatever the family refuses."""
    pools = {k: kv_pool.BlockPool(4, 64) for k in ("global", "window")}
    with pytest.raises(NotImplementedError, match="several kinds"):
        kv_pool.PagedPrefixCache(
            pools["global"], 64,
            host_pool=kv_pool.HostBlockPool(budget_bytes=1 << 20),
            extra_pools={"window": pools["window"]})


def test_presets_and_the_recipes_names():
    from skypilot_tpu.recipes import serve_llm
    assert serve_llm.model_config("phi-4-mini-flash") == \
        phi4flash.Phi4FlashConfig()
    assert serve_llm.model_config("phi4flash-tiny").n_layers == 8
    with pytest.raises(ValueError, match="multiple of 4"):
        phi4flash.Phi4FlashConfig(n_layers=6)


def test_a_state_kept_in_bfloat16_fails_the_parity():
    """``h`` stored in bfloat16 between steps (the precision below the
    one the configuration states), everything else float32: the served
    logits leave the reference by tens of tolerances. The logits see
    it (0.0056 here, 19 tolerances); a token rule with a margin of 0.3
    logits cannot (PERF.md, PR 36: 0 of 425 positions on the chip)."""
    cfg = _tiny(max_seq_len=2055, state_dtype=jnp.bfloat16)
    weights = phi4flash.init(cfg, jax.random.key(0))
    prompt = _tokens(250, seed=14)
    with _Tap(weights, cfg=cfg, slots=2) as tap:
        assert tap.engine._cache["state_h"].dtype == jnp.bfloat16
        tokens, rows = tap.rows(tap.submit(prompt, 30))
    want = _reference_rows(weights, prompt, tokens, cfg)
    worst = np.abs(rows - want).max()
    assert 10 * TOL < worst < 0.1, worst
