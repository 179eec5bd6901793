"""The engine loop runs one step ahead of what it has read (PERF.md,
PR 29): sampled tokens go from program to program on the device, a
slot is retired when its last step is DISPATCHED and its request ends
when that step's tokens are READ, an iteration later. Held here:

  * the served tokens are the blocking order's (everything read before
    anything is planned — the drained case of the same loop) and the
    reference decode's, for every family, the bf16 and the int8 pool,
    greedy and seeded sampling, through every way a request can end
    (the int8 pool's reference is the same engine serving the request
    alone, read before anything is planned);
  * a block freed at dispatch and handed out again at once is never
    written early;
  * ``stpu_engine_lookahead_steps_total`` says how often the loop ran
    ahead, and is 0 while a slot drafts;
  * shutdown, drain and a crash with results unread end every request
    with its own outcome and leak no block;
  * a step's block table names the decoding slots' blocks and the
    scratch block in every other row, for every family (PR 34).
"""
import random
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import (brumby, deepseek, gemma, llama, mixtral,
                                 phi4flash)
from skypilot_tpu.observability import reqlog
from skypilot_tpu.serve import decode_engine
from skypilot_tpu.serve.decode_engine import DecodeEngine, EngineError
from skypilot_tpu.utils import fault_injection


def _tiny(family="llama"):
    if family == "mixtral":
        return mixtral, mixtral.MixtralConfig.tiny()
    if family == "gemma":
        return gemma, gemma.GemmaConfig.tiny(vocab_size=128)
    if family == "deepseek":
        return deepseek, deepseek.DeepseekV3Config.tiny(vocab_size=128)
    if family == "brumby":
        return brumby, brumby.BrumbyConfig.tiny(vocab_size=128)
    if family == "phi4flash":
        return phi4flash, phi4flash.Phi4FlashConfig.tiny(vocab_size=128)
    return llama, llama.LlamaConfig.tiny(vocab_size=128)


_SIZES = {"slots": 2, "max_seq": 64, "prefill_chunk": 8,
          "use_manifest": False}


def _engine(family="llama", int8=False, **kw):
    mdl, cfg = _tiny(family)
    params = mdl.init(cfg, jax.random.key(0))
    return mdl, cfg, params, DecodeEngine(
        cfg, params, **{**_SIZES, "kv_quant": int8, **kw})


def _drive(engine, blocking=False, each=None, rounds=600):
    """Step an UNSTARTED engine until idle. ``blocking`` reads every
    iteration's results before the next is planned: the order the
    engine had before it looked ahead. ``each`` is called after every
    admission (the moment a retired slot may have a new owner)."""
    for _ in range(rounds):
        engine._admit()
        if each is not None:
            each()
        did = engine._prefill_one()
        did = engine._decode_step() or did
        if blocking:
            engine._land(everything=True)
        if not did and not engine._waiting:
            assert not engine._behind and not engine._fresh
            return
    raise AssertionError("engine did not quiesce")


def _reference(mdl, cfg, params, prompt, n, int8=False):
    """The greedy tokens a request is owed: the row-cache decode's,
    or, for the int8 pool (not bit-identical to bf16 by design), what
    the same engine serves when the request is alone and every
    iteration is read before the next is planned."""
    if int8:
        engine = DecodeEngine(cfg, params, kv_quant=True, **_SIZES)
        req = engine.submit(prompt, max_tokens=n)
        _drive(engine, blocking=True)
        return req.result(timeout=5.0)
    ref = mdl.decode(cfg, params, jnp.asarray([prompt]),
                     jnp.int32(len(prompt)), n, len(prompt) + n)
    return [int(t) for t in ref[0]]


def _pool_is_whole(engine):
    pool = engine._pool
    assert all(s.request is None for s in engine._slots)
    assert pool.free_blocks() + len(engine.prefix_cache.nodes()) == \
        pool.usable_blocks
    assert pool._reserved == 0
    assert all(n.refs == 0 for n in engine.prefix_cache.nodes())


def _outcomes():
    return {o: decode_engine._REQUESTS.labels(outcome=o).get()
            for o in ("ok", "cancelled", "cache_full", "error",
                      "shutdown")}


def _specs(cfg, seed):
    """Ragged prompts (one, two and three chunks), max_tokens 1 and 2
    among them, greedy and seeded sampling mixed."""
    rng = random.Random(seed)
    lens = [3, 9, 17, 5, 12, 20, 2, 8]
    owed = [1, 2, 7, 5, 1, 9, 2, 6]
    temps = [0.0, 0.0, 0.8, 0.0, 1.0, 0.0, 0.6, 0.0]
    return [([rng.randint(1, cfg.vocab_size - 1) for _ in range(n)],
             m, t, 100 + j)
            for j, (n, m, t) in enumerate(zip(lens, owed, temps))]


# ===================================================== (a) token parity
@pytest.mark.parametrize("family,int8", [
    ("llama", False), ("llama", True), ("mixtral", False),
    ("mixtral", True), ("gemma", False), ("deepseek", False)])
def test_lookahead_serves_the_blocking_orders_tokens(family, int8):
    """Eight ragged requests over two slots, so slots are retired and
    taken over while their last tokens are unread: the loop that runs
    ahead, the same loop drained every iteration, and (greedy) the
    reference decode give the same tokens; and a request did end on
    the step after another was admitted into its slot."""
    mdl, cfg, params, ahead = _engine(family, int8)
    specs = _specs(cfg, seed=3)
    took_over = []

    def watch():
        for entry in ahead._behind:
            for i, req, outcome in entry.rows:
                if outcome is not None and i is not None and \
                        ahead._slots[i].request not in (None, req):
                    took_over.append(i)

    def serve(engine, **kw):
        reqs = [engine.submit(p, max_tokens=m, temperature=t, seed=s)
                for p, m, t, s in specs]
        _drive(engine, **kw)
        return [r.result(timeout=5.0) for r in reqs]

    got = serve(ahead, each=watch)
    _, _, _, drained = _engine(family, int8)
    assert got == serve(drained, blocking=True)
    assert took_over, "no slot changed hands with a token unread"
    assert decode_engine._LOOKAHEAD.get() > 0
    for (p, m, t, _), toks in zip(specs, got):
        assert len(toks) == m
        if t == 0.0 and family != "deepseek":   # no row-cache decode
            assert toks == _reference(mdl, cfg, params, p, m, int8)


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_started_engine_matches_the_hand_driven_one(int8):
    """The engine thread's loop and the hand-driven one are the same
    code: same tokens, whenever the submissions arrive."""
    mdl, cfg, params, byhand = _engine("llama", int8)
    specs = _specs(cfg, seed=5)
    reqs = [byhand.submit(p, max_tokens=m, temperature=t, seed=s)
            for p, m, t, s in specs]
    _drive(byhand)
    want = [r.result(timeout=5.0) for r in reqs]
    engine = DecodeEngine(cfg, params, slots=2, max_seq=64,
                          prefill_chunk=8, kv_quant=int8).start()
    try:
        reqs = []
        for p, m, t, s in specs:
            reqs.append(engine.submit(p, max_tokens=m, temperature=t,
                                      seed=s))
            time.sleep(0.003)
        assert [r.result(timeout=120.0) for r in reqs] == want
    finally:
        engine.shutdown()


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_cache_full_ends_a_request_at_dispatch(int8):
    """A slot whose next write would be the row's last position is
    retired when that step is dispatched, with ``cache_full``, and its
    tokens are the reference's first ones."""
    mdl, cfg, params, engine = _engine("llama", int8)
    prompt = list(range(1, 12))
    want = _reference(mdl, cfg, params, prompt, 20, int8)
    want_other = _reference(mdl, cfg, params, prompt[:4], 3, int8)
    engine._limit = 16           # pos 11 after the prompt: 5 tokens fit
    before = _outcomes()
    req = engine.submit(prompt, max_tokens=20)
    other = engine.submit(prompt[:4], max_tokens=3)
    _drive(engine)
    got = req.result(timeout=5.0)
    assert got == want[:len(got)]
    assert len(got) == 16 - 1 - len(prompt) + 1
    assert other.result(timeout=5.0) == want_other
    after = _outcomes()
    assert after["cache_full"] - before["cache_full"] == 1
    assert after["ok"] - before["ok"] == 1


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_cancel_mid_decode_ends_behind_its_unread_tokens(int8):
    """A cancel is seen when the next step is planned: the slot is
    retired there, the token already dispatched still arrives, and the
    stream ends after it — a prefix of the uncancelled stream."""
    mdl, cfg, params, engine = _engine("llama", int8)
    prompt = [5, 9, 42, 7]
    before = _outcomes()
    req = engine.submit(prompt, max_tokens=30)
    keep = engine.submit(prompt[::-1], max_tokens=12)
    engine._admit()
    for _ in range(5):
        engine._prefill_one()
        engine._decode_step()
    assert engine._behind        # a step's tokens are unread
    req.cancel()
    _drive(engine)
    got = req.result(timeout=5.0)
    assert 0 < len(got) < 30
    assert got == _reference(mdl, cfg, params, prompt, 30,
                             int8)[:len(got)]
    assert keep.result(timeout=5.0) == \
        _reference(mdl, cfg, params, prompt[::-1], 12, int8)
    after = _outcomes()
    assert after["cancelled"] - before["cancelled"] == 1
    _pool_is_whole(engine)


@pytest.mark.parametrize("family", ["llama", "deepseek"])
def test_resume_continues_the_stream_bit_identically(family):
    """``submit(resume=…)`` prefills the emitted tokens as prompt; the
    first token after them is sampled inside the chunk program at the
    original absolute position, so the continuation equals the
    uninterrupted stream, seeded sampling included."""
    mdl, cfg, params, engine = _engine(family)
    prompt = [3, 1, 4, 1, 5, 9, 2, 6, 5, 3]
    whole = engine.submit(prompt, max_tokens=12, temperature=0.9,
                          seed=77)
    _drive(engine)
    whole = whole.result(timeout=5.0)
    for cut in (1, 5, 11):
        rest = engine.submit(prompt, max_tokens=12 - cut,
                             temperature=0.9, seed=77,
                             resume=whole[:cut])
        _drive(engine)
        assert rest.result(timeout=5.0) == whole[cut:], cut


# ========================================== (b) a freed block's reuse
def test_block_freed_at_dispatch_is_reused_at_once_and_never_early(
        reference_stream):
    """One spare block: the second request waits at the queue's head
    until the first is retired — at the dispatch of its last step,
    which still reads and writes the blocks — and is admitted into
    those very blocks in the next iteration, its chunk queued behind
    that step on the device. Both streams are the reference's,
    prefilled in the engine's chunks (a bf16 near-tie of the 17-token
    prompt flips between one pass and chunks of 8, not between the
    caches: tests/conftest.py)."""
    mdl, cfg, params, engine = _engine(
        "llama", kv_pool_blocks=5)             # 4 usable blocks of 8
    first_p = list(range(1, 18))               # 17 + 7 = 24: 3 blocks
    second_p = list(range(40, 60))             # 20 + 10 = 30: 4 blocks
    first = engine.submit(first_p, max_tokens=7)
    second = engine.submit(second_p, max_tokens=10)
    seen = {}

    def watch():
        if engine._slots[0].request is first:
            seen["first"] = set(
                int(b) for b in engine._table[0, :engine._slots[0].blocks])
        for i, slot in enumerate(engine._slots):
            if slot.request is second and "unread" not in seen:
                # Admitted while the first one's last token is unread.
                seen["unread"] = any(
                    req is first and outcome == "ok"
                    for e in engine._behind for _, req, outcome in e.rows)
                seen["slot"] = i

    def after():
        for i, slot in enumerate(engine._slots):
            if slot.request is second:
                seen.setdefault("second", set()).update(
                    int(b) for b in engine._table[i, :slot.blocks])

    def both():
        watch()
        after()

    _drive(engine, each=both)
    assert first.result(timeout=5.0) == reference_stream(
        mdl, cfg, params, first_p, 7, chunk=_SIZES["prefill_chunk"])
    assert second.result(timeout=5.0) == reference_stream(
        mdl, cfg, params, second_p, 10, chunk=_SIZES["prefill_chunk"])
    assert seen["unread"], "the second request was admitted too late"
    assert seen["first"] & seen["second"], \
        "no block of the first request was handed to the second"
    _pool_is_whole(engine)


# ================================================= (c) the counter
@pytest.mark.parametrize("mode", ["bf16", "int8", "drafting",
                                  "drafts-off"])
def test_lookahead_counter_says_how_often_the_loop_ran_ahead(mode):
    """Over a run of plain decode steps nearly every step is
    dispatched with the one before unread; while any slot may draft,
    none is (the drafter reads the last token's value), and the tokens
    are the same either way; slots that stopped drafting let the loop
    run ahead again."""
    spec = {"drafting": {"spec_k": 4, "spec_ngram": 2},
            "drafts-off": {"spec_k": 4, "spec_ngram": 2,
                           "spec_min_accept": 1.5}}.get(mode, {})
    mdl, cfg, params, engine = _engine("llama", mode == "int8", **spec)
    prompts = [[5, 6, 7] * 6, [9, 4, 9, 4, 9, 4, 9, 4]]
    ahead0 = decode_engine._LOOKAHEAD.get()
    steps0 = {k: c.get() for k, c in decode_engine._STEP_KIND.items()}
    reqs = [engine.submit(p, max_tokens=40) for p in prompts]
    _drive(engine)
    ahead = decode_engine._LOOKAHEAD.get() - ahead0
    steps = {k: c.get() - steps0[k]
             for k, c in decode_engine._STEP_KIND.items()}
    _, _, _, plain = _engine("llama", mode == "int8")
    want = [plain.submit(p, max_tokens=40) for p in prompts]
    _drive(plain, blocking=True)
    assert [r.result(timeout=5.0) for r in reqs] == \
        [r.result(timeout=5.0) for r in want]
    if mode == "drafting":
        assert steps["verify"] > 0
        assert ahead == 0
    elif mode == "drafts-off":
        # Both slots give up after their 16-draft grace window.
        assert steps["verify"] > 0
        assert 0 < ahead < steps["decode"]
    else:
        assert steps["verify"] == 0
        assert ahead / steps["decode"] > 0.9
        assert ahead == steps["decode"] - 1


# ===================================== (d) ends with results unread
def _mid_flight(int8=False):
    """Three requests over two slots, driven by hand to the iteration
    in which the shortest one's last step has been dispatched and not
    read: its slot is retired, its request is not finished."""
    mdl, cfg, params, engine = _engine("llama", int8)
    specs = [([7, 3, 9, 1], 3), ([2, 8, 6, 4, 1], 30),
             ([11, 12, 13], 25)]
    reqs = [engine.submit(p, max_tokens=m) for p, m in specs]
    for _ in range(50):
        engine._admit()
        engine._prefill_one()
        engine._decode_step()
        if engine._retiring:
            break
    assert engine._retiring == 1 and engine._behind
    assert reqs[0].emitted < 3
    refs = [_reference(mdl, cfg, params, p, m, int8) for p, m in specs]
    return engine, reqs, refs


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_shutdown_reads_what_is_unread_before_it_frees_slots(int8):
    engine, reqs, refs = _mid_flight(int8)
    before = _outcomes()
    assert engine.in_flight() == 3       # one retiring, one live, one queued
    engine._stop = True
    engine._loop()                       # the thread's own exit path
    # Retired before the stop: every token, its own outcome.
    assert reqs[0].result(timeout=5.0) == refs[0]
    for req, ref in zip(reqs[1:], refs[1:]):
        got = []
        with pytest.raises(EngineError, match="engine shut down"):
            for tok in req.stream(timeout=5.0):
                got.append(tok)
        assert got == ref[:len(got)]
    after = _outcomes()
    assert after["ok"] - before["ok"] == 1
    assert after["shutdown"] - before["shutdown"] == 2
    assert engine.in_flight() == 0
    _pool_is_whole(engine)


def test_injected_step_fault_with_results_unread():
    """``engine.step`` fires before a dispatch, with the step before
    unread: the loop dies, the unread tokens are still delivered, the
    request retired before the fault ends ``ok``, the others with the
    fault's error, and the pool is whole."""
    engine, reqs, refs = _mid_flight()
    before = _outcomes()
    with fault_injection.inject("engine.step", times=1):
        engine._loop()
    assert engine.failed() and "InjectedFault" in engine.failed()
    assert reqs[0].result(timeout=5.0) == refs[0]
    got = []
    with pytest.raises(EngineError, match="InjectedFault"):
        for tok in reqs[1].stream(timeout=5.0):
            got.append(tok)
    assert got and got == refs[1][:len(got)]
    with pytest.raises(EngineError, match="InjectedFault"):
        reqs[2].result(timeout=5.0)
    after = _outcomes()
    assert after["ok"] - before["ok"] == 1
    assert after["error"] - before["error"] == 2
    _pool_is_whole(engine)
    with pytest.raises(EngineError, match="engine failed"):
        engine.submit([1, 2], max_tokens=2)


def test_drain_finishes_what_is_in_flight_and_counts_the_retiring():
    """``drain()`` refuses new work and lets the rest finish; a request
    whose slot is retired and whose last token is unread is still in
    flight, so a replica is not torn down under it."""
    engine, reqs, refs = _mid_flight()
    engine.drain()
    with pytest.raises(EngineError, match="draining"):
        engine.submit([1, 2], max_tokens=2)
    assert not any(s.request is reqs[0] for s in engine._slots)
    assert engine.in_flight() == 3
    _drive(engine)
    assert [r.result(timeout=5.0) for r in reqs] == refs
    assert engine.in_flight() == 0
    _pool_is_whole(engine)


def test_supervisor_restart_after_a_fault_with_results_unread():
    """The supervisor's ladder over the same seam, on the engine's own
    thread: requests in flight at the fault end (none hangs), the
    fresh engine serves the reference's tokens again."""
    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    sup = decode_engine.EngineSupervisor(
        lambda: DecodeEngine(cfg, params, slots=2, max_seq=64,
                             prefill_chunk=8),
        backoff_base=0.01, poll_interval=0.01).start()
    try:
        prompt = [4, 8, 15, 16, 23, 42]
        ref = _reference(mdl, cfg, params, prompt, 10)
        assert sup.submit(prompt, max_tokens=10).result(60.0) == ref
        with fault_injection.inject("engine.step", times=1, skip=3):
            reqs = [sup.submit(prompt, max_tokens=10) for _ in range(2)]
            for req in reqs:
                got = []
                with pytest.raises(EngineError):
                    for tok in req.stream(timeout=60.0):
                        got.append(tok)
                assert got == ref[:len(got)]
        deadline = time.monotonic() + 30.0
        while not sup.healthy() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sup.healthy() and sup.restarts == 1
        assert sup.submit(prompt, max_tokens=10).result(60.0) == ref
    finally:
        sup.shutdown()


# ==================== the interval a step's tokens are charged with
def test_device_time_shares_are_intervals_between_reads(tmp_state_dir):
    """A step's time is the interval between two reads: with dispatch
    running ahead, dispatch to read spans two steps, and the shares of
    one request would sum to about twice the time it was decoding."""
    mdl, cfg, params, engine = _engine()
    reqlog.arm(sample=1.0)
    try:
        t0 = time.perf_counter()
        req = engine.submit([5, 9, 42], max_tokens=40)
        _drive(engine)
        wall = time.perf_counter() - t0
    finally:
        reqlog.disarm()
    assert len(req.result(timeout=5.0)) == 40
    assert 0.0 < req.device_time_s <= wall
    assert req.reqlog_record["generated_tokens"] == 40
    assert req.reqlog_record["outcome"] == "ok"


def test_warmup_reaches_every_program_the_loop_dispatches():
    """No program is built after ``warmup()``: the chunk programs take
    the token vector whatever produced it (the engine's first upload,
    a chunk, a step), committed to its device from the start."""
    from skypilot_tpu.utils import compile_cache
    compile_cache.enable()

    def built():
        return sum(count.get()
                   for count, _ in compile_cache._BY_SOURCE.values())

    mdl, cfg = _tiny()
    params = mdl.init(cfg, jax.random.key(0))
    for int8 in (False, True):
        engine = DecodeEngine(cfg, params, slots=3, max_seq=64,
                              prefill_chunk=8, kv_quant=int8).start()
        try:
            engine.warmup()
            before = built()
            rng = random.Random(1)
            reqs = [engine.submit(
                [rng.randint(1, 127) for _ in range(rng.randint(2, 30))],
                max_tokens=rng.randint(1, 9)) for _ in range(8)]
            for r in reqs:
                r.result(timeout=120.0)
            assert built() == before, int8
        finally:
            engine.shutdown()


# ============================================== (f) the step's table
def _blocks_by_leaf(engine, slots_held):
    """For every pool leaf, the sorted block ids (of the leaf's kind)
    that the table rows of ``slots_held`` name
    (``DecodeEngine._slot_blocks``)."""
    held = {kind: set() for kind in engine._pools}
    for i in slots_held:
        for kind, blocks in engine._slot_blocks(i).items():
            held[kind].update(blocks)
    one = len(held) == 1
    return {leaf: sorted(next(iter(held.values())) if one
                         else held[leaf.split("_")[0]])
            for leaf in engine._cache}


@pytest.mark.parametrize("family", ["llama", "mixtral", "gemma",
                                    "deepseek", "brumby", "phi4flash"])
def test_a_steps_table_names_only_the_decoding_slots(family,
                                                     monkeypatch):
    """Three slots: one decodes, one prefills a four-chunk prompt
    beside it, one stays free. Every decode step's table holds the
    decoding slots' own rows and names block 0, the scratch block, in
    every other row — which is how a step's program knows the rows
    that decode (deepseek's expert layer computes no expert for the
    others; brumby's and phi4flash's steps leave their state alone) —
    and the prefilling slot's blocks, of every kind, hold after the
    step what they held before it, leaf for leaf."""
    _, cfg, _, engine = _engine(family, slots=3)
    seen = []
    step = decode_engine._paged_step

    def spy(cfg, params, cache, toks, pos, table, *rest):
        seen.append((np.asarray(table), engine._table.copy()))
        return step(cfg, params, cache, toks, pos, table, *rest)

    monkeypatch.setattr(decode_engine, "_paged_step", spy)
    rng = random.Random(5)
    draw = lambda n: [rng.randint(1, cfg.vocab_size - 1)
                      for _ in range(n)]
    reqs = [engine.submit(draw(5), max_tokens=12),
            engine.submit(draw(30), max_tokens=3)]
    beside_a_prefill = beside_a_free_slot = 0
    for _ in range(100):
        engine._admit()
        did = engine._prefill_one()
        slots = engine._slots
        decoding = [i for i, s in enumerate(slots) if s.request
                    and s.prefilled >= len(s.request.prompt)]
        prefilling = [i for i, s in enumerate(slots) if s.request
                      and 0 < s.prefilled < len(s.request.prompt)]
        blocks = _blocks_by_leaf(engine, prefilling)
        held = {leaf: np.asarray(a[:, blocks[leaf]])
                for leaf, a in engine._cache.items()}
        steps = len(seen)
        did = engine._decode_step() or did
        if len(seen) > steps:
            table, own = seen[-1]      # own: grown for this step
            others = [i for i in range(3) if i not in decoding]
            np.testing.assert_array_equal(table[decoding], own[decoding])
            assert (table[decoding, 0] != 0).all()
            assert not table[others].any()
            for leaf, a in engine._cache.items():
                np.testing.assert_array_equal(
                    np.asarray(a[:, blocks[leaf]]), held[leaf])
            beside_a_prefill += any(blocks.values())
            beside_a_free_slot += any(s.request is None for s in slots)
        if not did and not engine._waiting:
            break
    assert [len(r.result(timeout=5.0)) for r in reqs] == [12, 3]
    assert beside_a_prefill >= 3 and beside_a_free_slot >= 3
