"""DeepSeek-V3 (models/deepseek.py) held to its plain reference
(benchmarks/reference/deepseek_arch.py) at tiny sizes on the CPU, seeded
random weights: the full pass, chunked prefill and paged decode through
the engine's own programs (which pins absorbed = expanded attention),
the routing against a plain loop, the expert shares against the uncut
layer, the expert loop (only the held experts a counted row chose)
against the all-held-experts form, YaRN against its closed forms, what
the latent pool costs, the routing counters, and what the family
refuses."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import deepseek_arch as ref
from skypilot_tpu.models import deepseek, family_name, model_api
from skypilot_tpu.ops.pallas import paged_attention
from skypilot_tpu.observability import metrics
from skypilot_tpu.serve import decode_engine, gang_replica, kv_pool
from skypilot_tpu.serve.decode_engine import DecodeEngine


def _tiny(dtype=jnp.float32, **changes):
    return dataclasses.replace(deepseek.DeepseekV3Config.tiny(),
                               dtype=dtype, **changes)


def _tokens(n, seed=1, vocab=256):
    return np.asarray(jax.random.randint(jax.random.key(seed), (n,), 0,
                                         vocab))


def test_model_api_dispatch():
    cfg = _tiny()
    assert model_api(cfg) is deepseek
    assert family_name(cfg) == "deepseek"


# ------------------------------------------------------------ full pass
@pytest.mark.parametrize("rank", [0, 3])
def test_forward_matches_the_reference_float32(rank):
    """1e-4 relative: both compute in float32 with the same weights;
    what is left is the order of the sums (one scan body and tiles in
    the program, head blocks and an expert loop in the reference)."""
    cfg = _tiny(ep_rank=rank)
    params = deepseek.init(cfg, jax.random.key(0))
    toks = _tokens(48)
    with jax.default_matmul_precision("highest"):
        got = deepseek.forward(cfg, params, jnp.asarray(toks)[None])[0]
    want = ref.logits(cfg, params, toks)
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())


def test_forward_matches_the_reference_bfloat16():
    """The program in bf16 (weights, activations) against the float32
    reference on the same bf16 weights, in parts of the logits' range,
    over the rows whose own routing is further than 0.1 router logits
    from a tie: the median row within 0.015 and nine in ten within
    0.03. Reason: a bf16 value carries 8 bits, so each of the some 30
    matmuls between tokens and logits is off by about 2^-9 of its size;
    measured over five seeds, median 0.006-0.007 and ninth decile
    0.010-0.014. The largest row is not bounded: a row that attends to
    a token whose expert bf16 chose otherwise inherits that change
    (0.076 at this seed). A wrong scale, frequency or expert weight
    moves EVERY row by tenths of the range."""
    cfg = _tiny(dtype=jnp.bfloat16)
    params = deepseek.init(cfg, jax.random.key(0))
    toks = _tokens(48)
    got = deepseek.forward(cfg, params, jnp.asarray(toks)[None])[0]
    want, slack = ref.logits_and_slack(cfg, params, toks)
    held = np.asarray(slack) > 0.1
    assert held.sum() > 20
    err = np.abs(np.asarray(got, np.float32)
                 - np.asarray(want)).max(-1)[held] / float(jnp.ptp(want))
    assert np.median(err) < 0.015 and np.quantile(err, 0.9) < 0.03


# ----------------------------------------- the engine's paged programs
def _paged_logits(cfg, params, toks, bt, prompt_len):
    """Logits at every position: the prompt in chunks through
    ``_paged_prefill_chunk``'s forward (expanded attention), the rest
    token by token through ``_paged_step``'s (absorbed attention)."""
    pool = deepseek.init_paged_cache(cfg, 16, bt)
    table = jnp.arange(1, 13, dtype=jnp.int32)[None]
    rows = []
    for c in range(prompt_len // bt):
        logits, pool, _ = deepseek.forward_with_paged_cache(
            cfg, params, jnp.asarray(toks[c * bt:(c + 1) * bt])[None],
            pool, table, jnp.int32(c * bt), window=2 * bt,
            write_block=table[0, c])
        rows.append(logits[0])
    for i in range(prompt_len, len(toks)):
        logits, pool, _ = deepseek.forward_with_paged_cache(
            cfg, params, jnp.asarray(toks[i:i + 1])[None], pool, table,
            jnp.asarray([i]), window=2 * bt)
        rows.append(logits[0])
    return jnp.concatenate(rows, axis=0)


def test_chunked_prefill_and_paged_decode_match_the_reference():
    """Every position's logits: three chunks of the expanded form, then
    24 steps of the absorbed form across tile boundaries, against the
    reference's one full pass. This is what pins absorbed = expanded."""
    cfg = _tiny()
    params = deepseek.init(cfg, jax.random.key(0))
    toks = _tokens(48)
    with jax.default_matmul_precision("highest"):
        got = _paged_logits(cfg, params, toks, bt=8, prompt_len=24)
    want = ref.logits(cfg, params, toks)
    assert float(jnp.abs(got - want).max()) < 1e-4 * float(
        jnp.abs(want).max())


def test_engine_serves_the_reference_greedy_tokens():
    """Through DecodeEngine and its jitted _paged_prefill_chunk /
    _paged_step: the served tokens are the reference's greedy ones
    (float32; margins under 1e-4 would be a coincidence of the seed)."""
    cfg = _tiny()
    params = deepseek.init(cfg, jax.random.key(0))
    engine = DecodeEngine(cfg, params, slots=3, max_seq=64,
                          prefill_chunk=8,
                          use_manifest=False).start()
    try:
        prompts = [list(map(int, _tokens(n, seed=n))) for n in (19, 5, 9)]
        handles = [engine.submit(p, max_tokens=10) for p in prompts]
        served = [h.result(timeout=300.0) for h in handles]
    finally:
        engine.shutdown()
    for prompt, tokens in zip(prompts, served):
        seq = prompt + tokens
        want = ref.logits(cfg, params, np.asarray(seq))
        greedy = np.asarray(jnp.argmax(want, axis=-1))
        assert tokens == list(greedy[len(prompt) - 1:len(seq) - 1])


def test_verify_window_equals_decode_steps():
    """verify_step_paged (write_pos, absorbed form, T > 1) gives the
    logits of as many 1-token steps."""
    cfg = _tiny()
    params = deepseek.init(cfg, jax.random.key(0))
    toks = _tokens(12)
    with jax.default_matmul_precision("highest"):
        want = _paged_logits(cfg, params, toks, bt=8, prompt_len=8)
        pool = deepseek.init_paged_cache(cfg, 16, 8)
        table = jnp.arange(1, 13, dtype=jnp.int32)[None]
        _, pool, _ = deepseek.forward_with_paged_cache(
            cfg, params, jnp.asarray(toks[:8])[None], pool, table,
            jnp.int32(0), window=16, write_block=table[0, 0])
        got, _ = deepseek.verify_step_paged(
            cfg, params, jnp.asarray(toks[8:12])[None], pool, table,
            jnp.asarray([8]), jnp.asarray([3]), window=16)
    assert float(jnp.abs(got[0] - want[8:12]).max()) < 1e-4


def _loop_step(cfg, params, toks, pool, table, pos):
    """The decode step as the tile loop computes it: the same 1-token
    forward with its write positions named, which takes the absorbed
    tile loop over every slot to the longest one's length."""
    return deepseek.forward_with_paged_cache(
        cfg, params, toks, pool, table, pos, window=16,
        write_pos=pos[:, None])


def test_decode_steps_give_the_tile_loops_tokens_and_pool():
    """Four slots, one of which does not decode, prefilled to 8, 16 and
    16 tokens in chunks, then ten greedy steps through the kernel and
    through the tile loop, each feeding its own tokens: the same tokens,
    logits within float32's rounding, the first layer's latent rows and
    roped keys equal to the bit (they depend on the tokens alone) and
    the later layers' within rounding. The step reports the blocks its
    kernel calls fetched: ``step_reads``' list once a layer. A slot's
    share of the 12 columns is 3 blocks: the first eight steps make
    the list over 3 columns, the last two, at positions 24 and 25, over
    all 12."""
    cfg = _tiny()
    params = deepseek.init(cfg, jax.random.key(0))
    bt, slots = 8, 4
    pool = deepseek.init_paged_cache(cfg, 64, bt)
    table = np.zeros((slots, 12), np.int32)
    for i, n in enumerate((1, 2, 0, 2)):      # prompt blocks a slot
        table[i] = np.arange(1 + 12 * i, 13 + 12 * i) if n else 0
    table = jnp.asarray(table)
    pos = jnp.asarray([8, 16, 0, 16], jnp.int32)
    for i in (0, 1, 3):
        for c in range(int(pos[i]) // bt):
            _, pool, _ = deepseek.forward_with_paged_cache(
                cfg, params,
                jnp.asarray(_tokens(bt, seed=10 * i + c))[None], pool,
                table[i:i + 1], jnp.int32(c * bt), window=16,
                write_block=table[i, c])
    step = jax.jit(lambda toks, pool, pos: deepseek.forward_with_paged_cache(
        cfg, params, toks, pool, table, pos, window=16))
    loop = jax.jit(lambda toks, pool, pos: _loop_step(
        cfg, params, toks, pool, table, pos))
    toks = jnp.asarray(_tokens(slots, seed=99))[:, None]
    got, want = (toks, pool), (toks, pool)
    live = np.asarray(table[:, 0] != 0)
    for k in range(10):
        g_logits, g_pool, report = step(got[0], got[1], pos + k)
        w_logits, w_pool, _ = loop(want[0], want[1], pos + k)
        g_logits, w_logits = np.asarray(g_logits), np.asarray(w_logits)
        scale = np.abs(w_logits[live]).max()
        assert np.abs(g_logits - w_logits)[live].max() < 1e-5 * scale
        reads = paged_attention.step_reads(table, table[:, 0] != 0,
                                           pos + k, pos + k + 1, bt)
        assert int(report[-1]) == int(reads.fetched()) * cfg.n_layers
        got = (jnp.argmax(g_logits, -1).astype(jnp.int32), g_pool)
        want = (jnp.argmax(w_logits, -1).astype(jnp.int32), w_pool)
        np.testing.assert_array_equal(np.asarray(got[0])[live],
                                      np.asarray(want[0])[live])
    # Block 0 is the scratch block, which the slot that does not decode
    # writes with whatever its row sampled.
    for name in ("c_kv", "k_r"):
        np.testing.assert_array_equal(got[1][name][0, 1:],
                                      want[1][name][0, 1:])
    np.testing.assert_allclose(got[1]["c_kv"][:, 1:],
                               want[1]["c_kv"][:, 1:], rtol=1e-5, atol=1e-5)


def test_only_the_decode_step_reads_through_the_kernel(monkeypatch):
    """Which attention each program traces, by the shapes alone: the
    1-token step calls ``stpu_paged_mla_attn`` once a scanned stack and
    never the tile loop; a prefill chunk and a verify window call the
    tile loop and never the kernel."""
    cfg = _tiny()
    params = jax.eval_shape(lambda: deepseek.init(cfg, jax.random.key(0)))
    pool = jax.eval_shape(lambda: deepseek.init_paged_cache(cfg, 32, 8))
    calls = {"kernel": 0, "loop": 0}

    def spy(name, fn):
        def wrapped(*args, **kwargs):
            calls[name] += 1
            return fn(*args, **kwargs)
        return wrapped

    monkeypatch.setattr(deepseek.paged_mla_attention, "attend",
                        spy("kernel", deepseek.paged_mla_attention.attend))
    monkeypatch.setattr(deepseek, "_attend", spy("loop", deepseek._attend))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    programs = {
        "step": (lambda p, c, toks, table, pos:
                 deepseek.forward_with_paged_cache(
                     cfg, p, toks, c, table, pos, window=16),
                 (i32(3, 1), i32(3, 8), i32(3))),
        "chunk": (lambda p, c, toks, table, pos, block:
                  deepseek.forward_with_paged_cache(
                      cfg, p, toks, c, table, pos, window=16,
                      write_block=block),
                  (i32(1, 8), i32(1, 8), i32(), i32())),
        "verify": (lambda p, c, toks, table, pos, spec:
                   deepseek.verify_step_paged(cfg, p, toks, c, table, pos,
                                              spec, window=16),
                   (i32(3, 4), i32(3, 8), i32(3), i32(3))),
    }
    for name, (program, args) in programs.items():
        calls.update(kernel=0, loop=0)
        jax.eval_shape(program, params, pool, *args)
        assert calls == ({"kernel": 2, "loop": 0} if name == "step"
                         else {"kernel": 0, "loop": 2}), name


# -------------------------------------------------------------- routing
def _route_by_hand(cfg, logits, bias):
    """The published routing for one token, as a plain loop."""
    e, g = cfg.n_routed_experts, cfg.n_group
    s = [1.0 / (1.0 + math.exp(-float(x))) for x in logits]
    sb = [a + float(b) for a, b in zip(s, bias)]
    size = e // g
    score = [sum(sorted(sb[i * size:(i + 1) * size])[-2:])
             for i in range(g)]
    kept = sorted(range(g), key=lambda i: -score[i])[:cfg.topk_group]
    allowed = [i for i in range(e) if i // size in kept]
    chosen = sorted(allowed, key=lambda i: -sb[i])[:cfg.top_k]
    total = sum(s[i] for i in chosen)
    return {i: cfg.routed_scaling_factor * s[i] / total for i in chosen}


def test_routing_matches_a_plain_loop():
    cfg = _tiny()
    rng = np.random.RandomState(0)
    logits = rng.randn(64, cfg.n_routed_experts).astype(np.float32)
    bias = (0.3 * rng.randn(cfg.n_routed_experts)).astype(np.float32)
    w, chosen = deepseek.route(cfg, jnp.asarray(logits),
                               jnp.asarray(bias))
    w, chosen = np.asarray(w), np.asarray(chosen)
    for t in range(64):
        want = _route_by_hand(cfg, logits[t], bias)
        assert set(np.flatnonzero(chosen[t])) == set(want)
        for i, g in want.items():
            assert w[t, i] == pytest.approx(g, rel=1e-5)
    # Renormalised, then scaled.
    np.testing.assert_allclose(w.sum(-1), cfg.routed_scaling_factor,
                               rtol=1e-5)


def test_bias_moves_the_choice_and_not_the_weight():
    cfg = _tiny()
    logits = jnp.asarray(np.linspace(-1.0, 1.0, cfg.n_routed_experts,
                                     dtype=np.float32))[None]
    zero = jnp.zeros((cfg.n_routed_experts,), jnp.float32)
    w0, chosen0 = deepseek.route(cfg, logits, zero)
    # Lift expert 8 (group 2, kept either way) over its neighbours.
    lifted = zero.at[8].set(1.0)
    w1, chosen1 = deepseek.route(cfg, logits, lifted)
    assert not bool(chosen0[0, 8]) and bool(chosen1[0, 8])
    s8 = float(jax.nn.sigmoid(logits[0, 8]))
    total = float(jnp.sum(jnp.where(chosen1[0],
                                    jax.nn.sigmoid(logits[0]), 0.0)))
    # Its weight is made from its UNBIASED score.
    assert float(w1[0, 8]) == pytest.approx(
        cfg.routed_scaling_factor * s8 / total, rel=1e-5)


def test_best_experts_in_a_group_left_out_are_lost():
    """4 groups of 4, 2 kept: the token's single best expert sits alone
    in a group whose second score is poor, so the group's sum of two
    loses and the expert with it."""
    cfg = _tiny()
    logits = np.full((cfg.n_routed_experts,), -3.0, np.float32)
    logits[0] = 4.0                      # group 0: one star, nothing else
    logits[4:6] = 2.5                    # group 1: two good ones
    logits[8:10] = 2.0                   # group 2: two good ones
    zero = jnp.zeros((cfg.n_routed_experts,), jnp.float32)
    _, chosen = deepseek.route(cfg, jnp.asarray(logits)[None], zero)
    chosen = np.flatnonzero(np.asarray(chosen[0]))
    assert 0 not in chosen
    assert {4, 5, 8, 9} <= set(chosen) and len(chosen) == cfg.top_k


def test_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """One sparse layer: the routed parts of the four ranks' shares
    (each the program's moe_block on its own 4 experts), with the
    shared expert and the residual counted once, make what the
    reference gives for the layer holding all 16 experts."""
    whole = _tiny(n_experts_held=16, ep_size=1)
    params = deepseek.init(whole, jax.random.key(3))
    layer = {k: v[0] for k, v in params["moe_layers"].items()}
    x = jax.random.normal(jax.random.key(4), (1, 12, whole.dim))
    with jax.default_matmul_precision("highest"):
        y, weights, _ = ref._route(
            x[0], layer["mlp_norm"], layer["router"],
            layer["router_bias"],
            (whole.n_group, whole.topk_group, whole.top_k,
             whole.routed_scaling_factor, 0, 16, whole.norm_eps))
        want = x[0]
        for e in range(16):
            want = ref._swiglu(want, y, weights[:, e],
                               layer["we_gate"][e], layer["we_up"][e],
                               layer["we_down"][e])
        ones = jnp.ones((12,))
        want = ref._swiglu(want, y, ones, layer["ws_gate"],
                           layer["ws_up"], layer["ws_down"])
        common = ref._swiglu(x[0], y, ones, layer["ws_gate"],
                             layer["ws_up"], layer["ws_down"])
        got = common
        hits = 0
        for rank in range(4):
            share = _tiny(ep_rank=rank)
            lp = dict(layer)
            for name in ("we_gate", "we_up", "we_down"):
                lp[name] = layer[name][4 * rank:4 * rank + 4]
            out, chosen = deepseek.moe_block(share, x, lp)
            got = got + (out[0] - common)
            hits += int(chosen.sum())
    assert hits == 12 * whole.top_k       # every choice lands somewhere
    assert float(jnp.abs(got - want).max()) < 1e-4


# ------------------------------------------- the expert loop (PR 34)
def _all_held(cfg, x, lp):
    """moe_block as it was until PR 34: every held expert computed for
    every token and weighted, zero where it was not chosen; one
    product over (expert, column), rounded once."""
    lo = cfg.ep_rank * cfg.n_experts_held
    hi = lo + cfg.n_experts_held
    y = deepseek.llama.rms_norm(x, lp["mlp_norm"], cfg.norm_eps)
    logits = jnp.einsum("btd,de->bte", y.astype(jnp.float32),
                        lp["router"],
                        precision=jax.lax.Precision.HIGHEST)
    w, chosen = deepseek.route(cfg, logits, lp["router_bias"])
    w = w[..., lo:hi].astype(y.dtype)
    gate = jax.nn.silu(jnp.einsum("btd,edm->btem", y, lp["we_gate"]))
    up = jnp.einsum("btd,edm->btem", y, lp["we_up"])
    routed = jnp.einsum("btem,emd->btd", gate * up * w[..., None],
                        lp["we_down"])
    shared = (jax.nn.silu(y @ lp["ws_gate"]) * (y @ lp["ws_up"])
              ) @ lp["ws_down"]
    return x + routed + shared, x + shared, chosen[..., lo:hi]


def _sparse_layer(cfg, key=3):
    stack = deepseek.init(cfg, jax.random.key(key))["moe_layers"]
    return {k: v[1] for k, v in stack.items()}


def _poisoned(lp, keep):
    """``lp`` (one sparse layer, or the stack) with NaN in the three
    matrices of every held expert that ``keep`` ((held,) or (layers,
    held) bool) leaves out: a product that reads one of them shows
    (NaN times a zero weight is NaN)."""
    keep = jnp.asarray(keep)
    out = dict(lp)
    for name in deepseek._EXPERTS:
        mask = keep.reshape(keep.shape + (1,) * (lp[name].ndim - keep.ndim))
        out[name] = jnp.where(mask, lp[name], jnp.nan)
    return out


# 64 routed experts in 16 ranks of 4, top-8 of 4 groups of 8: a row
# chooses a given held expert one time in eight, as the cell's 8 of 256
# does one time in 32.
_EP16 = dict(n_routed_experts=64, n_experts_held=4, ep_size=16,
             n_group=8, topk_group=4, top_k=8)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("rank", [0, 15])
@pytest.mark.parametrize("rows", ["all", "one", "none"])
@pytest.mark.parametrize("shape", [(12, 1), (1, 64)])
def test_expert_loop_equals_the_all_held_form_on_counted_rows(
        dtype, rank, rows, shape):
    """A decode step's twelve rows and a chunk's 64 with a padded tail
    (40 real), every row counting, one, and none: each counted row's
    result is the all-held-experts form's. In float32 to 1e-6 of the
    largest value: the same products, summed over the experts in
    another order. In bf16 to ONE bf16 step of the larger of the
    routed part and the result (2^-7 of it), and to the bit in 99
    values of 100 (on this CPU: in every value): both forms sum the
    experts' float32 products in float32 and round once, so the sums
    differ in their last float32 bits at most, which can move a value
    that sits on a rounding boundary by one step. The
    experts that no counted row chose are NaN: the loop never reads
    them. With no row counted no expert runs: the routed part is zero
    and the shared expert is still added."""
    cfg = _tiny(dtype=dtype, ep_rank=rank, **_EP16)
    lp = _sparse_layer(cfg)
    b, t = shape
    x = jax.random.normal(jax.random.key(5), (b, t, cfg.dim)
                          ).astype(dtype)
    with jax.default_matmul_precision("highest"):
        want, unrouted, chosen = _all_held(cfg, x, lp)
        real = jnp.arange(t)[None, :] < (40 if t > 1 else 1)
        if rows == "all":
            counts = jnp.broadcast_to(real, (b, t))   # not the padded tail
        elif rows == "one":                # the first that asks for any
            first = jnp.argmax((chosen.any(-1) & real).reshape(-1))
            counts = jnp.zeros((b * t,), bool).at[first].set(True
                                                             ).reshape(b, t)
        else:
            counts = jnp.zeros((b, t), bool)
        asked = np.asarray(deepseek.experts_asked(chosen, counts))
        got, got_chosen = jax.jit(
            lambda x, lp, counts: deepseek.moe_block(cfg, x, lp, counts)
        )(x, _poisoned(lp, asked), counts)
    np.testing.assert_array_equal(got_chosen, chosen)
    assert asked.any() == (rows != "none")
    keep = np.asarray(counts)
    got, want, unrouted = (np.asarray(a, np.float32)
                           for a in (got, want, unrouted))
    assert np.isfinite(got).all()
    if rows == "none":
        np.testing.assert_array_equal(got, unrouted)
        return
    got, want = got[keep], want[keep]
    if dtype == jnp.float32:
        assert np.abs(got - want).max() < 1e-6 * np.abs(want).max()
        return
    size = np.maximum(np.abs(want), np.abs(want - unrouted[keep]))
    assert (np.abs(got - want) <= 2.0 ** -7 * size).all()
    assert (got == want).mean() > 0.99


def test_a_row_that_does_not_count_never_makes_an_expert_computed():
    """Two rows of a decode step, each choosing held experts the other
    does not; only row 0 counts (row 1's table names the scratch
    block). The program computes exactly what row 0 chose, in every
    sparse layer, row 0's logits are those of the step in which both
    rows count, and the experts only row 1 asked for are NaN
    throughout: none was read."""
    cfg = _tiny()
    params = deepseek.init(cfg, jax.random.key(0))
    step = jax.jit(lambda params, toks, pool, table, pos:
                   deepseek.forward_with_paged_cache(
                       cfg, params, toks, pool, table, pos, window=16))
    pool = deepseek.init_paged_cache(cfg, 8, 8)
    both = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([3, 5], jnp.int32)
    for seed in range(40):
        toks = jnp.asarray(_tokens(2, seed=seed))[:, None]
        want, _, (chosen, computed, _) = step(params, toks, pool, both,
                                              pos)
        chosen = np.asarray(chosen)[:, 0]             # (row, layer, held)
        np.testing.assert_array_equal(computed, chosen.any(axis=0))
        if (chosen[1] & ~chosen[0]).any() and chosen[0].any():
            break
    else:
        raise AssertionError("no seed gave row 1 an expert of its own")
    only_row0 = both.at[1].set(0)
    poisoned = {**params, "moe_layers": _poisoned(params["moe_layers"],
                                                  chosen[0])}
    got, _, (got_chosen, computed, _) = step(poisoned, toks, pool,
                                             only_row0, pos)
    np.testing.assert_array_equal(np.asarray(got_chosen)[0, 0], chosen[0])
    np.testing.assert_array_equal(computed, chosen[0])
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)


# ----------------------------------------------------------------- YaRN
def test_yarn_frequencies_and_softmax_scale_closed_forms():
    cfg = deepseek.DeepseekV3Config()
    inv = deepseek.yarn_inv_freq(cfg)
    base = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    # 32 rotations over 4096 positions end at dimension 10.4 -> 10,
    # one rotation at 22.4 -> 23: kept below, divided by 40 above,
    # blended linearly between.
    np.testing.assert_allclose(inv[:11], base[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], base[23:] / 40, rtol=1e-6)
    i = 17
    ramp = (i - 10) / 13
    assert inv[i] == pytest.approx(
        base[i] * (1 - ramp) + base[i] / 40 * ramp, rel=1e-6)
    m = 0.1 * math.log(40) + 1
    assert m == pytest.approx(1.3689, abs=1e-4)
    assert deepseek.softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * m * m, rel=1e-9)
    # The reference's own derivation agrees.
    freq, scale = ref.yarn(cfg)
    np.testing.assert_allclose(freq, inv, rtol=1e-6)
    assert scale == pytest.approx(deepseek.softmax_scale(cfg), rel=1e-9)
    # No scaling group: plain RoPE and 1 / sqrt(head).
    plain = dataclasses.replace(cfg, rope_scaling=None)
    np.testing.assert_allclose(deepseek.yarn_inv_freq(plain), base,
                               rtol=1e-6)
    assert deepseek.softmax_scale(plain) == pytest.approx(192 ** -0.5)


def test_rope_yarn_scores_equal_the_paired_form():
    """The program rotates in half-split order, the reference the
    published pairs: their query-key products agree."""
    cfg = _tiny()
    q = jax.random.normal(jax.random.key(0), (1, 6, 2, 8))
    k = jax.random.normal(jax.random.key(1), (1, 6, 1, 8))
    pos = jnp.arange(6)[None]
    got = jnp.einsum("bthr,bsr->bhts", deepseek.rope_yarn(cfg, q, pos),
                     deepseek.rope_yarn(cfg, k, pos)[:, :, 0])
    freq, _ = ref.yarn(cfg)
    want = jnp.einsum("thr,sr->hts", ref.rotary(q[0], freq),
                      ref.rotary(k[0], freq)[:, 0])
    np.testing.assert_allclose(got[0], want, atol=1e-5)


# ------------------------------------------------- what the pool costs
def test_latent_pool_costs_1152_bytes_a_token_a_layer():
    """Published latent widths (512 + 64) in bf16, everything else
    tiny: the pool's arrays, cache_bytes_per_device(), the gauge and
    kv_pool.block_bytes_for all read blocks x 64 x layers x 576 x 2."""
    cfg = _tiny(dtype=jnp.bfloat16, kv_lora_rank=512, qk_rope_head_dim=64)
    params = deepseek.init(cfg, jax.random.key(0))
    engine = DecodeEngine(cfg, params, slots=2, max_seq=256,
                          kv_block_tokens=64, use_manifest=False)
    try:
        blocks = engine.kv_config()["pool_blocks"]
        want = blocks * 64 * cfg.n_layers * 576 * 2
        assert sum(engine.cache_bytes_per_device().values()) == want
        assert sum(v.nbytes for v in engine._cache.values()) == want
        assert kv_pool.block_bytes_for(cfg, 64) == want // blocks
        assert f"stpu_engine_kv_pool_block_bytes {want // blocks}" in \
            metrics.render()
        assert want // blocks // 64 == cfg.n_layers * 1152
    finally:
        engine.shutdown()


@pytest.mark.parametrize("family", ["llama", "mixtral", "gemma"])
def test_block_bytes_for_agrees_with_the_kv_formula(family):
    """The families that cache keys and values cost what
    kv_pool.block_bytes reckons for them, bf16 and int8."""
    from skypilot_tpu.models import gemma, llama, mixtral
    cfg = {"llama": llama.LlamaConfig.tiny(),
           "mixtral": mixtral.MixtralConfig.tiny(),
           "gemma": gemma.GemmaConfig.tiny()}[family]
    for quantized in (False, True):
        assert kv_pool.block_bytes_for(
            cfg, 16, quantized=quantized) == kv_pool.block_bytes(
                16, cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
                quantized=quantized)


# ------------------------------------------------------------- counters
def _counter(name):
    for line in metrics.render().splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return 0.0


def test_routing_counters_move_by_what_the_batch_chose():
    """Three slots decode one step: the counters grow by the held
    experts the LIVE slots' tokens chose (pairs, and distinct experts a
    layer), as moe_block says for the same tokens; the free slot's ride
    is not counted, by the host (``chosen[live]``) nor by the program
    (its table row names the scratch block):
    ``stpu_moe_experts_computed_total``, what the program's expert loop
    ran over, grows as ``stpu_moe_experts_hit_total`` does — in this
    step, and over the rest of a served run in which a third request
    prefills in four chunks beside the decoding two and slots retire
    at different steps. ``stpu_attn_blocks_read_total`` grows by the
    blocks the step's attention kernel fetched, the last of its
    report."""
    cfg = _tiny()
    params = deepseek.init(cfg, jax.random.key(0))
    engine = DecodeEngine(cfg, params, slots=3, max_seq=64,
                          prefill_chunk=8, use_manifest=False)
    try:
        for n in (5, 7):
            engine.submit(list(map(int, _tokens(n, seed=n))),
                          max_tokens=4 + n)
        engine._admit()
        while any(s.request is not None and
                  s.prefilled < len(s.request.prompt)
                  for s in engine._slots):
            engine._prefill_one()
        live = engine._live()
        assert len(live) == 2
        toks = engine._toks      # the chunks' first tokens, on the device
        pos = jnp.asarray([s.pos for s in engine._slots], jnp.int32)
        for i in live:
            engine._ensure_block(i, engine._slots[i].pos // 8)
        # What this step's forward chooses, on a copy of the pool.
        _, _, (chosen, computed, blocks) = deepseek.forward_with_paged_cache(
            cfg, params, toks[:, None],
            jax.tree.map(jnp.copy, engine._cache),
            engine._step_table(live), pos, window=engine._window)
        chosen = np.asarray(chosen)[live, 0]      # (live, layers, held)
        np.testing.assert_array_equal(computed, chosen.any(axis=0))
        # Two slots at positions 5 and 7: one entry of two blocks each,
        # in each of the three layers.
        assert int(blocks) == 2 * 2 * cfg.n_layers
        names = ("stpu_moe_tokens_routed_total",
                 "stpu_moe_experts_hit_total",
                 "stpu_moe_experts_computed_total")
        routed0, hit0, computed0 = map(_counter, names)
        blocks0 = _counter("stpu_attn_blocks_read_total")
        assert engine._decode_step() == 2
        # The step's tokens and routing are read one iteration later,
        # and the step dispatched then is not in the counters yet.
        assert _counter("stpu_moe_tokens_routed_total") == routed0
        assert engine._decode_step() == 2
        assert _counter("stpu_moe_tokens_routed_total") - routed0 == \
            chosen.sum()
        assert _counter("stpu_moe_experts_hit_total") - hit0 == \
            chosen.any(axis=0).sum()
        assert _counter("stpu_moe_experts_computed_total") - computed0 \
            == chosen.any(axis=0).sum()
        assert _counter("stpu_attn_blocks_read_total") - blocks0 == blocks
        assert chosen.sum() > 0
        engine.submit(list(map(int, _tokens(30, seed=30))), max_tokens=6)
        for _ in range(60):
            engine._admit()
            did = engine._prefill_one()
            if not (engine._decode_step() or did):
                break
        assert not engine._behind and not engine._fresh
        routed, hit, computed = map(_counter, names)
        assert computed - computed0 == hit - hit0 > chosen.any(0).sum()
    finally:
        engine.shutdown()


# ------------------------------------------------------------- refusals
def test_refusals_name_the_family():
    cfg = _tiny()
    params = deepseek.init(cfg, jax.random.key(0))
    with pytest.raises(NotImplementedError, match="deepseek.*int8 pool"):
        DecodeEngine(cfg, params, slots=2, max_seq=64,
                     kv_quant=True, use_manifest=False)
    with pytest.raises(NotImplementedError, match="deepseek.*int8 wei"):
        DecodeEngine(cfg, params, slots=2, max_seq=64,
                     weight_quant=True, use_manifest=False)
    mesh, rules = gang_replica.build_mesh(
        gang_replica.ReplicaTopology(hosts=1, ici_axes={"tp": 2}))
    with pytest.raises(NotImplementedError, match="deepseek.*tp > 1"):
        DecodeEngine(cfg, params, slots=2, max_seq=64,
                     mesh=mesh, rules=rules, use_manifest=False)
    with pytest.raises(NotImplementedError, match="deepseek.*tp > 1"):
        gang_replica.cache_shardings(cfg, mesh, rules)
    lora = jax.tree.map(lambda a: a, params)
    lora["dense_layers"]["wq_a_lora_a"] = jnp.zeros((1, cfg.dim, 2))
    with pytest.raises(NotImplementedError, match="deepseek.*LoRA"):
        deepseek.forward(cfg, lora, jnp.zeros((1, 4), jnp.int32))
    # No row cache to refuse: the family has the one cached forward.
    for gone in ("init_cache", "forward_with_cache", "decode",
                 "verify_step", "paged_cache_specs"):
        assert not hasattr(deepseek, gone), gone
    with pytest.raises(ValueError, match="deepseek"):
        dataclasses.replace(cfg, ep_size=3)


def test_serve_llm_presets():
    from skypilot_tpu.recipes import serve_llm
    tiny = serve_llm.model_config("deepseek-tiny")
    assert tiny == deepseek.DeepseekV3Config.tiny()
    cell = serve_llm.model_config("deepseek-v3-5l-ep16")
    assert (cell.n_layers, cell.n_dense_layers, cell.n_experts_held,
            cell.ep_size, cell.vocab_size) == (5, 1, 16, 16, 16160)
    assert (cell.dim, cell.n_heads, cell.kv_lora_rank, cell.q_lora_rank,
            cell.n_routed_experts, cell.top_k) == (7168, 128, 512, 1536,
                                                   256, 8)
