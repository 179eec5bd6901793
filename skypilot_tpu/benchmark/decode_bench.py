"""Shared serving-decode measurement core.

Used by tools/bench_moe_decode.py (hand runs) and bench.py's `serving`
leg (driver-tracked BENCH json) so the two can never drift apart —
VERDICT r4 weak #3 was exactly that drift: hand-run decode numbers that
never reached the round-over-round record. Reference bar: serving
throughput is the reference's headline README metric
(/root/reference/README.md:49).

The measurements:

  * ``measure_decode`` — fixed-batch incremental decode (prefill +
    KV-cached per-token steps; dense top-2 expert routing for MoE) in
    tokens/second, comparable with rounds r01-r05, now split into
    prefill latency and steady-state per-token decode latency. The KV
    cache is allocated by the caller and DONATED through the jit
    boundary so each step updates it in place (no second full-size
    cache in HBM).
  * ``measure_engine_paged`` — the continuous-batching decode engine
    (serve/decode_engine.py: one device-resident block pool +
    per-slot block tables, serve/kv_pool.py) under a mixed-length
    arrival mix (heterogeneous prompt lengths and token budgets),
    with the pool sized to HALF of slots x max_seq tokens: tok/s,
    peak pool utilization, and peak concurrent live slots — the
    capacity-per-byte story.
  * ``measure_engine_q8`` — the paged engine with int8 KV blocks and
    int8 weights (STPU_KV_QUANT / STPU_WEIGHT_QUANT): quantized tok/s
    plus the block-capacity ratio vs bf16 at the SAME HBM byte budget
    (the >= 1.8x floor bench_compare gates).
  * ``measure_engine_spec`` — self-speculative decoding (n-gram
    drafts + one batched multi-token verify pass per step) on the
    chat shared-prefix mix at a b8 slot count, with
    the same-mix non-speculative baseline and the draft acceptance
    rate reported beside the headline tok/s — and the two runs'
    streams bit-asserted identical.
  * ``measure_engine_prefix`` — the engine under a SHARED-PREFIX mix
    (one system prompt, unique tails — the dominant production LLM
    traffic shape) with the shared-prefix KV cache on: reports warm
    throughput, hit rate, prefill tokens saved, and the warm/cold
    TTFT split (both wall seconds and deterministic
    steps-to-first-token).
  * ``measure_engine_slo`` — the whole data plane (serve_llm replica
    behind an in-process LB) under the open-loop load generator
    (benchmark/loadgen.py): goodput under declared TTFT/TPOT SLOs,
    p99 TTFT, and achieved tok/s under Poisson load — the
    bench_compare-gated serving-SLO leg.
  * ``measure_engine_chaos`` — the SLO leg with TWO replicas and a
    hard replica kill mid-run: a kill-free baseline pass, then the
    same schedule with one replica's engine + server torn down at
    ``kill_at_frac`` of the run. In-flight streams on the dead
    replica heal through the LB's journal resume; the gated headline
    is ``chaos_goodput_ratio`` (chaos goodput / baseline goodput,
    the durable-streams "within 5% of kill-free" contract).

Models are scaled to fit one v5e chip (full 8x7B / 8B need a pod
slice).
"""
from __future__ import annotations

import dataclasses
import random
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp


def build(family: str, dim: int = 1024, layers: int = 8,
          experts: int = 8, tiny: bool = False):
    """(module, config) for a single-chip-sized model of the family.

    ``tiny=True`` returns the families' ``.tiny()`` test configs
    instead — CPU-friendly shapes for plumbing runs (`stpu tune
    --tiny`, CI smoke); the numbers they produce are NOT comparable
    with the single-chip bench trajectory."""
    if tiny:
        if family == "llama":
            from skypilot_tpu.models import llama as mdl
            return mdl, mdl.LlamaConfig.tiny(vocab_size=128)
        if family == "mixtral":
            from skypilot_tpu.models import mixtral as mdl
            return mdl, mdl.MixtralConfig.tiny()
        if family == "gemma":
            from skypilot_tpu.models import gemma as mdl
            return mdl, mdl.GemmaConfig.tiny(vocab_size=128)
        raise ValueError(f"unknown family {family!r}")
    if family == "llama":
        from skypilot_tpu.models import llama as mdl
        cfg = mdl.LlamaConfig(
            vocab_size=32768, dim=2048, n_heads=16, n_kv_heads=8,
            mlp_dim=8192, n_layers=16, max_seq_len=2048)
    elif family == "mixtral":
        from skypilot_tpu.models import mixtral as mdl
        cfg = dataclasses.replace(
            mdl.MixtralConfig.mixtral_8x7b(),
            vocab_size=32768, dim=dim, n_layers=layers,
            n_heads=16, n_kv_heads=8, mlp_dim=3584,
            n_experts=experts, max_seq_len=2048)
    elif family == "gemma":
        from skypilot_tpu.models import gemma as mdl
        cfg = mdl.GemmaConfig.single_chip_bench()
    else:
        raise ValueError(f"unknown family {family!r}")
    return mdl, cfg


def _model_info(family: str, cfg, params) -> Dict[str, Any]:
    return {"family": family, "dim": cfg.dim,
            "layers": cfg.n_layers,
            "experts": getattr(cfg, "n_experts", 0),
            "mlp_dim": cfg.mlp_dim,
            "params": sum(x.size for x in jax.tree.leaves(params))}


def measure_decode(family: str, batch: int = 8, prompt_len: int = 128,
                   tokens: int = 128, repeats: int = 3,
                   **shape_kw) -> Dict[str, Any]:
    """Best-of-N jitted end-to-end decode (recipes/serve_llm.py
    _decode contract): unjitted, every eager op pays a dispatch of
    its own and the measurement is of the host, not the chip.

    Besides the end-to-end number (comparable with r01-r05), the
    prefill and steady-state decode phases are timed separately: a
    single end-to-end figure hides whether a regression sits in the
    O(S) prefill or the per-token loop, and TTFT (prefill) vs
    tokens/sec (steady state) are different serving SLOs.
    """
    mdl, cfg = build(family, **shape_kw)
    params = mdl.init(cfg, jax.random.key(0))
    b, s = batch, prompt_len
    prompt = jax.random.randint(jax.random.key(1), (b, s), 0,
                                cfg.vocab_size)
    max_seq = s + tokens

    # KV caches are allocated OUTSIDE the jitted programs, donated, and
    # RETURNED (then dropped): XLA only aliases a donated input to an
    # output, so returning the final cache is what makes the
    # O(layers * batch * max_seq) buffer update in place instead of
    # double-buffering in HBM every call.
    decode_jit = jax.jit(
        lambda p, pr, tl, cache: mdl.decode(cfg, p, pr, tl, tokens,
                                            max_seq, cache=cache,
                                            return_cache=True),
        donate_argnums=(3,))
    prefill_jit = jax.jit(
        lambda p, pr, tl, cache: mdl.forward_with_cache(
            cfg, p, pr, cache, jnp.int32(0), valid_len=tl,
            logits_at=tl - 1),
        donate_argnums=(3,))
    step_jit = jax.jit(
        lambda p, tok, cache, pos: mdl.forward_with_cache(
            cfg, p, tok, cache, pos),
        donate_argnums=(2,))

    def run():
        cache = mdl.init_cache(cfg, b, max_seq)
        out, _ = decode_jit(params, prompt, jnp.int32(s), cache)
        return int(out[0, -1])  # value fetch forces completion

    run()                      # compile + warm
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        run()
        best = min(best, time.perf_counter() - t0)

    # Prefill alone (compile + warm, then best-of-N).
    def run_prefill():
        cache = mdl.init_cache(cfg, b, max_seq)
        logits, cache = prefill_jit(params, prompt, jnp.int32(s), cache)
        return float(logits[0, 0, 0]), cache

    _, cache = run_prefill()
    best_prefill = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        _, cache = run_prefill()
        best_prefill = min(best_prefill, time.perf_counter() - t0)

    # Steady-state per-token decode: timed jitted single steps against
    # the warm cache (the cache row frontier advances each step, like a
    # live serving loop).
    tok = jnp.zeros((b, 1), jnp.int32)
    logits, cache = step_jit(params, tok, cache, jnp.int32(s))  # warm
    jax.block_until_ready(logits)   # keep the warm step out of the timer
    n_steps = min(max(tokens // 4, 8), tokens - 1)
    t0 = time.perf_counter()
    for i in range(n_steps):
        logits, cache = step_jit(params, tok, cache,
                                 jnp.int32(s + 1 + i))
    float(logits[0, 0, 0])     # force the chain
    steady = (time.perf_counter() - t0) / n_steps

    toks = b * tokens
    return {
        "model": _model_info(family, cfg, params),
        "batch": b,
        "prompt_len": s,
        "decode_tokens": tokens,
        "decode_seconds": round(best, 3),
        "tokens_per_sec": round(toks / best, 1),
        "ms_per_token_per_seq": round(best / tokens * 1e3, 2),
        "prefill_ms": round(best_prefill * 1e3, 2),
        "decode_ms_per_token_steady": round(steady * 1e3, 3),
        "steady_tokens_per_sec": round(b / steady, 1),
    }


def measure_engine_paged(family: str, slots: int = 16,
                         n_requests: int = 48, max_prompt: int = 192,
                         max_tokens: int = 64,
                         pool_tokens: int = 0,
                         block_tokens: int = 0,
                         engine_kw: Optional[Dict[str, Any]] = None,
                         **shape_kw) -> Dict[str, Any]:
    """Paged-KV engine throughput under a MIXED-LENGTH arrival mix —
    the capacity story of the block pool measured as a bench leg.

    A deterministic (seeded) mix of prompt lengths in [8, max_prompt]
    and token budgets in [8, max_tokens] is submitted all at once.
    The pool is sized (``pool_tokens``, default = half of ``slots`` x
    max_seq tokens) so whole rows of the same HBM spend would number
    ``slots/2``; the engine runs ``slots`` block tables over it and
    admission packs by ACTUAL length, so the mixed mix sustains more
    live slots per byte of KV. Reports
    generated tok/s (``engine_paged_tok_s``), the pool high-water
    utilization (``kv_pool_utilization`` — peak blocks in use over
    usable blocks; higher = denser packing of the same HBM), the
    peak concurrent live slots, and the stepstats phase breakdown."""
    from skypilot_tpu.observability import stepstats
    from skypilot_tpu.serve.decode_engine import DecodeEngine

    mdl, cfg = build(family, **shape_kw)
    params = mdl.init(cfg, jax.random.key(0))
    max_seq = max_prompt + max_tokens
    chunk = block_tokens or 64          # tuner-pinnable block size
    max_seq += (-max_seq) % chunk       # keep chunk | max_seq
    budget = pool_tokens or (slots * max_seq) // 2
    kw = dict(prefill_chunk=chunk,
              kv_pool_blocks=budget // chunk + 1, use_manifest=False)
    kw.update(engine_kw or {})
    engine = DecodeEngine(cfg, params, slots=slots, max_seq=max_seq,
                          **kw)
    engine.start()
    engine.warmup()

    rng = random.Random(0)
    specs = [([rng.randint(1, cfg.vocab_size - 1)
               for _ in range(rng.randint(8, max_prompt))],
              rng.randint(8, max_tokens))
             for _ in range(n_requests)]
    was_armed = stepstats.ENABLED
    stepstats.arm(ring=8192, sync_every=16)
    stepstats.reset()
    try:
        t0 = time.perf_counter()
        reqs = [engine.submit(p, max_tokens=mt) for p, mt in specs]
        total = sum(len(r.result(timeout=1800.0)) for r in reqs)
        dt = time.perf_counter() - t0
        snap = stepstats.snapshot()
        pool = engine._pool
        utilization = pool.peak_in_use / max(pool.usable_blocks, 1)
        peak_slots = engine.peak_live_slots
        zero_copy = engine.prefix_cache.stats()["zero_copy_hits"]
    finally:
        if not was_armed:
            stepstats.disarm()
        engine.shutdown()
    return {
        "model": _model_info(family, cfg, params),
        "slots": slots,
        "requests": n_requests,
        "max_prompt": max_prompt,
        "max_tokens": max_tokens,
        "pool_blocks": pool.num_blocks,
        "block_tokens": pool.block_tokens,
        "generated_tokens": total,
        "wall_seconds": round(dt, 3),
        "engine_paged_tok_s": round(total / dt, 1),
        "kv_pool_utilization": round(utilization, 3),
        "peak_live_slots": peak_slots,
        "zero_copy_hits": zero_copy,
        "phase_breakdown": snap.get("phases", {}),
        "busy_fraction": snap.get("busy_fraction"),
    }


def measure_engine_q8(family: str, slots: int = 16,
                      n_requests: int = 48, max_prompt: int = 192,
                      max_tokens: int = 64, pool_tokens: int = 0,
                      block_tokens: int = 0,
                      engine_kw: Optional[Dict[str, Any]] = None,
                      **shape_kw) -> Dict[str, Any]:
    """int8-quantized serving: throughput through the quantized paged
    engine plus the CAPACITY ratio the quantization exists for.

    Capacity half: size a bf16 pool exactly like measure_engine_paged
    (same byte budget), then count how many int8+scale blocks the SAME
    byte budget holds — measured from the real device cache arrays'
    nbytes, cross-checked against kv_pool.block_bytes — and assert the
    >= 1.8x floor (the bench_compare-gated ``kv_pool_capacity_blocks``
    leg; the theoretical ratio is just under 2x, the scale tax is one
    f32 per layer/head per block).

    Throughput half: the SAME seeded mixed-length mix as
    measure_engine_paged runs through a kv_quant + weight_quant engine
    whose pool holds the capacity-expanded block count, reported as
    ``engine_q8_tok_s``. Output parity with bf16 is NOT asserted here
    (quantization changes numerics by design) — that gate lives in
    tests/test_quant.py (top-1 agreement + perplexity bound)."""
    from skypilot_tpu.observability import stepstats
    from skypilot_tpu.serve import kv_pool
    from skypilot_tpu.serve.decode_engine import DecodeEngine

    mdl, cfg = build(family, **shape_kw)
    params = mdl.init(cfg, jax.random.key(0))
    max_seq = max_prompt + max_tokens
    chunk = block_tokens or 64          # tuner-pinnable block size
    max_seq += (-max_seq) % chunk       # keep chunk | max_seq
    budget = pool_tokens or (slots * max_seq) // 2
    bf16_blocks = budget // chunk + 1

    # Per-block bytes from REAL device arrays (a 2-block probe pool),
    # cross-checked against the kv_pool sizing math the docs quote.
    probe_b = mdl.init_paged_cache(cfg, 2, chunk)
    probe_q = mdl.init_paged_cache(cfg, 2, chunk, quantized=True)
    bb_bf16 = sum(v.nbytes for v in probe_b.values()) // 2
    bb_q8 = sum(v.nbytes for v in probe_q.values()) // 2
    del probe_b, probe_q
    kv_bytes = jnp.dtype(cfg.dtype).itemsize
    assert bb_bf16 == kv_pool.block_bytes(
        chunk, cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
        kv_dtype_bytes=kv_bytes), "bf16 block-byte math drifted"
    assert bb_q8 == kv_pool.block_bytes(
        chunk, cfg.n_layers, cfg.n_kv_heads, cfg.head_dim,
        quantized=True), "int8 block-byte math drifted"

    byte_budget = bf16_blocks * bb_bf16
    q8_blocks = byte_budget // bb_q8
    # Gate on the per-block byte ratio — blocks-per-byte is the
    # capacity lever and is pool-size independent; the realized block
    # counts below inherit it modulo integer flooring at tiny pools.
    ratio = bb_bf16 / bb_q8
    if ratio < 1.8:
        raise RuntimeError(
            f"quantized pool fits only {ratio:.2f}x the bf16 blocks "
            f"({bb_q8} vs {bb_bf16} bytes/block) at the same HBM "
            f"budget — below the 1.8x capacity gate")

    kw = dict(prefill_chunk=chunk,
              kv_pool_blocks=q8_blocks,
              kv_quant=True, weight_quant=True, use_manifest=False)
    kw.update(engine_kw or {})
    engine = DecodeEngine(cfg, params, slots=slots, max_seq=max_seq,
                          **kw)
    engine.start()
    engine.warmup()

    rng = random.Random(0)
    specs = [([rng.randint(1, cfg.vocab_size - 1)
               for _ in range(rng.randint(8, max_prompt))],
              rng.randint(8, max_tokens))
             for _ in range(n_requests)]
    was_armed = stepstats.ENABLED
    stepstats.arm(ring=8192, sync_every=16)
    stepstats.reset()
    try:
        t0 = time.perf_counter()
        reqs = [engine.submit(p, max_tokens=mt) for p, mt in specs]
        total = sum(len(r.result(timeout=1800.0)) for r in reqs)
        dt = time.perf_counter() - t0
        snap = stepstats.snapshot()
        pool = engine._pool
        utilization = pool.peak_in_use / max(pool.usable_blocks, 1)
        peak_slots = engine.peak_live_slots
    finally:
        if not was_armed:
            stepstats.disarm()
        engine.shutdown()
    return {
        "model": _model_info(family, cfg, params),
        "slots": slots,
        "requests": n_requests,
        "max_prompt": max_prompt,
        "max_tokens": max_tokens,
        "block_tokens": chunk,
        "byte_budget": byte_budget,
        "block_bytes_bf16": bb_bf16,
        "block_bytes_q8": bb_q8,
        "kv_pool_capacity_blocks_bf16": bf16_blocks,
        "kv_pool_capacity_blocks": q8_blocks,
        "kv_capacity_ratio": round(ratio, 3),
        "generated_tokens": total,
        "wall_seconds": round(dt, 3),
        "engine_q8_tok_s": round(total / dt, 1),
        "kv_pool_utilization": round(utilization, 3),
        "peak_live_slots": peak_slots,
        "phase_breakdown": snap.get("phases", {}),
        "busy_fraction": snap.get("busy_fraction"),
    }


def measure_engine_spec(family: str, slots: int = 8,
                        n_requests: int = 32, shared_prefix: int = 128,
                        max_unique: int = 32, max_tokens: int = 64,
                        spec_k: int = 4, spec_ngram: int = 3,
                        **shape_kw) -> Dict[str, Any]:
    """Self-speculative decoding throughput on the chat
    (shared-prefix) mix — the per-request speed lever batching can't
    reach, measured at a b8 slot count.

    One shared system prompt with deterministic (seeded) unique tails,
    greedy — the production chat shape PR 3's prefix cache targets and
    the shape n-gram self-drafts are strongest on (templated prompts +
    the repetitive continuations small-vocab greedy decode settles
    into). The SAME seeded workload runs twice through the paged
    engine (the serving default): drafting off, then ``spec_k`` drafts
    per slot per step — output is bit-asserted identical, so the leg
    can never "win" by changing tokens. Reports the speculative tok/s
    (``engine_spec_tok_s``, the bench_compare-gated headline), the
    same-mix baseline (``engine_spec_baseline_tok_s``, honesty
    detail — the speedup ratio is the two divided), and the draft
    acceptance rate (``spec_accept_rate``) that explains it: emitted
    tokens per verify pass ~= 1 + accept_rate * k.
    """
    from skypilot_tpu.observability import stepstats
    from skypilot_tpu.serve.decode_engine import DecodeEngine

    mdl, cfg = build(family, **shape_kw)
    params = mdl.init(cfg, jax.random.key(0))
    chunk = 64
    max_seq = shared_prefix + max_unique + max_tokens
    max_seq += (-max_seq) % chunk       # keep chunk | max_seq
    rng = random.Random(0)
    shared = [rng.randint(1, cfg.vocab_size - 1)
              for _ in range(shared_prefix)]

    def tail():
        # Templated chat tail: a short per-request motif repeated with
        # noise — the few-shot / structured-format shape prompt-lookup
        # drafting exists for (outputs and prompts re-walk the same
        # token runs), rather than i.i.d.-random tokens no real chat
        # mix resembles.
        motif = [rng.randint(1, cfg.vocab_size - 1)
                 for _ in range(4)]
        out: list = []
        while len(out) < max_unique:
            out += motif + [rng.randint(1, cfg.vocab_size - 1)]
        return out[:rng.randint(8, max_unique)]

    specs = [(shared + tail(), rng.randint(16, max_tokens))
             for _ in range(n_requests)]

    def run(k):
        engine = DecodeEngine(cfg, params, slots=slots,
                              max_seq=max_seq, prefill_chunk=chunk,
                              spec_k=k,
                              spec_ngram=spec_ngram,
                              use_manifest=False)
        engine.start()
        engine.warmup()
        if k:
            # Compile the verify program OUTSIDE the timed window (a
            # guaranteed-draft prompt: motif repetition makes the
            # n-gram matcher fire on the first decode step), exactly
            # like warmup() keeps the prefill/step compiles out.
            engine.submit([7, 8, 9] * 6, max_tokens=6).result(
                timeout=1800.0)
        try:
            t0 = time.perf_counter()
            reqs = [engine.submit(p, max_tokens=mt)
                    for p, mt in specs]
            streams = [r.result(timeout=1800.0) for r in reqs]
            dt = time.perf_counter() - t0
            drafted = sum(r.spec_drafted for r in reqs)
            accepted = sum(r.spec_accepted for r in reqs)
        finally:
            engine.shutdown()
        return streams, sum(map(len, streams)), dt, drafted, accepted

    was_armed = stepstats.ENABLED
    stepstats.arm(ring=8192, sync_every=16)
    stepstats.reset()
    try:
        base_streams, base_total, base_dt, _, _ = run(0)
        stepstats.reset()
        streams, total, dt, drafted, accepted = run(spec_k)
        snap = stepstats.snapshot()
    finally:
        if not was_armed:
            stepstats.disarm()
    if streams != base_streams:
        raise AssertionError(
            "speculative streams diverged from the non-speculative "
            "baseline — the bit-identity contract is broken")
    return {
        "model": _model_info(family, cfg, params),
        "slots": slots,
        "requests": n_requests,
        "shared_prefix": shared_prefix,
        "spec_k": spec_k,
        "spec_ngram": spec_ngram,
        "generated_tokens": total,
        "wall_seconds": round(dt, 3),
        "engine_spec_tok_s": round(total / dt, 1),
        "engine_spec_baseline_tok_s": round(base_total / base_dt, 1),
        "spec_speedup": round(base_dt / dt, 3),
        "drafted_tokens": drafted,
        "accepted_tokens": accepted,
        "spec_accept_rate": round(accepted / max(drafted, 1), 3),
        "phase_breakdown": snap.get("phases", {}),
        "busy_fraction": snap.get("busy_fraction"),
    }


def measure_engine_tp(family: str, tp: int = 2, slots: int = 8,
                      n_requests: int = 24, max_prompt: int = 192,
                      max_tokens: int = 64,
                      **shape_kw) -> Dict[str, Any]:
    """Tensor-parallel engine throughput under the mixed-length mix.

    The sharded-replica serving path (serve/gang_replica.py): params
    sharded by param_specs, the KV cache by cache_specs, over a
    ``tp``-wide mesh, the replica's ICI domain. Needs ``tp`` visible
    devices; a run on virtual CPU devices exercises the path but its
    number is not the chip's (the tool's result names its device).
    The bit-parity tests own correctness.
    """
    import jax as jax_lib
    from skypilot_tpu.serve import gang_replica
    from skypilot_tpu.serve.decode_engine import DecodeEngine

    if len(jax_lib.devices()) < tp:
        raise RuntimeError(
            f"engine_tp needs {tp} devices, JAX sees "
            f"{len(jax_lib.devices())}")
    mdl, cfg = build(family, **shape_kw)
    params = mdl.init(cfg, jax.random.key(0))
    topology = gang_replica.ReplicaTopology(hosts=1,
                                            ici_axes={"tp": tp})
    mesh, rules = gang_replica.build_mesh(topology)
    params = gang_replica.shard_params(cfg, params, mesh, rules)
    engine = DecodeEngine(cfg, params, slots=slots,
                          max_seq=max_prompt + max_tokens,
                          prefill_chunk=64, mesh=mesh, rules=rules,
                          use_manifest=False)
    engine.start()
    engine.warmup()
    rng = random.Random(0)
    specs = [([rng.randint(1, cfg.vocab_size - 1)
               for _ in range(rng.randint(8, max_prompt))],
              rng.randint(8, max_tokens))
             for _ in range(n_requests)]
    try:
        t0 = time.perf_counter()
        reqs = [engine.submit(p, max_tokens=mt) for p, mt in specs]
        total = sum(len(r.result(timeout=1800.0)) for r in reqs)
        dt = time.perf_counter() - t0
    finally:
        engine.shutdown()
    return {
        "model": _model_info(family, cfg, params),
        "slots": slots,
        "requests": n_requests,
        "tp": tp,
        "topology": topology.label(),
        "generated_tokens": total,
        "wall_seconds": round(dt, 3),
        "engine_tp_tok_s": round(total / dt, 1),
    }


def measure_engine_prefix(family: str, slots: int = 8,
                          n_requests: int = 24,
                          shared_prefix: int = 256,
                          max_unique: int = 32, max_tokens: int = 48,
                          **shape_kw) -> Dict[str, Any]:
    """Engine throughput under shared-prefix traffic through the paged
    pool's zero-copy prefix cache.

    One ``shared_prefix``-token system prompt, a deterministic (seeded)
    unique tail per request. Phase 1 (cold): a single request prefills
    the whole prompt and publishes its blocks on free (a refcount
    adoption into the trie). Phase 2 (warm): ``n_requests`` concurrent
    requests alias the shared blocks into their tables instead of
    recomputing them. Reported TTFT is split cold/warm in BOTH wall
    seconds and steps-to-first-token (the chunk-prefill count —
    deterministic, where wall seconds carry the host's noise),
    and the hit rate / tokens saved come from the engine's own pool
    stats so the bench and the /metrics counters can never disagree.
    """
    from skypilot_tpu.serve.decode_engine import DecodeEngine

    mdl, cfg = build(family, **shape_kw)
    params = mdl.init(cfg, jax.random.key(0))
    chunk = 64
    max_seq = shared_prefix + max_unique + max_tokens
    max_seq += (-max_seq) % chunk       # keep chunk | max_seq
    engine = DecodeEngine(cfg, params, slots=slots, max_seq=max_seq,
                          prefill_chunk=chunk,
                          use_manifest=False)
    engine.start()
    engine.warmup()

    rng = random.Random(0)
    shared = [rng.randint(1, cfg.vocab_size - 1)
              for _ in range(shared_prefix)]
    def tail():
        return [rng.randint(1, cfg.vocab_size - 1)
                for _ in range(rng.randint(1, max_unique))]
    try:
        # Cold leg: full prefill, then the prompt chunks are published.
        cold = engine.submit(shared + tail(),
                             max_tokens=rng.randint(16, max_tokens))
        cold.result(timeout=1800.0)
        ttft_cold = cold.first_token_at - cold.submitted_at
        # Hit rate over the WARM phase only (the cold leg and the
        # warmup request are misses by construction).
        stats0 = engine.prefix_cache.stats()

        t0 = time.perf_counter()
        reqs = [engine.submit(shared + tail(),
                              max_tokens=rng.randint(16, max_tokens))
                for _ in range(n_requests)]
        total = sum(len(r.result(timeout=1800.0)) for r in reqs)
        dt = time.perf_counter() - t0
    finally:
        stats = engine.prefix_cache.stats()
        engine.shutdown()
    warm_ttfts = sorted(r.first_token_at - r.submitted_at
                        for r in reqs)
    hits = stats["hits"] - stats0["hits"]
    misses = stats["misses"] - stats0["misses"]
    return {
        "model": _model_info(family, cfg, params),
        "slots": slots,
        "requests": n_requests,
        "shared_prefix": shared_prefix,
        "generated_tokens": total,
        "wall_seconds": round(dt, 3),
        "engine_prefix_tok_s": round(total / dt, 1),
        "prefix_hit_rate": round(hits / max(hits + misses, 1), 3),
        "prefill_tokens_saved": stats["tokens_saved"],
        "ttft_cold_s": round(ttft_cold, 4),
        # Median: warm requests queue behind each other on the shared
        # slots, so the tail reflects queueing, not the cache.
        "ttft_warm_s": round(warm_ttfts[len(warm_ttfts) // 2], 4),
        "steps_to_first_token_cold": cold.prefill_chunks,
        "steps_to_first_token_warm": max(r.prefill_chunks
                                         for r in reqs),
    }


def measure_engine_tier(family: str, slots: int = 8,
                        n_requests: int = 12,
                        prompt_blocks: int = 2, max_tokens: int = 8,
                        host_cache_mb: float = 64.0,
                        engine_kw: Optional[Dict[str, Any]] = None,
                        **shape_kw) -> Dict[str, Any]:
    """Host-RAM KV tier: warm re-hit TTFT vs cold prefill under a
    prefix working set ~2x the HBM pool.

    ``n_requests`` distinct ``prompt_blocks``-block prompts publish
    into a pool sized to hold only about HALF that working set, so
    cold admissions evict and the evictions spill D2H into the host
    tier. After the cold phase the trie is force-drained to the host
    tier (paced against the spill queue) and every prompt is
    re-submitted: a warm hit now costs one H2D block restore per
    chunk instead of a chunk prefill. Reports the cold vs re-hit
    median TTFT in BOTH wall seconds and steps-to-first-token (the
    chunk-prefill count — deterministic, immune to dispatch
    variance), the tier hit rate over the warm phase, and the host
    pool's own spill/re-admit counters so the bench and /metrics can
    never disagree."""
    from skypilot_tpu.serve.decode_engine import DecodeEngine

    mdl, cfg = build(family, **shape_kw)
    params = mdl.init(cfg, jax.random.key(0))
    chunk = 64
    # A few tail tokens past the last full block so admission can
    # re-admit ALL prompt_blocks blocks (an exact-multiple prompt
    # keeps its final block for prefill).
    prompt_len = prompt_blocks * chunk + 7
    max_seq = prompt_len + max_tokens
    max_seq += (-max_seq) % chunk       # keep chunk | max_seq
    # Pool = half the published working set, plus headroom for the
    # live slots' own rows (cold requests run one at a time).
    working_blocks = n_requests * prompt_blocks
    pool_blocks = working_blocks // 2 + 2 * (max_seq // chunk) + 1
    kw = dict(prefill_chunk=chunk,
              kv_pool_blocks=pool_blocks,
              prefix_cache_mb=host_cache_mb, use_manifest=False)
    kw.update(engine_kw or {})
    engine = DecodeEngine(cfg, params, slots=slots, max_seq=max_seq,
                          **kw)
    engine.start()
    engine.warmup()

    rng = random.Random(0)
    prompts = [[rng.randint(1, cfg.vocab_size - 1)
                for _ in range(prompt_len)]
               for _ in range(n_requests)]

    def _quiesce(deadline_s: float = 30.0) -> None:
        t_end = time.perf_counter() + deadline_s
        while (engine.spill_in_flight() > 0
               and time.perf_counter() < t_end):
            time.sleep(0.005)

    try:
        # Cold leg: sequential so each TTFT is pure prefill cost,
        # not queueing. Evictions (and their spills) happen inline.
        t0 = time.perf_counter()
        cold_reqs = []
        total = 0
        for p in prompts:
            r = engine.submit(p, max_tokens=max_tokens)
            total += len(r.result(timeout=1800.0))
            cold_reqs.append(r)
        # Drain every published block to the host tier so the warm
        # leg measures the re-admission path, paced so the bounded
        # spill queue never overflows into drop-on-evict.
        while True:
            while engine.spill_in_flight() >= 16:
                time.sleep(0.001)
            if not engine.prefix_cache.evict_one():
                break
        _quiesce()

        warm_reqs = []
        for p in prompts:
            r = engine.submit(p, max_tokens=max_tokens)
            total += len(r.result(timeout=1800.0))
            warm_reqs.append(r)
        dt = time.perf_counter() - t0
    finally:
        tier = engine.host_tier_stats()
        engine.shutdown()

    cold_ttfts = sorted(r.first_token_at - r.submitted_at
                        for r in cold_reqs)
    warm_ttfts = sorted(r.first_token_at - r.submitted_at
                        for r in warm_reqs)
    hits = sum(1 for r in warm_reqs if r.cached_prompt_tokens > 0)
    return {
        "model": _model_info(family, cfg, params),
        "slots": slots,
        "requests": n_requests,
        "prompt_blocks": prompt_blocks,
        "pool_blocks": pool_blocks,
        "host_cache_mb": host_cache_mb,
        "generated_tokens": total,
        "wall_seconds": round(dt, 3),
        "engine_tier_tok_s": round(total / dt, 1),
        "tier_cold_ttft_s": round(
            cold_ttfts[len(cold_ttfts) // 2], 4),
        "tier_rehit_ttft_s": round(
            warm_ttfts[len(warm_ttfts) // 2], 4),
        "tier_hit_rate": round(hits / max(n_requests, 1), 3),
        "steps_to_first_token_cold": max(r.prefill_chunks
                                         for r in cold_reqs),
        "steps_to_first_token_rehit": max(r.prefill_chunks
                                          for r in warm_reqs),
        "host_tier": tier,
    }


def measure_engine_slo(family: str, *, slots: int = 8,
                       qps: float = 6.0, duration_s: float = 8.0,
                       seed: int = 0, slo_ttft_s: float = 3.0,
                       slo_tpot_s: float = 0.5,
                       max_tokens: int = 16,
                       **shape_kw) -> Dict[str, Any]:
    """SLO-graded serving leg: the family's engine behind a REAL
    serve_llm replica and an in-process LB, driven by the open-loop
    load generator (benchmark/loadgen.py) under the shared-prefix chat
    mix. Unlike measure_engine_paged (engine in isolation, submit-all
    -at-once), this measures what a USER sees through the whole data
    plane — HTTP parse, LB proxy hop, engine queueing under a Poisson
    arrival process — and grades it against declared TTFT/TPOT SLOs.
    The reported ``slo_goodput`` / ``p99_ttft_s`` / ``loadgen_tok_s``
    are the bench_compare-gated headline: an LB-policy, autoscaler, or
    engine regression that only shows under concurrent load lands
    here, where the isolated-engine legs stay green.
    """
    import json
    import tempfile
    import threading
    import urllib.request

    from skypilot_tpu.benchmark import loadgen
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve.load_balancing_policies import (
        PrefixAffinityPolicy)
    from skypilot_tpu.serve.replica_managers import _free_port

    mdl, cfg = build(family, **shape_kw)
    params = mdl.init(cfg, jax.random.key(0))
    port, lb_port = _free_port(), _free_port()
    httpd = serve_llm.serve(cfg, params, port, engine_slots=slots)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    replica_url = f"http://127.0.0.1:{port}"
    deadline = time.time() + 600
    while time.time() < deadline:          # warmup = first compile
        try:
            with urllib.request.urlopen(replica_url + "/health",
                                        timeout=2) as resp:
                if resp.status == 200:
                    break
        except Exception:  # noqa: stpu-except — warming; poll again
            pass
        time.sleep(0.2)
    else:
        raise RuntimeError("replica never became healthy")

    spec = loadgen.LoadSpec(
        mix="chat", arrival="poisson", qps=qps, duration_s=duration_s,
        seed=seed, max_tokens=max_tokens,
        vocab=min(cfg.vocab_size, 32000))
    # Warm the FULL serving path before the clock starts: beyond
    # engine.warmup()'s prefill/decode programs, the first
    # shared-prefix traffic compiles the prefix-cache gather (slot
    # free publishes chunks) and insert (hit restores them) splices —
    # a compile each. A cold trace would measure the
    # XLA compiler, not the serving stack: the first requests eat the
    # compiles and everything queued behind them times out at the LB.
    # Two sequential requests sharing the TRACE's own first prefix
    # force every program exactly once.
    warm_prefix = loadgen._prefixes(spec)[0]
    for i in range(2):
        body = json.dumps({"prompt": warm_prefix + [17 + i],
                           "max_tokens": 2}).encode()
        warm_req = urllib.request.Request(
            replica_url + "/generate", data=body,
            headers={"Content-Type": "application/json"},
            method="POST")
        with urllib.request.urlopen(warm_req, timeout=600) as resp:
            resp.read()

    policy = PrefixAffinityPolicy()
    policy.set_ready_replicas([replica_url])
    lb = lb_lib.run_load_balancer(lb_port, policy,
                                  lb_lib.RequestRecorder())
    # Tail requests queue behind slot contention under load; the LB's
    # default 120s first-byte timeout would convert a saturated-but-
    # alive engine into 502s mid-leg.
    lb.RequestHandlerClass.upstream_timeout = 300.0
    try:
        report = loadgen.run(
            f"http://127.0.0.1:{lb_port}", spec,
            slo_ttft_s=slo_ttft_s, slo_tpot_s=slo_tpot_s,
            scrape_interval=1.0,
            out_dir=tempfile.mkdtemp(
                prefix=f"stpu-loadgen-bench-{family}-"),
            request_timeout=300.0)
    finally:
        lb.shutdown()
        if httpd.engine is not None:
            httpd.engine.shutdown()
        httpd.shutdown()
    ttft = report["latency_s"]["ttft"] or {}
    return {
        "model": _model_info(family, cfg, params),
        "slots": slots,
        "offered_qps": report["qps"]["offered"],
        "achieved_qps": report["qps"]["achieved"],
        "requests": report["requests"]["scheduled"],
        "errors": report["requests"]["error"],
        "slo_ttft_s": slo_ttft_s,
        "slo_tpot_s": slo_tpot_s,
        "slo_goodput": report["goodput"]["fraction"],
        "p99_ttft_s": ttft.get("p99"),
        "p50_ttft_s": ttft.get("p50"),
        "loadgen_tok_s": report["tokens"]["tok_s"],
        "schedule_sha256": report["schedule_sha256"],
        "report_dir": report["out_dir"],
    }


def measure_engine_chaos(family: str, *, slots: int = 8,
                         qps: float = 6.0, duration_s: float = 8.0,
                         seed: int = 0, slo_ttft_s: float = 3.0,
                         slo_tpot_s: float = 0.5,
                         max_tokens: int = 16,
                         kill_at_frac: float = 0.5,
                         **shape_kw) -> Dict[str, Any]:
    """Durable-streams chaos leg: the SLO leg's data plane with TWO
    replicas, run twice on the same schedule — once kill-free
    (baseline), once with replica A's engine and HTTP server torn
    down ``kill_at_frac`` into the run (the in-process equivalent of
    a SIGKILL: in-flight streams drop without ``[DONE]``, new
    connects are refused). The LB's stream journal resumes the broken
    streams on replica B and its breaker ejects A for the
    pre-first-byte traffic, so goodput should barely move — the
    reported ``chaos_goodput_ratio`` (chaos / baseline goodput) is
    the "within 5% of kill-free" durable-streams contract, gated
    higher-is-better by bench_compare alongside the absolute
    ``chaos_slo_goodput``. ``resumed_streams`` > 0 is what separates
    "healed by resume" from "nothing was in flight when A died".
    """
    import json
    import tempfile
    import threading
    import urllib.request

    from skypilot_tpu.benchmark import loadgen
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve.load_balancing_policies import (
        PrefixAffinityPolicy)
    from skypilot_tpu.serve.replica_managers import _free_port

    mdl, cfg = build(family, **shape_kw)
    params = mdl.init(cfg, jax.random.key(0))
    lb_port = _free_port()
    servers = []
    urls = []
    for _ in range(2):
        port = _free_port()
        httpd = serve_llm.serve(cfg, params, port, engine_slots=slots)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        servers.append(httpd)
        urls.append(f"http://127.0.0.1:{port}")

    deadline = time.time() + 600
    pending = list(urls)
    while pending and time.time() < deadline:
        url = pending[0]
        try:
            with urllib.request.urlopen(url + "/health",
                                        timeout=2) as resp:
                if resp.status == 200:
                    pending.pop(0)
                    continue
        except Exception:  # noqa: stpu-except — warming; poll again
            pass
        time.sleep(0.2)
    if pending:
        raise RuntimeError("replica never became healthy")

    spec = loadgen.LoadSpec(
        mix="chat", arrival="poisson", qps=qps, duration_s=duration_s,
        seed=seed, max_tokens=max_tokens,
        vocab=min(cfg.vocab_size, 32000))
    # Warm BOTH replicas' full serving paths (same rationale as
    # measure_engine_slo): a resume landing on a cold peer would
    # measure the XLA compiler, not the splice.
    warm_prefix = loadgen._prefixes(spec)[0]
    for url in urls:
        for i in range(2):
            body = json.dumps({"prompt": warm_prefix + [17 + i],
                               "max_tokens": 2}).encode()
            warm_req = urllib.request.Request(
                url + "/generate", data=body,
                headers={"Content-Type": "application/json"},
                method="POST")
            with urllib.request.urlopen(warm_req, timeout=600) as resp:
                resp.read()

    policy = PrefixAffinityPolicy()
    policy.set_ready_replicas(list(urls))
    lb = lb_lib.run_load_balancer(lb_port, policy,
                                  lb_lib.RequestRecorder())
    lb.RequestHandlerClass.upstream_timeout = 300.0
    target = f"http://127.0.0.1:{lb_port}"
    kill_at = max(duration_s * kill_at_frac, 0.1)

    def _kill_replica_a() -> None:
        # The in-process stand-in for a provider SIGKILL: engine
        # shutdown drops every in-flight stream mid-token (no [DONE]),
        # server_close refuses new connects. No drain, no notice.
        victim = servers[0]
        if victim.engine is not None:
            victim.engine.shutdown()
        victim.shutdown()
        victim.server_close()

    killer = threading.Timer(kill_at, _kill_replica_a)
    killer.daemon = True
    try:
        baseline = loadgen.run(
            target, spec, slo_ttft_s=slo_ttft_s,
            slo_tpot_s=slo_tpot_s, scrape_interval=1.0,
            out_dir=tempfile.mkdtemp(
                prefix=f"stpu-chaos-base-{family}-"),
            request_timeout=300.0)
        killer.start()
        chaos = loadgen.run(
            target, spec, slo_ttft_s=slo_ttft_s,
            slo_tpot_s=slo_tpot_s, scrape_interval=1.0,
            out_dir=tempfile.mkdtemp(
                prefix=f"stpu-chaos-kill-{family}-"),
            request_timeout=300.0)
    finally:
        killer.cancel()
        lb.shutdown()
        for httpd in servers:
            try:
                if httpd.engine is not None:
                    httpd.engine.shutdown()
                httpd.shutdown()
            except Exception:  # noqa: stpu-except — A is already dead
                pass
    base_frac = baseline["goodput"]["fraction"]
    chaos_frac = chaos["goodput"]["fraction"]
    server = chaos.get("server", {})
    return {
        "model": _model_info(family, cfg, params),
        "slots": slots,
        "replicas": 2,
        "offered_qps": chaos["qps"]["offered"],
        "requests": chaos["requests"]["scheduled"],
        "kill_at_s": round(kill_at, 3),
        "slo_ttft_s": slo_ttft_s,
        "slo_tpot_s": slo_tpot_s,
        "baseline_slo_goodput": base_frac,
        "chaos_slo_goodput": chaos_frac,
        "chaos_goodput_ratio": round(
            chaos_frac / max(base_frac, 1e-9), 4),
        "chaos_errors": chaos["requests"]["error"],
        "resumed_streams": server.get("resumed_streams", 0.0),
        "lb_stream_resumes": server.get("lb_stream_resumes", {}),
        "resume_gap": server.get("resume_gap"),
        "schedule_sha256": chaos["schedule_sha256"],
        "baseline_report_dir": baseline["out_dir"],
        "chaos_report_dir": chaos["out_dir"],
    }


def measure_ckpt(family: str, repeats: int = 3,
                 **shape_kw) -> Dict[str, Any]:
    """Checkpoint save/restore latency for a family's full param set.

    The number that bounds two halves of the preemption story: how much
    step-path time a --ckpt-every save can cost (save_s, synchronous
    worst case — the async Checkpointer hides most of it), and how long
    a recovery relaunch stalls before its first step (restore_s).
    Measured through the real train/checkpoint.py path — atomic rename,
    checksummed manifest and all — into a throwaway directory; best of
    ``repeats`` to shed filesystem-cache noise, same policy as the
    decode legs.
    """
    import shutil
    import tempfile

    from skypilot_tpu.train import checkpoint as checkpoint_lib

    mdl, cfg = build(family, **shape_kw)
    params = mdl.init(cfg, jax.random.key(0))
    jax.block_until_ready(params)
    tree = {"params": params}
    ckpt_dir = tempfile.mkdtemp(prefix=f"stpu-ckpt-bench-{family}-")
    try:
        save_s = restore_s = float("inf")
        nbytes = 0
        for i in range(repeats):
            t0 = time.perf_counter()
            checkpoint_lib.save(ckpt_dir, i, tree, keep=1)
            save_s = min(save_s, time.perf_counter() - t0)
            t0 = time.perf_counter()
            restored = checkpoint_lib.restore_latest(ckpt_dir,
                                                     like=tree)
            restore_s = min(restore_s, time.perf_counter() - t0)
            assert restored is not None and restored.step == i
        import json as json_lib
        import pathlib as pathlib_lib
        manifest = sorted(
            pathlib_lib.Path(ckpt_dir).glob("ckpt-*.json"))[-1]
        nbytes = json_lib.loads(
            manifest.read_text())["payload_bytes"]
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    return {
        "ckpt_save_s": round(save_s, 4),
        "ckpt_restore_s": round(restore_s, 4),
        "ckpt_bytes": nbytes,
        "repeats": repeats,
        "model": _model_info(family, cfg, params),
    }
