"""Headline benchmark: Llama training MFU / tokens-per-sec on one chip.

Prints exactly ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

The north-star target (BASELINE.json) is >=40% MFU for llama finetuning
on TPU, so ``vs_baseline`` reports achieved-MFU / 40%. Three legs, all
against BASELINE.md's blueprint targets rather than only the
largest-fitting model (VERDICT r2 weak-item 2):

  * headline  — the LARGEST Llama config that fits the attached chip,
    seq 2048 (candidates big-to-small; one the compiler refuses with
    RESOURCE_EXHAUSTED is skipped, and every skip is recorded in the
    JSON detail so a downsized run is visible in the result);
  * long_context — seq 8192 through the streamed flash-attention
    kernel family (the capability built for exactly this);
  * eight_b_shape — Llama-3.1-8B's layer geometry (dim 4096, mlp
    14336, GQA 32/8) with as many layers as fit one chip, under remat +
    gradient accumulation (optax.MultiSteps) — the per-chip behavior of
    the 8B target whose full weights cannot fit a single 16 GB chip.

Cold-start latency is broken down (imports / init / first-step compile)
and the JAX persistent compilation cache is enabled
(skypilot_tpu/utils/compile_cache.py), so warm reruns skip XLA
compilation (target <30 s start-to-first-step warm).

This measures the chip and has no CPU mode: without a TPU, or on a
device the peak table does not know, it exits non-zero. It also exits
non-zero when any leg failed, after printing the JSON with the errors.
One process holds the chip at a time: this process never starts a JAX
backend; the legs that train in-process run in a child
(``--inprocess-legs``), and every other leg is a child of its own,
started after the last has exited.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_T_START = time.perf_counter()

import jax  # noqa: E402

_T_IMPORT = time.perf_counter()

from skypilot_tpu.utils import compile_cache  # noqa: E402

# Peak-FLOPs table and device matching live in
# observability/trainstats.py now (one registry shared with the live
# MFU gauge, so bench and telemetry can never disagree on a chip's
# peak).
def _peak_flops(device) -> float:
    from skypilot_tpu.observability import trainstats
    return trainstats.peak_flops_for_device(device)


def _tpu_candidates(llama):
    """Largest-first model configs for a 16 GB v5e chip. Llama-3.1-8B
    itself cannot fit one chip (16 GB of bf16 params alone); the honest
    single-chip headline is the largest config whose params + bf16 adam
    moments + remat activations fit. Measured: 24 layers compiles and
    runs; 26+ is rejected by the compiler's memory check."""
    base = dict(vocab_size=32768, dim=2048, n_heads=16, n_kv_heads=8,
                mlp_dim=8192, max_seq_len=4096)
    return [
        llama.LlamaConfig(n_layers=24, **base),   # 1.64 B
        llama.LlamaConfig(n_layers=20, **base),   # 1.39 B
        llama.LlamaConfig(n_layers=16, **base),   # 1.14 B
    ]


def _does_not_fit(msg: str) -> bool:
    # How the TPU compiler (and the allocator) refuse a program that
    # does not fit the chip's memory.
    return "RESOURCE_EXHAUSTED" in msg


def _run_candidate(cfg, batch, seq, steps, warmup, accum_steps=1,
                   chunked_ce=False, optimizer="adamw"):
    import optax

    from skypilot_tpu.models import llama
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.train import trainer

    mesh = mesh_lib.make_mesh({"dp": 1}, devices=[jax.devices()[0]])
    params = llama.init(cfg, jax.random.key(0))
    t_init = time.perf_counter()
    tx = trainer.make_optimizer(
        trainer.TrainConfig(warmup_steps=2, total_steps=1000,
                            optimizer=optimizer))
    if accum_steps > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=accum_steps)
    state = trainer.init_train_state(params, tx)
    state = jax.device_put(
        state, trainer.state_shardings(mesh, mesh_lib.DEFAULT_RULES,
                                       llama.param_specs(cfg), state))
    extra = {}
    if chunked_ce:
        # Fused chunked head+CE: full-sequence logits never materialize
        # (trainer.chunked_cross_entropy_loss). Wins at long context;
        # at the short-seq headline the classic loss is faster.
        extra = dict(
            trunk_fn=lambda p, t, constrain: llama.forward_trunk(
                cfg, p, t, constrain=constrain),
            head_fn=llama.head_weights)
    step = trainer.make_train_step(
        lambda p, t, constrain: llama.forward(cfg, p, t,
                                              constrain=constrain),
        tx, mesh, mesh_lib.DEFAULT_RULES,
        with_grad_norm=False, **extra)
    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0,
                                cfg.vocab_size)
    batch_dict = {"tokens": tokens}

    state, metrics = step(state, batch_dict)
    float(metrics["loss"])  # the value fetch waits for the step
    t_first = time.perf_counter()

    for _ in range(warmup - 1):
        state, metrics = step(state, batch_dict)
    float(metrics["loss"])

    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step(state, batch_dict)
    final_loss = float(metrics["loss"])  # forces the whole chain
    dt = time.perf_counter() - t0
    assert final_loss == final_loss, "loss is NaN"
    timings = {
        "import_seconds": round(_T_IMPORT - _T_START, 1),
        "init_seconds": round(t_init - _T_START, 1),
        "start_to_first_step_seconds": round(t_first - _T_START, 1),
    }
    return batch * seq * steps / dt, timings


def _try_candidates(candidates, batch, seq, steps, warmup, skipped,
                    accum_steps=1, chunked_ce=False, optimizer="adamw"):
    """Largest-first; a candidate that does not fit is skipped and
    recorded. Returns (cfg, tokens_per_sec, timings) or raises
    SystemExit."""
    for cfg in candidates:
        try:
            tps, timings = _run_candidate(cfg, batch, seq, steps,
                                          warmup, accum_steps,
                                          chunked_ce=chunked_ce,
                                          optimizer=optimizer)
            return cfg, tps, timings
        except Exception as e:  # noqa: BLE001 — anything but
            # does-not-fit is re-raised
            msg = str(e)
            if not _does_not_fit(msg):
                raise
            print(f"bench: {cfg.n_layers}L candidate did not fit: "
                  f"{msg[:300]}", file=sys.stderr)
            skipped.append({"n_layers": cfg.n_layers, "dim": cfg.dim,
                            "reason": msg[:200]})
            # Keep only the string: traceback frames would pin the
            # failed candidate's params in HBM.
            del e
    raise SystemExit(f"no candidate config fit; skipped: {skipped}")


def _long_context_leg(llama, peak: float) -> dict:
    """Long-context training through the streamed flash kernel family
    (BASELINE.md long-context target). Four seq points — 8k/16k/32k/64k
    — so the MFU-vs-seq CURVE is recorded, not claimed (VERDICT r4 next
    #4a; r4 reported only the 8192 point). The top-level fields stay the
    seq-8192 leg for round-over-round comparability; `curve` carries
    every point. Longer sequences shrink layers largest-first so the
    remat residuals still fit 16 GB."""
    base = dict(vocab_size=32768, dim=2048, n_heads=16, n_kv_heads=8,
                mlp_dim=8192,
                # Never re-run the quadratic kernel in bwd, and stream
                # the roped q/k/v through pinned host RAM instead of
                # recomputing their projections — measured r5: matches
                # save_flash_qkv where that fits (8k) and beats
                # save_flash by +1.5 MFU pts at 16k where qkv OOMs
                # (docs/performance.md offload experiment).
                remat_policy="save_flash_offload_qkv")
    per_seq = [
        # (seq, layer candidates largest-first, timed steps). Probed on
        # the chip: 16L fits ≤16k, 8L at 32k, 4L at 64k (12L/32k and
        # 6L/64k fit but clock lower MFU).
        (8192, (16,), 6),
        (16384, (16, 12), 3),
        (32768, (8, 6), 2),
        (65536, (4,), 2),
    ]
    batch = 1
    curve: list = []
    headline: dict = {}
    for seq, layer_opts, steps in per_seq:
        candidates = [
            llama.LlamaConfig(n_layers=n, max_seq_len=seq, **base)
            for n in layer_opts
        ]
        skipped: list = []
        try:
            cfg, tps, _ = _try_candidates(candidates, batch, seq, steps,
                                          2, skipped, chunked_ce=True)
        except SystemExit:
            curve.append({"seq_len": seq,
                          "error": f"did not fit: {skipped}"})
            continue
        entry = {
            "seq_len": seq,
            "n_layers": cfg.n_layers,
            "tokens_per_sec_per_chip": round(tps, 1),
            "mfu_pct": round(
                tps * cfg.flops_per_token() / peak * 100.0, 2),
            "mfu_incl_attention_pct": round(
                tps * cfg.flops_per_token(seq) / peak * 100.0, 2),
            "params": cfg.num_params(),
            "skipped": skipped,
        }
        curve.append(entry)
        if seq == 8192:
            headline = dict(entry)
    if not headline:
        headline = {"error": "seq-8192 leg did not fit"}
    headline["curve"] = curve
    return headline


def _eight_b_shape_leg(llama, peak: float) -> dict:
    """Llama-3.1-8B layer geometry per chip under remat + grad accum.
    The full 8B cannot fit one 16 GB chip (bf16 params alone are 16 GB);
    this measures the per-chip behavior of its exact layer shape — the
    number that, scaled by layers/chips, predicts the v5p-64 target."""
    candidates = [
        llama.LlamaConfig(vocab_size=32768, dim=4096, n_heads=32,
                          n_kv_heads=8, mlp_dim=14336, n_layers=n,
                          max_seq_len=4096)
        for n in (8, 6, 4, 2)
    ]
    seq, batch, steps, accum = 2048, 8, 8, 1
    skipped: list = []
    try:
        # Adafactor: factored second moment drops ~8 bytes/param of
        # optimizer state, which is what lets ≥6 layers of the 8B shape
        # (218M params/layer) fit a 16 GB chip (r3's 6L candidate OOM'd
        # under full Adam moments) — and batch 8 with no grad accum.
        cfg, tps, _ = _try_candidates(candidates, batch, seq, steps, 2,
                                      skipped, accum_steps=accum,
                                      optimizer="adafactor")
    except SystemExit:
        return {"error": f"no 8B-shape candidate fit: {skipped}"}
    mfu = tps * cfg.flops_per_token() / peak * 100.0
    return {
        "n_layers": cfg.n_layers,
        "optimizer": "adafactor",
        "grad_accum_steps": accum,
        "tokens_per_sec_per_chip": round(tps, 1),
        "mfu_pct": round(mfu, 2),
        "mfu_incl_attention_pct": round(
            tps * cfg.flops_per_token(seq) / peak * 100.0, 2),
        "params": cfg.num_params(),
        "skipped": skipped,
    }


def _serving_leg() -> dict:
    """Driver-tracked decode throughput (VERDICT r4 next #3): llama /
    MoE / gemma decode tok/s at batch 8/32/64, fixed config, through
    the same measurement core the hand-run tool uses — each leg in a
    FRESH subprocess so it is independent of earlier legs' device
    state and measured exactly the way users run the tool. Each
    fixed-batch point now also records the prefill/steady-state split
    (prefill_ms / decode_ms_per_token_steady), and a per-family
    ``engine_paged_tok_s`` leg measures the continuous-batching
    decode engine under a mixed-length arrival mix — the traffic the
    fixed-batch path cannot batch. The run-to-run spread of these
    legs has not been measured on today's code."""
    out: dict = {}
    tool = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "tools", "bench_moe_decode.py")

    def run_tool(extra_args, timeout=900, env=None):
        proc = subprocess.run(
            [sys.executable, tool] + extra_args,
            capture_output=True, text=True, timeout=timeout, env=env)
        if proc.returncode != 0:
            raise RuntimeError(
                proc.stderr.strip().splitlines()[-1]
                if proc.stderr.strip() else f"exit {proc.returncode}")
        return json.loads(proc.stdout.strip().splitlines()[-1])

    for family in ("llama", "mixtral", "gemma"):
        for batch in (8, 32, 64):
            key = f"{family}_decode_tok_s_b{batch}"
            try:
                r = run_tool(["--family", family, "--batch", str(batch),
                              "--repeats", "5"])
                out[key] = r["tokens_per_sec"]
                out[f"{family}_prefill_ms_b{batch}"] = r.get(
                    "prefill_ms")
                out[f"{family}_decode_ms_tok_b{batch}"] = r.get(
                    "decode_ms_per_token_steady")
                out.setdefault(f"{family}_model", r["model"])
            except Exception as e:  # noqa: BLE001 — a failed leg must
                # be visible in the json, not sink the whole bench run.
                out[key] = None
                out[f"{key}_error"] = str(e)[:200]
        # Engine serving leg: the engine on a block pool of HALF of
        # slots x max_seq tokens under a mixed-length mix — throughput
        # per byte of KV plus the pool's peak utilization.
        key = f"{family}_engine_paged_tok_s"
        try:
            # 16 slots over the bytes of 8 whole rows: twice the slot
            # count on the same bytes is the leg's point.
            r = run_tool(["--family", family, "--mode", "paged",
                          "--slots", "16", "--requests", "48"],
                         timeout=1200)
            out[key] = r["engine_paged_tok_s"]
            out[f"{family}_kv_pool_utilization"] = \
                r["kv_pool_utilization"]
            out[f"{family}_engine_paged_detail"] = {
                k: r.get(k) for k in ("slots", "requests",
                                      "pool_blocks", "block_tokens",
                                      "peak_live_slots",
                                      "zero_copy_hits",
                                      "generated_tokens",
                                      "wall_seconds",
                                      "phase_breakdown",
                                      "busy_fraction")}
        except Exception as e:  # noqa: BLE001
            out[key] = None
            out[f"{key}_error"] = str(e)[:200]
        # int8-quantized serving leg: the paged engine with int8 KV
        # blocks (per-block/head scales in the pool) + int8 weights —
        # the capacity lever. Two gated numbers: quantized tok/s and
        # the block count the SAME HBM byte budget holds vs bf16
        # (>= 1.8x, asserted inside the leg AND gated as a
        # bench_compare metric so the ratio can never silently erode).
        key = f"{family}_engine_q8_tok_s"
        try:
            r = run_tool(["--family", family, "--mode", "q8",
                          "--slots", "16", "--requests", "48"],
                         timeout=1200)
            out[key] = r["engine_q8_tok_s"]
            out[f"{family}_kv_pool_capacity_blocks"] = \
                r["kv_pool_capacity_blocks"]
            out[f"{family}_engine_q8_detail"] = {
                k: r.get(k) for k in ("slots", "requests",
                                      "block_tokens", "byte_budget",
                                      "block_bytes_bf16",
                                      "block_bytes_q8",
                                      "kv_pool_capacity_blocks_bf16",
                                      "kv_capacity_ratio",
                                      "kv_pool_utilization",
                                      "peak_live_slots",
                                      "generated_tokens",
                                      "wall_seconds",
                                      "phase_breakdown",
                                      "busy_fraction")}
        except Exception as e:  # noqa: BLE001
            out[key] = None
            out[f"{key}_error"] = str(e)[:200]
        # Speculative-decoding serving leg: n-gram self-drafts + one
        # batched multi-token verify pass per step, on the chat
        # (shared-prefix) mix at a b8 slot count — the
        # per-request speed lever batching can't reach. The leg
        # bit-asserts speculative streams == non-speculative before
        # reporting, runs the same-mix baseline for the honest
        # speedup ratio, and carries the acceptance rate that
        # explains the number (tokens per verify pass ~= 1 + rate*k).
        key = f"{family}_engine_spec_tok_s"
        try:
            r = run_tool(["--family", family, "--mode", "spec"],
                         timeout=1200)
            out[key] = r["engine_spec_tok_s"]
            out[f"{family}_spec_accept_rate"] = r["spec_accept_rate"]
            out[f"{family}_engine_spec_detail"] = {
                k: r.get(k) for k in ("slots", "requests",
                                      "shared_prefix", "spec_k",
                                      "spec_ngram",
                                      "engine_spec_baseline_tok_s",
                                      "spec_speedup",
                                      "drafted_tokens",
                                      "accepted_tokens",
                                      "generated_tokens",
                                      "wall_seconds",
                                      "phase_breakdown",
                                      "busy_fraction")}
        except Exception as e:  # noqa: BLE001
            out[key] = None
            out[f"{key}_error"] = str(e)[:200]
        # Shared-prefix serving leg: engine + prefix KV cache under a
        # shared-system-prompt mix — the hit rate and the warm/cold
        # TTFT split are the whole point of the cache, so they are
        # tracked round-over-round alongside the throughput.
        key = f"{family}_engine_prefix_tok_s"
        try:
            r = run_tool(["--family", family, "--mode", "prefix"],
                         timeout=1200)
            out[key] = r["engine_prefix_tok_s"]
            out[f"{family}_prefix_hit_rate"] = r["prefix_hit_rate"]
            out[f"{family}_prefix_ttft_cold_s"] = r["ttft_cold_s"]
            out[f"{family}_prefix_ttft_warm_s"] = r["ttft_warm_s"]
            out[f"{family}_engine_prefix_detail"] = {
                k: r[k] for k in ("slots", "requests", "shared_prefix",
                                  "prefill_tokens_saved",
                                  "steps_to_first_token_cold",
                                  "steps_to_first_token_warm",
                                  "generated_tokens", "wall_seconds")}
        except Exception as e:  # noqa: BLE001
            out[key] = None
            out[f"{key}_error"] = str(e)[:200]
        # Host-tier serving leg: the paged engine with the host-RAM
        # KV spill tier on, under a prefix working set ~2x the HBM
        # pool — evictions spill D2H, warm re-submissions re-admit
        # H2D. bench_compare gates the throughput higher-is-better
        # and the re-hit TTFT lower-is-better: a re-admission path
        # that silently degrades to full prefill shows up as a
        # re-hit TTFT rise, not just a tok/s dip.
        key = f"{family}_engine_tier_tok_s"
        try:
            r = run_tool(["--family", family, "--mode", "tier"],
                         timeout=1200)
            out[key] = r["engine_tier_tok_s"]
            out[f"{family}_tier_rehit_ttft_s"] = r["tier_rehit_ttft_s"]
            out[f"{family}_tier_cold_ttft_s"] = r["tier_cold_ttft_s"]
            out[f"{family}_tier_hit_rate"] = r["tier_hit_rate"]
            out[f"{family}_engine_tier_detail"] = {
                k: r.get(k) for k in ("slots", "requests",
                                      "prompt_blocks", "pool_blocks",
                                      "host_cache_mb",
                                      "steps_to_first_token_cold",
                                      "steps_to_first_token_rehit",
                                      "host_tier",
                                      "generated_tokens",
                                      "wall_seconds")}
        except Exception as e:  # noqa: BLE001
            out[key] = None
            out[f"{key}_error"] = str(e)[:200]
        # SLO-graded serving leg: the family's engine behind a real
        # serve_llm replica + in-process LB, driven by the open-loop
        # load generator (benchmark/loadgen.py) under the chat mix —
        # goodput under TTFT/TPOT SLOs, p99 TTFT, and achieved tok/s
        # under Poisson load. bench_compare gates goodput/tok_s as
        # higher-is-better and p99 TTFT as lower-is-better, so LB-
        # policy/autoscaler/engine regressions that only show under
        # concurrent load fail the pipeline like MFU regressions do.
        key = f"{family}_slo_goodput"
        try:
            r = run_tool(["--family", family, "--mode", "loadgen"],
                         timeout=1200)
            out[key] = r["slo_goodput"]
            out[f"{family}_p99_ttft_s"] = r["p99_ttft_s"]
            out[f"{family}_loadgen_tok_s"] = r["loadgen_tok_s"]
            out[f"{family}_loadgen_detail"] = {
                k: r[k] for k in ("offered_qps", "achieved_qps",
                                  "requests", "errors", "slo_ttft_s",
                                  "slo_tpot_s", "p50_ttft_s",
                                  "schedule_sha256")}
        except Exception as e:  # noqa: BLE001
            out[key] = None
            out[f"{key}_error"] = str(e)[:200]
        # Durable-streams chaos leg: the loadgen data plane over TWO
        # replicas with one hard-killed mid-run. The LB's stream
        # journal resumes the broken streams on the survivor, so the
        # gated chaos_goodput_ratio (chaos / kill-free baseline, same
        # schedule) holding near 1.0 IS the durability contract —
        # bench_compare's 5% tolerance on the ratio is the "within 5%
        # of kill-free" acceptance bound, and resumed_streams in the
        # detail proves the healing actually exercised.
        key = f"{family}_chaos_goodput_ratio"
        try:
            r = run_tool(["--family", family, "--mode", "chaos"],
                         timeout=1800)
            out[key] = r["chaos_goodput_ratio"]
            out[f"{family}_chaos_slo_goodput"] = r["chaos_slo_goodput"]
            out[f"{family}_chaos_detail"] = {
                k: r.get(k) for k in ("baseline_slo_goodput",
                                      "resumed_streams",
                                      "lb_stream_resumes",
                                      "resume_gap", "chaos_errors",
                                      "kill_at_s", "offered_qps",
                                      "requests", "schedule_sha256")}
        except Exception as e:  # noqa: BLE001
            out[key] = None
            out[f"{key}_error"] = str(e)[:200]
        # Tuned-constants serving leg (`stpu tune`): the paged engine
        # leg re-run at the tuning manifest's constants, with the
        # default-constants number beside it. bench_compare gates the
        # tuned tok/s higher-is-better like the other engine legs;
        # tuned >= default holds by construction (the tuner measures
        # both through this same leg and only persists winners), so a
        # flip here means the manifest went stale for this device.
        # The manifest payload-sha tag lands in the detail so
        # bench_compare --manifest can assert WHICH manifest produced
        # a round.
        key = f"{family}_engine_tuned_tok_s"
        try:
            r = run_tool(["--family", family, "--mode", "tuned"],
                         timeout=1800)
            out[key] = r["engine_tuned_tok_s"]
            out[f"{family}_engine_tuned_detail"] = {
                k: r.get(k) for k in ("slots", "requests",
                                      "engine_tuned_default_tok_s",
                                      "tuned_constants",
                                      "tune_manifest",
                                      "generated_tokens",
                                      "wall_seconds",
                                      "dispatch_ms_mean",
                                      "device_ms_mean")}
        except Exception as e:  # noqa: BLE001
            out[key] = None
            out[f"{key}_error"] = str(e)[:200]
        # Checkpoint save/restore latency for the family's full param
        # set (train/checkpoint.py): bounds the step-path cost of
        # --ckpt-every and the relaunch stall of a preemption recovery.
        # LOWER is better — bench_compare gates these via its
        # lower-is-better metric set.
        key = f"{family}_ckpt_save_s"
        try:
            r = run_tool(["--family", family, "--mode", "ckpt"],
                         timeout=900)
            out[key] = r["ckpt_save_s"]
            out[f"{family}_ckpt_restore_s"] = r["ckpt_restore_s"]
            out[f"{family}_ckpt_bytes"] = r["ckpt_bytes"]
        except Exception as e:  # noqa: BLE001
            out[key] = None
            out[f"{key}_error"] = str(e)[:200]
    return out


def _train_leg() -> dict:
    """Training-goodput legs: each family's FULL recipe loop in a fresh
    subprocess with STPU_TRAINSTATS=1 armed — the MFU/goodput numbers
    come from the recipe's own trainstats snapshot, i.e. exactly what
    `stpu jobs top` shows for a managed run. The point is tracking the
    instrumented loop (delayed loss fetch, data-wait/ckpt accounting)
    round-over-round, so a regression in recipe-loop goodput or in the
    telemetry itself fails the pipeline like an MFU regression does.
    Small configs by design: the headline leg owns peak per-chip MFU;
    this leg owns the recipe path."""
    legs = {
        "llama": ("skypilot_tpu.recipes.llama_lora",
                  ["--model", "tiny", "--steps", "30",
                   "--batch-size", "8", "--seq-len", "512"]),
        "gemma": ("skypilot_tpu.recipes.gemma_lora",
                  ["--model", "tiny", "--steps", "30",
                   "--batch-size", "8", "--seq-len", "512"]),
        "mixtral": ("skypilot_tpu.recipes.mixtral_ep",
                    ["--model", "tiny", "--steps", "30",
                     "--batch-size", "8", "--seq-len", "256"]),
    }
    out: dict = {}
    for family, (mod, extra) in legs.items():
        env = dict(os.environ)
        env["STPU_TRAINSTATS"] = "1"
        # Hermetic: no checkpoint resume, no shared trainstats dir.
        env.pop("STPU_JOB_CKPT_DIR", None)
        env.pop("STPU_TRAINSTATS_DIR", None)
        try:
            proc = subprocess.run(
                [sys.executable, "-m", mod] + extra,
                capture_output=True, text=True, timeout=900, env=env)
            if proc.returncode != 0:
                raise RuntimeError(
                    proc.stderr.strip().splitlines()[-1]
                    if proc.stderr.strip()
                    else f"exit {proc.returncode}")
            r = json.loads(proc.stdout.strip().splitlines()[-1])
            out[f"{family}_train_mfu"] = r.get("train_mfu")
            out[f"{family}_train_detail"] = {
                k: r.get(k) for k in ("train_goodput",
                                      "train_step_seconds",
                                      "train_tokens_per_sec",
                                      "tokens_per_second",
                                      "steps", "final_loss")}
        except Exception as e:  # noqa: BLE001 — a failed leg must be
            # visible in the json, not sink the whole bench run.
            out[f"{family}_train_mfu"] = None
            out[f"{family}_train_mfu_error"] = str(e)[:200]
    return out


def _inprocess_legs() -> dict:
    """The legs that train in this process: headline, long context and
    the 8B layer shape. Run as the child ``bench.py --inprocess-legs``:
    from its first ``jax.devices()`` call until it exits, this process
    holds the chip."""
    cache_dir = compile_cache.enable()
    warm_cache = os.path.isdir(cache_dir) and bool(os.listdir(cache_dir))
    from skypilot_tpu.models import llama

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"bench: no TPU (JAX reports platform {dev.platform!r}); "
            f"this benchmark measures the chip and has no CPU mode")
    peak = _peak_flops(dev)
    if peak <= 0:
        raise SystemExit(
            f"bench: device_kind {dev.device_kind!r} is not in the "
            f"peak table (observability/trainstats.py PEAK_FLOPS)")

    batch, seq, steps, warmup = 8, 2048, 10, 3
    skipped: list = []
    cfg, tok_per_sec, timings = _try_candidates(
        _tpu_candidates(llama), batch, seq, steps, warmup, skipped)
    # Headline is the conservative 6N convention (no attention term,
    # comparable across rounds); the attention-inclusive figure is
    # in detail.
    mfu = tok_per_sec * cfg.flops_per_token() / peak * 100.0
    mfu_attn = tok_per_sec * cfg.flops_per_token(seq) / peak * 100.0
    return {
        "metric": "llama_train_mfu_1chip",
        "value": round(mfu, 2),
        "unit": "%MFU",
        "vs_baseline": round(mfu / 40.0, 3),
        "detail": {
            "tokens_per_sec_per_chip": round(tok_per_sec, 1),
            "platform": dev.platform,
            "device": dev.device_kind,
            "device_count": jax.device_count(),
            "params": cfg.num_params(),
            "seq_len": seq,
            "mfu_incl_attention": round(mfu_attn, 2),
            "headline_skipped_candidates": skipped,
            "compilation_cache_warm": warm_cache,
            **timings,
            "long_context": _long_context_leg(llama, peak),
            "eight_b_shape": _eight_b_shape_leg(llama, peak),
        },
    }


def _failed_legs(node, path="detail") -> list:
    """Paths of every ``error`` / ``*_error`` entry under ``node``."""
    found = []
    if isinstance(node, dict):
        for key, value in node.items():
            here = f"{path}.{key}"
            if key == "error" or key.endswith("_error"):
                found.append(here)
            else:
                found.extend(_failed_legs(value, here))
    elif isinstance(node, list):
        for i, value in enumerate(node):
            found.extend(_failed_legs(value, f"{path}[{i}]"))
    return found


def main() -> int:
    if sys.argv[1:] == ["--inprocess-legs"]:
        print(json.dumps(_inprocess_legs()))
        return 0
    # First the child that trains in-process: where there is no TPU it
    # says so and nothing else is started.
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--inprocess-legs"],
        stdout=subprocess.PIPE, text=True)
    if proc.returncode != 0:
        print(f"bench: the in-process legs failed (exit "
              f"{proc.returncode}); no result", file=sys.stderr)
        return 1
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    result["detail"]["serving"] = _serving_leg()
    result["detail"]["train"] = _train_leg()
    print(json.dumps(result))
    failed = _failed_legs(result["detail"])
    if failed:
        print(f"bench: {len(failed)} leg(s) failed: "
              f"{', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
