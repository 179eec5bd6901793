"""Serve-side decode throughput on the real chip (llama / Mixtral MoE /
gemma) — CLI front-end over the shared measurement core
(skypilot_tpu/benchmark/decode_bench.py), which bench.py's `serving`
leg also uses so hand runs and the driver-tracked BENCH json can't
drift.

Usage: python tools/bench_moe_decode.py [--family mixtral|llama|gemma]
           [--batch 8] [--tokens 128]
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

# Runnable as `python tools/bench_moe_decode.py` from anywhere: the
# script dir (tools/) is what lands on sys.path, not the repo root.
sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--family", choices=("mixtral", "llama", "gemma"),
                   default="mixtral")
    p.add_argument("--mode", choices=("fixed", "paged", "q8",
                                      "spec", "prefix", "ckpt",
                                      "loadgen", "chaos", "tp",
                                      "tuned", "tier"),
                   default="fixed",
                   help="fixed: bucketed batch decode (r01-r05 "
                        "comparable); paged: the continuous-batching "
                        "decode engine (one device KV block pool + "
                        "block tables, sized to half of slots x "
                        "max_seq) under a mixed-length mix — "
                        "tok/s + pool utilization; q8: the "
                        "engine with int8 KV blocks + int8 weights — "
                        "quantized tok/s and the block-capacity "
                        "ratio vs bf16 at the same HBM budget; spec: "
                        "self-speculative decoding (n-gram drafts + "
                        "one batched verify pass) on the chat "
                        "shared-prefix mix, with the same-mix "
                        "non-speculative baseline and acceptance "
                        "rate — streams bit-asserted identical; "
                        "prefix: "
                        "engine under shared-prefix traffic with the "
                        "shared-prefix KV cache on (warm/cold TTFT "
                        "split + hit rate); ckpt: crash-consistent "
                        "checkpoint save/restore latency for the "
                        "family's full param set (train/checkpoint.py); "
                        "loadgen: the full serve_llm+LB data plane "
                        "under the open-loop load generator, graded "
                        "against TTFT/TPOT SLOs (goodput, p99 TTFT); "
                        "chaos: the loadgen leg over TWO replicas "
                        "with one hard-killed mid-run — goodput vs "
                        "the kill-free baseline (the LB stream-"
                        "resume durability contract); "
                        "tp: the tensor-parallel sharded engine "
                        "(serve/gang_replica.py) over a --tp-wide "
                        "mesh — needs that many visible devices; "
                        "tuned: the paged "
                        "engine leg at the `stpu tune` manifest's "
                        "constants next to the hand-pinned defaults "
                        "— the tuned >= default acceptance leg "
                        "(STPU_TUNE_MANIFEST selects the manifest; "
                        "with no entry a quick in-process "
                        "paged-only sweep supplies the constants); "
                        "tier: the host-RAM KV spill tier under a "
                        "prefix working set ~2x the HBM pool — "
                        "warm re-hit TTFT vs cold prefill TTFT, "
                        "tier hit rate, spill/re-admit counters")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--prompt-len", type=int, default=128)
    p.add_argument("--tokens", type=int, default=128)
    p.add_argument("--repeats", type=int, default=3,
                   help="best-of-N timing")
    p.add_argument("--slots", type=int, default=8,
                   help="engine mode: concurrent decode slots")
    p.add_argument("--requests", type=int, default=32,
                   help="engine modes: requests submitted")
    p.add_argument("--shared-prefix", type=int, default=256,
                   help="prefix mode: shared system-prompt tokens")
    p.add_argument("--spec-k", type=int, default=4,
                   help="spec mode: drafted tokens per slot per step")
    p.add_argument("--qps", type=float, default=6.0,
                   help="loadgen mode: offered Poisson arrival rate")
    p.add_argument("--duration", type=float, default=8.0,
                   help="loadgen mode: trace length in seconds")
    p.add_argument("--slo-ttft", type=float, default=3.0,
                   help="loadgen mode: TTFT SLO in seconds")
    p.add_argument("--slo-tpot", type=float, default=0.5,
                   help="loadgen mode: per-output-token SLO in seconds")
    p.add_argument("--tp", type=int, default=2,
                   help="tp mode: tensor-parallel degree (mesh width)")
    p.add_argument("--dim", type=int, default=1024)
    p.add_argument("--layers", type=int, default=8)
    p.add_argument("--experts", type=int, default=8)
    args = p.parse_args()

    shape_kw = {}
    if args.family == "mixtral":
        shape_kw = dict(dim=args.dim, layers=args.layers,
                        experts=args.experts)
    elif any(f in sys.argv for f in ("--dim", "--layers", "--experts")):
        p.error("--dim/--layers/--experts only apply to "
                "--family mixtral (llama/gemma shapes are fixed)")

    # The same persistent compilation cache as bench.py, whose serving
    # leg shells out here per family and mode.
    import jax
    from skypilot_tpu.utils import compile_cache
    compile_cache.enable()

    from skypilot_tpu.benchmark import decode_bench
    if args.mode == "paged":
        result = decode_bench.measure_engine_paged(
            args.family, slots=args.slots, n_requests=args.requests,
            **shape_kw)
    elif args.mode == "q8":
        result = decode_bench.measure_engine_q8(
            args.family, slots=args.slots, n_requests=args.requests,
            **shape_kw)
    elif args.mode == "spec":
        result = decode_bench.measure_engine_spec(
            args.family, slots=args.slots, n_requests=args.requests,
            spec_k=args.spec_k, **shape_kw)
    elif args.mode == "prefix":
        result = decode_bench.measure_engine_prefix(
            args.family, slots=args.slots,
            shared_prefix=args.shared_prefix, **shape_kw)
    elif args.mode == "ckpt":
        result = decode_bench.measure_ckpt(
            args.family, repeats=args.repeats, **shape_kw)
    elif args.mode == "loadgen":
        result = decode_bench.measure_engine_slo(
            args.family, slots=args.slots, qps=args.qps,
            duration_s=args.duration, slo_ttft_s=args.slo_ttft,
            slo_tpot_s=args.slo_tpot, **shape_kw)
    elif args.mode == "chaos":
        result = decode_bench.measure_engine_chaos(
            args.family, slots=args.slots, qps=args.qps,
            duration_s=args.duration, slo_ttft_s=args.slo_ttft,
            slo_tpot_s=args.slo_tpot, **shape_kw)
    elif args.mode == "tp":
        result = decode_bench.measure_engine_tp(
            args.family, tp=args.tp, slots=args.slots,
            n_requests=args.requests, **shape_kw)
    elif args.mode == "tier":
        result = decode_bench.measure_engine_tier(
            args.family, slots=args.slots, n_requests=args.requests,
            **shape_kw)
    elif args.mode == "tuned":
        from skypilot_tpu.tune import manifest as tune_manifest
        entry, tag = tune_manifest.entry_for(family=args.family,
                                             slots=args.slots)
        if entry is None:
            # No manifest for this config: a quick paged-only sweep
            # supplies (and parity-gates) the constants in-process —
            # the leg then still measures tuned vs default the same
            # way, just without a persisted provenance tag.
            from skypilot_tpu.tune import sweep as tune_sweep
            win = tune_sweep.sweep_one(
                args.family, "paged", quick=True, slots=args.slots,
                shape_kw=shape_kw, log=lambda m: print(m,
                                                       file=sys.stderr))
            entry, tag = (win or {}).get("knobs", {}), "adhoc"
        engine_kw = {k: v for k, v in
                     (("block", entry.get("block", 0)),
                      ("window_blocks",
                       entry.get("window_blocks", 0))) if v}
        tuned = decode_bench.measure_engine_paged(
            args.family, slots=args.slots, n_requests=args.requests,
            block_tokens=entry.get("chunk", 0), engine_kw=engine_kw,
            **shape_kw)
        default = decode_bench.measure_engine_paged(
            args.family, slots=args.slots, n_requests=args.requests,
            **shape_kw)
        result = dict(tuned)
        result["engine_tuned_tok_s"] = result.pop(
            "engine_paged_tok_s")
        result["engine_tuned_default_tok_s"] = \
            default["engine_paged_tok_s"]
        result["tuned_constants"] = dict(
            engine_kw, chunk=entry.get("chunk", 0))
        result["tune_manifest"] = tag
    else:
        result = decode_bench.measure_decode(
            args.family, batch=args.batch, prompt_len=args.prompt_len,
            tokens=args.tokens, repeats=args.repeats, **shape_kw)
    # Every result names what it ran on: a number from a CPU run must
    # not be read as the chip's.
    from skypilot_tpu.parallel import mesh as mesh_lib
    result["device"] = mesh_lib.device_info()
    print(json.dumps(result))


if __name__ == "__main__":
    main()
