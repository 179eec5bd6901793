"""Diff two bench JSON files; fail on metric regressions.

    python tools/bench_compare.py BENCH_old.json BENCH_new.json \
        [--threshold 5] [--metrics glob,glob,...]

Guards the bench trajectory in CI the way tier-1 tests guard
correctness: exit 1 when any NAMED serving/training metric regresses
by more than ``--threshold`` percent (default 5), so a PR that tanks
decode throughput or MFU fails the pipeline instead of quietly
shipping a slower round. Metrics are addressed by dotted path into the
bench JSON (bench.py's single-line document) and selected by glob
patterns. Metrics come in two polarities: the default set is
higher-is-better (tok/s, MFU, hit rate); DEFAULT_METRICS_LOWER /
``--metrics-lower`` name lower-is-better latencies (checkpoint
save/restore seconds), where a regression is the new value RISING by
more than the threshold. A metric named by an EXACT (non-glob) pattern
that disappears from the new file also fails — a silently dropped
headline is a regression in disguise. Null values (failed legs record
null + an _error key) are skipped with a warning line.
"""
from __future__ import annotations

import argparse
import fnmatch
import json
import sys
from typing import Dict, List, Sequence, Tuple

# Higher-is-better metrics tracked round-over-round. Keep in sync with
# bench.py's output shape (tests/test_bench_compare.py pins a fixture).
DEFAULT_METRICS = (
    "value",                                        # headline MFU
    "detail.tokens_per_sec_per_chip",
    "detail.long_context.tokens_per_sec_per_chip",
    "detail.long_context.mfu_pct",
    "detail.eight_b_shape.tokens_per_sec_per_chip",
    "detail.serving.*_decode_tok_s_b*",
    "detail.serving.*_engine_paged_tok_s",
    "detail.serving.*_engine_q8_tok_s",
    "detail.serving.*_engine_spec_tok_s",
    "detail.serving.*_kv_pool_utilization",
    # Quantized pool capacity: blocks the q8 pool fits at the SAME HBM
    # byte budget as bf16. The leg itself asserts >= 1.8x vs bf16;
    # gating the block count here keeps the ratio from eroding
    # round-over-round (e.g. scale-array bloat shrinking the pool).
    "detail.serving.*_kv_pool_capacity_blocks",
    # Tuned-constants engine leg (`stpu tune` manifest applied): the
    # autotuner only persists parity-gated winners measured >= the
    # default through this same leg, so a drop here means the manifest
    # went stale for the device this round ran on.
    "detail.serving.*_engine_tuned_tok_s",
    "detail.serving.*_engine_prefix_tok_s",
    "detail.serving.*_prefix_hit_rate",
    # Host-RAM KV spill tier: decode throughput with spill/re-admit
    # traffic in flight, and the warm-phase tier hit rate. The re-hit
    # TTFT companion lives in DEFAULT_METRICS_LOWER.
    "detail.serving.*_engine_tier_tok_s",
    "detail.serving.*_tier_hit_rate",
    "detail.serving.*_slo_goodput",
    "detail.serving.*_loadgen_tok_s",
    # Durable-streams chaos leg: goodput with a replica hard-killed
    # mid-run over goodput kill-free on the same schedule. The LB's
    # journal resume holds this near 1.0; the compare threshold on
    # the ratio IS the "within 5% of kill-free" durability bound
    # (chaos_slo_goodput rides the *_slo_goodput glob above).
    "detail.serving.*_chaos_goodput_ratio",
    # Training-goodput legs (bench.py _train_leg): live MFU from the
    # armed trainstats recipe runs — a regression in recipe-loop
    # goodput or the telemetry itself fails CI like a serving one.
    "detail.train.*_train_mfu",
)

# Lower-is-better metrics (latencies): a regression is the value going
# UP by more than the threshold.
DEFAULT_METRICS_LOWER = (
    "detail.serving.*_ckpt_save_s",
    "detail.serving.*_ckpt_restore_s",
    "detail.serving.*_p99_ttft_s",
    # Host-tier warm re-hit TTFT: a re-admission path that silently
    # degrades to full prefill shows up here as a latency rise even
    # when raw tok/s survives.
    "detail.serving.*_tier_rehit_ttft_s",
)


def unwrap(doc: dict) -> dict:
    """Accept both bench.py's bare document and the driver-tracked
    BENCH_r*.json wrapper ({"n": ..., "rc": ..., "parsed": {...}})."""
    parsed = doc.get("parsed")
    if isinstance(parsed, dict) and "value" in parsed:
        return parsed
    return doc


def flatten(doc, prefix: str = "") -> Dict[str, float]:
    """Numeric leaves of a nested JSON document by dotted path."""
    out: Dict[str, float] = {}
    if isinstance(doc, dict):
        for key, val in doc.items():
            path = f"{prefix}.{key}" if prefix else str(key)
            out.update(flatten(val, path))
    elif isinstance(doc, (int, float)) and not isinstance(doc, bool):
        out[prefix] = float(doc)
    return out


def compare(old: dict, new: dict, patterns: List[str],
            threshold_pct: float,
            lower_patterns: Sequence[str] = ()
            ) -> Tuple[List[str], List[str]]:
    """(report lines, regression lines). A regression is a selected
    higher-is-better metric dropping more than threshold_pct, a
    lower-is-better metric RISING more than threshold_pct, or an
    exact-named metric missing from the new document."""
    old_flat, new_flat = flatten(unwrap(old)), flatten(unwrap(new))
    report: List[str] = []
    regressions: List[str] = []
    seen = set()
    # Lower-is-better patterns claim their paths FIRST: a broad
    # higher-is-better glob (e.g. detail.serving.*) overlapping a
    # latency metric must not invert its polarity via the seen-dedup.
    tagged = ([(p, True) for p in lower_patterns] +
              [(p, False) for p in patterns])
    for pattern, lower_is_better in tagged:
        is_glob = any(c in pattern for c in "*?[")
        matched = sorted(p for p in old_flat
                         if fnmatch.fnmatchcase(p, pattern))
        if not matched and not is_glob:
            report.append(f"-- {pattern}: absent in old file; skipped")
            continue
        for path in matched:
            if path in seen:
                continue
            seen.add(path)
            old_v = old_flat[path]
            if path not in new_flat:
                # Null in new (failed leg) or dropped key.
                line = (f"!! {path}: {old_v:g} -> missing/null in new")
                if is_glob:
                    report.append(f"-- {path}: gone in new; skipped")
                else:
                    report.append(line)
                    regressions.append(line)
                continue
            new_v = new_flat[path]
            if old_v <= 0:
                report.append(f"-- {path}: non-positive baseline "
                              f"{old_v:g}; skipped")
                continue
            change = (new_v - old_v) / old_v * 100.0
            marker = "ok"
            if lower_is_better:
                if change > threshold_pct:
                    marker = "REGRESSION"
            elif change < -threshold_pct:
                marker = "REGRESSION"
            line = (f"{marker:>10}  {path}: {old_v:g} -> {new_v:g} "
                    f"({change:+.1f}%"
                    f"{', lower is better' if lower_is_better else ''})")
            report.append(line)
            if marker == "REGRESSION":
                regressions.append(line)
    return report, regressions


def manifest_tags(doc: dict) -> Dict[str, str]:
    """Tuning-manifest provenance tags recorded by the serving leg:
    ``{family: tag}`` from ``detail.serving.*_engine_tuned_detail``
    (tag = manifest payload-sha prefix, "default", or "adhoc")."""
    serving = (unwrap(doc).get("detail") or {}).get("serving") or {}
    out: Dict[str, str] = {}
    for key, val in serving.items():
        if key.endswith("_engine_tuned_detail") and isinstance(val,
                                                               dict):
            tag = val.get("tune_manifest")
            if tag:
                out[key[:-len("_engine_tuned_detail")]] = str(tag)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="Fail (exit 1) on >threshold%% regressions "
                    "between two bench JSON files.")
    parser.add_argument("old", help="baseline BENCH_*.json")
    parser.add_argument("new", help="candidate BENCH_*.json")
    parser.add_argument("--threshold", type=float, default=5.0,
                        help="allowed drop in percent (default 5)")
    parser.add_argument("--metrics", default=None,
                        help="comma-separated dotted-path globs "
                             "(default: the tracked serving/training "
                             "set)")
    parser.add_argument("--metrics-lower", default=None,
                        help="comma-separated dotted-path globs of "
                             "LOWER-is-better metrics (default: the "
                             "tracked checkpoint-latency set)")
    parser.add_argument("--manifest", nargs="?", const="", default=None,
                        metavar="EXPECTED_TAG",
                        help="report the tuning-manifest provenance "
                             "tags (sha prefix) the two rounds' tuned "
                             "serving legs ran with; with a value, "
                             "ALSO fail unless every tag in the new "
                             "file matches it — pins a CI round to "
                             "one reviewed manifest")
    args = parser.parse_args(argv)

    with open(args.old) as f:
        old = json.load(f)
    with open(args.new) as f:
        new = json.load(f)
    patterns = (args.metrics.split(",") if args.metrics
                else list(DEFAULT_METRICS))
    lower = (args.metrics_lower.split(",") if args.metrics_lower
             else list(DEFAULT_METRICS_LOWER))
    report, regressions = compare(old, new, patterns, args.threshold,
                                  lower_patterns=lower)
    for line in report:
        print(line)
    if args.manifest is not None:
        old_tags, new_tags = manifest_tags(old), manifest_tags(new)
        for fam in sorted(set(old_tags) | set(new_tags)):
            print(f"manifest    {fam}: {old_tags.get(fam, '-')} -> "
                  f"{new_tags.get(fam, '-')}")
        if args.manifest:
            bad = {f: t for f, t in new_tags.items()
                   if t != args.manifest}
            if bad or not new_tags:
                print(f"\nbench_compare: new round's tuning manifest "
                      f"!= expected {args.manifest!r}: "
                      f"{bad or 'no tuned legs recorded'}",
                      file=sys.stderr)
                return 1
    if regressions:
        print(f"\nbench_compare: {len(regressions)} metric(s) "
              f"regressed more than {args.threshold:g}%",
              file=sys.stderr)
        return 1
    print(f"\nbench_compare: no regression beyond "
          f"{args.threshold:g}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())
