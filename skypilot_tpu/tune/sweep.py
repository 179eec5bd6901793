"""The offline sweep behind ``stpu tune``.

For each requested ``(family, mode)`` the driver measures candidate
constant combinations through the SAME decode_bench legs bench.py
tracks (so the tuner's objective can never drift from the recorded
bench trajectory), prunes losers early at a small step count, re-runs
the survivors at the full budget, parity-gates the winner
(:mod:`skypilot_tpu.tune.parity`), and persists it to the sha-pinned
manifest (:mod:`skypilot_tpu.tune.manifest`).

Search space (declared, not discovered — every axis is a constant the
engine already threads through ``resolve_kv_geometry``):

====== ==================== ========================================
mode   axes                 objective leg
====== ==================== ========================================
paged  chunk x window       measure_engine_paged  (block pool)
spec   spec_k               measure_engine_spec   (drafting depth)
q8     chunk x window       measure_engine_q8     (int8 KV+weights)
====== ==================== ========================================

tok/s is the headline objective; stepstats ``dispatch_ms_mean`` /
``device_ms_mean`` ride along as diagnostics in the manifest entry so
a regression hunt can tell dispatch-bound from device-bound winners.
Modes run in table order and merge into one entry per tuning key.
"""
from __future__ import annotations

import subprocess
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from skypilot_tpu.tune import manifest as tune_manifest
from skypilot_tpu.tune.parity import ParityError, check_parity

FAMILIES = ("llama", "mixtral", "gemma")
MODES = ("paged", "spec", "q8")

# Candidate axes per mode. Values are chosen to stay aligned with the
# engine's invariants by construction: chunk must divide max_seq
# (resolve_kv_geometry halves it until it does), window is derived in
# whole chunks.
SEARCH_SPACE: Dict[str, Dict[str, Sequence[int]]] = {
    "paged": {"chunk": (32, 64, 128), "window_blocks": (2, 4, 8)},
    "spec": {"spec_k": (0, 2, 4, 8)},
    "q8": {"chunk": (32, 64, 128), "window_blocks": (2, 4, 8)},
}

# The hand-pinned constants every sweep measures as its baseline
# candidate — the winner is reported NEXT TO this number, and when no
# candidate beats it the manifest simply records the default (tuned
# >= default holds by construction: both are measured the same way in
# the same process).
DEFAULTS: Dict[str, Dict[str, int]] = {
    "paged": {"chunk": 64, "window_blocks": 4},
    "spec": {"spec_k": 0},
    "q8": {"chunk": 64, "window_blocks": 4},
}

_LEG_KEY = {"paged": "engine_paged_tok_s",
            "spec": "engine_spec_tok_s",
            "q8": "engine_q8_tok_s"}

_QUANT = {"q8": (True, True)}   # mode -> (kv_quant, weight_quant)

# Prune rule: after the small-budget round, keep candidates within
# PRUNE_MARGIN_PCT of the round's best (capped at PRUNE_KEEP), plus
# the default. Small-step tok/s is noisy; the margin is deliberately
# loose so pruning only drops clear losers.
PRUNE_MARGIN_PCT = 15.0
PRUNE_KEEP = 3


def _budgets(quick: bool) -> Dict[str, Dict[str, int]]:
    if quick:
        return {"prune": dict(n_requests=6, max_tokens=16,
                              max_prompt=48),
                "final": dict(n_requests=12, max_tokens=24,
                              max_prompt=96)}
    return {"prune": dict(n_requests=8, max_tokens=24,
                          max_prompt=96),
            "final": dict(n_requests=32, max_tokens=64,
                          max_prompt=192)}


def _candidates(mode: str) -> List[Dict[str, int]]:
    axes = SEARCH_SPACE[mode]
    combos: List[Dict[str, int]] = [{}]
    for name, values in axes.items():
        combos = [dict(c, **{name: v}) for c in combos
                  for v in values]
    default = DEFAULTS[mode]
    if default not in combos:
        combos.insert(0, default)
    return combos


def _measure(mode: str, family: str, cand: Dict[str, int],
             budget: Dict[str, int], slots: int,
             shape_kw: Dict[str, Any]) -> Dict[str, Any]:
    from skypilot_tpu.benchmark import decode_bench
    if mode in ("paged", "q8"):
        kw = {}
        if cand.get("window_blocks"):
            kw["window_blocks"] = cand["window_blocks"]
        fn = (decode_bench.measure_engine_paged if mode == "paged"
              else decode_bench.measure_engine_q8)
        return fn(family, slots=slots,
                  block_tokens=cand.get("chunk", 0), engine_kw=kw,
                  **budget, **shape_kw)
    if mode == "spec":
        b = dict(budget)
        b.pop("max_prompt", None)
        return decode_bench.measure_engine_spec(
            family, slots=slots, spec_k=cand.get("spec_k", 0),
            shared_prefix=min(128, 4 * b["max_tokens"]),
            max_unique=max(8, b["max_tokens"] // 2), **b, **shape_kw)
    raise ValueError(f"unknown tune mode {mode!r}")


def _gate(mode: str, family: str, cand: Dict[str, int]) -> None:
    kv_quant, _ = _QUANT.get(mode, (False, False))
    check_parity(
        family, chunk=cand.get("chunk", 0),
        window_blocks=cand.get("window_blocks", 0),
        spec_k=cand.get("spec_k", 0), kv_quant=kv_quant)


def _provenance(legs: Sequence[str]) -> Dict[str, str]:
    import jax
    try:
        commit = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=__file__.rsplit("/skypilot_tpu/", 1)[0],
        ).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        commit = "unknown"
    return {
        "device_kind": jax.devices()[0].device_kind,
        "commit": commit,
        "created": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "tool": "stpu tune",
        "legs": ",".join(legs),
    }


def sweep_one(family: str, mode: str, *, quick: bool = False,
              slots: int = 8, shape_kw: Optional[Dict[str, Any]] = None,
              log: Callable[[str], None] = print
              ) -> Optional[Dict[str, Any]]:
    """Sweep one (family, mode); returns the parity-gated winner as
    ``{"knobs": {...}, "objective": {...}}`` or None when every
    candidate that beat the default failed the parity gate AND the
    default itself failed (never observed; the default geometry is
    tier-1-pinned)."""
    shape_kw = dict(shape_kw or {})
    budgets = _budgets(quick)
    cands = _candidates(mode)
    leg = _LEG_KEY[mode]
    default = DEFAULTS[mode]

    # Round 1: every candidate at the small budget.
    scores: List[float] = []
    for cand in cands:
        r = _measure(mode, family, cand, budgets["prune"], slots,
                     shape_kw)
        scores.append(float(r[leg]))
        log(f"tune[{family}/{mode}] probe {cand} -> "
            f"{r[leg]:g} tok/s")
    best = max(scores)
    ranked = sorted(range(len(cands)), key=lambda i: -scores[i])
    keep = [i for i in ranked
            if scores[i] >= best * (1 - PRUNE_MARGIN_PCT / 100.0)]
    keep = keep[:PRUNE_KEEP]
    default_idx = cands.index(default)
    if default_idx not in keep:
        keep.append(default_idx)
    log(f"tune[{family}/{mode}] pruned {len(cands)} -> {len(keep)} "
        f"candidates")

    # Round 2: survivors at the full budget.
    finals: List[Dict[str, Any]] = []
    for i in keep:
        r = _measure(mode, family, cands[i], budgets["final"], slots,
                     shape_kw)
        finals.append({"cand": cands[i], "result": r,
                       "tok_s": float(r[leg])})
        log(f"tune[{family}/{mode}] final {cands[i]} -> "
            f"{r[leg]:g} tok/s")
    finals.sort(key=lambda f: -f["tok_s"])
    default_tok_s = next(f["tok_s"] for f in finals
                         if f["cand"] == default)

    # Winner = best survivor that passes the parity gate.
    for f in finals:
        try:
            _gate(mode, family, f["cand"])
        except ParityError as err:
            log(f"tune[{family}/{mode}] REJECTED {f['cand']}: {err}")
            continue
        r = f["result"]
        objective = {
            "leg": leg, "tok_s": f["tok_s"],
            "default_tok_s": default_tok_s,
            "dispatch_ms_mean": r.get("dispatch_ms_mean"),
            "device_ms_mean": r.get("device_ms_mean"),
        }
        log(f"tune[{family}/{mode}] winner {f['cand']} "
            f"({f['tok_s']:g} vs default {default_tok_s:g} tok/s)")
        return {"knobs": dict(f["cand"]), "objective": objective}
    log(f"tune[{family}/{mode}] no candidate survived the parity "
        f"gate — keeping defaults")
    return None


def run_sweep(families: Sequence[str] = FAMILIES,
              modes: Sequence[str] = MODES, *, quick: bool = False,
              slots: int = 8, tiny: bool = False,
              out_path=None, log: Callable[[str], None] = print
              ) -> Dict[str, Any]:
    """Full sweep -> manifest on disk. Returns the written document."""
    shape_kw = {"tiny": True} if tiny else {}
    entries: Dict[str, Dict[str, Any]] = {}
    legs: List[str] = []
    for family in families:
        for mode in modes:
            kv_quant, weight_quant = _QUANT.get(mode, (False, False))
            win = sweep_one(family, mode, quick=quick, slots=slots,
                            shape_kw=shape_kw, log=log)
            if win is None:
                continue
            key = tune_manifest.tuning_key(
                family, slots, tp=1, kv_quant=kv_quant,
                weight_quant=weight_quant)
            entry = entries.setdefault(
                key, {"parity": "pass", "objective": {}})
            entry.update(win["knobs"])
            entry["objective"][_LEG_KEY[mode]] = win["objective"]
            legs.append(f"{family}/{mode}")
    doc = tune_manifest.save(entries, _provenance(legs),
                             path=out_path)
    log(f"tune: wrote {len(entries)} entr"
        f"{'y' if len(entries) == 1 else 'ies'} "
        f"(sha {doc['sha256'][:12]}) to "
        f"{out_path or tune_manifest.default_path()}")
    return doc
