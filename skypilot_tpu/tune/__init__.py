"""Engine autotuner: offline constant sweep + startup tuning manifest.

The serving engine's hot-path constants — split-KV attention tile
(``block``), prefill chunk / paged KV block size (``chunk``), paged
gather window (``window_blocks``), speculative draft depth
(``spec_k``) — were historically hand-pinned once and shared by every
model family and topology. This package closes the loop the ROADMAP
("Autotuned attention kernels + a self-improving perf loop") asks for:

* :mod:`skypilot_tpu.tune.manifest` — the sha256-pinned JSON manifest
  (``~/.stpu/tuning/manifest.json``) mapping a tuning key
  ``(family, batch-band, tp-degree, quant-mode)`` to tuned constants,
  with provenance (device kind, commit, bench leg, measured tok/s).
  Stdlib-only: the decode engine loads it at geometry resolution and
  must not pull anything heavy.
* :mod:`skypilot_tpu.tune.parity` — the correctness gate: a winner is
  persisted only after the greedy + seeded engine-vs-``models.decode``
  parity suite passes AT the tuned constants (tile-size changes are
  bit-identical only when aligned — the tuner proves it, never
  assumes it).
* :mod:`skypilot_tpu.tune.sweep` — the offline sweep driver behind
  ``stpu tune``: candidate configs measured through the existing
  ``decode_bench.measure_engine_{paged,spec,q8}`` legs (tok/s
  headline; stepstats dispatch/device means as diagnostics), losing
  configs pruned early at small step counts.

At engine startup, ``serve/decode_engine.resolve_kv_geometry`` looks
the manifest up (env ``STPU_TUNE_MANIFEST``; ``0`` disables, unset
falls back to the default path) so tuned geometry rides the gang
welcome handshake — a follower whose manifest drifted from the
leader's resolves different constants and dies at join, exactly like
a kv/quant config mismatch today.
"""
from skypilot_tpu.tune import manifest  # noqa: F401  (re-export)
