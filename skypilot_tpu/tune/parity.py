"""The autotuner's correctness gate: parity AT the tuned constants.

A tile/chunk/window/spec change is only bit-identical when the tile
boundaries align with what the online-softmax and block-table paths
assume — the sweep must PROVE a winner preserves output, never assume
it. This module runs, at an explicit candidate geometry, the same
invariants the tier-1 suites pin at default geometry:

* greedy: every engine stream equals the request's own
  ``models.decode`` fixed-path reference token-for-token (for int8 KV
  the reference is the default-constants quantized engine — q8 is not
  bit-identical to the bf16 models path by design, so the gate holds
  the GEOMETRY fixed-point instead: tuned constants must not change
  what default constants produce);
* seeded: a temperature>0 request at the tuned constants reproduces
  the default-constants engine's stream bit-for-bit — the
  fold_in(seed, position) sampling keys depend on logits only, so any
  divergence means the tuned geometry changed the math, not the
  sampler.

``stpu tune`` calls :func:`check_parity` on every winner before the
manifest entry is persisted; the non-default-geometry tier-1 tests
(tests/test_tune.py) call the same function so the gate itself is
pinned.
"""
from __future__ import annotations

import random
from typing import Any, Dict, Optional


class ParityError(AssertionError):
    """A tuned geometry changed engine output."""


def _tiny_model(family: str):
    if family == "mixtral":
        from skypilot_tpu.models import mixtral as mdl
        cfg = mdl.MixtralConfig.tiny()
    elif family == "gemma":
        from skypilot_tpu.models import gemma as mdl
        cfg = mdl.GemmaConfig.tiny(vocab_size=128)
    else:
        from skypilot_tpu.models import llama as mdl
        cfg = mdl.LlamaConfig.tiny(vocab_size=128)
    import jax
    return mdl, cfg, mdl.init(cfg, jax.random.key(0))


def _engine(cfg, params, *, kv_quant: bool, max_seq: int,
            engine_kw: Optional[Dict[str, Any]] = None):
    from skypilot_tpu.serve.decode_engine import DecodeEngine
    return DecodeEngine(cfg, params, slots=2, max_seq=max_seq,
                        kv_quant=kv_quant, use_manifest=False,
                        **(engine_kw or {})).start()


def _drain(engine, specs):
    reqs = [engine.submit(p, max_tokens=mt, temperature=t, seed=s)
            for p, mt, t, s in specs]
    return [r.result(timeout=600.0) for r in reqs]


def check_parity(family: str, *, block: int = 0, chunk: int = 0,
                 window_blocks: int = 0, spec_k: int = 0,
                 kv_quant: bool = False,
                 max_seq: int = 64, n_requests: int = 4,
                 max_tokens: int = 6) -> None:
    """Raise :class:`ParityError` unless the engine at the candidate
    constants reproduces reference output, greedy AND seeded.

    Zero-valued knobs mean "default" (the candidate does not tune
    them). Runs on tiny models — the gate checks NUMERICS of the
    geometry, which is model-size independent, so it stays cheap
    enough to run per winner inside the sweep and per parametrization
    in tier-1.
    """
    import jax.numpy as jnp

    mdl, cfg, params = _tiny_model(family)
    tuned_kw: Dict[str, Any] = {}
    if block:
        tuned_kw["block"] = int(block)
    if chunk:
        tuned_kw["kv_block_tokens"] = int(chunk)
    if window_blocks:
        tuned_kw["window_blocks"] = int(window_blocks)
    if spec_k:
        tuned_kw["spec_k"] = int(spec_k)

    rng = random.Random(1234)
    vocab = cfg.vocab_size
    # Ragged lengths spanning chunk boundaries, greedy + seeded rows.
    specs = []
    for i in range(n_requests):
        prompt = [rng.randint(1, vocab - 1)
                  for _ in range(rng.randint(3, max_seq // 2))]
        seeded = i % 2 == 1
        specs.append((prompt, max_tokens,
                      0.8 if seeded else 0.0, 40 + i))

    tuned = _engine(cfg, params, kv_quant=kv_quant, max_seq=max_seq,
                    engine_kw=tuned_kw)
    try:
        got = _drain(tuned, specs)
    finally:
        tuned.shutdown()
    ref_engine = _engine(cfg, params, kv_quant=kv_quant,
                         max_seq=max_seq)
    try:
        want = _drain(ref_engine, specs)
    finally:
        ref_engine.shutdown()

    label = (f"{family} block={block or 'dflt'} chunk={chunk or 'dflt'}"
             f" window_blocks={window_blocks or 'dflt'}"
             f" spec_k={spec_k or 'dflt'} kv_quant={kv_quant}")
    for i, ((prompt, mt, temp, _seed), g, w) in enumerate(
            zip(specs, got, want)):
        if g != w:
            raise ParityError(
                f"tuned vs default-engine stream diverged ({label}), "
                f"request {i} temp={temp}: {g} != {w}")
        if temp == 0.0 and not kv_quant:
            ref = mdl.decode(cfg, params,
                             jnp.asarray([prompt], jnp.int32),
                             jnp.int32(len(prompt)), mt, max_seq)
            if g != [int(t) for t in ref[0]]:
                raise ParityError(
                    f"tuned engine vs models.decode diverged "
                    f"({label}), request {i}: {g} != "
                    f"{[int(t) for t in ref[0]]}")
