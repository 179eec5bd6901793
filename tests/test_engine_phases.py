"""The engine loop's own account of its time: the phase seam
(``stpu.engine.<phase>`` spans + ``stpu_engine_loop_seconds_total``),
the step counter, the queue-wait / prefill / inter-token histograms and
the compile counter. The registry is process-global, so every test
reads deltas around its own engine.
"""
import glob
import threading

import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.models import llama
from skypilot_tpu.observability import stepstats
from skypilot_tpu.serve import decode_engine
from skypilot_tpu.serve.decode_engine import DecodeEngine
from skypilot_tpu.utils import compile_cache

PHASES = tuple(decode_engine._PHASE_SECONDS)
HISTOGRAMS = {"ttft": decode_engine._TTFT,
              "queue_wait": decode_engine._QUEUE_WAIT,
              "prefill": decode_engine._PREFILL_SECONDS,
              "itl": decode_engine._ITL}


def _tiny():
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    return cfg, llama.init(cfg, jax.random.key(0))


def _read():
    out = {f"phase/{p}": c.get()
           for p, c in decode_engine._PHASE_SECONDS.items()}
    out.update({f"steps/{k}": c.get()
                for k, c in decode_engine._STEP_KIND.items()})
    for name, hist in HISTOGRAMS.items():
        _, total, count = hist.labels().snapshot()
        out[f"{name}/sum"], out[f"{name}/count"] = total, count
    out["tokens"] = decode_engine._TOKENS.get()
    return out


def _serve(prompts, max_tokens, **engine_kwargs):
    """All ``prompts`` through a fresh tiny engine, submitted at once
    (more than its slots, so some wait in the queue); the registry's
    deltas and the token lists."""
    cfg, params = _tiny()
    before = _read()
    engine = DecodeEngine(cfg, params, slots=2, max_seq=96,
                          prefill_chunk=8, **engine_kwargs).start()
    try:
        reqs = [engine.submit(p, max_tokens=max_tokens) for p in prompts]
        tokens = [r.result(timeout=600) for r in reqs]
    finally:
        engine.shutdown()
    after = _read()
    return {k: after[k] - before[k] for k in after}, tokens


@pytest.fixture
def armed(tmp_state_dir):
    stepstats.arm(ring=4096, sync_every=0)
    stepstats.reset()
    yield
    stepstats.disarm()
    stepstats.reset()


PROMPTS = [[1 + i, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11][:3 + 2 * i]
           for i in range(5)]


def test_every_phase_counts_and_steps_match_the_step_ring(armed):
    delta, tokens = _serve(PROMPTS, 12)
    for phase in PHASES:
        assert delta[f"phase/{phase}"] > 0.0, phase
    ring = stepstats.steps_tail()
    assert delta["steps/decode"] == sum(
        1 for r in ring if r["decode_tokens"])
    # One chunk per iteration that prefilled; no host tier, no drafts.
    assert delta["steps/prefill"] == sum(
        1 for r in ring if r["prefill_tokens"])
    assert delta["steps/restore"] == delta["steps/verify"] == 0
    assert delta["tokens"] == sum(len(t) for t in tokens) == 5 * 12


def test_phases_partition_the_loop(armed):
    """Between the first and the last iteration that did work the
    engine thread is inside exactly one phase: the phase seconds sum to
    that span (both are perf_counter() reads of the one thread, so a
    loaded runner stretches them alike). ``wait`` is left out: the
    thread idles before the first submit and after the last token.
    ``with`` blocks around the phases missed 3 % of it on the chip (a
    returning frame's device arrays, PERF.md PR 26); the clock's
    switch leaves nothing between two phases."""
    delta, _ = _serve([[7 + i, 3, 9] * 4 for i in range(8)], 80)
    ring = stepstats.steps_tail()
    span = ring[-1]["mono"] - (ring[0]["mono"] - ring[0]["dur"])
    assert span >= 0.5, f"the run was too short to judge: {span:.3f} s"
    inside = sum(delta[f"phase/{p}"] for p in PHASES if p != "wait")
    assert abs(inside - span) <= 0.02 * span, (inside, span)


@pytest.mark.parametrize("pool_blocks", [0, 3], ids=["slots", "blocks"])
def test_queue_wait_and_prefill_split_the_ttft(pool_blocks):
    """Whatever a request waits for at the queue's head: a free slot
    (five requests, two slots) or free blocks (two usable blocks, and
    a request of up to 15 tokens reserves both, so one is served at a
    time beside an empty slot)."""
    delta, tokens = _serve(PROMPTS, 4, kv_pool_blocks=pool_blocks)
    n = len(tokens)
    assert delta["ttft/count"] == n
    assert delta["queue_wait/count"] == n
    assert delta["prefill/count"] == n
    assert delta["queue_wait/sum"] + delta["prefill/sum"] == \
        pytest.approx(delta["ttft/sum"], abs=1e-6 * n)
    assert delta["queue_wait/sum"] > 0.0


@pytest.mark.parametrize("spec_k", [0, 4], ids=["plain", "speculative"])
def test_itl_counts_every_gap_after_a_first_token(spec_k):
    # A self-repeating prompt makes the n-gram matcher draft, so the
    # speculative case emits several tokens from one verify step.
    prompts = [[5, 6, 7] * 6, [9, 4, 9, 4, 9, 4, 9, 4], [3, 1, 2]]
    delta, tokens = _serve(prompts, 10, spec_k=spec_k,
                           spec_ngram=2)
    emitted = sum(len(t) for t in tokens)
    assert delta["tokens"] == emitted
    assert delta["itl/count"] == emitted - len(tokens)
    assert (delta["steps/verify"] > 0) == bool(spec_k)


def test_itl_buckets_resolve_the_step_modes():
    edges = [b for b in decode_engine._ITL.buckets if b <= 0.5]
    assert edges[0] == 0.005 and edges[-1] == 0.5
    assert all(hi / lo <= 1.15 + 1e-9
               for lo, hi in zip(edges, edges[1:]))


def test_profiler_trace_holds_the_engine_spans(tmp_path):
    """A ``jax.profiler`` trace with the Python tracer off (what POST
    /profile takes) shows the loop's phases on the engine thread's
    line of the host plane."""
    cfg, params = _tiny()
    engine = DecodeEngine(cfg, params, slots=2, max_seq=64,
                          prefill_chunk=8).start()
    try:
        engine.warmup()
        jax.profiler.start_trace(
            str(tmp_path), profiler_options=stepstats.profile_options())
        try:
            engine.submit([1, 2, 3], max_tokens=6).result(timeout=600)
        finally:
            jax.profiler.stop_trace()
    finally:
        engine.shutdown()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    lines = [{e.name for e in line.events}
             for plane in data.planes if plane.name.startswith("/host:")
             for line in plane.lines]
    # One line holds them all: this engine's thread (an idle engine
    # another test of this process left behind has a line of its own).
    assert any({"stpu.engine.schedule.admit",
                "stpu.engine.schedule.prefill",
                "stpu.engine.schedule.decode", "stpu.engine.fetch",
                "stpu.engine.emit"} <= names for names in lines)
    # No Python frames: the tracer that hooks every thread is off.
    assert not any(n.startswith("$") for names in lines for n in names)


def test_compile_counter_counts_each_program_once():
    compile_cache.enable()
    compile_cache.enable()          # a second call adds no listener

    def total():
        return sum(count.get()
                   for count, _ in compile_cache._BY_SOURCE.values())

    x = jnp.arange(7.0)
    x.block_until_ready()
    before = total()
    fn = jax.jit(lambda v: (v * 3.0 + 1.0).sum())
    fn(x).block_until_ready()
    assert total() == before + 1
    fn(x).block_until_ready()       # cached in the process: no build
    assert total() == before + 1
    # From another thread too (the engine thread compiles at warm-up).
    t = threading.Thread(
        target=lambda: jax.jit(lambda v: v - 2.0)(x).block_until_ready())
    t.start()
    t.join(timeout=120)
    assert not t.is_alive()
    assert total() == before + 2
