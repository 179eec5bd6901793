"""The program's own account of its time: the phase clock
(observability/phases.py) under the engine loop (``stpu.engine.<phase>``
spans + ``stpu_engine_loop_seconds_total``), under a server's and a
trainer's start-up (``stpu.startup.<phase>`` +
``stpu_startup_seconds_total``); dispatches into a drained device, long
phase instances and the garbage collector's seconds; the step counter,
the queue-wait / prefill / inter-token histograms and the compile
counter. The registry is process-global, so every test reads deltas
around its own engine.
"""
import gc
import glob
import json
import pathlib
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu.models import llama
from skypilot_tpu.observability import phases
from skypilot_tpu.observability import stepstats
from skypilot_tpu.recipes import serve_llm
from skypilot_tpu.serve import decode_engine
from skypilot_tpu.serve.decode_engine import DecodeEngine
from skypilot_tpu.utils import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]

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
    out.update({f"starved/{k}": c.get()
                for k, c in decode_engine._STARVED_KIND.items()})
    out.update({f"drained/{p}": c.get()
                for p, c in decode_engine._DRAINED.items()})
    for p in PHASES:
        if p != "wait":
            out[f"long/{p}"] = decode_engine._LONG_PHASES.labels(
                phase=p).get()
            out[f"long_s/{p}"] = \
                decode_engine._LONG_PHASE_SECONDS.labels(phase=p).get()
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


def test_there_is_one_clock_and_the_engine_uses_it():
    """The clock lives in observability/phases.py alone; the engine
    loop's six phases and their series keep their names."""
    found = [str(path.relative_to(REPO))
             for path in (REPO / "skypilot_tpu").rglob("*.py")
             if "class _PhaseClock" in path.read_text()]
    assert found == ["skypilot_tpu/observability/phases.py"]
    cfg, params = _tiny()
    engine = DecodeEngine(cfg, params, slots=2, max_seq=64,
                          prefill_chunk=8)
    assert type(engine._phase) is phases._PhaseClock
    assert PHASES == ("schedule.admit", "schedule.prefill",
                      "schedule.decode", "fetch", "emit", "wait")
    assert decode_engine._LOOP_SECONDS.name == \
        "stpu_engine_loop_seconds_total"


def _drive_until_idle(engine):
    for _ in range(200):
        engine._admit()
        did = engine._prefill_one()
        if not (engine._decode_step() or did):
            return
    raise AssertionError("the engine never ran dry")


def test_a_dispatch_into_a_drained_device_is_starved(monkeypatch):
    """An unstarted engine driven by hand. After everything dispatched
    has been read the device's queue is empty: the next phase switch
    sees it, the seconds from there to the next dispatch land under
    the phases they passed in (here the one that slept), and that
    dispatch counts as starved. A dispatch that finds the program
    before it still running counts nothing."""
    cfg, params = _tiny()
    engine = DecodeEngine(cfg, params, slots=2, max_seq=96,
                          prefill_chunk=8)
    req = engine.submit([5, 6, 7], max_tokens=12)
    engine._admit()
    assert engine._prefill_one() and engine._decode_step()
    engine._land(everything=True)       # nothing is left on the device
    before = _read()
    engine._enter("schedule.admit")
    time.sleep(0.05)
    assert engine._decode_step()
    delta = {k: v - before[k] for k, v in _read().items()}
    assert delta["starved/decode"] == 1
    assert delta["starved/prefill"] == delta["starved/verify"] == 0
    assert delta["drained/schedule.admit"] >= 0.05
    # The dispatch closed the account: what came after it (the fetch
    # of nothing, the next switch) added to no phase. (``wait`` is
    # left out here and below: an idle engine that another test of
    # this process left behind counts there all the time.)
    others = sum(delta[f"drained/{p}"] for p in PHASES
                 if p not in ("schedule.admit", "wait"))
    assert others < 0.05, delta
    assert engine._drained_in is None

    # Straight behind another: the poll says the device is busy.
    monkeypatch.setattr(engine, "_device_drained", lambda: False)
    before = _read()
    assert engine._decode_step() and engine._decode_step()
    delta = {k: v - before[k] for k, v in _read().items()}
    assert delta["steps/decode"] == 2
    assert delta["starved/decode"] == 0
    assert sum(delta[f"drained/{p}"] for p in PHASES
               if p != "wait") == 0.0
    monkeypatch.undo()
    _drive_until_idle(engine)
    assert len(req.result(timeout=5.0)) == 12

    # The engine has run dry. Once it has waited with the queue
    # drained, its looks for work stay under ``wait``: no slow host.
    engine._enter("wait")
    before = _read()
    time.sleep(0.02)
    _drive_until_idle(engine)
    delta = {k: v - before[k] for k, v in _read().items()}
    assert delta["drained/wait"] >= 0.02
    assert sum(delta[f"drained/{p}"] for p in PHASES
               if p != "wait") == 0.0


def test_a_long_phase_instance_is_counted_once_under_its_phase(
        monkeypatch):
    """One ``emit`` stretched past LONG_PHASE_S (a client's queue that
    sleeps once) counts once under ``emit`` with its seconds; ``wait``,
    whose condition wait times out near the threshold, has no series
    at all."""
    slept = []
    emit = decode_engine.Request._emit

    def slow_once(self, token, now):
        if not slept:
            slept.append(True)
            time.sleep(phases.LONG_PHASE_S + 0.03)
        emit(self, token, now)

    cfg, params = _tiny()
    engine = DecodeEngine(cfg, params, slots=2, max_seq=64,
                          prefill_chunk=8).start()
    try:
        engine.warmup()         # no compile inside a counted phase
        time.sleep(0.2)         # a few waits that time out
        before = _read()
        monkeypatch.setattr(decode_engine.Request, "_emit", slow_once)
        engine.submit([1, 2, 3], max_tokens=3).result(timeout=600)
    finally:
        engine.shutdown()
    delta = {k: v - before[k] for k, v in _read().items()}
    assert delta["long/emit"] == 1
    assert delta["long_s/emit"] >= phases.LONG_PHASE_S + 0.03
    assert 'phase="wait"' not in decode_engine._LONG_PHASES.render()
    assert 'phase="wait"' not in \
        decode_engine._LONG_PHASE_SECONDS.render()


def test_the_gc_hook_counts_a_forced_collection():
    compile_cache.enable()
    compile_cache.enable()          # a second call adds no hook
    assert gc.callbacks.count(phases.on_gc) == 1
    before = phases._GC[2].get()
    gc.collect()
    assert phases._GC[2].get() > before


STARTUP_CHILD = """
import json, threading
from skypilot_tpu.models import llama
from skypilot_tpu.observability import phases
from skypilot_tpu.recipes import serve_llm
from skypilot_tpu.utils import compile_cache
compile_cache.enable()
cfg = llama.LlamaConfig.tiny(vocab_size=128)
params = serve_llm.init_params(cfg, 0)
ready = threading.Event()
httpd = serve_llm.serve(cfg, params, 0, ready_event=ready,
                        engine_slots=2, prefix_cache_mb=0)
assert ready.wait(300)
print("READY " + json.dumps(phases.startup_seconds()), flush=True)
httpd.engine.shutdown()
httpd.server_close()
"""


def test_startup_phases_add_up_to_the_time_to_ready():
    """A tiny server in a process of its own: ``import`` (from the
    kernel's start of the process), ``weights``, ``engine`` and
    ``warmup`` are each positive and together cover the time from the
    spawn to ``ready`` but for the few lines between them."""
    spawned = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, "-c", STARTUP_CHILD], cwd=str(REPO),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        line = ""
        for line in proc.stdout:
            if line.startswith("READY "):
                break
        wall = time.monotonic() - spawned
        assert line.startswith("READY "), proc.stderr.read()[-2000:]
        assert proc.wait(timeout=60) == 0
    finally:
        proc.kill()
        proc.stdout.close()
        proc.stderr.close()
    took = json.loads(line[len("READY "):])
    assert sorted(took) == ["engine", "import", "warmup", "weights"]
    assert all(v > 0.0 for v in took.values()), took
    total = sum(took.values())
    # /proc/uptime ticks in hundredths; the rest is this side's spawn.
    assert total <= wall + 0.05, (took, wall)
    assert wall - total <= max(1.0, 0.25 * wall), (took, wall)


def test_a_supervisor_restart_adds_to_engine_only():
    """The restarted engine comes through the factory again: start-up
    phase ``engine`` grows (a counter), ``import`` and ``weights`` do
    not. Nothing warms a restarted engine up (its programs are the
    process's already), so ``warmup`` stays where it was."""
    cfg, _ = _tiny()
    params = serve_llm.init_params(cfg, 0)
    ready = threading.Event()
    httpd = serve_llm.serve(cfg, params, 0, ready_event=ready,
                            engine_slots=2, prefix_cache_mb=0)
    try:
        assert ready.wait(600)
        before = phases.startup_seconds()
        httpd.engine.restart_now()
        after = phases.startup_seconds()
    finally:
        httpd.engine.shutdown()
        httpd.server_close()
    assert after["engine"] > before["engine"] > 0.0
    grew = {p for p in after if after[p] != before[p]}
    assert grew <= {"engine", "warmup"}, (before, after)
    assert httpd.engine.restarts == 1


def test_run_lora_returns_its_startup_by_phase():
    from skypilot_tpu.recipes import llama_lora
    m = llama_lora.main(["--model", "tiny", "--steps", "2",
                         "--batch-size", "2", "--seq-len", "32"])
    took = m["startup_seconds"]
    assert {"weights", "compile", "first_loss"} <= set(took)
    assert all(took[p] > 0.0 for p in ("weights", "compile",
                                       "first_loss")), took
    own = took["weights"] + took["compile"] + took["first_loss"]
    # The three partition the call from ``weights`` on; before it lie
    # only the (absent) gang's rendezvous and a no-op enable().
    assert own <= m["start_to_first_loss_seconds"] + 0.02
    assert own >= m["start_to_first_loss_seconds"] - 0.25


def test_profiler_trace_holds_the_engine_spans(tmp_path):
    """A ``jax.profiler`` trace with the Python tracer off (what POST
    /profile takes) shows the loop's phases on the engine thread's
    line of the host plane, a server's start-up phases on the threads
    that ran them, and the span a dispatch into a drained device
    closes."""
    cfg, _ = _tiny()
    jax.profiler.start_trace(
        str(tmp_path), profiler_options=stepstats.profile_options())
    try:
        params = serve_llm.init_params(cfg, 0)
        ready = threading.Event()
        httpd = serve_llm.serve(cfg, params, 0, ready_event=ready,
                                engine_slots=2, prefix_cache_mb=0)
        try:
            assert ready.wait(600)
            httpd.engine.submit([1, 2, 3], max_tokens=6).result(
                timeout=600)
            time.sleep(0.2)     # the device drains; the loop waits
            httpd.engine.submit([4, 5, 6], max_tokens=2).result(
                timeout=600)
        finally:
            httpd.engine.shutdown()
            httpd.server_close()
    finally:
        jax.profiler.stop_trace()
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
                "stpu.engine.emit", "stpu.engine.starved"} <= names
               for names in lines)
    everywhere = set().union(*lines)
    assert {"stpu.startup.weights", "stpu.startup.engine",
            "stpu.startup.warmup"} <= everywhere
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
