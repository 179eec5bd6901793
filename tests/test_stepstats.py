"""Per-step performance telemetry + flight recorder (ISSUE 11).

Acceptance pinned here:
  * an injected ``engine.step`` crash (existing fault seam) produces a
    flight-recorder dump with the terminal exception and >= 1
    pre-crash step record, and ``stpu perf show`` renders it;
  * ``GET /perf`` serves the phase breakdown from the replica and the
    LB merges every ready replica's /perf into one document;
  * disarmed, the engine hot path is provably stepstats-free
    (monkeypatch-bomb, the tracing/fault-injection pattern) and the
    armed engine's tok/s stays within noise of unarmed (slow-marked).
"""
import json
import threading
import time
import urllib.request

import pytest
from click.testing import CliRunner

from skypilot_tpu.observability import stepstats
from skypilot_tpu.utils import fault_injection


@pytest.fixture
def armed(tmp_state_dir):
    stepstats.arm(ring=256, sync_every=0)
    stepstats.reset()
    yield tmp_state_dir
    stepstats.disarm()
    stepstats.reset()


def _tiny_llm():
    import jax

    from skypilot_tpu.models import llama
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    return cfg, params


# ------------------------------------------------------------ ring unit
def test_ring_record_and_snapshot(armed):
    for i in range(300):            # ring=256: oldest 44 evicted
        stepstats.record(dur=0.001, phase="decode", live_slots=2,
                         queue_depth=1, decode_tokens=2)
    snap = stepstats.snapshot()
    assert snap["armed"] is True
    assert snap["steps"] == 256
    assert snap["total_steps"] == 300
    assert snap["phases"]["decode"]["steps"] == 256
    assert snap["phases"]["decode"]["seconds"] == pytest.approx(
        0.256, rel=1e-6)
    assert 0.0 < snap["busy_fraction"] <= 1.0
    assert snap["occupancy"]["mean"] == 2.0
    assert snap["queue_depth"] == 1
    # Eviction kept the running sums consistent with the resident set.
    assert sum(p["steps"] for p in snap["phases"].values()) == 256


def test_ring_mixed_phases_and_tokens(armed):
    stepstats.record(dur=0.002, phase="prefill", live_slots=1,
                     queue_depth=0, prefill_tokens=64)
    stepstats.record(dur=0.001, phase="decode", live_slots=3,
                     queue_depth=0, decode_tokens=3)
    stepstats.record(dur=0.003, phase="mixed", live_slots=3,
                     queue_depth=0, prefill_tokens=64,
                     decode_tokens=3)
    snap = stepstats.snapshot()
    assert set(snap["phases"]) == {"prefill", "decode", "mixed"}
    shares = sum(p["share"] for p in snap["phases"].values())
    assert shares == pytest.approx(1.0, abs=0.01)
    assert snap["tokens_per_sec"]["prefill"] > 0
    assert snap["tokens_per_sec"]["decode"] > 0


def test_sync_due_cadence(armed):
    stepstats.arm(ring=256, sync_every=3)
    assert [stepstats.sync_due() for _ in range(7)] == [
        False, False, True, False, False, True, False]
    stepstats.arm(ring=256, sync_every=0)
    assert not any(stepstats.sync_due() for _ in range(10))


def test_sampled_sync_times_the_wait(armed):
    class _Arr:
        def __init__(self):
            self.calls = 0

        def block_until_ready(self):
            self.calls += 1
            time.sleep(0.01)

    arr = _Arr()
    waited = stepstats.sampled_sync(arr)
    assert arr.calls == 1
    assert waited >= 0.009
    # Non-array values (no block_until_ready) never raise.
    assert stepstats.sampled_sync(object()) >= 0.0


def test_derived_metrics_exposed(armed):
    from skypilot_tpu.observability import metrics, promtext
    stepstats.record(dur=0.002, phase="decode", live_slots=4,
                     queue_depth=0, decode_tokens=4,
                     dispatch_s=0.0002, device_s=0.0015)
    families = promtext.parse(metrics.render())
    assert promtext.histogram(
        families, "stpu_engine_step_seconds",
        phase="decode").count > 0
    assert promtext.value(
        families, "stpu_engine_busy_fraction") > 0
    assert "stpu_engine_phase_tokens_per_sec" in families
    assert promtext.histogram(
        families, "stpu_engine_step_dispatch_seconds").count > 0
    assert promtext.histogram(
        families, "stpu_engine_step_device_seconds").count > 0


# --------------------------------------------------------- engine wired
@pytest.mark.usefixtures("tmp_state_dir")
def test_disarmed_engine_is_stepstats_free(monkeypatch):
    """Mirror of the tracing/fault-injection zero-cost guarantee: with
    stepstats unarmed, a full engine request (admission, chunked
    prefill, decode steps, slot free) never reaches the module past
    the ENABLED flag — any record/record_admission/sync call trips the
    monkeypatched bomb."""
    from skypilot_tpu.serve.decode_engine import DecodeEngine

    assert not stepstats.ENABLED

    def bomb(*args, **kwargs):
        raise AssertionError(
            "stepstats reached while unarmed (hot path must guard on "
            "stepstats.ENABLED)")

    monkeypatch.setattr(stepstats, "record", bomb)
    monkeypatch.setattr(stepstats, "record_admission", bomb)
    monkeypatch.setattr(stepstats, "sampled_sync", bomb)
    monkeypatch.setattr(stepstats, "sync_due", bomb)

    cfg, params = _tiny_llm()
    engine = DecodeEngine(cfg, params, slots=2, max_seq=64,
                          prefill_chunk=8).start()
    try:
        toks = engine.submit([1, 2, 3], max_tokens=4).result(
            timeout=600)
        assert len(toks) == 4
    finally:
        engine.shutdown()


def test_jitted_steps_are_stepstats_free():
    """The jitted programs themselves carry no telemetry code —
    recording rides the host-side supervisor loop only."""
    import inspect

    from skypilot_tpu.serve import decode_engine
    for fn in (decode_engine._paged_step,
               decode_engine._paged_prefill_chunk,
               decode_engine._paged_spec_step):
        assert "stepstats" not in inspect.getsource(fn)


def test_armed_engine_records_steps_and_admissions(armed):
    from skypilot_tpu.serve.decode_engine import DecodeEngine

    stepstats.arm(ring=512, sync_every=4)
    cfg, params = _tiny_llm()
    engine = DecodeEngine(cfg, params, slots=2, max_seq=96,
                          prefill_chunk=16).start()
    try:
        reqs = [engine.submit([1 + i, 2, 3], max_tokens=8)
                for i in range(3)]
        total = sum(len(r.result(timeout=600)) for r in reqs)
        assert total == 24
    finally:
        engine.shutdown()
    snap = stepstats.snapshot()
    assert snap["steps"] > 0
    # Both phases showed up: chunked prefill AND batched decode.
    assert "decode" in snap["phases"] or "mixed" in snap["phases"]
    assert snap["tokens_per_sec"]["decode"] > 0
    # sync_every=4 with >= 8 decode steps: at least one sampled split.
    assert snap.get("sync", {}).get("samples", 0) >= 1
    assert snap.get("dispatch_ms_mean") is not None
    admits = stepstats.admissions_tail()
    assert len(admits) >= 3        # warmup + the three requests
    assert admits[-1]["prompt_tokens"] == 3
    assert admits[-1]["max_tokens"] == 8
    assert admits[-1]["queue_wait_s"] >= 0.0


def test_engine_crash_writes_flight_dump_and_cli_renders_it(armed):
    """THE acceptance path: injected engine.step crash -> dump with
    terminal exception + pre-crash step records -> `stpu perf show`
    renders it; the engine_failed event references the dump."""
    from skypilot_tpu import cli
    from skypilot_tpu.observability import events
    from skypilot_tpu.serve import decode_engine
    from skypilot_tpu.serve.decode_engine import (DecodeEngine,
                                                  EngineError,
                                                  EngineSupervisor)

    cfg, params = _tiny_llm()
    sup = EngineSupervisor(
        lambda: DecodeEngine(cfg, params, slots=2, max_seq=96,
                             prefill_chunk=16),
        max_restarts=1, backoff_base=0.05,
        poll_interval=0.02).start()
    try:
        # Healthy request first: the ring must hold PRE-crash steps.
        sup.engine.submit([1, 2, 3], max_tokens=6).result(timeout=600)
        with fault_injection.inject("engine.step", times=1):
            req = sup.submit([4, 5, 6], max_tokens=6)
            with pytest.raises(EngineError):
                req.result(timeout=600)
        # Wait for the supervisor's engine_failed event (it carries
        # the flight-dump reference) — the dump itself is written
        # synchronously on the crash path before the request fails.
        deadline = time.time() + 10
        while time.time() < deadline:
            if any(r.get("event") == "engine_failed"
                   for r in events.read(kind="engine", limit=None)):
                break
            time.sleep(0.05)
    finally:
        sup.shutdown()
        fault_injection.clear()
    dumps = stepstats.list_dumps()
    assert dumps, "engine crash produced no flight-recorder dump"
    doc = stepstats.read_dump()
    assert doc["reason"] == "engine_crash"
    assert "InjectedFault" in doc["error"]
    assert len(doc["steps"]) >= 1
    assert doc["snapshot"]["steps"] >= 1
    # The lifecycle event references the dump path.
    failed = [r for r in events.read(kind="engine", limit=None)
              if r.get("event") == "engine_failed"]
    assert failed and failed[-1].get("flightrec")
    assert failed[-1]["flightrec"].endswith(".json")

    runner = CliRunner()
    out = runner.invoke(cli.cli, ["perf", "show"])
    assert out.exit_code == 0, out.output
    assert "engine_crash" in out.output
    assert "InjectedFault" in out.output
    assert "decode" in out.output or "prefill" in out.output

    out = runner.invoke(cli.cli, ["perf", "dump"])
    assert out.exit_code == 0, out.output
    assert dumps[-1] in out.output
    out = runner.invoke(cli.cli, ["perf", "dump", dumps[-1]])
    assert out.exit_code == 0
    assert json.loads(out.output)["reason"] == "engine_crash"
    # del the decode_engine ref keeps linters honest about the import
    del decode_engine


def test_dump_flight_roundtrip_and_prefix_resolution(armed):
    stepstats.record(dur=0.001, phase="decode", live_slots=1,
                     queue_depth=0, decode_tokens=1)
    path = stepstats.dump_flight("sigterm", error=None)
    assert path is not None and path.endswith(".json")
    doc = stepstats.read_dump()
    assert doc["reason"] == "sigterm"
    assert doc["steps"][-1]["decode_tokens"] == 1
    # Unique-prefix resolution + clean errors.
    name = stepstats.list_dumps()[-1]
    assert stepstats.read_dump(name[:20])["reason"] == "sigterm"
    with pytest.raises(FileNotFoundError):
        stepstats.read_dump("zzz-no-such-dump")


@pytest.mark.usefixtures("tmp_state_dir")
def test_read_dump_without_dumps_raises(monkeypatch):
    with pytest.raises(FileNotFoundError):
        stepstats.read_dump()


def test_dump_retention_cap(armed, monkeypatch):
    """Crash/restart paths dump unconditionally, so retention must be
    bounded: only the newest KEEP_DUMPS survive replica churn."""
    monkeypatch.setattr(stepstats, "KEEP_DUMPS", 5)
    for i in range(9):
        assert stepstats.dump_flight("engine_crash",
                                     error=f"crash {i}")
    dumps = stepstats.list_dumps()
    assert len(dumps) == 5
    # The newest dump is the one kept last.
    assert stepstats.read_dump()["error"] == "crash 8"


def test_begin_profile_atomic_claim(armed):
    """POST /profile's claim must be atomic: the second claimant is
    refused (409 on the handler side) instead of both being promised a
    capture."""
    assert stepstats.begin_profile() is True
    assert stepstats.begin_profile() is False
    with pytest.raises(RuntimeError):
        stepstats.capture_profile(0.05)
    # The claimed path releases the slot on completion.
    import jax
    orig = jax.profiler

    class _P:
        ProfileOptions = orig.ProfileOptions

        @staticmethod
        def start_trace(path, profiler_options):
            pass

        @staticmethod
        def stop_trace():
            pass

    jax.profiler = _P
    try:
        stepstats.capture_profile(0.05, claimed=True)
        # Slot released on completion: claimable again.
        assert stepstats.begin_profile() is True
        stepstats.capture_profile(0.05, claimed=True)
    finally:
        jax.profiler = orig
    assert not stepstats._profile_active


# ------------------------------------------------- /perf + LB merge e2e
def test_replica_perf_endpoint_and_lb_merge(armed):
    import socket

    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve.load_balancing_policies import (
        RoundRobinPolicy)

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    stepstats.arm(ring=512, sync_every=4)
    cfg, params = _tiny_llm()
    port = free_port()
    httpd = serve_llm.serve(cfg, params, port, engine_slots=2,
                            prefix_cache_mb=0.0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    replica = f"http://127.0.0.1:{port}"
    lb = None
    try:
        deadline = time.time() + 600
        while time.time() < deadline:
            try:
                with urllib.request.urlopen(replica + "/health",
                                            timeout=2) as resp:
                    if resp.status == 200:
                        break
            except Exception:
                pass
            time.sleep(0.1)
        body = json.dumps({"prompt": [1, 2, 3],
                           "max_tokens": 6}).encode()
        req = urllib.request.Request(
            replica + "/generate", data=body,
            headers={"Content-Type": "application/json"},
            method="POST")
        with urllib.request.urlopen(req, timeout=600) as resp:
            assert resp.status == 200

        with urllib.request.urlopen(replica + "/perf",
                                    timeout=10) as resp:
            doc = json.loads(resp.read())
        assert doc["armed"] is True
        assert doc["steps"] > 0
        assert doc["phases"]
        assert doc["engine"]["healthy"] is True

        # LB merge: one fetch of the service endpoint covers the stack.
        policy = RoundRobinPolicy()
        policy.set_ready_replicas([replica])
        lb = lb_lib.run_load_balancer(free_port(), policy,
                                      lb_lib.RequestRecorder())
        lb_url = f"http://127.0.0.1:{lb.server_address[1]}"
        with urllib.request.urlopen(lb_url + "/perf",
                                    timeout=10) as resp:
            merged = json.loads(resp.read())
        assert replica in merged["replicas"]
        assert merged["replicas"][replica]["phases"]
        assert merged["aggregate"]["replicas"] == 1
        assert merged["aggregate"]["phases"]
        assert merged["aggregate"]["tokens_per_sec"]["decode"] > 0
    finally:
        if lb is not None:
            lb.shutdown()
        if httpd.engine is not None:
            httpd.engine.shutdown()
        httpd.shutdown()


def test_lb_perf_merge_reports_dead_replica():
    """A replica that cannot be scraped is REPORTED in the /perf merge
    — an {"error": ...} entry under its url — and EXCLUDED from the
    aggregate, so a half-dead fleet reads as degraded instead of
    healthy-but-slower."""
    import http.server
    import socket

    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve.load_balancing_policies import (
        RoundRobinPolicy)

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    perf_doc = {"armed": True, "steps": 4,
                "phases": {"decode": {"steps": 4, "seconds": 0.01}},
                "tokens_per_sec": {"prefill": 0.0, "decode": 100.0},
                "busy_fraction": 0.5}

    class _Replica(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            body = json.dumps(perf_doc).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *args):
            del args

    httpd = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Replica)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    live = f"http://127.0.0.1:{httpd.server_address[1]}"
    dead = f"http://127.0.0.1:{free_port()}"   # nothing listening

    policy = RoundRobinPolicy()
    policy.set_ready_replicas([live, dead])
    lb = lb_lib.run_load_balancer(free_port(), policy,
                                  lb_lib.RequestRecorder())
    try:
        lb_url = f"http://127.0.0.1:{lb.server_address[1]}"
        with urllib.request.urlopen(lb_url + "/perf",
                                    timeout=30) as resp:
            merged = json.loads(resp.read())
        assert merged["replicas"][live]["phases"]
        assert "error" in merged["replicas"][dead]
        assert merged["aggregate"]["replicas"] == 1   # healthy only
        assert merged["aggregate"]["errors"] == 1
        assert merged["aggregate"]["tokens_per_sec"]["decode"] == 100.0
    finally:
        lb.shutdown()
        httpd.shutdown()


def test_profile_endpoint_capture(armed, monkeypatch):
    import socket

    from skypilot_tpu.recipes import serve_llm

    import jax
    calls = {"start": None, "stop": 0, "python_tracer_level": None}

    class _FakeProfiler:
        ProfileOptions = jax.profiler.ProfileOptions
        # The engine thread names its phases on whatever profiler
        # there is.
        TraceAnnotation = jax.profiler.TraceAnnotation

        @staticmethod
        def start_trace(path, profiler_options):
            calls["start"] = path
            calls["python_tracer_level"] = \
                profiler_options.python_tracer_level

        @staticmethod
        def stop_trace():
            calls["stop"] += 1

    monkeypatch.setattr(jax, "profiler", _FakeProfiler)

    def free_port():
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            return s.getsockname()[1]

    cfg, params = _tiny_llm()
    port = free_port()
    # One slot: /profile does not wait for the engine's warm-up.
    httpd = serve_llm.serve(cfg, params, port, engine_slots=1)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        url = f"http://127.0.0.1:{port}/profile?seconds=0.05"
        req = urllib.request.Request(url, data=b"", method="POST")
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 202
            doc = json.loads(resp.read())
        assert doc["profile_dir"]
        deadline = time.time() + 10
        while time.time() < deadline and calls["stop"] == 0:
            time.sleep(0.02)
        assert calls["start"] == doc["profile_dir"]
        assert calls["stop"] == 1
        # An operator's trace never carries the Python tracer.
        assert calls["python_tracer_level"] == 0
        # Malformed seconds -> clean 400, not a crash.
        bad = urllib.request.Request(
            f"http://127.0.0.1:{port}/profile?seconds=abc",
            data=b"", method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=10)
        assert ei.value.code == 400
        ei.value.read()
    finally:
        httpd.shutdown()
        httpd.engine.shutdown()


# -------------------------------------------------------------- CLI bits
def test_perf_cli_requires_target(tmp_state_dir):
    from skypilot_tpu import cli
    out = CliRunner().invoke(cli.cli, ["perf"])
    assert out.exit_code != 0
    assert "--url" in out.output


def test_perf_cli_renders_url_snapshot(armed):
    import http.server
    import socketserver

    doc = {"armed": True, "ring_size": 64, "steps": 10,
           "total_steps": 10, "window_s": 1.0, "busy_fraction": 0.5,
           "phases": {"decode": {"steps": 10, "seconds": 0.5,
                                 "share": 1.0}},
           "tokens_per_sec": {"prefill": 0.0, "decode": 40.0},
           "occupancy": {"mean": 2.0, "last": 2}, "queue_depth": 0,
           "admissions": 3}

    class _Perf(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = json.dumps(doc).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    class _Srv(socketserver.ThreadingMixIn, http.server.HTTPServer):
        daemon_threads = True

    srv = _Srv(("127.0.0.1", 0), _Perf)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        from skypilot_tpu import cli
        out = CliRunner().invoke(
            cli.cli,
            ["perf", "--url",
             f"http://127.0.0.1:{srv.server_address[1]}"])
        assert out.exit_code == 0, out.output
        assert "decode" in out.output
        assert "busy 50.0%" in out.output
    finally:
        srv.shutdown()


def test_metrics_watch_rate_annotation():
    from skypilot_tpu.cli import (_annotate_counter_rates,
                                  _counter_samples)
    t0 = ("# HELP stpu_x_total x\n# TYPE stpu_x_total counter\n"
          "stpu_x_total 10\n"
          'stpu_y_total{code="200"} 4\n'
          "# HELP stpu_g g\n# TYPE stpu_g gauge\nstpu_g 7\n")
    # stpu_y_total belongs to stpu_x_total's TYPE block only if it
    # shares the prefix — it does not, so only stpu_x_total counts.
    prev = _counter_samples(t0)
    assert prev == {"stpu_x_total": 10.0}
    t1 = t0.replace("stpu_x_total 10", "stpu_x_total 30")
    out = _annotate_counter_rates(t1, prev, dt=2.0)
    assert "stpu_x_total 30  (+10/s)" in out
    assert "stpu_g 7\n" in out          # gauges untouched
    # Counter reset renders (reset), not a negative rate.
    t2 = t0.replace("stpu_x_total 10", "stpu_x_total 3")
    out = _annotate_counter_rates(t2, prev, dt=2.0)
    assert "stpu_x_total 3  (reset)" in out


def test_env_knobs_registered():
    from skypilot_tpu.utils import env_contract
    for knob in ("STPU_STEPSTATS", "STPU_STEPSTATS_RING",
                 "STPU_STEPSTATS_SYNC_EVERY"):
        assert knob in env_contract.REGISTRY
    assert env_contract.REGISTRY["STPU_STEPSTATS_RING"].default == \
        "1024"


# ------------------------------------------------- loadgen mono stamps
def test_metrics_scraper_monotonic_stamps(tmp_state_dir):
    import http.server
    import socketserver

    from skypilot_tpu.benchmark.loadgen import MetricsScraper
    from skypilot_tpu.observability import metrics

    class _Metrics(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = metrics.render().encode()
            self.send_response(200)
            self.send_header("Content-Type", metrics.CONTENT_TYPE)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    class _Srv(socketserver.ThreadingMixIn, http.server.HTTPServer):
        daemon_threads = True

    srv = _Srv(("127.0.0.1", 0), _Metrics)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    tmp_state_dir.mkdir(parents=True, exist_ok=True)
    series = tmp_state_dir / "metrics.jsonl"
    try:
        scraper = MetricsScraper(
            f"http://127.0.0.1:{srv.server_address[1]}",
            interval=60.0, series_path=series)
        scraper._t0 = time.perf_counter()
        assert scraper.scrape_once() is not None
        time.sleep(0.05)
        assert scraper.scrape_once() is not None
    finally:
        srv.shutdown()
    # Monotonic window: positive, and independent of wall clock.
    assert scraper.window_seconds() >= 0.04
    assert scraper.first_mono is not None
    assert scraper.last_mono > scraper.first_mono
    records = [json.loads(line)
               for line in series.read_text().splitlines()]
    assert all("mono" in r and "ts" in r for r in records)
    assert records[-1]["mono"] > records[0]["mono"]


# ----------------------------------------------------- overhead (slow)
@pytest.mark.slow
@pytest.mark.usefixtures("tmp_state_dir")
def test_engine_throughput_armed_vs_unarmed_within_noise():
    """Armed stepstats does O(1) host bookkeeping per supervisor-loop
    iteration, never per-token device work — tok/s must stay within
    noise of the unarmed engine (generous CPU-CI bound; the bench
    harness's phase-breakdown fields carry the TPU-side check)."""
    from skypilot_tpu.serve.decode_engine import DecodeEngine

    cfg, params = _tiny_llm()

    def run():
        engine = DecodeEngine(cfg, params, slots=4, max_seq=96,
                              prefill_chunk=16).start()
        try:
            engine.warmup()
            t0 = time.perf_counter()
            reqs = [engine.submit([1 + i, 2, 3, 4], max_tokens=24)
                    for i in range(8)]
            total = sum(len(r.result(timeout=600)) for r in reqs)
            return total / (time.perf_counter() - t0)
        finally:
            engine.shutdown()

    cold = run()                   # warm the jit caches once, discard
    del cold
    unarmed = run()
    stepstats.arm(ring=1024, sync_every=8)
    stepstats.reset()
    try:
        armed_rate = run()
        snap = stepstats.snapshot()
    finally:
        stepstats.disarm()
        stepstats.reset()
    assert snap["steps"] > 0       # the armed leg measured something
    assert armed_rate >= 0.5 * unarmed, (armed_rate, unarmed)
