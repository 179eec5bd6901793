"""Request analytics: wide-event records, tail-biased sampling, and
the capture→replay bridge into loadgen.

ISSUE 20 acceptance pinned here:
  * one streamed request through a real LB + replica + engine writes
    ONE joined JSONL record (LB half + engine half folded from the
    trailing ``stats`` SSE frame, which the client never sees);
  * at ``STPU_REQLOG_SAMPLE=0.01`` an injected error and an injected
    slow request BOTH still produce records (the tail is never
    sampled away);
  * disarmed, the LB proxy path and the engine submit path never
    reach the reqlog module past the ENABLED flag (monkeypatch-bomb
    pinned, mirror of the tracing/fault-injection guarantee);
  * capture → ``derive_spec`` → replay is deterministic (identical
    schedule digest across two derivations from the same records) and
    the replayed run reproduces the source run's prefix-cache hit
    rate within ±10% absolute.
"""
import dataclasses
import json
import socket
import threading
import time
import urllib.request

import pytest
from click.testing import CliRunner

from skypilot_tpu.observability import reqlog, tracing


@pytest.fixture
def rl_armed(tmp_state_dir):
    reqlog.arm(sample=1.0)
    yield tmp_state_dir
    reqlog.disarm()


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _tiny_llm():
    import jax

    from skypilot_tpu.models import llama
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    return cfg, params


# ------------------------------------------------------- sampling unit
def test_disarmed_writes_nothing(tmp_state_dir):
    assert not reqlog.ENABLED
    assert reqlog.write_record(
        {"request_id": reqlog.mint_id(), "status": "200"}) is False
    import pathlib
    assert not pathlib.Path(reqlog.requests_path()).exists()


def test_mint_id_shape():
    a, b = reqlog.mint_id(), reqlog.mint_id()
    assert a != b
    for rid in (a, b):
        assert len(rid) == 32
        assert int(rid, 16) >= 0    # pure hex, trace-id compatible


def test_keep_reason_contract(rl_armed):
    reqlog.arm(sample=1.0, slow_ttft=1.0, slow_e2e=10.0)
    ok = {"status": "200", "ttft_s": 0.05, "e2e_s": 0.5}
    assert reqlog.keep_reason(ok) is None
    assert reqlog.keep_reason({"status": "500"}) == "error"
    assert reqlog.keep_reason({"status": "upstream_aborted"}) == "error"
    assert reqlog.keep_reason(
        {"status": "200", "error": "boom"}) == "error"
    assert reqlog.keep_reason(
        {"status": "200", "resumed": True}) == "resumed"
    assert reqlog.keep_reason(
        {"status": "200", "ttft_s": 2.0}) == "slow_ttft"
    assert reqlog.keep_reason(
        {"status": "200", "ttft_s": 0.1, "e2e_s": 20.0}) == "slow_e2e"
    # error outranks slow: a failed request is kept as an error.
    assert reqlog.keep_reason({"status": "503", "ttft_s": 5.0}) == \
        "error"
    assert reqlog.is_slow({"ttft_s": 2.0})
    assert reqlog.is_slow({"e2e_s": 11.0})
    assert not reqlog.is_slow(ok)


def test_tail_biased_sampling_keeps_errors_and_slow(rl_armed):
    """The acceptance pin: at sample=0.01 plain successes are thinned
    but an injected error, an injected slow request, and a resumed
    stream ALWAYS land — tails are the point of a request log."""
    reqlog.arm(sample=0.01, slow_ttft=1.0, slow_e2e=10.0)
    kept = sum(
        1 for _ in range(300)
        if reqlog.write_record({"request_id": reqlog.mint_id(),
                                "status": "200", "ttft_s": 0.01,
                                "e2e_s": 0.05}))
    # P(>=30 keeps | n=300, p=0.01) is astronomically small.
    assert kept < 30
    err = {"request_id": reqlog.mint_id(), "status": "500"}
    slow = {"request_id": reqlog.mint_id(), "status": "200",
            "ttft_s": 5.0}
    resumed = {"request_id": reqlog.mint_id(), "status": "200",
               "ttft_s": 0.01, "resumed": True}
    assert reqlog.write_record(err) is True
    assert reqlog.write_record(slow) is True
    assert reqlog.write_record(resumed) is True
    assert err["keep"] == "error"
    assert slow["keep"] == "slow_ttft"
    assert resumed["keep"] == "resumed"
    recs = reqlog.read()
    by_id = {r["request_id"]: r for r in recs}
    assert by_id[err["request_id"]]["keep"] == "error"
    assert by_id[slow["request_id"]]["keep"] == "slow_ttft"
    assert by_id[resumed["request_id"]]["keep"] == "resumed"
    # Uniform-sample keeps carry NO keep marker (they are the
    # baseline, not a biased keep).
    assert all("keep" not in r for r in recs
               if r["status"] == "200" and not r.get("resumed")
               and not reqlog.is_slow(r))


def test_read_by_id_prefix(rl_armed):
    a = {"request_id": "aa" * 16, "status": "200"}
    b = {"request_id": "ab" * 16, "status": "200"}
    reqlog.write_record(a)
    reqlog.write_record(b)
    assert [r["request_id"] for r in reqlog.read(request_id="aa")] == \
        ["aa" * 16]
    # A shared prefix returns both — the CLI turns that into an
    # "ambiguous id" error.
    assert len(reqlog.read(request_id="a")) == 2
    assert reqlog.read(request_id="ff") == []


# ----------------------------------------------------------- e2e joined
@pytest.mark.usefixtures("tmp_state_dir")
def test_reqlog_e2e_joined_record():
    """One streamed request through real LB + replica + engine: the
    client sees tokens and [DONE] (never the stats frame); the log
    gets ONE joined record with both halves. A non-streamed request
    degrades to an LB-only record — engine halves ride SSE."""
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve.load_balancing_policies import \
        RoundRobinPolicy

    assert not tracing.ENABLED       # reqlog arms INDEPENDENTLY
    reqlog.arm(sample=1.0)
    cfg, params = _tiny_llm()
    ready = threading.Event()
    httpd = serve_llm.serve(cfg, params, 0, ready_event=ready,
                            engine_slots=2)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    assert ready.wait(timeout=300)
    replica = f"http://127.0.0.1:{httpd.server_address[1]}"
    policy = RoundRobinPolicy()
    policy.set_ready_replicas([replica])
    lb = lb_lib.run_load_balancer(0, policy, lb_lib.RequestRecorder())
    lb_url = f"http://127.0.0.1:{lb.server_address[1]}"

    def generate(payload):
        req = urllib.request.Request(
            lb_url + "/generate", data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        with urllib.request.urlopen(req, timeout=120) as resp:
            return resp.status, resp.read()

    try:
        status, body = generate({"prompt": [1, 2, 3], "max_tokens": 4,
                                 "stream": True})
        assert status == 200
        assert b"[DONE]" in body
        assert body.count(b'"token"') == 4
        # The engine half must NOT leak into the client stream.
        assert b"event: stats" not in body
        assert b"queue_wait_s" not in body

        rec = None
        deadline = time.time() + 20
        while time.time() < deadline:
            recs = [r for r in reqlog.read()
                    if r.get("path") == "/generate"
                    and r.get("stream")]
            if recs and "engine" in recs[0]:
                rec = recs[0]
                break
            time.sleep(0.05)
        assert rec is not None, "joined record never landed"

        # LB half.
        assert len(rec["request_id"]) == 32
        assert rec["method"] == "POST"
        assert rec["status"] == "200"
        assert rec["replica"] == replica
        assert rec["policy"] == "RoundRobinPolicy"
        assert rec["attempts"] == 1 and rec["retries"] == 0
        assert rec["resumed"] is False
        assert rec["trace_sampled"] is False     # tracing stayed off
        assert rec["prompt_tokens"] == 3
        assert rec["max_tokens"] == 4
        assert rec["stream"] is True
        assert len(rec["prefix_hash"]) == 16
        assert rec["e2e_s"] > 0
        assert rec["ttft_s"] is not None and rec["ttft_s"] >= 0
        assert rec["bytes_streamed"] > 0
        assert "keep" not in rec                 # plain success

        # Engine half (folded from the stripped stats frame).
        eng = rec["engine"]
        assert eng["prompt_tokens"] == 3
        assert eng["generated_tokens"] == 4
        assert eng["queue_wait_s"] is not None
        assert eng["device_time_s"] > 0
        assert eng["ttft_s"] is not None
        assert eng["outcome"] == "ok" and eng["error"] is None
        assert isinstance(eng["kv_quant"], bool)
        assert "kv_paged" not in eng      # one engine: nothing to say
        assert eng["restarts"] == 0

        # Non-streamed: the JSON response path has no SSE frame to
        # ride — the record degrades to LB-only, exactly like a
        # legacy replica.
        n_before = len(reqlog.read())
        status, body = generate({"prompt": [4, 5], "max_tokens": 2})
        assert status == 200
        assert len(json.loads(body)["tokens"]) == 2
        plain = None
        deadline = time.time() + 20
        while time.time() < deadline:
            recs = reqlog.read()
            if len(recs) > n_before:
                plain = [r for r in recs[n_before:]
                         if r.get("path") == "/generate"][0]
                break
            time.sleep(0.05)
        assert plain is not None
        assert plain["status"] == "200"
        assert plain["prompt_tokens"] == 2
        assert "engine" not in plain

        # The LB's admin surface: GET /requests serves the records so
        # `stpu requests SERVICE` works without shell access.
        with urllib.request.urlopen(lb_url + "/requests?limit=5",
                                    timeout=30) as resp:
            assert resp.status == 200
            served = json.loads(resp.read())
        assert {r["request_id"] for r in served} >= {
            rec["request_id"], plain["request_id"]}
    finally:
        reqlog.disarm()
        lb.shutdown()
        httpd.engine.shutdown()
        httpd.shutdown()


# ------------------------------------------------------ overhead guard
@pytest.mark.usefixtures("tmp_state_dir")
def test_reqlog_disarmed_zero_cost(monkeypatch):
    """With reqlog disarmed, the full LB proxy path and the engine
    submit/prefill/decode/free path never reach the reqlog module past
    the ENABLED flag — any mint/classify/write trips the bomb."""
    import http.server
    import socketserver

    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve.decode_engine import DecodeEngine
    from skypilot_tpu.serve.load_balancing_policies import \
        RoundRobinPolicy

    assert not reqlog.ENABLED

    def bomb(*args, **kwargs):
        raise AssertionError(
            "reqlog reached while disarmed (hot path must guard on "
            "reqlog.ENABLED)")

    monkeypatch.setattr(reqlog, "write_record", bomb)
    monkeypatch.setattr(reqlog, "mint_id", bomb)
    monkeypatch.setattr(reqlog, "keep_reason", bomb)

    class _Ok(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_GET(self):
            body = b"ok"
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    class _Srv(socketserver.ThreadingMixIn, http.server.HTTPServer):
        daemon_threads = True

    upstream = _Srv(("127.0.0.1", 0), _Ok)
    threading.Thread(target=upstream.serve_forever,
                     daemon=True).start()
    policy = RoundRobinPolicy()
    policy.set_ready_replicas(
        [f"http://127.0.0.1:{upstream.server_address[1]}"])
    lb = lb_lib.run_load_balancer(0, policy, lb_lib.RequestRecorder())
    try:
        url = f"http://127.0.0.1:{lb.server_address[1]}/x"
        with urllib.request.urlopen(url, timeout=10) as resp:
            assert resp.status == 200
    finally:
        lb.shutdown()
        upstream.shutdown()

    # Engine path: admission, chunked prefill, decode steps, slot free.
    cfg, params = _tiny_llm()
    engine = DecodeEngine(cfg, params, slots=2, max_seq=64,
                          prefill_chunk=8).start()
    try:
        toks = engine.submit([1, 2, 3], max_tokens=4).result(
            timeout=600)
        assert len(toks) == 4
    finally:
        engine.shutdown()


def test_jitted_steps_are_reqlog_free():
    """The jitted/batched compute functions — the per-token hot path —
    carry NO reqlog code even armed: the engine half is assembled at
    slot free, and the device-time share is accumulated host-side in
    the (unjitted) step driver under a guard."""
    import inspect

    from skypilot_tpu.serve import decode_engine
    for fn in (decode_engine._paged_step,
               decode_engine._paged_spec_step,
               decode_engine._paged_prefill_chunk,
               decode_engine._sample, decode_engine._sample_multi):
        assert "reqlog" not in inspect.getsource(fn), fn.__name__


@pytest.mark.slow
@pytest.mark.usefixtures("tmp_state_dir")
def test_engine_throughput_reqlog_armed_within_noise():
    """Armed reqlog costs one dict build per REQUEST (at slot free)
    plus one float add per step — decode throughput must stay within
    noise of the disarmed engine (generous CPU-CI bound)."""
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
    reqlog.arm(sample=1.0)
    try:
        armed = run()
    finally:
        reqlog.disarm()
    assert armed >= 0.5 * unarmed, (armed, unarmed)


# ------------------------------------------------- capture→replay e2e
@pytest.mark.usefixtures("tmp_state_dir")
def test_capture_derive_replay_reproduces_hit_rate(tmp_path):
    """The acceptance story: drive a real paged LB + engine with
    loadgen, capture the wide-event records, derive a spec, and replay
    the derived schedule against the SAME stack. Derivation is
    deterministic (identical digest twice, order-insensitive) and the
    replay reproduces the source run's prefix-cache hit rate within
    ±10% absolute."""
    from skypilot_tpu.benchmark import loadgen
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve.load_balancing_policies import \
        RoundRobinPolicy

    reqlog.arm(sample=1.0)
    cfg, params = _tiny_llm()
    ready = threading.Event()
    # One slot serializes admission: cold misses per prefix are
    # deterministic (exactly one), so the hit-rate comparison isn't
    # noised by concurrent same-prefix admissions racing the trie.
    httpd = serve_llm.serve(cfg, params, 0, ready_event=ready,
                            engine_slots=1)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    assert ready.wait(timeout=300)
    policy = RoundRobinPolicy()
    policy.set_ready_replicas(
        [f"http://127.0.0.1:{httpd.server_address[1]}"])
    lb = lb_lib.run_load_balancer(0, policy, lb_lib.RequestRecorder())
    lb_url = f"http://127.0.0.1:{lb.server_address[1]}"

    def hit_rate(records):
        halves = [r["engine"] for r in records if r.get("engine")]
        prompt = sum(h.get("prompt_tokens") or 0 for h in halves)
        cached = sum(h.get("cached_prompt_tokens") or 0
                     for h in halves)
        assert prompt > 0
        return cached / prompt

    try:
        # Source run: real traffic with prefix-reuse structure.
        src_spec = loadgen.LoadSpec(
            mix="chat", arrival="poisson", qps=12.0, duration_s=2.0,
            seed=3, n_prefixes=2, prompt_tokens=96, max_tokens=4,
            temperature=0.0, vocab=100)
        src_report = loadgen.run(
            lb_url, src_spec, out_dir=str(tmp_path / "src"),
            scrape_interval=1.0)
        assert src_report["source"] == "spec"
        assert src_report["requests"]["error"] == 0, src_report

        captured = [r for r in reqlog.read()
                    if r.get("path") == "/generate"]
        assert len(captured) >= 10
        n_before = len(reqlog.read())

        # Deterministic derivation: same records, any order →
        # identical spec → bit-identical schedule digest.
        d1 = loadgen.derive_spec(captured)
        d2 = loadgen.derive_spec(list(reversed(captured)))
        assert d1 == d2
        dig1 = loadgen.schedule_digest(loadgen.build_schedule(d1))
        dig2 = loadgen.schedule_digest(loadgen.build_schedule(d2))
        assert dig1 == dig2
        assert d1.mix == "chat"
        assert d1.n_prefixes == 2        # prefix structure recovered
        assert d1.max_tokens == 4

        # Replay: the records never carry prompt text, so prompts are
        # SYNTHESIZED — vocab is harness shaping (tiny model), pinned
        # AFTER the determinism assertions above.
        replay_spec = dataclasses.replace(d1, vocab=100)
        schedule = loadgen.build_schedule(replay_spec)
        sched_path = str(tmp_path / "schedule.json")
        digest = loadgen.save_schedule(sched_path, replay_spec,
                                       schedule)
        report = loadgen.run(
            lb_url, None, schedule_file=sched_path,
            out_dir=str(tmp_path / "replay"), scrape_interval=1.0)
        assert report["source"] == "schedule"
        assert report["schedule_sha256"] == digest
        assert report["requests"]["error"] == 0, report
        # Open-loop integrity surfaced either way.
        assert report["driver"]["lag_p99_s"] is not None

        replayed = [r for r in reqlog.read()[n_before:]
                    if r.get("path") == "/generate"]
        assert len(replayed) >= 10
        src_hit = hit_rate(captured)
        replay_hit = hit_rate(replayed)
        assert src_hit > 0           # the paged trie actually hit
        assert abs(src_hit - replay_hit) <= 0.10, \
            (src_hit, replay_hit)
    finally:
        reqlog.disarm()
        lb.shutdown()
        httpd.engine.shutdown()
        httpd.shutdown()


# --------------------------------------------------------- CLI surface
def test_cli_requests_and_capture(rl_armed, tmp_path):
    """`stpu requests` / `stpu requests show` / `stpu loadgen capture`
    over synthetic records: table + detail rendering, filters, and a
    derived schedule whose digest verifies on reload."""
    from skypilot_tpu import cli as cli_mod
    from skypilot_tpu.benchmark import loadgen

    base = 1700000000.0
    ids = []
    for i in range(24):
        rid = f"{i:02x}" * 16
        ids.append(rid)
        rec = {
            "request_id": rid, "ts": base + i * 0.25,
            "method": "POST", "path": "/generate",
            "trace_sampled": False, "replica": "http://r1:9000",
            "policy": "RoundRobinPolicy", "attempts": 1, "retries": 0,
            "resumed": False, "status": "200",
            "ttft_s": 0.02, "e2e_s": 0.3, "bytes_streamed": 512,
            "prompt_tokens": 80 + (i % 5), "max_tokens": 8,
            "temperature": 0.0, "stream": True,
            "prefix_hash": ("aa" * 8 if i % 2 else "bb" * 8),
        }
        if i == 3:
            rec["status"] = "503"
            rec["engine"] = {"queue_wait_s": 0.001,
                             "prompt_tokens": 83,
                             "cached_prompt_tokens": 64,
                             "generated_tokens": 8,
                             "outcome": "error", "error": "boom"}
        if i == 5:
            rec["ttft_s"] = 3.0
        assert reqlog.write_record(rec)

    runner = CliRunner()
    result = runner.invoke(cli_mod.cli, ["requests", "--limit", "50"])
    assert result.exit_code == 0, result.output
    assert ids[0][:8] in result.output
    assert "REQUEST" in result.output and "TTFT" in result.output
    assert "error" in result.output        # keep column for the 503

    result = runner.invoke(cli_mod.cli,
                           ["requests", "--status", "503"])
    assert result.exit_code == 0, result.output
    assert ids[3][:8] in result.output
    assert ids[4][:8] not in result.output

    result = runner.invoke(cli_mod.cli, ["requests", "--slow"])
    assert result.exit_code == 0, result.output
    assert ids[5][:8] in result.output
    assert ids[4][:8] not in result.output

    result = runner.invoke(cli_mod.cli, ["requests", "--json",
                                         "--limit", "50"])
    assert result.exit_code == 0, result.output
    parsed = [json.loads(line)
              for line in result.output.splitlines() if line]
    assert len(parsed) == 24                 # JSONL, one per record

    # Detail view: engine sub-block when joined, degradation note
    # when LB-only.
    result = runner.invoke(cli_mod.cli,
                           ["requests", "show", ids[3][:10]])
    assert result.exit_code == 0, result.output
    assert "engine" in result.output
    assert "queue_wait_s" in result.output
    result = runner.invoke(cli_mod.cli,
                           ["requests", "show", ids[4][:10]])
    assert result.exit_code == 0, result.output
    assert "LB-only" in result.output

    # capture → schedule.json: digest echoed, reload verifies, and a
    # second derivation pins the identical digest.
    out = str(tmp_path / "schedule.json")
    result = runner.invoke(cli_mod.cli, [
        "loadgen", "capture",
        "--from", str(reqlog.requests_path()), "--out", out])
    assert result.exit_code == 0, result.output
    spec, schedule, digest = loadgen.load_schedule(out)
    assert digest[:12] in result.output
    assert spec.n_prefixes == 2
    out2 = str(tmp_path / "schedule2.json")
    result = runner.invoke(cli_mod.cli, [
        "loadgen", "capture",
        "--from", str(reqlog.requests_path()), "--out", out2])
    assert result.exit_code == 0, result.output
    assert loadgen.load_schedule(out2)[2] == digest
