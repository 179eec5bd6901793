"""Distributed tracing: span subsystem + end-to-end trace reassembly.

ISSUE 5 acceptance pinned here:
  * one request driven LB -> replica -> decode engine with tracing
    armed reassembles into a SINGLE trace tree: LB root carrying
    retry/policy annotations, replica child, engine queue/prefill/
    decode grandchildren;
  * ``stpu trace export --perfetto`` on that trace emits Chrome
    trace-event JSON with ph/ts/dur/pid/tid fields;
  * unarmed, the LB request path and the engine step never touch the
    tracing module beyond the ENABLED flag check (mirror of the
    fault-injection zero-cost guarantee).
"""
import json
import socket
import threading
import time
import urllib.request

import pytest
from click.testing import CliRunner

from skypilot_tpu.observability import tracing


@pytest.fixture
def armed(tmp_state_dir):
    tracing.arm(sample=1.0)
    yield tmp_state_dir
    tracing.disarm()


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


# ------------------------------------------------------------- span unit
def test_span_lifecycle_and_record(armed):
    span = tracing.start_span("unit.root", kind="test",
                              attrs={"k": "v"})
    span.event("mark", detail=1)
    span.set_attr("k2", 2)
    with tracing.start_span("unit.child", parent=span) as child:
        child_id = child.span_id
    span.end(status="ok", bytes=7)
    span.end(status="error")   # idempotent: second end is a no-op
    recs = tracing.read(trace_id=span.trace_id)
    assert len(recs) == 2
    by_name = {r["name"]: r for r in recs}
    root = by_name["unit.root"]
    assert root["parent_id"] is None
    assert root["status"] == "ok"                 # not overwritten
    assert root["attrs"] == {"k": "v", "k2": 2, "bytes": 7}
    assert root["dur"] >= 0
    assert root["events"][0]["name"] == "mark"
    assert root["events"][0]["at"] >= 0
    assert root["run_id"]
    child = by_name["unit.child"]
    assert child["parent_id"] == root["span_id"]
    assert child["span_id"] == child_id
    assert child["trace_id"] == root["trace_id"]


def test_context_wire_roundtrip():
    ctx = tracing.SpanContext("ab" * 16, "cd" * 8, True)
    wire = tracing.format_ctx(ctx)
    back = tracing.parse_ctx(wire)
    assert (back.trace_id, back.span_id, back.sampled) == \
        (ctx.trace_id, ctx.span_id, True)
    unsampled = tracing.parse_ctx(tracing.format_ctx(
        tracing.SpanContext("ab" * 16, "cd" * 8, False)))
    assert unsampled.sampled is False
    # Garbage never raises — a hostile header must not 500 the LB.
    for bad in (None, "", "zz", "deadbeef-cafe-01", "x" * 200):
        assert tracing.parse_ctx(bad) is None
    assert tracing.extract({tracing.HEADER: wire}).span_id == \
        ctx.span_id
    assert tracing.extract({}) is None


def test_env_carrier_and_adoption(armed, monkeypatch):
    monkeypatch.setenv(tracing.ENV_CTX, "sentinel")  # restored after
    span = tracing.start_span("launch.root", kind="jobs")
    tracing.set_env_context(span.context())
    got = tracing.from_env()
    assert got.trace_id == span.trace_id
    assert got.span_id == span.span_id
    child_env = tracing.child_env()
    assert child_env[tracing.ENABLE_ENV] == "1"
    assert tracing.parse_ctx(child_env[tracing.ENV_CTX]).trace_id == \
        span.trace_id
    span.end()
    # adopt_ctx (gang-driver side): a spec-carried context re-arms
    # tracing and re-exports the env for the driver's own children.
    tracing.disarm()
    ctx = tracing.adopt_ctx(tracing.format_ctx(span.context()))
    assert tracing.ENABLED and ctx.trace_id == span.trace_id
    assert tracing.from_env().span_id == span.span_id
    # Junk never arms.
    tracing.disarm()
    assert tracing.adopt_ctx("not-a-context") is None
    assert not tracing.ENABLED


def test_sampling_root_decision_child_inheritance(armed):
    tracing.arm(sample=0.0)
    # An unsampled root records nothing but still CARRIES the negative
    # decision: its context serializes with the 00 flag, so the next
    # hop (armed replica) does NOT open its own root — traces are
    # whole or absent, never torn at a process boundary.
    root = tracing.start_span("unsampled.root")
    ctx = root.context()
    assert ctx is not None and ctx.sampled is False
    assert tracing.format_ctx(ctx).endswith("-00")
    root.event("e")
    root.end()
    child = tracing.start_span("downstream.hop", parent=ctx)
    assert child.context().sampled is False       # decision inherited
    assert child.context().trace_id == ctx.trace_id
    child.end()
    tracing.record_span("downstream.phase", "test", child.context(),
                        start_mono=0.0, end_mono=1.0)
    import pathlib
    assert not pathlib.Path(tracing.trace_path()).exists()
    # A sampled inbound context overrides the local rate the same way:
    # the decision was made at the root, the trace must stay whole.
    inbound = tracing.SpanContext("ef" * 16, "ab" * 8, True)
    span = tracing.start_span("sampled.child", parent=inbound)
    assert span is not tracing.NOOP
    span.end()
    assert tracing.read(trace_id="ef" * 16)


def test_disabled_writes_nothing(tmp_state_dir):
    assert not tracing.ENABLED
    span = tracing.start_span("off.root")
    assert span is tracing.NOOP
    span.event("e")
    span.end()
    tracing.record_span("off.retro", "test", None, start_mono=0.0)
    import pathlib
    assert not pathlib.Path(tracing.trace_path()).exists()


def test_record_span_retroactive(armed):
    parent = tracing.start_span("retro.parent")
    t0 = time.perf_counter()
    time.sleep(0.02)
    t1 = time.perf_counter()
    tracing.record_span("retro.phase", "test", parent.context(),
                        start_mono=t0, end_mono=t1,
                        attrs={"n": 3}, events=[{"name": "e", "at": 0}])
    parent.end()
    recs = tracing.read(trace_id=parent.trace_id)
    phase = next(r for r in recs if r["name"] == "retro.phase")
    assert abs(phase["dur"] - (t1 - t0)) < 1e-6
    assert phase["parent_id"] == parent.span_id
    # Reconstructed wall start sits inside the parent's window.
    root = next(r for r in recs if r["name"] == "retro.parent")
    assert root["ts"] - 0.5 <= phase["ts"] <= root["ts"] + root["dur"]


def test_assemble_orphans_surface_as_roots(armed):
    span = tracing.start_span("orphan.child", parent=tracing.SpanContext(
        "aa" * 16, "bb" * 8, True))
    span.end()
    roots = tracing.assemble("aa" * 16)
    assert len(roots) == 1                 # parent record never landed
    assert roots[0]["span"]["name"] == "orphan.child"


# ----------------------------------------------------- launch carriers
def test_gang_env_carries_trace_context(armed, monkeypatch):
    """The gang driver's host environments carry STPU_TRACE_CTX +
    STPU_TRACE (the STPU_RUN_ID pattern), so job-side spans nest under
    the gang span; unarmed, the host env is untouched."""
    monkeypatch.setenv(tracing.ENV_CTX, "placeholder")  # restored
    from skypilot_tpu.agent import gang_exec
    span = tracing.start_span("gang.run", kind="gang")
    tracing.set_env_context(span.context())
    spec = {"node_ips": ["10.0.0.1", "10.0.0.2"],
            "hosts": [{"kind": "ssh"}, {"kind": "ssh"}],
            "task_id": "t1", "cluster_name": "c1",
            "envs": {}}
    env = gang_exec._build_env(spec, rank=1)
    assert env[tracing.ENABLE_ENV] == "1"
    assert tracing.parse_ctx(env[tracing.ENV_CTX]).span_id == \
        span.span_id
    span.end()
    # The backend stamps the same context into the gang job spec.
    from skypilot_tpu.observability import tracing as t2
    assert t2.env_context() == tracing.format_ctx(span.context())
    tracing.disarm()
    assert tracing.env_context() is None      # stale env can't leak
    env = gang_exec._build_env(spec, rank=0)
    assert tracing.ENABLE_ENV not in env
    assert tracing.ENV_CTX not in env


# ----------------------------------------------------------- e2e + CLI
def _walk(nodes):
    for node in nodes:
        yield node
        yield from _walk(node["children"])


@pytest.mark.usefixtures("tmp_state_dir")
def test_trace_e2e_lb_replica_engine():
    """The acceptance story: request → LB (dead replica first: retry)
    → live replica → decode engine, reassembled into ONE tree; then
    `stpu trace export --perfetto` on it."""
    from skypilot_tpu import cli as cli_mod
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve.load_balancing_policies import \
        RoundRobinPolicy

    tracing.arm(sample=1.0)
    cfg, params = _tiny_llm()
    ready = threading.Event()
    httpd = serve_llm.serve(cfg, params, 0, ready_event=ready)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    assert ready.wait(timeout=300)
    replica = f"http://127.0.0.1:{httpd.server_address[1]}"
    dead = f"http://127.0.0.1:{_free_port()}"
    policy = RoundRobinPolicy()
    # Dead replica FIRST: round-robin's first pick fails pre-first-byte
    # and the retry lands on the live one — a real retry annotation.
    policy.set_ready_replicas([dead, replica])
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
        status, body = generate({"prompt": [1, 2, 3], "max_tokens": 4})
        assert status == 200
        assert len(json.loads(body)["tokens"]) == 4

        # Span records land as each side's span ENDS (the LB root and
        # replica span close after the response bytes are out) — poll
        # for the complete tree.
        tree = None
        deadline = time.time() + 20
        while time.time() < deadline:
            rows = [r for r in tracing.list_traces()
                    if r["name"] == "lb.request"]
            if rows:
                roots = tracing.assemble(rows[0]["trace_id"])
                if sum(1 for _ in _walk(roots)) >= 6:
                    tree = roots
                    break
            time.sleep(0.05)
        assert tree is not None, "trace never completed"
        assert len(tree) == 1                    # a SINGLE tree
        root = tree[0]["span"]
        assert root["name"] == "lb.request"
        assert root["parent_id"] is None
        assert root["attrs"]["code"] == "200"

        # Retry + policy annotations on the LB root.
        ev = root["events"]
        names = [e["name"] for e in ev]
        assert "retry" in names and "upstream_failed" in names
        selects = [e for e in ev if e["name"] == "select"]
        assert [s["target"] for s in selects] == [dead, replica]
        assert selects[0]["policy"] == "RoundRobinPolicy"
        assert selects[1]["attempt"] == 1

        # Replica child, engine grandchildren.
        gen = [c for c in tree[0]["children"]
               if c["span"]["name"] == "replica.generate"]
        assert len(gen) == 1
        assert gen[0]["span"]["attrs"]["prompt_tokens"] == 3
        engine_spans = {c["span"]["name"]: c["span"]
                       for c in gen[0]["children"]}
        assert {"engine.queue", "engine.prefill",
                "engine.decode"} <= set(engine_spans)
        assert engine_spans["engine.prefill"]["attrs"][
            "steps_to_first_token"] >= 1
        assert engine_spans["engine.decode"]["attrs"]["tokens"] == 4
        # Every span shares the one trace id.
        assert all(n["span"]["trace_id"] == root["trace_id"]
                   for n in _walk(tree))

        # Critical path runs root -> replica -> an engine span.
        cp = tracing.critical_path(tree[0])
        assert cp[0] == root["span_id"]
        assert len(cp) == 3

        # A streamed request additionally records stream delivery.
        status, body = generate({"prompt": [1, 2, 3], "max_tokens": 3,
                                 "stream": True})
        assert status == 200 and b"[DONE]" in body
        deadline = time.time() + 20
        stream_rec = stream_tree = None
        while time.time() < deadline:
            recs = [r for r in tracing.read()
                    if r["name"] == "replica.stream"]
            if recs:
                stream_rec = recs[0]
                # The LB root lands last (it ends after the replica) —
                # wait for the tree to be complete.
                roots = tracing.assemble(stream_rec["trace_id"])
                if len(roots) == 1 and \
                        roots[0]["span"]["name"] == "lb.request":
                    stream_tree = roots
                    break
            time.sleep(0.05)
        assert stream_rec is not None
        assert stream_rec["attrs"]["tokens"] == 3
        assert stream_tree is not None, "stream trace never completed"

        # ------------------------------------------------ CLI surface
        runner = CliRunner()
        result = runner.invoke(cli_mod.cli, ["trace", "list"])
        assert result.exit_code == 0, result.output
        assert root["trace_id"] in result.output

        # Abbreviated id + indented tree + critical-path marker.
        result = runner.invoke(
            cli_mod.cli,
            ["trace", "show", root["trace_id"][:10], "--events"])
        assert result.exit_code == 0, result.output
        assert "lb.request" in result.output
        assert "  replica.generate" in result.output   # indented child
        assert "engine.prefill" in result.output
        assert "*" in result.output                    # critical path
        assert "retry" in result.output                # annotation

        # Perfetto export: Chrome trace-event JSON with the fields
        # chrome://tracing validates (ph/ts/dur/pid/tid).
        result = runner.invoke(
            cli_mod.cli,
            ["trace", "export", "--perfetto", root["trace_id"]])
        assert result.exit_code == 0, result.output
        doc = json.loads(result.output)
        events = doc["traceEvents"]
        assert events
        for e in events:
            assert e["ph"] in ("X", "i")
            assert isinstance(e["ts"], (int, float))
            assert isinstance(e["pid"], int)
            assert isinstance(e["tid"], int)
            assert e["name"]
        complete = [e for e in events if e["ph"] == "X"]
        assert {e["name"] for e in complete} >= {
            "lb.request", "replica.generate", "engine.queue",
            "engine.prefill", "engine.decode"}
        assert all(isinstance(e["dur"], (int, float)) and e["dur"] >= 0
                   for e in complete)
        # Span annotations ride along as instant events.
        assert any(e["name"] == "lb.request.retry" for e in events)
    finally:
        tracing.disarm()
        lb.shutdown()
        httpd.engine.shutdown()
        httpd.shutdown()


# ------------------------------------------------------ overhead guard
@pytest.mark.usefixtures("tmp_state_dir")
def test_tracing_unarmed_zero_cost(monkeypatch):
    """Mirror of the fault-injection zero-cost guarantee: with tracing
    unarmed, the full LB proxy path and the engine submit/prefill/
    decode path never reach the tracing module past the ENABLED flag —
    any start_span/record_span call trips the monkeypatched bomb."""
    import http.server
    import socketserver

    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve.decode_engine import DecodeEngine
    from skypilot_tpu.serve.load_balancing_policies import \
        RoundRobinPolicy

    assert not tracing.ENABLED

    def bomb(*args, **kwargs):
        raise AssertionError(
            "tracing reached while unarmed (hot path must guard on "
            "tracing.ENABLED)")

    monkeypatch.setattr(tracing, "start_span", bomb)
    monkeypatch.setattr(tracing, "record_span", bomb)

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


def test_engine_step_is_tracing_free():
    """The batched decode step — the per-token hot path — carries NO
    tracing code even when armed: engine spans ride request edges
    (admission, prefill completion, slot free), never the step."""
    import inspect

    from skypilot_tpu.serve import decode_engine
    assert "tracing" not in inspect.getsource(
        decode_engine.DecodeEngine._decode_step)
    assert "tracing" not in inspect.getsource(decode_engine._paged_step)


@pytest.mark.slow
@pytest.mark.usefixtures("tmp_state_dir")
def test_engine_throughput_armed_vs_unarmed_within_noise():
    """Armed tracing records a handful of spans per REQUEST, never
    per-token work — decode throughput must stay within noise of the
    unarmed engine (generous CPU-CI bound; the bench harness's
    measure_engine_ragged reports `traced` for the TPU-side check)."""
    from skypilot_tpu.serve.decode_engine import DecodeEngine

    cfg, params = _tiny_llm()

    def run(trace_root):
        engine = DecodeEngine(cfg, params, slots=4, max_seq=96,
                              prefill_chunk=16).start()
        try:
            engine.warmup()
            t0 = time.perf_counter()
            reqs = [engine.submit([1 + i, 2, 3, 4], max_tokens=24,
                                  trace=trace_root)
                    for i in range(8)]
            total = sum(len(r.result(timeout=600)) for r in reqs)
            return total / (time.perf_counter() - t0)
        finally:
            engine.shutdown()

    cold = run(None)               # warm the jit caches once, discard
    del cold
    unarmed = run(None)
    tracing.arm(sample=1.0)
    try:
        root = tracing.start_span("bench.root", kind="bench")
        armed = run(root.context())
        root.end()
    finally:
        tracing.disarm()
    # Spans were actually recorded (the armed leg measured something).
    assert any(r["name"] == "engine.decode" for r in tracing.read())
    assert armed >= 0.5 * unarmed, (armed, unarmed)
