"""Fault-tolerant serving: chaos tests driven by the deterministic
fault-injection harness (skypilot_tpu/utils/fault_injection.py).

The stories pinned here (ISSUE 4 acceptance):
  * a pre-first-byte replica failure is retried on another replica —
    the client sees a complete 200, never a 502, and the circuit
    breaker ejects the dead replica ahead of the controller's probes;
  * an engine-loop crash flips the replica /health endpoint to 503,
    the supervisor restarts the engine with fresh state, and traffic
    recovers;
  * scaling down a replica with an in-flight token stream completes
    that stream before termination (graceful drain);
plus the satellites: aborted-stream accounting, the LB body cap, probe
anti-flap, and the swallowed-exception lint.
"""
import http.client
import http.server
import json
import socket
import socketserver
import threading
import time
import urllib.error
import urllib.request

import pytest

from skypilot_tpu.serve import load_balancer as lb_lib
from skypilot_tpu.serve.load_balancing_policies import (
    PrefixAffinityPolicy, RoundRobinPolicy)
from skypilot_tpu.utils import fault_injection as fi


@pytest.fixture(autouse=True)
def _clear_faults():
    fi.clear()
    yield
    fi.clear()


# ------------------------------------------------------------ fixtures
class _Server(socketserver.ThreadingMixIn, http.server.HTTPServer):
    daemon_threads = True
    allow_reuse_address = True

    def handle_error(self, request, client_address):
        pass    # mid-stream deaths are intentional here; keep CI quiet


class _OkHandler(http.server.BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    hits = None     # set per test to a list

    def log_message(self, *a):
        pass

    def _ok(self):
        if self.hits is not None:
            self.hits.append(self.path)
        body = b'{"ok": true}'
        self.send_response(200)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    do_GET = _ok

    def do_POST(self):
        length = int(self.headers.get("Content-Length") or 0)
        self.rfile.read(length)
        self._ok()


def _start(handler_cls):
    server = _Server(("127.0.0.1", 0), handler_cls)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, f"http://127.0.0.1:{server.server_address[1]}"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read()


def _get_code(url, timeout=10):
    try:
        return _get(url, timeout=timeout)[0]
    except urllib.error.HTTPError as e:
        return e.code


# ================================================== fault-injection unit
def test_fault_spec_parse_and_modes():
    rules = fi.parse_spec(
        "lb.upstream:error:p=0.5;engine.step:raise:times=1;"
        "replica.probe:delay:s=0.01")
    by_point = {r.point: r for r in rules}
    assert by_point["lb.upstream"].p == 0.5
    assert by_point["engine.step"].times == 1
    assert by_point["replica.probe"].mode == "delay"
    for bad in ("engine.step", "x:explode", "x:raise:p=nope",
                "x:raise:frobnicate=1"):
        with pytest.raises(fi.FaultSpecError):
            fi.parse_spec(bad)


def test_fire_times_budget_and_enabled_flag():
    assert not fi.ENABLED
    fi.fire("engine.step")           # unarmed: no-op
    fi.activate("engine.step", times=2)
    assert fi.ENABLED
    for _ in range(2):
        with pytest.raises(fi.InjectedFault):
            fi.fire("engine.step")
    fi.fire("engine.step")           # budget exhausted: no-op
    assert fi.fires("engine.step") == 2
    fi.clear()
    assert not fi.ENABLED


def test_injected_fault_is_connection_error():
    # The choke points sit behind except-clauses that catch
    # connection-shaped failures; injection must ride the SAME path.
    assert issubclass(fi.InjectedFault, ConnectionError)


def test_probabilistic_faults_reproducible_under_seed():
    def pattern():
        fi.configure("p.test:raise:p=0.5", seed=1234)
        out = []
        for _ in range(32):
            try:
                fi.fire("p.test")
                out.append(0)
            except fi.InjectedFault:
                out.append(1)
        return out

    first, second = pattern(), pattern()
    assert first == second              # seeded chaos replays exactly
    assert 0 < sum(first) < 32          # and actually mixes outcomes
    fi.configure("p.test:raise:p=0.5", seed=99)
    third = []
    for _ in range(32):
        try:
            fi.fire("p.test")
            third.append(0)
        except fi.InjectedFault:
            third.append(1)
    assert third != first               # a new seed is a new run


# ====================================================== policy exclusion
def test_round_robin_exclusion():
    p = RoundRobinPolicy()
    p.set_ready_replicas(["http://a", "http://b"])
    assert p.select_replica(exclude={"http://a"}) == "http://b"
    assert p.select_replica(exclude={"http://a", "http://b"}) is None
    # No exclusion: still rotates.
    got = {p.select_replica() for _ in range(4)}
    assert got == {"http://a", "http://b"}


def test_prefix_affinity_exclusion_deterministic():
    p = PrefixAffinityPolicy()
    urls = [f"http://r{i}" for i in range(3)]
    p.set_ready_replicas(urls)
    req = {"path": "/generate",
           "body": json.dumps({"prompt": list(range(64)),
                               "max_tokens": 4}).encode()}
    owner = p.select_replica(req)
    p.report_done(owner)
    alt1 = p.select_replica(req, exclude={owner})
    p.report_done(alt1)
    alt2 = p.select_replica(req, exclude={owner})
    p.report_done(alt2)
    assert alt1 == alt2 != owner     # retries spill deterministically
    assert p.select_replica(req, exclude=set(urls)) is None
    # Excluded selections must not leak in-flight slots.
    assert all(v == 0 for v in p._inflight.values())


# ================================================= circuit breaker unit
def test_circuit_breaker_state_machine():
    br = lb_lib.CircuitBreaker(threshold=2, backoff_base=0.05,
                               backoff_cap=0.05, jitter=0.0, seed=7)
    url = "http://r1"
    br.record_failure(url)
    assert br.state(url) == "closed"
    br.record_failure(url)
    assert br.state(url) == "open"          # threshold hit: ejected
    assert br.blocked([url]) == {url}
    time.sleep(0.08)
    assert br.blocked([url]) == set()       # backoff over: half-open
    assert br.state(url) == "half_open"
    br.record_failure(url)                  # failed probe: re-open
    assert br.state(url) == "open"
    time.sleep(0.12)                        # doubled backoff (capped)
    assert br.blocked([url]) == set()
    br.record_success(url)
    assert br.state(url) == "closed"        # full cycle closed again
    # The whole cycle is observable in the exposition.
    from skypilot_tpu.observability import metrics
    assert 'stpu_lb_breaker_state{replica="http://r1"} 0' \
        in metrics.render()
    assert lb_lib._BREAKER_EJECTIONS.labels(replica=url).get() >= 1
    br.prune([])
    assert br.state(url) == "closed"


# ======================================================== LB retry e2e
def test_lb_retries_dead_replica_and_breaker_ejects():
    """Acceptance (a): with one dead replica in rotation every request
    still completes 200 via retry; after the failure threshold the
    breaker ejects the dead replica so later requests don't even pay
    the failed connect."""
    hits = []
    handler = type("H", (_OkHandler,), {"hits": hits})
    server, ok_url = _start(handler)
    dead = f"http://127.0.0.1:{_free_port()}"
    policy = RoundRobinPolicy()
    policy.set_ready_replicas([ok_url, dead])
    lb = lb_lib.run_load_balancer(0, policy, lb_lib.RequestRecorder())
    lb.breaker.threshold = 2
    lb.breaker.backoff_base = 30.0        # stays open for the test
    retries0 = lb_lib._RETRIES.get()
    try:
        for _ in range(8):
            status, body = _get(
                f"http://127.0.0.1:{lb.server_address[1]}/x")
            assert status == 200 and json.loads(body) == {"ok": True}
        assert lb_lib._RETRIES.get() > retries0
        assert lb.breaker.state(dead) == "open"
        assert lb_lib._BREAKER_EJECTIONS.labels(
            replica=dead).get() >= 1
        # Ejected: requests stop trying the dead replica entirely.
        r1 = lb_lib._RETRIES.get()
        for _ in range(4):
            status, _ = _get(
                f"http://127.0.0.1:{lb.server_address[1]}/x")
            assert status == 200
        assert lb_lib._RETRIES.get() == r1
        # Breaker + retry families ride the LB's own /metrics.
        _, text = _get(
            f"http://127.0.0.1:{lb.server_address[1]}/metrics")
        text = text.decode()
        assert f'stpu_lb_breaker_state{{replica="{dead}"}} 1' in text
        assert "stpu_lb_upstream_retries_total" in text
        assert "stpu_lb_breaker_ejections_total" in text
    finally:
        lb.shutdown()
        server.shutdown()


def test_lb_breaker_half_open_readmits_recovered_replica():
    hits = []
    handler = type("H", (_OkHandler,), {"hits": hits})
    server, ok_url = _start(handler)
    port = _free_port()
    flaky = f"http://127.0.0.1:{port}"
    policy = RoundRobinPolicy()
    policy.set_ready_replicas([ok_url, flaky])
    lb = lb_lib.run_load_balancer(0, policy, lb_lib.RequestRecorder())
    lb.breaker.threshold = 2
    lb.breaker.backoff_base = 0.2
    lb.breaker.backoff_cap = 0.2
    revived = None
    try:
        for _ in range(8):
            assert _get(
                f"http://127.0.0.1:{lb.server_address[1]}/x")[0] == 200
        assert lb.breaker.state(flaky) == "open"
        # The replica comes back on the same port; after the backoff a
        # half-open probe (live traffic) closes the circuit.
        revived = _Server(("127.0.0.1", port), handler)
        threading.Thread(target=revived.serve_forever,
                         daemon=True).start()
        time.sleep(0.3)
        deadline = time.time() + 10
        while time.time() < deadline:
            assert _get(
                f"http://127.0.0.1:{lb.server_address[1]}/x")[0] == 200
            if lb.breaker.state(flaky) == "closed":
                break
            time.sleep(0.05)
        assert lb.breaker.state(flaky) == "closed"
    finally:
        lb.shutdown()
        server.shutdown()
        if revived is not None:
            revived.shutdown()


def test_lb_retries_503_when_peer_available():
    """A draining/warming replica answers 503; with a healthy peer in
    rotation the LB re-routes instead of passing the 503 through (the
    drain-gap closer); with NO healthy peer the 503 passes through."""

    class _Unavailable(_OkHandler):
        def _ok(self):
            body = b'{"error": "draining"}'
            self.send_response(503)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        do_GET = _ok

    bad_server, bad_url = _start(_Unavailable)
    ok_server, ok_url = _start(type("H", (_OkHandler,), {}))
    policy = RoundRobinPolicy()
    policy.set_ready_replicas([bad_url, ok_url])
    lb = lb_lib.run_load_balancer(0, policy, lb_lib.RequestRecorder())
    try:
        for _ in range(4):
            assert _get(
                f"http://127.0.0.1:{lb.server_address[1]}/x")[0] == 200
    finally:
        lb.shutdown()
    policy2 = RoundRobinPolicy()
    policy2.set_ready_replicas([bad_url])
    lb2 = lb_lib.run_load_balancer(0, policy2, lb_lib.RequestRecorder())
    try:
        assert _get_code(
            f"http://127.0.0.1:{lb2.server_address[1]}/x") == 503
    finally:
        lb2.shutdown()
        bad_server.shutdown()
        ok_server.shutdown()


# ============================================ aborted-stream accounting
def test_lb_mid_stream_death_counts_aborted_and_returns_slot():
    """Satellite: a replica dying MID-stream is recorded as
    code="upstream_aborted" (not a clean 200, and not the
    client_closed code — the REPLICA died, the client was still
    there), is NOT retried (the status line already went out; this is
    a GET, so the stream journal doesn't apply either), and
    report_done still returns the in-flight slot."""

    class _DieMidStream(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        def do_GET(self):
            self.send_response(200)
            self.send_header("Content-Type", "text/event-stream")
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            data = b"data: one\n\n"
            self.wfile.write(f"{len(data):x}\r\n".encode())
            self.wfile.write(data + b"\r\n")
            self.wfile.flush()
            # Die without the chunked terminator: an abrupt close the
            # LB sees as IncompleteRead mid-body.
            try:
                self.connection.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            self.close_connection = True

    class _Recording(RoundRobinPolicy):
        def __init__(self):
            super().__init__()
            self.done = []

        def report_done(self, url):
            self.done.append(url)

    server, url = _start(_DieMidStream)
    policy = _Recording()
    policy.set_ready_replicas([url])
    lb = lb_lib.run_load_balancer(0, policy, lb_lib.RequestRecorder())
    lb.breaker.threshold = 1       # one mid-stream death must eject
    aborted0 = lb_lib._REQUESTS.labels(method="GET",
                                       code="upstream_aborted").get()
    ok0 = lb_lib._REQUESTS.labels(method="GET", code="200").get()
    retries0 = lb_lib._RETRIES.get()
    try:
        conn = http.client.HTTPConnection(
            "127.0.0.1", lb.server_address[1], timeout=10)
        conn.request("GET", "/stream")
        resp = conn.getresponse()
        assert resp.status == 200      # the 2xx line DID go out
        got = b""
        with pytest.raises((http.client.HTTPException, ConnectionError,
                            OSError)):
            while True:
                chunk = resp.read1(65536)
                if not chunk:
                    # Truncated chunked stream surfaces as an error on
                    # some paths and a short read on others; normalize.
                    raise http.client.IncompleteRead(got)
                got += chunk
        conn.close()
        deadline = time.time() + 5
        while time.time() < deadline and lb_lib._REQUESTS.labels(
                method="GET", code="upstream_aborted").get() == aborted0:
            time.sleep(0.05)
        assert lb_lib._REQUESTS.labels(
            method="GET", code="upstream_aborted").get() == aborted0 + 1
        assert lb_lib._REQUESTS.labels(
            method="GET", code="200").get() == ok0
        assert lb_lib._RETRIES.get() == retries0   # no mid-stream retry
        assert policy.done == [url]                # slot returned
        # An accept-then-die replica feeds the breaker too: success is
        # only recorded after the WHOLE stream proxies, so mid-stream
        # deaths accumulate instead of self-neutralizing.
        assert lb.breaker.state(url) == "open"
    finally:
        lb.shutdown()
        server.shutdown()


# ============================================================= body cap
def test_lb_request_body_cap_413():
    hits = []
    handler = type("H", (_OkHandler,), {"hits": hits})
    server, url = _start(handler)
    policy = RoundRobinPolicy()
    policy.set_ready_replicas([url])
    lb = lb_lib.run_load_balancer(0, policy, lb_lib.RequestRecorder())
    lb.RequestHandlerClass.max_body_bytes = 1024
    try:
        big = b"x" * 4096
        req = urllib.request.Request(
            f"http://127.0.0.1:{lb.server_address[1]}/gen", data=big,
            method="POST")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 413
        assert hits == []              # never reached a replica
        # An in-cap body still proxies.
        req = urllib.request.Request(
            f"http://127.0.0.1:{lb.server_address[1]}/gen",
            data=b"y" * 512, method="POST")
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert resp.status == 200
        assert hits == ["/gen"]
    finally:
        lb.shutdown()
        server.shutdown()


# ===================================================== engine supervision
class _CrashOnStart:
    """Engine stub whose compute loop is dead on arrival — drives the
    supervisor's restart/permanent-down ladder without paying real
    model setup per restart."""

    def __init__(self):
        self._failed = None

    def start(self):
        self._failed = "InjectedFault: boom"
        return self

    def submit(self, *a, **k):
        from skypilot_tpu.serve import decode_engine
        raise decode_engine.EngineError(f"engine failed: {self._failed}")

    def drain(self):
        pass

    def in_flight(self):
        return 0

    def shutdown(self):
        pass


def test_supervisor_permanent_down_after_max_fast_failures():
    from skypilot_tpu.serve import decode_engine
    sup = decode_engine.EngineSupervisor(
        _CrashOnStart, max_restarts=2, backoff_base=0.01,
        backoff_cap=0.02, fast_failure_seconds=10.0,
        poll_interval=0.01).start()
    try:
        deadline = time.time() + 10
        while time.time() < deadline and not sup.permanently_down:
            time.sleep(0.02)
        assert sup.permanently_down
        assert sup.restarts == 2       # tried exactly max_restarts times
        assert not sup.healthy()
        with pytest.raises(decode_engine.EngineError,
                           match="permanently down"):
            sup.submit([1], max_tokens=1)
    finally:
        sup.shutdown()


def _tiny_llm():
    import jax
    from skypilot_tpu.models import llama
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    return cfg, params


def test_engine_crash_health_503_supervisor_restart_recovers():
    """Acceptance (b): crash the engine loop via the fault harness →
    /health flips to 503 (no zombie replica) → the supervisor restarts
    the engine with fresh state → the next request succeeds and is
    bit-identical to pre-crash output."""
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import decode_engine

    cfg, params = _tiny_llm()
    ready = threading.Event()
    httpd = serve_llm.serve(cfg, params, 0, ready_event=ready,
                            engine_restart_backoff=0.5)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    assert ready.wait(timeout=120)
    port = httpd.server_address[1]
    base = f"http://127.0.0.1:{port}"
    restarts0 = decode_engine._RESTARTS.get()

    def generate():
        req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": [1, 2, 3],
                             "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        try:
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read() or b"{}")

    try:
        status, payload = generate()
        assert status == 200 and len(payload["tokens"]) == 4
        baseline = payload["tokens"]

        fi.activate("engine.step", times=1)
        status, payload = generate()
        assert status == 503           # clean EngineError, not a hang
        assert fi.fires("engine.step") == 1
        # Zombie-killer: the health endpoint must report the dead
        # engine (the HTTP process itself is perfectly alive).
        deadline = time.time() + 5
        saw_unhealthy = False
        while time.time() < deadline:
            if _get_code(base + "/health") == 503:
                saw_unhealthy = True
                break
            time.sleep(0.01)
        assert saw_unhealthy, "dead engine never surfaced on /health"
        # Supervisor restarts (0.5s backoff) and health recovers.
        deadline = time.time() + 30
        while time.time() < deadline:
            if _get_code(base + "/health") == 200:
                break
            time.sleep(0.05)
        assert _get_code(base + "/health") == 200
        status, payload = generate()
        assert status == 200
        assert payload["tokens"] == baseline   # fresh cache, same math
        assert httpd.engine.restarts >= 1
        assert decode_engine._RESTARTS.get() >= restarts0 + 1
    finally:
        fi.clear()
        httpd.engine.shutdown()
        httpd.shutdown()


def test_engine_drain_finishes_inflight_rejects_new():
    from skypilot_tpu.serve import decode_engine

    cfg, params = _tiny_llm()
    engine = decode_engine.DecodeEngine(cfg, params, slots=2,
                                        max_seq=128,
                                        prefill_chunk=16).start()
    try:
        engine.warmup()
        # Slow each decode step so the drain demonstrably overlaps a
        # live stream.
        fi.activate("engine.step", mode="delay", delay=0.02)
        req = engine.submit([1, 2, 3], max_tokens=12)
        it = req.stream(timeout=60)
        first = next(it)
        engine.drain()
        with pytest.raises(decode_engine.EngineError, match="draining"):
            engine.submit([1], max_tokens=2)
        toks = [first] + list(it)
        assert len(toks) == 12         # in-flight stream ran to the end
        deadline = time.time() + 5
        while time.time() < deadline and engine.in_flight():
            time.sleep(0.02)
        assert engine.in_flight() == 0
    finally:
        fi.clear()
        engine.shutdown()


# ================================================== graceful drain e2e
@pytest.mark.usefixtures("tmp_state_dir")
def test_scale_down_drains_inflight_stream():
    """Acceptance (c): scale_down of a READY replica with a live SSE
    stream completes the stream (every token + [DONE]) before the
    cluster is terminated, and the drain lifecycle lands in the event
    log."""
    from skypilot_tpu.observability import events
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.resources import Resources
    from skypilot_tpu.serve import replica_managers, serve_state
    from skypilot_tpu.serve.serve_state import ReplicaStatus
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    from skypilot_tpu.task import Task

    cfg, params = _tiny_llm()
    ready = threading.Event()
    httpd = serve_llm.serve(cfg, params, 0, ready_event=ready)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    assert ready.wait(timeout=120)
    port = httpd.server_address[1]
    url = f"http://127.0.0.1:{port}"

    spec = SkyServiceSpec(readiness_path="/health", min_replicas=1,
                          initial_delay_seconds=60,
                          drain_timeout_seconds=30)
    task = Task("drain-svc", run="true")
    task.set_resources(Resources(cloud="local"))
    task.service = spec
    mgr = replica_managers.SkyPilotReplicaManager("svc-drain", spec,
                                                  task)
    info = replica_managers.ReplicaInfo(1, "svc-drain-replica-1", port,
                                        spec=spec)
    info.url = url
    info.status = ReplicaStatus.READY
    mgr.replicas[1] = info

    results = {}

    def consume():
        conn = http.client.HTTPConnection("127.0.0.1", port,
                                          timeout=120)
        conn.request("POST", "/generate",
                     body=json.dumps({"prompt": [1, 2, 3],
                                      "max_tokens": 30,
                                      "stream": True}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        chunks = []
        while True:
            chunk = resp.read1(65536)
            if not chunk:
                break
            chunks.append(chunk)
        results["text"] = b"".join(chunks).decode()
        results["done_at"] = time.monotonic()
        conn.close()

    # Slow decode steps so the stream is demonstrably in flight when
    # the drain starts.
    fi.activate("engine.step", mode="delay", delay=0.05)
    client = threading.Thread(target=consume, daemon=True)
    client.start()
    try:
        deadline = time.time() + 30
        while time.time() < deadline:
            _, body = _get(url + "/drain")
            if json.loads(body)["in_flight"] >= 1:
                break
            time.sleep(0.02)
        else:
            pytest.fail("stream never registered in flight")

        mgr.scale_down(1, sync=True)       # auto-drains (READY + spec)
        terminated_at = time.monotonic()
        client.join(timeout=60)
        assert "done_at" in results, "client stream never finished"
        text = results["text"]
        tokens = [ln for ln in text.splitlines()
                  if ln.startswith("data: {")]
        assert len(tokens) == 30, f"truncated stream: {len(tokens)}/30"
        assert "data: [DONE]" in text      # clean SSE terminator
        # The stream finished BEFORE termination proceeded.
        assert results["done_at"] <= terminated_at
        # Replica record cleaned up; lifecycle events recorded.
        assert serve_state.get_replicas("svc-drain") == []
        evs = [e["event"] for e in events.read(kind="replica",
                                               name="svc-drain/1",
                                               limit=None)]
        assert "drain_start" in evs and "drain_complete" in evs
        # Draining replica rejects NEW work (the LB re-routes on 503).
        req = urllib.request.Request(
            url + "/generate",
            data=json.dumps({"prompt": [5], "max_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(req, timeout=10)
        assert exc.value.code == 503
    finally:
        fi.clear()
        httpd.engine.shutdown()
        httpd.shutdown()


@pytest.mark.usefixtures("tmp_state_dir")
def test_scale_down_without_drain_support_terminates_immediately():
    """A replica whose server has no /drain endpoint (plain HTTP
    servers, pre-drain tasks) degrades to the old kill-immediately
    path instead of stalling out the drain deadline."""
    from skypilot_tpu.observability import events
    from skypilot_tpu.resources import Resources
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.serve.serve_state import ReplicaStatus
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    from skypilot_tpu.task import Task

    class _GetOnly(http.server.BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *a):
            pass

        do_GET = _OkHandler._ok
        hits = None
        # no do_POST: POST /drain gets a 501, like python -m http.server

    server, url = _start(_GetOnly)
    spec = SkyServiceSpec(readiness_path="/", min_replicas=1,
                          drain_timeout_seconds=30)
    task = Task("nodrain-svc", run="true")
    task.set_resources(Resources(cloud="local"))
    task.service = spec
    mgr = replica_managers.SkyPilotReplicaManager("svc-nodrain", spec,
                                                  task)
    info = replica_managers.ReplicaInfo(
        1, "svc-nodrain-replica-1",
        server.server_address[1], spec=spec)
    info.url = url
    info.status = ReplicaStatus.READY
    mgr.replicas[1] = info
    t0 = time.monotonic()
    mgr.scale_down(1, sync=True)
    assert time.monotonic() - t0 < 10    # no 30s drain stall
    evs = [e["event"] for e in events.read(kind="replica",
                                           name="svc-nodrain/1",
                                           limit=None)]
    assert "drain_unsupported" in evs
    server.shutdown()


@pytest.mark.usefixtures("tmp_state_dir")
def test_recovery_finishes_interrupted_drain():
    """A controller crash mid-drain leaves a DRAINING row; the
    restarted controller must FINISH the teardown, not re-adopt the
    husk as STARTING — its server's drain flag is irreversible, so an
    adopted husk would probe READY while refusing every request (a
    zombie that also keeps billing)."""
    from skypilot_tpu.resources import Resources
    from skypilot_tpu.serve import replica_managers, serve_state
    from skypilot_tpu.serve.serve_state import ReplicaStatus
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    from skypilot_tpu.task import Task

    serve_state.upsert_replica("svc-rec", 1, "svc-rec-replica-1",
                               ReplicaStatus.DRAINING,
                               "http://127.0.0.1:9",   # long gone
                               launched_at=time.time())
    spec = SkyServiceSpec(readiness_path="/", min_replicas=1,
                          drain_timeout_seconds=30)
    task = Task("rec-svc", run="true")
    task.set_resources(Resources(cloud="local"))
    task.service = spec
    mgr = replica_managers.SkyPilotReplicaManager("svc-rec", spec, task)
    deadline = time.time() + 30
    while time.time() < deadline:
        if (1 not in mgr.replicas and
                serve_state.get_replicas("svc-rec") == []):
            break
        time.sleep(0.1)
    assert 1 not in mgr.replicas, "DRAINING husk was adopted"
    assert serve_state.get_replicas("svc-rec") == []


# ====================================================== probe anti-flap
@pytest.mark.usefixtures("tmp_state_dir")
def test_probe_anti_flap_requires_success_streak():
    """Satellite: after a probe failure a replica needs 2 consecutive
    successes before re-admission — one lucky probe must not bounce an
    oscillating replica back into the LB rotation."""
    from skypilot_tpu.resources import Resources
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.serve.serve_state import ReplicaStatus
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    from skypilot_tpu.task import Task

    server, url = _start(type("H", (_OkHandler,), {}))
    spec = SkyServiceSpec(readiness_path="/", min_replicas=1,
                          initial_delay_seconds=0)
    task = Task("flap-svc", run="true")
    task.set_resources(Resources(cloud="local"))
    task.service = spec
    mgr = replica_managers.SkyPilotReplicaManager("svc-flap", spec,
                                                  task)
    info = replica_managers.ReplicaInfo(
        1, "svc-flap-replica-1", server.server_address[1], spec=spec)
    info.url = url
    info.status = ReplicaStatus.READY
    info.first_ready_at = time.time()
    mgr.replicas[1] = info
    try:
        with fi.inject("replica.probe", times=1):
            mgr._probe_one(info)
        assert info.status == ReplicaStatus.NOT_READY
        mgr._probe_one(info)     # 1st success: still quarantined
        assert info.status == ReplicaStatus.NOT_READY
        mgr._probe_one(info)     # 2nd consecutive success: re-admitted
        assert info.status == ReplicaStatus.READY
        # A failure mid-streak resets the counter.
        with fi.inject("replica.probe", times=1):
            mgr._probe_one(info)
        assert info.status == ReplicaStatus.NOT_READY
        mgr._probe_one(info)
        assert info.status == ReplicaStatus.NOT_READY
    finally:
        server.shutdown()


# ================================================= gang-replica chaos
def _spawn_gang_replica(port, env_extra=None, hosts=2,
                        extra_args=None):
    """2-process gang replica (serve_llm self-spawn mode), unsharded
    (tp=1) so the fault-path tests pay no mesh-compile tax."""
    import pathlib
    import subprocess
    import sys
    env = dict(__import__("os").environ)
    env["PYTHONPATH"] = str(
        pathlib.Path(__file__).resolve().parent.parent)
    env["STPU_GANG_HB_TIMEOUT"] = "2"
    env.update(env_extra or {})
    return subprocess.Popen(
        [sys.executable, "-m", "skypilot_tpu.recipes.serve_llm",
         "--model", "tiny", "--port", str(port),
         "--replica-hosts", str(hosts)] + list(extra_args or ()),
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        start_new_session=True)


def _wait_code(url, want, timeout=240):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        try:
            code = _get_code(url, timeout=5)
        except (urllib.error.URLError, ConnectionError, OSError):
            code = None      # not listening yet / mid-restart
        if code == want:
            return True
        time.sleep(0.25)
    return False


def _gang_members(port):
    return json.loads(
        _get(f"http://127.0.0.1:{port}/gang")[1])["members"]


def _pid_alive(pid):
    import os
    try:
        os.kill(pid, 0)
        return True
    except ProcessLookupError:
        return False
    except PermissionError:
        return True


@pytest.mark.usefixtures("tmp_state_dir")
def test_gang_drain_and_shutdown_leave_no_orphan_followers():
    """POST /drain propagates to the follower's engine (gang-wide
    drain), and SIGTERM teardown reaps every self-spawned follower —
    scale-down must never orphan a gang member process."""
    import os
    import signal as signal_lib
    import subprocess
    port = _free_port()
    proc = _spawn_gang_replica(port)
    base = f"http://127.0.0.1:{port}"
    try:
        assert _wait_code(base + "/health", 200), "gang never ready"
        follower_pids = [m["pid"] for m in _gang_members(port)
                         if m["role"] == "follower"]
        assert follower_pids and all(_pid_alive(p)
                                     for p in follower_pids)
        # Drain: replica refuses new work, gang stays up (draining is
        # not degradation — /gang keeps answering).
        req = urllib.request.Request(base + "/drain", data=b"{}",
                                     method="POST")
        with urllib.request.urlopen(req, timeout=10) as resp:
            payload = json.loads(resp.read())
        assert payload["draining"] is True
        gen = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": [1], "max_tokens": 2}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            urllib.request.urlopen(gen, timeout=10)
            assert False, "draining replica accepted work"
        except urllib.error.HTTPError as e:
            assert e.code == 503
        # SIGTERM: the leader broadcasts shutdown + reaps followers.
        os.kill(proc.pid, signal_lib.SIGTERM)
        proc.wait(timeout=30)
        assert proc.returncode == 143
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and any(
                _pid_alive(p) for p in follower_pids):
            time.sleep(0.2)
        leaked = [p for p in follower_pids if _pid_alive(p)]
        assert not leaked, f"orphaned follower processes: {leaked}"
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=10)


@pytest.mark.usefixtures("tmp_state_dir")
def test_gang_follower_kill_via_chaos_seam_recovers():
    """A seeded `gang.host` kill fault SIGKILLs the follower at its
    first mirrored submission (the same seam host_wrapper fires for
    gang-launched hosts): host 0's /health flips 503, the whole-gang
    supervisor restart respawns the member, and traffic recovers."""
    port = _free_port()
    # The fault spec rides the leader's env into the self-spawned
    # follower; the leader itself never fires gang.host.
    proc = _spawn_gang_replica(
        port, env_extra={"STPU_FAULTS": "gang.host:kill:times=1"})
    base = f"http://127.0.0.1:{port}"
    try:
        assert _wait_code(base + "/health", 200), "gang never ready"
        before = [m["pid"] for m in _gang_members(port)
                  if m["role"] == "follower"]
        gen = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": [1, 2],
                             "max_tokens": 4}).encode(),
            headers={"Content-Type": "application/json"})
        # The broadcast of this admission kills the follower; host 0's
        # own engine still answers the request.
        with urllib.request.urlopen(gen, timeout=120) as resp:
            assert resp.status == 200
        assert _wait_code(base + "/health", 503, timeout=30), \
            "/health never flipped after the chaos kill"
        assert _wait_code(base + "/health", 200, timeout=120), \
            "whole-gang restart never recovered"
        after = [m["pid"] for m in _gang_members(port)
                 if m["role"] == "follower"]
        assert after and after != before
        with urllib.request.urlopen(gen, timeout=120) as resp:
            assert resp.status == 200
    finally:
        if proc.poll() is None:
            proc.terminate()
            try:
                proc.wait(timeout=15)
            except Exception:  # noqa: stpu-except — best-effort teardown of a test subprocess
                proc.kill()


# ====================================== preemption-notice proactive drain
def test_preempt_notice_watch_sets_event_and_counter():
    """Unit: the metadata watcher treats an injected
    ``replica.preempt_notice`` fault AS the provider's notice — it
    sets the shared event (the /health surface), counts the notice,
    and stops (the notice is terminal for the replica)."""
    from skypilot_tpu.recipes import serve_llm
    notice = threading.Event()
    before = serve_llm._PREEMPT_NOTICES.get()
    fi.activate("replica.preempt_notice")
    try:
        serve_llm.preempt_notice_watch(notice, poll=0.01)
    finally:
        fi.clear()
    assert notice.is_set()
    assert serve_llm._PREEMPT_NOTICES.get() == before + 1


@pytest.mark.usefixtures("tmp_state_dir")
def test_preempt_notice_probe_drains_ahead_of_kill():
    """Tentpole (3) at the manager layer: a replica that is serving
    fine but advertising ``preempt_notice: true`` on /health is
    flipped DRAINING by the very probe that saw the notice —
    synchronously, so the same controller tick already counts it
    not-alive and launches the replacement (replace-ahead) — with the
    notice in the event log and the replica out of the ready set."""
    from skypilot_tpu.observability import events
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.resources import Resources
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.serve.serve_state import ReplicaStatus
    from skypilot_tpu.serve.service_spec import SkyServiceSpec
    from skypilot_tpu.task import Task

    cfg, params = _tiny_llm()
    ready = threading.Event()
    httpd = serve_llm.serve(cfg, params, 0, ready_event=ready)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    assert ready.wait(timeout=120)
    port = httpd.server_address[1]
    url = f"http://127.0.0.1:{port}"

    spec = SkyServiceSpec(readiness_path="/health", min_replicas=1,
                          initial_delay_seconds=60,
                          drain_timeout_seconds=30)
    task = Task("preempt-svc", run="true")
    task.set_resources(Resources(cloud="local"))
    task.service = spec
    mgr = replica_managers.SkyPilotReplicaManager("svc-preempt", spec,
                                                  task)
    info = replica_managers.ReplicaInfo(1, "svc-preempt-replica-1",
                                        port, spec=spec)
    info.url = url
    info.status = ReplicaStatus.READY
    info.first_ready_at = time.time()
    mgr.replicas[1] = info
    try:
        # Healthy, no notice: the probe keeps it READY.
        _, body = _get(url + "/health")
        assert "preempt_notice" not in json.loads(body)
        mgr._probe_one(info)
        assert info.status == ReplicaStatus.READY

        # The provider's notice lands (what preempt_notice_watch sets
        # when the replica.preempt_notice fault fires): /health keeps
        # answering 200 — the replica is NOT sick — but carries the
        # notice.
        httpd.RequestHandlerClass.server_ctx["preempt_notice"].set()
        code, body = _get(url + "/health")
        assert code == 200
        assert json.loads(body)["preempt_notice"] is True

        mgr._probe_one(info)
        # DRAINING the moment the probe returns — not after a
        # teardown thread got scheduled — so this tick's reconcile
        # already sees alive < target and replaces ahead of the kill.
        assert info.status == ReplicaStatus.DRAINING
        assert not ReplicaStatus.DRAINING.is_alive()
        assert url not in mgr.ready_urls()
        evs = [e["event"] for e in events.read(kind="replica",
                                               name="svc-preempt/1",
                                               limit=None)]
        assert "preempt_notice" in evs
        # A second probe mid-drain must not double-drain.
        mgr._probe_one(info)
        assert evs.count("preempt_notice") == 1
        # The husk drains through the normal teardown (drain_start in
        # the log; the record survives for postmortem).
        deadline = time.time() + 30
        while time.time() < deadline:
            evs = [e["event"] for e in events.read(
                kind="replica", name="svc-preempt/1", limit=None)]
            if "drain_complete" in evs:
                break
            time.sleep(0.1)
        assert "drain_start" in evs
    finally:
        httpd.engine.shutdown()
        httpd.shutdown()


# ============================================ gang SIGKILL + LB resume
@pytest.mark.usefixtures("tmp_state_dir")
def test_gang_sigkill_mid_stream_lb_resume_bit_identical():
    """ISSUE 19 acceptance: a 2-host gang replica SIGKILLed (the real
    preemption, no drain, no goodbye) mid-stream with speculative
    decode + paged int8 KV on — the LB's journal resumes the stream
    on a peer replica and the CLIENT's bytes are bit-identical to the
    uninterrupted run, greedy and seeded."""
    flags = ["--kv-quant", "1", "--spec-k", "3",
             "--spec-ngram", "2"]
    port_a, port_b = _free_port(), _free_port()
    # A (the victim): 2-host gang, decode slowed through the fault
    # seam so the SIGKILL demonstrably lands mid-stream. B (the
    # survivor): same model + config, full speed.
    proc_a = _spawn_gang_replica(
        port_a, hosts=2, extra_args=flags,
        env_extra={"STPU_FAULTS": "engine.step:delay:s=0.04"})
    proc_b = _spawn_gang_replica(port_b, hosts=1, extra_args=flags)
    a = f"http://127.0.0.1:{port_a}"
    b = f"http://127.0.0.1:{port_b}"

    class _Ordered:
        def set_ready_replicas(self, urls):
            pass

        def select_replica(self, request=None, exclude=None):
            for url in (a, b):
                if url not in (exclude or ()):
                    return url
            return None

        def report_done(self, url):
            pass

        def ready_replicas(self):
            return [a, b]

    def stream_bytes(base, doc, sink=None, timeout=120):
        conn = http.client.HTTPConnection(
            *base.split("//", 1)[1].split(":"), timeout=timeout)
        try:
            conn.request("POST", "/generate", body=json.dumps(doc),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            chunks = []
            while True:
                chunk = resp.read1(65536)
                if not chunk:
                    break
                chunks.append(chunk)
                if sink is not None:
                    sink.append(chunk)
            return resp.status, b"".join(chunks)
        finally:
            conn.close()

    lb_handler = type("Handler", (lb_lib._ProxyHandler,), {
        "policy": _Ordered(), "recorder": lb_lib.RequestRecorder(),
        "breaker": None, "upstream_timeout": 300.0,
        "journal_account": lb_lib.JournalAccount()})
    lb = lb_lib._ThreadingHTTPServer(("127.0.0.1", _free_port()),
                                     lb_handler)
    threading.Thread(target=lb.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{lb.server_address[1]}"
    follower_pids = []
    try:
        assert _wait_code(a + "/health", 200), "gang A never ready"
        assert _wait_code(b + "/health", 200), "replica B never ready"
        follower_pids = [m["pid"] for m in _gang_members(port_a)
                         if m["role"] == "follower"]

        prompt, mt = [1, 2, 3], 12
        greedy = {"prompt": prompt, "max_tokens": mt, "stream": True}
        seeded = dict(greedy, temperature=0.9, seed=21)
        refs = {}
        for name, doc in (("greedy", greedy), ("seeded", seeded)):
            status, body = stream_bytes(b, doc)
            assert status == 200, f"reference {name} failed"
            refs[name] = body
        assert refs["greedy"] != refs["seeded"]

        # Round 1 (greedy): LB-side stream kill via the lb.stream
        # fault point; the splice comes from gang A's peer B.
        before_ok = lb_lib._RESUMES.labels(outcome="ok").get()
        fi.activate("lb.stream", times=1, skip=4)
        try:
            status, body = stream_bytes(base, greedy)
        finally:
            fi.clear()
        assert status == 200
        assert body == refs["greedy"], "greedy splice diverged"

        # Round 2 (seeded): SIGKILL the whole gang A process group
        # mid-stream — the hard preemption. The journal resumes on B.
        result = {}
        sink = []

        def consume():
            result["out"] = stream_bytes(base, seeded, sink=sink)

        client = threading.Thread(target=consume, daemon=True)
        client.start()
        deadline = time.time() + 60
        while time.time() < deadline:
            if b"".join(sink).count(b"data: {") >= 2:
                break
            time.sleep(0.02)
        else:
            pytest.fail("stream never produced tokens via gang A")
        import os
        import signal as signal_lib
        os.killpg(os.getpgid(proc_a.pid), signal_lib.SIGKILL)
        client.join(timeout=120)
        assert "out" in result, "client stream never finished"
        status, body = result["out"]
        assert status == 200
        assert body == refs["seeded"], "post-SIGKILL splice diverged"
        # The LB counts a resume after it has sent the stream's
        # terminator (_splice_from), so the client can be here first.
        deadline = time.time() + 5
        while time.time() < deadline and lb_lib._RESUMES.labels(
                outcome="ok").get() < before_ok + 2:
            time.sleep(0.01)
        assert lb_lib._RESUMES.labels(
            outcome="ok").get() >= before_ok + 2
    finally:
        fi.clear()
        lb.shutdown()
        import os
        import signal as signal_lib
        for proc in (proc_a, proc_b):
            if proc.poll() is None:
                try:
                    os.killpg(os.getpgid(proc.pid),
                              signal_lib.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait(timeout=10)
        # The gang's self-spawned followers sit in their own sessions;
        # the 2s heartbeat timeout reaps them, but don't leak on a
        # fast exit either.
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline and any(
                _pid_alive(p) for p in follower_pids):
            time.sleep(0.2)
        for pid in follower_pids:
            if _pid_alive(pid):
                try:
                    os.kill(pid, signal_lib.SIGKILL)
                except ProcessLookupError:
                    pass
