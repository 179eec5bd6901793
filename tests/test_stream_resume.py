"""Durable streams: LB mid-stream resume + engine resume admission.

The contract under test (ISSUE 19 tentpole):
  * while proxying a streaming /generate the LB journals every token
    event it forwards; when the UPSTREAM dies mid-stream (never the
    client) it re-picks a peer excluding every replica the request
    already burned, re-submits with the `resume: {emitted, pos}`
    extension, and splices the continuation into the SAME client
    stream — the client's bytes are bit-identical to an uninterrupted
    run, greedy and seeded alike;
  * a peer that ignores `resume` and replays from position 0 is
    deduped, with every replayed token VERIFIED against the journal
    (a divergent peer must abort, not corrupt the stream);
  * resumes are budgeted (STPU_LB_STREAM_RESUMES) and the journal is
    byte-capped (STPU_LB_RESUME_JOURNAL_MB) — exhaustion and eviction
    degrade to the plain upstream abort, never an unbounded promise;
  * the engine side: `resume.emitted` re-enters as a prompt extension
    and generation continues at the same absolute positions with the
    original seed (fold_in(seed, position) sampling), dense and paged,
    spec-on, so the splice really is bit-identical;
plus the game-day lever: fault point ``lb.stream`` kills a proxied
stream after K reads and the resume ladder heals it end to end.
"""
import http.client
import http.server
import json
import socket
import socketserver
import struct
import threading
import time
import types
import urllib.request

import pytest

from skypilot_tpu.observability import metrics
from skypilot_tpu.serve import load_balancer as lb_lib
from skypilot_tpu.serve.load_balancing_policies import (
    LoadBalancingPolicy)
from skypilot_tpu.utils import fault_injection as fi


@pytest.fixture(autouse=True)
def _clear_faults():
    fi.clear()
    yield
    fi.clear()


# ====================================================== stub LB stack
class _Server(socketserver.ThreadingMixIn, http.server.HTTPServer):
    daemon_threads = True
    allow_reuse_address = True

    def handle_error(self, request, client_address):
        pass    # mid-stream deaths are intentional here; keep CI quiet


def _tok(prompt, pos):
    """The stub's deterministic sampler: the token at absolute
    position ``pos`` is a pure function of (prompt, pos) — the same
    replica-independence the real engine gets from
    fold_in(seed, position), so any honest peer continues the exact
    stream the dead one was emitting."""
    return (sum(prompt) * 31 + pos * 7) % 997


class _Replica(http.server.BaseHTTPRequestHandler):
    """Stub replica speaking the serve_llm resume contract: honors
    `resume: {emitted, pos}` by emitting from the absolute position
    (acknowledged via X-STPU-Resume), or — with ``honor_resume`` off —
    replays from 0 like a pre-resume replica. ``abort_after`` drops
    the connection after N token events of THIS request (no [DONE]);
    ``token_offset`` simulates a divergent peer."""
    protocol_version = "HTTP/1.1"
    abort_after = None
    honor_resume = True
    token_offset = 0
    delay = 0.0
    hits = None         # list of (port, start_pos, honored)

    def log_message(self, *a):
        pass

    def do_POST(self):
        length = int(self.headers.get("Content-Length") or 0)
        req = json.loads(self.rfile.read(length) or b"{}")
        prompt = [int(t) for t in req["prompt"]]
        mt = int(req.get("max_tokens", 8))
        resume = req.get("resume")
        start, honored = 0, False
        if resume is not None and self.honor_resume:
            start, honored = int(resume["pos"]), True
        if self.hits is not None:
            self.hits.append((self.server.server_address[1], start,
                              honored))
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        if honored:
            self.send_header("X-STPU-Resume", str(start))
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()
        sent = 0
        for pos in range(start, mt):
            if self.delay:
                time.sleep(self.delay)
            if self.abort_after is not None and sent >= self.abort_after:
                self.wfile.flush()
                self.connection.close()
                return
            tok = _tok(prompt, pos) + self.token_offset
            lb_lib.write_chunk(
                self.wfile, f'data: {{"token": {tok}}}\n\n'.encode())
            sent += 1
        lb_lib.write_chunk(self.wfile, b"data: [DONE]\n\n")
        lb_lib.end_chunks(self.wfile)


class _OrderedPolicy(LoadBalancingPolicy):
    """First non-excluded URL in a fixed priority order — the tests
    need a deterministic initial pick (the failing replica) and a
    deterministic resume pick (the next peer)."""

    def __init__(self, urls):
        self._urls = list(urls)
        self.done = []

    def set_ready_replicas(self, urls):
        self._urls = list(urls)

    def select_replica(self, request=None, exclude=None):
        excl = exclude or ()
        for url in self._urls:
            if url not in excl:
                return url
        return None

    def report_done(self, url):
        self.done.append(url)

    def ready_replicas(self):
        return list(self._urls)


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _start_replica(**attrs):
    handler = type("Replica", (_Replica,), dict(attrs))
    server = _Server(("127.0.0.1", 0), handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, f"http://127.0.0.1:{server.server_address[1]}"


def _start_lb(policy, **handler_attrs):
    handler_attrs.setdefault("journal_account", lb_lib.JournalAccount())
    handler = type("Handler", (lb_lib._ProxyHandler,), {
        "policy": policy, "recorder": lb_lib.RequestRecorder(),
        "breaker": lb_lib.CircuitBreaker(), **handler_attrs})
    server = lb_lib._ThreadingHTTPServer(("127.0.0.1", _free_port()),
                                         handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    return server, f"http://127.0.0.1:{server.server_address[1]}"


def _expected(prompt, mt):
    body = b"".join(f'data: {{"token": {_tok(prompt, p)}}}\n\n'.encode()
                    for p in range(mt))
    return body + b"data: [DONE]\n\n"


def _stream(base, doc, timeout=30):
    """POST a streaming /generate, reading until EOF. Returns
    (status, bytes, truncated) — truncated means the chunked stream
    died before its terminator (the LB gave up mid-stream)."""
    host, port = base.split("//", 1)[1].split(":")
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("POST", "/generate", body=json.dumps(doc),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        chunks, truncated = [], False
        try:
            while True:
                chunk = resp.read1(65536)
                if not chunk:
                    break
                chunks.append(chunk)
        except (http.client.IncompleteRead, http.client.HTTPException,
                ConnectionError, OSError) as e:
            truncated = True
            partial = getattr(e, "partial", None)
            if partial:
                chunks.append(partial)
        return resp.status, b"".join(chunks), truncated
    finally:
        conn.close()


def _await(predicate, timeout=5.0):
    """The LB handler thread finishes its accounting (outcome
    counters, request-code labels, slot returns) a beat AFTER the
    client sees the stream terminator — poll instead of racing it."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return True
        time.sleep(0.01)
    return predicate()


def _resumes(outcome):
    return lb_lib._RESUMES.labels(outcome=outcome).get()


def _code(code, method="POST"):
    return lb_lib._REQUESTS.labels(method=method, code=code).get()


def _gap_count():
    return lb_lib._RESUME_GAP.labels().snapshot()[2]


# ========================================================= unit layer
def test_sse_token_parse():
    assert lb_lib._sse_token(b'data: {"token": 42}\n\n') == 42
    assert lb_lib._sse_token(b"data: [DONE]\n\n") is None
    assert lb_lib._sse_token(b": keepalive\n\n") is None
    assert lb_lib._sse_token(b"data: not-json\n\n") is None
    assert lb_lib._sse_token(b'data: {"text": "hi"}\n\n') is None


def test_journal_account_charge_release():
    acct = lb_lib.JournalAccount(cap_bytes=100)
    assert acct.charge(60) and acct.used() == 60
    assert not acct.charge(41)      # over cap: refused, not clamped
    assert acct.used() == 60
    acct.release(60)
    assert acct.used() == 0
    acct.release(10)                # over-release clamps at zero
    assert acct.used() == 0


def test_stream_journal_resume_body_and_eviction():
    body = json.dumps({"prompt": [1, 2], "max_tokens": 8,
                       "stream": True, "seed": 7}).encode()
    doc = json.loads(body)
    acct = lb_lib.JournalAccount(cap_bytes=10 * 1024)
    j = lb_lib.StreamJournal({"path": "/generate", "body": body}, doc,
                             1, acct)
    assert j.can_resume() and acct.used() > 0
    # Before any token went out the re-submission IS the original
    # request (plain re-submit, nothing to dedupe).
    assert j.resume_body() == body
    j.append(10)
    j.append(11)
    resumed = json.loads(j.resume_body())
    assert resumed["resume"] == {"emitted": [10, 11], "pos": 2}
    assert resumed["seed"] == 7                   # original sampling
    j.release()
    assert acct.used() == 0

    # Cap too small for even the request body: evicted at birth, and
    # the account never leaks a partial charge.
    tiny = lb_lib.JournalAccount(cap_bytes=8)
    before = _resumes("evicted")
    j2 = lb_lib.StreamJournal({"path": "/generate", "body": body},
                              doc, 1, tiny)
    assert j2.evicted and not j2.can_resume()
    assert tiny.used() == 0
    assert _resumes("evicted") == before + 1
    j2.evict()                                    # idempotent
    assert _resumes("evicted") == before + 1


def test_maybe_journal_gates_on_streaming_generate_posts():
    def probe(method="POST", path="/generate", doc=None, body=None):
        if body is None:
            body = json.dumps(doc).encode() if doc is not None else b""
        ns = types.SimpleNamespace(max_stream_resumes=1, path=path,
                                   journal_account=None)
        return lb_lib._ProxyHandler._maybe_journal(
            ns, method, body, {"path": path, "body": body})

    ok = {"prompt": [1], "max_tokens": 4, "stream": True}
    assert isinstance(probe(doc=ok), lb_lib.StreamJournal)
    assert probe(path="/generate?x=1", doc=ok) is not None
    assert probe(method="GET", doc=ok) is None
    assert probe(path="/metrics", doc=ok) is None
    assert probe(body=b"not json") is None
    assert probe(body=b"") is None
    assert probe(doc={"prompt": [1]}) is None          # not streaming
    # A request that already carries `resume` belongs to an upstream
    # resuming tier — journaling it again would double-dedupe.
    assert probe(doc=dict(ok, resume={"emitted": [1],
                                      "pos": 1})) is None
    ns = types.SimpleNamespace(max_stream_resumes=0, path="/generate",
                               journal_account=None)
    body = json.dumps(ok).encode()
    assert lb_lib._ProxyHandler._maybe_journal(
        ns, "POST", body, {"path": "/generate", "body": body}) is None


# ================================================= LB splice behavior
def test_resume_splice_bit_identical_honored_peer():
    """Tentpole acceptance: upstream dies after 3 events, the LB
    splices the continuation from a resume-honoring peer — the client
    bytes equal the uninterrupted run byte for byte, the peer started
    at the absolute position (no replay), and the slot accounting
    returned every pick."""
    hits = []
    sa, a = _start_replica(abort_after=3, hits=hits)
    sb, b = _start_replica(hits=hits)
    policy = _OrderedPolicy([a, b])
    lb, base = _start_lb(policy)
    before_ok, before_gap = _resumes("ok"), _gap_count()
    before_200 = _code("200")
    try:
        prompt, mt = [3, 1, 4], 9
        status, body, truncated = _stream(
            base, {"prompt": prompt, "max_tokens": mt, "stream": True,
                   "seed": 5})
        assert status == 200 and not truncated
        assert body == _expected(prompt, mt)
        assert _await(lambda: _resumes("ok") == before_ok + 1)
        assert _gap_count() == before_gap + 1     # stall was measured
        assert _await(lambda: _code("200") == before_200 + 1)
        # Peer B was resumed AT position 3 (honored), not replayed.
        assert hits == [(sa.server_address[1], 0, False),
                        (sb.server_address[1], 3, True)]
        # Both the original pick and the resume pick returned slots.
        assert _await(lambda: sorted(policy.done) == sorted([a, b]))
    finally:
        lb.shutdown(), sa.shutdown(), sb.shutdown()


def test_resume_dedupes_replay_from_zero_peer():
    """A peer without resume admission replays from position 0: the
    LB drops the overlap (verifying each replayed token against its
    journal) and the client still sees one seamless stream."""
    hits = []
    sa, a = _start_replica(abort_after=4, hits=hits)
    sb, b = _start_replica(honor_resume=False, hits=hits)
    lb, base = _start_lb(_OrderedPolicy([a, b]))
    before_ok = _resumes("ok")
    try:
        prompt, mt = [2, 7], 10
        status, body, truncated = _stream(
            base, {"prompt": prompt, "max_tokens": mt, "stream": True})
        assert status == 200 and not truncated
        assert body == _expected(prompt, mt)
        assert _await(lambda: _resumes("ok") == before_ok + 1)
        assert hits[-1] == (sb.server_address[1], 0, False)  # replayed
    finally:
        lb.shutdown(), sa.shutdown(), sb.shutdown()


def test_resume_divergent_peer_aborts_instead_of_corrupting():
    """The replayed overlap is VERIFIED: a peer emitting different
    tokens (wrong weights, wrong seed path) must not be spliced — the
    client keeps a clean truncated stream ending at an event boundary,
    never silently wrong bytes."""
    sa, a = _start_replica(abort_after=3)
    sb, b = _start_replica(honor_resume=False, token_offset=5)
    lb, base = _start_lb(_OrderedPolicy([a, b]))
    before = {k: _resumes(k) for k in ("failed", "exhausted", "ok")}
    before_ua = _code("upstream_aborted")
    try:
        prompt, mt = [9, 9], 8
        status, body, truncated = _stream(
            base, {"prompt": prompt, "max_tokens": mt, "stream": True})
        assert status == 200 and truncated
        # Exactly the 3 pre-death events, all correct, no [DONE].
        want = b"".join(
            f'data: {{"token": {_tok(prompt, p)}}}\n\n'.encode()
            for p in range(3))
        assert body == want
        assert b"[DONE]" not in body
        assert _await(
            lambda: _resumes("failed") == before["failed"] + 1)
        assert _await(
            lambda: _resumes("exhausted") == before["exhausted"] + 1)
        assert _resumes("ok") == before["ok"]
        assert _await(
            lambda: _code("upstream_aborted") == before_ua + 1)
    finally:
        lb.shutdown(), sa.shutdown(), sb.shutdown()


def test_resume_budget_exhaustion_clean_abort():
    """Budget 1 (the default): when the continuation dies too, the
    stream degrades to a clean abort — every byte the client DID get
    is correct and ends at an event boundary."""
    sa, a = _start_replica(abort_after=3)
    sb, b = _start_replica(abort_after=2)      # continuation dies too
    lb, base = _start_lb(_OrderedPolicy([a, b]))
    before = {k: _resumes(k) for k in ("failed", "exhausted")}
    try:
        prompt, mt = [6, 2], 12
        status, body, truncated = _stream(
            base, {"prompt": prompt, "max_tokens": mt, "stream": True})
        assert status == 200 and truncated
        # 3 events from A + 2 spliced from B, all at the right
        # absolute positions.
        want = b"".join(
            f'data: {{"token": {_tok(prompt, p)}}}\n\n'.encode()
            for p in range(5))
        assert body == want
        assert _await(
            lambda: _resumes("failed") == before["failed"] + 1)
        assert _await(
            lambda: _resumes("exhausted") == before["exhausted"] + 1)
    finally:
        lb.shutdown(), sa.shutdown(), sb.shutdown()


def test_resume_budget_two_survives_double_death():
    """STPU_LB_STREAM_RESUMES=2 equivalent: two mid-stream deaths,
    two splices, one bit-identical client stream."""
    sa, a = _start_replica(abort_after=3)
    sb, b = _start_replica(abort_after=2)
    sc, c = _start_replica()
    lb, base = _start_lb(_OrderedPolicy([a, b, c]),
                         max_stream_resumes=2)
    before_ok = _resumes("ok")
    try:
        prompt, mt = [8, 8, 8], 11
        status, body, truncated = _stream(
            base, {"prompt": prompt, "max_tokens": mt, "stream": True,
                   "seed": 13})
        assert status == 200 and not truncated
        assert body == _expected(prompt, mt)
        assert _await(lambda: _resumes("ok") == before_ok + 1)
    finally:
        lb.shutdown(), sa.shutdown(), sb.shutdown(), sc.shutdown()


def test_resume_no_replica_left():
    """A single-replica service has nowhere to resume: the abort is
    clean and labeled no_replica, not a hang or a retry storm."""
    sa, a = _start_replica(abort_after=2)
    lb, base = _start_lb(_OrderedPolicy([a]))
    before = _resumes("no_replica")
    before_ua = _code("upstream_aborted")
    try:
        status, body, truncated = _stream(
            base, {"prompt": [1], "max_tokens": 6, "stream": True})
        assert status == 200 and truncated
        assert _await(lambda: _resumes("no_replica") == before + 1)
        assert _await(
            lambda: _code("upstream_aborted") == before_ua + 1)
    finally:
        lb.shutdown(), sa.shutdown()


def test_journal_cap_eviction_degrades_to_plain_abort():
    """STPU_LB_RESUME_JOURNAL_MB equivalent: a cap the stream outgrows
    evicts the journal mid-flight — the stream keeps proxying, the
    death degrades to a plain upstream abort, and every charged byte
    is released."""
    sa, a = _start_replica(abort_after=6)
    sb, b = _start_replica()
    # Body charge (+64) fits; the cap runs out after ~2 token appends,
    # well before the death at event 6.
    body = json.dumps({"prompt": [4, 4], "max_tokens": 10,
                       "stream": True}).encode()
    acct = lb_lib.JournalAccount(
        cap_bytes=len(body) + 64 + 2 * lb_lib.StreamJournal.TOKEN_BYTES)
    lb, base = _start_lb(_OrderedPolicy([a, b]), journal_account=acct)
    before_ev, before_ok = _resumes("evicted"), _resumes("ok")
    before_ua = _code("upstream_aborted")
    try:
        status, got, truncated = _stream(
            base, {"prompt": [4, 4], "max_tokens": 10, "stream": True})
        assert status == 200 and truncated
        assert _await(lambda: _resumes("evicted") == before_ev + 1)
        assert _await(
            lambda: _code("upstream_aborted") == before_ua + 1)
        assert _resumes("ok") == before_ok          # no resume attempt
        assert _await(lambda: acct.used() == 0)     # nothing leaked
    finally:
        lb.shutdown(), sa.shutdown(), sb.shutdown()


def test_journal_released_after_clean_completion():
    acct = lb_lib.JournalAccount()
    sa, a = _start_replica()
    lb, base = _start_lb(_OrderedPolicy([a]), journal_account=acct)
    try:
        prompt, mt = [5], 7
        status, body, truncated = _stream(
            base, {"prompt": prompt, "max_tokens": mt, "stream": True})
        assert status == 200 and not truncated
        assert body == _expected(prompt, mt)
        assert _await(lambda: acct.used() == 0)
    finally:
        lb.shutdown(), sa.shutdown()


def test_client_disconnect_is_not_resumed_and_not_charged():
    """Satellite (a): the CLIENT hanging up mid-stream is not an
    upstream failure — no resume attempt, no breaker charge, and the
    request lands under code="client_closed" (which the SLO burn
    monitor does not count as bad)."""
    sa, a = _start_replica(delay=0.02)
    sb, b = _start_replica(delay=0.02)
    lb, base = _start_lb(_OrderedPolicy([a, b]))
    resumes_before = {k: _resumes(k)
                      for k in ("ok", "failed", "no_replica")}
    cc_before, ua_before = _code("client_closed"), _code(
        "upstream_aborted")
    try:
        host, port = base.split("//", 1)[1].split(":")
        conn = http.client.HTTPConnection(host, int(port), timeout=10)
        conn.request("POST", "/generate",
                     body=json.dumps({"prompt": [1, 2],
                                      "max_tokens": 50,
                                      "stream": True}),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read1(1)                      # stream demonstrably live
        # A REAL client death: SO_LINGER(0) close sends RST so the
        # LB's next write fails (a plain close() here would leave the
        # fd alive via the response object's makefile reference).
        conn.sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                             struct.pack("ii", 1, 0))
        resp.close()
        conn.close()                       # client dies mid-stream
        assert _await(lambda: _code("client_closed") == cc_before + 1,
                      timeout=10)
        assert _code("upstream_aborted") == ua_before
        for k, v in resumes_before.items():
            assert _resumes(k) == v, f"resume outcome {k} moved"
        # No breaker charge for a client hang-up: both replicas stay
        # selectable.
        handler = lb.RequestHandlerClass
        assert handler.breaker.blocked([a, b]) == set()
    finally:
        lb.shutdown(), sa.shutdown(), sb.shutdown()


def test_lb_stream_fault_point_heals_via_resume():
    """Satellite (b): the game-day lever. ``lb.stream`` killing the
    proxied stream after K upstream reads is healed by the resume
    ladder — the client never notices the drill."""
    sa, a = _start_replica(delay=0.005)
    sb, b = _start_replica(delay=0.005)
    lb, base = _start_lb(_OrderedPolicy([a, b]))
    before_ok = _resumes("ok")
    try:
        fi.activate("lb.stream", times=1, skip=3)
        prompt, mt = [7, 7], 10
        status, body, truncated = _stream(
            base, {"prompt": prompt, "max_tokens": mt, "stream": True})
        assert fi.fires("lb.stream") == 1
        assert status == 200 and not truncated
        assert body == _expected(prompt, mt)
        assert _await(lambda: _resumes("ok") == before_ok + 1)
    finally:
        fi.clear()
        lb.shutdown(), sa.shutdown(), sb.shutdown()


# ============================================ engine resume admission
def _tiny_llm():
    import jax
    from skypilot_tpu.models import llama
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    return cfg, params


def _post_json(base, doc, timeout=120):
    req = urllib.request.Request(
        base + "/generate", data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def _sse_tokens(body):
    return [json.loads(ln[6:])["token"]
            for ln in body.decode().splitlines()
            if ln.startswith("data: {")]


def test_replica_resume_admission_bit_identical():
    """Engine resume admission end to end on a real replica: the
    emitted prefix re-enters as a prompt extension and the
    continuation equals the uninterrupted run's tail exactly — greedy
    AND seeded — with X-STPU-Resume acknowledging the admission on
    the stream path. Malformed resumes keep the 400 contract."""
    from skypilot_tpu.recipes import serve_llm

    cfg, params = _tiny_llm()
    ready = threading.Event()
    httpd = serve_llm.serve(cfg, params, 0, ready_event=ready)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    assert ready.wait(timeout=120)
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    prompt, mt, cut = [1, 2, 3], 8, 3
    try:
        for sampling in ({"temperature": 0.0},
                         {"temperature": 0.9, "seed": 7}):
            status, _, raw = _post_json(
                base, {"prompt": prompt, "max_tokens": mt, **sampling})
            assert status == 200
            full = json.loads(raw)["tokens"]
            assert len(full) == mt

            resume = {"emitted": full[:cut], "pos": cut}
            # Non-stream continuation: exactly the tail.
            status, _, raw = _post_json(
                base, {"prompt": prompt, "max_tokens": mt,
                       "resume": resume, **sampling})
            assert status == 200
            assert json.loads(raw)["tokens"] == full[cut:]
            # Stream continuation: acknowledged + bit-identical tail.
            status, headers, raw = _post_json(
                base, {"prompt": prompt, "max_tokens": mt,
                       "stream": True, "resume": resume, **sampling})
            assert status == 200
            assert headers.get("X-STPU-Resume") == str(cut)
            assert _sse_tokens(raw) == full[cut:]
            assert raw.rstrip().endswith(b"data: [DONE]")

        # 400 contract: malformed resumes are refused BEFORE any
        # engine admission.
        for bad in ([1, 2],                          # not an object
                    {"emitted": [], "pos": 0},       # empty
                    {"emitted": [1, 2], "pos": 3},   # pos mismatch
                    {"emitted": list(range(mt)), "pos": mt}):  # >= mt
            status, _, raw = _post_json(
                base, {"prompt": prompt, "max_tokens": mt,
                       "resume": bad})
            assert status == 400, (bad, raw)
    finally:
        httpd.engine.shutdown()
        httpd.shutdown()


def test_engine_resume_paged_spec_quant_bit_identical():
    """Engine-level resume admission with the hard config on: paged
    KV + int8 KV quant + speculative decoding. submit(resume=prefix)
    must continue at the same absolute positions — greedy and
    seeded — because resumed sampling keys are fold_in(seed, pos),
    not a function of what lives in this replica's cache."""
    from skypilot_tpu.serve import decode_engine

    cfg, params = _tiny_llm()
    engine = decode_engine.DecodeEngine(
        cfg, params, slots=2, max_seq=128, prefill_chunk=8,
        kv_quant=True, spec_k=3, spec_ngram=2,
        use_manifest=False).start()
    prompt, mt, cut = [1, 2, 3, 4], 10, 4
    try:
        for temperature, seed in ((0.0, 0), (0.8, 11)):
            full = engine.submit(prompt, max_tokens=mt,
                                 temperature=temperature,
                                 seed=seed).result(timeout=300)
            assert len(full) == mt
            tail = engine.submit(prompt, max_tokens=mt - cut,
                                 temperature=temperature, seed=seed,
                                 resume=full[:cut]).result(timeout=300)
            assert tail == full[cut:], (temperature, seed)
    finally:
        engine.shutdown()


# =========================================== e2e: kill a real replica
def test_e2e_mid_stream_replica_death_bit_identical():
    """The whole ladder on real replicas: two engine-backed serve_llm
    servers behind the LB; the stream's upstream dies mid-flight
    (injected stream kill for greedy + seeded, then a REAL engine
    death) and the client's bytes equal the uninterrupted reference
    every time. Token determinism across replicas is the engine's
    replica-independent fold_in(seed, position) sampling."""
    from skypilot_tpu.recipes import serve_llm

    cfg, params = _tiny_llm()
    servers = []
    for _ in range(2):
        ready = threading.Event()
        httpd = serve_llm.serve(cfg, params, 0, ready_event=ready)
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
        assert ready.wait(timeout=120)
        servers.append(httpd)
    sa, sb = servers
    a = f"http://127.0.0.1:{sa.server_address[1]}"
    b = f"http://127.0.0.1:{sb.server_address[1]}"
    # breaker=None: the injected kills below must not eject replica A
    # from selection — each round has to START on A to die there.
    lb, base = _start_lb(_OrderedPolicy([a, b]), breaker=None,
                         upstream_timeout=120)
    prompt, mt = [1, 2, 3], 12
    greedy = {"prompt": prompt, "max_tokens": mt, "stream": True}
    seeded = dict(greedy, temperature=0.9, seed=21)
    try:
        # Uninterrupted references, straight from replica B.
        refs = {}
        for name, doc in (("greedy", greedy), ("seeded", seeded)):
            status, body, truncated = _stream(b, doc, timeout=120)
            assert status == 200 and not truncated
            refs[name] = body

        # Injected stream kill (fault point lb.stream), both sampling
        # modes: the resume splice from B is bit-identical.
        for name, doc in (("greedy", greedy), ("seeded", seeded)):
            before_ok = _resumes("ok")
            fi.activate("lb.stream", times=1, skip=4)
            try:
                status, body, truncated = _stream(base, doc,
                                                  timeout=120)
            finally:
                fi.clear()
            assert status == 200 and not truncated, name
            assert body == refs[name], f"{name} splice diverged"
            assert _await(lambda: _resumes("ok") == before_ok + 1)

        # A REAL replica death: slow the decode so the kill lands
        # mid-stream, then shut A's engine down under a live stream.
        fi.activate("engine.step", mode="delay", delay=0.03)
        before_ok = _resumes("ok")
        result = {}

        def consume():
            result["out"] = _stream(base, seeded, timeout=120)

        client = threading.Thread(target=consume, daemon=True)
        client.start()
        deadline = time.time() + 60
        while time.time() < deadline:       # wait: stream in flight
            if sa.engine.in_flight() >= 1:
                break
            time.sleep(0.01)
        else:
            pytest.fail("stream never reached replica A")
        time.sleep(0.1)                     # a few tokens out first
        sa.engine.shutdown()                # the preempted replica
        client.join(timeout=120)
        fi.clear()
        assert "out" in result, "client stream never finished"
        status, body, truncated = result["out"]
        assert status == 200 and not truncated
        assert body == refs["seeded"], "post-death splice diverged"
        assert _await(lambda: _resumes("ok") == before_ok + 1)
    finally:
        fi.clear()
        lb.shutdown()
        for httpd in servers:
            try:
                httpd.engine.shutdown()
            except Exception:   # noqa: BLE001 — A's engine already dead
                pass
            httpd.shutdown()
