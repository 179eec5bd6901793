"""Minimal TPU LLM inference server — the JetStream/vLLM-TPU serve config.

Reference analog: llm/vllm/serve.yaml and llm/mixtral/serve.yaml (the
reference points SkyServe at a vLLM container). Native version: a
stdlib-http server around the shared model decode stack, exposing the
endpoints SkyServe probes and balances:

    GET  /health    -> 200 once the model is compiled (readiness probe)
    GET  /metrics   -> Prometheus exposition (engine slot/queue/token
                       metrics; merged into the LB's /metrics snapshot)
    POST /generate  {"prompt": [ids...], "max_tokens": N,
                     "temperature": 0.7, "seed": 1} -> {"tokens": [...]}

Requests are served by the slot-based continuous-batching decode engine
(serve/decode_engine.py), the only path from a request to a model:
concurrent requests of ANY prompt length share one paged KV pool,
joining mid-flight into free slots (chunked prefill interleaved with
decode) and streaming per slot.

    python -m skypilot_tpu.recipes.serve_llm --model tiny --port 8080
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import signal
import subprocess
import sys
import threading
import time
import traceback
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import jax
import jax.numpy as jnp

from skypilot_tpu.agent import constants as agent_constants
from skypilot_tpu.models import (brumby, deepseek, family_name, gemma,
                                 llama, mixtral, model_api, phi4flash)
from skypilot_tpu.observability import metrics
from skypilot_tpu.observability import phases
from skypilot_tpu.observability import reqlog
from skypilot_tpu.observability import stepstats
from skypilot_tpu.observability import tracing
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.serve import decode_engine
from skypilot_tpu.serve import gang_replica
from skypilot_tpu.serve import kv_pool
from skypilot_tpu.serve import load_balancing_policies
from skypilot_tpu.train import distributed
from skypilot_tpu.utils import compile_cache
from skypilot_tpu.utils import fault_injection


# Request limits: a hostile request cannot trigger unbounded allocation,
# and their sum is the engine's max_seq (what the pool is sized by).
MAX_PROMPT_TOKENS = 1024
MAX_GEN_TOKENS = 256

# Engine defaults (overridable per serve() call / env). The prefill
# chunk deliberately has NO constant here: the recipe leaves it at
# resolve_kv_geometry's 0 sentinel so the one derivation (tuning
# manifest -> DEFAULT_PREFILL_CHUNK fallback) lives in decode_engine —
# a literal here was exactly the three-call-site drift magnet the
# autotuner PR removed.
ENGINE_SLOTS = int(os.environ.get("STPU_ENGINE_SLOTS", "4"))
NO_ENGINE_SLOTS = ("engine slots must be at least 1: every request is "
                   "served by the decode engine")
# Host-RAM KV spill tier budget (MiB) under the paged pool's trie:
# LRU-evicted prefix blocks spill D2H into a bounded host pool and
# re-admit H2D on a warm match, so the effective prefix cache grows
# from the HBM pool to host RAM at the cost of one block transfer per
# re-hit. 0 turns the tier off (evictions drop the leaf); default on
# at 64 MiB.
ENGINE_PREFIX_CACHE_MB = float(
    os.environ.get("STPU_PREFIX_CACHE_MB", "64"))
# The KV block pool's size in blocks. 0 = auto-size: slots * max_seq /
# block + 1 scratch; doubled under KV_QUANT — int8 blocks are ~half
# the bytes.
ENGINE_KV_POOL_BLOCKS = int(os.environ.get("STPU_KV_POOL_BLOCKS", "0"))
# 0 = block size follows the prefill chunk (64).
ENGINE_KV_BLOCK_TOKENS = int(
    os.environ.get("STPU_KV_BLOCK_TOKENS", "0"))
# Quantized serving (decode_engine quant mode): KV_QUANT stores int8
# KV blocks + per-(layer, block, head) f32 scales in the pool (~2x
# block capacity at the same HBM budget);
# WEIGHT_QUANT serves int8 per-channel-scaled params. NOT
# bit-identical to bf16 — gated by the tests/test_quant.py parity
# suite (top-1 agreement + perplexity bound per family).
ENGINE_KV_QUANT = os.environ.get("STPU_KV_QUANT", "0") == "1"
ENGINE_WEIGHT_QUANT = os.environ.get("STPU_WEIGHT_QUANT", "0") == "1"
# Self-speculative decoding (decode_engine spec mode): up to K n-gram
# drafted tokens per slot per step, verified in one batched forward —
# bit-identical output, fewer memory-bound passes per token on
# repetitive/templated traffic. 0 disables (this release's default;
# the bench legs and chat-heavy deployments turn it on).
ENGINE_SPEC_K = int(os.environ.get("STPU_SPEC_K", "0"))
ENGINE_SPEC_NGRAM = int(os.environ.get("STPU_SPEC_NGRAM", "3"))
ENGINE_SPEC_MIN_ACCEPT = float(
    os.environ.get("STPU_SPEC_MIN_ACCEPT", "0.2"))
# Per-token stream timeout: how long a client handler waits for the
# NEXT token before declaring the engine wedged (surfaced as a clean
# EngineError, not a hang). Operator-tunable — the right bound is how
# fast wedged-device detection should be vs. the slowest honest step.
STREAM_TIMEOUT_SECONDS = float(
    os.environ.get("STPU_STREAM_TIMEOUT", "600"))
# Preemption-notice watcher poll interval (seconds): how often the
# replica checks the provider's metadata preemption signal (the fault
# point ``replica.preempt_notice`` stands in for the metadata server in
# tests and game-days). On a notice the replica KEEPS serving — it only
# advertises the notice via /health so the controller can flip it
# DRAINING and launch the replacement BEFORE the kill lands
# (replace-ahead); in-flight streams resume on peers through the LB
# journal when the kill arrives. 0 disables the watcher.
PREEMPT_NOTICE_POLL = float(
    os.environ.get("STPU_PREEMPT_NOTICE_POLL", "1.0"))
# Engine supervision (decode_engine.EngineSupervisor): restart a
# crashed engine loop this many times (capped exponential backoff
# starting at BACKOFF seconds) before declaring the replica dead.
ENGINE_MAX_RESTARTS = int(os.environ.get("STPU_ENGINE_MAX_RESTARTS",
                                         "3"))
ENGINE_RESTART_BACKOFF = float(
    os.environ.get("STPU_ENGINE_RESTART_BACKOFF", "1.0"))

# Topology tag for this replica (hosts x tp), exported so the LB's
# merged /metrics and loadgen reports can attribute SLO shifts to a
# replica_topology change. Info-style gauge: value is always 1, the
# labels carry the fact.
_TOPOLOGY_INFO = metrics.gauge(
    "stpu_replica_topology_info",
    "Replica serving topology (hosts x tensor-parallel degree); "
    "value is constant 1.", ("hosts", "tp"))
_PREEMPT_NOTICES = metrics.counter(
    "stpu_serve_preempt_notices_total",
    "Provider preemption notices observed by the replica's metadata "
    "watcher (fault point replica.preempt_notice); each one is a "
    "replace-ahead trigger for the controller.")


def _device_memory(param_bytes: dict, engine) -> list:
    kv_bytes = engine.cache_bytes_per_device()
    rows = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        rows.append({"id": d.id,
                     "param_bytes": param_bytes.get(d.id, 0),
                     "kv_bytes": kv_bytes.get(d.id, 0),
                     "bytes_in_use": stats.get("bytes_in_use"),
                     "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                     "bytes_limit": stats.get("bytes_limit")})
    return rows


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"  # chunked responses need 1.1
    server_ctx = None  # set by serve()

    def log_message(self, *args):
        pass

    def _json(self, code: int, payload: dict):
        body = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):
        if self.path in ("/", "/health"):
            ctx = self.server_ctx
            ready = ctx["ready"].is_set()
            engine = ctx["engine"]
            gang = ctx.get("gang")
            if ctx["warmup_error"]:
                # Terminal: the warm-up raised (the compiler refused a
                # program, the gang never formed). Never "warming" for
                # ever — probes and the smoke read the cause here.
                self._json(500, {"status": "warmup_failed",
                                 "error": ctx["warmup_error"]})
            elif not ready:
                self._json(503, {"status": "warming"})
            elif gang is not None and not gang.healthy():
                # Gang replicas probe as ONE unit: host 0's /health
                # speaks for every host (the leader's membership
                # monitor), so a dead follower can never hide behind a
                # READY replica serving partial-gang garbage.
                self._json(503, {"status": "gang_degraded"})
            elif not engine.healthy():
                # The readiness probe must tell the truth about the
                # ENGINE, not just the HTTP process: a dead/restarting
                # engine behind a 200 probe is a zombie replica that
                # blackholes its share of traffic.
                self._json(503, {"status": "engine_down"})
            else:
                payload = {"status": "ok", "device": ctx["device"]}
                notice = ctx.get("preempt_notice")
                if notice is not None and notice.is_set():
                    # Preemption notice observed: the replica is still
                    # fully serving (200), but the controller's probe
                    # reads this flag and flips the replica DRAINING —
                    # replace-ahead, before the kill ever lands.
                    payload["preempt_notice"] = True
                self._json(200, payload)
        elif self.path == "/drain":
            self._json(200, self._drain_payload())
        elif self.path == "/perf":
            # Step-telemetry snapshot (observability/stepstats.py):
            # phase breakdown, occupancy, sampled dispatch/device
            # split over the step ring. Meaningful content needs
            # STPU_STEPSTATS=1 on the replica; disarmed it reports
            # armed=false with an empty ring. The LB merges every
            # ready replica's /perf like it merges /metrics.
            self._json(200, self._perf_payload())
        elif self.path == "/gang":
            gang = self.server_ctx.get("gang")
            if gang is None:
                self._json(404, {"error": "not a gang replica"})
            else:
                self._json(200, {
                    "topology": gang.topology.to_config(),
                    "label": gang.topology.label(),
                    "healthy": gang.healthy(),
                    "restarts": gang.restarts,
                    "members": gang.members_info()})
        elif self.path == "/metrics":
            # Replica-local registry (engine slot/queue/token families);
            # the LB pulls this into its merged /metrics snapshot.
            body = metrics.render().encode()
            self.send_response(200)
            self.send_header("Content-Type", metrics.CONTENT_TYPE)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)
        else:
            self._json(404, {"error": "not found"})

    # ------------------------------------------------------------ perf
    def _perf_payload(self) -> dict:
        ctx = self.server_ctx
        doc = stepstats.snapshot()
        # What the replica runs on, as JAX reports it, and where its
        # bytes are: per device, the parameter and KV bytes resident
        # there (from the arrays' shards) and the allocator's own
        # counters (None where the backend keeps none, e.g. the CPU).
        engine = ctx["engine"]
        doc["device"] = dict(ctx["device"], memory=_device_memory(
            ctx["param_bytes"], engine))
        doc["engine"] = {
            "healthy": engine.healthy(),
            "in_flight": engine.in_flight(),
            "draining": engine.draining(),
            "restarts": engine.restarts,
        }
        kv = engine.kv_config()
        if kv:
            # Quant mode line for `stpu perf`: which int8 paths
            # this replica serves with (resolve_kv_geometry output
            # — the same dict the gang handshake compares).
            doc["quant"] = {
                "kv_quant": int(kv.get("kv_quant", 0)),
                "weight_quant": int(kv.get("weight_quant", 0)),
                "pool_blocks": int(kv.get("pool_blocks", 0)),
            }
            # Tuning line for `stpu perf`: the constants this
            # replica actually decodes with and which manifest
            # (payload-sha tag, or "default") supplied them.
            doc["tuning"] = {
                "block": int(kv.get("block", 0)),
                "chunk": int(kv.get("chunk", 0)),
                "window": int(kv.get("window", 0)),
                "spec_k": int(kv.get("spec_k", 0)),
                "manifest": kv.get("manifest", "default"),
            }
        # Host KV tier line for `stpu perf`: spill/re-admit and
        # residency counters from the engine's HostBlockPool
        # (absent while the tier is off).
        tier = engine.host_tier_stats()
        if tier:
            doc["tier"] = {
                "budget_mb": float(tier.get("budget_mb", 0.0)),
                "bytes": int(tier.get("bytes", 0)),
                "blocks": int(tier.get("blocks", 0)),
                "spilled": int(tier.get("spilled", 0)),
                "dropped": int(tier.get("evict_drops", 0)),
                "lru_dropped": int(tier.get("lru_dropped", 0)),
                "readmitted": int(tier.get("readmitted_blocks", 0)),
                "rehits": int(tier.get("rehits", 0)),
            }
        return doc

    def _start_profile(self) -> None:
        """POST /profile?seconds=N: capture an on-device
        ``jax.profiler`` trace to ``~/.stpu/logs/profiles/<stamp>/``.
        The capture runs on its own thread (the handler answers 202
        immediately with the target directory); one capture at a time
        per process."""
        import urllib.parse
        query = urllib.parse.parse_qs(
            urllib.parse.urlsplit(self.path).query)
        try:
            seconds = float(query.get("seconds", ["5"])[0])
        except ValueError:
            self._json(400, {"error": "seconds must be numeric"})
            return
        # Atomic claim BEFORE the 202: a racing second request must be
        # told 409, not promised a directory that never appears.
        if not stepstats.begin_profile():
            self._json(409, {"error": "a profile capture is already "
                                      "running"})
            return
        out_dir = os.path.join(
            str(stepstats.profiles_dir()),
            time.strftime("%Y%m%d-%H%M%S"))

        def capture():
            try:
                stepstats.capture_profile(seconds, out_dir=out_dir,
                                          claimed=True)
            except Exception:  # noqa: stpu-except — best-effort capture; the 202 already told the client where to look
                pass

        threading.Thread(target=capture, daemon=True,
                         name="profile-capture").start()
        self._json(202, {"profile_dir": out_dir,
                         "seconds": min(max(seconds, 0.05), 120.0)})

    # ----------------------------------------------------------- drain
    def _drain_payload(self) -> dict:
        ctx = self.server_ctx
        with ctx["inflight_lock"]:
            handler_inflight = ctx["inflight"][0]
        engine = ctx["engine"]
        # The engine's slot count hits zero while a handler thread
        # may still be FLUSHING queued tokens to a slow client — the
        # handler count covers that tail, so report the max of the
        # two views or a drain could truncate a live stream.
        return {"draining": engine.draining(),
                "in_flight": max(engine.in_flight(), handler_inflight)}

    def _start_drain(self) -> None:
        """POST /drain: stop admitting new generations, report what is
        still in flight. The replica manager polls GET /drain until
        in_flight hits 0 (or its deadline) before terminating, so live
        token streams finish instead of truncating mid-rollout."""
        ctx = self.server_ctx
        ctx["engine"].drain()
        gang = ctx.get("gang")
        if gang is not None:
            # Drain is gang-wide: follower engines stop admitting too,
            # so scale-down leaves no host mid-lockstep.
            gang.drain()
        self._json(200, self._drain_payload())

    def do_POST(self):
        # Body consumed up front on EVERY path: an early error response
        # that leaves unread body bytes on an HTTP/1.1 keep-alive
        # connection corrupts the next request parsed off it.
        length = int(self.headers.get("Content-Length", 0))
        raw = self.rfile.read(length) if length else b""
        if self.path == "/drain":
            self._start_drain()
            return
        if self.path == "/profile" or self.path.startswith("/profile?"):
            self._start_profile()
            return
        if self.path != "/generate":
            self._json(404, {"error": "not found"})
            return
        if self.server_ctx["engine"].draining():
            # A submit would raise EngineError anyway; this answers
            # before the body is parsed (503 → the LB retries on a
            # non-draining peer).
            self._json(503, {"error": "replica draining"})
            return
        try:
            req = json.loads(raw or b"{}")
            prompt = [int(t) for t in req["prompt"]]
            if not 1 <= len(prompt) <= MAX_PROMPT_TOKENS:
                raise ValueError(
                    f"prompt length must be in [1, {MAX_PROMPT_TOKENS}]")
            mt = min(max(int(req.get("max_tokens", 16)), 1),
                     MAX_GEN_TOKENS)
            # Quantized so the jit cache stays bounded; 0.0 = greedy.
            temperature = round(
                max(0.0, min(float(req.get("temperature", 0.0)), 2.0)),
                1)
            # Mask to uint32 range: any int is a valid seed, and an
            # out-of-range value must not escape the 400 contract.
            seed = int(req.get("seed", 0)) & 0xFFFFFFFF
            ctx = self.server_ctx
            stream = bool(req.get("stream"))
            # LB mid-stream resume contract: ``resume.emitted`` are the
            # tokens the client already received (they become a prompt
            # extension in the engine), ``resume.pos`` the absolute
            # emission position to continue from. The engine's
            # fold_in(seed, position) sampling keys make the
            # continuation bit-identical to the uninterrupted run.
            resume = None
            rd = req.get("resume")
            if rd is not None:
                if not isinstance(rd, dict):
                    raise ValueError("resume must be an object")
                resume = [int(t) for t in rd.get("emitted") or []]
                if not resume:
                    raise ValueError("resume.emitted must be non-empty")
                if int(rd.get("pos", -1)) != len(resume):
                    raise ValueError(
                        "resume.pos must equal len(resume.emitted)")
                if len(resume) >= mt:
                    raise ValueError(
                        "resume.emitted already covers max_tokens")
        except (KeyError, ValueError, TypeError) as e:
            self._json(400, {"error": str(e)})
            return
        engine = ctx["engine"]
        # Replica hop of the request's trace, continued from the LB's
        # X-STPU-Trace header (tracing.ENABLED guard = zero tracing
        # cost unarmed); the engine parents its queue/prefill/decode
        # spans under this one via the submit trace context.
        span = None
        if tracing.ENABLED:
            span = tracing.start_span(
                "replica.generate", kind="replica",
                parent=tracing.extract(self.headers),
                attrs={"prompt_tokens": len(prompt), "max_tokens": mt,
                       "stream": stream,
                       "resume": len(resume) if resume else 0})
        # GET /drain must see requests this handler is still streaming
        # after the engine has handed their last token over.
        with ctx["inflight_lock"]:
            ctx["inflight"][0] += 1
        status = "error"
        try:
            self._engine_generate(engine, prompt, mt, temperature,
                                  seed, stream, span, resume)
            status = "ok"
        except decode_engine.EngineError as e:
            if span is not None:
                span.event("engine_error", error=str(e))
            self._json(503, {"error": str(e)})
        except (KeyError, ValueError, TypeError) as e:
            self._json(400, {"error": str(e)})
        except Exception as e:  # noqa: BLE001 — pre-header failures
            # must still produce a clean JSON error; once headers are
            # out, _sse has already swallowed the exception and dropped
            # the connection, so this catch never corrupts a stream.
            self._json(500, {"error": f"{type(e).__name__}: {e}"})
        finally:
            with ctx["inflight_lock"]:
                ctx["inflight"][0] -= 1
            if span is not None:
                span.end(status=status)

    # ----------------------------------------------------- engine path
    def _engine_generate(self, engine, prompt, mt, temperature, seed,
                         stream, span=None, resume=None) -> None:
        gang = self.server_ctx.get("gang")
        trace = span.context() if span is not None else None
        if trace is None and reqlog.ENABLED:
            # Request-analytics join key: with tracing disarmed the LB
            # still stamps X-STPU-Trace (a reqlog-minted id, sampled
            # flag 00), and carrying it into the engine keys the
            # engine half of the request record. extract/parse are
            # pure string work — no tracing I/O, and the 00 flag keeps
            # every engine tracing guard short-circuited.
            trace = tracing.extract(self.headers)
        # Resume admission: ``mt`` is the ORIGINAL request budget — the
        # engine re-prefills the emitted tokens as a prompt extension
        # and regenerates only the remainder, emitting from the same
        # absolute positions (same seed) the dead upstream would have.
        remaining = mt - (len(resume) if resume else 0)
        if gang is not None:
            # Mirror the admission (prompt + sampling seed) to every
            # follower host BEFORE the local submit, so all hosts see
            # the same request order and execute identical jitted
            # submissions (the lockstep half of the gang contract).
            # Broadcast + local submit are ONE critical section:
            # concurrent handler threads interleaving them would admit
            # (A,B) on followers but (B,A) on host 0 — divergent slot
            # state, and on a real ICI-federated slice a mismatched
            # SPMD program.
            with self.server_ctx["gang_admit_lock"]:
                gang.broadcast_generate(prompt, remaining, temperature,
                                        seed, trace=trace,
                                        resume=resume)
                req = engine.submit(prompt, max_tokens=remaining,
                                    temperature=temperature, seed=seed,
                                    trace=trace, resume=resume)
        else:
            req = engine.submit(prompt, max_tokens=remaining,
                                temperature=temperature, seed=seed,
                                trace=trace, resume=resume)
        timeout = self.server_ctx["stream_timeout"]
        if not stream:
            self._json(200, {"tokens": req.result(timeout=timeout)})
            return
        it = req.stream(timeout=timeout)
        try:
            # First token BEFORE the headers go out: a prefill/compile
            # error must still be reportable as a clean JSON error, not
            # a corrupted half-stream.
            first = next(it)
        except decode_engine.EngineError as e:
            if span is not None:
                # end() here (idempotent — do_POST's finally no-ops)
                # so a 503'd stream records error like the non-stream
                # path, not a healthy-looking hop.
                span.event("engine_error", error=str(e))
                span.end(status="error")
            self._json(503, {"error": str(e)})
            return
        except StopIteration:
            self._json(200, {"tokens": []})
            return
        self._sse(req, [first], it, span,
                  resume_len=len(resume) if resume else 0)

    # ------------------------------------------------------------- SSE
    def _sse(self, req, first_tokens, rest_iter, span=None,
             resume_len: int = 0) -> None:
        """SSE token stream: one `data: {"token": N}` event per decoded
        token, flushed as produced (chunked transfer), then
        `data: [DONE]` — the OpenAI-style contract LLM clients expect.
        A mid-stream failure drops the connection (a JSON error would
        corrupt the stream; the truncated stream is the signal)."""
        from skypilot_tpu.serve.load_balancer import (end_chunks,
                                                      write_chunk)
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.send_header("Cache-Control", "no-cache")
        if resume_len:
            # Acknowledges the resume admission to the splicing LB:
            # this stream's first event is the token at absolute
            # position ``resume_len``, not position 0.
            self.send_header("X-STPU-Resume", str(resume_len))
        self.send_header("Transfer-Encoding", "chunked")
        self.end_headers()

        def emit(payload: str) -> None:
            write_chunk(self.wfile, f"data: {payload}\n\n".encode())

        t0 = time.perf_counter() if span is not None else 0.0
        sent = 0
        try:
            for tok in first_tokens:
                emit(json.dumps({"token": int(tok)}))
                sent += 1
            for tok in rest_iter:
                emit(json.dumps({"token": int(tok)}))
                sent += 1
            if reqlog.ENABLED:
                self._emit_stats_frame(req)
            emit("[DONE]")
            end_chunks(self.wfile)
            if span is not None:
                # Stream-delivery child span: first flush → [DONE].
                tracing.record_span("replica.stream", "replica",
                                    span.context(), start_mono=t0,
                                    attrs={"tokens": sent})
        except Exception:  # noqa: BLE001 — client gone / engine died
            req.cancel()  # free the slot; don't decode into a void
            self.close_connection = True
            if span is not None:
                tracing.record_span("replica.stream", "replica",
                                    span.context(), start_mono=t0,
                                    status="error",
                                    attrs={"tokens": sent,
                                           "aborted": True})

    def _emit_stats_frame(self, req) -> None:
        """Trailing ``event: stats`` SSE frame (reqlog armed only): the
        engine half of the wide-event request record, assembled by
        _free_slot and readable once the token iterator exhausts
        (_DONE is queued after the record is attached), enriched with
        the engine-level fields the slot cannot see (quant modes,
        restarts survived). The LB strips this frame from the client
        stream and folds it into its half; a legacy LB/custom client
        that does not strip must ignore non-``data:``-only SSE events
        per the SSE spec. Emission failures fall through to _sse's
        abort path like any other mid-stream write error."""
        from skypilot_tpu.serve.load_balancer import write_chunk
        half = getattr(req, "reqlog_record", None)
        if half is None:
            return
        engine = self.server_ctx["engine"]
        kv = engine.kv_config()
        half["kv_quant"] = bool(kv.get("kv_quant"))
        half["weight_quant"] = bool(kv.get("weight_quant"))
        half["restarts"] = engine.restarts
        write_chunk(self.wfile,
                    b"event: stats\ndata: "
                    + json.dumps(half, default=str).encode()
                    + b"\n\n")


def preempt_notice_watch(notice: threading.Event,
                         poll: float = None) -> None:
    """Watch the provider's preemption metadata signal.

    Real deployments poll the cloud metadata endpoint (e.g. the GCE
    ``instance/preempted`` key); this repro's signal source is the
    fault point ``replica.preempt_notice`` — an injected fault IS the
    notice, which makes the whole replace-ahead path game-day drivable.
    On a notice: set the shared event (surfaced via /health as
    ``preempt_notice: true``) and stop — the notice is terminal for
    this replica's lifetime; the controller takes it from there.
    """
    if poll is None:
        poll = PREEMPT_NOTICE_POLL
    while not notice.is_set():
        try:
            if fault_injection.ENABLED:
                fault_injection.fire("replica.preempt_notice")
        except fault_injection.InjectedFault:
            notice.set()
            _PREEMPT_NOTICES.inc()
            return
        time.sleep(poll)


def serve(cfg: llama.LlamaConfig, params, port: int,
          ready_event: threading.Event = None,
          engine_slots: int = None,
          prefix_cache_mb: float = None,
          stream_timeout: float = None,
          engine_max_restarts: int = None,
          engine_restart_backoff: float = None,
          topology: "gang_replica.ReplicaTopology" = None,
          mesh=None, rules=None,
          gang: "gang_replica.GangLeader" = None,
          kv_pool_blocks: int = None,
          kv_block_tokens: int = None,
          kv_quant: bool = None,
          weight_quant: bool = None,
          spec_k: int = None,
          spec_ngram: int = None,
          spec_min_accept: float = None
          ) -> ThreadingHTTPServer:
    """Start the replica server: requests are served through the
    continuous-batching decode engine with ``engine_slots`` slots
    (default: env STPU_ENGINE_SLOTS or 4; at least 1).
    ``prefix_cache_mb`` (default: env STPU_PREFIX_CACHE_MB or 64) is
    the host-RAM KV spill tier budget in MiB under the pool's trie —
    evicted prefix blocks spill D2H and re-admit H2D on a warm match;
    0 turns the tier off.
    ``stream_timeout`` (default: env STPU_STREAM_TIMEOUT or 600) is the
    per-token wait before a wedged engine surfaces as a clean error.
    ``kv_quant``/``weight_quant`` (default: env STPU_KV_QUANT /
    STPU_WEIGHT_QUANT or 0) serve int8 KV blocks / int8 params —
    ~2x KV capacity per HBM byte, parity-gated (NOT bit-identical).
    ``spec_k`` (default: env STPU_SPEC_K or 0) arms self-speculative
    decoding — k n-gram-drafted tokens per slot verified in one
    batched forward, bit-identical output.
    The engine runs under an EngineSupervisor: a crashed compute loop
    flips /health to 503 and is restarted with fresh state (capped
    backoff, ``engine_max_restarts`` consecutive fast failures →
    permanently down so the replica manager replaces the replica).

    Sharded replicas (gang_replica.py): ``mesh``/``rules`` make the
    engine tensor-parallel (params must arrive pre-sharded), and
    ``gang`` is host 0's GangLeader — admitted requests broadcast to
    followers, /health covers gang membership, drain propagates, and
    an engine crash-restart restarts every host's engine."""
    if engine_slots is None:
        engine_slots = ENGINE_SLOTS
    if engine_slots < 1:
        raise ValueError(NO_ENGINE_SLOTS)
    if prefix_cache_mb is None:
        prefix_cache_mb = ENGINE_PREFIX_CACHE_MB
    if stream_timeout is None:
        stream_timeout = STREAM_TIMEOUT_SECONDS
    if engine_max_restarts is None:
        engine_max_restarts = ENGINE_MAX_RESTARTS
    if engine_restart_backoff is None:
        engine_restart_backoff = ENGINE_RESTART_BACKOFF
    if kv_pool_blocks is None:
        kv_pool_blocks = ENGINE_KV_POOL_BLOCKS
    if kv_block_tokens is None:
        kv_block_tokens = ENGINE_KV_BLOCK_TOKENS
    if kv_quant is None:
        kv_quant = ENGINE_KV_QUANT
    if weight_quant is None:
        weight_quant = ENGINE_WEIGHT_QUANT
    if spec_k is None:
        spec_k = ENGINE_SPEC_K
    if spec_ngram is None:
        spec_ngram = ENGINE_SPEC_NGRAM
    if spec_min_accept is None:
        spec_min_accept = ENGINE_SPEC_MIN_ACCEPT
    phases.import_done()
    ctx = {"ready": ready_event or threading.Event(),
           "stream_timeout": float(stream_timeout), "gang": gang,
           "warmup_error": None, "device": mesh_lib.device_info(),
           "param_bytes": mesh_lib.bytes_per_device(params),
           "gang_admit_lock": threading.Lock(),
           "preempt_notice": threading.Event(),
           "inflight": [0], "inflight_lock": threading.Lock()}
    _TOPOLOGY_INFO.labels(
        hosts=str(topology.hosts if topology else 1),
        tp=str(topology.tp if topology else 1)).set(1)
    first_build = [True]

    def _engine_factory():
        if gang is not None and not first_build[0]:
            # Supervisor crash-restart: followers rebuild in
            # lockstep or the gang serves from desynced caches.
            gang.broadcast_restart()
        first_build[0] = False
        # Start-up phase ``engine``: the pool laid out and allocated,
        # the trie. A restart comes through here again and adds to it.
        with phases.startup_phase("engine"):
            return decode_engine.DecodeEngine(
                cfg, params, slots=engine_slots,
                max_seq=MAX_PROMPT_TOKENS + MAX_GEN_TOKENS,
                prefix_cache_mb=prefix_cache_mb,
                mesh=mesh, rules=rules,
                kv_pool_blocks=int(kv_pool_blocks),
                kv_block_tokens=int(kv_block_tokens),
                kv_quant=bool(kv_quant),
                weight_quant=bool(weight_quant),
                spec_k=int(spec_k),
                spec_ngram=int(spec_ngram),
                spec_min_accept=float(spec_min_accept))

    ctx["engine"] = decode_engine.EngineSupervisor(
        _engine_factory, max_restarts=engine_max_restarts,
        backoff_base=engine_restart_backoff).start()

    handler = type("Handler", (_Handler,), {"server_ctx": ctx})
    httpd = ThreadingHTTPServer(("0.0.0.0", port), handler)
    httpd.engine = ctx["engine"]  # visible for shutdown/tests
    httpd.gang = gang

    def warmup():
        try:
            # Start-up phase ``warmup``: the chunk's and the step's
            # programs built (or read from the cache) and run once.
            with phases.startup_phase("warmup"):
                if gang is not None and not gang.wait_ready():
                    raise gang_replica.GangError(
                        f"the serving gang of {gang.topology.hosts} "
                        f"hosts did not form: {gang.members_info()}")
                ctx["engine"].warmup()
        except Exception as e:  # noqa: BLE001 — thread boundary: a
            # warm-up that dies in silence leaves /health "warming"
            # for ever; record the cause where probes read it.
            traceback.print_exc()
            print(f"serve_llm: warm-up failed: {type(e).__name__}: {e}",
                  file=sys.stderr, flush=True)
            ctx["warmup_error"] = f"{type(e).__name__}: {e}"
            return
        ctx["ready"].set()

    threading.Thread(target=warmup, daemon=True).start()
    if PREEMPT_NOTICE_POLL > 0:
        threading.Thread(target=preempt_notice_watch,
                         args=(ctx["preempt_notice"],),
                         daemon=True, name="preempt-watch").start()
    return httpd


def _resolve_kv(args) -> dict:
    """CLI flags > STPU_KV_* env > defaults — resolved ONCE and used
    for the local engine, the follower engines, and the gang kv-config
    handshake, so every host of a gang replica sizes its pool
    identically."""
    return {
        "pool_blocks": (int(args.kv_pool_blocks)
                        if args.kv_pool_blocks is not None
                        else ENGINE_KV_POOL_BLOCKS),
        "block_tokens": (int(args.kv_block_tokens)
                         if args.kv_block_tokens is not None
                         else ENGINE_KV_BLOCK_TOKENS),
        "kv_quant": (bool(args.kv_quant) if args.kv_quant is not None
                     else ENGINE_KV_QUANT),
        "weight_quant": (bool(args.weight_quant)
                         if args.weight_quant is not None
                         else ENGINE_WEIGHT_QUANT),
        "spec_k": (int(args.spec_k) if args.spec_k is not None
                   else ENGINE_SPEC_K),
        "spec_ngram": (int(args.spec_ngram)
                       if args.spec_ngram is not None
                       else ENGINE_SPEC_NGRAM),
        "spec_min_accept": (float(args.spec_min_accept)
                            if args.spec_min_accept is not None
                            else ENGINE_SPEC_MIN_ACCEPT),
        "prefix_cache_mb": (float(args.prefix_cache_mb)
                            if args.prefix_cache_mb is not None
                            else ENGINE_PREFIX_CACHE_MB),
    }


def _resolve_topology(args) -> "gang_replica.ReplicaTopology":
    """CLI flags > STPU_REPLICA_TOPOLOGY env (stamped by the replica
    manager) > unsharded default."""
    if args.replica_hosts or args.tp:
        hosts = int(args.replica_hosts or 1)
        tp = int(args.tp or 1)
        return gang_replica.ReplicaTopology(
            hosts=hosts, ici_axes={"tp": tp} if tp > 1 else {})
    return (gang_replica.ReplicaTopology.from_env()
            or gang_replica.ReplicaTopology())


def model_config(model: str, dtype: str = None):
    """The config a ``--model`` name (and ``--dtype`` override) selects."""
    cfg = {
        "tiny": llama.LlamaConfig.tiny,
        "8b": llama.LlamaConfig.llama3_8b,
        "mixtral-tiny": mixtral.MixtralConfig.tiny,
        "mixtral-8x7b": mixtral.MixtralConfig.mixtral_8x7b,
        "gemma-tiny": gemma.GemmaConfig.tiny,
        "gemma-2b": gemma.GemmaConfig.gemma_2b,
        "gemma-7b": gemma.GemmaConfig.gemma_7b,
        "deepseek-tiny": deepseek.DeepseekV3Config.tiny,
        "deepseek-v3-5l-ep16": deepseek.DeepseekV3Config.v3_5l_ep16,
        "brumby-tiny": brumby.BrumbyConfig.tiny,
        "brumby-14b-6l": brumby.BrumbyConfig.b14_6l,
        "phi4flash-tiny": phi4flash.Phi4FlashConfig.tiny,
        "phi-4-mini-flash": phi4flash.Phi4FlashConfig.mini_flash,
    }[model]()
    if dtype:
        cfg = dataclasses.replace(
            cfg, dtype={"bfloat16": jnp.bfloat16,
                        "float32": jnp.float32}[dtype])
    return cfg


def init_params(cfg, seed: int, mesh=None, rules=None):
    """Random parameters, created where they will live: under a mesh
    each leaf is generated directly into its sharding, so a model
    larger than one chip (gemma-7b over tp=4) never materialises on
    one. The values do not depend on the sharding."""
    phases.import_done()
    api = model_api(cfg)
    # Start-up phase ``weights``: the init traced, built (or read from
    # the cache) and dispatched. It ends on the host's return; what
    # the device still has to fill runs under the engine's build and
    # the warm-up's compile, which wait for it where they need it.
    with phases.startup_phase("weights"):
        shardings = None
        if mesh is not None:
            shardings = mesh_lib.tree_shardings(mesh, rules,
                                                api.param_specs(cfg))
        return jax.jit(functools.partial(api.init, cfg),
                       out_shardings=shardings)(
                           jax.random.PRNGKey(seed))


def _spawn_follower_cmd(args, rank: int, topology, leader_port: int):
    """Self-spawn dev gang (`--replica-hosts N` outside a gang launch):
    follower processes on THIS machine, carrying the same rank/env
    contract a gang-launched host would see (SKYPILOT_NODE_RANK +
    STPU_TRACE_CTX propagation)."""
    env = dict(os.environ)
    env[agent_constants.NODE_RANK] = str(rank)
    env[agent_constants.NUM_NODES] = str(topology.hosts)
    env[gang_replica.GANG_ADDR_ENV] = f"127.0.0.1:{leader_port}"
    env.update(tracing.child_env())
    argv = [sys.executable, "-m", "skypilot_tpu.recipes.serve_llm",
            "--model", args.model, "--seed", str(args.seed),
            "--port", str(args.port),
            "--replica-hosts", str(topology.hosts),
            "--tp", str(topology.tp)]
    if args.dtype:
        argv += ["--dtype", args.dtype]
    if args.engine_slots is not None:
        argv += ["--engine-slots", str(args.engine_slots)]
    if args.prefix_cache_mb is not None:
        argv += ["--prefix-cache-mb", str(args.prefix_cache_mb)]
    if args.kv_pool_blocks is not None:
        argv += ["--kv-pool-blocks", str(args.kv_pool_blocks)]
    if args.kv_block_tokens is not None:
        argv += ["--kv-block-tokens", str(args.kv_block_tokens)]
    if args.kv_quant is not None:
        argv += ["--kv-quant", str(int(args.kv_quant))]
    if args.weight_quant is not None:
        argv += ["--weight-quant", str(int(args.weight_quant))]
    if args.spec_k is not None:
        argv += ["--spec-k", str(args.spec_k)]
    if args.spec_ngram is not None:
        argv += ["--spec-ngram", str(args.spec_ngram)]
    if args.spec_min_accept is not None:
        argv += ["--spec-min-accept", str(args.spec_min_accept)]
    return subprocess.Popen(argv, env=env, start_new_session=True)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--model",
                   choices=["tiny", "8b", "mixtral-tiny", "mixtral-8x7b",
                            "gemma-tiny", "gemma-2b", "gemma-7b",
                            "deepseek-tiny", "deepseek-v3-5l-ep16",
                            "brumby-tiny", "brumby-14b-6l",
                            "phi4flash-tiny", "phi-4-mini-flash"],
                   default="tiny")
    p.add_argument("--port", type=int, default=8080)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--replica-hosts", type=int, default=None,
                   help="hosts in this replica's serving gang (default "
                        "env STPU_REPLICA_TOPOLOGY or 1). Outside a "
                        "gang launch, host 0 self-spawns the follower "
                        "processes — the single-machine dev analog of "
                        "a gang-scheduled slice. Every process of a "
                        "gang needs devices of its own: on one host "
                        "that works on the CPU platform only, since a "
                        "host's TPU chips belong to the first process "
                        "that takes them; a gang that cannot form "
                        "turns /health into warmup_failed")
    p.add_argument("--tp", type=int, default=None,
                   help="tensor-parallel degree over the replica's "
                        "devices (params + KV cache sharded via "
                        "parallel/mesh.py ShardingRules)")
    p.add_argument("--dtype", choices=["bfloat16", "float32"],
                   default=None,
                   help="override the model compute dtype (float32 "
                        "makes TP output bit-identical to the "
                        "unsharded engine; bfloat16 matches only to "
                        "bf16 rounding, like any resharding)")
    p.add_argument("--engine-slots", type=int, default=None,
                   help="decode-engine slots, at least 1 (default env "
                        "STPU_ENGINE_SLOTS or 4)")
    p.add_argument("--prefix-cache-mb", type=float, default=None,
                   help="host-RAM KV spill tier budget in MiB under "
                        "the paged trie: LRU-evicted prefix blocks "
                        "spill D2H and re-admit H2D on a warm match. "
                        "0 = tier off (evictions drop). Default env "
                        "STPU_PREFIX_CACHE_MB or 64")
    p.add_argument("--kv-pool-blocks", type=int, default=None,
                   help="KV pool size in blocks incl. scratch "
                        "(0 = auto: slots * max_seq / block + 1; "
                        "default env STPU_KV_POOL_BLOCKS)")
    p.add_argument("--kv-block-tokens", type=int, default=None,
                   help="paged-KV block size in tokens (also the "
                        "prefill chunk; 0 = the default 64-token "
                        "chunk; default env STPU_KV_BLOCK_TOKENS)")
    p.add_argument("--kv-quant", type=int, choices=(0, 1),
                   default=None,
                   help="1 stores int8 KV blocks (+ per-block/head "
                        "scales) in the paged pool — ~2x blocks at "
                        "the same HBM budget. "
                        "NOT bit-identical to bf16 (parity-gated by "
                        "tests/test_quant.py). Default env "
                        "STPU_KV_QUANT or 0")
    p.add_argument("--weight-quant", type=int, choices=(0, 1),
                   default=None,
                   help="1 serves int8 per-channel-quantized params "
                        "(matmul weights + embed/lm_head; norms, "
                        "LoRA and the MoE router stay full "
                        "precision). Default env STPU_WEIGHT_QUANT "
                        "or 0")
    p.add_argument("--spec-k", type=int, default=None,
                   help="speculative decoding: tokens drafted per "
                        "slot per step from the slot's own n-gram "
                        "history, verified in one batched forward (0 "
                        "disables; default env STPU_SPEC_K or 0). "
                        "Output is bit-identical either way — greedy "
                        "AND seeded sampling")
    p.add_argument("--spec-ngram", type=int, default=None,
                   help="draft matcher n-gram length (default env "
                        "STPU_SPEC_NGRAM or 3)")
    p.add_argument("--spec-min-accept", type=float, default=None,
                   help="per-slot acceptance-rate floor below which a "
                        "slot stops drafting (default env "
                        "STPU_SPEC_MIN_ACCEPT or 0.2)")
    p.add_argument("--stream-timeout", type=float, default=None,
                   help="seconds to wait for the NEXT token before "
                        "failing the request as engine-stalled "
                        "(default env STPU_STREAM_TIMEOUT or 600); "
                        "lower = faster wedged-device detection, "
                        "higher = tolerate slower models")
    p.add_argument("--engine-max-restarts", type=int, default=None,
                   help="consecutive fast engine-crash restarts before "
                        "the replica reports permanently unhealthy "
                        "(default env STPU_ENGINE_MAX_RESTARTS or 3)")
    p.add_argument("--lb-port", type=int, default=0,
                   help="also start an in-process load balancer on "
                        "this port fronting the replica — the "
                        "single-host dev analog of the `stpu serve` "
                        "data plane")
    p.add_argument("--lb-policy",
                   choices=sorted(
                       load_balancing_policies.POLICIES),
                   default=None,
                   help="routing policy for the --lb-port balancer; "
                        "prefix_affinity keeps shared-prefix traffic "
                        "on the replica whose prefix cache is warm. "
                        "Deployed services set "
                        "service.load_balancing_policy in the YAML "
                        "instead.")
    args = p.parse_args(argv)
    engine_slots = (args.engine_slots if args.engine_slots is not None
                    else ENGINE_SLOTS)
    if engine_slots < 1:
        p.error(NO_ENGINE_SLOTS)
    if args.lb_policy and not args.lb_port:
        p.error("--lb-policy only configures the --lb-port balancer; "
                "deployed services set service.load_balancing_policy "
                "in the YAML")

    topology = _resolve_topology(args)
    rank = int(os.environ.get(agent_constants.NODE_RANK, "0"))
    # Bring up jax.distributed from the gang env contract (federates
    # every host's chips on a real slice; non-fatal no-op elsewhere).
    distributed.initialize_from_env()
    compile_cache.enable()
    device = mesh_lib.device_info()
    print(f"serve_llm: model={args.model} platform={device['platform']} "
          f"device_kind={device['kind']!r} devices={device['count']} "
          f"topology={topology.label()}", flush=True)
    cfg = model_config(args.model, args.dtype)
    mesh, rules = gang_replica.build_mesh(topology)
    params = init_params(cfg, args.seed, mesh, rules)

    kv = _resolve_kv(args)
    # The handshake compares EFFECTIVE geometry (auto-sized pool
    # included), not raw knobs: two hosts with identical STPU_KV_* but
    # different slot counts would auto-size different pools and pass a
    # raw-knob check while diverging in admission.
    kv_geo = decode_engine.resolve_kv_geometry(
        slots=engine_slots,
        max_seq=MAX_PROMPT_TOKENS + MAX_GEN_TOKENS,
        kv_pool_blocks=kv["pool_blocks"],
        kv_block_tokens=kv["block_tokens"],
        kv_quant=kv["kv_quant"], weight_quant=kv["weight_quant"],
        spec_k=kv["spec_k"], spec_ngram=kv["spec_ngram"],
        spec_min_accept=kv["spec_min_accept"],
        host_cache_mb=kv["prefix_cache_mb"],
        family=family_name(cfg),
        tp=(mesh.devices.size if mesh is not None else 1),
        layout=kv_pool.pool_layout(cfg))
    if topology.hosts > 1 and rank > 0:
        # Non-zero hosts never front HTTP: they run the lockstep
        # follower loop against the leader's gang channel, mirroring
        # every submission into the same sharded engine.
        def _follower_engine():
            return decode_engine.DecodeEngine(
                cfg, params,
                slots=engine_slots,
                max_seq=MAX_PROMPT_TOKENS + MAX_GEN_TOKENS,
                prefix_cache_mb=kv["prefix_cache_mb"],
                mesh=mesh, rules=rules,
                kv_pool_blocks=kv["pool_blocks"],
                kv_block_tokens=kv["block_tokens"],
                kv_quant=kv["kv_quant"],
                weight_quant=kv["weight_quant"],
                spec_k=kv["spec_k"],
                spec_ngram=kv["spec_ngram"],
                spec_min_accept=kv["spec_min_accept"])

        sys.exit(gang_replica.follower_serve(
            _follower_engine, topology,
            gang_replica.follower_addr(args.port), rank,
            kv_config=kv_geo))

    gang = None
    if topology.hosts > 1:
        gang_launched = int(os.environ.get(
            agent_constants.NUM_NODES, "1")) > 1 and \
            not os.environ.get(gang_replica.GANG_ADDR_ENV)
        if gang_launched:
            # Followers derive the channel address from the env
            # contract (head ip + serving port + offset), so the bind
            # port is fixed.
            gang = gang_replica.GangLeader(
                topology,
                port=args.port + gang_replica.GANG_PORT_OFFSET,
                kv_config=kv_geo)
        else:
            # Self-spawn dev gang: OS-assigned channel port, followers
            # on this machine with the address stamped explicitly
            # (the lambda reads gang.port after construction binds it).
            gang = gang_replica.GangLeader(
                topology, spawn=lambda r: _spawn_follower_cmd(
                    args, r, topology, gang.port),
                kv_config=kv_geo)
            gang.start_followers()

    httpd = serve(cfg, params, args.port,
                  engine_slots=engine_slots,
                  prefix_cache_mb=kv["prefix_cache_mb"],
                  stream_timeout=args.stream_timeout,
                  engine_max_restarts=args.engine_max_restarts,
                  topology=topology, mesh=mesh, rules=rules,
                  gang=gang,
                  kv_pool_blocks=kv["pool_blocks"],
                  kv_block_tokens=kv["block_tokens"],
                  kv_quant=kv["kv_quant"],
                  weight_quant=kv["weight_quant"],
                  spec_k=kv["spec_k"], spec_ngram=kv["spec_ngram"],
                  spec_min_accept=kv["spec_min_accept"])
    if gang is not None:
        # Whole-gang restart rebuilds host 0's engine too.
        gang.set_engine_reset(httpd.engine.restart_now)

    def _term(signum, frame):
        del signum, frame
        # Flight recorder first: a SIGTERM'd replica's last step ring
        # is the only record of what it was doing when the teardown /
        # scale-down landed (armed replicas only — an unarmed ring is
        # empty and a dump per routine teardown would just be noise).
        if stepstats.ENABLED:
            stepstats.dump_flight("sigterm")
        if gang is not None:
            # SIGTERM propagates to every host: followers get an
            # explicit shutdown, self-spawned ones are reaped — no
            # orphan processes.
            gang.shutdown()
        os._exit(143)
    signal.signal(signal.SIGTERM, _term)
    if args.lb_port:
        from skypilot_tpu.serve import load_balancer as lb_lib
        policy = load_balancing_policies.make_policy(args.lb_policy)
        policy.set_ready_replicas([f"http://127.0.0.1:{args.port}"])
        lb_lib.run_load_balancer(args.lb_port, policy,
                                 lb_lib.RequestRecorder())
        print(f"serve_llm: LB ({args.lb_policy or 'round_robin'}) "
              f"on :{args.lb_port}", flush=True)
    print(f"serve_llm: listening on :{args.port}", flush=True)
    httpd.serve_forever()


if __name__ == "__main__":
    main()
