"""Slot-based continuous-batching decode engine over one paged KV pool.

The one way a request reaches a model: HTTP handler
(recipes/serve_llm.py) -> :class:`DecodeEngine` -> serve/kv_pool.py ->
models/* ``forward_with_paged_cache`` / ``verify_step_paged``. The
engine holds ONE device-resident pool of fixed-size KV blocks (block =
the prefill chunk) that backs every slot through per-slot block tables,
and runs one jitted decode step over all slots every iteration:

  * requests join MID-FLIGHT into free slots — the prompt is prefilled
    in fixed-size chunks interleaved with decode steps, so a long
    arriving prompt never stalls tokens already streaming from other
    slots for more than one chunk;
  * every slot sits at its own sequence position — the model's
    per-slot (B,) ``start_pos``/``valid_len`` contract masks each slot
    to its own valid prefix, and the split-KV attention loop gathers
    K/V through the table only up to the longest live frontier;
  * slots acquire blocks lazily as they prefill and decode; admission
    reserves the request's worst-case block count up front (free-block
    based, with deterministic FIFO head-of-line backpressure, so
    admitted work is never preempted). Finished slots free at once;
    stale K/V in a block handed out again is never attendable (masked
    until overwritten), the invariant the parity tests pin;
  * the pool IS the shared-prefix cache: identical leading tokens
    produce identical KV blocks (causal attention), so a trie maps
    chunk hashes to refcounted blocks, a hit is a block-table entry
    write (zero-copy, no host round-trip) and publish-on-free is a
    refcount transfer. At least one trailing prompt token is always
    prefilled so the first token is sampled from real logits.
    ``prefix_cache_mb`` sizes the host-RAM spill tier under the trie;
  * what a slot's memory is made of is the family's to say
    (kv_pool.pool_layout): blocks of tokens as above; blocks of tokens
    of which only the newest window's are kept, released BEHIND the
    sequence (:meth:`DecodeEngine._release_behind`); and a block that
    is a sequence's whole recurrent STATE, which every step rewrites,
    so that admission reserves a fixed number, nothing grows, and a
    prefix hit restores from a SNAPSHOT, copy-on-write
    (:meth:`DecodeEngine._chunk_target`). A slot may hold all three at
    once (models/phi4flash.py), each kind from a pool of its own, all
    of them named by the slot's one table row;
  * the pool is DONATED through all three jitted programs (prefill
    chunk, decode step, verify step), so the engine keeps one buffer
    from step to step. Donation names the buffer the result lands in,
    not what the program builds on the way: the layer scan SCANS the
    layer parameters and the layer index and CARRIES the stacked pool,
    each layer scattering rows into and gathering blocks from
    [layer, ...] of that one buffer
    (models/llama.forward_with_paged_cache). That no second pool
    exists inside a program is held by the compiled temporaries
    (tests/test_paged_kv.py::
    test_paged_entry_points_hold_one_pool_buffer), not by the
    donation.

The reference the engine's tokens are held to is the models' own
row-cache ``decode`` (models/llama.decode over ``forward_with_cache``):
bit-identical when the attention window's tile boundaries align.

Sampling is reproducible per request: the key for the token at
position p is fold_in(fold_in(root, seed), p), independent of which
slot the request landed in or what else shared the batch.

Quantized KV serving (``kv_quant=True`` / STPU_KV_QUANT=1): every
pool block stores int8 K/V codes plus ONE f32 scale per
(layer, block, kv_head) in a parallel scales array sized off the same
block table (models/llama.init_paged_cache(quantized=True)). Blocks
quantize on write inside paged_attention_block — symmetric absmax
codes with a grow-only per-block scale, so the common decode append
re-uses the resident codes exactly — and dequantize inside the
attention gather, folded into the f32 upcast the online-softmax tile
already performs, so _attn_tile stays the ONE shared attention kernel.
An int8+scale block is ~half the bytes of a bf16 block, so the same
HBM budget holds ~2x the blocks (auto-sizing doubles pool_blocks):
more concurrent slots AND more prefix-cache residency. Output is NOT
bit-identical to bf16 (quantization changes numerics by design) — the
gate is the parity suite in tests/test_quant.py (top-1 agreement +
perplexity bound per family). ``weight_quant`` rides the same flags:
params pass through models/*.quantize_params (int8 codes + per-output-
channel scales, TP sharding and donation preserved).

Self-speculative decoding (``spec_k > 0`` / STPU_SPEC_K): decode is
memory-bound — every 1-token step streams the whole KV prefix and the
params through HBM to emit ONE token per slot — so per-request speed
is capped by bandwidth no matter how well slots batch. Speculation is
the lever batching can't reach: a free n-gram / prompt-lookup matcher
over each slot's OWN token history (prompt + output; an O(1)
incremental index, no second model) drafts up to k tokens per slot
per step, and one batched forward verifies all k+1 positions at once
(models/*.verify_step_paged — the (B,) start_pos/valid_len contract
generalized to a (B, K+1) logits-at-positions window). Targets are
re-sampled with the engine's own fold_in(seed, pos) keys, so
acceptance is exact-match and the output stream is BIT-IDENTICAL to
non-speculative decode for greedy and seeded sampling alike (under
deterministic per-position keys, rejection sampling against a
deterministic draft degenerates to exact match — stronger than
distribution-preserving). A rejected suffix rolls back by truncating
the grown block-table tail back into the pool. Slots whose traffic
doesn't repeat (acceptance below STPU_SPEC_MIN_ACCEPT) stop drafting
automatically, so the worst case degrades to the plain step plus one
dict lookup.
"""
from __future__ import annotations

import collections
import functools
import queue
import threading
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.models import family_name, model_api
from skypilot_tpu.observability import events
from skypilot_tpu.observability import metrics
from skypilot_tpu.observability import phases
from skypilot_tpu.observability import reqlog
from skypilot_tpu.observability import stepstats
from skypilot_tpu.observability import tracing
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.serve import kv_pool
from skypilot_tpu.utils import fault_injection

# ----------------------------------------------------------------- metrics
_SLOTS_TOTAL = metrics.gauge(
    "stpu_engine_slots_total", "Decode-engine slots configured.")
_SLOTS_OCCUPIED = metrics.gauge(
    "stpu_engine_slots_occupied", "Decode-engine slots holding a live "
    "request (prefilling or decoding).")
_QUEUE_DEPTH = metrics.gauge(
    "stpu_engine_queue_depth", "Requests admitted but not yet assigned "
    "a slot.")
_TOKENS = metrics.counter(
    "stpu_engine_decode_tokens_total", "Tokens emitted by the engine.")
_TOK_RATE = metrics.histogram(
    "stpu_engine_decode_tokens_per_sec",
    "Per-step decode throughput (live slots / step wall time).",
    buckets=(1, 4, 16, 64, 256, 1024, 4096, 16384, 65536))
_TTFT = metrics.histogram(
    "stpu_engine_ttft_seconds",
    "Submit-to-first-token latency per request.",
    buckets=metrics.LATENCY_BUCKETS)
_REQUESTS = metrics.counter(
    "stpu_engine_requests_total", "Engine requests by outcome.",
    ("outcome",))
_PREFIX_HITS = metrics.counter(
    "stpu_engine_prefix_cache_hits_total",
    "Admissions that reused >= 1 cached prompt chunk.")
_PREFIX_MISSES = metrics.counter(
    "stpu_engine_prefix_cache_misses_total",
    "Admissions that found no cached prompt chunk.")
_PREFIX_SAVED = metrics.counter(
    "stpu_engine_prefill_tokens_saved_total",
    "Prompt tokens restored from the prefix cache instead of "
    "prefilled.")
_PREFIX_TTFT = metrics.histogram(
    "stpu_engine_prefix_ttft_seconds",
    "Submit-to-first-token latency split by prefix-cache outcome.",
    ("cache",), buckets=metrics.LATENCY_BUCKETS)
_KV_POOL_TOTAL = metrics.gauge(
    "stpu_engine_kv_pool_blocks_total",
    "Usable KV blocks in the paged pool (scratch block excluded).")
_KV_POOL_FREE = metrics.gauge(
    "stpu_engine_kv_pool_blocks_free",
    "KV pool blocks on the free list (neither a live slot nor the "
    "prefix trie holds them).")
_KV_POOL_PINNED = metrics.gauge(
    "stpu_engine_kv_pool_blocks_pinned",
    "Distinct KV pool blocks referenced by live slots (pinned "
    "against eviction).")
_KV_POOL_BLOCK_BYTES = metrics.gauge(
    "stpu_engine_kv_pool_block_bytes",
    "Device bytes per KV pool block across all layers (codes + "
    "scales when quantized) — pool HBM budget is this times "
    "blocks_total.")
_KV_QUANT_ENABLED = metrics.gauge(
    "stpu_engine_kv_quant_enabled",
    "1 while the paged pool stores int8 KV blocks (STPU_KV_QUANT), "
    "else 0 — info gauge, rides the LB /metrics merge.")
_WEIGHT_QUANT_ENABLED = metrics.gauge(
    "stpu_engine_weight_quant_enabled",
    "1 while the engine serves int8 quantized params "
    "(STPU_WEIGHT_QUANT), else 0 — info gauge.")
_STATE_SNAPSHOTS = metrics.counter(
    "stpu_engine_state_snapshots_total",
    "State snapshots of a family whose pool block is a sequence's whole "
    "state: taken = a chunk boundary's state became a prefix-trie node "
    "(the block a later chunk read and did not write), restored = an "
    "admission started from one, evicted = LRU took one back.",
    ("event",))
_CACHE_BLOCKS = metrics.gauge(
    "stpu_engine_cache_blocks",
    "Pool blocks in use by kind: global / window = distinct blocks of "
    "tokens that live slots' tables name (the full layers', the window "
    "layers'), state = live sequences' own state blocks, snapshot = "
    "prefix-trie nodes of a family with a state (each holds one block "
    "of every kind).", ("kind",))
_WINDOW_RELEASED = metrics.counter(
    "stpu_engine_window_blocks_released_total",
    "Window-layer blocks a live sequence gave back because it had "
    "moved past them (a refcount drop: the prefix trie may still hold "
    "the block).")
_ZERO_COPY_HITS = metrics.counter(
    "stpu_engine_prefix_zero_copy_hits_total",
    "Prefix-cache hits served by aliasing pool blocks into the "
    "slot's block table — no insert/gather copies, no host "
    "round-trip.")
_KV_HOST_BYTES = metrics.gauge(
    "stpu_engine_kv_host_bytes",
    "Bytes resident in the host-RAM KV spill tier (HostBlockPool), "
    "bounded by the --prefix-cache-mb / STPU_PREFIX_CACHE_MB budget.")
_KV_HOST_BLOCKS = metrics.gauge(
    "stpu_engine_kv_host_blocks",
    "Spilled KV blocks resident in the host tier.")
_KV_TIER_HITS = metrics.counter(
    "stpu_engine_kv_tier_hits_total",
    "Paged admissions by the deepest tier their prompt prefix "
    "reached: hbm = device-resident trie blocks aliased zero-copy; "
    "host = at least one block re-admitted H2D from the host tier; "
    "miss = no cached prefix.", ("tier",))
_KV_HOST_READMITS = metrics.counter(
    "stpu_engine_kv_host_readmitted_blocks_total",
    "KV blocks restored H2D from the host tier into freshly reserved "
    "pool blocks (warm re-hits paying one block transfer instead of "
    "a chunk prefill).")
_SPEC_DRAFTED = metrics.counter(
    "stpu_engine_spec_drafted_tokens_total",
    "Tokens drafted by the self-speculative n-gram matcher and "
    "submitted to a batched verify step.")
_SPEC_ACCEPTED = metrics.counter(
    "stpu_engine_spec_accepted_tokens_total",
    "Drafted tokens accepted by verification (emitted without their "
    "own decode step).")
_SPEC_ACCEPT_RATE = metrics.histogram(
    "stpu_engine_spec_accept_rate",
    "Per-verify-step draft acceptance rate (accepted / drafted).",
    buckets=(0.0, 0.1, 0.25, 0.5, 0.75, 0.9, 1.0))
_RESTARTS = metrics.counter(
    "stpu_engine_restarts_total",
    "Engine restarts by the supervisor after a compute-loop crash.")
_ENGINE_UP = metrics.gauge(
    "stpu_engine_up",
    "1 while the decode engine accepts work; 0 while it is failed, "
    "restarting, or permanently down.")

_QUEUE_WAIT = metrics.histogram(
    "stpu_engine_queue_wait_seconds",
    "Submit-to-slot-assigned wait per admitted request; with "
    "stpu_engine_prefill_seconds it splits stpu_engine_ttft_seconds.",
    buckets=metrics.LATENCY_BUCKETS)
_PREFILL_SECONDS = metrics.histogram(
    "stpu_engine_prefill_seconds",
    "Slot-assigned-to-first-token latency per request (chunked "
    "prefill, interleaved with other slots' chunks and decode steps).",
    buckets=metrics.LATENCY_BUCKETS)
# Edges 15 % apart from 5 ms to 500 ms: an iteration without a prefill
# chunk and one with (46 and 90 ms in PERF.md's cell 1, 40 and 75 in
# cell 2) are two modes a p95 must tell apart, and LATENCY_BUCKETS has
# 25, 50 and 100 ms there.
_ITL = metrics.histogram(
    "stpu_engine_itl_seconds",
    "Gap between two token emissions of one slot, from its second "
    "token on (tokens one verify step accepts together are 0 apart).",
    buckets=tuple(round(0.005 * 100 ** (i / 33), 5) for i in range(34))
    + (1.0, 2.5))
_MOE_ROUTED = metrics.counter(
    "stpu_moe_tokens_routed_total",
    "Token-expert pairs of decode steps that landed on an expert this "
    "rank holds (live slots only; families whose expert layer holds a "
    "share of the routed experts).")
_MOE_HIT = metrics.counter(
    "stpu_moe_experts_hit_total",
    "Held experts, summed over sparse layers, that at least one live "
    "slot's token of a decode step chose.")
_MOE_COMPUTED = metrics.counter(
    "stpu_moe_experts_computed_total",
    "Held experts, summed over sparse layers, that a decode step's "
    "program computed: what its expert loop ran over, read back "
    "beside the tokens. Equals stpu_moe_experts_hit_total where the "
    "program's decoding rows are the scheduler's.")
_ATTN_BLOCKS_READ = metrics.counter(
    "stpu_attn_blocks_read_total",
    "Key/value blocks that decode steps' attention reads fetched from "
    "the pool, summed over the layers that attend, counted by the "
    "step's program from the per-slot counts that bound its kernel's "
    "loops and read back beside the tokens (families whose step reads "
    "the decoding slots' visible blocks in place).")
_STEPS = metrics.counter(
    "stpu_engine_steps_total",
    "Device programs the engine loop dispatched, by kind: decode "
    "(1-token step), verify (speculative window), prefill (one "
    "chunk), restore (one host-tier block H2D).", ("kind",))
_STEP_KIND = {k: _STEPS.labels(kind=k)
              for k in ("decode", "verify", "prefill", "restore")}
_PREFILL_CHUNKS = metrics.counter(
    "stpu_engine_prefill_chunks_total",
    "Prefill chunks dispatched, by whether the chunk ends its prompt "
    "(only such a chunk's logits are sampled): a chunk that does not "
    "is one whose layers that keep no cache, and whose head, a family "
    "may leave out.", ("ends_prompt",))
_CHUNK_ENDS = {final: _PREFILL_CHUNKS.labels(
    ends_prompt="yes" if final else "no") for final in (True, False)}
_LOOKAHEAD = metrics.counter(
    "stpu_engine_lookahead_steps_total",
    "Decode steps dispatched while the step before was still unread "
    "on the device, so that the host's work between two steps ran "
    "under a step; over stpu_engine_steps_total{kind=decode,verify} "
    "it says how often the loop runs ahead.")
_LOOP_SECONDS = metrics.counter(
    "stpu_engine_loop_seconds_total",
    "Engine-thread seconds by loop phase; the phases partition an "
    "iteration, so their sum is the thread's time.", ("phase",))
# The loop's clock is observability/phases.py's: ``schedule.*`` ends
# where its program is dispatched, ``fetch`` is every blocking read of
# a device value, ``emit`` what the host does with the tokens, ``wait``
# the idle condition wait.
_PHASE_SECONDS = {p: _LOOP_SECONDS.labels(phase=p) for p in (
    "schedule.admit", "schedule.prefill", "schedule.decode", "fetch",
    "emit", "wait")}
# Why the device waited, counted by the loop itself: the device is
# idle in live traffic exactly when a program is dispatched and the
# newest program dispatched before it has already ended, which the
# host sees without blocking (``is_ready()`` of that program's
# result; 0.2-0.3 us a poll on the chip, PERF.md PR 38).
_STARVED = metrics.counter(
    "stpu_engine_starved_dispatches_total",
    "Programs dispatched into a device whose queue had drained (the "
    "newest program dispatched before had already ended), by kind; "
    "over stpu_engine_steps_total it says how often the host kept "
    "the device waiting.", ("kind",))
_STARVED_KIND = {k: _STARVED.labels(kind=k)
                 for k in ("decode", "verify", "prefill")}
_DRAINED_SECONDS = metrics.counter(
    "stpu_engine_drained_seconds_total",
    "Engine-thread seconds, by loop phase, from the first phase "
    "switch that found the device's queue drained to the next "
    "dispatch: a LOWER bound on the device's idle time (the queue "
    "drained somewhere inside the phase before that switch). Seconds "
    "under wait are an engine with no live work.", ("phase",))
_DRAINED = {p: _DRAINED_SECONDS.labels(phase=p) for p in _PHASE_SECONDS}
_LONG_PHASES = metrics.counter(
    "stpu_engine_long_phases_total",
    "Instances of a loop phase that lasted phases.LONG_PHASE_S (0.06 "
    "s) or more: a pause of the engine thread, named by the phase it "
    "struck (wait, whose condition wait times out, is never counted).",
    ("phase",))
_LONG_PHASE_SECONDS = metrics.counter(
    "stpu_engine_long_phase_seconds_total",
    "Seconds of those instances, whole.", ("phase",))


_DONE = object()          # end-of-stream sentinel on a request's queue


class EngineError(RuntimeError):
    """The engine rejected or failed a request."""


class Request:
    """One in-flight generation; tokens arrive on an internal queue."""

    def __init__(self, prompt: List[int], max_tokens: int,
                 temperature: float, seed: int, trace=None,
                 resume=None):
        self.prompt = [int(t) for t in prompt]
        # Resume admission: prior-emitted tokens become a prompt
        # extension. The sampling key for the token at absolute
        # position p is fold_in(fold_in(root, seed), p) regardless of
        # where prompt ends and generation begins, so prefilling
        # prompt + emitted and decoding with the ORIGINAL seed
        # continues the stream bit-identically from position
        # len(prompt) + len(resume). max_tokens stays "tokens still to
        # generate" — the caller subtracts what was already emitted.
        if resume:
            self.prompt.extend(int(t) for t in resume)
        self.max_tokens = int(max_tokens)
        self.temperature = float(temperature)
        self.seed = int(seed) & 0xFFFFFFFF
        # Speculative-decoding accounting (engine-set): tokens this
        # request's slot drafted / had accepted by verification. Zero
        # while speculation is off.
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.submitted_at = time.perf_counter()
        self.first_token_at: Optional[float] = None
        # Tokens handed to the client so far, and when the last one
        # was: the engine reads a token's value an iteration after it
        # dispatched the program that samples it, when the slot may
        # already serve another request, so what emission advances
        # lives here and not on the slot.
        self.emitted = 0
        self.emitted_at = 0.0
        self.error: Optional[str] = None
        self.cancelled = False
        # Distributed-tracing parent context (tracing.SpanContext from
        # the replica handler's span, or None): the engine emits
        # queue/prefix/prefill/decode child spans under it. Always
        # None while tracing is unarmed — the hot-path guards below
        # short-circuit on tracing.ENABLED first.
        self.trace = trace
        self.admitted_at: Optional[float] = None
        self.prefill_start: Optional[float] = None
        # Prefix-cache accounting, set by the engine: prompt tokens
        # restored from the pool, and model forward passes (chunk
        # prefills) actually run before the first token — the
        # deterministic steps-to-first-token the warm/cold tests and
        # the bench compare (wall TTFT carries the host's noise).
        self.cached_prompt_tokens = 0
        self.prefill_chunks = 0
        # Request-analytics accounting (observability/reqlog.py), only
        # ever written under ``reqlog.ENABLED`` guards: the request's
        # device-time share (step_dur/live_slots summed per decode
        # step), the KV tier its prefix matched, and the finished
        # engine-half record _finish_request attaches for the serve layer
        # to read after _DONE.
        self.device_time_s = 0.0
        self.kv_tier: Optional[str] = None
        self.reqlog_record: Optional[Dict[str, Any]] = None
        self._out: "queue.Queue[Any]" = queue.Queue()

    def cancel(self) -> None:
        """Ask the engine to stop decoding this request (the slot frees
        at the next step). Safe from any thread, e.g. on client
        disconnect mid-stream."""
        self.cancelled = True

    def stream(self, timeout: float = 600.0):
        """Yield token ids as the engine produces them; raises
        EngineError if the request failed or the engine produced no
        token within ``timeout`` (a wedged device must surface as a
        diagnosable error, not a bare queue.Empty)."""
        while True:
            try:
                item = self._out.get(timeout=timeout)
            except queue.Empty:
                self.cancel()
                raise EngineError(
                    f"no token within {timeout:.0f}s (engine stalled "
                    f"or overloaded)") from None
            if item is _DONE:
                if self.error:
                    raise EngineError(self.error)
                return
            yield item

    def result(self, timeout: float = 600.0) -> List[int]:
        """Block until the request finishes; returns all tokens."""
        return list(self.stream(timeout=timeout))

    # engine-side
    def _emit(self, token: int, now: float) -> None:
        """ONE emission seam for all three token producers (final
        prefill chunk, plain decode step, speculative verify step):
        the client queue, the token counter, the first-token and the
        inter-token histograms advance together. ``now`` is the
        instant the producer's fetch returned, shared by every token
        it brought."""
        if self.first_token_at is None:
            self.first_token_at = now
            _TTFT.observe(now - self.submitted_at)
            _PREFILL_SECONDS.observe(now - self.admitted_at)
        else:
            _ITL.observe(now - self.emitted_at)
        self.emitted_at = now
        self.emitted += 1
        self._out.put(int(token))
        _TOKENS.inc()

    def _finish(self, error: Optional[str] = None) -> None:
        self.error = error
        self._out.put(_DONE)


class _Slot:
    """Host-side state of one slot and its block table: what the
    engine knows when it DISPATCHES a program. Nothing here depends
    on a token's value; what arrives with the values is on the
    :class:`Request` and in the slot's draft history."""

    __slots__ = ("request", "pos", "generated", "prefilled",
                 "held", "cached", "blocks", "reserved", "pending",
                 "win_lo", "win_own", "win_reserved", "own_state",
                 "state_reserved",
                 "history", "ngram_index", "drafted", "accepted",
                 "spec_off")

    def __init__(self):
        self.request: Optional[Request] = None
        self.pos = 0          # valid length of the slot (= next write)
        self.generated = 0    # tokens whose sampling is dispatched
        self.prefilled = 0    # prompt tokens already prefilled
        self.held: List[Any] = []           # pinned prefix-pool nodes
        self.cached = 0       # prompt tokens restored from the pool
        self.blocks = 0       # valid token-block table entries
        self.reserved = 0     # token blocks still promised, unclaimed
        # Window kind: the lowest chunk index whose block is still held
        # (held: [win_lo, blocks)), which of those count against the
        # slot's own budget (not aliased, not adopted by the trie), and
        # the budget still unclaimed.
        self.win_lo = 0
        self.win_own: set = set()
        self.win_reserved = 0
        # State kind: whether table column 0 names a block of the
        # slot's own (else a snapshot or the scratch block), and the
        # reservation for it.
        self.own_state = False
        self.state_reserved = 0
        # Host-tier re-admits this slot still owes: (logical chunk
        # index, trie node, fetched host payload) in chunk order,
        # consumed one per engine iteration by _restore_one.
        self.pending: List[tuple] = []
        # Speculative decoding (spec_k > 0 only): the slot's full
        # token history (prompt + emitted), an incremental n-gram ->
        # last-start index over it (O(1) draft lookup), and the
        # drafted/accepted counters the auto-disable threshold and the
        # engine.verify span read.
        self.history: List[int] = []
        self.ngram_index: Dict[tuple, int] = {}
        self.drafted = 0
        self.accepted = 0
        self.spec_off = False


class _Unread:
    """A token vector some dispatched program leaves on the device,
    and whom its rows are for: ``rows`` holds (slot index or None,
    request, outcome or None) in emission order — row ``i`` of the
    vector is the request's next token, an outcome says that it is
    its last (the slot was retired when the program was dispatched),
    and no index means no token, only the end (a cancel). ``routing``
    is a step's report beside the tokens (:func:`_paged_step`): the
    count of key/value blocks its attention read, after deepseek's two
    held-expert arrays (chosen by each row, computed by the program);
    ``t0`` a decode step's dispatch instant (None for a prefill
    chunk)."""

    __slots__ = ("toks", "routing", "rows", "t0")

    def __init__(self, toks, rows, routing=None, t0=None):
        self.toks, self.routing, self.t0 = toks, routing, t0
        self.rows = collections.deque(rows)


# ------------------------------------------------------- jitted entry points
def _first_token(logits, toks, tok_row, seed, pos, temp):
    """What a prefill chunk does for the decode step that follows it:
    sample the token its last real position predicts (the request's
    own key, ``pos`` = the prompt's length) and write it into row
    ``tok_row`` of ``toks``, the step programs' input vector, ON THE
    DEVICE. The host reads the value one iteration later, with the
    step's. ``tok_row`` is the slot's on a prompt's final chunk and
    one past the last slot otherwise: the write is dropped, the
    program the same."""
    tok = _sample(logits, seed[None], pos[None], temp[None])
    return toks.at[tok_row].set(tok[0], mode="drop")


@functools.partial(jax.jit, static_argnums=(0, 8),
                   donate_argnums=(2,))
def _paged_prefill_chunk(cfg, params, cache, buf, table_row, start,
                         valid, wb, window, toks, tok_row, seed,
                         temp):
    """Prefill ONE chunk of ONE slot's prompt into the paged pool.

    buf: (P,) tokens for positions [start, start+P) of the slot (tail
    may be padding on the prompt's final chunk). ``valid`` is the
    absolute count of real tokens after this chunk — padding K/V
    written past it stays masked until decode steps overwrite it.
    ``table_row`` is the slot's block table (the attention gather
    path) and ``wb`` the physical block the chunk lands in (a
    whole-block write — chunks and blocks are the same granularity,
    which is what lets prefix hits alias whole blocks instead of
    splicing rows). The pool is donated: the write happens in place.
    Returns (``toks`` with the first token in ``tok_row``
    (:func:`_first_token`), pool). A ``tok_row`` past the last slot says
    the chunk does not end its prompt: a family whose later layers keep
    no cache may leave them out then (``ends_prompt``); the others
    ignore it, and their program does not compute it."""
    api = model_api(cfg)
    logits, cache, *_ = api.forward_with_paged_cache(
        cfg, params, buf[None, :], cache, table_row[None, :], start,
        valid_len=valid, logits_at=jnp.maximum(valid - start - 1, 0),
        window=window, write_block=wb,
        ends_prompt=tok_row < toks.shape[0])
    return _first_token(logits[:, 0], toks, tok_row, seed, valid,
                        temp), cache


@jax.jit
def _slice_block(cache, block):
    """D2H spill snapshot: every pool leaf's slice at physical block
    ``block`` (axis 1 — codes and scales alike) as fresh device
    buffers. Taking the slice pins the block's CONTENT: the XLA
    runtime orders later donated in-place writes to the pool after
    this read, so the drain thread can land the bytes while the block
    is already reallocated and being overwritten. ``block`` is traced,
    so one program serves every block id."""
    return {k: jax.lax.dynamic_index_in_dim(v, block, axis=1,
                                            keepdims=False)
            for k, v in cache.items()}


@functools.partial(jax.jit, donate_argnums=(0,))
def _host_restore_block(cache, block, parts):
    """Re-admit ONE spilled KV block H2D: write the uploaded per-leaf
    slices back at physical block ``block`` (axis 1 of every pool
    leaf). The pool is donated — the restore is an in-place update,
    preserving the paged engine's single-buffer invariant exactly as
    prefill chunks and decode steps do. ``block`` is traced: one
    program serves every restore."""
    return {k: jax.lax.dynamic_update_index_in_dim(
                cache[k], parts[k], block, axis=1)
            for k in cache}


@functools.partial(jax.jit, static_argnums=(0, 6),
                   donate_argnums=(2,))
def _paged_step(cfg, params, cache, toks, pos, table, window, temps,
                seeds):
    """One decode step over ALL slots through their block tables: each
    slot's new K/V row scatters into block ``table[b, pos//bt]``, and
    attention gathers every slot's valid prefix through its table.
    Slots that do not decode ride along with a table row of zeros (the
    scratch block; :meth:`DecodeEngine._step_table`) and are ignored
    host-side. The pool is donated (in-place update).

    A family whose step reports what it did gets the report back beside
    the tokens, ``((nxt, report), pool)``, so that the step's one
    blocking fetch brings it: a report ends in the key/value blocks the
    step's attention fetched (one scalar; phi4flash's is that alone),
    and deepseek's holds before it which held experts each token chose
    ((B, T, sparse layers, held) bool, handed on with a row a slot) and
    which of them the program computed ((sparse layers, held) bool)."""
    api = model_api(cfg)
    logits, cache, *routing = api.forward_with_paged_cache(
        cfg, params, toks[:, None], cache, table, pos, window=window)
    logits = logits[:, -1]
    nxt = _sample(logits, seeds, pos + 1, temps)
    if routing:
        first, *rest = routing[0]
        nxt = (nxt, (first[:, 0], *rest) if rest else (first,))
    return nxt, cache


def _sample_multi(logits, seeds, pos, temps):
    """Per-slot, per-column target sampling for a verify window:
    column j of ``logits`` (B, T, vocab) is the distribution of the
    token at absolute position pos + j + 1, so its key is the SAME
    fold_in(fold_in(root, seed), pos + j + 1) the 1-token step would
    fold — which is what makes speculative output bit-identical to
    non-speculative decode for greedy AND seeded sampling (under
    per-position keys, rejection sampling against a deterministic
    draft collapses to exact-match verification)."""
    t = logits.shape[1]
    positions = pos[:, None] + 1 + jnp.arange(t)[None, :]   # (B, T)
    return jax.vmap(
        lambda lg, p: _sample(lg, seeds, p, temps),
        in_axes=(1, 1), out_axes=1)(logits, positions)


def _accept_counts(toks, targets, spec_len):
    """Leading-match acceptance: drafts toks[:, 1:] are accepted up to
    the first position where the draft disagrees with the target the
    engine's sampler would have emitted (and never past the slot's
    real draft count ``spec_len``). Returns (B,) accepted counts."""
    k = toks.shape[1] - 1
    match = ((toks[:, 1:] == targets[:, :-1]) &
             (jnp.arange(k)[None, :] < spec_len[:, None]))
    return jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1),
                   axis=1)


def _verify_window(last, drafts):
    """(B, K+1) verify window: each slot's last sampled token, which
    never left the device, then its drafts (padding past spec_len)."""
    return jnp.concatenate([last[:, None], drafts], axis=1)


def _verified(toks, logits, pos, spec_len, temps, seeds):
    """Targets, accepted counts and each slot's new last token (the
    correction token after its accepted drafts: the next step's
    input, kept on the device like a plain step's result)."""
    targets = _sample_multi(logits, seeds, pos, temps)
    accepts = _accept_counts(toks, targets, spec_len)
    last = jnp.take_along_axis(targets, accepts[:, None], axis=1)[:, 0]
    return targets, accepts, last


@functools.partial(jax.jit, static_argnums=(0, 8),
                   donate_argnums=(2,))
def _paged_spec_step(cfg, params, cache, last, drafts, pos, spec_len,
                     table, window, temps, seeds):
    """One speculative verify step over ALL slots: each slot's window
    [last token, draft_1..draft_k, padding] forwards in one pass,
    writing and gathering through its block table (models
    verify_step_paged), targets are sampled per position with the
    engine's fold_in(seed, pos) keys, and drafts are accepted up to
    the first mismatch. Returns (targets (B, T), accepts (B,),
    last (B,), pool) — the engine emits targets[b, :accepts[b] + 1]
    per live slot, so the device->host transfer is two small int
    arrays, never the (B, T, vocab) logits. The pool is donated; the
    engine truncates the rejected suffix's blocks back afterwards
    (block-table truncate + reservation return)."""
    api = model_api(cfg)
    toks = _verify_window(last, drafts)
    logits, cache = api.verify_step_paged(cfg, params, toks, cache,
                                          table, pos, spec_len,
                                          window=window)
    return (*_verified(toks, logits, pos, spec_len, temps, seeds),
            cache)


@jax.jit
def _sample(logits, seeds, positions, temps):
    """Per-slot sampling, reproducible per request: the key for the
    token at position p is fold_in(fold_in(root, seed), p) — slot
    placement and batch composition never change a request's sample
    stream. temps == 0 is greedy."""
    root = jax.random.key(0)

    def one(seed, p, row, t):
        k = jax.random.fold_in(jax.random.fold_in(root, seed), p)
        return jax.random.categorical(k, row / jnp.maximum(t, 1e-4))

    sampled = jax.vmap(one)(seeds, positions, logits, temps)
    greedy = jnp.argmax(logits, axis=-1)
    return jnp.where(temps > 0.0, sampled, greedy).astype(jnp.int32)


def _default_split_kv_block() -> int:
    """The kernel's hand-pinned tile default — imported lazily so the
    ONE place the constant is consulted for geometry is this module's
    derivation, not a module-global rebinding sites can drift from."""
    from skypilot_tpu.models.llama import SPLIT_KV_BLOCK
    return SPLIT_KV_BLOCK


# Default prefill chunk / paged KV block size, tokens. Callers pass
# prefill_chunk=0 ("resolve it for me"): the tuning manifest may
# override, else this constant applies — the single derivation that
# used to be serve_llm's ENGINE_PREFILL_CHUNK literal at three call
# sites.
DEFAULT_PREFILL_CHUNK = 64


def resolve_kv_geometry(*, slots: int, max_seq: int,
                        prefill_chunk: int = 0,
                        kv_pool_blocks: int = 0,
                        kv_block_tokens: int = 0,
                        kv_quant: bool = False,
                        weight_quant: bool = False,
                        spec_k: int = 0, spec_ngram: int = 3,
                        spec_min_accept: float = 0.0,
                        block: int = 0, window_blocks: int = 0,
                        host_cache_mb: float = 0.0,
                        family: Optional[str] = None, tp: int = 1,
                        use_manifest: bool = True,
                        layout: Optional[kv_pool.PoolLayout] = None
                        ) -> Dict[str, Any]:
    """EFFECTIVE KV-cache geometry for an engine config — the single
    derivation DecodeEngine.__init__, kv_config() and the gang
    kv-handshake all share, so auto-sized values (pool blocks, shrunk
    chunk, attention window, table length) can never drift between
    what an engine actually runs and what the gang compares. Raw
    knobs are NOT comparable across hosts: two hosts with identical
    STPU_KV_* but different slot counts auto-size different pools.
    The speculative-decoding knobs ride along: draft/accept decisions
    are a pure function of the mirrored admission sequence ONLY when
    every host drafts identically, so a spec mismatch must fail the
    handshake like a pool mismatch would. So do the quantization
    flags: kv_quant halves bytes per block, so the AUTO pool sizing
    doubles — a leader/follower quant-flag drift means differently
    sized pools and divergent admission decisions, which the
    handshake's dict comparison now rejects for free.

    Tuned constants (skypilot_tpu/tune/): when ``family`` is given and
    ``use_manifest`` is left on, the sha-pinned tuning manifest is
    consulted for the key ``(family, batch-band(slots), tp,
    quant-mode)`` and supplies ``block`` (the attention tile the window
    mirrors), ``chunk``, ``window_blocks`` (gather window, in blocks) and
    ``spec_k`` — but ONLY for knobs the caller left at their 0
    sentinel: explicit arguments (CLI flags, env knobs, sweep
    candidates) always win over the manifest, and
    ``STPU_TUNE_MANIFEST=0`` disables the lookup outright. The
    manifest tag (payload-sha prefix, or "default") rides the output
    dict, so gang members that resolved geometry from DIFFERENT
    manifests fail the welcome handshake even if the constants
    happen to coincide — tuned geometry drifts are join-fatal exactly
    like kv/quant drifts.

    What the keys mean. ``chunk`` is the prefill chunk and the prefix
    trie's key unit, in tokens, for every family. ``layout`` is the
    family's kv_pool.PoolLayout (default: blocks of tokens alone) and
    ``pools`` the blocks of each kind it names, scratch included;
    ``pool_blocks`` is the first kind's. For blocks of tokens
    (``global``) ``chunk`` is also a block's size and the kind
    auto-sizes to ``slots * max_seq / chunk + 1`` (every slot at full
    length, plus scratch); where that is the only kind ``table_len`` is
    how many blocks one slot's table can name. A ``window`` kind
    auto-sizes to ``slots * layout.window_blocks(chunk)``. A ``state``
    kind (``seq_blocks`` = ``layout.state_blocks`` > 0) holds no tokens
    at all and auto-sizes to ``slots * seq_blocks``; every kind of a
    family with a state gains ``snapshot_blocks`` = max(2, 3 * slots //
    4) blocks for the prefix trie's nodes (and for the fresh block a
    chunk writes while the one it read becomes a snapshot), and a
    slot's token limit is then ``max_seq``, not its table's span.
    ``table_len`` counts the state's column, then ``max_seq / chunk``
    columns for each kind of token block the family has beside it."""
    max_seq = int(max_seq)
    manifest_tag = "default"
    if use_manifest and family:
        from skypilot_tpu.tune import manifest as tune_manifest
        entry, manifest_tag = tune_manifest.entry_for(
            family=family, slots=int(slots), tp=int(tp),
            kv_quant=bool(kv_quant), weight_quant=bool(weight_quant))
        if entry is not None:
            if not block:
                block = int(entry.get("block", 0))
            if not prefill_chunk and not kv_block_tokens:
                prefill_chunk = int(entry.get("chunk", 0))
            if not window_blocks:
                window_blocks = int(entry.get("window_blocks", 0))
            if not spec_k:
                spec_k = int(entry.get("spec_k", 0))
    if kv_block_tokens:
        prefill_chunk = int(kv_block_tokens)
    if not prefill_chunk:
        prefill_chunk = DEFAULT_PREFILL_CHUNK
    chunk = max(min(int(prefill_chunk), max_seq), 1)
    while max_seq % chunk:
        chunk //= 2
    # The row-cache reference's attention tile (models/llama.
    # _split_kv_attention), tuned or default, clamped to max_seq: the
    # width the attention window mirrors unless window_blocks says
    # otherwise.
    block_eff = max(min(int(block) or _default_split_kv_block(),
                        max_seq), 1)
    # Auto sizing: slots * max_seq tokens of bf16 KV plus the scratch
    # block. An int8 block (codes + one f32 scale per layer/head) is
    # ~half the bytes, so the same budget holds 2x the blocks.
    layout = layout or kv_pool.PoolLayout()
    seq_blocks = layout.state_blocks
    snapshot_blocks = max(2, 3 * int(slots) // 4) if seq_blocks else 0
    span = max_seq // chunk
    per_slot = {"global": (2 if kv_quant else 1) * span,
                "window": min(span, layout.window_blocks(chunk)),
                "state": seq_blocks}
    pools = {kind: int(slots) * per_slot[kind] + snapshot_blocks + 1
             for kind in layout.kinds()}
    first = layout.kinds()[0]
    total = pools[first] = int(kv_pool_blocks) or pools[first]
    if window_blocks:
        window = max(min(int(window_blocks) * chunk,
                         max_seq // chunk * chunk), chunk)
    else:
        # Mirror the reference's tile so the tile boundaries align
        # (the bit-parity condition), floored to whole blocks.
        window = max(block_eff // chunk * chunk, chunk)
    nbw = window // chunk
    # The host spill-tier budget (MiB) rides the geometry dict: the
    # tier changes eviction outcomes and therefore admission timing,
    # so a leader/follower budget drift is join-fatal via the same
    # welcome comparison as a pool or quant drift.
    # "paged": 1 says nothing any more (there is one engine); it stays
    # because chip_smoke.py, which a deleting PR may not edit, reads it.
    return {
        "paged": 1, "slots": int(slots), "max_seq": max_seq,
        "chunk": chunk,
        "block": block_eff, "manifest": manifest_tag,
        "kv_quant": int(bool(kv_quant)),
        "weight_quant": int(bool(weight_quant)),
        "spec_k": int(spec_k), "spec_ngram": int(spec_ngram),
        "spec_min_accept": float(spec_min_accept),
        "pool_blocks": total, "window": window,
        "table_len": (seq_blocks + (len(pools) - 1) * span if seq_blocks
                      else -(-(total - 1) // nbw) * nbw),
        "seq_blocks": seq_blocks, "snapshot_blocks": snapshot_blocks,
        "pools": pools, "host_mb": float(host_cache_mb)}


class DecodeEngine:
    """Fixed-slot continuous-batching scheduler over one paged KV pool.

    One background thread owns all device compute: each iteration it
    (1) admits queued requests into free slots, (2) advances at most
    one pending prefill by one chunk, (3) dispatches one batched decode
    step for every live slot — so prefill of a joining request
    interleaves with, instead of blocking, in-flight decode — and only
    then (4) reads the tokens of the iteration BEFORE and hands them
    to the clients. Sampled tokens go from one program to the next on
    the device (``_toks``), so the host runs one step behind the
    device and its work between two steps is hidden under a step
    (:meth:`_decode_step`, PERF.md PR 29). A slot's life has two
    halves with one owner each: what is known at dispatch
    (:meth:`_retire`) and what arrives with the values
    (:meth:`_land`, :meth:`_finish_request`).
    """

    def __init__(self, cfg, params, *, slots: int = 4,
                 max_seq: int = 1024, prefill_chunk: int = 0,
                 max_queue: int = 256, prefix_cache_mb: float = 0.0,
                 mesh=None, rules=None,
                 kv_pool_blocks: int = 0, kv_block_tokens: int = 0,
                 kv_quant: bool = False, weight_quant: bool = False,
                 spec_k: int = 0, spec_ngram: int = 3,
                 spec_min_accept: float = 0.0, block: int = 0,
                 window_blocks: int = 0, use_manifest: bool = True):
        # prefix_cache_mb is the HOST-TIER byte budget (MiB) for the
        # pool's trie: evicted prefix blocks spill D2H into a bounded
        # host pool and re-admit H2D on a warm match. 0 turns the tier
        # off (evictions drop the leaf).
        host_mb = float(prefix_cache_mb or 0.0)
        if slots < 1:
            raise ValueError("slots must be >= 1")
        if spec_k < 0:
            raise ValueError("spec_k must be >= 0 (0 disables)")
        if spec_k and spec_ngram < 1:
            raise ValueError("spec_ngram must be >= 1")
        self._cfg = cfg
        self._api = model_api(cfg)
        # The one question about the pool's accounting, asked once:
        # which kinds of block a slot holds (kv_pool.PoolLayout).
        self._layout = kv_pool.pool_layout(cfg)
        # int8 weight serving: quantize here (idempotent — params may
        # arrive pre-quantized from a checkpoint) and, under a mesh,
        # re-place by the QUANTIZED spec tree so codes shard like the
        # weights they encode and scales ride their output channel.
        self._weight_quant = bool(weight_quant)
        if self._weight_quant and not self._api.params_quantized(params):
            params = self._api.quantize_params(cfg, params)
            if mesh is not None:
                from skypilot_tpu.serve import gang_replica
                params = gang_replica.shard_params(cfg, params, mesh,
                                                   rules)
        self._params = params
        self._slots = [_Slot() for _ in range(slots)]
        self._kv_quant = bool(kv_quant)
        # Self-speculative decoding (module docstring): k drafted
        # tokens per slot per step, verified in one batched forward.
        # 0 disables.
        self._spec_k = int(spec_k)
        self._spec_ngram = int(spec_ngram)
        self._spec_min_accept = float(spec_min_accept)
        # Per-verify-step telemetry scratch (consumed by _record_step
        # while stepstats is armed).
        self._step_spec_drafted = 0
        self._step_spec_accepted = 0
        self.peak_live_slots = 0
        # Tensor-parallel serving (serve/gang_replica.py): with a mesh,
        # params arrive pre-sharded (ShardingRules over param_specs)
        # and the KV pool is placed by cache_specs — the jitted entry
        # points are unchanged, GSPMD partitions them from the operand
        # shardings and donation still aliases in place (pinned by
        # tests/test_sharded_replica.py).
        self._mesh = mesh
        self._rules = rules
        # The prefill chunk is the pool's BLOCK size — blocks and
        # chunks being the same unit is what makes a prefix hit a
        # whole-block alias. The derivation lives in
        # resolve_kv_geometry so the gang handshake compares exactly
        # what this engine runs.
        geo = resolve_kv_geometry(
            slots=slots, max_seq=max_seq,
            prefill_chunk=prefill_chunk,
            kv_pool_blocks=kv_pool_blocks,
            kv_block_tokens=kv_block_tokens,
            kv_quant=self._kv_quant,
            weight_quant=self._weight_quant, spec_k=self._spec_k,
            spec_ngram=self._spec_ngram,
            spec_min_accept=self._spec_min_accept,
            block=block, window_blocks=window_blocks,
            host_cache_mb=host_mb,
            family=family_name(cfg),
            tp=(mesh.devices.size if mesh is not None else 1),
            use_manifest=use_manifest, layout=self._layout)
        self._kv_geometry = geo
        chunk = geo["chunk"]
        self._chunk = chunk
        # Tuned constants may enable drafting even when the caller
        # passed the 0 sentinel — read the EFFECTIVE value back from
        # the geometry, the same dict the handshake compares.
        self._spec_k = geo["spec_k"]
        refuse_options = getattr(self._api, "refuse_engine_options", None)
        if refuse_options is not None:
            refuse_options(cfg, spec_k=self._spec_k,
                           host_cache_mb=geo["host_mb"])
        self._max_queue = int(max_queue)
        # Host-RAM spill tier state (host_mb > 0 only, but the
        # attributes always exist — shutdown and introspection touch
        # them on every engine).
        self._host_pool: Optional[kv_pool.HostBlockPool] = None
        self._spill_q: Optional["queue.Queue"] = None
        self._spill_thread: Optional[threading.Thread] = None
        self._spill_stop = False
        self._readmitted_blocks = 0
        # ONE device-resident pool for slot growth AND the prefix
        # cache (serve/kv_pool.py), sized and tiled by
        # resolve_kv_geometry.
        # ... one BlockPool a kind of block the layout names, each
        # with block ids of its own. ``_pool`` is the first kind's: the
        # one the prefix trie's ``node.block`` lives in.
        layout = self._layout
        self._win_blocks = layout.window_blocks(chunk)
        self._pools = {
            kind: kv_pool.BlockPool(
                n, chunk,
                seq_blocks=layout.state_blocks if kind == "state" else 0)
            for kind, n in geo["pools"].items()}
        kinds = layout.kinds()
        self._pool = self._pools[kinds[0]]
        self._window_pool = self._pools.get("window")
        self._state_pool = self._pools.get("state")
        self._window = geo["window"]    # attention tile, whole blocks
        # Per-slot LOGICAL capacity is the pool, not a row: the table
        # can address every usable block (rounded up so the last
        # attention tile's table slice stays in bounds). One row names
        # all of a slot's blocks: the state's in column 0 where there
        # is one, then the token blocks by chunk index from ``_tok0``,
        # then the window's from ``_win0``.
        self._table_len = geo["table_len"]
        self._table = np.zeros((slots, self._table_len), np.int32)
        self._tok0 = layout.state_blocks
        self._win0 = self._tok0 + int(max_seq) // chunk
        make_cache = functools.partial(
            self._api.init_paged_cache, cfg,
            geo["pool_blocks"] if len(kinds) == 1 else geo["pools"],
            chunk, quantized=self._kv_quant)
        # Host-RAM spill tier under the trie: evictions demote blocks
        # D2H through a bounded queue drained off the compute thread;
        # warm matches re-admit H2D during the prefill phase
        # (_restore_one). Budget 0 = tier off.
        if geo["host_mb"] > 0:
            self._host_pool = kv_pool.HostBlockPool(
                int(geo["host_mb"] * (1 << 20)))
            self._spill_q = queue.Queue(maxsize=32)
            self._spill_thread = threading.Thread(
                target=self._drain_spills, name="kv-spill-drain",
                daemon=True)
            self._spill_thread.start()
        # The pool IS the prefix cache: the trie is just an index over
        # blocks, so it is always on (a hit is a table write; a miss
        # costs one dict walk).
        self.prefix_cache = kv_pool.PagedPrefixCache(
            self._pool, chunk, host_pool=self._host_pool,
            spill=(self._spill_block
                   if self._host_pool is not None else None),
            extra_pools={k: self._pools[k] for k in kinds[1:]})
        _KV_POOL_TOTAL.set(self._pool.usable_blocks)
        _KV_POOL_FREE.set(self._pool.free_blocks())
        if mesh is None:
            self._cache = make_cache()
        else:
            # Created directly into its shards: at tp=4 the whole pool
            # never sits on one chip beside that chip's weights.
            from skypilot_tpu.serve import gang_replica
            shardings = gang_replica.cache_shardings(cfg, mesh, rules)
            # cache_shardings always carries k_scale/v_scale entries;
            # a bf16 cache has no such leaves, so filter by the tree
            # the engine actually holds.
            self._cache = jax.jit(make_cache, out_shardings={
                k: shardings[k] for k in jax.eval_shape(make_cache)})()
        # Taken once: every step donates the cache, so its leaves may
        # not be read from another thread; shapes and shardings stay.
        self._cache_device_bytes = mesh_lib.bytes_per_device(self._cache)
        # Every slot's last sampled token, ON THE DEVICE from one
        # program to the next: a step's result is the next step's
        # input as it stands, a final prefill chunk writes its first
        # token into its slot's row (_first_token). The host reads
        # the values to hand them to the clients, never to feed them
        # back — which is what lets it dispatch iteration k before it
        # has read iteration k-1 (_decode_step). Free rows hold
        # whatever was sampled there last and are ignored.
        self._toks = jax.device_put(
            np.zeros((slots,), np.int32),
            None if mesh is None else jax.sharding.NamedSharding(
                mesh, jax.sharding.PartitionSpec()))
        # Dispatched and not yet read: by the iteration under way,
        # and by the one before it (due at this one's end).
        self._fresh: List[_Unread] = []
        self._behind: List[_Unread] = []
        self._landed_at = 0.0           # perf_counter() of the last read
        # Requests between the two halves of their end: slot retired,
        # last token still unread (in_flight() counts them).
        self._retiring = 0
        # A slot whose next write would be the last position ends: its
        # table's span, or (a state holds no positions) max_seq.
        self._limit = (int(max_seq) if layout.state_blocks
                       else self._table_len * chunk)
        _KV_POOL_BLOCK_BYTES.set(kv_pool.block_bytes_for(
            cfg, chunk, quantized=self._kv_quant))
        _KV_QUANT_ENABLED.set(int(self._kv_quant))
        _WEIGHT_QUANT_ENABLED.set(int(self._weight_quant))
        self._waiting: "collections.deque[Request]" = collections.deque()
        self._cond = threading.Condition()
        # Engine thread only, both: the loop's phase and, beside it,
        # whether the device's queue is known to have drained
        # (:meth:`_enter`, :meth:`_dispatching`).
        self._phase = phases._PhaseClock(
            "stpu.engine.", _LOOP_SECONDS, tuple(_PHASE_SECONDS),
            long_count=_LONG_PHASES, long_seconds=_LONG_PHASE_SECONDS,
            long_phases=[p for p in _PHASE_SECONDS if p != "wait"])
        self._newest = None         # a result of the newest program
        self._drained_in: Optional[str] = None   # the phase it is
        self._drained_t0 = 0.0      # charged to, and from when
        self._starved_span = None
        self._stop = False
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        self._failed: Optional[str] = None
        # Step-telemetry scratch (stepstats armed only): the decode
        # step's dispatch/device split, consumed by the loop's record.
        self._step_dispatch_s: Optional[float] = None
        self._step_device_s: Optional[float] = None
        # Flight-recorder dump written by the crash path, stamped into
        # the supervisor's engine_failed event.
        self.flightrec: Optional[str] = None
        _SLOTS_TOTAL.set(slots)

    # ------------------------------------------------------------- public
    def start(self) -> "DecodeEngine":
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="decode-engine", daemon=True)
            self._thread.start()
        return self

    def submit(self, prompt, max_tokens: int, temperature: float = 0.0,
               seed: int = 0, trace=None, resume=None) -> Request:
        """Enqueue a generation; returns the Request handle (stream()
        or result()). Raises EngineError on invalid size, full queue,
        or a dead engine. ``trace`` is an optional tracing.SpanContext
        to parent the engine's per-phase spans under.

        ``resume`` (list of previously-emitted token ids) admits a
        mid-stream continuation: the tokens prefill as a prompt
        extension — through the prefix trie / host tier like any
        prompt, zero-copy where the blocks survive — and emission
        starts at absolute position len(prompt) + len(resume) under
        the ORIGINAL seed, so the continuation is bit-identical to the
        uninterrupted run. ``max_tokens`` is the REMAINING budget."""
        req = Request(prompt, max_tokens, temperature, seed,
                      trace=trace, resume=resume)
        if not req.prompt:
            raise EngineError("empty prompt")
        if req.max_tokens < 1:
            raise EngineError("max_tokens must be >= 1")
        # The admission bound is POOL CAPACITY, not a per-slot row
        # length: a request fits if its worst-case block count does.
        need = self._pool.blocks_for(len(req.prompt) + req.max_tokens)
        if need > self._pool.usable_blocks:
            raise EngineError(
                f"prompt ({len(req.prompt)}) + max_tokens "
                f"({req.max_tokens}) exceeds the KV pool "
                f"({self._pool.usable_blocks} blocks x "
                f"{self._chunk} tokens)")
        if self._layout.state_blocks and \
                len(req.prompt) + req.max_tokens > self._limit:
            raise EngineError(
                f"prompt ({len(req.prompt)}) + max_tokens "
                f"({req.max_tokens}) exceeds the engine's max_seq "
                f"({self._limit})")
        with self._cond:
            if self._failed:
                raise EngineError(f"engine failed: {self._failed}")
            if self._stop:
                raise EngineError("engine is shut down")
            if self._draining:
                raise EngineError(
                    "engine draining (replica shutting down)")
            if len(self._waiting) >= self._max_queue:
                raise EngineError("engine queue full")
            self._waiting.append(req)
            _QUEUE_DEPTH.set(len(self._waiting))
            self._cond.notify()
        return req

    def warmup(self) -> None:
        """Compile the prefill-chunk and decode-step programs (one
        tiny request end to end). max_tokens=2 so the request survives
        past its prefill-sampled first token and forces one
        _paged_step — with max_tokens=1 the decode-step program would
        first compile on the first production request, stalling it for
        the full XLA compile."""
        self.start()
        self.submit([1], max_tokens=2).result(timeout=600.0)

    def drain(self) -> None:
        """Stop admitting new requests (submit raises EngineError);
        live slots keep decoding to completion. The graceful half of a
        replica scale-down: the manager polls in_flight() and tears the
        replica down once it hits zero."""
        with self._cond:
            self._draining = True
            self._cond.notify()

    def draining(self) -> bool:
        return self._draining

    def kv_config(self) -> Dict[str, Any]:
        """The engine's EFFECTIVE KV-cache geometry
        (resolve_kv_geometry output — auto-sized pool and the
        speculative-decoding knobs included), the piece of state a
        gang leader and its followers must agree on byte-for-byte or
        admission/backpressure (and draft/accept) decisions diverge
        across hosts. serve_llm derives the same dict via
        resolve_kv_geometry for the welcome handshake, and that
        function's docstring says what ``pool_blocks``, ``table_len``
        and ``chunk`` mean for a family whose pool has a sequence's
        state in it (``seq_blocks`` > 0; ``snapshot_blocks`` is the
        rule's share for prefix snapshots) or more than one kind of
        block (``pools``)."""
        return dict(self._kv_geometry)

    def cache_bytes_per_device(self) -> Dict[int, int]:
        """Device id -> KV-cache bytes resident there (what /perf
        reports beside the weights)."""
        return dict(self._cache_device_bytes)

    def in_flight(self) -> int:
        """Requests admitted or queued and not yet finished."""
        with self._cond:
            return (len(self._waiting) + len(self._live()) +
                    self._retiring)

    def failed(self) -> Optional[str]:
        """The error that killed the compute loop, if it died."""
        return self._failed

    def shutdown(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
        if self._spill_thread is not None:
            self._spill_stop = True
            self._spill_thread.join(timeout=10.0)

    # --------------------------------------------------- host KV tier
    def _spill_block(self, node) -> bool:
        """Offer an eviction victim to the host tier (called by the
        trie's evict_one on the compute thread). MUST NOT block: it
        snapshots the block's per-leaf slices (async device work),
        kicks D2H with copy_to_host_async — the checkpoint writer's
        overlap pattern — and hands the in-flight buffers to the drain
        thread. False declines the spill (injected fault, drain
        backlog, unreadable buffers) and the eviction degrades to a
        plain drop-on-evict."""
        if fault_injection.ENABLED:
            try:
                fault_injection.fire("engine.spill", block=node.block)
            except fault_injection.InjectedFault:
                return False
        if node.path in self._host_pool:
            # Inclusive tier: the bytes are already down (stored or in
            # flight) — demotion is free, no second D2H.
            return True
        if self._spill_q.full():
            # Bounded in-flight D2H: never queue-block an eviction on
            # a slow drain; dropping under backlog is the safe cheap
            # choice (the counter shows it).
            return False
        try:
            slices = _slice_block(self._cache, jnp.int32(node.block))
            for part in slices.values():
                start = getattr(part, "copy_to_host_async", None)
                if callable(start):
                    start()
        except RuntimeError:
            return False
        self._host_pool.mark_inflight(node.path)
        self._spill_q.put((node.path, slices))
        return True

    def _drain_spills(self) -> None:
        """Background D2H drain (daemon thread): land each in-flight
        spill's bytes on host (np.asarray finds the copy_to_host_async
        transfer done or rides it out) and store them in the host
        pool. The compute thread never joins this — a slow host path
        surfaces as spill-queue backpressure (drops), never as decode
        stalls."""
        while True:
            try:
                item = self._spill_q.get(timeout=0.1)
            except queue.Empty:
                if self._spill_stop:
                    return
                continue
            path, slices = item
            try:
                arrays = {k: np.asarray(v) for k, v in slices.items()}
            except Exception:  # noqa: BLE001 — deleted buffer / device
                # error mid-drain: this spill is lost, serving is not.
                self._host_pool.clear_inflight(path)
                continue
            self._host_pool.put(path, arrays)
            self._update_host_gauges()

    def _update_host_gauges(self) -> None:
        if self._host_pool is not None:
            s = self._host_pool.stats()
            _KV_HOST_BYTES.set(s["bytes"])
            _KV_HOST_BLOCKS.set(s["blocks"])

    def spill_in_flight(self) -> int:
        """Spills kicked D2H whose drain has not landed yet (0 = the
        host tier is quiescent — tests and the bench leg poll this)."""
        if self._host_pool is None:
            return 0
        return self._host_pool.stats()["inflight"]

    def host_tier_stats(self) -> Dict[str, Any]:
        """Host-tier introspection for /perf and the CLI tier line;
        {} while the tier is off (budget 0)."""
        if self._host_pool is None:
            return {}
        out = dict(self._host_pool.stats())
        out["budget_mb"] = self._kv_geometry["host_mb"]
        out["readmitted_blocks"] = self._readmitted_blocks
        trie = self.prefix_cache.stats()
        out["host_chunks"] = trie["host_chunks"]
        out["promotions"] = trie["promotions"]
        out["evict_spills"] = trie["spills"]
        out["evict_drops"] = trie["drops"]
        return out

    # ------------------------------------------------------------ internals
    # ------------------------------------------ why the device waited
    def _device_drained(self) -> bool:
        """Has the newest program dispatched already ended? One
        non-blocking poll of one of its results."""
        return self._newest is not None and self._newest.is_ready()

    def _enter(self, phase: Optional[str]) -> float:
        """Switch the loop's phase and keep the drained account beside
        it. At the first switch that finds the device's queue drained
        the loop opens a ``stpu.engine.starved`` span and polls no
        more; from that instant to the next dispatch
        (:meth:`_dispatching`) each phase's seconds also go to
        ``stpu_engine_drained_seconds_total{phase}``. A lower bound on
        the device's idle time: the queue drained somewhere inside the
        phase BEFORE that switch. Once the loop has waited with the
        queue drained the engine had run dry, and everything up to the
        next dispatch stays under ``wait``: an idle loop's looks for
        work, and the admission that ends the spell, are no slow host
        (they read as 0.18 % of an idle engine's time under
        ``schedule`` before; PERF.md PR 38)."""
        now = self._phase.enter(phase)
        if self._drained_in is not None:
            _DRAINED[self._drained_in].inc(now - self._drained_t0)
            self._drained_t0 = now
            if phase is None:
                self._starved_span.__exit__(None, None, None)
                self._drained_in = self._starved_span = None
            elif self._drained_in != "wait":
                self._drained_in = phase
        elif phase is not None and self._device_drained():
            self._drained_in, self._drained_t0 = phase, now
            self._starved_span = jax.profiler.TraceAnnotation(
                "stpu.engine.starved")
        return now

    def _dispatching(self, kind: Optional[str]) -> None:
        """The next statement dispatches a program of ``kind`` (None:
        one that is not counted by kind). If the device's queue has
        drained, this is a STARVED dispatch: the device sat idle until
        now. Close the drained account and the span here, before the
        call, so that the span ends inside the device's idle gap (a
        launch that follows an idle device is the anchor
        benchmarks/host_spans.py needs between the two planes'
        clocks)."""
        if self._drained_in is not None:
            _DRAINED[self._drained_in].inc(
                time.perf_counter() - self._drained_t0)
            self._starved_span.__exit__(None, None, None)
            self._drained_in = self._starved_span = None
        elif self._device_drained():
            # Drained since the last switch: a span of an instant.
            jax.profiler.TraceAnnotation(
                "stpu.engine.starved").__exit__(None, None, None)
        else:
            return
        if kind is not None:
            _STARVED_KIND[kind].inc()

    def _live(self) -> List[int]:
        return [i for i, s in enumerate(self._slots) if s.request]

    def _publish_paged(self, i: int) -> None:
        """Paged publish-on-free: ADOPT the slot's full prompt blocks
        into the trie — a refcount transfer (kv_pool.publish retains,
        the slot's own reference drops right after in _retire), not
        a gather. Zero device work, zero host copies. The final
        partial prompt block (prompt tail + generated tokens share it)
        is never published."""
        if self._layout.state_blocks:
            # A state block holds the state after everything the slot
            # has seen, not after a prompt chunk: its snapshots were
            # taken as it prefilled, with the token blocks that belong
            # to them (_chunk_target).
            return
        slot = self._slots[i]
        self.prefix_cache.publish(
            slot.request.prompt, slot.prefilled,
            lambda j: int(self._table[i, j]))

    def _release_paged(self, i: int) -> None:
        """Return every pool reference the slot holds, of every kind:
        unpin aliased prefix nodes (their token blocks are the table's
        first len(held)), release fresh token blocks (the table's
        [len(held), blocks)), the window blocks still held and the
        slot's own state block, hand back unused reservations.
        Idempotent at the slot level — held/blocks/reserved are
        cleared, so a second call is a no-op instead of a
        double-decrement."""
        slot = self._slots[i]
        lay, tok0 = self._layout, self._tok0
        if slot.pending:
            # Pending re-admits never took pool references — drop the
            # trie pins only (cancel / error before their restore ran;
            # the fetched payloads simply fall out of scope).
            self.prefix_cache.unpin_pending(
                [n for _, n, _ in slot.pending])
            slot.pending = []
        # A state alone pins only the snapshot it starts from.
        aliased = len(slot.held) if lay.tokens else 0
        if slot.held:
            self.prefix_cache.unpin(slot.held)
            slot.held = []
        for j in range(aliased, slot.blocks):
            self._pool.release(int(self._table[i, tok0 + j]))
        if lay.window:
            # Its own and the aliased alike: admission retained those.
            for j in range(slot.win_lo, slot.blocks):
                self._window_pool.release(
                    int(self._table[i, self._win0 + j]))
            self._window_pool.unreserve(slot.win_reserved)
            slot.win_lo = slot.win_reserved = 0
            slot.win_own = set()
        if lay.state_blocks:
            if slot.own_state:
                self._state_pool.release(int(self._table[i, 0]))
                slot.own_state = False
            self._state_pool.unreserve(slot.state_reserved)
            slot.state_reserved = 0
        self._table[i] = 0
        slot.blocks = 0
        if slot.reserved:
            self._pool.unreserve(slot.reserved)
            slot.reserved = 0
        _KV_POOL_FREE.set(self._pool.free_blocks())

    def _retire(self, i: int, error: Optional[str] = None) -> None:
        """The DISPATCH half of a slot's end: the program that samples
        the request's last token has been dispatched (or the request
        was cancelled, or the engine is going down), so the slot and
        its blocks are free for the next admission. Every program
        dispatched from here on is queued behind that one on the
        device, so a block handed out again is overwritten only after
        it was last read. The request itself ends when its last token
        has been read: :meth:`_finish_request`, an iteration later."""
        slot = self._slots[i]
        if error is None:
            # Refcount transfer into the trie BEFORE the slot's own
            # references drop; skipped on engine failure/shutdown
            # (device state not trustworthy).
            self._publish_paged(i)
        self._release_paged(i)
        self._retiring += 1      # before the slot stops counting
        slot.request = None
        slot.pos = slot.generated = slot.prefilled = 0
        slot.cached = 0
        slot.history = []
        slot.ngram_index = {}
        slot.drafted = slot.accepted = 0
        slot.spec_off = False
        # Gauge updated HERE so every free path (finish, cancel during
        # prefill, cache-full) is reflected even while the loop idles.
        _SLOTS_OCCUPIED.set(len(self._live()))

    def _finish_request(self, req: Request, outcome: str,
                        error: Optional[str] = None) -> None:
        """The FETCH half: every token the request will get has been
        handed over, so close its spans, attach its record and end its
        stream."""
        if tracing.ENABLED and req.trace is not None \
                and req.trace.sampled:
            # Decode child span: first token → last. A request that
            # died before its first token anchors at submit so the
            # failure still shows on the timeline.
            tracing.record_span(
                "engine.decode", "engine", req.trace,
                start_mono=(req.first_token_at or req.submitted_at),
                status="error" if error else "ok",
                attrs={"tokens": req.emitted, "outcome": outcome})
            if req.spec_drafted:
                # Speculative-verify child span: one retroactive
                # summary per request (a span per verify STEP would
                # be token-granular spam), so a trace shows how much
                # of the stream speculation paid for.
                tracing.record_span(
                    "engine.verify", "engine", req.trace,
                    start_mono=(req.first_token_at
                                or req.submitted_at),
                    attrs={"drafted": req.spec_drafted,
                           "accepted": req.spec_accepted,
                           "accept_rate": round(
                               req.spec_accepted / req.spec_drafted,
                               4)})
        if reqlog.ENABLED:
            # Engine half of the wide-event request record: every
            # field is something the request already tracks. Attached
            # BEFORE _finish puts _DONE, so the serve handler's stream
            # loop can read it once the iterator exhausts and ship it
            # to the LB as the trailing stats frame.
            req.reqlog_record = {
                "queue_wait_s": (
                    round(req.admitted_at - req.submitted_at, 6)
                    if req.admitted_at is not None else None),
                "prompt_tokens": len(req.prompt),
                "cached_prompt_tokens": req.cached_prompt_tokens,
                "generated_tokens": req.emitted,
                "kv_tier": req.kv_tier,
                "spec_drafted": req.spec_drafted,
                "spec_accepted": req.spec_accepted,
                "ttft_s": (
                    round(req.first_token_at - req.submitted_at, 6)
                    if req.first_token_at is not None else None),
                "device_time_s": round(req.device_time_s, 6),
                "outcome": outcome,
                "error": error,
            }
        req._finish(error)
        self._retiring -= 1
        _REQUESTS.labels(outcome=outcome).inc()

    def _record_admission(self, i: int, req: Request,
                          slot: "_Slot") -> None:
        """One admission-telemetry record (only reached while
        stepstats.ENABLED — the call site guards)."""
        stepstats.record_admission(
            slot=i, prompt_tokens=len(req.prompt),
            max_tokens=req.max_tokens, cached_tokens=slot.cached,
            queue_wait_s=req.admitted_at - req.submitted_at)

    @staticmethod
    def _stamp_admitted(req: Request) -> None:
        """The request has its slot: ONE clock read that the queue-wait
        histogram, the request record, the step ring and the traced
        spans all share."""
        req.admitted_at = time.perf_counter()
        _QUEUE_WAIT.observe(req.admitted_at - req.submitted_at)

    def _try_admit_paged(self, i: int, req: Request) -> bool:
        """Reservation-based paged admission (compute thread): alias
        the longest cached prefix into the slot's block table (pin —
        the zero-copy hit), then reserve every block of every kind the
        request can ever need, evicting LRU unpinned trie leaves to
        make room. False = head-of-line backpressure: the request
        stays at the queue head until slot frees / evictions make it
        fit — deterministic and preemption-free (an admitted request
        can never lose a block, so nothing decoding is ever rolled
        back).

        By kind (kv_pool.PoolLayout): token blocks of the ``k``
        matched chunks are aliased and the rest, to the request's
        longest, reserved; of a window kind the last ``window_blocks -
        1`` matched are aliased (what the next token's window still
        reaches) and as many reserved as the slot can hold of its own
        at once; a state reserves its fixed count and the slot's first
        chunk READS the deepest matched node's snapshot
        (:meth:`_chunk_target`). A node holds a block of every kind,
        so a hit restores all of them to one chunk boundary."""
        lay = self._layout
        nodes = self.prefix_cache.match(req.prompt)
        # Split the match by residency: a device-resident prefix (the
        # zero-copy alias) followed by a host-resident suffix to
        # re-admit H2D. Payloads are fetched NOW — holding the host
        # arrays keeps the bytes alive against concurrent LRU drops
        # for the life of the slot.
        dev_nodes: List[Any] = []
        pending: List[tuple] = []
        for node in nodes:
            if node.block >= 0 and not pending:
                dev_nodes.append(node)
            elif node.block < 0 and self._host_pool is not None:
                payload = self._host_pool.get(node.path)
                if payload is None:
                    break       # D2H still in flight (or just dropped)
                pending.append((node, payload))
            else:
                break
        # With no token blocks to alias, the DEEPEST node alone is
        # pinned, until its snapshot is read; the nodes above it are
        # kept by the trie's leaf-only eviction, as any interior node.
        held = dev_nodes if lay.tokens else dev_nodes[-1:]
        self.prefix_cache.pin(held)
        pend_nodes = [n for n, _ in pending]
        self.prefix_cache.pin_pending(pend_nodes)
        # Host re-admits draw FRESH blocks, budgeted like any other
        # un-cached chunk (same worst-case reservation); the restore
        # itself runs off the hot path in the prefill-phase interleave.
        tokens = len(req.prompt) + req.max_tokens
        hit = len(dev_nodes)
        fresh = self._pool.blocks_for(tokens) - hit if lay.tokens else 0
        needed = {"global": fresh, "window": min(fresh, self._win_blocks),
                  "state": lay.state_blocks}
        while any(pool.available() < needed[kind]
                  for kind, pool in self._pools.items()):
            if not self._evict_leaf():
                self.prefix_cache.unpin(held)
                self.prefix_cache.unpin_pending(pend_nodes)
                return False
        for kind, pool in self._pools.items():
            pool.reserve(needed[kind])
        slot = self._slots[i]
        slot.request = req
        slot.held = held
        slot.pending = [(hit + j, node, payload)
                        for j, (node, payload) in enumerate(pending)]
        slot.blocks = 0
        if lay.tokens:
            for j, node in enumerate(dev_nodes):
                self._table[i, self._tok0 + j] = node.block
            slot.blocks = hit
            slot.reserved = needed["global"]
        if lay.window:
            slot.win_lo = max(0, hit - (self._win_blocks - 1))
            for j in range(slot.win_lo, hit):
                block = dev_nodes[j].extra["window"]
                self._window_pool.retain(block)
                self._table[i, self._win0 + j] = block
            slot.win_own = set()
            slot.win_reserved = needed["window"]
        if lay.state_blocks:
            slot.own_state = False
            slot.state_reserved = needed["state"]
            # The deepest node's snapshot: its block of the state kind,
            # which is the node's one block where a state is all.
            self._table[i, 0] = dev_nodes[-1].extra.get(
                "state", dev_nodes[-1].block) if hit else 0
        slot.cached = hit * self._chunk
        # The device-resident "restore" is already done: the aliased
        # blocks ARE the prefilled prefix. Host-resident chunks join
        # the frontier one _restore_one at a time; prefill resumes
        # after the last cached token either way.
        slot.prefilled = slot.pos = slot.cached
        slot.generated = 0
        req.cached_prompt_tokens = slot.cached
        # A hit restored from a snapshot alone aliases nothing.
        self.prefix_cache.note_result(hit + len(pending),
                                      zero_copy=lay.tokens)
        if dev_nodes and lay.tokens:
            _ZERO_COPY_HITS.inc()
        if dev_nodes and lay.state_blocks:
            _STATE_SNAPSHOTS.labels(event="restored").inc()
        self._count_admission(
            req, hit + len(pending),
            "host" if pending else "hbm" if dev_nodes else "miss")
        return True

    def _count_admission(self, req: Request, chunks: int,
                         tier: str) -> None:
        """A successful admission on the prefix counters: a hit that
        saved ``chunks`` chunks of prefill from ``tier``, or a miss."""
        if chunks:
            _PREFIX_HITS.inc()
            _PREFIX_SAVED.inc(chunks * self._chunk)
        else:
            _PREFIX_MISSES.inc()
        _KV_TIER_HITS.labels(tier=tier).inc()
        if reqlog.ENABLED:
            req.kv_tier = tier

    def _evict_leaf(self):
        """LRU-evict one unpinned trie leaf; what ``evict_one`` says."""
        evicted = self.prefix_cache.evict_one()
        if evicted and self._layout.state_blocks:
            _STATE_SNAPSHOTS.labels(event="evicted").inc()
        return evicted

    def _chunk_target(self, i: int, start: int) -> int:
        """The STATE block a prefill chunk writes, the chunk at
        ``start`` of slot ``i``'s prompt; ``table[i, 0]`` names the
        block it reads. Copy-on-write, one chunk at a time:

        * the slot's FIRST chunk reads what admission put there — a
          matched node's snapshot, or the scratch block's zero state —
          and writes the slot's own block, drawn from its reservation;
          the snapshot is never written (a family with no token blocks
          pinned its node for this read alone and unpins it here);
        * a later chunk reads the slot's own block, which holds the
          state after the ``start // chunk`` full chunks before it. If
          a spare block is free (or an unpinned trie leaf can be
          evicted for one), the chunk writes THAT and the block it
          read becomes the trie's node for those chunks — a snapshot
          taken with no copy, together with the last of those chunks'
          token blocks where the family has them — or frees if the
          node is there already. With no spare block the chunk
          rewrites the slot's block in place and no snapshot is taken:
          a full pool costs prefix reuse, never a request.

        A window block the trie adopts stops counting against the
        slot's budget (the slot will give it back before its end and
        the trie will not), so a snapshot also needs one window block
        free, to promise the slot in its place.

        The caller uploads the table row (the block to READ) before it
        points ``table[i, 0]`` at the block returned here. Programs run
        in dispatch order, so a block is rewritten only after whatever
        read it last."""
        slot, lay, pool = self._slots[i], self._layout, self._state_pool
        if not slot.own_state:
            block = pool.alloc()
            slot.state_reserved -= 1
            slot.own_state = True
            if slot.held and not lay.tokens:
                self.prefix_cache.unpin(slot.held)
                slot.held = []
            return block
        read = int(self._table[i, 0])
        spare = [pool] + ([self._window_pool] if lay.window else [])
        while any(p.available() < 1 for p in spare):
            if not self._evict_leaf():
                return read
        block = pool.alloc(reserved=False)
        done = start // self._chunk
        if not lay.tokens:
            taken = self.prefix_cache.publish_snapshot(
                slot.request.prompt, done, read)
        else:
            extra = {"state": read}
            if lay.window:
                extra["window"] = int(self._table[i, self._win0 + done - 1])
            taken = self.prefix_cache.publish_snapshot(
                slot.request.prompt, done,
                int(self._table[i, self._tok0 + done - 1]), extra)
            if taken:
                self._window_budget_back(slot, done - 1)
        if taken:
            _STATE_SNAPSHOTS.labels(event="taken").inc()
        pool.release(read)
        return block

    def _release_behind(self, i: int) -> None:
        """Give back the window blocks slot ``i`` has moved past: a
        block whose last row is older than the window of the NEXT token
        (``slot.pos``) is read by no program dispatched from here on.
        Called where a retired slot's blocks are returned, right after
        a dispatch: programs run in dispatch order, so a step that is
        dispatched and not yet read still finds the block as it was,
        and whoever is handed the block writes it only afterwards. A
        refcount drop: the trie may still hold the block for a shared
        prefix. One of the slot's own goes back to its budget."""
        slot = self._slots[i]
        first = max(slot.pos - self._layout.window + 1, 0) // self._chunk
        while slot.win_lo < min(first, slot.blocks):
            j = slot.win_lo
            self._window_pool.release(int(self._table[i, self._win0 + j]))
            self._table[i, self._win0 + j] = 0
            self._window_budget_back(slot, j)
            slot.win_lo = j + 1
            _WINDOW_RELEASED.inc()

    def _window_budget_back(self, slot: "_Slot", j: int) -> None:
        """Window block ``j`` stops counting against the slot's budget
        (released behind it, or adopted by the trie): promise the slot
        another in its place. The callers know one is free."""
        if j in slot.win_own:
            slot.win_own.discard(j)
            self._window_pool.reserve(1)
            slot.win_reserved += 1

    def _admit(self) -> None:
        # Traced-phase stamps taken under the lock, RECORDED after it:
        # record_span does file I/O, and a slow disk under the
        # admission condition would stall every concurrent submit().
        emits: List[tuple] = []
        with self._cond:
            free = [i for i, s in enumerate(self._slots)
                    if s.request is None]
            free.reverse()          # pop() from the end = slot order
            while self._waiting and free:
                req = self._waiting[0]
                if req.cancelled:
                    self._waiting.popleft()
                    req._finish()
                    _REQUESTS.labels(outcome="cancelled").inc()
                    continue
                traced = (tracing.ENABLED and req.trace is not None
                          and req.trace.sampled)
                t0 = time.perf_counter() if traced else 0.0
                i = free[-1]
                if not self._try_admit_paged(i, req):
                    break       # FIFO head-of-line backpressure
                free.pop()
                self._waiting.popleft()
                slot = self._slots[i]
                self._stamp_admitted(req)
                if stepstats.ENABLED:
                    self._record_admission(i, req, slot)
                if traced:
                    emits.append(("engine.queue", req.trace,
                                  req.submitted_at, req.admitted_at,
                                  {"slot": i}))
                    emits.append(("engine.prefix_lookup", req.trace,
                                  t0, req.admitted_at,
                                  {"hit": bool(slot.held),
                                   "cached_tokens": slot.cached,
                                   "zero_copy": True}))
            _QUEUE_DEPTH.set(len(self._waiting))
        live = len(self._live())
        self.peak_live_slots = max(self.peak_live_slots, live)
        _SLOTS_OCCUPIED.set(live)
        self._update_pool_gauges()
        for name, trace, t0, t1, attrs in emits:
            tracing.record_span(name, "engine", trace,
                                start_mono=t0, end_mono=t1,
                                attrs=attrs)

    def _update_pool_gauges(self) -> None:
        lay = self._layout
        _KV_POOL_FREE.set(self._pool.free_blocks())
        # Distinct blocks that live slots' tables name, by kind.
        named = {kind: set() for kind in self._pools}
        for i, s in enumerate(self._slots):
            if s.request is not None:
                for kind, blocks in self._slot_blocks(i).items():
                    named[kind].update(blocks)
        _KV_POOL_PINNED.set(len(named[lay.kinds()[0]]))
        for kind, blocks in named.items():
            _CACHE_BLOCKS.labels(kind=kind).set(len(blocks))
        if lay.state_blocks:
            snapshots = self.prefix_cache.stats()["chunks"]
            _CACHE_BLOCKS.labels(kind="snapshot").set(snapshots)

    def _slot_blocks(self, i: int) -> Dict[str, List[int]]:
        """The blocks slot ``i``'s table row names, by kind: its token
        blocks (aliased and own), the window blocks it still holds, its
        own state block."""
        slot, row, out = self._slots[i], self._table[i], {}
        if self._layout.tokens:
            out["global"] = row[self._tok0:
                                self._tok0 + slot.blocks].tolist()
        if self._layout.window:
            out["window"] = row[self._win0 + slot.win_lo:
                                self._win0 + slot.blocks].tolist()
        if slot.own_state:
            out["state"] = [int(row[0])]
        return out

    def _table_upload(self, i: int):
        """Slot ``i``'s row of the block table as a program's input:
        a COPY, because the upload may read the host's memory after
        the call returns (on the CPU the device array IS that memory)
        and the engine writes the table again — retires a slot, grows
        a block — before it waits for anything."""
        return jnp.asarray(self._table[i].copy())

    def _step_table(self, live: List[int]):
        """The block table a decode or verify step runs with: the
        decoding slots' rows, and the scratch block (0) in every
        other row. That is how a step's program knows which rows
        decode, from an argument it already has. A step REWRITES the
        state block a row names and skips the scratch block (a slot in
        the middle of its prefill keeps its state; free slots cost the
        state's layers nothing); blocks of tokens get a non-decoding
        row's unattended cache row in the scratch block, not the
        slot's own, and deepseek's expert layer computes no expert for
        it."""
        table = np.zeros_like(self._table)    # a copy, as above
        table[live] = self._table[live]
        return jnp.asarray(table)

    def _ensure_block(self, i: int, j: int) -> int:
        """Back slot ``i``'s logical block ``j`` of tokens, of each
        kind of token block, allocating from the slot's admission
        reservation on first touch (lazy growth — blocks are claimed as
        prefill/decode actually reaches them). A state holds its fixed
        blocks from the slot's first chunk on and never grows: nothing
        to do where that is all there is."""
        if not self._layout.tokens:
            return 0
        slot = self._slots[i]
        if j < slot.blocks:
            return int(self._table[i, self._tok0 + j])
        if j != slot.blocks:
            raise EngineError(
                f"non-contiguous block growth: slot {i} has "
                f"{slot.blocks} blocks, asked for logical block {j}")
        if slot.reserved <= 0:
            raise EngineError(
                f"slot {i} reservation exhausted — admission "
                "under-reserved (worst-case block math is wrong)")
        block = self._pool.alloc()
        slot.reserved -= 1
        self._table[i, self._tok0 + j] = block
        if self._layout.window:
            self._table[i, self._win0 + j] = self._window_pool.alloc()
            slot.win_reserved -= 1
            slot.win_own.add(j)
        slot.blocks = j + 1
        return block

    def _prefill_one(self) -> int:
        """Advance the first slot with un-prefilled prompt by ONE
        chunk; the final chunk samples the first token on the device
        (into the slot's row of ``_toks``, where this iteration's
        decode step finds it) and leaves it to be read with that
        step's tokens. Returns the number of prompt tokens prefilled
        (0 = no prefill work) — truthy exactly when work happened, and
        the per-step telemetry's prefill-token count when stepstats is
        armed."""
        self._enter("schedule.prefill")
        for i, slot in enumerate(self._slots):
            req = slot.request
            if req is None or slot.prefilled >= len(req.prompt):
                continue
            if req.cancelled:
                # No token of it is dispatched yet: it ends here.
                self._retire(i)
                self._finish_request(req, "cancelled")
                continue
            if self._spec_k and not slot.history:
                # Every request passes through here at least once (the
                # prefix cache always leaves >= 1 trailing prompt token
                # to prefill), so this is the one draft-state seam.
                self._spec_init(slot, req)
            if tracing.ENABLED and req.trace is not None \
                    and req.trace.sampled and req.prefill_start is None:
                req.prefill_start = time.perf_counter()
            if slot.pending:
                # Host-tier re-admits ride the prefill phase: ONE
                # block restore per engine iteration, drawn from the
                # slot's admission reservation like a chunked prefill
                # — the decode step never waits on an H2D transfer.
                return self._restore_one(i)
            start = slot.prefilled
            piece = req.prompt[start:start + self._chunk]
            # Pad host-side (numpy), NOT with a jnp zeros/at/set: the
            # eager at/set compiles one XLA pad program PER DISTINCT
            # final-chunk length, so a live traffic mix steadily grows
            # the jit cache and pays compile jitter on the prefill hot
            # path. A plain host-array upload needs no program at all.
            buf_np = np.zeros((self._chunk,), np.int32)
            buf_np[:len(piece)] = piece
            buf = jnp.asarray(buf_np)
            valid = start + len(piece)
            final = valid >= len(req.prompt)
            # Where the first token goes: the slot's row, or nowhere.
            first = (self._toks,
                     np.int32(i if final else len(self._slots)),
                     np.uint32(req.seed), np.float32(req.temperature))
            if fault_injection.ENABLED:
                fault_injection.fire("engine.prefill", slot=i,
                                     start=start)
            _STEP_KIND["prefill"].inc()
            _CHUNK_ENDS[final].inc()
            # The block the chunk writes: its block of tokens, or
            # where there is a state the state's (the token blocks are
            # then the ones the table names at ``start``).
            wb = self._ensure_block(i, start // self._chunk)
            if self._layout.state_blocks:
                wb = self._chunk_target(i, start)
            row = self._table_upload(i)
            self._dispatching("prefill")
            self._toks, self._cache = _paged_prefill_chunk(
                self._cfg, self._params, self._cache, buf, row,
                jnp.int32(start), jnp.int32(valid), jnp.int32(wb),
                self._window, *first)
            self._newest = self._toks
            if self._layout.state_blocks:
                self._table[i, 0] = wb     # the slot's state, from now
            req.prefill_chunks += 1
            slot.prefilled = valid
            slot.pos = valid
            if self._layout.window:
                self._release_behind(i)
            if final:
                slot.generated = 1
                self._fresh.append(_Unread(
                    self._toks, [(i, req, self._maybe_retire(i))]))
            return len(piece)
        return 0

    def _restore_one(self, i: int) -> int:
        """Re-admit ONE pending host-tier block for slot ``i`` into
        the paged pool (H2D), advancing the slot's cached frontier by
        a chunk. The block comes out of the slot's admission
        reservation exactly as a fresh prefill chunk's would; if
        another slot already promoted the node back to HBM since
        admission, this collapses to a plain zero-copy alias and the
        spare reservation returns. Returns the chunk's token count —
        prefill-phase work for the step telemetry."""
        slot = self._slots[i]
        req = slot.request
        j, node, payload = slot.pending.pop(0)
        if node.block < 0:
            block = self._pool.alloc()
            slot.reserved -= 1
            self.prefix_cache.promote(node, block)
            parts = {k: jnp.asarray(v) for k, v in payload.items()}
            _STEP_KIND["restore"].inc()
            self._dispatching(None)
            self._cache = _host_restore_block(
                self._cache, jnp.int32(block), parts)
            self._newest = next(iter(self._cache.values()))
            self._readmitted_blocks += 1
            _KV_HOST_READMITS.inc()
        else:
            self._pool.retain(node.block)
            self._pool.unreserve(1)
            slot.reserved -= 1
        # Chunk-order append keeps _release_paged's table-position
        # invariant: held nodes are exactly table[0:len(held)].
        slot.held.append(node)
        self._table[i, j] = node.block
        slot.blocks = j + 1
        slot.cached += self._chunk
        slot.prefilled = slot.pos = (j + 1) * self._chunk
        req.cached_prompt_tokens = slot.cached
        return self._chunk

    def _maybe_retire(self, i: int) -> Optional[str]:
        """After a dispatch: if the program just dispatched samples
        the last token slot ``i``'s request is owed, retire the slot
        and return the request's outcome (the caller hands it on with
        the token's row); None while it owes more. Counts, never a
        token's value: the engine has no stop-token rule."""
        slot = self._slots[i]
        if slot.generated >= slot.request.max_tokens:
            outcome = "ok"
        elif slot.pos + 1 >= self._limit:
            outcome = "cache_full"
        else:
            return None
        self._retire(i)
        return outcome

    def _first_token_out(self, req: Request) -> None:
        """What the engine records once per request, when its first
        token (a final prefill chunk's) has been read."""
        _PREFIX_TTFT.labels(
            cache="hit" if req.cached_prompt_tokens else "miss"
        ).observe(req.first_token_at - req.submitted_at)
        if tracing.ENABLED and req.trace is not None \
                and req.trace.sampled:
            # Chunked-prefill child span, closing at the first token:
            # steps_to_first_token is the chunk-prefill count (the
            # first token is sampled from the final chunk's logits in
            # this engine).
            tracing.record_span(
                "engine.prefill", "engine", req.trace,
                start_mono=(req.prefill_start or req.submitted_at),
                attrs={"prompt_tokens": len(req.prompt),
                       "cached_tokens": req.cached_prompt_tokens,
                       "steps_to_first_token": req.prefill_chunks})

    def _land(self, everything: bool = False) -> None:
        """Read what the iteration before this one left on the device
        (``everything``: this one's dispatches too) in ONE blocking
        fetch, and hand the tokens on: the FETCH half of a slot's
        life — client queues, the latency histograms, the step's
        report's counters, the end of every request whose last token
        this brings. It touches no slot but to keep a drafting slot's
        history."""
        due = self._behind + self._fresh if everything else self._behind
        if not due:
            return
        self._enter("fetch")
        fetched = jax.device_get([(e.toks, e.routing) for e in due])
        now = self._enter("emit")
        for entry, (toks, routing) in zip(due, fetched):
            rows = entry.rows
            if entry.t0 is not None:
                live = [i for i, _, _ in rows if i is not None]
                *moe, blocks = routing or (None,)
                if blocks is not None:
                    _ATTN_BLOCKS_READ.inc(int(blocks))
                if moe:
                    chosen, computed = moe
                    chosen = chosen[live]      # (live, layers, held)
                    _MOE_ROUTED.inc(int(chosen.sum()))
                    _MOE_HIT.inc(int(chosen.any(axis=0).sum()))
                    _MOE_COMPUTED.inc(int(computed.sum()))
                # A step's time is the interval between two reads:
                # with the loop a step ahead, dispatch to read spans
                # two.
                dt = max(now - max(entry.t0, self._landed_at), 1e-9)
                if reqlog.ENABLED:
                    # Device-time share for cost attribution: the
                    # step's wall duration split evenly across the
                    # slots that rode it — host-side bookkeeping only.
                    for i, req, _ in rows:
                        if i is not None:
                            req.device_time_s += dt / len(live)
                _TOK_RATE.observe(len(live) / dt)
            while rows:
                i, req, outcome = rows.popleft()
                if i is not None:
                    tok = int(toks[i])
                    req._emit(tok, now)
                    if req.emitted == 1:
                        self._first_token_out(req)
                    if self._spec_k and self._slots[i].request is req:
                        self._spec_track(self._slots[i], tok)
                if outcome is not None:
                    self._finish_request(req, outcome)
        self._landed_at = now
        self._behind = []
        if everything:
            self._fresh = []

    # -------------------------------------------- speculative decoding
    def _spec_init(self, slot: "_Slot", req: Request) -> None:
        """Seed the slot's draft state from the prompt (spec_k > 0
        only): the token history plus an incremental n-gram ->
        latest-start index over every n-gram FULLY inside
        history[:-1]. The final n-gram registers lazily when the next
        token lands (:meth:`_spec_track`), so a lookup pattern can
        never match itself. Called LAZILY from the compute thread's
        first prefill touch, never under the admission condition — the
        O(prompt) index build on a multi-thousand-token prompt must
        not stall concurrent submit() callers."""
        slot.history = list(req.prompt)
        slot.ngram_index = {}
        slot.drafted = slot.accepted = 0
        slot.spec_off = False
        h, n = slot.history, self._spec_ngram
        for s in range(len(h) - n):
            slot.ngram_index[tuple(h[s:s + n])] = s

    def _spec_track(self, slot: "_Slot", tok: int) -> None:
        """Append an emitted token to the slot's history and index the
        n-gram that just became FULLY interior (ends at the previous
        token). O(1) per token — the draft lookup is a dict get, not a
        scan, so drafting costs the hot loop nothing measurable."""
        h = slot.history
        h.append(tok)
        s = len(h) - self._spec_ngram - 1
        if s >= 0:
            slot.ngram_index[tuple(h[s:s + self._spec_ngram])] = s

    def _draft(self, slot: "_Slot") -> List[int]:
        """n-gram / prompt-lookup draft over the slot's OWN history:
        the most recent earlier occurrence of the last n tokens
        proposes its continuation — free (no second model), and strong
        exactly on the shared-prefix / templated / self-repeating
        output mixes production chat traffic is made of. Clamped to
        remaining - 1 tokens so even a fully-accepted window never
        writes past the request's admission-reserved worst case."""
        req = slot.request
        if slot.spec_off:
            return []
        k = min(self._spec_k, req.max_tokens - slot.generated - 1)
        if k <= 0:
            return []
        h, n = slot.history, self._spec_ngram
        if len(h) < n + 1:
            return []
        s = slot.ngram_index.get(tuple(h[-n:]))
        if s is None:
            return []
        return h[s + n:s + n + k]

    def _step_inputs(self, live: List[int]):
        """(pos, temps, seeds) batch vectors shared by the plain
        decode step and the speculative verify step — free slots ride
        with temp 0 / seed 0 and are ignored host-side. ONE builder so
        the two paths can never sample from different inputs."""
        pos = jnp.asarray([s.pos for s in self._slots], jnp.int32)
        temps = jnp.asarray(
            [s.request.temperature if i in live else 0.0
             for i, s in enumerate(self._slots)], jnp.float32)
        seeds = jnp.asarray(
            [s.request.seed if i in live else 0
             for i, s in enumerate(self._slots)], jnp.uint32)
        return pos, temps, seeds

    def _stamp_dispatch(self, t0: float, synced) -> None:
        """Step-telemetry dispatch/device split, shared by both decode
        paths (armed only — callers guard on stepstats.ENABLED): the
        jitted call returned at DISPATCH (device still executing), so
        the gap from t0 is host dispatch work; every Nth step the
        sanctioned sampled_sync times the remaining device wait."""
        self._step_dispatch_s = time.perf_counter() - t0
        self._step_device_s = (stepstats.sampled_sync(synced)
                               if stepstats.sync_due() else None)

    def _verify_decode_step(self, live: List[int],
                            drafts: Dict[int, List[int]]) -> int:
        """One speculative verify step replacing the 1-token decode
        step: all live slots' [last token, drafts...] windows forward
        in a single batched pass, targets are re-sampled with the
        engine's own per-position keys, and each slot emits its
        accepted prefix plus the correction token — 1..k+1 tokens for
        one memory-bound pass. How many a slot accepts decides its
        next position, so this step is read before anything else is
        planned: never dispatched ahead, and nothing is unread when
        it is (the caller landed it all to draft). Rollback of a
        rejected suffix is a host-side frontier rewind: the grown
        block-table tail is truncated and its reservation returned.
        Returns tokens emitted."""
        drafts_np = np.zeros((len(self._slots), self._spec_k), np.int32)
        spec_np = np.zeros((len(self._slots),), np.int32)
        for i in live:
            d = drafts.get(i)
            if d:
                drafts_np[i, :len(d)] = d
                spec_np[i] = len(d)
        pos, temps, seeds = self._step_inputs(live)
        t0 = time.perf_counter()
        if fault_injection.ENABLED:
            fault_injection.fire("engine.verify", live=len(live),
                                 drafted=int(spec_np.sum()))
        _STEP_KIND["verify"].inc()
        # Back every position the window may write from the slots'
        # admission reservations (the remaining-1 draft clamp keeps
        # the window inside the reserved worst case).
        for i in live:
            slot = self._slots[i]
            for j in range(slot.pos // self._chunk,
                           (slot.pos + int(spec_np[i]))
                           // self._chunk + 1):
                self._ensure_block(i, j)
        table = self._step_table(live)
        self._dispatching("verify")
        targets, accepts, self._toks, self._cache = _paged_spec_step(
            self._cfg, self._params, self._cache, self._toks,
            jnp.asarray(drafts_np), pos, jnp.asarray(spec_np), table,
            self._window, temps, seeds)
        self._newest = self._toks
        if stepstats.ENABLED:
            self._stamp_dispatch(t0, accepts)
        self._enter("fetch")
        targets, accepts = jax.device_get((targets, accepts))
        now = self._landed_at = self._enter("emit")
        dt = max(now - t0, 1e-9)
        if reqlog.ENABLED:
            # Per-request device-time share (see _land).
            share = dt / len(live)
            for i in live:
                self._slots[i].request.device_time_s += share
        emitted = 0
        drafted_step = accepted_step = 0
        for i in live:
            slot = self._slots[i]
            req = slot.request
            k_i = int(spec_np[i])
            a = int(accepts[i])
            base_pos = slot.pos
            for j in range(a + 1):
                tok = int(targets[i, j])
                req._emit(tok, now)
                self._spec_track(slot, tok)
            slot.pos = base_pos + a + 1
            slot.generated += a + 1
            emitted += a + 1
            if k_i:
                slot.drafted += k_i
                slot.accepted += a
                req.spec_drafted += k_i
                req.spec_accepted += a
                drafted_step += k_i
                accepted_step += a
                if (not slot.spec_off and slot.drafted >= 16
                        and slot.accepted <
                        self._spec_min_accept * slot.drafted):
                    # This slot's traffic doesn't repeat: every future
                    # draft would widen the verify window for nothing.
                    slot.spec_off = True
            # Block-table truncate: blocks grown for the rejected
            # suffix go back (refcount 1 — decode blocks are never
            # shared) and their reservation draws are RE-PROMISED
            # (release + reserve is atomic on this thread, and the
            # just-freed block guarantees available() >= 1), so the
            # preemption-free admission invariant holds: the slot
            # keeps its worst case, it just returns the physical
            # blocks until the frontier really gets there.
            needed = (base_pos + a) // self._chunk + 1
            while slot.blocks > needed:
                j = slot.blocks - 1
                self._pool.release(int(self._table[i, j]))
                self._pool.reserve(1)
                self._table[i, j] = 0
                slot.blocks = j
                slot.reserved += 1
            # Its tokens are out already: both halves of its end.
            outcome = self._maybe_retire(i)
            if outcome is not None:
                self._finish_request(req, outcome)
        if drafted_step:
            _SPEC_DRAFTED.inc(drafted_step)
            _SPEC_ACCEPTED.inc(accepted_step)
            _SPEC_ACCEPT_RATE.observe(accepted_step / drafted_step)
        if stepstats.ENABLED:
            self._step_spec_drafted = drafted_step
            self._step_spec_accepted = accepted_step
        _TOK_RATE.observe(emitted / dt)
        return emitted

    def _decode_step(self) -> int:
        """Dispatch one batched step over every slot whose prompt is
        fully prefilled and which still owes tokens, THEN read what
        the iteration before left on the device (:meth:`_land`): the
        step's input is the vector the last step left there, so the
        host's emitting, admitting and planning between two steps run
        while the device works, not while it waits. Exact, not
        speculative: what is decided at dispatch (positions, block
        growth, who is owed a token, whose last this is) depends on
        counts alone.

        Where a value IS needed to plan — a live slot may draft from
        its history (``spec_k`` and not ``spec_off``) — everything
        unread is landed first and the step after is not dispatched
        ahead: the order the engine had before it looked ahead, as
        the drained case of the same code. A speculative verify step
        is read at once (:meth:`_verify_decode_step`).

        Returns the number of rows dispatched; when 0, nothing is
        left unread either (whoever drives the engine by hand loops
        until this and ``_prefill_one`` return 0)."""
        self._enter("schedule.decode")
        drafting = self._spec_k and any(
            s.request is not None and not s.spec_off
            for s in self._slots)
        if drafting and (self._behind or self._fresh):
            self._land(everything=True)
            self._enter("schedule.decode")
        live = []
        for i, slot in enumerate(self._slots):
            req = slot.request
            if req is None or slot.prefilled < len(req.prompt):
                continue
            if not req.cancelled:
                live.append(i)
                continue
            # Its end goes behind whatever of its tokens is unread.
            self._retire(i)
            unread = self._fresh or self._behind
            if unread:
                unread[-1].rows.append((None, req, "cancelled"))
            else:
                self._finish_request(req, "cancelled")
        if not live:
            self._land(everything=True)
            return 0
        if drafting:
            drafts = {i: self._draft(self._slots[i]) for i in live}
            if any(drafts.values()):
                return self._verify_decode_step(live, drafts)
        pos, temps, seeds = self._step_inputs(live)
        t0 = time.perf_counter()
        if fault_injection.ENABLED:
            fault_injection.fire("engine.step", live=len(live))
        _STEP_KIND["decode"].inc()
        if any(e.t0 is not None for e in self._behind):
            _LOOKAHEAD.inc()
        # Lazy growth BEFORE the step: each live slot's write position
        # must be backed (reservation guarantees a block exists —
        # admission is preemption-free).
        for i in live:
            self._ensure_block(i, self._slots[i].pos // self._chunk)
        table = self._step_table(live)
        self._dispatching("decode")
        nxt, self._cache = _paged_step(
            self._cfg, self._params, self._cache, self._toks, pos,
            table, self._window, temps, seeds)
        if stepstats.ENABLED:
            self._stamp_dispatch(t0, nxt)
        self._toks, routing = nxt if isinstance(nxt, tuple) \
            else (nxt, None)
        self._newest = self._toks
        rows = []
        for i in live:
            slot = self._slots[i]
            slot.pos += 1
            slot.generated += 1
            if self._layout.window:
                self._release_behind(i)
            rows.append((i, slot.request, self._maybe_retire(i)))
        self._fresh.append(_Unread(self._toks, rows, routing, t0))
        self._land()
        self._behind, self._fresh = self._fresh, []
        return len(live)

    def _record_step(self, t0: float, pf: int, dc: int) -> None:
        """One step-ring record for an iteration that did work (only
        reached while stepstats.ENABLED — the caller guards)."""
        stepstats.record(
            dur=time.perf_counter() - t0,
            phase=("mixed" if pf and dc
                   else "prefill" if pf else "decode"),
            live_slots=len(self._live()),
            queue_depth=len(self._waiting),
            prefill_tokens=pf, decode_tokens=dc, paged=True,
            kv_free=self._pool.free_blocks(),
            kv_usable=self._pool.usable_blocks,
            dispatch_s=self._step_dispatch_s if dc else None,
            device_s=self._step_device_s if dc else None,
            spec_drafted=self._step_spec_drafted if dc else 0,
            spec_accepted=self._step_spec_accepted if dc else 0)
        self._step_dispatch_s = None
        self._step_device_s = None
        self._step_spec_drafted = 0
        self._step_spec_accepted = 0

    def _loop(self) -> None:
        try:
            while True:
                with self._cond:
                    if self._stop:
                        break
                # Per-step telemetry (observability/stepstats.py) is
                # recorded around the WHOLE iteration — admit + one
                # prefill chunk + one batched decode step — so the
                # ring shows where supervisor-loop time goes. Disarmed
                # cost: one module-flag load and a falsy branch
                # (pinned by the monkeypatch-bomb test).
                armed = stepstats.ENABLED
                t0 = time.perf_counter() if armed else 0.0
                # What the iteration before dispatched is read by this
                # one whatever else it does: that is work too.
                owed = bool(self._behind)
                self._enter("schedule.admit")
                self._admit()
                pf = self._prefill_one()
                dc = self._decode_step()
                did = bool(pf or dc or owed)
                if armed and did:
                    self._enter("emit")
                    self._record_step(t0, pf, dc)
                if not did:
                    self._enter("wait")
                    with self._cond:
                        if not self._waiting and not self._stop:
                            self._cond.wait(timeout=0.05)
        except Exception as e:  # noqa: BLE001 — a dead compute thread
            # must fail every caller loudly, not hang their queues.
            msg = f"{type(e).__name__}: {e}"
            # Flight recorder: the last ring of step/admission records
            # plus the terminal exception survive the crash on disk —
            # the supervisor stamps the path into engine_failed.
            self.flightrec = stepstats.dump_flight("engine_crash",
                                                   error=msg)
            with self._cond:
                self._failed = msg
                self._stop = True
        # Drain. First what is dispatched and unread: a request whose
        # last step went out before the stop (or the fault) gets its
        # tokens and its own outcome.
        err = self._failed or "engine shut down"
        outcome = "error" if self._failed else "shutdown"
        try:
            self._land(everything=True)
        except Exception:  # noqa: stpu-except — the device is gone and the unread tokens with it; their requests end below
            pass
        self._enter(None)
        for entry in self._behind + self._fresh:
            for _, req, last in entry.rows:
                if last is not None:
                    self._finish_request(req, outcome, err)
        self._behind, self._fresh = [], []
        # Then anything still attached.
        for i, slot in enumerate(self._slots):
            req = slot.request
            if req is not None:
                self._retire(i, error=err)
                self._finish_request(req, outcome, err)
        with self._cond:
            waiting, self._waiting = list(self._waiting), \
                collections.deque()
        for req in waiting:
            req._finish(err)
            _REQUESTS.labels(outcome=outcome).inc()
        _SLOTS_OCCUPIED.set(0)
        _QUEUE_DEPTH.set(0)


class EngineSupervisor:
    """Babysit a DecodeEngine; restart it when the compute loop dies.

    Without supervision a dead engine loop is the worst failure mode in
    the stack: the HTTP process keeps answering the readiness probe, so
    the controller keeps the replica READY and the LB keeps routing to
    it — a zombie that blackholes its share of traffic until a human
    notices. The supervisor closes that hole from both sides:

      * ``healthy()`` is False the moment the loop dies (and stays
        False through the restart backoff) — the replica's /health
        endpoint returns 503, probes fail, and the controller pulls the
        replica until the engine is back;
      * the engine is rebuilt from scratch (``factory`` returns a fresh
        DecodeEngine: new KV cache, empty slots — device state after an
        arbitrary crash is not trustworthy) under capped exponential
        backoff; jitted programs are process-cached, so a restart does
        not re-pay XLA compiles;
      * ``max_restarts`` consecutive FAST failures (death within
        ``fast_failure_seconds`` of start — the deterministic-crash
        signature) leave the engine down for good: /health stays 503,
        probes keep failing, and the replica manager's
        user-code-failure path tears the replica down.

    Requests never hang across any of this: the dying engine drains its
    queue with EngineErrors, and submits during a restart hit the dead
    engine's (or the permanent-down) clean EngineError.

    API-compatible with DecodeEngine where serve handlers touch it
    (submit/warmup/drain/in_flight/shutdown), so recipes/serve_llm.py
    swaps it in transparently.
    """

    def __init__(self, factory: Callable[[], "DecodeEngine"], *,
                 max_restarts: int = 3, backoff_base: float = 1.0,
                 backoff_cap: float = 30.0,
                 fast_failure_seconds: float = 30.0,
                 poll_interval: float = 0.1):
        self._factory = factory
        self.max_restarts = int(max_restarts)
        self.backoff_base = float(backoff_base)
        self.backoff_cap = float(backoff_cap)
        self.fast_failure_seconds = float(fast_failure_seconds)
        self._poll = float(poll_interval)
        self._lock = threading.Lock()
        self._engine: Optional[DecodeEngine] = None
        self._stop = False
        self._draining = False
        self.permanently_down = False
        self.restarts = 0            # lifetime restarts (tests)
        self._consecutive = 0        # consecutive fast failures
        self._started_at = 0.0
        self._watch_thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------- public
    def start(self) -> "EngineSupervisor":
        if self._watch_thread is None:
            self._engine = self._factory().start()
            self._started_at = time.monotonic()
            _ENGINE_UP.set(1)
            self._watch_thread = threading.Thread(
                target=self._watch, name="engine-supervisor",
                daemon=True)
            self._watch_thread.start()
        return self

    @property
    def engine(self) -> Optional["DecodeEngine"]:
        """The live engine (swapped on restart) — for tests and
        introspection (prefix_cache etc.)."""
        return self._engine

    @property
    def prefix_cache(self):
        engine = self._engine
        return engine.prefix_cache if engine is not None else None

    def healthy(self) -> bool:
        """True iff the engine accepts work RIGHT NOW. Wired to the
        replica /health endpoint: 503 while failed/restarting/down."""
        if self.permanently_down or self._stop:
            return False
        engine = self._engine
        return engine is not None and engine._failed is None

    def submit(self, prompt, max_tokens: int, temperature: float = 0.0,
               seed: int = 0, trace=None, resume=None) -> Request:
        if self.permanently_down:
            raise EngineError(
                f"engine permanently down after {self.max_restarts} "
                "consecutive fast failures")
        engine = self._engine
        if engine is None:
            raise EngineError("engine not started")
        # A dead/restarting engine raises its own clean EngineError.
        return engine.submit(prompt, max_tokens=max_tokens,
                             temperature=temperature, seed=seed,
                             trace=trace, resume=resume)

    def warmup(self) -> None:
        engine = self._engine
        if engine is not None:
            engine.warmup()

    def drain(self) -> None:
        self._draining = True
        engine = self._engine
        if engine is not None:
            engine.drain()

    def draining(self) -> bool:
        return self._draining

    def kv_config(self) -> Dict[str, Any]:
        engine = self._engine
        return engine.kv_config() if engine is not None else {}

    def host_tier_stats(self) -> Dict[str, Any]:
        engine = self._engine
        return engine.host_tier_stats() if engine is not None else {}

    def cache_bytes_per_device(self) -> Dict[int, int]:
        engine = self._engine
        return (engine.cache_bytes_per_device()
                if engine is not None else {})

    def in_flight(self) -> int:
        engine = self._engine
        return engine.in_flight() if engine is not None else 0

    def restart_now(self) -> None:
        """Tear down the live engine and build a fresh one immediately
        (the whole-gang restart path: losing a gang member invalidates
        lockstep state on EVERY host, so host 0's engine restarts with
        the gang even though its own loop never crashed). In-flight
        requests fail with the shutdown EngineError — their stream died
        with the gang. Not a crash: the consecutive-fast-failure ladder
        is untouched."""
        # The outgoing engine's step ring documents what the gang was
        # doing when the member died — dump it before the state is
        # superseded (reason distinguishes it from a crash dump).
        flightrec = stepstats.dump_flight("gang_restart")
        new_engine = self._factory().start()
        with self._lock:
            # Capture the outgoing engine under the SAME lock as the
            # swap: the _watch crash-restart path swaps concurrently
            # (a slice-wide fault can kill a follower AND crash host
            # 0's loop), and a stale read here would orphan _watch's
            # fresh engine with a live loop thread and a full KV cache.
            if self._stop or self._draining:
                abandon, old = True, None
            else:
                old = self._engine
                self._engine = new_engine
                self._started_at = time.monotonic()
                abandon = False
        if abandon:
            new_engine.shutdown()
            return
        if old is not None:
            old.shutdown()
        self.restarts += 1
        _RESTARTS.inc()
        _ENGINE_UP.set(1)
        events.emit("engine", "decode-engine", "engine_restarted",
                    reason="gang", flightrec=flightrec)

    def shutdown(self) -> None:
        self._stop = True
        if self._watch_thread is not None:
            self._watch_thread.join(timeout=10.0)
        engine = self._engine
        if engine is not None:
            engine.shutdown()

    # ------------------------------------------------------------ internal
    def _sleep(self, seconds: float) -> bool:
        """Interruptible sleep; False if shutdown/drain cut it short."""
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            if self._stop or self._draining:
                return False
            time.sleep(min(self._poll, 0.05))
        return True

    def _watch(self) -> None:
        while not self._stop:
            time.sleep(self._poll)
            engine = self._engine
            if engine is None or engine._failed is None:
                continue
            # Gauge flips BEFORE the going-down check: a crash during
            # a drain must not leave stpu_engine_up stuck at 1 while
            # /health reports 503.
            _ENGINE_UP.set(0)
            if self._draining or self._stop:
                return      # going down anyway: don't resurrect
            error = engine._failed
            fast = (time.monotonic() - self._started_at <
                    self.fast_failure_seconds)
            self._consecutive = self._consecutive + 1 if fast else 1
            # The crash path wrote a flight-recorder dump (last step
            # ring + terminal exception); reference it from the event
            # so `stpu status --events` leads straight to the
            # post-mortem artifact.
            events.emit("engine", "decode-engine", "engine_failed",
                        error=error, consecutive=self._consecutive,
                        flightrec=getattr(engine, "flightrec", None))
            if self._consecutive > self.max_restarts:
                # Deterministic crash loop: stop burning device time.
                # /health stays 503; the replica manager's probe path
                # declares the replica FAILED and tears it down.
                self.permanently_down = True
                events.emit("engine", "decode-engine", "engine_down",
                            restarts=self.restarts)
                return
            delay = min(self.backoff_base * 2 ** (self._consecutive - 1),
                        self.backoff_cap)
            if not self._sleep(delay):
                return
            try:
                new_engine = self._factory().start()
            except Exception as e:  # noqa: BLE001 — a failing factory
                # (OOM on cache alloc, device gone) counts as another
                # fast failure next iteration, not a supervisor crash.
                events.emit("engine", "decode-engine",
                            "engine_restart_failed", error=repr(e))
                self._started_at = time.monotonic()
                continue
            with self._lock:
                # shutdown()/drain() may have landed while the factory
                # ran (fresh cache alloc can outlast shutdown's join
                # timeout) — swapping in the new engine then would
                # leak its loop thread and KV cache on a replica being
                # torn down, with /health flipping healthy again.
                if self._stop or self._draining:
                    abandon = True
                else:
                    self._engine = new_engine
                    abandon = False
            if abandon:
                new_engine.shutdown()
                return
            self._started_at = time.monotonic()
            self.restarts += 1
            _RESTARTS.inc()
            _ENGINE_UP.set(1)
            events.emit("engine", "decode-engine", "engine_restarted",
                        attempt=self._consecutive)
