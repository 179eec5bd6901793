"""One phase clock, and what the process says about itself with it.

:class:`_PhaseClock` is the seam: a thread is inside one named phase
at a time, each phase a span on the host plane of whatever
``jax.profiler`` trace runs and seconds on a counter family of
``/metrics``. It is used in three places: the engine loop's six phases
(serve/decode_engine.py: ``stpu.engine.<phase>``,
``stpu_engine_loop_seconds_total``), a server's start-up
(recipes/serve_llm.py, a :func:`startup_phase` around each) and a
trainer's (recipes/llama_lora.py, one :func:`startup_clock` switched
through them), both ``stpu.startup.<phase>`` and
``stpu_startup_seconds_total``. Always on: no flag arms it.

Beside it, two things a process knows about itself and nobody else
can time: when the kernel started it (:func:`import_done`) and what
its garbage collector cost (:func:`on_gc`).
"""
from __future__ import annotations

import contextlib
import os
import time
from typing import Dict, Optional, Sequence, Set

import jax

from skypilot_tpu.observability import metrics

# A phase instance this long is a pause, not work: above the longest
# normal instance of the engine loop (a fetch that waits through a
# chunk and a step: 31 ms in PERF.md's cell 6) and below the pauses
# that make and unmake verdicts (82-109 ms, PERF.md section 7).
LONG_PHASE_S = 0.06


class _PhaseClock:
    """Which phase its thread is in: :meth:`enter` closes the phase
    before and opens the named one at the same instant, as a
    ``<span_prefix><phase>`` span on the host plane of whatever
    ``jax.profiler`` trace is running (an inactive-tracer test
    otherwise) and as seconds on ``seconds{phase}``. One thread a
    clock. A switch and not a ``with`` block, so that the phases
    partition the thread's time by construction: what Python does
    between two blocks (a returning frame drops the step's device
    arrays and the handler threads take the GIL: 1-2 ms a step on the
    chip, PERF.md PR 26) belongs to the phase it ends.

    ``long_count`` / ``long_seconds`` (families labelled by phase):
    an instance of one of ``long_phases`` that lasted
    :data:`LONG_PHASE_S` or more adds one and its seconds there, so
    that a pause is named by the phase it struck."""

    __slots__ = ("_prefix", "_seconds", "_long", "_open", "_span", "_t0")

    def __init__(self, span_prefix: str, seconds: metrics.Counter,
                 phases: Sequence[str], *,
                 long_count: Optional[metrics.Counter] = None,
                 long_seconds: Optional[metrics.Counter] = None,
                 long_phases: Sequence[str] = ()):
        self._prefix = span_prefix
        # Every series exists from the start: 0 is a value.
        self._seconds = {p: seconds.labels(phase=p) for p in phases}
        self._long = {p: (long_count.labels(phase=p),
                          long_seconds.labels(phase=p))
                      for p in long_phases}
        self._open = self._span = None

    def enter(self, phase: Optional[str]) -> float:
        """End the open phase and begin ``phase`` (None: begin none);
        returns the instant of the switch."""
        now = time.perf_counter()
        if self._span is not None:
            lasted = now - self._t0
            self._seconds[self._open].inc(lasted)
            if lasted >= LONG_PHASE_S and self._open in self._long:
                count, seconds = self._long[self._open]
                count.inc()
                seconds.inc(lasted)
            self._span.__exit__(None, None, None)
            self._span = None
        if phase is not None:
            self._open, self._t0 = phase, now
            # A TraceMe starts when it is built.
            self._span = jax.profiler.TraceAnnotation(
                self._prefix + phase)
        return now


# ---------------------------------------------------------------- start-up
_STARTUP_SECONDS = metrics.counter(
    "stpu_startup_seconds_total",
    "Seconds of this process's start-up by phase. A server: import = "
    "the kernel's start of the process -> its first device query "
    "returned (interpreter, imports, backend init), weights = the "
    "parameter init traced, built and dispatched, engine = the decode "
    "engine built (pool, trie), warmup = its programs built or read "
    "from the cache and run once. A trainer: import, weights, compile "
    "= the first step's call, first_loss = until its loss is on the "
    "host. A counter: an engine restart adds to engine.", ("phase",))
_import_done = False
_startup_seen: Set[str] = set()     # phases whose series exist


def startup_clock(phases: Sequence[str]) -> _PhaseClock:
    """A clock over start-up phases ``phases`` for the calling thread
    (``stpu.startup.<phase>``, ``stpu_startup_seconds_total``)."""
    _startup_seen.update(phases)
    return _PhaseClock("stpu.startup.", _STARTUP_SECONDS, phases)


@contextlib.contextmanager
def startup_phase(phase: str):
    """The body is start-up phase ``phase`` of this process. A clock
    of its own each time, so that a restart on the supervisor's thread
    and a warm-up on another each keep their span on their own
    thread."""
    clock = startup_clock((phase,))
    clock.enter(phase)
    try:
        yield
    finally:
        clock.enter(None)


def startup_seconds() -> Dict[str, float]:
    """What each start-up phase this process has entered has counted
    so far."""
    return {p: _STARTUP_SECONDS.labels(phase=p).get()
            for p in sorted(_startup_seen)}


def _since_process_start() -> Optional[float]:
    """Seconds since the kernel started this process (the fork, so the
    exec, the interpreter's start and every import lie inside), from
    ``/proc/self/stat`` field 22 against ``/proc/uptime``; None where
    the platform has no such files."""
    try:
        with open("/proc/self/stat") as f:
            # The command (field 2) may hold spaces; fields 3.. follow
            # its closing parenthesis.
            started = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - started / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return None


def import_done() -> None:
    """Close start-up phase ``import``, once a process: make the first
    device query (the backend starts here if nothing started it) and
    count the seconds since the kernel started the process. Called by
    whichever of ``compile_cache.enable()``, ``serve_llm.init_params``
    and ``serve_llm.serve`` runs first; where the platform cannot say
    when the process started, the phase stays absent, not guessed."""
    global _import_done
    if _import_done:
        return
    _import_done = True
    jax.devices()
    elapsed = _since_process_start()
    if elapsed is not None:
        _startup_seen.add("import")
        _STARTUP_SECONDS.labels(phase="import").inc(elapsed)


# ---------------------------------------------------------------------- GC
_GC_SECONDS = metrics.counter(
    "stpu_process_gc_seconds_total",
    "Seconds this process's garbage collector ran, by generation: a "
    "collection stops every Python thread, the engine loop's too.",
    ("generation",))
_GC = {g: _GC_SECONDS.labels(generation=g) for g in (0, 1, 2)}
_gc_t0 = 0.0


def on_gc(phase: str, info: dict) -> None:
    """A ``gc.callbacks`` hook (``compile_cache.enable()`` registers
    it once a process): a collection's duration goes to its
    generation's counter. Collections do not nest, so one start
    instant is enough."""
    global _gc_t0
    if phase == "start":
        _gc_t0 = time.perf_counter()
    else:
        _GC[info["generation"]].inc(time.perf_counter() - _gc_t0)
