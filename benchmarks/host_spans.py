"""The device's idle time, named by what the engine thread was doing.

``trace_reduce`` can name a gap only from outside: by the program before
it and the program after. The engine loop (``serve/decode_engine.py``)
names its own phases on the profiler's clock, ``stpu.engine.<phase>``
spans on the engine thread's line of the host plane, and the phases
partition an iteration. This file intersects the two: device 0's idle
intervals (the complement of the ``XLA Ops`` busy union, inside the
window ``trace_reduce.reduce`` keeps: first to last device event) with
each phase's spans, in seconds per phase, plus the idle seconds under no
span at all.

Four shares of the window come out of it, which sum to
``device_idle_pct.serve`` (the same trace, window and busy union):

  schedule      idle under ``schedule.admit/prefill/decode``: the host
                choosing and preparing the next program up to its
                dispatch, and the launch until the device starts;
  fetch         idle under ``fetch``: after a program has ended, until
                the blocking read of its result returns;
  emit          idle under ``emit``: tokens to their streams, slots
                freed, prefixes published;
  unattributed  idle under ``wait`` or under no span. Over a tenth of
                the idle share here means the phases do not partition
                the loop and the seam is wrong.

The two clocks do not agree, so they are aligned first. In five chip
traces the host plane ran 0.2 to 1.8 ms ahead of the device plane (in
the worst, programs "began" 1.4 ms before the call that launched them
did; PERF.md, PR 26): against gaps of 5 ms that moves up to a third of the
idle time to the wrong phase. The seam gives an anchor on each clock:
``schedule.decode`` ends where the decode program is dispatched, and a
program launched into an idle device starts then, less a launch latency
of tens of microseconds. ``clock_offset_s`` is the median, over such
launches, of (the span's end on the host's clock - the program's start
on the device's); every span is moved back by it before anything is
intersected, and the launch latency is read as part of it. What
is left is checked: with the offset removed a decode program must start
at or after the start of the ``schedule.decode`` span that launched it
and END inside the ``fetch`` span that waits for it. The worst distance
from either is ``clock_residual_s``; beyond ``MAX_RESIDUAL_S``, or with no
launch to anchor on, :func:`idle_share` gives None.

``python -m benchmarks.host_spans <trace.xplane.pb | profile dir>``
prints the whole attribution; ``--record out.json.gz`` also keeps the
first ``--first-ms`` of the device lines and the engine's spans in the
form ``trace_reduce.load_recorded`` reads.

NOT YET A PER-LAYER METRIC: ``benchmarks/run.py`` removes the profile
directory before it computes the per-layer metrics, so a metric file
cannot open the trace (PERF.md, section 7, says which two lines to
move). Under ``BENCH_DESCRIBE=1`` the directory is kept; that is how
the numbers in PERF.md were read.
"""
from __future__ import annotations

import bisect
import os
from typing import Dict, List, Optional, Tuple

from benchmarks import trace_reduce

PREFIX = "stpu.engine."
SHARES = {"schedule.admit": "schedule", "schedule.prefill": "schedule",
          "schedule.decode": "schedule", "fetch": "fetch", "emit": "emit",
          "wait": "unattributed"}
# Every program whose result the loop fetches right after its dispatch.
DECODE_PROGRAMS = ("_paged_step", "_engine_step", "_paged_spec_step",
                   "_spec_step")
# A launch anchors the clocks only if the device had been idle this long
# before the program started: then the host's dispatch set its start,
# not the program before it (a step behind a prefill chunk starts
# 0.5 ms after the chunk ends, whenever it was dispatched).
LAUNCH_IDLE_NS = 1_000_000
MAX_RESIDUAL_S = 0.5e-3

Interval = Tuple[int, int]


def merge(intervals: List[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals``."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def complement(busy: List[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` that no interval of the merged ``busy``
    covers."""
    out, at = [], window[0]
    for s, e in busy:
        if s > at:
            out.append((at, min(s, window[1])))
        at = max(at, e)
    if at < window[1]:
        out.append((at, window[1]))
    return [(s, e) for s, e in out if e > s]


def overlap_ns(a: List[Interval], b: List[Interval]) -> int:
    """Total overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] <= b[j][1]:
            i += 1
        else:
            j += 1
    return total


def engine_spans(data) -> Dict[str, List[Interval]]:
    """phase -> its spans, from the one host line that carries
    ``stpu.engine.*`` events ({} where none does: a program older than
    the seam, or a trace of something else)."""
    best: Dict[str, List[Interval]] = {}
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans: Dict[str, List[Interval]] = {}
            for e in line.events:
                phase = e.name[len(PREFIX):]
                if e.name.startswith(PREFIX) and phase in SHARES:
                    spans.setdefault(phase, []).append(
                        (int(e.start_ns),
                         int(e.start_ns + e.duration_ns)))
            if sum(map(len, spans.values())) > \
                    sum(map(len, best.values())):
                best = spans
    return {k: sorted(v) for k, v in best.items()}


def decode_programs(modules) -> List[Tuple[int, int, bool]]:
    """(start, end, launched into an idle device) of every decode
    program on the device, helper programs ignored."""
    out, prev_end = [], None
    for start, end, name in modules:
        if end - start < trace_reduce.HELPER_NS:
            continue
        if trace_reduce.program_name(name) in DECODE_PROGRAMS:
            out.append((start, end, prev_end is not None and
                        start - prev_end >= LAUNCH_IDLE_NS))
        prev_end = end if prev_end is None else max(prev_end, end)
    return out


def clock_offset_ns(programs, launches: List[Interval]
                    ) -> Tuple[int, int]:
    """(host clock - device clock, launches it rests on): the median,
    over decode programs launched into an idle device, of the end of
    the nearest ``schedule.decode`` span less the program's start."""
    if not launches:
        return 0, 0
    ends = [e for _, e in launches]
    deltas = []
    for start, _, idle_before in programs:
        if not idle_before:
            continue
        k = bisect.bisect_left(ends, start)
        near = [ends[j] - start for j in (k - 1, k)
                if 0 <= j < len(ends)]
        deltas.append(min(near, key=abs))
    if not deltas:
        return 0, 0
    deltas.sort()
    return deltas[len(deltas) // 2], len(deltas)


def clock_residual_ns(programs, launches: List[Interval],
                      fetches: List[Interval]) -> Tuple[int, int]:
    """(worst violation, programs checked) on aligned clocks: how far a
    decode program starts BEFORE the ``schedule.decode`` span that
    launched it (the last to begin before the program's end), or how
    far its end lies from the nearest ``fetch`` span (0 inside one).
    Programs at the trace's edges, whose spans were cut off, are not
    checked."""
    if not launches or not fetches:
        return 0, 0
    fetch_starts = [s for s, _ in fetches]
    launch_starts = [s for s, _ in launches]
    worst, checked = 0, 0
    for start, end, _ in programs:
        if not fetches[0][0] <= end <= fetches[-1][1]:
            continue
        checked += 1
        k = bisect.bisect_right(fetch_starts, end) - 1
        off = max(end - fetches[k][1], 0)
        if off and k + 1 < len(fetches):
            off = min(off, fetches[k + 1][0] - end)
        j = bisect.bisect_right(launch_starts, end) - 1
        if j >= 0:
            off = max(off, launches[j][0] - start)
        worst = max(worst, off)
    return worst, checked


def attribute(data) -> Optional[dict]:
    """The attribution of device 0's idle time, or None where the
    trace has no device plane or no engine spans."""
    planes = trace_reduce.device_planes(data)
    spans = engine_spans(data)
    if not planes or not spans:
        return None
    lines = trace_reduce._lines(planes[0])
    mods = trace_reduce._events(lines["XLA Modules"]) \
        if "XLA Modules" in lines else []
    ops = trace_reduce._events(lines["XLA Ops"]) \
        if "XLA Ops" in lines else []
    busy_src = ops or mods
    if not busy_src:
        return None
    window = (min(s for s, _, _ in busy_src),
              max(e for _, e, _ in busy_src))
    busy = merge([(s, e) for s, e, _ in busy_src])
    idle = complement(busy, window)
    idle_ns = sum(e - s for s, e in idle)
    programs = decode_programs(mods)
    offset, anchors = clock_offset_ns(
        programs, spans.get("schedule.decode", []))
    spans = {p: [(a - offset, b - offset) for a, b in v]
             for p, v in spans.items()}
    residual, checked = clock_residual_ns(
        programs, spans.get("schedule.decode", []),
        spans.get("fetch", []))
    by_phase = {p: overlap_ns(idle, merge(v)) for p, v in spans.items()}
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": trace_reduce.union_seconds(busy),
        "idle_s": idle_ns / 1e9,
        "idle_gaps": len(idle),
        "idle_by_phase_s": {p: v / 1e9 for p, v in sorted(
            by_phase.items())},
        "idle_unspanned_s": (idle_ns - sum(by_phase.values())) / 1e9,
        "spans": {p: len(v) for p, v in sorted(spans.items())},
        "span_s": {p: sum(e - s for s, e in v) / 1e9
                   for p, v in sorted(spans.items())},
        "clock_offset_s": offset / 1e9,
        "clock_anchors": anchors,
        "clock_residual_s": residual / 1e9,
        "programs_checked": checked,
    }


def shares(att: dict) -> Dict[str, float]:
    """The four shares of the window, in per cent."""
    out = {"schedule": 0.0, "fetch": 0.0, "emit": 0.0,
           "unattributed": att["idle_unspanned_s"]}
    for phase, seconds in att["idle_by_phase_s"].items():
        out[SHARES[phase]] += seconds
    return {k: 100.0 * v / att["window_s"] for k, v in out.items()}


def idle_share(run: dict, part: str) -> Optional[float]:
    """What a metric file ``idle_pct.<part>`` returns for a run: the
    share, or None where the trace is gone, has no engine spans, or its
    clocks could not be aligned (no launch into an idle device, no
    decode program among the fetch spans) or still disagree by more
    than ``MAX_RESIDUAL_S`` once they are."""
    path = (run.get("trace") or {}).get("path")
    if not path or not os.path.isfile(path):
        return None
    if "host_spans" not in run:
        from jax.profiler import ProfileData
        run["host_spans"] = attribute(ProfileData.from_file(path))
    att = run["host_spans"]
    if att is None or not att["clock_anchors"] or \
            not att["programs_checked"] or \
            att["clock_residual_s"] > MAX_RESIDUAL_S:
        return None
    return shares(att)[part]


def record(data, out_json_gz: str, first_ms: float) -> None:
    """Keep the first ``first_ms`` (from the first program's start) of
    device 0's ``XLA Modules`` and ``XLA Ops`` lines and of the
    engine's spans, operation names cut to 48 characters: a small
    recorded trace for the tests."""
    import gzip
    import json
    plane = trace_reduce.device_planes(data)[0]
    lines = trace_reduce._lines(plane)
    evs = {k: trace_reduce._events(lines[k])
           for k in ("XLA Modules", "XLA Ops") if k in lines}
    start = evs["XLA Modules"][0][0]
    stop = start + int(first_ms * 1e6)

    def cut(events):
        return [[s - start, e - s, n[:48]] for s, e, n in events
                if s >= start and e <= stop]

    host = sorted((s, e, PREFIX + p)
                  for p, v in engine_spans(data).items() for s, e in v)
    doc = {"planes": [
        {"name": plane.name,
         "lines": {k: cut(v) for k, v in evs.items()}},
        {"name": "/host:CPU", "lines": {"decode-engine": cut(host)}}]}
    with gzip.open(out_json_gz, "wt") as f:
        json.dump(doc, f, separators=(",", ":"))


def main(argv=None) -> int:
    import argparse
    import json

    from jax.profiler import ProfileData
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("trace", help="an .xplane.pb, or a profile directory")
    p.add_argument("--record", default="")
    p.add_argument("--first-ms", type=float, default=400.0)
    args = p.parse_args(argv)
    path = args.trace
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    data = ProfileData.from_file(path)
    att = attribute(data)
    if att is None:
        print(json.dumps({"path": path, "engine_spans": 0}))
        return 1
    att["shares_pct"] = shares(att)
    att["idle_pct"] = 100.0 * att["idle_s"] / att["window_s"]
    att["path"] = path
    print(json.dumps(att, indent=1))
    if args.record:
        record(data, args.record, args.first_ms)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
