"""From a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` alone. A TPU trace has one plane a
chip (``/device:TPU:<n>``); on it the line ``XLA Modules`` holds one
event for every execution of a compiled program, named after the jitted
function (``jit__paged_step(<fingerprint>)``), and the line ``XLA Ops``
one event for every HLO operation inside them, containers (``while``,
``conditional``, ``call``) included with their children nested inside.

What is reduced, per device and then averaged over the devices:

* busy: the union of the intervals in which an operation ran (the ``XLA
  Ops`` line; a container covers its children, so the union counts
  neither twice), inside the kept window; idle share = 1 - busy/window;
* time per program: count, total and mean device time by the jitted
  function's name with the fingerprint cut off;
* time per operation: self time (an event's duration less its nested
  children's) by the operation's name — kernels and fusions by the names
  the trace gives them;
* collective time: self time of ``all-reduce``, ``all-gather``,
  ``reduce-scatter``, ``all-to-all`` and ``collective-permute``
  operations (their ``-start``/``-done`` halves included);
* idle gaps: the gaps between programs, named by the program before and
  the program after (helper programs under 50 microseconds, which the
  host issues to convert a scalar argument, count as part of the gap),
  summed by that name.

``reduce(path, window=...)`` keeps the events inside a window given in
the trace's own nanoseconds; ``steady_window`` finds, for a training
trace that holds the compile and the first steps too, the span of the
last N executions of one program.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)")
CONTAINER = re.compile(r"^%?(while|conditional|call)([.\s(]|$)")
FINGERPRINT = re.compile(r"\(\d+\)$")
# Between two engine programs the host issues scalar conversions
# (``jit_convert_element_type``, under a microsecond each) for the next
# one's arguments. They are the host preparing inputs, not work: a gap is
# named by the programs of at least this length on either side.
HELPER_NS = 50_000


def find_xplane(profile_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def program_name(event_name: str) -> str:
    """``jit__paged_step(123)`` -> ``_paged_step``."""
    name = FINGERPRINT.sub("", event_name.strip())
    return name[4:] if name.startswith("jit_") else name


def _events(line) -> List[Tuple[int, int, str]]:
    out = [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
           for e in line.events]
    out.sort(key=lambda t: (t[0], -t[1]))
    return out


def union_seconds(intervals: List[Tuple[int, int]]) -> float:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def self_times(events: List[Tuple[int, int, str]]) -> Dict[str, List]:
    """name -> [count, self seconds]: duration less nested children's."""
    out: Dict[str, List] = {}
    stack: List[List] = []          # [end, name, duration, children]

    def close(item):
        _, name, dur, kids = item
        row = out.setdefault(name, [0, 0.0])
        row[0] += 1
        row[1] += max(dur - kids, 0) / 1e9

    for s, e, name in events:
        while stack and s >= stack[-1][0]:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([e, name, e - s, 0])
    while stack:
        close(stack.pop())
    return out


def _clip(events, window):
    if window is None:
        return events
    lo, hi = window
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def device_planes(data) -> List:
    planes = [(int(DEVICE_PLANE.match(p.name).group(1)), p)
              for p in data.planes if DEVICE_PLANE.match(p.name)]
    return [p for _, p in sorted(planes, key=lambda t: t[0])]


def _lines(plane) -> Dict[str, object]:
    return {line.name: line for line in plane.lines}


def steady_window(data, program: str, last_n: int
                  ) -> Optional[Tuple[int, int]]:
    """(start, end) in ns from the start of the ``last_n``-th last
    execution of ``program`` to the end of the last, on device 0."""
    planes = device_planes(data)
    if not planes:
        return None
    mods = _lines(planes[0]).get("XLA Modules")
    if mods is None:
        return None
    runs = [(s, e) for s, e, n in _events(mods)
            if program_name(n) == program]
    if len(runs) < last_n or last_n < 1:
        return None
    return runs[-last_n][0], runs[-1][1]


def reduce_device(plane, window=None) -> dict:
    lines = _lines(plane)
    mods = _clip(_events(lines["XLA Modules"]), window) \
        if "XLA Modules" in lines else []
    ops = _clip(_events(lines["XLA Ops"]), window) \
        if "XLA Ops" in lines else []
    busy_src = ops or mods
    if not busy_src:
        return {"window_s": 0.0, "busy_s": 0.0, "programs": {},
                "ops": {}, "collective_s": 0.0,
                "collective_in_program_s": {}, "gaps": {}}
    if window is None:
        window = (min(s for s, _, _ in busy_src),
                  max(e for _, e, _ in busy_src))
    programs: Dict[str, List] = {}
    for s, e, n in mods:
        row = programs.setdefault(program_name(n), [0, 0.0])
        row[0] += 1
        row[1] += (e - s) / 1e9
    selfs = self_times(ops)
    collective = sum(v[1] for k, v in selfs.items() if COLLECTIVE.match(k))
    # Collective time by the program it ran inside (an operation lies
    # inside the execution of its program on the same device).
    starts = [s for s, _, _ in mods]
    in_program: Dict[str, float] = {}
    for s, e, n in ops:
        if not COLLECTIVE.match(n):
            continue
        at = bisect.bisect_right(starts, s) - 1
        if at >= 0 and s < mods[at][1]:
            key = program_name(mods[at][2])
            in_program[key] = in_program.get(key, 0.0) + (e - s) / 1e9
    gaps: Dict[str, float] = {}
    prev_end, prev_name = None, None
    for s, e, n in mods:
        if e - s < HELPER_NS:
            continue
        if prev_end is not None and s > prev_end:
            key = f"{prev_name} -> {program_name(n)}"
            gaps[key] = gaps.get(key, 0.0) + (s - prev_end) / 1e9
        if prev_end is None or e > prev_end:
            prev_end, prev_name = e, program_name(n)
    return {
        "window_s": (window[1] - window[0]) / 1e9,
        "busy_s": union_seconds([(s, e) for s, e, _ in busy_src]),
        "programs": {k: {"count": v[0], "total_s": v[1]}
                     for k, v in programs.items()},
        "ops": {k: {"count": v[0], "self_s": v[1]}
                for k, v in selfs.items()},
        "collective_s": collective,
        "collective_in_program_s": in_program,
        "gaps": gaps,
    }


def reduce(path: str, window=None) -> dict:
    """The whole trace: per-device reductions and their mean."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    return reduce_data(data, window)


def reduce_data(data, window=None) -> dict:
    devices = [reduce_device(p, window) for p in device_planes(data)]
    devices = [d for d in devices if d["window_s"] > 0]
    if not devices:
        return {"devices": 0}
    n = len(devices)

    def merged(key: str, field: str) -> Dict[str, dict]:
        out: Dict[str, dict] = {}
        for d in devices:
            for name, row in d[key].items():
                acc = out.setdefault(name, {"count": 0.0, field: 0.0})
                acc["count"] += row["count"] / n
                acc[field] += row[field] / n
        return out

    gaps: Dict[str, float] = {}
    in_program: Dict[str, float] = {}
    for d in devices:
        for k, v in d["gaps"].items():
            gaps[k] = gaps.get(k, 0.0) + v / n
        for k, v in d["collective_in_program_s"].items():
            in_program[k] = in_program.get(k, 0.0) + v / n
    return {
        "devices": n,
        "window_s": sum(d["window_s"] for d in devices) / n,
        "busy_s": sum(d["busy_s"] for d in devices) / n,
        "busy_s_per_device": [d["busy_s"] for d in devices],
        "programs": merged("programs", "total_s"),
        "ops": merged("ops", "self_s"),
        "collective_s": sum(d["collective_s"] for d in devices) / n,
        "collective_in_program_s": in_program,
        "gaps": gaps,
    }


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The contract's ``breakdown``: the device operations that took
    most (self) time and the idle gaps that took most, ten each."""
    ops = sorted(((k, v["self_s"]) for k, v in reduced["ops"].items()
                  if not CONTAINER.match(k)),
                 key=lambda t: -t[1])[:top]
    gaps = sorted(reduced["gaps"].items(), key=lambda t: -t[1])[:top]
    # The trace names an operation by its whole HLO text; the head of
    # it (name, result shape, opcode) is enough to find it again.
    return {"device_ops": [[k[:160], v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def describe(path: str, limit: int = 12) -> str:
    """Planes, lines and the commonest event names: what to look at by
    hand before trusting a reduction."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            tally: Dict[str, List] = {}
            for e in evs:
                row = tally.setdefault(e.name, [0, 0])
                row[0] += 1
                row[1] += e.duration_ns
            for name, (c, ns) in sorted(
                    tally.items(), key=lambda t: -t[1][1])[:limit]:
                out.append(f"    {c:7d} x {ns / 1e6:12.3f} ms  {name[:90]}")
    return "\n".join(out)


def record(path: str, out_json: str, first_ms: float = 400.0) -> None:
    """Keep the first ``first_ms`` of every device plane's ``XLA
    Modules`` and ``XLA Ops`` lines as JSON: a small recorded trace for
    the tests (``load_recorded`` reads it back)."""
    import json
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in device_planes(data):
        lines = _lines(plane)
        evs = {k: _events(lines[k]) for k in ("XLA Modules", "XLA Ops")
               if k in lines}
        if not evs.get("XLA Modules"):
            continue
        start = evs["XLA Modules"][0][0]
        stop = start + int(first_ms * 1e6)
        planes.append({"name": plane.name, "lines": {
            k: [[s - start, e - s, n] for s, e, n in v
                if s >= start and e <= stop] for k, v in evs.items()}})
    with open(out_json, "w") as f:
        json.dump({"planes": planes}, f, separators=(",", ":"))


def load_recorded(path: str):
    """A ``record``-ed trace as objects shaped like ProfileData's."""
    import gzip
    import json
    from types import SimpleNamespace as NS
    opener = gzip.open if str(path).endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    return NS(planes=[NS(name=p["name"], lines=[
        NS(name=k, events=[NS(start_ns=s, duration_ns=d, name=n)
                           for s, d, n in v])
        for k, v in p["lines"].items()]) for p in doc["planes"]])
