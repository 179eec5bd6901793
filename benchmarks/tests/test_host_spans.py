"""The idle attribution (``host_spans``): seconds worked out by hand on
planes built like ``trace_reduce.load_recorded``'s, its agreement with
``trace_reduce``'s own idle share, the alignment of the two clocks and
the check of what is left."""
import pathlib
from types import SimpleNamespace as NS

import pytest

from benchmarks import host_spans as hs
from benchmarks import trace_reduce as tr

US = 100_000         # one unit of the timeline below: 0.1 ms
# The first 400 ms of a traced run of mixtral8x7b-chat-steady on the
# chip (PR 26; ``python -m benchmarks.host_spans <trace> --record``):
# eleven decode steps, no prefill chunk, the engine's spans beside them.
RECORDED = pathlib.Path(__file__).with_name(
    "recorded_serve_hostspans.json.gz")


def line(name, events):
    return NS(name=name, events=[
        NS(start_ns=s, duration_ns=d, name=n) for s, d, n in events])


def trace(modules, ops, engine, others=()):
    """A device plane and a host plane whose second line is the engine
    thread's."""
    return NS(planes=[
        NS(name="/device:TPU:0", lines=[line("XLA Modules", modules),
                                        line("XLA Ops", ops)]),
        NS(name="/host:CPU", lines=[line("python", list(others)),
                                    line("python", engine)])])


# Two decode steps of 100 units, 40 apart, then 30, a chunk of 50 and 20
# until a last step: three idle gaps, 90 units in 440.
MODULES = [(0, 100 * US, "jit__paged_step(1)"),
           (140 * US, 100 * US, "jit__paged_step(1)"),
           (270 * US, 50 * US, "jit__paged_prefill_chunk(2)"),
           (340 * US, 100 * US, "jit__paged_step(1)")]
OPS = [(0, 100 * US, "while.5"), (10 * US, 30 * US, "fusion.9"),
       (140 * US, 100 * US, "while.5"), (270 * US, 50 * US, "fusion.11"),
       (340 * US, 100 * US, "while.5")]
P = hs.PREFIX
ENGINE = [
    # First gap, 100-140: fetch returns at 110, emit to 125, admit to
    # 128, an empty prefill scan to 130, 5 under no span, decode
    # scheduling from 135 to its dispatch, where the device starts.
    (90 * US, 20 * US, P + "fetch"),
    (110 * US, 15 * US, P + "emit"),
    (125 * US, 3 * US, P + "schedule.admit"),
    (128 * US, 2 * US, P + "schedule.prefill"),
    (135 * US, 5 * US, P + "schedule.decode"),
    # A child span inside a phase is not a phase.
    (136 * US, 2 * US, P + "schedule.decode.upload"),
    # Wholly inside the second step: no idle time under it.
    (150 * US, 40 * US, P + "wait"),
    # Second gap, 240-270: fetch to 250, emit to 262, prefill
    # scheduling to 275.
    (200 * US, 50 * US, P + "fetch"),
    (250 * US, 12 * US, P + "emit"),
    (262 * US, 13 * US, P + "schedule.prefill"),
    # Third gap, 320-340: decode scheduling throughout.
    (318 * US, 22 * US, P + "schedule.decode"),
    (340 * US, 102 * US, P + "fetch"),
]


def test_idle_seconds_by_phase_worked_by_hand():
    att = hs.attribute(trace(MODULES, OPS, ENGINE))
    assert att["window_s"] == pytest.approx(440e-4)
    assert att["idle_s"] == pytest.approx(90e-4)
    assert att["idle_gaps"] == 3
    by = att["idle_by_phase_s"]
    assert by["fetch"] == pytest.approx((10 + 10) * 1e-4)
    assert by["emit"] == pytest.approx((15 + 12) * 1e-4)
    assert by["schedule.admit"] == pytest.approx(3e-4)
    assert by["schedule.prefill"] == pytest.approx((2 + 8) * 1e-4)
    assert by["schedule.decode"] == pytest.approx((5 + 20) * 1e-4)
    assert by["wait"] == 0.0
    assert att["idle_unspanned_s"] == pytest.approx(5e-4)
    assert att["clock_offset_s"] == 0.0 and att["clock_anchors"] == 2
    assert att["spans"]["fetch"] == 3
    assert "schedule.decode.upload" not in att["spans"]
    got = hs.shares(att)
    assert got["schedule"] == pytest.approx(100 * 38 / 440)
    assert got["fetch"] == pytest.approx(100 * 20 / 440)
    assert got["emit"] == pytest.approx(100 * 27 / 440)
    assert got["unattributed"] == pytest.approx(100 * 5 / 440)


def test_shares_sum_to_trace_reduce_idle_share():
    data = trace(MODULES, OPS, ENGINE)
    red = tr.reduce_data(data)
    att = hs.attribute(data)
    assert att["busy_s"] == pytest.approx(red["busy_s"])
    assert att["window_s"] == pytest.approx(red["window_s"])
    idle_pct = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    assert sum(hs.shares(att).values()) == pytest.approx(idle_pct)


def test_engine_line_is_found_among_the_host_lines():
    noise = [(0, 5 * US, "PjitFunction(_paged_step)"),
             (7 * US, 2 * US, P + "emit")]      # one stray span only
    spans = hs.engine_spans(trace(MODULES, OPS, ENGINE, others=noise))
    assert len(spans["fetch"]) == 3 and len(spans["emit"]) == 2
    # A program older than the seam: nothing to read, and no error.
    assert hs.engine_spans(trace(MODULES, OPS, [])) == {}
    assert hs.attribute(trace(MODULES, OPS, [])) is None
    assert hs.attribute(NS(planes=[])) is None


def shifted(ns):
    return [(s + ns, d, n) for s, d, n in ENGINE]


@pytest.mark.parametrize("late_ns", [-3 * US, 0, 18 * US])
def test_the_host_clock_is_aligned_on_the_launches(late_ns):
    """Whatever the host plane's clock is off by (1.8 ms in one chip
    trace), the attribution is the one of aligned clocks: a decode
    program launched into an idle device starts where its
    ``schedule.decode`` span ends."""
    att = hs.attribute(trace(MODULES, OPS, shifted(late_ns)))
    assert att["clock_offset_s"] == pytest.approx(late_ns / 1e9)
    # The first step has no program before it: two launches anchor.
    assert att["clock_anchors"] == 2
    assert att["clock_residual_s"] == 0.0
    assert att["programs_checked"] == 3
    assert hs.shares(att) == pytest.approx(
        hs.shares(hs.attribute(trace(MODULES, OPS, ENGINE))))


def test_a_step_behind_a_chunk_is_no_anchor():
    # The last step starts 0.2 ms after the chunk: the device set its
    # start, whenever the host dispatched it (here 1 ms earlier).
    modules = MODULES[:2] + [(270 * US, 68 * US, MODULES[2][2])] + \
        MODULES[3:]
    engine = [e for e in ENGINE if e[0] < 318 * US] + [
        (318 * US, 12 * US, P + "schedule.decode"),
        (330 * US, 112 * US, P + "fetch")]
    att = hs.attribute(trace(modules, OPS, engine))
    assert att["clock_anchors"] == 1 and att["clock_offset_s"] == 0.0
    assert att["clock_residual_s"] == 0.0


def test_what_alignment_leaves_is_the_worst_violation():
    # The second step's fetch returns 7 units before the step ends on
    # the device: no offset explains that and the launches both.
    engine = [(s, 33 * US, n) if s == 200 * US else (s, d, n)
              for s, d, n in ENGINE]
    att = hs.attribute(trace(MODULES, OPS, engine))
    assert att["clock_offset_s"] == 0.0
    assert att["clock_residual_s"] == pytest.approx(7e-4)
    # A program that starts before the span that launched it began,
    # and one that ends before its fetch has begun.
    assert hs.clock_residual_ns(
        [(100, 200, True)], [(110, 120)], [(120, 250)]) == (10, 1)
    assert hs.clock_residual_ns(
        [(100, 200, True)], [(90, 95)], [(0, 50), (230, 300)]) == (30, 1)
    assert hs.clock_residual_ns([(100, 200, True)], [], []) == (0, 0)


def test_idle_share_refuses_a_trace_it_cannot_trust(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(b"")
    good = hs.attribute(trace(MODULES, OPS, ENGINE))
    run = {"trace": {"path": str(path)}, "host_spans": good}
    assert hs.idle_share(run, "fetch") == pytest.approx(100 * 20 / 440)
    for bad in (dict(good, clock_residual_s=0.6e-3),
                dict(good, clock_anchors=0),
                dict(good, programs_checked=0), None):
        assert hs.idle_share({**run, "host_spans": bad}, "fetch") is None
    # The trace is gone (run.py removes it before the metrics today).
    assert hs.idle_share({"trace": {"path": str(tmp_path / "no")}},
                         "fetch") is None
    assert hs.idle_share({"trace": None}, "fetch") is None


def test_recorded_chip_trace_is_aligned_and_attributed():
    data = tr.load_recorded(RECORDED)
    att, red = hs.attribute(data), tr.reduce_data(data)
    # The host plane ran 0.38 ms ahead of the device in this trace.
    assert att["clock_offset_s"] == pytest.approx(0.38e-3, abs=0.01e-3)
    assert att["clock_anchors"] == 10 and att["programs_checked"] == 11
    assert att["clock_residual_s"] == 0.0
    idle_pct = 100.0 * (1.0 - red["busy_s"] / red["window_s"])
    got = hs.shares(att)
    assert sum(got.values()) == pytest.approx(idle_pct)
    # The phases partition the loop: next to nothing is under no span.
    assert got["unattributed"] < 0.1 * idle_pct
    assert got["schedule"] > got["emit"] > got["fetch"] > 0.0


def test_interval_arithmetic():
    assert hs.merge([(5, 9), (0, 3), (2, 4), (9, 10)]) == [(0, 4), (5, 10)]
    assert hs.complement([(0, 4), (5, 10)], (0, 12)) == [(4, 5), (10, 12)]
    assert hs.complement([], (3, 8)) == [(3, 8)]
    assert hs.overlap_ns([(0, 4), (6, 9)], [(2, 7), (8, 20)]) == 2 + 1 + 1
