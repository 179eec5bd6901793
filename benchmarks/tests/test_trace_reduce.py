"""The trace reduction: arithmetic on a hand-made trace, and the whole
reduction on a small trace recorded on the chip and kept beside this
file (``recorded_serve_trace.json.gz``: the first two engine iterations,
165 ms, of the device plane's ``XLA Modules`` and ``XLA Ops`` lines of
cell 1's first traced run on a TPU v5e, written by
``trace_reduce.record`` and cut, names shortened to 160 characters)."""
import pathlib
from types import SimpleNamespace as NS

import pytest

from benchmarks import trace_reduce as tr

HERE = pathlib.Path(__file__).resolve().parent


def plane(modules, ops, name="/device:TPU:0"):
    def line(lname, evs):
        return NS(name=lname, events=[
            NS(start_ns=s, duration_ns=d, name=n) for s, d, n in evs])
    return NS(name=name, lines=[line("XLA Modules", modules),
                                line("XLA Ops", ops)])


def test_union_counts_an_overlap_once():
    assert tr.union_seconds([(0, 10), (5, 20), (30, 40)]) == 30 / 1e9
    assert tr.union_seconds([]) == 0.0


def test_self_time_leaves_out_nested_children():
    # A while loop of 100 ns holds two fusions of 30 ns each.
    events = [(0, 100, "while.1"), (10, 40, "fusion.2"),
              (50, 80, "fusion.2"), (100, 120, "copy.3")]
    got = tr.self_times(events)
    assert got["while.1"] == [1, 40 / 1e9]
    assert got["fusion.2"] == [2, 60 / 1e9]
    assert got["copy.3"] == [1, 20 / 1e9]


def test_program_names_lose_prefix_and_fingerprint():
    assert tr.program_name("jit__paged_step(1234567)") == "_paged_step"
    assert tr.program_name("jit_step_fn(9)") == "step_fn"


def test_reduction_of_a_hand_made_trace():
    us = 1000
    modules = [(0, 100 * us, "jit__paged_step(1)"),
               (150 * us, 50 * us, "jit__paged_prefill_chunk(2)"),
               (200 * us, 100 * us, "jit__paged_step(1)")]
    ops = [(0, 100 * us, "while.5"), (10 * us, 20 * us, "all-reduce.7"),
           (40 * us, 50 * us, "fusion.9"),
           (150 * us, 50 * us, "fusion.11"),
           (200 * us, 100 * us, "while.5"),
           (210 * us, 20 * us, "all-reduce.7")]
    data = NS(planes=[plane(modules, ops),
                      NS(name="/host:CPU", lines=[])])
    got = tr.reduce_data(data)
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(300e-6)
    assert got["busy_s"] == pytest.approx(250e-6)
    assert got["programs"]["_paged_step"] == {
        "count": 2.0, "total_s": pytest.approx(200e-6)}
    assert got["programs"]["_paged_prefill_chunk"]["count"] == 1.0
    assert got["collective_s"] == pytest.approx(40e-6)
    assert got["collective_in_program_s"] == {
        "_paged_step": pytest.approx(40e-6)}
    assert got["gaps"] == {
        "_paged_step -> _paged_prefill_chunk": pytest.approx(50e-6)}
    top = tr.breakdown(got)
    names = [n for n, _ in top["device_ops"]]
    assert "while.5" not in names          # a container is not a kernel
    assert names[0] == "fusion.9"
    assert top["idle_gaps"][0][0] == "_paged_step -> _paged_prefill_chunk"
    # A window keeps what lies inside it and clips what crosses it.
    half = tr.reduce_data(data, window=(0, 150 * us))
    assert half["window_s"] == pytest.approx(150e-6)
    assert half["busy_s"] == pytest.approx(100e-6)
    assert half["programs"]["_paged_step"]["count"] == 1.0


def test_steady_window_spans_the_last_executions():
    modules = [(i * 1000, 600, "jit_step_fn(3)") for i in range(8)]
    data = NS(planes=[plane(modules, [])])
    assert tr.steady_window(data, "step_fn", 3) == (5000, 7600)
    assert tr.steady_window(data, "step_fn", 9) is None
    assert tr.steady_window(data, "other", 1) is None


# ---------------------------------------------------- the recorded trace
@pytest.fixture(scope="module")
def recorded():
    return tr.load_recorded(HERE / "recorded_serve_trace.json.gz")


def line_events(data, name):
    (plane,) = tr.device_planes(data)
    (line,) = [ln for ln in plane.lines if ln.name == name]
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def test_recorded_trace_programs(recorded):
    got = tr.reduce_data(recorded)
    assert got["devices"] == 1
    # Counted here by hand from the XLA Modules line.
    mods = line_events(recorded, "XLA Modules")
    for program, prefix in (("_paged_step", "jit__paged_step("),
                            ("_paged_prefill_chunk",
                             "jit__paged_prefill_chunk(")):
        mine = [e - s for s, e, n in mods if n.startswith(prefix)]
        assert got["programs"][program]["count"] == len(mine) == 2
        assert got["programs"][program]["total_s"] == pytest.approx(
            sum(mine) / 1e9)
    # As the chip ran them: a chunk of 64 tokens and a step of 32 slots
    # through 16 layers of Mistral-7B's widths.
    assert got["programs"]["_paged_prefill_chunk"]["total_s"] == \
        pytest.approx(0.0792, abs=5e-4)
    assert got["programs"]["_paged_step"]["total_s"] == \
        pytest.approx(0.0735, abs=5e-4)


def test_recorded_trace_busy_union_and_idle_share(recorded):
    got = tr.reduce_data(recorded)
    ops = line_events(recorded, "XLA Ops")
    # Busy by another route: walk the sorted end points and count the
    # time during which at least one operation is open.
    points = sorted([(s, 1) for s, _, _ in ops] + [(e, -1) for _, e, _ in ops],
                    key=lambda t: (t[0], -t[1]))
    open_now, since, busy = 0, 0, 0
    for at, step in points:
        if open_now == 0 and step == 1:
            since = at
        open_now += step
        if open_now == 0:
            busy += at - since
    assert got["busy_s"] == pytest.approx(busy / 1e9)
    span = max(e for _, e, _ in ops) - min(s for s, _, _ in ops)
    assert got["window_s"] == pytest.approx(span / 1e9)
    idle = 1.0 - got["busy_s"] / got["window_s"]
    assert 0.05 < idle < 0.10           # 7 % between the programs
    # Self times add up to the busy time: nothing is counted twice.
    assert sum(v["self_s"] for v in got["ops"].values()) == \
        pytest.approx(got["busy_s"], rel=1e-6)
    # No collective on one chip; the gaps lie between the two programs.
    assert got["collective_s"] == 0.0
    gaps = dict(tr.breakdown(got)["idle_gaps"])
    assert any("_paged_step" in k and "_paged_prefill_chunk" in k
               for k in gaps)
    top = tr.breakdown(got)["device_ops"]
    assert len(top) == 10 and not any(
        n.startswith("%while") for n, _ in top)
