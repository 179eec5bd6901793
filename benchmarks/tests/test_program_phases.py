"""The thirteen readers of PR 38 on hand-made scrapes in the form the
runner keeps them (``run["samples"]``: (time, {(name, labels): value})):
each metric's arithmetic, ``None`` on a program without the series (the
parent under these files), 0 where the counter exists and did not grow,
and the start-up metrics reading the FIRST scrape inside the window."""
import pytest

from benchmarks import cells

PHASES = ("schedule.admit", "schedule.prefill", "schedule.decode",
          "fetch", "emit", "wait")


def _series(name, label, values):
    return {(name, ((label, k),)): float(v) for k, v in values.items()}


def _scrape(steps, starved, drained, long_s, gc_s, startup, compiled):
    out = {}
    out.update(_series("stpu_engine_steps_total", "kind", steps))
    out.update(_series("stpu_engine_starved_dispatches_total", "kind",
                       starved))
    out.update(_series("stpu_engine_drained_seconds_total", "phase",
                       dict(zip(PHASES, drained))))
    out.update(_series("stpu_engine_long_phase_seconds_total", "phase",
                       dict(zip(PHASES[:5], long_s))))
    out.update(_series("stpu_process_gc_seconds_total", "generation",
                       dict(zip("012", gc_s))))
    out.update(_series("stpu_startup_seconds_total", "phase", startup))
    out.update(_series("stpu_xla_compiles_total", "source", compiled))
    return out


STARTUP = {"import": 6.25, "weights": 3.5, "engine": 0.75, "warmup": 9.0}
# A scrape of the lead-in (before t0), two inside the window 50 s apart
# and one after it. An engine restart AFTER the window's first scrape
# would add to ``engine``: the readers must not see it.
BEFORE = _scrape(
    {"decode": 900, "verify": 0, "prefill": 90},
    {"decode": 1, "verify": 0, "prefill": 5},
    (0.01, 0.02, 0.03, 0.0, 0.01, 4.0), (0, 1.2, 1.1, 0, 0),
    (0.2, 0.1, 0.3), {**STARTUP, "warmup": 8.0},
    {"compiled": 2, "cache": 5})
FIRST = _scrape(
    {"decode": 1000, "verify": 0, "prefill": 100},
    {"decode": 2, "verify": 0, "prefill": 6},
    (0.01, 0.02, 0.03, 0.0, 0.01, 4.0), (0, 1.2, 1.1, 0, 0),
    (0.25, 0.1, 0.3), STARTUP, {"compiled": 3, "cache": 5})
LAST = _scrape(
    {"decode": 5000, "verify": 0, "prefill": 500},
    {"decode": 102, "verify": 0, "prefill": 9},
    (0.11, 0.07, 0.23, 0.0, 0.06, 4.5), (0, 1.2, 1.1, 0, 0.085),
    (0.30, 0.12, 0.34), {**STARTUP, "engine": 1.5},
    {"compiled": 3, "cache": 5})
AFTER = _scrape(
    {"decode": 5100, "verify": 0, "prefill": 510},
    {"decode": 150, "verify": 0, "prefill": 9},
    (0.5, 0.5, 0.5, 0.5, 0.5, 5.0), (1, 2, 2, 1, 1), (1, 1, 1),
    {**STARTUP, "engine": 1.5}, {"compiled": 9, "cache": 5})
SAMPLES = [(95.0, BEFORE), (100.5, FIRST), (149.5, LAST), (151.0, AFTER)]
NEW = ("stpu_engine_starved_dispatches_total",
       "stpu_engine_drained_seconds_total",
       "stpu_engine_long_phase_seconds_total",
       "stpu_process_gc_seconds_total", "stpu_startup_seconds_total")


def _run(samples):
    return {"samples": samples, "t0": 100.0, "t1": 150.0}


def _parent(samples):
    """The same scrapes of a program older than PR 38: the step and
    compile counters are there (PR 26), the new series are not."""
    return [(t, {k: v for k, v in s.items() if k[0] not in NEW})
            for t, s in samples]


@pytest.fixture(scope="module")
def metrics():
    return {m.NAME: m for m in cells.layer_metrics("serve")}


EXPECTED = {
    "startup_import_s": 6.25, "startup_weights_s": 3.5,
    "startup_engine_s": 0.75, "startup_warmup_s": 9.0,
    "startup_programs_compiled": 3.0,
    "decode_starved_pct": 100.0 * 100 / 4000,
    "host_bound_idle_pct.schedule": 100.0 * (0.10 + 0.05 + 0.20) / 50.0,
    "host_bound_idle_pct.fetch": 0.0,
    "host_bound_idle_pct.emit": 100.0 * 0.05 / 50.0,
    "engine_long_phase_ms.schedule": 0.0,
    "engine_long_phase_ms.fetch": 0.0,
    "engine_long_phase_ms.emit": 85.0,
    "gc_ms_in_window": 1e3 * (0.05 + 0.02 + 0.04),
}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_each_reader_by_hand(metrics, name):
    mod = metrics[name]
    assert mod.RUNNERS == ("serve",) and mod.SOURCE == "program_counter"
    assert mod.compute(_run(SAMPLES)) == pytest.approx(EXPECTED[name],
                                                       abs=1e-9)


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_program_without_the_series_reads_none(metrics, name):
    # ``startup_programs_compiled`` too, though PR 26's counter is
    # there: it is read with the phases it explains or not at all.
    assert metrics[name].compute(_run(_parent(SAMPLES))) is None


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_a_window_without_a_scrape_reads_none(metrics, name):
    assert metrics[name].compute(_run(SAMPLES[:1] + SAMPLES[3:])) is None


def test_a_counter_that_did_not_grow_reads_zero(metrics):
    later = {**FIRST, **_series("stpu_engine_steps_total", "kind",
                                {"decode": 5000, "verify": 0,
                                 "prefill": 500})}
    quiet = _run([(100.5, FIRST), (149.5, later)])
    for name, mod in metrics.items():
        if name.startswith(("decode_starved", "host_bound_idle",
                            "engine_long_phase", "gc_ms")):
            assert mod.compute(quiet) == 0.0, name


def test_the_parts_are_the_loops_phases_but_wait(metrics):
    from benchmarks.layer_metrics import _window
    folded = [p for part in _window.PARTS.values() for p in part]
    assert sorted(folded) == sorted(p for p in PHASES if p != "wait")


def test_the_declaration_lists_each_reader_for_the_serving_cells():
    import json
    decl = json.loads((cells.ROOT.parent / "BENCHMARK.json").read_text())
    serving = [w["name"] for w in decl["workloads"]
               if w["name"] != "mistral7b-lora-2k"]
    by_name = {m["name"]: m for m in decl["per_layer"]}
    mods = {m.NAME: m for m in cells.layer_metrics("serve")}
    for name in EXPECTED:
        entry, mod = by_name[name], mods[name]
        assert entry["workloads"] == serving
        assert (entry["unit"], entry["better"], entry["layer"],
                entry["moves"], entry["source"]) == (
            mod.UNIT, mod.BETTER, mod.LAYER, mod.MOVES, mod.SOURCE)
