"""What the metrics of PR 38 read the same way from the scrapes of the
server's ``/metrics`` (``run["samples"]``): a value at the window's
FIRST scrape (start-up's counters have stopped by then, and no cell
restarts an engine), and the engine loop's per-phase counters folded
into the three parts a reader asks about. A file whose name starts with
``_`` is not a metric."""
from benchmarks.layer_metrics import _scrapes

# The loop's phases by part: the three ``schedule.*`` end in a dispatch.
PARTS = {"schedule": ("schedule.admit", "schedule.prefill",
                      "schedule.decode"),
         "fetch": ("fetch",), "emit": ("emit",)}


def first_value(run, name: str, **labels):
    """One series at the first scrape at or after ``t0``; None where
    the window holds no scrape or the program does not export the
    series (it is older, or the platform could not say)."""
    key = (name, tuple(sorted(labels.items())))
    for t, sample in run["samples"]:
        if run["t0"] <= t <= run["t1"]:
            return sample.get(key)
    return None


def part_delta(run, name: str, part: str):
    """Growth over the window of ``name{phase}`` summed over the
    phases of ``part``; None where any of them is absent."""
    grown = [_scrapes.counter_delta(run, name, phase=p)
             for p in PARTS[part]]
    return None if None in grown else sum(grown)


def startup_s(run, phase: str):
    return first_value(run, "stpu_startup_seconds_total", phase=phase)


def host_bound_idle_pct(run, part: str):
    """Seconds the loop knew the device's queue drained, under
    ``part``, over the window: the program's own LOWER bound on that
    part of the device's idle share, from 50 s of counters where the
    trace has 3 s."""
    drained = part_delta(run, "stpu_engine_drained_seconds_total", part)
    if drained is None:
        return None
    return 100.0 * drained / (run["t1"] - run["t0"])


def long_phase_ms(run, part: str):
    """Milliseconds inside phase instances of ``part`` that lasted
    ``phases.LONG_PHASE_S`` (0.06 s) or more, over the window: 0 is
    the value of a run without a pause."""
    seconds = part_delta(run, "stpu_engine_long_phase_seconds_total",
                         part)
    return None if seconds is None else 1e3 * seconds
