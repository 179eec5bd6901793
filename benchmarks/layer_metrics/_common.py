"""What several per-layer metrics read the same way from a run's reduced
trace (``trace_reduce.reduce``). A file whose name starts with ``_`` is
not a metric."""


def program_mean_ms(run, program: str):
    """Mean device time of one execution of ``program``, or None."""
    row = ((run.get("trace") or {}).get("programs") or {}).get(program)
    if not row or not row["count"]:
        return None
    return row["total_s"] / row["count"] * 1e3


def idle_pct(run):
    """1 - busy union / traced window, in per cent, or None."""
    trace = run.get("trace") or {}
    if not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
