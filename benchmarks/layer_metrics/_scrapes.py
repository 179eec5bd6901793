"""What several per-layer metrics read the same way from the scrapes of
the server's ``/metrics`` (``run["samples"]``, every 0.5 s of a traced
run). A file whose name starts with ``_`` is not a metric."""


def counter_delta(run, name: str, **labels):
    """Growth of one counter series between the first scrape at or
    after ``t0`` and the last at or before ``t1``; None where the
    program does not export the series (it is older than the counter)
    or the window holds fewer than two scrapes."""
    key = (name, tuple(sorted(labels.items())))
    inside = [s for t, s in run["samples"] if run["t0"] <= t <= run["t1"]]
    if len(inside) < 2 or key not in inside[-1]:
        return None
    return inside[-1][key] - inside[0].get(key, 0.0)


def histogram_p95_ms(run, name: str):
    """p95 of a histogram's growth over the window, interpolated inside
    the bucket, in milliseconds; None where it did not grow."""
    from benchmarks import loadgen
    bounds, cum = loadgen.histogram_delta(
        run["samples"], name, run["t0"], run["t1"])
    q = loadgen.histogram_quantile(bounds, cum, 0.95)
    return None if q is None else q * 1e3
