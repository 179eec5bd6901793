"""What the decoder-hybrid-decoder metrics read the same way from a
run. A file whose name starts with ``_`` is not a metric."""

FAMILY = "phi4flash"


def labelled_gauge_mean(run, name: str, **labels):
    """Mean of one labelled gauge series over the window's scrapes, or
    None where the program does not export it."""
    key = (name, tuple(sorted(labels.items())))
    vals = [s[key] for t, s in run["samples"]
            if run["t0"] <= t <= run["t1"] and key in s]
    return sum(vals) / len(vals) if vals else None


def decoding_lengths(records, a, b):
    """(tokens, weight) of every sequence between its first and its
    last token at the client inside [a, b]: its length at the middle of
    the time it decoded there (tokens taken as arriving evenly), and
    the share of the window it decoded for."""
    out = []
    for r in records:
        if r["first"] is None or r["last"] is None:
            continue
        lo, hi = max(r["first"], a), min(r["last"], b)
        if hi <= lo:
            continue
        span = max(r["last"] - r["first"], 1e-9)
        mid = ((lo + hi) / 2 - r["first"]) / span * len(r["tokens"])
        out.append((r["prompt_tokens"] + mid, (hi - lo) / max(b - a, 1e-9)))
    return out
