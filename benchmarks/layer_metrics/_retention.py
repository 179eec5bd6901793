"""What the power-retention metrics read the same way from a run's
client records. A file whose name starts with ``_`` is not a metric."""


def live_sequences(records, a, b) -> float:
    """Sequences between their first and their last token at the
    client, averaged over the window [a, b]: the sequences a decode
    step of that window carries."""
    total = 0.0
    for r in records:
        if r["first"] is None or r["last"] is None:
            continue
        total += max(min(r["last"], b) - max(r["first"], a), 0.0)
    return total / max(b - a, 1e-9)
