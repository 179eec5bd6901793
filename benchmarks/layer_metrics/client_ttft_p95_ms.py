"""p95, over the requests due in the window, of the time from the
instant a request was DUE to its first streamed token at the client.

Not an end-to-end metric yet: at the 1.6-2 requests a second this engine
sustains, a 50 s window holds 80-100 requests, the p95 lies between the
fourth and fifth slowest, and one request changing places moves it by a
fifth (PERF.md, PR 24: interquartile spreads of 8-19 % between runs of
identical traffic). Reported beside the bounded metrics until the engine
serves enough requests a window for the tail to be judged."""
NAME, UNIT, BETTER = "client_ttft_p95_ms", "ms", "lower"
LAYER = "scheduler"
MOVES = "tpot_p95_ms"
SOURCE = "host_clock"
RUNNERS = ("serve",)


def compute(run):
    return (run.get("e2e") or {}).get("ttft_p95_ms")
