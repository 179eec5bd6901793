"""Traffic for a serving cell: the schedule builder and the client.

A corrected copy of ``skypilot_tpu/benchmark/loadgen.py`` (seeded
schedule, open-loop HTTP/SSE driver, dispatch lag). What differs, and why:

* A latency is timed from the instant the request was DUE, not from the
  instant it was sent: a stalled generator or server then shows in the
  latency of the requests it delayed.
* Lengths are log-normal (heavy-tailed), not uniform, and every seed gets
  the SAME multiset of prompt lengths, output lengths and inter-arrival
  gaps: the distribution's evenly spaced quantiles. A mix that names an
  ``order_seed`` also fixes their ORDER, and ``--seed`` then changes the
  token ids and the weights alone. (Measured, PERF.md, PR 24: near its
  knee this engine's tails and its tokens a second follow the order of
  the long prompts — six orders of the same requests spread them by 59 %
  and 17 %, two runs of one order by under 2 % — so an order per seed
  measures the draw, not the program.) Without ``order_seed`` the order
  is the seed's.
* One thread drives every connection through ``selectors`` (the original
  starts a thread per request); a second thread scrapes ``/metrics``
  when asked. Nothing of ``events``, ``jsonl_log`` or
  ``fault_injection`` is kept; of ``promtext`` only a text parser.

A traffic mix is a JSON file of parameters (``benchmarks/traffic/``)::

    {"loop": "open" | "closed",
     "arrival": "poisson",               # open loop
     "clients": 64,                      # closed loop
     "lead_in_s": 5,                     # same traffic before the window
     "prompt_tokens": {"dist": "lognormal", "median": 256, "sigma": 0.9,
                       "min": 16, "max": 1024},
     "output_tokens": {...},
     "shared_prefix": {"count": 4, "tokens": 128, "min_own_tokens": 8},
     "warm_prefixes": true,              # see prefix_warmers()
     "order_seed": 24,                   # fixes the order for every seed
     "temperature": 0.0}

The rate of an open loop is the cell's, not the mix's.
"""
from __future__ import annotations

import dataclasses
import errno
import hashlib
import json
import math
import selectors
import socket
import statistics
import threading
import time
import urllib.request
from random import Random
from typing import Dict, List, Optional, Tuple


# ----------------------------------------------------------------- schedule
@dataclasses.dataclass
class Req:
    index: int
    due: Optional[float]        # seconds after the window opens (< 0:
    #                             lead-in); None in a closed loop
    prompt: Tuple[int, ...]
    max_tokens: int
    measured: bool = True


def quantile_lengths(spec: dict, n: int) -> List[int]:
    """``n`` lengths at the evenly spaced quantiles of ``spec``'s
    distribution, clipped to its min/max, in rising order."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    nd = statistics.NormalDist()
    vals = [spec["median"] * math.exp(
        spec["sigma"] * nd.inv_cdf((i + 0.5) / n)) for i in range(n)]
    return [min(max(int(round(v)), lo), hi) for v in vals]


def quantile_gaps(rate: float, n: int, span: float) -> List[float]:
    """``n`` exponential inter-arrival gaps at the evenly spaced
    quantiles, scaled to sum to ``span`` seconds."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
    scale = span / sum(gaps)
    return [g * scale for g in gaps]


def _prompt(mix: dict, seed: int, index: int, length: int, vocab: int,
            prefixes: List[List[int]], prefix_of: int) -> Tuple[int, ...]:
    rng = Random(f"{seed}/tokens/{index}")
    own = [rng.randrange(1, vocab) for _ in range(length)]
    share = mix.get("shared_prefix")
    if not share:
        return tuple(own)
    # The system prompt counts in the length; a request always ends in
    # at least ``min_own_tokens`` of its own, so a prompt shorter than
    # the system prompt carries a cut one.
    keep = max(min(int(share["tokens"]),
                   length - int(share.get("min_own_tokens", 1))), 0)
    return tuple(prefixes[prefix_of][:keep] + own[keep:])


def _shuffled(values: list, key: str) -> list:
    out = list(values)
    Random(key).shuffle(out)
    return out


def build_requests(mix: dict, *, n: int, seed: int, vocab: int,
                   tag: str) -> List[Req]:
    """``n`` requests without arrival times: lengths at the quantiles,
    paired and ordered by the seed, token ids from the seed."""
    order = mix.get("order_seed", seed)
    plens = _shuffled(quantile_lengths(mix["prompt_tokens"], n),
                      f"{order}/{tag}/prompt")
    olens = _shuffled(quantile_lengths(mix["output_tokens"], n),
                      f"{order}/{tag}/output")
    share = mix.get("shared_prefix")
    prefixes: List[List[int]] = []
    which = [0] * n
    if share:
        for p in range(int(share["count"])):
            rng = Random(f"{seed}/prefix/{p}")
            prefixes.append([rng.randrange(1, vocab)
                             for _ in range(int(share["tokens"]))])
        which = _shuffled([i % len(prefixes) for i in range(n)],
                          f"{order}/{tag}/which")
    return [Req(index=i, due=None,
                prompt=_prompt(mix, seed, f"{tag}{i}", plens[i], vocab,
                               prefixes, which[i]),
                max_tokens=olens[i]) for i in range(n)]


def build_schedule(mix: dict, *, rate: Optional[float], seconds: float,
                   seed: int, vocab: int) -> List[Req]:
    """The run's requests. Open loop: ``round(rate * lead_in_s)``
    unmeasured requests before the window and ``round(rate * seconds)``
    inside it, each with its due time. Closed loop: a pool of requests
    the clients draw from in order, without times."""
    lead = float(mix.get("lead_in_s", 0.0))
    if mix["loop"] == "closed":
        n = int(mix.get("pool", 1024))
        return build_requests(mix, n=n, seed=seed, vocab=vocab, tag="w")
    if mix.get("arrival", "poisson") != "poisson":
        raise ValueError(f"unknown arrival {mix['arrival']!r}")
    out: List[Req] = []
    for tag, span, start in (("l", lead, -lead), ("w", seconds, 0.0)):
        n = int(round(rate * span))
        if n <= 0:
            continue
        reqs = build_requests(mix, n=n, seed=seed, vocab=vocab, tag=tag)
        gaps = _shuffled(quantile_gaps(rate, n, span),
                         f"{mix.get('order_seed', seed)}/{tag}/gaps")
        t = start
        for r, g in zip(reqs, gaps):
            r.due = t
            r.measured = tag == "w"
            t += g
        out.extend(reqs)
    for i, r in enumerate(out):
        r.index = i
    return out


def prefix_warmers(mix: dict, *, seed: int, vocab: int) -> List[Req]:
    """One short request for each shared system prompt (the prompt, the
    least tokens of its own, the least output). The engine publishes a
    prompt's blocks to its prefix cache when the request ENDS, so a
    server that has just started answers its first ten seconds of
    traffic without a hit; a deployment's system prompts are hot. Sent
    and awaited before the lead-in, as set-up."""
    share = mix.get("shared_prefix")
    if not share or not mix.get("warm_prefixes"):
        return []
    own = int(share.get("min_own_tokens", 1))
    out = []
    for p in range(int(share["count"])):
        rng = Random(f"{seed}/prefix/{p}")
        prefix = [rng.randrange(1, vocab)
                  for _ in range(int(share["tokens"]))]
        rng = Random(f"{seed}/warm/{p}")
        out.append(Req(
            index=p, due=0.0, measured=False,
            prompt=tuple(prefix + [rng.randrange(1, vocab)
                                   for _ in range(own)]),
            max_tokens=int(mix["output_tokens"]["min"])))
    return out


def schedule_digest(schedule: List[Req]) -> str:
    """sha256 over the whole schedule: equal digests, identical traffic."""
    doc = [[r.index, repr(r.due), list(r.prompt), r.max_tokens,
            r.measured] for r in schedule]
    return hashlib.sha256(
        json.dumps(doc, separators=(",", ":")).encode()).hexdigest()


# ------------------------------------------------------------------- client
class _Conn:
    __slots__ = ("req", "sock", "out", "buf", "status", "tokens", "sent",
                 "due_abs", "first_at", "last_at", "done", "error",
                 "client")

    def __init__(self, req: Req, due_abs: float, client: int):
        self.req = req
        self.sock: Optional[socket.socket] = None
        self.out = b""
        self.buf = b""
        self.status = 0
        self.tokens: List[int] = []
        self.sent = 0.0
        self.due_abs = due_abs
        self.first_at: Optional[float] = None
        self.last_at: Optional[float] = None
        self.done = False
        self.error: Optional[str] = None
        self.client = client


def _request_bytes(req: Req, temperature: float, port: int) -> bytes:
    body = json.dumps({"prompt": list(req.prompt),
                       "max_tokens": req.max_tokens,
                       "temperature": temperature, "seed": req.index,
                       "stream": True}, separators=(",", ":")).encode()
    head = (f"POST /generate HTTP/1.1\r\nHost: 127.0.0.1:{port}\r\n"
            f"Content-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n")
    return head.encode() + body


class Driver:
    """Send a schedule at ``port`` from one thread and keep, for every
    request, when it was due, sent, first and last answered.

    ``window`` is (t0, t1) on ``time.monotonic()``: a token that arrives
    inside it counts into ``tokens_in_window``."""

    def __init__(self, port: int, schedule: List[Req], mix: dict, *,
                 t0: float, seconds: float, drain_s: float = 60.0):
        self.port = port
        self.schedule = schedule
        self.mix = mix
        self.t0 = t0
        self.t1 = t0 + seconds
        self.drain_s = drain_s
        self.temperature = float(mix.get("temperature", 0.0))
        self.sel = selectors.DefaultSelector()
        self.records: List[dict] = []
        self.tokens_in_window = 0
        self.open = 0

    # -- one connection -------------------------------------------------
    def _start(self, req: Req, due_abs: float, client: int = -1) -> None:
        c = _Conn(req, due_abs, client)
        c.out = _request_bytes(req, self.temperature, self.port)
        s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        s.setblocking(False)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        c.sock = s
        c.sent = time.monotonic()
        rc = s.connect_ex(("127.0.0.1", self.port))
        if rc not in (0, errno.EINPROGRESS):
            c.error = f"connect_{errno.errorcode.get(rc, rc)}"
            self._finish(c)
            return
        self.sel.register(s, selectors.EVENT_WRITE, c)
        self.open += 1

    def _finish(self, c: _Conn) -> None:
        if c.sock is not None:
            try:
                self.sel.unregister(c.sock)
                self.open -= 1
            except (KeyError, ValueError):
                pass
            c.sock.close()
            c.sock = None
        n = len(c.tokens)
        ok = c.done and c.error is None and c.status == 200
        if c.error is None and not ok:
            c.error = (f"http_{c.status}" if c.status != 200
                       else "truncated_stream")
        self.records.append({
            "index": c.req.index, "measured": c.req.measured,
            "prompt_tokens": len(c.req.prompt),
            "max_tokens": c.req.max_tokens, "tokens": c.tokens,
            "ok": ok, "error": c.error,
            "due": c.due_abs - self.t0, "sent": c.sent - self.t0,
            "dispatch_lag_s": c.sent - c.due_abs,
            "first": (c.first_at - self.t0
                      if c.first_at is not None else None),
            "last": (c.last_at - self.t0
                     if c.last_at is not None else None),
            "ttft_s": (c.first_at - c.due_abs
                       if c.first_at is not None else None),
            "tpot_s": ((c.last_at - c.first_at) / (n - 1)
                       if n > 1 else None),
            "client": c.client,
        })

    def _on_write(self, c: _Conn) -> None:
        err = c.sock.getsockopt(socket.SOL_SOCKET, socket.SO_ERROR)
        if err:
            c.error = f"connect_{errno.errorcode.get(err, err)}"
            self._finish(c)
            return
        try:
            n = c.sock.send(c.out)
        except (BlockingIOError, InterruptedError):
            return
        except OSError as e:
            c.error = type(e).__name__
            self._finish(c)
            return
        c.out = c.out[n:]
        if not c.out:
            self.sel.modify(c.sock, selectors.EVENT_READ, c)

    def _on_read(self, c: _Conn) -> None:
        try:
            chunk = c.sock.recv(65536)
        except (BlockingIOError, InterruptedError):
            return
        except OSError as e:
            c.error = type(e).__name__
            self._finish(c)
            return
        now = time.monotonic()
        if not chunk:
            self._finish(c)
            return
        c.buf += chunk
        if not c.status:
            end = c.buf.find(b"\r\n\r\n")
            if end < 0:
                return
            try:
                c.status = int(c.buf.split(b" ", 2)[1])
            except (IndexError, ValueError):
                c.status = -1
            c.buf = c.buf[end + 4:]
            if c.status != 200:
                self._finish(c)
                return
        # One SSE event per chunk of the chunked body; an event ends in
        # a blank line, and its ``data:`` line holds a token or [DONE].
        while True:
            end = c.buf.find(b"\n\n")
            if end < 0:
                break
            event, c.buf = c.buf[:end], c.buf[end + 2:]
            at = event.find(b"data: ")
            if at < 0 or b"event: " in event:
                continue
            payload = event[at + 6:].split(b"\n", 1)[0].strip()
            if payload == b"[DONE]":
                c.done = True
                self._finish(c)
                return
            try:
                tok = json.loads(payload)["token"]
            except (ValueError, KeyError, TypeError):
                continue
            c.tokens.append(int(tok))
            c.last_at = now
            if c.first_at is None:
                c.first_at = now
            if self.t0 <= now < self.t1:
                self.tokens_in_window += 1

    # -- the loops ------------------------------------------------------
    def _pump(self, timeout: float) -> List[_Conn]:
        finished_before = len(self.records)
        for key, mask in self.sel.select(max(timeout, 0.0)):
            c = key.data
            if c.sock is None:
                continue
            if mask & selectors.EVENT_WRITE:
                self._on_write(c)
            if c.sock is not None and mask & selectors.EVENT_READ:
                self._on_read(c)
        return self.records[finished_before:]

    def run(self) -> None:
        if self.mix["loop"] == "closed":
            self._run_closed()
        else:
            self._run_open()
        # Whatever is still open at the deadline failed.
        for key in list(self.sel.get_map().values()):
            key.data.error = "deadline"
            self._finish(key.data)
        self.sel.close()

    def _run_open(self) -> None:
        nxt = 0
        deadline = self.t1 + self.drain_s
        while True:
            now = time.monotonic()
            while (nxt < len(self.schedule)
                   and self.t0 + self.schedule[nxt].due <= now):
                r = self.schedule[nxt]
                self._start(r, self.t0 + r.due)
                nxt += 1
            if nxt >= len(self.schedule) and not self.open:
                return
            if now > deadline:
                return
            wait = 0.25
            if nxt < len(self.schedule):
                wait = min(wait,
                           self.t0 + self.schedule[nxt].due - now)
            self._pump(wait)

    def _run_closed(self) -> None:
        lead = float(self.mix.get("lead_in_s", 0.0))
        pool = iter(self.schedule)
        start = self.t0 - lead
        while time.monotonic() < start:
            time.sleep(min(0.05, max(start - time.monotonic(), 0.0)))
        for client in range(int(self.mix["clients"])):
            r = next(pool)
            r.due = time.monotonic() - self.t0
            self._start(r, time.monotonic(), client)
        deadline = self.t1 + self.drain_s
        while self.open and time.monotonic() < deadline:
            for rec in self._pump(0.25):
                now = time.monotonic()
                if now >= self.t1:
                    continue
                r = next(pool, None)
                if r is None:
                    raise RuntimeError(
                        "the closed loop ran out of requests: raise "
                        "the mix's `pool`")
                r.due = now - self.t0
                self._start(r, now, rec["client"])
        # A closed loop measures the requests that began in the window.
        for rec in self.records:
            rec["measured"] = 0.0 <= rec["due"] < self.t1 - self.t0


# ----------------------------------------------------------------- counters
def parse_metrics(text: str) -> Dict[Tuple[str, Tuple], float]:
    """Prometheus text format -> {(name, sorted label pairs): value}."""
    out: Dict[Tuple[str, Tuple], float] = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        try:
            head, value = line.rsplit(" ", 1)
            labels: Tuple = ()
            if "{" in head:
                name, rest = head.split("{", 1)
                pairs = []
                for part in rest.rstrip("}").split(","):
                    if "=" in part:
                        k, v = part.split("=", 1)
                        pairs.append((k.strip(), v.strip().strip('"')))
                labels = tuple(sorted(pairs))
            else:
                name = head
            out[(name.strip(), labels)] = float(value)
        except ValueError:
            continue
    return out


class Scraper(threading.Thread):
    """GET /metrics every ``interval`` seconds; keeps (t, parsed)."""

    def __init__(self, port: int, interval: float):
        super().__init__(daemon=True, name="bench-scraper")
        self.url = f"http://127.0.0.1:{port}/metrics"
        self.interval = interval
        self.samples: List[Tuple[float, dict]] = []
        self._halt = threading.Event()

    def scrape(self) -> Optional[dict]:
        try:
            with urllib.request.urlopen(self.url, timeout=5.0) as r:
                parsed = parse_metrics(r.read().decode())
        except OSError:
            return None
        self.samples.append((time.monotonic(), parsed))
        return parsed

    def run(self) -> None:
        while not self._halt.is_set():
            self.scrape()
            self._halt.wait(self.interval)

    def stop(self) -> None:
        self._halt.set()
        self.join(timeout=10.0)


def gauge_series(samples, name: str, t0: float, t1: float) -> List[float]:
    return [s[(name, ())] for t, s in samples
            if t0 <= t <= t1 and (name, ()) in s]


def histogram_delta(samples, name: str, t0: float, t1: float):
    """(upper bounds, cumulative counts) of histogram ``name`` between
    the first sample at or after t0 and the last at or before t1."""
    inside = [s for t, s in samples if t0 <= t <= t1]
    if len(inside) < 2:
        return [], []
    first, last = inside[0], inside[-1]
    rows = []
    for (n, labels), v in last.items():
        if n != name + "_bucket":
            continue
        le = dict(labels).get("le")
        if le is None or len(labels) != 1:
            continue
        bound = math.inf if le in ("+Inf", "inf") else float(le)
        rows.append((bound, v - first.get((n, labels), 0.0)))
    rows.sort()
    return [b for b, _ in rows], [c for _, c in rows]


def histogram_quantile(bounds: List[float], cumulative: List[float],
                       q: float) -> Optional[float]:
    """Linear interpolation inside the bucket that holds quantile q."""
    if not cumulative or cumulative[-1] <= 0:
        return None
    rank = q * cumulative[-1]
    lo_b, lo_c = 0.0, 0.0
    for b, c in zip(bounds, cumulative):
        if c >= rank:
            if math.isinf(b):
                return lo_b
            return lo_b + (b - lo_b) * ((rank - lo_c) / max(c - lo_c, 1e-12))
        lo_b, lo_c = b, c
    return lo_b


def percentile(values: List[float], q: float) -> Optional[float]:
    """Linear-interpolation percentile of raw samples."""
    if not values:
        return None
    vals = sorted(values)
    pos = q * (len(vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)
