#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once through the entry points a user calls, at the
published widths of Gemma-2B (18 layers, d 2048, 8 query heads / 1 KV
head, head 256, MLP 16384, vocab 256,000; random weights from a seed):

  serve      ``python -m skypilot_tpu.recipes.serve_llm --model gemma-2b``
             with the recipe's own defaults, requests through the LB
             port: a short and a 1,000-token prompt, 64 new tokens each,
             four in flight at once, one SSE-streamed, one prompt
             repeated (a prefix hit).
  serve-ref  the engine's tokens against the plain reference
             (``model_api(cfg).decode`` and teacher-forced
             ``forward_with_cache`` logits), in a child of its own
             after the server has exited. Rule: wherever the
             reference's top-1/top-2 logit margin exceeds MARGIN, the
             engine's token is the reference's top-1; and ``decode``'s
             own greedy tokens leave the engine's only at a position
             under that margin. (In bf16 a near-tie's argmax depends on
             the prefill tiling, so identity is not asked below it.)
             A random tied-embedding model echoes its last prompt token
             whatever its cache holds, so the same child also drives the
             recipe's engine itself (``serve_llm.serve()``: paged pool,
             batched, the repeated prompt from the prefix cache) and
             holds every row of logits it samples from to the
             reference's within LOGIT_BOUND of the row's range — with a
             control, the same tokens over another request's cache,
             that has to show twice that.
  train      ``python -m skypilot_tpu.recipes.gemma_lora --model 2b`` at
             seq 2048: the Pallas flash kernels forward and backward,
             loss finite and falling, one checkpoint written and read
             back.

One process holds the chip at a time: this script never starts a JAX
backend, and runs each phase as a child only after the last has exited.
Any phase that fails, a child that dies, a warm-up that raises or a
deadline that passes makes it exit non-zero with the cause and the
child's last lines. On success the last line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the children's JAX reports it. Without a TPU it fails
and prints no such line.

``--chips 4`` runs only the sharded paths and what they are compared
with, each in one process that drives all four chips: gemma-7b served
at ``--tp 4`` (the ``examples/serve_gemma_sharded.yaml`` deployment cut
to one host) against the reference jitted over the same mesh, and
gemma-2b LoRA on the recipe's ``{"fsdp": -1}`` mesh against the same
first step on a one-device mesh; both with per-device bytes showing
that no chip holds the whole model.

``--tiny`` rehearses the same control flow at tiny size where there is
no chip (``JAX_PLATFORMS=cpu``, and for ``--chips 4`` also
``XLA_FLAGS=--xla_force_host_platform_device_count=4``). The phases run
and are checked; the verdict is "not ok" whatever it runs on, because
a tiny run says nothing about the published widths.
"""
from __future__ import annotations

import argparse
import atexit
import json
import math
import os
import pathlib
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = pathlib.Path(__file__).resolve().parent

# The contract is 1200 s, compilation included; stop short of it so the
# cause of an overrun is this script's message and not a kill.
TOTAL_SECONDS = 1140.0
READY_SECONDS = 600.0        # spawn -> /health ok (init + warm-up compile)
REQUEST_SECONDS = 300.0      # one /generate round trip
CHILD_SECONDS = 600.0        # a serve-ref or train child

NEW_TOKENS = 64
# Top-1/top-2 margin of the reference's float32 logits (computed from
# bf16 activations) above which the engine must agree with it.
MARGIN = 1.0
# The comparison must not be vacuous: at least this many positions,
# over all requests, have to clear the margin.
MIN_COMPARED = 16
# Engine logits against the reference's along the same tokens: the
# largest difference over the vocabulary in any row, as a share of that
# row's logit range (max - min). The two compute the same function in
# bf16 with different tilings (64-token paged chunks and one-token
# steps against one 1,024-token pass), so they differ by rounding; a
# crossed slot, a wrong block table or a broken prefix restore differs
# by the context. The control (the same tokens over another request's
# cache) has to show at least twice the bound, or the rule is blind.
LOGIT_BOUND = 0.03
# Sharded against one-device first-step loss, relative.
LOSS_RTOL = 1e-2
# Steps until "the loss fell" is not a coin toss. At the recipe's
# default learning rate the adapters (B starts at zero) move the loss of
# a full-width Gemma-2B by about 0.1 in 8 steps, 0.5 in 12-16 and 0.9
# in 24, against a batch-to-batch spread of 0.03-0.1 (CPU probe at seq
# 256, PR 22). A step takes well under a second on the chip.
TRAIN_STEPS = 24
TRAIN4_STEPS = 16

# Published vocabulary sizes (models/gemma.py): the in-vocab check.
VOCAB = {"gemma-2b": 256000, "gemma-7b": 256000, "gemma-tiny": 512}

_children: list = []


class Failed(Exception):
    """A phase failed; the message is the cause shown to the user."""


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ------------------------------------------------------------- children
class Child:
    """One phase's process, in a session of its own, logging to files."""

    def __init__(self, name: str, argv: list, out: pathlib.Path,
                 env: dict = None):
        self.name = name
        self.stdout_path = out / f"{name}.stdout"
        self.stderr_path = out / f"{name}.stderr"
        self.t0 = time.monotonic()
        with open(self.stdout_path, "wb") as so, \
                open(self.stderr_path, "wb") as se:
            self.proc = subprocess.Popen(
                argv, stdout=so, stderr=se, cwd=str(REPO),
                env=env or child_env(), start_new_session=True)
        _children.append(self)

    def stdout(self) -> str:
        return self.stdout_path.read_text(errors="replace")

    def tail(self, lines: int = 15) -> str:
        err = self.stderr_path.read_text(errors="replace").splitlines()
        out = self.stdout().splitlines()
        return "\n".join(
            ["  [stderr] " + ln[:400] for ln in err[-lines:]] +
            ["  [stdout] " + ln[:400] for ln in out[-5:]])

    def died(self) -> str:
        return (f"the {self.name} child exited with code "
                f"{self.proc.returncode}:\n{self.tail()}")

    def wait(self, seconds: float) -> int:
        try:
            return self.proc.wait(timeout=max(seconds, 0.0))
        except subprocess.TimeoutExpired:
            self.stop()
            raise Failed(f"the {self.name} child passed its deadline "
                         f"({seconds:.0f} s):\n{self.tail()}")

    def stop(self) -> None:
        """SIGTERM the child's whole session, then SIGKILL what is left."""
        if self.proc.poll() is None:
            for sig, grace in ((signal.SIGTERM, 10.0),
                               (signal.SIGKILL, 5.0)):
                try:
                    os.killpg(self.proc.pid, sig)
                except (ProcessLookupError, PermissionError):
                    break
                try:
                    self.proc.wait(timeout=grace)
                    break
                except subprocess.TimeoutExpired:
                    continue
        # Followers or helpers the child left in its session.
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass


def child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH")
                       else []))
    env["PYTHONUNBUFFERED"] = "1"
    env.setdefault("TPU_LOG_DIR", "disabled")
    return env


def stop_all() -> None:
    for child in _children:
        child.stop()


def _on_signal(signum, frame):
    del frame
    stop_all()
    sys.exit(128 + signum)


def last_json(text: str, what: str) -> dict:
    for line in reversed(text.strip().splitlines()):
        if line.startswith("{"):
            try:
                return json.loads(line)
            except ValueError:
                break
    raise Failed(f"{what} printed no JSON result line")


def run_to_end(name: str, argv: list, out: pathlib.Path,
               deadline: float) -> tuple:
    """Run a child to its end; (its last JSON line, wall seconds)."""
    child = Child(name, argv, out)
    rc = child.wait(min(CHILD_SECONDS, deadline - time.monotonic()))
    if rc != 0:
        raise Failed(child.died())
    return (last_json(child.stdout(), f"the {name} child"),
            time.monotonic() - child.t0)


# ----------------------------------------------------------------- http
def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(url: str, payload: dict = None, timeout: float = 10.0):
    """(status, body bytes); connection errors raise OSError."""
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def generate(port: int, req: dict, deadline: float) -> list:
    """One /generate through the LB; SSE when req['stream']."""
    body = {"prompt": req["prompt"], "max_tokens": NEW_TOKENS,
            "temperature": 0.0, "stream": bool(req.get("stream"))}
    status, raw = http(
        f"http://127.0.0.1:{port}/generate", body,
        timeout=max(min(REQUEST_SECONDS, deadline - time.monotonic()),
                    1.0))
    if status != 200:
        raise Failed(f"request {req['name']}: HTTP {status}: "
                     f"{raw[:300]!r}")
    if not body["stream"]:
        return json.loads(raw)["tokens"]
    tokens, done = [], False
    for line in raw.decode().splitlines():
        if line == "data: [DONE]":
            done = True
        elif line.startswith("data: "):
            tokens.append(json.loads(line[len("data: "):])["token"])
    if not done:
        raise Failed(f"request {req['name']}: the SSE stream ended "
                     f"without [DONE] after {len(tokens)} tokens")
    return tokens


def metric(text: str, name: str) -> float:
    """Sum of a metric family's samples in a Prometheus exposition."""
    total, seen = 0.0, False
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):][:1] in (" ", "{"):
            total += float(line.rsplit(" ", 1)[1])
            seen = True
    if not seen:
        raise Failed(f"/metrics has no {name}")
    return total


# ---------------------------------------------------------------- serve
def make_requests(vocab: int, seed: int) -> list:
    rng = random.Random(seed)

    def prompt(n):
        return [rng.randrange(1, vocab) for _ in range(n)]

    long_prompt = prompt(1000)
    return [
        {"name": "short", "prompt": prompt(12)},
        {"name": "long", "prompt": long_prompt},
        {"name": "streamed", "prompt": prompt(200), "stream": True},
        {"name": "mid", "prompt": prompt(333)},
        # Sent after the others have finished: the prefix hit.
        {"name": "long-again", "prompt": long_prompt},
    ]


def wait_ready(child: Child, port: int, deadline: float,
               need_tpu: bool) -> dict:
    """Poll until /health is ok; the server's own device statement."""
    limit = min(time.monotonic() + READY_SECONDS, deadline)
    banner_seen = False
    while time.monotonic() < limit:
        if child.proc.poll() is not None:
            raise Failed(child.died())
        if not banner_seen and " platform=" in child.stdout():
            banner_seen = True
            platform = child.stdout().split(" platform=")[1].split()[0]
            if need_tpu and platform != "tpu":
                raise Failed(f"no TPU: the server reports "
                             f"platform={platform}")
        try:
            status, raw = http(f"http://127.0.0.1:{port}/health",
                               timeout=5.0)
        except OSError:
            time.sleep(0.5)
            continue
        if status == 200:
            return json.loads(raw)
        if status == 500:
            raise Failed(f"the server's warm-up failed: "
                         f"{json.loads(raw).get('error')}\n{child.tail()}")
        time.sleep(0.5)
    raise Failed(f"/health was not ok {READY_SECONDS:.0f} s after the "
                 f"server started:\n{child.tail()}")


def serve_phase(model: str, tp: int, out: pathlib.Path, deadline: float,
                need_tpu: bool, seed: int) -> dict:
    port, lb_port = free_port(), free_port()
    argv = [sys.executable, "-m", "skypilot_tpu.recipes.serve_llm",
            "--model", model, "--seed", str(seed),
            "--port", str(port), "--lb-port", str(lb_port)]
    if tp > 1:
        argv += ["--tp", str(tp), "--replica-hosts", "1"]
    say(f"serve: {' '.join(argv[1:])}")
    child = Child("serve", argv, out)
    try:
        health = wait_ready(child, port, deadline, need_tpu)
        ready_s = time.monotonic() - child.t0
        device = health["device"]
        say(f"serve: ready after {ready_s:.1f} s (parameter init + "
            f"warm-up compile) on {device}")

        requests = make_requests(VOCAB[model], seed)
        results, errors = {}, []

        def run(req):
            try:
                t0 = time.monotonic()
                results[req["name"]] = generate(lb_port, req, deadline)
                req["seconds"] = time.monotonic() - t0
            except Exception as e:  # noqa: BLE001 — thread boundary:
                # collected and raised by the phase below.
                errors.append(f"{req['name']}: {type(e).__name__}: {e}")

        t0 = time.monotonic()
        first = [threading.Thread(target=run, args=(r,))
                 for r in requests[:-1]]
        for t in first:
            t.start()
        for t in first:
            t.join()
        if not errors:
            run(requests[-1])
        if child.proc.poll() is not None:
            raise Failed(child.died())
        if errors:
            raise Failed("requests failed: " + "; ".join(errors) +
                         "\n" + child.tail())
        for req in requests:
            toks = results[req["name"]]
            bad = [t for t in toks
                   if not (isinstance(t, int) and 0 <= t < VOCAB[model])]
            if len(toks) != NEW_TOKENS or bad:
                raise Failed(
                    f"request {req['name']}: asked {NEW_TOKENS} tokens "
                    f"in [0, {VOCAB[model]}), got {len(toks)} with "
                    f"{len(bad)} out of vocabulary: {toks[:8]}")
            say(f"serve: {req['name']:<10} prompt {len(req['prompt']):>4}"
                f" -> {len(toks)} tokens in {req['seconds']:.2f} s"
                f"{' (SSE)' if req.get('stream') else ''}: {toks[:4]}...")
        say(f"serve: {len(requests)} requests through the LB in "
            f"{time.monotonic() - t0:.1f} s, {len(first)} in flight "
            f"at once")

        _, raw = http(f"http://127.0.0.1:{port}/metrics")
        text = raw.decode()
        pool = metric(text, "stpu_engine_kv_pool_blocks_total")
        hits = metric(text, "stpu_engine_prefix_cache_hits_total")
        saved = metric(text, "stpu_engine_prefill_tokens_saved_total")
        if pool <= 0:
            raise Failed("/metrics shows no paged KV pool "
                         "(stpu_engine_kv_pool_blocks_total is 0)")
        if hits < 1:
            raise Failed("/metrics shows no prefix hit for the repeated "
                         "prompt")
        say(f"serve: paged pool of {pool:.0f} blocks, {hits:.0f} prefix "
            f"hit(s), {saved:.0f} prefill tokens saved")

        _, raw = http(f"http://127.0.0.1:{port}/perf")
        memory = json.loads(raw)["device"]["memory"]
        for row in memory:
            say(f"serve: device {row['id']}: parameters "
                f"{gib(row['param_bytes'])}, KV {gib(row['kv_bytes'])}, "
                f"in use {gib(row['bytes_in_use'])}, allocator peak "
                f"{gib(row['peak_bytes_in_use'])}")
        if tp > 1:
            check_spread("parameters", [r["param_bytes"] for r in memory],
                         tp)
            check_spread("KV pool", [r["kv_bytes"] for r in memory], tp)
            total = sum(r["param_bytes"] for r in memory)
            peaks = [r["peak_bytes_in_use"] for r in memory]
            if need_tpu and max(peaks) > 0.5 * total:
                raise Failed(f"a device's peak ({gib(max(peaks))}) is "
                             f"over half the model ({gib(total)}): the "
                             f"model was not created sharded")
    finally:
        child.stop()
    path = out / "serve_requests.json"
    path.write_text(json.dumps(
        [{"name": r["name"], "prompt": r["prompt"],
          "tokens": results[r["name"]]} for r in requests]))
    return {"device": device, "requests_file": str(path)}


def gib(n) -> str:
    if n is None:
        return "n/a"
    return (f"{n / 2**30:.2f} GiB" if n >= 2**28
            else f"{n / 2**20:.2f} MiB")


def check_spread(what: str, per_device: list, n: int) -> None:
    """Each of ``n`` devices holds about 1/n of ``what``."""
    total = sum(per_device)
    if len(per_device) != n or total <= 0 or any(
            abs(b / total - 1.0 / n) > 0.25 / n for b in per_device):
        raise Failed(f"{what} are not spread over {n} devices "
                     f"(about 1/{n} each): {per_device}")
    say(f"{what}: {[gib(b) for b in per_device]} of {gib(total)}, "
        f"1/{n} each")


# ---------------------------------------------------------------- train
def check_train(m: dict, need_tpu: bool) -> None:
    first, final = m["first_loss"], m["final_loss"]
    if not (first is not None and final is not None and
            math.isfinite(first) and math.isfinite(final)):
        raise Failed(f"train: loss not finite: {first} -> {final}")
    if not final < first:
        raise Failed(f"train: loss did not fall: {first} -> {final}")
    traces = m["attention_traces"]
    if need_tpu and (traces["kernel"] < 1 or traces["reference"] or
                     traces["kernel_replicated"]):
        raise Failed(f"train: the step was not traced into the flash "
                     f"kernel alone, split over the mesh: {traces}")
    say(f"train: loss {first:.4f} -> {final:.4f} over {m['steps']} "
        f"steps, {m['tokens_per_second']} tok/s by the recipe's own "
        f"clock, first loss {m['start_to_first_loss_seconds']} s after "
        f"start; attention traced as {traces}")
    say(f"train: base parameters per device "
        f"{[gib(b) for b in m['base_bytes_per_device']]}, allocator "
        f"peak per device "
        f"{[gib(b) for b in m['peak_bytes_per_device']]} (a floor: it "
        f"does not see the compiled step's temporaries)")


def train_phase(out: pathlib.Path, deadline: float, need_tpu: bool,
                tiny: bool, seed: int) -> dict:
    from skypilot_tpu.train import checkpoint  # numpy only, no JAX
    ckpt_dir = out / "ckpt"
    # Batch 2: the compiled step needs 11.4 GiB of the chip's 15.75 at
    # batch 2 and 14.6 at batch 3 (memory_analysis(), rehearsal 3).
    argv = [sys.executable, "-m", "skypilot_tpu.recipes.gemma_lora",
            "--model", "tiny" if tiny else "2b", "--seed", str(seed),
            "--steps", str(TRAIN_STEPS), "--batch-size", "2",
            "--seq-len", "128" if tiny else "2048",
            "--checkpoint-dir", str(ckpt_dir),
            "--ckpt-every", str(TRAIN_STEPS)]
    say(f"train: {' '.join(argv[1:])}")
    m, wall = run_to_end("train", argv, out, deadline)
    check_train(m, need_tpu)
    restored = checkpoint.restore_latest(ckpt_dir)
    if restored is None or restored.step != TRAIN_STEPS:
        raise Failed(f"train: no readable checkpoint of step {TRAIN_STEPS} "
                     f"in {ckpt_dir}")
    nbytes = sum(a.nbytes for a in restored.tree.values()
                 if a is not None)
    say(f"train: checkpoint of step {restored.step} read back, "
        f"{len(restored.tree)} leaves, {nbytes} bytes, sha256 "
        f"{restored.manifest_sha256[:12]}; child took {wall:.1f} s")
    return {"device": m["device"]}


# ----------------------------------------------- children that touch JAX
def engine_logits(cfg, params, mesh, rules, topology, reqs) -> dict:
    """Drive the recipe's own engine in this process — ``serve()``
    with its defaults, so the server's geometry: paged pool, prefix
    trie — and keep every row of logits it samples from.

    The engine hands out tokens only. ``decode_engine._sample`` is the
    one place where both the first token (inside the jitted prefill
    chunk) and every decode step (inside the jitted step) turn logits
    into a token, and it is called with the request's seed and the
    token's absolute position; so a tap there, keyed by distinct
    seeds, names each row whatever slot the request landed in. Every
    chunk samples (one program); one that does not end its prompt
    samples at a prompt position and the engine drops the token, so
    the tap leaves those rows out."""
    from unittest import mock

    import jax
    import numpy as np

    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import decode_engine

    seeds = {1001 + i: r for i, r in enumerate(reqs)}
    rows: dict = {}

    def keep(logits, seed, position):
        for row, sd, pos in zip(np.asarray(logits), np.asarray(seed),
                                np.asarray(position)):
            if int(sd) in seeds and \
                    int(pos) >= len(seeds[int(sd)]["prompt"]):
                rows[(int(sd), int(pos))] = np.array(row, np.float32)

    sample = decode_engine._sample

    def tapped(logits, seed, position, temps):
        jax.debug.callback(keep, logits, seed, position)
        return sample(logits, seed, position, temps)

    ready = threading.Event()
    with mock.patch.object(decode_engine, "_sample", tapped):
        httpd = serve_llm.serve(cfg, params, 0, ready_event=ready,
                                topology=topology, mesh=mesh, rules=rules)
        try:
            ctx = httpd.RequestHandlerClass.server_ctx
            limit = time.monotonic() + READY_SECONDS
            while not ready.wait(0.5):
                if ctx["warmup_error"] or time.monotonic() > limit:
                    raise RuntimeError(
                        f"the in-process engine did not warm up: "
                        f"{ctx['warmup_error'] or 'deadline'}")
            engine = httpd.engine
            handles = [engine.submit(r["prompt"], NEW_TOKENS, seed=sd)
                       for sd, r in list(seeds.items())[:-1]]
            tokens = [h.result(timeout=REQUEST_SECONDS) for h in handles]
            # The repeated prompt, once the first copy has finished.
            again = engine.submit(reqs[-1]["prompt"], NEW_TOKENS,
                                  seed=max(seeds))
            tokens.append(again.result(timeout=REQUEST_SECONDS))
            jax.effects_barrier()
            paged = bool(engine.kv_config()["paged"])
        finally:
            httpd.engine.shutdown()
            httpd.server_close()
    vocab = len(next(iter(rows.values())))
    logits = np.zeros((len(reqs), NEW_TOKENS, vocab), np.float32)
    for i, (sd, r) in enumerate(seeds.items()):
        for j in range(NEW_TOKENS):
            logits[i, j] = rows.pop((sd, len(r["prompt"]) + j))
    if rows:
        raise RuntimeError(f"the engine sampled at positions nobody "
                           f"asked for: {sorted(rows)[:8]}")
    return {"tokens": np.asarray(tokens, np.int32), "logits": logits,
            "paged": paged,
            "prefix_cached_tokens": again.cached_prompt_tokens}


def child_serve_ref(args) -> int:
    """The plain reference for the serve phase's requests, on the same
    parameters (same seed, same sharding when ``--tp`` > 1): the token
    rule for what came back over HTTP, and the logits rule for the
    engine driven in this process."""
    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from skypilot_tpu.models import model_api
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import gang_replica
    from skypilot_tpu.utils import compile_cache
    compile_cache.enable()

    reqs = json.loads(pathlib.Path(args.requests_file).read_text())
    cfg = serve_llm.model_config(args.model)
    api = model_api(cfg)
    topology = gang_replica.ReplicaTopology(
        hosts=1, ici_axes={"tp": args.tp} if args.tp > 1 else {})
    mesh, rules = gang_replica.build_mesh(topology)
    params = serve_llm.init_params(cfg, args.seed, mesh, rules)

    b = len(reqs)
    s_pad = serve_llm.MAX_PROMPT_TOKENS
    max_seq = serve_llm.MAX_PROMPT_TOKENS + serve_llm.MAX_GEN_TOKENS
    prompts = np.zeros((b, s_pad), np.int32)
    for i, r in enumerate(reqs):
        prompts[i, :len(r["prompt"])] = r["prompt"]
    true_len = np.asarray([len(r["prompt"]) for r in reqs], np.int32)
    served = np.asarray([r["tokens"] for r in reqs], np.int32)

    @jax.jit
    def reference(params, prompts, true_len, tokens):
        # 1. The reference's own greedy continuation.
        own = api.decode(cfg, params, prompts, true_len, NEW_TOKENS,
                         max_seq)
        # 2. Its logits along the ENGINE's tokens (teacher-forced): the
        # prompt, then the engine's continuation as one more chunk.
        cache = api.init_cache(cfg, b, max_seq)
        first, cache = api.forward_with_cache(
            cfg, params, prompts, cache, jnp.int32(0),
            valid_len=true_len, logits_at=true_len - 1)
        rest, _ = api.forward_with_cache(
            cfg, params, tokens, cache, true_len,
            valid_len=true_len + NEW_TOKENS)
        # 3. The control: the same continuation over the NEXT request's
        # prompt cache and positions — what a crossed slot or a wrong
        # block table would compute. Rows 1.. only: they feed the same
        # tokens and differ by the cache alone (row 0 would be another
        # prompt's last token, which any rule tells apart).
        crossed_len = jnp.roll(true_len, -1)
        crossed, _ = api.forward_with_cache(
            cfg, params, tokens,
            jax.tree.map(lambda a: jnp.roll(a, -1, axis=1), cache),
            crossed_len, valid_len=crossed_len + NEW_TOKENS)
        return (own, jnp.concatenate([first, rest[:, :-1]], axis=1),
                crossed[:, :-1])

    def distance(a, ref):
        """Per row: the largest logit difference over the vocabulary,
        relative to the reference row's range (max - min)."""
        return (np.abs(a - ref).max(-1) /
                (ref.max(-1) - ref.min(-1)))

    # ---- tokens: what the server returned over HTTP.
    own, logits, crossed = jax.device_get(
        reference(params, prompts, true_len, served))
    top2 = np.partition(logits, -2, axis=-1)[..., -2:]
    top1, margin = logits.argmax(-1), top2[..., 1] - top2[..., 0]
    gated = margin > MARGIN
    wrong = gated & (top1 != served)
    diverged_over_margin = []
    for i, r in enumerate(reqs):
        differs = np.nonzero(own[i] != served[i])[0]
        if differs.size and margin[i, differs[0]] > MARGIN:
            diverged_over_margin.append(
                {"request": r["name"], "position": int(differs[0]),
                 "margin": float(margin[i, differs[0]])})

    # ---- logits: the engine driven here, on its own tokens.
    eng = engine_logits(cfg, params, mesh, rules, topology, reqs)
    same_tokens = bool((eng["tokens"] == served).all())
    if not same_tokens:
        _, logits, crossed = jax.device_get(
            reference(params, prompts, true_len, eng["tokens"]))
    error = distance(eng["logits"], logits)
    control = distance(eng["logits"][:, 1:], crossed)
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    failures = [what for failed, what in [
        (wrong.any(), "a served token is not the reference's top-1 "
                      "at a position over the margin"),
        (diverged_over_margin, "decode() leaves the served tokens at "
                               "a position over the margin"),
        (gated.sum() < MIN_COMPARED,
         f"fewer than {MIN_COMPARED} positions clear the margin"),
        (not eng["paged"], "the engine driven here is not paged"),
        (eng["prefix_cached_tokens"] <= 0,
         "the repeated prompt was not served from the prefix cache"),
        (error.max() > LOGIT_BOUND,
         f"the engine's logits leave the reference's by more than "
         f"{LOGIT_BOUND:g} of the logit range"),
        (np.median(control, axis=1).min() < 2 * LOGIT_BOUND,
         f"the control is blind: over another request's cache half a "
         f"request's rows stay within {2 * LOGIT_BOUND:g} of the "
         f"logit range"),
    ] if failed]
    result = {
        "ok": not failures,
        "failures": failures,
        "positions": int(served.size),
        "compared": int(gated.sum()),
        "agree_where_compared": int((gated & (top1 == served)).sum()),
        "agree_anywhere": int((top1 == served).sum()),
        "decode_identical_requests": int(
            (own == served).all(axis=1).sum()),
        "margin_min": float(margin.min()),
        "margin_median": float(np.median(margin)),
        "wrong": [{"request": reqs[i]["name"], "position": int(j),
                   "margin": float(margin[i, j]),
                   "engine": int(served[i, j]),
                   "reference": int(top1[i, j])}
                  for i, j in zip(*np.nonzero(wrong))][:8],
        "decode_diverged_over_margin": diverged_over_margin,
        "engine_paged": eng["paged"],
        "engine_prefix_cached_tokens": eng["prefix_cached_tokens"],
        "engine_tokens_as_served": same_tokens,
        "logit_rows": int(error.size),
        "logit_range_median": float(np.median(
            logits.max(-1) - logits.min(-1))),
        "logit_error_max": float(error.max()),
        "logit_error_median": float(np.median(error)),
        "logit_error_max_per_request": [float(e)
                                        for e in error.max(axis=1)],
        "control_median_per_request": [
            float(c) for c in np.median(control, axis=1)],
        "control_min": float(control.min()),
        "device": mesh_lib.device_info(),
        "peak_bytes_per_device": [s.get("peak_bytes_in_use")
                                  for s in stats],
        "seconds": round(time.monotonic() - t0, 1),
    }
    print(json.dumps(result), flush=True)
    return 0


def child_train4(args) -> int:
    """gemma_lora on the recipe's own ``{"fsdp": -1}`` mesh over every
    device, then the same first step on a one-device mesh in this same
    process: the recipe builds its mesh from ``jax.devices()``, so the
    twin runs with ``make_mesh`` held to the first device."""
    from unittest import mock

    import jax

    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.recipes import gemma_lora

    # Batch 4 x seq 1024: the global batch must divide by four AND fit
    # the one-device twin; 4 x 2048 needs more than one chip's 15.75 GiB
    # (memory_analysis(), rehearsal 3), 4 x 1024 needs 11.1.
    common = ["--model", "tiny" if args.tiny else "2b",
              "--seed", str(args.seed), "--batch-size", "4",
              "--seq-len", "128" if args.tiny else "1024"]
    sharded = gemma_lora.main(common + ["--steps", str(TRAIN4_STEPS)])
    make_mesh = mesh_lib.make_mesh
    with mock.patch.object(
            mesh_lib, "make_mesh",
            lambda axes, devices=None: make_mesh(
                axes, devices=jax.devices()[:1])):
        single = gemma_lora.main(common + ["--steps", "1"])
    print(json.dumps({"sharded": sharded, "single": single}), flush=True)
    return 0


# ----------------------------------------------------------------- main
def ref_phase(model: str, tp: int, serve: dict, out: pathlib.Path,
              deadline: float, seed: int) -> dict:
    argv = [sys.executable, str(REPO / "chip_smoke.py"),
            "--child", "serve-ref", "--model", model, "--tp", str(tp),
            "--seed", str(seed), "--requests-file", serve["requests_file"]]
    r, _ = run_to_end("serve-ref", argv, out, deadline)
    say(f"serve-ref: {r['compared']} of {r['positions']} positions "
        f"clear the margin of {MARGIN} (minimum {r['margin_min']:.3f}, "
        f"median {r['margin_median']:.3f}); the engine agrees with the "
        f"reference at {r['agree_where_compared']} of them and at "
        f"{r['agree_anywhere']} of all; decode() is token-identical "
        f"for {r['decode_identical_requests']} requests; allocator "
        f"peak {[gib(p) for p in r['peak_bytes_per_device']]}; "
        f"{r['seconds']} s")
    say(f"serve-ref: engine driven in the child (paged "
        f"{r['engine_paged']}, {r['engine_prefix_cached_tokens']} "
        f"prompt tokens of the repeated request from the prefix cache, "
        f"tokens as served {r['engine_tokens_as_served']}): "
        f"{r['logit_rows']} rows of logits leave the reference's by at "
        f"most {r['logit_error_max']:.5f} of the row's range (median "
        f"{r['logit_error_median']:.5f}, median range "
        f"{r['logit_range_median']:.2f} logits; bound {LOGIT_BOUND:g}); "
        f"the control — the same tokens over another request's cache "
        f"— leaves it by {min(r['control_median_per_request']):.3f} at "
        f"the median row of the request it shows least in (its least "
        f"row {r['control_min']:.3f}; it has to show "
        f"{2 * LOGIT_BOUND:g})")
    if not r["ok"]:
        raise Failed(f"serve-ref: the engine left the reference: "
                     f"{'; '.join(r['failures'])}. "
                     f"wrong={r['wrong']} "
                     f"decode={r['decode_diverged_over_margin']} "
                     f"compared={r['compared']} "
                     f"error per request={r['logit_error_max_per_request']} "
                     f"control per request="
                     f"{r['control_median_per_request']}")
    return {"device": r["device"]}


def train4_phase(out: pathlib.Path, deadline: float, need_tpu: bool,
                 tiny: bool, seed: int) -> dict:
    argv = [sys.executable, str(REPO / "chip_smoke.py"),
            "--child", "train4", "--seed", str(seed)]
    if tiny:
        argv.append("--tiny")
    say("train: gemma_lora on the {'fsdp': -1} mesh, then its first "
        "step on one device")
    r, wall = run_to_end("train4", argv, out, deadline)
    sharded, single = r["sharded"], r["single"]
    check_train(sharded, need_tpu)
    check_spread("base parameters", sharded["base_bytes_per_device"], 4)
    a, b = sharded["first_loss"], single["first_loss"]
    if not abs(a - b) <= LOSS_RTOL * abs(b):
        raise Failed(f"train: first-step loss on four devices {a} and "
                     f"on one {b} differ by more than {LOSS_RTOL:g} "
                     f"relative")
    say(f"train: first-step loss {a:.5f} on four devices, {b:.5f} on "
        f"one (tolerance {LOSS_RTOL:g} relative); child took "
        f"{wall:.1f} s")
    return {"device": sharded["device"]}


def verdict_refused(tiny: bool, device: dict) -> str:
    """Why phases that all passed still earn no "ok" line ('' = they
    do): the verdict belongs to a full-width run on a TPU."""
    if tiny:
        return (f"a --tiny rehearsal on {device} says nothing about "
                f"the published widths")
    if device["platform"] != "tpu":
        return f"the phases ran on {device}, not on a TPU"
    return ""


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the sharded serve and train paths and "
                        "what they are compared with")
    p.add_argument("--tiny", action="store_true",
                   help="rehearse the control flow at tiny size (never "
                        "ok, whatever it runs on)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--out", type=pathlib.Path,
                   default=REPO / "chip_smoke_out",
                   help="logs, request file and checkpoints")
    p.add_argument("--child", choices=("serve-ref", "train4"),
                   help=argparse.SUPPRESS)
    p.add_argument("--model", help=argparse.SUPPRESS)
    p.add_argument("--tp", type=int, default=1, help=argparse.SUPPRESS)
    p.add_argument("--requests-file", help=argparse.SUPPRESS)
    args = p.parse_args()
    if args.child == "serve-ref":
        return child_serve_ref(args)
    if args.child == "train4":
        return child_train4(args)

    from skypilot_tpu.utils import compile_cache  # no JAX at import
    atexit.register(stop_all)
    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    t0 = time.monotonic()
    deadline = t0 + TOTAL_SECONDS
    out = args.out.resolve()
    out.mkdir(parents=True, exist_ok=True)
    cache = pathlib.Path(compile_cache.cache_dir() or os.devnull)
    entries = len(list(cache.iterdir())) if cache.is_dir() else 0
    say(f"compile cache {cache} holds {entries} entries "
        f"({'warm' if entries else 'cold'} start)")
    need_tpu = not args.tiny
    if args.chips == 1:
        model, tp = ("gemma-tiny" if args.tiny else "gemma-2b"), 1
    else:
        model, tp = ("gemma-tiny" if args.tiny else "gemma-7b"), 4
    try:
        phases = [serve_phase(model, tp, out, deadline, need_tpu,
                              args.seed)]
        phases.append(ref_phase(model, tp, phases[0], out, deadline,
                                args.seed))
        if args.chips == 1:
            phases.append(train_phase(out, deadline, need_tpu,
                                      args.tiny, args.seed))
        else:
            phases.append(train4_phase(out, deadline, need_tpu,
                                       args.tiny, args.seed))
        devices = [ph["device"] for ph in phases]
        if any(d != devices[0] for d in devices):
            raise Failed(f"the phases ran on different devices: "
                         f"{devices}")
        if args.chips == 4 and devices[0]["count"] != 4:
            raise Failed(f"--chips 4 ran on {devices[0]['count']} "
                         f"devices")
    except Failed as e:
        print(f"chip_smoke: FAILED after {time.monotonic() - t0:.0f} s: "
              f"{e}", file=sys.stderr, flush=True)
        return 1
    finally:
        stop_all()
    say(f"all phases passed in {time.monotonic() - t0:.0f} s")
    refused = verdict_refused(args.tiny, devices[0])
    if refused:
        print(f"chip_smoke: not ok: {refused}", file=sys.stderr,
              flush=True)
        return 1
    print(json.dumps({"ok": True, "device": devices[0]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
