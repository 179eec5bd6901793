"""The serving cell's child: the one process that holds the chips.

Builds the configuration's dataclass, makes the weights on the device in
one jitted call from the seed (``serve_llm.init_params``), starts the
recipe's own server (``serve_llm.serve``: HTTP handler -> decode engine
-> paged pool -> model) and waits for its warm-up. The parent sends
traffic to the server's port like any client, and asks this process over
a second, small HTTP port for what only the holder of the chips can say:
the device, the memory peak, the reduction of a profiler trace, and the
comparison of served tokens with the plain reference.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from benchmarks import cells

# The served token must be the reference's top-1 wherever the
# reference's top-1/top-2 margin exceeds MARGIN logits. Reason: with
# seeded random weights and an untied head the logits of a position are
# close to Gaussian over the vocabulary (standard deviation about 1), so
# the top two are often within a few tenths; the engine computes in bf16
# through a paged cache, the reference in float32, and a near-tie's
# argmax flips on that rounding. Measured on the chip (PERF.md, Findings,
# PR 24): the program's bf16 forward pass leaves the float32 reference's
# logits by at most 0.077 (Mistral-7B, 16 layers, 462 rows), and in 26
# runs no served token of the dense model disagreed above a reference
# margin of 0.041. A flip needs a margin under about twice the error, so
# 0.3 is four times the error and seven times the worst flip seen, and
# still leaves about a quarter of all positions (some hundred a run) held
# to the rule. Computing in a lower precision than bf16 (int8 weights:
# errors of several tenths) fails it.
MARGIN = 0.3
# A mixture of experts chooses its experts: a discrete choice, which two
# implementations may rightly make differently where the router's logits
# of the last expert kept and the first left out are closer than their
# rounding; the token's logits then differ by far more than rounding
# (a third of its MLP output comes from another expert). A position is
# held to the rule only if, in every layer, the reference's routing is
# further from a tie than this many router logits. Router logits are
# about N(0, 1); the engine's differ from the reference's by about 0.01
# (bf16 activations), so 0.05 is five times that.
ROUTER_SLACK = 0.05
# The rule must bind somewhere: fewer positions over the margin than
# this and the check says nothing.
MIN_POSITIONS = 32


def say(msg: str) -> None:
    print(f"serve-child: {msg}", file=sys.stderr, flush=True)


class State:
    def __init__(self):
        self.status = "warming"
        self.error = None
        self.info: dict = {}
        self.stop = threading.Event()
        self.traced = threading.Event()
        self.traced.set()


def check_requests(module_name: str, cfg, params, requests: list,
                   pad_to: int, rows_pad: int, calibrate: bool,
                   model_lib=None) -> dict:
    """Teacher-force prompt + served tokens through the plain reference
    and hold every served token to the margin rule."""
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    ref = importlib.import_module(
        f"benchmarks.reference.{module_name}_arch")
    out = {"positions": 0, "over_margin": 0, "agree_over_margin": 0,
           "agree_all": 0, "worst_disagreeing_margin": 0.0,
           "margin": MARGIN, "requests": len(requests)}
    bf16_err = 0.0
    for r in requests:
        prompt, served = list(r["prompt"]), list(r["tokens"])
        n = len(served)
        seq = np.zeros((pad_to,), np.int32)
        seq[:len(prompt) + n] = prompt + served
        # Row p predicts token p + 1: the served tokens sit at
        # positions len(prompt) .. len(prompt) + n - 1.
        rows = np.zeros((rows_pad,), np.int32)
        rows[:n] = np.arange(len(prompt) - 1, len(prompt) + n - 1)
        logits, slack = ref.logits_and_slack(cfg, params, seq, rows=rows)
        logits = logits[:n]
        top2 = jax.lax.top_k(logits, 2)
        margins = np.asarray(top2[0][:, 0] - top2[0][:, 1])
        top1 = np.asarray(top2[1][:, 0])
        same = top1 == np.asarray(served)
        over = margins > MARGIN
        if slack is not None:
            routed = np.asarray(slack[:n]) > ROUTER_SLACK
            out["routing_near_tie"] = out.get("routing_near_tie", 0) + int(
                (~routed).sum())
            over &= routed
            margins = np.where(routed, margins, 0.0)
        out["positions"] += n
        out["over_margin"] += int(over.sum())
        out["agree_over_margin"] += int((same & over).sum())
        out["agree_all"] += int(same.sum())
        if (~same).any():
            out["worst_disagreeing_margin"] = max(
                out["worst_disagreeing_margin"],
                float(margins[~same].max()))
        if calibrate and model_lib is not None:
            # The program's own bf16 forward pass (no cache, no engine)
            # against the reference: the size of bf16's rounding in
            # logits, which the margin is set from.
            got = model_lib.forward(cfg, params, jnp.asarray(seq)[None])
            got = got[0] if isinstance(got, tuple) else got
            got = np.asarray(got[0][jnp.asarray(rows)][:n], np.float32)
            bf16_err = max(bf16_err, float(
                np.abs(got - np.asarray(logits)).max()))
    out["tolerated"] = int(ref.TOLERATED_SHARE * out["over_margin"])
    out["ok"] = (out["over_margin"] - out["agree_over_margin"]
                 <= out["tolerated"]
                 and out["over_margin"] >= min(MIN_POSITIONS,
                                               out["positions"] // 4))
    if calibrate:
        out["bf16_forward_max_abs_error"] = bf16_err
    return out


def start_trace(state: State, out_dir: str, seconds: float) -> dict:
    """Trace this process for ``seconds`` on a thread of its own, with
    the profiler's Python tracer off. The server's own ``POST /profile``
    (``stepstats.capture_profile``) takes JAX's defaults, which put a
    ``sys.setprofile`` hook on every thread — handlers and the engine
    loop included — and so slow the very host work whose gaps the trace
    is read for; it is not used for that reason (PERF.md, section 7)."""
    import jax
    state.traced.clear()

    def capture():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        try:
            jax.profiler.start_trace(out_dir, profiler_options=options)
            try:
                time.sleep(seconds)
            finally:
                jax.profiler.stop_trace()
        finally:
            state.traced.set()

    threading.Thread(target=capture, daemon=True,
                     name="bench-trace").start()
    return {"profile_dir": out_dir, "seconds": seconds}


def make_handler(state: State, ctx: dict):
    class Control(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def log_message(self, *args):
            pass

        def _json(self, code, payload):
            body = json.dumps(payload).encode()
            self.send_response(code)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_GET(self):
            if self.path == "/info":
                self._json(200, {"status": state.status,
                                 "error": state.error, **state.info})
            elif self.path == "/memory":
                self._json(200, {"memory_peak_bytes": cells.memory_peak()})
            else:
                self._json(404, {"error": "not found"})

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            req = json.loads(self.rfile.read(n) or b"{}")
            try:
                if self.path == "/check":
                    self._json(200, check_requests(
                        ctx["family"], ctx["cfg"], ctx["params"],
                        req["requests"], ctx["pad_to"], ctx["rows_pad"],
                        bool(req.get("calibrate")), ctx["module"]))
                elif self.path == "/trace":
                    self._json(202, start_trace(
                        state, req["dir"], float(req["seconds"])))
                elif self.path == "/reduce":
                    from benchmarks import trace_reduce
                    state.traced.wait(timeout=300.0)
                    path = trace_reduce.find_xplane(req["profile_dir"])
                    if path is None:
                        self._json(200, {"devices": 0, "path": None})
                        return
                    red = trace_reduce.reduce(path)
                    red["path"] = path
                    if req.get("describe"):
                        red["describe"] = trace_reduce.describe(path, 40)
                        trace_reduce.record(path, os.path.join(
                            req["profile_dir"], "recorded.json"))
                    self._json(200, red)
                elif self.path == "/shutdown":
                    self._json(200, {"ok": True})
                    state.stop.set()
                else:
                    self._json(404, {"error": "not found"})
            except Exception as e:  # noqa: BLE001 — the parent reads it
                import traceback
                traceback.print_exc()
                self._json(500, {"error": f"{type(e).__name__}: {e}"})
    return Control


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--cell", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--ports-file", required=True)
    p.add_argument("--spawned-at", type=float, required=True)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)

    state = State()
    ctx: dict = {}
    # Both servers take a port from the system (a port chosen by the
    # parent beforehand can be taken by the time the server binds: its
    # own polling connections draw from the same range).
    control = ThreadingHTTPServer(("127.0.0.1", 0),
                                  make_handler(state, ctx))
    threading.Thread(target=control.serve_forever, daemon=True,
                     name="bench-control").start()

    cell = cells.load_cell(args.cell, tiny=args.tiny)
    config = cell["config"]
    import jax
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.recipes import serve_llm
    from skypilot_tpu.serve import gang_replica
    from skypilot_tpu.utils import compile_cache
    compile_cache.enable()
    device = mesh_lib.device_info()
    say(f"device {device}")
    refusal = cells.refusal(device, cell, args.tiny)
    if refusal:
        say(refusal)
        with open(args.ports_file, "w") as f:
            json.dump({"refused": refusal}, f)
        return 3
    module, cfg = cells.model_config(config)
    settings = config["serve"]
    tp = int(settings.get("tp", 1))
    topology = gang_replica.ReplicaTopology(
        ici_axes={"tp": tp} if tp > 1 else {})
    mesh, rules = gang_replica.build_mesh(topology)
    params = serve_llm.init_params(cfg, args.seed % (2 ** 31 - 9), mesh,
                                   rules)
    ready = threading.Event()
    httpd = serve_llm.serve(
        cfg, params, 0, ready_event=ready,
        engine_slots=int(settings["engine_slots"]),
        prefix_cache_mb=float(settings.get("prefix_cache_mb", 0)),
        topology=topology, mesh=mesh, rules=rules)
    threading.Thread(target=httpd.serve_forever, daemon=True,
                     name="bench-serve").start()
    with open(args.ports_file + ".tmp", "w") as f:
        json.dump({"serve": httpd.server_address[1],
                   "control": control.server_address[1]}, f)
    os.replace(args.ports_file + ".tmp", args.ports_file)
    server_ctx = httpd.RequestHandlerClass.server_ctx
    while not ready.wait(0.2):
        if server_ctx["warmup_error"]:
            state.status = "failed"
            state.error = server_ctx["warmup_error"]
            say(f"warm-up failed: {state.error}")
            time.sleep(1.0)
            return 4
    ready_s = time.monotonic() - args.spawned_at
    engine = httpd.engine
    ctx.update(family=config["family"], module=module, cfg=cfg,
               params=params,
               pad_to=serve_llm.MAX_PROMPT_TOKENS + serve_llm.MAX_GEN_TOKENS,
               rows_pad=serve_llm.MAX_GEN_TOKENS)
    state.info = {
        "device": device, "ready_s": ready_s,
        "kv": engine.kv_config(),
        "param_bytes_per_device": list(
            mesh_lib.bytes_per_device(params).values()),
        "cache_bytes_per_device": list(
            engine.cache_bytes_per_device().values()),
        "max_prompt_tokens": serve_llm.MAX_PROMPT_TOKENS,
        "max_gen_tokens": serve_llm.MAX_GEN_TOKENS,
        "vocab_size": cfg.vocab_size,
    }
    state.status = "ready"
    say(f"ready after {ready_s:.1f} s: {state.info}")
    state.stop.wait()
    engine.shutdown()
    httpd.shutdown()
    httpd.server_close()
    control.shutdown()
    control.server_close()
    return 0


if __name__ == "__main__":
    os._exit(main())
