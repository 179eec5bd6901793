#!/usr/bin/env python3
"""One run of one cell: ``python benchmarks/run.py --workload <cell>
--seed <n> --seconds <s> --trace <0|1>``.

This process never starts a JAX backend. It reads the cell's files
(``workloads/<cell>.json`` -> ``configs/``, ``traffic/``), starts ONE
child that holds the cell's chips (``runners/serve.py`` or
``runners/train.py``), drives it, prints what it learns on earlier lines
and the contract's one JSON object on the last. Without a TPU, or on a
``device_kind`` that ``peaks.json`` does not list, the child refuses and
this process exits non-zero with no result line.

Three further modes, none of which the driver uses:

``--tiny``       a CPU rehearsal at the configuration's ``tiny`` sizes
                 (``JAX_PLATFORMS=cpu``; refused where there is a TPU).
                 Runs every step, prints what it checked, never a result
                 line, and exits 1 when all passed (2 when not): a tiny
                 run on a CPU says nothing about the chip.
``--sweep a,b``  serving cells: one child, one window per rate, and for
                 each the backlog at its end — how the knee was found.
``--calibrate``  also run the program's own bf16 forward pass against
                 the reference, to size the margin of ``correct``.
"""
from __future__ import annotations

import argparse
import atexit
import json
import os
import pathlib
import shutil
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from random import Random

REPO = pathlib.Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmarks import cells, loadgen  # noqa: E402

READY_SECONDS = 1100.0      # a first run compiles
CHECK_REQUESTS = 4
_children: list = []


def say(msg: str) -> None:
    print(f"bench: {msg}", flush=True)


# ------------------------------------------------------------------ child
def child_env(out: pathlib.Path) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH")
                       else []))
    env["PYTHONUNBUFFERED"] = "1"
    env.setdefault("TPU_LOG_DIR", "disabled")
    # The program's logs, profiles and flight records go under the run's
    # own directory inside the checkout, not under $HOME.
    env["STPU_HOME"] = str(out / "stpu_home")
    env.pop("BENCH_RUN", None)
    return env


def spawn(module: str, argv: list, out: pathlib.Path):
    out.mkdir(parents=True, exist_ok=True)
    so = open(out / "child.stdout", "wb")
    se = open(out / "child.stderr", "wb")
    proc = subprocess.Popen(
        [sys.executable, "-m", module] + argv, stdout=so, stderr=se,
        cwd=str(REPO), env=child_env(out), start_new_session=True)
    so.close()
    se.close()
    _children.append(proc)
    return proc


def stop(proc) -> None:
    if proc.poll() is None:
        for sig, grace in ((signal.SIGTERM, 10.0), (signal.SIGKILL, 5.0)):
            try:
                os.killpg(proc.pid, sig)
            except (ProcessLookupError, PermissionError):
                break
            try:
                proc.wait(timeout=grace)
                break
            except subprocess.TimeoutExpired:
                continue
    try:
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def stop_all() -> None:
    for proc in _children:
        stop(proc)


def _on_signal(signum, frame):
    del frame
    stop_all()
    sys.exit(128 + signum)


def tail(out: pathlib.Path, lines: int = 25) -> str:
    text = []
    for name in ("child.stderr", "child.stdout"):
        path = out / name
        if path.is_file():
            rows = path.read_text(errors="replace").splitlines()[-lines:]
            text += [f"  [{name}] {r[:300]}" for r in rows]
    return "\n".join(text)


def http(port: int, path: str, payload=None, timeout: float = 600.0):
    data = None if payload is None else json.dumps(payload).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=data,
        method="GET" if payload is None else "POST")
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return json.loads(r.read())
    except urllib.error.HTTPError as e:
        # The child answers a failure with a JSON body that says why.
        return json.loads(e.read() or b'{"error": "no body"}')


class Refused(Exception):
    """The run cannot give a result; the message says why."""


# ------------------------------------------------------------------ serve
def wait_ready(proc, ports_file: pathlib.Path, out: pathlib.Path) -> dict:
    """The child's /info once its server is warm. The child binds its
    ports itself and names them in ``ports_file``."""
    deadline = time.monotonic() + READY_SECONDS
    ports = None
    while time.monotonic() < deadline:
        if ports is None and ports_file.is_file():
            ports = json.loads(ports_file.read_text())
            if "refused" in ports:
                raise Refused(f"the child refused: {ports['refused']}")
        if proc.poll() is not None:
            raise Refused(f"the child exited with code {proc.returncode} "
                          f"before it was ready:\n{tail(out)}")
        if ports is None:
            time.sleep(0.25)
            continue
        try:
            info = http(ports["control"], "/info", timeout=5.0)
        except (OSError, ValueError):
            time.sleep(0.25)
            continue
        if info["status"] == "ready":
            return {**info, "ports": ports}
        if info["status"] == "failed":
            raise Refused(f"the child refused: {info['error']}")
        time.sleep(0.25)
    raise Refused(f"the child was not ready in {READY_SECONDS:.0f} s:\n"
                  f"{tail(out)}")


def window(port: int, cell: dict, *, rate, seconds: float, seed: int,
           vocab: int, scrape: float, profile_at=None, profile_s=0.0,
           control=None, profile_dir=None):
    """One measured window. Returns (driver, scraper, t0, profile)."""
    mix = cell["traffic"]
    schedule = loadgen.build_schedule(mix, rate=rate, seconds=seconds,
                                      seed=seed, vocab=vocab)
    say(f"schedule: {len(schedule)} requests, sha256 "
        f"{loadgen.schedule_digest(schedule)[:16]}")
    warmers = loadgen.prefix_warmers(mix, seed=seed, vocab=vocab)
    if warmers:
        warm = loadgen.Driver(port, warmers, {**mix, "loop": "open"},
                              t0=time.monotonic(), seconds=0.0)
        warm.run()
        bad = [r["error"] for r in warm.records if not r["ok"]]
        if bad:
            raise Refused(f"a prefix warmer failed: {bad}")
    lead = float(mix.get("lead_in_s", 0.0))
    t0 = time.monotonic() + lead + 0.2
    driver = loadgen.Driver(port, schedule, mix, t0=t0, seconds=seconds)
    scraper = None
    if scrape:
        scraper = loadgen.Scraper(port, scrape)
        scraper.start()
    profile = {}
    if profile_at is not None:
        def fire():
            delay = t0 + profile_at - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            started = time.monotonic()
            try:
                reply = http(control, "/trace", {
                    "dir": str(profile_dir), "seconds": profile_s},
                    timeout=10.0)
            except (OSError, ValueError) as e:
                profile["error"] = str(e)
                return
            profile.update(reply, a=started - t0,
                           b=started - t0 + profile_s)
        threading.Thread(target=fire, daemon=True).start()
    driver.run()
    if scraper is not None:
        scraper.scrape()
        scraper.stop()
    return driver, scraper, t0, profile


def summarise(driver, seconds: float) -> dict:
    recs = [r for r in driver.records if r["measured"]]
    ok = [r for r in recs if r["ok"]]
    lag = [r["dispatch_lag_s"] for r in driver.records]
    ttft = [r["ttft_s"] for r in ok]
    tpot = [r["tpot_s"] for r in ok if r["tpot_s"] is not None]
    return {
        # Lead-in requests count too: nothing the run sent may fail.
        "attempted": len(driver.records),
        "failed": sum(1 for r in driver.records if not r["ok"]),
        "measured": len(recs),
        "ttft_p50_ms": _ms(loadgen.percentile(ttft, 0.5)),
        "ttft_p95_ms": _ms(loadgen.percentile(ttft, 0.95)),
        "tpot_p50_ms": _ms(loadgen.percentile(tpot, 0.5)),
        "tpot_p95_ms": _ms(loadgen.percentile(tpot, 0.95)),
        "completed_tok_s": driver.tokens_in_window / seconds,
        "samples_ttft": len(ttft), "samples_tpot": len(tpot),
        "dispatch_lag_p99_s": loadgen.percentile(lag, 0.99),
        "last_finish_after_window_s": max(
            [r["last"] or 0.0 for r in driver.records] + [0.0]) - seconds,
        "errors": sorted({r["error"] for r in driver.records
                          if r["error"]}),
    }


def thirds(series: list) -> list:
    """Means of the first, middle and last third of a scraped gauge."""
    n = max(len(series) // 3, 1)
    parts = (series[:n], series[n:2 * n], series[-n:])
    return [sum(p) / max(len(p), 1) for p in parts]


def _ms(v):
    return None if v is None else v * 1e3


def tokens_sound(driver, vocab: int) -> list:
    """Every completed request returned its requested count of
    in-vocabulary tokens; the faults found, if any."""
    bad = []
    for r in driver.records:
        if not r["ok"]:
            continue
        if len(r["tokens"]) != r["max_tokens"]:
            bad.append(f"request {r['index']}: {len(r['tokens'])} tokens "
                       f"for {r['max_tokens']} asked")
        elif not all(0 <= t < vocab for t in r["tokens"]):
            bad.append(f"request {r['index']}: a token outside the "
                       "vocabulary")
    return bad


def run_serve(args, cell: dict, out: pathlib.Path, t_start: float) -> dict:
    ports_file = out / "ports.json"
    ports_file.unlink(missing_ok=True)
    spawned = time.monotonic()
    argv = ["--cell", cell["name"], "--seed", str(args.seed),
            "--ports-file", str(ports_file), "--spawned-at", repr(spawned)]
    if args.tiny:
        argv.append("--tiny")
    out.mkdir(parents=True, exist_ok=True)
    proc = spawn("benchmarks.runners.serve", argv, out)
    info = wait_ready(proc, ports_file, out)
    serve_port, control = info["ports"]["serve"], info["ports"]["control"]
    say(f"child ready after {info['ready_s']:.1f} s on {info['device']}; "
        f"kv {info['kv']}")
    vocab = int(info["vocab_size"])

    if args.sweep:
        for k, rate in enumerate(float(x) for x in args.sweep.split(",")):
            driver, scraper, t0, _ = window(
                serve_port, cell, rate=rate, seconds=args.seconds,
                seed=args.seed + k, vocab=vocab, scrape=0.5)
            s = summarise(driver, args.seconds)
            t1 = t0 + args.seconds
            queue = loadgen.gauge_series(
                scraper.samples, "stpu_engine_queue_depth", t0, t1)
            slots = loadgen.gauge_series(
                scraper.samples, "stpu_engine_slots_occupied", t0, t1)
            say("SWEEP " + json.dumps({
                "rate": rate, **s, "queue_thirds": thirds(queue),
                "queue_max": max(queue or [0]),
                "slots_thirds": thirds(slots)}))
        http(control, "/shutdown", {})
        proc.wait(timeout=60)
        raise Refused("a sweep gives no result line")

    rate = cell.get("rate")
    profile_s = min(3.0, max(args.seconds / 4, 0.5))
    driver, scraper, t0, profile = window(
        serve_port, cell, rate=rate, seconds=args.seconds, seed=args.seed,
        vocab=vocab, scrape=0.5 if args.trace else 0.0,
        profile_at=min(5.0, args.seconds / 3) if args.trace else None,
        profile_s=profile_s, control=control, profile_dir=out / "profile")
    setup_s = t0 - t_start
    s = summarise(driver, args.seconds)
    say(f"window: {json.dumps(s)}")

    faults = tokens_sound(driver, vocab)
    if s["failed"]:
        # A failed or refused request misses every latency limit; the
        # traffic is chosen so that none does.
        faults.append(f"{s['failed']} of {s['attempted']} requests "
                      f"failed: {s['errors']}")
    done = [r for r in driver.records if r["ok"] and r["measured"]]
    sample = Random(f"{args.seed}/check").sample(
        done, min(CHECK_REQUESTS, len(done)))
    by_index = {r.index: r for r in driver.schedule}
    check = http(control, "/check", {
        "requests": [{"prompt": list(by_index[r["index"]].prompt),
                      "tokens": r["tokens"]} for r in sample],
        "calibrate": bool(args.calibrate)})
    say(f"reference check: {json.dumps(check)}")
    if "error" in check:
        faults.append(f"the reference check failed: {check['error']}")
    elif not check["ok"]:
        faults.append("served tokens leave the reference's over the "
                      "margin")
    for f in faults[:10]:
        say(f"FAULT {f}")

    trace = None
    if args.trace:
        if profile.get("profile_dir"):
            # /reduce waits for the capture thread to have stopped the
            # trace and written its file.
            trace = http(control, "/reduce", {
                "profile_dir": profile["profile_dir"],
                "describe": bool(os.environ.get("BENCH_DESCRIBE"))})
            if trace.get("describe"):
                (out / "trace.txt").write_text(trace.pop("describe"))
            say(f"trace: {trace.get('path')} devices "
                f"{trace.get('devices')}")
        else:
            say(f"no profile was taken: {profile}")
    memory = http(control, "/memory")["memory_peak_bytes"]
    http(control, "/shutdown", {})
    try:
        proc.wait(timeout=60)
    except subprocess.TimeoutExpired:
        pass

    run = {
        "cell": cell, "config": cell["config"], "child": info,
        "records": driver.records, "e2e": s,
        "samples": scraper.samples if scraper else [],
        "t0": t0, "t1": t0 + args.seconds,
        "profile": ((profile["a"], profile["b"]) if "a" in profile
                    else None),
        "trace": trace if trace and trace.get("devices") else None,
    }
    e2e = {"setup_s": setup_s, "ttft_p95_ms": s["ttft_p95_ms"],
           "tpot_p95_ms": s["tpot_p95_ms"],
           "completed_tok_s": s["completed_tok_s"]}
    return {"run": run, "e2e": e2e, "correct": not faults,
            "attempted": s["attempted"], "failed": s["failed"],
            "device": info["device"], "memory": memory}


# ------------------------------------------------------------------ train
def run_train(args, cell: dict, out: pathlib.Path, t_start: float) -> dict:
    spawned = time.monotonic()
    argv = ["--cell", cell["name"], "--seed", str(args.seed),
            "--seconds", str(args.seconds), "--trace", str(args.trace),
            "--out", str(out), "--spawned-at", repr(spawned)]
    if args.tiny:
        argv.append("--tiny")
    result_path = out / "train_result.json"
    if result_path.exists():
        result_path.unlink()
    proc = spawn("benchmarks.runners.train", argv, out)
    try:
        rc = proc.wait(timeout=READY_SECONDS)
    except subprocess.TimeoutExpired:
        raise Refused(f"the train child passed {READY_SECONDS:.0f} s:\n"
                      f"{tail(out)}")
    if rc != 0 or not result_path.is_file():
        raise Refused(f"the train child exited with code {rc}:\n"
                      f"{tail(out)}")
    train = json.loads(result_path.read_text())
    tok_s = train["steps"] * train["tokens_per_step"] / train["window_s"]
    say(f"train: {train['steps']} steps in {train['window_s']:.2f} s; "
        f"first loss {train['first_loss']} (reference "
        f"{train['reference_first_loss']}, off by "
        f"{train['first_loss_rel_error']:.2e} relative); correct "
        f"{train['correct']}")
    trace = train.get("trace")
    run = {"cell": cell, "config": cell["config"], "train": train,
           "trace": trace if trace and trace.get("devices") else None,
           "records": [], "samples": []}
    return {"run": run,
            "e2e": {"setup_s": train["setup_end_mono"] - t_start,
                    "train_tok_s": tok_s},
            "correct": bool(train["correct"]),
            "attempted": train["steps"], "failed": 0,
            "device": train["device"],
            "memory": train["memory_peak_bytes"]}


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    t_start = time.monotonic()
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--out", default=str(REPO / ".bench_out"))
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--sweep", default="")
    p.add_argument("--calibrate", action="store_true")
    args = p.parse_args(argv)

    atexit.register(stop_all)
    signal.signal(signal.SIGTERM, _on_signal)
    signal.signal(signal.SIGINT, _on_signal)
    cell = cells.load_cell(args.workload, tiny=args.tiny)
    out = pathlib.Path(args.out) / args.workload
    shutil.rmtree(out / "profile", ignore_errors=True)
    try:
        got = (run_train if cell["runner"] == "train"
               else run_serve)(args, cell, out, t_start)
    except Refused as e:
        print(f"bench: no result: {e}", file=sys.stderr, flush=True)
        stop_all()
        return 1
    finally:
        stop_all()

    if not os.environ.get("BENCH_DESCRIBE"):
        # A trace is tens of megabytes; it has been reduced.
        shutil.rmtree(out / "profile", ignore_errors=True)
    device = dict(got["device"])
    wanted = ["setup_s"] + list(cell["end_to_end"])
    run = got["run"]
    if not args.tiny:
        run["peaks"] = cells.peaks()[device["kind"]]
    metrics = {}
    if args.trace:
        for mod in cells.layer_metrics(cell["runner"]):
            if mod.MOVES not in wanted:
                continue
            value = mod.compute(run)
            if value is not None:
                metrics[mod.NAME] = {"value": value, "unit": mod.UNIT}
    else:
        units = {"setup_s": "s", "ttft_p95_ms": "ms", "tpot_p95_ms": "ms",
                 "completed_tok_s": "tokens/s", "train_tok_s": "tokens/s"}
        for name in wanted:
            if got["e2e"].get(name) is None:
                raise SystemExit(f"bench: no value for {name}")
            metrics[name] = {"value": got["e2e"][name],
                             "unit": units[name]}
    device["memory_peak_bytes"] = got["memory"]
    line = {"correct": bool(got["correct"]),
            "attempted": got["attempted"], "failed": got["failed"],
            "metrics": metrics, "device": device}
    trace = run.get("trace")
    if args.trace and trace:
        from benchmarks import trace_reduce
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        line["breakdown"] = trace_reduce.breakdown(trace)
        say("programs: " + json.dumps(trace["programs"]))
    say("end to end: " + json.dumps(got["e2e"]))
    if args.tiny:
        passed = got["correct"] and got["failed"] == 0 and bool(metrics)
        say("REHEARSAL " + json.dumps(line)[:2000])
        say(f"rehearsal {'passed' if passed else 'FAILED'}: a tiny run on "
            "a CPU is not a measurement; no result line")
        return 1 if passed else 2
    if args.trace and not trace:
        print("bench: no result: the traced run has no device trace",
              file=sys.stderr, flush=True)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
