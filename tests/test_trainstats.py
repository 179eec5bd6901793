"""Training goodput telemetry (trainstats): step ring, live MFU,
gang straggler detection, flight recorder, jobs-controller scrape and
`stpu jobs top`.

Acceptance pinned here:
  * disarmed, the recipe train loop is provably trainstats-free
    (monkeypatch-bomb, the stepstats pattern) and the armed loop's
    step time stays within noise of unarmed (slow-marked);
  * an armed 2-host gang training job with an injected slow host and a
    mid-run preemption SIGKILL shows the straggler event + skew gauge,
    a controller-synthesized flight dump containing pre-crash steps of
    BOTH hosts, and post-recovery `stpu jobs top` renders MFU/goodput/
    recovery count scraped through the jobs controller store.
"""
import json
import os
import pathlib
import sys
import textwrap
import time

import pytest
from click.testing import CliRunner

from skypilot_tpu.observability import trainstats

REPO_ROOT = str(pathlib.Path(__file__).resolve().parent.parent)


@pytest.fixture
def armed(tmp_state_dir):
    trainstats.arm(ring=128, sync_every=0)
    trainstats.reset()
    yield tmp_state_dir
    trainstats.disarm()
    trainstats.reset()


# ------------------------------------------------------------ ring unit
def test_ring_aggregates_and_eviction(armed):
    trainstats.arm(ring=64)
    for s in range(1, 101):        # ring=64: oldest 36 evicted
        trainstats.record_step(step=s, dur=0.002, tokens=100,
                               data_wait_s=0.0005, ckpt_s=0.0001)
    snap = trainstats.snapshot()
    assert snap["armed"] is True
    assert snap["steps"] == 64
    assert snap["total_steps"] == 100
    assert snap["step_seconds_mean"] == pytest.approx(0.002, rel=1e-6)
    assert snap["tokens_per_sec"] > 0
    assert snap["last"]["step"] == 100
    # Eviction kept the running sums consistent with the resident set.
    tail = trainstats.steps_tail()
    assert len(tail) == 64
    assert [r["step"] for r in tail] == list(range(37, 101))


def test_delayed_values_attach_to_previous_record(armed):
    trainstats.record_step(step=1, dur=0.01, tokens=10)
    trainstats.record_step(step=2, dur=0.01, tokens=10,
                           delayed={"loss": 1.5, "grad_norm": 0.25})
    recs = trainstats.steps_tail()
    # Step 1's loss arrived with step 2's record (one-step-delayed
    # fetch); step 2's own values are still outstanding.
    assert recs[0]["step"] == 1
    assert recs[0]["loss"] == 1.5
    assert recs[0]["grad_norm"] == 0.25
    assert recs[1]["loss"] is None
    snap = trainstats.snapshot()
    # The snapshot surfaces the newest record that HAS a loss (the
    # newest record's own loss is always one rotation away).
    assert snap["last"]["step"] == 2
    assert snap["last"]["loss"] == 1.5
    assert snap["last"]["loss_step"] == 1


def test_mfu_and_goodput_math(armed, monkeypatch):
    # Fake monotonic clock: the ring's window must match the fabricated
    # durs, exactly like a real loop where dur ~= elapsed.
    clock = {"t": 1000.0}
    monkeypatch.setattr(time, "perf_counter", lambda: clock["t"])
    trainstats.configure(flops_per_token=200.0, peak_flops=1e6)
    for s in range(1, 21):
        clock["t"] += 0.004 + 0.001 + 0.0005 + 0.0005  # step+stalls+slack
        trainstats.record_step(step=s, dur=0.004, tokens=50,
                               data_wait_s=0.001, ckpt_s=0.0005)
    snap = trainstats.snapshot()
    # MFU == tok/s * flops_per_token / peak, from the same window.
    assert snap["mfu"] == pytest.approx(
        snap["tokens_per_sec"] * 200.0 / 1e6, rel=0.01)
    g = snap["goodput"]
    assert set(g) == {"productive", "data_wait", "ckpt", "restart"}
    assert g["restart"] == 0.0
    assert g["data_wait"] > 0 and g["ckpt"] > 0
    assert 0 < g["productive"] <= 1.0
    assert sum(g.values()) <= 1.0 + 1e-6
    # Restart downtime dilutes the denominator: productive drops, the
    # restart component appears.
    trainstats.note_downtime(snap["window_s"])
    clock["t"] += 0.006
    trainstats.record_step(step=21, dur=0.004, tokens=50)
    snap2 = trainstats.snapshot()
    assert snap2["downtime_s"] > 0
    assert snap2["goodput"]["restart"] > 0.3
    assert snap2["goodput"]["productive"] < g["productive"]


def test_mfu_none_without_peak(armed):
    trainstats.record_step(step=1, dur=0.01, tokens=10)
    assert trainstats.snapshot()["mfu"] is None


def test_sync_cadence_and_sampled_sync(armed):
    trainstats.arm(ring=128, sync_every=3)
    assert [trainstats.sync_due() for _ in range(7)] == [
        False, False, True, False, False, True, False]

    class _Val:
        waited = False

        def block_until_ready(self):
            self.waited = True

    v = _Val()
    dt = trainstats.sampled_sync(v)
    assert v.waited and dt >= 0.0
    # Duck-typed: a plain float (no block_until_ready) is fine.
    assert trainstats.sampled_sync(1.0) >= 0.0
    # sync_every=0 never fires.
    trainstats.arm(ring=128, sync_every=0)
    assert not any(trainstats.sync_due() for _ in range(10))


def test_peak_flops_for_device():
    class _Dev:
        def __init__(self, kind):
            self.device_kind = kind

    assert trainstats.peak_flops_for_device(
        _Dev("TPU v5e")) == trainstats.PEAK_FLOPS["v5e"]
    assert trainstats.peak_flops_for_device(
        _Dev("TPU v5 lite")) == trainstats.PEAK_FLOPS["v5e"]
    # A bare "v5" names no generation in the table: unknown, no guess.
    assert trainstats.peak_flops_for_device(_Dev("TPU v5")) == 0.0
    assert trainstats.peak_flops_for_device(
        _Dev("TPU v5p")) == trainstats.PEAK_FLOPS["v5p"]
    assert trainstats.peak_flops_for_device(_Dev("TPU v4")) == \
        trainstats.PEAK_FLOPS["v4"]
    assert trainstats.peak_flops_for_device(_Dev("cpu")) == 0.0


def test_env_knobs_registered():
    from skypilot_tpu.utils import env_contract
    reg = env_contract.REGISTRY
    assert reg["STPU_TRAINSTATS"].default == "0"
    assert reg["STPU_TRAINSTATS_RING"].default == "512"
    assert reg["STPU_TRAINSTATS_SYNC_EVERY"].default == "0"
    assert reg["STPU_TRAINSTATS_DIR"].default is None
    assert reg["STPU_TRAIN_STRAGGLER_SECONDS"].default == "2.0"


# ----------------------------------------------------- straggler scan
def _write_host_jsonl(out_dir, rank, ts, step=5):
    with open(os.path.join(out_dir, f"host-{rank}.jsonl"), "a") as f:
        for i in range(3):
            f.write(json.dumps({
                "seq": i, "step": step - 2 + i, "ts": ts - (2 - i),
                "mono": 0.0, "dur": 0.01, "tokens": 100,
                "data_wait_s": 0.0, "ckpt_s": 0.0}) + "\n")


def test_straggler_detection_and_edge_trigger(armed, tmp_path):
    out_dir = str(tmp_path / "ts")
    os.makedirs(out_dir)
    now = time.time()
    trainstats.configure(host=0, hosts=2, out_dir=out_dir,
                         job="mj-train", straggler_s=1.0)
    _write_host_jsonl(out_dir, 0, now)            # fresh
    _write_host_jsonl(out_dir, 1, now - 10.0)     # 10s stale
    lag = trainstats.check_stragglers(now=now)
    # 2-host median = mean → host 1 lags (10/2)=5s > 1s threshold.
    assert set(lag) == {1}
    assert lag[1] == pytest.approx(5.0, abs=0.5)
    snap = trainstats.snapshot()
    assert snap["stragglers"] == [1]
    assert snap["host_skew_s"] == pytest.approx(5.0, abs=0.5)
    # Edge-triggered event: exactly one train_straggler for host 1,
    # even after a second scan that still sees it lagging.
    trainstats.check_stragglers(now=now)
    from skypilot_tpu.observability import events
    evs = [e for e in events.read(kind="train")
           if e.get("event") == "train_straggler"]
    assert len(evs) == 1
    assert evs[0]["host"] == 1
    assert evs[0]["lag_s"] == pytest.approx(5.0, abs=0.5)


def test_straggler_needs_two_hosts_and_threshold(armed, tmp_path):
    out_dir = str(tmp_path / "ts")
    os.makedirs(out_dir)
    now = time.time()
    _write_host_jsonl(out_dir, 0, now)
    _write_host_jsonl(out_dir, 1, now - 10.0)
    # hosts=1 → no scan; threshold 0 → disabled.
    trainstats.configure(host=0, hosts=1, out_dir=out_dir)
    assert trainstats.check_stragglers(now=now) == {}
    trainstats.configure(host=0, hosts=2, out_dir=out_dir,
                         straggler_s=0.0)
    assert trainstats.check_stragglers(now=now) == {}


# -------------------------------------------------- flight recorder
def test_dump_flight_roundtrip_and_retention(armed, tmp_path):
    out_dir = str(tmp_path / "ts")
    trainstats.configure(out_dir=out_dir, job="mj-train")
    for s in range(1, 6):
        trainstats.record_step(step=s, dur=0.01, tokens=10)
    path = trainstats.dump_flight("train_crash", error="boom()")
    assert path and os.path.exists(path)
    assert "train_crash" in os.path.basename(path)
    doc = trainstats.read_dump(dir_path=os.path.dirname(path))
    assert doc["reason"] == "train_crash"
    assert doc["error"] == "boom()"
    assert doc["snapshot"]["total_steps"] == 5
    assert [r["step"] for r in doc["steps"]] == [1, 2, 3, 4, 5]
    # Retention: the dir never holds more than KEEP_DUMPS dumps.
    for _ in range(trainstats.KEEP_DUMPS + 5):
        trainstats.dump_flight("test_prune")
    assert len(trainstats.list_dumps(
        os.path.dirname(path))) <= trainstats.KEEP_DUMPS


def test_dump_dir_flight_synthesizes_gang_dump(armed, tmp_path):
    out_dir = str(tmp_path / "ts")
    os.makedirs(out_dir)
    now = time.time()
    _write_host_jsonl(out_dir, 0, now)
    _write_host_jsonl(out_dir, 1, now - 3.0)
    with open(os.path.join(out_dir, "snapshot.json"), "w") as f:
        json.dump({"mfu": 0.41, "host_skew_s": 1.5}, f)
    path = trainstats.dump_dir_flight("job_preempted", out_dir, tail=2)
    assert path and os.path.exists(path)
    doc = json.load(open(path))
    assert doc["synthesized"] is True
    assert doc["reason"] == "job_preempted"
    assert set(doc["hosts"]) == {"0", "1"}
    assert len(doc["hosts"]["0"]) == 2          # tail honored
    assert doc["snapshot"]["mfu"] == 0.41
    # An empty dir yields no dump (nothing to post-mortem).
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    assert trainstats.dump_dir_flight("x", empty) is None


# ------------------------------------------- recipe loop integration
def _lora_args(tmp_path, steps=3):
    return ["--model", "tiny", "--steps", str(steps),
            "--batch-size", "2", "--seq-len", "64",
            "--checkpoint-dir", str(tmp_path / "ckpt")]


def test_disarmed_train_loop_is_trainstats_free(tmp_state_dir,
                                                tmp_path, monkeypatch):
    """Monkeypatch-bomb: with ENABLED False, a full recipe run must
    never construct or touch trainstats state — the disarmed hot-loop
    cost is exactly one module-attribute load per guard."""
    from skypilot_tpu.recipes import llama_lora

    def _boom(*a, **k):
        raise AssertionError("trainstats touched while disarmed")

    trainstats.disarm()
    for name in ("configure", "record_step", "sampled_sync",
                 "sync_due", "snapshot", "flush", "dump_flight",
                 "note_downtime", "check_stragglers"):
        monkeypatch.setattr(trainstats, name, _boom)
    metrics = llama_lora.main(_lora_args(tmp_path))
    assert metrics["steps"] == 3
    assert "train_mfu" not in metrics


def test_armed_recipe_reports_goodput(armed, tmp_path, monkeypatch):
    """Armed CPU run: the recipe emits the train_* keys from its own
    trainstats snapshot, the delayed loss landed in the ring, and the
    shared out_dir got the host JSONL + snapshot.json the controller
    scrapes."""
    from skypilot_tpu.recipes import llama_lora
    out_dir = str(tmp_path / "ts")
    monkeypatch.setenv("STPU_TRAINSTATS_DIR", out_dir)
    metrics = llama_lora.main(_lora_args(tmp_path, steps=4))
    assert metrics["train_mfu"] is None          # CPU: peak unknown
    assert metrics["train_tokens_per_sec"] > 0
    assert metrics["train_step_seconds"] > 0
    assert 0 < metrics["train_goodput"]["productive"] <= 1.0
    snap = trainstats.snapshot()
    assert snap["total_steps"] == 4
    # One-step-delayed: steps 1..3 carry their loss, the last is still
    # outstanding in the ring (drained into the metrics only).
    recs = trainstats.steps_tail()
    assert all(r["loss"] is not None for r in recs[:-1])
    assert os.path.exists(os.path.join(out_dir, "host-0.jsonl"))
    assert os.path.exists(os.path.join(out_dir, "snapshot.json"))
    scraped = json.load(open(os.path.join(out_dir, "snapshot.json")))
    assert scraped["job"] == "llama_lora"


def test_recipe_crash_dumps_flight(armed, tmp_path, monkeypatch):
    """The train.step chaos seam raising mid-loop produces a
    train_crash flight dump with the pre-crash steps."""
    from skypilot_tpu.recipes import llama_lora
    from skypilot_tpu.utils import fault_injection
    out_dir = str(tmp_path / "ts")
    monkeypatch.setenv("STPU_TRAINSTATS_DIR", out_dir)
    fault_injection.configure("train.step:raise:skip=2")
    try:
        with pytest.raises(fault_injection.InjectedFault):
            llama_lora.main(_lora_args(tmp_path, steps=6))
    finally:
        fault_injection.clear()
    dumps = trainstats.list_dumps(os.path.join(out_dir, "flightrec"))
    assert any("train_crash" in d for d in dumps)
    doc = trainstats.read_dump(
        dir_path=os.path.join(out_dir, "flightrec"))
    assert doc["reason"] == "train_crash"
    assert "InjectedFault" in doc["error"]
    assert len(doc["steps"]) >= 2               # pre-crash records


@pytest.mark.slow
def test_armed_overhead_within_noise(tmp_state_dir, tmp_path):
    """Armed vs unarmed recipe step time stays within noise (the
    zero-cost-when-disarmed + cheap-when-armed contract)."""
    from skypilot_tpu.recipes import llama_lora

    def run(arm):
        trainstats.reset()
        if arm:
            trainstats.arm(ring=256, sync_every=0)
        else:
            trainstats.disarm()
        t0 = time.perf_counter()
        llama_lora.main(["--model", "tiny", "--steps", "30",
                         "--batch-size", "2", "--seq-len", "64"])
        return time.perf_counter() - t0

    run(False)                                   # compile warmup
    unarmed = min(run(False) for _ in range(2))
    armed_t = min(run(True) for _ in range(2))
    trainstats.disarm()
    trainstats.reset()
    # Generous noise bound: CI boxes jitter, but armed must not be
    # systematically slower (a sync on the hot path would be 2x+).
    assert armed_t < unarmed * 1.5, (armed_t, unarmed)


# ----------------------------------------------- jobs state columns
@pytest.mark.usefixtures("tmp_state_dir")
def test_jobs_state_train_columns_roundtrip():
    from skypilot_tpu.jobs import state as jobs_state
    job_id = jobs_state.add_job("ts-cols", "/dev/null", "local", 1)
    job = jobs_state.get_job(job_id)
    assert job["mfu"] is None and job["goodput"] is None
    jobs_state.set_train_stats(job_id, 0.42, 1234.5, 0.91)
    job = jobs_state.get_job(job_id)
    assert job["mfu"] == pytest.approx(0.42)
    assert job["tok_s"] == pytest.approx(1234.5)
    assert job["goodput"] == pytest.approx(0.91)


def test_dashboard_pct_cells():
    from skypilot_tpu.jobs import dashboard
    assert dashboard._pct(None) == "-"
    assert dashboard._pct(0.425) == "42.5%"
    html = dashboard._render([{
        "job_id": 1, "job_name": "j", "status": "RUNNING",
        "recovery_count": 0, "mfu": 0.4, "goodput": 0.9,
        "cluster_name": "c", "submitted_at": time.time(),
        "failure_reason": None}])
    assert "40.0%" in html and "90.0%" in html


# ------------------------------------------------- jobs top rendering
def test_jobs_top_render_fallback_to_row_columns():
    from skypilot_tpu import cli as cli_mod
    job = {"job_id": 7, "job_name": "mj", "status": "RUNNING",
           "recovery_count": 2, "last_ckpt_step": 40,
           "mfu": 0.33, "tok_s": 9000.0, "goodput": 0.88}
    out = cli_mod._render_jobs_top(job, {})
    assert "recoveries 2" in out
    assert "ckpt @40" in out
    assert "MFU 33.0%" in out                    # row-column fallback
    assert "tok/s 9000" in out
    assert "productive 88.0%" in out
    assert "no trainstats snapshot yet" in out
    # With a snapshot, the live values win over the row columns.
    doc = {"snapshot": {
        "mfu": 0.5, "tokens_per_sec": 100.0, "steps_per_sec": 2.5,
        "goodput": {"productive": 0.95, "data_wait": 0.01,
                    "ckpt": 0.02, "restart": 0.02},
        "hosts": 2, "host_skew_s": 0.12, "stragglers": [1],
        "last": {"step": 50, "loss": 2.5, "grad_norm": 1.0}}}
    out = cli_mod._render_jobs_top(job, doc)
    assert "MFU 50.0%" in out
    assert "at step 50" in out
    assert "loss       2.5000" in out
    assert "stragglers 1" in out
    assert "no trainstats snapshot" not in out


# ------------------------------------------------------- gang e2e
def _wait_for(predicate, timeout=30, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if predicate():
            return
        time.sleep(0.1)
    raise TimeoutError(f"timed out waiting for {msg}")


def _wait_status(job_id, statuses, timeout=60):
    from skypilot_tpu.jobs import state as jobs_state
    deadline = time.time() + timeout
    st = None
    while time.time() < deadline:
        st = jobs_state.get_status(job_id)
        if st in statuses:
            return st
        time.sleep(0.1)
    raise TimeoutError(f"job {job_id} stuck at {st}, wanted {statuses}")


def _gang_script(tmp_path):
    """Two-host gang task: both hosts record armed trainstats into the
    controller-stamped $STPU_JOB_CKPT_DIR. Attempt 1: host 1 goes
    silent after 3 steps (the injected straggler) while host 0 keeps
    stepping, detects the lag, records it, then hangs to be preempted.
    Attempt 2 (marker exists): both hosts finish quickly."""
    script = tmp_path / "gang_train.py"
    script.write_text(textwrap.dedent(f"""
        import json, os, sys, time
        sys.path.insert(0, {REPO_ROOT!r})
        from skypilot_tpu.observability import trainstats
        rank = int(os.environ.get("SKYPILOT_NODE_RANK", "0"))
        marker = os.path.join({str(tmp_path)!r}, f"attempt-{{rank}}")
        first = not os.path.exists(marker)
        open(marker, "a").write("x\\n")
        trainstats.arm(ring=64)
        trainstats.configure(flops_per_token=100.0, peak_flops=1e12,
                             host=rank, hosts=2, job="mj-train-gang",
                             straggler_s=0.4)
        if not first:
            for s in range(1, 6):
                trainstats.record_step(step=s, dur=0.01, tokens=1000,
                                       delayed={{"loss": 2.0}})
                time.sleep(0.02)
            trainstats.flush()
            print("recovered-done")
            sys.exit(0)
        if rank == 1:
            for s in range(1, 4):
                trainstats.record_step(step=s, dur=0.01, tokens=1000)
                time.sleep(0.05)
            time.sleep(120)      # the slow host: stops reporting
        step = 0
        lag = {{}}
        deadline = time.time() + 30
        while time.time() < deadline:
            step += 1
            trainstats.record_step(step=step, dur=0.01, tokens=1000,
                                   data_wait_s=0.001,
                                   delayed={{"loss": 3.0}})
            lag = trainstats.check_stragglers(now=time.time())
            if lag:
                break
            time.sleep(0.1)
        trainstats.flush()
        with open(os.path.join({str(tmp_path)!r}, "straggler.json"),
                  "w") as f:
            json.dump({{"lagging": lag,
                       "skew": trainstats.snapshot()["host_skew_s"],
                       "steps": step}}, f)
        time.sleep(120)          # hang: preempted mid-run here
    """))
    return script


@pytest.mark.usefixtures("tmp_state_dir")
def test_gang_straggler_preemption_recovery_jobs_top(tmp_path,
                                                     monkeypatch):
    """The PR's e2e acceptance: armed 2-host gang job → injected slow
    host flags a straggler (event + skew gauge) → mid-run preemption
    kill → controller synthesizes a gang flight dump with pre-crash
    steps → recovery succeeds → `stpu jobs top` renders MFU/goodput/
    recovery count scraped through the controller store."""
    from skypilot_tpu import cli as cli_mod
    from skypilot_tpu import jobs
    from skypilot_tpu.jobs import state as jobs_state
    from skypilot_tpu.jobs.state import ManagedJobStatus
    from skypilot_tpu.observability import events
    from skypilot_tpu.provision import local as local_provider
    from skypilot_tpu.task import Task
    from skypilot_tpu.resources import Resources

    monkeypatch.setenv("STPU_JOBS_POLL_SECONDS", "0.2")
    script = _gang_script(tmp_path)
    task = Task("mj-train-gang",
                run=f"{sys.executable} {script}", num_nodes=2)
    task.set_resources(Resources(cloud="local", use_spot=True))
    job_id = jobs.launch(task, detach=True, controller="local")

    _wait_status(job_id, {ManagedJobStatus.RUNNING}, timeout=30)
    straggler_file = tmp_path / "straggler.json"
    _wait_for(straggler_file.exists, timeout=45,
              msg="host 0 to flag the injected straggler")
    seen = json.loads(straggler_file.read_text())
    assert "1" in seen["lagging"]               # host 1 flagged
    assert seen["skew"] > 0.4                   # over the threshold

    job = jobs_state.get_job(job_id)
    ckpt_dir = job["ckpt_dir"]
    stats_dir = os.path.join(ckpt_dir, "trainstats")
    assert os.path.exists(os.path.join(stats_dir, "host-0.jsonl"))
    assert os.path.exists(os.path.join(stats_dir, "host-1.jsonl"))

    # Controller scraped the snapshot into its store + the jobs row.
    def _scraped():
        j = jobs_state.get_job(job_id)
        return j.get("mfu") is not None and j.get("tok_s")
    _wait_for(_scraped, timeout=15, msg="controller trainstats scrape")

    # Mid-run kill: preempt the cluster while host 0 hangs.
    local_provider.simulate_preemption(job["cluster_name"])
    status = _wait_status(
        job_id, {ManagedJobStatus.SUCCEEDED, ManagedJobStatus.FAILED,
                 ManagedJobStatus.FAILED_CONTROLLER}, timeout=90)
    assert status == ManagedJobStatus.SUCCEEDED
    job = jobs_state.get_job(job_id)
    assert job["recovery_count"] >= 1

    # The straggler event was emitted (edge-triggered, from host 0).
    evs = [e for e in events.read(kind="train")
           if e.get("event") == "train_straggler"]
    assert evs and evs[0]["host"] == 1

    # The controller dumped a synthesized gang flight on preemption,
    # containing pre-crash steps of BOTH hosts.
    dumps = trainstats.list_dumps(
        os.path.join(stats_dir, "flightrec"))
    preempt_dumps = [d for d in dumps if "job_preempted" in d]
    assert preempt_dumps
    doc = trainstats.read_dump(
        preempt_dumps[-1],
        dir_path=os.path.join(stats_dir, "flightrec"))
    assert doc["synthesized"] is True
    assert set(doc["hosts"]) >= {"0", "1"}
    assert doc["hosts"]["1"]                    # slow host's records
    assert doc["hosts"]["1"][-1]["step"] == 3   # died at step 3
    assert doc["snapshot"] is not None

    # Scraped series persisted for `stpu jobs top`.
    from skypilot_tpu.utils import paths
    train_doc_path = (paths.logs_dir() / "managed_jobs" /
                      f"controller-{job_id}-train.json")
    assert train_doc_path.exists()
    train_doc = json.loads(train_doc_path.read_text())
    assert train_doc["series"]["stpu_train_mfu"], \
        "controller store has no MFU points"
    assert train_doc["snapshot"]["job"] == "mj-train-gang"

    # Post-recovery dashboard: MFU/goodput/recoveries all render.
    result = CliRunner().invoke(cli_mod.cli,
                                ["jobs", "top", str(job_id)])
    assert result.exit_code == 0, result.output
    assert f"job        {job_id}" in result.output
    assert "recoveries" in result.output and "MFU" in result.output
    assert "goodput    productive" in result.output
    assert "gang       hosts 2" in result.output
    # The persisted row columns agree with the scrape.
    assert job["mfu"] is not None
    assert job["goodput"] is not None
