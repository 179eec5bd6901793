"""Multi-host sharded serving: one replica = one gang-scheduled slice.

The contract under test (ISSUE 8 acceptance), strongest first:

  * a 2-process gang replica (self-spawned followers on the forced
    CPU mesh) serves end-to-end through LB → host 0 → TP engine with
    BIT-IDENTICAL greedy output and seeded-sampling parity vs the
    single-process engine; killing the follower mid-stream flips
    /health to 503, the whole-gang supervisor restart recovers, the
    next request through the LB succeeds, and the whole story is
    traced as ONE tree (lb.request → replica.generate → gang.run);
  * the serving instantiation of parallel/mesh.py resolves: TP-sharded
    KV cache specs for all 3 families (with the kv_heads divisibility
    fallback) and donation preserved through the sharded jitted
    decode/prefill entry points — a dropped donation silently doubles
    the KV cache in HBM;
  * topology plumbing: schema validation, spec round-trip, the replica
    manager gang-launching all hosts as ONE replica (num_nodes + env),
    the stpu_replica_topology_info gauge, and loadgen report
    attribution;
  * (the serve/ collectives lint now lives in tests/test_static_analysis.py).
"""
import dataclasses
import importlib.util
import json
import os
import pathlib
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from skypilot_tpu import exceptions
from skypilot_tpu.models import gemma, llama, mixtral
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.serve import decode_engine
from skypilot_tpu.serve import gang_replica
from skypilot_tpu.serve.service_spec import SkyServiceSpec
from skypilot_tpu.utils import schemas

REPO = pathlib.Path(__file__).resolve().parent.parent


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.read()


def _get_code(url, timeout=10):
    try:
        return _get(url, timeout=timeout)[0]
    except urllib.error.HTTPError as e:
        return e.code
    except (urllib.error.URLError, ConnectionError, OSError):
        return None


def _post_json(url, payload, timeout=120):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read())


# ================================================ topology spec plumbing
def test_replica_topology_schema_and_semantics():
    ok = {"readiness_probe": "/health",
          "replica_topology": {"hosts": 2, "ici_axes": {"tp": 2}}}
    schemas.validate_service(ok)
    spec = SkyServiceSpec.from_yaml_config(ok)
    assert spec.replica_topology == {"hosts": 2,
                                     "ici_axes": {"tp": 2}}
    topo = gang_replica.ReplicaTopology.from_config(
        spec.replica_topology)
    assert (topo.hosts, topo.tp, topo.label()) == (2, 2, "2x2")

    with pytest.raises(exceptions.InvalidTaskError):
        schemas.validate_service(
            {"readiness_probe": "/",
             "replica_topology": {"hosts": 0}})
    with pytest.raises(exceptions.InvalidTaskError):
        schemas.validate_service(
            {"readiness_probe": "/",
             "replica_topology": {"hosts": 2, "slices": 1}})
    with pytest.raises(exceptions.InvalidTaskError):
        schemas.validate_service(
            {"readiness_probe": "/",
             "replica_topology": {"ici_axes": {"tp": 2}}})
    with pytest.raises(exceptions.InvalidTaskError):
        # Schema-legal shape, semantically bad axis size.
        SkyServiceSpec.from_yaml_config(
            {"readiness_probe": "/",
             "replica_topology": {"hosts": 2,
                                  "ici_axes": {"tp": 0}}})


def test_replica_topology_yaml_roundtrip():
    spec = SkyServiceSpec.from_yaml_config(
        {"readiness_probe": "/health",
         "replicas": 1,
         "replica_topology": {"hosts": 2, "ici_axes": {"tp": 4}}})
    again = SkyServiceSpec.from_yaml_config(spec.to_yaml_config())
    assert again.replica_topology == spec.replica_topology
    # Unsharded specs don't grow a topology block.
    plain = SkyServiceSpec(readiness_path="/")
    assert "replica_topology" not in plain.to_yaml_config()


def test_topology_env_roundtrip(monkeypatch):
    topo = gang_replica.ReplicaTopology(hosts=2, ici_axes={"tp": 2})
    monkeypatch.setenv(gang_replica.TOPOLOGY_ENV, topo.to_env_json())
    assert gang_replica.ReplicaTopology.from_env() == topo
    monkeypatch.setenv(gang_replica.TOPOLOGY_ENV, "{not json")
    with pytest.raises(gang_replica.GangError):
        gang_replica.ReplicaTopology.from_env()


@pytest.mark.usefixtures("tmp_state_dir")
def test_replica_manager_gang_launches_all_hosts(monkeypatch):
    """A topology-bearing spec launches the replica as ONE gang: the
    task copy carries num_nodes = hosts and the topology env, and the
    controller/LB still see exactly one replica."""
    from skypilot_tpu.resources import Resources
    from skypilot_tpu.serve import replica_managers
    from skypilot_tpu.task import Task

    task = Task("tp-svc", run="python -m skypilot_tpu.recipes.serve_llm"
                              " --port $SKYPILOT_SERVE_REPLICA_PORT")
    task.set_resources(Resources(cloud="local"))
    task.service = SkyServiceSpec(
        readiness_path="/health", min_replicas=1,
        replica_topology={"hosts": 2, "ici_axes": {"tp": 2}})
    mgr = replica_managers.SkyPilotReplicaManager(
        "tp-svc", task.service, task)
    captured = {}

    def fake_launch(t, cluster_name=None, detach_run=None,
                    stream_logs=None):
        captured["num_nodes"] = t.num_nodes
        captured["envs"] = dict(t.envs)
        raise RuntimeError("stop before provisioning")

    monkeypatch.setattr(replica_managers.execution, "launch",
                        fake_launch)
    mgr.scale_up(1)
    for t in list(mgr._threads):
        t.join(timeout=30)
    assert captured["num_nodes"] == 2
    topo = json.loads(captured["envs"][gang_replica.TOPOLOGY_ENV])
    assert topo == {"hosts": 2, "ici_axes": {"tp": 2}}
    # One gang == one replica row.
    assert len(mgr.replicas) <= 1


# ===================================== mesh rules on the serving path
def _families():
    return [("llama", llama, llama.LlamaConfig.tiny(vocab_size=128)),
            ("mixtral", mixtral, mixtral.MixtralConfig.tiny()),
            ("gemma", gemma, gemma.GemmaConfig.tiny(vocab_size=128))]


def test_cache_specs_tp_sharding_all_families():
    """cache_specs resolves to a TP sharding on the kv_heads dim for
    every family whose head count divides the mesh — and re-points at
    the trailing head_dim axis (matching the packed kv projection's
    sharding, so donation survives) when it doesn't (gemma tiny's
    single KV head)."""
    mesh = mesh_lib.make_mesh({"tp": 2}, devices=jax.devices()[:2])
    rules = mesh_lib.DEFAULT_RULES
    for name, mdl, cfg in _families():
        # One spec per family: the one cache_shardings reads.
        assert not hasattr(mdl, "paged_cache_specs")
        specs = mdl.cache_specs(cfg)
        assert set(specs) == {"k", "v"}
        shardings = gang_replica.cache_shardings(cfg, mesh, rules)
        for key in ("k", "v"):
            spec = shardings[key].spec
            if cfg.n_kv_heads % 2 == 0:
                assert spec == mesh_lib.P(None, None, None, "tp"), \
                    (name, spec)
            else:
                assert spec == mesh_lib.P(None, None, None, None,
                                          "tp"), (name, spec)
        # The raw logical spec still names kv_heads for the divisible
        # case — the fallback is resolution-time, not spec-time.
        assert specs["k"][3] == "kv_heads"
        # Param side: the vocab projection and MLP shard over tp.
        psh = mesh_lib.tree_shardings(mesh, rules,
                                      mdl.param_specs(cfg))
        assert "tp" in str(psh["embed"].spec)


def test_tp_engine_bit_identical_to_single_process(reference_stream):
    """The tensor-parallel engine (params by param_specs, pool by
    cache_specs, tp=2 mesh) reproduces the unsharded row-cache
    reference's token streams BIT-IDENTICALLY — greedy and seeded
    sampling — in f32 (bf16 matches only to bf16 rounding, like any
    resharding)."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=128),
                              dtype=jnp.float32)
    params = llama.init(cfg, jax.random.key(0))
    topo = gang_replica.ReplicaTopology(hosts=1, ici_axes={"tp": 2})
    mesh, rules = gang_replica.build_mesh(topo)
    sparams = gang_replica.shard_params(cfg, params, mesh, rules)

    reqs = [([1, 2, 3, 4, 5], 8, 0.0, 0),
            ([7, 9, 11], 10, 0.8, 123),
            ([4] * 70, 6, 0.0, 0),          # chunked prefill path
            ([5, 6], 8, 1.1, 7)]

    def run(engine):
        out = []
        try:
            handles = [engine.submit(p, max_tokens=mt,
                                     temperature=t, seed=s)
                       for p, mt, t, s in reqs]
            for h in handles:
                out.append(h.result(timeout=600.0))
        finally:
            engine.shutdown()
        return out

    tp = run(decode_engine.DecodeEngine(
        cfg, sparams, slots=2, max_seq=128, mesh=mesh,
        rules=rules).start())
    assert tp == [reference_stream(llama, cfg, params, p, mt,
                                   temperature=t, seed=s, max_seq=128)
                  for p, mt, t, s in reqs]


# ==================================================== 2-process gang e2e
def _spawn_gang(port, env_extra=None, hosts=2, tp=2,
                model="tiny", dtype="float32"):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO)
    env["STPU_GANG_HB_TIMEOUT"] = "2"
    env.update(env_extra or {})
    argv = [sys.executable, "-m", "skypilot_tpu.recipes.serve_llm",
            "--model", model, "--port", str(port),
            "--replica-hosts", str(hosts)]
    if tp > 1:
        argv += ["--tp", str(tp)]
    if dtype:
        argv += ["--dtype", dtype]
    return subprocess.Popen(argv, env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL,
                            start_new_session=True)


def _wait_health(base, timeout=240, want=200):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if _get_code(base + "/health", timeout=5) == want:
            return True
        time.sleep(0.25)
    return False


def _terminate(proc):
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=10)


@pytest.mark.usefixtures("tmp_state_dir")
def test_gang_replica_e2e_parity_failover_and_trace():
    """The acceptance story in one gang session: LB → host 0 → TP
    engine parity, follower kill mid-stream → 503 → whole-gang restart
    → LB recovers, all traced as one tree."""
    from skypilot_tpu.observability import tracing
    from skypilot_tpu.serve import load_balancer as lb_lib
    from skypilot_tpu.serve.load_balancing_policies import (
        RoundRobinPolicy)

    # Single-process references, bit-for-bit: the engine's sampling
    # scheme (fold_in(root, seed), pos) is the contract, so the
    # reference is a plain in-process engine with identical cfg/seed.
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(),
                              dtype=jnp.float32)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    ref_engine = decode_engine.DecodeEngine(
        cfg, params, slots=2, max_seq=128).start()
    try:
        greedy_ref = ref_engine.submit(
            [1, 2, 3, 4], max_tokens=8).result(timeout=600.0)
        sampled_ref = ref_engine.submit(
            [9, 8, 7], max_tokens=8, temperature=0.7,
            seed=42).result(timeout=600.0)
    finally:
        ref_engine.shutdown()

    tracing.arm()
    port = _free_port()
    proc = _spawn_gang(port, env_extra={"STPU_TRACE": "1"})
    lb_port = _free_port()
    policy = RoundRobinPolicy()
    policy.set_ready_replicas([f"http://127.0.0.1:{port}"])
    lb = lb_lib.run_load_balancer(lb_port, policy,
                                  lb_lib.RequestRecorder())
    base = f"http://127.0.0.1:{lb_port}"
    try:
        assert _wait_health(base, timeout=240), \
            "gang replica never became healthy"

        # --- parity through LB → host 0 → TP engine
        _code, out = _post_json(base + "/generate",
                                {"prompt": [1, 2, 3, 4],
                                 "max_tokens": 8})
        assert out["tokens"] == greedy_ref
        _code, out = _post_json(base + "/generate",
                                {"prompt": [9, 8, 7], "max_tokens": 8,
                                 "temperature": 0.7, "seed": 42})
        assert out["tokens"] == sampled_ref

        # --- gang introspection: exactly one replica, two hosts
        gang = json.loads(_get(f"http://127.0.0.1:{port}/gang")[1])
        assert gang["label"] == "2x2"
        follower = [m for m in gang["members"]
                    if m["role"] == "follower"][0]

        # --- kill the follower MID-STREAM
        stream_req = urllib.request.Request(
            base + "/generate",
            data=json.dumps({"prompt": [1, 2, 3], "max_tokens": 64,
                             "stream": True}).encode(),
            headers={"Content-Type": "application/json"})
        resp = urllib.request.urlopen(stream_req, timeout=60)
        assert resp.read(16)            # stream is live
        os.kill(follower["pid"], signal.SIGKILL)
        deadline = time.monotonic() + 30
        flipped = False
        while time.monotonic() < deadline:
            if _get_code(f"http://127.0.0.1:{port}/health",
                         timeout=5) == 503:
                flipped = True
                break
            time.sleep(0.05)
        assert flipped, "/health never flipped to 503 on member death"
        try:
            resp.read()                 # stream ends or truncates
        except Exception:  # noqa: stpu-except — truncation IS the documented mid-stream failure signal
            pass
        resp.close()

        # --- whole-gang supervisor restart recovers the LB path
        assert _wait_health(base, timeout=120), \
            "gang never recovered after whole-gang restart"
        deadline = time.monotonic() + 60
        out = None
        while time.monotonic() < deadline:
            try:
                _code, out = _post_json(
                    base + "/generate",
                    {"prompt": [1, 2, 3, 4], "max_tokens": 8})
                break
            except (urllib.error.URLError, ConnectionError,
                    OSError):
                time.sleep(0.5)
        assert out is not None and out["tokens"] == greedy_ref, \
            "post-restart output diverged from the single-process " \
            "engine"
        gang = json.loads(_get(f"http://127.0.0.1:{port}/gang")[1])
        assert gang["restarts"] >= 1
        new_follower = [m for m in gang["members"]
                        if m["role"] == "follower"][0]
        assert new_follower["pid"] != follower["pid"]

        # --- one trace tree: lb.request → replica.generate → gang.run
        time.sleep(0.5)                 # let the sinks flush
        rows = [r for r in tracing.read()
                if r.get("name") == "lb.request"
                and r.get("attrs", {}).get("path") == "/generate"]
        assert rows, "no lb.request roots recorded"
        found = False
        for row in rows:
            for root in tracing.assemble(row["trace_id"]):
                gens = [c for c in root["children"]
                        if c["span"]["name"] == "replica.generate"]
                for gen in gens:
                    if any(g["span"]["name"] == "gang.run"
                           for g in gen["children"]):
                        found = True
        assert found, ("lb.request → replica.generate → gang.run "
                       "never assembled into one tree")
    finally:
        tracing.disarm()
        lb.shutdown()
        _terminate(proc)


# ======================================================== observability
def test_topology_info_gauge_in_replica_metrics():
    from skypilot_tpu.observability import metrics
    from skypilot_tpu.recipes import serve_llm

    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    ready = threading.Event()
    httpd = serve_llm.serve(
        cfg, params, 0, ready_event=ready, engine_slots=1,
        topology=gang_replica.ReplicaTopology(hosts=2,
                                              ici_axes={"tp": 4}))
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        assert ready.wait(timeout=300)
        port = httpd.server_address[1]
        _status, body = _get(f"http://127.0.0.1:{port}/metrics")
        text = body.decode()
        assert ('stpu_replica_topology_info{hosts="2",tp="4"} 1'
                in text), text[-2000:]
    finally:
        httpd.shutdown()
        httpd.engine.shutdown()
    del metrics


def test_loadgen_report_carries_replica_topology(tmp_path):
    """The loadgen report attributes the run to the serving topology
    scraped from /metrics (stpu_replica_topology_info riding the LB
    merge), so an SLO regression next to a topology change reads as
    caused by it."""
    import http.server
    import socketserver

    from skypilot_tpu.benchmark import loadgen

    class _Metrics(http.server.BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            body = (
                "# HELP stpu_replica_topology_info topo\n"
                "# TYPE stpu_replica_topology_info gauge\n"
                'stpu_replica_topology_info{hosts="2",tp="2"} 1\n'
            ).encode()
            self.send_response(200)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    server = socketserver.TCPServer(("127.0.0.1", 0), _Metrics)
    server.allow_reuse_address = True
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        url = f"http://127.0.0.1:{server.server_address[1]}"
        scraper = loadgen.MetricsScraper(
            url, interval=10.0, series_path=tmp_path / "m.jsonl")
        assert scraper.scrape_once() is not None
        sets = scraper.label_sets("stpu_replica_topology_info")
        assert sets == [{"hosts": "2", "tp": "2"}]
    finally:
        server.shutdown()
