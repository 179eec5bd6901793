"""Multi-host sharded serving: one replica = one gang-scheduled slice.

Serving was the one layer still ignoring the framework's reason to
exist: replicas were single-process, capping servable model size at one
chip's HBM, while training already had `parallel/mesh.py` sharding,
`agent/gang_exec.py` gang launch, and the `jax.distributed` rank/env
contract. This module threads that stack through serving:

  * ``ReplicaTopology`` — the per-replica slice shape declared in the
    service YAML (``replica_topology: {hosts: N, ici_axes: {tp: K}}``),
    validated by utils/schemas.py and carried replica-side in the
    ``STPU_REPLICA_TOPOLOGY`` env var (stamped by replica_managers next
    to SKYPILOT_SERVE_REPLICA_PORT);
  * mesh/sharding helpers — the serving instantiation of
    parallel/mesh.py: params sharded by ``param_specs`` and the KV
    cache by ``cache_specs`` under ``DEFAULT_RULES`` (heads / kv_heads
    / mlp / vocab over the ``tp`` axis). The decode engine's jitted
    entry points are untouched: GSPMD partitions them from the operand
    shardings and donation still aliases the cache in place;
  * ``GangLeader`` — host 0's side of the gang: accepts every follower
    host's connection (rank/env contract: SKYPILOT_NODE_RANK,
    SKYPILOT_NODE_IPS), broadcasts admitted requests + sampling seeds
    so every host executes identical jitted steps, monitors membership
    (a dead follower flips the replica /health to 503 — no zombie READY
    gangs), and treats the gang as ONE unit on failure: whole-gang
    restart (every member's engine rebuilt, self-spawned followers
    respawned) under the same capped-fast-failure ladder as
    EngineSupervisor;
  * ``follower_serve`` — the lockstep loop non-zero hosts run instead
    of HTTP: build the same sharded engine, mirror every broadcast
    submission, heartbeat, and die with the leader (socket EOF) so
    scale-down / crash-restart never orphans a follower process.

Failure semantics by layer: inside the replica, the leader's monitor
flips /health and drives the whole-gang restart; outside it, the gang
driver's slice-atomic cancel (first host failure kills all hosts) and
the replica manager's probe path replace the entire gang as one
replica — the LB / controller / autoscaler never see partial capacity.

On ICI-federated platforms (real TPU slices) the mesh spans every
host's chips and the broadcast mirrors submissions into one SPMD
program; on non-federated platforms (the CPU local provider, forced
host device count) each host builds the same local mesh and replays
the same program — the contract the hermetic tests pin bit-identically.

jax is imported lazily: the topology dataclass is control-plane (the
service spec and replica manager import it without pulling the compute
stack).
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import signal
import socket
import subprocess
import threading
import time
from typing import Any, Callable, Dict, List, Mapping, Optional

from skypilot_tpu.observability import events
from skypilot_tpu.observability import metrics
from skypilot_tpu.observability import tracing
from skypilot_tpu.utils import fault_injection

# Env var carrying the replica's topology JSON from the replica manager
# to every host of the gang (next to SKYPILOT_SERVE_REPLICA_PORT).
TOPOLOGY_ENV = "STPU_REPLICA_TOPOLOGY"
# Where followers find the leader's gang channel. Gang-launched hosts
# derive it (head ip from SKYPILOT_NODE_IPS + replica port + OFFSET);
# self-spawned dev followers get it stamped explicitly.
GANG_ADDR_ENV = "STPU_GANG_SERVE_ADDR"
# The gang channel binds the replica's serving port + this offset on
# host 0 (the provisioner opened the serving port; +1 rides the same
# contiguous range real clouds open for serve).
GANG_PORT_OFFSET = 1

HEARTBEAT_SECONDS = float(os.environ.get("STPU_GANG_HB_SECONDS", "0.5"))
HEARTBEAT_TIMEOUT_SECONDS = float(
    os.environ.get("STPU_GANG_HB_TIMEOUT", "5"))
# Whole-gang restarts: same ladder shape as EngineSupervisor — this
# many consecutive FAST gang deaths (member died within
# fast_failure_seconds of the gang coming up) leave the replica
# permanently unhealthy so the probe path replaces the whole gang.
MAX_GANG_RESTARTS = int(os.environ.get("STPU_GANG_MAX_RESTARTS", "3"))

_MEMBERS_ALIVE = metrics.gauge(
    "stpu_gang_members_alive",
    "Live hosts in this replica's serving gang (leader included).")
_GANG_RESTARTS = metrics.counter(
    "stpu_gang_restarts_total",
    "Whole-gang restarts after a member death.")
_GANG_UP = metrics.gauge(
    "stpu_gang_up",
    "1 while every gang member is alive; 0 while degraded/restarting.")


class GangError(RuntimeError):
    """Gang membership / topology failure."""


# ------------------------------------------------------------- topology
@dataclasses.dataclass(frozen=True)
class ReplicaTopology:
    """Per-replica slice shape: ``hosts`` gang members, ``ici_axes``
    named mesh axes over the slice's chips (serving uses ``tp``)."""

    hosts: int = 1
    ici_axes: Mapping[str, int] = dataclasses.field(
        default_factory=dict)

    @property
    def tp(self) -> int:
        """Total model-parallel degree (product of the ICI axes)."""
        return int(math.prod(self.ici_axes.values())) or 1

    @property
    def is_sharded(self) -> bool:
        return self.hosts > 1 or self.tp > 1

    def label(self) -> str:
        """``<hosts>x<tp>`` — the topology tag metrics / loadgen
        reports attribute SLO shifts to."""
        return f"{self.hosts}x{self.tp}"

    @classmethod
    def from_config(cls, config: Optional[Mapping[str, Any]]
                    ) -> Optional["ReplicaTopology"]:
        """Parse + semantically validate the ``replica_topology`` YAML
        block (schema-level shape checks live in utils/schemas.py)."""
        if not config:
            return None
        hosts = int(config.get("hosts", 1))
        axes = {str(k): int(v)
                for k, v in (config.get("ici_axes") or {}).items()}
        if hosts < 1:
            raise GangError(f"replica_topology.hosts must be >= 1, "
                            f"got {hosts}")
        for name, size in axes.items():
            if size < 1:
                raise GangError(
                    f"replica_topology.ici_axes.{name} must be >= 1, "
                    f"got {size}")
        return cls(hosts=hosts, ici_axes=axes)

    def to_config(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {"hosts": self.hosts}
        if self.ici_axes:
            out["ici_axes"] = dict(self.ici_axes)
        return out

    @classmethod
    def from_env(cls) -> Optional["ReplicaTopology"]:
        raw = os.environ.get(TOPOLOGY_ENV)
        if not raw:
            return None
        try:
            return cls.from_config(json.loads(raw))
        except (ValueError, TypeError) as e:
            raise GangError(
                f"invalid {TOPOLOGY_ENV} JSON: {e}") from e

    def to_env_json(self) -> str:
        return json.dumps(self.to_config())


# -------------------------------------------------------- mesh building
def build_mesh(topology: ReplicaTopology):
    """(mesh, rules) for the serving topology, or (None, None) for the
    unsharded tp=1 case.

    On an ICI-federated runtime (real slice after
    ``jax.distributed.initialize``) the mesh spans every host's chips;
    on non-federated platforms each host lays the SAME axes over its
    first ``tp`` local devices — the identical-program half of the
    lockstep contract."""
    import jax
    from skypilot_tpu.parallel import mesh as mesh_lib
    if topology.tp <= 1:
        return None, None
    devices = jax.devices()
    if len(devices) < topology.tp:
        raise GangError(
            f"replica_topology needs {topology.tp} devices for "
            f"ici_axes {dict(topology.ici_axes)}, but only "
            f"{len(devices)} are visible (on CPU, force them with "
            f"XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{topology.tp})")
    mesh = mesh_lib.make_mesh(dict(topology.ici_axes),
                              devices=devices[:topology.tp])
    return mesh, mesh_lib.DEFAULT_RULES


def shard_params(cfg, params, mesh, rules):
    """Place params by their logical param_specs under (mesh, rules).
    An int8 quantize_params tree (detected by its embed_scale leaf)
    places by the quantized spec tree — codes shard like the weights
    they encode, scales ride their output channel's shard."""
    import jax
    from skypilot_tpu.models import model_api
    from skypilot_tpu.parallel import mesh as mesh_lib
    api = model_api(cfg)
    specs = api.param_specs(cfg, quantized="embed_scale" in params)
    return jax.device_put(
        params, mesh_lib.tree_shardings(mesh, rules, specs))


def cache_shardings(cfg, mesh, rules):
    """NamedShardings for the KV cache under (mesh, rules).

    The ONE place the kv_heads divisibility check lives: a family whose
    n_kv_heads does not divide the resolved tp axis size (gemma's
    single KV head) shards the trailing head_dim axis instead of
    erroring. That is not just a capacity fallback — the kv projection
    itself is sharded over the packed ``kv_heads_x_dim`` param axis, so
    GSPMD propagates exactly that head_dim sharding onto the updated
    cache; matching it keeps the donated input aliasable (a replicated
    cache would silently drop the donation and double the KV cache in
    HBM — pinned by tests/test_sharded_replica.py). Only when head_dim
    does not divide either does the cache fall back to replicated.

    The returned dict also carries ``k_scale``/``v_scale`` entries for
    the int8 paged pool's per-(layer, block, kv_head) scale arrays —
    callers with a bf16 cache just ignore them (the engine filters by
    its cache's keys). A scale array ENDS in kv_heads, so the head_dim
    fallback cannot re-point its trailing axis; scales replicate
    instead, which is byte-trivial (4 bytes per block-head against
    block_tokens * head_dim code bytes)."""
    from jax.sharding import NamedSharding, PartitionSpec
    from skypilot_tpu.models import model_api
    api = model_api(cfg)
    specs = dict(api.cache_specs(cfg))
    specs.setdefault("k_scale", ("layers", None, "kv_heads"))
    specs.setdefault("v_scale", ("layers", None, "kv_heads"))

    def fix(spec: tuple):
        tp = rules.axis_size("kv_heads", mesh)
        if "kv_heads" not in spec or cfg.n_kv_heads % tp == 0:
            return rules.sharding(spec, mesh)
        resolved = [None] * len(spec)
        if (spec[-1] != "kv_heads" and
                int(getattr(cfg, "head_dim", 0)) % tp == 0):
            resolved[-1] = rules.resolve_axis("kv_heads", mesh)
        return NamedSharding(mesh, PartitionSpec(*resolved))

    return {name: fix(spec) for name, spec in specs.items()}


# ------------------------------------------------------- wire protocol
def _send_line(sock_file, msg: Dict[str, Any]) -> None:
    sock_file.write((json.dumps(msg) + "\n").encode())
    sock_file.flush()


class _Member:
    __slots__ = ("rank", "pid", "sock", "wfile", "last_hb", "alive")

    def __init__(self, rank: int, pid: int, sock, wfile):
        self.rank = rank
        self.pid = pid
        self.sock = sock
        self.wfile = wfile
        self.last_hb = time.monotonic()
        self.alive = True


class GangLeader:
    """Host 0's gang coordination: membership, broadcast, restart.

    ``spawn`` (optional) is a ``rank -> subprocess.Popen`` callable for
    the self-spawned dev/test gang (`serve_llm --replica-hosts N` on
    one machine); gang-launched followers are other machines'
    processes, owned by the gang driver — there the leader only flips
    health and the slice-atomic cancel + replica-manager probe path
    replace the whole gang."""

    def __init__(self, topology: ReplicaTopology, *, port: int = 0,
                 spawn: Optional[Callable[[int], Any]] = None,
                 engine_reset: Optional[Callable[[], None]] = None,
                 hb_timeout: Optional[float] = None,
                 max_restarts: Optional[int] = None,
                 fast_failure_seconds: float = 30.0,
                 backoff_base: float = 0.5,
                 kv_config: Optional[Dict[str, Any]] = None):
        self.topology = topology
        # KV-cache geometry the leader's engine serves with (paged /
        # pool blocks / block size). Stamped into every follower's
        # welcome: under paging, each host runs its OWN block pool and
        # mirrors admissions, so the pools must be sized identically
        # or admission backpressure diverges across the gang. A
        # follower that disagrees dies loudly at join instead of
        # silently falling out of lockstep mid-traffic.
        self.kv_config = dict(kv_config) if kv_config else None
        self._expected = max(topology.hosts - 1, 0)
        self._spawn = spawn
        self._engine_reset = engine_reset
        self._hb_timeout = (HEARTBEAT_TIMEOUT_SECONDS
                            if hb_timeout is None else float(hb_timeout))
        self.max_restarts = (MAX_GANG_RESTARTS if max_restarts is None
                             else int(max_restarts))
        self._fast = float(fast_failure_seconds)
        self._backoff_base = float(backoff_base)
        self._lock = threading.Lock()
        self._send_lock = threading.Lock()  # serialize broadcasts
        self._members: Dict[int, _Member] = {}
        self._procs: Dict[int, Any] = {}
        # The watchdog only judges membership once the gang has fully
        # formed — followers joining one by one at startup is warm-up,
        # not degradation.
        self._armed = False
        self._degraded = False
        self._draining = False
        self._closed = False
        self.permanently_down = False
        self.restarts = 0
        self._consecutive = 0
        self._up_since = time.monotonic()
        self._listener = socket.socket(socket.AF_INET,
                                       socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET,
                                  socket.SO_REUSEADDR, 1)
        self._listener.bind(("0.0.0.0", int(port)))
        self._listener.listen(max(self._expected, 1))
        self.port = self._listener.getsockname()[1]
        threading.Thread(target=self._accept_loop, daemon=True,
                         name="gang-accept").start()
        self._monitor_thread = threading.Thread(
            target=self._monitor, daemon=True, name="gang-monitor")
        self._monitor_thread.start()
        _GANG_UP.set(1)

    # ---------------------------------------------------------- public
    def set_engine_reset(self, fn: Callable[[], None]) -> None:
        """Late-bind the host-0 engine rebuild hook (the engine
        supervisor is constructed after the leader)."""
        self._engine_reset = fn

    def start_followers(self) -> None:
        """Self-spawn mode: launch every follower process."""
        if self._spawn is None:
            return
        for rank in range(1, self.topology.hosts):
            self._procs[rank] = self._spawn(rank)

    def wait_ready(self, timeout: float = 120.0) -> bool:
        """Block until every expected follower has joined."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                if (len([m for m in self._members.values() if m.alive])
                        >= self._expected):
                    self._up_since = time.monotonic()
                    self._armed = True
                    return True
            if self._closed:
                return False
            time.sleep(0.05)
        return False

    def healthy(self) -> bool:
        """True iff every gang member is alive RIGHT NOW — wired into
        host 0's /health so a dead follower can never hide behind a
        READY replica (the zombie-gang hole)."""
        if self.permanently_down or self._closed:
            return False
        if self._degraded:
            return False
        with self._lock:
            alive = sum(1 for m in self._members.values() if m.alive)
        return alive >= self._expected

    def members_info(self) -> List[Dict[str, Any]]:
        out = [{"rank": 0, "pid": os.getpid(), "alive": True,
                "role": "leader"}]
        with self._lock:
            for rank in sorted(self._members):
                m = self._members[rank]
                out.append({"rank": m.rank, "pid": m.pid,
                            "alive": m.alive, "role": "follower"})
        return out

    def broadcast_generate(self, prompt, max_tokens: int,
                           temperature: float, seed: int,
                           trace=None, resume=None) -> None:
        """Mirror one admitted request (+ its sampling seed) to every
        follower so each host executes the identical jitted submission.
        A resume admission (prior-emitted tokens re-submitted after a
        mid-stream failure) rides the same broadcast, so followers
        prefill the identical extended prompt and stay in lockstep.
        Recorded as the request's ``gang.run`` hop when traced."""
        t0 = time.perf_counter()
        msg = {"op": "generate",
               "prompt": [int(t) for t in prompt],
               "max_tokens": int(max_tokens),
               "temperature": float(temperature),
               "seed": int(seed)}
        if resume:
            msg["resume"] = [int(t) for t in resume]
        if trace is not None:
            # The request's identity rides the broadcast too: traced
            # runs parent the mirrored submissions under the same
            # trace, and reqlog-only runs (sampled flag 00 — tracing
            # guards stay cold) key follower-side accounting by the
            # same request id.
            ctx = tracing.format_ctx(trace)
            if ctx:
                msg["trace"] = ctx
        self._broadcast(msg)
        if tracing.ENABLED and trace is not None and trace.sampled:
            tracing.record_span(
                "gang.run", "gang", trace, start_mono=t0,
                attrs={"hosts": self.topology.hosts,
                       "topology": self.topology.label()})

    def drain(self) -> None:
        """Propagate a replica drain to every follower: their engines
        stop admitting and finish in-flight work, mirroring host 0."""
        self._draining = True
        self._broadcast({"op": "drain"})

    def broadcast_restart(self) -> None:
        """Host 0's engine is being rebuilt (supervisor crash-restart):
        every follower rebuilds too, or the gang falls out of
        lockstep."""
        self._broadcast({"op": "restart"})

    def shutdown(self) -> None:
        """Tear the gang down: followers get an explicit shutdown (and
        self-spawned ones a SIGTERM + reap) — scale-down must never
        orphan a follower process."""
        self._closed = True
        self._broadcast({"op": "shutdown"})
        with self._lock:
            members = list(self._members.values())
            self._members.clear()
        for m in members:
            try:
                m.sock.close()
            except OSError:
                pass
        try:
            self._listener.close()
        except OSError:
            pass
        for proc in self._procs.values():
            if proc.poll() is None:
                try:
                    proc.terminate()
                except OSError:
                    pass
        deadline = time.monotonic() + 10.0
        for proc in self._procs.values():
            try:
                proc.wait(timeout=max(deadline - time.monotonic(),
                                      0.1))
            except subprocess.TimeoutExpired:
                proc.kill()
        _GANG_UP.set(0)
        _MEMBERS_ALIVE.set(0)

    # -------------------------------------------------------- internals
    def _broadcast(self, msg: Dict[str, Any]) -> None:
        with self._lock:
            members = [m for m in self._members.values() if m.alive]
        with self._send_lock:
            for m in members:
                try:
                    _send_line(m.wfile, msg)
                except (OSError, ValueError):
                    m.alive = False

    def _accept_loop(self) -> None:
        while not self._closed:
            try:
                conn, _addr = self._listener.accept()
            except OSError:
                return
            threading.Thread(target=self._serve_member,
                             args=(conn,), daemon=True).start()

    def _serve_member(self, conn) -> None:
        conn.settimeout(30.0)
        rfile = conn.makefile("rb")
        wfile = conn.makefile("wb")
        try:
            hello = json.loads(rfile.readline() or b"{}")
            if hello.get("op") != "hello":
                conn.close()
                return
        except (OSError, ValueError):
            conn.close()
            return
        member = _Member(int(hello.get("rank", -1)),
                         int(hello.get("pid", 0)), conn, wfile)
        # Welcome goes out BEFORE the member is registered: broadcasts
        # only iterate registered members, so nothing else can write
        # this buffered wfile yet — registering first would let a
        # concurrent broadcast interleave bytes mid-welcome and
        # corrupt the line protocol.
        welcome: Dict[str, Any] = {"op": "welcome",
                                   "hosts": self.topology.hosts}
        if self.kv_config is not None:
            welcome["kv"] = self.kv_config
        try:
            _send_line(wfile, welcome)
        except OSError:
            conn.close()
            return
        with self._lock:
            self._members[member.rank] = member
            alive = sum(1 for m in self._members.values() if m.alive)
        _MEMBERS_ALIVE.set(alive + 1)
        events.emit("gang_replica", f"rank-{member.rank}", "joined",
                    pid=member.pid, hosts=self.topology.hosts)
        conn.settimeout(self._hb_timeout)
        while not self._closed:
            try:
                line = rfile.readline()
            except (OSError, ValueError):
                break
            if not line:
                break       # EOF: the follower process died
            try:
                msg = json.loads(line)
            except ValueError:
                continue
            if msg.get("op") == "hb":
                member.last_hb = time.monotonic()
        member.alive = False

    def _alive_count(self) -> int:
        now = time.monotonic()
        with self._lock:
            n = 0
            for m in self._members.values():
                if m.alive and now - m.last_hb > self._hb_timeout:
                    m.alive = False    # hung, not just exited
                if m.alive:
                    n += 1
        return n

    def _monitor(self) -> None:
        """Membership watchdog: a member death flips health (503) and —
        when this leader owns the follower processes — drives the
        whole-gang restart."""
        while not self._closed:
            time.sleep(0.1)
            if self._closed or self._draining:
                return
            if not self._armed:
                continue
            alive = self._alive_count()
            _MEMBERS_ALIVE.set(alive + 1)
            dead_procs = [r for r, p in self._procs.items()
                          if p.poll() is not None]
            if alive >= self._expected and not dead_procs:
                continue
            # Degraded: /health goes 503 FIRST, then the restart path.
            self._degraded = True
            _GANG_UP.set(0)
            fast = (time.monotonic() - self._up_since < self._fast)
            self._consecutive = self._consecutive + 1 if fast else 1
            events.emit("gang_replica", "gang", "member_lost",
                        alive=alive, expected=self._expected,
                        consecutive=self._consecutive)
            if self._consecutive > self.max_restarts:
                # Deterministic gang crash loop: stay down for good so
                # the probe path replaces the whole replica.
                self.permanently_down = True
                events.emit("gang_replica", "gang", "gang_down",
                            restarts=self.restarts)
                return
            if self._spawn is None:
                # Gang-launched: the gang driver's slice-atomic cancel
                # + the replica manager restart the gang from outside;
                # stay degraded until members rejoin (a restarted
                # member reconnecting restores health below).
                self._await_rejoin()
                continue
            self._restart_gang()

    def _await_rejoin(self) -> None:
        while not self._closed and not self._draining:
            if self._alive_count() >= self._expected:
                if self._engine_reset is not None:
                    try:
                        self._engine_reset()
                    except Exception as e:  # noqa: BLE001 — a failed
                        # rebuild must not kill the monitor thread: the
                        # watchdog IS the zombie-READY guard, and the
                        # still-degraded gang retries next tick.
                        events.emit("gang_replica", "gang",
                                    "engine_reset_failed",
                                    error=repr(e))
                        time.sleep(0.5)
                        continue
                self._degraded = False
                self._up_since = time.monotonic()
                _GANG_UP.set(1)
                events.emit("gang_replica", "gang", "recovered",
                            restarts=self.restarts)
                return
            time.sleep(0.1)

    def _restart_gang(self) -> None:
        """Whole-gang restart: every member is torn down and respawned,
        and host 0's engine is rebuilt — membership loss invalidates
        lockstep state on every host, so a partial restart would serve
        from desynchronized caches."""
        delay = min(self._backoff_base * 2 ** (self._consecutive - 1),
                    30.0)
        deadline = time.monotonic() + delay
        while time.monotonic() < deadline:
            if self._closed or self._draining:
                return
            time.sleep(0.05)
        with self._lock:
            members = list(self._members.values())
            self._members.clear()
        for m in members:
            try:
                m.sock.close()
            except OSError:
                pass
        for rank, proc in list(self._procs.items()):
            if proc.poll() is None:
                try:
                    proc.terminate()
                    proc.wait(timeout=10.0)
                except (OSError, subprocess.TimeoutExpired):
                    proc.kill()
        if self._engine_reset is not None:
            try:
                self._engine_reset()
            except Exception as e:  # noqa: BLE001 — a failed engine
                # rebuild counts as another fast failure next tick, not
                # a dead monitor thread.
                events.emit("gang_replica", "gang",
                            "engine_reset_failed", error=repr(e))
        for rank in range(1, self.topology.hosts):
            self._procs[rank] = self._spawn(rank)
        if self.wait_ready(timeout=60.0):
            self._degraded = False
            self.restarts += 1
            _GANG_RESTARTS.inc()
            _GANG_UP.set(1)
            events.emit("gang_replica", "gang", "restarted",
                        attempt=self._consecutive)
        # else: next monitor tick counts another failure.


# ------------------------------------------------------------ follower
def follower_addr(port: int) -> str:
    """Where this (non-zero-rank) host finds the leader's gang channel:
    explicit STPU_GANG_SERVE_ADDR (self-spawn), else head host ip from
    the gang env contract + the serving port + offset."""
    explicit = os.environ.get(GANG_ADDR_ENV)
    if explicit:
        return explicit
    from skypilot_tpu.agent import constants
    ips = (os.environ.get(constants.NODE_IPS) or "").splitlines()
    if not ips:
        raise GangError(
            f"no {GANG_ADDR_ENV} and no {constants.NODE_IPS}: a "
            "follower host needs the gang env contract to find host 0")
    return f"{ips[0]}:{int(port) + GANG_PORT_OFFSET}"


def _drain_request(req) -> None:
    try:
        for _ in req.stream(timeout=600.0):
            pass
    except Exception:  # noqa: stpu-except — follower mirrors discard tokens; request-level failures surface on host 0
        pass


def follower_serve(engine_factory: Callable[[], Any], topology:
                   ReplicaTopology, addr: str, rank: int,
                   connect_timeout: float = 60.0,
                   kv_config: Optional[Dict[str, Any]] = None) -> int:
    """The lockstep loop a non-zero host runs instead of HTTP.

    Connects to the leader's gang channel, heartbeats, and mirrors
    every broadcast: ``generate`` submits into the local sharded
    engine (tokens discarded — host 0 owns the client stream),
    ``drain`` stops admissions, ``restart`` rebuilds the engine with
    fresh state, ``shutdown``/EOF exits — the leader going away takes
    every follower with it, so no scale-down or crash-restart can
    orphan this process. Returns the process exit code.

    ``kv_config`` is this host's KV-cache geometry (paged / pool
    blocks / block size): when both sides declare one, the leader's
    welcome is cross-checked and a mismatch kills the follower
    IMMEDIATELY — under paging each host mirrors admissions into its
    own block pool, so differently-sized pools would make admission
    backpressure (and therefore slot state) silently diverge across
    the gang. Token output is placement-independent (attention reads
    through the table), but capacity decisions are not."""
    host, port_s = addr.rsplit(":", 1)
    deadline = time.monotonic() + connect_timeout
    sock = None
    while time.monotonic() < deadline:
        try:
            sock = socket.create_connection((host, int(port_s)),
                                            timeout=5.0)
            break
        except OSError:
            time.sleep(0.2)
    if sock is None:
        raise GangError(f"follower rank {rank}: leader at {addr} "
                        f"unreachable for {connect_timeout:.0f}s")
    sock.settimeout(None)
    rfile = sock.makefile("rb")
    wfile = sock.makefile("wb")
    _send_line(wfile, {"op": "hello", "rank": rank,
                       "pid": os.getpid()})
    engine = engine_factory()
    engine.start()
    stop = threading.Event()

    # SIGTERM (teardown / gang cancel) must drain through the same
    # clean-exit path as a leader shutdown. Setting the flag alone is
    # not enough: the main loop blocks in readline() and PEP 475
    # restarts the syscall after the handler returns — shutting the
    # socket down makes the restarted read return EOF/EBADF so the
    # loop actually exits.
    def _on_term(*_a):
        stop.set()
        try:
            sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
    if threading.current_thread() is threading.main_thread():
        # Signal handlers are main-thread-only; an in-process follower
        # (tests) is torn down by leader EOF instead.
        signal.signal(signal.SIGTERM, _on_term)

    def heartbeat() -> None:
        while not stop.is_set():
            try:
                _send_line(wfile, {"op": "hb", "rank": rank})
            except (OSError, ValueError):
                stop.set()
                return
            stop.wait(HEARTBEAT_SECONDS)

    hb = threading.Thread(target=heartbeat, daemon=True,
                          name="gang-heartbeat")
    hb.start()
    events.emit("gang_replica", f"rank-{rank}", "follower_up",
                leader=addr)
    rc = 0
    try:
        while not stop.is_set():
            try:
                line = rfile.readline()
            except (OSError, ValueError):
                break       # socket shut down (SIGTERM) or torn
            if not line:
                break           # leader gone: die with the gang
            try:
                msg = json.loads(line)
            except ValueError:
                continue
            op = msg.get("op")
            if op == "welcome":
                leader_kv = msg.get("kv")
                if (leader_kv is not None and kv_config is not None
                        and dict(leader_kv) != dict(kv_config)):
                    events.emit("gang_replica", f"rank-{rank}",
                                "kv_config_mismatch",
                                leader=leader_kv, local=dict(kv_config))
                    rc = 1
                    break
                continue
            # Deterministic follower chaos (tests): the same seam name
            # host_wrapper fires post-barrier, so one STPU_FAULTS
            # grammar kills a gang member whether it came up through
            # the gang driver or the self-spawned dev gang.
            if fault_injection.ENABLED:
                fault_injection.fire("gang.host", rank=rank, op=op)
            if op == "generate":
                try:
                    req = engine.submit(
                        msg["prompt"],
                        max_tokens=msg["max_tokens"],
                        temperature=msg.get("temperature", 0.0),
                        seed=msg.get("seed", 0),
                        trace=tracing.parse_ctx(msg.get("trace")),
                        resume=msg.get("resume"))
                except Exception:  # noqa: stpu-except — the leader's own submit failed identically and answered the client; the mirror must not die over it
                    continue
                threading.Thread(target=_drain_request, args=(req,),
                                 daemon=True).start()
            elif op == "drain":
                engine.drain()
            elif op == "restart":
                engine.shutdown()
                engine = engine_factory()
                engine.start()
            elif op == "shutdown":
                break
    finally:
        stop.set()
        engine.shutdown()
        try:
            sock.close()
        except OSError:
            pass
        events.emit("gang_replica", f"rank-{rank}", "follower_exit",
                    rc=rc)
    return rc
