"""The STPU_* environment-variable contract — one registry, one truth.

Every ``STPU_*`` knob the framework reads is declared here with its
default and a one-line doc. The ``stpu-env`` analyzer
(``analysis/rules_env.py``) statically cross-checks every
``os.environ``/``os.getenv`` read in ``skypilot_tpu/`` against this
table: an unregistered read fails, and a read whose inline default
literal disagrees with the registered default fails — the config-drift
failure mode where two layers parse the same knob differently.

``stpu check --env-table`` renders the registry as the markdown knob
table embedded in docs/static-analysis.md (a tier-1 test keeps the doc
byte-identical to :func:`render_markdown_table`, so it can never
drift).

Stdlib-only and import-light: the analyzer and the CLI both import it,
and neither wants jax.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

PREFIX = "STPU_"


@dataclasses.dataclass(frozen=True)
class EnvKnob:
    name: str
    # The default literal as it appears at read sites (``None`` = the
    # knob is unset-sensitive: code branches on presence, not value).
    default: Optional[str]
    doc: str


def _k(name: str, default: Optional[str], doc: str) -> EnvKnob:
    if not name.startswith(PREFIX):
        raise ValueError(f"env knob {name!r} must start with {PREFIX}")
    if not doc.strip():
        raise ValueError(f"env knob {name!r} needs a doc line")
    return EnvKnob(name, default, doc)


_KNOBS = (
    # ------------------------------------------------ client state
    _k("STPU_HOME", "~/.stpu",
       "Client state root (utils/paths.py). Controllers export the "
       "expanded form $HOME/.stpu — same directory after expanduser."),
    _k("STPU_SSH_CONFIG", "~/.ssh/config",
       "SSH config parsed for cluster host aliases."),
    _k("STPU_BUCKET_ROOT", None,
       "Global local-bucket namespace root; controllers export "
       "$STPU_HOME/buckets so head and client resolve one namespace."),
    _k("STPU_TIMELINE_FILE", None,
       "Write a Chrome-trace timeline of CLI phases to this path."),
    # ------------------------------------------------ observability
    _k("STPU_RUN_ID", None,
       "Run id correlating lifecycle events CLI -> gang driver -> "
       "hosts; auto-generated and exported when unset."),
    _k("STPU_DISABLE_EVENTS", "0",
       "\"1\" disables the JSONL lifecycle event log."),
    _k("STPU_TRACE", "0",
       "\"1\" arms distributed tracing in this process and children."),
    _k("STPU_TRACE_SAMPLE", "1",
       "Root-span sampling rate in [0, 1]; children inherit so traces "
       "are whole-or-absent."),
    _k("STPU_TRACE_CTX", None,
       "Serialized parent span context stamped into child envs "
       "(trace32-span16-flags)."),
    _k("STPU_STEPSTATS", "0",
       "\"1\" arms per-engine-step performance telemetry (step ring, "
       "/perf phase breakdown, flight-recorder context)."),
    _k("STPU_STEPSTATS_RING", "1024",
       "Step-ring capacity in records (the window /perf aggregates "
       "over and the flight recorder dumps)."),
    _k("STPU_STEPSTATS_SYNC_EVERY", "0",
       "Sample a timed block_until_ready every N decode steps to "
       "split dispatch vs device time (0 disables; the only "
       "sanctioned sync on the serve hot path)."),
    _k("STPU_REQLOG", "0",
       "\"1\" arms the wide-event per-request analytics log "
       "(requests.jsonl: one joined LB+engine record per request)."),
    _k("STPU_REQLOG_SAMPLE", "1",
       "Request-log keep rate in [0, 1] for SUCCESSFUL requests; "
       "errors, resumed streams and slow requests are always kept."),
    _k("STPU_REQLOG_SLOW_TTFT", "1.0",
       "TTFT seconds at or above which a request counts as slow and "
       "bypasses request-log sampling."),
    _k("STPU_REQLOG_SLOW_E2E", "10.0",
       "End-to-end seconds at or above which a request counts as slow "
       "and bypasses request-log sampling."),
    _k("STPU_DISABLE_USAGE_COLLECTION", "0",
       "\"1\" disables usage reporting (wins over configured sinks)."),
    # ------------------------------------------------ fleet telemetry
    _k("STPU_FLEET", "1",
       "\"0\" disarms the controller-resident fleet telemetry "
       "collector (no store, no SLO monitor, /fleet answers 503)."),
    _k("STPU_FLEET_COLLECT_SECONDS", "0",
       "Fleet collector scrape period, seconds (0 = follow the "
       "controller tick)."),
    _k("STPU_FLEET_RAW_SECONDS", "10",
       "Fleet store raw-tier bucket width, seconds."),
    _k("STPU_FLEET_RAW_RETENTION", "900",
       "Fleet store raw-tier retention, seconds; older points "
       "downsample into the rollup tier."),
    _k("STPU_FLEET_ROLLUP_SECONDS", "60",
       "Fleet store rollup-tier bucket width, seconds."),
    _k("STPU_FLEET_ROLLUP_RETENTION", "86400",
       "Fleet store rollup-tier retention, seconds (the telemetry "
       "horizon)."),
    _k("STPU_SLO_FAST_WINDOW", "300",
       "SLO burn-rate fast window, seconds (page-worthy burn)."),
    _k("STPU_SLO_SLOW_WINDOW", "3600",
       "SLO burn-rate slow window, seconds (sustained burn; breach "
       "needs BOTH windows over the threshold)."),
    _k("STPU_SLO_BURN_THRESHOLD", "1.0",
       "Burn-rate multiple that trips a breach in both windows (1.0 "
       "= burning the error budget exactly at the sustainable rate)."),
    # ------------------------------------------------ chaos
    _k("STPU_FAULTS", None,
       "Fault-injection spec (point:mode:p=..;...) armed at import."),
    _k("STPU_FAULTS_SEED", "0",
       "Seed for the fault-injection RNG (bit-identical chaos runs)."),
    # ------------------------------------------------ backends/agent
    _k("STPU_SKIP_IDENTITY_CHECK", None,
       "\"1\" skips the cloud-identity ownership check on cluster "
       "state handover."),
    _k("STPU_DISABLE_DAEMON", None,
       "\"1\" skips spawning the head agent daemon (hermetic tests)."),
    _k("STPU_DAEMON_INTERVAL", None,
       "Agent daemon poll interval override, seconds."),
    _k("STPU_AUTOSTOP_GRACE_SECONDS", "10",
       "Grace window before autostop teardown after the idle trigger."),
    _k("STPU_TEARDOWN_GRACE_SECONDS", "5",
       "SIGTERM grace for local jobs to flush a final checkpoint "
       "before teardown removes host dirs (0 disables)."),
    _k("STPU_FORCE_PY_AGENT", None,
       "Any value forces the pure-python gang coordinator over the "
       "native host agent."),
    _k("STPU_SKIP_HEALTH_PROBE", None,
       "\"1\" skips the pre-barrier TPU health probe on gang launch."),
    _k("STPU_EXEC_TOKEN", None,
       "Auth token presented to the remote exec agent."),
    _k("STPU_GANG_COORD_ADDR", None,
       "host:port of the gang coordinator for host wrappers."),
    _k("STPU_GANG_COORD_TOKEN", "",
       "Auth token for the direct-connect gang coordinator; empty "
       "selects the loopback-only unauthenticated mode."),
    # ------------------------------------------------ jobs/training
    _k("STPU_JOBS_POLL_SECONDS", "15",
       "Managed-jobs controller watch-tick interval, seconds."),
    _k("STPU_JOB_CKPT_DIR", None,
       "Per-task checkpoint dir stamped into every (re)launch by the "
       "jobs controller; recipes default --checkpoint-dir to it."),
    _k("STPU_PROFILE_DIR", None,
       "Write an on-device XLA profile of the training loop here."),
    _k("STPU_TRAINSTATS", "0",
       "\"1\" arms per-train-step goodput telemetry (step ring, live "
       "MFU, goodput breakdown, straggler detection, flight-recorder "
       "crash dumps)."),
    _k("STPU_TRAINSTATS_RING", "512",
       "Train-step ring capacity in records (the window MFU/goodput "
       "aggregate over and the flight recorder dumps)."),
    _k("STPU_TRAINSTATS_SYNC_EVERY", "0",
       "Sample a timed block_until_ready every N train steps to split "
       "dispatch vs device time (0 disables; the only sanctioned "
       "sync on the train hot path)."),
    _k("STPU_TRAINSTATS_DIR", None,
       "Trainstats output dir for per-host JSONL + snapshot.json "
       "(default $STPU_JOB_CKPT_DIR/trainstats when a managed job, "
       "else in-memory only)."),
    _k("STPU_TRAIN_STRAGGLER_SECONDS", "2.0",
       "Per-host step-boundary lag over the gang median that flags a "
       "straggler (host 0 scans; 0 disables)."),
    _k("STPU_BENCHMARK_LOG_DIR", None,
       "Benchmark-harness summary-log dir (callbacks.init contract)."),
    # ------------------------------------------------ serve control
    _k("STPU_SERVE_TICK_SECONDS", "10",
       "Serve controller reconcile tick, seconds."),
    _k("STPU_LB_SYNC_SECONDS", "2",
       "LB <-> controller sync interval, seconds."),
    _k("STPU_LB_POLICY", None,
       "Default load-balancing policy when the spec sets none."),
    _k("STPU_LB_RETRIES", "2",
       "Extra pre-first-byte attempts per proxied request."),
    _k("STPU_LB_MAX_BODY_BYTES", "10485760",
       "Request-body cap (413 above it, checked before buffering)."),
    _k("STPU_LB_BREAKER_THRESHOLD", "3",
       "Consecutive connect failures that eject a replica."),
    _k("STPU_LB_BREAKER_BACKOFF", "2",
       "Breaker half-open re-probe backoff base, seconds."),
    _k("STPU_LB_BREAKER_BACKOFF_CAP", "60",
       "Breaker backoff ceiling, seconds."),
    _k("STPU_LB_STREAM_RESUMES", "1",
       "Mid-stream resume attempts per proxied stream: upstream "
       "deaths after the first byte re-submit prompt+emitted to a "
       "peer and splice the continuation (0 disables journaling)."),
    _k("STPU_LB_RESUME_JOURNAL_MB", "8",
       "Global byte budget (MiB) for in-flight stream resume "
       "journals; over-budget streams evict (degrade to plain "
       "abort)."),
    # ------------------------------------------------ serve engine
    _k("STPU_ENGINE_SLOTS", "4",
       "Decode-engine slot count (continuous-batching concurrency), "
       "at least 1."),
    _k("STPU_KV_QUANT", "0",
       "\"1\" stores int8 KV blocks + per-(layer, block, head) f32 "
       "scales in the paged pool — ~2x blocks at the same HBM "
       "budget (auto pool sizing doubles). "
       "NOT bit-identical to bf16, gated by the tests/test_quant.py "
       "parity suite."),
    _k("STPU_WEIGHT_QUANT", "0",
       "\"1\" serves int8 per-output-channel-quantized params "
       "(matmul weights + embed/lm_head; norms, LoRA adapters and "
       "the MoE router stay full precision). Parity-gated like "
       "STPU_KV_QUANT."),
    _k("STPU_SPEC_K", "0",
       "Speculative decoding: tokens drafted per slot per decode "
       "step, verified in one batched forward (0 disables; output "
       "stays bit-identical to non-speculative decode)."),
    _k("STPU_SPEC_NGRAM", "3",
       "Speculative draft matcher n-gram length over each slot's own "
       "token history (prompt lookup)."),
    _k("STPU_SPEC_MIN_ACCEPT", "0.2",
       "Per-slot draft acceptance-rate floor: a slot whose measured "
       "acceptance falls below it (after >= 16 drafted tokens) stops "
       "drafting."),
    _k("STPU_KV_POOL_BLOCKS", "0",
       "Paged-KV pool size in blocks incl. the scratch block (0 = "
       "auto: slots * max_seq / block + 1; "
       "doubled under STPU_KV_QUANT=1 — int8 blocks are ~half the "
       "bytes)."),
    _k("STPU_KV_BLOCK_TOKENS", "0",
       "Paged-KV block size in tokens; also becomes the prefill "
       "chunk — blocks and chunks are one unit (0 = the engine's "
       "prefill chunk, default 64)."),
    _k("STPU_PREFIX_CACHE_MB", "64",
       "Host-RAM KV spill-tier budget in MiB under the paged prefix "
       "trie: LRU-evicted prefix blocks spill D2H into a bounded "
       "host pool and re-admit H2D on a warm match instead of "
       "re-prefilling. 0 disables the tier (evictions drop the KV). "
       "Rides the gang kv-config handshake."),
    _k("STPU_TUNE_MANIFEST", None,
       "Tuning-manifest override for the decode engine: a path loads "
       "that sha256-pinned `stpu tune` manifest, \"0\" disables "
       "tuning (hand-pinned defaults), unset auto-loads "
       "~/.stpu/tuning/manifest.json when present. Tuned geometry "
       "rides the gang kv-config handshake, so every member must "
       "resolve the same manifest."),
    _k("STPU_STREAM_TIMEOUT", "600",
       "Per-token stream timeout before the engine is declared "
       "wedged, seconds."),
    _k("STPU_ENGINE_MAX_RESTARTS", "3",
       "Consecutive fast engine crashes before permanent-down."),
    _k("STPU_ENGINE_RESTART_BACKOFF", "1.0",
       "Engine crash-restart backoff base, seconds."),
    _k("STPU_PREEMPT_NOTICE_POLL", "1.0",
       "Replica preemption-notice watcher poll interval, seconds "
       "(fault point replica.preempt_notice; 0 disables). A notice "
       "surfaces on /health and triggers controller replace-ahead."),
    # ------------------------------------------------ gang replicas
    _k("STPU_REPLICA_TOPOLOGY", None,
       "hosts x tp replica topology stamped by replica_managers into "
       "every gang member's env."),
    _k("STPU_GANG_SERVE_ADDR", None,
       "Explicit gang channel address for self-spawned followers "
       "(dev stacks); gang-launched followers derive it from the env "
       "contract instead."),
    _k("STPU_GANG_HB_SECONDS", "0.5",
       "Gang follower heartbeat interval, seconds."),
    _k("STPU_GANG_HB_TIMEOUT", "5",
       "Heartbeat silence that marks a gang member dead, seconds."),
    _k("STPU_GANG_MAX_RESTARTS", "3",
       "Consecutive fast whole-gang restarts before permanent-down."),
)

REGISTRY: Dict[str, EnvKnob] = {k.name: k for k in _KNOBS}
if len(REGISTRY) != len(_KNOBS):
    raise RuntimeError("duplicate STPU_* names in env_contract")


def get(name: str) -> EnvKnob:
    return REGISTRY[name]


def render_markdown_table() -> str:
    """The knob table embedded in docs/static-analysis.md (a tier-1
    test pins the doc to this exact output)."""
    lines = ["| knob | default | meaning |",
             "|---|---|---|"]
    for knob in sorted(REGISTRY.values(), key=lambda k: k.name):
        default = "(unset)" if knob.default is None else \
            f"`{knob.default}`" if knob.default else "`\"\"`"
        lines.append(f"| `{knob.name}` | {default} | {knob.doc} |")
    return "\n".join(lines)
