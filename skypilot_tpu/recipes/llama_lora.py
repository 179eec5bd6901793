"""Llama-3.1 LoRA finetune with crash-consistent checkpointing — the
flagship recipe.

Reference analog: llm/llama-3_1-finetuning/lora.yaml (torchtune LoRA with
checkpoints to a MOUNT-mode bucket, lines 24-30 — the reference's
checkpoint/resume pattern). Native version: low-rank adapters on the
attention projections of models/llama.py (applied as y@A@B inside
`lora_dense`, never materializing the full-rank delta), base weights
frozen via gradients taken only w.r.t. the adapter subtree, and
crash-consistent checkpoints (train/checkpoint.py: atomic rename +
checksummed manifest + async D2H) written to --checkpoint-dir every
``--ckpt-every`` steps — point it at a MOUNT-mode storage path
(examples/llama31_lora.yaml), or let a managed job stamp it via
$STPU_JOB_CKPT_DIR, and a preempted run resumes **bit-identically**:
the full train state (adapters, optimizer state, step, data position,
PRNG key) round-trips as raw bytes and the data stream replays from
the exact saved position.

Preemption grace: the agent layer forwards SIGTERM to this process
(agent/host_wrapper.py); the loop finishes the in-flight step, saves a
final checkpoint, and exits with rc 143 so the controller records an
interrupted (not succeeded) task with a fresh checkpoint to resume.

    python -m skypilot_tpu.recipes.llama_lora --model tiny --steps 20 \
        --checkpoint-dir /checkpoints/run1
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from skypilot_tpu.models import llama
from skypilot_tpu.observability import phases
from skypilot_tpu.observability import trainstats
from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.recipes import synthetic_data
from skypilot_tpu.train import checkpoint as checkpoint_lib
from skypilot_tpu.train import distributed, trainer
from skypilot_tpu.utils import compile_cache
from skypilot_tpu.utils import fault_injection


def init_lora(cfg: llama.LlamaConfig, rank: int, key: jax.Array,
              targets=("wq", "wk", "wv", "wo")) -> dict:
    """Adapter tree matching the stacked-layer layout: A ~ N(0, 1/d), B = 0
    (so the model starts exactly at the base weights)."""
    d = cfg.dim
    outs = {"wq": cfg.n_heads * cfg.head_dim,
            "wk": cfg.n_kv_heads * cfg.head_dim,
            "wv": cfg.n_kv_heads * cfg.head_dim,
            "wo": d}
    ins = {"wq": d, "wk": d, "wv": d, "wo": cfg.n_heads * cfg.head_dim}
    layers = {}
    keys = jax.random.split(key, len(targets))
    for k, name in zip(keys, targets):
        layers[name + "_lora_a"] = (
            jax.random.normal(k, (cfg.n_layers, ins[name], rank),
                              dtype=jnp.float32) *
            (ins[name] ** -0.5)).astype(cfg.dtype)
        layers[name + "_lora_b"] = jnp.zeros(
            (cfg.n_layers, rank, outs[name]), dtype=cfg.dtype)
    return {"layers": layers}


def merge_params(base: dict, lora: dict) -> dict:
    merged = dict(base)
    merged["layers"] = {**base["layers"], **lora["layers"]}
    return merged


def num_params(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def device_report(base_bytes: dict) -> dict:
    """What the run used, as JAX reports it: the device, and per local
    device the base parameters' bytes resident there (``base_bytes``,
    from their shards) and the allocator's peak (None where the
    backend keeps none, e.g. the CPU). Host values only."""
    devices = jax.local_devices()
    return {
        "device": mesh_lib.device_info(),
        "base_bytes_per_device": [base_bytes.get(d.id, 0)
                                  for d in devices],
        "peak_bytes_per_device": [
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in devices],
    }


def build_arg_parser(model_choices, default_model) -> argparse.ArgumentParser:
    p = argparse.ArgumentParser()
    p.add_argument("--model", choices=model_choices,
                   default=default_model)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--batch-size", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--lora-rank", type=int, default=8)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--checkpoint-dir", type=str,
                   default=os.environ.get(checkpoint_lib.CKPT_DIR_ENV),
                   help="checkpoint root (train/checkpoint.py format); "
                        "a MOUNT-mode bucket path makes runs resumable "
                        "across preemptions. Defaults to "
                        f"${checkpoint_lib.CKPT_DIR_ENV}, which the "
                        "managed-jobs controller stamps per job.")
    p.add_argument("--ckpt-every", "--save-every", dest="ckpt_every",
                   type=int, default=10,
                   help="save a checkpoint every N steps (a preemption "
                        "replays at most N-1 steps)")
    p.add_argument("--ckpt-keep", type=int,
                   default=checkpoint_lib.DEFAULT_KEEP,
                   help="retention: newest checkpoints kept on disk")
    p.add_argument("--ckpt-sync", action="store_true",
                   help="write checkpoints synchronously on the step "
                        "path (default: async D2H + background write)")
    return p


def main(argv=None) -> dict:
    args = build_arg_parser(["tiny", "8b"], "tiny").parse_args(argv)
    cfg = (llama.LlamaConfig.llama3_8b() if args.model == "8b"
           else llama.LlamaConfig.tiny())
    return run_lora(llama, cfg, args, recipe_name="llama_lora")


_STARTUP_PHASES = ("weights", "compile", "first_loss")


def run_lora(model_lib, cfg, args, recipe_name: str) -> dict:
    """LoRA finetune loop, generic over the dense model families (llama
    and gemma share forward/param_specs/lora_dense; gemma_lora.py passes
    its module + config here)."""
    setup_t0 = time.perf_counter()
    ctx = distributed.initialize_from_env()
    compile_cache.enable()
    # Start-up by phase, on the clock a server's start-up uses
    # (observability/phases.py): ``import`` ended with the process's
    # first device query (the line above, or an earlier caller's),
    # ``weights`` runs to the first step's call, ``compile`` is that
    # call, ``first_loss`` until its loss is on the host.
    startup = phases.startup_clock(_STARTUP_PHASES)
    startup_before = phases.startup_seconds()
    startup.enter("weights")
    if args.seq_len > cfg.max_seq_len:
        raise SystemExit(f"--seq-len {args.seq_len} exceeds model max "
                         f"{cfg.max_seq_len}")

    mesh = mesh_lib.make_mesh({"fsdp": -1})
    rules = mesh_lib.DEFAULT_RULES
    device = mesh_lib.device_info()
    print(f"{recipe_name}: model={args.model} "  # noqa: stpu-host-sync startup banner of host ints, before the loop
          f"platform={device['platform']} "
          f"device_kind={device['kind']!r} "
          f"devices={device['count']} "
          f"rank={ctx.rank}/{ctx.num_nodes}", flush=True)

    # Base params: sharded by the rule table (fsdp over embed axes); the
    # adapters are tiny and stay replicated.
    base_shardings = mesh_lib.tree_shardings(mesh, rules,
                                             model_lib.param_specs(cfg))
    base = jax.jit(lambda k: model_lib.init(cfg, k),
                   out_shardings=base_shardings)(
                       jax.random.PRNGKey(args.seed))
    base_bytes = mesh_lib.bytes_per_device(base)
    lora = init_lora(cfg, args.lora_rank, jax.random.PRNGKey(args.seed + 1))
    tx = optax.adamw(args.lr)
    opt_state = tx.init(lora)
    start_step = 0
    data_start = 0
    # Training PRNG key: carried in the checkpoint (full-TrainState
    # contract) so any stochastic op added later resumes mid-stream
    # instead of restarting its randomness.
    rng_dev = jax.random.PRNGKey(args.seed + 2)
    train_rng = jax.device_get(rng_dev)

    def _state_tree(step: int):
        return {"lora": lora, "opt_state": opt_state,
                "step": np.int64(step), "data_pos": np.int64(step),
                "rng": train_rng}

    saver = None
    if args.checkpoint_dir:
        ckpt_dir = os.path.abspath(
            os.path.expanduser(args.checkpoint_dir))
        saver = checkpoint_lib.Checkpointer(
            ckpt_dir, keep=args.ckpt_keep,
            async_save=not args.ckpt_sync)
        restored = checkpoint_lib.restore_latest(ckpt_dir,
                                                 like=_state_tree(0))
        if restored is not None:
            # Restored leaves are host arrays; put them back as
            # replicated (uncommitted-on-one-device clashes with the
            # mesh-sharded base inside jit). Raw-byte round-trip: no
            # dtype cast, so resume is bit-identical.
            from jax.sharding import NamedSharding, PartitionSpec
            replicated = NamedSharding(mesh, PartitionSpec())
            def _replicate(x):
                return jax.device_put(jnp.asarray(x), replicated)
            lora = jax.tree.map(_replicate, restored.tree["lora"])
            opt_state = jax.tree.map(_replicate,
                                     restored.tree["opt_state"])
            train_rng = np.asarray(restored.tree["rng"])
            start_step = int(restored.tree["step"])
            # Data position is its own leaf (not derived from step):
            # loops where they diverge — gradient accumulation,
            # multi-epoch shuffles — resume the stream correctly.
            data_start = int(restored.tree["data_pos"])
            print(f"{recipe_name}: resumed from step {start_step}",
                  flush=True)

    def constrain(x, spec):
        return mesh_lib.constrain(x, mesh, rules, spec)

    @jax.jit
    def step_fn(base, lora, opt_state, tokens):
        base = jax.tree.map(jax.lax.stop_gradient, base)

        def loss_fn(lora):
            params = merge_params(base, lora)
            with mesh_lib.use_mesh(mesh, rules):
                logits = model_lib.forward(cfg, params, tokens,
                                       constrain=constrain)
            return trainer.cross_entropy_loss(logits[:, :-1],
                                              tokens[:, 1:])
        loss, grads = jax.value_and_grad(loss_fn)(lora)
        updates, opt_state = tx.update(grads, opt_state, lora)
        return optax.apply_updates(lora, updates), opt_state, loss

    data = synthetic_data.lm_tokens(args.seed + ctx.rank, 256,
                                    args.seq_len, cfg.vocab_size)
    # Preemption grace: the gang layer forwards SIGTERM here; finish
    # the in-flight step, save, exit 143 (train/checkpoint.py).
    grace = checkpoint_lib.GraceHandler.install()
    if trainstats.ENABLED:
        trainstats.configure(
            flops_per_token=cfg.flops_per_token(args.seq_len),
            peak_flops=trainstats.detect_peak_flops(),
            host=ctx.rank, hosts=ctx.num_nodes, job=recipe_name)
        if start_step:
            # A resumed run's setup wall (restore + re-init) is
            # restart downtime in the goodput breakdown.
            trainstats.note_downtime(time.perf_counter() - setup_t0)
    t0 = time.time()
    loss = None
    losses = []
    first_loss_s = None

    def record_loss(value: float) -> None:
        nonlocal first_loss_s
        if not losses:
            first_loss_s = startup.enter(None) - setup_t0
        losses.append(value)

    # One-step-delayed loss fetch: each iteration fetches the PREVIOUS
    # step's loss (already resident by then) so logging never syncs
    # the hot loop — float(loss) here would stall every step.
    delayed = trainer.DelayedFetch()
    tokens_per_step = args.batch_size * args.seq_len
    # On-device XLA profile of the training loop when STPU_PROFILE_DIR
    # is set (tensorboard-loadable); zero-cost no-op otherwise. The
    # `with` guarantees the trace is finalized even when a step raises.
    from skypilot_tpu import callbacks
    try:
        with callbacks.device_profile():
            # Data position: skip replays the RNG draws of the
            # completed steps, so step k's batch is the same whether
            # or not the run was interrupted (bit-identical resume).
            mark = time.perf_counter()
            for i, (tokens,) in enumerate(
                    synthetic_data.batches((data,), args.batch_size,
                                           args.seed,
                                           args.steps - start_step,
                                           skip=data_start)):
                data_wait = time.perf_counter() - mark
                step = start_step + i + 1
                step_t0 = (startup.enter("compile") if i == 0
                           else time.perf_counter())
                lora, opt_state, loss = step_fn(base, lora, opt_state,
                                                jnp.asarray(tokens))
                if i == 0:
                    startup.enter("first_loss")
                dispatch_s = time.perf_counter() - step_t0
                fetched = None
                prev = delayed.rotate(loss)
                if prev is not None:
                    host_loss = jax.device_get(prev)
                    fetched = float(host_loss)
                    record_loss(fetched)
                device_s = None
                if trainstats.ENABLED and trainstats.sync_due():
                    device_s = trainstats.sampled_sync(loss)
                dur = time.perf_counter() - step_t0
                # Chaos seam: deterministic mid-epoch crash/preempt
                # (STPU_FAULTS="train.step:kill:skip=K").
                if fault_injection.ENABLED:
                    fault_injection.fire("train.step", step=step)
                # Snapshot ONCE: SIGTERM landing between a
                # save-condition read and the exit-branch read must not
                # skip the grace save while still reporting it happened.
                preempting = grace.triggered
                ckpt_s = 0.0
                if saver is not None and (step % args.ckpt_every == 0
                                          or step == args.steps
                                          or preempting):
                    ckpt_t0 = time.perf_counter()
                    saver.save(step, _state_tree(step))
                    ckpt_s = time.perf_counter() - ckpt_t0
                if trainstats.ENABLED:
                    trainstats.record_step(
                        step=step, dur=dur, tokens=tokens_per_step,
                        data_wait_s=data_wait, ckpt_s=ckpt_s,
                        dispatch_s=dispatch_s, device_s=device_s,
                        delayed=({"loss": fetched}
                                 if fetched is not None else None))
                if preempting:
                    if saver is not None:
                        saver.wait()  # the grace save must be durable
                    if trainstats.ENABLED:
                        trainstats.dump_flight("sigterm")
                    print(json.dumps({
                        "recipe": recipe_name, "preempted": True,
                        "resumed_from": start_step, "stopped_at": step,
                        "last_ckpt_step": (saver.last_saved_step
                                           if saver is not None
                                           else None),
                    }), flush=True)
                    raise SystemExit(
                        checkpoint_lib.GraceHandler.GRACE_EXIT_CODE)
                mark = time.perf_counter()
            # Drain the outstanding handle: the fetch both logs the
            # final loss and blocks until the last step completed.
            final = delayed.drain()
            if final is not None:
                host_loss = jax.device_get(final)
                record_loss(float(host_loss))
    except (Exception, KeyboardInterrupt) as e:
        if trainstats.ENABLED:
            trainstats.dump_flight("train_crash", error=repr(e))
        raise
    startup.enter(None)     # a run of no step never left ``weights``
    if saver is not None:
        saver.wait()

    wall = time.time() - t0  # noqa: stpu-wallclock workload wall-time report
    steps_run = max(args.steps - start_step, 0)
    tokens_seen = steps_run * args.batch_size * args.seq_len
    # Host copy for reporting: the adapters are tiny, and counting the
    # device tree directly would sync it into the metrics print.
    lora_host = jax.device_get(lora)
    took = phases.startup_seconds()
    startup_took = {p: round(took[p] - startup_before[p], 3)
                    for p in _STARTUP_PHASES}
    if "import" in took:        # the process's, not this call's
        startup_took["import"] = round(took["import"], 3)
    metrics = {
        "recipe": recipe_name,
        "model": args.model,
        "lora_params": num_params(lora_host),
        "base_params": cfg.num_params(),
        "resumed_from": start_step,
        "last_ckpt_step": (saver.last_saved_step
                           if saver is not None else None),
        "steps": args.steps,
        "first_loss": losses[0] if losses else None,
        "final_loss": losses[-1] if losses else None,
        "tokens_per_second": round(tokens_seen / wall, 1) if wall else 0,
        "wall_seconds": round(wall, 2),
        # Start of run_lora to the first loss on the host: parameter
        # init, the step's compile and one step.
        "start_to_first_loss_seconds": (
            round(first_loss_s, 2) if first_loss_s is not None else None),
        # The same by phase: this call's weights, compile and
        # first_loss add up to the line above less what the call spent
        # before ``weights`` (the gang's rendezvous and, in a process
        # whose first device query this call made, the back end's
        # start, which are the process's ``import``).
        "startup_seconds": startup_took,
        # Which attention implementation the step was traced into
        # (ops/attention.py TRACES): a shape that fell back to the
        # O(S^2) reference shows here, not only in the step time.
        "attention_traces": attention_ops.trace_counts(),
        **device_report(base_bytes),
    }
    if trainstats.ENABLED:
        snap = trainstats.snapshot()
        metrics["train_mfu"] = snap["mfu"]
        metrics["train_goodput"] = snap["goodput"]
        metrics["train_step_seconds"] = snap["step_seconds_mean"]
        metrics["train_tokens_per_sec"] = snap["tokens_per_sec"]
        trainstats.flush()
    print(json.dumps(metrics), flush=True)  # noqa: stpu-host-sync end-of-run report of host values, after the loop
    return metrics


if __name__ == "__main__":
    main()
