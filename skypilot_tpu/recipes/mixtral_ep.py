"""Mixtral-8x7B expert-parallel pretraining step — the MoE named config.

Reference analog: llm/mixtral/ (the reference hands vLLM a set of GPUs and
vLLM does the expert math internally). Native version: models/mixtral.py's
one-hot dispatch/combine MoE trained under an ep-sharded mesh; XLA inserts
the expert all-to-alls over ICI.

    python -m skypilot_tpu.recipes.mixtral_ep --model tiny --steps 10
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from skypilot_tpu.models import mixtral
from skypilot_tpu.observability import trainstats
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.recipes import synthetic_data
from skypilot_tpu.train import distributed, trainer
from skypilot_tpu.utils import compile_cache


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--model", choices=["tiny", "8x7b"], default="tiny")
    p.add_argument("--steps", type=int, default=10)
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--ep", type=int, default=-1,
                   help="expert-parallel axis size (-1: all devices)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--with-grad-norm", action="store_true",
                   help="report grad_norm per step (an EXTRA full "
                        "sweep over every gradient — pure MFU tax, "
                        "so benches leave it off)")
    args = p.parse_args(argv)

    ctx = distributed.initialize_from_env()
    compile_cache.enable()
    cfg = (mixtral.MixtralConfig.mixtral_8x7b() if args.model == "8x7b"
           else mixtral.MixtralConfig.tiny())

    n_dev = jax.device_count()
    ep = args.ep if args.ep != -1 else min(n_dev, cfg.n_experts)
    mesh = mesh_lib.make_mesh({"dp": -1, "ep": ep})
    rules = mesh_lib.DEFAULT_RULES
    print(f"mixtral_ep: model={args.model} "  # noqa: stpu-host-sync startup banner of host ints, before the loop
          f"mesh={dict(mesh.shape)} "
          f"rank={ctx.rank}/{ctx.num_nodes}", flush=True)

    shardings = mesh_lib.tree_shardings(mesh, rules,
                                        mixtral.param_specs(cfg))
    params = jax.jit(lambda k: mixtral.init(cfg, k),
                     out_shardings=shardings)(
                         jax.random.PRNGKey(args.seed))
    tx = trainer.make_optimizer(trainer.TrainConfig(total_steps=args.steps))
    state = trainer.init_train_state(params, tx)

    # grad_norm defaults OFF here: its extra sweep over every gradient
    # is pure MFU tax on the bench path (trainer.make_train_step).
    step = trainer.make_train_step(
        lambda p, tokens, constrain: mixtral.forward(
            cfg, p, tokens, constrain=constrain),
        tx, mesh, rules, with_grad_norm=args.with_grad_norm)

    if trainstats.ENABLED:
        trainstats.configure(
            flops_per_token=cfg.flops_per_token(),
            peak_flops=trainstats.detect_peak_flops(),
            host=ctx.rank, hosts=ctx.num_nodes, job="mixtral_ep")
    data = synthetic_data.lm_tokens(args.seed, 128, args.seq_len,
                                    cfg.vocab_size)
    t0 = time.time()
    aux_loss = None
    losses = []
    # One-step-delayed metrics fetch: each iteration fetches the
    # PREVIOUS step's metrics dict (already resident) — float()-ing
    # this step's loss here would sync the device every iteration.
    delayed = trainer.DelayedFetch()
    tokens_per_step = args.batch_size * args.seq_len
    try:
        mark = time.perf_counter()
        for i, (tokens,) in enumerate(
                synthetic_data.batches((data,), args.batch_size,
                                       args.seed, args.steps)):
            data_wait = time.perf_counter() - mark
            step_t0 = time.perf_counter()
            state, metrics = step(state, {"tokens": jnp.asarray(tokens)})
            dispatch_s = time.perf_counter() - step_t0
            fetched = None
            grad_norm = None
            prev = delayed.rotate(metrics)
            if prev is not None:
                host_m = jax.device_get(prev)
                fetched = float(host_m["loss"])
                losses.append(fetched)
                aux_loss = float(host_m["aux_loss"])
                if "grad_norm" in host_m:
                    grad_norm = float(host_m["grad_norm"])
            device_s = None
            if trainstats.ENABLED and trainstats.sync_due():
                device_s = trainstats.sampled_sync(metrics["loss"])
            dur = time.perf_counter() - step_t0
            if trainstats.ENABLED:
                trainstats.record_step(
                    step=i + 1, dur=dur, tokens=tokens_per_step,
                    data_wait_s=data_wait, dispatch_s=dispatch_s,
                    device_s=device_s,
                    delayed=({"loss": fetched, "grad_norm": grad_norm}
                             if fetched is not None else None))
            mark = time.perf_counter()
        # Drain: fetching the final metrics blocks until the last
        # step's results are ready (the old end-of-run fence).
        final = delayed.drain()
        if final is not None:
            host_m = jax.device_get(final)
            losses.append(float(host_m["loss"]))
            aux_loss = float(host_m["aux_loss"])
    except (Exception, KeyboardInterrupt) as e:
        if trainstats.ENABLED:
            trainstats.dump_flight("train_crash", error=repr(e))
        raise
    wall = time.time() - t0  # noqa: stpu-wallclock workload wall-time report

    out = {
        "recipe": "mixtral_ep",
        "model": args.model,
        "mesh": dict(mesh.shape),
        "steps": args.steps,
        "first_loss": losses[0] if losses else None,
        "final_loss": losses[-1] if losses else None,
        "aux_loss": aux_loss,
        "tokens_per_second": round(
            args.steps * args.batch_size * args.seq_len / wall, 1),
        "wall_seconds": round(wall, 2),
    }
    if trainstats.ENABLED:
        snap = trainstats.snapshot()
        out["train_mfu"] = snap["mfu"]
        out["train_goodput"] = snap["goodput"]
        out["train_step_seconds"] = snap["step_seconds_mean"]
        out["train_tokens_per_sec"] = snap["tokens_per_sec"]
        trainstats.flush()
    print(json.dumps(out), flush=True)  # noqa: stpu-host-sync host metrics report after the loop (mesh shape is host ints)
    return out


if __name__ == "__main__":
    main()
