"""The training cell's child: LoRA through the recipe's own loop.

``llama_lora.run_lora(model_lib, cfg, args, name)`` takes a count of
steps, not a time. So this runner calls it twice in one process: a first
call of a few steps compiles the step (or reads it from the compile
cache) and times warm steps from the ``trainstats`` ring; from that the
count for ``--seconds`` follows, and the second call runs
``warm_steps`` unmeasured steps and then the measured ones. Set-up ends
where the first measured step begins.

Each step's record ends after a blocking fetch of the previous step's
loss (the recipe's one-step-delayed fetch), so the time between the end
of the last warm step and the end of the last step is the device's time
for the measured steps and everything the host did between them.

A traced run (``--trace 1``) traces the second call only, takes the
mix's ``traced_steps`` measured steps, and reduces the span of those
steps in the trace; the program's tracing and the warm steps the trace
also holds are left out.

Correctness: the first loss (the adapters start at zero, so it is the
base model's) equals the plain reference's loss on the same first batch
within LOSS_TOLERANCE, and every loss is finite.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
import types

from benchmarks import cells

# Relative. The step computes in bf16 (8 bits of mantissa: each matmul
# rounds at about 4e-3 relative) through 16 layers and averages 8,188
# token losses of about 10.4 nats each; the rounding is unbiased, so the
# mean moves far less than one logit's error. Measured on the chip
# (PERF.md, Findings, PR 24). A step in a lower precision (int8 weights)
# or with a layer left out moves the loss by more than this.
LOSS_TOLERANCE = 5e-3


def say(msg: str) -> None:
    print(f"train-child: {msg}", file=sys.stderr, flush=True)


def lora_args(settings: dict, seed: int, steps: int):
    return types.SimpleNamespace(
        model="benchmark", steps=steps, batch_size=int(settings["batch"]),
        seq_len=int(settings["seq_len"]),
        lora_rank=int(settings["lora_rank"]),
        lr=float(settings.get("lr", 1e-3)), seed=seed,
        checkpoint_dir=None, ckpt_every=10 ** 9, ckpt_keep=1,
        ckpt_sync=False)


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--cell", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, default=0)
    p.add_argument("--out", required=True)
    p.add_argument("--spawned-at", type=float, required=True)
    p.add_argument("--tiny", action="store_true")
    args = p.parse_args(argv)

    cell = cells.load_cell(args.cell, tiny=args.tiny)
    config, mix = cell["config"], cell["traffic"]
    import jax
    import numpy as np
    from skypilot_tpu.observability import trainstats
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.recipes import llama_lora, synthetic_data
    from skypilot_tpu.utils import compile_cache
    compile_cache.enable()
    device = mesh_lib.device_info()
    say(f"device {device}")
    refusal = cells.refusal(device, cell, args.tiny)
    if refusal:
        say(refusal)
        return 3
    module, cfg = cells.model_config(config)
    settings = config["train"]
    seed = args.seed % (2 ** 31 - 9)
    warm = int(mix.get("warm_steps", 2))
    tokens_per_step = int(settings["batch"]) * int(settings["seq_len"])
    trainstats.arm()

    # First call: compile, then time warm steps.
    trainstats.reset()
    called = time.monotonic()
    first = llama_lora.run_lora(module, cfg, lora_args(settings, seed, 4),
                                recipe_name="bench_lora_warm")
    ready_s = (called - args.spawned_at
               + first["start_to_first_loss_seconds"])
    ring = trainstats.steps_tail()
    step_s = min(r["dur"] for r in ring[2:])
    say(f"warm step {step_s:.3f} s; first loss {first['first_loss']}")
    measured = max(int(math.ceil(args.seconds / step_s)), 2)
    if args.trace:
        measured = min(measured, int(mix.get("traced_steps", 6)))
    profile_dir = os.path.join(args.out, "profile")
    if args.trace:
        # Not through STPU_PROFILE_DIR: the recipe's
        # ``callbacks.device_profile()`` takes JAX's defaults, whose
        # Python tracer records every frame of the step's tracing and
        # lowering (millions of events). Same window, Python tracer off.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(profile_dir, profiler_options=options)

    # Second call: `warm` unmeasured steps, then the measured ones.
    trainstats.reset()
    try:
        second = llama_lora.run_lora(
            module, cfg, lora_args(settings, seed, warm + measured),
            recipe_name="bench_lora")
    finally:
        if args.trace:
            jax.profiler.stop_trace()
    ring = trainstats.steps_tail()
    assert len(ring) == warm + measured, (len(ring), warm, measured)
    window_start = ring[warm - 1]["mono"]
    window_end = ring[-1]["mono"]
    losses = [r["loss"] for r in ring if r["loss"] is not None]
    losses.append(second["final_loss"])
    result = {
        "device": device,
        "ready_s": ready_s,
        "setup_end_mono": window_start,
        "steps": measured, "tokens_per_step": tokens_per_step,
        "window_s": window_end - window_start,
        "step_seconds": [r["dur"] for r in ring[warm:]],
        "first_loss": first["first_loss"],
        "losses_finite": all(math.isfinite(x) for x in losses),
        "final_loss": second["final_loss"],
        "attention_traces": second["attention_traces"],
        "base_bytes_per_device": second["base_bytes_per_device"],
    }

    if args.trace:
        from benchmarks import trace_reduce
        path = trace_reduce.find_xplane(profile_dir)
        reduced = {"devices": 0}
        if path is not None:
            from jax.profiler import ProfileData
            data = ProfileData.from_file(path)
            names = sorted({trace_reduce.program_name(e.name)
                            for pl in trace_reduce.device_planes(data)
                            for ln in pl.lines if ln.name == "XLA Modules"
                            for e in ln.events})
            say(f"programs in the trace: {names}")
            program = next((n for n in names if "step_fn" in n), None)
            window = (trace_reduce.steady_window(data, program, measured)
                      if program else None)
            if window is not None:
                reduced = trace_reduce.reduce_data(data, window)
                reduced["program"] = program
                reduced["steps"] = measured
            if os.environ.get("BENCH_DESCRIBE"):
                with open(os.path.join(args.out, "trace.txt"), "w") as f:
                    f.write(trace_reduce.describe(path, limit=40))
                trace_reduce.record(
                    path, os.path.join(args.out, "recorded.json"), 200.0)
        result["trace"] = reduced

    # The reference's loss on the first batch, after the timed part.
    import importlib
    ref = importlib.import_module(
        f"benchmarks.reference.{config['family']}_arch")
    base = jax.jit(lambda k: module.init(cfg, k))(jax.random.PRNGKey(seed))
    data = synthetic_data.lm_tokens(seed, 256, int(settings["seq_len"]),
                                    cfg.vocab_size)
    (batch,) = next(synthetic_data.batches(
        (data,), int(settings["batch"]), seed, 1))
    ref_loss = ref.loss(cfg, base, np.asarray(batch))
    result["reference_first_loss"] = ref_loss
    result["first_loss_rel_error"] = abs(
        first["first_loss"] - ref_loss) / abs(ref_loss)
    result["loss_tolerance"] = LOSS_TOLERANCE
    result["correct"] = bool(
        result["losses_finite"]
        and result["first_loss_rel_error"] <= LOSS_TOLERANCE
        and abs(second["first_loss"] - first["first_loss"]) == 0.0)
    result["memory_peak_bytes"] = cells.memory_peak()
    with open(os.path.join(args.out, "train_result.json"), "w") as f:
        json.dump(result, f)
    say(f"done: {json.dumps({k: v for k, v in result.items() if k != 'trace'})}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
