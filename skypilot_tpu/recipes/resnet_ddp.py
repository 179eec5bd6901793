"""Multi-node data-parallel ResNet — the DDP benchmark named config.

Reference analog: examples/torch_ddp_benchmark/torch_ddp_benchmark.yaml
(resnet101 under torch DDP, wired by MASTER_ADDR/NODE_RANK env vars; its
published numbers are in BASELINE.md). Native version: a flax ResNet whose
gradient sync is an XLA psum over the global device mesh, bootstrapped from
the framework env contract via `train.distributed.initialize_from_env` —
the first real consumer of SKYPILOT_COORDINATOR_ADDR.

Sync paths, picked automatically:
  * federated (real multi-host TPU slice): one jit over the global mesh,
    per-process data via make_array_from_process_local_data; psum rides ICI.
  * non-federated multi-process (CPU local provider in tests): local jit +
    coordination-service KV mean-allreduce of gradients — still true
    synchronous DDP (all ranks average every step), just not an XLA
    collective.

    python -m skypilot_tpu.recipes.resnet_ddp --steps 30 --tiny
"""
from __future__ import annotations

import argparse
import hashlib
import json
import time
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from skypilot_tpu.observability import trainstats
from skypilot_tpu.recipes import synthetic_data
from skypilot_tpu.train import distributed
from skypilot_tpu.utils import compile_cache


class ResNetBlock(nn.Module):
    features: int
    strides: int = 1

    @nn.compact
    def __call__(self, x):
        residual = x
        y = nn.Conv(self.features, (3, 3), strides=(self.strides,) * 2,
                    use_bias=False)(x)
        y = nn.GroupNorm(num_groups=8)(y)
        y = nn.relu(y)
        y = nn.Conv(self.features, (3, 3), use_bias=False)(y)
        y = nn.GroupNorm(num_groups=8)(y)
        if residual.shape != y.shape:
            residual = nn.Conv(self.features, (1, 1),
                               strides=(self.strides,) * 2,
                               use_bias=False)(x)
        return nn.relu(y + residual)


class ResNet(nn.Module):
    """Stage widths/depths configurable; GroupNorm instead of BatchNorm so
    data parallelism needs no cross-device batch-stat sync."""
    stage_sizes: Sequence[int] = (2, 2, 2, 2)
    width: int = 64
    n_classes: int = 1000

    @nn.compact
    def __call__(self, x):
        x = nn.Conv(self.width, (7, 7), strides=(2, 2), use_bias=False)(x)
        x = nn.GroupNorm(num_groups=8)(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
        for i, n_blocks in enumerate(self.stage_sizes):
            feats = self.width * (2 ** i)
            for j in range(n_blocks):
                x = ResNetBlock(feats, strides=2 if i > 0 and j == 0
                                else 1)(x)
        x = x.mean(axis=(1, 2))
        return nn.Dense(self.n_classes)(x)


def _param_digest(params) -> str:
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(params):
        h.update(np.asarray(leaf, dtype=np.float32).tobytes())
    return h.hexdigest()[:16]


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch-size", type=int, default=32,
                   help="per-process batch size")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tiny", action="store_true",
                   help="small model/images for CPU tests")
    p.add_argument("--out-file", type=str, default=None,
                   help="write final metrics+param digest JSON here")
    args = p.parse_args(argv)

    ctx = distributed.initialize_from_env()
    compile_cache.enable()
    if args.tiny:
        model = ResNet(stage_sizes=(1, 1), width=8, n_classes=10)
        args.image_size = 32
    else:
        model = ResNet(stage_sizes=(3, 4, 23, 3), width=64)  # resnet101

    print(f"resnet_ddp: rank={ctx.rank}/{ctx.num_nodes} "  # noqa: stpu-host-sync startup banner of host ints, before the loop
          f"local_devices={jax.local_device_count()} "
          f"global_devices={jax.device_count()} federated={ctx.federated}",
          flush=True)

    # Every process generates the same dataset (seeded) and reads its own
    # batch shard by rank, exactly like a sharded dataloader.
    n_classes = 10 if args.tiny else 1000

    def sample_batch(step: int):
        r = np.random.RandomState(args.seed + step * ctx.num_nodes
                                  + ctx.rank)
        x = r.randn(args.batch_size, args.image_size, args.image_size,
                    3).astype(np.float32)
        y = r.randint(0, n_classes, size=(args.batch_size,)).astype(
            np.int32)
        return x, y

    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, args.image_size, args.image_size, 3)))
    tx = optax.sgd(args.lr, momentum=0.9)
    opt_state = tx.init(params)

    if ctx.federated:
        # One logical program over all hosts' devices; batch sharded over
        # the dp axis, params replicated; XLA inserts the grad psum.
        world_batch_ = args.batch_size * ctx.num_nodes
        if world_batch_ % jax.device_count():
            raise SystemExit(
                f"global batch {world_batch_} not divisible by "
                f"{jax.device_count()} devices; raise --batch-size")
        mesh = Mesh(np.asarray(jax.devices()), ("dp",))  # noqa: stpu-host-sync device handles are host-side objects, not arrays
        batch_sharding = NamedSharding(mesh, P("dp"))
        replicated = NamedSharding(mesh, P())
        params = jax.device_put(params, replicated)
        opt_state = jax.device_put(opt_state, replicated)

        def globalize(x):
            return jax.make_array_from_process_local_data(
                batch_sharding, x)
    else:
        globalize = jnp.asarray

    @jax.jit
    def step_fn(params, x, y):
        def loss_fn(params):
            logits = model.apply(params, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        return grads, loss

    @jax.jit
    def apply_fn(params, opt_state, grads):
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state

    if trainstats.ENABLED:
        trainstats.configure(
            peak_flops=trainstats.detect_peak_flops(),
            host=ctx.rank, hosts=ctx.num_nodes, job="resnet_ddp")
    iter_times = []
    loss = None
    try:
        for i in range(args.steps):
            data_t0 = time.perf_counter()
            x, y = sample_batch(i)
            data_wait = time.perf_counter() - data_t0
            t0 = time.perf_counter()
            grads, loss = step_fn(params, globalize(x), globalize(y))
            if ctx.is_multiprocess and not ctx.federated:
                grads = distributed.kv_allreduce_mean(grads, ctx,
                                                      tag=str(i))
            params, opt_state = apply_fn(params, opt_state, grads)
            # The DDP bench fences every iteration by design — iter
            # times measure the full step, not just dispatch.
            jax.block_until_ready(params)  # noqa: stpu-host-sync benchmark iteration fence by design
            dur = time.perf_counter() - t0
            iter_times.append(dur)
            if trainstats.ENABLED:
                trainstats.record_step(step=i + 1, dur=dur,
                                       tokens=args.batch_size,
                                       data_wait_s=data_wait)
    except (Exception, KeyboardInterrupt) as e:
        if trainstats.ENABLED:
            trainstats.dump_flight("train_crash", error=repr(e))
        raise

    world_batch = args.batch_size * max(ctx.num_nodes, 1)
    p50 = float(np.median(iter_times[2:] or iter_times))
    # Host copies for the report: digesting/printing the device trees
    # directly would sync them inside the metrics build.
    params_host = jax.device_get(params)
    loss_host = jax.device_get(loss)
    metrics = {
        "recipe": "resnet_ddp",
        "rank": ctx.rank,
        "num_nodes": ctx.num_nodes,
        "steps": args.steps,
        "final_loss": float(loss_host),
        "p50_iter_seconds": round(p50, 4),
        "examples_per_second": round(world_batch / p50, 1),
        "param_digest": _param_digest(params_host),
    }
    if trainstats.ENABLED:
        snap = trainstats.snapshot()
        metrics["train_goodput"] = snap["goodput"]
        metrics["train_step_seconds"] = snap["step_seconds_mean"]
        trainstats.flush()
    print(json.dumps(metrics), flush=True)
    if args.out_file:
        with open(args.out_file, "w") as f:
            json.dump(metrics, f)
    return metrics


if __name__ == "__main__":
    main()
