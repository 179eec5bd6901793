"""Flax MNIST — the minimum end-to-end "aha" recipe.

Reference analog: examples/tpu/tpuvm_mnist.yaml (clones the flax repo and
runs its MNIST example on a TPU VM). Native version: a small flax CNN,
jit-compiled, sharded over whatever devices the host has; launched by
examples/tpu_mnist.yaml.

    python -m skypilot_tpu.recipes.mnist --steps 300
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from skypilot_tpu import callbacks as sky_callback
from skypilot_tpu.recipes import synthetic_data
from skypilot_tpu.train import distributed
from skypilot_tpu.utils import compile_cache


class CNN(nn.Module):
    @nn.compact
    def __call__(self, x):
        x = nn.Conv(features=16, kernel_size=(3, 3))(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, window_shape=(2, 2), strides=(2, 2))
        x = nn.Conv(features=32, kernel_size=(3, 3))(x)
        x = nn.relu(x)
        x = nn.avg_pool(x, window_shape=(2, 2), strides=(2, 2))
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(features=128)(x)
        x = nn.relu(x)
        return nn.Dense(features=10)(x)


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=300)
    p.add_argument("--batch-size", type=int, default=128)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    ctx = distributed.initialize_from_env()
    compile_cache.enable()
    print(f"mnist: devices={jax.devices()} rank={ctx.rank}/"
          f"{ctx.num_nodes}", flush=True)

    model = CNN()
    images, labels = synthetic_data.mnist_like(args.seed, 8192)
    test_x, test_y = synthetic_data.mnist_like(args.seed + 1, 1024)

    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, 28, 28, 1)))
    tx = optax.adam(args.lr)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        def loss_fn(params):
            logits = model.apply(params, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state, loss

    @jax.jit
    def accuracy(params, x, y):
        return jnp.mean(jnp.argmax(model.apply(params, x), -1) == y)

    sky_callback.init(total_steps=args.steps)
    t0 = time.time()
    loss = None
    for x, y in sky_callback.step_iterator(
            synthetic_data.batches((images, labels), args.batch_size,
                                   args.seed, args.steps)):
        params, opt_state, loss = step(params, opt_state, x, y)
    loss.block_until_ready()
    sky_callback.flush()

    acc = float(accuracy(params, test_x, test_y))
    metrics = {
        "recipe": "mnist",
        "steps": args.steps,
        "final_loss": float(loss),
        "test_accuracy": acc,
        "wall_seconds": round(time.time() - t0, 2),  # noqa: stpu-wallclock workload wall-time report
    }
    print(json.dumps(metrics), flush=True)
    if args.steps >= 100 and acc < 0.8:
        raise SystemExit(f"mnist accuracy {acc:.3f} below 0.8 — "
                         f"training did not converge")
    return metrics


if __name__ == "__main__":
    main()
