"""Text-classification finetune — huggingface_glue_imdb named config.

Reference analog: examples/huggingface_glue_imdb_app.yaml (BERT finetune on
IMDB via HF Trainer). Native version: a small transformer encoder
classifier in flax over hermetic sentiment data; 1 node, CPU-runnable (the
BASELINE.md contract for this config).

    python -m skypilot_tpu.recipes.glue_imdb --steps 200
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn

from skypilot_tpu.recipes import synthetic_data
from skypilot_tpu.train import distributed
from skypilot_tpu.utils import compile_cache


class EncoderBlock(nn.Module):
    dim: int
    heads: int

    @nn.compact
    def __call__(self, x):
        y = nn.LayerNorm()(x)
        y = nn.MultiHeadDotProductAttention(num_heads=self.heads)(y, y)
        x = x + y
        y = nn.LayerNorm()(x)
        y = nn.Dense(self.dim * 4)(y)
        y = nn.gelu(y)
        return x + nn.Dense(self.dim)(y)


class TextClassifier(nn.Module):
    vocab_size: int = 1000
    dim: int = 64
    heads: int = 4
    n_layers: int = 2
    n_classes: int = 2

    @nn.compact
    def __call__(self, tokens):
        x = nn.Embed(self.vocab_size, self.dim)(tokens)
        pos = self.param("pos", nn.initializers.normal(0.02),
                         (tokens.shape[-1], self.dim))
        x = x + pos
        for _ in range(self.n_layers):
            x = EncoderBlock(self.dim, self.heads)(x)
        x = nn.LayerNorm()(x)
        return nn.Dense(self.n_classes)(x.mean(axis=1))


def main(argv=None) -> dict:
    p = argparse.ArgumentParser()
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch-size", type=int, default=64)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    distributed.initialize_from_env()
    compile_cache.enable()
    model = TextClassifier()
    tokens, labels = synthetic_data.imdb_like(args.seed, 4096,
                                              seq_len=args.seq_len)
    test_x, test_y = synthetic_data.imdb_like(args.seed + 1, 512,
                                              seq_len=args.seq_len)

    params = model.init(jax.random.PRNGKey(args.seed),
                        jnp.zeros((1, args.seq_len), jnp.int32))
    tx = optax.adamw(args.lr)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        def loss_fn(params):
            logits = model.apply(params, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    @jax.jit
    def accuracy(params, x, y):
        return jnp.mean(jnp.argmax(model.apply(params, x), -1) == y)

    t0 = time.time()
    loss = None
    for x, y in synthetic_data.batches((tokens, labels), args.batch_size,
                                       args.seed, args.steps):
        params, opt_state, loss = step(params, opt_state, x, y)
    loss.block_until_ready()

    acc = float(accuracy(params, test_x, test_y))
    metrics = {
        "recipe": "glue_imdb",
        "steps": args.steps,
        "final_loss": float(loss),
        "test_accuracy": acc,
        "wall_seconds": round(time.time() - t0, 2),  # noqa: stpu-wallclock workload wall-time report
    }
    print(json.dumps(metrics), flush=True)
    if args.steps >= 150 and acc < 0.75:
        raise SystemExit(f"glue_imdb accuracy {acc:.3f} below 0.75")
    return metrics


if __name__ == "__main__":
    main()
