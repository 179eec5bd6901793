"""Flash-attention kernel microbenchmark on the chip (a TPU v5e: it
fails on anything else, since its peak is that chip's).

Measures the fwd kernel (and fwd+bwd) as a fraction of USEFUL-work peak:
useful FLOPs count only the causally-unmasked half of the score matrix,
so a perfect kernel that skipped all masked work would score 100%.

The kernel runs N iterations INSIDE one jit via lax.scan, so one
dispatch is spread over all of them, and timing ends with a value
fetch, which waits for the device.

Usage:  python tools/bench_flash.py [--seq 8192] [--iters 20]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
from jax import lax

from skypilot_tpu.ops.pallas import flash_attention as fa
from skypilot_tpu.parallel import mesh as mesh_lib

V5E_PEAK = 197e12


def causal_flops(b, s, h, d, bwd: bool = False) -> float:
    """Useful MXU FLOPs: 2 dots fwd (qk^T, pv), 5 dots bwd
    (qk^T recompute, dp=do v^T, dq=ds k, dk=ds^T q, dv=p^T do),
    each 2*s*s*d/2 (causal half) per head."""
    per_dot = 2 * s * s * d * 0.5
    n_dots = 5 if bwd else 2
    return b * h * n_dots * per_dot


def _time_best(run, args, reps: int = 3) -> float:
    float(run(*args))  # warm-up (compile) + force
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        float(run(*args))
        best = min(best, time.perf_counter() - t0)
    return best


def bench(fn, args, iters: int) -> float:
    """Seconds per iteration: `iters` chained applications inside ONE
    jit (scan), forced with a value fetch; best of 3."""

    def body(c, _):
        out = fn(*c[:3]).astype(c[0].dtype)
        # Chain the output into q so iterations can't be elided.
        return (out, c[1], c[2]), ()

    @jax.jit
    def run(q, k, v):
        (qf, _, _), _ = lax.scan(body, (q, k, v), None, length=iters)
        return jnp.sum(qf.astype(jnp.float32))

    return _time_best(run, args) / iters


def bench_bwd(fn, args, iters: int) -> float:
    grad = jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v)
                                            .astype(jnp.float32)),
                    argnums=(0, 1, 2))

    def body(c, _):
        dq, dk, dv = grad(*c)
        return (dq.astype(c[0].dtype), dk.astype(c[1].dtype),
                dv.astype(c[2].dtype)), ()

    @jax.jit
    def run(q, k, v):
        (dq, _, _), _ = lax.scan(body, (q, k, v), None, length=iters)
        return jnp.sum(dq.astype(jnp.float32))

    return _time_best(run, args) / iters


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seq", type=int, default=8192)
    p.add_argument("--batch", type=int, default=1)
    p.add_argument("--heads", type=int, default=16)
    p.add_argument("--kv-heads", type=int, default=8)
    p.add_argument("--dim", type=int, default=128)
    p.add_argument("--iters", type=int, default=40)
    p.add_argument("--block-q", type=int, default=fa.DEFAULT_BLOCK_Q)
    p.add_argument("--block-k", type=int, default=fa.DEFAULT_BLOCK_K)
    args = p.parse_args()
    device = mesh_lib.device_info()
    if device["platform"] != "tpu" or "v5 lite" not in device["kind"]:
        raise SystemExit(
            f"bench_flash: needs a TPU v5e (its peak is the "
            f"denominator); JAX reports {device}")

    b, s, h, d = args.batch, args.seq, args.heads, args.dim
    kvh = args.kv_heads
    key = jax.random.key(0)
    q = jax.random.normal(key, (b, s, h, d), dtype=jnp.bfloat16)
    k = jax.random.normal(key, (b, s, kvh, d), dtype=jnp.bfloat16)
    v = jax.random.normal(key, (b, s, kvh, d), dtype=jnp.bfloat16)

    def attn(q, k, v):
        return fa.flash_attention(q, k, v, causal=True,
                                  block_q=args.block_q,
                                  block_k=args.block_k)

    fwd_dt = bench(attn, (q, k, v), args.iters)
    fwd_fl = causal_flops(b, s, h, d)
    fwd_tfs = fwd_fl / fwd_dt / 1e12

    fb_dt = bench_bwd(attn, (q, k, v), max(4, args.iters // 2))
    # grad-of-sum reruns the fwd (vjp fwd) + bwd: 2 + 5 dots.
    fb_fl = causal_flops(b, s, h, d) + causal_flops(b, s, h, d, bwd=True)
    fb_tfs = fb_fl / fb_dt / 1e12

    print(json.dumps({
        "device": device,
        "shape": {"b": b, "s": s, "h": h, "kvh": kvh, "d": d},
        "blocks": [args.block_q, args.block_k],
        "fwd_ms": round(fwd_dt * 1e3, 3),
        "fwd_tflops": round(fwd_tfs, 2),
        "fwd_pct_useful_peak": round(fwd_tfs / (V5E_PEAK / 1e12) * 100,
                                     2),
        "fwdbwd_ms": round(fb_dt * 1e3, 3),
        "fwdbwd_tflops": round(fb_tfs, 2),
        "fwdbwd_pct_useful_peak": round(
            fb_tfs / (V5E_PEAK / 1e12) * 100, 2),
    }))


if __name__ == "__main__":
    main()
