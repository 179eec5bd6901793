"""Plain reference for the Llama/Mistral decoder block, independent of
``skypilot_tpu/models/llama.py``: the forward pass as the published
architecture describes it (pre-norm RMSNorm, grouped-query attention with
rotary embeddings in the half-split "rotate_half" layout, SwiGLU MLP,
untied or tied output head), in straightforward ``jax.numpy`` and
float32 under ``default_matmul_precision("highest")``. No cache, no
kernels, no batching, no scan: one sequence, a Python loop over layers,
one layer's weights cast to float32 at a time (a float32 copy of the
model does not fit on the chip beside the server's bf16 one).

It reads the program's parameter tree (stacked layers: ``wq`` is
``(L, d, heads * head_dim)`` and so on) because the weights come from
the program's own seeded init; nothing else of the program is used.

Departures from the published description: none in the mathematics.
Mistral-7B-v0.3 has no sliding window; a configuration that sets one is
refused.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
# Share of the positions held to the token rule (``runners/serve.py``)
# that may disagree all the same: none. A dense model makes no discrete
# choice on the way to its logits, and in 26 runs on the chip no served
# token disagreed above a reference margin of 0.041 (PERF.md, PR 24).
TOLERATED_SHARE = 0.0


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def rotary(x, theta):
    """x: (S, H, D). Position i rotates the pairs (x[j], x[j + D/2])."""
    s, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], axis=-1)


def attention(x, lw, *, n_heads, n_kv_heads, head_dim, theta, eps):
    """One pre-norm attention block with its residual. x: (S, d)."""
    s = x.shape[0]
    y = rms_norm(x, lw["attn_norm"], eps)
    q = (y @ lw["wq"].astype(F32)).reshape(s, n_heads, head_dim)
    k = (y @ lw["wk"].astype(F32)).reshape(s, n_kv_heads, head_dim)
    v = (y @ lw["wv"].astype(F32)).reshape(s, n_kv_heads, head_dim)
    q, k = rotary(q, theta), rotary(k, theta)
    group = n_heads // n_kv_heads
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(head_dim))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, -1)
    return x + out @ lw["wo"].astype(F32)


def swiglu(x, lw, eps):
    y = rms_norm(x, lw["mlp_norm"], eps)
    gate = jax.nn.silu(y @ lw["w_gate"].astype(F32))
    up = y @ lw["w_up"].astype(F32)
    return x + (gate * up) @ lw["w_down"].astype(F32)


@functools.partial(jax.jit, static_argnames=("shape",))
def _layer(x, lw, shape):
    n_heads, n_kv_heads, head_dim, theta, eps = shape
    with jax.default_matmul_precision("highest"):
        x = attention(x, lw, n_heads=n_heads, n_kv_heads=n_kv_heads,
                      head_dim=head_dim, theta=theta, eps=eps)
        return swiglu(x, lw, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm, eps) @ head.astype(F32)


def shape_of(cfg) -> tuple:
    if getattr(cfg, "sliding_window", None):
        raise ValueError("the reference has no sliding window")
    return (cfg.n_heads, cfg.n_kv_heads, cfg.dim // cfg.n_heads,
            float(cfg.rope_theta), float(cfg.norm_eps))


def logits(cfg, params, tokens, rows=None):
    """float32 logits of one sequence. ``tokens``: (S,) ints. ``rows``
    (optional index array) keeps only those positions' rows of the head,
    which is where the memory goes at a 32k vocabulary."""
    shape = shape_of(cfg)
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    n_layers = params["layers"]["wq"].shape[0]
    for i in range(n_layers):
        lw = {k: v[i] for k, v in params["layers"].items()}
        x = _layer(x, lw, shape)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    head = (params["lm_head"] if "lm_head" in params
            else params["embed"].T)
    return _head(x, params["final_norm"], head, shape[-1])


def logits_and_slack(cfg, params, tokens, rows=None):
    """(logits, None): a dense model makes no discrete choice on the
    way to its logits (see ``mixtral_arch.logits_and_slack``)."""
    return logits(cfg, params, tokens, rows), None


def loss(cfg, params, tokens):
    """Mean next-token cross-entropy of a (B, S) batch, as a trainer
    defines it: positions 0..S-2 predict tokens 1..S-1."""
    total, count = 0.0, 0
    for seq in tokens:
        lg = logits(cfg, params, seq[:-1])
        logp = jax.nn.log_softmax(lg, axis=-1)
        tgt = jnp.asarray(seq[1:])
        total += float(-jnp.sum(
            jnp.take_along_axis(logp, tgt[:, None], axis=-1)))
        count += int(tgt.shape[0])
    return total / count
