"""Plain reference for the Brumby-14B decoder block, independent of
``skypilot_tpu/models/brumby.py``: Qwen3-14B's block (pre-norm RMSNorm,
grouped heads with a per-head RMSNorm on queries and keys, rotary
embeddings in the half-split "rotate_half" layout, SwiGLU MLP, untied
head) with softmax attention replaced by power retention of degree 2,
in its ATTENTION form over the whole sequence (Manifest AI, "Scaling
Context Requires Rethinking Attention", arXiv:2507.04239): query head
``a`` reads key/value head ``a // group``, the gate is one scalar a
key/value head a token, ``log g = log_sigmoid(h W_g)``, and with ``B_i``
its running sum

    w_ij = (q_i . k_j)^2 / head_dim * exp(B_i - B_j)      for j <= i
    y_i  = sum_j w_ij v_j / (sum_j w_ij + eps)

float32 under ``default_matmul_precision("highest")``. No feature map,
no state, no chunks, no cache, no kernels, no batching: one sequence, a
Python loop over layers, inside it one over the key/value heads (each
with the query heads that read it: ``wo`` is linear, so their parts add
up) and one over blocks of the MLP's columns, and the head computed a
slice of the vocabulary at a time: a layer's weights in float32 are
1.3 GB and the head's 3.1 GB, beside a server that fills the chip.

It reads the program's parameter tree (stacked layers: ``wq`` is
``(L, d, heads * head_dim)``, ``wg`` ``(L, d, kv_heads)`` and so on)
because the weights come from the program's own seeded init; nothing
else of the program is used.

Departures from the published description: none in the mathematics that
is written down above. What the published config has no key for is set
as the configuration file's ``assumed`` says (degree 2, the normalised
form, eps 1e-6, the gate's shape); the published kernels' option of
serving short contexts from a key/value cache (``switch_over_seq_len``)
is left out, here and in the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_SLICES = 16
MLP_SLICES = 8
# Share of the positions held to the token rule (``runners/serve.py``)
# that may disagree all the same: none. A dense model makes no discrete
# choice on the way to its logits. Measured on the chip at the published
# widths (PERF.md, PR 33): the program's bf16 forward leaves these
# logits by at most 0.26-0.40 (over 151,936 logits of some 250 rows a
# request; the GQA models read 0.08: a weight (q . k)^2 has a zero
# where softmax's exp has none, so bf16's rounding of q and k weighs
# more in a row whose undecayed weights are small), yet in 21 runs no
# served token disagreed above a reference margin of 0.18, none of
# 4,293 positions over the 0.3 margin; with the state kept in bfloat16
# (the precision below the one the configuration states) 1 of 179 and
# 3 of 174 did, at margins up to 1.09, and both runs came out not
# correct.
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


def retention_group(x, lw, *, group, head_dim, theta, eps,
                    retention_eps):
    """What ONE key/value head and the ``group`` query heads that read
    it add to the residual stream. x: (S, d); ``lw`` holds the input
    norm, the two per-head norms and that head's columns of ``wq``,
    ``wk``, ``wv``, ``wg`` and rows of ``wo``. The block's output is
    the sum of these over the key/value heads (``wo`` is linear)."""
    s = x.shape[0]
    h = rms_norm(x, lw["attn_norm"], eps)
    q = (h @ lw["wq"].astype(F32)).reshape(s, group, head_dim)
    k = (h @ lw["wk"].astype(F32)).reshape(s, 1, head_dim)
    v = h @ lw["wv"].astype(F32)                              # (S, HD)
    q = rotary(rms_norm(q, lw["q_norm"], eps), theta)
    k = rotary(rms_norm(k, lw["k_norm"], eps), theta)[:, 0]
    log_gate = jax.nn.log_sigmoid(h @ lw["wg"].astype(F32))[:, 0]
    run = jnp.cumsum(log_gate)                                # B_i
    causal = jnp.tril(jnp.ones((s, s), bool))
    # exp(B_i - B_j), masked before the exponential: above the
    # diagonal the difference is positive and may overflow.
    decay = jnp.exp(jnp.where(causal, run[:, None] - run[None, :],
                              -jnp.inf))
    scores = jnp.einsum("qgd,kd->gqk", q, k)
    w = jnp.square(scores) / F32(head_dim) * decay[None]
    num = jnp.einsum("gqk,kd->qgd", w, v)
    den = jnp.sum(w, axis=-1).T[..., None] + F32(retention_eps)
    return (num / den).reshape(s, -1) @ lw["wo"].astype(F32)


def swiglu_columns(x, lw, eps):
    """What one block of the MLP's columns adds to the residual stream
    (``w_down`` is linear, so the blocks' parts add up)."""
    y = rms_norm(x, lw["mlp_norm"], eps)
    gate = jax.nn.silu(y @ lw["w_gate"].astype(F32))
    up = y @ lw["w_up"].astype(F32)
    return (gate * up) @ lw["w_down"].astype(F32)


@functools.partial(jax.jit, static_argnames=("shape",))
def _retention_group(x, lw, shape):
    n_heads, n_kv_heads, head_dim, theta, eps, retention_eps = shape
    with jax.default_matmul_precision("highest"):
        return retention_group(
            x, lw, group=n_heads // n_kv_heads, head_dim=head_dim,
            theta=theta, eps=eps, retention_eps=retention_eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _swiglu_columns(x, lw, eps):
    with jax.default_matmul_precision("highest"):
        return swiglu_columns(x, lw, eps)


def layer(x, stack, i, shape):
    """Layer ``i`` of the stacked parameters, a key/value head and an
    eighth of the MLP's columns at a time, each cut out of the stack
    where it lies: in float32 a layer's weights are 1.3 GB and its
    (heads, S, S) weights as much again at S = 1280, beside a server
    that leaves the chip 3 GB (my chip run, PR 33: a whole layer at once
    took the process's peak from 13.7 to 15.3 GB for one request and to
    16.3 of 16.9 for four)."""
    n_heads, n_kv_heads, head_dim = shape[:3]
    wide = n_heads // n_kv_heads * head_dim
    norms = {k: stack[k][i] for k in ("attn_norm", "q_norm", "k_norm")}
    for j in range(n_kv_heads):
        q_cols = slice(j * wide, (j + 1) * wide)
        kv_cols = slice(j * head_dim, (j + 1) * head_dim)
        part = {**norms, "wq": stack["wq"][i, :, q_cols],
                "wk": stack["wk"][i, :, kv_cols],
                "wv": stack["wv"][i, :, kv_cols],
                "wg": stack["wg"][i, :, j:j + 1],
                "wo": stack["wo"][i, q_cols]}
        add = _retention_group(x, part, shape)
        total = add if j == 0 else total + add
    x = x + total
    ff = stack["w_gate"].shape[2]
    edges = [ff * b // MLP_SLICES for b in range(MLP_SLICES + 1)]
    for b, (lo, hi) in enumerate(zip(edges, edges[1:])):
        add = _swiglu_columns(
            x, {"mlp_norm": stack["mlp_norm"][i],
                "w_gate": stack["w_gate"][i, :, lo:hi],
                "w_up": stack["w_up"][i, :, lo:hi],
                "w_down": stack["w_down"][i, lo:hi]}, shape[4])
        mlp = add if b == 0 else mlp + add
    return x + mlp


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, final_norm, eps):
    return rms_norm(x, final_norm, eps)


@jax.jit
def _head_slice(x, head):
    with jax.default_matmul_precision("highest"):
        return x @ head.astype(F32)


def shape_of(cfg) -> tuple:
    return (cfg.n_heads, cfg.n_kv_heads, cfg.dim // cfg.n_heads,
            float(cfg.rope_theta), float(cfg.norm_eps),
            float(cfg.retention_eps))


def logits(cfg, params, tokens, rows=None):
    """float32 logits of one sequence. ``tokens``: (S,) ints. ``rows``
    (optional index array) keeps only those positions' rows of the
    head."""
    shape = shape_of(cfg)
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    for i in range(params["layers"]["wq"].shape[0]):
        x = layer(x, params["layers"], i, shape)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    x = _normed(x, params["final_norm"], shape[4])
    head = params["lm_head"]
    edges = [head.shape[1] * i // HEAD_SLICES
             for i in range(HEAD_SLICES + 1)]
    return jnp.concatenate(
        [_head_slice(x, head[:, lo:hi])
         for lo, hi in zip(edges, edges[1:])], axis=-1)


def logits_and_slack(cfg, params, tokens, rows=None):
    """(logits, None): a dense model makes no discrete choice on the
    way to its logits (see ``mixtral_arch.logits_and_slack``)."""
    return logits(cfg, params, tokens, rows), None
