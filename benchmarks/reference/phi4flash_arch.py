"""Plain reference for Phi-4-mini-flash-reasoning (SambaY,
arXiv:2507.06607 "Decoder-Hybrid-Decoder Architecture for Efficient
Reasoning with Long Generation"), independent of
``skypilot_tpu/models/phi4flash.py``. Every layer ``i`` of ``L`` is

    x += mixer_i(LN(x));  x += (silu(g) * u) W_2,  [g, u] = LN(x) W_1

with a LayerNorm that has a bias, no positional encoding anywhere, and
the mixer by index:

  even i <= L/2     Mamba-1:  [u, z] = x W_in;  u = silu(conv_4(u) + b)
                    (causal, depthwise);  [d_r, B, C] = u W_x;
                    d = softplus(d_r W_dt + b_dt);  A = -exp(A_log);
                    h_t = exp(d_t A) * h_(t-1) + (d_t u_t) B_t^T  on
                    (E, N);  y_t = h_t C_t + D * u_t;  out (y * silu(z))
                    W_out.  Layer L/2 hands m = y to the memory units.
  odd i < L/2       differential attention under a window of W: the W
                    newest keys, the token's own included.  Heads pair
                    up in order: query pairs (q1, q2), key pairs (k1,
                    k2), v = [v1; v2]; query pair j reads key/value
                    pair j // 2;
                    o = (softmax(q1 k1^T / sqrt(hd)) - lam softmax(q2
                    k2^T / sqrt(hd))) v,  lam = exp(lq1 . lk1) -
                    exp(lq2 . lk2) + lam_init,  lam_init = 0.8 - 0.6
                    exp(-0.3 i);  o = RMSNorm(o) (1 - lam_init);  output
                    projection with bias.
  i = L/2 + 1       the same, no window: the one full layer.
  even i > L/2 + 1  gated memory unit:  (silu(x W_1) * m) W_2.
  odd i > L/2 + 1   cross attention on layer L/2 + 1's keys and values:
                    a query and an output projection (and lam) of its
                    own, no key/value projection.

float32 under ``default_matmul_precision("highest")``; a sequential
``lax.scan`` over the tokens for ``h``; two explicit softmaxes under one
causal-and-window mask; no cache, no chunks, no padded queries, no
kernels, no batching: one sequence, a Python loop over the layers, the
MLP a slice of its columns at a time and the head a slice of the
vocabulary at a time, so that a 1,280-token pass fits beside a server
that holds 7.7 GB of weights and its pools.

It reads the program's parameter tree (each kind of layer a stack inside
``front``, ``mid`` or ``back``; ``a_log`` is stored (N, E), the conv's
weight (4, E) with its last row on the current token) because the
weights come from the program's own seeded init; nothing else of the
program is used.

Departures from the published description: none in the mathematics
above. What the published config has no key for is set as the
configuration file's ``assumed`` says (Mamba's expand 2, conv 4, state
16, rank ceil(d / 16); the differential form, its pairing and
``lam_init``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HEAD_SLICES = 16
MLP_SLICES = 4
# Share of the positions held to the token rule (``runners/serve.py``)
# that may disagree all the same: none. A dense model makes no discrete
# choice on the way to its logits. The two readings it lies between,
# on the chip at the published widths (PERF.md, section 6, PR 36): the
# committed program's bf16 forward leaves these logits by 0.255-0.258
# at most, and in 26 served runs on 26 seeds no served token disagreed
# above a reference margin of 0.183, none of 5,285 positions over the
# 0.3 margin. With every weight rounded ONCE to a float8 mantissa (3
# bits, by integer arithmetic on the bfloat16's bits: 93.75 % of the
# elements changed; the precision below the stated bfloat16; this
# reference still reading the seeded weights) the forward leaves them
# by 3.01 and 117 of 184 positions over the margin disagreed (margins
# to 0.88): not correct. Also not correct: a state snapshot restored
# one chunk off (12 of 222, to 0.70) and a window mask 64 keys too wide
# (92 of 222, to 0.86). What the token rule does NOT see, and this
# share cannot be set to see: ``h`` kept in bfloat16 between steps (0
# of 425 in two runs, worst disagreeing margin 0.15) moves the logits
# by less than 0.1; tests/test_phi4flash.py holds it at the level of
# logits.
TOLERATED_SHARE = 0.0


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w.astype(F32) \
        + b.astype(F32)


def where_is(n_layers: int, i: int):
    """Layer ``i``'s (group, kind, index in the stack) in the tree."""
    half = n_layers // 2
    if i < half:
        return "front", ("ssm" if i % 2 == 0 else "attn"), i // 2
    if i <= half + 1:
        return "mid", ("ssm" if i == half else "attn"), 0
    return "back", ("gmu" if i % 2 == 0 else "cross"), (i - half - 2) // 2


def mamba(x, lw, eps):
    """(what the block adds to the residual stream, y before the gate).
    x: (S, d)."""
    s = x.shape[0]
    xn = layer_norm(x, lw["norm1_w"], lw["norm1_b"], eps)
    u, z = jnp.split(xn @ lw["in_proj"].astype(F32), 2, axis=-1)
    w = lw["conv_w"].astype(F32)                        # (taps, E)
    taps = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, u.shape[1]), F32), u])
    # Row j of the weight meets the input taps - 1 - j tokens back.
    u = sum(padded[j:j + s] * w[j] for j in range(taps))
    u = jax.nn.silu(u + lw["conv_b"].astype(F32))
    n = lw["a_log"].shape[0]
    dbc = u @ lw["x_proj"].astype(F32)
    r = dbc.shape[1] - 2 * n
    delta = jax.nn.softplus(dbc[:, :r] @ lw["dt_proj"].astype(F32)
                            + lw["dt_bias"].astype(F32))   # (S, E)
    b_t, c_t = dbc[:, r:r + n], dbc[:, r + n:]
    a = -jnp.exp(lw["a_log"].astype(F32)).T               # (E, N)

    def step(h, xs):
        d_t, u_t, bt, ct = xs
        h = jnp.exp(d_t[:, None] * a) * h \
            + (d_t * u_t)[:, None] * bt[None, :]
        return h, h @ ct

    _, y = jax.lax.scan(step, jnp.zeros(a.shape, F32),
                        (delta, u, b_t, c_t))
    y = y + lw["d_skip"].astype(F32) * u
    return (y * jax.nn.silu(z)) @ lw["out_proj"].astype(F32), y


def differential(q, k, v, lw, mask, lam_init, eps):
    """q: (S, heads, hd); k, v: (S, kv_heads, hd); mask (S, S). Returns
    what the block adds to the residual stream."""
    s, heads, hd = q.shape
    q = q.reshape(s, heads // 2, 2, hd)
    k = k.reshape(s, -1, 2, hd)
    v = v.reshape(s, k.shape[1], 2 * hd)                 # [v1; v2]
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)

    def soft(a, b):
        scores = jnp.einsum("qpd,kpd->pqk", a, b) / math.sqrt(hd)
        return jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf),
                              axis=-1)

    lam = (jnp.exp(jnp.dot(lw["lambda_q1"], lw["lambda_k1"]))
           - jnp.exp(jnp.dot(lw["lambda_q2"], lw["lambda_k2"])) + lam_init)
    w = soft(q[:, :, 0], k[:, :, 0]) - lam * soft(q[:, :, 1], k[:, :, 1])
    o = jnp.einsum("pqk,kpd->qpd", w, v)                  # (S, pairs, 2hd)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + eps) * lw["subln"].astype(F32)
    o = o * (1.0 - lam_init)
    return o.reshape(s, -1) @ lw["wo"].astype(F32) + lw["bo"].astype(F32)


def causal_mask(s: int, window: int = 0):
    q, k = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    mask = k <= q
    if window:
        mask &= k > q - window
    return mask


def attention(x, lw, *, heads, kv_heads, window, lam_init, eps):
    """(what the block adds, k, v)."""
    s = x.shape[0]
    hd = x.shape[1] // heads
    xn = layer_norm(x, lw["norm1_w"], lw["norm1_b"], eps)
    qkv = xn @ lw["wqkv"].astype(F32) + lw["bqkv"].astype(F32)
    q = qkv[:, :heads * hd].reshape(s, heads, hd)
    k = qkv[:, heads * hd:(heads + kv_heads) * hd].reshape(s, kv_heads, hd)
    v = qkv[:, (heads + kv_heads) * hd:].reshape(s, kv_heads, hd)
    return differential(q, k, v, lw, causal_mask(s, window), lam_init,
                        eps), k, v


def cross_attention(x, lw, k, v, *, heads, lam_init, eps):
    s = x.shape[0]
    xn = layer_norm(x, lw["norm1_w"], lw["norm1_b"], eps)
    q = (xn @ lw["wq"].astype(F32) + lw["bq"].astype(F32)).reshape(
        s, heads, -1)
    return differential(q, k, v, lw, causal_mask(s), lam_init, eps)


def memory_unit(x, lw, m, eps):
    xn = layer_norm(x, lw["norm1_w"], lw["norm1_b"], eps)
    return (jax.nn.silu(xn @ lw["in_proj"].astype(F32)) * m) \
        @ lw["out_proj"].astype(F32)


def swiglu_columns(x, lw, eps):
    """What one slice of the MLP's columns adds to the residual stream
    (``w_down`` is linear, so the slices' parts add up)."""
    y = layer_norm(x, lw["norm2_w"], lw["norm2_b"], eps)
    return (jax.nn.silu(y @ lw["w_gate"].astype(F32))
            * (y @ lw["w_up"].astype(F32))) @ lw["w_down"].astype(F32)


def _highest(fn):
    def run(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kwargs)
    return run


_mamba = jax.jit(_highest(mamba), static_argnames=("eps",))
_attention = jax.jit(_highest(attention), static_argnames=(
    "heads", "kv_heads", "window", "eps"))
_cross = jax.jit(_highest(cross_attention), static_argnames=(
    "heads", "eps"))
_unit = jax.jit(_highest(memory_unit), static_argnames=("eps",))
_swiglu_columns = jax.jit(_highest(swiglu_columns),
                          static_argnames=("eps",))


def mlp(x, stack, j, eps):
    """Layer ``j`` of ``stack``'s MLP, a slice of its columns at a time,
    each cut out of the stack where it lies. ``w_gu`` holds the gate's
    columns, then the up product's."""
    ff = stack["w_down"].shape[1]
    edges = [ff * b // MLP_SLICES for b in range(MLP_SLICES + 1)]
    total = 0.0
    for lo, hi in zip(edges, edges[1:]):
        total = total + _swiglu_columns(
            x, {"norm2_w": stack["norm2_w"][j],
                "norm2_b": stack["norm2_b"][j],
                "w_gate": stack["w_gu"][j, :, lo:hi],
                "w_up": stack["w_gu"][j, :, ff + lo:ff + hi],
                "w_down": stack["w_down"][j, lo:hi]}, eps)
    return x + total


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, w, b, eps):
    return layer_norm(x, w, b, eps)


@jax.jit
def _head_slice(x, rows):
    with jax.default_matmul_precision("highest"):
        return x @ rows.astype(F32).T


def logits(cfg, params, tokens, rows=None):
    """float32 logits of one sequence. ``tokens``: (S,) ints. ``rows``
    (optional index array) keeps only those positions' rows of the
    head."""
    eps, n_layers = float(cfg.norm_eps), int(cfg.n_layers)
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    m = keys = values = None
    for i in range(n_layers):
        group, kind, j = where_is(n_layers, i)
        stack = params[group][kind]
        lw = {name: leaf[j] for name, leaf in stack.items()
              if name not in ("w_gu", "w_down")}
        lam_init = 0.8 - 0.6 * math.exp(-0.3 * i)
        if kind == "ssm":
            add, y = _mamba(x, lw, eps=eps)
            if group == "mid":
                m = y
        elif kind == "attn":
            add, k, v = _attention(
                x, lw, heads=cfg.n_heads, kv_heads=cfg.n_kv_heads,
                window=cfg.sliding_window if group == "front" else 0,
                lam_init=lam_init, eps=eps)
            if group == "mid":
                keys, values = k, v
        elif kind == "gmu":
            add = _unit(x, lw, m, eps=eps)
        else:
            add = _cross(x, lw, keys, values, heads=cfg.n_heads,
                         lam_init=lam_init, eps=eps)
        x = mlp(x + add, stack, j, eps)
    if rows is not None:
        x = x[jnp.asarray(rows)]
    x = _normed(x, params["final_norm_w"], params["final_norm_b"], eps)
    head = params["embed"]                      # tied: (vocab, d)
    edges = [head.shape[0] * i // HEAD_SLICES
             for i in range(HEAD_SLICES + 1)]
    return jnp.concatenate(
        [_head_slice(x, head[lo:hi])
         for lo, hi in zip(edges, edges[1:])], axis=-1)


def logits_and_slack(cfg, params, tokens, rows=None):
    """(logits, None): a dense model makes no discrete choice on the
    way to its logits (see ``mixtral_arch.logits_and_slack``)."""
    return logits(cfg, params, tokens, rows), None
