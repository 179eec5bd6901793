"""Plain reference for the DeepSeek-V3 decoder block, independent of
``skypilot_tpu/models/deepseek.py``: the forward pass as the published
architecture describes it, in straightforward ``jax.numpy`` and float32
under ``default_matmul_precision("highest")``. No cache, no kernels, no
batching, no scan, and only the EXPANDED form of attention: one
sequence, a Python loop over layers, over blocks of heads, over column
blocks of the dense MLP and over the held experts, so that it fits on
the chip beside the server's bf16 copy.

It reads the program's parameter tree (two stacks, ``dense_layers`` and
``moe_layers``; ``wq_nope``/``wq_rope`` are ``q_b_proj`` and
``w_uk``/``w_uv`` ``kv_b_proj``, split by what they make) because the weights come from the program's own seeded init;
nothing else of the program is used.

The layer, token at position p (x its input, rms = RMSNorm):

  attention   c_q = rms(x W_qa); q = c_q W_qb -> per head [q_nope; q_rope];
              [c_kv; k_r] = x W_kva; c_kv = rms(c_kv);
              q_rope, k_r rotated by YaRN's frequencies at p (k_r is one
              for all heads); per head k = [c_kv W_UK_h; k_r],
              v = c_kv W_UV_h; scores q . k * (192^-1/2 * m^2),
              m = 0.1 * mscale_all_dim * ln(factor) + 1; causal softmax;
              output concat_h(o_h) W_o.
  dense MLP   SwiGLU, layers < first_k_dense_replace.
  experts     s = sigmoid(x W_r) in float32; s^ = s + b; a group's score
              is the sum of its two largest s^; the topk_group best
              groups are kept and the top_k largest s^ among their
              experts chosen; g_e = routed_scaling_factor * s_e / sum of
              the chosen s; y = sum over chosen AND HELD e of g_e E_e(x)
              + E_shared(x), each E a SwiGLU.

Departures from the published description. (1) The held share: with
``ep_size`` ranks the layer holds experts ``ep_rank * held ..`` and adds
only their part of the routed sum; what the absent ranks' experts would
add is left out, here and in the program alike, and that partial result
goes on to the next layer. (2) The multi-token-prediction module
(``num_nextn_predict_layers``) is left out: it takes no part in the
next-token forward pass. (3) Every held expert is computed for every
token and weighted by zero where it was not chosen — the same function,
written without a gather.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
# Share of the positions held to the token rule (``runners/serve.py``)
# that may disagree all the same: as for Mixtral. Leaving out the
# positions whose OWN routing is near a tie does not reach every effect
# of a routing choice: a token whose held expert the engine chose
# differently has another latent row in the layers above, and a later
# position that attends to it strongly inherits a part of that
# difference. An engine that computes in a lower precision, or reads
# another request's cache, disagrees on tens of a hundred.
TOLERATED_SHARE = 0.03
# Heads attended together, columns of the dense MLP multiplied
# together: 16 x 1280 x 1280 float32 scores are 105 MB.
HEAD_BLOCK = 16
MLP_BLOCK = 2048


def rms_norm(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(F32)


def yarn(cfg):
    """(rotary frequencies (rope/2,), softmax scale) from the published
    ``rope_scaling``: frequency i is ``theta^(-2i/d)``, kept where it
    turns more than ``beta_fast`` times over the original context,
    divided by ``factor`` where it turns fewer than ``beta_slow`` times,
    and blended linearly over the dimensions between."""
    d, theta = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    scaling = dict(cfg.rope_scaling or ())
    freq = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    scale = (cfg.qk_nope_head_dim + d) ** -0.5
    if not scaling:
        return freq.astype(np.float32), scale
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])
    # The (fractional) dimension whose wave turns `n` times over the
    # original context: theta^(2i/d) * 2 pi * n = orig.
    dim_at = lambda n: d * math.log(orig / (2 * math.pi * n)) / (
        2 * math.log(theta))
    low = max(math.floor(dim_at(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim_at(scaling["beta_slow"])), d - 1)
    blend = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3),
                    0.0, 1.0)
    freq = freq * (1.0 - blend) + freq / factor * blend
    m = 0.1 * float(scaling["mscale_all_dim"]) * math.log(factor) + 1.0
    return freq.astype(np.float32), scale * m * m


def rotary(x, freq):
    """x: (S, H, R). Position p rotates each PAIR (x[2i], x[2i + 1]) by
    p * freq[i], as the published checkpoints lay the values out."""
    s = x.shape[0]
    ang = jnp.arange(s, dtype=F32)[:, None] * jnp.asarray(freq)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


@functools.partial(jax.jit, static_argnames=("c", "eps"))
def _latents(x, attn_norm, wq_a, q_norm, wkv_a, kv_norm, freq, c, eps):
    """(c_q, c_kv, rotated k_r (S, R)) of one layer's input."""
    with jax.default_matmul_precision("highest"):
        y = rms_norm(x, attn_norm, eps)
        c_q = rms_norm(y @ wq_a.astype(F32), q_norm, eps)
        kv = y @ wkv_a.astype(F32)
        c_kv = rms_norm(kv[:, :c], kv_norm, eps)
        return c_q, c_kv, rotary(kv[:, None, c:], freq)[:, 0]


@functools.partial(jax.jit, static_argnames=("scale",))
def _heads(c_q, c_kv, k_r, wq_nope, wq_rope, w_uk, w_uv, wo, freq, scale):
    """What a block of heads adds to the layer's output. The per-head
    projections lie head-major, as the program's tree holds them:
    wq_nope (heads, nope, Q) and wq_rope (rope, heads, Q) are
    ``q_b_proj``, w_uk (heads, nope, C) and w_uv (heads, C, v) are
    ``kv_b_proj``; wo: (heads * v, d)."""
    s = c_q.shape[0]
    with jax.default_matmul_precision("highest"):
        q_nope = jnp.einsum("sq,hnq->shn", c_q, wq_nope.astype(F32))
        q_rope = rotary(jnp.einsum("sq,rhq->shr", c_q,
                                   wq_rope.astype(F32)), freq)
        k_nope = jnp.einsum("sc,hnc->shn", c_kv, w_uk.astype(F32))
        v = jnp.einsum("sc,hcv->shv", c_kv, w_uv.astype(F32))
        scores = (jnp.einsum("qhd,khd->hqk", q_nope, k_nope)
                  + jnp.einsum("qhr,kr->hqk", q_rope, k_r)) * scale
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf),
                               axis=-1)
        out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, -1)
        return out @ wo.astype(F32)


def attention(cfg, x, stack, i, freq, scale):
    """One pre-norm latent-attention block with its residual, the heads
    in blocks."""
    v = cfg.v_head_dim
    c_q, c_kv, k_r = _latents(
        x, stack["attn_norm"][i], stack["wq_a"][i], stack["q_norm"][i],
        stack["wkv_a"][i], stack["kv_norm"][i], freq,
        c=cfg.kv_lora_rank, eps=float(cfg.norm_eps))
    for h0 in range(0, cfg.n_heads, HEAD_BLOCK):
        h1 = min(h0 + HEAD_BLOCK, cfg.n_heads)
        x = x + _heads(
            c_q, c_kv, k_r, stack["wq_nope"][i, h0:h1],
            stack["wq_rope"][i, :, h0:h1], stack["w_uk"][i, h0:h1],
            stack["w_uv"][i, h0:h1], stack["wo"][i, h0 * v:h1 * v],
            freq, scale=scale)
    return x


@jax.jit
def _swiglu(acc, y, weight, w_gate, w_up, w_down):
    """acc + weight * SwiGLU(y) through (a column block of) one MLP."""
    with jax.default_matmul_precision("highest"):
        h = jax.nn.silu(y @ w_gate.astype(F32)) * (y @ w_up.astype(F32))
        return acc + weight[:, None] * (h @ w_down.astype(F32))


def _swiglu_blocked(acc, y, weight, w_gate, w_up, w_down):
    for c0 in range(0, w_gate.shape[1], MLP_BLOCK):
        acc = _swiglu(acc, y, weight, w_gate[:, c0:c0 + MLP_BLOCK],
                      w_up[:, c0:c0 + MLP_BLOCK],
                      w_down[c0:c0 + MLP_BLOCK])
    return acc


@functools.partial(jax.jit, static_argnames=("shape",))
def _route(x, mlp_norm, router, bias, shape):
    """(normed input, weights (S, E) over ALL routed experts — zero
    where not chosen —, slack (S,)). ``slack`` is the distance, in
    router logits, from the nearest tie that can change the HELD
    experts' part of the result: a chosen held expert against the best
    expert left out, the last expert chosen against the best held one
    left out, and the same between the groups kept and left out that
    hold held experts. A tie between two absent experts moves this
    rank's result only through the renormalisation, continuously, and
    does not count. A gap between two biased scores is turned into
    logits by the slope of the sigmoid AT the scores involved (the
    larger of the two; a group's score by the sum of its two members'
    slopes, the largest over the groups): two logits a gap apart meet
    when each moves half of it, which is the unit of Mixtral's slack."""
    n_group, topk_group, top_k, scaling, lo, hi, eps = shape
    e = router.shape[1]
    with jax.default_matmul_precision("highest"):
        y = rms_norm(x, mlp_norm, eps)
        s = jax.nn.sigmoid(y @ router.astype(F32))
    sb = s + bias.astype(F32)
    slope = jnp.maximum(s * (1.0 - s), 1e-6)
    at = lambda idx: jnp.take_along_axis(slope, idx[:, None], -1)[:, 0]
    held = (jnp.arange(e) >= lo) & (jnp.arange(e) < hi)
    inf = jnp.inf

    groups = sb.reshape(-1, n_group, e // n_group)
    top2, top2_at = jax.lax.top_k(groups, 2)
    group_score = jnp.sum(top2, axis=-1)
    group_slope = jnp.max(jnp.sum(jnp.take_along_axis(
        slope.reshape(groups.shape), top2_at, -1), axis=-1), axis=-1)
    ranked = jnp.sort(group_score, axis=-1)[:, ::-1]
    kept = group_score >= ranked[:, topk_group - 1:topk_group]
    group_holds = jnp.any(held.reshape(n_group, -1), axis=-1)
    group_gap = jnp.minimum(
        jnp.min(jnp.where(kept & group_holds, group_score, inf), -1)
        - ranked[:, topk_group],
        ranked[:, topk_group - 1]
        - jnp.max(jnp.where(~kept & group_holds, group_score, -inf), -1))

    candidate = jnp.where(jnp.repeat(kept, e // n_group, axis=-1), sb,
                          -inf)
    best, best_at = jax.lax.top_k(candidate, top_k + 1)
    last_in, first_out = best[:, top_k - 1], best[:, top_k]
    chosen = candidate >= last_in[:, None]
    weakest_held = jnp.where(chosen & held, candidate, inf)
    best_held_out = jnp.where(~chosen & held, candidate, -inf)
    expert_slack = jnp.minimum(
        (jnp.min(weakest_held, -1) - first_out) / jnp.maximum(
            at(jnp.argmin(weakest_held, -1)), at(best_at[:, top_k])),
        (last_in - jnp.max(best_held_out, -1)) / jnp.maximum(
            at(best_at[:, top_k - 1]), at(jnp.argmax(best_held_out, -1))))
    weights = jnp.where(chosen, s, 0.0)
    weights = scaling * weights / jnp.sum(weights, axis=-1, keepdims=True)
    return y, weights, jnp.minimum(expert_slack, group_gap / group_slope)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, w, eps):
    return rms_norm(x, w, eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, final_norm, head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, final_norm, eps) @ head.astype(F32)


def logits(cfg, params, tokens, rows=None):
    """float32 logits of one sequence; see ``llama_arch.logits``."""
    return logits_and_slack(cfg, params, tokens, rows)[0]


def logits_and_slack(cfg, params, tokens, rows=None):
    """(logits, slack): ``slack`` is, for every kept row, the least of
    ``_route``'s over the sparse layers (see
    ``mixtral_arch.logits_and_slack`` for why a check needs it)."""
    eps = float(cfg.norm_eps)
    freq, scale = yarn(cfg)
    lo = cfg.ep_rank * cfg.n_experts_held
    shape = (cfg.n_group, cfg.topk_group, cfg.top_k,
             float(cfg.routed_scaling_factor), lo,
             lo + cfg.n_experts_held, eps)
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    slack = jnp.full((x.shape[0],), jnp.inf, F32)
    ones = jnp.ones((x.shape[0],), F32)
    dense = params["dense_layers"]
    for i in range(dense["attn_norm"].shape[0]):
        x = attention(cfg, x, dense, i, freq, scale)
        x = _swiglu_blocked(x, _normed(x, dense["mlp_norm"][i], eps),
                            ones, dense["w_gate"][i], dense["w_up"][i],
                            dense["w_down"][i])
    moe = params["moe_layers"]
    for i in range(moe["attn_norm"].shape[0]):
        x = attention(cfg, x, moe, i, freq, scale)
        y, weights, gap = _route(x, moe["mlp_norm"][i], moe["router"][i],
                                 moe["router_bias"][i], shape)
        slack = jnp.minimum(slack, gap)
        for e in range(cfg.n_experts_held):
            x = _swiglu(x, y, weights[:, lo + e], moe["we_gate"][i, e],
                        moe["we_up"][i, e], moe["we_down"][i, e])
        x = _swiglu(x, y, ones, moe["ws_gate"][i], moe["ws_up"][i],
                    moe["ws_down"][i])
    if rows is not None:
        x = x[jnp.asarray(rows)]
        slack = slack[jnp.asarray(rows)]
    return _head(x, params["final_norm"], params["lm_head"], eps), slack
