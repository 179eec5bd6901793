"""Plain reference for the Mixtral decoder block, independent of
``skypilot_tpu/models/mixtral.py``: Llama's attention block
(``llama_arch``) and, in place of the MLP, the sparse mixture of experts
as published — router logits over all experts in float32, softmax, the
top ``num_experts_per_tok`` kept and renormalised to sum to one, each
kept expert a SwiGLU MLP, outputs summed with their weights. float32,
``default_matmul_precision("highest")``, no cache, no kernels, no
capacity and no dropping: every token reaches both its experts. One
expert's weights are cast to float32 at a time (a layer's eight are
5.6 GB in float32).

Departures from the published description: every expert is computed for
every token and weighted by zero where it was not chosen — the same
function, written without a gather.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmarks.reference import llama_arch

F32 = jnp.float32
# Share of the positions held to the token rule (``runners/serve.py``)
# that may disagree all the same. Leaving out the positions whose OWN
# routing is near a tie does not reach every effect of a routing choice:
# a token whose experts the engine chose differently has other keys and
# values in the layers above, and a later position that attends to it
# strongly inherits a part of that difference. Measured on the chip
# (PERF.md, PR 24): 1 disagreement (reference margin 0.43) among 737
# held positions of 10 runs, none in the other 9. 3 % of some 75 held
# positions is 2; an engine that computes in a lower precision, or reads
# another request's cache, disagrees on tens.
TOLERATED_SHARE = 0.03


@functools.partial(jax.jit, static_argnames=("shape",))
def _attention(x, lw, shape):
    n_heads, n_kv_heads, head_dim, theta, eps = shape
    with jax.default_matmul_precision("highest"):
        return llama_arch.attention(
            x, lw, n_heads=n_heads, n_kv_heads=n_kv_heads,
            head_dim=head_dim, theta=theta, eps=eps)


@functools.partial(jax.jit, static_argnames=("top_k", "eps"))
def _route(x, mlp_norm, router, top_k, eps):
    """(normed activations (S, d), weights (S, E)): softmax over all
    experts, top-k kept, renormalised."""
    with jax.default_matmul_precision("highest"):
        y = llama_arch.rms_norm(x, mlp_norm, eps)
        probs = jax.nn.softmax(y @ router.astype(F32), axis=-1)
        top, idx = jax.lax.top_k(probs, top_k)
        keep = jnp.sum(jax.nn.one_hot(idx, probs.shape[-1], dtype=F32),
                       axis=-2)
        # How far the choice is from a tie: the router logit of the last
        # expert kept less that of the first one left out.
        ranked = jax.lax.top_k(y @ router.astype(F32), top_k + 1)[0]
        slack = ranked[:, top_k - 1] - ranked[:, top_k]
        return (y, probs * keep / jnp.sum(top, axis=-1, keepdims=True),
                slack)


@jax.jit
def _expert(acc, y, weight, w_gate, w_up, w_down):
    with jax.default_matmul_precision("highest"):
        h = jax.nn.silu(y @ w_gate.astype(F32)) * (y @ w_up.astype(F32))
        return acc + weight[:, None] * (h @ w_down.astype(F32))


def logits(cfg, params, tokens, rows=None):
    """float32 logits of one sequence; see ``llama_arch.logits``."""
    return logits_and_slack(cfg, params, tokens, rows)[0]


def logits_and_slack(cfg, params, tokens, rows=None):
    """(logits, slack). ``slack`` is, for every kept row, the least
    distance in router logits, over the layers, between the last expert
    chosen for that token and the first one left out. Routing is a
    discrete choice: where the slack is smaller than the rounding that
    separates two implementations, they may rightly choose different
    experts, and the token's logits then differ by far more than
    rounding."""
    shape = llama_arch.shape_of(cfg)
    slack = None
    eps = shape[-1]
    x = params["embed"][jnp.asarray(tokens)].astype(F32)
    layers = params["layers"]
    for i in range(layers["wq"].shape[0]):
        lw = {k: layers[k][i] for k in ("attn_norm", "wq", "wk", "wv",
                                        "wo")}
        x = _attention(x, lw, shape)
        y, weights, gap = _route(x, layers["mlp_norm"][i],
                                 layers["router"][i], cfg.top_k, eps)
        slack = gap if slack is None else jnp.minimum(slack, gap)
        out = jnp.zeros_like(x)
        for e in range(cfg.n_experts):
            out = _expert(out, y, weights[:, e], layers["w_gate"][i, e],
                          layers["w_up"][i, e], layers["w_down"][i, e])
        x = x + out
    if rows is not None:
        x = x[jnp.asarray(rows)]
        slack = slack[jnp.asarray(rows)]
    return llama_arch._head(x, params["final_norm"], params["lm_head"],
                            eps), slack
