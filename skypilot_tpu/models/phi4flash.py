"""Phi-4-mini-flash-reasoning decoder (SambaY), serving path: a
decoder-hybrid-decoder of five mixers, whose layers keep THREE kinds of
per-sequence memory side by side (arXiv:2507.06607).

Every layer ``i`` is ``x += mixer_i(LN(x)); x += MLP(LN(x))`` with a
LayerNorm that has a bias and a SwiGLU MLP whose gate and up products
are one matrix. No positional encoding anywhere. The mixer by index, at
``L`` layers (32 published):

  * even ``i <= L/2``: **Mamba-1** (selective state space): a causal
    depthwise conv of 4 over ``u``, ``h_t = exp(d_t A) h_(t-1) + (d_t
    u_t) B_t^T`` on (E, N) in float32, read out by ``C_t``, gated by
    ``silu(z)``. Layer ``L/2`` also hands its read-out ``m`` (before the
    gate) to the gated memory units of the same token;
  * odd ``i < L/2``: **differential attention under a sliding window**:
    heads pair up in order, ``o = (softmax(q1 k1^T) - lam softmax(q2
    k2^T)) [v1; v2]``, a per-pair RMSNorm, ``(1 - lam_init)``;
  * ``L/2 + 1``: the same attention with no window: the one full layer;
  * even ``i > L/2 + 1``: **gated memory unit** ``(silu(x W1) * m) W2``;
  * odd ``i > L/2 + 1``: **cross attention** on layer ``L/2 + 1``'s
    keys and values: a query and an output projection of its own, no
    key/value projection.

So the layers are no single scan: ``L/4`` pairs (Mamba, window), the two
middle layers, ``L/4 - 1`` pairs (unit, cross). The parameter tree is
laid out by those three groups (``front``, ``mid``, ``back``), each kind
of layer a stack of its own, so that every scan scans whole stacks.

The pool (:func:`init_paged_cache`, :func:`pool_layout`) has three kinds
of block behind one slot, each kind with block ids of its own, and one
row of the engine's table names all of a slot's blocks (:func:`_tables`):

  * ``global_k/v (1, blocks, pairs, block_tokens, 2 * head_dim)``: the
    full layer's keys and values, appended with the sequence, aliased by
    the prefix trie, written by ONE layer and read by it and by every
    cross layer (a chunk gathers them once; a decode step reads the
    decoding slots' blocks where they lie, eight times:
    ops/pallas/paged_attention.py). The pairs lie OUTSIDE a
    block's rows: 10 pairs are no multiple of the TPU's 8 sublanes, and
    with them next to the lanes the compiler re-laid every pool to
    (tokens, lanes) tiles on the way in and out of each program
    (rehearsal 3, PERF.md PR 36);
  * ``window_k/v (L/4, blocks, ...)``: the window layers' keys and
    values, of which a sequence keeps the newest ``window`` tokens'
    blocks (the engine releases a block BEHIND the sequence and zeroes
    its table entry);
  * ``state_h (L/4 + 1, blocks, N, E)`` float32 and ``state_conv (L/4 +
    1, blocks, 3 E)``: one block is one sequence's whole recurrent state
    in every Mamba layer, rewritten by every step, restored from a
    snapshot and never aliased. ``E`` is minor: 5120 is 40 rows of 128
    lanes where ``N`` is 16.

A key/value pair ``(k1, k2)`` is stored as one row of ``2 * head_dim`` =
128 lanes and ``[v1; v2]`` likewise, and a query of 64 is padded with
zeros into the half it multiplies, so that differential attention is
plain grouped attention at head size 128 with four "heads" a pair
(:func:`_pad_queries`): the TPU's lanes are full and ``q1 . k1`` is
exact (the zeros add nothing).

Not supported, and refused by name (:func:`refuse`): ``tp > 1``, the
int8 pool, int8 weights, LoRA, speculative decoding, the host spill
tier.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from skypilot_tpu.models import llama
from skypilot_tpu.ops.pallas import paged_attention

Params = Dict[str, Any]
F32 = jnp.float32


def refuse(what: str, why: str):
    raise NotImplementedError(
        f"phi4flash (Phi-4-mini-flash-reasoning): {what} is not "
        f"supported: {why}")


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig:
    vocab_size: int = 200064
    dim: int = 2560
    n_layers: int = 32
    n_heads: int = 40
    n_kv_heads: int = 20
    mlp_dim: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2
    norm_eps: float = 1e-5
    max_seq_len: int = 262144
    dtype: Any = jnp.bfloat16
    # What the published config has no key for
    # (benchmarks/configs/phi-4-mini-flash-reasoning.json, ``assumed``):
    # Mamba-1's sizes and the dtype of its state.
    ssm_expand: int = 2
    ssm_conv: int = 4
    ssm_state: int = 16
    state_dtype: Any = jnp.float32

    def __post_init__(self):
        for name in ("dtype", "state_dtype"):
            if isinstance(getattr(self, name), str):
                object.__setattr__(self, name,
                                   jnp.dtype(getattr(self, name)).type)
        if self.n_layers % 4 or self.n_layers < 8 or self.mb_per_layer != 2:
            raise ValueError(
                f"phi4flash: {self.n_layers} layers at mb_per_layer "
                f"{self.mb_per_layer}: the pattern needs a Mamba layer "
                "every 2 and a multiple of 4 layers, at least 8")
        if self.dim % self.n_heads or self.n_heads % (2 * self.n_kv_heads) \
                or self.n_kv_heads % 2:
            raise ValueError(
                f"phi4flash: {self.n_heads} heads over {self.n_kv_heads} "
                "key/value heads: differential attention pairs both, and "
                "a pair of queries reads one pair of keys")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def kv_pairs(self) -> int:
        return self.n_kv_heads // 2

    @property
    def ssm_inner(self) -> int:
        """``E``: the width Mamba and the memory units work at."""
        return self.ssm_expand * self.dim

    @property
    def dt_rank(self) -> int:
        return -(-self.dim // 16)

    @property
    def n_front(self) -> int:
        """Pairs (Mamba, window attention) before the middle."""
        return self.n_layers // 4

    @property
    def n_back(self) -> int:
        """Pairs (memory unit, cross attention) after it."""
        return self.n_layers // 4 - 1

    @staticmethod
    def mini_flash() -> "Phi4FlashConfig":
        """The published model, whole
        (benchmarks/configs/phi-4-mini-flash-reasoning.json)."""
        return Phi4FlashConfig()

    @staticmethod
    def tiny(vocab_size: int = 256) -> "Phi4FlashConfig":
        return Phi4FlashConfig(vocab_size=vocab_size, dim=128, n_layers=8,
                               n_heads=8, n_kv_heads=4, mlp_dim=256,
                               sliding_window=128, max_seq_len=2048)


def layer_kinds(cfg: Phi4FlashConfig):
    """For every layer index: (group, kind, index in the kind's stack)."""
    half = cfg.n_layers // 2
    out = []
    for i in range(cfg.n_layers):
        if i < half:
            out.append(("front", "ssm" if i % 2 == 0 else "attn", i // 2))
        elif i <= half + 1:
            out.append(("mid", "ssm" if i == half else "attn", 0))
        else:
            out.append(("back", "gmu" if i % 2 == 0 else "cross",
                        (i - half - 2) // 2))
    return out


def lambda_init(layer: int) -> float:
    """Differential attention's ``lam_init`` at absolute layer index
    ``layer``."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _lambda_inits(cfg: Phi4FlashConfig, group: str, kind: str):
    return np.asarray([lambda_init(i) for i, k in
                       enumerate(layer_kinds(cfg))
                       if k[:2] == (group, kind)], np.float32)


# ------------------------------------------------------------ parameters
_MLP_SPECS = {"norm1_w": ("layers", "embed"), "norm1_b": ("layers", "embed"),
              "norm2_w": ("layers", "embed"), "norm2_b": ("layers", "embed"),
              "w_gu": ("layers", "embed", "mlp"),
              "w_down": ("layers", "mlp", "embed")}
_DIFF_SPECS = {"lambda_q1": ("layers", None), "lambda_k1": ("layers", None),
               "lambda_q2": ("layers", None), "lambda_k2": ("layers", None),
               "subln": ("layers", None),
               "wo": ("layers", "q_heads_x_dim", "embed"),
               "bo": ("layers", "embed")}
_KIND_SPECS = {
    "ssm": {"in_proj": ("layers", "embed", None),
            "conv_w": ("layers", None, None), "conv_b": ("layers", None),
            "x_proj": ("layers", None, None),
            "dt_proj": ("layers", None, None), "dt_bias": ("layers", None),
            "a_log": ("layers", None, None), "d_skip": ("layers", None),
            "out_proj": ("layers", None, "embed")},
    "attn": {"wqkv": ("layers", "embed", None), "bqkv": ("layers", None),
             **_DIFF_SPECS},
    "gmu": {"in_proj": ("layers", "embed", None),
            "out_proj": ("layers", None, "embed")},
    "cross": {"wq": ("layers", "embed", "q_heads_x_dim"),
              "bq": ("layers", None), **_DIFF_SPECS},
}
_GROUPS = {"front": ("ssm", "attn"), "mid": ("ssm", "attn"),
           "back": ("gmu", "cross")}


def param_specs(cfg: Phi4FlashConfig, *, quantized: bool = False) -> Params:
    if quantized:
        refuse("int8 weights", "quantize_params has no tree of five "
               "mixers")
    return {"embed": ("vocab", "embed"),
            **{g: {k: {**_KIND_SPECS[k], **_MLP_SPECS} for k in kinds}
               for g, kinds in _GROUPS.items()},
            "final_norm_w": ("embed",), "final_norm_b": ("embed",)}


def _init_kind(cfg: Phi4FlashConfig, kind: str, n: int, key) -> Params:
    """``n`` stacked layers of one kind, mixer and MLP, seeded."""
    d, e, hd, dt = cfg.dim, cfg.ssm_inner, cfg.head_dim, cfg.dtype
    q_out, kv_out = cfg.n_heads * hd, cfg.n_kv_heads * hd
    k = iter(jax.random.split(key, 24))

    def dense(shape, fan_in, scale=1.0):
        return (jax.random.normal(next(k), (n,) + shape, dtype=F32)
                * (scale * fan_in ** -0.5)).astype(dt)

    def small(shape, std=0.02):
        return (jax.random.normal(next(k), (n,) + shape, dtype=F32)
                * std).astype(dt)

    out = {"norm1_w": jnp.ones((n, d), dt), "norm1_b": small((d,)),
           "norm2_w": jnp.ones((n, d), dt), "norm2_b": small((d,)),
           "w_gu": dense((d, 2 * cfg.mlp_dim), d),
           "w_down": dense((cfg.mlp_dim, d), cfg.mlp_dim)}
    if kind in ("attn", "cross"):
        out.update({name: small((hd,), 0.1).astype(F32) for name in
                    ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")})
        out.update(subln=jnp.ones((n, 2 * hd), dt),
                   wo=dense((q_out, d), q_out), bo=small((d,)))
    if kind == "attn":
        out.update(wqkv=dense((d, q_out + 2 * kv_out), d),
                   bqkv=small((q_out + 2 * kv_out,)))
    elif kind == "cross":
        out.update(wq=dense((d, q_out), d), bq=small((q_out,)))
    elif kind == "gmu":
        out.update(in_proj=dense((d, e), d), out_proj=dense((e, d), e))
    elif kind == "ssm":
        # Mamba's convention: A = -(1 .. N) a channel, and the step's
        # bias the inverse softplus of steps log-uniform in [1e-3,
        # 1e-1], so that a state remembers tens to thousands of tokens.
        steps = jnp.exp(jax.random.uniform(next(k), (n, e), dtype=F32)
                        * (math.log(1e-1) - math.log(1e-3))
                        + math.log(1e-3))
        out.update(
            in_proj=dense((d, 2 * e), d),
            conv_w=small((cfg.ssm_conv, e), 0.5), conv_b=small((e,)),
            x_proj=dense((e, cfg.dt_rank + 2 * cfg.ssm_state), e),
            dt_proj=dense((cfg.dt_rank, e), cfg.dt_rank, 0.5),
            dt_bias=steps + jnp.log(-jnp.expm1(-steps)),
            a_log=jnp.broadcast_to(jnp.log(jnp.arange(
                1, cfg.ssm_state + 1, dtype=F32))[None, :, None],
                (n, cfg.ssm_state, e)),
            d_skip=jnp.ones((n, e), F32),
            out_proj=dense((e, d), e))
    return out


def init(cfg: Phi4FlashConfig, key: jax.Array) -> Params:
    """Seeded random parameters: each kind of layer a stack of its own
    inside its group, the embedding (tied: it is the head too) and the
    final LayerNorm."""
    k = jax.random.split(key, 8)
    sizes = {"front": cfg.n_front, "mid": 1, "back": cfg.n_back}
    out, j = {}, 1
    for group, kinds in _GROUPS.items():
        out[group] = {}
        for kind in kinds:
            out[group][kind] = _init_kind(cfg, kind, sizes[group], k[j])
            j += 1
    out["embed"] = (jax.random.normal(k[0], (cfg.vocab_size, cfg.dim),
                                      dtype=F32)
                    * cfg.dim ** -0.5).astype(cfg.dtype)
    out["final_norm_w"] = jnp.ones((cfg.dim,), cfg.dtype)
    out["final_norm_b"] = jnp.zeros((cfg.dim,), cfg.dtype)
    return out


def quantize_params(cfg: Phi4FlashConfig, params: Params) -> Params:
    param_specs(cfg, quantized=True)


def params_quantized(params: Params) -> bool:
    return False


def _refuse_lora(params: Params) -> None:
    for group in _GROUPS:
        for stack in params[group].values():
            if any(name.endswith("_lora_a") for name in stack):
                refuse("LoRA", "lora_dense has no adapters for five "
                       "mixers, and the recipe injects none here")


# ------------------------------------------------------------ the blocks
def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float) -> jax.Array:
    x32 = x.astype(F32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), axis=-1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (out * w.astype(F32) + b.astype(F32)).astype(x.dtype)


def mlp_block(cfg: Phi4FlashConfig, x: jax.Array, lp: Params) -> jax.Array:
    """``x + (silu(g) * u) W_2`` with ``[g, u] = LN(x) W_1``."""
    y = layer_norm(x, lp["norm2_w"], lp["norm2_b"], cfg.norm_eps)
    gate, up = jnp.split(jnp.matmul(y, lp["w_gu"]), 2, axis=-1)
    return x + jnp.matmul(jax.nn.silu(gate) * up, lp["w_down"])


def _ssm_inputs(cfg: Phi4FlashConfig, y: jax.Array, lp: Params,
                tail: jax.Array, keep: jax.Array):
    """What the recurrence runs on, for (B, T) tokens. ``tail`` (B, 3,
    E) is the conv's last inputs from before, ``keep`` (B, T) marks the
    real tokens. Returns (u after conv and silu (B, T, E), z, step (B,
    T, E) float32 and 0 on a row that is not kept, B_t and C_t (B, T, N)
    float32, the conv's inputs [tail, u] (B, T + 3, E))."""
    e, n, r = cfg.ssm_inner, cfg.ssm_state, cfg.dt_rank
    t = y.shape[1]
    u, z = jnp.split(jnp.matmul(y, lp["in_proj"]), 2, axis=-1)
    seen = jnp.concatenate([tail.astype(u.dtype), u], axis=1)
    conv = lp["conv_b"].astype(F32)
    for j in range(cfg.ssm_conv):
        conv = conv + (seen[:, j:j + t].astype(F32)
                       * lp["conv_w"][j].astype(F32))
    u = jax.nn.silu(conv).astype(y.dtype)
    dbc = jnp.matmul(u, lp["x_proj"], preferred_element_type=F32)
    step = jax.nn.softplus(
        jnp.matmul(dbc[..., :r].astype(y.dtype), lp["dt_proj"],
                   preferred_element_type=F32) + lp["dt_bias"])
    step = jnp.where(keep[..., None], step, 0.0)
    return u, z, step, dbc[..., r:r + n], dbc[..., r + n:], seen


def _ssm_step(lp: Params, h, u, step, b_t, c_t):
    """The recurrence once: h (B, N, E) float32, u and step (B, E), b_t
    and c_t (B, N). Returns (read-out (B, E) float32, h)."""
    a = -jnp.exp(lp["a_log"].astype(F32))                    # (N, E)
    h = (jnp.exp(step[:, None, :] * a) * h
         + (step * u.astype(F32))[:, None, :] * b_t[:, :, None])
    y = jnp.sum(h * c_t[:, :, None], axis=1) + lp["d_skip"] * u.astype(F32)
    return y, h


def _ssm_scan(lp: Params, h, u, step, b_t, c_t):
    """:func:`_ssm_step` over T tokens in order. A row whose step is 0
    leaves ``h`` as it was (``exp(0) = 1``, nothing added)."""
    if u.shape[1] == 1:
        y, h = _ssm_step(lp, h, u[:, 0], step[:, 0], b_t[:, 0], c_t[:, 0])
        return y[:, None], h

    def one(h, xs):
        y, h = _ssm_step(lp, h, *xs)
        return h, y

    h, y = jax.lax.scan(one, h, tuple(
        a.swapaxes(0, 1) for a in (u, step, b_t, c_t)))
    return y.swapaxes(0, 1), h


def ssm_block(cfg: Phi4FlashConfig, x: jax.Array, lp: Params, h, tail,
              keep: jax.Array, n_kept: jax.Array):
    """Pre-norm Mamba residual block on (B, T) tokens from the state
    ``h`` (B, N, E) and the conv tail (B, 3, E). ``n_kept`` (B,) counts
    the kept rows (they lead). Returns (x + mixer, the read-out ``m``
    before the gate (B, T, E), h, tail)."""
    with jax.named_scope("stpu.ssm"):
        y = layer_norm(x, lp["norm1_w"], lp["norm1_b"], cfg.norm_eps)
        u, z, step, b_t, c_t, seen = _ssm_inputs(cfg, y, lp, tail, keep)
        m, h = _ssm_scan(lp, h.astype(F32), u, step, b_t, c_t)
        m = m.astype(x.dtype)
        # The conv's inputs at the last three kept tokens: rows n_kept
        # .. n_kept + 2 of [tail, u].
        tail = jax.vmap(lambda s, n: jax.lax.dynamic_slice_in_dim(
            s, n, cfg.ssm_conv - 1, axis=0))(seen, n_kept)
        out = jnp.matmul(m * jax.nn.silu(z), lp["out_proj"])
        return x + out, m, h, tail


def gmu_block(cfg: Phi4FlashConfig, x: jax.Array, lp: Params,
              m: jax.Array) -> jax.Array:
    """Pre-norm gated memory unit: ``(silu(LN(x) W_1) * m) W_2`` with
    ``m`` the middle Mamba layer's read-out of the same token."""
    with jax.named_scope("stpu.gmu"):
        y = layer_norm(x, lp["norm1_w"], lp["norm1_b"], cfg.norm_eps)
        gate = jax.nn.silu(jnp.matmul(y, lp["in_proj"]))
        return x + jnp.matmul(gate * m, lp["out_proj"])


def _pad_queries(cfg: Phi4FlashConfig, q: jax.Array) -> jax.Array:
    """(B, T, heads * head_dim) -> (B, T, kv_pairs, 4, 2 * head_dim):
    query ``q1`` of a pair in the lanes of ``k1`` and zeros in those of
    ``k2``, ``q2`` the other way, so that one product with the stored
    row ``[k1; k2]`` gives ``q1 . k1`` and ``q2 . k2``. Along axis 3:
    (first pair q1, q2, second pair q1, q2) of the two query pairs that
    read this key/value pair."""
    b, t = q.shape[:2]
    q = q.reshape(b, t, cfg.n_heads // 2, 2, cfg.head_dim)
    zeros = jnp.zeros_like(q[..., 0, :])
    q = jnp.stack([jnp.concatenate([q[..., 0, :], zeros], axis=-1),
                   jnp.concatenate([zeros, q[..., 1, :]], axis=-1)], axis=3)
    return q.reshape(b, t, cfg.kv_pairs, -1, 2 * cfg.head_dim)


def _attend(cfg: Phi4FlashConfig, q, k, v, mask):
    """Softmax attention in one pass: q (B, T, P, 4, 128); k and v (B,
    blocks, P, rows, 128), as the pool's blocks lie, in the activations'
    dtype; mask (B, T, blocks * rows). Scores and both softmaxes in
    float32. Returns float32 (B, T, P, 4, 128). (Gathered straight into
    (B, P, S, 128) instead, the window layers' blocks were re-laid once
    more a layer and a step read 32.8 ms for 28.4; my chip run, PR 36.)"""
    s = jnp.einsum("btkgd,bnkjd->bkgtnj", q, k,
                   preferred_element_type=F32) * cfg.head_dim ** -0.5
    blocked = s.shape
    s = s.reshape(blocked[:4] + (-1,))
    msk = mask[:, None, None]
    s = jnp.where(msk, s, -1e30)
    p = jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)) * msk
    den = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("bkgtnj,bnkjd->btkgd",
                      (p / den).astype(v.dtype).reshape(blocked), v,
                      preferred_element_type=F32)


def _as_block(k: jax.Array) -> jax.Array:
    """Keys or values of a whole sequence (B, S, P, 128) as ONE block
    of S rows (B, 1, P, S, 128)."""
    return k.transpose(0, 2, 1, 3)[:, None]


def _diff_out(cfg: Phi4FlashConfig, x, o, lp: Params, lam_init):
    """From the four read-outs a key/value pair to the block's output:
    ``o1 - lam o2`` a query pair, the pair's RMSNorm, ``(1 -
    lam_init)``, the output projection with its bias."""
    b, t = o.shape[:2]
    lam = (jnp.exp(jnp.sum(lp["lambda_q1"] * lp["lambda_k1"]))
           - jnp.exp(jnp.sum(lp["lambda_q2"] * lp["lambda_k2"]))
           + lam_init)
    o = o.reshape(b, t, cfg.n_heads // 2, 2, 2 * cfg.head_dim)
    o = o[..., 0, :] - lam * o[..., 1, :]
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + cfg.norm_eps)
    o = (o * lp["subln"].astype(F32) * (1.0 - lam_init)).astype(x.dtype)
    return x + jnp.matmul(o.reshape(b, t, -1), lp["wo"]) + lp["bo"]


def cross_block(cfg: Phi4FlashConfig, x, lp: Params, lam_init,
                attend) -> jax.Array:
    """Pre-norm cross attention on the full layer's keys and values: a
    query and an output projection of its own, no key/value
    projection. ``attend`` takes the padded queries to the four
    read-outs a key/value pair, however the caller keeps those keys
    and values."""
    with jax.named_scope("stpu.cross_attn"):
        y = layer_norm(x, lp["norm1_w"], lp["norm1_b"], cfg.norm_eps)
        q = (llama._finished_dense(y, lp, "wq")
             + lp["bq"].astype(F32)).astype(y.dtype)
        return _diff_out(cfg, x, attend(_pad_queries(cfg, q)), lp,
                         lam_init)


def _qkv(cfg: Phi4FlashConfig, y: jax.Array, lp: Params):
    """(padded queries, k, v) of an attention layer, each product
    finished before it is reshaped (llama.cached_qkv_proj's lesson)."""
    b, t = y.shape[:2]
    q_out, kv_out = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    qkv = (llama._finished_dense(y, lp, "wqkv")
           + lp["bqkv"].astype(F32)).astype(y.dtype)
    rows = (b, t, cfg.kv_pairs, 2 * cfg.head_dim)
    return (_pad_queries(cfg, qkv[..., :q_out]),
            qkv[..., q_out:q_out + kv_out].reshape(rows),
            qkv[..., q_out + kv_out:].reshape(rows))


def _dense_mask(positions: jax.Array, window: int = 0) -> jax.Array:
    """(B, S, S): causal, and under a window the ``window`` newest
    keys, the token's own included."""
    q, k = positions[:, :, None], positions[:, None, :]
    mask = k <= q
    if window:
        mask &= k > q - window
    return mask


# ------------------------------------------------------- forward, no cache
def forward(cfg: Phi4FlashConfig, params: Params, tokens: jax.Array,
            positions: Optional[jax.Array] = None) -> jax.Array:
    """Token ids (B, S) -> float32 logits (B, S, vocab), no cache."""
    _refuse_lora(params)
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    keep = jnp.ones((b, s), bool)
    n_kept = jnp.full((b,), s, jnp.int32)
    x = llama._decode_embed(cfg, params, tokens)

    def ssm(x, lp):
        h = jnp.zeros((b, cfg.ssm_state, cfg.ssm_inner), F32)
        tail = jnp.zeros((b, cfg.ssm_conv - 1, cfg.ssm_inner), x.dtype)
        x, m, _, _ = ssm_block(cfg, x, lp, h, tail, keep, n_kept)
        return mlp_block(cfg, x, lp), m

    def attn(x, lp, lam_init, window):
        with jax.named_scope("stpu.diff_attn"):
            y = layer_norm(x, lp["norm1_w"], lp["norm1_b"], cfg.norm_eps)
            q, k, v = _qkv(cfg, y, lp)
            k, v = _as_block(k), _as_block(v)
            o = _attend(cfg, q, k, v, _dense_mask(positions, window))
            x = _diff_out(cfg, x, o, lp, lam_init)
        return mlp_block(cfg, x, lp), (k, v)

    def front(x, scanned):
        sp, ap, lam_init = scanned
        x, _ = ssm(x, sp)
        x, _ = attn(x, ap, lam_init, cfg.sliding_window)
        return x, None

    x, _ = jax.lax.scan(front, x, (
        params["front"]["ssm"], params["front"]["attn"],
        _lambda_inits(cfg, "front", "attn")))
    mid = jax.tree.map(lambda a: a[0], params["mid"])
    x, m = ssm(x, mid["ssm"])
    x, (k, v) = attn(x, mid["attn"], lambda_init(cfg.n_layers // 2 + 1), 0)
    mask = _dense_mask(positions)

    def back(x, scanned):
        gp, cp, lam_init = scanned
        x = mlp_block(cfg, gmu_block(cfg, x, gp, m), gp)
        x = cross_block(cfg, x, cp, lam_init,
                        lambda q: _attend(cfg, q, k, v, mask))
        return mlp_block(cfg, x, cp), None

    x, _ = jax.lax.scan(back, x, (
        params["back"]["gmu"], params["back"]["cross"],
        _lambda_inits(cfg, "back", "cross")))
    return lm_head(cfg, params, x)


def lm_head(cfg: Phi4FlashConfig, params: Params, x: jax.Array):
    """Final LayerNorm and the tied head: float32 logits against the
    embedding's rows, contracted where they lie (no transpose)."""
    x = layer_norm(x, params["final_norm_w"], params["final_norm_b"],
                   cfg.norm_eps)
    return jax.lax.dot_general(x, params["embed"],
                               (((2,), (1,)), ((), ())),
                               preferred_element_type=F32)


# --------------------------------------------------------------- the pool
def pool_layout(cfg: Phi4FlashConfig) -> Dict[str, Any]:
    """What the engine asks a family once (serve/kv_pool.py:
    ``pool_layout``, the fields of its ``PoolLayout``): what a slot's
    memory is made of. Here all three kinds: token blocks of the full
    layer, token blocks of the window layers of which a sequence keeps
    the newest ``sliding_window`` tokens', and one state block."""
    return {"tokens": True, "window": cfg.sliding_window,
            "state_blocks": 1}


def refuse_engine_options(cfg: Phi4FlashConfig, *, spec_k: int,
                          host_cache_mb: float) -> None:
    """Engine options this family cannot serve, refused at start-up."""
    if spec_k:
        refuse("speculative decoding (spec_k > 0)",
               "a rejected draft needs the Mamba state rolled back, and "
               "a state is not a row that a table truncates")
    if host_cache_mb > 0:
        refuse("the host spill tier (prefix_cache_mb > 0; pass "
               "--prefix-cache-mb 0)",
               "a prefix is three kinds of block and a state snapshot: "
               "spilling and restoring them together has no program yet")


def init_paged_cache(cfg: Phi4FlashConfig, num_blocks, block_tokens: int,
                     *, quantized: bool = False) -> Dict[str, jax.Array]:
    """The three pools, a leaf's name led by its kind (``global_``,
    ``window_``, ``state_``; serve/kv_pool.py sizes each kind by its
    leaves), blocks on axis 1 as in every family's pool. ``num_blocks``
    is a count a kind, or one count for all. Block 0 of each kind is its
    scratch block; the state's stays zero (a step skips a slot whose
    table names it) and is what a cold prompt's first chunk reads."""
    if quantized:
        refuse("the int8 pool (kv_quant)",
               "a state that every step decays and adds to has no "
               "per-block scale that holds")
    if not isinstance(num_blocks, dict):
        num_blocks = dict.fromkeys(("global", "window", "state"),
                                   num_blocks)
    rows = (cfg.kv_pairs, block_tokens, 2 * cfg.head_dim)
    e = cfg.ssm_inner

    def kv(layers, kind):
        return jnp.zeros((layers, num_blocks[kind]) + rows, cfg.dtype)

    ssm = (cfg.n_front + 1, num_blocks["state"])
    return {"global_k": kv(1, "global"), "global_v": kv(1, "global"),
            "window_k": kv(cfg.n_front, "window"),
            "window_v": kv(cfg.n_front, "window"),
            "state_h": jnp.zeros(ssm + (cfg.ssm_state, e),
                                 cfg.state_dtype),
            "state_conv": jnp.zeros(ssm + ((cfg.ssm_conv - 1) * e,),
                                    cfg.dtype)}


def cache_specs(cfg: Phi4FlashConfig):
    """Asked for by gang_replica.cache_shardings alone, to lay a cache
    over a mesh."""
    refuse("tp > 1", "the three pools and the Mamba scan know no mesh")


def _tables(table: jax.Array):
    """One row of the engine's table names all of a slot's blocks:
    column 0 the state block, then the full layer's blocks by chunk
    index, then the window layers' (0 where released or not yet
    there)."""
    span = (table.shape[1] - 1) // 2
    return table[:, 0], table[:, 1:1 + span], table[:, 1 + span:]


def _write_rows(pool_k, pool_v, li, table, bt: int, start_pos, k, v):
    """Scatter the new rows of k and v (B, T, P, 128) into layer
    ``li``'s blocks through ``table`` (B, span): a decode step's one
    row a slot at its position, a chunk's rows into the block of its
    first position (llama.paged_write_targets: row scatters keep the
    pool in the layout it arrived in)."""
    b, t = k.shape[:2]
    first = jnp.take_along_axis(table, (start_pos // bt)[:, None],
                                axis=1)[:, 0]
    blk, off = llama.paged_write_targets(
        table, bt, b, t, start_pos, first[0] if t > 1 else None, None)
    # One row of 128 lanes a (token, pair): a scatter whose window is a
    # pair's whole (pairs, lanes) slab makes the TPU compiler lay the
    # carried pool out by that slab and convert it back for the gather.
    at = (li, blk[..., None], jnp.arange(k.shape[2]), off[..., None])
    return (pool_k.at[at].set(k.astype(pool_k.dtype)),
            pool_v.at[at].set(v.astype(pool_v.dtype)))


def _gather(pool_k, pool_v, li, table, first, count: int):
    """Blocks ``first .. first + count - 1`` (by chunk index, a slot its
    own ``first``) of layer ``li`` through ``table``: (k, v (B, count,
    P, bt, 128), the rows' positions (B, count * bt)). A chunk index
    past the table reads the scratch block at a position no query
    reaches."""
    b, span = table.shape
    bt = pool_k.shape[3]
    idx = first[:, None] + jnp.arange(count)[None, :]
    phys = jnp.where(idx < span, jnp.take_along_axis(
        table, jnp.minimum(idx, span - 1), axis=1), 0)
    kpos = (idx[:, :, None] * bt + jnp.arange(bt)).reshape(b, -1)
    return pool_k[li, phys], pool_v[li, phys], kpos


def _paged_mask(kpos, positions, valid_len, window: int = 0):
    q, k = positions[:, :, None], kpos[:, None, :]
    mask = (k <= q) & (k < valid_len[:, None, None])
    if window:
        mask &= k > q - window
    return mask


def paged_ssm_block(cfg: Phi4FlashConfig, x, lp: Params, li, pool,
                    blocks, positions, valid_len, write_block):
    """:func:`ssm_block` against the state pool, carried whole. A
    decode step (T == 1) rewrites each slot's state in place at
    ``blocks[b]`` and skips a slot on the scratch block; a prefill
    chunk (B == 1) reads the state at ``blocks[0]`` and writes the new
    one to ``write_block``, rows at or past ``valid_len`` leaving it
    untouched. Returns (x, m, pool_h, pool_conv)."""
    b, t = x.shape[:2]
    pool_h, pool_c = pool
    keep = positions < valid_len[:, None]
    if t == 1:
        keep &= (blocks != 0)[:, None]
    elif b != 1 or write_block is None:
        raise ValueError("a paged Mamba chunk needs B == 1 and a "
                         f"write_block; got B={b}, T={t}")
    n_kept = jnp.sum(keep, axis=1).astype(jnp.int32)
    tail = pool_c[li, blocks].reshape(b, cfg.ssm_conv - 1, -1)
    x, m, h, tail = ssm_block(cfg, x, lp, pool_h[li, blocks], tail, keep,
                              n_kept)
    h = h.astype(pool_h.dtype)
    tail = tail.reshape(b, -1).astype(pool_c.dtype)
    if t == 1:
        # A skipped slot's step was 0 and its tail did not shift: what
        # it writes back to the scratch block is what it read.
        return (x, m, pool_h.at[li, blocks].set(h),
                pool_c.at[li, blocks].set(tail))
    return (x, m, pool_h.at[li, write_block].set(h[0]),
            pool_c.at[li, write_block].set(tail[0]))


def forward_with_paged_cache(cfg: Phi4FlashConfig, params: Params,
                             tokens: jax.Array,
                             cache: Dict[str, jax.Array],
                             table: jax.Array, start_pos: jax.Array,
                             valid_len: Optional[jax.Array] = None,
                             logits_at: Optional[jax.Array] = None, *,
                             window: int,
                             write_block: Optional[jax.Array] = None,
                             write_pos: Optional[jax.Array] = None):
    """llama.forward_with_paged_cache's contract over the three pools.
    ``table`` (B, 1 + 2 span) names a slot's blocks of every kind
    (:func:`_tables`); ``write_block`` is the STATE block a chunk
    writes (it reads the one at ``table[0, 0]``), its key/value rows go
    to the blocks the table names at its first position; ``window``
    (the engine's attention tile) tiles nothing here. Three scans, each
    scanning its layers' parameters and carrying the pools it writes.

    The sixteen attention reads go one of two ways, by the shape of the
    call. A decode step (T == 1) reads each DECODING slot's visible
    blocks where they lie in the pool (ops/pallas/paged_attention.py;
    a slot whose row names the scratch state block reads nothing) and
    returns, as a third result, ``(the blocks its sixteen reads
    fetched,)``. A chunk (B == 1) gathers its one slot's
    blocks and attends in one pass (:func:`_attend`): they are 0.2 % of
    a chunk's bytes."""
    del window
    if write_pos is not None:
        refuse("speculative decoding (spec_k > 0)",
               "a rejected draft needs the Mamba state rolled back")
    _refuse_lora(params)
    b, t = tokens.shape
    start_pos, valid_len, positions = llama.slot_positions(
        b, t, start_pos, valid_len)
    state_blk, table_g, table_w = _tables(table)
    bt = cache["global_k"].shape[3]
    scale = cfg.head_dim ** -0.5
    if t == 1:
        reads_w, reads_g = (paged_attention.step_reads(
            tbl, state_blk != 0, start_pos, valid_len, bt, w)
            for tbl, w in ((table_w, cfg.sliding_window), (table_g, 0)))

        def attend_w(q, wk, wv, li):
            return paged_attention.attend(q[:, 0], wk, wv, li, reads_w,
                                          scale)[:, None]
    else:
        first_w = jnp.maximum(start_pos - cfg.sliding_window + 1, 0) // bt
        n_w = paged_attention.window_blocks(cfg.sliding_window, bt)

        def attend_w(q, wk, wv, li):
            kb, vb, kpos = _gather(wk, wv, li, table_w, first_w, n_w)
            return _attend(cfg, q, kb, vb, _paged_mask(
                kpos, positions, valid_len, cfg.sliding_window))

    x = llama._decode_embed(cfg, params, tokens)

    def ssm(x, lp, li, state):
        x, m, *state = paged_ssm_block(cfg, x, lp, li, state, state_blk,
                                       positions, valid_len, write_block)
        return mlp_block(cfg, x, lp), m, tuple(state)

    def front(carry, scanned):
        x, state, (wk, wv) = carry
        sp, ap, lam_init, li = scanned
        x, _, state = ssm(x, sp, li, state)
        with jax.named_scope("stpu.diff_attn"):
            y = layer_norm(x, ap["norm1_w"], ap["norm1_b"], cfg.norm_eps)
            q, k, v = _qkv(cfg, y, ap)
            wk, wv = _write_rows(wk, wv, li, table_w, bt, start_pos, k, v)
            x = _diff_out(cfg, x, attend_w(q, wk, wv, li), ap, lam_init)
        return (mlp_block(cfg, x, ap), state, (wk, wv)), None

    (x, state, (wk, wv)), _ = jax.lax.scan(
        front,
        (x, (cache["state_h"], cache["state_conv"]),
         (cache["window_k"], cache["window_v"])),
        (params["front"]["ssm"], params["front"]["attn"],
         _lambda_inits(cfg, "front", "attn"), jnp.arange(cfg.n_front)))
    mid = jax.tree.map(lambda a: a[0], params["mid"])
    x, m, (sh, sc) = ssm(x, mid["ssm"], cfg.n_front, state)
    with jax.named_scope("stpu.diff_attn"):
        ap = mid["attn"]
        y = layer_norm(x, ap["norm1_w"], ap["norm1_b"], cfg.norm_eps)
        q, k, v = _qkv(cfg, y, ap)
        gk, gv = _write_rows(cache["global_k"], cache["global_v"], 0,
                             table_g, bt, start_pos, k, v)
        # The one full layer's keys and values: this layer and every
        # cross layer read them (a chunk gathers them ONCE).
        if t == 1:
            def attend_g(q):
                return paged_attention.attend(q[:, 0], gk, gv, 0, reads_g,
                                              scale)[:, None]
        else:
            kb, vb, kpos = _gather(gk, gv, 0, table_g,
                                   jnp.zeros((b,), jnp.int32),
                                   table_g.shape[1])
            mask = _paged_mask(kpos, positions, valid_len)

            def attend_g(q):
                return _attend(cfg, q, kb, vb, mask)

        x = _diff_out(cfg, x, attend_g(q), ap,
                      lambda_init(cfg.n_layers // 2 + 1))
    x = mlp_block(cfg, x, ap)

    def back(x, scanned):
        gp, cp, lam_init = scanned
        x = mlp_block(cfg, gmu_block(cfg, x, gp, m), gp)
        x = cross_block(cfg, x, cp, lam_init, attend_g)
        return mlp_block(cfg, x, cp), None

    x, _ = jax.lax.scan(back, x, (
        params["back"]["gmu"], params["back"]["cross"],
        _lambda_inits(cfg, "back", "cross")))
    logits = lm_head(cfg, params, llama.read_out(x, logits_at))
    cache = {"global_k": gk, "global_v": gv, "window_k": wk,
             "window_v": wv, "state_h": sh, "state_conv": sc}
    if t > 1:
        return logits, cache
    return logits, cache, (cfg.n_front * reads_w.fetched()
                           + (1 + cfg.n_back) * reads_g.fetched(),)


def verify_step_paged(cfg: Phi4FlashConfig, *args, **kwargs):
    refuse("speculative decoding (spec_k > 0)",
           "a rejected draft needs the Mamba state rolled back")
