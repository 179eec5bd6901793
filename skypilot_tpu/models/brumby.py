"""Brumby-14B decoder, serving path: power retention (degree 2) in
place of softmax attention, so a layer keeps a FIXED-SIZE recurrent
state a sequence instead of keys and values a token.

The block is Qwen3-14B's (pre-norm RMSNorm, grouped heads of 128 with
per-head ``q_norm``/``k_norm`` and rotate-half RoPE, SwiGLU MLP, untied
head) with its attention replaced. Query head ``a`` reads key/value
head ``a // group``; with ``B_i`` the running sum of the gate's
``log_sigmoid`` the published attention form is, for ``j <= i``,

    w_ij = (q_i . k_j)^2 / head_dim * exp(B_i - B_j)
    y_i  = sum_j w_ij v_j / (sum_j w_ij + eps)

and ``(q . k)^2 = phi(q) . phi(k)`` for the symmetric square ``phi``,
so the same thing is a recurrence over a state a key/value head:

    S_t = g_t S_(t-1) + v_t phi(k_t)^T        (head_dim x D)
    z_t = g_t z_(t-1) + phi(k_t)              (D)
    y_t = S_t phi(q_t) / (z_t . phi(q_t) + head_dim * eps)

What differs from llama.py, and where it lives:

  * ``phi`` keeps whole 16 x 16 tiles ``I <= J`` of the outer product
    (off-diagonal tiles times sqrt 2): ``D = 9216`` at head size 128, a
    multiple of 128 lanes (the untiled 8256 is not, and the full square,
    16384, would double the bytes of the very thing a step moves). It is
    two one-hot products and a multiply, exact for bf16 inputs;
  * the paged pool's block is ONE SEQUENCE'S WHOLE STATE
    (:func:`init_paged_cache`: ``S (layers, blocks, kv_heads, head_dim,
    D)`` and ``z (layers, blocks, kv_heads, D)``, float32, ``D`` minor so
    both leaves lie in the TPU's natural layout), ``table[b, 0]`` names
    slot ``b``'s block, and the engine learns from
    :func:`pool_layout` that a sequence costs a fixed number of blocks
    which every step REWRITES;
  * two forms pinned to one reference (benchmarks/reference/
    brumby_arch.py, the attention form): a prefill chunk runs the chunk
    form (quadratic inside its 64 tokens, the state across chunks;
    :func:`_chunk`), reading the state at ``table[0, 0]`` and writing
    the new one to ``write_block``; a decode step runs the recurrence
    once, in place, in one Pallas kernel that reads and writes each
    live slot's state once (:func:`_retention_step`; float32 products
    on the VPU throughout). A slot whose
    table row names block 0 (the scratch block: a free slot, or one in
    the middle of its prefill) is skipped there, so block 0 stays zero
    and is what a cold prompt's first chunk reads.

Not supported, and refused by name (:func:`refuse`): ``tp > 1``, the
int8 pool, int8 weights, LoRA, speculative decoding, the host spill
tier. The published kernels' option of serving short contexts from a
key/value cache and switching to the state later
(``switch_over_seq_len``) is left out, here and in the reference.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from skypilot_tpu.models import llama
from skypilot_tpu.ops.pallas import flash_attention

Params = Dict[str, Any]
F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST

TILE = 16            # edge of the outer product's tiles phi keeps
_LANES = 128
_STATE_TILE = 512    # values of D a kernel step moves (x kv_heads x head_dim)


def refuse(what: str, why: str):
    raise NotImplementedError(
        f"brumby (Brumby-14B): {what} is not supported: {why}")


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    vocab_size: int = 151936
    dim: int = 5120
    n_layers: int = 40
    n_heads: int = 40
    n_kv_heads: int = 8
    mlp_dim: int = 17408
    rope_theta: float = 1000000.0
    norm_eps: float = 1e-6
    max_seq_len: int = 32768
    dtype: Any = jnp.bfloat16
    # The retention itself, which the published config has no key for
    # (benchmarks/configs/brumby-14b-6l.json, ``assumed``): degree 2,
    # the normalised form with this epsilon, the state in float32.
    retention_eps: float = 1e-6
    state_dtype: Any = jnp.float32

    def __post_init__(self):
        for name in ("dtype", "state_dtype"):
            if isinstance(getattr(self, name), str):
                object.__setattr__(self, name,
                                   jnp.dtype(getattr(self, name)).type)
        if self.dim % self.n_heads or self.n_heads % self.n_kv_heads \
                or self.head_dim % TILE:
            raise ValueError(
                f"brumby: {self.n_heads} heads of {self.head_dim} over "
                f"{self.n_kv_heads} key/value heads: heads must divide "
                f"the width, key/value heads the heads, and {TILE} the "
                "head size")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def state_dim(self) -> int:
        """``D``: the length of ``phi`` of one head."""
        n = self.head_dim // TILE
        return n * (n + 1) // 2 * TILE * TILE

    @staticmethod
    def b14_6l() -> "BrumbyConfig":
        """One chip holding 6 of the 40 layers with the embedding and
        the head (benchmarks/configs/brumby-14b-6l.json)."""
        return BrumbyConfig(n_layers=6)

    @staticmethod
    def tiny(vocab_size: int = 256) -> "BrumbyConfig":
        return BrumbyConfig(vocab_size=vocab_size, dim=128, n_layers=2,
                            n_heads=4, n_kv_heads=2, mlp_dim=256,
                            max_seq_len=2048)


# ------------------------------------------------------------ parameters
def param_specs(cfg: BrumbyConfig, *, quantized: bool = False) -> Params:
    if quantized:
        refuse("int8 weights", "quantize_params has no retention tree")
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "q_heads_x_dim"),
            "wk": ("layers", "embed", "kv_heads_x_dim"),
            "wv": ("layers", "embed", "kv_heads_x_dim"),
            "wg": ("layers", "embed", None),
            "q_norm": ("layers", None),
            "k_norm": ("layers", None),
            "wo": ("layers", "q_heads_x_dim", "embed"),
            "mlp_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


def init(cfg: BrumbyConfig, key: jax.Array) -> Params:
    """Seeded random parameters, stacked by layer. Each projection is a
    matrix of its own, heads major in its columns, split by what it
    makes (queries, keys, values, gates): every product reads its weight
    whole, in the layout it is stored in, and is finished before it is
    reshaped to heads (llama.cached_qkv_proj's lesson, PERF.md PR 32)."""
    d, hd, n, dt = cfg.dim, cfg.head_dim, cfg.n_layers, cfg.dtype
    h, kvh = cfg.n_heads, cfg.n_kv_heads
    k = jax.random.split(key, 11)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, dtype=F32)
                * (fan_in ** -0.5)).astype(dt)

    return {
        "embed": dense(k[0], (cfg.vocab_size, d), d),
        "layers": {
            "attn_norm": jnp.ones((n, d), dtype=dt),
            "wq": dense(k[1], (n, d, h * hd), d),
            "wk": dense(k[2], (n, d, kvh * hd), d),
            "wv": dense(k[3], (n, d, kvh * hd), d),
            "wg": dense(k[4], (n, d, kvh), d),
            "q_norm": jnp.ones((n, hd), dtype=dt),
            "k_norm": jnp.ones((n, hd), dtype=dt),
            "wo": dense(k[5], (n, h * hd, d), h * hd),
            "mlp_norm": jnp.ones((n, d), dtype=dt),
            "w_gate": dense(k[6], (n, d, cfg.mlp_dim), d),
            "w_up": dense(k[7], (n, d, cfg.mlp_dim), d),
            "w_down": dense(k[8], (n, cfg.mlp_dim, d), cfg.mlp_dim),
        },
        "final_norm": jnp.ones((d,), dtype=dt),
        "lm_head": dense(k[9], (d, cfg.vocab_size), d),
    }


def quantize_params(cfg: BrumbyConfig, params: Params) -> Params:
    param_specs(cfg, quantized=True)


def params_quantized(params: Params) -> bool:
    return False


# ------------------------------------------------------- the feature map
@functools.lru_cache(maxsize=None)
def _feature_index(hd: int):
    """For every entry of ``phi``: which value of the head it takes from
    the left, which from the right, and its coefficient (1 on a diagonal
    tile, sqrt 2 off it). Host constants of the trace."""
    tile_i, tile_j = np.triu_indices(hd // TILE)
    i, j = np.divmod(np.arange(TILE * TILE), TILE)
    left = (tile_i[:, None] * TILE + i[None, :]).reshape(-1)
    right = (tile_j[:, None] * TILE + j[None, :]).reshape(-1)
    coef = np.where(tile_i == tile_j, 1.0, np.sqrt(2.0))
    return left, right, np.repeat(coef, TILE * TILE).astype(np.float32)


def phi(a: jax.Array) -> jax.Array:
    """(..., head_dim) -> (..., D) float32 with ``phi(a) . phi(b) ==
    (a . b)^2``: the tiles ``I <= J`` of ``a a^T``. Two one-hot products
    pick the factors (exact: one term a sum; bf16 values pass the MXU
    unrounded) and the VPU multiplies them, so no lane is gathered."""
    hd = a.shape[-1]
    left, right, coef = _feature_index(hd)

    def pick(index):
        return jnp.matmul(a, jax.nn.one_hot(index, hd, dtype=a.dtype,
                                            axis=0),
                          precision=_HIGHEST, preferred_element_type=F32)

    return pick(left) * pick(right) * coef


# ----------------------------------------------------------- projections
def _project(cfg: BrumbyConfig, x: jax.Array, lp: Params,
             positions: jax.Array):
    """(q (B, T, KVH, G, HD), k (B, T, KVH, HD), v (B, T, KVH, HD),
    log_gate (B, T, KVH) float32): normed and roped queries and keys in
    the activations' dtype, values, and one gate a key/value head."""
    if any(name.endswith("_lora_a") for name in lp):
        refuse("LoRA", "lora_dense has no adapters for the gate, and "
               "the recipe injects none here")
    b, t = x.shape[0], x.shape[1]
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    y = llama.rms_norm(x, lp["attn_norm"], cfg.norm_eps)

    def heads(name, norm):
        out = llama._finished_dense(y, lp, name).reshape(b, t, -1, hd)
        out = llama.rms_norm(out, lp[norm], cfg.norm_eps)
        return llama.rope(out, positions, cfg.rope_theta).astype(y.dtype)

    q = heads("wq", "q_norm").reshape(b, t, kvh, -1, hd)
    k = heads("wk", "k_norm")
    v = llama._finished_dense(y, lp, "wv").astype(y.dtype)
    log_gate = jax.nn.log_sigmoid(llama._finished_dense(y, lp, "wg"))
    return q, k, v.reshape(b, t, kvh, hd), log_gate


def _chunk(cfg: BrumbyConfig, q, k, v, log_gate, keep, state, norm):
    """The chunk form over T tokens: quadratic inside the chunk, the
    state across it. ``keep`` (B, T) bool marks the real tokens: a row
    past it leaves the state untouched (gate 1, ``phi(k)`` 0). ``state``
    (B, KVH, HD, D) and ``norm`` (B, KVH, D) are what came before.
    Returns (y (B, T, KVH, G, HD) float32, new state, new norm). Every
    exponent is <= 0, so there is no running maximum."""
    t = q.shape[1]
    log_gate = jnp.where(keep[..., None], log_gate, 0.0)
    k = jnp.where(keep[..., None, None], k, 0)
    run = jnp.cumsum(log_gate, axis=1)                    # (B, T, KVH)
    total = run[:, -1]                                    # (B, KVH)
    causal = jnp.tril(jnp.ones((t, t), bool))[None, :, :, None]
    decay = jnp.exp(jnp.where(causal, run[:, :, None] - run[:, None], 0))
    s = jnp.einsum("btkgh,bskh->btskg", q, k,
                   preferred_element_type=F32)
    w = jnp.where(causal, decay, 0.0)[..., None] * s * s   # (B,T,S,K,G)
    num = jnp.einsum("btskg,bskh->btkgh", w, v.astype(F32))
    den = jnp.sum(w, axis=2)                              # (B, T, K, G)
    pq = phi(q)                                           # (B,T,K,G,D)
    carried = jnp.exp(run)                                # (B, T, KVH)
    # phi(q) against the state in float32 products: in one bf16 pass
    # phi(q) . phi(k) is no longer the square it stands for, a weight
    # can come out negative and a normaliser near zero.
    num += carried[..., None, None] * jnp.einsum(
        "btkgd,bkhd->btkgh", pq, state.astype(F32), precision=_HIGHEST)
    den += carried[..., None] * jnp.einsum(
        "btkgd,bkd->btkg", pq, norm.astype(F32), precision=_HIGHEST)
    y = num / (den + cfg.head_dim * cfg.retention_eps)[..., None]
    # What each token still weighs when the chunk ends. The increments
    # are sums of 64 terms: worth float32 products.
    left = jnp.exp(total[:, None] - run)                  # (B, T, KVH)
    pk = phi(k)                                           # (B, T, K, D)
    last = jnp.exp(total)
    state = (last[..., None, None] * state.astype(F32) + jnp.einsum(
        "bskh,bskd->bkhd", v.astype(F32) * left[..., None], pk,
        precision=_HIGHEST)).astype(state.dtype)
    norm = (last[..., None] * norm.astype(F32) + jnp.einsum(
        "bsk,bskd->bkd", left, pk, precision=_HIGHEST)).astype(norm.dtype)
    return y, state, norm


# ------------------------------------------------------- the state pool
def pool_layout(cfg: BrumbyConfig) -> Dict[str, Any]:
    """What the engine asks a family once (serve/kv_pool.py:
    ``pool_layout``, the fields of its ``PoolLayout``): a sequence holds
    one state block whatever its length and no blocks of tokens; every
    step rewrites the block, so none is ever shared between a live slot
    and the prefix trie. A family without this function pages by the
    token."""
    return {"tokens": False, "state_blocks": 1}


def refuse_engine_options(cfg: BrumbyConfig, *, spec_k: int,
                          host_cache_mb: float) -> None:
    """Engine options this family cannot serve, refused at start-up."""
    if spec_k:
        refuse("speculative decoding (spec_k > 0)",
               "a rejected draft needs the state rolled back, and a "
               "state is not a row that a table truncates")
    if host_cache_mb > 0:
        refuse("the host spill tier (prefix_cache_mb > 0; pass "
               "--prefix-cache-mb 0)",
               "a snapshot is the whole state of a sequence: spilling "
               "and restoring one has no program yet")


def init_paged_cache(cfg: BrumbyConfig, num_blocks: int,
                     block_tokens: int, *, quantized: bool = False
                     ) -> Dict[str, jax.Array]:
    """The state pool: block ``n`` (axis 1, as in every family's pool)
    holds one sequence's whole state in every layer, ``S`` (layers,
    blocks, kv_heads, head_dim, D) and its normaliser ``z`` (layers,
    blocks, kv_heads, D). ``block_tokens`` is the engine's prefill chunk
    and sizes nothing here. ``D`` is minor in both leaves: 9216 is 72
    rows of 128 lanes, so the TPU lays both out as they are indexed, and
    a kernel step moves a (kv_heads, head_dim, tile of D) slab. Block 0
    is the scratch block and stays zero."""
    del block_tokens
    if quantized:
        refuse("the int8 pool (kv_quant)",
               "a state that every step decays and adds to has no "
               "per-block scale that holds")
    lead = (cfg.n_layers, num_blocks, cfg.n_kv_heads)
    return {"S": jnp.zeros(lead + (cfg.head_dim, cfg.state_dim),
                           cfg.state_dtype),
            "z": jnp.zeros(lead + (cfg.state_dim,), cfg.state_dtype)}


def cache_specs(cfg: BrumbyConfig):
    """Asked for by gang_replica.cache_shardings alone, to lay a cache
    over a mesh."""
    refuse("tp > 1", "the state's kernel and its pool know no mesh")


def _state_tile(d: int) -> int:
    """The largest multiple of 128 lanes, at most _STATE_TILE, that
    divides D."""
    return max(t for t in range(_LANES, min(_STATE_TILE, d) + 1, _LANES)
               if d % t == 0)


def _step_kernel(li_ref, blk_ref, vg_ref, gz_ref, pk_ref, pq_ref, s_ref,
                 z_ref, s_out, z_out, num_out, den_out, acc, dacc, *,
                 kvh: int, group: int, tile: int):
    """One (slot, tile of D) step of the recurrence, all key/value heads:
    decay the state slab, add ``v phi(k)^T``, write it back, and add the
    slab's part of ``S phi(q)`` and ``z . phi(q)`` to the slot's sums
    (the sums over lanes wait for the last tile). A slot on the scratch
    block copies its one slab through, once.

    Every product is a VPU product in float32, the read-out too. On the
    MXU (Mosaic multiplies float32 operands in bf16 passes, as XLA's
    default precision does) ``phi(q) . phi(k)`` stops being the square
    it is: a weight can come out negative, a normaliser near zero, and
    the output of a head is no longer an average of values — on seeded
    inputs the two read-outs differed by 1e4 where the values were
    under 3, at the same speed (my chip run, PERF.md PR 33: the kernel
    moves its state at 92 % of the chip's bandwidth either way)."""
    b, dt = pl.program_id(0), pl.program_id(1)
    chunks = tile // _LANES

    @pl.when(dt == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)
        dacc[...] = jnp.zeros_like(dacc)

    live = blk_ref[b] != 0

    @pl.when(live)
    def _():
        z_new = (gz_ref[:, 0:1] * z_ref[...].astype(F32)
                 + pk_ref[...])                           # (KVH, tile)
        z_new = z_new.astype(z_out.dtype)
        z_out[...] = z_new
        for h in range(kvh):
            s_new = (vg_ref[:, kvh + h:kvh + h + 1]
                     * s_ref[h].astype(F32)
                     + vg_ref[:, h:h + 1] * pk_ref[h:h + 1, :])
            # What is read is what was stored: a state kept in a
            # narrower type is read back as that.
            stored = s_new.astype(s_out.dtype)            # (HD, tile)
            s_out[h] = stored
            s_new = stored.astype(F32)
            for a in range(group):
                # A row of phi(q) is read 128 lanes at a time: a slice
                # of a loaded row at a lane offset does not broadcast
                # over sublanes (Mosaic: "Invalid input layout").
                part = s_new[:, :_LANES] * pq_ref[h, a:a + 1, :_LANES]
                for c in range(1, chunks):
                    lanes = slice(c * _LANES, (c + 1) * _LANES)
                    part += s_new[:, lanes] * pq_ref[h, a:a + 1, lanes]
                acc[h, a] += part
            zq = pq_ref[h] * z_new[h:h + 1, :].astype(F32)  # (GP, tile)
            part = zq[:, :_LANES]
            for c in range(1, chunks):
                part += zq[:, c * _LANES:(c + 1) * _LANES]
            dacc[h] += part

    # A skipped slot stays on one slab for all its steps (``slab``
    # below), so its one copy-through is made once.
    @pl.when(jnp.logical_not(live) & (dt == 0))
    def _():
        s_out[...] = s_ref[...]
        z_out[...] = z_ref[...]

    @pl.when(dt == pl.num_programs(1) - 1)
    def _():
        for h in range(kvh):
            for a in range(group):
                num_out[h, :, a:a + 1] = jnp.sum(acc[h, a], axis=1,
                                                 keepdims=True)
            den_out[h] = jnp.broadcast_to(
                jnp.sum(dacc[h], axis=1, keepdims=True), den_out.shape[1:])


def _retention_step(cfg: BrumbyConfig, li, blocks, q, k, v, log_gate,
                    pool_s, pool_z):
    """The recurrence once for every slot, in place in the pool.
    q (B, KVH, G, HD), k and v (B, KVH, HD), log_gate (B, KVH), blocks
    (B,) int32: each slot's state block, 0 = skip the slot. Returns
    (y (B, KVH, G, HD) float32, pool_s, pool_z), the pools the donated
    buffers they came in (``input_output_aliases``)."""
    b, kvh, group, hd = q.shape
    d = cfg.state_dim
    tile = _state_tile(d)
    gp = -(-group // 8) * 8
    # Rows of zeros up to a sublane tile, added before phi so that
    # phi's product is written once, in the kernel's shape.
    pq = phi(jnp.pad(q, ((0, 0), (0, 0), (0, gp - group), (0, 0))))
    pk = phi(k)
    gate = jnp.exp(log_gate)
    # Values and gates as columns: a column broadcasts over lanes.
    vg = jnp.concatenate(
        [v.astype(F32).transpose(0, 2, 1),
         jnp.broadcast_to(gate[:, None, :], (b, hd, kvh))], axis=-1)
    gz = jnp.broadcast_to(gate[:, :, None], (b, kvh, _LANES))

    def slab(bi, dt, li_ref, blk_ref):
        # A skipped slot stays on one slab: nothing is fetched again.
        blk = blk_ref[bi]
        return li_ref[0], blk, jnp.where(blk != 0, dt, 0)

    def state_map(bi, dt, li_ref, blk_ref):
        layer, blk, at = slab(bi, dt, li_ref, blk_ref)
        return layer, blk, 0, 0, at

    def norm_map(bi, dt, li_ref, blk_ref):
        layer, blk, at = slab(bi, dt, li_ref, blk_ref)
        return layer, blk, 0, at

    state_spec = pl.BlockSpec((None, None, kvh, hd, tile), state_map)
    norm_spec = pl.BlockSpec((None, None, kvh, tile), norm_map)
    pool_s, pool_z, num, den = pl.pallas_call(
        functools.partial(_step_kernel, kvh=kvh, group=group, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, d // tile),
            in_specs=[
                pl.BlockSpec((None, hd, 2 * kvh),
                             lambda bi, dt, *_: (bi, 0, 0)),
                pl.BlockSpec((None, kvh, _LANES),
                             lambda bi, dt, *_: (bi, 0, 0)),
                pl.BlockSpec((None, kvh, tile),
                             lambda bi, dt, *_: (bi, 0, dt)),
                pl.BlockSpec((None, kvh, gp, tile),
                             lambda bi, dt, *_: (bi, 0, 0, dt)),
                state_spec, norm_spec,
            ],
            out_specs=[
                state_spec, norm_spec,
                pl.BlockSpec((None, kvh, hd, gp),
                             lambda bi, dt, *_: (bi, 0, 0, 0)),
                pl.BlockSpec((None, kvh, gp, _LANES),
                             lambda bi, dt, *_: (bi, 0, 0, 0)),
            ],
            scratch_shapes=[pltpu.VMEM((kvh, group, hd, _LANES), F32),
                            pltpu.VMEM((kvh, gp, _LANES), F32)]),
        out_shape=[jax.ShapeDtypeStruct(pool_s.shape, pool_s.dtype),
                   jax.ShapeDtypeStruct(pool_z.shape, pool_z.dtype),
                   jax.ShapeDtypeStruct((b, kvh, hd, gp), F32),
                   jax.ShapeDtypeStruct((b, kvh, gp, _LANES), F32)],
        # Operands count the two prefetched scalars.
        input_output_aliases={6: 0, 7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=flash_attention._interpret(),
        name="stpu_retention_step",
    )(jnp.reshape(li, (1,)).astype(jnp.int32), blocks.astype(jnp.int32),
      vg, gz, pk, pq, pool_s, pool_z)
    num = num.transpose(0, 1, 3, 2)[:, :, :group]         # (B,KVH,G,HD)
    den = den[:, :, :group, 0] + cfg.head_dim * cfg.retention_eps
    return num / den[..., None], pool_s, pool_z


# ------------------------------------------------------ the layer's half
def retention_block(cfg: BrumbyConfig, x: jax.Array, lp: Params,
                    positions: jax.Array) -> jax.Array:
    """Pre-norm power-retention residual block with no cache: the chunk
    form over the whole sequence, 64 tokens at a time."""
    b, s = x.shape[0], x.shape[1]
    kvh, hd, c = cfg.n_kv_heads, cfg.head_dim, 64
    with jax.named_scope("stpu.retention"):
        q, k, v, log_gate = _project(cfg, x, lp, positions)
        pad = -s % c
        keep = jnp.arange(s + pad) < s

        def chunks(a):
            a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
            return a.reshape((b, -1, c) + a.shape[2:]).swapaxes(0, 1)

        def one(carry, xs):
            qc, kc, vc, gc, keep_c = xs
            y, *carry = _chunk(cfg, qc, kc, vc, gc,
                               jnp.broadcast_to(keep_c, (b, c)), *carry)
            return tuple(carry), y

        zero = (jnp.zeros((b, kvh, hd, cfg.state_dim), cfg.state_dtype),
                jnp.zeros((b, kvh, cfg.state_dim), cfg.state_dtype))
        _, y = jax.lax.scan(one, zero, (chunks(q), chunks(k), chunks(v),
                                        chunks(log_gate),
                                        keep.reshape(-1, c)))
        y = y.swapaxes(0, 1).reshape(b, s + pad, -1)[:, :s]
        return x + llama.lora_dense(y.astype(x.dtype), lp, "wo")


def paged_retention_block(cfg: BrumbyConfig, x: jax.Array, lp: Params,
                          li: jax.Array, pool: Dict[str, jax.Array],
                          table: jax.Array, positions: jax.Array,
                          valid_len: jax.Array,
                          write_block: Optional[jax.Array]):
    """Pre-norm power-retention residual block against the state pool,
    carried whole. A decode step (T == 1) runs the recurrence once for
    every slot in place at ``table[b, 0]``; a prefill chunk (B == 1)
    reads the state at ``table[0, 0]``, runs the chunk form (rows at or
    past ``valid_len`` leave the state untouched) and writes the new
    state to ``write_block``. Returns (x + retention, pool)."""
    b, t = x.shape[0], x.shape[1]
    with jax.named_scope("stpu.retention"):
        q, k, v, log_gate = _project(cfg, x, lp, positions)
        if t == 1:
            y, pool_s, pool_z = _retention_step(
                cfg, li, table[:, 0], q[:, 0], k[:, 0], v[:, 0],
                log_gate[:, 0], pool["S"], pool["z"])
            y = y[:, None]
        else:
            if b != 1 or write_block is None:
                raise ValueError(
                    "a paged retention chunk needs B == 1 and a "
                    f"write_block; got B={b}, T={t}")
            read = table[0, 0]
            y, state, norm = _chunk(
                cfg, q, k, v, log_gate, positions < valid_len[:, None],
                pool["S"][li, read][None], pool["z"][li, read][None])
            pool_s = pool["S"].at[li, write_block].set(state[0])
            pool_z = pool["z"].at[li, write_block].set(norm[0])
        y = y.reshape(b, t, -1).astype(x.dtype)
        return (x + llama.lora_dense(y, lp, "wo"),
                {"S": pool_s, "z": pool_z})


# ------------------------------------------------------- forward passes
def forward(cfg: BrumbyConfig, params: Params, tokens: jax.Array,
            positions: Optional[jax.Array] = None) -> jax.Array:
    """Token ids (B, S) -> float32 logits (B, S, vocab), no cache."""
    b, s = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    x = llama._decode_embed(cfg, params, tokens)
    x, _ = jax.lax.scan(
        lambda x, lp: (llama.mlp_block(cfg, retention_block(
            cfg, x, lp, positions), lp), None), x, params["layers"])
    return llama.lm_head(cfg, params, x, lambda a, _spec: a)


def forward_with_paged_cache(cfg: BrumbyConfig, params: Params,
                             tokens: jax.Array,
                             cache: Dict[str, jax.Array],
                             table: jax.Array, start_pos: jax.Array,
                             valid_len: Optional[jax.Array] = None,
                             logits_at: Optional[jax.Array] = None, *,
                             window: int,
                             write_block: Optional[jax.Array] = None,
                             write_pos: Optional[jax.Array] = None
                             ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """llama.forward_with_paged_cache's contract over the state pool:
    ``table[b, 0]`` is slot ``b``'s state block, positions feed RoPE
    alone, ``window`` tiles nothing. One scan that scans the layers'
    parameters and carries the pool (llama.py's rule)."""
    del window
    if write_pos is not None:
        refuse("speculative decoding (spec_k > 0)",
               "a rejected draft needs the state rolled back")
    b, t = tokens.shape
    _, valid_len, positions = llama.slot_positions(b, t, start_pos,
                                                   valid_len)
    x = llama._decode_embed(cfg, params, tokens)

    def layer_fn(carry, scanned):
        x, pool = carry
        lp, li = scanned
        x, pool = paged_retention_block(cfg, x, lp, li, pool, table,
                                        positions, valid_len, write_block)
        return (llama.mlp_block(cfg, x, lp), pool), None

    (x, pool), _ = jax.lax.scan(
        layer_fn, (x, dict(cache)),
        (params["layers"], jnp.arange(cfg.n_layers)))
    logits = llama.lm_head(cfg, params, llama.read_out(x, logits_at),
                           lambda a, _spec: a)
    return logits, pool


def verify_step_paged(cfg: BrumbyConfig, *args, **kwargs):
    refuse("speculative decoding (spec_k > 0)",
           "a rejected draft needs the state rolled back")
