"""The flash kernels, compiled for a TPU v5e that is described, not attached.

Interpret mode (every other kernel test) cannot show what the chip's
compiler refuses: a block not aligned to the tiling, more scoped VMEM
than a kernel may take, a kernel the partitioner cannot split. These
compile the real lowering, forward and backward, at the widths the main
path uses — head 256 (Gemma-2B) and 128 (Llama), S 2048 (resident
family) and 8192 (triangular when causal, streamed when not) — and look
for the kernel in the compiled program. Nothing runs: no results, no
times. The paged serving programs are compiled here too, at Mistral-7B's
widths: whether the KV pool stays one buffer is the TPU compiler's
choice of layouts, which the CPU's compile of the same program does not
make.

All in this one file and one process: the topology is described inside
a module-scoped fixture (only one process at a time may load the TPU
library, and xdist gives a file to one worker), and the kernels'
interpret choice is steered from here, not by an option of the program.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from skypilot_tpu.models import (brumby, deepseek, llama, mixtral,
                                 phi4flash)
from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.ops.pallas import flash_attention as fa
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.parallel import mesh_attention
from skypilot_tpu.serve import decode_engine, kv_pool


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever keeps the TPU
        # compiler from describing a chip here is a reason to skip.
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture
def for_the_chip(monkeypatch):
    """Real lowering instead of interpret mode, and the persistent
    cache off: a program compiled for a described chip is written to it
    but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    monkeypatch.setattr(fa, "_interpret", lambda: False)
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _fwd_bwd(attn):
    def loss(q, k, v):
        return attn(q, k, v).astype(jnp.float32).sum()
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))


def _compiled_text(fn, sharding, b, s, h, kvh, d):
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16,
                             sharding=sharding)
    kv = jax.ShapeDtypeStruct((b, s, kvh, d), jnp.bfloat16,
                              sharding=sharding)
    return fn.lower(q, kv, kv).compile().as_text()


@pytest.mark.parametrize("b,s,h,kvh,d,causal,family", [
    (1, 2048, 8, 1, 256, True, "resident"),    # Gemma-2B, the smoke
    (4, 2048, 8, 1, 256, True, "resident"),    # 16.6 MiB of scoped VMEM
    (1, 2048, 8, 1, 256, False, "resident"),
    (1, 2048, 16, 8, 128, True, "resident"),   # Llama GQA
    (1, 2048, 16, 8, 128, False, "resident"),
    (1, 8192, 8, 1, 256, True, "triangular"),
    (1, 8192, 8, 1, 256, False, "streamed"),
    (1, 8192, 32, 8, 128, True, "triangular"),
    (1, 8192, 16, 8, 128, False, "streamed"),
])
def test_flash_kernels_compile_for_v5e(topo, for_the_chip, b, s, h, kvh,
                                       d, causal, family):
    assert fa._use_resident(s, d) == (family == "resident")
    before = attention_ops.trace_counts()
    text = _compiled_text(
        _fwd_bwd(lambda q, k, v: fa.flash_attention(q, k, v,
                                                    causal=causal)),
        SingleDeviceSharding(topo.devices[0]), b, s, h, kvh, d)
    # Forward, dq and dk/dv: three kernels, and no reference fallback.
    assert text.count("tpu_custom_call") >= 3
    # Each under the name a device trace will show it by.
    suffix = {"resident": "_resident", "triangular": "_tri",
              "streamed": ""}[family]
    for kernel in ("fwd", "dq", "dkv"):
        assert f"(stpu_flash_{kernel}{suffix})" in text
    after = attention_ops.trace_counts()
    assert after["reference"] == before["reference"]


def test_kernel_partitions_over_a_described_mesh(topo, for_the_chip):
    """The LoRA recipe's {"fsdp": 4} mesh at Gemma-2B's shapes: the
    compiler does not partition a Mosaic kernel, the model's entry
    point has to hand it over inside a shard_map."""
    mesh = mesh_lib.make_mesh({"fsdp": 4}, devices=topo.devices)
    rules = mesh_lib.DEFAULT_RULES

    def attn(q, k, v):
        with mesh_lib.use_mesh(mesh, rules):
            return mesh_attention.attention_from_context(
                q, k, v, causal=True, impl="pallas")

    text = _compiled_text(_fwd_bwd(attn),
                          NamedSharding(mesh, P("fsdp")),
                          4, 2048, 8, 1, 256)
    assert text.count("tpu_custom_call") >= 3


@pytest.mark.parametrize("axes", [
    {"pp": 4},              # every axis manual already: no nesting
    {"pp": 2, "tp": 2},     # heads split inside a stage
    {"dp": 2, "pp": 2},     # batch split inside a stage
])
def test_kernel_compiles_inside_the_pipeline(topo, for_the_chip, axes):
    """A pipeline stage is already manual over 'pp'; the kernel's
    shard_map nests inside it over the axes still automatic. Forward
    and backward of the pipelined model, kernel forced."""
    cfg = dataclasses.replace(
        llama.LlamaConfig.tiny(vocab_size=512), dim=512, n_layers=4,
        n_heads=4, n_kv_heads=2, mlp_dim=1024, attention_impl="pallas")
    assert cfg.head_dim == 128
    mesh = mesh_lib.make_mesh(axes, devices=topo.devices)
    rules = mesh_lib.PIPELINE_RULES

    def loss(params, tokens):
        logits = llama.forward_pipelined(cfg, params, tokens, mesh=mesh,
                                         rules=rules, num_microbatches=2)
        return logits.astype(jnp.float32).mean()

    shardings = mesh_lib.tree_shardings(mesh, rules,
                                        llama.param_specs(cfg))
    params = jax.tree.map(
        lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
        jax.eval_shape(lambda: llama.init(cfg, jax.random.key(0))),
        shardings)
    tokens = jax.ShapeDtypeStruct(
        (4, 512), jnp.int32,
        sharding=rules.sharding(("batch", None), mesh))
    before = attention_ops.trace_counts()
    text = jax.jit(jax.value_and_grad(loss)).lower(
        params, tokens).compile().as_text()
    assert "tpu_custom_call" in text
    after = attention_ops.trace_counts()
    assert after["kernel"] > before["kernel"]
    assert after["reference"] == before["reference"]
    assert after["kernel_replicated"] == before["kernel_replicated"]


def _compile_paged(topo, family, entry, *, llama_layers=2,
                   kv_quant=False, weight_quant=False):
    """One of the engine's three paged programs compiled for one
    described chip at a benchmark cell's widths and slots (Mistral-7B
    at ``llama_layers``, Mixtral-8x7B at the cell's four, DeepSeek-V3's
    five), 20 blocks of 64 rows a slot. Returns (compiled, params,
    pool), the last two as shapes."""
    if family == "deepseek":
        model, slots = deepseek, 64
        cfg = deepseek.DeepseekV3Config.v3_5l_ep16()
    elif family == "mixtral":
        model, slots = mixtral, 64
        cfg = dataclasses.replace(mixtral.MixtralConfig.mixtral_8x7b(),
                                  n_layers=4)
    else:
        model, slots = llama, 32
        cfg = llama.LlamaConfig(vocab_size=32768, dim=4096,
                                n_layers=llama_layers, n_heads=32,
                                n_kv_heads=8, mlp_dim=14336,
                                rope_theta=1e6, max_seq_len=32768)
    bt, max_seq, window = 64, 1280, 256
    one_chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)

    def weights():
        tree = model.init(cfg, jax.random.key(0))
        return model.quantize_params(cfg, tree) if weight_quant else tree

    params = on_chip(jax.eval_shape(weights))
    pool = on_chip(jax.eval_shape(lambda: model.init_paged_cache(
        cfg, slots * max_seq // bt + 1, bt, quantized=kv_quant)))
    i32, table_len = jnp.int32, max_seq // bt
    sampling = (arg(jnp.float32, slots), arg(jnp.uint32, slots))
    args = {
        "_paged_step": (arg(i32, slots), arg(i32, slots),
                        arg(i32, slots, table_len), window, *sampling),
        "_paged_prefill_chunk": (arg(i32, bt), arg(i32, table_len),
                                 arg(i32), arg(i32), arg(i32), window,
                                 arg(i32, slots), arg(i32),
                                 arg(jnp.uint32), arg(jnp.float32)),
        "_paged_spec_step": (arg(i32, slots), arg(i32, slots, 4),
                             arg(i32, slots), arg(i32, slots),
                             arg(i32, slots, table_len), window,
                             *sampling),
    }[entry]
    compiled = getattr(decode_engine, entry).lower(
        cfg, params, pool, *args).compile()
    return compiled, params, pool


@pytest.mark.parametrize("family,entry,quantized", [
    ("llama", "_paged_step", False),
    ("llama", "_paged_prefill_chunk", False),
    ("llama", "_paged_prefill_chunk", True),
    ("llama", "_paged_spec_step", False),
    ("deepseek", "_paged_step", False),
    ("deepseek", "_paged_prefill_chunk", False),
])
def test_paged_programs_keep_the_pool_in_place_on_v5e(topo, for_the_chip,
                                                      family, entry,
                                                      quantized):
    """Mistral-7B's widths (two layers of them), 32 slots, 641 blocks
    of 64 rows — and DeepSeek-V3's (the cell's one dense and four sparse
    layers, 16 experts held, 64 slots, both scans): temporaries under a quarter of
    the pool and no pool-shaped copy. The latent pool as ONE leaf 576
    wide, or with a 64-wide leaf for the roped key, fails this: the TPU
    gives an array whose last axis is no multiple of 128 lanes a layout
    with the BLOCKS minor and converts the whole pool on the way in and
    out of every program (0.53 GB of temporaries beside a 0.47 GB pool;
    PERF.md, PR 28); hence the key's leaf of bytes. tests/test_paged_kv.py holds the same on the CPU
    for every family; this holds what only the TPU compiler decides. A
    prefill chunk written as one whole-block dynamic-update-slice
    passed there and read 1.5 times the pool in temporaries here: the
    compiler gave the carried pool the layout of the update operand
    ({4,2,3,1,0}: the projection's output, bitcast) and converted the
    whole pool back for the attention's gather in every layer. Written
    as a row scatter, like the decode step's, the pool keeps the
    layout it arrived in."""
    compiled, _, pool = _compile_paged(topo, family, entry,
                                       kv_quant=quantized)
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.25 * pool_bytes, (temp, pool_bytes)
    # Rows of tokens, not the int8 pool's scale a block and head.
    for leaf in (a for a in pool.values() if a.ndim > 3):
        dims = ",".join(map(str, leaf.shape))
        copies = re.findall(
            rf"^\s*(?:ROOT )?(\S+ = \w+\[{dims}\]\S* copy)\(",
            compiled.as_text(), re.M)
        assert not copies, copies


def _standing_alone(text, shapes):
    """The instructions of a compiled program whose result has one of
    ``shapes`` ("1,4096,4096", in any layout) and that are either a
    ``copy`` (anywhere) or stand outside every fused computation: a
    buffer of that shape of its own. A slice that is fused into the
    product that reads it appears only inside that product's
    ``fused_computation``."""
    found, fused = [], False
    for line in text.splitlines():
        if line and not line[0].isspace():    # a computation's head, or }
            fused = "fused_computation" in line.split("(")[0]
            continue
        m = re.match(r"\s*(?:ROOT )?(\S+) = \w+\[([\d,]+)\]\S* ([\w-]+)\(",
                     line)
        if m and m.group(2) in shapes and (m.group(3) == "copy"
                                           or not fused):
            found.append(f"{m.group(1)} {m.group(3)} [{m.group(2)}]")
    return found


def _weight_shaped(text, layers):
    """:func:`_standing_alone` for ONE layer of each stacked weight
    matrix, ``[1, *leaf.shape[1:]]``: a layer's weight cut out of its
    stack into a buffer of its own, or re-laid."""
    return _standing_alone(
        text, {",".join(map(str, (1,) + leaf.shape[1:]))
               for leaf in jax.tree.leaves(layers) if leaf.ndim >= 3})


def test_weight_shaped_finds_a_cut_out_and_a_re_laid_weight():
    """The reader above on four lines of the parent's compiled step
    (PR 30) and one fused slice: the text it must find, and the text it
    must let pass."""
    layers = {"wq": jax.ShapeDtypeStruct((2, 4096, 4096), jnp.bfloat16),
              "attn_norm": jax.ShapeDtypeStruct((2, 4096), jnp.bfloat16)}
    text = """\
%fused_computation.98 (param_0.1: bf16[2,4096,4096], param_1.2: s32[]) -> bf16[1,4096,4096] {
  ROOT %dynamic-slice.9 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)S(1)} dynamic-slice(%param_0.1, %param_1.2)
}
%body (p: (bf16[32,1,4096], bf16[2,4096,4096])) -> (bf16[32,1,4096]) {
  %slice.1 = bf16[1,4096]{1,0} dynamic-slice(%norm, %i)
  %constant_dynamic-slice_fusion.8 = bf16[1,4096,4096]{2,1,0:T(8,128)(2,1)S(1)} fusion(%w, %i), kind=kLoop
  %copy.57 = bf16[1,4096,4096]{1,2,0:T(8,128)(2,1)S(1)} copy(%constant_dynamic-slice_fusion.8)
}
"""
    assert _weight_shaped(text, layers) == [
        "%constant_dynamic-slice_fusion.8 fusion [1,4096,4096]",
        "%copy.57 copy [1,4096,4096]"]


@pytest.mark.parametrize("family,entry,weight_quant", [
    ("llama", "_paged_step", False),
    ("llama", "_paged_prefill_chunk", False),
    ("llama", "_paged_spec_step", False),
    ("mixtral", "_paged_step", False),
    ("llama", "_paged_step", True),
])
def test_paged_programs_read_each_weight_in_place_on_v5e(
        topo, for_the_chip, family, entry, weight_quant):
    """No program of the engine cuts a layer's weight out of its stack
    into a buffer of its own, or transposes one: every matrix of
    ``params["layers"]`` is read once a layer, where it lies, by the
    product it feeds. Until PR 32 the v5e compiler gave q the layout
    that the reshape to heads and rope's half-split want and re-laid
    the WEIGHTS to match — ``constant_dynamic-slice_fusion
    bf16[1,4096,4096]{2,1,0}`` and ``copy bf16[1,4096,4096]{1,2,0}`` for
    ``wq``, the same at ``[1,4096,1024]`` for ``wk`` and ``wv``, in
    every layer of every step, chunk and verify step, in Mixtral's
    step too and as ``s8`` under int8 weights (8.7 % of the device's
    time for ``wq`` alone in ``mistral7b-chat-steady``; ledger, PR 30).
    ``llama.cached_qkv_proj`` finishes the three products first; this
    is its contract, whatever it is written with. The CPU's compiler
    never re-laid them: only this compile shows it. Mistral-7B at the
    cell's 16 layers: at two the compiler fetches a whole 64 MB stack
    ahead of the loop in per-layer ``slice-start``s, which is neither
    the fault nor what a deployment's depth compiles to."""
    compiled, params, _ = _compile_paged(topo, family, entry,
                                         llama_layers=16,
                                         weight_quant=weight_quant)
    found = _weight_shaped(compiled.as_text(), params["layers"])
    assert not found, found


@pytest.mark.parametrize("entry", ["_paged_step", "_paged_prefill_chunk"])
def test_expert_loop_reads_each_held_expert_in_place_on_v5e(
        topo, for_the_chip, entry):
    """DeepSeek-V3's step and chunk at the cell's shapes (64 slots,
    four sparse layers of 16 held experts): the expert layer's loop
    (PERF.md, PR 34) reads ``we_gate[layer, e]``, ``we_up[layer, e]``
    and ``we_down[layer, e]`` where they lie in their (4, 16, ...)
    stacks. No buffer has the shape of one expert's matrix, of a
    layer's sixteen or of a whole stack outside the product that reads
    it (cut out ahead of the loop, a layer's experts would be a copy
    of 0.7 GB in every layer of every step), none is re-laid, every
    fused computation that takes a stack is called from inside the
    loop's body, the three products with it, and the latent pool is
    still one buffer aliased in place."""
    compiled, params, pool = _compile_paged(topo, "deepseek", entry)
    text = compiled.as_text()
    experts = {name: params["moe_layers"][name]
               for name in deepseek._EXPERTS}
    shapes = {",".join(map(str, shape))
              for leaf in experts.values()
              for shape in (leaf.shape, (1,) + leaf.shape[1:],
                            leaf.shape[1:], (1, 1) + leaf.shape[2:],
                            (1,) + leaf.shape[2:], leaf.shape[2:])}
    found = [line for line in _standing_alone(text, shapes)
             if " parameter " not in line
             and " get-tuple-element " not in line]
    assert not found, found
    stacks = "|".join(re.escape(",".join(map(str, leaf.shape)))
                      for leaf in experts.values())
    readers = re.findall(
        rf"^%(\S+) \([^)]*: bf16\[(?:{stacks})\]", text, re.M)
    assert len(readers) >= 3, readers
    for name in readers:
        calls = [line for line in text.splitlines()
                 if f"calls=%{name}," in line or f"calls=%{name} " in line]
        assert calls and all("stpu.moe/while/body" in line
                             for line in calls), (name, calls)
    assert len(re.findall(r"stpu\.moe/while/body/dot_general", text)) >= 3
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert compiled.memory_analysis().alias_size_in_bytes >= pool_bytes


@pytest.mark.parametrize("entry", ["_paged_step", "_paged_prefill_chunk"])
def test_state_pool_programs_stay_in_place_on_v5e(topo, for_the_chip,
                                                  entry):
    """Brumby-14B at the cell's six layers, 16 slots and 29 state
    blocks of 228 MB: the pool (6.62 GB beside 7.08 GB of weights; a
    second one does not fit the chip) is one buffer in both programs —
    temporaries under ONE block, no pool-shaped copy — no weight is cut
    out of its stack or re-laid, and the decode step's state update is
    the Mosaic kernel, which interpret mode never compiles (a row of
    ``phi(q)`` sliced at a lane offset did not broadcast over sublanes
    until it was read 128 lanes at a time; PERF.md, PR 33). The chunk's
    whole-block write of the new state is one the TPU compiles in
    place: its operand comes out of a product with ``D`` minor, the
    layout the pool has."""
    cfg, slots, blocks = brumby.BrumbyConfig.b14_6l(), 16, 29
    one_chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    params = on_chip(jax.eval_shape(
        lambda: brumby.init(cfg, jax.random.key(0))))
    pool = on_chip(jax.eval_shape(
        lambda: brumby.init_paged_cache(cfg, blocks, 64)))
    i32 = jnp.int32
    args = {
        "_paged_step": (arg(i32, slots), arg(i32, slots),
                        arg(i32, slots, 1), 64, arg(jnp.float32, slots),
                        arg(jnp.uint32, slots)),
        "_paged_prefill_chunk": (arg(i32, 64), arg(i32, 1), arg(i32),
                                 arg(i32), arg(i32), 64, arg(i32, slots),
                                 arg(i32), arg(jnp.uint32),
                                 arg(jnp.float32)),
    }[entry]
    compiled = getattr(decode_engine, entry).lower(
        cfg, params, pool, *args).compile()
    text = compiled.as_text()
    block = sum(a.size * a.dtype.itemsize
                for a in jax.tree.leaves(pool)) // blocks
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < block, memory.temp_size_in_bytes
    assert memory.alias_size_in_bytes >= blocks * block
    for leaf in pool.values():
        dims = ",".join(map(str, leaf.shape))
        copies = re.findall(
            rf"^\s*(?:ROOT )?(\S+ = \w+\[{dims}\]\S* copy)\(", text,
            re.M)
        assert not copies, copies
    found = _weight_shaped(text, params["layers"])
    assert not found, found
    assert ("stpu_retention_step" in text) == (entry == "_paged_step")


def _copies_of(text, dims):
    """Instructions of a compiled program that copy an array of shape
    ``dims``: a ``copy`` yields the array, a ``copy-start`` a tuple of
    source, destination and a context word."""
    return re.findall(
        rf"^\s*(?:ROOT )?(\S+ = (?:\w+\[{dims}\]\S* copy"
        rf"|\(\w+\[{dims}\].* copy-start))\(", text, re.M)


def test_a_copy_and_a_fetch_ahead_of_a_shape_are_both_found():
    text = """
  %copy.7 = bf16[8,625,10,64,128]{4,3,2,1,0:T(8,128)(2,1)} copy(%p.1)
  %copy-start.9 = (bf16[8,625,10,64,128]{4,3,2,1,0:T(8,128)(2,1)}, bf16[8,625,10,64,128]{4,3,2,1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) copy-start(%p.2)
  %copy-done.9 = bf16[8,625,10,64,128]{4,3,2,1,0} copy-done(%copy-start.9)
  %copy.8 = f32[64,200064]{1,0} copy(%logits)
"""
    found = _copies_of(text, "8,625,10,64,128")
    assert [f.split()[0] for f in found] == ["%copy.7", "%copy-start.9"]
    assert not _copies_of(text, "1,1329,10,64,128")


@pytest.mark.parametrize("entry", ["_paged_step", "_paged_prefill_chunk"])
def test_three_kinds_of_pool_stay_in_place_on_v5e(topo, for_the_chip,
                                                  entry):
    """Phi-4-mini-flash-reasoning whole (32 layers, every width, 7.71 GB
    of weights), 64 slots and the engine's own pools for them: 1,329
    blocks of the full layer, 625 of the eight window layers, 113
    states, 2.44 GB. Every pool leaf is ONE buffer in both programs,
    aliased from entry to exit, with no copy of its shape, synchronous
    or fetched ahead (``copy-start``: what a kernel that takes a pool
    through a ``BlockSpec`` costs, PERF.md section 7). What the
    programs keep beside them (rehearsal 3, PR 37): the step 54.8 MB, of
    which 51.2 are the float32 logits of its 64 rows, the chunk 0.01 GB. Until PR 37 the step kept 0.63 GB, the keys and
    values it gathered for all 64 slots (layer 17's once for its eight
    readers, 0.42 GB, and a window layer's 0.19 GB); now its sixteen
    attention reads are calls of ``stpu_paged_diff_attn``, which copies
    single blocks of the decoding slots out of the pools where they lie,
    and the chunk, one slot's 9 + 20 blocks, gathers as before and
    holds no such call. Two things this compile refused before any chip
    call, both the TPU's choice of layouts: with a key/value pair's 10
    pairs next to the lanes (no multiple of 8 sublanes) every pool was
    converted to (rows, lanes) tiles and back around each program (3.7
    GB of temporaries); and with the pairs outside a block's rows but
    the new rows scattered a (pairs, lanes) slab at a time, the carried
    pool took the slab's layout and was converted for the gather, 3.8
    GB again. One 128-lane row a (token, pair) keeps the layout the
    pool arrives in. No layer's weight is cut out of a scanned stack."""
    cfg, slots = phi4flash.Phi4FlashConfig(), 64
    geo = decode_engine.resolve_kv_geometry(
        slots=slots, max_seq=1280, use_manifest=False,
        layout=kv_pool.pool_layout(cfg))
    assert geo["pools"] == {"global": 1329, "window": 625, "state": 113}
    one_chip = SingleDeviceSharding(topo.devices[0])
    on_chip = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=one_chip), tree)
    arg = lambda dtype, *shape: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one_chip)
    params = on_chip(jax.eval_shape(
        lambda: phi4flash.init(cfg, jax.random.key(0))))
    pool = on_chip(jax.eval_shape(
        lambda: phi4flash.init_paged_cache(cfg, geo["pools"], 64)))
    i32, table_len = jnp.int32, geo["table_len"]
    args = {
        "_paged_step": (arg(i32, slots), arg(i32, slots),
                        arg(i32, slots, table_len), 256,
                        arg(jnp.float32, slots), arg(jnp.uint32, slots)),
        "_paged_prefill_chunk": (arg(i32, 64), arg(i32, table_len),
                                 arg(i32), arg(i32), arg(i32), 256,
                                 arg(i32, slots), arg(i32),
                                 arg(jnp.uint32), arg(jnp.float32)),
    }[entry]
    compiled = getattr(decode_engine, entry).lower(
        cfg, params, pool, *args).compile()
    text = compiled.as_text()
    pool_bytes = sum(a.size * a.dtype.itemsize
                     for a in jax.tree.leaves(pool))
    assert pool_bytes == 2_438_379_520
    memory = compiled.memory_analysis()
    bound = {"_paged_step": 0.06e9, "_paged_prefill_chunk": 0.02e9}[entry]
    assert memory.temp_size_in_bytes < bound, memory.temp_size_in_bytes
    assert memory.alias_size_in_bytes >= pool_bytes
    assert ("stpu_paged_diff_attn" in text) == (entry == "_paged_step")
    for name, leaf in pool.items():
        dims = ",".join(map(str, leaf.shape))
        copies = _copies_of(text, dims)
        assert not copies, (name, copies)
        # One layout from entry to exit: the one the leaf arrives in.
        layouts = set(re.findall(rf"\w+\[{dims}\](\{{[\d,]*)", text))
        minor_to_major = "{" + ",".join(
            str(i) for i in reversed(range(leaf.ndim)))
        assert layouts == {minor_to_major}, (name, layouts)
    # The scanned stacks (8 and 7 layers): no layer cut out or re-laid.
    # The middle layers' matrices are parameters of their own, one
    # layer each, which the compiler may fetch ahead (``copy-start``).
    scanned = {g: params[g] for g in ("front", "back")}
    found = [f for f in _weight_shaped(text, scanned)
             if f.split()[1] not in ("parameter", "get-tuple-element",
                                     "bitcast", "copy-start", "copy-done")
             and "[1,16,5120]" not in f]      # exp(a_log): 0.3 MB
    assert not found, found
