"""GPipe pipeline parallelism tests (8-device CPU mesh)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import llama
from skypilot_tpu.ops import attention as attention_ops
from skypilot_tpu.parallel import mesh as mesh_lib, pipeline
from skypilot_tpu.train import trainer


def test_gpipe_matches_sequential_stages():
    """A stack of affine stages pipelined == applied sequentially."""
    mesh = mesh_lib.make_mesh({"pp": 4, "tp": 2})
    n_stages, m, mb, d = 4, 4, 2, 16
    w = jax.random.normal(jax.random.key(0), (n_stages, d, d)) * 0.3
    x = jax.random.normal(jax.random.key(1), (m, mb, d))

    def stage_fn(lp, x_mb, _ex):
        return jnp.tanh(x_mb @ lp["w"])

    out = jax.jit(lambda w, x: pipeline.gpipe(
        stage_fn, {"w": w}, x, mesh=mesh, num_microbatches=m))(w, x)

    ref = x
    for i in range(n_stages):
        ref = jnp.tanh(ref @ w[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_gpipe_no_pp_axis_sequential_fallback():
    mesh = mesh_lib.make_mesh({"dp": 8})
    n_stages, m, mb, d = 3, 2, 4, 8
    w = jax.random.normal(jax.random.key(0), (n_stages, d, d)) * 0.3
    x = jax.random.normal(jax.random.key(1), (m, mb, d))

    def stage_fn(lp, x_mb, _ex):
        return jnp.tanh(x_mb @ lp["w"])

    out = pipeline.gpipe(stage_fn, {"w": w}, x, mesh=mesh,
                         num_microbatches=m)
    ref = x
    for i in range(n_stages):
        ref = jnp.tanh(ref @ w[i])
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_llama_pipelined_matches_plain_forward():
    # f32 so pipelined vs plain is exact up to reassociation, not bf16 noise
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                              dtype=jnp.float32)
    mesh = mesh_lib.make_mesh({"dp": 2, "pp": 2, "tp": 2})
    rules = mesh_lib.PIPELINE_RULES
    params = llama.init(cfg, jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, 64)

    plain = llama.forward(cfg, params, tokens)
    piped = jax.jit(lambda p, t: llama.forward_pipelined(
        cfg, p, t, mesh=mesh, rules=rules, num_microbatches=2))(
            params, tokens)
    np.testing.assert_allclose(np.asarray(piped), np.asarray(plain),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
def test_llama_pipelined_trains(impl):
    """'pallas': a stage is already manual over 'pp', so the kernel's
    shard_map has to nest inside it over dp and tp (on a TPU 'auto' is
    the kernel)."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=64),
                              attention_impl=impl)
    before = attention_ops.trace_counts()
    mesh = mesh_lib.make_mesh({"dp": 2, "pp": 2, "tp": 2})
    rules = mesh_lib.PIPELINE_RULES
    params = llama.init(cfg, jax.random.key(0))
    tx = trainer.make_optimizer(trainer.TrainConfig(
        learning_rate=1e-2, warmup_steps=1, total_steps=30))
    state = trainer.init_train_state(params, tx)
    step = trainer.make_train_step(
        lambda p, t, constrain: llama.forward_pipelined(
            cfg, p, t, mesh=mesh, rules=rules, num_microbatches=2),
        tx, mesh, rules)
    tokens = jax.random.randint(jax.random.key(2), (4, 32), 0, 64)
    state, m0 = step(state, {"tokens": tokens})
    for _ in range(8):
        state, m = step(state, {"tokens": tokens})
    assert float(m["loss"]) < float(m0["loss"])
    after = attention_ops.trace_counts()
    assert (after["kernel"] > before["kernel"]) == (impl == "pallas")
    assert after["kernel_replicated"] == before["kernel_replicated"]
