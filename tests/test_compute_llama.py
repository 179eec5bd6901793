"""Hermetic compute tests on the 8-device CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from skypilot_tpu.models import llama
from skypilot_tpu.parallel import mesh as mesh_lib
from skypilot_tpu.train import trainer


def test_mesh_construction():
    m = mesh_lib.make_mesh({"dp": 2, "tp": 4})
    assert m.shape == {"dp": 2, "tp": 4}
    m2 = mesh_lib.make_mesh({"dp": -1, "tp": 2})
    assert m2.shape["dp"] == 4


def test_sharding_rules_drop_absent_axes():
    m = mesh_lib.make_mesh({"dp": 2, "tp": 4})
    rules = mesh_lib.DEFAULT_RULES
    spec = rules.spec(("batch", "act_seq", "heads"), m)
    # fsdp/sp absent from mesh -> batch maps to ('dp',), act_seq drops.
    assert spec == jax.sharding.PartitionSpec("dp", None, "tp")


def test_llama_forward_shapes():
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.key(0))
    tokens = jnp.zeros((2, 16), dtype=jnp.int32)
    logits = llama.forward(cfg, params, tokens)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert logits.dtype == jnp.float32


def test_llama_causality():
    """Changing a future token must not change past logits."""
    cfg = llama.LlamaConfig.tiny()
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.key(0))
    t1 = jnp.array([[1, 2, 3, 4, 5, 6, 7, 8]], dtype=jnp.int32)
    t2 = t1.at[0, 5].set(9)
    l1 = llama.forward(cfg, params, t1)
    l2 = llama.forward(cfg, params, t2)
    np.testing.assert_allclose(l1[0, :5], l2[0, :5], rtol=2e-2, atol=2e-3)
    assert not np.allclose(l1[0, 5:], l2[0, 5:], atol=1e-4)


def test_train_step_decreases_loss_sharded():
    cfg = llama.LlamaConfig.tiny(vocab_size=64)
    mesh = mesh_lib.make_mesh({"dp": 2, "fsdp": 2, "tp": 2})
    rules = mesh_lib.DEFAULT_RULES
    params = llama.init(cfg, jax.random.key(0))
    tx = trainer.make_optimizer(trainer.TrainConfig(
        learning_rate=1e-2, warmup_steps=1, total_steps=50))
    state = trainer.init_train_state(params, tx)

    shardings = trainer.state_shardings(
        mesh, rules, llama.param_specs(cfg),
        jax.eval_shape(lambda: state))
    state = jax.device_put(state, shardings)

    step = trainer.make_train_step(
        lambda p, t, constrain: llama.forward(cfg, p, t,
                                              constrain=constrain),
        tx, mesh, rules)
    tokens = jax.random.randint(jax.random.key(1), (4, 32), 0, 64)
    batch = {"tokens": tokens}
    state, m0 = step(state, batch)
    for _ in range(10):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])
    # params actually sharded: embed spec ("vocab","embed") -> (tp, fsdp).
    emb_shard = state.params["embed"].sharding
    assert emb_shard.spec == jax.sharding.PartitionSpec("tp", "fsdp")


def test_kv_cache_decode_matches_full_forward():
    """Cached incremental decode (prefill + per-token steps) must produce
    exactly the greedy continuation that full-recompute forward gives —
    including with a right-padded prompt bucket."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from skypilot_tpu.models import llama

    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    b, s, mt = 2, 13, 6
    prompt = jax.random.randint(jax.random.key(1), (b, s), 1, 128)

    # Reference: recompute the full prefix per token.
    buf = jnp.zeros((b, s + mt), jnp.int32).at[:, :s].set(prompt)
    ref = []
    for i in range(mt):
        logits = llama.forward(cfg, params, buf[:, :s + i])
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        buf = buf.at[:, s + i].set(nxt)
        ref.append(nxt)
    ref = jnp.stack(ref, axis=1)

    # Cached, with the prompt right-padded to a bucket of 16.
    padded = jnp.zeros((b, 16), jnp.int32).at[:, :s].set(prompt)
    got = llama.greedy_decode(cfg, params, padded, jnp.int32(s), mt,
                              max_seq=16 + mt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_multislice_mesh_and_train_step():
    """Hybrid DCN x ICI mesh: dp crosses slices, fsdp within; a train
    step compiles and runs with DEFAULT_RULES on the virtual mesh."""
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.models import llama
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.train import trainer

    mesh = mesh_lib.make_multislice_mesh({"fsdp": -1}, num_slices=2)
    assert mesh.axis_names == ("dp", "fsdp")
    assert mesh.shape["dp"] == 2 and mesh.shape["fsdp"] == 4

    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = llama.init(cfg, jax.random.key(0))
    tx = trainer.make_optimizer(
        trainer.TrainConfig(warmup_steps=1, total_steps=10))
    state = trainer.init_train_state(params, tx)
    state = jax.device_put(state, trainer.state_shardings(
        mesh, mesh_lib.DEFAULT_RULES, llama.param_specs(cfg), state))
    step = trainer.make_train_step(
        lambda p, t, constrain: llama.forward(cfg, p, t,
                                              constrain=constrain),
        tx, mesh, mesh_lib.DEFAULT_RULES)
    tokens = jax.random.randint(jax.random.key(1), (8, 64), 0, 128)
    state, metrics = step(state, {"tokens": tokens})
    assert jnp.isfinite(metrics["loss"]).item()

    # Error paths: indivisible slices, dcn/ici name clash.
    import pytest
    with pytest.raises(ValueError, match="divisible"):
        mesh_lib.make_multislice_mesh({"fsdp": -1}, num_slices=3)
    with pytest.raises(ValueError, match="also named"):
        mesh_lib.make_multislice_mesh({"dp": -1}, num_slices=2)


def test_make_mesh_from_env(monkeypatch):
    from skypilot_tpu.train import distributed
    monkeypatch.setenv("SKYPILOT_NUM_SLICES", "2")
    mesh = distributed.make_mesh_from_env({"fsdp": -1})
    assert mesh.axis_names == ("dp", "fsdp") and mesh.shape["dp"] == 2
    monkeypatch.setenv("SKYPILOT_NUM_SLICES", "1")
    mesh = distributed.make_mesh_from_env({"fsdp": -1})
    assert mesh.axis_names == ("fsdp",)


def test_chunked_ce_matches_classic():
    """chunked_cross_entropy_loss (fused head+CE, logits never
    materialized) must agree with the classic full-logits loss in value
    AND gradients — including a non-chunk-divisible sequence (pad+mask
    path) and a loss mask."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from skypilot_tpu.train import trainer

    b, s, d, v = 2, 9, 16, 37   # s=9 exercises padding (CE_CHUNK > s)
    key = jax.random.key(0)
    hidden = jax.random.normal(key, (b, s, d), dtype=jnp.float32)
    head = jax.random.normal(jax.random.key(1), (d, v),
                             dtype=jnp.float32)
    targets = jax.random.randint(jax.random.key(2), (b, s), 0, v)
    mask = (jax.random.uniform(jax.random.key(3), (b, s)) > 0.3)

    def classic(hidden, head):
        logits = hidden @ head
        return trainer.cross_entropy_loss(logits, targets, mask)

    def chunked(hidden, head):
        return trainer.chunked_cross_entropy_loss(hidden, head, targets,
                                                  mask)

    old = trainer.CE_CHUNK
    trainer.CE_CHUNK = 4          # force multiple chunks + padding
    try:
        lc, gc = jax.value_and_grad(classic, argnums=(0, 1))(hidden,
                                                             head)
        lk, gk = jax.value_and_grad(chunked, argnums=(0, 1))(hidden,
                                                             head)
    finally:
        trainer.CE_CHUNK = old
    np.testing.assert_allclose(float(lc), float(lk), rtol=1e-5)
    for a, b_ in zip(gc, gk):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-4, atol=1e-5)


def test_adafactor_optimizer_trains():
    """TrainConfig(optimizer='adafactor') builds a working optimizer
    (factored second moment — the 8B-shape depth enabler)."""
    import jax
    import jax.numpy as jnp
    from skypilot_tpu.models import llama
    from skypilot_tpu.parallel import mesh as mesh_lib
    from skypilot_tpu.train import trainer

    cfg = llama.LlamaConfig.tiny(vocab_size=64)
    tx = trainer.make_optimizer(trainer.TrainConfig(
        warmup_steps=1, total_steps=50, learning_rate=1e-2,
        optimizer="adafactor"))
    state = trainer.init_train_state(llama.init(cfg, jax.random.key(0)),
                                     tx)
    mesh = mesh_lib.make_mesh({"dp": -1})
    step = trainer.make_train_step(
        lambda p, t, constrain: llama.forward(cfg, p, t,
                                              constrain=constrain),
        tx, mesh, mesh_lib.DEFAULT_RULES)
    batch = {"tokens": jax.random.randint(jax.random.key(1), (2, 64),
                                          0, 64)}
    state, m0 = step(state, batch)
    for _ in range(12):
        state, m = step(state, batch)
    assert float(m["loss"]) < float(m0["loss"])


def test_training_forward_keeps_the_fused_projection():
    """PR 32 finishes q, k and v behind an optimization barrier in the
    forwards against a KV cache (llama.cached_qkv_proj), so that the
    v5e compiler reads each weight where it lies. The training forward
    is not one of them: no barrier, and the matmuls it has always had —
    a layer's q, k, v, scores, values, wo and three of the MLP (the
    scan's body, once), and the head."""
    cfg = llama.LlamaConfig.tiny(vocab_size=128)
    params = jax.eval_shape(lambda: llama.init(cfg, jax.random.key(0)))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    # A jaxpr prints the jaxprs inside it (scan and remat bodies) too.
    text = str(jax.make_jaxpr(
        lambda p, t: llama.forward(cfg, p, t))(params, tokens))
    assert "optimization_barrier" not in text
    assert text.count(" dot_general[") == 10
    # The control: the same reading finds the barriers where they belong.
    cache = jax.eval_shape(lambda: llama.init_cache(cfg, 2, 32))
    cached = str(jax.make_jaxpr(
        lambda p, t, c: llama.forward_with_cache(cfg, p, t, c, 0))(
            params, tokens, cache))
    assert cached.count(" optimization_barrier ") == 3
