"""LLaMA flagship tests (BASELINE config 3 path)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.models.llama import (LlamaConfig, init_params, forward,
                                     loss_fn, param_shardings, LLAMA_TINY)
from paddle_tpu.distributed.trainer import MeshConfig, Trainer, make_mesh


CFG = LlamaConfig(vocab_size=128, hidden_size=64, intermediate_size=128,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=64,
                  dtype=jnp.float32, remat=False)


class TestFunctionalLlama:
    @pytest.mark.slow
    def test_forward_shape_and_finite(self):
        params = init_params(CFG, jax.random.key(0))
        tokens = jnp.zeros((2, 8), jnp.int32)
        logits = forward(params, tokens, CFG)
        assert logits.shape == (2, 8, 128)
        assert np.isfinite(np.asarray(logits)).all()

    def test_causality(self):
        """Changing a future token must not affect earlier logits."""
        params = init_params(CFG, jax.random.key(0))
        rng = np.random.RandomState(0)
        t1 = rng.randint(0, 128, (1, 8)).astype(np.int32)
        t2 = t1.copy()
        t2[0, -1] = (t2[0, -1] + 1) % 128
        l1 = np.asarray(forward(params, jnp.asarray(t1), CFG))
        l2 = np.asarray(forward(params, jnp.asarray(t2), CFG))
        np.testing.assert_allclose(l1[0, :-1], l2[0, :-1], atol=1e-5)
        assert not np.allclose(l1[0, -1], l2[0, -1])

    @pytest.mark.slow
    def test_gqa_matches_full_heads_shape(self):
        cfg_full = LlamaConfig(**{**CFG.__dict__, "num_key_value_heads": 4})
        params = init_params(cfg_full, jax.random.key(0))
        logits = forward(params, jnp.zeros((1, 4), jnp.int32), cfg_full)
        assert logits.shape == (1, 4, 128)

    @pytest.mark.slow
    def test_loss_decreases_under_training(self):
        params = init_params(CFG, jax.random.key(0))
        mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
        trainer = Trainer(lambda p, t, l: loss_fn(p, t, l, CFG), mesh,
                          param_shardings(mesh, CFG),
                          data_spec=P(), lr=1e-2)
        state = trainer.init_state(params)
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, 128, (4, 16)), jnp.int32)
        labels = tokens  # memorise identity mapping
        losses = []
        for _ in range(5):
            state, m = trainer.step(state, tokens, labels)
            losses.append(float(m["loss"]))
        assert losses[-1] < losses[0], losses

    @pytest.mark.slow
    def test_remat_same_loss(self):
        cfg_r = LlamaConfig(**{**CFG.__dict__, "remat": True})
        params = init_params(CFG, jax.random.key(0))
        tokens = jnp.asarray(np.random.RandomState(1).randint(
            0, 128, (2, 8)), jnp.int32)
        l1 = loss_fn(params, tokens, tokens, CFG)
        l2 = loss_fn(params, tokens, tokens, cfg_r)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-6)


class TestShardedLlama:
    @pytest.mark.slow
    def test_sharded_matches_single_device(self):
        """The SPMD-partitioned step must equal the single-device step."""
        params = init_params(CFG, jax.random.key(0))
        rng = np.random.RandomState(0)
        tokens = jnp.asarray(rng.randint(0, 128, (4, 16)), jnp.int32)
        labels = jnp.asarray(rng.randint(0, 128, (4, 16)), jnp.int32)

        mesh1 = make_mesh(MeshConfig(), devices=jax.devices()[:1])
        t1 = Trainer(lambda p, t, l: loss_fn(p, t, l, CFG), mesh1,
                     param_shardings(mesh1, CFG), data_spec=P(), lr=1e-3,
                     donate=False)
        s1 = t1.init_state(init_params(CFG, jax.random.key(0)))
        s1, m1 = t1.step(s1, tokens, labels)

        mesh8 = make_mesh(MeshConfig(dp=2, fsdp=2, tp=2),
                          devices=jax.devices()[:8])
        t8 = Trainer(lambda p, t, l: loss_fn(p, t, l, CFG), mesh8,
                     param_shardings(mesh8, CFG),
                     data_spec=P(("dp", "fsdp")), lr=1e-3, donate=False)
        s8 = t8.init_state(init_params(CFG, jax.random.key(0)))
        s8, m8 = t8.step(s8, tokens, labels)

        np.testing.assert_allclose(float(m1["loss"]), float(m8["loss"]),
                                   rtol=1e-5)
        w1 = np.asarray(s1.params["layers"]["q_proj"])
        w8 = np.asarray(s8.params["layers"]["q_proj"])
        np.testing.assert_allclose(w1, w8, rtol=1e-4, atol=1e-5)

    def test_param_shardings_cover_tree(self):
        mesh = make_mesh(MeshConfig(fsdp=2, tp=2, dp=2),
                         devices=jax.devices()[:8])
        params = init_params(CFG, jax.random.key(0))
        specs = param_shardings(mesh, CFG)
        jax.tree_util.tree_map(lambda p, s: None, params, specs)  # same tree

    @pytest.mark.slow
    def test_grad_accumulation(self):
        params = init_params(CFG, jax.random.key(0))
        mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
        tr = Trainer(lambda p, t, l: loss_fn(p, t, l, CFG), mesh,
                     param_shardings(mesh, CFG), data_spec=P(),
                     lr=1e-3, accumulate_steps=2)
        state = tr.init_state(params)
        rng = np.random.RandomState(0)
        # [accum, micro_batch, seq]
        tokens = jnp.asarray(rng.randint(0, 128, (2, 2, 16)), jnp.int32)
        state, m = tr.step(state, tokens, tokens)
        assert np.isfinite(float(m["loss"]))


class TestLlamaLayerAPI:
    @pytest.mark.slow
    def test_layer_model_forward_backward(self):
        from paddle_tpu.models.llama import LlamaForCausalLM
        cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                          intermediate_size=64, num_hidden_layers=1,
                          num_attention_heads=2, num_key_value_heads=2,
                          dtype=jnp.float32)
        model = LlamaForCausalLM(cfg)
        ids = paddle.to_tensor(np.random.randint(0, 64, (2, 8)))
        loss, logits = model(ids, labels=ids)
        assert logits.shape == [2, 8, 64]
        loss.backward()
        grads = [p.grad for p in model.parameters()]
        assert all(g is not None for g in grads)


class TestDryrun:
    @pytest.mark.slow
    @pytest.mark.parametrize("n", [1, 2, 4, 8])
    def test_dryrun_sizes(self, n):
        from paddle_tpu.distributed.dryrun import run_dryrun
        run_dryrun(n)

    def test_resolve_devices_takes_the_default_backend(self):
        """The mesh is the default backend's devices (here the CPU mesh
        conftest chose) — no probe, no other platform."""
        import jax
        from paddle_tpu.distributed.dryrun import resolve_devices
        devices = resolve_devices(2)
        assert devices == jax.devices()[:2]

    def test_resolve_devices_raises_when_devices_are_absent(self):
        """More devices than the backend has is an error that says how
        to get a virtual mesh — never a quiet switch of platform."""
        from paddle_tpu.distributed.dryrun import resolve_devices
        with pytest.raises(RuntimeError, match="64 devices asked for"):
            resolve_devices(64)


@pytest.mark.slow
def test_trainer_nan_watch():
    """check_nan_inf catches non-finite loss inside the compiled
    hybrid-parallel step."""
    import jax.numpy as jnp
    from paddle_tpu.core.flags import GLOBAL_FLAGS
    from paddle_tpu.models.llama import init_params, param_shardings

    mesh = make_mesh(MeshConfig())
    params = init_params(CFG, jax.random.PRNGKey(0))

    def poisoned(p, t, l):
        return loss_fn(p, t, l, CFG) + jnp.log(jnp.float32(-1.0))

    GLOBAL_FLAGS.set("check_nan_inf", True)
    try:
        tr = Trainer(poisoned, mesh, param_shardings(mesh, CFG), lr=1e-4)
        state = tr.init_state(params)
        toks = jnp.zeros((2, 16), jnp.int32)
        import pytest as _pytest
        with _pytest.raises(FloatingPointError, match="check_nan_inf"):
            tr.step(state, toks, toks)
    finally:
        GLOBAL_FLAGS.set("check_nan_inf", False)


@pytest.mark.slow
def test_fused_linear_cross_entropy_matches_unfused():
    """Chunked lm-head+CE (Liger-style) must match the materialized
    logits path in value and gradient."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import (LlamaConfig, init_params, loss_fn,
                                         forward)
    from paddle_tpu.models._common import (masked_cross_entropy,
                                           fused_linear_cross_entropy)

    cfg = LlamaConfig(vocab_size=503, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=64)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 503, (2, 33)),
                       jnp.int32)
    labels = jnp.roll(toks, -1, 1).at[:, -1].set(-1)
    fused = float(loss_fn(params, toks, labels, cfg))
    unfused = float(masked_cross_entropy(forward(params, toks, cfg),
                                         labels))
    assert abs(fused - unfused) < 1e-4
    gf = jax.grad(lambda p: loss_fn(p, toks, labels, cfg))(params)
    gu = jax.grad(lambda p: masked_cross_entropy(
        forward(p, toks, cfg), labels))(params)
    mx = max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32) -
                                   b.astype(jnp.float32)).max()), gf, gu)))
    assert mx < 2e-2  # bf16 params

    # helper with odd T / small chunks
    h = jnp.asarray(np.random.randn(7, 16), jnp.float32)
    hd = jnp.asarray(np.random.randn(16, 29), jnp.float32)
    lb = jnp.asarray(np.random.randint(-1, 29, (7,)), jnp.int32)
    assert abs(float(fused_linear_cross_entropy(h, hd, lb, chunk_size=3)) -
               float(masked_cross_entropy(h @ hd, lb))) < 1e-5
