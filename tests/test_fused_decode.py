"""Cached-decode paths of the fused transformer ops (reference:
python/paddle/incubate/nn/functional/fused_transformer.py generation
mode: cache_kvs/time_step/pre_caches/rotary_embs; CacheKV growth in
fused_multi_head_attention)."""
import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.incubate.nn.functional as IF

t = paddle.to_tensor


def _mk_stack(rng, n_layers, hid, nh, ffn, dtype=np.float32):
    hd = hid // nh
    mk = lambda *s: t((rng.randn(*s) * 0.05).astype(dtype))
    return dict(
        ln_scales=[mk(hid) + 1.0 for _ in range(n_layers)],
        ln_biases=[mk(hid) for _ in range(n_layers)],
        qkv_weights=[mk(3, nh, hd, hid) for _ in range(n_layers)],
        qkv_biases=[mk(3, nh, hd) for _ in range(n_layers)],
        linear_weights=[mk(hid, hid) for _ in range(n_layers)],
        linear_biases=[mk(hid) for _ in range(n_layers)],
        ffn_ln_scales=[mk(hid) + 1.0 for _ in range(n_layers)],
        ffn_ln_biases=[mk(hid) for _ in range(n_layers)],
        ffn1_weights=[mk(hid, ffn) for _ in range(n_layers)],
        ffn1_biases=[mk(ffn) for _ in range(n_layers)],
        ffn2_weights=[mk(ffn, hid) for _ in range(n_layers)],
        ffn2_biases=[mk(hid) for _ in range(n_layers)],
    )


def _caches(b, nh, hd, m, n_layers):
    return [t(np.zeros((2, b, nh, m, hd), np.float32))
            for _ in range(n_layers)]


class TestFusedMultiTransformerCached:
    def test_prefill_plus_decode_matches_full_causal(self):
        """Prefill S tokens then greedy-decode G more, one
        time_step'ed call each; the per-position outputs must equal ONE
        non-cached causal run over the full S+G sequence."""
        rng = np.random.RandomState(0)
        B, S, G, HID, NH, FFN, L = 2, 5, 3, 16, 2, 32, 2
        HD = HID // NH
        w = _mk_stack(rng, L, HID, NH, FFN)
        x_full = (rng.randn(B, S + G, HID) * 0.1).astype(np.float32)

        # ground truth: non-cached run with an explicit causal mask
        total = S + G
        causal = np.where(np.tril(np.ones((total, total))) > 0, 0.0,
                          -1e30).astype(np.float32)[None, None]
        full = IF.fused_multi_transformer(
            t(x_full), **w, pre_layer_norm=True,
            attn_mask=t(causal), training=False)
        full = np.asarray(full.numpy())

        caches = _caches(B, NH, HD, S + G + 2, L)
        out_p, caches = IF.fused_multi_transformer(
            t(x_full[:, :S]), **w, pre_layer_norm=True,
            cache_kvs=caches, training=False)
        np.testing.assert_allclose(np.asarray(out_p.numpy()),
                                   full[:, :S], rtol=2e-4, atol=2e-5)
        for g in range(G):
            out_d, caches = IF.fused_multi_transformer(
                t(x_full[:, S + g:S + g + 1]), **w, pre_layer_norm=True,
                cache_kvs=caches, time_step=t(np.array([S + g], np.int32)),
                training=False)
            np.testing.assert_allclose(
                np.asarray(out_d.numpy())[:, 0], full[:, S + g],
                rtol=2e-4, atol=2e-5, err_msg=f"decode step {g}")

    def test_pre_caches_equal_split_prefill(self):
        """Splitting a prompt at P and feeding the first part's k/v as
        pre_caches must reproduce the full prefill's suffix outputs."""
        rng = np.random.RandomState(1)
        B, P, S2, HID, NH, FFN, L = 1, 3, 4, 8, 2, 16, 2
        HD = HID // NH
        w = _mk_stack(rng, L, HID, NH, FFN)
        x = (rng.randn(B, P + S2, HID) * 0.1).astype(np.float32)

        caches = _caches(B, NH, HD, P + S2, L)
        out_full, caches = IF.fused_multi_transformer(
            t(x), **w, pre_layer_norm=True, cache_kvs=caches,
            training=False)
        pre = [t(np.asarray(c.numpy())[:, :, :, :P].copy())
               for c in caches]

        caches2 = _caches(B, NH, HD, P + S2, L)
        out_sfx, caches2 = IF.fused_multi_transformer(
            t(x[:, P:]), **w, pre_layer_norm=True, cache_kvs=caches2,
            pre_caches=pre, training=False)
        np.testing.assert_allclose(
            np.asarray(out_sfx.numpy()),
            np.asarray(out_full.numpy())[:, P:], rtol=2e-4, atol=2e-5)
        # the prefix landed in the cache too
        np.testing.assert_allclose(
            np.asarray(caches2[0].numpy())[:, :, :, :P],
            np.asarray(pre[0].numpy()), rtol=1e-6)

    def test_rotary_decode_consistent_with_prefill(self):
        """With rotary embeddings, decode steps must agree with a
        one-shot prefill over the full sequence (two different code
        paths through the rotary + cache logic)."""
        rng = np.random.RandomState(2)
        B, S, G, HID, NH, FFN, L = 1, 4, 2, 8, 2, 16, 1
        HD = HID // NH
        w = _mk_stack(rng, L, HID, NH, FFN)
        total = S + G
        x = (rng.randn(B, total, HID) * 0.1).astype(np.float32)
        inv = 1.0 / (10000 ** (np.arange(0, HD, 2) / HD))
        pos = np.arange(total)[:, None] * inv[None]
        cos = np.repeat(np.cos(pos), 2, axis=-1)[None, None]
        sin = np.repeat(np.sin(pos), 2, axis=-1)[None, None]
        rot = np.stack([cos, sin]).astype(np.float32)  # [2,1,1,T,HD]

        caches = _caches(B, NH, HD, total, L)
        out_full, caches = IF.fused_multi_transformer(
            t(x), **w, pre_layer_norm=True, cache_kvs=caches,
            rotary_embs=t(rot), rotary_emb_dims=1, training=False)

        caches2 = _caches(B, NH, HD, total, L)
        out_p, caches2 = IF.fused_multi_transformer(
            t(x[:, :S]), **w, pre_layer_norm=True, cache_kvs=caches2,
            rotary_embs=t(rot[:, :, :, :S]), rotary_emb_dims=1,
            training=False)
        np.testing.assert_allclose(np.asarray(out_p.numpy()),
                                   np.asarray(out_full.numpy())[:, :S],
                                   rtol=2e-4, atol=2e-5)
        for g in range(G):
            out_d, caches2 = IF.fused_multi_transformer(
                t(x[:, S + g:S + g + 1]), **w, pre_layer_norm=True,
                cache_kvs=caches2,
                rotary_embs=t(rot[:, :, :, S + g:S + g + 1]),
                rotary_emb_dims=1,
                time_step=t(np.array([S + g], np.int32)), training=False)
            np.testing.assert_allclose(
                np.asarray(out_d.numpy())[:, 0],
                np.asarray(out_full.numpy())[:, S + g],
                rtol=2e-4, atol=2e-5, err_msg=f"rotary decode step {g}")

    def test_seq_lens_masks_padded_prompt(self):
        """A shorter prompt padded to S with garbage must produce the
        same prefill outputs (at valid positions) as the unpadded one."""
        rng = np.random.RandomState(3)
        B, S, HID, NH, FFN, L = 1, 6, 8, 2, 16, 1
        HD = HID // NH
        w = _mk_stack(rng, L, HID, NH, FFN)
        real = 4
        x = (rng.randn(B, S, HID) * 0.1).astype(np.float32)
        x_pad = x.copy()
        x_pad[:, real:] = 99.0   # garbage padding

        c1 = _caches(B, NH, HD, S, L)
        out1, _ = IF.fused_multi_transformer(
            t(x[:, :real]), **w, pre_layer_norm=True, cache_kvs=c1,
            training=False)
        c2 = _caches(B, NH, HD, S, L)
        out2, c2 = IF.fused_multi_transformer(
            t(x_pad), **w, pre_layer_norm=True, cache_kvs=c2,
            seq_lens=t(np.array([real], np.int32)), training=False)
        np.testing.assert_allclose(
            np.asarray(out2.numpy())[:, :real],
            np.asarray(out1.numpy()), rtol=2e-4, atol=2e-5)

        # ragged decode: the padded cache (garbage at [real, S)) must
        # produce the same next-token output as the unpadded cache —
        # the seq_lens mask keeps garbage slots out of the softmax
        nxt = (rng.randn(B, 1, HID) * 0.1).astype(np.float32)
        d1, _ = IF.fused_multi_transformer(
            t(nxt), **w, pre_layer_norm=True, cache_kvs=c1,
            seq_lens=t(np.array([real], np.int32)),
            time_step=t(np.array([real], np.int32)), training=False)
        d2, _ = IF.fused_multi_transformer(
            t(nxt), **w, pre_layer_norm=True, cache_kvs=c2,
            seq_lens=t(np.array([real], np.int32)),
            time_step=t(np.array([real], np.int32)), training=False)
        np.testing.assert_allclose(np.asarray(d2.numpy()),
                                   np.asarray(d1.numpy()),
                                   rtol=2e-4, atol=2e-5)

    def test_numpy_cache_kvs_updated(self):
        """Caches passed as raw numpy arrays must still come back
        updated (the returned list carries the new values)."""
        rng = np.random.RandomState(9)
        B, S, HID, NH, FFN, L = 1, 3, 8, 2, 16, 1
        HD = HID // NH
        w = _mk_stack(rng, L, HID, NH, FFN)
        x = (rng.randn(B, S, HID) * 0.1).astype(np.float32)
        np_caches = [np.zeros((2, B, NH, S, HD), np.float32)]
        _, out_caches = IF.fused_multi_transformer(
            t(x), **w, pre_layer_norm=True, cache_kvs=np_caches,
            training=False)
        assert np.abs(np.asarray(out_caches[0].numpy())).sum() > 0


class TestFusedMHACache:
    def test_cache_growth_matches_full_run_last_token(self):
        """Reference cache_kv semantics: plain (non-causal) attention
        over [cache; new]. A multi-token append over an empty cache must
        therefore equal the non-cached run at EVERY position, and the
        subsequent single-token decode must equal the full run's last
        row."""
        rng = np.random.RandomState(4)
        B, S, HID, NH = 2, 5, 16, 2
        HD = HID // NH
        qkv_w = t((rng.randn(3, NH, HD, HID) * 0.05).astype(np.float32))
        qkv_b = t((rng.randn(3, NH, HD) * 0.05).astype(np.float32))
        lin_w = t((rng.randn(HID, HID) * 0.05).astype(np.float32))
        lin_b = t((rng.randn(HID) * 0.05).astype(np.float32))
        ln_s = t(np.ones(HID, np.float32))
        ln_b = t(np.zeros(HID, np.float32))
        x = (rng.randn(B, S, HID) * 0.1).astype(np.float32)

        full = IF.fused_multi_head_attention(
            t(x), qkv_w, lin_w, pre_layer_norm=True, pre_ln_scale=ln_s,
            pre_ln_bias=ln_b, qkv_bias=qkv_b, linear_bias=lin_b,
            dropout_rate=0.0, attn_dropout_rate=0.0, training=False)
        full = np.asarray(full.numpy())

        empty = t(np.zeros((2, B, NH, 0, HD), np.float32))
        out_pre, cache = IF.fused_multi_head_attention(
            t(x[:, :S - 1]), qkv_w, lin_w, pre_layer_norm=True,
            pre_ln_scale=ln_s, pre_ln_bias=ln_b, qkv_bias=qkv_b,
            linear_bias=lin_b, cache_kv=empty, dropout_rate=0.0,
            attn_dropout_rate=0.0, training=False)
        assert list(cache.shape) == [2, B, NH, S - 1, HD]
        # multi-token append == the non-cached run over the same prefix
        full_pre = IF.fused_multi_head_attention(
            t(x[:, :S - 1]), qkv_w, lin_w, pre_layer_norm=True,
            pre_ln_scale=ln_s, pre_ln_bias=ln_b, qkv_bias=qkv_b,
            linear_bias=lin_b, dropout_rate=0.0,
            attn_dropout_rate=0.0, training=False)
        np.testing.assert_allclose(np.asarray(out_pre.numpy()),
                                   np.asarray(full_pre.numpy()),
                                   rtol=2e-4, atol=2e-5)
        out, cache = IF.fused_multi_head_attention(
            t(x[:, S - 1:]), qkv_w, lin_w, pre_layer_norm=True,
            pre_ln_scale=ln_s, pre_ln_bias=ln_b, qkv_bias=qkv_b,
            linear_bias=lin_b, cache_kv=cache, dropout_rate=0.0,
            attn_dropout_rate=0.0, training=False)
        assert list(cache.shape) == [2, B, NH, S, HD]
        np.testing.assert_allclose(np.asarray(out.numpy())[:, 0],
                                   full[:, -1], rtol=2e-4, atol=2e-5)


class TestFusedMultiTransformerLayer:
    def test_layer_decode_roundtrip(self):
        """The FusedMultiTransformer layer drives the cached path:
        prefill + one decode step agree with one full prefill."""
        from paddle_tpu.incubate.nn import FusedMultiTransformer
        rng = np.random.RandomState(7)
        B, S, HID, NH, FFN, L = 1, 4, 8, 2, 16, 2
        HD = HID // NH
        lyr = FusedMultiTransformer(HID, NH, FFN, num_layers=L)
        lyr.eval()
        x = (rng.randn(B, S + 1, HID) * 0.1).astype(np.float32)

        c1 = _caches(B, NH, HD, S + 1, L)
        out_full, _ = lyr(t(x), caches=c1)
        c2 = _caches(B, NH, HD, S + 1, L)
        _, c2 = lyr(t(x[:, :S]), caches=c2)
        out_d, _ = lyr(t(x[:, S:]), caches=c2,
                       time_step=t(np.array([S], np.int32)))
        np.testing.assert_allclose(
            np.asarray(out_d.numpy())[:, 0],
            np.asarray(out_full.numpy())[:, S], rtol=2e-4, atol=2e-5)


class TestVarlenPreCache:
    def test_prefix_always_attendable(self):
        """pre_cache_length=P: prefix keys bypass kv_seq_lens and the
        causal rule; equivalent to a manual softmax over [prefix; live
        suffix]."""
        import jax.numpy as jnp
        import jax
        rng = np.random.RandomState(5)
        B, H, SQ, P, SK_body, D = 1, 1, 3, 2, 4, 8
        SK = P + SK_body
        q = (rng.randn(B, H, SQ, D) * 0.3).astype(np.float32)
        k = (rng.randn(B, H, SK, D) * 0.3).astype(np.float32)
        v = (rng.randn(B, H, SK, D) * 0.3).astype(np.float32)
        kl = 3   # only 3 of the 4 body keys live

        out = IF.variable_length_memory_efficient_attention(
            t(q), t(k), t(v), t(np.array([SQ], np.int32)),
            t(np.array([kl], np.int32)), causal=True, pre_cache_length=P)
        out = np.asarray(out.numpy())

        # manual: query i sees prefix (all P) + body j<=i (j<kl)
        sc = (q[0, 0] @ k[0, 0].T) / np.sqrt(D)
        for i in range(SQ):
            for j in range(SK):
                body_j = j - P
                if j >= P and (body_j > i + (SK - P - SQ)
                               or body_j >= kl):
                    sc[i, j] = -1e30
        p = np.exp(sc - sc.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        want = p @ v[0, 0]
        np.testing.assert_allclose(out[0, 0], want, rtol=2e-4, atol=2e-5)


class TestGenerateRunCache:
    """The compiled generate runner must be reused across calls (a fresh
    jit per call costs a full retrace per serving request) but must NOT
    serve stale constants after the config object is mutated."""

    def _cfg(self):
        from paddle_tpu.models.llama import LlamaConfig
        return LlamaConfig(vocab_size=97, hidden_size=32,
                           intermediate_size=64, num_hidden_layers=2,
                           num_attention_heads=4, num_key_value_heads=2,
                           max_position_embeddings=64)

    def test_runner_reused_for_same_shape(self):
        import jax.numpy as jnp
        from paddle_tpu.inference import generation as G
        from paddle_tpu.models.llama import init_params
        import jax
        cfg = self._cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jnp.zeros((1, 8), jnp.int32)
        g = G.GenerationConfig(max_new_tokens=4, greedy=True)
        G._RUN_CACHE.clear()
        out1 = G.generate(params, toks, cfg, g)
        n_after_first = len(G._RUN_CACHE)
        out2 = G.generate(params, toks, cfg, g)
        assert n_after_first == 1
        assert len(G._RUN_CACHE) == 1          # no second entry
        np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))

    def test_mutated_config_misses_cache(self):
        import jax.numpy as jnp
        from paddle_tpu.inference import generation as G
        from paddle_tpu.models.llama import init_params
        import jax
        cfg = self._cfg()
        params = init_params(cfg, jax.random.PRNGKey(0))
        toks = jnp.zeros((1, 8), jnp.int32)
        g = G.GenerationConfig(max_new_tokens=4, greedy=True)
        G._RUN_CACHE.clear()
        G.generate(params, toks, cfg, g)
        cfg.rope_theta = cfg.rope_theta * 2   # mutate in place
        G.generate(params, toks, cfg, g)
        # value-keyed cache: the mutated config must get its own runner
        assert len(G._RUN_CACHE) == 2


class TestTrainerBatchStaging:
    def test_already_placed_array_passes_through(self):
        """An input whose sharding already matches must NOT be re-put
        (each re-put is a blocking h2d roundtrip per step)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                    make_mesh)
        import jax.numpy as jnp
        mesh = make_mesh(MeshConfig())
        tr = Trainer(lambda p, t: jnp.sum(p["w"]) * 0.0, mesh,
                     {"w": PartitionSpec()}, lr=1e-3)
        x = jnp.zeros((4, 8), jnp.int32)
        staged = jax.device_put(x, NamedSharding(mesh, tr.data_spec))
        assert tr._stage_batch(staged) is staged
        # host numpy still gets placed
        out = tr._stage_batch(np.zeros((4, 8), np.int32))
        assert isinstance(out, jax.Array)


class TestFusedOptimizerPath:
    def test_fused_matches_per_leaf_update(self):
        """Trainer(fused_optimizer=True) — the flat Pallas AdamW path
        (interpret mode off-TPU) must track the per-leaf XLA update."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec
        from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                    make_mesh)

        def loss_fn(p, x):
            h = jnp.tanh(x @ p["w1"])
            return jnp.mean(jnp.square(h @ p["w2"]))

        rng = np.random.RandomState(0)
        params = {"w1": jnp.asarray(rng.randn(8, 16), jnp.float32),
                  "w2": jnp.asarray(rng.randn(16, 4), jnp.float32)}
        specs = {"w1": PartitionSpec(), "w2": PartitionSpec()}
        x = jnp.asarray(rng.randn(32, 8), jnp.float32)
        mesh = make_mesh(MeshConfig())

        outs = {}
        for fused in (False, True):
            tr = Trainer(loss_fn, mesh, specs, lr=1e-2, grad_clip=1.0,
                         fused_optimizer=fused, donate=False)
            st = tr.init_state(dict(params))
            for _ in range(3):
                st, m = tr.step(st, x)
            outs[fused] = (np.asarray(m["loss"]),
                           np.asarray(m["grad_norm"]),
                           {k: np.asarray(v) for k, v in st.params.items()})
        np.testing.assert_allclose(outs[True][0], outs[False][0],
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(outs[True][1], outs[False][1],
                                   rtol=1e-5, atol=1e-6)
        for k in params:
            np.testing.assert_allclose(outs[True][2][k], outs[False][2][k],
                                       rtol=1e-4, atol=1e-5)

    def test_forced_fused_rejects_multidevice_and_mixed_dtype(self):
        """fused_optimizer=True must fail loudly where auto would
        decline: flat unsharded state on a multi-device mesh silently
        loses FSDP sharding, and mixed dtypes get silently cast."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh, PartitionSpec
        from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                    make_mesh)

        def loss_fn(p, x):
            return jnp.mean(jnp.square(x @ p["w"]))

        devs = np.array(jax.devices()[:2]).reshape(2, 1, 1)
        mesh2 = Mesh(devs, ("dp", "fsdp", "sp"))
        tr = Trainer(loss_fn, mesh2, {"w": PartitionSpec()},
                     fused_optimizer=True)
        with pytest.raises(ValueError, match="UNSHARDED"):
            tr.init_state({"w": jnp.ones((4, 4), jnp.float32)})

        mesh1 = make_mesh(MeshConfig())
        # TWO non-fp32 dtypes: no single shadow can cover both
        tr = Trainer(lambda p, x: jnp.mean(x @ p["w"]), mesh1,
                     {"w": PartitionSpec(), "b": PartitionSpec()},
                     fused_optimizer=True)
        with pytest.raises(ValueError, match="floating"):
            tr.init_state({"w": jnp.ones((4, 4), jnp.float16),
                           "b": jnp.ones((4,), jnp.bfloat16)})

    def test_fused_mixed_dtype_tree_matches_per_leaf(self):
        """The llama layout (bf16 weights + fp32 norms) must run the
        fused path: fp32 leaves slice back exact from the master, bf16
        leaves from the shadow; three steps track the per-leaf update."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec
        from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                    make_mesh)

        def loss_fn(p, x):
            h = jnp.tanh(x @ p["w"].astype(jnp.float32))
            return jnp.mean(jnp.square(h * p["scale"]))

        rng = np.random.RandomState(0)
        params = {"w": jnp.asarray(rng.randn(8, 16), jnp.bfloat16),
                  "scale": jnp.ones((16,), jnp.float32)}
        specs = {"w": PartitionSpec(), "scale": PartitionSpec()}
        x = jnp.asarray(rng.randn(32, 8), jnp.float32)
        mesh = make_mesh(MeshConfig())

        outs = {}
        for fused in (False, True):
            tr = Trainer(loss_fn, mesh, specs, lr=1e-2, grad_clip=1.0,
                         fused_optimizer=fused, donate=False)
            st = tr.init_state(dict(params))
            assert tr._fused == fused
            for _ in range(3):
                st, m = tr.step(st, x)
            outs[fused] = (np.asarray(m["loss"]),
                           {k: np.asarray(v, np.float32)
                            for k, v in st.params.items()})
        np.testing.assert_allclose(outs[True][0], outs[False][0],
                                   rtol=1e-3, atol=1e-4)
        for k in params:
            np.testing.assert_allclose(outs[True][1][k], outs[False][1][k],
                                       rtol=2e-2, atol=2e-3)
        # dtypes preserved through the fused update
        tr = Trainer(loss_fn, mesh, specs, fused_optimizer=True,
                     donate=False)
        st = tr.init_state(dict(params))
        st, _ = tr.step(st, x)
        assert st.params["w"].dtype == jnp.bfloat16
        assert st.params["scale"].dtype == jnp.float32

    def test_fused_bf16_moment_dtype(self):
        """moment_dtype=bfloat16 halves mu/nu storage; the update still
        descends and state dtypes stay step-invariant (donation)."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec
        from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                    make_mesh)

        def loss_fn(p, x):
            return jnp.mean(jnp.square(x @ p["w"]))

        rng = np.random.RandomState(2)
        mesh = make_mesh(MeshConfig())
        for fused in (False, True):
            tr = Trainer(loss_fn, mesh, {"w": PartitionSpec()}, lr=1e-2,
                         fused_optimizer=fused, donate=False,
                         moment_dtype=jnp.bfloat16)
            st = tr.init_state(
                {"w": jnp.asarray(rng.randn(16, 4), jnp.float32)})
            assert jax.tree_util.tree_leaves(st.mu)[0].dtype == jnp.bfloat16
            losses = []
            for _ in range(5):
                st, m = tr.step(
                    st, jnp.asarray(rng.randn(32, 16), jnp.float32))
                losses.append(float(m["loss"]))
                assert jax.tree_util.tree_leaves(st.mu)[0].dtype \
                    == jnp.bfloat16
            assert losses[-1] < losses[0]

    def test_fused_with_nan_check(self):
        """FLAGS_check_nan_inf rebuilds the step without donation; the
        fused path must survive the rebuild and report finite metrics."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec
        from paddle_tpu.core.flags import GLOBAL_FLAGS
        from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                    make_mesh)

        def loss_fn(p, x):
            return jnp.mean(jnp.square(x @ p["w"]))

        rng = np.random.RandomState(1)
        params = {"w": jnp.asarray(rng.randn(8, 4), jnp.float32)}
        mesh = make_mesh(MeshConfig())
        tr = Trainer(loss_fn, mesh, {"w": PartitionSpec()}, lr=1e-2,
                     fused_optimizer=True)
        st = tr.init_state(params)
        x = jnp.asarray(rng.randn(16, 8), jnp.float32)
        GLOBAL_FLAGS.set("check_nan_inf", True)
        try:
            st, m = tr.step(st, x)
            assert np.isfinite(float(m["loss"]))
            # a poisoned batch must raise, not silently update
            bad = x.at[0, 0].set(jnp.nan)
            try:
                tr.step(st, bad)
                raised = False
            except FloatingPointError:
                raised = True
            assert raised
        finally:
            GLOBAL_FLAGS.set("check_nan_inf", False)

    @staticmethod
    def _mixed_tree_trainer(fused, **kw):
        """The llama layout at toy size (bf16 weights + fp32 norms) and
        the training cell's optimizer state (bf16 moments)."""
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec
        from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                    make_mesh)

        def loss_fn(p, x):
            h = jnp.tanh(x @ p["w"].astype(jnp.float32))
            return jnp.mean(jnp.square(h * p["scale"]))

        rng = np.random.RandomState(0)
        params = {"w": jnp.asarray(rng.randn(8, 16), jnp.bfloat16),
                  "scale": jnp.ones((16,), jnp.float32)}
        specs = {"w": PartitionSpec(), "scale": PartitionSpec()}
        x = jnp.asarray(rng.randn(32, 8), jnp.float32)
        tr = Trainer(loss_fn, make_mesh(MeshConfig()), specs, lr=1e-2,
                     grad_clip=1.0, fused_optimizer=fused, donate=False,
                     moment_dtype=jnp.bfloat16, **kw)
        return tr, params, x

    def test_2d_launch_in_the_step_over_flat_state(self):
        """The step's optimizer pinned to the Pallas variant (interpreted
        here): the state stays the flat vectors it was (what a checkpoint
        and the benchmark's per-leaf slices read), the launch views them
        ``(rows, 128)``, ``metrics()["optimizer_variant"]`` names what the
        step traced, and two steps track the per-leaf trainer."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas import fused_adamw as fa
        from paddle_tpu.ops.pallas.registry import KERNELS

        outs = {}
        for fused in (False, True):
            tr, params, x = self._mixed_tree_trainer(fused)
            st = tr.init_state(dict(params))
            assert tr.metrics()["optimizer_variant"] == {
                "variant": None, "block": None}
            if fused:
                for flat in (st.master, st.mu, st.nu):
                    assert flat.shape == (fa.BLOCK,)
                assert st.master.dtype == jnp.float32
                assert st.mu.dtype == st.nu.dtype == jnp.bfloat16
            with KERNELS.force("fused_adamw", "pallas_fused"):
                for _ in range(2):
                    st, m = tr.step(st, x)
            outs[fused] = (np.asarray(m["loss"]),
                           {k: np.asarray(v, np.float32)
                            for k, v in st.params.items()})
            assert tr.metrics()["optimizer_variant"] == (
                {"variant": "pallas_fused",
                 "block": [fa.BLOCK // fa.LANES, fa.LANES]} if fused
                else {"variant": "per_leaf", "block": None})
        # a later trace under another pin is another record
        tr.step(st, x)
        assert tr.optimizer_variant == {"variant": "unfused", "block": None}
        np.testing.assert_allclose(outs[True][0], outs[False][0],
                                   rtol=1e-3, atol=1e-4)
        for k in outs[True][1]:
            np.testing.assert_allclose(outs[True][1][k], outs[False][1][k],
                                       rtol=2e-2, atol=2e-3)

    def test_checkpoint_round_trip_of_the_flat_state(self, tmp_path):
        """A fused trainer's state through ``dist.save_state_dict`` /
        ``load_state_dict``: on disk the flat state has the shape it
        always had (a multiple of BLOCK, unchanged by the 2-D launch),
        and a step from the restored state is the step from the saved
        one, bit for bit."""
        import jax
        import jax.numpy as jnp
        import paddle_tpu.distributed as dist
        from paddle_tpu.distributed.trainer import TrainState
        from paddle_tpu.ops.pallas import fused_adamw as fa
        from paddle_tpu.ops.pallas.registry import KERNELS

        tr, params, x = self._mixed_tree_trainer(True)
        st = tr.init_state(dict(params))
        with KERNELS.force("fused_adamw", "pallas_fused"):
            st, _ = tr.step(st, x)
            names = ("params", "master", "mu", "nu", "step")
            # npz holds no bfloat16: those leaves travel as their bits
            bits = lambda v: (jax.lax.bitcast_convert_type(v, jnp.uint16)  # noqa: E731
                              if v.dtype == jnp.bfloat16 else v)
            saved = jax.tree_util.tree_map(bits, dict(zip(names, st.tree())))
            dist.save_state_dict(saved, str(tmp_path))
            # a target that is no Tensor is handed back under its flat key
            flat = dict.fromkeys([f"params.{k}" for k in params]
                                 + list(names[1:]))
            dist.load_state_dict(flat, str(tmp_path))
            for k in ("master", "mu", "nu"):
                assert flat[k].shape == (fa.BLOCK,)
            unbits = lambda v, like: (  # noqa: E731
                jax.lax.bitcast_convert_type(jnp.asarray(v), jnp.bfloat16)
                if like.dtype == jnp.bfloat16 else jnp.asarray(v, like.dtype))
            restored = TrainState(
                {k: unbits(flat[f"params.{k}"], st.params[k])
                 for k in params},
                *(unbits(flat[k], getattr(st, k)) for k in names[1:]))
            a, ma = tr.step(st, x)
            b, mb = tr.step(restored, x)
        assert float(ma["loss"]) == float(mb["loss"])
        for u, v in zip(jax.tree_util.tree_leaves(a.tree()),
                        jax.tree_util.tree_leaves(b.tree())):
            np.testing.assert_array_equal(np.asarray(u, np.float32),
                                          np.asarray(v, np.float32))
