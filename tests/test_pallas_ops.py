"""Pallas kernel numerics vs XLA references (interpret mode on CPU; the
same kernels compile to Mosaic on TPU). Gate per SURVEY.md §7 step 5."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.flash_attention import _ref_attention
from paddle_tpu.ops.pallas.flash_attention import flash_attention_pallas
from paddle_tpu.ops.pallas.norms import rms_norm_pallas, layer_norm_pallas
from paddle_tpu.ops import rms_norm_ref, layer_norm_ref
from paddle_tpu.ops.rope import apply_rope, build_rope_cache


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_fwd_matches_ref(self, causal):
        rng = np.random.RandomState(0)
        b, s, h, d = 2, 128, 2, 64
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        o = flash_attention_pallas(q, k, v, causal=causal)
        ref = _ref_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.slow
    def test_bwd_matches_ref(self):
        rng = np.random.RandomState(1)
        b, s, h, d = 1, 128, 2, 64
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)

        def f(q, k, v):
            return jnp.sum(flash_attention_pallas(q, k, v, causal=True) ** 2)

        def g(q, k, v):
            return jnp.sum(_ref_attention(q, k, v, causal=True) ** 2)

        gp = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(g, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gp, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                       atol=5e-5, rtol=5e-4)

    def test_uneven_seq_multiblock(self):
        rng = np.random.RandomState(2)
        b, s, h, d = 1, 1024, 1, 64  # 2 blocks of 512
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        o = flash_attention_pallas(q, q, q, causal=True)
        ref = _ref_attention(q, q, q, causal=True)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   atol=3e-5, rtol=3e-5)


class TestNorms:
    @pytest.mark.slow
    def test_rms_norm_fwd_bwd(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(4, 64, 128), jnp.float32)
        w = jnp.asarray(rng.rand(128) + 0.5, jnp.float32)
        out = rms_norm_pallas(x, w)
        ref = rms_norm_ref(x, w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
        g1 = jax.grad(lambda x, w: jnp.sum(rms_norm_pallas(x, w) ** 2),
                      argnums=(0, 1))(x, w)
        g2 = jax.grad(lambda x, w: jnp.sum(rms_norm_ref(x, w) ** 2),
                      argnums=(0, 1))(x, w)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_layer_norm(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(8, 128), jnp.float32)
        w = jnp.asarray(rng.rand(128) + 0.5, jnp.float32)
        b = jnp.asarray(rng.randn(128), jnp.float32)
        out = layer_norm_pallas(x, w, b)
        ref = layer_norm_ref(x, w, b)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


class TestRope:
    def test_rotation_preserves_norm(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 16, 4, 64), jnp.float32)
        out = apply_rope(x)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(out), axis=-1),
            np.linalg.norm(np.asarray(x), axis=-1), rtol=1e-5)

    def test_relative_property(self):
        """<rope(q,m), rope(k,n)> depends only on m-n."""
        rng = np.random.RandomState(0)
        d = 32
        q = jnp.asarray(rng.randn(1, 1, 1, d), jnp.float32)
        k = jnp.asarray(rng.randn(1, 1, 1, d), jnp.float32)
        sin, cos = build_rope_cache(64, d)

        def at(x, pos):
            return apply_rope(x, sin, cos,
                              position_ids=jnp.asarray([[pos]]))[0, 0, 0]

        d1 = float(jnp.dot(at(q, 5), at(k, 3)))
        d2 = float(jnp.dot(at(q, 12), at(k, 10)))
        assert abs(d1 - d2) < 1e-3

    def test_position_ids_gather(self):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(1, 4, 2, 32), jnp.float32)
        full = apply_rope(x)
        pid = apply_rope(x, position_ids=jnp.asarray([[0, 1, 2, 3]]))
        np.testing.assert_allclose(np.asarray(full), np.asarray(pid),
                                   atol=1e-6)


class TestFusedAdamW:
    def test_matches_formula(self):
        from paddle_tpu.ops.pallas.fused_adamw import fused_adamw
        rng = np.random.RandomState(0)
        n = 256
        p = jnp.asarray(rng.randn(n), jnp.float32)
        g = jnp.asarray(rng.randn(n), jnp.float32)
        m = jnp.zeros(n, jnp.float32)
        v = jnp.zeros(n, jnp.float32)
        p2, m2, v2 = fused_adamw(p, g, m, v, lr=0.1, step=1.0,
                                 weight_decay=0.01)
        m_ref = 0.1 * np.asarray(g)
        v_ref = 0.001 * np.asarray(g) ** 2
        mhat = m_ref / (1 - 0.9)
        vhat = v_ref / (1 - 0.999)
        p_ref = np.asarray(p) * (1 - 0.1 * 0.01) - \
            0.1 * mhat / (np.sqrt(vhat) + 1e-8)
        np.testing.assert_allclose(np.asarray(p2), p_ref, rtol=1e-5,
                                   atol=1e-6)

    # the flat count of each layout, in units the module names: whole
    # blocks, a ragged last block, less than one block, and a count the
    # 1-D entry has to pad (prime: no tile, no lane divides it)
    LAYOUTS = {
        "whole_blocks": lambda fa: 2 * fa.ROWS * fa.LANES,
        "ragged_last_block": lambda fa: (fa.ROWS + 256) * fa.LANES,
        "below_one_block": lambda fa: 48 * fa.LANES,
        "flat_awkward_n": lambda fa: 1009,
    }

    @pytest.mark.parametrize("grad_scale", [None, 0.37])
    @pytest.mark.parametrize("layout", sorted(LAYOUTS))
    @pytest.mark.parametrize("shadow", [None, "bfloat16"])
    @pytest.mark.parametrize("moments", ["float32", "bfloat16"])
    def test_2d_launch_matches_reference(self, moments, shadow, layout,
                                         grad_scale):
        """The (rows, LANES) launch, interpreted, against
        ``adamw_update_ref`` on the flat vectors: same dtypes and shapes
        out, and every number within one unit of the last place of the
        larger operand of the sum that made it. Not bit for bit here:
        XLA's CPU backend contracts a multiply and an add into one
        rounding where they share a fusion, and the interpreted launch
        and the composition fuse differently (measured: 1-3% of the
        master's numbers differ, by that one unit). On the chip the
        launch equals the 1-D kernel it replaces bit for bit (PERF.md
        section 6, PR 33)."""
        from paddle_tpu.ops.pallas import fused_adamw as fa
        n = self.LAYOUTS[layout](fa)
        rng = np.random.RandomState(n % 1000)
        mdt = jnp.dtype(moments)
        p = jnp.asarray(rng.randn(n) * 0.02, jnp.float32)
        # gradients over six decades, as a real tree's leaves differ
        g = jnp.asarray(rng.randn(n) * np.exp2(rng.randint(-20, 0, n)),
                        jnp.float32)
        m = jnp.asarray(rng.randn(n) * 1e-4, mdt)
        v = jnp.asarray(rng.randn(n) ** 2 * 1e-7, mdt)
        kw = dict(beta1=0.9, beta2=0.95, weight_decay=0.1,
                  grad_scale=grad_scale, shadow_dtype=shadow)
        want = fa.adamw_update_ref(p, g, m, v, 3e-4, 2.0, **kw)
        if layout == "flat_awkward_n":
            got = fa.fused_adamw(p, g, m, v, 3e-4, 2.0, **kw)
        else:
            got = [o.reshape(-1) for o in fa.fused_adamw_2d(
                *(x.reshape(-1, fa.LANES) for x in (p, g, m, v)),
                3e-4, 2.0, **kw)]
        assert len(got) == len(want) == (4 if shadow else 3)
        f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
        # the larger operand of the sum behind each output (the second
        # moment's terms are both positive: the output itself)
        p_big = np.maximum(np.abs(f32(p)), np.abs(f32(want[0]) - f32(p)))
        m_big = np.maximum(0.9 * np.abs(f32(m)),
                           0.1 * np.abs(f32(g)) * (grad_scale or 1.0))
        bigs = [p_big, m_big, f32(want[2]), p_big]
        for a, b, big, units in zip(got, want, bigs, (16, 2, 2, 16)):
            assert a.dtype == b.dtype and a.shape == b.shape == (n,)
            allowed = units * 2.0 ** -23 * big
            if b.dtype == jnp.bfloat16:   # and one flip of its rounding
                allowed = allowed + 2.0 ** -7 * np.abs(f32(b))
            worst = np.max(np.abs(f32(a) - f32(b)) - allowed)
            assert worst <= 0, (str(b.dtype), float(worst))

    def test_2d_entry_refuses_what_is_not_whole_tiles(self):
        from paddle_tpu.ops.pallas import fused_adamw as fa
        for shape in ((16, 256), (24, fa.LANES)):
            z = jnp.zeros(shape, jnp.float32)
            with pytest.raises(ValueError, match="multiple of 16"):
                fa.fused_adamw_2d(z, z, z, z, 1e-3, 1.0)


class TestNormRowPadding:
    def test_rms_prime_rows(self):
        from paddle_tpu.ops.pallas.norms import rms_norm_pallas
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(1, 127, 256), jnp.float32)  # prime rows
        w = jnp.asarray(rng.randn(256), jnp.float32)
        o = rms_norm_pallas(x, w)
        xf = np.asarray(x)
        ref = xf / np.sqrt((xf ** 2).mean(-1, keepdims=True) + 1e-6) \
            * np.asarray(w)
        assert o.shape == x.shape
        np.testing.assert_allclose(np.asarray(o), ref, atol=2e-5)

    def test_layernorm_prime_rows(self):
        from paddle_tpu.ops.pallas.norms import layer_norm_pallas
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(127, 256), jnp.float32)
        w = jnp.asarray(rng.randn(256), jnp.float32)
        b = jnp.asarray(rng.randn(256), jnp.float32)
        o = layer_norm_pallas(x, w, b)
        xf = np.asarray(x)
        mu = xf.mean(-1, keepdims=True)
        var = xf.var(-1, keepdims=True)
        ref = (xf - mu) / np.sqrt(var + 1e-5) * np.asarray(w) \
            + np.asarray(b)
        assert o.shape == x.shape
        np.testing.assert_allclose(np.asarray(o), ref, atol=2e-5)


class TestFlashAttentionExtended:
    """GQA / segment-id (varlen) / bias capabilities of the Pallas kernel
    (reference varlen path: paddle/phi/kernels/gpu/flash_attn_kernel.cu:137)."""

    def _qkv(self, b=2, s=256, h=4, kvh=2, d=64, seed=0):
        rng = np.random.RandomState(seed)
        q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
        k = jnp.asarray(rng.randn(b, s, kvh, d), jnp.float32)
        v = jnp.asarray(rng.randn(b, s, kvh, d), jnp.float32)
        return q, k, v

    @pytest.mark.slow
    @pytest.mark.parametrize("causal", [False, True])
    def test_gqa_matches_ref(self, causal):
        q, k, v = self._qkv(kvh=1)
        o = flash_attention_pallas(q, k, v, causal=causal)
        ref = _ref_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.slow
    def test_bias_fwd_bwd(self):
        q, k, v = self._qkv(h=2, kvh=2, s=128)
        rng = np.random.RandomState(3)
        bias = jnp.asarray(rng.randn(1, 2, 128, 128) * 0.5, jnp.float32)

        def lp(q, k, v, b):
            return jnp.sum(flash_attention_pallas(q, k, v, causal=True,
                                                  bias=b,
                                                  bias_grad=True) ** 2)

        def lr(q, k, v, b):
            return jnp.sum(_ref_attention(q, k, v, causal=True,
                                          bias=b) ** 2)

        gp = jax.grad(lp, argnums=(0, 1, 2, 3))(q, k, v, bias)
        gr = jax.grad(lr, argnums=(0, 1, 2, 3))(q, k, v, bias)
        for a, b_ in zip(gp, gr):
            scale = float(jnp.abs(b_).max()) + 1e-9
            np.testing.assert_allclose(np.asarray(a) / scale,
                                       np.asarray(b_) / scale,
                                       atol=2e-5)

    @pytest.mark.slow
    def test_segment_ids_block_cross_attention(self):
        q, k, v = self._qkv(h=2, kvh=2, s=256, seed=5)
        seg = jnp.asarray(
            np.sort(np.random.RandomState(6).randint(0, 3, (2, 256)),
                    axis=1), jnp.int32)
        o = flash_attention_pallas(q, k, v, causal=True, segment_ids=seg)
        ref = _ref_attention(q, k, v, causal=True, segment_ids=seg)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    @pytest.mark.slow
    def test_flash_attn_unpadded(self):
        import paddle_tpu as paddle
        import paddle_tpu.nn.functional as F
        rng = np.random.RandomState(7)
        lens = [60, 100, 96]
        total, h, d = sum(lens), 2, 64
        cu = np.cumsum([0] + lens).astype(np.int32)
        q = rng.randn(total, h, d).astype(np.float32)
        k = rng.randn(total, h, d).astype(np.float32)
        v = rng.randn(total, h, d).astype(np.float32)
        out, _ = F.flash_attn_unpadded(
            paddle.to_tensor(q), paddle.to_tensor(k), paddle.to_tensor(v),
            paddle.to_tensor(cu), paddle.to_tensor(cu), causal=True)
        out = np.asarray(out._value)
        # per-sequence reference: attention confined to each span
        for i, (a, b_) in enumerate(zip(cu[:-1], cu[1:])):
            ref = _ref_attention(jnp.asarray(q[None, a:b_]),
                                 jnp.asarray(k[None, a:b_]),
                                 jnp.asarray(v[None, a:b_]), causal=True)
            np.testing.assert_allclose(out[a:b_], np.asarray(ref[0]),
                                       atol=2e-5, rtol=2e-5)


    @pytest.mark.slow
    def test_fully_masked_rows_zero(self):
        # a query whose segment id matches no key must output 0 (not the
        # mean of V) and contribute nothing to dk/dv
        q, k, v = self._qkv(b=1, h=2, kvh=2, s=128, seed=9)
        seg_q = jnp.full((1, 128), 7, jnp.int32).at[0, :64].set(0)
        seg_k = jnp.zeros((1, 128), jnp.int32)
        o = flash_attention_pallas(q, k, v, segment_ids=seg_q,
                                   kv_segment_ids=seg_k)
        np.testing.assert_allclose(np.asarray(o[0, 64:]), 0.0, atol=1e-6)
        ref = _ref_attention(q, k, v, segment_ids=seg_q,
                             kv_segment_ids=seg_k)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

        def lp(kk):
            return jnp.sum(flash_attention_pallas(
                q, k=kk, v=v, segment_ids=seg_q,
                kv_segment_ids=seg_k) ** 2)

        def lr(kk):
            return jnp.sum(_ref_attention(
                q, k=kk, v=v, segment_ids=seg_q,
                kv_segment_ids=seg_k) ** 2)
        gk_p = jax.grad(lp)(k)
        gk_r = jax.grad(lr)(k)
        np.testing.assert_allclose(np.asarray(gk_p), np.asarray(gk_r),
                                   atol=2e-4)


class TestAutotune:
    def test_autotune_sweeps_and_caches(self, tmp_path, monkeypatch):
        from paddle_tpu.ops.pallas import autotune as at
        cache = at.AutotuneCache(str(tmp_path / "tune.json"))
        monkeypatch.setattr(at, "_cache", cache)
        from paddle_tpu.core.flags import GLOBAL_FLAGS
        GLOBAL_FLAGS.set("kernel_autotune", True)
        calls = []

        def build(cfg):
            def fn(x):
                calls.append(cfg)
                import time
                time.sleep(0.02 if cfg == "slow" else 0.0)
                return x + 1
            return fn

        import paddle_tpu.ops.pallas._util as u
        prev = u._FORCE_INTERPRET
        u.set_force_interpret(False)  # autotune is a no-op in interpret mode
        try:
            cfg = at.autotune("toy|(4,)", ["slow", "fast"], build,
                              (jnp.ones(4),), warmup=1, iters=2)
            assert cfg == "fast"
            calls.clear()
            # second lookup: cache hit, no sweep
            cfg2 = at.autotune("toy|(4,)", ["slow", "fast"], build,
                               (jnp.ones(4),))
            assert cfg2 == "fast" and not calls
            # persistent across instances
            cache2 = at.AutotuneCache(str(tmp_path / "tune.json"))
            assert cache2.get("toy|(4,)") == 1
        finally:
            u.set_force_interpret(prev)
            GLOBAL_FLAGS.set("kernel_autotune", False)


@pytest.mark.slow
@pytest.mark.slow
def test_flash_attn_unpadded_dropout_in_kernel():
    """dropout>0 rides inside the fused kernel (position-keyed hash
    mask); training=False returns the no-dropout fused result."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    rng = np.random.RandomState(0)
    tq, h, d = 12, 2, 8
    q = paddle.to_tensor(rng.randn(tq, h, d).astype(np.float32))
    k = paddle.to_tensor(rng.randn(tq, h, d).astype(np.float32))
    v = paddle.to_tensor(rng.randn(tq, h, d).astype(np.float32))
    cu = paddle.to_tensor(np.array([0, 5, 12], np.int32))
    o0, _ = F.flash_attn_unpadded(q, k, v, cu, cu, causal=True)
    o1, _ = F.flash_attn_unpadded(q, k, v, cu, cu, causal=True,
                                  dropout=0.3, training=True)
    assert np.asarray(o1.numpy()).shape == (tq, h, d)
    assert not np.allclose(np.asarray(o0.numpy()), np.asarray(o1.numpy()))
    o2, _ = F.flash_attn_unpadded(q, k, v, cu, cu, causal=True,
                                  dropout=0.3, training=False)
    np.testing.assert_allclose(np.asarray(o0.numpy()),
                               np.asarray(o2.numpy()), atol=1e-5)
    # deterministic under the framework seed; varies across seeds
    paddle.seed(123)
    a, _ = F.flash_attn_unpadded(q, k, v, cu, cu, causal=True,
                                 dropout=0.3, training=True)
    paddle.seed(123)
    b, _ = F.flash_attn_unpadded(q, k, v, cu, cu, causal=True,
                                 dropout=0.3, training=True)
    np.testing.assert_allclose(np.asarray(a.numpy()),
                               np.asarray(b.numpy()))


class TestFlashDropout:
    """In-kernel attention dropout (VERDICT round-2 §2: 'in-kernel
    dropout RNG still missing'). The keep mask is a counter-based hash
    of absolute positions, so the forward and both backward kernels —
    and a full-matrix jnp reference — regenerate it identically."""

    def _qkv(self, B=2, S=128, H=4, KVH=2, D=64):
        import jax.numpy as jnp
        rng = np.random.RandomState(0)
        return (jnp.asarray(rng.randn(B, S, H, D), jnp.float32),
                jnp.asarray(rng.randn(B, S, KVH, D), jnp.float32),
                jnp.asarray(rng.randn(B, S, KVH, D), jnp.float32))

    def _ref(self, q, k, v, seed, rate):
        # the production full-matrix composition IS the reference — one
        # copy of the hash/GQA layout to keep bit-identical
        return _ref_attention(q, k, v, causal=True, dropout_rate=rate,
                              dropout_seed=seed)

    @pytest.mark.slow
    def test_dropout_with_segment_ids_matches_reference(self):
        """Varlen (segment-id) masking and in-kernel dropout compose —
        the actual flash_attn_unpadded training path on TPU."""
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_pallas)
        q, k, v = self._qkv(B=2, S=128, H=2, KVH=2)
        seg = jnp.concatenate([jnp.zeros((2, 64), jnp.int32),
                               jnp.ones((2, 64), jnp.int32)], axis=1)
        seed = jnp.asarray(11, jnp.uint32)
        o_k = flash_attention_pallas(q, k, v, causal=True,
                                     segment_ids=seg, dropout_rate=0.3,
                                     dropout_seed=seed)
        o_r = _ref_attention(q, k, v, causal=True, segment_ids=seg,
                             dropout_rate=0.3, dropout_seed=seed)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   rtol=2e-5, atol=2e-5)
        # grads through the varlen+dropout kernel match the composition
        import jax as _jax

        def lk(q, k, v):
            return (flash_attention_pallas(
                q, k, v, causal=True, segment_ids=seg, dropout_rate=0.3,
                dropout_seed=seed).astype(jnp.float32) ** 2).sum()

        def lr(q, k, v):
            return (_ref_attention(
                q, k, v, causal=True, segment_ids=seg, dropout_rate=0.3,
                dropout_seed=seed).astype(jnp.float32) ** 2).sum()

        gk = _jax.grad(lk, (0, 1, 2))(q, k, v)
        gr = _jax.grad(lr, (0, 1, 2))(q, k, v)
        for a, b, name in zip(gk, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name)

    @pytest.mark.slow
    def test_fwd_and_grads_match_exact_mask_reference(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_pallas)
        q, k, v = self._qkv()
        seed = jnp.asarray(77, jnp.uint32)
        rate = 0.3
        o_k = flash_attention_pallas(q, k, v, causal=True,
                                     dropout_rate=rate, dropout_seed=seed)
        o_r = self._ref(q, k, v, seed, rate)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                                   rtol=2e-5, atol=2e-5)

        def lk(q, k, v):
            return (flash_attention_pallas(
                q, k, v, causal=True, dropout_rate=rate,
                dropout_seed=seed).astype(jnp.float32) ** 2).sum()

        def lr(q, k, v):
            return (self._ref(q, k, v, seed, rate)
                    .astype(jnp.float32) ** 2).sum()

        gk = jax.grad(lk, (0, 1, 2))(q, k, v)
        gr = jax.grad(lr, (0, 1, 2))(q, k, v)
        for a, b, name in zip(gk, gr, "qkv"):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-4, err_msg=name)

    @pytest.mark.slow
    def test_deterministic_and_mean_preserving(self):
        import jax.numpy as jnp
        from paddle_tpu.ops.pallas.flash_attention import (
            flash_attention_pallas)
        q, k, v = self._qkv(B=1, S=128, H=2, KVH=1)
        o0 = np.asarray(flash_attention_pallas(q, k, v, causal=True))
        seed = jnp.asarray(5, jnp.uint32)
        a = np.asarray(flash_attention_pallas(
            q, k, v, causal=True, dropout_rate=0.3, dropout_seed=seed))
        b = np.asarray(flash_attention_pallas(
            q, k, v, causal=True, dropout_rate=0.3, dropout_seed=seed))
        np.testing.assert_array_equal(a, b)
        acc = np.zeros_like(o0)
        N = 24
        for i in range(N):
            acc += np.asarray(flash_attention_pallas(
                q, k, v, causal=True, dropout_rate=0.3,
                dropout_seed=jnp.asarray(100 + i, jnp.uint32)))
        err = np.abs(acc / N - o0).mean() / (np.abs(o0).mean() + 1e-9)
        assert err < 0.15, err


def test_autotune_cache_key_matches_tuned_blocks():
    """bench's flash_tune reports winners via autotune_cache_key; it must
    stay byte-identical to the key _tuned_blocks writes, or the sweep
    silently reports None winners after a key-format change."""
    import jax
    import jax.numpy as jnp
    from unittest import mock
    from paddle_tpu.ops.pallas import flash_attention as F

    q = jnp.zeros((8, 2048, 128), jnp.bfloat16)   # folded [b*h, s, d]
    k = jnp.zeros((4, 2048, 128), jnp.bfloat16)
    seen = {}

    def fake_get(ck):
        seen["ck"] = ck
        return None

    with mock.patch.object(F, "autotune_cache_key",
                           wraps=F.autotune_cache_key):
        with mock.patch.object(
                __import__("paddle_tpu.ops.pallas.autotune",
                           fromlist=["_cache"])._cache, "get",
                side_effect=fake_get):
            from paddle_tpu.core.flags import GLOBAL_FLAGS
            prev = GLOBAL_FLAGS.get("kernel_autotune")
            GLOBAL_FLAGS.set("kernel_autotune", True)
            try:
                # traced call -> reads the cache via the internal key
                jax.eval_shape(
                    lambda q, k: F._tuned_blocks(
                        q, k, k, None, None, None, 1.0, True,
                        (8, 4, 2048, 2048, 128, 1.0, True)) or (1, 1),
                    q, k)
            finally:
                GLOBAL_FLAGS.set("kernel_autotune", prev)
    expect = F.autotune_cache_key(8, 2048, 2048, 4, 128, True,
                                  "bfloat16")
    assert seen.get("ck") == expect, (seen.get("ck"), expect)
