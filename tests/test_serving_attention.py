"""Serving fused attention ops (reference:
incubate/nn/functional/block_multihead_attention.py,
masked_multihead_attention.py, blha_get_max_len.py)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.incubate.nn.functional as IF


def _ref_step_attention(q, kc, vc, lens):
    """Loop reference: per-seq attention over cache[:len+1]."""
    B, H, D = q.shape
    out = np.zeros((B, H, D), np.float32)
    for i in range(B):
        L = int(lens[i]) + 1
        s = np.einsum("hd,hsd->hs", q[i], kc[i, :, :L]) / np.sqrt(D)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[i] = np.einsum("hs,hsd->hd", p, vc[i, :, :L])
    return out


def test_blha_get_max_len():
    enc = paddle.to_tensor(np.asarray([5, 2, 9], np.int32))
    dec = paddle.to_tensor(np.asarray([0, 7, 1], np.int32))
    me, md = IF.blha_get_max_len(enc, dec, paddle.to_tensor(np.ones(3)))
    assert int(me.numpy()[0]) == 9 and int(md.numpy()[0]) == 7


def test_masked_multihead_attention_matches_loop():
    rng = np.random.RandomState(0)
    B, H, S, D = 2, 4, 16, 8
    cache = rng.randn(2, B, H, S, D).astype(np.float32)
    lens = np.asarray([3, 7], np.int32)
    x = rng.randn(B, 3 * H * D).astype(np.float32)
    bias = rng.randn(3, H, D).astype(np.float32)

    out, cache2 = IF.masked_multihead_attention(
        paddle.to_tensor(x), cache_kv=paddle.to_tensor(cache),
        bias=paddle.to_tensor(bias),
        sequence_lengths=paddle.to_tensor(lens))
    out, cache2 = np.asarray(out.numpy()), np.asarray(cache2.numpy())

    qkv = x.reshape(B, 3, H, D) + bias.reshape(1, 3, H, D)
    kc, vc = cache[0].copy(), cache[1].copy()
    for i in range(B):
        kc[i, :, lens[i]] = qkv[i, 1]
        vc[i, :, lens[i]] = qkv[i, 2]
    ref = _ref_step_attention(qkv[:, 0], kc, vc, lens)
    np.testing.assert_allclose(out, ref.reshape(B, H * D), atol=2e-5)
    # cache written in place at the right slot, elsewhere untouched
    np.testing.assert_allclose(cache2[0], kc, atol=1e-6)
    np.testing.assert_allclose(cache2[1], vc, atol=1e-6)


def test_masked_mha_long_src_mask_clamped():
    """Regression (ADVICE.md r5): a src_mask whose last dim exceeds the
    cache S_max made the pad width negative (jnp.pad raised). It must
    clamp to S_max — matching the result of passing the pre-clamped
    mask — like the decode tgt_mask path does."""
    rng = np.random.RandomState(7)
    B, H, S, D = 2, 2, 8, 4
    cache = rng.randn(2, B, H, S, D).astype(np.float32)
    lens = np.asarray([3, 6], np.int32)
    x = rng.randn(B, 3 * H * D).astype(np.float32)
    long_mask = rng.randn(B, 1, S + 5).astype(np.float32)  # > S_max

    out_long, _ = IF.masked_multihead_attention(
        paddle.to_tensor(x), cache_kv=paddle.to_tensor(cache),
        src_mask=paddle.to_tensor(long_mask),
        sequence_lengths=paddle.to_tensor(lens))
    out_clamped, _ = IF.masked_multihead_attention(
        paddle.to_tensor(x), cache_kv=paddle.to_tensor(cache),
        src_mask=paddle.to_tensor(long_mask[:, :, :S]),
        sequence_lengths=paddle.to_tensor(lens))
    np.testing.assert_allclose(np.asarray(out_long.numpy()),
                               np.asarray(out_clamped.numpy()),
                               atol=1e-6)
    # short masks still pad up to S_max
    out_short, _ = IF.masked_multihead_attention(
        paddle.to_tensor(x), cache_kv=paddle.to_tensor(cache),
        src_mask=paddle.to_tensor(long_mask[:, :, :2]),
        sequence_lengths=paddle.to_tensor(lens))
    assert np.isfinite(np.asarray(out_short.numpy())).all()


def test_masked_mha_gates_quant_args():
    x = paddle.to_tensor(np.zeros((1, 3 * 2 * 4), np.float32))
    cache = paddle.to_tensor(np.zeros((2, 1, 2, 8, 4), np.float32))
    with pytest.raises(NotImplementedError, match="quantized-cache"):
        IF.masked_multihead_attention(
            x, cache_kv=cache,
            qkv_out_scale=paddle.to_tensor(np.ones(1)))


def _bmha_setup(rng, B, H, D, BS, MB):
    NB = B * MB + 1
    kc = rng.randn(NB, H, BS, D).astype(np.float32)
    vc = rng.randn(NB, H, BS, D).astype(np.float32)
    tables = rng.permutation(NB - 1)[:B * MB].reshape(B, MB) + 1
    return kc, vc, tables.astype(np.int32)


def test_block_mha_decode_matches_loop():
    rng = np.random.RandomState(1)
    B, H, D, BS, MB = 2, 2, 8, 4, 3
    kc, vc, tables = _bmha_setup(rng, B, H, D, BS, MB)
    dec = np.asarray([5, 2], np.int32)     # tokens already cached
    qkv = rng.randn(B, 3 * H * D).astype(np.float32)

    out, _, kc2, vc2 = IF.block_multihead_attention(
        paddle.to_tensor(qkv), paddle.to_tensor(kc),
        paddle.to_tensor(vc),
        paddle.to_tensor(np.zeros(B, np.int32)),       # enc lens
        paddle.to_tensor(dec),
        paddle.to_tensor(np.ones(B, np.int32)),        # this time: 1
        paddle.to_tensor(np.zeros(B, np.int32)),
        paddle.to_tensor(np.zeros(B, np.int32)),
        paddle.to_tensor(np.arange(B + 1, dtype=np.int32)),
        paddle.to_tensor(np.arange(B + 1, dtype=np.int32)),
        paddle.to_tensor(tables), block_size=BS)
    out = np.asarray(out.numpy())

    # loop reference over a dense per-seq cache
    pk = qkv.reshape(B, 3, H, D)
    dense_k = np.zeros((B, H, MB * BS, D), np.float32)
    dense_v = np.zeros((B, H, MB * BS, D), np.float32)
    for i in range(B):
        for m in range(MB):
            dense_k[i, :, m * BS:(m + 1) * BS] = kc[tables[i, m]]
            dense_v[i, :, m * BS:(m + 1) * BS] = vc[tables[i, m]]
        dense_k[i, :, dec[i]] = pk[i, 1]
        dense_v[i, :, dec[i]] = pk[i, 2]
    ref = _ref_step_attention(pk[:, 0], dense_k, dense_v, dec)
    np.testing.assert_allclose(out, ref.reshape(B, H * D), atol=3e-2)
    # the written slot landed in the right page
    kc2 = np.asarray(kc2.numpy())
    pg, sl = tables[0, dec[0] // BS], dec[0] % BS
    np.testing.assert_allclose(kc2[pg, :, sl], pk[0, 1], atol=1e-6)


def test_block_mha_prefill_writes_pages_and_attends_causal():
    rng = np.random.RandomState(2)
    B, H, D, BS, MB = 2, 2, 8, 4, 3
    kc, vc, tables = _bmha_setup(rng, B, H, D, BS, MB)
    lens = np.asarray([6, 3], np.int32)
    T = int(lens.sum())
    cu = np.concatenate([[0], np.cumsum(lens)]).astype(np.int32)
    qkv = rng.randn(T, 3 * H * D).astype(np.float32)

    out, _, kc2, vc2 = IF.block_multihead_attention(
        paddle.to_tensor(qkv), paddle.to_tensor(kc),
        paddle.to_tensor(vc),
        paddle.to_tensor(lens),                        # enc lens
        paddle.to_tensor(np.zeros(B, np.int32)),
        paddle.to_tensor(lens),
        paddle.to_tensor(np.zeros(T, np.int32)),
        paddle.to_tensor(np.zeros(B, np.int32)),
        paddle.to_tensor(cu), paddle.to_tensor(cu),
        paddle.to_tensor(tables), block_size=BS)
    out = np.asarray(out.numpy())

    pk = qkv.reshape(T, 3, H, D)
    for i in range(B):
        q = pk[cu[i]:cu[i + 1], 0]
        k = pk[cu[i]:cu[i + 1], 1]
        v = pk[cu[i]:cu[i + 1], 2]
        L = int(lens[i])
        s = np.einsum("thd,shd->hts", q, k) / np.sqrt(D)
        mask = np.tril(np.ones((L, L), bool))
        s = np.where(mask[None], s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        ref = np.einsum("hts,shd->thd", p, v).reshape(L, H * D)
        np.testing.assert_allclose(out[cu[i]:cu[i + 1]], ref, atol=2e-5)
    # cached prompt K readable back through the tables
    kc2 = np.asarray(kc2.numpy())
    tok = 5                                            # seq 0, pos 5
    pg, sl = tables[0, tok // BS], tok % BS
    np.testing.assert_allclose(kc2[pg, :, sl], pk[tok, 1], atol=1e-6)


def test_block_mha_decode_honors_tgt_mask():
    """An additive tgt_mask that blanks all but position 0 must change
    the output to attend only there (regression: the mask used to be
    silently ignored)."""
    rng = np.random.RandomState(4)
    B, H, D, BS, MB = 1, 2, 8, 4, 2
    kc, vc, tables = _bmha_setup(rng, B, H, D, BS, MB)
    dec = np.asarray([3], np.int32)
    qkv = rng.randn(B, 3 * H * D).astype(np.float32)
    S = MB * BS
    neg = np.full((B, 1, 1, S), -1e9, np.float32)
    neg[:, :, :, 0] = 0.0

    def run(mask):
        out = IF.block_multihead_attention(
            paddle.to_tensor(qkv), paddle.to_tensor(kc),
            paddle.to_tensor(vc),
            paddle.to_tensor(np.zeros(B, np.int32)),
            paddle.to_tensor(dec),
            paddle.to_tensor(np.ones(B, np.int32)),
            paddle.to_tensor(np.zeros(B, np.int32)),
            paddle.to_tensor(np.zeros(B, np.int32)),
            paddle.to_tensor(np.arange(B + 1, dtype=np.int32)),
            paddle.to_tensor(np.arange(B + 1, dtype=np.int32)),
            paddle.to_tensor(tables), block_size=BS,
            tgt_mask=mask)[0]
        return np.asarray(out.numpy())

    masked = run(paddle.to_tensor(neg))
    # attending only to position 0 == that position's value rows
    v0 = vc[tables[0, 0], :, 0]                    # [H, D]
    np.testing.assert_allclose(masked.reshape(H, D), v0, atol=1e-4)
    unmasked = run(None)
    assert np.abs(masked - unmasked).max() > 1e-3


def test_block_mha_rejects_mixed_phase():
    rng = np.random.RandomState(3)
    B, H, D, BS, MB = 2, 2, 8, 4, 2
    kc, vc, tables = _bmha_setup(rng, B, H, D, BS, MB)
    with pytest.raises(NotImplementedError, match="mixed"):
        IF.block_multihead_attention(
            paddle.to_tensor(rng.randn(2, 3 * H * D).astype(np.float32)),
            paddle.to_tensor(kc), paddle.to_tensor(vc),
            paddle.to_tensor(np.asarray([4, 0], np.int32)),  # enc
            paddle.to_tensor(np.asarray([0, 2], np.int32)),  # dec
            paddle.to_tensor(np.ones(B, np.int32)),
            paddle.to_tensor(np.zeros(B, np.int32)),
            paddle.to_tensor(np.zeros(B, np.int32)),
            paddle.to_tensor(np.arange(B + 1, dtype=np.int32)),
            paddle.to_tensor(np.arange(B + 1, dtype=np.int32)),
            paddle.to_tensor(tables), block_size=BS)


def test_block_mha_decode_int8_static_cache():
    """Static int8 cache mode (reference block_attn.h int8 path): the
    decode step over quantized pools tracks the bf16 result within
    quantization tolerance, and the written slot is int8."""
    from paddle_tpu.ops.paged_attention import quantize_pools
    rng = np.random.RandomState(5)
    B, H, D, BS, MB = 2, 2, 8, 4, 3
    kc, vc, tables = _bmha_setup(rng, B, H, D, BS, MB)
    dec = np.asarray([5, 2], np.int32)
    qkv = rng.randn(B, 3 * H * D).astype(np.float32)
    common = [
        paddle.to_tensor(np.zeros(B, np.int32)), paddle.to_tensor(dec),
        paddle.to_tensor(np.ones(B, np.int32)),
        paddle.to_tensor(np.zeros(B, np.int32)),
        paddle.to_tensor(np.zeros(B, np.int32)),
        paddle.to_tensor(np.arange(B + 1, dtype=np.int32)),
        paddle.to_tensor(np.arange(B + 1, dtype=np.int32)),
        paddle.to_tensor(tables)]

    ref_out = IF.block_multihead_attention(
        paddle.to_tensor(qkv), paddle.to_tensor(kc),
        paddle.to_tensor(vc), *common, block_size=BS)[0].numpy()

    # quantize [NB, H, BS, D] -> pool layout and back
    kq, vq, ks, vs = quantize_pools(jnp.swapaxes(jnp.asarray(kc), 1, 2),
                                    jnp.swapaxes(jnp.asarray(vc), 1, 2))
    kq8 = np.asarray(jnp.swapaxes(kq, 1, 2))
    vq8 = np.asarray(jnp.swapaxes(vq, 1, 2))
    out, _, kc2, _ = IF.block_multihead_attention(
        paddle.to_tensor(qkv), paddle.to_tensor(kq8),
        paddle.to_tensor(vq8), *common, block_size=BS,
        cache_k_dequant_scales=paddle.to_tensor(np.asarray(ks)),
        cache_v_dequant_scales=paddle.to_tensor(np.asarray(vs)))
    rel = np.abs(out.numpy() - ref_out).max() / (
        np.abs(ref_out).max() + 1e-9)
    assert rel < 0.05, rel
    assert np.asarray(kc2.numpy()).dtype == np.int8


def test_generate_paged_int8_cache_close_logits_and_runs():
    """generate_paged(cache_dtype='int8'): the per-step decode logits
    over quantized pools track the bf16-cache logits within quant
    tolerance (token chains on a RANDOM model legally diverge at
    near-ties, so logits — not greedy chains — are the right check),
    and the end-to-end int8 loop runs with int8 pools."""
    from paddle_tpu.inference import generation as G
    from paddle_tpu.models.llama import LlamaConfig, init_params
    from paddle_tpu.ops.paged_attention import quantize_pools

    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=4,
                      max_position_embeddings=96, dtype=jnp.float32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S, BS, MB = 2, 16, 8, 4
    k_cache, v_cache = G.init_cache(cfg, B, MB * BS)
    toks = jnp.asarray(np.random.RandomState(0).randint(0, 256, (B, S)),
                       jnp.int32)
    logits, k_cache, v_cache = G.cached_forward(
        params, toks, cfg, k_cache, v_cache, 0)
    # repack densely into per-seq pages (identity tables)
    L, KV, hd = cfg.num_hidden_layers, 4, cfg.head_dim
    NB = B * MB
    kp = jnp.reshape(k_cache, (L, NB, BS, KV, hd))
    vp = jnp.reshape(v_cache, (L, NB, BS, KV, hd))
    tables = jnp.asarray(
        np.arange(NB).reshape(B, MB), jnp.int32)
    lens = jnp.full((B,), S, jnp.int32)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)

    lg_bf, _, _ = G._decode_step(params, tok, cfg, kp, vp,
                                       tables, lens)
    kq, vq, ks, vs = jax.vmap(quantize_pools)(kp, vp)
    lg_i8, kq2, _ = G._decode_step(params, tok, cfg, kq, vq,
                                         tables, lens,
                                         kv_scales=(ks, vs))
    assert kq2.dtype == jnp.int8
    rel = float(jnp.max(jnp.abs(lg_i8 - lg_bf))
                / (jnp.max(jnp.abs(lg_bf)) + 1e-9))
    assert rel < 0.05, rel

    # end-to-end int8 serving loop runs and emits valid tokens
    g = G.GenerationConfig(max_new_tokens=8, greedy=True)
    out = np.asarray(G.generate_paged(params, toks, cfg, g,
                                      cache_dtype="int8"))
    assert out.shape == (B, S + 8)
    assert ((0 <= out) & (out < 256)).all()
