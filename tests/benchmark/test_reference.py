"""The plain reference against a small case written out by hand in
numpy float64, its optimizer against AdamW's update written out, and
the lower-precision control against the reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import dense_decoder as ref
from benchmarks.weights import dense_decoder_stacked as weights

MODEL = {"hidden_size": 16, "intermediate_size": 24, "vocab_size": 40,
         "num_attention_heads": 4, "num_key_value_heads": 2,
         "head_dim": 4, "num_hidden_layers": 2, "rms_norm_eps": 1e-5,
         "rope_theta": 100.0, "tie_word_embeddings": False}


@pytest.fixture(scope="module")
def params():
    # std 0.02 would leave every logit near zero: scale the draw up so a
    # wrong rotation or mask moves the result
    p = weights.make(MODEL, 11, jnp.float32)
    return jax.tree_util.tree_map(
        lambda a: a * 12.0 if a.ndim >= 2 else a, p)


def by_hand(p, tokens):
    """float64 numpy, one position after another, no vectorised mask."""
    P = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    D, H, KV, hd = 16, 4, 2, 4
    S = len(tokens)
    x = P["embed_tokens"][tokens]

    def norm(v, w):
        return v / np.sqrt((v * v).mean(-1, keepdims=True) + 1e-5) * w

    def rot(v, pos):                      # v [hd]
        half = hd // 2
        out = v.copy()
        for i in range(half):
            ang = pos / (100.0 ** (2 * i / hd))
            a, b = v[i], v[i + half]
            out[i] = a * np.cos(ang) - b * np.sin(ang)
            out[i + half] = b * np.cos(ang) + a * np.sin(ang)
        return out

    for l in range(2):
        W = {k: v[l] for k, v in P["layers"].items()}
        h = norm(x, W["input_norm"])
        q = (h @ W["q_proj"]).reshape(S, H, hd)
        k = (h @ W["k_proj"]).reshape(S, KV, hd)
        v = (h @ W["v_proj"]).reshape(S, KV, hd)
        att = np.zeros((S, H, hd))
        for s in range(S):
            for hh in range(H):
                kv = hh // (H // KV)
                qs = rot(q[s, hh], s)
                sc = np.array([qs @ rot(k[t, kv], t) / np.sqrt(hd)
                               for t in range(s + 1)])
                w = np.exp(sc - sc.max())
                w /= w.sum()
                att[s, hh] = sum(w[t] * v[t, kv] for t in range(s + 1))
        x = x + att.reshape(S, H * hd) @ W["o_proj"]
        h = norm(x, W["post_norm"])
        g = h @ W["gate_proj"]
        x = x + ((g / (1 + np.exp(-g))) * (h @ W["up_proj"])) @ W["down_proj"]
    return norm(x, P["final_norm"]) @ P["lm_head"]


def test_logits_match_the_hand_written_case(params):
    tokens = np.array([3, 17, 5, 39, 0, 8, 21], np.int32)
    want = by_hand(params, tokens)
    got = np.asarray(ref.logits_at(params, MODEL, tokens, np.arange(7)))
    assert np.abs(want).max() > 0.5          # the case says something
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    # padding after the sequence changes nothing before it
    padded = np.concatenate([tokens, np.zeros(9, np.int32)])
    again = np.asarray(ref.logits_at(params, MODEL, padded, np.arange(7)))
    np.testing.assert_allclose(again, got, atol=1e-6)


def test_served_margins(params):
    prompt = np.array([3, 17, 5, 39], np.int32)
    seq = list(prompt)
    for _ in range(5):                    # the reference's own greedy run
        lg = by_hand(params, np.array(seq))
        seq.append(int(lg[-1].argmax()))
    served = np.array(seq[4:], np.int32)
    assert np.all(ref.served_margins(params, MODEL, prompt, served,
                                     pad_to=8) < 1e-5)
    wrong = served.copy()
    wrong[2] = (wrong[2] + 1) % 40        # one token altered
    gaps = ref.served_margins(params, MODEL, prompt, wrong, pad_to=8)
    assert gaps[2] > 1e-3 and np.all(gaps[:2] < 1e-5)


def test_loss_is_cross_entropy_of_the_logits(params):
    toks = np.array([[3, 17, 5, 39, 0, 8], [9, 9, 30, 2, 11, 4]], np.int32)
    labels = np.roll(toks, -1, axis=1)
    want = 0.0
    for b in range(2):
        lg = by_hand(params, toks[b])
        lse = np.log(np.exp(lg).sum(-1))
        want += (lse - lg[np.arange(6), labels[b]]).sum()
    got = float(ref.loss(params, MODEL, jnp.asarray(toks),
                         jnp.asarray(labels)))
    assert got == pytest.approx(want / 12, rel=1e-5)


def test_adamw_follows_the_written_update(params):
    toks = jnp.asarray(np.array([[3, 17, 5, 39, 0, 8]], np.int32))
    labels = jnp.roll(toks, -1, axis=1)
    opt = {"lr": 1e-2, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
           "weight_decay": 0.1, "grad_clip": 1.0,
           "moment_dtype": "float32"}
    out = ref.adamw_steps(params, MODEL, [(toks, labels)], opt)
    g = jax.grad(lambda p: ref.loss(p, MODEL, toks, labels))(params)
    gn = float(jnp.sqrt(sum(jnp.sum(x * x)
                            for x in jax.tree_util.tree_leaves(g))))
    scale = min(1.0, 1.0 / gn)
    gq = np.asarray(g["layers"]["q_proj"], np.float64) * scale
    assert out["first_grad"]["layers/q_proj"] == pytest.approx(
        np.sqrt((gq * gq).sum()), rel=1e-5)
    # step 1 of AdamW: mhat = g, vhat = g^2 -> p(1 - lr wd) - lr g/(|g|+eps)
    p0 = np.asarray(params["layers"]["q_proj"], np.float64)
    moved = p0 * (1 - 1e-2 * 0.1) - 1e-2 * gq / (np.abs(gq) + 1e-8) - p0
    assert out["moved"]["layers/q_proj"] == pytest.approx(
        np.sqrt((moved * moved).sum()), rel=1e-4)
    assert len(out["losses"]) == 1 and np.isfinite(out["losses"][0])


def test_the_control_is_not_the_reference(params):
    tokens = np.array([3, 17, 5, 39, 0, 8, 21], np.int32)
    a = np.asarray(ref.logits_at(params, MODEL, tokens, np.arange(7)))
    b = np.asarray(ref.logits_at(params, MODEL, tokens, np.arange(7),
                                 fake_quant="int8"))
    err = np.abs(a - b).max()
    assert 1e-4 < err < 0.5 * np.abs(a).max()
    with pytest.raises(ValueError):
        ref.logits_at(params, MODEL, tokens, np.arange(7), fake_quant="int3")
