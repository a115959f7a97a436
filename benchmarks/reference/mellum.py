"""Plain reference of the Mellum 2 decoder (``model_type: mellum``):
sliding-window and full attention layers by the published pattern, each
followed by a top-k expert layer with no shared MLP, an untied head.

Written from the equations in float32 with
``jax.default_matmul_precision("highest")`` (every product is given
``Precision.HIGHEST``); no cache, no kernels, no batching, nothing
imported from the program:

    h = x + Attn(rmsnorm(x)),   y = h + MoE(rmsnorm(h)),   eps 1e-6
    Attn: 32 query heads over 4 key/value heads of 128, scale
      1/sqrt(128), rotate-half RoPE (theta 500000). A sliding_attention
      layer uses the plain frequencies and position i sees j with
      0 <= i - j < sliding_window; a full_attention layer sees every
      j <= i and uses YaRN: inv_freq = interp * ramp + extrap * (1 -
      ramp), extrap = theta^(-2k/d), interp = extrap / factor, ramp =
      clip((k - low) / (high - low), 0, 1), low = floor(c(beta_fast)),
      high = ceil(c(beta_slow)), c(r) = d ln(L0 / (2 pi r)) / (2 ln
      theta) clipped to [0, d - 1]; cos and sin times attention_factor.
    MoE: router over all num_experts, softmax, the top k renormalised
      to sum 1; each expert W_down(silu(W_gate u) * W_up u).

Attention is computed a block of 512 queries at a time (a window layer
against the 1,536 keys that block can see), so that a request of 25
thousand tokens fits beside the weights; the weights are read as the
benchmark made them and upcast one layer (one expert) at a time. It is
given the same share as the program: of the top-k only the experts
``[expert_offset, expert_offset + num_local_experts)`` contribute.

Departures from the published description, each on purpose: seeded
weights, not the checkpoint; no normalisation of q and k and no
multi-token-prediction head (the published config has no key for
either). ``fake_quant`` is the control of "How correct is decided",
never the reference itself: "fp8" / "int8" round every matrix product's
operands to that grid (absmax scale per row / per output channel), the
nearest precision below the bfloat16 the configuration states;
"window_ignored" lets the sliding_attention layers see every earlier
position (what a program that lost the window would serve).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 512


def _fq(x, axis, fake_quant):
    """Round to the lower precision's grid with an absmax scale along
    ``axis``: "int8" (127 steps a side) or "fp8" (float8 e4m3, largest
    finite value 448); anything else leaves ``x`` as it is."""
    top = {"int8": 127.0, "fp8": 448.0}.get(fake_quant)
    if top is None:
        return x
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if fake_quant == "int8":
        return jnp.round(x / s) * s
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, fake_quant=None):
    """x [.., K] @ w [K, N] in float32."""
    return jnp.matmul(_fq(x.astype(F32), -1, fake_quant),
                      _fq(w.astype(F32), 0, fake_quant), precision=HIGHEST)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(F32)


def pattern(model):
    return tuple(model["layer_types"][:model["num_hidden_layers"]])


def inv_freq(model, kind):
    """(float64 [head_dim / 2], factor on cos and sin) of one kind of
    layer, from its section of ``rope_parameters``."""
    rp = model["rope_parameters"][kind]
    d, theta = model["head_dim"], float(rp["rope_theta"])
    k = np.arange(d // 2, dtype=np.float64)
    extrap = theta ** (-2.0 * k / d)
    if rp["rope_type"] == "default":
        return extrap, 1.0
    assert rp["rope_type"] == "yarn", rp

    def c(rotations):
        return (d * math.log(rp["original_max_position_embeddings"]
                             / (2 * math.pi * rotations))
                / (2 * math.log(theta)))

    low = max(math.floor(c(rp["beta_fast"])), 0)
    high = min(math.ceil(c(rp["beta_slow"])), d - 1)
    ramp = np.clip((k - low) / max(high - low, 0.001), 0.0, 1.0)
    return ((extrap / rp["factor"]) * ramp + extrap * (1.0 - ramp),
            float(rp["attention_factor"]))


def rope(x, freq, factor):
    """x [S, heads, d] at positions 0..S-1, rotate-half."""
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    h = x.shape[-1] // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(u, w, model, freq, factor, window, fake_quant=None):
    """u [S, D] (already normalised) -> [S, D]. ``window``: None, or how
    many positions a query sees, itself included. S is a multiple of
    QUERY_BLOCK or smaller than it."""
    S = u.shape[0]
    H, KV, d = (model["num_attention_heads"], model["num_key_value_heads"],
                model["head_dim"])
    q = rope(_mm(u, w["q_proj"], fake_quant).reshape(S, H, d), freq, factor)
    k = rope(_mm(u, w["k_proj"], fake_quant).reshape(S, KV, d), freq, factor)
    v = _mm(u, w["v_proj"], fake_quant).reshape(S, KV, d)
    q = q.reshape(S, KV, H // KV, d)
    QB = min(QUERY_BLOCK, S)
    # a block of queries sees the keys of its own block and, under a
    # window, of as many whole blocks before it as the window reaches
    back = S - QB if window is None else -(-(window - 1) // QB) * QB
    back = min(back, S - QB)
    kp = jnp.concatenate([jnp.zeros((back, KV, d), F32), k])
    vp = jnp.concatenate([jnp.zeros((back, KV, d), F32), v])

    def block(q0):
        qb = jax.lax.dynamic_slice_in_dim(q, q0, QB)
        kb = jax.lax.dynamic_slice_in_dim(kp, q0, back + QB)
        vb = jax.lax.dynamic_slice_in_dim(vp, q0, back + QB)
        s = jnp.einsum("sngh,tnh->ngst", qb, kb, precision=HIGHEST) \
            / math.sqrt(d)
        i = q0 + jnp.arange(QB)[:, None]
        j = q0 - back + jnp.arange(back + QB)[None, :]
        see = (j >= 0) & (j <= i)
        if window is not None:
            see = see & (i - j < window)
        s = jnp.where(see[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("ngst,tnh->sngh", p, vb, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(0, S, QB))
    return _mm(o.reshape(S, H * d), w["o_proj"], fake_quant)


def experts(u, w, model, fake_quant=None):
    """u [S, D] -> what the held experts add."""
    F, k = model["moe_intermediate_size"], model["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm(u, w["router"], fake_quant), axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    gates = top / jnp.sum(top, axis=-1, keepdims=True)   # norm_topk_prob

    def one(acc, xs):
        j, w_in, w_out = xs
        h = _mm(u, w_in, fake_quant)
        o = _mm(jax.nn.silu(h[:, :F]) * h[:, F:], w_out, fake_quant)
        gate = jnp.sum(jnp.where(idx == j, gates, 0.0), axis=-1)
        return acc + gate[:, None] * o, None

    held = (jnp.arange(w["w_in"].shape[0], dtype=idx.dtype)
            + model.get("expert_offset", 0))
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (held, w["w_in"], w["w_out"]))
    return out


def _pick(tree, i):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


def _frozen(model):
    return tuple(sorted(
        (k, v) for k, v in model.items()
        if isinstance(v, (int, float, bool, str)) and k != "name"))


@functools.partial(jax.jit,
                   static_argnames=("kind", "items", "window", "factor",
                                    "fake_quant"))
def _layer(x, mixers, moe, freq, i, l, kind, items, window, factor,
           fake_quant):
    """One layer on x [S, D]: ``mixers`` is the stack of its kind, ``i``
    its index there, ``l`` its index among all layers."""
    model = dict(items)
    eps = model["rms_norm_eps"]
    w = _pick(mixers, i)
    h = x + attention(rms_norm(x, w["input_norm"], eps), w, model, freq,
                      factor, window, fake_quant)
    wm = _pick(moe, l)
    return h + experts(rms_norm(h, wm["post_norm"], eps), wm, model,
                       fake_quant)


@functools.partial(jax.jit, static_argnames=("eps", "fake_quant"))
def _head(x, rows, final_norm, head, eps, fake_quant):
    return _mm(rms_norm(x[rows], final_norm, eps), head, fake_quant)


STACK = {"sliding_attention": "window", "full_attention": "full"}


def logits_at(params, model, tokens, rows, fake_quant=None):
    """Float32 logits [len(rows), V] of one sequence ``tokens`` [S] at
    the positions ``rows``. ``tokens`` may be padded at the end: every
    layer is causal, so earlier positions are unaffected."""
    items = _frozen(model)
    x = jnp.take(params["embed_tokens"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(F32)
    seen = {}
    for l, kind in enumerate(pattern(model)):
        freq, factor = inv_freq(model, kind)
        window = (model["sliding_window"]
                  if kind == "sliding_attention"
                  and fake_quant != "window_ignored" else None)
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        x = _layer(x, params[STACK[kind]], params["moe"],
                   jnp.asarray(freq, F32), jnp.int32(i), jnp.int32(l),
                   kind, items, window, factor, fake_quant)
    return _head(x, jnp.asarray(rows, jnp.int32), params["final_norm"],
                 params["lm_head"], model["rms_norm_eps"], fake_quant)


def padded_length(n, pad_to=QUERY_BLOCK):
    """``n`` rounded up to ``pad_to`` times a power of two: a handful
    of shapes whatever the requests' lengths."""
    m = pad_to
    while m < n:
        m *= 2
    return m


def served_margins(params, model, prompt, served, pad_to=QUERY_BLOCK,
                   fake_quant=None):
    """For each served token: how far the reference's logit for it lies
    below the reference's best logit at that position (0 where the
    served token is the reference's own choice). With ``fake_quant`` it
    is the control: the token judged is the one the control puts first
    at each position of the same prompt and tokens."""
    prompt, served = np.asarray(prompt), np.asarray(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n = seq.size
    padded = np.zeros(padded_length(n, pad_to), np.int32)
    padded[:n] = seq
    g = served.size
    rows = np.full(-(-g // 128) * 128, n - 1, np.int32)
    rows[:g] = np.arange(prompt.size - 1, n)
    ref = logits_at(params, model, padded, rows)
    judged = np.zeros(rows.size, np.int32)
    judged[:g] = served
    if fake_quant is not None:
        low = logits_at(params, model, padded, rows, fake_quant)
        gaps = _gaps(ref, jnp.argmax(low, axis=-1).astype(jnp.int32))
    else:
        gaps = _gaps(ref, jnp.asarray(judged))
    return np.asarray(gaps, np.float64)[:g]


@jax.jit
def _gaps(ref, judged):
    got = jnp.take_along_axis(ref, judged[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - got
