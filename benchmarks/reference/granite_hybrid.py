"""Plain reference of the Granite-4.0-H decoder (HF ``GraniteMoeHybrid``,
``model_type: granitemoehybrid``): Mamba-2 and NoPE grouped-query
attention mixers by the published layer pattern, each followed by a
top-k expert layer plus one shared gated MLP, with the embedding,
residual, attention and logits multipliers.

Written from the equations in float32 with
``jax.default_matmul_precision("highest")`` (every product is given
``Precision.HIGHEST``); no cache, no kernels, no batching, nothing
imported from the program. The Mamba-2 mixer is the RECURRENCE, one
position after another (``lax.scan``), so it shares no algorithm with
the program's chunked scan:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,  y_t = S_t C_t + D x_t

It reads the weights the benchmark made and upcasts one layer (one
expert) at a time. It is given the same share as the program: the
router scores all ``num_experts``; of the top-k only the experts
``[expert_offset, expert_offset + num_local_experts)`` contribute, the
shared MLP whole; ids and logits are over the vocabulary slice.

Departures, each on purpose: seeded weights, not the checkpoint;
``time_step_limit`` is HF's default (0, inf), so ``dt`` is not clamped;
``fake_quant`` is the control of "How correct is decided", never the
reference itself: "fp8" / "int8" round every matrix product's operands
to that grid (absmax scale per row / per output channel), the nearest
precision below the bfloat16 the configuration states; "state_bf16"
rounds the recurrent state to bfloat16 after every position.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


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


def sizes(model):
    """The sizes the equations need, from the published keys."""
    H = model["mamba_n_heads"]
    hp = model["mamba_d_head"]
    G, N = model["mamba_n_groups"], model["mamba_d_state"]
    held = model["num_local_experts"]
    return {"D": model["hidden_size"], "H": H, "hp": hp, "G": G, "N": N,
            "d_in": H * hp, "C": H * hp + 2 * G * N,
            "K": model["mamba_d_conv"],
            "AH": model["num_attention_heads"],
            "KV": model["num_key_value_heads"],
            "held": held, "E": model.get("num_experts") or held,
            "offset": model.get("expert_offset", 0),
            "k": model["num_experts_per_tok"],
            "F": model["intermediate_size"],
            "e": model["embedding_multiplier"],
            "r": model["residual_multiplier"],
            "a": model["attention_multiplier"],
            "ls": model["logits_scaling"], "eps": model["rms_norm_eps"]}


def pattern(model):
    return tuple(model["layer_types"][:model["num_hidden_layers"]])


def mamba_mixer(u, w, sz, fake_quant=None):
    """u [S, D] (already normalised) -> [S, D], from a zero state."""
    S = u.shape[0]
    H, hp, G, N, K = sz["H"], sz["hp"], sz["G"], sz["N"], sz["K"]
    d_in, C = sz["d_in"], sz["C"]
    zxd = _mm(u, w["in_proj"], fake_quant)
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:d_in + C], zxd[:, d_in + C:]
    # depthwise causal convolution, kernel K, with bias; then SiLU
    ext = jnp.concatenate([jnp.zeros((K - 1, C), F32), xbc], axis=0)
    conv = w["conv_b"].astype(F32)[None, :]
    for k in range(K):
        conv = conv + ext[k:k + S] * w["conv_w"][k].astype(F32)[None, :]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_in].reshape(S, H, hp)
    B = xbc[:, d_in:d_in + G * N].reshape(S, G, N)
    Cm = xbc[:, d_in + G * N:].reshape(S, G, N)
    B, Cm = (jnp.repeat(t, H // G, axis=1) for t in (B, Cm))   # [S,H,N]
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(F32)[None, :])
    A = -jnp.exp(w["A_log"].astype(F32))
    Dw = w["D"].astype(F32)

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        if fake_quant == "state_bf16":
            state = state.astype(jnp.bfloat16).astype(F32)
        y = jnp.sum(state * c_t[:, None, :], axis=-1) + Dw[:, None] * x_t
        return state, y

    _, y = jax.lax.scan(step, jnp.zeros((H, hp, N), F32), (x, dt, B, Cm))
    g = y.reshape(S, d_in) * jax.nn.silu(z)
    g = rms_norm(g, w["norm"], sz["eps"])
    return _mm(g, w["out_proj"], fake_quant)


def attention_mixer(u, w, sz, fake_quant=None):
    """u [S, D] -> [S, D]: GQA, no bias, no position embedding,
    ``softmax(a q k^T + causal) v``."""
    S = u.shape[0]
    AH, KV = sz["AH"], sz["KV"]
    q = _mm(u, w["q_proj"], fake_quant).reshape(S, KV, AH // KV, -1)
    k = _mm(u, w["k_proj"], fake_quant).reshape(S, KV, -1)
    v = _mm(u, w["v_proj"], fake_quant).reshape(S, KV, -1)
    s = jnp.einsum("sngh,tnh->ngst", q, k, precision=HIGHEST) * sz["a"]
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("ngst,tnh->sngh", p, v, precision=HIGHEST)
    return _mm(o.reshape(S, -1), w["o_proj"], fake_quant)


def experts_and_shared(u, w, sz, fake_quant=None):
    """u [S, D] -> what the held experts and the shared MLP add."""
    F, k = sz["F"], sz["k"]
    logits = _mm(u, w["router"], fake_quant)         # all E experts
    top, idx = jax.lax.top_k(logits, k)
    gates = jax.nn.softmax(top, axis=-1)             # over those k

    def one(acc, xs):
        j, w_in, w_out = xs
        h = _mm(u, w_in, fake_quant)
        o = _mm(jax.nn.silu(h[:, :F]) * h[:, F:], w_out, fake_quant)
        gate = jnp.sum(jnp.where(idx == j, gates, 0.0), axis=-1)
        return acc + gate[:, None] * o, None

    held = jnp.arange(sz["held"], dtype=idx.dtype) + sz["offset"]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (held, w["w_in"], w["w_out"]))
    h = _mm(u, w["shared_in"], fake_quant)
    fs = h.shape[-1] // 2
    return out + _mm(jax.nn.silu(h[:, :fs]) * h[:, fs:], w["shared_out"],
                     fake_quant)


def _pick(tree, i):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


@functools.partial(jax.jit, static_argnames=("kind", "items", "fake_quant"))
def _layer(x, mixers, moe, i, l, kind, items, fake_quant):
    """One layer on x [S, D]: ``mixers`` is the stack of its kind,
    ``i`` its index there, ``l`` its index among all layers."""
    sz = dict(items)
    w = _pick(mixers, i)
    u = rms_norm(x, w["input_norm"], sz["eps"])
    mix = (mamba_mixer(u, w, sz, fake_quant) if kind == "mamba"
           else attention_mixer(u, w, sz, fake_quant))
    h = x + sz["r"] * mix
    wm = _pick(moe, l)
    u = rms_norm(h, wm["post_norm"], sz["eps"])
    return h + sz["r"] * experts_and_shared(u, wm, sz, fake_quant)


@functools.partial(jax.jit, static_argnames=("eps", "ls", "fake_quant"))
def _head(x, rows, final_norm, embed, eps, ls, fake_quant):
    return _mm(rms_norm(x[rows], final_norm, eps), embed.T, fake_quant) / ls


def logits_at(params, model, tokens, rows, fake_quant=None):
    """Float32 logits [len(rows), V] of one sequence ``tokens`` [S] at
    the positions ``rows``. ``tokens`` may be padded at the end: every
    mixer is causal, so earlier positions are unaffected."""
    sz = sizes(model)
    items = tuple(sorted(sz.items()))
    x = jnp.take(params["embed_tokens"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(F32) * sz["e"]
    seen = {"mamba": 0, "attention": 0}
    for l, kind in enumerate(pattern(model)):
        stack = params["mamba" if kind == "mamba" else "attn"]
        x = _layer(x, stack, params["moe"], jnp.int32(seen[kind]),
                   jnp.int32(l), kind, items, fake_quant)
        seen[kind] += 1
    return _head(x, jnp.asarray(rows, jnp.int32), params["final_norm"],
                 params["embed_tokens"], sz["eps"], sz["ls"], fake_quant)


def served_margins(params, model, prompt, served, pad_to=512,
                   fake_quant=None):
    """For each served token: how far the reference's logit for it lies
    below the reference's best logit at that position (0 where the
    served token is the reference's own choice). With ``fake_quant`` it
    is the control: the token judged is the one the lower precision
    puts first at each position of the same prompt and tokens."""
    prompt, served = np.asarray(prompt), np.asarray(served)
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    n = seq.size
    padded = np.zeros(-(-n // pad_to) * pad_to, np.int32)
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
