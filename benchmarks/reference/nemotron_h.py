"""Plain reference of the Nemotron-H decoder (HF ``NemotronH``,
``model_type: nemotron_h``; NVIDIA-Nemotron-3-Nano-30B-A3B): every layer
is ONE half, by its letter in ``hybrid_override_pattern``: ``M`` a
Mamba-2 mixer with ``n_groups`` B/C groups and a gated RMS norm over
each group's channels, ``*`` grouped-query attention with no position
embedding, ``E`` sigmoid-routed experts of two matrices with relu^2
plus one shared MLP of the same form. ``x <- x + f(rmsnorm(x))``, a
final norm, an untied head, no multipliers.

Written from the equations in float32 with
``jax.default_matmul_precision("highest")`` (every product is given
``Precision.HIGHEST``); no cache, no kernels, no batching, nothing
imported from the program. The Mamba-2 mixer is the RECURRENCE, one
position after another (``lax.scan``), so it shares no algorithm with
the program's chunked scan:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t,  y_t = S_t C_t + D x_t

The route is written out too: float32 sigmoid scores over all
``num_experts``; ``num_experts_per_tok`` times the largest of score +
bias is taken (the first of equals) and struck; the gates are the
SCORES (not score + bias) at the chosen, over their sum, times
``routed_scaling_factor``.

It reads the weights the benchmark made and upcasts one layer (one
expert) at a time; of an expert's first matrix and of ``in_proj`` it
reads the published columns (the tree stores both with zero columns up
to whole lanes of 128). It is given the same share as the program: of
the chosen only the experts ``[expert_offset, expert_offset +
n_routed_experts)`` contribute, the shared MLP whole; ids and logits
are over the vocabulary slice.

Departures from the published description, each on purpose: seeded
weights, not the checkpoint; no position embedding in attention (the
published implementation applies none; ``rope_theta`` is read by
nothing); ``dt`` is not clamped (``time_step_limit`` (0, inf));
``n_group = topk_group = 1``, so no limiting of the choice to groups of
experts is computed. ``fake_quant`` is the control of "How correct is
decided", never the reference itself: "fp8" / "int8" round every matrix
product's operands to that grid (absmax scale per row / per output
channel), the nearest precision below the bfloat16 the configuration
states; "state_bf16" rounds the recurrent state to bfloat16 after
every position.
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
    H, hp = model["mamba_num_heads"], model["mamba_head_dim"]
    G, N = model["n_groups"], model["ssm_state_size"]
    held = model["n_routed_experts"]
    return {"D": model["hidden_size"], "H": H, "hp": hp, "G": G, "N": N,
            "d_in": H * hp, "C": H * hp + 2 * G * N,
            "K": model["conv_kernel"],
            "AH": model["num_attention_heads"],
            "KV": model["num_key_value_heads"], "hd": model["head_dim"],
            "held": held, "E": model.get("num_experts") or held,
            "offset": model.get("expert_offset", 0),
            "k": model["num_experts_per_tok"],
            "scale": model["routed_scaling_factor"],
            "eps": model["layer_norm_epsilon"]}


def pattern(model):
    return tuple(model["hybrid_override_pattern"]
                 [:model["num_hidden_layers"]])


def mamba_mixer(u, w, sz, fake_quant=None):
    """u [S, D] (already normalised) -> [S, D], from a zero state."""
    S = u.shape[0]
    H, hp, G, N, K = sz["H"], sz["hp"], sz["G"], sz["N"], sz["K"]
    d_in, C = sz["d_in"], sz["C"]
    zxd = _mm(u, w["in_proj"], fake_quant)
    # the published columns [z | xBC | dt] (the tree stores zeros past)
    z, xbc = zxd[:, :d_in], zxd[:, d_in:d_in + C]
    dt = zxd[:, d_in + C:d_in + C + H]
    # depthwise causal convolution, kernel K, with bias; then SiLU
    ext = jnp.concatenate([jnp.zeros((K - 1, C), F32), xbc], axis=0)
    conv = w["conv_b"].astype(F32)[None, :]
    for k in range(K):
        conv = conv + ext[k:k + S] * w["conv_w"][k].astype(F32)[None, :]
    xbc = jax.nn.silu(conv)
    x = xbc[:, :d_in].reshape(S, H, hp)
    B = xbc[:, d_in:d_in + G * N].reshape(S, G, N)
    Cm = xbc[:, d_in + G * N:].reshape(S, G, N)
    # head h reads group h // (H / G)
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
    # the gated norm, over each group's d_in / G channels
    g = g.reshape(S, G, d_in // G)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + sz["eps"])
    g = g.reshape(S, d_in) * w["norm"].astype(F32)
    return _mm(g, w["out_proj"], fake_quant)


def attention_mixer(u, w, sz, fake_quant=None):
    """u [S, D] -> [S, D]: GQA, no bias, no position embedding,
    ``softmax(q k^T / sqrt(hd) + causal) v``. The queries are taken in
    blocks (of at most 256; every row of the scores is still whole), so
    that the float32 scores of a 2,048-token sequence fit beside the
    weights: 32 heads x 2,048 x 2,048 x 4 B is 0.5 GB, and a product at
    ``HIGHEST`` keeps several of them."""
    S = u.shape[0]
    AH, KV, hd = sz["AH"], sz["KV"], sz["hd"]
    q = _mm(u, w["q_proj"], fake_quant).reshape(S, KV, AH // KV, hd)
    k = _mm(u, w["k_proj"], fake_quant).reshape(S, KV, hd)
    v = _mm(u, w["v_proj"], fake_quant).reshape(S, KV, hd)
    rows = next(b for b in (256, 128, 64, 32, 16, 8, 4, 2, 1) if S % b == 0)

    def block(xs):
        qb, pos = xs                       # [rows, KV, G, hd], [rows]
        # a Python float: a numpy float64 would promote the scores (the
        # program runs with x64 enabled) and the chip emulates float64
        s = jnp.einsum("sngh,tnh->ngst", qb, k,
                       precision=HIGHEST) * float(hd) ** -0.5
        causal = pos[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("ngst,tnh->sngh", p, v, precision=HIGHEST)

    o = jax.lax.map(block, (q.reshape(S // rows, rows, KV, AH // KV, hd),
                            jnp.arange(S).reshape(S // rows, rows)))
    return _mm(o.reshape(S, -1), w["o_proj"], fake_quant)


def choose(scores, bias, k):
    """scores [S, E] -> (gates [S, k], experts [S, k]): k times the
    largest of score + bias (the first of equals), then struck."""
    left = scores + bias.astype(F32)[None, :]
    picked = []
    for _ in range(k):
        j = jnp.argmax(left, axis=-1)
        picked.append(j)
        left = jnp.where(jnp.arange(left.shape[-1])[None, :] == j[:, None],
                         -jnp.inf, left)
    idx = jnp.stack(picked, axis=-1)
    top = jnp.take_along_axis(scores, idx, axis=-1)
    return top / jnp.sum(top, axis=-1, keepdims=True), idx


def relu2(h):
    return jnp.square(jnp.maximum(h, 0.0))


def experts_and_shared(u, w, sz, fake_quant=None):
    """u [S, D] -> what the held experts and the shared MLP add."""
    scores = jax.nn.sigmoid(_mm(u, w["router"], fake_quant))   # all E
    gates, idx = choose(scores, w["router_bias"], sz["k"])
    gates = gates * sz["scale"]

    def one(acc, xs):
        j, w_in, w_out = xs
        # the published width: the tree stores w_in wider (zero columns)
        w_in = w_in[:, :w_out.shape[0]]
        o = _mm(relu2(_mm(u, w_in, fake_quant)), w_out, fake_quant)
        gate = jnp.sum(jnp.where(idx == j, gates, 0.0), axis=-1)
        return acc + gate[:, None] * o, None

    held = jnp.arange(sz["held"], dtype=idx.dtype) + sz["offset"]
    out, _ = jax.lax.scan(one, jnp.zeros_like(u),
                          (held, w["w_in"], w["w_out"]))
    return out + _mm(relu2(_mm(u, w["shared_in"], fake_quant)),
                     w["shared_out"], fake_quant)


def _pick(tree, i):
    return jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        tree)


@functools.partial(jax.jit, static_argnames=("kind", "items", "fake_quant"))
def _layer(x, stack, i, kind, items, fake_quant):
    """One layer on x [S, D]: ``stack`` is the stack of its half, ``i``
    its index there."""
    sz = dict(items)
    w = _pick(stack, i)
    if kind == "E":
        u = rms_norm(x, w["post_norm"], sz["eps"])
        return x + experts_and_shared(u, w, sz, fake_quant)
    u = rms_norm(x, w["input_norm"], sz["eps"])
    return x + (mamba_mixer(u, w, sz, fake_quant) if kind == "M"
                else attention_mixer(u, w, sz, fake_quant))


@functools.partial(jax.jit, static_argnames=("eps", "fake_quant"))
def _head(x, rows, final_norm, head, eps, fake_quant):
    return _mm(rms_norm(x[rows], final_norm, eps), head, fake_quant)


_STACK = {"M": "mamba", "*": "attn", "E": "moe"}


def logits_at(params, model, tokens, rows, fake_quant=None):
    """Float32 logits [len(rows), V] of one sequence ``tokens`` [S] at
    the positions ``rows``. ``tokens`` may be padded at the end: every
    mixer is causal, so earlier positions are unaffected."""
    sz = sizes(model)
    items = tuple(sorted(sz.items()))
    x = jnp.take(params["embed_tokens"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(F32)
    seen = {"M": 0, "*": 0, "E": 0}
    for kind in pattern(model):
        x = _layer(x, params[_STACK[kind]], jnp.int32(seen[kind]), kind,
                   items, fake_quant)
        seen[kind] += 1
    return _head(x, jnp.asarray(rows, jnp.int32), params["final_norm"],
                 params["lm_head"], sz["eps"], fake_quant)


def token_gaps(params, model, prompt, served, pad_to=512, fake_quant=None):
    """For EVERY served token: how far the reference's logit for it
    lies below the reference's best logit at that position (0 where
    the served token is the reference's own choice). With
    ``fake_quant`` it is the control: the token judged is the one the
    lower precision puts first at each position of the same prompt and
    tokens."""
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


def last_third(gaps):
    """The part of one request's gaps that ``correct`` is decided on:
    those of the LAST THIRD of its served tokens.

    What a request's late tokens add is the recurrent state's history.
    A state kept in bfloat16 is rounded once a decode step, and what
    that does to the logits grows with the number of steps: the float32
    reference with its state rounded after every position (nothing
    else changed; the CPU, this cell's size) puts its own first choice
    0.045 below the reference's best at positions 64-128, 0.065 at
    128-256, 0.076 at 384-512, 0.114 at 768-1024. Against that stands a
    floor that does not grow so: served in bfloat16, a quarter of the
    tokens are not the float32 reference's choice at any position (the
    route: PERF.md, PR 43). Over ALL of a request's tokens the
    bfloat16 state read 1.0 to 1.6 times what the sound program read
    and could not be told from it; over the last third the floor is
    the same and the state's share is the largest the traffic has. A
    fraction and not a position, so that the rule is the same for a
    request of 64 tokens and one of 1,024 and for the tiny preset.

    What the earlier two thirds would show alone is not judged at this
    size: a fault that lasts (a wrong state hand-over, a wrong page, a
    wrong route) is in the late tokens too, one that touched only a
    request's first tokens is not; the CPU tests hold prefill and the
    first decode steps to the reference in LOGITS."""
    return gaps[(2 * gaps.size) // 3:]


def served_margins(params, model, prompt, served, pad_to=512,
                   fake_quant=None):
    """What the benchmark's comparison takes its widest and mean gap
    over, from one finished request: :func:`token_gaps` of the last
    third of its served tokens (:func:`last_third` says why)."""
    return last_third(token_gaps(params, model, prompt, served, pad_to,
                                 fake_quant))


@jax.jit
def _gaps(ref, judged):
    got = jnp.take_along_axis(ref, judged[:, None], axis=-1)[:, 0]
    return jnp.max(ref, axis=-1) - got
