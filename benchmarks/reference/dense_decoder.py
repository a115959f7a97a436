"""Plain reference of a dense decoder: RMSNorm, RoPE, grouped-query
attention, SwiGLU, no biases (Mistral-7B-v0.3, `modeling_mistral.py`).

Written from the equations in float32 with
``jax.default_matmul_precision("highest")``; no cache, no kernels, no
batching, and nothing imported from the program. It reads the weights
the benchmark made (bf16) and upcasts one layer at a time: the layer
loop is Python, so XLA never holds a float32 copy of all layers.

Departures from the published description, each on purpose:
- weights are seeded random, not the published checkpoint;
- the optimizer's moments are *stored* in the type the configuration
  states (bf16 for the training cell), the update itself is float32;
- ``fake_quant`` (None | "int8" | "fp8") rounds every matmul operand
  (and the keys and values) to that grid with an absmax scale per row /
  per output channel: the control of "How correct is decided", never
  the reference itself.
"""
import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _fq(x, axis, fake_quant):
    """Round to the lower precision's grid with an absmax scale along
    ``axis``: "int8" (127 steps a side) or "fp8" (float8 e4m3, largest
    finite value 448). Straight-through: the rounded value forward, the
    identity backward."""
    if fake_quant is None:
        return x
    top = {"int8": 127.0, "fp8": 448.0}.get(fake_quant)
    if top is None:
        raise ValueError(f"unknown fake_quant {fake_quant!r}")
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    s = jnp.where(s == 0, 1.0, s)
    if fake_quant == "int8":
        low = jnp.round(x / s)
    else:
        low = (x / s).astype(jnp.float8_e4m3fn).astype(F32)
    return x + jax.lax.stop_gradient(low * s - x)


def _mm(x, w, fake_quant=None):
    """x [.., K] @ w [K, N] in float32."""
    x = _fq(x.astype(F32), -1, fake_quant)
    w = _fq(w.astype(F32), 0, fake_quant)
    return jnp.matmul(x, w, precision=HIGHEST)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * weight.astype(F32)


def rope(x, theta):
    """x [S, heads, hd]; rotate-half convention, positions 0..S-1."""
    s, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    sin, cos = jnp.sin(ang)[:, None, :], jnp.cos(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(x, w, model, fake_quant=None):
    """One block on x [S, D] float32; ``w`` is one layer's weights."""
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    D = model["hidden_size"]
    hd = model.get("head_dim") or D // H
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    s = x.shape[0]
    h = rms_norm(x, w["input_norm"], eps)
    q = rope(_mm(h, w["q_proj"], fake_quant).reshape(s, H, hd), theta)
    k = rope(_mm(h, w["k_proj"], fake_quant).reshape(s, KV, hd), theta)
    v = _mm(h, w["v_proj"], fake_quant).reshape(s, KV, hd)
    if fake_quant is not None:        # an int8 cache holds k and v
        k, v = _fq(k, -1, fake_quant), _fq(v, -1, fake_quant)
    g = H // KV
    q = q.reshape(s, KV, g, hd)
    scores = jnp.einsum("sngh,tnh->ngst", q, k, precision=HIGHEST)
    scores = scores / jnp.sqrt(F32(hd))
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    attn = jnp.einsum("ngst,tnh->sngh", probs, v, precision=HIGHEST)
    x = x + _mm(attn.reshape(s, H * hd), w["o_proj"], fake_quant)
    h = rms_norm(x, w["post_norm"], eps)
    gate = _mm(h, w["gate_proj"], fake_quant)
    up = _mm(h, w["up_proj"], fake_quant)
    return x + _mm(jax.nn.silu(gate) * up, w["down_proj"], fake_quant)


@functools.partial(jax.jit, static_argnames=("model_items", "fake_quant"))
def _layer_i(x, layers, i, model_items, fake_quant):
    w = jax.tree_util.tree_map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False),
        layers)
    return layer(x, w, dict(model_items), fake_quant)


@functools.partial(jax.jit, static_argnames=("eps", "fake_quant"))
def _head(x, rows, final_norm, head, eps, fake_quant):
    return _mm(rms_norm(x[rows], final_norm, eps), head, fake_quant)


def _static(model):
    keys = ("num_attention_heads", "num_key_value_heads", "hidden_size",
            "head_dim", "rms_norm_eps", "rope_theta")
    return tuple((k, model[k]) for k in keys if model.get(k) is not None)


def logits_at(params, model, tokens, rows, fake_quant=None):
    """Float32 logits [len(rows), V] of one sequence ``tokens`` [S] at
    the positions ``rows``. ``tokens`` may be padded at the end (causal
    attention keeps earlier positions unaffected)."""
    x = jnp.take(params["embed_tokens"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(F32)
    items = _static(model)
    for i in range(model["num_hidden_layers"]):
        x = _layer_i(x, params["layers"], jnp.int32(i), items, fake_quant)
    head = params.get("lm_head")
    if head is None:
        head = params["embed_tokens"].T
    return _head(x, jnp.asarray(rows, jnp.int32), params["final_norm"],
                 head, model["rms_norm_eps"], fake_quant)


def served_margins(params, model, prompt, served, pad_to=512,
                   fake_quant=None):
    """For each served token: how far the reference's logit for it lies
    below the reference's best logit at that position (0 where the
    served token is the reference's own choice). With ``fake_quant`` it
    is the control: the token judged is the one the lower precision
    puts first at each position of the same prompt and tokens."""
    import numpy as np
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


# -- training: loss, gradients and AdamW, as the configuration states -----
def loss(params, model, tokens, labels, fake_quant=None):
    """Mean next-token cross entropy of ``tokens`` [B, S] against
    ``labels`` [B, S]; params float32."""
    w_layers = params["layers"]

    def one(seq):
        x = jnp.take(params["embed_tokens"], seq, axis=0).astype(F32)
        for i in range(model["num_hidden_layers"]):
            w = jax.tree_util.tree_map(lambda a, i=i: a[i], w_layers)
            x = jax.checkpoint(
                lambda x, w: layer(x, w, model, fake_quant))(x, w)
        head = params.get("lm_head")
        if head is None:
            head = params["embed_tokens"].T
        return _mm(rms_norm(x, params["final_norm"], model["rms_norm_eps"]),
                   head, fake_quant)

    total = 0.0
    for b in range(tokens.shape[0]):         # no batching: row by row
        lg = one(tokens[b])
        lse = jax.nn.logsumexp(lg, axis=-1)
        pick = jnp.take_along_axis(lg, labels[b][:, None], axis=-1)[:, 0]
        total = total + jnp.sum(lse - pick)
    return total / (tokens.shape[0] * tokens.shape[1])


def leaf_norms(tree):
    """name -> float32 L2 norm, one per leaf (stacked layers: one leaf)."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(v.astype(F32)))) for path, v in flat}


def adamw_steps(params_lp, model, batches, opt, fake_quant=None):
    """Follow ``len(batches)`` optimizer steps from the low-precision
    weights ``params_lp``: float32 master copy, global-norm clipping,
    bias-corrected AdamW with decoupled decay on every leaf, moments
    stored in ``opt["moment_dtype"]``. Returns the losses, the per-leaf
    norms of the first (clipped) gradient, and the per-leaf norms of the
    master weights' change over all the steps."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd, clip = opt["lr"], opt["weight_decay"], opt["grad_clip"]
    mdt = jnp.dtype(opt.get("moment_dtype", "float32"))
    vg = jax.jit(jax.value_and_grad(
        lambda p, t, l: loss(p, model, t, l, fake_quant)))

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(p, g, mu, nu, scale, step):
        g = g * scale
        mu_n = b1 * mu.astype(F32) + (1 - b1) * g
        nu_n = b2 * nu.astype(F32) + (1 - b2) * jnp.square(g)
        mhat = mu_n / (1 - F32(b1) ** step)
        vhat = nu_n / (1 - F32(b2) ** step)
        p = p * (1 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
        return p, mu_n.astype(mdt), nu_n.astype(mdt)

    tm = jax.tree_util.tree_map
    master = tm(lambda v: jnp.array(v, dtype=F32, copy=True), params_lp)
    mu = tm(lambda v: jnp.zeros(v.shape, mdt), master)
    nu = tm(lambda v: jnp.zeros(v.shape, mdt), master)
    losses, first_grad, moved = [], None, None
    for n, (tokens, labels) in enumerate(batches, 1):
        lval, grads = vg(master, tokens, labels)
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in
                          jax.tree_util.tree_leaves(grads)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-12)) \
            if clip else F32(1.0)
        if first_grad is None:
            first_grad = {k: float(v) * float(scale)
                          for k, v in leaf_norms(grads).items()}
        leaves_p, treedef = jax.tree_util.tree_flatten(master)
        out = [update(p, g, m, v, scale, F32(n)) for p, g, m, v in zip(
            leaves_p, jax.tree_util.tree_leaves(grads),
            jax.tree_util.tree_leaves(mu), jax.tree_util.tree_leaves(nu))]
        del grads, leaves_p
        master = jax.tree_util.tree_unflatten(treedef, [o[0] for o in out])
        mu = jax.tree_util.tree_unflatten(treedef, [o[1] for o in out])
        nu = jax.tree_util.tree_unflatten(treedef, [o[2] for o in out])
        losses.append(float(lval))
    moved = {k: float(v) for k, v in leaf_norms(tm(
        lambda m, p: m - p.astype(F32), master, params_lp)).items()}
    return {"losses": losses, "first_grad": first_grad, "moved": moved}
