"""Seeded random weights for the Granite-4.0-H decoder, made on the
device in one jitted call from the seed, in the type they are served in.

The tree has the layout ``paddle_tpu.models.granite_hybrid`` expects
(each kind of layer stacked on a leading axis: ``mamba`` over the
Mamba-2 layers, ``attn`` over the attention layers, ``moe`` over all
layers), but it is made here, by the benchmark: the program is handed
the weights and the plain reference reads the same arrays. Only the
experts HELD (``num_local_experts``) are made; the router keeps all
``num_experts`` columns.

What is set beyond the published file (the config lists it under
``assumed``):

- matrices: normal, std 0.02, as the dense decoder's;
- the embedding: normal, std 0.02 / 16. The head is tied to it and the
  embedding enters the residual stream times 12, so at std 0.02 a
  token's own logit (12 x 0.02^2 x 4096 / rms / 16, some 60 standard
  deviations of the other logits) would be the largest at every
  position: every served token would be a copy of its input, and the
  correctness check would compare nothing the layers compute. At
  0.02 / 16 the embedding is a twentieth of the stream after ten layers
  and the layers decide the largest logit;
- the recurrence, so that a step's decay ``exp(dt A)`` is neither 0
  nor 1 and the state REMEMBERS over the contexts the cells offer:
  ``dt`` log-uniform in [1e-3, 1e-1] with ``dt_bias`` its inverse
  softplus (Mamba-2's own range), ``A`` uniform in [0.1, 1]
  (``A_log`` its logarithm): a head's decay a step lies between 0.905
  and 0.9999, it forgets over 10 to 10,000 positions, and the state's
  part of the mixer's output is the larger one (0.86 of it in a
  simulation of these statistics, PR 27). With Mamba-2's own ``A`` in
  [1, 16] most heads forget within ten positions and the skip term
  ``D x`` carries the output: what a chunk hands the next would then
  hardly show in what is served, and the correctness check could not
  see the state at all. ``D`` = 1; the convolution's taps normal with
  std 0.3 (four taps: unit gain) and bias 0; norms 1.

Each stacked leaf is drawn layer by layer (``lax.map``), so the float32
temporaries of the normal draw are one layer's (one expert's for the
expert stacks), not the whole stack's.
"""
import jax
import jax.numpy as jnp

STD = 0.02
EMBED_STD = STD / 16
CONV_STD = 0.3
A_RANGE = (0.1, 1.0)
DT_RANGE = (1e-3, 1e-1)
F32 = jnp.float32


def sizes(model):
    H, hp = model["mamba_n_heads"], model["mamba_d_head"]
    G, N = model["mamba_n_groups"], model["mamba_d_state"]
    pattern = model["layer_types"][:model["num_hidden_layers"]]
    held = model["num_local_experts"]
    return {"D": model["hidden_size"], "V": model["vocab_size"],
            "Hm": H, "d_in": H * hp, "C": H * hp + 2 * G * N,
            "K": model["mamba_d_conv"],
            "AH": model["num_attention_heads"],
            "KV": model["num_key_value_heads"],
            "hd": model["hidden_size"] // model["num_attention_heads"],
            "held": held, "E": model.get("num_experts") or held,
            "F": model["intermediate_size"],
            "Fs": model["shared_intermediate_size"],
            "L": len(pattern),
            "Lm": sum(t == "mamba" for t in pattern),
            "La": sum(t == "attention" for t in pattern)}


def shapes(model):
    """group -> {leaf: shape of one layer's matrix}."""
    z = sizes(model)
    D = z["D"]
    return {
        "mamba": {"in_proj": (D, z["d_in"] + z["C"] + z["Hm"]),
                  "out_proj": (z["d_in"], D)},
        "attn": {"q_proj": (D, z["AH"] * z["hd"]),
                 "k_proj": (D, z["KV"] * z["hd"]),
                 "v_proj": (D, z["KV"] * z["hd"]),
                 "o_proj": (z["AH"] * z["hd"], D)},
        "moe": {"router": (D, z["E"]),
                "w_in": (z["held"], D, 2 * z["F"]),
                "w_out": (z["held"], z["F"], D),
                "shared_in": (D, 2 * z["Fs"]),
                "shared_out": (z["Fs"], D)},
    }


def _draw(key, shape, dtype, std=STD):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def _stack(key, n, shape, dtype):
    """[n, *shape], one layer at a time; an expert stack one expert at
    a time inside its layer."""
    def one(k):
        if len(shape) == 3:
            return jax.lax.map(lambda kk: _draw(kk, shape[1:], dtype),
                               jax.random.split(k, shape[0]))
        return _draw(k, shape, dtype)
    return jax.lax.map(one, jax.random.split(key, n))


def _make(key, model, dtype):
    z = sizes(model)
    D, Lm, La, L, Hm = z["D"], z["Lm"], z["La"], z["L"], z["Hm"]
    table = shapes(model)
    names = sorted((g, n) for g in table for n in table[g])
    keys = dict(zip(names, jax.random.split(key, len(names))))
    depth = {"mamba": Lm, "attn": La, "moe": L}
    tree = {g: {n: _stack(keys[g, n], depth[g], shape, dtype)
                for n, shape in table[g].items()} for g in table}
    k_e, k_c, k_a, k_dt = jax.random.split(jax.random.fold_in(key, 1), 4)
    dt = jnp.exp(jax.random.uniform(k_dt, (Lm, Hm), F32,
                                    *(jnp.log(v) for v in DT_RANGE)))
    tree["mamba"].update(
        input_norm=jnp.ones((Lm, D), F32),
        conv_w=_draw(k_c, (Lm, z["K"], z["C"]), dtype, CONV_STD),
        conv_b=jnp.zeros((Lm, z["C"]), F32),
        dt_bias=dt + jnp.log(-jnp.expm1(-dt)),
        A_log=jnp.log(jax.random.uniform(k_a, (Lm, Hm), F32, *A_RANGE)),
        D=jnp.ones((Lm, Hm), F32),
        norm=jnp.ones((Lm, z["d_in"]), F32))
    tree["attn"]["input_norm"] = jnp.ones((La, D), F32)
    tree["moe"]["post_norm"] = jnp.ones((L, D), F32)
    return {"embed_tokens": _draw(k_e, (z["V"], D), dtype, EMBED_STD),
            "final_norm": jnp.ones((D,), F32), **tree}


def make(model, seed, dtype=jnp.bfloat16):
    """The parameter tree for ``model`` (a dict of the configuration's
    keys) from ``seed``."""
    fn = jax.jit(lambda key: _make(key, model, dtype))
    return fn(jax.random.key(int(seed) % (2 ** 63)))


def count(model):
    """Parameters of the tree ``make`` returns."""
    n = 0
    for leaf in jax.tree_util.tree_leaves(jax.eval_shape(
            lambda: _make(jax.random.key(0), model, jnp.bfloat16))):
        n += leaf.size
    return n
