"""Seeded random weights for the Nemotron-H decoder, made on the device
in one jitted call from the seed, in the type they are served in.

The tree has the layout ``paddle_tpu.models.nemotron_h`` expects (each
half stacked on a leading axis: ``mamba`` over the ``M`` layers,
``attn`` over the ``*`` layers, ``moe`` over the ``E`` layers), but it
is made here, by the benchmark: the program is handed the weights and
the plain reference reads the same arrays. Only the experts HELD
(``n_routed_experts``) are made; the router and its bias keep all
``num_experts`` columns.

What is set beyond the published file (the config lists it under
``assumed``):

- matrices, the embedding and the untied head: normal, std 0.02. There
  is no multiplier on the embedding and no tie, so a token's own logit
  is not favoured and the layers decide the largest logit;
- the router's ``e_score_correction_bias``: normal, std 0.02, float32.
  The sigmoid scores of a router drawn at 0.02 over a normalised input
  lie within a few percent of 1/2, so a bias of that size changes the
  choice for a share of the tokens: the choice (score + bias) and the
  gates (score alone) are both seen. At zero that term would be
  untested;
- the recurrence as ``benchmarks/weights/granite_hybrid.py`` argues it:
  ``dt`` log-uniform in [1e-3, 1e-1] (the published ``time_step_min`` /
  ``time_step_max``) with ``dt_bias`` its inverse softplus, ``A``
  uniform in [0.1, 1], so that a head's decay a step lies between 0.905
  and 0.9999 and the state carries most of the mixer's output; ``D`` =
  1; the convolution's taps normal with std 0.3 (four taps: unit gain)
  and bias 0; norms 1.

One thing follows the program's tree and not the published shapes: two
stacked matrices are drawn as published and STORED with their columns
rounded up to whole lanes of 128, the extra columns zero: an expert's
first matrix ``w_in`` ([D, 1856] -> 1920 wide) and the Mamba-2
``in_proj`` ([D, 10304] -> 10368). The program says why
(``ops/moe_experts.moe_experts``, ``models/pattern.mamba_in``). The zero
columns add exact zeros to nothing; the reference reads the published
columns.

Each stacked leaf is drawn layer by layer (``lax.map``), so the float32
temporaries of the normal draw are one layer's (one expert's for the
expert stacks), not the whole stack's.
"""
import jax
import jax.numpy as jnp

STD = 0.02
BIAS_STD = 0.02
CONV_STD = 0.3
A_RANGE = (0.1, 1.0)
DT_RANGE = (1e-3, 1e-1)
F32 = jnp.float32


def sizes(model):
    H, hp = model["mamba_num_heads"], model["mamba_head_dim"]
    G, N = model["n_groups"], model["ssm_state_size"]
    pattern = model["hybrid_override_pattern"][:model["num_hidden_layers"]]
    held = model["n_routed_experts"]
    return {"D": model["hidden_size"], "V": model["vocab_size"],
            "Hm": H, "d_in": H * hp, "C": H * hp + 2 * G * N,
            "K": model["conv_kernel"],
            "AH": model["num_attention_heads"],
            "KV": model["num_key_value_heads"], "hd": model["head_dim"],
            "held": held, "E": model.get("num_experts") or held,
            "F": model["moe_intermediate_size"],
            "Fs": model["moe_shared_expert_intermediate_size"],
            "Lm": pattern.count("M"), "La": pattern.count("*"),
            "Le": pattern.count("E")}


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
                "w_in": (z["held"], D, z["F"]),
                "w_out": (z["held"], z["F"], D),
                "shared_in": (D, z["Fs"]),
                "shared_out": (z["Fs"], D)},
    }


def _draw(key, shape, dtype, std=STD):
    return (jax.random.normal(key, shape, F32) * std).astype(dtype)


def _stack(key, n, shape, dtype, lanes=None):
    """[n, *shape], one layer at a time; an expert stack one expert at
    a time inside its layer. ``lanes``: store the last axis that wide,
    zeros past the drawn columns."""
    def draw(k, shape):
        x = _draw(k, shape, dtype)
        if lanes is None:
            return x
        return jnp.pad(x, ((0, 0),) * (x.ndim - 1)
                       + ((0, lanes - shape[-1]),))

    def one(k):
        if len(shape) == 3:
            return jax.lax.map(lambda kk: draw(kk, shape[1:]),
                               jax.random.split(k, shape[0]))
        return draw(k, shape)
    return jax.lax.map(one, jax.random.split(key, n))


def storage_width(width):
    """The columns ``w_in`` and ``in_proj`` are stored with."""
    return -(-width // 128) * 128


def _make(key, model, dtype):
    z = sizes(model)
    D, Lm, La, Le, Hm = z["D"], z["Lm"], z["La"], z["Le"], z["Hm"]
    table = shapes(model)
    names = sorted((g, n) for g in table for n in table[g])
    keys = dict(zip(names, jax.random.split(key, len(names))))
    depth = {"mamba": Lm, "attn": La, "moe": Le}
    tree = {g: {n: _stack(keys[g, n], depth[g], shape, dtype,
                          storage_width(shape[-1])
                          if n in ("w_in", "in_proj") else None)
                for n, shape in table[g].items()} for g in table}
    k_e, k_h, k_c, k_a, k_dt, k_b = jax.random.split(
        jax.random.fold_in(key, 1), 6)
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
    tree["moe"].update(
        post_norm=jnp.ones((Le, D), F32),
        router_bias=_draw(k_b, (Le, z["E"]), F32, BIAS_STD))
    return {"embed_tokens": _draw(k_e, (z["V"], D), dtype),
            "lm_head": _draw(k_h, (D, z["V"]), dtype),
            "final_norm": jnp.ones((D,), F32), **tree}


def make(model, seed, dtype=jnp.bfloat16):
    """The parameter tree for ``model`` (a dict of the configuration's
    keys) from ``seed``."""
    fn = jax.jit(lambda key: _make(key, model, dtype))
    return fn(jax.random.key(int(seed) % (2 ** 63)))


def count(model):
    """Parameters of the tree ``make`` returns, less the zero columns
    ``w_in`` and ``in_proj`` are stored with."""
    z = sizes(model)
    wide = z["d_in"] + z["C"] + z["Hm"]
    n = -z["D"] * (z["Le"] * z["held"] * (storage_width(z["F"]) - z["F"])
                   + z["Lm"] * (storage_width(wide) - wide))
    for leaf in jax.tree_util.tree_leaves(jax.eval_shape(
            lambda: _make(jax.random.key(0), model, jnp.bfloat16))):
        n += leaf.size
    return n
