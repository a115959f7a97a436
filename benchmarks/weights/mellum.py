"""Seeded random weights for the Mellum 2 decoder, made on the device
in one jitted call from the seed, in the type they are served in.

The tree has the layout ``paddle_tpu.models.mellum`` expects (each kind
of layer stacked on a leading axis: ``window`` over the sliding-window
layers, ``full`` over the full-attention layers, ``moe`` over all
layers; ``lm_head`` [D, V] beside ``embed_tokens`` [V, D]: the head is
not tied), but it is made here, by the benchmark: the program is handed
the weights and the plain reference reads the same arrays. Only the
experts HELD (``num_local_experts``) are made; the router keeps all
``num_experts`` columns.

What is set beyond the published file (the config lists it under
``assumed``): every matrix normal with std 0.02, as the dense
decoder's; norms 1. The head is its own matrix, so no token's logit is
its own embedding's square (the tied granite head needed a smaller
embedding for that) and the logits' spread is that of a sum of 2,304
products: about 1.

Each stacked leaf is drawn layer by layer (``lax.map``), so the float32
temporaries of the normal draw are one layer's (one expert's for the
expert stacks), not the whole stack's.
"""
import jax
import jax.numpy as jnp

# a normal draw in the served type, and a stack of them made a layer
# (an expert) at a time: the granite weights' own
from benchmarks.weights.granite_hybrid import _draw, _stack

F32 = jnp.float32


def sizes(model):
    pattern = model["layer_types"][:model["num_hidden_layers"]]
    E = model["num_experts"]
    return {"D": model["hidden_size"], "V": model["vocab_size"],
            "H": model["num_attention_heads"],
            "KV": model["num_key_value_heads"], "hd": model["head_dim"],
            "E": E, "held": model.get("num_local_experts") or E,
            "F": model["moe_intermediate_size"], "L": len(pattern),
            "Lw": sum(t == "sliding_attention" for t in pattern),
            "Lg": sum(t == "full_attention" for t in pattern)}


def shapes(model):
    """group -> {leaf: shape of one layer's matrix}."""
    z = sizes(model)
    D = z["D"]
    attn = {"q_proj": (D, z["H"] * z["hd"]),
            "k_proj": (D, z["KV"] * z["hd"]),
            "v_proj": (D, z["KV"] * z["hd"]),
            "o_proj": (z["H"] * z["hd"], D)}
    return {"window": attn, "full": dict(attn),
            "moe": {"router": (D, z["E"]),
                    "w_in": (z["held"], D, 2 * z["F"]),
                    "w_out": (z["held"], z["F"], D)}}


def _make(key, model, dtype):
    z = sizes(model)
    D = z["D"]
    table = shapes(model)
    names = sorted((g, n) for g in table for n in table[g])
    keys = dict(zip(names, jax.random.split(key, len(names))))
    depth = {"window": z["Lw"], "full": z["Lg"], "moe": z["L"]}
    tree = {g: {n: _stack(keys[g, n], depth[g], shape, dtype)
                for n, shape in table[g].items()} for g in table}
    k_e, k_h = jax.random.split(jax.random.fold_in(key, 1))
    tree["window"]["input_norm"] = jnp.ones((z["Lw"], D), F32)
    tree["full"]["input_norm"] = jnp.ones((z["Lg"], D), F32)
    tree["moe"]["post_norm"] = jnp.ones((z["L"], D), F32)
    return {"embed_tokens": _draw(k_e, (z["V"], D), dtype),
            "lm_head": _draw(k_h, (D, z["V"]), dtype),
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
