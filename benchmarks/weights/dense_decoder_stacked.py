"""Seeded random weights for a dense decoder, made on the device.

One jitted call from the seed, in the type the weights are served in.
The tree has the layout the program's model code expects (layers stacked
on a leading axis, the names of ``paddle_tpu.models.llama``), but it is
made here, by the benchmark: the program is handed the weights and the
plain reference reads the same arrays, so neither side's numbers depend
on code of the other.

Each stacked leaf is drawn layer by layer (``lax.map``), so the float32
temporaries of the normal draw are one layer's, not the whole stack's.
"""
import jax
import jax.numpy as jnp

STD = 0.02


def shapes(model):
    """name -> (stacked over layers?, per-layer shape)."""
    D, F, V = (model["hidden_size"], model["intermediate_size"],
               model["vocab_size"])
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or D // H
    out = {
        "embed_tokens": (False, (V, D)),
        "q_proj": (True, (D, H * hd)), "k_proj": (True, (D, KV * hd)),
        "v_proj": (True, (D, KV * hd)), "o_proj": (True, (H * hd, D)),
        "gate_proj": (True, (D, F)), "up_proj": (True, (D, F)),
        "down_proj": (True, (F, D)),
    }
    if not model.get("tie_word_embeddings", False):
        out["lm_head"] = (False, (D, V))
    return out


def _draw(key, shape, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * STD).astype(dtype)


def _make(key, model, dtype, shardings=None):
    L, D = model["num_hidden_layers"], model["hidden_size"]
    table = shapes(model)
    keys = dict(zip(sorted(table), jax.random.split(key, len(table))))
    layers = {"input_norm": jnp.ones((L, D), jnp.float32),
              "post_norm": jnp.ones((L, D), jnp.float32)}
    top = {"final_norm": jnp.ones((D,), jnp.float32)}
    for name, (stacked, shape) in table.items():
        if stacked:
            layers[name] = jax.lax.map(
                lambda k, s=shape: _draw(k, s, dtype),
                jax.random.split(keys[name], L))
        else:
            top[name] = _draw(keys[name], shape, dtype)
    return {**top, "layers": layers}


def make(model, seed, dtype=jnp.bfloat16, out_shardings=None):
    """The parameter tree for ``model`` (a dict of published keys) from
    ``seed``. ``out_shardings`` (a matching tree) makes a sharded model
    in place, never whole on one device."""
    fn = jax.jit(lambda key: _make(key, model, dtype),
                 out_shardings=out_shardings)
    return fn(jax.random.key(int(seed) % (2 ** 63)))


def count(model):
    """Parameters of the tree ``make`` returns (norms included)."""
    L, D = model["num_hidden_layers"], model["hidden_size"]
    n = (2 * L + 1) * D
    for stacked, shape in shapes(model).values():
        n += (L if stacked else 1) * shape[0] * shape[1]
    return n
