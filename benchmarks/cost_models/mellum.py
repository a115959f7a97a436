"""Operations and bytes the Mellum 2 decoder needs, from its shapes
alone: the least a chip could do for the work (every byte across HBM
once, every operation of the algorithm, nothing recomputed, nothing
padded). A measured device time is held against ``least_seconds``; a
share over 100% means a count here is too high and is a bug to find.

What is counted per decode step, for ``live`` decoding slots:
- every weight that takes part for every token once: the attention
  projections of all layers, the routers, the untied head (the
  embedding is a gather of ``live`` rows);
- of the expert matrices only those of the experts TOUCHED in the step:
  with ``live`` tokens each choosing k of E experts, a held expert is
  touched with probability 1 - (1 - k/E)^live (routing taken as
  uniform: skew touches fewer, so this errs towards a lower share);
- keys and values: a full-attention layer reads every live token's, a
  sliding-window layer only those inside the window. The window's
  share is not derivable from a mean context length (a mean of minima
  is not the minimum of means), so the shape carries it: ``win_tokens``
  (mean over the traced decode steps of the tokens the window layers'
  launches visit, from the engine's ``kv_tokens_held_window``); where
  it is absent the window layers are counted at ONE token a live slot,
  which can only lower a share.
"""
from benchmarks.cost_models.granite_hybrid import least_seconds  # noqa: F401


def dims(model):
    pattern = model["layer_types"][:model["num_hidden_layers"]]
    E = model["num_experts"]
    return {"D": model["hidden_size"], "V": model["vocab_size"],
            "H": model["num_attention_heads"],
            "KV": model["num_key_value_heads"], "hd": model["head_dim"],
            "E": E, "held": model.get("num_local_experts") or E,
            "k": model["num_experts_per_tok"],
            "F": model["moe_intermediate_size"],
            "W": model["sliding_window"], "L": len(pattern),
            "Lw": sum(t == "sliding_attention" for t in pattern),
            "Lg": sum(t == "full_attention" for t in pattern)}


def attn_params(z):
    return 2 * z["D"] * z["H"] * z["hd"] + 2 * z["D"] * z["KV"] * z["hd"]


def expert_params(z):
    return 3 * z["D"] * z["F"]


def layer_params(z):
    """One layer as held here: attention, router, norms, held experts."""
    return (attn_params(z) + z["D"] * z["E"] + 2 * z["D"]
            + z["held"] * expert_params(z))


def total_params(model):
    z = dims(model)
    return z["L"] * layer_params(z) + 2 * z["V"] * z["D"] + z["D"]


def kv_page_bytes(model, block_size=16, kv_bytes=2):
    """One page of one layer, keys and values."""
    z = dims(model)
    return 2 * block_size * z["KV"] * z["hd"] * kv_bytes


def touched(z, tokens):
    """Expected number of held experts that get a token."""
    return z["held"] * (1.0 - (1.0 - z["k"] / z["E"]) ** tokens)


def moe_experts(model, tokens, weight_bytes=2, act_bytes=2):
    """The expert launches (both grouped products) of ONE layer over
    ``tokens`` tokens: (flops, bytes)."""
    z = dims(model)
    used = tokens * z["k"] * z["held"] / z["E"]       # rows computed
    flops = 2 * used * expert_params(z)
    moved = (touched(z, tokens) * expert_params(z) * weight_bytes
             + used * (2 * z["D"] + 3 * z["F"]) * act_bytes)
    return flops, moved


def window_tokens(shape):
    """Tokens the window layers' launches visit in a decode step."""
    live = shape.get("live_slots", shape["slots"])
    return shape.get("win_tokens", live)


def attention_launch(model, tokens, live, kv_bytes=2, act_bytes=2):
    """One ``paged_attention_decode`` launch over ``tokens`` live keys
    of ``live`` slots: (flops, bytes)."""
    z = dims(model)
    moved = (2 * tokens * z["KV"] * z["hd"] * kv_bytes
             + 2 * live * z["H"] * z["hd"] * act_bytes)
    return 4 * z["H"] * z["hd"] * tokens, moved


def paged_attention_decode(model, shape):
    """The MEAN launch of a decode step: Lg over every live token, Lw
    over the tokens inside the window."""
    z = dims(model)
    live = shape.get("live_slots", shape["slots"])
    g = attention_launch(model, shape["live_tokens"] + live, live)
    w = attention_launch(model, window_tokens(shape), live)
    n = z["Lg"] + z["Lw"]
    return ((z["Lg"] * g[0] + z["Lw"] * w[0]) / n,
            (z["Lg"] * g[1] + z["Lw"] * w[1]) / n)


def decode_step(model, shape, weight_bytes=2, act_bytes=2):
    """One decode step with ``live_slots`` of ``slots`` decoding, their
    contexts holding ``live_tokens`` tokens together: (flops, bytes)."""
    z = dims(model)
    live = shape.get("live_slots", shape["slots"])
    always = (z["L"] * (attn_params(z) + z["D"] * z["E"])
              + z["D"] * z["V"])
    e_flops, e_bytes = moe_experts(model, live, weight_bytes, act_bytes)
    a_flops, a_bytes = paged_attention_decode(model, shape)
    moved = (always * weight_bytes + z["L"] * (e_bytes + a_bytes)
             + live * z["V"] * 4)
    flops = 2 * always * live + z["L"] * (e_flops + a_flops)
    return flops, moved


# -- by name, for the layer metrics' readers ---------------------------
# shape: what the driver saw in the traced window ("slots"; the mean
# "live_slots" and "live_tokens" of a decode step) and, where a reader
# adds it from the engine's counters, "win_tokens". A KERNELS entry
# gives the cost of that computation in ONE execution of its program
# (all its layers), except ``paged_attention_decode``: one launch.
PROGRAMS = {"decode_step": decode_step}


def _all_layers(model, shape):
    flops, moved = moe_experts(model,
                               shape.get("live_slots", shape["slots"]))
    n = dims(model)["L"]
    return n * flops, n * moved


KERNELS = {
    "moe_experts": _all_layers,
    "paged_attention_decode": paged_attention_decode,
}
