"""Operations and bytes the Granite-4.0-H decoder needs, from its shapes
alone: the least a chip could do for the work (every byte across HBM
once, every operation of the algorithm, nothing recomputed, nothing
padded). A measured device time is held against ``least_seconds``; a
share over 100% means a count here is too high and is a bug to find.

What is counted per decode step, for ``live`` decoding slots:
- every weight that takes part for every token once: the Mamba-2 and
  attention projections, router, shared MLP, the tied head;
- of the expert matrices only those of the experts TOUCHED in the step:
  with ``live`` tokens each choosing k of E experts, a held expert is
  touched with probability 1 - (1 - k/E)^live (routing taken as
  uniform: skew touches fewer, so this errs towards a lower share);
- the recurrent state of the live slots read once and written once
  (the convolution's tail likewise), the live keys and values once;
- operations: 2 a weight a token, the held experts a token actually
  uses (k x held / E of them on average), the state update (6 a state
  element: decay, outer product, add, readout), attention over the
  live context.
"""


def dims(model):
    H, hp = model["mamba_n_heads"], model["mamba_d_head"]
    G, N = model["mamba_n_groups"], model["mamba_d_state"]
    D = model["hidden_size"]
    pattern = model["layer_types"][:model["num_hidden_layers"]]
    held = model["num_local_experts"]
    AH, KV = model["num_attention_heads"], model["num_key_value_heads"]
    return {"D": D, "V": model["vocab_size"], "Hm": H, "hp": hp, "N": N,
            "d_in": H * hp, "C": H * hp + 2 * G * N,
            "K": model["mamba_d_conv"], "AH": AH, "KV": KV,
            "hd": D // AH, "held": held,
            "E": model.get("num_experts") or held,
            "k": model["num_experts_per_tok"],
            "F": model["intermediate_size"],
            "Fs": model["shared_intermediate_size"], "L": len(pattern),
            "Lm": sum(t == "mamba" for t in pattern),
            "La": sum(t == "attention" for t in pattern)}


def mamba_params(z):
    """Matmul weights of one Mamba-2 mixer."""
    return z["D"] * (z["d_in"] + z["C"] + z["Hm"]) + z["d_in"] * z["D"]


def attn_params(z):
    return 2 * z["D"] * z["AH"] * z["hd"] + 2 * z["D"] * z["KV"] * z["hd"]


def dense_moe_params(z):
    """The router and the shared MLP of one layer."""
    return z["D"] * z["E"] + 3 * z["D"] * z["Fs"]


def expert_params(z):
    return 3 * z["D"] * z["F"]


def touched(z, tokens):
    """Expected number of held experts that get a token."""
    return z["held"] * (1.0 - (1.0 - z["k"] / z["E"]) ** tokens)


def least_seconds(flops, bytes_, peak):
    t_f = flops / peak["flops_per_s"]
    t_b = bytes_ / peak["bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")


def moe_experts(model, tokens, weight_bytes=2, act_bytes=2):
    """The expert launches (both grouped products) of ONE layer over
    ``tokens`` tokens: (flops, bytes)."""
    z = dims(model)
    used = tokens * z["k"] * z["held"] / z["E"]       # rows computed
    flops = 2 * used * expert_params(z)
    moved = (touched(z, tokens) * expert_params(z) * weight_bytes
             + used * (2 * z["D"] + 3 * z["F"]) * act_bytes)
    return flops, moved


def ssm_update(model, live, state_bytes=4, act_bytes=2):
    """The one-token state update of ONE Mamba-2 layer for ``live``
    slots: (flops, bytes). The state is read once and written once."""
    z = dims(model)
    elems = live * z["Hm"] * z["hp"] * z["N"]
    moved = (2 * elems * state_bytes
             + live * (z["C"] + 2 * z["d_in"]) * act_bytes)
    return 6 * elems, moved


def ssd_scan(model, tokens, block=None, act_bytes=2):
    """The chunked scan of ONE Mamba-2 layer over ``tokens`` prompt
    tokens, in blocks of ``block`` (the published mamba_chunk_size):
    (flops, bytes). Inside a block: C.B^T (2 Q N a token), its product
    with the inputs (2 Q hp a head a token); across blocks: the block's
    state (2 hp N a head a token) and its readout (the same). The
    inputs are read and the outputs written once; the slot's state is
    read and written once a chunk, which is small beside them."""
    z = dims(model)
    Q = block or model.get("mamba_chunk_size", 256)
    per_token = (2 * Q * z["N"] / 2                       # C.B^T, causal
                 + z["Hm"] * 2 * Q * z["hp"] / 2          # (CB^T) x
                 + 2 * z["Hm"] * 2 * z["hp"] * z["N"])    # state in, out
    moved = tokens * (z["C"] + z["Hm"] + z["d_in"]) * act_bytes \
        + tokens * z["d_in"] * 4
    return tokens * per_token, moved


def decode_step(model, slots, live_slots, live_tokens, weight_bytes=2,
                kv_bytes=2, act_bytes=2, state_bytes=4):
    """One decode step with ``live_slots`` of ``slots`` decoding, their
    contexts holding ``live_tokens`` tokens together: (flops, bytes)."""
    z = dims(model)
    live = live_slots
    always = (z["Lm"] * mamba_params(z) + z["La"] * attn_params(z)
              + z["L"] * dense_moe_params(z) + z["D"] * z["V"])
    e_flops, e_bytes = moe_experts(model, live, weight_bytes, act_bytes)
    s_flops, s_bytes = ssm_update(model, live, state_bytes, act_bytes)
    kv = (live_tokens + live) * z["La"] * 2 * z["KV"] * z["hd"] * kv_bytes
    moved = (always * weight_bytes + z["L"] * e_bytes + z["Lm"] * s_bytes
             + kv + live * z["V"] * 4)
    flops = (2 * always * live + z["L"] * e_flops + z["Lm"] * s_flops
             + 4 * z["AH"] * z["hd"] * z["La"] * live_tokens)
    return flops, moved


# -- by name, for the layer metrics' readers ---------------------------
# shape: what the driver saw in the traced window ("slots"; the mean
# "live_slots" and "live_tokens" of a decode step; "tokens": the prompt
# tokens the engine counted there). A KERNELS entry gives the cost of
# that computation in ONE execution of its program (all its layers),
# or, where the shape holds "tokens", for that many prompt tokens.
PROGRAMS = {
    "decode_step": lambda model, shape: decode_step(
        model, shape["slots"], shape.get("live_slots", shape["slots"]),
        shape["live_tokens"]),
}


def _layers(n_key, fn):
    def cost(model, shape):
        flops, moved = fn(model, shape)
        n = dims(model)[n_key]
        return n * flops, n * moved
    return cost


KERNELS = {
    "moe_experts": _layers("L", lambda model, shape: moe_experts(
        model, shape.get("live_slots", shape["slots"]))),
    "ssm_update": _layers("Lm", lambda model, shape: ssm_update(
        model, shape.get("live_slots", shape["slots"]))),
    "ssd_scan": _layers("Lm", lambda model, shape: ssd_scan(
        model, shape["tokens"])),
}
