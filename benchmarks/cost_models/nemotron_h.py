"""Operations and bytes the Nemotron-H decoder needs, from its shapes
alone: the least a chip could do for the work (every byte across HBM
once, every operation of the algorithm, nothing recomputed, nothing
padded). A measured device time is held against ``least_seconds``; a
share over 100% means a count here is too high and is a bug to find.

Every layer is ONE half (``M`` Mamba-2, ``*`` attention, ``E``
experts), so each term below is counted over the layers of its letter.
What is counted per decode step, for ``live`` decoding slots:
- every weight that takes part for every token once: the Mamba-2 and
  attention projections, the routers, the shared MLPs, the untied head
  (the embedding is a gather of ``live`` rows);
- of the expert matrices only those of the experts TOUCHED in the step
  (a launch that visits the touched experts fetches no other): the
  number the PROGRAM counted on the device (``experts_touched``: held
  experts that got a token, a layer a decode step; counter
  ``experts_touched_held`` over ``expert_layer_steps``, handed over by
  ``reducers/roofline_counted.py``). Where no count is given (a prefill
  chunk, a hand calculation) routing is taken as uniform: with
  ``tokens`` tokens each choosing k of E experts a held expert is
  touched with probability 1 - (1 - k/E)^tokens; skewed routing touches
  FEWER, so against a launch that skips the untouched that expectation
  reads HIGH. An expert is TWO matrices (relu^2, not gated): two
  products a row;
- the recurrent state of the live slots read once and written once
  (the convolution's tail likewise), whatever the number of B/C
  groups: a group's B and C are 2 x N numbers a slot beside N x 512 of
  state; the live keys and values once;
- operations: 2 a weight a token, the held experts a token actually
  uses (k x held / E of them on average), the state update (6 a state
  element: decay, outer product, add, readout), attention over the
  live context.
"""
from benchmarks.cost_models.granite_hybrid import least_seconds  # noqa: F401


def dims(model):
    H, hp = model["mamba_num_heads"], model["mamba_head_dim"]
    G, N = model["n_groups"], model["ssm_state_size"]
    pattern = model["hybrid_override_pattern"][:model["num_hidden_layers"]]
    held = model["n_routed_experts"]
    return {"D": model["hidden_size"], "V": model["vocab_size"], "Hm": H,
            "hp": hp, "G": G, "N": N, "d_in": H * hp,
            "C": H * hp + 2 * G * N, "K": model["conv_kernel"],
            "AH": model["num_attention_heads"],
            "KV": model["num_key_value_heads"], "hd": model["head_dim"],
            "held": held, "E": model.get("num_experts") or held,
            "k": model["num_experts_per_tok"],
            "F": model["moe_intermediate_size"],
            "Fs": model["moe_shared_expert_intermediate_size"],
            "Q": model.get("chunk_size", 128),
            "Lm": pattern.count("M"), "La": pattern.count("*"),
            "Le": pattern.count("E")}


def mamba_params(z):
    """Matmul weights of one Mamba-2 mixer."""
    return z["D"] * (z["d_in"] + z["C"] + z["Hm"]) + z["d_in"] * z["D"]


def attn_params(z):
    return 2 * z["D"] * z["AH"] * z["hd"] + 2 * z["D"] * z["KV"] * z["hd"]


def dense_moe_params(z):
    """The router and the shared MLP of one expert layer."""
    return z["D"] * z["E"] + 2 * z["D"] * z["Fs"]


def expert_params(z):
    return 2 * z["D"] * z["F"]


def total_params(model):
    """Parameters held here (norms and the router's bias included)."""
    z = dims(model)
    return (z["Lm"] * (mamba_params(z) + z["D"] + z["d_in"] + 3 * z["Hm"]
                       + (z["K"] + 1) * z["C"])
            + z["La"] * (attn_params(z) + z["D"])
            + z["Le"] * (dense_moe_params(z) + z["held"] * expert_params(z)
                         + z["D"] + z["E"])
            + 2 * z["V"] * z["D"] + z["D"])


def touched(z, tokens):
    """Expected number of held experts that get a token, were routing
    uniform (it is not: see the module's text)."""
    return z["held"] * (1.0 - (1.0 - z["k"] / z["E"]) ** tokens)


def moe_experts(model, tokens, weight_bytes=2, act_bytes=2, fetched=None):
    """The expert launches (both grouped products) of ONE layer over
    ``tokens`` tokens: (flops, bytes). ``fetched``: the held experts
    that got a token, as the program counted them (None: the uniform
    expectation)."""
    z = dims(model)
    used = tokens * z["k"] * z["held"] / z["E"]       # rows computed
    flops = 2 * used * expert_params(z)
    hit = touched(z, tokens) if fetched is None else fetched
    moved = (hit * expert_params(z) * weight_bytes
             + used * (2 * z["D"] + 2 * z["F"]) * act_bytes)
    return flops, moved


def ssm_update(model, live, state_bytes=4, act_bytes=2):
    """The one-token state update of ONE Mamba-2 layer for ``live``
    slots, at any number of B/C groups: (flops, bytes). The state is
    read once and written once."""
    z = dims(model)
    elems = live * z["Hm"] * z["hp"] * z["N"]
    moved = (2 * elems * state_bytes
             + live * (z["C"] + 2 * z["d_in"]) * act_bytes)
    return 6 * elems, moved


def ssd_scan(model, tokens, block=None, act_bytes=2):
    """The chunked scan of ONE Mamba-2 layer over ``tokens`` prompt
    tokens, in blocks of ``block`` (the published chunk_size): (flops,
    bytes). Inside a block: C.B^T for each of the G groups (2 Q N a
    token a group), its product with the inputs (2 Q hp a head a
    token); across blocks: the block's state (2 hp N a head a token)
    and its readout (the same). The inputs are read and the outputs
    written once; the slot's state is read and written once a chunk,
    which is small beside them."""
    z = dims(model)
    Q = block or z["Q"]
    per_token = (z["G"] * 2 * Q * z["N"] / 2              # C.B^T, causal
                 + z["Hm"] * 2 * Q * z["hp"] / 2          # (CB^T) x
                 + 2 * z["Hm"] * 2 * z["hp"] * z["N"])    # state in, out
    moved = tokens * (z["C"] + z["Hm"] + z["d_in"]) * act_bytes \
        + tokens * z["d_in"] * 4
    return tokens * per_token, moved


def attention_launch(model, tokens, live, kv_bytes=2, act_bytes=2):
    """One ``paged_attention_decode`` launch over ``tokens`` live keys
    of ``live`` slots: (flops, bytes)."""
    z = dims(model)
    moved = (2 * tokens * z["KV"] * z["hd"] * kv_bytes
             + 2 * live * z["AH"] * z["hd"] * act_bytes)
    return 4 * z["AH"] * z["hd"] * tokens, moved


def paged_attention_decode(model, shape):
    """One launch of a decode step (every ``*`` layer's is alike)."""
    live = shape.get("live_slots", shape["slots"])
    return attention_launch(model, shape["live_tokens"] + live, live)


def decode_step(model, shape, weight_bytes=2, act_bytes=2, state_bytes=4):
    """One decode step with ``live_slots`` of ``slots`` decoding, their
    contexts holding ``live_tokens`` tokens together: (flops, bytes)."""
    z = dims(model)
    live = shape.get("live_slots", shape["slots"])
    always = (z["Lm"] * mamba_params(z) + z["La"] * attn_params(z)
              + z["Le"] * dense_moe_params(z) + z["D"] * z["V"])
    e_flops, e_bytes = moe_experts(model, live, weight_bytes, act_bytes,
                                   shape.get("experts_touched"))
    s_flops, s_bytes = ssm_update(model, live, state_bytes, act_bytes)
    a_flops, a_bytes = paged_attention_decode(model, shape)
    moved = (always * weight_bytes + z["Le"] * e_bytes + z["Lm"] * s_bytes
             + z["La"] * a_bytes + live * z["V"] * 4)
    flops = (2 * always * live + z["Le"] * e_flops + z["Lm"] * s_flops
             + z["La"] * a_flops)
    return flops, moved


def prefill_chunk(model, tokens, context=0, weight_bytes=2, act_bytes=2):
    """One chunk of ``tokens`` prompt tokens of a request that already
    holds ``context``: (flops, bytes). The weights once (a chunk of a
    few hundred tokens touches every held expert), the scan, causal
    attention over context + chunk, one row of logits."""
    z = dims(model)
    always = (z["Lm"] * mamba_params(z) + z["La"] * attn_params(z)
              + z["Le"] * dense_moe_params(z))
    e_flops, e_bytes = moe_experts(model, tokens, weight_bytes, act_bytes)
    s_flops, s_bytes = ssd_scan(model, tokens, act_bytes=act_bytes)
    keys = context + tokens / 2
    a_flops = 4 * z["AH"] * z["hd"] * tokens * keys
    a_bytes = 2 * (context + tokens) * z["KV"] * z["hd"] * act_bytes
    flops = (2 * always * tokens + z["Le"] * e_flops + z["Lm"] * s_flops
             + z["La"] * a_flops + 2 * z["D"] * z["V"])
    moved = ((always + z["D"] * z["V"]) * weight_bytes + z["Le"] * e_bytes
             + z["Lm"] * s_bytes + z["La"] * a_bytes)
    return flops, moved


# -- by name, for the layer metrics' readers ---------------------------
# shape: what the driver saw in the traced window ("slots"; the mean
# "live_slots" and "live_tokens" of a decode step; "tokens": the prompt
# tokens the engine counted there) and what the program counted
# ("experts_touched": held experts that got a token, a layer a decode
# step). A KERNELS entry gives the cost of
# that computation in ONE execution of its program (all its layers),
# or, where the shape holds "tokens", for that many prompt tokens;
# ``paged_attention_decode``: one launch.
PROGRAMS = {
    "decode_step": decode_step,
    "prefill_chunk": lambda model, shape: prefill_chunk(
        model, shape["tokens"], shape.get("context", 0)),
}


def _layers(n_key, fn):
    def cost(model, shape):
        flops, moved = fn(model, shape)
        n = dims(model)[n_key]
        return n * flops, n * moved
    return cost


def _live(shape):
    return shape.get("live_slots", shape["slots"])


KERNELS = {
    "moe_experts": _layers("Le", lambda model, shape: moe_experts(
        model, _live(shape), fetched=shape.get("experts_touched"))),
    "ssm_update": _layers("Lm", lambda model, shape: ssm_update(
        model, _live(shape))),
    "ssd_scan": _layers("Lm", lambda model, shape: ssd_scan(
        model, shape["tokens"])),
    "paged_attention_decode": paged_attention_decode,
}
