"""Operations and bytes a dense decoder needs, from its shapes alone.

The least a chip could do for the work: every byte that has to cross
HBM once, every floating-point operation of the algorithm, nothing
recomputed and nothing padded. A measured device time is held against
``least_seconds``; a share over 100% means a count here is too high (or
the time leaves out part of the work) and is a bug to find, not to clip.
"""


def dims(model):
    D, F, V = (model["hidden_size"], model["intermediate_size"],
               model["vocab_size"])
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or D // H
    return D, F, V, H, KV, hd, model["num_hidden_layers"]


def layer_matmul_params(model):
    D, F, _, H, KV, hd, _ = dims(model)
    return D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F


def matmul_params(model):
    """Weights that take part in a matrix product for every token: the
    layers and the output head; the embedding is a row lookup."""
    D, _, V, *_, L = dims(model)
    return L * layer_matmul_params(model) + D * V


def least_seconds(flops, bytes_, peak):
    """(seconds, which bound) on a chip of ``peak`` = {"flops_per_s",
    "bytes_per_s"}."""
    t_f = flops / peak["flops_per_s"]
    t_b = bytes_ / peak["bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")


def decode_step(model, slots, live_tokens, weight_bytes=2, kv_bytes=2,
                act_bytes=2):
    """One decode step over ``slots`` sequences whose contexts hold
    ``live_tokens`` tokens together: (flops, bytes).

    Bytes: every matmul weight and norm once, one embedding row a slot,
    the live keys and values once, the new keys and values written, the
    logits written in float32. FLOPs: 2 a weight a slot, and for each
    slot 4 x heads x head size a context token a layer (scores and
    weighted sum)."""
    D, F, V, H, KV, hd, L = dims(model)
    w = matmul_params(model) * weight_bytes + (2 * L + 1) * D * 4
    kv_read = live_tokens * L * 2 * KV * hd * kv_bytes
    kv_write = slots * L * 2 * KV * hd * kv_bytes
    io = slots * D * act_bytes + slots * V * 4
    flops = 2 * matmul_params(model) * slots + 4 * H * hd * L * live_tokens
    return flops, w + kv_read + kv_write + io


def decode_mlp_block(model, slots, weight_bytes=2, act_bytes=2):
    """One launch of the fused decode MLP (norm, gate, up, SwiGLU, down,
    residual) for ``slots`` rows: (flops, bytes)."""
    D, F, *_ = dims(model)
    return (6 * slots * D * F,
            3 * D * F * weight_bytes + D * 4 + 2 * slots * D * act_bytes)


#: matrix products of S x S x head size that one launch has to make,
#: given what it is handed: fwd (scores, weighted sum); dq (scores
#: again, dP, dQ); dkv (scores again, dP, dV, dK)
FLASH_PRODUCTS = {"flash_attention_fwd": 2, "flash_attention_bwd_dq": 3,
                  "flash_attention_bwd_dkv": 4}


def flash_attention(model, kernel, batch, seq, act_bytes=2):
    """One causal launch of ``kernel`` over [batch, seq]: (flops, bytes).
    Causal: half of each S x S product."""
    D, F, V, H, KV, hd, L = dims(model)
    flops = FLASH_PRODUCTS[kernel] * 2 * batch * H * seq * seq * hd / 2
    q = batch * seq * H * hd * act_bytes
    kv = batch * seq * KV * hd * act_bytes
    moved = {"flash_attention_fwd": 2 * q + 2 * kv,          # q,k,v -> o
             "flash_attention_bwd_dq": 3 * q + 2 * kv,       # q,k,v,do -> dq
             "flash_attention_bwd_dkv": 2 * q + 4 * kv}[kernel]
    return flops, moved


def train_flops_per_token(model, seq):
    """Forward and backward of one token in a causal sequence of ``seq``:
    6 a matmul weight, and 3 x (2 products x 2 x heads x head size x
    seq / 2) a layer for attention. Recomputation is not counted."""
    D, F, V, H, KV, hd, L = dims(model)
    return 6 * matmul_params(model) + 3 * 2 * H * hd * seq * L


def train_step(model, batch, seq):
    """(flops, tokens) of one optimizer step."""
    return train_flops_per_token(model, seq) * batch * seq, batch * seq


def paged_attention_decode(model, slots, live_tokens, kv_bytes=2,
                           act_bytes=2):
    """One launch (one layer) of decode attention over paged keys and
    values holding ``live_tokens`` tokens: (flops, bytes)."""
    D, F, V, H, KV, hd, L = dims(model)
    return (4 * H * hd * live_tokens,
            live_tokens * 2 * KV * hd * kv_bytes
            + 2 * slots * H * hd * act_bytes)


# -- by name, for the layer metrics' readers ---------------------------
# shape: what the driver saw in the traced window ("slots", the mean
# "live_tokens" of a decode step; "batch" and "seq" of a train step)
PROGRAMS = {
    "decode_step": lambda model, shape: decode_step(
        model, shape["slots"], shape["live_tokens"]),
}
KERNELS = {
    "decode_mlp_block": lambda model, shape: decode_mlp_block(
        model, shape["slots"]),
    "paged_attention_decode": lambda model, shape: paged_attention_decode(
        model, shape["slots"], shape["live_tokens"]),
    **{k: (lambda model, shape, k=k: flash_attention(
        model, k, shape["batch"], shape["seq"])) for k in FLASH_PRODUCTS},
}
