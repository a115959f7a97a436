"""The Granite-4.0-H family at a small size on the CPU, against the
benchmark's plain reference (``benchmarks/reference/granite_hybrid.py``:
float32, the Mamba-2 mixer as the step-by-step recurrence, nothing of
the program imported): the full-sequence forward, the chunked scan and
the one-token update against the recurrence, padding, the expert
layer's share and its droplessness.

Small size: hidden 64, 4 Mamba heads x 16, state 16, 8 experts top-3,
period "2 Mamba, 1 attention, 1 Mamba".
"""
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401 — x64 mode, as every caller has it
from paddle_tpu.inference import hybrid
from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.ops import mamba2
from paddle_tpu.ops.moe_experts import (expert_counts, gated_mlp,
                                        moe_experts, route)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.reference import granite_hybrid as ref  # noqa: E402

CFG = gh.GRANITE_HYBRID_TINY
F32 = jnp.float32


def model_of(cfg):
    """The reference's view of a program config: the published keys."""
    return {
        "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
        "intermediate_size": cfg.intermediate_size,
        "shared_intermediate_size": cfg.shared_intermediate_size,
        "num_hidden_layers": cfg.num_hidden_layers,
        "layer_types": list(cfg.layer_types),
        "num_attention_heads": cfg.num_attention_heads,
        "num_key_value_heads": cfg.num_key_value_heads,
        "num_local_experts": cfg.num_local_experts,
        "num_experts": cfg.num_experts,
        "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "mamba_n_heads": cfg.mamba_n_heads,
        "mamba_d_head": cfg.mamba_d_head,
        "mamba_d_state": cfg.mamba_d_state,
        "mamba_n_groups": cfg.mamba_n_groups,
        "mamba_d_conv": cfg.mamba_d_conv,
        "embedding_multiplier": cfg.embedding_multiplier,
        "residual_multiplier": cfg.residual_multiplier,
        "attention_multiplier": cfg.attention_multiplier,
        "logits_scaling": cfg.logits_scaling,
        "rms_norm_eps": cfg.rms_norm_eps}


@pytest.fixture(scope="module")
def params():
    return gh.init_params(CFG, jax.random.key(3))


def test_pattern_and_segments():
    assert CFG.pattern == ("mamba", "mamba", "attention", "mamba")
    assert CFG.segments() == [("mamba", 0, 2, 0), ("attention", 2, 1, 0),
                              ("mamba", 3, 1, 2)]
    assert (CFG.num_recurrent_layers, CFG.num_kv_layers) == (3, 1)
    full = gh.GraniteHybridConfig()          # the published pattern
    assert [i for i, t in enumerate(full.pattern) if t == "attention"] \
        == [5, 15, 25, 35]
    ten = gh.GraniteHybridConfig(num_hidden_layers=10)
    assert [(k, n) for k, _, n, _ in ten.segments()] \
        == [("mamba", 5), ("attention", 1), ("mamba", 4)]


def test_held_experts_must_lie_among_the_routed():
    with pytest.raises(ValueError, match="not among the 8"):
        gh.GraniteHybridConfig(num_local_experts=6, num_experts=8,
                               expert_offset=4)


@pytest.mark.parametrize("n", [21, 8, 40], ids=["pads", "one-block",
                                                "blocks"])
def test_forward_matches_the_reference(params, n):
    """(a) The program's full-sequence forward (chunked scan) against
    the reference (recurrence), logits, float32."""
    toks = np.random.default_rng(n).integers(0, CFG.vocab_size, n) \
        .astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(gh.forward(params, jnp.asarray(toks), CFG))
    want = np.asarray(ref.logits_at(params, model_of(CFG), toks,
                                    np.arange(n)))
    assert np.abs(want).max() > 1e-3
    # the layers decide the largest logit, not the tied embedding
    assert (want.argmax(-1) == toks).mean() < 0.5
    np.testing.assert_allclose(got, want, atol=2e-7)


def _scan_inputs(P, rng, heads=4, hp=16, groups=1, N=16):
    x = jnp.asarray(rng.normal(size=(P, heads, hp)), F32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.3, (P, heads)), F32)
    a = -jnp.asarray(rng.uniform(1, 16, (heads,)), F32)
    b = jnp.asarray(rng.normal(size=(P, groups, N)), F32)
    c = jnp.asarray(rng.normal(size=(P, groups, N)), F32)
    d = jnp.asarray(rng.normal(size=(heads,)), F32)
    s0 = jnp.asarray(rng.normal(size=(N, heads * hp)), F32)
    return x, dt, a, b, c, d, s0


def _recurrence(x, dt, a, b, c, d, s0):
    """Step by step, in numpy float64; state [N, H*hp]."""
    x, dt, a, b, c, d = (np.asarray(t, np.float64)
                         for t in (x, dt, a, b, c, d))
    P, H, hp = x.shape
    rep = H // b.shape[1]
    s = np.asarray(s0, np.float64).T.reshape(H, hp, -1).copy()
    ys = []
    for t in range(P):
        bt, ct = np.repeat(b[t], rep, 0), np.repeat(c[t], rep, 0)
        s = (np.exp(dt[t] * a)[:, None, None] * s
             + (dt[t][:, None] * x[t])[:, :, None] * bt[:, None, :])
        ys.append((s * ct[:, None, :]).sum(-1) + d[:, None] * x[t])
    return np.stack(ys), s.reshape(H * hp, -1).T


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("P,block", [(32, 8), (16, 16), (24, 256)])
def test_ssd_scan_is_the_recurrence(P, block, groups):
    args = _scan_inputs(P, np.random.default_rng(P + groups),
                        groups=groups)
    y, s = mamba2.ssd_scan(*args, block=block)
    want_y, want_s = _recurrence(*args)
    np.testing.assert_allclose(np.asarray(y), want_y, atol=2e-5)
    np.testing.assert_allclose(np.asarray(s), want_s, atol=2e-5)


def test_ssd_scan_refuses_a_ragged_blocking():
    args = _scan_inputs(12, np.random.default_rng(0))
    with pytest.raises(ValueError, match="do not divide"):
        mamba2.ssd_scan(*args, block=8)


@pytest.mark.parametrize("groups", [1, 2])
@pytest.mark.parametrize("state_dtype", ["float32", "bfloat16"])
def test_ssm_update_is_one_step_and_leaves_idle_slots(state_dtype,
                                                      groups):
    rng = np.random.default_rng(7)
    S, H, hp, N, Lm = 3, 4, 16, 16, 2
    x, dt, a, b, c, d, _ = _scan_inputs(S, rng, groups=groups)
    dt = dt.at[1].set(0.0)                       # slot 1 is not decoding
    pool = jnp.asarray(rng.normal(size=(Lm, S, N, H * hp)),
                       jnp.dtype(state_dtype))
    y, new = mamba2.ssm_update(x, dt, a, b, c, d, pool, jnp.int32(1))
    assert new.dtype == pool.dtype
    assert bool((new[0] == pool[0]).all())       # the other layer
    assert bool((new[1, 1] == pool[1, 1]).all())     # bit for bit
    for slot in (0, 2):
        one = tuple(t[slot:slot + 1] for t in (x, dt)) + (a,) + tuple(
            t[slot:slot + 1] for t in (b, c)) + (d,)
        _, want_s = _recurrence(*one, pool[1, slot].astype(F32))
        tol = 2e-6 if state_dtype == "float32" else 2e-2
        np.testing.assert_allclose(
            np.asarray(new[1, slot].astype(F32)), want_s, atol=tol)
        # y reads the state as it was stored
        stored = np.asarray(new[1, slot].astype(F32), np.float64)
        ct = np.repeat(np.asarray(c[slot], np.float64), H // groups, 0)
        want_y = ((stored.T.reshape(H, hp, N) * ct[:, None, :]).sum(-1)
                  + np.asarray(d)[:, None] * np.asarray(x[slot]))
        np.testing.assert_allclose(np.asarray(y[slot]), want_y, atol=1e-5)


def test_ssm_update_launch_matches_the_composition():
    """The Pallas launch (interpreted here) against the composition the
    CPU routes to, bit for bit in the stored state."""
    from paddle_tpu.ops.pallas.mamba2 import (slot_state_read,
                                              slot_state_write,
                                              ssm_update_pallas)
    rng = np.random.default_rng(9)
    S, H, hp, N, Lm = 3, 4, 32, 16, 2
    x, dt, a, b, c, d, _ = _scan_inputs(S, rng, hp=hp)
    pool = jnp.asarray(rng.normal(size=(Lm, S, N, H * hp)), F32)
    y0, p0 = mamba2.ssm_update(x, dt, a, b, c, d, pool, jnp.int32(1))
    decay = jnp.repeat(jnp.exp(dt * a[None]), hp, axis=1)
    xdt = (x * dt[..., None]).reshape(S, H * hp)
    y1, p1 = ssm_update_pallas(decay, xdt, b[:, 0], c[:, 0], pool,
                               jnp.int32(1))
    y1 = y1.reshape(S, H, hp) + x * d[None, :, None]
    np.testing.assert_allclose(np.asarray(p0), np.asarray(p1), atol=1e-6)
    assert bool((p1[0] == pool[0]).all())        # the other layer
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-5)
    got = slot_state_read(p1, 1, 2)
    assert bool((got == p1[1, 2]).all())
    back = slot_state_write(p1, 0, 1, got * 2)
    assert bool((back[0, 1] == got * 2).all())
    assert bool((back[1] == p1[1]).all()) \
        and bool((back[0, 0] == p1[0, 0]).all())


def test_conv_chunk_and_step_agree():
    rng = np.random.default_rng(2)
    P, C, K = 12, 24, 4
    x = jnp.asarray(rng.normal(size=(P, C)), F32)
    w = jnp.asarray(rng.normal(size=(K, C)), F32)
    bias = jnp.asarray(rng.normal(size=(C,)), F32)
    tail = jnp.asarray(rng.normal(size=(K - 1, C)), F32)
    y, new_tail = mamba2.causal_conv1d(x, w, bias, tail, P)
    t = tail[None]
    for i in range(P):                # one position at a time
        yi, t = mamba2.conv_update(x[i][None], w, bias, t,
                                   jnp.ones((1,), bool))
        np.testing.assert_allclose(np.asarray(yi[0]), np.asarray(y[i]),
                                   atol=1e-6)
    assert bool((t[0] == new_tail).all())
    _, kept = mamba2.conv_update(x[0][None], w, bias, tail[None],
                                 jnp.zeros((1,), bool))
    assert bool((kept[0] == tail).all())         # an idle slot's tail


def _engine_state(cfg, slots, seed):
    """State pools with something in them (a reused slot's leftovers)."""
    rng = np.random.default_rng(seed)
    st = hybrid.init_state(cfg, slots)
    return {**st, "ssm": jnp.asarray(rng.normal(size=st["ssm"].shape),
                                      st["ssm"].dtype),
            "conv": jnp.asarray(rng.normal(size=st["conv"].shape),
                                st["conv"].dtype)}


def test_padding_does_not_advance_the_recurrence():
    """(c) at the mixer: the same 8 positions alone and followed by 24
    rows of padding (any values, ``dt`` 0, the tail taken at the last
    real row) leave the scan's state and the convolution's tail
    identical, bit for bit."""
    rng = np.random.default_rng(3)
    n, P, C, K = 8, 32, 24, 4
    x, dt, a, b, c, d, s0 = _scan_inputs(P, rng)
    dt = dt.at[n:].set(0.0)
    scan = jax.jit(lambda x, dt, b, c: mamba2.ssd_scan(
        x, dt, a, b, c, d, s0, block=8))
    y_short, s_short = scan(x[:n], dt[:n], b[:n], c[:n])
    y_long, s_long = scan(x, dt, b, c)
    assert float(jnp.abs(s_short - s0).max()) > 1e-3     # it did advance
    assert bool((s_short == s_long).all())
    assert bool((y_short == y_long[:n]).all())
    xc = jnp.asarray(rng.normal(size=(P, C)), F32)
    w = jnp.asarray(rng.normal(size=(K, C)), F32)
    tail = jnp.asarray(rng.normal(size=(K - 1, C)), F32)
    conv = jax.jit(lambda xc, n: mamba2.causal_conv1d(
        xc, w, jnp.zeros((C,)), tail, n))
    y_short, t_short = conv(xc[:n], n)
    y_long, t_long = conv(xc, n)
    assert bool((t_short == t_long).all())
    assert bool((t_short == xc[n - K + 1:n]).all())
    assert bool((y_short == y_long[:n]).all())
    assert bool((conv(xc, 0)[1] == tail).all())          # nothing real


def test_padded_and_unpadded_chunk_leave_identical_state(params):
    """(c) at the chunk program: a chunk of 8 real tokens padded to the
    bucket of 32 leaves the slot's recurrent state as the unpadded
    chunk of 8 does: bit for bit in the first Mamba layer (whose inputs
    are the same bits), and to the last bit or two further down, where
    XLA's matrix products over 8 and over 32 rows round a row
    differently; the same keys, values and logits."""
    n, slot = 8, 1
    toks = np.random.default_rng(4).integers(0, CFG.vocab_size, n) \
        .astype(np.int32)
    L, KV, hd = CFG.num_kv_layers, CFG.num_key_value_heads, CFG.head_dim
    table = jnp.arange(1, 7, dtype=jnp.int32)           # 6 pages of 8
    outs = []
    for P in (8, 32):
        padded = np.zeros(P, np.int32)
        padded[:n] = toks
        pools = [jnp.zeros((L, 8, 8, KV, hd), F32) for _ in range(2)]
        state = hybrid.reset_slot(_engine_state(CFG, 3, seed=1), slot)
        outs.append(jax.jit(
            lambda t, kp, vp, st: hybrid.prefill_chunk(
                params, t, CFG, kp, vp, table, table, 0, n, slot, st))(
            jnp.asarray(padded), *pools, state))
    (lg0, k0, v0, s0), (lg1, k1, v1, s1) = outs
    assert float(jnp.abs(s0["ssm"][:, slot]).max()) > 1e-3
    for key in ("ssm", "conv"):
        assert bool((s0[key][0] == s1[key][0]).all()), key
        np.testing.assert_allclose(np.asarray(s0[key]),
                                   np.asarray(s1[key]), atol=1e-6)
    # page 0 is the scratch page: the padding's keys went there only
    for a, b in ((k0[:, 1:], k1[:, 1:]), (v0[:, 1:], v1[:, 1:]),
                 (lg0, lg1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert float(jnp.abs(k1[:, 1]).max()) > 0 \
        and float(jnp.abs(k1[:, 2:]).max()) == 0.0
    # and the other slots were not touched
    before = _engine_state(CFG, 3, seed=1)
    for key in ("ssm", "conv"):
        assert bool((s1[key][:, 0] == before[key][:, 0]).all())
        assert bool((s1[key][:, 2] == before[key][:, 2]).all())


def test_chunks_and_decode_steps_match_the_reference(params):
    """(b) at the programs' level: a prompt of 20 through chunks of 8
    (three chunks, the last padded) and one of 5 (shorter than a
    bucket), then decode steps for both slots side by side: the logits
    at EVERY served position against the reference's full forward."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
               for n in (20, 5)]
    steps, BS, P = 6, 8, 8
    L, KV, hd = CFG.num_kv_layers, CFG.num_key_value_heads, CFG.head_dim
    kp, vp = (jnp.zeros((L, 16, BS, KV, hd), F32) for _ in range(2))
    state = _engine_state(CFG, 2, seed=5)
    tables = np.zeros((2, 6), np.int32)
    tables[0, :4], tables[1, :2] = [1, 2, 3, 4], [5, 6]
    chunk = jax.jit(lambda t, kp, vp, table, pos0, n, slot, st:
                    hybrid.prefill_chunk(params, t, CFG, kp, vp, table,
                                         table, pos0, n, slot, st))
    served = [[], []]            # (position, logits) per request
    for slot, prompt in enumerate(prompts):
        state = hybrid.reset_slot(state, slot)
        for pos0 in range(0, prompt.size, P):
            n = min(P, prompt.size - pos0)
            t = np.zeros(P, np.int32)
            t[:n] = prompt[pos0:pos0 + n]
            lg, kp, vp, state = chunk(jnp.asarray(t), kp, vp,
                                      jnp.asarray(tables[slot]), pos0, n,
                                      slot, state)
        served[slot].append(np.asarray(lg[0]))
    seqs = [list(p) for p in prompts]
    step = jax.jit(lambda tok, kp, vp, seq, st: hybrid.decode_step(
        params, tok, CFG, kp, vp, jnp.asarray(tables), seq, st))
    for _ in range(steps):
        tok = [int(served[s][-1].argmax()) for s in range(2)]
        seq = np.array([len(s) for s in seqs], np.int32)
        for s in range(2):
            seqs[s].append(tok[s])
        lg, kp, vp, state = step(jnp.asarray(tok, jnp.int32), kp, vp,
                                 jnp.asarray(seq), state)
        for s in range(2):
            served[s].append(np.asarray(lg[s]))
    model = model_of(CFG)
    for s, prompt in enumerate(prompts):
        full = np.asarray(seqs[s], np.int32)
        rows = np.arange(prompt.size - 1, full.size)
        want = np.asarray(ref.logits_at(params, model, full, rows))
        got = np.stack(served[s])
        assert got.shape == want.shape == (steps + 1, CFG.vocab_size)
        np.testing.assert_allclose(got, want, atol=3e-6)
    # the counts the decode steps summed: two live slots, every layer
    assert int(state["stats"][0]) == steps * 2 * 3 * CFG.num_hidden_layers


# -- the expert layer ------------------------------------------------------
def _layer(rng, T=12, D=64, E=8, F=32, Fs=48):
    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.1, F32)
    return (w(T, D) * 10, {"router": w(D, E), "w_in": w(E, D, 2 * F),
                           "w_out": w(E, F, D),
                           "shared_in": w(D, 2 * Fs),
                           "shared_out": w(Fs, D)})


def _reference_layer(u, w, held, offset, k=3):
    sz = {"F": w["w_out"].shape[1], "k": k, "held": held,
          "offset": offset}
    part = {**w, "w_in": w["w_in"][offset:offset + held],
            "w_out": w["w_out"][offset:offset + held]}
    return np.asarray(ref.experts_and_shared(u, part, sz))


def test_the_shares_add_up_to_the_whole_layer():
    """(d) Two chips each hold half of a layer's experts. What the two
    halves compute, with the shared MLP (which every chip computes
    alike) counted once, is the uncut reference layer; and each half is
    the reference's half."""
    u, w = _layer(np.random.default_rng(0))
    gates, experts = route(u, w["router"], 3)
    shared = gated_mlp(u, w["shared_in"], w["shared_out"])
    halves = []
    for offset in (0, 4):
        part = moe_experts(u, gates, experts, w["w_in"][offset:offset + 4],
                           w["w_out"][offset:offset + 4], offset=offset)
        halves.append(part)
        np.testing.assert_allclose(
            np.asarray(part + shared),
            _reference_layer(u, w, 4, offset), atol=2e-5)
    whole = _reference_layer(u, w, 8, 0)
    assert np.abs(np.asarray(halves[0])).max() > 1e-3
    np.testing.assert_allclose(np.asarray(halves[0] + halves[1] + shared),
                               whole, atol=3e-5)
    # with every expert held it is the whole layer in one call
    np.testing.assert_allclose(
        np.asarray(moe_experts(u, gates, experts, w["w_in"], w["w_out"])
                   + shared), whole, atol=3e-5)


def test_a_layer_addressed_in_the_stack_is_that_layer():
    rng = np.random.default_rng(1)
    u, w = _layer(rng)
    gates, experts = route(u, w["router"], 3)
    stack_in = jnp.stack([w["w_in"] * 0 + 7, w["w_in"], w["w_in"] * 3])
    stack_out = jnp.stack([w["w_out"] * 0 + 7, w["w_out"], w["w_out"]])
    one = moe_experts(u, gates, experts, w["w_in"], w["w_out"])
    got = jax.jit(lambda l: moe_experts(u, gates, experts, stack_in,
                                        stack_out, layer=l))(jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(one), atol=1e-6)


def test_no_token_is_dropped_when_all_choose_one_expert():
    """(e) Dropless: every token of a batch routed to the same experts
    (a capacity bucket would drop all but a few) gets its full sum."""
    rng = np.random.default_rng(2)
    u, w = _layer(rng, T=40)
    u = jnp.abs(u)                    # so that one column can dominate
    router = w["router"].at[:, 5].set(3.0).at[:, 2].set(2.0) \
        .at[:, 6].set(1.0)
    gates, experts = route(u, router, 3)
    assert bool((experts == jnp.asarray([5, 2, 6])).all())
    got = moe_experts(u, gates, experts, w["w_in"], w["w_out"])
    want = _reference_layer(u, {**w, "router": router}, 8, 0) \
        - np.asarray(gated_mlp(u, w["shared_in"], w["shared_out"]))
    assert (np.abs(want).max(axis=1) > 1e-4).all()     # every token
    np.testing.assert_allclose(np.asarray(got), want, atol=3e-5)
    counts = expert_counts(experts, jnp.ones((40,), bool), 8, 4, 4)
    assert [int(v) for v in counts] == [120, 80, 40, 2]   # 5, 6 held


def test_expert_counts_skip_idle_rows():
    experts = jnp.asarray([[0, 1, 2], [0, 4, 5], [0, 6, 7]], jnp.int32)
    live = jnp.asarray([True, False, True])
    assert [int(v) for v in expert_counts(experts, live, 8, 4, 0)] \
        == [6, 4, 2, 3]     # experts 0, 1, 2 of the held four got one
