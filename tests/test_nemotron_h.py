"""The Nemotron-H family at a small size on the CPU, against the
benchmark's plain reference (``benchmarks/reference/nemotron_h.py``:
float32, the Mamba-2 mixer as the step-by-step recurrence, the route
written out, nothing of the program imported): a layer that is ONE
half, the loops a pattern is run as, the full-sequence forward, the two
serving programs, the sigmoid route, the norm over each B/C group, the
state update for several groups, and the expert layer's share.

Small size: hidden 48, 8 Mamba heads x 16 in 4 B/C groups, state 16, 8
experts top-3 of width 24 (stored 128 wide), pattern "MEMEM*EME".
"""
import dataclasses
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401 — x64 mode, as every caller has it
from paddle_tpu.inference import hybrid
from paddle_tpu.models import granite_hybrid as gh
from paddle_tpu.models import mellum
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.models import pattern as pt
from paddle_tpu.ops import mamba2
from paddle_tpu.ops.moe_experts import (ExpertHalf, expert_counts, mlp,
                                        moe_experts, route)
from paddle_tpu.ops.pallas.mamba2 import group_blocks, ssm_update_pallas

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.reference import nemotron_h as ref  # noqa: E402

CFG = nh.NEMOTRON_H_TINY
F32 = jnp.float32
_KEYS = ("hidden_size", "vocab_size", "num_hidden_layers",
         "hybrid_override_pattern", "num_attention_heads",
         "num_key_value_heads", "head_dim", "mamba_num_heads",
         "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
         "n_routed_experts", "num_experts", "expert_offset",
         "num_experts_per_tok", "moe_intermediate_size",
         "moe_shared_expert_intermediate_size", "routed_scaling_factor",
         "layer_norm_epsilon")


def model_of(cfg):
    """The reference's view of a program config: the published keys."""
    return {k: getattr(cfg, k) for k in _KEYS}


@pytest.fixture(scope="module")
def params():
    return nh.init_params(CFG, jax.random.key(3))


# -- a layer's halves, and the loops of a pattern --------------------------
def test_every_layer_is_one_half():
    assert CFG.pattern == tuple("MEMEM*EME")
    halves = [(CFG.kinds[n].mixer, CFG.kinds[n].experts)
              for n in CFG.pattern]
    assert all((m is None) == e for m, e in halves)
    assert (CFG.num_recurrent_layers, CFG.num_kv_layers,
            CFG.num_expert_layers) == (4, 1, 4)
    sp = hybrid.served_pattern(CFG)
    assert (sp.recurrent_layers, sp.window_layers, sp.expert_layers) \
        == (4, 0, 4)
    # granite's and Mellum 2's layers are both halves
    for cfg in (gh.GRANITE_HYBRID_TINY, mellum.MELLUM_TINY):
        assert all(k.mixer and k.experts for k in cfg.kinds.values())
        assert hybrid.served_pattern(cfg).expert_layers \
            == cfg.num_hidden_layers
    with pytest.raises(ValueError, match="no half"):
        pt.LayerKind("x", None, None, experts=False)
    with pytest.raises(ValueError, match="dense MLP half"):
        nh.NemotronHConfig(hybrid_override_pattern="ME-", num_hidden_layers=3)


def test_the_pattern_runs_as_repeats_of_a_unit():
    """Units of two layers: "MEMEM*EMEMEM*EME" is (ME) x 2, M, *, (EM)
    x 3, *, E, M, E: nine loops, ten compiled layers, where runs of
    equal layers would be sixteen loops of one (which compile 2.4 times
    as long: PERF.md, PR 43). The unit's width is no option."""
    cfg = nh.NemotronHConfig(num_hidden_layers=16)
    got = [(r.first, r.repeats, "".join(m.kind.name for m in r.members))
           for r in pt.runs(cfg)]
    assert got == [(0, 2, "ME"), (4, 1, "M"), (5, 1, "*"), (6, 3, "EM"),
                   (12, 1, "*"), (13, 1, "E"), (14, 1, "M"), (15, 1, "E")]
    # every layer once, each half at its own place in its stack
    seen = {"M": [], "*": [], "E": []}
    for r in pt.runs(cfg):
        for i in range(r.repeats):
            for m in r.members:
                k, e = m.at(i)
                seen[m.kind.name].append(e if m.kind.name == "E" else k)
    assert seen == {"M": list(range(7)), "*": [0, 1], "E": list(range(7))}
    assert len(cfg.segments()) == 16
    assert not hasattr(cfg, "run_unit")


def test_runs_of_the_other_families_are_their_segments():
    """Equal neighbours come first: granite and Mellum 2 run as their
    runs of equal layers, and a layer with both halves reads ``moe`` at
    its own number."""
    for cfg in (gh.GRANITE_HYBRID_TINY, mellum.MELLUM_TINY,
                gh.GraniteHybridConfig(num_hidden_layers=10),
                mellum.MellumConfig()):
        got = [(m.kind.name, r.first, r.repeats, m.k0)
               for r in pt.runs(cfg) for m in r.members]
        assert got == cfg.segments()
        assert all(m.e0 == r.first and (m.dk, m.de) == (1, 1)
                   for r in pt.runs(cfg) for m in r.members)


# -- the equations -----------------------------------------------------------
@pytest.mark.parametrize("n", [21, 8, 40], ids=["pads", "one-block",
                                                "blocks"])
def test_forward_matches_the_reference(params, n):
    """The program's full-sequence forward (chunked scan, grouped
    products) against the reference (recurrence, expert by expert),
    logits, float32: 2e-6 is float32 rounding through nine layers."""
    toks = np.random.default_rng(n).integers(0, CFG.vocab_size, n) \
        .astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(nh.forward(params, jnp.asarray(toks), CFG))
    want = np.asarray(ref.logits_at(params, model_of(CFG), toks,
                                    np.arange(n)))
    assert np.abs(want).max() > 1e-2
    assert (want.argmax(-1) == toks).mean() < 0.5
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_route_is_the_references_choice_on_ties_and_under_a_bias():
    """Sigmoid scores in float32; the choice is of score + bias, the
    gates are the scores alone, over their sum, times the scaling. On
    ties both take the first of equals."""
    rng = np.random.default_rng(0)
    T, D, E, k = 24, 32, 16, 4
    u = jnp.asarray(rng.normal(size=(T, D)), F32)
    w = jnp.asarray(rng.normal(size=(D, E)) * 0.3, F32)
    # columns 3, 4, 9 equal: ties in every row
    w = w.at[:, 4].set(w[:, 3]).at[:, 9].set(w[:, 3])
    bias = jnp.asarray(rng.normal(size=(E,)) * 0.5, F32)
    with jax.default_matmul_precision("highest"):
        for b in (None, bias, jnp.zeros((E,), F32)):
            gates, experts = route(u, w, k, "sigmoid", b, 2.5)
            scores = jax.nn.sigmoid(ref._mm(u, w))
            want_g, want_e = ref.choose(
                scores, jnp.zeros((E,), F32) if b is None else b, k)
            assert bool((experts == want_e).all())
            np.testing.assert_allclose(np.asarray(gates),
                                       np.asarray(want_g) * 2.5, rtol=1e-6)
            np.testing.assert_allclose(np.asarray(gates.sum(-1)), 2.5,
                                       rtol=1e-6)
        plain, _ = route(u, w, k, "sigmoid", None, 1.0)
        chosen_plain = route(u, w, k, "sigmoid", None, 2.5)[1]
        chosen_bias = route(u, w, k, "sigmoid", bias, 2.5)[1]
    # the bias changes the choice, and is no part of a gate
    assert float((chosen_plain != chosen_bias).mean()) > 0.2
    g_bias, e_bias = route(u, w, k, "sigmoid", bias, 1.0)
    s = np.asarray(jax.nn.sigmoid(u @ w))
    top = np.take_along_axis(s, np.asarray(e_bias), -1)
    np.testing.assert_allclose(np.asarray(g_bias),
                               top / top.sum(-1, keepdims=True), rtol=1e-5)
    assert np.asarray(plain).shape == (T, k)
    # the softmax route is what it was
    g, e = route(u, w, k)
    lg = np.asarray(u @ w)
    assert bool((np.sort(np.asarray(e), -1)
                 == np.sort(np.argsort(-lg, -1, kind="stable")[:, :k],
                            -1)).all())
    np.testing.assert_allclose(np.asarray(g.sum(-1)), 1.0, rtol=1e-6)


def test_expert_half_reads_what_the_config_says():
    with pytest.raises(ValueError, match="scoring"):
        ExpertHalf("tanh")
    with pytest.raises(ValueError, match="activation"):
        ExpertHalf(act="gelu")
    assert CFG.expert_half == ExpertHalf("sigmoid", 2.5, "relu2")
    for key, bad in (("n_group", 2), ("topk_group", 2),
                     ("norm_topk_prob", False), ("mlp_hidden_act", "silu")):
        with pytest.raises(ValueError, match=key):
            dataclasses.replace(CFG, **{key: bad})
    with pytest.raises(ValueError, match="not among the 8"):
        dataclasses.replace(CFG, n_routed_experts=6, num_experts=8,
                            expert_offset=4)


def test_gated_norm_is_over_each_group():
    """``mamba_out``'s RMS norm is taken over each B/C group's
    channels, not over all of d_inner (which is what one group is)."""
    rng = np.random.default_rng(1)
    T, d_in, G = 5, CFG.mamba_d_inner, CFG.n_groups
    lp = {"norm": jnp.asarray(rng.uniform(0.5, 1.5, d_in), F32),
          "out_proj": jnp.eye(d_in, CFG.hidden_size, dtype=F32)}
    y = jnp.asarray(rng.normal(size=(T, CFG.mamba_num_heads,
                                     CFG.mamba_head_dim)), F32)
    # one group far larger than the others: a norm over all of d_inner
    # would flatten the others
    y = y.at[:, :CFG.mamba_num_heads // G].multiply(100.0)
    z = jnp.asarray(rng.normal(size=(T, d_in)), F32)
    x = jnp.zeros((T, CFG.hidden_size), F32)
    got = np.asarray(pt.mamba_out(lp, x, y, z, CFG))
    g = np.asarray(y).reshape(T, d_in) * np.asarray(jax.nn.silu(z))
    g = g.reshape(T, G, d_in // G)
    want = (g / np.sqrt((g ** 2).mean(-1, keepdims=True)
                        + CFG.rms_norm_eps)).reshape(T, d_in) \
        * np.asarray(lp["norm"])
    np.testing.assert_allclose(got, want[:, :CFG.hidden_size], rtol=2e-5,
                               atol=1e-6)
    whole = g.reshape(T, d_in) / np.sqrt(
        (g.reshape(T, d_in) ** 2).mean(-1, keepdims=True))
    assert np.abs(whole[:, d_in // G:]).max() < 0.5      # flattened
    assert np.abs(want[:, d_in // G:]).max() > 1.0


# -- the state update for several B/C groups -------------------------------
def _inputs(S, rng, H=8, hp=16, G=4, N=16):
    x = jnp.asarray(rng.normal(size=(S, H, hp)), F32)
    dt = jnp.asarray(rng.uniform(1e-3, 0.3, (S, H)), F32)
    a = -jnp.asarray(rng.uniform(0.1, 1, (H,)), F32)
    b = jnp.asarray(rng.normal(size=(S, G, N)), F32)
    c = jnp.asarray(rng.normal(size=(S, G, N)), F32)
    d = jnp.asarray(rng.normal(size=(H,)), F32)
    return x, dt, a, b, c, d


def test_ssm_update_at_eight_groups_is_one_position_of_the_scan():
    """One token of every slot through ``ssm_update`` is one position
    through ``ssd_scan`` from the same state; a slot that is not
    decoding (dt 0) keeps its state bit for bit."""
    rng = np.random.default_rng(2)
    S, H, hp, G, N, Lm = 3, 16, 16, 8, 16, 2
    x, dt, a, b, c, d = _inputs(S, rng, H, hp, G, N)
    dt = dt.at[1].set(0.0)
    pool = jnp.asarray(rng.normal(size=(Lm, S, N, H * hp)), F32)
    y, new = mamba2.ssm_update(x, dt, a, b, c, d, pool, jnp.int32(1))
    assert bool((new[0] == pool[0]).all())
    assert bool((new[1, 1] == pool[1, 1]).all())
    for s in (0, 2):
        want_y, want_s = mamba2.ssd_scan(x[s:s + 1], dt[s:s + 1], a,
                                         b[s:s + 1], c[s:s + 1], d,
                                         pool[1, s], block=1)
        np.testing.assert_allclose(np.asarray(new[1, s]),
                                   np.asarray(want_s), atol=2e-6)
        np.testing.assert_allclose(np.asarray(y[s]), np.asarray(want_y[0]),
                                   atol=1e-5)


@pytest.mark.parametrize("H,hp,G,blocks", [
    (16, 64, 8, (1024, 8, 1)),      # the whole row one block of 8 groups
    (64, 64, 8, (2048, 4, 1)),      # the published mixer: 4 groups a block
    (64, 64, 1, (2048, 1, 2)),      # one group over two blocks
    (96, 64, 2, (1536, 1, 2)),      # a group of 3072 lanes: two blocks
], ids=["8-in-1", "published", "one-group", "group-spans-blocks"])
def test_grouped_launch_matches_the_composition(H, hp, G, blocks):
    """The Pallas launch (interpreted here) against the composition the
    CPU routes to: the stored state bit for bit close, the other layer
    and an idle slot untouched."""
    assert group_blocks(H * hp, G) == blocks
    rng = np.random.default_rng(H + G)
    S, N, Lm = 3, 16, 2
    x, dt, a, b, c, d = _inputs(S, rng, H, hp, G, N)
    dt = dt.at[2].set(0.0)
    pool = jnp.asarray(rng.normal(size=(Lm, S, N, H * hp)), F32)
    y0, p0 = mamba2.ssm_update(x, dt, a, b, c, d, pool, jnp.int32(1))
    decay = jnp.repeat(jnp.exp(dt * a[None]), hp, axis=1)
    xdt = (x * dt[..., None]).reshape(S, H * hp)
    y1, p1 = ssm_update_pallas(decay, xdt, b, c, pool, jnp.int32(1))
    y1 = y1.reshape(S, H, hp) + x * d[None, :, None]
    np.testing.assert_allclose(np.asarray(p0), np.asarray(p1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-5)
    assert bool((p1[0] == pool[0]).all())
    assert bool((p1[1, 2] == pool[1, 2]).all())          # bit for bit


def test_groups_of_no_whole_lanes_take_the_composition():
    assert group_blocks(8 * 16, 4) is None          # 32 lanes a group
    assert group_blocks(4096, 8) == (2048, 4, 1)
    assert group_blocks(8192, 1) == (2048, 1, 4)    # granite's blocks


# -- the two serving programs ----------------------------------------------
def _engine_state(cfg, slots, seed):
    rng = np.random.default_rng(seed)
    st = hybrid.init_state(cfg, slots)
    return {**st, "ssm": jnp.asarray(rng.normal(size=st["ssm"].shape),
                                      st["ssm"].dtype),
            "conv": jnp.asarray(rng.normal(size=st["conv"].shape),
                                st["conv"].dtype)}


def test_chunks_and_decode_steps_match_the_reference(params):
    """A prompt of 20 through chunks of 8 (three chunks, the last
    padded) and one of 5, then decode steps for both slots side by side
    with a third slot idle: the LOGITS at every served position against
    the reference's full forward (3e-6: float32 rounding; the chunked
    scan against the recurrence), and the idle slot's state bit for
    bit."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
               for n in (20, 5)]
    steps, BS, P = 6, 8, 8
    L, KV, hd = CFG.num_kv_layers, CFG.num_key_value_heads, CFG.head_dim
    kp, vp = (jnp.zeros((L, 16, BS, KV, hd), F32) for _ in range(2))
    state = _engine_state(CFG, 3, seed=5)
    idle = {k: np.asarray(state[k][:, 2]) for k in ("ssm", "conv")}
    tables = np.zeros((3, 6), np.int32)
    tables[0, :4], tables[1, :2] = [1, 2, 3, 4], [5, 6]
    chunk = jax.jit(lambda t, kp, vp, table, pos0, n, slot, st:
                    hybrid.prefill_chunk(params, t, CFG, kp, vp, table,
                                         table, pos0, n, slot, st))
    served = [[], []]
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
        tok = [int(served[s][-1].argmax()) for s in range(2)] + [0]
        seq = np.array([len(s) for s in seqs] + [0], np.int32)
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
    for k in ("ssm", "conv"):
        assert bool((np.asarray(state[k][:, 2]) == idle[k]).all()), k
    # the counts the decode steps summed: two live slots, 3 choices in
    # each of the four EXPERT layers (not in all nine)
    assert int(state["stats"][0]) == steps * 2 * 3 * CFG.num_expert_layers


# -- the expert layer's share ------------------------------------------------
def _layer(rng, T=12, D=48, E=8, F=24, Fs=40):
    def w(*shape):
        return jnp.asarray(rng.normal(size=shape) * 0.1, F32)
    return (w(T, D) * 10, {
        "router": w(D, E), "router_bias": w(E) * 0.5,
        # the first matrix stored wider than the expert (zero columns)
        "w_in": jnp.pad(w(E, D, F), ((0, 0), (0, 0), (0, 128 - F))),
        "w_out": w(E, F, D), "shared_in": w(D, Fs),
        "shared_out": w(Fs, D)})


def _reference_layer(u, w, held, offset, k=3):
    sz = {"k": k, "held": held, "offset": offset, "scale": 2.5}
    part = {**w, "w_in": w["w_in"][offset:offset + held],
            "w_out": w["w_out"][offset:offset + held]}
    return np.asarray(ref.experts_and_shared(u, part, sz))


def test_the_shares_add_up_to_the_whole_layer():
    """THE SHARE TEST. Two chips each hold half of a layer's experts.
    What the two halves compute, with the shared MLP (which every chip
    computes alike) counted once, is the uncut reference layer; and
    each half is the reference's half."""
    u, w = _layer(np.random.default_rng(0))
    gates, experts = route(u, w["router"], 3, "sigmoid", w["router_bias"],
                           2.5)
    shared = mlp(u, w["shared_in"], w["shared_out"], "relu2")
    halves = []
    for offset in (0, 4):
        part = moe_experts(u, gates, experts, w["w_in"][offset:offset + 4],
                           w["w_out"][offset:offset + 4], offset=offset,
                           act="relu2")
        halves.append(part)
        np.testing.assert_allclose(
            np.asarray(part + shared),
            _reference_layer(u, w, 4, offset), atol=5e-5)
    whole = _reference_layer(u, w, 8, 0)
    assert min(np.abs(np.asarray(h)).max() for h in halves) > 1e-2
    np.testing.assert_allclose(np.asarray(halves[0] + halves[1] + shared),
                               whole, atol=5e-5)
    np.testing.assert_allclose(
        np.asarray(moe_experts(u, gates, experts, w["w_in"], w["w_out"],
                               act="relu2") + shared), whole, atol=5e-5)
    counts = expert_counts(experts, jnp.ones((12,), bool), 8, 4, 4)
    assert int(counts[0]) == 36 and 0 < int(counts[1]) < 36


def test_a_layer_addressed_in_the_stack_is_that_layer():
    u, w = _layer(np.random.default_rng(1))
    gates, experts = route(u, w["router"], 3, "sigmoid", None, 2.5)
    stack_in = jnp.stack([w["w_in"] * 0 + 7, w["w_in"], w["w_in"] * 3])
    stack_out = jnp.stack([w["w_out"] * 0 + 7, w["w_out"], w["w_out"]])
    one = moe_experts(u, gates, experts, w["w_in"], w["w_out"],
                      act="relu2")
    got = jax.jit(lambda l: moe_experts(
        u, gates, experts, stack_in, stack_out, layer=l,
        act="relu2"))(jnp.int32(1))
    np.testing.assert_allclose(np.asarray(got), np.asarray(one), atol=1e-6)


# -- the grouped launch that visits the touched experts ------------------
def test_layout_puts_each_experts_rows_in_its_own_blocks():
    """Blocks of 16 rows, each of one expert; a group padded up to
    whole blocks; an assignment held elsewhere has no row; the blocks
    past the last used name its expert (nothing to fetch)."""
    from paddle_tpu.ops.pallas import moe_experts as launch
    held, TM = 4, launch.TM
    # expert 0: 17 rows (two blocks), 1: none, 2: 16 (one block), 3: 1;
    # six assignments to experts held elsewhere
    key = np.array([0] * 17 + [2] * 16 + [3] + [held] * 6, np.int32)
    np.random.default_rng(0).shuffle(key)
    dest, src, block_expert, n_used = launch.layout(jnp.asarray(key), held)
    nb = launch.blocks_for(key.size, held)
    assert nb == -(-(40 + 4 * 15) // 16) and src.shape == (nb * TM,)
    assert int(n_used[0]) == 4
    assert [int(e) for e in block_expert[:4]] == [0, 0, 2, 3]
    assert all(int(e) == 3 for e in block_expert[4:])       # no refetch
    dest = np.asarray(dest)
    assert (dest[key == held] == nb * TM).all()              # no row
    rows = dest[key < held]
    assert len(set(rows)) == rows.size                       # injective
    for e, blocks in ((0, (0, 1)), (2, (2,)), (3, (3,))):
        assert set(dest[key == e] // TM) <= set(blocks)
    # rows of one expert keep the assignments' order, and each padded
    # row in use is fed by its own assignment
    assert (np.diff(dest[key == 0]) > 0).all()
    src = np.asarray(src)
    assert (src[rows] == np.flatnonzero(key < held)).all()


@pytest.mark.parametrize("offset,layer", [(0, None), (4, 1), (0, 2)])
def test_grouped_launch_matches_the_sorted_product(offset, layer):
    """``ops/pallas/moe_experts.py`` (interpreted here) against XLA's
    grouped product over the sorted assignments: the same held experts'
    part. Each assignment's row leaves its launch rounded to bfloat16
    (an ulp is 0.125 at the largest values here, ~20) and the two sum
    their float32 products in another order: one ulp of the largest."""
    from paddle_tpu.ops import moe_experts as me
    rng = np.random.default_rng(3)
    T, D, E, held, F, k, L = 40, 256, 8, 4, 112, 3, 3
    bf = jnp.bfloat16
    u = jnp.asarray(rng.normal(size=(T, D)), bf)
    gates, experts = route(u, jnp.asarray(rng.normal(size=(D, E)) * 0.1,
                                          bf), k, "sigmoid", None, 2.5)
    w_in = jnp.pad(jnp.asarray(rng.normal(size=(L, held, D, F)) * 0.1, bf),
                   ((0, 0),) * 3 + ((0, 16),))
    w_out = jnp.asarray(rng.normal(size=(L, held, F, D)) * 0.1, bf)
    if layer is None:
        w_in, w_out = w_in[1], w_out[1]
    args = (u, gates, experts, w_in, w_out, offset, layer, "relu2")
    want = np.asarray(me._ragged(*args), np.float32)
    got = np.asarray(me._grouped(*args), np.float32)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, rtol=0, atol=0.13)
    # every token to the same experts: one group of 40 rows, the
    # others empty
    same = jnp.zeros_like(experts) + jnp.asarray([offset + 1, 7, offset],
                                                 jnp.int32)
    args = (u, gates, same) + args[3:]
    np.testing.assert_allclose(np.asarray(me._grouped(*args), np.float32),
                               np.asarray(me._ragged(*args), np.float32),
                               rtol=0, atol=0.13)


def test_the_registry_takes_the_grouped_launch_where_xla_tiles_by_128():
    """Chosen by what the widths are, not by a family's name: XLA's
    ``ragged_dot`` tiles a dimension by the largest of 512 / 256 / 128
    that divides it, 128 where none does (``tests/test_chip_compile``
    reads that out of the compiled text)."""
    from paddle_tpu.ops.moe_experts import experts_meta
    from paddle_tpu.ops.pallas.moe_experts import supports, xla_tile
    from paddle_tpu.ops.pallas.registry import KERNELS
    assert [xla_tile(n) for n in (4096, 1536, 2304, 1792, 2688, 1920,
                                  1856)] == [512, 512, 256, 256, 128, 128,
                                             128]
    bf = "bfloat16"
    assert supports(2688, 1920, 1856, "relu2", bf)[0]         # Nemotron-H
    assert "gated" in supports(4096, 1536, 768, "silu_gated", bf)[1]
    assert "XLA's launch" in supports(4096, 2048, 2048, "relu2", bf)[1]
    assert "XLA's launch" in supports(2304, 1792, 896, "relu2", bf)[1]
    assert not supports(2688, 1920, 1856, "relu2", "float32")[0]
    sds = jax.ShapeDtypeStruct
    meta = experts_meta(sds((7, 64, 2688, 1920), jnp.bfloat16),
                        sds((7, 64, 1856, 2688), jnp.bfloat16), "relu2")
    # on the CPU the sorted product runs, and says why
    rows = {r["name"]: r for r in KERNELS.explain("moe_experts", meta)}
    assert rows["xla_ragged"]["selected"]
    assert "TPU" in rows["pallas_grouped"]["reason"]
    on_chip = dict(meta, backend="tpu", interpret=False)
    assert KERNELS.explain("moe_experts", on_chip)[0]["selected"]


def test_stored_columns_past_the_width_are_zero_and_add_nothing(params):
    """``w_in`` and ``in_proj`` are stored with their columns rounded
    up to whole lanes; what is past the published width is zero."""
    F = CFG.moe_intermediate_size
    wide = CFG.mamba_d_inner + CFG.mamba_conv_dim + CFG.mamba_num_heads
    assert (CFG.expert_storage_width, CFG.in_proj_storage_width) \
        == (128, 512)
    full = nh.NemotronHConfig()
    assert (full.expert_storage_width, full.in_proj_storage_width) \
        == (1920, 10368)
    assert params["moe"]["w_in"].shape[-1] == 128
    assert float(jnp.abs(params["moe"]["w_in"][..., F:]).max()) == 0.0
    assert float(jnp.abs(params["moe"]["w_in"][..., :F]).max()) > 0.0
    assert params["mamba"]["in_proj"].shape[-1] == 512
    assert float(jnp.abs(params["mamba"]["in_proj"][..., wide:]).max()) \
        == 0.0
