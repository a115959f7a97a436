"""A model with sliding-window and full-attention layers through
``ServingEngine``: the same ``submit`` / ``step`` / ``drain`` as the
other models, with two classes of KV pages under one manager: a full
layer's pages are kept to the request's end, a window layer's go back
to the pool once they lie behind the window. Small size on the CPU
(window 12, pages of 4, two periods of the pattern, 8 experts top-3),
against the benchmark's plain reference (float32, no cache)."""
import math
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401
from paddle_tpu.inference import GenerationConfig, ServingEngine, hybrid
from paddle_tpu.models import mellum
from paddle_tpu.models import pattern as pt
from paddle_tpu.observability import SERVE_SPANS
from paddle_tpu.ops import rope
from paddle_tpu.ops.moe_experts import route
from paddle_tpu.ops.paged_attention import (BlockManager, WindowPages,
                                            paged_attention_decode_xla)
from paddle_tpu.ops.pallas import paged_attention as pa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
from benchmarks.reference import mellum as ref  # noqa: E402

CFG = mellum.MELLUM_TINY           # window 12, 8 layers, 8 experts top-3
W, BS = CFG.sliding_window, 4
GEOMETRY = dict(capacity=3, block_size=BS, num_blocks=160, max_seq_len=128,
                prefill_buckets=(8, 16))
PUBLISHED_YARN = {
    "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
    "original_max_position_embeddings": 8192, "beta_fast": 32,
    "beta_slow": 1, "attention_factor": 1.2772588722239782}


def model_of(cfg):
    """The reference's view of a program config: the published keys."""
    return {
        "hidden_size": cfg.hidden_size, "vocab_size": cfg.vocab_size,
        "moe_intermediate_size": cfg.moe_intermediate_size,
        "num_hidden_layers": cfg.num_hidden_layers,
        "layer_types": list(cfg.layer_types),
        "num_attention_heads": cfg.num_attention_heads,
        "num_key_value_heads": cfg.num_key_value_heads,
        "head_dim": cfg.head_dim, "num_experts": cfg.num_experts,
        "num_local_experts": cfg.num_local_experts,
        "expert_offset": cfg.expert_offset,
        "num_experts_per_tok": cfg.num_experts_per_tok,
        "sliding_window": cfg.sliding_window,
        "rope_parameters": cfg.rope_parameters,
        "rms_norm_eps": cfg.rms_norm_eps}


@pytest.fixture(scope="module")
def params():
    return mellum.init_params(CFG, jax.random.key(3))


def engine(params, cfg=CFG, **kw):
    return ServingEngine(params, cfg, **{**GEOMETRY, **kw})


def prompts(sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, CFG.vocab_size, n).astype(np.int32)
            for n in sizes]


def poison_free_window_pages(eng):
    """Whatever the window class's free list holds may be anything."""
    free = np.asarray(eng.mgr.window.free, np.int32)
    st = eng._state
    eng._state = {**st, "k_win": st["k_win"].at[:, free].set(jnp.nan),
                  "v_win": st["v_win"].at[:, free].set(jnp.nan)}


def serve(eng, ps, new=6, poison=True, **kw):
    reqs = [eng.submit(p, GenerationConfig(max_new_tokens=new,
                                           greedy=True), **kw)
            for p in ps]
    while not eng.idle:
        assert eng.step()
        eng.mgr.check()
        if poison:
            poison_free_window_pages(eng)
    return reqs


def margins(params, req, cfg=CFG):
    """Each served token's logit below the reference's best there."""
    return ref.served_margins(params, model_of(cfg), req.prompt,
                              np.asarray(req.tokens, np.int32), pad_to=16)


# -- the equations ---------------------------------------------------------
def test_yarn_table_is_the_published_formula():
    """low, high, the ramp and the factor at the published numbers."""
    assert rope.yarn_correction_range(128, PUBLISHED_YARN) == (18, 35)
    inv, factor = rope.rope_frequencies(128, PUBLISHED_YARN)
    assert factor == 1.2772588722239782
    assert abs(factor - (0.1 * math.log(16) + 1)) < 1e-12
    k = np.arange(64, dtype=np.float64)
    extrap = 500000.0 ** (-2 * k / 128)
    ramp = np.clip((k - 18) / (35 - 18), 0, 1)
    want = extrap / 16 * ramp + extrap * (1 - ramp)
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    # extrapolated (untouched) up to low, interpolated (/16) from high
    np.testing.assert_allclose(inv[:19], extrap[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], extrap[35:] / 16, rtol=1e-6)
    plain, one = rope.rope_frequencies(
        128, {"rope_type": "default", "rope_theta": 500000})
    assert one == 1.0
    np.testing.assert_allclose(plain, extrap, rtol=1e-6)
    # the reference computes the same table on its own
    model = {"head_dim": 128, "rope_parameters": {
        "full_attention": PUBLISHED_YARN,
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}}}
    mine, f = ref.inv_freq(model, "full_attention")
    np.testing.assert_allclose(mine, want, rtol=1e-12)
    assert f == factor
    with pytest.raises(ValueError, match="rope_type"):
        rope.rope_frequencies(128, {"rope_type": "llama3",
                                    "rope_theta": 1e4})


def test_rotation_is_rotate_half_times_the_factor():
    x = np.random.default_rng(0).standard_normal((5, 2, 8)).astype("f4")
    inv = np.array([1.0, 0.5, 0.25, 0.125], np.float32)
    pos = jnp.asarray([0, 3, 7, 100, 1000])
    got = np.asarray(rope.rotate_half(jnp.asarray(x), pos, inv, 1.25))
    ang = np.asarray(pos, np.float64)[:, None] * inv[None]
    cos, sin = np.cos(ang)[:, None] * 1.25, np.sin(ang)[:, None] * 1.25
    want = np.concatenate([x[..., :4] * cos - x[..., 4:] * sin,
                           x[..., 4:] * cos + x[..., :4] * sin], -1)
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_window_mask_is_itself_and_the_window_minus_one_before():
    """``0 <= i - j < window`` in the full-sequence program's attention:
    a value placed at one position reaches exactly the ``window``
    queries from it on."""
    T, w = 20, 6
    cfg = CFG
    q = jnp.zeros((T, cfg.num_attention_heads, cfg.head_dim))
    k = jnp.zeros((T, cfg.num_key_value_heads, cfg.head_dim))
    for j in (0, 7, 19):
        v = jnp.zeros_like(k).at[j].set(1.0)
        o = np.asarray(pt.attn_dense(q, k, v, jnp.arange(T), cfg, w))
        seen = np.nonzero(o[:, 0] > 0)[0]
        assert list(seen) == [i for i in range(T) if 0 <= i - j < w]
        o = np.asarray(pt.attn_dense(q, k, v, jnp.arange(T), cfg, None))
        assert list(np.nonzero(o[:, 0] > 0)[0]) == list(range(j, T))


def test_router_gates_are_the_softmax_renormalised_over_the_top_k():
    """``norm_topk_prob``: softmax over all experts, the k largest,
    divided by their sum, is ``route``'s softmax over the top-k logits."""
    rng = np.random.default_rng(1)
    u = jnp.asarray(rng.standard_normal((9, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 64)), jnp.float32)
    gates, experts = route(u, w, 8)
    probs = jax.nn.softmax(u @ w, axis=-1)
    top, idx = jax.lax.top_k(probs, 8)
    np.testing.assert_array_equal(np.asarray(experts), np.asarray(idx))
    np.testing.assert_allclose(np.asarray(gates),
                               np.asarray(top / top.sum(-1, keepdims=True)),
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)


def test_config_reads_the_published_keys_verbatim():
    import json
    conf = json.load(open(os.path.join(
        ROOT, "benchmarks/configs/mellum2-12b-a2.5b-l8.json")))
    cfg = mellum.MellumConfig(**{k: conf[k]
                                 for k in conf["program"]["config_keys"]})
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (2304, 32, 4, 128)
    assert (cfg.num_experts, cfg.num_local_experts,
            cfg.num_experts_per_tok, cfg.moe_intermediate_size) == (
                64, 64, 8, 896)
    assert cfg.pattern == ("sliding_attention",) * 3 + (
        "full_attention",) + ("sliding_attention",) * 3 + (
            "full_attention",)
    assert (cfg.num_kv_layers, cfg.num_window_layers) == (2, 6)
    kinds = cfg.kinds
    assert kinds["sliding_attention"].window == 1024
    assert kinds["sliding_attention"].pool == "window"
    assert kinds["full_attention"].pool == "global"
    assert kinds["full_attention"].window is None
    assert dict(kinds["full_attention"].rope) == PUBLISHED_YARN
    assert kinds["full_attention"].rope_table(128)[1] == \
        1.2772588722239782
    assert cfg.segments() == [
        ("sliding_attention", 0, 3, 0), ("full_attention", 3, 1, 0),
        ("sliding_attention", 4, 3, 3), ("full_attention", 7, 1, 1)]
    # what the program does not build is refused by name
    with pytest.raises(ValueError, match="norm_topk_prob"):
        mellum.MellumConfig(norm_topk_prob=False)
    with pytest.raises(ValueError, match="mlp_layer_types"):
        mellum.MellumConfig(mlp_layer_types=("dense",) * 28)
    with pytest.raises(ValueError, match="layer_types holds"):
        mellum.MellumConfig(layer_types=("mamba",) * 28)


def test_forward_is_the_references_equations(params):
    toks = prompts([50], seed=9)[0]
    got = np.asarray(mellum.forward(params, jnp.asarray(toks), CFG))
    padded = np.zeros(64, np.int32)
    padded[:50] = toks
    want = np.asarray(ref.logits_at(params, model_of(CFG), padded,
                                    np.arange(50)))
    np.testing.assert_allclose(got, want, atol=2e-5)


# -- the launch --------------------------------------------------------------
def _ring_case(seed=0, hd=16):
    """Three slots (several windows long, shorter than the window, idle)
    in a ring of 6 pages; every page no slot holds is NaN."""
    rng = np.random.default_rng(seed)
    KV, H, R, N, win = 2, 4, 6, 40, 13
    lens = np.array([37, 9, 1])
    first = np.maximum(lens - win, 0)
    kp = np.full((2, N, BS, KV, hd), np.nan, np.float32)
    vp = kp.copy()
    kp[:, 0] = vp[:, 0] = 0
    tab = np.zeros((3, R), np.int32)
    free, seqs = list(range(1, N)), []
    for b, T in enumerate(lens):
        ks, vs = (rng.standard_normal((T, KV, hd)).astype(np.float32)
                  for _ in range(2))
        seqs.append((ks, vs))
        for n in range(first[b] // BS, (T - 1) // BS + 1):
            pg = free.pop(rng.integers(len(free)))
            tab[b, n % R] = pg
            lo, hi = n * BS, min((n + 1) * BS, T)
            kp[1, pg, :hi - lo], vp[1, pg, :hi - lo] = ks[lo:hi], vs[lo:hi]
    q = rng.standard_normal((3, H, hd)).astype(np.float32)
    want = np.zeros((3, H, hd))
    for b in range(3):
        ks, vs = seqs[b]
        for h in range(H):
            k = ks[first[b]:lens[b], h // 2].astype(np.float64)
            s = k @ q[b, h] * 0.25
            p = np.exp(s - s.max())
            want[b, h] = p / p.sum() @ vs[first[b]:lens[b], h // 2]
    args = tuple(map(jnp.asarray, (q, kp, vp, tab,
                                   lens.astype(np.int32))))
    return args, jnp.asarray(first, jnp.int32), want


@pytest.mark.parametrize("launch", ["xla", "pallas-1", "pallas-2",
                                    "pallas-4"])
def test_launch_starts_at_the_first_live_position(launch):
    """Both variants visit the pages from each slot's first live
    position on (the table a ring), mask the head of the first one, and
    never read a page the slot gave back (they hold NaN here)."""
    args, first, want = _ring_case()
    if launch == "xla":
        got = paged_attention_decode_xla(*args, scale=0.25, layer=1,
                                         first=first)
    else:
        got = pa.paged_attention_decode_pallas(
            *args, scale=0.25, layer=1, first=first,
            pages_per_step=int(launch[-1]))
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-6)


@pytest.mark.parametrize("launch", ["xla", "pallas"])
def test_first_position_zero_is_todays_program_bit_for_bit(launch):
    rng = np.random.default_rng(2)
    kp, vp = (jnp.asarray(rng.standard_normal((1, 30, BS, 2, 16)),
                          jnp.float32) for _ in range(2))
    q = jnp.asarray(rng.standard_normal((3, 4, 16)), jnp.float32)
    tab = jnp.asarray(rng.integers(1, 30, (3, 6)), jnp.int32)
    lens = jnp.asarray([23, 8, 1], jnp.int32)
    fn = (paged_attention_decode_xla if launch == "xla" else
          lambda *a, **k: pa.paged_attention_decode_pallas(
              *a, pages_per_step=2, **k))
    plain = fn(q, kp, vp, tab, lens, scale=0.3, layer=0)
    zero = fn(q, kp, vp, tab, lens, scale=0.3, layer=0,
              first=jnp.zeros(3, jnp.int32))
    np.testing.assert_array_equal(np.asarray(plain), np.asarray(zero))


def test_launch_reckons_the_pages_it_visits():
    """``pool_bytes_fetched`` counts a slot's pages from its first live
    one on, not from the table's start."""
    from paddle_tpu.ops.pallas._util import capture_kernel_launches
    args, first, _ = _ring_case()
    with capture_kernel_launches() as launches:
        pa.paged_attention_decode_pallas(*args, scale=0.25, layer=1,
                                         first=first, pages_per_step=2)
    spec, = [s for s in launches if s.name == "paged_attention_decode"]
    assert spec.num_scalar_prefetch == 4
    page = BS * 2 * 16 * 4
    lens, f = np.asarray(args[4]), np.asarray(first)
    fetched = spec.inputs[1].fetched_bytes(None, lens, None, f)
    assert fetched == page * sum(-(-lens[b] // BS) - f[b] // BS
                                 for b in range(3))
    assert fetched == page * (4 + 3 + 1)       # of 10 + 3 + 1 live pages


# -- the cache manager ---------------------------------------------------------
def test_window_pages_go_back_and_are_handed_out_again():
    mgr = BlockManager(8, BS, 8, window=W, window_blocks=9, window_ring=4)
    win = mgr.window
    assert isinstance(win, WindowPages) and len(win.free) == 8
    assert win.need(100) == 4 and win.need(5) == 2
    win.reserve(7, 100)
    assert win.advance(7, 0 - (W - 1), 8) == (0, True)
    assert sorted(win.tables[7]) == [0, 1]
    assert win.advance(7, 5 - (W - 1), 9) == (0, True)     # block 2
    assert win.advance(7, 6 - (W - 1), 10) == (0, False)
    # position 16's query sees 5..16: block 0 (tokens 0-3) is behind it
    gone, changed = win.advance(7, 16 - (W - 1), 17)
    assert (gone, changed) == (1, True)
    assert sorted(win.tables[7]) == [1, 2, 3, 4]
    row = win.row(7)
    assert row[4 % 4] == win.tables[7][4] and (row > 0).all()
    # the page that came back is the next one handed out
    back = win.free[-1]
    win.reserve(8, 3)
    win.advance(8, 0 - (W - 1), 3)
    assert win.tables[8] == {0: back}
    assert mgr.check() == []
    # admission reckons the class: 4 + 1 reserved of 8, no room for 4 more
    assert win.can_reserve(12) and not win.can_reserve(100)
    with pytest.raises(RuntimeError, match="at once"):
        win.advance(7, 0, 40)
    mgr.release(7)
    mgr.release(8)
    assert len(win.free) == 8 and not win.tables and not win.reserved
    assert mgr.check() == []


def test_check_sees_a_corrupt_window_class():
    mgr = BlockManager(8, BS, 8, window=W, window_blocks=9, window_ring=4)
    win = mgr.window
    win.reserve(1, 40)
    win.advance(1, 0, 8)
    held = win.tables[1][0]
    win.free.append(held)
    assert any("held by table 1 and free" in p or "and free" in p
               for p in mgr.check(raise_on_violation=False))
    win.free.pop()
    lost = win.free.pop()
    assert any(f"window page {lost} leaked" in p
               for p in mgr.check(raise_on_violation=False))
    with pytest.raises(RuntimeError, match="BlockManager.check failed"):
        mgr.check()


# -- through the programs: logits, not tokens -------------------------------------
def run_programs(params, prompt, n_decode, chunk, poison=True):
    """Prefill ``prompt`` in chunks of ``chunk`` and decode ``n_decode``
    reference-chosen tokens through the two programs and both page
    classes, in slot 1 of 2. Returns the logits after the prompt and
    after every decode step, and the window pages given back."""
    cfg = CFG
    S = prompt.size
    total = S + n_decode
    pat = hybrid.served_pattern(cfg)
    ring = pat.ring(BS, chunk)
    MB = -(-(total + chunk) // BS)
    mgr = BlockManager(MB + 1, BS, MB, window=W,
                       window_blocks=2 * ring + 1, window_ring=ring)
    mgr.allocate(-1, 1)
    table = np.zeros(MB, np.int32)
    pages = mgr.allocate(0, total)
    table[:len(pages)] = pages
    mgr.window.reserve(0, total)
    L = cfg.num_kv_layers
    kp = jnp.zeros((L, MB + 1, BS, cfg.num_key_value_heads, cfg.head_dim),
                   cfg.dtype)
    vp = jnp.zeros_like(kp)
    state = hybrid.init_state(cfg, 2, window_blocks=2 * ring + 1,
                              block_size=BS, ring=ring)
    released = 0

    def advance(state, lo, hi):
        nonlocal released
        gone, _ = mgr.window.advance(0, lo - (W - 1), hi)
        released += gone
        mgr.check()
        rows = np.zeros((2, ring), np.int32)
        rows[1] = mgr.window.row(0)
        state = {**state, "win_tables": jnp.asarray(rows)}
        if poison:
            free = np.asarray(mgr.window.free, np.int32)
            state["k_win"] = state["k_win"].at[:, free].set(jnp.nan)
            state["v_win"] = state["v_win"].at[:, free].set(jnp.nan)
        return state

    # jitted, as the engine runs them
    chunk_fn = jax.jit(
        lambda toks, kp, vp, pos0, n, st: hybrid.prefill_chunk(
            params, toks, cfg, kp, vp, jnp.asarray(table),
            jnp.asarray(table), pos0, n, 1, st))
    step_fn = jax.jit(
        lambda tok, kp, vp, tables, lens, st: hybrid.decode_step(
            params, tok, cfg, kp, vp, tables, lens, st))
    out = []
    for pos0 in range(0, S, chunk):
        n = min(chunk, S - pos0)
        toks = np.zeros(chunk, np.int32)
        toks[:n] = prompt[pos0:pos0 + n]
        state = advance(state, pos0, pos0 + n)
        lg, kp, vp, state = chunk_fn(jnp.asarray(toks), kp, vp, pos0, n,
                                     state)
    out.append(np.asarray(lg[0]))
    tables = np.zeros((2, MB), np.int32)
    tables[1] = table
    seq = list(prompt)
    for _ in range(n_decode):
        tok = int(np.argmax(out[-1]))
        state = advance(state, len(seq), len(seq) + 1)
        lens = np.array([0, len(seq)], np.int32)
        lg, kp, vp, state = step_fn(
            jnp.asarray([0, tok], jnp.int32), kp, vp, jnp.asarray(tables),
            jnp.asarray(lens), state)
        seq.append(tok)
        out.append(np.asarray(lg[1]))
    return np.stack(out), np.asarray(seq, np.int32), released


@pytest.mark.parametrize("S,chunk", [
    (50, 8),      # a chunk boundary inside the window (8 < 12)
    (48, 12),     # at the window
    (50, 16),     # beyond it
    (7, 8),       # shorter than a chunk and than the window
], ids=["inside", "at", "beyond", "short"])
def test_chunks_then_decode_give_the_references_logits(params, S, chunk):
    """Prefill in chunks, then decode through the cache, for a sequence
    several windows long: the logits are the full forward's, with every
    window page that was given back overwritten with NaN."""
    prompt = prompts([S], seed=S)[0]
    n_decode = 22     # across several steps at which a page goes back
    got, seq, released = run_programs(params, prompt, n_decode, chunk)
    padded = np.zeros(ref.padded_length(seq.size, 16), np.int32)
    padded[:seq.size] = seq
    want = np.asarray(ref.logits_at(
        params, model_of(CFG), padded, np.arange(S - 1, S + n_decode)))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=3e-5)
    # the window layers never held more than window + chunk + a page
    if S > W:
        assert released >= (S + n_decode - W - chunk) // BS - 1 > 0


def test_ignoring_the_window_is_seen(params):
    """The comparison has teeth: a reference whose window layers see
    everything disagrees with what is served."""
    eng = engine(params)
    r, = serve(eng, prompts([60], seed=4), new=12)
    assert margins(params, r).max() < 1e-6
    wide = ref.served_margins(params, model_of(CFG), r.prompt,
                              np.asarray(r.tokens, np.int32), pad_to=16,
                              fake_quant="window_ignored")
    assert wide.max() > 1e-3


# -- through the engine ---------------------------------------------------------
def test_served_tokens_are_the_references_choice(params):
    """More requests than slots, prompts several windows long beside
    short ones, chunks beside decode steps, slots of different length in
    one step; every free window page is NaN after every step. Every
    served token is the float32 reference's own choice."""
    eng = engine(params)
    reqs = serve(eng, prompts([70, 5, 33, 9, 50, 3]), new=14)
    for r in reqs:
        assert len(r.tokens) == 14
        assert margins(params, r).max() < 1e-6
    assert len({t for r in reqs for t in r.tokens}) > 6
    c = eng.counters
    assert c["decode_traces"] == 1
    assert c["prefill_traces"] == {8: 1, 16: 1}
    assert c["window_pages_released"] > 30
    assert 0 < c["kv_tokens_held_window"] < c["kv_tokens_seen_window"]
    assert c["kv_pages_live_global"] > 0
    m = eng.metrics()["pattern"]
    assert "recurrent" not in eng.metrics()
    assert (m["kv_layers"], m["window_layers"]) == (2, 6)
    assert m["window"]["positions"] == W
    assert m["window"]["ring_pages"] == -(-(W + 16) // BS) + 1 == 8
    assert m["window"]["pool_pages"] == 3 * 8 + 1
    assert m["window"]["pages_released"] == c["window_pages_released"]
    assert 0 < m["window"]["held_share"] < 1
    assert m["experts"]["assignments"] == c["expert_assignments"] > 0
    assert m["experts"]["held_share"] == 1.0
    # everything is given back at the end, in both classes
    assert len(eng.mgr.window.free) == 3 * 8
    assert not eng.mgr.window.reserved
    assert len(eng.mgr.free) == 160 - 1


def test_pools_are_as_deep_as_their_page_class(params):
    eng = engine(params)
    KV, hd = CFG.num_key_value_heads, CFG.head_dim
    assert eng._k_pools.shape == (2, 160, BS, KV, hd)
    assert eng._state["k_win"].shape == (6, 25, BS, KV, hd)
    assert eng._state["win_tables"].shape == (3, 8)
    assert set(eng._state) == {"k_win", "v_win", "win_tables", "stats"}
    roof = eng.metrics()["roofline"]
    assert roof["reckoned"] is False and "window" in roof["why"]


def test_a_released_page_serves_another_request_at_once(params):
    """Two requests side by side: pages the long one gives back are the
    ones the short one is handed (the free list is last in, first out),
    and both are served right."""
    eng = engine(params, capacity=2)
    long_, = [eng.submit(prompts([64], seed=6)[0],
                         GenerationConfig(max_new_tokens=20, greedy=True))]
    for _ in range(6):
        eng.step()
    held_before = set(eng.mgr.window.tables[long_.req_id].values())
    short, = [eng.submit(prompts([20], seed=7)[0],
                         GenerationConfig(max_new_tokens=20, greedy=True))]
    seen = set()
    while not eng.idle:
        eng.step()
        eng.mgr.check()
        poison_free_window_pages(eng)
        seen |= set(eng.mgr.window.tables.get(short.req_id, {}).values())
    assert seen & held_before      # a page of the long request, reused
    for r in (long_, short):
        assert margins(params, r).max() < 1e-6


def test_window_release_is_a_span_inside_a_step(params):
    eng = engine(params, observability=True)
    serve(eng, prompts([30, 12]), poison=False)
    names = [e.name for e in eng.observability.timeline.events()]
    assert "serve/window_release" in SERVE_SPANS
    assert names.count("serve/window_release") >= \
        eng.counters["decode_steps"]
    eng.reset_metrics()
    assert eng.counters["window_pages_released"] == 0
    assert eng.counters["kv_tokens_seen_window"] == 0


def test_prefix_cache_is_off_and_counted(params):
    """A matched prefix's window pages are gone: the cache stays off for
    this model, every request it would have looked up is counted, and
    what is served is still right for requests that share a prefix."""
    shared = prompts([40], seed=2)[0]
    ps = [np.concatenate([shared, t]) for t in prompts([5, 9, 3], seed=3)]
    eng = engine(params, prefix_cache=True)
    reqs = serve(eng, ps)
    assert eng.counters["prefix_skipped_window"] == 3
    assert "prefix_skipped_recurrent" not in eng.counters
    assert eng._pcache is None and "prefix_cache" not in eng.metrics()
    assert eng.counters["prefill_tokens"] == sum(p.size for p in ps)
    for r in reqs:
        assert margins(params, r).max() < 1e-6
    assert eng.metrics()["pattern"]["prefix_skipped_window"] == 3


@pytest.mark.parametrize("kw,match", [
    (dict(mesh=2), "expert exchange"),
    (dict(weight_quant="int8"), "expert stacks"),
    (dict(cache_dtype="int8"), "calibrated through the dense"),
    (dict(prefix_cache=True, kv_offload=True), "two page lifetimes"),
    (dict(state_dtype="bfloat16"), "no recurrent layer"),
], ids=["mesh", "weight_quant", "cache_int8", "kv_offload", "state_dtype"])
def test_refusals_name_what_is_missing(params, kw, match):
    with pytest.raises(ValueError, match=match):
        engine(params, **kw)


def test_engine_takes_no_option_for_the_window(params):
    import inspect
    names = set(inspect.signature(ServingEngine.__init__).parameters)
    assert not {n for n in names if "window" in n or "ring" in n}


def test_preempted_request_keeps_both_classes_of_pages(params):
    """A more urgent request evicts a decoding one; the victim keeps its
    global pages AND its window pages, and resumes where it was. The
    window pool holds every slot's whole ring and no more, so a victim
    is evicted only while both requests' worst cases fit it (5 + 3 of
    its 8 pages here); otherwise the urgent request waits."""
    eng = engine(params, capacity=1)
    low = eng.submit(prompts([4])[0],
                     GenerationConfig(max_new_tokens=16, greedy=True),
                     priority=5)
    for _ in range(8):
        eng.step()
    assert eng.live_slots == 1 and 0 < len(low.tokens) < 16
    high = eng.submit(prompts([6], seed=4)[0],
                      GenerationConfig(max_new_tokens=6, greedy=True),
                      priority=0)
    while not eng.idle:
        eng.step()
        eng.mgr.check()
        poison_free_window_pages(eng)
    assert eng.counters["preemptions"] == 1 and low.preemptions == 1
    assert len(low.tokens) == 16 and len(high.tokens) == 6
    for r in (low, high):
        assert margins(params, r).max() < 1e-6


def test_programs_audit_clean(params):
    """The static audit of the engine's programs (donation, carry,
    retrace hazards) covers the window pools and tables too."""
    eng = engine(params)
    reports = eng.audit(register=False)
    assert [r.program for r in reports] == [
        "serving_decode", "serving_prefill_8", "serving_prefill_16"]
    for r in reports:
        assert [f for f in r.findings if f.severity == "error"] == [], \
            r.to_dict()


def test_one_pair_of_programs_serves_both_pattern_models():
    """The granite hybrid and this model go through the same two
    functions; the engine holds no model's name, only the description."""
    from paddle_tpu.models import granite_hybrid as gh
    g = hybrid.served_pattern(gh.GRANITE_HYBRID_TINY)
    m = hybrid.served_pattern(CFG)
    assert (g.recurrent_layers, g.window_layers, g.window) == (3, 0, 0)
    assert (m.recurrent_layers, m.window_layers, m.window) == (0, 6, W)
    assert g.prefix_skip_counter == "prefix_skipped_recurrent"
    assert m.prefix_skip_counter == "prefix_skipped_window"
    from paddle_tpu.models import llama
    assert hybrid.served_pattern(llama.LLAMA_TINY) is None
    import paddle_tpu.inference.serving as serving
    src = open(serving.__file__).read()
    assert "_recurrent" not in src.replace("prefix_skipped_recurrent", "")
    assert "mellum" not in src.lower() and "granite" not in src.lower()
