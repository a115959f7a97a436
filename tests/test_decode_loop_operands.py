"""The decode program's layer loop moves no bytes a kernel did not ask
for (PR 26): the KV pools are loop carry, written in place, and the
Pallas launches of the loop take the carried pools / the stacked MLP
weights whole, with the layer's index.

Three guards, all on the CPU:

- the traced jaxpr of every decode program: the pools are in the layer
  loop's carry (not among its stacked inputs or outputs) and every pool
  / MLP-weight operand of a ``paged_attention_decode`` or
  ``decode_mlp_block`` launch is that carried / closed-over array, not
  a one-layer slice of it;
- the indexed kernels equal, bit for bit, the same kernel on the slice;
- the layered pool write touches no other layer.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.extend.core import Literal

import paddle_tpu  # noqa: F401 — x64 mode, as every caller has it
from paddle_tpu.inference import generation as G
from paddle_tpu.inference import tp as TP
from paddle_tpu.models import llama
from paddle_tpu.ops import paged_attention as PA
from paddle_tpu.ops.pallas import fused_decode_block as fdb
from paddle_tpu.ops.pallas.paged_attention import (
    paged_attention_decode_pallas)
from paddle_tpu.ops.pallas.registry import KERNELS
from paddle_tpu.quantization import ptq

# the benchmark's rehearsal size (benchmarks/configs/*.json "rehearse")
CFG = llama.LlamaConfig(vocab_size=512, hidden_size=128,
                        intermediate_size=256, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=2,
                        max_position_embeddings=256)
B, BS, NB, MB = 4, 8, 96, 8
LAUNCHES = ("paged_attention_decode", "decode_mlp_block")


# ---------------------------------------------------------------------------
# the jaxpr of the layer loop
# ---------------------------------------------------------------------------
def _local_params(tp, collective):
    """The parameter shapes one shard of a tp-way mesh holds (tp=1:
    the whole tree): heads and intermediate columns split, and under
    the "gather" placement o_proj / down_proj whole."""
    sd = jax.eval_shape(lambda: llama.init_params(CFG, dtype=jnp.float32))
    cols = {"q_proj", "k_proj", "v_proj", "gate_proj", "up_proj"}
    rows = {"o_proj", "down_proj"} if collective == "psum" else set()

    def cut(name, v):
        s = list(v.shape)
        if name in cols:
            s[2] //= tp
        if name in rows:
            s[1] //= tp
        return jax.ShapeDtypeStruct(tuple(s), v.dtype)

    sd["layers"] = {k: cut(k, v) for k, v in sd["layers"].items()}
    return sd


def _trace(program):
    """make_jaxpr of one decode program as the chip traces it: the
    registry's two decode ops pinned to what they select there (the
    Pallas launches, as in every serving cell; "unfused": the MLP
    composition beside the attention launch)."""
    tp, collective, axis_env = 1, None, None
    fn = G._decode_step
    if program.startswith("tp_"):
        tp, collective, axis_env = 2, program.split("_")[1], [("tp", 2)]
        fn = functools.partial(TP._tp_decode_step, axis="tp",
                               collective=collective)
    mlp = "unfused" if program == "unfused" else "pallas_fused"
    L, KV, hd = (CFG.num_hidden_layers, CFG.num_key_value_heads,
                 CFG.head_dim)
    pool = jax.ShapeDtypeStruct((L, NB, BS, KV // tp, hd), jnp.float32)
    ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    with KERNELS.force("paged_attention_decode", "pallas"), \
            KERNELS.force("decode_mlp_block", mlp), \
            KERNELS.record() as picked:
        return picked, jax.make_jaxpr(
            lambda p, tok, kp, vp, bt, sl: fn(p, tok, CFG, kp, vp, bt, sl),
            axis_env=axis_env)(
                _local_params(tp, collective), ints(B), pool, pool,
                ints(B, MB), ints(B))


def _scans(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _scans(sub)


def _operand_origins(jaxpr, origin, found):
    """Walk ``jaxpr`` collecting, for each Pallas launch, where each of
    its operands comes from. ``origin`` maps a var to "carry" / "const"
    / "xs" (the loop body's own inputs) or to the name of the primitive
    that made it; a write into the carried pool keeps its origin, and
    so does a reshape (the attention launch sees a page as the
    ``[BS * KV, hd]`` rows it is in memory: a merge of neighbouring
    axes, which ``tests/test_chip_compile.py -k second_copy`` holds to
    be no copy)."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        ins = ["literal" if isinstance(v, Literal)
               else origin.get(v, "outer") for v in eqn.invars]
        if name == "pallas_call":
            found.append((eqn.params["name"],
                          [(v.aval.shape, o)
                           for v, o in zip(eqn.invars, ins)]))
        subs = list(jax.core.jaxprs_in_params(eqn.params))
        if subs and name != "pallas_call":
            for sub in subs:            # a call: operands pass through
                inner = dict(zip(sub.invars, ins[-len(sub.invars):]))
                _operand_origins(sub, inner, found)
        for v in eqn.outvars:
            origin[v] = ins[0] if name in ("scatter", "reshape") else name
    return found


@pytest.mark.parametrize("program",
                         ["fused", "unfused", "tp_psum", "tp_gather"])
def test_layer_loop_carries_pools_and_indexes_stacked_operands(program):
    picked, closed = _trace(program)
    (loop,) = [s for s in _scans(closed.jaxpr)
               if any(len(v.aval.shape) == 5 for v in s.invars)]
    body = loop.params["jaxpr"].jaxpr
    nc, nk = loop.params["num_consts"], loop.params["num_carry"]
    kinds = ["const"] * nc + ["carry"] * nk \
        + ["xs"] * (len(body.invars) - nc - nk)
    rank = lambda v: len(v.aval.shape)                    # noqa: E731
    # both pools ride in the carry, none is a stacked input or output
    assert [k for v, k in zip(body.invars, kinds) if rank(v) == 5] \
        == ["carry", "carry"]
    assert not [v for v in loop.outvars[nk:] if rank(v) >= 4]
    launches = [(n, ops) for n, ops in _operand_origins(
        body, dict(zip(body.invars, kinds)), []) if n in LAUNCHES]
    names = [n for n, _ in launches]
    want = {"fused": LAUNCHES, "tp_psum": LAUNCHES}.get(
        program, LAUNCHES[:1])          # the compositions: attention only
    assert sorted(names) == sorted(want), names
    for name, operands in launches:
        big = {r: [o for shape, o in operands if len(shape) == r]
               for r in (5, 4, 3, 2)}
        if name == "paged_attention_decode":
            # k and v: the carried pools whole, every layer and page,
            # a page's [BS, KV] axes merged; no one-layer pool
            whole = [o for shape, o in operands if len(shape) == 4
                     and shape[:2] == (CFG.num_hidden_layers, NB)]
            assert whole == ["carry", "carry"], operands
            assert len(big[4]) == 2 and not big[5], operands
        else:
            # norm row, gate, up, down: closed over whole; x is rank 2
            assert len(big[3]) == 4 and not big[4], operands
            weights = [o for shape, o in operands
                       if len(shape) == 3 and shape[1] > 1]
            assert weights == ["const"] * 3, operands
    # and the engine's record, read off what dispatch picked for this
    # trace, says the same
    assert fdb.launch_operands(picked) == {n: "index" for n in names}


# ---------------------------------------------------------------------------
# parity of the indexed kernels (interpret mode)
# ---------------------------------------------------------------------------
def _stack(leaves):
    return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *leaves)


def _same(a, b):
    return np.array_equal(np.asarray(a, np.float32),
                          np.asarray(b, np.float32))


@pytest.mark.parametrize("weights", [None, "int8", "int4"])
def test_indexed_mlp_kernel_equals_the_kernel_on_the_slice(weights):
    L, D, F = 3, 128, 256
    k = jax.random.split(jax.random.key(3), 5)
    x = jax.random.normal(k[0], (B, D), jnp.bfloat16)
    nw = jax.random.normal(k[1], (L, D), jnp.bfloat16)
    mk = lambda kk, s: (jax.random.normal(kk, s) * 0.05   # noqa: E731
                        ).astype(jnp.bfloat16)
    ws = [mk(k[2], (L, D, F)), mk(k[3], (L, D, F)), mk(k[4], (L, F, D))]
    if weights:
        bits = int(weights[-1])
        ws = [_stack([ptq.quantize_leaf(w[l], bits, **(
            {"pack_axis": axis} if bits == 4 else {}))
            for l in range(L)]) for w, axis in zip(ws, (0, 0, 1))]
    at = lambda t, l: jax.tree_util.tree_map(             # noqa: E731
        lambda a: a[l], t)
    for l in range(L):                       # first, middle, last
        got = jax.jit(lambda l: fdb.fused_mlp_block_pallas(
            x, nw, *ws, layer=l, block_f=128))(jnp.int32(l))
        want = fdb.fused_mlp_block_pallas(
            x, nw[l], *[at(w, l) for w in ws], block_f=128)
        assert _same(got, want), (weights, l)


def test_indexed_paged_attention_equals_the_kernel_on_the_slice():
    L, N, KV, hd, H = 3, 12, 2, 32, 4
    k = jax.random.split(jax.random.key(4), 3)
    kp = jax.random.normal(k[0], (L, N, BS, KV, hd), jnp.bfloat16)
    vp = jax.random.normal(k[1], (L, N, BS, KV, hd), jnp.bfloat16)
    q = jax.random.normal(k[2], (B, H, hd), jnp.bfloat16)
    bt = jnp.asarray(np.random.RandomState(0).permutation(N)
                     .reshape(B, 3), jnp.int32)
    lens = jnp.asarray([5, 24, 0, 17], jnp.int32)
    for l in range(L):
        got = jax.jit(lambda l: paged_attention_decode_pallas(
            q, kp, vp, bt, lens, layer=l))(jnp.int32(l))
        want = paged_attention_decode_pallas(q, kp[l], vp[l], bt, lens)
        assert _same(got, want), l


@pytest.mark.parametrize("quant", [False, True])
def test_layered_pool_write_touches_no_other_layer(quant):
    L, N, KV, hd = 3, 6, 2, 16
    dt = jnp.int8 if quant else jnp.float32
    rng = np.random.RandomState(1)
    kp = jnp.asarray(rng.randint(-5, 5, (L, N, BS, KV, hd)), dt)
    vp = jnp.asarray(rng.randint(-5, 5, (L, N, BS, KV, hd)), dt)
    bt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    lens = jnp.asarray([3, 9], jnp.int32)      # page 1 slot 3, page 4 slot 1
    k_new = jnp.asarray(rng.randn(2, KV, hd) * 3, jnp.float32)
    v_new = jnp.asarray(rng.randn(2, KV, hd) * 3, jnp.float32)
    sc = (jnp.full((KV,), 0.5), jnp.full((KV,), 0.25))
    write = functools.partial(PA.write_to_pool_quant, k_scale=sc[0],
                              v_scale=sc[1]) if quant else PA.write_to_pool
    for l in range(L):
        k2, v2 = jax.jit(lambda l: write(kp, vp, bt, lens, k_new, v_new,
                                         layer=l))(jnp.int32(l))
        k1, v1 = write(kp[l], vp[l], bt, lens, k_new, v_new)
        for new, old, one in ((k2, kp, k1), (v2, vp, v1)):
            assert _same(new[l], one)           # the one-layer write
            rest = [i for i in range(L) if i != l]
            assert _same(new[jnp.asarray(rest)], old[jnp.asarray(rest)])
