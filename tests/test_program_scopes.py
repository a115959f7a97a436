"""The registry of compiled programs (paddle_tpu/observability/
programs.py) and the ``jax.named_scope``s through the decode, chunk and
training programs. With no profiler session nothing of the registry
runs; under one each owner notes every program it dispatches, once,
without one more trace or compilation and without keeping the owner
alive, and nearly every instruction of a noted program resolves to a
name of ``PROGRAM_SCOPES``."""
import collections
import contextlib
import gc
import weakref

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from benchmarks.harness import CompileCounter
from paddle_tpu.distributed.trainer import MeshConfig, Trainer, make_mesh
from paddle_tpu.inference import GenerationConfig, ServingEngine
from paddle_tpu.inference.tp import ServingMesh
from paddle_tpu.models import granite_hybrid as gh, llama, mellum
from paddle_tpu.models import nemotron_h as nh
from paddle_tpu.models.llama import loss_fn, param_shardings
from paddle_tpu.observability import PROGRAM_SCOPES, programs, tracing

DENSE = llama.LlamaConfig(vocab_size=97, hidden_size=64,
                          intermediate_size=128, num_hidden_layers=2,
                          num_attention_heads=4, num_key_value_heads=2,
                          max_position_embeddings=128, dtype=jnp.float32,
                          remat=False)
GEOMETRY = dict(capacity=3, block_size=8, num_blocks=64, max_seq_len=128,
                prefill_buckets=(8, 32))
# opcodes that run nothing: never an operation of a trace
NO_WORK = programs._NO_WORK


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """JAX's persistent cache keys a program WITHOUT its metadata: an
    entry written before a scope was added or renamed would hand these
    tests the old scopes. They compile their own."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def build(kind):
    if kind == "dense":
        return ServingEngine(llama.init_params(DENSE, jax.random.key(0)),
                             DENSE, **GEOMETRY)
    if kind == "tp2":
        return ServingEngine(llama.init_params(DENSE, jax.random.key(0)),
                             DENSE, mesh=ServingMesh.make(tp=2),
                             **GEOMETRY)
    mod = {"granite": gh, "mellum": mellum, "nemotron": nh}[kind]
    cfg = {"granite": gh.GRANITE_HYBRID_TINY,
           "mellum": mellum.MELLUM_TINY,
           "nemotron": nh.NEMOTRON_H_TINY}[kind]
    return ServingEngine(mod.init_params(cfg, jax.random.key(3)), cfg,
                         **GEOMETRY)


def serve(eng, sizes=(5, 20, 40), new=4):
    rng = np.random.default_rng(0)
    for n in sizes:
        eng.submit(rng.integers(0, 97, n).astype(np.int32),
                   GenerationConfig(max_new_tokens=new, greedy=True))
    eng.drain()


@contextlib.contextmanager
def session(tmp_path):
    """A real profiler session, without the Python tracer."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        yield


@pytest.fixture
def forbidden(monkeypatch):
    """The registry emptied, and everything of it that works patched to
    raise: what a run with no profiler session must never reach."""
    def boom(*a, **k):
        raise AssertionError("the registry ran without a session")
    programs.REGISTRY.clear()
    monkeypatch.setattr(programs, "note", boom)
    monkeypatch.setattr(programs.ProgramRegistry, "note", boom)
    monkeypatch.setattr(programs.Program, "from_text", boom)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """{kind: (programs of a served engine, facts of the run)}: each
    engine is built and warmed with no session, serves the same work
    twice under one, and is gone when its programs are read."""
    out = {}
    counter = CompileCounter()
    for kind in ("dense", "granite", "mellum", "tp2", "nemotron"):
        eng = build(kind)
        jax.block_until_ready(eng.params)
        counter.reset()
        serve(eng)
        facts = {"compiles_untraced": counter.n,
                 "keys_untraced": list(eng._program_keys)}
        with session(tmp_path_factory.mktemp(kind)):
            counter.reset()
            serve(eng)
            facts["compiles"] = counter.n
            facts["keys"] = list(eng._program_keys)
            # the same work again compiles and notes nothing
            serve(eng)
            facts["compiles_again"] = counter.n
            facts["keys_again"] = list(eng._program_keys)
        facts["decode_traces"] = eng.counters["decode_traces"]
        facts["prefill_traces"] = dict(eng.counters["prefill_traces"])
        facts["own"] = [p.key for p in eng.program_scopes()]
        ref = weakref.ref(eng)
        keys = list(eng._program_keys)
        del eng
        gc.collect()
        facts["engine_dead"] = ref() is None
        out[kind] = (programs.scopes(keys), facts)
    return out


def trainer(traces=None, **kw):
    def loss(p, t, l):
        if traces is not None:
            traces.append(1)
        return loss_fn(p, t, l, DENSE)
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    return Trainer(loss, mesh, param_shardings(mesh, DENSE), data_spec=P(),
                   lr=1e-3, **kw)


def batch():
    toks = np.random.RandomState(0).randint(0, 97, (2, 8))
    return (jnp.asarray(toks, jnp.int32),
            jnp.asarray(np.roll(toks, -1, -1), jnp.int32))


def steps(tr, state, n=2):
    for _ in range(n):
        state, m = tr.step(state, *batch())
        jax.block_until_ready(m)
    return state


def fresh(traces=None, **kw):
    tr = trainer(traces, **kw)
    state = tr.init_state(llama.init_params(DENSE, jax.random.key(0),
                                            dtype=jnp.float32))
    jax.block_until_ready(state.params)
    return tr, state


@pytest.fixture(scope="module")
def trained(tmp_path_factory):
    """{path: (programs, facts)} of a tiny Trainer: the plain step and
    the observed one, the per-leaf optimizer and the fused one; two
    steps with no session, then two and two more under one."""
    out = {}
    counter = CompileCounter()
    for name, kw in (("plain", {}), ("observed", {"observability": True}),
                     ("fused", {"fused_optimizer": True})):
        traces = []
        tr, state = fresh(traces, **kw)
        counter.reset()
        state = steps(tr, state)
        facts = {"compiles_untraced": counter.n,
                 "traces_untraced": len(traces),
                 "keys_untraced": list(tr._program_keys)}
        with session(tmp_path_factory.mktemp(name)):
            counter.reset()
            state = steps(tr, state)
            facts["keys"] = list(tr._program_keys)
            state = steps(tr, state)
            facts["keys_again"] = list(tr._program_keys)
            facts["compiles"] = counter.n
        facts["traces"] = len(traces)
        facts["fused"] = bool(tr._fused)
        out[name] = (tr.program_scopes(), facts)
    return out


def work(prog):
    return [h for h, op in prog.opcodes.items() if op not in NO_WORK]


def share_named(prog):
    heads = work(prog)
    named = [h for h in heads if programs.resolve(prog.scopes[h])]
    return len(named) / len(heads)


# -- the names ---------------------------------------------------------
def test_program_scopes_are_frozen():
    assert PROGRAM_SCOPES == (
        "embed", "layers", "kv_gather", "kv_scatter", "layer/qkv",
        "layer/kv_write", "layer/attention", "layer/attn_out",
        "layer/mixer_in", "layer/mixer_out", "layer/mlp", "layer/router",
        "moe_experts", "ssm_update", "ssd_scan", "head", "sample",
        "forward", "loss", "optimizer/grads", "optimizer/clip",
        "optimizer/update", "optimizer/params_out")
    assert isinstance(PROGRAM_SCOPES, tuple)
    assert len(set(PROGRAM_SCOPES)) == len(PROGRAM_SCOPES)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/while/body/closed_call/layer/qkv/dot_general", "layer/qkv"),
    ("jit(step)/layers/while/body/add", "layers"),
    ("jit(step)/layers/while/body/closed_call/layer/mlp/mul",
     "layers/layer/mlp"),
    ("jit(step_fn)/jvp(forward)/while/body/closed_call/dot_general",
     "forward"),
    ("jit(step_fn)/transpose(jvp(forward))/while/body/closed_call/"
     "checkpoint/rematted_computation/tanh", "forward"),
    ("jit(step_fn)/transpose(jvp(loss))/add_any", "loss"),
    ("jit(step)/jit(_sample_slots)/sample/argmax", "sample"),
    ("jit(chunk)/shard_map/kv_gather/gather", "kv_gather"),
    ("jit(step)/cond/branch_1_fun/head/dot_general", "head"),
    ("jit(chunk)/while", None),
    ("params['embed_tokens']", None),
    ("reduce_sum", None),
])
def test_scope_of_an_op_name(op_name, scope):
    assert programs.scope_of(op_name) == scope


@pytest.mark.parametrize("scope, name", [
    ("layer/qkv", "layer/qkv"), ("layers/layer/qkv", "layer/qkv"),
    ("layers", "layers"), ("layers/moe_experts", "moe_experts"),
    ("forward/flash_attention", "forward"),
    ("optimizer/update/adamw", "optimizer/update"),
    ("somebody/elses", None), ("layer", None), (None, None), ("", None),
])
def test_resolve_to_the_innermost_name(scope, name):
    assert programs.resolve(scope) == name


# -- no session: nothing of the registry runs --------------------------
def test_tracing_says_whether_a_session_is_open(tmp_path):
    assert tracing() is False
    with session(tmp_path):
        assert tracing() is True
    assert tracing() is False


@pytest.mark.parametrize("kind", ["dense", "granite"])
def test_engine_without_a_session_touches_no_registry(forbidden, kind):
    eng = build(kind)
    serve(eng)                   # warmed: every program's first dispatch
    serve(eng)                   # ... and stepped
    assert eng.counters["decode_traces"] == 1
    assert eng._program_keys == [] and not eng._decode_noted
    assert eng._prefill_noted == set()
    assert eng.program_scopes() == []
    assert programs.scopes() == []


@pytest.mark.parametrize("kw", [{}, {"observability": True},
                                {"fused_optimizer": True}],
                         ids=["plain", "observed", "fused"])
def test_trainer_without_a_session_touches_no_registry(forbidden, kw):
    tr, state = fresh(**kw)
    steps(tr, state, 3)
    assert tr._program_keys == [] and not tr._noted
    assert tr.program_scopes() == []
    assert programs.scopes() == []


# -- the engines, under a session --------------------------------------
@pytest.mark.parametrize("kind", ["dense", "granite", "mellum", "tp2", "nemotron"])
def test_engine_notes_each_program_once_under_a_session(served, kind):
    progs, facts = served[kind]
    # built, warmed and served with no session: nothing noted
    assert facts["keys_untraced"] == []
    assert facts["compiles_untraced"] > 0
    assert facts["decode_traces"] == 1
    assert set(facts["prefill_traces"].values()) == {1}
    # the decode program and one chunk program a bucket used
    assert len(facts["keys"]) == 1 + len(facts["prefill_traces"])
    assert facts["keys_again"] == facts["keys"] == facts["own"]
    # no compilation beyond the untraced count
    assert facts["compiles"] == facts["compiles_again"] == 0
    names = collections.Counter(p.name for p in progs)
    assert names == {"jit_step": 1,
                     "jit_chunk": len(facts["prefill_traces"])}


@pytest.mark.parametrize("kind", ["dense", "granite", "mellum", "tp2", "nemotron"])
def test_registry_keeps_no_engine_alive(served, kind):
    progs, facts = served[kind]
    assert facts["engine_dead"] and progs


def test_first_dispatch_under_a_session_costs_no_compilation(
        tmp_path, monkeypatch):
    """An engine whose very first dispatches fall under a session (the
    capture then runs in front of the first call, as PR 40's did): as
    many compilations as with ``note`` doing nothing, and one trace."""
    counter = CompileCounter()

    def compiles():
        eng = build("dense")
        jax.block_until_ready(eng.params)
        counter.reset()
        serve(eng)
        assert eng.counters["decode_traces"] == 1
        return counter.n, len(eng._program_keys)
    with session(tmp_path):
        with_registry, noted = compiles()
        monkeypatch.setattr(programs, "note", lambda fn, args: -1)
        assert compiles() == (with_registry, noted)
    assert noted == 3


def test_a_rebuilt_decode_program_is_noted_again(tmp_path):
    """``_decode_route`` changed (a registry pin): the new program is
    un-noted, and a session's first dispatch of it notes it."""
    from paddle_tpu.ops.pallas.registry import KERNELS
    eng = build("dense")
    with session(tmp_path):
        serve(eng, sizes=(5,))
        assert len(eng._program_keys) == 2 and eng._decode_noted
        with KERNELS.force("paged_attention_decode", "xla"):
            serve(eng, sizes=(5,))
        # the pin re-keys the chunk programs too
        assert len(eng._program_keys) == 4
    assert [p.name for p in eng.program_scopes()].count("jit_step") == 2


@pytest.mark.parametrize("kind", ["dense", "granite", "mellum", "tp2",
                                  "nemotron"])
def test_instructions_resolve_to_program_scopes(served, kind):
    for prog in served[kind][0]:
        unnamed = [h for h in work(prog)
                   if not programs.resolve(prog.scopes[h])]
        # every instruction resolves or is listed: the list is short
        assert share_named(prog) >= 0.95, (prog.name, unnamed)
        # what the compiler made says where its scope came from
        assert all(prog.scopes[h] for h in prog.made)
        assert set(prog.scopes) == set(prog.types) == set(prog.opcodes)


@pytest.mark.parametrize("kind, wanted", [
    ("dense", {"embed", "layers", "layer/qkv", "layer/kv_write",
               "layer/attention", "layer/attn_out", "layer/mlp", "head",
               "sample"}),
    ("granite", {"embed", "layer/mixer_in", "ssm_update",
                 "layer/mixer_out", "layer/router", "moe_experts",
                 "layer/mlp", "layer/qkv", "layer/attention", "head"}),
    ("mellum", {"embed", "layer/qkv", "layer/kv_write", "layer/attention",
                "layer/attn_out", "layer/router", "moe_experts", "head",
                "sample"}),
    ("tp2", {"embed", "layer/qkv", "layer/attention", "layer/attn_out",
             "layer/mlp", "head", "sample"}),
    # every layer ONE half: the expert-only layer under layer/router,
    # moe_experts and layer/mlp, the state update under ssm_update
    ("nemotron", {"embed", "layer/mixer_in", "ssm_update",
                  "layer/mixer_out", "layer/router", "moe_experts",
                  "layer/mlp", "layer/qkv", "layer/kv_write",
                  "layer/attention", "layer/attn_out", "head", "sample"}),
])
def test_decode_program_holds_its_scopes(served, kind, wanted):
    step = next(p for p in served[kind][0] if p.name == "jit_step")
    have = {programs.resolve(s) for s in step.scopes.values()}
    assert wanted <= have, wanted - have


@pytest.mark.parametrize("kind, wanted", [
    ("dense", {"kv_gather", "kv_scatter", "layer/qkv", "layer/attention",
               "layer/mlp", "head", "sample"}),
    ("granite", {"layer/mixer_in", "ssd_scan", "layer/mixer_out",
                 "moe_experts", "layer/attention", "head"}),
    ("mellum", {"layer/kv_write", "layer/attention", "moe_experts"}),
    ("tp2", {"kv_gather", "kv_scatter", "layer/attn_out", "layer/mlp"}),
    ("nemotron", {"layer/mixer_in", "ssd_scan", "layer/mixer_out",
                  "layer/router", "moe_experts", "layer/mlp",
                  "layer/attention", "head"}),
])
def test_chunk_programs_hold_their_scopes(served, kind, wanted):
    for chunk in (p for p in served[kind][0] if p.name == "jit_chunk"):
        have = {programs.resolve(s) for s in chunk.scopes.values()}
        assert wanted <= have, wanted - have


def test_an_execution_is_joined_to_its_own_bucket(served):
    """Two ``jit_chunk`` programs number their instructions alike: the
    result types tell their executions apart."""
    small, large = (p for p in served["dense"][0] if p.name == "jit_chunk")
    shared = [h for h in work(small) if h in large.types
              and small.types[h] != large.types[h]]
    assert shared, "the buckets' programs share instruction names"
    h = shared[0]
    event = f"%{h} = {small.types[h]} fusion(f32[] %x), kind=kLoop"
    assert small.holds([event]) and not large.holds([event])
    assert small.holds([f"%{h} = "]) and large.holds([f"%{h} = "])
    assert not small.holds(["%no_such_instruction.7 = f32[] add()"])
    assert small.scope(event) == small.scopes[h]


# -- the trainer, under a session --------------------------------------
@pytest.mark.parametrize("path", ["plain", "observed", "fused"])
def test_trainer_notes_its_step_once_under_a_session(trained, path):
    progs, facts = trained[path]
    assert facts["keys_untraced"] == []
    assert facts["compiles_untraced"] > 0
    assert len(facts["keys"]) == 1 and facts["keys_again"] == facts["keys"]
    assert [p.name for p in progs] == ["jit_step_fn"]
    assert [p.key for p in progs] == facts["keys"]
    # neither one more trace nor one more compilation
    assert facts["traces"] == facts["traces_untraced"]
    assert facts["compiles"] == 0
    assert facts["fused"] == (path == "fused")


@pytest.mark.parametrize("path", ["plain", "observed", "fused"])
def test_training_step_resolves_to_program_scopes(trained, path):
    prog = trained[path][0][0]
    unnamed = [h for h in work(prog)
               if not programs.resolve(prog.scopes[h])]
    assert share_named(prog) >= 0.95, unnamed
    have = {programs.resolve(s) for s in prog.scopes.values()}
    want = {"forward", "loss", "optimizer/clip", "optimizer/update"}
    if path == "fused":      # (float32 leaves come out of the per-leaf
        # update as they are: nothing to convert)
        want |= {"optimizer/grads", "optimizer/params_out"}
    assert want <= have, want - have


def test_backward_operation_keeps_its_forwards_scope(trained):
    """``transpose(jvp(forward))`` and ``transpose(jvp(loss))`` in the
    step's own text read ``forward`` and ``loss``, and such
    instructions are among the program's operations."""
    tr = trainer()
    state = tr.init_state(llama.init_params(DENSE, jax.random.key(0),
                                            dtype=jnp.float32))
    tr._build()
    text = tr._step_fn.lower(state.tree(), jnp.float32(1e-3),
                             *batch()).compile().as_text()
    prog = programs.Program.from_text(text)
    found = collections.Counter()
    for line in text.splitlines():
        for scope in ("forward", "loss"):
            if f'op_name="jit(step_fn)/transpose(jvp({scope}))' in line:
                head = programs.instruction_head(
                    line.strip().removeprefix("ROOT "))
                if head in prog.scopes and head not in prog.made:
                    assert programs.resolve(prog.scopes[head]) == scope
                    found[scope] += 1
    assert found["forward"] and found["loss"]


def test_trainer_is_not_kept_alive(tmp_path):
    tr, state = fresh()
    with session(tmp_path):
        state = steps(tr, state)
    keys = list(tr._program_keys)
    ref = weakref.ref(tr)
    del tr, state
    gc.collect()
    assert ref() is None
    assert [p.name for p in programs.scopes(keys)] == ["jit_step_fn"]


# -- the registry ------------------------------------------------------
TEXT = """HloModule jit_step, is_scheduled=true

%fused_computation (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %mul.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(step)/layers/while/body/closed_call/layer/mlp/mul"}
}

%body (arg: (s32[], f32[4])) -> (s32[], f32[4]) {
  %arg = (s32[], f32[4]{0}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[4]{0} get-tuple-element(%arg), index=1
  %copy.3 = f32[4]{0} copy(%x)
  %fusion.1 = f32[4]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation
  %copy.4 = f32[4]{0} copy(%fusion.1)
  %one = s32[] constant(1)
  %add.2 = s32[] add(%i, %one), metadata={op_name="jit(step)/layers/while/body/add"}
  %copy.5 = s32[] copy(%i)
  ROOT %tuple.1 = (s32[], f32[4]{0}) tuple(%add.2, %copy.4)
}

%cond (arg.1: (s32[], f32[4])) -> pred[] {
  %arg.1 = (s32[], f32[4]{0}) parameter(0)
  %i.1 = s32[] get-tuple-element(%arg.1), index=0
  %n = s32[] constant(2)
  ROOT %lt.1 = pred[] compare(%i.1, %n), direction=LT, metadata={op_name="jit(step)/layers/while/cond/lt"}
}

ENTRY %main (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %zero = s32[] constant(0)
  %convert.9 = f32[4]{0} convert(%a)
  %negate.7 = f32[4]{0} negate(%a)
  %tuple.0 = (s32[], f32[4]{0}) tuple(%zero, %convert.9)
  %while.1 = (s32[], f32[4]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(step)/layers/while"}
  %y = f32[4]{0} get-tuple-element(%while.1), index=1
  ROOT %exp.1 = f32[4]{0} exponential(%y), metadata={op_name="jit(step)/head/exp"}
}
"""


def test_program_from_text():
    prog = programs.Program.from_text(TEXT)
    assert prog.name == "jit_step"
    assert "mul.1" not in prog.scopes           # the inside of a fusion
    assert prog.scopes["fusion.1"] == "layers/layer/mlp"   # its root's
    assert prog.scopes["add.2"] == "layers"
    assert prog.scopes["lt.1"] == "layers"
    assert prog.scopes["exp.1"] == "head"
    # what the compiler made: its reader's, what it reads', its loop's
    assert prog.scopes["copy.3"] == "layers/layer/mlp"
    assert prog.made["copy.3"] == "fusion.1"
    assert prog.scopes["copy.4"] == "layers/layer/mlp"
    assert prog.made["copy.4"] == "fusion.1"
    assert prog.scopes["copy.5"] == "layers"
    assert prog.made["copy.5"] == "(its loop)"
    assert prog.scopes["convert.9"] == "layers"     # through the tuple
    assert prog.made["convert.9"] == "while.1"
    assert prog.scopes["negate.7"] is None      # nothing names it
    assert prog.types["fusion.1"] == "f32[4]{0}"
    assert prog.opcodes["while.1"] == "while"


def test_a_plain_callable_is_not_noted():
    reg = programs.ProgramRegistry()
    assert reg.note(lambda x: x, (1,)) == -1
    assert reg.scopes() == [] and reg.scopes([-1]) == []


def test_a_compiled_program_is_kept_as_it_is():
    """An owner that dispatches a ``jax.stages.Compiled`` of its own
    (the observed Trainer) hands that: nothing is lowered."""
    reg = programs.ProgramRegistry()
    compiled = jax.jit(lambda x: x + 1).lower(jnp.ones(3)).compile()
    key = reg.note(compiled, None)
    assert [p.name for p in reg.scopes([key])] == ["jit__lambda"]


def test_registry_keeps_the_newest_and_finds_by_content():
    reg = programs.ProgramRegistry(keep=2)
    other = TEXT.replace("f32[4]", "f32[8]")
    k0, k1 = reg.add_text(TEXT), reg.add_text(other)
    op = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %copy.3), kind=kLoop"
    assert reg.find("jit_step(123)", [op]).key == k1
    assert reg.find("jit_step(9)", [op.replace("8", "4")]).key == k0
    assert reg.find("jit_chunk(123)", [op]) is None
    assert reg.find("jit_step(1)", ["%fusion.77 = f32[8]{0} fusion()"]) \
        is None
    assert [p.key for p in reg.scopes([k1])] == [k1]
    k2 = reg.add_text(TEXT)
    assert [p.key for p in reg.scopes()] == [k1, k2]     # k0 fell out
    reg.clear()
    assert reg.scopes() == []
