"""The readers that put a trace's operations down to the program's named
scopes (``reducers/scoped_ms.py``, ``scoped_share_pct.py``,
``scoped_roofline.py``), on a small recorded trace in the tests' JSON
form and a hand-made registry of compiled programs: what holds their
arithmetic, since a rehearsal on the CPU has no ``XLA Modules`` line and
reads None."""
import json
import types

import pytest

from benchmarks import harness, trace as T
from benchmarks.reducers import scoped_ms, scoped_roofline, scoped_share_pct
from paddle_tpu.observability import programs

CALL = ('custom-call(f32[8]{0} %x), custom_call_target="tpu_custom_call", '
        'operand_layout_constraints={f32[8]{0}}')


def module(name, shape, scope_of_fusion_1):
    """A compiled module's text with one loop: ``fusion.1`` under the
    given scope, a copy the compiler made in front of it, a launch, a
    collective and the expert layer's two products."""
    op = f'metadata={{op_name="jit({name})/layers/while/body/closed_call'
    return f"""HloModule jit_{name}, is_scheduled=true

%fused (p: f32[{shape}]) -> f32[{shape}] {{
  %p = f32[{shape}]{{0}} parameter(0)
  ROOT %mul.1 = f32[{shape}]{{0}} multiply(%p, %p), {op}/{scope_of_fusion_1}/mul"}}
}}

%body (arg: (s32[], f32[{shape}])) -> (s32[], f32[{shape}]) {{
  %arg = (s32[], f32[{shape}]{{0}}) parameter(0)
  %i = s32[] get-tuple-element(%arg), index=0
  %x = f32[{shape}]{{0}} get-tuple-element(%arg), index=1
  %copy.2 = f32[{shape}]{{0}} copy(%x)
  %fusion.1 = f32[{shape}]{{0}} fusion(%copy.2), kind=kLoop, calls=%fused
  %decode_mlp_block.9 = f32[{shape}]{{0}} custom-call(%fusion.1), custom_call_target="tpu_custom_call", {op}/layer/mlp/pallas_call"}}
  %psum.14 = f32[{shape}]{{0}} all-reduce(%decode_mlp_block.9), to_apply=%fused, {op}/layer/mlp/psum"}}
  %sort.4 = f32[{shape}]{{0}} sort(%psum.14), to_apply=%fused, {op}/moe_experts/sort"}}
  %ragged-dot-none.1 = f32[{shape}]{{0}} custom-call(%sort.4), custom_call_target="ragged_dot", {op}/moe_experts/ragged_dot_general"}}
  ROOT %tuple.1 = (s32[], f32[{shape}]{{0}}) tuple(%i, %ragged-dot-none.1)
}}

%cond (arg.1: (s32[], f32[{shape}])) -> pred[] {{
  %arg.1 = (s32[], f32[{shape}]{{0}}) parameter(0)
  ROOT %lt.1 = pred[] constant(true)
}}

ENTRY %main (a: f32[{shape}]) -> f32[{shape}] {{
  %a = f32[{shape}]{{0}} parameter(0)
  %zero = s32[] constant(0)
  %weird.5 = f32[{shape}]{{0}} negate(%a)
  %tuple.0 = (s32[], f32[{shape}]{{0}}) tuple(%zero, %a)
  %while.3 = (s32[], f32[{shape}]{{0}}) while(%tuple.0), condition=%cond, body=%body, metadata={{op_name="jit({name})/layers/while"}}
  ROOT %y = f32[{shape}]{{0}} get-tuple-element(%while.3), index=1
}}
"""


def ev(start, dur, head, shape, rest):
    return [start, dur, f"%{head} = f32[{shape}]{{0}} {rest}"]


# microseconds on the device's clock (the JSON holds nanoseconds)
RECORDED = {
    "modules": {"0": [
        [0, 100, "jit_chunk(111)"], [200, 100, "jit_chunk(222)"],
        [400, 100, "jit_step(333)"], [600, 100, "jit_step(333)"],
        [800, 50, "jit_other(9)"]]},
    "ops": {"0": [
        # the small bucket's chunk: a while of 90 spanning 30 + 20
        [5, 90, "%while.3 = (s32[], f32[8]{0}) while(%tuple.0)"],
        ev(10, 30, "fusion.1", 8, "fusion(f32[8]{0} %copy.2), kind=kLoop"),
        ev(50, 20, "copy.2", 8, "copy(f32[8]{0} %x)"),
        # the large bucket's: the same names, other types
        ev(210, 60, "fusion.1", 32, "fusion(f32[32]{0} %copy.2)"),
        ev(275, 5, "weird.5", 32, "negate(f32[32]{0} %a)"),
        # outside every execution: left out
        ev(350, 25, "fusion.1", 8, "fusion(f32[8]{0} %copy.2), kind=kLoop"),
        # two decode steps
        ev(400, 40, "decode_mlp_block.9", 8, CALL),
        ev(445, 10, "psum.14", 8, "all-reduce(f32[8]{0} %decode_mlp_block.9)"),
        ev(460, 6, "sort.4", 8, "sort(f32[8]{0} %psum.14)"),
        ev(470, 24, "ragged-dot-none.1", 8,
           'custom-call(f32[8]{0} %sort.4), custom_call_target="ragged_dot"'),
        ev(600, 40, "decode_mlp_block.9", 8, CALL),
        ev(645, 10, "psum.14", 8, "all-reduce(f32[8]{0} %decode_mlp_block.9)"),
        ev(660, 6, "sort.4", 8, "sort(f32[8]{0} %psum.14)"),
        ev(670, 24, "ragged-dot-none.1", 8,
           'custom-call(f32[8]{0} %sort.4), custom_call_target="ragged_dot"'),
        ev(700, 20, "fusion.1", 8, "fusion(f32[8]{0} %copy.2), kind=kLoop"),
        # a program nothing asks about
        ev(810, 30, "fusion.1", 8, "fusion(f32[8]{0} %copy.2), kind=kLoop"),
    ]},
    "host": [],
}
PATTERNS = {"decode": r"^jit_step\(", "prefill": r"^jit_chunk\("}


@pytest.fixture
def recorded(tmp_path):
    us = json.loads(json.dumps(RECORDED))
    for line in ("modules", "ops"):
        us[line]["0"] = [[s * 1e3, d * 1e3, n] for s, d, n in us[line]["0"]]
    path = tmp_path / "trace.json"
    path.write_text(json.dumps(us))
    return T.Trace.from_json(str(path))


@pytest.fixture
def registry(monkeypatch):
    """The program's registry, replaced by a hand-made one: two chunk
    programs whose ``fusion.1`` lie in different scopes, one decode
    program."""
    reg = programs.ProgramRegistry()
    monkeypatch.setattr(programs, "REGISTRY", reg)
    scoped_ms._JOINS.clear()
    return reg


def fill(reg):
    reg.add_text(module("chunk", 8, "layer/qkv"))
    reg.add_text(module("chunk", 32, "layer/mlp"))
    reg.add_text(module("step", 8, "layer/attn_out"))


def sources(trace, **more):
    return {"trace": trace, "programs": PATTERNS, **more}


def lines(capsys):
    return [json.loads(l)["program_scopes"]
            for l in capsys.readouterr().out.splitlines()
            if l.startswith('{"program_scopes"')]


def test_two_chunk_programs_are_kept_apart(recorded, registry, capsys):
    fill(registry)
    src = sources(recorded)
    qkv = scoped_ms.read(src, {"program": "prefill",
                               "scopes": ["layer/qkv"]})
    mlp = scoped_ms.read(src, {"program": "prefill",
                               "scopes": ["layer/mlp"]})
    # two executions: fusion.1 (30) and the copy made for it (20) in
    # the small bucket's, fusion.1 (60) in the large one's
    assert qkv == pytest.approx((30 + 20) / 2 * 1e-3)
    assert mlp == pytest.approx(60 / 2 * 1e-3)
    # a pattern over the names' last part
    both = scoped_ms.read(src, {"program": "prefill", "scopes": ["layer/*"]})
    assert both == pytest.approx(qkv + mlp)
    tables = lines(capsys)               # once a noted program that ran
    assert [(t["program"], t["module"], t["executions"])
            for t in tables] == [("decode", "jit_step", 2),
                                 ("prefill", "jit_chunk", 1),
                                 ("prefill", "jit_chunk", 1)]
    small, large = tables[1:]
    assert small["execution_ms"] == large["execution_ms"] == 0.1
    ms = dict(small["ms"])
    assert ms["layer/qkv"] == pytest.approx(0.050)
    assert ms["layers"] == pytest.approx(0.040)     # the while's own 40
    assert small["top_unnamed"] == []
    assert dict(large["ms"])[scoped_ms.UNNAMED] == pytest.approx(0.005)
    assert large["top_unnamed"] == [["weird.5", pytest.approx(0.005)]]
    assert large["top"][0][:2] == ["fusion.1", "layer/mlp"]
    # asked again: the join is made once, nothing is printed again
    scoped_ms.read(src, {"program": "decode", "scopes": ["layer/mlp"]})
    assert lines(capsys) == []


def test_a_program_the_session_never_saw_is_borne(recorded, registry,
                                                  capsys):
    """The traced window ran no chunk of the large bucket before the
    session closed on the registry's side (not noted): its executions
    join nothing and are left out, the small bucket's still read."""
    registry.add_text(module("chunk", 8, "layer/qkv"))
    registry.add_text(module("step", 8, "layer/attn_out"))
    src = sources(recorded)
    qkv = scoped_ms.read(src, {"program": "prefill",
                               "scopes": ["layer/qkv"]})
    assert qkv == pytest.approx((30 + 20) / 1 * 1e-3)   # one execution
    assert scoped_ms.read(src, {"program": "prefill",
                                "scopes": ["layer/mlp"]}) == 0.0
    assert [(t["program"], t["executions"]) for t in lines(capsys)] \
        == [("decode", 2), ("prefill", 1)]


def test_operation_outside_every_execution_is_left_out(recorded, registry):
    fill(registry)
    src = sources(recorded)
    everything = scoped_ms.read(src, {"program": "prefill",
                                      "scopes": ["*"]})
    # 30 + 20 + 40 (the while) + 60; weird.5 has no scope; the
    # fusion.1 at 350 us ran inside no execution
    assert everything == pytest.approx(150 / 2 * 1e-3)
    step = scoped_ms.read(src, {"program": "decode", "scopes": ["*"]})
    # ... and the one at 700 us starts as the second step ends
    assert step == pytest.approx(80 * 1e-3)


def test_xla_made_is_neither_launch_nor_collective(recorded, registry):
    fill(registry)
    got = scoped_ms.read(sources(recorded),
                         {"program": "decode", "select": "xla_made"})
    # of 40 + 10 + 6 + 24 a step: the sort and XLA's own grouped product
    assert got == pytest.approx(30 * 1e-3)
    assert scoped_ms.is_launch("%k.1 = f32[8]{0} " + CALL)
    assert not scoped_ms.is_launch(
        '%r = f32[8]{0} custom-call(f32[8]{0} %a), '
        'custom_call_target="ragged_dot"')
    assert not scoped_ms.is_launch("%custom-call.3 = f32[8]{0} add()")


def test_coverage_is_the_share_that_resolves(recorded, registry):
    fill(registry)
    src = sources(recorded)
    assert scoped_share_pct.read(src, {"program": "decode"}) == 100.0
    # the large bucket's weird.5 (5 of 155) resolves to nothing
    assert scoped_share_pct.read(src, {"program": "prefill"}) \
        == pytest.approx(100 * 150 / 155)
    assert scoped_share_pct.read(
        src, {"program": "decode", "scopes": ["moe_experts"]}) \
        == pytest.approx(100 * 30 / 80)


def test_scoped_roofline_hands_the_scope_to_scope_roofline(recorded,
                                                           registry, capsys):
    fill(registry)
    cost = types.SimpleNamespace(
        KERNELS={"moe_experts": lambda model, shape: (2.0, shape["slots"])},
        least_seconds=lambda flops, moved, peak: (moved / peak["bw"],
                                                  "bytes"))
    src = sources(recorded, shape={"slots": 3.0}, peak={"bw": 1e6},
                  model={}, cost_model=cost, traced={})
    args = {"program": "decode", "cost": "moe_experts",
            "scopes": ["moe_experts"]}
    # least 3 us a step, measured 6 + 24 us a step
    assert scoped_roofline.read(src, args) == pytest.approx(10.0)
    said = [json.loads(l)["roofline"]
            for l in capsys.readouterr().out.splitlines()
            if l.startswith('{"roofline"')]
    assert said[0]["operations"] == 4 and said[0]["executions"] == 2
    assert said[0]["measured_s"] == pytest.approx(60e-6)
    # through roofline_counted, where the cost model wants a counter
    counted = harness.plugin("reducers", "roofline_counted").read(
        {**src, "traced": {"engine0": {"a": 0, "b": 0},
                           "engine1": {"a": 12, "b": 2}}},
        {"shape": {"slots": ["a", "b"]}, "reducer": "scoped_roofline",
         "args": args})
    assert counted == pytest.approx(20.0)
    # a scope nothing ran under, a rehearsal without a peak
    assert scoped_roofline.read(src, {**args, "scopes": ["ssd_scan"]}) \
        is None
    assert scoped_roofline.read({**src, "peak": None}, args) is None


@pytest.mark.parametrize("reader", [scoped_ms, scoped_share_pct,
                                    scoped_roofline])
def test_none_where_there_is_nothing_to_read(recorded, registry, reader,
                                             monkeypatch):
    args = {"program": "decode", "scopes": ["layer/mlp"],
            "cost": "moe_experts"}
    # an empty registry: a run that noted nothing
    assert reader.read(sources(recorded), args) is None
    fill(registry)
    # no XLA Modules line (a rehearsal on the CPU), no trace, a program
    # the configuration does not name, executions of no noted program
    bare = T.Trace(recorded.ops, {}, [])
    assert reader.read(sources(bare), args) is None
    assert reader.read(sources(None), args) is None
    assert reader.read(sources(recorded), {**args, "program": "train"}) \
        is None
    assert reader.read({"trace": recorded,
                        "programs": {"decode": r"^jit_other\("}},
                       args) is None
    # a commit of the program without the registry
    monkeypatch.setattr(scoped_ms, "registry", lambda: None)
    scoped_ms._JOINS.clear()
    assert reader.read(sources(recorded), args) is None


NEW = ["decode_xla_ms.thr", "decode_xla_ms.lat", "decode_xla_ms.tp4",
       "moe_experts_scope_roofline", "moe_experts_scope_roofline.m2",
       "optimizer_ms.train", "scope_coverage_pct.thr",
       "chunk_attention_ms.lat", "chunk_kv_view_ms.tp4"]


@pytest.mark.parametrize("name", NEW)
def test_new_metric_names_its_reader_and_a_program_of_its_cell(name):
    bench = harness.load_benchmark()
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    spec = harness.load_json("layer_metrics", name + ".json")
    reader = harness.plugin("reducers", spec["reducer"])
    assert reader in (scoped_ms, scoped_share_pct, scoped_roofline)
    for cell in entry["workloads"]:
        cfg = harness.load_json(
            "configs", harness.find_cell(bench, cell)["config"] + ".json")
        assert spec["args"]["program"] in cfg["program"]["programs"]
        if "cost" in spec["args"]:
            cm = harness.plugin("cost_models", cfg["cost_model"])
            assert spec["args"]["cost"] in cm.KERNELS
    for scope in spec["args"].get("scopes", ()):
        assert any(scoped_ms.fnmatch.fnmatchcase(s, scope)
                   for s in programs.PROGRAM_SCOPES), scope
