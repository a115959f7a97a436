"""paddle_tpu.analysis unit tests: each rule pass against a minimal
program that exhibits (and one that avoids) its bug class, the program
registry, and the component audit hooks (Trainer / ServingEngine /
fused Optimizer). The marquee case is the auditor self-test: the
dtype-promotion rule must flag the VERBATIM pre-fix AdamW update (the
bug that motivated the whole subsystem) and stay silent on the fixed
one."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.analysis import (Finding, ProgramRegistry, ProgramSpec,
                                 abstract_signature, audit_program,
                                 audit_spec, diff_findings,
                                 findings_to_json, load_baseline,
                                 publish_findings, write_baseline)
from paddle_tpu.analysis.catalog import build_demo_regression

pytestmark = pytest.mark.audit

F32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731


def _codes(report):
    return sorted(f.code for f in report.findings)


# -- rule 1: dtype promotion --------------------------------------------

def test_dtype_rule_flags_prefix_adamw_and_not_fixed():
    """The auditor self-test (the rule catches the bug that motivated
    it): pre-fix `1 - b1 ** step` flagged as F64_PROMOTION, the
    shipped fp32-bias-correction `_adamw_update` silent."""
    from paddle_tpu.distributed.trainer import _adamw_update
    rep = audit_spec(build_demo_regression())
    assert "F64_PROMOTION" in _codes(rep)
    f = next(f for f in rep.findings if f.code == "F64_PROMOTION")
    assert f.severity == "error"
    assert f.rule == "dtype_promotion"

    def fixed_step(state, g):
        new_state, gnorm = _adamw_update(g, state, jnp.float32(1e-3))
        return new_state, gnorm

    state = ((F32(8, 4),), (F32(8, 4),), (F32(8, 4),), (F32(8, 4),),
             jax.ShapeDtypeStruct((), jnp.int32))
    rep2 = audit_program(jax.jit(fixed_step), state, (F32(8, 4),),
                         name="fixed_adamw",
                         carry={i: i for i in range(5)})
    assert rep2.findings == []


def test_dtype_rule_silent_when_inputs_are_f64():
    """A genuinely-f64 program (x64 user feeding f64 state) is not a
    promotion bug."""
    def f(x):
        return x * 2.0
    rep = audit_program(jax.jit(f),
                        jax.ShapeDtypeStruct((8,), jnp.float64),
                        name="native_f64")
    assert rep.findings == []


def test_dtype_rule_bf16_upcast_threshold():
    def f(x):
        return x.astype(jnp.float32).sum()
    big = jax.ShapeDtypeStruct((2048, 2048), jnp.bfloat16)  # 16 MiB f32
    rep = audit_program(jax.jit(f), big, name="upcast",
                        config={"dtype_promotion_rule":
                                {"upcast_min_bytes": 1 << 20}})
    assert "BF16_UPCAST_BLOAT" in _codes(rep)
    # same program, default 8 MiB threshold on a small operand: silent
    small = jax.ShapeDtypeStruct((16, 16), jnp.bfloat16)
    rep2 = audit_program(jax.jit(f), small, name="upcast_small")
    assert rep2.findings == []


# -- rule 2: donation ---------------------------------------------------

def test_donation_rule_donated_unaliased():
    def f(a):
        return jnp.float32(a.sum())          # no output matches a
    rep = audit_program(jax.jit(f, donate_argnums=(0,)), F32(64, 64),
                        name="dead_donation")
    assert _codes(rep) == ["DONATED_UNALIASED"]


def test_donation_rule_donatable_not_donated():
    def f(a):
        return a + 1.0
    big = F32(1024, 1024)                    # 4 MiB, state-shaped
    rep = audit_program(jax.jit(f), big, name="missed_donation")
    assert _codes(rep) == ["DONATABLE_NOT_DONATED"]
    # donated: clean
    rep2 = audit_program(jax.jit(f, donate_argnums=(0,)), big,
                         name="donated")
    assert rep2.findings == []
    # below the large-state threshold: not worth a finding
    rep3 = audit_program(jax.jit(f), F32(8, 8), name="small_state")
    assert rep3.findings == []


# -- rule 3: retrace hazards --------------------------------------------

def test_retrace_rule_multiple_signatures():
    def f(x):
        return x + 1
    spec = ProgramSpec(name="sig_drift", fn=jax.jit(f),
                       args=(F32(4, 4),))
    spec.record_signature()
    spec.record_signature((F32(8, 4),), {})       # second distinct sig
    rep = audit_spec(spec)
    assert "MULTIPLE_SIGNATURES" in _codes(rep)
    # recording the SAME signature twice dedups: no finding
    spec2 = ProgramSpec(name="sig_stable", fn=jax.jit(f),
                        args=(F32(4, 4),))
    spec2.record_signature()
    spec2.record_signature()
    assert "MULTIPLE_SIGNATURES" not in _codes(audit_spec(spec2))


def test_retrace_rule_float_static_arg():
    def f(x, scale):
        return x * scale
    spec = ProgramSpec(name="float_static",
                       fn=jax.jit(f, static_argnums=(1,)),
                       args=(F32(4,), 0.5),
                       static_argnums=(1,), static_argvals=(0.5,))
    rep = audit_spec(spec)
    assert "FLOAT_STATIC_ARG" in _codes(rep)


def test_retrace_rule_carry_drift():
    rep = audit_spec(build_demo_regression())
    drift = [f for f in rep.findings if f.code == "CARRY_DTYPE_DRIFT"]
    assert len(drift) == 1                  # exactly the master leaf
    assert drift[0].detail["out_aval"].startswith("float64")
    assert drift[0].detail["in_aval"].startswith("float32")
    assert drift[0].severity == "error"


# -- rule 4: collective consistency -------------------------------------

def _mesh22():
    from jax.sharding import Mesh
    return Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "tp"))


def test_collective_rule_unknown_axis():
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.core.jax_compat import shard_map
    mesh = _mesh22()

    def body(x):
        return jax.lax.psum(x, "dp")

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp", "tp"),
                           out_specs=P(None, "tp"), check_vma=False))
    # clean: axis exists in the shard_map mesh
    rep = audit_program(fn, F32(8, 8), name="psum_ok")
    assert rep.findings == []
    # a bare collective with no enclosing mesh and no declared axes
    def naked(x):
        return jax.lax.psum(x, "model")
    spec = ProgramSpec(name="naked_psum", fn=naked, args=(F32(4),),
                       mesh_axes=("dp",))
    rep2 = audit_spec(spec)
    codes = _codes(rep2)
    assert "UNKNOWN_COLLECTIVE_AXIS" in codes or "TRACE_ERROR" in codes


def test_collective_rule_cond_divergence():
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.core.jax_compat import shard_map
    mesh = _mesh22()

    def body(x):
        y = jax.lax.psum(x, "dp")

        def yes(v):
            return jax.lax.psum(v, "tp")

        def no(v):
            return v * 2.0

        return jax.lax.cond(y[0, 0] > 0, yes, no, y)

    fn = jax.jit(shard_map(body, mesh=mesh, in_specs=P("dp", "tp"),
                           out_specs=P(), check_vma=False))
    rep = audit_program(fn, F32(8, 8), name="cond_div")
    assert "COND_COLLECTIVE_DIVERGENCE" in _codes(rep)
    f = next(f for f in rep.findings
             if f.code == "COND_COLLECTIVE_DIVERGENCE")
    assert f.detail["branch_sequences"] in (
        [[], ["psum@tp"]], [["psum@tp"], []])


# -- rule 5: constant bloat ---------------------------------------------

def test_constant_bloat_rule():
    C = jnp.ones((640, 640), jnp.float32)          # ~1.6 MiB

    def f(x):
        return x + C

    rep = audit_program(jax.jit(f), F32(640, 640), name="const_heavy")
    codes = _codes(rep)
    assert "LARGE_CONSTANT" in codes
    # passed as an argument instead: clean
    def g(x, c):
        return x + c
    rep2 = audit_program(jax.jit(g), F32(640, 640), F32(640, 640),
                         name="const_arg")
    assert "LARGE_CONSTANT" not in _codes(rep2)


# -- finding schema / baseline / registry -------------------------------

FINDING_KEYS = {"rule", "code", "severity", "program", "site",
                "message", "detail", "fingerprint"}


def test_finding_schema_frozen():
    rep = audit_spec(build_demo_regression())
    assert rep.findings
    for f in rep.findings:
        d = f.to_dict()
        assert set(d.keys()) == FINDING_KEYS
        assert d["severity"] in ("error", "warning", "info")
        assert d["fingerprint"] == \
            f"{d['program']}::{d['rule']}::{d['code']}::{d['site']}"
    doc = findings_to_json([rep])
    assert set(doc.keys()) == {"version", "programs", "summary"}
    assert set(doc["summary"].keys()) == {"programs", "findings",
                                          "by_severity"}


def test_baseline_roundtrip_and_diff(tmp_path):
    rep = audit_spec(build_demo_regression())
    path = str(tmp_path / "baseline.json")
    write_baseline([rep], path)
    base = load_baseline(path)
    new, fixed = diff_findings([rep], base)
    assert new == [] and fixed == []
    # drop one accepted fingerprint -> that finding is NEW again
    victim = rep.findings[0].fingerprint
    del base["findings"][victim]
    new, fixed = diff_findings([rep], base)
    assert [f.fingerprint for f in new] == [victim]
    # a baseline entry that stopped reproducing -> FIXED
    base["findings"]["ghost::rule::CODE::site"] = {"rule": "rule"}
    _, fixed = diff_findings([rep], base)
    assert fixed == ["ghost::rule::CODE::site"]


def test_broken_baseline_raises(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text('{"version": 99, "findings": {}}')
    with pytest.raises(ValueError, match="version"):
        load_baseline(str(p))


def test_catalog_rejects_unknown_program_names():
    """A typo'd --program must never let the gate pass after auditing
    nothing (exit 0 on zero programs is a vacuous pass)."""
    from paddle_tpu.analysis.catalog import build_catalog
    with pytest.raises(ValueError, match="unknown catalog program"):
        build_catalog(names=["serving-decode"])   # hyphen typo


def test_registry_latest_wins_and_trace_error():
    reg = ProgramRegistry()

    def f(x):
        return x + 1

    reg.register(ProgramSpec(name="p", fn=jax.jit(f), args=(F32(4),)))
    assert "p" in reg and len(reg) == 1
    spec2 = ProgramSpec(name="p", fn=jax.jit(f), args=(F32(8),))
    reg.register(spec2)
    assert reg.get("p") is spec2            # latest registration wins
    # a registered program that cannot trace is itself a finding
    def broken(x):
        raise RuntimeError("boom")
    rep = audit_spec(ProgramSpec(name="b", fn=broken, args=(F32(4),)))
    assert _codes(rep) == ["TRACE_ERROR"]
    assert rep.findings[0].severity == "error"


def test_registry_reregister_keeps_signatures_for_same_fn():
    """Re-registering the SAME callable under the same name (e.g.
    Trainer.audit after the observed step recorded compile signatures)
    must keep the recorded history — wiping it would blind
    MULTIPLE_SIGNATURES — while a different callable starts fresh (a
    stranger's signatures would fabricate drift)."""
    reg = ProgramRegistry()
    jf = jax.jit(lambda x: x + 1)
    spec = reg.register(ProgramSpec(name="p", fn=jf, args=(F32(4),)))
    spec.record_signature((F32(8),), {})      # observed drift
    assert len(spec.signatures) == 2
    again = reg.register(ProgramSpec(name="p", fn=jf, args=(F32(4),)))
    assert len(again.signatures) == 2         # history preserved
    assert "MULTIPLE_SIGNATURES" in _codes(audit_spec(again))
    other = reg.register(
        ProgramSpec(name="p", fn=jax.jit(lambda x: x * 2),
                    args=(F32(4),)))
    assert len(other.signatures) == 1         # new program, no ghosts


def test_publish_findings_counter():
    rep = audit_spec(build_demo_regression())
    counters = {}
    n = publish_findings(rep, counters=counters)
    assert n == len(rep.findings) > 0        # demo: errors + a warning
    assert counters["audit_findings"] == n
    publish_findings([], counters=counters)
    assert counters["audit_findings"] == n   # accumulates, not resets
    # info findings are advisory report detail, not a counter signal
    # (the intentional master-weight bf16->f32 upcast must not read as
    # a bench regression)
    info = Finding(rule="dtype_promotion", code="BF16_UPCAST_BLOAT",
                   severity="info", program="p", message="m")
    assert publish_findings([info], counters=counters) == 0
    assert counters["audit_findings"] == n


# -- component audit hooks ----------------------------------------------

def test_serving_engine_audit_clean_and_counters_restored():
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.llama import LlamaConfig, init_params
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=2,
                      max_position_embeddings=32, remat=False)
    eng = ServingEngine(init_params(cfg, jax.random.PRNGKey(0)), cfg,
                        capacity=2, block_size=8, max_seq_len=32,
                        prefill_buckets=(8,), prefix_cache=True)
    before = {"decode": eng.counters["decode_traces"],
              "prefill": dict(eng.counters["prefill_traces"])}
    reports = eng.audit()
    assert {r.program for r in reports} == {
        "serving_decode", "serving_prefill_8", "serving_page_copy"}
    assert all(r.findings == [] for r in reports)
    # tracing fresh program instances must not disturb the trace
    # counters the tier-1 suite pins
    assert eng.counters["decode_traces"] == before["decode"]
    assert eng.counters["prefill_traces"] == before["prefill"]
    assert eng.counters["audit_findings"] == 0


def test_fused_optimizer_audit_after_step():
    from paddle_tpu.optimizer import AdamW
    w = paddle.to_tensor(np.ones((16, 16), np.float32),
                         stop_gradient=False)
    opt = AdamW(learning_rate=1e-3, parameters=[w], weight_decay=0.01)
    with pytest.raises(RuntimeError, match="one optimizer step"):
        opt.audit_spec()
    (w.sum()).backward()
    opt.step()
    rep = opt.audit()
    assert rep.program == "fused_optimizer_step"
    assert rep.findings == []


def test_trainer_audit_registers_and_is_clean():
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.analysis import REGISTRY
    from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                make_mesh)
    from paddle_tpu.models.llama import (LlamaConfig, init_params,
                                         loss_fn, param_shardings)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=2,
                      max_position_embeddings=16, remat=False)
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    tr = Trainer(lambda p, t, l: loss_fn(p, t, l, cfg), mesh,
                 param_shardings(mesh, cfg), data_spec=P())
    state = tr.init_state(init_params(cfg, jax.random.PRNGKey(0)))
    toks = np.zeros((2, 16), np.int32)
    rep = tr.audit(state, toks, toks)
    assert rep.findings == []
    assert tr.counters["audit_findings"] == 0
    spec = REGISTRY.get("train_step")
    assert spec is not None and spec.carry    # registered with carry map


def test_observed_trainer_drift_surfaces_as_multiple_signatures():
    """The observed trainer registers its spec at first compile and
    records every later compile's signature, so a real mid-run batch
    drift survives Trainer.audit()'s re-registration (same fn merges
    history) and the retrace rule reports it. A FRESH trainer under
    the same registry name must not inherit those signatures."""
    import warnings
    from jax.sharding import PartitionSpec as P
    from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                                make_mesh)
    from paddle_tpu.models.llama import (LlamaConfig, init_params,
                                         loss_fn, param_shardings)
    cfg = LlamaConfig(vocab_size=64, hidden_size=32,
                      intermediate_size=64, num_hidden_layers=2,
                      num_attention_heads=2, num_key_value_heads=2,
                      max_position_embeddings=16, remat=False)
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])

    def make():
        tr = Trainer(lambda p, t, l: loss_fn(p, t, l, cfg), mesh,
                     param_shardings(mesh, cfg), data_spec=P(),
                     observability=True)
        return tr, tr.init_state(init_params(cfg, jax.random.PRNGKey(0)))

    tr, state = make()
    t1 = np.zeros((2, 8), np.int32)
    t2 = np.zeros((4, 8), np.int32)           # drifted batch shape
    state, _ = tr.step(state, t1, t1)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        state, _ = tr.step(state, t2, t2)
    codes = _codes(tr.audit(state, t2, t2))
    assert "MULTIPLE_SIGNATURES" in codes
    tr2, state2 = make()
    state2, _ = tr2.step(state2, t1, t1)
    assert "MULTIPLE_SIGNATURES" not in _codes(
        tr2.audit(state2, t1, t1))            # no cross-trainer ghosts
