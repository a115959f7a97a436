"""Kernel-geometry auditor: capture layer, rule passes, the tier-1
gate vs the committed KERNEL_AUDIT_BASELINE.json, the CLI contract,
and the registry-wide pallas-vs-fallback differential sweep."""
import glob
import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.kernel_audit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "kernel_audit.py")
COMMITTED_BASELINE = os.path.join(REPO, "KERNEL_AUDIT_BASELINE.json")

# importing the kernel modules registers every op (the differential
# sweep and the coverage assertions iterate the live registry)
from paddle_tpu.ops.pallas import (fused_adamw as fa,           # noqa: E402
                                   fused_decode_block as fdb,
                                   fused_prefill_block as fpb,
                                   fused_train as ft, norms)
from paddle_tpu.ops import moe_experts as _moe  # noqa: E402,F401 — its op
from paddle_tpu.ops.pallas._util import (KernelLaunchSpec,      # noqa: E402
                                         KernelOperand,
                                         capture_kernel_launches)
from paddle_tpu.ops.pallas.registry import KERNELS              # noqa: E402


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, CLI, *args],
                          capture_output=True, text=True, env=env,
                          timeout=600)


# -- the tier-1 gate (in-process: ONE capture+audit of the catalog,
# shared by the gate and coverage assertions) --------------------------

@pytest.fixture(scope="module")
def catalog_reports():
    from paddle_tpu.analysis.kernel_catalog import audit_kernels
    return audit_kernels()


def test_kernel_audit_gate_catalog_clean_vs_committed_baseline(
        catalog_reports):
    """THE gate: every kernel case (tiny + flagship serving/training
    shape classes) plus the registry lint, audited against the
    committed baseline — no new findings. A regression here means a
    kernel's launch geometry (grid coverage, bounds, write
    injectivity, VMEM windows, dispatch-key coverage) changed in a way
    the baseline does not accept."""
    from paddle_tpu.analysis import diff_findings, load_baseline
    baseline = load_baseline(COMMITTED_BASELINE)
    new, _fixed = diff_findings(catalog_reports, baseline)
    assert new == [], "\n".join(
        f"{f.fingerprint}: {f.message}" for f in new)


def test_demo_regression_fails_the_gate_in_process():
    """The injected pre-fix non-divisor block_f kernel must produce
    NEW GRID_FLOOR_DROP findings vs the committed baseline — the gate
    can actually fail on the review-caught bug class."""
    from paddle_tpu.analysis import diff_findings, load_baseline
    from paddle_tpu.analysis.kernel_catalog import (
        build_demo_kernel_regression)
    rep = build_demo_kernel_regression()
    new, _ = diff_findings([rep], load_baseline(COMMITTED_BASELINE))
    assert {f.code for f in new} == {"GRID_FLOOR_DROP"}
    assert len(new) >= 2            # wg AND wu tails are both dropped


# -- coverage: no unaudited pallas_call --------------------------------

def test_every_pallas_call_site_routes_through_the_capture_layer():
    """Static proof that no kernel can dodge the auditor: the ONLY
    ``pl.pallas_call`` call site under ops/pallas/ is the
    audited_pallas_call gateway in _util.py itself."""
    offenders = {}
    root = os.path.join(REPO, "paddle_tpu", "ops", "pallas")
    for path in glob.glob(os.path.join(root, "**", "*.py"),
                          recursive=True):
        with open(path) as fh:
            hits = len(re.findall(r"pl\.pallas_call\s*\(", fh.read()))
        if hits and os.path.relpath(path, root) != "_util.py":
            offenders[os.path.relpath(path, root)] = hits
    assert offenders == {}, (
        f"direct pl.pallas_call site(s) outside audited_pallas_call: "
        f"{offenders} — route them through ops/pallas/_util."
        f"audited_pallas_call so the geometry auditor sees them")


def test_catalog_captures_every_declared_kernel(catalog_reports):
    """Dynamic proof: tracing the catalog captures a KernelLaunchSpec
    for every declared launch name (COVERAGE_GAP findings would fail
    the gate test; this pins the declared set itself so a NEW kernel
    that never joins the catalog is caught too)."""
    from paddle_tpu.analysis.kernel_catalog import ALL_KERNEL_NAMES
    assert ALL_KERNEL_NAMES == {
        "rms_norm_fwd", "rms_norm_bwd", "residual_rms_norm_fwd",
        "layer_norm_fwd", "fused_adamw", "paged_attention_decode",
        "flash_attention_fwd", "flash_attention_bwd_dq",
        "flash_attention_bwd_dkv",
        "decode_mlp_block", "prefill_attn_block",
        "linear_ce_fwd", "linear_ce_bwd_dx", "linear_ce_bwd_dh",
        "swiglu_fwd", "swiglu_bwd",
        # PR 27: the Mamba-2 state pool's launches (ops/pallas/mamba2.py)
        "ssm_update", "ssm_state_read", "ssm_state_write"}
    captured = set()
    for r in catalog_reports:
        assert not any(f.code in ("COVERAGE_GAP", "TRACE_ERROR")
                       for f in r.findings), r.to_dict()
        captured.update(r.meta.get("kernels", []))
    assert captured == set(ALL_KERNEL_NAMES)


def test_registry_ops_all_have_lint_metas_and_key_declarations():
    """Every registered op is covered by the registry lint's sample
    metas AND carries a declare_cache_key declaration — an op added
    without either shows up here before it ships."""
    from paddle_tpu.analysis.kernel_catalog import _lint_metas
    metas = _lint_metas()
    assert set(KERNELS.ops()) == set(metas)
    for op in KERNELS.ops():
        assert KERNELS.cache_key_decl(op) is not None, op


# -- rule unit tests on synthetic launches ------------------------------

def _spec(grid, outs, ins=(), scratch=(), accum=(), prefetch=(),
          nsp=0, budget=10 << 20, kernel=None):
    return KernelLaunchSpec(
        name="synthetic", grid=tuple(grid), num_scalar_prefetch=nsp,
        prefetch=tuple(prefetch), inputs=tuple(ins),
        outputs=tuple(outs), scratch=tuple(scratch),
        accum_outputs=tuple(accum), vmem_budget=budget,
        interpret=True, kernel=kernel)


def _op(shape, block, index_map, dtype="float32", space="vmem"):
    return KernelOperand(shape=tuple(shape), dtype=dtype,
                         block_shape=tuple(block) if block else None,
                         index_map=index_map, space=space)


def _codes(findings):
    return sorted(f.code for f in findings)


def test_rule_grid_floor_drop_output_and_input():
    from paddle_tpu.analysis.kernel_rules import check_launch
    # output (128,) in blocks of 32 but the grid only runs 3 steps
    spec = _spec((3,), [_op((128,), (32,), lambda i: (i,))])
    assert _codes(check_launch(spec)) == ["GRID_FLOOR_DROP"]
    # the fused-MLP class: full output, under-read weight input
    spec = _spec((1,),
                 [_op((2, 8), (2, 8), lambda j: (0, 0))],
                 ins=[_op((8, 96), (8, 64), lambda j: (0, j))],
                 accum=(0,))
    found = check_launch(spec)
    assert _codes(found) == ["GRID_FLOOR_DROP"]
    assert found[0].site == "synthetic/in0"
    # divisor grid: silent
    spec = _spec((4,), [_op((128,), (32,), lambda i: (i,))])
    assert check_launch(spec) == []


def test_rule_coverage_exempts_an_aliased_output():
    """An in-place launch writes one part of a pool that is aliased to
    its output: the blocks it does not write keep the input's contents
    (ops/pallas/mamba2.py updates one layer of the state pool so)."""
    import dataclasses
    from paddle_tpu.analysis.kernel_rules import check_launch
    pool = _op((2, 128), (1, 32), lambda i: (1, i))
    spec = _spec((4,), [pool], ins=[pool])
    assert _codes(check_launch(spec)) == ["GRID_FLOOR_DROP"] * 2
    spec = dataclasses.replace(spec, num_scalar_prefetch=1,
                               input_output_aliases={1: 0})
    assert check_launch(spec) == []


def test_rule_input_coverage_exempts_scalar_prefetch_launches():
    """Paged kernels read live pages only — data-dependent input
    coverage must not false-positive."""
    from paddle_tpu.analysis.kernel_rules import check_launch
    spec = _spec(
        (2,),
        [_op((2, 4), (1, 4), lambda b, bt: (b, 0))],
        ins=[_op((16, 4), (1, 4), lambda b, bt: (int(bt[b]), 0))],
        prefetch=[((2,), "int32")], nsp=1)
    assert check_launch(spec) == []


def test_rule_oob_block():
    from paddle_tpu.analysis.kernel_rules import check_launch
    # off-by-one index map: block 4 starts at 128 >= extent 128
    spec = _spec((4,), [_op((128,), (32,), lambda i: (i,))],
                 ins=[_op((128,), (32,), lambda i: (i + 1,))])
    assert "OOB_BLOCK" in _codes(check_launch(spec))
    # a partially overhanging LAST block is legal (Pallas masks it)
    spec = _spec((4,), [_op((100,), (32,), lambda i: (i,))])
    assert check_launch(spec) == []


def test_rule_write_race_requires_declared_accumulation():
    from paddle_tpu.analysis.kernel_rules import check_launch
    out = _op((2, 8), (2, 8), lambda j: (0, 0))
    ins = [_op((8, 64), (8, 32), lambda j: (0, j))]
    undeclared = _spec((2,), [out], ins=ins)
    assert _codes(check_launch(undeclared)) == ["WRITE_RACE"]
    declared = _spec((2,), [out], ins=ins, accum=(0,))
    assert check_launch(declared) == []


def test_rule_vmem_overcommit_window_model(monkeypatch):
    from paddle_tpu.analysis.kernel_rules import check_launch
    # 2 varying f32 (1024, 1024) blocks = 2 x 2 x 4MiB = 16MiB, plus a
    # 4MiB scratch -> 20MiB > the 16MiB envelope
    big = lambda: _spec(  # noqa: E731
        (4,),
        [_op((4096, 1024), (1024, 1024), lambda i: (i, 0))],
        ins=[_op((4096, 1024), (1024, 1024), lambda i: (i, 0))],
        scratch=[((1024, 1024), "float32", "vmem")])
    found = check_launch(big())
    assert _codes(found) == ["VMEM_OVERCOMMIT"]
    assert found[0].detail["need_bytes"] == 20 << 20
    # a constant-index block is resident once, not double-buffered:
    # 2 x 4MiB const + 4MiB scratch = 12MiB fits
    const = _spec(
        (4,),
        [_op((1024, 1024), (1024, 1024), lambda i: (0, 0))],
        ins=[_op((1024, 1024), (1024, 1024), lambda i: (0, 0))],
        scratch=[((1024, 1024), "float32", "vmem")], accum=(0,))
    assert check_launch(const) == []
    # an operator-raised fused budget raises the envelope with it
    monkeypatch.setenv("PADDLE_TPU_SCOPED_VMEM_BUDGET", str(32 << 20))
    assert check_launch(big()) == []


def test_rule_vmem_resident_share_in_combined_launches():
    """Combined multi-window launches (the single-launch decode block:
    page operands streamed per grid step, the weight windows + scratch
    resident for the whole launch) must ALSO fit their resident share
    under the per-launch dispatch budget — the streamed double-buffer
    envelope alone would let an oversized resident set sneak through.
    All-resident launches keep the historic envelope-only contract
    (the const spec in the window-model test above)."""
    from paddle_tpu.analysis.kernel_rules import check_launch
    const_w = _op((1024, 1024), (1024, 1024), lambda i: (0, 0))
    streamed = _op((4096, 8), (1024, 8), lambda i: (i, 0))
    spec = _spec((4,), [_op((4, 8), (1, 8), lambda i: (i, 0))],
                 ins=[const_w, const_w, streamed],
                 scratch=[((1024, 1024), "float32", "vmem")])
    found = check_launch(spec)    # 2x4MiB const + 4MiB scratch > 10MiB
    assert _codes(found) == ["VMEM_OVERCOMMIT"]
    assert found[0].site == "synthetic/resident"
    assert found[0].detail["resident_bytes"] == 12 << 20
    # the same launch under a budget that holds its resident share
    roomy = _spec((4,), [_op((4, 8), (1, 8), lambda i: (i, 0))],
                  ins=[const_w, const_w, streamed],
                  scratch=[((1024, 1024), "float32", "vmem")],
                  budget=16 << 20)
    assert check_launch(roomy) == []


def test_rule_vmem_counts_prefetch_streamed_pages_double_buffered():
    """A page operand whose index map derefs the prefetch table
    collapses to page 0 on the all-zero sample — the window model must
    still charge it as streamed (2x double-buffered, probed on the
    ramp sample), or a pipelining kernel sneaks under the envelope."""
    from paddle_tpu.analysis.kernel_rules import check_launch
    page = _op((64, 1024, 1024), (1, 1024, 1024),
               lambda b, bt: (int(bt[b]), 0, 0))       # 4MiB f32 page
    out = _op((4, 8), (1, 8), lambda b, bt: (b, 0))
    spec = _spec((4,), [out], ins=[page, page, page],
                 prefetch=[((4,), "int32")], nsp=1,
                 scratch=[((1024, 1024), "float32", "vmem")])
    found = check_launch(spec)    # 3 pages x2x4MiB + 4MiB scratch
    assert _codes(found) == ["VMEM_OVERCOMMIT"]
    assert found[0].detail["need_bytes"] == (28 << 20) + 64  # + out windows


def test_rule_vmem_refuses_the_adamw_block_the_chip_refuses(monkeypatch):
    """``fused_adamw``'s block height is chosen against the scoped
    VMEM: at 4096 rows the chip's compiler refuses every dtype mix
    ("vmem while allocating on stack"), and the window model refuses
    the catalog's cases with it; at the module's ROWS both pass."""
    from paddle_tpu.analysis.kernel_catalog import audit_case, kernel_cases
    cases = [c for c in kernel_cases() if c.name.startswith("fused_adamw@")
             and not c.name.endswith("@tiny")]
    assert len(cases) == 2
    for case in cases:
        assert audit_case(case).findings == []
    monkeypatch.setattr(fa, "ROWS", 4096)
    for case in cases:
        assert _codes(audit_case(case).findings) == ["VMEM_OVERCOMMIT"]


def test_rule_scratch_mismatch():
    from paddle_tpu.analysis.kernel_rules import check_launch

    def kernel(a_ref, b_ref, o_ref):
        pass

    ok = _spec((1,), [_op((8,), (8,), lambda i: (i,))],
               ins=[_op((8,), (8,), lambda i: (i,))] * 2,
               kernel=kernel)
    assert check_launch(ok) == []
    missing = _spec((1,), [_op((8,), (8,), lambda i: (i,))],
                    ins=[_op((8,), (8,), lambda i: (i,))] * 2,
                    scratch=[((8, 8), "float32", "vmem")],
                    kernel=kernel)             # kernel lacks the scratch ref
    assert _codes(check_launch(missing)) == ["SCRATCH_MISMATCH"]
    empty = _spec((1,), [_op((8,), (8,), lambda i: (i,))],
                  scratch=[((0, 8), "float32", "vmem")])
    assert "SCRATCH_MISMATCH" in _codes(check_launch(empty))


def test_rule_dispatch_key_gap():
    from paddle_tpu.analysis.kernel_rules import dispatch_key_rule
    from paddle_tpu.ops.pallas.registry import KernelRegistry
    reg = KernelRegistry()
    reg.register("op", "fancy", lambda: None, priority=10,
                 supports=lambda m: (m["n"] < 8 and not m["hidden_knob"],
                                     "r"))
    reg.register("op", "plain", lambda: None, priority=0)
    meta = {"n": 4, "hidden_knob": False, "dtype": "float32"}
    # undeclared op -> one finding
    found = dispatch_key_rule(reg, "op", meta)
    assert _codes(found) == ["DISPATCH_KEY_GAP"]
    assert found[0].site == "op:undeclared"
    # declaration missing the hidden knob -> the gap is named
    reg.declare_cache_key("op", ("n", "dtype"))
    found = dispatch_key_rule(reg, "op", meta)
    assert len(found) == 1 and found[0].detail["gap"] == ["hidden_knob"]
    # full declaration (via covers aliasing) -> silent
    reg.declare_cache_key("op", ("n", "dtype", "route"),
                          covers={"hidden_knob": "route"})
    assert dispatch_key_rule(reg, "op", meta) == []


def test_fused_train_key_covers_budget_and_interpret(monkeypatch):
    """The trainer/train-step program caches must key on every
    dispatch input the supports() predicates read — the budget env
    knob included (the _PAGED_CACHE stale-route class)."""
    from paddle_tpu.distributed.trainer import _fused_train_key
    k0 = _fused_train_key()
    monkeypatch.setenv("PADDLE_TPU_FUSED_VMEM_BUDGET", str(1 << 20))
    assert _fused_train_key() != k0


# -- CLI contract (subprocess: fast --case subsets) ---------------------

def test_cli_clean_gate_and_json_schema(tmp_path):
    out_json = str(tmp_path / "findings.json")
    r = _run("--case", "fused_swiglu@tiny", "--json", out_json,
             "--quiet")
    assert r.returncode == 0, r.stderr + r.stdout
    with open(out_json) as fh:
        doc = json.load(fh)
    assert set(doc.keys()) == {"version", "programs", "summary"}
    assert list(doc["programs"]) == ["fused_swiglu@tiny"]
    assert doc["summary"]["findings"] == 0


def test_cli_demo_regression_fails_and_banks_json(tmp_path):
    out_json = str(tmp_path / "findings.json")
    r = _run("--case", "rms_norm@tiny", "--demo-regression",
             "--json", out_json)
    assert r.returncode == 2, r.stderr + r.stdout
    assert "GRID_FLOOR_DROP" in r.stderr
    with open(out_json) as fh:
        doc = json.load(fh)
    assert set(doc["programs"]) == {"rms_norm@tiny",
                                    "demo_prefix_mlp_block@tiny"}


def test_cli_bad_invocations_exit_3_and_list_names_cases():
    # kept in one test: each subprocess pays the full package import
    assert _run("--case", "nope", "--quiet").returncode == 3
    assert _run("--write-baseline", "--demo-regression",
                "--quiet").returncode == 3
    # subset --write-baseline over the SHARED baseline would drop every
    # other case's accepted fingerprints
    assert _run("--case", "rms_norm", "--write-baseline",
                "--quiet").returncode == 3
    names = _run("--list").stdout.split()
    assert "rms_norm@tiny" in names
    assert "decode_mlp_block@flagship_serving_int8_weights" in names
    assert "kernel_registry" in names


# -- registry-wide differential sweep (satellite) -----------------------
#
# One parametrized test that sweeps EVERY registered op: the
# pallas_fused variant under interpret vs the priority-0 fallback at
# supports()-boundary shapes (ragged/prime dims, non-divisor tiles,
# hd % 8 edges, the exact VMEM budget edge), asserting numeric parity
# — plus a clean-fallback check that auto dispatch under interpret
# selects the fallback with a human-readable reason.

_RNG = np.random.RandomState(7)


def _f32(*shape):
    return jnp.asarray(_RNG.randn(*shape) * 0.3, jnp.float32)


def _flat(tree):
    return jnp.concatenate(
        [jnp.ravel(t).astype(jnp.float32)
         for t in jax.tree_util.tree_leaves(tree)])


def _diff_rms_norm_bwd():
    x, w, g = _f32(13, 32), _f32(32), _f32(13, 32)   # prime row count
    run = lambda fn: fn(1e-6, (x, w), g)             # noqa: E731
    return run, ("rms_norm_bwd",)


def _diff_rms_norm_residual():
    d, x, w = _f32(13, 32), _f32(13, 32), _f32(32)

    def run(fn):
        return fn(d, x, w, 1e-6, mode=None)
    return run, ("rms_norm_residual",)


def _diff_fused_linear_ce():
    h, w = _f32(12, 32), _f32(32, 100)               # T%8!=0, V%128!=0
    lab = jnp.asarray(
        np.where(_RNG.rand(12) < 0.3, -100, _RNG.randint(0, 100, 12)),
        jnp.int32)

    def run(fn):
        loss, grads = jax.value_and_grad(
            lambda hh, ww: fn(hh, ww, lab), argnums=(0, 1))(h, w)
        return loss, grads
    return run, ("fused_linear_ce",)


def _diff_fused_swiglu():
    g, u = _f32(13, 64), _f32(13, 64)                # ragged rows

    def run(fn):
        out, grads = jax.value_and_grad(
            lambda gg, uu: fn(gg, uu).sum(), argnums=(0, 1))(g, u)
        return out, grads
    return run, ("fused_swiglu",)


def _diff_fused_adamw():
    n = 1000                                          # pad path
    p, g = _f32(n), _f32(n) * 0.01
    m = jnp.zeros((n,), jnp.float32)
    v = jnp.zeros((n,), jnp.float32)

    def run(fn):
        return fn(p, g, m, v, 1e-3, 3.0, grad_scale=jnp.float32(0.5),
                  shadow_dtype=jnp.bfloat16)
    return run, ("fused_adamw",)


def _diff_paged_attention_decode():
    B, H, KV, hd, BS, MB = 2, 4, 2, 16, 8, 3         # MB odd, GQA 2
    N = B * MB + 1
    q, kp, vp = _f32(B, H, hd), _f32(N, BS, KV, hd), _f32(N, BS, KV, hd)
    bt = jnp.asarray(
        _RNG.permutation(N - 1)[: B * MB].reshape(B, MB) + 1, jnp.int32)
    ln = jnp.asarray([5, BS * MB], jnp.int32)    # ragged, and a full table

    def run(fn):
        return fn(q, kp, vp, bt, ln)
    return run, ("paged_attention_decode",)


def _diff_moe_experts():
    # 5 held of 8 experts from the third on, groups of 0 to 13 rows
    # (blocks of 16: whole, partial, none), a layer of a stack, the
    # first matrix stored wider than the expert
    from paddle_tpu.ops import moe_experts as me
    T, D, E, held, F, k = 24, 32, 8, 5, 24, 3
    u = _f32(T, D)
    gates, experts = me.route(u, _f32(D, E), k, "sigmoid", None, 2.5)
    w_in = jnp.pad(_f32(2, held, D, F) * 0.2, ((0, 0),) * 3 + ((0, 8),))
    w_out = _f32(2, held, F, D) * 0.2

    def run(fn):
        return fn(u, gates, experts, w_in, w_out, 2, 1, "relu2")
    return run, ("moe_experts",)


def _diff_decode_mlp_block():
    B, D, F = 2, 32, 96                               # no divisor tile
    args = (_f32(B, D), jnp.abs(_f32(D)) + 0.5, _f32(D, F),
            _f32(D, F), _f32(F, D))

    def run(fn):
        return fn(*args)
    return run, ("decode_mlp_block",)


def _diff_prefill_attn_block():
    # warm mid-page start, ragged valid rows (13 of 16), odd page count
    P, D, H, KV, hd, BS, MB = 16, 32, 4, 2, 16, 8, 5
    N = MB + 3
    x, nw = _f32(P, D), jnp.abs(_f32(D)) + 0.5
    wq, wk, wv = _f32(D, H * hd), _f32(D, KV * hd), _f32(D, KV * hd)
    wo = _f32(H * hd, D)
    pos0, n_valid = 10, 13
    inv = 1.0 / (10000.0 ** (np.arange(0, hd, 2) / hd))
    ang = (pos0 + np.arange(P))[:, None] * inv[None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    kp, vp = _f32(N, BS, KV, hd), _f32(N, BS, KV, hd)
    tab = jnp.asarray(_RNG.permutation(N - 1)[:MB] + 1, jnp.int32)

    def run(fn):
        xo, kn, vn = fn(x, nw, wq, wk, wv, wo, sin, cos, kp, vp, tab,
                        jnp.int32(pos0), jnp.int32(n_valid))
        # rows past n_valid of xo are unspecified in the ragged fused
        # kernel (their compute is skipped) — compare the live rows
        return xo[:n_valid], kn, vn
    return run, ("prefill_attn_block",)


def _diff_prefill_mlp_block():
    P, D, F = 16, 32, 96                              # prefill rows
    args = (_f32(P, D), jnp.abs(_f32(D)) + 0.5, _f32(D, F),
            _f32(D, F), _f32(F, D))

    def run(fn):
        return fn(*args)
    return run, ("prefill_mlp_block",)


_DIFF_CASES = {
    "rms_norm_bwd": _diff_rms_norm_bwd,
    "rms_norm_residual": _diff_rms_norm_residual,
    "fused_linear_ce": _diff_fused_linear_ce,
    "fused_swiglu": _diff_fused_swiglu,
    "fused_adamw": _diff_fused_adamw,
    "paged_attention_decode": _diff_paged_attention_decode,
    "moe_experts": _diff_moe_experts,
    "decode_mlp_block": _diff_decode_mlp_block,
    "prefill_attn_block": _diff_prefill_attn_block,
    "prefill_mlp_block": _diff_prefill_mlp_block,
}


def test_differential_sweep_covers_every_registered_op():
    """A newly registered op without a differential case fails HERE —
    the sweep cannot silently shrink relative to the registry."""
    assert set(_DIFF_CASES) == set(KERNELS.ops())


@pytest.mark.parametrize("op", sorted(_DIFF_CASES))
def test_pallas_variant_matches_fallback_at_boundary_shapes(op):
    build = _DIFF_CASES[op]
    run, (op_name,) = build()
    # the highest-priority variant is the Pallas one
    pname = KERNELS.variants(op_name)[0].name
    with KERNELS.force(op_name, pname):
        got = run(KERNELS.variant(op_name, pname).fn)
    want = run(KERNELS.variants(op_name)[-1].fn)      # priority-0
    np.testing.assert_allclose(np.asarray(_flat(got), np.float32),
                               np.asarray(_flat(want), np.float32),
                               rtol=5e-5, atol=5e-5,
                               err_msg=f"{op}: pallas(interpret) vs "
                                       "priority-0 fallback diverged")


@pytest.mark.parametrize("op", sorted(_DIFF_CASES))
def test_auto_dispatch_under_interpret_falls_back_with_reason(op):
    """At the supports() boundary (interpret mode is itself the
    hardest boundary off-TPU) auto dispatch must select the priority-0
    fallback and every rejected variant must carry a human-readable
    reason string."""
    from paddle_tpu.analysis.kernel_catalog import _lint_metas
    meta = dict(_lint_metas()[op])
    meta["interpret"] = True
    rows = KERNELS.explain(op, meta)
    selected = [r for r in rows if r["selected"]]
    assert selected and selected[0]["priority"] == 0, rows
    for r in rows:
        assert isinstance(r["reason"], str) and r["reason"], rows


def test_supports_boundary_exact_vmem_budget_edge():
    """The CE predicate flips exactly AT the budget: the worst-case
    window bytes of the first fitting tile are <= budget by
    construction, budget-1 rejects it (with the budget named), and the
    fused_mlp candidate list obeys the same edge."""
    need = ft._ce_vmem_need(64, 256, 2048, 2)       # the smallest tile
    meta = ft.ce_meta(4096, 2048, 32000, jnp.bfloat16)
    meta["interpret"] = False
    meta["vmem_budget"] = need
    ok, why = ft._supports_ce(meta)
    assert ok, why
    meta["vmem_budget"] = need - 1
    ok, why = ft._supports_ce(meta)
    assert not ok and "VMEM" in why
    # the fused_mlp candidate list obeys the same edge: one byte under
    # the 512-tile's need drops 512 from the fitting list (the next
    # smaller divisor tile takes over as the traced default)
    bneed = fdb._mlp_vmem_need(8, 1024, 2, 512)
    assert fdb._mlp_fitting_candidates(8, 1024, 4096, 2, bneed)[0] == 512
    assert fdb._mlp_fitting_candidates(
        8, 1024, 4096, 2, bneed - 1)[0] == 256


def test_paged_attention_refusals_each_name_their_reason():
    """Every way ``paged_attention_decode`` stays off its kernel says
    why: the interpreter, a backend that is no TPU, int8 pools, and (by
    the registry, for every variant tagged "pallas") a program GSPMD
    partitions."""
    from paddle_tpu.ops import paged_attention as pa
    from paddle_tpu.ops.pallas import _util

    def refusal(**kw):
        meta = {"backend": "tpu", "interpret": False,
                "pool_dtype": "bfloat16", "head_dim": 128, **kw}
        rows = {r["name"]: r for r in
                KERNELS.explain("paged_attention_decode", meta)}
        assert rows["xla"]["selected"] != rows["pallas"]["selected"]
        return None if rows["pallas"]["selected"] \
            else rows["pallas"]["reason"]

    assert set(pa.decode_attention_meta(jnp.bfloat16)) == {
        "backend", "interpret", "pool_dtype", "head_dim"}
    assert refusal() is None
    # the v5e compiler refuses the launch at head_dim 64: supports()
    # says so, and a whole number of 128-lane rows passes
    assert "head_dim 64" in refusal(head_dim=64)
    assert refusal(head_dim=256) is None
    assert "interpret" in refusal(interpret=True)
    assert "'cpu'" in refusal(backend="cpu")
    assert "int8 pools" in refusal(pool_dtype="int8")
    _util.set_force_interpret(False)
    try:
        with _util.gspmd_program(4):
            assert "GSPMD" in refusal()
    finally:
        _util.set_force_interpret(None)
