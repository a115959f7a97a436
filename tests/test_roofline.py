"""Kernel roofline observatory: modeled bytes/FLOPs per launch
(hand-checked against the captured geometry), the FLOP-formula
registry's full-coverage contract, the roofline classification math,
the per-decode-variant step model, peak-table source labelling, the
trace_summary CLI's roofline readout + error handling, and the
kernel_bench_gate roofline mode incl. its --demo-regression
self-check."""
import json
import os
import subprocess
import sys

import pytest

import jax
import jax.numpy as jnp

pytestmark = pytest.mark.roofline

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SUMMARY_CLI = os.path.join(REPO, "tools", "trace_summary.py")
GATE_CLI = os.path.join(REPO, "tools", "kernel_bench_gate.py")

from paddle_tpu.analysis.kernel_catalog import (ALL_KERNEL_NAMES,  # noqa: E402
                                                FLOP_FORMULAS,
                                                flop_formula_findings,
                                                modeled_flops)
from paddle_tpu.analysis.kernel_rules import modeled_launch_bytes  # noqa: E402
from paddle_tpu.observability.compile import (UnknownDevicePeak,  # noqa: E402
                                              device_peak_flops,
                                              device_peak_hbm_bw)
from paddle_tpu.observability.roofline import (capture_kernel_costs,  # noqa: E402
                                               decode_roofline,
                                               decode_step_bytes,
                                               kernel_cost,
                                               roofline_chrome_events,
                                               roofline_point)
from paddle_tpu.ops.pallas._util import capture_kernel_launches    # noqa: E402


def _cli(path, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run([sys.executable, path, *args],
                          capture_output=True, text=True, env=env,
                          timeout=600)


# -- FLOP formula coverage ---------------------------------------------


def test_flop_formula_full_coverage():
    """Every audited kernel name has a registered formula — the
    COVERAGE_GAP analogue: a new kernel without one is a finding, not
    a silent hole in the roofline."""
    assert set(ALL_KERNEL_NAMES) <= set(FLOP_FORMULAS)
    assert flop_formula_findings() == []


# -- hand-checked bytes/FLOPs ------------------------------------------


def test_paged_decode_bytes_flops_hand_checked():
    """Self-fetched-operand model, pinned geometry (pages_per_step=2,
    B=2, H=4, KV=2, hd=16, BS=8, MB=4, f32; the grid is (B,) = (2,)):

    - q [2,4,16]: one (1,4,16) block per batch row -> 2 x 256 B
    - k/v pools: left in HBM (``any`` space), no blocks to count; the
      launch declares what it copies out of each: the slots' live
      pages, which on the full prefetch probe (every length past the
      table) are all B*MB=8 pages -> 8 x (8*2*16*4) = 8192 B each
    - out [2,4,16]: 2 x 256 B
    - scratch: the K and V double buffers [2,2,8,2,16] f32 (4096 B
      each) and m/l/acc are VMEM; the [2,2,2] DMA semaphores are not

    total 17408 B; FLOPs = 4*B*H*hd*MB*BS = 16384 (QK^T + PV over the
    same probe: the full table)."""
    from paddle_tpu.analysis.kernel_rules import check_launch
    from paddle_tpu.ops.pallas.paged_attention import (
        paged_attention_decode_pallas)
    B, H, KV, hd, BS, NP, MB = 2, 4, 2, 16, 8, 8, 4
    q = jnp.zeros((B, H, hd), jnp.float32)
    pool = jnp.zeros((NP, BS, KV, hd), jnp.float32)
    bt = jnp.zeros((B, MB), jnp.int32)
    ln = jnp.zeros((B,), jnp.int32)
    with capture_kernel_launches() as specs:
        jax.eval_shape(
            lambda *a: paged_attention_decode_pallas(
                *a, pages_per_step=2), q, pool, pool, bt, ln)
    (spec,) = specs
    assert spec.name == "paged_attention_decode"
    assert tuple(spec.grid) == (B,)
    assert [op.space for op in spec.inputs] == ["vmem", "any", "any"]
    assert ((2, 2, 2), "dma_sem", "semaphore") in spec.scratch
    assert check_launch(spec) == []
    bm = modeled_launch_bytes(spec)
    assert bm["total_bytes"] == 512 + 8192 + 8192 + 512 == 17408
    assert bm["read_bytes"] == 17408 - 512
    assert bm["written_bytes"] == 512
    assert modeled_flops(spec) == 4 * B * H * hd * MB * BS == 16384


def test_capture_kernel_costs_end_to_end(monkeypatch):
    from paddle_tpu.ops.pallas.norms import rms_norm_pallas
    # the CPU has no peak on record: the operator override names one
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "197e12")
    monkeypatch.setenv("PADDLE_TPU_PEAK_HBM_BW", "819e9")
    x = jnp.zeros((24, 128), jnp.float32)
    w = jnp.zeros((128,), jnp.float32)
    rows = capture_kernel_costs(rms_norm_pallas, x, w,
                                times_us={"rms_norm_fwd": 10.0})
    (row,) = rows
    assert row["kernel"] == "rms_norm_fwd"
    assert row["flops_model"] == "formula"
    assert row["bytes_modeled"] > 0
    assert row["bound"] == "memory"       # norms sit far left of ridge
    assert row["achieved_bw_frac"] is not None


# -- roofline classification math --------------------------------------


def test_roofline_point_bounds_and_fractions():
    peaks = {"peak_flops": 100e12, "peak_hbm_bw": 1e12,
             "peak_source": {"flops": "test", "hbm_bw": "test"}}
    # ridge = 100 FLOP/B: intensity 10 -> memory bound
    p = roofline_point(1e9, 1e10, peaks=peaks)
    assert p["intensity"] == 10.0 and p["bound"] == "memory"
    # bytes bound: 1e9 B / 1e12 B/s = 1000 us (>> 100 us compute side)
    assert p["time_at_roofline_us"] == 1000.0
    assert p["achieved_bw_frac"] is None   # no measured time
    # measured at 2x the floor -> 50% of peak BW, 50% of roofline
    p = roofline_point(1e9, 1e10, time_us=2000.0, peaks=peaks)
    assert p["achieved_bw_frac"] == 0.5
    assert p["roofline_frac"] == 0.5
    assert p["achieved_flops_frac"] == 0.05
    # intensity 1000 -> compute bound
    p = roofline_point(1e7, 1e10, peaks=peaks)
    assert p["bound"] == "compute"
    # missing inputs stay None, never zero
    p = roofline_point(None, None, time_us=5.0, peaks=peaks)
    assert p["intensity"] is None and p["bound"] is None
    assert p["achieved_bw_frac"] is None


def test_decode_step_bytes_closed_forms():
    B, D, H, KV, hd, F, BS, MB = 4, 64, 4, 2, 16, 128, 8, 4
    sb = decode_step_bytes(B, D, H, KV, hd, F, BS, MB,
                           act_itemsize=2, weight_itemsize=2,
                           pool_itemsize=2)
    Hhd, KVhd = H * hd, KV * hd
    w_attn = (D * Hhd + 2 * D * KVhd + Hhd * D) * 2
    w_mlp = 3 * D * F * 2
    kv = 2 * B * MB * BS * KVhd * 2
    x = B * D * 2
    assert set(sb) == {"pallas_fused", "unfused"}
    assert sb["pallas_fused"] == w_attn + w_mlp + kv + 4 * x
    assert sb["unfused"] == w_attn + w_mlp + kv + 10 * x \
        + 6 * B * F * 2
    # int8 weights shrink only the weight terms
    sb8 = decode_step_bytes(B, D, H, KV, hd, F, BS, MB,
                            weight_itemsize=1)
    assert sb8["pallas_fused"] == w_attn // 2 + w_mlp // 2 + kv + 4 * x


def test_decode_roofline_and_chrome_events():
    peaks = {"peak_flops": 100e12, "peak_hbm_bw": 1e12,
             "peak_source": {"flops": "test", "hbm_bw": "test"}}
    rep = decode_roofline({"pallas_fused": 1_000_000},
                          measured_us={"pallas_fused": 2.0},
                          peaks=peaks)
    row = rep["variants"]["pallas_fused"]
    assert row["step_us_at_peak_bw"] == 1.0    # 1 MB / 1 TB/s
    assert row["achieved_bw_frac"] == 0.5
    rep2 = decode_roofline({"unfused": 500}, peaks=peaks)
    assert rep2["variants"]["unfused"]["achieved_bw_frac"] is None
    events = roofline_chrome_events(rep)
    assert events == [{"name": "roofline:pallas_fused", "ph": "C",
                       "ts": 0.0,
                       "args": {"bytes_per_step": 1_000_000}}]


# -- peak table source labelling ---------------------------------------


class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


def test_peak_table_keyed_by_device_kind(monkeypatch):
    """The chip's own ``device_kind`` answers with its documented
    peaks; a device that is not in the table raises (no default), and
    the operator's env override wins over both."""
    monkeypatch.delenv("PADDLE_TPU_PEAK_HBM_BW", raising=False)
    monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v5 lite")])
    bw, src = device_peak_hbm_bw()
    assert bw == 819e9 and src.startswith("TPU v5 lite: Google Cloud")
    fl, fsrc = device_peak_flops()
    assert fl == 197e12 and fsrc.startswith("TPU v5 lite: Google Cloud")
    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev("TPU v9 zeta")])
    for fn in (device_peak_flops, device_peak_hbm_bw):
        with pytest.raises(UnknownDevicePeak, match="TPU v9 zeta"):
            fn()
    monkeypatch.setenv("PADDLE_TPU_PEAK_HBM_BW", "1.5e12")
    assert device_peak_hbm_bw() == (1.5e12, "env")


def test_unknown_device_reports_no_peak_derived_number(monkeypatch):
    """On the CPU under test there is no peak: modeled bytes still
    report, every peak-derived field is None (never a fraction of an
    assumed chip), and the trainer's MFU is None."""
    from paddle_tpu.observability.compile import CompileWatcher
    from paddle_tpu.observability.roofline import peak_snapshot
    monkeypatch.delenv("PADDLE_TPU_PEAK_HBM_BW", raising=False)
    monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS", raising=False)
    peaks = peak_snapshot()
    assert peaks["peak_flops"] is None and peaks["peak_hbm_bw"] is None
    assert peaks["peak_source"]["hbm_bw"].startswith("unknown:")
    p = roofline_point(1e9, 1e10, time_us=2000.0)
    assert p["intensity"] == 10.0
    assert p["bound"] is None and p["time_at_roofline_us"] is None
    assert p["achieved_bw_frac"] is None and p["roofline_frac"] is None
    rep = decode_roofline({"unfused": 500}, measured_us={"unfused": 9.0})
    assert rep["variants"]["unfused"] == {
        "bytes_per_step": 500, "step_us_at_peak_bw": None,
        "achieved_bw_frac": None}
    w = CompileWatcher.__new__(CompileWatcher)
    w.programs = {"step": {"cost": {"flops": 1e9}}}
    assert w.mfu("step", steps=2, wall_s=1.0) is None


# -- trace_summary CLI: roofline readout + robust load ------------------


def _write_timeline(path, roofline=True):
    meta = {"kind": "meta", "schema": 1, "mode": "serving"}
    if roofline:
        meta["roofline"] = {
            "variants": {"unfused": {"bytes_per_step": 424192,
                                     "step_us_at_peak_bw": 0.518,
                                     "achieved_bw_frac": None}},
            "peak_hbm_bw": 819e9,
            "peak_source": {"flops": "TPU v5 lite: doc",
                            "hbm_bw": "TPU v5 lite: doc"}}
    with open(path, "w") as f:
        f.write(json.dumps(meta) + "\n")
        for i in range(4):
            f.write(json.dumps(
                {"kind": "event", "name": "decode_step",
                 "t": 0.001 * i, "dur_ms": 2.0,
                 "decode_variant": "unfused"}) + "\n")


def test_trace_summary_roofline_readout(tmp_path):
    p = tmp_path / "t.jsonl"
    _write_timeline(str(p))
    r = _cli(SUMMARY_CLI, str(p), "--mode", "serving")
    assert r.returncode == 0, r.stderr
    assert "us measured," in r.stdout
    assert "us at peak BW" in r.stdout
    assert "of roofline" in r.stdout
    r = _cli(SUMMARY_CLI, str(p), "--mode", "serving", "--json")
    dec = json.loads(r.stdout)["decode"]
    row = dec["variants"]["unfused"]
    assert row["step_us_at_peak_bw"] == 0.518
    assert row["bytes_per_step_modeled"] == 424192
    # 2000 us measured vs 0.518 us floor (rounded to 4 decimals)
    assert row["roofline_frac"] == pytest.approx(0.518 / 2000, abs=1e-4)


def test_trace_summary_error_paths(tmp_path):
    # missing file: one-line error, nonzero, no traceback
    r = _cli(SUMMARY_CLI, str(tmp_path / "nope.jsonl"))
    assert r.returncode == 2
    assert "cannot read timeline file" in r.stderr
    assert "Traceback" not in r.stderr
    # empty file
    p = tmp_path / "empty.jsonl"
    p.write_text("")
    r = _cli(SUMMARY_CLI, str(p))
    assert r.returncode == 2
    assert "empty timeline file" in r.stderr
    assert "Traceback" not in r.stderr
    # truncated JSON (no parseable records at all)
    p = tmp_path / "trunc.jsonl"
    p.write_text('{"kind": "meta", "sch')
    r = _cli(SUMMARY_CLI, str(p))
    assert r.returncode == 2
    assert "no parseable timeline records" in r.stderr
    assert "Traceback" not in r.stderr


# -- kernel_bench_gate --roofline --------------------------------------


def _bank(tmp_path, fracs):
    doc = {"parsed": {"kernels": {"interpret": False, "cases": {
        k: {"ok": True, "us_pallas": 100.0, "achieved_bw_frac": v}
        for k, v in fracs.items()}}}}
    (tmp_path / "BENCH_r01.json").write_text(json.dumps(doc))


def _capture(tmp_path, fracs, name="fresh.json"):
    doc = {"kernels": {"interpret": False, "cases": {
        k: {"ok": True, "us_pallas": 100.0, "achieved_bw_frac": v}
        for k, v in fracs.items()}}}
    p = tmp_path / name
    p.write_text(json.dumps(doc))
    return str(p)


def test_roofline_gate_clean_and_regressed(tmp_path):
    _bank(tmp_path, {"paged_decode": 0.60})
    ok = _capture(tmp_path, {"paged_decode": 0.55}, "ok.json")
    r = _cli(GATE_CLI, "--capture", ok, "--roofline",
             "--repo", str(tmp_path))
    assert r.returncode == 0, r.stderr
    bad = _capture(tmp_path, {"paged_decode": 0.10}, "bad.json")
    r = _cli(GATE_CLI, "--capture", bad, "--roofline",
             "--repo", str(tmp_path))
    assert r.returncode == 1
    assert "ROOFLINE REGRESSION" in r.stderr


def test_roofline_gate_skip_semantics(tmp_path):
    # no banked roofline data -> SKIP (exit 0), same as the timing gate
    cap = _capture(tmp_path, {"paged_decode": 0.5})
    r = _cli(GATE_CLI, "--capture", cap, "--roofline",
             "--repo", str(tmp_path / "nothing"))
    assert r.returncode == 0
    assert "SKIP" in r.stdout


def test_roofline_gate_demo_regression():
    """The injected bandwidth collapse MUST fail the gate — end-to-end
    proof the roofline wiring can actually reject."""
    r = _cli(GATE_CLI, "--demo-regression")
    assert r.returncode == 1
    assert "ROOFLINE REGRESSION" in r.stderr
    # and it refuses to shadow a real capture
    r = _cli(GATE_CLI, "--demo-regression", "--capture", "x.json")
    assert r.returncode == 3
