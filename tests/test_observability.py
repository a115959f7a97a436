"""Serving-stack observability (paddle_tpu/observability/): metrics
contract (schema stability, percentile monotonicity), request-lifecycle
timelines + chrome-trace export, retrace watchdog, stall diagnostics,
and the disabled-mode zero-overhead guarantee. The acceptance bar: a
30-request stream with observability ENABLED reports full latency
distributions and per-step gauges while greedy output stays
bit-identical and steady state stays 1 decode program + <=1 trace per
prefill bucket."""
import json
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.models import llama
from paddle_tpu.inference import (GenerationConfig, ServingEngine,
                                  generate)
from paddle_tpu.observability import (Histogram, Observability,
                                      RetraceWatchdog, TelemetryConfig,
                                      TelemetryPlane)
from paddle_tpu.observability import timeline as timeline_mod

CFG = llama.LlamaConfig(vocab_size=97, hidden_size=64,
                        intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=2,
                        max_position_embeddings=128, dtype=jnp.float32,
                        remat=False)


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def _engine(params, **kw):
    kw.setdefault("capacity", 2)
    kw.setdefault("block_size", 4)
    kw.setdefault("prefill_buckets", (8, 16))
    kw.setdefault("max_seq_len", 64)
    return ServingEngine(params, CFG, **kw)


# -- metrics primitives ------------------------------------------------

def test_histogram_percentile_monotonicity():
    vals = np.random.RandomState(0).lognormal(1.0, 2.0, 5000)
    h = Histogram()
    for v in vals:
        h.observe(float(v))
    s = h.snapshot()
    assert s["count"] == 5000
    assert s["min"] <= s["p50"] <= s["p95"] <= s["p99"] <= s["max"]
    # resolution: percentiles within ~one bucket (~9%) of exact
    for q, key in ((50, "p50"), (95, "p95"), (99, "p99")):
        exact = float(np.percentile(vals, q))
        assert s[key] == pytest.approx(exact, rel=0.10), (key, exact)


def test_histogram_edge_cases():
    h = Histogram()
    assert h.snapshot()["p99"] == 0.0          # empty
    h.observe(0.0)                             # zero bucket
    h.observe(-1.0)
    h.observe(5.0)
    s = h.snapshot()
    assert s["count"] == 3 and s["min"] == -1.0 and s["max"] == 5.0
    assert 0.0 <= s["p50"] <= s["p95"] <= s["p99"] <= 5.0


# -- metrics schema contract -------------------------------------------

BASE_KEYS = {
    "decode_traces", "prefill_traces", "calibration_traces",
    "decode_steps", "prefill_chunks", "prefill_tokens",
    "live_slot_steps", "tokens_generated", "requests_submitted",
    "requests_completed", "drain_truncations", "wall_time_s",
    "tokens_per_sec", "prefill_tokens_per_sec", "ttft_ms_mean",
    "ttft_ms_max", "slot_utilization",
    "decode_variant",        # r11: fused decode-block dispatch report
    # r15: SLO-aware admission (preempt/requeue counters + the
    # per-class queue-wait / slo_attainment scheduler report)
    "preemptions", "requeues", "deadline_expired", "scheduler",
    # r16: host-RAM KV offload tier (spill extract / restore insert
    # traces + bytes each direction; zeros without kv_offload)
    "offload_traces", "kv_spill_bytes", "kv_restore_bytes",
    # r17: fused prefill-block dispatch report + the bucket-pad rows
    # fed to prefill chunks (the compute the ragged fused kernels skip)
    "prefill_variant", "prefill_pad_tokens",
    # r18: weight-quantization dispatch report ({"mode": "off"} on fp
    # engines; mode/weight_dtype/attn/mlp on weight_quant engines —
    # trace-time snapshot, the decode_variant contract)
    "weight_quant_variant",
    # r21: roofline observatory (per-variant modeled bytes/step + the
    # bandwidth-bound step-time floor; present in BOTH obs modes)
    "roofline",
}
OBS_KEYS = {"latency", "gauges", "retrace_warnings", "stall_dumps",
            "timeline_events", "timeline_dropped"}
LATENCY_KEYS = {"ttft_ms", "tpot_ms", "queue_wait_ms", "e2e_ms",
                "prefill_chunk_ms", "decode_step_ms", "step_ms"}
HIST_KEYS = {"count", "unit", "mean", "min", "max", "p50", "p95", "p99"}


def _run_stream(eng, n=4, seed=0, max_new=4):
    rng = np.random.RandomState(seed)
    rs = [eng.submit(rng.randint(0, 97, (int(s),)).astype(np.int32),
                     GenerationConfig(max_new_tokens=max_new,
                                      greedy=True))
          for s in rng.randint(4, 14, n)]
    eng.drain()
    return rs


def test_metrics_schema_frozen_disabled(params):
    """The metric key set is a CONTRACT: bench output and downstream
    parsers rely on it. Extend deliberately (update this test), never
    by accident."""
    eng = _engine(params)
    _run_stream(eng)
    m = eng.metrics()
    assert set(m.keys()) == BASE_KEYS
    # the two launches' variants as the registry picked them for the
    # trace, and PR 26's "operands": how each Pallas launch of the layer
    # loop gets its layer; off the TPU the compositions launch nothing
    assert m["decode_variant"] == {"attn": "xla", "mlp": "unfused",
                                   "operands": {}, "qkv": "fused_stack"}
    assert m["weight_quant_variant"] == {"mode": "off"}


def test_metrics_schema_frozen_enabled(params):
    eng = _engine(params, observability=True)
    _run_stream(eng)
    m = eng.metrics()
    assert set(m.keys()) == BASE_KEYS | OBS_KEYS
    assert set(m["decode_variant"].keys()) == {"attn", "mlp", "operands",
                                               "qkv"}
    assert set(m["latency"].keys()) == LATENCY_KEYS
    for name, snap in m["latency"].items():
        assert set(snap.keys()) == HIST_KEYS, name
    # engine-run percentile monotonicity on the real TTFT data
    t = m["latency"]["ttft_ms"]
    assert t["count"] == 4
    assert t["p50"] <= t["p95"] <= t["p99"] <= t["max"]
    # prefix-cache engines add exactly the prefix_cache sub-dict;
    # telemetry (r22) adds exactly the telemetry sub-dict, itself a
    # frozen sub-schema
    eng2 = _engine(params, prefix_cache=True, observability=True,
                   telemetry=TelemetryConfig(sample_every=2,
                                             detectors=()))
    _run_stream(eng2)
    m2 = eng2.metrics()
    assert set(m2.keys()) == \
        BASE_KEYS | OBS_KEYS | {"prefix_cache", "telemetry"}
    assert set(m2["telemetry"].keys()) == {"samples", "series",
                                           "alerts", "rules"}
    assert set(m2["telemetry"]["alerts"].keys()) == {"page", "ticket"}
    assert m2["telemetry"]["samples"] >= 1
    assert m2["telemetry"]["series"] > 0
    # the scheduler section carries the raw SLO counters the burn-rate
    # windows difference (r22)
    assert set(m2["scheduler"].keys()) == {
        "per_class", "slo_attainment", "slo_seen", "slo_attained",
        "queue_depth"}


def test_metrics_schema_frozen_tp(params):
    """Mesh'd engines extend the frozen schema by exactly "mesh"
    (always) and "collectives" (observability on — the bound flight
    recorder's structured sub-dict); the raw recorder counters must
    never leak as top-level keys in either mode."""
    from paddle_tpu.inference import ServingMesh
    mesh = ServingMesh.make(tp=2, collective="psum")
    eng = _engine(params, mesh=mesh)                 # disabled mode
    _run_stream(eng)
    m = eng.metrics()
    assert set(m.keys()) == BASE_KEYS | {"mesh"}
    assert set(m["mesh"].keys()) == {"axis", "tp", "collective"}
    eng2 = _engine(params, mesh=mesh, observability=True)
    _run_stream(eng2)
    m2 = eng2.metrics()
    assert set(m2.keys()) == BASE_KEYS | OBS_KEYS | {"mesh",
                                                     "collectives"}
    assert set(m2["collectives"].keys()) == {"calls", "bytes",
                                             "latency_ms"}
    assert set(m2["latency"].keys()) == LATENCY_KEYS
    assert m2["collectives"]["calls"]["psum@tp"] > 0
    for hist in m2["collectives"]["latency_ms"].values():
        assert set(hist.keys()) == HIST_KEYS


@pytest.mark.roofline
def test_metrics_roofline_schema(params, monkeypatch):
    """The roofline sub-dict (r21) is schema-stable in BOTH obs modes:
    per-arm modeled bytes/step + the bandwidth-bound step-time floor,
    the labelled peak pair, the active dispatch arm and layer count.
    The CPU has no peak on record, so the operator override names one
    (the arithmetic is what is under test, not the number)."""
    monkeypatch.setenv("PADDLE_TPU_PEAK_HBM_BW", "819e9")
    for obs in (False, True):
        eng = _engine(params, observability=obs)
        _run_stream(eng)
        roof = eng.metrics()["roofline"]
        assert set(roof.keys()) == {"variants", "peak_hbm_bw",
                                    "peak_source", "active", "layers"}
        assert set(roof["variants"].keys()) == {"pallas_fused",
                                                "unfused"}
        for row in roof["variants"].values():
            assert set(row.keys()) == {"bytes_per_step",
                                       "step_us_at_peak_bw",
                                       "achieved_bw_frac"}
            assert row["bytes_per_step"] > 0
            assert row["step_us_at_peak_bw"] > 0
        assert roof["active"] in roof["variants"]
        assert roof["layers"] >= 1
        # the compositions materialise their intermediates, so their
        # modeled step traffic can never undercut the two launches'
        assert roof["variants"]["unfused"]["bytes_per_step"] >= \
            roof["variants"]["pallas_fused"]["bytes_per_step"]
        # only the obs-enabled engine has a measured mean to attribute
        if obs:
            act = roof["variants"][roof["active"]]
            assert act["achieved_bw_frac"] is not None


def test_gauges_sampled_each_step(params):
    eng = _engine(params, prefix_cache=True, observability=True)
    _run_stream(eng, n=3)
    g = eng.metrics()["gauges"]
    for key in ("pages_free", "pages_in_use", "kv_refcount_total",
                "queue_depth", "live_slots", "prefix_tree_pages",
                "prefix_hit_ratio"):
        assert key in g, key
        assert g[key]["last"] is not None
    # the series saw real allocator pressure over time (tree-held pages
    # keep pages_in_use high at the end, so >= not >)
    assert len(eng.observability.registry.gauges["pages_free"].series) > 0
    assert g["pages_in_use"]["max"] >= g["pages_in_use"]["last"] > 0


# -- satellites ---------------------------------------------------------

def test_reset_metrics_excludes_warmup_ttft(params):
    """A request in flight across reset_metrics() must not leak its
    warmup-measured TTFT into the post-reset window."""
    eng = _engine(params, capacity=2)
    rng = np.random.RandomState(7)
    # r1 decodes long enough to stay in flight across the reset
    r1 = eng.submit(rng.randint(0, 97, (6,)).astype(np.int32),
                    GenerationConfig(max_new_tokens=12, greedy=True))
    for _ in range(3):
        eng.step()
    assert r1.ttft is not None and not r1.done
    eng.reset_metrics()
    m = eng.metrics()
    assert m["ttft_ms_mean"] is None       # r1's TTFT is warmup data
    r2 = eng.submit(rng.randint(0, 97, (5,)).astype(np.int32),
                    GenerationConfig(max_new_tokens=2, greedy=True))
    eng.drain()
    m = eng.metrics()
    assert r2.ttft is not None
    # only r2's post-reset TTFT counts (metrics rounds to 3 decimals)
    assert m["ttft_ms_mean"] == round(r2.ttft * 1e3, 3)
    assert m["ttft_ms_max"] == round(r2.ttft * 1e3, 3)


def test_reset_metrics_excludes_warmup_ttft_from_histograms(params):
    """The ttft_ms HISTOGRAM must apply the same warmup exclusion as
    ttft_ms_mean/max — the two must never disagree in one snapshot."""
    eng = _engine(params, capacity=2, observability=True)
    rng = np.random.RandomState(7)
    r1 = eng.submit(rng.randint(0, 97, (6,)).astype(np.int32),
                    GenerationConfig(max_new_tokens=12, greedy=True))
    for _ in range(3):
        eng.step()
    assert r1.ttft is not None and not r1.done
    eng.reset_metrics()
    eng.drain()                     # r1 finishes post-reset
    m = eng.metrics()
    assert m["ttft_ms_mean"] is None
    assert m["latency"]["ttft_ms"]["count"] == 0
    # the JSONL record survives, flagged as warmup
    recs = list(eng.observability.request_records)
    assert len(recs) == 1 and recs[0].get("warmup") is True


def test_prefill_tokens_per_sec(params):
    eng = _engine(params)
    rng = np.random.RandomState(8)
    total_prompt = 0
    for s in (5, 9, 13):
        eng.submit(rng.randint(0, 97, (s,)).astype(np.int32),
                   GenerationConfig(max_new_tokens=3, greedy=True))
        total_prompt += s
    eng.drain()
    m = eng.metrics()
    assert eng.counters["prefill_tokens"] == total_prompt
    assert m["prefill_tokens_per_sec"] > 0
    # consistency: tokens/s ratios match the raw counters
    assert (m["prefill_tokens_per_sec"] / m["tokens_per_sec"]) == \
        pytest.approx(total_prompt / m["tokens_generated"], rel=0.01)


def test_drain_truncation_observable(params):
    eng = _engine(params)
    rng = np.random.RandomState(9)
    eng.submit(rng.randint(0, 97, (8,)).astype(np.int32),
               GenerationConfig(max_new_tokens=10, greedy=True))
    n = eng.drain(max_steps=2)
    assert n == 2
    assert eng.last_drain_truncated is True
    assert eng.counters["drain_truncations"] == 1
    assert not eng.idle
    n2 = eng.drain()                       # clean drain resets the flag
    assert n2 > 0 and eng.last_drain_truncated is False
    assert eng.idle
    assert eng.counters["drain_truncations"] == 1
    # a drain that finishes exactly AT max_steps is NOT a truncation
    eng.submit(rng.randint(0, 97, (4,)).astype(np.int32),
               GenerationConfig(max_new_tokens=2, greedy=True))
    probe = eng.drain()
    eng.submit(rng.randint(0, 97, (4,)).astype(np.int32),
               GenerationConfig(max_new_tokens=2, greedy=True))
    assert eng.drain(max_steps=probe) == probe
    assert eng.last_drain_truncated is False


# -- retrace watchdog ---------------------------------------------------

def test_watchdog_unit():
    wd = RetraceWatchdog(warn=False)
    c = {"decode_traces": 1, "calibration_traces": 0,
         "prefill_traces": {8: 1}}
    assert wd.check(c) == 0                # not armed yet
    wd.mark_warmup(c)
    assert wd.check(c) == 0                # clean
    c["decode_traces"] += 1
    c["prefill_traces"][16] = 1
    assert wd.check(c) == 2
    assert wd.check(c) == 0                # baseline advanced: warn once


def test_watchdog_fires_on_forced_retrace(params):
    """Warm up bucket 8 only, reset (arms the watchdog), then submit a
    prompt needing bucket 16 — a genuinely new prefill program after
    warmup, exactly what the watchdog exists to catch."""
    eng = _engine(params, observability=True)
    rng = np.random.RandomState(10)
    eng.submit(rng.randint(0, 97, (6,)).astype(np.int32),
               GenerationConfig(max_new_tokens=2, greedy=True))
    eng.drain()
    eng.reset_metrics()
    assert eng.observability.watchdog.armed
    eng.submit(rng.randint(0, 97, (14,)).astype(np.int32),
               GenerationConfig(max_new_tokens=2, greedy=True))
    with pytest.warns(RuntimeWarning, match="retrace after warmup"):
        eng.drain()
    m = eng.metrics()
    assert m["retrace_warnings"] >= 1
    assert any(e["program"] == "prefill[16]"
               for e in eng.observability.watchdog.events)
    # steady traffic on warmed buckets stays silent
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", RuntimeWarning)
        eng.submit(rng.randint(0, 97, (6,)).astype(np.int32),
                   GenerationConfig(max_new_tokens=2, greedy=True))
        eng.drain()


# -- stall diagnostics --------------------------------------------------

def test_stall_dump_on_starved_drain(params, tmp_path):
    """An engine starved by an undersized pool must leave a flight-
    recorder dump: scheduler snapshot + timeline tail, as JSON."""
    dump = tmp_path / "stall.json"
    obs = Observability(stall_dump_path=str(dump))
    eng = _engine(params, num_blocks=10, observability=obs)
    rng = np.random.RandomState(11)
    # hold 7 of the 9 usable pages hostage via a foreign allocation so
    # the queued request (needs 6 pages) can never admit
    eng.mgr.allocate(999, 7 * 4)
    eng.submit(rng.randint(0, 97, (20,)).astype(np.int32),
               GenerationConfig(max_new_tokens=4, greedy=True))
    with pytest.raises(RuntimeError, match="starved") as ei:
        eng.drain()
    assert str(dump) in str(ei.value)      # the error names the dump
    report = json.loads(dump.read_text())
    assert report["reason"].startswith("drain starved")
    sched = report["scheduler"]
    assert sched["queue_depth"] == 1
    assert sched["queued"][0]["need_pages"] == 6
    assert sched["pages_free"] == 2
    assert all(s["phase"] == "idle" for s in sched["slots"])
    assert any(e["name"] == "submit" for e in report["timeline_tail"])
    assert eng.metrics()["stall_dumps"] == 1


def test_step_deadline_dump(params, tmp_path):
    dump = tmp_path / "deadline.json"
    obs = Observability(step_deadline_s=0.0, stall_dump_path=str(dump))
    eng = _engine(params, observability=obs)
    rng = np.random.RandomState(12)
    eng.submit(rng.randint(0, 97, (5,)).astype(np.int32),
               GenerationConfig(max_new_tokens=2, greedy=True))
    eng.step()                             # any real step blows a 0s deadline
    assert dump.exists()
    assert "deadline" in json.loads(dump.read_text())["reason"]
    assert eng.metrics()["stall_dumps"] >= 1


# -- disabled mode: zero overhead --------------------------------------

def test_disabled_mode_allocates_no_event_objects(params, monkeypatch):
    """observability=False must not allocate a single TimelineEvent or
    Observability object anywhere in the serving loop."""
    def boom(*a, **k):
        raise AssertionError("event object allocated in disabled mode")
    monkeypatch.setattr(timeline_mod.TimelineEvent, "__init__", boom)
    monkeypatch.setattr(Observability, "__init__", boom)
    monkeypatch.setattr(TelemetryPlane, "__init__", boom)
    eng = _engine(params)
    assert eng.observability is None
    assert eng.telemetry is None
    rs = _run_stream(eng, n=3, seed=13)
    assert all(r.done for r in rs)
    m = eng.metrics()
    assert "latency" not in m and "gauges" not in m
    assert "telemetry" not in m
    with pytest.raises(RuntimeError, match="disabled"):
        eng.export_trace("/tmp/never.json")


# -- acceptance: full stream with observability on ---------------------

def test_enabled_stream_parity_traces_and_exports(monkeypatch, params,
                                                  tmp_path):
    """30-request mixed-arrival stream with observability ENABLED:
    greedy outputs stay bit-identical to generate(), steady state stays
    1 decode program + <=1 trace per prefill bucket, latency/gauge
    distributions are populated, and the chrome trace + JSONL exports
    are valid."""
    # the roofline header needs a peak; the CPU has none on record
    monkeypatch.setenv("PADDLE_TPU_PEAK_HBM_BW", "819e9")
    rng = np.random.RandomState(14)
    eng = _engine(params, capacity=3, observability=True)
    pending = []
    for i in range(30):
        S, N = int(rng.randint(3, 17)), int(rng.randint(2, 7))
        pending.append((rng.randint(0, 97, (S,)).astype(np.int32),
                        GenerationConfig(max_new_tokens=N, greedy=True)))
    submitted = []
    while pending or not eng.idle:
        for _ in range(min(len(pending), 1 + int(rng.randint(0, 3)))):
            p, g = pending.pop(0)
            submitted.append((p, g, eng.submit(p, g)))
        eng.step()
    assert len(submitted) == 30
    c = eng.counters
    assert c["decode_traces"] == 1, c
    assert all(n <= 1 for n in c["prefill_traces"].values()), c
    # bit-identical greedy output vs single-request generate()
    for p, g, r in submitted[:5]:
        want = np.asarray(generate(params, jnp.asarray(p)[None], CFG,
                                   g))[0, p.size:]
        np.testing.assert_array_equal(np.asarray(r.tokens), want)
    m = eng.metrics()
    lat = m["latency"]
    assert lat["ttft_ms"]["count"] == 30
    assert lat["tpot_ms"]["count"] > 0
    assert lat["queue_wait_ms"]["count"] == 30
    for name in ("ttft_ms", "tpot_ms", "queue_wait_ms"):
        s = lat[name]
        assert s["p50"] <= s["p95"] <= s["p99"], name
    assert m["gauges"]["pages_free"]["last"] is not None
    assert m["retrace_warnings"] == 0
    # chrome trace: valid json, per-request spans + counter tracks
    trace_path = tmp_path / "trace.json"
    eng.export_trace(str(trace_path))
    trace = json.loads(trace_path.read_text())
    evs = trace["traceEvents"]
    assert any(e.get("ph") == "X" and "decode" in e.get("name", "")
               for e in evs)
    assert any(e.get("ph") == "C" and e.get("name") == "pages_free"
               for e in evs)
    names = {e["name"] for e in evs if e.get("ph") == "X"}
    assert any(n.startswith("req") and n.endswith(":prefill")
               for n in names)
    # JSONL: meta + events + 30 request records; trace_summary parses it
    jsonl_path = tmp_path / "tl.jsonl"
    eng.write_timeline(str(jsonl_path))
    recs = [json.loads(ln)
            for ln in jsonl_path.read_text().splitlines()]
    kinds = [r["kind"] for r in recs]
    assert kinds[0] == "meta"
    assert kinds.count("request") == 30
    assert kinds.count("event") > 30
    # r20: every decode_step event carries its serving variant so
    # trace_summary can attribute decode time per implementation
    dsteps = [r for r in recs
              if r["kind"] == "event" and r["name"] == "decode_step"]
    assert dsteps
    assert all(r.get("decode_variant") in ("pallas_fused", "unfused")
               for r in dsteps)
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import trace_summary
    finally:
        sys.path.pop(0)
    meta, events, requests = trace_summary.load(str(jsonl_path))
    summary = trace_summary.summarize(meta, events, requests, top=5)
    assert summary["requests"] == 30
    assert "decode_step" in summary["phases"]
    # r20 per-variant decode attribution: one bucket per variant seen,
    # counts covering every stamped decode_step event
    dec = summary["decode"]["variants"]
    assert set(dec) <= {"pallas_fused", "unfused"}
    assert sum(v["count"] for v in dec.values()) == len(dsteps)
    # r21: arms the meta roofline header models also carry modeled
    # bytes/step + the peak-BW step-time floor (and the measured/floor
    # ratio when the mean is nonzero)
    for v in dec.values():
        assert {"count", "total_ms", "max_ms", "mean_ms",
                "bytes_per_step_modeled",
                "step_us_at_peak_bw"} <= set(v.keys())
        assert v["bytes_per_step_modeled"] > 0
        assert v["step_us_at_peak_bw"] > 0
    assert len(summary["slowest_steps"]) == 5
    r = summary["request_latency"]["ttft_ms"]
    assert r["p50"] <= r["p95"] <= r["p99"] <= r["max"]
    text = trace_summary.render(summary)
    assert "decode_step" in text and "ttft_ms" in text
