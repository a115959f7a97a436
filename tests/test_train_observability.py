"""Training & multichip observability (r9): trainer metrics contract
(frozen schema enabled + disabled), compile telemetry (wall time /
cost-analysis MFU / memory-analysis HBM on CPU), bit-identical
loss/grad_norm with observability on vs off, the host-vs-device gap
dump, the flight-recorder unification (monotonic clock, registry feed,
bounded dump retention, reset/configure, deterministic hang watchdog)
and ``tools/trace_summary.py --mode train``."""
import json
import os
import sys
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.models.llama import (LlamaConfig, init_params, loss_fn,
                                     param_shardings)
from paddle_tpu.distributed.trainer import (MeshConfig, Trainer,
                                            make_mesh)
from paddle_tpu.distributed.flight_recorder import (
    FlightRecorder, enable_flight_recorder, disable_flight_recorder,
    get_flight_recorder)
from paddle_tpu.observability import (MetricsRegistry, Observability,
                                      TRAIN_HISTOGRAMS)
from paddle_tpu.observability import timeline as timeline_mod

CFG = LlamaConfig(vocab_size=97, hidden_size=32, intermediate_size=64,
                  num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, max_position_embeddings=32,
                  dtype=jnp.float32, remat=False)


def _trainer(**kw):
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    kw.setdefault("data_spec", P())
    kw.setdefault("lr", 1e-3)
    return Trainer(lambda p, t, l: loss_fn(p, t, l, CFG), mesh,
                   param_shardings(mesh, CFG), **kw)


def _batch(seed=0, b=2, s=8):
    rng = np.random.RandomState(seed)
    toks = jnp.asarray(rng.randint(0, 97, (b, s)), jnp.int32)
    return toks, jnp.asarray(np.roll(np.asarray(toks), -1, -1))


# -- trainer metrics schema contract ------------------------------------

BASE_KEYS = {"steps", "samples", "tokens", "wall_time_s",
             "samples_per_sec", "tokens_per_sec", "optimizer_variant"}
OBS_KEYS = {"latency", "gauges", "compile", "compiles",
            "retrace_warnings", "mfu", "hbm", "host_gap_findings",
            "stall_dumps", "timeline_events", "timeline_dropped"}
HIST_KEYS = {"count", "unit", "mean", "min", "max", "p50", "p95", "p99"}


def test_trainer_metrics_schema_frozen_disabled():
    """The metric key set is a CONTRACT (bench output + downstream
    parsers): extend deliberately, never by accident."""
    tr = _trainer()
    state = tr.init_state(init_params(CFG, jax.random.key(0)))
    toks, labels = _batch()
    for _ in range(2):
        state, _ = tr.step(state, toks, labels)
    m = tr.metrics()
    assert set(m.keys()) == BASE_KEYS
    assert m["steps"] == 2
    # a multi-leaf CPU trainer takes no flat state: the record says so
    assert m["optimizer_variant"] == {"variant": "per_leaf", "block": None}
    assert m["samples"] == 4 and m["tokens"] == 32
    assert m["tokens_per_sec"] > 0


def test_trainer_metrics_schema_frozen_enabled():
    tr = _trainer(observability=True)
    state = tr.init_state(init_params(CFG, jax.random.key(0)))
    toks, labels = _batch()
    for _ in range(3):
        state, _ = tr.step(state, toks, labels)
    m = tr.metrics()
    assert set(m.keys()) == BASE_KEYS | OBS_KEYS
    assert set(m["latency"].keys()) == set(TRAIN_HISTOGRAMS)
    for name, snap in m["latency"].items():
        assert set(snap.keys()) == HIST_KEYS, name
    st = m["latency"]["step_ms"]
    assert st["count"] == 3
    assert st["p50"] <= st["p95"] <= st["p99"] <= st["max"]
    # loss/grad_norm gauges sampled every step
    for key in ("loss", "grad_norm"):
        assert m["gauges"][key]["last"] is not None, key


# -- compile telemetry / MFU / HBM (CPU smoke) --------------------------

def test_compile_telemetry_and_mfu_smoke(monkeypatch):
    """cost_analysis FLOPs -> automatic MFU, memory_analysis -> HBM
    breakdown — on the CPU backend (the API contract; absolute numbers
    only mean something on real hardware, and the CPU has no peak on
    record, so the operator override names one)."""
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "197e12")
    tr = _trainer(observability=True)
    state = tr.init_state(init_params(CFG, jax.random.key(0)))
    toks, labels = _batch()
    state, _ = tr.step(state, toks, labels)
    m = tr.metrics()
    assert m["compiles"] >= 1
    prog = m["compile"]["programs"]["train_step"]
    assert prog["count"] >= 1
    assert prog["wall_ms_total"] > 0
    assert prog["cost"]["flops"] > 0
    hbm = m["hbm"]
    assert hbm["argument_bytes"] > 0
    assert hbm["total_bytes"] > 0
    assert set(hbm) >= {"argument_bytes", "output_bytes", "temp_bytes",
                        "total_bytes"}
    mfu = m["mfu"]
    assert mfu is not None
    assert mfu["flops_per_step_per_device"] == prog["cost"]["flops"]
    assert 0.0 <= mfu["mfu"] <= 1.0
    assert mfu["peak_flops_per_chip"] > 0
    # compile_ms histogram + timeline event recorded
    assert m["latency"]["compile_ms"]["count"] >= 1
    names = [e.name for e in tr.observability.timeline.events()]
    assert "compile" in names and "train_step" in names


def test_compile_watchdog_arms_on_reset():
    """reset_metrics() arms the compile watcher: a genuinely new batch
    signature after warmup warns (the train-step retrace watchdog); a
    steady signature stays silent."""
    tr = _trainer(observability=True)
    state = tr.init_state(init_params(CFG, jax.random.key(0)))
    toks, labels = _batch()
    # two warmup steps (one would do since the fp32 bias correction
    # fixed the x64 master promotion — kept at two so this test pins
    # the watchdog contract, not the warmup length)
    for _ in range(2):
        state, _ = tr.step(state, toks, labels)
    tr.reset_metrics()
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", RuntimeWarning)
        state, _ = tr.step(state, toks, labels)   # steady: silent
    assert tr.metrics()["retrace_warnings"] == 0
    toks2, labels2 = _batch(b=4, s=8)             # new batch shape
    with pytest.warns(RuntimeWarning, match="after warmup"):
        state, _ = tr.step(state, toks2, labels2)
    assert tr.metrics()["retrace_warnings"] == 1
    # re-arming starts a fresh retrace window: the fixed leak's old
    # warnings must not haunt the next window's snapshot
    tr.reset_metrics()
    assert tr.metrics()["retrace_warnings"] == 0


# -- multi-device AOT sharding (the r12 step-2 failure, fixed r15) ------

def _md_trainer(**kw):
    mesh = make_mesh(MeshConfig(fsdp=2), devices=jax.devices()[:2])
    kw.setdefault("data_spec", P())
    kw.setdefault("lr", 1e-3)
    return Trainer(lambda p, t, l: loss_fn(p, t, l, CFG), mesh,
                   param_shardings(mesh, CFG), **kw)


def test_multi_device_observed_trainer_survives_step2_resharding():
    """The pre-existing failure recorded in the verify skill since r12:
    on a multi-device mesh, GSPMD propagation re-shards some state
    leaves in the step-1 OUTPUT and the observed path's AOT executable
    rejected them at step 2 ("input sharding(s) does not match"). The
    compiled-cache key now includes each leaf's sharding, so step 2 is
    one extra warmup compile at the propagated (fixed-point) layout —
    and losses stay bit-identical to the unobserved trainer."""
    runs = []
    for obs in (False, True):
        tr = _md_trainer(observability=obs)
        state = tr.init_state(init_params(CFG, jax.random.key(0)))
        losses = []
        for i in range(3):
            toks, labels = _batch(seed=i)
            state, m = tr.step(state, toks, labels)   # step 2 used to raise
            losses.append(float(m["loss"]))
        runs.append(losses)
        if obs:
            # one compile per GSPMD layout (initial + propagated),
            # stable afterwards; the clean path never demoted to jit
            assert tr.metrics()["compile"]["count"] == 2
            assert tr._aot_fallback is False
    assert runs[0] == runs[1]


@pytest.mark.slow
def test_observed_step_falls_back_to_jit_on_sharding_reject(
        monkeypatch):
    """Belt-and-braces path: if a backend still rejects the committed
    shardings at call time, the observed step demotes to the plain jit
    path with a ONE-TIME warning instead of killing the train loop —
    and the math is unchanged (same jitted program)."""
    tr = _trainer(observability=True)
    ref = _trainer()
    state = tr.init_state(init_params(CFG, jax.random.key(2)))
    rstate = ref.init_state(init_params(CFG, jax.random.key(2)))
    toks, labels = _batch()

    def reject(self, tree, lr, staged):
        def boom(*a, **k):
            raise ValueError(
                "Compiled object called with input sharding(s) does "
                "not match the sharding(s) the computation was "
                "compiled with")
        return boom, 0.0

    monkeypatch.setattr(Trainer, "_compiled_for", reject)
    with pytest.warns(RuntimeWarning, match="falling back"):
        state, m = tr.step(state, toks, labels)
    assert tr._aot_fallback is True
    rstate, rm = ref.step(rstate, toks, labels)
    assert float(m["loss"]) == float(rm["loss"])
    # demoted: later steps run the jit path silently
    import warnings as _w
    with _w.catch_warnings():
        _w.simplefilter("error", RuntimeWarning)
        state, m2 = tr.step(state, toks, labels)
    rstate, rm2 = ref.step(rstate, toks, labels)
    assert float(m2["loss"]) == float(rm2["loss"])
    assert tr.metrics()["latency"]["step_ms"]["count"] == 2


# -- numerics: observability must not change the math -------------------

def test_bit_identical_loss_with_observability_on_vs_off():
    """10 steps, same init, same batches: loss and grad_norm must be
    BIT-identical with observability on vs off (the observed step runs
    the same jitted program through lower().compile())."""
    results = []
    for obs in (False, True):
        tr = _trainer(observability=obs)
        state = tr.init_state(init_params(CFG, jax.random.key(1)))
        run = []
        for i in range(10):
            toks, labels = _batch(seed=i)
            state, m = tr.step(state, toks, labels)
            run.append((float(m["loss"]), float(m["grad_norm"])))
        results.append(run)
    assert results[0] == results[1]   # exact float equality, all steps


# -- host-vs-device gap detector ----------------------------------------

def test_host_gap_dump_on_forced_per_step_staging(tmp_path,
                                                  monkeypatch):
    """The llama failure mode, synthesized: force the staging phase to
    dwarf the device wait and the detector must emit a flight-recorder
    dump naming the phase split."""
    dump = tmp_path / "gap.json"
    obs = Observability(stall_dump_path=str(dump),
                        histograms=TRAIN_HISTOGRAMS)
    tr = _trainer(observability=obs, host_gap_factor=1.5,
                  host_gap_min_ms=5.0)
    orig = Trainer._stage_batch

    def slow_stage(self, b):
        time.sleep(0.01)          # the forced per-step h2d residual
        return orig(self, b)

    monkeypatch.setattr(Trainer, "_stage_batch", slow_stage)
    state = tr.init_state(init_params(CFG, jax.random.key(0)))
    toks, labels = _batch()
    for _ in range(2):
        state, _ = tr.step(state, toks, labels)
    m = tr.metrics()
    assert m["host_gap_findings"] >= 1
    assert m["stall_dumps"] >= 1
    assert dump.exists()
    # reset_metrics restarts the gap window: warmup findings must not
    # pollute (or dump-starve) the measured window
    tr.reset_metrics()
    assert tr.metrics()["host_gap_findings"] == 0
    assert tr._gap.dumps == 0
    report = json.loads(dump.read_text())
    assert "host-vs-device gap" in report["reason"]
    split = report["scheduler"]["phase_split"]
    assert split["stage_ms"] > split["device_wait_ms"]
    # the gap event is on the timeline too
    assert any(e.name == "host_gap"
               for e in tr.observability.timeline.events())


def test_no_gap_dump_on_healthy_steps():
    tr = _trainer(observability=True)   # default 4x/50ms thresholds
    state = tr.init_state(init_params(CFG, jax.random.key(0)))
    toks, labels = _batch()
    state, _ = tr.step(state, toks, labels)
    staged = tuple(tr._stage_batch(b) for b in (toks, labels))
    for _ in range(3):
        state, _ = tr.step(state, *staged)   # pre-staged: no h2d
    # tiny model on CPU: steps are fast, min_wall_ms gates the detector
    assert tr.metrics()["host_gap_findings"] == 0
    assert tr.metrics()["stall_dumps"] == 0


# -- disabled mode: zero overhead ---------------------------------------

def test_disabled_mode_no_event_objects_no_extra_sync(monkeypatch):
    """observability=False must not allocate a single TimelineEvent or
    Observability object, and must not add a block_until_ready sync."""
    def boom(*a, **k):
        raise AssertionError("allocated in disabled mode")
    monkeypatch.setattr(timeline_mod.TimelineEvent, "__init__", boom)
    monkeypatch.setattr(Observability, "__init__", boom)
    monkeypatch.setattr(jax, "block_until_ready", boom)
    tr = _trainer()
    assert tr.observability is None
    state = tr.init_state(init_params(CFG, jax.random.key(0)))
    toks, labels = _batch()
    state, m = tr.step(state, toks, labels)
    assert np.isfinite(float(m["loss"]))
    mm = tr.metrics()
    assert "latency" not in mm and "gauges" not in mm
    with pytest.raises(RuntimeError, match="disabled"):
        tr.export_trace("/tmp/never.json")
    with pytest.raises(RuntimeError, match="disabled"):
        tr.write_timeline("/tmp/never.jsonl")


# -- prefetch queue-depth gauge -----------------------------------------

def test_prefetch_queue_depth_gauge():
    tr = _trainer(observability=True)
    state = tr.init_state(init_params(CFG, jax.random.key(0)))
    rng = np.random.RandomState(3)

    def batches():
        for _ in range(4):
            toks = rng.randint(0, 97, (2, 8)).astype(np.int32)
            yield toks, np.roll(toks, -1, -1)

    for toks, labels in tr.prefetch(batches()):
        state, _ = tr.step(state, toks, labels)
    g = tr.metrics()["gauges"]
    assert "prefetch_queue_depth" in g
    assert g["prefetch_queue_depth"]["last"] is not None


# -- exports ------------------------------------------------------------

def test_trainer_chrome_and_jsonl_exports(tmp_path):
    tr = _trainer(observability=True)
    state = tr.init_state(init_params(CFG, jax.random.key(0)))
    toks, labels = _batch()
    for _ in range(3):
        state, _ = tr.step(state, toks, labels)
    trace_path = tmp_path / "train_trace.json"
    tr.export_trace(str(trace_path))
    trace = json.loads(trace_path.read_text())
    evs = trace["traceEvents"]
    assert any(e.get("ph") == "X" and e.get("name") == "train_step"
               for e in evs)
    assert any(e.get("ph") == "C" and e.get("name") == "loss"
               for e in evs)
    jsonl_path = tmp_path / "train_tl.jsonl"
    tr.write_timeline(str(jsonl_path))
    lines = [json.loads(ln)
             for ln in jsonl_path.read_text().splitlines()]
    assert lines[0]["kind"] == "meta"
    assert lines[0]["mode"] == "train"
    assert "mesh" in lines[0]
    steps = [ln for ln in lines if ln.get("name") == "train_step"]
    assert len(steps) == 3
    for s in steps:
        assert {"stage_ms", "dispatch_ms", "sync_ms",
                "dur_ms"} <= set(s)


# -- trace_summary --mode train -----------------------------------------

def _import_trace_summary():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "tools"))
    try:
        import trace_summary
    finally:
        sys.path.pop(0)
    return trace_summary


def test_trace_summary_train_mode_canned(tmp_path):
    """--mode train on a canned timeline: per-phase breakdown, per-step
    host-vs-device gap, top-N slowest, compile log."""
    path = tmp_path / "train.jsonl"
    rows = [{"kind": "meta", "schema": 1, "mode": "train",
             "mesh": {"dp": 1}, "events": 5, "dropped": 0},
            {"kind": "event", "name": "compile", "t_ns": 0,
             "dur_ms": 900.0, "program": "train_step", "count": 1},
            {"kind": "event", "name": "train_step", "t_ns": 1, "step": 1,
             "dur_ms": 3400.0, "stage_ms": 3200.0, "dispatch_ms": 10.0,
             "sync_ms": 190.0},
            {"kind": "event", "name": "train_step", "t_ns": 2, "step": 2,
             "dur_ms": 210.0, "stage_ms": 5.0, "dispatch_ms": 5.0,
             "sync_ms": 200.0},
            # fast step: huge host/device ratio but tiny wall — must
            # NOT count as host-bound (the live detector's min_wall_ms
            # predicate, mirrored offline)
            {"kind": "event", "name": "train_step", "t_ns": 5, "step": 3,
             "dur_ms": 5.0, "stage_ms": 4.0, "dispatch_ms": 1.0,
             "sync_ms": 0.0},
            {"kind": "event", "name": "host_gap", "t_ns": 3, "step": 1,
             "host_ms": 3210.0, "device_wait_ms": 190.0},
            {"kind": "event", "name": "stall", "t_ns": 4,
             "reason": "host-vs-device gap: step 1 ..."}]
    path.write_text("\n".join(json.dumps(r) for r in rows) + "\n")
    ts = _import_trace_summary()
    meta, events, requests = ts.load(str(path))
    s = ts.summarize_train(meta, events, top=5)
    assert s["phases"]["stage_ms"]["count"] == 3
    assert s["phases"]["stage_ms"]["max_ms"] == 3200.0
    assert s["phases"]["sync_ms"]["mean_ms"] == pytest.approx(
        (190.0 + 200.0 + 0.0) / 3, rel=1e-6)
    gap = s["host_device_gap"]
    assert gap["steps"] == 3 and gap["host_bound_steps"] == 1
    # the genuinely host-bound step leads the list — NOT the fast step
    # whose near-zero sync produces a huge but meaningless ratio
    g1 = gap["worst"][0]
    assert g1["step"] == 1 and g1["host_bound"]
    assert g1["ratio"] == pytest.approx(3210.0 / 190.0, rel=0.01)
    g3 = next(g for g in gap["worst"] if g["step"] == 3)
    assert not g3["host_bound"]              # below min wall
    assert s["slowest_steps"][0]["step"] == 1
    assert s["compiles"][0]["program"] == "train_step"
    assert s["host_gap_events"] == 1 and len(s["stalls"]) == 1
    text = ts.render_train(s)
    assert "host-vs-device" in text and "stage_ms" in text
    # the CLI auto-detects train mode from the meta header
    import contextlib
    import io
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        assert ts.main([str(path), "--json"]) == 0
    assert json.loads(buf.getvalue())["host_device_gap"][
        "host_bound_steps"] == 1


# -- flight recorder: unification satellites ----------------------------

def test_flight_recorder_monotonic_clock_and_dump_clock_base(tmp_path):
    """CommTask timestamps ride the shared monotonic clock (they line
    up with the timeline), and dumps carry the wall/monotonic base pair
    so absolute times are recoverable."""
    dump = tmp_path / "fr.json"
    rec = enable_flight_recorder(timeout=3600.0, dump_path=str(dump))
    try:
        t_before = Observability.now()
        task = rec.begin("all_reduce", "dp", (4,), "float32")
        rec.end(task)
        t_after = Observability.now()
        assert t_before <= task.start_ts <= task.end_ts <= t_after
        # the monotonic domain, not the wall clock: a regression to
        # time.time() would put start_ts ~epoch-sized seconds away
        assert abs(task.start_ts - t_before) < 60.0
        rec.dump(reason="clock test")
        report = json.loads(dump.read_text())
        clock = report["clock"]
        assert {"wall", "monotonic", "monotonic_at_dump"} <= set(clock)
        # reconstructed absolute start lands within a minute of now
        abs_start = clock["wall"] + (task.start_ts - clock["monotonic"])
        assert abs(abs_start - time.time()) < 60.0
        assert report["entries"][0]["op"] == "all_reduce"
    finally:
        disable_flight_recorder()


def test_flight_recorder_dump_retention(tmp_path):
    """Successive dumps must not clobber the first report; past
    max_dumps nothing new is written (counted instead)."""
    dump = tmp_path / "hang.json"
    rec = FlightRecorder(timeout=3600.0, dump_path=str(dump),
                         max_dumps=3)
    rec.enabled = True
    t = rec.begin("all_reduce", "dp", (8,), "float32")
    rec.end(t)
    p0 = rec.dump(reason="first")
    p1 = rec.dump(reason="second")
    p2 = rec.dump(reason="third")
    assert p0 == str(dump)
    assert p1 == str(tmp_path / "hang.1.json")
    assert p2 == str(tmp_path / "hang.2.json")
    assert json.loads(dump.read_text())["reason"] == "first"
    assert json.loads((tmp_path / "hang.1.json").read_text())[
        "reason"] == "second"
    # beyond the cap: suppressed, not written
    p3 = rec.dump(reason="fourth")
    assert p3 == "" and rec.dumps_suppressed == 1
    assert not (tmp_path / "hang.3.json").exists()


def test_flight_recorder_dump_log_survives_reenable(tmp_path):
    """The dump log must survive reset()/re-enable: forgetting written
    files would hand the next hang the FIRST report's path to clobber
    — the overwrite bug this PR fixes, via the re-enable door."""
    dump = tmp_path / "hang.json"
    rec = enable_flight_recorder(timeout=3600.0, dump_path=str(dump))
    try:
        t = rec.begin("all_reduce", "dp", (4,), "float32")
        rec.end(t)
        assert rec.dump(reason="first") == str(dump)
        enable_flight_recorder(timeout=3600.0, dump_path=str(dump))
        t = rec.begin("all_reduce", "dp", (4,), "float32")
        rec.end(t)
        assert rec.dump(reason="second") == str(tmp_path / "hang.1.json")
        assert json.loads(dump.read_text())["reason"] == "first"
    finally:
        disable_flight_recorder()


def test_flight_recorder_reenable_keeps_pending_task(tmp_path):
    """enable_flight_recorder routes through configure()/reset(): an
    in-flight task survives a re-enable (its end() still lands, the
    watchdog can still catch it hanging)."""
    rec = enable_flight_recorder(timeout=3600.0)
    try:
        task = rec.begin("all_gather", "tp", (16,), "float32")
        assert task is not None and task.pending
        # re-enable with new knobs: pending task must survive
        rec2 = enable_flight_recorder(
            timeout=1800.0, dump_path=str(tmp_path / "d.json"),
            capacity=64)
        assert rec2 is rec
        assert rec.timeout == 1800.0 and rec.capacity == 64
        live = rec.tasks()
        assert any(t.seq == task.seq and t.pending for t in live)
        rec.end(task)
        assert not task.pending
        assert [t for t in rec.tasks() if t.seq == task.seq][0].end_ts \
            is not None
        # completed history was cleared by the reset
        assert all(t.seq >= task.seq for t in rec.tasks())
    finally:
        disable_flight_recorder()


def test_flight_recorder_watchdog_fires_then_stays_silent(tmp_path):
    """Hang watchdog on a simulated pending collective: fires (writes
    the dump) while the task is stuck past the timeout, reports it only
    once, and stays silent after the task completes."""
    dump = tmp_path / "wd.json"
    rec = FlightRecorder(timeout=0.01, dump_path=str(dump))
    rec.enabled = True
    task = rec.begin("all_reduce", "dp", (1024,), "float32")
    time.sleep(0.03)                      # now pending > timeout
    assert rec.check_once() == 1          # fires: new hung task
    assert dump.exists()
    report = json.loads(dump.read_text())
    assert "pending" in report["reason"]
    assert report["scheduler"]["pending"] == 1
    assert report["timeline_tail"][0]["op"] == "all_reduce"
    assert rec.check_once() == 0          # same hang: reported once
    rec.end(task)
    time.sleep(0.02)
    assert rec.check_once() == 0          # completed: silent
    t2 = rec.begin("broadcast", None, (2,), "float32")
    rec.end(t2)
    assert rec.check_once() == 0          # fast op: silent


def test_flight_recorder_feeds_registry_and_chrome_track(tmp_path):
    """bind_flight_recorder: completed collectives feed per-(op, axis)
    latency histograms + bytes counters into the observability
    registry, and the chrome export gains the per-rank collective
    track."""
    import paddle_tpu.distributed as dist
    obs = Observability()
    rec = enable_flight_recorder(timeout=3600.0)
    try:
        obs.bind_flight_recorder(rec)
        t = paddle.to_tensor(np.ones((8,), np.float32))
        dist.all_reduce(t)
        dist.all_reduce(t)
        h = obs.registry.histograms.get("collective_all_reduce@world_ms")
        assert h is not None and h.count == 2
        assert obs.registry.counters["collective_calls"][
            "all_reduce@world"] == 2
        assert obs.registry.counters["collective_bytes"][
            "all_reduce@world"] == 2 * 8 * 4
        obs.timeline.record("decode_step", dur_ms=1.0)
        path = tmp_path / "trace.json"
        obs.export_chrome(str(path))
        evs = json.loads(path.read_text())["traceEvents"]
        colls = [e for e in evs if e.get("name") == "all_reduce@world"]
        assert len(colls) == 2
        assert all(e["tid"] == 1000 for e in colls)   # rank-0 track
    finally:
        disable_flight_recorder()


def test_flight_recorder_per_axis_histograms():
    from paddle_tpu.observability import MetricsRegistry as _MR
    reg = _MR()
    rec = FlightRecorder(timeout=3600.0)
    rec.enabled = True
    rec.bind(registry=reg)
    for axis in ("dp", "dp", "mp"):
        t = rec.begin("all_reduce", axis, (4,), "float32")
        rec.end(t)
    assert reg.histograms["collective_all_reduce@dp_ms"].count == 2
    assert reg.histograms["collective_all_reduce@mp_ms"].count == 1
    assert reg.counters["collective_bytes"]["all_reduce@dp"] == 2 * 16


# -- stall-dump retention bound (Observability side) --------------------

def test_observability_stall_dump_retention(tmp_path):
    obs = Observability(stall_dump_path=str(tmp_path / "s.json"),
                        max_stall_dumps=2)
    p0 = obs.stall_dump("one", {})
    p1 = obs.stall_dump("two", {})
    p2 = obs.stall_dump("three", {})
    assert p0 == str(tmp_path / "s.json")
    assert p1 == str(tmp_path / "s.1.json")
    assert p2 == "" and obs.stall_dumps_suppressed == 1
    # suppressed dumps count, without growing the log unboundedly
    assert len(obs.stall_dumps) == 2


def test_stderr_dumps_are_never_capped(capsys):
    """Console diagnostics must not go dark: with no dump_path, every
    hang report goes to stderr regardless of max_dumps (only written
    FILES count against the retention bound)."""
    rec = FlightRecorder(timeout=3600.0, max_dumps=2)
    rec.enabled = True
    for i in range(4):
        t = rec.begin("all_reduce", "dp", (4,), "float32")
        rec.end(t)
        assert rec.dump(reason=f"hang {i}") == ""
    assert rec.dumps_suppressed == 0
    assert capsys.readouterr().err.count("[stall-dump]") == 4


def test_reenable_clears_stale_dump_path(tmp_path, capsys):
    """enable_flight_recorder() with the default dump_path must clear a
    previous caller's path — a hang report must not land in a stale
    (possibly deleted) file instead of the console."""
    stale = tmp_path / "stale.json"
    rec = enable_flight_recorder(timeout=3600.0, dump_path=str(stale))
    try:
        rec2 = enable_flight_recorder(timeout=3600.0)   # defaults
        assert rec2.dump_path is None
        t = rec2.begin("all_reduce", "dp", (4,), "float32")
        rec2.end(t)
        assert rec2.dump(reason="post-reenable") == ""
        assert "[stall-dump]" in capsys.readouterr().err
        assert not stale.exists()
    finally:
        disable_flight_recorder()


# -- trainer + flight recorder unification ------------------------------

def test_trainer_reset_survives_bound_flight_recorder(tmp_path):
    """reset_metrics() must reset ONLY the trainer's own counters: the
    bound recorder's dict-valued collective counters live in the same
    adopted dict and collectives must keep working after a reset."""
    import paddle_tpu.distributed as dist
    tr = _trainer(observability=True)
    rec = enable_flight_recorder(timeout=3600.0)
    try:
        tr.observability.bind_flight_recorder(rec)
        state = tr.init_state(init_params(CFG, jax.random.key(0)))
        toks, labels = _batch()
        state, _ = tr.step(state, toks, labels)
        t = paddle.to_tensor(np.ones((4,), np.float32))
        dist.all_reduce(t)
        m = tr.metrics()
        assert m["collectives"]["calls"]["all_reduce@world"] == 1
        assert m["collectives"]["bytes"]["all_reduce@world"] == 16
        # the latency histograms are part of the public contract, not
        # dead data behind registry internals
        lat = m["collectives"]["latency_ms"]["all_reduce@world"]
        assert lat["count"] == 1 and set(lat) == HIST_KEYS
        # base schema grows exactly the conditional sub-dict
        assert set(m.keys()) == BASE_KEYS | OBS_KEYS | {"collectives"}
        tr.reset_metrics()
        dist.all_reduce(t)          # must not crash on a zeroed dict
        m = tr.metrics()
        assert m["steps"] == 0      # trainer window reset...
        assert m["collectives"]["calls"]["all_reduce@world"] == 2
        # ...recorder counters survived (cumulative, like trace counts)
    finally:
        disable_flight_recorder()


# -- the AdamW x64 bias-correction fix (the bug the compile telemetry
# -- found at runtime in r9; fixed at the source in this PR) -----------

def _legacy_adamw_update(grads, state, lr, b1=0.9, b2=0.95, eps=1e-8,
                         wd=0.1, grad_clip=1.0):
    """VERBATIM pre-fix _adamw_update math: `1 - b1 ** step` with an
    int32 step drops its weak type under the global x64 flag and
    promotes the master tree to float64. Kept as the reference for the
    bit-identical-in-f32 assertion and the auditor self-test."""
    params, master, mu, nu, step = state
    step = step + 1
    gnorm_sq = sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                   for g in jax.tree_util.tree_leaves(grads))
    gnorm = jnp.sqrt(gnorm_sq)
    scale = jnp.minimum(1.0, grad_clip / jnp.maximum(gnorm, 1e-12)) \
        if grad_clip else 1.0

    def upd(g, m, mu_i, nu_i):
        g32 = g.astype(jnp.float32) * scale
        mu_n = b1 * mu_i.astype(jnp.float32) + (1 - b1) * g32
        nu_n = b2 * nu_i.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
        mhat = mu_n / (1 - b1 ** step)
        vhat = nu_n / (1 - b2 ** step)
        m_n = m * (1.0 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
        return m_n, mu_n.astype(mu_i.dtype), nu_n.astype(nu_i.dtype)

    tl = jax.tree_util.tree_leaves
    treedef = jax.tree_util.tree_structure(grads)
    new_m, new_mu, new_nu = [], [], []
    for g, m, mi, ni in zip(tl(grads), tl(master), tl(mu), tl(nu)):
        a, b, c = upd(g, m, mi, ni)
        new_m.append(a)
        new_mu.append(b)
        new_nu.append(c)
    unf = lambda ls: jax.tree_util.tree_unflatten(treedef, ls)  # noqa: E731
    master_n, mu_n, nu_n = unf(new_m), unf(new_mu), unf(new_nu)
    params_n = jax.tree_util.tree_map(
        lambda m, p: m.astype(p.dtype), master_n, params)
    return (params_n, master_n, mu_n, nu_n, step), gnorm


def _tiny_opt_state(key=0):
    rng = np.random.RandomState(key)
    mk = lambda *s: jnp.asarray(rng.randn(*s), jnp.float32)  # noqa: E731
    params = {"w": mk(8, 4), "b": mk(4)}
    master = jax.tree_util.tree_map(lambda v: v.astype(jnp.float32),
                                    params)
    mu = jax.tree_util.tree_map(jnp.zeros_like, master)
    nu = jax.tree_util.tree_map(jnp.zeros_like, master)
    return (params, master, mu, nu, jnp.zeros((), jnp.int32))


def test_adamw_fix_keeps_f32_state_under_x64():
    """The repo runs with jax_enable_x64 globally on (paddle int64 /
    float64 semantics) — exactly the config that promoted the pre-fix
    master tree to float64 after step 1."""
    from paddle_tpu.distributed.trainer import _adamw_update
    assert jax.config.jax_enable_x64        # the bug's precondition
    # the updates run JITTED, like the trainer's step: the weak type
    # survives eager execution (weak f64 defers to the f32 array) but
    # is dropped under tracing — the bug only exists in the compiled
    # step, which is why it took compile telemetry to find and why a
    # trace-level static auditor is the right tool to catch it
    fixed_fn = jax.jit(
        lambda g, s: _adamw_update(g, s, jnp.float32(1e-3)))
    state = _tiny_opt_state()
    g = jax.tree_util.tree_map(jnp.ones_like, state[0])
    for _ in range(3):
        state, _ = fixed_fn(g, state)
    for leaf in jax.tree_util.tree_leaves(state[1]):    # master
        assert leaf.dtype == jnp.float32
    assert state[4].dtype == jnp.int32                  # step
    # and the legacy math really does widen (the bug exists, the fix
    # is not vacuous)
    legacy_fn = jax.jit(
        lambda g, s: _legacy_adamw_update(g, s, jnp.float32(1e-3)))
    legacy, _ = legacy_fn(g, _tiny_opt_state())
    assert {str(leaf.dtype) for leaf in
            jax.tree_util.tree_leaves(legacy[1])} == {"float64"}


def test_adamw_fix_bit_identical_to_legacy_in_f32():
    """With x64 off the weak-typed legacy path already ran pow(f32,
    f32): the explicit fp32 bias correction must be the SAME program —
    bit-identical state after 5 steps, not merely close."""
    from paddle_tpu.distributed.trainer import _adamw_update
    with jax.enable_x64(False):
        s_new, s_old = _tiny_opt_state(1), _tiny_opt_state(1)
        for i in range(5):
            rng = np.random.RandomState(100 + i)
            g = {"w": jnp.asarray(rng.randn(8, 4), jnp.float32),
                 "b": jnp.asarray(rng.randn(4), jnp.float32)}
            s_new, gn_new = _adamw_update(g, s_new, jnp.float32(1e-3))
            s_old, gn_old = _legacy_adamw_update(g, s_old,
                                                 jnp.float32(1e-3))
        assert float(gn_new) == float(gn_old)
        for a, b in zip(jax.tree_util.tree_leaves(s_new),
                        jax.tree_util.tree_leaves(s_old)):
            assert a.dtype == b.dtype
            assert np.array_equal(np.asarray(a), np.asarray(b))


def test_adamw_single_compile_across_10_steps_with_x64():
    """The regression the fix buys back: one compile for the whole run
    (pre-fix, the step-1 master promotion changed the state signature
    and recompiled at step 2 inside every bench window)."""
    assert jax.config.jax_enable_x64
    tr = _trainer(observability=True)
    state = tr.init_state(init_params(CFG, jax.random.key(0)))
    toks, labels = _batch()
    losses = []
    for _ in range(10):
        state, m = tr.step(state, toks, labels)
        losses.append(float(m["loss"]))
    assert tr.metrics()["compiles"] == 1
    for leaf in jax.tree_util.tree_leaves(state.master):
        assert leaf.dtype == jnp.float32
    assert state.step.dtype == jnp.int32
    assert all(np.isfinite(losses))
