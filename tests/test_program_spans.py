"""The program's own spans (paddle_tpu/observability/spans.py): one
primitive, two sinks. Under a profiler session every phase of a
``ServingEngine.step()`` and of a ``Trainer.step()`` lands on the
profiler's host line (the device trace's clock); with
``observability=True`` the same phases land in the ``Timeline`` and the
histograms; with neither, nothing is recorded anywhere."""
import glob

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from paddle_tpu.distributed.trainer import MeshConfig, Trainer, make_mesh
from paddle_tpu.inference import GenerationConfig, ServingEngine
from paddle_tpu.models import llama
from paddle_tpu.models.llama import loss_fn, param_shardings
from paddle_tpu.observability import (SERVE_SPANS, TRAIN_SPANS,
                                      Observability, span)
from paddle_tpu.observability import timeline as timeline_mod

CFG = llama.LlamaConfig(vocab_size=97, hidden_size=64,
                        intermediate_size=128, num_hidden_layers=2,
                        num_attention_heads=4, num_key_value_heads=2,
                        max_position_embeddings=128, dtype=jnp.float32,
                        remat=False)
# spans whose phase the Timeline already holds under an older event,
# fed from the span's duration: no twin is recorded
OLDER_EVENT = {"serve/prefill_dispatch": "prefill_chunk",
               "serve/decode_dispatch": "decode_step",
               "serve/token_sync": "decode_step"}


@pytest.fixture(scope="module")
def params():
    return llama.init_params(CFG, jax.random.key(0), dtype=jnp.float32)


def _engine(params, **kw):
    return ServingEngine(params, CFG, capacity=2, block_size=4,
                         prefill_buckets=(8, 16), max_seq_len=64, **kw)


def _submit(eng, n, new=4, seed=0):
    rng = np.random.RandomState(seed)
    return eng.submit(rng.randint(0, 97, (n,)).astype(np.int32),
                      GenerationConfig(max_new_tokens=new, greedy=True))


def _trainer(**kw):
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    return Trainer(lambda p, t, l: loss_fn(p, t, l, CFG), mesh,
                   param_shardings(mesh, CFG), data_spec=P(), lr=1e-3,
                   **kw)


def _batch():
    toks = np.random.RandomState(0).randint(0, 97, (2, 8))
    return (jnp.asarray(toks, jnp.int32),
            jnp.asarray(np.roll(toks, -1, -1), jnp.int32))


def _profiled(tmp_path, work):
    """Host events [(start_ns, end_ns, name, stats)] of the profiler's
    python line while ``work()`` ran."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        work()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))[0]
    out = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                out.extend((e.start_ns, e.start_ns + e.duration_ns, e.name,
                            dict(e.stats)) for e in line.events)
    return out


@pytest.fixture(scope="module")
def served(params, tmp_path_factory):
    """One engine under a profiler session, the invariant check on so
    that ``serve/observe`` has a branch to run: a 20-token prompt (two
    chunks) beside a short one, so that a chunk and a decode step share
    a step."""
    import os
    os.environ["PADDLE_TPU_CHECK_INVARIANTS"] = "1"
    try:
        eng = _engine(params)
    finally:
        del os.environ["PADDLE_TPU_CHECK_INVARIANTS"]
    _submit(eng, 5)
    eng.drain()                       # compile outside the session

    def work():
        _submit(eng, 5, seed=1)
        eng.step()
        _submit(eng, 20, seed=2)
        eng.drain()
    return _profiled(tmp_path_factory.mktemp("serve"), work), eng


def test_span_names_are_frozen():
    assert SERVE_SPANS == (
        "serve/step", "serve/admit", "serve/state_reset",
        "serve/prefill_stage", "serve/prefill_dispatch", "serve/first_token_sync",
        "serve/window_release", "serve/table_upload",
        "serve/decode_dispatch", "serve/token_sync",
        "serve/emit", "serve/observe")
    assert TRAIN_SPANS == ("train/stage", "train/dispatch", "train/sync")


# serve/state_reset is entered only by an engine whose model has
# recurrent layers, serve/window_release only by one whose model has
# window layers: tests/test_granite_hybrid_serving.py and
# tests/test_mellum_serving.py hold them to the same two sinks
DENSE_SPANS = tuple(n for n in SERVE_SPANS
                    if n not in ("serve/state_reset",
                                 "serve/window_release"))


@pytest.mark.parametrize("name", DENSE_SPANS)
def test_served_request_yields_every_span_inside_a_step(served, name):
    events, _ = served
    mine = [e for e in events if e[2] == name]
    assert mine, name
    steps = [e for e in events if e[2] == "serve/step"]
    if name != "serve/step":
        for s, e, _, _ in mine:       # each child inside one serve/step
            assert any(a <= s and e <= b for a, b, _, _ in steps), name


def test_prefill_dispatch_carries_its_request(served):
    events, _ = served
    chunks = [e[3] for e in events if e[2] == "serve/prefill_dispatch"]
    assert {c["req_id"] for c in chunks} == {1, 2}
    long = [c for c in chunks if c["req_id"] == 2]
    assert [(c["pos0"], c["n"], c["bucket"]) for c in long] == [
        (0, 16, 16), (16, 4, 8)]
    firsts = [e[3] for e in events if e[2] == "serve/first_token_sync"]
    assert [f["req_id"] for f in firsts] == [1, 2]


def test_mixed_steps_counts_what_it_says(params):
    eng = _engine(params)
    _submit(eng, 5, new=6)
    eng.step()            # the prompt's one chunk, its first token, and
    #                       the slot's first decode step: a mixed step
    eng.step()            # decode alone
    assert (eng.counters["mixed_steps"], eng.counters["decode_steps"]) \
        == (1, 2)
    _submit(eng, 20, new=2, seed=1)
    eng.step()            # first chunk of the long prompt + a decode step
    assert eng.counters["mixed_steps"] == 2
    eng.drain()
    assert not eng.step()             # an idle poll is no step
    c = eng.counters
    assert c["mixed_steps"] == c["prefill_chunks"] == 3
    assert c["decode_steps"] == 5, dict(c)
    assert "mixed_steps" not in eng.metrics()   # its key set is frozen
    eng.reset_metrics()
    assert eng.counters["mixed_steps"] == 0


@pytest.mark.parametrize("name", DENSE_SPANS)
def test_observability_holds_the_same_phases(params, name):
    """The second sink: the Timeline holds each phase once with a
    duration, under the span's name or, where the engine had an event
    for that phase before, under that event's name; the older events
    and the histograms are fed by the same spans."""
    eng = _engine(params, observability=True)
    _submit(eng, 5)
    _submit(eng, 20, seed=1)
    eng.drain()
    events = eng.observability.timeline.events()
    by = {}
    for e in events:
        by.setdefault(e.name, []).append(e)
    mine = by.get(OLDER_EVENT.get(name, name))
    assert mine and all(e.dur_ms is not None and e.dur_ms >= 0
                        for e in mine)
    assert (name in by) == (name not in OLDER_EVENT)    # never twice
    lat = eng.metrics()["latency"]
    assert lat["step_ms"]["count"] == len(by["serve/step"]) \
        == eng.counters["decode_steps"]
    assert lat["prefill_chunk_ms"]["count"] == len(by["prefill_chunk"]) \
        == len(by["serve/prefill_stage"]) == 3
    assert lat["decode_step_ms"]["count"] == len(by["decode_step"]) \
        == len(by["serve/emit"])
    assert by["prefill_chunk"][0].req_id == 0
    assert by["serve/first_token_sync"][0].req_id == 0


def test_neither_sink_nothing_recorded(params, monkeypatch):
    """observability=False and no profiler session: a step appends to
    no ring and allocates no TimelineEvent; the span reads no clock."""
    def boom(*a, **k):
        raise AssertionError("event object allocated in disabled mode")
    monkeypatch.setattr(timeline_mod.TimelineEvent, "__init__", boom)
    monkeypatch.setattr(Observability, "__init__", boom)
    eng = _engine(params)
    req = _submit(eng, 5)
    eng.drain()
    assert req.done and eng.observability is None
    with span("serve/step") as s:
        pass
    assert s.dur_ms is None


def test_dropped_span_reaches_no_sink():
    obs = Observability()
    with span("serve/step", obs, hist="step_ms") as s:
        s.drop()
    assert len(obs.timeline) == 0 and s.dur_ms is None
    assert obs.hist("step_ms").snapshot()["count"] == 0
    with span("serve/step", obs, hist="step_ms", req_id=3, n=2) as s:
        pass
    (ev,) = obs.timeline.events()
    assert (ev.name, ev.req_id, ev.meta) == ("serve/step", 3, {"n": 2})
    assert ev.dur_ms == s.dur_ms
    assert obs.hist("step_ms").snapshot()["count"] == 1
    # ring=False: timed for the caller and the histogram, not recorded
    with span("serve/token_sync", obs, hist="step_ms", ring=False) as s:
        pass
    assert len(obs.timeline) == 1 and s.dur_ms >= 0
    assert obs.hist("step_ms").snapshot()["count"] == 2


@pytest.mark.parametrize("name", TRAIN_SPANS)
def test_train_spans_around_a_step(tmp_path, name):
    """The plain path enters stage and dispatch; the observed path
    waits for the device and adds sync, and feeds its phase histograms
    from the same spans."""
    observed = name == "train/sync"
    tr = _trainer(observability=observed)
    state = tr.init_state(llama.init_params(CFG, jax.random.key(0),
                                            dtype=jnp.float32))
    state, _ = tr.step(state, *_batch())          # compile outside
    box = {}

    def work():
        box["state"], box["m"] = tr.step(state, *_batch())
    events = _profiled(tmp_path, work)
    names = [e[2] for e in events if e[2].startswith("train/")]
    want = list(TRAIN_SPANS) if observed else list(TRAIN_SPANS[:2])
    assert names == want
    if observed:
        lat = tr.metrics()["latency"]
        assert lat["stage_ms"]["count"] == lat["sync_ms"]["count"] \
            == lat["dispatch_ms"]["count"] == lat["step_ms"]["count"] == 2
        # the ring holds the step once: train_step carries the split
        tl = tr.observability.timeline.events()
        assert [e.name for e in tl if e.name != "compile"] \
            == ["train_step"] * 2
        assert {"stage_ms", "dispatch_ms", "sync_ms"} <= set(tl[-1].meta)
