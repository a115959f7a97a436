"""The reduction from a device trace to numbers, on a trace recorded on
a TPU v5e (PR 23: three steps of the serving engine at the
``mistral-7b-v0.3-l16`` widths, operation names cut to 100 characters)
and on small hand-made ones."""
import os

import pytest

from benchmarks import trace as T

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_serving_trace.json.gz")


@pytest.fixture(scope="module")
def recorded():
    return T.Trace.from_json(RECORDED)


def test_union_counts_overlap_once():
    assert T.union_ns([(0, 10), (5, 10), (30, 5)]) == 20
    assert T.union_ns([(0, 10), (2, 3)]) == 10
    assert T.union_ns([]) == 0


def test_self_times_subtract_children():
    ev = [(0, 100, "%while.1 = x"), (10, 20, "%a.1 = y"),
          (40, 30, "%b.2 = z"), (45, 5, "%c = w"), (200, 10, "%d = v")]
    got = {n.split(" ")[0]: (d, leaf) for n, _, d, leaf in T.self_times(ev)}
    assert got == {"%while.1": (50, False), "%a.1": (20, True),
                   "%b.2": (25, False), "%c": (5, True), "%d": (10, True)}


def test_op_names():
    name = "%decode_mlp_block.9 = bf16[32,4096]{1,0} custom-call(...)"
    assert T.op_head(name) == "decode_mlp_block.9"
    assert T.op_key(name) == "decode_mlp_block"
    assert T.op_key("%fusion = f32[] fusion()") == "fusion"


def test_recorded_busy_idle(recorded):
    # three engine steps: chunk+decode, chunk+decode, small chunk+decode
    assert recorded.window_s() == pytest.approx(0.359833, abs=1e-5)
    assert recorded.busy_s() == pytest.approx(0.338854, abs=1e-5)
    assert recorded.busy_s() < recorded.window_s()
    gaps = recorded.idle_gaps()
    assert sum(gaps.values()) == pytest.approx(
        recorded.window_s() - recorded.busy_s(), rel=1e-6)
    # the device waits longest while the host reads the sampled tokens
    assert max(gaps, key=gaps.get) == "np.asarray(jax.Array)"


def test_recorded_programs_and_kernels(recorded):
    decode = recorded.module_runs(r"^jit_step\(")
    prefill = recorded.module_runs(r"^jit_chunk\(")
    assert len(decode) == 3 and len(prefill) == 3
    assert sum(decode) / 3 == pytest.approx(0.0711331, rel=1e-4)
    launches, seconds = recorded.kernel("decode_mlp_block")
    assert launches == 3 * 16                      # one a layer a step
    assert 0.4e-3 < seconds / launches < 0.6e-3
    assert recorded.kernel("no_such_kernel") == (0, 0.0)
    # self times never count a while body twice: they sum to the union
    total = sum(v[1] for v in recorded.op_seconds().values())
    assert total == pytest.approx(recorded.busy_s(), rel=1e-3)


def test_recorded_breakdown_shape(recorded):
    b = recorded.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True)


def test_exposed_collective_share():
    ops = {0: [(0, 100, "%fusion.1 = f"), (100, 50, "%all-reduce.3 = a"),
               (120, 10, "%fusion.2 = f"), (150, 50, "%fusion.3 = f")]}
    tr = T.Trace(ops, {0: []}, [])
    # all-reduce runs 100..150, compute covers 120..130 of it: 40 exposed
    assert tr.exposed_collective_share() == pytest.approx(40 / 200)
    none = T.Trace({0: [(0, 10, "%fusion = f")]}, {0: []}, [])
    assert none.exposed_collective_share() == 0.0


def test_share_over_100_raises():
    assert T.share_pct(1.0, 4.0, "k") == 25.0
    with pytest.raises(ValueError, match="roofline"):
        T.share_pct(1.01, 1.0, "k")


def test_empty_trace_is_refused():
    with pytest.raises(ValueError, match="no device operation"):
        T.Trace({}, {}, [])


def test_json_round_trip(tmp_path, recorded):
    p = str(tmp_path / "t.json.gz")
    recorded.to_json(p)
    again = T.Trace.from_json(p)
    assert again.busy_s() == recorded.busy_s()


def test_profiler_writes_a_trace_the_reducer_reads(tmp_path):
    """The CPU has no device plane: the operations are then the host
    events that carry an ``hlo_op`` (rehearsal only)."""
    import jax
    import jax.numpy as jnp
    prof = T.Profiler(str(tmp_path / "trace"))
    prof.start()
    with jax.profiler.TraceAnnotation("bench/engine_step"):
        x = jnp.ones((64, 64))
        jax.block_until_ready(jax.jit(lambda a: a @ a + 1)(x))
    prof.stop()
    tr = prof.load()
    assert tr.busy_s() > 0 and tr.window_s() >= tr.busy_s()
    assert any(n == "bench/engine_step" for _, _, n in tr.host)
    assert not os.path.exists(str(tmp_path / "trace"))
