"""The readers of the program's own spans and counters
(``reducers/program_span_ms``, ``idle_in_spans_pct``,
``counter_ratio_pct``, ``exposed_collective_pct``), each on a hand-built
trace with known answers; and a traced CPU rehearsal of the chat cell,
which has to report the span metrics (host spans are there without a
chip)."""
import json

import pytest

from benchmarks import harness, run as bench_run
from benchmarks.trace import Trace

MS = 1_000_000          # nanoseconds
STEP = "serve/step"
SYNCS = ["serve/token_sync", "serve/first_token_sync"]


def reader(name):
    return harness.plugin("reducers", name).read


def trace(host, ops=None):
    """Device 0 runs ``ops`` [(start_ms, dur_ms, name)]; the host line
    holds ``host`` likewise."""
    ops = ops or [(0, 1, "%fusion.1 = f32[] fusion()")]
    ns = lambda rows: [(s * MS, d * MS, n) for s, d, n in rows]  # noqa: E731
    return Trace({0: ns(ops)}, {0: []}, ns(host))


# two steps of 10 and 20 ms; the second holds a first-token read of 4 ms
# with JAX's own event nested inside it, and a token read of 6 ms
HOST = [(0, 10, STEP), (2, 3, "serve/token_sync"),
        (20, 20, STEP), (21, 4, "serve/first_token_sync"),
        (22, 2, "np.asarray(jax.Array)"), (30, 6, "serve/token_sync"),
        (50, 5, "bench/idle_sleep")]


@pytest.mark.parametrize("less,want", [
    ([], 15.0),                              # (10 + 20) / 2
    (SYNCS, (10 - 3 + 20 - 4 - 6) / 2),
    (SYNCS + ["np.asarray(jax.Array)"], (10 - 3 + 20 - 4 - 6) / 2),
], ids=["whole", "less-syncs", "nested-child-once"])
def test_program_span_ms(less, want):
    got = reader("program_span_ms")(
        {"trace": trace(HOST)}, {"span": STEP, "less": less})
    assert got == pytest.approx(want)


def test_program_span_ms_child_outside_a_parent_is_not_subtracted():
    host = HOST + [(45, 3, "serve/token_sync")]      # under no step
    got = reader("program_span_ms")(
        {"trace": trace(host)}, {"span": STEP, "less": SYNCS})
    assert got == pytest.approx((10 - 3 + 20 - 4 - 6) / 2)


def test_idle_in_spans_counts_a_gap_under_jax_own_event():
    """Busy 0-4, 8-30, 34-40 ms; window 0-60 (the host's last event
    ends it). The gap 4-8 lies under JAX's DevicePut inside
    serve/table_upload inside the first step: still the step's. The gap
    30-34 lies in the second step; 40-60 under the caller's sleep."""
    ops = [(0, 4, "%a.1 = f32[] fusion()"), (8, 22, "%b.2 = f32[] fusion()"),
           (34, 6, "%c.3 = f32[] fusion()")]
    host = [(0, 10, STEP), (3, 6, "serve/table_upload"),
            (4, 4, "DevicePut"), (20, 20, STEP),
            (40, 20, "bench/idle_sleep")]
    t = trace(host, ops)
    got = reader("idle_in_spans_pct")({"trace": t}, {"span": STEP})
    assert got == pytest.approx(100.0 * (4 + 4) / 60)
    # Trace.idle_gaps names the innermost event: the reason for a reader
    # of its own
    assert "DevicePut" in t.idle_gaps()
    idle = harness.plugin("reducers", "device_idle").read({"trace": t}, {})
    assert got <= idle == pytest.approx(100.0 * 28 / 60)


@pytest.mark.parametrize("step_end,inside", [(12, 4), (20, 12), (30, 16)],
                         ids=["ends-in-the-gap", "covers-the-gap",
                              "runs-on-under-the-next-operation"])
def test_idle_in_spans_splits_a_gap_at_the_span_s_edge(step_end, inside):
    """Busy 0-8, 20-24, 28-40 ms: gaps 8-20 and 24-28. A step from 4 ms
    to ``step_end``, another 32-40 under an operation. Only the part of
    a gap under a step is the step's (the rest is the caller's loop),
    and a step's time under an operation is not idle."""
    ops = [(0, 8, "%a.1 = f32[] fusion()"), (20, 4, "%b.2 = f32[] fusion()"),
           (28, 12, "%c.3 = f32[] fusion()")]
    host = [(4, step_end - 4, STEP), (step_end, 1, "bench/next_due"),
            (32, 8, STEP)]
    got = reader("idle_in_spans_pct")({"trace": trace(host, ops)},
                                      {"span": STEP})
    assert got == pytest.approx(100.0 * inside / 40)


def test_counter_ratio_pct():
    read = reader("counter_ratio_pct")
    args = {"num": "mixed_steps", "den": "decode_steps"}
    traced = {"engine0": {"mixed_steps": 3, "decode_steps": 10},
              "engine1": {"mixed_steps": 8, "decode_steps": 30}}
    assert read({"traced": traced}, args) == pytest.approx(25.0)
    traced["engine1"]["decode_steps"] = 10           # did not move
    assert read({"traced": traced}, args) is None


PSUM = ("%psum.14 = bf16[32,4096]{1,0:T(8,128)(2,1)S(1)} all-reduce("
        "%fusion.92), channel_id=1, replica_groups={{0,1,2,3}}, "
        "to_apply=%region_3.6, metadata={op_name=\"jit(step)/shard_map/"
        "while/body/closed_call/psum\"}")


@pytest.mark.parametrize("collective", [
    "%all-reduce.3 = f32[] all-reduce()", PSUM,
    "%all-gather-start.2 = f32[] all-gather-start()"],
    ids=["named-by-xla", "psum-of-a-shard_map", "async-start"])
def test_exposed_collective_pct(collective):
    """A collective of 10 ms with a fusion of 4 ms running inside it
    (events of a device's line nest, never cross), all inside the
    ``while`` of the layer loop: 6 exposed of a window of 40. A jax psum
    keeps its own name and is known by its opcode."""
    ops = [(0, 5, "%fusion.1 = f32[] fusion()"),
           (8, 14, "%while.7 = (s32[], f32[]) while(%tuple.1), "
                   "condition=%cond, body=%body"),
           (10, 10, collective),
           (12, 4, "%fusion.4 = f32[] fusion()"),
           (30, 10, "%fusion.2 = f32[] fusion()")]
    got = reader("exposed_collective_pct")(
        {"trace": trace([(0, 1, STEP)], ops)}, {})
    assert got == pytest.approx(100.0 * 6 / 40)


@pytest.mark.parametrize("name,args", [
    ("program_span_ms", {"span": STEP, "less": SYNCS}),
    ("idle_in_spans_pct", {"span": STEP}),
    ("counter_ratio_pct", {"num": "mixed_steps", "den": "decode_steps"}),
    ("exposed_collective_pct", {}),
])
def test_nothing_to_read_is_none(name, args):
    """A commit of the program without the spans and counters (the
    parent of the PR that brought them), a one-chip trace without a
    collective, a run without a trace: the metric is left out."""
    bare = trace([(0, 5, "bench/engine_step")])
    old = {"engine0": {"decode_steps": 1}, "engine1": {"decode_steps": 9}}
    assert reader(name)({"trace": bare, "traced": old}, args) is None
    assert reader(name)({"trace": None, "traced": None}, args) is None


def test_traced_rehearsal_reports_the_span_metrics(capsys):
    bench_run.main(["--rehearse", "--workload", "mistral7b-chat-steady",
                    "--seed", "11", "--seconds", "2", "--trace", "1"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = line["metrics"]
    for name in ("step_host_ms.lat", "idle_in_step_pct.lat",
                 "mixed_step_pct.lat"):
        assert name in got, name
    assert got["step_host_ms.lat"]["value"] > 0
    assert 0 <= got["idle_in_step_pct.lat"]["value"] \
        <= got["device_idle_pct.lat"]["value"]
    assert 0 <= got["mixed_step_pct.lat"]["value"] <= 100
    gaps = [name for name, _ in line["breakdown"]["idle_gaps"]]
    assert any(name.startswith("serve/") for name in gaps), gaps
