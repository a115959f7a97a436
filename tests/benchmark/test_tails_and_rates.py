"""ISSUE 47: every judged number has to resolve. A cell is judged on ONE
tail of ``tpot_ms``, the highest that its window's count of requests
supports; ``setup_s`` runs from the end of the imports and ``import_s``
stands beside it in every cell; the new mixes rehearse."""
import json
import types

import numpy as np
import pytest

from benchmarks import harness
from benchmarks.drivers import serving_engine as drv
from benchmarks.tools import spread as spread_tool

BENCH = harness.load_benchmark()
CELLS = {c["name"]: c for c in BENCH["workloads"]}
E2E = {m["name"]: m for m in BENCH["end_to_end"]}
TAILS = {f"tpot_p{q}_ms": q for q in drv.TPOT_TAILS}
BEYOND = 10                    # values a percentile needs beyond it
#: cells judged on a tail with FEWER beyond it than the count asks for,
#: {cell: least count beyond}, each with its reason (PERF.md 2).
#: Mellum 2 (82 requests): ISSUE 47 asks for the highest tail that has
#: the count AND meets the rule; over its ten runs p75, which has the
#: count, does not meet it (2.84% against 2.5%) and p90 (8.2 beyond)
#: does (2.13%): the same 82 requests top every run's tail, so what
#: spreads is the level, not the sample. tp4 (107 requests): not run on
#: four chips in PR 47, so it stays as the parent judges it (p95)
MEASURED = {"mellum2-code-mixed": 8, "mistral7b-tp4-chat-steady": 5}


def open_loop_cells():
    """The cells whose mix fixes a rate of arrivals."""
    return [name for name, c in CELLS.items()
            if "arrivals" in harness.load_mix(c["traffic"])]


def window_requests(cell):
    mix = harness.load_mix(CELLS[cell]["traffic"])
    cfg = harness.load_json("configs", CELLS[cell]["config"] + ".json")
    gen = harness.plugin("generators", mix["generator"]).Generator(
        mix, 7, BENCH["run_seconds"], cfg["vocab_size"])
    return gen.offered()["window_requests"]


def judged_tail(cell):
    return [name for name in TAILS if cell in E2E.get(name, {})
            .get("workloads", ())]


@pytest.mark.parametrize("cell", open_loop_cells())
def test_a_cell_is_judged_on_one_tail_its_window_supports(cell):
    mine = judged_tail(cell)
    assert len(mine) == 1, mine
    q = TAILS[mine[0]]
    n = window_requests(cell)
    # (a sessions mix counts its sessions' turns)
    assert n * (100 - q) / 100.0 >= MEASURED.get(cell, BEYOND), \
        (cell, mine[0], n)


@pytest.mark.parametrize("cell", [c for c in CELLS
                                  if c not in open_loop_cells()])
def test_a_cell_without_arrivals_is_judged_on_no_tail(cell):
    assert judged_tail(cell) == []


@pytest.mark.parametrize("name", sorted(TAILS))
def test_the_tails_share_one_definition(name):
    if name not in E2E:
        pytest.skip("no cell is judged on it")
    m = E2E[name]
    assert (m["unit"], m["better"], m["source"]) == ("ms", "lower",
                                                     "host_clock")
    # a bound is set from the ten runs of the cells judged on the metric
    # (PERF.md 2), inside what the driver takes
    assert 0.01 <= m["bound"] <= 0.1 and m["workloads"]


def test_per_layer_metrics_move_the_tail_their_cell_is_judged_on():
    for m in BENCH["per_layer"]:
        if m["moves"] in TAILS:
            for cell in m["workloads"]:
                assert judged_tail(cell) == [m["moves"]], (m["name"], cell)


def rec(first, last, n, due=0.0):
    r = drv.Record({"prompt": np.zeros(4, np.int32), "max_new_tokens": n},
                   types.SimpleNamespace(admit_t=due), due, due, True)
    r.first_t, r.last_t, r.seen = first, last, n
    return r


def test_window_metrics_computes_the_three_tails(capsys):
    # 21 requests whose mean gap is 1, 2, ... 21 ms, plus one of a single
    # token, which has no gap and enters no tail
    done = [rec(1.0, 1.0 + k * 1e-3 * 10, 11) for k in range(1, 22)]
    done.append(rec(1.0, 1.0, 1))
    counts = {"t_open": 0.0, "t_close": 10.0, "window_tokens": 232,
              "steps": 1}
    win, e2e, samples = drv.window_metrics(done, counts, 10.0, False)
    assert len(win) == 22 and len(samples["tpot_ms"]) == 21
    assert samples["tpot_tokens"] == [11] * 21
    assert e2e["tpot_p75_ms"] == pytest.approx(16.0)
    assert e2e["tpot_p90_ms"] == pytest.approx(19.0)
    assert e2e["tpot_p95_ms"] == pytest.approx(20.0)
    summary = [json.loads(l) for l in capsys.readouterr().out.splitlines()
               if '"summary"' in l][-1]["summary"]["tpot_ms"]
    assert (summary["p75"], summary["p90"], summary["p95"]) == \
        pytest.approx((16.0, 19.0, 20.0))
    assert summary["n"] == 21


def test_a_closed_loop_reports_no_tail(capsys):
    counts = {"t_open": 0.0, "t_close": 10.0, "window_tokens": 11,
              "steps": 1}
    _, e2e, _ = drv.window_metrics([rec(1.0, 2.0, 11)], counts, 10.0, True)
    assert set(e2e) == {"out_tok_s"}


def test_import_s_is_a_per_layer_metric_of_every_cell():
    m = [m for m in BENCH["per_layer"] if m["name"] == "import_s"]
    assert len(m) == 1 and "workloads" not in m[0]
    assert m[0]["moves"] == "setup_s" and m[0]["source"] == "host_clock"
    for cell in CELLS:
        reported = [e["name"] for e in
                    harness.metrics_of(BENCH, "end_to_end", cell)]
        names = [p["name"] for p in
                 harness.metrics_of(BENCH, "per_layer", cell, reported)]
        assert "import_s" in names, cell
    read = harness.plugin("reducers", "source_value").read
    assert read({"import_s": 12.5}, {"key": "import_s"}) == 12.5
    assert read({}, {"key": "import_s"}) is None


def test_setup_runs_from_the_end_of_the_imports(capsys):
    """One rehearsed run: ``setup_s`` is the build, the warm-up and the
    warm traffic, and ``import_s`` the phases before them."""
    from benchmarks import run as bench_run
    bench_run.main(["--rehearse", "--workload", "mistral7b-chat-steady",
                    "--seed", "3", "--seconds", "1", "--trace", "0"])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    phases = [l for l in lines if "setup_phases" in l][-1]["setup_phases"]
    assert {"harness_s", "import_jax_s", "devices_s", "import_program_s",
            "build_s", "warm_up_s", "warm_traffic_s"} <= set(phases)
    setup = lines[-1]["metrics"]["setup_s"]["value"]
    own = phases["build_s"] + phases["warm_up_s"] + phases["warm_traffic_s"]
    assert own <= setup <= own + 0.5


NEW_MIXES = sorted(c["traffic"] for c in CELLS.values()
                   if c["traffic"].endswith("-pr47"))


@pytest.mark.parametrize("name", NEW_MIXES)
def test_a_new_mix_keeps_the_old_ones_work(name):
    new = harness.load_json("traffic", name + ".json")
    changed = {"why", "arrivals", "grace_s"}
    assert set(new) <= {"base"} | changed
    old = harness.load_mix(new["base"])
    merged = harness.load_mix(name)
    for key in old:
        if key not in changed:
            assert merged[key] == old[key], key
    assert merged["arrivals"]["process"] == old["arrivals"]["process"]
    assert (merged["arrivals"], merged["grace_s"]) != (old["arrivals"],
                                                      old["grace_s"])


def test_the_four_chip_mix_outlasts_the_profilers_stop():
    mix = harness.load_mix(CELLS["mistral7b-tp4-chat-steady"]["traffic"])
    assert mix["grace_s"] > 50


def test_the_rule_of_the_spread_tool():
    assert spread_tool.spread([1.0, 2.0, 3.0]) is None
    # statistics.quantiles(n=4) of 1..10: 2.75 and 8.25
    assert spread_tool.spread(list(map(float, range(1, 11)))) == \
        pytest.approx(5.5 / 5.5)
    row = spread_tool.row("m", [100.0, 101.0, 100.5, 99.5, 100.2, 100.1,
                                99.9, 100.3, 100.0, 99.8], bound=0.03)
    assert row["half_bound_pct"] == 1.5 and row["meets_rule"] is True


# -- the sessions mix: a turn follows its answer ------------------------
def _served_sessions(mix, seed, serve_s, monkeypatch):
    """Drive the session generator as the serving driver does, every
    answer taking ``serve_s``: [(session, turn, due)] as handed out."""
    mod = harness.plugin("generators", "open_loop_sessions")
    now = {"t": 0.0}
    monkeypatch.setattr(mod.time, "perf_counter", lambda: 100.0 + now["t"])
    gen = mod.Generator(mix, seed, 6.0, 512)
    seen, live = [], []
    while now["t"] < gen.end + 2.0:
        for spec in gen.due(now["t"]):
            seen.append((spec["session"], spec["turn"], spec["due"]))
            live.append((now["t"] + serve_s, spec))
        for item in [x for x in live if x[0] <= now["t"]]:
            live.remove(item)
            r = types.SimpleNamespace(spec=item[1], req=types.SimpleNamespace(
                tokens=[1] * item[1]["max_new_tokens"]))
            gen.finished(r.spec)
        now["t"] += 0.01
    assert gen.answers_unseen == 0
    return seen


@pytest.mark.parametrize("serve_s", [0.05, 0.21])
def test_a_turn_falls_due_think_s_after_its_answer(serve_s, monkeypatch):
    """What PR 47 measured and left as it is: the due time of a turn
    follows the instant its answer was served, so a run's schedule
    follows the program's pace (PERF.md 6)."""
    mix = harness.load_mix(CELLS["mistral7b-prefix-sessions"]["traffic"])
    mix.update(mix["rehearse"])
    seen = _served_sessions(mix, 3, serve_s, monkeypatch)
    by = {}
    for s, k, due in seen:
        by.setdefault(s, []).append(due)
    gaps = [b - a for dues in by.values() for a, b in zip(dues, dues[1:])]
    assert gaps and all(serve_s + mix["think_s"] - 0.02 <= g
                        <= serve_s + mix["think_s"] + 0.03 for g in gaps)


def test_the_sessions_cell_keeps_its_sessions():
    mix = harness.load_mix(CELLS["mistral7b-prefix-sessions"]["traffic"])
    old = harness.load_mix("prefix-sessions")
    for key in old:
        if key not in ("why", "arrivals"):
            assert mix[key] == old[key], key
    assert set(mix) == set(old)
    assert mix["arrivals"]["rate_per_s"] > old["arrivals"]["rate_per_s"]


# -- the knee rule (fixed before the windows that vote were read) ------
def _rows(rates, ttft_p95, queue_mid, queue_end):
    return [{"rate_per_s": r, "ttft_p95_ms": t, "queue_mid": m,
             "queue_end": e, "unfinished": 0}
            for r, t, m, e in zip(rates, ttft_p95, queue_mid, queue_end)]


def test_the_knee_rule():
    from benchmarks.tools import sweep_rate
    # PR 47's first chat sweep, one window a rate (my chip runs)
    chat = _rows([3.52, 4.05, 4.65, 5.35, 6.15, 7.07, 8.13, 9.35],
                 [239, 270, 312, 541, 315, 422, 1478, 6267],
                 [0, 0, 0, 0, 0, 0, 8, 23], [0, 0, 0, 0, 0, 1, 0, 52])
    got = sweep_rate.knee(chat)
    # the first rate that fails ends the search, whatever 6.15 reads
    assert (got["knee"], got["rate"], got["reached"]) == (4.65, 3.7, True)
    assert got["plateau_ttft_p95_ms"] == 270
    # swept again, a rate's windows vote: more than half have to hold
    again = _rows([5.35] * 3, [300, 310, 620], [0] * 3, [0] * 3)
    assert sweep_rate.knee(chat + again[:2])["knee"] == 6.15
    assert sweep_rate.knee(chat + again[1:])["knee"] == 4.65    # 2 of 4
    assert sweep_rate.knee(chat + again)["windows"]["5.35"] == 4
    # a sweep that ends on a sustained rate has not found the knee
    assert sweep_rate.knee(chat[:3])["reached"] is False
    sessions = _rows([2.01, 2.31, 2.66, 3.06, 3.52, 4.05],
                     [145, 232, 243, 558, 576, 2572],
                     [0, 0, 0, 0, 0, 6], [0, 0, 0, 0, 3, 37])
    assert sweep_rate.knee(sessions)["knee"] == 2.66
    # a queue that grows fails a rate whatever its tail reads
    grown = _rows([1.0, 1.15, 1.32], [100, 100, 100], [0, 0, 1], [0, 0, 9])
    assert sweep_rate.knee(grown)["knee"] == 1.15
    stuck = [dict(r, unfinished=3) for r in grown]
    assert sweep_rate.knee(stuck) is None
