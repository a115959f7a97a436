"""Every cell, rehearsed end to end on the CPU at the tiny preset of
its configuration's file, with the last line held to the contract; and
the refusals: no chip, too few chips, a bare directory."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks import harness, run as bench_run

BENCH = harness.load_benchmark()
CELLS = [c["name"] for c in BENCH["workloads"]]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "7"}


def rehearse(capsys, *argv):
    bench_run.main(["--rehearse", *argv])
    lines = [l for l in capsys.readouterr().out.splitlines() if l.strip()]
    for l in lines:                       # every line is one JSON object
        assert isinstance(json.loads(l), dict), l
    return json.loads(lines[-1]), [json.loads(l) for l in lines[:-1]]


def hold_to_contract(line, cell, trace):
    assert set(line) - {"breakdown"} == {"correct", "attempted", "failed",
                                         "metrics", "device", "compared"}
    # each number compared beside its limit comes last in the line
    assert list(line)[-1] == "compared" and line["compared"]
    for row in line["compared"].values():
        assert set(row) == {"value", "limit", "ok"}
    assert line["correct"] == all(r["ok"] for r in line["compared"].values())
    assert isinstance(line["correct"], bool)
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    assert dev["platform"] == "cpu"       # a rehearsal names its device
    e2e = {m["name"]: m for m in
           harness.metrics_of(BENCH, "end_to_end", cell)}
    per = {m["name"]: m for m in
           harness.metrics_of(BENCH, "per_layer", cell, e2e)}
    want = per if trace else e2e
    assert line["metrics"], "a cell reports at least one metric"
    for name, m in line["metrics"].items():
        assert name in want, name
        assert m["unit"] == want[name]["unit"]
        assert isinstance(m["value"], float) and m["value"] == m["value"]
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] >= dev["busy_s"]
        b = line["breakdown"]
        assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
        # what needs the chip's planes is left out on the CPU, the
        # counters and host spans are read everywhere
        assert set(line["metrics"]) <= set(per)
    else:
        assert set(line["metrics"]) == set(e2e)
        assert all(m["value"] > 0 for m in line["metrics"].values())


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_of_every_cell(capsys, cell, trace):
    line, earlier = rehearse(capsys, "--workload", cell, "--seed",
                             str(2 ** 31 + 5), "--seconds", "2",
                             "--trace", str(trace))
    hold_to_contract(line, cell, trace)
    assert line["correct"] is True
    checks = [e["check"] for e in earlier if "check" in e]
    assert checks and all("limit" in c and "value" in c for c in checks)
    assert earlier[0]["start"]["device"]["platform"] == "cpu"


def test_same_seed_same_work(capsys):
    a, _ = rehearse(capsys, "--workload", CELLS[0], "--seed", "9",
                    "--seconds", "1", "--trace", "0")
    b, _ = rehearse(capsys, "--workload", CELLS[0], "--seed", "9",
                    "--seconds", "1", "--trace", "0")
    assert a["attempted"] == b["attempted"]


def run_cli(args, cwd=harness.ROOT, env=ENV):
    return subprocess.run([sys.executable, "benchmarks/run.py", *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=300)


def no_result(proc):
    assert proc.returncode != 0
    for l in proc.stdout.splitlines():
        assert '"correct"' not in l


def test_refuses_to_measure_without_a_chip():
    proc = run_cli(["--workload", CELLS[0], "--seed", "1", "--seconds",
                    "1", "--trace", "0"])
    no_result(proc)
    assert "needs a TPU" in proc.stderr


def test_refuses_fewer_chips_than_the_cell_asks(monkeypatch):
    import jax
    with pytest.raises(SystemExit) as e:
        harness.require_chips(jax, len(jax.devices()) + 1, rehearse=True)
    assert e.value.code not in (0, None)

    class FakeTpu:
        platform, device_kind = "tpu", "TPU v5 lite"
    monkeypatch.setattr(jax, "devices", lambda: [FakeTpu()])
    assert harness.require_chips(jax, 1, rehearse=False)["count"] == 1
    with pytest.raises(SystemExit):
        harness.require_chips(jax, 4, rehearse=False)


def test_unknown_workload_is_refused():
    no_result(run_cli(["--workload", "no-such-cell", "--rehearse"]))


def test_bare_directory_exits_non_zero(tmp_path):
    """Only BENCHMARK.json and the files under ``paths``: the program is
    not there, so there is nothing to measure."""
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    for p in BENCH["paths"]:
        shutil.copytree(os.path.join(harness.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in ENV.items() if k != "PYTHONPATH"}
    no_result(run_cli(["--workload", CELLS[0], "--seed", "1", "--seconds",
                       "1", "--trace", "0", "--rehearse"],
                      cwd=str(tmp_path), env=env))
