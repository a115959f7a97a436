"""bench.py's parent: a failed config makes the exit code non-zero and
no stored capture stands in for a live result; the ladders bank every
rung as it completes."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import bench  # noqa: E402


# -- exit code + no stored result ---------------------------------------
@pytest.fixture
def quiet_bench(monkeypatch):
    """main() over the two headline configs only (its partial file,
    BENCH_PARTIAL.json, is gitignored)."""
    monkeypatch.setenv("BENCH_FAST", "1")


def _last_json(capsys):
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def test_main_exits_zero_when_every_config_ran(quiet_bench, monkeypatch,
                                               capsys):
    monkeypatch.setattr(
        bench, "_spawn", lambda name, timeout: {"value": 7.0, "cfg": name})
    assert bench.main() == 0
    out = _last_json(capsys)
    assert out["value"] == 7.0 and "failed" not in out


def test_main_exits_nonzero_and_names_the_failed_config(
        quiet_bench, monkeypatch, capsys):
    def spawn(name, timeout):
        if name == "llama":
            return {"error": "child rc=1: RESOURCE_EXHAUSTED"}
        return {"value": 7.0}
    monkeypatch.setattr(bench, "_spawn", spawn)
    assert bench.main() == 1
    out = _last_json(capsys)
    assert out["failed"] == ["llama"]
    assert "RESOURCE_EXHAUSTED" in out["llama_error"]
    assert "llama" not in out          # no stored number in its place


def test_spawn_reports_a_raising_child_as_an_error():
    """A config that raises ends its child non-zero; the parent gets
    the error, never a parsed result."""
    r = bench._spawn("no_such_config", timeout=60)
    assert "error" in r and "rc=1" in r["error"]
    assert "KeyError" in r["error"]


def test_no_stored_capture_machinery_left():
    assert not hasattr(bench, "_merge_opportunistic")
    assert not hasattr(bench, "_attach_probe_evidence")
    root = os.path.dirname(os.path.abspath(bench.__file__))
    assert not os.path.exists(
        os.path.join(root, "BENCH_OPPORTUNISTIC.json"))
    assert not os.path.exists(
        os.path.join(root, "tools", "opportunistic_bench.py"))


# -- per-rung partial banking ---------------------------------------------
@pytest.fixture
def bank_file(tmp_path, monkeypatch):
    path = tmp_path / "BENCH_LADDER_PARTIAL.json"
    monkeypatch.setenv("BENCH_BANK_PATH", str(path))
    return path


def _read(path):
    with open(path) as f:
        return json.load(f)


def test_llama_ladder_banks_each_rung(bank_file, monkeypatch):
    """Every completed rung must already be on disk when the NEXT rung
    starts — a parent killed mid-ladder keeps the partial curve."""
    seen_at_spawn = []

    def fake_spawn(name, timeout):
        assert name == "llama_rung"
        if bank_file.exists():
            seen_at_spawn.append(len(_read(bank_file)
                                     ["llama_ladder"]["curve"]))
        else:
            seen_at_spawn.append(0)
        i = int(os.environ["BENCH_LADDER_IDX"])
        return {"label": bench.LLAMA_LADDER[i][0], "value": 100.0 + i,
                "mfu": 0.1 + 0.01 * i, "params": 10 ** 6 * (i + 1)}

    monkeypatch.setattr(bench, "_spawn", fake_spawn)
    r = bench._llama_ladder(timeout=10 ** 6)
    n = len(bench.LLAMA_LADDER)
    assert seen_at_spawn == list(range(n))     # rung i sees i banked
    banked = _read(bank_file)["llama_ladder"]
    assert banked["done"] == n and banked["total"] == n
    assert [c["label"] for c in banked["curve"]] == \
        [c["label"] for c in r["curve"]]


def test_env_ladder_banks_partial_sweep_on_errors(bank_file,
                                                 monkeypatch):
    """keep_best sweeps must bank after every point, including failed
    ones (the error string is the evidence)."""
    calls = []

    def fake_spawn(name, timeout):
        calls.append(os.environ["BENCH_RESNET_POINT"])
        if len(calls) == 2:
            return {"error": "RESOURCE_EXHAUSTED: oom"}
        return {"value": 1000.0 + len(calls), "metric": "m"}

    monkeypatch.setattr(bench, "_spawn", fake_spawn)
    r = bench._env_ladder("resnet50_one", "BENCH_RESNET_POINT",
                          ("256:O1", "512:O1", "384:O1"),
                          timeout=10 ** 6, per_cap=600, keep_best=True)
    banked = _read(bank_file)["resnet50_one:BENCH_RESNET_POINT"]
    assert len(banked["sweep"]) == 3
    assert "RESOURCE_EXHAUSTED" in banked["sweep"]["512:O1"]
    assert r["value"] == 1003.0        # best of the two successes


def test_env_ladder_fallback_banks_first_success(bank_file,
                                                 monkeypatch):
    """The fallback ladder (keep_best=False) returns at the first
    success but must still bank it."""
    monkeypatch.setattr(bench, "_spawn",
                        lambda name, timeout: {"value": 7.0})
    r = bench._env_ladder("llama", "BENCH_LLAMA_RUNG", (0, 1),
                          timeout=10 ** 6, per_cap=600)
    assert r["value"] == 7.0
    banked = _read(bank_file)["llama:BENCH_LLAMA_RUNG"]
    assert banked["sweep"]["0"] == 7.0
