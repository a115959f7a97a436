"""``BENCHMARK.json`` and every file it names, against the contract's
limits: keys, characters of names and units, one file per configuration,
traffic mix and layer metric, published widths unchanged."""
import json
import os
import re

import pytest

from benchmarks import harness

ROOT = harness.ROOT
BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
#: the published config.json of mistralai/Mistral-7B-v0.3
PUBLISHED = {"hidden_size": 4096, "intermediate_size": 14336,
             "num_hidden_layers": 32, "num_attention_heads": 32,
             "num_key_value_heads": 8, "vocab_size": 32768,
             "rope_theta": 1000000.0, "rms_norm_eps": 1e-05,
             "max_position_embeddings": 32768, "sliding_window": None,
             "tie_word_embeddings": False}
WIDTHS = ("hidden_size", "intermediate_size", "num_attention_heads",
          "num_key_value_heads", "head_dim", "vocab_size")


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 \
        and "\n" not in s and "\t" not in s


def under_paths(path):
    return any(path == p or path.startswith(p + "/") for p in BENCH["paths"])


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/run.py"]
    assert all(line(w) for w in BENCH["command"])
    assert 1 <= len(BENCH["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in BENCH["paths"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 65536


def test_every_file_under_paths_is_well_named():
    bad = []
    for p in BENCH["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), ROOT)
                if not PATH.match(rel) or not f.endswith(
                        (".py", ".json", ".md", ".gz", ".sh")):
                    bad.append(rel)
    assert bad == []


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_entry_and_file(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and line(cfg["source"]) and line(cfg["why"])
    assert cfg["source"].startswith("https://")
    assert under_paths(cfg["file"]) and PATH.match(cfg["file"])
    assert cfg["file"] == f"benchmarks/configs/{cfg['name']}.json"
    assert len(cfg["reduced"]) <= 16
    assert all(NAME.match(k) for k in cfg["reduced"])
    with open(os.path.join(ROOT, cfg["file"])) as fh:
        body = json.load(fh)
    for key in ("source", "published_keys", "reduced", "assumed",
                "deployment", "chips", "driver", "weights", "reference",
                "cost_model", "tolerance", "program", "rehearse"):
        assert key in body, key
    assert body["source"] == cfg["source"] and body["name"] == cfg["name"]
    assert sorted(body["reduced"]) == sorted(cfg["reduced"])
    assert not set(cfg["reduced"]) & set(WIDTHS)      # never a width
    for key, value in PUBLISHED.items():
        if key in cfg["reduced"]:
            assert body[key] != value
        else:
            assert body[key] == value, key
    assert body["head_dim"] == 128
    # every piece of code the file names is a file of the benchmark
    for kind, key in (("drivers", "driver"), ("weights", "weights"),
                      ("reference", "reference"),
                      ("cost_models", "cost_model")):
        assert os.path.isfile(os.path.join(
            harness.HERE, kind, body[key] + ".py")), (kind, body[key])
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


def test_configuration_files_are_distinct():
    files = [c["file"] for c in BENCH["configs"]]
    names = [c["name"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files) and len(set(names)) == len(names)
    assert 1 <= len(names) <= 24


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_workload_entry_and_mix(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert all(NAME.match(cell[k]) for k in ("name", "config", "traffic"))
    assert cell["chips"] in (1, 4) and line(cell["why"])
    assert cell["config"] in [c["name"] for c in BENCH["configs"]]
    mix = harness.load_mix(cell["traffic"])
    assert os.path.isfile(os.path.join(
        harness.HERE, "generators", mix["generator"] + ".py"))
    assert "why" in mix and "rehearse" in mix and "trace_s" in mix
    e2e = harness.metrics_of(BENCH, "end_to_end", cell["name"])
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    assert harness.metrics_of(BENCH, "per_layer", cell["name"], names)


def test_workloads_are_distinct_and_few_take_four_chips():
    cells = BENCH["workloads"]
    assert len({c["name"] for c in cells}) == len(cells) <= 24
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    four = sum(c["chips"] == 4 for c in cells)
    assert four <= max(1, len(cells) // 4)


@pytest.mark.parametrize("m", BENCH["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_metric(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                      "source"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("host_clock", "device_trace")
    assert 0.01 <= m["bound"] <= 0.1
    cells = {c["name"] for c in BENCH["workloads"]}
    assert set(m.get("workloads", cells)) <= cells


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_and_its_file(m):
    assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                      "layer", "moves"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    assert line(m["layer"])
    e2e = {e["name"]: e for e in BENCH["end_to_end"]}
    # only the process's own start (import_s) moves the set-up time
    assert m["moves"] in e2e
    assert (m["moves"] == "setup_s") == (m["layer"] == "process start")
    cells = {c["name"] for c in BENCH["workloads"]}
    for cell in m.get("workloads", ()):
        assert cell in cells
        assert cell in e2e[m["moves"]].get("workloads", cells)
    spec = harness.load_json("layer_metrics", m["name"] + ".json")
    for key in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[key] == m[key], key
    assert spec.get("workloads") == m.get("workloads")
    assert os.path.isfile(os.path.join(
        harness.HERE, "reducers", spec["reducer"] + ".py"))
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%" and m["better"] == "higher"


def test_metric_names_are_distinct_and_layers_spelled_once():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(names)) == len(names)
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    on_disk = {f[:-5] for f in os.listdir(
        os.path.join(harness.HERE, "layer_metrics"))}
    assert on_disk == {m["name"] for m in BENCH["per_layer"]}
    with open(os.path.join(ROOT, "PERF.md")) as fh:
        perf = fh.read()
    for layer in {m["layer"] for m in BENCH["per_layer"]}:
        assert layer in perf, layer
