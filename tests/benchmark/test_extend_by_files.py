"""A later PR adds a configuration, a traffic mix and a layer metric by
adding files and entries: nothing that is there needs an edit. Proved on
a temporary copy of the benchmark."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

from benchmarks import harness


def digest(root):
    out = {}
    for d, dirs, files in os.walk(root):
        dirs[:] = [x for x in dirs if x not in ("__pycache__",)]
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_cell_from_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    root.mkdir()
    shutil.copytree(os.path.join(harness.ROOT, "benchmarks"),
                    root / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(harness.ROOT, "paddle_tpu"),
               root / "paddle_tpu")
    before = digest(root / "benchmarks")
    bench = harness.load_benchmark()

    # a configuration: a file of sizes (another depth of the tiny preset)
    cfg = harness.load_json("configs", "mistral-7b-v0.3-l16.json")
    cfg["name"] = "later-model"
    cfg["rehearse"]["model"]["num_hidden_layers"] = 3
    (root / "benchmarks/configs/later-model.json").write_text(
        json.dumps(cfg))
    # a traffic mix: a file of parameters over a mix that is there
    (root / "benchmarks/traffic/later-burst.json").write_text(json.dumps({
        "base": "chat-steady", "why": "a later PR's mix",
        "rehearse": {**harness.load_mix("chat-steady")["rehearse"],
                     "arrivals": {"process": "poisson", "rate_per_s": 9.0}}}))
    # a layer metric: a file, and a small reader of its own
    (root / "benchmarks/layer_metrics/later_decode_steps.json").write_text(
        json.dumps({"name": "later_decode_steps", "layer": "decode program",
                    "unit": "steps", "better": "lower",
                    "source": "program_counter", "moves": "out_tok_s",
                    "workloads": ["later-cell"], "reducer": "later_counter",
                    "args": {"key": "decode_steps"}}))
    (root / "benchmarks/reducers/later_counter.py").write_text(
        "def read(sources, args):\n"
        "    return float(sources['engine'][args['key']])\n")
    bench["configs"].append({
        "name": "later-model", "source": cfg["source"],
        "file": "benchmarks/configs/later-model.json",
        "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({
        "name": "later-cell", "config": "later-model",
        "traffic": "later-burst", "chips": 1, "why": "test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_mean_ms", "tpot_p95_ms", "out_tok_s"):
            m["workloads"].append("later-cell")
    bench["per_layer"].append({
        "name": "later_decode_steps", "unit": "steps", "better": "lower",
        "source": "program_counter", "layer": "decode program",
        "moves": "out_tok_s", "workloads": ["later-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    for trace in ("0", "1"):
        proc = subprocess.run(
            [sys.executable, "benchmarks/run.py", "--workload", "later-cell",
             "--seed", "5", "--seconds", "2", "--trace", trace,
             "--rehearse"], cwd=str(root), env=env, capture_output=True,
            text=True, timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        assert line["correct"] is True and line["attempted"] >= 15
        if trace == "1":
            assert line["metrics"]["later_decode_steps"]["value"] > 0
        else:
            assert set(line["metrics"]) == {"setup_s", "ttft_mean_ms",
                                            "tpot_p95_ms", "out_tok_s"}
    after = digest(root / "benchmarks")
    shutil.rmtree(root / ".jax_cache", ignore_errors=True)
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/later-model.json", "layer_metrics/later_decode_steps.json",
        "reducers/later_counter.py", "traffic/later-burst.json"]
