#!/usr/bin/env python3
"""The rule a cell is held to, measured: N runs of ONE commit, a fresh
process each, the cell's own window, ``--trace 0``, a seed a run; then
the spread (IQR / median, quartiles as ``statistics.quantiles(n=4)``
gives them: the driver's) of every end-to-end metric the cell is judged
on, which has to be at most HALF the metric's bound.

    python3 benchmarks/tools/spread.py --workload <cell> [<cell> ...] \\
        --runs 10 --seed0 4700000100 --out chiprun_out/spread

    python3 benchmarks/tools/spread.py --table chiprun_out/spread

The first form needs the cells' chips (this process never touches JAX;
each run is a child that does); it keeps every run's output and
``--dump`` under ``--out`` and ends with the table. The second reads a
directory of kept runs again, here or anywhere, and prints the table
alone: per cell the requests attempted, each judged metric's median,
spread and half-bound, the spreads of p50 / p75 / p90 / p95 of
``tpot_ms`` (all requests, and those with at least ``--min-gaps``
gaps), and of the phases of ``import_s`` and ``setup_s``.
"""
import argparse
import glob
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import load_benchmark, percentile  # noqa: E402

IMPORT_PHASES = ("harness_s", "import_jax_s", "devices_s",
                 "import_program_s")


def spread(values):
    """IQR / median, as the driver takes it; None under four values."""
    if len(values) < 4:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def run_cell(cell, runs, seed0, seconds, out, rehearse=False):
    os.makedirs(out, exist_ok=True)
    for i in range(runs):
        seed = seed0 + i
        stem = os.path.join(out, f"{cell}.{seed}")
        cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
               "--workload", cell, "--seed", str(seed), "--trace", "0",
               "--dump", stem + ".dump.json"]
        if seconds is not None:
            cmd += ["--seconds", str(seconds)]
        if rehearse:
            cmd.append("--rehearse")
        with open(stem + ".out", "w") as fo, open(stem + ".err", "w") as fe:
            rc = subprocess.run(cmd, cwd=ROOT, stdout=fo, stderr=fe).returncode
        print(json.dumps({"ran": cell, "seed": seed, "rc": rc}), flush=True)


def load(out):
    """{cell: [run]}: a run is its last line, its dump, its seed."""
    cells = {}
    for path in sorted(glob.glob(os.path.join(out, "*.out"))):
        cell, seed = os.path.basename(path)[:-4].rsplit(".", 1)
        lines = [l for l in open(path).read().splitlines() if l.strip()]
        if not lines or '"correct"' not in lines[-1]:
            cells.setdefault(cell, []).append({"seed": int(seed),
                                               "line": None})
            continue
        run = {"seed": int(seed), "line": json.loads(lines[-1]),
               "earlier": [json.loads(l) for l in lines[:-1]]}
        dump = path[:-4] + ".dump.json"
        if os.path.isfile(dump):
            run["dump"] = json.load(open(dump))
        cells.setdefault(cell, []).append(run)
    return cells


def row(name, values, bound=None):
    sp = spread(values)
    out = {"what": name, "n": len(values),
           "median": statistics.median(values) if values else None,
           "spread_pct": None if sp is None else round(100 * sp, 3),
           "values": [round(v, 4) for v in values]}
    if bound is not None:
        out["half_bound_pct"] = round(50 * bound, 3)
        out["meets_rule"] = sp is not None and sp <= bound / 2
    return out


def table(out, min_gaps):
    bounds = {m["name"]: m["bound"] for m in load_benchmark()["end_to_end"]}
    for cell, runs in load(out).items():
        good = [r for r in runs if r["line"]]
        print(json.dumps({
            "cell": cell, "runs": len(runs), "no_result": len(runs)
            - len(good), "correct": sum(r["line"]["correct"] for r in good),
            "attempted": [r["line"]["attempted"] for r in good],
            "failed": [r["line"]["failed"] for r in good],
            "seeds": [r["seed"] for r in runs]}))
        if not good:
            continue
        for name in good[0]["line"]["metrics"]:
            print(json.dumps(row(name, [r["line"]["metrics"][name]["value"]
                                        for r in good], bounds.get(name))))
        dumps = [r["dump"] for r in good if r.get("dump")]
        if not dumps:
            continue
        for key in dumps[0].get("end_to_end", {}):
            if key not in good[0]["line"]["metrics"]:
                print(json.dumps(row("unjudged " + key,
                                     [d["end_to_end"][key] for d in dumps])))
        for key in dumps[0]["phases"]:
            print(json.dumps(row("phase " + key,
                                 [d["phases"].get(key, 0.0) for d in dumps])))
        print(json.dumps(row("phases' sum import_s", [
            sum(d["phases"].get(p, 0.0) for p in IMPORT_PHASES)
            for d in dumps])))
        samples = [d["samples"] for d in dumps if d.get("samples")]
        if not samples or not samples[0].get("tpot_ms"):
            continue
        for label, keep in (("all", 1), (f">={min_gaps} gaps", min_gaps)):
            kept = [[t for t, n in zip(s["tpot_ms"], s["tpot_tokens"])
                     if n - 1 >= keep] for s in samples]
            print(json.dumps({"tpot sample": label,
                              "requests": [len(k) for k in kept]}))
            for q in (50, 75, 90, 95):
                print(json.dumps(row(f"tpot_ms p{q} ({label})",
                                     [percentile(k, q) for k in kept])))
        print(json.dumps(row("ttft_ms p95", [percentile(s["ttft_ms"], 95)
                                             for s in samples])))


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", nargs="+")
    ap.add_argument("--runs", type=int, default=10)
    ap.add_argument("--seed0", type=int, default=4700000100)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default="chiprun_out/spread")
    ap.add_argument("--table", default=None)
    ap.add_argument("--min-gaps", type=int, default=16)
    ap.add_argument("--rehearse", action="store_true")
    a = ap.parse_args()
    if a.table:
        return table(a.table, a.min_gaps)
    for cell in a.workload:
        run_cell(cell, a.runs, a.seed0, a.seconds, a.out, a.rehearse)
    table(a.out, a.min_gaps)


if __name__ == "__main__":
    main()
