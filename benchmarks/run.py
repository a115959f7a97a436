#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which alone touches JAX. It needs a TPU and the cell's
number of chips and exits non-zero without them, printing no result.
``--rehearse`` runs the tiny preset of the configuration's file on
whatever JAX finds (the CPU in the tests): its lines name that device
and are never a measurement. ``--control <name>`` switches on one of
the lower-precision paths the configuration's file lists under
``controls``; such a run has to end with ``correct`` false.

The last line of standard output is the result object; every earlier
line is one JSON object too (what was offered, summaries, each number
the correctness check compared beside its limit). The result's last key,
``compared``, holds those numbers and limits again, and they are the
last lines of standard error.
"""
import argparse
import json
import os
import shutil
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import harness  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default=None)
    ap.add_argument("--dump", default=None,
                    help="write the driver's per-request samples here")
    return ap.parse_args(argv)


def context(args, bench, t_start):
    """Everything a driver needs, from the files the workload names."""
    cell = harness.find_cell(bench, args.workload)
    cfg = harness.load_json("configs", cell["config"] + ".json")
    mix = harness.load_mix(cell["traffic"])
    model = {k: v for k, v in cfg.items()
             if k in cfg["published_keys"]}
    engine_options = dict(cfg.get("engine", {}))
    trainer_options = dict(cfg.get("trainer", {}))
    tolerance = dict(cfg["tolerance"])
    if args.rehearse:
        tiny = cfg["rehearse"]
        model.update(tiny["model"])
        engine_options.update(tiny.get("engine", {}))
        trainer_options.update(tiny.get("trainer", {}))
        tolerance.update(tiny.get("tolerance", {}))
        mix.update(mix.get("rehearse", {}))
    ctx = {"cell": cell, "config": cfg, "mix": mix, "model": model,
           "engine_options": engine_options,
           "trainer_options": trainer_options, "tolerance": tolerance,
           "seed": args.seed, "trace": bool(args.trace),
           "rehearse": args.rehearse, "chips": cell["chips"],
           "seconds": float(args.seconds if args.seconds is not None
                            else bench["run_seconds"]),
           "t_start": t_start,
           # scratch for the trace and the reference's numbers; one a
           # process, so that runs side by side (the tests' workers) do
           # not sweep each other's files away
           "trace_dir": os.path.join(ROOT, ".bench_trace",
                                     f"{cell['name']}.{os.getpid()}")}
    if args.control:
        control = cfg["controls"][args.control]
        ctx["engine_options"].update(control.get("engine", {}))
        ctx["trainer_options"].update(control.get("trainer", {}))
        if "reference_fake_quant" in control:
            ctx["reference_fake_quant"] = control["reference_fake_quant"]
    return ctx


def main(argv=None, t_start=None):
    args = parse(argv)
    t_start = T_START if t_start is None else t_start
    harness.COMPARED.clear()
    bench = harness.load_benchmark()
    ctx = context(args, bench, t_start)
    cell = ctx["cell"]

    driver = harness.plugin("drivers", ctx["config"]["driver"])
    # the process's start, phase by phase: the interpreter and the
    # harness's own files, JAX, the chip's client, the program. Their
    # sum is ``import_s``; ``setup_s`` runs from its end (t_imported)
    phases = ctx["phases"] = harness.Phases(t_start)
    phases.mark("harness_s")
    if hasattr(driver, "before_jax"):     # e.g. a reference of its own
        driver.before_jax(ctx, sys.argv[1:] if argv is None else argv)
        phases.skip()

    import jax
    phases.mark("import_jax_s")
    device = harness.require_chips(jax, cell["chips"], args.rehearse)
    phases.mark("devices_s")
    ctx["peak"] = (harness.peak_table(device["kind"])
                   if device["platform"] == "tpu" else None)
    import paddle_tpu  # noqa: F401 — the program: x64 mode, compile cache
    ctx["t_imported"] = phases.mark("import_program_s")
    ctx["import_s"] = sum(phases.values())
    ctx["compiles"] = harness.CompileCounter()
    harness.say(start={"workload": cell["name"], "seed": args.seed,
                       "seconds": ctx["seconds"], "trace": args.trace,
                       "rehearse": args.rehearse, "control": args.control,
                       "device": device,
                       "compile_cache": jax.config.jax_compilation_cache_dir})

    try:
        result = driver.run(ctx)
    finally:
        shutil.rmtree(ctx["trace_dir"], ignore_errors=True)
    harness.say(setup_phases=phases)
    if args.dump:
        with open(args.dump, "w") as fh:
            json.dump({"samples": result["sources"].get("samples"),
                       "phases": phases,
                       "end_to_end": result["end_to_end"]}, fh)

    e2e = harness.metrics_of(bench, "end_to_end", cell["name"])
    reported = [m["name"] for m in e2e if m["name"] in result["end_to_end"]]
    device["memory_peak_bytes"] = result["memory_peak_bytes"]
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        sources = result["sources"]
        line["metrics"] = harness.read_layer_metrics(
            bench, cell["name"], reported, sources)
        trace = sources["trace"]
        harness.say(trace={"device_ops": sum(map(len, trace.ops.values())),
                           "program_runs": sum(map(len,
                                                   trace.modules.values())),
                           "host_events": len(trace.host)})
        device["busy_s"] = trace.busy_s()
        device["window_s"] = trace.window_s()
        line["breakdown"] = trace.breakdown()
    else:
        units = {m["name"]: m["unit"] for m in e2e}
        line["metrics"] = {n: {"value": float(result["end_to_end"][n]),
                               "unit": units[n]} for n in reported}
    line["device"] = device
    # each number compared beside its limit: the line's last key and the
    # last lines of standard error
    line["compared"] = {
        r["compared"]: {"value": r["value"] if r["value"] == r["value"]
                        else None, "limit": r["limit"], "ok": r["ok"]}
        for r in harness.COMPARED}
    for name, r in line["compared"].items():
        print(f"compared: {name}: {r['value']} (limit: {r['limit']})"
              f"{'' if r['ok'] else ' NOT MET'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return line


if __name__ == "__main__":
    main()
