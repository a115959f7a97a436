"""What every driver of the benchmark shares: finding files by name,
the device check, host spans, the compile counter, percentiles, the
peak table and the last line.

Nothing here knows a configuration, a traffic mix or a layer metric by
name. A workload of ``BENCHMARK.json`` names a configuration and a mix;
their files name the code they need; each is opened by that name:

    configs/<config>.json         traffic/<traffic>.json
    layer_metrics/<metric>.json   drivers/<driver>.py
    generators/<generator>.py     reducers/<reducer>.py
    reference/<name>.py           cost_models/<name>.py
    weights/<name>.py
"""
import contextlib
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")


class Refused(SystemExit):
    """The run cannot be a measurement: exit non-zero, print no result."""

    def __init__(self, why):
        print(f"benchmark: {why}", file=sys.stderr, flush=True)
        super().__init__(3)


def say(**kv):
    """One JSON line of a run's earlier output (never the last line)."""
    print(json.dumps(kv, default=float), flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as fh:
        return json.load(fh)


def load_benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        return json.load(fh)


def load_mix(name):
    """A traffic mix; ``base`` names a mix whose keys it overrides."""
    mix = load_json("traffic", name + ".json")
    if "base" in mix:
        merged = load_mix(mix["base"])
        merged.update({k: v for k, v in mix.items() if k != "base"})
        return merged
    return mix


def plugin(kind, name):
    """The module ``benchmarks/<kind>/<name>.py``."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    return importlib.import_module(f"benchmarks.{kind}.{name}")


def resolve(dotted):
    """``package.module:attribute`` of the program under test."""
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    mod, _, attr = dotted.partition(":")
    return getattr(importlib.import_module(mod), attr)


def find_cell(bench, name):
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise Refused(f"no workload {name!r} in BENCHMARK.json")


def metrics_of(bench, section, cell, reported=None):
    """The metrics of ``section`` that ``cell`` reports: those that list
    it under ``workloads``, and those without the key whose ``moves``
    (per-layer) is an end-to-end metric the cell reports."""
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in (reported or ()):
            out.append(m)
    return out


# -- the device ------------------------------------------------------------
def device_info(jax):
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(jax, chips, rehearse):
    """A measurement needs a TPU and the cell's number of chips; a
    rehearsal (CPU, tests) says so in every line it prints."""
    info = device_info(jax)
    if rehearse:
        if info["count"] < chips:
            raise Refused(f"rehearsal of a {chips}-chip cell needs "
                          f"{chips} devices, JAX found {info}")
        return info
    if info["platform"] != "tpu":
        raise Refused(f"needs a TPU, JAX found {info} "
                      "(--rehearse runs the tiny preset on the CPU)")
    if info["count"] < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found {info}")
    return info


def memory_peak_bytes(jax, chips):
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def peak_table(kind):
    table = load_json("peaks.json")
    if kind not in table:
        raise Refused(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# -- clocks, spans, counters ---------------------------------------------
clock = time.perf_counter


class Phases(dict):
    """Lengths of a run's consecutive phases (host clock): ``mark`` ends
    the phase that began at the last mark; ``skip`` leaves out what ran
    since (the training reference's process)."""

    def __init__(self, t0):
        super().__init__()
        self._t = t0

    def mark(self, name):
        now = clock()
        self[name] = now - self._t
        self._t = now
        return now

    def skip(self):
        self._t = clock()


class Spans:
    """Host spans of the harness's own calls into the program, kept in
    memory; with ``annotate`` they are also written into the profiler's
    trace, on the device trace's clock."""

    def __init__(self, annotate=False):
        self.rows = {}                 # name -> [(start, end)]
        self._annotate = None
        if annotate:
            import jax.profiler
            self._annotate = jax.profiler.TraceAnnotation

    @contextlib.contextmanager
    def span(self, name):
        ann = self._annotate("bench/" + name) if self._annotate else None
        if ann is not None:
            ann.__enter__()
        t0 = clock()
        try:
            yield
        finally:
            t1 = clock()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.rows.setdefault(name, []).append((t0, t1))


class CompileCounter:
    """Backend compilations (or fetches from the persistent cache) since
    ``reset``: inside the window there must be none."""

    def __init__(self):
        from jax import monitoring
        self.n = 0
        self.seconds = 0.0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += secs

    def reset(self):
        self.n, self.seconds = 0, 0.0


def percentile(values, q):
    """Linear-interpolated percentile (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


def summary(values):
    """Count, median and tail of a sample, for the earlier lines."""
    if not len(values):
        return {"n": 0}
    return {"n": len(values), "p50": percentile(values, 50),
            "p95": percentile(values, 95), "max": float(max(values))}


# -- limits of the correctness check -----------------------------------
#: every row a Check reported in this process: run.py closes its result
#: line and its standard error with them (what the driver's record keeps
#: of a run that is not correct)
COMPARED = []


class Check:
    """Each number compared, beside its limit; ``ok`` once all are in."""

    def __init__(self):
        self.rows = []

    def le(self, name, value, limit):
        value = float(value)
        good = value <= limit           # NaN compares false
        self.rows.append({"compared": name, "value": value,
                          "limit": limit, "ok": bool(good)})
        return good

    def true(self, name, good, detail=None):
        self.rows.append({"compared": name, "value": detail,
                          "limit": "must hold", "ok": bool(good)})
        return bool(good)

    @property
    def ok(self):
        return bool(self.rows) and all(r["ok"] for r in self.rows)

    def report(self):
        for r in self.rows:
            say(check=r)
        COMPARED.extend(self.rows)


# -- per-layer metrics -------------------------------------------------
def read_layer_metrics(bench, cell, reported, sources):
    """Run the reader of every per-layer metric the cell lists. A reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics_of(bench, "per_layer", cell, reported):
        spec = load_json("layer_metrics", m["name"] + ".json")
        reader = plugin("reducers", spec["reducer"])
        value = reader.read(sources, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
