"""From the profiler's trace to numbers: the one reduction every PR uses.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. What a TPU v5e trace holds (looked at by hand, PR 23):

- plane ``/device:TPU:<n>``, line ``XLA Modules``: one event for each
  execution of a compiled program, named ``jit_<function>(<hash>)``;
- same plane, line ``XLA Ops``: one event for each operation, named by
  its HLO text (``%decode_mlp_block.9 = bf16[...] custom-call(...)``).
  A ``while`` or ``conditional`` spans the operations of its body, so
  events nest and a plain sum counts time twice: ``self_times`` takes
  each event's duration less its children's;
- plane ``/host:CPU``, line ``python*`` (the main thread, named after
  the command): ``TraceAnnotation`` spans (the
  harness's are ``bench/<name>``) and JAX's own host events, on the
  same clock as the device's events (nanoseconds).

``Trace`` is the small form the reducers read; it also loads from and
saves to JSON, which is how the recorded trace of the tests is kept.
"""
import glob
import gzip
import json
import os
import re
import shutil

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
_SUFFIX = re.compile(r"\.\d+$")


def op_head(name):
    """``%decode_mlp_block.9 = bf16[...] custom-call(...)`` ->
    ``decode_mlp_block.9``: one operation of one program."""
    return name.split(" = ", 1)[0].strip().lstrip("%")


def op_key(name):
    """... -> ``decode_mlp_block``: a kernel's launches, whatever number
    the compiler gave the operation."""
    return _SUFFIX.sub("", op_head(name))


def union_ns(intervals):
    """Total length covered by (start, duration) intervals."""
    total, end = 0.0, None
    for s, d in sorted(intervals):
        e = s + d
        if end is None or s > end:
            total += d
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def merged(intervals):
    """Sorted, disjoint (start, end) pairs covering the intervals."""
    out = []
    for s, d in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], s + d)
        else:
            out.append([s, s + d])
    return out


def self_times(events):
    """[(name, start, self duration, is leaf)] of one line's events:
    duration less the direct children's (events nest, never cross)."""
    evs = sorted(events, key=lambda e: (e[0], -e[1]))
    out, stack = [], []          # stack of [end, index into out]
    for s, d, name in evs:
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            parent = out[stack[-1][1]]
            parent[2] -= d
            parent[3] = False
        out.append([name, s, d, True])
        stack.append([s + d, len(out) - 1])
    return [tuple(o) for o in out]


class Trace:
    """ops / modules: {device index: [(start_ns, dur_ns, name)]};
    host: [(start_ns, dur_ns, name)] of the python line."""

    def __init__(self, ops, modules, host):
        self.ops = {int(k): [tuple(e) for e in v] for k, v in ops.items()}
        self.modules = {int(k): [tuple(e) for e in v]
                        for k, v in modules.items()}
        self.host = [tuple(e) for e in host]
        self._self_times = {}        # device -> self_times(ops), made once
        if not any(self.ops.values()):
            raise ValueError("the trace holds no device operation")

    # -- loading ---------------------------------------------------------
    @classmethod
    def from_xplane(cls, path):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        ops, modules, host = {}, {}, []
        for plane in data.planes:
            m = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if m and line.name in ("XLA Ops", "XLA Modules"):
                    dst = ops if line.name == "XLA Ops" else modules
                    dst.setdefault(int(m.group(1)), []).extend(
                        (e.start_ns, e.duration_ns, e.name)
                        for e in line.events)
                elif (plane.name == "/host:CPU"
                      and line.name.startswith("python")):
                    # the line is named after the thread, and that after
                    # the command: "python", "python3", "python3.12"
                    host.extend((e.start_ns, e.duration_ns, e.name)
                                for e in line.events)
        if not ops:
            ops[0] = _cpu_ops(data)
        return cls(ops, modules, host)

    @classmethod
    def from_json(cls, path):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rt") as fh:
            d = json.load(fh)
        return cls(d["ops"], d["modules"], d["host"])

    def to_json(self, path):
        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "wt") as fh:
            json.dump({"ops": self.ops, "modules": self.modules,
                       "host": self.host}, fh)

    # -- the window ------------------------------------------------------
    def bounds_ns(self):
        evs = [e for v in self.ops.values() for e in v] + self.host
        return min(e[0] for e in evs), max(e[0] + e[1] for e in evs)

    def window_s(self):
        lo, hi = self.bounds_ns()
        return (hi - lo) / 1e9

    def busy_s(self):
        """Seconds in which an operation ran, averaged over devices."""
        per = [union_ns((s, d) for s, d, _ in v) for v in self.ops.values()]
        return sum(per) / len(per) / 1e9

    # -- sums ------------------------------------------------------------
    def module_runs(self, pattern, device=0):
        """Durations (s) of the executions of programs matching
        ``pattern`` on one device."""
        rx = re.compile(pattern)
        return [d / 1e9 for _, d, name in self.modules.get(device, ())
                if rx.search(name)]

    def self_times(self, device=0):
        """``self_times`` of one device's operations; several readers and
        the breakdown ask, the sort over ~10^5 events is made once."""
        if device not in self._self_times:
            self._self_times[device] = self_times(self.ops.get(device, ()))
        return self._self_times[device]

    def op_seconds(self, device=0, key=op_key):
        """{operation key: [launches, self seconds]} on one device."""
        out = {}
        for name, _, d, _ in self.self_times(device):
            row = out.setdefault(key(name), [0, 0.0])
            row[0] += 1
            row[1] += d / 1e9
        return out

    def kernel(self, name, device=0):
        """(launches, seconds) of the operation keyed ``name``."""
        n, s = self.op_seconds(device).get(name, (0, 0.0))
        return n, s

    def exposed_collective_share(self, device=0):
        """Share of the window in which a collective runs on the device
        and no other operation does (containers such as ``while`` are
        not operations of their own)."""
        events = self.ops.get(device, ())
        coll = merged((s, d) for s, d, name in events
                      if COLLECTIVE.match(op_key(name)))
        comp = merged((s, d) for name, s, d, leaf in self.self_times(device)
                      if leaf and not COLLECTIVE.match(op_key(name)))
        exposed, j = 0.0, 0
        for s, e in coll:
            cur = s
            while j < len(comp) and comp[j][1] <= cur:
                j += 1
            k = j
            while k < len(comp) and comp[k][0] < e:
                exposed += max(0.0, comp[k][0] - cur)
                cur = max(cur, comp[k][1])
                k += 1
            exposed += max(0.0, e - cur)
        lo, hi = self.bounds_ns()
        return exposed / (hi - lo)

    # -- idle ------------------------------------------------------------
    def idle_gaps(self, device=0):
        """{what the host was doing: seconds the device sat idle under
        it}: each gap between device operations goes to the innermost
        host event that holds the gap's middle ("(no host span)" where
        none does)."""
        lo, hi = self.bounds_ns()
        busy = merged((s, d) for s, d, _ in self.ops.get(device, ()))
        gaps, cur = [], lo
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if hi > cur:
            gaps.append((cur, hi))
        host = sorted(self.host, key=lambda e: (e[0], -e[1]))
        out, stack, i = {}, [], 0
        for s, e in gaps:                 # gaps ascend, host events nest
            mid = (s + e) / 2
            while i < len(host) and host[i][0] <= mid:
                stack.append(host[i])
                i += 1
            while stack and stack[-1][0] + stack[-1][1] <= mid:
                stack.pop()
            # an event that ended is dropped only from the top: look down
            # for the innermost one that still holds the middle
            name = next((h[2] for h in reversed(stack)
                         if h[0] + h[1] > mid), "(no host span)")
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
        return out

    def breakdown(self, top=10):
        ops = sorted(((k, v[1]) for k, v in
                      self.op_seconds(key=op_head).items()),
                     key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_gaps().items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _cpu_ops(data):
    """A rehearsal on the CPU has no device plane: its operations are
    the host-line events that carry an ``hlo_op`` (never a measurement,
    only so that the traced path runs end to end in the tests)."""
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if line.name.startswith("python"):
                continue
            out.extend((e.start_ns, e.duration_ns, e.name)
                       for e in line.events
                       if any(k == "hlo_op" for k, _ in e.stats))
    return out


def share_pct(least_s, measured_s, what):
    """``least_s / measured_s`` in percent. Over 100 the count of
    operations or bytes is too high, or the time leaves out part of the
    work: an error to find, never a number to clip."""
    pct = 100.0 * least_s / measured_s
    if pct > 100.0:
        raise ValueError(
            f"{what}: {pct:.1f}% of its roofline (least {least_s:.6f} s, "
            f"measured {measured_s:.6f} s): the cost model or the timing "
            "is wrong")
    return pct


class Profiler:
    """Starts and stops the JAX profiler on a fixed directory inside
    the checkout, with Python's own tracer off (it slows the host)."""

    def __init__(self, directory):
        self.dir = directory
        shutil.rmtree(directory, ignore_errors=True)

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def load(self):
        files = glob.glob(os.path.join(self.dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace to {self.dir}")
        try:
            return Trace.from_xplane(sorted(files)[-1])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
