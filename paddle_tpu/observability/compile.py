"""Compile telemetry + host-vs-device gap detection for jitted programs.

The training half of the framework was dark: the llama bench's 3.2 s
host-side h2d residual (vs ~200 ms of device compute) had to be
diagnosed by hand with XPlane, and MFU was hand-derived from a flops
formula. This module captures, for every jitted program routed through
it:

- **compile wall time + retrace counts** — an AOT ``lower().compile()``
  wrapped in a timer, keyed by the program's abstract input signature,
  so a shape/dtype leak shows up as a counted (and, once armed, warned)
  recompile instead of a silent seconds-long stall;
- **``cost_analysis()``** FLOPs / bytes-accessed per execution — the
  numerator of an *automatic* MFU (no hand-derived flops formula);
- **``memory_analysis()``** HBM breakdown (arguments / outputs / temps
  / generated code) plus a live-HBM gauge where the backend exposes
  ``memory_stats()``.

It also hosts the :class:`HostGapDetector`: per-step phase timings
(stage/h2d, compiled dispatch, host sync) are compared and a
flight-recorder-style dump fires when host-side time dwarfs the time
actually spent waiting on the device — the exact llama-residual
failure mode, detected automatically this time.
"""
from __future__ import annotations

import os
import time
import warnings
from typing import Dict, Optional

__all__ = ["CompileWatcher", "HostGapDetector", "UnknownDevicePeak",
           "device_peak_flops", "device_peak_hbm_bw", "live_hbm_bytes"]

# Per-chip peaks keyed by ``jax.devices()[0].device_kind``: dense-matmul
# FLOP/s (bf16) and HBM bandwidth (bytes/s), each with its source. The
# ONE peak table — bench.py's formula MFU, the cost-analysis MFU and
# every roofline row delegate here, so no two denominators in a capture
# can disagree. A device that is not in the table is an error, not a
# default: a fraction of an assumed peak is not a measurement.
_PEAKS = {
    "TPU v5 lite": {
        "flops": 197e12, "hbm_bw": 819e9,
        "source": "Google Cloud documentation, 'TPU v5e': 197 TFLOP/s "
                  "bf16, 819 GB/s HBM per chip"},
}


class UnknownDevicePeak(LookupError):
    """No peak on record for this ``device_kind`` (and no override)."""


def _device_peak(field, env_var):
    """``(value, source)``: the ``env_var`` override when set (exact
    hardware known to the operator), else the table row of the first
    device's ``device_kind``. Raises :class:`UnknownDevicePeak` for a
    device that is not in the table. The source string rides into
    ``metrics()`` and the roofline reports."""
    env = os.environ.get(env_var)
    if env:
        return float(env), "env"
    import jax
    kind = jax.devices()[0].device_kind
    row = _PEAKS.get(kind)
    if row is None:
        raise UnknownDevicePeak(
            f"no peak on record for device_kind {kind!r} (known: "
            f"{sorted(_PEAKS)}); add it to observability/compile.py "
            f"with its source, or set {env_var}")
    return row[field], f"{kind}: {row['source']}"


def device_peak_flops():
    """Peak bf16 FLOP/s per chip: ``(value, source)``; the
    ``PADDLE_TPU_PEAK_FLOPS`` override, else the ``device_kind`` table.
    Raises :class:`UnknownDevicePeak` for an unknown device."""
    return _device_peak("flops", "PADDLE_TPU_PEAK_FLOPS")


def device_peak_hbm_bw():
    """Peak HBM bandwidth per chip in bytes/s: ``(value, source)``.
    Same contract as :func:`device_peak_flops` with the
    ``PADDLE_TPU_PEAK_HBM_BW`` override."""
    return _device_peak("hbm_bw", "PADDLE_TPU_PEAK_HBM_BW")


def live_hbm_bytes(device=None) -> Optional[int]:
    """Bytes currently allocated on ``device`` via PjRt
    ``memory_stats()``; None where the backend does not report (CPU)."""
    try:
        if device is None:
            import jax
            device = jax.devices()[0]
        stats = device.memory_stats()
        if stats:
            return int(stats.get("bytes_in_use", 0)) or None
    except Exception:  # noqa: BLE001 — telemetry must never raise
        pass
    return None


def _cost_dict(compiled) -> Optional[Dict]:
    """Flatten ``compiled.cost_analysis()`` to {flops, bytes_accessed}:
    only the well-known keys are kept (the full dict carries
    per-operand entries with unstable names)."""
    ca = compiled.cost_analysis()
    if not ca:
        return None
    out = {}
    for key in ("flops", "bytes accessed"):
        v = ca.get(key)
        if v is not None:
            out[key.replace(" ", "_")] = float(v)
    return out or None


def _memory_dict(compiled) -> Optional[Dict]:
    """``compiled.memory_analysis()`` → HBM breakdown in bytes."""
    try:
        ma = compiled.memory_analysis()
    except Exception:  # noqa: BLE001
        return None
    if ma is None:
        return None
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
    out = {}
    for f in fields:
        v = getattr(ma, f, None)
        if v is not None:
            out[f.replace("_size_in_bytes", "_bytes")] = int(v)
    if not out:
        return None
    # aliased bytes are donated inputs — they overlap outputs, so the
    # peak estimate counts them once
    out["total_bytes"] = (out.get("argument_bytes", 0)
                          + out.get("output_bytes", 0)
                          + out.get("temp_bytes", 0)
                          + out.get("generated_code_bytes", 0)
                          - out.get("alias_bytes", 0))
    return out


class CompileWatcher:
    """Per-program compile telemetry with a retrace watchdog.

    ``compile(name, jitted, *args)`` runs the AOT ``lower().compile()``
    path, times it, counts it, and extracts cost/memory analysis. Once
    :meth:`arm` is called (the warmup→reset idiom the serving watchdog
    established), any further compile of an armed program warns — a
    steady-state train loop must run ONE program.
    """

    def __init__(self, registry=None, timeline=None, warn: bool = True):
        self.registry = registry
        self.timeline = timeline
        self.warn = warn
        self.programs: Dict[str, Dict] = {}
        self.retrace_events: list = []
        self._armed = False

    def compile(self, name: str, jitted, *args, **kwargs):
        """AOT-compile ``jitted`` for ``args`` and record the event;
        returns the compiled executable."""
        t0 = time.perf_counter()
        compiled = jitted.lower(*args, **kwargs).compile()
        wall_s = time.perf_counter() - t0
        rec = self.programs.get(name)
        if rec is None:
            rec = self.programs[name] = {
                "count": 0, "wall_s_total": 0.0, "wall_s_last": 0.0,
                "cost": None, "memory": None}
        rec["count"] += 1
        rec["wall_s_total"] += wall_s
        rec["wall_s_last"] = wall_s
        # cost/memory reflect the LAST compile: a retrace changed the
        # program, so the stale analysis would misprice MFU
        rec["cost"] = _cost_dict(compiled)
        rec["memory"] = _memory_dict(compiled)
        if self.registry is not None:
            self.registry.histogram("compile_ms").observe(wall_s * 1e3)
        if self.timeline is not None:
            self.timeline.record("compile", program=name,
                                 dur_ms=wall_s * 1e3,
                                 count=rec["count"])
        if self._armed:
            finding = {"program": name, "traces": 1,
                       "compile_ms": round(wall_s * 1e3, 3)}
            self.retrace_events.append(finding)
            if self.warn:
                warnings.warn(
                    f"compile of {name!r} after warmup "
                    f"({wall_s * 1e3:.1f} ms) — a steady-state train "
                    "loop must reuse one compiled program; a shape or "
                    "dtype leak in the batch stream retraces every "
                    "occurrence", RuntimeWarning, stacklevel=3)
        return compiled

    def arm(self):
        """Declare warmup complete: further compiles warn + count.
        Re-arming restarts the retrace window — a fixed leak's old
        warnings must not haunt the next window's snapshot (the
        compile_ms histogram resets alongside, via reset_window)."""
        self._armed = True
        self.retrace_events = []

    @property
    def armed(self) -> bool:
        return self._armed

    @property
    def total_compiles(self) -> int:
        return sum(r["count"] for r in self.programs.values())

    def flops_per_step(self, name: str) -> Optional[float]:
        rec = self.programs.get(name)
        if rec and rec.get("cost"):
            return rec["cost"].get("flops")
        return None

    def mfu(self, name: str, steps: int, wall_s: float) -> Optional[Dict]:
        """Cost-analysis-derived MFU over a measured window.

        ``cost_analysis()`` reports PER-DEVICE FLOPs for an SPMD-
        partitioned program (verified: a matmul sharded 4 ways reports
        whole/4), so per-device flops over the per-chip peak IS the
        per-chip MFU — no device-count factor on either side."""
        flops = self.flops_per_step(name)
        if not flops or steps <= 0 or wall_s <= 0:
            return None
        try:
            peak, source = device_peak_flops()
        except UnknownDevicePeak:
            return None      # no peak, no utilization: not measured
        return {"mfu": round(flops * steps / (wall_s * peak), 4),
                "flops_per_step_per_device": flops,
                "peak_flops_per_chip": peak, "peak_source": source}

    def snapshot(self) -> Dict:
        progs = {}
        for name, r in self.programs.items():
            progs[name] = {
                "count": r["count"],
                "wall_ms_total": round(r["wall_s_total"] * 1e3, 3),
                "wall_ms_last": round(r["wall_s_last"] * 1e3, 3),
                **({"cost": r["cost"]} if r["cost"] else {}),
                **({"memory": r["memory"]} if r["memory"] else {}),
            }
        return {"count": self.total_compiles,
                "retraces_after_warmup": len(self.retrace_events),
                "programs": progs}


class HostGapDetector:
    """Detect steps where host-side time dwarfs device-wait time.

    Per step the trainer hands over its phase split: ``stage_ms``
    (batch h2d staging), ``dispatch_ms`` (the compiled call returning
    — async dispatch makes this pure host work) and ``sync_ms`` (the
    block-until-ready wait, i.e. the time the device was actually the
    bottleneck). When ``stage + dispatch > factor × sync`` and the step
    is big enough to matter, the host — not the device — owns the step,
    and a flight-recorder-style dump is emitted through the provided
    callback (bounded count; detection keeps counting after the cap).
    """

    def __init__(self, factor: float = 4.0, min_wall_ms: float = 50.0,
                 max_dumps: int = 4):
        self.factor = float(factor)
        self.min_wall_ms = float(min_wall_ms)
        self.max_dumps = int(max_dumps)
        self.findings: list = []
        self.dumps = 0

    def reset(self):
        """Restart the detection window (the warmup→reset idiom):
        findings clear and the dump budget refills — warmup's first-
        staging gap must not spend the measured window's budget."""
        self.findings = []
        self.dumps = 0

    def observe(self, step: int, stage_ms: float, dispatch_ms: float,
                sync_ms: float) -> Optional[Dict]:
        host_ms = stage_ms + dispatch_ms
        wall_ms = host_ms + sync_ms
        if wall_ms < self.min_wall_ms:
            return None
        if host_ms <= self.factor * max(sync_ms, 1e-3):
            return None
        finding = {"step": step, "host_ms": round(host_ms, 3),
                   "stage_ms": round(stage_ms, 3),
                   "dispatch_ms": round(dispatch_ms, 3),
                   "device_wait_ms": round(sync_ms, 3),
                   "host_over_device": round(
                       host_ms / max(sync_ms, 1e-3), 1)}
        self.findings.append(finding)
        return finding

    def should_dump(self) -> bool:
        if self.dumps >= self.max_dumps:
            return False
        self.dumps += 1
        return True
