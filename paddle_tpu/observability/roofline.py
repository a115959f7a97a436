"""Kernel roofline observatory: modeled bytes/FLOPs per Pallas launch.

Five PRs of megakernel work were justified by raw microsecond A/Bs;
this module says *how close to the hardware* each kernel runs and
*why* a variant wins, in the units the fusion literature reports:
bytes moved, FLOPs, arithmetic intensity, and % of the roofline.

The two model halves live next to what they price:

- **bytes** — :func:`paddle_tpu.analysis.kernel_rules.modeled_launch_bytes`
  walks the SAME captured index maps the ``VMEM_OVERCOMMIT`` window
  model walks, but sums revisit-elided block fetches over the full
  grid instead of maxing windows over one step;
- **FLOPs** — :data:`paddle_tpu.analysis.kernel_catalog.FLOP_FORMULAS`
  registers one formula per audited launch name, with a
  ``FLOP_FORMULA_GAP`` finding when a kernel lacks one.

This module pairs them with the per-chip peaks
(:func:`~paddle_tpu.observability.compile.device_peak_flops` /
:func:`~paddle_tpu.observability.compile.device_peak_hbm_bw`, shared
env > generation > labelled-default contract) to classify each launch
memory- vs compute-bound and — given a measured time — compute
achieved-bandwidth / achieved-FLOPs fractions and the
time-at-peak-bandwidth lower bound the trace tooling prints.

Everything here is host-side arithmetic on captured
:class:`~paddle_tpu.ops.pallas._util.KernelLaunchSpec` geometry: no
device work, no syncs, usable under ``jax.eval_shape``.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

from .compile import (UnknownDevicePeak, device_peak_flops,
                      device_peak_hbm_bw)

__all__ = ["kernel_cost", "roofline_point", "capture_kernel_costs",
           "decode_step_bytes", "decode_roofline",
           "roofline_chrome_events"]


def peak_snapshot() -> Dict:
    """The labelled peak pair every roofline row prices against. On a
    device with no peak on record (the CPU under test) both peaks are
    ``None`` and the source says so: the modeled bytes and FLOPs still
    report, every peak-derived field stays ``None``."""
    def one(fn):
        try:
            return fn()
        except UnknownDevicePeak as e:
            return None, f"unknown: {e}"
    flops, flops_src = one(device_peak_flops)
    bw, bw_src = one(device_peak_hbm_bw)
    return {"peak_flops": flops, "peak_hbm_bw": bw,
            "peak_source": {"flops": flops_src, "hbm_bw": bw_src}}


def _sig4(x: float) -> float:
    # achieved fractions span ~1e-5 (interpret/CPU steps) to ~1.0 (on
    # chip): significant figures, not decimal places — round(2e-5, 4)
    # would report a real measurement as 0.0
    return float(f"{x:.4g}")


def roofline_point(bytes_modeled: Optional[float],
                   flops_modeled: Optional[float],
                   time_us: Optional[float] = None,
                   peaks: Optional[Dict] = None) -> Dict:
    """Classify one (bytes, FLOPs[, measured time]) point against the
    device roofline.

    Returns ``intensity`` (FLOPs/byte), ``bound`` (``"memory"`` /
    ``"compute"`` by the ridge point ``peak_flops / peak_hbm_bw``),
    the bound-side lower-bound execution time ``time_at_roofline_us``
    and — when a measured ``time_us`` is given — ``achieved_bw_frac``,
    ``achieved_flops_frac`` and ``roofline_frac`` (lower bound over
    measured: 1.0 means the launch runs AT the roofline). Fields whose
    inputs are missing are ``None``, never silently zero.
    """
    peaks = peaks or peak_snapshot()
    peak_flops = peaks["peak_flops"]
    peak_bw = peaks["peak_hbm_bw"]
    out: Dict = {"intensity": None, "bound": None,
                 "time_at_roofline_us": None,
                 "achieved_bw_frac": None, "achieved_flops_frac": None,
                 "roofline_frac": None,
                 "peak_source": peaks["peak_source"]}
    has_bytes = bytes_modeled is not None and bytes_modeled > 0
    has_flops = flops_modeled is not None and flops_modeled > 0
    if has_bytes and has_flops:
        intensity = flops_modeled / bytes_modeled
        out["intensity"] = round(intensity, 3)
        if peak_flops and peak_bw:
            ridge = peak_flops / peak_bw
            out["bound"] = "memory" if intensity < ridge else "compute"
    # a missing peak is a missing input: its side of the roofline and
    # its achieved fraction stay None
    has_bytes = has_bytes and bool(peak_bw)
    has_flops = has_flops and bool(peak_flops)
    t_bw = bytes_modeled / peak_bw if has_bytes else None
    t_fl = flops_modeled / peak_flops if has_flops else None
    t_roof = max(t for t in (t_bw, t_fl) if t is not None) \
        if (t_bw is not None or t_fl is not None) else None
    if t_roof is not None:
        out["time_at_roofline_us"] = round(t_roof * 1e6, 3)
    if time_us is not None and time_us > 0:
        t_s = time_us * 1e-6
        if has_bytes:
            out["achieved_bw_frac"] = _sig4(
                bytes_modeled / t_s / peak_bw)
        if has_flops:
            out["achieved_flops_frac"] = _sig4(
                flops_modeled / t_s / peak_flops)
        if t_roof is not None:
            out["roofline_frac"] = _sig4(t_roof / t_s)
    return out


def kernel_cost(spec, time_us: Optional[float] = None,
                memo: Optional[Dict] = None,
                peaks: Optional[Dict] = None) -> Dict:
    """One captured launch -> its full roofline row: modeled bytes
    (read/written split), modeled FLOPs (``None`` + a
    ``flops_model: "missing"`` marker when the kernel has no
    registered formula — the gap is also a gate finding), and the
    :func:`roofline_point` classification."""
    from ..analysis.kernel_catalog import modeled_flops
    from ..analysis.kernel_rules import modeled_launch_bytes

    bm = modeled_launch_bytes(spec, memo)
    flops = modeled_flops(spec)
    row = {"kernel": spec.name, "grid": list(spec.grid),
           "bytes_modeled": int(bm["total_bytes"]),
           "read_bytes": int(bm["read_bytes"]),
           "written_bytes": int(bm["written_bytes"]),
           "flops_modeled": flops,
           "flops_model": "formula" if flops is not None else "missing"}
    row.update(roofline_point(row["bytes_modeled"], flops,
                              time_us=time_us, peaks=peaks))
    return row


def capture_kernel_costs(fn: Callable, *args,
                         times_us: Optional[Dict[str, float]] = None
                         ) -> List[Dict]:
    """Trace ``fn(*args)`` under launch capture (``jax.eval_shape`` —
    abstract, no compute) and price every captured launch. ``times_us``
    optionally maps kernel name -> measured microseconds to fill the
    achieved fractions."""
    import jax

    from ..ops.pallas._util import capture_kernel_launches

    with capture_kernel_launches() as specs:
        jax.eval_shape(fn, *args)
    peaks = peak_snapshot()
    times_us = times_us or {}
    return [kernel_cost(s, time_us=times_us.get(s.name), peaks=peaks)
            for s in specs]


# -- per-decode-variant step model (engine metrics / trace_summary) -----


def decode_step_bytes(B: int, D: int, H: int, KV: int, hd: int, F: int,
                      BS: int, MB: int, act_itemsize: float = 2,
                      weight_itemsize: float = 2,
                      pool_itemsize: float = 2) -> Dict[str, int]:
    """Closed-form modeled HBM bytes for ONE decode step of each
    dispatch arm, at full occupancy (``B`` live rows, full ``MB``-page
    block tables — the same max-traffic convention as the kernel-level
    model). The arms differ exactly where the transition-count model
    says they differ:

    - ``pallas_fused`` (``paged_attention_decode`` + ``decode_mlp_block``
      launches): every weight read once, one residual round-trip
      between the stages;
    - ``unfused`` (reference composition): every weight read once plus
      the materialised intermediates (q/k/v/attn-out activations and
      the (B, F) gate/up/swish tensors) round-tripping through HBM.

    Weight scales / sin-cos rows / block tables are small and
    deliberately ignored. Returns bytes per variant name.
    """
    Hhd, KVhd = H * hd, KV * hd
    w_attn = (D * Hhd + 2 * D * KVhd + Hhd * D) * weight_itemsize
    w_mlp = 3 * D * F * weight_itemsize
    kv = 2 * B * MB * BS * KVhd * pool_itemsize
    x = B * D * act_itemsize
    return {
        # attn: x in, x' out; mlp: x' in, y out (new k/v rows out are
        # ~B*KVhd, ignored: << kv)
        "pallas_fused": int(w_attn + w_mlp + kv + 4 * x),
        # norms + q/k/v/o + attn-out + mlp in/out: ~10 activation
        # round-trips of (B, D) + gate/up/swish (B, F) materialised
        "unfused": int(w_attn + w_mlp + kv + 10 * x
                       + 6 * B * F * act_itemsize),
    }


def decode_roofline(step_bytes: Dict[str, int],
                    measured_us: Optional[Dict[str, float]] = None,
                    peaks: Optional[Dict] = None) -> Dict:
    """The engine-metrics roofline sub-dict: per-variant modeled
    bytes/step and the bandwidth-bound lower-bound step time, plus
    achieved-bandwidth fraction where a measured mean step time is
    known (``measured_us``: variant -> microseconds)."""
    peaks = peaks or peak_snapshot()
    peak_bw = peaks["peak_hbm_bw"]
    measured_us = measured_us or {}
    variants = {}
    for name, nbytes in step_bytes.items():
        row = {"bytes_per_step": int(nbytes),
               "step_us_at_peak_bw": None, "achieved_bw_frac": None}
        if peak_bw:
            t_bw_us = nbytes / peak_bw * 1e6
            row["step_us_at_peak_bw"] = round(t_bw_us, 3)
            t = measured_us.get(name)
            if t:
                row["achieved_bw_frac"] = _sig4(t_bw_us / t)
        variants[name] = row
    return {"variants": variants, "peak_hbm_bw": peak_bw,
            "peak_source": peaks["peak_source"]}


def roofline_chrome_events(report: Dict, t_us: float = 0.0) -> List[Dict]:
    """Render a :func:`decode_roofline` report (or any
    ``{"variants": {name: {...}}}`` mapping) as chrome-trace counter
    events — one ``roofline:<name>`` annotation track per arm carrying
    the modeled bytes/step, so the Perfetto view of a serving trace
    shows the bandwidth-bound floor next to the measured rows."""
    events = []
    for name, row in sorted(report.get("variants", {}).items()):
        events.append({"name": f"roofline:{name}", "ph": "C",
                       "ts": t_us,
                       "args": {"bytes_per_step":
                                row.get("bytes_per_step", 0)}})
    return events
