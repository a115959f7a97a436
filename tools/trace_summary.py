#!/usr/bin/env python
"""Summarize an observability-timeline JSONL.

Reads the structured per-phase JSONL the observability layer emits next
to each BENCH capture and prints, without needing a browser:

serving mode (ServingEngine.write_timeline /
DisaggregatedEngine.write_timeline):
- per-phase breakdown: count / total / mean / max wall time per event
  name (decode_step, prefill_chunk, ...),
- the top-N slowest timed steps (the retrace or allocator hiccup is
  almost always one of these),
- per-request latency distributions (queue wait, TTFT, TPOT, e2e)
  with p50/p95/p99 computed from the request records,
- a scheduler section when the SLO-admission machinery left traces:
  per-priority-class queue-wait percentiles (request records carry
  their class), preemption / resume / deadline-expiry counts, and the
  KV-handoff breakdown (count, bytes, extract/put/insert phase means)
  for disaggregated timelines.

train mode (Trainer.write_timeline, ``--mode train`` or auto-detected
from the meta header):
- per-phase breakdown of the step: stage (batch h2d), dispatch
  (compiled call), sync (device wait) totals/means,
- host-vs-device gap per step (host = stage + dispatch vs device =
  sync) with the worst offenders listed — the llama h2d-residual
  diagnosis, from a file,
- top-N slowest steps and every compile event (program, wall time).

Usage:  python tools/trace_summary.py TIMELINE.jsonl
            [--mode auto|serving|train] [--top 10] [--json]
"""
import argparse
import json
import sys


def _percentile(sorted_vals, q):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1,
            max(0, int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[i]


class TimelineError(Exception):
    """A timeline file the summary cannot work from — reported as ONE
    line on stderr with a nonzero exit, never a traceback (the CLI is
    scripted after bench runs; a stack trace in the log helps no
    one)."""


def load(path):
    meta, events, requests = {}, [], []
    try:
        f = open(path)
    except OSError as e:
        raise TimelineError(
            f"cannot read timeline file {path!r}: "
            f"{e.strerror or e}")
    malformed = 0
    parsed = 0
    with f:
        for ln, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                malformed += 1
                print(f"warning: skipping malformed line {ln}",
                      file=sys.stderr)
                continue
            kind = rec.get("kind")
            if kind == "meta":
                meta = rec
                parsed += 1
            elif kind == "event":
                events.append(rec)
                parsed += 1
            elif kind == "request":
                requests.append(rec)
                parsed += 1
    if parsed == 0:
        if malformed:
            raise TimelineError(
                f"{path}: no parseable timeline records "
                f"({malformed} malformed line(s) — truncated JSONL?)")
        raise TimelineError(
            f"{path}: empty timeline file (no meta/event/request "
            "records)")
    return meta, events, requests


def summarize(meta, events, requests, top=10):
    out = {"meta": {k: meta.get(k) for k in
                    ("schema", "events", "dropped", "capacity",
                     "num_blocks", "block_size") if k in meta}}

    phases = {}
    for ev in events:
        d = ev.get("dur_ms")
        if d is None:
            continue
        p = phases.setdefault(ev["name"], {"count": 0, "total_ms": 0.0,
                                           "max_ms": 0.0})
        p["count"] += 1
        p["total_ms"] += d
        p["max_ms"] = max(p["max_ms"], d)
    for p in phases.values():
        p["mean_ms"] = round(p["total_ms"] / p["count"], 3)
        p["total_ms"] = round(p["total_ms"], 3)
        p["max_ms"] = round(p["max_ms"], 3)
    out["phases"] = phases

    timed = [ev for ev in events if ev.get("dur_ms") is not None]
    timed.sort(key=lambda e: -e["dur_ms"])
    out["slowest_steps"] = timed[:top]

    lat = {}
    # warmup-flagged records (in flight across reset_metrics) are
    # excluded, matching the engine's own histogram exclusion
    live = [r for r in requests if not r.get("warmup")]
    for key in ("queue_wait_ms", "ttft_ms", "tpot_ms", "e2e_ms"):
        vals = [r[key] for r in live if r.get(key) is not None]
        if vals:
            lat[key] = _dist(vals)
    out["request_latency"] = lat
    out["requests"] = len(requests)

    sched = summarize_scheduler(events, live)
    if sched is not None:
        out["scheduler"] = sched
    rt = summarize_routing(events)
    if rt is not None:
        out["routing"] = rt
    pre = summarize_prefill(events)
    if pre is not None:
        out["prefill"] = pre
    dec = summarize_decode(events, meta)
    if dec is not None:
        out["decode"] = dec
    return out


def summarize_decode(events, meta=None):
    """The decode section: per-variant step attribution from the
    ``decode_variant`` field the engines stamp on each decode_step
    event ("pallas_fused" = the paged-attention and MLP launches are
    both Pallas kernels, "unfused" = the XLA compositions) — so a
    capture says WHICH decode arm its steps ran, mirroring the prefill
    ``variant`` attribution above. Returns None when no decode_step
    event carries the stamp (older timelines keep their summary
    shape)."""
    steps = [ev for ev in events if ev.get("name") == "decode_step"
             and ev.get("decode_variant") is not None]
    if not steps:
        return None
    per = {}
    for ev in steps:
        v = per.setdefault(str(ev["decode_variant"]), {
            "count": 0, "total_ms": 0.0, "max_ms": 0.0})
        v["count"] += 1
        d = ev.get("dur_ms") or 0.0
        v["total_ms"] += d
        v["max_ms"] = max(v["max_ms"], d)
    for v in per.values():
        v["mean_ms"] = round(v["total_ms"] / v["count"], 3)
        v["total_ms"] = round(v["total_ms"], 3)
        v["max_ms"] = round(v["max_ms"], 3)
    # roofline attribution (r21): the meta header carries the engine's
    # per-arm modeled bytes/step and the bandwidth-bound step-time
    # floor — pair each measured arm with its floor so the summary
    # prints "% of roofline", not just raw microseconds
    roof = (meta or {}).get("roofline") or {}
    rvars = roof.get("variants") or {}
    for name, v in per.items():
        r = rvars.get(name)
        if not r:
            continue
        v["bytes_per_step_modeled"] = r.get("bytes_per_step")
        v["step_us_at_peak_bw"] = r.get("step_us_at_peak_bw")
        floor_us = r.get("step_us_at_peak_bw")
        mean_us = v["mean_ms"] * 1e3
        if floor_us and mean_us > 0:
            v["roofline_frac"] = float(f"{floor_us / mean_us:.4g}")
    out = {"variants": per}
    if rvars:
        out["peak_hbm_bw"] = roof.get("peak_hbm_bw")
        out["peak_source"] = roof.get("peak_source")
    return out


def summarize_prefill(events):
    """The prefill section (r17): per-bucket chunk timings, ragged
    occupancy (valid vs bucket-padded tokens fed to the chunks), and
    fused-vs-ref variant attribution from the ``variant`` field the
    engines stamp on each prefill_chunk event. Returns None when the
    timeline has no bucketed prefill chunks (train mode / decode-only
    windows keep their old summary shape)."""
    chunks = [ev for ev in events if ev.get("name") == "prefill_chunk"
              and ev.get("bucket") is not None]
    if not chunks:
        return None
    per = {}
    for ev in chunks:
        b = per.setdefault(int(ev["bucket"]), {
            "count": 0, "total_ms": 0.0, "max_ms": 0.0,
            "valid_tokens": 0, "pad_tokens": 0})
        b["count"] += 1
        d = ev.get("dur_ms") or 0.0
        b["total_ms"] += d
        b["max_ms"] = max(b["max_ms"], d)
        n = int(ev.get("n") or 0)
        b["valid_tokens"] += n
        b["pad_tokens"] += max(int(ev["bucket"]) - n, 0)
    for b in per.values():
        b["mean_ms"] = round(b["total_ms"] / b["count"], 3)
        b["total_ms"] = round(b["total_ms"], 3)
        b["max_ms"] = round(b["max_ms"], 3)
        fed = b["valid_tokens"] + b["pad_tokens"]
        b["occupancy"] = round(b["valid_tokens"] / fed, 4) if fed \
            else None
    variants = {}
    for ev in chunks:
        v = ev.get("variant") or "unknown"
        variants[v] = variants.get(v, 0) + 1
    tot_valid = sum(b["valid_tokens"] for b in per.values())
    tot_pad = sum(b["pad_tokens"] for b in per.values())
    fed = tot_valid + tot_pad
    return {"per_bucket": {str(k): v for k, v in sorted(per.items())},
            "occupancy": round(tot_valid / fed, 4) if fed else None,
            "variants": variants}


def _dist(vals):
    vals = sorted(vals)
    return {"count": len(vals),
            "mean": round(sum(vals) / len(vals), 3),
            "p50": round(_percentile(vals, 0.50), 3),
            "p95": round(_percentile(vals, 0.95), 3),
            "p99": round(_percentile(vals, 0.99), 3),
            "max": round(vals[-1], 3)}


def summarize_scheduler(events, requests):
    """The SLO-admission section: per-priority-class queue-wait
    percentiles from the request records, preemption/resume/expiry
    counts from the timeline, and the KV-handoff phase breakdown
    (disaggregated engines). Returns None when the timeline carries no
    scheduler traces at all — plain FIFO timelines keep their old
    summary shape."""
    counts = {name: sum(1 for ev in events if ev.get("name") == name)
              for name in ("preempt", "resume", "expired", "handoff")}
    classes = sorted({r.get("priority") for r in requests
                      if r.get("priority") is not None})
    multi_class = len(classes) > 1
    if not any(counts.values()) and not multi_class:
        return None
    out = {"preemptions": counts["preempt"],
           "resumes": counts["resume"],
           "deadline_expired": counts["expired"]}
    per = {}
    for cls in classes:
        waits = [r["queue_wait_ms"] for r in requests
                 if r.get("priority") == cls
                 and r.get("queue_wait_ms") is not None]
        if waits:
            per[str(cls)] = _dist(waits)
    if per:
        out["per_class_queue_wait_ms"] = per
    hand = [ev for ev in events if ev.get("name") == "handoff"]
    if hand:
        h = {"count": len(hand),
             "bytes_total": sum(ev.get("bytes", 0) for ev in hand),
             "pages_total": sum(ev.get("pages", 0) for ev in hand),
             "handoff_ms": _dist([ev["dur_ms"] for ev in hand
                                  if ev.get("dur_ms") is not None])}
        for phase in ("extract_ms", "put_ms", "insert_ms"):
            vals = [ev[phase] for ev in hand if ev.get(phase) is not None]
            if vals:
                h[phase + "_mean"] = round(sum(vals) / len(vals), 3)
        out["handoff"] = h
    return out


def summarize_routing(events):
    """The fleet routing section: warm/cold/diverted counts, warm-hit
    ratio, and each replica's share of the routed requests. Returns
    None when the timeline carries no ``route`` events — single-engine
    timelines keep their old summary shape."""
    routes = [ev for ev in events if ev.get("name") == "route"]
    if not routes:
        return None
    per = {}
    warm = diverted = 0
    for ev in routes:
        rep = str(ev.get("replica"))
        d = per.setdefault(rep, {"routed": 0, "warm": 0, "diverted": 0})
        d["routed"] += 1
        if ev.get("matched_tokens", 0):
            d["warm"] += 1
            warm += 1
        if ev.get("diverted"):
            d["diverted"] += 1
            diverted += 1
    n = len(routes)
    for d in per.values():
        d["share"] = round(d["routed"] / n, 4)
    return {"requests": n, "warm": warm, "cold": n - warm,
            "diverted": diverted,
            "warm_hit_ratio": round(warm / n, 4),
            "per_replica": {k: per[k] for k in sorted(per)}}


def render(summary):
    lines = []
    m = summary["meta"]
    lines.append(f"timeline: {m.get('events', '?')} events "
                 f"({m.get('dropped', 0)} dropped), "
                 f"{summary['requests']} request records")
    lines.append("")
    lines.append(f"{'phase':<18}{'count':>8}{'total ms':>12}"
                 f"{'mean ms':>10}{'max ms':>10}")
    for name, p in sorted(summary["phases"].items(),
                          key=lambda kv: -kv[1]["total_ms"]):
        lines.append(f"{name:<18}{p['count']:>8}{p['total_ms']:>12}"
                     f"{p['mean_ms']:>10}{p['max_ms']:>10}")
    if summary["slowest_steps"]:
        lines.append("")
        lines.append(f"top {len(summary['slowest_steps'])} slowest steps:")
        for ev in summary["slowest_steps"]:
            extra = {k: v for k, v in ev.items()
                     if k not in ("kind", "name", "dur_ms", "t_ns")}
            lines.append(f"  {ev['dur_ms']:>10.3f} ms  {ev['name']:<16}"
                         f"{json.dumps(extra) if extra else ''}")
    if summary["request_latency"]:
        lines.append("")
        lines.append(f"{'latency':<16}{'count':>7}{'mean':>10}"
                     f"{'p50':>10}{'p95':>10}{'p99':>10}{'max':>10}")
        for name, s in summary["request_latency"].items():
            lines.append(f"{name:<16}{s['count']:>7}{s['mean']:>10}"
                         f"{s['p50']:>10}{s['p95']:>10}{s['p99']:>10}"
                         f"{s['max']:>10}")
    pre = summary.get("prefill")
    if pre:
        lines.append("")
        lines.append(
            f"prefill: occupancy {pre['occupancy']} "
            f"(valid/fed token ratio), variants "
            + ", ".join(f"{k}={v}"
                        for k, v in sorted(pre["variants"].items())))
        lines.append(f"{'bucket':<10}{'chunks':>8}{'mean ms':>10}"
                     f"{'max ms':>10}{'valid tok':>11}{'pad tok':>9}"
                     f"{'occ':>7}")
        for bk, b in pre["per_bucket"].items():
            lines.append(f"{bk:<10}{b['count']:>8}{b['mean_ms']:>10}"
                         f"{b['max_ms']:>10}{b['valid_tokens']:>11}"
                         f"{b['pad_tokens']:>9}{b['occupancy']:>7}")
    dec = summary.get("decode")
    if dec:
        lines.append("")
        lines.append("decode variants:")
        lines.append(f"{'variant':<16}{'steps':>8}{'total ms':>12}"
                     f"{'mean ms':>10}{'max ms':>10}")
        for name, v in sorted(dec["variants"].items(),
                              key=lambda kv: -kv[1]["total_ms"]):
            lines.append(f"{name:<16}{v['count']:>8}{v['total_ms']:>12}"
                         f"{v['mean_ms']:>10}{v['max_ms']:>10}")
        roofed = [(n, v) for n, v in sorted(dec["variants"].items())
                  if v.get("step_us_at_peak_bw")]
        if roofed:
            src = (dec.get("peak_source") or {}).get("hbm_bw", "?")
            lines.append(f"roofline (peak HBM BW "
                         f"{dec.get('peak_hbm_bw', 0) / 1e9:.0f} GB/s, "
                         f"{src}):")
            for name, v in roofed:
                mean_us = v["mean_ms"] * 1e3
                frac = v.get("roofline_frac")
                # %.1f would print interpret-scale fractions as 0.0%
                pct = f"{frac * 100:.3g}%" if frac is not None else "?"
                lines.append(
                    f"  {name}: {mean_us:.1f} us measured, "
                    f"{v['step_us_at_peak_bw']} us at peak BW "
                    f"-> {pct} of roofline "
                    f"({v.get('bytes_per_step_modeled', 0)} modeled "
                    "bytes/step)")
    sched = summary.get("scheduler")
    if sched:
        lines.append("")
        lines.append(f"scheduler: {sched['preemptions']} preemptions, "
                     f"{sched['resumes']} resumes, "
                     f"{sched['deadline_expired']} deadline-expired")
        per = sched.get("per_class_queue_wait_ms", {})
        if per:
            lines.append(f"{'class wait ms':<16}{'count':>7}{'mean':>10}"
                         f"{'p50':>10}{'p95':>10}{'p99':>10}{'max':>10}")
            for cls, s in per.items():
                lines.append(f"{'class ' + cls:<16}{s['count']:>7}"
                             f"{s['mean']:>10}{s['p50']:>10}"
                             f"{s['p95']:>10}{s['p99']:>10}"
                             f"{s['max']:>10}")
        h = sched.get("handoff")
        if h:
            lines.append(
                f"kv handoff: {h['count']} transfers, "
                f"{h['bytes_total']} bytes, p50 "
                f"{h['handoff_ms']['p50']} ms (extract "
                f"{h.get('extract_ms_mean', 0.0)} / put "
                f"{h.get('put_ms_mean', 0.0)} / insert "
                f"{h.get('insert_ms_mean', 0.0)})")
    rt = summary.get("routing")
    if rt:
        lines.append("")
        lines.append(
            f"fleet routing: {rt['requests']} requests, "
            f"warm {rt['warm']} / cold {rt['cold']} "
            f"(warm-hit {rt['warm_hit_ratio']}), "
            f"{rt['diverted']} diverted")
        lines.append(f"{'replica':<18}{'routed':>8}{'share':>9}"
                     f"{'warm':>7}{'diverted':>10}")
        for name, d in rt["per_replica"].items():
            lines.append(f"{name:<18}{d['routed']:>8}{d['share']:>9}"
                         f"{d['warm']:>7}{d['diverted']:>10}")
    return "\n".join(lines)


def summarize_train(meta, events, top=10, gap_factor=4.0,
                    min_wall_ms=50.0):
    """Train-mode summary over ``train_step``/``compile``/``host_gap``
    events: per-phase totals, host-vs-device gap per step, slowest
    steps, compile log. ``host_bound_steps`` applies the SAME predicate
    as the live HostGapDetector (ratio > gap_factor AND wall >=
    min_wall_ms) — the offline diagnosis must not contradict the live
    one on identical data (fast steps have huge ratios but no one
    cares about a 2 ms step)."""
    out = {"meta": {k: meta.get(k) for k in
                    ("schema", "events", "dropped", "mode", "mesh",
                     "accumulate_steps") if k in meta}}
    steps = [ev for ev in events if ev.get("name") == "train_step"]
    phases = {}
    for key in ("stage_ms", "dispatch_ms", "sync_ms"):
        vals = sorted(ev[key] for ev in steps if ev.get(key) is not None)
        if vals:
            phases[key] = {"count": len(vals),
                           "total_ms": round(sum(vals), 3),
                           "mean_ms": round(sum(vals) / len(vals), 3),
                           "p50_ms": round(_percentile(vals, 0.50), 3),
                           "max_ms": round(vals[-1], 3)}
    out["phases"] = phases

    gaps = []
    for ev in steps:
        host = (ev.get("stage_ms") or 0.0) + (ev.get("dispatch_ms")
                                              or 0.0)
        dev = ev.get("sync_ms")
        if dev is None:
            continue
        gaps.append({"step": ev.get("step"),
                     "host_ms": round(host, 3),
                     "device_wait_ms": round(dev, 3),
                     "ratio": round(host / max(dev, 1e-3), 1),
                     "host_bound": (host > gap_factor * max(dev, 1e-3)
                                    and host + dev >= min_wall_ms)})
    # genuinely host-bound steps first (then by host time): sorting on
    # raw ratio would bury the one real 3 s host-bound step under a
    # pile of trivially fast steps whose sync rounds to ~0
    gaps.sort(key=lambda g: (not g["host_bound"], -g["host_ms"]))
    out["host_device_gap"] = {
        "steps": len(gaps),
        "host_bound_steps": sum(1 for g in gaps if g["host_bound"]),
        "worst": gaps[:top]}

    timed = [ev for ev in steps if ev.get("dur_ms") is not None]
    timed.sort(key=lambda e: -e["dur_ms"])
    out["slowest_steps"] = timed[:top]
    out["compiles"] = [{k: ev.get(k) for k in
                        ("program", "dur_ms", "count")}
                       for ev in events if ev.get("name") == "compile"]
    out["host_gap_events"] = sum(1 for ev in events
                                 if ev.get("name") == "host_gap")
    out["stalls"] = [ev.get("reason") for ev in events
                     if ev.get("name") == "stall"]
    return out


def render_train(summary):
    lines = []
    m = summary["meta"]
    lines.append(f"train timeline: {m.get('events', '?')} events "
                 f"({m.get('dropped', 0)} dropped), mesh="
                 f"{m.get('mesh')}")
    lines.append("")
    lines.append(f"{'phase':<14}{'count':>7}{'total ms':>12}"
                 f"{'mean ms':>10}{'p50 ms':>10}{'max ms':>10}")
    for name, p in summary["phases"].items():
        lines.append(f"{name:<14}{p['count']:>7}{p['total_ms']:>12}"
                     f"{p['mean_ms']:>10}{p['p50_ms']:>10}"
                     f"{p['max_ms']:>10}")
    g = summary["host_device_gap"]
    lines.append("")
    lines.append(f"host-vs-device: {g['host_bound_steps']}/{g['steps']} "
                 "steps host-bound")
    for w in g["worst"][:5]:
        lines.append(f"  step {w['step']}: host {w['host_ms']} ms vs "
                     f"device wait {w['device_wait_ms']} ms "
                     f"({w['ratio']}x)")
    if summary["compiles"]:
        lines.append("")
        lines.append("compiles:")
        for c in summary["compiles"]:
            lines.append(f"  {c.get('program')}: {c.get('dur_ms'):.1f} ms"
                         f" (#{c.get('count')})")
    if summary["slowest_steps"]:
        lines.append("")
        lines.append(f"top {len(summary['slowest_steps'])} slowest steps:")
        for ev in summary["slowest_steps"]:
            lines.append(f"  {ev['dur_ms']:>10.3f} ms  step "
                         f"{ev.get('step')}")
    if summary["stalls"]:
        lines.append("")
        lines.append(f"stalls: {len(summary['stalls'])}")
        for r in summary["stalls"][:5]:
            lines.append(f"  {r}")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="timeline JSONL file")
    ap.add_argument("--mode", choices=("auto", "serving", "train"),
                    default="auto",
                    help="summary flavor (auto reads the meta header)")
    ap.add_argument("--top", type=int, default=10,
                    help="slowest steps to list (default 10)")
    ap.add_argument("--json", action="store_true",
                    help="emit the summary as JSON instead of a table")
    args = ap.parse_args(argv)
    try:
        meta, events, requests = load(args.path)
    except TimelineError as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    mode = args.mode
    if mode == "auto":
        mode = meta.get("mode", "serving")
    if mode == "train":
        summary = summarize_train(meta, events, top=args.top)
        print(json.dumps(summary, indent=1) if args.json
              else render_train(summary))
    else:
        summary = summarize(meta, events, requests, top=args.top)
        print(json.dumps(summary, indent=1) if args.json
              else render(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
