#!/usr/bin/env python
"""Per-kernel bench regression gate against the banked BENCH trajectory.

The BENCH captures bank a ``kernels`` section with per-kernel
``us_pallas`` timings (bench.py ``bench_kernels``, persisted in
``BENCH_OPPORTUNISTIC.json`` and the per-round ``BENCH_rNN.json``
files). A tuning or fusion regression used to be invisible until a
reviewer eyeballed the numbers; this tool turns the trajectory into a
gate: a fresh capture whose ``us_pallas`` exceeds the banked best by
more than the threshold fails with exit code 1, the way audit findings
fail tools/program_audit.py.

Usage:
  python tools/kernel_bench_gate.py --capture fresh.json       # gate
  python tools/kernel_bench_gate.py --capture fresh.json --threshold 0.5
  python tools/kernel_bench_gate.py --list-banked              # show refs
  python tools/kernel_bench_gate.py --capture fresh.json --json out.json

``--capture`` accepts either a bare ``bench_kernels`` result (a dict
with ``cases``) or a full bench.py output document (the ``kernels`` key
is used). The banked reference for each kernel is the BEST (minimum)
``us_pallas`` across every banked capture — a regression is measured
against the trajectory's high-water mark, not last round's possibly-
already-regressed number.

bench.py runs this as a post-window step after the ``kernels`` config
(opt out with ``BENCH_KERNEL_GATE=0``; threshold via
``BENCH_KERNEL_GATE_THRESHOLD``, default 0.30 — device timing noise at
these microsecond scales makes tighter gates flaky).

Coverage is exactly ``bench_kernels``'s timed case set: the serving
decode kernels AND the fused training kernels (``fused_linear_ce``,
``fused_swiglu``, ``rms_norm_bwd`` — each timed over the full fwd+bwd
the trainer runs), so a training-fusion regression fails bench runs
the same way a decode regression does.

``--roofline`` switches the gated quantity from raw ``us_pallas`` to
the roofline observatory's ``achieved_bw_frac`` (bench.py prices every
case's modeled bytes against the measured time): a kernel whose
achieved-bandwidth fraction DROPS below the banked best by more than
the threshold (``BENCH_ROOFLINE_GATE_THRESHOLD``, default 0.30) fails,
and ``BENCH_ROOFLINE_GATE_FLOOR`` (default off) additionally flags any
kernel running far below its memory-bound roofline regardless of
history. ``--demo-regression`` self-checks the roofline gate with an
injected bandwidth collapse — it MUST exit nonzero.

Exit codes: 0 pass (or nothing comparable — no banked data / interpret
capture: a gate with no reference must not fail vacuously), 1 regression
over threshold, 3 bad invocation.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DEFAULT_THRESHOLD = 0.30


def _kernel_cases(doc):
    """A bench doc (full output, opportunistic bank, or bare kernels
    result) -> {kernel: us_pallas} for timed, non-interpret cases."""
    if not isinstance(doc, dict):
        return {}
    k = doc.get("kernels") if "cases" not in doc else doc
    if not isinstance(k, dict) or k.get("interpret"):
        return {}
    out = {}
    for name, case in (k.get("cases") or {}).items():
        us = case.get("us_pallas") if isinstance(case, dict) else None
        if isinstance(us, (int, float)) and us > 0:
            out[name] = float(us)
    return out


def _roofline_cases(doc):
    """A bench doc -> {kernel: achieved_bw_frac} for timed cases the
    roofline observatory priced (bench.py BENCH_ROOFLINE rows)."""
    if not isinstance(doc, dict):
        return {}
    k = doc.get("kernels") if "cases" not in doc else doc
    if not isinstance(k, dict) or k.get("interpret"):
        return {}
    out = {}
    for name, case in (k.get("cases") or {}).items():
        frac = case.get("achieved_bw_frac") \
            if isinstance(case, dict) else None
        if isinstance(frac, (int, float)) and frac > 0:
            out[name] = float(frac)
    return out


def _banked_docs(repo: str):
    """Every parseable banked BENCH document (BENCH_rNN files wrap the
    output under "parsed")."""
    paths = sorted(glob.glob(os.path.join(repo, "BENCH_r*.json")))
    paths += [os.path.join(repo, "BENCH_OPPORTUNISTIC.json")]
    for path in paths:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        for d in (doc, doc.get("parsed") if isinstance(doc, dict)
                  else None):
            if d:
                yield path, d


def collect_banked(repo: str = _REPO):
    """Best (minimum) banked us_pallas per kernel across the BENCH
    trajectory, with the source file of each reference."""
    best, src = {}, {}
    for path, d in _banked_docs(repo):
        for name, us in _kernel_cases(d).items():
            if name not in best or us < best[name]:
                best[name] = us
                src[name] = os.path.basename(path)
    return best, src


def collect_banked_roofline(repo: str = _REPO):
    """Best (MAXIMUM) banked achieved_bw_frac per kernel — the
    trajectory's closest-to-roofline run is the reference a bandwidth
    regression is measured against."""
    best, src = {}, {}
    for path, d in _banked_docs(repo):
        for name, frac in _roofline_cases(d).items():
            if name not in best or frac > best[name]:
                best[name] = frac
                src[name] = os.path.basename(path)
    return best, src


def gate_capture(capture, threshold: float = DEFAULT_THRESHOLD,
                 repo: str = _REPO):
    """Diff a fresh capture against the banked trajectory.

    Returns a dict: ``status`` pass|regressed|no_reference, per-kernel
    ``regressions`` (over threshold), ``improved`` (faster than the
    banked best), ``new`` (no banked reference yet), ``checked``."""
    fresh = _kernel_cases(capture)
    banked, src = collect_banked(repo)
    res = {"threshold": threshold, "checked": 0, "regressions": {},
           "improved": {}, "new": sorted(set(fresh) - set(banked)),
           # banked keys this capture did NOT time: a shrunken capture
           # must be visible, not silently ungated (no-silent-caps)
           "skipped_banked": sorted(set(banked) - set(fresh)),
           "status": "pass"}
    if not fresh:
        res["status"] = "no_reference"
        res["note"] = ("capture has no timed us_pallas cases "
                       "(interpret mode or all errored)")
        return res
    if not banked:
        res["status"] = "no_reference"
        res["note"] = "no banked BENCH trajectory to diff against"
        return res
    if not set(fresh) & set(banked):
        # trajectory files EXIST and the capture timed kernels, yet not
        # one key lines up — a renamed case set would otherwise ride a
        # bare "pass" forever while gating nothing
        res["status"] = "no_reference"
        res["note"] = (f"no comparable kernel keys: capture has "
                       f"{sorted(fresh)}, banked trajectory has "
                       f"{sorted(banked)}")
        return res
    for name in sorted(set(fresh) & set(banked)):
        res["checked"] += 1
        ratio = fresh[name] / banked[name]
        entry = {"us_pallas": fresh[name], "banked_best": banked[name],
                 "banked_in": src[name], "ratio": round(ratio, 3)}
        if ratio > 1.0 + threshold:
            res["regressions"][name] = entry
        elif ratio < 1.0:
            res["improved"][name] = entry
    if res["regressions"]:
        res["status"] = "regressed"
    return res


def _diff_roofline(fresh, banked, src, threshold: float,
                   floor: float = 0.0):
    """Roofline-mode diff core (separated so --demo-regression can
    inject synthetic references): fresh/banked map kernel ->
    achieved_bw_frac; LOWER is worse, so a regression is
    ``fresh < banked_best * (1 - threshold)``. ``floor`` > 0
    additionally flags any fresh kernel below that absolute
    achieved-bandwidth fraction, banked or not."""
    res = {"mode": "roofline", "threshold": threshold, "floor": floor,
           "checked": 0, "regressions": {}, "improved": {},
           "new": sorted(set(fresh) - set(banked)),
           "skipped_banked": sorted(set(banked) - set(fresh)),
           "status": "pass"}
    if floor:
        for name, frac in sorted(fresh.items()):
            if frac < floor:
                res["regressions"][name] = {
                    "achieved_bw_frac": frac, "floor": floor,
                    "reason": "below_floor"}
    if not fresh:
        res["status"] = "no_reference"
        res["note"] = ("capture has no achieved_bw_frac rows "
                       "(interpret mode, BENCH_ROOFLINE=0, or untimed)")
        return res
    if not (set(fresh) & set(banked)):
        if res["regressions"]:
            res["status"] = "regressed"
            return res
        res["status"] = "no_reference"
        res["note"] = ("no banked achieved_bw_frac references to diff "
                       "against" if not banked else
                       f"no comparable kernel keys: capture has "
                       f"{sorted(fresh)}, banked trajectory has "
                       f"{sorted(banked)}")
        return res
    for name in sorted(set(fresh) & set(banked)):
        res["checked"] += 1
        ratio = fresh[name] / banked[name]
        entry = {"achieved_bw_frac": fresh[name],
                 "banked_best": banked[name], "banked_in": src[name],
                 "ratio": round(ratio, 3), "reason": "regressed_bw"}
        if ratio < 1.0 - threshold:
            res["regressions"].setdefault(name, entry)
        elif ratio > 1.0:
            res["improved"][name] = entry
    if res["regressions"]:
        res["status"] = "regressed"
    return res


def gate_roofline(capture, threshold: float = DEFAULT_THRESHOLD,
                  floor: float = 0.0, repo: str = _REPO):
    """Diff a fresh capture's achieved-bandwidth fractions against the
    banked trajectory's best (same SKIP semantics as the timing gate)."""
    fresh = _roofline_cases(capture)
    banked, src = collect_banked_roofline(repo)
    return _diff_roofline(fresh, banked, src, threshold, floor)


def build_demo_roofline_regression(threshold: float = DEFAULT_THRESHOLD):
    """Self-check: an injected bandwidth collapse (a kernel that banked
    at 62% of peak HBM bandwidth now achieving 5%) that MUST trip the
    roofline gate — proving the wiring end to end, kernel_audit.py
    --demo-regression style."""
    banked = {"decode_mlp_block": 0.62}
    src = {"decode_mlp_block": "<demo>"}
    fresh = {"decode_mlp_block": 0.05}
    res = _diff_roofline(fresh, banked, src, threshold)
    res["demo"] = True
    return res


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--capture", metavar="PATH",
                    help="fresh bench JSON (full output or bare "
                         "kernels result)")
    ap.add_argument("--threshold", type=float, default=None,
                    help="allowed change vs the banked best (0.30 = "
                         "+30%% us_pallas growth, or -30%% "
                         "achieved_bw_frac drop with --roofline)")
    ap.add_argument("--roofline", action="store_true",
                    help="gate achieved_bw_frac (roofline observatory "
                         "rows) instead of raw us_pallas")
    ap.add_argument("--floor", type=float, default=float(
        os.environ.get("BENCH_ROOFLINE_GATE_FLOOR", "0")),
        help="with --roofline: flag any kernel below this absolute "
             "achieved-bandwidth fraction (default off)")
    ap.add_argument("--demo-regression", action="store_true",
                    help="roofline-gate self-check: inject a bandwidth "
                         "collapse that must fail the gate")
    ap.add_argument("--repo", default=_REPO,
                    help="repo dir holding the banked BENCH files")
    ap.add_argument("--json", metavar="PATH",
                    help="write the gate result document to PATH")
    ap.add_argument("--list-banked", action="store_true",
                    help="print the banked per-kernel references and "
                         "exit")
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    say = (lambda *a: None) if args.quiet else print
    roofline = args.roofline or args.demo_regression
    if args.threshold is None:
        args.threshold = float(os.environ.get(
            "BENCH_ROOFLINE_GATE_THRESHOLD" if roofline
            else "BENCH_KERNEL_GATE_THRESHOLD", DEFAULT_THRESHOLD))

    if args.list_banked:
        banked, src = (collect_banked_roofline if roofline
                       else collect_banked)(args.repo)
        unit = "bw_frac" if roofline else "us"
        for name in sorted(banked):
            print(f"{name:24s} {banked[name]:10.4g} {unit}  "
                  f"({src[name]})")
        if not banked:
            print("(no banked kernel captures found)")
        return 0
    if args.threshold < 0:
        print("[kernel-gate] threshold must be >= 0", file=sys.stderr)
        return 3
    if args.demo_regression:
        if args.capture:
            print("[kernel-gate] --demo-regression refuses a real "
                  "--capture: the injected collapse would shadow it",
                  file=sys.stderr)
            return 3
        res = build_demo_roofline_regression(args.threshold)
    else:
        if not args.capture:
            print("[kernel-gate] --capture is required (or "
                  "--list-banked / --demo-regression)", file=sys.stderr)
            return 3
        try:
            with open(args.capture) as f:
                capture = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            print(f"[kernel-gate] cannot read capture "
                  f"{args.capture}: {e}", file=sys.stderr)
            return 3
        if roofline:
            res = gate_roofline(capture, threshold=args.threshold,
                                floor=args.floor, repo=args.repo)
        else:
            res = gate_capture(capture, threshold=args.threshold,
                               repo=args.repo)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(res, f, indent=2, sort_keys=True)
            f.write("\n")

    if res["status"] == "no_reference":
        say(f"[kernel-gate] SKIP: {res.get('note', '')}")
        for name in res.get("skipped_banked", []):
            say(f"[kernel-gate] skipped banked key (no fresh timing): "
                f"{name}")
        return 0
    for name, e in res["regressions"].items():
        if "achieved_bw_frac" in e:
            ref = (f"vs banked {e['banked_best']:.4f} "
                   f"({e['banked_in']}) = {e['ratio']:.2f}x"
                   if "banked_best" in e
                   else f"below floor {e['floor']:.4f}")
            print(f"[kernel-gate] ROOFLINE REGRESSION {name}: "
                  f"achieved_bw_frac {e['achieved_bw_frac']:.4f} "
                  f"{ref} (threshold -{res['threshold']:.0%})",
                  file=sys.stderr)
        else:
            print(f"[kernel-gate] REGRESSION {name}: "
                  f"{e['us_pallas']:.1f}us "
                  f"vs banked {e['banked_best']:.1f}us "
                  f"({e['banked_in']}) = {e['ratio']:.2f}x (threshold "
                  f"{1 + res['threshold']:.2f}x)", file=sys.stderr)
    for name, e in res["improved"].items():
        if "achieved_bw_frac" in e:
            say(f"[kernel-gate] improved {name}: achieved_bw_frac "
                f"{e['achieved_bw_frac']:.4f} vs banked "
                f"{e['banked_best']:.4f} ({e['ratio']:.2f}x)")
        else:
            say(f"[kernel-gate] improved {name}: "
                f"{e['us_pallas']:.1f}us vs banked "
                f"{e['banked_best']:.1f}us ({e['ratio']:.2f}x)")
    if res["new"]:
        say(f"[kernel-gate] new kernels (no banked reference yet): "
            f"{', '.join(res['new'])}")
    if res["skipped_banked"]:
        # exactly which banked keys this run did NOT gate — a capture
        # that quietly stopped timing a kernel must say so
        say(f"[kernel-gate] banked keys skipped (not timed by this "
            f"capture): {', '.join(res['skipped_banked'])}")
    sign = "-" if res.get("mode") == "roofline" else "+"
    if res["status"] == "regressed":
        print(f"[kernel-gate] GATE FAILED: {len(res['regressions'])} "
              f"kernel(s) regressed past {sign}{res['threshold']:.0%}",
              file=sys.stderr)
        return 1
    say(f"[kernel-gate] gate clean: {res['checked']} kernel(s) within "
        f"{sign}{res['threshold']:.0%} of the banked trajectory")
    return 0


if __name__ == "__main__":
    sys.exit(main())
