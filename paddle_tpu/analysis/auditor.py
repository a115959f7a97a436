"""Audit orchestration: trace a spec, run the rule passes, diff
findings against a committed baseline.

Tracing is the only jax work an audit does: ``jax.make_jaxpr`` over the
spec's callable with its abstract signature (static argnums respected),
plus — when the ambient config has x64 OFF — a second trace under
``jax_enable_x64`` (the *probe*): the dtype-promotion and carry-drift
rules read the probed jaxpr because the bug class they hunt only
manifests when the global x64 flag flips. Neither trace compiles or
executes anything, and neither touches the audited jit object's
compilation cache (``make_jaxpr`` runs its own trace).

Baselines: ``write_baseline`` freezes the current finding fingerprints;
``diff_findings`` splits a later run into (new, fixed). The CI gate
(``tools/program_audit.py`` / the ``pytest -m audit`` tier-1 test)
fails on NEW findings only — a fixed finding just shrinks the baseline
on its next refresh.
"""
from __future__ import annotations

import json
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Tuple

from .registry import REGISTRY, ProgramRegistry, ProgramSpec, \
    abstract_signature
from .rules import ALL_RULES, Finding, ProgramArtifacts

__all__ = ["AuditReport", "audit_spec", "audit_program", "audit_registry",
           "trace_artifacts", "findings_to_json", "write_baseline",
           "load_baseline", "diff_findings", "publish_findings",
           "BASELINE_VERSION"]

BASELINE_VERSION = 1


@dataclass
class AuditReport:
    """Findings + provenance for one audited program."""
    program: str
    findings: List[Finding] = field(default_factory=list)
    rules_run: List[str] = field(default_factory=list)
    meta: Dict = field(default_factory=dict)

    def to_dict(self) -> Dict:
        return {"program": self.program,
                "findings": [f.to_dict() for f in self.findings],
                "rules_run": list(self.rules_run),
                "meta": dict(self.meta)}


def _flat_io(closed, spec: ProgramSpec):
    """(in_avals, out_avals, donated_mask) for a traced program.

    A jitted callable traces to a single top-level pjit eqn whose
    params carry ``donated_invars`` per flat input — the authoritative
    donation declaration. A plain callable falls back to the outer
    jaxpr's in/out avals and the spec's ``donate_argnums`` mapped
    through per-arg leaf counts (skipped when static argnums shift the
    flat layout)."""
    import jax

    # the OUTER jaxpr's invars/outvars are the user-order flat lists
    # (a pjit eqn's own outvars DROP pass-through outputs and its
    # invars gain lifted consts — indices there would misalign the
    # carry map and the donation mask)
    jaxpr = closed.jaxpr
    in_avals = tuple(v.aval for v in jaxpr.invars)
    out_avals = tuple(getattr(v, "aval", None) for v in jaxpr.outvars)
    donated = [False] * len(in_avals)
    if len(jaxpr.eqns) == 1 and "donated_invars" in jaxpr.eqns[0].params:
        eqn = jaxpr.eqns[0]
        dmap = {id(v): bool(d) for v, d in
                zip(eqn.invars, eqn.params["donated_invars"])}
        return (in_avals, out_avals,
                tuple(dmap.get(id(v), False) for v in jaxpr.invars))
    if spec.donate_argnums and not spec.static_argnums:
        off = 0
        for i, a in enumerate(spec.args):
            n = len(jax.tree_util.tree_leaves(a))
            if i in spec.donate_argnums:
                for j in range(off, min(off + n, len(donated))):
                    donated[j] = True
            off += n
    return in_avals, out_avals, tuple(donated)


def trace_artifacts(spec: ProgramSpec, x64_probe: bool = True
                    ) -> ProgramArtifacts:
    """Trace ``spec`` into :class:`ProgramArtifacts` (ambient jaxpr +
    optional x64-probed jaxpr). Raises whatever the trace raises —
    callers turn that into a TRACE_ERROR finding."""
    import jax

    mk0 = (jax.make_jaxpr(spec.fn, static_argnums=spec.static_argnums)
           if spec.static_argnums else jax.make_jaxpr(spec.fn))
    if spec.axis_env:
        # per-shard bodies (functions meant to run INSIDE shard_map)
        # reference axes they do not bind; trace them under the spec's
        # declared axis bindings (jax_compat.extend_axis_env)
        from ..core.jax_compat import extend_axis_env

        def mk(*a, **kw):
            with extend_axis_env(spec.axis_env):
                return mk0(*a, **kw)
    else:
        mk = mk0
    closed = mk(*spec.args, **spec.kwargs)
    in_avals, out_avals, donated = _flat_io(closed, spec)
    art = ProgramArtifacts(spec=spec, closed=closed, in_avals=in_avals,
                           out_avals=out_avals, donated=donated)
    if x64_probe and not jax.config.jax_enable_x64:
        with jax.enable_x64(True):
            closed_x64 = mk(*spec.args, **spec.kwargs)
        (art.in_avals_x64, art.out_avals_x64, _) = \
            _flat_io(closed_x64, spec)
        art.closed_x64 = closed_x64
    # note: no lower()/compile() here — every current rule reads the
    # jaxpr level (donation via pjit donated_invars), and lowering
    # would re-trace the whole program for text nothing consumes
    return art


def audit_spec(spec: ProgramSpec, rules=ALL_RULES,
               config: Optional[Dict[str, Dict]] = None,
               x64_probe: bool = True) -> AuditReport:
    """Run every rule pass over one spec. A trace failure becomes a
    single TRACE_ERROR finding (severity error) — a registered program
    that stopped tracing is itself a regression the gate must catch.

    ``config`` maps rule function __name__ -> kwargs (thresholds)."""
    report = AuditReport(program=spec.name,
                         rules_run=[r.__name__ for r in rules])
    try:
        art = trace_artifacts(spec, x64_probe=x64_probe)
    except Exception as e:  # noqa: BLE001
        report.findings.append(Finding(
            rule="auditor", code="TRACE_ERROR", severity="error",
            program=spec.name, site=type(e).__name__,
            message=f"program failed to trace: {type(e).__name__}: {e}",
            detail={"exception": type(e).__name__}))
        report.meta["trace_error"] = str(e)
        return report
    report.meta["x64_probed"] = art.closed_x64 is not None
    cfg = config or {}
    for rule in rules:
        report.findings.extend(rule(art, **cfg.get(rule.__name__, {})))
    return report


def audit_program(fn, *args, name: str = "program", rules=ALL_RULES,
                  config=None, x64_probe: bool = True,
                  **meta) -> AuditReport:
    """Ad-hoc audit of a callable: builds a throwaway spec (abstract
    signature derived from ``args``) and runs :func:`audit_spec`.
    ``meta`` forwards ProgramSpec fields (donate_argnums, carry,
    mesh_axes, static_argnums...)."""
    kwargs = meta.pop("kwargs", {})
    spec = ProgramSpec(name=name, fn=fn,
                       args=tuple(abstract_signature(args)),
                       kwargs=dict(abstract_signature(kwargs)), **meta)
    return audit_spec(spec, rules=rules, config=config,
                      x64_probe=x64_probe)


def audit_registry(registry: Optional[ProgramRegistry] = None,
                   names: Optional[Iterable[str]] = None,
                   rules=ALL_RULES, config=None,
                   x64_probe: bool = True) -> List[AuditReport]:
    registry = registry if registry is not None else REGISTRY
    wanted = list(names) if names is not None else registry.names()
    reports = []
    for n in wanted:
        spec = registry.get(n)
        if spec is None:
            reports.append(AuditReport(
                program=n, findings=[Finding(
                    rule="auditor", code="UNKNOWN_PROGRAM",
                    severity="error", program=n, site="registry",
                    message=f"no program named {n!r} in the registry")]))
            continue
        reports.append(audit_spec(spec, rules=rules, config=config,
                                  x64_probe=x64_probe))
    return reports


# -- baseline workflow --------------------------------------------------


def findings_to_json(reports: List[AuditReport]) -> Dict:
    """The CLI's JSON document: per-program reports + a summary."""
    n_by_sev: Dict[str, int] = {}
    for r in reports:
        for f in r.findings:
            n_by_sev[f.severity] = n_by_sev.get(f.severity, 0) + 1
    return {"version": BASELINE_VERSION,
            "programs": {r.program: r.to_dict() for r in reports},
            "summary": {"programs": len(reports),
                        "findings": sum(len(r.findings) for r in reports),
                        "by_severity": dict(sorted(n_by_sev.items()))}}


def _all_findings(reports: List[AuditReport]) -> List[Finding]:
    return [f for r in reports for f in r.findings]


def write_baseline(reports: List[AuditReport], path: str) -> Dict:
    """Freeze current fingerprints as the accepted baseline."""
    doc = {"version": BASELINE_VERSION,
           "findings": {f.fingerprint: {
               "rule": f.rule, "code": f.code, "severity": f.severity,
               "program": f.program, "message": f.message}
               for f in _all_findings(reports)}}
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return doc


def load_baseline(path: str) -> Dict:
    with open(path) as fh:
        doc = json.load(fh)
    if doc.get("version") != BASELINE_VERSION:
        raise ValueError(
            f"baseline {path}: version {doc.get('version')!r} != "
            f"{BASELINE_VERSION} — regenerate with --write-baseline")
    if not isinstance(doc.get("findings"), dict):
        raise ValueError(f"baseline {path}: missing findings dict")
    return doc


def diff_findings(reports: List[AuditReport], baseline: Dict
                  ) -> Tuple[List[Finding], List[str]]:
    """(new findings not in baseline, baseline fingerprints now fixed).
    The gate fails on ``new`` only."""
    current = _all_findings(reports)
    base = set(baseline.get("findings", {}))
    new = [f for f in current if f.fingerprint not in base]
    have = {f.fingerprint for f in current}
    fixed = sorted(fp for fp in base if fp not in have)
    return new, fixed


_SEV_RANK = {"info": 0, "warning": 1, "error": 2}


def publish_findings(findings, counters: Optional[Dict] = None,
                     obs=None, min_severity: str = "warning") -> int:
    """Surface an audit result to the observability layer: a findings
    counter in the component's adopted counter dict and a timeline
    event. Only findings at ``min_severity`` or above count (default
    warning: info findings — e.g. the intentional master-weight
    bf16→f32 upcast — are advisory report detail, not a bench-capture
    regression signal). Returns the counted number."""
    flat: List[Finding] = []
    for x in ([findings] if isinstance(findings, AuditReport)
              else list(findings)):
        flat.extend(x.findings if isinstance(x, AuditReport) else [x])
    floor = _SEV_RANK.get(min_severity, 1)
    n = sum(1 for f in flat if _SEV_RANK.get(f.severity, 2) >= floor)
    if counters is not None:
        counters["audit_findings"] = counters.get("audit_findings", 0) + n
    if obs is not None:
        obs.timeline.record("program_audit", findings=n,
                            total=len(flat))
    return n
