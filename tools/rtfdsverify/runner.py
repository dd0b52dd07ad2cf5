"""Verify orchestration: build targets → trace signatures → run checks
→ baseline → verdict. The chassis (Finding, Baseline, severity gate)
is rtfdslint's; only the evidence source differs (traced jaxprs
instead of parsed source)."""

from __future__ import annotations

import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional

from rtfdslint.baseline import Baseline
from rtfdslint.finding import Finding, RuleStats, severity_rank

DEFAULT_BASELINE = "tools/rtfdsverify/baseline.json"


@dataclass
class VerifyResult:
    """Mirror of ``rtfdslint.runner.LintResult`` over verification
    targets (kept schema-compatible so ``rtfds lint --json`` can carry
    a verifier block unchanged)."""

    findings: List[Finding] = field(default_factory=list)   # active
    baselined: List[Finding] = field(default_factory=list)
    stale_baseline: List[dict] = field(default_factory=list)
    stats: Dict[str, RuleStats] = field(default_factory=dict)
    targets: List[str] = field(default_factory=list)
    signatures_verified: int = 0

    def gate_failures(self, strict: bool = False) -> List[Finding]:
        bad = ("P0", "P1") if not strict else ("P0", "P1", "P2")
        return [f for f in self.findings if f.severity in bad]

    def to_json(self, strict: bool = False) -> dict:
        return {
            "version": 1,
            "targets": self.targets,
            "signatures_verified": self.signatures_verified,
            "strict": strict,
            "findings": [f.to_json() for f in self.findings],
            "baselined": [f.to_json() for f in self.baselined],
            "stale_baseline_entries": self.stale_baseline,
            "checks": {k: v.to_json()
                       for k, v in sorted(self.stats.items())},
            "summary": {
                "active": len(self.findings),
                "gate_failures": len(self.gate_failures(strict=strict)),
                "baselined": len(self.baselined),
            },
        }


def run_verify(root: str,
               targets: Optional[list] = None,
               baseline_path: Optional[str] = DEFAULT_BASELINE,
               checks: Optional[List[str]] = None) -> VerifyResult:
    """Run the device-contract verifier.

    ``targets`` defaults to :func:`~.targets.build_default_targets`
    (pass a list of :class:`~.targets.VerifyTarget` to verify specific
    engines — the sensitivity fixtures do). ``baseline_path`` is
    repo-root-relative; None verifies without a baseline. ``checks``
    filters by check name (unknown names are a hard error, never a
    vacuous pass — same contract as rtfdslint's ``--rule``).
    """
    # The proofs trace shapes only and never need an accelerator: pin the
    # CPU whoever the caller is (the rtfdslint --verify-device path gets
    # here without the Makefile's env pin), so a verifier run never takes
    # the chip from the one process that may hold it.
    import jax

    jax.config.update("jax_platforms", "cpu")

    from .checks import all_checks, known_check_names
    from .targets import build_default_targets

    selected = all_checks()
    if checks:
        unknown = set(checks) - known_check_names()
        if unknown:
            raise ValueError(
                f"unknown check name(s) {sorted(unknown)} — see "
                "--list-checks for the catalog")
        selected = [c for c in selected if c.name in set(checks)]
    if targets is None:
        targets = build_default_targets()

    raw: List[Finding] = []
    n_sigs = 0
    for t in targets:
        inventory = t.engine.dispatch_inventory()
        traced: dict = {}
        for sig in inventory:
            n_sigs += 1
            try:
                traced[sig.key] = t.engine.signature_step(sig).trace(
                    *t.engine.signature_templates(sig))
            # a trace failure is exactly what aot-coverage must report,
            # whatever its type — never abort the other signatures
            except Exception as e:  # noqa: BLE001
                traced[sig.key] = e
        for check_cls in selected:
            raw.extend(check_cls().run(t, inventory, traced))

    baseline = Baseline(path="")
    if baseline_path:
        bp = baseline_path if os.path.isabs(baseline_path) \
            else os.path.join(root, baseline_path)
        baseline = Baseline.load(bp)

    result = VerifyResult(targets=[t.name for t in targets],
                          signatures_verified=n_sigs)
    raw.sort(key=lambda f: (f.path, f.context, f.rule, f.message))
    for f in raw:
        stats = result.stats.setdefault(f.rule, RuleStats())
        if baseline.absorb(f):
            f.suppressed = "baseline"
            result.baselined.append(f)
            stats.baselined += 1
        else:
            result.findings.append(f)
            stats.active += 1
    if targets and baseline_path:
        result.stale_baseline = baseline.stale_entries()
    return result


def render_human(result: VerifyResult, verbose: bool = False,
                 strict: bool = False) -> str:
    out: List[str] = []
    for f in sorted(result.findings,
                    key=lambda f: (severity_rank(f.severity), f.path,
                                   f.context)):
        out.append(f.render())
    if verbose and result.baselined:
        out.append("")
        out.append(f"-- baselined ({len(result.baselined)}):")
        out.extend("   " + f.render() for f in result.baselined)
    if result.stale_baseline:
        out.append("")
        out.append("-- stale baseline entries (matched nothing; delete "
                   "or re-run --update-baseline):")
        for ent in result.stale_baseline:
            out.append(f"   {ent.get('rule')} {ent.get('context', '')}: "
                       f"{ent.get('message', '')[:80]}")
    counts = {"P0": 0, "P1": 0, "P2": 0}
    for f in result.findings:
        counts[f.severity] += 1
    gate = result.gate_failures(strict=strict)
    bar = "P0/P1/P2" if strict else "P0/P1"
    out.append("")
    out.append(
        f"rtfdsverify: {len(result.targets)} target(s), "
        f"{result.signatures_verified} signature(s), "
        f"{len(result.findings)} active finding(s) "
        f"[P0={counts['P0']} P1={counts['P1']} P2={counts['P2']}], "
        f"{len(result.baselined)} baselined")
    out.append("gate: " + (f"FAIL — unbaselined {bar} present"
                           if gate else f"clean (no unbaselined {bar})"))
    return "\n".join(out)


def update_baseline(root: str, result: VerifyResult,
                    baseline_path: str, reason: str) -> int:
    """``--update-baseline``: absorb current gate failures, carrying
    prior reasons forward (rtfdslint semantics)."""
    bp = baseline_path if os.path.isabs(baseline_path) \
        else os.path.join(root, baseline_path)
    prior = Baseline.load(bp)
    keep = result.gate_failures() + result.baselined
    return Baseline.write(bp, keep, prior, reason)
