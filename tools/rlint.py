#!/usr/bin/env python
"""rlint CLI: JAX/thread-discipline static analysis for rl_tpu.

Usage::

    python tools/rlint.py rl_tpu/                 # gate: exit 1 on unsuppressed
    python tools/rlint.py rl_tpu/ --list          # show suppressed findings too
    python tools/rlint.py rl_tpu/ --no-baseline   # raw findings, no gating
    python tools/rlint.py rl_tpu/ --rule R001     # one rule only
    python tools/rlint.py rl_tpu/ --ir            # + compile & audit the IR set
    python tools/rlint.py rl_tpu/ --diff HEAD~1   # only what the revision touched
    python tools/rlint.py rl_tpu/ --strict        # stale suppressions fail too
    python tools/rlint.py rl_tpu/ --write-baseline --reason "cold path: ..."
    python tools/rlint.py rl_tpu/ --artifact RLINT_pr15.json

Two tiers share one baseline and one gate:

- **AST** (R001–R007) lints source files.
- **IR** (R101–R105, ``--ir``) compiles the framework's registered hot
  programs (serving / Anakin / async off-policy — the
  ``rl_tpu.compile.auditset`` set) through a throwaway executable store
  and audits each lowered jaxpr + HLO: host callbacks, unhonored
  donation, shard-local collectives, f64 creep, dead computation.

``--diff <rev>`` scopes both tiers to the change: AST findings are
reported only for the ``.py`` files the revision touched (the index
stays package-wide so call-graph reachability matches a full run), and
the IR set reuses the *persistent* executable store so programs whose
fingerprint/signature did not change reload their serialized
executable and skip re-audit.

The baseline (``.rlint-baseline.json`` at the repo root) is the triage
ledger: suppressions need a reason, stale entries are warnings
(failures under ``--strict``). The ``--artifact`` mode writes the
bench.py-style committed summary (findings by rule, fixed vs
suppressed, IR audit roll-up).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from rl_tpu.analysis import (  # noqa: E402
    ALL_RULES,
    Baseline,
    DEFAULT_BASELINE,
    IR_RULES,
    analyze_paths,
)

# a --diff touching any of these prefixes can change what the registry
# lowers, so the IR set must re-run (store reuse keeps it incremental)
IR_SENSITIVE = (
    "rl_tpu/compile/",
    "rl_tpu/analysis/ir",
    "rl_tpu/models/",
    "rl_tpu/trainers/",
    "rl_tpu/objectives/",
    "rl_tpu/modules/",
    "rl_tpu/collectors/",
    "rl_tpu/data/",
    "rl_tpu/envs/",
    "rl_tpu/parallel/",
)


def changed_files(rev: str) -> list[str]:
    """Repo-relative paths the working tree changed vs ``rev`` (diff +
    untracked, so a not-yet-committed new module is still linted)."""
    diff = subprocess.run(
        ["git", "diff", "--name-only", rev, "--", "."],
        cwd=REPO, capture_output=True, text=True, check=True,
    ).stdout.split()
    untracked = subprocess.run(
        ["git", "ls-files", "--others", "--exclude-standard"],
        cwd=REPO, capture_output=True, text=True, check=True,
    ).stdout.split()
    seen: dict[str, None] = {}
    for p in diff + untracked:
        seen.setdefault(p, None)
    return list(seen)


def run_ir(baseline_path: str, *, fresh_store: bool) -> tuple:
    """Compile the audit set; returns ``(auditor, status)``. The auditor
    carries its own baseline so IR findings merge into the same gate."""
    from rl_tpu.analysis.ir import IRAuditor
    from rl_tpu.compile.auditset import run_ir_audit

    auditor = IRAuditor(baseline_path=baseline_path)
    return run_ir_audit(auditor=auditor, fresh_store=fresh_store)


def build_artifact(findings, unsup, sup, baseline: Baseline, paths,
                   ir_auditor=None, ir_status=None) -> dict:
    rules = list(ALL_RULES) + (list(IR_RULES) if ir_auditor is not None else [])
    by_rule = {}
    for rid in rules:
        found = [f for f in findings if f.rule == rid]
        by_rule[rid] = {
            "found": len(found),
            "suppressed": sum(1 for f in sup if f.rule == rid),
            "unsuppressed": sum(1 for f in unsup if f.rule == rid),
        }
    fixed_by_rule: dict = {}
    for entry in baseline.fixed:
        fixed_by_rule[entry.get("rule", "?")] = fixed_by_rule.get(entry.get("rule", "?"), 0) + 1
    art = {
        "tool": "rlint",
        "paths": list(paths),
        "rules": rules,
        "by_rule": by_rule,
        "total": {
            "found": len(findings),
            "suppressed": len(sup),
            "unsuppressed": len(unsup),
            "fixed_in_prs": len(baseline.fixed),
        },
        "fixed_by_rule": fixed_by_rule,
        "fixed": baseline.fixed,
    }
    if ir_auditor is not None:
        by_program = {}
        for rep in sorted(ir_auditor._snapshot(), key=lambda r: r.name):
            d = {
                "findings": len(rep.findings),
                "donated_declared": rep.donated_declared,
                "donated_honored": rep.donated_honored,
            }
            if rep.cost is not None:
                d["flops"] = rep.cost.flops
                d["bytes"] = rep.cost.bytes
            by_program[rep.name] = d
        art["ir"] = {
            "status": dict(ir_status or {}),
            "programs_audited": ir_auditor.programs_audited(),
            "by_program": by_program,
        }
    return art


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[1].strip())
    ap.add_argument("paths", nargs="*", default=None,
                    help="files or directories to analyze (default: rl_tpu/)")
    ap.add_argument("--baseline", default=os.path.join(REPO, DEFAULT_BASELINE))
    ap.add_argument("--no-baseline", action="store_true",
                    help="report every finding; no suppression, no gating exit code")
    ap.add_argument("--rule", action="append", default=None,
                    help="restrict to a rule id (repeatable)")
    ap.add_argument("--list", action="store_true",
                    help="also print suppressed findings (with their reasons)")
    ap.add_argument("--ir", action="store_true",
                    help="compile the rl_tpu.compile.auditset programs through a "
                         "fresh executable store and gate the R101-R105 IR rules")
    ap.add_argument("--diff", metavar="REV", default=None,
                    help="lint only files changed vs REV; the IR set runs (with "
                         "the persistent store, so unchanged programs skip) only "
                         "when IR-sensitive modules changed")
    ap.add_argument("--strict", action="store_true",
                    help="stale baseline suppressions fail the gate (exit 1)")
    ap.add_argument("--write-baseline", action="store_true",
                    help="add current unsuppressed findings to the baseline")
    ap.add_argument("--reason", default="TODO: triage",
                    help="reason recorded for --write-baseline additions")
    ap.add_argument("--json", default=None, help="dump findings as JSON to a file")
    ap.add_argument("--artifact", default=None,
                    help="write the committed summary artifact (e.g. RLINT_pr15.json)")
    args = ap.parse_args(argv)
    paths = args.paths or [os.path.join(REPO, "rl_tpu")]

    run_ast = True
    run_the_ir = args.ir
    fresh_store = True
    diff_scope: set | None = None
    if args.diff is not None:
        changed = changed_files(args.diff)
        py = [
            p for p in changed
            if p.endswith(".py") and p.startswith("rl_tpu/") and
            os.path.exists(os.path.join(REPO, p))
        ]
        if py:
            # the call-graph index must stay PACKAGE-wide even for a scoped
            # run: analyzing one file alone changes unique-method-name call
            # resolution (a method unique within the file but ambiguous in
            # the package would grow a hot edge a full run never has), so
            # only the *reporting* is scoped to the changed files
            diff_scope = set(py)
        else:
            run_ast = False
        ir_hit = sorted(
            p for p in changed
            if p.endswith(".py") and p.startswith(IR_SENSITIVE)
        )
        if ir_hit:
            run_the_ir = True
            fresh_store = False  # unchanged fingerprints reload + skip audit
            print(f"rlint: --diff {args.diff}: {len(py)} changed file(s), "
                  f"IR set re-runs ({ir_hit[0]}{' …' if len(ir_hit) > 1 else ''})")
        else:
            print(f"rlint: --diff {args.diff}: {len(py)} changed file(s), "
                  "no IR-sensitive modules touched")

    findings = analyze_paths(paths, rules=args.rule, root=REPO) if run_ast else []
    if diff_scope is not None:
        findings = [f for f in findings if f.file in diff_scope]

    ir_auditor = None
    ir_status: dict = {}
    if run_the_ir and (args.rule is None or any(r in IR_RULES for r in args.rule)):
        ir_auditor, ir_status = run_ir(
            "" if args.no_baseline else args.baseline, fresh_store=fresh_store
        )
        ir_findings = ir_auditor.findings()
        if args.rule is not None:
            ir_findings = [f for f in ir_findings if f.rule in args.rule]
        findings = findings + sorted(
            ir_findings, key=lambda f: (f.file, f.line, f.rule)
        )
        failures = {k: v for k, v in ir_status.items() if v != "ok"}
        for name, why in failures.items():
            print(f"rlint: error: IR audit target {name!r}: {why}", file=sys.stderr)
        print(f"rlint: IR set: {ir_auditor.programs_audited()} program(s) audited, "
              f"{len(ir_findings)} finding(s)")

    if args.no_baseline:
        for f in findings:
            print(f.format())
        print(f"rlint: {len(findings)} finding(s), baseline not applied")
        return 0

    baseline = Baseline.load(args.baseline)
    unsup, sup, stale = baseline.split(findings)
    # staleness is only meaningful for files/programs this run actually
    # analyzed: a --diff scoped to three files must not damn every other
    # suppression, and IR-program entries are only live when --ir ran
    if args.diff is not None:
        scope = diff_scope or set()
        stale = [
            s for s in stale
            if s.get("file") in scope
            or (ir_auditor is not None
                and str(s.get("file", "")).startswith("program:"))
        ]
    elif ir_auditor is None:
        stale = [s for s in stale if not str(s.get("file", "")).startswith("program:")]
    # an IR-set builder crash means programs went unaudited — that must
    # not read as "clean"
    ir_broken = any(v != "ok" for v in ir_status.values())

    if args.write_baseline:
        for f in unsup:
            baseline.add(f, args.reason)
        baseline.save(args.baseline)
        print(f"rlint: baseline updated with {len(unsup)} suppression(s) -> {args.baseline}")
        unsup, sup, stale = baseline.split(findings)

    if args.list:
        reasons = {s["fingerprint"]: s.get("reason", "") for s in baseline.suppressions}
        for f in sup:
            print(f"SUPPRESSED {f.format()}  reason: {reasons.get(f.fingerprint, '?')}")
    for f in unsup:
        print(f.format())
    for s in stale:
        sev = "error" if args.strict else "warning"
        print(
            f"rlint: {sev}: stale suppression {s.get('fingerprint')} "
            f"({s.get('rule')} {s.get('file')} [{s.get('qualname')}]) — "
            "the finding no longer fires; remove it from the baseline",
            file=sys.stderr,
        )

    if args.json:
        with open(args.json, "w") as f:
            json.dump([x.to_dict() for x in findings], f, indent=2)
            f.write("\n")
    if args.artifact:
        art = build_artifact(findings, unsup, sup, baseline, paths,
                             ir_auditor=ir_auditor, ir_status=ir_status)
        with open(args.artifact, "w") as f:
            json.dump(art, f, indent=2, sort_keys=False)
            f.write("\n")
        print(f"rlint: artifact -> {args.artifact}")

    n_sup = len(sup)
    print(
        f"rlint: {len(findings)} finding(s): {len(unsup)} unsuppressed, "
        f"{n_sup} suppressed, {len(stale)} stale suppression(s)"
    )
    if unsup or ir_broken:
        return 1
    if args.strict and stale:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
