"""CLI for the contract auditor: ``python -m repro.analysis``.

Default run executes all three prongs — the AST contract lint
(SIM001..SIM009) over ``src/repro`` and ``benchmarks/``, the trace-time
launch audit (SIM101..SIM105) over the sharded backend on one chip and on
four, and the runtime conservation audit (SIM201..SIM204) of the timeline
accounting — applies ``baseline.toml`` and prints every finding.
``--check`` turns non-baselined findings into a nonzero exit (the CI
gate); ``--write-baseline`` regenerates the allowlist from the current
tree (reasons of already-pinned entries are preserved); ``--github``
additionally emits ``::error`` problem-matcher annotations and
``--json-out`` dumps the full finding set for upload as a CI artifact.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

from .baseline import apply_baseline, load_baseline, write_baseline
from .contracts import run_contracts
from .findings import Finding

DEFAULT_BASELINE = Path(__file__).resolve().parent / "baseline.toml"
REPO_ROOT = Path(__file__).resolve().parents[3]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m repro.analysis",
        description="SiM backend-contract auditor: AST lint (SIM001..009) "
                    "+ jaxpr launch audit (SIM101..105) + runtime "
                    "conservation audit (SIM201..204).")
    p.add_argument("--check", action="store_true",
                   help="exit nonzero when any non-baselined finding exists")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="emit findings as JSON instead of text")
    p.add_argument("--json-out", type=Path, default=None,
                   help="additionally dump the finding sets as JSON to this "
                        "file (CI artifact)")
    p.add_argument("--github", action="store_true",
                   help="emit GitHub ::error problem-matcher annotations "
                        "for new findings")
    p.add_argument("--write-baseline", action="store_true",
                   help="regenerate the baseline from the current findings "
                        "(keeps reasons of entries that are still hit)")
    p.add_argument("--baseline", type=Path, default=DEFAULT_BASELINE,
                   help="allowlist path (default: the committed "
                        "src/repro/analysis/baseline.toml)")
    p.add_argument("--root", type=Path, default=REPO_ROOT,
                   help="repository root (default: inferred from package)")
    p.add_argument("--paths", type=Path, nargs="*", default=None,
                   help="lint these files/dirs instead of src/repro + "
                        "benchmarks")
    p.add_argument("--rules", nargs="*", default=None,
                   help="restrict the lint to these rule IDs (e.g. SIM001)")
    p.add_argument("--no-lint", action="store_true",
                   help="skip the AST contract lint")
    p.add_argument("--no-audit", action="store_true",
                   help="skip the trace-time launch audit")
    p.add_argument("--no-hlo", action="store_true",
                   help="skip the audit's compiled-HLO byte cross-check")
    p.add_argument("--no-conservation", action="store_true",
                   help="skip the runtime conservation audit (SIM201..204)")
    return p


def _select_rules(ids):
    from .rules import RULES_BY_ID
    unknown = [r for r in ids if r not in RULES_BY_ID]
    if unknown:
        raise SystemExit(f"unknown rule IDs {unknown}; "
                         f"known: {sorted(RULES_BY_ID)}")
    return [RULES_BY_ID[r] for r in ids]


def _default_paths(root: Path) -> list[Path]:
    paths = [root / "src" / "repro"]
    bench = root / "benchmarks"
    if bench.is_dir():
        paths.append(bench)
    return paths


def collect_findings(args) -> list[Finding]:
    findings: list[Finding] = []
    if not args.no_lint:
        rules = _select_rules(args.rules) if args.rules else None
        paths = args.paths if args.paths is not None \
            else _default_paths(args.root)
        findings.extend(run_contracts(args.root, paths=paths, rules=rules))
    if not args.no_audit:
        from .launch_audit import run_audit
        findings.extend(run_audit(hlo=not args.no_hlo))
    if not args.no_conservation:
        from .conservation import run_conservation
        findings.extend(run_conservation())
    return findings


def _github_annotation(f: Finding) -> str:
    """One ::error problem-matcher line per new finding.  Audit findings
    (path ``audit:sharded``) have no source location; they annotate the
    workflow without file/line coordinates."""
    msg = f"{f.rule} [{f.slug}] {f.symbol}: {f.message or f.slug}"
    msg = msg.replace("%", "%25").replace("\r", "%0D").replace("\n", "%0A")
    if f.path.startswith("audit:"):
        return f"::error title={f.rule}::{msg}"
    return (f"::error file={f.path},line={max(f.line, 1)},"
            f"title={f.rule}::{msg}")


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    findings = collect_findings(args)
    entries = load_baseline(args.baseline)

    if args.write_baseline:
        reasons = {e.key(): e.reason for e in entries if e.reason}
        write_baseline(args.baseline, findings, reasons)
        print(f"wrote {len(findings)} accepted findings to {args.baseline}")
        return 0

    new, accepted, stale = apply_baseline(findings, entries)

    payload = {
        "new": [vars(f) for f in new],
        "accepted": [vars(f) for f in accepted],
        "stale": [vars(e) for e in stale],
    }
    if args.json_out is not None:
        args.json_out.write_text(json.dumps(payload, indent=2) + "\n")
    if args.as_json:
        print(json.dumps(payload, indent=2))
    else:
        for f in new:
            print(f.format())
        for e in stale:
            print(f"stale baseline entry (no longer found): "
                  f"{e.rule} {e.path} {e.symbol} [{e.slug}]",
                  file=sys.stderr)
        print(f"{len(new)} new finding(s), {len(accepted)} baselined, "
              f"{len(stale)} stale baseline entr(ies)", file=sys.stderr)
    if args.github:
        for f in new:
            print(_github_annotation(f))

    if args.check and new:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
