"""CLI: ``python -m lightgbm_tpu.analysis [paths...]``.

Three layers, one entry point (docs/ANALYSIS.md):

* default — **jaxlint**, the AST pass over source (rules R1-R17 plus the
  concurrency rules L1-L5).  Runs without touching JAX device state.
  Stale pragmas (a ``disable=Rn`` whose line no longer triggers Rn) warn
  by default; ``--strict-pragmas`` promotes them to findings.
* ``--locks`` — the **concurrency layer** alone (rules L1-L5 over the
  whole-package lock model, analysis/locks.py): lock-order inversions,
  blocking calls under locks, unguarded shared mutations, predicate-free
  Condition.waits, orphan threads.
* ``--jaxpr`` — the **jaxpr executable audit** (rules J1-J6 over the
  registered contracts, analysis/contracts.py).  Traces the flagship
  executables hermetically on the host CPU; ``--contract NAME`` selects
  a subset (repeatable).

Exit status 0 when no unsuppressed findings, 1 otherwise, 2 on bad usage
— so the pytest gates (tests/test_jaxlint_gate.py, tests/
test_jaxpr_audit.py) and pre-commit runs (helpers/run_jaxlint.py) share
one contract.
"""

from __future__ import annotations

import argparse
import sys
from pathlib import Path

from .core import RULES, run
from . import rules  # noqa: F401
from . import locks  # noqa: F401  — registers L1-L5


def _main_jaxpr(args) -> int:
    from . import jaxpr_audit
    from .contracts import CONTRACTS

    if args.list_contracts:
        for name in sorted(CONTRACTS):
            c = CONTRACTS[name]
            print(f"{name}  [{len(c.collectives)} collective(s), "
                  f"{len(c.donated_args)} donated arg(s)]")
            print(f"      {c.description}")
        for rid in sorted(jaxpr_audit.JAXPR_RULES):
            print(f"{rid}  {jaxpr_audit.JAXPR_RULES[rid]}")
        return 0

    names = list(args.contract) if args.contract else None
    if names:
        unknown = [n for n in names if n not in CONTRACTS]
        if unknown:
            print(f"error: unknown contracts {unknown}; known: "
                  f"{sorted(CONTRACTS)}", file=sys.stderr)
            return 2
    report = jaxpr_audit.run_jaxpr_audit(names)
    for f in report.findings:
        print(f.format())
    if args.show_suppressed:
        for f, reason in report.waived:
            print(f"[waived: {reason}] {f.format()}")
    for r in report.results:
        coll = r.detail.get("collectives")
        extra = f", collectives: {len(coll)}" if coll is not None else ""
        print(f"jaxpr-audit: {r.name}: "
              f"{'ok' if r.ok else f'{len(r.findings)} finding(s)'}"
              f"{extra}", file=sys.stderr)
    n, w = len(report.findings), len(report.waived)
    print(f"jaxpr-audit: {n} finding(s), {w} waived", file=sys.stderr)
    return 0 if report.ok else 1


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.analysis",
        description="jaxlint: JAX/TPU purity & recompile static analysis "
                    "(AST layer R1-R14; --jaxpr: traced-IR audit J1-J6)")
    parser.add_argument("paths", nargs="*", default=None,
                        help="files/directories to scan (default: the "
                             "installed lightgbm_tpu package)")
    parser.add_argument("--rules", default=None,
                        help="comma-separated rule ids (default: all)")
    parser.add_argument("--show-suppressed", action="store_true",
                        help="also list pragma-suppressed (or contract-"
                             "waived) findings")
    parser.add_argument("--list-rules", action="store_true",
                        help="print the rule catalogue and exit")
    parser.add_argument("--strict-pragmas", action="store_true",
                        help="promote stale pragmas (suppressions whose "
                             "line no longer triggers the named rule) "
                             "from warnings to findings")
    parser.add_argument("--locks", action="store_true",
                        help="run only the concurrency layer (rules L1-L5 "
                             "over the package lock model)")
    parser.add_argument("--jaxpr", action="store_true",
                        help="run the jaxpr executable audit (J1-J6 over "
                             "the registered contracts) instead of the "
                             "AST layer")
    parser.add_argument("--contract", action="append", metavar="NAME",
                        help="audit only this contract (repeatable; "
                             "implies --jaxpr)")
    parser.add_argument("--list-contracts", action="store_true",
                        help="print the contract + J-rule catalogue and "
                             "exit (implies --jaxpr)")
    args = parser.parse_args(argv)

    if args.locks and (args.jaxpr or args.contract or args.list_contracts
                       or args.rules):
        print("error: --locks selects the L1-L5 layer and contradicts "
              "--jaxpr/--contract/--list-contracts/--rules",
              file=sys.stderr)
        return 2

    if args.jaxpr or args.contract or args.list_contracts:
        if args.paths:
            # the audit runs REGISTERED contracts, not source paths — a
            # path here means the caller expects a scoped scan it would
            # not get; fail loudly like other bad usage
            print("error: --jaxpr audits registered contracts and takes "
                  "no paths (use --contract NAME to select)",
                  file=sys.stderr)
            return 2
        return _main_jaxpr(args)

    if args.list_rules:
        for rid in sorted(RULES):
            rule = RULES[rid]
            print(f"{rid}  {rule.name}")
            for line in rule.doc.splitlines():
                print(f"      {line.strip()}")
        return 0

    if args.paths:
        roots = [Path(p) for p in args.paths]
    else:
        roots = [Path(__file__).resolve().parent.parent]
    for r in roots:
        if not r.exists():
            print(f"error: no such path: {r}", file=sys.stderr)
            return 2

    rule_ids = None
    if args.locks:
        rule_ids = [rid for rid, rule in RULES.items()
                    if rule.layer == "locks"]
    elif args.rules:
        rule_ids = [r.strip() for r in args.rules.split(",") if r.strip()]
        unknown = [r for r in rule_ids if r not in RULES]
        if unknown:
            print(f"error: unknown rules {unknown}; known: {sorted(RULES)}",
                  file=sys.stderr)
            return 2

    report = run(roots, rule_ids, strict_pragmas=args.strict_pragmas)
    for f in report.findings:
        print(f.format())
    if args.show_suppressed:
        for f, p in report.suppressed:
            print(f"[suppressed: {p.reason}] {f.format()}")
    if report.stale and not args.strict_pragmas:
        # default-on warning: retired pragmas must not accumulate
        for f in report.stale:
            print(f"warning: {f.format()}", file=sys.stderr)
    n, s = len(report.findings), len(report.suppressed)
    print(f"jaxlint: {n} finding(s), {s} suppressed, "
          f"{len(report.stale)} stale pragma(s)", file=sys.stderr)
    return 0 if report.ok else 1


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:  # e.g. `... | head` closed the pipe
        sys.exit(0)
