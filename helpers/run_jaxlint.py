#!/usr/bin/env python
"""Standalone static-analysis runner for pre-commit use — ALL layers:

    python helpers/run_jaxlint.py                  # AST lint + locks + jaxpr
    python helpers/run_jaxlint.py --ast-only       # R-rules only, no JAX
    python helpers/run_jaxlint.py --locks-only     # L-rules only, no JAX
    python helpers/run_jaxlint.py --show-suppressed
    python helpers/run_jaxlint.py lightgbm_tpu/ops --rules R1,R3
    python helpers/run_jaxlint.py --jaxpr --contract predict_warm_single

Layer 1 (jaxlint, rules R1-R17) scans source ASTs and runs without
touching JAX device state.  Layer 2 (the concurrency layer, rules L1-L5,
analysis/locks.py) builds the whole-package lock model and checks lock
ordering, blocking calls under locks, guard discipline, Condition.wait
predicates, and thread lifecycle — also pure AST, also no JAX.  Layer 3
(jaxpr audit, rules J1-J6) traces the registered flagship executables
hermetically on the host CPU and verifies their IR contracts
(analysis/contracts.py).  A default full scan runs layers 1+2 in
one pass (same rule registry) and piggybacks layer 3 behind them;
``--ast-only`` / ``--locks-only`` scope to one AST-side layer, and
``--list-rules``, ``--rules`` subsets, and explicit sub-package paths
keep the run scoped the same way (a scoped question gets a scoped
answer; the audit is whole-package by nature and costs real tracing
time).  Exit code 0 = clean (the contract tests/test_jaxlint_gate.py +
tests/test_lock_lint.py + tests/test_jaxpr_audit.py enforce in tier-1),
1 = findings, 2 = bad usage.
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from lightgbm_tpu.analysis.__main__ import main  # noqa: E402
from lightgbm_tpu.analysis.core import RULES  # noqa: E402

if __name__ == "__main__":
    argv = sys.argv[1:]
    ast_only = "--ast-only" in argv
    locks_only = "--locks-only" in argv
    argv = [a for a in argv if a not in ("--ast-only", "--locks-only")]
    jaxpr_flags = ("--jaxpr", "--contract", "--list-contracts")
    jaxpr_only = any(a.startswith(f) for a in argv for f in jaxpr_flags)
    if (ast_only or locks_only) and jaxpr_only:
        print("error: --ast-only/--locks-only contradict --jaxpr/"
              "--contract/--list-contracts", file=sys.stderr)
        sys.exit(2)
    if ast_only and locks_only:
        print("error: --ast-only contradicts --locks-only (a default run "
              "covers both layers)", file=sys.stderr)
        sys.exit(2)
    # the jaxpr layer only piggybacks on FULL default scans: an
    # informational run (--list-rules) or a scoped one (--rules,
    # --ast-only/--locks-only, explicit sub-package paths) asked a
    # narrow question, and silently paying the whole audit behind it
    # would be a surprise
    narrow = any(a.startswith(("--rules", "--list-rules")) for a in argv)
    scoped = any(not a.startswith("-") for a in argv)
    if locks_only:
        if narrow:
            print("error: --locks-only contradicts --rules/--list-rules",
                  file=sys.stderr)
            sys.exit(2)
        argv = ["--locks"] + argv
    elif ast_only:
        if narrow:
            print("error: --ast-only contradicts --rules/--list-rules",
                  file=sys.stderr)
            sys.exit(2)
        # scope to the R-layer by explicit rule selection: the L rules
        # share the registry, so a bare default run covers both
        ast_rules = ",".join(sorted(
            rid for rid, rule in RULES.items() if rule.layer == "ast"))
        argv = ["--rules", ast_rules] + argv
    if not scoped:
        pkg = Path(__file__).resolve().parent.parent / "lightgbm_tpu"
        argv = ([] if jaxpr_only else [str(pkg)]) + argv
    if jaxpr_only:
        sys.exit(main(argv))
    rc = main(argv)
    if not (ast_only or locks_only or narrow or scoped):
        # layer 3 shares the exit-code contract; forward the flag it
        # understands
        passthru = [a for a in argv if a == "--show-suppressed"]
        rc = max(rc, main(["--jaxpr"] + passthru))
    sys.exit(rc)
