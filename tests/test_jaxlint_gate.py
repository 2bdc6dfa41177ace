"""Tier-1 jaxlint gate: the analyzer over the whole package must report
ZERO unsuppressed findings, and every suppression must carry a reason —
the jit-purity analogue of the reference keeping its CI sanitizer builds
green (SURVEY §6.2).  A new host sync, per-call jit, use-after-donate,
axis-name typo or trace-impurity anywhere in lightgbm_tpu/ fails this test
at PR time instead of surfacing as benchmark archaeology."""

import functools
from pathlib import Path

import lightgbm_tpu
from lightgbm_tpu.analysis import RULES, run
from lightgbm_tpu.analysis.__main__ import main as jaxlint_main

PKG_DIR = Path(lightgbm_tpu.__file__).resolve().parent


@functools.lru_cache(maxsize=2)
def _package_report(strict_pragmas=False):
    # a whole-package lint walk costs ~10s; the source tree cannot change
    # mid-session, so the gate tests share one Report per pragma mode
    return run([PKG_DIR], strict_pragmas=strict_pragmas)


def test_package_has_zero_unsuppressed_findings():
    report = _package_report()
    assert report.ok, "new jaxlint findings (fix or pragma with a reason):\n" \
        + "\n".join(f.format() for f in report.findings)


def test_every_suppression_carries_a_reason():
    report = _package_report()
    for finding, pragma in report.suppressed:
        assert pragma.reason.strip(), f"reasonless pragma hides {finding.format()}"


def test_known_intentional_suppressions_are_still_needed():
    """The suppressed set documents real, intentional exceptions: the
    fused-step factory pragmas in gbdt.py remain, and the rounds grower
    the cells run needs none (this test pins the floor, not the exact
    set)."""
    report = _package_report()
    files = {Path(f.file).name for f, _ in report.suppressed}
    assert "gbdt.py" in files  # cached fused-step/eval jit factories (R2)
    assert "treegrow_fast.py" not in files, (
        "the rounds grower has no per-round host pull to suppress — a "
        "suppression there means one came in")


def test_all_rules_are_registered():
    assert {"R1", "R2", "R3", "R4", "R5", "R6", "R7", "R8", "R9", "R10",
            "R11", "R12", "R13", "R14", "R15", "R16", "R17", "R19",
            "R20", "R21", "L1", "L2", "L3", "L4", "L5"} <= set(RULES)


def test_package_has_zero_stale_pragmas():
    """Every suppression in the tree still earns its keep: a pragma whose
    line no longer triggers the named rule (like the per-round R1 pragma
    retired in round 7) must be deleted, not accumulated."""
    report = _package_report(strict_pragmas=True)
    stale = [f for f in report.findings if f.rule == "P1"]
    assert not stale, "stale pragmas (delete the retired suppressions):\n" \
        + "\n".join(f.format() for f in stale)


def test_cli_exit_codes():
    assert jaxlint_main([str(PKG_DIR)]) == 0
    assert jaxlint_main(["--list-rules"]) == 0
    assert jaxlint_main(["/no/such/path"]) == 2
    assert jaxlint_main([str(PKG_DIR), "--rules", "R99"]) == 2


def test_cli_flags_a_dirty_tree(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "import jax\nimport numpy as np\n\n"
        "@jax.jit\ndef f(x):\n    return np.asarray(x)\n")
    assert jaxlint_main([str(bad)]) == 1
