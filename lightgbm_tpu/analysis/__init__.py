"""jaxlint — static purity/recompile analysis for the TPU hot paths.

CI analogue of the reference's ASan/UBSan sanitizer builds (SURVEY §6.2),
specialized to the failure modes of a jitted JAX codebase:

====  =======================  =============================================
R1    host-sync-in-hot-path    np.asarray/.item()/float() on device values
                               in traced code or jit-dispatching host loops
R2    recompile-hazard         per-call jax.jit construction; unhashable
                               static-arg literals
R3    use-after-donate         reads of a variable after it was passed in a
                               donate_argnums position
R4    collective-axis-name     psum/all_gather/... axis strings must match
                               the mesh module's declared axis constants
R5    impure-under-jit         Python RNG / time.* / global mutation inside
                               traced functions
...   (R6-R14: see docs/ANALYSIS.md for the full catalogue)
====  =======================  =============================================

A second, trace-level layer lives in :mod:`.jaxpr_audit` +
:mod:`.contracts` (rules J1-J6): it traces the registered flagship
executables hermetically and verifies the collective-free /
all-donated contracts on the jaxpr — properties the AST rules
structurally cannot see.  Import it explicitly (it is not imported here, so
``lightgbm_tpu.analysis`` stays JAX-free for pre-commit use).

A third, concurrency layer lives in :mod:`.locks` (rules L1-L5): it
builds a whole-package lock model (which Lock/RLock/Condition attributes
exist, which ``with`` blocks acquire them, which attributes mutate under
which guards) and pins lock discipline — order inversions, blocking
calls under locks, unguarded shared mutations, predicate-free waits and
orphan threads.  It shares the AST layer's registry, pragma format and
stale-pragma detection; ``--locks`` selects it alone.  Its runtime twin
is :mod:`lightgbm_tpu.utils.locktrace` (witness-graph lock wrappers).

Usage::

    python -m lightgbm_tpu.analysis lightgbm_tpu/            # full package
    python -m lightgbm_tpu.analysis --rules R1,R3 ops/        # subset
    python -m lightgbm_tpu.analysis --strict-pragmas          # stale=fail
    python -m lightgbm_tpu.analysis --jaxpr                   # traced-IR audit
    python -m lightgbm_tpu.analysis --jaxpr --contract predict_warm_single

or from tests::

    from lightgbm_tpu.analysis import run
    report = run([pkg_dir])
    assert report.ok, "\\n".join(f.format() for f in report.findings)

Suppressions are inline pragmas with a mandatory reason::

    info = np.asarray(info_d)  # jaxlint: disable=R1 (the one sync per round)

See docs/ANALYSIS.md for the rule catalogue and how to add a rule.
"""

from .core import (Finding, PackageIndex, Pragma, Report, RULES,
                   register_rule, run)
from . import rules  # noqa: F401  — registers R1-R17 on import
from . import locks  # noqa: F401  — registers the concurrency layer L1-L5

__all__ = ["Finding", "PackageIndex", "Pragma", "Report", "RULES",
           "register_rule", "run"]
