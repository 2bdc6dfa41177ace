"""Process-wide graceful kernel degradation.

The Pallas histogram kernel is the TPU hot path, but its failure mode is
all-or-nothing: a Mosaic compile rejection or a kernel launch failure
kills the training run even though a numerically identical XLA
formulation exists (ops/histogram.py onehot/scatter).  Before this
module the only way around a broken kernel was a manual env var set by a
human after the crash.

Now the dispatchers catch a Pallas failure ONCE, log it through
utils/log.py, and permanently fall back to the XLA path for the rest of
the process:

* :func:`available` is consulted where the ``use_pallas`` statics are
  decided (grower entry points), so later traces compile without the
  broken kernel;
* :func:`disable` records the reason and logs a single warning;
* :func:`is_pallas_failure` classifies an exception so real errors
  (shape bugs, OOM on the XLA side, user errors) still propagate.

The registry is deliberately process-global and never re-enables: a
kernel that failed to compile once will fail again, and flapping between
paths would retrace per tree.  ``reset()`` exists for tests.
"""

from __future__ import annotations

import threading
from typing import Dict, Optional

from . import locktrace as _lt
from .log import log_warning

# registry keys
HIST = "hist_pallas"
# Retired keys: the kernels they named are deleted and nothing disables
# them.  chipbench/harness/stages.py (not a non-benchmark PR's to edit)
# and chip_smoke.py still list them in their no-fallback checks, so the
# names stay until a benchmark PR drops them there.
PARTITION = "partition"
ROUND = "round"

_lock = _lt.lock("degrade.registry")
_disabled: Dict[str, str] = {}

# substrings that identify a Pallas/Mosaic kernel failure in exception
# text (case-insensitive).  Deliberately narrow: an arbitrary XLA error
# must NOT trigger a silent fallback (bare "custom_call" would also match
# pure_callback/io_callback failures — excluded).
_SIGNATURES = ("mosaic", "pallas", "tpu custom call", "kernel compile")

# Python-level errors are bugs in this repo (a renamed jax API, a wrong
# argument), never a kernel the compiler refused — whatever their text
# says: "module 'jax.experimental.pallas.tpu' has no attribute ..." names
# Pallas and is not a Pallas failure.  They always propagate.
_NEVER_KERNEL_FAILURES = (AttributeError, TypeError, ImportError, NameError)


def available(feature: str) -> bool:
    with _lock:
        return feature not in _disabled


def disable(feature: str, reason: str) -> None:
    """Permanently (for this process) route ``feature`` to its XLA
    fallback.  Logs once; repeat calls are no-ops."""
    with _lock:
        if feature in _disabled:
            return
        _disabled[feature] = reason
    from ..obs import metrics as _obs  # lazy: keep import graph unchanged

    _obs.counter("degrade_disabled_total").inc()
    _obs.event("degrade", feature=feature, reason=reason[:200])
    log_warning(
        f"Pallas kernel {feature!r} failed and is disabled for this "
        f"process; falling back to the XLA path permanently ({reason}). "
        "See docs/ROBUSTNESS.md.")


def disabled_reason(feature: str) -> Optional[str]:
    with _lock:
        return _disabled.get(feature)


def is_pallas_failure(exc: BaseException) -> bool:
    """True when ``exc`` looks like a Pallas/Mosaic kernel failure (or an
    injected one from utils/faults.py) rather than a generic error."""
    from .faults import InjectedFault

    if isinstance(exc, InjectedFault):
        return exc.site.startswith("pallas")
    if isinstance(exc, _NEVER_KERNEL_FAILURES):
        return False
    text = f"{type(exc).__name__}: {exc}".lower()
    return any(sig in text for sig in _SIGNATURES)


def describe(exc: BaseException, limit: int = 200) -> str:
    return f"{type(exc).__name__}: {str(exc)[:limit]}"


def run_with_fallback(feature: str, primary, fallback, *,
                      fault_site: Optional[str] = None,
                      surface_errors: bool = False):
    """THE catch-once/degrade-forever pattern, in one place.

    Runs ``primary()`` while ``feature`` is available; a classified
    Pallas failure (or an armed ``fault_site`` injection) disables the
    feature and runs ``fallback()``.  Non-kernel errors always propagate;
    ``surface_errors`` propagates EVERYTHING (correctness harnesses like
    Pallas interpret mode must not silently fall back).  Dispatchers call
    this at trace time (fallback lands inside the trace); grower entry
    wrappers call it at the host level for compile/execute-time failures."""
    if available(feature):
        try:
            if fault_site is not None:
                from . import faults

                faults.maybe_fail(fault_site)
            return primary()
        except Exception as e:  # noqa: BLE001 — classified below
            if surface_errors or not is_pallas_failure(e):
                raise
            disable(feature, describe(e))
    return fallback()


def reset() -> None:
    """Re-enable everything (tests only)."""
    with _lock:
        _disabled.clear()
