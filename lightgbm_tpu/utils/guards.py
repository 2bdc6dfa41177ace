"""Non-finite guard rails (docs/ROBUSTNESS.md).

Two layers keep NaN/inf out of a boosting run without costing the hot
path anything:

1. **Boundary validation** — labels, weights and init_score are checked
   once, host-side, at ``Dataset`` construction (:func:`validate_finite`).
   O(N) numpy on data the host already holds; a poisoned target fails in
   milliseconds with the offending row index instead of 2000 silently
   constant trees later.

2. **Device-side training guards** — gradients/hessians/split stats can
   still go non-finite mid-run (custom objectives, fp overflow).  The
   guard signal is computed ON DEVICE inside work that is already
   dispatched (O(num_leaves) reductions folded into the growers' round
   bodies / iteration epilogue) and is only PULLED at points where the
   host syncs anyway: the growers accumulate a first-bad-iteration scalar
   checked at the existing deferred sync points (the %32 finish probe,
   eval, flush, save).  Detection can
   therefore lag the corruption by up to 32 iterations on the fastest
   path — the error is ROUND-STAMPED with the iteration the corruption
   entered, which is what makes the lag acceptable.

Host-side ``np.isnan(...)``/``float(...)`` pulls on per-round tensors
inside grower loops are the anti-pattern these layers exist to prevent;
jaxlint R7 (lightgbm_tpu/analysis/rules.py) flags them statically.
"""

from __future__ import annotations

import numpy as np


class NonFiniteError(ValueError):
    """Non-finite data reached training — raised by the boundary
    validators and the device-side guard rails.  Subclasses ValueError so
    generic callers treat it as bad input, which it is."""


def validate_finite(name: str, arr, where: str = "Dataset") -> None:
    """Raise :class:`NonFiniteError` if ``arr`` (None allowed) contains
    NaN/inf, with the count and first offending index in the message."""
    if arr is None:
        return
    a = np.asarray(arr, dtype=np.float64)
    finite = np.isfinite(a)
    if finite.all():
        return
    bad = int(a.size - np.count_nonzero(finite))
    first = int(np.argmin(finite.ravel()))
    kind = "NaN" if np.isnan(a.ravel()[first]) else "inf"
    raise NonFiniteError(
        f"{where} {name} contains {bad} non-finite value(s) "
        f"(first: {kind} at flat index {first} of {a.size}). "
        f"Training on non-finite {name} values silently corrupts every "
        "subsequent boosting round — clean or impute them before "
        "constructing the Dataset (docs/ROBUSTNESS.md). Non-finite "
        "FEATURE values are fine; they take the missing-value path.")
