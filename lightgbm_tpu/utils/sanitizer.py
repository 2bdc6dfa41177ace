"""Runtime retrace/donation/dispatch sanitizer.

The static pass (``lightgbm_tpu/analysis``, jaxlint R2/R6) catches recompile
and dispatch-structure hazards visible in the AST; *varying* static
arguments, shape drift, and the actual per-round dispatch/sync traffic are
runtime properties.  This module turns them into executable assertions: a
process-global ``jax.monitoring`` listener counts every jaxpr trace and every
XLA backend compile, and :class:`CompileCounter` exposes deltas so a test can
pin "N boosting rounds at fixed shape compile exactly once".

Dispatch side (round 7): host round loops that dispatch jitted work record
each dispatch through :func:`record_dispatch` and route every host read of
device data through :func:`sync_pull` (a BLOCKING pull, which stalls the
device queue) or the :func:`async_pull_start`/:func:`async_pull_result`
pair (a pipelined read that overlaps device compute and never stalls the
device queue).  :class:`DispatchCounter` snapshots all of it, so "a warm
predict is exactly ONE dispatch and ONE accounted sync" is an executable
invariant (tests/test_predict_budget.py, tests/test_retrace.py), not
benchmark archaeology — :meth:`DispatchCounter.assert_round_budget` is
the gate.

Counting is cumulative and process-wide — the listener is installed once and
never removed (``jax.monitoring`` has no unregister; ``clear_event_listeners``
would nuke listeners we don't own).  Counters snapshot on ``__enter__`` and
report deltas, so nesting and interleaving are safe.

Donation side: XLA silently ignores ``donate_argnums`` on platforms without
buffer aliasing (CPU warns and copies), so "the step donates its state"
is only true where donation is supported.  :func:`donation_consumed`
reports whether a donated input was actually invalidated, and
:func:`assert_donation_consumed` asserts it on platforms that support
donation while degrading to a no-op where XLA ignores it — tests stay green
on the CPU tier-1 mesh and bite on device.
"""

from __future__ import annotations

import threading
from typing import Iterable, Optional

import jax
import numpy as np

from ..obs import metrics as _obs
from . import locktrace as _lt

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
# jax times COMPILE_EVENT around compile_or_get_cached, so a program loaded
# from the persistent cache counts as a compile too; this event marks those
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = _lt.lock("sanitizer.counts")
_counts = {"compiles": 0, "traces": 0, "dispatches": 0, "host_syncs": 0,
           "async_resolves": 0, "cache_hits": 0}
_installed = False


def _obs_collect() -> dict:
    """Snapshot-time bridge into the metrics registry (docs/OBSERVABILITY.md):
    this module stays the single authoritative ledger — counting here twice
    per dispatch would tax the hot path for nothing — and every metrics
    snapshot reads it once through this collector.  Process-cumulative."""
    with _lock:
        c = dict(_counts)
    return {"counters": {
        "device_compiles_total": c["compiles"],
        "device_traces_total": c["traces"],
        "device_dispatches_total": c["dispatches"],
        "device_host_syncs_total": c["host_syncs"],
        "device_async_resolves_total": c["async_resolves"],
    }}


_obs.register_collector("sanitizer", _obs_collect)


def _listener(event: str, duration: float, **_kw) -> None:  # noqa: ARG001
    if event == COMPILE_EVENT:
        with _lock:
            _counts["compiles"] += 1
    elif event == TRACE_EVENT:
        with _lock:
            _counts["traces"] += 1


def _event_listener(event: str, **_kw) -> None:
    if event == CACHE_HIT_EVENT:
        with _lock:
            _counts["cache_hits"] += 1


def _install() -> None:
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    jax.monitoring.register_event_duration_secs_listener(_listener)
    jax.monitoring.register_event_listener(_event_listener)


def compile_totals() -> dict:
    """Cumulative (process-lifetime) compile/trace counts since install."""
    _install()
    with _lock:
        return dict(_counts)


class RetraceError(AssertionError):
    """A jit compiled/retraced more than the test's contract allows."""


class CompileCounter:
    """Context manager counting XLA backend compiles and jaxpr traces in the
    enclosed block.

    >>> with CompileCounter() as c:
    ...     train_some_rounds()
    >>> assert c.compiles == 0  # everything was warm

    ``compiles`` counts backend (HLO -> executable) compiles: the expensive
    event, and the one "exactly one compile per (shape, dtype) config" pins.
    A program the persistent compile cache served is counted there too and
    also in ``cache_hits``: ``compiles - cache_hits`` were really compiled.
    ``traces`` counts jaxpr traces: cheaper, but a per-round retrace that
    hits the persistent compile cache still shows up here.
    """

    def __init__(self) -> None:
        self._c0: Optional[int] = None
        self._t0: Optional[int] = None
        self._h0: Optional[int] = None

    def __enter__(self) -> "CompileCounter":
        _install()
        with _lock:
            self._c0 = _counts["compiles"]
            self._t0 = _counts["traces"]
            self._h0 = _counts["cache_hits"]
        return self

    def __exit__(self, *exc) -> None:
        return None

    @property
    def compiles(self) -> int:
        with _lock:
            return _counts["compiles"] - self._c0

    @property
    def traces(self) -> int:
        with _lock:
            return _counts["traces"] - self._t0

    @property
    def cache_hits(self) -> int:
        with _lock:
            return _counts["cache_hits"] - self._h0

    def assert_compiles(self, expected: int, what: str = "block") -> None:
        got = self.compiles
        if got != expected:
            raise RetraceError(
                f"{what}: expected exactly {expected} backend compile(s), "
                f"observed {got} (traces: {self.traces}) — a static arg or "
                "shape is varying per call; see docs/ANALYSIS.md")

    def assert_no_recompile(self, what: str = "block") -> None:
        """The steady-state contract: zero compiles AND zero traces —
        every dispatch in the block hit a warm jit cache."""
        got_c, got_t = self.compiles, self.traces
        if got_c or got_t:
            raise RetraceError(
                f"{what}: expected a warm cache but observed {got_c} "
                f"compile(s) / {got_t} trace(s) — something retraces per "
                "call (varying static arg, new closure identity, or shape "
                "drift); see docs/ANALYSIS.md")


def expect_compiles(expected: int, what: str = "block") -> "_ExpectCompiles":
    """``with expect_compiles(1): ...`` — raises RetraceError on mismatch."""
    return _ExpectCompiles(expected, what)


class _ExpectCompiles(CompileCounter):
    def __init__(self, expected: int, what: str) -> None:
        super().__init__()
        self._expected = expected
        self._what = what

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc_type is None:
            self.assert_compiles(self._expected, self._what)
        return None


# ---------------------------------------------------------------------------
# dispatch / host-sync accounting
# ---------------------------------------------------------------------------

def record_dispatch(n: int = 1) -> None:
    """Count a device dispatch issued by a host driver loop.  Call sites
    are the loop's jitted calls (one call == one XLA execution enqueued,
    which costs host time; docs/NEXT.md round-3 note).

    Honest scope: unlike compiles/traces (measured via jax.monitoring),
    dispatch counting is INSTRUMENTATION-BASED — jax emits no monitoring
    event on warm executions (verified on this toolchain), so an
    uninstrumented second dispatch in a loop is invisible to the runtime
    budget.  The structural guard for that class is static: jaxlint R6
    flags consecutive donated dispatches in round loops, and the sync
    half of the budget (``sync_pull`` vs ``async_pull_*``) covers the
    expensive regression (blocking pulls) by routing EVERY host
    read in the drivers through this module."""
    with _lock:
        _counts["dispatches"] += n


def sync_pull(x):
    """BLOCKING host pull of a device value: the caller stalls until the
    device queue drains to this value (the deeper the pipeline, the
    longer).  Returns the numpy value.  Every counted call in a
    steady-state round loop is a round-trip the loop failed to pipeline —
    the class :meth:`DispatchCounter.assert_round_budget` pins to zero."""
    with _lock:
        _counts["host_syncs"] += 1
    return np.asarray(x)


def async_pull_start(x) -> None:
    """Begin a device->host copy WITHOUT waiting (pipelined read).  Pair
    with :func:`async_pull_result` at least one dispatch later: by then
    the producing computation has retired behind newer queued work, so
    resolving the copy does not stall the device pipeline."""
    getattr(x, "copy_to_host_async", lambda: None)()


def async_pull_result(x):
    """Resolve a read started with :func:`async_pull_start`.  Counted
    separately from blocking syncs: the host may wait here, but the
    device queue keeps executing the already-dispatched rounds, so
    device utilization is unaffected."""
    with _lock:
        _counts["async_resolves"] += 1
    return np.asarray(x)


class BudgetError(AssertionError):
    """A host round loop exceeded its dispatch/sync budget."""


class DispatchCounter(CompileCounter):
    """Context manager counting dispatches and host pulls (plus compiles/
    traces, inherited) in the enclosed block.

    >>> with DispatchCounter() as d:
    ...     bst.predict(X)
    >>> d.assert_round_budget(1, syncs_per_round=1, what="warm predict")

    Per-rank semantics under SPMD: the ledger is per host PROCESS.
    Single-controller, the host's one dispatch of a shard_mapped step IS
    every rank's dispatch, and in-dispatch collectives add neither
    dispatches nor host syncs by construction.  In multi-controller runs
    each process carries its own ledger.
    """

    def __enter__(self) -> "DispatchCounter":
        super().__enter__()
        with _lock:
            self._d0 = _counts["dispatches"]
            self._h0 = _counts["host_syncs"]
            self._a0 = _counts["async_resolves"]
        return self

    @property
    def dispatches(self) -> int:
        with _lock:
            return _counts["dispatches"] - self._d0

    @property
    def host_syncs(self) -> int:
        with _lock:
            return _counts["host_syncs"] - self._h0

    @property
    def async_resolves(self) -> int:
        with _lock:
            return _counts["async_resolves"] - self._a0

    def assert_round_budget(self, rounds: int, *,
                            dispatches_per_round: int = 1,
                            syncs_per_round: int = 0,
                            what: str = "round loop") -> None:
        """The steady-state contract of a fused round loop: exactly
        ``dispatches_per_round`` dispatches and ``syncs_per_round``
        blocking pulls per round across the block."""
        got_d, got_s = self.dispatches, self.host_syncs
        want_d = rounds * dispatches_per_round
        want_s = rounds * syncs_per_round
        if got_d != want_d or got_s != want_s:
            raise BudgetError(
                f"{what}: {rounds} round(s) budgeted "
                f"{want_d} dispatch(es) + {want_s} blocking sync(s), "
                f"observed {got_d} + {got_s} "
                f"(async resolves: {self.async_resolves}) — a phase was "
                "dispatched separately or a host pull crept into the loop; "
                "see docs/ANALYSIS.md (R6)")


# ---------------------------------------------------------------------------
# donation
# ---------------------------------------------------------------------------

def donation_supported() -> bool:
    """Whether the default backend honors donate_argnums (CPU ignores it)."""
    return jax.default_backend() in ("tpu", "gpu")


def donation_consumed(*arrays) -> bool:
    """True when every given donated INPUT buffer was actually invalidated
    by the call it was donated to (``Array.is_deleted``)."""
    return all(getattr(a, "is_deleted", lambda: False)() for a in arrays)


def assert_donation_consumed(arrays: Iterable, what: str = "donated state"
                             ) -> None:
    """Assert donated inputs were consumed — i.e. the donation actually
    took (the donated jit aliased the buffers) AND the caller cannot be
    holding a live reference it might read after the call.  No-op on
    platforms where XLA ignores donation."""
    if not donation_supported():
        return
    arrays = list(arrays)
    if not donation_consumed(*arrays):
        alive = sum(1 for a in arrays
                    if not getattr(a, "is_deleted", lambda: False)())
        raise AssertionError(
            f"{what}: {alive}/{len(arrays)} donated buffer(s) still alive "
            "after the call — donation was dropped (aliasing rejected) or "
            "the state is not threaded linearly (jaxlint R3)")
