"""Tracing / profiling harness.

Reference: the TIMETAG-gated wall-clock tallies in src/treelearner/*.cpp
(global_timer) and the CLI's per-phase timing logs.  TPU-native analogue:
`jax.profiler` device traces (viewable in TensorBoard/Perfetto) plus a
host-side section timer with the reference's "Time for X: Y s" log style.

Section tallies live in the process-wide metrics registry
(``lightgbm_tpu/obs``) as ``section_seconds.<name>`` histograms — one
thread-safe store shared with the rest of the telemetry layer, replacing
the module-global dicts this module carried before round 10 (they raced
under concurrent sections and were invisible to metrics snapshots).
``log_timings`` reads and (optionally) clears them; they also appear in
every ``metrics_file=`` snapshot and the ``python -m lightgbm_tpu.obs``
dump.
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Dict, Iterator

import jax

from ..obs import metrics as _obs
from ..obs import trace as _trace
from .log import log_info


def _drain_device_queue() -> None:
    """Wait for outstanding device work: a tiny fresh value is enqueued
    behind it and awaited.  On the attached chip ``block_until_ready``
    waits as long as a host pull does (chip_smoke.py, probe 3: 143.8 ms
    against 144.6 ms on a 144 ms job, TPU v5e, PR 21)."""
    jax.block_until_ready(jax.device_put(0.0) + 0)


@contextlib.contextmanager
def device_trace(log_dir: str) -> Iterator[None]:
    """Capture a device trace (XLA ops, Pallas kernels, transfers) for the
    enclosed block; open `log_dir` with TensorBoard or Perfetto.
    TPU analogue of nvprof over the reference's CUDA learner."""
    with jax.profiler.trace(log_dir):
        yield


@contextlib.contextmanager
def annotate(name: str) -> Iterator[None]:
    """Label the enclosed dispatches in device traces
    (jax.profiler.TraceAnnotation)."""
    with jax.profiler.TraceAnnotation(name):
        yield


def _jax_annotation_factory(name: str, attrs: dict):
    """obs/trace.py annotation factory: spans carrying a ``step``/
    ``iteration`` attribute mirror into StepTraceAnnotation (so the
    profiler's step view lines up with boosting iterations), everything
    else into TraceAnnotation."""
    step = attrs.get("step", attrs.get("iteration"))
    if step is not None:
        return jax.profiler.StepTraceAnnotation(name, step_num=int(step))
    return jax.profiler.TraceAnnotation(name)


def install_jax_annotations() -> None:
    """Mirror every context-manager span (obs/trace.py) into jax.profiler
    annotations, lining host spans up with on-chip XLA traces captured via
    :func:`device_trace`.  The obs package itself stays stdlib-only: THIS
    module (which already imports jax) owns the bridge, and it is
    installed automatically when ``LGBMTPU_JAX_PROFILER=1`` — the layers
    that open spans (models/gbdt.py, engine) import this module, so the
    env opt-in needs no further wiring."""
    _trace.set_annotation_factory(_jax_annotation_factory)


if os.environ.get("LGBMTPU_JAX_PROFILER") == "1":
    install_jax_annotations()


@contextlib.contextmanager
def timed_section(name: str, sync: bool = False) -> Iterator[None]:
    """Host wall-clock tally per section (reference: global_timer's
    start/stop pairs).  With sync=True the section first drains outstanding
    device work through the documented host-pull sync, attributing async
    dispatch correctly.  Without sync, the tally measures HOST time only —
    async device work dispatched inside the section may still be in flight
    when it closes (jaxlint R9 flags the raw-perf_counter form of that
    mistake)."""
    if sync:
        _drain_device_queue()
    t0 = time.perf_counter()
    try:
        with annotate(name):
            yield
    finally:
        dt = time.perf_counter() - t0
        # always=True: entering a timed_section IS the opt-in — the tally
        # must not go silent under telemetry=false (the pre-round-10
        # module-global tallies recorded unconditionally too)
        _obs.histogram(f"{_obs.SECTION_PREFIX}{name}").observe(
            dt, always=True)


def log_timings(reset: bool = True) -> Dict[str, float]:
    """Emit the accumulated section tallies (reference: the TIMETAG summary
    printed at the end of training).  Returns {section: total_seconds}."""
    sections = _obs.histogram_items(_obs.SECTION_PREFIX)
    out = {}
    for full_name, h in sections.items():
        name = full_name[len(_obs.SECTION_PREFIX):]
        out[name] = h.total
    for name in sorted(out, key=out.get, reverse=True):
        h = sections[_obs.SECTION_PREFIX + name]
        log_info(f"Time for {name}: {h.total:.6f} s ({h.count} calls)")
    if reset:
        _obs.clear_prefix(_obs.SECTION_PREFIX)
    return out
