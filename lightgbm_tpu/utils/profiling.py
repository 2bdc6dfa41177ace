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
import glob
import os
import re
import time
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax

from ..obs import metrics as _obs
from ..obs import trace as _trace
from . import sanitizer as _san
from .log import log_info


# The phases of one boosting round on the device, as the ``jax.named_scope``
# components the program puts into every operation's ``op_name`` (trace-time
# only: a scope costs nothing at run time).  One catalogue: the scopes in
# models/gbdt.py and ops/ open them through :func:`phase_scope`,
# :func:`device_phase_seconds` reduces a device trace by them, and
# docs/OBSERVABILITY.md lists them.
DEVICE_PHASES = (
    "gbdt.gradients",  # objective.get_gradients of the round
    "rank.gather",  # ranking objectives: scores by row into the query buckets
    "rank.sort",  # each query's best-ranked rows taken in rank order
    "rank.pairs",  # the pair terms of the truncation window (xendcg: softmax)
    "rank.scatter",  # gradients and hessians from the buckets' lanes by row
    "grow.root",  # the root's full pass and totals
    "grow.partition",  # admission, split apply, row routing (leaf_id)
    "grow.slots",  # slot per row for the pass
    "hist.payload",  # the kernel's per-tree base (once a tree); the einsum
    # route's mask, split, one-hot x base, reshape (every pass)
    "hist.rowpad",  # the einsum route's pads to its row tile
    "hist.kernel",  # the Pallas kernel (the one-hot einsum at <= 64 bins)
    "hist.unpack",  # slice, hi + lo, transpose; unbundle and psum
    "grow.sibling",  # per-slot parent slices, subtraction, in-place
    # updates of state.hist a leaf at a time
    "grow.split_search",  # _batched_best and the merge into state.best
    "grow.cat_search",  # inside it: the categorical candidates of the
    # categorical columns (ops/split.py); an operation is booked under the
    # innermost scope, so the two rows do not overlap
    "grow.leaf_values",  # renewal or leaf_output, the final TreeArrays
    "gbdt.score_update",  # row_delta and the score add
)


def phase_scope(name: str):
    """``jax.named_scope`` of one catalogued phase; a name outside
    :data:`DEVICE_PHASES` is a programming error and raises at trace time."""
    if name not in DEVICE_PHASES:
        raise ValueError(f"{name!r} is not in profiling.DEVICE_PHASES")
    return jax.named_scope(name)


# The phases of one boosting round on the host, as spans (obs/trace.py)
# inside ``boost_round``: where ``Booster.update()`` spends its wall clock,
# dispatching and waiting.  One catalogue, as for the device: models/gbdt.py
# opens them through :func:`host_phase`, :func:`host_phase_ms_per_tree`
# reads the ring a tree at a time by them (for a benchmark's reader),
# :func:`device_phase_seconds` names the device's idle gaps by them,
# and docs/OBSERVABILITY.md lists them.  Every line of ``update()`` that
# dispatches or pulls lies inside one.
HOST_PHASES = (
    "update.gradients",  # objective.get_gradients (the ranking step), or the
    # caller's gradients to the device; the fault-injection sites
    "update.masks",  # bagging and feature masks; each class tree's node RNG
    # and CEGB penalty
    "update.grow",  # the grower call of each class tree, its arguments and
    # its localisation; the fused route's one step call
    "update.score",  # guard accumulate, CEGB bookkeeping, renewal, linear
    # fit, the score adds to the training and every validation score
    "update.sync",  # every host pull: the iter % 32 check and its guard
    # check, the fused route's finish probe, the strict grower's host tree
    "host.flush",  # _flush_pending, outside update(): device trees to host
    # trees and the pass counters they carry
)
_HOST_PHASE_SET = frozenset(HOST_PHASES)
# the sanitizer ledger's deltas a phase carries: the two it counts on every
# route.  Its dispatch count is kept only where a driver calls
# record_dispatch (the fused step, OOC), so on the rounds grower a phase
# would read 0 dispatches whatever it issued: a profiler trace counts them
# (``PjRtCpuExecutable::Execute`` on the CPU, tests/test_trace_budget.py)
_LEDGER_KEYS = ("host_syncs", "compiles")


@contextlib.contextmanager
def _ledger_span(name: str) -> Iterator[_trace.Span]:
    c0 = _san.compile_totals()
    with _trace.span(name) as sp:
        yield sp
        c1 = _san.compile_totals()
        sp.set(**{k: c1[k] - c0[k] for k in _LEDGER_KEYS})


def host_phase(name: str):
    """The span of one catalogued host phase, carrying the sanitizer
    ledger's ``host_syncs`` and ``compiles`` deltas; a name
    outside :data:`HOST_PHASES` raises.  With telemetry off it is the
    disabled span, a no-op."""
    if name not in _HOST_PHASE_SET:
        raise ValueError(f"{name!r} is not in profiling.HOST_PHASES")
    if not _obs.enabled():
        return _trace.span(name)
    return _ledger_span(name)


def host_phase_ms_per_tree(name: str, trees: int) -> Optional[float]:
    """Host milliseconds a tree in one catalogued phase: the durations of
    the ``name`` children of the ring's last ``trees`` ``boost_round``
    spans, summed and divided by ``trees``.  None where the ring holds fewer
    ``boost_round`` spans (telemetry off records none)."""
    if name not in _HOST_PHASE_SET:
        raise ValueError(f"{name!r} is not in profiling.HOST_PHASES")
    rounds = _trace.spans("boost_round")
    if trees <= 0 or len(rounds) < trees:
        return None
    ids = {s.get("id") for s in rounds[-trees:]} - {None}
    return 1e3 * sum(s["dur"] for s in _trace.spans(name)
                     if s.get("parent") in ids) / trees


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


def _annotation_factory(name: str, attrs: dict):
    """obs/trace.py's bridge to jax.profiler, with no switch: a span that
    carries ``step``/``iteration`` (``boost_round``) opens a
    StepTraceAnnotation, so a profiler trace has a step per tree, and every
    other span a TraceAnnotation, so the host's phases lie on the device
    trace's clock.  Outside a profiler session a TraceMe is a flag test."""
    step = attrs.get("step", attrs.get("iteration"))
    if step is not None:
        return jax.profiler.StepTraceAnnotation(name, step_num=int(step))
    return jax.profiler.TraceAnnotation(name)


def install_annotations() -> None:
    """Mirror every context-manager span into jax.profiler annotations:
    installed when this module is imported (the layers that open spans
    import it).  The obs package itself stays stdlib-only: THIS module,
    which imports jax already, owns the bridge."""
    _trace.set_annotation_factory(_annotation_factory)


install_annotations()


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


# ---------------------------------------------------------------------------
# From a device trace to seconds per phase
# ---------------------------------------------------------------------------
#
# The TPU's XLA Ops events carry the HLO line and a start and a duration,
# no op_name and no module (chip run, PR 25).  The scope of an operation is
# therefore looked up in the HLO the trace itself carries: the
# ``/host:metadata`` plane holds one HloProto per executed module, under the
# name the ``XLA Modules`` line gives each run of it.  ``ProfileData`` does
# not expose that plane's event metadata, so the few protobuf fields needed
# are read off the wire here (field numbers from tsl's xplane.proto and
# xla's hlo.proto and xla_data.proto).

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OPS_LINE, _MODULES_LINE = "XLA Ops", "XLA Modules"
_METADATA_PLANE = "/host:metadata"
_HOST_PLANE = "/host:CPU"
_PROGRAM_SPANS = frozenset(HOST_PHASES + ("boost_round",))
IDLE_GAPS_KEPT = 10
_PHASE_SET = frozenset(DEVICE_PHASES)
_GROWER_PREFIXES = ("grow.", "hist.")


def _wire_fields(buf) -> Iterator[Tuple[int, int, object]]:
    """(field number, wire type, value) of one protobuf message: an int for
    a varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        value = shift = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            if b < 0x80:
                return value
            shift += 7

    while i < n:
        key = varint()
        num, wire = key >> 3, key & 7
        if wire == 0:
            yield num, wire, varint()
        elif wire == 2:
            size = varint()
            yield num, wire, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            yield num, wire, buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")


def _sub(buf, number: int) -> Iterator[memoryview]:
    return (v for num, wire, v in _wire_fields(buf)
            if num == number and wire == 2)


def _text(buf, number: int) -> str:
    return next((bytes(v).decode("utf-8", "replace")
                 for v in _sub(buf, number)), "")


def _hlo_op_names(hlo_proto) -> Dict[str, str]:
    """{instruction name: op_name} of one serialized HloProto."""
    out: Dict[str, str] = {}
    for module in _sub(hlo_proto, 1):  # HloProto.hlo_module
        for comp in _sub(module, 3):  # HloModuleProto.computations
            for ins in _sub(comp, 2):  # HloComputationProto.instructions
                name = op_name = ""
                for num, wire, v in _wire_fields(ins):  # one pass a message
                    if wire == 2 and num == 1:  # HloInstructionProto.name
                        name = bytes(v).decode("utf-8", "replace")
                    elif wire == 2 and num == 7:  # .metadata (OpMetadata)
                        op_name = _text(v, 2)  # OpMetadata.op_name
                if op_name:
                    out[name] = op_name
    return out


def trace_op_names(xplane_path: str) -> Dict[str, Dict[str, str]]:
    """{module run name: {instruction name: op_name}} from the HLO protos a
    profiler trace carries in its metadata plane; ``{}`` where it has
    none."""
    with open(xplane_path, "rb") as fh:
        space = memoryview(fh.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in _sub(space, 1):  # XSpace.planes
        if _text(plane, 2) != _METADATA_PLANE:  # XPlane.name
            continue
        for entry in _sub(plane, 4):  # XPlane.event_metadata, a map entry
            for meta in _sub(entry, 2):  # the entry's XEventMetadata
                names: Dict[str, str] = {}
                for stat in _sub(meta, 5):  # XEventMetadata.stats
                    for proto in _sub(stat, 6):  # XStat.bytes_value
                        names.update(_hlo_op_names(proto))
                out[_text(meta, 2)] = names  # XEventMetadata.name
    return out


def _instruction_name(event_name: str) -> str:
    """``%fusion.12 = f32[..] fusion(..)`` is ``fusion.12``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def phase_of(op_name: str) -> Optional[str]:
    """The innermost catalogued component of an ``op_name``.  A scope entered
    under a transformation is written inside it, ``vmap(grow.cat_search)``:
    the search of a round's children is one vmapped call."""
    for part in reversed(op_name.split("/")):
        part = part.rsplit("(", 1)[-1].rstrip(")")
        if part in _PHASE_SET:
            return part
    return None


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_device_trace(xplane_path: str, outer: Sequence[str] = ()) -> dict:
    """The part of a trace the phase reduction reads, as a plain structure
    (the same on a live ``.xplane.pb`` and in a test)::

        {"chips": [{"ops": [[hlo line, start_ns, dur_ns], ...],
                    "modules": [[run name, start_ns, dur_ns], ...]}],
         "op_names": {run name: {instruction: op_name}},
         "steps": host events that carry a step number (a boost_round each),
         "host": [[name, start_ns, dur_ns, line], ...]}

    ``host`` holds the program's spans (``boost_round`` and
    :data:`HOST_PHASES`) of the ``/host:CPU`` plane, and the caller's own
    annotations there whose names begin with one of ``outer`` (a benchmark's
    around ``update()``), each with the index of its line (a thread).
    """
    from jax.profiler import ProfileData

    chips, steps, host = [], 0, []
    for plane in ProfileData.from_file(xplane_path).planes:
        if _DEVICE_PLANE.match(plane.name):
            chip = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {_OPS_LINE: "ops", _MODULES_LINE: "modules"}.get(
                    line.name)
                if key is not None:
                    chip[key] = [[e.name, float(e.start_ns),
                                  float(e.duration_ns)] for e in line.events]
            if chip["ops"]:
                chips.append(chip)
        elif plane.name.startswith("/host:"):
            keep = plane.name == _HOST_PLANE
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if any(k == "step_num" for k, _ in e.stats):
                        steps += 1
                    if keep and (e.name in _PROGRAM_SPANS
                                 or e.name.startswith(tuple(outer))):
                        host.append([e.name, float(e.start_ns),
                                     float(e.duration_ns), i])
    return {"chips": chips, "op_names": trace_op_names(xplane_path),
            "steps": steps, "host": host}


def _self_ns(events: List[list]) -> List[float]:
    """Each event's duration less that of the events nested directly in it
    (a ``while`` or a ``conditional`` holds its body's operations on the
    same line), in the order of ``events``, which is by start, the longer
    first."""
    out: List[float] = []
    stack: List[Tuple[float, int]] = []  # (end, index into out)
    for _, start, dur in events:
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]] -= dur
        out.append(dur)
        stack.append((start + dur, len(out) - 1))
    return out


def phase_seconds(trace: dict) -> dict:
    """Device seconds per catalogued phase, by each operation's self time,
    averaged over the chips that ran something.  An operation without a
    catalogued scope counts under ``unscoped_s`` where its module is the
    grower's (a module that holds a ``grow.*`` or ``hist.*`` operation), and
    under ``outside_grower`` by its module's name otherwise: eager
    operations of the boosting loop, each a module of its own.  ``busy_s``
    is the union of the operations' intervals; the phases, ``unscoped_s``
    and ``outside_grower`` sum to it where operations on one chip nest and
    do not otherwise overlap.  ``trees`` counts the runs of the grower's
    module on a chip (the ``XLA Modules`` line: a tree each, or a round each
    where the fused step grows the round's trees in one run); ``host_steps``
    the host's step events, a ``boost_round`` each, which are fewer where
    ``update()`` had issued the first traced tree before the trace began.
    ``host_phases`` and ``idle_gaps`` read the trace's ``host`` events
    (:func:`host_phase_seconds`, :func:`idle_gaps`)."""
    op_names = trace["op_names"]
    growers = sorted(m for m, names in op_names.items() if any(
        (phase_of(o) or "").startswith(_GROWER_PREFIXES)
        for o in names.values()))
    phases = {p: 0.0 for p in DEVICE_PHASES}
    outside: Dict[str, float] = {}
    unscoped_ops: Dict[str, float] = {}
    unscoped = busy = 0.0
    runs = 0
    for chip in trace["chips"]:
        modules = sorted(chip["modules"], key=lambda e: e[1])
        runs += sum(1 for m in modules if m[0] in growers)
        ops = sorted(chip["ops"], key=lambda e: (e[1], -e[2]))
        end = float("-inf")  # busy: the union of the operations' intervals
        for _, start, dur in ops:
            busy += max(start + dur - max(start, end), 0.0) * 1e-9
            end = max(end, start + dur)
        k = 0
        for (line, start, _), self_ns in zip(ops, _self_ns(ops)):
            while k < len(modules) and start >= modules[k][1] + modules[k][2]:
                k += 1
            inside = k < len(modules) and modules[k][1] <= start
            module = modules[k][0] if inside else "(no module)"
            name = _instruction_name(line)
            phase = phase_of(op_names.get(module, {}).get(name, ""))
            sec = self_ns * 1e-9
            if phase is not None:
                phases[phase] += sec
            elif module in growers:
                unscoped += sec
                base = re.sub(r"(\.\d+)+$", "", name)
                unscoped_ops[base] = unscoped_ops.get(base, 0.0) + sec
            else:
                base = module.split("(", 1)[0]
                outside[base] = outside.get(base, 0.0) + sec
    n = max(len(trace["chips"]), 1)
    ranked = sorted(unscoped_ops.items(), key=lambda kv: -kv[1])[:12]
    host = trace.get("host", [])
    return {
        "chips": len(trace["chips"]),
        "trees": runs // n,
        "host_steps": trace["steps"],
        "grower_modules": [g.split("(", 1)[0] for g in growers],
        "busy_s": busy / n,
        "phases": {p: s / n for p, s in phases.items()},
        "unscoped_s": unscoped / n,
        "unscoped_ops": [[name, s / n] for name, s in ranked],
        "outside_grower": {m: s / n for m, s in sorted(
            outside.items(), key=lambda kv: -kv[1])},
        "host_phases": host_phase_seconds(host),
        "idle_gaps": idle_gaps(trace["chips"], host),
    }


def host_phase_seconds(host: List[list]) -> Dict[str, float]:
    """Host seconds of each catalogued phase of :data:`HOST_PHASES`, by self
    time: a span less the catalogued spans nested in it, line by line."""
    out = {p: 0.0 for p in HOST_PHASES}
    lines: Dict[int, List[list]] = {}
    for name, start, dur, line in host:
        if name in _HOST_PHASE_SET:
            lines.setdefault(line, []).append([name, start, dur])
    for events in lines.values():
        events.sort(key=lambda e: (e[1], -e[2]))
        for (name, _, _), self_ns in zip(events, _self_ns(events)):
            out[name] += self_ns * 1e-9
    return out


def _covering(host: List[list], t: float, names) -> Optional[str]:
    """The innermost (latest begun) span named in ``names`` that covers
    ``t``."""
    best = None
    for name, start, dur, _ in host:
        if name in names and start <= t < start + dur and (
                best is None or start >= best[1]):
            best = (name, start)
    return None if best is None else best[0]


def idle_gaps(chips: List[dict], host: List[list],
              n: int = IDLE_GAPS_KEPT) -> List[list]:
    """The ``n`` longest gaps between a chip's busy intervals, longest
    first, as ``[name, seconds]``: each named by the innermost program span
    (``boost_round`` or a host phase) that covers the gap's middle, else by
    the innermost other span of ``host`` that does (the caller's ``outer``
    annotations), else ``host:other``."""
    gaps = []  # (ns, start, end)
    for chip in chips:
        end = None
        for _, start, dur in sorted(chip["ops"], key=lambda e: e[1]):
            if end is not None and start > end:
                gaps.append((start - end, end, start))
            end = start + dur if end is None else max(end, start + dur)
    other = {e[0] for e in host} - _PROGRAM_SPANS
    out = []
    for ns, a, b in sorted(gaps, key=lambda g: -g[0])[:n]:
        mid = (a + b) / 2
        out.append([_covering(host, mid, _PROGRAM_SPANS)
                    or _covering(host, mid, other) or "host:other",
                    ns * 1e-9])
    return out


def device_phase_seconds(log_dir: str, outer: Sequence[str] = ()) -> dict:
    """Reduce the newest device trace under ``log_dir`` (as
    :func:`device_trace` or ``jax.profiler.start_trace`` wrote it) to device
    seconds per phase of :data:`DEVICE_PHASES`: see :func:`phase_seconds`.
    This libtpu's raw trace has no ``Steps`` line on the device plane (xprof
    derives it later from the host's step events), so the trees are counted
    by the runs of the grower's module and the steps on the host's lines.
    ``outer`` names the caller's own host annotations (by prefix) that name
    an idle gap no program span covers."""
    return phase_seconds(read_device_trace(find_xplane(log_dir), outer))


def log_device_phases(log_dir: str) -> dict:
    """:func:`device_phase_seconds`, logged in :func:`log_timings`' style:
    the device phases, then the host phases and the longest idle gaps."""
    r = device_phase_seconds(log_dir)
    rows = [(p, s) for p, s in r["phases"].items() if s > 0.0]
    rows.append(("unscoped", r["unscoped_s"]))
    rows.extend((f"outside_grower:{m}", s)
                for m, s in r["outside_grower"].items())
    busy = r["busy_s"] or 1.0
    for name, sec in sorted(rows, key=lambda kv: -kv[1]):
        log_info(f"Time for {name}: {sec:.6f} s "
                 f"({100.0 * sec / busy:.1f}% of device time)")
    log_info(f"Time for device, busy: {r['busy_s']:.6f} s "
             f"({r['trees']} trees, {r['host_steps']} host steps, "
             f"{r['chips']} chip(s))")
    for name, sec in r["host_phases"].items():
        if sec > 0.0:
            log_info(f"Time for host {name}: {sec:.6f} s")
    for name, sec in r["idle_gaps"]:
        log_info(f"Device idle {1e3 * sec:.3f} ms under {name}")
    return r
