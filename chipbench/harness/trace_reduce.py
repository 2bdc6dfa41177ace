"""From a profiler trace to numbers: device planes, union of busy intervals,
durations by event-name pattern, the operations that took most time and the
longest idle gaps by what the host was doing.

A trace is read once into a plain structure (``read_xplane``), so that the
reduction runs the same on a live ``.xplane.pb`` and on the small recorded
trace the tests keep::

    {"planes": [{"name": str,
                 "lines": [{"name": str,
                            "events": [[name, start_ns, duration_ns], ...]}]}]}
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Iterable, Optional, Sequence

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# the line of a device plane that holds one event per executed operation;
# the other lines (steps, modules, the framework's scopes) cover the same
# time again and would count it twice
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
HOST_SPANS = ("chipbench_update", "chipbench_wait")


def short_name(text: str) -> str:
    """An operation's name from the HLO line the trace shows for it:
    ``%fusion.12 = f32[..] fusion(..)`` is ``fusion.12``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")[:200]


def base_name(name: str) -> str:
    """``_hist_pallas_raw.94`` and ``_hist_pallas_raw.80`` are one kernel."""
    return re.sub(r"(\.\d+)+$", "", name)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def read_xplane(path: str, keep_host: Sequence[str] = HOST_SPANS) -> dict:
    """The trace as a plain structure.  Of the host planes only the events
    named in ``keep_host`` are kept (the benchmark's own annotations)."""
    from jax.profiler import ProfileData

    planes, names = [], []
    for plane in ProfileData.from_file(path).planes:
        names.append(plane.name)
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = [[short_name(e.name), float(e.start_ns),
                       float(e.duration_ns)]
                      for e in line.events
                      if device or e.name in keep_host]
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes, "plane_names": names}


def load_recorded(path: str) -> dict:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        return json.load(fh)


def save_recorded(trace: dict, path) -> None:
    os.makedirs(os.path.dirname(str(path)) or ".", exist_ok=True)
    with gzip.open(str(path), "wt") as fh:
        json.dump(trace, fh)


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def op_events(plane: dict) -> list[list]:
    """The executed operations of one device plane, by start."""
    for line in plane["lines"]:
        if line["name"] == OPS_LINE:
            return sorted(line["events"], key=lambda e: e[1])
    return []


def host_spans(trace: dict) -> list[list]:
    out = []
    for p in trace["planes"]:
        if p["name"] == HOST_PLANE:
            for line in p["lines"]:
                out.extend(line["events"])
    return sorted(out, key=lambda e: e[1])


def busy_intervals(events: Iterable[list]) -> list[tuple[float, float]]:
    """Union of [start, end) of the events, merged, in ns."""
    out: list[list[float]] = []
    for _, start, dur in sorted(events, key=lambda e: e[1]):
        end = start + dur
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def seconds_by_pattern(events: Iterable[list], patterns: Sequence[str]
                       ) -> Optional[float]:
    """Seconds covered by the events whose name matches a pattern (their
    union, so an operation nested in a matching one is not counted twice).
    None where nothing matched: a reader with nothing to read says nothing."""
    regs = [re.compile(p) for p in patterns]
    hit = [e for e in events if any(r.search(e[0]) for r in regs)]
    if not hit:
        return None
    return sum(b - a for a, b in busy_intervals(hit)) * 1e-9


def kernel_seconds(ctx: dict, spec: dict) -> Optional[float]:
    """Device seconds per chip of the kernel a metric's ``patterns`` name,
    over the traced trees; None without a trace, a traced tree or a match."""
    if ctx.get("trace") is None or not len(ctx["traced"]):
        return None
    s = seconds_by_pattern(ctx["trace"]["events"], spec["patterns"])
    return None if not s else s / ctx["trace"]["chips"]


def self_times(events: Iterable[list]) -> list[list]:
    """[name, self_ns] of every event: its duration less that of the events
    nested directly in it (a ``while`` or a ``conditional`` holds the
    operations of its body on the same line)."""
    out: list[list] = []
    stack: list[tuple[float, int]] = []  # (end, index into out)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        out.append([name, dur])
        stack.append((start + dur, len(out) - 1))
    return out


def top_ops(events: Iterable[list], n: int = 10) -> list[list]:
    """The operations that took most time themselves, by kernel (numbered
    copies of one operation together), in seconds."""
    total: dict[str, float] = {}
    for name, ns in self_times(events):
        key = base_name(name)
        total[key] = total.get(key, 0.0) + ns
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9] for name, ns in ranked]


def idle_gaps(intervals: Sequence[tuple[float, float]], spans: Sequence[list],
              n: int = 10) -> list[list]:
    """The longest gaps between busy intervals, each named by the
    benchmark's host span that covers its middle (``host:other`` where
    none does)."""
    gaps = []
    for (_, end), (start, _) in zip(intervals, intervals[1:]):
        mid = (end + start) / 2
        what = next((s[0] for s in spans if s[1] <= mid < s[1] + s[2]),
                    "host:other")
        gaps.append([what, (start - end) * 1e-9])
    return sorted(gaps, key=lambda g: -g[1])[:n]


def reduce(trace: dict) -> Optional[dict]:
    """Busy seconds (averaged over the chips that ran something), the length
    of the traced window, and the breakdown.  None where no device plane
    holds an operation."""
    planes = [ev for ev in map(op_events, device_planes(trace)) if ev]
    if not planes:
        return None
    spans = host_spans(trace)
    busy, windows, all_events = [], [], []
    gaps: list[list] = []
    for events in planes:
        iv = busy_intervals(events)
        busy.append(sum(b - a for a, b in iv) * 1e-9)
        windows.append((iv[-1][1] - iv[0][0]) * 1e-9)
        gaps.extend(idle_gaps(iv, spans))
        all_events.extend(events)
    chips = len(planes)
    return {
        "chips": chips,
        "busy_s": sum(busy) / chips,
        "window_s": max(windows),
        "events": all_events,
        "device_ops": [[n, s / chips] for n, s in top_ops(all_events)],
        "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10],
    }


class WindowTracer:
    """Wraps a few whole trees in the middle of the window in a profiler
    trace.  The window tells it, before each ``update()``, how many trees it
    has issued and how many it knows to have ended."""

    def __init__(self, log_dir: str, skip_trees: int, n_trees: int):
        self.log_dir = log_dir
        self.skip = int(skip_trees)
        self.n = int(n_trees)
        self.first: Optional[int] = None  # first traced tree of the window
        self.last: Optional[int] = None  # one past the last
        self._issued = 0

    def before_tree(self, issued: int, done: int) -> None:
        import jax

        self._issued = issued
        if self.first is None:
            if done >= self.skip and issued > done:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(self.log_dir, profiler_options=opts)
                self.first = done
        elif self.last is None and done >= self.first + self.n:
            jax.profiler.stop_trace()
            self.last = done

    def close(self) -> None:
        """After the window's last tree has ended."""
        import jax

        if self.first is not None and self.last is None:
            jax.profiler.stop_trace()
            self.last = self._issued + 1

    def traced_trees(self, window_trees: int) -> range:
        if self.first is None:
            return range(0)
        return range(self.first, min(self.last, window_trees))
