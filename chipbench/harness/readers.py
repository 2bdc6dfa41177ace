"""The generic per-layer readers.  A metric's file names one under
``reader``; a metric that needs more has a ``.py`` of its own name beside
its ``.json``, with a ``read(ctx, spec)`` of its own.

``ctx`` is what a run has to read from: ``spans`` (host spans, seconds),
``counters``, ``window`` (trees, seconds), ``least_s`` (the least time of
every window tree, ``work.py``), ``traced`` (the window trees inside the
trace), ``trace`` (``trace_reduce.reduce``'s result, or None with
``--trace 0``), ``peaks`` and ``config``.  A reader that finds nothing to
read returns None, and the harness leaves the metric out of the line.
"""

from __future__ import annotations

from typing import Optional


def span(ctx: dict, spec: dict) -> Optional[float]:
    """Sum of the named host spans, where every one was taken."""
    names = spec["args"]["spans"]
    if any(n not in ctx["spans"] for n in names):
        return None
    return float(sum(ctx["spans"][n] for n in names))


def counter(ctx: dict, spec: dict) -> Optional[float]:
    value = ctx["counters"].get(spec["args"]["counter"])
    return None if value is None else float(value)


GENERIC = {"span": span, "counter": counter}


def read_all(specs: list[dict], ctx: dict, workload: str,
             per_layer: list[dict]) -> dict:
    """Every per-layer metric this workload reports and whose reader found
    something: ``{name: {"value", "unit"}}``.  Which cells report a metric is
    said in one place, ``per_layer[].workloads`` of ``BENCHMARK.json`` (an
    entry without the key is reported by every cell), so a later cell joins a
    metric that is there by an appended name and no edit under
    ``chipbench/``.  A metric's file that ``BENCHMARK.json`` does not name is
    not reported."""
    listed = {m["name"]: m for m in per_layer}
    out = {}
    for spec in specs:
        entry = listed.get(spec["name"])
        if entry is None or workload not in entry.get("workloads",
                                                      [workload]):
            continue
        fn = (spec["module"].read if spec.get("module") is not None
              else GENERIC[spec["reader"]])
        value = fn(ctx, spec)
        if value is not None:
            out[spec["name"]] = {"value": float(value), "unit": spec["unit"]}
    return out
