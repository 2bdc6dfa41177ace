"""Where a cell's device time goes, by the phases the program names: drive
the cell as ``run.py`` does (data, ``Dataset``, booster, warm trees, the
window with a few whole trees traced), hand the trace to the program's own
reduction (``lightgbm_tpu.utils.profiling.device_phase_seconds``, which reads
each operation's ``jax.named_scope`` out of the HLO the trace carries) before
the trace is deleted, and check it against the harness's reduction of the
same trace and against the program's pass counters.

    python chipbench/tools/phases.py --workload higgs-train --seed 2500000001 \
        --seconds 20

One JSON line on standard output: milliseconds per traced tree and share of
busy device time by phase, ``unscoped`` (operations of the grower's module
that carry no catalogued scope, the largest by name) and ``outside_grower``
(by module), then ``checks``:

- ``kernel``: the ``hist.kernel`` phase against ``trace_reduce.kernel_seconds``
  (the accepted metrics' ``^_hist_pallas_raw``);
- ``sum``: phases + unscoped + outside_grower against the harness's ``busy_s``;
- ``passes``: ``hist_passes_per_tree`` (the program's counter) against the
  kernel's events per traced tree over the calls per pass;
- ``useful``: ``hist_stream_useful_pct`` against the rows ``harness/work.py``
  reads off the same trees over passes x rows.

Runs no reference and judges nothing: ``run.py`` does.  Exits 3 off the chip,
4 where the trace holds no device operation.
"""

from __future__ import annotations

import argparse
import json
import math
import pathlib
import shutil
import sys

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))

from chipbench.harness import (loader, readers, stages, trace_reduce,  # noqa: E402
                               work)

CACHE_DIR = HERE.parent / ".chipbench_cache"
FEATURE_CHUNK = 128  # ops/hist_pallas._FEAT_BLOCK: one kernel call a chunk


class PhaseTracer(trace_reduce.WindowTracer):
    """The window's tracer, which also reads its trace both ways before the
    caller deletes it."""

    def reductions(self) -> tuple:
        from lightgbm_tpu.utils import profiling

        harness = trace_reduce.reduce(trace_reduce.read_xplane(
            trace_reduce.find_xplane(self.log_dir)))
        return profiling.device_phase_seconds(self.log_dir), harness


def gap_pct(a: float, b: float) -> float:
    return 100.0 * abs(a - b) / b if b else math.inf


def report(phases: dict, harness: dict, ctx: dict, run: dict,
           per_layer: list, cell_name: str) -> dict:
    """The tool's line from the two reductions and the run's record."""
    n_traced = len(ctx["traced"])
    rows = dict(phases["phases"], unscoped=phases["unscoped_s"],
                outside_grower=sum(phases["outside_grower"].values()))
    busy = phases["busy_s"]
    in_grower = sum(phases["phases"].values()) + phases["unscoped_s"]
    specs = loader.load_layer_metrics()
    by_name = {s["name"]: s for s in specs}
    kernel_s = trace_reduce.kernel_seconds(
        ctx, by_name["hist_kernel_ms_per_tree"])
    metrics = readers.read_all(specs, ctx, cell_name, per_layer)
    calls_per_pass = math.ceil(run["n_features"] / FEATURE_CHUNK)
    kernel_events = sum(1 for e in harness["events"]
                        if e[0].startswith("_hist_pallas_raw"))
    passes_traced = kernel_events / calls_per_pass / max(n_traced, 1)
    needed = sum(run["tree_rows"])
    checks = {
        "kernel": {"hist_kernel_s": phases["phases"]["hist.kernel"],
                   "kernel_seconds_s": kernel_s,
                   "gap_pct": gap_pct(phases["phases"]["hist.kernel"],
                                      kernel_s or 0.0)},
        "sum": {"sum_s": sum(rows.values()), "busy_s": harness["busy_s"],
                "gap_pct": gap_pct(sum(rows.values()), harness["busy_s"])},
    }
    passes = metrics.get("hist_passes_per_tree")
    if passes is not None:
        checks["passes"] = {
            "counter_per_tree": passes["value"],
            "kernel_events_per_traced_tree_per_call": passes_traced,
            "gap_pct": gap_pct(passes["value"], passes_traced)}
        streamed = passes["value"] * len(run["tree_rows"]) * run["n_rows"]
        useful = metrics["hist_stream_useful_pct"]["value"]
        checks["useful"] = {
            "counter_pct": useful,
            "work_rows_over_streamed_pct": 100.0 * needed / streamed,
            "gap_pct": gap_pct(useful, 100.0 * needed / streamed)}
    return {
        "workload": cell_name,
        "traced_trees": n_traced,
        "program_trees": phases["trees"],
        "host_steps": phases["host_steps"],
        "grower_modules": phases["grower_modules"],
        "busy_s": busy,
        "ms_per_tree": {k: 1e3 * v / max(n_traced, 1)
                        for k, v in rows.items()},
        "share_pct": {k: 100.0 * v / busy for k, v in rows.items()},
        "grower_scoped_pct": 100.0 * (1.0 - phases["unscoped_s"] / in_grower),
        "unscoped_ops_s": phases["unscoped_ops"],
        "outside_grower_s": phases["outside_grower"],
        "checks": checks,
        "metrics": {k: v["value"] for k, v in metrics.items()},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    def say(msg: str) -> None:
        print(f"[phases] {msg}", file=sys.stderr, flush=True)

    bench = loader.load_benchmark()
    cell = loader.load_cell(loader.find_workload(bench, args.workload))

    import jax

    d = jax.devices()[0]
    if d.platform != "tpu" or len(jax.devices()) < cell["chips"]:
        say(f"needs {cell['chips']} TPU chip(s), found {d.platform}: a "
            "device time comes from the chip only")
        return 3
    peaks = loader.load_peaks(d.device_kind)
    stages.compile_cache_on()
    trace_dir = str(CACHE_DIR / f"phases-{cell['name']}-{args.seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    tr = cell["traffic"]["trace"]
    try:
        run, program = stages.drive(
            cell, args.seed, args.seconds, str(CACHE_DIR),
            lambda: PhaseTracer(trace_dir, tr["skip_trees"], tr["trees"]),
            log=say)
        stages.check_no_fallback(cell, run["flags"])
        phases, harness = run["tracer"].reductions()
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    del program
    if harness is None or not phases["chips"]:
        say("the trace holds no operation on a device")
        return 4
    win = run["window"]
    least = [work.least_time(r, run["n_features"], peaks)["seconds"]
             for r in run["tree_rows"][run["warm_trees"]:]]
    ctx = {"spans": run["spans"], "window": win, "peaks": peaks,
           "config": cell["config"], "least_s": least,
           "counters": {"compiles_in_window": win["compiles"]},
           "trace": harness,
           "traced": run["tracer"].traced_trees(win["trees"])}
    out = report(phases, harness, ctx, run, bench["per_layer"], cell["name"])
    out.update(seed=args.seed, trees_per_s=win["trees"] / win["seconds"],
               device={"platform": d.platform, "kind": d.device_kind,
                       "count": len(jax.devices())})
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
