"""One process, one cell, once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

In order: fail unless the first device is a TPU of a kind in the peaks table
and there are as many as the cell asks for; turn on the persistent compile
cache; draw the data from ``--seed``; build the ``Dataset``; build the
``Booster`` and run the first ``update()`` (compiles or loads) and the other
warm ones; the window; then read the peak memory, drop the program's state,
run the plain reference over the first trees and compare; with ``--trace 1``
reduce the trace.  The last line of standard output is the one JSON object
the contract fixes; the last lines of standard error are the numbers compared,
each beside its limit.  Everything from process start to the window's start
is ``setup_s``.
"""

from __future__ import annotations

import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))

from chipbench.harness import loader, readers, stages, trace_reduce, work  # noqa: E402

CACHE_DIR = HERE.parent / ".chipbench_cache"


def say(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def device_block() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def memory_peak_bytes() -> int:
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = loader.load_benchmark()
    workload = loader.find_workload(bench, args.workload)
    cell = loader.load_cell(workload)
    specs = loader.load_layer_metrics()

    import jax

    t_jax = time.perf_counter()
    dev = device_block()
    t_chip = time.perf_counter()
    if dev["platform"] != "tpu" or dev["count"] < cell["chips"]:
        say(f"needs {cell['chips']} TPU chip(s), found {dev}: this benchmark "
            "runs on the chip only and writes no number from anywhere else")
        return 3
    peaks = loader.load_peaks(dev["kind"])  # a kind not in the table raises

    compile_cache = stages.compile_cache_on()
    t_staged = time.perf_counter()
    say(f"device {json.dumps(dev)} compile cache {compile_cache}")

    tracer_factory = None
    trace_dir = str(CACHE_DIR / f"trace-{cell['name']}-{args.seed}")
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        tr = cell["traffic"]["trace"]
        tracer_factory = lambda: trace_reduce.WindowTracer(  # noqa: E731
            trace_dir, tr["skip_trees"], tr["trees"])

    run, program = stages.drive(cell, args.seed, args.seconds,
                                str(CACHE_DIR), tracer_factory, log=say)
    win, spans = run["window"], run["spans"]
    setup_s = win["t0"] - _T_START
    # set-up before the first stage, by the host clock: the cell's files
    # with numpy and jax imported, the runtime's start (jax.devices()), and
    # the program's import with the compile cache's switch
    spans.update(load_cell_s=t_jax - _T_START, reach_chip_s=t_chip - t_jax,
                 import_program_s=t_staged - t_chip)
    stages.check_no_fallback(cell, run["flags"])
    peak = memory_peak_bytes()
    del program
    stages.free_program()

    say(f"peak memory {peak / 1e9:.2f} GB; set-up {setup_s:.1f} s")
    correct, compared = stages.judge(cell, run)
    say(f"reference {spans['reference_s']:.1f} s")

    n_warm = run["warm_trees"]
    least = [work.least_time(r, run["n_features"], peaks)
             for r in run["tree_rows"][n_warm:]]
    device = dict(dev, memory_peak_bytes=peak)
    result = {"correct": correct, "attempted": win["trees"], "failed": 0}
    ctx = {"spans": spans, "window": win, "peaks": peaks,
           "config": cell["config"], "least_s": [w["seconds"] for w in least],
           "counters": {"compiles_in_window": win["compiles"]},
           "trace": None, "traced": range(0)}
    if args.trace:
        tracer = run["tracer"]
        trace = trace_reduce.read_xplane(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        reduced = trace_reduce.reduce(trace)
        if reduced is None:
            say("the trace holds no operation on a device")
            return 4
        ctx.update(trace=reduced, traced=tracer.traced_trees(win["trees"]))
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        result["metrics"] = readers.read_all(specs, ctx, cell["name"],
                                             bench["per_layer"])
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    else:
        result["metrics"] = {
            "trees_per_s": {"value": win["trees"] / win["seconds"],
                            "unit": "trees/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    result["device"] = device
    result["run"] = {
        "workload": cell["name"], "seed": args.seed, "setup_s": setup_s,
        "window_s": win["seconds"], "trees": win["trees"],
        "spans": dict(spans), "flags": run["flags"],
        "compiles_in_window": win["compiles"],
        "cache_loads_in_window": win["cache_loads"],
        "issued_at_s": win["issued_at_s"],
        "work": {"bound": least[0]["bound"],
                 "rows_per_tree": [w["rows"] for w in least]},
        "total_s": time.perf_counter() - _T_START}
    result["compared"] = compared
    for name, c in compared.items():
        say(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    say(f"correct {correct}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
