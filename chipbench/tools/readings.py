"""Read, in one process, what the comparison's numbers are over many seeds:
for the program as the configuration states it (the lower reading), for the
control one precision down and for each planted fault (the upper readings).
Limits are set from these and from nothing else (PERF.md, section 2).  Every
side's numbers then go through ``compare.judge`` with the cell's own limits,
as a run's do: the program has to come out correct on every seed, the control
and each fault not correct.

    python chipbench/tools/readings.py --workload higgs-train --seeds 12 \
        --control-seeds 3 --out chiprun_out/readings-higgs-train.jsonl

Needs no measured window: it drives the warm trees through the window's own
call and compares them.  One JSON object a seed on standard output, then one
line of verdicts a side; exits 1 where a verdict is not the one due.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parent))

from chipbench.harness import compare, faults, loader, stages  # noqa: E402


def side(cell, ds, k, extra=None, fault=None):
    bst = stages.build_booster(cell, ds, extra)
    if fault is not None:
        faults.FAULTS[fault](bst)
    scores, _ = stages.warm(bst, k, 2, stages.Spans())
    flags = stages.booster_flags(bst, ds)
    out = compare.program_side(scores, list(bst._gbdt.models)[:k])
    del bst
    return out, flags


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=None,
                    help="seeds on which the faults are planted too "
                    "(default: as many as --control-seeds)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    cell = loader.load_cell(loader.find_workload(loader.load_benchmark(),
                                                 args.workload))
    import jax

    if jax.devices()[0].platform != "tpu" and not args.workload.startswith(
            "toy"):
        print("readings are taken on the chip", file=sys.stderr)
        return 3
    stages.compile_cache_on()
    k = int(cell["traffic"]["reference_trees"])
    control = cell["config"]["control"]
    cache = str(HERE.parent / ".chipbench_cache")
    limits = cell["config"]["limits"]
    loss = compare.loss_named(cell["config"])
    verdicts: dict = {}
    out = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        data = stages.make_data(cell, seed)
        ds = stages.build_dataset(cell, data, cache, seed)
        sides = {}
        sides["program"], flags = side(cell, ds, k)
        if i < args.control_seeds:
            sides["control"], cflags = side(cell, ds, k, control["params"])
        if i < (args.control_seeds if args.fault_seeds is None
                else args.fault_seeds):
            for name in faults.FAULTS:
                sides[name], _ = side(cell, ds, k, fault=name)
        t1 = time.perf_counter()
        del ds
        stages.free_program()
        ref = stages.run_reference(cell, data, k)
        t2 = time.perf_counter()
        row = {"workload": args.workload, "seed": seed,
               "program_s": t1 - t0, "reference_s": t2 - t1, "flags": flags}
        if "control" in sides:
            row["control_flags"] = cflags
        row["correct"] = {}
        for name, s in sides.items():
            row[name] = compare.numbers(s, ref, data, **loss)
            ok, compared = compare.judge(row[name], limits)
            row["correct"][name] = ok
            verdicts.setdefault(name, []).append(ok)
            if not ok:
                row.setdefault("failed", {})[name] = [
                    n for n, c in compared.items()
                    if not c["value"] <= c["limit"]]
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    due = True
    for name, oks in verdicts.items():
        print(f"verdicts {args.workload} {name}: correct on {sum(oks)} of "
              f"{len(oks)} seeds", flush=True)
        due = due and (all(oks) if name == "program" else not any(oks))
    return 0 if due else 1


if __name__ == "__main__":
    sys.exit(main())
