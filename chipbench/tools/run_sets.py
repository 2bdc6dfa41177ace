"""Run a cell as the bounds are measured: two sets of runs with the same seeds
in both, each run a process of its own, and after them a few traced runs.
The parent never touches JAX, so every child finds the chip free.  Writes each
run's result line to ``--out`` and prints, per set and metric, the median and
the spread (distance between the quartiles, ``statistics.quantiles(n=4)``,
as a share of the median).

    python chipbench/tools/run_sets.py --workload higgs-train --runs 6 \
        --traced 3 --seconds 51 --out chiprun_out/sets-higgs-train.jsonl
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import subprocess
import sys

RUN = pathlib.Path(__file__).resolve().parents[1] / "run.py"


def one(workload: str, seed: int, seconds: float, trace: int) -> dict:
    out = subprocess.run(
        [sys.executable, str(RUN), "--workload", workload, "--seed",
         str(seed), "--seconds", str(seconds), "--trace", str(trace)],
        capture_output=True, text=True)
    lines = out.stdout.strip().splitlines()
    if out.returncode != 0 or not lines:
        return {"seed": seed, "trace": trace, "rc": out.returncode,
                "stderr": out.stderr[-2000:]}
    return dict(json.loads(lines[-1]), seed=seed, trace=trace, rc=0)


def show(r: dict) -> str:
    return (f"seed {r['seed']} rc {r['rc']} correct {r.get('correct')} "
            + " ".join(f"{k}={v['value']:.5g}"
                       for k, v in r.get("metrics", {}).items()))


def spread(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first-seed", type=int, default=2_400_000_011)
    ap.add_argument("--traced-seeds", type=int, nargs="*", default=None,
                    help="seeds of the traced runs, in place of --traced")
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    pathlib.Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    seeds = [args.first_seed + 104_729 * i for i in range(args.runs)]
    with open(args.out, "a") as fh:
        for s in range(args.sets):
            rows = []
            for seed in seeds:
                r = dict(one(args.workload, seed, args.seconds, 0), set=s)
                rows.append(r)
                fh.write(json.dumps(r) + "\n")
                fh.flush()
                print(f"set {s} {show(r)}", flush=True)
            good = [r for r in rows if r["rc"] == 0]
            for name in (good[0]["metrics"] if good else ()):
                vals = [r["metrics"][name]["value"] for r in good]
                # the first run of a call may compile: set-up apart
                if name == "setup_s" and s == 0:
                    vals = vals[1:]
                if len(vals) >= 2:
                    print(f"set {s} {name}: median "
                          f"{statistics.median(vals):.6g} spread "
                          f"{spread(vals):.4%} of {len(vals)}", flush=True)
        traced = args.traced_seeds if args.traced_seeds is not None else [
            args.first_seed + 15_485_863 * (i + 1) for i in range(args.traced)]
        for seed in traced:
            r = dict(one(args.workload, seed, args.seconds, 1), set="traced")
            fh.write(json.dumps(r) + "\n")
            fh.flush()
            print(f"traced {show(r)} "
                  f"total_s={r.get('run', {}).get('total_s')}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
