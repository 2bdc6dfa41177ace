"""Continual-training benchmark (round 19): the train-while-serving loop.

``serve_bench.py`` measures the serving PROCESS at a fixed model;
this measures the loop that keeps the model FRESH while it serves
(lightgbm_tpu/continual): streaming ingest throughput (in-memory window
and CRC'd durable-cache append), refit vs append-trees update latency,
and serve p50/p99 ACROSS rollovers — concurrent callers hammering the
runtime while the runner publishes refit and append updates — compared
against the committed BENCH_serve_r01 single-model baseline when it is
present next to the repo root.

``parity`` runs first and asserts IN THE ARTIFACT PATH that the
runner's rollovers reproduce the offline application of the same
primitives tree-bitwise, and that every served response during the
under-load run matches a legitimately published ensemble version — the
tests/test_continual.py pins, re-checked where the numbers are made.

Artifact contract mirrors bench.py: one JSON snapshot line printed +
flushed after every completed workload; the metrics snapshot rides every
emit and the jaxpr-audit verdict (incl. ``continual_refit_leaves``) is
embedded at the end.  Set CONTINUAL_BENCH_OUT to also write the final
snapshot to a file (e.g. BENCH_continual_r01.json).

Env knobs: CONTINUAL_BENCH_TREES (default 60), CONTINUAL_BENCH_CHUNK
(rows per ingest chunk, default 4096), CONTINUAL_BENCH_CHUNKS (default
16), CONTINUAL_BENCH_BUDGET_S (default 300), CONTINUAL_BENCH_OUT.
"""

import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_T0 = time.monotonic()
_BUDGET_S = float(os.environ.get("CONTINUAL_BENCH_BUDGET_S", 300))

_STATE = {
    "metric": "continual_ingest_rows_per_sec",
    "value": None,
    "unit": "rows/sec",
    "vs_baseline": None,  # serve-across-rollovers vs BENCH_serve_r01
    "workloads": {},
}


def _emit():
    try:
        from lightgbm_tpu.obs import metrics as _obs

        _STATE["metrics"] = _obs.snapshot()
    except Exception:  # noqa: BLE001 — artifact robustness first
        pass
    line = json.dumps(_STATE, default=str) + "\n"
    sys.stdout.write(line)
    sys.stdout.flush()
    out = os.environ.get("CONTINUAL_BENCH_OUT")
    if out:
        with open(out, "w") as fh:
            fh.write(line)


def _remaining():
    return _BUDGET_S - (time.monotonic() - _T0)


def _guarded(name, fn, budget_floor=10.0):
    if _remaining() < budget_floor:
        _STATE["workloads"][name] = {"skipped": "budget"}
        _emit()
        return
    try:
        fn()
    except Exception as e:  # noqa: BLE001 — artifact robustness
        _STATE["workloads"][name] = {"error": f"{type(e).__name__}: {e}"[:300]}
    _emit()


def _pcts(lat_s):
    lat = np.asarray(lat_s) * 1e3
    return (round(float(np.percentile(lat, 50)), 3),
            round(float(np.percentile(lat, 99)), 3))


def _trees_of(bst):
    s = bst.model_to_string()
    return s[s.index("Tree=0"):s.index("end of trees")]


def _setup(trees, f=16, n=20000, seed=0):
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 31,
                              "max_bin": 63, "verbosity": -1},
                      train_set=ds)
    for _ in range(trees):
        bst.update()
    return bst, ds, rng


def _chunk(rng, n, f=16):
    Xc = rng.randn(n, f)
    return Xc, (Xc[:, 0] + 0.4 * Xc[:, 1] > 0).astype(float)


def bench_parity(bst, ds, rng):
    """Runner rollovers == offline application, tree-bitwise."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.continual.refit import make_refit_entry, refit_leaves

    cr = lgb.continual_train(bst, {"append_trees": 2}, reference=ds,
                             start=False)
    chunks = [_chunk(rng, 2048) for _ in range(2)]
    cr.ingest(*chunks[0])
    cr.update("refit")
    cr.ingest(*chunks[1])
    cr.update("append")

    off = lgb.Booster(model_str=bst.model_to_string())
    off._gbdt.cfg = bst._gbdt.cfg
    entry = make_refit_entry(off._gbdt.objective,
                             off._gbdt.cfg.refit_decay_rate,
                             off._gbdt.cfg.lambda_l2)
    refit_leaves(off._gbdt, chunks[0][0], chunks[0][1], entry=entry)
    Xw = np.concatenate([c[0] for c in chunks])
    yw = np.concatenate([c[1] for c in chunks])
    off2 = lgb.train({"objective": "binary", "num_leaves": 31,
                      "max_bin": 63, "verbosity": -1},
                     lgb.Dataset(Xw, label=yw, reference=ds),
                     num_boost_round=2, init_model=off)
    ok = _trees_of(cr.booster) == _trees_of(off2)
    _STATE["workloads"]["parity"] = {
        "rollovers": 2, "tree_bitwise_vs_offline": ok}
    if not ok:
        raise AssertionError("runner rollovers diverged from the offline "
                             "application of the same primitives")
    return cr


def bench_ingest(bst, ds, rng, chunk_rows, n_chunks, tmp):
    """Streaming ingest rows/s: in-memory window vs durable CRC'd cache
    append (the append REWRITES the cache, so its cost grows with the
    cache — the artifact reports first/last chunk to show the slope)."""
    import lightgbm_tpu as lgb

    chunks = [_chunk(rng, chunk_rows) for _ in range(n_chunks)]

    cr = lgb.continual_train(bst, {}, reference=ds, start=False,
                             window_rows=chunk_rows * n_chunks)
    t0 = time.perf_counter()
    for c in chunks:
        cr.ingest(*c)
    mem_s = time.perf_counter() - t0
    mem_rps = round(chunk_rows * n_chunks / mem_s, 1)

    cache = os.path.join(tmp, "ingest.bin")
    cr2 = lgb.continual_train(bst, {}, reference=ds, start=False,
                              cache_path=cache,
                              window_rows=chunk_rows * n_chunks)
    per_chunk = []
    for c in chunks:
        t1 = time.perf_counter()
        cr2.ingest(*c)
        per_chunk.append(time.perf_counter() - t1)
    dur_rps = round(chunk_rows * n_chunks / sum(per_chunk), 1)
    _STATE["workloads"]["ingest"] = {
        "chunk_rows": chunk_rows, "chunks": n_chunks,
        "window_rows_per_sec": mem_rps,
        "durable_rows_per_sec": dur_rps,
        "durable_first_chunk_ms": round(per_chunk[0] * 1e3, 2),
        "durable_last_chunk_ms": round(per_chunk[-1] * 1e3, 2),
        "cache_bytes": os.path.getsize(cache),
    }
    _STATE["value"] = mem_rps
    _STATE["metric"] = f"continual_ingest_rows_per_sec_c{chunk_rows}"
    _emit()


def bench_update_latency(bst, ds, rng, chunk_rows):
    """Refit vs append-trees update latency (warm: the runner's cached
    refit entry and the already-compiled growers)."""
    import lightgbm_tpu as lgb

    cr = lgb.continual_train(bst, {"append_trees": 2}, reference=ds,
                             start=False)
    refit_lat, append_lat = [], []
    for _ in range(2):  # warmups: first refit + first append compile
        cr.ingest(*_chunk(rng, chunk_rows))
        cr.update("refit")
        cr.ingest(*_chunk(rng, chunk_rows))
        cr.update("append")
    for _ in range(5):
        cr.ingest(*_chunk(rng, chunk_rows))
        t0 = time.perf_counter()
        cr.update("refit")
        refit_lat.append(time.perf_counter() - t0)
    for _ in range(3):
        cr.ingest(*_chunk(rng, chunk_rows))
        t0 = time.perf_counter()
        cr.update("append")
        append_lat.append(time.perf_counter() - t0)
    r50, r99 = _pcts(refit_lat)
    a50, a99 = _pcts(append_lat)
    _STATE["workloads"]["update_latency"] = {
        "window_rows": chunk_rows,
        "refit": {"p50_ms": r50, "max_ms": round(max(refit_lat) * 1e3, 2),
                  "reps": len(refit_lat)},
        "append_2_trees": {"p50_ms": a50,
                           "max_ms": round(max(append_lat) * 1e3, 2),
                           "reps": len(append_lat)},
        "refit_vs_append_speedup": round(a50 / max(r50, 1e-9), 2),
    }
    _emit()


def bench_serve_across_rollovers(bst, ds, rng, tmp):
    """Concurrent callers through the runtime WHILE the runner publishes
    refit + append rollovers: p50/p99 across the swaps, every response
    verified against a published version, zero sheds — then compared to
    the committed BENCH_serve_r01 closed-loop baseline."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import metrics as _obs
    from lightgbm_tpu.serve import ServingRuntime

    rt = ServingRuntime(bst, max_wait_ms=2, shed_unhealthy=False)
    cr = lgb.continual_train(bst, {"append_trees": 2}, runtime=rt,
                             reference=ds, state_dir=tmp, start=False)
    Q = rng.randn(64, 16)
    slices = [Q[i * 16:(i + 1) * 16] for i in range(4)]
    for s in slices:
        rt.predict(s, raw_score=True, timeout=120)  # warm the rungs
    versions = [bst]
    lat = []
    responses = []
    stop = threading.Event()
    errs = []

    def caller():
        try:
            while not stop.is_set():
                for i, s in enumerate(slices):
                    t1 = time.perf_counter()
                    r = rt.predict(s, raw_score=True, timeout=120)
                    lat.append(time.perf_counter() - t1)
                    responses.append((i, r))
        except BaseException as e:  # noqa: BLE001
            errs.append(f"{type(e).__name__}: {e}")

    shed0 = _obs.counter("serve_shed_total").value
    threads = [threading.Thread(target=caller) for _ in range(4)]
    for t in threads:
        t.start()
    rollovers = 0
    try:
        for kind in ("refit", "append", "refit"):
            cr.ingest(*_chunk(rng, 4096))
            cr.update(kind)
            versions.append(cr.booster)
            rollovers += 1
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60)
    rt.stop()
    if errs:
        raise AssertionError(f"serving under rollover failed: {errs[:3]}")
    refs = [[v.predict(s, raw_score=True) for s in slices]
            for v in versions]
    bad = sum(1 for i, r in responses
              if not any(np.array_equal(refs[v][i], r)
                         for v in range(len(versions))))
    if bad:
        raise AssertionError(
            f"{bad}/{len(responses)} responses match no published version")
    p50, p99 = _pcts(lat)
    shed = _obs.counter("serve_shed_total").value - shed0
    row = {
        "rollovers": rollovers, "requests": len(responses),
        "rows_per_req": 16, "p50_ms": p50, "p99_ms": p99,
        "sheds_during_rollover": int(shed),
        "responses_bitwise_verified": True,
    }
    # vs the committed single-model serving baseline (same 16-row
    # closed-loop shape at C=4), when the artifact is present
    base_path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_serve_r01.json")
    if os.path.exists(base_path):
        try:
            with open(base_path) as fh:
                base = json.loads(fh.read().strip())
            b = base.get("workloads", {}).get("closed_C4", {}).get(
                "coalesced", {})
            if b:
                row["baseline_serve_r01_C4"] = {
                    "p50_ms": b.get("p50_ms"), "p99_ms": b.get("p99_ms")}
                _STATE["vs_baseline"] = round(
                    p99 / max(float(b.get("p99_ms") or 0), 1e-9), 2)
        except (ValueError, OSError):
            pass
    _STATE["workloads"]["serve_across_rollovers"] = row
    _emit()


def main():
    import tempfile

    import jax

    trees = int(os.environ.get("CONTINUAL_BENCH_TREES", 60))
    chunk_rows = int(os.environ.get("CONTINUAL_BENCH_CHUNK", 4096))
    n_chunks = int(os.environ.get("CONTINUAL_BENCH_CHUNKS", 16))
    _STATE["platform"] = jax.devices()[0].platform
    _STATE["trees"] = trees

    bst, ds, rng = _setup(trees)
    tmp = tempfile.mkdtemp(prefix="continual_bench_")

    _guarded("parity", lambda: bench_parity(bst, ds, rng),
             budget_floor=20.0)
    _guarded("ingest",
             lambda: bench_ingest(bst, ds, rng, chunk_rows, n_chunks, tmp),
             budget_floor=30.0)
    _guarded("update_latency",
             lambda: bench_update_latency(bst, ds, rng, chunk_rows),
             budget_floor=45.0)
    _guarded("serve_across_rollovers",
             lambda: bench_serve_across_rollovers(bst, ds, rng, tmp),
             budget_floor=30.0)

    # jaxpr-audit verdict (docs/ANALYSIS.md): the artifact carries proof
    # the continual_refit_leaves contract (and the rest) held at trace
    # time, next to the numbers
    def _embed_audit():
        from lightgbm_tpu.analysis.jaxpr_audit import verdict

        _STATE["jaxpr_audit"] = verdict(exec_contracts=False)
        _STATE["workloads"]["jaxpr_audit"] = {
            "ok": _STATE["jaxpr_audit"].get("ok")}

    _guarded("jaxpr_audit", _embed_audit, budget_floor=30.0)

    _STATE["elapsed_s"] = round(time.monotonic() - _T0, 1)
    _emit()
    return 0


if __name__ == "__main__":
    sys.exit(main())
