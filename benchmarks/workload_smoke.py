"""Throughput smoke for the non-binary baseline workloads (BASELINE.md):
LambdaRank (MSLR-like) and multiclass (Airline-like) — plus, round 9, a
SERVING smoke that asserts the warm-predict dispatch budget and parity
against the host ``Tree.predict_batch`` walk, so CI catches serving
regressions without the chip.  Prints iters/sec (train) and rows/sec
(predict) for each on the current backend."""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bench_rank(n, q_len, iters):
    import jax
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(0)
    nq = n // q_len
    n = nq * q_len
    X = rng.randn(n, 64).astype(np.float32)
    w = rng.randn(64) / 8
    rel = X @ w + 0.7 * rng.randn(n)
    # 0-4 relevance labels per query by rank within query
    y = np.zeros(n)
    for qi in range(nq):
        s = slice(qi * q_len, (qi + 1) * q_len)
        order = np.argsort(np.argsort(-rel[s]))
        y[s] = np.clip(4 - order // (q_len // 5 + 1), 0, 4)
    d = lgb.Dataset(X, label=y, group=np.full(nq, q_len))
    bst = lgb.Booster(params={"objective": "lambdarank", "num_leaves": 31,
                              "max_bin": 63, "verbosity": -1}, train_set=d)
    bst.update()
    jax.block_until_ready(bst._gbdt._score)
    t0 = time.perf_counter()
    for _ in range(iters):
        bst.update()
    jax.block_until_ready(bst._gbdt._score)
    return iters / (time.perf_counter() - t0)


def bench_multiclass(n, k, iters):
    import jax
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(1)
    X = rng.randn(n, 28).astype(np.float32)
    centers = rng.randn(k, 28)
    y = np.argmax(X @ centers.T + rng.randn(n, k), axis=1).astype(np.float64)
    d = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params={"objective": "multiclass", "num_class": k,
                              "num_leaves": 31, "max_bin": 63,
                              "verbosity": -1}, train_set=d)
    bst.update()
    jax.block_until_ready(bst._gbdt._score)
    t0 = time.perf_counter()
    for _ in range(iters):
        bst.update()
    jax.block_until_ready(bst._gbdt._score)
    return iters / (time.perf_counter() - t0)


def bench_predict(n_rows=2000, n_trees=24, iters=20):
    """Fast serving smoke (small T/N, runs off-chip in seconds): trains a
    tiny model, ASSERTS the warm-call serving budget (1 dispatch + 1 sync,
    no retrace — the tests/test_predict_budget.py contract, re-checked here
    in the artifact path) and raw-prediction parity against the host
    ``Tree.predict_batch`` f64 walk, then reports warm rows/sec."""
    import time

    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.sanitizer import DispatchCounter

    rng = np.random.RandomState(2)
    X = rng.randn(n_rows, 16)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 15,
                              "max_bin": 63, "verbosity": -1},
                      train_set=lgb.Dataset(X, label=y))
    for _ in range(n_trees):
        bst.update()
    raw = bst.predict(X, raw_score=True)  # warm: pack + bucket compile

    host = np.zeros(n_rows)
    for t in bst._gbdt._trees_for_export(0, -1):
        host += t.predict_batch(np.asarray(X, np.float64))
    err = float(np.abs(raw - host).max())
    assert err < 1e-4, f"device serving path diverged from host walk: {err}"

    with DispatchCounter() as d:
        bst.predict(X, raw_score=True)
    assert d.dispatches == 1, f"warm predict cost {d.dispatches} dispatches"
    assert d.host_syncs == 1, f"warm predict cost {d.host_syncs} syncs"
    d.assert_no_recompile("warm predict smoke")

    # the metrics snapshot bench.py / predict_bench.py embed in their
    # artifacts must be schema-valid and cover the serving keys here too
    from lightgbm_tpu.obs import metrics as _obs

    snap = _obs.snapshot()
    _obs.validate_snapshot(snap)
    for key in ("predict_requests_total", "predict_bucket_hits_total",
                "train_boost_rounds_total", "device_dispatches_total"):
        assert key in snap["counters"], f"metrics snapshot missing {key}"
    assert snap["histograms"]["predict_warm_latency_ms"]["count"] >= 1, (
        "warm predict left no latency reservoir samples")
    # round 11: per-bucket latency labels + span tracing ride the same run
    assert any(k.startswith('predict_warm_latency_ms{bucket="')
               for k in snap["histograms"]), (
        "per-bucket warm-latency labels missing from the snapshot")
    from lightgbm_tpu.obs import trace as _tr

    assert _tr.spans("boost_round") and _tr.spans("predict.raw"), (
        "span tracing left no boost_round/predict spans")

    t0 = time.perf_counter()
    for _ in range(iters):
        bst.predict(X, raw_score=True)
    return n_rows * iters / (time.perf_counter() - t0), err


def bench_ooc(n_rows=3000, n_feat=8, rounds=3):
    """Out-of-core smoke (round 12, runs off-chip in seconds): trains
    from a ``save_binary`` cache in BOTH out-of-core regimes — resident
    (stream-assembled device matrix) and spill (chunked-histogram
    grower) — ASSERTS bitwise model parity against plain in-memory
    training, checks the snapshot carries the OOC keys, and reports
    streamed rows/sec for the spill run."""
    import tempfile

    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import metrics as _obs

    rng = np.random.RandomState(4)
    X = rng.randn(n_rows, n_feat)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
              "verbosity": -1, "feature_pre_filter": False}

    def train(ds):
        bst = lgb.Booster(params=params, train_set=ds)
        for _ in range(rounds):
            bst.update()
        return bst.model_to_string()

    want = train(lgb.Dataset(X, label=y, params=dict(params)))
    with tempfile.TemporaryDirectory() as td:
        cache = os.path.join(td, "smoke.bin")
        base = lgb.Dataset(X, label=y, params=dict(params))
        base.construct()
        base.save_binary(cache)

        resident = lgb.Dataset(cache, params=dict(
            params, out_of_core=True, out_of_core_chunk_rows=257))
        got = train(resident)
        assert got == want, "resident OOC diverged from in-memory"
        assert resident.bins is None, "resident OOC materialized host bins"

        spill = lgb.Dataset(cache, params=dict(
            params, out_of_core=True, max_rows_in_hbm=n_rows // 4,
            out_of_core_chunk_rows=512))
        # delta, not the cumulative process-global counter: earlier OOC
        # work in this process must not inflate rows/sec (the pattern
        # ooc_bench.bench_spill_train uses)
        passes0 = _obs.counter("train_ooc_passes_total").value
        t0 = time.perf_counter()
        got = train(spill)
        dt = time.perf_counter() - t0
        assert got == want, "spill OOC diverged from in-memory"
        assert spill.ooc_spill and spill.bins_device is None

    snap = _obs.snapshot()
    _obs.validate_snapshot(snap)
    for key in ("train_ooc_passes_total", "train_ooc_chunks_total"):
        assert key in snap["counters"], f"metrics snapshot missing {key}"
    passes = snap["counters"]["train_ooc_passes_total"] - passes0
    return n_rows * passes / dt, passes


def bench_serve(n_rows=600, n_feat=8, n_trees=12):
    """Round-18 serving-loop smoke: concurrent requests through the
    coalescing runtime must come back BITWISE equal to individual
    predicts, the queued set must coalesce into fewer batches than
    requests, and the snapshot must carry the serve keys — so an
    off-chip CI run catches serving-loop regressions in the artifact
    path, not just in tier-1."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import metrics as _obs
    from lightgbm_tpu.serve import ServingRuntime

    rng = np.random.RandomState(11)
    X = rng.randn(n_rows, n_feat)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 15,
                              "max_bin": 63, "verbosity": -1},
                      train_set=lgb.Dataset(X, label=y))
    for _ in range(n_trees):
        bst.update()

    parts = [X[i * 16:(i + 1) * 16] for i in range(8)]
    want = [bst.predict(p, raw_score=True) for p in parts]
    batches0 = _obs.counter("serve_batches_total").value
    rt = ServingRuntime(bst, max_wait_ms=100, start=False,
                        shed_unhealthy=False)
    handles = [rt.submit(p, raw_score=True) for p in parts]
    t0 = time.perf_counter()
    rt.start()
    got = [rt.result(h, timeout=120) for h in handles]
    dt = time.perf_counter() - t0
    rt.stop()
    for w, g in zip(want, got):
        assert np.array_equal(w, g), "coalesced response diverged"
    batches = _obs.counter("serve_batches_total").value - batches0
    assert batches < len(parts), (
        f"8 queued requests dispatched as {batches} batches — no "
        "coalescing happened")

    snap = _obs.snapshot()
    _obs.validate_snapshot(snap)
    for key in ("serve_requests_total", "serve_batches_total",
                "serve_coalesced_rows_total"):
        assert key in snap["counters"], f"metrics snapshot missing {key}"
    assert "serve_queue_depth" in snap["gauges"]
    assert snap["histograms"]["serve_batch_occupancy"]["count"] >= 1
    assert any(k.startswith('serve_request_latency_ms{tenant="')
               for k in snap["histograms"]), (
        "per-tenant serve latency labels missing from the snapshot")
    # round-25 phase breakdown: every request crossed all five phases,
    # so each labeled reservoir must have fired at least once
    for ph in ("queue", "coalesce", "staging", "dispatch", "sliceout"):
        key = _obs.labeled("serve_phase_ms", phase=ph)
        assert snap["histograms"].get(key, {}).get("count", 0) >= 1, (
            f"phase breakdown missing {key}")
    ex = snap["histograms"]["serve_request_latency_ms"].get("exemplar")
    assert ex and ex.get("trace_id"), (
        "serve_request_latency_ms carries no trace-id exemplar")
    return len(parts), batches, sum(p.shape[0] for p in parts) / dt


def bench_fleet_serve(n_rows=600, n_feat=8, n_trees=12):
    """Round-23 fleet-serve smoke: a 2-replica ServingFleet survives an
    injected replica death with ZERO lost requests and bitwise parity
    against individual predicts, requeues the failed batch, restarts the
    replacement, and leaves the fleet snapshot keys — so an off-chip CI
    run catches serve-path resilience regressions in the artifact path,
    not just in tier-1."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import metrics as _obs
    from lightgbm_tpu.serve import ServingFleet
    from lightgbm_tpu.utils import faults as _flt

    rng = np.random.RandomState(23)
    X = rng.randn(n_rows, n_feat)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 15,
                              "max_bin": 63, "verbosity": -1},
                      train_set=lgb.Dataset(X, label=y))
    for _ in range(n_trees):
        bst.update()

    parts = [X[i * 16:(i + 1) * 16] for i in range(8)]
    want = [bst.predict(p, raw_score=True) for p in parts]
    d0 = _obs.counter("serve_replica_deaths_total").value
    q0 = _obs.counter("serve_requeues_total").value
    fl = ServingFleet(bst, replicas=2, max_wait_ms=20, hedge_ms=0,
                      restart_backoff_ms=50, shed_unhealthy=False)
    t0 = time.perf_counter()
    try:
        # warm with the fault env UNSET (fire() only counts armed sites)
        fl.predict(X[:16], raw_score=True, timeout=120)
        os.environ["LGBMTPU_FAULT"] = "replica_death:0"
        handles = [fl.submit(p, raw_score=True) for p in parts]
        got = [fl.result(h, timeout=120) for h in handles]
        for w, g in zip(want, got):
            assert np.array_equal(w, g), (
                "fleet response diverged across the injected death")
        assert _obs.counter("serve_replica_deaths_total").value == d0 + 1
        assert _obs.counter("serve_requeues_total").value > q0, (
            "the dead replica's batch was never requeued")
        deadline = time.monotonic() + 15
        while (any(r.state != 0 for r in fl._replicas)
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert fl.stats()["replicas"] == {0: "active", 1: "active"}, (
            "replacement replica never rejoined rotation")
    finally:
        os.environ.pop("LGBMTPU_FAULT", None)
        _flt.reset()
        fl.stop()
    dt = time.perf_counter() - t0

    snap = _obs.snapshot()
    _obs.validate_snapshot(snap)
    for key in ("serve_replica_deaths_total", "serve_requeues_total",
                "serve_replica_restarts_total", "faults_injected_total"):
        assert key in snap["counters"], f"metrics snapshot missing {key}"
    assert "serve_fleet_degraded" in snap["gauges"]
    assert any(k.startswith('serve_replica_batch_ms{replica="')
               for k in snap["histograms"]), (
        "per-replica batch latency labels missing from the snapshot")
    return len(parts), dt


def bench_continual(n_rows=600, n_feat=6, n_trees=6):
    """Round-19 continual smoke: a refit + an append rollover through a
    live ServingRuntime must keep every response bitwise equal to a
    published ensemble's cold predict, drop the staleness gauge to zero,
    and leave the continual snapshot keys — so an off-chip CI run
    catches train-while-serving regressions in the artifact path."""
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import metrics as _obs
    from lightgbm_tpu.serve import ServingRuntime

    rng = np.random.RandomState(19)
    X = rng.randn(n_rows, n_feat)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 15,
                              "max_bin": 63, "verbosity": -1},
                      train_set=ds)
    for _ in range(n_trees):
        bst.update()
    rt = ServingRuntime(bst, max_wait_ms=2, shed_unhealthy=False)
    cr = lgb.continual_train(bst, {"append_trees": 2}, runtime=rt,
                             reference=ds, start=False)
    Q = rng.randn(32, n_feat)
    t0 = time.perf_counter()
    for kind in ("refit", "append"):
        Xc = rng.randn(200, n_feat)
        yc = (Xc[:, 0] + 0.4 * Xc[:, 1] > 0).astype(float)
        cr.ingest(Xc, yc)
        assert _obs.gauge("model_staleness_rows").value >= 200
        done = cr.update(kind)
        assert done == kind
        assert _obs.gauge("model_staleness_rows").value == 0.0
        got = rt.predict(Q, raw_score=True, timeout=120)
        assert np.array_equal(
            got, cr.booster.predict(Q, raw_score=True)), (
            f"served response diverged from the {kind}-published ensemble")
    dt = time.perf_counter() - t0
    rt.stop()
    assert cr.booster.num_trees() == n_trees + 2

    snap = _obs.snapshot()
    _obs.validate_snapshot(snap)
    for key in ("continual_rollovers_total", "continual_refits_total",
                "continual_appends_total", "continual_ingested_rows_total"):
        assert key in snap["counters"], f"metrics snapshot missing {key}"
    for key in ("model_staleness_rows", "model_staleness_s"):
        assert key in snap["gauges"], f"metrics snapshot missing {key}"
    assert len(_obs.events("continual_rollover")) == 2
    return 2, cr.booster.num_trees(), dt


def main():
    n = int(os.environ.get("SMOKE_ROWS", 1_000_000))
    iters = int(os.environ.get("SMOKE_ITERS", 10))
    which = (sys.argv[1].split(",") if len(sys.argv) > 1
             else ["rank", "multiclass", "predict", "serve", "ooc",
                   "continual", "fleet_serve"])
    if "rank" in which:
        ips = bench_rank(n, q_len=128, iters=iters)
        print(f"lambdarank {n//1000}k rows x64f q128 63bins: {ips:.2f} iters/sec", flush=True)
    if "multiclass" in which:
        ips = bench_multiclass(n, k=5, iters=iters)
        print(f"multiclass5 {n//1000}k rows x28f 63bins: {ips:.2f} iters/sec (5 trees/iter)", flush=True)
    if "predict" in which:
        rps, err = bench_predict()
        print(f"predict 2k rows x16f T24: {rps:.0f} rows/sec warm "
              f"(1 dispatch/call, host-walk parity {err:.1e})", flush=True)
    if "serve" in which:
        reqs, batches, rps = bench_serve()
        print(f"serve 8x16-row concurrent requests: {batches} coalesced "
              f"batch(es), bitwise parity, {rps:.0f} rows/sec", flush=True)
    if "ooc" in which:
        rps, passes = bench_ooc()
        print(f"out_of_core 3k rows x8f: {rps:.0f} streamed rows/sec spill "
              f"({passes} hist passes, resident+spill bitwise parity)",
              flush=True)
    if "continual" in which:
        rollovers, trees, dt = bench_continual()
        print(f"continual 600 rows x6f: {rollovers} zero-downtime "
              f"rollovers (refit+append) -> {trees} trees, served "
              f"bitwise, staleness drops, snapshot keys ok ({dt:.1f}s)",
              flush=True)
    if "fleet_serve" in which:
        reqs, dt = bench_fleet_serve()
        print(f"fleet_serve 2 replicas x{reqs} requests: injected replica "
              f"death, 0 lost, bitwise parity, requeued + restarted, "
              f"snapshot keys ok ({dt:.1f}s)", flush=True)


if __name__ == "__main__":
    main()
