"""Out-of-core benchmark: the round-12 data-path levers.

Two levers, each emitting BENCH-style rows (bench.py contract — a full
JSON snapshot line printed + flushed after EVERY completed workload, so a
driver timeout keeps everything measured so far):

* ``stream_ingest_<chunk>`` — rows/sec assembling the device matrix from
  a ``save_binary`` cache through the chunked reader + one-deep upload
  prefetch (io/stream.py), per chunk size.  The resident-regime ingest
  cost: how fast a cache becomes a trainable device matrix.
* ``spill_train_<chunk>`` — spill-regime training throughput
  (ops/treegrow_ooc.py): streamed rows/sec across all histogram passes
  of a small boosting run, per chunk size, with bitwise parity vs
  in-memory training asserted in the artifact path itself.

Env knobs: OOC_BENCH_ROWS (default 120k), OOC_BENCH_FEATURES (default
16), OOC_BENCH_CHUNKS (csv, default "4096,16384,65536"),
OOC_BENCH_BUDGET_S (default 300), OOC_BENCH_OUT (also write the final
snapshot to a file, e.g. BENCH_ooc_r01.json).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_T0 = time.monotonic()
_BUDGET_S = float(os.environ.get("OOC_BENCH_BUDGET_S", 300))

_STATE = {
    "metric": "ooc_stream_rows_per_sec",
    "value": None,
    "unit": "rows/sec",
    "vs_baseline": None,  # no reference out-of-core anchor (BASELINE.md)
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
    out = os.environ.get("OOC_BENCH_OUT")
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


def _make_cache(n, f, path):
    """Bin a synthetic dataset once and save_binary it — every lever
    streams from this cache, like a real out-of-core run would."""
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(0)
    X = rng.randn(n, f).astype(np.float32)
    y = ((X[:, 0] + 0.5 * X[:, 1] + 0.3 * rng.randn(n)) > 0).astype(
        np.float64)
    ds = lgb.Dataset(X, label=y, params={"max_bin": 255, "verbosity": -1})
    ds.construct()
    ds.save_binary(path)
    return X, y


def bench_stream_ingest(cache, n, chunks):
    """Resident-regime ingest: cache -> assembled device matrix."""
    import jax
    import lightgbm_tpu as lgb

    for chunk in chunks:
        name = f"stream_ingest_{chunk}"
        if _remaining() < 10:
            _STATE["workloads"][name] = {"skipped": "budget"}
            continue
        t0 = time.perf_counter()
        ds = lgb.Dataset(cache, params={
            "max_bin": 255, "verbosity": -1, "out_of_core": True,
            "out_of_core_chunk_rows": chunk})
        ds.construct()
        jax.block_until_ready(ds.bins_device)
        dt = time.perf_counter() - t0
        _STATE["workloads"][name] = {
            "rows_per_sec": round(n / dt, 1), "ingest_s": round(dt, 3),
            "chunk_rows": chunk}
        if _STATE["value"] is None or n / dt > _STATE["value"]:
            _STATE["value"] = round(n / dt, 1)
        _emit()


def bench_spill_train(cache, X, y, n, chunks, rounds=2):
    """Spill-regime chunked-histogram training: streamed rows/sec across
    all histogram passes, parity-asserted against in-memory training."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import metrics as _obs

    params = {"objective": "binary", "num_leaves": 15, "max_bin": 255,
              "verbosity": -1, "feature_pre_filter": False,
              "min_data_in_leaf": 20}

    def train(ds):
        bst = lgb.Booster(params=dict(params, **(
            {"out_of_core": True, "max_rows_in_hbm": 1,
             "out_of_core_chunk_rows": ds_chunk}
            if ds is not mem_ds else {})), train_set=ds)
        for _ in range(rounds):
            bst.update()
        return bst.model_to_string()

    mem_ds = lgb.Dataset(X, label=y, params=dict(params))
    ds_chunk = 0
    want = train(mem_ds)

    for chunk in chunks:
        name = f"spill_train_{chunk}"
        if _remaining() < 20:
            _STATE["workloads"][name] = {"skipped": "budget"}
            continue
        ds_chunk = chunk
        ds = lgb.Dataset(cache, params=dict(
            params, out_of_core=True, max_rows_in_hbm=1,
            out_of_core_chunk_rows=chunk))
        passes0 = _obs.counter("train_ooc_passes_total").value
        t0 = time.perf_counter()
        got = train(ds)
        dt = time.perf_counter() - t0
        passes = _obs.counter("train_ooc_passes_total").value - passes0
        _STATE["workloads"][name] = {
            "streamed_rows_per_sec": round(n * passes / dt, 1),
            "train_s": round(dt, 3), "hist_passes": passes,
            "chunk_rows": chunk, "bitwise_parity": got == want}
        if got != want:
            raise AssertionError(
                f"spill training diverged from in-memory at chunk={chunk}")
        _emit()


def main():
    import jax

    n = int(os.environ.get("OOC_BENCH_ROWS", 120_000))
    f = int(os.environ.get("OOC_BENCH_FEATURES", 16))
    chunks = [int(c) for c in os.environ.get(
        "OOC_BENCH_CHUNKS", "4096,16384,65536").split(",")]
    platform = jax.devices()[0].platform
    _STATE["platform"] = platform

    cache = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                         ".bench_cache", f"ooc_{n}x{f}.bin")
    os.makedirs(os.path.dirname(cache), exist_ok=True)
    t0 = time.perf_counter()
    X, y = _make_cache(n, f, cache)
    _STATE["workloads"]["make_cache"] = {
        "rows": n, "features": f, "bin_and_save_s":
        round(time.perf_counter() - t0, 2)}
    _emit()

    _guarded("stream_ingest", lambda: bench_stream_ingest(cache, n, chunks))
    _guarded("spill_train",
             lambda: bench_spill_train(cache, X, y, n, chunks),
             budget_floor=30.0)

    _STATE["elapsed_s"] = round(time.monotonic() - _T0, 1)
    _emit()
    try:
        os.remove(cache)  # the synthetic cache is a scratch artifact
    except OSError:
        pass


if __name__ == "__main__":
    main()
