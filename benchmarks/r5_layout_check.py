"""Round-5 probe: measure the channel-first hist-state rework end-to-end.

Compares against the round-4 ledger (docs/PERF_NOTES.md):
  narrow 1M x 28, 31 leaves:  63-bin 35.1 it/s | 255-bin 11.0-11.8 it/s
  epsilon 400k x 2000, 255 leaves, 255-bin int8: 5.06 s/iter (full-pass)
                                  windowed int8: ~8.2 s/iter profiled

Timing uses a host pull of a score slice (PERF_NOTES round 4).

Usage: python benchmarks/r5_layout_check.py [narrow|epsilon|windowed]
"""

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

CACHE_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".bench_cache")


def _time_iters(bst, iters):
    import lightgbm_tpu  # noqa: F401

    t0 = time.perf_counter()
    for _ in range(iters):
        bst.update()
    _ = np.asarray(bst._gbdt._score[:8])  # force pipeline drain
    return (time.perf_counter() - t0) / iters


def narrow():
    import lightgbm_tpu as lgb

    n, f = 1_000_000, 28
    rng = np.random.RandomState(0)
    X = rng.randn(n, f).astype(np.float32)
    w = rng.randn(f) / np.sqrt(f)
    y = ((X @ w + 0.3 * rng.randn(n)) > 0).astype(np.float64)
    for mb in (63, 255):
        params = {"objective": "binary", "num_leaves": 31, "max_bin": mb,
                  "verbosity": -1, "min_data_in_leaf": 20}
        ds = lgb.Dataset(X, label=y)
        t0 = time.perf_counter()
        bst = lgb.Booster(params=params, train_set=ds)
        bst.update()
        _ = np.asarray(bst._gbdt._score[:8])
        warm = time.perf_counter() - t0
        spi = _time_iters(bst, 30)
        print(f"narrow {mb}bins: {1.0/spi:.2f} it/s ({spi*1e3:.1f} ms/iter)"
              f" warmup {warm:.0f}s", flush=True)


def _epsilon_dataset(lgb, mb):
    os.makedirs(CACHE_DIR, exist_ok=True)
    cache = os.path.join(CACHE_DIR, f"epsilon_{mb}.bin")
    params = {"max_bin": mb}
    if not os.path.exists(cache):
        rng = np.random.RandomState(1)
        ne, fe = 400_000, 2000
        Xe = rng.randn(ne, fe).astype(np.float32)
        ye = ((Xe[:, :64] @ rng.randn(64) + rng.randn(ne)) > 0).astype(
            np.float64)
        t0 = time.perf_counter()
        ds = lgb.Dataset(Xe, label=ye, params=params)
        ds.construct()
        print(f"epsilon binning took {time.perf_counter()-t0:.0f}s",
              flush=True)
        ds.save_binary(cache)
        return ds
    t0 = time.perf_counter()
    ds = lgb.Dataset(cache, params=params)
    ds.construct()
    print(f"epsilon cache reload took {time.perf_counter()-t0:.0f}s",
          flush=True)
    return ds


def epsilon(windowed=False):
    import lightgbm_tpu as lgb

    ds = _epsilon_dataset(lgb, 255)
    params = {"objective": "binary", "num_leaves": 255, "max_bin": 255,
              "verbosity": -1, "min_data_in_leaf": 20}
    if windowed:
        params["windowed_growth"] = True
    t0 = time.perf_counter()
    bst = lgb.Booster(params=params, train_set=ds)
    bst.update()
    _ = np.asarray(bst._gbdt._score[:8])
    warm = time.perf_counter() - t0
    spi = _time_iters(bst, 5)
    tag = "windowed" if windowed else "fullpass"
    print(f"epsilon 255bins {tag}: {spi:.2f} s/iter warmup {warm:.0f}s",
          flush=True)


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "narrow"
    if which == "narrow":
        narrow()
    elif which == "epsilon":
        epsilon(False)
    elif which == "windowed":
        epsilon(True)
