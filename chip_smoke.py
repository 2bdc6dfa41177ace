"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process drives the main path once through the entry points a user calls
(``lgb.Dataset``, ``lgb.train``, ``Booster.predict``, ``lgb.serve``) at the
full width of the shapes the repo claims, with data generated from a seed,
and checks each result by the repo's own means.  It passes only on a TPU and
only if no fallback fired: the kernel-degradation registry is untouched, the
fused step was never disabled, and every booster ran the grower it names.

    python chip_smoke.py        # on the chip, through the chip tool

Exit code 0 and a last stdout line of exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
mean every leg passed; the line before it, ``[chip_smoke] report {...}``,
carries versions, the cache directory and per-leg times with compile apart.
Without an accelerator it exits non-zero before doing any work and prints
no result.  No leg catches an exception.

Every leg is a function of its sizes, so ``tests/test_chip_smoke.py`` runs
each at toy size on the CPU backend; the device and no-fallback assertions
live in :func:`check_no_fallback`, which only ``main`` calls.
"""

from __future__ import annotations

import importlib.metadata
import json
import os
import sys
import tempfile
import time
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import metrics as obs_metrics
from lightgbm_tpu.obs import server as obs_server
from lightgbm_tpu.ops import split as split_mod
from lightgbm_tpu.ops.hist_pallas import (bins_shadow,
                                          histogram_pallas_multi,
                                          histogram_pallas_multi_quantized,
                                          recommended_leaf_tile)
from lightgbm_tpu.parallel.mesh import make_mesh
from lightgbm_tpu.utils import degrade
from lightgbm_tpu.utils.compile_cache import use_compile_cache
from lightgbm_tpu.utils.sanitizer import CompileCounter

# (features, bins, leaves): the narrow block of the primary shape and the
# Epsilon block (fifteen 128-feature kernel calls plus a ragged 80)
KERNEL_SHAPES = ((28, 256, 31), (2000, 256, 255))
NARROW_ROWS = 1_000_000
NARROW_FEATURES = 28
# host binning is ~0.4 us per value: 400k x 2000 would take minutes, so the
# wide leg keeps the full width and depth and cuts the rows
WIDE_ROWS = 32_768
WIDE_FEATURES = 2000
# validation AUC after ten rounds at learning_rate 0.1 on the seeded data
# below: 0.867 at 20k rows, 0.877 at 200k (CPU, rounds grower, both bin
# widths).  The floors catch a broken grower, not a drifting one.
NARROW_AUC_FLOOR = 0.85
WIDE_AUC_FLOOR = 0.75
# the categorical leg: (integer columns with missing values, levels of each
# categorical column, parameters).  Criteo's 13 + 26 (its published level
# counts capped at 255 bins) at the benchmark cell's settings, where PR 36
# found the fault the leg guards against, and a narrower table on the chip's
# defaults; other column counts compile to other programs
CLICK_ROWS = 500_000
CLICK_CELL = dict(tree_growth_mode="rounds", hist_precision="f32",
                  use_quantized_grad=False, fused_training=False)
CLICK_SHAPES = {
    "13+26": (13, (255, 255, 255, 255, 255, 24, 255, 255, 3, 255, 255, 255,
                   255, 27, 255, 255, 10, 255, 255, 4, 255, 18, 15, 255, 105,
                   255), CLICK_CELL),
    "4+8": (4, (3, 4, 24, 105, 255, 27, 10, 255), {}),
}
# tolerances of __graft_entry__'s sharded-vs-serial tree comparison
LEAF_RTOL = LEAF_ATOL = 2e-3


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def device_block() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def versions() -> dict:
    return {pkg: importlib.metadata.version(pkg)
            for pkg in ("jax", "jaxlib", "libtpu")}


def cache_entries(cache_dir: str) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def auc(y: np.ndarray, score: np.ndarray) -> float:
    """Rank-sum AUC (ties broken by order: scores here are continuous)."""
    order = np.argsort(score, kind="stable")
    rank = np.empty(len(score), np.float64)
    rank[order] = np.arange(1, len(score) + 1)
    pos = y > 0.5
    n_pos, n_neg = int(pos.sum()), int((~pos).sum())
    return float((rank[pos].sum() - n_pos * (n_pos + 1) / 2) / (n_pos * n_neg))


def booster_flags(bst) -> dict:
    """What the no-fallback check reads off a trained booster."""
    g = bst._gbdt
    return {"on_tpu": bool(g._on_tpu), "use_fast": bool(g._use_fast),
            "use_fast_dp": bool(g._use_fast_dp),
            "fused_eligible": bool(g._fused_eligible(None)),
            "fused_built": g._fused_step is not None,
            "fused_disabled": bool(g._fused_disabled),
            "quantized": bool(g.cfg.use_quantized_grad)}


# ---------------------------------------------------------------------------
# leg 1: the two histogram kernels, called directly, outside every net
# ---------------------------------------------------------------------------

def hist_oracle(bins, chans, leaf, tile, num_bins):
    """numpy per-leaf histograms (tile, C, F, B) in float64, the oracle of
    bench.py's kernel smoke extended to every leaf, feature and channel."""
    n, f = bins.shape
    flat = ((leaf[:, None].astype(np.int64) * f + np.arange(f)[None, :])
            * num_bins + bins).ravel()
    size = tile * f * num_bins
    out = [np.bincount(flat, weights=np.repeat(c.astype(np.float64), f),
                       minlength=size).reshape(tile, f, num_bins)
           for c in chans]
    return np.stack(out, axis=1)


def leg_kernels(n_rows: int = 4096, shapes=KERNEL_SHAPES) -> dict:
    out = {}
    t_leg = time.perf_counter()
    for f, b, leaves in shapes:
        rng = np.random.RandomState(7)
        bins = rng.randint(0, b, size=(n_rows, f)).astype(np.int16)
        g = rng.randn(n_rows).astype(np.float32)
        h = (np.abs(rng.randn(n_rows)) + 0.1).astype(np.float32)
        gq = rng.randint(-8, 9, size=n_rows).astype(np.int8)
        hq = rng.randint(0, 17, size=n_rows).astype(np.int8)
        # nine rows in ten of the first half, one in ten of the second: a
        # row tile that takes the kernel's dense product and one that packs
        mask = rng.rand(n_rows) < np.where(
            np.arange(n_rows) < n_rows // 2, 0.9, 0.1)
        m = mask.astype(np.float64)

        tile = recommended_leaf_tile(b, f, leaves)
        leaf = rng.randint(0, tile, size=n_rows).astype(np.int32)
        want = hist_oracle(bins, [g * m, h * m, m], leaf, tile, b)
        tile_q = recommended_leaf_tile(b, f, leaves, quantized=True)
        leaf_q = rng.randint(0, tile_q, size=n_rows).astype(np.int32)
        want_q = hist_oracle(bins, [gq * m, hq * m, m], leaf_q, tile_q, b)
        # the packed tile reads its bins feature-major: from the shadow the
        # call builds of (N, F), and from one handed in as a grower hands
        # Dataset.bins_device_t
        first = None  # the times reported are the first calls', which compile
        for shadow in (None, bins_shadow(jnp.asarray(bins))):
            t0 = time.perf_counter()
            got = np.asarray(histogram_pallas_multi(
                jnp.asarray(bins), jnp.asarray(g), jnp.asarray(h),
                jnp.asarray(mask), jnp.asarray(leaf), 0, tile, b,
                bins_t=shadow))
            t_f32 = time.perf_counter() - t0
            assert got.shape == (tile, 3, f, b), got.shape
            # bf16x2-split products carry ~17 mantissa bits, f32 accumulation
            np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3)
            np.testing.assert_array_equal(got[:, 2], want[:, 2])  # counts

            t0 = time.perf_counter()
            got_q = np.asarray(histogram_pallas_multi_quantized(
                jnp.asarray(bins), jnp.asarray(gq), jnp.asarray(hq),
                jnp.asarray(mask), jnp.asarray(leaf_q), 0, tile_q, b,
                bins_t=shadow))
            t_q = time.perf_counter() - t0
            assert got_q.dtype == np.int32
            assert got_q.shape == (tile_q, 3, f, b)
            np.testing.assert_array_equal(got_q, want_q.astype(np.int64))
            first = first or (t_f32, t_q)
        t_f32, t_q = first

        out[f"{f}x{b}"] = {"leaf_tile_f32": tile, "leaf_tile_int8": tile_q,
                           "f32_first_call_s": round(t_f32, 2),
                           "int8_first_call_s": round(t_q, 2)}
        say(f"kernels {f}x{b}: f32 tile {tile} {t_f32:.1f}s, "
            f"int8 tile {tile_q} {t_q:.1f}s, both match the numpy oracle, "
            "the shadow built and handed in")
    out["wall_s"] = round(time.perf_counter() - t_leg, 2)
    return out


# ---------------------------------------------------------------------------
# legs 2, 3, 5: training through lgb.train
# ---------------------------------------------------------------------------

def narrow_data(n_rows: int, n_valid: int):
    """bench.py's primary workload: Higgs-shaped binary, linear logit.
    The weights are drawn first, so every row count poses the same
    problem and a floor found at a small size holds at the full one."""
    rng = np.random.RandomState(0)
    n = n_rows + n_valid
    w = rng.randn(NARROW_FEATURES) / np.sqrt(NARROW_FEATURES)
    X = rng.randn(n, NARROW_FEATURES).astype(np.float32)
    y = ((X @ w + 0.3 * rng.randn(n)) > 0).astype(np.float64)
    return X[:n_rows], y[:n_rows], X[n_rows:], y[n_rows:]


def wide_data(n_rows: int, n_features: int):
    """bench.py's Epsilon-shaped generator: 64 informative columns."""
    rng = np.random.RandomState(1)
    k = min(64, n_features)
    w = rng.randn(k)
    X = rng.randn(n_rows, n_features).astype(np.float32)
    y = ((X[:, :k] @ w + rng.randn(n_rows)) > 0).astype(np.float64)
    return X, y


def timed_train(params, train, rounds, valid=None):
    """lgb.train with a stamp per iteration, taken once the iteration's
    scores are on the device; the first one carries tracing and
    compilation."""
    stamps, evals = [], {}

    def stamp(env):
        jax.block_until_ready(env.model._gbdt._score)
        stamps.append(time.perf_counter())

    callbacks = [stamp]
    if valid is not None:
        callbacks.append(lgb.record_evaluation(evals))
    with CompileCounter() as cc:
        t0 = time.perf_counter()
        bst = lgb.train(params, train, rounds,
                        valid_sets=[valid] if valid is not None else None,
                        valid_names=["valid"] if valid is not None else None,
                        callbacks=callbacks)
        t1 = time.perf_counter()
    times = {"wall_s": round(t1 - t0, 2),
             "first_iter_s": round(stamps[0] - t0, 2),
             "rest_s": round(t1 - stamps[0], 2),
             "compiles": cc.compiles, "cache_hits": cc.cache_hits,
             "backend_compiles": cc.compiles - cc.cache_hits}
    return bst, times, evals


def save_load_bit_exact(bst, X) -> np.ndarray:
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "model.txt")
        bst.save_model(path)
        reloaded = lgb.Booster(model_file=path)
    p = bst.predict(X)
    np.testing.assert_array_equal(p, reloaded.predict(X))
    return p


def leg_train_narrow(max_bin: int, n_rows: int = NARROW_ROWS,
                     rounds: int = 10, **overrides):
    n_valid = max(n_rows // 10, 1000)
    Xt, yt, Xv, yv = narrow_data(n_rows, n_valid)
    t0 = time.perf_counter()
    train = lgb.Dataset(Xt, label=yt, params={"max_bin": max_bin})
    valid = lgb.Dataset(Xv, label=yv, reference=train)
    train.construct()
    valid.construct()
    bin_s = time.perf_counter() - t0
    params = dict(objective="binary", metric="auc", num_leaves=31,
                  learning_rate=0.1, min_data_in_leaf=20, max_bin=max_bin,
                  verbosity=-1, **overrides)
    bst, times, evals = timed_train(params, train, rounds, valid)
    assert bst.num_trees() == rounds, bst.num_trees()
    p = save_load_bit_exact(bst, Xv)
    assert np.isfinite(p).all() and p.shape == (n_valid,)
    a = auc(yv, p)
    # the device evaluator's AUC and the host's over the same predictions
    assert abs(evals["valid"]["auc"][-1] - a) < 2e-3, (evals, a)
    assert a > NARROW_AUC_FLOOR, a
    out = dict(times, rows=n_rows, max_bin=max_bin, rounds=rounds,
               bin_s=round(bin_s, 2), auc=round(a, 5),
               flags=booster_flags(bst))
    say(f"train narrow {n_rows}x{NARROW_FEATURES} max_bin={max_bin}: "
        f"auc {a:.4f}, first iter {times['first_iter_s']}s, rest "
        f"{times['rest_s']}s, {times['backend_compiles']} backend compiles, "
        "save/load bit-exact")
    return out, bst, (Xv, yv)


def leg_train_wide(n_rows: int = WIDE_ROWS, n_features: int = WIDE_FEATURES,
                   rounds: int = 3, num_leaves: int = 255, **overrides):
    X, y = wide_data(n_rows, n_features)
    t0 = time.perf_counter()
    train = lgb.Dataset(X, label=y, params={"max_bin": 255})
    train.construct()
    bin_s = time.perf_counter() - t0
    params = dict(objective="binary", num_leaves=num_leaves, max_bin=255,
                  learning_rate=0.1, min_data_in_leaf=20, verbosity=-1,
                  **overrides)
    bst, times, _ = timed_train(params, train, rounds)
    assert bst.num_trees() == rounds, bst.num_trees()
    sample = slice(0, min(n_rows, 8192))
    p = save_load_bit_exact(bst, X[sample])
    assert np.isfinite(p).all()
    a = auc(y[sample], p)
    assert a > WIDE_AUC_FLOOR, a
    leaves = [int(t.num_leaves) for t in bst._gbdt.models]
    assert min(leaves) > num_leaves // 4, leaves
    out = dict(times, rows=n_rows, features=n_features, rounds=rounds,
               num_leaves=num_leaves, bin_s=round(bin_s, 2),
               train_auc=round(a, 5), leaves_per_tree=leaves,
               flags=booster_flags(bst))
    say(f"train wide {n_rows}x{n_features} x255 bins x{num_leaves} leaves: "
        f"train auc {a:.4f}, leaves {leaves}, first iter "
        f"{times['first_iter_s']}s, rest {times['rest_s']}s, "
        f"int8={out['flags']['quantized']}")
    return out, bst


# ---------------------------------------------------------------------------
# leg 4: predict against the host walk, and the serving runtime
# ---------------------------------------------------------------------------

def host_walk(model_path: str, X: np.ndarray) -> np.ndarray:
    """Raw margin from the saved model's trees, walked on the host in
    float64 (Tree.predict_batch), no device involved."""
    trees = lgb.Booster(model_file=model_path)._gbdt.models
    X64 = np.asarray(X, np.float64)
    return sum(t.predict_batch(X64) for t in trees)


def leg_predict_serve(bst, n_predict: int = 100_000,
                      n_requests: int = 32) -> dict:
    rng = np.random.RandomState(11)
    X = rng.randn(n_predict, bst.num_feature()).astype(np.float32)
    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "model.txt")
        bst.save_model(path)

        t0 = time.perf_counter()
        raw = bst.predict(X, raw_score=True)
        out["predict_first_s"] = round(time.perf_counter() - t0, 2)
        t0 = time.perf_counter()
        raw2 = bst.predict(X, raw_score=True)
        out["predict_warm_s"] = round(time.perf_counter() - t0, 3)
        np.testing.assert_array_equal(raw, raw2)
        want = host_walk(path, X)
        # device sums f32 leaf values, the host f64; a row whose value sits
        # within one f32 ulp above a threshold may take the other branch
        # (models/gbdt.py::_f32_threshold_upper), so a handful may differ
        off = np.abs(raw - want) > 1e-4
        assert off.sum() <= max(n_predict // 10_000, 1), int(off.sum())
        out["predict_rows"] = n_predict
        out["predict_rows_off_host_walk"] = int(off.sum())
        say(f"predict {n_predict} rows: first {out['predict_first_s']}s, "
            f"warm {out['predict_warm_s']}s, {int(off.sum())} rows off the "
            "host walk")

        sizes = [1, 3, 8, 17, 64, 200, 1000, 4096]
        starts = rng.randint(0, n_predict - max(sizes), size=n_requests)
        reqs = [X[s:s + sizes[i % len(sizes)]] for i, s in enumerate(starts)]
        want_each = [bst.predict(r) for r in reqs]  # also warms the ladder
        rt = lgb.serve(path, {"serve_max_wait_ms": 3, "metrics_port": 0,
                              "verbosity": -1})
        try:
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=n_requests) as pool:
                futures = [pool.submit(rt.predict, r, timeout=300)
                           for r in reqs]
                got = [f.result(timeout=600) for f in futures]
            out["serve_s"] = round(time.perf_counter() - t0, 2)
            for w, g_ in zip(want_each, got):
                np.testing.assert_array_equal(w, g_)
            health = json.load(urllib.request.urlopen(
                obs_server.get_server().url("/healthz"), timeout=10))
            assert health["status"] == "ok", health
        finally:
            rt.stop()
            obs_server.stop_server()
        out["serve_requests"] = n_requests
        say(f"serve: {n_requests} concurrent requests of 1..4096 rows "
            f"bit-equal to individual predicts in {out['serve_s']}s, "
            "/healthz ok")
    return out


# ---------------------------------------------------------------------------
# leg 5: the same training path over every chip of the host
# ---------------------------------------------------------------------------

def leg_multichip(ref_bst, ref_out: dict, valid, n_rows: int = NARROW_ROWS,
                  rounds: int = 10, **overrides) -> dict:
    """Leg 2 at 255 bins with tree_learner=data over jax.devices(),
    compared with the one-chip booster ``ref_bst``."""
    n_dev = jax.device_count()
    Xv, yv = valid
    Xt, yt, _, _ = narrow_data(n_rows, len(yv))
    train = lgb.Dataset(Xt, label=yt, params={"max_bin": 255})
    params = dict(objective="binary", num_leaves=31, learning_rate=0.1,
                  min_data_in_leaf=20, max_bin=255, verbosity=-1,
                  tree_learner="data", **overrides)
    bst, times, _ = timed_train(params, train, rounds)
    g = bst._gbdt
    sd = g._dp
    assert sd is not None, "tree_learner=data built no ShardedData"
    assert len(sd.bins.sharding.device_set) == n_dev
    shard_rows = {s.data.shape[0] for s in sd.bins.addressable_shards}
    assert shard_rows == {sd.padded // n_dev}, shard_rows

    same = 0
    for i, (a, b) in enumerate(zip(ref_bst._gbdt.models, g.models)):
        if not (a.num_leaves == b.num_leaves
                and np.array_equal(a.split_feature, b.split_feature)
                and np.array_equal(a.threshold, b.threshold)):
            break
        np.testing.assert_allclose(a.leaf_value, b.leaf_value,
                                   rtol=LEAF_RTOL, atol=LEAF_ATOL)
        same = i + 1
    assert same >= 1, "the first sharded tree differs from the one-chip tree"

    p = bst.predict(Xv)
    a = auc(yv, p)
    assert abs(a - ref_out["auc"]) < 1e-3, (a, ref_out["auc"])
    np.testing.assert_array_equal(bst.predict(Xv, mesh=make_mesh()), p)
    out = dict(times, devices=n_dev, rows=n_rows, rounds=rounds,
               shard_rows=sd.padded // n_dev, auc=round(a, 5),
               trees_equal_to_one_chip=same, flags=booster_flags(bst))
    say(f"multichip {n_dev} devices: {sd.padded // n_dev} rows per shard, "
        f"first {same}/{rounds} trees equal the one-chip run, auc {a:.4f} "
        f"(one chip {ref_out['auc']}), sharded predict bit-equal, first iter "
        f"{times['first_iter_s']}s, rest {times['rest_s']}s")
    return out


# ---------------------------------------------------------------------------
# leg 6: three host-link probes, printed, not metrics
# ---------------------------------------------------------------------------

def leg_probes(dispatches: int = 500, pulls: int = 50, matmul_dim: int = 4096,
               matmul_steps: int = 200) -> dict:
    bump = jax.jit(lambda v: v + 1)
    x = jax.block_until_ready(bump(jnp.zeros((8,), jnp.float32)))
    t0 = time.perf_counter()
    for _ in range(dispatches):
        x = bump(x)
    enqueue_s = time.perf_counter() - t0
    jax.block_until_ready(x)
    chain_s = time.perf_counter() - t0
    trips = []
    for _ in range(pulls):
        t0 = time.perf_counter()
        jax.block_until_ready(bump(x))
        trips.append(time.perf_counter() - t0)

    pull = []
    for _ in range(pulls):
        y = jax.block_until_ready(bump(x))
        t0 = time.perf_counter()
        np.asarray(y)
        pull.append(time.perf_counter() - t0)

    @jax.jit
    def long_job(a):
        def body(_, c):
            return jnp.dot(c, a, preferred_element_type=jnp.float32).astype(
                jnp.bfloat16)
        return jax.lax.fori_loop(0, matmul_steps, body, a)

    a = jnp.full((matmul_dim, matmul_dim), 1.0 / matmul_dim, jnp.bfloat16)
    np.asarray(long_job(a)[0, 0])  # compile and warm
    t0 = time.perf_counter()
    jax.block_until_ready(long_job(a))
    bur_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    np.asarray(long_job(a)[0, 0])
    pull_s = time.perf_counter() - t0
    out = {
        "dispatch_enqueue_us": round(enqueue_s / dispatches * 1e6, 1),
        "dispatch_chained_us": round(chain_s / dispatches * 1e6, 1),
        "dispatch_round_trip_us": round(float(np.median(trips)) * 1e6, 1),
        "ready_scalar_pull_us": round(float(np.median(pull)) * 1e6, 1),
        "long_job_block_until_ready_ms": round(bur_s * 1e3, 2),
        "long_job_host_pull_ms": round(pull_s * 1e3, 2),
        # an early return would show as a wait far shorter than the pull
        "block_until_ready_honest": bool(bur_s > 0.8 * pull_s),
    }
    say("probes: " + json.dumps(out))
    return out


# ---------------------------------------------------------------------------

# ---------------------------------------------------------------------------
# leg 7: categorical columns and missing values, every leaf against its rows
# ---------------------------------------------------------------------------

def click_data(n_rows: int, n_int: int, levels, seed: int = 36):
    """Integer counts with missing values, then categorical ids with Zipf
    frequencies; the label from a seeded effect a column and level."""
    rng = np.random.RandomState(seed)
    X = np.empty((n_rows, n_int + len(levels)), np.float32)
    z = np.zeros(n_rows)
    for j in range(n_int):
        X[:, j] = np.floor(np.exp(1.5 * rng.randn(n_rows)))
        z += rng.randn() * np.log1p(X[:, j])
        X[rng.rand(n_rows) < 0.15 * (j % 5), j] = np.nan
    for j, lv in enumerate(levels, start=n_int):
        p = 1.0 / np.arange(1, lv + 1) ** 1.1
        X[:, j] = rng.choice(lv, n_rows, p=p / p.sum())
        z += (0.5 * rng.randn(lv))[X[:, j].astype(np.int64)]
    y = (z + rng.randn(n_rows) > np.quantile(z, 0.744)).astype(np.float64)
    return X, y


def leaves_off_their_rows(bst, X) -> int:
    """Leaves whose tracked count is not the number of rows that the tree's
    own rules send there (``Tree.predict_leaf_batch``, a host walk).  The
    grower counts a leaf from its histogram sums and routes its rows by the
    split it stores, so the two part wherever the stored split is not the
    one that was counted."""
    off = 0
    for t in bst._gbdt.models:
        nl = int(t.num_leaves)
        rows = np.bincount(t.predict_leaf_batch(X), minlength=nl)
        off += int((rows != np.asarray(t.leaf_count)[:nl]).sum())
    return off


def cat_mask_by_gather(rank_asc, rank_desc, slot, v, best_t):
    """``split.winner_cat_mask`` as it stood until PR 36: equal to it on
    every CPU path, compiled wrongly by XLA:TPU under libtpu 0.0.34 inside
    the growers' vmap.  Kept so that a later libtpu can be read against it."""
    bins_idx = jnp.arange(rank_asc.shape[1], dtype=jnp.int32)
    return jnp.where(v == 0, bins_idx == best_t,
                     jnp.where(v == 1, rank_asc[slot] <= best_t,
                               rank_desc[slot] <= best_t))


def leg_categorical(n_rows: int = CLICK_ROWS, shapes=CLICK_SHAPES,
                    rounds: int = 4, num_leaves: int = 255, **overrides):
    """Trees over categorical and integer-with-missing columns: every family
    of split is taken and every leaf holds the rows it counted.  Then the
    first shape again with the faulty form planted (reported, not asserted:
    0 says this installation compiles the old form right)."""
    out = {}
    for name, (n_int, levels, cell) in shapes.items():
        X, y = click_data(n_rows, n_int, levels)
        cats = list(range(n_int, n_int + len(levels)))
        params = dict(objective="binary", num_leaves=num_leaves, max_bin=255,
                      learning_rate=0.1, min_data_in_leaf=1,
                      min_sum_hessian_in_leaf=20.0, verbosity=-1,
                      **dict(cell, **overrides))

        def grow():
            train = lgb.Dataset(X, label=y, categorical_feature=cats)
            return timed_train(params, train, rounds)

        bst, times, _ = grow()
        trees = bst._gbdt.models
        nodes = sum(int(t.num_leaves) - 1 for t in trees)
        cat_nodes = sum(int(t.num_cat) for t in trees)
        left_by_default = sum(
            int((t.default_left()[:t.num_leaves - 1]
                 & ~t.is_categorical_node()[:t.num_leaves - 1]).sum())
            for t in trees)
        off = leaves_off_their_rows(bst, X)
        assert off == 0, (name, off)
        assert 0 < cat_nodes < nodes and left_by_default > 0, (
            name, nodes, cat_nodes, left_by_default)
        out[name] = dict(times, rows=n_rows, rounds=rounds, nodes=nodes,
                         cat_nodes=cat_nodes, left_by_default=left_by_default,
                         leaves_off_their_rows=off, flags=booster_flags(bst))
        if "gather_form_leaves_off" not in out:  # once, on the first shape
            sound = split_mod.winner_cat_mask
            split_mod.winner_cat_mask = cat_mask_by_gather
            jax.clear_caches()
            try:
                out["gather_form_leaves_off"] = leaves_off_their_rows(
                    grow()[0], X)
            finally:
                split_mod.winner_cat_mask = sound
                jax.clear_caches()
        say(f"categorical {name} x{n_rows}: {nodes} nodes, {cat_nodes} "
            f"categorical, {left_by_default} missing-left, every leaf holds "
            f"its rows; first iter {times['first_iter_s']}s")
    say(f"categorical: the form of before PR 36 puts "
        f"{out['gather_form_leaves_off']} leaves off their rows")
    return out


def check_no_fallback(legs: dict) -> None:
    """The run passed on the device and the kernels it names, or it did not
    pass: any fired net is a failure."""
    for key in (degrade.HIST, degrade.PARTITION, degrade.ROUND):
        assert degrade.disabled_reason(key) is None, (
            key, degrade.disabled_reason(key))
    assert obs_metrics.counter("degrade_disabled_total").value == 0
    flagged = {name: leg["flags"] for name, leg in legs.items()
               if isinstance(leg, dict) and "flags" in leg}
    flagged.update({f"categorical {name}": shape["flags"]
                    for name, shape in legs.get("categorical", {}).items()
                    if isinstance(shape, dict)})
    for name, flags in flagged.items():
        assert flags["on_tpu"], (name, flags)
        assert flags["use_fast"] or flags["use_fast_dp"], (name, flags)
        assert not flags["fused_disabled"], (name, flags)
        # the fused step ran wherever it was eligible, and nowhere else
        assert flags["fused_built"] == flags["fused_eligible"], (name, flags)
    for name in ("train_narrow_255", "train_narrow_63"):
        assert legs[name]["flags"]["fused_built"], name
    wide = legs["train_wide"]["flags"]
    assert wide["quantized"] and not wide["fused_built"], wide
    if "flags" in legs["multichip"]:
        assert legs["multichip"]["flags"]["use_fast_dp"], legs["multichip"]


def main() -> int:
    cache_dir = use_compile_cache()
    dev = device_block()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform == "
              f"{dev['platform']!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 1
    vers = versions()
    entries0 = cache_entries(cache_dir)
    say(f"device {json.dumps(dev)} versions {json.dumps(vers)} "
        f"compile cache {cache_dir} ({entries0} entries)")
    t_all = time.perf_counter()

    legs = {}
    legs["kernels"] = leg_kernels()
    legs["train_narrow_255"], bst255, valid = leg_train_narrow(255)
    legs["train_narrow_63"], _, _ = leg_train_narrow(63)
    legs["train_wide"], _ = leg_train_wide()
    legs["predict_serve"] = leg_predict_serve(bst255)
    if jax.device_count() >= 4:
        legs["multichip"] = leg_multichip(
            bst255, legs["train_narrow_255"], valid)
    else:
        legs["multichip"] = {"skipped": f"{jax.device_count()} device"}
        say("multichip: skipped, 1 device")
    legs["probes"] = leg_probes()
    legs["categorical"] = leg_categorical()
    check_no_fallback(legs)

    report = {"versions": vers, "cache_dir": cache_dir,
              "cache_entries": [entries0, cache_entries(cache_dir)],
              "wall_s": round(time.perf_counter() - t_all, 1), "legs": legs}
    say("report " + json.dumps(report))
    # the driver's contract: the last line is this object and nothing more
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
