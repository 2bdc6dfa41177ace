"""Sanitizer-job analogue (SURVEY §6.2): the reference's CI runs an
ASan/UBSan build; the jit-purity equivalent here is training under
jax.enable_checks (internal invariant checking) and jax.debug_nans
(NaN propagation detection) — across every grower the engine can select:
strict, rounds, int8-quantized rounds, the rounds grower at its own
call, and a loopback data-parallel round.  The static half of the sanitizer story is jaxlint
(lightgbm_tpu/analysis, gated by test_jaxlint_gate.py); the retrace half
is utils/sanitizer.py (gated by test_retrace.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb


def _train_small(extra_params=None):
    rng = np.random.RandomState(0)
    X = rng.randn(300, 4)
    y = (X[:, 0] > 0).astype(float)
    d = lgb.Dataset(X, label=y)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    params.update(extra_params or {})
    bst = lgb.train(params, d, num_boost_round=3)
    p = bst.predict(X)
    assert np.isfinite(p).all()


def test_train_under_enable_checks():
    with jax.enable_checks(True):
        _train_small()


def test_train_under_enable_checks_rounds_grower():
    with jax.enable_checks(True):
        _train_small({"tree_growth_mode": "rounds"})


def test_train_under_debug_nans():
    """jax.debug_nans historically conflated the growers' -inf gain
    sentinels with NaNs on some paths; the sentinel plumbing is now clean
    enough to train under it — keep it that way."""
    with jax.debug_nans(True):
        _train_small()


def test_train_quantized_under_checks_and_debug_nans():
    """int8 discretized gradients (stochastic rounding, int32 accumulate,
    dequantized split eval) on the rounds grower under both sanitizers."""
    with jax.enable_checks(True), jax.debug_nans(True):
        _train_small({"tree_growth_mode": "rounds",
                      "use_quantized_grad": True})


def _grower_inputs(n=1500, f=10, seed=0):
    from lightgbm_tpu.binning import DatasetBinner

    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = X @ rng.randn(f)
    binner = DatasetBinner.fit(X, max_bin=63)
    bins = jnp.asarray(binner.transform(X), jnp.int16)
    return binner, bins, jnp.asarray(0.6 * y, jnp.float32)


def test_rounds_grower_call_under_enable_checks():
    """The rounds grower at its own call (64 bins: the einsum histogram
    route, four leaves a pass) threads a per-leaf histogram state through
    a device loop — the carry/threading invariants are exactly what
    enable_checks' internal assertions exercise."""
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.ops.treegrow_fast import grow_tree_fast

    binner, bins, grad = _grower_inputs()
    n, f = bins.shape
    with jax.enable_checks(True):
        tree, leaf = grow_tree_fast(
            bins, grad, jnp.ones((n,), jnp.float32),
            jnp.ones((n,), bool), jnp.ones((n,), jnp.float32),
            jnp.ones((f,), bool),
            jnp.asarray(binner.num_bins_per_feature),
            jnp.asarray(binner.missing_bin_per_feature),
            num_leaves=15, num_bins=64,
            params=SplitParams(min_data_in_leaf=5.0),
            leaf_tile=4, use_pallas=False)
    nl = int(tree.num_leaves)
    assert nl == 15
    assert np.isfinite(np.asarray(tree.leaf_value[:nl])).all()
    assert int(np.asarray(leaf).max()) == nl - 1


def test_data_parallel_round_under_enable_checks():
    """One loopback data-parallel growth round (shard_map + psum over the
    virtual CPU mesh) under enable_checks: the collective/sharding layer
    runs with JAX's internal invariant checks on."""
    from lightgbm_tpu.binning import DatasetBinner
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.parallel.data_parallel import (ShardedData,
                                                     grow_tree_data_parallel)
    from lightgbm_tpu.parallel.mesh import make_mesh

    if jax.device_count() < 4:
        pytest.skip("needs the virtual multi-device CPU mesh")
    rng = np.random.RandomState(7)
    n, f = 1200, 8
    X = rng.randn(n, f)
    y = X @ rng.randn(f)
    binner = DatasetBinner.fit(X, max_bin=31)
    bins = binner.transform(X)
    mesh = make_mesh(4)
    sharded = ShardedData(mesh, bins, binner.num_bins_per_feature,
                          binner.missing_bin_per_feature)
    with jax.enable_checks(True):
        tree, leaf = grow_tree_data_parallel(
            sharded,
            sharded.pad_rows(np.asarray(0.6 * y, np.float32)),
            sharded.pad_rows(np.full(n, 0.25, np.float32)),
            sharded.pad_rows(np.ones(n, bool), fill=False),
            sharded.pad_rows(np.ones(n, np.float32), fill=1.0),
            jnp.ones((f,), bool),
            num_leaves=7, num_bins=binner.max_num_bins,
            params=SplitParams(min_data_in_leaf=10))
    nl = int(tree.num_leaves)
    assert nl > 1
    assert np.isfinite(np.asarray(tree.leaf_value[:nl])).all()


def test_no_nans_in_training_state():
    """debug_nans-style spot check without the context manager (the grower
    uses -inf sentinels deliberately, which jax.debug_nans conflates with
    NaNs on some paths): every intermediate the booster keeps must be
    finite-or-sentinel, never NaN."""
    rng = np.random.RandomState(1)
    X = rng.randn(400, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    d = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbosity": -1}, train_set=d)
    for _ in range(4):
        bst.update()
        assert not np.isnan(np.asarray(bst._gbdt._score)).any()
        assert not np.isnan(np.asarray(bst._gbdt._cur_grad)).any()
    for t in bst._gbdt.models:
        assert np.isfinite(t.leaf_value[: t.num_leaves]).all()


def test_dispatch_counter_accounting():
    """DispatchCounter deltas: dispatches, blocking pulls and pipelined
    resolves are counted independently and snapshot-scoped."""
    from lightgbm_tpu.utils import sanitizer as san

    x = jnp.arange(8.0)
    with san.DispatchCounter() as d:
        san.record_dispatch()
        san.record_dispatch(2)
        v = san.sync_pull(x)
        san.async_pull_start(x)
        w = san.async_pull_result(x)
    assert (d.dispatches, d.host_syncs, d.async_resolves) == (3, 1, 1)
    assert np.asarray(v).shape == (8,) and np.asarray(w).shape == (8,)
    # a fresh counter starts from the new baseline
    with san.DispatchCounter() as d2:
        pass
    assert (d2.dispatches, d2.host_syncs, d2.async_resolves) == (0, 0, 0)


def test_dispatch_counter_round_budget():
    from lightgbm_tpu.utils import sanitizer as san

    with san.DispatchCounter() as d:
        for _ in range(4):
            san.record_dispatch()
    d.assert_round_budget(4, what="clean loop")
    with pytest.raises(san.BudgetError):
        d.assert_round_budget(4, dispatches_per_round=2, what="two-phase")

    with san.DispatchCounter() as d2:
        san.record_dispatch()
        san.sync_pull(jnp.zeros(()))
    with pytest.raises(san.BudgetError):
        d2.assert_round_budget(1, what="loop with a blocking pull")
