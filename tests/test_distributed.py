"""Distributed (SPMD) tests on the virtual 8-device CPU mesh.

Reference test-strategy analogue: tests/python_package_test/test_dask.py
(distributed model ~ single-process model) and
tests/distributed/_test_distributed.py (SURVEY.md §5.2-5.3).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.binning import DatasetBinner
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.ops.treegrow import grow_tree
from lightgbm_tpu.parallel.data_parallel import ShardedData, grow_tree_data_parallel
from lightgbm_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def synth():
    rng = np.random.RandomState(0)
    n, f = 4000, 10
    X = rng.randn(n, f)
    w = rng.randn(f)
    y = ((X @ w + rng.randn(n)) > 0).astype(np.float64)
    return X, y

pytestmark = pytest.mark.slow


def test_eight_devices_available():
    assert jax.device_count() >= 8


def test_dp_tree_matches_serial(synth):
    """Data-parallel growth must produce the same tree as serial growth
    (reference invariant: every rank applies the identical split)."""
    X, y = synth
    n, f = X.shape
    binner = DatasetBinner.fit(X, max_bin=63)
    bins = binner.transform(X)
    rng = np.random.RandomState(1)
    grad = (0.5 - y + 0.1 * rng.rand(n)).astype(np.float32)
    hess = np.full(n, 0.25, np.float32)
    params = SplitParams(min_data_in_leaf=10)

    tree_s, leaf_s = grow_tree(
        jnp.asarray(bins.astype(np.int32)), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones(n, bool), jnp.ones(n, jnp.float32), jnp.ones(f, bool),
        jnp.asarray(binner.num_bins_per_feature), jnp.asarray(binner.missing_bin_per_feature),
        num_leaves=15, num_bins=binner.max_num_bins, params=params,
    )

    mesh = make_mesh(8)
    sharded = ShardedData(mesh, bins, binner.num_bins_per_feature, binner.missing_bin_per_feature)
    tree_d, leaf_d = grow_tree_data_parallel(
        sharded,
        sharded.pad_rows(grad),
        sharded.pad_rows(hess),
        sharded.row_valid,
        sharded.pad_rows(np.ones(n, np.float32), fill=1.0),
        jnp.ones(f, bool),
        num_leaves=15, num_bins=binner.max_num_bins, params=params,
    )

    assert int(tree_s.num_leaves) == int(tree_d.num_leaves)
    m = int(tree_s.num_leaves) - 1
    np.testing.assert_array_equal(
        np.asarray(tree_s.split_feature)[:m], np.asarray(tree_d.split_feature)[:m]
    )
    np.testing.assert_array_equal(
        np.asarray(tree_s.threshold_bin)[:m], np.asarray(tree_d.threshold_bin)[:m]
    )
    np.testing.assert_allclose(
        np.asarray(tree_s.leaf_value)[: m + 1], np.asarray(tree_d.leaf_value)[: m + 1],
        rtol=2e-3, atol=2e-3,
    )
    np.testing.assert_array_equal(np.asarray(leaf_s), np.asarray(leaf_d)[:n])


def test_end_to_end_data_parallel_close_to_serial(synth):
    """Full training with tree_learner=data ~ serial (reference: test_dask.py
    asserts distributed model predictions close to single-process)."""
    X, y = synth
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 10, "max_bin": 63}
    b_serial = lgb.train(dict(params), lgb.Dataset(X, label=y), num_boost_round=10)
    b_dp = lgb.train(dict(params, tree_learner="data"), lgb.Dataset(X, label=y), num_boost_round=10)
    assert b_dp._gbdt._dp is not None, "data-parallel path not engaged"
    p_s = b_serial.predict(X, raw_score=True)
    p_d = b_dp.predict(X, raw_score=True)
    np.testing.assert_allclose(p_s, p_d, rtol=5e-3, atol=5e-3)


def test_dryrun_multichip_entry():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_entry_compiles():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert np.asarray(out).shape == (args[0].shape[0],)


def test_fp_tree_matches_serial(synth):
    """Feature-parallel growth (features sharded, rows replicated) must equal
    the serial tree (reference: FeatureParallelTreeLearner applies the
    identical split on every machine)."""
    from lightgbm_tpu.parallel.feature_parallel import (
        FeatureShardedData, grow_tree_feature_parallel,
    )

    X, y = synth
    n, f = X.shape
    binner = DatasetBinner.fit(X, max_bin=63)
    bins = binner.transform(X)
    rng = np.random.RandomState(2)
    grad = (0.5 - y + 0.1 * rng.rand(n)).astype(np.float32)
    hess = np.full(n, 0.25, np.float32)
    params = SplitParams(min_data_in_leaf=10)

    tree_s, leaf_s = grow_tree(
        jnp.asarray(bins.astype(np.int32)), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones(n, bool), jnp.ones(n, jnp.float32), jnp.ones(f, bool),
        jnp.asarray(binner.num_bins_per_feature), jnp.asarray(binner.missing_bin_per_feature),
        num_leaves=15, num_bins=binner.max_num_bins, params=params,
    )

    mesh = make_mesh(8)
    fsh = FeatureShardedData(mesh, bins, binner.num_bins_per_feature,
                             binner.missing_bin_per_feature)
    tree_f, leaf_f = grow_tree_feature_parallel(
        fsh, jnp.asarray(grad), jnp.asarray(hess), jnp.ones(n, bool),
        jnp.ones(n, jnp.float32), np.ones(f, bool),
        num_leaves=15, num_bins=binner.max_num_bins, params=params,
    )
    assert int(tree_s.num_leaves) == int(tree_f.num_leaves)
    m = int(tree_s.num_leaves) - 1
    np.testing.assert_array_equal(
        np.asarray(tree_s.split_feature)[:m], np.asarray(tree_f.split_feature)[:m]
    )
    np.testing.assert_array_equal(
        np.asarray(tree_s.threshold_bin)[:m], np.asarray(tree_f.threshold_bin)[:m]
    )
    np.testing.assert_allclose(
        np.asarray(tree_s.leaf_value)[: m + 1], np.asarray(tree_f.leaf_value)[: m + 1],
        rtol=2e-3, atol=2e-3,
    )
    np.testing.assert_array_equal(np.asarray(leaf_s), np.asarray(leaf_f)[:n])


def test_end_to_end_feature_parallel(synth):
    X, y = synth
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 10, "max_bin": 63}
    b_serial = lgb.train(dict(params), lgb.Dataset(X, label=y), num_boost_round=8)
    b_fp = lgb.train(dict(params, tree_learner="feature"), lgb.Dataset(X, label=y), num_boost_round=8)
    assert b_fp._gbdt._fp is not None, "feature-parallel path not engaged"
    np.testing.assert_allclose(
        b_serial.predict(X, raw_score=True), b_fp.predict(X, raw_score=True),
        rtol=5e-3, atol=5e-3,
    )


def test_end_to_end_voting_parallel(synth):
    """Voting-parallel (PV-Tree): with top_k >= num_features the election is
    exhaustive, so the model must match data-parallel/serial closely; with a
    small top_k it must still train a usable model (reference:
    VotingParallelTreeLearner is an approximation by design)."""
    X, y = synth
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 10, "max_bin": 63}
    b_serial = lgb.train(dict(params), lgb.Dataset(X, label=y), num_boost_round=8)
    b_vp_full = lgb.train(
        dict(params, tree_learner="voting", top_k=X.shape[1]),
        lgb.Dataset(X, label=y), num_boost_round=8,
    )
    np.testing.assert_allclose(
        b_serial.predict(X, raw_score=True), b_vp_full.predict(X, raw_score=True),
        rtol=5e-3, atol=5e-3,
    )
    b_vp = lgb.train(
        dict(params, tree_learner="voting", top_k=3),
        lgb.Dataset(X, label=y), num_boost_round=8,
    )
    pred = b_vp.predict(X)
    acc = float(((pred > 0.5) == (y > 0.5)).mean())
    assert acc > 0.8, acc


def test_rounds_grower_serial_equals_data_parallel():
    """The round-batched grower must produce the identical tree under SPMD
    data parallelism (per-round psum merge) as serially."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.ops.treegrow_fast import grow_tree_fast
    from lightgbm_tpu.parallel.data_parallel import (
        ShardedData, grow_tree_fast_data_parallel,
    )
    from lightgbm_tpu.parallel.mesh import make_mesh

    rng = np.random.RandomState(11)
    n, f, B = 4096, 6, 32
    bins = rng.randint(0, B - 1, size=(n, f)).astype(np.int32)
    y = (bins[:, 0] + bins[:, 1] > B).astype(np.float32)
    grad = (0.5 - y).astype(np.float32)
    hess = np.full(n, 0.25, np.float32)
    nbpf = np.full(f, B, np.int32)
    mbpf = np.full(f, -1, np.int32)
    params = SplitParams(min_data_in_leaf=5)

    t_serial, _ = grow_tree_fast(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones((n,), bool), jnp.ones((n,), jnp.float32),
        jnp.ones((f,), bool), jnp.asarray(nbpf), jnp.asarray(mbpf),
        num_leaves=15, num_bins=B, params=params, use_pallas=False,
    )

    mesh = make_mesh()
    sd = ShardedData(mesh, bins, nbpf, mbpf)
    t_dp, _ = grow_tree_fast_data_parallel(
        sd, sd.pad_rows(grad), sd.pad_rows(hess),
        sd.pad_rows(np.ones(n, bool), fill=False),
        sd.pad_rows(np.ones(n, np.float32), fill=1.0),
        jnp.ones((f,), bool),
        num_leaves=15, num_bins=B, params=params, use_pallas=False,
    )
    assert int(t_serial.num_leaves) == int(t_dp.num_leaves)
    for name in ("split_feature", "threshold_bin", "left_child", "right_child"):
        np.testing.assert_array_equal(
            np.asarray(getattr(t_serial, name)), np.asarray(getattr(t_dp, name))
        )
    np.testing.assert_allclose(
        np.asarray(t_serial.leaf_value), np.asarray(t_dp.leaf_value),
        rtol=1e-5, atol=1e-5,
    )


def test_booster_data_parallel_rounds_mode_trains():
    """Booster-level: tree_learner=data + rounds grower (the async fast-DP
    dispatch incl. device-side pad/reshard) trains and predicts sanely."""
    rng = np.random.RandomState(12)
    X = rng.randn(4000, 6).astype(np.float32)
    y = ((X @ rng.randn(6)) > 0).astype(np.float64)
    import lightgbm_tpu as lgb

    ds = lgb.Dataset(X, label=y)
    bst = lgb.Booster(
        params={"objective": "binary", "num_leaves": 15, "verbosity": -1,
                "tree_learner": "data", "tree_growth_mode": "rounds"},
        train_set=ds,
    )
    for _ in range(8):
        bst.update()
    assert bst._gbdt._use_fast_dp  # the fast-DP branch actually ran
    p = bst.predict(X)
    acc = np.mean((p > 0.5) == (y > 0))
    assert acc > 0.9


@pytest.mark.parametrize("mode", ["strict", "rounds"])
def test_data_parallel_monotone_intermediate(mode):
    """Intermediate monotone bounds under tree_learner=data on the 8-device
    mesh: leaf state is replicated (hists psummed before split search), so
    the bound recomputation is SPMD-safe in both growth modes and the
    trained model must be pointwise monotone (PARITY.md row 29)."""
    rng = np.random.RandomState(4)
    n = 2000
    X = rng.randn(n, 3)
    y = (2.0 * X[:, 0] + np.sin(3 * X[:, 0]) - 1.5 * X[:, 1]
         + np.sin(2 * X[:, 2]) + 0.1 * rng.randn(n))
    bst = lgb.train(
        {"objective": "regression", "num_leaves": 15, "verbosity": -1,
         "tree_learner": "data", "tree_growth_mode": mode,
         "min_data_in_leaf": 5, "max_bin": 63,
         "monotone_constraints": [1, -1, 0],
         "monotone_constraints_method": "intermediate"},
        lgb.Dataset(X, label=y), 8)
    assert bst._gbdt._dp is not None, "data-parallel path not engaged"
    for f_idx, sign in ((0, 1), (1, -1)):
        for i in range(10):
            rows = np.repeat(rng.randn(1, 3), 60, axis=0)
            rows[:, f_idx] = np.linspace(-2.5, 2.5, 60)
            d = np.diff(bst.predict(rows)) * sign
            assert d.min() >= -1e-6, (mode, f_idx, d.min())
