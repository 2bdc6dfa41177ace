"""Fused training-step guards (models/gbdt.py _fused_eligible/_get_fused_step)."""

import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.histogram import histogram_onehot_multi, histogram_scatter

pytestmark = pytest.mark.slow


def _fit(params, n=400, rounds=3, rank=False):
    rng = np.random.RandomState(0)
    X = rng.randn(n, 4)
    if rank:
        y = rng.randint(0, 3, n).astype(float)
        d = lgb.Dataset(X, label=y, group=np.full(n // 20, 20))
    else:
        y = (X[:, 0] > 0).astype(float)
        d = lgb.Dataset(X, label=y)
    bst = lgb.train({**params, "verbosity": -1}, d, num_boost_round=rounds)
    return bst


def test_stateful_ranking_objectives_not_fused():
    # rank_xendcg draws fresh RNG per iteration -> never fusable
    bst = _fit({"objective": "rank_xendcg", "tree_growth_mode": "rounds"}, rank=True)
    assert not bst._gbdt._fused_eligible(None)
    assert bst.num_trees() == 3


def test_position_bias_lambdarank_fuses_and_matches():
    # position-bias state rides the fused step as a carry (fused_state
    # protocol) — the fused run must reproduce the unfused run exactly,
    # including the learned biases
    rng = np.random.RandomState(0)
    X = rng.randn(400, 4)
    y = rng.randint(0, 3, 400).astype(float)
    params = {"objective": "lambdarank", "verbosity": -1, "num_leaves": 7,
              "lambdarank_position_bias_regularization": 0.1,
              "tree_growth_mode": "rounds"}
    preds, biases = {}, {}
    for fuse in (True, False):
        d = lgb.Dataset(X, label=y, group=np.full(20, 20),
                        position=np.tile(np.arange(20), 20))
        bst = lgb.Booster(params=params, train_set=d)
        if fuse:
            assert bst._gbdt._fused_eligible(None)
        else:
            bst._gbdt._fused_eligible = lambda grad: False
        for _ in range(3):
            bst.update()
        preds[fuse] = bst.predict(X)
        biases[fuse] = np.asarray(bst._gbdt.objective.pos_bias)
    assert np.abs(biases[True]).max() > 0  # biases actually learned
    np.testing.assert_allclose(biases[True], biases[False], rtol=1e-5,
                               atol=1e-7)
    np.testing.assert_allclose(preds[True], preds[False], rtol=1e-5,
                               atol=1e-7)


def test_plain_lambdarank_fuses_and_matches():
    rng = np.random.RandomState(5)
    X = rng.randn(400, 4)
    y = rng.randint(0, 3, 400).astype(float)
    params = {"objective": "lambdarank", "verbosity": -1,
              "num_leaves": 7, "tree_growth_mode": "rounds"}
    preds = {}
    for fuse in (True, False):
        d = lgb.Dataset(X, label=y, group=np.full(20, 20))
        bst = lgb.Booster(params=params, train_set=d)
        if fuse:
            assert bst._gbdt._fused_eligible(None)
        else:
            bst._gbdt._fused_eligible = lambda grad: False
        for _ in range(3):
            bst.update()
        preds[fuse] = bst.predict(X)
    np.testing.assert_allclose(preds[True], preds[False], rtol=1e-5, atol=1e-7)


def test_reset_parameter_schedule_does_not_invalidate_fused_cache():
    bst = _fit({"objective": "binary", "tree_growth_mode": "rounds"})
    g = bst._gbdt
    if not g._fused_eligible(None):
        pytest.skip("fused path not engaged on this backend")
    step_before = g._get_fused_step()
    # learning_rate is a traced runtime arg: schedule changes must keep cache
    g.cfg.update({"learning_rate": 0.05})
    g.reset_split_params()
    assert g._fused_step is step_before
    # a baked constant (lambda_l2) must invalidate
    g.cfg.update({"lambda_l2": 3.0})
    g.reset_split_params()
    assert g._fused_step is None


def test_fused_path_matches_unfused():
    rng = np.random.RandomState(1)
    X = rng.randn(600, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    preds = {}
    for mode, fuse in (("rounds", True), ("rounds", False)):
        d = lgb.Dataset(X, label=y)
        bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                                  "verbosity": -1, "tree_growth_mode": mode},
                          train_set=d)
        if not fuse:
            # force the unfused path
            bst._gbdt._fused_eligible = lambda grad: False
        for _ in range(4):
            bst.update()
        preds[fuse] = bst.predict(X)
    np.testing.assert_allclose(preds[True], preds[False], rtol=1e-5, atol=1e-7)


def test_fused_goss_matches_unfused():
    """In-trace GOSS uses the same PRNG stream and formula as the host
    path, so fused and unfused training must build identical models."""
    rng = np.random.RandomState(3)
    X = rng.randn(800, 5)
    y = (X[:, 0] + 0.5 * rng.randn(800) > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "data_sample_strategy": "goss", "learning_rate": 0.5,
              "top_rate": 0.3, "other_rate": 0.2,
              "tree_growth_mode": "rounds"}
    preds = {}
    for fuse in (True, False):
        d = lgb.Dataset(X, label=y)
        bst = lgb.Booster(params=params, train_set=d)
        if not fuse:
            bst._gbdt._fused_eligible = lambda grad: False
        for _ in range(6):  # warmup = 2 iters at lr 0.5, then real GOSS
            bst.update()
        preds[fuse] = bst.predict(X)
    np.testing.assert_allclose(preds[True], preds[False], rtol=1e-5, atol=1e-7)


def test_fused_multiclass_matches_unfused():
    rng = np.random.RandomState(4)
    X = rng.randn(500, 5)
    y = rng.randint(0, 3, 500)
    params = {"objective": "multiclass", "num_class": 3, "num_leaves": 7,
              "verbosity": -1, "tree_growth_mode": "rounds"}
    preds = {}
    for fuse in (True, False):
        d = lgb.Dataset(X, label=y.astype(float))
        bst = lgb.Booster(params=params, train_set=d)
        if not fuse:
            bst._gbdt._fused_eligible = lambda grad: False
        for _ in range(3):
            bst.update()
        assert bst.num_trees() == 9 if fuse else True
        preds[fuse] = bst.predict(X)
    np.testing.assert_allclose(preds[True], preds[False], rtol=1e-5, atol=1e-7)


def test_onehot_multi_bf16_precision():
    n, F, B, L = 3000, 4, 32, 2
    rng = np.random.RandomState(2)
    bins = jnp.asarray(rng.randint(0, B, size=(n, F)).astype(np.int16))
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray(rng.rand(n).astype(np.float32))
    mask = jnp.ones((n,), bool)
    lid = jnp.asarray(rng.randint(0, L, size=(n,)).astype(np.int32))
    out = histogram_onehot_multi(bins, grad, hess, mask, lid, 0, L, B,
                                 precision="bf16")
    assert out.shape == (L, 3, F, B)
    ref = histogram_scatter(bins, grad, hess, (lid == 0).astype(jnp.float32), B)
    scale = np.abs(np.asarray(ref)).max() + 1
    rel = np.max(np.abs(np.asarray(out[0]) - np.asarray(ref))) / scale
    assert rel < 5e-3  # bf16-rounded payload tolerance


def test_fused_failure_falls_back_to_unfused():
    # a compile failure in the fused step must degrade to the unfused
    # path, not kill training — once, with no retry, and leave its trace
    bst = _fit({"objective": "binary", "tree_growth_mode": "rounds"}, rounds=1)
    g = bst._gbdt
    if not g._fused_eligible(None):
        pytest.skip("fused path not engaged on this backend")

    calls = []

    def boom():
        def step(*a, **k):
            calls.append(1)
            raise RuntimeError("synthetic compile failure")
        return step

    g._get_fused_step = boom
    assert not g.train_one_iter()  # completes via the unfused path
    assert len(calls) == 1
    assert g._fused_disabled
    assert not g._fused_eligible(None)
    assert bst.num_trees() == 2
