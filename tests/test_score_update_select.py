"""The score update reads a row's leaf value by compare-and-select
(``models/gbdt._add_leaf_scores``), not by gather.  The oracle is the form the
boosting loop ran before: three eager operations, the product rounded to
float32 on the ``(L,)`` table, then the gather, then the add.  (One jit of
the three is no oracle: XLA's CPU backend contracts the multiply and the add
into one rounding.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models import gbdt as gbdt_mod


def _eager_gather(score, leaf_value, shrinkage, leaf_id, col=0):
    t = leaf_value * jnp.float32(shrinkage)
    d = t[leaf_id]
    if score.ndim == 1:
        return score + d
    return score.at[:, col].add(d)


def _case(n, n_leaves, k, seed):
    """Scores, leaf values (negatives, denormals, zeros) and leaf ids that
    leave the last leaf empty wherever there is more than one."""
    rng = np.random.RandomState(seed)
    values = rng.randn(n_leaves).astype(np.float32)
    values[rng.rand(n_leaves) < 0.2] = np.float32(1e-40)  # denormal
    values[rng.rand(n_leaves) < 0.1] = np.float32(-3e-41)
    if n_leaves > 4:
        values[3] = 0.0
    values[0] = -abs(values[0]) - np.float32(0.5)
    leaf_id = rng.randint(0, max(n_leaves - 1, 1), n).astype(np.int32)
    shape = (n,) if k == 1 else (n, k)
    score = rng.randn(*shape).astype(np.float32)
    score.reshape(-1)[:: 7] = 0.0  # where a denormal delta is all of the sum
    return score, values, leaf_id


@pytest.mark.parametrize("shrinkage", [1.0, 0.1])
@pytest.mark.parametrize("k,col", [(1, 0), (3, 0), (3, 1), (3, 2)])
@pytest.mark.parametrize("n", [1, 127, 2048, 5000])
@pytest.mark.parametrize("n_leaves", [1, 2, 31, 255])
def test_select_equals_the_eager_gather(n_leaves, n, k, col, shrinkage):
    score, values, leaf_id = _case(n, n_leaves, k, seed=n_leaves * 7919 + n)
    if n_leaves > 1:
        assert (leaf_id != n_leaves - 1).all()  # a leaf no row sits in
    want = np.asarray(_eager_gather(
        jnp.asarray(score), jnp.asarray(values), shrinkage,
        jnp.asarray(leaf_id), col))
    got = np.asarray(gbdt_mod._add_leaf_scores(
        jnp.asarray(score), jnp.asarray(values), shrinkage,
        jnp.asarray(leaf_id), col=col))
    assert got.shape == score.shape and got.dtype == np.float32
    assert np.array_equal(got, want)
    assert not np.array_equal(got, score)  # something was added


@pytest.mark.parametrize("k,col", [(1, 0), (3, 1)])
def test_the_lowered_update_holds_no_gather(k, col):
    n, n_leaves = 4096, 255
    score = jnp.zeros((n,) if k == 1 else (n, k), jnp.float32)
    text = gbdt_mod._add_leaf_scores.lower(
        score, jnp.zeros((n_leaves,), jnp.float32), 0.1,
        jnp.zeros((n,), jnp.int32), col=col).as_text()
    assert "stablehlo.compare" in text and "stablehlo.select" in text
    assert "gather" not in text
    # the same check finds the gather in the form this one replaced
    old = jax.jit(_eager_gather, static_argnames=("col",)).lower(
        score, jnp.zeros((n_leaves,), jnp.float32), 0.1,
        jnp.zeros((n,), jnp.int32), col=col).as_text()
    assert "gather" in old


def _train(params, X, y, rounds, tmp_path, name):
    bst = lgb.train(params, lgb.Dataset(X, label=y), rounds)
    g = bst._gbdt
    assert g._use_fast and g._fused_step is None  # the path the cells run
    path = tmp_path / f"{name}.txt"
    bst.save_model(str(path))
    return np.asarray(g._score), path.read_text()


@pytest.mark.parametrize("objective", ["binary", "multiclass"])
def test_training_equals_training_with_the_gather(objective, tmp_path,
                                                  monkeypatch):
    rng = np.random.RandomState(11)
    X = rng.randn(1500, 10).astype(np.float32)
    margin = X @ rng.randn(10) + 0.3 * rng.randn(1500)
    params = {"objective": objective, "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5, "tree_growth_mode": "rounds",
              "fused_training": False}
    if objective == "binary":
        y = (margin > 0).astype(np.float64)
    else:
        y = np.digitize(margin, [-1.0, 1.0]).astype(np.float64)
        params["num_class"] = 3
    score, model = _train(params, X, y, 5, tmp_path, "select")
    calls = []

    def gather(score, leaf_value, shrinkage, leaf_id, col=0):
        calls.append(col)
        return _eager_gather(score, leaf_value, shrinkage, leaf_id, col)

    monkeypatch.setattr(gbdt_mod, "_add_leaf_scores", gather)
    score_g, model_g = _train(params, X, y, 5, tmp_path, "gather")
    assert calls == ([0] * 5 if objective == "binary" else [0, 1, 2] * 5)
    assert score.shape == (1500,) + ((3,) if objective == "multiclass" else ())
    assert np.array_equal(score, score_g)
    assert model == model_g
    assert model.count("Tree=") == len(calls)
