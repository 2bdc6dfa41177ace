"""Non-finite guard rails (docs/ROBUSTNESS.md): boundary validation at
Dataset construction and the deferred device-side guard on the rounds
and strict growers' paths (which must cost zero accounted syncs between
the sync points the loop already has)."""

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.utils import faults
from lightgbm_tpu.utils.guards import NonFiniteError


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _data(n=300, f=5, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)
    return X, y


# ---------------------------------------------------------------------------
# boundary validation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bad", [np.nan, np.inf, -np.inf])
def test_nonfinite_label_raises_at_construct(bad):
    X, y = _data()
    y = y.copy()
    y[7] = bad
    with pytest.raises(NonFiniteError, match=r"label.*index 7"):
        lgb.Dataset(X, label=y).construct()


def test_nonfinite_weight_and_init_score_raise():
    X, y = _data()
    w = np.ones(len(y))
    w[3] = np.nan
    with pytest.raises(NonFiniteError, match="weight"):
        lgb.Dataset(X, label=y, weight=w).construct()
    s = np.zeros(len(y))
    s[0] = np.inf
    with pytest.raises(NonFiniteError, match="init_score"):
        lgb.Dataset(X, label=y, init_score=s).construct()


def test_set_field_validates_too():
    X, y = _data()
    d = lgb.Dataset(X, label=y)
    bad = y.copy()
    bad[0] = np.nan
    with pytest.raises(NonFiniteError):
        d.set_label(bad)


def test_train_boundary_raises_before_any_boosting():
    X, y = _data()
    y = y.copy()
    y[0] = np.nan
    with pytest.raises(NonFiniteError):
        lgb.train({"objective": "binary", "verbosity": -1},
                  lgb.Dataset(X, label=y), 2)


def test_nan_features_are_still_fine():
    """Features keep the missing-value path — only targets are guarded."""
    X, y = _data()
    X = X.copy()
    X[::7, 2] = np.nan
    bst = lgb.train({"objective": "binary", "num_leaves": 7,
                     "verbosity": -1}, lgb.Dataset(X, label=y), 3)
    assert np.isfinite(bst.predict(X)).all()


# ---------------------------------------------------------------------------
# rounds grower: the guard is folded on device, pulled at sync points only
# ---------------------------------------------------------------------------

_ROUNDS = {"objective": "regression", "tree_growth_mode": "rounds",
           "fused_training": False, "num_leaves": 15, "min_data_in_leaf": 5,
           "verbosity": -1}


def test_rounds_grower_guard_raises_iteration_stamped_without_syncs(
        monkeypatch):
    """NaN gradients entering the rounds grower's unfused loop at
    iteration 2 abort the run with that iteration in the message, and the
    guard rode the device: no accounted host pull in the updates before
    the sync point (here: serialization) that reads it."""
    from lightgbm_tpu.utils.sanitizer import DispatchCounter

    X, y = _data(n=900, f=8, seed=5)
    monkeypatch.setenv("LGBMTPU_FAULT", "nonfinite_grad:2")
    bst = lgb.Booster(params=dict(_ROUNDS), train_set=lgb.Dataset(X, label=y))
    assert bst._gbdt._use_fast
    with DispatchCounter() as d:
        for _ in range(4):
            bst.update()
    assert d.host_syncs == 0
    with pytest.raises(NonFiniteError, match="iteration 2"):
        bst.model_to_string()


def test_rounds_grower_clean_budget_pin_with_guards_on():
    """With the finite guard folded into every iteration's epilogue, a
    steady-state rounds-grower update still compiles nothing and pulls
    nothing through the accounted ledger (the wider retrace pin lives in
    tests/test_retrace.py)."""
    from lightgbm_tpu.utils.sanitizer import DispatchCounter

    X, y = _data(n=900, f=8, seed=6)
    bst = lgb.Booster(params=dict(_ROUNDS), train_set=lgb.Dataset(X, label=y))
    for _ in range(2):
        bst.update()
    np.asarray(bst._gbdt._score)  # warmup compiles, drained

    with DispatchCounter() as d:
        for _ in range(3):
            bst.update()
        np.asarray(bst._gbdt._score)
    assert d.host_syncs == 0
    d.assert_no_recompile("rounds-grower updates, guards on")
    assert bst.num_trees() == 5
    bst.model_to_string()  # the guard's sync point: clean


# ---------------------------------------------------------------------------
# fast/full-pass mirror: deferred device-side guard
# ---------------------------------------------------------------------------

def test_custom_fobj_nan_grads_raise_round_stamped():
    """A custom objective emitting NaN gradients at iteration 3 must fail
    loudly with that iteration in the message.  Detection is deferred to
    a sync point (here: model serialization) by design — the stamp, not
    the detection latency, is the contract."""
    X, y = _data(seed=1)
    d = lgb.Dataset(X, label=y)

    calls = {"n": 0}

    def fobj(preds, train_set):
        calls["n"] += 1
        g = preds - y
        h = np.ones_like(g)
        if calls["n"] == 3:
            g = g.copy()
            g[0] = np.nan
        return g, h

    bst = lgb.train({"objective": fobj, "num_leaves": 7, "verbosity": -1},
                    d, 5)
    with pytest.raises(NonFiniteError, match="iteration 3"):
        bst.model_to_string()


def test_injected_nonfinite_grad_detected_via_eval_sync():
    """LGBMTPU_FAULT=nonfinite_grad:2 on a run with a valid set: eval
    syncs every round, so the guard fires within a round of the
    corruption, stamped with iteration 2."""
    import os

    X, y = _data(seed=2)
    os.environ["LGBMTPU_FAULT"] = "nonfinite_grad:2"
    try:
        d = lgb.Dataset(X, label=y)
        dv = lgb.Dataset(X[:100], label=y[:100], reference=d)
        with pytest.raises(NonFiniteError, match="iteration 2"):
            # fused_training=False keeps the per-phase path, where the
            # gradient injection site lives (fused steps compute g/h
            # in-trace and are covered by the fobj test above)
            lgb.train({"objective": "regression", "fused_training": False,
                       "num_leaves": 7, "verbosity": -1},
                      d, 6, valid_sets=[dv])
    finally:
        os.environ.pop("LGBMTPU_FAULT", None)


def test_injected_nonfinite_hess_detected_at_save():
    import os

    X, y = _data(seed=3)
    os.environ["LGBMTPU_FAULT"] = "nonfinite_hess:1"
    try:
        bst = lgb.train({"objective": "regression", "num_leaves": 7,
                         "verbosity": -1, "fused_training": False},
                        lgb.Dataset(X, label=y), 3)
        with pytest.raises(NonFiniteError, match="iteration 1"):
            bst.model_to_string()
    finally:
        os.environ.pop("LGBMTPU_FAULT", None)
