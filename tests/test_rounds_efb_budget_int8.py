"""The rounds grower where the strict grower cannot be the reference at the
grower's call: EFB-bundled columns (the ``Dataset`` makes the bundles),
trees the ``num_leaves`` budget truncates (the two growers may differ:
what every tree still owes), and int8 gradients (the strict grower has no
quantized path; the reference is the float rounds grower on the
dequantized gradients).  Sister of ``test_rounds_equals_strict.py``, whose
helpers it shares; each stands in for a case of the windowed family's
deleted tests (PR 30)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.split import SplitParams, leaf_output
from lightgbm_tpu.ops.treegrow_fast import grow_tree_fast
from tests.test_rounds_equals_strict import (NUM_LEAVES, ROUTES,
                                             assert_same_tree, canon,
                                             make_case)

# ---------------------------------------------------------------------------
# EFB needs the Dataset: through lgb.train, tree_growth_mode rounds | strict
# ---------------------------------------------------------------------------

def _onehot_data(n=600, groups=4, seed=0):
    """``groups`` blocks of 8 mutually exclusive one-hot columns (sparse
    enough for EFB to bundle) and two dense columns."""
    rng = np.random.RandomState(seed)
    cats = rng.randint(0, 8, size=(n, groups))
    X = np.zeros((n, groups * 8 + 2), np.float32)
    for g in range(groups):
        X[np.arange(n), g * 8 + cats[:, g]] = 1.0
    X[:, -2:] = rng.randn(n, 2)
    y = ((cats[:, 0] == 3) * 2.0 + (cats[:, 1] >= 4) * 1.0 + X[:, -2]
         + 0.3 * rng.randn(n))
    return X, y


def _train_first_tree(mode, max_bin, tile, monkeypatch):
    from lightgbm_tpu.models.gbdt import GBDT

    if tile is not None:
        monkeypatch.setattr(GBDT, "_leaf_tile",
                            lambda self, ts, use_efb=True: tile)
    X, y = _onehot_data()
    ds = lgb.Dataset(X, label=y, params={"max_bin": max_bin})
    bst = lgb.train(
        {"objective": "regression", "num_leaves": NUM_LEAVES,
         "min_data_in_leaf": 40, "max_bin": max_bin, "verbosity": -1,
         "tree_growth_mode": mode, "fused_training": False},
        ds, num_boost_round=1)
    assert ds.efb is not None and ds.efb.num_bundled < X.shape[1] // 2
    assert bst._gbdt._use_fast == (mode == "rounds")
    return bst._gbdt.models[0], X


def _host_canon(tree):
    """canon() for a host ``Tree``: preorder (feature, threshold, counts)."""
    out = []

    def walk(c):
        if c < 0:
            out.append(("leaf", int(tree.leaf_count[~c]),
                        float(tree.leaf_value[~c])))
            return
        out.append(("node", int(tree.split_feature[c]),
                    float(tree.threshold[c]), float(tree.split_gain[c])))
        walk(int(tree.left_child[c]))
        walk(int(tree.right_child[c]))

    walk(0)
    return out


@pytest.mark.parametrize("tile", [1, None], ids=["tile1", "tile-rec"])
@pytest.mark.parametrize("max_bin", [63, 255])
def test_rounds_equals_strict_efb_bundled(max_bin, tile, monkeypatch):
    """EFB-bundled columns: the rounds grower histograms the bundles and
    unbundles; the strict grower reads the columns.  Same first tree."""
    got, X = _train_first_tree("rounds", max_bin, tile, monkeypatch)
    want, _ = _train_first_tree("strict", max_bin, None, monkeypatch)
    assert 4 <= want.num_leaves < NUM_LEAVES
    g, w = _host_canon(got), _host_canon(want)
    assert len(g) == len(w)
    for a, b in zip(g, w):
        assert a[:2] == b[:2], (a, b)  # kind + feature | kind + count
        np.testing.assert_allclose(a[2:], b[2:], rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(got.predict_batch(X), want.predict_batch(X),
                               rtol=2e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# budget-truncated trees: the two growers may differ; what must still hold
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("num_leaves,kind", [(6, "numeric"), (11, "mask"),
                                             (16, "weights"),
                                             (9, "categorical")])
def test_budget_truncated_tree_invariants(num_leaves, kind, route):
    max_bin, num_bins, use_pallas = ROUTES[route]
    args, kw, _, _ = make_case(kind, max_bin, seed=3)
    params = SplitParams(min_data_in_leaf=5.0, min_gain_to_split=0.05,
                         lambda_l2=0.25)
    tree, leaf = grow_tree_fast(
        *args, num_leaves=num_leaves, num_bins=num_bins, params=params,
        leaf_tile=4, use_pallas=use_pallas, **kw)
    t = jax.tree_util.tree_map(np.asarray, tree)
    leaf = np.asarray(leaf)
    _, nodes, leaves, _ = canon(tree)
    # the budget binds: exactly num_leaves leaves, num_leaves - 1 splits
    assert int(t.num_leaves) == num_leaves == len(leaves)
    assert sorted(nodes) == list(range(num_leaves - 1))
    # every row in exactly one live leaf; in-bag counts add up
    assert leaf.min() >= 0 and leaf.max() < num_leaves
    _, grad, hess, mask, sw = (np.asarray(a) for a in args[:5])
    inbag = mask.astype(bool)
    np.testing.assert_array_equal(
        t.leaf_count[:num_leaves],
        np.bincount(leaf[inbag], minlength=num_leaves))
    assert t.leaf_count[:num_leaves].sum() == inbag.sum()
    assert t.leaf_count[:num_leaves].min() >= params.min_data_in_leaf
    # each leaf's value is leaf_output of ITS rows' sums
    G = np.bincount(leaf[inbag], weights=(grad * sw)[inbag],
                    minlength=num_leaves)
    H = np.bincount(leaf[inbag], weights=(hess * sw)[inbag],
                    minlength=num_leaves)
    want = np.asarray(leaf_output(jnp.asarray(G, jnp.float32),
                                  jnp.asarray(H, jnp.float32), params))
    np.testing.assert_allclose(t.leaf_value[:num_leaves], want,
                               rtol=2e-4, atol=1e-6)
    # no split under min_gain_to_split
    assert t.split_gain[: num_leaves - 1].min() > params.min_gain_to_split


# ---------------------------------------------------------------------------
# int8 gradients: the quantized rounds grower against the float rounds
# grower on the dequantized gradients
# ---------------------------------------------------------------------------

QBINS = 16


def _efb_case(max_bin):
    """The one-hot fixture at the grower's call, bundle tables included."""
    X, y = _onehot_data()
    ds = lgb.Dataset(X, label=y, params={"max_bin": max_bin})
    ds.construct()
    efb_bins, gather, default = ds.efb_device_tables()
    n, f = X.shape
    args = (ds.bins_device, jnp.asarray(0.6 * y, jnp.float32),
            jnp.ones((n,), jnp.float32), jnp.ones((n,), bool),
            jnp.ones((n,), jnp.float32), jnp.ones((f,), bool),
            ds.num_bins_pf_device, ds.missing_bin_pf_device)
    kw = dict(efb_bins=efb_bins, efb_gather=gather, efb_default=default)
    return args, kw, int(ds.max_num_bins)


def _quant_case(kind, max_bin, num_bins):
    if kind == "efb":
        return _efb_case(max_bin)
    args, kw, _, _ = make_case(kind, max_bin, seed=7)
    return args, kw, num_bins


def _dequantized(grad, hess, row_mask):
    """The grower's own discretisation (round to nearest, no noise), as
    ``_grow_fast_impl`` writes it."""
    inbag = row_mask.astype(jnp.float32)
    g_scale = jnp.maximum(jnp.max(jnp.abs(grad) * inbag) / (QBINS // 2),
                          1e-30)
    h_scale = jnp.maximum(jnp.max(hess * inbag) / QBINS, 1e-30)
    gq = jnp.clip(jnp.round(grad / g_scale), -127, 127).astype(jnp.int8)
    hq = jnp.clip(jnp.round(hess / h_scale), 0, 127).astype(jnp.int8)
    return gq.astype(jnp.float32) * g_scale, hq.astype(jnp.float32) * h_scale


@pytest.mark.parametrize("renew", [False, True], ids=["plain", "renew"])
@pytest.mark.parametrize("kind", ["numeric", "categorical", "efb"])
def test_quantized_rounds_is_float_rounds_on_dequantized_gradients(kind,
                                                                  renew):
    """Property: off the chip the quantized grower histograms the
    DEQUANTIZED values with the float path's own code, so it is the float
    rounds grower fed those values bit for bit — scales, clipping and the
    in-bag maximum included.  With leaf renewal the structure stays and
    each leaf's value becomes ``leaf_output`` of its rows' TRUE sums."""
    args, kw, num_bins = _quant_case(kind, 255, 256)
    params = SplitParams(min_data_in_leaf=20.0, lambda_l2=0.1)
    common = dict(num_leaves=15, num_bins=num_bins, params=params,
                  leaf_tile=4, use_pallas=False, **kw)
    got, got_leaf = grow_tree_fast(
        *args, quantize_bins=QBINS, stochastic_rounding=False,
        quant_renew=renew, **common)
    gd, hd = _dequantized(args[1], args[2], args[3])
    want, want_leaf = grow_tree_fast(args[0], gd, hd, *args[3:], **common)
    nl = int(want.num_leaves)
    assert nl >= 6
    if kind == "categorical":
        assert bool(np.asarray(want.is_cat)[: nl - 1].any())
    if renew:
        leaf = np.asarray(got_leaf)
        G = np.bincount(leaf, weights=np.asarray(args[1]), minlength=15)
        H = np.bincount(leaf, weights=np.asarray(args[2]), minlength=15)
        true = leaf_output(jnp.asarray(G, jnp.float32),
                           jnp.asarray(H, jnp.float32), params)
        np.testing.assert_allclose(np.asarray(got.leaf_value)[:nl],
                                   np.asarray(true)[:nl], rtol=1e-5,
                                   atol=1e-7)
        assert not np.allclose(np.asarray(got.leaf_value)[:nl],
                               np.asarray(want.leaf_value)[:nl], rtol=1e-6)
        got = got._replace(leaf_value=want.leaf_value)
    assert_same_tree(got, got_leaf, want, want_leaf, exact_values=True)


@pytest.mark.parametrize("stochastic", [False, True],
                         ids=["nearest", "stochastic"])
@pytest.mark.parametrize("kind", ["numeric", "categorical", "efb"])
def test_int8_histogram_route_is_exact_on_integer_gradients(kind,
                                                            stochastic):
    """Property: the integer route itself (64 bins or fewer: int8 x int8
    one-hot products summed in int32, then scaled).  Gradients that are
    whole numbers with max |g| = QBINS/2 and max h = QBINS have both
    scales exactly 1.0, so quantising is the identity under either
    rounding, every sum is a small integer that float32 holds exactly,
    and the int8 tree equals the float tree bit for bit."""
    args, kw, num_bins = _quant_case(kind, 63, 64)
    assert num_bins <= 64
    rng = np.random.RandomState(11)
    g = np.clip(np.round(np.asarray(args[1]) * 3.0), -8, 8)
    g[0], g[1] = 8.0, -8.0
    h = rng.randint(1, QBINS + 1, g.shape[0]).astype(np.float32)
    h[0] = QBINS
    args = (args[0], jnp.asarray(g, jnp.float32), jnp.asarray(h),) + args[3:]
    common = dict(num_leaves=15, num_bins=num_bins,
                  params=SplitParams(min_data_in_leaf=20.0), leaf_tile=4,
                  use_pallas=True, **kw)
    got, got_leaf = grow_tree_fast(
        *args, quantize_bins=QBINS, stochastic_rounding=stochastic,
        quant_key=jax.random.PRNGKey(5), **common)
    want, want_leaf = grow_tree_fast(*args, **common)
    assert int(want.num_leaves) >= 6
    assert_same_tree(got, got_leaf, want, want_leaf, exact_values=True)
