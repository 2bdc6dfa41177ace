"""The rounds grower against the strict grower, split for split.

``ops/treegrow_fast.py``'s docstring: the only deviation from the strict
leaf-wise grower is the growth ORDER, and the leaf set differs only "when
the ``num_leaves`` budget truncates the final round".  So on a tree that
saturates before the budget binds (``min_data_in_leaf`` or ``max_depth``
stops it) both growers must give the same splits — feature, threshold bin,
default direction, every row's leaf and the leaf counts exactly; gains and
leaf values to float tolerance — up to the order of node ids.  That is what this
file pins, over data kind x histogram route x ``leaf_tile``; where the
budget does truncate, the invariants every tree owes are pinned instead.

Why a tolerance and not bits: the two growers sum a leaf's rows in
different orders (one multi-slot pass a round against one pass a split, an
einsum against a scatter), so a histogram cell differs in its last bits and
the larger sibling, taken by subtraction, with it.  The fixtures draw
continuous targets, whose candidate gains lie orders of magnitude further
apart than that, so the argmax never flips.

Both cells of the benchmark run this grower; until PR 30 tier-1 reached it
only as the reference of the windowed family's tests.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.binning import DatasetBinner
from lightgbm_tpu.ops.hist_pallas import recommended_leaf_tile
from lightgbm_tpu.ops.split import SplitParams, leaf_output
from lightgbm_tpu.ops.treegrow import grow_tree
from lightgbm_tpu.ops.treegrow_fast import grow_tree_fast

N, F = 480, 4
NUM_LEAVES = 31  # never reached: min_data_in_leaf / max_depth stop first

# histogram route: (max_bin, num_bins, use_pallas).  With use_pallas the
# rounds grower takes the fused one-hot einsum at 64 bins or fewer (pure
# XLA, so it runs here); at 255 bins the CPU has only the scatter fallback.
ROUTES = {"b63-einsum": (63, 64, True), "b255-cpu": (255, 256, False)}


def canon(tree):
    """Preorder walk of a device tree: (node ids, leaf ids, rank of each
    leaf id), so two trees compare position by position whatever order
    their growers numbered nodes and leaves in (and whichever side a
    categorical node calls left)."""
    t = jax.tree_util.tree_map(np.asarray, tree)
    nodes, leaves = [], []
    rank = np.full(t.leaf_value.shape[0], -1, np.int64)

    def walk(c):
        if c < 0:
            rank[~c] = len(leaves)
            leaves.append(~c)
            return
        nodes.append(c)
        kids = [int(t.left_child[c]), int(t.right_child[c])]
        if t.is_cat[c]:
            # "these categories left" and "the other categories left" are
            # one partition and tie exactly, so which side is called left
            # is the argmax's pick among equals: smaller side first
            kids.sort(key=lambda k: t.internal_count[k] if k >= 0
                      else t.leaf_count[~k])
        for k in kids:
            walk(k)

    if int(t.num_leaves) > 1:
        walk(0)
    else:
        rank[0] = 0
        leaves.append(0)
    assert len(leaves) == int(t.num_leaves)
    return t, np.asarray(nodes, np.int64), np.asarray(leaves, np.int64), rank


def assert_same_tree(got, got_leaf, want, want_leaf, *, exact_values=False):
    """``got`` and ``want`` are the same tree up to node/leaf numbering."""
    g, gn, gl, grank = canon(got)
    w, wn, wl, wrank = canon(want)
    assert int(g.num_leaves) == int(w.num_leaves)
    for name in ("split_feature", "default_left", "is_cat"):
        np.testing.assert_array_equal(
            getattr(g, name)[gn], getattr(w, name)[wn], err_msg=name)
    # a categorical node's threshold counts the categories of its sorted
    # prefix; one with no row in the node moves no row and no gain, so two
    # thresholds tie exactly and the last bit of a sum picks between them.
    # The rows' leaves, compared below, are what such a node decides.
    num = ~g.is_cat[gn]
    np.testing.assert_array_equal(g.threshold_bin[gn][num],
                                  w.threshold_bin[wn][num])
    np.testing.assert_array_equal(g.leaf_count[gl], w.leaf_count[wl])
    np.testing.assert_array_equal(g.leaf_depth[gl], w.leaf_depth[wl])
    tol = dict(rtol=0, atol=0) if exact_values else dict(rtol=2e-4, atol=1e-6)
    np.testing.assert_allclose(g.split_gain[gn], w.split_gain[wn], **tol)
    np.testing.assert_allclose(g.leaf_value[gl], w.leaf_value[wl], **tol)
    np.testing.assert_allclose(g.leaf_weight[gl], w.leaf_weight[wl], **tol)
    # every row lands in the same leaf
    np.testing.assert_array_equal(grank[np.asarray(got_leaf)],
                                  wrank[np.asarray(want_leaf)])


def make_case(kind, max_bin, seed=0, n=N, f=F):
    """Binned inputs of one data kind at the grower's call: the positional
    arguments both growers share, the keyword arguments both take, and the
    ``SplitParams``/``max_depth`` that make the tree saturate."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = X @ rng.randn(f) + 0.5 * np.sin(3 * X[:, 0]) + 0.3 * rng.randn(n)
    cats = ()
    if kind == "missing":
        X[rng.rand(n) < 0.15, 1] = np.nan
        X[rng.rand(n) < 0.10, 2] = np.nan
    if kind == "categorical":
        c = rng.randint(0, 9, n)
        X[:, 0] = c
        y = (rng.randn(9) * 2.0)[c] + X[:, 1] + 0.3 * rng.randn(n)
        cats = (0,)
    binner = DatasetBinner.fit(X, max_bin=max_bin, categorical_features=cats)
    bins = jnp.asarray(binner.transform(X), jnp.int16)
    grad = jnp.asarray(0.6 * y, jnp.float32)
    hess = jnp.ones((n,), jnp.float32)
    row_mask = jnp.ones((n,), bool)
    sample_weight = jnp.ones((n,), jnp.float32)
    kw = {}
    params = SplitParams(min_data_in_leaf=30.0)
    max_depth = -1
    if kind == "mask":
        row_mask = jnp.asarray(rng.rand(n) < 0.75)
    if kind == "weights":
        w = rng.uniform(0.5, 2.0, n)
        grad = jnp.asarray(0.6 * y * w, jnp.float32)
        hess = jnp.asarray(w, jnp.float32)
        sample_weight = jnp.asarray(rng.choice([1.0, 2.5], n), jnp.float32)
        params = SplitParams(min_data_in_leaf=30.0,
                             min_sum_hessian_in_leaf=1.0, lambda_l2=0.5)
    if kind == "categorical":
        kw["categorical_mask"] = jnp.asarray(np.arange(f) == 0)
    if kind == "monotone":
        kw["monotone_constraints"] = jnp.asarray([1, -1, 0, 0][:f], jnp.int32)
    if kind == "max_depth":
        params = SplitParams(min_data_in_leaf=5.0)
        max_depth = 3
    args = (bins, grad, hess, row_mask, sample_weight, jnp.ones((f,), bool),
            jnp.asarray(binner.num_bins_per_feature),
            jnp.asarray(binner.missing_bin_per_feature))
    return args, kw, params, max_depth


def tiles(num_bins, f, num_leaves=NUM_LEAVES, quantized=False):
    return {"tile1": 1,
            "tile-rec": recommended_leaf_tile(num_bins, f, num_leaves,
                                              quantized=quantized)}


CALL_KINDS = ("numeric", "mask", "missing", "categorical", "weights",
              "monotone", "max_depth")


@pytest.mark.parametrize("tile", ["tile1", "tile-rec"])
@pytest.mark.parametrize("route", sorted(ROUTES))
@pytest.mark.parametrize("kind", CALL_KINDS)
def test_rounds_equals_strict_on_saturating_trees(kind, route, tile):
    max_bin, num_bins, use_pallas = ROUTES[route]
    args, kw, params, max_depth = make_case(kind, max_bin)
    common = dict(num_leaves=NUM_LEAVES, num_bins=num_bins,
                  max_depth=max_depth, params=params, **kw)
    want, want_leaf = grow_tree(*args, **common)
    got, got_leaf = grow_tree_fast(
        *args, leaf_tile=tiles(num_bins, F)[tile], use_pallas=use_pallas,
        **common)
    nl = int(want.num_leaves)
    # the fixture does what its name says: a real tree, under the budget
    assert 4 <= nl < NUM_LEAVES, nl
    if kind == "categorical":
        assert bool(np.asarray(want.is_cat)[: nl - 1].any())
    if kind == "missing":
        used = np.asarray(want.split_feature)[: nl - 1]
        assert np.isin(used, [1, 2]).any()
    if kind == "max_depth":
        assert int(np.asarray(want.leaf_depth)[:nl].max()) == 3
    assert_same_tree(got, got_leaf, want, want_leaf)
