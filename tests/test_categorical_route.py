"""The categorical route of the rounds grower (PR 36): rows of a categorical
slot are routed through a bitset and no per-row gather, the categorical
candidates are searched over the categorical columns alone and ranked
without a sort, and none of it reaches a data set that has no categorical
column.  Every new form against the form it replaced, bit for bit."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.binning import DatasetBinner
from lightgbm_tpu.ops import split as split_mod
from lightgbm_tpu.ops import treegrow_fast as tf
from lightgbm_tpu.ops.split import (BestSplit, SplitParams, find_best_split,
                                    rank_in_order)
from lightgbm_tpu.ops.treegrow import grow_tree
from lightgbm_tpu.ops.treegrow_fast import (_grow_fast_impl, grow_tree_fast,
                                            in_bitset, pack_bitset,
                                            partition_rows)
from lightgbm_tpu.utils import profiling

from test_rounds_equals_strict import assert_same_tree


# ---------------------------------------------------------------------------
# 1. routing: the bitset against the gather, row for row
# ---------------------------------------------------------------------------

def gather_partition(columns, axis, lid, s, accept, inv_rank, right_of,
                     missing_bin_per_feature, leaf_tile):
    """``partition_rows`` as it stood before PR 36: ``cat_mask[leaf][bin]``,
    a gather a row, in every slot."""
    leaf_id = lid
    for r in range(leaf_tile):
        leaf_r = inv_rank[r]
        feat_r = s.feature[leaf_r]
        fcol = jax.lax.dynamic_index_in_dim(
            columns, feat_r, axis=axis, keepdims=False).astype(jnp.int32)
        gl = jnp.where(fcol == missing_bin_per_feature[feat_r],
                       s.default_left[leaf_r],
                       fcol <= s.threshold_bin[leaf_r])
        gl = jnp.where(s.is_cat[leaf_r], s.cat_mask[leaf_r][fcol], gl)
        sel = accept[leaf_r] & (lid == leaf_r)
        leaf_id = jnp.where(sel & ~gl, right_of[leaf_r], leaf_id)
    return leaf_id


def routing_case(seed, num_bins, share_cat, shadow):
    rng = np.random.default_rng(seed)
    n, f, leaves, tile = 4096, 7, 31, 6
    bins = rng.integers(0, num_bins, (n, f)).astype(np.int16)
    lid = rng.integers(0, 12, n).astype(np.int32)
    zeros = jnp.zeros((leaves,), jnp.float32)
    s = BestSplit(
        gain=zeros,
        feature=jnp.asarray(rng.integers(0, f, leaves), jnp.int32),
        threshold_bin=jnp.asarray(rng.integers(0, num_bins, leaves),
                                  jnp.int32),
        default_left=jnp.asarray(rng.random(leaves) < 0.5),
        is_cat=jnp.asarray(rng.random(leaves) < share_cat),
        cat_mask=jnp.asarray(rng.random((leaves, num_bins)) < 0.4),
        left_sum_g=zeros, left_sum_h=zeros, left_count=zeros,
        right_sum_g=zeros, right_sum_h=zeros, right_count=zeros)
    # the round's slots split leaves that hold rows; one is not admitted
    inv_rank = np.concatenate([rng.permutation(12), np.arange(12, leaves)])
    accept = np.ones(leaves, bool)
    accept[inv_rank[tile - 1]] = False
    accept, inv_rank = jnp.asarray(accept), jnp.asarray(inv_rank, jnp.int32)
    right_of = jnp.asarray((12 + np.arange(leaves)).astype(np.int32))
    # some columns with a missing bin (the last), some without
    missing = jnp.asarray(np.where(np.arange(f) % 2 == 0, num_bins - 1, -1),
                          jnp.int32)
    if shadow:  # the feature-major shadow and ids shaped as its tiles
        columns, axis = jnp.asarray(bins.T.reshape(f, 2, 16, 128)), 0
        lid = lid.reshape(2, 16, 128)
    else:
        columns, axis = jnp.asarray(bins), 1
    return (columns, axis, jnp.asarray(lid), s, accept, inv_rank, right_of,
            missing, tile)


@pytest.mark.parametrize("shadow", [False, True], ids=["rows", "shadow"])
@pytest.mark.parametrize("share_cat", [0.0, 0.5, 1.0],
                         ids=["no-cat-slot", "mixed", "all-cat"])
@pytest.mark.parametrize("num_bins", [255, 256, 64, 33, 5])
@pytest.mark.parametrize("seed", [0, 1])
def test_bitset_routing_equals_gather_routing(seed, num_bins, share_cat,
                                              shadow):
    args = routing_case(seed, num_bins, share_cat, shadow)
    want = gather_partition(*args)
    got = partition_rows(*args, True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(got), np.asarray(args[2]))
    if share_cat == 0.0:  # and the numerical route gives the same rows
        np.testing.assert_array_equal(
            np.asarray(partition_rows(*args, False)), np.asarray(want))


@pytest.mark.parametrize("num_bins", [1, 31, 32, 33, 255, 256, 300])
def test_a_bitset_holds_every_bin(num_bins):
    rng = np.random.default_rng(num_bins)
    mask = rng.random((3, num_bins)) < 0.5
    words = pack_bitset(jnp.asarray(mask))
    assert words.shape == (3, -(-num_bins // 32)) and words.dtype == jnp.uint32
    idx = jnp.arange(num_bins, dtype=jnp.int32)
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(in_bitset(words[i], idx)), mask[i])


def test_the_categorical_partition_lowers_without_a_gather():
    args = routing_case(0, 255, 0.5, True)
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                          (args[0], *args[2:8]))

    def lowered(fn):
        return jax.jit(lambda c, *a: fn(c, 0, *a, 6)).lower(*shapes).as_text()

    assert "gather" in lowered(gather_partition)
    assert "gather" not in lowered(
        lambda *a: partition_rows(*a, True))


# ---------------------------------------------------------------------------
# 2. the rank without a sort, and the search on the categorical index set
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(256,), (3, 40), (2, 5, 17), (4, 1)])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rank_equals_argsort_of_argsort_with_ties_in_bin_order(seed, shape):
    rng = np.random.default_rng(seed)
    keys = rng.integers(-3, 4, shape).astype(np.float32) / 4  # many ties
    keys[rng.random(shape) < 0.2] = np.inf  # unused bins
    keys[rng.random(shape) < 0.1] = -0.0  # equal to 0.0 in a sort
    keys[rng.random(shape) < 0.05] = np.nan  # last of all, in bin order
    keys = jnp.asarray(keys)
    want = jnp.argsort(jnp.argsort(keys, axis=-1), axis=-1)
    np.testing.assert_array_equal(np.asarray(rank_in_order(keys)),
                                  np.asarray(want))
    if seed == 0:
        assert "sort" not in jax.jit(rank_in_order).lower(keys).as_text()


def sorted_search(hist_nm, keys):
    """The ordered prefix sums as ``gain_plane`` took them before PR 36."""
    order = jnp.argsort(keys, axis=1)
    return (jnp.argsort(order, axis=1),
            jnp.cumsum(jnp.take_along_axis(hist_nm, order[None], axis=2),
                       axis=2))


def search_case(seed, f=9, b=64, cats=(2, 3, 5, 8)):
    rng = np.random.default_rng(seed)
    hist = rng.standard_normal((3, f, b)).astype(np.float32)
    hist[1] = np.abs(hist[1]) * 30
    hist[2] = rng.integers(0, 25, (f, b))  # some bins empty: unused
    hist[:, 3, 3:] = 0  # one-hot sized: three used bins
    hist[:, 5, 4:] = 0  # four
    hist[:2] *= hist[2] > 0
    missing = np.where(np.arange(f) % 3 == 0, b - 1, -1)
    missing[list(cats)] = -1
    hist[:, missing < 0, b - 1] = 0  # a last bin only where it is a missing bin
    tot = hist[:, 0, :].sum(axis=1)
    mask = np.zeros(f, bool)
    mask[list(cats)] = True
    return (jnp.asarray(hist), tot, jnp.full((f,), b, jnp.int32),
            jnp.asarray(missing, jnp.int32), jnp.asarray(mask))


@pytest.mark.parametrize("with_feature_mask", [False, True])
@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_search_on_the_index_set_equals_search_over_all_columns(
        seed, with_feature_mask):
    hist, tot, nb, missing, mask = search_case(seed)
    fmask = (jnp.asarray(np.arange(hist.shape[1]) != 2)
             if with_feature_mask else None)
    base = dict(min_data_in_leaf=2, min_sum_hessian_in_leaf=1.0,
                max_cat_to_onehot=4, max_cat_threshold=8)
    cols = tuple(int(c) for c in np.flatnonzero(np.asarray(mask)))

    def search(params):
        return find_best_split(hist, tot[0], tot[1], tot[2], nb, missing,
                               params, feature_mask=fmask,
                               categorical_mask=mask)

    want = search(SplitParams(**base))  # cat_features None: every column
    got = search(SplitParams(**base, cat_features=cols))
    for name, a, b_ in zip(BestSplit._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_),
                                      err_msg=name)
    # the columns alone, each in turn: whichever kind wins, the same split
    for f in range(hist.shape[1]):
        only = jnp.asarray(np.arange(hist.shape[1]) == f)
        a = find_best_split(hist, tot[0], tot[1], tot[2], nb, missing,
                            SplitParams(**base, cat_features=cols),
                            feature_mask=only, categorical_mask=mask)
        b_ = find_best_split(hist, tot[0], tot[1], tot[2], nb, missing,
                             SplitParams(**base), feature_mask=only,
                             categorical_mask=mask)
        for name, x, y in zip(BestSplit._fields, a, b_):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                          err_msg=f"{name} column {f}")
        assert bool(a.is_cat) == (bool(mask[f]) and float(a.gain) > -1e29)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_ordered_sums_are_the_sorted_ones_bit_for_bit(seed, monkeypatch):
    """The one-hot placement and the sort put the same bins in the same
    places, so the prefix sums, and with them every candidate's gain, are
    the old ones to the last bit: the search with the sort put back in
    finds the same split with the same sums."""
    hist, tot, nb, missing, mask = search_case(seed + 10)
    params = SplitParams(min_data_in_leaf=2, min_sum_hessian_in_leaf=1.0,
                         cat_features=tuple(np.flatnonzero(np.asarray(mask))))
    got = find_best_split(hist, tot[0], tot[1], tot[2], nb, missing, params,
                          categorical_mask=mask)
    keys = jnp.asarray(np.random.default_rng(seed).integers(
        0, 5, (4, 64)).astype(np.float32))
    h = hist[:, :4, :]
    rank, sums = sorted_search(h, keys)
    np.testing.assert_array_equal(np.asarray(rank_in_order(keys)),
                                  np.asarray(rank))
    at = rank[:, None, :] == jnp.arange(64)[None, :, None]
    placed = jnp.sum(jnp.where(at[None], h[:, :, None, :], 0.0), axis=3)
    np.testing.assert_array_equal(np.asarray(jnp.cumsum(placed, axis=2)),
                                  np.asarray(sums))
    monkeypatch.setattr(
        split_mod, "rank_in_order",
        lambda k: jnp.argsort(jnp.argsort(k, axis=-1), axis=-1))
    want = find_best_split(hist, tot[0], tot[1], tot[2], nb, missing, params,
                           categorical_mask=mask)
    for name, a, b_ in zip(BestSplit._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_),
                                      err_msg=name)


# ---------------------------------------------------------------------------
# 3. the two growers, split for split, on click-log columns
# ---------------------------------------------------------------------------

LEVELS = (3, 4, 24, 105, 255)  # one-hot sized, mid, and every bin a level


def click_case(seed, n=3000, max_bin=255):
    """Integer columns with a missing bin and categorical columns of
    ``LEVELS`` levels, a continuous target (no two candidates near a tie)."""
    rng = np.random.RandomState(seed)
    n_int = 3
    X = np.empty((n, n_int + len(LEVELS)))
    X[:, :n_int] = np.floor(np.exp(rng.randn(n, n_int) * 1.5))
    X[rng.rand(n) < 0.45, 0] = np.nan
    X[rng.rand(n) < 0.05, 2] = np.nan
    y = 0.4 * np.log1p(np.nan_to_num(X[:, 0], nan=7.0)) - 0.2 * np.log1p(
        X[:, 1])
    for j, levels in enumerate(LEVELS):
        c = np.minimum(rng.zipf(1.6, n) - 1, levels - 1)
        X[:, n_int + j] = c
        y = y + (rng.randn(levels) * (0.8 if j % 2 == 0 else 0.3))[c]
    y = y + 0.3 * rng.randn(n)
    cats = tuple(range(n_int, n_int + len(LEVELS)))
    binner = DatasetBinner.fit(X, max_bin=max_bin, categorical_features=cats)
    f = X.shape[1]
    args = (jnp.asarray(binner.transform(X), jnp.int16),
            jnp.asarray(0.6 * y, jnp.float32), jnp.ones((n,), jnp.float32),
            jnp.ones((n,), bool), jnp.ones((n,), jnp.float32),
            jnp.ones((f,), bool), jnp.asarray(binner.num_bins_per_feature),
            jnp.asarray(binner.missing_bin_per_feature))
    assert binner.missing_bin_per_feature[0] >= 0
    return args, jnp.asarray(binner.categorical_mask), cats, binner


@pytest.mark.parametrize("tile", [1, 8])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_rounds_equals_strict_on_click_log_columns(seed, tile):
    args, mask, cats, binner = click_case(seed)
    num_bins = int(binner.max_num_bins) + 1
    common = dict(num_leaves=63, num_bins=num_bins, categorical_mask=mask)
    base = dict(min_data_in_leaf=40.0, cat_smooth=10.0, cat_l2=10.0)
    # the strict grower over every column, the rounds grower on the set
    want, want_leaf = grow_tree(*args, params=SplitParams(**base), **common)
    got, got_leaf = grow_tree_fast(
        *args, params=SplitParams(**base, cat_features=cats),
        leaf_tile=tile, use_pallas=False, **common)
    nl = int(want.num_leaves)
    assert 8 <= nl < 63, nl
    nodes = slice(0, nl - 1)
    assert np.asarray(want.is_cat)[nodes].any()
    assert (~np.asarray(want.is_cat)[nodes]).any()
    assert_same_tree(got, got_leaf, want, want_leaf)


def test_both_missing_directions_are_taken_on_click_log_columns():
    """Over the seeds of the test above a missing integer goes left in some
    node and right in another: the default direction is chosen by gain."""
    seen = set()
    for seed in range(3):
        args, mask, cats, binner = click_case(seed)
        tree, _ = grow_tree_fast(
            *args, params=SplitParams(min_data_in_leaf=40.0,
                                      cat_features=cats),
            num_leaves=63, num_bins=int(binner.max_num_bins) + 1,
            categorical_mask=mask, leaf_tile=8, use_pallas=False)
        nl = int(tree.num_leaves)
        feat = np.asarray(tree.split_feature)[:nl - 1]
        on_missing = np.isin(feat, [0, 2]) & ~np.asarray(tree.is_cat)[:nl - 1]
        seen |= set(np.asarray(tree.default_left)[:nl - 1][on_missing])
    assert seen == {False, True}


def test_every_family_of_categorical_split_is_taken():
    """One-hot (a column of 3 or 4 levels) and many-against-many (24 levels
    or more) both win nodes of these trees."""
    families = set()
    for seed in range(3):
        args, mask, cats, binner = click_case(seed)
        tree, _ = grow_tree_fast(
            *args, params=SplitParams(min_data_in_leaf=40.0,
                                      cat_features=cats),
            num_leaves=63, num_bins=int(binner.max_num_bins) + 1,
            categorical_mask=mask, leaf_tile=8, use_pallas=False)
        nl = int(tree.num_leaves)
        for node in np.flatnonzero(np.asarray(tree.is_cat)[:nl - 1]):
            levels = LEVELS[int(tree.split_feature[node]) - 3]
            left = int(np.asarray(tree.cat_mask)[node].sum())
            families.add("one-hot" if levels <= 4 else "many")
            assert 1 <= left <= 32 and (levels > 4 or left == 1)
    assert families == {"one-hot", "many"}


# ---------------------------------------------------------------------------
# 4. a data set without a categorical column sees none of this
# ---------------------------------------------------------------------------

def lowered_grower(num_bins, use_pallas, categorical=False,
                   params=SplitParams(min_data_in_leaf=1), debug=False):
    """The rounds grower lowered for the TPU at toy size (nothing compiles,
    nothing runs), the Mosaic kernel's serialized body cut out: it carries
    the line numbers of this package's files."""
    n, f = 1500, 5
    s = jax.ShapeDtypeStruct
    args = (s((n, f), jnp.int16), s((n,), jnp.float32), s((n,), jnp.float32),
            s((n,), jnp.bool_), s((n,), jnp.float32), s((f,), jnp.bool_),
            s((f,), jnp.int32), s((f,), jnp.int32))
    if categorical:
        args += (s((f,), jnp.bool_),)
    lowered = _grow_fast_impl.trace(
        *args, num_leaves=8, num_bins=num_bins, params=params, leaf_tile=4,
        hist_precision="f32", use_pallas=use_pallas).lower(
            lowering_platforms=("tpu",))
    text = (lowered.as_text(dialect="hlo", debug_info=True) if debug
            else lowered.as_text())
    return re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", text)


@pytest.mark.parametrize("route", [(255, True), (63, True), (255, False)])
def test_without_a_categorical_column_stated_columns_change_no_program(route):
    """Relational, inside this tree: with no categorical mask the grower
    lowers to one text whatever ``cat_features`` says, so nothing of the
    categorical search is keyed into a numerical data set's program (the
    accepted cells' numbers on the chip are PERF.md section 6, PR 36, (8))."""
    plain = lowered_grower(*route)
    stated = lowered_grower(*route, params=SplitParams(
        min_data_in_leaf=1, cat_features=(1, 3)))
    assert stated == plain
    assert "grow.cat_search" not in plain


def test_without_a_categorical_column_no_categorical_code_is_traced(
        monkeypatch):
    def never(*a, **k):
        raise AssertionError("categorical code traced for numerical data")

    monkeypatch.setattr(split_mod, "_categorical_candidates", never)
    monkeypatch.setattr(tf, "pack_bitset", never)
    text = lowered_grower(255, False)
    assert "grow.cat_search" not in text
    with pytest.raises(AssertionError, match="categorical code traced"):
        lowered_grower(255, False, categorical=True)


def test_the_cat_search_scope_reaches_the_hlo_inside_split_search():
    """``tests/test_phase_tracing.py``'s pattern, for the one phase that
    needs a categorical column: the scope is in the lowered HLO, nested in
    ``grow.split_search``, and the innermost scope is what an operation is
    booked under."""
    assert "grow.cat_search" in profiling.DEVICE_PHASES
    text = lowered_grower(255, True, categorical=True, debug=True,
                          params=SplitParams(min_data_in_leaf=1,
                                             cat_features=(1, 3)))
    names = set(re.findall(r'op_name="([^"]*)"', text))
    hits = [n for n in names if profiling.phase_of(n) == "grow.cat_search"]
    assert hits
    for n in hits:  # the children's search is vmapped: vmap(grow.cat_search)
        before = n.split("grow.cat_search")[0].split("/")
        assert "grow.split_search" in before
    # the numerical plane of the same search stays under grow.split_search
    assert any(profiling.phase_of(n) == "grow.split_search" for n in names)
    # and no sort is left in the search
    assert not re.search(r"= \S+ sort\(.*grow\.(cat|split)_search", text)


# ---------------------------------------------------------------------------
# 5. what the route tells the operator
# ---------------------------------------------------------------------------

def click_frame(seed=0, n=2000):
    rng = np.random.RandomState(seed)
    X = np.empty((n, 4))
    X[:, 0] = np.floor(np.exp(rng.randn(n)))
    X[rng.rand(n) < 0.3, 0] = np.nan
    X[:, 1] = rng.randn(n)
    X[:, 2] = np.minimum(rng.zipf(1.5, n) - 1, 39)  # 40 levels
    X[:, 3] = rng.randint(0, 3, n)  # 3 levels
    y = ((rng.randn(40) * 1.5)[X[:, 2].astype(int)] + X[:, 1]
         + rng.randn(n) > 0).astype(float)
    return X, y


@pytest.mark.parametrize("cats", [[2, 3], []], ids=["categorical", "plain"])
def test_the_flush_counts_the_nodes_and_the_booster_sets_the_gauges(cats):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.obs import metrics as obs

    obs.reset()
    X, y = click_frame()
    ds = lgb.Dataset(X, label=y, categorical_feature=cats)
    bst = lgb.Booster({"objective": "binary", "verbosity": -1,
                       "num_leaves": 15, "min_data_in_leaf": 20,
                       "tree_growth_mode": "rounds",
                       "fused_training": False}, ds)
    assert bst._gbdt._split_params.cat_features == (tuple(cats) or None)
    assert obs.gauge("cat_features").value == len(cats)
    assert obs.gauge("cat_bins_longest").value == (40 if cats else 0)
    for _ in range(3):
        bst.update()
    trees = bst._gbdt.models  # the flush counts
    assert len(trees) == 3
    nodes = sum(t.num_leaves - 1 for t in trees)
    cat_nodes = sum(t.num_cat for t in trees)
    assert obs.counter("train_split_nodes_total").value == nodes > 0
    assert obs.counter("train_cat_split_nodes_total").value == cat_nodes
    assert (cat_nodes > 0) == bool(cats)


def test_a_reset_keeps_the_search_on_the_categorical_columns():
    """A learning-rate schedule (``reset_parameter``) rebuilds SplitParams
    from the configuration and the binner: the jit key is the one it was,
    stated columns included, so the next tree neither compiles nor traces a
    grower anew, nor falls to the search over all columns."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.sanitizer import CompileCounter

    X, y = click_frame()
    ds = lgb.Dataset(X, label=y, categorical_feature=[2, 3])
    bst = lgb.Booster({"objective": "binary", "verbosity": -1,
                       "num_leaves": 15, "min_data_in_leaf": 20,
                       "tree_growth_mode": "rounds",
                       "fused_training": False}, ds)
    before = bst._gbdt._split_params
    assert before.cat_features == (2, 3)
    bst.update()
    bst.update()
    bst.reset_parameter({"learning_rate": 0.05})
    assert bst._gbdt._split_params == before
    with CompileCounter() as c:
        bst.update()
    assert c.compiles == 0 and c.traces == 0
    bst.reset_parameter({"lambda_l2": 1.0})  # a baked constant: a new key,
    assert bst._gbdt._split_params.cat_features == (2, 3)  # the same columns
