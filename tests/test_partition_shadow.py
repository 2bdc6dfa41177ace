"""The rounds grower over the feature-major shadow against the grower
without it, split for split and row for row.

On the chip ``models/gbdt.py`` hands ``Dataset.bins_device_t()`` to
``grow_tree_fast``: the partition then reads a split's column as a run of
whole tiles and the per-row leaf ids ride the shadow's row tiles through the
rounds (PERF.md section 6, PR 31).  The CPU passes no shadow, so until PR 31
no tier-1 test ran that path.  It is integer compares and selects over the
same bins: every tree and every row's leaf must be what the ``(N, F)`` column
path gives, bit for bit, and the rows the shadow pads must enter nothing.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.binning import DatasetBinner
from lightgbm_tpu.ops.hist_pallas import ROW_TILE, pass_counts
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.ops.treegrow_fast import grow_tree_fast

F = 5
NUM_LEAVES = 15
# rows against the shadow's row tile: whole tiles, a ragged last tile, less
# than one tile
ROWS = {"whole-tiles": 2 * ROW_TILE, "ragged": ROW_TILE + 437, "under-a-tile": 600}


def shadow_of(bins):
    """What ``Dataset.bins_device_t`` builds, from a binned matrix alone."""
    n, f = bins.shape
    tiles = -(-n // ROW_TILE)
    t = np.zeros((f, tiles * ROW_TILE), np.int16)
    t[:, :n] = np.asarray(bins).T
    return jnp.asarray(t.reshape(f, tiles, ROW_TILE // 128, 128))


def make_case(kind, n, seed=0):
    """-> (positional arguments of grow_tree_fast, keyword arguments)."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, F)
    y = X @ rng.randn(F) + 0.5 * np.sin(3 * X[:, 0]) + 0.3 * rng.randn(n)
    cats = ()
    if kind.startswith("missing"):
        X[rng.rand(n) < 0.3, 0] = np.nan
        X[rng.rand(n) < 0.2, 1] = np.nan
        # the missing rows' gradients pull them to one side of every split
        y = y + (3.0 if kind == "missing-right" else -3.0) * np.isnan(X[:, 0])
    if kind == "categorical":
        c = rng.randint(0, 9, n)
        X[:, 0] = c
        y = (rng.randn(9) * 2.0)[c] + X[:, 1] + 0.3 * rng.randn(n)
        cats = (0,)
    binner = DatasetBinner.fit(X, max_bin=255, categorical_features=cats)
    bins = jnp.asarray(binner.transform(X), jnp.int16)
    row_mask = jnp.ones((n,), bool)
    kw = dict(num_leaves=NUM_LEAVES, num_bins=256, leaf_tile=4,
              params=SplitParams(min_data_in_leaf=20.0), use_pallas=False)
    if kind == "bagged":
        row_mask = jnp.asarray(rng.rand(n) < 0.6)
    if kind == "categorical":
        kw["categorical_mask"] = jnp.asarray(np.arange(F) == 0)
    if kind == "quantized":
        kw.update(quantize_bins=16, stochastic_rounding=False, quant_renew=True)
    if kind == "forced":
        kw.update(forced_leaf=jnp.asarray([0, 1], jnp.int32),
                  forced_feature=jnp.asarray([2, 3], jnp.int32),
                  forced_bin=jnp.asarray([100, 120], jnp.int32), n_forced=2)
    if kind == "cegb-lazy":
        kw.update(cegb_lazy_penalty=jnp.full((F,), 0.01, jnp.float32),
                  cegb_lazy_used=jnp.zeros((n, F), bool))
    args = (bins, jnp.asarray(0.6 * y, jnp.float32), jnp.ones((n,), jnp.float32),
            row_mask, jnp.ones((n,), jnp.float32), jnp.ones((F,), bool),
            jnp.asarray(binner.num_bins_per_feature),
            jnp.asarray(binner.missing_bin_per_feature))
    return args, kw


def assert_identical(got, want):
    for name, g, w in zip(want[0]._fields, got[0], want[0]):
        if w is not None:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=name)
    for g, w in zip(got[1:], want[1:]):  # the rows' leaves; CEGB's charges
        assert g.shape == w.shape
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


KINDS = ("numeric", "missing-left", "missing-right", "categorical", "bagged",
         "quantized", "forced", "cegb-lazy")
CASES = [(k, r) for k in KINDS for r in ROWS
         if k in ("numeric", "bagged") or r == "ragged"]


@pytest.mark.parametrize("kind,rows", CASES)
def test_shadow_grows_the_column_paths_tree(kind, rows):
    n = ROWS[rows]
    args, kw = make_case(kind, n)
    want = grow_tree_fast(*args, **kw)
    got = grow_tree_fast(*args, bins_t=shadow_of(args[0]), **kw)
    tree = jax.tree_util.tree_map(np.asarray, want[0])
    nl = int(tree.num_leaves)
    assert nl >= 6, nl  # a real tree, several rounds of it
    assert want[1].shape == (n,)
    if kind.startswith("missing"):
        # the fixture does what its name says: a split on a feature with
        # missing rows sends them left, or right
        on_missing = np.isin(tree.split_feature[: nl - 1], [0, 1])
        sides = tree.default_left[: nl - 1][on_missing]
        assert sides.size and bool(sides[0]) == (kind == "missing-left")
    if kind == "categorical":
        assert bool(tree.is_cat[: nl - 1].any())
    if kind == "forced":
        assert list(tree.split_feature[:2]) == [2, 3]
    if kind == "bagged":
        assert 0 < int(np.asarray(args[3]).sum()) < n
    assert_identical(got, want)


def test_shadow_with_the_kernel_interpreted():
    """The chip's route: the Pallas kernel takes its slots by row and its
    counts from the ids as they lie, a row tile an index."""
    from jax.experimental.pallas import tpu as pltpu

    args, kw = make_case("bagged", ROWS["ragged"])
    kw.update(use_pallas=True, hist_precision="f32")
    with pltpu.force_tpu_interpret_mode():
        want = grow_tree_fast(*args, **kw)
        got = grow_tree_fast(*args, bins_t=shadow_of(args[0]), **kw)
    assert int(want[0].hist_blocks) > 0  # the kernel ran, and packed
    assert_identical(got, want)


def test_rows_the_shadow_pads_are_in_no_leaf():
    """The leaves hold N rows, the ones returned, though the shadow pads a
    row tile with bin 0 and the padded rows' ids move with the splits."""
    n = ROWS["under-a-tile"]
    args, kw = make_case("numeric", n)
    tree, leaf_id = grow_tree_fast(*args, bins_t=shadow_of(args[0]), **kw)
    counts = np.bincount(np.asarray(leaf_id), minlength=NUM_LEAVES)
    np.testing.assert_array_equal(
        np.asarray(tree.leaf_count)[: int(tree.num_leaves)],
        counts[: int(tree.num_leaves)])
    assert int(np.asarray(tree.leaf_count).sum()) == n


def test_pass_counts_of_rows_laid_out_by_tile():
    rng = np.random.RandomState(3)
    n = 2 * ROW_TILE + 300
    mask = rng.rand(n) < 0.3
    tiled = np.zeros(3 * ROW_TILE, bool)
    tiled[:n] = mask
    np.testing.assert_array_equal(
        np.asarray(pass_counts(jnp.asarray(tiled.reshape(3, ROW_TILE // 128, 128)))),
        np.asarray(pass_counts(jnp.asarray(mask))))


@pytest.mark.parametrize("rows", sorted(ROWS))
def test_dataset_shadow_is_the_transposed_bins(rows):
    n = ROWS[rows]
    rng = np.random.RandomState(1)
    X = rng.randn(n, F)
    ds = lgb.Dataset(X, label=rng.randn(n)).construct()
    shadow = ds.bins_device_t()
    tiles = -(-n // ROW_TILE)
    assert shadow.shape == (F, tiles, ROW_TILE // 128, 128)
    assert shadow.dtype == jnp.int16
    flat = np.asarray(shadow).reshape(F, -1)
    np.testing.assert_array_equal(flat[:, :n], np.asarray(ds.bins).T)
    assert not flat[:, n:].any()
    assert ds.bins_device_t() is shadow  # kept, not rebuilt


def test_dataset_shadow_is_rebuilt_after_subset():
    rng = np.random.RandomState(2)
    n = ROW_TILE + 50
    ds = lgb.Dataset(rng.randn(n, F), label=rng.randn(n)).construct()
    ds.bins_device_t()
    idx = np.arange(0, n, 3)
    sub = ds.subset(idx)
    shadow = sub.bins_device_t()
    assert shadow.shape == (F, 1, ROW_TILE // 128, 128)
    np.testing.assert_array_equal(
        np.asarray(shadow).reshape(F, -1)[:, : len(idx)],
        np.asarray(ds.bins)[idx].T)


def test_out_of_core_shadow_from_the_device_matrix():
    """The resident out-of-core dataset keeps no host matrix: its shadow is
    the transposed device matrix, padded the same way."""
    rng = np.random.RandomState(4)
    n = 900
    X = rng.randn(n, F)
    ds = lgb.Dataset(X, label=rng.randn(n)).construct()
    want = np.asarray(ds.bins_device_t())
    ooc = lgb.Dataset(X, label=rng.randn(n)).construct()
    ooc.bins, ooc._bins_device_t = None, None  # as _ooc_assemble_device leaves it
    np.testing.assert_array_equal(np.asarray(ooc.bins_device_t()), want)


def test_bundled_features_through_train(monkeypatch):
    """EFB: the histograms run over the bundles, the partition over the
    unbundled features' shadow.  ``models/gbdt.py`` hands the grower the
    dataset's shadow on the chip alone, so here its call is given it."""
    from lightgbm_tpu.ops import treegrow_fast

    rng = np.random.RandomState(0)
    n, groups = ROW_TILE + 300, 4
    cats = rng.randint(0, 8, size=(n, groups))
    X = np.zeros((n, groups * 8 + 2), np.float32)
    for g in range(groups):  # mutually exclusive one-hot blocks: EFB bundles
        X[np.arange(n), g * 8 + cats[:, g]] = 1.0
    X[:, -2:] = rng.randn(n, 2)
    y = ((cats[:, 0] == 3) * 2.0 + (cats[:, 1] >= 4) * 1.0 + X[:, -2]
         + 0.3 * rng.randn(n))
    params = {"objective": "regression", "num_leaves": NUM_LEAVES,
              "min_data_in_leaf": 40, "verbosity": -1,
              "tree_growth_mode": "rounds", "fused_training": False}
    real, seen = treegrow_fast.grow_tree_fast, []

    def models(with_shadow):
        ds = lgb.Dataset(X, label=y)

        def grow(*args, **kw):
            assert args[14] is not None and args[17] is None  # bundles; CPU
            if with_shadow:
                args = args[:17] + (ds.bins_device_t(),) + args[18:]
                seen.append(args[17].shape)
            return real(*args, **kw)

        monkeypatch.setattr(treegrow_fast, "grow_tree_fast", grow)
        bst = lgb.train(params, ds, num_boost_round=3)
        assert ds.efb is not None and ds.efb.num_bundled < X.shape[1] // 2
        return bst.model_to_string()

    assert models(True) == models(False)
    assert seen == [(X.shape[1], 2, ROW_TILE // 128, 128)] * 3
