"""Plain reference: leaf-wise histogram GBDT for binary log loss over integer
columns with a missing bin and categorical columns, its trees grown in rounds
as ``leafwise_rounds`` grows them.

Straightforward ``jax.numpy``, float32, no kernel, nothing imported from the
program and nothing the program made: the bins and labels the benchmark drew
from the seed and the training parameters of the configuration's file.  The
histogram pass, the gradients and the round schedule are ``leafwise_rounds``'
own (see that file); what is here is the split search and the routing.

Which column is what.  ``params["categorical_feature"]`` lists the
categorical columns, as a user hands them to ``lgb.train``.  Every other
column is an integer column whose last bin, ``missing_bin(max_bin)`` of the
generator's module (``datagen/click_columns.py``, benchmark code), holds its
missing values; a categorical column of this deployment has no missing bin.

The search of one leaf, from its histogram ``[3, F, B]`` (gradient, hessian,
count) and its sums ``G, H, C``; ``gain(g, h, l2) = g^2 / (h + l2)``:

* **an integer column.**  The missing bin is set aside (its sums ``m``).  A
  threshold ``t`` (every bin but the last two: the last bin that holds a
  value, and the missing bin) sends ``bin <= t`` left; it is tried twice, the
  missing rows right (left sums: the prefix) and left (the prefix plus
  ``m``); ``gain(L) + gain(R) - gain(parent)`` with ``lambda_l2``.  Missing
  goes left only where that gains strictly more.
* **a categorical column.**  Its used bins are those holding a row.  With at
  most ``max_cat_to_onehot`` used bins: every used bin alone on the left.
  Else: the used bins ordered by ``g / (h + cat_smooth)`` (``jnp.argsort``,
  so equal keys in bin order), ascending and descending, and every prefix of
  either order on the left, of at most ``max_cat_threshold`` bins, at most
  half of the used bins rounded up, and fewer than all of them; descending
  only where it gains strictly more.  ``lambda_l2 + cat_l2`` in all three
  terms of the gain.  A bin that is not on the left goes right, so a missing
  bin, had the column one, would never be on the left.
* both sides of a candidate hold ``min_data_in_leaf`` rows and
  ``min_sum_hessian_in_leaf`` hessian, and its gain is above
  ``min_gain_to_split``.  The leaf takes the candidate of largest gain, the
  first in (column, bin) order among equals, a categorical candidate lying at
  its bin (one against the rest) or at its prefix's length less one.

A leaf's value is ``-G / (H + lambda_l2)`` times the learning rate, whatever
the split that made it (``cat_l2`` is in the gain alone).

**Where this, and the program, part from upstream**
(``FeatureHistogram::FindBestThresholdCategoricalInner``, LightGBM 4.x, from
memory: no copy is on this machine).  Upstream drops the bins that hold
fewer than ``cat_smooth`` rows from the order before it scans it; scans with
``min_data_per_group`` as the least rows a side of the scan may add
(``min_data_per_group`` is read by the program's binner alone,
``lightgbm_tpu/binning.py``, and by no split search); adds ``kEpsilon`` to
the hessians of the one-hot family; and takes the one-against-the-rest
family with ``lambda_l2`` alone, ``cat_l2`` entering only the ordered scan.
Neither side here does any of the four: listed, not changed (PERF.md section
7, 8, is the precedent).
"""

from __future__ import annotations

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np


def _file(*parts: str):
    path = pathlib.Path(__file__).resolve().parents[1].joinpath(*parts)
    spec = importlib.util.spec_from_file_location(
        f"chipbench_file_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


rounds = _file("reference", "leafwise_rounds.py")


def missing_bin(n_bins: int) -> int:
    """An integer column's missing bin, by the generator's convention."""
    return _file("datagen", "click_columns.py").missing_bin(n_bins)


def _best_split(hist, g_sum, h_sum, count, is_cat, miss_bin, *, min_data,
                min_hess, l2, min_gain, cat_l2, cat_smooth, max_cat_threshold,
                max_cat_to_onehot):
    """The best candidate of one leaf: (gain, column, bin, left gradient,
    hessian, count, missing goes left, categorical, the bins on the left
    ``[B]``).  ``is_cat`` ``[F]`` bool; ``miss_bin`` ``[F]``, -1 for none."""
    _, f, b = hist.shape
    idx = jnp.arange(b)
    at_missing = idx[None, :] == miss_bin[:, None]  # [F, B]
    held = jnp.where(at_missing[None], 0.0, hist)
    m = jnp.sum(jnp.where(at_missing[None], hist, 0.0), axis=2)  # [3, F]

    def sides_ok(lc, lh):
        return ((lc >= min_data) & (count - lc >= min_data)
                & (lh >= min_hess) & (h_sum - lh >= min_hess))

    def split_gain(lg, lh, reg):
        def leaf(g, h):
            return g * g / (h + reg + rounds.KEPSILON)
        return leaf(lg, lh) + leaf(g_sum - lg, h_sum - lh) - leaf(g_sum, h_sum)

    # ---- integer columns: a threshold, the missing rows either way ----
    last_value_bin = b - jnp.where(miss_bin >= 0, 2, 1)  # [F]
    is_threshold = idx[None, :] < last_value_bin[:, None]
    prefix = jnp.cumsum(held, axis=2)

    def thresholds(add):
        lg, lh, lc = (prefix[c] + add[c][:, None] for c in range(3))
        gain = jnp.where(is_threshold & sides_ok(lc, lh),
                         split_gain(lg, lh, l2), -jnp.inf)
        return gain, (lg, lh, lc)

    gain_right, left_right = thresholds(jnp.zeros_like(m))
    gain_left, left_left = thresholds(m)
    to_left = gain_left > gain_right
    num_gain = jnp.where(to_left, gain_left, gain_right)
    num_left = tuple(jnp.where(to_left, a, c)
                     for a, c in zip(left_left, left_right))

    # ---- categorical columns ----
    used = (held[2] > 0) & ~at_missing
    n_used = jnp.sum(used, axis=1)[:, None]
    ratio = held[0] / (held[1] + cat_smooth)
    reg = l2 + cat_l2

    def ordered(keys):
        order = jnp.argsort(keys, axis=1)  # stable: equal keys in bin order
        rank = jnp.argsort(order, axis=1)
        run = jnp.cumsum(jnp.take_along_axis(held, order[None], axis=2),
                         axis=2)
        lg, lh, lc = run[0], run[1], run[2]
        length = idx[None, :] + 1
        ok = ((length <= max_cat_threshold) & (length <= (n_used + 1) // 2)
              & (length < n_used) & sides_ok(lc, lh))
        return (jnp.where(ok, split_gain(lg, lh, reg), -jnp.inf), rank,
                (lg, lh, lc))

    gain_asc, rank_asc, left_asc = ordered(jnp.where(used, ratio, jnp.inf))
    gain_desc, rank_desc, left_desc = ordered(jnp.where(used, -ratio, jnp.inf))
    gain_one = jnp.where(used & sides_ok(held[2], held[1]),
                         split_gain(held[0], held[1], reg), -jnp.inf)
    one_hot = n_used <= max_cat_to_onehot
    descending = gain_desc > gain_asc
    cat_gain = jnp.where(one_hot, gain_one,
                         jnp.where(descending, gain_desc, gain_asc))
    cat_left = tuple(
        jnp.where(one_hot, one, jnp.where(descending, d, a))
        for one, d, a in zip((held[0], held[1], held[2]), left_desc, left_asc))

    cat_col = is_cat[:, None]
    gain = jnp.where(cat_col, cat_gain, num_gain)
    gain = jnp.where(gain > min_gain, gain, -jnp.inf).reshape(-1)
    best = jnp.argmax(gain)
    col, at = best // b, best % b
    left = tuple(jnp.where(cat_col, c, n_).reshape(-1)[best]
                 for c, n_ in zip(cat_left, num_left))
    on_left = jnp.where(
        one_hot[col, 0], idx == at,
        jnp.where(descending[col, at], rank_desc[col], rank_asc[col]) <= at)
    return (gain[best], col.astype(jnp.int32), at.astype(jnp.int32), *left,
            to_left.reshape(-1)[best] & ~is_cat[col], is_cat[col],
            on_left & is_cat[col])


SEARCH_STATICS = ("min_data", "min_hess", "l2", "min_gain", "cat_l2",
                  "cat_smooth", "max_cat_threshold", "max_cat_to_onehot")


@functools.partial(jax.jit, static_argnames=SEARCH_STATICS)
def _search_root(hist0, is_cat, miss_bin, **kw):
    tot = hist0[:, 0, :].sum(axis=1)
    return tot, _best_split(hist0, tot[0], tot[1], tot[2], is_cat, miss_bin,
                            **kw)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnames=SEARCH_STATICS)
def _settle_round(hists, small, left_leaf, right_leaf, small_is_left,
                  child_stats, is_cat, miss_bin, **kw):
    """``leafwise_rounds._settle_round`` with this file's search: store both
    children's histograms of every split of the round and search them."""
    n_leaves = hists.shape[0]
    parent = hists[jnp.clip(left_leaf, 0, n_leaves - 1)]
    big = parent - small
    sel = small_is_left[:, None, None, None]
    left = jnp.where(sel, small, big)
    right = jnp.where(sel, big, small)
    hists = hists.at[left_leaf].set(left, mode="drop")
    hists = hists.at[right_leaf].set(right, mode="drop")
    search = jax.vmap(lambda h, g_, h_, c_: _best_split(
        h, g_, h_, c_, is_cat, miss_bin, **kw))
    return hists, search(jnp.concatenate([left, right], axis=0),
                         child_stats[:, 0], child_stats[:, 1],
                         child_stats[:, 2])


@jax.jit
def _partition(leaf_id, bins, miss_bin, leaf, feature, threshold, to_left,
               is_cat, members, right):
    """Send the rows of every split leaf that do not go left to the new
    leaf.  An integer split: ``bin <= threshold`` left, the missing bin where
    the split says.  A categorical split: left where the row's bin is one of
    the split's ``members`` (``[tile, max_cat_threshold]``, padded with -1).
    An unused slot has ``leaf == -2`` and matches no row."""
    out = leaf_id
    for r in range(leaf.shape[0]):
        col = jnp.take(bins, feature[r], axis=1).astype(jnp.int32)
        by_value = jnp.where(col == miss_bin[feature[r]], to_left[r],
                             col <= threshold[r])
        by_level = jnp.any(col[:, None] == members[r][None, :], axis=1)
        left = jnp.where(is_cat[r], by_level, by_value)
        out = jnp.where((leaf_id == leaf[r]) & ~left, right[r], out)
    return out


def train(bins: np.ndarray, label: np.ndarray, params: dict, *, n_trees: int,
          leaf_tile: int, row_block: int = 2048,
          payload_terms: int = 3) -> dict:
    """Boost ``n_trees`` trees; returns what ``leafwise_rounds.train``
    returns (the score after 0..n_trees trees and per tree the sum of split
    gains, the root split's gain and hessian total, the leaf count, each
    leaf's row count) and per split whether it is categorical and the bins it
    sends left."""
    n, f = bins.shape
    n_bins = int(params["max_bin"])
    num_leaves = int(params["num_leaves"])
    lr = float(params["learning_rate"])
    l2 = float(params.get("lambda_l2", 0.0))
    most = int(params.get("max_cat_threshold", 32))
    kw = dict(min_data=float(params.get("min_data_in_leaf", 20)),
              min_hess=float(params.get("min_sum_hessian_in_leaf", 1e-3)),
              l2=l2, min_gain=float(params.get("min_gain_to_split", 0.0)),
              cat_l2=float(params.get("cat_l2", 10.0)),
              cat_smooth=float(params.get("cat_smooth", 10.0)),
              max_cat_threshold=most,
              max_cat_to_onehot=int(params.get("max_cat_to_onehot", 4)))
    tile = int(leaf_tile)
    cat_cols = np.zeros(f, bool)
    cat_cols[list(params.get("categorical_feature", ()))] = True
    is_cat = jnp.asarray(cat_cols)
    miss_bin = jnp.asarray(np.where(cat_cols, -1, missing_bin(n_bins)),
                           jnp.int32)

    n_pad = -(-n // row_block) * row_block
    bins_d = jnp.pad(jnp.asarray(bins, jnp.uint8), ((0, n_pad - n), (0, 0)))
    label_d = jnp.pad(jnp.asarray(label, jnp.float32), (0, n_pad - n))
    root_id = jnp.where(jnp.arange(n_pad) < n, 0, -1).astype(jnp.int32)
    mean = float(np.mean(np.asarray(label, np.float64)))
    score = jnp.full((n_pad,), np.log(mean / (1.0 - mean)), jnp.float32)
    scores = [np.asarray(score[:n])]
    trees = []
    hist = functools.partial(
        rounds._hist_pass, n_slots=tile, n_bins=n_bins, row_block=row_block,
        terms=payload_terms,
        dot_dtype=(jnp.bfloat16 if jax.default_backend() == "tpu"
                   else jnp.float32))

    def found_rows(found, r):
        return tuple(np.asarray(x[r]) for x in found)

    for _ in range(n_trees):
        g, h = rounds._gradients(score, label_d)
        leaf_id = root_id
        hist0 = hist(bins_d, root_id, g, h)[0]
        hists = jnp.zeros((num_leaves,) + hist0.shape, jnp.float32)
        hists = hists.at[0].set(hist0)
        tot, best0 = _search_root(hist0, is_cat, miss_bin, **kw)
        tot = np.asarray(tot, np.float64)
        # host-side leaf table: sums, and each searched leaf's best split
        sums = {0: tuple(tot)}
        best = {0: tuple(np.asarray(x) for x in best0)}
        n_leaves, gain_sum, root_gain = 1, 0.0, float(best[0][0])
        splits = []

        while n_leaves < num_leaves:
            can = sorted((l for l in best if best[l][0] > -np.inf),
                         key=lambda l: (-best[l][0], l))
            acc = can[:min(tile, num_leaves - n_leaves)]
            if not acc:
                break
            leaf = np.full(tile, -2, np.int32)
            feat = np.zeros(tile, np.int32)
            thr = np.zeros(tile, np.int32)
            to_left = np.zeros(tile, bool)
            cat = np.zeros(tile, bool)
            members = np.full((tile, most), -1, np.int32)
            right = np.full(tile, num_leaves, np.int32)
            left_slot = np.full(tile, num_leaves, np.int32)
            small_is_left = np.zeros(tile, bool)
            slot_of_leaf = np.full(num_leaves, -1, np.int32)
            stats = np.zeros((2 * tile, 3), np.float32)
            for r, l in enumerate(acc):
                gain, ft, tb, lg, lh, lc, dl, ic, on_left = best.pop(l)
                gain, lg, lh, lc = (x.item() for x in (gain, lg, lh, lc))
                pg, ph, pc = sums[l]
                new = n_leaves + r
                leaf[r], feat[r], thr[r], right[r] = l, ft, tb, new
                to_left[r], cat[r] = dl, ic
                levels = np.flatnonzero(on_left)
                members[r, :len(levels)] = levels
                left_slot[r] = l
                sums[l] = (lg, lh, lc)
                sums[new] = (pg - lg, ph - lh, pc - lc)
                small_is_left[r] = lc <= pc - lc
                slot_of_leaf[l if small_is_left[r] else new] = r
                stats[r] = sums[l]
                stats[tile + r] = sums[new]
                gain_sum += gain
                splits.append({"leaf": l, "feature": int(ft), "bin": int(tb),
                               "gain": gain, "left_count": lc,
                               "left_hess": lh, "default_left": bool(dl),
                               "categorical": bool(ic),
                               "left_bins": levels.tolist()})
            leaf_id = _partition(leaf_id, bins_d, miss_bin, leaf, feat, thr,
                                 to_left, cat, members, right)
            small = hist(bins_d,
                         rounds._slots_of(leaf_id, jnp.asarray(slot_of_leaf)),
                         g, h)
            hists, found = _settle_round(
                hists, small, jnp.asarray(left_slot), jnp.asarray(right),
                jnp.asarray(small_is_left), jnp.asarray(stats), is_cat,
                miss_bin, **kw)
            found = [np.asarray(x) for x in found]
            for r, l in enumerate(acc):
                best[l] = found_rows(found, r)
                best[n_leaves + r] = found_rows(found, tile + r)
            n_leaves += len(acc)

        value = np.zeros(num_leaves, np.float32)
        counts = np.zeros(n_leaves, np.int64)
        for l in range(n_leaves):
            sg, sh, sc = sums[l]
            value[l] = -sg / (sh + l2 + rounds.KEPSILON) * lr
            counts[l] = int(round(sc))
        score = rounds._add_leaf_values(score, leaf_id, jnp.asarray(value))
        scores.append(np.asarray(score[:n]))
        trees.append({"gain_sum": gain_sum, "root_gain": root_gain,
                      "num_leaves": n_leaves, "leaf_count": counts,
                      "root_hess": float(tot[1]), "root_sums": tuple(tot),
                      "splits": splits})
        del hists
    return {"scores": scores, "trees": trees}
