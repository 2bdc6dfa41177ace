"""Plain reference: leaf-wise histogram GBDT for binary log loss, growing each
tree in rounds.

Straightforward ``jax.numpy``, no kernel, nothing imported from the program
and nothing the program made: it takes the bins and labels the benchmark
drew from the seed and the training parameters of the configuration's file.

Semantics (LightGBM's serial tree learner, as the configuration states them):

* score starts at ``log(p / (1 - p))`` of the label mean; per tree
  ``g = sigmoid(score) - y`` and ``h = p (1 - p)``; with a weight a row the
  mean is the weighted one and both are multiplied by the row's weight (a
  leaf's row count stays a count);
* a leaf's best split is the (feature, bin) with the largest
  ``GL^2/(HL+l2) + GR^2/(HR+l2) - G^2/(H+l2)`` among thresholds that leave
  both sides ``min_data_in_leaf`` rows and ``min_sum_hessian_in_leaf``
  hessian, and only if that gain is above ``min_gain_to_split``; rows with
  ``bin <= threshold`` go left;
* growth is best-first in rounds (``tree_growth_mode=rounds`` with
  ``leaf_tile`` splits a round): every round splits the ``leaf_tile`` leaves
  of largest gain among those already searched, within what is left of
  ``num_leaves``; fresh children are searched at the end of the round and
  compete from the next.  ``leaf_tile = num_leaves`` would be level-free
  best-first growth in which children wait one round;
* a leaf's value is ``-G / (H + l2)`` times the learning rate.

The histogram of a round is one pass over all rows: a one-hot of the bins
times a payload that carries, for each of the round's smaller children, the
gradient and hessian as three bfloat16 terms each (24 bits together, so
exactly) and a count, summed in float32; the larger sibling is the parent less the smaller.
``payload_terms=1`` keeps one bfloat16 term: the precision below, which the
control of the comparison uses.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

KEPSILON = 1e-15
FEATURE_BLOCK = 128


def _split_terms(x, terms: int):
    """x (float32) as ``terms`` addends that bfloat16 holds exactly, largest
    first: each is what is left, cut to its leading 8 bits, so three of them
    are x to the last bit.  Cut by masking the bits, not by converting to
    bfloat16 and back: XLA on the TPU takes such a round trip for a no-op
    (``xla_allow_excess_precision``), which left one term and no others."""
    out, rest = [], x
    for _ in range(terms):
        bits = jax.lax.bitcast_convert_type(rest, jnp.uint32)
        t = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                         jnp.float32)
        out.append(t.astype(jnp.bfloat16))
        rest = rest - t
    return out


@functools.partial(jax.jit, static_argnames=("n_slots", "n_bins", "row_block",
                                             "terms", "dot_dtype"))
def _hist_pass(bins, slot, g, h, *, n_slots, n_bins, row_block, terms,
               dot_dtype):
    """Per-slot histograms ``[n_slots, 3, F, B]`` (gradient, hessian, count)
    of the rows whose ``slot`` is 0..n_slots-1; rows with slot -1 count
    nowhere.  ``dot_dtype`` is the type the product's operands are handed
    over in: bfloat16 on the chip, float32 where the backend has no
    bfloat16 product; the operands' values are bfloat16 either way."""
    n, f = bins.shape
    lanes = 2 * terms + 1
    blocks = [(a, min(a + FEATURE_BLOCK, f))
              for a in range(0, f, FEATURE_BLOCK)]
    bin_ids = jnp.arange(n_bins, dtype=bins.dtype)
    slot_ids = jnp.arange(n_slots, dtype=jnp.int32)

    def body(i, acc):
        lo = i * row_block
        b = jax.lax.dynamic_slice_in_dim(bins, lo, row_block, 0)
        s = jax.lax.dynamic_slice_in_dim(slot, lo, row_block, 0)
        gb = jax.lax.dynamic_slice_in_dim(g, lo, row_block, 0)
        hb = jax.lax.dynamic_slice_in_dim(h, lo, row_block, 0)
        chans = (_split_terms(gb, terms) + _split_terms(hb, terms)
                 + [jnp.ones_like(gb, jnp.bfloat16)])
        pay = jnp.stack(chans, axis=1)  # [R, lanes]
        in_slot = (s[:, None] == slot_ids[None, :]).astype(jnp.bfloat16)
        pay = (in_slot[:, :, None] * pay[:, None, :]).reshape(
            row_block, n_slots * lanes).astype(dot_dtype)
        outs = []
        for a, z in blocks:
            onehot = (b[:, a:z, None] == bin_ids[None, None, :]).astype(
                dot_dtype)
            outs.append(jnp.einsum("rc,rfb->cfb", pay, onehot,
                                   preferred_element_type=jnp.float32))
        return acc + jnp.concatenate(outs, axis=1)

    acc = jax.lax.fori_loop(
        0, n // row_block, body,
        jnp.zeros((n_slots * lanes, f, n_bins), jnp.float32))
    acc = acc.reshape(n_slots, lanes, f, n_bins)
    return jnp.stack([acc[:, :terms].sum(axis=1),
                      acc[:, terms:2 * terms].sum(axis=1),
                      acc[:, 2 * terms]], axis=1)


def _best_split(hist, g_sum, h_sum, count, *, min_data, min_hess, l2,
                min_gain):
    """Best (gain, feature, bin, left sums) of one leaf from its histogram
    ``[3, F, B]``; gain is -inf where no threshold is allowed."""
    _, f, b = hist.shape
    cum = jnp.cumsum(hist, axis=2)
    lg, lh, lc = cum[0], cum[1], cum[2]
    rg, rh, rc = g_sum - lg, h_sum - lh, count - lc
    ok = ((jnp.arange(b)[None, :] < b - 1)
          & (lc >= min_data) & (rc >= min_data)
          & (lh >= min_hess) & (rh >= min_hess))

    def leaf_gain(g, h):
        return g * g / (h + l2 + KEPSILON)

    gain = leaf_gain(lg, lh) + leaf_gain(rg, rh) - leaf_gain(g_sum, h_sum)
    gain = jnp.where(ok & (gain > min_gain), gain, -jnp.inf).reshape(-1)
    best = jnp.argmax(gain)
    return (gain[best], (best // b).astype(jnp.int32),
            (best % b).astype(jnp.int32),
            lg.reshape(-1)[best], lh.reshape(-1)[best], lc.reshape(-1)[best])


@functools.partial(jax.jit, static_argnames=("min_data", "min_hess", "l2",
                                             "min_gain"))
def _search_root(hist0, *, min_data, min_hess, l2, min_gain):
    tot = hist0[:, 0, :].sum(axis=1)
    return tot, _best_split(hist0, tot[0], tot[1], tot[2], min_data=min_data,
                            min_hess=min_hess, l2=l2, min_gain=min_gain)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("min_data", "min_hess", "l2", "min_gain"))
def _settle_round(hists, small, left_leaf, right_leaf, small_is_left,
                  child_stats, *, min_data, min_hess, l2, min_gain):
    """Store both children's histograms of every split of the round (the
    left child keeps the parent's slot) and search them.  ``left_leaf`` is
    out of range for an unused slot, which the scatter drops."""
    n_leaves = hists.shape[0]
    parent = hists[jnp.clip(left_leaf, 0, n_leaves - 1)]
    big = parent - small
    sel = small_is_left[:, None, None, None]
    left = jnp.where(sel, small, big)
    right = jnp.where(sel, big, small)
    hists = hists.at[left_leaf].set(left, mode="drop")
    hists = hists.at[right_leaf].set(right, mode="drop")
    both = jnp.concatenate([left, right], axis=0)
    search = jax.vmap(functools.partial(
        _best_split, min_data=min_data, min_hess=min_hess, l2=l2,
        min_gain=min_gain))
    return hists, search(both, child_stats[:, 0], child_stats[:, 1],
                         child_stats[:, 2])


@jax.jit
def _partition(leaf_id, bins, leaf, feature, threshold, right):
    """Send the rows of every split leaf that lie right of its threshold to
    the new leaf.  An unused slot has ``leaf == -2`` and matches no row."""
    out = leaf_id
    for r in range(leaf.shape[0]):
        col = jnp.take(bins, feature[r], axis=1).astype(jnp.int32)
        out = jnp.where((leaf_id == leaf[r]) & (col > threshold[r]),
                        right[r], out)
    return out


@jax.jit
def _gradients(score, label):
    p = jax.nn.sigmoid(score)
    return p - label, p * (1.0 - p)


@jax.jit
def _slots_of(leaf_id, slot_of_leaf):
    return jnp.where(leaf_id >= 0, slot_of_leaf[jnp.maximum(leaf_id, 0)], -1)


@jax.jit
def _add_leaf_values(score, leaf_id, value):
    return score + value[jnp.maximum(leaf_id, 0)]


def train(bins: np.ndarray, label: np.ndarray, params: dict, *, n_trees: int,
          leaf_tile: int, row_block: int = 2048, payload_terms: int = 3,
          weight=None) -> dict:
    """Boost ``n_trees`` trees; returns the score after 0..n_trees trees
    (host float32), and per tree the sum of split gains, the root split's
    gain, the leaf count and each leaf's row count."""
    n, f = bins.shape
    n_bins = int(params["max_bin"])
    num_leaves = int(params["num_leaves"])
    lr = float(params["learning_rate"])
    kw = dict(min_data=float(params.get("min_data_in_leaf", 20)),
              min_hess=float(params.get("min_sum_hessian_in_leaf", 1e-3)),
              l2=float(params.get("lambda_l2", 0.0)),
              min_gain=float(params.get("min_gain_to_split", 0.0)))
    tile = int(leaf_tile)

    n_pad = -(-n // row_block) * row_block
    bins_d = jnp.pad(jnp.asarray(bins, jnp.uint8), ((0, n_pad - n), (0, 0)))
    label_d = jnp.pad(jnp.asarray(label, jnp.float32), (0, n_pad - n))
    root_id = jnp.where(jnp.arange(n_pad) < n, 0, -1).astype(jnp.int32)

    weight_d = None if weight is None else jnp.pad(
        jnp.asarray(weight, jnp.float32), (0, n_pad - n))
    mean = float(np.average(np.asarray(label, np.float64), weights=weight))
    score = jnp.full((n_pad,), np.log(mean / (1.0 - mean)), jnp.float32)
    scores = [np.asarray(score[:n])]
    trees = []
    hist = functools.partial(_hist_pass, n_slots=tile, n_bins=n_bins,
                             row_block=row_block, terms=payload_terms,
                             dot_dtype=(jnp.bfloat16
                                        if jax.default_backend() == "tpu"
                                        else jnp.float32))

    for _ in range(n_trees):
        g, h = _gradients(score, label_d)
        if weight_d is not None:
            g, h = g * weight_d, h * weight_d
        leaf_id = root_id
        hists = jnp.zeros((num_leaves, 3, f, n_bins), jnp.float32)
        hist0 = hist(bins_d, root_id, g, h)[0]
        hists = hists.at[0].set(hist0)
        tot, best0 = _search_root(hist0, **kw)
        tot = np.asarray(tot, np.float64)
        # host-side leaf table: sums, and each searched leaf's best split
        sums = {0: tuple(tot)}
        best = {0: tuple(np.asarray(x).item() for x in best0)}
        n_leaves, gain_sum, root_gain = 1, 0.0, best[0][0]
        splits = []  # (leaf, feature, bin, gain, left count, left hessian)

        while n_leaves < num_leaves:
            can = sorted((l for l in best if best[l][0] > -np.inf),
                         key=lambda l: (-best[l][0], l))
            acc = can[:min(tile, num_leaves - n_leaves)]
            if not acc:
                break
            leaf = np.full(tile, -2, np.int32)
            feat = np.zeros(tile, np.int32)
            thr = np.zeros(tile, np.int32)
            right = np.full(tile, num_leaves, np.int32)
            left_slot = np.full(tile, num_leaves, np.int32)
            small_is_left = np.zeros(tile, bool)
            slot_of_leaf = np.full(num_leaves, -1, np.int32)
            stats = np.zeros((2 * tile, 3), np.float32)
            for r, l in enumerate(acc):
                gain, ft, tb, lg, lh, lc = best.pop(l)
                pg, ph, pc = sums[l]
                new = n_leaves + r
                leaf[r], feat[r], thr[r], right[r] = l, ft, tb, new
                left_slot[r] = l
                sums[l] = (lg, lh, lc)
                sums[new] = (pg - lg, ph - lh, pc - lc)
                small_is_left[r] = lc <= pc - lc
                slot_of_leaf[l if small_is_left[r] else new] = r
                stats[r] = sums[l]
                stats[tile + r] = sums[new]
                gain_sum += gain
                splits.append((l, int(ft), int(tb), gain, lc, lh))
            leaf_id = _partition(leaf_id, bins_d, leaf, feat, thr, right)
            small = hist(bins_d, _slots_of(leaf_id, jnp.asarray(slot_of_leaf)),
                         g, h)
            hists, found = _settle_round(
                hists, small, jnp.asarray(left_slot), jnp.asarray(right),
                jnp.asarray(small_is_left), jnp.asarray(stats), **kw)
            found = [np.asarray(x) for x in found]
            for r, l in enumerate(acc):
                best[l] = tuple(x[r].item() for x in found)
                best[n_leaves + r] = tuple(x[tile + r].item() for x in found)
            n_leaves += len(acc)

        value = np.zeros(num_leaves, np.float32)
        counts = np.zeros(n_leaves, np.int64)
        for l in range(n_leaves):
            sg, sh, sc = sums[l]
            value[l] = -sg / (sh + kw["l2"] + KEPSILON) * lr
            counts[l] = int(round(sc))
        score = _add_leaf_values(score, leaf_id, jnp.asarray(value))
        scores.append(np.asarray(score[:n]))
        trees.append({"gain_sum": gain_sum, "root_gain": root_gain,
                      "num_leaves": n_leaves, "leaf_count": counts,
                      "root_hess": float(tot[1]), "root_sums": tuple(tot),
                      "splits": splits})
        del hists
    return {"scores": scores, "trees": trees}
