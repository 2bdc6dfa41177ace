"""Plain reference: LambdaRank over query groups, its trees grown leaf-wise
in rounds as ``leafwise_rounds`` grows them.

Straightforward ``jax.numpy``, float32 throughout, no kernel, nothing
imported from the program and nothing the program made: the bins, labels and
query sizes the benchmark drew from the seed and the training parameters of
the configuration's file.  The tree growing is ``leafwise_rounds``' own
functions (histogram pass, split search, partition: see that file); what is
here is the objective.

The lambdas (``objective=lambdarank``, as the program defines them today).
The score starts at 0.  In a query of ``n`` rows the rows are ranked by
score, descending, ties in row order.  With ``r`` the rank, ``T =
lambdarank_truncation_level``, ``D(r) = 1 / log2(r + 2)`` for ``r < T`` and 0
beyond, ``G(l) = label_gain[l]`` (``2^l - 1``), ``M`` the query's inverse
maximum DCG at ``min(n, T)`` (0 where that DCG is 0): every pair of rows with
different labels of which at least one ranks inside ``T`` has, with ``hi``
the row of the larger label and ``lo`` the other,

    delta  = |G(l_hi) - G(l_lo)| * |D(r_hi) - D(r_lo)| * M
    rho    = 1 / (1 + exp(sigmoid * (s_hi - s_lo)))
    lambda = sigmoid * rho * delta
    h      = sigmoid^2 * rho * (1 - rho) * delta

``g_hi -= lambda``, ``g_lo += lambda``, both hessians ``+= h``; with
``lambdarank_norm`` and ``L`` the sum of ``lambda`` over the query's pairs,
each once, the query's gradients and hessians are scaled by
``log2(1 + L) / L`` where ``L > 0``.

**Where this, and the program, part from upstream** (LightGBM 4.x
``rank_objective.hpp``, from memory: no copy is on this machine): upstream
keeps the discount of a row ranked beyond ``T``, divides ``delta`` by
``0.01 + |s_hi - s_lo|`` under ``lambdarank_norm``, and counts each pair
twice in ``L``.  Neither side does any of the three.

The layout is this file's own: one sort of all rows by (query, score
descending, row), a row's rank its position less its query's start, the
queries in blocks of ``query_block``, each block padded to the longest query
with lanes in rank order, and a loop over the ``T`` window rows, each against
every row ranked after it.
"""

from __future__ import annotations

import functools
import importlib.util
import pathlib

import jax
import jax.numpy as jnp
import numpy as np


def _sibling(name: str):
    path = pathlib.Path(__file__).with_name(name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_file_reference_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


rounds = _sibling("leafwise_rounds")


def label_gain(params: dict) -> np.ndarray:
    gains = params.get("label_gain") or [2.0 ** i - 1.0 for i in range(31)]
    return np.asarray(gains, np.float64)


def inverse_max_dcg(label: np.ndarray, group: np.ndarray, gains: np.ndarray,
                    truncation: int) -> np.ndarray:
    """1 / DCG of each query's labels in their best order, cut at
    ``truncation``; 0 for a query with no gain."""
    query = np.repeat(np.arange(len(group)), group)
    start = np.cumsum(group) - group
    best = np.lexsort((-label, query))
    rank = np.arange(len(label)) - np.repeat(start, group)
    top = rank < truncation
    dcg = np.bincount(query[top], gains[label[best][top].astype(np.int64)]
                      / np.log2(rank[top] + 2.0), minlength=len(group))
    return np.where(dcg > 0, 1.0 / np.where(dcg > 0, dcg, 1.0), 0.0)


@functools.partial(jax.jit, static_argnames=("sigmoid", "truncation", "norm"))
def _block_lambdas(s, label, gain, lens, inv_mdcg, *, sigmoid, truncation,
                   norm):
    """One block of queries, a query a row, lanes in rank order, lanes from
    ``lens`` on padding.  (grad, hess) in the same layout."""
    q, w = s.shape
    rank = jnp.arange(w)
    disc = jnp.where(rank < truncation,
                     1.0 / jnp.log2(rank.astype(jnp.float32) + 2.0), 0.0)
    valid = rank[None, :] < lens[:, None]
    def window_row(a, carry):  # the row of rank a against the rows after it
        grad, hess, total = carry

        def of_a(x):
            return jax.lax.dynamic_slice_in_dim(x, a, 1, axis=1)

        pair = valid & (rank[None, :] > a) & (label != of_a(label))
        a_hi = of_a(label) > label
        d = of_a(s) - s
        d = jnp.where(a_hi, d, -d)  # s_hi - s_lo
        delta = (jnp.abs(of_a(gain) - gain)
                 * jnp.abs(disc[a] - disc)[None, :] * inv_mdcg[:, None])
        rho = 1.0 / (1.0 + jnp.exp(sigmoid * d))
        lam = jnp.where(pair, sigmoid * rho * delta, 0.0)
        hes = jnp.where(pair, sigmoid * sigmoid * rho * (1.0 - rho) * delta,
                        0.0)
        to_other = jnp.where(a_hi, lam, -lam)  # the larger label's row loses
        at_a = (rank == a)[None, :]
        grad = grad + to_other - jnp.where(
            at_a, to_other.sum(axis=1, keepdims=True), 0.0)
        hess = hess + hes + jnp.where(
            at_a, hes.sum(axis=1, keepdims=True), 0.0)
        return grad, hess, total + lam.sum(axis=1)

    zeros = jnp.zeros((q, w), jnp.float32)
    grad, hess, total = jax.lax.fori_loop(
        0, min(truncation, w), window_row,
        (zeros, zeros, jnp.zeros((q,), jnp.float32)))
    if norm:
        scale = jnp.where(total > 0, jnp.log2(1.0 + total)
                          / jnp.maximum(total, 1e-20), 1.0)
        grad, hess = grad * scale[:, None], hess * scale[:, None]
    return grad, hess


@jax.jit
def _ranked(score, query):
    """Rows in the order (query, score descending, row)."""
    return jnp.lexsort((jnp.arange(score.shape[0]), -score, query))


class Lambdas:
    """The objective on one set of rows: built once from the labels and the
    query sizes, then called with the score of every round."""

    def __init__(self, label: np.ndarray, group: np.ndarray, params: dict,
                 query_block: int = 512):
        label = np.asarray(label, np.float64)
        group = np.asarray(group, np.int64)
        self.n, self.nq = len(label), len(group)
        self.kw = dict(
            sigmoid=float(params.get("sigmoid", 1.0)),
            truncation=int(params.get("lambdarank_truncation_level", 30)),
            norm=bool(params.get("lambdarank_norm", True)))
        gains = label_gain(params)
        self.block = int(query_block)
        self.width = int(group.max())
        start = np.cumsum(group) - group
        self.query = jnp.asarray(np.repeat(np.arange(self.nq), group),
                                 jnp.int32)
        self.label = jnp.asarray(label, jnp.float32)
        self.gain = jnp.asarray(gains[label.astype(np.int64)], jnp.float32)
        # a ranked row's lane in the blocks laid end to end: its query's row
        # of the padded table, then its rank
        rank = np.arange(self.n) - np.repeat(start, group)
        self.lane = jnp.asarray(
            np.repeat(np.arange(self.nq), group) * self.width + rank)
        pad = -self.nq % self.block
        start, lens = np.pad(start, (0, pad)), np.pad(group, (0, pad))
        inv_mdcg = np.pad(inverse_max_dcg(
            label, group, gains, self.kw["truncation"]), (0, pad))
        # a block of queries: where its lanes lie among the ranked rows (a
        # padded lane reads the last row, masked), its lengths, its 1 / DCGs
        self.blocks = [
            (jnp.asarray(np.minimum(start[lo:lo + self.block, None]
                                    + np.arange(self.width)[None, :],
                                    self.n - 1), jnp.int32),
             jnp.asarray(lens[lo:lo + self.block], jnp.int32),
             jnp.asarray(inv_mdcg[lo:lo + self.block], jnp.float32))
            for lo in range(0, len(lens), self.block)]

    def __call__(self, score):
        """(grad, hess) by row, float32, for the score by row."""
        order = _ranked(score, self.query)
        s, label, gain = score[order], self.label[order], self.gain[order]
        grads, hesss = [], []
        for at, lens, inv_mdcg in self.blocks:
            g, h = _block_lambdas(s[at], label[at], gain[at], lens, inv_mdcg,
                                  **self.kw)
            grads.append(g.reshape(-1))
            hesss.append(h.reshape(-1))
        # by ranked row, then back to the rows' own order
        back = jnp.argsort(order)
        return (jnp.concatenate(grads)[self.lane][back],
                jnp.concatenate(hesss)[self.lane][back])


def train(bins: np.ndarray, label: np.ndarray, params: dict, *, n_trees: int,
          leaf_tile: int, group, row_block: int = 2048,
          payload_terms: int = 3) -> dict:
    """Boost ``n_trees`` trees on the lambdas; returns what
    ``leafwise_rounds.train`` returns: the score after 0..n_trees trees (host
    float32) and per tree the sum of split gains, the root split's gain and
    hessian total, the leaf count and each leaf's row count."""
    n, f = bins.shape
    if int(np.sum(group)) != n:
        raise ValueError("the query sizes do not sum to the rows")
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
    root_id = jnp.where(jnp.arange(n_pad) < n, 0, -1).astype(jnp.int32)
    with jax.default_matmul_precision("highest"):
        lambdas = Lambdas(label, group, params)
        score = jnp.zeros((n_pad,), jnp.float32)
        scores = [np.asarray(score[:n])]
        trees = []
        hist = functools.partial(
            rounds._hist_pass, n_slots=tile, n_bins=n_bins,
            row_block=row_block, terms=payload_terms,
            dot_dtype=(jnp.bfloat16 if jax.default_backend() == "tpu"
                       else jnp.float32))
        for _ in range(n_trees):
            g, h = lambdas(score[:n])
            g = jnp.pad(g, (0, n_pad - n))
            h = jnp.pad(h, (0, n_pad - n))
            score, tree = _grow(bins_d, root_id, g, h, score, hist,
                                num_leaves, tile, lr, kw)
            scores.append(np.asarray(score[:n]))
            trees.append(tree)
    return {"scores": scores, "trees": trees}


def _grow(bins_d, root_id, g, h, score, hist, num_leaves, tile, lr, kw):
    """One tree on the gradients ``g``, ``h``: ``leafwise_rounds.train``'s
    loop body, on that file's functions.  Returns the new score and the
    tree's record."""
    f = bins_d.shape[1]
    leaf_id = root_id
    hist0 = hist(bins_d, root_id, g, h)[0]
    hists = jnp.zeros((num_leaves,) + hist0.shape, jnp.float32)
    hists = hists.at[0].set(hist0)
    tot, best0 = rounds._search_root(hist0, **kw)
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
        leaf_id = rounds._partition(leaf_id, bins_d, leaf, feat, thr, right)
        small = hist(bins_d,
                     rounds._slots_of(leaf_id, jnp.asarray(slot_of_leaf)),
                     g, h)
        hists, found = rounds._settle_round(
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
        value[l] = -sg / (sh + kw["l2"] + rounds.KEPSILON) * lr
        counts[l] = int(round(sc))
    score = rounds._add_leaf_values(score, leaf_id, jnp.asarray(value))
    return score, {"gain_sum": gain_sum, "root_gain": root_gain,
                   "num_leaves": n_leaves, "leaf_count": counts,
                   "root_hess": float(tot[1]), "root_sums": tuple(tot),
                   "splits": splits}
