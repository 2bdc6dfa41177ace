"""Jitted leaf-wise tree growth.

TPU-native re-design of the reference's serial tree learner
(reference: src/treelearner/serial_tree_learner.cpp ->
SerialTreeLearner::{Train,BeforeTrain,FindBestSplits,Split} and its CUDA
sibling src/treelearner/cuda/cuda_single_gpu_tree_learner.cpp).

Design differences from the reference, chosen for XLA (SURVEY.md §10.1):
  * No per-leaf row-index lists (DataPartition).  Instead a per-row `leaf_id`
    vector is maintained; partitioning a leaf is a pure elementwise update and
    histogramming a leaf is a masked scatter.  Fixed shapes throughout.
  * The whole tree is grown inside ONE `lax.fori_loop` with `num_leaves - 1`
    trip count; exhausted trees turn remaining iterations into no-ops via
    `lax.cond` (the reference `break`s out of its leaf loop).
  * Histogram subtraction trick preserved: only the smaller child is
    histogrammed; the sibling is parent - child.
  * Under `shard_map` the same code runs data-parallel: histograms and leaf
    aggregates are `psum`'d over the mesh axis, after which every shard
    computes identical splits (reference analogue:
    src/treelearner/data_parallel_tree_learner.cpp, with psum standing in for
    ReduceScatter + SyncUpGlobalBestSplit).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from .histogram import histogram
from .split import (
    BestSplit, SplitParams, find_best_split, forced_split_candidate,
    gain_plane, leaf_output, leaf_output_smoothed, KMIN_SCORE,
)


class TreeArrays(NamedTuple):
    """Structure-of-arrays tree (reference: class Tree in include/LightGBM/tree.h).

    Internal node slots: 0..num_leaves-2 (slot t = t-th split).  Children
    encode leaves as ~leaf_index (negative), matching the reference's
    left_child_/right_child_ convention.
    """

    num_leaves: jnp.ndarray  # i32 scalar — actual leaf count
    split_feature: jnp.ndarray  # (L-1,) i32
    threshold_bin: jnp.ndarray  # (L-1,) i32
    default_left: jnp.ndarray  # (L-1,) bool
    split_gain: jnp.ndarray  # (L-1,) f32
    left_child: jnp.ndarray  # (L-1,) i32
    right_child: jnp.ndarray  # (L-1,) i32
    internal_value: jnp.ndarray  # (L-1,) f32 — leaf output the node would have
    internal_weight: jnp.ndarray  # (L-1,) f32 — sum hessian
    internal_count: jnp.ndarray  # (L-1,) f32
    leaf_value: jnp.ndarray  # (L,) f32
    leaf_weight: jnp.ndarray  # (L,) f32 — sum hessian
    leaf_count: jnp.ndarray  # (L,) f32
    leaf_sum_g: jnp.ndarray  # (L,) f32 (for quantized/renew paths)
    leaf_depth: jnp.ndarray  # (L,) i32
    is_cat: jnp.ndarray  # (L-1,) bool — node is a categorical (bitset) split
    cat_mask: jnp.ndarray  # (L-1, B) bool — bins going left at cat nodes
    path_features: Optional[jnp.ndarray] = None  # (L, F) bool (linear trees)
    hist_passes: Optional[jnp.ndarray] = None  # i32 scalar — full passes over
    # the rows this tree took (the rounds grower counts them; others: None)
    hist_blocks: Optional[jnp.ndarray] = None  # i32 scalar — sub-blocks of
    # hist_pallas.SUB_BLOCK rows the Pallas kernel multiplied in those passes
    hist_blocks_packed: Optional[jnp.ndarray] = None  # i32 scalar — those of
    # them that lay in a packed tile: what the kernel's move served


class GrowState(NamedTuple):
    leaf_id: jnp.ndarray  # (N,) i32
    hist: jnp.ndarray  # (L, 3, F, B) — channel-first (see ops/histogram.py)
    best: BestSplit  # vectorized over L
    leaf_sum_g: jnp.ndarray  # (L,)
    leaf_sum_h: jnp.ndarray
    leaf_count: jnp.ndarray
    leaf_depth: jnp.ndarray  # (L,) i32
    leaf_parent: jnp.ndarray  # (L,) i32 node the leaf hangs from (-1 for root)
    leaf_side: jnp.ndarray  # (L,) i32 0=left 1=right
    num_leaves_cur: jnp.ndarray  # i32
    leaf_out_lo: jnp.ndarray  # (L,) f32 — monotone output lower bounds
    leaf_out_hi: jnp.ndarray  # (L,) f32 — monotone output upper bounds
    leaf_out: jnp.ndarray  # (L,) f32 — each leaf's (smoothed/clipped) output
    cegb_used: jnp.ndarray  # (F,) bool — features already split on in this tree
    used_features: jnp.ndarray  # (L, F) bool or () — path features (interaction constraints)
    tree: TreeArrays
    forced_active: jnp.ndarray = True  # () bool — forced prefix still applying
    # (reference: ForceSplits stops at the FIRST invalid forced split; the
    # precomputed schedule's leaf ids assume every prior entry applied, so a
    # rejected entry must disable all later ones, not just itself)
    anc: jnp.ndarray = False  # (L, L-1) bool ancestor masks, or () placeholder
    aside: jnp.ndarray = False  # (L, L-1) bool — leaf on the RIGHT side of m
    # (maintained only for monotone_method="intermediate")
    node_mono: jnp.ndarray = False  # (L-1,) i32 monotone dir per node (0 at
    # cat nodes) — feature-parallel shards the constraint vector, so the
    # per-node direction must be recorded at split time (intermediate only)
    lazy_used: jnp.ndarray = False  # (N, F) bool — rows charged per feature
    lazy_counts: jnp.ndarray = False  # (L, F) f32 — per-leaf uncharged rows
    # (maintained only for CEGB cegb_penalty_feature_lazy; reference:
    # CostEfficientGradientBoosting feature_used_in_data bitset)


def _empty_best(num_leaves: int, num_bins: int) -> BestSplit:
    z = jnp.zeros((num_leaves,), dtype=jnp.float32)
    zi = jnp.zeros((num_leaves,), dtype=jnp.int32)
    return BestSplit(
        gain=jnp.full((num_leaves,), KMIN_SCORE, dtype=jnp.float32),
        feature=zi,
        threshold_bin=zi,
        default_left=jnp.zeros((num_leaves,), dtype=bool),
        is_cat=jnp.zeros((num_leaves,), dtype=bool),
        cat_mask=jnp.zeros((num_leaves, num_bins), dtype=bool),
        left_sum_g=z,
        left_sum_h=z,
        left_count=z,
        right_sum_g=z,
        right_sum_h=z,
        right_count=z,
    )


def _set_best(best: BestSplit, i: jnp.ndarray, s: BestSplit) -> BestSplit:
    return BestSplit(*[arr.at[i].set(v) for arr, v in zip(best, s)])


def _intermediate_bounds(anc, aside, node_mono, leaf_out, n_live, L):
    """Monotone 'intermediate' bounds (reference: monotone_constraints.hpp ->
    IntermediateLeafConstraints): instead of compounding midpoint fences
    (basic), each leaf is bounded by the ACTUAL output extremes of the
    opposite subtree at every monotone ancestor — sound under sequential
    splits because a new leaf respects all existing opposite-side leaves and
    future opposite-side leaves respect it in turn.

    anc/aside: (L, L-1) ancestor masks (aside = leaf on the right side).
    node_mono: (L-1,) per-node monotone direction, 0 at categorical nodes —
    recorded at split time because in feature-parallel mode the constraint
    vector is feature-SHARDED while tree.split_feature holds global ids
    (indexing it there would silently misindex).  Returns (lo, hi) (L,)."""
    live = (jnp.arange(L, dtype=jnp.int32) < n_live)[:, None]  # (L, 1)
    left_m = anc & ~aside & live  # (L, M) leaf ℓ lives in m's left subtree
    right_m = anc & aside & live
    o = leaf_out[:, None]
    ninf, pinf = -jnp.inf, jnp.inf
    l_max = jnp.max(jnp.where(left_m, o, ninf), axis=0)  # (M,)
    l_min = jnp.min(jnp.where(left_m, o, pinf), axis=0)
    r_max = jnp.max(jnp.where(right_m, o, ninf), axis=0)
    r_min = jnp.min(jnp.where(right_m, o, pinf), axis=0)
    d = node_mono  # (M,)
    # d=+1 (non-decreasing): right-side leaves >= max(left outputs),
    #                        left-side leaves <= min(right outputs)
    # d=-1 mirrored
    lo_c = jnp.maximum(
        jnp.where(right_m & (d > 0)[None, :], l_max[None, :], ninf),
        jnp.where(left_m & (d < 0)[None, :], r_max[None, :], ninf),
    )
    hi_c = jnp.minimum(
        jnp.where(left_m & (d > 0)[None, :], r_min[None, :], pinf),
        jnp.where(right_m & (d < 0)[None, :], l_min[None, :], pinf),
    )
    return jnp.max(lo_c, axis=1), jnp.min(hi_c, axis=1)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_leaves",
        "num_bins",
        "max_depth",
        "params",
        "hist_strategy",
        "axis_name",
        "parallel_mode",
        "top_k",
        "track_path",
        "n_forced",
        "monotone_method",
    ),
)
def grow_tree(
    bins: jnp.ndarray,  # (N, F) int — binned features (device-resident)
    grad: jnp.ndarray,  # (N,) f32
    hess: jnp.ndarray,  # (N,) f32
    row_mask: jnp.ndarray,  # (N,) bool — bagging/GOSS row selection
    sample_weight: jnp.ndarray,  # (N,) f32 — GOSS amplification (1.0 if unused)
    feature_mask: jnp.ndarray,  # (F,) bool — feature_fraction selection
    num_bins_per_feature: jnp.ndarray,  # (F,) i32
    missing_bin_per_feature: jnp.ndarray,  # (F,) i32 (-1 = no missing bin)
    categorical_mask: jnp.ndarray = None,  # (F,) bool — categorical features
    monotone_constraints: jnp.ndarray = None,  # (F,) i32 in {-1,0,1}
    interaction_sets: jnp.ndarray = None,  # (S, F) bool — allowed feature sets
    rng_key: jnp.ndarray = None,  # base PRNG key (extra_trees / bynode)
    cegb_feature_penalty: jnp.ndarray = None,  # (F,) pre-scaled coupled penalties
    cegb_lazy_penalty: jnp.ndarray = None,  # (F,) pre-scaled lazy penalties
    cegb_lazy_used: jnp.ndarray = None,  # (N, F) bool — rows already charged
    forced_leaf: jnp.ndarray = None,  # (K,) i32 — forced-split schedule
    forced_feature: jnp.ndarray = None,  # (K,) i32   (reference: ForceSplits
    forced_bin: jnp.ndarray = None,  # (K,) i32        from forcedsplits JSON)
    feature_contri: jnp.ndarray = None,  # (F,) split-gain multipliers
    *,
    num_leaves: int,
    num_bins: int,
    max_depth: int = -1,
    params: SplitParams = SplitParams(),
    hist_strategy: str = "auto",
    axis_name: Optional[str] = None,
    parallel_mode: str = "data",  # with axis_name: data | feature | voting
    top_k: int = 20,  # voting mode: per-shard feature votes (reference: top_k)
    track_path: bool = False,  # maintain per-leaf path features (linear trees)
    n_forced: int = 0,
    monotone_method: str = "basic",  # basic | intermediate (serial/data modes)
) -> tuple[TreeArrays, jnp.ndarray]:
    """Grow one tree; returns (tree, final leaf_id per row).

    `leaf_id` is maintained for ALL rows (in-bag and out-of-bag), so the score
    update after growth is simply `leaf_value[leaf_id]` — the partition-based
    fast path of the reference's ScoreUpdater::AddScore.
    """
    n, f = bins.shape
    bins = bins.astype(jnp.int32)
    grad = grad.astype(jnp.float32) * sample_weight
    hess = hess.astype(jnp.float32) * sample_weight
    L = num_leaves
    mode = parallel_mode if axis_name is not None else "serial"
    if mode in ("feature", "voting"):  # a shard or an elected subset of the
        # columns is searched: the stated indices are not its columns'
        params = params._replace(cat_features=None)
    # CEGB lazy per-(row, feature) fetch charges (reference:
    # cost_effective_gradient_boosting.hpp — DeltaGain subtracts
    # penalty_feature_lazy[f] * #uncharged rows in the leaf; rows charge
    # when a split applies).  Serial-mode only: the (N, F) charge state is
    # row-global and the distributed wrappers do not thread it.
    use_lazy = (cegb_lazy_penalty is not None and cegb_lazy_used is not None
                and mode == "serial")
    use_intermediate = (
        monotone_method == "intermediate"
        and monotone_constraints is not None
        # serial: sequential splits, the textbook case.  data: every shard
        # holds identical replicated leaf state (hists are psummed before
        # split search).  feature/voting (round 5): the re-evaluate-all
        # path vmaps best_for over leaves, batching its collectives
        # (pmax/psum merges and the voting election) across the leaf dim —
        # every shard still computes identical bounds because leaf outputs
        # and node directions are replicated (node_mono records the split
        # feature's direction at split time, since the constraint vector
        # itself is feature-sharded in feature mode).
        and mode in ("serial", "data", "feature", "voting")
    )

    def psum(x):
        return jax.lax.psum(x, axis_name) if axis_name is not None else x

    def leaf_hist(mask):
        h = histogram(bins, grad, hess, mask, num_bins, strategy=hist_strategy)
        # data-parallel: rows sharded, merge now (reference ReduceScatter).
        # feature-parallel: each shard sees ALL rows for ITS features — local
        # hist is already complete.  voting: keep local, merge per-vote later.
        return psum(h) if mode == "data" else h

    def allowed_from_used(used):
        """Features allowed at a leaf = union of interaction sets containing
        ALL features already used on the leaf's path (reference:
        col_sampler.hpp interaction-constraint filtering)."""
        ok_s = ~jnp.any(used[None, :] & ~interaction_sets, axis=1)  # (S,)
        if mode == "feature":
            # a set qualifies only if no shard's local feature block used a
            # feature outside it (used/sets are column-sharded)
            ok_s = jax.lax.pmin(ok_s.astype(jnp.int32), axis_name) > 0
        return jnp.any(interaction_sets & ok_s[:, None], axis=0)  # (F,)

    def best_for(hist_leaf, sum_g, sum_h, count, depth, out_lo=None, out_hi=None,
                 used=None, node_id=None, parent_out=None, cegb_used=None,
                 lazy_counts=None):
        fmask = feature_mask
        if interaction_sets is not None and used is not None:
            fmask = fmask & allowed_from_used(used) if fmask is not None else allowed_from_used(used)
        key = None
        if rng_key is not None and node_id is not None:
            key = jax.random.fold_in(rng_key, node_id)
        cegb_pen = None
        if cegb_feature_penalty is not None:
            cegb_pen = jnp.where(cegb_used, 0.0, cegb_feature_penalty)
        if lazy_counts is not None:
            lz = cegb_lazy_penalty * lazy_counts
            cegb_pen = lz if cegb_pen is None else cegb_pen + lz
        kw = dict(
            feature_mask=fmask,
            categorical_mask=categorical_mask,
            monotone_constraints=monotone_constraints,
            out_lo=out_lo,
            out_hi=out_hi,
            rng_key=key,
            depth=depth.astype(jnp.float32) if hasattr(depth, 'astype') else jnp.float32(depth),
            parent_output=parent_out,
            cegb_feature_penalty=cegb_pen,
            feature_contri=feature_contri,
        )
        if mode == "voting":
            # PV-Tree (reference: voting_parallel_tree_learner.cpp): each
            # shard votes its top_k features by LOCAL gain; the global tally
            # elects ~2*top_k features whose histograms alone are merged.
            loc = jnp.sum(hist_leaf[:, 0, :], axis=1)  # local leaf totals (3,)
            local_gain, _ = gain_plane(
                hist_leaf, loc[0], loc[1], loc[2],
                num_bins_per_feature, missing_bin_per_feature, params, **kw,
            )
            per_f = jnp.max(local_gain, axis=1)  # (F,)
            kth = jax.lax.top_k(per_f, min(top_k, f))[0][-1]
            vote = (per_f >= kth) & (per_f > KMIN_SCORE / 2)
            tally = jax.lax.psum(vote.astype(jnp.int32), axis_name)
            # deterministic top-2k election, ties to the lower feature index
            score = tally.astype(jnp.int32) * (f + 1) - jnp.arange(f, dtype=jnp.int32)
            n_elect = min(2 * top_k, f)
            # DCN-frugal merge (the point of PV-Tree, reference:
            # VotingParallelTreeLearner: only elected features' histograms
            # cross the wire): gather the top-2k slice and psum THAT —
            # n_elect/F of the full-width bytes.  `score` is replicated
            # (built from the psum'd tally), so el_idx is identical on every
            # shard and the collective stays congruent.
            _, el_idx = jax.lax.top_k(score, n_elect)
            sub_hist = jax.lax.psum(hist_leaf[:, el_idx], axis_name)  # (3, E, B)

            def sub(arr):
                return None if arr is None else arr[el_idx]

            kw_sub = dict(kw)
            kw_sub["feature_mask"] = sub(kw["feature_mask"])
            kw_sub["categorical_mask"] = sub(kw_sub.get("categorical_mask"))
            kw_sub["monotone_constraints"] = sub(kw_sub.get("monotone_constraints"))
            if kw_sub.get("cegb_feature_penalty") is not None:
                kw_sub["cegb_feature_penalty"] = kw_sub["cegb_feature_penalty"][el_idx]
            if kw_sub.get("feature_contri") is not None:
                kw_sub["feature_contri"] = kw_sub["feature_contri"][el_idx]
            s = find_best_split(
                sub_hist, sum_g, sum_h, count,
                num_bins_per_feature[el_idx], missing_bin_per_feature[el_idx],
                params, **kw_sub,
            )
            s = s._replace(feature=el_idx[s.feature])
        else:
            s = find_best_split(
                hist_leaf, sum_g, sum_h, count,
                num_bins_per_feature, missing_bin_per_feature, params, **kw,
            )
        if mode == "feature":
            # feature-parallel merge (reference:
            # FeatureParallelTreeLearner::SyncUpGlobalBestSplit — Allreduce
            # with a max-gain reducer over serialized SplitInfo): winner rank
            # = lowest shard achieving the max gain; its SplitInfo (with the
            # feature index globalized) is broadcast by psum-masking.
            ax = jax.lax.axis_index(axis_name)
            nshards = jax.lax.psum(1, axis_name)
            gmax = jax.lax.pmax(s.gain, axis_name)
            cand = jnp.where(s.gain >= gmax, ax, nshards)
            wrank = jax.lax.pmin(cand, axis_name)
            sel = ax == wrank

            def bc(x):
                masked = jnp.where(sel, x, jnp.zeros_like(x))
                out = jax.lax.psum(masked.astype(jnp.float32) if x.dtype == bool else masked, axis_name)
                return (out > 0) if x.dtype == bool else out

            s = BestSplit(
                gain=gmax,
                feature=bc(s.feature + ax * f),
                threshold_bin=bc(s.threshold_bin),
                default_left=bc(s.default_left),
                is_cat=bc(s.is_cat),
                cat_mask=bc(s.cat_mask),
                left_sum_g=bc(s.left_sum_g),
                left_sum_h=bc(s.left_sum_h),
                left_count=bc(s.left_count),
                right_sum_g=bc(s.right_sum_g),
                right_sum_h=bc(s.right_sum_h),
                right_count=bc(s.right_count),
            )
        # depth cap (reference: max_depth check in BeforeFindBestSplit)
        if max_depth > 0:
            s = s._replace(gain=jnp.where(depth >= max_depth, KMIN_SCORE, s.gain))
        return s

    # --- leaf 0: all in-bag rows ---
    mask0 = row_mask.astype(jnp.float32)
    hist0 = leaf_hist(mask0)
    sum0 = jnp.sum(hist0[:, 0, :], axis=1)  # totals from feature 0's hist: (3,)
    if mode == "voting":
        sum0 = psum(sum0)  # local hists in voting mode; leaf stats are global
    g0, h0, c0 = sum0[0], sum0[1], sum0[2]

    leaf_out0 = leaf_output(g0, h0, params)
    cegb_used0 = jnp.zeros((f,), bool)
    if use_lazy:
        lazy_used0 = cegb_lazy_used
        lazy_counts0 = jnp.einsum(
            "n,nf->f", mask0, (~lazy_used0).astype(jnp.float32))

    tree0 = TreeArrays(
        num_leaves=jnp.asarray(1, jnp.int32),
        split_feature=jnp.zeros((L - 1,), jnp.int32),
        threshold_bin=jnp.zeros((L - 1,), jnp.int32),
        default_left=jnp.zeros((L - 1,), bool),
        split_gain=jnp.zeros((L - 1,), jnp.float32),
        left_child=jnp.zeros((L - 1,), jnp.int32),
        right_child=jnp.zeros((L - 1,), jnp.int32),
        internal_value=jnp.zeros((L - 1,), jnp.float32),
        internal_weight=jnp.zeros((L - 1,), jnp.float32),
        internal_count=jnp.zeros((L - 1,), jnp.float32),
        leaf_value=jnp.zeros((L,), jnp.float32),
        leaf_weight=jnp.zeros((L,), jnp.float32),
        leaf_count=jnp.zeros((L,), jnp.float32),
        leaf_sum_g=jnp.zeros((L,), jnp.float32),
        leaf_depth=jnp.zeros((L,), jnp.int32),
        is_cat=jnp.zeros((L - 1,), bool),
        cat_mask=jnp.zeros((L - 1, num_bins), bool),
    )

    state = GrowState(
        leaf_id=jnp.zeros((n,), jnp.int32),
        hist=jnp.zeros((L, 3, f, num_bins), jnp.float32).at[0].set(hist0),
        best=_set_best(
            _empty_best(L, num_bins), jnp.asarray(0),
            best_for(
                hist0, g0, h0, c0, jnp.asarray(0),
                out_lo=jnp.float32(-jnp.inf), out_hi=jnp.float32(jnp.inf),
                used=(jnp.zeros((f,), bool) if interaction_sets is not None else None),
                node_id=jnp.asarray(0, jnp.int32),
                parent_out=leaf_out0, cegb_used=cegb_used0,
                lazy_counts=(lazy_counts0 if use_lazy else None),
            ),
        ),
        leaf_sum_g=jnp.zeros((L,), jnp.float32).at[0].set(g0),
        leaf_sum_h=jnp.zeros((L,), jnp.float32).at[0].set(h0),
        leaf_count=jnp.zeros((L,), jnp.float32).at[0].set(c0),
        leaf_depth=jnp.zeros((L,), jnp.int32),
        leaf_parent=jnp.full((L,), -1, jnp.int32),
        leaf_side=jnp.zeros((L,), jnp.int32),
        num_leaves_cur=jnp.asarray(1, jnp.int32),
        leaf_out_lo=jnp.full((L,), -jnp.inf, jnp.float32),
        leaf_out_hi=jnp.full((L,), jnp.inf, jnp.float32),
        leaf_out=jnp.zeros((L,), jnp.float32).at[0].set(leaf_out0),
        cegb_used=cegb_used0,
        used_features=(
            jnp.zeros((L, f), bool)
            if (interaction_sets is not None or track_path)
            else jnp.zeros((), bool)
        ),
        tree=tree0,
        forced_active=jnp.asarray(True),
        anc=(jnp.zeros((L, L - 1), bool) if use_intermediate
             else jnp.zeros((), bool)),
        aside=(jnp.zeros((L, L - 1), bool) if use_intermediate
               else jnp.zeros((), bool)),
        node_mono=(jnp.zeros((L - 1,), jnp.int32) if use_intermediate
                   else jnp.zeros((), bool)),
        lazy_used=(lazy_used0 if use_lazy else jnp.zeros((), bool)),
        lazy_counts=(jnp.zeros((L, f), jnp.float32).at[0].set(lazy_counts0)
                     if use_lazy else jnp.zeros((), bool)),
    )

    def _forced_candidate(state: GrowState, i):
        """Materialize the i-th forced split (reference: ForceSplits —
        SerialTreeLearner applies the JSON tree prefix through the standard
        split evaluation, so constraints like min_data still gate it).
        Returns (leaf, BestSplit, valid)."""
        fi = jnp.minimum(i, n_forced - 1)
        fl = jnp.clip(forced_leaf[fi], 0, L - 1)
        s_f = forced_split_candidate(
            state.hist[fl], state.leaf_sum_g[fl], state.leaf_sum_h[fl],
            state.leaf_count[fl], num_bins_per_feature, missing_bin_per_feature,
            params, forced_feature[fi], forced_bin[fi],
            categorical_mask=categorical_mask,
            monotone_constraints=monotone_constraints,
            out_lo=state.leaf_out_lo[fl], out_hi=state.leaf_out_hi[fl],
            depth=state.leaf_depth[fl].astype(jnp.float32),
            parent_output=state.leaf_out[fl],
            feature_contri=feature_contri,
        )
        # valid = the forced leaf exists and the cell is a legal split
        valid = (forced_leaf[fi] < state.num_leaves_cur) & (s_f.gain > KMIN_SCORE / 2)
        if max_depth > 0:
            valid = valid & (state.leaf_depth[fl] < max_depth)
        return fl, s_f, valid

    def do_split(state: GrowState, forced=None) -> GrowState:
        best_leaf = jnp.argmax(state.best.gain).astype(jnp.int32)
        s = jax.tree.map(lambda a: a[best_leaf], state.best)
        if forced is not None:
            use_forced, f_leaf, s_f = forced
            best_leaf = jnp.where(use_forced, f_leaf, best_leaf)
            s = jax.tree.map(
                lambda a, b: jnp.where(use_forced, a, b), s_f, s
            )
        node = state.num_leaves_cur - 1  # next internal node slot
        new_leaf = state.num_leaves_cur  # right child's leaf index

        # --- partition: pure elementwise leaf_id update (reference:
        # DataPartition::Split, but with no data movement) ---
        if mode == "feature":
            # only the shard owning the winning feature can evaluate the
            # decision; rows are replicated, so broadcast go_left by psum
            # (reference: all machines apply the identical split after
            # SyncUpGlobalBestSplit because data is replicated)
            ax = jax.lax.axis_index(axis_name)
            local_f = s.feature - ax * f
            owned = (local_f >= 0) & (local_f < f)
            lf = jnp.clip(local_f, 0, f - 1)
            fcol = bins[:, lf]
            is_missing = fcol == missing_bin_per_feature[lf]
            gl_num = jnp.where(is_missing, s.default_left, fcol <= s.threshold_bin)
            gl = jnp.where(s.is_cat, s.cat_mask[fcol], gl_num) & owned
            go_left = jax.lax.psum(gl.astype(jnp.int32), axis_name) > 0
        else:
            fcol = bins[:, s.feature]
            is_missing = fcol == missing_bin_per_feature[s.feature]
            go_left_num = jnp.where(is_missing, s.default_left, fcol <= s.threshold_bin)
            # categorical: bin in the winning subset -> left (missing/unseen
            # bins never enter the subset: CategoricalDecision -> right)
            go_left = jnp.where(s.is_cat, s.cat_mask[fcol], go_left_num)
        in_leaf = state.leaf_id == best_leaf
        leaf_id = jnp.where(in_leaf & ~go_left, new_leaf, state.leaf_id)

        # --- histogram the smaller child; sibling by subtraction ---
        left_smaller = s.left_count <= s.right_count
        small_leaf = jnp.where(left_smaller, best_leaf, new_leaf)
        mask_small = (leaf_id == small_leaf) & row_mask
        hist_small = leaf_hist(mask_small.astype(jnp.float32))
        parent_hist = state.hist[best_leaf]
        hist_big = parent_hist - hist_small
        hist_left = jnp.where(left_smaller, hist_small, hist_big)
        hist_right = jnp.where(left_smaller, hist_big, hist_small)
        hist = state.hist.at[best_leaf].set(hist_left).at[new_leaf].set(hist_right)

        # --- record the node (reference: Tree::Split) ---
        parent_out = state.leaf_out[best_leaf]
        cegb_used = (
            state.cegb_used.at[s.feature].set(True)
            if cegb_feature_penalty is not None else state.cegb_used
        )
        if use_lazy:
            # charge the split leaf's in-bag rows for the split feature,
            # THEN compute the children's uncharged counts (a child split
            # on the same feature is free)
            charge = in_leaf & row_mask
            lazy_used = state.lazy_used.at[:, s.feature].set(
                state.lazy_used[:, s.feature] | charge)
            m_l = ((leaf_id == best_leaf) & row_mask).astype(jnp.float32)
            counts_l = jnp.einsum(
                "n,nf->f", m_l, (~lazy_used).astype(jnp.float32))
            # rows partition across leaves, so the parent's stored counts are
            # still current at split time; after charging s.feature the
            # children's counts for it are 0, and the right child holds the
            # remainder — one einsum instead of two
            parent_counts = state.lazy_counts[best_leaf].at[s.feature].set(0.0)
            counts_r = jnp.maximum(parent_counts - counts_l, 0.0)
            lazy_counts = (state.lazy_counts.at[best_leaf].set(counts_l)
                           .at[new_leaf].set(counts_r))
        else:
            lazy_used, lazy_counts = state.lazy_used, state.lazy_counts
        old_parent = state.leaf_parent[best_leaf]
        old_side = state.leaf_side[best_leaf]
        t = state.tree
        # re-point the grandparent's child slot from ~best_leaf to this node
        lc = jnp.where(
            (old_parent >= 0) & (old_side == 0),
            t.left_child.at[old_parent].set(node),
            t.left_child,
        )
        rc = jnp.where(
            (old_parent >= 0) & (old_side == 1),
            t.right_child.at[old_parent].set(node),
            t.right_child,
        )
        lc = lc.at[node].set(-best_leaf - 1)
        rc = rc.at[node].set(-new_leaf - 1)
        depth_child = state.leaf_depth[best_leaf] + 1
        tree = t._replace(
            num_leaves=state.num_leaves_cur + 1,
            split_feature=t.split_feature.at[node].set(s.feature),
            threshold_bin=t.threshold_bin.at[node].set(s.threshold_bin),
            default_left=t.default_left.at[node].set(s.default_left),
            split_gain=t.split_gain.at[node].set(s.gain),
            left_child=lc,
            right_child=rc,
            internal_value=t.internal_value.at[node].set(parent_out),
            internal_weight=t.internal_weight.at[node].set(state.leaf_sum_h[best_leaf]),
            internal_count=t.internal_count.at[node].set(state.leaf_count[best_leaf]),
            is_cat=t.is_cat.at[node].set(s.is_cat),
            cat_mask=t.cat_mask.at[node].set(s.cat_mask),
        )

        # --- update leaf aggregates ---
        leaf_sum_g = state.leaf_sum_g.at[best_leaf].set(s.left_sum_g).at[new_leaf].set(s.right_sum_g)
        leaf_sum_h = state.leaf_sum_h.at[best_leaf].set(s.left_sum_h).at[new_leaf].set(s.right_sum_h)
        leaf_count = state.leaf_count.at[best_leaf].set(s.left_count).at[new_leaf].set(s.right_count)
        leaf_depth = state.leaf_depth.at[best_leaf].set(depth_child).at[new_leaf].set(depth_child)
        leaf_parent = state.leaf_parent.at[best_leaf].set(node).at[new_leaf].set(node)
        leaf_side = state.leaf_side.at[best_leaf].set(0).at[new_leaf].set(1)

        # --- monotone bounds for the children (reference:
        # BasicLeafConstraints::SetChildrenConstraints — after a split on a
        # monotone feature the children's outputs are fenced at the midpoint
        # of the two clipped outputs; non-monotone splits inherit bounds) ---
        p_lo = state.leaf_out_lo[best_leaf]
        p_hi = state.leaf_out_hi[best_leaf]
        out_l_c = leaf_output_smoothed(s.left_sum_g, s.left_sum_h, s.left_count,
                                       parent_out, params)
        out_r_c = leaf_output_smoothed(s.right_sum_g, s.right_sum_h, s.right_count,
                                       parent_out, params)
        if monotone_constraints is not None:
            if mode == "feature":
                ax_m = jax.lax.axis_index(axis_name)
                lf_m = s.feature - ax_m * f
                owned_m = (lf_m >= 0) & (lf_m < f)
                mono_c = jax.lax.psum(
                    jnp.where(owned_m, monotone_constraints[jnp.clip(lf_m, 0, f - 1)], 0),
                    axis_name,
                )
            else:
                mono_c = monotone_constraints[s.feature]
            out_l = jnp.clip(out_l_c, p_lo, p_hi)
            out_r = jnp.clip(out_r_c, p_lo, p_hi)
            out_l_c, out_r_c = out_l, out_r
            mid = 0.5 * (out_l + out_r)
            l_hi = jnp.where(mono_c > 0, jnp.minimum(p_hi, mid), p_hi)
            r_lo = jnp.where(mono_c > 0, jnp.maximum(p_lo, mid), p_lo)
            l_lo = jnp.where(mono_c < 0, jnp.maximum(p_lo, mid), p_lo)
            r_hi = jnp.where(mono_c < 0, jnp.minimum(p_hi, mid), p_hi)
        else:
            l_lo, l_hi, r_lo, r_hi = p_lo, p_hi, p_lo, p_hi
        leaf_out_lo = state.leaf_out_lo.at[best_leaf].set(l_lo).at[new_leaf].set(r_lo)
        leaf_out_hi = state.leaf_out_hi.at[best_leaf].set(l_hi).at[new_leaf].set(r_hi)
        leaf_out = state.leaf_out.at[best_leaf].set(out_l_c).at[new_leaf].set(out_r_c)

        if use_intermediate:
            # maintain ancestor masks and recompute EVERY leaf's bounds from
            # the opposite-subtree output extremes (reference:
            # IntermediateLeafConstraints — looser than compounded midpoints)
            anc_child = state.anc[best_leaf].at[node].set(True)
            aside_l = state.aside[best_leaf]
            aside_r = aside_l.at[node].set(True)
            anc = state.anc.at[best_leaf].set(anc_child).at[new_leaf].set(anc_child)
            aside = state.aside.at[best_leaf].set(aside_l).at[new_leaf].set(aside_r)
            # record this node's monotone direction (mono_c was computed
            # above, psum-broadcast from the owner shard in feature mode)
            node_mono = state.node_mono.at[node].set(
                jnp.where(s.is_cat, 0, mono_c))
            leaf_out_lo, leaf_out_hi = _intermediate_bounds(
                anc, aside, node_mono, leaf_out,
                state.num_leaves_cur + 1, L,
            )
        else:
            anc, aside = state.anc, state.aside
            node_mono = state.node_mono

        if interaction_sets is not None or track_path:
            if mode == "feature":
                ax = jax.lax.axis_index(axis_name)
                local_f = s.feature - ax * f
                owned = (local_f >= 0) & (local_f < f)
                marked = state.used_features[best_leaf].at[
                    jnp.clip(local_f, 0, f - 1)
                ].set(True)
                used_child = jnp.where(owned, marked, state.used_features[best_leaf])
            else:
                used_child = state.used_features[best_leaf].at[s.feature].set(True)
            used_features = (
                state.used_features.at[best_leaf].set(used_child).at[new_leaf].set(used_child)
            )
            if interaction_sets is None:
                used_child = None  # path tracking only — not a split filter
        else:
            used_features = state.used_features
            used_child = None

        # --- best splits for the two fresh leaves ---
        if use_intermediate:
            # bounds of OTHER leaves may have moved (their opposite subtree
            # changed), so their cached best splits are stale — re-evaluate
            # every live leaf (reference: IntermediateLeafConstraints'
            # leaves_to_update recompute set; here the vectorized plane makes
            # recompute-all the simpler exact equivalent)
            node_ids_all = jnp.clip(leaf_parent, 0, None) * 2 + leaf_side + 1
            used_all = used_features if interaction_sets is not None else None

            def one(hist_l, g, h, c, dep, lo, hi, nid, pout, u, lzc):
                return best_for(hist_l, g, h, c, dep, out_lo=lo, out_hi=hi,
                                used=u, node_id=nid, parent_out=pout,
                                cegb_used=cegb_used, lazy_counts=lzc)

            in_axes = (0, 0, 0, 0, 0, 0, 0, 0, 0,
                       0 if used_all is not None else None,
                       0 if use_lazy else None)
            bb = jax.vmap(one, in_axes=in_axes)(
                hist, leaf_sum_g, leaf_sum_h, leaf_count, leaf_depth,
                leaf_out_lo, leaf_out_hi, node_ids_all, leaf_out, used_all,
                lazy_counts if use_lazy else None,
            )
            live_l = jnp.arange(L, dtype=jnp.int32) < (state.num_leaves_cur + 1)
            best = bb._replace(gain=jnp.where(live_l, bb.gain, KMIN_SCORE))
        else:
            bl = best_for(hist_left, s.left_sum_g, s.left_sum_h, s.left_count, depth_child,
                          out_lo=l_lo, out_hi=l_hi, used=used_child, node_id=2 * node + 1,
                          parent_out=out_l_c, cegb_used=cegb_used,
                          lazy_counts=(lazy_counts[best_leaf] if use_lazy else None))
            br = best_for(hist_right, s.right_sum_g, s.right_sum_h, s.right_count, depth_child,
                          out_lo=r_lo, out_hi=r_hi, used=used_child, node_id=2 * node + 2,
                          parent_out=out_r_c, cegb_used=cegb_used,
                          lazy_counts=(lazy_counts[new_leaf] if use_lazy else None))
            best = _set_best(_set_best(state.best, best_leaf, bl), new_leaf, br)

        return GrowState(
            leaf_id=leaf_id,
            hist=hist,
            best=best,
            leaf_sum_g=leaf_sum_g,
            leaf_sum_h=leaf_sum_h,
            leaf_count=leaf_count,
            leaf_depth=leaf_depth,
            leaf_parent=leaf_parent,
            leaf_side=leaf_side,
            num_leaves_cur=state.num_leaves_cur + 1,
            leaf_out_lo=leaf_out_lo,
            leaf_out_hi=leaf_out_hi,
            leaf_out=leaf_out,
            cegb_used=cegb_used,
            used_features=used_features,
            tree=tree,
            forced_active=state.forced_active,
            anc=anc,
            aside=aside,
            node_mono=node_mono,
            lazy_used=lazy_used,
            lazy_counts=lazy_counts,
        )

    def body(i, state: GrowState) -> GrowState:
        can_split = jnp.max(state.best.gain) > KMIN_SCORE / 2
        if n_forced > 0:
            f_leaf, s_f, f_valid = _forced_candidate(state, i)
            in_sched = i < n_forced
            use_forced = in_sched & f_valid & state.forced_active
            # first invalid in-schedule entry permanently disables the rest
            state = state._replace(
                forced_active=state.forced_active & (~in_sched | f_valid)
            )
            can_split = can_split | use_forced
            return jax.lax.cond(
                can_split,
                lambda st: do_split(st, forced=(use_forced, f_leaf, s_f)),
                lambda st: st,
                state,
            )
        return jax.lax.cond(can_split, do_split, lambda st: st, state)

    state = jax.lax.fori_loop(0, L - 1, body, state)

    # finalize leaf values (reference: leaf outputs are computed during growth;
    # equivalent here since sums are exact)
    if params.path_smooth > 0 or use_intermediate:
        # smoothed / monotone-clipped AT CREATION.  With intermediate bounds
        # this is required for correctness, not just convenience: bounds keep
        # evolving after a leaf is created, and re-clipping raw outputs to the
        # FINAL bounds can cross a monotone split (creation-time clips always
        # satisfy the pairwise invariant).
        leaf_value = state.leaf_out
    else:
        leaf_value = leaf_output(state.leaf_sum_g, state.leaf_sum_h, params)
        if monotone_constraints is not None:
            leaf_value = jnp.clip(leaf_value, state.leaf_out_lo, state.leaf_out_hi)
    active = jnp.arange(L, dtype=jnp.int32) < state.num_leaves_cur
    tree = state.tree._replace(
        num_leaves=state.num_leaves_cur,
        leaf_value=jnp.where(active, leaf_value, 0.0),
        leaf_weight=jnp.where(active, state.leaf_sum_h, 0.0),
        leaf_count=jnp.where(active, state.leaf_count, 0.0),
        leaf_sum_g=jnp.where(active, state.leaf_sum_g, 0.0),
        leaf_depth=state.leaf_depth,
        path_features=(state.used_features if track_path else None),
    )
    if use_lazy:
        # hand the cross-tree charge state back (reference: the
        # feature_used_in_data bitset persists across trees)
        return tree, state.leaf_id, state.lazy_used
    return tree, state.leaf_id
