"""Split-gain search over histograms, as vectorized XLA reductions.

TPU-native replacement for the reference's per-feature threshold scan
(reference: src/treelearner/feature_histogram.hpp ->
FeatureHistogram::FindBestThreshold / FindBestThresholdSequentially and
src/treelearner/cuda/cuda_best_split_finder.cu).  Where the reference scans
bins left->right and right->left per feature in scalar code, here the whole
(F, B) plane is evaluated at once with cumulative sums, both missing-value
default directions evaluated in parallel, and the argmax taken as a single
XLA reduction — the formulation that maps to the VPU/MXU instead of a loop.

Math (must match reference exactly; SURVEY.md §8):
  ThresholdL1(g, l1) = sign(g) * max(0, |g| - l1)
  leaf_output = -ThresholdL1(G, l1) / (H + l2)        [clipped to max_delta_step]
  leaf_gain   = ThresholdL1(G, l1)^2 / (H + l2)       [x0.5 cancels in deltas]
  split_gain  = gain(L) + gain(R) - gain(parent)
constraints: counts >= min_data_in_leaf, hess >= min_sum_hessian_in_leaf,
             split_gain > min_gain_to_split.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.profiling import phase_scope

KEPSILON = 1e-15  # reference: feature_histogram.hpp kEpsilon added to hessians
KMIN_SCORE = -1e30


class SplitParams(NamedTuple):
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    path_smooth: float = 0.0
    # categorical split params (reference: FindBestThresholdCategoricalInner)
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_threshold: int = 32
    max_cat_to_onehot: int = 4
    # the categorical columns' indices: the categorical candidates are
    # computed for these columns alone.  models/gbdt.py states them from the
    # binner at set-up and after every reset (``_make_split_params``).  None
    # means every column the mask marks at run time, computed over all of
    # them: kept only for a grower whose columns are a shard or an elected
    # subset (ops/treegrow.py's feature and voting modes clear the field)
    # and for a direct caller of these functions.  Part of the jit key like
    # every field here, so a data set without categorical columns compiles
    # the program it always did
    cat_features: Optional[tuple] = None
    # node-level sampling (reference: ColSampler bynode / extra_trees)
    feature_fraction_bynode: float = 1.0
    extra_trees: bool = False
    # monotone split gain penalty (reference: config monotone_penalty ->
    # ComputeMonotoneSplitGainPenalty in monotone_constraints.hpp)
    monotone_penalty: float = 0.0
    # CEGB (reference: src/treelearner/cost_effective_gradient_boosting.hpp):
    # split gain is charged cegb_tradeoff * cegb_penalty_split * num_data
    # plus per-feature penalties (passed per-leaf via cegb_feature_penalty)
    cegb_tradeoff: float = 1.0
    cegb_penalty_split: float = 0.0


class BestSplit(NamedTuple):
    """Per-leaf best split description (reference: struct SplitInfo in
    src/treelearner/split_info.hpp — incl. its cat_threshold bitset, here a
    dense (B,) bool mask over bins that go LEFT)."""

    gain: jnp.ndarray  # f32
    feature: jnp.ndarray  # i32
    threshold_bin: jnp.ndarray  # i32 (bin <= threshold_bin -> left)
    default_left: jnp.ndarray  # bool (missing goes left)
    is_cat: jnp.ndarray  # bool — categorical (bitmask) split
    cat_mask: jnp.ndarray  # (B,) bool — bins going left (categorical only)
    left_sum_g: jnp.ndarray
    left_sum_h: jnp.ndarray
    left_count: jnp.ndarray
    right_sum_g: jnp.ndarray
    right_sum_h: jnp.ndarray
    right_count: jnp.ndarray


def threshold_l1(g: jnp.ndarray, l1: jnp.ndarray) -> jnp.ndarray:
    return jnp.sign(g) * jnp.maximum(jnp.abs(g) - l1, 0.0)


def leaf_output(sum_g, sum_h, p: SplitParams):
    """reference: FeatureHistogram::CalculateSplittedLeafOutput."""
    out = -threshold_l1(sum_g, p.lambda_l1) / (sum_h + p.lambda_l2 + KEPSILON)
    if p.max_delta_step > 0:
        out = jnp.clip(out, -p.max_delta_step, p.max_delta_step)
    return out


def leaf_output_smoothed(sum_g, sum_h, count, parent_output, p: SplitParams):
    """Path-smoothed leaf output (reference: CalculateSplittedLeafOutput with
    USE_SMOOTHING: ret = raw * n/(n+smooth) + parent_output * smooth/(n+smooth),
    written there as (n/s)/(n/s + 1) with s = path_smooth)."""
    raw = leaf_output(sum_g, sum_h, p)
    if p.path_smooth <= 0:
        return raw
    alpha = count / (count + p.path_smooth)
    return raw * alpha + parent_output * (1.0 - alpha)


def gain_given_output(sum_g, sum_h, l1, l2, out):
    """reference: GetLeafGainGivenOutput (x-0.5 factor dropped as elsewhere)."""
    tg = threshold_l1(sum_g, l1)
    return -(2.0 * tg * out + (sum_h + l2 + KEPSILON) * out * out)


def monotone_split_gain_penalty(depth, penalization):
    """reference: LeafConstraintsBase::ComputeMonotoneSplitGainPenalty —
    forbids monotone splits on the first floor(penalization) levels and
    continuously penalizes beyond (returns the multiplicative factor)."""
    depth = depth.astype(jnp.float32) if hasattr(depth, "astype") else jnp.float32(depth)
    eps = 1e-10
    full = penalization >= depth + 1.0
    small = penalization <= 1.0
    f_small = 1.0 - penalization / jnp.exp2(depth) + eps
    f_big = 1.0 - jnp.exp2(penalization - 1.0 - depth) + eps
    return jnp.where(full, eps, jnp.where(small, f_small, f_big))


def leaf_gain(sum_g, sum_h, p: SplitParams):
    """reference: GetLeafGain in feature_histogram.hpp (0.5 factor dropped —
    it cancels in gain deltas; reference keeps it, so model-format split_gain
    values are written with the 0.5 applied at serialization time)."""
    tg = threshold_l1(sum_g, p.lambda_l1)
    denom = sum_h + p.lambda_l2 + KEPSILON
    if p.max_delta_step > 0:
        # with output clipping the gain must be evaluated at the clipped output
        # (reference: GetLeafGainGivenOutput)
        out = jnp.clip(-tg / denom, -p.max_delta_step, p.max_delta_step)
        return -(2.0 * tg * out + denom * out * out)
    return tg * tg / denom


def _gain_l2(sum_g, sum_h, l1, l2, max_delta_step):
    """leaf_gain with explicit regularizers (categorical adds cat_l2)."""
    tg = threshold_l1(sum_g, l1)
    denom = sum_h + l2 + KEPSILON
    if max_delta_step > 0:
        out = jnp.clip(-tg / denom, -max_delta_step, max_delta_step)
        return -(2.0 * tg * out + denom * out * out)
    return tg * tg / denom


def gain_plane(
    hist: jnp.ndarray,  # (3, F, B) f32 — per-feature histograms for ONE leaf
    # (channel-first: the minor (F, B) tile pair lays out pad-free on TPU)
    parent_sum_g: jnp.ndarray,
    parent_sum_h: jnp.ndarray,
    parent_count: jnp.ndarray,
    num_bins_per_feature: jnp.ndarray,  # (F,) i32 total bins incl. missing slot
    missing_bin_per_feature: jnp.ndarray,  # (F,) i32; -1 if feature has no NaN bin
    params: SplitParams,
    feature_mask: jnp.ndarray | None = None,  # (F,) bool — col sampling / constraints
    categorical_mask: jnp.ndarray | None = None,  # (F,) bool — categorical features
    monotone_constraints: jnp.ndarray | None = None,  # (F,) i32 in {-1,0,1}
    out_lo: jnp.ndarray | None = None,  # scalar — leaf output lower bound
    out_hi: jnp.ndarray | None = None,  # scalar — leaf output upper bound
    rng_key: jnp.ndarray | None = None,  # per-node key (extra_trees / bynode)
    depth: jnp.ndarray | None = None,  # scalar — leaf depth (monotone_penalty)
    parent_output: jnp.ndarray | None = None,  # scalar — this leaf's output (path_smooth)
    cegb_feature_penalty: jnp.ndarray | None = None,  # (F,) pre-scaled coupled penalty
    feature_contri: jnp.ndarray | None = None,  # (F,) split-gain multipliers
):
    """Evaluate every (feature, threshold, missing-direction) candidate and
    return `(gain (F, B), ctx)` — the full candidate-gain plane plus the
    context needed to materialize the winner (select_from_plane).  Split out
    from the selection so the voting-parallel learner can vote on per-feature
    local gains (reference: VotingParallelTreeLearner's local SplitInfo ranks).

    Numerical split semantics: rows with bin <= t go left; missing rows go to
    the default direction.  Missing bin sits at index (num_bins-1) when
    present (binning.py), and is excluded from the cumulative scan.
    """
    _, f, b = hist.shape
    bins_idx = jnp.arange(b, dtype=jnp.int32)

    # zero-out the missing bin from the scan; keep its mass separately
    has_missing = missing_bin_per_feature >= 0  # (F,)
    is_missing_bin = bins_idx[None, :] == missing_bin_per_feature[:, None]  # (F, B)
    hist_nm = jnp.where(is_missing_bin[None], 0.0, hist)  # (3, F, B)
    miss = jnp.sum(jnp.where(is_missing_bin[None], hist, 0.0), axis=2)  # (3, F)

    cum = jnp.cumsum(hist_nm, axis=2)  # (3, F, B) left stats at threshold=b

    # candidate validity: threshold t splits between bin t and t+1; the last
    # non-missing bin cannot be a threshold.
    last_nm_bin = num_bins_per_feature - jnp.where(has_missing, 2, 1)  # index of last non-missing bin

    # node-level feature sampling (reference: ColSampler::GetByNode) and
    # extra_trees' single random threshold per feature (ExtraTreeLearner-like
    # mode folded into the scan by masking candidates)
    if rng_key is not None:
        k_bynode, k_extra = jax.random.split(rng_key)
        if params.feature_fraction_bynode < 1.0:
            keep = jax.random.uniform(k_bynode, (f,)) < params.feature_fraction_bynode
            feature_mask = keep if feature_mask is None else (feature_mask & keep)

    valid_thr = bins_idx[None, :] < last_nm_bin[:, None]  # (F, B)
    if rng_key is not None and params.extra_trees:
        rbin = jnp.floor(
            jax.random.uniform(k_extra, (f,)) * jnp.maximum(last_nm_bin, 1)
        ).astype(jnp.int32)
        valid_thr = valid_thr & (bins_idx[None, :] == rbin[:, None])
    if feature_mask is not None:
        valid_thr = valid_thr & feature_mask[:, None]

    parent_g = parent_sum_g
    parent_h = parent_sum_h
    use_smooth = params.path_smooth > 0 and parent_output is not None
    if use_smooth:
        # with path smoothing all gains are evaluated at actual (smoothed)
        # outputs; the parent term uses the leaf's stored output
        # (reference: the USE_SMOOTHING instantiations of GetSplitGains)
        gain_parent = gain_given_output(
            parent_g, parent_h, params.lambda_l1, params.lambda_l2, parent_output
        )
    else:
        gain_parent = leaf_gain(parent_g, parent_h, params)

    def eval_direction(missing_left: bool):
        add = miss if missing_left else jnp.zeros_like(miss)  # (3, F)
        left_g = cum[0] + add[0][:, None]
        left_h = cum[1] + add[1][:, None]
        left_c = cum[2] + add[2][:, None]
        right_g = parent_g - left_g
        right_h = parent_h - left_h
        right_c = parent_count - left_c
        ok = (
            valid_thr
            & (left_c >= params.min_data_in_leaf)
            & (right_c >= params.min_data_in_leaf)
            & (left_h >= params.min_sum_hessian_in_leaf)
            & (right_h >= params.min_sum_hessian_in_leaf)
        )
        if monotone_constraints is None and not use_smooth:
            g = leaf_gain(left_g, left_h, params) + leaf_gain(right_g, right_h, params) - gain_parent
        else:
            # output-based gains: smoothing shrinks child outputs towards
            # the parent's; the basic monotone method additionally clips to
            # the inherited [out_lo, out_hi] band and rejects ordering
            # violations (reference: monotone_constraints.hpp
            # BasicLeafConstraints + GetSplitGainGivenOutput).
            if use_smooth:
                out_l = leaf_output_smoothed(left_g, left_h, left_c, parent_output, params)
                out_r = leaf_output_smoothed(right_g, right_h, right_c, parent_output, params)
            else:
                out_l = leaf_output(left_g, left_h, params)
                out_r = leaf_output(right_g, right_h, params)
            if monotone_constraints is not None:
                lo = jnp.float32(-jnp.inf) if out_lo is None else out_lo
                hi = jnp.float32(jnp.inf) if out_hi is None else out_hi
                out_l = jnp.clip(out_l, lo, hi)
                out_r = jnp.clip(out_r, lo, hi)
            g = (
                gain_given_output(left_g, left_h, params.lambda_l1, params.lambda_l2, out_l)
                + gain_given_output(right_g, right_h, params.lambda_l1, params.lambda_l2, out_r)
                - gain_parent
            )
            if monotone_constraints is not None:
                mono = monotone_constraints[:, None]
                viol = ((mono > 0) & (out_l > out_r)) | ((mono < 0) & (out_l < out_r))
                # a leaf whose [lo, hi] band has gone EMPTY (stacked
                # constraints from different monotone ancestors can
                # conflict as bounds evolve) is unsplittable: any child
                # output would breach one of the ancestors.  clip() above
                # silently returns hi in that case, so gate explicitly.
                ok = ok & ~viol & (lo <= hi)
        g = jnp.where(ok, g, KMIN_SCORE)
        return g, (left_g, left_h, left_c)

    gain_r, stats_r = eval_direction(False)  # missing -> right
    gain_l, stats_l = eval_direction(True)  # missing -> left
    # where the feature has no missing values the two directions tie; prefer
    # missing->right to mirror the reference's default (default_left=false
    # when there is nothing to route).
    use_left = gain_l > gain_r
    gain = jnp.where(use_left, gain_l, gain_r)  # (F, B)

    if categorical_mask is not None:
        gain = jnp.where(categorical_mask[:, None], KMIN_SCORE, gain)
        with phase_scope("grow.cat_search"):
            gain, cat_ctx = _categorical_candidates(
                gain, hist_nm, is_missing_bin, parent_g, parent_h,
                parent_count, params, categorical_mask, feature_mask)

    # ------------------------------------------------------------------
    # gain adjustments applied BEFORE the min_gain_to_split gate, matching
    # the reference's ordering (penalized gain must beat min_gain_shift)
    # ------------------------------------------------------------------
    live = gain > KMIN_SCORE / 2
    if (
        params.monotone_penalty > 0
        and monotone_constraints is not None
        and depth is not None
    ):
        factor = monotone_split_gain_penalty(depth, params.monotone_penalty)
        is_mono = (monotone_constraints != 0)[:, None]
        gain = jnp.where(live & is_mono, gain * factor, gain)
    # ordering mirrors the reference: the min_gain gate sees RAW gains
    # (FindBestThresholdSequentially's min_gain_shift), then the chosen
    # gain is scaled by feature_contri (output->gain *= penalty) and the
    # CEGB delta is subtracted (SerialTreeLearner after FindBestThreshold);
    # an adjusted gain must stay positive to produce a split
    gate = live & (gain > params.min_gain_to_split)
    gain = jnp.where(gate, gain, KMIN_SCORE)
    has_adjust = False
    if feature_contri is not None:
        # reference: config feature_contri — gain[i] = max(0, contri[i]) * gain[i]
        contri = jnp.maximum(feature_contri.astype(jnp.float32), 0.0)
        gain = jnp.where(gate, gain * contri[:, None], gain)
        has_adjust = True
    if params.cegb_penalty_split > 0 or cegb_feature_penalty is not None:
        pen = jnp.zeros((f,), jnp.float32)
        if params.cegb_penalty_split > 0:
            pen = pen + params.cegb_tradeoff * params.cegb_penalty_split * parent_count
        if cegb_feature_penalty is not None:
            pen = pen + cegb_feature_penalty
        gain = jnp.where(gate, gain - pen[:, None], gain)
        has_adjust = True
    if has_adjust:
        gain = jnp.where(gate & (gain > 0), gain, KMIN_SCORE)

    ctx = dict(
        use_left=use_left,
        stats_l=stats_l,
        stats_r=stats_r,
        parent_g=parent_g,
        parent_h=parent_h,
        parent_count=parent_count,
        categorical_mask=categorical_mask,
    )
    if categorical_mask is not None:
        ctx.update(cat_ctx)
    return gain, ctx


def rank_in_order(keys: jnp.ndarray) -> jnp.ndarray:
    """``argsort(argsort(keys, axis=-1), axis=-1)`` of a stable ascending
    sort (equal keys in index order, NaN last), with no sort: an entry's
    rank is the count of entries that come before it, a ``(B, B)`` compare a
    row.  At 256 keys a row the compares are cheap on the VPU where XLA's
    sort on the TPU is its slowest operation (PERF.md section 6, PR 36)."""
    b = keys.shape[-1]
    mine, other = keys[..., :, None], keys[..., None, :]
    nan_mine, nan_other = jnp.isnan(mine), jnp.isnan(other)
    less = (other < mine) | (nan_mine & ~nan_other)
    equal = (other == mine) | (nan_mine & nan_other)
    i = jnp.arange(b, dtype=jnp.int32)
    earlier = i[None, :] < i[:, None]  # [mine, other]: other's index first
    return jnp.sum(less | (equal & earlier), axis=-1, dtype=jnp.int32)


def _categorical_candidates(gain, hist_nm, is_missing_bin, parent_g, parent_h,
                            parent_count, params: SplitParams,
                            categorical_mask, feature_mask):
    """The categorical candidates of one leaf, laid over ``gain`` in the
    categorical columns (reference: feature_histogram.hpp ->
    FindBestThresholdCategoricalInner).  Two families:
      one-hot   (<= max_cat_to_onehot used bins): each bin alone vs rest;
      many-vs-many: bins ordered by sum_g/(sum_h+cat_smooth), a prefix of
        the order (scanned from both ends, bounded by max_cat_threshold)
        goes left.  cat_l2 is added to lambda_l2 in the gain.
    The missing bin is excluded from left subsets (NaN/unseen -> right),
    matching Tree::CategoricalDecision's not-in-bitset => right.

    Computed over ``params.cat_features`` alone where the caller stated them
    (26 of Criteo's 39 columns), and with no sort: a bin's place in either
    order is :func:`rank_in_order`, the bins are put in that order by a
    one-hot product (exact: one term a sum), and the prefix sums are the
    cumulative sums they always were, so every candidate is the old one bit
    for bit.  Returns the plane and what ``select_from_plane`` needs."""
    _, f, b = hist_nm.shape
    bins_idx = jnp.arange(b, dtype=jnp.int32)
    cols = params.cat_features
    if cols is None:
        slot_of = None  # a column is its own slot
    else:
        cols = list(cols)  # static: a tuple of Python ints
        slot_of = np.zeros((f,), np.int32)
        slot_of[cols] = np.arange(len(cols), dtype=np.int32)
        hist_nm = hist_nm[:, cols, :]  # (3, Fc, B)
        is_missing_bin = is_missing_bin[cols, :]
    l2c = params.lambda_l2 + params.cat_l2

    def cgain(g_, h_):
        return _gain_l2(g_, h_, params.lambda_l1, l2c, params.max_delta_step)

    gain_parent_cat = cgain(parent_g, parent_h)
    used = (hist_nm[2] > 0) & ~is_missing_bin  # (Fc, B)
    num_used = jnp.sum(used, axis=1)  # (Fc,)
    ratio = jnp.where(
        used,
        hist_nm[0] / (hist_nm[1] + params.cat_smooth),
        jnp.inf,
    )

    def cat_ok(l_c, r_c, l_h, r_h):
        return (
            (l_c >= params.min_data_in_leaf)
            & (r_c >= params.min_data_in_leaf)
            & (l_h >= params.min_sum_hessian_in_leaf)
            & (r_h >= params.min_sum_hessian_in_leaf)
        )

    def eval_sorted(keys):
        rank = rank_in_order(keys)  # (Fc, B): unused bins last, in bin order
        at = rank[:, None, :] == bins_idx[None, :, None]  # (Fc, place, bin)
        sh = jnp.sum(jnp.where(at[None], hist_nm[:, :, None, :], 0.0), axis=3)
        cum = jnp.cumsum(sh, axis=2)  # prefix stats; index k-1 = prefix len k
        k_len = bins_idx[None, :] + 1  # (1, B) prefix length at index b
        lg_, lh_, lc_ = cum[0], cum[1], cum[2]
        rg_, rh_, rc_ = parent_g - lg_, parent_h - lh_, parent_count - lc_
        # reference additionally caps each scan direction at half the
        # used bins ((used_bin + 1) / 2 in
        # FindBestThresholdCategoricalInner), so both-direction scans
        # never consider the same partition twice.
        ok = (
            (k_len <= params.max_cat_threshold)
            & (k_len <= (num_used[:, None] + 1) // 2)
            & (k_len < num_used[:, None])
            & cat_ok(lc_, rc_, lh_, rh_)
        )
        g_ = cgain(lg_, lh_) + cgain(rg_, rh_) - gain_parent_cat
        g_ = jnp.where(ok, g_, KMIN_SCORE)
        return g_, rank, (lg_, lh_, lc_)

    gain_asc, rank_asc, st_asc = eval_sorted(ratio)
    gain_desc, rank_desc, st_desc = eval_sorted(
        jnp.where(used, -ratio, jnp.inf)
    )
    # one-hot: bin b alone goes left
    oh_l = hist_nm  # (3, Fc, B)
    oh_ok = (
        used
        & cat_ok(
            oh_l[2], parent_count - oh_l[2],
            oh_l[1], parent_h - oh_l[1],
        )
    )
    gain_oh = (
        cgain(oh_l[0], oh_l[1])
        + cgain(parent_g - oh_l[0], parent_h - oh_l[1])
        - gain_parent_cat
    )
    gain_oh = jnp.where(oh_ok, gain_oh, KMIN_SCORE)

    onehot_mode = (num_used <= params.max_cat_to_onehot)[:, None]  # (Fc, 1)
    gain_mvm = jnp.maximum(gain_asc, gain_desc)
    variant_mvm = jnp.where(gain_desc > gain_asc, 2, 1)
    gain_cat = jnp.where(onehot_mode, gain_oh, gain_mvm)
    variant = jnp.where(onehot_mode, 0, variant_mvm)  # (Fc, B)
    cat_col = categorical_mask[:, None]
    if feature_mask is not None:
        cat_col = cat_col & feature_mask[:, None]
    if slot_of is not None:
        gain_cat = gain_cat[slot_of]  # (F, B); a numerical row is masked out
    gain = jnp.where(cat_col, gain_cat, gain)
    return gain, dict(
        variant=variant, rank_asc=rank_asc, rank_desc=rank_desc,
        st_asc=st_asc, st_desc=st_desc, oh_l=oh_l, cat_slot=slot_of,
    )


def winner_cat_mask(rank_asc, rank_desc, slot, v, best_t):
    """The winning categorical candidate's bins-going-left, ``(B,)``: bin
    ``best_t`` alone (``v`` 0), or the bins whose place in the ascending
    (1) or descending (2) order is at most ``best_t``.

    The column's two rank rows are taken by compare-and-select over the
    columns and the family by an index into the three stacked masks, as
    ``select_from_plane`` takes the sums.  Not ``rank[slot]`` and a ``where``
    on ``v``: under the growers' vmap XLA:TPU (libtpu 0.0.34) fused those
    gathers and the scalar select into the mask's consumer and read the
    descending order's row where ``v`` said ascending, so rows were routed by
    one order and counted by the other (PERF.md section 6, PR 36; no CPU
    path shows it).  ``chip_smoke.leg_categorical`` holds this form to every
    leaf's rows on the chip and keeps the faulty one as a reproducer."""
    bins_idx = jnp.arange(rank_asc.shape[1], dtype=jnp.int32)
    mine = jnp.arange(rank_asc.shape[0], dtype=jnp.int32)[:, None] == slot
    row_asc = jnp.sum(jnp.where(mine, rank_asc, 0), axis=0)
    row_desc = jnp.sum(jnp.where(mine, rank_desc, 0), axis=0)
    masks = jnp.stack([bins_idx == best_t, row_asc <= best_t,
                       row_desc <= best_t])
    return masks[v]


def select_from_plane(gain: jnp.ndarray, ctx: dict) -> BestSplit:
    """Materialize the argmax candidate of a gain plane into a BestSplit."""
    f, b = gain.shape
    use_left = ctx["use_left"]
    stats_l, stats_r = ctx["stats_l"], ctx["stats_r"]
    parent_g, parent_h, parent_count = (
        ctx["parent_g"], ctx["parent_h"], ctx["parent_count"]
    )
    categorical_mask = ctx["categorical_mask"]

    flat = gain.reshape(-1)
    best = jnp.argmax(flat)
    best_gain = flat[best]
    best_f = (best // b).astype(jnp.int32)
    best_t = (best % b).astype(jnp.int32)
    best_left = use_left.reshape(-1)[best]

    def pick(sl, sr):
        return jnp.where(best_left, sl.reshape(-1)[best], sr.reshape(-1)[best])

    lg = pick(stats_l[0], stats_r[0])
    lh = pick(stats_l[1], stats_r[1])
    lc = pick(stats_l[2], stats_r[2])
    best_is_cat = jnp.asarray(False)
    best_cat_mask = jnp.zeros((b,), dtype=bool)

    if categorical_mask is not None:
        variant, rank_asc, rank_desc = ctx["variant"], ctx["rank_asc"], ctx["rank_desc"]
        st_asc, st_desc, oh_l = ctx["st_asc"], ctx["st_desc"], ctx["oh_l"]
        best_is_cat = categorical_mask[best_f]
        # the categorical arrays hold a row a categorical column where the
        # caller stated the columns (SplitParams.cat_features)
        slot = best_f if ctx["cat_slot"] is None else jnp.asarray(
            ctx["cat_slot"])[best_f]
        at = slot * b + best_t
        v = variant.reshape(-1)[at]
        best_cat_mask = jnp.where(
            best_is_cat,
            winner_cat_mask(rank_asc, rank_desc, slot, v, best_t),
            jnp.zeros((b,), bool))

        def pick_cat():
            stats = [
                (oh_l[0], oh_l[1], oh_l[2]),
                st_asc,
                st_desc,
            ]
            g_ = jnp.stack([s[0].reshape(-1)[at] for s in stats])[v]
            h_ = jnp.stack([s[1].reshape(-1)[at] for s in stats])[v]
            c_ = jnp.stack([s[2].reshape(-1)[at] for s in stats])[v]
            return g_, h_, c_

        cg, ch, cc = pick_cat()
        lg = jnp.where(best_is_cat, cg, lg)
        lh = jnp.where(best_is_cat, ch, lh)
        lc = jnp.where(best_is_cat, cc, lc)
        best_left = jnp.where(best_is_cat, False, best_left)

    return BestSplit(
        gain=best_gain,
        feature=best_f,
        threshold_bin=best_t,
        default_left=best_left,
        is_cat=best_is_cat,
        cat_mask=best_cat_mask,
        left_sum_g=lg,
        left_sum_h=lh,
        left_count=lc,
        right_sum_g=parent_g - lg,
        right_sum_h=parent_h - lh,
        right_count=parent_count - lc,
    )


def find_best_split(
    hist: jnp.ndarray,
    parent_sum_g: jnp.ndarray,
    parent_sum_h: jnp.ndarray,
    parent_count: jnp.ndarray,
    num_bins_per_feature: jnp.ndarray,
    missing_bin_per_feature: jnp.ndarray,
    params: SplitParams,
    feature_mask: jnp.ndarray | None = None,
    categorical_mask: jnp.ndarray | None = None,
    monotone_constraints: jnp.ndarray | None = None,
    out_lo: jnp.ndarray | None = None,
    out_hi: jnp.ndarray | None = None,
    rng_key: jnp.ndarray | None = None,
    depth: jnp.ndarray | None = None,
    parent_output: jnp.ndarray | None = None,
    cegb_feature_penalty: jnp.ndarray | None = None,
    feature_contri: jnp.ndarray | None = None,
) -> BestSplit:
    """gain_plane + select_from_plane (reference: FindBestThreshold)."""
    return _plane_then_select(
        hist, parent_sum_g, parent_sum_h, parent_count,
        num_bins_per_feature, missing_bin_per_feature, params,
        feature_mask, categorical_mask, monotone_constraints, out_lo, out_hi,
        rng_key, depth, parent_output, cegb_feature_penalty, feature_contri,
        cell=None,
    )


def forced_split_candidate(
    hist: jnp.ndarray,  # (3, F, B) — the target leaf's histograms
    parent_sum_g, parent_sum_h, parent_count,
    num_bins_per_feature, missing_bin_per_feature,
    params: SplitParams,
    forced_feature, forced_bin,  # scalars — the scheduled cell
    categorical_mask=None, monotone_constraints=None,
    out_lo=None, out_hi=None, depth=None, parent_output=None,
    feature_contri=None,
) -> BestSplit:
    """Materialize a forced split (reference: SerialTreeLearner::ForceSplits
    — the scheduled (feature, bin) cell is evaluated through the standard
    gain machinery so min_data/min_hess/monotone gates still apply).  Shared
    by the strict and rounds growers; validity = `gain > KMIN_SCORE / 2` on
    the returned split, checked by the caller along with leaf/depth gates."""
    _, f, b = hist.shape
    cell = (
        (jnp.arange(f, dtype=jnp.int32)[:, None] == forced_feature)
        & (jnp.arange(b, dtype=jnp.int32)[None, :] == forced_bin)
    )
    return _plane_then_select(
        hist, parent_sum_g, parent_sum_h, parent_count,
        num_bins_per_feature, missing_bin_per_feature, params,
        None, categorical_mask, monotone_constraints, out_lo, out_hi,
        None, depth, parent_output, None, feature_contri,
        cell=cell,
    )


def _plane_then_select(
    hist, parent_sum_g, parent_sum_h, parent_count,
    num_bins_per_feature, missing_bin_per_feature, params,
    feature_mask, categorical_mask, monotone_constraints, out_lo, out_hi,
    rng_key, depth, parent_output, cegb_feature_penalty, feature_contri,
    cell,
) -> BestSplit:
    gain, ctx = gain_plane(
        hist, parent_sum_g, parent_sum_h, parent_count,
        num_bins_per_feature, missing_bin_per_feature, params,
        feature_mask=feature_mask,
        categorical_mask=categorical_mask,
        monotone_constraints=monotone_constraints,
        out_lo=out_lo,
        out_hi=out_hi,
        rng_key=rng_key,
        depth=depth,
        parent_output=parent_output,
        cegb_feature_penalty=cegb_feature_penalty,
        feature_contri=feature_contri,
    )
    if cell is not None:
        gain = jnp.where(cell, gain, KMIN_SCORE)
    return select_from_plane(gain, ctx)
