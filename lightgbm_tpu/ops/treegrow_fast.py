"""Round-batched leaf-wise tree growth — the TPU throughput grower.

Motivation (*log*, 1M x 28 x 256 on a v5e, before the kernel packed its
rows): one full-data histogram pass cost ~6 ms regardless of how few rows
were masked in.  Since PR 29 the Pallas kernel's cost follows the rows of
the pass (ops/hist_pallas.py: each row tile pays for whole sub-blocks of
its rows in the pass, plus a floor per tile), so a pass over small children
is cheap but not free.  The strict
leaf-wise grower (ops/treegrow.py) pays a pass per SPLIT (num_leaves-1
passes/tree).  This grower pays it per ROUND: each round splits EVERY
already-evaluated leaf whose gain clears the bar (best-gain-first within the
remaining num_leaves budget), then computes histograms for ALL new smaller
children in ONE multi-channel Pallas pass (lanes = leaf-slot one-hot x
bf16x2 payload — ops/hist_pallas.py::histogram_pallas_multi), recovers the
bigger siblings by subtraction, and evaluates all fresh leaves with one
vmapped split search.  A 31-leaf tree takes ~6 rounds, not 30 passes.

Semantics vs the reference (src/treelearner/serial_tree_learner.cpp):
identical split math, identical per-leaf histograms; the only deviation is
the growth ORDER — strict best-first splits one leaf at a time and lets a
fresh child compete immediately, while this grower defers fresh children to
the next round.  When the num_leaves budget truncates the final round the
resulting leaf set can differ from the reference's.  This is the same class
of deviation as the reference's own device variants (its CUDA learner
documents minor tree differences vs CPU).  `tree_growth_mode=strict`
(config.py) selects the exact-order grower instead; CPU runs default to
strict, TPU runs to rounds.

Supported here: numerical + categorical splits, missing handling, monotone
(basic AND intermediate — same-round splits under a shared monotone node
are deferred so bound evolution stays sequential, see round_body) +
interaction constraints, max_depth, extra_trees/bynode sampling, CEGB
(split/coupled/lazy per-row charges; lazy is single-device), data-parallel
via shard_map psum (axis_name).  Feature- and voting-parallel modes stay
on the strict grower (their cost is comms-, not pass-, shaped).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..utils import degrade as _degrade
from ..utils.profiling import phase_scope
from .hist_pallas import (bins_shadow, blocks_multiplied, blocks_packed,
                          pass_counts, payload_base, payload_base_quantized)
from .histogram import (histogram, histogram_multi, histogram_multi_quantized,
                        histogram_onehot_multi,
                        histogram_onehot_multi_quantized, unbundle_hists)
from .split import (
    BestSplit, SplitParams, find_best_split, forced_split_candidate,
    leaf_output, leaf_output_smoothed, KMIN_SCORE,
)
from .treegrow import TreeArrays, _empty_best, _intermediate_bounds, _set_best


@jax.jit
def predict_leaf_arrays(
    arrays: TreeArrays,
    bins: jnp.ndarray,  # (N, F) int — binned rows (train binner's bin space)
    missing_bin_per_feature: jnp.ndarray,  # (F,) i32
) -> jnp.ndarray:
    """Leaf index per row for a DEVICE tree (fixed-shape vectorized walk;
    host analogue: Tree::GetLeafIndex).  Children encode leaves as ~leaf."""
    n = bins.shape[0]
    L = arrays.leaf_value.shape[0]
    bins = bins.astype(jnp.int32)
    start = jnp.where(arrays.num_leaves > 1, 0, -1).astype(jnp.int32)
    cur0 = jnp.full((n,), 0, jnp.int32) + start

    def body(_, cur):
        is_node = cur >= 0
        nd = jnp.clip(cur, 0, max(L - 2, 0))
        ft = arrays.split_feature[nd]
        col = jnp.take_along_axis(bins, ft[:, None], axis=1)[:, 0]
        miss = col == missing_bin_per_feature[ft]
        gl = jnp.where(miss, arrays.default_left[nd], col <= arrays.threshold_bin[nd])
        gl = jnp.where(arrays.is_cat[nd], arrays.cat_mask[nd, col], gl)
        nxt = jnp.where(gl, arrays.left_child[nd], arrays.right_child[nd])
        return jnp.where(is_node, nxt, cur)

    cur = jax.lax.fori_loop(0, max(L - 1, 1), body, cur0)
    return -cur - 1  # ~cur: node ids exhausted, only leaves remain


class FastState(NamedTuple):
    leaf_id: jnp.ndarray  # (N,) i32; with a shadow (bins_t) the rows ride
    # its tiles, (N/C, C/128, 128), padded rows and all, until the tree returns
    hist: jnp.ndarray  # (L, 3, F, B) f32 — channel-first: the minor (F, B)
    # tile pair pads ~nothing on TPU, vs 42.7x for a trailing dim of 3
    best: BestSplit  # vectorized over L (gain=KMIN for unevaluated leaves)
    leaf_sum_g: jnp.ndarray  # (L,)
    leaf_sum_h: jnp.ndarray
    leaf_count: jnp.ndarray
    leaf_depth: jnp.ndarray
    leaf_parent: jnp.ndarray
    leaf_side: jnp.ndarray
    num_leaves_cur: jnp.ndarray  # i32
    leaf_out_lo: jnp.ndarray
    leaf_out_hi: jnp.ndarray
    leaf_out: jnp.ndarray  # (L,) f32 — each leaf's (smoothed/clipped) output
    cegb_used: jnp.ndarray  # (F,) bool — features split on in this tree
    used_features: jnp.ndarray  # (L, F) bool or () placeholder
    fresh: jnp.ndarray  # (L,) bool — leaves created this round, need hist+eval
    small_slot: jnp.ndarray  # (L,) i32 — pass slot of each fresh SMALL child, -1 otherwise
    slot_left: jnp.ndarray  # (tile,) i32 — left-child leaf per pass slot (-1
    # inactive).  The parent's hist lives in the LEFT child's state slot
    # (left keeps the parent's leaf id), so the pass can gather parents and
    # do the sibling subtraction on COMPACT (tile,...) arrays instead of
    # the full (L,...) state (measured 57 ms/round of full-state
    # scatter+subtract at Epsilon shape — benchmarks/probe_r5_fixed.py)
    slot_right: jnp.ndarray  # (tile,) i32 — right-child leaf per slot (-1)
    slot_small_left: jnp.ndarray  # (tile,) bool — slot's small child is left
    progress: jnp.ndarray  # bool — this round applied at least one split
    hist_passes: jnp.ndarray  # i32 — full passes over the rows so far: the
    # root's, plus one for every hist_and_eval taken
    hist_blocks: jnp.ndarray  # (2,) i32 — sub-blocks of rows the Pallas kernel
    # put through its one-hot product in those passes, and those of them that
    # were a packed tile's (0 on the other routes)
    tree: TreeArrays
    anc: jnp.ndarray = False  # (L, L-1) bool ancestor masks, or () placeholder
    aside: jnp.ndarray = False  # (L, L-1) bool — leaf on the RIGHT side of m
    # (maintained only for monotone_method="intermediate"; see treegrow.py)
    lazy_used: jnp.ndarray = False  # (N, F) bool — rows charged per feature
    lazy_counts: jnp.ndarray = False  # (L, F) f32 — per-leaf uncharged rows
    # (maintained only for CEGB cegb_penalty_feature_lazy; reference:
    # CostEfficientGradientBoosting feature_used_in_data bitset)


def _batched_best(
    hist_batch,  # (L, 3, F, B)
    sum_g, sum_h, count,  # (L,)
    num_bins_pf, missing_bin_pf, params,
    feature_mask, categorical_mask, monotone, interaction_sets,
    out_lo, out_hi, used, node_ids, rng_key,
    depth=None, parent_out=None, cegb_pen=None, feature_contri=None,
    lazy_pen=None, lazy_counts=None,  # (F,) penalties x (L, F) uncharged rows
):
    """find_best_split vmapped over leaves."""
    if depth is None:
        depth = jnp.zeros_like(sum_g)
    if parent_out is None:
        parent_out = jnp.zeros_like(sum_g)

    def one(hist, g, h, c, lo, hi, u, nid, dep, pout, lzc):
        fmask = feature_mask
        if interaction_sets is not None and u is not None:
            ok_s = ~jnp.any(u[None, :] & ~interaction_sets, axis=1)
            allowed = jnp.any(interaction_sets & ok_s[:, None], axis=0)
            fmask = allowed if fmask is None else (fmask & allowed)
        key = jax.random.fold_in(rng_key, nid) if rng_key is not None else None
        pen = cegb_pen
        if lzc is not None:
            # CEGB lazy per-row fetch charges: penalty scales with this
            # leaf's uncharged in-bag rows per feature (reference:
            # CostEfficientGradientBoosting::DetailedSplitGain)
            lz = lazy_pen * lzc
            pen = lz if pen is None else pen + lz
        return find_best_split(
            hist, g, h, c, num_bins_pf, missing_bin_pf, params,
            feature_mask=fmask, categorical_mask=categorical_mask,
            monotone_constraints=monotone, out_lo=lo, out_hi=hi, rng_key=key,
            depth=dep.astype(jnp.float32), parent_output=pout,
            cegb_feature_penalty=pen, feature_contri=feature_contri,
        )

    in_axes = (0, 0, 0, 0, 0, 0, 0 if used is not None else None, 0, 0, 0,
               0 if lazy_counts is not None else None)
    return jax.vmap(one, in_axes=in_axes)(
        hist_batch, sum_g, sum_h, count, out_lo, out_hi, used, node_ids,
        depth, parent_out, lazy_counts,
    )


def partition_rows(
    columns: jnp.ndarray,  # the bins, a feature an index of ``axis``
    axis: int,
    lid: jnp.ndarray,  # i32 leaf of every row, shaped as a column is
    s: BestSplit,  # (L,) the split each leaf would take
    accept: jnp.ndarray,  # (L,) bool: the leaves that split this round
    inv_rank: jnp.ndarray,  # (L,) i32: the leaf at each admission rank
    right_of: jnp.ndarray,  # (L,) i32: the id a leaf's right child gets
    missing_bin_per_feature: jnp.ndarray,
    leaf_tile: int,
    categorical: bool,
) -> jnp.ndarray:
    """The rows' leaves after a round's <= leaf_tile accepted splits: the
    left child keeps its parent's id.

    A static loop over the slots with dynamic-slice COLUMN reads: per-row
    take_along_axis gathers lower catastrophically on TPU (*log*, 1M rows:
    ~30 ms a round).  Over the feature-major shadow (axis 0, basic.Dataset.
    bins_device_t) a column is a run of whole (8, 128) tiles shaped as the
    ids are, and the slots' compares and selects compile to ONE fusion that
    reads the round's columns and the ids once: 0.30 ms a round at 10.5M
    rows x 8 slots on a v5e, the HBM's rate, and 0.09 at 400k x 10 (PERF.md
    section 6, PR 31; tests/test_hist_pallas_mosaic.py holds the compile to
    that).  The (F, N) shadow with (N,) ids before it took a fusion a slot
    on one sublane in eight: 4.8 and 0.20 ms.

    A categorical slot reads its split's bins-going-left as a bitset
    (reference: Common::FindInBitset, elementwise): the (B,) mask packed
    into 32-bit words once a slot, a row's word chosen by compare-and-select
    on ``bin >> 5``, its bit by shift and mask.  No per-row gather: the
    lookup ``cat_mask[leaf][bin]`` that stood here was a gather of N rows a
    slot, whether or not the slot's split was categorical, and broke the one
    fusion (PERF.md section 6, PR 36 has what it cost at 15.3M rows)."""
    leaf_id = lid
    if categorical:
        words = pack_bitset(s.cat_mask)  # (L, B/32) u32
    for r in range(leaf_tile):
        leaf_r = inv_rank[r]
        live = accept[leaf_r]  # rank r admitted?
        feat_r = s.feature[leaf_r]
        fcol = jax.lax.dynamic_index_in_dim(
            columns, feat_r, axis=axis, keepdims=False).astype(jnp.int32)
        miss_r = fcol == missing_bin_per_feature[feat_r]
        gl = jnp.where(miss_r, s.default_left[leaf_r],
                       fcol <= s.threshold_bin[leaf_r])
        if categorical:
            gl = jnp.where(s.is_cat[leaf_r],
                           in_bitset(words[leaf_r], fcol), gl)
        sel = live & (lid == leaf_r)
        leaf_id = jnp.where(sel & ~gl, right_of[leaf_r], leaf_id)
    return leaf_id


def pack_bitset(mask: jnp.ndarray) -> jnp.ndarray:
    """(..., B) bool -> (..., ceil(B / 32)) uint32, bit ``b & 31`` of word
    ``b >> 5`` set where ``mask[..., b]``."""
    b = mask.shape[-1]
    n_words = -(-b // 32)
    m = jnp.pad(mask, [(0, 0)] * (mask.ndim - 1) + [(0, n_words * 32 - b)])
    m = m.reshape(mask.shape[:-1] + (n_words, 32)).astype(jnp.uint32)
    return jnp.sum(m << jnp.arange(32, dtype=jnp.uint32), axis=-1,
                   dtype=jnp.uint32)


def in_bitset(words: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Whether bit ``idx`` of the (W,) uint32 ``words`` is set, for every
    element of the int32 ``idx`` (0 <= idx < 32 W): the word by an unrolled
    compare-and-select on ``idx >> 5`` (W is 8 at 256 bins), the bit by shift
    and mask."""
    hi = idx >> 5
    word = jnp.zeros(idx.shape, jnp.uint32)
    for w in range(words.shape[0]):
        word = jnp.where(hi == w, words[w], word)
    return ((word >> (idx & 31).astype(jnp.uint32)) & 1).astype(bool)


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_leaves", "num_bins", "max_depth", "params", "axis_name",
        "leaf_tile", "hist_precision", "use_pallas", "quantize_bins",
        "stochastic_rounding", "quant_renew", "track_path", "n_forced",
        "monotone_method",
    ),
)
def _grow_fast_impl(
    bins: jnp.ndarray,  # (N, F) int
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    row_mask: jnp.ndarray,
    sample_weight: jnp.ndarray,
    feature_mask: jnp.ndarray,
    num_bins_per_feature: jnp.ndarray,
    missing_bin_per_feature: jnp.ndarray,
    categorical_mask: jnp.ndarray = None,
    monotone_constraints: jnp.ndarray = None,
    interaction_sets: jnp.ndarray = None,
    rng_key: jnp.ndarray = None,
    quant_key: jnp.ndarray = None,
    cegb_feature_penalty: jnp.ndarray = None,  # (F,) pre-scaled coupled penalties
    efb_bins: jnp.ndarray = None,  # (N, F_b) bundled bin matrix (io/efb.py)
    efb_gather: jnp.ndarray = None,  # (F, B) int32 into flat (F_b*B)+zero-pad
    efb_default: jnp.ndarray = None,  # (F, B) bool default slots
    bins_t: jnp.ndarray = None,  # (F, N/C, C/128, 128) feature-major
    # shadow (basic.Dataset.bins_device_t): a feature is a run of whole
    # tiles, so the partition reads a split's columns and nothing else, and
    # the per-row state of the rounds rides the same (N/C, C/128, 128) tiles
    feature_contri: jnp.ndarray = None,  # (F,) split-gain multipliers
    forced_leaf: jnp.ndarray = None,  # (K,) i32 — forced-split schedule
    forced_feature: jnp.ndarray = None,  # (K,) i32   (reference: ForceSplits
    forced_bin: jnp.ndarray = None,  # (K,) i32        from forcedsplits JSON)
    cegb_lazy_penalty: jnp.ndarray = None,  # (F,) pre-scaled lazy penalties
    cegb_lazy_used: jnp.ndarray = None,  # (N, F) bool — rows already charged
    *,
    num_leaves: int,
    num_bins: int,
    max_depth: int = -1,
    params: SplitParams = SplitParams(),
    axis_name: Optional[str] = None,
    leaf_tile: int = 16,
    hist_precision: str = "f32",
    use_pallas: bool = True,
    quantize_bins: int = 0,
    stochastic_rounding: bool = True,
    quant_renew: bool = False,
    track_path: bool = False,
    n_forced: int = 0,
    monotone_method: str = "basic",  # basic | intermediate
) -> tuple[TreeArrays, jnp.ndarray]:
    """Grow one tree in rounds; returns (tree, final leaf_id per row).

    quantize_bins > 0 enables quantized training (reference:
    src/treelearner/gradient_discretizer.cpp): gradients/hessians are
    discretized to ints (stochastic rounding), histograms accumulate
    exactly in int32 on the int8 MXU, and split evaluation sees the
    rescaled sums.  quant_renew recomputes leaf outputs from the true f32
    gradients after growth (reference: RenewIntGradTreeOutput).
    """
    n, f = bins.shape
    # bins stay in their storage dtype (int16 on device — half the HBM of
    # int32 at Epsilon scale); kernels and column slices upcast per tile
    # The rounds' per-row state (leaf ids, pass slots, the bag) lies as the
    # shadow's rows do, the padded rows out of every bag; to_rows is (N,)
    # again, for whoever needs a row a row
    mask_t = row_mask
    if bins_t is not None:
        tiles = bins_t.shape[1:]
        mask_t = jnp.pad(row_mask, (0, math.prod(tiles) - n)).reshape(tiles)

    def to_rows(x):
        return x if bins_t is None else x.reshape(-1)[:n]

    with phase_scope("grow.root"):
        grad = grad.astype(jnp.float32) * sample_weight
        hess = hess.astype(jnp.float32) * sample_weight
    grad_true, hess_true = grad, hess
    L = num_leaves

    def psum(x):
        return jax.lax.psum(x, axis_name) if axis_name is not None else x

    if quantize_bins:
        with phase_scope("grow.root"):
            # discretize: grad in [-half, half], hess in [0, quantize_bins]
            # (reference: GradientDiscretizer::DiscretizeGradients)
            half = max(quantize_bins // 2, 1)
            inbag = row_mask.astype(jnp.float32)

            def pmax(x):
                return jax.lax.pmax(x, axis_name) if axis_name is not None else x

            g_scale = jnp.maximum(pmax(jnp.max(jnp.abs(grad) * inbag)) / half, 1e-30)
            h_scale = jnp.maximum(pmax(jnp.max(hess * inbag)) / quantize_bins, 1e-30)
            gs = grad / g_scale
            hs = hess / h_scale
            if stochastic_rounding:
                if quant_key is None:
                    quant_key = jax.random.PRNGKey(0)
                kg, kh = jax.random.split(quant_key)
                gq = jnp.floor(gs + jax.random.uniform(kg, gs.shape))
                hq = jnp.floor(hs + jax.random.uniform(kh, hs.shape))
            else:
                gq = jnp.round(gs)
                hq = jnp.round(hs)
            gq = jnp.clip(gq, -127, 127).astype(jnp.int8)
            hq = jnp.clip(hq, 0, 127).astype(jnp.int8)
            # everything downstream sees the dequantized values so leaf stats,
            # subtraction and split eval are consistent with the int histograms
            grad = gq.astype(jnp.float32) * g_scale
            hess = hq.astype(jnp.float32) * h_scale
            quant_scale = jnp.stack([g_scale, h_scale, jnp.float32(1.0)])

    hist_bins = bins if efb_bins is None else efb_bins

    def unbundle(h):
        if efb_gather is None:
            return h
        return unbundle_hists(h, efb_gather, efb_default, f, num_bins)

    # the Pallas kernel's per-tree input (ops/hist_pallas.py): the rows'
    # channels depend on grad, hess and row_mask alone, so they are laid out
    # once here and every pass of the tree, the root's too, takes the same
    # array.  XLA does not hoist a loop-invariant N-sized build by itself.
    # Likewise the feature-major bins that the kernel's packed tiles read:
    # the shadow where it is the shadow of the matrix the kernel reads, else
    # built here, once a tree.
    hist_base = hist_bins_t = None
    if use_pallas and num_bins > 64:
        with phase_scope("hist.payload"):
            hist_base = (payload_base_quantized(gq, hq, row_mask)
                         if quantize_bins else
                         payload_base(grad, hess, row_mask, hist_precision))
            hist_bins_t = (bins_t if bins_t is not None and efb_bins is None
                           else bins_shadow(hist_bins))

    def multi_hist(leaf_slot, tile):
        """A slot a row (as the leaf ids lie) -> (tile, 3, F, B) f32:
        per-slot histograms, one pass; and the sub-blocks the Pallas kernel
        multiplied for it."""
        keep = mask_t & (leaf_slot >= 0)
        counts, blocks = None, jnp.zeros((2,), jnp.int32)
        with phase_scope("grow.slots"):
            if hist_base is not None:  # once a pass, for every chunk
                counts = pass_counts(keep)
                blocks = jnp.stack([
                    blocks_multiplied(counts, hist_bins.shape, num_bins),
                    blocks_packed(counts, hist_bins.shape, num_bins)])
            # the histogram routes take a row a row
            keep, leaf_slot = to_rows(keep), to_rows(leaf_slot)
        if use_pallas and quantize_bins:
            if num_bins <= 64:
                # same measured strategy selection as the float path: XLA's
                # fused one-hot (here int8 x int8 -> int32) wins at narrow
                # bins; exactness is identical
                h = histogram_onehot_multi_quantized(
                    hist_bins, gq, hq, keep,
                    jnp.maximum(leaf_slot, 0), 0, tile, num_bins,
                )
            else:
                h = histogram_multi_quantized(
                    hist_bins, gq, hq, keep,
                    jnp.maximum(leaf_slot, 0), 0, tile, num_bins,
                    base=hist_base, counts=counts, bins_t=hist_bins_t,
                )
        elif use_pallas and num_bins <= 64:
            # measured strategy selection (ops/histogram.py docstring): at
            # narrow bins XLA's fused one-hot einsum beats the Pallas kernel
            h = histogram_onehot_multi(
                hist_bins, grad, hess, keep,
                jnp.maximum(leaf_slot, 0), 0, tile, num_bins,
                precision=hist_precision,
            )
        elif use_pallas:
            h = histogram_multi(
                hist_bins, grad, hess, keep,
                jnp.maximum(leaf_slot, 0), 0, tile, num_bins,
                precision=hist_precision, base=hist_base, counts=counts,
                bins_t=hist_bins_t,
            )
        else:
            # CPU/test fallback: per-slot masked scatter histograms (uses the
            # dequantized grad/hess, so results match the int path's scaling)
            def one(s):
                m = row_mask & (leaf_slot == s)
                return histogram(hist_bins, grad, hess, m.astype(jnp.float32),
                                 num_bins, strategy="scatter")
            with phase_scope("hist.kernel"):
                h = jax.vmap(one)(jnp.arange(tile, dtype=jnp.int32))
        with phase_scope("hist.unpack"):
            h = unbundle(h)
            if use_pallas and quantize_bins:  # int32 sums back to floats
                h = h.astype(jnp.float32) * quant_scale[:, None, None]
            return psum(h), blocks

    # ---- root ----
    with phase_scope("grow.root"):
        hist0, blocks0 = multi_hist(
            jnp.where(mask_t, 0, -1).astype(jnp.int32), 1)
        hist0 = hist0[0]
        sum0 = jnp.sum(hist0[:, 0, :], axis=1)  # totals from feature 0: (3,)
        g0, h0, c0 = sum0[0], sum0[1], sum0[2]

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

    use_used = interaction_sets is not None or track_path
    used0 = jnp.zeros((L, f), bool) if use_used else jnp.zeros((), bool)
    use_intermediate = (
        monotone_method == "intermediate" and monotone_constraints is not None
    )
    # CEGB lazy charges are row-global state; the distributed wrappers do
    # not thread them (rows are sharded), mirroring the strict grower
    use_lazy = (cegb_lazy_penalty is not None and cegb_lazy_used is not None
                and axis_name is None)
    leaf_out0 = leaf_output(g0, h0, params)
    cegb_used0 = jnp.zeros((f,), bool)
    cegb_pen0 = (
        jnp.where(cegb_used0, 0.0, cegb_feature_penalty)
        if cegb_feature_penalty is not None else None
    )

    if use_lazy:
        lazy_used0 = cegb_lazy_used
        lazy_counts0 = jnp.einsum(
            "n,nf->f", row_mask.astype(jnp.float32),
            (~lazy_used0).astype(jnp.float32))
    with phase_scope("grow.split_search"):
        best0 = _set_best(
            _empty_best(L, num_bins), jnp.asarray(0),
            jax.tree.map(
                lambda a: a[0],
                _batched_best(
                    hist0[None], jnp.asarray([g0]), jnp.asarray([h0]),
                    jnp.asarray([c0]), num_bins_per_feature,
                    missing_bin_per_feature, params, feature_mask,
                    categorical_mask, monotone_constraints, interaction_sets,
                    jnp.asarray([-jnp.inf], jnp.float32),
                    jnp.asarray([jnp.inf], jnp.float32),
                    used0[:1] if interaction_sets is not None else None,
                    jnp.asarray([0], jnp.int32), rng_key,
                    depth=jnp.asarray([0.0], jnp.float32),
                    parent_out=jnp.asarray([leaf_out0]),
                    cegb_pen=cegb_pen0,
                    feature_contri=feature_contri,
                    lazy_pen=cegb_lazy_penalty if use_lazy else None,
                    lazy_counts=lazy_counts0[None] if use_lazy else None,
                ),
            ),
        )

    with phase_scope("grow.root"):  # the state the loop carries
        state = FastState(
            leaf_id=jnp.zeros(mask_t.shape, jnp.int32),
            hist=jnp.zeros((L, 3, f, num_bins), jnp.float32).at[0].set(hist0),
            best=best0,
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
            used_features=used0,
            fresh=jnp.zeros((L,), bool),
            small_slot=jnp.full((L,), -1, jnp.int32),
            slot_left=jnp.full((leaf_tile,), -1, jnp.int32),
            slot_right=jnp.full((leaf_tile,), -1, jnp.int32),
            slot_small_left=jnp.zeros((leaf_tile,), bool),
            progress=jnp.asarray(True),
            hist_passes=jnp.asarray(1, jnp.int32),  # the root's
            hist_blocks=blocks0,
            tree=tree0,
            anc=(jnp.zeros((L, L - 1), bool) if use_intermediate
                 else jnp.zeros((), bool)),
            aside=(jnp.zeros((L, L - 1), bool) if use_intermediate
                   else jnp.zeros((), bool)),
            lazy_used=(lazy_used0 if use_lazy else jnp.zeros((), bool)),
            lazy_counts=(jnp.zeros((L, f), jnp.float32).at[0].set(lazy_counts0)
                         if use_lazy else jnp.zeros((), bool)),
        )

    eps = KMIN_SCORE / 2

    def round_body(state: FastState, forced=None) -> FastState:
        # ---------- phase 1: accept splits for this round ----------
        if forced is None:
            gains = state.best.gain  # (L,) KMIN for unevaluated/exhausted
            can = gains > eps
            if max_depth > 0:
                can = can & (state.leaf_depth < max_depth)
            if use_intermediate:
                # Intermediate bounds make same-round splits INTERACT when
                # their leaves sit under a common monotone node: applying
                # one moves the opposite-subtree extremes the other was
                # searched against, and stacked constraints from different
                # ancestors can then clip a child into an EMPTY interval
                # (clip returns hi, breaching lo — a real monotonicity
                # violation, caught by the stress test).  Admit at most one
                # split per monotone-connected component and defer the
                # rest: a deferred leaf is re-searched next round under the
                # updated bounds (hist_and_eval re-evaluates every live
                # leaf), which reproduces the strict grower's sequential
                # semantics split-for-split.  A candidate conflicting with
                # ANY better-ranked candidate is deferred (slightly more
                # conservative than greedy-vs-admitted; one extra round at
                # worst).
                d_nodes = jnp.where(
                    state.tree.is_cat, 0,
                    monotone_constraints[state.tree.split_feature])
                mono_anc = (state.anc & (d_nodes != 0)[None, :]).astype(
                    jnp.float32)  # (L, L-1)
                conflict = (mono_anc @ mono_anc.T) > 0.5  # shared mono anc
                pre_rank = jnp.argsort(jnp.argsort(
                    jnp.where(can, -gains, jnp.inf)))
                better = pre_rank[None, :] < pre_rank[:, None]
                veto = jnp.any(conflict & better & can[None, :], axis=1) & can
                can = can & ~veto
            budget = L - state.num_leaves_cur  # how many new leaves fit
            # best-gain-first admission within budget, but at most leaf_tile
            # splits per round (one multi-hist pass).  The accepted set is a
            # PREFIX of the stable sort order (can-leaves sort first), so
            # the sort doubles as the rank->leaf map below — one argsort
            # fewer in the trace (round-7 warmup diet,
            # benchmarks/probe_trace_ops.py)
            srt = jnp.argsort(jnp.where(can, -gains, jnp.inf))
            order_rank = jnp.argsort(srt)
            accept = can & (order_rank < jnp.minimum(budget, leaf_tile))
            inv_rank = srt  # leaf at rank r; ranks >= k_acc are guarded by
            # accept[] at every use
            s = state.best  # vectorized split info (L,)
        else:
            # forced round (reference: ForceSplits): admit EXACTLY the
            # scheduled split so right-child numbering (split s -> leaf s+1)
            # matches the precomputed schedule; state.best is preserved for
            # the free-growth rounds that follow
            f_leaf, s_f, f_valid = forced
            accept = (jnp.arange(L, dtype=jnp.int32) == f_leaf) & f_valid
            order_rank = jnp.where(accept, 0, L)
            inv_rank = jnp.argsort(order_rank)  # forced leaf at rank 0
            s = jax.tree.map(lambda b, v: b.at[f_leaf].set(v), state.best, s_f)
        k_acc = jnp.sum(accept.astype(jnp.int32))

        # per accepted leaf: new node slot + right-child leaf id, ordered by rank
        acc_rank = jnp.where(accept, order_rank, L)  # (L,)
        node_of = state.num_leaves_cur - 1 + acc_rank  # node slot (valid where accept)
        right_of = state.num_leaves_cur + acc_rank  # right-child leaf id

        # ---------- row partition: all accepted splits at once ----------
        lid = state.leaf_id
        leaf_id = partition_rows(
            *((bins, 1) if bins_t is None else (bins_t, 0)),
            lid, s, accept, inv_rank, right_of, missing_bin_per_feature,
            leaf_tile, categorical_mask is not None)

        # ---------- bookkeeping for accepted splits ----------
        idx = jnp.arange(L, dtype=jnp.int32)
        safe_node = jnp.clip(node_of, 0, L - 2)

        t = state.tree
        parent_out = state.leaf_out
        old_parent = state.leaf_parent
        old_side = state.leaf_side
        # re-point grandparent child slots from ~leaf to the new node
        # (out-of-range sentinel positions are dropped by the scatter)
        repoint_l = accept & (old_parent >= 0) & (old_side == 0)
        repoint_r = accept & (old_parent >= 0) & (old_side == 1)
        lc = t.left_child.at[jnp.where(repoint_l, old_parent, 2 * L)].set(
            safe_node, mode="drop")
        rc = t.right_child.at[jnp.where(repoint_r, old_parent, 2 * L)].set(
            safe_node, mode="drop")
        # new node's children: ~left_leaf, ~right_leaf
        node_pos = jnp.where(accept, node_of, 2 * L)
        lc = lc.at[node_pos].set(-idx - 1, mode="drop")
        rc = rc.at[node_pos].set(-right_of - 1, mode="drop")

        depth_child = state.leaf_depth + 1
        tree = t._replace(
            num_leaves=state.num_leaves_cur + k_acc,
            split_feature=t.split_feature.at[node_pos].set(s.feature, mode="drop"),
            threshold_bin=t.threshold_bin.at[node_pos].set(s.threshold_bin, mode="drop"),
            default_left=t.default_left.at[node_pos].set(s.default_left, mode="drop"),
            split_gain=t.split_gain.at[node_pos].set(s.gain, mode="drop"),
            left_child=lc,
            right_child=rc,
            internal_value=t.internal_value.at[node_pos].set(parent_out, mode="drop"),
            internal_weight=t.internal_weight.at[node_pos].set(state.leaf_sum_h, mode="drop"),
            internal_count=t.internal_count.at[node_pos].set(state.leaf_count, mode="drop"),
            is_cat=t.is_cat.at[node_pos].set(s.is_cat, mode="drop"),
            cat_mask=t.cat_mask.at[node_pos].set(s.cat_mask, mode="drop"),
        )

        # ---------- leaf aggregate updates (left keeps id, right gets new) ----------
        right_pos = jnp.where(accept, right_of, 2 * L)

        def upd(arr, left_val, right_val):
            arr = jnp.where(accept, left_val, arr)
            return arr.at[right_pos].set(right_val, mode="drop")

        leaf_sum_g = upd(state.leaf_sum_g, s.left_sum_g, s.right_sum_g)
        leaf_sum_h = upd(state.leaf_sum_h, s.left_sum_h, s.right_sum_h)
        leaf_count = upd(state.leaf_count, s.left_count, s.right_count)
        leaf_depth = jnp.where(accept, depth_child, state.leaf_depth)
        leaf_depth = leaf_depth.at[right_pos].set(depth_child, mode="drop")
        leaf_parent = jnp.where(accept, node_of, state.leaf_parent)
        leaf_parent = leaf_parent.at[right_pos].set(
            jnp.where(accept, node_of, 0), mode="drop")
        leaf_side = jnp.where(accept, 0, state.leaf_side)
        leaf_side = leaf_side.at[right_pos].set(1, mode="drop")

        # ---------- children outputs (path-smoothed) + monotone bounds ----------
        p_lo, p_hi = state.leaf_out_lo, state.leaf_out_hi
        out_l_c = leaf_output_smoothed(s.left_sum_g, s.left_sum_h, s.left_count,
                                       state.leaf_out, params)
        out_r_c = leaf_output_smoothed(s.right_sum_g, s.right_sum_h, s.right_count,
                                       state.leaf_out, params)
        if use_intermediate:
            # --- intermediate bounds under round-batched splits ---
            # Masks update vectorized: the left child keeps the parent's
            # leaf slot (ancestors + the new node, left side); the right
            # child's row adds the new node on the right side.
            node_oh = jax.nn.one_hot(
                jnp.where(accept, node_of, L), L - 1, dtype=bool)  # (L, L-1)
            anc_child = state.anc | node_oh
            anc = jnp.where(accept[:, None], anc_child, state.anc)
            anc = anc.at[right_pos].set(anc_child, mode="drop")
            aside = state.aside.at[right_pos].set(
                state.aside | node_oh, mode="drop")

            # Creation-time clipping: admitted splits are pairwise
            # NON-interacting (admission defers leaves sharing a monotone
            # ancestor, see phase 1), so each child's bounds are exactly
            # the parent's CURRENT stored bounds (state.leaf_out_lo/hi are
            # the end-of-last-round recompute over this same state).
            # Bounds are evaluated at the parent's slot: both children
            # share all ancestor constraints, and the new node's own
            # column contributes nothing at creation (its opposite side is
            # the not-yet-live sibling); sibling ordering is enforced by
            # the split search and preserved by clipping both children
            # into the same interval.
            lo_all, hi_all = state.leaf_out_lo, state.leaf_out_hi
            ol = jnp.clip(out_l_c, lo_all, hi_all)
            orr = jnp.clip(out_r_c, lo_all, hi_all)
            leaf_out = jnp.where(accept, ol, state.leaf_out)
            leaf_out = leaf_out.at[right_pos].set(orr, mode="drop")
            # rounds grower runs serial/data only — the constraint vector
            # is full-width here, so the per-node direction is a lookup
            node_mono = jnp.where(
                tree.is_cat, 0, monotone_constraints[tree.split_feature])
            leaf_out_lo, leaf_out_hi = _intermediate_bounds(
                anc, aside, node_mono, leaf_out,
                state.num_leaves_cur + k_acc, L,
            )
        else:
            if monotone_constraints is not None:
                mono_c = monotone_constraints[s.feature]
                out_l_c = jnp.clip(out_l_c, p_lo, p_hi)
                out_r_c = jnp.clip(out_r_c, p_lo, p_hi)
                mid = 0.5 * (out_l_c + out_r_c)
                l_hi = jnp.where(mono_c > 0, jnp.minimum(p_hi, mid), p_hi)
                r_lo = jnp.where(mono_c > 0, jnp.maximum(p_lo, mid), p_lo)
                l_lo = jnp.where(mono_c < 0, jnp.maximum(p_lo, mid), p_lo)
                r_hi = jnp.where(mono_c < 0, jnp.minimum(p_hi, mid), p_hi)
            else:
                l_lo, l_hi, r_lo, r_hi = p_lo, p_hi, p_lo, p_hi
            leaf_out_lo = jnp.where(accept, l_lo, state.leaf_out_lo)
            leaf_out_lo = leaf_out_lo.at[right_pos].set(r_lo, mode="drop")
            leaf_out_hi = jnp.where(accept, l_hi, state.leaf_out_hi)
            leaf_out_hi = leaf_out_hi.at[right_pos].set(r_hi, mode="drop")
            leaf_out = jnp.where(accept, out_l_c, state.leaf_out)
            leaf_out = leaf_out.at[right_pos].set(out_r_c, mode="drop")
            anc, aside = state.anc, state.aside
        cegb_used = state.cegb_used
        if cegb_feature_penalty is not None:
            cegb_used = cegb_used.at[
                jnp.where(accept, s.feature, 2 * f)
            ].set(True, mode="drop")

        if use_lazy:
            # charge every accepted leaf's in-bag rows for its split
            # feature, THEN count each child's uncharged rows (a child
            # split on the same feature is free) — the round-batched
            # mirror of the strict grower's per-split charge (reference:
            # CostEfficientGradientBoosting::UpdateUsedFeature)
            lazy_used = state.lazy_used
            lid_rows, leaf_id_rows = to_rows(lid), to_rows(leaf_id)
            for r in range(leaf_tile):
                leaf_r = inv_rank[r]
                live_r = accept[leaf_r]
                feat_r = s.feature[leaf_r]
                sel = live_r & (lid_rows == leaf_r) & row_mask
                lazy_used = lazy_used.at[:, feat_r].set(
                    lazy_used[:, feat_r] | sel)
            # one pass counts all LEFT children (they keep the parent's
            # slot); the right child is the parent remainder with the
            # split feature zeroed on both sides
            oh_left = jnp.stack(
                [(accept[inv_rank[r]] & (leaf_id_rows == inv_rank[r])
                  & row_mask)
                 for r in range(leaf_tile)], axis=1).astype(jnp.float32)
            counts_left = jnp.einsum(
                "nt,nf->tf", oh_left, (~lazy_used).astype(jnp.float32))
            lazy_counts = state.lazy_counts
            for r in range(leaf_tile):
                leaf_r = inv_rank[r]
                live_r = accept[leaf_r]
                feat_r = s.feature[leaf_r]
                parent_cnt = lazy_counts[leaf_r].at[feat_r].set(0.0)
                cl = counts_left[r].at[feat_r].set(0.0)
                cr = jnp.maximum(parent_cnt - cl, 0.0)
                rp = jnp.clip(right_of[leaf_r], 0, L - 1)
                lazy_counts = jnp.where(
                    live_r, lazy_counts.at[leaf_r].set(cl).at[rp].set(cr),
                    lazy_counts)
        else:
            lazy_used, lazy_counts = state.lazy_used, state.lazy_counts

        if use_used:
            used_child = jnp.where(
                accept[:, None],
                state.used_features | jax.nn.one_hot(s.feature, f, dtype=bool),
                state.used_features,
            )
            used_features = used_child.at[right_pos].set(used_child, mode="drop")
        else:
            used_features = state.used_features

        # ---------- fresh/small bookkeeping ----------
        left_smaller = s.left_count <= s.right_count
        fresh = jnp.zeros((L,), bool)
        fresh = jnp.where(accept, True, fresh)
        fresh = fresh.at[right_pos].set(True, mode="drop")
        small_leaf = jnp.where(left_smaller, idx, right_of)  # per accepted split
        slot = jnp.where(accept, acc_rank, -1)  # pass slot = admission rank
        small_slot = jnp.full((L,), -1, jnp.int32)
        small_pos = jnp.where(accept, small_leaf, 2 * L)
        small_slot = small_slot.at[small_pos].set(slot, mode="drop")
        # per-slot child maps: the parent's hist stays in the LEFT child's
        # state slot (left keeps the parent's leaf id), so the pass phase
        # gathers parents and subtracts on compact (tile,...) arrays — no
        # full-state parent snapshot (it measured 17 ms/round at Epsilon
        # shape; benchmarks/probe_r5_fixed.py)
        pos_r = jnp.where(accept, acc_rank, leaf_tile)
        slot_left = jnp.full((leaf_tile,), -1, jnp.int32).at[pos_r].set(
            idx, mode="drop")
        slot_right = jnp.full((leaf_tile,), -1, jnp.int32).at[pos_r].set(
            right_of, mode="drop")
        slot_small_left = jnp.zeros((leaf_tile,), bool).at[pos_r].set(
            left_smaller, mode="drop")
        hist = state.hist

        # invalidate best for split leaves (children evaluated next round)
        best = state.best
        kmin = jnp.full((L,), KMIN_SCORE, jnp.float32)
        best = best._replace(gain=jnp.where(fresh, kmin, best.gain))

        return FastState(
            leaf_id=leaf_id,
            hist=hist,
            best=best,
            leaf_sum_g=leaf_sum_g,
            leaf_sum_h=leaf_sum_h,
            leaf_count=leaf_count,
            leaf_depth=leaf_depth,
            leaf_parent=leaf_parent,
            leaf_side=leaf_side,
            num_leaves_cur=state.num_leaves_cur + k_acc,
            leaf_out_lo=leaf_out_lo,
            leaf_out_hi=leaf_out_hi,
            leaf_out=leaf_out,
            cegb_used=cegb_used,
            used_features=used_features,
            fresh=fresh,
            small_slot=small_slot,
            slot_left=slot_left,
            slot_right=slot_right,
            slot_small_left=slot_small_left,
            progress=k_acc > 0,
            hist_passes=state.hist_passes,
            hist_blocks=state.hist_blocks,
            tree=tree,
            anc=anc,
            aside=aside,
            lazy_used=lazy_used,
            lazy_counts=lazy_counts,
        )

    def hist_and_eval(state: FastState) -> FastState:
        # ---------- phase 2: one pass for all small children ----------
        # slot per row (small_slot[leaf_id]) via a static slot loop — small
        # table gathers at (N,) lower poorly on TPU (see partition above)
        with phase_scope("grow.slots"):
            lid = state.leaf_id
            leaf_slot = jnp.full(lid.shape, -1, jnp.int32)
            for r in range(leaf_tile):
                has_r = state.small_slot == r  # (L,)
                leaf_r = jnp.argmax(has_r).astype(jnp.int32)
                exists = jnp.any(has_r)
                leaf_slot = jnp.where(exists & (lid == leaf_r), r, leaf_slot)
        fresh_hists, blocks = multi_hist(leaf_slot, leaf_tile)  # (tile, 3, F, B)
        with phase_scope("grow.sibling"):
            idx = jnp.arange(L, dtype=jnp.int32)
            # COMPACT sibling recovery (round 5): parent hists live in the left
            # children's slots; gather the <= tile parents, subtract, and
            # scatter both children once — O(tile) state traffic instead of the
            # full-(L,...) scatter/subtract/where chain (measured 57 ms/round
            # at Epsilon shape, round 5)
            active = state.slot_left >= 0  # (tile,)
            sl = jnp.clip(state.slot_left, 0, L - 1)
            sr = jnp.clip(state.slot_right, 0, L - 1)
            parent_hists = state.hist[sl]  # (tile, 3, F, B)
            big_hists = parent_hists - fresh_hists
            sml = state.slot_small_left[:, None, None, None]
            left_hists = jnp.where(sml, fresh_hists, big_hists)
            right_hists = jnp.where(sml, big_hists, fresh_hists)
            lpos = jnp.where(active, sl, 2 * L)
            rpos = jnp.where(active, sr, 2 * L)
            hist = state.hist.at[lpos].set(left_hists, mode="drop").at[rpos].set(
                right_hists, mode="drop")

        # ---------- phase 3: evaluate fresh leaves (one vmapped search) ----------
        with phase_scope("grow.split_search"):
            node_ids = jnp.clip(state.leaf_parent, 0, None) * 2 + state.leaf_side + 1
            cegb_pen = (
                jnp.where(state.cegb_used, 0.0, cegb_feature_penalty)
                if cegb_feature_penalty is not None else None
            )
            if use_intermediate:
                # bounds of EVERY leaf may have moved this round (their opposite
                # subtrees changed), so cached best splits are stale — re-search
                # all live leaves (reference: IntermediateLeafConstraints'
                # leaves_to_update set; recompute-all is the vectorized exact
                # equivalent, same trade as the strict grower makes)
                bb = _batched_best(
                    hist, state.leaf_sum_g, state.leaf_sum_h, state.leaf_count,
                    num_bins_per_feature, missing_bin_per_feature, params,
                    feature_mask, categorical_mask, monotone_constraints,
                    interaction_sets, state.leaf_out_lo, state.leaf_out_hi,
                    state.used_features if interaction_sets is not None else None,
                    node_ids, rng_key,
                    depth=state.leaf_depth, parent_out=state.leaf_out,
                    cegb_pen=cegb_pen,
                    feature_contri=feature_contri,
                    lazy_pen=cegb_lazy_penalty if use_lazy else None,
                    lazy_counts=state.lazy_counts if use_lazy else None,
                )
                live = idx < state.num_leaves_cur
                best = bb._replace(gain=jnp.where(live, bb.gain, KMIN_SCORE))
                return state._replace(
                    hist=hist, best=best,
                    hist_passes=state.hist_passes + 1,
                    hist_blocks=state.hist_blocks + blocks,
                    fresh=jnp.zeros((L,), bool),
                    small_slot=jnp.full((L,), -1, jnp.int32),
                    slot_left=jnp.full((leaf_tile,), -1, jnp.int32),
                    slot_right=jnp.full((leaf_tile,), -1, jnp.int32),
                    slot_small_left=jnp.zeros((leaf_tile,), bool))
            # only the fresh children need evaluation, and their hists are
            # ALREADY compact (left_hists/right_hists above): feed the search
            # directly instead of re-gathering (2*tile, 3, F, B) from the state
            # (that gather measured 18 ms/round at Epsilon shape)
            cand = jnp.concatenate([sl, sr])  # (2*tile,) candidate leaf ids
            cand_ok = jnp.concatenate([active, active])
            cand_hists = jnp.concatenate([left_hists, right_hists], axis=0)
            ci = jnp.where(cand_ok, cand, 0)
            bb = _batched_best(
                cand_hists, state.leaf_sum_g[ci], state.leaf_sum_h[ci],
                state.leaf_count[ci],
                num_bins_per_feature, missing_bin_per_feature, params,
                feature_mask, categorical_mask, monotone_constraints,
                interaction_sets, state.leaf_out_lo[ci], state.leaf_out_hi[ci],
                state.used_features[ci] if interaction_sets is not None else None,
                node_ids[ci], rng_key,
                depth=state.leaf_depth[ci], parent_out=state.leaf_out[ci],
                cegb_pen=cegb_pen,
                feature_contri=feature_contri,
                lazy_pen=cegb_lazy_penalty if use_lazy else None,
                lazy_counts=state.lazy_counts[ci] if use_lazy else None,
            )
            scatter_pos = jnp.where(cand_ok, cand, 2 * L)  # drop inactive slots

            def merge(old, new):
                return old.at[scatter_pos].set(new, mode="drop")

            best = BestSplit(*[merge(o, nw) for o, nw in zip(state.best, bb)])
            return state._replace(
                hist=hist, best=best,
                hist_passes=state.hist_passes + 1,
                hist_blocks=state.hist_blocks + blocks,
                fresh=jnp.zeros((L,), bool),
                small_slot=jnp.full((L,), -1, jnp.int32),
                slot_left=jnp.full((leaf_tile,), -1, jnp.int32),
                slot_right=jnp.full((leaf_tile,), -1, jnp.int32),
                slot_small_left=jnp.zeros((leaf_tile,), bool))

    def cond(state: FastState):
        more_leaves = state.num_leaves_cur < L
        any_gain = jnp.max(state.best.gain) > eps
        return state.progress & more_leaves & any_gain

    def partition(state: FastState, forced=None) -> FastState:
        with phase_scope("grow.partition"):
            return round_body(state, forced)

    def body(state: FastState):
        state = partition(state)
        return jax.lax.cond(
            state.progress, hist_and_eval, lambda st: st, state
        )

    if n_forced > 0:
        # forced prefix (reference: SerialTreeLearner::ForceSplits): one
        # single-split round per schedule entry, BEFORE gain-driven growth.
        # The candidate is evaluated through the standard gain plane masked
        # to the scheduled (feature, bin) cell, so min_data/min_hess/monotone
        # gates apply; the first invalid entry disables the rest (the
        # schedule's leaf ids assume every prior entry applied).
        def forced_candidate(state: FastState, i: int):
            fl = jnp.clip(forced_leaf[i], 0, L - 1)
            s_f = forced_split_candidate(
                state.hist[fl], state.leaf_sum_g[fl], state.leaf_sum_h[fl],
                state.leaf_count[fl], num_bins_per_feature,
                missing_bin_per_feature, params,
                forced_feature[i], forced_bin[i],
                categorical_mask=categorical_mask,
                monotone_constraints=monotone_constraints,
                out_lo=state.leaf_out_lo[fl], out_hi=state.leaf_out_hi[fl],
                depth=state.leaf_depth[fl].astype(jnp.float32),
                parent_output=state.leaf_out[fl],
                feature_contri=feature_contri,
            )
            valid = (
                (forced_leaf[i] < state.num_leaves_cur)
                & (state.num_leaves_cur < L)
                & (s_f.gain > KMIN_SCORE / 2)
            )
            if max_depth > 0:
                valid = valid & (state.leaf_depth[fl] < max_depth)
            return fl, s_f, valid

        forced_ok = jnp.asarray(True)
        for i in range(n_forced):
            fl, s_f, valid = forced_candidate(state, i)
            valid = valid & forced_ok
            forced_ok = valid
            state = partition(state, forced=(fl, s_f, valid))
            state = jax.lax.cond(state.progress, hist_and_eval,
                                 lambda st: st, state)
        # a rejected forced entry leaves progress=False; free growth still runs
        state = state._replace(progress=jnp.asarray(True))

    state = jax.lax.while_loop(cond, body, state)
    leaf_id = to_rows(state.leaf_id)

    with phase_scope("grow.leaf_values"):
        if quant_renew and quantize_bins and not use_intermediate:
            # recompute leaf outputs from the TRUE f32 gradients (reference:
            # GBDT::Train -> RenewIntGradTreeOutput after quantized growth)
            mrow = row_mask.astype(jnp.float32)
            Gt = psum(jnp.zeros((L,), jnp.float32).at[leaf_id].add(grad_true * mrow))
            Ht = psum(jnp.zeros((L,), jnp.float32).at[leaf_id].add(hess_true * mrow))
            leaf_value = leaf_output(Gt, Ht, params)
            if monotone_constraints is not None:
                leaf_value = jnp.clip(leaf_value, state.leaf_out_lo, state.leaf_out_hi)
        elif params.path_smooth > 0 or use_intermediate:
            # smoothed / monotone-clipped AT CREATION.  Under intermediate
            # bounds this is required for correctness: bounds keep evolving
            # after a leaf is created, and re-clipping recomputed outputs to
            # the FINAL bounds can cross a monotone split (see treegrow.py) —
            # which is also why quantized renewal is skipped above when
            # intermediate is active.
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
            hist_passes=state.hist_passes,
            hist_blocks=state.hist_blocks[0],
            hist_blocks_packed=state.hist_blocks[1],
        )
    if use_lazy:
        # hand the cross-tree charge state back (reference: the
        # feature_used_in_data bitset persists across trees)
        return tree, leaf_id, state.lazy_used
    return tree, leaf_id


def grow_tree_fast(*args, use_pallas: bool = True, **kwargs):
    """Public entry: :func:`_grow_fast_impl` behind the graceful
    kernel-degradation net (utils/degrade.py).  ``use_pallas`` folds
    in the degradation registry before becoming a jit static; a Pallas
    failure surfacing at trace or backend-COMPILE time is caught once,
    logged, and the tree regrown on the XLA histogram path.

    Honest scope: this impl returns un-materialized device arrays — an
    ASYNC execute-time kernel failure surfaces at the caller's next
    blocking pull, outside this net.  Compile-time rejection is the
    dominant real-world Mosaic failure class; the env escape hatches
    remain for the rest."""
    if not (use_pallas and _degrade.available(_degrade.HIST)):
        return _grow_fast_impl(*args, use_pallas=False, **kwargs)
    return _degrade.run_with_fallback(
        _degrade.HIST,
        lambda: _grow_fast_impl(*args, use_pallas=True, **kwargs),
        lambda: _grow_fast_impl(*args, use_pallas=False, **kwargs))
