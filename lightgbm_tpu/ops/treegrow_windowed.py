"""Windowed round-batched growth — the wide-regime (Epsilon-class) grower.

The round-batched grower (treegrow_fast.py) pays one FULL-N multi-leaf
histogram pass per round: at Epsilon shape (400k x 2000 x 255 bins, 255
leaves) that is ~26 passes x ~200 ms streaming all rows every time, even
though a round only needs histograms for its small children.  This grower
keeps rows PHYSICALLY grouped by leaf (reference: DataPartition's
[start, count) ranges — src/treelearner/data_partition.hpp) so each round
gathers ONLY the small-children rows into a power-of-two window and runs
the pass over that window: total row-touches drop from rounds*N toward
~N (docs/PERF_NOTES.md round-4 plan).

Round 7 structure — ONE donated jit dispatch per round, ZERO blocking
host syncs in steady state.  Rounds 1-6 ran a host loop with two jitted
phases (admit, then pass at a host-chosen static window size W) and one
blocking ``np.asarray`` between them: ~0.10-0.14 s/round of fixed admit
cost, 2 host dispatches and a blocking pull capped the grower at parity
with the full-pass grower (docs/NEXT.md round-6 lever 1).  Now:

* ``_round_fused`` traces admit AND pass in one jitted, donated body.
  The window size W is still jit-static (power-of-two-laddered to bound
  Mosaic compiles), but the host no longer syncs to learn it —
  W is PREDICTED, and the round body verifies on device that the real
  window fits (it always does, see the bound below); a breach skips the
  round and reports, so a wrong prediction costs a retried dispatch,
  never a wrong tree.
* the host pipelines 1 round deep: it dispatches round r+1 before
  resolving round r-1's 4-scalar info vector, which was copied back with
  ``copy_to_host_async`` one dispatch earlier — the read overlaps device
  compute of the in-flight round, so the device queue never drains
  (utils/sanitizer.py async_pull_* accounting).
* W prediction: every split's small child holds <= floor(cnt/2) of its
  leaf, and any leaf split within the next TWO rounds descends from a
  leaf live now — two same-parent descendants' small children sum to
  <= floor(parent_cnt/2) — so the sum of the top-(leaf_tile ∧ budget)
  values of floor(leaf_cnt/2) over live leaves bounds BOTH following
  rounds' window totals.  The round body emits that bound (``whint``)
  and the host ladders it two dispatches later: the factor-2 window
  ladder absorbs the slack.
* the row partition inside the fused body goes through
  ops/partition.py::partition_rows: the Pallas segment kernel
  (ops/partition_pallas.py) on TPU — touching only the split segments —
  with the O(N) XLA permutation as the CPU/fallback path.

The per-round dispatch/sync budget is an executable invariant: the
driver counts every dispatch and host pull through utils/sanitizer.py,
``LGBMTPU_DISPATCH_BUDGET=1`` makes it raise on a breach, and
tests/test_retrace.py pins "1 dispatch, 0 blocking syncs per round,
zero retraces" at fixed shape.

Scope (gated in models/gbdt.py): numerical AND (round 5) categorical
splits + EFB bundles; no forced splits / interaction constraints /
monotone constraints / CEGB-lazy — configurations outside this envelope
fall back to the full-pass rounds grower, which supports everything.
Quantized int8 training IS supported (it is the wide-regime TPU
default).

Round 14 (docs/DISTRIBUTED.md "Sharded fused rounds"): the fused round
also runs SPMD over the ICI mesh.  ``_round_fused`` takes an
``axis_name``; under shard_map each rank histograms its local row
shard's window and the leaf-histogram merge is ONE in-dispatch
collective (psum, or psum_scatter + owned-feature split search), with
the 5-scalar info vector collective-merged so the one-round-behind
host protocol stays rank-consistent.  The host loop is shared
(:func:`_run_fused_rounds`); the shard_map plumbing and the SPMD entry
live in parallel/data_parallel.py::grow_tree_windowed_data_parallel.
The 1-dispatch/0-sync budget pin holds PER RANK (single-controller: one
host dispatch fans out over the mesh; tests/test_retrace.py).

Round 20 (docs/DISTRIBUTED.md "Hierarchical merge"): with
``dcn_axis_name`` the round runs the TWO-LEVEL multi-slice merge — the
intra-slice histogram merge above rides ``axis_name`` (the ici axis)
UNCHANGED, the split search goes through the top-k feature election
(parallel/hierarchy.py::dcn_topk_best: slice-local vote, k-feature
histogram exchange, global election — the only histogram-shaped dcn
traffic), and the scalar protocol merges span both axes.  The nested
shard_map plumbing and the SPMD entry live in parallel/hierarchy.py::
grow_tree_windowed_hierarchical.

Round 15: the round executable's IR is ALSO pinned statically — the
jaxpr audit contracts ``windowed_round_float`` / ``_quantized`` /
``_sharded_psum`` / ``_sharded_scatter`` (analysis/contracts.py) trace
:func:`_round_fused` hermetically and verify the exact collective
sequence (one large merge per strategy, declared protocol spine), every
donated WState buffer consumable, and a f64/callback/transfer-free body
under a live-set budget.  Because :func:`_run_fused_rounds` receives the
dispatch as a closure, the AST rules (R1/R6/R13) cannot see into this
body — a change to the collectives or the donation structure here must
update the contract declarations next to their reasoning, or it fails
tests/test_jaxpr_audit.py (docs/ANALYSIS.md "Jaxpr audit layer").
"""

from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..obs import metrics as _obs
from ..obs import trace as _trace
from ..utils import degrade as _degrade
from ..utils import sanitizer as _san
from ..utils.guards import NonFiniteError
from .histogram import (histogram, histogram_multi,
                        histogram_multi_quantized, unbundle_hists)
from .partition import partition_rows
from .split import (BestSplit, SplitParams, leaf_output, KMIN_SCORE,
                    select_from_feature_best)
from .treegrow import TreeArrays, _empty_best, _set_best
from .treegrow_fast import _batched_best


class WState(NamedTuple):
    order: jnp.ndarray  # (N,) i32 — row ids physically grouped by leaf
    leaf_start: jnp.ndarray  # (L,) i32 — position of each leaf's range
    leaf_cnt: jnp.ndarray  # (L,) i32
    leaf_id: jnp.ndarray  # (N,) i32 — leaf per ROW (for score updates)
    hist: jnp.ndarray  # (L, 3, F, B) f32 — channel-first (ops/histogram.py)
    best: BestSplit
    leaf_sum_g: jnp.ndarray
    leaf_sum_h: jnp.ndarray
    leaf_count: jnp.ndarray
    leaf_depth: jnp.ndarray
    leaf_parent: jnp.ndarray
    leaf_side: jnp.ndarray
    num_leaves_cur: jnp.ndarray
    leaf_out: jnp.ndarray
    tree: TreeArrays


def _ladder(n: int, floor: int = 8192):
    """The W ladder for (n, floor): factor-4 steps to 128k, then
    factor-2, clamped to (and ending at) round_up(n, floor).  Each
    distinct W is a separate Mosaic compile of the fused round
    (1-5 min on this toolchain), so the ladder stays short — but r5
    WPROF showed early rounds with ~130-170k small-children rows landing
    on W=524288 (> N=400k itself!) under pure factor-4, paying 2.5-4x
    window overshoot exactly where passes are biggest.  Ladder for
    N=400k: 8k, 32k, 128k, 256k, 400k-pad (5 sizes)."""
    cap = -(-n // floor) * floor
    w = floor
    while True:
        yield min(w, cap)
        if w >= cap:
            return
        w *= 4 if w < 131072 else 2


def _window_size(x: int, n: int, floor: int = 8192) -> int:
    """Window size quantization: the first ladder rung covering ``x``."""
    for w in _ladder(n, floor):
        if w >= x:
            break
    return w


def _window_rung(w: int, n: int, floor: int = 8192) -> int:
    """Ladder index of window size ``w`` (0 = the floor rung) for the
    same (n, floor) the driver laddered with.  Span attribute only: the
    whint-overshoot question — does the bound climb the ladder earlier
    than the realized windows justify? — is answerable from one trace
    when every ``windowed_round`` span carries its rung and the
    transition that led to it (docs/NEXT.md round-11 queue)."""
    for r, c in enumerate(_ladder(n, floor)):
        if c >= w:
            break
    return r


def _split_tables(axis_name, merge, f_loc, num_bins_pf, missing_bin_pf,
                  feature_mask, categorical_mask, feature_contri,
                  feature_axis_name=None):
    """Per-rank feature tables for the split search.  Replicated (full-F)
    outside the owned-feature merge; when features are OWNED — under
    ``merge="scatter"`` (each rank holds its contiguous F/R block of the
    reduce-scattered histograms) or on a 2-D mesh (each feature-axis
    block holds complete histograms for its F/d_f slice by layout) — the
    rank searches only its block, so the tables are dynamic-sliced at
    this rank's offset along the OWNING axis.  One code path serves both
    ownership sources (reference: the data-parallel learner's per-rank
    feature ownership after ReduceScatter).  Returns the tables plus the
    rank's feature offset (None when features are not owned)."""
    own_axis = (feature_axis_name if feature_axis_name is not None
                else (axis_name if merge == "scatter" else None))
    if own_axis is None:
        return (num_bins_pf, missing_bin_pf, feature_mask, categorical_mask,
                feature_contri, None)
    f0 = jax.lax.axis_index(own_axis) * f_loc

    def sl(v):
        return (None if v is None
                else jax.lax.dynamic_slice_in_dim(v, f0, f_loc, 0))

    return (sl(num_bins_pf), sl(missing_bin_pf), sl(feature_mask),
            sl(categorical_mask), sl(feature_contri), f0)


def _merge_best(bb: BestSplit, axis_name, f0) -> BestSplit:
    """Owned-feature winner election (reference: SyncUpGlobalBestSplit —
    Allreduce of per-rank SplitInfo): globalize each rank's best feature
    index, pmax the gain, tie-break to the lowest-ranked owner (= lowest
    global feature block, matching the replicated argmax), and broadcast
    every winner field from the owner by psum-masking.  All in-dispatch:
    no host-loop collective, no extra dispatch."""
    if axis_name is None or f0 is None:
        return bb
    bb = bb._replace(feature=bb.feature + f0)
    ax_i = jax.lax.axis_index(axis_name)
    gmax = jax.lax.pmax(bb.gain, axis_name)
    cand = jnp.where(bb.gain >= gmax, ax_i, jnp.int32(2 ** 30))
    mine = jax.lax.pmin(cand, axis_name) == ax_i

    def bcast(x):
        m = mine.reshape(mine.shape + (1,) * (x.ndim - 1))
        if x.dtype == bool:
            return jax.lax.psum(
                jnp.where(m, x, False).astype(jnp.int32), axis_name) > 0
        return jax.lax.psum(jnp.where(m, x, jnp.zeros((), x.dtype)),
                            axis_name)

    return BestSplit(*[bcast(x) for x in bb])


@functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "num_bins", "max_depth", "params",
                     "leaf_tile", "W", "use_pallas", "quantize_bins",
                     "hist_precision", "has_cat", "pallas_partition",
                     "axis_name", "merge", "megakernel", "mk_interpret",
                     "dcn_axis_name", "dcn_top_k", "feature_axis_name"),
    donate_argnums=(0,),  # the 1.5 GB-at-Epsilon hist state threads
    # linearly through the host round loop; donation lets XLA update it in
    # place instead of alloc+copy per call (benchmarks/probe_r5_fixed.py)
)
def _round_fused(
    state: WState,
    bins_t: jnp.ndarray,  # (F, N) int16 — FIXED original row order
    grad: jnp.ndarray,  # (N,) f32 by ROW id (dequantized under quant)
    hess: jnp.ndarray,
    gq: Optional[jnp.ndarray],  # (N,) int8 or None
    hq: Optional[jnp.ndarray],
    quant_scale: Optional[jnp.ndarray],  # (3,) or None
    row_mask: jnp.ndarray,  # (N,) bool by ROW id
    num_bins_pf: jnp.ndarray,
    missing_bin_pf: jnp.ndarray,
    feature_mask: jnp.ndarray,
    rng_key: Optional[jnp.ndarray],
    feature_contri: Optional[jnp.ndarray],
    categorical_mask: Optional[jnp.ndarray] = None,
    efb_bins_t: Optional[jnp.ndarray] = None,  # (F_b, N) bundled matrix
    efb_gather: Optional[jnp.ndarray] = None,  # (F, B) -> flat (F_b*B)+pad
    efb_default: Optional[jnp.ndarray] = None,  # (F, B) bool default slots
    *,
    num_leaves: int,
    num_bins: int,
    max_depth: int,
    params: SplitParams,
    leaf_tile: int,
    W: int,
    use_pallas: bool,
    quantize_bins: int,
    hist_precision: str,
    has_cat: bool = False,
    pallas_partition: bool = False,
    axis_name: Optional[str] = None,
    merge: str = "psum",
    megakernel: bool = False,
    mk_interpret: bool = False,
    dcn_axis_name: Optional[str] = None,
    dcn_top_k: int = 0,
    feature_axis_name: Optional[str] = None,
):
    """One whole boosting round in one traced body: gain admission,
    segment partition, bookkeeping, window gather, multi-leaf pass,
    sibling subtraction, fresh-leaf search, next-window bound.

    Returns (state', info) with info = [k_acc, window_total, fits_W,
    whint, finite] (i32) — the ONLY values that ever reach the host, read
    asynchronously one round behind.  If the admitted splits' window
    would not fit the static W (impossible while the whint bound holds;
    kept as a device-verified safety net), the round applies NOTHING
    (bitwise-identical state passthrough) and reports fits_W=0 with the
    needed total so the host retries at a corrected W.

    With ``axis_name`` the body runs SPMD under shard_map over the mesh
    data axis (docs/DISTRIBUTED.md "Sharded fused rounds"): rows (and
    every row-indexed input) are this RANK's shard, the leaf-histogram
    merge is a single in-dispatch collective — ``psum`` with
    ``merge="psum"`` (replicated histograms, replicated split search) or
    ``psum_scatter`` with ``merge="scatter"`` (owned-feature split search
    + winner election, the ReduceScatter analogue) — and the 5-scalar
    info vector is collective-merged so every rank's host ladder sees
    identical values.  Physical row bookkeeping (order, leaf ranges,
    partition) stays rank-local; split decisions and tree arrays are
    replicated.

    With ``dcn_axis_name`` the body runs the TWO-LEVEL hierarchical merge
    (docs/DISTRIBUTED.md "Hierarchical merge"): ``axis_name`` is the
    intra-slice ICI axis — the histogram merge above runs UNCHANGED
    there, per slice — and the split search crosses slices DCN-frugally:
    each slice elects its ``dcn_top_k`` best features per candidate
    locally, only those k features' histograms + gain scalars travel the
    ``dcn`` axis (parallel/hierarchy.py::dcn_topk_best), and a global
    election picks the winner.  ``state.hist`` then holds SLICE-domain
    histograms (sibling subtraction works per slice), the scalar
    protocol merges (window election, info vector) span BOTH axes, and
    NO full-F histogram ever crosses DCN — pinned statically by jaxlint
    R17 and the jaxpr-audit ``dcn_max_bytes`` contract pin.

    With ``feature_axis_name`` the body runs over a 2-D (feature, row)
    mesh (docs/DISTRIBUTED.md "2-D sharding"): ``bins_t`` is this rank's
    (F/d_f, N/d_r) tile, rows and every row-indexed input are the ROW
    shard (replicated across the feature axis), and the per-leaf window
    histograms are COMPLETE for the owned feature block by layout — the
    histogram merge stays the row-axis collective alone, with ZERO
    collective over the feature axis (pinned by jaxlint R20 and the
    ``windowed_round_2d_*`` jaxpr contracts).  The split search reuses
    the scatter merge's owned-feature machinery (``_split_tables`` /
    ``_merge_best``) with the feature axis as the owning axis, and the
    winner's split decisions — computable only on the owner block, which
    alone holds the winner feature's bin column — are psum-broadcast
    over the feature axis (a (N,)-bool vector, the only feature-axis
    exchange in the round).  Row-domain sums stay on the row axes alone:
    rows are REPLICATED across the feature axis, so summing there would
    over-count by d_f.
    """
    L = num_leaves
    f = bins_t.shape[0]
    n = state.order.shape[0]
    # axis discipline: `sum_axes` are the ROW-sharding axes — row-domain
    # sums (window counts, leaf totals) merge there and ONLY there (rows
    # are replicated across the feature axis; summing there would
    # over-count by the feature-axis size).  `all_axes` adds the feature
    # axis for the IDEMPOTENT protocol merges (pmin/pmax agreement on
    # ok/total/whint/finite): under the two-level merge, window-child
    # election and the info vector are GLOBAL agreements (all slices, all
    # ranks, all feature blocks) while the histogram merge stays
    # per-slice on axis_name alone
    sum_axes = tuple(a for a in (axis_name, dcn_axis_name) if a is not None)
    all_axes = sum_axes + (
        (feature_axis_name,) if feature_axis_name is not None else ())

    def pall(x):  # cross-rank ROW-domain sum; identity on 1 device
        return jax.lax.psum(x, sum_axes) if sum_axes else x
    eps = KMIN_SCORE / 2
    idx = jnp.arange(L, dtype=jnp.int32)
    pos = jnp.arange(n, dtype=jnp.int32)

    # ---- admission (identical semantics to treegrow_fast round_body) ----
    gains = state.best.gain
    can = gains > eps
    if max_depth > 0:
        can = can & (state.leaf_depth < max_depth)
    budget = L - state.num_leaves_cur
    key = jnp.where(can, -gains, jnp.inf)
    srt = jnp.argsort(key)  # leaf at rank r (stable); doubles as inv_rank
    order_rank = jnp.argsort(srt)
    accept0 = can & (order_rank < jnp.minimum(budget, leaf_tile))
    s = state.best

    # ---- split decisions + segment geometry (pre-partition) ----
    # One fused gather instead of leaf_tile full-N column gathers (measured
    # ~240 ms/round the sequential way at 400k x 2000): slice the <= tile
    # accepted split features into a (tile, N) block (contiguous row reads
    # of bins_t), gather the row order ONCE along the position axis, and
    # select each position's own segment's row with an elementwise one-hot.
    seg_id = jnp.full((n,), -1, jnp.int32)
    seg_start = jnp.zeros((leaf_tile,), jnp.int32)
    seg_len = jnp.zeros((leaf_tile,), jnp.int32)
    ord_rows = state.order
    leaf_of_rank = srt[:leaf_tile]
    live_rk = accept0[leaf_of_rank]
    feats_rk = jnp.where(live_rk, s.feature[leaf_of_rank], 0)
    if feature_axis_name is not None:
        # 2-D mesh: bins_t holds only this rank's owned feature block, so
        # a winner column exists on exactly ONE feature block (feats_rk
        # are GLOBAL indices < f*d_f — every value has one owner).  Gather
        # the clipped local column here; the owner's decisions are
        # psum-broadcast over the feature axis after go_left is complete.
        f0_dec = jax.lax.axis_index(feature_axis_name) * f
        feats_loc = feats_rk - f0_dec
        own_rk = (feats_loc >= 0) & (feats_loc < f)
        cols = bins_t[jnp.clip(feats_loc, 0, f - 1)]  # (tile, N) by ROW id
    else:
        own_rk = None
        cols = bins_t[feats_rk]  # (tile, N) by ROW id
    colv = cols[:, ord_rows].astype(jnp.int32)  # (tile, N) by POSITION
    for r in range(leaf_tile):
        leaf_r = leaf_of_rank[r]
        live_r = live_rk[r]
        st, ct = state.leaf_start[leaf_r], state.leaf_cnt[leaf_r]
        seg_start = seg_start.at[r].set(jnp.where(live_r, st, 0))
        seg_len = seg_len.at[r].set(jnp.where(live_r, ct, 0))
        in_seg = live_r & (pos >= st) & (pos < st + ct)
        seg_id = jnp.where(in_seg, r, seg_id)
    sid = jnp.clip(seg_id, 0, leaf_tile - 1)
    oh = (jnp.arange(leaf_tile, dtype=jnp.int32)[:, None] == sid[None, :])
    # per-rank split scalars broadcast through the same one-hot — keeps
    # every (N,)-shaped op elementwise (no small-table row gathers)
    thr_rk = s.threshold_bin[leaf_of_rank][:, None]
    dl_rk = s.default_left[leaf_of_rank][:, None]
    mb_rk = missing_bin_pf[feats_rk][:, None]
    vals = jnp.sum(jnp.where(oh, colv, 0), axis=0)
    thr = jnp.sum(jnp.where(oh, thr_rk, 0), axis=0)
    mb = jnp.sum(jnp.where(oh, mb_rk, -1), axis=0) + (leaf_tile - 1)
    dl = jnp.any(oh & dl_rk, axis=0)
    go_left = jnp.where(vals == mb, dl, vals <= thr)
    if has_cat:
        # categorical winners route by bitset membership (reference:
        # Tree::CategoricalDecision — not-in-subset, incl. missing, goes
        # right); same per-rank one-hot select as the numeric scalars
        cat_rk = s.is_cat[leaf_of_rank][:, None]  # (tile, 1)
        cmask_rk = s.cat_mask[leaf_of_rank]  # (tile, B)
        go_cat_rk = jnp.take_along_axis(cmask_rk, colv, axis=1)  # (tile, N)
        in_cat = jnp.any(oh & cat_rk, axis=0)
        gc = jnp.any(oh & go_cat_rk, axis=0)
        go_left = jnp.where(in_cat, gc, go_left)
    if feature_axis_name is not None:
        # broadcast each position's decision from its segment's OWNER
        # feature block — the only block whose go_left gathered the real
        # winner column.  Exactly one block owns each slot's feature, so
        # the psum is a pure select; positions outside every live segment
        # take slot 0's value and are masked downstream (seg_id < 0).
        # This (N,)-bool vector is the round's ONLY feature-axis data
        # exchange — the histogram phase stays @feature-collective-free.
        own_pos = jnp.any(oh & own_rk[:, None], axis=0)
        go_left = jax.lax.psum(
            jnp.where(own_pos, go_left, False).astype(jnp.int32),
            feature_axis_name) > 0

    # ---- on-device window verification (the fused round's safety net) ----
    # per-slot left counts from the one-hot the decisions already built —
    # O(tile*N) elementwise, no extra cumsums; in-segment positions only
    in_seg_all = seg_id >= 0
    left_counts = jnp.sum(
        (oh & (go_left & in_seg_all)[None, :]).astype(jnp.int32), axis=1)
    # which child gets histogrammed directly must be the GLOBALLY smaller
    # one: under SPMD every rank contributes its local window rows to one
    # collective-merged histogram, so ranks must agree on the side even
    # when their local row splits disagree (single-device: pall is the
    # identity and this is exactly min(left, count-left))
    left_small = 2 * pall(left_counts) <= pall(seg_len)  # (tile,)
    win_cnt_rk = jnp.where(
        live_rk,
        jnp.where(left_small, left_counts, seg_len - left_counts), 0)
    total = jnp.sum(win_cnt_rk)  # LOCAL rows this rank must window
    ok = total <= W  # guaranteed by the whint bound; verified anyway
    if all_axes:
        # one rank breaching skips the round EVERYWHERE (the no-op must be
        # fleet-consistent), and the host's corrected W must cover the
        # worst rank — merged here so the async info vector is replicated
        ok = jax.lax.pmin(ok.astype(jnp.int32), all_axes) > 0
        total = jax.lax.pmax(total, all_axes)

    # everything applied below is gated on `ok`: a breached prediction
    # makes the whole round a bitwise no-op (state threads through
    # unchanged) and the host folds the correction into the next dispatch
    accept = accept0 & ok
    live_rk = live_rk & ok
    k_acc = jnp.sum(accept.astype(jnp.int32))
    acc_rank = jnp.where(accept, order_rank, L)
    node_of = state.num_leaves_cur - 1 + acc_rank
    right_of = state.num_leaves_cur + acc_rank
    seg_id = jnp.where(ok, seg_id, -1)
    seg_len_eff = jnp.where(ok, seg_len, 0)
    n_left_seg = jnp.where(live_rk, left_counts, 0)

    # ---- order-independent bookkeeping (leaf stats, slot maps, leaf
    # ranges, this round's windows), hoisted AHEAD of the partition: the
    # megakernel consumes the window geometry and — single-device — the
    # candidate stats inside the SAME kernel that partitions the rows.
    # Pure statement reordering for the legacy path (same value graph).
    right_pos = jnp.where(accept, right_of, 2 * L)

    def upd(arr, left_val, right_val):
        arr = jnp.where(accept, left_val, arr)
        return arr.at[right_pos].set(right_val, mode="drop")

    leaf_sum_g = upd(state.leaf_sum_g, s.left_sum_g, s.right_sum_g)
    leaf_sum_h = upd(state.leaf_sum_h, s.left_sum_h, s.right_sum_h)
    leaf_count = upd(state.leaf_count, s.left_count, s.right_count)
    depth_child = state.leaf_depth + 1
    leaf_depth = jnp.where(accept, depth_child, state.leaf_depth)
    leaf_depth = leaf_depth.at[right_pos].set(depth_child, mode="drop")
    leaf_parent = jnp.where(accept, node_of, state.leaf_parent)
    leaf_parent = leaf_parent.at[right_pos].set(
        jnp.where(accept, node_of, 0), mode="drop")
    leaf_side = jnp.where(accept, 0, state.leaf_side)
    leaf_side = leaf_side.at[right_pos].set(1, mode="drop")
    out_l = leaf_output(s.left_sum_g, s.left_sum_h, params)
    out_r = leaf_output(s.right_sum_g, s.right_sum_h, params)
    leaf_out = jnp.where(accept, out_l, state.leaf_out)
    leaf_out = leaf_out.at[right_pos].set(out_r, mode="drop")
    num_leaves_new = state.num_leaves_cur + k_acc

    # per-slot child maps stay LOCAL to the fused body (rounds 1-6 carried
    # them in WState to hand admit's result to the separate pass dispatch;
    # the fusion is what lets them die here).
    # The window child is chosen by PHYSICAL row counts — the same
    # quantity the gather pays for, the `ok` check verified against W,
    # and the whint bound promises about (rounds 1-6 chose by in-bag
    # counts, which under bagging can pick the physically BIGGER child
    # and desynchronize the window sum from the verified total; which
    # child is histogrammed directly vs recovered by subtraction does
    # not change the children's histograms).  Under SPMD the choice is
    # by GLOBAL counts (left_small above) so every rank windows the same
    # child and the collective merge sums one child's rows.
    left_smaller_rk = left_small  # (tile,) per slot, rank-consistent
    fresh = jnp.where(accept, True, jnp.zeros((L,), bool))
    fresh = fresh.at[right_pos].set(True, mode="drop")
    pos_r = jnp.where(accept, acc_rank, leaf_tile)
    slot_left = jnp.full((leaf_tile,), -1, jnp.int32).at[pos_r].set(
        idx, mode="drop")
    slot_right = jnp.full((leaf_tile,), -1, jnp.int32).at[pos_r].set(
        right_of, mode="drop")
    slot_small_left = live_rk & left_smaller_rk  # slot r == rank r

    # leaf ranges (the order-independent half of the range bookkeeping;
    # the per-row leaf ids need the partitioned order and follow it)
    leaf_start, leaf_cnt = state.leaf_start, state.leaf_cnt
    for r in range(leaf_tile):
        leaf_r = srt[r]
        live_r = accept[leaf_r]
        st, ct = state.leaf_start[leaf_r], state.leaf_cnt[leaf_r]
        lc = n_left_seg[r]
        rp = jnp.clip(right_of[leaf_r], 0, L - 1)
        leaf_start = jnp.where(
            live_r, leaf_start.at[rp].set(st + lc), leaf_start)
        leaf_cnt = jnp.where(
            live_r, leaf_cnt.at[leaf_r].set(lc).at[rp].set(ct - lc), leaf_cnt)

    # windows: per admission rank, the SMALL child's [start, cnt)
    win_start = jnp.zeros((leaf_tile,), jnp.int32)
    win_cnt = jnp.zeros((leaf_tile,), jnp.int32)
    for r in range(leaf_tile):
        leaf_r = srt[r]
        live_r = accept[leaf_r]
        sm = jnp.where(left_smaller_rk[r], leaf_r,
                       jnp.clip(right_of[leaf_r], 0, L - 1))
        win_start = win_start.at[r].set(jnp.where(live_r, leaf_start[sm], 0))
        win_cnt = win_cnt.at[r].set(jnp.where(live_r, leaf_cnt[sm], 0))

    # candidate slot maps (shared by the sibling recovery below and the
    # megakernel's fused tail)
    active = slot_left >= 0  # (tile,)
    sl = jnp.clip(slot_left, 0, L - 1)
    sr = jnp.clip(slot_right, 0, L - 1)
    parent_hists = state.hist[sl]  # (tile, 3, F, B)
    cand = jnp.concatenate([sl, sr])
    cand_ok = jnp.concatenate([active, active])
    ci = jnp.where(cand_ok, cand, 0)

    # ---- partition the physical row order at segment boundaries ----
    mk_tail = megakernel and axis_name is None
    if megakernel:
        # THE round megakernel (ops/round_pallas.py): partition movements,
        # the one-sweep window histogram, and (single-device) the on-core
        # split-gain reduction, all in ONE Pallas call.  Same raw-order
        # contract as the partition kernel: merge untouched positions
        # back.  Under SPMD the kernel stops after the histograms so the
        # single in-dispatch collective merge below stays UNCHANGED.
        from .round_pallas import round_megakernel

        if efb_bins_t is not None or rng_key is not None:
            raise ValueError(
                "megakernel round outside its envelope (EFB bundles / "
                "per-node rng) — the entry gate must fall back to the "
                "three-pass round")
        cand_tab = (jnp.stack([
            leaf_sum_g[ci], leaf_sum_h[ci], leaf_count[ci],
            leaf_depth[ci].astype(jnp.float32), leaf_out[ci]])
            if mk_tail else None)
        mk_out = round_megakernel(
            bins_t, ord_rows, go_left, grad, hess, row_mask,
            seg_start, seg_len_eff, n_left_seg, win_start, win_cnt,
            slot_small_left.astype(jnp.int32),
            parent_hists if mk_tail else None,
            cand_tab,
            num_bins_pf if mk_tail else None,
            missing_bin_pf if mk_tail else None,
            feature_mask if mk_tail else None,
            categorical_mask if mk_tail else None,
            feature_contri if mk_tail else None,
            num_bins=num_bins, leaf_tile=leaf_tile, params=params,
            fuse_tail=mk_tail, has_cat=has_cat, interpret=mk_interpret)
        new_order = jnp.where(seg_id >= 0, mk_out[0], ord_rows)
    else:
        mk_out = None
        new_order, _ = partition_rows(
            ord_rows, seg_id, seg_start, seg_len_eff, go_left,
            use_pallas=pallas_partition)

    # ---- per-row leaf ids (needs the partitioned order) ----
    lid_pos = state.leaf_id[new_order]  # leaf per POSITION (pre-split)
    for r in range(leaf_tile):
        leaf_r = srt[r]
        live_r = accept[leaf_r]
        st, ct = state.leaf_start[leaf_r], state.leaf_cnt[leaf_r]
        lc = n_left_seg[r]
        in_right = live_r & (pos >= st + lc) & (pos < st + ct)
        lid_pos = jnp.where(in_right, right_of[leaf_r], lid_pos)
    leaf_id = jnp.zeros_like(state.leaf_id).at[new_order].set(lid_pos)

    # ---- tree arrays (identical bookkeeping to round_body) ----
    t = state.tree
    parent_out = state.leaf_out
    old_parent, old_side = state.leaf_parent, state.leaf_side
    repoint_l = accept & (old_parent >= 0) & (old_side == 0)
    repoint_r = accept & (old_parent >= 0) & (old_side == 1)
    safe_node = jnp.clip(node_of, 0, L - 2)
    lc_t = t.left_child.at[jnp.where(repoint_l, old_parent, 2 * L)].set(
        safe_node, mode="drop")
    rc_t = t.right_child.at[jnp.where(repoint_r, old_parent, 2 * L)].set(
        safe_node, mode="drop")
    node_pos = jnp.where(accept, node_of, 2 * L)
    lc_t = lc_t.at[node_pos].set(-idx - 1, mode="drop")
    rc_t = rc_t.at[node_pos].set(-right_of - 1, mode="drop")
    tree = t._replace(
        num_leaves=state.num_leaves_cur + k_acc,
        split_feature=t.split_feature.at[node_pos].set(s.feature, mode="drop"),
        threshold_bin=t.threshold_bin.at[node_pos].set(s.threshold_bin, mode="drop"),
        default_left=t.default_left.at[node_pos].set(s.default_left, mode="drop"),
        split_gain=t.split_gain.at[node_pos].set(s.gain, mode="drop"),
        left_child=lc_t,
        right_child=rc_t,
        internal_value=t.internal_value.at[node_pos].set(parent_out, mode="drop"),
        internal_weight=t.internal_weight.at[node_pos].set(state.leaf_sum_h, mode="drop"),
        internal_count=t.internal_count.at[node_pos].set(state.leaf_count, mode="drop"),
        is_cat=t.is_cat.at[node_pos].set(s.is_cat, mode="drop"),
        cat_mask=t.cat_mask.at[node_pos].set(s.cat_mask, mode="drop"),
    )

    best = state.best._replace(
        gain=jnp.where(fresh, jnp.full((L,), KMIN_SCORE, jnp.float32),
                       state.best.gain))

    # ---- pass: window histograms -> sibling subtraction -> fresh-leaf
    # split search (same trace, no dispatch).  Three sources for the
    # child histograms: the megakernel's fused tail (everything already
    # computed in-kernel), the megakernel's histogram-only output (the
    # SPMD case: the collective merge below must stay the round's single
    # large in-dispatch collective), or the legacy gather + multi-leaf
    # pass (three bin sweeps — docs/PERF_NOTES.md round 16).
    mk_bests = None
    if megakernel and mk_tail:
        _, left_hists, right_hists, mk_bests = mk_out
    else:
        if megakernel:
            fresh_hists = mk_out[1]
        else:
            offs = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                    jnp.cumsum(win_cnt).astype(jnp.int32)])
            w_total = offs[-1]
            aw = jnp.arange(W, dtype=jnp.int32)
            # slot per window element: number of boundaries <= position
            slot_of = jnp.sum(
                (aw[:, None] >= offs[1:][None, :]).astype(jnp.int32), axis=1)
            slot_of = jnp.clip(slot_of, 0, leaf_tile - 1)
            wpos = win_start[slot_of] + (aw - offs[slot_of])
            valid = aw < w_total
            wpos = jnp.where(valid, wpos, 0)
            rows = new_order[wpos]  # (W,) row ids

            # feature-major window gather (a row gather on the (N, F)
            # layout measured ~909 ms at 1M x 28; column slices of (F, N)
            # are ~20x cheaper), then ONE contiguous transpose for the
            # row-major kernel — a lane->sublane reshape per feature
            # inside a feature-major kernel blew the 16M scoped-VMEM
            # budget (measured 19.6M)
            hist_src = bins_t if efb_bins_t is None else efb_bins_t
            sub_bins = hist_src[:, rows].T  # (W, F) or (W, F_b)
            mask_w = row_mask[rows] & valid

            def unbundle(h):
                if efb_gather is None:
                    return h
                return unbundle_hists(h, efb_gather, efb_default, f,
                                      num_bins)

            if quantize_bins and use_pallas:
                hi = histogram_multi_quantized(
                    sub_bins, gq[rows], hq[rows], mask_w, slot_of, 0,
                    leaf_tile, num_bins)
                fresh_hists = unbundle(hi).astype(
                    jnp.float32) * quant_scale[:, None, None]
            elif use_pallas:
                fresh_hists = unbundle(histogram_multi(
                    sub_bins, grad[rows], hess[rows], mask_w, slot_of, 0,
                    leaf_tile, num_bins, precision=hist_precision))
            else:
                # CPU/test fallback: masked scatter per slot over the window
                g_w, h_w = grad[rows], hess[rows]

                def one(sl_):
                    m = (mask_w & (slot_of == sl_)).astype(jnp.float32)
                    return histogram(sub_bins, g_w, h_w, m, num_bins,
                                     strategy="scatter")
                fresh_hists = unbundle(
                    jax.vmap(one)(jnp.arange(leaf_tile, dtype=jnp.int32)))

        # ---- in-dispatch cross-rank histogram merge ----
        # each rank histogrammed ONLY its local shard of the window; the
        # merge is one collective INSIDE the already-donated dispatch — no
        # host-loop collective, no second dispatch (reference:
        # DataParallelTreeLearner's per-split ReduceScatter, paid here
        # once per ROUND).  "psum" leaves every rank with the global
        # (tile, 3, F, B) block; "scatter" leaves each rank the global
        # block for its OWNED F/R feature slice only (half the merge
        # bytes, split search parallelized over F).  The megakernel path
        # feeds its local histograms through this SAME merge unchanged.
        if axis_name is not None:
            if merge == "scatter":
                fresh_hists = jax.lax.psum_scatter(
                    fresh_hists, axis_name, scatter_dimension=2, tiled=True)
            else:
                fresh_hists = jax.lax.psum(fresh_hists, axis_name)

        # COMPACT sibling recovery (round 5, mirrors treegrow_fast):
        # gather the <= tile parent hists from the left-child slots,
        # subtract, scatter both children once — O(tile) state traffic
        big_hists = parent_hists - fresh_hists
        sml = slot_small_left[:, None, None, None]
        left_hists = jnp.where(sml, fresh_hists, big_hists)
        right_hists = jnp.where(sml, big_hists, fresh_hists)

    lpos = jnp.where(active, sl, 2 * L)
    rpos = jnp.where(active, sr, 2 * L)
    hist = state.hist.at[lpos].set(left_hists, mode="drop").at[rpos].set(
        right_hists, mode="drop")

    # fresh-leaf split search directly on the compact child hists; under
    # merge="scatter" each rank searches its owned feature block and the
    # winner is elected + broadcast in-dispatch (_merge_best).  With the
    # megakernel tail the per-feature reduction already happened ON-CORE
    # (ops/split.py::reduce_plane_per_feature inside the kernel); only
    # the O(F) cross-feature selection runs here.
    node_ids = jnp.clip(leaf_parent, 0, None) * 2 + leaf_side + 1
    cand_hists = jnp.concatenate([left_hists, right_hists], axis=0)
    if mk_bests is not None:
        def _sel(fbx, ch, pg, ph, pc):
            return select_from_feature_best(
                fbx, pg, ph, pc, categorical_mask=categorical_mask,
                cand_hist=ch, missing_bin_per_feature=missing_bin_pf,
                params=params, num_bins=num_bins)

        bb = jax.vmap(_sel)(mk_bests, cand_hists, leaf_sum_g[ci],
                            leaf_sum_h[ci], leaf_count[ci])
    else:
        nb_l, mb_l, fm_l, cm_l, fc_l, f0 = _split_tables(
            axis_name, merge, state.hist.shape[2], num_bins_pf,
            missing_bin_pf, feature_mask, categorical_mask, feature_contri,
            feature_axis_name=feature_axis_name)
        if dcn_axis_name is not None:
            # two-level split search (parallel/hierarchy.py): the cand
            # hists above are SLICE-domain (merged over axis_name only);
            # each slice votes its top-k features per candidate, only k
            # features' histograms + gain scalars cross the dcn axis, and
            # the winner is elected on the k-feature GLOBAL histograms —
            # the PV-Tree/voting-parallel route, in-dispatch
            from ..parallel.hierarchy import dcn_topk_best

            bb = dcn_topk_best(
                cand_hists, leaf_sum_g[ci], leaf_sum_h[ci], leaf_count[ci],
                nb_l, mb_l, fm_l, cm_l, fc_l,
                params=params, top_k=dcn_top_k, dcn_axis=dcn_axis_name,
                depth=leaf_depth[ci], parent_out=leaf_out[ci])
        else:
            bb = _batched_best(
                cand_hists, leaf_sum_g[ci], leaf_sum_h[ci],
                leaf_count[ci], nb_l, mb_l, params,
                fm_l, cm_l, None, None,
                jnp.full((2 * leaf_tile,), -jnp.inf, jnp.float32),
                jnp.full((2 * leaf_tile,), jnp.inf, jnp.float32),
                None, node_ids[ci], rng_key,
                depth=leaf_depth[ci], parent_out=leaf_out[ci],
                feature_contri=fc_l,
            )
        bb = _merge_best(
            bb, feature_axis_name if feature_axis_name is not None
            else axis_name, f0)
    scatter_pos = jnp.where(cand_ok, cand, 2 * L)

    def merge(old, new):
        return old.at[scatter_pos].set(new, mode="drop")

    best = BestSplit(*[merge(o, nw) for o, nw in zip(best, bb)])

    # ---- next-window bound for the host's ladder prediction ----
    # any leaf split within the next two rounds descends from a leaf live
    # NOW; the small children under one live ancestor sum to
    # <= floor(ancestor_cnt/2), and distinct split leaves have distinct
    # live ancestors — so the top-(tile ∧ budget) floor(cnt/2) over live
    # leaves bounds both following window totals.  Exact enough that the
    # factor-2 ladder absorbs the slack; always an over- (never under-)
    # estimate, so the on-device `ok` check cannot trip while the host
    # ladders this value.
    #
    # SPMD variant: the halving argument is GLOBAL (the window child is
    # the globally smaller one), but W bounds each rank's LOCAL window —
    # and a globally-small child can hold up to ALL of one rank's rows of
    # its ancestor.  The sound local bound drops the halving: top-(tile ∧
    # budget) local leaf_cnt over live leaves covers both following
    # rounds (window children under one live ancestor are disjoint row
    # subsets of it).  pmax makes the laddered W cover the worst rank.
    live_next = idx < num_leaves_new
    half_cnt = jnp.where(
        live_next, leaf_cnt // 2 if axis_name is None else leaf_cnt, 0)
    k_top = min(leaf_tile, L)
    top_halves = jax.lax.top_k(half_cnt, k_top)[0]
    budget_next = jnp.maximum(L - num_leaves_new, 0)
    whint = jnp.sum(jnp.where(
        jnp.arange(k_top, dtype=jnp.int32) < jnp.minimum(
            budget_next, leaf_tile),
        top_halves, 0))
    if all_axes:
        whint = jax.lax.pmax(whint, all_axes)

    state = WState(
        order=new_order, leaf_start=leaf_start, leaf_cnt=leaf_cnt,
        leaf_id=leaf_id, hist=hist, best=best,
        leaf_sum_g=leaf_sum_g, leaf_sum_h=leaf_sum_h, leaf_count=leaf_count,
        leaf_depth=leaf_depth, leaf_parent=leaf_parent, leaf_side=leaf_side,
        num_leaves_cur=num_leaves_new, leaf_out=leaf_out, tree=tree,
    )
    # ---- non-finite guard rail (docs/ROBUSTNESS.md layer 2) ----
    # O(L) reductions over stats this round already produced, folded into
    # the SAME info vector the host reads one round behind: the guard
    # costs zero extra dispatches and zero blocking syncs.  Dead slots
    # hold zeros / KMIN, so any non-finite value is corruption that
    # entered through the gradients/hessians or split accumulation.
    finite = (jnp.isfinite(leaf_sum_g).all()
              & jnp.isfinite(leaf_sum_h).all()
              & jnp.isfinite(leaf_out).all()
              & ~jnp.isnan(best.gain).any())
    if all_axes:
        # replicated by construction (split stats come from the merged
        # histograms), but pmin pins rank consistency as an invariant —
        # the host's one-round-behind guard must never see ranks disagree
        finite = jax.lax.pmin(finite.astype(jnp.int32), all_axes) > 0
    info = jnp.stack([
        k_acc, total, ok.astype(jnp.int32), whint.astype(jnp.int32),
        finite.astype(jnp.int32),
    ]).astype(jnp.int32)
    return state, info


@functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "num_bins", "params", "leaf_tile",
                     "use_pallas", "quantize_bins", "hist_precision",
                     "stochastic_rounding", "axis_name", "merge",
                     "dcn_axis_name", "dcn_top_k", "feature_axis_name"),
)
def _w_init(
    bins_t, grad, hess, row_mask, sample_weight, num_bins_pf,
    missing_bin_pf, feature_mask, rng_key, quant_key, feature_contri,
    categorical_mask=None, efb_bins_t=None, efb_gather=None,
    efb_default=None,
    *,
    num_leaves: int,
    num_bins: int,
    params: SplitParams,
    leaf_tile: int,
    use_pallas: bool,
    quantize_bins: int,
    hist_precision: str,
    stochastic_rounding: bool,
    axis_name: Optional[str] = None,
    merge: str = "psum",
    dcn_axis_name: Optional[str] = None,
    dcn_top_k: int = 0,
    feature_axis_name: Optional[str] = None,
):
    """Root state: quantize gradients, run the one full-N pass, seed best.

    Under ``axis_name`` (SPMD, see :func:`_round_fused`): rows are this
    rank's shard, quantization scales are pmaxed so every rank encodes
    int8 gradients on the same grid, and the root histogram is merged
    with the same collective the rounds use.  With ``dcn_axis_name`` the
    histogram merge stays per-slice (axis_name only) and the root split
    election goes through the same two-level top-k exchange the rounds
    use; scalar totals and quant scales merge across BOTH axes.  With
    ``feature_axis_name`` (2-D mesh) the root histogram over the local
    (F/d_f, N/d_r) tile is already complete for the owned feature block
    after the row-axis merge — ZERO feature-axis collectives — and the
    root election runs the owned-feature search; row-domain totals merge
    over the row axes only (rows are replicated across feature blocks)
    while the quant-scale pmax spans every axis (idempotent: pins
    cross-block grid consistency)."""
    f, n = bins_t.shape
    L = num_leaves
    grad = grad.astype(jnp.float32) * sample_weight
    hess = hess.astype(jnp.float32) * sample_weight
    grad_true, hess_true = grad, hess
    sum_axes = tuple(a for a in (axis_name, dcn_axis_name) if a is not None)
    all_axes = sum_axes + (
        (feature_axis_name,) if feature_axis_name is not None else ())

    def pmaxg(x):
        return jax.lax.pmax(x, all_axes) if all_axes else x

    gq = hq = quant_scale = None
    if quantize_bins:
        half = max(quantize_bins // 2, 1)
        inbag = row_mask.astype(jnp.float32)
        g_scale = jnp.maximum(
            pmaxg(jnp.max(jnp.abs(grad) * inbag)) / half, 1e-30)
        h_scale = jnp.maximum(
            pmaxg(jnp.max(hess * inbag)) / quantize_bins, 1e-30)
        gs, hs = grad / g_scale, hess / h_scale
        if stochastic_rounding:
            kg, kh = jax.random.split(
                quant_key if quant_key is not None else jax.random.PRNGKey(0))
            gqf = jnp.floor(gs + jax.random.uniform(kg, gs.shape))
            hqf = jnp.floor(hs + jax.random.uniform(kh, hs.shape))
        else:
            gqf, hqf = jnp.round(gs), jnp.round(hs)
        gq = jnp.clip(gqf, -127, 127).astype(jnp.int8)
        hq = jnp.clip(hqf, 0, 127).astype(jnp.int8)
        grad = gq.astype(jnp.float32) * g_scale
        hess = hq.astype(jnp.float32) * h_scale
        quant_scale = jnp.stack([g_scale, h_scale, jnp.float32(1.0)])

    hist_src = (bins_t if efb_bins_t is None else efb_bins_t).T

    def unbundle1(h):
        if efb_gather is None:
            return h[0]
        return unbundle_hists(h, efb_gather, efb_default, f, num_bins)[0]

    if quantize_bins and use_pallas:
        hist0 = unbundle1(histogram_multi_quantized(
            hist_src, gq, hq, row_mask, jnp.zeros((n,), jnp.int32), 0, 1,
            num_bins)).astype(jnp.float32) * quant_scale[:, None, None]
    elif use_pallas:
        hist0 = unbundle1(histogram_multi(
            hist_src, grad, hess, row_mask, jnp.zeros((n,), jnp.int32), 0, 1,
            num_bins, precision=hist_precision))
    else:
        hist0 = unbundle1(histogram(
            hist_src, grad, hess, row_mask.astype(jnp.float32), num_bins,
            strategy="scatter")[None])
    # totals from feature 0 of the LOCAL hist, summed across ranks (a
    # 3-scalar psum); the histogram itself merges with the round's
    # collective — psum (replicated) or psum_scatter (owned F/R slice)
    sum0 = jnp.sum(hist0[:, 0, :], axis=1)  # totals from feature 0: (3,)
    if sum_axes:  # row-domain: every feature block's local feature 0
        # already holds ALL local rows (each row lands in one bin per
        # feature, padded dead features in bin 0) — summing the feature
        # axis too would over-count by d_f
        sum0 = jax.lax.psum(sum0, sum_axes)
    if axis_name is not None:
        if merge == "scatter":
            hist0 = jax.lax.psum_scatter(
                hist0, axis_name, scatter_dimension=1, tiled=True)
        else:
            hist0 = jax.lax.psum(hist0, axis_name)
    g0, h0, c0 = sum0[0], sum0[1], sum0[2]
    leaf_out0 = leaf_output(g0, h0, params)

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
    nb_l, mb_l, fm_l, cm_l, fc_l, f0_off = _split_tables(
        axis_name, merge, hist0.shape[1], num_bins_pf, missing_bin_pf,
        feature_mask, categorical_mask, feature_contri,
        feature_axis_name=feature_axis_name)
    if dcn_axis_name is not None:
        from ..parallel.hierarchy import dcn_topk_best

        bb0 = dcn_topk_best(
            hist0[None], jnp.asarray([g0]), jnp.asarray([h0]),
            jnp.asarray([c0]), nb_l, mb_l, fm_l, cm_l, fc_l,
            params=params, top_k=dcn_top_k, dcn_axis=dcn_axis_name,
            depth=jnp.asarray([0.0], jnp.float32),
            parent_out=jnp.asarray([leaf_out0]))
    else:
        bb0 = _batched_best(
            hist0[None], jnp.asarray([g0]), jnp.asarray([h0]),
            jnp.asarray([c0]), nb_l, mb_l, params,
            fm_l, cm_l, None, None,
            jnp.asarray([-jnp.inf], jnp.float32),
            jnp.asarray([jnp.inf], jnp.float32),
            None, jnp.asarray([0], jnp.int32), rng_key,
            depth=jnp.asarray([0.0], jnp.float32),
            parent_out=jnp.asarray([leaf_out0]),
            feature_contri=fc_l,
        )
    best0 = _set_best(
        _empty_best(L, num_bins), jnp.asarray(0),
        jax.tree.map(lambda a: a[0], _merge_best(
            bb0, feature_axis_name if feature_axis_name is not None
            else axis_name, f0_off)),
    )
    state = WState(
        order=jnp.arange(n, dtype=jnp.int32),
        leaf_start=jnp.zeros((L,), jnp.int32),
        leaf_cnt=jnp.zeros((L,), jnp.int32).at[0].set(n),
        leaf_id=jnp.zeros((n,), jnp.int32),
        hist=jnp.zeros((L, 3, hist0.shape[1], num_bins),
                       jnp.float32).at[0].set(hist0),
        best=best0,
        leaf_sum_g=jnp.zeros((L,), jnp.float32).at[0].set(g0),
        leaf_sum_h=jnp.zeros((L,), jnp.float32).at[0].set(h0),
        leaf_count=jnp.zeros((L,), jnp.float32).at[0].set(c0),
        leaf_depth=jnp.zeros((L,), jnp.int32),
        leaf_parent=jnp.full((L,), -1, jnp.int32),
        leaf_side=jnp.zeros((L,), jnp.int32),
        num_leaves_cur=jnp.asarray(1, jnp.int32),
        leaf_out=jnp.zeros((L,), jnp.float32).at[0].set(leaf_out0),
        tree=tree0,
    )
    return state, grad, hess, gq, hq, quant_scale, grad_true, hess_true


@functools.partial(jax.jit, static_argnames=("params", "quant_renew",
                                             "axis_name", "dcn_axis_name",
                                             "feature_axis_name"))
def _w_finalize(state: WState, grad_true, hess_true, row_mask,
                *, params: SplitParams, quant_renew: bool,
                axis_name: Optional[str] = None,
                dcn_axis_name: Optional[str] = None,
                feature_axis_name: Optional[str] = None):
    # `feature_axis_name` is accepted for uniform static threading on the
    # 2-D mesh but contributes NO collective: every sum here is
    # row-domain (rows are replicated across feature blocks — summing
    # the feature axis would over-count by d_f) and the inputs are
    # already feature-replicated.
    L = state.leaf_out.shape[0]
    sum_axes = tuple(a for a in (axis_name, dcn_axis_name) if a is not None)
    if quant_renew:
        mrow = row_mask.astype(jnp.float32)
        Gt = jnp.zeros((L,), jnp.float32).at[state.leaf_id].add(
            grad_true * mrow)
        Ht = jnp.zeros((L,), jnp.float32).at[state.leaf_id].add(
            hess_true * mrow)
        if sum_axes:  # true-gradient renewal sums the ROW axes
            Gt = jax.lax.psum(Gt, sum_axes)
            Ht = jax.lax.psum(Ht, sum_axes)
        leaf_value = leaf_output(Gt, Ht, params)
    else:
        leaf_value = leaf_output(state.leaf_sum_g, state.leaf_sum_h, params)
    active = jnp.arange(L, dtype=jnp.int32) < state.num_leaves_cur
    tree = state.tree._replace(
        num_leaves=state.num_leaves_cur,
        leaf_value=jnp.where(active, leaf_value, 0.0),
        leaf_weight=jnp.where(active, state.leaf_sum_h, 0.0),
        leaf_count=jnp.where(active, state.leaf_count, 0.0),
        leaf_sum_g=jnp.where(active, state.leaf_sum_g, 0.0),
        leaf_depth=state.leaf_depth,
    )
    return tree, state.leaf_id


def _grow_windowed_impl(
    bins_t: jnp.ndarray,  # (F, N) int16 feature-major
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    row_mask: jnp.ndarray,
    sample_weight: jnp.ndarray,
    feature_mask: jnp.ndarray,
    num_bins_pf: jnp.ndarray,
    missing_bin_pf: jnp.ndarray,
    rng_key: Optional[jnp.ndarray] = None,
    quant_key: Optional[jnp.ndarray] = None,
    feature_contri: Optional[jnp.ndarray] = None,
    categorical_mask: Optional[jnp.ndarray] = None,
    efb_bins_t: Optional[jnp.ndarray] = None,  # (F_b, N) bundled matrix
    efb_gather: Optional[jnp.ndarray] = None,
    efb_default: Optional[jnp.ndarray] = None,
    *,
    num_leaves: int,
    num_bins: int,
    max_depth: int = -1,
    params: SplitParams = SplitParams(),
    leaf_tile: int = 16,
    hist_precision: str = "f32",
    use_pallas: bool = True,
    quantize_bins: int = 0,
    stochastic_rounding: bool = True,
    quant_renew: bool = False,
    stats: Optional[dict] = None,
    guard_label: str = "",
    megakernel: bool = False,
    mk_interpret: bool = False,
) -> tuple[TreeArrays, jnp.ndarray]:
    """Host-driven windowed growth; returns (tree, leaf_id per row).

    One donated dispatch per round, zero blocking host syncs in steady
    state (module docstring).  ``stats``, when given, receives the
    driver's dispatch/sync ledger: {rounds, dispatches, host_syncs,
    async_resolves, retries, windows} — what tests/test_retrace.py pins.
    """
    common = dict(num_leaves=num_leaves, num_bins=num_bins, params=params,
                  leaf_tile=leaf_tile)
    state, g_d, h_d, gq, hq, qs, g_true, h_true = _w_init(
        bins_t, grad, hess, row_mask, sample_weight, num_bins_pf,
        missing_bin_pf, feature_mask, rng_key, quant_key, feature_contri,
        categorical_mask, efb_bins_t, efb_gather, efb_default,
        use_pallas=use_pallas, quantize_bins=quantize_bins,
        hist_precision=hist_precision,
        stochastic_rounding=stochastic_rounding, **common)

    n = bins_t.shape[1]
    if megakernel and _obs.enabled():
        # host-side static — zero extra dispatches/syncs (the budget pin
        # in tests/test_retrace.py runs with the megakernel ON)
        _obs.counter("train_megakernel_trees_total").inc()

    def round_fn(st, W):
        st, info = _round_fused(
            st, bins_t, g_d, h_d, gq, hq, qs, row_mask,
            num_bins_pf, missing_bin_pf, feature_mask, rng_key,
            feature_contri, categorical_mask,
            efb_bins_t, efb_gather, efb_default,
            max_depth=max_depth, W=W, use_pallas=use_pallas,
            quantize_bins=quantize_bins, hist_precision=hist_precision,
            has_cat=categorical_mask is not None,
            pallas_partition=PALLAS_PARTITION, megakernel=megakernel,
            mk_interpret=mk_interpret, **common)
        return st, info

    # round 1 needs no feedback: a round's window (the small children)
    # can never exceed floor(N/2) rows, whatever it admits
    state = _run_fused_rounds(
        round_fn, state, n_ladder=n,
        w_first=_window_size(max(n // 2, 1), n),
        num_leaves=num_leaves, stats=stats, guard_label=guard_label)

    return _w_finalize(state, g_true, h_true, row_mask, params=params,
                       quant_renew=bool(quant_renew and quantize_bins))


def _run_fused_rounds(round_fn, state, *, n_ladder: int, w_first: int,
                      num_leaves: int, stats: Optional[dict],
                      guard_label: str, floor: int = 8192):
    """The one-dispatch/zero-sync round protocol (module docstring),
    factored out of :func:`_grow_windowed_impl` so the SPMD driver
    (parallel/data_parallel.py::grow_tree_windowed_data_parallel) runs
    the IDENTICAL host loop — same W ladder, same one-round-behind async
    info reads, same drain, same dispatch/sync accounting and telemetry —
    over a shard_mapped round.  ``round_fn(state, W) -> (state', info)``
    must be a single donated dispatch; ``n_ladder`` is the row count the
    W ladder quantizes against (the LOCAL shard size under SPMD: W bounds
    each rank's own window).  ``floor`` is the ladder's minimum rung:
    8192 per ROUND for the solo/SPMD growers (compile-cost bound — each W
    is its own Mosaic compile), but a BATCHED round (treegrow_fleet.py)
    quantizes the floor on the total live window across the batch, so
    its per-lane floor shrinks as 8192/B; W padding is row masking only,
    so the grown trees are bitwise invariant to the floor."""
    prof = os.environ.get("LGBMTPU_WPROF") == "1"
    enforce = os.environ.get("LGBMTPU_DISPATCH_BUDGET") == "1"
    n = n_ladder
    W = w_first
    pending: list = []  # dispatched rounds whose info is still in flight
    n_leaves = 1
    rounds = 0
    retries = 0
    windows: list = []
    import time as _time
    t_open = _time.perf_counter()
    # span anchor: per-round spans close ONLY at the accounted async-info
    # resolves below (the round-7 protocol's existing sync points), so the
    # intervals are device-inclusive without adding a single pull — the
    # pattern jaxlint R10 pins for span closes
    t_resolve_prev: Optional[float] = None
    rung_prev: Optional[int] = None  # last resolved round's ladder rung
    t_last = _time.perf_counter() if prof else 0.0
    # every productive round admits >= 1 split, reads lag 1 round, plus
    # defensive headroom for retried (skipped) rounds
    max_rounds = 2 * num_leaves + 4
    converged = False
    resolved = 0  # rounds whose info the host has read (lags `rounds` by 1)
    counter = _san.DispatchCounter()
    counter.__enter__()
    try:
        while rounds < max_rounds:
            _san.record_dispatch()
            state, info_d = round_fn(state, W)
            _san.async_pull_start(info_d)
            pending.append(info_d)
            rounds += 1
            windows.append(W)
            if len(pending) < 2:
                continue  # pipeline fill: resolve reads one dispatch behind
            info = _san.async_pull_result(pending.pop(0))
            k_acc, total, ok, whint, finite = (int(info[0]), int(info[1]),
                                               int(info[2]), int(info[3]),
                                               int(info[4]))
            w_ran = windows[resolved]  # the W THIS round ran with (the loop
            # variable has moved on to later dispatches)
            resolved += 1
            # telemetry rides the values the async protocol ALREADY pulled —
            # host dict updates only, zero extra dispatches/syncs (the
            # DispatchCounter budget pin runs with this enabled)
            if _obs.enabled():
                _obs.histogram("train_window_rows").observe(total)
                _obs.histogram("train_window_fill").observe(
                    total / max(w_ran, 1))
                # the resolve we just did IS an accounted sync: the
                # resolve-to-resolve interval is the honest wall clock of
                # the round that retired between them (the first one also
                # carries init + pipeline fill, flagged in the attrs)
                t_now = _time.perf_counter()
                # W-ladder context (round 12): the rung this round ran
                # on, the transition that brought it there, and the
                # whint that will ladder W two dispatches later — one
                # trace now answers whether whint overshoots the
                # realized windows (rows vs W per rung)
                rung = _window_rung(w_ran, n, floor)
                _trace.record_span(
                    "windowed_round",
                    t_now - (t_resolve_prev if t_resolve_prev is not None
                             else t_open),
                    round=resolved, k_acc=k_acc, rows=total, W=w_ran,
                    rung=rung,
                    rung_delta=(0 if rung_prev is None
                                else rung - rung_prev),
                    whint=whint,
                    first=t_resolve_prev is None)
                t_resolve_prev = t_now
                rung_prev = rung
            if not finite:
                _obs.counter("train_nonfinite_errors_total").inc()
                _obs.event("nonfinite", phase="windowed", round=resolved)
                raise NonFiniteError(
                    f"non-finite gradients/hessians/split stats on device "
                    f"at windowed round {resolved}{guard_label}: refusing "
                    "to keep boosting on NaNs. The guard rode the round's "
                    "async info vector (read one round behind, zero extra "
                    "dispatches/syncs) — check labels/weights/custom "
                    "objective outputs; see docs/ROBUSTNESS.md")
            if prof:
                t_now = _time.perf_counter()
                print(f"[WPROF] k={k_acc:2d} total={total:7d} W={w_ran:7d} "
                      f"round={t_now - t_last:6.3f}s", flush=True)
                t_last = t_now
            if not ok:
                # prediction breached (whint bound violated — a bug, not a
                # workload property): the device skipped the round; fold the
                # corrected W into the next dispatch instead of syncing
                retries += 1
                W = _window_size(max(total, 1), n, floor)
                continue
            n_leaves += k_acc
            if k_acc == 0 or n_leaves >= num_leaves:
                converged = True
                break
            W = _window_size(max(whint, 1), n, floor)
        # drain the in-flight round's info so its finite flag is checked
        # too (the pipeline runs one dispatch ahead of the resolve point;
        # without the drain, corruption in the final rounds would slip
        # past the in-loop guard and only be caught by the deferred
        # booster-level check)
        while pending:
            info = _san.async_pull_result(pending.pop(0))
            resolved += 1
            if _obs.enabled():
                # drained rounds get their span too — the trace must hold
                # exactly `rounds` windowed_round spans per tree (the last
                # round of a tree resolves HERE, one dispatch behind), and
                # this resolve is just as accounted as the in-loop one
                t_now = _time.perf_counter()
                rung = _window_rung(windows[resolved - 1], n, floor)
                _trace.record_span(
                    "windowed_round",
                    t_now - (t_resolve_prev if t_resolve_prev is not None
                             else t_open),
                    round=resolved, k_acc=int(info[0]), rows=int(info[1]),
                    W=windows[resolved - 1],
                    rung=rung,
                    rung_delta=(0 if rung_prev is None
                                else rung - rung_prev),
                    whint=int(info[3]),
                    first=t_resolve_prev is None, drained=True)
                t_resolve_prev = t_now
                rung_prev = rung
            if not int(info[4]):
                _obs.counter("train_nonfinite_errors_total").inc()
                _obs.event("nonfinite", phase="windowed_drain",
                           round=resolved)
                raise NonFiniteError(
                    f"non-finite gradients/hessians/split stats on device "
                    f"at windowed round {resolved}{guard_label} (drained "
                    "in-flight round): refusing to finalize a tree grown "
                    "on NaNs; see docs/ROBUSTNESS.md")
    finally:
        pending.clear()
        counter.__exit__(None, None, None)
        if stats is not None:
            stats.update(rounds=rounds, dispatches=counter.dispatches,
                         host_syncs=counter.host_syncs,
                         async_resolves=counter.async_resolves,
                         retries=retries, windows=windows)
        if _obs.enabled():
            # per-tree summary from the driver's own host-side ledger
            _obs.counter("train_windowed_rounds_total").inc(rounds)
            _obs.counter("train_windowed_retries_total").inc(retries)
            _obs.event("windowed_tree", rounds=rounds, retries=retries,
                       dispatches=counter.dispatches,
                       host_syncs=counter.host_syncs,
                       async_resolves=counter.async_resolves)
            # tree-level span closing here, right after the drain loop's
            # final accounted resolve emptied `pending` — every dispatched
            # round's info has been read, so the interval covers the whole
            # tree's device work without adding a sync
            _trace.record_span("windowed_tree",
                               _time.perf_counter() - t_open,
                               rounds=rounds, retries=retries,
                               dispatches=counter.dispatches)
    if not converged:
        # the safety headroom ran out (repeated window-bound breaches):
        # growth stopped early with a valid but under-grown tree — make
        # that LOUD even without the enforce gate armed
        from ..utils.log import log_warning
        log_warning(
            f"windowed growth exhausted its round budget ({max_rounds} "
            f"dispatches, {retries} window retries) before reaching "
            f"num_leaves={num_leaves}; the tree is valid but under-grown "
            "— this indicates a whint bound violation, please report")

    if enforce:
        counter.assert_round_budget(rounds, what="windowed round loop")
        if retries:
            raise _san.BudgetError(
                f"windowed round loop: {retries} window-prediction "
                "retries — the whint bound under-predicted (see "
                "ops/treegrow_windowed.py round-7 notes)")

    return state


# The Pallas segment partition (ops/partition_pallas.py) is not selected:
# Mosaic under jax 0.9.0 / libtpu 0.0.34 refuses its compaction loop
# ("Cannot store scalars to VMEM", ROADMAP.md Design item 1), and a grower
# that selected it paid a failed compile and a fallback on every process
# start.  The rounds take the XLA permutation; the kernel stays reachable
# through partition_rows(interpret=True), which the tests use.
PALLAS_PARTITION = False


def megakernel_mode(use_pallas_eff: bool, *, rng_key=None, efb_bins_t=None,
                    quantize_bins: int = 0, mode: Optional[str] = None,
                    loud: bool = True) -> tuple[bool, bool]:
    """The round-megakernel gate, shared by the single-device entry below
    and the SPMD entry (parallel/data_parallel.py): returns
    ``(megakernel, mk_interpret)`` statics for :func:`_round_fused`.

    ``mode`` (the Booster's ``megakernel`` extra param, models/gbdt.py)
    overrides ``LGBMTPU_MEGAKERNEL``; both select: ``auto`` (default —
    OFF: Mosaic under jax 0.9.0 / libtpu 0.0.34 refuses the kernel with
    "Cannot store scalars to VMEM", ROADMAP.md Design item 1, so ``auto``
    no longer pays a failed compile and a fallback on every process
    start), ``1`` (forced ON),
    ``interpret`` (ON through the Mosaic interpreter — the off-chip
    correctness harness, which IGNORES the degradation registry exactly
    like the partition kernel's interpret path: a degraded process must
    re-run the kernel and surface, never silently grow three-pass
    trees), ``0`` (OFF).

    The megakernel envelope excludes EFB bundles, per-node feature
    sampling (the rng-keyed scan cannot run on-core), and — on the
    Pallas hot path — int8-quantized training: the three-pass round
    accumulates quantized histograms exactly on the int8 MXU while the
    committed megakernel folds the DEQUANTIZED f32 values (bitwise with
    the XLA round, NOT with the int8 kernel), so until the int8 MXU
    accumulate variant lands (docs/NEXT.md) a quantized+Pallas config
    must not silently change numerics.  Every excluded-but-requested
    configuration falls back to the three-pass round LOUDLY — counter +
    event, never a silent divergence — exactly like the degradation
    registry's kernel-failure fallback."""
    if mode is None:
        mode = os.environ.get("LGBMTPU_MEGAKERNEL", "auto")
    mode = str(mode).lower()
    if mode not in ("1", "interpret"):
        return False, False
    if mode != "interpret" and not _degrade.available(_degrade.ROUND):
        return False, False
    reason = None
    if efb_bins_t is not None:
        reason = "efb"
    elif rng_key is not None:
        reason = "node_rng"
    elif quantize_bins and use_pallas_eff:
        reason = "quantized_mxu"
    if reason is not None:
        if loud:
            _obs.counter("megakernel_envelope_fallbacks_total").inc()
            _obs.event("megakernel_fallback", reason=reason)
        return False, False
    return True, mode == "interpret"


def grow_tree_windowed(*args, use_pallas: bool = True,
                       megakernel_opt: Optional[str] = None, **kwargs):
    """Public entry: :func:`_grow_windowed_impl` behind the graceful
    kernel-degradation net (utils/degrade.py).

    ``use_pallas`` is folded with the degradation registry BEFORE it
    becomes a jit static, so a process that already lost its Pallas
    kernels traces straight to the XLA paths.  A Pallas/Mosaic failure
    that only surfaces at backend-compile or execute time escapes the
    trace-time dispatchers — it is caught here once, logged, recorded,
    and the whole tree is regrown from the ORIGINAL inputs on the XLA
    path (only internal WState buffers were donated to the failed
    dispatch; the grower inputs are intact).

    The net is LAYERED for the round megakernel: a megakernel failure
    disables only :data:`~..utils.degrade.ROUND` and regrows on the
    three-pass round (which may still use the Pallas hist + partition
    kernels); a histogram-kernel failure there degrades HIST as before.
    In ``LGBMTPU_MEGAKERNEL=interpret`` mode failures SURFACE (the
    correctness harness must never silently fall back, mirroring the
    partition kernel's interpret contract)."""
    use_p = use_pallas and _degrade.available(_degrade.HIST)
    rng_key = args[8] if len(args) > 8 else kwargs.get("rng_key")
    efb_bins_t = args[12] if len(args) > 12 else kwargs.get("efb_bins_t")
    mk, mk_interp = megakernel_mode(
        use_p, rng_key=rng_key, efb_bins_t=efb_bins_t,
        quantize_bins=kwargs.get("quantize_bins", 0), mode=megakernel_opt)

    def three_pass():
        if not use_p:
            return _grow_windowed_impl(*args, use_pallas=False, **kwargs)
        return _degrade.run_with_fallback(
            _degrade.HIST,
            lambda: _grow_windowed_impl(*args, use_pallas=True, **kwargs),
            lambda: _grow_windowed_impl(*args, use_pallas=False, **kwargs))

    if not mk:
        return three_pass()
    if mk_interp:
        # correctness harness: always run the kernel (the degradation
        # registry is ignored by megakernel_mode) and surface every
        # failure — the partition kernel's interpret contract
        from ..utils import faults as _faults

        _faults.maybe_fail("pallas_round")
        return _grow_windowed_impl(*args, use_pallas=use_p, megakernel=True,
                                   mk_interpret=True, **kwargs)
    return _degrade.run_with_fallback(
        _degrade.ROUND,
        lambda: _grow_windowed_impl(*args, use_pallas=use_p, megakernel=True,
                                    mk_interpret=False, **kwargs),
        three_pass, fault_site="pallas_round")
