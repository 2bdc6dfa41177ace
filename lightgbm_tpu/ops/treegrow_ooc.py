"""Out-of-core tree growth: chunked histogram accumulation over a
streamed binned matrix (docs round 12 — the spill regime of the
``out_of_core=`` data path).

The in-memory growers take the whole (N, F) binned matrix as one traced
device input, which is exactly what a dataset LARGER THAN HBM cannot
provide.  This grower keeps only the O(N) vectors on device — leaf ids,
gradients, hessians, masks — plus the O(L*F*B) histogram state, and
streams the binned matrix through the device in fixed-shape row chunks
(io/stream.py: pinned reused host buffers, one-deep upload prefetch) once
per histogram pass.  The matrix itself is never device-resident.

Exactness contract (pinned by tests/test_out_of_core.py): the grower is
a chunk-streamed mirror of the STRICT grower (ops/treegrow.py grow_tree,
serial mode) with the scatter histogram strategy.  Two facts make the
mirror bitwise, not approximately, equal:

* the per-leaf masked scatter histogram is an order-preserving fold —
  seeding each chunk's scatter-add with the running accumulator
  continues the SAME row-order addition chain the one-shot scatter
  performs, so any chunk partition (1 row, odd sizes, powers of two,
  all-N) produces bit-identical histograms;
* every other per-split computation (split search, leaf bookkeeping,
  partition decisions) is either O(L)/O(F) device math reusing the very
  same functions (``find_best_split``, ``leaf_output``) or an
  elementwise per-row update whose chunking cannot reorder anything.

Bitwise parity with IN-MEMORY training therefore holds whenever the
in-memory grower also selects the scatter strategy — max_bin > 64 or
> 512 features (ops/histogram.py ``histogram(strategy="auto")``), which
is precisely the wide regime out-of-core exists for.  Narrow-bin
in-memory runs use the one-hot einsum whose reduction tree differs in
ulps; the models are statistically indistinguishable but not bit-equal,
and the tests pin the scatter regime only.

Envelope (gated in models/gbdt.py): serial single-device, numerical +
categorical splits, bagging/GOSS row masks, feature_fraction, max_depth.
No monotone/interaction/forced splits, CEGB, linear leaves or
extra_trees — configurations outside the envelope raise at setup rather
than silently training something else.

Dispatch/sync shape (honest): this is a host-driven per-split loop —
one small blocking pull per split for the can-split decision (the strict
grower's host analogue) plus ``ceil(N/chunk)`` chunk dispatches per
pass.  The RESIDENT out-of-core regime runs the standard growers over a
stream-assembled device matrix and is not this loop;
tests/test_out_of_core.py pins both.
The chunk steps' IR is pinned by the ``ooc_root_chunk`` /
``ooc_split_chunk`` audit contracts (analysis/contracts.py): donated
accumulators consumable, collective/callback/transfer-free bodies,
bounded live set (docs/ANALYSIS.md "Jaxpr audit layer").
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..obs import metrics as _obs
from ..utils import sanitizer as _san
from .split import (BestSplit, SplitParams, find_best_split, leaf_output,
                    leaf_output_smoothed, KMIN_SCORE)
from .treegrow import TreeArrays, _empty_best, _set_best


class OocState(NamedTuple):
    hist: jnp.ndarray  # (L, 3, F, B) f32
    best: BestSplit
    leaf_sum_g: jnp.ndarray  # (L,)
    leaf_sum_h: jnp.ndarray
    leaf_count: jnp.ndarray
    leaf_depth: jnp.ndarray
    leaf_parent: jnp.ndarray
    leaf_side: jnp.ndarray
    num_leaves_cur: jnp.ndarray
    leaf_out: jnp.ndarray
    tree: TreeArrays


def _slice_rows(vec, row_lo, c: int):
    return jax.lax.dynamic_slice_in_dim(vec, row_lo, c, axis=0)


@functools.partial(jax.jit, static_argnames=("num_bins",), donate_argnums=(0,))
def _hist_chunk_update(
    hist,  # (3, F, B) f32 — running accumulator (donated)
    chunk_bins,  # (C, F) int — fixed-shape padded chunk
    mask,  # (C,) f32 — leaf-membership x row_mask weights (0.0 on pads)
    grad_c,  # (C,) f32 — sample-weighted, sliced from the resident vector
    hess_c,  # (C,) f32
    valid,  # (C,) bool — False on the padded tail
    *,
    num_bins: int,
):
    """Seed-and-continue masked scatter: bit-for-bit the next chunk of the
    one-shot ``histogram_scatter`` fold (module docstring).  PAD rows
    route to an out-of-range index and are dropped entirely — a padded
    row must contribute NOTHING, not even a +0.0 that could flip a -0.0
    accumulator bit (in-memory rows, masked or not, all scatter)."""
    c, f = chunk_bins.shape
    payload = jnp.stack([grad_c * mask, hess_c * mask, mask], axis=0)
    payload = jnp.broadcast_to(payload[:, :, None], (3, c, f))
    flat = chunk_bins.astype(jnp.int32) + (
        jnp.arange(f, dtype=jnp.int32) * num_bins)[None, :]
    flat = jnp.where(valid[:, None], flat, f * num_bins)
    hf = hist.reshape(3, f * num_bins)
    return hf.at[:, flat].add(payload, mode="drop").reshape(hist.shape)


@functools.partial(jax.jit, static_argnames=("num_bins",), donate_argnums=(0,))
def _root_chunk_step(
    hist,  # (3, F, B) f32 — running accumulator (donated)
    chunk_bins,  # (C, F) int
    row_lo,  # i32 scalar (traced)
    valid,  # (C,) bool
    grad_pad,  # (Np,) f32 resident (sample-weighted)
    hess_pad,  # (Np,) f32
    row_mask_pad,  # (Np,) bool
    *,
    num_bins: int,
):
    """One chunk of the root pass: the leaf-0 membership mask and the
    resident-vector slices happen INSIDE the jit, so the sweep costs
    exactly the one accounted dispatch per chunk the module docstring
    promises (no eager mask/slice round-trips in the host hot loop)."""
    c = chunk_bins.shape[0]
    mask = (_slice_rows(row_mask_pad, row_lo, c) & valid).astype(jnp.float32)
    hist = _hist_chunk_update(
        hist, chunk_bins, mask,
        _slice_rows(grad_pad, row_lo, c), _slice_rows(hess_pad, row_lo, c),
        valid, num_bins=num_bins)
    return hist


@functools.partial(jax.jit, static_argnames=("num_bins",),
                   donate_argnums=(0, 1))
def _split_chunk_step(
    leaf_id_pad,  # (Np,) i32 — resident, donated
    hist_small,  # (3, F, B) f32 — the small child's accumulator, donated
    chunk_bins,  # (C, F) int
    row_lo,  # i32 scalar (traced)
    valid,  # (C,) bool
    grad_pad,  # (Np,) f32 resident (sample-weighted)
    hess_pad,  # (Np,) f32
    row_mask_pad,  # (Np,) bool
    missing_bin_pf,  # (F,) i32
    sel,  # dict of traced split scalars (see grow_tree_ooc)
    *,
    num_bins: int,
):
    """One chunk of a split's fused partition + small-child histogram
    sweep: update the chunk's leaf ids by the split decision, then fold
    the chunk's small-child rows into the histogram accumulator.  The
    partition is elementwise (chunking changes nothing); the histogram
    is the seeded fold (bitwise, module docstring)."""
    c = chunk_bins.shape[0]
    lid = _slice_rows(leaf_id_pad, row_lo, c)
    fcol = jnp.take_along_axis(
        chunk_bins.astype(jnp.int32),
        jnp.broadcast_to(sel["feature"], (c,))[:, None], axis=1)[:, 0]
    is_missing = fcol == missing_bin_pf[sel["feature"]]
    go_left_num = jnp.where(is_missing, sel["default_left"],
                            fcol <= sel["threshold_bin"])
    go_left = jnp.where(sel["is_cat"], sel["cat_mask"][fcol], go_left_num)
    in_leaf = lid == sel["best_leaf"]
    new_lid = jnp.where(in_leaf & ~go_left & valid, sel["new_leaf"], lid)
    leaf_id_pad = jax.lax.dynamic_update_slice(leaf_id_pad, new_lid, (row_lo,))

    mask_small = ((new_lid == sel["small_leaf"])
                  & _slice_rows(row_mask_pad, row_lo, c)).astype(jnp.float32)
    hist_small = _hist_chunk_update(
        hist_small, chunk_bins, mask_small,
        _slice_rows(grad_pad, row_lo, c), _slice_rows(hess_pad, row_lo, c),
        valid, num_bins=num_bins)
    return leaf_id_pad, hist_small


@jax.jit
def _select_split(best: BestSplit, num_leaves_cur):
    """The winning leaf's split scalars (device, no pull) — mirrors the
    strict grower's ``do_split`` selection."""
    best_leaf = jnp.argmax(best.gain).astype(jnp.int32)
    s = jax.tree.map(lambda a: a[best_leaf], best)
    left_smaller = s.left_count <= s.right_count
    return {
        "best_leaf": best_leaf,
        "feature": s.feature,
        "threshold_bin": s.threshold_bin,
        "default_left": s.default_left,
        "is_cat": s.is_cat,
        "cat_mask": s.cat_mask,
        "new_leaf": num_leaves_cur,
        "small_leaf": jnp.where(left_smaller, best_leaf, num_leaves_cur),
    }


def _best_for(hist_leaf, sum_g, sum_h, count, depth, parent_out,
              feature_mask, num_bins_pf, missing_bin_pf, categorical_mask,
              params: SplitParams, max_depth: int):
    """Identical kwargs to the strict grower's serial-mode ``best_for``
    (no monotone/interaction/CEGB/rng — outside the OOC envelope)."""
    s = find_best_split(
        hist_leaf, sum_g, sum_h, count, num_bins_pf, missing_bin_pf,
        params, feature_mask=feature_mask, categorical_mask=categorical_mask,
        out_lo=jnp.float32(-jnp.inf), out_hi=jnp.float32(jnp.inf),
        depth=(depth.astype(jnp.float32) if hasattr(depth, "astype")
               else jnp.float32(depth)),
        parent_output=parent_out,
    )
    if max_depth > 0:
        s = s._replace(gain=jnp.where(depth >= max_depth, KMIN_SCORE, s.gain))
    return s


@functools.partial(jax.jit, static_argnames=("num_leaves", "num_bins",
                                             "max_depth", "params"))
def _root_state(
    hist0, feature_mask, num_bins_pf, missing_bin_pf, categorical_mask,
    *,
    num_leaves: int,
    num_bins: int,
    max_depth: int,
    params: SplitParams,
) -> OocState:
    """Root leaf state from the streamed root histogram — the strict
    grower's leaf-0 setup, with the hist handed in instead of computed."""
    L = num_leaves
    f = hist0.shape[1]
    sum0 = jnp.sum(hist0[:, 0, :], axis=1)  # totals from feature 0: (3,)
    g0, h0, c0 = sum0[0], sum0[1], sum0[2]
    leaf_out0 = leaf_output(g0, h0, params)
    best0 = _set_best(
        _empty_best(L, num_bins), jnp.asarray(0),
        _best_for(hist0, g0, h0, c0, jnp.asarray(0), leaf_out0,
                  feature_mask, num_bins_pf, missing_bin_pf,
                  categorical_mask, params, max_depth))
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
    return OocState(
        hist=jnp.zeros((L, 3, f, num_bins), jnp.float32).at[0].set(hist0),
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


@functools.partial(jax.jit, static_argnames=("num_leaves", "num_bins",
                                             "max_depth", "params"),
                   donate_argnums=(0,))
def _finish_split(
    state: OocState,
    hist_small,
    feature_mask, num_bins_pf, missing_bin_pf, categorical_mask,
    *,
    num_leaves: int,
    num_bins: int,
    max_depth: int,
    params: SplitParams,
) -> OocState:
    """Post-sweep bookkeeping — a faithful mirror of the strict grower's
    ``do_split`` tail (serial mode, envelope features only)."""
    best_leaf = jnp.argmax(state.best.gain).astype(jnp.int32)
    s = jax.tree.map(lambda a: a[best_leaf], state.best)
    node = state.num_leaves_cur - 1
    new_leaf = state.num_leaves_cur
    left_smaller = s.left_count <= s.right_count

    parent_hist = state.hist[best_leaf]
    hist_big = parent_hist - hist_small
    hist_left = jnp.where(left_smaller, hist_small, hist_big)
    hist_right = jnp.where(left_smaller, hist_big, hist_small)
    hist = state.hist.at[best_leaf].set(hist_left).at[new_leaf].set(hist_right)

    parent_out = state.leaf_out[best_leaf]
    old_parent = state.leaf_parent[best_leaf]
    old_side = state.leaf_side[best_leaf]
    t = state.tree
    lc = jnp.where((old_parent >= 0) & (old_side == 0),
                   t.left_child.at[old_parent].set(node), t.left_child)
    rc = jnp.where((old_parent >= 0) & (old_side == 1),
                   t.right_child.at[old_parent].set(node), t.right_child)
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
        internal_weight=t.internal_weight.at[node].set(
            state.leaf_sum_h[best_leaf]),
        internal_count=t.internal_count.at[node].set(
            state.leaf_count[best_leaf]),
        is_cat=t.is_cat.at[node].set(s.is_cat),
        cat_mask=t.cat_mask.at[node].set(s.cat_mask),
    )

    leaf_sum_g = state.leaf_sum_g.at[best_leaf].set(
        s.left_sum_g).at[new_leaf].set(s.right_sum_g)
    leaf_sum_h = state.leaf_sum_h.at[best_leaf].set(
        s.left_sum_h).at[new_leaf].set(s.right_sum_h)
    leaf_count = state.leaf_count.at[best_leaf].set(
        s.left_count).at[new_leaf].set(s.right_count)
    leaf_depth = state.leaf_depth.at[best_leaf].set(
        depth_child).at[new_leaf].set(depth_child)
    leaf_parent = state.leaf_parent.at[best_leaf].set(
        node).at[new_leaf].set(node)
    leaf_side = state.leaf_side.at[best_leaf].set(0).at[new_leaf].set(1)

    out_l_c = leaf_output_smoothed(s.left_sum_g, s.left_sum_h, s.left_count,
                                   parent_out, params)
    out_r_c = leaf_output_smoothed(s.right_sum_g, s.right_sum_h,
                                   s.right_count, parent_out, params)
    leaf_out = state.leaf_out.at[best_leaf].set(out_l_c).at[new_leaf].set(
        out_r_c)

    bl = _best_for(hist_left, s.left_sum_g, s.left_sum_h, s.left_count,
                   depth_child, out_l_c, feature_mask, num_bins_pf,
                   missing_bin_pf, categorical_mask, params, max_depth)
    br = _best_for(hist_right, s.right_sum_g, s.right_sum_h, s.right_count,
                   depth_child, out_r_c, feature_mask, num_bins_pf,
                   missing_bin_pf, categorical_mask, params, max_depth)
    best = _set_best(_set_best(state.best, best_leaf, bl), new_leaf, br)

    return OocState(
        hist=hist, best=best, leaf_sum_g=leaf_sum_g, leaf_sum_h=leaf_sum_h,
        leaf_count=leaf_count, leaf_depth=leaf_depth,
        leaf_parent=leaf_parent, leaf_side=leaf_side,
        num_leaves_cur=state.num_leaves_cur + 1, leaf_out=leaf_out,
        tree=tree,
    )


def grow_tree_ooc(
    chunk_source: Callable,  # () -> iterator of (row_lo, host_chunk)
    n: int,
    f: int,
    grad: jnp.ndarray,  # (N,) f32
    hess: jnp.ndarray,  # (N,) f32
    row_mask: jnp.ndarray,  # (N,) bool
    sample_weight: jnp.ndarray,  # (N,) f32
    feature_mask: jnp.ndarray,  # (F,) bool
    num_bins_pf: jnp.ndarray,
    missing_bin_pf: jnp.ndarray,
    categorical_mask: Optional[jnp.ndarray] = None,
    *,
    num_leaves: int,
    num_bins: int,
    max_depth: int = -1,
    params: SplitParams = SplitParams(),
    chunk_rows: int,
    stats: Optional[dict] = None,
) -> tuple[TreeArrays, jnp.ndarray]:
    """Grow one tree over a streamed binned matrix; returns
    (tree, leaf_id per row) — the strict grower's contract.

    ``chunk_source`` is re-invoked once per histogram pass (1 root pass +
    1 pass per split); each invocation must yield the SAME chunks in the
    same order (io/stream.py sources do).  ``stats``, when given,
    receives {splits, passes, chunks} for the bench/telemetry layer.
    """
    from ..io.stream import prefetch_device

    L = num_leaves
    c_rows = max(int(chunk_rows), 1)
    n_pad = -(-n // c_rows) * c_rows
    statics = dict(num_leaves=L, num_bins=num_bins, max_depth=max_depth,
                   params=params)

    def pad_to(vec, fill):
        return jnp.pad(vec, (0, n_pad - n), constant_values=fill)

    # the sample-weight fold mirrors grow_tree's entry exactly
    grad_pad = pad_to(grad.astype(jnp.float32) * sample_weight, 0)
    hess_pad = pad_to(hess.astype(jnp.float32) * sample_weight, 0)
    row_mask_pad = pad_to(row_mask, False)
    leaf_id_pad = jnp.zeros((n_pad,), jnp.int32)

    passes = chunks_seen = 0

    # valid-tail masks take at most TWO values per sweep (all-True for
    # full chunks, one tail variant) — build each once instead of paying
    # an eager arange+compare round-trip per chunk per pass
    _valid_cache: dict = {}

    def _valid(m: int) -> jnp.ndarray:
        v = _valid_cache.get(m)
        if v is None:
            v = _valid_cache[m] = jnp.arange(c_rows, dtype=jnp.int32) < m
        return v

    # ---- root pass: one streamed sweep builds leaf 0's histogram ----
    hist = jnp.zeros((3, f, num_bins), jnp.float32)
    for row_lo, m, dev in prefetch_device(
            chunk_source(), dtype=jnp.int16, pad_rows=c_rows):
        _san.record_dispatch()
        hist = _root_chunk_step(
            hist, dev, jnp.int32(row_lo), _valid(m),
            grad_pad, hess_pad, row_mask_pad, num_bins=num_bins)
        chunks_seen += 1
    passes += 1
    state = _root_state(hist, feature_mask, num_bins_pf, missing_bin_pf,
                        categorical_mask, **statics)

    # ---- per-split host loop (the strict grower's fori_loop, streamed) ----
    splits = 0
    for _ in range(L - 1):
        # the can-split decision is a REAL host data dependency (the loop
        # must stop when no gain clears the bar) — one small accounted
        # pull per split, the strict grower's host-driven analogue
        gmax = float(_san.sync_pull(jnp.max(state.best.gain)))
        if not gmax > KMIN_SCORE / 2:
            break
        sel = _select_split(state.best, state.num_leaves_cur)
        hist_small = jnp.zeros((3, f, num_bins), jnp.float32)
        for row_lo, m, dev in prefetch_device(
                chunk_source(), dtype=jnp.int16, pad_rows=c_rows):
            _san.record_dispatch()
            leaf_id_pad, hist_small = _split_chunk_step(
                leaf_id_pad, hist_small, dev, jnp.int32(row_lo), _valid(m),
                grad_pad, hess_pad, row_mask_pad, missing_bin_pf, sel,
                num_bins=num_bins)
            chunks_seen += 1
        passes += 1
        splits += 1
        state = _finish_split(state, hist_small, feature_mask, num_bins_pf,
                              missing_bin_pf, categorical_mask, **statics)

    # ---- finalize (mirror of grow_tree's tail, envelope features) ----
    if params.path_smooth > 0:
        leaf_value = state.leaf_out
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
    if stats is not None:
        stats.update(splits=splits, passes=passes, chunks=chunks_seen)
    if _obs.enabled():
        _obs.counter("train_ooc_passes_total").inc(passes)
        _obs.counter("train_ooc_chunks_total").inc(chunks_seen)
    return tree, leaf_id_pad[:n]
