"""2-D (feature x row) sharded fused windowed rounds — the wide-F regime.

docs/DISTRIBUTED.md "2-D sharding".  Data-parallel and voting shard rows,
the hierarchical merge shards slices; this layer shards FEATURES too
(reference: src/treelearner/feature_parallel_tree_learner.cpp — each
machine owns a feature subset and finds its local best split — composed
with the data-parallel row split, i.e. the reference's "data+feature"
grid the voting learner approximates).  The bin matrix is laid out
``P(feature, row)`` over a named 2-D mesh (SNIPPETS.md [3]'s GSPMD
pattern): each device owns an ``(F/d_f, N/d_r)`` tile, so

* per-leaf window histograms are COMPLETE for the owned feature block by
  layout — the merge is the row-axis psum alone, with ZERO collective
  over the feature axis (pinned by jaxlint R20 + the
  ``windowed_round_2d_*`` jaxpr contracts);
* the split election reuses the scatter merge's owned-feature winner
  machinery (ops/treegrow_windowed.py::_split_tables/_merge_best) with
  the feature axis as the owning axis;
* the winner's go/no-go row decisions — computable only on the owner
  block — are one psum-broadcast ``(N_loc,)`` bool over the feature
  axis, the round's ONLY feature-axis data exchange; partition
  movements stay row-local.

The host loop is the IDENTICAL async protocol (_run_fused_rounds): the
5-scalar info vector, W-ladder, and 1-dispatch/0-sync/0-retrace budget
per rank ride unchanged (tests/test_feature2d.py pins the budget with
telemetry + tracing ON).

Composition hook: ``_round_fused`` takes ``feature_axis_name`` alongside
``dcn_axis_name``, so a 3-axis (dcn, feature, row) mesh is a builder +
spec away — the jaxpr audit's per-axis byte accounting was built to pin
it (analysis/jaxpr_audit.py).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.split import SplitParams
from ..ops.treegrow import TreeArrays
from jax import shard_map
from .data_parallel import _WOPT_SPECS, _pad_features
from .mesh import DATA_AXIS, FEATURE_AXIS

MERGE_2D = "psum"  # the feature2d histogram merge is always the row psum
# (scatter would re-shard the already-feature-complete histograms)


def feature2d_axis_sizes(mesh: Mesh) -> Tuple[int, int]:
    """(d_row, d_feature) of a 2-D mesh."""
    return int(mesh.shape[DATA_AXIS]), int(mesh.shape[FEATURE_AXIS])


class Sharded2DData:
    """Training arrays laid out over the 2-D (data, feature) mesh.

    Rows pad to a multiple of d_row (padding rows carry row_valid=0 so
    they never contribute to histograms); features pad to a multiple of
    d_feature with DEAD features — num_bins=1, missing_bin=-1, a False
    feature_mask — exactly like the scatter merge's F padding, so a
    padded feature can never win a split and feature_fraction sampling
    can never draw it (the mask zeroes it out of the search).  The bin
    matrix lives feature-major as the ``(F_pad, N_pad)`` tile grid
    ``P(feature, row)``; row-indexed vectors ride ``P(data)`` (replicated
    across the feature axis); per-feature tables are replicated — the
    owned-feature search dynamic-slices its block in-trace, sharing the
    scatter merge's code path."""

    def __init__(self, mesh: Mesh, bins: np.ndarray, num_bins_pf: np.ndarray,
                 missing_bin_pf: np.ndarray):
        self.mesh = mesh
        d_r, d_f = feature2d_axis_sizes(mesh)
        n, f = bins.shape
        self.n_row_shards = d_r
        self.n_feature_shards = d_f
        self.num_data = n
        self.num_features = f
        self.padded = n + ((-n) % d_r)
        self.f_pad = f + ((-f) % d_f)
        self.row_sharding = NamedSharding(mesh, P(DATA_AXIS))
        self.rep_sharding = NamedSharding(mesh, P())
        self.tile_sharding = NamedSharding(mesh, P(FEATURE_AXIS, DATA_AXIS))
        bt = np.zeros((self.f_pad, self.padded), bins.dtype)
        bt[:f, :n] = bins.T  # pad features read bin 0 for every row (dead)
        self.bins_t = jax.device_put(bt, self.tile_sharding)
        row_valid = np.zeros(self.padded, bool)
        row_valid[:n] = True
        self.row_valid = jax.device_put(row_valid, self.row_sharding)
        self.num_bins_pf = _pad_features(
            num_bins_pf, self.f_pad, 1, self.rep_sharding)
        self.missing_bin_pf = _pad_features(
            missing_bin_pf, self.f_pad, -1, self.rep_sharding)

    def pad_rows_device(self, arr, dtype, fill=0.0) -> jnp.ndarray:
        """Pad + lay a row vector over the row axis (replicated across the
        feature axis) without a host round-trip."""
        arr = jnp.asarray(arr, dtype)
        pad = self.padded - self.num_data
        if pad:
            arr = jnp.concatenate(
                [arr, jnp.full((pad,) + arr.shape[1:], fill, dtype)])
        return jax.device_put(arr, self.row_sharding)


def _2d_state_spec():
    """WState layout on the 2-D mesh: row bookkeeping is per-ROW-rank
    (replicated across feature blocks), histograms are per-FEATURE-block
    (complete for the owned features, replicated across row ranks after
    the row psum), and decisions/tree are fully replicated."""
    from ..ops.split import BestSplit
    from ..ops.treegrow_windowed import WState

    row = P(DATA_AXIS)
    return WState(
        order=row, leaf_start=row, leaf_cnt=row, leaf_id=row,
        hist=P(None, None, FEATURE_AXIS, None),
        best=BestSplit(*([P()] * len(BestSplit._fields))),
        leaf_sum_g=P(), leaf_sum_h=P(), leaf_count=P(), leaf_depth=P(),
        leaf_parent=P(), leaf_side=P(), num_leaves_cur=P(), leaf_out=P(),
        tree=TreeArrays(*([P()] * len(TreeArrays._fields))),
    )


@functools.lru_cache(maxsize=32)
def _windowed_init_2d(mesh: Mesh, extra_names: tuple, statics: tuple):
    from ..ops import treegrow_windowed as _tw

    kwargs = dict(statics)
    quant = bool(kwargs.get("quantize_bins"))

    def wrapped(bins_t, grad, hess, row_mask, sw, nbpf, mbpf, fmask, *extras):
        ex = dict(zip(extra_names, extras))
        return _tw._w_init.__wrapped__(
            bins_t, grad, hess, row_mask, sw, nbpf, mbpf, fmask,
            ex.get("rng_key"), ex.get("quant_key"), ex.get("feature_contri"),
            ex.get("categorical_mask"), None, None, None,
            axis_name=DATA_AXIS, merge=MERGE_2D,
            feature_axis_name=FEATURE_AXIS, **kwargs)

    state_spec = _2d_state_spec()
    row = P(DATA_AXIS)
    qspec = (row, row, P()) if quant else (None, None, None)
    return jax.jit(shard_map(
        wrapped, mesh=mesh,
        in_specs=(P(FEATURE_AXIS, DATA_AXIS), row, row, row, row,
                  P(), P(), P())
        + tuple(_WOPT_SPECS[n] for n in extra_names),
        out_specs=(state_spec, row, row) + qspec + (row, row),
        check_vma=False,
    ))


@functools.lru_cache(maxsize=256)
def _windowed_round_2d(mesh: Mesh, W: int, extra_names: tuple,
                       statics: tuple):
    """One cached donated jit per (mesh, W-ladder rung, statics) — the 2-D
    mirror of data_parallel._windowed_round_sharded."""
    from ..ops import treegrow_windowed as _tw

    kwargs = dict(statics)

    def wrapped(state, bins_t, grad, hess, row_mask, nbpf, mbpf, fmask,
                *extras):
        ex = dict(zip(extra_names, extras))
        return _tw._round_fused.__wrapped__(
            state, bins_t, grad, hess,
            ex.get("gq"), ex.get("hq"), ex.get("quant_scale"),
            row_mask, nbpf, mbpf, fmask,
            ex.get("rng_key"), ex.get("feature_contri"),
            ex.get("categorical_mask"), None, None, None,
            W=W, axis_name=DATA_AXIS, merge=MERGE_2D,
            feature_axis_name=FEATURE_AXIS, **kwargs)

    state_spec = _2d_state_spec()
    row = P(DATA_AXIS)
    return jax.jit(shard_map(
        wrapped, mesh=mesh,
        in_specs=(state_spec, P(FEATURE_AXIS, DATA_AXIS), row, row, row,
                  P(), P(), P())
        + tuple(_WOPT_SPECS[n] for n in extra_names),
        out_specs=(state_spec, P()),  # info is collective-merged on device
        check_vma=False,
    ), donate_argnums=(0,))


@functools.lru_cache(maxsize=32)
def _windowed_finalize_2d(mesh: Mesh, statics: tuple):
    from ..ops import treegrow_windowed as _tw

    kwargs = dict(statics)

    def wrapped(state, grad_true, hess_true, row_mask):
        return _tw._w_finalize.__wrapped__(
            state, grad_true, hess_true, row_mask,
            axis_name=DATA_AXIS, feature_axis_name=FEATURE_AXIS, **kwargs)

    row = P(DATA_AXIS)
    return jax.jit(shard_map(
        wrapped, mesh=mesh,
        in_specs=(_2d_state_spec(), row, row, row),
        out_specs=(TreeArrays(*([P()] * len(TreeArrays._fields))), row),
        check_vma=False,
    ))


def grow_tree_windowed_feature2d(
    sharded: Sharded2DData,
    grad: jnp.ndarray,  # (Npad,) over DATA_AXIS, replicated @feature
    hess: jnp.ndarray,
    row_mask: jnp.ndarray,
    sample_weight: jnp.ndarray,
    feature_mask: jnp.ndarray,  # (F,) replicated
    categorical_mask: Optional[jnp.ndarray] = None,
    rng_key: Optional[jnp.ndarray] = None,
    quant_key: Optional[jnp.ndarray] = None,
    feature_contri: Optional[jnp.ndarray] = None,
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
) -> Tuple[TreeArrays, jnp.ndarray]:
    """Fused windowed growth over the 2-D (feature, row) mesh: each
    steady-state round is ONE donated dispatch and ZERO blocking host
    syncs per rank, the histogram phase crosses the feature axis with
    ZERO collectives, and the trees are structurally EXACT vs the
    single-device grower (tests/test_feature2d.py parity matrix).

    Like the scatter merge, the owned-feature split search requires the
    sampled feature set to span the full axis deterministically on every
    rank — per-node feature sampling is refused."""
    from ..ops import treegrow_windowed as _tw
    from ..utils import degrade as _degrade

    if (rng_key is not None or params.feature_fraction_bynode < 1.0
            or params.extra_trees):
        raise ValueError(
            "tree_learner=feature2d (owned-feature split search) is "
            "incompatible with per-node feature sampling "
            "(feature_fraction_bynode/extra_trees): each feature block "
            "searches only its owned features; use tree_learner=data")
    mesh = sharded.mesh
    f_pad = sharded.f_pad
    rep = sharded.rep_sharding
    bins_t = sharded.bins_t
    nbpf = sharded.num_bins_pf
    mbpf = sharded.missing_bin_pf
    fmask = _pad_features(jnp.asarray(feature_mask, bool), f_pad, False, rep)
    cmask = _pad_features(categorical_mask, f_pad, False, rep)
    fcontri = _pad_features(feature_contri, f_pad, 1.0, rep)

    use_pallas = bool(use_pallas and _degrade.available(_degrade.HIST))
    common = dict(num_leaves=num_leaves, num_bins=num_bins, params=params,
                  leaf_tile=leaf_tile)

    init_statics = tuple(sorted(dict(
        common, use_pallas=use_pallas, quantize_bins=quantize_bins,
        hist_precision=hist_precision,
        stochastic_rounding=stochastic_rounding).items()))
    init_opt = {"quant_key": quant_key, "feature_contri": fcontri,
                "categorical_mask": cmask}
    init_names = tuple(k for k, v in init_opt.items() if v is not None)
    init_fn = _windowed_init_2d(mesh, init_names, init_statics)
    state, g_d, h_d, gq, hq, qs, g_true, h_true = init_fn(
        bins_t, grad, hess, row_mask, sample_weight, nbpf, mbpf, fmask,
        *(init_opt[k] for k in init_names))

    # the megakernel stops before the collective merge and assumes the
    # full-F bin matrix per rank; it stays off the 2-D mesh until its
    # owned-block variant lands (mirrors the hierarchical entry)
    round_statics = tuple(sorted(dict(
        common, max_depth=max_depth, use_pallas=use_pallas,
        quantize_bins=quantize_bins, hist_precision=hist_precision,
        has_cat=categorical_mask is not None,
        pallas_partition=False, megakernel=False,
        mk_interpret=False).items()))
    round_opt = {"gq": gq, "hq": hq, "quant_scale": qs,
                 "feature_contri": fcontri, "categorical_mask": cmask}
    round_names = tuple(k for k, v in round_opt.items() if v is not None)
    round_vals = tuple(round_opt[k] for k in round_names)

    def round_fn(st, W):
        fn = _windowed_round_2d(mesh, W, round_names, round_statics)
        return fn(st, bins_t, g_d, h_d, row_mask, nbpf, mbpf, fmask,
                  *round_vals)

    # W bounds each ROW rank's local window (the feature axis replicates
    # rows, so the ladder domain is the row shard — same bound as the
    # 1-D sharded entry)
    n_loc = sharded.padded // sharded.n_row_shards
    state = _tw._run_fused_rounds(
        round_fn, state, n_ladder=n_loc,
        w_first=_tw._window_size(max(n_loc, 1), n_loc),
        num_leaves=num_leaves, stats=stats, guard_label=guard_label)

    fin_statics = tuple(sorted(dict(
        params=params,
        quant_renew=bool(quant_renew and quantize_bins)).items()))
    fin = _windowed_finalize_2d(mesh, fin_statics)
    return fin(state, g_true, h_true, row_mask)
