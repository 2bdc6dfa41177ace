"""Multi-slice scale-out: the hierarchical two-level merge
(docs/DISTRIBUTED.md "Hierarchical merge").

The sharded fused round (parallel/data_parallel.py) assumes ONE ICI mesh
where a full (tile, 3, F, B) histogram ``psum`` is cheap.  Crossing DCN
— multi-slice v5e, anything past one pod slice — breaks that assumption:
at Epsilon shape a full merge moves ~1.5 GB per round, and DCN bandwidth
is an order of magnitude below ICI.  This module maps the reference's
voting-parallel route (PV-Tree; src/treelearner/
voting_parallel_tree_learner.cpp — local top-k feature election, global
vote, histogram exchange for ONLY the elected features) onto a nested
(dcn, ici) mesh:

* **inside a slice** the round keeps its single in-dispatch merge —
  ``psum`` or ``psum_scatter`` over the ``ici`` axis, the J1 collective
  sequence unchanged per slice (the jaxpr-audit contracts
  ``windowed_round_hierarchical_{psum,voting}`` pin this against the
  legacy sharded round);
* **between slices** only top-k-shaped traffic crosses the ``dcn``
  axis: each slice elects its ``top_k_features`` best features per
  split candidate from its slice-local gains (reusing ops/split.py's
  gain-plane machinery), ships the k gain scalars + feature ids
  (all_gather), and after a deterministic global vote ships ONLY the
  elected k features' histogram columns (psum) — so the per-round DCN
  byte bill is ≤ k histograms' worth per candidate, provable statically
  (jaxpr-audit ``dcn_max_bytes``; jaxlint R17 bans any full-F histogram
  operand on the dcn axis);
* everything stays inside the ONE donated dispatch: the 5-scalar async
  info vector and the window-child election merge across BOTH axes in
  the same trace, so the 1-dispatch/0-sync/0-retrace budget holds per
  rank exactly as on the single-level mesh (tests/test_hierarchy.py).

``WState.hist`` lives in SLICE domain under the two-level merge (each
slice's row-sum; sibling subtraction is closed per slice), sharded over
the dcn axis of the state spec, so no full-F histogram is ever
replicated — or moved — across slices.

When ``top_k_features`` covers every candidate feature the election is
exhaustive and the grown tree is structurally EXACT vs the single-mesh
sharded round (the global vote set is sorted ascending, so argmax
tie-breaks match the flat search bit-for-bit); smaller k is the
PV-Tree approximation, like the reference's ``top_k``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.split import (BestSplit, KMIN_SCORE, SplitParams, find_best_split,
                         gain_plane)
from ..ops.treegrow import TreeArrays
from jax import shard_map
from .mesh import DCN_AXIS, ICI_AXIS, slice_axis_sizes


# ---------------------------------------------------------------------------
# the device-side two-phase election (called from _round_fused's trace)
# ---------------------------------------------------------------------------

def dcn_topk_best(
    cand_hists: jnp.ndarray,  # (C, 3, Fd, B) SLICE-domain candidate hists
    parent_g: jnp.ndarray,    # (C,) GLOBAL parent stats (replicated)
    parent_h: jnp.ndarray,
    parent_c: jnp.ndarray,
    num_bins_pf: jnp.ndarray,      # (Fd,) this rank's feature tables
    missing_bin_pf: jnp.ndarray,
    feature_mask: Optional[jnp.ndarray],
    categorical_mask: Optional[jnp.ndarray],
    feature_contri: Optional[jnp.ndarray],
    *,
    params: SplitParams,
    top_k: int,
    dcn_axis: str,
    depth: Optional[jnp.ndarray] = None,       # (C,)
    parent_out: Optional[jnp.ndarray] = None,  # (C,)
) -> BestSplit:
    """The hierarchical split search, entirely in-dispatch.

    Phase A (vote): per candidate, evaluate the full gain plane on the
    SLICE-local histograms with SLICE-local parent stats (summed from the
    candidate's own histogram — any feature's bins sum to the child's
    slice totals) and take each feature's best gain; ``top_k`` of those
    (gain scalars + feature ids) are all_gathered over the dcn axis.

    Phase B (elect + exchange): every slice deterministically scores the
    gathered votes (sum of valid local gains per feature; ``top_k``
    winners, ids sorted ascending so a full-coverage election reproduces
    the flat search's tie-breaks), gathers ONLY the elected features'
    histogram columns, psums them over dcn — the one histogram-shaped
    DCN collective, ≤ k features' worth per candidate — and runs the
    exact split selection on the now-GLOBAL k-feature histograms with
    the global parent stats.  The winner's feature index is mapped back
    to this rank's feature domain, so the caller's owned-feature
    ``_merge_best`` election (scatter merges) composes unchanged.

    Feature tables here are the caller's rank-local tables: full F under
    the intra-slice psum merge, the owned F/R block under scatter — the
    vote/exchange always stays inside one rank's feature domain, which
    is what keeps the dcn operands top-k-shaped (jaxlint R17)."""
    C, _, fd, _b = cand_hists.shape
    k = max(1, min(top_k, fd))  # top_k is a jit static (a Python int)
    if depth is None:
        depth = jnp.zeros_like(parent_g)
    if parent_out is None:
        parent_out = jnp.zeros_like(parent_g)
    depth = depth.astype(jnp.float32)

    # --- phase A: slice-local per-feature gains -------------------------
    # slice-local child totals from feature 0's bins (every window row
    # lands in exactly one bin per feature — pad features included, whose
    # rows all sit in bin 0 — so any feature's sum is the child total)
    loc = jnp.sum(cand_hists[:, :, 0, :], axis=2)  # (C, 3)

    def _local_fgain(h, pg, ph, pc, d, po):
        g, _ = gain_plane(
            h, pg, ph, pc, num_bins_pf, missing_bin_pf, params,
            feature_mask=feature_mask, categorical_mask=categorical_mask,
            depth=d, parent_output=po, feature_contri=feature_contri)
        return jnp.max(g, axis=1)  # (Fd,) best gain per feature

    fgain = jax.vmap(_local_fgain)(
        cand_hists, loc[:, 0], loc[:, 1], loc[:, 2], depth, parent_out)

    vote_gain, vote_idx = jax.lax.top_k(fgain, k)  # (C, k)
    all_gain = jax.lax.all_gather(vote_gain, dcn_axis)  # (S, C, k)
    all_idx = jax.lax.all_gather(vote_idx, dcn_axis)    # (S, C, k)

    # --- phase B: deterministic global vote + k-feature exchange --------
    # score = sum of VALID local gains per voted feature (dead votes —
    # gain KMIN — contribute nothing, exactly like unvoted features);
    # top_k ties break to the lowest feature id (stable), and the elected
    # set is sorted ascending so full coverage (k >= Fd) reproduces the
    # flat search's candidate order bit-for-bit
    contrib = jnp.where(all_gain > KMIN_SCORE / 2, all_gain, 0.0)
    c_idx = jnp.broadcast_to(
        jnp.arange(C, dtype=jnp.int32)[None, :, None], all_idx.shape)
    score = jnp.zeros((C, fd), jnp.float32).at[c_idx, all_idx].add(contrib)
    g_idx = jnp.sort(jax.lax.top_k(score, k)[1].astype(jnp.int32), axis=1)

    sub = jnp.take_along_axis(
        cand_hists, g_idx[:, None, :, None], axis=2)  # (C, 3, k, B)
    # THE histogram-shaped DCN collective: k features' columns per
    # candidate — never the full-F plane (jaxlint R17's whole point)
    sub = jax.lax.psum(sub, dcn_axis)

    opt = {}
    if feature_mask is not None:
        opt["feature_mask"] = feature_mask[g_idx]
    if categorical_mask is not None:
        opt["categorical_mask"] = categorical_mask[g_idx]
    if feature_contri is not None:
        opt["feature_contri"] = feature_contri[g_idx]

    def _best_one(h, nb, mb, pg, ph, pc, d, po, feature_mask=None,
                  categorical_mask=None, feature_contri=None):
        return find_best_split(
            h, pg, ph, pc, nb, mb, params, feature_mask=feature_mask,
            categorical_mask=categorical_mask, depth=d, parent_output=po,
            feature_contri=feature_contri)

    bb = jax.vmap(_best_one)(
        sub, num_bins_pf[g_idx], missing_bin_pf[g_idx],
        parent_g, parent_h, parent_c, depth, parent_out, **opt)
    # winner feature back to this rank's feature domain
    feat = jnp.take_along_axis(
        g_idx, bb.feature[:, None].astype(jnp.int32), axis=1)[:, 0]
    return bb._replace(feature=feat)


# ---------------------------------------------------------------------------
# nested-mesh data layout
# ---------------------------------------------------------------------------

_ROW_SPEC = P((DCN_AXIS, ICI_AXIS))


class SlicedData:
    """Training arrays laid out over the nested (dcn, ici) mesh: rows
    sharded over BOTH axes (slice-major — the slice's contiguous row
    block splits over its ici ranks), per-feature tables replicated.
    The hierarchical twin of parallel/data_parallel.py::ShardedData."""

    def __init__(self, mesh: Mesh, bins: np.ndarray, num_bins_pf: np.ndarray,
                 missing_bin_pf: np.ndarray):
        self.mesh = mesh
        self.num_slices, self.ranks_per_slice = slice_axis_sizes(mesh)
        n, f = bins.shape
        self.n_devices = mesh.devices.size
        self.row_sharding = NamedSharding(mesh, _ROW_SPEC)
        self.rep_sharding = NamedSharding(mesh, P())
        pad = (-n) % self.n_devices
        self.num_data = n
        self.padded = n + pad
        if pad:
            bins = np.concatenate(
                [bins, np.zeros((pad, f), bins.dtype)], axis=0)
        row_valid = np.zeros(self.padded, bool)
        row_valid[:n] = True
        self.bins = jax.device_put(bins, self.row_sharding)
        self.row_valid = jax.device_put(row_valid, self.row_sharding)
        self.num_bins_pf = jax.device_put(num_bins_pf, self.rep_sharding)
        self.missing_bin_pf = jax.device_put(missing_bin_pf,
                                             self.rep_sharding)

    @classmethod
    def from_sharded(cls, mesh: Mesh, sharded) -> "SlicedData":
        """Build from an already device-resident flat-mesh
        :class:`~..data_parallel.ShardedData` WITHOUT a second host
        upload of the bin matrix: the nested (dcn, ici) row layout over
        the same device order places byte-identical per-device blocks as
        the flat `P(data)` layout (both pad to the device-count multiple
        and split dim 0 contiguously), so the ``device_put`` reshard is
        an alias, not a copy — the booster keeps ONE device copy of the
        dominant array while both meshes stay usable (models/gbdt.py
        builds the flat layout first for the non-windowed fallback
        growers)."""
        if getattr(sharded, "process_local", False):
            raise ValueError(
                "SlicedData.from_sharded requires a single-controller "
                "ShardedData (pre_partition multi-controller is not "
                "wired through the hierarchical path)")
        self = cls.__new__(cls)
        self.mesh = mesh
        self.num_slices, self.ranks_per_slice = slice_axis_sizes(mesh)
        self.n_devices = mesh.devices.size
        if sharded.padded % self.n_devices:
            raise ValueError(
                f"flat layout padded to {sharded.padded} rows does not "
                f"cover {self.n_devices} nested-mesh devices")
        self.row_sharding = NamedSharding(mesh, _ROW_SPEC)
        self.rep_sharding = NamedSharding(mesh, P())
        self.num_data = sharded.num_data
        self.padded = sharded.padded
        self.bins = jax.device_put(sharded.bins, self.row_sharding)
        self.row_valid = jax.device_put(sharded.row_valid,
                                        self.row_sharding)
        self.num_bins_pf = jax.device_put(sharded.num_bins_pf,
                                          self.rep_sharding)
        self.missing_bin_pf = jax.device_put(sharded.missing_bin_pf,
                                             self.rep_sharding)
        return self

    def pad_rows(self, arr: np.ndarray, fill=0.0) -> jnp.ndarray:
        pad = self.padded - self.num_data
        if pad:
            a = np.asarray(arr)
            arr = np.concatenate(
                [a, np.full((pad,) + a.shape[1:], fill, a.dtype)])
        return jax.device_put(np.asarray(arr), self.row_sharding)

    def pad_rows_device(self, arr, dtype, fill=0.0) -> jnp.ndarray:
        arr = jnp.asarray(arr, dtype)
        pad = self.padded - self.num_data
        if pad:
            arr = jnp.concatenate(
                [arr, jnp.full((pad,) + arr.shape[1:], fill, dtype)])
        return jax.device_put(arr, self.row_sharding)

    def bins_t(self, f_pad: Optional[int] = None) -> jnp.ndarray:
        """Feature-major (F_pad, N_padded) copy, rows sharded over both
        mesh axes; cached per f_pad (see ShardedData.bins_t)."""
        key = int(f_pad or 0)
        cache = getattr(self, "_bins_t_cache", None)
        if cache is None:
            cache = self._bins_t_cache = {}
        if key not in cache:
            f = self.bins.shape[1]
            cache[key] = _bins_t_builder_hier(
                self.mesh, f, f_pad or f)(self.bins)
        return cache[key]


@functools.lru_cache(maxsize=16)
def _bins_t_builder_hier(mesh: Mesh, f: int, f_pad: int):
    def t(b):
        bt = b.T
        if f_pad > f:
            bt = jnp.concatenate(
                [bt, jnp.zeros((f_pad - f, b.shape[0]), b.dtype)])
        return bt

    return jax.jit(
        t, out_shardings=NamedSharding(mesh, P(None, (DCN_AXIS, ICI_AXIS))))


# ---------------------------------------------------------------------------
# jit(shard_map) builders over the nested mesh
# ---------------------------------------------------------------------------

def _hier_state_spec(merge: str):
    from ..ops.treegrow_windowed import WState

    # hist is SLICE-domain: each slice's full-F sum under the psum merge
    # (replicated over ici, distinct per slice -> sharded over dcn along
    # F), the owned F/R block under scatter (distinct per rank -> sharded
    # over both axes along F).  Never replicated across slices: no full-F
    # histogram exists globally, by layout.
    hist = (P(None, None, DCN_AXIS, None) if merge == "psum"
            else P(None, None, (DCN_AXIS, ICI_AXIS), None))
    row = _ROW_SPEC
    return WState(
        order=row, leaf_start=row, leaf_cnt=row, leaf_id=row, hist=hist,
        best=BestSplit(*([P()] * len(BestSplit._fields))),
        leaf_sum_g=P(), leaf_sum_h=P(), leaf_count=P(), leaf_depth=P(),
        leaf_parent=P(), leaf_side=P(), num_leaves_cur=P(), leaf_out=P(),
        tree=TreeArrays(*([P()] * len(TreeArrays._fields))),
    )


_HOPT_SPECS = {
    "gq": _ROW_SPEC, "hq": _ROW_SPEC, "quant_scale": P(),
    "quant_key": P(), "feature_contri": P(), "categorical_mask": P(),
}


@functools.lru_cache(maxsize=32)
def _windowed_init_hier(mesh: Mesh, merge: str, top_k: int,
                        extra_names: tuple, statics: tuple):
    from ..ops import treegrow_windowed as _tw

    kwargs = dict(statics)
    quant = bool(kwargs.get("quantize_bins"))

    def wrapped(bins_t, grad, hess, row_mask, sw, nbpf, mbpf, fmask,
                *extras):
        ex = dict(zip(extra_names, extras))
        return _tw._w_init.__wrapped__(
            bins_t, grad, hess, row_mask, sw, nbpf, mbpf, fmask,
            None, ex.get("quant_key"), ex.get("feature_contri"),
            ex.get("categorical_mask"), None, None, None,
            axis_name=ICI_AXIS, merge=merge, dcn_axis_name=DCN_AXIS,
            dcn_top_k=top_k, **kwargs)

    state_spec = _hier_state_spec(merge)
    row = _ROW_SPEC
    qspec = (row, row, P()) if quant else (None, None, None)
    return jax.jit(shard_map(
        wrapped, mesh=mesh,
        in_specs=(P(None, (DCN_AXIS, ICI_AXIS)), row, row, row, row,
                  P(), P(), P())
        + tuple(_HOPT_SPECS[n] for n in extra_names),
        out_specs=(state_spec, row, row) + qspec + (row, row),
        check_vma=False,
    ))


@functools.lru_cache(maxsize=256)
def _windowed_round_hier(mesh: Mesh, W: int, merge: str, top_k: int,
                         extra_names: tuple, statics: tuple):
    """One cached donated jit per (mesh, W rung, merge, top_k, statics) —
    the nested-mesh mirror of data_parallel._windowed_round_sharded."""
    from ..ops import treegrow_windowed as _tw

    kwargs = dict(statics)

    def wrapped(state, bins_t, grad, hess, row_mask, nbpf, mbpf, fmask,
                *extras):
        ex = dict(zip(extra_names, extras))
        return _tw._round_fused.__wrapped__(
            state, bins_t, grad, hess,
            ex.get("gq"), ex.get("hq"), ex.get("quant_scale"),
            row_mask, nbpf, mbpf, fmask,
            None, ex.get("feature_contri"),
            ex.get("categorical_mask"), None, None, None,
            W=W, axis_name=ICI_AXIS, merge=merge, dcn_axis_name=DCN_AXIS,
            dcn_top_k=top_k, **kwargs)

    state_spec = _hier_state_spec(merge)
    row = _ROW_SPEC
    return jax.jit(shard_map(
        wrapped, mesh=mesh,
        in_specs=(state_spec, P(None, (DCN_AXIS, ICI_AXIS)), row, row, row,
                  P(), P(), P())
        + tuple(_HOPT_SPECS[n] for n in extra_names),
        out_specs=(state_spec, P()),  # info is collective-merged on device
        check_vma=False,
    ), donate_argnums=(0,))


@functools.lru_cache(maxsize=32)
def _windowed_finalize_hier(mesh: Mesh, merge: str, statics: tuple):
    from ..ops import treegrow_windowed as _tw

    kwargs = dict(statics)

    def wrapped(state, grad_true, hess_true, row_mask):
        return _tw._w_finalize.__wrapped__(
            state, grad_true, hess_true, row_mask,
            axis_name=ICI_AXIS, dcn_axis_name=DCN_AXIS, **kwargs)

    row = _ROW_SPEC
    return jax.jit(shard_map(
        wrapped, mesh=mesh,
        in_specs=(_hier_state_spec(merge), row, row, row),
        out_specs=(TreeArrays(*([P()] * len(TreeArrays._fields))), row),
        check_vma=False,
    ))


def _pad_features(v, f_pad: int, fill, sharding):
    if v is None:
        return None
    v = jnp.asarray(v)
    if v.shape[0] < f_pad:
        v = jnp.concatenate(
            [v, jnp.full((f_pad - v.shape[0],) + v.shape[1:], fill,
                         v.dtype)])
    return jax.device_put(v, sharding)


def grow_tree_windowed_hierarchical(
    sliced: SlicedData,
    grad: jnp.ndarray,  # (Npad,) sharded over (dcn, ici)
    hess: jnp.ndarray,
    row_mask: jnp.ndarray,
    sample_weight: jnp.ndarray,
    feature_mask: jnp.ndarray,  # (F,) replicated
    categorical_mask: Optional[jnp.ndarray] = None,
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
    merge: str = "psum",  # intra-slice: "psum" | "scatter"
    top_k_features: int = 32,
    stats: Optional[dict] = None,
    guard_label: str = "",
) -> Tuple[TreeArrays, jnp.ndarray]:
    """SPMD fused windowed growth over the nested (dcn, ici) mesh: each
    steady-state round is ONE donated dispatch and ZERO blocking host
    syncs per rank, the intra-slice histogram merge rides ``merge`` over
    the ici axis unchanged, and only top-k-shaped traffic crosses dcn
    (module docstring).  Same host loop, same W-ladder protocol, same
    telemetry as the single-level sharded entry.

    Per-node feature sampling is refused for BOTH merges here: the
    slice-local vote must be deterministic and identical across slices,
    which a per-slice sampled feature set breaks (the single-level
    scatter merge's refusal, widened to the election)."""
    from ..ops import treegrow_windowed as _tw
    from ..utils import degrade as _degrade

    if merge not in ("psum", "scatter"):
        raise ValueError(f"merge must be 'psum' or 'scatter', got {merge!r}")
    if params.feature_fraction_bynode < 1.0 or params.extra_trees:
        raise ValueError(
            "the hierarchical two-level merge is incompatible with "
            "per-node feature sampling (feature_fraction_bynode/"
            "extra_trees): the slice-local top-k vote must be "
            "deterministic and slice-consistent")
    if int(top_k_features) < 1:
        raise ValueError(
            f"top_k_features must be >= 1, got {top_k_features}")
    mesh = sliced.mesh
    n_ici = sliced.ranks_per_slice
    f = int(sliced.num_bins_pf.shape[0])
    f_pad = (-(-f // n_ici) * n_ici) if merge == "scatter" else f
    rep = sliced.rep_sharding
    bins_t = sliced.bins_t(f_pad if f_pad != f else None)
    nbpf = _pad_features(sliced.num_bins_pf, f_pad, 1, rep)
    mbpf = _pad_features(sliced.missing_bin_pf, f_pad, -1, rep)
    fmask = _pad_features(jnp.asarray(feature_mask, bool), f_pad, False, rep)
    cmask = _pad_features(categorical_mask, f_pad, False, rep)
    fcontri = _pad_features(feature_contri, f_pad, 1.0, rep)
    top_k = int(top_k_features)

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
    init_fn = _windowed_init_hier(mesh, merge, top_k, init_names,
                                  init_statics)
    state, g_d, h_d, gq, hq, qs, g_true, h_true = init_fn(
        bins_t, grad, hess, row_mask, sample_weight, nbpf, mbpf, fmask,
        *(init_opt[k] for k in init_names))

    round_statics = tuple(sorted(dict(
        common, max_depth=max_depth, use_pallas=use_pallas,
        quantize_bins=quantize_bins, hist_precision=hist_precision,
        has_cat=categorical_mask is not None,
        # the Pallas partition + round megakernel stay off the
        # hierarchical path until wired under the nested mesh (the
        # hist kernels still run via use_pallas)
        pallas_partition=False, megakernel=False,
        mk_interpret=False).items()))
    round_opt = {"gq": gq, "hq": hq, "quant_scale": qs,
                 "feature_contri": fcontri, "categorical_mask": cmask}
    round_names = tuple(k for k, v in round_opt.items() if v is not None)
    round_vals = tuple(round_opt[k] for k in round_names)

    def round_fn(st, W):
        fn = _windowed_round_hier(mesh, W, merge, top_k, round_names,
                                  round_statics)
        return fn(st, bins_t, g_d, h_d, row_mask, nbpf, mbpf, fmask,
                  *round_vals)

    # each rank's window is bounded by its LOCAL rows (see the sharded
    # entry: the halving argument is global, the ladder local)
    n_loc = sliced.padded // sliced.n_devices
    state = _tw._run_fused_rounds(
        round_fn, state, n_ladder=n_loc,
        w_first=_tw._window_size(max(n_loc, 1), n_loc),
        num_leaves=num_leaves, stats=stats, guard_label=guard_label)

    fin_statics = tuple(sorted(dict(
        params=params,
        quant_renew=bool(quant_renew and quantize_bins)).items()))
    fin = _windowed_finalize_hier(mesh, merge, fin_statics)
    return fin(state, g_true, h_true, row_mask)
