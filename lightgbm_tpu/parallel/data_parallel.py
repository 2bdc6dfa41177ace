"""Data-parallel tree learning over a device mesh.

TPU-native re-design of the reference's parallel tree learners
(reference: src/treelearner/data_parallel_tree_learner.cpp,
feature_parallel_tree_learner.cpp, voting_parallel_tree_learner.cpp and the
Network collectives they call — ReduceScatter of histogram buffers,
Allreduce(max-gain SplitInfo), GlobalSyncUpBySum).

Mapping (SURVEY.md §3.5):
  * rows sharded over the mesh DATA_AXIS (reference: pre_partition row split);
  * each shard histograms its local rows, then `jax.lax.psum` merges the
    (3, F, B) histogram across the axis — standing in for the reference's
    ReduceScatter + per-rank feature ownership.  Because every shard then
    holds the GLOBAL histogram, split finding is replicated and the
    SyncUpGlobalBestSplit Allreduce disappears entirely: all shards compute
    the same argmax deterministically.
  * per-row leaf ids stay shard-local; tree arrays come out replicated.

This collapses the reference's 3-collective-per-split protocol into one psum
per histogram — the right trade on ICI where bandwidth is plentiful and
latency dominates.  A psum_scatter + owned-feature variant (closer to the
reference at DCN scale) is the voting-parallel path's job.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.split import SplitParams
from ..ops.treegrow import TreeArrays, grow_tree
from jax import shard_map
from .mesh import DATA_AXIS, data_axis_size


class ShardedData:
    """Training arrays laid out over the mesh's data axis (rows padded to a
    multiple of the axis size; padding rows carry row_mask=0 so they never
    contribute to histograms)."""

    def __init__(self, mesh: Mesh, bins: np.ndarray, num_bins_pf: np.ndarray,
                 missing_bin_pf: np.ndarray, *, process_local: bool = False):
        """process_local=True (reference: pre_partition): `bins` holds only
        THIS process's rows; the global array is assembled from per-process
        shards (each process pads its share to a per-device multiple), so no
        rank ever materializes the full dataset."""
        self.mesh = mesh
        n, f = bins.shape
        self.n_devices = mesh.devices.size
        self.process_local = process_local and jax.process_count() > 1
        self.row_sharding = NamedSharding(mesh, P(DATA_AXIS))
        self.rep_sharding = NamedSharding(mesh, P())
        if self.process_local:
            local_dev = self.n_devices // jax.process_count()
            pad = (-n) % max(local_dev, 1)
            self.num_data = n  # LOCAL rows (Dataset holds the local shard)
            self.local_padded = n + pad
            self.padded = self.local_padded * jax.process_count()
        else:
            pad = (-n) % self.n_devices
            self.num_data = n
            self.padded = n + pad
            self.local_padded = self.padded
        if pad:
            bins = np.concatenate([bins, np.zeros((pad, f), bins.dtype)], axis=0)
        row_valid = np.zeros(self.local_padded, bool)
        row_valid[:n] = True
        self.bins = self._put_rows(bins)
        self.row_valid = self._put_rows(row_valid)
        self.num_bins_pf = jax.device_put(num_bins_pf, self.rep_sharding)
        self.missing_bin_pf = jax.device_put(missing_bin_pf, self.rep_sharding)

    def _put_rows(self, arr: np.ndarray) -> jnp.ndarray:
        if self.process_local:
            return jax.make_array_from_process_local_data(
                self.row_sharding, np.asarray(arr)
            )
        return jax.device_put(arr, self.row_sharding)

    def pad_rows(self, arr: np.ndarray, fill=0.0) -> jnp.ndarray:
        pad = self.local_padded - self.num_data
        if pad:
            a = np.asarray(arr)  # convert ONCE; metadata reads off the binding
            arr = np.concatenate(
                [a, np.full((pad,) + a.shape[1:], fill, a.dtype)])
        return self._put_rows(arr)

    def local_rows(self, global_arr) -> np.ndarray:
        """Extract THIS process's rows of a row-sharded global array
        (ordered by each shard's global offset), trimmed of padding."""
        shards = sorted(global_arr.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        out = np.concatenate([np.asarray(s.data) for s in shards], axis=0)
        return out[: self.num_data]

    def pad_rows_device(self, arr, dtype, fill=0.0) -> jnp.ndarray:
        """Pad + reshard WITHOUT a host round-trip (the async rounds-grower
        path: grad/hess/masks are already device arrays)."""
        if self.process_local:
            # device_put with a global sharding would treat every rank's
            # [local, zeros] as the same global array and feed rank 1+ the
            # zero padding; go through the per-process assembly path (one
            # host hop — correctness over pipelining in multi-controller)
            return self.pad_rows(np.asarray(jnp.asarray(arr, dtype)), fill)
        arr = jnp.asarray(arr, dtype)
        pad = self.padded - self.num_data
        if pad:
            arr = jnp.concatenate(
                [arr, jnp.full((pad,) + arr.shape[1:], fill, dtype)]
            )
        return jax.device_put(arr, self.row_sharding)

    def bins_t(self, f_pad: Optional[int] = None) -> jnp.ndarray:
        """Feature-major (F_pad, N_padded) device copy of the bins, rows
        sharded over the mesh data axis — the windowed grower's layout
        (column slices of (F, N) are ~20x cheaper than row gathers of
        (N, F); ops/treegrow_windowed.py).  ``f_pad`` zero-pads the
        feature dim (the psum_scatter merge needs F divisible by the axis
        size; pad features carry num_bins=1 and a False feature_mask so
        they can never win a split).  Built once device-side (a sharded
        transpose — XLA routes the all-to-all) and cached."""
        key = int(f_pad or 0)
        cache = getattr(self, "_bins_t_cache", None)
        if cache is None:
            cache = self._bins_t_cache = {}
        if key not in cache:
            f = self.bins.shape[1]
            cache[key] = _bins_t_builder(self.mesh, f, f_pad or f)(self.bins)
        return cache[key]


@functools.lru_cache(maxsize=16)
def _bins_t_builder(mesh: Mesh, f: int, f_pad: int):
    """Cached jitted sharded transpose (rows-sharded (N, F) -> rows-sharded
    feature-major (F_pad, N)) — one trace per (mesh, shape) config."""
    def t(b):
        bt = b.T
        if f_pad > f:
            bt = jnp.concatenate(
                [bt, jnp.zeros((f_pad - f, b.shape[0]), b.dtype)])
        return bt

    return jax.jit(t, out_shardings=NamedSharding(mesh, P(None, DATA_AXIS)))


@functools.lru_cache(maxsize=64)
def _sharded_grower(mesh, grower, extra_names: tuple, grower_kwargs: tuple):
    """Cached jitted shard_map wrapper around a grower function.  Cached so
    repeated boosting iterations reuse one trace/compile (the closure would
    otherwise key a fresh jit every call); shared by the strict and rounds
    growers so the shard_map plumbing cannot diverge."""
    kwargs = dict(grower_kwargs)

    def wrapped(bins, grad_, hess_, mask_, sw_, fmask_, nbpf_, mbpf_, *extras):
        return grower(
            bins, grad_, hess_, mask_, sw_, fmask_, nbpf_, mbpf_,
            **dict(zip(extra_names, extras)), **kwargs,
        )

    return jax.jit(
        shard_map(
            wrapped,
            mesh=mesh,
            in_specs=(
                P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS),
                P(DATA_AXIS), P(), P(), P(),
            ) + tuple(P() for _ in extra_names),
            out_specs=(
                TreeArrays(*([P()] * len(TreeArrays._fields))),  # replicated
                P(DATA_AXIS),  # leaf_id
            ),
            check_vma=False,
        )
    )


def _run_sharded(sharded, grower, opt, grower_kwargs, grad, hess, row_mask,
                 sample_weight, feature_mask):
    extra_names = tuple(k for k, v in opt.items() if v is not None)
    extra_vals = tuple(opt[k] for k in extra_names)
    fn = _sharded_grower(sharded.mesh, grower, extra_names,
                         tuple(sorted(grower_kwargs.items())))
    return fn(
        sharded.bins, grad, hess, row_mask, sample_weight, feature_mask,
        sharded.num_bins_pf, sharded.missing_bin_pf, *extra_vals,
    )


def grow_tree_data_parallel(
    sharded: ShardedData,
    grad: jnp.ndarray,  # (Npad,) sharded over DATA_AXIS
    hess: jnp.ndarray,
    row_mask: jnp.ndarray,  # (Npad,) bool sharded — bagging AND validity
    sample_weight: jnp.ndarray,
    feature_mask: jnp.ndarray,  # (F,) replicated
    categorical_mask: Optional[jnp.ndarray] = None,
    monotone_constraints: Optional[jnp.ndarray] = None,
    interaction_sets: Optional[jnp.ndarray] = None,
    rng_key: Optional[jnp.ndarray] = None,  # replicated — identical per-node
    # sampling on every shard keeps the SPMD trees in lockstep
    feature_contri: Optional[jnp.ndarray] = None,  # (F,) replicated
    *,
    num_leaves: int,
    num_bins: int,
    max_depth: int = -1,
    params: SplitParams = SplitParams(),
    hist_strategy: str = "auto",
    parallel_mode: str = "data",  # "data" or "voting" (rows sharded in both)
    top_k: int = 20,
    monotone_method: str = "basic",
) -> Tuple[TreeArrays, jnp.ndarray]:
    """SPMD tree growth: identical trees on every shard, shard-local leaf ids.

    reference call-stack analogue: DataParallelTreeLearner::Train (SURVEY.md
    §4.4) with psum in place of ReduceScatter/Allreduce.
    """
    opt = {
        "categorical_mask": categorical_mask,
        "monotone_constraints": monotone_constraints,
        "interaction_sets": interaction_sets,
        "rng_key": rng_key,
        "feature_contri": feature_contri,
    }
    kw = dict(
        num_leaves=num_leaves, num_bins=num_bins, max_depth=max_depth,
        params=params, hist_strategy=hist_strategy, axis_name=DATA_AXIS,
        parallel_mode=parallel_mode, top_k=top_k,
        monotone_method=monotone_method,
    )
    return _run_sharded(sharded, grow_tree, opt, kw, grad, hess, row_mask,
                        sample_weight, feature_mask)


def grow_tree_fast_data_parallel(
    sharded: ShardedData,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    row_mask: jnp.ndarray,
    sample_weight: jnp.ndarray,
    feature_mask: jnp.ndarray,
    categorical_mask: Optional[jnp.ndarray] = None,
    monotone_constraints: Optional[jnp.ndarray] = None,
    interaction_sets: Optional[jnp.ndarray] = None,
    rng_key: Optional[jnp.ndarray] = None,
    quant_key: Optional[jnp.ndarray] = None,
    cegb_feature_penalty: Optional[jnp.ndarray] = None,
    feature_contri: Optional[jnp.ndarray] = None,  # (F,) replicated
    *,
    num_leaves: int,
    num_bins: int,
    max_depth: int = -1,
    params: SplitParams = SplitParams(),
    leaf_tile: int = 8,
    hist_precision: str = "f32",
    use_pallas: bool = True,
    quantize_bins: int = 0,
    stochastic_rounding: bool = True,
    quant_renew: bool = False,
    track_path: bool = False,
    monotone_method: str = "basic",
) -> Tuple[TreeArrays, jnp.ndarray]:
    """Round-batched grower under SPMD data parallelism: each shard runs the
    multi-leaf histogram pass over its rows, one psum per round merges the
    (tile, 3, F, B) block, and every shard applies the identical splits
    (reference analogue: DataParallelTreeLearner with the multi-leaf pass
    replacing per-split ReduceScatter rounds).  Intermediate monotone
    bounds work unchanged: leaf aggregates are psummed, so every shard's
    bound recomputation sees identical state."""
    from ..ops.treegrow_fast import grow_tree_fast

    opt = {
        "categorical_mask": categorical_mask,
        "monotone_constraints": monotone_constraints,
        "interaction_sets": interaction_sets,
        "rng_key": rng_key,
        "quant_key": quant_key,
        "cegb_feature_penalty": cegb_feature_penalty,
        "feature_contri": feature_contri,
    }
    kw = dict(
        num_leaves=num_leaves, num_bins=num_bins, max_depth=max_depth,
        params=params, axis_name=DATA_AXIS, leaf_tile=leaf_tile,
        hist_precision=hist_precision, use_pallas=use_pallas,
        quantize_bins=quantize_bins, stochastic_rounding=stochastic_rounding,
        quant_renew=quant_renew, track_path=track_path,
        monotone_method=monotone_method,
    )
    return _run_sharded(sharded, grow_tree_fast, opt, kw, grad, hess,
                        row_mask, sample_weight, feature_mask)


@functools.partial(jax.jit, static_argnames=("axis_name",))
def _psum_scalar(x, axis_name: str):
    return jax.lax.psum(x, axis_name)


@functools.lru_cache(maxsize=8)
def _metric_sums_fn(mesh: Mesh):
    """Cached per-mesh reduction jit: building it inline in
    distributed_metric_sums keyed a fresh trace every eval round (jaxlint R2)."""
    return jax.jit(
        shard_map(
            lambda l, w: (jax.lax.psum(l, DATA_AXIS), jax.lax.psum(w, DATA_AXIS)),
            mesh=mesh,
            in_specs=(P(DATA_AXIS), P(DATA_AXIS)),
            out_specs=(P(), P()),
            check_vma=False,
        )
    )


def distributed_metric_sums(mesh: Mesh, local_loss_sum: jnp.ndarray, local_weight_sum: jnp.ndarray):
    """Distributed metric reduction (reference: Network::GlobalSyncUpBySum used
    by Metric::Eval in every distributed mode)."""
    return _metric_sums_fn(mesh)(local_loss_sum, local_weight_sum)


# ---------------------------------------------------------------------------
# sharded fused windowed rounds (docs/DISTRIBUTED.md "Sharded fused rounds")
#
# The one-dispatch windowed round (ops/treegrow_windowed.py) under SPMD:
# each rank histograms its LOCAL row shard's window and the leaf-histogram
# merge is a single collective INSIDE the already-donated dispatch — psum
# (merge="psum": replicated histograms + replicated split search, the ICI
# default) or psum_scatter (merge="scatter": owned-feature split search +
# in-dispatch winner election, the reference's ReduceScatter analogue).
# The host loop is the IDENTICAL async protocol (_run_fused_rounds): 1
# dispatch, 0 blocking syncs, 0 retraces per steady-state round PER RANK,
# with the 5-scalar info vector collective-merged on device so the
# one-round-behind W-ladder/whint/finite reads are rank-consistent.
# ---------------------------------------------------------------------------

def _windowed_state_spec(merge: str):
    from ..ops.split import BestSplit
    from ..ops.treegrow_windowed import WState

    hist = P() if merge == "psum" else P(None, None, DATA_AXIS, None)
    return WState(
        order=P(DATA_AXIS), leaf_start=P(DATA_AXIS), leaf_cnt=P(DATA_AXIS),
        leaf_id=P(DATA_AXIS), hist=hist,
        best=BestSplit(*([P()] * len(BestSplit._fields))),
        leaf_sum_g=P(), leaf_sum_h=P(), leaf_count=P(), leaf_depth=P(),
        leaf_parent=P(), leaf_side=P(), num_leaves_cur=P(), leaf_out=P(),
        tree=TreeArrays(*([P()] * len(TreeArrays._fields))),
    )


# per-optional-input sharding: row-indexed arrays ride the data axis,
# everything else is replicated
_WOPT_SPECS = {
    "gq": P(DATA_AXIS), "hq": P(DATA_AXIS), "quant_scale": P(),
    "rng_key": P(), "quant_key": P(), "feature_contri": P(),
    "categorical_mask": P(),
}


@functools.lru_cache(maxsize=32)
def _windowed_init_sharded(mesh: Mesh, merge: str, extra_names: tuple,
                           statics: tuple):
    from ..ops import treegrow_windowed as _tw

    kwargs = dict(statics)
    quant = bool(kwargs.get("quantize_bins"))

    def wrapped(bins_t, grad, hess, row_mask, sw, nbpf, mbpf, fmask, *extras):
        ex = dict(zip(extra_names, extras))
        return _tw._w_init.__wrapped__(
            bins_t, grad, hess, row_mask, sw, nbpf, mbpf, fmask,
            ex.get("rng_key"), ex.get("quant_key"), ex.get("feature_contri"),
            ex.get("categorical_mask"), None, None, None,
            axis_name=DATA_AXIS, merge=merge, **kwargs)

    state_spec = _windowed_state_spec(merge)
    row = P(DATA_AXIS)
    qspec = (row, row, P()) if quant else (None, None, None)
    return jax.jit(shard_map(
        wrapped, mesh=mesh,
        in_specs=(P(None, DATA_AXIS), row, row, row, row, P(), P(), P())
        + tuple(_WOPT_SPECS[n] for n in extra_names),
        out_specs=(state_spec, row, row) + qspec + (row, row),
        check_vma=False,
    ))


@functools.lru_cache(maxsize=256)
def _windowed_round_sharded(mesh: Mesh, W: int, merge: str,
                            extra_names: tuple, statics: tuple):
    """One cached donated jit per (mesh, W-ladder rung, merge, statics) —
    the SPMD mirror of the single-device ladder's per-rung compiles."""
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
            W=W, axis_name=DATA_AXIS, merge=merge, **kwargs)

    state_spec = _windowed_state_spec(merge)
    row = P(DATA_AXIS)
    return jax.jit(shard_map(
        wrapped, mesh=mesh,
        in_specs=(state_spec, P(None, DATA_AXIS), row, row, row,
                  P(), P(), P())
        + tuple(_WOPT_SPECS[n] for n in extra_names),
        out_specs=(state_spec, P()),  # info is collective-merged on device
        check_vma=False,
    ), donate_argnums=(0,))


@functools.lru_cache(maxsize=32)
def _windowed_finalize_sharded(mesh: Mesh, merge: str, statics: tuple):
    from ..ops import treegrow_windowed as _tw

    kwargs = dict(statics)

    def wrapped(state, grad_true, hess_true, row_mask):
        return _tw._w_finalize.__wrapped__(
            state, grad_true, hess_true, row_mask,
            axis_name=DATA_AXIS, **kwargs)

    row = P(DATA_AXIS)
    return jax.jit(shard_map(
        wrapped, mesh=mesh,
        in_specs=(_windowed_state_spec(merge), row, row, row),
        out_specs=(TreeArrays(*([P()] * len(TreeArrays._fields))), row),
        check_vma=False,
    ))


def _pad_features(v, f_pad: int, fill, sharding):
    """Pad a per-feature table to the scatter merge's F multiple (pad
    features are dead: num_bins=1, mask False — they can never win)."""
    if v is None:
        return None
    v = jnp.asarray(v)
    if v.shape[0] < f_pad:
        v = jnp.concatenate(
            [v, jnp.full((f_pad - v.shape[0],) + v.shape[1:], fill, v.dtype)])
    return jax.device_put(v, sharding)


def grow_tree_windowed_data_parallel(
    sharded: ShardedData,
    grad: jnp.ndarray,  # (Npad,) sharded over DATA_AXIS
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
    merge: str = "psum",  # "psum" | "scatter" (owned-feature ReduceScatter)
    stats: Optional[dict] = None,
    guard_label: str = "",
    megakernel_opt: Optional[str] = None,
) -> Tuple[TreeArrays, jnp.ndarray]:
    """SPMD fused windowed growth: the flagship one-dispatch round over the
    ICI mesh.  Each steady-state round is ONE donated dispatch and ZERO
    blocking host syncs per rank (pinned by tests/test_retrace.py with the
    DispatchCounter, telemetry and tracing on); the histogram merge and the
    info-vector reduction both ride inside that dispatch.

    ``merge="scatter"`` pays when split search dominates (owned features
    parallelize it R-ways and the merge moves half the bytes) but requires
    deterministic replicated admission — it is refused with per-node
    feature sampling (feature_fraction_bynode/extra_trees), whose sampled
    set must span the full feature axis on every rank."""
    from ..ops import treegrow_windowed as _tw
    from ..utils import degrade as _degrade

    if merge not in ("psum", "scatter"):
        raise ValueError(f"merge must be 'psum' or 'scatter', got {merge!r}")
    if merge == "scatter" and (
            rng_key is not None or params.feature_fraction_bynode < 1.0
            or params.extra_trees):
        raise ValueError(
            "merge='scatter' (owned-feature split search) is incompatible "
            "with per-node feature sampling (feature_fraction_bynode/"
            "extra_trees): each rank samples only its owned block; use "
            "merge='psum'")
    mesh = sharded.mesh
    n_dev = data_axis_size(mesh)
    f = int(sharded.num_bins_pf.shape[0])
    f_pad = (-(-f // n_dev) * n_dev) if merge == "scatter" else f
    rep = sharded.rep_sharding
    bins_t = sharded.bins_t(f_pad if f_pad != f else None)
    nbpf = _pad_features(sharded.num_bins_pf, f_pad, 1, rep)
    mbpf = _pad_features(sharded.missing_bin_pf, f_pad, -1, rep)
    fmask = _pad_features(jnp.asarray(feature_mask, bool), f_pad, False, rep)
    cmask = _pad_features(categorical_mask, f_pad, False, rep)
    fcontri = _pad_features(feature_contri, f_pad, 1.0, rep)

    use_pallas = bool(use_pallas and _degrade.available(_degrade.HIST))
    # round megakernel (ops/round_pallas.py) under SPMD: each rank's
    # partition + window histogram is one fused kernel; the leaf-histogram
    # merge stays the round's single in-dispatch collective (psum /
    # psum_scatter below, UNCHANGED), so the split search runs post-merge
    # exactly as before.  Same envelope gate as the single-device entry.
    mk, mk_interp = _tw.megakernel_mode(use_pallas, rng_key=rng_key,
                                        efb_bins_t=None,
                                        quantize_bins=quantize_bins,
                                        mode=megakernel_opt)
    common = dict(num_leaves=num_leaves, num_bins=num_bins, params=params,
                  leaf_tile=leaf_tile)

    def _grow(megakernel: bool, mk_interpret: bool):
        init_statics = tuple(sorted(dict(
            common, use_pallas=use_pallas, quantize_bins=quantize_bins,
            hist_precision=hist_precision,
            stochastic_rounding=stochastic_rounding).items()))
        init_opt = {"rng_key": rng_key, "quant_key": quant_key,
                    "feature_contri": fcontri, "categorical_mask": cmask}
        init_names = tuple(k for k, v in init_opt.items() if v is not None)
        init_fn = _windowed_init_sharded(mesh, merge, init_names,
                                         init_statics)
        state, g_d, h_d, gq, hq, qs, g_true, h_true = init_fn(
            bins_t, grad, hess, row_mask, sample_weight, nbpf, mbpf, fmask,
            *(init_opt[k] for k in init_names))

        round_statics = tuple(sorted(dict(
            common, max_depth=max_depth, use_pallas=use_pallas,
            quantize_bins=quantize_bins, hist_precision=hist_precision,
            has_cat=categorical_mask is not None,
            pallas_partition=_tw.PALLAS_PARTITION,
            megakernel=megakernel, mk_interpret=mk_interpret).items()))
        round_opt = {"gq": gq, "hq": hq, "quant_scale": qs,
                     "rng_key": rng_key, "feature_contri": fcontri,
                     "categorical_mask": cmask}
        round_names = tuple(k for k, v in round_opt.items()
                            if v is not None)
        round_vals = tuple(round_opt[k] for k in round_names)

        def round_fn(st, W):
            fn = _windowed_round_sharded(mesh, W, merge, round_names,
                                         round_statics)
            return fn(st, bins_t, g_d, h_d, row_mask, nbpf, mbpf, fmask,
                      *round_vals)

        # each rank's window is bounded by its LOCAL rows (the globally-
        # small child can hold all of one rank's rows of its ancestor —
        # the halving argument is global, so the local ladder starts at
        # the full shard)
        n_loc = sharded.padded // n_dev
        state = _tw._run_fused_rounds(
            round_fn, state, n_ladder=n_loc,
            w_first=_tw._window_size(max(n_loc, 1), n_loc),
            num_leaves=num_leaves, stats=stats, guard_label=guard_label)

        fin_statics = tuple(sorted(dict(
            params=params,
            quant_renew=bool(quant_renew and quantize_bins)).items()))
        fin = _windowed_finalize_sharded(mesh, merge, fin_statics)
        return fin(state, g_true, h_true, row_mask)

    if not mk:
        return _grow(False, False)
    if mk_interp:
        # correctness harness: registry ignored, failures surface (the
        # single-device entry's interpret contract)
        from ..utils import faults as _faults

        _faults.maybe_fail("pallas_round")
        return _grow(True, True)
    # the LAYERED degrade net, sharded edition: a megakernel failure at
    # compile/execute time disables ROUND and regrows this tree on the
    # three-pass sharded round from the ORIGINAL inputs (only internal
    # WState buffers were donated to the failed dispatch)
    return _degrade.run_with_fallback(
        _degrade.ROUND, lambda: _grow(True, False),
        lambda: _grow(False, False), fault_site="pallas_round")
