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
from .mesh import DATA_AXIS


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
