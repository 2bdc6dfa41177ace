"""Feature-parallel tree learning over a device mesh.

TPU-native re-design of the reference's feature-parallel learner
(reference: src/treelearner/feature_parallel_tree_learner.cpp —
FeatureParallelTreeLearner<...>: every machine holds ALL rows, features are
partitioned; each finds the best split on its own features;
SyncUpGlobalBestSplit Allreduces the max-gain SplitInfo; all machines apply
the identical split locally).

Mapping (SURVEY.md §3.5 "TP-analog"):
  * the binned matrix is sharded on the FEATURE axis (columns), rows
    replicated — the model/width-dimension sharding of GBDT;
  * per-shard local best split -> `pmax` gain + lowest-rank winner broadcast
    (ops/treegrow.py mode="feature");
  * the partition decision for the winning feature is computed on its owner
    shard and broadcast with a psum — replacing the reference's "no row
    exchange needed because data is replicated" with one tiny collective.

Features are padded to a multiple of the axis size with trivial columns
(1 bin, never splittable), mirroring the reference's uneven feature
partition handling.
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


class FeatureShardedData:
    """Training arrays laid out with features sharded over the mesh axis."""

    def __init__(self, mesh: Mesh, bins: np.ndarray, num_bins_pf: np.ndarray,
                 missing_bin_pf: np.ndarray):
        self.mesh = mesh
        n, f = bins.shape
        self.n_devices = mesh.devices.size
        pad = (-f) % self.n_devices
        self.num_feature = f
        self.padded_f = f + pad
        if pad:
            # trivial pad features: constant bin 0, 1 bin, no missing stream
            bins = np.concatenate([bins, np.zeros((n, pad), bins.dtype)], axis=1)
            num_bins_pf = np.concatenate([num_bins_pf, np.ones(pad, np.int32)])
            missing_bin_pf = np.concatenate([missing_bin_pf, np.full(pad, -1, np.int32)])
        self.col_sharding = NamedSharding(mesh, P(None, DATA_AXIS))
        self.f_sharding = NamedSharding(mesh, P(DATA_AXIS))
        self.rep_sharding = NamedSharding(mesh, P())
        self.bins = jax.device_put(bins, self.col_sharding)
        self.num_bins_pf = jax.device_put(np.asarray(num_bins_pf, np.int32), self.f_sharding)
        self.missing_bin_pf = jax.device_put(np.asarray(missing_bin_pf, np.int32), self.f_sharding)

    def pad_features(self, arr: np.ndarray, fill=0) -> jnp.ndarray:
        """Pad a per-feature array and shard it over the mesh axis."""
        arr = np.asarray(arr)
        pad = self.padded_f - self.num_feature
        if pad:
            arr = np.concatenate([arr, np.full((pad,) + arr.shape[1:], fill, arr.dtype)])
        return jax.device_put(arr, self.f_sharding)

    def pad_sets(self, arr: np.ndarray) -> jnp.ndarray:
        """Pad interaction sets (S, F) on the feature axis and shard."""
        arr = np.asarray(arr)
        pad = self.padded_f - self.num_feature
        if pad:
            arr = np.concatenate(
                [arr, np.zeros((arr.shape[0], pad), arr.dtype)], axis=1
            )
        return jax.device_put(arr, NamedSharding(self.mesh, P(None, DATA_AXIS)))


@functools.lru_cache(maxsize=64)
def _fp_grower(mesh: Mesh, names: tuple, num_leaves: int, num_bins: int,
               max_depth: int, params: SplitParams, hist_strategy: str,
               monotone_method: str):
    """Cached jitted shard_map wrapper for feature-parallel growth: building
    the closure inline retraced EVERY boosting iteration (jaxlint R2); caching
    on (mesh, extras, static config) reuses one trace/compile, matching
    data_parallel._sharded_grower."""
    spec_of = {
        "categorical_mask": P(DATA_AXIS),
        "monotone_constraints": P(DATA_AXIS),
        "interaction_sets": P(None, DATA_AXIS),
        "rng_key": P(),
        "feature_contri": P(DATA_AXIS),
    }

    def wrapped(bins, grad_, hess_, mask_, sw_, fmask_, nbpf_, mbpf_, *extras):
        return grow_tree(
            bins, grad_, hess_, mask_, sw_, fmask_, nbpf_, mbpf_,
            **dict(zip(names, extras)),
            num_leaves=num_leaves,
            num_bins=num_bins,
            max_depth=max_depth,
            params=params,
            hist_strategy=hist_strategy,
            axis_name=DATA_AXIS,
            parallel_mode="feature",
            monotone_method=monotone_method,
        )

    return jax.jit(
        shard_map(
            wrapped,
            mesh=mesh,
            in_specs=(
                P(None, DATA_AXIS),  # bins: columns sharded
                P(),  # grad (replicated rows)
                P(),  # hess
                P(),  # row_mask
                P(),  # sample_weight
                P(DATA_AXIS),  # feature_mask
                P(DATA_AXIS),  # num_bins_pf
                P(DATA_AXIS),  # missing_bin_pf
            ) + tuple(spec_of[k] for k in names),
            out_specs=(
                TreeArrays(*([P()] * len(TreeArrays._fields))),  # replicated
                P(),  # leaf_id replicated (all shards hold all rows)
            ),
            check_vma=False,
        )
    )


def grow_tree_feature_parallel(
    sharded: FeatureShardedData,
    grad: jnp.ndarray,  # (N,) replicated
    hess: jnp.ndarray,
    row_mask: jnp.ndarray,  # (N,) bool replicated
    sample_weight: jnp.ndarray,
    feature_mask: jnp.ndarray,  # (F,) host array — padded+sharded here
    categorical_mask: Optional[jnp.ndarray] = None,
    monotone_constraints: Optional[jnp.ndarray] = None,
    interaction_sets: Optional[jnp.ndarray] = None,
    rng_key: Optional[jnp.ndarray] = None,
    feature_contri: Optional[jnp.ndarray] = None,  # (F,) host array
    *,
    num_leaves: int,
    num_bins: int,
    max_depth: int = -1,
    params: SplitParams = SplitParams(),
    hist_strategy: str = "auto",
    monotone_method: str = "basic",
) -> Tuple[TreeArrays, jnp.ndarray]:
    """SPMD feature-parallel growth: identical trees on every shard.

    NOTE: per-node RNG (extra_trees / feature_fraction_bynode) uses the same
    key on every shard but operates on different feature blocks, so the
    node-level sampling stays consistent shard-locally — matching the
    reference where each machine samples only its own features.
    """
    mesh = sharded.mesh
    fmask = sharded.pad_features(np.asarray(feature_mask, bool), fill=False)
    opt = {}
    if categorical_mask is not None:
        opt["categorical_mask"] = sharded.pad_features(
            np.asarray(categorical_mask, bool), fill=False
        )
    if monotone_constraints is not None:
        opt["monotone_constraints"] = sharded.pad_features(
            np.asarray(monotone_constraints, np.int32), fill=0
        )
    if interaction_sets is not None:
        opt["interaction_sets"] = sharded.pad_sets(np.asarray(interaction_sets, bool))
    if rng_key is not None:
        opt["rng_key"] = rng_key
    if feature_contri is not None:
        opt["feature_contri"] = sharded.pad_features(
            np.asarray(feature_contri, np.float32), fill=0.0
        )
    names = list(opt.keys())
    vals = tuple(opt[k] for k in names)
    fn = _fp_grower(mesh, tuple(names), num_leaves, num_bins, max_depth,
                    params, hist_strategy, monotone_method)
    rep = sharded.rep_sharding
    return fn(
        sharded.bins,
        jax.device_put(grad, rep),
        jax.device_put(hess, rep),
        jax.device_put(row_mask, rep),
        jax.device_put(sample_weight, rep),
        fmask,
        sharded.num_bins_pf,
        sharded.missing_bin_pf,
        *vals,
    )
