"""Gradient-boosting orchestration.

Reference: src/boosting/gbdt.cpp (GBDT::{Init,TrainOneIter,UpdateScore,
RollbackOneIter}), gbdt_model_text.cpp (SaveModelToString/LoadModelFromString),
dart.hpp, rf.hpp, sample_strategy.cpp / bagging.hpp / goss.hpp,
score_updater.hpp.

TPU-first structure: the boosting loop stays in Python (it is inherently
sequential — one tree depends on the previous scores), but every O(N) step is
a jitted device op: gradient computation, tree growth (ops/treegrow.py), and
the score update `score += leaf_value[leaf_id]`, which needs no traversal since
tree growth maintains per-row leaf ids for ALL rows (the partition-based fast
path of ScoreUpdater::AddScore).  It is written as a compare-and-select over
the tree's leaf values and not as a gather (`_add_leaf_scores`): a gather
costs this chip 4 to 10 ns an index whatever the table's size.
"""

from __future__ import annotations

import functools
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..config import Config
from ..metrics import Metric, create_metrics
from ..objectives import Objective, create_objective
from ..obs import metrics as _obs
from ..obs import trace as _trace
from ..ops.split import SplitParams
from ..ops.treegrow import grow_tree
from ..ops import predict as predict_ops
from ..utils import faults as _faults
from ..utils import profiling as _profiling  # the phase scopes; importing
# also installs the jax.profiler bridge of the spans: a step per boost_round
# always, every span under LGBMTPU_JAX_PROFILER=1 (obs/ itself stays jax-free)
from ..utils import locktrace as _lt
from ..utils import sanitizer as _san
from .tree import Tree, tree_from_device

_MODEL_VERSION = "v4"

# serving bucket ladder: predict batches pad N up to the next power of two
# (floor 8) so the jitted traversal compiles once per bucket instead of once
# per distinct batch size.  Padding rows are masked on device; the padded
# result is bit-identical to the unpadded one (rows traverse independently).
_PREDICT_BUCKET_MIN = 8


def _predict_bucket(n: int) -> int:
    """Row-bucket for a batch of n rows; LGBMTPU_PREDICT_BUCKETS=0 disables
    (exact shapes — one compile per distinct N, the pre-round-9 behavior)."""
    if os.environ.get("LGBMTPU_PREDICT_BUCKETS", "1") == "0":
        return n
    b = _PREDICT_BUCKET_MIN
    while b < n:
        b <<= 1
    return b


@functools.lru_cache(maxsize=64)
def _sharded_raw_entry(mesh, k: int, has_cat: bool):
    """Giant-batch serving entry: the stacked traversal as ONE SPMD
    dispatch over the row ("data") axis of ``mesh``.

    Rows traverse independently and the per-row tree sum keeps the exact
    single-device reduction order inside each rank, so the row-sharded
    result is BITWISE the single-device ``predict_raw`` — the same
    property that makes the bucket ladder safe makes the row split safe.
    The body has ZERO collectives (each rank emits exactly its own row
    block); the packed per-tree tables ride replicated.  On a 2-D
    (feature x row) training mesh ``P(data)`` shards rows and replicates
    over the feature axis, so the training mesh is directly servable."""
    from jax.sharding import PartitionSpec as _P

    from ..parallel.mesh import DATA_AXIS as _AX

    row, rep = _P(_AX), _P()

    def run(x, active, sf, th, dl, mt, lc, rc, nl, lv, *cat):
        ckw = {}
        if has_cat:
            ckw = dict(is_cat=cat[0], cat_base=cat[1], cat_nwords=cat[2],
                       cat_words=cat[3])
        if k == 1:
            return predict_ops.predict_raw_values(
                x, sf, th, dl, mt, lc, rc, nl, lv, active=active, **ckw)
        return predict_ops.predict_raw_multiclass(
            x, sf, th, dl, mt, lc, rc, nl, lv, active=active, k=k, **ckw)

    in_specs = (row, row) + (rep,) * (8 + (4 if has_cat else 0))
    return jax.jit(jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                                 out_specs=row, check_vma=False))


def _dummy_tree() -> Tree:
    """Single-leaf zero-value tree: pads the tree axis of a packed ensemble
    so every early-stop window has the same static size (contributes exactly
    0.0 to every row — leaf 0 of a num_leaves=1 tree)."""
    z32 = np.zeros(0, np.int32)
    return Tree(
        num_leaves=1, split_feature=z32, threshold=np.zeros(0, np.float64),
        threshold_bin=None, decision_type=np.zeros(0, np.uint8),
        split_gain=np.zeros(0, np.float32), left_child=z32, right_child=z32,
        internal_value=np.zeros(0, np.float64),
        internal_weight=np.zeros(0, np.float64),
        internal_count=np.zeros(0, np.int64),
        leaf_value=np.zeros(1, np.float64),
        leaf_weight=np.zeros(1, np.float64),
        leaf_count=np.zeros(1, np.int64),
    )


# rows of one (8, 128) float32 tile: a 1-D array of whole tiles is the same
# bytes on the chip as its (rows / 128, 128) reshape, so the reshape is free
_SCORE_ROW_TILE = 1024


@functools.partial(jax.jit, static_argnames=("col",), donate_argnums=(0,))
def _add_leaf_scores(score, leaf_value, shrinkage, leaf_id, col=0):
    """The score update of one tree: ``score`` (its column ``col`` where an
    iteration grows K trees and the score is ``(N, K)``) plus
    ``(leaf_value * shrinkage)[leaf_id]``, with no gather.  Each row compares
    its leaf id with every leaf and sums what the compare selects: one term
    is the leaf's value and the others are 0, so the sum is the float32 the
    gather reads (``np.array_equal``; a leaf value of -0.0 adds as +0.0).
    The product is rounded on the ``(L,)`` table before anything is selected,
    as the eager form rounded it.

    The leaves lie on the major axis and the rows on whole tiles, so XLA fuses
    compare, select and sum into one pass over the rows and stores no
    ``(L, N)`` array: 2.1 ms for 10.5M rows and 255 leaves on a v5e where the
    gather took 86 to 102 ms (PERF.md section 6, PR 35).  It costs N x L
    operations; the gather is the cheaper only beyond some 4,000 leaves,
    where the tree's passes cost eighty times either.  ``score`` is
    donated."""
    with _profiling.phase_scope("gbdt.score_update"):
        table = leaf_value * shrinkage
        n = leaf_id.shape[0]
        ids = jnp.pad(leaf_id, (0, -n % _SCORE_ROW_TILE)).reshape(-1, 128)
        leaves = jnp.arange(table.shape[0], dtype=ids.dtype)[:, None, None]
        picked = jnp.where(ids[None] == leaves, table[:, None, None],
                           jnp.float32(0))
        row_delta = jnp.sum(picked, axis=0).reshape(-1)[:n]
        if score.ndim == 1:
            return score + row_delta
        return score.at[:, col].add(row_delta)


def _f32_threshold_upper(t: np.ndarray) -> np.ndarray:
    """Round f64 thresholds UP to f32 so the device's f32 traversal keeps the
    invariant `v <= t (f64)  =>  f32(v) <= t32`: rows left of the split stay
    left.  (Plain nearest-rounding can err in both directions; the remaining
    right-side window (t, t32] is below one f32 ulp — reference traverses in
    double, include/LightGBM/tree.h NumericalDecision.)"""
    t = np.asarray(t, np.float64)
    t32 = t.astype(np.float32)
    bump = t32.astype(np.float64) < t
    return np.where(bump, np.nextafter(t32, np.float32(np.inf)), t32)


def _quantized_wide_default(*, on_tpu: bool, n_features: int,
                            max_num_bins: int, tree_learner: str,
                            tree_growth_mode: str, explicitly_set: bool,
                            has_monotone: bool, device_count: int = 1) -> bool:
    """TPU device default for int8 quantized training: only the WIDE
    wide-bin regime on the rounds grower, never overriding an explicit
    user choice, never with monotone constraints (renewal interplay).
    Pure predicate so the gate is unit-testable off-chip (the suite runs
    CPU-pinned).  tree_learner='data' takes the rounds grower only with
    multiple devices (_use_fast_dp's gate); single-device 'data' runs the
    strict grower, which trains float — enabling the default there would
    just produce contradictory logs."""
    rounds_grower = (
        (tree_learner == "serial"
         or (tree_learner == "data" and device_count > 1))
        and (tree_growth_mode == "rounds"
             or (tree_growth_mode == "auto" and on_tpu))
    )
    return (on_tpu and max_num_bins > 64 and n_features >= 256
            and rounds_grower and not explicitly_set and not has_monotone)


# guards lazy _pack_lock creation on instances that predate the lock
# (unpickled state, legacy deepcopies) — see GBDT._plock
_PACK_LOCK_INIT = _lt.lock("gbdt.pack_init")


class GBDT:
    """reference: class GBDT in src/boosting/gbdt.h."""

    average_output = False  # RF mode: predictions are averaged over trees

    def __init__(self, cfg: Config, train_set=None, objective: Optional[Objective] = None):
        self.cfg = cfg
        self.objective = objective if objective is not None else create_objective(cfg)
        self.train_set = None
        self._models: List[Tree] = []  # flattened: iter-major, class-minor
        # device trees not yet materialized to host (fast async path): the
        # round-batched grower runs whole iterations without host syncs and
        # trees are converted lazily on first host access (save/predict/...)
        self._pending: List[tuple] = []
        self.iter_ = 0
        self.num_tree_per_iteration = cfg.num_tree_per_iteration
        self.init_scores = [0.0] * self.num_tree_per_iteration
        self.best_iteration = -1
        self.feature_names: List[str] = []
        self.metrics: List[Metric] = []
        self.train_name = "training"  # overridable via valid_names (engine.py)
        self.valid_sets: List = []
        self.valid_names: List[str] = []
        self._valid_scores: List[jnp.ndarray] = []
        self._pred_cache = None
        self._pack_version = 0  # bumped by _invalidate_pred_cache
        # pack lock (round 19, lightgbm_tpu/continual): trainer-thread
        # mutations (refit/append under a live ServingRuntime) bump
        # _pack_version and evict stale entries UNDER THE SAME LOCK the
        # serving threads' _packed lookup/insert holds — an unlocked
        # bump racing a lookup could evict a dict entry mid-iteration or
        # publish a pack under a version it no longer belongs to
        self._pack_lock = _lt.rlock("gbdt.pack")
        self.binner = None
        self.rng = np.random.RandomState(cfg.seed)
        # non-finite guard rail (docs/ROBUSTNESS.md): first boosting
        # iteration (1-based) whose tree carried NaN/inf, 0 = clean.
        # Accumulated ON DEVICE per iteration (O(num_leaves), no syncs)
        # and pulled only at points that already sync (_guard_check)
        self._guard_bad_iter = jnp.asarray(0, jnp.int32)
        # telemetry is default-on and process-wide (docs/OBSERVABILITY.md);
        # an explicit telemetry= param applies for this model's lifetime,
        # and a model WITHOUT one restores the process default — so one
        # model's telemetry=false cannot silently swallow a later model's
        # metrics_file= snapshot
        _obs.set_enabled(bool(cfg.telemetry) if cfg.is_set("telemetry")
                         else _obs.DEFAULT_ENABLED)
        if train_set is not None:
            self.reset_training_data(train_set)

    # ------------------------------------------------------------------
    @property
    def models(self) -> List[Tree]:
        """Host trees; converts any pending device trees first (the fast
        grower defers tree_from_device so training never blocks on the
        host<->device round-trip — reference keeps trees host-side always)."""
        self._flush_pending()
        return self._models

    @models.setter
    def models(self, value) -> None:
        self._pending = []
        self._models = value
        self._invalidate_pred_cache("models_setter")

    def _plock(self) -> "_lt.TracedLock":
        """The pack lock, lazily recreated for instances that predate it
        (unpickled/legacy state); creation races are excluded by the
        module-level init lock."""
        lock = getattr(self, "_pack_lock", None)
        if lock is None:
            with _PACK_LOCK_INIT:
                lock = getattr(self, "_pack_lock", None)
                if lock is None:
                    lock = self._pack_lock = _lt.rlock("gbdt.pack")
        return lock

    def __getstate__(self):
        # locks cannot be pickled/deepcopied; _plock recreates on demand
        d = dict(self.__dict__)
        d.pop("_pack_lock", None)
        return d

    def __setstate__(self, d):
        self.__dict__.update(d)
        # re-create the pack lock under the SAME init lock _plock uses:
        # the old unconditional assignment raced a concurrent _plock()
        # caller — it could mint lock A (and start serving under it)
        # between the __dict__ update and this line, after which the
        # overwrite published lock B and two threads held "the" pack
        # lock simultaneously.  Create-if-absent under _PACK_LOCK_INIT
        # makes exactly one lock win both paths.
        with _PACK_LOCK_INIT:
            if getattr(self, "_pack_lock", None) is None:
                self._pack_lock = _lt.rlock("gbdt.pack")

    def _invalidate_pred_cache(self, reason: str) -> None:
        """VERSION the packed-ensemble serving cache instead of nulling it
        (round 18, lightgbm_tpu/serve): a model mutation bumps
        ``_pack_version`` — the leading component of every ``_packed``
        key — so the next predict packs fresh under the new version while
        entries of the PREVIOUS version stay resident and servable.  A
        hot swap (refit / set_leaf_output / continued training under a
        live serving runtime) therefore never cools the cache for
        in-flight predicts: a reader that grabbed the pre-mutation pack
        keeps its device arrays, and a reader racing the bump still finds
        the old entry instead of rebuilding mid-request.  Versions older
        than ``_PACKED_KEEP_VERSIONS`` are evicted here, counted in
        ``predict_stale_pack_evictions_total``.  Real invalidations (a
        populated cache bumped) are counted so serving dashboards can see
        churn — training every round vs an occasional leaf edit look very
        different here.

        Round 19 (continual training) made the bump+evict ATOMIC with the
        serving threads' ``_packed`` lookup by sharing ``_pack_lock``: a
        trainer-thread refit/append racing a coalesced predict could
        otherwise evict dict entries mid-lookup or let a pack built
        against the pre-mutation trees publish under the post-mutation
        version (tests/test_continual.py hammers exactly this)."""
        with self._plock():
            if getattr(self, "_pred_cache", None):
                _obs.counter("predict_cache_invalidations_total").inc()
                _obs.event("pred_cache_invalidate", reason=reason,
                           version=self._pack_version + 1)
            self._pack_version = getattr(self, "_pack_version", 0) + 1
            cache = getattr(self, "_pred_cache", None)
            if cache:
                floor = self._pack_version - self._PACKED_KEEP_VERSIONS
                stale = [key for key in cache if key[0] <= floor]
                for key in stale:
                    del cache[key]
                if stale:
                    _obs.counter(
                        "predict_stale_pack_evictions_total").inc(len(stale))

    def _flush_pending(self) -> None:
        if self._pending:
            self._guard_check()
            pending, self._pending = self._pending, []
            for arrays, shrink, linear_fit in pending:
                tree = tree_from_device(arrays, self.binner, linear=linear_fit)
                tree.apply_shrinkage(shrink)
                self._models.append(tree)
                if arrays.hist_passes is not None:
                    self._count_hist_passes(int(arrays.hist_passes),
                                            int(arrays.hist_blocks),
                                            int(arrays.hist_blocks_packed),
                                            tree)
                self._count_rank_work()
                # the tree's nodes, and those that split on a categorical
                # column: from the host tree the flush has just built
                _obs.counter("train_split_nodes_total").inc(
                    tree.num_leaves - 1)
                _obs.counter("train_cat_split_nodes_total").inc(tree.num_cat)

    def _count_hist_passes(self, passes: int, blocks: int, packed: int,
                           tree: Tree) -> None:
        """What the tree's histogram passes read against what the tree
        needed, from arrays the flush has on the host already (no pull on
        the hot path).  Every pass of the rounds grower streams all rows of
        the ``Dataset``; a leaf-wise learner with histogram subtraction
        needs the rows once for the root and then the smaller child of
        every split.  What the Pallas kernel put through its one-hot product
        lies between the two: sub-blocks of ``hist_pallas.SUB_BLOCK`` rows,
        as many as each row tile's rows in the pass fill (0 from a grower
        route that does not run the kernel); ``packed`` of them lay in tiles
        that packed their rows, the others in tiles multiplied whole."""
        n_rows = int(self.train_set.num_data())
        _obs.counter("train_hist_passes_total").inc(passes)
        _obs.counter("train_hist_rows_streamed_total").inc(passes * n_rows)
        _obs.counter("train_hist_rows_needed_total").inc(
            n_rows + tree.smaller_child_rows())
        _obs.counter("train_hist_blocks_multiplied_total").inc(blocks)
        _obs.counter("train_hist_blocks_packed_total").inc(packed)

    def _count_rank_work(self) -> None:
        """What a ranking objective's gradient step worked through for the
        tree: the rows, the lanes of its query buckets (padding too) and the
        pair terms formed.  Numbers of the layout, fixed at ``set_query``."""
        work = getattr(self.objective, "rank_work", None)
        if work is not None:
            rows, lanes, pairs = work
            _obs.counter("train_rank_rows_total").inc(rows)
            _obs.counter("train_rank_lanes_total").inc(lanes)
            _obs.counter("train_rank_pairs_total").inc(pairs)

    # -- non-finite guard rail (docs/ROBUSTNESS.md) --------------------
    def _guard_accumulate(self, arrays) -> None:
        """Fold this iteration's tree stats into the device-side guard
        flag: O(num_leaves) reductions, no host pull (the growers have no
        per-round host read to ride)."""
        ok = (jnp.isfinite(arrays.leaf_value).all()
              & ~jnp.isnan(arrays.split_gain).any())
        self._guard_bad_iter = jnp.where(
            (self._guard_bad_iter == 0) & ~ok,
            jnp.asarray(self.iter_ + 1, jnp.int32), self._guard_bad_iter)

    def _guard_check(self) -> None:
        """Pull and test the guard flag — callers are points that sync
        anyway (eval, flush, save, the %32 finish probe), so detection
        lags corruption by at most the sync cadence while the error stays
        stamped with the iteration the corruption ENTERED."""
        bad = int(np.asarray(self._guard_bad_iter))
        if bad:
            from ..utils.guards import NonFiniteError

            _obs.counter("train_nonfinite_errors_total").inc()
            _obs.event("nonfinite", phase="guard_check", iteration=bad)
            raise NonFiniteError(
                f"non-finite leaf values/split gains entered the model at "
                f"boosting iteration {bad}: the gradients or hessians went "
                "NaN/inf (custom objective output? fp overflow?) and every "
                "tree from that iteration on is invalid. Detection is "
                "deferred to sync points by design — the device-side guard "
                "costs no extra dispatches; see docs/ROBUSTNESS.md")

    # ------------------------------------------------------------------
    def reset_training_data(self, train_set) -> None:
        """reference: GBDT::ResetTrainingData."""
        self._fused_step = None
        self._nobag_cache = None
        self._forced_cache = None
        self._eval_jit_cache = None
        self._finish_probe = None
        if self.cfg.num_machines > 1:
            # multi-host bring-up (reference: Network::Init from machine
            # list).  MUST run before the first JAX computation — so before
            # Dataset.construct uploads anything (jax.distributed.initialize
            # rejects an already-initialized backend).
            from ..parallel.distributed import init_distributed

            init_distributed(self.cfg)
        self.train_set = train_set
        train_set.construct()
        self.binner = train_set.binner
        self.feature_names = list(train_set.feature_names)
        self.metrics = create_metrics(self.cfg)
        n = train_set.num_data()
        k = self.num_tree_per_iteration
        self._label = jnp.asarray(train_set.label, dtype=jnp.float32)
        self._weight = (
            None if train_set.weight is None else jnp.asarray(train_set.weight, jnp.float32)
        )
        shape = (n,) if k == 1 else (n, k)
        init = np.zeros(shape, dtype=np.float32)
        if self.objective is not None and hasattr(self.objective, "prepare"):
            # label-dependent objective state (is_unbalance weights etc.) is
            # needed regardless of boost_from_average
            self.objective.prepare(np.asarray(train_set.label), train_set.weight)
        if self.objective is not None and self.cfg.boost_from_average and not self.models:
            # pre-partition multi-controller runs compute the init score from
            # the GLOBAL label distribution (reference: BoostFromScore syncs
            # via Network::GlobalSyncUpBySum); equal shard sizes required
            init_label, init_weight = self._label, self._weight
            if (
                self.cfg.pre_partition
                and jax.process_count() > 1
                and self.cfg.tree_learner in ("data", "voting")
            ):
                from jax.experimental import multihost_utils

                init_label = jnp.asarray(
                    multihost_utils.process_allgather(self._label, tiled=True)
                )
                if self._weight is not None:
                    init_weight = jnp.asarray(
                        multihost_utils.process_allgather(self._weight, tiled=True)
                    )
            if k == 1:
                self.init_scores = [self.objective.boost_from_score(init_label, init_weight)]
                init += np.float32(self.init_scores[0])
            else:
                # per-class init (reference: multiclass BoostFromScore per tree id)
                self.init_scores = []
                lbl_all = np.asarray(init_label)
                w_all = None if init_weight is None else np.asarray(init_weight)
                for c in range(k):
                    lbl = (lbl_all == c).astype(np.float32)
                    p = float(lbl.mean() if w_all is None else np.average(lbl, weights=w_all))
                    p = min(max(p, 1e-15), 1 - 1e-15)
                    self.init_scores.append(float(np.log(p / (1 - p))))
                init += np.asarray(self.init_scores, dtype=np.float32)[None, :]
        if train_set.init_score is not None:
            init += np.asarray(train_set.init_score, dtype=np.float32).reshape(shape)
        self._score = jnp.asarray(init)
        if self.objective is not None and hasattr(self.objective, "set_query") and train_set.query_boundaries is not None:
            self.objective.set_query(train_set.query_boundaries, np.asarray(train_set.label))
            if (
                hasattr(self.objective, "set_positions")
                and getattr(train_set, "position", None) is not None
            ):
                self.objective.set_positions(train_set.position)
        self._split_params = self._make_split_params()
        cat_mask = np.asarray(self.binner.categorical_mask)
        self._allowed_features = jnp.ones(cat_mask.shape, dtype=bool)
        # feature_pre_filter (reference: DatasetLoader — ignore features that
        # can never produce a split satisfying min_data_in_leaf, whatever the
        # threshold or missing direction).  Exact per-feature check on bin
        # counts; numerical features only (categorical splits are subsets).
        if (
            self.cfg.feature_pre_filter
            and self.cfg.min_data_in_leaf > 1
            and getattr(train_set, "bins", None) is not None
            # out_of_core never materializes the host matrix the filter
            # scans; the (purely optimizing) filter is skipped there
            and jax.process_count() <= 1
            # multi-controller: ranks may hold different row shards, so
            # local counts could derive DIVERGENT feature masks and break
            # the identical-SPMD-program invariant; the reference filters
            # from globally-synced sample counts — until counts are psum'd
            # here, skip the (purely optimizing) filter in that mode
        ):
            bins_h = np.asarray(train_set.bins)
            nbpf_h = np.asarray(train_set.binner.num_bins_per_feature)
            mbpf_h = np.asarray(train_set.binner.missing_bin_per_feature)
            md = int(self.cfg.min_data_in_leaf)
            n_rows_h, n_feat_h = bins_h.shape
            bmax = int(nbpf_h.max()) if n_feat_h else 1
            allowed = np.ones(n_feat_h, dtype=bool)
            # one flattened bincount per feature block (not F python loops);
            # block size bounds the (N, blk) int64 temp to ~128MB
            blk = max(1, 2**24 // max(n_rows_h, 1))
            for j0 in range(0, n_feat_h, blk):
                j1 = min(j0 + blk, n_feat_h)
                nb = j1 - j0
                flat = bins_h[:, j0:j1].astype(np.int64)
                flat += np.arange(nb, dtype=np.int64)[None, :] * bmax
                counts = np.bincount(flat.ravel(), minlength=nb * bmax).reshape(nb, bmax)
                for dj in range(nb):
                    j = j0 + dj
                    if cat_mask[j] or nbpf_h[j] <= 1:
                        continue
                    cm = counts[dj].copy()
                    m = int(cm[mbpf_h[j]]) if mbpf_h[j] >= 0 else 0
                    if mbpf_h[j] >= 0:
                        cm[mbpf_h[j]] = 0
                    p = np.cumsum(cm[: int(nbpf_h[j])])[:-1]  # left counts
                    if p.size == 0:
                        continue
                    q = (n_rows_h - m) - p
                    lo, hi = np.minimum(p, q), np.maximum(p, q)
                    # the missing mass may join the smaller side
                    if not np.any((hi >= md) & (lo + m >= md)):
                        allowed[j] = False
            if not allowed.all():
                from ..utils.log import log_info
                log_info(
                    f"feature_pre_filter: {int((~allowed).sum())} feature(s) "
                    f"cannot satisfy min_data_in_leaf={md} and were excluded"
                )
                self._allowed_features = jnp.asarray(allowed)
        # pass None when no categorical features so the all-numerical jit
        # graph skips the categorical candidate evaluation entirely
        self._categorical_mask = jnp.asarray(cat_mask) if cat_mask.any() else None
        _obs.gauge("cat_features").set(int(cat_mask.sum()))
        _obs.gauge("cat_bins_longest").set(max(
            (m.num_bins for m in self.binner.mappers if m.is_categorical),
            default=0))
        # monotone constraints (reference: monotone_constraints.hpp, "basic")
        f = train_set.num_feature()
        mc = list(self.cfg.monotone_constraints or [])
        if mc and any(int(c) != 0 for c in mc):
            mc = (mc + [0] * f)[:f]
            self._monotone = jnp.asarray(np.asarray(mc, np.int32))
        else:
            self._monotone = None
        # per-feature split-gain multipliers (reference: config feature_contri
        # — gain[i] = max(0, contri[i]) * gain[i] in FindBestThreshold)
        fc = list(self.cfg.feature_contri or [])
        if fc and any(float(c) != 1.0 for c in fc):
            fc = (fc + [1.0] * f)[:f]
            self._feature_contri = jnp.asarray(np.asarray(fc, np.float32))
        else:
            self._feature_contri = None
        # interaction constraints (reference: config interaction_constraints
        # parsed into index sets; col_sampler.hpp filters per-leaf)
        sets = _parse_interaction_constraints(
            self.cfg.interaction_constraints, self.feature_names
        )
        if sets:
            mat = np.zeros((len(sets), f), dtype=bool)
            for i, st in enumerate(sets):
                for j in st:
                    if 0 <= j < f:
                        mat[i, j] = True
            self._interaction_sets = jnp.asarray(mat)
        else:
            self._interaction_sets = None
        self._needs_node_rng = bool(
            self.cfg.extra_trees or self.cfg.feature_fraction_bynode < 1.0
        )
        # growth scheduling: round-batched grower on TPU (tree_growth_mode)
        self._on_tpu = jax.devices()[0].platform == "tpu"
        if _quantized_wide_default(
                on_tpu=self._on_tpu,
                n_features=train_set.num_feature(),
                max_num_bins=train_set.max_num_bins,
                tree_learner=self.cfg.tree_learner,
                tree_growth_mode=self.cfg.tree_growth_mode,
                explicitly_set=self.cfg.is_set("use_quantized_grad"),
                has_monotone=self._monotone is not None,
                device_count=jax.device_count()):
            # TPU device default for the WIDE wide-bin regime: int8
            # quantized training.  The int8 payload carries 3 channels/leaf
            # (no bf16x2 split), doubling the Mosaic kernel's leaf tile and
            # halving admission rounds — measured Epsilon-class 400k x 2000
            # x 255 bins: 8.0 -> 5.1 s/iter.  At NARROW shapes the pass is
            # a single feature chunk and quantized ~= float within run
            # variance (measured 1M x 28 x 255: 10.7-10.9 vs 11.8 it/s),
            # so the default stays float there.  Stochastic rounding +
            # exact int32 accumulation + f32 leaf renewal keep AUC at parity
            # (0.93101 vs 0.93116 measured; docs/PERF_NOTES.md round 4).
            # An explicit use_quantized_grad either way always wins;
            # monotone runs stay float (renewal interplay, see warning
            # below).
            from ..utils.log import log_info
            self.cfg.use_quantized_grad = True
            if not self.cfg.is_set("quant_train_renew_leaf"):
                self.cfg.quant_train_renew_leaf = True
            log_info(
                "wide data with max_bin > 64 on TPU: enabling int8 "
                "quantized training (use_quantized_grad=true, leaf renewal "
                "on); set use_quantized_grad=false for the float path.")
        mode = self.cfg.tree_growth_mode
        self._use_fast = (
            self.cfg.tree_learner == "serial"
            and (mode == "rounds" or (mode == "auto" and self._on_tpu))
        )
        # rounds grower under SPMD data parallelism (voting/feature modes
        # stay on the strict grower — their cost is comms-shaped)
        self._use_fast_dp = (
            self.cfg.tree_learner == "data"
            and (mode == "rounds" or (mode == "auto" and self._on_tpu))
            and jax.device_count() > 1  # matches the _dp construction gate
        )
        # CEGB coupled per-feature penalties (reference: cegb.hpp); the
        # across-trees "feature already used anywhere" state lives here and
        # is updated on device after every tree
        if any(p != 0 for p in (self.cfg.cegb_penalty_feature_coupled or [])):
            pen = np.zeros(f, np.float32)
            for i, v in enumerate((self.cfg.cegb_penalty_feature_coupled or [])[:f]):
                pen[i] = self.cfg.cegb_tradeoff * float(v)
            self._cegb_coupled = jnp.asarray(pen)
            self._cegb_used_global = jnp.zeros((f,), bool)
        else:
            self._cegb_coupled = None
            self._cegb_used_global = None
        from ..utils.log import log_warning
        self.cfg.warn_na_params()
        if self.cfg.bagging_by_query and getattr(train_set, "query_boundaries", None) is None:
            log_warning("bagging_by_query is set but the dataset has no "
                        "query groups; falling back to row-wise bagging")
        if (
            self.cfg.forcedsplits_filename
            and self.cfg.tree_learner != "serial"
            and jax.device_count() > 1
        ):
            # the distributed wrappers (parallel/{data,feature}_parallel.py)
            # do not thread the forced schedule; warn instead of silently
            # dropping it (single-device runs fall back to the serial
            # growers, which DO apply it in both growth modes)
            log_warning(
                "forcedsplits_filename is not applied by the distributed "
                "tree learners (tree_learner=data/feature/voting on a "
                "multi-device mesh); use tree_learner=serial to force splits."
            )
        # CEGB lazy per-(row, feature) fetch charges (reference:
        # cost_effective_gradient_boosting.hpp feature_used_in_data): state
        # is (N, F) across trees, threaded through the strict serial grower
        if any(p != 0 for p in (self.cfg.cegb_penalty_feature_lazy or [])):
            lazy = np.zeros(f, np.float32)
            for i, v in enumerate((self.cfg.cegb_penalty_feature_lazy or [])[:f]):
                lazy[i] = self.cfg.cegb_tradeoff * float(v)
            self._cegb_lazy = jnp.asarray(lazy)
            self._cegb_lazy_used = jnp.zeros((train_set.num_data(), f), bool)
            if self.cfg.tree_learner != "serial" and jax.device_count() > 1:
                # the (N, F) charge state is row-global; the distributed
                # wrappers do not thread it across shards
                log_warning(
                    "cegb_penalty_feature_lazy is applied by the single-"
                    "device growers only (strict or rounds); this "
                    "distributed configuration IGNORES it."
                )
        else:
            self._cegb_lazy = None
            self._cegb_lazy_used = None
        if self._monotone is not None:
            mmethod = self.cfg.monotone_constraints_method
            if mmethod == "advanced":
                log_warning(
                    "monotone_constraints_method='advanced' is not "
                    "implemented; using 'intermediate' (measured headroom "
                    "bound: benchmarks/monotone_advanced_headroom.py)."
                )
            if (mmethod in ("intermediate", "advanced")
                    and self.cfg.use_quantized_grad
                    and self.cfg.quant_train_renew_leaf):
                log_warning(
                    "quant_train_renew_leaf is skipped under intermediate "
                    "monotone bounds: renewed leaf values cannot be "
                    "re-clipped to evolving bounds without crossing a "
                    "monotone split; leaf values keep their creation-time "
                    "(clipped, quantized) outputs."
                )
        # out-of-core spill regime (docs round 12): the binned matrix is
        # NOT device-resident — training routes to the chunk-streamed
        # grower (ops/treegrow_ooc.py), whose envelope is the strict
        # grower's core (numerical + categorical, bagging, max_depth).
        # Features that need the whole matrix (or a grower outside the
        # mirror) raise here rather than silently train something else.
        self._ooc_spill = bool(getattr(train_set, "ooc_spill", False))
        if self._ooc_spill:
            mc_l = list(self.cfg.monotone_constraints or [])
            blocked = {
                "monotone_constraints": any(int(c) != 0 for c in mc_l),
                "interaction_constraints": bool(
                    self.cfg.interaction_constraints),
                "forcedsplits_filename": bool(self.cfg.forcedsplits_filename),
                "cegb penalties": any(
                    p != 0 for p in
                    (self.cfg.cegb_penalty_feature_coupled or [])
                    + (self.cfg.cegb_penalty_feature_lazy or [])),
                "linear_tree": bool(self.cfg.linear_tree),
                "extra_trees / feature_fraction_bynode": bool(
                    self.cfg.extra_trees
                    or self.cfg.feature_fraction_bynode < 1.0),
                "tree_learner != serial": self.cfg.tree_learner != "serial",
                "boosting = dart": self.cfg.boosting == "dart",
            }
            bad = [k for k, v in blocked.items() if v]
            if bad:
                raise ValueError(
                    "out_of_core spill training (rows > max_rows_in_hbm) "
                    f"does not support: {', '.join(bad)} — raise "
                    "max_rows_in_hbm (resident regime supports everything) "
                    "or drop the option; see ops/treegrow_ooc.py")
            if self.cfg.use_quantized_grad:
                from ..utils.log import log_warning as _lw
                _lw("use_quantized_grad is ignored by the out-of-core "
                    "spill grower; this run trains float (strict-grower "
                    "mirror)")
        self._linear = bool(self.cfg.linear_tree) and self.cfg.tree_learner == "serial"
        if self.cfg.linear_tree and not self._linear:
            log_warning(
                "linear_tree is implemented for tree_learner=serial only; "
                "training proceeds with CONSTANT leaves."
            )
        if self._linear and self.cfg.boosting == "dart":
            log_warning(
                "linear_tree is not supported with boosting=dart (drop/renorm "
                "assumes constant leaves); training with CONSTANT leaves."
            )
            self._linear = False
        if self._linear and self.objective is not None and self.objective.need_renew:
            # reference: Config::CheckParamConflict forbids linear trees with
            # objectives that renew leaf outputs (l1/huber/quantile/mape)
            raise ValueError(
                f"linear_tree is not supported with objective="
                f"{self.objective.name} (leaf-output renewal)"
            )
        if self._linear and getattr(train_set, "raw_device", None) is None:
            raise ValueError(
                "linear_tree requires raw feature values: the Dataset was "
                "constructed without linear_tree in its params (or raw data "
                "was freed). Pass params={'linear_tree': True} to Dataset."
            )
        if self.cfg.use_quantized_grad and not (self._use_fast or self._use_fast_dp):
            log_warning(
                "use_quantized_grad is implemented on the rounds grower "
                "(tree_growth_mode=rounds / auto-on-TPU) only; this run "
                "trains UNQUANTIZED on the strict grower."
            )
        # distributed tree learner over the device mesh (reference:
        # TreeLearner::CreateTreeLearner picking {serial,data,feature,voting})
        self._dp = None
        self._fp = None
        if self.cfg.tree_learner in ("data", "feature", "voting"):
            import jax as _jax

            if _jax.device_count() > 1:
                from ..parallel.mesh import make_mesh

                # resident out_of_core datasets never hold host bins; the
                # sharded learners split a host copy once (spill regime is
                # already gated to tree_learner=serial above)
                host_bins = train_set._host_bins(
                    f"tree_learner={self.cfg.tree_learner}")
                mesh = make_mesh()
                if self.cfg.tree_learner == "feature":
                    from ..parallel.feature_parallel import FeatureShardedData

                    self._fp = FeatureShardedData(
                        mesh,
                        np.asarray(host_bins),
                        np.asarray(train_set.binner.num_bins_per_feature),
                        np.asarray(train_set.binner.missing_bin_per_feature),
                    )
                else:
                    from ..parallel.data_parallel import ShardedData

                    self._pre_partition = (
                        self.cfg.pre_partition and jax.process_count() > 1
                    )
                    self._dp = ShardedData(
                        mesh,
                        np.asarray(host_bins),
                        np.asarray(train_set.binner.num_bins_per_feature),
                        np.asarray(train_set.binner.missing_bin_per_feature),
                        process_local=self._pre_partition,
                    )

    def _make_split_params(self) -> SplitParams:
        """The jit-static split hyperparameters, from the configuration and
        the binner: at set-up and after every config mutation alike, so that
        a reset keeps the categorical search on its own columns."""
        cat = np.flatnonzero(np.asarray(self.binner.categorical_mask))
        return SplitParams(
            lambda_l1=self.cfg.lambda_l1,
            lambda_l2=self.cfg.lambda_l2,
            min_data_in_leaf=self.cfg.min_data_in_leaf,
            min_sum_hessian_in_leaf=self.cfg.min_sum_hessian_in_leaf,
            min_gain_to_split=self.cfg.min_gain_to_split,
            max_delta_step=self.cfg.max_delta_step,
            path_smooth=self.cfg.path_smooth,
            cat_l2=self.cfg.cat_l2,
            cat_smooth=self.cfg.cat_smooth,
            max_cat_threshold=self.cfg.max_cat_threshold,
            max_cat_to_onehot=self.cfg.max_cat_to_onehot,
            feature_fraction_bynode=self.cfg.feature_fraction_bynode,
            extra_trees=bool(self.cfg.extra_trees),
            monotone_penalty=self.cfg.monotone_penalty,
            cegb_tradeoff=self.cfg.cegb_tradeoff,
            cegb_penalty_split=self.cfg.cegb_penalty_split,
            cat_features=tuple(int(c) for c in cat) if cat.size else None,
        )

    def reset_split_params(self) -> None:
        """Refresh jit-static split hyperparams after a config mutation
        (reference: GBDT::ResetConfig via reset_parameter callbacks)."""
        self._split_params = self._make_split_params()
        # the fused step bakes SplitParams plus several other config fields
        # as traced constants — but learning_rate is a runtime argument, so
        # the common reset_parameter(learning_rate=...) schedule must NOT
        # retrace every iteration; invalidate only when a baked constant
        # really changed (reference: GBDT::ResetConfig propagates num_leaves
        # etc. to the tree learner)
        if getattr(self, "_fused_key", None) != self._fused_bake_key():
            self._fused_step = None
            # a changed baked constant yields a fresh trace, so a previous
            # compile failure no longer applies — give fused another chance
            self._fused_disabled = False
            # the fused predict+convert entry bakes objective constants
            # (e.g. cfg.sigmoid) as traced constants too
            self._convert_entry = None

    def add_valid(self, valid_set, name: str) -> None:
        valid_set.construct(reference=self.train_set)
        self.valid_sets.append(valid_set)
        self.valid_names.append(name)
        n = valid_set.num_data()
        k = self.num_tree_per_iteration
        shape = (n,) if k == 1 else (n, k)
        init = np.zeros(shape, dtype=np.float32)
        if self.init_scores and any(s != 0.0 for s in self.init_scores):
            init += np.asarray(self.init_scores, dtype=np.float32) if k > 1 else np.float32(self.init_scores[0])
        if valid_set.init_score is not None:
            init += np.asarray(valid_set.init_score, dtype=np.float32).reshape(shape)
        # replay existing trees (continued training)
        score = jnp.asarray(init)
        for i, tree in enumerate(self.models):
            c = i % k
            if tree.is_linear:
                vals = jnp.asarray(
                    tree.predict_batch(np.asarray(valid_set.raw_device)),
                    jnp.float32,
                )
            else:
                leaf = valid_set.predict_leaf_binned_tree(tree)
                vals = jnp.asarray(tree.leaf_value, jnp.float32)[leaf]
            if k == 1:
                score = score + vals
            else:
                score = score.at[:, c].add(vals)
        self._valid_scores.append(score)

    # ------------------------------------------------------------------
    def _bagging_mask(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Row selection for this iteration: (mask bool, weights f32).

        reference: BaggingSampleStrategy (bagging.hpp) & GOSSStrategy
        (goss.hpp) via SampleStrategy::CreateSampleStrategy."""
        n = self.train_set.num_data()
        cfg = self.cfg
        if cfg.data_sample_strategy == "goss" or cfg.boosting == "goss":
            return self._goss_mask()
        use_bagging = cfg.bagging_freq > 0 and (
            cfg.bagging_fraction < 1.0
            or cfg.pos_bagging_fraction < 1.0
            or cfg.neg_bagging_fraction < 1.0
        )
        if not use_bagging:
            if self._nobag_cache is None or self._nobag_cache[0].shape[0] != n:
                self._nobag_cache = (
                    jnp.ones((n,), dtype=bool), jnp.ones((n,), jnp.float32)
                )
            return self._nobag_cache
        if self._last_mask is not None and (self.iter_ % cfg.bagging_freq) != 0:
            # re-bag only every bagging_freq iterations (reference: bagging.hpp)
            return self._last_mask
        rng = np.random.RandomState(cfg.bagging_seed + self.iter_)
        qb = getattr(self.train_set, "query_boundaries", None)
        if cfg.bagging_by_query and qb is not None:
            # reference: bagging.hpp bagging_by_query — whole queries are
            # sampled so ranking pairs never straddle the in-bag boundary
            qb = np.asarray(qb)
            nq = len(qb) - 1
            qmask = rng.rand(nq) < cfg.bagging_fraction
            mask = np.repeat(qmask, np.diff(qb))
            out = (jnp.asarray(mask), jnp.ones((n,), jnp.float32))
            self._last_mask = out
            return out
        if cfg.pos_bagging_fraction < 1.0 or cfg.neg_bagging_fraction < 1.0:
            lbl = np.asarray(self.train_set.label)
            mask = np.zeros(n, dtype=bool)
            pos = lbl > 0
            mask[pos] = rng.rand(int(pos.sum())) < cfg.pos_bagging_fraction
            mask[~pos] = rng.rand(int((~pos).sum())) < cfg.neg_bagging_fraction
        else:
            mask = rng.rand(n) < cfg.bagging_fraction
        out = (jnp.asarray(mask), jnp.ones((n,), jnp.float32))
        self._last_mask = out
        return out

    def _goss_mask(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """GOSS (reference: goss.hpp): keep top `top_rate` rows by
        |grad*hess|, sample `other_rate` of the rest and amplify them by
        (1-top_rate)/other_rate.  First 1/learning_rate iterations use the
        full data (reference warm-up rule)."""
        n = self.train_set.num_data()
        cfg = self.cfg
        warmup = int(1.0 / max(cfg.learning_rate, 1e-12))
        if self.iter_ < warmup:
            return jnp.ones((n,), bool), jnp.ones((n,), jnp.float32)
        g, h = self._cur_grad, self._cur_hess
        score_abs = jnp.abs(g * h)
        if score_abs.ndim > 1:
            score_abs = jnp.sum(score_abs, axis=1)
        top_k = max(int(n * cfg.top_rate), 1)
        other_k = max(int(n * cfg.other_rate), 1)
        thresh = jnp.sort(score_abs)[-top_k]
        top_mask = score_abs >= thresh
        rng_key = jax.random.PRNGKey(cfg.bagging_seed + self.iter_)
        u = jax.random.uniform(rng_key, (n,))
        rest_prob = other_k / jnp.maximum(n - top_k, 1)
        rest_mask = (~top_mask) & (u < rest_prob)
        mask = top_mask | rest_mask
        amp = (1.0 - cfg.top_rate) / cfg.other_rate
        weights = jnp.where(rest_mask, amp, 1.0).astype(jnp.float32)
        return mask, weights

    def _feature_mask(self) -> jnp.ndarray:
        """reference: ColSampler::ResetByTree (col_sampler.hpp)."""
        f = self.train_set.num_feature()
        frac = self.cfg.feature_fraction
        if frac >= 1.0:
            return self._allowed_features
        rng = np.random.RandomState(self.cfg.feature_fraction_seed + self.iter_)
        k = max(int(np.ceil(f * frac)), 1)
        chosen = rng.choice(f, size=k, replace=False)
        mask = np.zeros(f, dtype=bool)
        mask[chosen] = True
        return jnp.asarray(mask) & self._allowed_features

    @property
    def _monotone_method(self) -> str:
        """Effective monotone method for the growers: 'advanced' downgrades
        to 'intermediate' (reference: LeafConstraintsBase::Create; the
        advanced cost-based refinement is descoped, warned at setup)."""
        if self._monotone is None:
            return "basic"
        return ("intermediate"
                if self.cfg.monotone_constraints_method
                in ("intermediate", "advanced") else "basic")

    def _leaf_tile(self, ts, use_efb: bool = True) -> int:
        quant = bool(self.cfg.use_quantized_grad)
        if ts.max_num_bins <= 64 and self._on_tpu:
            # XLA einsum strategy (ops/histogram.py) — no Mosaic VMEM
            # ceiling.  Measured: 8 is best at 31 leaves (pass cost grows
            # with lanes); deep trees amortize per-round fixed costs, so
            # go wider once rounds are leaf-count-bound.
            tile = 16 if self.cfg.num_leaves > 63 else 8
            return max(1, min(tile, self.cfg.num_leaves))
        f_eff = (
            ts.efb.num_bundled
            if use_efb and getattr(ts, "efb", None) is not None
            else ts.num_feature()
        )
        # channel-aware tile selection lives with the kernel cost model
        # (ops/hist_pallas.py::recommended_leaf_tile): ~60-lane budgets,
        # narrow tile16-bf16 / tile20-q16, wide 10-f32 / 20-q
        from ..ops.hist_pallas import recommended_leaf_tile

        return recommended_leaf_tile(
            ts.max_num_bins, f_eff, self.cfg.num_leaves,
            hist_precision=self.cfg.hist_precision, quantized=quant)

    _last_mask = None
    _nobag_cache = None
    _fused_step = None
    _report_finish_every_iter = False
    _finish_probe = None

    _pre_partition = False
    _cegb_lazy = None
    _cegb_lazy_used = None
    _fused_disabled = False
    _ooc_spill = False
    _convert_entry = None

    def _localize_tree(self, arrays, leaf_id_pad):
        """Multi-controller runs: bring the (replicated) tree and the
        (row-sharded) leaf ids back to process-local arrays so the host-side
        boosting state — scores, gradients, metrics — stays local, exactly
        like the reference keeps per-rank state local while only the tree
        learner communicates (reference: DataParallelTreeLearner)."""
        if jax.process_count() <= 1:
            return arrays, leaf_id_pad
        arrays = jax.tree.map(lambda a: jnp.asarray(np.asarray(a)), arrays)
        if self._pre_partition and self._dp is not None:
            # each rank keeps only ITS rows' leaf ids (pre_partition: no
            # rank ever holds the full row space)
            leaf_id_pad = jnp.asarray(self._dp.local_rows(leaf_id_pad))
        else:
            from jax.experimental import multihost_utils

            leaf_id_pad = jnp.asarray(
                multihost_utils.process_allgather(leaf_id_pad, tiled=True)
            )
        return arrays, leaf_id_pad

    def _fused_eligible(self, grad) -> bool:
        """The common hot path — single-class fast grower with a built-in
        objective and no per-iteration host work — can run gradients + tree
        + score update in ONE jit dispatch (every dispatch costs host time;
        the unfused loop makes ~12 of them per iteration)."""
        return (
            grad is None
            and self.cfg.fused_training
            and not self._fused_disabled
            and not self._ooc_spill  # bins are streamed, not traced inputs
            # each class tree inlines into the trace: cap the blowup
            and self.num_tree_per_iteration <= 8
            # very wide/deep shapes compile the combined trace pathologically
            # (observed: 255 leaves x 2000 features never finished); the
            # unfused path's ~12 extra dispatches per iteration are noise
            # at shapes this slow per-iteration anyway
            and self.cfg.num_leaves * self.train_set.num_feature() <= 100_000
            and self._use_fast
            and self._fp is None
            and self._dp is None
            and not self._linear
            and self.objective is not None
            and not self.objective.need_renew
            and self.objective.is_fusable()
            and self._cegb_coupled is None
            # lazy charges carry (N, F) state across iterations — kept on
            # the unfused loop rather than threading it through the step
            and self._cegb_lazy is None
            and not self._needs_node_rng
            and not self.cfg.use_quantized_grad
        )

    @property
    def _is_goss(self) -> bool:
        return self.cfg.data_sample_strategy == "goss" or self.cfg.boosting == "goss"

    _forced_cache = None

    def _forced_schedule(self):
        """Parse forcedsplits_filename into a (leaf, feature, bin) schedule
        for the strict grower (reference: SerialTreeLearner::ForceSplits —
        the JSON tree prefix is applied BFS before gain-driven growth;
        thresholds map to bins through the train binner)."""
        if not self.cfg.forcedsplits_filename:
            return None
        if self._forced_cache is not None:
            return self._forced_cache
        import json as _json
        from collections import deque

        with open(self.cfg.forcedsplits_filename) as fh:
            root = _json.load(fh)
        leaves, feats, bins_ = [], [], []
        # BFS with the grower's leaf numbering: left child keeps the parent's
        # leaf id; the right child of the s-th split gets leaf id s+1
        queue = deque([(root, 0)])
        step = 0
        while queue:
            node, leaf = queue.popleft()
            fidx = int(node["feature"])
            thr = float(node["threshold"])
            mapper = self.binner.mappers[fidx]
            # bin containing the threshold: value <= upper_bound semantics
            b = int(mapper.transform(np.asarray([thr]))[0])
            leaves.append(leaf)
            feats.append(fidx)
            bins_.append(b)
            right_leaf = step + 1
            if "left" in node and node["left"]:
                queue.append((node["left"], leaf))
            if "right" in node and node["right"]:
                queue.append((node["right"], right_leaf))
            step += 1
        self._forced_cache = (
            jnp.asarray(leaves, jnp.int32),
            jnp.asarray(feats, jnp.int32),
            jnp.asarray(bins_, jnp.int32),
            len(leaves),
        )
        return self._forced_cache

    def _fused_bake_key(self):
        """Every config field the fused trace bakes as a constant.  Must stay
        in sync with _get_fused_step/grow_kwargs: a field listed here forces
        a retrace on reset_parameter; a missing field is silently frozen."""
        ts = self.train_set
        return (
            self._split_params,
            self.cfg.sigmoid,
            self.cfg.num_leaves,
            self.cfg.max_depth,
            self.cfg.hist_precision,
            self._leaf_tile(ts) if ts is not None else None,
            self._is_goss,
            self.cfg.top_rate,
            self.cfg.other_rate,
            self.cfg.forcedsplits_filename,
            self._monotone_method,
        )

    def _get_fused_step(self):
        if self._fused_step is not None:
            return self._fused_step
        self._fused_key = self._fused_bake_key()  # baked into the trace below
        ts = self.train_set
        obj = self.objective
        label, weight = self._label, self._weight
        bins = ts.bins_device
        nbpf, mbpf = ts.num_bins_pf_device, ts.missing_bin_pf_device
        cat_mask, mono = self._categorical_mask, self._monotone
        contri = self._feature_contri
        inter = self._interaction_sets
        efb_tabs = ts.efb_device_tables() if getattr(ts, "efb", None) is not None else None
        bins_t = ts.bins_device_t() if self._on_tpu else None
        from ..ops.treegrow_fast import grow_tree_fast

        fs = self._forced_schedule()
        grow_kwargs = dict(
            num_leaves=self.cfg.num_leaves,
            num_bins=ts.max_num_bins,
            max_depth=self.cfg.max_depth,
            params=self._split_params,
            leaf_tile=self._leaf_tile(ts),
            hist_precision=self.cfg.hist_precision,
            use_pallas=self._on_tpu,
            # entries past num_leaves-1 can never apply; clamping avoids
            # unrolling dead traced rounds
            n_forced=(min(fs[3], self.cfg.num_leaves - 1) if fs else 0),
            monotone_method=self._monotone_method,
        )

        use_goss = self._is_goss
        n_rows = ts.num_data()
        top_rate, other_rate = self.cfg.top_rate, self.cfg.other_rate
        k = self.num_tree_per_iteration

        @jax.jit
        # jaxlint: disable=R2 (cached in self._fused_step; rebuilt only when _fused_bake_key changes)
        def step(score, row_mask, sample_weight, feature_mask, shrinkage,
                 goss_key, goss_warm, obj_state):
            with _profiling.phase_scope("gbdt.gradients"):
                g, h, new_obj_state = obj.fused_gradients(
                    score, label, weight, obj_state)
            if use_goss:
                # GOSS in-trace (reference: goss.hpp): the mask depends on
                # THIS iteration's gradients, so it must live inside the
                # fused step; goss_warm (traced bool) selects the full-data
                # warm-up behavior without retracing
                score_abs = jnp.abs(g * h)
                if score_abs.ndim > 1:
                    score_abs = jnp.sum(score_abs, axis=1)
                top_k = max(int(n_rows * top_rate), 1)
                other_k = max(int(n_rows * other_rate), 1)
                thresh = jnp.sort(score_abs)[-top_k]
                top_mask = score_abs >= thresh
                u = jax.random.uniform(goss_key, (n_rows,))
                rest_prob = other_k / jnp.maximum(n_rows - top_k, 1)
                rest_mask = (~top_mask) & (u < rest_prob)
                amp = (1.0 - top_rate) / other_rate
                row_mask = jnp.where(goss_warm, row_mask, top_mask | rest_mask)
                sample_weight = jnp.where(
                    goss_warm, sample_weight,
                    jnp.where(rest_mask, amp, 1.0).astype(jnp.float32),
                )
            arrays_all, leaf_all = [], []
            new_score = score
            for c in range(k):  # k static: multiclass trees inline in-trace
                gc = g if k == 1 else g[:, c]
                hc = h if k == 1 else h[:, c]
                arrays, leaf_id = grow_tree_fast(
                    bins, gc, hc, row_mask, sample_weight, feature_mask,
                    nbpf, mbpf, cat_mask, mono, inter, None, None, None,
                    efb_tabs[0] if efb_tabs else None,
                    efb_tabs[1] if efb_tabs else None,
                    efb_tabs[2] if efb_tabs else None,
                    bins_t,
                    contri,
                    fs[0] if fs else None,
                    fs[1] if fs else None,
                    fs[2] if fs else None,
                    **grow_kwargs,
                )
                new_score = _add_leaf_scores(
                    new_score, arrays.leaf_value, shrinkage, leaf_id, col=c)
                arrays_all.append(arrays)
                leaf_all.append(leaf_id)
            return (tuple(arrays_all), tuple(leaf_all), new_score, g, h,
                    new_obj_state)

        self._fused_step = step
        return step

    # ------------------------------------------------------------------
    def train_one_iter(self, grad: Optional[np.ndarray] = None, hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (reference: GBDT::TrainOneIter).  Returns
        True when training cannot continue (all trees constant).

        The telemetry wrapper around :meth:`_train_one_iter_impl` emits the
        per-round training summary (docs/OBSERVABILITY.md): one
        ``boost_round`` event carrying the round's dispatch/sync/compile
        deltas read from the sanitizer's host-side ledger — deliberately NO
        wall-clock delta, because the fast path dispatches asynchronously
        and an unsynced timer would be the jaxlint-R9 mistiming
        anti-pattern.  The ``boost_round`` SPAN around the impl carries the
        same ledger deltas; its duration is host-causal by design (spans
        never add a sync — jaxlint R10), and it mirrors into
        jax.profiler.StepTraceAnnotation (utils/profiling.py, no switch), so
        any profiler trace has a step per boosting iteration."""
        if not _obs.enabled():
            return self._train_one_iter_impl(grad, hess)
        c0 = _san.compile_totals()
        with _trace.span("boost_round", iteration=self.iter_) as sp:
            finished = self._train_one_iter_impl(grad, hess)
            c1 = _san.compile_totals()
            sp.set(dispatches=c1["dispatches"] - c0["dispatches"],
                   host_syncs=c1["host_syncs"] - c0["host_syncs"],
                   compiles=c1["compiles"] - c0["compiles"])
        _obs.counter("train_boost_rounds_total").inc()
        _obs.event("boost_round", iteration=self.iter_,
                   dispatches=c1["dispatches"] - c0["dispatches"],
                   host_syncs=c1["host_syncs"] - c0["host_syncs"],
                   compiles=c1["compiles"] - c0["compiles"],
                   traces=c1["traces"] - c0["traces"])
        return finished

    def _train_one_iter_impl(self, grad: Optional[np.ndarray] = None, hess: Optional[np.ndarray] = None) -> bool:
        ts = self.train_set
        k = self.num_tree_per_iteration
        if self._fused_eligible(grad):
            if self._is_goss:
                # masks computed in-trace; pass full-data placeholders
                if self._nobag_cache is None or self._nobag_cache[0].shape[0] != ts.num_data():
                    self._nobag_cache = (
                        jnp.ones((ts.num_data(),), bool),
                        jnp.ones((ts.num_data(),), jnp.float32),
                    )
                row_mask, sample_weight = self._nobag_cache
                goss_key = jax.random.PRNGKey(self.cfg.bagging_seed + self.iter_)
                warmup = int(1.0 / max(self.cfg.learning_rate, 1e-12))
                goss_warm = jnp.asarray(self.iter_ < warmup)
            else:
                row_mask, sample_weight = self._bagging_mask()
                goss_key = jax.random.PRNGKey(0)
                goss_warm = jnp.asarray(False)
            feature_mask = self._feature_mask()
            shrinkage = 1.0 if self.average_output else self.cfg.learning_rate
            step = self._get_fused_step()
            try:
                arrays_all, leaf_all, self._score, g, h, obj_state = step(
                    self._score, row_mask, sample_weight,
                    jnp.asarray(feature_mask), jnp.float32(shrinkage),
                    goss_key, goss_warm, self.objective.fused_state(),
                )
            except Exception as e:  # noqa: BLE001
                from ..utils.log import log_warning

                # nothing is mutated before `step` returns, so fall back to
                # the unfused path (re-enabled if reset_parameter changes a
                # baked constant and retraces).  _fused_disabled is the
                # trace of this net: chip_smoke.py fails on it.
                log_warning(
                    "fused training step failed "
                    f"({type(e).__name__}: {str(e)[:200]}); "
                    "falling back to per-phase dispatches"
                )
                self._fused_disabled = True
                self._fused_step = None
                # recurse into the impl: the telemetry wrapper already
                # opened this round's ledger window (one event per round)
                return self._train_one_iter_impl(grad, hess)
            self.objective.set_fused_state(obj_state)
            self._cur_grad, self._cur_hess = g, h
            for c, arrays in enumerate(arrays_all):
                self._guard_accumulate(arrays)
                self._pending.append((arrays, shrinkage, None))
                for vi, vs in enumerate(self.valid_sets):
                    from ..ops.treegrow_fast import predict_leaf_arrays

                    leaf_v = predict_leaf_arrays(
                        arrays, vs.bins_device, ts.missing_bin_pf_device,
                    )
                    self._valid_scores[vi] = _add_leaf_scores(
                        self._valid_scores[vi], arrays.leaf_value, shrinkage,
                        leaf_v, col=c)
            self.iter_ += 1
            self._invalidate_pred_cache("train_one_iter")
            if self._report_finish_every_iter:
                # C API path: the reference reports is_finished immediately.
                # Reading THIS iteration's num_leaves is a blocking pull that
                # stalls the async pipeline, so probe the
                # PREVIOUS iteration's trees — by now their step has retired,
                # making the read ~free; is_finished lags one iteration.
                prev = self._finish_probe
                self._finish_probe = (
                    self.iter_,
                    tuple(a.num_leaves for a in arrays_all),
                )
                for x in self._finish_probe[1]:
                    getattr(x, "copy_to_host_async", lambda: None)()
                # only trust a probe from the immediately preceding iteration
                # (rollback / reset / interleaved unfused iterations stale it)
                if prev is not None and prev[0] == self.iter_ - 1:
                    return all(int(np.asarray(x)) <= 1 for x in prev[1])
                return False
            if (self.iter_ % 32) == 0:
                # library path: syncing every iteration is too expensive (see
                # above); a finished model only accretes constant trees, so a
                # deferred check is safe — it is documented in engine.train.
                # The non-finite guard piggybacks on the same sync cadence.
                self._guard_check()
                return all(bool(a.num_leaves <= 1) for a in arrays_all)
            return False
        if grad is None:
            with _profiling.phase_scope("gbdt.gradients"):
                g, h = self.objective.get_gradients(
                    self._score, self._label, self._weight)
        else:
            g = jnp.asarray(grad, jnp.float32).reshape(self._score.shape)
            h = jnp.asarray(hess, jnp.float32).reshape(self._score.shape)
        # fault-injection sites: poison one gradient/hessian element at a
        # chosen iteration to drive the non-finite guard-rail tests
        # (utils/faults.py; no-ops unless LGBMTPU_FAULT arms them)
        g = _faults.corrupt_nonfinite("nonfinite_grad", self.iter_ + 1, g)
        h = _faults.corrupt_nonfinite("nonfinite_hess", self.iter_ + 1, h)
        self._cur_grad, self._cur_hess = g, h
        row_mask, sample_weight = self._bagging_mask()
        feature_mask = self._feature_mask()

        all_const = True
        for c in range(k):
            # recomputed per class tree: a feature used by an earlier class's
            # tree this iteration is no longer charged (reference: cegb.hpp
            # updates coupled state sequentially across trees)
            cegb_pen = None
            if self._cegb_coupled is not None:
                cegb_pen = jnp.where(self._cegb_used_global, 0.0, self._cegb_coupled)
            gc = g if k == 1 else g[:, c]
            hc = h if k == 1 else h[:, c]
            node_rng = (
                jax.random.PRNGKey(self.cfg.extra_seed + self.iter_ * 131 + c)
                if self._needs_node_rng else None
            )
            if self._ooc_spill:
                # out-of-core spill: the binned matrix streams through the
                # chunked grower (a strict-grower mirror — bitwise on the
                # scatter strategy, ops/treegrow_ooc.py)
                from ..ops.treegrow_ooc import grow_tree_ooc

                arrays, leaf_id = grow_tree_ooc(
                    ts.ooc_chunk_iter,
                    ts.num_data(),
                    ts.num_feature(),
                    jnp.asarray(gc, jnp.float32),
                    jnp.asarray(hc, jnp.float32),
                    jnp.asarray(row_mask, bool),
                    jnp.asarray(sample_weight, jnp.float32),
                    jnp.asarray(feature_mask, bool),
                    ts.num_bins_pf_device,
                    ts.missing_bin_pf_device,
                    self._categorical_mask,
                    num_leaves=self.cfg.num_leaves,
                    num_bins=ts.max_num_bins,
                    max_depth=self.cfg.max_depth,
                    params=self._split_params,
                    chunk_rows=ts.ooc_chunk_rows,
                )
            elif self._fp is not None:
                from ..parallel.feature_parallel import grow_tree_feature_parallel

                arrays, leaf_id = grow_tree_feature_parallel(
                    self._fp,
                    jnp.asarray(gc, jnp.float32),
                    jnp.asarray(hc, jnp.float32),
                    jnp.asarray(row_mask, bool),
                    jnp.asarray(sample_weight, jnp.float32),
                    np.asarray(feature_mask, bool),  # jaxlint: disable=R1 (feature_mask is a host numpy mask; the FP learner pads+shards host-side, no device pull)
                    self._categorical_mask,
                    self._monotone,
                    self._interaction_sets,
                    node_rng,
                    self._feature_contri,
                    num_leaves=self.cfg.num_leaves,
                    num_bins=ts.max_num_bins,
                    max_depth=self.cfg.max_depth,
                    params=self._split_params,
                    monotone_method=self._monotone_method,
                )
                arrays, leaf_id = self._localize_tree(arrays, leaf_id)
            elif self._dp is not None and self._use_fast_dp:
                from ..parallel.data_parallel import grow_tree_fast_data_parallel

                dp = self._dp
                quant = self.cfg.use_quantized_grad
                arrays, leaf_id_pad = grow_tree_fast_data_parallel(
                    dp,
                    dp.pad_rows_device(gc, jnp.float32),
                    dp.pad_rows_device(hc, jnp.float32),
                    dp.pad_rows_device(row_mask, bool, fill=False),
                    dp.pad_rows_device(sample_weight, jnp.float32, fill=1.0),
                    feature_mask,
                    self._categorical_mask,
                    self._monotone,
                    self._interaction_sets,
                    node_rng,
                    (jax.random.PRNGKey(self.cfg.seed * 1000003 + self.iter_ * 31 + c)
                     if quant else None),
                    cegb_pen,
                    self._feature_contri,
                    num_leaves=self.cfg.num_leaves,
                    num_bins=ts.max_num_bins,
                    max_depth=self.cfg.max_depth,
                    params=self._split_params,
                    leaf_tile=self._leaf_tile(ts, use_efb=False),
                    hist_precision=self.cfg.hist_precision,
                    use_pallas=self._on_tpu,
                    quantize_bins=(self.cfg.num_grad_quant_bins if quant else 0),
                    stochastic_rounding=bool(self.cfg.stochastic_rounding),
                    quant_renew=bool(self.cfg.quant_train_renew_leaf),
                    track_path=self._linear,
                    monotone_method=self._monotone_method,
                )
                arrays, leaf_id_pad = self._localize_tree(arrays, leaf_id_pad)
                leaf_id = leaf_id_pad[: ts.num_data()]
            elif self._dp is not None:
                from ..parallel.data_parallel import grow_tree_data_parallel

                dp = self._dp
                arrays, leaf_id_pad = grow_tree_data_parallel(
                    dp,
                    dp.pad_rows_device(gc, jnp.float32),
                    dp.pad_rows_device(hc, jnp.float32),
                    dp.pad_rows_device(row_mask, bool, fill=False),
                    dp.pad_rows_device(sample_weight, jnp.float32, fill=1.0),
                    feature_mask,
                    self._categorical_mask,
                    self._monotone,
                    self._interaction_sets,
                    node_rng,
                    self._feature_contri,
                    num_leaves=self.cfg.num_leaves,
                    num_bins=ts.max_num_bins,
                    max_depth=self.cfg.max_depth,
                    params=self._split_params,
                    parallel_mode=("voting" if self.cfg.tree_learner == "voting" else "data"),
                    top_k=self.cfg.top_k,
                    monotone_method=self._monotone_method,
                )
                arrays, leaf_id_pad = self._localize_tree(arrays, leaf_id_pad)
                leaf_id = leaf_id_pad[: ts.num_data()]
            elif self._use_fast:
                from ..ops.treegrow_fast import grow_tree_fast

                quant = self.cfg.use_quantized_grad
                efb_tabs = ts.efb_device_tables() if getattr(ts, "efb", None) is not None else None
                fs = self._forced_schedule()
                grow_out = grow_tree_fast(
                    ts.bins_device,
                    gc,
                    hc,
                    row_mask,
                    sample_weight,
                    feature_mask,
                    ts.num_bins_pf_device,
                    ts.missing_bin_pf_device,
                    self._categorical_mask,
                    self._monotone,
                    self._interaction_sets,
                    node_rng,
                    (jax.random.PRNGKey(self.cfg.seed * 1000003 + self.iter_ * 31 + c)
                     if quant else None),
                    cegb_pen,
                    efb_tabs[0] if efb_tabs else None,
                    efb_tabs[1] if efb_tabs else None,
                    efb_tabs[2] if efb_tabs else None,
                    ts.bins_device_t() if self._on_tpu else None,
                    self._feature_contri,
                    fs[0] if fs else None,
                    fs[1] if fs else None,
                    fs[2] if fs else None,
                    self._cegb_lazy,
                    self._cegb_lazy_used,
                    n_forced=(min(fs[3], self.cfg.num_leaves - 1) if fs else 0),
                    num_leaves=self.cfg.num_leaves,
                    num_bins=ts.max_num_bins,
                    max_depth=self.cfg.max_depth,
                    params=self._split_params,
                    # measured on-chip (bench.py sweep): 8 leaves/pass is
                    # the optimum — wider payload lanes slow the Mosaic
                    # kernel more than the saved admission rounds buy.
                    # Wide datasets cap further: the Mosaic toolchain rejects
                    # kernels whose output tensor F_pad*lanes*B*4 exceeds
                    # ~100MB (measured), so Epsilon-shape runs use fewer
                    # leaves per pass.
                    leaf_tile=self._leaf_tile(ts),
                    hist_precision=self.cfg.hist_precision,
                    use_pallas=self._on_tpu,
                    quantize_bins=(self.cfg.num_grad_quant_bins if quant else 0),
                    stochastic_rounding=bool(self.cfg.stochastic_rounding),
                    quant_renew=bool(self.cfg.quant_train_renew_leaf),
                    track_path=self._linear,
                    monotone_method=self._monotone_method,
                )
                if self._cegb_lazy is not None and len(grow_out) == 3:
                    arrays, leaf_id, self._cegb_lazy_used = grow_out
                else:
                    arrays, leaf_id = grow_out
            else:
                fs = self._forced_schedule()
                # jaxlint: disable=R13 (what this loop donates is the score to its update, not a fused round's state; the strict grower's merge is a dispatch of its own by design)
                grow_out = grow_tree(
                    ts.bins_device,
                    gc,
                    hc,
                    row_mask,
                    sample_weight,
                    feature_mask,
                    ts.num_bins_pf_device,
                    ts.missing_bin_pf_device,
                    self._categorical_mask,
                    self._monotone,
                    self._interaction_sets,
                    node_rng,
                    cegb_pen,
                    self._cegb_lazy,
                    self._cegb_lazy_used,
                    fs[0] if fs else None,
                    fs[1] if fs else None,
                    fs[2] if fs else None,
                    self._feature_contri,
                    num_leaves=self.cfg.num_leaves,
                    num_bins=ts.max_num_bins,
                    max_depth=self.cfg.max_depth,
                    params=self._split_params,
                    hist_strategy="auto",
                    track_path=self._linear,
                    n_forced=(fs[3] if fs else 0),
                    monotone_method=self._monotone_method,
                )
                if self._cegb_lazy is not None and len(grow_out) == 3:
                    arrays, leaf_id, self._cegb_lazy_used = grow_out
                else:
                    arrays, leaf_id = grow_out
            self._guard_accumulate(arrays)
            linear_fit = None
            if self._linear and arrays.path_features is not None:
                from ..ops.linear import fit_linear_leaves

                used_path = arrays.path_features
                if self._categorical_mask is not None:
                    used_path = used_path & ~self._categorical_mask[None, :]
                coef, const, fidx, nf, lin_pred, _good = fit_linear_leaves(
                    ts.raw_device, leaf_id,
                    gc * sample_weight, hc * sample_weight, row_mask,
                    used_path, arrays.leaf_value,
                    jnp.float32(self.cfg.linear_lambda),
                    # cap on path features per leaf model (reference fits
                    # ALL path features; 24 covers any tree this package
                    # grows at default depths — deeper paths are truncated
                    # to the lowest-indexed features)
                    K=min(24, ts.num_feature()),
                    num_leaves=self.cfg.num_leaves,
                )
                linear_fit = (coef, const, fidx, nf)
            if self._cegb_coupled is not None:
                valid_nodes = (
                    jnp.arange(self.cfg.num_leaves - 1) < arrays.num_leaves - 1
                )
                self._cegb_used_global = self._cegb_used_global.at[
                    jnp.where(valid_nodes, arrays.split_feature, 2 * self.cfg.num_leaves + self._cegb_used_global.shape[0])
                ].set(True, mode="drop")
            leaf_values = arrays.leaf_value
            if self.objective is not None and self.objective.need_renew:
                renewed = self.objective.renew_tree_output(
                    None, self._label, self._weight,
                    self._score if k == 1 else self._score[:, c],
                    leaf_id, self.cfg.num_leaves,
                )
                if renewed is not None:
                    active = jnp.arange(self.cfg.num_leaves) < arrays.num_leaves
                    leaf_values = jnp.where(active, renewed, 0.0)
                    arrays = arrays._replace(leaf_value=leaf_values)
            if self._use_fast:
                # async path: no host materialization — score/valid updates
                # run on device from the TreeArrays; the host Tree is built
                # lazily (self.models property) so iterations pipeline freely
                shrinkage = 1.0 if self.average_output else self.cfg.learning_rate
                all_const = jnp.logical_and(
                    jnp.asarray(all_const, dtype=bool), arrays.num_leaves <= 1
                )
                self._pending.append((arrays, shrinkage, linear_fit))
                if linear_fit is None:
                    self._score = _add_leaf_scores(
                        self._score, arrays.leaf_value, shrinkage, leaf_id,
                        col=c)
                else:
                    # a fitted model a leaf is no lookup.  Eager operations: a
                    # cached primitive is not traced again, so a scope would
                    # be missing from them; the phase reduction files them
                    # under outside_grower by their module
                    row_delta = lin_pred * jnp.float32(shrinkage)
                    if k == 1:
                        self._score = self._score + row_delta
                    else:
                        self._score = self._score.at[:, c].add(row_delta)
                for vi, vs in enumerate(self.valid_sets):
                    from ..ops.treegrow_fast import predict_leaf_arrays

                    leaf_v = predict_leaf_arrays(
                        arrays, vs.bins_device, ts.missing_bin_pf_device,
                    )
                    if linear_fit is None:
                        self._valid_scores[vi] = _add_leaf_scores(
                            self._valid_scores[vi], arrays.leaf_value,
                            shrinkage, leaf_v, col=c)
                        continue
                    from ..ops.linear import predict_linear_rows

                    vals = predict_linear_rows(
                        vs.raw_device, leaf_v, coef, const, fidx, nf,
                        arrays.leaf_value,
                    ) * jnp.float32(shrinkage)
                    if k == 1:
                        self._valid_scores[vi] = self._valid_scores[vi] + vals
                    else:
                        self._valid_scores[vi] = self._valid_scores[vi].at[:, c].add(vals)
                continue
            tree = tree_from_device(arrays, self.binner, linear=linear_fit)
            if tree.num_leaves > 1:
                all_const = False
            # RF (average_output) takes unscaled deltas regardless of which
            # alias ("rf"/"random_forest") selected the mode
            shrinkage = 1.0 if self.average_output else self.cfg.learning_rate
            tree.apply_shrinkage(shrinkage)
            # Trees hold PURE deltas during training; the boost_from_average
            # init score lives in self.init_scores and is folded into tree 0
            # only at serialization time (_trees_for_export), so valid-score
            # updates, rollback, DART rescaling and continued training all
            # treat trees uniformly (reference folds via Tree::AddBias; we
            # fold at save to keep the .txt model self-contained).
            dev_leaf_vals = jnp.asarray(tree.leaf_value, jnp.float32)
            pad = self.cfg.num_leaves - dev_leaf_vals.shape[0]
            if pad > 0:
                dev_leaf_vals = jnp.concatenate([dev_leaf_vals, jnp.zeros(pad, jnp.float32)])
            # the host tree's values carry the shrinkage already
            if linear_fit is None:
                self._score = _add_leaf_scores(
                    self._score, dev_leaf_vals, 1.0, leaf_id, col=c)
            else:
                row_delta = lin_pred * jnp.float32(tree.shrinkage)
                if k == 1:
                    self._score = self._score + row_delta
                else:
                    self._score = self._score.at[:, c].add(row_delta)
            self.models.append(tree)  # jaxlint: disable=L3 (append+version-bump protocol: the pack key carries (version, len) so a mid-build append is caught at insert; locking here would nest the models-property device flush under the pack lock — an L2)
            # valid scores
            for vi, vs in enumerate(self.valid_sets):
                leaf_v = vs.predict_leaf_binned_tree(tree)
                if linear_fit is None:
                    self._valid_scores[vi] = _add_leaf_scores(
                        self._valid_scores[vi], dev_leaf_vals, 1.0, leaf_v,
                        col=c)
                    continue
                from ..ops.linear import predict_linear_rows

                vals = predict_linear_rows(
                    vs.raw_device, jnp.asarray(leaf_v), coef, const, fidx, nf,
                    arrays.leaf_value,
                ) * jnp.float32(tree.shrinkage)
                if k == 1:
                    self._valid_scores[vi] = self._valid_scores[vi] + vals
                else:
                    self._valid_scores[vi] = self._valid_scores[vi].at[:, c].add(vals)
        self.iter_ += 1
        self._invalidate_pred_cache("train_one_iter")
        if not isinstance(all_const, bool):
            # fast path: only force the cannot-split flag to host every 32
            # iterations, so callers doing `if train_one_iter(): break` don't
            # serialize the pipeline.  The reference stops the moment a
            # constant tree appears; we detect within 32 iterations (once an
            # iteration is constant the score stops changing, so every later
            # iteration is constant too and the next check catches it).
            if (self.iter_ % 32) == 0:
                self._guard_check()
                return bool(all_const)
            return False
        return all_const

    def rollback_one_iter(self) -> None:
        """reference: GBDT::RollbackOneIter.  The tree-list pops and the
        version bump share one pack-lock section (round 19): a serving
        pack build racing the rollback retries at insert time instead of
        caching a half-popped ensemble under the pre-rollback version."""
        if self.iter_ <= 0:
            return
        with self._plock():
            self._rollback_one_iter_locked()

    def _rollback_one_iter_locked(self) -> None:
        k = self.num_tree_per_iteration
        for c in reversed(range(k)):
            tree = self.models.pop()
            if tree.is_linear:
                vals = jnp.asarray(
                    tree.predict_batch(np.asarray(self.train_set.raw_device)),  # jaxlint: disable=L2 (rollback is a mutator: the pop + score rebuild must be atomic vs serving pack builds, and the linear-path pull is trainer-thread-only)
                    jnp.float32,
                )
            else:
                leaf_id = self.train_set.predict_leaf_binned_tree(tree)
                vals = jnp.asarray(tree.leaf_value, jnp.float32)[leaf_id]
            if k == 1:
                self._score = self._score - vals
            else:
                self._score = self._score.at[:, c].add(-vals)
            for vi, vs in enumerate(self.valid_sets):
                leaf_v = vs.predict_leaf_binned_tree(tree)
                vv = jnp.asarray(tree.leaf_value, jnp.float32)[leaf_v]
                if k == 1:
                    self._valid_scores[vi] = self._valid_scores[vi] - vv
                else:
                    self._valid_scores[vi] = self._valid_scores[vi].at[:, c].add(-vv)
        self.iter_ -= 1
        self._invalidate_pred_cache("rollback_one_iter")

    # ------------------------------------------------------------------
    def _converted(self, score: jnp.ndarray) -> np.ndarray:
        if self.objective is not None:
            return np.asarray(self.objective.convert_output(score))
        return np.asarray(score)

    def _eval_margin(self, score: jnp.ndarray) -> jnp.ndarray:
        """Margin used for metric evaluation; RF averages (scores accumulate
        raw sums during training)."""
        return score

    _eval_jit_cache = None

    def _device_evaluator(self, data_idx: int, ds, dev_metrics):
        """One jit per eval set covering every device-capable metric
        (reference: the CUDA build's device metric reductions,
        src/metric/cuda/cuda_pointwise_metric.cu).  convert_output runs
        in-trace; only len(dev_metrics) scalars cross to the host."""
        if self._eval_jit_cache is None:
            self._eval_jit_cache = {}
        key = (data_idx, tuple(type(m) for m in dev_metrics), ds.weight is None)
        hit = self._eval_jit_cache.get(key)
        if hit is not None:
            return hit
        obj = self.objective
        if data_idx == 0 and self._label is not None:
            # the training labels/weights already live on device
            label_dev, weight_dev = self._label, self._weight
        else:
            label_dev = jnp.asarray(np.asarray(ds.label))
            weight_dev = None if ds.weight is None else jnp.asarray(
                np.asarray(ds.weight), jnp.float32
            )
        # rank metrics need the eval set's padded query layout + ideal DCGs
        # (host-precomputed per dataset, device constants in the trace);
        # the layout is computed once and shared by every rank metric
        shared = None
        if any(m.needs_queries for m in dev_metrics):
            from ..metrics import pad_queries

            pad_idx_np, pad_mask_np = pad_queries(ds.query_boundaries)
            shared = {
                "pad_idx_np": pad_idx_np, "pad_mask_np": pad_mask_np,
                "pad_idx": jnp.asarray(pad_idx_np),
                "pad_mask": jnp.asarray(pad_mask_np),
            }
        qconsts = {
            id(m): m.device_query_constants(
                np.asarray(ds.label), ds.query_boundaries, shared)
            for m in dev_metrics if m.needs_queries
        }

        @jax.jit
        # jaxlint: disable=R2 (cached in self._eval_jit_cache keyed by (data_idx, metric set))
        def run(margin, label, weight):
            pred = obj.convert_output(margin) if obj is not None else margin
            outs = []
            for m in dev_metrics:
                if m.needs_queries:
                    outs.append(jnp.asarray(
                        m.device_eval_queries(pred, qconsts[id(m)]),
                        jnp.float32))
                else:
                    outs.append(jnp.asarray(
                        m.device_eval(pred, label, weight),
                        jnp.float32).reshape(-1))
            return jnp.concatenate(outs)

        entry = (run, label_dev, weight_dev)
        self._eval_jit_cache[key] = entry
        return entry

    def _eval_target(self, data_idx: int):
        """data_idx -> (dataset, raw score, display name); 0 = train,
        i>0 = (i-1)-th valid set."""
        if data_idx == 0:
            return self.train_set, self._score, self.train_name
        return (self.valid_sets[data_idx - 1],
                self._valid_scores[data_idx - 1],
                self.valid_names[data_idx - 1])

    def _eval_at_synced(self, data_idx: int) -> List[Tuple[str, str, float, bool]]:
        """Distributed eval under pre_partition: each rank holds only its
        row shard, so metric values must sync across processes (reference:
        Metric::Eval + Network::GlobalSyncUpBySum).  Decomposable metrics
        sum local (numerator, denominator) pairs; the AUC family gathers
        shard predictions and evaluates globally on every rank."""
        from ..basic import _allgather_rows_f64 as gather

        ds, score, name = self._eval_target(data_idx)
        pred = self._converted(self._eval_margin(score))
        label = np.asarray(ds.label)
        weight = None if ds.weight is None else np.asarray(ds.weight)
        qb = ds.query_boundaries

        per_metric = [(m, m.eval_sums(pred, label, weight, qb))
                      for m in self.metrics]
        sum_rows = [(num, den) for _, s in per_metric if s is not None
                    for (_, num, den, _) in s]
        totals = None
        if sum_rows:
            loc = np.ascontiguousarray(np.asarray(sum_rows, np.float64))
            totals = gather(loc.reshape(1, -1)).reshape(
                -1, len(sum_rows), 2).sum(axis=0)
        gathered = None
        out: List[Tuple[str, str, float, bool]] = []
        i = 0
        for m, s in per_metric:
            if s is not None:
                for (mn, _, _, hib) in s:
                    num_g, den_g = totals[i]
                    out.append((name, mn,
                                m.transform(num_g / max(den_g, 1e-300)), hib))
                    i += 1
            else:
                if gathered is None:
                    gathered = (
                        gather(pred),
                        gather(label),
                        None if weight is None else gather(weight),
                    )
                for (mn, v, hib) in m.eval(*gathered, None):
                    out.append((name, mn, v, hib))
        return out

    def eval_at(self, data_idx: int) -> List[Tuple[str, str, float, bool]]:
        """data_idx 0 = training, 1.. = valid sets (reference: GBDT::GetEvalAt).
        Returns (dataset_name, metric_name, value, is_higher_better)."""
        # eval pulls metric scalars anyway — piggyback the non-finite
        # guard so runs with valid sets detect corruption within a round
        self._guard_check()
        if self._pre_partition and jax.process_count() > 1:
            return self._eval_at_synced(data_idx)
        ds, score, name = self._eval_target(data_idx)
        k = self.num_tree_per_iteration
        dev_metrics = [
            m for m in self.metrics
            if self.objective is not None and m.supports_device(k)
            and (not m.needs_queries or ds.query_boundaries is not None)
        ]
        host_metrics = [m for m in self.metrics if m not in dev_metrics]
        out_by_metric = {}
        if dev_metrics:
            run, label_dev, weight_dev = self._device_evaluator(
                data_idx, ds, dev_metrics
            )
            vals = np.asarray(run(self._eval_margin(score), label_dev, weight_dev))
            off = 0
            for m in dev_metrics:
                if m.needs_queries:
                    names = m.device_out_names()
                else:
                    names = [m.name]
                out_by_metric[id(m)] = [
                    (nm, m.transform(float(vals[off + j])), m.is_higher_better)
                    for j, nm in enumerate(names)
                ]
                off += len(names)
        if host_metrics:
            pred = self._converted(self._eval_margin(score))
            label = np.asarray(ds.label)
            weight = None if ds.weight is None else np.asarray(ds.weight)
            for m in host_metrics:
                out_by_metric[id(m)] = m.eval(
                    pred, label, weight, ds.query_boundaries
                )
        out = []
        for m in self.metrics:  # preserve configured metric order
            for mn, v, hib in out_by_metric[id(m)]:
                out.append((name, mn, v, hib))
        return out

    # ------------------------------------------------------------------
    def _stacked(self, start: int = 0, num_iteration: int = -1, trees=None):
        k = self.num_tree_per_iteration
        if trees is None:
            trees = self.models
            lo = start * k
            hi = len(trees) if num_iteration < 0 else min((start + num_iteration) * k, len(trees))
            trees = trees[lo:hi]
        if not trees:
            return None
        max_l = max(max((t.num_leaves for t in trees), default=1), 2)
        m = max_l - 1
        T = len(trees)

        def pad(get, dtype, width, fill=0):
            out = np.full((T, width), fill, dtype=dtype)
            for i, t in enumerate(trees):
                a = get(t)
                out[i, : len(a)] = a
            return jnp.asarray(out)

        out = dict(
            split_feature=pad(lambda t: t.split_feature, np.int32, m),
            threshold=pad(lambda t: _f32_threshold_upper(t.threshold), np.float32, m),
            default_left=pad(lambda t: t.default_left(), bool, m),
            missing_type=pad(
                lambda t: (t.decision_type.astype(np.int32) >> 2) & 3, np.int32, m
            ),
            left_child=pad(lambda t: t.left_child, np.int32, m, fill=-1),
            right_child=pad(lambda t: t.right_child, np.int32, m, fill=-1),
            num_leaves=jnp.asarray([t.num_leaves for t in trees], jnp.int32),
            leaf_value=pad(lambda t: t.leaf_value, np.float32, max_l),
            k=k,
            T=T,
        )
        if any(t.num_cat > 0 for t in trees):
            # flat bitset words + per-node (offset, word-count) so the device
            # traversal can do Tree::CategoricalDecision with two gathers
            is_cat_np = np.zeros((T, m), bool)
            base_np = np.zeros((T, m), np.int32)
            nw_np = np.zeros((T, m), np.int32)
            words = []
            off = 0
            for i, t in enumerate(trees):
                icm = np.asarray(t.is_categorical_node(), bool)
                is_cat_np[i, : len(icm)] = icm
                for ndx in np.nonzero(icm)[0]:
                    ci = int(t.threshold[ndx])
                    lo = int(t.cat_boundaries[ci])
                    hi = int(t.cat_boundaries[ci + 1])
                    base_np[i, ndx] = off + lo
                    nw_np[i, ndx] = hi - lo
                w = np.asarray(t.cat_threshold, np.uint32)
                words.append(w)
                off += len(w)
            out["is_cat"] = jnp.asarray(is_cat_np)
            out["cat_base"] = jnp.asarray(base_np)
            out["cat_nwords"] = jnp.asarray(nw_np)
            out["cat_words"] = jnp.asarray(
                np.concatenate(words) if off else np.zeros(1, np.uint32))
        return out

    # -- packed-ensemble serving cache (round 9; versioned round 18) ---
    _PACKED_CACHE_CAP = 32  # bounds early-stop chunk windows etc.
    # versions retained after a mutation: the current one plus the
    # previous (in-flight serving readers of the pre-mutation pack) —
    # older versions are evicted by _invalidate_pred_cache, counted in
    # predict_stale_pack_evictions_total
    _PACKED_KEEP_VERSIONS = 2

    def _packed(self, start: int = 0, num_iteration: int = -1, *,
                pad_trees_to: int = 0):
        """Device-resident packed ensemble for serving: the `_stacked` SoA
        arrays built once per (version, tree range, model state) and
        cached, so a warm ``predict`` performs ZERO host-side re-pack and
        re-upload.

        The cache lives in ``self._pred_cache`` (None = empty).  Every
        model mutation (train_one_iter, rollback_one_iter, the ``models``
        setter, Booster.refit/shuffle_models, the C-API leaf refits)
        BUMPS ``_pack_version`` instead of nulling the dict
        (_invalidate_pred_cache), so the key's leading version component
        makes stale entries unreachable while the previous version stays
        servable for in-flight serving readers — and the key additionally
        carries ``len(self.models)`` as a belt-and-braces guard.

        ``pad_trees_to`` pads the tree axis with single-leaf zero-value
        trees to a multiple of that window so the early-stop chunk op runs
        every chunk through one executable.  Packed entries also carry:

        * ``_trees``: the export-form host trees (linear path, scale)
        * ``_linear``: True when any tree has linear leaves (host walk)
        """
        k = self.num_tree_per_iteration
        races = 0
        while True:
            # lookup UNDER the pack lock (shared with
            # _invalidate_pred_cache — round 19): a trainer-thread bump
            # cannot evict entries mid-lookup or race the key's version
            # component
            if races >= 3:
                # a sustained mutation cadence (e.g. a set_leaf_output
                # loop) must not starve a serving build forever: after a
                # few lost races, build UNDER the lock — mutators wait
                # one build instead of the reader retrying unboundedly
                with self._plock():
                    return self._packed_build_locked(start, num_iteration,
                                                     pad_trees_to)
            with self._plock():
                v0 = self._pack_version
                n_models = len(self.models)  # flushes pending device trees
                lo = start * k
                hi = n_models if num_iteration < 0 else min(
                    (start + num_iteration) * k, n_models)
                key = (v0, lo, hi, n_models, pad_trees_to)
                if self._pred_cache is None:
                    self._pred_cache = {}
                hit = self._pred_cache.get(key)
                if hit is not None:
                    _obs.counter("predict_packed_cache_hits_total").inc()
                    return hit
                _obs.counter("predict_packed_cache_misses_total").inc()
            # build OUTSIDE the lock (host re-pack + device uploads must
            # not stall concurrent serving lookups of resident versions)
            trees = self._trees_for_export(start, num_iteration)
            pack_trees = trees
            if pad_trees_to and trees:
                pad = (-len(trees)) % pad_trees_to
                pack_trees = trees + [_dummy_tree()] * pad
            s = self._stacked(trees=pack_trees) if pack_trees else None
            if s is not None:
                s["_trees"] = trees
                s["_linear"] = any(t.is_linear for t in trees)
            with self._plock():
                if self._pack_version != v0:
                    # a mutation landed mid-build: the freshly packed
                    # arrays may reflect post-mutation trees, so caching
                    # them under the pre-mutation version would hand
                    # in-flight readers a torn pack — rebuild under the
                    # new version instead
                    _obs.counter("predict_pack_build_races_total").inc()
                    races += 1
                    continue
                if len(self._pred_cache) >= self._PACKED_CACHE_CAP:
                    self._pred_cache.pop(next(iter(self._pred_cache)))
                self._pred_cache[key] = s
                return s

    def _packed_build_locked(self, start: int, num_iteration: int,
                             pad_trees_to: int):
        """The starvation fallback: one full lookup+build+insert with the
        pack lock HELD — no mutation can interleave, so progress is
        guaranteed after repeated build races (callers: _packed only)."""
        k = self.num_tree_per_iteration
        n_models = len(self.models)
        lo = start * k
        hi = n_models if num_iteration < 0 else min(
            (start + num_iteration) * k, n_models)
        key = (self._pack_version, lo, hi, n_models, pad_trees_to)
        if self._pred_cache is None:
            self._pred_cache = {}
        hit = self._pred_cache.get(key)
        if hit is not None:
            _obs.counter("predict_packed_cache_hits_total").inc()
            return hit
        _obs.counter("predict_packed_cache_misses_total").inc()
        trees = self._trees_for_export(start, num_iteration)
        pack_trees = trees
        if pad_trees_to and trees:
            pad = (-len(trees)) % pad_trees_to
            pack_trees = trees + [_dummy_tree()] * pad
        s = self._stacked(trees=pack_trees) if pack_trees else None
        if s is not None:
            s["_trees"] = trees
            s["_linear"] = any(t.is_linear for t in trees)
        if len(self._pred_cache) >= self._PACKED_CACHE_CAP:
            self._pred_cache.pop(next(iter(self._pred_cache)))
        self._pred_cache[key] = s
        return s

    # -- serving telemetry (docs/OBSERVABILITY.md) ---------------------
    @staticmethod
    def _serve_t0() -> Tuple[float, int]:
        """(wall clock, compile count) opening a serving entry's telemetry
        window — closed by :meth:`_serve_note` AFTER the entry's accounted
        ``sync_pull``, so the latency reservoir measures the real
        end-to-end call (dispatch + device compute + pull), never the
        async-enqueue time (the jaxlint-R9 mistiming class)."""
        return time.perf_counter(), _san.compile_totals()["compiles"]

    def _serve_note(self, entry: str, n: int, t0c0: Tuple[float, int],
                    bucket: Optional[int] = None,
                    trace_ctx=None) -> None:
        """Record one serving call.  Bucket hit/miss is decided by whether
        the call compiled anything (a miss = a new bucket/shape opened);
        only hits feed the warm-latency reservoirs, so cold compiles never
        pollute the p50/p99 the serving round cares about.  ``bucket``
        (the pow-2 ladder rung the batch padded to) additionally labels a
        per-bucket reservoir — ``predict_warm_latency_ms{bucket="128"}``
        in the Prometheus output — so multi-bucket request mixes stay
        attributable.  The closing timer read is honest by construction:
        every entry calls this AFTER its accounted ``sync_pull``, and the
        retroactive span records the same interval (jaxlint R9/R10)."""
        if not _obs.enabled():
            return
        t0, c0 = t0c0
        dt_ms = (time.perf_counter() - t0) * 1e3
        warm = _san.compile_totals()["compiles"] == c0
        _obs.counter("predict_requests_total").inc()
        _obs.counter("predict_rows_total").inc(n)
        if warm:
            _obs.counter("predict_bucket_hits_total").inc()
            _obs.histogram("predict_warm_latency_ms").observe(dt_ms)
            # per-entry reservoirs are LABEL SETS on the one family
            # (predict_warm_latency_ms{entry="raw"}), not dotted-suffix
            # names — the dotted form rendered as a separate Prometheus
            # family per entry (round-11 infra note, retired round 18)
            _obs.histogram(_obs.labeled(
                "predict_warm_latency_ms", entry=entry)).observe(dt_ms)
            if bucket is not None:
                _obs.histogram(_obs.labeled(
                    "predict_warm_latency_ms", bucket=bucket)).observe(dt_ms)
        else:
            _obs.counter("predict_bucket_misses_total").inc()
        # trace_ctx (when a serving dispatcher passed its leg context)
        # makes the device-side span a CHILD of that dispatch leg — this
        # runs on dispatcher threads whose ambient span stack is empty,
        # so parentage must arrive explicitly (the R21 rule)
        _trace.record_span(f"predict.{entry}", dt_ms / 1e3,
                           parent=trace_ctx, rows=n,
                           bucket=bucket, warm=warm)

    def _pad_rows(self, X: np.ndarray, n_bucket: int) -> jnp.ndarray:
        """(N, F) host batch -> (n_bucket, F) f32 device array, zero-padded
        tail (padding rows are masked on device by the serving ops)."""
        xh = np.zeros((n_bucket, X.shape[1]), dtype=np.float32)
        xh[: X.shape[0]] = X
        return jnp.asarray(xh)

    def _active_mask(self, n: int, n_bucket: int) -> Optional[jnp.ndarray]:
        if n_bucket == n:
            return None
        m = np.zeros(n_bucket, dtype=bool)
        m[:n] = True
        return jnp.asarray(m)

    def predict_raw(self, X: np.ndarray, start_iteration: int = 0, num_iteration: int = -1) -> np.ndarray:
        """Raw margin prediction on raw feature values (device traversal).

        Uses the export representation — init score folded into the first
        tree(s) per class — so an in-memory model and its .txt save/load
        round-trip predict BIT-IDENTICALLY (the reference also folds:
        Tree::AddBias).

        Serving contract (round 9, pinned by tests/test_predict_budget.py):
        a warm call is ONE device dispatch and ONE blocking pull — the
        packed ensemble comes from the `_packed` cache, the batch is padded
        to the `_predict_bucket` ladder so the traversal compiles once per
        bucket, and multiclass reduces all k classes in that same single
        dispatch (predict_ops.predict_raw_multiclass)."""
        s = self._packed(start_iteration, num_iteration)
        n = X.shape[0]
        k = self.num_tree_per_iteration
        if s is None:
            init = np.asarray(self.init_scores, dtype=np.float64)
            base = np.zeros((n, k), dtype=np.float64) + init[None, :]
            return base[:, 0] if k == 1 else base
        trees = s["_trees"]
        if s["_linear"]:
            # linear leaves evaluate per-leaf ridge models on raw features:
            # vectorized host walk
            Xh = np.asarray(X, dtype=np.float64)
            n_per_class = max(len(trees) // k, 1)
            scale = (1.0 / n_per_class) if self.average_output else 1.0
            outs = np.zeros((n, k), dtype=np.float64)
            for i, t in enumerate(trees):
                outs[:, i % k] += t.predict_batch(Xh) * scale
            return outs[:, 0] if k == 1 else outs
        # categorical bitset decisions ride the device traversal too
        # (Tree::CategoricalDecision as two gathers over flat bitset words)
        cat_kw = {}
        if "is_cat" in s:
            cat_kw = dict(cat_words=s["cat_words"])
        t0c0 = self._serve_t0()
        nb = _predict_bucket(n)
        x = self._pad_rows(X, nb)
        active = self._active_mask(n, nb)
        n_per_class = max(s["T"] // k, 1)
        scale = (1.0 / n_per_class) if self.average_output else 1.0
        _san.record_dispatch()
        if k == 1:
            out = predict_ops.predict_raw_values(
                x, s["split_feature"], s["threshold"], s["default_left"],
                s["missing_type"], s["left_child"], s["right_child"],
                s["num_leaves"], s["leaf_value"],
                is_cat=s.get("is_cat"), cat_base=s.get("cat_base"),
                cat_nwords=s.get("cat_nwords"), active=active, **cat_kw,
            )
            res = np.asarray(
                _san.sync_pull(out)[:n], dtype=np.float64) * scale
            self._serve_note("raw", n, t0c0, bucket=nb)
            return res
        # multiclass: ONE class-reshaped dispatch (predict_raw_multiclass)
        # replaced the k-dispatch per-class host loop; outputs are
        # bit-identical (same per-class summation order)
        out = predict_ops.predict_raw_multiclass(
            x, s["split_feature"], s["threshold"], s["default_left"],
            s["missing_type"], s["left_child"], s["right_child"],
            s["num_leaves"], s["leaf_value"],
            is_cat=s.get("is_cat"), cat_base=s.get("cat_base"),
            cat_nwords=s.get("cat_nwords"), active=active, k=k, **cat_kw,
        )
        res = np.asarray(_san.sync_pull(out)[:n], dtype=np.float64) * scale
        self._serve_note("raw_multiclass", n, t0c0, bucket=nb)
        return res

    def predict_raw_sharded(self, X: np.ndarray, mesh,
                            start_iteration: int = 0,
                            num_iteration: int = -1) -> np.ndarray:
        """``predict_raw`` for giant batches: score a row-sharded ``X`` as
        ONE SPMD dispatch over the row axis of ``mesh``.

        Serving contract (pinned by tests/test_predict_budget.py): BITWISE
        equal to the single-device ``predict_raw``, and a warm call is one
        packed-cache hit, ONE dispatch and ONE blocking pull.  N pads to
        ``d_row * _predict_bucket(ceil(N / d_row))`` so every rank sees the
        same per-rank bucket ladder (one compile per bucket per mesh); the
        padded rows are masked on device exactly like the single-device
        ladder.  The replicated per-tree tables are placed on the mesh once
        per (pack, mesh) and cached inside the pack, so warm calls move
        ONLY the row-sharded batch."""
        s = self._packed(start_iteration, num_iteration)
        n = X.shape[0]
        k = self.num_tree_per_iteration
        if s is None or s["_linear"]:
            # nothing traverses on device (init-score-only or host-walked
            # linear leaves) — the single-device path is already optimal
            return self.predict_raw(X, start_iteration, num_iteration)
        from jax.sharding import NamedSharding, PartitionSpec as _P
        from ..parallel.mesh import DATA_AXIS as _AX

        d_r = int(mesh.shape[_AX])
        t0c0 = self._serve_t0()
        nb = d_r * _predict_bucket(max(1, -(-n // d_r)))
        row_s = NamedSharding(mesh, _P(_AX))
        xh = np.zeros((nb, X.shape[1]), dtype=np.float32)
        xh[:n] = X
        x = jax.device_put(xh, row_s)
        am = np.zeros(nb, dtype=bool)
        am[:n] = True
        active = jax.device_put(am, row_s)
        has_cat = "is_cat" in s
        tabs = s.setdefault("_mesh_tables", {}).get(mesh)
        if tabs is None:
            rep_s = NamedSharding(mesh, _P())
            names = ["split_feature", "threshold", "default_left",
                     "missing_type", "left_child", "right_child",
                     "num_leaves", "leaf_value"]
            if has_cat:
                names += ["is_cat", "cat_base", "cat_nwords", "cat_words"]
            tabs = tuple(jax.device_put(s[m], rep_s) for m in names)
            s["_mesh_tables"][mesh] = tabs
        entry = _sharded_raw_entry(mesh, k, has_cat)
        n_per_class = max(s["T"] // k, 1)
        scale = (1.0 / n_per_class) if self.average_output else 1.0
        _san.record_dispatch()
        out = entry(x, active, *tabs)
        res = np.asarray(_san.sync_pull(out)[:n], dtype=np.float64) * scale
        self._serve_note("raw_sharded", n, t0c0, bucket=nb)
        return res

    def _get_convert_entry(self):
        """Jitted traversal + ``objective.convert_output`` in ONE trace:
        a converted warm predict is one dispatch + one accounted pull
        (round 12 — it was 2 dispatches: the raw traversal, then a
        separate convert dispatch over the re-uploaded raw result).
        Cached for the model's lifetime; reset_split_params nulls it when
        a baked objective constant (e.g. ``sigmoid``) changes.  The
        entry's traced IR is pinned by the ``predict_warm_converted``
        audit contract on a real toy booster (analysis/contracts.py) —
        precisely because this jit closes over instance state the AST
        rules cannot follow."""
        if self._convert_entry is not None:
            return self._convert_entry
        obj = self.objective

        @functools.partial(jax.jit, static_argnames=("k",))
        # jaxlint: disable=R2 (cached in self._convert_entry; nulled only when a baked constant changes)
        def run(x, sf, th, dl, mt, lc, rc, nl, lv, is_cat, cat_base,
                cat_nwords, cat_words, active, *, k):
            if k == 1:
                out = predict_ops.predict_raw_values(
                    x, sf, th, dl, mt, lc, rc, nl, lv, is_cat=is_cat,
                    cat_base=cat_base, cat_nwords=cat_nwords,
                    cat_words=cat_words, active=active)
            else:
                out = predict_ops.predict_raw_multiclass(
                    x, sf, th, dl, mt, lc, rc, nl, lv, is_cat=is_cat,
                    cat_base=cat_base, cat_nwords=cat_nwords,
                    cat_words=cat_words, active=active, k=k)
            # conversions are rowwise (sigmoid/exp/softmax): padded rows
            # cannot leak into real ones, so the bucket ladder stays safe
            return obj.convert_output(out)

        self._convert_entry = run
        return run

    def _predict_converted(self, X, start_iteration, num_iteration):
        """Fused converted predict (serving contract: 1 dispatch + 1
        accounted pull, packed-cache hit, bucket ladder).  Returns None
        when the fused entry does not apply (no trees, linear leaves,
        RF averaging — the caller falls back to the 2-dispatch path,
        also reachable via ``LGBMTPU_FUSED_CONVERT=0``)."""
        s = self._packed(start_iteration, num_iteration)
        if s is None or s["_linear"]:
            return None
        n = X.shape[0]
        k = self.num_tree_per_iteration
        t0c0 = self._serve_t0()
        nb = _predict_bucket(n)
        x = self._pad_rows(X, nb)
        active = self._active_mask(n, nb)
        run = self._get_convert_entry()
        _san.record_dispatch()
        out = run(x, s["split_feature"], s["threshold"], s["default_left"],
                  s["missing_type"], s["left_child"], s["right_child"],
                  s["num_leaves"], s["leaf_value"], s.get("is_cat"),
                  s.get("cat_base"), s.get("cat_nwords"), s.get("cat_words"),
                  active, k=k)
        res = np.asarray(_san.sync_pull(out)[:n])
        self._serve_note("converted", n, t0c0, bucket=nb)
        return res

    # -- coalesced serving dispatch (round 18, lightgbm_tpu/serve) ------
    @staticmethod
    def _coalesced_raw_fn(k: int):
        """The raw-path executable a coalesced batch dispatches: the SAME
        module-level jitted traversal the single-caller warm entries use
        (``predict_ops.predict_raw_values`` / ``predict_raw_multiclass``)
        — never a serve-owned jit.  The serving loop therefore reuses the
        bucket ladder's already-compiled executables (zero retraces by
        construction), and the ``predict_coalesced_bucket`` audit
        contract (analysis/contracts.py) traces exactly this function, so
        the coalescer can never silently grow a second executable
        family."""
        return (predict_ops.predict_raw_values if k == 1
                else predict_ops.predict_raw_multiclass)

    def _coalescible(self, raw_score: bool) -> bool:
        """Whether a ``predict(raw_score=)`` call can ride the coalesced
        batch path BITWISE — the same envelope as the single-caller fast
        entries: a packed non-linear ensemble, no prediction
        early-stopping (its per-row tree count is margin-dependent), and
        for converted output the fused-convert conditions (a real
        objective, no RF host-side averaging, escape hatch honored).
        Ineligible models are served per-request through the full
        ``predict`` path by the runtime (still correct, not coalesced)."""
        early = (
            self.cfg.pred_early_stop
            and not self.average_output
            and self.objective is not None
            and getattr(self.objective, "name", "") in (
                "binary", "multiclass", "multiclassova")
        )
        if early:
            return False
        s = self._packed(0, -1)
        if s is None or s["_linear"]:
            return False
        if raw_score or self.objective is None:
            return True
        return (not self.average_output
                and os.environ.get("LGBMTPU_FUSED_CONVERT", "1") != "0")

    def predict_coalesced(self, x, active, n, *, convert: bool,
                          trace_ctx=None):
        """One coalesced serving batch (lightgbm_tpu/serve/runtime.py):
        ``x`` is an ALREADY-STAGED (nb, F) f32 device batch — the
        runtime's pinned-buffer upload, enqueued while the previous batch
        executes — and ``active`` its row mask (None at exact rung fill,
        mirroring ``_active_mask``).  ONE dispatch + ONE accounted sync
        for the whole batch; rows slice back out per request BITWISE
        equal to individual ``predict`` calls (rows traverse
        independently, conversions are rowwise, and the padded result is
        pinned bit-identical to the unpadded one).

        ``convert=False`` returns raw margins ((n,) or (n, k), f64 with
        the RF scale applied exactly as ``predict_raw``); ``convert=True``
        dispatches the SAME fused instance-cached entry as
        ``_predict_converted``.  The caller checks :meth:`_coalescible`
        first; serving an ineligible model here would silently change
        semantics, so it raises instead."""
        s = self._packed(0, -1)
        if s is None or s["_linear"]:
            raise ValueError(
                "predict_coalesced: model is not coalescible (empty or "
                "linear-leaf ensemble) — route through predict()")
        k = self.num_tree_per_iteration
        t0c0 = self._serve_t0()
        nb = x.shape[0]
        _san.record_dispatch()
        if convert:
            run = self._get_convert_entry()
            out = run(x, s["split_feature"], s["threshold"],
                      s["default_left"], s["missing_type"], s["left_child"],
                      s["right_child"], s["num_leaves"], s["leaf_value"],
                      s.get("is_cat"), s.get("cat_base"), s.get("cat_nwords"),
                      s.get("cat_words"), active, k=k)
            res = np.asarray(_san.sync_pull(out)[:n])
        else:
            cat_kw = {}
            if "is_cat" in s:
                cat_kw = dict(cat_words=s["cat_words"])
            fn = self._coalesced_raw_fn(k)
            kkw = {} if k == 1 else dict(k=k)
            out = fn(x, s["split_feature"], s["threshold"],
                     s["default_left"], s["missing_type"], s["left_child"],
                     s["right_child"], s["num_leaves"], s["leaf_value"],
                     is_cat=s.get("is_cat"), cat_base=s.get("cat_base"),
                     cat_nwords=s.get("cat_nwords"), active=active,
                     **kkw, **cat_kw)
            n_per_class = max(s["T"] // k, 1)
            scale = (1.0 / n_per_class) if self.average_output else 1.0
            res = np.asarray(_san.sync_pull(out)[:n], dtype=np.float64) * scale
        self._serve_note("coalesced", n, t0c0, bucket=nb,
                         trace_ctx=trace_ctx)
        return res

    def predict(self, X, raw_score=False, start_iteration=0, num_iteration=-1,
                pred_leaf=False, pred_contrib=False, mesh=None) -> np.ndarray:
        """``mesh=`` routes the raw traversal through the row-sharded
        giant-batch entry (:meth:`predict_raw_sharded`) — bitwise the
        single-device result.  Early-stopping, pred_leaf and pred_contrib
        have data-dependent/host-side structure and keep the single-device
        path even when a mesh is passed."""
        X = np.asarray(X, dtype=np.float64)
        if pred_leaf:
            return self._predict_leaf(X, start_iteration, num_iteration)
        if pred_contrib:
            return self.predict_contrib(X, start_iteration, num_iteration)
        early_stop = (
            self.cfg.pred_early_stop
            and not self.average_output  # RF averages trees; chunked sums break it
            and self.objective is not None
            and getattr(self.objective, "name", "") in ("binary", "multiclass", "multiclassova")
        )
        if (
            not raw_score
            and not early_stop
            and mesh is None
            and self.objective is not None
            # RF scales raw margins by 1/T on the host in f64 before
            # converting — keep that exact path rather than re-deriving it
            and not self.average_output
            and os.environ.get("LGBMTPU_FUSED_CONVERT", "1") != "0"
        ):
            res = self._predict_converted(X, start_iteration, num_iteration)
            if res is not None:
                return res
        if early_stop:
            raw = self._predict_raw_early_stop(X, start_iteration, num_iteration)
        elif mesh is not None:
            raw = self.predict_raw_sharded(X, mesh, start_iteration,
                                           num_iteration)
        else:
            raw = self.predict_raw(X, start_iteration, num_iteration)
        if raw_score or self.objective is None:
            return raw
        # output conversion rides the same row-bucket ladder: convert_output
        # is jitted per shape, so padding keeps it at one compile per bucket
        # (conversions are rowwise — sigmoid/exp/softmax — so padded rows
        # cannot leak into real ones)
        n = raw.shape[0]
        nb = _predict_bucket(n)
        if nb != n:
            pad = np.zeros((nb,) + raw.shape[1:], raw.dtype)
            pad[:n] = raw
            _san.record_dispatch()
            return _san.sync_pull(self.objective.convert_output(
                jnp.asarray(pad)))[:n]
        _san.record_dispatch()
        return _san.sync_pull(self.objective.convert_output(jnp.asarray(raw)))

    def _predict_leaf(self, X: np.ndarray, start_iteration: int = 0,
                      num_iteration: int = -1) -> np.ndarray:
        """``pred_leaf``: leaf index per (row, tree) — (N, T) i32.

        Round 9 routes this through the stacked device traversal
        (ops/predict.py predict_leaf_values) instead of the per-tree host
        walk: one dispatch over the cached packed ensemble, f32 decision
        semantics identical to predict_raw (leaf structure is shared with
        the value path — `_f32_threshold_upper` keeps left rows left)."""
        n = X.shape[0]
        s = self._packed(start_iteration, num_iteration)
        if s is None:
            return np.zeros((n, 0), dtype=np.int32)
        t0c0 = self._serve_t0()
        nb = _predict_bucket(n)
        x = self._pad_rows(X, nb)
        cat_kw = {}
        if "is_cat" in s:
            cat_kw = dict(
                is_cat=s["is_cat"], cat_base=s["cat_base"],
                cat_nwords=s["cat_nwords"], cat_words=s["cat_words"])
        _san.record_dispatch()
        out = predict_ops.predict_leaf_values(
            x, s["split_feature"], s["threshold"], s["default_left"],
            s["missing_type"], s["left_child"], s["right_child"],
            s["num_leaves"], **cat_kw,
        )
        res = np.asarray(_san.sync_pull(out)[:n], dtype=np.int32)
        self._serve_note("leaf", n, t0c0, bucket=nb)
        return res

    def _predict_raw_early_stop(self, X, start_iteration=0, num_iteration=-1):
        """Prediction early stopping (reference: include/LightGBM/
        prediction_early_stop.h + predictor.hpp): every pred_early_stop_freq
        trees, rows whose margin (|raw| for binary, top1-top2 for multiclass)
        exceeds pred_early_stop_margin stop accumulating further trees.

        Round 9: every chunk keeps ALL rows in the padded batch and masks
        early-stopped rows ON DEVICE (predict_ops.predict_raw_window with a
        traced tree offset over the window-padded packed ensemble), so each
        chunk reuses ONE compiled executable — the old path shrank the
        active set host-side (``X[active]``, jaxlint R8) and compiled
        O(chunks) times per distinct active-set size."""
        k = self.num_tree_per_iteration
        total = len(self.models) // k
        if num_iteration is not None and num_iteration >= 0:
            total = min(total, start_iteration + num_iteration)
        freq = max(int(self.cfg.pred_early_stop_freq), 1)
        margin = float(self.cfg.pred_early_stop_margin)
        X = np.asarray(X)
        n = X.shape[0]
        n_iters = total - start_iteration
        if n_iters <= 0:
            return self.predict_raw(X, start_iteration, 0)
        # a freq beyond the model is one all-trees chunk, not a dummy-tree
        # pad blowup (the old chunked path's min(freq, total - it))
        freq = min(freq, n_iters)
        window = freq * k
        s = self._packed(start_iteration, n_iters, pad_trees_to=window)
        if s is None:
            return self.predict_raw(X, start_iteration, 0)
        if s["_linear"]:
            # linear leaves walk on host — chunk over full rows (no device
            # executable to protect; masked accumulation keeps semantics)
            raw = None
            active = np.ones(n, dtype=bool)
            it = start_iteration
            while it < total:
                chunk = min(freq, total - it)
                part = self.predict_raw(X, it, chunk)
                raw = part if raw is None else raw + np.where(
                    (active if part.ndim == 1 else active[:, None]), part, 0.0)
                it += chunk
                active &= self._early_stop_active(raw, margin)
                if not active.any():
                    break
            return raw
        cat_kw = {}
        if "is_cat" in s:
            cat_kw = dict(cat_words=s["cat_words"])
        t0c0 = self._serve_t0()
        nb = _predict_bucket(n)
        x = self._pad_rows(X, nb)
        active = np.zeros(nb, dtype=bool)
        active[:n] = True
        shape = (n,) if k == 1 else (n, k)
        raw = np.zeros(shape, dtype=np.float64)
        for ci in range(s["T"] // window):
            _san.record_dispatch()
            out = predict_ops.predict_raw_window(
                x, jnp.int32(ci * window),
                s["split_feature"], s["threshold"], s["default_left"],
                s["missing_type"], s["left_child"], s["right_child"],
                s["num_leaves"], s["leaf_value"],
                is_cat=s.get("is_cat"), cat_base=s.get("cat_base"),
                cat_nwords=s.get("cat_nwords"),
                active=jnp.asarray(active), k=k, window=window, **cat_kw,
            )
            # the margin test is a REAL host data dependency (the loop's
            # exit condition) — one accounted blocking pull per chunk
            raw += _san.sync_pull(out)[:n].astype(np.float64)
            active[:n] &= self._early_stop_active(raw, margin)
            if not active[:n].any():
                break
        # the last chunk's sync_pull already drained the device queue, so
        # the whole-call latency is honestly attributed (every chunk ends
        # in an accounted blocking pull)
        self._serve_note("raw_early_stop", n, t0c0, bucket=nb)
        return raw

    @staticmethod
    def _early_stop_active(raw: np.ndarray, margin: float) -> np.ndarray:
        """Rows whose margin has NOT yet cleared pred_early_stop_margin."""
        if raw.ndim == 1:
            m = np.abs(raw)
        else:
            top2 = np.partition(raw, -2, axis=1)[:, -2:]
            m = top2[:, 1] - top2[:, 0]
        return m < margin

    def predict_contrib(self, X, start_iteration=0, num_iteration=-1) -> np.ndarray:
        """SHAP values via the per-tree path algorithm (reference:
        Tree::PredictContrib / TreeSHAP in tree.cpp)."""
        if any(t.is_linear for t in self.models):
            # reference: Predictor raises a fatal for contrib on linear trees
            raise ValueError("predict_contrib is not supported for linear trees")
        from .shap import tree_shap_ensemble

        k = self.num_tree_per_iteration
        # export trees fold the boost_from_average init into the first tree per
        # class, so the SHAP bias column matches predictions (the constant
        # shift lands in the expected value, not in feature attributions)
        trees = self._trees_for_export(start_iteration, num_iteration)
        return tree_shap_ensemble(trees, np.asarray(X, np.float64), k)

    def to_if_else(self) -> str:
        """Standalone C++ predictor source (reference: task=convert_model,
        GBDT::SaveModelToIfElse + Tree::ToIfElse in src/io/tree.cpp).

        Precision contract: the emitted code evaluates in float64 and
        bit-matches the host f64 tree walk (Tree.predict summed over
        exported trees).  Booster.predict runs the f32 device path, so the
        two agree only to ~1e-6 relative — same as the reference, whose
        ToIfElse output is double while GPU predict paths are float.
        """
        from .tree import tree_to_if_else

        trees = self._trees_for_export(0, -1)
        k = self.num_tree_per_iteration
        parts = [
            "// Generated by lightgbm_tpu task=convert_model",
            "#include <cmath>",
            "",
        ]
        for i, t in enumerate(trees):
            parts.append(tree_to_if_else(t, i))
            parts.append("")
        n_per_class = max(len(trees) // k, 1) if trees else 1
        scale = (1.0 / n_per_class) if self.average_output else 1.0
        calls = " + ".join(f"PredictTree{i}(x)" for i in range(len(trees))) or "0.0"
        if k == 1:
            parts.append("extern \"C\" double PredictRaw(const double* x) {")
            parts.append(f"  return ({calls}) * {scale:.17g};")
            parts.append("}")
            obj = self._objective_string()
            if obj.startswith("binary"):
                parts.append("extern \"C\" double Predict(const double* x) {")
                parts.append("  return 1.0 / (1.0 + std::exp(-PredictRaw(x)));")
                parts.append("}")
            else:
                parts.append("extern \"C\" double Predict(const double* x) {")
                parts.append("  return PredictRaw(x);")
                parts.append("}")
        else:
            parts.append(f"static const int kNumClass = {k};")
            parts.append("extern \"C\" void PredictRaw(const double* x, double* out) {")
            for c in range(k):
                terms = " + ".join(
                    f"PredictTree{i}(x)" for i in range(c, len(trees), k)
                ) or "0.0"
                parts.append(f"  out[{c}] = ({terms}) * {scale:.17g};")
            parts.append("}")
            parts.append("extern \"C\" void Predict(const double* x, double* out) {")
            parts.append("  PredictRaw(x, out);")
            parts.append("  double m = out[0]; for (int c = 1; c < kNumClass; ++c) if (out[c] > m) m = out[c];")
            parts.append("  double s = 0.0; for (int c = 0; c < kNumClass; ++c) { out[c] = std::exp(out[c] - m); s += out[c]; }")
            parts.append("  for (int c = 0; c < kNumClass; ++c) out[c] /= s;")
            parts.append("}")
        return "\n".join(parts) + "\n"

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split") -> np.ndarray:
        """reference: GBDT::FeatureImportance."""
        f = len(self.feature_names) if self.feature_names else (
            self.train_set.num_feature() if self.train_set else 0
        )
        imp = np.zeros(f, dtype=np.float64)
        for t in self.models:
            for i in range(t.num_internal):
                if importance_type == "split":
                    imp[t.split_feature[i]] += 1.0
                else:
                    imp[t.split_feature[i]] += max(float(t.split_gain[i]), 0.0)
        return imp

    # ------------------------------------------------------------------
    # model text format (reference: gbdt_model_text.cpp)
    # ------------------------------------------------------------------
    def _objective_string(self) -> str:
        o = self.cfg.objective
        if o == "binary":
            return f"binary sigmoid:{self.cfg.sigmoid:g}"
        if o in ("multiclass", "multiclassova"):
            return f"{o} num_class:{self.cfg.num_class}"
        if o == "lambdarank":
            return "lambdarank"
        if o == "regression" and self.cfg.reg_sqrt:
            # reference: RegressionL2loss::ToString emits "regression sqrt"
            return "regression sqrt"
        return o

    def _trees_for_export(self, start: int, num_iteration: int,
                          fold: bool = True) -> List[Tree]:
        """Trees with the init score folded in so the saved model is
        self-contained (reference: Tree::AddBias semantics): for gbdt/dart the
        first tree per class gets +init; for RF (averaged output) EVERY tree
        gets +init so avg(trees) = init + avg(deltas).  ``fold=False``
        returns the raw iteration window unchanged — the raw-delta
        snapshot form, which carries init separately."""
        import copy as _copy

        k = self.num_tree_per_iteration
        lo = start * k
        hi = len(self.models) if num_iteration < 0 else min((start + num_iteration) * k, len(self.models))
        trees = list(self.models[lo:hi])
        if not fold or lo != 0 or not any(s != 0.0 for s in self.init_scores):
            return trees
        if self.average_output:
            fold_idx = range(len(trees))
        else:
            fold_idx = range(min(k, len(trees)))
        for i in fold_idx:
            c = i % k
            t = _copy.deepcopy(trees[i])
            t.leaf_value = t.leaf_value + self.init_scores[c]
            t.internal_value = t.internal_value + self.init_scores[c]
            if t.is_linear and t.leaf_const is not None:
                # linear prediction reads leaf_const, not leaf_value
                t.leaf_const = t.leaf_const + self.init_scores[c]
            trees[i] = t
        return trees

    def save_model_to_string(self, num_iteration: int = -1, start_iteration: int = 0,
                             importance_type: str = None,
                             raw_deltas: bool = False) -> str:
        # never serialize (or snapshot) a model poisoned by non-finite
        # training values — the deferred guard is settled here at the latest
        self._guard_check()
        if importance_type is None:
            # reference: config saved_feature_importance_type selects the
            # importance written into the model file (0=split, 1=gain)
            importance_type = (
                "gain" if int(self.cfg.saved_feature_importance_type) == 1
                else "split"
            )
        k = self.num_tree_per_iteration
        # raw_deltas: the snapshot form (docs/ROBUSTNESS.md "Elastic fleet
        # recovery") — trees stay PURE deltas and the boost_from_average
        # init score is carried as an explicit `init_scores=` header line
        # instead of being folded into tree 0's float64 leaf values.
        # Folding rounds (fl64(v0+init)), so a resume replaying folded
        # trees reconstructs fl32(v0+init) where the live run held
        # fl32(init)+fl32(v0) — a last-ulp score skew that cascades into
        # every post-resume tree.  Raw-delta snapshots make crash-resume
        # BITWISE-identical to uninterrupted training.
        trees = self._trees_for_export(start_iteration, num_iteration,
                                       fold=not raw_deltas)
        feature_names = self.feature_names or [f"Column_{i}" for i in range(self.train_set.num_feature())]
        if self.binner is not None:
            infos = []
            for m in self.binner.mappers:
                if m.is_trivial:
                    infos.append("none")
                elif m.is_categorical:
                    infos.append(":".join(str(int(c)) for c in m.categories))
                else:
                    infos.append(f"[{m.min_value:g}:{m.max_value:g}]")
        else:
            infos = ["none"] * len(feature_names)

        blocks = [t.to_string(i, precise=raw_deltas) for i, t in enumerate(trees)]
        tree_sizes = [len(b) + 1 for b in blocks]
        lines = [
            "tree",
            f"version={_MODEL_VERSION}",
            f"num_class={self.cfg.num_class}",
            f"num_tree_per_iteration={k}",
            "label_index=0",
            f"max_feature_idx={len(feature_names) - 1}",
            f"objective={self._objective_string()}",
            *(["average_output"] if self.average_output else []),
            # exact decimal round-trip (repr) — float() recovers the same
            # f64 bits, so a resumed run rebuilds the identical score base
            *([f"init_scores=" + " ".join(repr(float(s))
                                          for s in self.init_scores)]
              if raw_deltas else []),
            "feature_names=" + " ".join(feature_names),
            "feature_infos=" + " ".join(infos),
            "tree_sizes=" + " ".join(str(s) for s in tree_sizes),
            "",
        ]
        out = "\n".join(lines) + "\n" + "\n".join(blocks)
        out += "\nend of trees\n\n"
        imp = self.feature_importance(importance_type)
        order = np.argsort(-imp, kind="stable")
        out += "feature_importances:\n"
        for i in order:
            if imp[i] > 0:
                out += f"{feature_names[i]}={imp[i]:g}\n"
        out += "\nparameters:\n"
        cfg = self.cfg.to_dict()
        for key in ("objective", "boosting", "num_iterations", "learning_rate", "num_leaves",
                    "max_depth", "min_data_in_leaf", "lambda_l1", "lambda_l2", "max_bin",
                    "num_class", "seed", "tree_learner", "device_type"):
            out += f"[{key}: {cfg.get(key)}]\n"
        out += "end of parameters\n\npandas_categorical:null\n"
        return out

    @classmethod
    def load_model_from_string(cls, model_str: str) -> "GBDT":
        header, _, rest = model_str.partition("\nTree=")
        kv = {}
        for line in header.splitlines():
            if "=" in line:
                key, v = line.split("=", 1)
                kv[key.strip()] = v.strip()
        obj_str = kv.get("objective", "regression").split()
        params: Dict[str, object] = {"objective": obj_str[0]}
        for tok in obj_str[1:]:
            if ":" in tok:
                pk, pv = tok.split(":", 1)
                params[pk] = pv
            elif tok == "sqrt":  # reference: "regression sqrt"
                params["reg_sqrt"] = True
        if int(kv.get("num_class", 1)) > 1:
            params["num_class"] = int(kv["num_class"])
        cfg = Config.from_dict(params)
        booster = cls(cfg)
        booster.feature_names = kv.get("feature_names", "").split()
        booster.num_tree_per_iteration = int(kv.get("num_tree_per_iteration", 1))
        booster.average_output = any(
            line.strip() == "average_output" for line in header.splitlines()
        )
        if "init_scores" in kv:
            # raw-delta snapshot form: trees are pure deltas, the init
            # score rides this header line (save_model_to_string raw_deltas)
            booster.init_scores = [float(v) for v in kv["init_scores"].split()]
            if len(booster.init_scores) != booster.num_tree_per_iteration:
                # a count mismatch means a torn header or a class-count
                # mix-up; silently zeroing would load a model whose
                # predictions are missing the boost_from_average base
                raise ValueError(
                    f"snapshot init_scores header has "
                    f"{len(booster.init_scores)} entries but "
                    f"num_tree_per_iteration is "
                    f"{booster.num_tree_per_iteration} — torn or "
                    "mismatched raw-delta snapshot (docs/ROBUSTNESS.md)")
        else:
            booster.init_scores = [0.0] * booster.num_tree_per_iteration  # folded into trees
        trees_part = rest.split("\nend of trees")[0]
        blocks = ("Tree=" + trees_part).split("\nTree=")
        for b in blocks:
            if b.strip():
                booster.models.append(Tree.from_string("Tree=" + b if not b.startswith("Tree=") else b))
        booster.iter_ = len(booster.models) // max(booster.num_tree_per_iteration, 1)
        return booster


class DART(GBDT):
    """reference: src/boosting/dart.hpp — dropout boosting."""

    def train_one_iter(self, grad=None, hess=None) -> bool:
        cfg = self.cfg
        k = self.num_tree_per_iteration
        n_iters_done = self.iter_
        rng = np.random.RandomState(cfg.drop_seed + n_iters_done)
        drop_idx: List[int] = []
        if n_iters_done > 0 and rng.rand() >= cfg.skip_drop:
            if cfg.uniform_drop:
                mask = rng.rand(n_iters_done) < cfg.drop_rate
                drop_idx = list(np.nonzero(mask)[0])
            else:
                want = max(int(round(n_iters_done * cfg.drop_rate)), 1)
                drop_idx = list(rng.choice(n_iters_done, size=min(want, n_iters_done), replace=False))
            drop_idx = drop_idx[: cfg.max_drop] if cfg.max_drop > 0 else drop_idx
        # remove dropped trees' contribution from scores
        self._dart_removed = []
        for it in drop_idx:
            for c in range(k):
                tree = self.models[it * k + c]
                leaf_id = self.train_set.predict_leaf_binned_tree(tree)
                vals = jnp.asarray(tree.leaf_value, jnp.float32)[leaf_id]
                if k == 1:
                    self._score = self._score - vals
                else:
                    self._score = self._score.at[:, c].add(-vals)
        finished = super().train_one_iter(grad, hess)
        # normalization (reference: DART::Normalize)
        n_drop = len(drop_idx)
        if n_drop > 0:
            if cfg.xgboost_dart_mode:
                new_scale = cfg.learning_rate / (n_drop + cfg.learning_rate)
                old_scale = n_drop / (n_drop + cfg.learning_rate)
            else:
                new_scale = 1.0 / (n_drop + 1.0)
                old_scale = n_drop / (n_drop + 1.0)
            for c in range(k):
                new_tree = self.models[-k + c]
                new_tree.apply_shrinkage(new_scale)
            for it in drop_idx:
                for c in range(k):
                    self.models[it * k + c].apply_shrinkage(old_scale)
            # rebuild scores: add back dropped trees (rescaled) and fix new tree scale
            for it in drop_idx:
                for c in range(k):
                    tree = self.models[it * k + c]
                    leaf_id = self.train_set.predict_leaf_binned_tree(tree)
                    vals = jnp.asarray(tree.leaf_value, jnp.float32)[leaf_id]
                    if k == 1:
                        self._score = self._score + vals
                    else:
                        self._score = self._score.at[:, c].add(vals)
            for c in range(k):
                tree = self.models[-k + c]
                leaf_id = self.train_set.predict_leaf_binned_tree(tree)
                # score currently holds the un-rescaled new tree: subtract the difference
                vals = jnp.asarray(tree.leaf_value, jnp.float32)[leaf_id]
                corr = vals * (1.0 / new_scale - 1.0)
                if k == 1:
                    self._score = self._score - corr
                else:
                    self._score = self._score.at[:, c].add(-corr)
        return finished


class RF(GBDT):
    """reference: src/boosting/rf.hpp — bagging-only forest, averaged output."""

    average_output = True

    def __init__(self, cfg: Config, train_set=None, objective=None):
        if cfg.bagging_freq <= 0 or cfg.bagging_fraction >= 1.0:
            raise ValueError("Random forest needs bagging (bagging_freq > 0 and bagging_fraction < 1)")
        super().__init__(cfg, train_set, objective)

    def train_one_iter(self, grad=None, hess=None) -> bool:
        # RF computes gradients at the (fixed) init score every iteration
        if grad is None and self.objective is not None:
            base = jnp.zeros_like(self._score) + jnp.asarray(
                np.asarray(self.init_scores, dtype=np.float32)
                if self.num_tree_per_iteration > 1 else np.float32(self.init_scores[0])
            )
            g, h = self.objective.get_gradients(base, self._label, self._weight)
            grad, hess = np.asarray(g), np.asarray(h)
        return super().train_one_iter(grad, hess)

    def _eval_margin(self, score):
        # _score holds init + sum(deltas); metrics need init + mean(deltas)
        init = np.asarray(self.init_scores, dtype=np.float32)
        init = init[0] if self.num_tree_per_iteration == 1 else init[None, :]
        return init + (score - init) / max(self.iter_, 1)


def create_boosting(cfg: Config, train_set=None) -> GBDT:
    """reference: Boosting::CreateBoosting in src/boosting/boosting.cpp."""
    name = cfg.boosting
    if name in ("gbdt", "gbrt", "goss"):
        if name == "goss":
            cfg.data_sample_strategy = "goss"
        return GBDT(cfg, train_set)
    if name == "dart":
        return DART(cfg, train_set)
    if name in ("rf", "random_forest"):
        return RF(cfg, train_set)
    raise ValueError(f"Unknown boosting type: {name}")

def _parse_interaction_constraints(spec, feature_names):
    """Parse interaction_constraints: "[0,1,2],[2,3]" or list of lists of
    feature indices/names (reference: Config interaction_constraints string)."""
    if not spec:
        return []
    if isinstance(spec, str):
        import re

        groups = re.findall(r"\[([^\]]*)\]", spec)
        sets = []
        for g in groups:
            items = [t.strip() for t in g.split(",") if t.strip()]
            sets.append(items)
    else:
        sets = [list(g) for g in spec]
    out = []
    name_to_idx = {n: i for i, n in enumerate(feature_names or [])}
    for g in sets:
        idxs = []
        for it in g:
            if isinstance(it, str) and not it.lstrip("-").isdigit():
                if it in name_to_idx:
                    idxs.append(name_to_idx[it])
            else:
                idxs.append(int(it))
        out.append(idxs)
    return out
