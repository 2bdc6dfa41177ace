"""Objective functions: gradients/hessians as pure jitted array functions.

TPU-native re-design of the reference's objective layer
(reference: src/objective/objective_function.cpp factory,
regression_objective.hpp, binary_objective.hpp, multiclass_objective.hpp,
xentropy_objective.hpp, rank_objective.hpp, and their CUDA twins under
src/objective/cuda/ — here one implementation serves every backend since XLA
compiles the same code for TPU and CPU).

Each objective exposes:
  * get_gradients(score, label, weight) -> (grad, hess), both (N,) or (N, K)
  * boost_from_score(label, weight) -> float init score (reference:
    ObjectiveFunction::BoostFromScore, used when boost_from_average=true)
  * convert_output(score) -> prediction-space outputs (reference:
    ObjectiveFunction::ConvertOutput)
  * renew_tree_output(...) optional per-leaf refit (L1/quantile/MAPE/Huber —
    reference: RenewTreeOutput); implemented with masked per-leaf weighted
    quantiles on device.
"""

from __future__ import annotations

import functools
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .obs import metrics as _obs
from .utils.profiling import phase_scope

Array = jnp.ndarray


class Objective:
    """Base class; subclasses are lightweight param holders — all math is in
    jit-compiled static methods closed over Python-float hyperparams."""

    name = "custom"
    num_model_per_iteration = 1
    need_renew = False
    is_constant_hessian = False
    # get_gradients is a pure jnp function of (score, label, weight) and may
    # be traced inside the fused training step (models/gbdt.py); objectives
    # with per-iteration host state must set this False (or override
    # is_fusable for instance-dependent purity)
    fusable = True

    def is_fusable(self) -> bool:
        return self.fusable

    # fused-state protocol: objectives with per-iteration device state (e.g.
    # LambdaRank position biases) stay fusable by threading that state
    # through the fused step as an explicit carry instead of mutating self
    # in-trace.  fused_state() -> carry (or None); fused_gradients is PURE
    # and returns (grad, hess, new_carry); set_fused_state writes the carry
    # back after the step retires.
    def fused_state(self):
        return None

    def fused_gradients(self, score: Array, label: Array,
                        weight: Optional[Array], state):
        g, h = self.get_gradients(score, label, weight)
        return g, h, state

    def set_fused_state(self, state) -> None:
        pass

    def __init__(self, cfg: Config):
        self.cfg = cfg

    def get_gradients(self, score: Array, label: Array, weight: Optional[Array]) -> Tuple[Array, Array]:
        raise NotImplementedError

    def boost_from_score(self, label: Array, weight: Optional[Array]) -> float:
        return 0.0

    def convert_output(self, score: Array) -> Array:
        return score

    def renew_tree_output(self, leaf_pred, label, weight, score, leaf_id, num_leaves) -> Optional[Array]:
        return None

    def _w(self, weight, label):
        return jnp.ones_like(label) if weight is None else weight


class RegressionL2(Objective):
    """reference: RegressionL2loss in regression_objective.hpp.

    reg_sqrt (plain L2 only, as in the reference): the model is fit to
    sign(y)*sqrt(|y|) and predictions are squared back in ConvertOutput —
    metrics see original-scale outputs through GBDT._converted."""

    name = "regression"
    is_constant_hessian = True

    def __init__(self, cfg: Config):
        super().__init__(cfg)
        self.sqrt = bool(cfg.reg_sqrt) and type(self) is RegressionL2

    def _t(self, label):
        if self.sqrt:
            return jnp.sign(label) * jnp.sqrt(jnp.abs(label))
        return label

    def get_gradients(self, score, label, weight):
        w = self._w(weight, label)
        return (score - self._t(label)) * w, w

    def boost_from_score(self, label, weight):
        label = self._t(jnp.asarray(label))
        if weight is None:
            return float(jnp.mean(label))
        return float(jnp.sum(label * weight) / jnp.sum(weight))

    def convert_output(self, score):
        if self.sqrt:
            return jnp.sign(score) * score * score
        return score


class RegressionL1(Objective):
    """reference: RegressionL1loss — gradient is sign, leaf renewed to the
    weighted median of residuals (RenewTreeOutput with percentile 0.5)."""

    name = "regression_l1"
    need_renew = True
    is_constant_hessian = True

    def get_gradients(self, score, label, weight):
        w = self._w(weight, label)
        return jnp.sign(score - label) * w, w

    def boost_from_score(self, label, weight):
        return float(_weighted_quantile_np(np.asarray(label), None if weight is None else np.asarray(weight), 0.5))

    def renew_tree_output(self, leaf_pred, label, weight, score, leaf_id, num_leaves):
        residual = label - score
        return _per_leaf_weighted_quantile(residual, self._w(weight, label), leaf_id, num_leaves, 0.5)


class RegressionHuber(RegressionL2):
    """reference: RegressionHuberLoss (alpha)."""

    name = "huber"
    need_renew = False
    is_constant_hessian = True

    def get_gradients(self, score, label, weight):
        a = self.cfg.alpha
        w = self._w(weight, label)
        diff = score - label
        g = jnp.where(jnp.abs(diff) <= a, diff, jnp.sign(diff) * a)
        return g * w, w


class RegressionFair(Objective):
    """reference: RegressionFairLoss (fair_c)."""

    name = "fair"
    is_constant_hessian = False

    def get_gradients(self, score, label, weight):
        c = self.cfg.fair_c
        w = self._w(weight, label)
        x = score - label
        g = c * x / (jnp.abs(x) + c)
        h = c * c / ((jnp.abs(x) + c) ** 2)
        return g * w, h * w


class RegressionPoisson(Objective):
    """reference: RegressionPoissonLoss — scores in log space; hessian uses
    poisson_max_delta_step safeguard (see sklearn test_compare_lightgbm.py:101
    for the behavioral consequence)."""

    name = "poisson"

    def get_gradients(self, score, label, weight):
        w = self._w(weight, label)
        g = (jnp.exp(score) - label) * w
        h = jnp.exp(score + self.cfg.poisson_max_delta_step) * w
        return g, h

    def boost_from_score(self, label, weight):
        w = 1.0 if weight is None else weight
        mean = float(jnp.sum(label * w) / jnp.sum(jnp.ones_like(label) * w))
        return float(np.log(max(mean, 1e-9)))

    def convert_output(self, score):
        return jnp.exp(score)


class RegressionGamma(RegressionPoisson):
    """reference: RegressionGammaLoss."""

    name = "gamma"

    def get_gradients(self, score, label, weight):
        w = self._w(weight, label)
        g = (1.0 - label * jnp.exp(-score)) * w
        h = label * jnp.exp(-score) * w
        return g, h


class RegressionTweedie(RegressionPoisson):
    """reference: RegressionTweedieLoss (tweedie_variance_power rho)."""

    name = "tweedie"

    def get_gradients(self, score, label, weight):
        rho = self.cfg.tweedie_variance_power
        w = self._w(weight, label)
        exp1 = jnp.exp((1.0 - rho) * score)
        exp2 = jnp.exp((2.0 - rho) * score)
        g = (-label * exp1 + exp2) * w
        h = (-label * (1.0 - rho) * exp1 + (2.0 - rho) * exp2) * w
        return g, h


class RegressionQuantile(Objective):
    """reference: RegressionQuantileloss (alpha), leaf renewed to the alpha
    quantile of residuals."""

    name = "quantile"
    need_renew = True
    is_constant_hessian = True

    def get_gradients(self, score, label, weight):
        a = self.cfg.alpha
        w = self._w(weight, label)
        g = jnp.where(score >= label, 1.0 - a, -a)
        return g * w, w

    def boost_from_score(self, label, weight):
        return float(_weighted_quantile_np(np.asarray(label), None if weight is None else np.asarray(weight), self.cfg.alpha))

    def renew_tree_output(self, leaf_pred, label, weight, score, leaf_id, num_leaves):
        residual = label - score
        return _per_leaf_weighted_quantile(residual, self._w(weight, label), leaf_id, num_leaves, self.cfg.alpha)


class RegressionMAPE(Objective):
    """reference: RegressionMAPELOSS — label-scaled weights, median renew."""

    name = "mape"
    need_renew = True
    is_constant_hessian = True

    def get_gradients(self, score, label, weight):
        w = self._w(weight, label)
        scale = w / jnp.maximum(1.0, jnp.abs(label))
        scale = scale / jnp.mean(scale)
        return jnp.sign(score - label) * scale, scale

    def boost_from_score(self, label, weight):
        # same 1/max(1,|label|)-scaled weights as the boosting rounds
        # (reference: RegressionMAPELOSS::BoostFromScore weighted percentile)
        lab = np.asarray(label, np.float64)
        w = np.ones_like(lab) if weight is None else np.asarray(weight, np.float64)
        w = w / np.maximum(1.0, np.abs(lab))
        return float(_weighted_quantile_np(lab, w, 0.5))

    def renew_tree_output(self, leaf_pred, label, weight, score, leaf_id, num_leaves):
        w = self._w(weight, label) / jnp.maximum(1.0, jnp.abs(label))
        return _per_leaf_weighted_quantile(label - score, w, leaf_id, num_leaves, 0.5)


class BinaryLogloss(Objective):
    """reference: BinaryLogloss in binary_objective.hpp.

    grad = sigmoid_scale * (p - y) * label_weight; hess = scale^2 p (1-p) w.
    is_unbalance / scale_pos_weight set the positive-label weight.
    """

    name = "binary"

    def __init__(self, cfg: Config):
        super().__init__(cfg)
        self.pos_weight = cfg.scale_pos_weight

    def prepare(self, label: np.ndarray, weight) -> None:
        if self.cfg.is_unbalance:
            pos = float(np.sum(label > 0))
            neg = float(len(label) - pos)
            if pos > 0 and neg > 0:
                self.pos_weight = neg / pos

    def get_gradients(self, score, label, weight):
        sig = self.cfg.sigmoid
        w = self._w(weight, label)
        y = jnp.where(label > 0, 1.0, -1.0)
        lw = jnp.where(label > 0, self.pos_weight, 1.0) * w
        response = -y * sig / (1.0 + jnp.exp(y * sig * score))
        grad = response * lw
        hess = jnp.abs(response) * (sig - jnp.abs(response)) * lw
        return grad, hess

    def boost_from_score(self, label, weight):
        if weight is None:
            p = float(jnp.mean(jnp.where(label > 0, 1.0, 0.0)))
        else:
            p = float(jnp.sum(jnp.where(label > 0, weight, 0.0)) / jnp.sum(weight))
        p = min(max(p, 1e-15), 1.0 - 1e-15)
        return float(np.log(p / (1.0 - p)) / self.cfg.sigmoid)

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-self.cfg.sigmoid * score))


class MulticlassSoftmax(Objective):
    """reference: MulticlassSoftmax — K trees per iteration; hessian carries
    the factor-2 convention (sklearn utils.py:69-77 documents it)."""

    name = "multiclass"

    def __init__(self, cfg: Config):
        super().__init__(cfg)
        self.num_model_per_iteration = cfg.num_class

    def get_gradients(self, score, label, weight):
        # score: (N, K); label: (N,) int class ids
        k = self.cfg.num_class
        w = self._w(weight, label)[:, None]
        p = jax.nn.softmax(score, axis=-1)
        y = jax.nn.one_hot(label.astype(jnp.int32), k, dtype=score.dtype)
        grad = (p - y) * w
        hess = 2.0 * p * (1.0 - p) * w
        return grad, hess

    def convert_output(self, score):
        return jax.nn.softmax(score, axis=-1)


class MulticlassOVA(Objective):
    """reference: MulticlassOVA — K independent binary problems."""

    name = "multiclassova"

    def __init__(self, cfg: Config):
        super().__init__(cfg)
        self.num_model_per_iteration = cfg.num_class
        self.binary = BinaryLogloss(cfg)

    def get_gradients(self, score, label, weight):
        k = self.cfg.num_class
        y = jax.nn.one_hot(label.astype(jnp.int32), k, dtype=score.dtype)
        grads, hesss = jax.vmap(
            lambda s, yy: self.binary.get_gradients(s, yy, weight), in_axes=(1, 1), out_axes=1
        )(score, y)
        return grads, hesss

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-self.cfg.sigmoid * score))


class CrossEntropy(Objective):
    """reference: CrossEntropy in xentropy_objective.hpp (labels in [0,1])."""

    name = "cross_entropy"

    def get_gradients(self, score, label, weight):
        w = self._w(weight, label)
        p = 1.0 / (1.0 + jnp.exp(-score))
        return (p - label) * w, p * (1.0 - p) * w

    def boost_from_score(self, label, weight):
        p = float(jnp.mean(label)) if weight is None else float(
            jnp.sum(label * weight) / jnp.sum(weight)
        )
        p = min(max(p, 1e-15), 1 - 1e-15)
        return float(np.log(p / (1 - p)))

    def convert_output(self, score):
        return 1.0 / (1.0 + jnp.exp(-score))


# the pair terms one piece of a bucket forms at a time, in lanes: 16M
# float32 lanes are 64 MB a temporary, whatever XLA chooses to keep of them
_PAIR_PIECE_LANES = 1 << 24


class _QueryBucket(NamedTuple):
    """The queries of one width ``S``, a power of two: every query longer
    than ``S / 2`` and no longer than ``S``, a query a row of the block."""

    rows: Array  # (Q, S) int32: the row a lane holds; num_data in a padded lane
    lens: Array  # (Q,) int32: rows of each query; lanes from there on are padding
    label: Array  # (Q, S) float32: the rows' labels, 0 in a padded lane


class _QueryLayout(NamedTuple):
    """Every query in the bucket of its width, and the way back."""

    buckets: Tuple[_QueryBucket, ...]
    lane_of_row: Array  # (N,) int32: a row's lane, the buckets' lanes end to end


def _lane_ids(bucket: _QueryBucket) -> Array:
    return jax.lax.broadcasted_iota(jnp.int32, bucket.rows.shape, 1)


def _valid_lanes(bucket: _QueryBucket) -> Array:
    return _lane_ids(bucket) < bucket.lens[:, None]


def _rows_to_lanes(values: Array, bucket: _QueryBucket) -> Array:
    """(N,) by row to the bucket's (Q, S); a padded lane reads the last row
    and is masked by whoever reads it."""
    return jnp.take(values, bucket.rows, mode="clip")


def _lanes_to_rows(layout: _QueryLayout, per_bucket) -> Array:
    """The buckets' (Q, S) blocks back to (N,) by row.  Every row lies in
    one lane, so the way back is a gather too and nothing is added up."""
    flat = jnp.concatenate([x.reshape(-1) for x in per_bucket])
    return flat.at[layout.lane_of_row].get(mode="promise_in_bounds")


class _RankingObjective(Objective):
    """The query layout the ranking objectives share (reference:
    RankingObjective in rank_objective.hpp, a thread a query).

    Queries are bucketed by length: a query of ``n`` rows lies in the bucket
    of width ``S``, the power of two with ``S / 2 < n <= S``, as one row of a
    dense ``(Q_b, S)`` block of row indices; lanes from ``n`` on are padding,
    so the padded lanes of all buckets are under twice the rows, where one
    block padded to the longest query is queries x longest (docs/RANKING.md).
    Built once on the host, a dozen widths and no loop over the queries.
    Every round gathers the scores into the buckets, works a bucket at a
    time and gathers the gradients back by row, in one jitted step."""

    # per-iteration host state (xendcg's RNG iteration counter) must not be
    # baked into a traced step; LambdaRank overrides this — its position
    # biases ride the fused step as an explicit carry (fused_state protocol)
    fusable = False
    # (rows, padded lanes, pair lanes) one gradient step works through:
    # models/gbdt.py adds them to the train_rank_* counters a tree
    rank_work: Optional[Tuple[int, int, int]] = None

    def set_query(self, query_boundaries: np.ndarray, labels: np.ndarray):
        qb = np.asarray(query_boundaries, np.int64)
        self.query_boundaries = qb
        lens = np.diff(qb)
        n = int(qb[-1]) if len(qb) else 0
        self.max_query = int(lens.max()) if len(lens) else 0
        width = np.ones_like(lens)
        width[lens > 1] = 1 << np.ceil(
            np.log2(lens[lens > 1])).astype(np.int64)
        width = np.where(width < lens, 2 * width, width)  # log2 rounded down
        label_pad = np.append(np.asarray(labels, np.float32).ravel()[:n],
                              np.float32(0.0))
        lane_of_row = np.zeros(n, np.int64)
        buckets, self._bucket_queries, lanes = [], [], 0
        for w in np.unique(width):  # a width a turn: a dozen, not a query each
            queries = np.flatnonzero(width == w)
            lane = np.arange(w)
            valid = lane[None, :] < lens[queries, None]
            rows = np.where(valid, qb[queries, None] + lane[None, :], n)
            lane_of_row[rows[valid]] = lanes + np.flatnonzero(valid.ravel())
            buckets.append(_QueryBucket(
                rows=jnp.asarray(rows, jnp.int32),
                lens=jnp.asarray(lens[queries], jnp.int32),
                label=jnp.asarray(label_pad[rows])))
            self._bucket_queries.append(queries)
            lanes += rows.size
        if max(lanes, n) >= 2 ** 31:
            raise ValueError("the query layout indexes its lanes in int32: "
                             f"{lanes} lanes are too many")
        self._layout = _QueryLayout(tuple(buckets),
                                    jnp.asarray(lane_of_row, jnp.int32))
        self.rank_work = (n, lanes, self._pair_lanes())
        _obs.gauge("rank_buckets").set(len(buckets))
        _obs.gauge("rank_longest_query").set(self.max_query)

    def _pair_lanes(self) -> int:
        """Pair terms a gradient step forms over all buckets."""
        return 0


class RankXENDCG(_RankingObjective):
    """reference: RankXENDCGObjective in rank_xendcg_objective.hpp — the
    listwise cross-entropy NDCG surrogate (Bruch 2020, "An Alternative Cross
    Entropy Loss for Learning-to-Rank").

    Per query: rho = softmax(scores); phi_i = 2^label_i − u_i with u_i ~
    Uniform(0,1) resampled each iteration (objective_seed); then the
    three-term gradient
        l1_i = rho_i − phi_i / Σphi
        l2_i = l1_i − rho_i · Σl1
        λ_i  = l2_i − rho_i · Σl2,   h_i = rho_i (1 − rho_i).
    """

    name = "rank_xendcg"

    def __init__(self, cfg: Config):
        super().__init__(cfg)
        self._iter = 0
        self._seed = int(getattr(cfg, "objective_seed", 5))

    def get_gradients(self, score, label, weight):
        """``label`` is the one ``set_query`` was given: the layout holds it
        by lane already.  The uniforms are drawn a row, so the layout does
        not decide which row gets which."""
        key = jax.random.PRNGKey(self._seed + self._iter)
        self._iter += 1
        return _xendcg_step(score, self._layout, key)


@jax.jit
def _xendcg_step(score, layout, key):
    """XE-NDCG gradients of every row: one gather in, a bucket at a time,
    one gather out."""
    u_row = jax.random.uniform(key, score.shape, dtype=jnp.float32)
    grads, hesss = [], []
    for bucket in layout.buckets:
        with phase_scope("rank.gather"):
            s = _rows_to_lanes(score, bucket)
            u = _rows_to_lanes(u_row, bucket)
        with phase_scope("rank.pairs"):
            g, h = _xendcg_query(s, bucket.label, _valid_lanes(bucket), u)
        grads.append(g)
        hesss.append(h)
    if not grads:
        return jnp.zeros_like(score), jnp.zeros_like(score)
    with phase_scope("rank.scatter"):
        return (_lanes_to_rows(layout, grads), _lanes_to_rows(layout, hesss))


def _xendcg_query(scores, labels, mask, u):
    """XE-NDCG gradients of the queries of one (Q, S) block, a query a row,
    lanes outside ``mask`` padding: (Q, S) in and out."""
    neg_inf = jnp.float32(-1e30)
    masked = jnp.where(mask, scores, neg_inf)
    rho = jax.nn.softmax(masked, axis=1)
    rho = jnp.where(mask, rho, 0.0)
    phi = jnp.where(mask, jnp.exp2(labels.astype(jnp.float32)) - u, 0.0)
    denom = jnp.maximum(jnp.sum(phi, axis=1, keepdims=True), 1e-20)
    l1 = rho - phi / denom
    l2 = l1 - rho * jnp.sum(l1, axis=1, keepdims=True)
    lam = l2 - rho * jnp.sum(l2, axis=1, keepdims=True)
    hess = rho * (1.0 - rho)
    return jnp.where(mask, lam, 0.0), jnp.where(mask, hess, 0.0)


class CrossEntropyLambda(Objective):
    """reference: CrossEntropyLambda in xentropy_objective.hpp ("xentlambda"):
    alternative parameterization of cross entropy where the (optional) weight
    scales the Poisson-style intensity lambda = w * log1p(e^f); the label is
    a probability in [0, 1].  Gradients/hessians are derived by elementwise
    jax autodiff of the stable loss expression (the reference hand-derives
    the same closed forms)."""

    name = "cross_entropy_lambda"

    @staticmethod
    def _loss(f, t, w):
        lam = w * jnp.log1p(jnp.exp(f))
        # -log(1 - e^-lam) stably
        log1m = jnp.log(-jnp.expm1(-jnp.maximum(lam, 1e-30)))
        return (1.0 - t) * lam - t * log1m

    def get_gradients(self, score, label, weight):
        w = jnp.ones_like(score) if weight is None else weight
        g = jax.vmap(jax.grad(self._loss))(score, label, w)
        h = jax.vmap(jax.grad(jax.grad(self._loss)))(score, label, w)
        return g, jnp.maximum(h, 1e-8)

    def convert_output(self, score):
        # yhat = 1 - exp(-log1p(e^f)) = sigmoid(f) at unit weight
        return jax.nn.sigmoid(score)

    def boost_from_score(self, label, weight):
        p = float(jnp.clip(jnp.mean(label), 1e-6, 1 - 1e-6))
        return float(np.log(p / (1 - p)))


class LambdarankNDCG(_RankingObjective):
    """reference: LambdarankNDCG in rank_objective.hpp.

    Pairwise NDCG-weighted lambdas inside each query, over the query
    buckets of :class:`_RankingObjective`.  In a query of ``n`` rows the
    rows are ranked by score, descending, **stable: ties in row order** (at
    the first round every score is 0 and from the second every row of a leaf
    ties, so the tie rule decides every discount).  With ``r`` the rank,
    ``T`` the truncation level, ``D(r) = 1 / log2(r + 2)`` for ``r < T`` and
    0 beyond, ``G`` the label gain and ``M`` the query's inverse maximum DCG
    at ``min(n, T)`` (0 where that DCG is 0): every pair of rows with
    different labels of which at least one ranks inside ``T`` has, with
    ``hi`` the row of the larger label,

        delta  = |G(l_hi) - G(l_lo)| * |D(r_hi) - D(r_lo)| * M
        rho    = 1 / (1 + exp(sigmoid * (s_hi - s_lo)))
        lambda = sigmoid * rho * delta
        h      = sigmoid^2 * rho * (1 - rho) * delta

    ``g_hi -= lambda``, ``g_lo += lambda``, both hessians ``+= h``; under
    ``lambdarank_norm``, with ``L`` the sum of ``lambda`` over the query's
    pairs, each once, all of a query's gradients and hessians are scaled by
    ``log2(1 + L) / L``.  The better-ranked row of such a pair is one of the
    ``T`` best, so a bucket forms ``(Q_b, min(T, S), S)`` pair terms, a piece
    of its queries at a time, and never ``(Q_b, S, S)``.

    Unlike upstream (4.x, from memory; no copy is on this machine), which
    keeps the discount of a row ranked beyond ``T``, divides ``delta`` by
    ``0.01 + |s_hi - s_lo|`` under ``lambdarank_norm`` and counts each pair
    twice in ``L``.
    """

    name = "lambdarank"
    # always fusable: plain lambdas are pure, and position-bias state rides
    # the fused step as a carry (fused_state protocol below)
    fusable = True

    def __init__(self, cfg: Config):
        super().__init__(cfg)
        self.truncation = cfg.lambdarank_truncation_level
        self.norm = cfg.lambdarank_norm
        self.sigmoid = cfg.sigmoid if cfg.sigmoid > 0 else 1.0
        gains = cfg.label_gain
        if not gains:
            gains = [float(2**i - 1) for i in range(31)]
        self.label_gain = np.asarray(gains, dtype=np.float64)

    def set_query(self, query_boundaries: np.ndarray, labels: np.ndarray):
        """The layout, and each query's inverse maximum DCG at
        ``min(n, T)`` (reference: inverse_max_dcgs_ in
        LambdarankNDCG::Init): the labels sorted within their queries, best
        first, the gains of the ``T`` first times their discounts summed by
        query."""
        super().set_query(query_boundaries, labels)
        qb = self.query_boundaries
        lens = np.diff(qb)
        lab = np.clip(np.asarray(labels).ravel()[:int(qb[-1])].astype(
            np.int64), 0, len(self.label_gain) - 1)
        query = np.repeat(np.arange(len(lens)), lens)
        best_first = lab[np.lexsort((-lab, query))]
        rank = np.arange(len(lab)) - np.repeat(qb[:-1], lens)
        top = rank < self.truncation
        max_dcg = np.bincount(
            query[top], self.label_gain[best_first[top]]
            / np.log2(rank[top] + 2.0), minlength=len(lens))
        self.inverse_max_dcg = np.where(
            max_dcg > 0, 1.0 / np.where(max_dcg > 0, max_dcg, 1.0), 0.0)
        self._inv_mdcg = tuple(
            jnp.asarray(self.inverse_max_dcg[q], jnp.float32)
            for q in self._bucket_queries)
        # the gains of the labels that occur: the step looks a gain up by a
        # chain of selects, a link a table entry
        self._gain = jnp.asarray(self.label_gain[:int(lab.max()) + 1]
                                 if len(lab) else self.label_gain[:1],
                                 jnp.float32)

    def _pair_lanes(self) -> int:
        return sum(int(b.rows.shape[0]) * min(self.truncation, b.rows.shape[1])
                   * int(b.rows.shape[1]) for b in self._layout.buckets)

    def set_positions(self, positions: np.ndarray):
        """Enable position-bias correction (reference: rank_objective.hpp —
        positions_/pos_biases_ and UpdatePositionBiasFactors).  The model
        score is augmented with a learned additive per-position bias during
        lambda computation; the biases themselves are refit each iteration
        with a Newton step regularized by
        lambdarank_position_bias_regularization, so the TREES learn the
        position-debiased ranking while the biases absorb presentation
        effects (unbiased LambdaRank)."""
        positions = np.append(np.asarray(positions, np.int64).ravel(), 0)
        # by lane, as the labels lie; a padded lane reads position 0, masked
        self._pos = tuple(jnp.asarray(positions[np.asarray(b.rows)], jnp.int32)
                          for b in self._layout.buckets)
        self.num_positions = int(positions.max()) + 1
        self.pos_bias = jnp.zeros((self.num_positions,), jnp.float32)
        self.pos_reg = float(getattr(self.cfg, "lambdarank_position_bias_regularization", 0.0))

    _pos = None

    def _gradients_core(self, score, pos_bias):
        """PURE lambda computation: position bias enters as an argument and
        the refit bias is returned, so this body can trace inside the fused
        step with the bias as a carry."""
        return _lambdarank_step(
            score, self._layout, self._inv_mdcg, self._gain,
            None if pos_bias is None else self._pos, pos_bias,
            sigmoid=float(self.sigmoid), truncation=int(self.truncation),
            norm=bool(self.norm),
            pos_reg=0.0 if pos_bias is None else self.pos_reg)

    def get_gradients(self, score, label, weight):
        """``label`` is the one ``set_query`` was given: the layout holds it
        by lane already."""
        bias = self.pos_bias if self._pos is not None else None
        grad, hess, new_bias = self._gradients_core(score, bias)
        if self._pos is not None:
            self.pos_bias = new_bias
        return grad, hess

    # fused-state protocol: the position biases ride the fused step as a
    # carry (reference: UpdatePositionBiasFactors runs once per iteration —
    # here that Newton refit happens in-trace and the carry is written back
    # when the step retires)
    def fused_state(self):
        return self.pos_bias if self._pos is not None else None

    def fused_gradients(self, score, label, weight, state):
        return self._gradients_core(score, state)

    def set_fused_state(self, state) -> None:
        if state is not None:
            self.pos_bias = state


@functools.partial(jax.jit, static_argnames=("sigmoid", "truncation", "norm",
                                             "pos_reg"))
def _lambdarank_step(score, layout, inv_mdcg, label_gain, pos, pos_bias, *,
                     sigmoid, truncation, norm, pos_reg):
    """The lambdas of every row and, with position biases, their Newton
    refit: scores gathered into the buckets, the ``truncation`` best-ranked
    rows of every query picked out in rank order, their pair terms against
    the rows ranked after them, gathered out by row.  ``inv_mdcg`` and
    ``pos`` are a bucket each."""
    grads, hesss = [], []
    bias_g = bias_h = None
    if pos_bias is not None:
        bias_g = bias_h = jnp.zeros_like(pos_bias)
    for i, bucket in enumerate(layout.buckets):
        with phase_scope("rank.gather"):
            s = _rows_to_lanes(score, bucket)
            if pos_bias is not None:
                # the lambdas see the score with its row's position bias
                s = s + jnp.where(_valid_lanes(bucket), pos_bias[pos[i]], 0.0)
        with phase_scope("rank.sort"):
            window = _rank_window(s, bucket, min(truncation, s.shape[1]))
        with phase_scope("rank.pairs"):
            g, h = _pair_lambdas(
                (s, bucket.label, bucket.lens, inv_mdcg[i]) + window,
                label_gain, sigmoid=sigmoid, norm=norm)
        if pos_bias is not None:
            flat = pos[i].reshape(-1)
            bias_g = bias_g.at[flat].add(g.reshape(-1))
            bias_h = bias_h.at[flat].add(h.reshape(-1))
        grads.append(g)
        hesss.append(h)
    if pos_bias is not None:
        # Newton refit of the biases from this iteration's lambdas
        # (reference: UpdatePositionBiasFactors once per iteration)
        pos_bias = pos_bias - (bias_g + pos_reg * pos_bias) / (
            bias_h + pos_reg + 1e-9)
    if not grads:
        return jnp.zeros_like(score), jnp.zeros_like(score), pos_bias
    with phase_scope("rank.scatter"):
        return (_lanes_to_rows(layout, grads), _lanes_to_rows(layout, hesss),
                pos_bias)


def _rank_window(s, bucket, tw):
    """The ``tw`` best-ranked rows of every query of the bucket, by score,
    descending, **ties in row order**: ``tw`` turns, each taking the first
    lane that holds the largest score left.  No sort: a discount is 0 beyond
    the truncation level, so a rank past the window is never asked for.
    Returns ``rank`` (Q, S) int32, a lane's rank where it is under ``tw``
    and ``tw`` elsewhere, and the window's scores and labels (Q, tw) in rank
    order; a window slot from a query's length on holds nothing and is
    masked by whoever reads it."""
    lane = _lane_ids(bucket)
    slot = jax.lax.broadcasted_iota(jnp.int32, (s.shape[0], tw), 1)

    def turn(a, carry):
        left, rank, win_s, win_label = carry
        best = jnp.max(left, axis=1, keepdims=True)
        first = jnp.min(jnp.where(left == best, lane, s.shape[1]), axis=1,
                        keepdims=True)
        taken = (lane == first) & (a < bucket.lens[:, None])
        label = jnp.sum(jnp.where(taken, bucket.label, 0.0), axis=1,
                        keepdims=True)
        return (jnp.where(taken, -jnp.inf, left), jnp.where(taken, a, rank),
                jnp.where(slot == a, best, win_s),
                jnp.where(slot == a, label, win_label))

    zeros = jnp.zeros(slot.shape, jnp.float32)
    _, rank, win_s, win_label = jax.lax.fori_loop(
        0, tw, turn, (jnp.where(_valid_lanes(bucket), s, -jnp.inf),
                      jnp.full(s.shape, tw, jnp.int32), zeros, zeros))
    return rank, win_s, win_label


def _pair_lambdas(arrays, label_gain, **kw):
    """Lambdas and hessians of one bucket: the whole bucket where its pair
    terms fit ``_PAIR_PIECE_LANES``, else its queries in equal pieces, one
    after the other.  ``arrays`` are ``_pair_lambdas_piece``'s, a query a
    row each."""
    q, w = arrays[0].shape
    per_piece = max(1, _PAIR_PIECE_LANES // (arrays[-1].shape[1] * w))
    if q <= per_piece:
        return _pair_lambdas_piece(*arrays, label_gain, **kw)
    pieces = -(-q // per_piece)
    per_piece = -(-q // pieces)

    def in_pieces(x):
        # the queries padded on are empty: no lane of theirs is valid
        pad = [(0, pieces * per_piece - q)] + [(0, 0)] * (x.ndim - 1)
        return jnp.pad(x, pad).reshape((pieces, per_piece) + x.shape[1:])

    g, h = jax.lax.map(
        lambda a: _pair_lambdas_piece(*a, label_gain, **kw),
        tuple(in_pieces(x) for x in arrays))
    return g.reshape(-1, w)[:q], h.reshape(-1, w)[:q]


def _pair_lambdas_piece(s, label, lens, inv_mdcg, rank, win_s, win_label,
                        label_gain, *, sigmoid, norm):
    """``s``, ``label``, ``rank``: (Q, S), a query a row, lanes from ``lens``
    on padding; ``win_s``, ``win_label``: (Q, window), the best-ranked rows
    in rank order (``_rank_window``).  Pairs the window row of rank ``a``
    with every lane ranked after it: each pair with a member inside the
    truncation level, once, as (Q, window, S) terms.  Returns (grad, hess),
    (Q, S) by lane."""
    w, tw = s.shape[1], win_s.shape[1]
    slot = jnp.arange(tw, dtype=jnp.int32)

    def gain_of(x):  # a short table: selects, no gather
        level = jnp.clip(x.astype(jnp.int32), 0, label_gain.shape[0] - 1)
        gain = jnp.zeros_like(x)
        for k in range(label_gain.shape[0]):
            gain = jnp.where(level == k, label_gain[k], gain)
        return gain

    def discount(r):  # 0 from the truncation level on, and past the window
        return jnp.where(r < tw, 1.0 / jnp.log2(r.astype(jnp.float32) + 2.0),
                         0.0)

    def a(x):  # the window's rows against
        return x[:, :, None]

    def b(x):  # every lane
        return x[:, None, :]

    lane = jnp.arange(w, dtype=jnp.int32)
    pair = ((b(rank) > slot[None, :, None])
            & (slot[None, :, None] < lens[:, None, None])
            & (lane[None, None, :] < lens[:, None, None])
            & (a(win_label) != b(label)))
    a_is_hi = a(win_label) > b(label)
    d_s = a(win_s) - b(s)
    d_s = jnp.where(a_is_hi, d_s, -d_s)  # s_hi - s_lo
    delta = (jnp.abs(a(gain_of(win_label)) - b(gain_of(label)))
             * jnp.abs(discount(slot)[None, :, None] - b(discount(rank)))
             * inv_mdcg[:, None, None])
    rho = 1.0 / (1.0 + jnp.exp(sigmoid * d_s))
    lam = jnp.where(pair, sigmoid * rho * delta, 0.0)
    hes = jnp.where(pair, sigmoid * sigmoid * rho * (1.0 - rho) * delta, 0.0)
    to_b = jnp.where(a_is_hi, lam, -lam)  # the row of the larger label loses
    # a window row's own sums go to the lane that holds its rank
    is_a = b(rank) == slot[None, :, None]

    def to_lanes(x):
        # the barrier keeps XLA from rewriting the sum over the lanes and
        # its way back as a window reduction over a stored (Q, window, S)
        x = jax.lax.optimization_barrier(x)
        return jnp.sum(jnp.where(is_a, a(x), 0.0), axis=1)

    grad = jnp.sum(to_b, axis=1) - to_lanes(jnp.sum(to_b, axis=2))
    hess = jnp.sum(hes, axis=1) + to_lanes(jnp.sum(hes, axis=2))
    if norm:
        total = jnp.sum(lam, axis=(1, 2))[:, None]
        scale = jnp.where(total > 0, jnp.log2(1.0 + total)
                          / jnp.maximum(total, 1e-20), 1.0)
        grad = grad * scale
        hess = hess * scale
    return grad, hess


# ---------------------------------------------------------------------------
# per-leaf weighted quantile (for RenewTreeOutput objectives)
# ---------------------------------------------------------------------------
def _per_leaf_weighted_quantile(values, weights, leaf_id, num_leaves, q):
    """Weighted q-quantile of `values` within each leaf (masked, O(L * N log N)
    via one shared sort — reference: PercentileFun/WeightedPercentileFun in
    regression_objective.hpp)."""
    order = jnp.argsort(values)
    v = values[order]
    w = weights[order]
    lid = leaf_id[order]

    def one_leaf(leaf):
        m = (lid == leaf).astype(v.dtype) * w
        cum = jnp.cumsum(m)
        total = cum[-1]
        target = q * total
        # first index where cumulative weight >= target
        idx = jnp.searchsorted(cum, target, side="left")
        idx = jnp.clip(idx, 0, v.shape[0] - 1)
        return v[idx]

    return jax.vmap(one_leaf)(jnp.arange(num_leaves))


def _weighted_quantile_np(values, weights, q):
    order = np.argsort(values)
    v = values[order]
    if weights is None:
        # reference PercentileFun: midpoint convention for even counts at q=0.5
        n = len(v)
        if n == 0:
            return 0.0
        pos = q * (n - 1)
        lo = int(np.floor(pos))
        hi = int(np.ceil(pos))
        return 0.5 * (v[lo] + v[hi]) if hi != lo else float(v[lo])
    w = np.asarray(weights)[order]
    cum = np.cumsum(w)
    target = q * cum[-1]
    idx = int(np.searchsorted(cum, target, side="left"))
    return float(v[min(idx, len(v) - 1)])


# ---------------------------------------------------------------------------
_REGISTRY: Dict[str, Callable[[Config], Objective]] = {
    "regression": RegressionL2,
    "regression_l1": RegressionL1,
    "huber": RegressionHuber,
    "fair": RegressionFair,
    "poisson": RegressionPoisson,
    "gamma": RegressionGamma,
    "tweedie": RegressionTweedie,
    "quantile": RegressionQuantile,
    "mape": RegressionMAPE,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "cross_entropy": CrossEntropy,
    "cross_entropy_lambda": CrossEntropyLambda,
        "lambdarank": LambdarankNDCG,
    "rank_xendcg": RankXENDCG,
}


def create_objective(cfg: Config) -> Optional[Objective]:
    """reference: ObjectiveFunction::CreateObjectiveFunction."""
    name = cfg.objective
    if name in ("none", "null", "custom", "na", ""):
        return None
    if name not in _REGISTRY:
        raise ValueError(f"Unknown objective: {name}")
    return _REGISTRY[name](cfg)
