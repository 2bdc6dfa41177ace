"""Evaluation metrics.

Reference: src/metric/ (binary_metric.hpp, regression_metric.hpp,
multiclass_metric.hpp, rank_metric.hpp, map_metric.hpp, dcg_calculator.cpp,
xentropy_metric.hpp) and Metric::CreateMetric in src/metric/metric.cpp.

Each metric returns (name, value, is_higher_better) — matching the tuple the
reference's eval framework hands to callbacks.  Computation is numpy/JAX on
the converted scores; distributed evaluation sums (loss, weight) pairs with a
psum in the mesh path (reference: Network::GlobalSyncUpBySum).
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .config import Config

EPS = 1e-15


def dcg_at_k(labels_sorted_desc: np.ndarray, k: int, label_gain: np.ndarray) -> float:
    """DCG of the given label order truncated at k (reference:
    DCGCalculator::CalDCGAtK in src/metric/dcg_calculator.cpp)."""
    k = min(k, len(labels_sorted_desc))
    if k <= 0:
        return 0.0
    lab = np.clip(labels_sorted_desc[:k].astype(np.int64), 0, len(label_gain) - 1)
    gains = label_gain[lab]
    discounts = 1.0 / np.log2(np.arange(k, dtype=np.float64) + 2.0)
    return float(np.sum(gains * discounts))


def ndcg_at_k(scores, labels, query_boundaries, k, label_gain) -> float:
    """Mean per-query NDCG@k (reference: NDCGMetric::Eval)."""
    nq = len(query_boundaries) - 1
    total, cnt = 0.0, 0
    for q in range(nq):
        lo, hi = query_boundaries[q], query_boundaries[q + 1]
        ql, qs = labels[lo:hi], scores[lo:hi]
        if np.all(ql == ql[0]):
            total += 1.0  # reference: queries w/o label variation count as 1
            cnt += 1
            continue
        order = np.argsort(-qs, kind="stable")
        d = dcg_at_k(ql[order], k, label_gain)
        ideal = dcg_at_k(np.sort(ql)[::-1], k, label_gain)
        total += d / ideal if ideal > 0 else 1.0
        cnt += 1
    return total / max(cnt, 1)


def _auc(scores: np.ndarray, labels: np.ndarray, weights: Optional[np.ndarray]) -> float:
    """Weighted AUC via rank statistic (reference: AUCMetric in
    binary_metric.hpp — trapezoid over the weighted ROC)."""
    if weights is None:
        weights = np.ones_like(scores, dtype=np.float64)
    order = np.argsort(scores, kind="mergesort")
    s, y, w = scores[order], labels[order], weights[order]
    pos_w = np.where(y > 0, w, 0.0)
    neg_w = np.where(y > 0, 0.0, w)
    # handle ties: group equal scores
    cum_neg = np.cumsum(neg_w)
    total_pos, total_neg = pos_w.sum(), neg_w.sum()
    if total_pos == 0 or total_neg == 0:
        return 1.0
    # For each positive, count negatives with lower score (+ half ties)
    _, inv, counts = np.unique(s, return_inverse=True, return_counts=True)
    grp_neg = np.bincount(inv, weights=neg_w)
    grp_pos = np.bincount(inv, weights=pos_w)
    cum_neg_before = np.concatenate([[0.0], np.cumsum(grp_neg)[:-1]])
    auc = np.sum(grp_pos * (cum_neg_before + 0.5 * grp_neg))
    return float(auc / (total_pos * total_neg))


class Metric:
    name: str = ""
    is_higher_better: bool = False

    def __init__(self, cfg: Config):
        self.cfg = cfg

    def eval(self, pred, label, weight, query_boundaries=None) -> List[Tuple[str, float, bool]]:
        raise NotImplementedError

    # device evaluation protocol (reference: src/metric/cuda/*): metrics
    # returning True from supports_device are evaluated INSIDE one jit per
    # eval set (gbdt.eval_at) — only a scalar crosses to the host, never the
    # (N,) score vector.  `device_eval` returns the pre-`transform` value.
    def supports_device(self, num_class: int) -> bool:
        return False

    def device_eval(self, pred, label, weight):
        raise NotImplementedError

    # rank metrics set True: they are evaluated via device_eval_queries with
    # per-dataset padded-query constants instead of device_eval
    needs_queries = False

    def transform(self, v: float) -> float:
        return v

    # distributed-eval protocol (reference: metrics call
    # Network::GlobalSyncUpBySum on their local sums): decomposable metrics
    # return [(name, local_numerator, local_denominator, higher_better)];
    # global value = transform(sum(num)/sum(den)).  None = not
    # sum-decomposable (the AUC family) — the caller gathers shard
    # predictions instead.
    def eval_sums(self, pred, label, weight, query_boundaries=None):
        return None


def _wmean(vals, weight):
    if weight is None:
        return float(np.mean(vals))
    return float(np.sum(vals * weight) / np.sum(weight))


class _Pointwise(Metric):
    """Pointwise metrics share one elementwise `point` function written
    against an array namespace (numpy on host, jax.numpy on device) so the
    device evaluator (reference: src/metric/cuda/cuda_pointwise_metric.cu)
    and the host path cannot diverge."""

    def point(self, pred, label, xp=np):
        raise NotImplementedError

    def transform(self, v: float) -> float:
        return v

    def eval(self, pred, label, weight, query_boundaries=None):
        v = self.transform(_wmean(self.point(np.asarray(pred), np.asarray(label)), weight))
        return [(self.name, v, self.is_higher_better)]

    def eval_sums(self, pred, label, weight, query_boundaries=None):
        v = self.point(np.asarray(pred), np.asarray(label))
        if weight is None:
            return [(self.name, float(np.sum(v)), float(v.size),
                     self.is_higher_better)]
        return [(self.name, float(np.sum(v * weight)),
                 float(np.sum(weight)), self.is_higher_better)]

    def supports_device(self, num_class: int) -> bool:
        return num_class == 1

    def device_eval(self, pred, label, weight):
        """Weighted mean of `point` as a traced scalar; `transform` is
        applied host-side to the fetched value."""
        import jax.numpy as jnp

        v = self.point(pred, label, xp=jnp)
        if weight is None:
            return jnp.sum(v) / v.shape[0]
        return jnp.sum(v * weight) / jnp.sum(weight)


class L2Metric(_Pointwise):
    name = "l2"

    def point(self, p, y, xp=np):
        return (p - y) ** 2


class RMSEMetric(L2Metric):
    name = "rmse"

    def transform(self, v):
        return float(np.sqrt(v))


class L1Metric(_Pointwise):
    name = "l1"

    def point(self, p, y, xp=np):
        return xp.abs(p - y)


class QuantileMetric(_Pointwise):
    name = "quantile"

    def point(self, p, y, xp=np):
        a = self.cfg.alpha
        d = y - p
        return xp.where(d >= 0, a * d, (a - 1.0) * d)


class HuberMetric(_Pointwise):
    name = "huber"

    def point(self, p, y, xp=np):
        a = self.cfg.alpha
        d = xp.abs(p - y)
        return xp.where(d <= a, 0.5 * d * d, a * (d - 0.5 * a))


class FairMetric(_Pointwise):
    name = "fair"

    def point(self, p, y, xp=np):
        c = self.cfg.fair_c
        x = xp.abs(p - y)
        return c * x - c * c * xp.log1p(x / c)


class PoissonMetric(_Pointwise):
    name = "poisson"

    def point(self, p, y, xp=np):
        eps = 1e-10
        lp = xp.log(xp.maximum(p, eps))
        return p - y * lp


class GammaMetric(_Pointwise):
    name = "gamma"

    def point(self, p, y, xp=np):
        eps = 1e-10
        x = xp.maximum(p, eps)
        return y / x + xp.log(x)


class GammaDevianceMetric(_Pointwise):
    name = "gamma_deviance"

    def point(self, p, y, xp=np):
        eps = 1e-10
        r = y / xp.maximum(p, eps)
        return 2.0 * (xp.log(xp.maximum(1.0 / xp.maximum(r, eps), eps)) + r - 1.0)


class TweedieMetric(_Pointwise):
    name = "tweedie"

    def point(self, p, y, xp=np):
        rho = self.cfg.tweedie_variance_power
        eps = 1e-10
        x = xp.maximum(p, eps)
        return -y * xp.power(x, 1 - rho) / (1 - rho) + xp.power(x, 2 - rho) / (2 - rho)


class MAPEMetric(_Pointwise):
    name = "mape"

    def point(self, p, y, xp=np):
        return xp.abs(p - y) / xp.maximum(1.0, xp.abs(y))


class BinaryLoglossMetric(_Pointwise):
    name = "binary_logloss"

    def point(self, p, y, xp=np):
        p = xp.clip(p, EPS, 1 - EPS)
        yy = (y > 0).astype(p.dtype)
        return -(yy * xp.log(p) + (1 - yy) * xp.log(1 - p))


class BinaryErrorMetric(_Pointwise):
    name = "binary_error"

    def point(self, p, y, xp=np):
        return ((p > 0.5) != (y > 0)).astype(p.dtype)


def _auc_device(scores, labels, weights):
    """jnp mirror of _auc: tie-grouped weighted rank statistic using
    fixed-shape segment sums (group count bounded by N)."""
    import jax.numpy as jnp

    n = scores.shape[0]
    order = jnp.argsort(scores, stable=True)
    s = scores[order]
    y = labels[order]
    w = jnp.ones_like(s) if weights is None else weights[order].astype(s.dtype)
    pos_w = jnp.where(y > 0, w, 0.0)
    neg_w = w - pos_w
    new_grp = jnp.concatenate([jnp.ones((1,), bool), s[1:] != s[:-1]])
    gid = jnp.cumsum(new_grp.astype(jnp.int32)) - 1  # (N,) 0-based group ids
    grp_neg = jnp.zeros((n,), s.dtype).at[gid].add(neg_w)
    grp_pos = jnp.zeros((n,), s.dtype).at[gid].add(pos_w)
    cum_neg_before = jnp.concatenate(
        [jnp.zeros((1,), s.dtype), jnp.cumsum(grp_neg)[:-1]]
    )
    tot_pos, tot_neg = jnp.sum(pos_w), jnp.sum(neg_w)
    auc = jnp.sum(grp_pos * (cum_neg_before + 0.5 * grp_neg))
    return jnp.where(
        (tot_pos == 0) | (tot_neg == 0), 1.0,
        auc / jnp.maximum(tot_pos * tot_neg, 1e-30),
    )


class AUCMetric(Metric):
    name = "auc"
    is_higher_better = True

    def eval(self, pred, label, weight, query_boundaries=None):
        return [(self.name, _auc(np.asarray(pred), np.asarray(label), weight), True)]

    def supports_device(self, num_class: int) -> bool:
        return num_class == 1

    def device_eval(self, pred, label, weight):
        return _auc_device(pred, label, weight)


class CrossEntropyMetric(_Pointwise):
    name = "cross_entropy"

    def point(self, p, y, xp=np):
        p = xp.clip(p, EPS, 1 - EPS)
        return -(y * xp.log(p) + (1 - y) * xp.log(1 - p))


class XentLambdaMetric(Metric):
    """reference: CrossEntropyLambdaMetric in xentropy_metric.hpp: the
    lambda-parameterized cross entropy, where a weight scales the intensity
    lambda = w * log1p(e^f) rather than the loss (differs from plain
    xentropy only when weights are present)."""

    name = "xentropy_lambda"

    def eval(self, pred, label, weight, query_boundaries=None):
        p = np.clip(np.asarray(pred, np.float64), EPS, 1 - EPS)
        t = np.asarray(label, np.float64)
        f = np.log(p / (1 - p))
        w = np.ones_like(p) if weight is None else np.asarray(weight, np.float64)
        lam = w * np.log1p(np.exp(f))
        loss = (1 - t) * lam - t * np.log(-np.expm1(-np.maximum(lam, 1e-300)))
        return [(self.name, float(np.mean(loss)), False)]

    def eval_sums(self, pred, label, weight, query_boundaries=None):
        v = self.eval(pred, label, weight)[0][1]
        n = np.shape(label)[0]  # metadata only — no conversion (jaxlint R14)
        return [(self.name, v * n, float(n), False)]


class AucMuMetric(Metric):
    """Multiclass AUC-mu (reference: auc_mu in src/metric/multiclass_metric.hpp,
    Kleiman & Page 2019): average over ordered class pairs (i, j) of the AUC
    separating class i from class j by the decision margin
    pred[:, i] - pred[:, j], optionally weighted by the auc_mu_weights
    misclassification-cost matrix."""

    name = "auc_mu"
    is_higher_better = True

    def __init__(self, cfg=None):
        self.weights = None
        w = list(getattr(cfg, "auc_mu_weights", []) or []) if cfg is not None else []
        if w:
            k = int(round(len(w) ** 0.5))
            if k * k == len(w):
                self.weights = np.asarray(w, np.float64).reshape(k, k)

    def eval(self, pred, label, weight, query_boundaries=None):
        p = np.asarray(pred)
        y = np.asarray(label).astype(np.int64)
        k = p.shape[1]
        total, wsum = 0.0, 0.0
        for i in range(k):
            for j in range(i + 1, k):
                # AUC(i vs j by margin) == AUC(j vs i by -margin): one sort
                # per unordered pair (reference iterates i < j too)
                rows = (y == i) | (y == j)
                if not rows.any() or (y[rows] == i).all() or (y[rows] == j).all():
                    continue
                margin = p[rows, i] - p[rows, j]
                lab = (y[rows] == i).astype(np.float64)
                wrow = None if weight is None else np.asarray(weight)[rows]
                a = _auc(margin, lab, wrow)
                pw = (
                    2.0 if self.weights is None
                    else float(self.weights[i, j] + self.weights[j, i])
                )
                total += pw * a
                wsum += pw
        return [(self.name, total / max(wsum, 1e-30), True)]

    def supports_device(self, num_class: int) -> bool:
        # class pairs unroll in-trace: k*(k-1)/2 masked device AUCs
        return 1 < num_class <= 12

    def device_eval(self, pred, label, weight):
        import jax.numpy as jnp

        k = pred.shape[1]
        y = label.astype(jnp.int32)
        w = (jnp.ones(pred.shape[0], jnp.float32) if weight is None
             else weight.astype(jnp.float32))
        # host parity: pairs skip by LABEL presence (unweighted), computed
        # once per class — zero-weight classes still count (their AUC
        # degenerates to 1.0 in _auc_device exactly like the host's _auc)
        class_present = [jnp.any(y == i) for i in range(k)]
        total = jnp.float32(0.0)
        wsum = jnp.float32(0.0)
        for i in range(k):
            for j in range(i + 1, k):
                # non-pair rows get weight 0 — they sort in but contribute
                # nothing, the fixed-shape analogue of the host's row subset
                pm = ((y == i) | (y == j)).astype(jnp.float32) * w
                lab = (y == i).astype(jnp.float32)
                a = _auc_device(pred[:, i] - pred[:, j], lab, pm)
                pw = (2.0 if self.weights is None
                      else float(self.weights[i, j] + self.weights[j, i]))
                valid = class_present[i] & class_present[j]
                total = total + jnp.where(valid, pw * a, 0.0)
                wsum = wsum + jnp.where(valid, pw, 0.0)
        return total / jnp.maximum(wsum, 1e-30)


class MultiLoglossMetric(Metric):
    name = "multi_logloss"

    def eval(self, pred, label, weight, query_boundaries=None):
        p = np.asarray(pred)  # (N, K)
        y = np.asarray(label).astype(np.int64)
        probs = np.clip(p[np.arange(len(y)), y], EPS, None)
        return [(self.name, _wmean(-np.log(probs), weight), False)]

    def eval_sums(self, pred, label, weight, query_boundaries=None):
        p = np.asarray(pred)
        y = np.asarray(label).astype(np.int64)
        v = -np.log(np.clip(p[np.arange(len(y)), y], EPS, None))
        if weight is None:
            return [(self.name, float(np.sum(v)), float(v.size), False)]
        return [(self.name, float(np.sum(v * weight)),
                 float(np.sum(weight)), False)]

    def supports_device(self, num_class: int) -> bool:
        return num_class > 1

    def device_eval(self, pred, label, weight):
        import jax.numpy as jnp

        y = label.astype(jnp.int32)
        probs = jnp.take_along_axis(pred, y[:, None], axis=1)[:, 0]
        v = -jnp.log(jnp.clip(probs, EPS, None))
        if weight is None:
            return jnp.sum(v) / v.shape[0]
        return jnp.sum(v * weight) / jnp.sum(weight)


class MultiErrorMetric(Metric):
    name = "multi_error"

    def _row_errors(self, pred, label) -> np.ndarray:
        p = np.asarray(pred)
        y = np.asarray(label).astype(np.int64)
        k = self.cfg.multi_error_top_k
        if k <= 1:
            return (np.argmax(p, axis=1) != y).astype(np.float64)
        topk = np.argsort(-p, axis=1)[:, :k]
        return 1.0 - (topk == y[:, None]).any(axis=1).astype(np.float64)

    def eval(self, pred, label, weight, query_boundaries=None):
        return [(self.name, _wmean(self._row_errors(pred, label), weight),
                 False)]

    def eval_sums(self, pred, label, weight, query_boundaries=None):
        e = self._row_errors(pred, label)
        if weight is None:
            return [(self.name, float(np.sum(e)), float(e.size), False)]
        return [(self.name, float(np.sum(e * weight)),
                 float(np.sum(weight)), False)]

    def supports_device(self, num_class: int) -> bool:
        return num_class > 1

    def device_eval(self, pred, label, weight):
        import jax
        import jax.numpy as jnp

        y = label.astype(jnp.int32)
        k = self.cfg.multi_error_top_k
        if k <= 1:
            err = (jnp.argmax(pred, axis=1) != y).astype(jnp.float32)
        else:
            _, topk = jax.lax.top_k(pred, min(k, pred.shape[1]))
            err = 1.0 - jnp.any(topk == y[:, None], axis=1).astype(jnp.float32)
        if weight is None:
            return jnp.sum(err) / err.shape[0]
        return jnp.sum(err * weight) / jnp.sum(weight)


def pad_queries(query_boundaries: np.ndarray):
    """Queries as a dense (Q, S) block padded to the longest query, for the
    ranking metrics (the objectives bucket their queries by length:
    objectives._RankingObjective).  Returns (pad_idx, pad_mask)."""
    qb = np.asarray(query_boundaries)
    nq = len(qb) - 1
    lens = np.diff(qb)
    smax = int(lens.max()) if nq else 0
    pad_idx = np.zeros((nq, smax), np.int64)
    pad_mask = np.zeros((nq, smax), bool)
    for q in range(nq):
        lo, hi = qb[q], qb[q + 1]
        pad_idx[q, : hi - lo] = np.arange(lo, hi)
        pad_mask[q, : hi - lo] = True
    return pad_idx, pad_mask


class _MeanPerQuery(Metric):
    """Ranking metrics averaging a per-query statistic decompose for
    distributed eval as (sum over local queries, #local queries).

    Device protocol (reference: the CUDA build's rank metrics,
    src/metric/cuda/cuda_rank_metric.cu): `device_query_constants`
    precomputes per-dataset tensors on host (padding, ideal DCGs);
    `device_eval_queries` is a pure jnp function evaluated inside the
    per-eval-set jit, returning one value per eval_at k."""

    needs_queries = True

    def eval_sums(self, pred, label, weight, query_boundaries=None):
        nq = float(len(query_boundaries) - 1)
        return [(nm, v * nq, nq, hib)
                for nm, v, hib in self.eval(pred, label, weight,
                                            query_boundaries)]

    def supports_device(self, num_class: int) -> bool:
        return num_class == 1

    def device_out_names(self):
        return [f"{self.name}@{k}" for k in self.cfg.eval_at]

    def device_query_constants(self, label: np.ndarray,
                               query_boundaries: np.ndarray,
                               shared: dict = None) -> dict:
        """`shared` (from the evaluator) carries the padded layout computed
        once per eval set: pad_idx/pad_mask as numpy + device arrays."""
        raise NotImplementedError

    def device_eval_queries(self, pred, consts: dict):
        raise NotImplementedError


class NDCGMetric(_MeanPerQuery):
    name = "ndcg"
    is_higher_better = True

    def __init__(self, cfg: Config):
        super().__init__(cfg)
        gains = cfg.label_gain or [float(2**i - 1) for i in range(31)]
        self.label_gain = np.asarray(gains, dtype=np.float64)

    def eval(self, pred, label, weight, query_boundaries=None):
        assert query_boundaries is not None, "ndcg requires query info"
        out = []
        for k in self.cfg.eval_at:
            v = ndcg_at_k(np.asarray(pred), np.asarray(label), query_boundaries, k, self.label_gain)
            out.append((f"ndcg@{k}", v, True))
        return out

    def device_query_constants(self, label, query_boundaries, shared=None):
        import jax.numpy as jnp

        label = np.asarray(label)
        qb = np.asarray(query_boundaries)
        if shared is not None:
            pad_idx, pad_mask = shared["pad_idx_np"], shared["pad_mask_np"]
            dev_idx, dev_mask = shared["pad_idx"], shared["pad_mask"]
        else:
            pad_idx, pad_mask = pad_queries(qb)
            dev_idx, dev_mask = jnp.asarray(pad_idx), jnp.asarray(pad_mask)
        nq = len(qb) - 1
        ks = list(self.cfg.eval_at)
        inv_ideal = np.zeros((len(ks), nq), np.float64)
        all_same = np.zeros(nq, bool)
        for q in range(nq):
            ql = label[qb[q]: qb[q + 1]]
            all_same[q] = bool(np.all(ql == ql[0]))
            ideal = np.sort(ql)[::-1]
            for i, k in enumerate(ks):
                m = dcg_at_k(ideal, min(len(ql), k), self.label_gain)
                inv_ideal[i, q] = 1.0 / m if m > 0 else 0.0
        return {
            "pad_idx": dev_idx,
            "pad_mask": dev_mask,
            "inv_ideal": jnp.asarray(inv_ideal, jnp.float32),
            "all_same": jnp.asarray(all_same),
            "gain_pad": jnp.asarray(  # per-slot gains, masked
                np.where(
                    pad_mask,
                    self.label_gain[np.clip(
                        label[pad_idx].astype(np.int64), 0,
                        len(self.label_gain) - 1)],
                    0.0,
                ), jnp.float32),
            "ks": ks,
        }

    def device_eval_queries(self, pred, consts):
        import jax.numpy as jnp

        idx, msk = consts["pad_idx"], consts["pad_mask"]
        s = pred[idx.reshape(-1)].reshape(idx.shape)
        ms = jnp.where(msk, s, jnp.float32(-1e30))
        order = jnp.argsort(-ms, axis=1, stable=True)
        ranks = jnp.argsort(order, axis=1)  # rank of each original slot
        disc = jnp.where(msk, 1.0 / jnp.log2(ranks.astype(jnp.float32) + 2.0),
                         0.0)
        gains = consts["gain_pad"]
        outs = []
        for i, k in enumerate(consts["ks"]):
            dcg = jnp.sum(gains * disc * (ranks < k), axis=1)  # (Q,)
            # host parity (ndcg_at_k): no-variation or zero-ideal queries
            # count as 1
            valid = (consts["inv_ideal"][i] > 0) & ~consts["all_same"]
            ndcg = jnp.where(valid, dcg * consts["inv_ideal"][i], 1.0)
            outs.append(jnp.mean(ndcg))
        return jnp.stack(outs)


class MAPMetric(_MeanPerQuery):
    name = "map"
    is_higher_better = True

    def eval(self, pred, label, weight, query_boundaries=None):
        assert query_boundaries is not None
        scores, labels = np.asarray(pred), np.asarray(label)
        out = []
        for k in self.cfg.eval_at:
            nq = len(query_boundaries) - 1
            total = 0.0
            for q in range(nq):
                lo, hi = query_boundaries[q], query_boundaries[q + 1]
                order = np.argsort(-scores[lo:hi], kind="stable")
                rel = (labels[lo:hi][order] > 0).astype(np.float64)
                kk = min(k, hi - lo)
                hits = np.cumsum(rel[:kk])
                prec = hits / np.arange(1, kk + 1)
                denom = max(min(int(rel.sum()), kk), 1)
                total += float(np.sum(prec * rel[:kk]) / denom)
            out.append((f"map@{k}", total / max(nq, 1), True))
        return out

    def device_query_constants(self, label, query_boundaries, shared=None):
        import jax.numpy as jnp

        label = np.asarray(label)
        if shared is not None:
            pad_idx, pad_mask = shared["pad_idx_np"], shared["pad_mask_np"]
            dev_idx, dev_mask = shared["pad_idx"], shared["pad_mask"]
        else:
            pad_idx, pad_mask = pad_queries(query_boundaries)
            dev_idx, dev_mask = jnp.asarray(pad_idx), jnp.asarray(pad_mask)
        rel_pad = np.where(pad_mask, label[pad_idx] > 0, False)
        return {
            "pad_idx": dev_idx,
            "pad_mask": dev_mask,
            "rel_pad": jnp.asarray(rel_pad),
            "ks": list(self.cfg.eval_at),
        }

    def device_eval_queries(self, pred, consts):
        import jax.numpy as jnp

        idx, msk = consts["pad_idx"], consts["pad_mask"]
        s = pred[idx.reshape(-1)].reshape(idx.shape)
        ms = jnp.where(msk, s, jnp.float32(-1e30))
        order = jnp.argsort(-ms, axis=1, stable=True)
        srel = jnp.take_along_axis(
            consts["rel_pad"], order, axis=1).astype(jnp.float32)
        hits = jnp.cumsum(srel, axis=1)
        pos = jnp.arange(1, srel.shape[1] + 1, dtype=jnp.float32)[None, :]
        prec = hits / pos
        total_rel = jnp.sum(srel, axis=1)
        outs = []
        for k in consts["ks"]:
            contrib = jnp.sum(prec * srel * (pos <= k), axis=1)
            denom = jnp.maximum(jnp.minimum(total_rel, float(k)), 1.0)
            outs.append(jnp.mean(contrib / denom))
        return jnp.stack(outs)


_METRICS: Dict[str, Callable[[Config], Metric]] = {
    "l2": L2Metric,
    "mse": L2Metric,
    "mean_squared_error": L2Metric,
    "regression": L2Metric,
    "regression_l2": L2Metric,
    "rmse": RMSEMetric,
    "l2_root": RMSEMetric,
    "root_mean_squared_error": RMSEMetric,
    "l1": L1Metric,
    "mae": L1Metric,
    "mean_absolute_error": L1Metric,
    "regression_l1": L1Metric,
    "quantile": QuantileMetric,
    "huber": HuberMetric,
    "fair": FairMetric,
    "poisson": PoissonMetric,
    "gamma": GammaMetric,
    "gamma_deviance": GammaDevianceMetric,
    "tweedie": TweedieMetric,
    "mape": MAPEMetric,
    "mean_absolute_percentage_error": MAPEMetric,
    "binary_logloss": BinaryLoglossMetric,
    "binary": BinaryLoglossMetric,
    "binary_error": BinaryErrorMetric,
    "auc": AUCMetric,
    "cross_entropy": CrossEntropyMetric,
    "xentropy": CrossEntropyMetric,
    "auc_mu": AucMuMetric,
    "xentropy_lambda": XentLambdaMetric,
    "multi_logloss": MultiLoglossMetric,
    "multiclass": MultiLoglossMetric,
    "softmax": MultiLoglossMetric,
    "multiclassova": MultiLoglossMetric,
    "multi_error": MultiErrorMetric,
    "ndcg": NDCGMetric,
    "lambdarank": NDCGMetric,
    "rank_xendcg": NDCGMetric,
    "map": MAPMetric,
    "mean_average_precision": MAPMetric,
}

_DEFAULT_METRIC_FOR_OBJECTIVE: Dict[str, str] = {
    "regression": "l2",
    "regression_l1": "l1",
    "huber": "huber",
    "fair": "fair",
    "poisson": "poisson",
    "gamma": "gamma",
    "tweedie": "tweedie",
    "quantile": "quantile",
    "mape": "mape",
    "binary": "binary_logloss",
    "multiclass": "multi_logloss",
    "multiclassova": "multi_logloss",
    "cross_entropy": "cross_entropy",
    "cross_entropy_lambda": "xentropy_lambda",
    "xentlambda": "xentropy_lambda",
    "lambdarank": "ndcg",
    "rank_xendcg": "ndcg",
}


def create_metrics(cfg: Config) -> List[Metric]:
    """reference: Metric::CreateMetric + Config metric-default resolution."""
    names = list(cfg.metric)
    if not names:
        default = _DEFAULT_METRIC_FOR_OBJECTIVE.get(cfg.objective)
        names = [default] if default else []
    out = []
    for name in names:
        # reference: "None"/"na"/"null"/"custom" disable metrics (the alias
        # list in docs/Parameters.rst is case-sensitive only in docs)
        if str(name).lower() in ("none", "null", "na", "custom", ""):
            continue
        if name not in _METRICS:
            raise ValueError(f"Unknown metric: {name}")
        out.append(_METRICS[name](cfg))
    return out
