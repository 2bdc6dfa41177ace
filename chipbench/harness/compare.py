"""The comparison that decides ``correct``.

Both sides are given as the same plain structure: the score of every training
row after 0, 1, .. K trees (float arrays) and, per tree, the sum of its split
gains, its leaf count and the row count of every leaf.  The program's side is
read off the timed booster (the object the window then drives); the
reference's side comes from ``reference/<name>.py``.

Numbers compared (each a gap of the program's reading from the reference's,
as a share of the reference's, worst tree first):

``loss_gap``      the objective's own loss on the training rows after each
                  tree: the function the configuration names under ``loss``
                  (``LOSSES``), binary log loss where it names none
``update1_gap``   norm of the first tree's change of the score (what the
                  first boosting step hands on: the gradient as applied)
``updateK_gap``   norm of the score's change after all K trees
``gain_gap``      sum of split gains of each tree
``root_gain_gap`` gain of each tree's first split: a difference of large
                  sums over all rows, which feels the histograms' precision
                  first and which no near-tie further down can move
``root_hess_gap`` sum of the hessians of all rows, as each tree's root holds
                  it: a plain total that no near-tie can move, and that one
                  bfloat16 term a hessian moves by a ten-thousandth
``rows_gap``      rows held by each tree's leaves against the rows trained
                  on (exact: limit 0)
``leaves_gap``    leaves of each tree (exact: limit 0)

A gap of norms, not the norm of a gap: two sound runs may break a near-tie
between two splits differently and so differ on some rows, while the
quantities above stay put.
"""

from __future__ import annotations

import numpy as np


def _mean(x: np.ndarray, data: dict) -> float:
    """Over the rows, by their weights where the data holds any."""
    return float(np.average(x, weights=data.get("weight")))


def binary_logloss(score, data: dict) -> float:
    s = np.asarray(score, np.float64)
    y = np.asarray(data["label"], np.float64)
    # log(1 + exp(s)) - y s, stable on both sides
    return _mean(np.logaddexp(0.0, s) - y * s, data)


def l2(score, data: dict) -> float:
    d = np.asarray(score, np.float64) - np.asarray(data["label"], np.float64)
    return _mean(d * d, data)


def ndcg(score, data: dict, at: int = 10) -> float:
    """One minus the mean over queries of NDCG@``at``, as LightGBM's metric
    counts it: gain ``2^label - 1``, discount ``1 / log2(rank + 2)``, ties in
    the score in row order, a query with no relevant row counted as 1."""
    group = np.asarray(data["group"], np.int64)
    gain = np.exp2(np.asarray(data["label"], np.float64)) - 1.0
    query = np.repeat(np.arange(len(group)), group)
    rank = np.arange(len(query)) - np.repeat(np.cumsum(group) - group, group)
    top = rank < at
    discount = 1.0 / np.log2(rank[top] + 2.0)

    def dcg(key):
        # lexsort is stable: rows of a query with equal keys keep their order
        order = np.lexsort((-np.asarray(key, np.float64), query))
        return np.bincount(query[top], gain[order][top] * discount,
                           minlength=len(group))

    got, best = dcg(score), dcg(gain)
    some = best > 0
    return 1.0 - float(np.mean(np.where(some, got / np.where(some, best, 1.0),
                                        1.0)))


LOSSES = {"binary_logloss": binary_logloss, "l2": l2, "ndcg": ndcg}


def loss_named(config: dict) -> dict:
    """The keywords ``numbers`` takes for a configuration's ``loss`` (default
    ``binary_logloss``) and, where it states one, ``loss_at`` (NDCG's cut)."""
    at = {"at": int(config["loss_at"])} if "loss_at" in config else {}
    return dict(loss=config.get("loss", "binary_logloss"), **at)


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-300)


def numbers(prog: dict, ref: dict, data: dict, loss: str = "binary_logloss",
            **loss_args) -> dict:
    """The compared numbers, unrounded.  Uses as many trees as the reference
    followed.  ``data`` is what the generator drew: the label, and the
    weights and query sizes where there are any, for the loss named
    (``loss_args`` are that function's own, NDCG's ``at``)."""
    if loss not in LOSSES:
        raise KeyError(f"no loss {loss!r} (has {sorted(LOSSES)})")
    loss_of = LOSSES[loss]
    k = len(ref["trees"])
    n = len(data["label"])
    if len(prog["trees"]) < k or len(prog["scores"]) < k + 1:
        raise ValueError("the program's side holds fewer trees than the "
                         "reference followed")
    loss_gap = max(_rel(loss_of(prog["scores"][i], data, **loss_args),
                        loss_of(ref["scores"][i], data, **loss_args))
                   for i in range(1, k + 1))

    def update(side, i):
        return _norm(np.asarray(side["scores"][i], np.float64)
                     - np.asarray(side["scores"][0], np.float64))

    gain = max(_rel(float(p["gain_sum"]), float(r["gain_sum"]))
               for p, r in zip(prog["trees"][:k], ref["trees"]))
    root = max(_rel(float(p["root_gain"]), float(r["root_gain"]))
               for p, r in zip(prog["trees"][:k], ref["trees"]))
    hess = max(_rel(float(p["root_hess"]), float(r["root_hess"]))
               for p, r in zip(prog["trees"][:k], ref["trees"]))
    rows = max(abs(int(np.sum(p["leaf_count"])) - n) / n
               for p in prog["trees"][:k])
    leaves = max(abs(int(p["num_leaves"]) - int(r["num_leaves"]))
                 for p, r in zip(prog["trees"][:k], ref["trees"]))
    return {
        "loss_gap": loss_gap,
        "update1_gap": _rel(update(prog, 1), update(ref, 1)),
        "updateK_gap": _rel(update(prog, k), update(ref, k)),
        "gain_gap": gain,
        "root_gain_gap": root,
        "root_hess_gap": hess,
        "rows_gap": rows,
        "leaves_gap": float(leaves),
    }


def judge(nums: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and, for the result line, each number beside its limit.
    A number without a limit in the configuration's file is a fault of the
    file, not a pass."""
    out, ok = {}, True
    for name, value in nums.items():
        if name not in limits:
            raise KeyError(f"the configuration states no limit for {name}")
        limit = float(limits[name])
        good = bool(np.isfinite(value) and value <= limit)
        ok = ok and good
        out[name] = {"value": value, "limit": limit}
    return ok, out


def program_side(scores: list, trees: list) -> dict:
    """The program's trees (host ``Tree`` objects with LightGBM's array
    names) and score snapshots in the structure ``numbers`` takes."""
    out = []
    for t in trees:
        nl = int(t.num_leaves)
        out.append({
            "gain_sum": float(np.sum(np.asarray(t.split_gain[:nl - 1],
                                                np.float64))),
            "root_gain": float(t.split_gain[0]) if nl > 1 else 0.0,
            "root_hess": float(t.internal_weight[0]) if nl > 1 else 0.0,
            "num_leaves": nl,
            "leaf_count": np.asarray(t.leaf_count[:nl], np.int64),
        })
    return {"scores": [np.asarray(s, np.float32).ravel() for s in scores],
            "trees": out}
