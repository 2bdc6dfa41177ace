"""The comparison that decides ``correct``.

Both sides are given as the same plain structure: the score of every training
row after 0, 1, .. K trees (float arrays) and, per tree, the sum of its split
gains, its leaf count and the row count of every leaf.  The program's side is
read off the timed booster (the object the window then drives); the
reference's side comes from ``reference/<name>.py``.

Numbers compared (each a gap of the program's reading from the reference's,
as a share of the reference's, worst tree first):

``loss_gap``      binary log loss of the training rows after each tree
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


def log_loss(score: np.ndarray, label: np.ndarray) -> float:
    s = np.asarray(score, np.float64)
    y = np.asarray(label, np.float64)
    # log(1 + exp(s)) - y s, stable on both sides
    return float(np.mean(np.logaddexp(0.0, s) - y * s))


def _norm(x) -> float:
    return float(np.sqrt(np.sum(np.square(np.asarray(x, np.float64)))))


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-300)


def numbers(prog: dict, ref: dict, label: np.ndarray) -> dict:
    """The compared numbers, unrounded.  Uses as many trees as the reference
    followed."""
    k = len(ref["trees"])
    n = len(label)
    if len(prog["trees"]) < k or len(prog["scores"]) < k + 1:
        raise ValueError("the program's side holds fewer trees than the "
                         "reference followed")
    loss = max(_rel(log_loss(prog["scores"][i], label),
                    log_loss(ref["scores"][i], label))
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
        "loss_gap": loss,
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
