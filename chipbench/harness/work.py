"""The work a tree needs, from shapes and from the trained tree, whatever
implements it.

A leaf-wise histogram learner with histogram subtraction has to visit, per
tree, every row once for the root and then, for every split, the rows of the
smaller child: ``N + sum over internal nodes of min(left, right)``.  Per
visited row it reads F bins of one byte (max_bin <= 256) and one gradient
pair (two float32), and makes 2F accumulations.  The one-hot product's
FLOPs, an int16 device copy of the bins and lanes padded to 128 are how one
kernel does this work, not the work: they are not counted, so a PR that does
away with them sees its share rise and not its yardstick move.
"""

from __future__ import annotations

import numpy as np

GRAD_PAIR_BYTES = 8  # gradient and hessian, float32 each
BIN_BYTES = 1  # max_bin <= 256


def child_counts(left_child, right_child, internal_count, leaf_count):
    """(left, right) row counts of every internal node.  Children encode a
    leaf as ``~leaf`` (negative), as ``models/tree.py`` does."""
    internal_count = np.asarray(internal_count, np.int64)
    leaf_count = np.asarray(leaf_count, np.int64)

    def count(c):
        c = np.asarray(c, np.int64)
        return np.where(c >= 0, internal_count[np.maximum(c, 0)],
                        leaf_count[np.maximum(-c - 1, 0)])

    return count(left_child), count(right_child)


def rows_visited(n_rows: int, left_child, right_child, internal_count,
                 leaf_count) -> int:
    """N for the root plus the smaller child of every split."""
    if len(np.asarray(left_child)) == 0:
        return int(n_rows)
    left, right = child_counts(left_child, right_child, internal_count,
                               leaf_count)
    return int(n_rows + np.minimum(left, right).sum())


def tree_rows(tree, n_rows: int) -> int:
    """``rows_visited`` of a host tree with LightGBM's array names."""
    k = int(tree.num_leaves) - 1
    return rows_visited(n_rows, tree.left_child[:k], tree.right_child[:k],
                        tree.internal_count[:k],
                        tree.leaf_count[:int(tree.num_leaves)])


def least_time(rows: int, n_features: int, peaks: dict) -> dict:
    """The least time the chip could take over ``rows`` visited rows, and
    which peak bounds it."""
    nbytes = rows * (n_features * BIN_BYTES + GRAD_PAIR_BYTES)
    ops = rows * 2 * n_features
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    t_ops = ops / peaks["flops_per_s_bf16"]
    return {"rows": int(rows), "bytes": int(nbytes), "ops": int(ops),
            "seconds": max(t_mem, t_ops),
            "bound": "memory" if t_mem >= t_ops else "compute"}
