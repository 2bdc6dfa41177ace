"""work.py against a hand-counted tree."""

import numpy as np

from chipbench.harness import loader, work


def test_three_leaf_tree_by_hand():
    # root (100 rows) -> node 1 (70) | leaf 0 (30); node 1 -> leaf 1 (45) | leaf 2 (25)
    left_child = np.array([1, -2])     # node 0: left is node 1; node 1: left is leaf 1
    right_child = np.array([-1, -3])   # node 0: right is leaf 0; node 1: right is leaf 2
    internal_count = np.array([100, 70])
    leaf_count = np.array([30, 45, 25])
    left, right = work.child_counts(left_child, right_child, internal_count,
                                    leaf_count)
    assert left.tolist() == [70, 45] and right.tolist() == [30, 25]
    # the root's 100, then the smaller child of each split: 30 and 25
    assert work.rows_visited(100, left_child, right_child, internal_count,
                             leaf_count) == 155


def test_stump_visits_every_row_once():
    assert work.rows_visited(100, [], [], [], [100]) == 100


def test_least_time_is_memory_bound_on_the_v5e():
    peaks = loader.load_peaks("TPU v5 lite")
    w = work.least_time(155, 28, peaks)
    assert w["bytes"] == 155 * (28 + 8) and w["ops"] == 155 * 56
    assert w["bound"] == "memory"
    assert w["seconds"] == w["bytes"] / 819e9


def test_unknown_device_kind_is_an_error():
    import pytest

    with pytest.raises(KeyError):
        loader.load_peaks("cpu")
