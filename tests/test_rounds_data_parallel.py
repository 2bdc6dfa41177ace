"""``grow_tree_fast_data_parallel == grow_tree_fast`` on the virtual CPU
devices ``tests/conftest.py`` forces.

The next four-chip cell stands on the data-parallel rounds grower
(``parallel/data_parallel.py``): every shard histograms its rows, one psum
a round merges the block, every shard applies the same splits.  So the
tree is the serial rounds grower's, up to the numbering of nodes admitted
in one round, to the float tolerance of a sum taken in shards.  Pinned over 2, 4 and 8 shards x float and int8
gradients x numeric, masked and categorical data and a row count no shard
count divides (the padding rows carry ``row_mask`` 0 and must change
nothing).  Before PR 30 tier-1 checked this in one toy leg of
``test_chip_smoke.py`` alone; the cases stand in for the windowed family's
sharded, two-level and 2-D equivalence tests.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.ops.treegrow_fast import grow_tree_fast
from lightgbm_tpu.parallel.data_parallel import (ShardedData,
                                                 grow_tree_fast_data_parallel)
from lightgbm_tpu.parallel.mesh import make_mesh
from lightgbm_tpu.utils.sanitizer import CompileCounter
from tests.test_rounds_equals_strict import (NUM_LEAVES, assert_same_tree,
                                             make_case)

# kind -> (make_case kind, rows): 483 = 3 * 7 * 23 divides by no shard count
KINDS = {"numeric": ("numeric", 480), "mask": ("mask", 480),
         "categorical": ("categorical", 480), "ragged-rows": ("numeric", 483)}


@pytest.mark.parametrize("quant", [0, 16], ids=["float", "int8"])
@pytest.mark.parametrize("shards", [2, 4, 8])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_rounds_data_parallel_equals_serial(kind, shards, quant):
    if jax.device_count() < shards:
        pytest.skip("needs the virtual multi-device CPU mesh")
    case, n = KINDS[kind]
    args, kw, params, _ = make_case(case, 63, seed=2, n=n)
    bins, grad, hess, mask, sw, fmask, nbpf, mbpf = args
    # a tree that saturates under the budget, as in test_rounds_equals_
    # strict: which leaves a truncated last round admits hangs on the
    # order of near-equal gains, and a sum taken in shards may flip it
    common = dict(num_leaves=NUM_LEAVES, num_bins=64, params=params,
                  leaf_tile=4,
                  use_pallas=False, quantize_bins=quant,
                  stochastic_rounding=False)
    want, want_leaf = grow_tree_fast(*args, **kw, **common)

    sd = ShardedData(make_mesh(shards), np.asarray(bins), np.asarray(nbpf),
                     np.asarray(mbpf))
    assert sd.padded % shards == 0 and (sd.padded > n) == (n % shards != 0)
    got, got_leaf = grow_tree_fast_data_parallel(
        sd, sd.pad_rows(np.asarray(grad)), sd.pad_rows(np.asarray(hess)),
        sd.pad_rows(np.asarray(mask), fill=False),
        sd.pad_rows(np.asarray(sw), fill=1.0), fmask,
        kw.get("categorical_mask"), **common)
    assert len(got_leaf.sharding.device_set) == shards
    nl = int(want.num_leaves)
    assert 4 <= nl < NUM_LEAVES
    if case == "categorical":
        assert bool(np.asarray(want.is_cat)[: nl - 1].any())
    assert_same_tree(got, np.asarray(got_leaf)[:n], want, want_leaf)


def test_booster_data_rounds_second_tree_compiles_nothing():
    """``tree_learner=data`` + ``tree_growth_mode=rounds`` through the
    Booster: the sharded rounds grower is taken, and once two trees are
    grown a further ``update()`` traces and compiles nothing (the pad and
    reshard of the gradients included)."""
    rng = np.random.RandomState(12)
    X = rng.randn(1003, 6).astype(np.float32)
    y = ((X @ rng.randn(6)) > 0).astype(np.float64)
    bst = lgb.Booster(
        params={"objective": "binary", "num_leaves": 15, "verbosity": -1,
                "min_data_in_leaf": 5, "tree_learner": "data",
                "tree_growth_mode": "rounds"},
        train_set=lgb.Dataset(X, label=y))
    g = bst._gbdt
    assert g._use_fast_dp and g._dp is not None and not g._use_fast
    assert g._dp.padded == 1008
    for _ in range(2):
        bst.update()
    np.asarray(g._score)
    with CompileCounter() as c:
        for _ in range(2):
            bst.update()
        np.asarray(g._score)
    c.assert_no_recompile("sharded rounds-grower updates")
    assert bst.num_trees() == 4
    acc = np.mean((bst.predict(X) > 0.5) == (y > 0))
    assert acc > 0.8, acc
