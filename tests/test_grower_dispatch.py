"""Which grower ``GBDT`` takes, as a table.

After PR 30 there are six ``grow_*`` entry points, each the one path for
its input and platform.  The chain in ``models/gbdt.py`` reads top to
bottom: spill (``grow_tree_ooc``), feature-parallel, the sharded rounds
grower, strict data/voting, the rounds grower, strict.  For each
``(tree_learner, tree_growth_mode, devices, out_of_core)`` this file pins
the grower one ``update()`` really calls (every entry point is wrapped and
calls through) and that the flags ``chip_smoke.booster_flags`` reads say
the same; and that a learner name the program does not have is refused by
name, not trained serially in silence.
"""

import numpy as np
import jax
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config

ENTRY_POINTS = {
    "grow_tree": "lightgbm_tpu.models.gbdt",
    "grow_tree_fast": "lightgbm_tpu.ops.treegrow_fast",
    "grow_tree_ooc": "lightgbm_tpu.ops.treegrow_ooc",
    "grow_tree_data_parallel": "lightgbm_tpu.parallel.data_parallel",
    "grow_tree_fast_data_parallel": "lightgbm_tpu.parallel.data_parallel",
    "grow_tree_feature_parallel": "lightgbm_tpu.parallel.feature_parallel",
}

# (tree_learner, tree_growth_mode, devices, out_of_core) -> grower.
# out_of_core: None in memory, "resident" streamed and assembled on the
# device, "spill" rows over max_rows_in_hbm.  The tests' platform is the
# CPU, where tree_growth_mode=auto means strict.
TABLE = [
    (("serial", "strict", 8, None), "grow_tree"),
    (("serial", "auto", 8, None), "grow_tree"),
    (("serial", "rounds", 8, None), "grow_tree_fast"),
    (("serial", "rounds", 1, None), "grow_tree_fast"),
    (("serial", "rounds", 8, "resident"), "grow_tree_fast"),
    (("serial", "strict", 8, "spill"), "grow_tree_ooc"),
    (("serial", "rounds", 8, "spill"), "grow_tree_ooc"),
    (("data", "rounds", 8, None), "grow_tree_fast_data_parallel"),
    (("data", "strict", 8, None), "grow_tree_data_parallel"),
    (("data", "auto", 8, None), "grow_tree_data_parallel"),
    (("voting", "rounds", 8, None), "grow_tree_data_parallel"),
    (("feature", "rounds", 8, None), "grow_tree_feature_parallel"),
    # one device: no mesh is built and the learner runs the strict grower
    (("data", "rounds", 1, None), "grow_tree"),
]


def _dataset(out_of_core, tmp_path):
    rng = np.random.RandomState(4)
    X = rng.randn(240, 4)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    params = {"max_bin": 255, "verbosity": -1, "feature_pre_filter": False}
    ds = lgb.Dataset(X, label=y, params=params)
    if out_of_core is None:
        return ds
    ds.construct()
    cache = str(tmp_path / "d.bin")
    ds.save_binary(cache)
    extra = {"out_of_core": True}
    if out_of_core == "spill":
        extra["max_rows_in_hbm"] = 50
    return lgb.Dataset(cache, params=dict(params, **extra))


@pytest.mark.parametrize("key,want", TABLE,
                         ids=["-".join(map(str, k)) for k, _ in TABLE])
def test_grower_taken(key, want, tmp_path, monkeypatch):
    import importlib

    learner, mode, devices, out_of_core = key
    if devices == 1:
        monkeypatch.setattr(jax, "device_count", lambda *a: 1)
    elif jax.device_count() < devices:
        pytest.skip("needs the virtual multi-device CPU mesh")
    called = []
    for name, module in ENTRY_POINTS.items():
        mod = importlib.import_module(module)

        def wrapper(*a, _real=getattr(mod, name), _name=name, **kw):
            called.append(_name)
            return _real(*a, **kw)

        monkeypatch.setattr(mod, name, wrapper)

    bst = lgb.Booster(
        params={"objective": "binary", "num_leaves": 7, "max_bin": 255,
                "min_data_in_leaf": 5, "verbosity": -1,
                "feature_pre_filter": False, "fused_training": False,
                "tree_learner": learner, "tree_growth_mode": mode},
        train_set=_dataset(out_of_core, tmp_path))
    bst.update()
    # the sharded entry points call the serial growers inside shard_map:
    # the first call is GBDT's own
    assert called and called[0] == want, called
    assert bst.num_trees() == 1

    g = bst._gbdt
    flags = {"use_fast": bool(g._use_fast), "use_fast_dp": bool(g._use_fast_dp),
             "dp": g._dp is not None, "fp": g._fp is not None,
             "spill": bool(g._ooc_spill)}
    read_off = ("grow_tree_ooc" if flags["spill"]
                else "grow_tree_feature_parallel" if flags["fp"]
                else "grow_tree_fast_data_parallel"
                if flags["dp"] and flags["use_fast_dp"]
                else "grow_tree_data_parallel" if flags["dp"]
                else "grow_tree_fast" if flags["use_fast"] else "grow_tree")
    assert read_off == want, flags
    import chip_smoke

    smoke = chip_smoke.booster_flags(bst)
    assert smoke["use_fast"] == flags["use_fast"]
    assert smoke["use_fast_dp"] == flags["use_fast_dp"]
    assert not smoke["fused_built"] and not smoke["fused_disabled"]


@pytest.mark.parametrize("name", ["feature2d", "dta", "data_parallel", ""])
def test_unknown_tree_learner_is_refused_by_name(name):
    """``feature2d`` went with the 2-D round (PR 30); it and any
    misspelling raise as upstream does, at the ``Config``."""
    with pytest.raises(ValueError, match="Unknown tree learner type") as e:
        Config.from_dict({"tree_learner": name})
    assert repr(name) in str(e.value)
    rng = np.random.RandomState(0)
    ds = lgb.Dataset(rng.randn(50, 3), label=rng.rand(50))
    with pytest.raises(ValueError, match="Unknown tree learner type"):
        lgb.train({"objective": "regression", "tree_learner": name,
                   "verbosity": -1}, ds, 1)


@pytest.mark.parametrize("alias", ["tree_learner", "tree", "tree_type",
                                   "tree_learner_type"])
@pytest.mark.parametrize("name", ["serial", "data", "feature", "voting"])
def test_known_tree_learners_and_their_aliases_are_accepted(alias, name):
    assert Config.from_dict({alias: name}).tree_learner == name
