"""What a generator may carry beside bins and labels (query sizes, weights,
positions), a configuration's ``categorical_features`` and its ``loss`` reach
``Dataset``, the reference and the comparison; and a deployment that states
none of them is driven by the calls it always was.  Every deployment here is
added as files under a temporary root, with no edit to ``chipbench/``."""

import json
import math

import numpy as np
import pytest

from chipbench.harness import compare, loader, stages

QUERY_SIZES = [100, 1, 299, 50, 150]  # unequal, one of a single row: 600 rows

GENERATORS = {
    "weighted_bins": """
import numpy as np
from chipbench.datagen import quantile_bins


def generate(config, seed):
    data = quantile_bins.generate(config, seed)
    rng = np.random.default_rng([int(seed), 1])
    data["weight"] = rng.uniform(0.25, 4.0, len(data["label"])).astype(
        np.float32)
    return data
""",
    "grouped_bins": """
import numpy as np
from chipbench.datagen import quantile_bins


def generate(config, seed):
    data = quantile_bins.generate(config, seed)
    n = len(data["label"])
    sizes = config["datagen_params"]["query_sizes"]
    data["group"] = np.tile(np.asarray(sizes, np.int64), n // sum(sizes))
    data["label"] = np.random.default_rng([int(seed), 2]).integers(
        0, 5, n).astype(np.float32)
    return data
""",
    "categorical_bins": """
import numpy as np
from chipbench.datagen import quantile_bins


def generate(config, seed):
    data = quantile_bins.generate(config, seed)
    for f in config["categorical_features"]:
        # a category id a drawn bin, in no order of size
        data["values"][:, f] = (np.arange(data["n_bins"]) * 7) % data["n_bins"]
    return data
""",
}

RECORDING_REFERENCE = """
def train(bins, label, params, **kw):
    return {"args": (bins, label, params), "kw": kw}
"""


def _deployment(toy_roots, name, datagen, datagen_params=(), **keys):
    """A toy configuration of its own with its generator, as added files."""
    root = toy_roots[0]
    (root / "datagen").mkdir(exist_ok=True)
    (root / "datagen" / f"{datagen}.py").write_text(GENERATORS[datagen])
    cfg = loader.load_json(root / "configs" / "toy.json")
    cfg.update(name=name, datagen=datagen, **keys)
    cfg["datagen_params"].update(datagen_params)
    (root / "configs" / f"{name}.json").write_text(json.dumps(cfg))
    return loader.load_cell({"name": f"{name}-train", "config": name,
                             "traffic": "short-train", "chips": 1}, toy_roots)


def test_weights_reach_the_program_and_the_reference(toy_roots, tmp_path):
    cell = _deployment(toy_roots, "toy-weighted", "weighted_bins")
    run, program = stages.drive(cell, 21, 0.3, str(tmp_path / "cache"))
    np.testing.assert_array_equal(program[1].get_weight(),
                                  np.asarray(run["data"]["weight"], np.float64))
    del program
    stages.free_program()
    correct, compared = stages.judge(cell, run)
    assert correct, compared
    # the reference without the weights is another model: they reached both
    bare = {k: v for k, v in run["data"].items() if k != "weight"}
    ref = stages.run_reference(cell, bare, run["reference_trees"])
    ok, compared = compare.judge(
        compare.numbers(run["program"], ref, run["data"]),
        cell["config"]["limits"])
    assert not ok, compared


def test_groups_reach_dataset_and_the_reference(toy_roots, tmp_path):
    (toy_roots[0] / "reference").mkdir()
    (toy_roots[0] / "reference" / "recording.py").write_text(
        RECORDING_REFERENCE)
    cell = _deployment(toy_roots, "toy-grouped", "grouped_bins",
                       {"query_sizes": QUERY_SIZES}, rows=6000,
                       reference="recording", loss="ndcg", loss_at=5)
    data = stages.make_data(cell, 5)
    assert sorted(np.unique(data["label"])) == [0, 1, 2, 3, 4]
    ds = stages.build_dataset(cell, data, str(tmp_path / "cache"), 5)
    assert ds.get_group().dtype == np.int64
    np.testing.assert_array_equal(ds.get_group(), np.tile(QUERY_SIZES, 10))
    assert ds.get_weight() is None and ds.get_position() is None
    got = stages.run_reference(cell, data, 2)
    assert sorted(got["kw"]) == ["group", "leaf_tile", "n_trees"]
    np.testing.assert_array_equal(got["kw"]["group"], data["group"])
    assert got["args"][0] is data["bins"] and got["args"][1] is data["label"]
    assert compare.loss_named(cell["config"]) == {"loss": "ndcg", "at": 5}
    # the program takes the Dataset: a ranking booster grows a tree on it
    bst = stages.build_booster(cell, ds, {"objective": "lambdarank"})
    bst.update()
    assert bst.num_trees() == 1


def test_a_dataset_that_dropped_the_group_is_not_a_measurement(
        toy_roots, tmp_path, monkeypatch):
    cell = _deployment(toy_roots, "toy-grouped", "grouped_bins",
                       {"query_sizes": QUERY_SIZES}, rows=600)
    from lightgbm_tpu.io import stream

    real = stream.create_bin_cache
    monkeypatch.setattr(
        stream, "create_bin_cache",
        lambda path, bins, mappers, group=None, **kw: real(
            path, bins, mappers, **kw))
    with pytest.raises(RuntimeError, match="group"):
        stages.build_dataset(cell, stages.make_data(cell, 5),
                             str(tmp_path / "cache"), 5)


def test_ndcg_equals_the_value_worked_out_by_hand():
    data = {"label": np.array([3, 2, 0, 0, 1], np.float32),
            "group": np.array([3, 2])}
    score = np.array([0.1, 0.9, 0.5, 0.5, 0.5], np.float32)
    # query 1 ranks labels 2, 0, 3: DCG@2 = 3/log2(2) + 0; the best order is
    # 3, 2: 7/log2(2) + 3/log2(3).  Query 2 ties, so row order, labels 0, 1:
    # DCG@2 = 0 + 1/log2(3); the best is 1/log2(2)
    q1 = 3.0 / (7.0 + 3.0 / math.log2(3.0))
    q2 = 1.0 / math.log2(3.0)
    assert compare.ndcg(score, data, at=2) == pytest.approx(
        1.0 - (q1 + q2) / 2.0, rel=1e-12)
    # at 1 the tie's first row, label 0, is all that counts in query 2
    assert compare.ndcg(score, data, at=1) == pytest.approx(
        1.0 - (3.0 / 7.0 + 0.0) / 2.0, rel=1e-12)
    # a query with no relevant row counts as 1, as LightGBM's metric does
    none = {"label": np.array([3, 2, 0, 0, 0], np.float32),
            "group": data["group"]}
    assert compare.ndcg(score, none, at=2) == pytest.approx(
        1.0 - (q1 + 1.0) / 2.0, rel=1e-12)


def test_losses_by_name_and_their_weights():
    data = {"label": np.array([1.0, 0.0, 1.0]),
            "weight": np.array([1.0, 2.0, 5.0])}
    score = np.array([0.0, 1.0, -2.0])
    each = np.logaddexp(0.0, score) - data["label"] * score
    assert compare.binary_logloss(score, data) == pytest.approx(
        (each[0] + 2 * each[1] + 5 * each[2]) / 8.0, rel=1e-12)
    assert compare.binary_logloss(score, {"label": data["label"]}) == \
        pytest.approx(each.mean(), rel=1e-12)
    assert compare.l2(score, data) == pytest.approx(
        (1.0 + 2 * 1.0 + 5 * 9.0) / 8.0, rel=1e-12)
    assert compare.loss_named({}) == {"loss": "binary_logloss"}
    side = {"scores": [score, score], "trees": [{
        "gain_sum": 1.0, "root_gain": 1.0, "root_hess": 1.0, "num_leaves": 2,
        "leaf_count": np.array([1, 2])}]}
    assert compare.numbers(side, side, data, loss="l2")["loss_gap"] == 0.0
    with pytest.raises(KeyError, match="no loss"):
        compare.numbers(side, side, data, loss="auc")


def test_categorical_features_give_categorical_mappers(toy_roots, tmp_path):
    cell = _deployment(toy_roots, "toy-categorical", "categorical_bins",
                       rows=600, categorical_features=[1, 4])
    data = stages.make_data(cell, 9)
    binner, lut = stages.fit_mappers(data, cell["config"]["params"],
                                     cell["config"]["categorical_features"])
    assert [m.is_categorical for m in binner.mappers] == [
        False, True, False, False, True, False]
    n_bins = data["n_bins"]
    for f in range(6):
        # every drawn bin keeps a bin of its own in the program's space
        assert sorted(lut[:, f]) == list(range(n_bins))
        mapper = binner.mappers[f]
        np.testing.assert_array_equal(
            lut[:, f], mapper.transform(data["values"][:, f]))
    ds = stages.build_dataset(cell, data, str(tmp_path / "cache"), 9)
    assert list(ds.binner.categorical_mask) == [
        False, True, False, False, True, False]
    np.testing.assert_array_equal(ds.bins, stages.program_bins(data, lut))


def test_a_plain_deployment_is_driven_by_the_calls_it_always_was(
        toy_cell, tmp_path, monkeypatch):
    """For the toy copy of ``higgs`` the binner, the cache writer and the
    reference receive exactly the keyword arguments they did before a
    generator could carry more, and no new one."""
    from lightgbm_tpu import binning
    from lightgbm_tpu.io import stream

    seen = {}

    def recording(name, real):
        def call(*args, **kw):
            seen[name] = (len(args), sorted(kw))
            return real(*args, **kw)
        return call

    monkeypatch.setattr(binning.DatasetBinner, "fit", recording(
        "fit", binning.DatasetBinner.fit))
    monkeypatch.setattr(stream, "create_bin_cache", recording(
        "create_bin_cache", stream.create_bin_cache))
    monkeypatch.setattr(toy_cell["reference"], "train", recording(
        "train", toy_cell["reference"].train))
    data = stages.make_data(toy_cell, 13)
    assert sorted(data) == ["bins", "label", "n_bins", "values"]
    assert stages.metadata(data) == {}
    ds = stages.build_dataset(toy_cell, data, str(tmp_path / "cache"), 13)
    assert ds.get_group() is None and ds.get_weight() is None
    stages.run_reference(toy_cell, data, 1)
    assert seen == {
        "fit": (1, ["max_bin", "min_data_in_bin"]),
        "create_bin_cache": (3, ["feature_names", "label"]),
        "train": (3, ["leaf_tile", "n_trees"])}
