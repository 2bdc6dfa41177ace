"""The click-log deployment ``criteo``: its generator, its plain reference
(numerical thresholds with both missing directions, both families of
categorical split) and a toy copy of the cell through the stages, added as
files under a temporary root."""

import json
import re

import numpy as np
import pytest

from chipbench.harness import compare, faults, loader, stages

CRITEO = loader.CHIPBENCH / "configs" / "criteo.json"
LIMIT_NAMES = {"loss_gap", "update1_gap", "updateK_gap", "gain_gap",
               "root_gain_gap", "root_hess_gap", "rows_gap", "leaves_gap"}
# what sound runs read here (CPU, toy size): 4e-6 at most on every number
# (the CPU's scatter histograms sum a leaf's rows one by one in float32);
# the reference one precision down reads 1e-4 or more on several
TOY_LIMITS = dict.fromkeys(LIMIT_NAMES, 2e-5) | {"rows_gap": 0.0,
                                                 "leaves_gap": 0.0}
# three integer columns (one never missing) and six categorical ones: two
# one-hot sized, two mid, two of 255 bins with a rest level
TOY_MISSING = [0.454, 0.0, 0.2]
TOY_LEVELS = [1460, 3, 24, 105, 4, 10131227]


def toy_criteo(rows=6000) -> dict:
    cfg = loader.load_json(CRITEO)
    cats = list(range(3, 9))
    cfg.update(name="toy-criteo", rows=rows, features=9,
               categorical_features=cats)
    # half the labels positive: the first tree's hessians are 0.25, which
    # the CPU's float32 scatter adds up exactly
    cfg["datagen_params"].update(missing_shares=TOY_MISSING,
                                 level_counts=TOY_LEVELS, effect_columns=4,
                                 positive_share=0.5)
    cfg["params"].update(num_leaves=15, min_sum_hessian_in_leaf=5.0,
                         categorical_feature=cats)
    cfg["limits"] = dict(TOY_LIMITS)
    return cfg


@pytest.fixture
def toy_cell(toy_roots):
    (toy_roots[0] / "configs" / "toy-criteo.json").write_text(
        json.dumps(toy_criteo()))
    return loader.load_cell({"name": "toy-criteo-train",
                             "config": "toy-criteo",
                             "traffic": "short-train", "chips": 1}, toy_roots)


@pytest.fixture(scope="module")
def gen():
    return loader.load_module(loader.CHIPBENCH / "datagen"
                              / "click_columns.py")


@pytest.fixture(scope="module")
def ref():
    return loader.load_module(loader.CHIPBENCH / "reference"
                              / "categorical_rounds.py")


# ---------------------------------------------------------------------------
# the committed files
# ---------------------------------------------------------------------------

def test_the_configuration_states_its_source_its_cut_and_what_it_assumes():
    cfg = loader.load_json(CRITEO)
    assert (cfg["rows"], cfg["features"]) == (15_280_205, 39)
    assert cfg["categorical_features"] == list(range(13, 39))
    assert cfg["reduced"] == ["rows"]
    assert "15,280,205 of 45,840,617" in cfg["reduced_why"]
    assert "360 s" in cfg["reduced_why"] and "4.35 GB" in cfg["reduced_why"]
    assert "Criteo Display Advertising" in cfg["source"]
    assert len(cfg["source"]) <= 200
    assert "one row of every three" in cfg["deployment"]
    p = cfg["params"]
    assert p["objective"] == "binary"
    assert (p["num_leaves"], p["learning_rate"], p["min_data_in_leaf"],
            p["min_sum_hessian_in_leaf"], p["max_bin"]) == (255, 0.1, 1,
                                                            100.0, 255)
    assert (p["cat_smooth"], p["cat_l2"], p["max_cat_threshold"],
            p["max_cat_to_onehot"], p["min_data_per_group"]) == (
                10.0, 10.0, 32, 4, 100)
    assert p["categorical_feature"] == cfg["categorical_features"]
    assert (p["use_missing"], p["enable_bundle"]) == (True, False)
    assert (p["tree_growth_mode"], p["hist_precision"], p["fused_training"],
            p["use_quantized_grad"], p["tree_learner"]) == (
                "rounds", "f32", False, False, "serial")
    assert cfg["loss"] == "binary_logloss"
    assert cfg["control"]["params"] == {"hist_precision": "bf16"}
    assert set(cfg["limits"]) == LIMIT_NAMES
    assert cfg["limits_read"] and "guarantees" in cfg
    assert cfg["xla_flags"] == loader.load_json(
        loader.CHIPBENCH / "configs" / "higgs.json")["xla_flags"]
    for key in ("level_counts", "missing_shares", "zipf_exponent, keep_levels",
                "label", "data", "leaf_tile"):
        assert key in cfg["assumed"]
    g = cfg["datagen_params"]
    assert len(g["missing_shares"]) == 13 and len(g["level_counts"]) == 26
    assert min(g["missing_shares"]) == 0.0 and max(g["missing_shares"]) < 0.8
    assert (min(g["level_counts"]), max(g["level_counts"])) == (3, 10131227)
    assert g["positive_share"] == 0.256 and isinstance(g["table_seed"], int)


def test_the_leaf_tile_is_the_one_the_program_recommends_at_this_shape():
    from lightgbm_tpu.ops.hist_pallas import recommended_leaf_tile

    cfg = loader.load_json(CRITEO)
    for side in (cfg, cfg["control"]):
        assert side["grower"]["leaf_tile"] == recommended_leaf_tile(
            cfg["params"]["max_bin"], cfg["features"],
            cfg["params"]["num_leaves"],
            hist_precision=side["grower"]["hist_precision"])


def test_the_cell_is_named_as_the_issue_names_it_and_every_cell_loads():
    bench = loader.load_benchmark()
    cell = loader.find_workload(bench, "criteo-train")
    assert cell == dict(cell, config="criteo", traffic="steady-train",
                        chips=1)
    loaded = loader.load_cell(cell)
    assert loaded["datagen"].__file__.endswith("click_columns.py")
    assert loaded["reference"].__file__.endswith("categorical_rounds.py")
    reports = [m["name"] for m in bench["per_layer"]
               if "criteo-train" in m["workloads"]]
    assert len(reports) == 10 and "hist_roofline" in reports
    assert reports == [m["name"] for m in bench["per_layer"]
                       if "istella-train" in m["workloads"]]
    assert len(bench["workloads"]) == 4
    assert all(w["chips"] == 1 for w in bench["workloads"])
    for w in bench["workloads"]:
        assert loader.load_cell(w)["config"]["name"] == w["config"]


def test_the_reference_imports_nothing_of_the_program(ref):
    text = (loader.CHIPBENCH / "reference"
            / "categorical_rounds.py").read_text()
    assert not re.search(r"^\s*(from|import)\s+lightgbm_tpu", text,
                         re.MULTILINE)
    assert "lightgbm_tpu" not in {m.split(".")[0] for m in vars(ref)}
    # the missing bin is the generator's convention, read from its module
    assert ref.missing_bin(255) == 254


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------

def rows_of(data, n_int):
    """Every row as one number whatever the order of its columns within
    their kind, with its label."""
    b = data["bins"].astype(np.int64)
    keys = []
    for part in (b[:, :n_int], b[:, n_int:]):
        part = np.sort(part, axis=1)
        keys.append(part @ (256 ** np.arange(part.shape[1])))
    return np.sort((keys[0] * 7 + keys[1] * 3) * 2
                   + data["label"].astype(np.int64))


def test_every_seed_poses_the_same_problem_in_another_order(toy_cell):
    a = stages.make_data(toy_cell, 3_600_000_123)
    again = stages.make_data(toy_cell, 3_600_000_123)
    b = stages.make_data(toy_cell, 3_600_000_124)
    for key in ("bins", "label", "values"):
        np.testing.assert_array_equal(a[key], again[key])
    assert not np.array_equal(a["bins"], b["bins"])
    np.testing.assert_array_equal(rows_of(a, 3), rows_of(b, 3))
    # a column keeps its kind: the integer columns' values are increasing
    # with a NaN last, the categorical ones' ids never NaN
    for data in (a, b):
        assert np.isnan(data["values"][-1, :3]).all()
        assert not np.isnan(data["values"][:, 3:]).any()
        assert (np.diff(data["values"][:-1, :3], axis=0) > 0).all()
    # and its values ride with it: a column's histogram by raw value
    def by_value(data):
        return sorted(
            tuple(np.bincount(data["bins"][:, f], minlength=255))
            + tuple(np.nan_to_num(data["values"][:, f], nan=-1.0))
            for f in range(9))
    assert by_value(a) == by_value(b)
    other = dict(toy_cell["config"], datagen_params=dict(
        toy_cell["config"]["datagen_params"], table_seed=5))
    c = toy_cell["datagen"].generate(other, 3_600_000_123)
    assert not np.array_equal(rows_of(c, 3), rows_of(a, 3))


def test_labels_missing_values_and_levels_hold_their_stated_shares(
        toy_roots, gen):
    cfg = toy_criteo(rows=60_000)
    cfg["datagen_params"]["positive_share"] = 0.256
    (toy_roots[0] / "configs" / "toy-criteo.json").write_text(json.dumps(cfg))
    cell = loader.load_cell({"name": "t", "config": "toy-criteo",
                             "traffic": "short-train", "chips": 1}, toy_roots)
    data = stages.make_data(cell, 7)
    n = 60_000
    assert data["bins"].dtype == np.uint8 and data["label"].dtype == np.float32
    assert abs(data["label"].mean() - 0.256) < 2 / n
    # the seed orders the columns within their kind: tell them by their
    # shares, which differ
    missing = np.sort((data["bins"][:, :3] == gen.missing_bin(255)).mean(0))
    np.testing.assert_allclose(missing, np.sort(TOY_MISSING), atol=0.01)
    assert missing[0] == 0.0  # a column that is never missing: an empty bin
    held = data["bins"][:, :3][data["bins"][:, :3] != 254]
    counts = np.bincount(held, minlength=254)[:254]
    assert counts.min() > 0.5 * counts.mean()  # near-uniform over 254 bins
    levels = gen.column_levels(cell["config"])
    assert levels == [255, 3, 24, 105, 4, 255]
    got = sorted(tuple(np.sort(np.bincount(data["bins"][:, f],
                                           minlength=255))[::-1] / n)
                 for f in range(3, 9))
    want = sorted(tuple(np.pad(np.sort(gen.level_shares(c, 1.1, 254))[::-1],
                               (0, 255 - lv)))
                  for c, lv in zip(TOY_LEVELS, levels))
    np.testing.assert_allclose(got, want, atol=0.01)
    # a column of 10 million levels keeps 254 and a rest level of 44%
    rest = gen.level_shares(10131227, 1.1, 254)
    assert len(rest) == 255 and 0.40 < rest[-1] < 0.48
    assert abs(rest.sum() - 1.0) < 1e-12
    # a bin's index says nothing of its level's size: the largest level of
    # a 255-bin column is not always bin 0
    at = gen.level_bins(cell["config"])
    assert sorted(at[0]) == list(range(255)) and list(at[1]) != [0, 1, 2]


def test_the_program_s_mapper_keeps_the_drawn_bins(toy_cell):
    """Category ids rise with the bin, so a drawn bin is the program's bin
    and "equal keys in bin order" is one order on both sides."""
    data = stages.make_data(toy_cell, 9)
    binner, lut = stages.fit_mappers(
        data, toy_cell["config"]["params"],
        toy_cell["config"]["categorical_features"])
    assert [m.is_categorical for m in binner.mappers] == [False] * 3 + [
        True] * 6
    assert list(binner.missing_bin_per_feature) == [254] * 3 + [-1] * 6
    drawn = np.unique(data["bins"][:, 3:])
    for f in range(9):
        used = np.unique(data["bins"][:, f])
        np.testing.assert_array_equal(lut[used, f], used)
    assert drawn.max() == 254
    np.testing.assert_array_equal(stages.program_bins(data, lut),
                                  data["bins"])


def test_a_program_that_gathers_by_row_is_refused_before_any_draw(
        toy_cell, monkeypatch):
    from lightgbm_tpu.utils import profiling

    gen = toy_cell["datagen"]
    assert gen.CAT_SCOPE in profiling.DEVICE_PHASES
    gen.refuse_a_gathering_partition()
    monkeypatch.setattr(profiling, "DEVICE_PHASES", tuple(
        s for s in profiling.DEVICE_PHASES if s != gen.CAT_SCOPE))
    monkeypatch.setattr(gen, "value_table", None)  # no draw
    with pytest.raises(RuntimeError, match="gathers a category mask by row"):
        stages.make_data(toy_cell, 1)


# ---------------------------------------------------------------------------
# the reference, by hand
# ---------------------------------------------------------------------------

HAND = dict(objective="binary", num_leaves=2, learning_rate=0.1, max_bin=8,
            min_data_in_leaf=1, min_sum_hessian_in_leaf=0.0, lambda_l2=0.0,
            min_gain_to_split=0.0, cat_smooth=10.0, cat_l2=10.0,
            max_cat_threshold=32, max_cat_to_onehot=4)


def hand_tree(ref, column, label, categorical):
    """One tree of two leaves on one column of twelve rows (and a constant
    column of the other kind beside it)."""
    column = np.asarray(column, np.uint8)
    bins = np.stack([column, np.zeros_like(column)], axis=1)
    if categorical:
        params = dict(HAND, categorical_feature=[0])
    else:
        params = dict(HAND, categorical_feature=[1])
    out = ref.train(bins, np.asarray(label, np.float32), params, n_trees=1,
                    leaf_tile=8, row_block=4)
    (tree,) = out["trees"]
    assert tree["num_leaves"] == 2 and len(tree["splits"]) == 1
    return tree["splits"][0], tree


def test_many_against_many_takes_the_levels_a_reader_would(ref):
    """Five levels (more than ``max_cat_to_onehot``), those of levels 1 and 3
    all clicks, the others none: ordered by gradient over hessian the two
    come first, and a prefix of two separates the labels.  The descending
    order's prefix of three is the same partition and ties, so the ascending
    one stands: levels 1 and 3 on the left."""
    level = [0, 0, 1, 1, 1, 2, 2, 3, 3, 4, 4, 4]
    label = [float(v in (1, 3)) for v in level]
    split, tree = hand_tree(ref, level, label, categorical=True)
    assert split["categorical"] and split["feature"] == 0
    assert split["left_bins"] == [1, 3] and split["bin"] == 1
    assert split["left_count"] == 5 and not split["default_left"]
    assert sorted(tree["leaf_count"]) == [5, 7]
    # by hand: p = 5/12, g = p - y, h = p (1 - p) a row; cat_l2 in the gain
    p = 5 / 12
    h = p * (1 - p)

    def gain(g_sum, rows):
        return g_sum ** 2 / (rows * h + 10.0)

    want = (gain(5 * (p - 1), 5) + gain(7 * p, 7)
            - gain(5 * (p - 1) + 7 * p, 12))
    assert split["gain"] == pytest.approx(want, rel=1e-5)


def test_one_against_the_rest_at_three_levels(ref):
    level = [0, 0, 0, 0, 1, 1, 1, 1, 2, 2, 2, 2]
    label = [0, 0, 1, 0, 0, 1, 0, 0, 1, 1, 1, 1]
    split, tree = hand_tree(ref, level, label, categorical=True)
    assert split["categorical"] and split["left_bins"] == [2]
    assert split["bin"] == 2 and split["left_count"] == 4
    assert sorted(tree["leaf_count"]) == [4, 8]


def test_a_missing_integer_goes_where_the_gain_is_larger(ref):
    """Bin 7 of 8 is the missing bin.  Clicks in bin 0 and among the missing,
    none in bins 1 to 3: the split at bin 0 with the missing rows on the
    left separates the labels, and no split with them on the right does."""
    value = [0, 0, 0, 1, 1, 2, 2, 3, 3, 7, 7, 7]
    label = [float(v in (0, 7)) for v in value]
    split, tree = hand_tree(ref, value, label, categorical=False)
    assert not split["categorical"] and split["feature"] == 0
    assert split["bin"] == 0 and split["default_left"]
    assert split["left_count"] == 6 and split["left_bins"] == []
    # the missing rows alone on one side: the threshold after the last bin
    # that holds a value, the missing rows on the right
    value = [0, 0, 0, 1, 1, 2, 2, 3, 3, 7, 7, 7]
    label = [float(v == 7) for v in value]
    split, tree = hand_tree(ref, value, label, categorical=False)
    assert split["bin"] == 3 and not split["default_left"]
    assert split["left_count"] == 9 and sorted(tree["leaf_count"]) == [3, 9]


# ---------------------------------------------------------------------------
# the toy cell through the stages
# ---------------------------------------------------------------------------

def test_the_toy_cell_is_correct_and_takes_both_kinds_of_split(toy_cell,
                                                               tmp_path):
    run, program = stages.drive(toy_cell, 2_500_000_001, 0.3,
                                str(tmp_path / "cache"))
    assert run["flags"]["use_fast"] and run["flags"]["leaf_tile"] == 8
    g = program[0]._gbdt
    assert g._split_params.cat_features == tuple(range(3, 9))
    trees = list(g.models)[:2]
    assert all(0 < t.num_cat < t.num_leaves - 1 for t in trees)
    del program, g
    stages.free_program()
    correct, compared = stages.judge(toy_cell, run)
    assert correct, compared
    assert set(compared) == LIMIT_NAMES
    # the reference grew the same kinds of split, a missing one sent left too
    ref = stages.run_reference(toy_cell, run["data"], 1)
    splits = ref["trees"][0]["splits"]
    assert sum(s["categorical"] for s in splits) == trees[0].num_cat
    assert any(s["default_left"] for s in splits)
    # without the categorical columns named it is another model
    bare = dict(toy_cell, config=dict(toy_cell["config"], params={
        k: v for k, v in toy_cell["config"]["params"].items()
        if k != "categorical_feature"}))
    other = stages.run_reference(bare, run["data"], run["reference_trees"])
    ok, compared = compare.judge(
        compare.numbers(run["program"], other, run["data"]),
        toy_cell["config"]["limits"])
    assert not ok, compared


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_one_precision_down_is_not_correct(toy_cell, seed):
    """The reference with one bfloat16 term a gradient, put in the program's
    place, fails at least one number; with three it passes them all."""
    data = stages.make_data(toy_cell, seed)
    ref = stages.run_reference(toy_cell, data, 2)
    limits = toy_cell["config"]["limits"]
    same = stages.run_reference(toy_cell, data, 2)
    ok, _ = compare.judge(compare.numbers(same, ref, data), limits)
    assert ok
    low = stages.run_reference(toy_cell, data, 2, payload_terms=1)
    ok, compared = compare.judge(compare.numbers(low, ref, data), limits)
    assert not ok, compared


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_planted_fault_is_not_correct_in_the_click_cell(toy_cell, tmp_path,
                                                          fault):
    run, program = stages.drive(toy_cell, 2_500_000_002, 0.2,
                                str(tmp_path / "cache"),
                                break_program=faults.FAULTS[fault])
    del program
    stages.free_program()
    correct, compared = stages.judge(toy_cell, run)
    assert not correct, compared
