"""The ranking deployment ``istella``: its generator, its plain reference
against the program's lambdas, and a toy copy of the cell through the
stages, added as files under a temporary root."""

import json
import re

import numpy as np
import pytest

from chipbench.harness import compare, faults, loader, stages

ISTELLA = loader.CHIPBENCH / "configs" / "istella.json"
LIMIT_NAMES = {"loss_gap", "update1_gap", "updateK_gap", "gain_gap",
               "root_gain_gap", "root_hess_gap", "rows_gap", "leaves_gap"}
# what sound runs read here (CPU, toy size): 4e-7 at most on every number;
# the reference on other query groups reads 3e-3 or more on six of them
TOY_LIMITS = dict.fromkeys(LIMIT_NAMES, 2e-5) | {"rows_gap": 0.0,
                                                 "leaves_gap": 0.0}


def toy_istella() -> dict:
    cfg = loader.load_json(ISTELLA)
    cfg.update(name="toy-istella", rows=6000, features=6, queries=40)
    cfg["datagen_params"].update(query_longest=300, informative=6)
    cfg["params"].update(num_leaves=15, min_sum_hessian_in_leaf=1.0)
    cfg["grower"]["leaf_tile"] = 8
    cfg["control"]["grower"]["leaf_tile"] = 16
    cfg["limits"] = dict(TOY_LIMITS)
    return cfg


@pytest.fixture
def toy_cell(toy_roots):
    (toy_roots[0] / "configs" / "toy-istella.json").write_text(
        json.dumps(toy_istella()))
    return loader.load_cell({"name": "toy-istella-train",
                             "config": "toy-istella",
                             "traffic": "short-train", "chips": 1}, toy_roots)


# ---------------------------------------------------------------------------
# the committed files
# ---------------------------------------------------------------------------

def test_the_configuration_states_its_source_its_cut_and_what_it_assumes():
    cfg = loader.load_json(ISTELLA)
    assert (cfg["rows"], cfg["features"], cfg["queries"]) == (
        4_883_750, 220, 15_480)
    assert cfg["reduced"] == ["queries", "rows"]
    assert "15,480 of 23,219" in cfg["reduced_why"]
    assert "4,883,750 of 7,325,625" in cfg["reduced_why"]
    assert "360 s" in cfg["reduced_why"]
    assert "Istella LETOR" in cfg["source"] and len(cfg["source"]) <= 200
    p = cfg["params"]
    assert p["objective"] == "lambdarank" and p["eval_at"] == [1, 3, 5, 10]
    assert (p["num_leaves"], p["learning_rate"], p["min_data_in_leaf"],
            p["min_sum_hessian_in_leaf"], p["max_bin"]) == (255, 0.1, 1,
                                                            100.0, 255)
    assert (p["lambdarank_truncation_level"], p["lambdarank_norm"],
            p["sigmoid"]) == (30, True, 1.0)
    assert (p["tree_growth_mode"], p["hist_precision"], p["fused_training"],
            p["use_quantized_grad"]) == ("rounds", "f32", False, False)
    assert (cfg["loss"], cfg["loss_at"]) == ("ndcg", 10)
    assert cfg["control"]["params"] == {"hist_precision": "bf16"}
    assert set(cfg["limits"]) == LIMIT_NAMES
    assert cfg["xla_flags"] == loader.load_json(
        loader.CHIPBENCH / "configs" / "higgs.json")["xla_flags"]
    for key in ("rows, features, queries, grades", "query lengths", "grades",
                "data", "leaf_tile"):
        assert key in cfg["assumed"]
    g = cfg["datagen_params"]
    assert (g["query_sigma"], g["query_longest"]) == (0.8, 2048)
    assert isinstance(g["table_seed"], int)
    assert g["grade_shares"] == [0.90, 0.04, 0.03, 0.02, 0.01]


def test_the_leaf_tile_is_the_one_the_program_recommends_at_this_shape():
    from lightgbm_tpu.ops.hist_pallas import recommended_leaf_tile

    cfg = loader.load_json(ISTELLA)
    for side in (cfg, cfg["control"]):
        assert side["grower"]["leaf_tile"] == recommended_leaf_tile(
            cfg["params"]["max_bin"], cfg["features"],
            cfg["params"]["num_leaves"],
            hist_precision=side["grower"]["hist_precision"])


def test_the_cell_is_named_as_the_issue_names_it():
    bench = loader.load_benchmark()
    cell = loader.find_workload(bench, "istella-train")
    assert cell == dict(cell, config="istella", traffic="steady-train",
                        chips=1)
    loaded = loader.load_cell(cell)
    assert loaded["datagen"].__file__.endswith("ranked_queries.py")
    assert loaded["reference"].__file__.endswith("lambdarank_rounds.py")
    reports = [m["name"] for m in bench["per_layer"]
               if "istella-train" in m["workloads"]]
    assert "tree_mfu" in reports and "hist_roofline" in reports
    assert len(reports) >= 10


def test_the_reference_imports_nothing_of_the_program():
    for name in ("lambdarank_rounds", "leafwise_rounds"):
        text = (loader.CHIPBENCH / "reference" / f"{name}.py").read_text()
        assert not re.search(r"^\s*(from|import)\s+lightgbm_tpu", text,
                             re.MULTILINE)
        assert not re.search(r"^\s*(from|import)\s+chipbench", text,
                             re.MULTILINE)


# ---------------------------------------------------------------------------
# the generator
# ---------------------------------------------------------------------------

def test_ranked_queries_is_deterministic_in_the_seed(toy_cell):
    a = stages.make_data(toy_cell, 3_400_000_123)
    b = stages.make_data(toy_cell, 3_400_000_123)
    c = stages.make_data(toy_cell, 3_400_000_124)
    for key in ("bins", "label", "group", "values"):
        np.testing.assert_array_equal(a[key], b[key])
    assert not np.array_equal(a["bins"], c["bins"])
    assert not np.array_equal(a["group"], c["group"])


def test_every_seed_poses_the_same_problem_in_another_order(toy_cell):
    """One table from ``table_seed``; the seed orders its queries, each
    one's rows kept together, and its columns: the same rows, labels and
    query lengths under every seed, so the work a tree takes stays put."""
    a = stages.make_data(toy_cell, 11)
    b = stages.make_data(toy_cell, 12)
    assert sorted(a["group"]) == sorted(b["group"])
    f = a["bins"].shape[1]

    def rows_of(data):  # a row whatever its columns' order, with its label
        key = np.sort(data["bins"].astype(np.int64), axis=1) @ (
            256 ** np.arange(f))
        return np.sort(key * 8 + data["label"].astype(np.int64))

    np.testing.assert_array_equal(rows_of(a), rows_of(b))
    # a query's rows stay together and in their order: the first query of
    # one seed lies somewhere in the other, column for column
    col = np.argsort(a["bins"].sum(axis=0), kind="stable")
    col_b = np.argsort(b["bins"].sum(axis=0), kind="stable")
    n0 = int(a["group"][0])
    first = a["bins"][:n0][:, col]
    starts = np.cumsum(b["group"]) - b["group"]
    assert any(n == n0 and np.array_equal(b["bins"][s:s + n][:, col_b], first)
               for s, n in zip(starts, b["group"]))
    other = dict(toy_cell["config"], datagen_params=dict(
        toy_cell["config"]["datagen_params"], table_seed=5))
    c = toy_cell["datagen"].generate(other, 11)
    assert sorted(c["group"]) != sorted(a["group"])


@pytest.mark.parametrize("rows,queries,longest", [
    (6000, 40, 300), (6000, 40, 150), (4000, 3900, 2048), (700, 3, 256)])
def test_query_lengths_sum_to_the_rows_inside_the_clip(rows, queries,
                                                       longest):
    mod = loader.load_module(loader.CHIPBENCH / "datagen"
                             / "ranked_queries.py")
    group = mod.draw_group(rows, queries, 0.8, longest,
                           np.random.default_rng(5))
    assert group.dtype == np.int64 and len(group) == queries
    assert int(group.sum()) == rows
    assert group.min() >= 1 and group.max() <= longest
    with pytest.raises(ValueError, match="cannot hold"):
        mod.draw_group(queries * longest + 1, queries, 0.8, longest,
                       np.random.default_rng(5))


def test_a_program_without_the_rank_scopes_is_refused_before_any_draw(
        toy_cell, monkeypatch):
    """PR 32's program pads every query to the longest: two trees of the
    cell's table in a 20 s window, which ``WindowTracer`` cannot trace.  It
    gets no run under either flag; it is told by the device scopes of the
    bucketed step."""
    from lightgbm_tpu.utils import profiling

    gen = toy_cell["datagen"]
    assert set(gen.RANK_SCOPES) <= set(profiling.DEVICE_PHASES)
    gen.refuse_a_padded_layout()
    monkeypatch.setattr(profiling, "DEVICE_PHASES", tuple(
        s for s in profiling.DEVICE_PHASES if not s.startswith("rank.")))
    monkeypatch.setattr(gen.quantile_bins, "value_table", None)  # no draw
    with pytest.raises(RuntimeError, match="pads every query to the longest"):
        stages.make_data(toy_cell, 1)


def test_grades_hold_their_shares_and_the_bins_are_near_uniform(toy_cell):
    data = stages.make_data(toy_cell, 77)
    n = toy_cell["config"]["rows"]
    assert data["label"].dtype == np.float32 and data["bins"].dtype == np.uint8
    shares = np.bincount(data["label"].astype(int), minlength=5) / n
    np.testing.assert_allclose(shares, [0.90, 0.04, 0.03, 0.02, 0.01],
                               atol=2 / n)
    assert int(data["group"].sum()) == n and data["group"].max() <= 300
    counts = np.bincount(data["bins"].ravel(), minlength=255)
    assert counts.min() > 0.5 * counts.mean()
    # five grades: every query of more than five rows holds ties in the label
    assert (data["group"] > 5).any()


# ---------------------------------------------------------------------------
# the reference against the program
# ---------------------------------------------------------------------------

def test_the_reference_s_lambdas_equal_the_program_s(toy_cell):
    import jax.numpy as jnp

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objectives import LambdarankNDCG

    data = stages.make_data(toy_cell, 31)
    params = toy_cell["config"]["params"]
    ref = toy_cell["reference"]
    lambdas = ref.Lambdas(data["label"], data["group"], params,
                          query_block=16)
    obj = LambdarankNDCG(Config(**{k: params[k] for k in (
        "objective", "lambdarank_truncation_level", "lambdarank_norm",
        "sigmoid")}))
    qb = np.concatenate([[0], np.cumsum(data["group"])])
    obj.set_query(qb, data["label"])
    np.testing.assert_allclose(
        ref.inverse_max_dcg(data["label"].astype(np.float64), data["group"],
                            ref.label_gain(params), 30),
        obj.inverse_max_dcg, rtol=1e-12)
    rng = np.random.default_rng(2)
    for score in (np.zeros(len(qb) and qb[-1], np.float32),
                  np.round(rng.standard_normal(qb[-1]), 1).astype(
                      np.float32)):
        want_g, want_h = lambdas(jnp.asarray(score))
        g, h = obj.get_gradients(jnp.asarray(score), None, None)
        for got, want in ((g, want_g), (h, want_h)):
            want = np.asarray(want)
            assert np.abs(want).max() > 0
            np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                                       atol=2e-6 * np.abs(want).max())


def test_the_toy_cell_is_correct_and_not_with_other_groups(toy_cell,
                                                           tmp_path):
    run, program = stages.drive(toy_cell, 2_500_000_001, 0.3,
                                str(tmp_path / "cache"))
    assert run["flags"]["use_fast"] and not run["flags"]["fused_built"]
    np.testing.assert_array_equal(program[1].get_group(),
                                  run["data"]["group"])
    assert program[0]._gbdt.objective.rank_work[0] == 6000
    del program
    stages.free_program()
    correct, compared = stages.judge(toy_cell, run)
    assert correct, compared
    assert set(compared) == LIMIT_NAMES
    # the reference on the same rows in other query groups is another model
    other = dict(run["data"], group=np.random.default_rng(0).permutation(
        run["data"]["group"]))
    ref = stages.run_reference(toy_cell, other, run["reference_trees"])
    ok, compared = compare.judge(
        compare.numbers(run["program"], ref, run["data"],
                        **compare.loss_named(toy_cell["config"])),
        toy_cell["config"]["limits"])
    assert not ok, compared


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_a_planted_fault_is_not_correct_in_the_ranking_cell(toy_cell,
                                                            tmp_path, fault):
    run, program = stages.drive(toy_cell, 2_500_000_002, 0.2,
                                str(tmp_path / "cache"),
                                break_program=faults.FAULTS[fault])
    del program
    stages.free_program()
    correct, compared = stages.judge(toy_cell, run)
    assert not correct, compared
