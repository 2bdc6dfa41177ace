"""Every stage of a run at toy size on the CPU backend: data, ``Dataset``,
window and comparison; the control and the faults come out not correct; and
``run.py``'s ``main`` on a CPU exits non-zero before any work and prints no
last line."""

import subprocess
import sys

import pytest

from chipbench.harness import compare, faults, loader, stages


def _drive(cell, tmp_path, seed=11, **kw):
    run, program = stages.drive(cell, seed, 0.3, str(tmp_path), **kw)
    del program
    stages.free_program()
    return run


def test_sound_run_is_correct_and_counts_its_trees(toy_cell, tmp_path):
    run = _drive(toy_cell, tmp_path)
    win = run["window"]
    assert win["trees"] >= 2 and win["seconds"] > 0
    assert run["flags"]["use_fast"] and run["flags"]["leaf_tile"] == 8
    assert len(run["tree_rows"]) == run["warm_trees"] + win["trees"]
    assert all(r >= run["n_rows"] for r in run["tree_rows"])
    for name in ("datagen_s", "dataset_file_s", "dataset_load_s",
                 "first_update_s", "warm_trees_s"):
        assert run["spans"][name] > 0
    correct, compared = stages.judge(toy_cell, run)
    assert correct, compared
    assert list(compared) == ["loss_gap", "update1_gap", "updateK_gap",
                              "gain_gap", "root_gain_gap", "root_hess_gap",
                              "rows_gap", "leaves_gap"]
    assert all(set(c) == {"value", "limit"} for c in compared.values())
    assert compared["rows_gap"]["value"] == 0.0
    assert compared["leaves_gap"]["value"] == 0.0
    assert run["spans"]["reference_s"] > 0


def test_grower_other_than_the_configuration_names_fails(toy_cell, tmp_path):
    run = _drive(toy_cell, tmp_path)
    stages.check_no_fallback(toy_cell, run["flags"])
    toy_cell["config"]["grower"]["leaf_tile"] = 4
    with pytest.raises(RuntimeError, match="leaf_tile"):
        stages.check_no_fallback(toy_cell, run["flags"])
    run["flags"]["fused_disabled"] = True
    with pytest.raises(RuntimeError, match="fused"):
        stages.check_no_fallback(toy_cell, run["flags"])


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_broken_timed_path_is_not_correct(toy_cell, tmp_path, fault):
    run = _drive(toy_cell, tmp_path, break_program=faults.FAULTS[fault])
    correct, compared = stages.judge(toy_cell, run)
    assert not correct, compared


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


def test_a_number_without_a_limit_is_an_error(toy_cell):
    nums = {"loss_gap": 0.0, "surprise": 0.0}
    with pytest.raises(KeyError):
        compare.judge(nums, toy_cell["config"]["limits"])


def test_nan_is_not_correct():
    ok, _ = compare.judge({"loss_gap": float("nan")}, {"loss_gap": 1.0})
    assert not ok


def test_main_on_a_cpu_exits_nonzero_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, str(loader.CHIPBENCH / "run.py"), "--workload",
         "higgs-train", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(loader.REPO)})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "runs on the chip only" in out.stderr
