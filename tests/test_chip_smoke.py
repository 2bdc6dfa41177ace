"""chip_smoke.py off the chip: it refuses the CPU backend, every leg runs
at toy size when called directly, and the two histogram kernels meet the
numpy oracle through the Pallas TPU interpreter (the only place tier-1
executes them: tests/test_hist_strategies.py only eval_shapes)."""

import json

import jax
import pytest
from jax.experimental.pallas import tpu as pltpu

import chip_smoke

# the CPU backend takes the rounds grower only when asked; on the chip it
# is the default, so the toy legs drive the grower the chip legs drive
ROUNDS = {"tree_growth_mode": "rounds"}


@pytest.fixture(scope="module")
def narrow():
    return chip_smoke.leg_train_narrow(255, n_rows=20_000, **ROUNDS)


def test_main_refuses_cpu_backend(capsys):
    assert chip_smoke.main() != 0
    captured = capsys.readouterr()
    assert captured.out == ""  # no result line, nothing that parses as one
    assert "no TPU" in captured.err


def test_last_stdout_line_is_the_contract_object(monkeypatch, capsys):
    """The driver parses the last line and accepts exactly these keys; the
    per-leg report goes on the line before it."""
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "device_block", lambda: dev)
    monkeypatch.setattr(chip_smoke, "leg_kernels", lambda: {})
    monkeypatch.setattr(chip_smoke, "leg_train_narrow",
                        lambda max_bin: ({"max_bin": max_bin}, None, None))
    monkeypatch.setattr(chip_smoke, "leg_train_wide", lambda: ({}, None))
    monkeypatch.setattr(chip_smoke, "leg_predict_serve", lambda bst: {})
    monkeypatch.setattr(chip_smoke, "leg_multichip", lambda *a: {})
    monkeypatch.setattr(chip_smoke, "leg_probes", lambda: {})
    monkeypatch.setattr(chip_smoke, "leg_categorical", lambda: {})
    monkeypatch.setattr(chip_smoke, "check_no_fallback", lambda legs: None)
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": dev}
    report = json.loads(lines[-2].removeprefix("[chip_smoke] report "))
    assert {"versions", "cache_dir", "cache_entries", "legs"} <= set(report)


def test_kernels_match_oracle_in_interpret_mode():
    with pltpu.force_tpu_interpret_mode():
        out = chip_smoke.leg_kernels(
            n_rows=512, shapes=((28, 256, 31), (130, 64, 255)))
    assert out["28x256"]["leaf_tile_f32"] == 8
    assert out["130x64"]["leaf_tile_int8"] == 20


def test_train_narrow_leg(narrow):
    out, bst, _ = narrow
    assert out["auc"] > chip_smoke.NARROW_AUC_FLOOR
    assert out["flags"]["use_fast"] and out["flags"]["fused_built"]
    assert not out["flags"]["on_tpu"] and not out["flags"]["fused_disabled"]
    json.dumps(out)


def test_train_wide_leg():
    out, _ = chip_smoke.leg_train_wide(
        n_rows=2000, n_features=160, num_leaves=15, **ROUNDS)
    assert out["leaves_per_tree"] == [15, 15, 15]
    assert not out["flags"]["quantized"]  # the int8 default is the chip's
    json.dumps(out)


def test_predict_serve_leg(narrow):
    out = chip_smoke.leg_predict_serve(narrow[1], n_predict=6000,
                                       n_requests=16)
    assert out["serve_requests"] == 16
    assert out["predict_rows_off_host_walk"] == 0


def test_multichip_leg(narrow):
    ref_out, ref_bst, valid = narrow
    out = chip_smoke.leg_multichip(ref_bst, ref_out, valid, n_rows=20_000,
                                   **ROUNDS)
    assert out["devices"] == 8 and out["shard_rows"] == 2500
    assert out["trees_equal_to_one_chip"] >= 1
    assert out["flags"]["use_fast_dp"]


def test_probes_leg():
    out = chip_smoke.leg_probes(dispatches=20, pulls=5, matmul_dim=128,
                                matmul_steps=4)
    assert set(out) >= {"dispatch_enqueue_us", "ready_scalar_pull_us",
                        "block_until_ready_honest"}


def test_categorical_leg():
    """Both kinds of column at toy size: every leaf holds the rows it
    counted, and so it does under the form of before PR 36, which is wrong
    only as XLA:TPU compiled it."""
    shapes = {"2+4": (2, (3, 24, 105, 255), chip_smoke.CLICK_CELL),
              "3+2": (3, (4, 255), {})}
    out = chip_smoke.leg_categorical(n_rows=8000, shapes=shapes, rounds=3,
                                     num_leaves=15, **ROUNDS)
    assert set(out) == {"2+4", "3+2", "gather_form_leaves_off"}
    assert out["gather_form_leaves_off"] == 0
    assert out["2+4"]["leaves_off_their_rows"] == 0
    assert not out["2+4"]["flags"]["fused_built"]
    json.dumps(out)


def test_categorical_leg_fails_where_rows_go_by_the_other_order(monkeypatch):
    """The fault PR 36 found on the chip, planted: the stored mask is the
    other order's, the counts are not."""
    sound = chip_smoke.split_mod.winner_cat_mask
    monkeypatch.setattr(
        chip_smoke.split_mod, "winner_cat_mask",
        lambda asc, desc, slot, v, t: sound(desc, asc, slot, v, t))
    jax.clear_caches()
    try:
        with pytest.raises(AssertionError, match="2\\+4"):
            chip_smoke.leg_categorical(
                n_rows=8000, rounds=3, num_leaves=15, **ROUNDS,
                shapes={"2+4": (2, (3, 24, 105, 255), chip_smoke.CLICK_CELL)})
    finally:
        jax.clear_caches()


def test_check_no_fallback_rejects_a_fired_net(narrow):
    """What main asserts after the legs: CPU flags are a failure, and so is
    a disabled fused step on an otherwise clean set of legs."""
    flags = dict(narrow[0]["flags"], on_tpu=True)
    wide = dict(flags, quantized=True, fused_built=False,
                fused_eligible=False)
    legs = {"train_narrow_255": {"flags": flags},
            "train_narrow_63": {"flags": flags},
            "train_wide": {"flags": wide},
            "multichip": {"skipped": "1 device"}}
    chip_smoke.check_no_fallback(legs)
    with pytest.raises(AssertionError):
        chip_smoke.check_no_fallback(
            dict(legs, train_narrow_63={"flags": narrow[0]["flags"]}))
    with pytest.raises(AssertionError):
        chip_smoke.check_no_fallback(dict(legs, train_narrow_255={
            "flags": dict(flags, fused_disabled=True)}))
