"""``hist_product_useful_pct``: the rows a tree needed over the rows the
histogram kernel put through its one-hot product, read through
``readers.read_all`` as a run reads it."""

import pytest

from chipbench.harness import loader, readers
from lightgbm_tpu.obs import metrics as obs
from lightgbm_tpu.ops import hist_pallas

NAME = "hist_product_useful_pct"
CTX = {"spans": {}, "counters": {}, "window": {"seconds": 2.0, "trees": 3},
       "least_s": [], "trace": None, "traced": range(0)}


@pytest.fixture
def program_state():
    def empty():
        obs.set_enabled(obs.DEFAULT_ENABLED)
        obs.reset()

    empty()
    yield
    empty()


def read(cell="higgs-train"):
    bench = loader.load_benchmark()
    specs = [s for s in loader.load_layer_metrics() if s["name"] == NAME]
    assert len(specs) == 1
    return readers.read_all(specs, CTX, cell, bench["per_layer"])


def test_it_is_the_last_entry_of_the_benchmark_and_lists_both_cells():
    entry = loader.load_benchmark()["per_layer"][-1]
    assert entry["name"] == NAME and entry["moves"] == "trees_per_s"
    assert entry["workloads"] == ["higgs-train", "epsilon-train"]
    assert entry["layer"] == "histogram kernel: ops/hist_pallas.py"


@pytest.mark.parametrize("cell", ["higgs-train", "epsilon-train"])
def test_needed_rows_over_the_rows_of_the_blocks_multiplied(program_state,
                                                            cell):
    obs.counter("train_hist_rows_needed_total").inc(39_000)
    obs.counter("train_hist_blocks_multiplied_total").inc(500)
    got = read(cell)[NAME]
    assert got["unit"] == "%"
    assert got["value"] == pytest.approx(
        100.0 * 39_000 / (500 * hist_pallas.SUB_BLOCK))
    assert read("some-later-cell") == {}


@pytest.mark.parametrize("case", ["no_kernel_ran", "a_program_without_it"])
def test_without_a_count_of_blocks_every_row_streamed_was_multiplied(
        program_state, monkeypatch, case):
    """The einsum route and the program before the kernel packed its rows
    put every row they stream through the product: the metric then reads
    what ``hist_stream_useful_pct`` reads."""
    obs.counter("train_hist_rows_needed_total").inc(39_000)
    obs.counter("train_hist_rows_streamed_total").inc(350_000)
    if case == "no_kernel_ran":
        obs.counter("train_hist_blocks_multiplied_total").inc(0)
    else:  # the parent's side of the PR that added the metric
        obs.counter("train_hist_blocks_multiplied_total").inc(500)
        monkeypatch.delattr(hist_pallas, "SUB_BLOCK")
    assert read()[NAME]["value"] == pytest.approx(100.0 * 39 / 350)


@pytest.mark.parametrize("case", ["another_grower", "telemetry_off"])
def test_it_says_nothing_where_there_is_nothing_to_read(program_state, case):
    if case == "telemetry_off":
        obs.counter("train_hist_rows_needed_total").inc(39_000)
        obs.counter("train_hist_blocks_multiplied_total").inc(500)
        assert len(read()) == 1
        obs.set_enabled(False)
    assert read() == {}
