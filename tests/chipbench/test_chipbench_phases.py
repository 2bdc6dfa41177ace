"""The three per-layer metrics that read the program's own counters and
spans, through ``readers.read_all`` as a run reads them, and the line
``tools/phases.py`` makes of the two reductions of one trace."""

import copy

import pytest

from chipbench.harness import loader, readers
from lightgbm_tpu.obs import metrics as obs
from lightgbm_tpu.obs import trace as obs_trace

NEW = ("hist_passes_per_tree", "hist_stream_useful_pct",
       "update_host_ms_per_tree")
CTX = {"spans": {}, "counters": {}, "window": {"seconds": 2.0, "trees": 3},
       "least_s": [], "trace": None, "traced": range(0)}


def read_new(ctx=CTX, cell="higgs-train"):
    bench = loader.load_benchmark()
    specs = [s for s in loader.load_layer_metrics() if s["name"] in NEW]
    assert len(specs) == 3
    return readers.read_all(specs, ctx, cell, bench["per_layer"])


def test_counter_metrics_read_the_programs_registry(program_state):
    obs.counter("train_boost_rounds_total").inc(4)
    obs.counter("train_hist_passes_total").inc(136)
    obs.counter("train_hist_rows_streamed_total").inc(136 * 1000)
    obs.counter("train_hist_rows_needed_total").inc(15640)
    got = read_new()
    assert got["hist_passes_per_tree"] == {"value": 34.0, "unit": "count"}
    assert got["hist_stream_useful_pct"] == {"value": 11.5, "unit": "%"}
    # a cell BENCHMARK.json does not list for them reports none
    assert read_new(cell="some-later-cell") == {}


def test_host_span_metric_is_the_mean_of_the_windows_last_spans(
        program_state):
    for dur in (9.0, 0.004, 0.006, 0.008):  # the first: a warm tree
        obs_trace.record_span("boost_round", dur)
    got = read_new()
    assert got["update_host_ms_per_tree"]["value"] == pytest.approx(6.0)
    assert got["update_host_ms_per_tree"]["unit"] == "ms"
    # fewer spans than the window's trees: nothing to read
    assert "update_host_ms_per_tree" not in read_new(
        dict(CTX, window={"seconds": 2.0, "trees": 5}))


@pytest.mark.parametrize("case", ["another_grower", "telemetry_off"])
def test_new_readers_say_nothing_where_there_is_nothing_to_read(
        program_state, case):
    obs.counter("train_boost_rounds_total").inc(3)
    if case == "telemetry_off":
        obs.counter("train_hist_passes_total").inc(30)
        obs.counter("train_hist_rows_streamed_total").inc(30000)
        obs.counter("train_hist_rows_needed_total").inc(9000)
        for _ in range(3):
            obs_trace.record_span("boost_round", 0.005)
        assert len(read_new()) == 3
        obs.set_enabled(False)
        obs_trace.reset_trace()  # a run with telemetry off records no span
    # another grower leaves the pass counters at 0 and absent alike
    assert read_new() == {}


def test_a_later_cell_joins_every_committed_metric_by_appended_names(
        toy_roots, program_state):
    """What ``test_chipbench_loader`` checks for the first seven metrics,
    with the count taken from ``BENCHMARK.json``: a second cell on a
    configuration that is there reads every committed metric once it is
    appended to their lists."""
    bench = copy.deepcopy(loader.load_benchmark())
    later = {"name": "higgs-train-short", "config": "higgs",
             "traffic": "short-train", "chips": 1, "why": "a later cell"}
    specs = loader.load_layer_metrics()
    assert len(specs) == len(bench["per_layer"])
    assert all("workloads" not in s for s in specs)
    assert loader.load_cell(later, toy_roots)["config"]["rows"] == 10500000
    obs.counter("train_boost_rounds_total").inc(4)
    obs.counter("train_hist_passes_total").inc(120)
    obs.counter("train_hist_rows_streamed_total").inc(120000)
    obs.counter("train_hist_rows_needed_total").inc(16000)
    for _ in range(4):
        obs_trace.record_span("boost_round", 0.002)
    ctx = dict(CTX, window={"seconds": 2.0, "trees": 4}, least_s=[0.01] * 4,
               counters={"compiles_in_window": 0}, traced=range(1, 3),
               spans={"first_update_s": 2.0, "datagen_s": 1.0,
                      "mappers_s": 0.1, "dataset_file_s": 2.0,
                      "dataset_load_s": 0.5},
               trace={"chips": 1, "busy_s": 3.0, "window_s": 4.0,
                      "events": [["_hist_pallas_raw.3", 0, 500_000_000]]})
    assert readers.read_all(specs, ctx, later["name"],
                            bench["per_layer"]) == {}
    for m in bench["per_layer"]:
        m["workloads"].append(later["name"])
    got = readers.read_all(specs, ctx, later["name"], bench["per_layer"])
    assert sorted(got) == sorted(m["name"] for m in bench["per_layer"])
    assert got["hist_kernel_ms_per_tree"]["value"] == 250.0
    assert got["hist_passes_per_tree"]["value"] == 30.0


# ---------------------------------------------------------------------------
# tools/phases.py
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tool():
    return loader.load_module(loader.CHIPBENCH / "tools" / "phases.py")


def test_the_tools_line_checks_the_programs_reduction_against_the_harness(
        tool, program_state):
    """Two traced trees of a 28-feature cell: the program's reduction says
    where 10 s of device time went, the harness's says how long the kernel
    ran and the device was busy, and the counters say 30 passes a tree."""
    from lightgbm_tpu.utils import profiling

    seconds = dict.fromkeys(profiling.DEVICE_PHASES, 0.0)
    seconds.update({"hist.kernel": 6.0, "hist.payload": 2.0,
                    "hist.rowpad": 1.0, "grow.partition": 0.5})
    phases = {"chips": 1, "trees": 2, "host_steps": 1,
              "grower_modules": ["jit__grow_fast_impl"], "busy_s": 10.0,
              "phases": seconds, "unscoped_s": 0.25,
              "unscoped_ops": [["copy", 0.25]],
              "outside_grower": {"jit_gather": 0.25}}
    events = [[f"_hist_pallas_raw.{i}", i * 1e8, 1e8] for i in range(60)]
    harness = {"chips": 1, "busy_s": 10.0, "window_s": 10.0, "events": events}
    n_rows, trees = 1000, 5  # three warm trees and the window's two
    obs.counter("train_boost_rounds_total").inc(trees)
    obs.counter("train_hist_passes_total").inc(30 * trees)
    obs.counter("train_hist_rows_streamed_total").inc(30 * trees * n_rows)
    obs.counter("train_hist_rows_needed_total").inc(3900 * trees)
    for _ in range(trees):
        obs_trace.record_span("boost_round", 0.004)
    ctx = dict(CTX, trace=harness, traced=range(0, 2),
               window={"seconds": 10.0, "trees": 2}, least_s=[0.001] * 2,
               counters={"compiles_in_window": 0})
    run = {"n_features": 28, "n_rows": n_rows, "tree_rows": [3900] * trees}
    out = tool.report(phases, harness, ctx, run,
                      loader.load_benchmark()["per_layer"], "higgs-train")
    assert out["ms_per_tree"]["hist.kernel"] == 3000.0
    assert out["share_pct"]["hist.payload"] == 20.0
    assert out["share_pct"]["outside_grower"] == 2.5
    assert out["grower_scoped_pct"] == pytest.approx(100 * 9.5 / 9.75)
    checks = out["checks"]
    assert checks["kernel"]["gap_pct"] == pytest.approx(0.0)
    assert checks["sum"]["gap_pct"] == pytest.approx(0.0)
    # 60 kernel events over one call a pass and two traced trees: 30 a tree
    assert checks["passes"]["gap_pct"] == pytest.approx(0.0)
    assert checks["useful"]["counter_pct"] == pytest.approx(13.0)
    assert checks["useful"]["gap_pct"] == pytest.approx(0.0)
    assert out["metrics"]["update_host_ms_per_tree"] == pytest.approx(4.0)
    # sixteen calls a pass at 2000 features: the same events are 1.875 passes
    wide = tool.report(phases, harness, ctx, dict(run, n_features=2000),
                       loader.load_benchmark()["per_layer"], "epsilon-train")
    assert wide["checks"]["passes"][
        "kernel_events_per_traced_tree_per_call"] == pytest.approx(1.875)
