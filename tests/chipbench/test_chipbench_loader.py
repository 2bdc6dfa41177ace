"""A configuration, a traffic mix and a per-layer metric are added as files,
and a cell as names in ``BENCHMARK.json``, with no edit to a file that is
there."""

import copy
import json

from chipbench.harness import loader, readers
from lightgbm_tpu.obs import metrics as obs
from lightgbm_tpu.obs import trace as obs_trace


def test_added_config_and_traffic_are_found_first(toy_roots, toy_cell):
    assert toy_cell["config"]["name"] == "toy"
    assert toy_cell["traffic"]["name"] == "short-train"
    assert toy_cell["traffic"]["warm_trees"] == 2
    # its generator and reference are the ones chipbench/ already has
    assert toy_cell["datagen"].__file__.endswith("quantile_bins.py")
    assert toy_cell["reference"].__file__.endswith("leafwise_rounds.py")
    # the traffic mix's parameters reach the training parameters
    assert toy_cell["config"]["params"]["bagging_freq"] == 0


CTX = {"spans": {"warm_trees_s": 1.5, "first_update_s": 2.0},
       "counters": {"compiles_in_window": 0},
       "window": {"seconds": 2.0, "trees": 4}, "least_s": [0.01] * 4,
       "trace": None, "traced": range(0)}


def test_a_later_cell_joins_the_committed_metrics_by_appended_names(
        toy_roots, program_state):
    """A second cell on a configuration that is there (with a traffic mix of
    its own: a pair appears once) reads every committed metric once
    ``BENCHMARK.json`` names it on their lists: no edit under
    ``chipbench/``."""
    bench = copy.deepcopy(loader.load_benchmark())
    later = {"name": "higgs-train-short", "config": "higgs",
             "traffic": "short-train", "chips": 1, "why": "a later cell"}
    bench["workloads"].append(later)
    specs = loader.load_layer_metrics()
    files = list((loader.CHIPBENCH / "layer_metrics").glob("*.json"))
    assert len(specs) == len(files) >= len(bench["per_layer"])
    assert all("workloads" not in s for s in specs)
    cell = loader.load_cell(later, toy_roots)
    assert cell["config"]["rows"] == 10500000
    assert cell["traffic"]["name"] == "short-train"
    spans = dict(CTX["spans"], datagen_s=1.0, mappers_s=0.1,
                 dataset_file_s=2.0, dataset_load_s=0.5)
    ctx = dict(CTX, spans=spans, traced=range(1, 3), trace={
        "chips": 1, "busy_s": 3.0, "window_s": 4.0,
        "events": [["_hist_pallas_raw.3", 0, 500_000_000]]})
    # what the readers of the program's own counters and spans find
    obs.counter("train_boost_rounds_total").inc(4)
    obs.counter("train_hist_passes_total").inc(120)
    obs.counter("train_hist_rows_streamed_total").inc(120000)
    obs.counter("train_hist_rows_needed_total").inc(16000)
    for _ in range(4):
        obs_trace.record_span("boost_round", 0.002)
    # not yet on the lists: it reports nothing, whatever there is to read
    assert readers.read_all(specs, ctx, later["name"],
                            bench["per_layer"]) == {}
    for m in bench["per_layer"]:
        m["workloads"].append(later["name"])
    got = readers.read_all(specs, ctx, later["name"], bench["per_layer"])
    assert sorted(got) == sorted(m["name"] for m in bench["per_layer"])
    assert got["dataset_s"]["value"] == 3.6
    assert got["device_idle_pct"]["value"] == 25.0
    assert got["hist_kernel_ms_per_tree"]["value"] == 250.0
    assert got["hist_roofline"]["value"] == 100.0 * 0.02 / 0.5
    # an entry without the key is reported by every cell
    for m in bench["per_layer"]:
        del m["workloads"]
    got = readers.read_all(specs, ctx, "any-cell", bench["per_layer"])
    assert len(got) == len(bench["per_layer"])


def test_an_added_layer_metric_is_read_with_its_own_reader(toy_roots):
    d = toy_roots[0] / "layer_metrics"
    d.mkdir()
    (d / "warm_tree_s.json").write_text(json.dumps({
        "name": "warm_tree_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "boosting loop: models/gbdt.py",
        "moves": "setup_s"}))
    (d / "warm_tree_s.py").write_text(
        "def read(ctx, spec):\n"
        "    return ctx['spans'].get('warm_trees_s')\n")
    specs = loader.load_layer_metrics(toy_roots)
    names = [s["name"] for s in specs]
    assert "warm_tree_s" in names and "tree_mfu" in names
    per_layer = loader.load_benchmark()["per_layer"]
    # a metric's file that BENCHMARK.json does not name is not reported
    got = readers.read_all(specs, CTX, "higgs-train", per_layer)
    assert "warm_tree_s" not in got
    per_layer = per_layer + [{"name": "warm_tree_s",
                              "workloads": ["toy-train"]}]
    got = readers.read_all(specs, CTX, "toy-train", per_layer)
    assert got == {"warm_tree_s": {"value": 1.5, "unit": "s"}}
    # the cells the committed metrics list get them; a reader with nothing
    # to read (no trace) says nothing
    got = readers.read_all(specs, CTX, "higgs-train", per_layer)
    assert got["tree_mfu"]["value"] == 100.0 * 0.04 / 2.0
    assert got["first_update_s"]["value"] == 2.0
    assert got["compiles_in_window"]["value"] == 0.0
    assert "dataset_s" not in got  # one of its spans was not taken
    assert "hist_roofline" not in got and "device_idle_pct" not in got


def test_a_configuration_s_xla_flags_reach_the_environment(toy_roots,
                                                           monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    loader.load_cell({"name": "toy-train", "config": "toy",
                      "traffic": "short-train", "chips": 1}, toy_roots)
    import os

    flags = os.environ["XLA_FLAGS"].split()
    assert flags == ["--xla_force_host_platform_device_count=8",
                     "--xla_allow_excess_precision=false"]
    loader.load_cell({"name": "toy-train", "config": "toy",
                      "traffic": "short-train", "chips": 1}, toy_roots)
    assert os.environ["XLA_FLAGS"].split() == flags  # once, not twice
