"""trace_reduce.py: on events made by hand, on a live CPU trace (the reader),
and on the small trace recorded on the chip."""

import pathlib

import pytest

from chipbench.harness import loader, readers, trace_reduce as tr

RECORDED = pathlib.Path(__file__).parent / "data" / "higgs-train.trace.json.gz"
MS = 1e6  # ns


def _trace(events, host=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": events},
            {"name": "XLA Modules", "events": [["jit_step", 0.0, 100 * MS]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": list(host)}]}]}


def test_busy_is_the_union_and_gaps_are_named_by_the_host_span():
    events = [["fusion.1", 0.0, 10 * MS], ["hist_kernel", 5 * MS, 10 * MS],
              ["fusion.2", 30 * MS, 10 * MS], ["hist_kernel", 60 * MS, 20 * MS]]
    host = [["chipbench_update", 14 * MS, 10 * MS],
            ["chipbench_wait", 24 * MS, 100 * MS]]
    red = tr.reduce(_trace(events, host))
    assert red["chips"] == 1
    assert red["busy_s"] == pytest.approx(0.045)  # 0-15, 30-40, 60-80
    assert red["window_s"] == pytest.approx(0.080)
    # the modules line covers the same time again and is not counted
    assert red["device_ops"][0] == ["hist_kernel", pytest.approx(0.030)]
    assert red["idle_gaps"] == [["chipbench_wait", pytest.approx(0.020)],
                                ["chipbench_update", pytest.approx(0.015)]]
    assert tr.seconds_by_pattern(red["events"], ["hist_"]) == pytest.approx(
        0.030)
    assert tr.seconds_by_pattern(red["events"], ["no_such_kernel"]) is None


def test_nested_matches_count_once():
    events = [["while.hist", 0.0, 10 * MS], ["hist_body", 2 * MS, 3 * MS]]
    assert tr.seconds_by_pattern(events, ["hist"]) == pytest.approx(0.010)


def test_a_trace_without_device_operations_reduces_to_nothing():
    assert tr.reduce({"planes": [{"name": "/host:CPU", "lines": []}]}) is None


def test_trace_readers_say_nothing_without_a_match_and_never_zero():
    specs = {s["name"]: s for s in loader.load_layer_metrics()}
    red = tr.reduce(_trace([["fusion.1", 0.0, 10 * MS],
                            ["fusion.2", 20 * MS, 20 * MS]]))
    ctx = {"spans": {}, "counters": {}, "trace": red, "traced": range(1, 2),
           "window": {"seconds": 1.0, "trees": 3}, "least_s": [1e-3] * 3}
    got = readers.read_all(list(specs.values()), ctx, "higgs-train",
                           loader.load_benchmark()["per_layer"])
    assert "hist_roofline" not in got and "hist_kernel_ms_per_tree" not in got
    assert got["device_idle_pct"]["value"] == pytest.approx(25.0)
    assert got["tree_mfu"]["value"] == pytest.approx(0.3)


def test_window_tracer_picks_whole_trees_in_the_middle(monkeypatch):
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    t = tr.WindowTracer("unused", skip_trees=1, n_trees=2)
    for issued in range(8):  # two trees in flight: done = issued - 1
        t.before_tree(issued, max(issued - 1, 0))
    t.close()
    assert calls == ["start", "stop"]
    assert list(t.traced_trees(8)) == [1, 2]
    short = tr.WindowTracer("unused", skip_trees=1, n_trees=2)
    for issued in range(3):
        short.before_tree(issued, max(issued - 1, 0))
    short.close()
    assert list(short.traced_trees(3)) == [1, 2]
    assert list(tr.WindowTracer("unused", 1, 2).traced_trees(5)) == []


def test_reader_of_a_live_trace_keeps_only_what_the_reduction_reads(tmp_path):
    import jax
    import jax.numpy as jnp

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("chipbench_update"):
            jnp.ones((64, 64)).sum().block_until_ready()
    trace = tr.read_xplane(tr.find_xplane(str(tmp_path)))
    assert "/host:CPU" in trace["plane_names"]
    assert tr.device_planes(trace) == []  # a CPU run has no device plane
    assert [e[0] for e in tr.host_spans(trace)] == ["chipbench_update"]
    path = tmp_path / "kept" / "t.json.gz"
    tr.save_recorded(trace, path)
    assert tr.load_recorded(str(path)) == trace


def test_reduction_of_the_trace_recorded_on_the_chip():
    """The first 1.2 s of higgs-train's traced window on the v5e (PR 24):
    the device is busy all through, the histogram kernel is found by its
    name in the trace, and the containers of the loop hold no time of their
    own."""
    trace = tr.load_recorded(str(RECORDED))
    assert "/device:TPU:0" in trace["plane_names"]
    red = tr.reduce(trace)
    assert red["chips"] == 1 and len(red["events"]) == 2531
    assert red["window_s"] == pytest.approx(1.2, abs=1e-6)
    assert red["busy_s"] == pytest.approx(1.19999, abs=2e-5)
    spec = {s["name"]: s for s in loader.load_layer_metrics()}[
        "hist_kernel_ms_per_tree"]
    kernel = tr.seconds_by_pattern(red["events"], spec["patterns"])
    assert kernel == pytest.approx(0.67971, abs=1e-4)
    ops = dict(red["device_ops"])
    assert ops["_hist_pallas_raw"] == pytest.approx(kernel)
    assert list(ops)[0] == "_hist_pallas_raw"
    assert ops.get("while", 0.0) < 0.01  # its body's operations hold the time
    assert sum(ops.values()) <= red["busy_s"] + 1e-6
    assert all(g[1] < 1e-4 for g in red["idle_gaps"])
    ctx = {"spans": {}, "counters": {}, "trace": red, "traced": range(0, 1),
           "window": {"seconds": 4.6, "trees": 1}, "least_s": [1.8e-3]}
    got = readers.read_all([spec], ctx, "higgs-train",
                           loader.load_benchmark()["per_layer"])
    assert got["hist_kernel_ms_per_tree"]["value"] == pytest.approx(679.71,
                                                                   abs=0.1)
