"""Observability subsystem (round 10, docs/OBSERVABILITY.md): registry
semantics, event schema, snapshot round-trip, fleet aggregation — plus THE
acceptance pin: with telemetry default-on, the rounds grower's training
budget (0 accounted syncs / 0 retraces per steady-state update) and the
round-9 serving budget (warm predict = 1 dispatch + 1 pull) hold unchanged
while the run leaves a non-empty, schema-valid metrics snapshot covering
train, predict, and a robustness event.

The legacy profiling-harness tests (device trace capture, debug_nans train)
stay ``slow``; everything else here is tier-1.
"""

import glob
import json
import os

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import metrics as obs
from lightgbm_tpu.utils.profiling import (device_trace, log_timings,
                                          timed_section)


@pytest.fixture(autouse=True)
def _fresh_registry():
    from lightgbm_tpu.obs import server as _srv
    from lightgbm_tpu.obs import trace as _trc

    obs.reset()
    obs.set_events_file(None)
    _trc.reset_trace()
    yield
    _srv.stop_server()
    obs.stop_periodic_snapshots(final_write=False)
    obs.reset()
    obs.set_events_file(None)
    _trc.reset_trace()


def _tiny_train(extra=None, rounds=3):
    rng = np.random.RandomState(0)
    X = rng.randn(800, 5).astype(np.float32)
    y = ((X @ rng.randn(5)) > 0).astype(np.float64)
    ds = lgb.Dataset(X, label=y)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    params.update(extra or {})
    bst = lgb.Booster(params=params, train_set=ds)
    for _ in range(rounds):
        bst.update()
    return bst, X, y


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_semantics():
    c = obs.counter("t_total")
    c.inc()
    c.inc(4)
    assert c.value == 5
    assert obs.counter("t_total") is c  # create-on-first-use, then shared
    g = obs.gauge("t_gauge")
    g.set(2.5)
    g.set(-1.0)
    assert g.value == -1.0


def test_histogram_reservoir_bounded_and_percentiles():
    h = obs.histogram("t_hist")
    for v in range(10_000):
        h.observe(float(v))
    assert h.count == 10_000
    assert h.total == sum(range(10_000))
    assert h.min == 0.0 and h.max == 9999.0
    assert len(h._samples) == obs.RESERVOIR_CAP  # hard memory bound
    p50, p99 = h.percentile(50), h.percentile(99)
    # reservoir estimate: generous tolerance, exact rank not required
    assert 3500 < p50 < 6500, p50
    assert p99 > 9000, p99
    s = h.summary()
    assert s["count"] == 10_000 and s["p50"] == p50


def test_disabled_registry_is_noop():
    obs.set_enabled(False)
    try:
        obs.counter("t_off").inc()
        obs.histogram("t_off_h").observe(1.0)
        obs.event("t_off_event")
        assert obs.counter("t_off").value == 0
        assert obs.histogram("t_off_h").count == 0
        assert not obs.events("t_off_event")
    finally:
        obs.set_enabled(True)


def test_collector_merges_into_snapshot():
    obs.register_collector(
        "t_coll", lambda: {"counters": {"t_coll_total": 7},
                           "gauges": {"t_coll_gauge": 1.5}})
    try:
        snap = obs.snapshot()
        assert snap["counters"]["t_coll_total"] == 7
        assert snap["gauges"]["t_coll_gauge"] == 1.5
        # the sanitizer collector is registered at import and always present
        assert "device_dispatches_total" in snap["counters"]
        assert "device_compiles_total" in snap["counters"]
    finally:
        obs.REGISTRY._collectors.pop("t_coll", None)


# ---------------------------------------------------------------------------
# events: schema + JSONL sink
# ---------------------------------------------------------------------------

def test_event_schema_and_jsonl_sink(tmp_path):
    sink = str(tmp_path / "events.jsonl")
    obs.set_events_file(sink)
    obs.event("unit_test", detail="abc", n=3)
    obs.event("unit_test", n=4)
    recs = [json.loads(line) for line in
            open(sink, encoding="utf-8").read().splitlines()]
    assert len(recs) == 2
    for rec in recs:
        # the schema every record carries (docs/OBSERVABILITY.md)
        assert isinstance(rec["ts"], float)
        assert rec["kind"] == "unit_test"
        assert "rank" in rec  # None outside launcher workers
    assert recs[0]["detail"] == "abc" and recs[1]["n"] == 4
    # the in-memory ring saw the same records
    assert len(obs.events("unit_test")) == 2


def test_event_sink_failure_is_silent_and_final(tmp_path, monkeypatch):
    """A sink that cannot open fails ONCE: events keep flowing to the
    ring, nothing raises, and the registry neither retries per event nor
    falls back to the env-configured path."""
    env_sink = tmp_path / "env.jsonl"
    monkeypatch.setenv("LGBMTPU_EVENTS_FILE", str(env_sink))
    obs.set_events_file(str(tmp_path / "no_such_dir" / "x.jsonl"))
    obs.event("sink_fail", n=1)
    obs.event("sink_fail", n=2)
    assert len(obs.events("sink_fail")) == 2  # ring unaffected
    assert not env_sink.exists()  # no silent fallback to the env path
    # reverting to env resolution picks the env sink up again
    obs.set_events_file(None)
    obs.event("sink_fail", n=3)
    assert env_sink.exists()


def test_event_rank_stamped_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("LIGHTGBM_TPU_RANK", "3")
    reg = obs.Registry()
    reg.event("ranked")
    assert reg.events("ranked")[0]["rank"] == 3


def test_fleet_event_aggregation(tmp_path):
    """parallel/launcher.py merges per-rank JSONLs time-sorted, skipping a
    crashed worker's torn last line."""
    a = tmp_path / "worker0.events.jsonl"
    b = tmp_path / "worker1.events.jsonl"
    a.write_text(json.dumps({"ts": 2.0, "kind": "boost_round", "rank": 0})
                 + "\n")
    b.write_text(json.dumps({"ts": 1.0, "kind": "boost_round", "rank": 1})
                 + "\n" + '{"ts": 3.0, "kind": "torn')  # mid-crash tail
    out = tmp_path / "fleet.jsonl"
    n = obs.merge_event_files([str(a), str(b), str(tmp_path / "gone")],
                              str(out))
    assert n == 2
    recs = [json.loads(line) for line in out.read_text().splitlines()]
    assert [r["rank"] for r in recs] == [1, 0]  # time-sorted across ranks


# ---------------------------------------------------------------------------
# snapshot round-trip + rendering
# ---------------------------------------------------------------------------

def test_snapshot_roundtrip_and_renderers(tmp_path):
    obs.counter("t_rt_total").inc(3)
    obs.gauge("t_rt_gauge").set(0.5)
    obs.histogram("t_rt_ms").observe(1.5)
    obs.histogram(obs.SECTION_PREFIX + "train").observe(2.0)
    obs.event("t_rt")
    path = str(tmp_path / "metrics.json")
    obs.write_snapshot(path)
    snap = obs.load_snapshot(path)  # validates the schema on load
    assert snap["schema"] == obs.SCHEMA
    assert snap["counters"]["t_rt_total"] == 3
    assert snap["histograms"]["t_rt_ms"]["count"] == 1
    assert snap["events_total"] == 1
    prom = obs.render_prometheus(snap)
    assert "# TYPE lgbmtpu_t_rt_total counter" in prom
    assert "lgbmtpu_t_rt_total 3" in prom
    assert 'lgbmtpu_t_rt_ms{quantile="0.5"} 1.5' in prom
    report = obs.render_lightgbm(snap)
    assert "Time for train: 2.000000 s (1 calls)" in report
    assert any(line.startswith("t_rt_total = 3") for line in report)
    with pytest.raises(ValueError):
        obs.validate_snapshot({"schema": "bogus"})


def test_serve_reservoirs_render_as_label_sets_one_family():
    """Round 18 (ISSUE 13 satellite): the per-entry warm-latency
    reservoirs are LABEL SETS on the one ``predict_warm_latency_ms``
    family — ``{entry="raw"}`` next to the round-11 ``{bucket="..."}``
    labels — not the deprecated dotted-suffix names, which rendered as a
    separate Prometheus family per entry.  Pins the rendered label sets
    and the stable family count."""
    rng = np.random.RandomState(2)
    X = rng.randn(120, 5)
    y = (X[:, 0] > 0).astype(float)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbosity": -1},
                      train_set=lgb.Dataset(X, label=y))
    for _ in range(2):
        bst.update()
    for _ in range(2):  # first call cold (compiles), second warm (records)
        bst.predict(X, raw_score=True)
        bst.predict(X)

    snap = obs.snapshot()
    hists = snap["histograms"]
    nb = 128  # the bucket X pads to
    assert 'predict_warm_latency_ms{entry="raw"}' in hists
    assert 'predict_warm_latency_ms{entry="converted"}' in hists
    assert f'predict_warm_latency_ms{{bucket="{nb}"}}' in hists
    assert not any("." in name and name.startswith("predict_warm_latency_ms")
                   for name in hists), "dotted-suffix reservoir names back"

    prom = obs.render_prometheus(snap)
    # ONE summary family, every variant a label set on it
    assert prom.count("# TYPE lgbmtpu_predict_warm_latency_ms summary") == 1
    assert "lgbmtpu_predict_warm_latency_ms_raw" not in prom
    assert 'lgbmtpu_predict_warm_latency_ms{entry="raw",quantile="0.5"}' \
        in prom
    assert ('lgbmtpu_predict_warm_latency_ms{entry="converted",'
            'quantile="0.99"}') in prom
    assert f'lgbmtpu_predict_warm_latency_ms{{bucket="{nb}",quantile=' \
        in prom


def test_obs_cli_dumps_snapshot(tmp_path, capsys):
    from lightgbm_tpu.obs.__main__ import main as obs_main

    obs.counter("t_cli_total").inc()
    path = str(tmp_path / "snap.json")
    obs.write_snapshot(path)
    assert obs_main([path]) == 0
    assert "lgbmtpu_t_cli_total 1" in capsys.readouterr().out
    assert obs_main([path, "--format", "lightgbm"]) == 0
    assert "t_cli_total = 1" in capsys.readouterr().out
    assert obs_main([str(tmp_path / "missing.json")]) == 2


# ---------------------------------------------------------------------------
# profiling satellite: registry-backed sections + honest sync
# ---------------------------------------------------------------------------

def test_timed_section_routes_through_registry():
    with timed_section("unit_section"):
        pass
    with timed_section("unit_section", sync=True):  # queue-drain path
        pass
    h = obs.histogram(obs.SECTION_PREFIX + "unit_section")
    assert h.count == 2
    totals = log_timings(reset=True)
    assert totals["unit_section"] > 0
    assert not obs.histogram_items(obs.SECTION_PREFIX)  # reset cleared them


# ---------------------------------------------------------------------------
# config plumbing: metrics_file= + telemetry=
# ---------------------------------------------------------------------------

def test_train_writes_metrics_file(tmp_path):
    rng = np.random.RandomState(1)
    X = rng.randn(400, 4)
    y = (X[:, 0] > 0).astype(float)
    mfile = str(tmp_path / "run_metrics.json")
    lgb.train({"objective": "binary", "num_leaves": 7, "verbosity": -1,
               "metrics_file": mfile},
              lgb.Dataset(X, label=y), num_boost_round=3)
    snap = obs.load_snapshot(mfile)
    assert snap["counters"]["train_boost_rounds_total"] == 3


def test_telemetry_param_disables_registry():
    try:
        _tiny_train({"telemetry": False}, rounds=2)
        assert not obs.enabled()
        assert obs.counter("train_boost_rounds_total").value == 0
    finally:
        obs.set_enabled(True)


# ---------------------------------------------------------------------------
# ACCEPTANCE: telemetry default-on, budgets unchanged, snapshot non-empty
# ---------------------------------------------------------------------------

def test_budgets_hold_with_telemetry_on_and_snapshot_covers_run(tmp_path):
    """ISSUE 5 acceptance, extended by ISSUE 6: train (the rounds
    grower's steady-state update budget) + predict (warm serving budget)
    with the registry active, SPAN TRACING recording, and the HTTP endpoint
    serving live — then assert a schema-valid snapshot covering train,
    predict, and a robustness event (an injected kernel degrade).  The
    round-11 contract is that live introspection adds zero accounted
    syncs and zero retraces to both budgets."""
    import json as _json
    import urllib.request

    from lightgbm_tpu.obs import server as obs_server
    from lightgbm_tpu.obs import trace as obs_trace
    from lightgbm_tpu.utils import degrade
    from lightgbm_tpu.utils.sanitizer import DispatchCounter

    assert obs.enabled()  # default-on is the contract under test
    obs_trace.reset_trace()
    srv = obs_server.MetricsServer(port=0).start()  # live while we train

    # -- train side: the rounds grower's unfused loop, telemetry on ------
    rounds_bst, _, _ = _tiny_train(
        {"tree_growth_mode": "rounds", "fused_training": False,
         "num_leaves": 15, "min_data_in_leaf": 5}, rounds=2)  # warmup
    assert rounds_bst._gbdt._use_fast
    np.asarray(rounds_bst._gbdt._score)
    with DispatchCounter() as d:
        for _ in range(3):
            rounds_bst.update()
        np.asarray(rounds_bst._gbdt._score)
    assert d.host_syncs == 0, d.host_syncs
    d.assert_no_recompile("rounds-grower updates with telemetry on")
    # every update left its host-causal span
    assert len(obs_trace.spans("boost_round")) == 5
    # the pass counters come off arrays the flush holds on the host
    assert rounds_bst.num_trees() == 5
    passes = obs.counter("train_hist_passes_total").value
    assert passes >= 5, passes
    assert (obs.counter("train_hist_rows_streamed_total").value
            == passes * 800)

    # -- predict side: the round-9 warm budget with telemetry recording --
    bst, Xb, _ = _tiny_train(rounds=4)
    bst.predict(Xb, raw_score=True)  # warm the bucket
    with DispatchCounter() as dp:
        bst.predict(Xb, raw_score=True)
    assert dp.dispatches == 1, dp.dispatches
    assert dp.host_syncs == 1, dp.host_syncs
    dp.assert_no_recompile("warm predict with telemetry on")
    assert obs_trace.spans("predict.raw"), "no predict spans"
    assert obs_trace.spans("boost_round"), "no boost_round spans"

    # -- the HTTP endpoint served the whole run and sees both families --
    prom_live = urllib.request.urlopen(
        srv.url("/metrics"), timeout=10).read().decode()
    assert "lgbmtpu_train_hist_passes_total" in prom_live
    assert "lgbmtpu_predict_requests_total" in prom_live
    assert 'lgbmtpu_predict_warm_latency_ms{bucket="' in prom_live
    hz = urllib.request.urlopen(srv.url("/healthz"), timeout=10)
    assert _json.load(hz)["status"] == "ok"
    srv.stop()

    # -- trace export round-trips as Chrome-trace JSON -------------------
    tpath = str(tmp_path / "run_trace.json")
    from lightgbm_tpu.obs import trace as _t
    assert _t.write_trace(tpath) > 0
    doc = _t.load_trace(tpath)
    assert all(ev["ph"] == "X" for ev in doc["traceEvents"])

    # -- robustness event: an injected kernel degrade -------------------
    degrade.reset()
    try:
        degrade.disable(degrade.HIST, "injected by test_observability")
    finally:
        degrade.reset()

    # -- the run left a non-empty, schema-valid snapshot -----------------
    snap = obs.snapshot()
    obs.validate_snapshot(snap)
    c = snap["counters"]
    assert c["train_hist_passes_total"] == passes  # train
    assert c["train_boost_rounds_total"] == 5 + 4
    assert c["predict_requests_total"] >= 2  # predict
    assert c["predict_bucket_hits_total"] >= 1
    assert snap["histograms"]["predict_warm_latency_ms"]["count"] >= 1
    assert c["degrade_disabled_total"] == 1  # robustness
    assert c["device_dispatches_total"] >= 1  # sanitizer collector merged
    kinds = {e["kind"] for e in obs.events()}
    assert {"boost_round", "degrade"} <= kinds
    # and the snapshot round-trips to a readable artifact
    path = str(tmp_path / "acceptance.json")
    obs.write_snapshot(path, snap)
    assert "lgbmtpu_train_hist_passes_total" in obs.render_prometheus(
        obs.load_snapshot(path))


# ---------------------------------------------------------------------------
# legacy profiling harness (slow: full device trace + debug_nans trains)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_device_trace_writes_profile(tmp_path):
    logdir = str(tmp_path / "trace")
    with device_trace(logdir):
        with timed_section("train"):
            _tiny_train()
    files = glob.glob(os.path.join(logdir, "**", "*"), recursive=True)
    assert any("trace" in f or f.endswith(".pb") or f.endswith(".json.gz") for f in files), files
    totals = log_timings()
    assert totals["train"] > 0


@pytest.mark.slow
def test_training_is_nan_clean_under_debug_nans():
    """jax debug_nans is the sanitizer-CI analog: any NaN produced inside a
    jitted training op raises immediately."""
    import jax

    jax.config.update("jax_debug_nans", True)
    try:
        bst, X, y = _tiny_train()
        p = bst.predict(X)
        assert np.isfinite(p).all()
        # missing values must stay NaN-clean too
        Xn = X.copy()
        Xn[::7, 0] = np.nan
        ds = lgb.Dataset(Xn, label=y)
        bst2 = lgb.Booster(
            params={"objective": "binary", "num_leaves": 7, "verbosity": -1},
            train_set=ds,
        )
        for _ in range(2):
            bst2.update()
        assert np.isfinite(bst2.predict(Xn)).all()
    finally:
        jax.config.update("jax_debug_nans", False)
