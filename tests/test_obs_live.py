"""Live introspection (round 11, docs/OBSERVABILITY.md): span tracing +
Chrome-trace export, the in-process HTTP metrics/health endpoint, fleet
metric aggregation, and the obs CLI's serve/tail/trace subcommands.

THE acceptance scenario lives at the bottom: ``curl /metrics`` during a
live ``engine.train`` returns Prometheus text with train + serve metric
families, and ``/healthz`` flips on an injected fault (``LGBMTPU_FAULT``)
without killing training.  The budget half of the round-11 contract (zero
extra dispatches/syncs/retraces with tracing and the server ON) is pinned
in test_observability.py's acceptance test.
"""

import ast
import json
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import metrics as obs
from lightgbm_tpu.obs import server as obs_server
from lightgbm_tpu.obs import trace as obs_trace
from lightgbm_tpu.obs.__main__ import main as obs_main, serve_snapshot


@pytest.fixture(autouse=True)
def _fresh_obs():
    obs.reset()
    obs.set_events_file(None)
    obs_trace.reset_trace()
    obs_trace.set_annotation_factory(None)
    yield
    obs_server.stop_server()
    obs.stop_periodic_snapshots(final_write=False)
    obs.reset()
    obs.set_events_file(None)
    obs_trace.reset_trace()
    obs_trace.set_annotation_factory(None)


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return r.status, r.read().decode()


# ---------------------------------------------------------------------------
# the obs package stays stdlib-only (ISSUE 6 acceptance: no jax in obs/)
# ---------------------------------------------------------------------------

def test_obs_package_imports_no_jax():
    """Static pin of the stdlib-only contract: no module under
    lightgbm_tpu/obs may import jax (or numpy — the launcher's thin
    worker processes and utils/faults.py record here without paying a
    backend import)."""
    obs_dir = Path(obs.__file__).resolve().parent
    for py in sorted(obs_dir.glob("*.py")):
        tree = ast.parse(py.read_text(), filename=str(py))
        for node in ast.walk(tree):
            names = []
            if isinstance(node, ast.Import):
                names = [a.name for a in node.names]
            elif isinstance(node, ast.ImportFrom) and node.level == 0:
                names = [node.module or ""]
            for name in names:
                root = name.split(".")[0]
                assert root not in ("jax", "jaxlib", "numpy"), (
                    f"{py.name} imports {name} — obs/ must stay "
                    "stdlib-only (docs/OBSERVABILITY.md)")


# ---------------------------------------------------------------------------
# span tracing
# ---------------------------------------------------------------------------

def test_spans_nest_and_carry_attributes():
    with obs_trace.span("outer", a=1) as sp:
        sp.set(b=2)
        with obs_trace.span("inner"):
            pass
    recs = obs_trace.spans()
    inner = [s for s in recs if s["name"] == "inner"][0]
    outer = [s for s in recs if s["name"] == "outer"][0]
    assert outer["attrs"] == {"a": 1, "b": 2}
    assert inner["depth"] == 1 and inner["parent"] == outer["id"]
    assert outer["dur"] >= inner["dur"] >= 0.0


def test_record_span_is_retroactive_and_disabled_registry_silences_spans():
    obs_trace.record_span("resolved_round", 0.25, k=3)
    (rec,) = obs_trace.spans("resolved_round")
    assert rec["dur"] == 0.25 and rec["attrs"]["k"] == 3
    assert rec["ts"] <= time.time()
    obs.set_enabled(False)
    try:
        with obs_trace.span("off"):
            pass
        obs_trace.record_span("off_retro", 0.1)
        assert not obs_trace.spans("off")
        assert not obs_trace.spans("off_retro")
    finally:
        obs.set_enabled(True)


def test_chrome_trace_export_roundtrip(tmp_path):
    with obs_trace.span("tree", rounds=7):
        pass
    path = str(tmp_path / "trace.json")
    assert obs_trace.write_trace(path) == 1
    # the file IS standard Chrome trace JSON (Perfetto-loadable) ...
    doc = json.loads(Path(path).read_text())
    (ev,) = doc["traceEvents"]
    assert ev["ph"] == "X" and ev["name"] == "tree"
    assert ev["dur"] >= 0 and ev["args"]["rounds"] == 7
    # ... and round-trips through the validating loader
    doc2 = obs_trace.load_trace(path)
    assert doc2["lgbmtpu"]["spans"][0]["name"] == "tree"
    with pytest.raises(ValueError):
        obs_trace.validate_trace({"traceEvents": []})


def test_ring_overflow_spills_to_jsonl_in_order(tmp_path):
    """Round 12: ring evictions are no longer silent — with a spill sink
    armed, the OLDEST span falls off into the JSONL sidecar (in eviction
    order) and counts trace_spans_spilled_total; the ring keeps the
    newest cap spans exactly as before."""
    path = str(tmp_path / "spill.jsonl")
    obs_trace.set_ring_cap(4)
    try:
        obs_trace.enable_spill(path)
        for i in range(10):
            obs_trace.record_span(f"s{i}", 0.01, i=i)
        assert [s["name"] for s in obs_trace.spans()] == [
            f"s{i}" for i in range(6, 10)]
        obs_trace.disable_spill()
        lines = [json.loads(ln)
                 for ln in Path(path).read_text().splitlines()]
        assert [ln["name"] for ln in lines] == [f"s{i}" for i in range(6)]
        assert lines[0]["attrs"] == {"i": 0}  # full records, not summaries
        assert obs.counter("trace_spans_spilled_total").value == 6
        assert obs.counter("trace_spans_dropped_total").value == 0
    finally:
        obs_trace.disable_spill()
        obs_trace.set_ring_cap(obs_trace.TRACE_RING_CAP)


def test_spill_byte_bound_and_no_sink_count_drops(tmp_path):
    """Past max_bytes the sink stops growing and evictions degrade to the
    dropped counter; with no sink at all every eviction is a counted
    drop — either way the metrics always say what the ring lost."""
    path = str(tmp_path / "spill.jsonl")
    obs_trace.set_ring_cap(1)
    try:
        obs_trace.enable_spill(path, max_bytes=200)
        for i in range(50):
            obs_trace.record_span("pad", 0.01, i=i)
        obs_trace.disable_spill()
        spilled = obs.counter("trace_spans_spilled_total").value
        dropped = obs.counter("trace_spans_dropped_total").value
        assert spilled >= 1 and dropped >= 1
        assert spilled + dropped == 49  # every eviction accounted
        # the sink respects the bound (may overshoot by < one record)
        assert Path(path).stat().st_size < 200 + 256
        # no sink: pure drops
        obs.reset()
        obs_trace.reset_trace()
        for i in range(5):
            obs_trace.record_span("nosink", 0.01)
        assert obs.counter("trace_spans_dropped_total").value == 4
        assert obs.counter("trace_spans_spilled_total").value == 0
    finally:
        obs_trace.disable_spill()
        obs_trace.set_ring_cap(obs_trace.TRACE_RING_CAP)


def test_spill_survives_process_restart(tmp_path):
    """A relaunched process (watchdog restart / resume=auto) re-arming
    the same spill path APPENDS — the pre-crash span history the sink
    exists to preserve is not truncated.  Re-arming after a CLEAN disarm
    in the same process truncates instead: a later run's evictions must
    not be appended to (and mistaken for) a finished run's history.
    Switching paths mid-process also truncates the new file."""
    path = str(tmp_path / "spill.jsonl")
    obs_trace.set_ring_cap(1)
    try:
        obs_trace.enable_spill(path)
        for i in range(4):
            obs_trace.record_span(f"run1_{i}", 0.01)
        obs_trace.disable_spill()
        # simulate a fresh process: sink state and ring both start empty
        obs_trace._spill_path = None
        obs_trace._spill_fh = None
        obs_trace._spill_clean = False
        obs_trace.reset_trace()
        obs_trace.enable_spill(path)
        for i in range(4):
            obs_trace.record_span(f"run2_{i}", 0.01)
        obs_trace.disable_spill()
        names = [json.loads(ln)["name"]
                 for ln in Path(path).read_text().splitlines()]
        assert names == ["run1_0", "run1_1", "run1_2",
                         "run2_0", "run2_1", "run2_2"]
        # in-process re-arm after the clean disarm above: SAME path
        # truncates — run 3's sidecar holds only run 3's evictions
        obs_trace.reset_trace()
        obs_trace.enable_spill(path)
        for i in range(3):
            obs_trace.record_span(f"run3_{i}", 0.01)
        obs_trace.disable_spill()
        names = [json.loads(ln)["name"]
                 for ln in Path(path).read_text().splitlines()]
        assert names == ["run3_0", "run3_1"]
        # mid-process path switch truncates the (stale) new target
        obs_trace.reset_trace()
        other = str(tmp_path / "other.jsonl")
        Path(other).write_text('{"name": "stale"}\n')
        obs_trace.enable_spill(other)
        obs_trace.record_span("x", 0.01)
        obs_trace.record_span("y", 0.01)
        obs_trace.disable_spill()
        assert "stale" not in Path(other).read_text()
    finally:
        obs_trace.disable_spill()
        obs_trace.set_ring_cap(obs_trace.TRACE_RING_CAP)


def test_engine_train_arms_spill_next_to_trace_file(tmp_path):
    """engine.train with trace_file= arms the sidecar spill sink, so a
    run that overflows the ring leaves <trace_file>.spill.jsonl behind."""
    trace_path = str(tmp_path / "run_trace.json")
    rng = np.random.RandomState(0)
    X = rng.randn(80, 4)
    y = (X[:, 0] > 0).astype(float)
    obs_trace.set_ring_cap(2)
    try:
        lgb.train({"objective": "binary", "verbosity": -1,
                   "trace_file": trace_path},
                  lgb.Dataset(X, label=y), num_boost_round=3)
        assert obs_trace.spill_path() == trace_path + ".spill.jsonl"
        assert Path(trace_path + ".spill.jsonl").exists()
        assert obs.counter("trace_spans_spilled_total").value >= 1
        assert Path(trace_path).exists()  # the main export still lands
    finally:
        obs_trace.disable_spill()
        obs_trace.set_ring_cap(obs_trace.TRACE_RING_CAP)


def test_engine_train_disarms_spill_on_exception(tmp_path):
    """The spill sink armed at train start must be disarmed on EVERY exit
    path — a run killed by a mid-boost exception must not leave the sink
    armed process-wide, or later unrelated work's ring evictions would be
    appended to (and mistaken for) the dead run's span history."""
    trace_path = str(tmp_path / "run_trace.json")
    rng = np.random.RandomState(0)
    X = rng.randn(80, 4)
    y = (X[:, 0] > 0).astype(float)

    def _boom(env):
        raise RuntimeError("mid-boost failure")

    try:
        with pytest.raises(RuntimeError, match="mid-boost failure"):
            lgb.train({"objective": "binary", "verbosity": -1,
                       "trace_file": trace_path},
                      lgb.Dataset(X, label=y), num_boost_round=3,
                      callbacks=[_boom])
        # spill_path() keeps the last-armed path for resume semantics; the
        # armed/disarmed state is the open file handle
        assert obs_trace._spill_fh is None  # disarmed despite the raise
        assert Path(trace_path).exists()  # partial-run trace still lands
    finally:
        obs_trace.disable_spill()


def test_span_exception_close_and_mismatched_exit():
    with pytest.raises(RuntimeError):
        with obs_trace.span("boom"):
            raise RuntimeError("x")
    (rec,) = obs_trace.spans("boom")
    assert rec["attrs"]["error"] == "RuntimeError"
    assert not getattr(obs_trace._tls, "stack", [])  # stack unwound


def test_annotation_factory_mirrors_spans():
    """The jax.profiler bridge contract (utils/profiling.py installs the
    one that mirrors every span behind LGBMTPU_JAX_PROFILER=1): the
    factory's context manager wraps the context-manager span's body."""
    entered, exited = [], []

    class _Cm:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            entered.append(self.name)

        def __exit__(self, *exc):
            exited.append(self.name)

    obs_trace.set_annotation_factory(lambda name, attrs: _Cm(name))
    with obs_trace.span("mirrored"):
        assert entered == ["mirrored"] and not exited
    assert exited == ["mirrored"]

    # the shipped factory maps iteration-carrying spans to step annotations
    from lightgbm_tpu.utils.profiling import _jax_annotation_factory
    import jax

    cm = _jax_annotation_factory("boost_round", {"iteration": 3})
    assert isinstance(cm, jax.profiler.StepTraceAnnotation)
    cm2 = _jax_annotation_factory("train", {})
    assert isinstance(cm2, jax.profiler.TraceAnnotation)


def test_boost_round_opens_a_step_annotation_without_a_switch(monkeypatch):
    """The bridge utils/profiling.py installs when it is imported, with
    LGBMTPU_JAX_PROFILER unset: a step per ``boost_round`` (so any profiler
    trace has one per tree), nothing for the other spans."""
    import jax

    from lightgbm_tpu.utils import profiling

    monkeypatch.delenv("LGBMTPU_JAX_PROFILER", raising=False)
    step = profiling._step_annotation_factory("boost_round", {"iteration": 3})
    assert isinstance(step, jax.profiler.StepTraceAnnotation)
    assert profiling._step_annotation_factory("train", {}) is None

    opened = []

    class _Step:
        def __init__(self, name, **kw):
            opened.append((name, kw))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            opened.append("closed")

    monkeypatch.setattr(jax.profiler, "StepTraceAnnotation", _Step)
    profiling.install_step_annotations()
    rng = np.random.RandomState(5)
    x = rng.randn(600, 4)
    bst = lgb.Booster({"objective": "binary", "num_leaves": 7,
                       "verbosity": -1},
                      lgb.Dataset(x, label=(x[:, 0] > 0).astype(float)))
    bst.update()
    bst.update()
    assert opened == [("boost_round", {"step_num": 0}), "closed",
                      ("boost_round", {"step_num": 1}), "closed"]
    # a span the bridge does not mirror is recorded all the same
    with obs_trace.span("not_a_step") as sp:
        assert sp._annotation is None
    assert [s["name"] for s in obs_trace.spans()].count("boost_round") == 2
    assert obs_trace.spans("not_a_step")


# ---------------------------------------------------------------------------
# HTTP endpoint lifecycle
# ---------------------------------------------------------------------------

def test_server_routes_and_clean_shutdown():
    obs.counter("t_live_total").inc(2)
    obs.gauge("t_live_gauge").set(1.5)
    obs.histogram(obs.labeled("t_live_ms", bucket=128)).observe(3.0)
    obs.event("t_live", n=1)
    obs.event("t_live", n=2)
    srv = obs_server.MetricsServer(port=0).start()
    try:
        code, prom = _get(srv.url("/metrics"))
        assert code == 200
        assert "lgbmtpu_t_live_total 2" in prom
        assert 'lgbmtpu_t_live_ms{bucket="128",quantile="0.5"} 3.0' in prom
        code, snap_body = _get(srv.url("/snapshot"))
        snap = json.loads(snap_body)
        obs.validate_snapshot(snap)
        assert snap["counters"]["t_live_total"] == 2
        code, hz = _get(srv.url("/healthz"))
        assert code == 200 and json.loads(hz)["status"] == "ok"
        code, ev = _get(srv.url("/events?tail=1&kind=t_live"))
        recs = [json.loads(line) for line in ev.splitlines()]
        assert len(recs) == 1 and recs[0]["n"] == 2
        with pytest.raises(urllib.error.HTTPError):
            _get(srv.url("/nope"))
    finally:
        srv.stop()
    # clean shutdown: the port no longer accepts connections
    with pytest.raises((urllib.error.URLError, ConnectionError, OSError)):
        urllib.request.urlopen(srv.url("/metrics"), timeout=2)
    srv.stop()  # idempotent


def test_server_concurrent_gets():
    obs.counter("t_conc_total").inc()
    srv = obs_server.MetricsServer(port=0).start()
    results, errors = [], []

    def hit():
        try:
            results.append(_get(srv.url("/metrics"))[0])
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    try:
        threads = [threading.Thread(target=hit) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errors and results == [200] * 8
    finally:
        srv.stop()


def test_server_port_in_use_falls_back_to_ephemeral():
    first = obs_server.MetricsServer(port=0).start()
    try:
        second = obs_server.MetricsServer(port=first.port).start()
        try:
            assert second.fell_back
            assert second.port != first.port
            assert _get(second.url("/metrics"))[0] == 200
            assert obs.counter(
                "metrics_server_port_fallbacks_total").value == 1
        finally:
            second.stop()
    finally:
        first.stop()


def test_healthz_flips_degraded_then_unhealthy():
    srv = obs_server.MetricsServer(port=0).start()
    try:
        assert json.loads(_get(srv.url("/healthz"))[1])["status"] == "ok"
        obs.counter("degrade_disabled_total").inc()
        code, body = _get(srv.url("/healthz"))
        body = json.loads(body)
        assert code == 200 and body["status"] == "degraded"
        assert body["problems"][0]["counter"] == "degrade_disabled_total"
        obs.counter("train_nonfinite_errors_total").inc()
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url("/healthz"))
        assert ei.value.code == 503
        assert json.loads(ei.value.read().decode())["status"] == "unhealthy"
    finally:
        srv.stop()


def test_singleton_start_is_idempotent_and_env_gated(monkeypatch):
    assert obs_server.maybe_start(None) is None  # no opt-in anywhere
    monkeypatch.setenv("LGBMTPU_METRICS_PORT", "-1")
    assert obs_server.maybe_start(None) is None  # explicit off
    monkeypatch.setenv("LGBMTPU_METRICS_PORT", "0")
    srv = obs_server.maybe_start(None)
    assert srv is not None and srv.running
    assert obs_server.start_server(0) is srv  # one process, one endpoint
    assert obs_server.maybe_start(12345) is srv
    obs_server.stop_server()
    assert obs_server.get_server() is None


# ---------------------------------------------------------------------------
# fleet metrics aggregation
# ---------------------------------------------------------------------------

def _rank_snapshot_file(tmp_path, rank, counters, gauge, samples):
    reg = obs.Registry()
    reg._rank = rank
    for name, v in counters.items():
        c = reg.counter(name)
        c._value = v  # direct: avoid the global-enabled gate
    reg.gauge("fleet_gauge")._value = gauge
    h = reg.histogram("fleet_ms")
    for s in samples:
        h.count += 1
        h.total += s
        h.min = s if h.min is None else min(h.min, s)
        h.max = s if h.max is None else max(h.max, s)
        h._samples.append(s)
    path = str(tmp_path / f"worker{rank}.metrics.json")
    obs.write_snapshot(path, reg.snapshot(include_samples=True))
    return path


def test_fleet_merge_sums_counters_maxes_gauges_merges_reservoirs(tmp_path):
    p0 = _rank_snapshot_file(tmp_path, 0, {"train_boost_rounds_total": 5},
                             2.0, [1.0, 2.0])
    p1 = _rank_snapshot_file(tmp_path, 1, {"train_boost_rounds_total": 7},
                             9.0, [3.0, 4.0])
    out = str(tmp_path / "fleet_metrics.json")
    fleet = obs.merge_snapshot_files([p0, p1], out)
    obs.validate_fleet_metrics(fleet)
    assert fleet["num_ranks"] == 2
    assert set(fleet["ranks"]) == {"0", "1"}
    agg = fleet["aggregate"]
    assert agg["counters"]["train_boost_rounds_total"] == 12  # summed
    assert agg["gauges"]["fleet_gauge"] == 9.0  # maxed
    h = agg["histograms"]["fleet_ms"]
    assert h["count"] == 4 and h["sum"] == 10.0
    assert h["min"] == 1.0 and h["max"] == 4.0
    assert h["p99"] == 4.0  # recomputed from the MERGED reservoir
    # the written artifact round-trips
    assert obs.load_fleet_metrics(out)["num_ranks"] == 2
    # per-rank labels in the Prometheus output, aggregate unlabeled
    prom = obs.render_prometheus_fleet(fleet)
    assert "lgbmtpu_train_boost_rounds_total 12" in prom
    assert 'lgbmtpu_train_boost_rounds_total{rank="0"} 5' in prom
    assert 'lgbmtpu_train_boost_rounds_total{rank="1"} 7' in prom
    assert 'lgbmtpu_fleet_ms_count{rank="1"} 2' in prom


def test_fleet_merge_survives_crashed_ranks(tmp_path):
    """The kill-path contract: rank 1 died before its first periodic
    write (no file), rank 2 left a torn file — the merge still yields a
    schema-valid artifact with the surviving rank plus the aggregate."""
    p0 = _rank_snapshot_file(tmp_path, 0, {"train_boost_rounds_total": 3},
                             1.0, [0.5])
    p1 = str(tmp_path / "worker1.metrics.json")  # never written
    p2 = str(tmp_path / "worker2.metrics.json")
    Path(p2).write_text('{"schema": "lgbmtpu-metr')  # torn mid-crash
    out = str(tmp_path / "fleet_metrics.json")
    fleet = obs.merge_snapshot_files([p0, p1, p2], out)
    obs.validate_fleet_metrics(fleet)
    assert fleet["num_ranks"] == 1
    assert sorted(fleet["skipped"]) == ["worker1.metrics.json",
                                       "worker2.metrics.json"]
    assert fleet["aggregate"]["counters"]["train_boost_rounds_total"] == 3


def test_launcher_aggregate_fleet_metrics_on_partial_fleet(tmp_path):
    """parallel/launcher.py's exit-path helper over a fleet where one
    rank crashed pre-write: file written, valid, one entry + aggregate."""
    from lightgbm_tpu.parallel.launcher import aggregate_fleet_metrics

    _rank_snapshot_file(tmp_path, 0, {"launcher_worker_spawns_total": 2},
                        0.0, [1.0])
    out = aggregate_fleet_metrics(str(tmp_path), num_machines=2)
    fleet = obs.load_fleet_metrics(out)
    assert fleet["num_ranks"] == 1 and "0" in fleet["ranks"]


def test_periodic_snapshot_writer_writes_immediately_and_stops(tmp_path):
    path = str(tmp_path / "rank.metrics.json")
    obs.counter("t_periodic_total").inc(4)
    obs.histogram("t_periodic_ms").observe(1.0)
    obs.start_periodic_snapshots(path, period_s=30.0)  # long period:
    # the immediate first write is the property under test (a worker dying
    # in round 1 must still leave a file)
    deadline = time.monotonic() + 10
    while not Path(path).exists() and time.monotonic() < deadline:
        time.sleep(0.01)
    snap = obs.load_snapshot(path)
    assert snap["counters"]["t_periodic_total"] == 4
    assert snap["histograms"]["t_periodic_ms"]["samples"] == [1.0]
    obs.counter("t_periodic_total").inc()
    obs.stop_periodic_snapshots()  # final flush makes the file exact
    assert obs.load_snapshot(path)["counters"]["t_periodic_total"] == 5


# ---------------------------------------------------------------------------
# obs CLI: serve / tail / trace subcommands + strict validation
# ---------------------------------------------------------------------------

def test_cli_dump_invalid_snapshot_exits_2_with_no_partial_report(
        tmp_path, capsys):
    bad = tmp_path / "bad.json"
    # schema header valid, body poisoned: the old CLI would print a
    # partial report before dying — now it must exit 2 with NO stdout
    bad.write_text(json.dumps({
        "schema": obs.SCHEMA, "ts": 1.0, "counters": {"x": "NaN-ish"},
        "gauges": {}, "histograms": {}, "events_total": 0}))
    assert obs_main([str(bad)]) == 2
    out = capsys.readouterr()
    assert out.out == ""
    assert "error" in out.err


def test_cli_trace_subcommand(tmp_path, capsys):
    with obs_trace.span("cli_span", n=1):
        pass
    src = str(tmp_path / "t.json")
    obs_trace.write_trace(src)
    # validate + re-emit a saved trace
    assert obs_main(["trace", src]) == 0
    doc = json.loads(capsys.readouterr().out)
    assert doc["traceEvents"][0]["name"] == "cli_span"
    # live-ring export to a file
    dst = str(tmp_path / "out.json")
    assert obs_main(["trace", "-o", dst]) == 0
    assert obs_trace.load_trace(dst)["traceEvents"]
    # invalid input exits 2
    (tmp_path / "nottrace.json").write_text("{}")
    assert obs_main(["trace", str(tmp_path / "nottrace.json")]) == 2


def test_cli_serve_subcommand_over_snapshot_file(tmp_path):
    obs.counter("t_serve_total").inc(6)
    obs.counter("degrade_disabled_total").inc()  # saved health: degraded
    spath = str(tmp_path / "snap.json")
    obs.write_snapshot(spath)
    epath = tmp_path / "events.jsonl"
    epath.write_text(json.dumps({"ts": 1.0, "kind": "boost_round"}) + "\n")
    srv = serve_snapshot(spath, port=0, events_path=str(epath))
    try:
        code, prom = _get(srv.url("/metrics"))
        assert code == 200 and "lgbmtpu_t_serve_total 6" in prom
        code, hz = _get(srv.url("/healthz"))
        assert json.loads(hz)["status"] == "degraded"
        code, ev = _get(srv.url("/events?tail=5"))
        assert json.loads(ev.splitlines()[0])["kind"] == "boost_round"
    finally:
        srv.stop()
    notsnap = tmp_path / "notsnap.json"
    notsnap.write_text("{}")
    with pytest.raises(ValueError):
        serve_snapshot(str(notsnap))
    assert obs_main(["serve", str(tmp_path / "missing.json")]) == 2


def test_cli_tail_subcommand(tmp_path, capsys):
    p = tmp_path / "events.jsonl"
    lines = [{"ts": float(i), "kind": "boost_round", "iteration": i}
             for i in range(5)]
    p.write_text("".join(json.dumps(r) + "\n" for r in lines)
                 + '{"ts": 9.0, "kind": "torn')  # crashed-worker tail
    assert obs_main(["tail", str(p), "-n", "2"]) == 0
    out = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [r["iteration"] for r in out] == [3, 4]  # newest N, torn skipped
    assert obs_main(["tail", str(p), "-n", "10", "--kind", "boost_round"]
                    ) == 0
    assert len(capsys.readouterr().out.splitlines()) == 5
    # the `tail -n 0` idiom prints NO history, not the whole file
    assert obs_main(["tail", str(p), "-n", "0"]) == 0
    assert capsys.readouterr().out == ""
    assert obs_main(["tail", str(tmp_path / "missing.jsonl")]) == 2


# ---------------------------------------------------------------------------
# ACCEPTANCE: /metrics during a LIVE engine.train; /healthz flips on an
# injected fault without killing training
# ---------------------------------------------------------------------------

def test_metrics_endpoint_live_during_train_and_healthz_fault_flip(
        monkeypatch, tmp_path):
    import jax.numpy as jnp

    from lightgbm_tpu.utils import degrade, faults

    rng = np.random.RandomState(11)
    X = rng.randn(600, 6)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0).astype(float)

    seen = {}

    def mid_train_probe(env):
        if env.iteration == 1 and "prom" not in seen:
            srv = obs_server.get_server()
            assert srv is not None, "metrics_port= did not start the server"
            seen["port"] = srv.port
            _, seen["prom"] = _get(srv.url("/metrics"))
            _, hz = _get(srv.url("/healthz"))
            seen["health_before"] = json.loads(hz)["status"]
            # injected fault (LGBMTPU_FAULT harness): the Pallas histogram
            # dispatcher fires mid-run and degrades to XLA — training must
            # survive, /healthz must flip
            monkeypatch.setenv("LGBMTPU_FAULT", "pallas_hist:0")
            faults.reset()
            from lightgbm_tpu.ops.histogram import histogram_multi

            n, f, tile, bins = 128, 2, 2, 8
            histogram_multi(
                jnp.asarray(rng.randint(0, bins, (n, f)), jnp.int16),
                jnp.ones((n,), jnp.float32), jnp.ones((n,), jnp.float32),
                jnp.ones((n,), bool),
                jnp.zeros((n,), jnp.int32), 0, tile, bins)
            monkeypatch.delenv("LGBMTPU_FAULT")
            faults.reset()
            code, hz = _get(srv.url("/healthz"))
            seen["health_after"] = json.loads(hz)["status"]
            seen["code_after"] = code

    mid_train_probe.order = 0

    degrade.reset()
    try:
        bst = lgb.train(
            {"objective": "binary", "num_leaves": 7, "verbosity": -1,
             "metrics_port": 0,
             "trace_file": str(tmp_path / "train_trace.json")},
            lgb.Dataset(X, label=y), num_boost_round=4,
            callbacks=[mid_train_probe])
    finally:
        degrade.reset()

    # training survived the fault and finished every round
    assert bst.current_iteration() == 4
    # /metrics DURING training carried the train family (serve counters
    # appear once predict runs; assert them post-predict below)
    assert "lgbmtpu_train_boost_rounds_total" in seen["prom"]
    assert "lgbmtpu_device_dispatches_total" in seen["prom"]
    assert seen["health_before"] == "ok"
    assert seen["health_after"] == "degraded" and seen["code_after"] == 200

    # the engine-started server is still live after train (long-lived
    # serving processes keep scraping it); serve family appears once a
    # predict has run
    bst.predict(X, raw_score=True)
    bst.predict(X, raw_score=True)
    srv = obs_server.get_server()
    assert srv is not None and srv.port == seen["port"]
    _, prom = _get(srv.url("/metrics"))
    assert "lgbmtpu_predict_requests_total" in prom
    assert 'lgbmtpu_predict_warm_latency_ms{bucket="' in prom
    obs_server.stop_server()

    # trace_file= left a Perfetto-loadable trace covering the run
    doc = obs_trace.load_trace(str(tmp_path / "train_trace.json"))
    names = {ev["name"] for ev in doc["traceEvents"]}
    assert "train" in names and "boost_round" in names
