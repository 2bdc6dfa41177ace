"""Request-scoped distributed tracing pins (round 24, ISSUE 20).

The tracing contract across the serve/continual/fleet runtime: every
``/predict`` response names its trace (honoring an inbound W3C
``traceparent``), cross-thread span emission takes the EXPLICIT parent
context (never the worker thread's ambient stack — the round-24 bugfix
jaxlint R21 now polices), per-request phase breakdowns land in labeled
reservoirs with zero new device pulls, the latency series carries a
trace-id exemplar, and one hedged + requeued request reconstructs as a
single connected story from the MERGED flight-recorder export — across
threads, replicas and per-rank trace files.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import metrics as obs
from lightgbm_tpu.obs import trace as _trc
from lightgbm_tpu.serve import ServingFleet, ServingRuntime
from lightgbm_tpu.utils import faults as flt


@pytest.fixture(autouse=True)
def _fresh_state():
    from lightgbm_tpu.obs import server as _srv

    obs.reset()
    _trc.reset_trace()
    _trc.configure_request_tracing(True, 1.0)
    os.environ.pop("LGBMTPU_FAULT", None)
    flt.reset()
    yield
    os.environ.pop("LGBMTPU_FAULT", None)
    flt.reset()
    _srv.stop_server()
    obs.reset()
    _trc.reset_trace()
    _trc.configure_request_tracing(True, 1.0)


def _binary_booster(n=400, f=6, rounds=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbosity": -1},
                      train_set=lgb.Dataset(X, label=y))
    for _ in range(rounds):
        bst.update()
    return bst, X


# ---------------------------------------------------------------------------
# the round-24 bugfix: explicit parent context wins over the worker
# thread's ambient span stack
# ---------------------------------------------------------------------------

def test_cross_thread_span_takes_explicit_parent_two_dispatchers():
    """Two dispatcher threads, each with its OWN ambient housekeeping
    span open, emit request spans for two different requests.  Pre-fix,
    Span.__enter__ let the thread-local stack leak into parentage even
    when an explicit parent was given — each request span would file
    under its dispatcher's housekeeping span (the WRONG trace).  The pin:
    every span lands in exactly its request's trace, parented on the
    request context it was handed."""
    reqs = [_trc.mint_request_context() for _ in range(2)]
    barrier = threading.Barrier(2)

    def dispatcher(ctx):
        with _trc.span("dispatcher.housekeeping"):
            barrier.wait()  # both ambient spans are open right now
            with _trc.span("serve.request", parent=ctx, rows=1):
                pass
            _trc.record_span("serve.batch", 1e-4, ctx=ctx.sibling())

    threads = [threading.Thread(target=dispatcher, args=(c,))
               for c in reqs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)

    req_spans = _trc.spans("serve.request")
    batch_spans = _trc.spans("serve.batch")
    assert len(req_spans) == 2 and len(batch_spans) == 2
    house_traces = {s["trace"] for s in _trc.spans("dispatcher.housekeeping")}
    for ctx in reqs:
        mine = [s for s in req_spans if s["trace"] == ctx.trace_id]
        assert len(mine) == 1, "request span filed under the wrong trace"
        # parented on the handed context, not the ambient housekeeping
        assert mine[0]["psid"] == ctx.span_id
        assert mine[0]["trace"] not in house_traces
        sib = [s for s in batch_spans if s["trace"] == ctx.trace_id]
        assert len(sib) == 1 and "psid" not in sib[0]  # sibling: no parent


def test_record_span_without_identity_still_adopts_same_thread_parent():
    """The training-loop form is unchanged: on ONE thread, a record_span
    with no explicit identity nests under the open ambient span."""
    with _trc.span("boost_round", iteration=3) as sp:
        _trc.record_span("tree_growth", 1e-4, trees=1)
    rec = _trc.spans("tree_growth")[-1]
    assert rec["trace"] == sp.ctx.trace_id
    assert rec["psid"] == sp.ctx.span_id


# ---------------------------------------------------------------------------
# /predict front door: traceparent in, trace_id out — on EVERY outcome
# ---------------------------------------------------------------------------

def test_http_predict_honors_inbound_traceparent_and_echoes_header():
    from lightgbm_tpu.obs import server as _srv

    srv = _srv.start_server(0)
    bst, X = _binary_booster()
    caller_trace = _trc.new_trace_id()
    caller_span = _trc.new_span_id()
    with ServingRuntime(bst, max_wait_ms=10, shed_unhealthy=False) as rt:
        body = json.dumps({"rows": X[:4].tolist(),
                           "raw_score": True}).encode()
        req = urllib.request.Request(
            srv.url("/predict"), data=body,
            headers={"Content-Type": "application/json",
                     "traceparent": f"00-{caller_trace}-{caller_span}-01"})
        resp = urllib.request.urlopen(req, timeout=60)
        out = json.loads(resp.read().decode())
        # the request JOINED the caller's trace: body + response header
        assert out["trace_id"] == caller_trace
        tp_out = resp.headers.get("traceparent")
        assert tp_out is not None and tp_out.startswith(
            f"00-{caller_trace}-")
        assert tp_out.endswith("-01")
        assert np.allclose(out["predictions"],
                           bst.predict(X[:4], raw_score=True))
    # and the serve.request span descends from the caller's span
    reqs = [s for s in _trc.spans("serve.request")
            if s["trace"] == caller_trace]
    assert len(reqs) == 1
    assert reqs[0]["psid"] == caller_span
    assert reqs[0]["attrs"]["outcome"] == "ok"


def test_http_predict_error_responses_still_carry_trace_id():
    from lightgbm_tpu.obs import server as _srv

    srv = _srv.start_server(0)
    bst, _ = _binary_booster()
    caller_trace = _trc.new_trace_id()
    with ServingRuntime(bst, max_wait_ms=10, shed_unhealthy=False):
        req = urllib.request.Request(  # no "rows": a 400, not a shed
            srv.url("/predict"), data=b'{"wrong": 1}',
            headers={"traceparent":
                     f"00-{caller_trace}-{_trc.new_span_id()}-01"})
        try:
            urllib.request.urlopen(req, timeout=60)
            raise AssertionError("expected HTTP 400")
        except urllib.error.HTTPError as e:
            assert e.code == 400
            out = json.loads(e.read().decode())
            assert out["error"] == "bad_request"
            # the failed request is exactly the one the caller needs to
            # look up: its trace rides the error body AND the header
            assert out["trace_id"] == caller_trace
            assert e.headers.get("traceparent", "").startswith(
                f"00-{caller_trace}-")


def test_http_predict_mints_fresh_trace_without_inbound_header():
    bst, X = _binary_booster()
    with ServingRuntime(bst, max_wait_ms=10, shed_unhealthy=False) as rt:
        code, body, tp = rt._http_predict(
            {"rows": X[:4].tolist(), "raw_score": True})
        assert code == 200
        tid = body["trace_id"]
        assert len(tid) == 32 and int(tid, 16) != 0
        assert tp == f"00-{tid}-" + tp.split("-")[2] + "-01"
        assert _trc.spans_for_trace(tid), "no spans under the minted trace"


def test_unsampled_request_keeps_ids_but_drops_spans():
    """trace_sample=0: the response still names a trace (correlation
    never degrades) but the recorder stays empty — and the flags nibble
    of the outbound traceparent says so."""
    _trc.configure_request_tracing(True, 0.0)
    bst, X = _binary_booster()
    with ServingRuntime(bst, max_wait_ms=10, shed_unhealthy=False) as rt:
        code, body, tp = rt._http_predict(
            {"rows": X[:4].tolist(), "raw_score": True})
        assert code == 200
        assert len(body["trace_id"]) == 32
        assert tp.endswith("-00")  # unsampled flag
    assert _trc.spans("serve.request") == []
    assert _trc.spans("serve.batch") == []


# ---------------------------------------------------------------------------
# phase breakdown + exemplar: the already-accounted sync points speak
# ---------------------------------------------------------------------------

def test_phase_breakdown_reservoirs_and_latency_exemplar():
    bst, X = _binary_booster()
    with ServingRuntime(bst, max_wait_ms=10, shed_unhealthy=False) as rt:
        y = rt.predict(X[:8], raw_score=True, timeout=120)
        assert np.array_equal(y, bst.predict(X[:8], raw_score=True))
    for ph in ("queue", "coalesce", "staging", "dispatch", "sliceout"):
        h = obs.histogram(obs.labeled("serve_phase_ms", phase=ph))
        assert h.count >= 1, f"phase reservoir {ph} never fed"
        assert h.min >= 0.0
    # the request span carries the same breakdown as attributes
    rec = _trc.spans("serve.request")[-1]
    for ph in ("queue", "coalesce", "staging", "dispatch", "sliceout"):
        assert f"{ph}_ms" in rec["attrs"]
    # the latency reservoir kept a witness trace id, and the Prometheus
    # render emits it as an OpenMetrics exemplar on the count series
    ex = obs.histogram("serve_request_latency_ms").exemplar
    assert ex and ex["trace_id"] == rec["trace"]
    prom = obs.render_prometheus(obs.snapshot())
    assert f'# {{trace_id="{ex["trace_id"]}"}}' in prom


# ---------------------------------------------------------------------------
# THE acceptance: one hedged + one requeued request reconstruct
# end-to-end from the MERGED flight-recorder export
# ---------------------------------------------------------------------------

def test_hedged_and_requeued_requests_reconstruct_from_merged_export(
        tmp_path):
    from lightgbm_tpu.obs.__main__ import main as obs_main

    bst, X = _binary_booster()

    # leg 1 — a REQUEUED request on a hedge-disabled fleet (a hedge would
    # race the injected failure and deliver first, absorbing the
    # requeue): dispatch failure at stage A of the first armed
    # execution, retried exactly once onto the other replica
    fl = ServingFleet(bst, replicas=2, max_wait_ms=60, hedge_ms=0,
                      restart_backoff_ms=50, shed_unhealthy=False)
    try:
        got = fl.predict(X[:16], raw_score=True, timeout=120)  # warm
        assert np.array_equal(got, bst.predict(X[:16], raw_score=True))
        fl.predict(X[:8], raw_score=True, timeout=120)  # warm the 8-rung
        os.environ["LGBMTPU_FAULT"] = "replica_dispatch:0"
        h = fl.submit(X[:8], raw_score=True)
        y = fl.result(h, timeout=120)
        assert np.array_equal(y, bst.predict(X[:8], raw_score=True))
        assert obs.counter("serve_requeues_total").value >= 1
    finally:
        os.environ.pop("LGBMTPU_FAULT", None)
        flt.reset()
        fl.stop()

    # leg 2 — a HEDGED request on a second fleet (the span ring spans
    # both lifetimes, exactly like a flight recorder): the armed replica
    # wedges at stage A, the 25 ms hedge dispatches a second copy, first
    # result wins, the watchdog reaps the wedged leg afterwards
    fl = ServingFleet(bst, replicas=2, max_wait_ms=60, hedge_ms=25,
                      hang_timeout_ms=2_000, restart_backoff_ms=50,
                      shed_unhealthy=False)
    try:
        got = fl.predict(X[:16], raw_score=True, timeout=120)  # warm
        os.environ["LGBMTPU_FAULT"] = "replica_hang:0"
        got = fl.predict(X[16:32], raw_score=True, timeout=120)
        assert np.array_equal(got, bst.predict(X[16:32], raw_score=True))
        assert obs.counter("serve_hedges_total").value >= 1
        # wait for the watchdog: the wedged leg's serve.leg span
        # (outcome=hang) is part of the story being reconstructed
        deadline = time.monotonic() + 30
        while (obs.counter("serve_replica_hangs_total").value < 1
               and time.monotonic() < deadline):
            time.sleep(0.02)
        assert obs.counter("serve_replica_hangs_total").value == 1
    finally:
        fl.stop()

    # split the ring across two per-"rank" trace files — request spans
    # on one lane, leg/batch/requeue/hedge records on the other — so the
    # reconstruction below can only succeed THROUGH the merge
    all_spans = _trc.spans()
    rank0 = [s for s in all_spans if s["name"] == "serve.request"]
    rank1 = [s for s in all_spans if s["name"] != "serve.request"]
    p0, p1 = str(tmp_path / "worker0.trace.json"), \
        str(tmp_path / "worker1.trace.json")
    _trc.write_trace(p0, rank0)
    _trc.write_trace(p1, rank1)
    merged = _trc.merge_trace_files([p0, p1])
    assert merged["lgbmtpu"]["merged"]["clock"] == "unix-wall"
    assert len(merged["lgbmtpu"]["merged"]["sources"]) == 2
    mspans = merged["lgbmtpu"]["spans"]

    # the REQUEUED request: its slice holds the whole story — its own
    # span (attempt=1), the failed leg, the requeue decision, and the
    # winning batch — drawn from BOTH source files
    retried = [s for s in mspans if s["name"] == "serve.request"
               and s["attrs"].get("attempt", 0) >= 1
               and s["attrs"].get("outcome") == "ok"]
    assert retried, "no request span records its retried attempt"
    sl = _trc.trace_slice(retried[0]["trace"], mspans)
    names = {s["name"] for s in sl}
    assert {"serve.request", "serve.leg", "serve.requeue",
            "serve.batch"} <= names, names
    legs = [s for s in sl if s["name"] == "serve.leg"]
    assert any(s["attrs"]["outcome"] == "error" for s in legs)
    assert all("replica" in s["attrs"] for s in legs)
    assert {s.get("src") for s in sl} == {"worker0.trace.json",
                                          "worker1.trace.json"}

    # the HEDGED request: both legs stay reachable — the hedge record,
    # the wedged original (outcome=hang), and the winning batch
    hedges = [s for s in mspans if s["name"] == "serve.hedge"]
    assert hedges and hedges[0]["attrs"]["outcome"] == "hedged"
    sl2 = _trc.trace_slice(hedges[0]["trace"], mspans)
    names2 = {s["name"] for s in sl2}
    assert {"serve.request", "serve.hedge", "serve.leg",
            "serve.batch"} <= names2, names2
    assert any(s["attrs"].get("outcome") == "hang"
               for s in sl2 if s["name"] == "serve.leg")
    assert any(s["attrs"].get("outcome") == "ok"
               for s in sl2 if s["name"] == "serve.batch")

    # CLI round-trip: merge + narrow to the requeued request's trace
    out = str(tmp_path / "slice.json")
    rc = obs_main(["trace", p0, p1, "--merge",
                   "--trace-id", retried[0]["trace"], "-o", out])
    assert rc == 0
    doc = _trc.load_trace(out)
    cli_names = {s["name"] for s in doc["lgbmtpu"]["spans"]}
    assert {"serve.request", "serve.leg", "serve.requeue",
            "serve.batch"} <= cli_names
    assert doc["lgbmtpu"]["merged"]["clock"] == "unix-wall"
    # the narrowed export is the slice, not the union
    assert len(doc["lgbmtpu"]["spans"]) == len(sl)


# ---------------------------------------------------------------------------
# launcher triad: per-rank trace files aggregate like events/metrics
# ---------------------------------------------------------------------------

def test_launcher_aggregates_per_rank_trace_files(tmp_path):
    from lightgbm_tpu.parallel.launcher import aggregate_fleet_trace

    ctx = _trc.TraceContext(_trc.new_trace_id())
    _trc.record_span("boost_round", 0.01, ctx=ctx, iteration=0)
    _trc.write_trace(str(tmp_path / "worker0.trace.json"))
    _trc.reset_trace()
    _trc.record_span("tree_growth", 0.005, parent=ctx, trees=1)
    _trc.write_trace(str(tmp_path / "worker1.trace.json"))

    merged_path = aggregate_fleet_trace(str(tmp_path), 2)
    assert merged_path == str(tmp_path / "fleet_trace.json")
    doc = _trc.load_trace(merged_path)
    srcs = {s["src"] for s in doc["lgbmtpu"]["spans"]}
    assert srcs == {"worker0.trace.json", "worker1.trace.json"}
    # rank 1's span joined rank 0's trace across files
    sl = _trc.trace_slice(ctx.trace_id, doc["lgbmtpu"]["spans"])
    assert {s["name"] for s in sl} == {"boost_round", "tree_growth"}

    # a missing rank file is a missing rank, not a crash; none -> None
    assert aggregate_fleet_trace(str(tmp_path), 4) is not None
    empty = tmp_path / "empty"
    empty.mkdir()
    assert aggregate_fleet_trace(str(empty), 2) is None
