"""Continual-training pins (round 19, ISSUE 14 — lightgbm_tpu/continual).

The train-while-serving contract: a ContinualRunner beside a live
ServingRuntime completes refit AND append-trees rollovers under
concurrent predict load with every response bitwise equal to a cold
``Booster.predict`` of a legitimately-published ensemble version, the
warm 1-dispatch/1-accounted-sync predict budget pinned ACROSS a rollover
(telemetry + span tracing + HTTP server ON), zero Overloaded sheds
attributable to the swap, ``model_staleness_s`` visibly dropping at each
rollover on ``/metrics`` — and a crash at the ``continual_swap`` fault
site resumes from the fleet manifest with the previous ensemble still
serving and no torn pack ever published.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.continual import ContinualError
from lightgbm_tpu.continual.refit import make_refit_entry, refit_leaves
from lightgbm_tpu.obs import metrics as obs
from lightgbm_tpu.serve import ServingRuntime
from lightgbm_tpu.utils import checkpoint as ckpt
from lightgbm_tpu.utils.sanitizer import DispatchCounter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
}

PARAMS = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
          "min_data_in_leaf": 5}


@pytest.fixture(autouse=True)
def _fresh_registry():
    from lightgbm_tpu.obs import server as _srv
    from lightgbm_tpu.obs import trace as _trc

    obs.reset()
    _trc.reset_trace()
    yield
    _srv.stop_server()
    obs.reset()
    _trc.reset_trace()


def _setup(n=500, f=6, rounds=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    ds = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params=dict(PARAMS), train_set=ds)
    for _ in range(rounds):
        bst.update()
    return bst, ds, X, y, rng


def _chunk(rng, n=150, f=6):
    Xc = rng.randn(n, f)
    yc = (Xc[:, 0] + 0.5 * Xc[:, 1] > 0).astype(float)
    return Xc, yc


def _trees(bst):
    s = bst.model_to_string()
    return s[s.index("Tree=0"):s.index("end of trees")]


def _prom_value(url, name):
    text = urllib.request.urlopen(url, timeout=10).read().decode()
    for line in text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return None


# ---------------------------------------------------------------------------
# THE acceptance: rollover under load
# ---------------------------------------------------------------------------

def test_rollover_under_concurrent_load_bitwise_and_budget_pinned(tmp_path):
    """>=2 refit + >=1 append rollovers while concurrent callers hammer
    the serving runtime: every response bitwise-matches a legitimately
    published ensemble version, zero sheds, warm budget pinned across
    the swap with telemetry + tracing + the HTTP server ON, and the
    staleness gauges drop at each rollover on the live /metrics."""
    from lightgbm_tpu.obs import server as _srv

    srv = _srv.start_server(0)
    bst, ds, X, y, rng = _setup()
    rt = ServingRuntime(bst, max_wait_ms=5, shed_unhealthy=False)
    cr = lgb.continual_train(
        bst, {"update_every_rows": 120, "append_trees": 2},
        runtime=rt, reference=ds, state_dir=str(tmp_path), start=False)

    Q = rng.randn(64, 6)
    slices = [Q[i * 16:(i + 1) * 16] for i in range(4)]
    versions = [bst]  # every ensemble ever published
    responses = []
    stop = threading.Event()
    errors = []

    def caller():
        try:
            while not stop.is_set():
                for i, s in enumerate(slices):
                    responses.append((i, rt.predict(
                        s, raw_score=True, timeout=60)))
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=caller) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        # 2 refit rollovers + 1 append rollover, live
        for kind_want in ("refit", "refit", "append"):
            Xc, yc = _chunk(rng)
            cr.ingest(Xc, yc)
            stale_rows = obs.gauge("model_staleness_rows").value
            assert stale_rows >= 150, stale_rows
            kind = cr.update(kind_want)
            assert kind == kind_want
            versions.append(cr.booster)
            assert obs.gauge("model_staleness_rows").value == 0.0
        time.sleep(0.2)  # let callers observe the final version
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errors, errors

    # bitwise: every in-flight response equals SOME published version's
    # cold predict; the post-rollover predict equals the FINAL version
    refs = [[v.predict(s, raw_score=True) for s in slices]
            for v in versions]
    for i, got in responses:
        assert any(np.array_equal(refs[v][i], got)
                   for v in range(len(versions))), (
            f"response for slice {i} matches no published ensemble")
    final = rt.predict(Q[:32], raw_score=True, timeout=60)
    assert np.array_equal(final, versions[-1].predict(Q[:32],
                                                      raw_score=True))
    assert cr.booster.num_trees() == 6  # 4 + append_trees

    # zero sheds attributable to the swaps
    assert obs.counter("serve_shed_total").value == 0

    # warm budget ACROSS the rollovers: 1 dispatch + 1 accounted sync,
    # no recompile — telemetry + tracing + HTTP server all ON
    rt.predict(Q[:32], raw_score=True, timeout=60)  # warm the rung
    with DispatchCounter() as d:
        rt.predict(Q[:32], raw_score=True, timeout=60)
    assert d.dispatches == 1, d.dispatches
    assert d.host_syncs == 1, d.host_syncs
    d.assert_no_recompile("warm predict across continual rollovers")

    # staleness visible on the LIVE endpoint: ingest raises it, the
    # rollover drops it
    Xc, yc = _chunk(rng)
    cr.ingest(Xc, yc)
    up = _prom_value(srv.url("/metrics"), "lgbmtpu_model_staleness_rows")
    assert up is not None and up >= 150
    cr.update("refit")
    down = _prom_value(srv.url("/metrics"), "lgbmtpu_model_staleness_rows")
    assert down == 0.0
    # rollover events carry the sanitizer ledger deltas
    evs = obs.events("continual_rollover")
    assert len(evs) == 4
    assert all("dispatches" in e and "host_syncs" in e for e in evs)
    assert {e["mode"] for e in evs} == {"refit", "append"}
    rt.stop()


# ---------------------------------------------------------------------------
# refit: parity, determinism, budget, bitwise online == offline
# ---------------------------------------------------------------------------

def test_device_refit_matches_host_refit_and_budget():
    bst, ds, X, y, rng = _setup()
    Xn, yn = _chunk(rng, n=300)
    host = bst.refit(Xn, yn, decay_rate=0.9)

    clone = lgb.Booster(model_str=bst.model_to_string())
    clone._gbdt.cfg = bst._gbdt.cfg
    entry = make_refit_entry(clone._gbdt.objective, 0.9,
                             clone._gbdt.cfg.lambda_l2)
    refit_leaves(clone._gbdt, Xn, yn, entry=entry)
    a = host.predict(X[:64], raw_score=True)
    b = clone.predict(X[:64], raw_score=True)
    # device f32 vs the host's f64 accumulation: numerically equal to
    # well under any split threshold's resolution
    assert np.abs(a - b).max() < 1e-4, np.abs(a - b).max()

    # determinism: the same refit twice is BITWISE the same model
    clone2 = lgb.Booster(model_str=bst.model_to_string())
    clone2._gbdt.cfg = bst._gbdt.cfg
    with DispatchCounter() as d:
        refit_leaves(clone2._gbdt, Xn, yn, entry=entry)
    assert clone.model_to_string() == clone2.model_to_string()
    # warm refit: ONE donated dispatch + ONE accounted sync, no recompile
    assert d.dispatches == 1 and d.host_syncs == 1, (d.dispatches,
                                                     d.host_syncs)
    d.assert_no_recompile("warm continual refit")


def test_multiclass_refit_matches_host_and_budget():
    """Round 20: the k-aware scan renews a multiclass ensemble — device
    refit vs the host ``Booster.refit`` recipe, determinism, and the
    1-dispatch/1-sync budget all hold at k=3."""
    rng = np.random.RandomState(7)
    X = rng.randn(400, 6)
    y = rng.randint(0, 3, 400).astype(float)
    params = {"objective": "multiclass", "num_class": 3, "num_leaves": 7,
              "verbosity": -1, "min_data_in_leaf": 5}
    bst = lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=4)
    Xn = rng.randn(400, 6)
    yn = rng.randint(0, 3, 400).astype(float)
    host = bst.refit(Xn, yn, decay_rate=0.9)

    clone = lgb.Booster(model_str=bst.model_to_string())
    clone._gbdt.cfg = bst.cfg
    entry = make_refit_entry(clone._gbdt.objective, 0.9,
                             clone._gbdt.cfg.lambda_l2, k=3)
    refit_leaves(clone._gbdt, Xn, yn, entry=entry)
    a = host.predict(X[:64], raw_score=True)
    b = clone.predict(X[:64], raw_score=True)
    assert np.abs(a - b).max() < 1e-4, np.abs(a - b).max()

    # determinism + budget: same refit twice is BITWISE the same model,
    # one donated dispatch + one accounted sync, no recompile
    clone2 = lgb.Booster(model_str=bst.model_to_string())
    clone2._gbdt.cfg = bst.cfg
    with DispatchCounter() as d:
        refit_leaves(clone2._gbdt, Xn, yn, entry=entry)
    assert clone.model_to_string() == clone2.model_to_string()
    assert d.dispatches == 1 and d.host_syncs == 1, (d.dispatches,
                                                     d.host_syncs)
    d.assert_no_recompile("warm multiclass refit")


def test_weighted_refit_matches_host_and_weight_flows():
    """Round 20: ``weight=`` reaches objective.get_gradients — device vs
    the host ``Booster.refit(weight=...)``, and weighted != unweighted."""
    bst, ds, X, y, rng = _setup()
    Xn, yn = _chunk(rng, n=300)
    w = rng.uniform(0.5, 2.0, len(yn))
    host = bst.refit(Xn, yn, decay_rate=0.9, weight=w)

    clone = lgb.Booster(model_str=bst.model_to_string())
    clone._gbdt.cfg = bst._gbdt.cfg
    refit_leaves(clone._gbdt, Xn, yn, weight=w)
    a = host.predict(X[:64], raw_score=True)
    b = clone.predict(X[:64], raw_score=True)
    assert np.abs(a - b).max() < 1e-4, np.abs(a - b).max()

    # the weights actually flow: unweighted refit lands elsewhere
    unw = lgb.Booster(model_str=bst.model_to_string())
    unw._gbdt.cfg = bst._gbdt.cfg
    refit_leaves(unw._gbdt, Xn, yn)
    assert np.abs(b - unw.predict(X[:64], raw_score=True)).max() > 1e-7


def test_fleet_refit_one_dispatch_matches_per_lane_solo():
    """The batched twin: B lanes renewed in ONE donated dispatch + ONE
    accounted sync, each lane's result equal (to f32 resolution) to a
    solo refit_leaves of that lane — weighted and unweighted."""
    from lightgbm_tpu.continual import fleet_refit_leaves

    rng = np.random.RandomState(11)
    B, N, F = 4, 400, 6
    X = rng.randn(N, F)
    labels = np.stack([(X[:, 0] + rng.randn(N) > 0).astype(float)
                       for _ in range(B)])
    def lanes():
        return [lgb.train(dict(PARAMS), lgb.Dataset(X, label=labels[b]),
                          num_boost_round=3) for b in range(B)]

    fb = lanes()
    Xn = rng.randn(N, F)
    labels_n = np.stack([(Xn[:, 0] > 0).astype(float) for _ in range(B)])
    W = rng.uniform(0.5, 2.0, (B, N))

    for weights in (None, W):
        fb2 = lanes()
        solo = []
        for b in range(B):
            cp = lgb.Booster(model_str=fb[b].model_to_string())
            cp._gbdt.cfg = fb[b]._gbdt.cfg
            refit_leaves(cp._gbdt, Xn, labels_n[b],
                         weight=None if weights is None else weights[b])
            solo.append(cp)
        with DispatchCounter() as d:
            fleet_refit_leaves(fb2, Xn, labels_n, weights=weights)
        assert d.dispatches == 1 and d.host_syncs == 1, (d.dispatches,
                                                         d.host_syncs)
        for b in range(B):
            ps = np.asarray(solo[b].predict(Xn[:64], raw_score=True))
            pf = np.asarray(fb2[b].predict(Xn[:64], raw_score=True))
            assert np.abs(ps - pf).max() < 1e-5, (weights is not None, b)

    # envelope: a multiclass lane refuses loudly
    ymc = rng.randint(0, 3, N).astype(float)
    mc = lgb.train({"objective": "multiclass", "num_class": 3,
                    "num_leaves": 7, "verbosity": -1,
                    "min_data_in_leaf": 5},
                   lgb.Dataset(X, label=ymc), num_boost_round=2)
    with pytest.raises(ContinualError):
        fleet_refit_leaves([mc], Xn, labels_n[:1])


def test_runner_rollovers_bitwise_equal_offline_application(tmp_path):
    """The under-load runner path IS the offline path: replaying the
    same ingest/update sequence offline reproduces the runner's ensemble
    tree-bitwise (refit and append both)."""
    bst, ds, X, y, rng = _setup()
    cr = lgb.continual_train(bst, {"append_trees": 2}, reference=ds,
                             start=False)
    chunks = [_chunk(rng) for _ in range(3)]
    cr.ingest(*chunks[0])
    cr.update("refit")
    cr.ingest(*chunks[1])
    cr.ingest(*chunks[2])
    cr.update("append")

    # offline: same primitives, by hand
    off = lgb.Booster(model_str=bst.model_to_string())
    off._gbdt.cfg = bst._gbdt.cfg
    entry = make_refit_entry(off._gbdt.objective,
                             off._gbdt.cfg.refit_decay_rate,
                             off._gbdt.cfg.lambda_l2)
    refit_leaves(off._gbdt, chunks[0][0], chunks[0][1], entry=entry)
    Xw = np.concatenate([c[0] for c in chunks])
    yw = np.concatenate([c[1] for c in chunks])
    params = dict(PARAMS)
    off2 = lgb.train(params, lgb.Dataset(Xw, label=yw, reference=ds),
                     num_boost_round=2, init_model=off)
    assert _trees(cr.booster) == _trees(off2)
    q = rng.randn(40, 6)
    assert np.array_equal(cr.booster.predict(q), off2.predict(q))


# ---------------------------------------------------------------------------
# crash mid-rollover: previous ensemble serves on, manifest resumes
# ---------------------------------------------------------------------------

_CRASH_COMMON = """
import os, sys, json
import numpy as np
sys.path.insert(0, {repo!r})
import lightgbm_tpu as lgb

rng = np.random.RandomState(11)
X = rng.randn(400, 5)
y = (X @ rng.randn(5) > 0).astype(np.float64)
ds = lgb.Dataset(X, label=y)
bst = lgb.Booster(params={params!r}, train_set=ds)
for _ in range(4):
    bst.update()
rt = lgb.serve(bst, {{"serve_max_wait_ms": 2}})
Q = rng.randn(32, 5)
c1 = (rng.randn(150, 5), None)
c1 = (c1[0], (c1[0] @ np.ones(5) > 0).astype(float))
c2 = (rng.randn(150, 5), None)
c2 = (c2[0], (c2[0] @ np.ones(5) > 0).astype(float))
"""

_CRASH_PART1 = _CRASH_COMMON + """
cr = lgb.continual_train(bst, {{}}, runtime=rt, reference=ds,
                         state_dir={d!r}, start=False)
cr.ingest(*c1)
cr.update("refit")
print("PRED1=" + json.dumps(
    rt.predict(Q, raw_score=True, timeout=60).tolist()), flush=True)
cr.ingest(*c2)
cr.update("refit")  # armed: continual_swap:2 crashes here
print("COMPLETED_WITHOUT_FAULT", flush=True)
"""

_CRASH_PART2 = _CRASH_COMMON + """
cr = lgb.continual_train(bst, {{}}, runtime=rt, reference=ds,
                         state_dir={d!r}, resume=True, start=False)
print("SEQ=%d" % cr.seq, flush=True)
print("PRED2=" + json.dumps(
    rt.predict(Q, raw_score=True, timeout=60).tolist()), flush=True)
"""


def test_crash_mid_rollover_resumes_previous_still_serving(tmp_path):
    """LGBMTPU_FAULT=continual_swap:2: update 2's durable checkpoint
    lands but the swap never happens — the process's served predictions
    stayed on ensemble seq-1 (no torn pack, no seq-2 rollover event),
    and a restarted runner resumes seq 2 from the manifest bitwise."""
    from lightgbm_tpu.utils.faults import CRASH_EXIT_CODE

    d = str(tmp_path)
    events = os.path.join(d, "events.jsonl")
    env = dict(os.environ, LGBMTPU_FAULT="continual_swap:2",
               LGBMTPU_EVENTS_FILE=events, **_CPU_ENV)
    env.pop("PYTEST_CURRENT_TEST", None)
    r = subprocess.run(
        [sys.executable, "-c",
         _CRASH_PART1.format(repo=REPO, d=d, params=PARAMS)],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == CRASH_EXIT_CODE, (r.stdout, r.stderr)
    assert "COMPLETED_WITHOUT_FAULT" not in r.stdout
    pred1 = json.loads(r.stdout.split("PRED1=")[1].splitlines()[0])

    # the update WAS durably checkpointed (seq 2 fleet-valid) ...
    found = ckpt.latest_valid_fleet_manifest(d, 1)
    assert found is not None and found[0] == 2, found
    # ... but never PUBLISHED: the event trail shows the seq-1 rollover,
    # the armed fault, and no seq-2 rollover
    with open(events, encoding="utf-8") as fh:
        evs = [json.loads(line) for line in fh if line.strip()]
    rollovers = [e for e in evs if e["kind"] == "continual_rollover"]
    assert [e["seq"] for e in rollovers] == [1]
    assert any(e["kind"] == "fault" and e["site"] == "continual_swap"
               for e in evs)

    # offline reference (no fault): seq-1 and seq-2 ensembles
    os.makedirs(os.path.join(d, "ref"), exist_ok=True)
    env2 = dict(os.environ, **_CPU_ENV)
    env2.pop("PYTEST_CURRENT_TEST", None)
    r_ref = subprocess.run(
        [sys.executable, "-c",
         _CRASH_PART1.format(repo=REPO, d=os.path.join(d, "ref"),
                             params=PARAMS)],
        env=env2, capture_output=True, text=True, timeout=300)
    assert "COMPLETED_WITHOUT_FAULT" in r_ref.stdout, (r_ref.stdout,
                                                       r_ref.stderr)
    ref1 = json.loads(r_ref.stdout.split("PRED1=")[1].splitlines()[0])
    # the crashed process served the seq-1 ensemble to the end
    assert pred1 == ref1

    # resume: the restarted runner picks seq 2 up from the manifest and
    # serves it — bitwise the ensemble the fault interrupted
    r2 = subprocess.run(
        [sys.executable, "-c",
         _CRASH_PART2.format(repo=REPO, d=d, params=PARAMS)],
        env=env2, capture_output=True, text=True, timeout=300)
    assert r2.returncode == 0, (r2.stdout, r2.stderr)
    assert "SEQ=2" in r2.stdout
    pred2 = json.loads(r2.stdout.split("PRED2=")[1].splitlines()[0])
    r2_ref = subprocess.run(
        [sys.executable, "-c",
         _CRASH_PART2.format(repo=REPO, d=os.path.join(d, "ref"),
                             params=PARAMS)],
        env=env2, capture_output=True, text=True, timeout=300)
    assert r2_ref.returncode == 0, (r2_ref.stdout, r2_ref.stderr)
    ref2 = json.loads(r2_ref.stdout.split("PRED2=")[1].splitlines()[0])
    assert pred2 == ref2


# ---------------------------------------------------------------------------
# the mutation/serve race surface (ISSUE 14 satellite 1)
# ---------------------------------------------------------------------------

def test_concurrent_inplace_refits_under_serving_load_evict_stale_packs():
    """Hammer coalesced predicts while the trainer thread refits the
    SERVED model in place: every response is bitwise one of the refit
    generations (the pack lock makes bump+lookup atomic and the build
    retry excludes torn packs), and the versioned cache EVICTS — the
    stale-pack eviction counter grows under swap load."""
    bst, ds, X, y, rng = _setup()
    g = bst._gbdt
    entry = make_refit_entry(g.objective, 0.9, g.cfg.lambda_l2)
    rt = ServingRuntime(bst, max_wait_ms=2, shed_unhealthy=False)
    Q = rng.randn(16, 6)
    stop = threading.Event()
    got = []
    errors = []

    def caller():
        try:
            while not stop.is_set():
                got.append(np.array(rt.predict(Q, raw_score=True,
                                               timeout=60)))
        except BaseException as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=caller) for _ in range(2)]
    for t in threads:
        t.start()
    generations = [bst.predict(Q, raw_score=True)]
    try:
        for k in range(6):
            Xc, yc = _chunk(rng, n=120)
            refit_leaves(g, Xc, yc, entry=entry)  # in-place, served live
            generations.append(bst.predict(Q, raw_score=True))
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    rt.stop()
    assert not errors, errors
    assert len(got) > 0
    for resp in got:
        assert any(np.array_equal(resp, gen) for gen in generations), (
            "a response matches NO refit generation — torn pack served")
    # 7 versions through a keep-2 window: stale packs were evicted
    assert obs.counter("predict_stale_pack_evictions_total").value > 0
    assert g._pack_version >= 6


# ---------------------------------------------------------------------------
# ingest: clamp-and-count, drift, durability, validation
# ---------------------------------------------------------------------------

def test_ingest_clamps_and_counts_against_frozen_mappers(tmp_path):
    bst, ds, X, y, rng = _setup()
    cache = str(tmp_path / "ingest.bin")
    cr = lgb.continual_train(bst, {}, reference=ds, cache_path=cache,
                             start=False)
    # rows far outside the training range: clamped into edge bins,
    # counted, never rebinned
    Xc, yc = _chunk(rng, n=100)
    Xc[:10, 0] = 1e9
    Xc[:5, 1] = -1e9
    s = cr.ingest(Xc, yc)
    assert s["clamped"] >= 15
    assert obs.counter("continual_clamped_values_total").value >= 15
    # the frozen mappers binned it: the durable cache holds exactly the
    # reference transform
    from lightgbm_tpu.io.stream import BinCacheStream

    st = BinCacheStream(cache)
    assert st.n_rows == 100
    swept = np.concatenate([v.copy() for _, v in st.chunks(64)])
    assert np.array_equal(swept, ds.binner.transform(Xc).astype(st.dtype))

    # drift telemetry: a label-shifted chunk moves the gauge
    Xs, _ = _chunk(rng, n=100)
    s2 = cr.ingest(Xs, np.ones(100))
    assert s2["label_drift"] > 0
    assert obs.gauge("continual_label_drift").value == s2["label_drift"]
    assert len(obs.events("continual_chunk")) == 2
    assert BinCacheStream(cache).n_rows == 200

    # non-finite labels refuse at the gate
    with pytest.raises(lgb.LightGBMError):
        cr.ingest(Xs[:3], np.asarray([0.0, np.nan, 1.0]))


def test_staleness_slo_flips_healthz_degraded():
    from lightgbm_tpu.obs import server as _srv

    bst, ds, X, y, rng = _setup()
    cr = lgb.continual_train(bst, {}, reference=ds, start=False,
                             staleness_slo_s=0.05)
    code, body = _srv.health()
    assert code == 200 and body["status"] == "ok"
    cr.ingest(*_chunk(rng))
    time.sleep(0.1)
    cr._publish_staleness()
    assert obs.gauge("continual_staleness_exceeded").value == 1.0
    code, body = _srv.health()
    assert code == 200 and body["status"] == "degraded"
    assert any(p.get("gauge") == "continual_staleness_exceeded"
               for p in body["problems"])
    cr.update("refit")
    assert obs.gauge("continual_staleness_exceeded").value == 0.0
    assert _srv.health()[1]["status"] == "ok"


def test_runner_thread_drives_row_policy():
    bst, ds, X, y, rng = _setup()
    cr = lgb.continual_train(bst, {"update_every_rows": 100},
                             reference=ds, start=True)
    try:
        before = obs.counter("continual_rollovers_total").value
        cr.ingest(*_chunk(rng, n=150))
        deadline = time.monotonic() + 20
        while (obs.counter("continual_rollovers_total").value == before
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert obs.counter("continual_rollovers_total").value == before + 1
        assert obs.counter("continual_refits_total").value >= 1
    finally:
        cr.stop()


def test_time_policy_update_every_s():
    bst, ds, X, y, rng = _setup()
    cr = lgb.continual_train(bst, {"update_every_s": 0.05},
                             reference=ds, start=False)
    cr.ingest(*_chunk(rng, n=10))
    time.sleep(0.08)  # the oldest un-incorporated row ages past the bound
    assert cr._due()
    assert cr.update("auto") == "refit"
    assert not cr._due()


# ---------------------------------------------------------------------------
# envelope refusals: loud, typed, never silent
# ---------------------------------------------------------------------------

def test_envelope_refusals():
    # multiclass: refused through round 19; round 21's k-aware scan makes
    # it ELIGIBLE — pin that the runner refits a k=3 model without error
    rng = np.random.RandomState(1)
    X = rng.randn(300, 5)
    y = rng.randint(0, 3, 300).astype(float)
    mc = lgb.Booster(params={"objective": "multiclass", "num_class": 3,
                             "num_leaves": 7, "verbosity": -1},
                     train_set=lgb.Dataset(X, label=y))
    mc.update()
    cr = lgb.continual_train(mc, {}, start=False)
    cr.ingest(X[:50], y[:50])
    assert cr.update("refit") == "refit"

    # append without frozen mappers refuses
    bst, ds, _, _, rng2 = _setup()
    plain = lgb.Booster(model_str=bst.model_to_string())
    plain._gbdt.cfg = bst._gbdt.cfg
    cr2 = lgb.continual_train(plain, {"append_trees": 2}, start=False)
    cr2.ingest(*_chunk(rng2))
    with pytest.raises(ContinualError):
        cr2.update("append")

    # a runner over a model the runtime does not serve refuses up front
    rt = ServingRuntime(bst, max_wait_ms=2, shed_unhealthy=False,
                        start=False)
    with pytest.raises(lgb.LightGBMError):
        lgb.continual_train(bst, {}, runtime=rt, model_name="other",
                            start=False)
    rt.stop()


def test_auto_update_falls_back_to_append_when_refit_ineligible():
    """A refit-ineligible ensemble (linear leaves — multiclass became
    eligible in round 21) with append_trees configured: auto updates
    take the append path instead of failing toward the refit the
    envelope already refused."""
    rng = np.random.RandomState(2)
    Xm = rng.randn(300, 5)
    ym = (Xm[:, 0] + 0.1 * rng.randn(300)).astype(float)
    dsm = lgb.Dataset(Xm, label=ym)
    lin = lgb.Booster(params={"objective": "regression", "linear_tree": True,
                              "num_leaves": 5, "verbosity": -1},
                      train_set=dsm)
    lin.update()
    cr = lgb.continual_train(lin, {"update_every_rows": 50,
                                   "append_trees": 1},
                             reference=dsm, start=False)
    cr.ingest(Xm[:60], ym[:60])
    assert cr.update("auto") == "append"
    assert cr.booster.num_trees() == 2  # 1 + 1 appended iteration


def test_window_overflow_evicts_pending_rows_honestly():
    """Rows evicted from the rolling window before any update could
    incorporate them leave the staleness accounting AND are counted as
    lost (continual_window_evicted_pending_rows_total) — never silently
    reported as incorporated."""
    bst, ds, X, y, rng = _setup()
    cr = lgb.continual_train(bst, {}, reference=ds, start=False,
                             window_rows=100)
    for _ in range(4):
        Xc, yc = _chunk(rng, n=60)
        cr.ingest(Xc, yc)
    # cap 100 holds ONE 60-row chunk: three chunks evicted while pending
    assert obs.counter(
        "continual_window_evicted_pending_rows_total").value == 180
    assert obs.gauge("model_staleness_rows").value == 60.0
    assert obs.events("continual_window_overflow")
    cr.update("refit")
    assert obs.gauge("model_staleness_rows").value == 0.0


def test_runner_thread_failure_backoff_and_healthz():
    """A deterministically failing update (linear-leaf refit-only runner
    — refit refuses linear models and no append is configured) backs off
    exponentially instead of retrying at tick cadence, and the failure
    counter flips /healthz degraded."""
    from lightgbm_tpu.obs import server as _srv

    rng = np.random.RandomState(3)
    Xm = rng.randn(200, 4)
    ym = (Xm[:, 0] + 0.1 * rng.randn(200)).astype(float)
    mc = lgb.Booster(params={"objective": "regression", "linear_tree": True,
                             "num_leaves": 5, "verbosity": -1},
                     train_set=lgb.Dataset(Xm, label=ym))
    mc.update()
    cr = lgb.continual_train(mc, {"update_every_rows": 10}, start=True)
    try:
        cr.ingest(Xm[:20], ym[:20])
        time.sleep(1.2)
    finally:
        cr.stop()
    fails = obs.counter("continual_update_failures_total").value
    assert 1 <= fails <= 3, fails  # ~24 ticks elapsed; backoff held
    code, body = _srv.health()
    assert code == 200 and body["status"] == "degraded"
    assert any(p.get("counter") == "continual_update_failures_total"
               for p in body["problems"])
