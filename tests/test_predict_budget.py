"""Serving-side compile/dispatch budget pins (round 9, ISSUE 4).

The training loop got its executable budget in rounds 6-7
(tests/test_retrace.py); this suite pins the PREDICT side: a warm
``Booster.predict`` is one packed-cache hit (zero host re-pack), exactly
ONE device dispatch and ONE blocking pull — for single-class, multiclass
and the early-stop chunk loop — and the row-bucket ladder keeps the
traversal at one compile per bucket across arbitrary batch sizes.
Padded-vs-unpadded and one-dispatch-vs-per-class outputs are pinned
BIT-identical, so the serving layer can never drift from the reference
predict semantics silently.
"""

import numpy as np
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.models.gbdt import _predict_bucket
from lightgbm_tpu.ops import predict as predict_ops
from lightgbm_tpu.utils.sanitizer import CompileCounter, DispatchCounter


def _binary_booster(n=600, f=6, rounds=5, seed=0, **extra):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    d = lgb.Dataset(X, label=y)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
    params.update(extra)
    bst = lgb.Booster(params=params, train_set=d)
    for _ in range(rounds):
        bst.update()
    return bst, X, y


def _multiclass_booster(n=500, f=5, k=3, rounds=4, seed=1):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = rng.randint(0, k, n).astype(float)
    d = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params={"objective": "multiclass", "num_class": k,
                              "num_leaves": 7, "verbosity": -1}, train_set=d)
    for _ in range(rounds):
        bst.update()
    return bst, X


def test_bucket_ladder_shape():
    assert _predict_bucket(1) == 8
    assert _predict_bucket(7) == 8
    assert _predict_bucket(8) == 8
    assert _predict_bucket(128) == 128
    assert _predict_bucket(129) == 256
    assert _predict_bucket(4000) == 4096


def test_warm_predict_is_one_dispatch_one_sync_zero_repack():
    """The steady-state serving contract: packed cache hit (no _stacked
    call), 1 dispatch, 1 blocking pull, 0 traces/compiles."""
    bst, X, _ = _binary_booster()
    bst.predict(X, raw_score=True)  # warm: packs + compiles the bucket

    g = bst._gbdt
    packs = []
    orig = g._stacked

    def counting_stacked(*a, **kw):
        packs.append(1)
        return orig(*a, **kw)

    g._stacked = counting_stacked
    try:
        with DispatchCounter() as d:
            bst.predict(X, raw_score=True)
    finally:
        g._stacked = orig
    assert not packs, "warm predict re-packed the ensemble host-side"
    assert d.dispatches == 1, d.dispatches
    assert d.host_syncs == 1, d.host_syncs
    d.assert_no_recompile("warm single-class predict_raw")


def test_bucket_ladder_compiles_once_per_bucket():
    """N in {1, 7, 128, 129, 4000} -> buckets {8, 8, 128, 256, 4096}: at
    most one compile per NEW bucket, zero on revisit (ISSUE acceptance)."""
    bst, _, _ = _binary_booster(n=4096)
    rng = np.random.RandomState(7)
    X = rng.randn(4000, 6)
    bst.predict(X[:1], raw_score=True)  # warm bucket 8

    with CompileCounter() as c:
        bst.predict(X[:1], raw_score=True)
        bst.predict(X[:7], raw_score=True)  # same bucket as N=1
    c.assert_no_recompile("N in {1,7} share the 8-bucket")

    for n in (128, 129, 4000):
        with CompileCounter() as cold:
            bst.predict(X[:n], raw_score=True)
        assert cold.compiles >= 1, f"N={n} should open a new bucket"
        with CompileCounter() as warm:
            bst.predict(X[:n], raw_score=True)
        warm.assert_no_recompile(f"bucket revisit at N={n}")


def test_padded_output_bit_identical_to_unpadded(monkeypatch):
    """Rows traverse independently: the bucket padding may NEVER change a
    result bit (the property that makes the ladder safe to default on)."""
    bst, X, _ = _binary_booster()
    padded = bst.predict(X[:129], raw_score=True)
    monkeypatch.setenv("LGBMTPU_PREDICT_BUCKETS", "0")
    unpadded = bst.predict(X[:129], raw_score=True)
    assert np.array_equal(padded, unpadded)

    bm, Xm = _multiclass_booster()
    monkeypatch.delenv("LGBMTPU_PREDICT_BUCKETS")
    p = bm.predict(Xm[:37], raw_score=True)
    monkeypatch.setenv("LGBMTPU_PREDICT_BUCKETS", "0")
    u = bm.predict(Xm[:37], raw_score=True)
    assert np.array_equal(p, u)


def test_multiclass_one_dispatch_and_bitwise_vs_per_class():
    """Multiclass raw prediction is ONE dispatch (the round-6 per-class
    host loop was k dispatches) and bit-identical to the per-class path."""
    bst, X = _multiclass_booster()
    k = bst.num_model_per_iteration()
    new = bst.predict(X, raw_score=True)  # warm + result

    with DispatchCounter() as d:
        again = bst.predict(X, raw_score=True)
    assert d.dispatches == 1, d.dispatches
    assert d.host_syncs == 1, d.host_syncs
    d.assert_no_recompile("warm multiclass predict_raw")
    assert np.array_equal(new, again)

    # the replaced implementation: one predict_raw_values per class slice
    g = bst._gbdt
    s = g._packed(0, -1)
    x = jnp.asarray(np.asarray(X, np.float32))
    parts = []
    for c in range(k):
        sel = slice(c, s["T"], k)
        parts.append(predict_ops.predict_raw_values(
            x, s["split_feature"][sel], s["threshold"][sel],
            s["default_left"][sel], s["missing_type"][sel],
            s["left_child"][sel], s["right_child"][sel],
            s["num_leaves"][sel], s["leaf_value"][sel]))
    old = np.asarray(jnp.stack(parts, axis=1), np.float64)
    assert np.array_equal(new, old), np.abs(new - old).max()


def test_early_stop_chunks_reuse_one_executable():
    """Prediction early-stopping keeps all rows in the padded batch and
    masks on device: warm chunks are 1 dispatch + 1 (real data dependency)
    pull each, and NOTHING recompiles across chunks or batch sizes within
    a bucket — the old X[active] path compiled per distinct active count."""
    bst, X, _ = _binary_booster(rounds=8, pred_early_stop=True,
                                pred_early_stop_freq=2,
                                pred_early_stop_margin=0.5)
    first = bst.predict(X)  # warm: compiles the chunk window once

    with DispatchCounter() as d:
        again = bst.predict(X)
    d.assert_no_recompile("warm early-stop chunks")
    assert np.array_equal(first, again)
    assert d.dispatches >= 1
    # the margin test after each chunk is the loop's exit condition: one
    # accounted blocking pull per chunk, nothing else
    assert d.host_syncs == d.dispatches, (d.dispatches, d.host_syncs)
    # a different batch size in the same bucket must stay warm too
    # (600 and 550 both pad to the 1024 bucket)
    with DispatchCounter() as d2:
        bst.predict(X[:550])
    d2.assert_no_recompile("early-stop at a second N in the same bucket")


def test_early_stop_matches_legacy_chunked_walk():
    """The masked-on-device rework is numerically identical to the
    shrinking-active-set implementation it replaced."""
    bst, X, _ = _binary_booster(rounds=8, pred_early_stop=True,
                                pred_early_stop_freq=2,
                                pred_early_stop_margin=0.5)
    g = bst._gbdt
    new = g._predict_raw_early_stop(X)

    k = g.num_tree_per_iteration
    total = len(g.models) // k
    freq = max(int(g.cfg.pred_early_stop_freq), 1)
    margin = float(g.cfg.pred_early_stop_margin)
    n = X.shape[0]
    raw = None
    active = np.ones(n, bool)
    it = 0
    while it < total:
        chunk = min(freq, total - it)
        if raw is None:
            raw = g.predict_raw(X, it, chunk)
        else:
            raw[active] += g.predict_raw(X[active], it, chunk)
        it += chunk
        active &= np.abs(raw) < margin
        if not active.any():
            break
    assert np.array_equal(new, raw)


def test_pred_leaf_device_traversal_matches_host_walk():
    """pred_leaf rides the stacked device traversal now — one dispatch,
    same leaf assignment as the per-tree host walk it replaced."""
    bst, X, _ = _binary_booster()
    leaves = bst.predict(X, pred_leaf=True)
    host = np.stack([t.predict_leaf(np.asarray(X, np.float64))
                     for t in bst._gbdt.models], axis=1)
    assert leaves.shape == host.shape
    assert np.array_equal(leaves, host)

    bst.predict(X, pred_leaf=True)  # warm
    with DispatchCounter() as d:
        bst.predict(X, pred_leaf=True)
    assert d.dispatches == 1
    assert d.host_syncs == 1
    d.assert_no_recompile("warm pred_leaf")


def test_converted_predict_is_one_dispatch_one_sync(monkeypatch):
    """Round 12: objective.convert_output is FUSED into the traversal
    dispatch — a converted warm predict is 1 dispatch + 1 accounted pull
    (it was 2 dispatches: traversal, then a separate convert), and the
    fused result is bitwise the legacy 2-dispatch path's."""
    bst, X, _ = _binary_booster()
    fused = bst.predict(X)  # warm: packs + compiles the fused bucket

    with DispatchCounter() as d:
        again = bst.predict(X)
    assert d.dispatches == 1, d.dispatches
    assert d.host_syncs == 1, d.host_syncs
    d.assert_no_recompile("warm converted predict")
    assert np.array_equal(fused, again)

    # the legacy 2-dispatch path must still exist (escape hatch) and be
    # bitwise identical
    monkeypatch.setenv("LGBMTPU_FUSED_CONVERT", "0")
    legacy_warm = bst.predict(X)  # warm the legacy convert executable
    with DispatchCounter() as d2:
        legacy = bst.predict(X)
    assert d2.dispatches == 2, d2.dispatches
    assert np.array_equal(fused, legacy) and np.array_equal(
        legacy_warm, legacy)


def test_converted_predict_multiclass_one_dispatch_and_bitwise(monkeypatch):
    bm, Xm = _multiclass_booster()
    fused = bm.predict(Xm)
    with DispatchCounter() as d:
        again = bm.predict(Xm)
    assert d.dispatches == 1, d.dispatches
    assert d.host_syncs == 1, d.host_syncs
    d.assert_no_recompile("warm converted multiclass predict")
    assert np.array_equal(fused, again)

    monkeypatch.setenv("LGBMTPU_FUSED_CONVERT", "0")
    legacy = bm.predict(Xm)
    assert np.array_equal(fused, legacy)


def test_converted_predict_bucket_padding_bit_identical(monkeypatch):
    """The fused convert rides the same bucket ladder: padding may never
    change a converted bit either."""
    bst, X, _ = _binary_booster()
    padded = bst.predict(X[:129])
    monkeypatch.setenv("LGBMTPU_PREDICT_BUCKETS", "0")
    unpadded = bst.predict(X[:129])
    assert np.array_equal(padded, unpadded)


# ---------------------------------------------------------------------------
# stale-cache hazard (ISSUE satellite): mutation after a predict must
# invalidate the packed ensemble
# ---------------------------------------------------------------------------

def test_training_after_predict_invalidates_packed_cache():
    bst, X, _ = _binary_booster(rounds=3)
    before = bst.predict(X, raw_score=True)
    for _ in range(3):
        bst.update()
    after = bst.predict(X, raw_score=True)
    assert not np.array_equal(before, after), \
        "predictions ignored the newly trained trees (stale packed cache)"
    # the fresh result must equal a fresh booster's view of the same model
    clone = lgb.Booster(model_str=bst.model_to_string())
    assert np.array_equal(after, clone.predict(X, raw_score=True))


def test_rollback_after_predict_invalidates_packed_cache():
    bst, X, _ = _binary_booster(rounds=4)
    four = bst.predict(X, raw_score=True)
    bst.rollback_one_iter()
    three = bst.predict(X, raw_score=True)
    assert not np.array_equal(four, three)
    clone = lgb.Booster(model_str=bst.model_to_string())
    assert np.array_equal(three, clone.predict(X, raw_score=True))


def test_refit_and_leaf_edit_invalidate_packed_cache():
    bst, X, y = _binary_booster(rounds=4)
    base = bst.predict(X, raw_score=True)

    refit = bst.refit(X, y, decay_rate=0.0)
    refit.predict(X, raw_score=True)  # populate ITS cache, then mutate:
    refit.set_leaf_output(0, 0, 123.0)
    edited = refit.predict(X, raw_score=True)
    clone = lgb.Booster(model_str=refit.model_to_string())
    assert np.array_equal(edited, clone.predict(X, raw_score=True))
    assert not np.array_equal(base, edited)


def test_shuffle_models_invalidates_packed_cache():
    """Order changes the early-stop chunking but not the full sum; the
    cache must repack either way.  Round 18: mutation BUMPS the pack
    version instead of nulling the dict — the pre-shuffle entries stay
    resident (hot-swap friendliness) but are unreachable by the new
    version-keyed lookup, so the next predict packs fresh."""
    bst, X, _ = _binary_booster(rounds=4)
    bst.predict(X, raw_score=True)
    g = bst._gbdt
    assert g._pred_cache  # populated
    v0 = g._pack_version
    np.random.seed(0)
    bst.shuffle_models()
    assert g._pack_version == v0 + 1
    assert all(key[0] <= v0 for key in g._pred_cache), \
        "shuffle left a current-version packed ensemble cached"
    from lightgbm_tpu.obs import metrics as _obs
    misses0 = _obs.counter("predict_packed_cache_misses_total").value
    bst.predict(X, raw_score=True)
    assert _obs.counter("predict_packed_cache_misses_total").value \
        == misses0 + 1, "post-shuffle predict served a stale pack"


def test_packed_versioning_keeps_previous_pack_servable_during_swap():
    """The hot-swap mechanism (round 18, lightgbm_tpu/serve + the
    continuous-training roadmap item): a mutation bumps the version, and
    the PREVIOUS version's pack stays resident and servable — an
    in-flight serving reader that grabbed the pre-mutation pack keeps
    working, bitwise, while new predicts see the new trees."""
    bst, X, _ = _binary_booster(rounds=3)
    old_clone = lgb.Booster(model_str=bst.model_to_string())
    before = bst.predict(X[:40], raw_score=True)
    g = bst._gbdt
    s_old = g._packed(0, -1)
    v0 = g._pack_version
    bst.update()  # the swap: in-place mutation under a live serving loop
    # the old pack is retained one version back...
    assert any(key[0] == v0 for key in g._pred_cache), \
        "mutation evicted the in-flight pack"
    # ...and its device arrays still serve the OLD model's bits
    nb = _predict_bucket(40)
    x = g._pad_rows(np.asarray(X[:40], np.float64), nb)
    active = g._active_mask(40, nb)
    out = predict_ops.predict_raw_values(
        x, s_old["split_feature"], s_old["threshold"],
        s_old["default_left"], s_old["missing_type"], s_old["left_child"],
        s_old["right_child"], s_old["num_leaves"], s_old["leaf_value"],
        active=active)
    got_old = np.asarray(out, np.float64)[:40]
    assert np.array_equal(got_old, before)
    assert np.array_equal(before, old_clone.predict(X[:40], raw_score=True))
    # new predicts use the new version (fresh trees included)
    after = bst.predict(X[:40], raw_score=True)
    assert not np.array_equal(before, after)


def test_stale_pack_versions_evicted_and_counted():
    """Retention is LRU-bounded (default: current + previous version);
    older versions evict with a counter, so a long-lived serving process
    training every round cannot leak packs."""
    from lightgbm_tpu.obs import metrics as _obs

    bst, X, _ = _binary_booster(rounds=2)
    g = bst._gbdt
    evict0 = _obs.counter("predict_stale_pack_evictions_total").value
    versions = []
    for _ in range(3):
        bst.predict(X[:16], raw_score=True)  # populate this version's pack
        versions.append(g._pack_version)
        bst.update()  # bump
    assert _obs.counter("predict_stale_pack_evictions_total").value \
        > evict0
    live = {key[0] for key in g._pred_cache}
    keep = g._PACKED_KEEP_VERSIONS
    assert all(v > g._pack_version - keep for v in live), (live,
                                                          g._pack_version)
    assert versions[0] not in live  # the oldest version is gone


def test_coalesced_batch_budget_and_parity():
    """The serving loop's dispatch entry (GBDT.predict_coalesced): one
    coalesced batch of K requests is ONE dispatch + ONE accounted sync,
    reusing the SAME executables as warm predict (zero retraces), and
    the packed rows slice back out bitwise equal to the individual
    calls.  The runtime-level version (threads + staging + server ON)
    lives in tests/test_serve.py; this is the entry-level pin."""
    import jax

    bst, X, _ = _binary_booster()
    g = bst._gbdt
    parts = [X[0:10], X[10:17], X[17:32]]  # 32 rows: exact rung fill
    want = [bst.predict(p, raw_score=True) for p in parts]
    batch = np.concatenate(parts, axis=0)
    x = jax.device_put(np.asarray(batch, np.float64).astype(np.float32))
    g.predict_coalesced(x, None, 32, convert=False)  # warm the 32 bucket

    with DispatchCounter() as d:
        out = g.predict_coalesced(x, None, 32, convert=False)
    assert d.dispatches == 1, d.dispatches
    assert d.host_syncs == 1, d.host_syncs
    d.assert_no_recompile("warm coalesced batch")
    off = 0
    for w in want:
        assert np.array_equal(w, out[off:off + len(w)]), \
            "coalesced rows diverged from the individual predict"
        off += len(w)


# ---------------------------------------------------------------------------
# giant-batch row-sharded predict (parallel round: the third mesh axis)
# ---------------------------------------------------------------------------


def test_row_sharded_predict_bitwise_and_one_dispatch():
    """``Booster.predict(..., mesh=)`` scores a row-sharded batch as ONE
    SPMD dispatch over the row axis: rows traverse independently and each
    rank keeps the single-device tree-sum order, so the sharded result is
    BITWISE the single-device one — and a warm call keeps the exact
    serving budget (packed-cache hit, 1 dispatch, 1 accounted pull, 0
    retraces) with the replicated tables resident on the mesh."""
    from lightgbm_tpu.parallel.mesh import make_mesh

    bst, X, _ = _binary_booster()
    mesh = make_mesh()
    want = bst.predict(X, raw_score=True)
    got = bst.predict(X, raw_score=True, mesh=mesh)  # warm the mesh entry
    assert np.array_equal(want, got)

    g = bst._gbdt
    packs = []
    orig = g._stacked

    def counting_stacked(*a, **kw):
        packs.append(1)
        return orig(*a, **kw)

    g._stacked = counting_stacked
    try:
        with DispatchCounter() as d:
            again = bst.predict(X, raw_score=True, mesh=mesh)
    finally:
        g._stacked = orig
    assert not packs, "warm sharded predict re-packed the ensemble"
    assert d.dispatches == 1, d.dispatches
    assert d.host_syncs == 1, d.host_syncs
    d.assert_no_recompile("warm row-sharded predict")
    assert np.array_equal(want, again)

    # converted output rides the same sharded raw traversal, bitwise
    assert np.array_equal(bst.predict(X), bst.predict(X, mesh=mesh))
    # the explicit entry point is the same path
    assert np.array_equal(want, bst.predict_sharded(X, mesh, raw_score=True))


def test_row_sharded_predict_multiclass_bitwise():
    from lightgbm_tpu.parallel.mesh import make_mesh

    bm, Xm = _multiclass_booster()
    mesh = make_mesh()
    want = bm.predict(Xm, raw_score=True)
    assert np.array_equal(want, bm.predict(Xm, raw_score=True, mesh=mesh))
    bm.predict(Xm, raw_score=True, mesh=mesh)
    with DispatchCounter() as d:
        bm.predict(Xm, raw_score=True, mesh=mesh)
    assert d.dispatches == 1 and d.host_syncs == 1, (d.dispatches,
                                                     d.host_syncs)
    d.assert_no_recompile("warm multiclass row-sharded predict")
    assert np.array_equal(bm.predict(Xm), bm.predict(Xm, mesh=mesh))


def test_row_sharded_predict_on_training_mesh_and_invalidates():
    """A 2-D mesh with a second axis beside the data axis serves directly
    — P(data) shards rows and replicates over the other axis — and
    mutation invalidates the mesh-resident tables with the pack itself."""
    import jax
    from jax.sharding import Mesh

    from lightgbm_tpu.parallel.mesh import DATA_AXIS

    bst, X, _ = _binary_booster()
    mesh = Mesh(np.asarray(jax.devices()[:8]).reshape(4, 2),
                (DATA_AXIS, "model"))
    want = bst.predict(X, raw_score=True)
    assert np.array_equal(want, bst.predict(X, raw_score=True, mesh=mesh))
    bst.update()  # bump the pack version
    after = bst.predict(X, raw_score=True, mesh=mesh)
    assert not np.array_equal(want, after)
    assert np.array_equal(after, bst.predict(X, raw_score=True))


def test_no_trees_and_single_row_paths():
    """Degenerate serving shapes: empty model and N=1 both work."""
    rng = np.random.RandomState(3)
    X = rng.randn(50, 4)
    y = (X[:, 0] > 0).astype(float)
    bst = lgb.Booster(params={"objective": "binary", "verbosity": -1},
                      train_set=lgb.Dataset(X, label=y))
    p = bst.predict(X, raw_score=True)  # zero trees: init score only
    assert p.shape == (50,)
    bst.update()
    one = bst.predict(X[:1], raw_score=True)
    assert one.shape == (1,)
    assert np.array_equal(one[0], bst.predict(X, raw_score=True)[0])
