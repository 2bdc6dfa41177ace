"""Out-of-core data-path equivalence (docs round 12, ISSUE 7).

The contract under test: streaming the binned matrix — from a
``save_binary`` cache or a host array, in ANY chunk size — may never
change a trained model by a single bit.

* resident regime (rows <= max_rows_in_hbm budget, or no budget): the
  streamed chunks assemble the identical device matrix, training runs
  the standard growers — bitwise trivially, pinned anyway.
* spill regime (rows > max_rows_in_hbm): the chunked-histogram grower
  (ops/treegrow_ooc.py) is a strict-grower mirror whose seeded
  scatter-add fold is order-preserving — bitwise vs IN-MEMORY training
  on the scatter histogram strategy (max_bin > 64), across chunk sizes
  {1 row, odd, pow2, N}.
* the rounds grower's steady state (no sync, no retrace) and its trees
  stay the same when fed from a stream-assembled (out_of_core resident)
  matrix.
"""

import os

import numpy as np
import pytest

import lightgbm_tpu as lgb


def _make_data(n=400, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.5 * X[:, 1] + 0.2 * rng.randn(n) > 0).astype(float)
    return X, y


# the spill grower mirrors the strict grower bitwise on the SCATTER
# histogram strategy — max_bin > 64 selects it in-memory too (the wide
# regime out-of-core exists for; ops/treegrow_ooc.py module docstring)
_PARAMS = {
    "objective": "binary",
    "num_leaves": 7,
    "max_bin": 255,
    "verbosity": -1,
    "feature_pre_filter": False,  # scans the host matrix OOC never holds
    "min_data_in_leaf": 5,
}


def _train_model_str(train_set, rounds=3, **extra):
    params = dict(_PARAMS)
    params.update(extra)
    bst = lgb.Booster(params=params, train_set=train_set)
    for _ in range(rounds):
        bst.update()
    return bst, bst.model_to_string()


# ---------------------------------------------------------------------------
# streaming reader
# ---------------------------------------------------------------------------

def test_bin_cache_stream_round_trips_the_matrix(tmp_path):
    """Chunked sequential reads of the npz member reassemble the exact
    binned matrix — including through the REUSED buffer (consumers that
    copy per chunk see stable data)."""
    from lightgbm_tpu.io.stream import BinCacheStream

    X, y = _make_data(n=123, f=5)
    ds = lgb.Dataset(X, label=y, params={"max_bin": 255})
    cache = str(tmp_path / "ds.bin")
    ds.construct()
    ds.save_binary(cache)
    want = np.asarray(ds.bins)

    stream = BinCacheStream(cache)
    assert stream.shape == want.shape
    for chunk_rows in (1, 7, 64, 123, 200):
        got = np.zeros_like(want)
        for lo, view in stream.chunks(chunk_rows):
            got[lo:lo + view.shape[0]] = view  # copy out of the reused buf
        np.testing.assert_array_equal(got, want)


def test_prefetch_device_preserves_chunks_despite_buffer_reuse():
    """The one-deep prefetch uploads with copy semantics: the reused host
    buffer being refilled for chunk k+1 must not corrupt chunk k."""
    from lightgbm_tpu.io.stream import prefetch_device

    rng = np.random.RandomState(1)
    data = rng.randint(0, 100, (50, 4)).astype(np.int16)
    buf = np.empty((8, 4), np.int16)

    def reusing_chunks():
        for lo in range(0, 50, 8):
            m = min(8, 50 - lo)
            buf[:m] = data[lo:lo + m]
            yield lo, buf[:m]

    seen = np.zeros_like(data)
    for lo, m, dev in prefetch_device(reusing_chunks(), pad_rows=8):
        seen[lo:lo + m] = np.asarray(dev)[:m]
    np.testing.assert_array_equal(seen, data)


# ---------------------------------------------------------------------------
# resident regime: streamed ingest, standard growers
# ---------------------------------------------------------------------------

def test_resident_ooc_from_cache_is_bitwise_across_chunk_sizes(tmp_path):
    X, y = _make_data()
    n = X.shape[0]
    mem_ds = lgb.Dataset(X, label=y, params=dict(_PARAMS))
    _, want = _train_model_str(mem_ds)

    base = lgb.Dataset(X, label=y, params=dict(_PARAMS))
    cache = str(tmp_path / "train.bin")
    base.construct()
    base.save_binary(cache)

    for chunk in (1, 37, 128, n):  # 1 row, odd, pow2, all-N
        ds = lgb.Dataset(cache, params=dict(
            _PARAMS, out_of_core=True, out_of_core_chunk_rows=chunk))
        bst, got = _train_model_str(ds)
        assert got == want, f"resident OOC diverged at chunk_rows={chunk}"
        # the ingest never materialized a host matrix
        assert ds.bins is None
        assert ds.bins_device is not None and not ds.ooc_spill


def test_resident_ooc_from_ndarray_uploads_whole_matrix():
    """out_of_core=True on an in-memory ndarray (no cache to stream from)
    in the resident regime takes the direct whole-array upload — host
    bins already exist, chunked placement would be pure overhead — and
    the device matrix is identical to the plain in-memory path's."""
    X, y = _make_data()
    mem = lgb.Dataset(X, label=y, params=dict(_PARAMS)).construct()
    ooc = lgb.Dataset(X, label=y, params=dict(
        _PARAMS, out_of_core=True)).construct()
    assert not ooc.ooc_spill and ooc.bins is not None
    np.testing.assert_array_equal(
        np.asarray(ooc.bins_device), np.asarray(mem.bins_device))


def test_resident_ooc_whole_matrix_paths_materialize_from_device(tmp_path):
    """subset()/add_features_from() (and other whole-matrix consumers)
    work on a resident out_of_core dataset by materializing ONE host copy
    from the assembled device matrix — they do not crash on bins=None."""
    X, y = _make_data(n=150, f=4)
    base = lgb.Dataset(X, label=y, params=dict(_PARAMS))
    cache = str(tmp_path / "r.bin")
    base.construct()
    base.save_binary(cache)

    ds = lgb.Dataset(cache, params=dict(_PARAMS, out_of_core=True))
    ds.construct()
    assert ds.bins is None
    sub = ds.subset([0, 5, 9, 44])
    np.testing.assert_array_equal(sub.bins, np.asarray(base.bins)[[0, 5, 9, 44]])

    ds2 = lgb.Dataset(cache, params=dict(_PARAMS, out_of_core=True))
    ds2.construct()
    other = lgb.Dataset(X[:, :2], label=y, params=dict(_PARAMS))
    other.construct()
    joined = ds2.add_features_from(other)
    assert joined.bins.shape == (150, 6)


def test_spill_ooc_whole_matrix_paths_raise_envelope_error(tmp_path):
    """A cache-streamed spill dataset has NO whole matrix anywhere — the
    same paths raise the clear envelope error, not a raw TypeError."""
    X, y = _make_data(n=200, f=4)
    base = lgb.Dataset(X, label=y, params=dict(_PARAMS))
    cache = str(tmp_path / "s.bin")
    base.construct()
    base.save_binary(cache)
    ds = lgb.Dataset(cache, params=dict(
        _PARAMS, out_of_core=True, max_rows_in_hbm=50))
    ds.construct()
    assert ds.ooc_spill and ds.bins is None and ds.bins_device is None
    with pytest.raises(lgb.basic.LightGBMError, match="spill regime"):
        ds.subset([0, 1, 2])
    other = lgb.Dataset(X[:, :2], label=y, params=dict(_PARAMS))
    with pytest.raises(lgb.basic.LightGBMError, match="spill regime"):
        ds.add_features_from(other)


# ---------------------------------------------------------------------------
# spill regime: chunked-histogram training
# ---------------------------------------------------------------------------

def test_spill_ooc_is_bitwise_identical_to_in_memory_training(tmp_path):
    """The headline equivalence (ISSUE acceptance): rows exceed the HBM
    budget, the matrix is never device-resident, and the trained model is
    BIT-identical to plain in-memory training — across chunk sizes
    {1, odd, pow2, N}, from both chunk sources (host array and cache)."""
    X, y = _make_data()
    n = X.shape[0]
    mem_ds = lgb.Dataset(X, label=y, params=dict(_PARAMS))
    _, want = _train_model_str(mem_ds)

    base = lgb.Dataset(X, label=y, params=dict(_PARAMS))
    cache = str(tmp_path / "train.bin")
    base.construct()
    base.save_binary(cache)

    for chunk in (1, 37, 128, n):
        ds = lgb.Dataset(cache, params=dict(
            _PARAMS, out_of_core=True, max_rows_in_hbm=n // 4,
            out_of_core_chunk_rows=chunk))
        bst, got = _train_model_str(ds)
        assert ds.ooc_spill and ds.bins_device is None
        assert got == want, f"spill OOC diverged at chunk_rows={chunk}"

    # host-array source (in-memory data whose DEVICE residency is capped)
    ds = lgb.Dataset(X, label=y, params=dict(
        _PARAMS, out_of_core=True, max_rows_in_hbm=100,
        out_of_core_chunk_rows=53))
    _, got = _train_model_str(ds)
    assert ds.ooc_spill
    assert got == want


def test_spill_ooc_with_bagging_and_feature_fraction(tmp_path):
    """Row/feature sampling rides the resident vectors, not the streamed
    matrix — sampled runs must stay bitwise too."""
    X, y = _make_data(n=350, seed=3)
    extra = dict(bagging_fraction=0.7, bagging_freq=1, feature_fraction=0.8)
    mem_ds = lgb.Dataset(X, label=y, params=dict(_PARAMS))
    _, want = _train_model_str(mem_ds, **extra)

    ds = lgb.Dataset(X, label=y, params=dict(
        _PARAMS, out_of_core=True, max_rows_in_hbm=64,
        out_of_core_chunk_rows=41))
    _, got = _train_model_str(ds, **extra)
    assert got == want


def test_spill_predictions_match_in_memory(tmp_path):
    X, y = _make_data(n=300, seed=5)
    mem_ds = lgb.Dataset(X, label=y, params=dict(_PARAMS))
    bst_mem, _ = _train_model_str(mem_ds)
    ds = lgb.Dataset(X, label=y, params=dict(
        _PARAMS, out_of_core=True, max_rows_in_hbm=50,
        out_of_core_chunk_rows=64))
    bst_ooc, _ = _train_model_str(ds)
    np.testing.assert_array_equal(
        bst_mem.predict(X), bst_ooc.predict(X))


def test_spill_envelope_raises_on_unsupported_features():
    X, y = _make_data(n=200)
    ds = lgb.Dataset(X, label=y, params=dict(
        _PARAMS, out_of_core=True, max_rows_in_hbm=50))
    with pytest.raises(ValueError, match="out_of_core spill"):
        lgb.Booster(params=dict(_PARAMS, out_of_core=True,
                                max_rows_in_hbm=50,
                                monotone_constraints=[1, 0, 0, 0, 0, 0]),
                    train_set=ds)


def test_spill_dispatch_accounting(tmp_path):
    """The spill grower's cost model is explicit: ceil(N/chunk) chunk
    dispatches per pass, 1 root pass + 1 pass per split, one accounted
    pull per split decision — all visible to the sanitizer ledger."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.ops.treegrow_ooc import grow_tree_ooc
    from lightgbm_tpu.io.stream import array_chunks
    from lightgbm_tpu.binning import DatasetBinner

    X, y = _make_data(n=256, f=5, seed=7)
    binner = DatasetBinner.fit(X, max_bin=255)
    bins = binner.transform(X)
    n, f = bins.shape
    stats = {}
    tree, leaf_id = grow_tree_ooc(
        lambda: array_chunks(bins, 64), n, f,
        jnp.asarray(0.6 * (y - 0.5), jnp.float32),
        jnp.ones((n,), jnp.float32),
        jnp.ones((n,), bool), jnp.ones((n,), jnp.float32),
        jnp.ones((f,), bool),
        jnp.asarray(binner.num_bins_per_feature),
        jnp.asarray(binner.missing_bin_per_feature),
        num_leaves=7, num_bins=256, params=SplitParams(min_data_in_leaf=5.0),
        chunk_rows=64, stats=stats)
    assert int(tree.num_leaves) > 1
    assert stats["passes"] == stats["splits"] + 1
    assert stats["chunks"] == stats["passes"] * 4  # 256 rows / 64-row chunks
    assert leaf_id.shape == (n,)


# ---------------------------------------------------------------------------
# the rounds grower on an out_of_core resident matrix
# ---------------------------------------------------------------------------

def test_rounds_grower_green_on_stream_assembled_matrix(tmp_path):
    """ISSUE acceptance: the rounds grower's steady state (0 accounted
    syncs / 0 retraces for a further tree) holds when its bins come from
    an out_of_core stream-assembled device matrix, and the tree is the
    in-memory matrix's tree bit for bit — the chunk feed happens at
    ingest, the grower's loop is untouched."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.ops.treegrow_fast import grow_tree_fast
    from lightgbm_tpu.utils.sanitizer import DispatchCounter

    rng = np.random.RandomState(11)
    n, f = 900, 8
    X = rng.randn(n, f)
    y = X @ rng.randn(f) + 0.2 * rng.randn(n)
    mem = lgb.Dataset(X, label=y, params={"max_bin": 31})
    mem.construct()
    cache = str(tmp_path / "w.bin")
    mem.save_binary(cache)
    ooc = lgb.Dataset(cache, params={
        "max_bin": 31, "out_of_core": True, "out_of_core_chunk_rows": 111})
    ooc.construct()
    # the stream-assembled matrix IS the in-memory matrix
    np.testing.assert_array_equal(
        np.asarray(ooc.bins_device), np.asarray(mem.bins_device))

    ones = jnp.ones((n,), jnp.float32)
    kw = dict(
        row_mask=jnp.ones((n,), bool),
        sample_weight=ones,
        feature_mask=jnp.ones((f,), bool),
        num_bins_per_feature=jnp.asarray(ooc.binner.num_bins_per_feature),
        missing_bin_per_feature=jnp.asarray(
            ooc.binner.missing_bin_per_feature),
    )
    static = dict(num_leaves=15, num_bins=32, params=SplitParams(
        min_data_in_leaf=5.0), leaf_tile=4, use_pallas=False)
    grads = [jnp.asarray(0.6 * y + 0.05 * k, jnp.float32) for k in range(2)]
    tree, leaf = grow_tree_fast(ooc.bins_device, grads[0], ones, **kw,
                                **static)
    jax.block_until_ready(leaf)

    with DispatchCounter() as d:
        tree, leaf = grow_tree_fast(ooc.bins_device, grads[1], ones, **kw,
                                    **static)
        jax.block_until_ready(leaf)
    assert int(tree.num_leaves) == 15
    assert d.host_syncs == 0, d.host_syncs
    d.assert_no_recompile("rounds grower on a stream-assembled matrix")
    want, want_leaf = grow_tree_fast(mem.bins_device, grads[1], ones, **kw,
                                     **static)
    for got_a, want_a in zip(jax.tree_util.tree_leaves(tree),
                             jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(np.asarray(got_a), np.asarray(want_a))
    np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want_leaf))


# ---------------------------------------------------------------------------
# per-chunk CRC32 integrity (round 13, ISSUE 8): a corrupt or truncated
# bin cache fails fast + row-ranged instead of training on garbage bins
# ---------------------------------------------------------------------------

def _make_cache(tmp_path, n=300, f=4, name="crc.bin"):
    X, y = _make_data(n=n, f=f)
    ds = lgb.Dataset(X, label=y, params=dict(_PARAMS))
    ds.construct()
    cache = str(tmp_path / name)
    ds.save_binary(cache)
    return cache, np.asarray(ds.bins)


def _rewrite_member(src, dst, member, transform):
    """Copy an npz, applying ``transform(bytes)`` to one member (None
    drops it)."""
    import zipfile

    with zipfile.ZipFile(src) as zin, zipfile.ZipFile(dst, "w") as zout:
        for name in zin.namelist():
            data = zin.read(name)
            if name == member:
                data = transform(data)
                if data is None:
                    continue
            zout.writestr(name, data)


def test_save_binary_carries_crc_table_and_verifies(tmp_path):
    from lightgbm_tpu.io.stream import BinCacheStream, bin_crc32s

    cache, bins = _make_cache(tmp_path)
    s = BinCacheStream(cache)
    assert s.crcs is not None and s.crc_rows > 0
    np.testing.assert_array_equal(s.crcs, bin_crc32s(bins, s.crc_rows))
    got = np.zeros_like(bins)
    for lo, view in s.chunks(37):
        got[lo:lo + view.shape[0]] = view
    np.testing.assert_array_equal(got, bins)


def test_corrupt_bin_cache_raises_row_ranged_error(tmp_path):
    """A flipped byte in the bins member surfaces as CorruptBinCacheError
    naming the failing CRC chunk and its row range — never as garbage
    bins silently reaching training.  Exercised with a small custom CRC
    block size so the MIDDLE chunk is the one named."""
    import zlib

    from lightgbm_tpu.io.stream import (BinCacheStream,
                                        CorruptBinCacheError, bin_crc32s)

    cache, bins = _make_cache(tmp_path)
    # rebuild the cache with 64-row CRC blocks and corrupt a row in
    # block 2 (rows 128..191) — stored UNCOMPRESSED so the byte flip
    # reaches the CRC check rather than a zlib error
    bad_bins = bins.copy()
    bad_bins[150, 1] ^= 0x1
    crc_rows = 64

    def poison(_):
        import io

        buf = io.BytesIO()
        np.save(buf, bad_bins)
        return buf.getvalue()

    bad = str(tmp_path / "bad.bin")
    _rewrite_member(cache, bad, "bins.npy", poison)
    _rewrite_member(bad, bad + "2", "bins_crc_rows.npy", lambda _: (
        lambda b: (np.save(b, np.asarray(crc_rows, np.int64)), b.getvalue())[1])(
        __import__("io").BytesIO()))
    good_crcs = bin_crc32s(bins, crc_rows)  # CRCs of the TRUE data

    def crc_member(_):
        import io

        buf = io.BytesIO()
        np.save(buf, good_crcs)
        return buf.getvalue()

    final = str(tmp_path / "final.bin")
    _rewrite_member(bad + "2", final, "bins_crc32.npy", crc_member)

    s = BinCacheStream(final)
    assert s.crc_rows == crc_rows
    with pytest.raises(CorruptBinCacheError) as ei:
        for _ in s.chunks(50):
            pass
    assert ei.value.chunk_index == 150 // crc_rows
    assert ei.value.row_lo == 128 and ei.value.row_hi == 192
    assert "rows [128, 192)" in str(ei.value)


def test_truncated_bin_cache_raises_corrupt_error(tmp_path):
    from lightgbm_tpu.io.stream import BinCacheStream, CorruptBinCacheError

    cache, bins = _make_cache(tmp_path)

    def truncate(data):
        return data[: len(data) - len(data) // 3]

    torn = str(tmp_path / "torn.bin")
    _rewrite_member(cache, torn, "bins.npy", truncate)
    with pytest.raises(CorruptBinCacheError, match="corrupt at CRC chunk"):
        for _ in BinCacheStream(torn).chunks(64):
            pass


def test_corrupt_cache_fails_training_not_silently(tmp_path):
    """End to end: an out_of_core dataset built on a corrupt cache raises
    CorruptBinCacheError during ingest — training never sees the bins."""
    from lightgbm_tpu.io.stream import CorruptBinCacheError

    cache, bins = _make_cache(tmp_path)
    bad_bins = bins.copy()
    bad_bins[7, 0] ^= 0x1

    def poison(_):
        import io

        buf = io.BytesIO()
        np.save(buf, bad_bins)
        return buf.getvalue()

    bad = str(tmp_path / "bad_e2e.bin")
    _rewrite_member(cache, bad, "bins.npy", poison)
    ds = lgb.Dataset(bad, params=dict(_PARAMS, out_of_core=True))
    with pytest.raises(CorruptBinCacheError):
        _train_model_str(ds)


def test_legacy_trailerless_cache_loads_with_warning(tmp_path, caplog):
    """Pre-round-13 caches (no CRC members) still stream — with a logged
    warning, since nothing can vouch for their bytes."""
    from lightgbm_tpu.io.stream import BinCacheStream

    cache, bins = _make_cache(tmp_path)
    legacy = str(tmp_path / "legacy.bin")
    _rewrite_member(cache, legacy, "bins_crc32.npy", lambda _: None)
    _rewrite_member(legacy, legacy + "2", "bins_crc_rows.npy",
                    lambda _: None)
    s = BinCacheStream(legacy + "2")
    assert s.crcs is None
    got = np.zeros_like(bins)
    for lo, view in s.chunks(100):
        got[lo:lo + view.shape[0]] = view
    np.testing.assert_array_equal(got, bins)


# ---------------------------------------------------------------------------
# crash-at-round-k resume equivalence in the SPILL regime (ISSUE 8):
# stream + chunked-histogram state resumes bitwise, across chunk sizes
# ---------------------------------------------------------------------------

_OOC_CRASH_SCRIPT = """
import os, sys
import numpy as np
sys.path.insert(0, {repo!r})
import lightgbm_tpu as lgb

params = dict({params!r}, out_of_core=True, max_rows_in_hbm={hbm},
              out_of_core_chunk_rows={chunk}, snapshot_freq=2,
              output_model={out!r})
ds = lgb.Dataset({cache!r}, params=params)
lgb.train(params, ds, 6)
print("COMPLETED_WITHOUT_FAULT", flush=True)
"""


@pytest.mark.parametrize("chunk", [53])
def test_spill_crash_at_round_k_resume_is_bitwise(tmp_path, chunk):
    """Kill the host at round 5 of 6 while training a cache-streamed
    SPILL dataset; re-running the command with resume=auto continues
    from the round-4 snapshot — stream position restarts per pass and
    the chunked-histogram folds replay — and the final model is BITWISE
    identical to the uninterrupted spill run (which is itself bitwise
    the in-memory model, pinned above)."""
    import subprocess
    import sys

    from lightgbm_tpu.utils.faults import CRASH_EXIT_CODE

    X, y = _make_data()
    n = X.shape[0]
    base = lgb.Dataset(X, label=y, params=dict(_PARAMS))
    base.construct()
    cache = str(tmp_path / "train.bin")
    base.save_binary(cache)

    ooc = dict(_PARAMS, out_of_core=True, max_rows_in_hbm=n // 4,
               out_of_core_chunk_rows=chunk)
    full_ds = lgb.Dataset(cache, params=ooc)
    full = lgb.train(ooc, full_ds, 6)

    out = str(tmp_path / f"m{chunk}.txt")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, LGBMTPU_FAULT="host_crash:5",
               JAX_PLATFORMS="cpu")
    env.pop("PYTEST_CURRENT_TEST", None)
    r = subprocess.run(
        [sys.executable, "-c", _OOC_CRASH_SCRIPT.format(
            repo=repo, params=_PARAMS, hbm=n // 4, chunk=chunk,
            out=out, cache=cache)],
        env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == CRASH_EXIT_CODE, (r.stdout, r.stderr)

    resume_params = dict(ooc, snapshot_freq=2, output_model=out)
    ds = lgb.Dataset(cache, params=resume_params)
    resumed = lgb.train(resume_params, ds, 6, resume="auto")
    assert resumed.num_trees() == 6
    assert ds.ooc_spill
    # params echo differs (snapshot_freq/output_model); the TREES must
    # not differ by a single bit
    def trees(s):
        return s.partition("\nTree=")[2]

    assert trees(resumed.model_to_string()) == trees(full.model_to_string())


def test_spill_resume_with_categorical_trees(tmp_path):
    """Categorical splits are inside the spill envelope, so resume must
    handle them too: the streamed multi-tree replay excludes cat trees,
    and the per-tree fallback walks host chunks — still bitwise."""
    rng = np.random.RandomState(5)
    n = 300
    X = np.hstack([rng.randint(0, 8, (n, 2)).astype(float),
                   rng.randn(n, 3)])
    y = ((X[:, 0] == 3) | (X[:, 2] > 0)).astype(float)
    base_params = dict(_PARAMS, categorical_feature=[0, 1])
    base = lgb.Dataset(X, label=y, params=base_params,
                       categorical_feature=[0, 1])
    base.construct()
    cache = str(tmp_path / "cat.bin")
    base.save_binary(cache)

    P = dict(base_params, out_of_core=True, max_rows_in_hbm=64,
             out_of_core_chunk_rows=53)
    full = lgb.train(P, lgb.Dataset(cache, params=P), 4)
    assert any(t.num_cat > 0 for t in full._gbdt.models)

    run = dict(P, snapshot_freq=2, output_model=str(tmp_path / "m.txt"))
    lgb.train(run, lgb.Dataset(cache, params=run), 2)
    resumed = lgb.train(run, lgb.Dataset(cache, params=run), 4,
                        resume="auto")

    def trees(s):
        return s.partition("\nTree=")[2]

    assert trees(resumed.model_to_string()) == trees(full.model_to_string())


# ---------------------------------------------------------------------------
# rank-sharded streams (round 14): each rank streams only its (row_lo,
# row_hi) shard of one shared save_binary cache
# ---------------------------------------------------------------------------

def test_shard_stream_parity_with_whole_cache(tmp_path):
    """A (row_lo, row_hi) shard stream must yield byte-identical rows to
    the same slice of a whole-cache sweep — across shard boundaries that
    cut CRC blocks and chunk sizes that straddle them."""
    from lightgbm_tpu.io.stream import BinCacheStream

    cache, bins = _make_cache(tmp_path, n=300, f=4)
    whole = np.zeros_like(bins)
    for lo, view in BinCacheStream(cache).chunks(41):
        whole[lo:lo + view.shape[0]] = view
    np.testing.assert_array_equal(whole, bins)
    for lo, hi in ((0, 100), (100, 230), (230, 300), (37, 263), (299, 300)):
        s = BinCacheStream(cache, shard=(lo, hi))
        assert s.shard_rows == hi - lo and s.n_rows == bins.shape[0]
        got = np.zeros((hi - lo, bins.shape[1]), bins.dtype)
        first = None
        for glo, view in s.chunks(41):
            first = glo if first is None else first
            got[glo - lo: glo - lo + view.shape[0]] = view
        assert first == lo  # yields GLOBAL row offsets
        np.testing.assert_array_equal(got, bins[lo:hi])


def test_shard_stream_rejects_bad_range(tmp_path):
    from lightgbm_tpu.io.stream import BinCacheStream

    cache, bins = _make_cache(tmp_path)
    for bad in ((-1, 10), (10, 10), (0, bins.shape[0] + 1), (20, 5)):
        with pytest.raises(ValueError):
            BinCacheStream(cache, shard=bad)


def _poisoned_cache(tmp_path, bins, cache, crc_rows=64, bad_row=150):
    """Rebuild ``cache`` with ``crc_rows``-row CRC blocks over the TRUE
    data but one corrupted row in the bins member (the
    test_corrupt_bin_cache_raises_row_ranged_error recipe)."""
    import io

    from lightgbm_tpu.io.stream import bin_crc32s

    bad_bins = bins.copy()
    bad_bins[bad_row, 1] ^= 0x1

    def npy_bytes(arr):
        buf = io.BytesIO()
        np.save(buf, arr)
        return buf.getvalue()

    p1 = str(tmp_path / "shard_bad1.bin")
    p2 = str(tmp_path / "shard_bad2.bin")
    final = str(tmp_path / "shard_bad.bin")
    _rewrite_member(cache, p1, "bins.npy", lambda _: npy_bytes(bad_bins))
    _rewrite_member(p1, p2, "bins_crc_rows.npy",
                    lambda _: npy_bytes(np.asarray(crc_rows, np.int64)))
    _rewrite_member(p2, final, "bins_crc32.npy",
                    lambda _: npy_bytes(bin_crc32s(bins, crc_rows)))
    return final, bad_bins


def test_shard_stream_verifies_fully_covered_crc_blocks(tmp_path):
    """Shard sweeps keep the integrity contract wherever it is provable:
    a corrupt byte in a FULLY covered CRC block raises row-ranged; blocks
    the shard cuts mid-way are skipped (their leading bytes were never
    read), not trusted blind."""
    from lightgbm_tpu.io.stream import BinCacheStream, CorruptBinCacheError

    cache, bins = _make_cache(tmp_path)
    final, bad_bins = _poisoned_cache(tmp_path, bins, cache)
    # corruption at row 150 lives in CRC block 2 (rows [128, 192))
    s = BinCacheStream(final, shard=(128, 300))
    with pytest.raises(CorruptBinCacheError) as ei:
        for _ in s.chunks(50):
            pass
    assert ei.value.row_lo == 128 and ei.value.row_hi == 192

    # shard entering block 2 mid-way: the block is unverifiable and
    # skipped; later blocks still verify — the sweep completes with the
    # shard's bytes intact
    s2 = BinCacheStream(final, shard=(140, 300))
    got = np.zeros((160, bins.shape[1]), bins.dtype)
    for glo, view in s2.chunks(33):
        got[glo - 140: glo - 140 + view.shape[0]] = view
    np.testing.assert_array_equal(got, bad_bins[140:300])

    # shard ending inside block 2 never completes the block: no check
    # fires, the partial rows stream through
    s3 = BinCacheStream(final, shard=(0, 160))
    rows = sum(v.shape[0] for _, v in s3.chunks(64))
    assert rows == 160


# ---------------------------------------------------------------------------
# append-able caches (round 19, ISSUE 14 — continual ingest durability)
# ---------------------------------------------------------------------------

def _bins_payload_offset(path, member="bins.npy"):
    """Byte offset of the member's raw element data inside the zip."""
    import zipfile

    with zipfile.ZipFile(path) as zf:
        off = zf.getinfo(member).header_offset
    data = open(path, "rb").read()
    idx = data.index(b"\x93NUMPY", off)
    hlen = int.from_bytes(data[idx + 8:idx + 10], "little")
    return idx + 10 + hlen


def test_append_rows_round_trip_and_dataset_reload(tmp_path):
    """Appending frozen-mapper-binned rows grows the cache in place:
    the CRC table covers old + new rows, the append log records the
    seam, and a Dataset reload sees the concatenation exactly."""
    from lightgbm_tpu.io.stream import BinCacheStream, append_rows

    cache, bins = _make_cache(tmp_path, n=300, f=4)
    ds0 = lgb.Dataset(cache, params=dict(_PARAMS))
    ds0.construct()
    Xn, yn = _make_data(n=120, f=4, seed=9)
    new_bins = ds0.binner.transform(Xn)
    total = append_rows(cache, new_bins, label=yn)
    assert total == 420

    s = BinCacheStream(cache)
    assert s.shape == (420, 4)
    assert list(s.append_log) == [300]
    got = np.concatenate([v.copy() for _, v in s.chunks(64)])
    np.testing.assert_array_equal(got[:300], bins)
    np.testing.assert_array_equal(got[300:], new_bins.astype(s.dtype))

    ds = lgb.Dataset(cache, params=dict(_PARAMS))
    ds.construct()
    assert ds.num_data() == 420
    np.testing.assert_array_equal(np.asarray(ds.bins)[300:],
                                  new_bins.astype(ds.bins.dtype))
    np.testing.assert_allclose(np.asarray(ds.label)[300:], yn)
    # a second append extends the log
    append_rows(cache, new_bins[:10], label=yn[:10])
    assert list(BinCacheStream(cache).append_log) == [300, 420]


def test_append_rows_validation(tmp_path):
    from lightgbm_tpu.io.stream import append_rows

    cache, bins = _make_cache(tmp_path, n=300, f=4)
    with pytest.raises(ValueError, match="labels"):
        append_rows(cache, bins[:5])  # cache carries labels; chunk must too
    with pytest.raises(ValueError, match="shape"):
        append_rows(cache, np.zeros((5, 9), np.uint8), label=np.zeros(5))
    with pytest.raises(ValueError, match="labels"):
        append_rows(cache, bins[:5], label=np.zeros(4))


def test_append_to_legacy_cache_upgrades_crc_table(tmp_path):
    """Appending to a trailerless (pre-round-13) cache UPGRADES it: the
    new file carries a full CRC table covering every row — old rows
    included — instead of silently mixing verified and unverifiable
    blocks."""
    from lightgbm_tpu.io.stream import (BinCacheStream, append_rows,
                                        bin_crc32s)

    cache, bins = _make_cache(tmp_path, n=300, f=4)
    legacy = str(tmp_path / "legacy.bin")
    _rewrite_member(cache, legacy, "bins_crc32.npy", lambda b: None)
    _rewrite_member(legacy, legacy + ".2", "bins_crc_rows.npy",
                    lambda b: None)
    os.replace(legacy + ".2", legacy)
    assert BinCacheStream(legacy).crcs is None  # really trailerless

    ds0 = lgb.Dataset(cache, params=dict(_PARAMS))
    ds0.construct()
    Xn, yn = _make_data(n=80, f=4, seed=9)
    append_rows(legacy, ds0.binner.transform(Xn), label=yn)
    s = BinCacheStream(legacy)
    assert s.crcs is not None
    got = np.concatenate([v.copy() for _, v in s.chunks(50)])  # verifies
    np.testing.assert_array_equal(
        s.crcs, bin_crc32s(got.astype(s.dtype), s.crc_rows))
    from lightgbm_tpu.obs import metrics as obs
    assert obs.counter("bin_cache_crc_upgrades_total").value >= 1


def _make_appended_cache(tmp_path, n_base=4000, n_new=2000, crc_rows=512):
    """A cache written with a small CRC block size, then appended once —
    the bins member comes out ZIP_STORED, so byte offsets map 1:1 to
    rows and the per-block table is fine-grained enough that OUR check
    fires before zipfile's whole-member CRC at EOF."""
    from lightgbm_tpu.io.stream import append_rows, write_bin_cache

    X, y = _make_data(n=n_base, f=4)
    ds = lgb.Dataset(X, label=y, params=dict(_PARAMS))
    ds.construct()
    cache = str(tmp_path / "appendable.bin")
    with open(cache, "wb") as fh:
        write_bin_cache(fh, ds.bins, ds.binner.mappers, label=y,
                        feature_names=ds.feature_names, crc_rows=crc_rows)
    Xn, yn = _make_data(n=n_new, f=4, seed=9)
    append_rows(cache, ds.binner.transform(Xn), label=yn)
    return cache, ds


def test_append_corruption_error_names_the_appended_chunk(tmp_path):
    """A corrupt byte in the appended region raises row-ranged AND names
    which append_rows() call wrote the bad rows."""
    from lightgbm_tpu.io.stream import BinCacheStream, CorruptBinCacheError

    cache, _ds = _make_appended_cache(tmp_path)
    data = bytearray(open(cache, "rb").read())
    payload = _bins_payload_offset(cache)
    data[payload + 4500 * 4 + 1] ^= 0xFF  # row 4500: inside the append
    open(cache, "wb").write(bytes(data))
    with pytest.raises(CorruptBinCacheError) as ei:
        for _ in BinCacheStream(cache).chunks(256):
            pass
    msg = str(ei.value)
    assert "appended chunk 0" in msg and "row 4000" in msg, msg
    # row-ranged at the 512-row CRC block holding row 4500
    assert ei.value.row_lo == 4096 and ei.value.row_hi == 4608, msg


def test_append_to_corrupt_cache_refuses_before_replace(tmp_path):
    """The old payload streams through the VERIFIED path on its way into
    the new file: a corrupt source raises row-ranged BEFORE the atomic
    replace, leaving the (corrupt, but unreplaced) original untouched —
    an append can never launder bad bytes under a fresh CRC table."""
    from lightgbm_tpu.io.stream import CorruptBinCacheError, append_rows

    cache, ds = _make_appended_cache(tmp_path)
    data = bytearray(open(cache, "rb").read())
    payload = _bins_payload_offset(cache)
    data[payload + 1000 * 4] ^= 0xFF
    open(cache, "wb").write(bytes(data))
    before = open(cache, "rb").read()
    Xn, yn = _make_data(n=50, f=4, seed=9)
    with pytest.raises(CorruptBinCacheError):
        append_rows(cache, ds.binner.transform(Xn), label=yn)
    assert open(cache, "rb").read() == before


# ---------------------------------------------------------------------------
# the launcher's rank-sharded cache feed (ISSUE 15 satellite): workers
# materialize ONLY their shard of a shared cache via BinCacheStream(shard=)
# ---------------------------------------------------------------------------

def test_dataset_bin_cache_shard_parity(tmp_path):
    """Dataset(cache, params={'bin_cache_shard': (lo, hi, pad)}) builds
    the identical binned rows/label/weight the full cache holds at
    [lo, hi) — plus weight-0 zero-bin padding to the fleet's equal-shard
    size — without ever materializing the whole matrix member."""
    cache, bins = _make_cache(tmp_path, n=300, f=4)
    with np.load(cache, allow_pickle=False) as z:
        full_label = np.asarray(z["label"])
    lo, hi, pad = 37, 263, 240  # a range cutting CRC blocks, padded
    ds = lgb.Dataset(cache,
                     params=dict(_PARAMS, bin_cache_shard=(lo, hi, pad)))
    ds.construct()
    got = np.asarray(ds.bins)
    assert got.shape == (pad, bins.shape[1])
    np.testing.assert_array_equal(got[: hi - lo], bins[lo:hi])
    assert (got[hi - lo:] == 0).all()
    np.testing.assert_array_equal(np.asarray(ds.label)[: hi - lo],
                                  full_label[lo:hi])
    w = np.asarray(ds.weight)
    assert (w[: hi - lo] == 1.0).all() and (w[hi - lo:] == 0.0).all()
    # an unpadded shard keeps weight=None semantics (no synthetic ones)
    ds2 = lgb.Dataset(cache, params=dict(_PARAMS,
                                         bin_cache_shard=(lo, hi)))
    ds2.construct()
    assert ds2.weight is None
    np.testing.assert_array_equal(np.asarray(ds2.bins), bins[lo:hi])


def test_dataset_bin_cache_shard_crc_boundary(tmp_path):
    """The shard feed keeps the integrity contract: a corrupt byte in a
    CRC block the shard fully covers raises row-ranged through
    read_cache_shard; a shard cutting the poisoned block mid-way cannot
    verify it (leading bytes never read) and streams through."""
    from lightgbm_tpu.io.stream import CorruptBinCacheError

    cache, bins = _make_cache(tmp_path)
    final, bad_bins = _poisoned_cache(tmp_path, bins, cache)
    ds = lgb.Dataset(final, params=dict(_PARAMS,
                                        bin_cache_shard=(128, 300)))
    with pytest.raises(CorruptBinCacheError) as ei:
        ds.construct()
    assert ei.value.row_lo == 128 and ei.value.row_hi == 192
    ds2 = lgb.Dataset(final, params=dict(_PARAMS,
                                         bin_cache_shard=(140, 300)))
    ds2.construct()
    np.testing.assert_array_equal(np.asarray(ds2.bins), bad_bins[140:300])


def test_cache_shard_fingerprint_tracks_bytes(tmp_path):
    """The launcher's shard fingerprint (CRC-table-derived, no payload
    read) is stable across reads, distinct per range, and flips when the
    shard's bytes change."""
    from lightgbm_tpu.io.stream import cache_shard_fingerprint

    cache, bins = _make_cache(tmp_path)
    fp = cache_shard_fingerprint(cache, 0, 150)
    assert fp and fp == cache_shard_fingerprint(cache, 0, 150)
    assert fp != cache_shard_fingerprint(cache, 150, 300)
    final, _ = _poisoned_cache(tmp_path, bins, cache, bad_row=10)
    assert cache_shard_fingerprint(final, 0, 150) != fp


def test_launcher_cache_feed_trains_equal_to_in_memory(tmp_path):
    """End to end: train_distributed(data_cache=) feeds the worker
    through the shard stream and produces the identical model a plain
    in-process training on the same cache does."""
    from lightgbm_tpu.parallel import launcher

    cache, _bins = _make_cache(tmp_path, n=400, f=5, name="feed.bin")
    params = dict(_PARAMS, bin_construct_sample_cnt=400)
    ref = lgb.train(dict(params), lgb.Dataset(cache), num_boost_round=4)
    ref_path = str(tmp_path / "ref_model.txt")
    ref.save_model(ref_path)
    bst, files = launcher.train_distributed(
        params, None, None, num_boost_round=4, num_machines=1,
        data_cache=cache,
        env_extra={"JAX_PLATFORMS": "cpu",
                   "XLA_FLAGS": "--xla_force_host_platform_device_count=1"})
    assert open(files[0]).read() == open(ref_path).read()
    with pytest.raises(ValueError, match="XOR"):
        launcher.train_distributed(params, np.zeros((4, 2)), None,
                                   num_boost_round=1, num_machines=1,
                                   data_cache=cache)


# ---------------------------------------------------------------------------
# segmented appends + compaction (round 23 — the continual runner's
# O(new rows) steady-state ingest: sidecar segments, threshold-triggered
# fold-back, crash-stranded sidecars ignored via the watermark)
# ---------------------------------------------------------------------------

def test_segment_append_leaves_base_untouched_and_reloads(tmp_path):
    """Under the threshold, appends land in CRC'd sidecars: the base file
    is BYTE-identical afterwards (O(new rows) per append), the stream and
    a Dataset reload both see base + segments as one logical cache, and
    the append log records every seam."""
    from lightgbm_tpu.io.stream import BinCacheStream, append_rows
    from lightgbm_tpu.obs import metrics as obs

    cache, bins = _make_cache(tmp_path, n=300, f=4, name="seg.bin")
    base_bytes = open(cache, "rb").read()
    ds0 = lgb.Dataset(cache, params=dict(_PARAMS))
    ds0.construct()
    Xn, yn = _make_data(n=90, f=4, seed=9)
    nb = ds0.binner.transform(Xn)
    c0 = obs.counter("bin_cache_segment_appends_total").value
    assert append_rows(cache, nb[:40], label=yn[:40],
                       segment_threshold=3) == 340
    assert append_rows(cache, nb[40:], label=yn[40:],
                       segment_threshold=3) == 390
    assert open(cache, "rb").read() == base_bytes  # base never rewritten
    assert os.path.exists(cache + ".seg.0")
    assert os.path.exists(cache + ".seg.1")
    assert obs.counter("bin_cache_segment_appends_total").value == c0 + 2

    s = BinCacheStream(cache)
    assert s.shape == (390, 4)
    assert [k for k, _sp, _n in s.segments] == [0, 1]
    assert list(s.append_log) == [300, 340]
    got = np.concatenate([v.copy() for _, v in s.chunks(64)])
    np.testing.assert_array_equal(got[:300], bins)
    np.testing.assert_array_equal(got[300:], nb.astype(s.dtype))

    ds = lgb.Dataset(cache, params=dict(_PARAMS))
    ds.construct()
    assert ds.num_data() == 390
    np.testing.assert_array_equal(np.asarray(ds.bins)[300:],
                                  nb.astype(np.asarray(ds.bins).dtype))
    np.testing.assert_allclose(np.asarray(ds.label)[300:], yn)


def test_segment_threshold_triggers_compaction(tmp_path):
    """Reaching the threshold folds every live segment back into the base
    through the verified rewrite: sidecars are deleted, the watermark
    covers the folded indices, and the logical rows are preserved
    exactly."""
    from lightgbm_tpu.io.stream import BinCacheStream, append_rows
    from lightgbm_tpu.obs import metrics as obs

    cache, bins = _make_cache(tmp_path, n=300, f=4, name="fold.bin")
    ds0 = lgb.Dataset(cache, params=dict(_PARAMS))
    ds0.construct()
    Xn, yn = _make_data(n=80, f=4, seed=9)
    nb = ds0.binner.transform(Xn)
    c0 = obs.counter("bin_cache_compactions_total").value
    append_rows(cache, nb[:30], label=yn[:30], segment_threshold=2)
    assert os.path.exists(cache + ".seg.0")
    assert obs.counter("bin_cache_compactions_total").value == c0
    append_rows(cache, nb[30:], label=yn[30:], segment_threshold=2)
    assert obs.counter("bin_cache_compactions_total").value == c0 + 1
    assert not os.path.exists(cache + ".seg.0")
    assert not os.path.exists(cache + ".seg.1")

    s = BinCacheStream(cache)
    assert not s.segments and s.shape == (380, 4)
    assert s.seg_watermark == 1  # both folded indices covered
    got = np.concatenate([v.copy() for _, v in s.chunks(50)])
    np.testing.assert_array_equal(got[:300], bins)
    np.testing.assert_array_equal(got[300:], nb.astype(s.dtype))
    with np.load(cache, allow_pickle=False) as z:
        assert len(z["label"]) == 380  # labels folded into the base npz
    ds = lgb.Dataset(cache, params=dict(_PARAMS))
    ds.construct()
    assert ds.num_data() == 380
    np.testing.assert_allclose(np.asarray(ds.label)[300:], yn)


def test_stale_sidecar_past_watermark_is_ignored(tmp_path):
    """A crash between compaction's atomic replace and its sidecar
    deletes strands already-folded segment files: the watermark makes
    every reader skip them — rows are never double-counted."""
    from lightgbm_tpu.io.stream import BinCacheStream, append_rows

    cache, _bins = _make_cache(tmp_path, n=300, f=4, name="stale.bin")
    ds0 = lgb.Dataset(cache, params=dict(_PARAMS))
    ds0.construct()
    Xn, yn = _make_data(n=60, f=4, seed=9)
    nb = ds0.binner.transform(Xn)
    append_rows(cache, nb[:25], label=yn[:25], segment_threshold=2)
    stranded = open(cache + ".seg.0", "rb").read()
    append_rows(cache, nb[25:], label=yn[25:], segment_threshold=2)
    assert not os.path.exists(cache + ".seg.0")  # compaction reaped it
    # the crash: the folded sidecar reappears after the base replace
    open(cache + ".seg.0", "wb").write(stranded)

    s = BinCacheStream(cache)
    assert not s.segments, "stale sidecar was re-counted"
    assert s.shape == (360, 4)
    ds = lgb.Dataset(cache, params=dict(_PARAMS))
    ds.construct()
    assert ds.num_data() == 360
    # temp files from an in-flight segment write are skipped too
    open(cache + ".seg.tmp123", "wb").write(b"junk")
    assert not BinCacheStream(cache).segments


def test_segment_fingerprint_moves_on_append_and_compaction(tmp_path):
    """The shard fingerprint covers sidecar bytes: every segment append
    moves it (the fleet manifests must notice new rows without reading
    payloads), and it never goes empty while segments carry CRC
    tables."""
    from lightgbm_tpu.io.stream import (append_rows,
                                        cache_shard_fingerprint)

    cache, _bins = _make_cache(tmp_path, n=300, f=4, name="fp.bin")
    ds0 = lgb.Dataset(cache, params=dict(_PARAMS))
    ds0.construct()
    Xn, yn = _make_data(n=60, f=4, seed=9)
    nb = ds0.binner.transform(Xn)
    fps = [cache_shard_fingerprint(cache, 0, 10_000)]
    append_rows(cache, nb[:20], label=yn[:20], segment_threshold=4)
    fps.append(cache_shard_fingerprint(cache, 0, 10_000))
    append_rows(cache, nb[20:], label=yn[20:], segment_threshold=4)
    fps.append(cache_shard_fingerprint(cache, 0, 10_000))
    assert all(fps), "fingerprint went unverifiable mid-ingest"
    assert len(set(fps)) == 3, "an append did not move the fingerprint"
    # a base-range fingerprint ignores the sidecars entirely
    assert cache_shard_fingerprint(cache, 0, 300) == \
        cache_shard_fingerprint(cache, 0, 300)
