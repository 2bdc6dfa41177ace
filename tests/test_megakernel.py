"""The round megakernel (ops/round_pallas.py): one HBM sweep of the bin
matrix per boosting round.  Acceptance matrix (ISSUE 11):

* BITWISE equality with the three-pass fused round across float /
  int8-quantized / categorical (Mosaic interpret mode — tier-1 has no
  TPU), single-device AND sharded (where the in-dispatch collective
  merge must stay unchanged);
* the per-feature on-core split-gain reduction is bitwise-equal to the
  flat-plane selection (ops/split.py shared machinery);
* unsupported scenarios (EFB bundles, per-node rng) fall back to the
  three-pass round LOUDLY — counter + event — never silently diverge;
* an injected Pallas failure degrades to the three-pass round through
  the utils/degrade.py registry without killing training, and interpret
  mode (the correctness harness) SURFACES failures instead.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from lightgbm_tpu.binning import DatasetBinner
from lightgbm_tpu.obs import metrics as obs
from lightgbm_tpu.ops import split as sp
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.ops.treegrow_windowed import grow_tree_windowed
from lightgbm_tpu.utils import degrade, faults


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    degrade.reset()
    faults.reset()
    monkeypatch.delenv("LGBMTPU_FAULT", raising=False)
    yield
    degrade.reset()
    faults.reset()


def _grow_both(args, kw, monkeypatch):
    """Grow one tree with the three-pass round and one with the
    megakernel round (interpret mode), returning both."""
    monkeypatch.setenv("LGBMTPU_MEGAKERNEL", "0")
    t0, l0 = grow_tree_windowed(*args, **kw)
    monkeypatch.setenv("LGBMTPU_MEGAKERNEL", "interpret")
    t1, l1 = grow_tree_windowed(*args, **kw)
    return (t0, l0), (t1, l1)


def _assert_trees_bitwise(got, want, tag=""):
    (t0, l0), (t1, l1) = want, got
    assert int(t1.num_leaves) == int(t0.num_leaves), tag
    for name in t0._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(t1, name)), np.asarray(getattr(t0, name)),
            err_msg=f"{tag}: TreeArrays.{name} diverged")
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l0),
                                  err_msg=f"{tag}: leaf ids diverged")


def _inputs(n=2500, f=12, seed=3, max_bin=63):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = X @ rng.randn(f) + 0.3 * rng.randn(n)
    binner = DatasetBinner.fit(X, max_bin=max_bin)
    bins_t = jnp.asarray(binner.transform(X).T, jnp.int16)
    grad = jnp.asarray(0.6 * y, jnp.float32)
    hess = jnp.ones((n,), jnp.float32)
    return binner, bins_t, grad, hess


_BASE = dict(num_leaves=15, num_bins=64,
             params=SplitParams(min_data_in_leaf=5.0), leaf_tile=4,
             use_pallas=False)


def _args(binner, bins_t, grad, hess, mask=None):
    n = bins_t.shape[1]
    f = bins_t.shape[0]
    return (bins_t, grad, hess,
            jnp.ones((n,), bool) if mask is None else mask,
            jnp.ones((n,), jnp.float32), jnp.ones((f,), bool),
            jnp.asarray(binner.num_bins_per_feature),
            jnp.asarray(binner.missing_bin_per_feature))


@pytest.mark.parametrize("masked", [False, True])
def test_megakernel_bitwise_float(masked, monkeypatch):
    """Float path, with and without a bagging mask: the megakernel round
    (partition + one-sweep histogram + on-core gain reduction) grows the
    bit-identical tree."""
    binner, bins_t, grad, hess = _inputs()
    n = bins_t.shape[1]
    mask = (jnp.asarray(np.random.RandomState(1).rand(n) < 0.8)
            if masked else None)
    want, got = _grow_both(_args(binner, bins_t, grad, hess, mask), _BASE,
                           monkeypatch)
    assert int(want[0].num_leaves) > 4
    _assert_trees_bitwise(got, want, f"float masked={masked}")


def test_megakernel_bitwise_quantized(monkeypatch):
    """int8-quantized config (CPU trace: dequantized fallback histograms,
    same as the three-pass round's) — the wide-regime default."""
    binner, bins_t, grad, hess = _inputs(n=1800, seed=7)
    kw = dict(_BASE, leaf_tile=2, quantize_bins=16,
              stochastic_rounding=False, quant_renew=True)
    want, got = _grow_both(_args(binner, bins_t, grad, hess), kw,
                           monkeypatch)
    assert int(want[0].num_leaves) > 4
    _assert_trees_bitwise(got, want, "quantized")


def test_megakernel_bitwise_categorical(monkeypatch):
    """Categorical splits: the on-core reduction carries the winning
    variant out and the winner's bitset mask is replayed bitwise from the
    child histogram (split.categorical_winner_mask)."""
    rng = np.random.RandomState(5)
    n, f, n_cat = 1800, 12, 8
    X = rng.randn(n, f)
    cats = rng.randint(0, n_cat, n)
    X[:, 0] = cats
    y = (rng.randn(n_cat) * 2.0)[cats] + X[:, 1] + 0.2 * rng.randn(n)
    binner = DatasetBinner.fit(X, max_bin=63, categorical_features=[0])
    bins_t = jnp.asarray(binner.transform(X).T, jnp.int16)
    grad = jnp.asarray(0.6 * y, jnp.float32)
    hess = jnp.ones((n,), jnp.float32)
    kw = dict(_BASE, leaf_tile=2,
              categorical_mask=jnp.asarray(np.arange(f) == 0))
    want, got = _grow_both(_args(binner, bins_t, grad, hess), kw,
                           monkeypatch)
    nl = int(want[0].num_leaves)
    assert bool(np.asarray(want[0].is_cat[: nl - 1]).any()), \
        "fixture grew no categorical splits"
    _assert_trees_bitwise(got, want, "categorical")


def test_per_feature_selection_matches_flat_selection():
    """The megakernel's on-core reduction contract: per-feature argmax +
    cross-feature selection (reduce_plane_per_feature +
    select_from_feature_best) is BITWISE the flat-plane argmax
    (find_best_split), including tie-heavy planes (duplicated feature
    columns) and the categorical variants."""
    F, B = 12, 32
    params = SplitParams(min_data_in_leaf=5.0)
    for seed in range(4):
        for cat in (False, True):
            for dup in (False, True):
                r = np.random.RandomState(seed)
                hist = np.abs(r.randn(3, F, B)).astype(np.float32)
                hist[0] = r.randn(F, B)
                if dup:  # duplicated columns -> exact cross-feature ties
                    hist[:, 1] = hist[:, 0]
                    hist[:, 7] = hist[:, 0]
                nbpf = np.full(F, B, np.int32)
                mbpf = np.full(F, B - 1, np.int32)
                mbpf[::3] = -1
                cmask = (jnp.asarray(np.arange(F) % 4 == 0) if cat
                         else None)
                pg = jnp.float32(hist[0].sum())
                ph = jnp.float32(hist[1].sum())
                pc = jnp.float32(hist[2].sum())
                histj = jnp.asarray(hist)
                kw = dict(categorical_mask=cmask, depth=jnp.float32(1.0),
                          parent_output=jnp.float32(0.1))
                want = sp.find_best_split(
                    histj, pg, ph, pc, jnp.asarray(nbpf), jnp.asarray(mbpf),
                    params, **kw)
                gain, ctx = sp.gain_plane(
                    histj, pg, ph, pc, jnp.asarray(nbpf), jnp.asarray(mbpf),
                    params, **kw)
                fb = sp.reduce_plane_per_feature(gain, ctx)
                got = sp.select_from_feature_best(
                    fb, pg, ph, pc, categorical_mask=cmask, cand_hist=histj,
                    missing_bin_per_feature=jnp.asarray(mbpf), params=params,
                    num_bins=B)
                for name in want._fields:
                    np.testing.assert_array_equal(
                        np.asarray(getattr(got, name)),
                        np.asarray(getattr(want, name)),
                        err_msg=f"seed={seed} cat={cat} dup={dup}: {name}")


def test_per_feature_reduction_is_feature_block_separable():
    """The in-kernel reduction runs on feature-BLOCK slices and
    concatenates — per-feature outputs must be identical to the full-F
    reduction (the property that lets the VMEM carry stay FB-sized)."""
    F, B, FB = 12, 32, 8
    params = SplitParams(min_data_in_leaf=5.0)
    r = np.random.RandomState(2)
    hist = jnp.asarray(np.abs(r.randn(3, F, B)).astype(np.float32))
    nbpf = jnp.full((F,), B, jnp.int32)
    mbpf = jnp.full((F,), B - 1, jnp.int32)
    pg, ph, pc = (jnp.float32(float(v.sum())) for v in np.asarray(hist))
    gain, ctx = sp.gain_plane(hist, pg, ph, pc, nbpf, mbpf, params)
    whole = sp.reduce_plane_per_feature(gain, ctx)
    parts = []
    for lo in range(0, F, FB):
        hi = min(lo + FB, F)
        g_s, ctx_s = sp.gain_plane(hist[:, lo:hi], pg, ph, pc,
                                   nbpf[lo:hi], mbpf[lo:hi], params)
        parts.append(sp.reduce_plane_per_feature(g_s, ctx_s))
    for name in whole._fields:
        np.testing.assert_array_equal(
            np.concatenate([np.asarray(getattr(p, name)) for p in parts]),
            np.asarray(getattr(whole, name)), err_msg=name)


def test_megakernel_envelope_efb_falls_back_loudly(monkeypatch):
    """EFB bundles are outside the megakernel envelope: with the
    megakernel FORCED on, the round must fall back to the three-pass
    body (bitwise-identical tree), bump the fallback counter, and leave
    a megakernel_fallback event — never silently diverge."""
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(6)
    n, groups = 1500, 8
    blocks = []
    for _ in range(groups):
        col = rng.randint(0, 8, n)
        oh = np.zeros((n, 8))
        oh[np.arange(n), col] = 1.0
        blocks.append(oh)
    X = np.concatenate(blocks + [rng.randn(n, 2)], axis=1)
    y = X @ rng.randn(X.shape[1]) + 0.1 * rng.randn(n)
    ds = lgb.Dataset(X, label=y)
    ds.construct()
    assert ds.efb is not None
    tabs = ds.efb_device_tables()
    f = ds.bins.shape[1]
    args = (jnp.asarray(ds.bins, jnp.int16).T,
            jnp.asarray(0.6 * y, jnp.float32), jnp.ones((n,), jnp.float32),
            jnp.ones((n,), bool), jnp.ones((n,), jnp.float32),
            jnp.ones((f,), bool), ds.num_bins_pf_device,
            ds.missing_bin_pf_device)
    kw = dict(num_leaves=15, num_bins=ds.max_num_bins,
              params=SplitParams(min_data_in_leaf=5.0), leaf_tile=4,
              use_pallas=False,
              efb_bins_t=ds.efb_bins_device_t(), efb_gather=tabs[1],
              efb_default=tabs[2])

    monkeypatch.setenv("LGBMTPU_MEGAKERNEL", "0")
    t0, l0 = grow_tree_windowed(*args, **kw)
    before = obs.counter("megakernel_envelope_fallbacks_total").value
    monkeypatch.setenv("LGBMTPU_MEGAKERNEL", "1")
    t1, l1 = grow_tree_windowed(*args, **kw)
    assert obs.counter(
        "megakernel_envelope_fallbacks_total").value == before + 1
    evs = [e for e in obs.events("megakernel_fallback")
           if e.get("reason") == "efb"]
    assert evs, "no megakernel_fallback event for the EFB exclusion"
    _assert_trees_bitwise((t1, l1), (t0, l0), "efb fallback")


def test_megakernel_envelope_node_rng_falls_back_loudly(monkeypatch):
    """Per-node feature sampling (rng-keyed scan) cannot run on-core —
    same loud fallback contract."""
    binner, bins_t, grad, hess = _inputs(n=1200, seed=11)
    kw = dict(_BASE, params=SplitParams(min_data_in_leaf=5.0,
                                        feature_fraction_bynode=0.5))
    args = _args(binner, bins_t, grad, hess) + (jax.random.PRNGKey(0),)

    monkeypatch.setenv("LGBMTPU_MEGAKERNEL", "0")
    t0, l0 = grow_tree_windowed(*args, **kw)
    before = obs.counter("megakernel_envelope_fallbacks_total").value
    monkeypatch.setenv("LGBMTPU_MEGAKERNEL", "1")
    t1, l1 = grow_tree_windowed(*args, **kw)
    assert obs.counter(
        "megakernel_envelope_fallbacks_total").value == before + 1
    assert any(e.get("reason") == "node_rng"
               for e in obs.events("megakernel_fallback"))
    _assert_trees_bitwise((t1, l1), (t0, l0), "node-rng fallback")


def test_megakernel_envelope_quantized_pallas_falls_back_loudly():
    """On the Pallas hot path, int8-quantized training is OUTSIDE the
    envelope: the three-pass round accumulates exact int8 histograms on
    the MXU while the committed megakernel folds dequantized f32 — until
    the int8 MXU accumulate lands, a quantized+Pallas config must fall
    back loudly rather than silently change numerics.  The CPU fallback
    path (no Pallas hist) stays in-envelope — that is what the bitwise
    quantized parity test above exercises."""
    from lightgbm_tpu.ops.treegrow_windowed import megakernel_mode

    before = obs.counter("megakernel_envelope_fallbacks_total").value
    mk, _ = megakernel_mode(True, quantize_bins=16, mode="1")
    assert mk is False
    assert obs.counter(
        "megakernel_envelope_fallbacks_total").value == before + 1
    assert any(e.get("reason") == "quantized_mxu"
               for e in obs.events("megakernel_fallback"))
    assert megakernel_mode(False, quantize_bins=16, mode="interpret")[0]
    assert megakernel_mode(True, quantize_bins=0, mode="1")[0]
    # auto selects nothing, even on the Pallas hot path of a healthy
    # process: Mosaic refuses the kernel (ROADMAP.md Design item 1)
    assert megakernel_mode(True, mode="auto") == (False, False)


def test_megakernel_interpret_ignores_degraded_registry():
    """The correctness harness must never silently grow three-pass trees
    because a PRIOR tree degraded ROUND: interpret mode bypasses the
    registry (the partition kernel's interpret contract); device modes
    honour it."""
    from lightgbm_tpu.ops.treegrow_windowed import megakernel_mode

    degrade.disable(degrade.ROUND, "test: prior failure")
    assert megakernel_mode(False, mode="interpret")[0] is True
    assert megakernel_mode(True, mode="1")[0] is False
    assert megakernel_mode(True, mode="auto")[0] is False


def test_megakernel_degrades_on_injected_failure(monkeypatch):
    """An injected pallas_round fault (modelling a Mosaic rejection of
    the megakernel) degrades ROUND permanently and regrows the tree on
    the three-pass round — training survives, results identical."""
    binner, bins_t, grad, hess = _inputs(n=1200, seed=12)
    args = _args(binner, bins_t, grad, hess)

    monkeypatch.setenv("LGBMTPU_MEGAKERNEL", "0")
    t0, l0 = grow_tree_windowed(*args, **_BASE)

    monkeypatch.setenv("LGBMTPU_MEGAKERNEL", "1")
    monkeypatch.setenv("LGBMTPU_FAULT", "pallas_round:0")
    t1, l1 = grow_tree_windowed(*args, **_BASE)
    _assert_trees_bitwise((t1, l1), (t0, l0), "degraded round")
    assert not degrade.available(degrade.ROUND)
    assert degrade.available(degrade.HIST)  # layered: only ROUND degraded
    # degraded process: the megakernel is skipped without needing a fault
    t2, l2 = grow_tree_windowed(*args, **_BASE)
    _assert_trees_bitwise((t2, l2), (t0, l0), "post-degrade")


def test_megakernel_interpret_mode_failures_surface(monkeypatch):
    """interpret mode is the correctness harness — injected failures must
    NOT be swallowed into a silent fallback (the partition kernel's
    contract, extended to the megakernel)."""
    binner, bins_t, grad, hess = _inputs(n=1200, seed=13)
    args = _args(binner, bins_t, grad, hess)
    monkeypatch.setenv("LGBMTPU_MEGAKERNEL", "interpret")
    monkeypatch.setenv("LGBMTPU_FAULT", "pallas_round:0")
    with pytest.raises(faults.InjectedFault):
        grow_tree_windowed(*args, **_BASE)
    assert degrade.available(degrade.ROUND)


def test_sharded_megakernel_bitwise_with_merge_unchanged(monkeypatch):
    """SPMD: the megakernel fuses each rank's partition + window
    histogram; the leaf-histogram merge stays the round's single
    in-dispatch collective (the jaxpr contract
    windowed_round_sharded_megakernel_psum pins the sequence is
    UNCHANGED), and the grown tree is bitwise the non-megakernel
    sharded tree."""
    from lightgbm_tpu.parallel.data_parallel import (
        ShardedData, grow_tree_windowed_data_parallel)
    from lightgbm_tpu.parallel.mesh import make_mesh

    rng = np.random.RandomState(9)
    n, f = 1024, 8
    X = rng.randn(n, f)
    y = X @ rng.randn(f) + 0.2 * rng.randn(n)
    binner = DatasetBinner.fit(X, max_bin=31)
    mesh = make_mesh()
    sd = ShardedData(mesh, binner.transform(X),
                     binner.num_bins_per_feature,
                     binner.missing_bin_per_feature)
    grad = sd.pad_rows((0.6 * y).astype(np.float32))
    hess = sd.pad_rows(np.ones(n, np.float32))
    sw = sd.pad_rows(np.ones(n, np.float32), fill=1.0)
    kw = dict(num_leaves=15, num_bins=32,
              params=SplitParams(min_data_in_leaf=5.0), leaf_tile=2,
              use_pallas=False)

    monkeypatch.setenv("LGBMTPU_MEGAKERNEL", "0")
    t0, l0 = grow_tree_windowed_data_parallel(
        sd, grad, hess, sd.row_valid, sw, jnp.ones((f,), bool), **kw)
    monkeypatch.setenv("LGBMTPU_MEGAKERNEL", "interpret")
    t1, l1 = grow_tree_windowed_data_parallel(
        sd, grad, hess, sd.row_valid, sw, jnp.ones((f,), bool), **kw)
    _assert_trees_bitwise((t1, l1), (t0, l0), "sharded psum")

    # the LAYERED degrade net, sharded edition: an injected megakernel
    # failure disables ROUND and regrows on the three-pass sharded round
    # (same tree) instead of killing distributed training
    monkeypatch.setenv("LGBMTPU_MEGAKERNEL", "1")
    monkeypatch.setenv("LGBMTPU_FAULT", "pallas_round:0")
    t2, l2 = grow_tree_windowed_data_parallel(
        sd, grad, hess, sd.row_valid, sw, jnp.ones((f,), bool), **kw)
    _assert_trees_bitwise((t2, l2), (t0, l0), "sharded degraded round")
    assert not degrade.available(degrade.ROUND)
