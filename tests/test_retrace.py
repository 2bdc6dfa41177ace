"""Retrace regression tests (runtime half of the jaxlint pass): the
compile-counter in utils/sanitizer.py pins "N boosting rounds at a fixed
(shape, dtype) config compile exactly once" — the per-round recompile
class becomes an executable assertion instead of benchmark archaeology."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.binning import DatasetBinner
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.ops.treegrow_fast import grow_tree_fast
from lightgbm_tpu.utils.sanitizer import (CompileCounter, RetraceError,
                                          expect_compiles)


def _grower_inputs(n=800, f=8, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = X @ rng.randn(f) + 0.2 * rng.randn(n)
    binner = DatasetBinner.fit(X, max_bin=31)
    bins = jnp.asarray(binner.transform(X), jnp.int16)
    kw = dict(
        row_mask=jnp.ones((n,), bool),
        sample_weight=jnp.ones((n,), jnp.float32),
        feature_mask=jnp.ones((f,), bool),
        num_bins_per_feature=jnp.asarray(binner.num_bins_per_feature),
        missing_bin_per_feature=jnp.asarray(binner.missing_bin_per_feature),
    )
    grads = [jnp.asarray(2.0 * (0.3 * y) + 0.1 * k, jnp.float32)
             for k in range(4)]
    hess = jnp.ones((n,), jnp.float32)
    static = dict(num_leaves=15, num_bins=32, params=SplitParams(
        min_data_in_leaf=5.0), leaf_tile=4, use_pallas=False)
    return bins, grads, hess, kw, static


def test_fast_grower_compiles_once_across_rounds():
    """Boosting calls the fast grower once per tree with identical shapes
    and statics; after the warm-up call, further rounds must be pure cache
    hits — zero traces, zero backend compiles."""
    bins, grads, hess, kw, static = _grower_inputs()
    # warm-up: the one compile this (shape, dtype, static) config is allowed
    tree, leaf = grow_tree_fast(bins, grads[0], hess, **kw, **static)
    jax.block_until_ready(leaf)

    with CompileCounter() as c:
        for g in grads[1:]:
            tree, leaf = grow_tree_fast(bins, g, hess, **kw, **static)
        jax.block_until_ready(leaf)
    c.assert_no_recompile("3 boosting rounds at fixed shape")


def test_counter_detects_artificial_retrace():
    """Introduce the retrace class the gate protects against — a static
    argument that varies across rounds — and demonstrate the counter
    catches it (the regression test above would fail exactly like this)."""
    bins, grads, hess, kw, static = _grower_inputs()
    tree, leaf = grow_tree_fast(bins, grads[0], hess, **kw, **static)
    jax.block_until_ready(leaf)

    with CompileCounter() as c:
        # same data, same shapes — but leaf_tile (a static) changes, which
        # is precisely what a per-round varying static does to the cache
        retraced = dict(static, leaf_tile=8)
        tree, leaf = grow_tree_fast(bins, grads[1], hess, **kw, **retraced)
        jax.block_until_ready(leaf)
    assert c.traces >= 1, "artificial retrace went unnoticed by the counter"

    with pytest.raises(RetraceError):
        c.assert_no_recompile("artificial retrace")


def test_expect_compiles_contract():
    @jax.jit
    def fn(x):
        return x * 2

    x = jnp.arange(8.0)
    with expect_compiles(1, "cold jit"):
        jax.block_until_ready(fn(x))
    with expect_compiles(0, "warm jit"):
        jax.block_until_ready(fn(x))
    with pytest.raises(RetraceError):
        with expect_compiles(3, "wrong expectation"):
            jax.block_until_ready(fn(x))


def test_booster_steady_state_does_not_retrace():
    """Engine-level: after two warm iterations (round 1 compiles the fused
    step; round 2 covers anything keyed off iteration parity), further
    Booster.update() rounds must not trace or compile anything new."""
    rng = np.random.RandomState(3)
    X = rng.randn(400, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(float)
    d = lgb.Dataset(X, label=y)
    bst = lgb.Booster(params={"objective": "binary", "num_leaves": 7,
                              "verbosity": -1}, train_set=d)
    for _ in range(2):
        bst.update()
    np.asarray(bst._gbdt._score)  # drain pending device work

    with CompileCounter() as c:
        for _ in range(3):
            bst.update()
        np.asarray(bst._gbdt._score)
    c.assert_no_recompile("Booster.update steady state")


def test_rounds_grower_steady_state_zero_syncs_no_retrace_telemetry_on():
    """The rounds grower's steady state with telemetry and span tracing
    default-ON: after the warm-up tree, a further tree at fixed shape
    traces and compiles nothing and leaves the accounted ledger empty —
    the grower pulls nothing to the host, so nothing the obs layer hooks
    can have added a sync either."""
    from lightgbm_tpu.obs import metrics as obs_metrics
    from lightgbm_tpu.utils.sanitizer import DispatchCounter

    assert obs_metrics.enabled()  # telemetry default-on: the pin's point
    bins, grads, hess, kw, static = _grower_inputs(n=900, seed=5)
    tree, leaf = grow_tree_fast(bins, grads[0], hess, **kw, **static)
    jax.block_until_ready(leaf)
    assert int(tree.num_leaves) > 1

    with DispatchCounter() as d:
        tree, leaf = grow_tree_fast(bins, grads[1], hess, **kw, **static)
        jax.block_until_ready(leaf)
    assert int(tree.num_leaves) == 15
    assert d.host_syncs == 0 and d.async_resolves == 0, (
        d.host_syncs, d.async_resolves)
    d.assert_no_recompile("a second rounds-grower tree at fixed shape")


def test_sharded_rounds_grower_zero_syncs_no_retrace_telemetry_on():
    """The data-parallel rounds grower (8-device loopback mesh, the psum
    merge inside the grower's program) keeps the same steady state —
    single-controller, so the host's dispatches ARE every rank's — with
    telemetry default-ON: the second tree compiles nothing and the
    accounted ledger stays empty."""
    from lightgbm_tpu.obs import metrics as obs_metrics
    from lightgbm_tpu.parallel.data_parallel import (
        ShardedData, grow_tree_fast_data_parallel)
    from lightgbm_tpu.parallel.mesh import make_mesh
    from lightgbm_tpu.utils.sanitizer import DispatchCounter

    assert obs_metrics.enabled()
    rng = np.random.RandomState(9)
    n, f = 1024, 8
    X = rng.randn(n, f)
    y = X @ rng.randn(f) + 0.2 * rng.randn(n)
    binner = DatasetBinner.fit(X, max_bin=31)
    sd = ShardedData(make_mesh(), binner.transform(X),
                     binner.num_bins_per_feature,
                     binner.missing_bin_per_feature)
    grads = [sd.pad_rows((0.6 * y + 0.05 * k).astype(np.float32))
             for k in range(2)]
    hess = sd.pad_rows(np.ones(n, np.float32))
    sw = sd.pad_rows(np.ones(n, np.float32), fill=1.0)
    kw = dict(num_leaves=15, num_bins=32,
              params=SplitParams(min_data_in_leaf=5.0), leaf_tile=4,
              use_pallas=False)
    tree, leaf = grow_tree_fast_data_parallel(
        sd, grads[0], hess, sd.row_valid, sw, jnp.ones((f,), bool), **kw)
    jax.block_until_ready(leaf)
    assert int(tree.num_leaves) > 1

    with DispatchCounter() as d:
        tree, leaf = grow_tree_fast_data_parallel(
            sd, grads[1], hess, sd.row_valid, sw, jnp.ones((f,), bool),
            **kw)
        jax.block_until_ready(leaf)
    assert int(tree.num_leaves) == 15
    assert d.host_syncs == 0 and d.async_resolves == 0, (
        d.host_syncs, d.async_resolves)
    d.assert_no_recompile("sharded rounds-grower steady state")
