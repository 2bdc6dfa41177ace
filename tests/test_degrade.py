"""Graceful kernel degradation (utils/degrade.py): a Pallas kernel
failure is caught once, logged via utils/log.py, and the process
permanently falls back to the numerically identical XLA path — no manual
env var, no dead run.  Driven by the pallas_* fault-injection sites."""

import logging

import numpy as np
import jax.numpy as jnp
import pytest

from lightgbm_tpu.utils import degrade, faults


@pytest.fixture(autouse=True)
def _clean():
    degrade.reset()
    faults.reset()
    yield
    degrade.reset()
    faults.reset()


def test_classifier_recognizes_kernel_failures_only():
    assert degrade.is_pallas_failure(faults.InjectedFault("pallas_hist", 0))
    assert degrade.is_pallas_failure(RuntimeError("Mosaic lowering failed"))
    assert degrade.is_pallas_failure(ValueError("pallas_call: bad block"))
    assert not degrade.is_pallas_failure(ValueError("shapes do not match"))
    assert not degrade.is_pallas_failure(
        faults.InjectedFault("worker_death", 1))


def test_python_level_errors_never_degrade():
    """API drift reads like a kernel failure ("module
    'jax.experimental.pallas.tpu' has no attribute ...") and is a bug in
    this repo: it propagates through the net and disables nothing, while
    a Mosaic-style runtime error still degrades."""
    drift = AttributeError(
        "module 'jax.experimental.pallas.tpu' has no attribute "
        "'RenamedParams'")
    for exc in (drift, TypeError("pallas_call() got an unexpected keyword"),
                ImportError("cannot import name 'pallas'"),
                NameError("name 'pltpu' is not defined (mosaic)")):
        assert not degrade.is_pallas_failure(exc)

    def primary():
        raise drift

    with pytest.raises(AttributeError, match="RenamedParams"):
        degrade.run_with_fallback(degrade.HIST, primary, lambda: "xla")
    assert degrade.available(degrade.HIST)
    assert degrade.disabled_reason(degrade.HIST) is None

    def mosaic():
        raise RuntimeError("Mosaic failed to compile TPU kernel: scoped "
                           "vmem limit exceeded")

    assert degrade.run_with_fallback(
        degrade.HIST, mosaic, lambda: "xla") == "xla"
    assert "Mosaic" in degrade.disabled_reason(degrade.HIST)


def test_disable_logs_once(caplog):
    logger = logging.getLogger("lgbm_degrade_test")
    import lightgbm_tpu as lgb
    from lightgbm_tpu.utils.log import set_verbosity

    # earlier suite tests train with verbosity=-1, which silences
    # log_warning process-wide — pin it back for this assertion
    set_verbosity(1)
    lgb.register_logger(logger)
    try:
        with caplog.at_level(logging.WARNING, logger="lgbm_degrade_test"):
            degrade.disable(degrade.HIST, "test reason")
            degrade.disable(degrade.HIST, "second reason ignored")
        assert len(caplog.records) == 1
        assert "falling back to the XLA path" in caplog.records[0].message
        assert not degrade.available(degrade.HIST)
        assert degrade.disabled_reason(degrade.HIST) == "test reason"
    finally:
        from lightgbm_tpu.utils import log as _log

        _log._logger = None


def _hist_fixture(n=256, f=4, tile=2, bins=16, seed=0):
    rng = np.random.RandomState(seed)
    b = jnp.asarray(rng.randint(0, bins, (n, f)), jnp.int16)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    h = jnp.asarray(rng.rand(n) + 0.5, jnp.float32)
    mask = jnp.asarray(rng.rand(n) < 0.9)
    leaf = jnp.asarray(rng.randint(0, tile, n), jnp.int32)
    return b, g, h, mask, leaf, tile, bins


def test_hist_dispatcher_degrades_and_matches_xla(monkeypatch):
    from lightgbm_tpu.ops.histogram import histogram_multi, histogram_onehot_multi

    b, g, h, mask, leaf, tile, bins = _hist_fixture()
    ref = histogram_onehot_multi(b, g, h, mask, leaf, 0, tile, bins)

    monkeypatch.setenv("LGBMTPU_FAULT", "pallas_hist:0")
    got = histogram_multi(b, g, h, mask, leaf, 0, tile, bins)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)
    assert not degrade.available(degrade.HIST)


def test_hist_dispatcher_quantized_degrades(monkeypatch):
    from lightgbm_tpu.ops.histogram import (histogram_multi_quantized,
                                            histogram_onehot_multi_quantized)

    rng = np.random.RandomState(1)
    n, f, tile, bins = 256, 3, 2, 16
    b = jnp.asarray(rng.randint(0, bins, (n, f)), jnp.int16)
    gq = jnp.asarray(rng.randint(-50, 50, n), jnp.int8)
    hq = jnp.asarray(rng.randint(0, 100, n), jnp.int8)
    mask = jnp.ones((n,), bool)
    leaf = jnp.asarray(rng.randint(0, tile, n), jnp.int32)
    ref = histogram_onehot_multi_quantized(b, gq, hq, mask, leaf, 0, tile,
                                           bins)
    monkeypatch.setenv("LGBMTPU_FAULT", "pallas_hist:0")
    got = histogram_multi_quantized(b, gq, hq, mask, leaf, 0, tile, bins)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert not degrade.available(degrade.HIST)


def _rounds_inputs(n=600, f=6, seed=9):
    from lightgbm_tpu.binning import DatasetBinner
    from lightgbm_tpu.ops.split import SplitParams

    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = X @ rng.randn(f) + 0.2 * rng.randn(n)
    binner = DatasetBinner.fit(X, max_bin=31)
    args = (jnp.asarray(binner.transform(X), jnp.int16),
            jnp.asarray(0.6 * y, jnp.float32), jnp.ones((n,), jnp.float32),
            jnp.ones((n,), bool), jnp.ones((n,), jnp.float32),
            jnp.ones((f,), bool),
            jnp.asarray(binner.num_bins_per_feature),
            jnp.asarray(binner.missing_bin_per_feature))
    static = dict(num_leaves=15, num_bins=32, params=SplitParams(
        min_data_in_leaf=5.0), leaf_tile=4)
    return args, static


def test_grower_level_retry_catches_execute_time_failures(monkeypatch):
    """A Pallas failure that escapes the trace-time dispatchers (compile/
    execute time) is caught by the rounds grower's wrapper: HIST is
    disabled and the tree regrown on the XLA path from the original
    inputs; the next tree folds the registry into the static before
    dispatch and never attempts the kernel."""
    from lightgbm_tpu.ops import treegrow_fast as tf

    calls = []
    real = tf._grow_fast_impl

    def flaky(*args, **kwargs):
        calls.append(kwargs.get("use_pallas"))
        if kwargs.get("use_pallas"):
            raise RuntimeError("Mosaic kernel compile failed (injected)")
        return real(*args, **kwargs)

    monkeypatch.setattr(tf, "_grow_fast_impl", flaky)
    args, static = _rounds_inputs()
    tree, leaf = tf.grow_tree_fast(*args, use_pallas=True, **static)
    assert calls == [True, False]
    assert int(tree.num_leaves) > 1
    assert not degrade.available(degrade.HIST)

    calls.clear()
    tf.grow_tree_fast(*args, use_pallas=True, **static)
    assert calls == [False]


def test_grower_level_retry_does_not_swallow_real_errors(monkeypatch):
    from lightgbm_tpu.ops import treegrow_fast as tf

    def broken(*args, **kwargs):
        raise ValueError("genuine bug, not a kernel failure")

    monkeypatch.setattr(tf, "_grow_fast_impl", broken)
    args, static = _rounds_inputs(seed=10)
    with pytest.raises(ValueError, match="genuine bug"):
        tf.grow_tree_fast(*args, use_pallas=True, **static)
    assert degrade.available(degrade.HIST)
