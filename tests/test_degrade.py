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
        degrade.run_with_fallback(degrade.PARTITION, primary, lambda: "xla")
    assert degrade.available(degrade.PARTITION)
    assert degrade.disabled_reason(degrade.PARTITION) is None

    def mosaic():
        raise RuntimeError("Mosaic failed to compile TPU kernel: scoped "
                           "vmem limit exceeded")

    assert degrade.run_with_fallback(
        degrade.PARTITION, mosaic, lambda: "xla") == "xla"
    assert "Mosaic" in degrade.disabled_reason(degrade.PARTITION)


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


def _partition_fixture(n=64, s=2, seed=0):
    rng = np.random.RandomState(seed)
    order = jnp.asarray(rng.permutation(n).astype(np.int32))
    seg_start = jnp.asarray([0, 40], jnp.int32)
    seg_len = jnp.asarray([24, 24], jnp.int32)
    seg_id = np.full(n, -1, np.int32)
    seg_id[0:24] = 0
    seg_id[40:64] = 1
    go_left = jnp.asarray(rng.rand(n) < 0.5)
    return order, jnp.asarray(seg_id), seg_start, seg_len, go_left


def test_partition_dispatcher_degrades_and_matches_xla(monkeypatch):
    """An injected Pallas failure in partition_rows falls back to the XLA
    permutation with IDENTICAL results, and records the degradation so
    later traces skip the kernel entirely."""
    from lightgbm_tpu.ops.partition import (partition_rows,
                                            stable_partition_ranges)

    order, seg_id, seg_start, seg_len, go_left = _partition_fixture()
    ref_order, ref_counts = stable_partition_ranges(
        order, seg_id, seg_start, seg_len, go_left)

    monkeypatch.setenv("LGBMTPU_FAULT", "pallas_partition:0")
    got_order, got_counts = partition_rows(
        order, seg_id, seg_start, seg_len, go_left, use_pallas=True)
    np.testing.assert_array_equal(np.asarray(got_order), np.asarray(ref_order))
    np.testing.assert_array_equal(np.asarray(got_counts),
                                  np.asarray(ref_counts))
    assert not degrade.available(degrade.PARTITION)
    # degraded process: the kernel is skipped without needing the fault
    got2, _ = partition_rows(order, seg_id, seg_start, seg_len, go_left,
                             use_pallas=True)
    np.testing.assert_array_equal(np.asarray(got2), np.asarray(ref_order))


def test_partition_interpret_mode_failures_surface(monkeypatch):
    """interpret=True is the correctness harness — injected failures must
    NOT be swallowed into a silent fallback there."""
    from lightgbm_tpu.ops.partition import partition_rows

    order, seg_id, seg_start, seg_len, go_left = _partition_fixture(seed=1)
    monkeypatch.setenv("LGBMTPU_FAULT", "pallas_partition:0")
    with pytest.raises(faults.InjectedFault):
        partition_rows(order, seg_id, seg_start, seg_len, go_left,
                       interpret=True)
    assert degrade.available(degrade.PARTITION)


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


def test_grower_level_retry_catches_execute_time_failures(monkeypatch):
    """A Pallas failure that escapes the trace-time dispatchers (compile/
    execute time) is caught by the grower wrapper: disable + regrow on
    the XLA path from the original inputs.  Since round 16 the net is
    LAYERED: with the megakernel forced on (``auto`` no longer selects
    it), the first failure is attributed to the ROUND kernel (retry on
    the three-pass round, Pallas hist still on); a second failure
    degrades HIST and lands on the XLA path."""
    from lightgbm_tpu.ops import treegrow_windowed as tw

    calls = []
    real = tw._grow_windowed_impl

    def flaky(*args, **kwargs):
        calls.append(kwargs.get("use_pallas"))
        if kwargs.get("use_pallas"):
            raise RuntimeError("Mosaic kernel compile failed (injected)")
        return real(*args, **kwargs)

    monkeypatch.setattr(tw, "_grow_windowed_impl", flaky)

    from tests.test_nonfinite import _windowed_inputs

    bins_t, grad, hess, kw, static = _windowed_inputs(seed=9)
    static = dict(static, use_pallas=True, megakernel_opt="1")
    tree, leaf = tw.grow_tree_windowed(bins_t, grad, hess, **kw, **static)
    assert calls == [True, True, False]
    assert int(tree.num_leaves) > 1
    assert not degrade.available(degrade.ROUND)
    assert not degrade.available(degrade.HIST)

    # a second tree folds the registry into the static before dispatch:
    # no pallas attempt, no exception
    calls.clear()
    tree2, _ = tw.grow_tree_windowed(bins_t, grad, hess, **kw, **static)
    assert calls == [False]


def test_grower_level_retry_does_not_swallow_real_errors(monkeypatch):
    from lightgbm_tpu.ops import treegrow_windowed as tw

    def broken(*args, **kwargs):
        raise ValueError("genuine bug, not a kernel failure")

    monkeypatch.setattr(tw, "_grow_windowed_impl", broken)
    from tests.test_nonfinite import _windowed_inputs

    bins_t, grad, hess, kw, static = _windowed_inputs(seed=10)
    with pytest.raises(ValueError, match="genuine bug"):
        tw.grow_tree_windowed(bins_t, grad, hess, **kw,
                              **dict(static, use_pallas=True))
    assert degrade.available(degrade.HIST)
