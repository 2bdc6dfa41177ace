"""Fault-injection harness unit tests (utils/faults.py): spec parsing,
deterministic once-only firing, rank gating, cross-process markers.  The
end-to-end recovery scenarios the harness drives live in test_resume.py
(host crash / snapshot-write crash), test_nonfinite.py (NaN grads),
test_degrade.py (Pallas kernel failure) and test_launcher.py
(worker death + watchdog restart)."""

import numpy as np
import pytest

from lightgbm_tpu.utils import faults


@pytest.fixture(autouse=True)
def _clean_registry():
    faults.reset()
    yield
    faults.reset()


def test_parse_spec_grammar():
    assert faults.parse_spec("") == {}
    assert faults.parse_spec("host_crash:3") == {"host_crash": 3}
    assert faults.parse_spec("host_crash:3,pallas_hist:0") == {
        "host_crash": 3, "pallas_hist": 0}
    with pytest.raises(ValueError):
        faults.parse_spec("host_crash")  # missing round
    with pytest.raises(ValueError):
        faults.parse_spec("host_crash:x")


def test_fire_is_deterministic_and_once(monkeypatch):
    monkeypatch.setenv("LGBMTPU_FAULT", "host_crash:3")
    assert not faults.fire("host_crash", 1)
    assert not faults.fire("host_crash", 2)
    assert faults.fire("host_crash", 3)
    # once only, even if the same round is probed again (a resumed loop)
    assert not faults.fire("host_crash", 3)
    # unarmed sites never fire
    assert not faults.fire("snapshot_write", 3)


def test_unarmed_env_is_free_of_side_effects(monkeypatch):
    monkeypatch.delenv("LGBMTPU_FAULT", raising=False)
    assert not faults.fire("host_crash", 1)
    faults.maybe_fail("pallas_hist")  # call-counted site: must not raise
    arr = np.ones(4)
    assert faults.corrupt_nonfinite("nonfinite_grad", 1, arr) is arr


def test_call_counted_sites(monkeypatch):
    monkeypatch.setenv("LGBMTPU_FAULT", "pallas_hist:2")
    faults.maybe_fail("pallas_hist")  # call 0
    faults.maybe_fail("pallas_hist")  # call 1
    with pytest.raises(faults.InjectedFault) as ei:
        faults.maybe_fail("pallas_hist")  # call 2 fires
    assert ei.value.site == "pallas_hist"
    faults.maybe_fail("pallas_hist")  # counter moved past: clean again
    monkeypatch.setenv("LGBMTPU_FAULT", "host_crash:1")
    with pytest.raises(ValueError):
        faults.fire("host_crash")  # armed round-stamped site needs a round


def test_rank_gating(monkeypatch):
    monkeypatch.setenv("LGBMTPU_FAULT", "worker_death:1")
    monkeypatch.setenv("LGBMTPU_FAULT_RANK", "1")
    monkeypatch.setenv("LIGHTGBM_TPU_RANK", "0")
    assert not faults.fire("worker_death", 1)
    faults.reset()
    monkeypatch.setenv("LIGHTGBM_TPU_RANK", "1")
    assert faults.fire("worker_death", 1)


def test_would_fire_peeks_and_consumes_nothing(tmp_path, monkeypatch):
    """``would_fire`` says what ``fire`` would do — round, rank gate, the
    once-only registry and marker — and leaves all of it untouched, so a
    crash site can wait for the state its scenario assumes and still die
    (the launcher's ``worker_death`` waits for the fleet's ack)."""
    monkeypatch.setenv("LGBMTPU_FAULT", "worker_death:1:5")
    monkeypatch.setenv("LGBMTPU_FAULT_ONCE_DIR", str(tmp_path))
    monkeypatch.setenv("LGBM_TPU_WORKER_ID", "0")
    assert not faults.would_fire("worker_death", 5)  # another rank's
    monkeypatch.setenv("LGBM_TPU_WORKER_ID", "1")
    assert not faults.would_fire("worker_death", 4)  # another round's
    for _ in range(3):
        assert faults.would_fire("worker_death", 5)
    assert not list(tmp_path.glob("lgbmtpu_fault_*.fired"))
    assert faults.fire("worker_death", 5)
    assert not faults.would_fire("worker_death", 5)  # fired: spent
    faults.reset()  # the relaunched process: the marker still stops it
    assert not faults.would_fire("worker_death", 5)


def test_once_dir_markers_survive_process_registry(tmp_path, monkeypatch):
    """The cross-process once-only contract: a marker file left by the
    'first process' stops the 'second process' (fresh registry) from
    re-firing — how a watchdog relaunch runs clean."""
    monkeypatch.setenv("LGBMTPU_FAULT", "worker_death:2")
    monkeypatch.setenv("LGBMTPU_FAULT_ONCE_DIR", str(tmp_path))
    assert faults.fire("worker_death", 2)
    faults.reset()  # simulate the relaunched process
    assert not faults.fire("worker_death", 2)
    markers = list(tmp_path.glob("lgbmtpu_fault_*.fired"))
    assert len(markers) == 1


def test_corrupt_nonfinite_poisons_at_round(monkeypatch):
    monkeypatch.setenv("LGBMTPU_FAULT", "nonfinite_grad:2")
    a = np.zeros(5)
    assert faults.corrupt_nonfinite("nonfinite_grad", 1, a) is a
    b = faults.corrupt_nonfinite("nonfinite_grad", 2, np.zeros(5))
    assert np.isnan(b[0]) and np.isfinite(b[1:]).all()

    import jax.numpy as jnp

    faults.reset()
    d = faults.corrupt_nonfinite("nonfinite_grad", 2, jnp.zeros((4,)))
    assert bool(jnp.isnan(d[0]))
