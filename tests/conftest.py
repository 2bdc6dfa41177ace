"""Test configuration: run on a virtual 8-device CPU mesh.

Mirrors the reference's multi-node-without-a-cluster test strategy
(tests/test_dask.py LocalCluster, tests/distributed/_test_distributed.py):
sharding tests run against N virtual CPU devices via
--xla_force_host_platform_device_count, no TPU required (SURVEY.md §5.3).

The suite is hermetic on the local CPU backend: ``JAX_PLATFORMS=cpu`` is set
before jax is imported and verified below.  (An os.execve re-exec is NOT an
option here: pytest's fd-level capture is already active when conftest loads,
so the re-exec'd process inherits redirected fds and its output is orphaned.)
"""

import gc
import os

import pytest

os.environ.setdefault("JAX_ENABLE_X64", "0")

# Persistent XLA compilation cache: tier-1 wall clock is dominated by CPU
# backend compiles (the bucket ladder + fused round re-compile identical
# HLO every run), and a warm disk cache roughly halves the suite.  The
# directory is use_compile_cache()'s (called below, once jax is importable
# under the environment set here); only compiles >= 0.5s are cached, so
# cheap per-test executables still exercise the real compile path and
# in-process retrace/budget pins (which hook trace events and executable
# reuse, not disk) are unaffected.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.5")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

from lightgbm_tpu.utils.compile_cache import use_compile_cache  # noqa: E402

use_compile_cache()

try:
    jax.config.update("jax_platforms", "cpu")
except Exception:  # backends already initialized: verified cpu below
    pass

# fail fast if another backend was selected anyway — a non-hermetic run
# would otherwise surface as confusing library failures
assert jax.default_backend() == "cpu", (
    f"test suite must run on local CPU, got {jax.default_backend()!r}"
)

# run the WHOLE suite under the runtime lock sanitizer, strict: every
# traced acquire checks the witness graph and raises LockOrderError on a
# cycle, and blocking acquires become 60s timeout-acquires so a true
# deadlock fails the test instead of hanging the run (docs/ANALYSIS.md)
from lightgbm_tpu.obs import metrics as _obs_metrics  # noqa: E402
from lightgbm_tpu.utils import degrade as _degrade  # noqa: E402
from lightgbm_tpu.utils import faults as _faults  # noqa: E402
from lightgbm_tpu.utils import locktrace as _locktrace  # noqa: E402

_locktrace.enable(True, strict=True)


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    """Drop jax's compiled programs after every test module.

    Each compiled or cache-loaded CPU executable holds memory maps, and the
    jit caches keep every one alive.  The suite in one process reached
    63,365 maps at the last sample before it died, against the kernel's
    vm.max_map_count of 65,530: a segmentation fault inside XLA, in
    compilation_cache.put_executable_and_time or get_executable_and_time,
    some 85% of the way through, with a cold cache or a warm one (PR 21).
    What a later module shares with an earlier one comes back from the
    persistent cache.

    A worker runs several files in one process (``--dist loadfile``), so
    the process-wide robustness state goes with the programs: the
    ``utils/degrade`` registry, armed fault sites and the ``obs``
    counters.  ``/healthz``, the serving runtime's shedding and
    ``chip_smoke.check_no_fallback`` read those as totals of a fresh
    process; what ``test_degrade.py`` or ``test_nonfinite.py`` left in
    them made ``test_chip_smoke.py`` fail whenever it shared their
    worker (PR 30)."""
    yield
    jax.clear_caches()
    gc.collect()
    _degrade.reset()
    _faults.reset()
    _obs_metrics.reset()
