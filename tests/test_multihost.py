"""Two-process loopback bring-up test (SURVEY §5.2 pattern; VERDICT item 9):
each process maps the reference-style machine list onto
jax.distributed.initialize, forms the GLOBAL device backend, and runs a
cross-process psum — the DCN collective path of the distributed learners."""

import os
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
sys.path.insert(0, {repo!r})

from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel.distributed import init_distributed

cfg = Config.from_dict({{
    "num_machines": 2,
    "machines": "127.0.0.1:{port},127.0.0.1:{port2}",
    "local_listen_port": {port},
    "time_out": 2,
}})
assert init_distributed(cfg)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 4, jax.device_count()

mesh = Mesh(np.asarray(jax.devices()), ("d",))
rank = jax.process_index()

def f(x):
    return jax.lax.psum(x, "d")

g = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("d"), out_specs=P()))
local = jax.make_array_from_process_local_data(
    jax.sharding.NamedSharding(mesh, P("d")),
    np.full((2,), float(rank + 1), np.float32),
)
out = g(local)
# ranks contribute 1+1+2+2 = 6; result is replicated so locally readable
val = float(np.asarray(out.addressable_data(0)).ravel()[0])
assert abs(val - 6.0) < 1e-6, val
print(f"RANK{{rank}}_OK", val)
"""


pytestmark = pytest.mark.slow

@pytest.mark.skipif(os.environ.get("SKIP_MULTIHOST") == "1", reason="opt-out")
def test_two_process_loopback_psum(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port, port2 = 29771, 29772
    procs = []
    for rank in range(2):
        script = _WORKER.format(repo=repo, port=port, port2=port2)
        env = dict(os.environ)
        env["LIGHTGBM_TPU_RANK"] = str(rank)
        # the backend is chosen when python starts: set it in the child env
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env.pop("PYTEST_CURRENT_TEST", None)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", script],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            )
        )
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=240)
        outs.append(out.decode())
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out[-3000:]}"
        assert f"RANK{rank}_OK" in out


_TRAIN_WORKER = r"""
import os, sys
sys.path.insert(0, {repo!r})

from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel.distributed import init_distributed

cfg = Config.from_dict({{
    "num_machines": 2,
    "machines": "127.0.0.1:{port},127.0.0.1:{port2}",
    "local_listen_port": {port},
    "time_out": 2,
}})
assert init_distributed(cfg)

import jax
import numpy as np
import lightgbm_tpu as lgb

assert jax.process_count() == 2
rank = jax.process_index()

rng = np.random.RandomState(11)
X = rng.randn(4000, 6)
y = (X @ rng.randn(6) + 0.3 * rng.randn(4000) > 0).astype(float)
params = {{"objective": "binary", "num_leaves": 8, "verbosity": -1,
          "tree_learner": "data", "min_data_in_leaf": 5}}
ds = lgb.Dataset(X, label=y)
bst = lgb.train(params, ds, 3)
s_dist = bst.model_to_string()
with open({out!r} + f".rank{{rank}}", "w") as fh:
    fh.write(s_dist)
if rank == 0:
    # reference: tests/distributed/_test_distributed.py — the distributed
    # model must equal the single-machine model.  Structure must match
    # EXACTLY; leaf values may differ at f32-psum-ordering level (the same
    # tolerance tests/test_distributed.py uses single-process).
    ds2 = lgb.Dataset(X, label=y)
    bst2 = lgb.train(dict(params, tree_learner="serial"), ds2, 3)
    s_serial = bst2.model_to_string()

    def parts(s, key):
        return [ln for ln in s.splitlines() if ln.startswith(key + "=")]

    for key in ("split_feature", "threshold", "decision_type", "num_leaves"):
        assert parts(s_dist, key) == parts(s_serial, key), key
    lv_d = [float(v) for ln in parts(s_dist, "leaf_value")
            for v in ln.split("=")[1].split()]
    lv_s = [float(v) for ln in parts(s_serial, "leaf_value")
            for v in ln.split("=")[1].split()]
    np.testing.assert_allclose(lv_d, lv_s, rtol=2e-3, atol=2e-3)
print(f"RANK{{rank}}_TRAIN_OK")
"""


_TWO_ROUND_WORKER = r"""
import os, sys
sys.path.insert(0, {repo!r})

from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel.distributed import init_distributed

cfg = Config.from_dict({{
    "num_machines": 2,
    "machines": "127.0.0.1:{port},127.0.0.1:{port2}",
    "local_listen_port": {port},
    "time_out": 2,
}})
assert init_distributed(cfg)

import jax
import numpy as np
import lightgbm_tpu as lgb

rank = jax.process_index()
rng = np.random.RandomState(13)
n = 4000
X = rng.randn(n, 5)
y = (X @ rng.randn(5) + 0.3 * rng.randn(n) > 0).astype(float)
nv = 1000
Xv = rng.randn(nv, 5)
yv = (Xv @ rng.randn(5) > 0).astype(float)

# each rank streams ONLY its contiguous shard from disk (two_round +
# pre_partition: bin boundaries must sync from the global reservoir sample)
lo, hi = rank * n // 2, (rank + 1) * n // 2
shard_path = {out!r} + f".shard{{rank}}.csv"
np.savetxt(shard_path, np.column_stack([y[lo:hi], X[lo:hi]]), delimiter=",")
params = {{"objective": "binary", "num_leaves": 8, "verbosity": -1,
          "tree_learner": "data", "min_data_in_leaf": 5,
          "pre_partition": True, "two_round": True,
          "bin_construct_sample_cnt": n,
          "metric": ["binary_logloss", "auc"]}}
ds = lgb.Dataset(shard_path, params=params)
vlo, vhi = rank * nv // 2, (rank + 1) * nv // 2
dv = lgb.Dataset(Xv[vlo:vhi], label=yv[vlo:vhi], reference=ds)
rec = {{}}
bst = lgb.train(params, ds, 3, valid_sets=[dv], valid_names=["v"],
                callbacks=[lgb.record_evaluation(rec)])
s_dist = bst.model_to_string()
with open({out!r} + f".rank{{rank}}", "w") as fh:
    fh.write(s_dist)
if rank == 0:
    # serial single-process on the full data must match: structure exactly,
    # leaf values and synced eval metrics to f32-ordering tolerance
    ds2 = lgb.Dataset(X, label=y, params={{"bin_construct_sample_cnt": n}})
    dv2 = lgb.Dataset(Xv, label=yv, reference=ds2)
    rec2 = {{}}
    bst2 = lgb.train({{"objective": "binary", "num_leaves": 8,
                      "verbosity": -1, "min_data_in_leaf": 5,
                      "metric": ["binary_logloss", "auc"]}}, ds2, 3,
                     valid_sets=[dv2], valid_names=["v"],
                     callbacks=[lgb.record_evaluation(rec2)])
    s_serial = bst2.model_to_string()

    def parts(s, key):
        return [ln for ln in s.splitlines() if ln.startswith(key + "=")]

    for key in ("split_feature", "threshold", "decision_type", "num_leaves"):
        assert parts(s_dist, key) == parts(s_serial, key), key
    # the synced valid-set metrics equal the serial full-set metrics
    for mname in ("binary_logloss", "auc"):
        a = np.asarray(rec["v"][mname], float)
        b = np.asarray(rec2["v"][mname], float)
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3), mname
print(f"RANK{{rank}}_2R_OK")
"""


@pytest.mark.skipif(os.environ.get("SKIP_MULTIHOST") == "1", reason="opt-out")
def test_two_round_pre_partition_with_synced_eval(tmp_path):
    """two_round streamed per-rank file shards + pre_partition: bin
    boundaries sync from the global reservoir sample, and valid-set metrics
    sync across ranks (GlobalSyncUpBySum analogue: decomposable metrics sum
    (num, den); AUC gathers shard predictions)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port, port2 = 29791, 29792
    out = str(tmp_path / "model")
    procs = []
    for rank in range(2):
        script = _TWO_ROUND_WORKER.format(repo=repo, port=port, port2=port2,
                                          out=out)
        env = dict(os.environ)
        env["LIGHTGBM_TPU_RANK"] = str(rank)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env.pop("PYTEST_CURRENT_TEST", None)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", script],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            )
        )
    outs = []
    for p in procs:
        o, _ = p.communicate(timeout=300)
        outs.append(o.decode())
    for rank, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{o[-4000:]}"
        assert f"RANK{rank}_2R_OK" in o
    with open(out + ".rank0") as fh:
        m0 = fh.read()
    with open(out + ".rank1") as fh:
        m1 = fh.read()
    assert m0 == m1


@pytest.mark.skipif(os.environ.get("SKIP_MULTIHOST") == "1", reason="opt-out")
def test_two_process_training_equality(tmp_path):
    """End-to-end cross-process training: 2 processes, rows sharded over a
    4-device global mesh (tree_learner=data), and the resulting model must be
    byte-identical to single-process serial training (reference:
    tests/distributed/_test_distributed.py)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port, port2 = 29781, 29782
    out = str(tmp_path / "model")
    procs = []
    for rank in range(2):
        script = _TRAIN_WORKER.format(repo=repo, port=port, port2=port2, out=out)
        env = dict(os.environ)
        env["LIGHTGBM_TPU_RANK"] = str(rank)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        env.pop("PYTEST_CURRENT_TEST", None)
        procs.append(
            subprocess.Popen(
                [sys.executable, "-c", script],
                env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            )
        )
    outs = []
    for p in procs:
        o, _ = p.communicate(timeout=300)
        outs.append(o.decode())
    for rank, (p, o) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{o[-4000:]}"
        assert f"RANK{rank}_TRAIN_OK" in o
    with open(out + ".rank0") as fh:
        m0 = fh.read()
    with open(out + ".rank1") as fh:
        m1 = fh.read()
    assert m0 == m1  # both processes hold the identical model
