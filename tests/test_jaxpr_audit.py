"""Tier-1 jaxpr-audit gate + per-J-rule fixtures (docs/ANALYSIS.md
"Jaxpr audit layer").

Gate half: every registered contract (analysis/contracts.py) must verify
clean on the container CPU — exactly the declared collectives (none, for
every contract the package has) on the declared mesh axis, every live
donated buffer consumable, zero f64 casts, zero host callbacks, the
live-set estimate under budget.  This is the static gate for the
regression class the AST rules cannot see.

Fixture half: each J rule is exercised on a deliberately broken tiny
executable, mirroring tests/test_jaxlint_rules.py's
positive/negative/waiver pattern.
"""

import numpy as np
import pytest

from lightgbm_tpu.analysis import jaxpr_audit
from lightgbm_tpu.analysis.contracts import CONTRACTS, Contract, Target


# ---------------------------------------------------------------------------
# the gate: one full audit per session, asserted from every angle
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def report():
    return jaxpr_audit.run_jaxpr_audit()


def test_contract_catalogue_pins_the_flagships():
    assert {
        "predict_warm_single", "predict_warm_multiclass",
        "predict_warm_converted", "predict_coalesced_bucket",
        "ooc_root_chunk", "ooc_split_chunk", "continual_refit_leaves",
    } == set(CONTRACTS)


def test_all_contracts_verify_clean(report):
    assert report.ok, (
        "jaxpr-audit findings (fix the executable or waive in "
        "analysis/contracts.py with a reason):\n"
        + "\n".join(f.format() for f in report.findings))


def test_single_device_bodies_are_collective_free(report):
    assert {r.name for r in report.results} == set(CONTRACTS)
    for r in report.results:
        assert r.detail.get("collectives") == [], (r.name, r.detail)


def test_coalesced_dispatch_is_the_warm_predict_family():
    """ISSUE 13: the serving runtime's coalesced dispatch must be the
    SAME traced executable family as warm predict — pinned two ways: the
    runtime's selector resolves to the very predict_ops functions the
    warm contracts audit (identity, so the contract traces the serving
    loop's real dispatch), and the audited body is collective-free /
    transfer-free like its warm siblings (the report gate above)."""
    from lightgbm_tpu.models.gbdt import GBDT
    from lightgbm_tpu.ops import predict as predict_ops
    from lightgbm_tpu.serve.runtime import audit_dispatch_fn

    assert audit_dispatch_fn(1) is predict_ops.predict_raw_values
    assert audit_dispatch_fn(4) is predict_ops.predict_raw_multiclass
    assert GBDT._coalesced_raw_fn(1) is predict_ops.predict_raw_values
    assert GBDT._coalesced_raw_fn(3) is predict_ops.predict_raw_multiclass


def test_continual_refit_is_one_donated_collective_free_dispatch(report):
    """ISSUE 14: the continual refit dispatch — resolved through the
    runner's own builder (continual.refit.audit_refit_fn) — is ONE
    donated executable: zero collectives (J1, single-device), the
    donated leaf table consumed and aliased in the lowering (J2), and
    transfer-free (J5, the report gate above)."""
    r = {x.name: x for x in report.results}["continual_refit_leaves"]
    assert r.detail.get("collectives") == []
    assert r.detail.get("live_donated_leaves") == 1
    assert r.detail.get("aliased_in_lowering") == 1


def test_donations_all_consumable(report):
    """J2 detail: every live donated leaf structurally matched an output
    (and on the single-device lowering, actually carries the aliasing
    attr)."""
    for r in report.results:
        live = r.detail.get("live_donated_leaves")
        if not live:
            continue
        assert r.detail.get("aliased_in_lowering") == live, (r.name, r.detail)


# ---------------------------------------------------------------------------
# per-rule fixtures: deliberately broken executables
# ---------------------------------------------------------------------------

def _fixture_contract(name, build, *, collectives=(), donated_args=(),
                      max_const_bytes=1 << 16, max_live_bytes=1 << 22,
                      waivers=None):
    return Contract(
        name=name, description="fixture", build=build,
        collectives=tuple(collectives), donated_args=tuple(donated_args),
        max_const_bytes=max_const_bytes, max_live_bytes=max_live_bytes,
        waivers=dict(waivers or {}),
        file=__file__, line=0)


def _loopback_shard_map(body, n_out=1):
    import jax
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from lightgbm_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(min(4, len(jax.devices())))
    return jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P("data"),),
        out_specs=tuple([P()] * n_out) if n_out > 1 else P(),
        check_vma=False))


def test_j1_two_collective_round_fails():
    """A deliberately TWO-psum body against a one-psum declaration: the
    regression (a second in-dispatch merge) source lint cannot see."""
    import jax
    import jax.numpy as jnp

    def body(x):  # x: (rows, bins) shard
        h = jax.lax.psum(x, "data")            # the declared merge
        h2 = jax.lax.psum(h * 2.0, "data")     # the smuggled second one
        return h + h2

    fn = _loopback_shard_map(body)
    c = _fixture_contract(
        "fixture_two_collectives",
        lambda: Target(fn, (jax.ShapeDtypeStruct((256, 32), jnp.float32),),
                       {}),
        collectives=("psum@data",))
    res = jaxpr_audit.audit_contract(c)
    assert any(f.rule == "J1" for f in res.findings), res.findings
    assert "sequence mismatch" in " ".join(
        f.message for f in res.findings if f.rule == "J1")


def test_j1_undeclared_axis_fails():
    """A collective on an axis the mesh module never declared."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from jax import shard_map

    mesh = Mesh(np.asarray(jax.devices()[:1]), ("rows",))
    fn = jax.jit(shard_map(
        lambda x: jax.lax.psum(x, "rows"), mesh=mesh,
        in_specs=(P("rows"),), out_specs=P(), check_vma=False))
    c = _fixture_contract(
        "fixture_bad_axis",
        lambda: Target(fn, (jax.ShapeDtypeStruct((64,), jnp.float32),), {}),
        collectives=("psum@rows",))
    res = jaxpr_audit.audit_contract(c)
    assert any(f.rule == "J1" and "undeclared axis" in f.message
               for f in res.findings), res.findings


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_j2_dropped_donation_fails():
    """A donated buffer whose aval matches no output: XLA would warn once
    and copy forever — the audit fails it statically."""
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, x):
        return jnp.sum(state) + jnp.sum(x)  # scalar out: (128,128) donor dies

    c = _fixture_contract(
        "fixture_dropped_donation",
        lambda: Target(step, (jax.ShapeDtypeStruct((128, 128), jnp.float32),
                              jax.ShapeDtypeStruct((128, 128), jnp.float32)),
                       {}),
        donated_args=(0,))
    res = jaxpr_audit.audit_contract(c)
    assert any(f.rule == "J2" for f in res.findings), res.findings


def test_j2_consumed_donation_passes():
    import functools

    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, donate_argnums=(0,))
    def step(state, x):
        return state + x

    c = _fixture_contract(
        "fixture_consumed_donation",
        lambda: Target(step, (jax.ShapeDtypeStruct((64, 64), jnp.float32),
                              jax.ShapeDtypeStruct((64, 64), jnp.float32)),
                       {}),
        donated_args=(0,))
    res = jaxpr_audit.audit_contract(c)
    assert res.ok, res.findings
    assert res.detail["aliased_in_lowering"] == 1


def test_j3_f64_leak_fails():
    """An f64 promotion inside the body (traced under x64 so the cast is
    real, as a chip run with x64 enabled would see it)."""
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):
        f = jax.jit(lambda x: x.astype(jnp.float64).sum())
        c = _fixture_contract(
            "fixture_f64_leak",
            lambda: Target(f, (jax.ShapeDtypeStruct((64,), jnp.float32),),
                           {}))
        res = jaxpr_audit.audit_contract(c)
    assert any(f_.rule == "J3" for f_ in res.findings), res.findings


def test_j4_host_callback_fails():
    import jax
    import jax.numpy as jnp

    def f(x):
        y = jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct((64,), jnp.float32), x)
        return y.sum()

    c = _fixture_contract(
        "fixture_callback",
        lambda: Target(jax.jit(f),
                       (jax.ShapeDtypeStruct((64,), jnp.float32),), {}))
    res = jaxpr_audit.audit_contract(c)
    assert any(f_.rule == "J4" for f_ in res.findings), res.findings


def test_j5_oversized_baked_constant_fails():
    """A closure-captured concrete array above the contract threshold:
    baked into the trace, re-materialized every dispatch."""
    import jax
    import jax.numpy as jnp

    table = jnp.asarray(np.random.RandomState(0).randn(4096, 8),
                        jnp.float32)  # 128 KiB > the 64 KiB default

    def f(x):
        return x @ table.T

    c = _fixture_contract(
        "fixture_baked_constant",
        lambda: Target(jax.jit(f),
                       (jax.ShapeDtypeStruct((16, 8), jnp.float32),), {}))
    res = jaxpr_audit.audit_contract(c)
    assert any(f_.rule == "J5" and "baked constant" in f_.message
               for f_ in res.findings), res.findings


def test_j6_live_set_budget_fails_on_blowup():
    """An O(L*F*B)-style intermediate blowing a tight budget."""
    import jax
    import jax.numpy as jnp

    def f(x):
        big = jnp.broadcast_to(x[:, None], (4096, 512)) * 2.0  # 8 MB f32
        return big.sum()

    c = _fixture_contract(
        "fixture_live_blowup",
        lambda: Target(jax.jit(f),
                       (jax.ShapeDtypeStruct((4096,), jnp.float32),), {}),
        max_live_bytes=1 << 20)
    res = jaxpr_audit.audit_contract(c)
    assert any(f_.rule == "J6" for f_ in res.findings), res.findings


def test_waiver_suppresses_with_reason_and_p0_without():
    import jax
    import jax.numpy as jnp

    def f(x):
        big = jnp.broadcast_to(x[:, None], (4096, 512)) * 2.0
        return big.sum()

    build = lambda: Target(  # noqa: E731
        jax.jit(f), (jax.ShapeDtypeStruct((4096,), jnp.float32),), {})
    waived = jaxpr_audit.audit_contract(_fixture_contract(
        "fixture_waived", build, max_live_bytes=1 << 20,
        waivers={"J6": "fixture: the blowup is the point"}))
    assert waived.ok and len(waived.waived) == 1

    bad = jaxpr_audit.audit_contract(_fixture_contract(
        "fixture_bad_waiver", build, max_live_bytes=1 << 20,
        waivers={"J6": "", "J99": "no such rule"}))
    assert sum(1 for f in bad.findings if f.rule == "P0") == 2
    assert any(f.rule == "J6" for f in bad.findings)  # empty reason ≠ waived


def test_cli_jaxpr_selection_and_exit_codes():
    from lightgbm_tpu.analysis.__main__ import main
    assert main(["--list-contracts"]) == 0
    assert main(["--jaxpr", "--contract", "ooc_root_chunk"]) == 0
    assert main(["--jaxpr", "--contract", "no_such_contract"]) == 2
