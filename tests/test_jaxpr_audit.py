"""Tier-1 jaxpr-audit gate + per-J-rule fixtures (docs/ANALYSIS.md
"Jaxpr audit layer").

Gate half: every registered contract (analysis/contracts.py) must verify
clean on the container CPU — the sharded fused round shows exactly the
declared collectives (ONE large merge per strategy) on the declared mesh
axis, every live donated buffer is consumable, zero f64 casts, zero host
callbacks, the live-set estimate under budget — and the runtime
DispatchCounter ledger agrees the collectives all rode the single
per-round dispatch.  This is the static gate for the regression class
the AST rules cannot see (the shared ``_run_fused_rounds`` driver
dispatches through a closure, R1/R6/R13 static-limits note).

Fixture half: each J rule is exercised on a deliberately broken tiny
executable (all under 8192 rows, so windowed fixtures stay on one
W-ladder rung), mirroring tests/test_jaxlint_rules.py's
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
        "windowed_round_float", "windowed_round_quantized",
        "windowed_round_sharded_psum", "windowed_round_sharded_scatter",
        "windowed_round_hierarchical_psum",
        "windowed_round_hierarchical_voting",
        "windowed_round_2d_float", "windowed_round_2d_quantized",
        "predict_warm_single", "predict_warm_multiclass",
        "predict_warm_converted", "predict_coalesced_bucket",
        "ooc_root_chunk", "ooc_split_chunk", "continual_refit_leaves",
        "fleet_round_batched",
    } <= set(CONTRACTS)


def test_all_contracts_verify_clean(report):
    assert report.ok, (
        "jaxpr-audit findings (fix the executable or waive in "
        "analysis/contracts.py with a reason):\n"
        + "\n".join(f.format() for f in report.findings))


def test_sharded_rounds_have_exactly_one_large_collective(report):
    """The headline invariant: per merge strategy, ONE collective moves
    histogram-sized bytes; everything else is scalar protocol traffic."""
    for r in report.results:
        if not r.name.startswith("windowed_round_sharded"):
            continue
        assert r.detail.get("large_collectives") == 1, (r.name, r.detail)


def test_2d_round_histogram_phase_never_crosses_the_feature_axis(report):
    """The wide-F headline: in the 2-D round, the histogram phase is a
    row-axis psum ALONE — the owned feature block's histograms are
    complete by layout, so the sequence shows ZERO hist-sized
    feature-axis traffic, and the per-axis byte bill proves the feature
    axis carries only the go/no-go row broadcast + election scalars."""
    from lightgbm_tpu.analysis.contracts import _2D_FEATURE_BUDGET
    for name in ("windowed_round_2d_float", "windowed_round_2d_quantized"):
        r = {x.name: x for x in report.results}[name]
        toks = r.detail["collectives"]
        # exactly one @data-only psum (the histogram merge) and it is the
        # largest collective in the round
        data_only = [t for t in toks if t == "psum@data"]
        assert len(data_only) == 3, (name, toks)  # 2 protocol + 1 hist
        bills = r.detail["axis_bytes"]
        assert bills["feature"] <= _2D_FEATURE_BUDGET, (name, bills)
        assert r.detail["feature_bytes"] == bills["feature"]
        # the row axis carries the histogram merge: orders of magnitude
        # more bytes than the feature axis at any realistic shape
        assert bills["data"] > bills["feature"], (name, bills)


def test_single_device_bodies_are_collective_free(report):
    for r in report.results:
        if r.name in ("windowed_round_float", "windowed_round_quantized",
                      "predict_warm_single", "predict_warm_multiclass",
                      "predict_warm_converted", "predict_coalesced_bucket",
                      "ooc_root_chunk", "ooc_split_chunk",
                      "continual_refit_leaves", "fleet_round_batched"):
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
    attr — the sharded CPU lowering drops aliasing wholesale, which is
    why the structural check is the platform-independent half)."""
    for r in report.results:
        live = r.detail.get("live_donated_leaves")
        if not live:
            continue
        if r.name.startswith(("windowed_round_sharded",
                              "windowed_round_hierarchical",
                              "windowed_round_2d")):
            continue  # aliasing attrs absent in multi-device CPU lowering
        assert r.detail.get("aliased_in_lowering") == live, (r.name, r.detail)


def test_ledger_crosscheck_agrees(report):
    """The sanitizer cross-check: the tiny sharded training's runtime
    ledger shows 1 dispatch / 0 blocking syncs per round, so every
    audited collective rode the one donated dispatch."""
    for merge in ("psum", "scatter"):
        summary = report.ledger[merge]
        assert summary["dispatches"] == summary["rounds"] > 0, summary
        assert summary["host_syncs"] == 0, summary
        assert summary["collectives_per_round"] == len(
            CONTRACTS[f"windowed_round_sharded_{merge}"].collectives)


def test_windowed_fixture_shapes_stay_on_one_rung():
    """All audited windowed fixtures sit under 8192 rows — the floor
    W-ladder rung — so the traced executable is the same one-rung round
    the budget pins exercise."""
    from lightgbm_tpu.analysis.contracts import _N, _W
    from lightgbm_tpu.ops.treegrow_windowed import _window_size
    assert _N < 8192
    assert _window_size(max(_N // 2, 1), _N) == _W == 8192


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
        family="", spine=(0, 0), waivers=dict(waivers or {}),
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
    """A deliberately TWO-psum round body against a one-psum declaration:
    the exact regression (a second in-dispatch merge) R13 cannot see
    through the closure dispatch."""
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
    assert main(["--jaxpr", "--contract", "ooc_root_chunk",
                 "--no-runtime"]) == 0
    assert main(["--jaxpr", "--contract", "no_such_contract"]) == 2


# ---------------------------------------------------------------------------
# J7: hbm-sweep-bound (ISSUE 11 — the megakernel's 3->1 claim, pinned)
# ---------------------------------------------------------------------------

def test_j7_megakernel_vs_three_pass_sweep_pins(report):
    """The headline: at the W=N sweep fixture, the megakernel round reads
    the bin matrix ONCE (+ the tile/f decisions-gather epsilon) where the
    legacy three-pass round reads it three times — pinned on the traced
    IR, not hoped."""
    detail = {r.name: r.detail for r in report.results}
    mk = detail["windowed_round_megakernel"]["bin_sweeps"]
    legacy = detail["windowed_round_three_pass_sweeps"]["bin_sweeps"]
    assert 1.0 <= mk <= 1.1, mk
    assert 3.0 <= legacy <= 3.2, legacy
    assert legacy / mk > 2.5  # the 3->1 fusion, as an IR-level ratio


def test_j7_sharded_megakernel_keeps_merge_protocol(report):
    """The sharded megakernel round's collective sequence is IDENTICAL to
    the legacy sharded round's — the single in-dispatch histogram merge
    unchanged (the ISSUE's sharded constraint)."""
    detail = {r.name: r.detail for r in report.results}
    assert (detail["windowed_round_sharded_megakernel_psum"]["collectives"]
            == detail["windowed_round_sharded_psum"]["collectives"])
    assert detail["windowed_round_sharded_megakernel_psum"][
        "large_collectives"] == 1


def test_j7_extra_sweep_fails():
    """A deliberately second full read of the bin matrix (the regression
    class: a new bin consumer added OUTSIDE the kernel) breaks the
    1-sweep budget."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    def round_body(bins, rows):
        w = bins[:, rows].T            # sweep 1: the window gather
        again = bins[:, rows].T        # sweep 2: the smuggled re-read
        return (w.astype(jnp.int32).sum()
                + again.astype(jnp.int32).sum())

    n, f = 1024, 16
    c = dataclasses.replace(
        _fixture_contract(
            "fixture_extra_sweep",
            lambda: Target(jax.jit(round_body),
                           (jax.ShapeDtypeStruct((f, n), jnp.int16),
                            jax.ShapeDtypeStruct((n,), jnp.int32)), {})),
        bin_arg=0, max_bin_sweeps=2.5)
    res = jaxpr_audit.audit_contract(c)
    assert any(f.rule == "J7" for f in res.findings), res.findings
    assert res.detail["bin_sweeps"] > 2.5


def _axis_mapped_ici_sequence(tokens):
    """Map a hierarchical round's collective tokens onto the legacy
    single-axis vocabulary: drop dcn-only collectives (the top-k
    election), rename both-axes scalar merges and ici merges to the
    legacy 'data' axis."""
    out = []
    for t in tokens:
        name, _, axes = t.partition("@")
        ax = set(axes.split(","))
        if ax == {"dcn"}:
            continue  # the election block: dcn-only, by design
        assert "ici" in ax, t
        out.append(f"{name}@data")
    return out


def test_hierarchical_ici_sequence_equals_legacy_sharded(report):
    """ISSUE 15 acceptance: per slice, the hierarchical round's ici
    collective sequence is IDENTICAL to the legacy sharded round's —
    the intra-slice merge (J1 sequence) is unchanged; only the dcn
    election block is new."""
    detail = {r.name: r.detail for r in report.results}
    for hier, legacy in (
            ("windowed_round_hierarchical_psum",
             "windowed_round_sharded_psum"),
            ("windowed_round_hierarchical_voting",
             "windowed_round_sharded_scatter")):
        assert (_axis_mapped_ici_sequence(detail[hier]["collectives"])
                == detail[legacy]["collectives"]), (hier, legacy)


def test_hierarchical_dcn_bytes_pinned(report):
    """The cross-slice byte bill: both hierarchical contracts carry a
    dcn_bytes detail under the declared dcn_max_bytes budget — ≤ top-k
    histograms' worth per round — and exactly TWO large collectives
    (one intra-slice merge + one top-k exchange), the dcn one bounded."""
    from lightgbm_tpu.analysis.contracts import (
        _BINS, _HIER_TOPK, _TILE)

    k_hist_bytes = 2 * _TILE * 3 * _HIER_TOPK * _BINS * 4
    for name in ("windowed_round_hierarchical_psum",
                 "windowed_round_hierarchical_voting"):
        c = CONTRACTS[name]
        r = {x.name: x for x in report.results}[name]
        assert c.dcn_max_bytes is not None
        assert 0 < r.detail["dcn_bytes"] <= c.dcn_max_bytes, r.detail
        # the election's histogram payload dominates; scalar slack only
        assert r.detail["dcn_bytes"] <= k_hist_bytes + 1024, r.detail
        assert r.detail["large_collectives"] == 2, r.detail


def test_dcn_bytes_fixture_full_histogram_over_dcn_fails():
    """A deliberately full-F histogram psum over the dcn axis against a
    top-k-sized budget: the regression class the hierarchical merge
    exists to prevent (and jaxlint R17 flags at the source level)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from lightgbm_tpu.analysis.jaxpr_audit import dcn_axis_bytes
    from jax import shard_map
    from lightgbm_tpu.parallel.mesh import make_mesh_hierarchical

    mesh = make_mesh_hierarchical(2, min(2, max(1, jax.device_count() // 2)))

    def body(h):  # (C, 3, F, B) full histogram block
        h = jax.lax.psum(h, "ici")          # intra-slice: fine
        return jax.lax.psum(h, "dcn")       # full-F over DCN: the bug

    fn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False))
    c = dataclasses.replace(
        _fixture_contract(
            "fixture_full_hist_over_dcn",
            lambda: Target(
                fn, (jax.ShapeDtypeStruct((8, 3, 64, 32), jnp.float32),),
                {}),
            collectives=("psum@ici", "psum@dcn")),
        dcn_max_bytes=4096)
    res = jaxpr_audit.audit_contract(c)
    assert any(f.rule == "J1" and "dcn" in f.message
               for f in res.findings), res.findings
    assert res.detail["dcn_bytes"] == 8 * 3 * 64 * 32 * 4
    # and the helper counts only dcn-crossing collectives
    assert dcn_axis_bytes([("psum", ("ici",), 100),
                           ("psum", ("ici", "dcn"), 8),
                           ("psum", ("dcn",), 50)]) == 58


def test_j7_detail_rides_the_artifact_verdict():
    """bench.py embeds verdict(); the J7-pinned contracts must appear in
    it so chip artifact rows carry the sweep proof next to J1-J6."""
    from lightgbm_tpu.analysis.contracts import CONTRACTS
    pinned = [n for n, c in CONTRACTS.items() if c.max_bin_sweeps]
    assert "windowed_round_megakernel" in pinned
    assert "windowed_round_three_pass_sweeps" in pinned
