"""Fleet growth — B independent boosters in ONE donated dispatch per round.

The north star serves millions of users, and millions of users don't
share one model: per-tenant personalization means FLEETS of small
ensembles.  Training those as a host loop over ``engine.train`` throws
away everything the fused round bought (1 dispatch / 0 syncs / 0
retraces per round) — B models cost B dispatches per round plus B
python drivers' worth of launch latency, and the chip idles between
them.  This module is the training-side mirror of the multi-tenant
serve table: :func:`jax.vmap` lifts the donated fused round
(ops/treegrow_windowed.py::_round_fused) over a leading model axis so B
boosters — SHARED bin matrix and frozen mappers, PER-MODEL gradients /
hessians / window state / split elections — advance as one donated
jitted dispatch per round.

Protocol.  The existing one-round-behind async driver
(:func:`~.treegrow_windowed._run_fused_rounds`) is reused UNCHANGED:
the (B, 5) per-lane info matrix folds to the driver's 5-scalar vector
inside the same dispatch —

* ``k_acc``  = min over ACTIVE lanes (k > 0), 0 when none remain.  A
  converged lane's round is a bitwise state passthrough with k = 0
  (no admissible split), so lanes that finish early ride as no-op
  lanes and the driver exits only when EVERY lane is done.  Active
  lanes admit >= 1 split per round, so the round count stays bounded
  by the slowest lane's solo schedule (< the driver's 2L+4 guard).
* ``total``  = max (retry re-ladders on the worst lane's need),
* ``ok``     = min (any lane's window breach retries the dispatch),
* ``whint``  = max (the W ladder quantizes on the max live window
  across the batch, so rung changes stay rare and retrace-free),
* ``finite`` = min (any lane going non-finite aborts the fleet —
  the guard names the fleet, the host splits blame by retraining solo).

Bitwise parity.  Each lane's trace is exactly the solo round body —
``jax.vmap`` over ``_round_fused.__wrapped__`` with the shared inputs
unmapped — so per-lane arithmetic is the same op sequence on the same
operands up to the host-side W schedule.  The fleet ladder FLOOR
quantizes on the max live window across the BATCH (per-lane floor
8192/B, 128-quantized; the solo 8192 floor is a per-round compile-cost
bound and a fleet round carries B lanes), so a fleet lane may run a
SMALLER W than its solo run — which is parity-neutral: W padding is row
masking (padded rows contribute exact zeros), each leaf's histogram
accumulates its own rows in row order regardless of how leaves pack
into windows, and admission stays the same best-first split sequence
however it rounds into dispatches.  tests/test_fleet_train.py pins
every lane of a B=64 fleet bitwise against its solo grower run (which
ladders at the 8192 floor), float and int8-quantized.  Mixed-fit
retries are benign the same way: lanes whose window fit already applied
their round (ok folds min, the driver retries without counting k), so a
fitting lane simply advances an uncounted round — admission never
skips.

int8 quantization matches solo bitwise because the stochastic-rounding
key is UNMAPPED under the vmap: every lane draws the same uniforms the
solo grower draws for that (seed, iteration), exactly the solo
semantics where the key depends on config, not data.

Scope (gated loudly here and in models/fleet.py::FleetBooster): the
single-device numerical envelope — no categorical splits, no EFB
bundles, no feature sampling (rng_key), no SPMD axes, no megakernel.
Everything a fleet lane needs beyond that envelope belongs to a solo
``engine.train`` run; jaxlint R18 flags the host-loop anti-pattern the
other direction.

The batched round's IR is pinned by the jaxpr-audit contract
``fleet_round_batched`` (analysis/contracts.py): vmap adds ZERO
collectives vs. the single-model round, donation is consumed on the
(B, ...) state, and peak-live scales linearly in B.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .split import SplitParams
from .treegrow import TreeArrays
from .treegrow_windowed import (PALLAS_PARTITION, _round_fused,
                                _run_fused_rounds, _w_finalize, _w_init,
                                _window_size)

_INT32_MAX = 2 ** 31 - 1


@functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "num_bins", "max_depth", "params",
                     "leaf_tile", "W", "use_pallas", "quantize_bins",
                     "hist_precision", "pallas_partition"),
    donate_argnums=(0,),  # the (B, ...) window state threads linearly
    # through the host round loop exactly like the solo grower's — donation
    # keeps fleet HBM at one stacked state, not two per round
)
def _fleet_round(
    state,  # WState with every leaf (B, ...)-stacked
    bins_t: jnp.ndarray,  # (F, N) int16 — SHARED, fixed original row order
    grad: jnp.ndarray,  # (B, N) f32 by row id (dequantized under quant)
    hess: jnp.ndarray,  # (B, N)
    gq: Optional[jnp.ndarray],  # (B, N) int8 or None
    hq: Optional[jnp.ndarray],
    quant_scale: Optional[jnp.ndarray],  # (B, 3) or None
    row_mask: jnp.ndarray,  # (B, N) bool — all-False rides as a no-op lane
    num_bins_pf: jnp.ndarray,  # SHARED per-feature tables
    missing_bin_pf: jnp.ndarray,
    feature_mask: jnp.ndarray,
    *,
    num_leaves: int,
    num_bins: int,
    max_depth: int,
    params: SplitParams,
    leaf_tile: int,
    W: int,
    use_pallas: bool,
    quantize_bins: int,
    hist_precision: str,
    pallas_partition: bool,
):
    """One boosting round for ALL B lanes: vmapped solo round body plus
    the in-dispatch (B, 5) -> (5,) info fold (module docstring)."""

    def lane(st, g, h, gql, hql, qsl, rm):
        # the UNDECORATED solo body: the inner jit would both ignore its
        # donation under this outer jit and add a trace layer per W; the
        # contracts trace the same .__wrapped__ (analysis/contracts.py)
        return _round_fused.__wrapped__(
            st, bins_t, g, h, gql, hql, qsl, rm,
            num_bins_pf, missing_bin_pf, feature_mask, None, None,
            None, None, None, None,
            num_leaves=num_leaves, num_bins=num_bins, max_depth=max_depth,
            params=params, leaf_tile=leaf_tile, W=W, use_pallas=use_pallas,
            quantize_bins=quantize_bins, hist_precision=hist_precision,
            has_cat=False, pallas_partition=pallas_partition)

    # axis_name-free vmap: zero collectives added vs. the solo round (J1)
    state, info_b = jax.vmap(lane)(state, grad, hess, gq, hq, quant_scale,
                                   row_mask)
    k_b = info_b[:, 0]
    act = k_b > 0
    # min over active lanes; 0 (converged fleet) only when none are active.
    # k=0 lanes are bitwise passthroughs, so min-over-active both bounds
    # the driver's n_leaves accounting from below (the >= num_leaves exit
    # can only fire once EVERY active lane exhausted its budget) and keeps
    # the exit exact: the driver stops exactly when the last lane does.
    k = jnp.where(act.any(),
                  jnp.min(jnp.where(act, k_b, jnp.int32(_INT32_MAX))),
                  jnp.int32(0))
    info = jnp.stack([
        k,
        jnp.max(info_b[:, 1]),  # total: retry ladders on the worst lane
        jnp.min(info_b[:, 2]),  # ok: any breach retries the dispatch
        jnp.max(info_b[:, 3]),  # whint: ladder on the max live window
        jnp.min(info_b[:, 4]),  # finite: any lane's NaN aborts the fleet
    ]).astype(jnp.int32)
    return state, info


@functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "num_bins", "params", "leaf_tile",
                     "use_pallas", "quantize_bins", "hist_precision",
                     "stochastic_rounding"),
)
def _fleet_init(
    bins_t, grad, hess, row_mask, sample_weight, num_bins_pf,
    missing_bin_pf, feature_mask, quant_key,
    *,
    num_leaves: int,
    num_bins: int,
    params: SplitParams,
    leaf_tile: int,
    use_pallas: bool,
    quantize_bins: int,
    hist_precision: str,
    stochastic_rounding: bool,
):
    """Root state for all B lanes in one dispatch: per-lane quantization
    scales, per-lane full-N root pass, per-lane seeded best.  The
    stochastic-rounding ``quant_key`` is UNMAPPED — every lane draws the
    solo grower's uniforms for this (seed, iteration), which is what the
    bitwise parity bar requires (module docstring)."""

    def lane(g, h, rm, sw):
        return _w_init.__wrapped__(
            bins_t, g, h, rm, sw, num_bins_pf, missing_bin_pf, feature_mask,
            None, quant_key, None, None, None, None, None,
            num_leaves=num_leaves, num_bins=num_bins, params=params,
            leaf_tile=leaf_tile, use_pallas=use_pallas,
            quantize_bins=quantize_bins, hist_precision=hist_precision,
            stochastic_rounding=stochastic_rounding)

    return jax.vmap(lane)(grad, hess, row_mask, sample_weight)


@functools.partial(jax.jit, static_argnames=("params", "quant_renew"))
def _fleet_finalize(state, grad_true, hess_true, row_mask, *,
                    params: SplitParams, quant_renew: bool):
    """Stacked tree extraction: (B, ...) TreeArrays + (B, N) leaf ids."""

    def lane(st, gt, ht, rm):
        return _w_finalize.__wrapped__(st, gt, ht, rm, params=params,
                                       quant_renew=quant_renew)

    return jax.vmap(lane)(state, grad_true, hess_true, row_mask)


def grow_fleet_windowed(
    bins_t: jnp.ndarray,  # (F, N) int16 feature-major — SHARED
    grad: jnp.ndarray,  # (B, N) f32
    hess: jnp.ndarray,  # (B, N) f32
    row_mask: jnp.ndarray,  # (B, N) bool
    sample_weight: jnp.ndarray,  # (B, N) f32
    feature_mask: jnp.ndarray,
    num_bins_pf: jnp.ndarray,
    missing_bin_pf: jnp.ndarray,
    quant_key: Optional[jnp.ndarray] = None,
    *,
    num_leaves: int,
    num_bins: int,
    max_depth: int = -1,
    params: SplitParams = SplitParams(),
    leaf_tile: int = 16,
    hist_precision: str = "f32",
    use_pallas: bool = False,
    quantize_bins: int = 0,
    stochastic_rounding: bool = True,
    quant_renew: bool = False,
    stats: Optional[dict] = None,
    guard_label: str = "",
) -> tuple[TreeArrays, jnp.ndarray]:
    """Grow one tree for EACH of B boosters; one donated dispatch/round.

    Returns ((B, ...)-stacked TreeArrays, (B, N) leaf_id).  ``stats``,
    when given, receives the shared driver's dispatch/sync ledger —
    {rounds, dispatches, host_syncs, async_resolves, retries, windows} —
    which is what the fleet budget pin in tests/test_retrace.py asserts
    at every B.  A lane whose ``row_mask`` is all-False is a no-op lane:
    its root leaf is -0.0 (ops/split.py::leaf_output's KEPSILON
    denominator, never NaN), it admits nothing, and its score update is
    a bitwise identity — device-side early stop, never a host-loop exit.
    """
    if grad.ndim != 2:
        raise ValueError(
            f"fleet: grad must be (B, N), got {grad.shape} — for a single "
            "model use ops.treegrow_windowed.grow_tree_windowed")
    b, n = grad.shape
    if bins_t.ndim != 2 or bins_t.shape[1] != n:
        raise ValueError(
            f"fleet: bins_t must be (F, {n}) shared across lanes, got "
            f"{bins_t.shape}")
    for name, arr in (("hess", hess), ("row_mask", row_mask),
                      ("sample_weight", sample_weight)):
        if arr.shape != (b, n):
            raise ValueError(
                f"fleet: {name} must be {(b, n)}, got {arr.shape}")

    common = dict(num_leaves=num_leaves, num_bins=num_bins, params=params,
                  leaf_tile=leaf_tile)
    state, g_d, h_d, gq, hq, qs, g_true, h_true = _fleet_init(
        bins_t, grad, hess, row_mask, sample_weight, num_bins_pf,
        missing_bin_pf, feature_mask, quant_key,
        use_pallas=use_pallas, quantize_bins=quantize_bins,
        hist_precision=hist_precision,
        stochastic_rounding=stochastic_rounding, **common)

    def round_fn(st, W):
        st, info = _fleet_round(
            st, bins_t, g_d, h_d, gq, hq, qs, row_mask,
            num_bins_pf, missing_bin_pf, feature_mask,
            max_depth=max_depth, W=W, use_pallas=use_pallas,
            quantize_bins=quantize_bins, hist_precision=hist_precision,
            pallas_partition=PALLAS_PARTITION, **common)
        return st, info

    # the solo async ladder drives the fleet UNCHANGED — same rungs, same
    # one-round-behind info reads — but the ladder FLOOR quantizes on the
    # max live window ACROSS THE BATCH: the solo 8192 floor is a
    # compile-cost bound per ROUND, and a fleet round carries B lanes, so
    # the per-lane floor shrinks as 8192/B (128-quantized).  W padding is
    # row masking only (padded rows contribute exact zeros), so every
    # lane stays bitwise equal to its solo run at the 8192 floor — pinned
    # in tests/test_fleet_train.py.  Without this, small-N fleets scatter
    # B x 8192 mostly-padding rows per round and the batched dispatch
    # degenerates to the host loop's total compute.
    lane_floor = max(128, (8192 // max(b, 1)) // 128 * 128)
    state = _run_fused_rounds(
        round_fn, state, n_ladder=n,
        w_first=_window_size(max(n // 2, 1), n, lane_floor),
        num_leaves=num_leaves, stats=stats, guard_label=guard_label,
        floor=lane_floor)

    return _fleet_finalize(state, g_true, h_true, row_mask, params=params,
                           quant_renew=bool(quant_renew and quantize_bins))
