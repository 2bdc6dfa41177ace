"""Histogram construction on device.

TPU-native replacement for the reference's histogram inner loops
(reference: src/io/dense_bin.hpp -> DenseBin::ConstructHistogram,
src/io/multi_val_dense_bin.hpp, src/treelearner/cuda/cuda_histogram_constructor.cu).

The reference accumulates (sum_grad, sum_hess) per bin with 4-way unrolled
scalar loops (CPU) or shared-memory atomics (CUDA).  TPUs have neither scalar
loops nor atomics; instead we express the histogram as an XLA scatter-add over
a flat (F*B) index space, which XLA lowers to a deterministic on-device
combiner.  A one-hot-matmul (MXU) variant is provided for wide-row tiles and
picked by a cost model, mirroring TrainingShareStates' col-wise/row-wise
choice (reference: src/io/train_share_states.cpp).

Channels: 0 = sum_grad, 1 = sum_hess, 2 = count (reference keeps 2 doubles and
recovers count; we keep an explicit count channel since f32 hessians do not
always encode counts).  Layout is CHANNEL-FIRST (3, F, B) / (L, 3, F, B)
everywhere — a trailing channel dim of 3 forces TPU tiled layouts to pad
the minor pair (B, 3) -> (B, 128) = 42.7x in every hist copy (measured,
docs/PERF_NOTES.md), while (F, B) minor tiles pad ~nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..utils.profiling import phase_scope

NUM_CHANNELS = 3


def histogram_scatter(
    bins: jnp.ndarray,  # (N, F) int
    grad: jnp.ndarray,  # (N,) f32
    hess: jnp.ndarray,  # (N,) f32
    mask: jnp.ndarray,  # (N,) bool or f32 — rows contributing to this hist
    num_bins: int,
) -> jnp.ndarray:
    """Masked histogram over all features: returns (3, F, B) f32.

    Rows with mask=0 contribute zeros (they still scatter, but with zero
    payload) — this is the TPU analogue of histogramming only the rows of one
    leaf (reference: Dataset::ConstructHistograms with use_indices=true).
    """
    n, f = bins.shape
    m = mask.astype(grad.dtype)
    flat_idx = bins.astype(jnp.int32) + (jnp.arange(f, dtype=jnp.int32) * num_bins)[None, :]
    payload = jnp.stack([grad * m, hess * m, m], axis=0)  # (3, N)
    payload = jnp.broadcast_to(payload[:, :, None], (NUM_CHANNELS, n, f))
    hist = jnp.zeros((NUM_CHANNELS, f * num_bins), dtype=grad.dtype)
    hist = hist.at[:, flat_idx].add(payload, mode="drop")
    return hist.reshape(NUM_CHANNELS, f, num_bins)


def histogram_onehot_matmul(
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    mask: jnp.ndarray,
    num_bins: int,
    row_tile: int = 8192,
) -> jnp.ndarray:
    """MXU variant: one-hot(bin) contracted against (grad, hess, 1) payloads.

    For a row tile of size T this is F batched (B x T)@(T x 3) matmuls — the
    systolic-array-friendly formulation of histogramming (SURVEY.md §10.1
    strategy 1).  Processes rows in tiles via lax.scan to bound memory.
    """
    n, f = bins.shape
    m = mask.astype(grad.dtype)
    payload = jnp.stack([grad * m, hess * m, m], axis=-1)  # (N, 3)

    pad = (-n) % row_tile
    if pad:
        bins = jnp.pad(bins, ((0, pad), (0, 0)))
        payload = jnp.pad(payload, ((0, pad), (0, 0)))
    nt = (n + pad) // row_tile
    bins_t = bins.reshape(nt, row_tile, f)
    pay_t = payload.reshape(nt, row_tile, NUM_CHANNELS)

    def body(acc, inp):
        b_tile, p_tile = inp  # (T, F), (T, 3)
        onehot = jax.nn.one_hot(b_tile.T, num_bins, dtype=grad.dtype)  # (F, T, B)
        # (3, T) @ (F, T, B) -> (3, F, B)
        h = jnp.einsum("ftb,tc->cfb", onehot, p_tile, precision=jax.lax.Precision.HIGHEST)
        return acc + h, None

    init = jnp.zeros((NUM_CHANNELS, f, num_bins), dtype=grad.dtype)
    hist, _ = jax.lax.scan(body, init, (bins_t, pay_t))
    return hist


def _leaf_lanes(base: jnp.ndarray, leaf_id: jnp.ndarray, leaf_base: int,
                num_leaves_tile: int) -> jnp.ndarray:
    """(N, ncl) channels -> the einsum routes' (N, L_tile * ncl) payload:
    lane l*ncl + c holds channel c of the rows in leaf leaf_base + l."""
    lid = leaf_id.astype(jnp.int32) - leaf_base
    onehot = (
        lid[:, None] == jnp.arange(num_leaves_tile, dtype=jnp.int32)[None, :]
    ).astype(base.dtype)  # (N, L_tile)
    return (onehot[:, :, None] * base[:, None, :]).reshape(
        base.shape[0], num_leaves_tile * base.shape[1])


def histogram_onehot_multi(
    bins: jnp.ndarray,  # (N, F) int
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    mask: jnp.ndarray,  # (N,) in-bag mask
    leaf_id: jnp.ndarray,  # (N,) i32 current leaf per row
    leaf_base: int,
    num_leaves_tile: int,
    num_bins: int,
    *,
    precision: str = "f32",
    row_tile: int = 8192,
) -> jnp.ndarray:
    """Per-leaf histograms for a tile of leaves in ONE data pass, pure-XLA
    einsum formulation -> (L_tile, 3, F, B) f32.

    Same contract as hist_pallas.histogram_pallas_multi; payload lanes are
    leaf-onehot x bf16x2-split (grad, hess, count) so products carry ~17
    mantissa bits with f32 accumulation.  Measured (v5e, in-jit): at
    num_bins <= 64 XLA's fused one-hot einsum beats the Pallas kernel
    (~4 ms vs ~8-10 ms per 1M x 28 pass); at 256 bins the Pallas kernel
    wins (~10 ms vs ~25 ms) — histogram strategy is selected per max_bin
    by the grower (the TrainingShareStates cost-model analogue)."""
    from .hist_pallas import _split_bf16x2

    n, f = bins.shape
    with phase_scope("hist.payload"):
        m = mask.astype(jnp.float32)
        g = grad.astype(jnp.float32) * m
        h = hess.astype(jnp.float32) * m
        if precision == "f32":
            g_hi, g_lo = _split_bf16x2(g)
            h_hi, h_lo = _split_bf16x2(h)
            base = jnp.stack(
                [g_hi, h_hi, m, g_lo, h_lo, jnp.zeros_like(m)], axis=-1)
        elif precision == "bf16":
            base = jnp.stack([g, h, m], axis=-1)
        else:
            raise ValueError(precision)
        ncl = base.shape[-1]
        payload = _leaf_lanes(base, leaf_id, leaf_base, num_leaves_tile)
    c = payload.shape[1]

    pad = (-n) % row_tile
    if pad:
        with phase_scope("hist.rowpad"):
            bins = jnp.pad(bins, ((0, pad), (0, 0)))
            payload = jnp.pad(payload, ((0, pad), (0, 0)))
    nt = (n + pad) // row_tile
    bins_t = bins.reshape(nt, row_tile, f)
    with phase_scope("hist.payload"):
        pay_t = payload.astype(jnp.bfloat16).reshape(nt, row_tile, c)

    def body(acc, inp):
        b_tile, p_tile = inp
        onehot = jax.nn.one_hot(b_tile.T, num_bins, dtype=jnp.bfloat16)  # (F, T, B)
        # natural dot output (f, b, c) — the CPU backend's dot thunk
        # rejects the lhs/rhs swap a "->cfb" spec induces for bf16 inputs
        hh = jnp.einsum("ftb,tc->fbc", onehot, p_tile,
                        preferred_element_type=jnp.float32)
        return acc + hh, None

    with phase_scope("hist.kernel"):
        init = jnp.zeros((f, num_bins, c), jnp.float32)
        hist, _ = jax.lax.scan(body, init, (bins_t, pay_t))
    with phase_scope("hist.unpack"):
        # one transpose per pass to the package's channel-first layout
        hist = jnp.transpose(hist, (2, 0, 1)).reshape(
            num_leaves_tile, ncl, f, num_bins)
        if precision == "f32":
            out3 = jnp.stack(
                [hist[:, 0] + hist[:, 3], hist[:, 1] + hist[:, 4],
                 hist[:, 2]],
                axis=1,
            )  # (L_tile, 3, F, B)
        else:
            out3 = hist
    return out3


def histogram_onehot_multi_quantized(
    bins: jnp.ndarray,  # (N, F) int
    grad_q: jnp.ndarray,  # (N,) int8 — discretized gradients
    hess_q: jnp.ndarray,  # (N,) int8 — discretized hessians (non-negative)
    mask: jnp.ndarray,  # (N,) in-bag mask
    leaf_id: jnp.ndarray,  # (N,) i32 current leaf per row
    leaf_base: int,
    num_leaves_tile: int,
    num_bins: int,
    *,
    row_tile: int = 8192,
) -> jnp.ndarray:
    """Quantized per-leaf histograms, pure-XLA int8 one-hot dot ->
    (L_tile, 3, F, B) int32 with EXACT integer accumulation (reference:
    gradient_discretizer.cpp int16/int32 histogram buffers).

    The narrow-bin sibling of hist_pallas.histogram_pallas_multi_quantized:
    at num_bins <= 64 the XLA fused one-hot einsum beats the Pallas kernel
    for the float path (measured, see histogram_onehot_multi) and the same
    selection applies to the int path — int8 x int8 dots accumulate in
    int32 on the MXU, so exactness is preserved."""
    n, f = bins.shape
    ncl = 3
    with phase_scope("hist.payload"):
        m8 = mask.astype(jnp.int8)
        base = jnp.stack(
            [grad_q.astype(jnp.int8) * m8, hess_q.astype(jnp.int8) * m8, m8],
            axis=-1,
        )  # (N, 3)
        payload = _leaf_lanes(base, leaf_id, leaf_base, num_leaves_tile)
    c = payload.shape[1]

    pad = (-n) % row_tile
    if pad:
        with phase_scope("hist.rowpad"):
            bins = jnp.pad(bins, ((0, pad), (0, 0)))
            payload = jnp.pad(payload, ((0, pad), (0, 0)))
    nt = (n + pad) // row_tile
    bins_t = bins.reshape(nt, row_tile, f)
    pay_t = payload.reshape(nt, row_tile, c)

    def body(acc, inp):
        b_tile, p_tile = inp
        onehot = jax.nn.one_hot(b_tile.T, num_bins, dtype=jnp.int8)  # (F,T,B)
        # natural dot output (f, b, c) — see histogram_onehot_multi
        hh = jnp.einsum("ftb,tc->fbc", onehot, p_tile,
                        preferred_element_type=jnp.int32)
        return acc + hh, None

    with phase_scope("hist.kernel"):
        init = jnp.zeros((f, num_bins, c), jnp.int32)
        hist, _ = jax.lax.scan(body, init, (bins_t, pay_t))
    with phase_scope("hist.unpack"):
        return jnp.transpose(hist, (2, 0, 1)).reshape(
            num_leaves_tile, ncl, f, num_bins)  # (L_tile, 3, F, B)


def histogram_multi(
    bins: jnp.ndarray,  # (N, F) int
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    mask: jnp.ndarray,
    leaf_id: jnp.ndarray,
    leaf_base: int,
    num_leaves_tile: int,
    num_bins: int,
    *,
    precision: str = "f32",
    base: jnp.ndarray = None,  # hist_pallas.payload_base, built once a tree
    counts: jnp.ndarray = None,  # hist_pallas.pass_counts(mask)
    bins_t: jnp.ndarray = None,  # hist_pallas.bins_shadow(bins)
) -> jnp.ndarray:
    """Multi-leaf histogram DISPATCHER for the Pallas-eligible growers ->
    (L_tile, 3, F, B).

    Tries the Pallas kernel; a kernel failure (or an armed
    ``pallas_hist`` fault-injection site) is caught ONCE, logged, and
    permanently degrades this process to the XLA one-hot path — identical
    contract, no manual env var needed (utils/degrade.py).  The decision
    runs at trace time: callers fold ``utils.degrade.available`` into
    their ``use_pallas`` static so post-failure traces compile without
    the broken kernel."""
    from ..utils import degrade as _degrade

    def _pallas():
        from .hist_pallas import histogram_pallas_multi

        return histogram_pallas_multi(
            bins, grad, hess, mask, leaf_id, leaf_base, num_leaves_tile,
            num_bins, precision=precision, base=base, counts=counts,
            bins_t=bins_t)

    return _degrade.run_with_fallback(
        _degrade.HIST, _pallas,
        lambda: histogram_onehot_multi(
            bins, grad, hess, mask, leaf_id, leaf_base, num_leaves_tile,
            num_bins, precision=precision),
        fault_site="pallas_hist")


def histogram_multi_quantized(
    bins: jnp.ndarray,  # (N, F) int
    grad_q: jnp.ndarray,
    hess_q: jnp.ndarray,
    mask: jnp.ndarray,
    leaf_id: jnp.ndarray,
    leaf_base: int,
    num_leaves_tile: int,
    num_bins: int,
    *,
    base: jnp.ndarray = None,  # hist_pallas.payload_base_quantized
    counts: jnp.ndarray = None,  # hist_pallas.pass_counts(mask)
    bins_t: jnp.ndarray = None,  # hist_pallas.bins_shadow(bins)
) -> jnp.ndarray:
    """Quantized sibling of :func:`histogram_multi` — same
    catch-once/degrade-forever dispatch over the int8 kernels."""
    from ..utils import degrade as _degrade

    def _pallas():
        from .hist_pallas import histogram_pallas_multi_quantized

        return histogram_pallas_multi_quantized(
            bins, grad_q, hess_q, mask, leaf_id, leaf_base,
            num_leaves_tile, num_bins, base=base, counts=counts,
            bins_t=bins_t)

    return _degrade.run_with_fallback(
        _degrade.HIST, _pallas,
        lambda: histogram_onehot_multi_quantized(
            bins, grad_q, hess_q, mask, leaf_id, leaf_base, num_leaves_tile,
            num_bins),
        fault_site="pallas_hist")


def histogram(
    bins: jnp.ndarray,
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    mask: jnp.ndarray,
    num_bins: int,
    strategy: str = "auto",
) -> jnp.ndarray:
    """Dispatch between strategies (reference analogue: TrainingShareStates'
    col-wise vs row-wise cost model)."""
    if strategy == "auto":
        # scatter wins for many features / large bins; matmul for narrow bins.
        strategy = "onehot" if num_bins <= 64 and bins.shape[1] <= 512 else "scatter"
    if strategy == "onehot":
        return histogram_onehot_matmul(bins, grad, hess, mask, num_bins)
    return histogram_scatter(bins, grad, hess, mask, num_bins)


def unbundle_hists(h: jnp.ndarray, efb_gather: jnp.ndarray,
                   efb_default: jnp.ndarray, num_feature: int,
                   num_bins: int) -> jnp.ndarray:
    """(tile, 3, F_b, B) bundle hists -> (tile, 3, F, B) per-feature hists:
    gather each feature's non-default slots; its default-bin row is
    leaf_total - sum(non-default) (reference most-freq-bin subtraction; see
    io/efb.py)."""
    tile = h.shape[0]
    flat = h.reshape(tile, 3, -1)
    flat = jnp.concatenate([flat, jnp.zeros((tile, 3, 1), h.dtype)], axis=2)
    hf = flat[:, :, efb_gather.reshape(-1)].reshape(
        tile, 3, num_feature, num_bins)
    leaf_tot = jnp.sum(h[:, :, 0, :], axis=2)  # (tile, 3)
    nondef = jnp.sum(hf, axis=3)  # (tile, 3, F)
    fill = leaf_tot[:, :, None] - nondef
    return hf + jnp.where(
        efb_default[None, None], fill[..., None], jnp.zeros((), h.dtype))


def fix_histogram_subtract(parent: jnp.ndarray, child: jnp.ndarray) -> jnp.ndarray:
    """Sibling histogram by subtraction (reference: Dataset::FixHistogram /
    the histogram subtraction trick) — exact because bins are identical."""
    return parent - child
