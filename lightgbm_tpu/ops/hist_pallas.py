"""Pallas TPU histogram kernels — the hot op of GBDT training.

TPU-native replacement for the reference's histogram inner loops
(reference: src/treelearner/cuda/cuda_histogram_constructor.cu,
src/io/dense_bin.hpp -> DenseBin::ConstructHistogram).  The CUDA kernel
accumulates into shared-memory atomics; TPUs have no atomics, so the
histogram is a one-hot matmul on the MXU with a VMEM accumulator that lives
across a sequential row-tile grid (SURVEY.md §10.1 strategy 2): per feature,
onehot(bin) in {0,1}^(T,B) is contracted against a (T, NC) payload.

Measured design notes (in-jit fori_loop probes on a v5e chip, N=1M F=28;
methodology + full numbers in docs/PERF_NOTES.md):

* A full-N pass costs ~8-10 ms and is INVARIANT to num_bins, payload
  lanes, row tile and bins layout — the floor is the per-(tile, feature)
  dot on this toolchain, NOT the one-hot build.  A hi/lo bin-decomposition
  variant (8x fewer MXU passes) measured 3x SLOWER; a pure-XLA one-hot
  einsum (ops/histogram.py::histogram_onehot_multi) beats this kernel at
  num_bins <= 64 (~3 ms) and loses above it — the grower selects per
  max_bin.
* Payload lanes are nearly free up to the 128-lane MXU tile: the (NC, B)
  output occupies the same MXU tiles for NC in 4..128.  Near-f32 precision
  therefore costs the same as bf16: the payload is split hi+lo bfloat16
  (bf16x2) into 8 lanes and recombined after accumulation.  hi is exact in
  bf16; lo is rounded to bf16, so products carry ~16-17 mantissa bits (vs 8
  for plain bf16, 24 for true f32) and accumulation is f32 — between the
  reference's float-hist and double-hist modes in practice.
* The same free-lane property batches MULTIPLE histograms in one pass:
  `histogram_pallas_multi` computes per-leaf histograms for up to 15 leaves
  (channels = leaf one-hot x payload) in a single data pass — the engine of
  the level-batched grower.
* Mosaic on this toolchain rejects bf16/int8 broadcast-selects (and int8
  compares); everything is built in 32-bit dtypes and cast at the dot.  The
  multi-leaf kernels measured ~20% faster at a 1024-row tile (verified to
  compile and run on-chip); the select-heavy experimental kernels that
  motivated the earlier 512 cap were removed after losing the benchmark.

Channels convention of the package: CHANNEL-FIRST (3, F, B) with channels
(sum_grad, sum_hess, count).  Channel-first is a measured TPU layout
decision (docs/PERF_NOTES.md round 4/5): a trailing dim of 3 forces XLA's
tiled layouts to pad the minor pair (B, 3) -> (B, 128) = 42.7x memory in
every hist copy/scatter; with (3, F, B) the minor tile pair (F, B) pads
~nothing at real shapes.  The reference makes the same device-driven
layout choice in src/treelearner/cuda/cuda_histogram_constructor.cu
(grad/hess interleaving picked for the GPU, not the host).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.profiling import phase_scope


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# Bytes of VMEM accumulator headroom shared by the histogram leaf-tile
# policy (recommended_leaf_tile below) AND the round megakernel's
# feature-block sizing (ops/round_pallas.py::megakernel_feature_block) —
# ONE budget so the two VMEM cost models can never drift apart.
VMEM_ACC_BUDGET = 8_000_000


def payload_channels(hist_precision: str, quantized: bool) -> int:
    """Payload lanes per leaf for the multi-leaf kernels: 6 for the
    bf16x2-split f32 path, 3 for rounded bf16 or int8-quantized."""
    return 3 if (quantized or hist_precision == "bf16") else 6


def recommended_leaf_tile(
    num_bins: int,
    n_features_effective: int,
    num_leaves: int,
    *,
    hist_precision: str = "f32",
    quantized: bool = False,
) -> int:
    """Leaves per multi-leaf pass for THIS module's kernels — the
    channel-aware tile selection, kept next to the VMEM cost model it
    budgets against (round 7; previously inlined in models/gbdt.py).

    Wide data runs one pallas_call per 128-feature chunk, so the VMEM
    accumulator — the binding constraint — is (min(F,128), lanes, B) f32
    regardless of total F; lanes beyond ~64 also measurably slow the dot
    (benchmarks/probe_b256b/c), so the wide-data budget is ~60 payload
    lanes: 10 leaves x 6ch float, or 20 leaves x 3ch quantized (the int
    path needs no bf16x2 split — half the lanes per leaf buys half the
    admission rounds).

    Narrow data (one feature chunk) is pass-count-bound, not lane-bound:
    the measured optimum is ~48-60 payload lanes — 8 leaves for the
    6-channel bf16x2 payload, 16 for 3-channel bf16, 20 for 3-lane int8
    (the tile16-bf16 / tile20-q16 configurations of
    benchmarks/probe_narrow255.py; docs/PERF_NOTES.md round 7 has the
    255-bin floor analysis they probe against).
    """
    ncl = payload_channels(hist_precision, quantized)
    fb = min(n_features_effective if n_features_effective > 0 else 1, 128)
    fb_pad = max(_round_up(fb, 8), 8)
    budget = VMEM_ACC_BUDGET  # shared with the megakernel (module const)
    bpad = _round_up(max(num_bins, 8), 8)  # kernel pads B to 8
    per_leaf = fb_pad * bpad * 4 * ncl  # f32/int32 accumulator lanes
    if n_features_effective <= 128:
        cap = 8 if ncl == 6 else (20 if quantized else 16)
    else:
        cap = 20 if quantized else 10  # both = ~60 lanes
    return max(1, min(cap, budget // max(per_leaf, 1), num_leaves))


_FEAT_BLOCK = 128  # feature-block width for wide datasets (Epsilon-class);
# Mosaic requires trailing block dims divisible by 128 (or the full array
# width, which covers every narrow dataset)


def _direct_kernel(bins_ref, pay_ref, out_ref, *, FB, B, NC, dtype):
    """Grid (feature_blocks, row_tiles); row tiles iterate fastest, so the
    accumulator lives across the row sweep of one feature block.

    Measured cost model (in-jit fori_loop probes, so that no host
    dispatch is in the timing, v5e): a full-N pass costs ~7.7-10 ms at
    N=1M, F=28 and
    is INVARIANT to num_bins (64 vs 256), payload lanes (8 vs 48), row
    tile (1024-8192), bins layout (row- vs feature-major), and even to
    replacing the one-hot compare with a constant — the floor is the
    per-(tile, feature) dot itself.  Consequence: payload lanes up to the
    128-wide MXU tile are FREE; fill them (21 leaves x 6ch) and cut the
    number of passes, do not shrink B or NC."""
    i = pl.program_id(1)

    # the revisited output block IS the accumulator (a separate VMEM
    # scratch would double the scoped footprint and OOM at 60 lanes x 256
    # bins x 128 features — measured 17.04M vs the 16M cap)
    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    pay = pay_ref[...].astype(dtype)  # (T, NC)
    T = pay.shape[0]
    iota_b = jax.lax.broadcasted_iota(jnp.int32, (T, B), 1)  # hoisted
    bins_i32 = bins_ref[...].astype(jnp.int32)  # (T, FB) upcast once
    for f in range(FB):
        binf = bins_i32[:, f][:, None]  # (T, 1)
        oh = (binf == iota_b).astype(dtype)  # (T, B)
        h = jax.lax.dot_general(
            pay, oh, (((0,), (0,)), ((), ())),
            preferred_element_type=out_ref.dtype,
        )  # (NC, B)
        out_ref[f] += h


@functools.partial(jax.jit, static_argnames=("num_bins", "row_tile", "matmul_dtype"))
def _hist_pallas_raw(
    bins: jnp.ndarray,  # (N, F) int16/int32
    payload: jnp.ndarray,  # (N, NC) f32 or int8
    *,
    num_bins: int,
    row_tile: int,
    matmul_dtype,
):
    n, f = bins.shape
    nc = payload.shape[1]
    B = _round_up(max(num_bins, 8), 8)
    acc_dtype = jnp.int32 if payload.dtype == jnp.int8 else jnp.float32

    if f > _FEAT_BLOCK:
        # wide data (Epsilon-class): one pallas_call PER 128-feature chunk,
        # unrolled in-trace.  Each call's output/accumulator is (128, NC, B)
        # — small enough that neither the Mosaic ~100MB output ceiling nor
        # scoped VMEM caps the payload lanes, so the leaf tile no longer
        # shrinks with total F (round 2 clamped row_tile to 512 and leaf
        # tile to ~5 at 2000x255; in-trace per-op launches cost no host
        # dispatch)
        outs = []
        for j0 in range(0, f, _FEAT_BLOCK):
            with phase_scope("hist.rowpad"):  # the chunk's copy of the bins
                chunk = bins[:, j0:j0 + _FEAT_BLOCK]
            outs.append(_hist_pallas_raw(
                chunk, payload, num_bins=num_bins, row_tile=row_tile,
                matmul_dtype=matmul_dtype))
        with phase_scope("hist.unpack"):
            return jnp.concatenate(outs, axis=0)

    FB = f  # narrow data: one feature block (wide F recursed above)
    n_pad = _round_up(n, row_tile)
    if n_pad != n:
        with phase_scope("hist.rowpad"):
            bins = jnp.pad(bins, ((0, n_pad - n), (0, 0)))
            payload = jnp.pad(payload, ((0, n_pad - n), (0, 0)))
    grid = (1, n_pad // row_tile)

    out_dims = (f, nc, B)
    # no scope and no name= here: XLA names the custom call after the
    # innermost component of its op_name, and the benchmark's kernel metrics
    # find it in a device trace as ``_hist_pallas_raw.N`` (_kernel_pass)
    out = pl.pallas_call(
        functools.partial(_direct_kernel, FB=FB, B=B, NC=nc, dtype=matmul_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((row_tile, FB), lambda j, i: (i, j), memory_space=pltpu.VMEM),
            pl.BlockSpec((row_tile, nc), lambda j, i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((FB, nc, B), lambda j, i: (j, 0, 0), memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct(out_dims, acc_dtype),
        cost_estimate=pl.CostEstimate(
            flops=2 * n_pad * FB * B * nc,
            bytes_accessed=n_pad * FB * bins.dtype.itemsize + n_pad * nc * 4,
            transcendentals=0,
        ),
    )(bins, payload)
    return out


def _kernel_pass(bins: jnp.ndarray, payload: jnp.ndarray, **kw):
    """:func:`_hist_pallas_raw` under the ``hist.kernel`` scope.  The scope
    sits outside the jitted function, so the custom call's op_name still ends
    ``jit(_hist_pallas_raw)/pallas_call`` and its name in a trace stays
    ``_hist_pallas_raw.N``; the pads inside carry ``hist.rowpad``, which as
    the inner scope is the one the phase reduction takes."""
    with phase_scope("hist.kernel"):
        return _hist_pallas_raw(bins, payload, **kw)


def _split_bf16x2(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x == hi + lo with both halves exactly representable in bfloat16."""
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, x - hi


def histogram_pallas(
    bins: jnp.ndarray,  # (N, F) int
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    mask: jnp.ndarray,
    num_bins: int,
    *,
    precision: str = "f32",
    row_tile: int = 512,
) -> jnp.ndarray:
    """Masked histogram -> (3, F, B) f32, MXU-accumulated on device.

    precision 'f32' packs bf16x2-split grad/hess into 8 payload lanes (same
    MXU cost as bf16; ~17-bit-mantissa products — see module docstring);
    'bf16' uses rounded payloads in 4 lanes (~8-bit mantissa).
    """
    with phase_scope("hist.payload"):
        m = mask.astype(jnp.float32)
        g = grad.astype(jnp.float32) * m
        h = hess.astype(jnp.float32) * m
        if precision == "f32":
            g_hi, g_lo = _split_bf16x2(g)
            h_hi, h_lo = _split_bf16x2(h)
            pay = jnp.stack([g_hi, h_hi, m, jnp.zeros_like(m), g_lo, h_lo,
                             jnp.zeros_like(m), jnp.zeros_like(m)], axis=-1)
        elif precision == "bf16":
            pay = jnp.stack([g, h, m, jnp.zeros_like(m)], axis=-1)
        else:
            raise ValueError(precision)
    out = _kernel_pass(
        bins, pay, num_bins=num_bins, row_tile=row_tile,
        matmul_dtype=jnp.bfloat16,
    )  # (F, NC, B)
    with phase_scope("hist.unpack"):
        if precision == "f32":
            out3 = jnp.stack(
                [out[:, 0] + out[:, 4], out[:, 1] + out[:, 5], out[:, 2]],
                axis=0,
            )  # (3, F, B)
        else:
            out3 = out[:, :3, :].transpose(1, 0, 2)
        if out3.shape[2] != num_bins:
            out3 = out3[:, :, :num_bins]
    return out3


def histogram_pallas_multi(
    bins: jnp.ndarray,  # (N, F) int
    grad: jnp.ndarray,
    hess: jnp.ndarray,
    mask: jnp.ndarray,  # (N,) in-bag mask
    leaf_id: jnp.ndarray,  # (N,) int32 current leaf per row
    leaf_base: int,
    num_leaves_tile: int,  # histograms for leaves [leaf_base, leaf_base + tile)
    num_bins: int,
    *,
    precision: str = "f32",
    row_tile: int = 1024,
) -> jnp.ndarray:
    """Per-leaf histograms for a tile of leaves in ONE data pass.

    Returns (L_tile, 3, F, B).  Channels are leaf-onehot x payload: lane
    l*NCL + c holds payload channel c masked to leaf leaf_base+l.  With
    NCL=8 (f32 precision) a 128-lane payload covers 16 leaves per pass.
    This is the TPU replacement for per-leaf row-index histogramming
    (reference: Dataset::ConstructHistograms over DataPartition indices).
    """
    with phase_scope("hist.payload"):
        m = mask.astype(jnp.float32)
        g = grad.astype(jnp.float32) * m
        h = hess.astype(jnp.float32) * m
        if precision == "f32":
            g_hi, g_lo = _split_bf16x2(g)
            h_hi, h_lo = _split_bf16x2(h)
            chans = [g_hi, h_hi, m, g_lo, h_lo, jnp.zeros_like(m)]
        elif precision == "bf16":
            chans = [g, h, m]
        else:
            raise ValueError(precision)
        ncl = len(chans)
        base = jnp.stack(chans, axis=-1)  # (N, ncl)
        lid = leaf_id.astype(jnp.int32) - leaf_base
        onehot = (
            lid[:, None]
            == jnp.arange(num_leaves_tile, dtype=jnp.int32)[None, :]
        ).astype(jnp.float32)  # (N, L_tile)
        pay = (onehot[:, :, None] * base[:, None, :]).reshape(
            bins.shape[0], num_leaves_tile * ncl
        )
        nc_pad = _round_up(num_leaves_tile * ncl, 4)
        if nc_pad != pay.shape[1]:
            pay = jnp.pad(pay, ((0, 0), (0, nc_pad - pay.shape[1])))
    out = _kernel_pass(
        bins, pay, num_bins=num_bins, row_tile=row_tile,
        matmul_dtype=jnp.bfloat16,
    )  # (F, nc_pad, B)
    with phase_scope("hist.unpack"):
        out = out[:, : num_leaves_tile * ncl, :].reshape(
            bins.shape[1], num_leaves_tile, ncl, -1
        )
        if precision == "f32":
            out3 = jnp.stack(
                [out[:, :, 0] + out[:, :, 3], out[:, :, 1] + out[:, :, 4],
                 out[:, :, 2]],
                axis=2,
            )  # (F, L_tile, 3, B)
        else:
            out3 = out[:, :, :3, :]
        out3 = jnp.transpose(out3, (1, 2, 0, 3))  # (L_tile, 3, F, B)
        if out3.shape[3] != num_bins:
            out3 = out3[:, :, :, :num_bins]
    return out3


def quantized_leaf_payload(grad_q, hess_q, mask, leaf_id, leaf_base,
                           num_leaves_tile) -> jnp.ndarray:
    """(N, L_tile*3) int8 payload: leaf-onehot x (grad_q, hess_q, count).
    Shared by the Pallas kernel and the XLA one-hot einsum so the two
    quantized strategies cannot desynchronize."""
    m8 = mask.astype(jnp.int8)
    base = jnp.stack(
        [grad_q.astype(jnp.int8) * m8, hess_q.astype(jnp.int8) * m8, m8],
        axis=-1,
    )  # (N, 3)
    lid = leaf_id.astype(jnp.int32) - leaf_base
    onehot = (
        lid[:, None] == jnp.arange(num_leaves_tile, dtype=jnp.int32)[None, :]
    ).astype(jnp.int8)  # (N, L_tile)
    return (onehot[:, :, None] * base[:, None, :]).reshape(
        grad_q.shape[0], num_leaves_tile * 3
    )


def histogram_pallas_multi_quantized(
    bins: jnp.ndarray,  # (N, F) int
    grad_q: jnp.ndarray,  # (N,) int8 — discretized gradients
    hess_q: jnp.ndarray,  # (N,) int8 — discretized hessians (non-negative)
    mask: jnp.ndarray,  # (N,) in-bag mask
    leaf_id: jnp.ndarray,  # (N,) int32 current leaf per row
    leaf_base: int,
    num_leaves_tile: int,
    num_bins: int,
    *,
    row_tile: int = 1024,
) -> jnp.ndarray:
    """Quantized per-leaf histograms for a tile of leaves in one pass ->
    (L_tile, 3, F, B) int32: exact integer accumulation on the int8 MXU
    (reference: gradient_discretizer.cpp + per-leaf ConstructHistograms).
    Lanes are leaf-onehot x (grad_q, hess_q, count) int8 payload."""
    ncl = 3
    with phase_scope("hist.payload"):
        pay = quantized_leaf_payload(grad_q, hess_q, mask, leaf_id,
                                     leaf_base, num_leaves_tile)
        nc_pad = _round_up(num_leaves_tile * ncl, 4)
        if nc_pad != pay.shape[1]:
            pay = jnp.pad(pay, ((0, 0), (0, nc_pad - pay.shape[1])))
    out = _kernel_pass(
        bins, pay, num_bins=num_bins, row_tile=row_tile, matmul_dtype=jnp.int8
    )  # (F, nc_pad, B) int32
    with phase_scope("hist.unpack"):
        out = out[:, : num_leaves_tile * ncl, :].reshape(
            bins.shape[1], num_leaves_tile, ncl, -1
        )
        out = jnp.transpose(out, (1, 2, 0, 3))  # (L_tile, 3, F, B)
        if out.shape[3] != num_bins:
            out = out[:, :, :, :num_bins]
    return out


def histogram_pallas_quantized(
    bins: jnp.ndarray,
    grad_q: jnp.ndarray,  # (N,) int8 — discretized gradients
    hess_q: jnp.ndarray,  # (N,) int8 — discretized hessians (non-negative)
    mask: jnp.ndarray,
    num_bins: int,
    *,
    row_tile: int = 512,
) -> jnp.ndarray:
    """Quantized histogram -> (3, F, B) int32 (grad_sum, hess_sum, count):
    exact int32 accumulation on the int8 MXU (reference:
    src/treelearner/gradient_discretizer.cpp quantized-training path)."""
    with phase_scope("hist.payload"):
        m8 = mask.astype(jnp.int8)
        pay = jnp.stack(
            [grad_q.astype(jnp.int8) * m8, hess_q.astype(jnp.int8) * m8, m8,
             jnp.zeros_like(m8)],
            axis=-1,
        )
    out = _kernel_pass(bins, pay, num_bins=num_bins, row_tile=row_tile,
                           matmul_dtype=jnp.int8)
    with phase_scope("hist.unpack"):
        out = out[:, :3, :].transpose(1, 0, 2)
        if out.shape[2] != num_bins:
            out = out[:, :, :num_bins]
    return out
