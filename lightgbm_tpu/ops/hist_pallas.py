"""Pallas TPU histogram kernels — the hot op of GBDT training.

TPU-native replacement for the reference's histogram inner loops
(reference: src/treelearner/cuda/cuda_histogram_constructor.cu,
src/io/dense_bin.hpp -> DenseBin::ConstructHistogram).  The CUDA kernel
accumulates into shared-memory atomics; TPUs have no atomics, so the
histogram is a one-hot matmul on the MXU with a VMEM accumulator that lives
across a sequential row-tile grid (SURVEY.md §10.1 strategy 2): per feature,
onehot(bin) in {0,1}^(T,B) is contracted against an (NC, T) payload.

The kernel's input contract: it is handed what already lies in HBM and
forms the rest in VMEM.  (1) The bin matrix (N, F), unpadded and unsliced:
the ragged last row tile is masked by row index in the kernel, and at
F > 128 each 128-feature chunk's call picks its columns in its index map.
(2) A per-tree BASE, channel-first (8, N): every row's payload channels
(``payload_base``: g_hi, h_hi, m, g_lo, h_lo for 'f32'; g, h, m for 'bf16';
``payload_base_quantized``: gq, hq, m int8) with m the in-bag mask.  It
depends on the gradients and the bag alone, so a grower builds it once a
tree, before its loop.  (3) One int32 SLOT per row and pass (-1 = in no
leaf of this pass).  Per row tile the kernel spreads the base's channels
over the leaf lanes with a 0/1 matrix on the MXU and keeps each row's own
leaf by a select: ``lane[l*ncl + c, t] = base[c, t] if slot[t] == l else
0``.  Nothing N-sized is built, padded or copied per pass (before PR 26 a
lane-expanded (N, tile x ncl) payload and row-padded bins were, every pass:
39.6% of a Higgs tree's device time).  What XLA still does by itself, once
a tree: it keeps an (N, F) int16 matrix feature-major on the device and
copies it to the row-major layout this kernel's operand asks for.

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
  (bf16x2) into two channels and recombined after accumulation.  hi is
  exact in bf16 (cut on the bit pattern, ``_split_bf16x2``: a conversion to
  bfloat16 and back is a no-op to XLA on the TPU under its default flags);
  lo is rounded to bf16 in the kernel, so products carry ~16-17 mantissa
  bits (vs 8 for plain bf16, 24 for true f32) and accumulation is f32 —
  between the reference's float-hist and double-hist modes in practice.
* The same free-lane property batches MULTIPLE histograms in one pass:
  `histogram_pallas_multi` computes per-leaf histograms for a tile of
  leaves (lanes = leaf x channel) in a single data pass — the engine of
  the level-batched grower.  The single-leaf entry points are its tile = 1
  case.
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

_BASE_ROWS = 8  # rows of the channel-first per-tree base: one f32 sublane tile


def _direct_kernel(chunk_ref, bins_ref, base_ref, slot_ref, out_ref, *, n,
                   tile, ncl):
    """Grid (1, row_tiles): the accumulator lives across the row sweep.
    ``chunk_ref`` is the scalar the bins' index map picked its 128 columns
    by; the body has no use for it.

    Per row tile the kernel forms the dot's (NC, T) operand in VMEM from the
    base block (8, T) and the slot ids (1, T):
    ``lane[l * ncl + c, t] = base[c, t] if slot[t] == l else 0``.  The
    channels are spread over the leaves by a 0/1 matrix on the MXU (exact:
    every channel is bfloat16-exact or an int8, or is rounded to bfloat16
    here as it would be at the dot), then a select by slot keeps each row's
    own leaf.  A select and not a product, and rows at or past ``n`` take
    slot -1: what the ragged last block holds past the arrays' end never
    reaches the accumulator.

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

    FB, NC, B = out_ref.shape
    K, T = base_ref.shape
    # the dots' operand type: int8 for the quantized base, bfloat16 otherwise
    dtype = jnp.int8 if base_ref.dtype == jnp.int8 else jnp.bfloat16
    row = i * T + jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    slot = jnp.where(row < n, slot_ref[...], -1)  # (1, T)

    # everything is built in 32-bit types and cast at the dots: Mosaic on
    # this toolchain refuses bf16/int8 broadcast-selects and int8 compares
    r = jax.lax.broadcasted_iota(jnp.int32, (NC, K), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (NC, K), 1)
    spread = ((r - r // ncl * ncl == c) & (r < tile * ncl)).astype(
        jnp.float32).astype(jnp.bfloat16)  # (NC, K), 0/1
    base = base_ref[...]
    if dtype == jnp.int8:
        base = base.astype(jnp.int32)
    wide = jnp.dot(spread, base.astype(jnp.float32).astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)  # (NC, T)
    leaf_of = jax.lax.broadcasted_iota(jnp.int32, (NC, 1), 0) // ncl
    lane = jnp.where(slot == leaf_of, wide, 0.0)  # (1, T) == (NC, 1)
    if dtype == jnp.int8:
        lane = lane.astype(jnp.int32)
    lane = lane.astype(dtype)  # (NC, T)

    iota_b = jax.lax.broadcasted_iota(jnp.int32, (T, B), 1)  # hoisted
    bins_i32 = bins_ref[...].astype(jnp.int32)  # (T, FB) upcast once
    for f in range(FB):
        binf = bins_i32[:, f][:, None]  # (T, 1)
        oh = (binf == iota_b).astype(dtype)  # (T, B)
        out_ref[f] += jnp.dot(lane, oh, preferred_element_type=out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "row_tile", "tile", "ncl"))
def _hist_pallas_raw(
    bins: jnp.ndarray,  # (N, F) int16/int32, as it lies in HBM
    base: jnp.ndarray,  # (_BASE_ROWS, N) f32 or int8: the per-tree channels
    slot: jnp.ndarray,  # (1, N) int32: the row's leaf of this pass, or -1
    chunk: jnp.ndarray,  # (1,) int32: which block of _FEAT_BLOCK features
    *,
    num_bins: int,
    row_tile: int,
    tile: int,
    ncl: int,
):
    """-> (min(F, 128), NC, B): lane ``l * ncl + c`` of the chunk's feature
    ``f`` holds channel ``c`` of the rows in slot ``l``, by bin.  Nothing
    N-sized is padded, sliced or copied on the way in: the ragged last row
    tile is masked in the kernel, and the call takes the whole bin matrix
    and picks its 128 columns in its index map.  The chunk is a prefetched
    scalar and not a static, so that the sixteen calls of a pass at
    F = 2000 are one traced and lowered function: with sixteen index maps
    Epsilon's first ``update()`` took 95 s instead of 20."""
    n, f = bins.shape
    B = _round_up(max(num_bins, 8), 8)
    quantized = base.dtype == jnp.int8
    nc = _round_up(tile * ncl, 32 if quantized else 8)
    FB = min(f, _FEAT_BLOCK)
    if n <= row_tile:
        row_tile = n  # one block, the arrays' full extent

    # no scope and no name= here: XLA names the custom call after the
    # innermost component of its op_name, and the benchmark's kernel metrics
    # find it in a device trace as ``_hist_pallas_raw.N`` (_leaf_histograms)
    return pl.pallas_call(
        functools.partial(_direct_kernel, n=n, tile=tile, ncl=ncl),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1, pl.cdiv(n, row_tile)),
            in_specs=[
                pl.BlockSpec((row_tile, FB), lambda _, i, c: (i, c[0]),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((_BASE_ROWS, row_tile), lambda _, i, c: (0, i),
                             memory_space=pltpu.VMEM),
                pl.BlockSpec((1, row_tile), lambda _, i, c: (0, i),
                             memory_space=pltpu.VMEM),
            ],
            out_specs=pl.BlockSpec((FB, nc, B), lambda j, i, c: (j, 0, 0),
                                   memory_space=pltpu.VMEM),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (FB, nc, B), jnp.int32 if quantized else jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * FB * B * nc,
            bytes_accessed=n * (FB * bins.dtype.itemsize
                                + _BASE_ROWS * base.dtype.itemsize + 4),
            transcendentals=0,
        ),
    )(chunk, bins, base, slot)


def _split_bf16x2(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """x == hi + lo, hi exactly representable in bfloat16: hi is x rounded
    to nearest-even at bit 16, cut on the bit pattern.  No float32 ->
    bfloat16 -> float32 round trip, which XLA on the TPU folds to a no-op
    under its default ``xla_allow_excess_precision`` (lo was 0 there)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    bits = bits + jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1))
    hi = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                      jnp.float32)
    return hi, x - hi


def _stack_base(chans: list) -> jnp.ndarray:
    """(N,) channels -> the channel-first (_BASE_ROWS, N) base, zeros after."""
    zero = jnp.zeros_like(chans[0])
    return jnp.stack(chans + [zero] * (_BASE_ROWS - len(chans)), axis=0)


def payload_base(grad: jnp.ndarray, hess: jnp.ndarray, mask: jnp.ndarray,
                 precision: str = "f32") -> jnp.ndarray:
    """The kernel's per-tree input: every row's channels, channel-first
    ``(8, N)`` f32 (lane-dense in HBM; a trailing dimension of 6 would be
    padded to 128 lanes).  ``g_hi, h_hi, m, g_lo, h_lo`` for 'f32' (the
    bf16x2 split of the module docstring), ``g, h, m`` for 'bf16', zeros
    after.  It depends on nothing a pass changes: a grower builds it once a
    tree and hands it to every pass."""
    m = mask.astype(jnp.float32)
    g = grad.astype(jnp.float32) * m
    h = hess.astype(jnp.float32) * m
    if precision == "f32":
        g_hi, g_lo = _split_bf16x2(g)
        h_hi, h_lo = _split_bf16x2(h)
        chans = [g_hi, h_hi, m, g_lo, h_lo]
    elif precision == "bf16":
        chans = [g, h, m]
    else:
        raise ValueError(precision)
    return _stack_base(chans)


def payload_base_quantized(grad_q: jnp.ndarray, hess_q: jnp.ndarray,
                           mask: jnp.ndarray) -> jnp.ndarray:
    """The int8 sibling of :func:`payload_base`: ``(8, N)`` int8 rows
    ``grad_q, hess_q, m`` and zeros."""
    m8 = mask.astype(jnp.int8)
    return _stack_base(
        [grad_q.astype(jnp.int8) * m8, hess_q.astype(jnp.int8) * m8, m8])


def _leaf_histograms(bins, base, mask, leaf_id, leaf_base, tile, num_bins,
                     ncl, row_tile):
    """One pass of the kernel -> (tile, ncl, F, B) in the accumulator's
    dtype: channel ``c`` of the rows that ``mask`` keeps and that sit in
    leaf ``leaf_base + l``."""
    with phase_scope("grow.slots"):
        slot = jnp.where(mask.astype(bool),
                         leaf_id.astype(jnp.int32) - leaf_base, -1)[None, :]
    f = bins.shape[1]
    # wide data (Epsilon-class): one pallas_call PER 128-feature chunk,
    # unrolled in-trace.  Each call's output/accumulator is (128, NC, B)
    # — small enough that neither the Mosaic ~100MB output ceiling nor
    # scoped VMEM caps the payload lanes, so the leaf tile no longer
    # shrinks with total F (round 2 clamped row_tile to 512 and leaf
    # tile to ~5 at 2000x255; in-trace per-op launches cost no host
    # dispatch).  The last chunk reads past the matrix's edge; its
    # surplus features are dropped below.
    #
    # the scope sits outside the jitted function, so the custom call's
    # op_name still ends ``jit(_hist_pallas_raw)/pallas_call`` and its name
    # in a trace stays ``_hist_pallas_raw.N``
    with phase_scope("hist.kernel"):
        outs = [
            _hist_pallas_raw(bins, base, slot, jnp.full((1,), j, jnp.int32),
                             num_bins=num_bins, row_tile=row_tile, tile=tile,
                             ncl=ncl)
            for j in range(pl.cdiv(f, _FEAT_BLOCK))]
    with phase_scope("hist.unpack"):
        out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=0)
        out = out[:f, : tile * ncl, :num_bins].reshape(
            f, tile, ncl, num_bins)
        return jnp.transpose(out, (1, 2, 0, 3))


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
    base: jnp.ndarray = None,  # payload_base(grad, hess, m, precision), m >= mask
) -> jnp.ndarray:
    """Per-leaf histograms for a tile of leaves in ONE data pass.

    Returns (L_tile, 3, F, B).  Channels are leaf-onehot x payload: lane
    l*NCL + c holds payload channel c masked to leaf leaf_base+l, formed in
    the kernel from ``base`` and the rows' slots.  A caller with many
    passes over the same gradients builds ``base`` once and hands it in;
    without it, it is built here.
    This is the TPU replacement for per-leaf row-index histogramming
    (reference: Dataset::ConstructHistograms over DataPartition indices).
    """
    if base is None:
        with phase_scope("hist.payload"):
            base = payload_base(grad, hess, mask, precision)
    out = _leaf_histograms(
        bins, base, mask, leaf_id, leaf_base, num_leaves_tile, num_bins,
        payload_channels(precision, False), row_tile)  # (L_tile, ncl, F, B)
    if precision == "f32":
        with phase_scope("hist.unpack"):
            out = jnp.stack([out[:, 0] + out[:, 3], out[:, 1] + out[:, 4],
                             out[:, 2]], axis=1)
    return out


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
    """Masked histogram -> (3, F, B) f32, MXU-accumulated on device: the
    one-leaf case of :func:`histogram_pallas_multi`."""
    return histogram_pallas_multi(
        bins, grad, hess, mask, jnp.zeros(bins.shape[:1], jnp.int32), 0, 1,
        num_bins, precision=precision, row_tile=row_tile)[0]


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
    base: jnp.ndarray = None,  # payload_base_quantized(grad_q, hess_q, m)
) -> jnp.ndarray:
    """Quantized per-leaf histograms for a tile of leaves in one pass ->
    (L_tile, 3, F, B) int32: exact integer accumulation on the int8 MXU
    (reference: gradient_discretizer.cpp + per-leaf ConstructHistograms).
    Same route as :func:`histogram_pallas_multi` with an int8 base."""
    if base is None:
        with phase_scope("hist.payload"):
            base = payload_base_quantized(grad_q, hess_q, mask)
    return _leaf_histograms(bins, base, mask, leaf_id, leaf_base,
                            num_leaves_tile, num_bins, 3, row_tile)


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
    return histogram_pallas_multi_quantized(
        bins, grad_q, hess_q, mask, jnp.zeros(bins.shape[:1], jnp.int32), 0,
        1, num_bins, row_tile=row_tile)[0]
