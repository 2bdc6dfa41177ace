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
(4) One int32 COUNT per row tile and pass (``pass_counts``): how many of
the tile's rows have a slot.  It arrives as a prefetched scalar and decides
what the tile costs.  (5) Since PR 37, up to 256 bins, the bins a second
time, FEATURE-MAJOR on the kernel's own row tiles (``bins_shadow``: (F,
row tiles, T / 128, 128); ``basic.Dataset.bins_device_t`` keeps it on the
device for the partition, and the rounds grower hands that in): what the
packed tiles read.  A dense tile reads the row-major block as before.

The cost of a pass follows the rows in the pass, not N (since PR 29; before,
every pass multiplied every row, 35 times a 255-leaf tree on 10.5M rows
where 3.9 passes' worth were needed).  Per row tile: no row in the pass,
the tile's DMA and nothing else; otherwise the tile's rows of the pass are
packed to the front in VMEM (a 0/1 place matrix on the MXU moves bins,
channels and slot in one product: a lane group's bins from the shadow
stacked under its channels and its slot, rows on the lanes in all three)
and the one-hot build and product run over whole SUB_BLOCK-row sub-blocks
of packed rows only, the one-hots with the bins on the sublanes; a tile so
full that packing would cost more takes the dense product over all its
rows (``_tile_cost``).  Above 256 bins nothing is packed (bfloat16 would
not hold the bins on the move): every tile with a row in the pass takes
the dense product, and the call takes no shadow.

Measured on a v5e, the kernel alone (its own time in a trace), ms a pass by
the share of rows in the pass, rows drawn at random (PERF.md section 6:
PR 29's readings, then PR 37's, taken with the rule fitted to cost alone,
which packs Epsilon's whole tiles too; as handed in a tile over three
quarters full is dense, 69.7 and 189.5 at 100%; before PR 29: 70.5 and
189.1 at every share):

    share of rows       100%    50%    25%    12%     4%     1%
    10.5M x 28, PR 29   71.3   71.3   42.5   23.3   12.8   12.9
    10.5M x 28, PR 37   69.7   46.0   25.7   14.2    7.9    7.9
    400k x 2000, PR 29  190.2  129.1  69.5   35.7   17.1   17.0
    400k x 2000, PR 37  172.6  94.1   52.4   28.1   15.6   15.6

A 2,048-row tile pays 0.60 us before its first sub-block and 0.99 us a
packed sub-block at 28 features (a dense sub-block 0.85), 2.35 and 3.33 us
at 128 features (dense 3.78).  Until PR 37 a packed sub-block cost 1.65 and
4.74 us: its bins were moved to (SUB_BLOCK, FB), rows on the sublanes, by a
product of their own beside the channels', and every feature's one-hot took
a column out of that block and spread it over the lanes.  That, and not the
two products, was what a packed sub-block paid over a dense one: one product
in their place alone saved 0.06 us; the packed block left feature-major,
its one-hots formed from rows, saved 0.66 and 1.41.  Both the tile's floor
(the rank of its rows, the stacks) and a packed sub-block's move (a
(SUB_BLOCK, row tile) place matrix) grow with the row tile, and the rows a
sparse tile rounds up to shrink with it: 2048 is where a 255-leaf tree's
late passes (2 to 6% of the rows each) came out cheapest at 28 features in
PR 29, and no worse than 1024 or 4096 at 128; not read again since.

Design notes (*log*: in-jit fori_loop probes at N=1M F=28 over the remote
link, before PR 26; methodology + numbers in docs/PERF_NOTES.md):

* The dense product's cost per row did not change with num_bins (64 vs
  256), payload lanes (8 vs 48), row tile (1024-8192) or bins layout.  A
  hi/lo bin-decomposition variant (8x fewer MXU passes) measured 3x SLOWER;
  a pure-XLA one-hot einsum (ops/histogram.py::histogram_onehot_multi)
  beats this kernel at num_bins <= 64 and loses above it — the grower
  selects per max_bin.
* Payload lanes up to ~64 cost the dense product nothing, so near-f32
  precision costs the same as bf16: the payload is split hi+lo bfloat16
  (bf16x2) into two channels and recombined after accumulation.  hi is
  exact in bf16 (cut on the bit pattern, ``_split_bf16x2``: a conversion to
  bfloat16 and back is a no-op to XLA on the TPU under its default flags);
  lo is rounded to bf16 in the kernel, so products carry ~16-17 mantissa
  bits (vs 8 for plain bf16, 24 for true f32) and accumulation is f32 —
  between the reference's float-hist and double-hist modes in practice.
* The same property batches MULTIPLE histograms in one pass:
  `histogram_pallas_multi` computes per-leaf histograms for a tile of
  leaves (lanes = leaf x channel) in a single data pass — the engine of
  the level-batched grower.  The single-leaf entry points are its tile = 1
  case.
* Mosaic on this toolchain rejects bf16/int8 broadcast-selects (and int8
  compares); everything is built in 32-bit dtypes and cast at the dot.

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


# Bytes of VMEM accumulator headroom for the histogram leaf-tile policy
# (recommended_leaf_tile below).
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
    budget = VMEM_ACC_BUDGET
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

ROW_TILE = 2048  # rows of a row tile: one DMA, one count
_DENSE_ROWS = 1024  # rows of one dense product inside a tile
_PACK_MAX_BINS = 256  # bfloat16 holds a bin up to here: packed rows' bins
# are moved as bfloat16
SUB_BLOCK = 128  # rows of a packed sub-block: what a tile pays in whole


def _direct_kernel(chunk_ref, cnt_ref, bins_ref, *refs, n, tile, ncl):
    """Grid (1, row_tiles): the accumulator lives across the row sweep.
    ``chunk_ref`` is the scalar the bins' index maps picked their 128
    features by; the body has no use for it.  ``refs`` are the feature-major
    bins ``shadow_ref`` (FB, 1, T / 128, 128), handed where tiles pack (up
    to 256 bins), then ``base_ref``, ``slot_ref`` and ``out_ref``.
    ``cnt_ref[i]`` is the number of rows of row tile ``i`` that have a slot
    in this pass (or more: see :func:`pass_counts`), and decides what the
    tile costs:

    * 0: the tile's DMA and nothing else.
    * so many that packing would cost more (:func:`_tile_cost`), or above
      256 bins: the dense product.  The dot's (NC, T) operand is formed from
      the base block (8, T) and the slot ids (1, T),
      ``lane[l * ncl + c, t] = base[c, t] if slot[t] == l else 0``: the
      channels are spread over the leaves by a 0/1 matrix on the MXU (exact:
      every channel is bfloat16-exact or an int8, or is rounded to bfloat16
      here as it would be at the dot), then a select by slot keeps each
      row's own leaf; per feature a (T, B) one-hot of the bins is contracted
      against it.
    * otherwise the tile's rows that have a slot are PACKED to the front
      and only ``ceil(cnt / SUB_BLOCK)`` sub-blocks of SUB_BLOCK rows go
      through the one-hot product.  A row's place among the packed rows is
      its rank among the tile's rows with a slot (inside each 128-lane group
      from one product with a 0/1 triangle, plus the groups before it); per
      sub-block a 0/1 matrix P (SUB_BLOCK, T) with ``P[s, t] = 1`` where
      row ``t`` takes place ``s`` moves the bins, the channels and the slot
      on the MXU in one product a lane group, the group's bins (FB, 128)
      from the shadow stacked under its base rows (exact: 0/1 times values
      that bfloat16 holds, bins up to 256 among them, one nonzero term a
      sum), and the same lanes are then built from the packed block, its
      one-hots with the bins on the sublanes and the rows on the lanes, as
      the packed block lies.  Places past the tile's count hold zeros and
      add nothing.  The sums of a tile are taken sub-block by sub-block, so
      a float histogram need not equal the dense product's digit for digit.

    Rows at or past ``n`` take slot -1 and their base is zeroed by a select
    before any product: what the ragged last block holds past the arrays'
    end never reaches the accumulator."""
    *shadow_refs, base_ref, slot_ref, out_ref = refs
    pack = bool(shadow_refs)
    i = pl.program_id(1)

    # the revisited output block IS the accumulator (a separate VMEM
    # scratch would double the scoped footprint and OOM at 60 lanes x 256
    # bins x 128 features — measured 17.04M vs the 16M cap)
    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    FB, NC, B = out_ref.shape
    K, T = base_ref.shape
    S = SUB_BLOCK
    cnt = cnt_ref[i]
    blocks, dense = _tile_cost(cnt, T, FB, pack)
    # the dots' operand type: int8 for the quantized base, bfloat16 otherwise
    dtype = jnp.int8 if base_ref.dtype == jnp.int8 else jnp.bfloat16

    # everything is built in 32-bit types and cast at the dots: Mosaic on
    # this toolchain refuses bf16/int8 broadcast-selects and int8 compares
    def bf16(x):
        return x.astype(jnp.float32).astype(jnp.bfloat16)

    def operands(r0, rows):
        """Rows [r0, r0 + rows) of the tile -> slot (1, rows) int32 and the
        base (K, rows) float32, rows past n and rows with no slot zeroed."""
        row = i * T + r0 + jax.lax.broadcasted_iota(jnp.int32, (1, rows), 1)
        slot = jnp.where(row < n, slot_ref[:, pl.ds(r0, rows)], -1)
        base = base_ref[:, pl.ds(r0, rows)]
        if dtype == jnp.int8:
            base = base.astype(jnp.int32)
        return slot, jnp.where(slot >= 0, base.astype(jnp.float32), 0.0)

    r = jax.lax.broadcasted_iota(jnp.int32, (NC, K), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (NC, K), 1)
    spread = bf16((r - r // ncl * ncl == c) & (r < tile * ncl))  # (NC, K) 0/1
    leaf_of = jax.lax.broadcasted_iota(jnp.int32, (NC, 1), 0) // ncl

    def accumulate(base, slot, bins_i32, rows_on_lanes=False):
        """base (K, W) bfloat16, slot (1, W), bins int32: the one-hot
        product of W rows into the accumulator.  The bins lie (W, FB) as a
        dense tile reads them, or (FB, W), rows on the lanes, as a packed
        tile moves them: its one-hots then have the bins on the sublanes (a
        feature's row against an iota, no column taken and spread over the
        lanes) and the product contracts the lanes of both."""
        W = slot.shape[1]
        wide = jnp.dot(spread, base,
                       preferred_element_type=jnp.float32)  # (NC, W)
        lane = jnp.where(slot == leaf_of, wide, 0.0)  # (1, W) == (NC, 1)
        if dtype == jnp.int8:
            lane = lane.astype(jnp.int32)
        lane = lane.astype(dtype)
        iota_b = jax.lax.broadcasted_iota(  # hoisted
            jnp.int32, (B, W) if rows_on_lanes else (W, B),
            0 if rows_on_lanes else 1)
        for f in range(FB):
            if rows_on_lanes:
                oh = (bins_i32[f:f + 1, :] == iota_b).astype(dtype)  # (B, W)
                out_ref[f] += jax.lax.dot_general(
                    lane, oh, (((1,), (1,)), ((), ())),
                    preferred_element_type=out_ref.dtype)
            else:
                oh = (bins_i32[:, f][:, None] == iota_b).astype(dtype)
                out_ref[f] += jnp.dot(lane, oh,
                                      preferred_element_type=out_ref.dtype)

    @pl.when(dense)
    def _():
        # in pieces of _DENSE_ROWS, so that the unrolled one-hots (and the
        # time Mosaic takes to compile them) do not grow with the row tile
        D = _DENSE_ROWS if T % _DENSE_ROWS == 0 else T

        def piece(p, carry):
            r0 = pl.multiple_of(p * D, D)
            slot, base = operands(r0, D)
            accumulate(base.astype(jnp.bfloat16), slot,
                       bins_ref[pl.ds(r0, D), :].astype(jnp.int32))
            return carry

        jax.lax.fori_loop(0, T // D, piece, 0)

    if not pack:
        return

    @pl.when((cnt > 0) & jnp.logical_not(dense))
    def _():
        G = T // 128
        slot, base = operands(0, T)
        # the slot rides in the base's last row, which no channel uses
        krow = jax.lax.broadcasted_iota(jnp.int32, (K, T), 0)
        base = jnp.where(krow == K - 1, slot.astype(jnp.float32), base)
        # place[g, j]: where row g * 128 + j goes among the packed rows
        taken = (slot >= 0).astype(jnp.float32)
        taken = jnp.concatenate(
            [taken[:, g * 128:(g + 1) * 128] for g in range(G)], axis=0)
        k = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 0)
        j = jax.lax.broadcasted_iota(jnp.int32, (128, 128), 1)
        rank = jnp.dot(taken.astype(jnp.bfloat16), bf16(k < j),
                       preferred_element_type=jnp.float32)  # in its group
        total = jnp.broadcast_to(jnp.sum(taken, axis=1, keepdims=True),
                                 (G, 128))
        gk = jax.lax.broadcasted_iota(jnp.int32, (G, G), 0)
        gj = jax.lax.broadcasted_iota(jnp.int32, (G, G), 1)
        before = jnp.dot(bf16(gj < gk), total.astype(jnp.bfloat16),
                         preferred_element_type=jnp.float32)  # groups before
        place = jnp.where(taken > 0, rank + before, -1.0).astype(jnp.int32)
        # a lane group's stack (K + FB, 128): the base's rows over the
        # group's bins from the shadow, rows on the lanes in both
        shadow_ref, = shadow_refs
        bins_t = shadow_ref[:, 0].astype(jnp.int32).astype(jnp.float32)
        stack = [
            jnp.concatenate([base[:, g * 128:(g + 1) * 128], bins_t[:, g, :]],
                            axis=0).astype(jnp.bfloat16)
            for g in range(G)]
        iota_s = jax.lax.broadcasted_iota(jnp.int32, (S, 128), 0)

        def block(b, carry):
            moved = jnp.zeros((K + FB, S), jnp.float32)
            for g in range(G):
                p = bf16(place[g:g + 1, :] - b * S == iota_s)  # (S, 128)
                moved += jax.lax.dot_general(
                    stack[g], p, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
            accumulate(moved[:K].astype(jnp.bfloat16),
                       moved[K - 1:K].astype(jnp.int32),
                       moved[K:].astype(jnp.int32), rows_on_lanes=True)
            return carry

        jax.lax.fori_loop(0, blocks, block, 0)


def _row_tile(n: int, row_tile: int) -> int:
    """The kernel's row tile: whole 128-row lane groups, and no more of them
    than the rows need."""
    return min(_round_up(row_tile, 128), _round_up(n, 128))


def pass_counts(mask: jnp.ndarray, row_tile: int = ROW_TILE) -> jnp.ndarray:
    """(row_tiles,) int32: the rows of each row tile of the kernel that
    ``mask`` keeps.  ``mask`` is (N,), or the rows laid out a row tile an
    index of the leading axis, the rows past N false (the rounds grower's
    leaf ids lie so: basic.Dataset.bins_device_t).  The kernel's cost
    follows these counts.  A count may be too high (rows the mask keeps but
    no slot of the pass takes: the kernel multiplies empty places), never
    too low."""
    keep = mask.astype(jnp.int32)
    if mask.ndim > 1:
        return jnp.sum(keep.reshape(mask.shape[0], -1), axis=1)
    n = mask.shape[0]
    t = _row_tile(n, row_tile)
    full = n // t
    counts = jnp.sum(keep[:full * t].reshape(full, t), axis=1)
    if full * t < n:  # the ragged last tile
        counts = jnp.concatenate([counts, jnp.sum(keep[full * t:])[None]])
    return counts


def _tile_cost(cnt, row_tile: int, feat_block: int, pack: bool):
    """What a row tile with ``cnt`` rows in the pass does -> (its rows in
    whole sub-blocks, whether it takes the dense product).  The kernel asks
    with its scalar, :func:`blocks_multiplied` with every tile's count.

    Packing is taken where it is the cheaper of the two, in tenths of one
    sub-block's dense one-hot product for one feature (v5e, PERF.md section
    6, PR 37: the kernel's own time in a trace, 28 and 128 features,
    2,048-row tiles of exactly 1, 2, 4, 6, 8, 10, 12 and 16 sub-blocks, a
    straight line in both): a dense tile costs ``row_tile / SUB_BLOCK``
    sub-blocks of ``feat_block`` features; a packed sub-block costs eight
    tenths of its features (its one-hots take a feature's row and no
    column) and six tenths for every 128-row lane group of the tile (its
    share of the place matrix and of the move), and the tile six tenths a
    feature and two a group before its first sub-block (the stacks read out
    of the shadow, the rank).  By cost alone a 2,048-row tile would pack up
    to 13 sub-blocks at 28 features, 14 at 39 and always at 128 (a full
    tile packed costs 0.92 of the dense product there).

    A tile more than three quarters full takes the dense product whatever
    it costs: a packed tile's sums reach the float32 accumulator in pieces
    of SUB_BLOCK rows, a dense tile's in pieces of ``_DENSE_ROWS``, and the
    passes that hold such tiles (the root's, its children's) sum over most
    of the rows.  With the root packed the ranking cell's ``root_hess_gap``
    read ten times the dense root's (PERF.md section 6, PR 37)."""
    groups = row_tile // 128
    whole = row_tile // SUB_BLOCK
    blocks = (cnt + SUB_BLOCK - 1) // SUB_BLOCK
    dense = cnt > 0
    if pack:
        dense &= ((blocks * (8 * feat_block + 6 * groups)
                   + 6 * feat_block + 2 * groups >= 10 * whole * feat_block)
                  | (4 * blocks > 3 * whole))
    return blocks, dense


def _pass_cost(counts, shape, num_bins, row_tile):
    """:func:`_tile_cost` of every row tile of a pass over bins of ``shape``
    (N, F) -> (the sub-blocks of a whole tile, each tile's own, whether each
    takes the dense product)."""
    t = _row_tile(shape[0], row_tile)
    return (t // SUB_BLOCK,
            *_tile_cost(counts, t, min(shape[1], _FEAT_BLOCK),
                        num_bins <= _PACK_MAX_BINS))


def blocks_multiplied(counts: jnp.ndarray, shape: tuple, num_bins: int,
                      row_tile: int = ROW_TILE) -> jnp.ndarray:
    """int32 scalar: the SUB_BLOCK-row blocks that a pass with these
    :func:`pass_counts` over bins of ``shape`` (N, F) puts through the
    one-hot product of each 128-feature chunk: a packed tile its rows in
    whole sub-blocks, a dense tile all of its own."""
    whole, blocks, dense = _pass_cost(counts, shape, num_bins, row_tile)
    return jnp.sum(jnp.where(dense, whole, blocks))


def blocks_packed(counts: jnp.ndarray, shape: tuple, num_bins: int,
                  row_tile: int = ROW_TILE) -> jnp.ndarray:
    """int32 scalar: those of :func:`blocks_multiplied` that lie in packed
    tiles, by the same rule: the sub-blocks that the place matrix's product
    moved before they were multiplied."""
    _, blocks, dense = _pass_cost(counts, shape, num_bins, row_tile)
    return jnp.sum(jnp.where(dense, 0, blocks))


def _shadow_shape(n: int, f: int, row_tile: int) -> tuple:
    t = _row_tile(n, row_tile)
    return f, pl.cdiv(n, t), t // 128, 128


def bins_shadow(bins: jnp.ndarray, row_tile: int = ROW_TILE) -> jnp.ndarray:
    """(N, F) bins -> (F, row_tiles, T / 128, 128), T the kernel's row tile:
    the bins feature-major on the kernel's own tiles, the rows past N bin 0
    (the kernel gives them slot -1 by row index).  What the packed tiles
    read; at the default row tile it is what ``basic.Dataset.bins_device_t``
    keeps on the device, and a caller that holds that hands it in."""
    shape = _shadow_shape(*bins.shape, row_tile)
    rows = shape[1] * shape[2] * 128
    return jnp.pad(bins.T, ((0, 0), (0, rows - bins.shape[0]))).reshape(shape)


@functools.partial(jax.jit,
                   static_argnames=("num_bins", "row_tile", "tile", "ncl"))
def _hist_pallas_raw(
    bins: jnp.ndarray,  # (N, F) int16/int32, as it lies in HBM
    bins_t: jnp.ndarray,  # bins_shadow(bins, row_tile); None above 256 bins
    base: jnp.ndarray,  # (_BASE_ROWS, N) f32 or int8: the per-tree channels
    slot: jnp.ndarray,  # (1, N) int32: the row's leaf of this pass, or -1
    chunk: jnp.ndarray,  # (1,) int32: which block of _FEAT_BLOCK features
    counts: jnp.ndarray,  # (row_tiles,) int32: pass_counts of slot >= 0
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
    and picks its 128 columns in its index map, and the shadow's 128 features
    likewise.  The chunk is a prefetched scalar and not a static, so that the
    sixteen calls of a pass at F = 2000 are one traced and lowered function:
    with sixteen index maps Epsilon's first ``update()`` took 95 s instead of
    20.  ``row_tile`` is rounded to whole 128-row groups (``_row_tile``)."""
    n, f = bins.shape
    B = _round_up(max(num_bins, 8), 8)
    quantized = base.dtype == jnp.int8
    nc = _round_up(tile * ncl, 32 if quantized else 8)
    FB = min(f, _FEAT_BLOCK)
    row_tile = _row_tile(n, row_tile)
    vmem = functools.partial(pl.BlockSpec, memory_space=pltpu.VMEM)
    shadow = [] if bins_t is None else [bins_t]

    # no scope and no name= here: XLA names the custom call after the
    # innermost component of its op_name, and the benchmark's kernel metrics
    # find it in a device trace as ``_hist_pallas_raw.N`` (_leaf_histograms)
    return pl.pallas_call(
        functools.partial(_direct_kernel, n=n, tile=tile, ncl=ncl),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1, pl.cdiv(n, row_tile)),
            in_specs=[
                vmem((row_tile, FB), lambda _, i, c, k: (i, c[0])),
                *[vmem((FB, 1, row_tile // 128, 128),
                       lambda _, i, c, k: (c[0], i, 0, 0)) for _ in shadow],
                vmem((_BASE_ROWS, row_tile), lambda _, i, c, k: (0, i)),
                vmem((1, row_tile), lambda _, i, c, k: (0, i)),
            ],
            out_specs=vmem((FB, nc, B), lambda j, i, c, k: (j, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct(
            (FB, nc, B), jnp.int32 if quantized else jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * n * FB * B * nc,
            bytes_accessed=n * (FB * bins.dtype.itemsize * (1 + len(shadow))
                                + _BASE_ROWS * base.dtype.itemsize + 4),
            transcendentals=0,
        ),
    )(chunk, counts, bins, *shadow, base, slot)


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
                     ncl, row_tile, counts=None, bins_t=None):
    """One pass of the kernel -> (tile, ncl, F, B) in the accumulator's
    dtype: channel ``c`` of the rows that ``mask`` keeps and that sit in
    leaf ``leaf_base + l``.  ``counts``: :func:`pass_counts` of ``mask`` at
    this ``row_tile``, from a caller that made them already.  ``bins_t``:
    :func:`bins_shadow` of ``bins`` at this ``row_tile``, from a caller that
    holds it; one on other tiles (a ``Dataset``'s, for fewer rows than its
    row tile) is built anew like none."""
    if num_bins > _PACK_MAX_BINS:
        bins_t = None  # no tile packs
    elif bins_t is None or bins_t.shape != _shadow_shape(*bins.shape,
                                                         row_tile):
        with phase_scope("hist.payload"):
            bins_t = bins_shadow(bins, row_tile)
    with phase_scope("grow.slots"):
        slot = jnp.where(mask.astype(bool),
                         leaf_id.astype(jnp.int32) - leaf_base, -1)[None, :]
        if counts is None:
            counts = pass_counts(slot[0] >= 0, row_tile)
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
            _hist_pallas_raw(bins, bins_t, base, slot,
                             jnp.full((1,), j, jnp.int32), counts,
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
    row_tile: int = ROW_TILE,
    base: jnp.ndarray = None,  # payload_base(grad, hess, m, precision), m >= mask
    counts: jnp.ndarray = None,  # pass_counts(mask, row_tile)
    bins_t: jnp.ndarray = None,  # bins_shadow(bins, row_tile)
) -> jnp.ndarray:
    """Per-leaf histograms for a tile of leaves in ONE data pass.

    Returns (L_tile, 3, F, B).  Channels are leaf-onehot x payload: lane
    l*NCL + c holds payload channel c masked to leaf leaf_base+l, formed in
    the kernel from ``base`` and the rows' slots.  A caller with many
    passes over the same gradients builds ``base`` once and hands it in;
    without it, it is built here.  Likewise ``counts``, for a caller that
    keeps them (the rounds grower counts what the kernel multiplied), and
    ``bins_t``, the feature-major bins that the packed tiles read.
    This is the TPU replacement for per-leaf row-index histogramming
    (reference: Dataset::ConstructHistograms over DataPartition indices).
    """
    if base is None:
        with phase_scope("hist.payload"):
            base = payload_base(grad, hess, mask, precision)
    out = _leaf_histograms(
        bins, base, mask, leaf_id, leaf_base, num_leaves_tile, num_bins,
        payload_channels(precision, False), row_tile, counts,
        bins_t)  # (L_tile, ncl, F, B)
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
    row_tile: int = ROW_TILE,
    base: jnp.ndarray = None,  # payload_base_quantized(grad_q, hess_q, m)
    counts: jnp.ndarray = None,  # pass_counts(mask, row_tile)
    bins_t: jnp.ndarray = None,  # bins_shadow(bins, row_tile)
) -> jnp.ndarray:
    """Quantized per-leaf histograms for a tile of leaves in one pass ->
    (L_tile, 3, F, B) int32: exact integer accumulation on the int8 MXU
    (reference: gradient_discretizer.cpp + per-leaf ConstructHistograms).
    Same route as :func:`histogram_pallas_multi` with an int8 base."""
    if base is None:
        with phase_scope("hist.payload"):
            base = payload_base_quantized(grad_q, hess_q, mask)
    return _leaf_histograms(bins, base, mask, leaf_id, leaf_base,
                            num_leaves_tile, num_bins, 3, row_tile, counts,
                            bins_t)


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
