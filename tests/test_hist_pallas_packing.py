"""What packing adds to the histogram kernel's contract (ops/hist_pallas.py):
each row tile packs its rows of the pass and multiplies whole sub-blocks of
them, or takes the dense product where that is the cheaper.  Every way a
tile's count can fall, through the Pallas TPU interpreter against the numpy
oracle, and the device's count of the sub-blocks multiplied."""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.ops import hist_pallas as hp
from test_hist_pallas_contract import _oracle, _run

# rows of each 1024-row tile that the pass takes; the last tile is ragged
# (309 rows of it lie inside the arrays)
PACKING_CASES = {
    "a_tile_with_no_row_in_the_pass": [0, 300, 120],
    "a_tile_with_every_row_in_it": [1024, 300, 0],
    "counts_that_are_multiples_of_the_sub_block": [256, 128, 128],
    "counts_one_over_a_multiple": [257, 129, 1],
    "a_count_that_needs_every_sub_block": [897, 1023, 5],
    # at a 1024-row tile packing would stop paying past 7 sub-blocks at 28
    # features and never at 128; a tile more than three quarters full, past
    # 6 sub-blocks, is dense at either width (hist_pallas._tile_cost)
    "counts_either_side_of_where_packing_stops_paying": [640, 641, 768, 769,
                                                         100],
    "a_ragged_last_tile_packed_to_its_end": [500, 309],
}


def _packing_data(counts, f, tile, num_bins):
    rng = np.random.RandomState(len(counts) * 1000 + f + counts[0])
    n = 1024 * (len(counts) - 1) + 309
    bins = rng.randint(0, num_bins, size=(n, f)).astype(np.int16)
    slot = np.full(n, -1, np.int32)
    for i, c in enumerate(counts):
        rows = i * 1024 + rng.permutation(min(1024, n - i * 1024))[:c]
        slot[rows] = rng.randint(0, tile, size=c)
    return n, bins, slot


def _check_against_oracle(bins, slot, tile, num_bins, precision, **kw):
    n, f = bins.shape
    rng = np.random.RandomState(n + f)
    live, lid = slot >= 0, np.maximum(slot, 0)
    with pltpu.force_tpu_interpret_mode():
        if precision == "int8":
            gq = rng.randint(-127, 128, size=n).astype(np.int8)
            hq = rng.randint(0, 128, size=n).astype(np.int8)
            got = _run(hp.histogram_pallas_multi_quantized,
                       (bins, gq, hq, live, lid), 0, tile, num_bins, **kw)
            chans = [gq.astype(np.float64), hq.astype(np.float64), np.ones(n)]
        else:
            grad = rng.randn(n).astype(np.float32)
            hess = (np.abs(rng.randn(n)) + 0.1).astype(np.float32)
            got = _run(hp.histogram_pallas_multi,
                       (bins, grad, hess, live, lid), 0, tile, num_bins,
                       precision=precision, **kw)
            chans = [grad, hess, np.ones(n)]
    want = _oracle(bins, chans, slot, tile, num_bins)
    if precision == "int8":
        np.testing.assert_array_equal(got, want.astype(np.int64))
        return got
    rtol = 1e-4 if precision == "f32" else 2e-2
    np.testing.assert_allclose(got[:, :2], want[:, :2], rtol=rtol, atol=rtol)
    np.testing.assert_array_equal(got[:, 2], want[:, 2])  # counts exact
    return got


@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("f", [28, 130])
@pytest.mark.parametrize("case", sorted(PACKING_CASES))
def test_packed_tiles_match_the_oracle(case, f, precision):
    """What packing adds: every way a tile's count can fall against the
    sub-block and the tile, 255 bins, four leaves a pass."""
    n, bins, slot = _packing_data(PACKING_CASES[case], f, 4, 255)
    _check_against_oracle(bins, slot, 4, 255, precision, row_tile=1024)


@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("f", [28, 130])
def test_a_dataset_s_shadow_handed_in_is_the_shadow_built(f, precision):
    """The packed tiles read their bins from the feature-major shadow.  A
    call that holds ``(N, F)`` alone builds it; a grower on the chip hands
    in what ``basic.Dataset.bins_device_t`` keeps: whole ROW_TILE tiles, the
    rows past a ragged N bin 0.  One histogram, digit for digit, from a
    dense tile, two packed ones and the ragged end; int8 the oracle's."""
    from test_partition_shadow import shadow_of

    n = 2 * hp.ROW_TILE + 437
    rng = np.random.RandomState(f)
    bins = rng.randint(0, 255, size=(n, f)).astype(np.int16)
    slot = rng.randint(0, 4, size=n).astype(np.int32)
    slot[hp.ROW_TILE:] = np.where(rng.rand(n - hp.ROW_TILE) < 0.15,
                                  slot[hp.ROW_TILE:], -1)
    counts = [int((slot[i:i + hp.ROW_TILE] >= 0).sum())
              for i in range(0, n, hp.ROW_TILE)]
    assert counts[0] == hp.ROW_TILE and 128 < counts[1] < 512 > counts[2] > 0
    shadow = shadow_of(bins)
    assert shadow.shape == (f, 3, hp.ROW_TILE // 128, 128)
    built = _check_against_oracle(bins, slot, 4, 255, precision)
    handed = _check_against_oracle(bins, slot, 4, 255, precision,
                                   bins_t=shadow)
    np.testing.assert_array_equal(handed, built)


def test_a_shadow_on_other_tiles_is_built_anew():
    """Fewer rows than a ``Dataset``'s row tile: its shadow is one 2,048-row
    tile, the kernel's tile is the rows in whole lane groups, and the call
    reads the shadow it builds."""
    from test_partition_shadow import shadow_of

    n, bins, slot = _packing_data([309], 28, 4, 255)
    shadow = shadow_of(bins)
    assert shadow.shape[1:] == (1, 16, 128) != hp.bins_shadow(
        jnp.asarray(bins)).shape[1:]
    _check_against_oracle(bins, slot, 4, 255, "f32", bins_t=shadow)


@pytest.mark.parametrize("precision", ["f32", "int8"])
def test_over_256_bins_every_tile_takes_the_dense_product(precision):
    """bfloat16 holds the bins up to 256 and no further, so such a call
    packs nothing; sparse and empty tiles all the same."""
    n, bins, slot = _packing_data([40, 0, 200], 28, 2, 300)
    assert bins.max() > 256
    _check_against_oracle(bins, slot, 2, 300, precision, row_tile=1024)


@pytest.mark.parametrize("row_tile", [256, 1024])
def test_counts_too_high_multiply_empty_places_and_change_nothing(row_tile):
    """A caller's own counts may count rows that no slot of the pass takes
    (the grower counts by its mask): the kernel runs the sub-blocks and they
    add zeros."""
    n, bins, slot = _packing_data([100, 700, 20], 28, 4, 64)
    high = hp.pass_counts(jnp.asarray(slot >= 0), row_tile) + 130
    _check_against_oracle(bins, slot, 4, 64, "f32", row_tile=row_tile,
                          counts=jnp.minimum(high, row_tile))


def _blocks_in_numpy(slot, f, num_bins, row_tile):
    """The sub-blocks a pass multiplies, from the slots: a tile's rows in
    the pass in whole sub-blocks, or the whole tile where the dense product
    is the cheaper (the rule of hist_pallas._tile_cost, written out); and
    those of them that lie in packed tiles."""
    n = len(slot)
    t = min(row_tile, -(-n // 128) * 128)
    groups, fb, total, packed = t // 128, min(f, 128), 0, 0
    for i in range(0, n, t):
        cnt = int((slot[i:i + t] >= 0).sum())
        blocks = -(-cnt // hp.SUB_BLOCK)
        dense = cnt > 0 and (
            num_bins > 256
            or blocks * (8 * fb + 6 * groups) + 6 * fb + 2 * groups
            >= 10 * (t // hp.SUB_BLOCK) * fb
            or 4 * blocks > 3 * (t // hp.SUB_BLOCK))
        total += t // hp.SUB_BLOCK if dense else blocks
        packed += 0 if dense else blocks
    return total, packed


@pytest.mark.parametrize("f, num_bins", [(28, 255), (130, 255), (28, 300)])
@pytest.mark.parametrize("case", sorted(PACKING_CASES))
def test_blocks_multiplied_is_what_the_tiles_round_up_to(case, f, num_bins):
    """The device's count of sub-blocks against numpy's, from the slots."""
    counts = PACKING_CASES[case]
    n, _, slot = _packing_data(counts, 1, 4, 8)
    got = hp.pass_counts(jnp.asarray(slot >= 0), 1024)
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), counts)
    blocks = hp.blocks_multiplied(got, (n, f), num_bins, 1024)
    assert blocks.dtype == jnp.int32
    assert int(blocks) == _blocks_in_numpy(slot, f, num_bins, 1024)[0]
    # the least a pass can multiply: its rows in whole sub-blocks, tile by
    # tile; the most: every tile that holds a row of it, whole
    least = sum(-(-c // hp.SUB_BLOCK) for c in counts)
    assert least <= int(blocks) <= 8 * sum(c > 0 for c in counts)
    if num_bins == 255 and max(counts) <= 768:  # no tile is dense
        assert int(blocks) == least


@pytest.mark.parametrize("f, num_bins", [(28, 255), (130, 255), (28, 300)])
@pytest.mark.parametrize("case", sorted(PACKING_CASES))
def test_blocks_packed_is_the_packed_tiles_share_of_them(case, f, num_bins):
    """What ``train_hist_blocks_packed_total`` adds up: the sub-blocks of
    the tiles that pack, by the same rule, against numpy's from the slots;
    the rest of the blocks multiplied are whole dense tiles."""
    n, _, slot = _packing_data(PACKING_CASES[case], 1, 4, 8)
    counts = hp.pass_counts(jnp.asarray(slot >= 0), 1024)
    packed = hp.blocks_packed(counts, (n, f), num_bins, 1024)
    assert packed.dtype == jnp.int32
    total, want = _blocks_in_numpy(slot, f, num_bins, 1024)
    assert int(packed) == want
    assert (total - want) % (1024 // hp.SUB_BLOCK) == 0
    assert int(hp.blocks_multiplied(counts, (n, f), num_bins, 1024)) == total
    if num_bins > 256:
        assert want == 0
