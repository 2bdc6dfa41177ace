"""The histogram kernel's input contract (ops/hist_pallas.py): the bins as
they lie, a channel-first per-tree base, one slot id per row; the leaf lanes
are formed in the kernel.  Run through the Pallas TPU interpreter against a
numpy oracle, and the bf16x2 split checked bit for bit."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from lightgbm_tpu.ops import hist_pallas as hp

N = 1333  # 309 rows into the second 1024-row tile
B = 37


def _oracle(bins, chans, slot, tile, B=B):
    """(tile, C, F, B) float64 sums of each channel over the rows of a slot."""
    n, f = bins.shape
    out = np.zeros((tile, len(chans), f, B))
    for l in range(tile):
        rows = np.flatnonzero(slot == l)
        for c, v in enumerate(chans):
            for j in range(f):
                out[l, c, j] = np.bincount(bins[rows, j], weights=v[rows],
                                           minlength=B)
    return out


def _run(fn, arrays, *static, **kw):
    """One jitted call through the TPU interpreter, waited for.  Not op by
    op: the interpreter's callbacks run jax operations of their own, and an
    eager operation dispatched beside a kernel still in flight can deadlock
    with them."""
    return np.asarray(jax.jit(lambda *a: fn(*a, *static, **kw))(
        *[jnp.asarray(a) for a in arrays]))


def _data(f, tile):
    rng = np.random.RandomState(f * 10 + tile)
    bins = rng.randint(0, B, size=(N, f)).astype(np.int16)
    grad = rng.randn(N).astype(np.float32)
    hess = (np.abs(rng.randn(N)) + 0.1).astype(np.float32)
    inbag = rng.rand(N) < 0.8
    # a third of the rows sit in a leaf this pass does not build
    leaf = rng.randint(-tile // 2 - 1, tile, size=N).astype(np.int32)
    return bins, grad, hess, inbag, leaf


@pytest.mark.parametrize("tile", [1, 8])
@pytest.mark.parametrize("precision", ["f32", "bf16", "int8"])
@pytest.mark.parametrize("f", [28, 130])
def test_leaf_histograms_match_the_oracle(f, precision, tile):
    """N short of a row tile, a ragged last feature chunk at F = 130, rows in
    no leaf of the pass (slot -1) and rows out of bag."""
    bins, grad, hess, inbag, leaf = _data(f, tile)
    live = inbag & (leaf >= 0)
    slot = np.where(live, leaf, -1)
    with pltpu.force_tpu_interpret_mode():
        if precision == "int8":
            rng = np.random.RandomState(5)
            gq = rng.randint(-127, 128, size=N).astype(np.int8)
            hq = rng.randint(0, 128, size=N).astype(np.int8)
            got = _run(hp.histogram_pallas_multi_quantized,
                       (bins, gq, hq, live, np.maximum(leaf, 0)), 0, tile, B)
            want = _oracle(bins, [gq.astype(np.float64),
                                  hq.astype(np.float64), np.ones(N)], slot,
                           tile)
            assert got.dtype == np.int32
            np.testing.assert_array_equal(got, want.astype(np.int64))
            return
        got = _run(hp.histogram_pallas_multi,
                   (bins, grad, hess, live, np.maximum(leaf, 0)), 0, tile, B,
                   precision=precision)
    assert got.shape == (tile, 3, f, B) and got.dtype == np.float32
    want = _oracle(bins, [grad, hess, np.ones(N)], slot, tile)
    # products carry ~17 bits of the gradient with the bf16x2 split, 8 without
    rtol = 1e-4 if precision == "f32" else 2e-2
    np.testing.assert_allclose(got[:, :2], want[:, :2], rtol=rtol, atol=rtol)
    np.testing.assert_array_equal(got[:, 2], want[:, 2])  # counts exact


@pytest.mark.parametrize("precision", ["f32", "int8"])
def test_a_base_built_once_serves_every_pass_of_a_tree(precision):
    """What the rounds grower does: the base from the in-bag mask alone, then
    passes that differ by their slots only."""
    tile = 4
    bins, grad, hess, inbag, leaf = _data(28, tile)
    q = precision == "int8"
    if q:
        grad = np.round(grad * 20).astype(np.int8)
        hess = np.round(hess * 20).astype(np.int8)
    with pltpu.force_tpu_interpret_mode():
        base = np.asarray((hp.payload_base_quantized if q else hp.payload_base)(
            jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(inbag)))
        assert base.shape == (8, N)
        call = (hp.histogram_pallas_multi_quantized if q
                else hp.histogram_pallas_multi)
        for shift in (0, 2):
            lid = (leaf + shift) % tile
            live = inbag & (leaf >= 0)
            with_base = _run(
                lambda *a: call(*a[:-1], 0, tile, B, base=a[-1]),
                (bins, grad, hess, live, lid, base))
            alone = _run(call, (bins, grad, hess, live, lid), 0, tile, B)
            np.testing.assert_array_equal(with_base, alone)


@pytest.mark.parametrize("quantized", [False, True])
def test_rows_past_n_never_reach_the_accumulator(quantized):
    """The last row tile is read to its end: what lies there (here NaN, or
    127s, in a live slot) is held out by the select on the row's index."""
    tile, ncl, f = 2, 3 if quantized else 6, 28
    bins, grad, hess, inbag, leaf = _data(f, tile)
    n_read = 2048  # what two 1024-row tiles cover
    slot = np.zeros((1, n_read), np.int32)
    slot[0, :N] = np.where(inbag, np.maximum(leaf, 0), -1)
    if quantized:
        base = np.full((8, n_read), 127, np.int8)
        base[:, :N] = np.asarray(hp.payload_base_quantized(
            jnp.asarray(np.round(grad * 20).astype(np.int8)),
            jnp.asarray(np.round(hess * 20).astype(np.int8)),
            jnp.asarray(inbag)))
    else:
        base = np.full((8, n_read), np.nan, np.float32)
        base[:, :N] = np.asarray(hp.payload_base(
            jnp.asarray(grad), jnp.asarray(hess), jnp.asarray(inbag)))
    kw = dict(num_bins=B, row_tile=1024, tile=tile, ncl=ncl)
    chunk = jnp.zeros((1,), jnp.int32)
    with pltpu.force_tpu_interpret_mode():
        counts = hp.pass_counts(jnp.asarray(slot[0, :N] >= 0), 1024)
        shadow = hp.bins_shadow(jnp.asarray(bins), 1024)
        long = np.asarray(hp._hist_pallas_raw(
            jnp.asarray(bins), shadow, jnp.asarray(base), jnp.asarray(slot),
            chunk, counts, **kw))
        exact = np.asarray(hp._hist_pallas_raw(
            jnp.asarray(bins), shadow, jnp.asarray(base[:, :N]),
            jnp.asarray(slot[:, :N]), chunk, counts, **kw))
    assert np.isfinite(long.astype(np.float64)).all()
    np.testing.assert_array_equal(long, exact)
    assert long[:, :tile * ncl].any()


SPLIT_CASES = {
    "random": np.random.RandomState(0).randn(4096).astype(np.float32),
    # lo is 2**-9 of x or less and has to stay a normal number itself
    "tiny": (np.random.RandomState(1).randn(512) * 1e-30).astype(np.float32),
    "huge": (np.random.RandomState(2).randn(512) * 1e37).astype(np.float32),
    "negative": -np.abs(np.random.RandomState(3).randn(512)).astype(
        np.float32),
    "ties_and_zeros": np.array(
        [0.0, 1.0, 1.00390625, 1.01171875, -1.00390625, 255.5, 3e38],
        np.float32),
}


@pytest.mark.parametrize("case", sorted(SPLIT_CASES))
def test_the_split_cuts_bits_and_loses_none(case):
    x = SPLIT_CASES[case]
    hi, lo = (np.asarray(a) for a in jax.jit(hp._split_bf16x2)(x))
    assert not (hi.view(np.uint32) & 0xFFFF).any()  # bfloat16 holds hi
    np.testing.assert_array_equal((hi + lo).view(np.uint32),
                                  x.view(np.uint32))
    # hi is x rounded to nearest-even, as the conversion rounds it
    want = np.asarray(jnp.asarray(x).astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_array_equal(hi.view(np.uint32), want.view(np.uint32))
    assert np.all(np.abs(lo) <= np.abs(x) * 2.0 ** -8)


def test_the_split_converts_nothing_to_bfloat16():
    """A float32 -> bfloat16 -> float32 round trip is what XLA on the TPU
    folds away under its default flags; the jaxpr holds none."""
    jaxpr = jax.make_jaxpr(hp._split_bf16x2)(jnp.zeros((8,), jnp.float32))
    text = str(jaxpr)
    assert "bf16" not in text and "bfloat16" not in text
    assert "bitcast_convert_type" in text
    for precision in ("f32", "bf16"):
        text = str(jax.make_jaxpr(
            lambda g, h, m: hp.payload_base(g, h, m, precision))(
                jnp.zeros((8,)), jnp.zeros((8,)), jnp.ones((8,), bool)))
        assert "bf16" not in text
