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
    """The last row tile is read to its end.  What lies there is held out by
    the select on the row's index: the shadow's rows past N (here the
    largest bin, where ``bins_shadow`` puts bin 0) and the ragged blocks of
    the base and the slots (the interpreter reads NaN, or 127s, and the
    largest int32, a slot >= 0, past an array's end)."""
    tile, ncl, f = 2, 3 if quantized else 6, 28
    bins, grad, hess, inbag, leaf = _data(f, tile)
    slot = np.where(inbag, np.maximum(leaf, 0), -1).astype(np.int32)[None]
    if quantized:
        gq = np.round(grad * 20).astype(np.int8)
        hq = np.round(hess * 20).astype(np.int8)
        base = hp.payload_base_quantized(jnp.asarray(gq), jnp.asarray(hq),
                                         jnp.asarray(inbag))
        chans = [gq.astype(np.float64), hq.astype(np.float64)]
    else:
        base = hp.payload_base(jnp.asarray(grad), jnp.asarray(hess),
                               jnp.asarray(inbag))
        chans = [grad.astype(np.float64), hess.astype(np.float64)]
    shadow = np.asarray(hp.bins_shadow(jnp.asarray(bins), 1024))
    assert shadow.shape == (f, 2, 8, 128)
    filled = shadow.reshape(f, -1).copy()
    filled[:, N:] = B - 1
    kw = dict(num_bins=B, tile=tile, ncl=ncl)
    chunk = jnp.zeros((1,), jnp.int32)
    with pltpu.force_tpu_interpret_mode():
        counts = hp.pass_counts(jnp.asarray(slot[0] >= 0), 1024)
        got = [np.asarray(hp._hist_pallas_raw(
            jnp.asarray(s.reshape(shadow.shape)), base, jnp.asarray(slot),
            chunk, counts, **kw)) for s in (shadow, filled)]
    assert np.isfinite(got[0].astype(np.float64)).all()
    np.testing.assert_array_equal(got[1], got[0])
    want = _oracle(bins, chans + [np.ones(N)], slot[0], tile)
    lanes = got[0][:, :tile * ncl, :B].reshape(f, tile, ncl, B)
    if quantized:
        np.testing.assert_array_equal(lanes.transpose(1, 2, 0, 3),
                                      want.astype(np.int64))
    else:  # the channels hi + lo
        sums = lanes[:, :, [0, 1, 2]] + np.concatenate(
            [lanes[:, :, [3, 4]], np.zeros_like(lanes[:, :, :1])], axis=2)
        np.testing.assert_allclose(sums.transpose(1, 2, 0, 3), want,
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("num_bins", [255, 300])
def test_the_kernel_takes_the_shadow_and_no_row_major_bins(num_bins):
    """The bins reach the kernel once, feature-major, at every bin count:
    ``_hist_pallas_raw`` has no ``(N, F)`` parameter, and in a call made
    with ``(N, F)`` bins the one ``int16`` operand of the ``pallas_call``
    is the shadow built of them."""
    import inspect

    params = list(inspect.signature(hp._hist_pallas_raw).parameters)
    assert params[:5] == ["bins_t", "base", "slot", "chunk", "counts"]
    bins, grad, hess, inbag, leaf = _data(28, 4)
    with pltpu.force_tpu_interpret_mode():
        jaxpr = jax.make_jaxpr(
            lambda *a: hp.histogram_pallas_multi(*a, 0, 4, num_bins,
                                                 row_tile=1024))(
            *[jnp.asarray(a) for a in (bins, grad, hess, inbag, leaf)])
    calls = [e for e in jaxpr.eqns
             if e.params.get("name") == "_hist_pallas_raw"]
    assert len(calls) == 1
    shapes = [(v.aval.shape, v.aval.dtype) for v in calls[0].invars]
    assert [s for s, dt in shapes if dt == jnp.int16] == [(28, 2, 8, 128)]
    # the shadow, the base, the slots, the chunk, a count a row tile
    assert sorted(s for s, _ in shapes) == sorted(
        [hp._shadow_shape(N, 28, 1024), (8, N), (1, N), (1,), (2,)])


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
