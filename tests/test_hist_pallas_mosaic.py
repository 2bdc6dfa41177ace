"""The histogram kernel is accepted by the TPU's compiler at the shapes the
benchmark's cells run, compiled here for a described v5e with no chip
attached.  Nothing runs: no result, no time.  (What the interpreter cannot
show: slices off the tiling, too much VMEM, an operation Mosaic refuses.)"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from lightgbm_tpu.ops import hist_pallas as hp


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # what is compiled for a described chip cannot be read back from the
    # persistent cache without one
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


SHAPES = {
    # rows, features, leaves a pass, channels a leaf, base dtype, bins
    "higgs_float": (10_500_000, 28, 8, 6, jnp.float32, 255),
    "higgs_root_one_leaf": (10_500_000, 28, 1, 6, jnp.float32, 255),
    "narrow_int8": (1_000_000, 28, 20, 3, jnp.int8, 255),
    "fewer_rows_than_a_tile": (500, 28, 4, 3, jnp.float32, 255),
    "over_256_bins_dense_alone": (1_000_000, 28, 8, 6, jnp.float32, 300),
    "epsilon_float_one_chunk_of_sixteen": (400_000, 2000, 10, 6, jnp.float32,
                                           255),
    "epsilon_int8_the_default_there": (400_000, 2000, 20, 3, jnp.int8, 255),
    "istella_float_one_chunk_of_two": (4_883_750, 220, 10, 6, jnp.float32,
                                       255),
    "criteo_float": (15_280_205, 39, 8, 6, jnp.float32, 255),
}


@pytest.mark.parametrize("case", sorted(SHAPES))
def test_mosaic_takes_the_kernel(one_chip, case):
    """With the feature-major shadow beside the bins wherever tiles pack:
    at 128 features a chunk the packed branch's stacks and the shadow's two
    buffers lie beside an 8.4 MB accumulator under the 16 MB scope."""
    n, f, tile, ncl, dtype, num_bins = SHAPES[case]

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    shadow = hp._shadow_shape(n, f, hp.ROW_TILE)
    compiled = jax.jit(
        lambda *a: hp._hist_pallas_raw(*a, num_bins=num_bins,
                                       row_tile=hp.ROW_TILE, tile=tile,
                                       ncl=ncl)).lower(
            s((n, f), jnp.int16),
            s(shadow, jnp.int16) if num_bins <= 256 else None,
            s((8, n), dtype), s((1, n), jnp.int32), s((1,), jnp.int32),
            s((shadow[1],), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,f,categorical", [
    (10_500_000, 28, False), (15_280_205, 39, True)],
    ids=["higgs-numerical", "criteo-categorical"])
def test_partition_over_the_shadow_is_one_pass(one_chip, n, f, categorical):
    """The rounds grower's partition at Higgs' shape, over the feature-major
    shadow as ``Dataset.bins_device_t`` lays it out: one fusion reads the
    shadow, nothing is a row of one sublane, and XLA counts the round's
    eight columns and the ids once each way, with half as much to spare
    (PERF.md section 6, PR 31: eight fusions and 588 MB before).  And at
    Criteo's shape with categorical slots, which read their bins-going-left
    as a bitset: the same one fusion, the same bytes, and no gather (PR 36:
    the lookup that stood there was a gather of every row in every slot)."""
    import re

    from lightgbm_tpu.ops.treegrow import _empty_best
    from lightgbm_tpu.ops.treegrow_fast import partition_rows

    slots, leaves = 8, 255
    tile = (-(-n // hp.ROW_TILE), hp.ROW_TILE // 128, 128)

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    best = jax.tree_util.tree_map(
        lambda a: s(a.shape, a.dtype),
        jax.eval_shape(lambda: _empty_best(leaves, 255)))

    def partition(bins_t, lid, best, accept, inv_rank, right_of, missing):
        return partition_rows(bins_t, 0, lid, best, accept, inv_rank,
                              right_of, missing, slots, categorical)

    compiled = jax.jit(partition).lower(
        s((f, *tile), jnp.int16), s(tile, jnp.int32), best,
        s((leaves,), jnp.bool_), s((leaves,), jnp.int32),
        s((leaves,), jnp.int32), s((f,), jnp.int32)).compile()
    text = compiled.as_text()
    readers = re.findall(r"= \S+ ([\w\-]+)\([^)]*%bins_t", text)
    assert readers == ["fusion"], readers
    assert not re.search(r"\[(1,)?%d\d\d\d\]" % (n // 1000), text)  # all rows in a row
    assert " gather(" not in text
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    rows = tile[0] * hp.ROW_TILE
    assert cost["bytes accessed"] <= 1.5 * (slots * 2 * rows + 8 * rows)


def test_rank_step_keeps_every_array_under_a_gigabyte_at_istella_s_shape(
        one_chip):
    """The lambdarank gradient step at the ranking cell's shape (4,883,750
    rows in 15,480 queries of up to 2,048 rows, truncation 30), compiled for
    the chip: a bucket's pair terms are (piece, 30, width) and never
    (queries, width, width), no array of the step reaches 1 GB, and XLA's
    temporaries stay under it too.  The dense form wrote (Q, S, S) tensors,
    260 TB each at this shape had XLA ever stored one."""
    import re

    import numpy as np

    from lightgbm_tpu import objectives
    from lightgbm_tpu.config import Config

    n, queries, longest = 4_883_750, 15_480, 2048
    rng = np.random.default_rng(34)
    lens = np.clip(np.rint(rng.lognormal(
        np.log(n / queries) - 0.32, 0.8, queries)), 1, longest).astype(int)
    lens[0] = longest
    qb = np.concatenate([[0], np.cumsum(lens)])
    obj = objectives.LambdarankNDCG(Config(objective="lambdarank"))
    obj.set_query(qb, rng.integers(0, 5, qb[-1]).astype(np.float32))
    rows, lanes, pairs = obj.rank_work
    assert lanes < 2 * rows and pairs <= 30 * lanes

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    args = jax.tree.map(s, (jnp.zeros((rows,), jnp.float32), obj._layout,
                            obj._inv_mdcg, obj._gain))
    compiled = objectives._lambdarank_step.lower(
        *args, None, None, sigmoid=1.0, truncation=30, norm=True,
        pos_reg=0.0).compile()
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 1e9
    widest = 0
    for dtype, dims in re.findall(r"\b(f32|s32|u32|pred)\[([\d,]+)\]",
                                  compiled.as_text()):
        size = int(np.prod([int(d) for d in dims.split(",")]))
        widest = max(widest, size * (1 if dtype == "pred" else 4))
    assert 4 * rows <= widest < 1e9
    text = compiled.as_text()
    assert re.search(r"f32\[\d+,30,2048\]", text)
    assert not re.search(r"\[\d+,2048,2048\]", text)
