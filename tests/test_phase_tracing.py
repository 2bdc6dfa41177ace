"""The boosting round seen from inside (docs/OBSERVABILITY.md, "Device
phases"): the named scopes of ``profiling.DEVICE_PHASES`` reach the HLO, the
rounds grower counts its passes, ``gbdt._flush_pending`` adds them up, and a
device trace reduces to seconds per phase by each operation's self time."""

import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import metrics as obs
from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.ops.treegrow_fast import _grow_fast_impl
from lightgbm_tpu.utils import profiling

REPO = pathlib.Path(__file__).resolve().parents[1]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# grow.cat_search needs a categorical column: tests/test_categorical_route.py
GROWER_PHASES = [p for p in profiling.DEVICE_PHASES
                 if p.startswith(("grow.", "hist."))
                 and p != "grow.cat_search"]


def _toy(n=1500, f=5, seed=3):
    rng = np.random.RandomState(seed)
    bins = rng.randint(0, 32, size=(n, f)).astype(np.int16)
    y = (bins[:, 0] + 0.5 * bins[:, 1] + rng.randn(n) * 4 > 24)
    grad = np.where(y, -0.5, 0.5).astype(np.float32)
    return bins, grad, np.full(n, 0.25, np.float32)


def _grow(bins, grad, hess, **kw):
    n, f = bins.shape
    kw = dict(dict(num_leaves=8, num_bins=32, leaf_tile=4, use_pallas=False,
                   params=SplitParams(min_data_in_leaf=1,
                                      min_sum_hessian_in_leaf=1e-3)), **kw)
    return _grow_fast_impl(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.ones(n, bool), jnp.ones(n, jnp.float32), jnp.ones(f, bool),
        jnp.full(f, 32, jnp.int32), jnp.full(f, -1, jnp.int32), **kw)


# ---------------------------------------------------------------------------
# 1. the catalogue reaches the HLO
# ---------------------------------------------------------------------------

TOY_N, TOY_F, TOY_TILE = 1500, 5, 4


def _lowered_grower_hlo(num_bins):
    """HLO text of the rounds grower lowered for the TPU at toy size: the
    paths the CPU backend does not take.  Lowering only: nothing compiles,
    nothing runs."""
    n, f = TOY_N, TOY_F
    s = jax.ShapeDtypeStruct
    args = (s((n, f), jnp.int16), s((n,), jnp.float32), s((n,), jnp.float32),
            s((n,), jnp.bool_), s((n,), jnp.float32), s((f,), jnp.bool_),
            s((f,), jnp.int32), s((f,), jnp.int32))
    lowered = _grow_fast_impl.trace(
        *args, num_leaves=8, num_bins=num_bins, params=SplitParams(),
        leaf_tile=TOY_TILE, hist_precision="f32", use_pallas=True).lower(
            lowering_platforms=("tpu",))
    return lowered.as_text(dialect="hlo", debug_info=True)


@pytest.fixture(scope="module")
def grower_hlo():
    """255 bins: the Pallas route (base, kernel, unpack)."""
    return _lowered_grower_hlo(255)


@pytest.fixture(scope="module")
def grower_op_names(grower_hlo):
    return set(re.findall(r'op_name="([^"]*)"', grower_hlo))


@pytest.fixture(scope="module")
def einsum_grower_op_names():
    """63 bins: the XLA einsum route, which still builds its payload and
    pads its rows every pass (``hist.rowpad``'s only home)."""
    return set(re.findall(r'op_name="([^"]*)"', _lowered_grower_hlo(63)))


@pytest.mark.parametrize("phase", GROWER_PHASES)
def test_every_grower_phase_is_a_scope_in_the_lowered_hlo(
        grower_op_names, einsum_grower_op_names, phase):
    names = (einsum_grower_op_names if phase == "hist.rowpad"
             else grower_op_names)
    hits = [n for n in names if phase in n.split("/")]
    assert hits, f"no operation of the lowered grower carries {phase}"
    assert all(profiling.phase_of(n) is not None for n in hits)


def test_a_pass_of_the_pallas_route_builds_and_pads_nothing_n_sized(
        grower_hlo):
    """The kernel takes its inputs as they lie: the base is built once,
    before the loop, under ``hist.payload``; inside the ``while`` body no
    operation pads, stacks or reshapes a lane-expanded payload (N x tile x
    6 elements) and none pads the bins."""
    ops = []  # (opcode, dtype, elements, op_name)
    for m in re.finditer(
            r'= (\w+)\[([\d,]*)\]\S* (\w[\w-]*)\(.*?op_name="([^"]*)"',
            grower_hlo):
        dims = [int(d) for d in m.group(2).split(",") if d]
        ops.append((m.group(3), m.group(1), int(np.prod(dims)), m.group(4)))
    in_loop = [o for o in ops if "/while/body/" in o[3]]
    assert len(in_loop) > 100
    expanded = TOY_N * TOY_TILE * 6
    assert not [o for o in in_loop if o[2] >= expanded
                and o[0] in ("pad", "concatenate", "reshape")]
    assert not [o for o in in_loop if o[0] == "pad" and o[2] >= TOY_N]
    assert not [o for o in ops if "hist.rowpad" in o[3].split("/")]
    payload = [o for o in ops if "hist.payload" in o[3].split("/")]
    assert payload and not [o for o in payload if "while" in o[3].split("/")]
    base = [o for o in payload if o[0] == "concatenate"]
    assert [o[1:3] for o in base] == [("f32", 8 * TOY_N)]


def test_the_kernel_keeps_the_name_the_benchmark_finds_it_by(grower_op_names):
    """The scope sits outside the jitted ``_hist_pallas_raw``: XLA names the
    custom call after the innermost component of its op_name, and
    ``hist_kernel_ms_per_tree`` finds it as ``_hist_pallas_raw.N``."""
    calls = [n for n in grower_op_names if n.endswith("jit(_hist_pallas_raw)")]
    assert len(calls) == 2  # the root's pass and the loop's
    assert all(n.split("/")[-2] == "hist.kernel" for n in calls)
    # before XLA inlines the jitted function its operations are named from
    # its own top: nothing stands between it and the pallas_call
    assert "pallas_call" in grower_op_names


def test_phase_scope_refuses_a_name_outside_the_catalogue():
    with pytest.raises(ValueError):
        profiling.phase_scope("grow.unheard_of")
    assert profiling.phase_of("jit(f)/grow.root/hist.payload/mul") == (
        "hist.payload")  # the innermost catalogued component
    assert profiling.phase_of("jit(f)/while/body/add") is None


# ---------------------------------------------------------------------------
# 2. pass and row counters
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("leaf_tile, rounds", [(1, 7), (4, 3), (8, 3)])
def test_hist_passes_is_the_root_plus_one_per_round(leaf_tile, rounds):
    """Eight leaves: a round splits at most ``leaf_tile`` leaves, and every
    leaf of this data can be split, so the tree takes 7 rounds at one split
    a round and 3 (1, 2, then 4 splits) at four or more."""
    tree, _ = _grow(*_toy(), leaf_tile=leaf_tile)
    assert int(tree.num_leaves) == 8
    assert int(tree.hist_passes) == 1 + rounds


def test_a_tree_that_cannot_split_took_the_root_pass_alone():
    bins, grad, hess = _toy()
    tree, _ = _grow(bins, grad, hess, params=SplitParams(
        min_data_in_leaf=10 ** 6))
    assert int(tree.num_leaves) == 1 and int(tree.hist_passes) == 1


def test_hist_blocks_counts_the_sub_blocks_the_kernel_multiplied():
    """Two leaves on the Pallas route (through the interpreter), 130
    features so that packing pays: the root's pass takes every row, the
    second the smaller child alone, and each row tile then pays whole
    sub-blocks of the rows it holds of the pass."""
    from jax.experimental.pallas import tpu as pltpu

    from lightgbm_tpu.ops import hist_pallas as hp

    n = 2 * hp.ROW_TILE + 300
    rng = np.random.RandomState(5)
    bins = rng.randint(0, 32, size=(n, 130)).astype(np.int16)
    bins[:, 0] = rng.rand(n) < 0.2  # the split every gain points at
    grad = np.where(bins[:, 0] > 0, -0.5, 0.5).astype(np.float32)
    with pltpu.force_tpu_interpret_mode():
        tree, leaf_id = _grow(bins, grad, np.full(n, 0.25, np.float32),
                              num_leaves=2, num_bins=128, use_pallas=True)
    assert int(tree.num_leaves) == 2 and int(tree.hist_passes) == 2
    leaf_id = np.asarray(leaf_id)
    small = leaf_id == np.argmin(np.bincount(leaf_id, minlength=2))
    assert 0.1 * n < small.sum() < 0.3 * n

    def blocks(rows):  # the rule itself: tests/test_hist_pallas_contract.py
        return int(hp.blocks_multiplied(
            hp.pass_counts(jnp.asarray(rows)), bins.shape, 128))

    every, packed = blocks(np.ones(n, bool)), blocks(small)
    assert int(tree.hist_blocks) == every + packed
    # the root's two whole tiles took the dense product and its ragged
    # third packed its 300 rows; every tile of the second pass packed

    def in_packed_tiles(rows):
        return int(hp.blocks_packed(
            hp.pass_counts(jnp.asarray(rows)), bins.shape, 128))

    assert in_packed_tiles(np.ones(n, bool)) == 3
    assert in_packed_tiles(small) == packed
    assert int(tree.hist_blocks_packed) == 3 + packed
    assert packed == sum(
        -(-int(small[i:i + hp.ROW_TILE].sum()) // hp.SUB_BLOCK)
        for i in range(0, n, hp.ROW_TILE)) < every / 3
    # the other routes run no kernel and count none
    tree, _ = _grow(*_toy(), num_leaves=2)
    assert int(tree.hist_blocks) == int(tree.hist_blocks_packed) == 0


def _booster(mode, n=4000):
    rng = np.random.RandomState(11)
    x = rng.randn(n, 8)
    y = ((x @ rng.randn(8) + rng.randn(n)) > 0).astype(float)
    return lgb.Booster({"objective": "binary", "num_leaves": 15,
                        "tree_growth_mode": mode, "verbosity": -1},
                       lgb.Dataset(x, label=y)), n


COUNTERS = ("train_hist_passes_total", "train_hist_rows_streamed_total",
            "train_hist_rows_needed_total",
            "train_hist_blocks_multiplied_total",
            "train_hist_blocks_packed_total")


def test_three_updates_and_a_flush_move_the_three_counters():
    from chipbench.harness import work

    obs.reset()
    bst, n = _booster("rounds")
    for _ in range(3):
        bst.update()
    g = bst._gbdt
    pending = list(g._pending)
    passes = [int(arrays.hist_passes) for arrays, _, _ in pending]
    assert len(passes) == 3 and all(p >= 2 for p in passes)
    # nothing is counted on the hot path: the flush brings the trees over
    assert all(obs.counter(c).value == 0 for c in COUNTERS)
    trees = g.models
    got = {c: obs.counter(c).value for c in COUNTERS}
    assert got["train_hist_passes_total"] == sum(passes)
    assert got["train_hist_rows_streamed_total"] == sum(passes) * n
    # the CPU's route runs no kernel: the counter is there and reads 0
    assert got["train_hist_blocks_multiplied_total"] == sum(
        int(arrays.hist_blocks) for arrays, _, _ in pending) == 0
    assert got["train_hist_blocks_packed_total"] == sum(
        int(arrays.hist_blocks_packed) for arrays, _, _ in pending) == 0
    assert got["train_hist_rows_needed_total"] == sum(
        work.tree_rows(t, n) for t in trees)
    assert obs.counter("train_boost_rounds_total").value == 3
    assert trees[0].smaller_child_rows() == work.tree_rows(trees[0], n) - n
    g.models  # a second flush has nothing pending and counts nothing
    assert {c: obs.counter(c).value for c in COUNTERS} == got


def test_another_grower_counts_no_pass():
    obs.reset()
    bst, _ = _booster("strict", n=1000)
    bst.update()
    assert bst._gbdt.models[0].num_leaves > 1
    assert all(obs.counter(c).value == 0 for c in COUNTERS)


# ---------------------------------------------------------------------------
# 3. from a trace to seconds per phase
# ---------------------------------------------------------------------------

def _recorded():
    """One chip, two modules.  The grower's holds a ``while`` of 100 us with
    a ``conditional`` of 60 us nested in it, which holds the kernel (40 us)
    and a layout copy without an op_name (10 us); the other module is an
    eager add.  Times in ns."""
    grow, add = "jit__grow_fast_impl(7)", "jit_add(9)"
    ops = [
        ["%fusion.1 = f32[8] fusion(%p)", 0, 20_000],
        ["%while.2 = (s32[]) while(%t), body=%b", 30_000, 100_000],
        ["%fusion.3 = s32[8] fusion(%x)", 30_000, 15_000],
        ["%conditional.4 = f32[] conditional(%c)", 50_000, 60_000],
        ["%_hist_pallas_raw.5 = f32[8,8] custom-call(%a)", 55_000, 40_000],
        ["%copy.6 = f32[8,8] copy(%h)", 96_000, 10_000],
        ["%fusion.7 = f32[8] fusion(%y)", 115_000, 10_000],
        ["%add.1 = f32[8] add(%a, %b)", 200_000, 5_000],
    ]
    modules = [[grow, 0, 130_000], [add, 199_000, 7_000]]
    prefix = "jit(_grow_fast_impl)/"
    op_names = {
        grow: {"fusion.1": prefix + "grow.root/hist.payload/mul",
               "while.2": prefix + "while",
               "fusion.3": prefix + "while/body/grow.partition/select_n",
               "conditional.4": prefix + "while/body/cond",
               "_hist_pallas_raw.5": prefix + "while/body/cond/branch_1_fun/"
               "hist.kernel/jit(_hist_pallas_raw)/pallas_call",
               "fusion.7": prefix + "while/body/grow.split_search/argmax"},
        add: {"add.1": "jit(add)/add"},
    }
    return {"chips": [{"ops": ops, "modules": modules}],
            "op_names": op_names, "steps": 1}


def test_self_times_of_a_nested_while_sum_to_busy_time():
    r = profiling.phase_seconds(_recorded())
    us = {k: round(v * 1e6, 6) for k, v in r["phases"].items() if v}
    assert us == {"hist.payload": 20.0, "grow.partition": 15.0,
                  "hist.kernel": 40.0, "grow.split_search": 10.0}
    # the containers' own time and the copy: 100-15-60-10, 60-40-10, 10
    assert r["unscoped_s"] == pytest.approx((15 + 10 + 10) * 1e-6)
    assert dict(r["unscoped_ops"]) == pytest.approx(
        {"while": 15e-6, "conditional": 10e-6, "copy": 10e-6})
    assert r["outside_grower"] == pytest.approx({"jit_add": 5e-6})
    assert r["busy_s"] == pytest.approx(125e-6)  # 20 + 100 + 5
    assert (sum(r["phases"].values()) + r["unscoped_s"]
            + sum(r["outside_grower"].values())) == pytest.approx(r["busy_s"])
    assert r["grower_modules"] == ["jit__grow_fast_impl"]
    assert (r["trees"], r["host_steps"], r["chips"]) == (1, 1, 1)
    assert set(r["phases"]) == set(profiling.DEVICE_PHASES)


def _varint(n):
    out = bytearray()
    while True:
        b, n = n & 0x7F, n >> 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num, payload):
    """One protobuf field: an int goes as a varint, bytes length-delimited."""
    if isinstance(payload, int):
        return _varint(num << 3) + _varint(payload)
    return _varint(num << 3 | 2) + _varint(len(payload)) + payload


def test_op_names_are_read_from_the_hlo_in_the_metadata_plane(tmp_path):
    """A hand-made XSpace: the metadata plane with one module's HloProto,
    laid out as the TPU's traces are (field numbers of xplane.proto and
    hlo.proto), beside a device plane that is skipped."""
    def instruction(name, op_name=None):
        meta = b"" if op_name is None else _field(
            7, _field(1, b"mul") + _field(2, op_name.encode()))
        return _field(2, _field(1, name.encode()) + _field(2, b"fusion")
                      + meta)

    module = _field(1, b"jit__grow_fast_impl") + _field(3, (
        _field(1, b"main")
        + instruction("fusion.1", "jit(_grow_fast_impl)/grow.slots/select_n")
        + instruction("copy.2")))
    hlo_proto = _field(1, module)
    event_meta = (_field(1, 7) + _field(2, b"jit__grow_fast_impl(7)")
                  + _field(5, _field(1, 1) + _field(6, hlo_proto)))
    plane = (_field(1, 3) + _field(2, b"/host:metadata")
             + _field(4, _field(1, 7) + _field(2, event_meta)))
    other = _field(1, 1) + _field(2, b"/device:TPU:0")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, other) + _field(1, plane))
    assert profiling.trace_op_names(str(path)) == {"jit__grow_fast_impl(7)": {
        "fusion.1": "jit(_grow_fast_impl)/grow.slots/select_n"}}


def test_device_phase_seconds_reads_a_live_trace(tmp_path):
    """On the CPU backend a trace has no TPU plane, so there is no device
    second to report (and none is): the reduction still reads the file, the
    HLO of the modules that ran, and a step per ``boost_round``."""
    obs.reset()
    profiling.install_step_annotations()
    bst, _ = _booster("rounds", n=1000)
    bst.update()
    with profiling.device_trace(str(tmp_path)):
        bst.update()
        bst.update()
        jax.block_until_ready(bst._gbdt._score)
    r = profiling.log_device_phases(str(tmp_path))
    assert r["host_steps"] == 2 and r["trees"] == 0
    assert r["chips"] == 0 and r["busy_s"] == 0.0
    assert set(r["phases"]) == set(profiling.DEVICE_PHASES)
    with pytest.raises(FileNotFoundError):
        profiling.device_phase_seconds(str(tmp_path / "nothing"))
