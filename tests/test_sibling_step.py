"""The rounds grower's sibling step (``ops/treegrow_fast.sibling_step``)
against the gather-and-scatter form it replaced, and the grower's lowered
program against that form coming back.

The step reads and writes the ``(L, 3, F, B)`` per-leaf state a leaf at a
time.  Its results must be the replaced form's bit for bit: the same float32
subtraction of the same operands, placed in the same leaves, and nothing
written where a slot is idle, even where the clamped index of an idle slot
is leaf 0 and leaf 0 is an active slot's parent in the same pass.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.ops.split import SplitParams
from lightgbm_tpu.ops.treegrow_fast import _grow_fast_impl, sibling_step

L, B = 31, 16


@jax.jit
def gather_scatter_step(hist, fresh, slot_left, slot_right, slot_small_left):
    """The form the step replaced: gather the parents, subtract, select,
    scatter both children, idle slots dropped.  -> (hist, children)."""
    n = hist.shape[0]
    active = slot_left >= 0
    sl = jnp.clip(slot_left, 0, n - 1)
    sr = jnp.clip(slot_right, 0, n - 1)
    big = hist[sl] - fresh
    sml = slot_small_left[:, None, None, None]
    left = jnp.where(sml, fresh, big)
    right = jnp.where(sml, big, fresh)
    lpos = jnp.where(active, sl, 2 * n)
    rpos = jnp.where(active, sr, 2 * n)
    hist = hist.at[lpos].set(left, mode="drop").at[rpos].set(right,
                                                             mode="drop")
    return hist, jnp.concatenate([left, right])


def slots(case, tile, rng):
    """A pass's slots as ``round_body`` lays them out: the admitted splits
    take slots 0 to k - 1 in rank order, each the parent's leaf id (its left
    child keeps it) and a new right id past the leaves already grown; the
    rest idle (-1)."""
    grown = L - tile  # leaves before the round: room for every slot's right
    if case == "all-active":
        k, parents = tile, rng.choice(grown, tile, replace=False)
    elif case == "some-idle":
        k = tile // 2
        parents = rng.choice(np.arange(1, grown), k, replace=False)
    else:  # leaf 0 splits in a pass that also has idle slots (clamped to 0)
        k = tile - 3
        parents = np.concatenate(
            [[0], rng.choice(np.arange(1, grown), k - 1, replace=False)])
        rng.shuffle(parents)
    left = np.full(tile, -1, np.int32)
    right = np.full(tile, -1, np.int32)
    left[:k] = parents
    right[:k] = grown + np.arange(k)
    return left, right


@pytest.mark.parametrize("small", ["left", "right", "mixed"])
@pytest.mark.parametrize("case", ["all-active", "some-idle",
                                  "leaf0-parent-beside-idle"])
@pytest.mark.parametrize("tile", [8, 10])
@pytest.mark.parametrize("f", [5, 300], ids=["narrow", "wide"])
def test_sibling_step_is_the_gather_scatter_form_bit_for_bit(f, tile, case,
                                                             small):
    rng = np.random.default_rng([f, tile, len(case), len(small)])
    hist = jnp.asarray(rng.standard_normal((L, 3, f, B)), jnp.float32)
    fresh = jnp.asarray(rng.standard_normal((tile, 3, f, B)), jnp.float32)
    left, right = slots(case, tile, rng)
    small_left = {"left": np.ones(tile, bool), "right": np.zeros(tile, bool),
                  "mixed": rng.random(tile) < 0.5}[small]
    a = (hist, fresh, jnp.asarray(left), jnp.asarray(right),
         jnp.asarray(small_left))
    want_hist, want_children = gather_scatter_step(*a)
    got_hist, got_children = jax.jit(sibling_step)(*a)
    assert np.array_equal(np.asarray(got_hist), np.asarray(want_hist))
    # the children the split search reads, active slots and idle alike
    assert np.array_equal(np.asarray(got_children), np.asarray(want_children))
    # an idle slot wrote nothing: every leaf no active slot names is as it was
    touched = set(left[left >= 0]) | set(right[right >= 0])
    kept = [i for i in range(L) if i not in touched]
    assert np.array_equal(np.asarray(got_hist)[kept], np.asarray(hist)[kept])


def _shapes(text: str) -> dict:
    """{instruction: its result's shape} from HLO text."""
    return dict(re.findall(r"^\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])",
                           text, re.M))


@pytest.mark.parametrize("variant", ["plain", "forced", "intermediate"])
def test_no_gather_or_scatter_on_the_whole_state(variant):
    """The rounds grower as lowered, before XLA optimises it: no gather and
    no scatter takes the ``(L, 3, F, B)`` state as its operand (at 2,000
    features XLA:TPU lowered the gather as slices of the whole operand, a
    read and a write of all 1.56 GB every pass).  Forced splits and
    intermediate monotone bounds read the whole state too, so they are
    held as well."""
    n, f, leaves, bins = 512, 6, 15, 16
    s = jax.ShapeDtypeStruct
    args = (s((n, f), jnp.int16), s((n,), jnp.float32), s((n,), jnp.float32),
            s((n,), jnp.bool_), s((n,), jnp.float32), s((f,), jnp.bool_),
            s((f,), jnp.int32), s((f,), jnp.int32))
    kw = dict(num_leaves=leaves, num_bins=bins, params=SplitParams(),
              leaf_tile=4, use_pallas=False)
    if variant == "forced":
        kw.update(forced_leaf=s((2,), jnp.int32),
                  forced_feature=s((2,), jnp.int32),
                  forced_bin=s((2,), jnp.int32), n_forced=2)
    if variant == "intermediate":
        kw.update(monotone_constraints=s((f,), jnp.int32),
                  monotone_method="intermediate")
    text = _grow_fast_impl.lower(*args, **kw).as_text(dialect="hlo")
    shapes = _shapes(text)
    state = f"f32[{leaves},3,{f},{bins}]"
    assert state in shapes.values()  # the state is in the program
    ops = re.findall(r" (gather|scatter)\(%?([\w.\-]+)", text)
    assert ops  # the grower's small tables are still gathered and scattered
    on_state = [(op, arg) for op, arg in ops if shapes.get(arg) == state]
    assert not on_state, on_state
