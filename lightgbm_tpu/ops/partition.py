"""Leaf-ordered row partition maintenance.

The TPU analogue of the reference's DataPartition (src/treelearner/
data_partition.hpp): rows are kept PHYSICALLY grouped by leaf so histogram
passes can be windowed to [start, start+count) ranges whose cost is
proportional to live rows instead of N (docs/PERF_NOTES.md round-3 plan).

The reference partitions with per-thread index buffers; here a round's
splits are applied as ONE fixed-shape stable permutation over the full row
order.  Two interchangeable implementations sit behind
:func:`partition_rows`:

* :func:`stable_partition_ranges` (XLA, this module): segment-relative
  cumulative sums + one permutation scatter.  Exact, shape-stable, runs
  everywhere — but O(N) per round (measured ~41 ms at 1M rows on a v5e)
  even when the round only splits a few small segments.
* ``ops/partition_pallas.py``: a Pallas kernel that touches ONLY the
  split segments (the in-place ``DataPartition::Split`` analogue), used
  by the fused windowed round on TPU; its raw output is merged back over
  the untouched positions here with the ``seg_id`` mask the admit phase
  already computed.  v2 keeps its buffers HBM-resident and streams
  per-chunk DMA, so there is NO row cap — the kernel is taken at any N
  (the v1 650k-row VMEM-staging fallback is deleted).

Both return identical results; tests/test_partition.py pins the Pallas
kernel (interpret mode) against the XLA path on the same fixtures.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def partition_rows(
    order: jnp.ndarray,  # (N,) i32 — current row ids, grouped by leaf
    seg_id: jnp.ndarray,  # (N,) i32 — split-segment id per POSITION, -1 = not split
    seg_start: jnp.ndarray,  # (S,) i32
    seg_len: jnp.ndarray,  # (S,) i32
    go_left: jnp.ndarray,  # (N,) bool per POSITION
    *,
    use_pallas: bool = False,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Apply a round's stable segment partition; returns
    ``(new_order, left_counts)``.

    ``use_pallas`` selects the segment-proportional TPU kernel
    (``interpret=True`` runs the same kernel through the Pallas
    interpreter for off-chip tests); otherwise the O(N) XLA permutation.
    The choice is made at trace time — both paths are pure functions of
    the same inputs with identical outputs.  The v2 kernel is
    HBM-resident with per-chunk DMA staging, so it is taken at ANY row
    count (v1's >650k silent XLA fallback is gone).  No grower passes
    ``use_pallas=True`` today: Mosaic refuses the kernel on the chip
    (ops/partition_pallas.py, "Validation status").
    """
    if use_pallas or interpret:
        from ..utils import degrade as _degrade
        from .partition_pallas import partition_pallas_segments

        def _pallas():
            raw, left_counts = partition_pallas_segments(
                order, seg_start, seg_len, go_left, interpret=interpret)
            return jnp.where(seg_id >= 0, raw, order), left_counts

        if interpret:
            # correctness harness: always run the kernel (ignore the
            # degradation registry) and surface every failure — a silent
            # fallback here would quietly test XLA against XLA
            from ..utils import faults as _faults

            _faults.maybe_fail("pallas_partition")
            return _pallas()

        # a kernel failure is caught ONCE, logged, and permanently degrades
        # this process to the XLA permutation — same results, O(N) instead
        # of segment-proportional (utils/degrade.py)
        return _degrade.run_with_fallback(
            _degrade.PARTITION, _pallas,
            lambda: stable_partition_ranges(
                order, seg_id, seg_start, seg_len, go_left),
            fault_site="pallas_partition")
    return stable_partition_ranges(order, seg_id, seg_start, seg_len, go_left)


@jax.jit
def stable_partition_ranges(
    order: jnp.ndarray,  # (N,) i32 — current row ids, grouped by leaf
    seg_id: jnp.ndarray,  # (N,) i32 — split-segment id per POSITION, -1 = not split
    seg_start: jnp.ndarray,  # (S,) i32 — start position of each segment
    seg_len: jnp.ndarray,  # (S,) i32 — length of each segment
    go_left: jnp.ndarray,  # (N,) bool per POSITION — split decision
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Stably partition every segment of `order` by `go_left` in one shot.

    Returns (new_order, left_counts (S,)).  Positions outside all segments
    are untouched.  reference: DataPartition::Split, vectorized over all of
    a round's split leaves at once.
    """
    n = order.shape[0]
    in_seg = seg_id >= 0
    sid = jnp.maximum(seg_id, 0)

    # segment-relative stable ranks via global cumsums restarted per segment:
    # rank_left(p) = (#left in segment up to p) - (#left in segment before start)
    left_f = (in_seg & go_left).astype(jnp.int32)
    right_f = (in_seg & ~go_left).astype(jnp.int32)
    cl = jnp.cumsum(left_f)
    cr = jnp.cumsum(right_f)
    start_pos = seg_start[sid]  # (N,) start position of my segment
    cl0 = jnp.where(start_pos > 0, cl[jnp.maximum(start_pos - 1, 0)], 0)
    cr0 = jnp.where(start_pos > 0, cr[jnp.maximum(start_pos - 1, 0)], 0)
    rank_l = cl - cl0  # 1-based among left rows of my segment
    rank_r = cr - cr0
    # per-segment left counts from the cumsum endpoints — O(S), and the
    # reason seg_len is a parameter
    seg_end = seg_start + jnp.maximum(seg_len - 1, 0)
    cl0_seg = jnp.where(seg_start > 0, cl[jnp.maximum(seg_start - 1, 0)], 0)
    n_left_seg = jnp.where(seg_len > 0, cl[seg_end] - cl0_seg, 0).astype(jnp.int32)

    dest = jnp.where(
        go_left,
        start_pos + rank_l - 1,
        start_pos + n_left_seg[sid] + rank_r - 1,
    )
    pos = jnp.arange(n, dtype=jnp.int32)
    dest = jnp.where(in_seg, dest, pos)
    new_order = jnp.zeros_like(order).at[dest].set(order)
    return new_order, n_left_seg
