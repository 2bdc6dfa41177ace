"""Trace-size budget: the growers' round-body jaxpr must stay small.

The r5 warmup regression (~137 s -> ~240 s fused-step compile on the
remote toolchain, docs/NEXT.md lever 4) motivated making trace size an
artifact metric (bench.py records trace_eqns per run); this test is the
tier-1 half — a generous ceiling that catches structural trace bloat
(an accidentally unrolled loop, a per-leaf-tile op explosion) at PR time
without being brittle to jax version drift.  Measured round-7 baselines:
grow_tree_fast tile8 ~1.74k eqns, tile16 ~2.23k
(benchmarks/probe_trace_ops.py)."""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks.probe_trace_ops import count_eqns, fast_grower_eqns  # noqa: E402


def test_fast_grower_trace_budget():
    assert fast_grower_eqns(leaf_tile=8) < 2300
    assert fast_grower_eqns(leaf_tile=16) < 3000


def test_fast_grower_round_body_is_a_device_loop():
    """The round body is traced once: the trace may not grow with the
    leaf budget, the rows or the bins (an unrolled round, a per-leaf or a
    per-bin Python loop would show here long before the ceiling above)."""
    base = fast_grower_eqns(leaf_tile=8)
    assert fast_grower_eqns(leaf_tile=8, num_leaves=255) == base
    assert fast_grower_eqns(leaf_tile=8, n=16384) == base
    assert fast_grower_eqns(leaf_tile=8, num_bins=256) == base


def test_count_eqns_descends_subjaxprs():
    import jax
    import jax.numpy as jnp

    def f(x):
        return jax.lax.fori_loop(0, 4, lambda i, a: a * 2 + i, x)

    j = jax.make_jaxpr(f)(jnp.float32(1.0))
    assert count_eqns(j.jaxpr) > len(j.jaxpr.eqns)
