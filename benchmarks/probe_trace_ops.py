"""Round-7 probe: traced-op counts of the growers' round bodies.

The r5 compact-pair rework took the primary fused-step warmup from ~137 s
to ~240 s (docs/NEXT.md lever 4).  Compile time on the remote Mosaic
toolchain scales with traced-op count far more than with FLOPs, so this
probe makes the trace size itself a measurable artifact: jaxpr equation
counts for grow_tree_fast (the fused step's dominant component) at
representative configs.  bench.py records the
primary-config count in every artifact (trace_eqns) so the next
regression is caught structurally, off-chip, before it costs a 4-minute
warmup on the chip.

Usage: python benchmarks/probe_trace_ops.py [leaf_tile ...]
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def count_eqns(jaxpr) -> int:
    """Total equations including sub-jaxprs (scan/while/cond bodies)."""
    total = 0
    todo = [jaxpr]
    while todo:
        j = todo.pop()
        total += len(j.eqns)
        for eqn in j.eqns:
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    todo.append(v.jaxpr)
                elif isinstance(v, (list, tuple)):
                    todo.extend(x.jaxpr for x in v if hasattr(x, "jaxpr"))
    return total


def fast_grower_eqns(n=4096, f=28, num_leaves=31, num_bins=64, leaf_tile=8):
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.split import SplitParams
    from lightgbm_tpu.ops.treegrow_fast import grow_tree_fast

    jaxpr = jax.make_jaxpr(
        lambda b, g, h, m, sw, fm, nb, mb: grow_tree_fast(
            b, g, h, m, sw, fm, nb, mb,
            num_leaves=num_leaves, num_bins=num_bins,
            params=SplitParams(), leaf_tile=leaf_tile, use_pallas=False)
    )(
        jnp.zeros((n, f), jnp.int16), jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32), jnp.ones((n,), bool),
        jnp.ones((n,), jnp.float32), jnp.ones((f,), bool),
        jnp.full((f,), num_bins, jnp.int32), jnp.full((f,), -1, jnp.int32),
    )
    return count_eqns(jaxpr.jaxpr)


def main():
    tiles = [int(t) for t in sys.argv[1:]] or [8, 16]
    for t in tiles:
        print(f"grow_tree_fast   leaf_tile={t:2d}: "
              f"{fast_grower_eqns(leaf_tile=t):6d} eqns", flush=True)


if __name__ == "__main__":
    main()
