"""The rounds grower's row partition alone (ops/treegrow_fast.py round_body):
a round's sweeps over the feature-major shadow, in three forms, timed on the
chip or, with ``--compile-only``, compiled for a described v5e (fusions and
XLA's bytes accessed; no chip, no time).

    flat      (F, N) shadow, (N,) leaf ids: the grower before PR 31
    tiled     (F, N/C, C) shadow, (N/C, C) leaf ids: the grower since
    tiled_1d  (F, N/C, C) shadow, (N,) leaf ids: the shadow alone
    tiled4    (F, N/C, C/128, 128) shadow, (N/C, C/128, 128) leaf ids: a row
              tile is whole (8, 128) tiles, so N/C pads to no sublane

    python benchmarks/probe_partition_sweep.py [--compile-only]

One JSON line a case: milliseconds a round by the host's clock around
``ROUNDS`` rounds in one dispatch (best of five), the operations of a round
that read the shadow and XLA's bytes accessed a round.  PERF.md section 6, PR 31, has what it read.
"""

import argparse
import json
import os
import re
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp

ROUNDS = 40
SHAPES = {"higgs": (10_500_000, 28, 8), "epsilon": (400_000, 2000, 10)}


def sweep(bins_t, lid, feat, thr, miss, dleft, leaf, right, live, flat_ids):
    """One round: what round_body's slot loop does to the leaf ids."""
    out = lid
    for r in range(feat.shape[0]):
        col = jax.lax.dynamic_index_in_dim(bins_t, feat[r], axis=0,
                                           keepdims=False).astype(jnp.int32)
        if flat_ids and col.ndim > 1:
            col = col.reshape(-1)[:lid.shape[0]]
        gl = jnp.where(col == miss[r], dleft[r], col <= thr[r])
        out = jnp.where(live[r] & (lid == leaf[r]) & ~gl, right[r], out)
    return out


def build(form, n, f, k, c):
    """-> (jitted ROUNDS rounds and one round, of (bins_t, lid, key); the
    shapes of bins_t and lid)."""
    tiles = -(-n // c)
    tile = (c // 128, 128) if form == "tiled4" else (c,)
    bshape = (f, n) if form == "flat" else (f, tiles, *tile)
    ishape = (tiles, *tile) if form in ("tiled", "tiled4") else (n,)

    def rounds(bins_t, lid, key, count=ROUNDS):
        def body(i, lid):
            ks = jax.random.split(jax.random.fold_in(key, i), 3)
            feat = jax.random.randint(ks[0], (k,), 0, f)
            thr = jax.random.randint(ks[1], (k,), 0, 255)
            leaf = jax.random.randint(ks[2], (k,), 0, i * k + 1)
            right = i * k + 1 + jnp.arange(k)
            return sweep(bins_t, lid, feat, thr, jnp.full((k,), 255),
                         thr % 2 == 0, leaf, right, thr >= 0,
                         form == "tiled_1d")
        if count == 1:
            return body(1, lid)
        return jax.lax.fori_loop(0, count, body, lid)

    def one(bins_t, lid, key):  # a round alone: what XLA makes of it
        return rounds(bins_t, lid, key, 1)

    return jax.jit(rounds), jax.jit(one), bshape, ishape


def round_fusions(compiled):
    """Of one compiled round: the operations that read the shadow, and XLA's
    bytes accessed."""
    names = re.findall(r"%([\w.\-]+) = \S+ [\w\-]+\([^)]*%bins_t",
                       compiled.as_text())
    cost = compiled.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    return names, cost.get("bytes accessed", 0.0)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--compile-only", action="store_true")
    args = ap.parse_args()
    sharding = None
    if args.compile_only:
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
        sharding = SingleDeviceSharding(topo.devices[0])
    elif jax.devices()[0].platform != "tpu":
        raise SystemExit(3)
    cases = [(s, "flat", 2048) for s in SHAPES]
    cases += [(s, form, c) for s in SHAPES
              for form, cs in (("tiled", (512, 1024, 2048, 8192)),
                               ("tiled_1d", (2048,)), ("tiled4", (2048,)))
              for c in cs]
    for shape, form, c in cases:
        n, f, k = SHAPES[shape]
        fn, one, bshape, ishape = build(form, n, f, k, c)
        key = jax.random.PRNGKey(31)
        if args.compile_only:
            def s(sh, dt):
                return jax.ShapeDtypeStruct(sh, dt, sharding=sharding)
            alone = one.lower(s(bshape, jnp.int16), s(ishape, jnp.int32),
                              s(key.shape, key.dtype)).compile()
            ms = None
        else:
            bins_t = jax.random.randint(key, bshape, 0, 256, jnp.int32
                                        ).astype(jnp.int16)
            lid = jnp.zeros(ishape, jnp.int32)
            alone = one.lower(bins_t, lid, key).compile()
            compiled = fn.lower(bins_t, lid, key).compile()
            compiled(bins_t, lid, key).block_until_ready()
            best = float("inf")
            for _ in range(5):
                t0 = time.perf_counter()
                compiled(bins_t, lid, key).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            ms = best * 1e3 / ROUNDS
            del bins_t, lid
        names, nbytes = round_fusions(alone)
        print(json.dumps({"shape": shape, "form": form, "c": c,
                          "ms_per_round": ms, "reads_shadow": names,
                          "mb_per_round": nbytes / 1e6}), flush=True)


if __name__ == "__main__":
    main()
