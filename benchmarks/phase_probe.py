"""In-jit phase costing for the fast grower (round-2 perf campaign).

Every dispatch costs host time, so individual jit calls cannot be timed
meaningfully.  This probe wraps each candidate
phase in a fori_loop of K iterations inside ONE jit; true per-iteration
device cost = (total - dispatch_floor) / K.  Each body varies with the
loop index (cheaply) to defeat loop-invariant hoisting.
"""

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

import lightgbm_tpu.ops.hist_pallas as hp
from lightgbm_tpu.ops.split import SplitParams, find_best_split

K = 20


def timed(name, fn, reps=5):
    out = fn()
    np.asarray(out).ravel()[:1]
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    np.asarray(out).ravel()[:1]
    ms = (time.perf_counter() - t0) / reps * 1e3
    print(f"{name:26s} {ms:8.2f} ms total -> {(ms):7.2f}/call; per-iter ~{ms/K:6.2f} ms",
          flush=True)
    return ms


def main():
    n, F, B, L = 1_000_000, 28, 256, 31
    rng = np.random.RandomState(0)
    bins = jnp.asarray(rng.randint(0, B, size=(n, F)).astype(np.int16))
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    hess = jnp.asarray(rng.rand(n).astype(np.float32))
    mask = jnp.ones((n,), bool)
    leaf_id0 = jnp.asarray(rng.randint(0, 8, size=(n,)).astype(np.int32))
    hist16 = jnp.asarray(rng.rand(16, 3, F, B).astype(np.float32))
    params = SplitParams(min_data_in_leaf=20.0)
    nbpf = jnp.full((F,), B, jnp.int32)
    mbpf = jnp.full((F,), B - 1, jnp.int32)
    fmask = jnp.ones((F,), bool)

    which = sys.argv[1].split(",") if len(sys.argv) > 1 else [
        "floor", "pass", "payload", "partition", "slotloop", "eval",
    ]

    if "floor" in which:
        x = jnp.ones((8,))
        timed("dispatch floor", jax.jit(lambda: x + 1.0))

    if "pass" in which:
        @jax.jit
        def pass_loop():
            def body(i, acc):
                g = grad * (1.0 + i.astype(jnp.float32) * 1e-9)
                h = hp.histogram_pallas_multi(
                    bins, g, hess, mask, leaf_id0, 0, 8, B,
                    precision="f32", row_tile=1024)
                return acc + h[0, 0, 0, 0]
            return jax.lax.fori_loop(0, K, body, jnp.float32(0))
        timed("multi pass (x20 in jit)", pass_loop)

    if "payload" in which:
        @jax.jit
        def payload_loop():
            def body(i, acc):
                g = grad * (1.0 + i.astype(jnp.float32) * 1e-9)
                m = mask.astype(jnp.float32)
                gm = g * m
                hm = hess * m
                g_hi = gm.astype(jnp.bfloat16).astype(jnp.float32)
                h_hi = hm.astype(jnp.bfloat16).astype(jnp.float32)
                chans = [g_hi, h_hi, m, gm - g_hi, hm - h_hi, jnp.zeros_like(m)]
                base = jnp.stack(chans, axis=-1)
                onehot = (leaf_id0[:, None] == jnp.arange(8, dtype=jnp.int32)[None, :]).astype(jnp.float32)
                pay = (onehot[:, :, None] * base[:, None, :]).reshape(n, 48)
                return acc + pay[0, 0] + pay[-1, -1]
            return jax.lax.fori_loop(0, K, body, jnp.float32(0))
        timed("payload prep (x20)", payload_loop)

    if "partition" in which:
        @jax.jit
        def partition_loop():
            def body(i, lid):
                for r in range(8):
                    fcol = jax.lax.dynamic_index_in_dim(
                        bins, (i + r) % F, axis=1, keepdims=False
                    ).astype(jnp.int32)
                    gl = fcol <= 128
                    lid = jnp.where((lid == r) & ~gl, lid + 8, lid)
                return lid
            return jax.lax.fori_loop(0, K, body, leaf_id0)
        timed("partition 8-col (x20)", partition_loop)

    if "slotloop" in which:
        small_slot = jnp.asarray(rng.permutation(L)[:L].astype(np.int32))

        @jax.jit
        def slot_loop():
            def body(i, acc):
                lid = leaf_id0 + i * 0
                leaf_slot = jnp.full((n,), -1, jnp.int32)
                ss = jnp.where(small_slot >= i % 3, small_slot, -1)
                for r in range(8):
                    has_r = ss == r
                    leaf_r = jnp.argmax(has_r).astype(jnp.int32)
                    exists = jnp.any(has_r)
                    leaf_slot = jnp.where(exists & (lid == leaf_r), r, leaf_slot)
                return acc + leaf_slot[0] + leaf_slot[-1]
            return jax.lax.fori_loop(0, K, body, jnp.int32(0))
        timed("slot-map loop (x20)", slot_loop)

    if "eval" in which:
        def one(hist, nid):
            return find_best_split(
                hist, hist[0].sum(), hist[1].sum(), hist[2].sum(),
                nbpf, mbpf, params, feature_mask=fmask, categorical_mask=None,
                monotone_constraints=None,
                out_lo=jnp.float32(-jnp.inf), out_hi=jnp.float32(jnp.inf),
                rng_key=None, depth=jnp.float32(0),
                parent_output=jnp.float32(0), cegb_feature_penalty=None,
            )

        @jax.jit
        def eval_loop():
            def body(i, acc):
                h = hist16 * (1.0 + i.astype(jnp.float32) * 1e-9)
                bb = jax.vmap(one, in_axes=(0, 0))(h, jnp.arange(16))
                return acc + bb.gain.sum()
            return jax.lax.fori_loop(0, K, body, jnp.float32(0))
        timed("eval 16 slots (x20)", eval_loop)


if __name__ == "__main__":
    main()
