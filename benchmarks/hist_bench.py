"""Microbenchmark of histogram strategies on the current backend.

Usage: python benchmarks/hist_bench.py [N] [F] [B]
Measures ms/histogram for each strategy and checks correctness vs a numpy
reference.  Drives the measured strategy table in ops/histogram.py.
"""

import sys
import time

import numpy as np

import jax
import jax.numpy as jnp


def timeit(fn, *args, reps=10):
    # Sync by host transfer.  Dispatch `reps` times back-to-back (they
    # serialize on device) and sync once, so the blocking pull amortizes
    # over reps.
    out = fn(*args)
    _ = np.asarray(out).ravel()[0]
    t0 = time.perf_counter()
    for _i in range(reps):
        out = fn(*args)
    host = np.asarray(out)
    dt = (time.perf_counter() - t0) / reps * 1e3
    return dt, host


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    f = int(sys.argv[2]) if len(sys.argv) > 2 else 28
    b = int(sys.argv[3]) if len(sys.argv) > 3 else 256

    rng = np.random.RandomState(0)
    bins = rng.randint(0, b, size=(n, f)).astype(np.int32)
    grad = rng.randn(n).astype(np.float32)
    hess = rng.rand(n).astype(np.float32)
    mask = (rng.rand(n) < 0.7)

    # numpy reference (channel-first (3, F, B) — package layout)
    ref = np.zeros((3, f, b), np.float64)
    m = mask.astype(np.float64)
    for j in range(f):
        ref[0, j] = np.bincount(bins[:, j], weights=grad * m, minlength=b)
        ref[1, j] = np.bincount(bins[:, j], weights=hess * m, minlength=b)
        ref[2, j] = np.bincount(bins[:, j], weights=m, minlength=b)

    db = jnp.asarray(bins)
    dg = jnp.asarray(grad)
    dh = jnp.asarray(hess)
    dm = jnp.asarray(mask)

    from lightgbm_tpu.ops.histogram import histogram_scatter, histogram_onehot_matmul
    from lightgbm_tpu.ops import hist_pallas as hp

    results = {}

    def check(name, out, tol):
        out = np.asarray(out, np.float64)
        err = np.max(np.abs(out - ref) / (np.abs(ref) + 1.0))
        ok = err < tol
        print(f"  {name}: rel_err={err:.2e} {'OK' if ok else 'FAIL'}")
        return ok

    variants = sys.argv[4].split(",") if len(sys.argv) > 4 else [
        "onehot_xla", "direct_f32_512", "direct_bf16_512", "q8_512", "multi16_512",
    ]

    refq = None
    for name in variants:
        try:
            if name == "scatter":
                fn = jax.jit(lambda: histogram_scatter(db, dg, dh, dm, b))
                ms, out = timeit(fn, reps=3)
                results[name] = ms
                check(name, out, 1e-4)
            elif name == "onehot_xla":
                fn = jax.jit(lambda: histogram_onehot_matmul(db, dg, dh, dm, b))
                ms, out = timeit(fn, reps=3)
                results[name] = ms
                check(name, out, 1e-4)
            elif name.startswith("q8_"):
                rt = int(name.split("_")[1])
                gq = jnp.asarray(np.clip(np.round(grad * 15), -31, 31).astype(np.int8))
                hq = jnp.asarray(np.clip(np.round(hess * 31), 0, 31).astype(np.int8))
                fn = jax.jit(
                    lambda r=rt: hp.histogram_pallas_quantized(
                        db, gq, hq, dm, b, row_tile=r
                    )
                )
                ms, out = timeit(fn)
                results[name] = ms
                if refq is None:
                    refq = np.zeros((3, f, b), np.int64)
                    mq = mask.astype(np.int64)
                    gqn = np.asarray(gq, np.int64)
                    hqn = np.asarray(hq, np.int64)
                    for j in range(f):
                        refq[0, j] = np.bincount(bins[:, j], weights=gqn * mq, minlength=b)
                        refq[1, j] = np.bincount(bins[:, j], weights=hqn * mq, minlength=b)
                        refq[2, j] = np.bincount(bins[:, j], weights=mq, minlength=b)
                exact = np.array_equal(np.asarray(out, np.int64), refq)
                print(f"  {name}: exact={'OK' if exact else 'FAIL'}")
            elif name.startswith("multi"):
                # multi-leaf pass: slot 0 = the mask, other slots empty; slot
                # 0's result must equal the single-leaf histogram
                tile = int(name[5:].split("_")[0])
                rt = int(name.split("_")[1])
                slot = jnp.where(dm, 0, -1).astype(jnp.int32)
                fn = jax.jit(
                    lambda t=tile, r=rt: hp.histogram_pallas_multi(
                        db, dg, dh, slot >= 0, jnp.maximum(slot, 0), 0, t, b,
                        precision="f32", row_tile=r,
                    )[0]
                )
                ms, out = timeit(fn)
                results[name] = ms
                check(name, out, 1e-4)
            else:
                _, prec, rt = name.split("_")
                fn = jax.jit(
                    lambda p=prec, r=int(rt): hp.histogram_pallas(
                        db, dg, dh, dm, b, precision=p, row_tile=r
                    )
                )
                ms, out = timeit(fn)
                results[f"pallas_{name}"] = ms
                check(name, out, 5e-3 if prec == "bf16" else 1e-4)
        except Exception as e:
            print(f"  {name}: ERROR {type(e).__name__}: {str(e)[:300]}", flush=True)

    print(f"\nN={n} F={f} B={b} on {jax.devices()[0].platform}")
    for k, v in sorted(results.items(), key=lambda kv: kv[1]):
        print(f"  {k:32s} {v:8.2f} ms")


if __name__ == "__main__":
    main()
