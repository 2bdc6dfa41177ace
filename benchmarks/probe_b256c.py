"""Dispatch-loop timing for the variants whose fori_loop jits were too large
to compile: dispatch R times back-to-back (they serialize on device), sync
once, subtract the per-dispatch host cost (docs/PERF_NOTES.md).  Coarser
than the in-jit probe but enough to rank."""

import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, "benchmarks")
from probe_b256b import make_cpack, N, F, B  # noqa: E402

DISPATCH_MS = 1.3
R = 30


def main():
    rng = np.random.RandomState(0)
    bins_np = rng.randint(0, B, size=(N, F)).astype(np.int16)
    pay_np = (rng.randn(N, 48) * 0.1).astype(np.float32)
    bins = jnp.asarray(bins_np)
    pay48 = jnp.asarray(pay_np)
    pay128 = jnp.asarray(np.pad(pay_np, ((0, 0), (0, 80))))
    pay_i8 = jnp.asarray(np.clip(np.round(pay_np / 0.02), -127, 127).astype(np.int8))

    cases = {
        "cpack4_base48": (make_cpack(4), pay48),  # control vs in-jit 7.6ms
        "cpack4_int8": (make_cpack(4, int8=True), pay_i8),
        "cpack1_nc128": (make_cpack(1, nc=128), pay128),
        "cpack4_nc128": (make_cpack(4, nc=128), pay128),
    }
    which = sys.argv[1].split(",") if len(sys.argv) > 1 else list(cases)
    for key in which:
        fn, pay = cases[key]
        try:
            out = fn(bins, pay)
            np.asarray(out).ravel()[:1]
        except Exception as e:  # noqa: BLE001
            print(f"{key:16s} FAILED: {type(e).__name__}: {str(e)[:160]}", flush=True)
            continue
        t0 = time.perf_counter()
        for _ in range(R):
            out = fn(bins, pay)
        np.asarray(out).ravel()[:1]
        total = (time.perf_counter() - t0) / R * 1e3
        print(f"{key:16s} per-pass ~{total - DISPATCH_MS:6.2f} ms "
              f"(raw {total:.2f})", flush=True)


if __name__ == "__main__":
    main()
