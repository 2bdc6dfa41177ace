"""Round-5 probe: locate the windowed grower's per-round FIXED cost.

r5 measured (WPROF, Epsilon 400k x 2000 x 256 x 255 leaves, int8):
admit+sync ~0.13 s/round, pass ~0.19 s at W=32768 (where the window work
itself is ~30 ms) — so ~0.15 s/round of the pass is fixed.  The
channel-first layout rework did NOT move it, so the padded-copy theory is
dead; suspects now are (a) undonated 1.5 GB hist-state buffers forcing
alloc+copy per jit call, (b) the full-state scatter/subtract chain, (c)
dispatch/arg plumbing.  Each probe isolates one.

Timing: host pull of a tiny slice (PERF_NOTES r4).
"""

import functools
import sys
import time

import numpy as np

sys.path.insert(0, "/root/repo")

import jax
import jax.numpy as jnp

L, F, B = 255, 2000, 256
REPS = 10


def timed(name, fn, *args):
    out = fn(*args)  # compile
    _ = np.asarray(jax.tree.leaves(out)[0].ravel()[:4])
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(*args)
    _ = np.asarray(jax.tree.leaves(out)[0].ravel()[:4])
    dt = (time.perf_counter() - t0) / REPS
    print(f"{name:44s} {dt*1e3:8.1f} ms/call", flush=True)
    return out


def timed_donated(name, fn, first, *rest):
    """fn donates arg 0: thread the output back as the next input."""
    out = fn(first, *rest)  # compile (donates `first`)
    _ = np.asarray(out.ravel()[:4])
    t0 = time.perf_counter()
    for _ in range(REPS):
        out = fn(out, *rest)
    _ = np.asarray(out.ravel()[:4])
    dt = (time.perf_counter() - t0) / REPS
    print(f"{name:44s} {dt*1e3:8.1f} ms/call", flush=True)


def main():
    hist = jnp.zeros((L, 3, F, B), jnp.float32)
    fresh = jnp.ones((16, 3, F, B), jnp.float32)
    small_pos = jnp.arange(16, dtype=jnp.int32) * 3
    idx = jnp.arange(L, dtype=jnp.int32)
    sib = jnp.clip(idx + 1, 0, L - 1)
    is_big = (idx % 2) == 0

    # (a) pure passthrough: cost of shipping the state through a jit
    @jax.jit
    def passthrough(h):
        return h + 0.0

    timed("state passthrough (copy 1.5 GB)", passthrough, hist)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def passthrough_don(h):
        return h + 0.0

    timed_donated("state passthrough DONATED", passthrough_don,
                  jnp.zeros_like(hist))

    # (b) the pass's hist-state op chain, undonated vs donated
    def chain(h, fr):
        h = h.at[small_pos].set(fr, mode="drop")
        big_sub = h[idx] - h[sib]
        return jnp.where(is_big[:, None, None, None], big_sub, h)

    timed("scatter+subtract chain", jax.jit(chain), hist, fresh)
    timed_donated("scatter+subtract chain DONATED",
                  functools.partial(jax.jit, donate_argnums=(0,))(chain),
                  jnp.zeros_like(hist), fresh)

    # (c) admit's parent snapshot
    def snapshot(h):
        return h.at[jnp.flip(small_pos)].set(h[:16], mode="drop")

    timed("parent snapshot scatter", jax.jit(snapshot), hist)
    timed_donated("parent snapshot DONATED",
                  functools.partial(jax.jit, donate_argnums=(0,))(snapshot),
                  jnp.zeros_like(hist))

    # (d) the fresh-leaf gather + batched search input slice
    fr_idx = jnp.arange(40, dtype=jnp.int32)

    @jax.jit
    def gather40(h):
        return h[fr_idx] * 2.0

    timed("hist[fr_idx] 40-slot gather", gather40, hist)


if __name__ == "__main__":
    main()
