"""All-continuous data drawn as bins from the seed.

Equal-count binning of a continuous feature fills its bins about evenly, so
the binned matrix of such data *is* near-uniform draws over the bins.  This
generator draws the bins directly (one byte a value), gives bin ``b`` of
every feature a fixed raw value (the standard normal quantile midpoint
``Phi^-1((b + 0.5) / B)``), and labels each row with a seeded linear teacher
over the raw values plus noise, thresholded at 0 (the shape of ``bench.py``'s
generators).  The raw matrix is ``values[bins[:, f], f]`` and is never built
at full size.

The teacher's weights come from the configuration (``teacher_seed``), so
every ``--seed`` poses the same problem on other rows; the rows and the label
noise come from ``--seed``.  Chunks are drawn from children of one
``SeedSequence``, so the result does not depend on the number of threads.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from statistics import NormalDist

import numpy as np

CHUNK_VALUES = 1 << 24  # values drawn per chunk
THREADS = 4


def value_table(n_bins: int, n_features: int) -> np.ndarray:
    """``values[b, f]``: the raw value of bin ``b``, increasing in ``b``."""
    nd = NormalDist()
    q = np.array([nd.inv_cdf((b + 0.5) / n_bins) for b in range(n_bins)])
    return np.repeat(q[:, None], n_features, axis=1)


def teacher(config: dict) -> np.ndarray:
    p = config["datagen_params"]
    k = min(int(p["informative"]), int(config["features"]))
    return np.random.default_rng(int(p["teacher_seed"])).standard_normal(k)


def generate(config: dict, seed: int) -> dict:
    n, f = int(config["rows"]), int(config["features"])
    n_bins = int(config["params"]["max_bin"])
    if n_bins > 256:
        raise ValueError("quantile_bins draws one byte a value: max_bin <= 256")
    values = value_table(n_bins, f)
    w = teacher(config)
    k = len(w)
    noise = float(config["datagen_params"]["noise"])
    wv = (values[:, :k] * w[None, :]).T.ravel()  # [k * B], feature-major
    offs = (np.arange(k) * n_bins)[None, :]

    chunk = max(1, CHUNK_VALUES // f)
    starts = list(range(0, n, chunk))
    children = np.random.SeedSequence(int(seed)).spawn(len(starts))
    bins = np.empty((n, f), np.uint8)
    label = np.empty(n, np.float32)

    def draw(i: int) -> None:
        lo = starts[i]
        hi = min(lo + chunk, n)
        rng = np.random.Generator(np.random.PCG64(children[i]))
        b = rng.integers(0, n_bins, size=(hi - lo, f), dtype=np.uint8)
        bins[lo:hi] = b
        score = np.take(wv, b[:, :k].astype(np.intp) + offs).sum(axis=1)
        score += noise * rng.standard_normal(hi - lo)
        label[lo:hi] = score > 0

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(draw, range(len(starts))))
    return {"bins": bins, "label": label, "values": values, "n_bins": n_bins}


def raw_matrix(data: dict) -> np.ndarray:
    """The raw float matrix the bins stand for.  Only for small sizes (the
    test that ties this route to ``lgb.Dataset(raw)``)."""
    b = data["bins"]
    return np.take_along_axis(data["values"], b.astype(np.intp), axis=0)
