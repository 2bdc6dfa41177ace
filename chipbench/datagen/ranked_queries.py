"""A graded query log: one table drawn once, its queries and columns put in
an order the seed picks.

The table comes from the configuration's ``table_seed``:

* **bins**: near-uniform over the bins, one byte a value, a chunk a child of
  one ``SeedSequence`` (``quantile_bins``' scheme and its value table).
* **group**: ``queries`` lengths from a log-normal of the mean
  ``rows / queries`` and ``query_sigma``, rounded, clipped to
  ``1..query_longest`` and then moved by single rows, in a seeded order and
  inside the clip, until they sum to ``rows`` exactly.
* **label**: a seeded linear teacher over the raw values plus noise (the
  teacher's weights from ``teacher_seed``), cut at its own quantiles so that
  the grades 0, 1, .. hold ``grade_shares`` of the rows.  With 90% of the rows
  at grade 0 every query of some length holds ties.

``--seed`` then orders the table: its queries in a seeded order, each query's
rows kept together and in their own order, and its feature columns in a
seeded order.  Every seed poses the same problem on the same rows, so the
trees are the same up to the columns' names and the order of the sums, and
the work a tree takes does not change with the seed: what the seed-to-seed
spread of the two first cells comes from (PERF.md section 7, (i)).

**A program without the query buckets is refused at once**
(``refuse_a_padded_layout``, before anything is drawn).  PR 32's program pads
every query to the longest and does run this table: 5.5 s a tree, so two
trees in a 20 s window (PERF.md section 4).  ``stages.window`` then ends
before ``WindowTracer`` has started, which waits for the window's first tree
to end and another to be issued, and a ``--trace 1`` run dies 200 s in, at
``find_xplane``, with nothing to read.  A run that can be timed and not traced
is no measurement of this cell, so such a program gets no run at all, under
either flag, and says why.  It is told by the device scopes of the bucketed
step, which ``tools/phases.py`` reads this cell's phase table by.  A
``benchmark`` PR that lets the tracer take a window of two trees can drop
this (PERF.md section 7, (n)).
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.datagen import quantile_bins

# the device scopes of the program's bucketed ranking step
# (lightgbm_tpu/utils/profiling.DEVICE_PHASES, docs/OBSERVABILITY.md)
RANK_SCOPES = ("rank.gather", "rank.sort", "rank.pairs", "rank.scatter")


def refuse_a_padded_layout() -> None:
    """Raise where the program has no bucketed ranking step (see above)."""
    from lightgbm_tpu.utils import profiling

    missing = [s for s in RANK_SCOPES if s not in profiling.DEVICE_PHASES]
    if missing:
        raise RuntimeError(
            f"this program has no device scope {missing}: it pads every "
            "query to the longest, grows fewer than three trees of this "
            "table in a window of 20 s, and the harness cannot trace such a "
            "window (chipbench/datagen/ranked_queries.py). Not run")


def draw_group(rows: int, queries: int, sigma: float, longest: int,
               rng: np.random.Generator) -> np.ndarray:
    """``queries`` lengths in ``1..longest`` that sum to ``rows``."""
    if not queries <= rows <= queries * longest:
        raise ValueError(f"{queries} queries of 1..{longest} rows cannot "
                         f"hold {rows} rows")
    mu = np.log(rows / queries) - 0.5 * sigma * sigma
    lens = np.clip(np.rint(rng.lognormal(mu, sigma, queries)), 1,
                   longest).astype(np.int64)
    order = rng.permutation(queries)
    while True:
        diff = rows - int(lens.sum())
        if diff == 0:
            return lens
        step = 1 if diff > 0 else -1
        room = order[(lens[order] < longest) if diff > 0
                     else (lens[order] > 1)]
        lens[room[:abs(diff)]] += step


def generate(config: dict, seed: int) -> dict:
    refuse_a_padded_layout()
    n, f = int(config["rows"]), int(config["features"])
    n_bins = int(config["params"]["max_bin"])
    if n_bins > 256:
        raise ValueError("ranked_queries draws one byte a value: "
                         "max_bin <= 256")
    p = config["datagen_params"]
    values = quantile_bins.value_table(n_bins, f)
    w = quantile_bins.teacher(config)
    k = len(w)
    wv = (values[:, :k] * w[None, :]).T.ravel()  # [k * B], feature-major
    offs = (np.arange(k) * n_bins)[None, :]

    # the order the seed picks: of the queries, and of the columns
    order = np.random.default_rng(int(seed))
    columns = order.permutation(f)
    chunk = max(1, quantile_bins.CHUNK_VALUES // f)
    starts = list(range(0, n, chunk))
    # the table: a child a chunk, as quantile_bins spawns them, and one more
    # for the query lengths
    children = np.random.SeedSequence(int(p["table_seed"])).spawn(
        len(starts) + 1)
    group = draw_group(n, int(config["queries"]), float(p["query_sigma"]),
                       int(p["query_longest"]),
                       np.random.Generator(np.random.PCG64(children[-1])))
    table = np.empty((n, f), np.uint8)  # columns in the seed's order already
    score = np.empty(n, np.float32)

    def draw(i: int) -> None:
        lo = starts[i]
        hi = min(lo + chunk, n)
        rng = np.random.Generator(np.random.PCG64(children[i]))
        b = rng.integers(0, n_bins, size=(hi - lo, f), dtype=np.uint8)
        table[lo:hi] = b[:, columns]
        s = np.take(wv, b[:, :k].astype(np.intp) + offs).sum(axis=1)
        score[lo:hi] = s + float(p["noise"]) * rng.standard_normal(hi - lo)

    with ThreadPoolExecutor(quantile_bins.THREADS) as pool:
        list(pool.map(draw, range(len(starts))))
    shares = np.asarray(p["grade_shares"], np.float64)
    cuts = np.quantile(score, np.cumsum(shares)[:-1])
    label = np.searchsorted(cuts, score, side="right").astype(np.float32)

    # the queries in the seed's order, each one's rows together as drawn
    queries = order.permutation(len(group))
    start = np.cumsum(group) - group
    moved = group[queries]
    rows = (np.repeat(start[queries] - (np.cumsum(moved) - moved), moved)
            + np.arange(n))
    bins = np.empty((n, f), np.uint8)

    def move(i: int) -> None:
        lo = starts[i]
        hi = min(lo + chunk, n)
        np.take(table, rows[lo:hi], axis=0, out=bins[lo:hi])

    with ThreadPoolExecutor(quantile_bins.THREADS) as pool:
        list(pool.map(move, range(len(starts))))
    return {"bins": bins, "label": label[rows], "values": values,
            "n_bins": n_bins, "group": moved}
