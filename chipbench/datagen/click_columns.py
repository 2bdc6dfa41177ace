"""A click log: integer count columns with missing values and hashed
categorical columns with skewed level frequencies.  One table drawn once, its
rows and its columns (within their kind) put in an order the seed picks.

The table comes from the configuration's ``table_seed`` (a chunk a child of
one ``SeedSequence``, one byte a value, ``quantile_bins``' scheme):

* **integer columns** (``0 .. len(missing_shares) - 1``): bins near-uniform
  over ``max_bin - 1`` bins, as equal-count binning of a continuous column
  fills them, and one more bin, the last (``missing_bin(n_bins)``), that holds
  the column's stated share of the rows: ``values[missing_bin, f]`` is NaN, so
  the program's binner gives the column a missing bin there.  A column whose
  share is 0 has the bin and no row in it.
* **categorical columns** (after them, one a published level count): the
  ``min(count, keep_levels)`` most frequent levels and, where the count is
  larger, one rest level holding the tail's mass; level ``k`` (from 1) of a
  column of ``count`` levels has the share ``k^-s / sum_j j^-s``, ``s`` the
  stated ``zipf_exponent`` (``level_shares``).  A level lies in a bin that a
  seeded order picks (``level_bins``), so a bin's index says nothing of its
  level's size or effect; ``values[b, f]`` is a category id a bin, rising
  with ``b``, so the program's mapper (which orders categories of equal count
  by id) keeps the drawn order, and "equal keys in bin order", the one rule
  for ties in a categorical search, means the same order on both sides of
  the comparison.  Bins past a column's levels are never drawn and repeat
  bin 0's id.  Frequencies are drawn through a table of 65,536 entries, so a
  share is kept to 1.6e-5.
* **label**: a seeded teacher (``teacher_seed``): linear in the integer
  columns' values (a seeded constant where the value is missing), a seeded
  effect a level in the first ``effect_columns`` categorical columns, plus
  noise; cut at its own quantile so that ``positive_share`` of the rows are
  positive.

``--seed`` then orders the table: its rows in a seeded order, the integer
columns in a seeded order among themselves and the categorical columns among
themselves, since the configuration states ``categorical_features`` by index.
Every seed poses the same problem on the same rows, so the trees are the same
up to the columns' names and the order of the sums, and the work a tree takes
does not change with the seed (PERF.md section 7, (i)).

**A program whose partition gathers by row is refused at once**
(``refuse_a_gathering_partition``, before anything is drawn): see that
function.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

from chipbench.datagen import quantile_bins

# the device scope of the program's categorical split search on its own
# columns (lightgbm_tpu/utils/profiling.DEVICE_PHASES, docs/OBSERVABILITY.md)
CAT_SCOPE = "grow.cat_search"
DRAW_TABLE = 1 << 16  # entries of the table a level is drawn through


def missing_bin(n_bins: int) -> int:
    """The bin of an integer column that holds its missing values: the last.
    The convention ``reference/categorical_rounds.py`` reads it by."""
    return int(n_bins) - 1


def layout(config: dict) -> tuple[int, int]:
    """(integer columns, categorical columns), the integer ones first."""
    p = config["datagen_params"]
    n_int, n_cat = len(p["missing_shares"]), len(p["level_counts"])
    if n_int + n_cat != int(config["features"]):
        raise ValueError(f"{n_int} integer and {n_cat} categorical columns "
                         f"are not {config['features']} features")
    want = list(range(n_int, n_int + n_cat))
    if list(config.get("categorical_features", ())) != want:
        raise ValueError("click_columns puts the categorical columns last: "
                         f"categorical_features must be {want}")
    return n_int, n_cat


def level_shares(count: int, exponent: float, keep: int) -> np.ndarray:
    """Shares of a column's drawn levels: the ``min(count, keep)`` most
    frequent of ``count`` levels under a Zipf law, then the rest's mass as one
    level where ``count > keep``."""
    count, keep = int(count), int(keep)
    head = np.arange(1, min(count, keep) + 1, dtype=np.float64) ** -exponent
    if count <= keep:
        return head / head.sum()
    # the tail's mass, summed in blocks so that no array of 10M is held long
    tail, lo = 0.0, keep + 1
    while lo <= count:
        hi = min(lo + (1 << 22), count + 1)
        tail += float((np.arange(lo, hi, dtype=np.float64) ** -exponent).sum())
        lo = hi
    out = np.append(head, tail)
    return out / out.sum()


def draw_table(shares: np.ndarray, bins: np.ndarray) -> np.ndarray:
    """``DRAW_TABLE`` entries, level ``l``'s bin ``bins[l]`` in
    ``round(share * DRAW_TABLE)`` of them (largest remainders, so they sum
    exactly): a uniform 16-bit draw indexed into it is a bin."""
    exact = shares * DRAW_TABLE
    n = np.floor(exact).astype(np.int64)
    short = DRAW_TABLE - int(n.sum())
    n[np.argsort(-(exact - n), kind="stable")[:short]] += 1
    return np.repeat(bins.astype(np.uint8), n)


def column_levels(config: dict) -> list[int]:
    """Drawn levels of each categorical column, the rest level counted."""
    p = config["datagen_params"]
    keep = int(p["keep_levels"])
    return [min(int(c), keep) + (int(c) > keep) for c in p["level_counts"]]


def level_bins(config: dict) -> list[np.ndarray]:
    """For each categorical column, the bin of each level, most frequent
    first and the rest level last: a seeded order of ``0 .. levels - 1``."""
    rng = np.random.default_rng([int(config["datagen_params"]["table_seed"]),
                                 1])
    return [rng.permutation(n) for n in column_levels(config)]


def refuse_a_gathering_partition() -> None:
    """Raise where the program has no categorical search of its own columns.

    PR 35's program routes the rows of every slot of every round through a
    per-row gather from the slot's 256-entry category mask once the data holds
    a categorical column, and grows fewer than three trees of this table in a
    window of 20 s (PERF.md section 4 has its measured seconds a tree), which
    ``harness/trace_reduce.WindowTracer`` cannot trace (PERF.md section 7,
    (n)).  A run that can be timed and not traced is no measurement of this
    cell, so such a program gets no run at all, under either flag, and says
    why.  It is told by the device scope this PR's search carries, which
    ``tools/phases.py`` reads this cell's phase table by."""
    from lightgbm_tpu.utils import profiling

    if CAT_SCOPE not in profiling.DEVICE_PHASES:
        raise RuntimeError(
            f"this program has no device scope {CAT_SCOPE!r}: its partition "
            "gathers a category mask by row in every slot of every round, "
            "grows fewer than three trees of this table in a window of 20 s, "
            "and the harness cannot trace such a window "
            "(chipbench/datagen/click_columns.py). Not run")


def value_table(config: dict) -> np.ndarray:
    """``values[b, f]`` of the table's own column order: the raw value of an
    integer column's bin (increasing, NaN in the missing bin) and the
    category id of a categorical column's."""
    n_int, n_cat = layout(config)
    n_bins = int(config["params"]["max_bin"])
    values = np.empty((n_bins, n_int + n_cat), np.float64)
    values[:n_bins - 1, :n_int] = quantile_bins.value_table(n_bins - 1, n_int)
    values[missing_bin(n_bins), :n_int] = np.nan
    for j, levels in enumerate(column_levels(config)):
        ids = np.arange(n_bins) * 7 + j % 7  # rising with the bin
        ids[levels:] = ids[0]
        values[:, n_int + j] = ids
    return values


def teacher(config: dict) -> dict:
    """The label's seeded teacher over the table's own column order."""
    n_int, n_cat = layout(config)
    n_bins = int(config["params"]["max_bin"])
    p = config["datagen_params"]
    rng = np.random.default_rng(int(p["teacher_seed"]))
    k = min(int(p["effect_columns"]), n_cat)
    return {"weight": rng.standard_normal(n_int),
            "missing": rng.standard_normal(n_int),
            "effect": float(p["effect_scale"]) * rng.standard_normal(
                (k, n_bins))}


def generate(config: dict, seed: int) -> dict:
    refuse_a_gathering_partition()
    n, f = int(config["rows"]), int(config["features"])
    n_bins = int(config["params"]["max_bin"])
    if n_bins > 256:
        raise ValueError("click_columns draws one byte a value: "
                         "max_bin <= 256")
    n_int, n_cat = layout(config)
    p = config["datagen_params"]
    if int(p["keep_levels"]) + 1 > n_bins:
        raise ValueError("keep_levels and the rest level do not fit max_bin")
    values = value_table(config)
    t = teacher(config)
    k = len(t["effect"])
    # what each bin of a teaching column adds to the score: [(n_int + k) * B]
    per_bin = np.where(np.isnan(values[:, :n_int]), t["missing"][None, :],
                       np.nan_to_num(values[:, :n_int]) * t["weight"][None, :])
    per_bin = np.concatenate([per_bin.T.ravel(), t["effect"].ravel()])
    teach = np.r_[0:n_int, n_int:n_int + k]
    offs = (np.arange(len(teach)) * n_bins)[None, :]
    missing_at = (np.asarray(p["missing_shares"], np.float64)
                  * DRAW_TABLE).round().astype(np.int64)
    tables = [draw_table(level_shares(c, float(p["zipf_exponent"]),
                                      int(p["keep_levels"])), at)
              for c, at in zip(p["level_counts"], level_bins(config))]

    # the order the seed picks: of the columns within their kind, of the rows
    order = np.random.default_rng(int(seed))
    columns = np.concatenate([order.permutation(n_int),
                              n_int + order.permutation(n_cat)])
    chunk = max(1, quantile_bins.CHUNK_VALUES // f)
    starts = list(range(0, n, chunk))
    children = np.random.SeedSequence(int(p["table_seed"])).spawn(len(starts))
    table = np.empty((n, f), np.uint8)  # columns in the seed's order already
    score = np.empty(n, np.float32)

    def draw(i: int) -> None:
        lo = starts[i]
        hi = min(lo + chunk, n)
        rng = np.random.Generator(np.random.PCG64(children[i]))
        b = np.empty((hi - lo, f), np.uint8)
        b[:, :n_int] = rng.integers(0, n_bins - 1, size=(hi - lo, n_int),
                                    dtype=np.uint8)
        u = rng.integers(0, DRAW_TABLE, size=(hi - lo, f), dtype=np.uint16)
        b[:, :n_int][u[:, :n_int] < missing_at[None, :]] = missing_bin(n_bins)
        for j, tab in enumerate(tables):
            np.take(tab, u[:, n_int + j], out=b[:, n_int + j])
        table[lo:hi] = b[:, columns]
        s = np.take(per_bin, b[:, teach].astype(np.intp) + offs).sum(axis=1)
        score[lo:hi] = s + float(p["noise"]) * rng.standard_normal(hi - lo)

    with ThreadPoolExecutor(quantile_bins.THREADS) as pool:
        list(pool.map(draw, range(len(starts))))
    cut = np.quantile(score, 1.0 - float(p["positive_share"]))
    label = (score > cut).astype(np.float32)

    rows = order.permutation(n)
    bins = np.empty((n, f), np.uint8)

    def move(i: int) -> None:
        lo = starts[i]
        hi = min(lo + chunk, n)
        np.take(table, rows[lo:hi], axis=0, out=bins[lo:hi])

    with ThreadPoolExecutor(quantile_bins.THREADS) as pool:
        list(pool.map(move, range(len(starts))))
    return {"bins": bins, "label": label[rows], "values": values[:, columns],
            "n_bins": n_bins}
