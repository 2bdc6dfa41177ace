"""The stages of one run, each a function of its sizes, so that a test drives
every one at toy size on the CPU backend and ``run.py``'s ``main`` only
strings them together on the chip.

From the program the stages take the system under test (``lgb.Dataset``,
``lgb.Booster``, ``Booster.update``), its binner and cache writer, its compile
counter and its fallback registry.  Everything that measures or judges is in
this directory.
"""

from __future__ import annotations

import contextlib
import functools
import gc
import os
import time
from collections import deque
from typing import Callable, Optional

import numpy as np


class Spans(dict):
    """Host spans by name, in seconds.  Fit for set-up, never for a device
    time."""

    @contextlib.contextmanager
    def timed(self, name: str):
        t = time.perf_counter()
        try:
            yield
        finally:
            self[name] = self.get(name, 0.0) + time.perf_counter() - t


def compile_cache_on() -> str:
    """The persistent compile cache where the program keeps it, small
    programs too: after a cell's first run in a checkout nothing compiles."""
    import jax
    from lightgbm_tpu.utils.compile_cache import use_compile_cache

    path = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ---------------------------------------------------------------------------
# data and Dataset
# ---------------------------------------------------------------------------

def make_data(cell: dict, seed: int) -> dict:
    return cell["datagen"].generate(cell["config"], seed)


# what a generator may return beside bins, label and values, and the type
# the cache file and the reference are handed it in
META = {"group": np.int64, "weight": np.float64, "position": np.int64}


def metadata(data: dict) -> dict:
    """The optional arrays the generator returned, by keyword.  Empty for
    data that holds none: the calls they are forwarded to are then the
    calls of a plain deployment, argument for argument."""
    return {name: np.asarray(data[name], dtype)
            for name, dtype in META.items() if data.get(name) is not None}


def fit_mappers(data: dict, params: dict, categorical_features=()):
    """The program's own binner, fitted on the small value table (each value
    repeated enough to pass ``min_data_in_bin``), so the thresholds a tree
    records are the program's.  A column in ``categorical_features`` gets a
    categorical mapper; its values are the category ids.  Returns the binner
    and the lookup from drawn bin to the program's bin, ``lut[b, f]``."""
    from lightgbm_tpu.binning import DatasetBinner

    values = data["values"]
    min_in_bin = int(params.get("min_data_in_bin", 3))
    kw = ({"categorical_features": categorical_features}
          if categorical_features else {})
    binner = DatasetBinner.fit(np.repeat(values, min_in_bin + 1, axis=0),
                               max_bin=int(params["max_bin"]),
                               min_data_in_bin=min_in_bin, **kw)
    lut = np.stack([binner.mappers[f].transform(values[:, f])
                    for f in range(values.shape[1])], axis=1)
    return binner, lut


def program_bins(data: dict, lut: np.ndarray) -> np.ndarray:
    """Drawn bins in the program's bin space (the identity where the binner
    gave every value a bin of its own, which is checked, not assumed)."""
    b = data["bins"]
    if np.array_equal(lut, np.arange(lut.shape[0])[:, None].repeat(
            lut.shape[1], axis=1)):
        return b
    return np.take_along_axis(lut.astype(b.dtype), b.astype(np.intp), axis=0)


def build_dataset(cell: dict, data: dict, cache_dir: str, seed: int,
                  spans: Optional[Spans] = None):
    """The binned ``Dataset`` through the public constructor for binned
    data: ``io/stream.create_bin_cache`` then ``lgb.Dataset(path)``.  The
    file is removed once loaded.  Query sizes, weights and positions ride in
    the file, and a ``Dataset`` that does not give them back as drawn is not
    a measurement."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.io.stream import create_bin_cache

    spans = spans if spans is not None else Spans()
    params = cell["config"]["params"]
    with spans.timed("mappers_s"):
        binner, lut = fit_mappers(
            data, params, cell["config"].get("categorical_features", ()))
        bins = program_bins(data, lut)
    meta = metadata(data)
    os.makedirs(cache_dir, exist_ok=True)
    path = os.path.join(cache_dir,
                        f"{cell['config']['name']}-{int(seed)}.bin")
    names = [f"Column_{i}" for i in range(bins.shape[1])]
    try:
        with spans.timed("dataset_file_s"):
            create_bin_cache(path, bins, binner.mappers,
                             label=np.asarray(data["label"], np.float64),
                             feature_names=names, **meta)
        with spans.timed("dataset_load_s"):
            ds = lgb.Dataset(path, params={"max_bin": int(params["max_bin"])})
            ds.construct()
    finally:
        if os.path.exists(path):
            os.remove(path)
    for name, want in meta.items():
        if not np.array_equal(ds.get_field(name), want):
            raise RuntimeError(f"the Dataset does not hold the {name} the "
                               "generator drew")
    return ds


# ---------------------------------------------------------------------------
# booster, warm trees, window
# ---------------------------------------------------------------------------

def build_booster(cell: dict, ds, extra_params: Optional[dict] = None):
    import lightgbm_tpu as lgb

    params = dict(cell["config"]["params"], **(extra_params or {}))
    return lgb.Booster(params, ds)


@functools.lru_cache(maxsize=None)
def _token_fn():
    import jax

    return jax.jit(lambda s: s.ravel()[:1])


def score_of(bst):
    return bst._gbdt._score


def issue(bst, tokens: deque) -> None:
    """One ``update()`` and a one-element token of the new score (the score
    itself is donated to the next step on the unfused path, so it cannot be
    waited on later)."""
    import jax

    with jax.profiler.TraceAnnotation("chipbench_update"):
        bst.update()
        tokens.append(_token_fn()(score_of(bst)))


def pace(tokens: deque, in_flight: int) -> None:
    """Wait for the tree ``in_flight`` back.  It stalls nothing: it only
    keeps the host from queueing the whole run ahead of the device."""
    import jax

    if len(tokens) >= in_flight:
        with jax.profiler.TraceAnnotation("chipbench_wait"):
            jax.block_until_ready(tokens[-in_flight])


def warm(bst, n_trees: int, in_flight: int, spans: Spans):
    """The first ``n_trees`` updates through the window's own call.  Returns
    the score before and after each (host copies) and the paced token queue,
    which the window goes on with."""
    import jax

    tokens: deque = deque(maxlen=in_flight + 1)
    scores = [np.asarray(score_of(bst))]
    t = time.perf_counter()
    for i in range(n_trees):
        pace(tokens, in_flight)
        issue(bst, tokens)
        jax.block_until_ready(score_of(bst))
        scores.append(np.asarray(score_of(bst)))
        now = time.perf_counter()
        name = "first_update_s" if i == 0 else "warm_trees_s"
        spans[name] = spans.get(name, 0.0) + now - t
        t = now
    return scores, tokens


def window(bst, tokens: deque, seconds: float, tree_s_guess: float,
           traffic: dict, tracer=None) -> dict:
    """``update()`` back to back until the next tree would end past
    ``seconds``.  ``t0`` after the warm booster's score is ready, ``t1``
    after the last tree's.  Ends on a tree boundary; no sync inside holds
    the device up."""
    import jax
    from lightgbm_tpu.utils.sanitizer import CompileCounter

    in_flight = int(traffic.get("in_flight_trees", 2))
    min_trees = int(traffic.get("min_window_trees", 2))
    jax.block_until_ready(score_of(bst))
    with CompileCounter() as cc:
        t0 = time.perf_counter()
        issued = 0
        issued_at = []  # host seconds into the window at each tree's issue
        while True:
            pace(tokens, in_flight)
            elapsed = time.perf_counter() - t0
            # window trees known to have ended: all but the in_flight - 1 last
            done = max(issued - in_flight + 1, 0)
            avg = elapsed / done if done else tree_s_guess
            ahead = issued - done + 1  # trees that end after now, this one too
            if issued >= min_trees and elapsed + ahead * avg > seconds:
                break
            if tracer is not None:
                tracer.before_tree(issued, done)
            issued_at.append(elapsed)
            issue(bst, tokens)
            issued += 1
        jax.block_until_ready(score_of(bst))
        t1 = time.perf_counter()
        if tracer is not None:
            tracer.close()
    return {"trees": issued, "t0": t0, "t1": t1, "seconds": t1 - t0,
            "compiles": cc.compiles - cc.cache_hits,
            "cache_loads": cc.cache_hits, "issued_at_s": issued_at}


def booster_flags(bst, ds) -> dict:
    """What the no-fallback check reads off a trained booster
    (``chip_smoke.py``'s, with the leaf tile the rounds grower ran at)."""
    g = bst._gbdt
    return {"on_tpu": bool(g._on_tpu), "use_fast": bool(g._use_fast),
            "fused_built": g._fused_step is not None,
            "fused_disabled": bool(g._fused_disabled),
            "quantized": bool(g.cfg.use_quantized_grad),
            "hist_precision": str(g.cfg.hist_precision),
            "leaf_tile": int(g._leaf_tile(ds))}


def check_no_fallback(cell: dict, flags: dict) -> None:
    """A run in which a net fired, or in which the booster ran another
    grower than the configuration's file names, is not a measurement."""
    from lightgbm_tpu.obs import metrics as obs_metrics
    from lightgbm_tpu.utils import degrade

    for key in (degrade.HIST, degrade.PARTITION, degrade.ROUND):
        if degrade.disabled_reason(key) is not None:
            raise RuntimeError(f"degrade net fired: {key}: "
                               f"{degrade.disabled_reason(key)}")
    if obs_metrics.counter("degrade_disabled_total").value != 0:
        raise RuntimeError("degrade_disabled_total is not 0")
    if flags["fused_disabled"]:
        raise RuntimeError(f"the fused step was disabled: {flags}")
    for key, want in cell["config"]["grower"].items():
        if flags[key] != want:
            raise RuntimeError(
                f"the booster ran {key}={flags[key]!r}, the configuration "
                f"names {want!r}: {flags}")


def rounds_counter() -> Optional[int]:
    from lightgbm_tpu.obs import metrics as obs_metrics

    if not obs_metrics.enabled():
        return None
    return int(obs_metrics.counter("train_boost_rounds_total").value)


def free_program() -> None:
    """Once the caller has dropped its booster and ``Dataset``: collect them
    and the compiled steps that close over their arrays, so that the
    reference finds the device empty."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


# ---------------------------------------------------------------------------
# reference and comparison
# ---------------------------------------------------------------------------

def run_reference(cell: dict, data: dict, n_trees: int, **kw) -> dict:
    """The plain reference on what the generator drew: ``group=``,
    ``weight=`` and ``position=`` only where the data holds them."""
    cfg = cell["config"]
    return cell["reference"].train(
        data["bins"], data["label"], cfg["params"], n_trees=n_trees,
        leaf_tile=int(cfg["grower"]["leaf_tile"]), **metadata(data), **kw)


def drive(cell: dict, seed: int, seconds: float, cache_dir: str,
          tracer_factory: Optional[Callable] = None,
          break_program: Optional[Callable] = None,
          log: Callable = lambda msg: None) -> tuple:
    """Everything of a run but the look for a chip and the printing: data,
    ``Dataset``, booster, warm trees, window, then the comparison.
    ``break_program(bst)`` lets a test break the timed path underneath.
    Returns the run's record and the (booster, Dataset) pair, which the
    caller drops before ``judge``."""
    from . import compare

    traffic = cell["traffic"]
    spans = Spans()
    with spans.timed("datagen_s"):
        data = make_data(cell, seed)
    log(f"data drawn in {spans['datagen_s']:.1f} s")
    ds = build_dataset(cell, data, cache_dir, seed, spans)
    n_rows, n_features = ds.num_data(), ds.num_feature()
    log(f"Dataset {n_rows} x {n_features}: file {spans['dataset_file_s']:.1f}"
        f" s, load {spans['dataset_load_s']:.1f} s")
    with spans.timed("booster_s"):
        bst = build_booster(cell, ds)
    if break_program is not None:
        break_program(bst)
    warm_n = int(traffic["warm_trees"])
    in_flight = int(traffic.get("in_flight_trees", 2))
    rounds0 = rounds_counter()
    scores, tokens = warm(bst, warm_n, in_flight, spans)
    guess = spans.get("warm_trees_s", spans["first_update_s"]) / max(
        warm_n - 1, 1)
    log(f"first update {spans['first_update_s']:.1f} s, a warm tree "
        f"{guess:.2f} s")
    tracer = tracer_factory() if tracer_factory is not None else None
    win = window(bst, tokens, seconds, guess, traffic, tracer)
    log(f"window {win['trees']} trees in {win['seconds']:.2f} s, "
        f"{win['compiles']} compiles")
    rounds1 = rounds_counter()
    if rounds0 is not None and rounds1 - rounds0 != warm_n + win["trees"]:
        raise RuntimeError(
            f"train_boost_rounds_total moved by {rounds1 - rounds0}, the "
            f"harness counted {warm_n} + {win['trees']} trees")
    flags = booster_flags(bst, ds)
    models = list(bst._gbdt.models)
    if len(models) != warm_n + win["trees"]:
        raise RuntimeError(f"the booster holds {len(models)} trees, the "
                           f"harness counted {warm_n + win['trees']}")
    k = min(int(traffic["reference_trees"]), warm_n)
    prog = compare.program_side(scores[:k + 1], models[:k])
    from . import work

    rows = [work.tree_rows(t, n_rows) for t in models]
    out = {"spans": spans, "window": win, "flags": flags, "warm_trees": warm_n,
           "tree_rows": rows, "n_rows": n_rows, "n_features": n_features,
           "tracer": tracer, "program": prog,
           "data": data, "reference_trees": k}
    return out, (bst, ds)


def judge(cell: dict, run: dict) -> tuple[bool, dict]:
    """The reference over the first trees and the verdict.  Called once the
    window has closed, the peak memory has been read and the program's state
    is freed."""
    from . import compare

    t = time.perf_counter()
    ref = run_reference(cell, run["data"], run["reference_trees"])
    run["spans"]["reference_s"] = time.perf_counter() - t
    nums = compare.numbers(run["program"], ref, run["data"],
                           **compare.loss_named(cell["config"]))
    return compare.judge(nums, cell["config"]["limits"])
