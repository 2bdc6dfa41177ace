"""User-facing Dataset and Booster.

Reference: python-package/lightgbm/basic.py — class Dataset (lazy
construction, reference= bin alignment, set_field/get_field, free_raw_data)
and class Booster (update, rollback_one_iter, eval, predict, save_model,
model_from_string, feature_importance...).

Unlike the reference there is no ctypes boundary: the "C API layer" of the
reference (src/c_api.cpp) collapses into direct Python calls; the hot arrays
live on the TPU as jax arrays owned by the model objects.
"""

from __future__ import annotations

import functools
import json
import os
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from .binning import DatasetBinner
from .config import Config
from .models.gbdt import GBDT, create_boosting
from .models.tree import Tree
from .ops import predict as predict_ops
from .utils import checkpoint as _checkpoint
from .utils.guards import validate_finite


class LightGBMError(Exception):
    """reference: LightGBMError in python-package/lightgbm/basic.py."""


class CorruptModelError(LightGBMError):
    """A model/snapshot file failed integrity verification (torn write,
    truncation, bit rot).  engine.train catches this to fall back to the
    newest valid snapshot; see utils/checkpoint.py."""


def _is_scipy_sparse(data) -> bool:
    return hasattr(data, "tocsr") and hasattr(data, "toarray")


@functools.partial(jax.jit, donate_argnums=(0,))
def _ooc_fill_rows(dev, chunk, row_lo):
    """One streamed-ingest step: place a fixed-shape row chunk into the
    (donated) device matrix.  Donation keeps the fill O(chunk) traffic
    per step instead of alloc+copy of the whole matrix."""
    return jax.lax.dynamic_update_slice(dev, chunk, (row_lo, 0))


def _to_2d_float(data) -> np.ndarray:
    """Accepts numpy arrays, pandas DataFrames (incl. category dtypes),
    scipy CSR/CSC matrices, Sequence objects, and lists thereof (reference:
    the c_api ingestion surface — DenseToCSR, CSR/CSC handlers, pandas
    categorical encoding in python-package/lightgbm/basic.py, and the
    Sequence streaming interface)."""
    if isinstance(data, Sequence_):
        data = _from_sequences([data])
    elif isinstance(data, list) and data and isinstance(data[0], Sequence_):
        data = _from_sequences(data)
    if hasattr(data, "dtypes") and hasattr(data, "columns"):  # pandas frame
        import pandas as pd  # local: pandas is optional

        cols = []
        for c in data.columns:
            col = data[c]
            if isinstance(col.dtype, pd.CategoricalDtype):
                codes = col.cat.codes.to_numpy().astype(np.float64)
                codes[codes < 0] = np.nan  # NA category -> missing
                cols.append(codes)
            else:
                cols.append(col.to_numpy(dtype=np.float64, na_value=np.nan))
        arr = np.stack(cols, axis=1)
        return arr
    if hasattr(data, "schema") and hasattr(data, "column"):  # pyarrow
        return _arrow_to_2d(data)
    if hasattr(data, "values"):  # pandas series
        data = data.values
    if _is_scipy_sparse(data):
        data = data.toarray()
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(-1, 1)
    return arr


def _arrow_to_2d(data) -> np.ndarray:
    """pyarrow Table/RecordBatch -> float64 matrix, column-at-a-time with no
    pandas hop (reference: include/LightGBM/arrow.h chunked-array iterators).
    Null-free numeric chunks convert zero-copy via the buffer protocol;
    chunks with nulls cast to float64 with NaN; dictionary columns use their
    integer codes (pandas-categorical semantics)."""
    import pyarrow as pa

    def chunk_values(chunk) -> np.ndarray:
        t = chunk.type
        if isinstance(t, pa.DictionaryType):
            idx = chunk.indices  # nulls live in the indices
            return idx.cast(pa.float64()).to_numpy(zero_copy_only=False)
        if pa.types.is_boolean(t):
            return chunk.cast(pa.float64()).to_numpy(zero_copy_only=False)
        if chunk.null_count == 0:
            return np.asarray(chunk, dtype=np.float64)
        return chunk.cast(pa.float64()).to_numpy(zero_copy_only=False)

    cols = []
    for i in range(data.num_columns):
        col = data.column(i)
        if (isinstance(col.type, pa.DictionaryType)
                and getattr(col, "num_chunks", 1) > 1):
            # per-chunk dictionaries may order categories differently; codes
            # are only comparable after unification
            col = col.unify_dictionaries()
        chunks = col.chunks if hasattr(col, "chunks") else [col]
        if len(chunks) == 1:
            cols.append(chunk_values(chunks[0]))
        elif not chunks:
            cols.append(np.zeros(0, np.float64))
        else:
            cols.append(np.concatenate([chunk_values(c) for c in chunks]))
    return np.stack(cols, axis=1) if cols else np.zeros((data.num_rows, 0))


class Sequence_:
    """Generic row-chunk data source (reference: lightgbm.Sequence —
    python-package/lightgbm/basic.py Sequence ABC + the push-rows streaming
    C API).  Subclass with __len__ and __getitem__ (row slice -> ndarray);
    `batch_size` bounds peak memory during construction."""

    batch_size = 65536

    def __len__(self) -> int:  # pragma: no cover - abstract
        raise NotImplementedError

    def __getitem__(self, idx):  # pragma: no cover - abstract
        raise NotImplementedError


def _from_sequences(seqs) -> np.ndarray:
    chunks = []
    for seq in seqs:
        n = len(seq)
        bs = max(int(getattr(seq, "batch_size", 65536)), 1)
        for lo in range(0, n, bs):
            chunk = np.asarray(seq[slice(lo, min(lo + bs, n))], np.float64)
            if chunk.ndim == 1:
                # a 1-D slice is a batch of single-feature ROWS
                chunk = chunk.reshape(-1, 1)
            chunks.append(chunk)
    return np.concatenate(chunks, axis=0)


def _allgather_rows_f64(local: np.ndarray) -> np.ndarray:
    """Row-concatenate a float64 array across processes BIT-EXACTLY (float64
    as int32 pairs — x64 is disabled in jax, and f32 rounding would corrupt
    values like bin boundaries vs the serial path).  Uneven per-rank row
    counts are handled by padding to the max count and slicing each rank's
    block back to its true length (reference: Network::Allgather carries
    per-rank byte counts)."""
    from jax.experimental import multihost_utils

    a = np.ascontiguousarray(np.asarray(local, np.float64))
    lead = a.shape[0]
    counts = np.asarray(multihost_utils.process_allgather(
        jnp.asarray([lead], jnp.int32), tiled=True)).ravel()
    cmax = int(counts.max()) if len(counts) else lead
    if lead < cmax:
        a = np.concatenate(
            [a, np.zeros((cmax - lead,) + a.shape[1:], np.float64)])
    bits = a.view(np.int32).reshape(cmax, -1)
    g = np.ascontiguousarray(np.asarray(
        multihost_utils.process_allgather(jnp.asarray(bits), tiled=True)))
    full = g.view(np.float64).reshape((len(counts) * cmax,) + a.shape[1:])
    if (counts == cmax).all():
        return full
    return np.concatenate([
        full[r * cmax: r * cmax + int(c)] for r, c in enumerate(counts)
    ])


def _sync_binning_sample(local: np.ndarray, target_cnt: int,
                         seed: int) -> np.ndarray:
    """Pre-partitioned multi-controller binning sync: every rank holds a
    different row shard, so bin boundaries must come from the GLOBAL sample
    (reference: DatasetLoader's distributed bin sync via
    Network::Allgather of BinMappers)."""
    import jax as _jax

    nproc = _jax.process_count()
    per = max(min(target_cnt // nproc, local.shape[0]), 1)
    rng_s = np.random.RandomState(seed)
    idx = (rng_s.choice(local.shape[0], per, replace=False)
           if local.shape[0] > per else np.arange(local.shape[0]))
    return _allgather_rows_f64(local[idx])


def _feature_names_of(data, num_features: int) -> List[str]:
    if hasattr(data, "schema") and hasattr(data, "column"):  # pyarrow:
        return [str(n) for n in data.schema.names]  # .columns is the arrays
    if hasattr(data, "columns"):
        return [str(c) for c in data.columns]
    return [f"Column_{i}" for i in range(num_features)]


class Dataset:
    """reference: class Dataset in python-package/lightgbm/basic.py.

    Lazily constructed: raw data is held until `construct()` (which the
    training entry calls), then binned via binning.DatasetBinner and shipped
    to the device as a compact int matrix.
    """

    def __init__(
        self,
        data,
        label=None,
        reference: Optional["Dataset"] = None,
        weight=None,
        group=None,
        init_score=None,
        feature_name: Union[str, List[str]] = "auto",
        categorical_feature: Union[str, List[int]] = "auto",
        params: Optional[Dict[str, Any]] = None,
        free_raw_data: bool = True,
        position=None,
    ):
        self.data = data
        self.label = None if label is None else np.asarray(label, dtype=np.float64).ravel()
        self.reference = reference
        self.weight = None if weight is None else np.asarray(weight, dtype=np.float64).ravel()
        self.group = None if group is None else np.asarray(group, dtype=np.int64).ravel()
        self.init_score = None if init_score is None else np.asarray(init_score, dtype=np.float64)
        self.params = dict(params or {})
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.free_raw_data = free_raw_data
        self._constructed = False
        self.binner: Optional[DatasetBinner] = None
        self.bins: Optional[np.ndarray] = None
        self.feature_names: List[str] = []
        # rank position info (reference: Metadata positions_; Dataset(position=...))
        self.position = None if position is None else np.asarray(position, np.int64).ravel()
        self._used_indices = None

    # -- construction ---------------------------------------------------
    def construct(self, reference: Optional["Dataset"] = None) -> "Dataset":
        if self._constructed:
            return self
        ref = reference if reference is not None else self.reference
        cfg = Config.from_dict(self.params)
        pre_binner = pre_bins = None
        if isinstance(self.data, (str, os.PathLike)):
            # file-path datasets (reference: Dataset accepts a path;
            # DatasetLoader::LoadFromFile).  two_round streams the file
            # twice — sample+count, then bin per chunk — and never holds
            # the raw float matrix (reference: two_round=true semantics).
            path = os.fspath(self.data)
            from .io.parser import load_data_file, load_data_file_two_round

            col_kw = dict(
                header=bool(cfg.header),
                label_column=cfg.label_column,
                weight_column=cfg.weight_column,
                group_column=cfg.group_column,
                ignore_column=cfg.ignore_column,
            )
            with open(path, "rb") as _fh:
                _magic = _fh.read(4)
            if _magic == b"PK\x03\x04":
                # save_binary npz checkpoint (reference:
                # DatasetLoader::LoadFromBinFile) — binned matrix + mappers
                # reload directly, no raw parsing or re-binning.  With
                # out_of_core= the matrix member is NOT materialized: it
                # streams in row chunks through a reused host buffer
                # (io/stream.py), and device residency follows
                # max_rows_in_hbm (docs round 12)
                from .binning import BinMapper

                shard_spec = self.params.get("bin_cache_shard")
                if cfg.out_of_core:
                    if shard_spec is not None:
                        raise ValueError(
                            "bin_cache_shard and out_of_core are not "
                            "combinable yet: the shard feed materializes "
                            "its rows (pass the shard to BinCacheStream "
                            "directly for streamed sweeps)")
                    from .io.stream import BinCacheStream

                    self._ooc_stream = BinCacheStream(path)
                with np.load(path, allow_pickle=False) as z:
                    sizes = z["upper_sizes"]
                    uppers = z["uppers"]
                    mt = z["missing_types"]
                    cat_sizes = (z["cat_sizes"] if "cat_sizes" in z.files
                                 else np.zeros(len(sizes), np.int64))
                    cats = z["cats"] if "cats" in z.files else np.zeros(0)
                    minv = (z["min_values"] if "min_values" in z.files
                            else np.zeros(len(sizes)))
                    maxv = (z["max_values"] if "max_values" in z.files
                            else np.zeros(len(sizes)))
                    mappers, off, coff = [], 0, 0
                    for i, s in enumerate(sizes):
                        s = int(s)
                        cs = int(cat_sizes[i])
                        mappers.append(BinMapper(
                            upper_bounds=uppers[off:off + s],
                            missing_type=int(mt[i]),
                            is_categorical=cs > 0,
                            categories=(cats[coff:coff + cs] if cs else None),
                            min_value=float(minv[i]),
                            max_value=float(maxv[i]),
                        ))
                        off += s
                        coff += cs
                    pre_binner = DatasetBinner(mappers=mappers)
                    pre_bins = (None if (
                        getattr(self, "_ooc_stream", None) is not None
                        or shard_spec is not None)
                        else np.asarray(z["bins"]))
                    _seg = None
                    if pre_bins is not None:
                        # live append segments (io/stream.py round 22)
                        # extend the cache past the base npz: the
                        # materialized load must see them too (the ooc
                        # stream and shard feed already compose them)
                        from .io.stream import load_segmented_cache

                        _seg = load_segmented_cache(path)
                        if _seg is not None:
                            pre_bins = _seg[0]
                    loaded = {
                        "label": (z["label"] if z["label"].size else None),
                        "weight": (z["weight"] if z["weight"].size else None),
                        "group": (z["group"] if z["group"].size else None),
                        "init_score": (
                            z["init_score"]
                            if "init_score" in z.files and z["init_score"].size
                            else None),
                        "position": (
                            z["position"]
                            if "position" in z.files and z["position"].size
                            else None),
                        "feature_names": [str(x) for x in z["feature_names"]],
                    }
                    if _seg is not None:
                        # per-row metadata concatenated across segments
                        loaded["label"] = (_seg[1] if _seg[1].size
                                           else None)
                        loaded["weight"] = (_seg[2] if _seg[2].size
                                            else None)
                if shard_spec is not None:
                    # rank-sharded cache feed (docs/DISTRIBUTED.md): this
                    # worker materializes ONLY its [lo, hi) rows of the
                    # shared cache — streamed through BinCacheStream's
                    # shard form with CRC verification of every fully
                    # covered block — plus optional weight-0 padding to
                    # the fleet's equal-shard size (pre_partition needs
                    # equal shards; pad rows can never contribute)
                    from .io.stream import read_cache_shard

                    s_lo, s_hi = int(shard_spec[0]), int(shard_spec[1])
                    pad_to = (int(shard_spec[2]) if len(shard_spec) > 2
                              else s_hi - s_lo)
                    if pad_to < s_hi - s_lo:
                        raise ValueError(
                            f"bin_cache_shard pad size {pad_to} is below "
                            f"the shard's {s_hi - s_lo} rows")
                    if loaded.get("group") is not None:
                        raise ValueError(
                            "bin_cache_shard does not support grouped "
                            "(ranking) caches: shard boundaries would cut "
                            "queries")
                    pre_bins = read_cache_shard(path, s_lo, s_hi)
                    n_pad = pad_to - (s_hi - s_lo)
                    if n_pad:
                        pre_bins = np.concatenate([
                            pre_bins,
                            np.zeros((n_pad, pre_bins.shape[1]),
                                     pre_bins.dtype)])

                    def _slice_pad(v, fill):
                        if v is None:
                            return None
                        v = np.asarray(v)[s_lo:s_hi]
                        if n_pad:
                            v = np.concatenate([
                                v, np.full((n_pad,) + v.shape[1:], fill,
                                           v.dtype)])
                        return v

                    w = loaded.get("weight")
                    if w is None and n_pad:
                        # padding must carry weight 0; synthesize unit
                        # weights for the real rows
                        w = np.ones(s_hi - s_lo, np.float64)
                        loaded["weight"] = np.concatenate(
                            [w, np.zeros(n_pad)])
                    else:
                        loaded["weight"] = _slice_pad(w, 0.0)
                    loaded["label"] = _slice_pad(loaded.get("label"), 0.0)
                    loaded["init_score"] = _slice_pad(
                        loaded.get("init_score"), 0.0)
                    loaded["position"] = _slice_pad(
                        loaded.get("position"), 0)
            elif cfg.two_round:
                import jax as _jax

                if ref is not None:
                    ref.construct()
                    factory = lambda sample, names: ref.binner  # noqa: E731
                else:
                    def factory(sample, names, _cfg=cfg):
                        cats_f = []
                        if isinstance(self.categorical_feature, (list, tuple)):
                            cats_f = [
                                names.index(c) if isinstance(c, str) else int(c)
                                for c in self.categorical_feature
                            ]
                        forced = None
                        if _cfg.forcedbins_filename:
                            with open(_cfg.forcedbins_filename) as fh:
                                forced = {
                                    int(e["feature"]):
                                        [float(v) for v in e["bin_upper_bound"]]
                                    for e in json.load(fh)
                                }
                        return DatasetBinner.fit(
                            sample, max_bin=_cfg.max_bin,
                            min_data_in_bin=_cfg.min_data_in_bin,
                            sample_cnt=len(sample),
                            use_missing=_cfg.use_missing,
                            zero_as_missing=_cfg.zero_as_missing,
                            categorical_features=cats_f,
                            max_bin_by_feature=_cfg.max_bin_by_feature,
                            seed=_cfg.data_random_seed,
                            forced_bins=forced,
                        )
                if (ref is None and cfg.pre_partition
                        and _jax.process_count() > 1):
                    # per-rank streamed shards: sync the reservoir sample
                    # across ranks before fitting mappers, so every rank
                    # bins on identical boundaries (same gather the
                    # in-memory pre_partition path uses)
                    inner_factory = factory

                    def factory(sample, names, _cfg=cfg,
                                _inner=inner_factory):
                        sample_g = _sync_binning_sample(
                            np.asarray(sample, np.float64),
                            _cfg.bin_construct_sample_cnt,
                            _cfg.data_random_seed)
                        return _inner(sample_g, names)

                loaded = load_data_file_two_round(
                    path, factory,
                    sample_cnt=cfg.bin_construct_sample_cnt,
                    seed=cfg.data_random_seed,
                    sample_needed=(ref is None), **col_kw,
                )
                pre_binner, pre_bins = loaded["binner"], loaded["bins"]
            else:
                loaded = load_data_file(path, **col_kw)
                self.data = loaded["data"]
            if self.label is None and loaded.get("label") is not None:
                self.label = np.asarray(loaded["label"], np.float64).ravel()
            if self.weight is None and loaded.get("weight") is not None:
                self.weight = np.asarray(loaded["weight"], np.float64).ravel()
            if self.group is None and loaded.get("group") is not None:
                self.group = np.asarray(loaded["group"], np.int64).ravel()
            if self.init_score is None and loaded.get("init_score") is not None:
                self.init_score = np.asarray(loaded["init_score"], np.float64)
            if self.position is None and loaded.get("position") is not None:
                self.position = np.asarray(loaded["position"], np.int64).ravel()
            if self.feature_name == "auto":
                self.feature_name = list(loaded["feature_names"])
        # non-finite guard rail layer 1 (docs/ROBUSTNESS.md): a NaN/inf
        # target silently corrupts every boosting round downstream — reject
        # it here, once, host-side, with the offending row in the message
        # (features are exempt: non-finite feature values take the
        # missing-value path in binning)
        validate_finite("label", self.label)
        validate_finite("weight", self.weight)
        validate_finite("init_score", self.init_score)
        # sparse inputs are binned straight from CSC (reference:
        # src/io/sparse_bin.hpp — stored nonzeros + implicit zeros); only the
        # compact binned matrix is materialized, never dense raw floats
        sparse_csc = None
        if pre_bins is not None or getattr(self, "_ooc_stream", None) is not None:
            raw = None
            num_feature = (pre_bins.shape[1] if pre_bins is not None
                           else self._ooc_stream.n_cols)
        elif _is_scipy_sparse(self.data) and cfg.is_enable_sparse:
            # (linear_tree + sparse raises below, before any raw upload)
            sparse_csc = self.data.tocsc()
            raw = None
            num_feature = sparse_csc.shape[1]
        else:
            raw = _to_2d_float(self.data)
            num_feature = raw.shape[1]
        self.feature_names = (
            list(self.feature_name)
            if isinstance(self.feature_name, (list, tuple))
            else _feature_names_of(self.data, num_feature)
        )
        cats: Sequence[int] = ()
        if isinstance(self.categorical_feature, (list, tuple)):
            cats = [
                self.feature_names.index(c) if isinstance(c, str) else int(c)
                for c in self.categorical_feature
            ]
        if pre_binner is not None:
            self.binner = pre_binner
        elif ref is not None:
            ref.construct()
            # bin alignment with the reference dataset (reference= semantics)
            self.binner = ref.binner
        else:
            forced_bins = None
            if cfg.forcedbins_filename:
                # reference: DatasetLoader reads the forced-bins JSON
                # ([{"feature": idx, "bin_upper_bound": [...]}]) and routes
                # each entry into BinMapper::FindBin as forced boundaries
                import json as _json

                with open(cfg.forcedbins_filename) as fh:
                    forced_bins = {
                        int(e["feature"]): [float(v) for v in e["bin_upper_bound"]]
                        for e in _json.load(fh)
                    }
            fit_kwargs = dict(
                max_bin=cfg.max_bin,
                min_data_in_bin=cfg.min_data_in_bin,
                sample_cnt=cfg.bin_construct_sample_cnt,
                use_missing=cfg.use_missing,
                zero_as_missing=cfg.zero_as_missing,
                categorical_features=cats,
                max_bin_by_feature=cfg.max_bin_by_feature,
                seed=cfg.data_random_seed,
                forced_bins=forced_bins,
            )
            import jax as _jax

            if (
                cfg.pre_partition
                and _jax.process_count() > 1
                and raw is not None
            ):
                sample_g = _sync_binning_sample(
                    raw, cfg.bin_construct_sample_cnt, cfg.data_random_seed)
                fit_kwargs["sample_cnt"] = len(sample_g)
                self.binner = DatasetBinner.fit(sample_g, **fit_kwargs)
            elif sparse_csc is not None:
                self.binner = DatasetBinner.fit_sparse(sparse_csc, **fit_kwargs)
            else:
                self.binner = DatasetBinner.fit(raw, **fit_kwargs)
        if pre_bins is not None:
            self.bins = pre_bins
        elif getattr(self, "_ooc_stream", None) is not None:
            self.bins = None  # never materialized host-side (out_of_core)
        elif sparse_csc is not None:
            self.bins = self.binner.transform_sparse(sparse_csc)
        else:
            self.bins = self.binner.transform(raw)
        # out-of-core residency decision (docs round 12): with out_of_core=
        # the binned matrix streams in row chunks; if the rows fit the
        # max_rows_in_hbm budget the chunks ASSEMBLE the device matrix
        # (resident regime — training is the standard growers, bit-for-bit)
        # and otherwise the matrix never becomes device-resident (spill
        # regime — chunked-histogram training, ops/treegrow_ooc.py)
        self.ooc = bool(cfg.out_of_core)
        self.ooc_spill = False
        self.ooc_chunk_rows = 0
        if self.ooc:
            from .io.stream import DEFAULT_CHUNK_ROWS

            n_rows_total = (self._ooc_stream.n_rows
                            if getattr(self, "_ooc_stream", None) is not None
                            else self.bins.shape[0])
            self.ooc_chunk_rows = int(cfg.out_of_core_chunk_rows) or min(
                DEFAULT_CHUNK_ROWS, n_rows_total)
            cap = int(cfg.max_rows_in_hbm)
            self.ooc_spill = 0 < cap < n_rows_total
        # int16 on device: half the HBM of int32 at Epsilon scale (max_bin
        # caps at 65535 by far); compute casts per tile
        if self.ooc_spill:
            self.bins_device = None  # larger than the HBM budget: streamed
        elif self.ooc:
            self.bins_device = self._ooc_assemble_device()
        else:
            self.bins_device = jnp.asarray(self.bins, jnp.int16)
        self._bins_device_t = None
        self.num_bins_pf_device = jnp.asarray(self.binner.num_bins_per_feature)
        self.missing_bin_pf_device = jnp.asarray(self.binner.missing_bin_per_feature)
        self.max_num_bins = int(self.binner.max_num_bins)
        # EFB (reference: DatasetLoader::FindGroups/FastFeatureBundling):
        # bundle sparse exclusive features so histogram passes scan fewer
        # columns; split search / trees stay in original-feature space
        self.efb = None
        self._efb_device = None
        if ref is not None:
            if getattr(ref, "efb", None) is not None:
                # aligned binning: reuse the plan; the bundled matrix for THIS
                # data is encoded lazily (valid sets never need it — only the
                # train set's histogram passes do)
                self.efb = ref.efb._replace(bundled_bins=None)
        elif cfg.enable_bundle and self.ooc:
            # EFB's bundling passes scan the full host matrix, which the
            # out-of-core path never materializes; the OOC growers run on
            # the unbundled feature space (envelope note, docs round 12)
            pass
        elif cfg.enable_bundle:
            from .io.efb import find_bundles

            # bundle capacity uses the FULL max_bin budget, not the widest
            # individual feature — one-hot blocks (2-bin features) must be
            # able to pack ~max_bin features per bundle (reference:
            # FeatureGroup bin counts exceed member features'); the histogram
            # width is raised to the bundle capacity below
            bundle_cap = max(self.max_num_bins, int(cfg.max_bin) + 1)
            self.efb = find_bundles(
                self.bins,
                self.binner.num_bins_per_feature,
                bundle_cap,
                categorical_mask=np.asarray(self.binner.categorical_mask),
                seed=cfg.data_random_seed,
            )
            if self.efb is not None and self.efb.is_useful:
                # histogram width follows the widest achieved column (the
                # gather-table stride), not the packing capacity
                self.max_num_bins = max(
                    self.max_num_bins, int(self.efb.gather_idx.shape[1])
                )
        if getattr(self, "_ooc_stream", None) is not None:
            self._num_data, self._num_feature = self._ooc_stream.shape
        else:
            self._num_data, self._num_feature = (
                self.bins.shape if raw is None else raw.shape
            )
        if cfg.linear_tree or (ref is not None and getattr(ref, "raw_device", None) is not None):
            # linear trees need raw feature values at fit/score time
            # (reference: linear_tree_learner.cpp keeps a raw-data view)
            if raw is None:
                raise LightGBMError(
                    "linear_tree requires dense raw feature values; pass "
                    "is_enable_sparse=False (sparse input) or disable "
                    "two_round (file streaming) to materialize them"
                )
            self.raw_device = jnp.asarray(raw.astype(np.float32))
        if self.free_raw_data:
            self.data = None
        self._constructed = True
        return self

    # -- out-of-core data plane (docs round 12) -------------------------
    ooc = False
    ooc_spill = False
    ooc_chunk_rows = 0
    _ooc_stream = None

    def _ooc_assemble_device(self) -> jnp.ndarray:
        """Resident regime: assemble the device matrix from streamed
        chunks — one reused host buffer, one-deep upload prefetch, a
        donated O(chunk) placement per step.  The assembled matrix is
        IDENTICAL to a whole-array upload (chunking is pure placement),
        so training downstream is bit-for-bit the in-memory path."""
        from .io.stream import prefetch_device

        if self._ooc_stream is None:
            # host bins are already fully materialized (ndarray input, no
            # cache to stream from) — chunked placement would rebuild the
            # identical matrix with ceil(N/chunk) extra dispatches for
            # zero host- or device-memory benefit; upload it whole, the
            # in-memory path's own idiom
            return jnp.asarray(self.bins, jnp.int16)
        n, f = self._ooc_stream.shape
        src = self._ooc_stream.chunks(self.ooc_chunk_rows)
        dev = jnp.zeros((n, f), jnp.int16)
        # no pad_rows: the tail chunk keeps its native shape (one extra
        # compile) so dynamic_update_slice can never clamp-shift the fill
        for row_lo, _m, chunk in prefetch_device(src, dtype=jnp.int16):
            dev = _ooc_fill_rows(dev, chunk, jnp.int32(row_lo))
        return dev

    def ooc_chunk_iter(self):
        """Fresh (row_lo, host_chunk_view) sweep over the binned matrix —
        the spill-regime grower re-invokes this once per histogram pass
        (ops/treegrow_ooc.py)."""
        if self._ooc_stream is not None:
            return self._ooc_stream.chunks(self.ooc_chunk_rows)
        from .io.stream import array_chunks

        return array_chunks(self.bins, self.ooc_chunk_rows)

    def efb_device_tables(self):
        """Lazy device tables for EFB training: (bundled_bins, gather,
        default_mask) — encoded/uploaded on first use (train set only)."""
        if self.efb is None:
            return None
        if self._efb_device is None:
            bundled = self.efb.bundled_bins
            if bundled is None:
                from .io.efb import apply_bundles

                bundled = apply_bundles(
                    self.efb, self.bins, self.binner.num_bins_per_feature
                )
                self.efb = self.efb._replace(bundled_bins=bundled)
            self._efb_device = (
                jnp.asarray(bundled, jnp.int16),
                jnp.asarray(self.efb.gather_idx),
                jnp.asarray(self.efb.default_mask),
            )
        return self._efb_device

    @property
    def query_boundaries(self) -> Optional[np.ndarray]:
        if self.group is None:
            return None
        return np.concatenate([[0], np.cumsum(self.group)]).astype(np.int64)

    def bins_device_t(self) -> jnp.ndarray:
        """(F, ceil(N / C), C / 128, 128) int16 feature-major shadow of
        bins_device, C = ops/hist_pallas.ROW_TILE, the rows padded up to
        whole row tiles with bin 0: ``shadow.reshape(F, -1)[:, :N]`` is
        ``bins.T``.  The feature axis and the row tiles' axis lead and are
        no axes of the device's (8, 128) tiles, so a feature is a run of
        whole tiles, nothing is padded but the last row tile, and the
        rounds grower's partition reads a split's column and nothing else
        (PERF.md section 6, PR 31).  Built lazily: only TPU training paths
        request it."""
        if getattr(self, "_bins_device_t", None) is None:
            from .ops.hist_pallas import ROW_TILE

            src = self.bins if self.bins is not None else self.bins_device
            if src is None:
                raise LightGBMError(
                    "bins_device_t needs a device-resident matrix, but "
                    "this out_of_core dataset exceeds max_rows_in_hbm "
                    "(spill regime) and only streams bins in chunks — "
                    "raise max_rows_in_hbm or drop out_of_core")
            n, f = src.shape
            tiles = -(-n // ROW_TILE)
            if self.bins is None:
                # out-of-core resident: the host matrix was never
                # materialized — transpose the assembled device matrix
                shadow = jnp.pad(jnp.transpose(src),
                                 ((0, 0), (0, tiles * ROW_TILE - n)))
            else:
                shadow = np.zeros((f, tiles * ROW_TILE), src.dtype)
                shadow[:, :n] = src.T
            self._bins_device_t = jnp.asarray(
                shadow.reshape(f, tiles, ROW_TILE // 128, 128), jnp.int16)
        return self._bins_device_t

    def num_data(self) -> int:
        if self._constructed:
            return self._num_data
        return _to_2d_float(self.data).shape[0]

    def num_feature(self) -> int:
        if self._constructed:
            return self._num_feature
        return _to_2d_float(self.data).shape[1]

    # -- field access (reference: Dataset.set_field/get_field) ----------
    def set_field(self, field_name: str, data) -> "Dataset":
        if field_name == "label":
            self.label = None if data is None else np.asarray(data, np.float64).ravel()
            validate_finite("label", self.label)
        elif field_name == "weight":
            self.weight = None if data is None else np.asarray(data, np.float64).ravel()
            validate_finite("weight", self.weight)
        elif field_name == "group" or field_name == "query":
            self.group = None if data is None else np.asarray(data, np.int64).ravel()
        elif field_name == "init_score":
            self.init_score = None if data is None else np.asarray(data, np.float64)
            validate_finite("init_score", self.init_score)
        elif field_name == "position":
            self.position = None if data is None else np.asarray(data, np.int64).ravel()
        else:
            raise LightGBMError(f"Unknown field: {field_name}")
        return self

    def get_field(self, field_name: str):
        return {
            "label": self.label,
            "weight": self.weight,
            "group": self.group,
            "query": self.group,
            "init_score": self.init_score,
            "position": self.position,
        }.get(field_name)

    set_label = lambda self, label: self.set_field("label", label)
    set_weight = lambda self, weight: self.set_field("weight", weight)
    set_group = lambda self, group: self.set_field("group", group)
    set_init_score = lambda self, s: self.set_field("init_score", s)
    set_position = lambda self, p: self.set_field("position", p)
    get_label = lambda self: self.label
    get_weight = lambda self: self.weight
    get_group = lambda self: self.group
    get_init_score = lambda self: self.init_score
    get_position = lambda self: self.position

    def get_data(self):
        """reference: Dataset.get_data — the raw data (None once freed)."""
        return self.data

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self.feature_names)

    def set_feature_name(self, feature_name) -> "Dataset":
        """reference: Dataset.set_feature_name."""
        if feature_name is not None and feature_name != "auto":
            names = list(feature_name)
            if self._constructed and len(names) != self.num_feature():
                raise LightGBMError(
                    f"Length of feature names {len(names)} does not equal "
                    f"number of features {self.num_feature()}"
                )
            self.feature_name = names
            if self._constructed:
                self.feature_names = names
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        """reference: Dataset.set_categorical_feature — must happen before
        construction (bin mappers depend on it)."""
        if self.categorical_feature == categorical_feature:
            return self
        if self._constructed:
            raise LightGBMError(
                "Cannot set categorical feature after freed raw data, "
                "set free_raw_data=False when construct Dataset to avoid this."
            )
        self.categorical_feature = categorical_feature
        return self

    def set_reference(self, reference: "Dataset") -> "Dataset":
        """reference: Dataset.set_reference — align bins to another dataset."""
        if self._constructed:
            if self.reference is reference:
                return self
            raise LightGBMError(
                "Cannot set reference after Dataset was constructed."
            )
        self.reference = reference
        return self

    def get_ref_chain(self, ref_limit: int = 100):
        """reference: Dataset.get_ref_chain — set of datasets along the
        reference= chain."""
        head = self
        ref_chain = set()
        while len(ref_chain) < ref_limit:
            if isinstance(head, Dataset):
                ref_chain.add(head)
                if head.reference is not None and head.reference not in ref_chain:
                    head = head.reference
                else:
                    break
            else:
                break
        return ref_chain

    def feature_num_bin(self, feature: Union[int, str]) -> int:
        """reference: Dataset.feature_num_bin (LGBM_DatasetGetFeatureNumBin)."""
        self.construct()
        if isinstance(feature, str):
            feature = self.feature_names.index(feature)
        return int(self.binner.mappers[feature].num_bins)

    def _host_bins(self, what: str) -> np.ndarray:
        """Host binned matrix for paths that need the whole thing at once.
        Resident out_of_core datasets never parse host bins, but hold the
        assembled device matrix — materialize one host copy from it; the
        spill regime has neither, so those paths are outside its envelope."""
        if self.bins is not None:
            return self.bins
        if self.bins_device is not None:
            # cached in a SEPARATE attribute so bins stays None (the OOC
            # sentinel) — per-tree callers (categorical traversal during
            # rollback/replay) must not pay a full device->host pull each
            cache = getattr(self, "_host_bins_cache", None)
            if cache is None or cache[0] is not self.bins_device:
                cache = (self.bins_device, np.asarray(self.bins_device))
                self._host_bins_cache = cache
            return cache[1]
        raise LightGBMError(
            f"{what} needs the full binned matrix, but this out_of_core "
            "dataset exceeds max_rows_in_hbm (spill regime) and only "
            "streams bins in chunks — raise max_rows_in_hbm or drop "
            "out_of_core; see ops/treegrow_ooc.py")

    def add_features_from(self, other: "Dataset") -> "Dataset":
        """Column-concatenate another constructed dataset (reference:
        Dataset::AddFeaturesFrom)."""
        self.construct()
        other.construct()
        if self.num_data() != other.num_data():
            raise LightGBMError("Cannot add features from Dataset with a different number of rows")
        self.binner = DatasetBinner(mappers=list(self.binner.mappers) + list(other.binner.mappers))
        self.bins = np.concatenate(
            [self._host_bins("add_features_from"),
             other._host_bins("add_features_from")], axis=1)
        self.bins_device = jnp.asarray(self.bins, jnp.int16)
        self._bins_device_t = None
        self.num_bins_pf_device = jnp.asarray(self.binner.num_bins_per_feature)
        self.missing_bin_pf_device = jnp.asarray(self.binner.missing_bin_per_feature)
        self.max_num_bins = int(self.binner.max_num_bins)
        self.feature_names = list(self.feature_names) + list(other.feature_names)
        self._num_feature = len(self.feature_names)
        if self.data is not None and other.data is not None:
            self.data = np.column_stack([_to_2d_float(self.data), _to_2d_float(other.data)])
        self.efb = None  # bundling plan is stale after adding columns
        self._efb_device = None
        return self

    def create_valid(self, data, label=None, weight=None, group=None, init_score=None,
                     params=None) -> "Dataset":
        """reference: Dataset.create_valid — valid set sharing this dataset's
        bin mappers."""
        return Dataset(
            data, label=label, reference=self, weight=weight, group=group,
            init_score=init_score, params=params or self.params,
        )

    def subset(self, used_indices, params=None) -> "Dataset":
        """Row subset sharing bin mappers (reference: Dataset.subset/CopySubrow)."""
        self.construct()
        idx = np.asarray(used_indices, dtype=np.int64)
        sub = Dataset.__new__(Dataset)
        sub.__dict__.update({k: v for k, v in self.__dict__.items()})
        sub.bins = self._host_bins("subset")[idx]
        sub.bins_device = jnp.asarray(sub.bins, jnp.int16)
        sub._bins_device_t = None
        if getattr(self, "efb", None) is not None:
            sub.efb = self.efb._replace(bundled_bins=None)  # re-encoded lazily
            sub._efb_device = None
        if getattr(self, "raw_device", None) is not None:
            sub.raw_device = self.raw_device[jnp.asarray(idx)]
        sub.label = None if self.label is None else self.label[idx]
        sub.weight = None if self.weight is None else self.weight[idx]
        sub.init_score = None if self.init_score is None else self.init_score[idx]
        if self.group is not None:
            # rebuild group sizes from the selected rows' query ids
            # (reference: Metadata partitioning of query boundaries)
            qid = np.repeat(np.arange(len(self.group)), self.group)[idx]
            change = np.nonzero(np.diff(qid) != 0)[0] + 1
            bounds = np.concatenate([[0], change, [len(qid)]])
            sub.group = np.diff(bounds).astype(np.int64)
        else:
            sub.group = None
        sub._num_data = len(idx)
        sub._used_indices = idx
        sub._constructed = True
        return sub

    def save_binary(self, filename: str) -> "Dataset":
        """Binned dataset checkpoint (reference: Dataset::SaveBinaryFile).
        Uses npz rather than the reference's custom byte format; a Dataset
        constructed from the saved path reloads the binned matrix and
        mappers directly, skipping raw parsing/binning (reference:
        DatasetLoader::LoadFromBinFile)."""
        self.construct()
        if self.bins is None:
            raise LightGBMError(
                "save_binary needs the host binned matrix, which an "
                "out_of_core dataset deliberately never materializes — "
                "the source cache it streams from IS the binary file")
        # write to the EXACT filename (np.savez appends .npz to bare paths;
        # the reference honors the given name)
        with open(filename, "wb") as fh:
            self._savez_binary(fh)
        return self

    def _savez_binary(self, fh) -> None:
        # one writer for every save_binary cache (io/stream.py): the
        # per-chunk CRC32 trailer table BinCacheStream re-verifies on
        # every streamed sweep rides along, so a torn or bit-rotted cache
        # fails row-ranged instead of training on garbage bins
        # (docs/ROBUSTNESS.md); the continual runner creates and APPENDS
        # to the same format through write_bin_cache/append_rows
        from .io.stream import write_bin_cache

        write_bin_cache(
            fh, self.bins, self.binner.mappers,
            label=self.label, weight=self.weight, group=self.group,
            # reference Metadata persists init_score and positions too
            # (SaveBinaryFile/LoadFromBinFile round-trip)
            init_score=self.init_score, position=self.position,
            feature_names=self.feature_names,
        )

    # -- tree traversal on binned data ----------------------------------
    def predict_leaf_binned_tree(self, tree: Tree) -> jnp.ndarray:
        """Leaf index per row for one tree on this dataset's binned matrix.
        Pads node arrays to power-of-two buckets to bound jit recompiles.

        Spill-regime out_of_core datasets (no device-resident matrix)
        traverse CHUNK-WISE over the stream — the path crash-resume's
        score replay takes (docs/ROBUSTNESS.md "Elastic fleet recovery"):
        a resumed rank rebuilds its score state without ever
        materializing the matrix."""
        n = self.num_data()
        m = tree.num_internal
        if m == 0:
            return jnp.zeros((n,), jnp.int32)
        if tree.num_cat > 0 and self.bins_device is not None:
            # categorical nodes need bin-subset membership — host walk
            return jnp.asarray(
                tree.predict_leaf_binned_batch(
                    np.asarray(self._host_bins("categorical-tree traversal")),
                    self.binner)
            )
        # model-string-loaded trees: recover bin-space thresholds lazily
        self._tree_threshold_bin(tree)
        cap = 1
        while cap < m:
            cap *= 2

        def pad(a, fill=0):
            a = np.asarray(a)  # convert ONCE; dtype reads off the binding
            out = np.full(cap, fill, dtype=a.dtype)
            out[:m] = a[:m]
            return jnp.asarray(out[None])

        if self.bins_device is None:
            return self._predict_leaf_binned_tree_streamed(tree, pad)

        leaf = predict_ops.predict_leaf_binned(
            self.bins_device,
            self.missing_bin_pf_device,
            pad(tree.split_feature),
            pad(tree.threshold_bin),
            pad(tree.default_left()),
            pad(tree.left_child, fill=-1),
            pad(tree.right_child, fill=-1),
            jnp.asarray([tree.num_leaves], jnp.int32),
        )[0]
        return leaf

    def _tree_threshold_bin(self, tree: Tree) -> None:
        """Recover bin-space thresholds for a model-string-loaded tree
        (exact when thresholds are this binner's bin uppers — the
        reference stores bin uppers as thresholds)."""
        if tree.threshold_bin is not None or tree.num_cat > 0:
            return
        m = tree.num_internal
        tb = np.zeros(m, np.int32)
        for i in range(m):
            f = int(tree.split_feature[i])
            tb[i] = int(self.binner.mappers[f].transform(
                np.asarray([tree.threshold[i]]))[0])
        tree.threshold_bin = tb

    def predict_leaf_binned_trees_chunked(self, trees):
        """One stream sweep for MANY trees: yields ``(row_lo, valid,
        leaf)`` per chunk where ``leaf`` is the (T, chunk_rows) leaf
        matrix from the stacked traversal kernel.  The spill-regime
        resume replay path: T separate :meth:`predict_leaf_binned_tree`
        sweeps would re-decompress the save_binary cache T times; this
        pays ONE sequential pass for the whole ensemble."""
        trees = list(trees)
        if any(t.num_cat > 0 for t in trees):
            raise LightGBMError(
                "categorical trees are outside the chunked multi-tree "
                "traversal (spill-regime replay; ops/treegrow_ooc.py)")
        for t in trees:
            self._tree_threshold_bin(t)
        m_max = max((t.num_internal for t in trees), default=0)
        cap = 1
        while cap < max(m_max, 1):
            cap *= 2

        def stack(get, dtype, fill=0):
            out = np.full((len(trees), cap), fill, dtype=dtype)
            for ti, t in enumerate(trees):
                m = t.num_internal
                if m:
                    out[ti, :m] = np.asarray(get(t))[:m]
            return jnp.asarray(out)

        args = (
            self.missing_bin_pf_device,
            stack(lambda t: t.split_feature, np.int32),
            stack(lambda t: t.threshold_bin, np.int32),
            stack(lambda t: t.default_left(), np.bool_),
            stack(lambda t: t.left_child, np.int32, fill=-1),
            stack(lambda t: t.right_child, np.int32, fill=-1),
            jnp.asarray([t.num_leaves for t in trees], jnp.int32),
        )
        from .io.stream import prefetch_device

        for row_lo, valid, dev in prefetch_device(
                self.ooc_chunk_iter(), dtype=jnp.int16,
                pad_rows=self.ooc_chunk_rows):
            yield row_lo, valid, predict_ops.predict_leaf_binned(dev, *args)

    def _predict_leaf_cat_streamed(self, tree: Tree) -> jnp.ndarray:
        """Categorical-tree spill traversal: the stream yields HOST chunk
        views, so the bin-subset host walk runs per chunk — no matrix
        materialization (host walks are the resident categorical path's
        behavior too)."""
        parts = []
        for _row_lo, chunk in self.ooc_chunk_iter():
            parts.append(np.asarray(
                tree.predict_leaf_binned_batch(np.array(chunk),
                                               self.binner)))
        return jnp.asarray(np.concatenate(parts).astype(np.int32))

    def _predict_leaf_binned_tree_streamed(self, tree: Tree, pad):
        """Spill-regime traversal: sweep the bin stream once, traversing
        each uploaded chunk with the same jitted kernel the resident path
        uses (chunks are padded to the stream's fixed chunk rows so the
        whole sweep compiles once; the tail rides the same executable
        with its pad rows discarded).  Per-chunk leaves stay ON DEVICE
        and concatenate once at the end — the sweep adds no host pulls."""
        if tree.num_cat > 0:
            return self._predict_leaf_cat_streamed(tree)
        args = (
            self.missing_bin_pf_device,
            pad(tree.split_feature),
            pad(tree.threshold_bin),
            pad(tree.default_left()),
            pad(tree.left_child, fill=-1),
            pad(tree.right_child, fill=-1),
            jnp.asarray([tree.num_leaves], jnp.int32),
        )
        from .io.stream import prefetch_device

        parts = []
        for _row_lo, valid, dev in prefetch_device(
                self.ooc_chunk_iter(), dtype=jnp.int16,
                pad_rows=self.ooc_chunk_rows):
            leaf = predict_ops.predict_leaf_binned(dev, *args)[0]
            parts.append(leaf[:valid])
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]


class Booster:
    """reference: class Booster in python-package/lightgbm/basic.py."""

    def __init__(
        self,
        params: Optional[Dict[str, Any]] = None,
        train_set: Optional[Dataset] = None,
        model_file: Optional[str] = None,
        model_str: Optional[str] = None,
    ):
        self.params = dict(params or {})
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._train_set = train_set
        if model_file is not None:
            # snapshots carry an integrity trailer (utils/checkpoint.py):
            # verify-and-strip so a torn file raises instead of parsing into
            # a half-model; plain model files (no trailer) load as before
            try:
                text = Path(model_file).read_text(encoding="utf-8")
            except UnicodeDecodeError as e:
                # bit rot / binary garbage: torn, not a crash — so the
                # engine's snapshot fallback can still run
                raise CorruptModelError(
                    f"{model_file} is not valid UTF-8 ({e}); the file is "
                    "corrupted") from None
            model_str, ok = _checkpoint.verify_text(text)
            if ok is False or (
                    ok is None and _checkpoint.is_snapshot_path(model_file)):
                # snapshots are always written WITH a trailer, so a
                # snapshot whose trailer is missing was truncated before
                # the trailer line — every bit as torn as a bad digest
                raise CorruptModelError(
                    f"{model_file} failed integrity verification (torn or "
                    "truncated checkpoint); resume from an older snapshot — "
                    "utils/checkpoint.py latest_valid_snapshot scans the "
                    "family, and engine.train falls back automatically")
        if model_str is not None:
            self._gbdt = GBDT.load_model_from_string(model_str)
            self.cfg = self._gbdt.cfg
        elif train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError("Training data should be Dataset instance")
            self.cfg = Config.from_dict(self.params)
            merged = dict(train_set.params or {})
            merged.update(self.params)
            train_set.params = merged
            self._gbdt = create_boosting(self.cfg, train_set)
        else:
            raise LightGBMError("need either params+train_set or a model")

    # -- training -------------------------------------------------------
    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; returns True if training should stop
        (reference: Booster.update / LGBM_BoosterUpdateOneIter)."""
        if train_set is not None and train_set is not self._train_set:
            self._train_set = train_set
            self._gbdt.reset_training_data(train_set)
        if fobj is not None:
            score = self._gbdt._score
            grad, hess = fobj(np.asarray(score), self._gbdt.train_set)
            return self.__boost(grad, hess)
        return self._gbdt.train_one_iter()

    def __boost(self, grad, hess) -> bool:
        return self._gbdt.train_one_iter(np.asarray(grad), np.asarray(hess))

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        self._gbdt.add_valid(data, name)
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """Mutate runtime-resettable params (reference: Booster.reset_parameter
        -> LGBM_BoosterResetParameter -> GBDT::ResetConfig)."""
        self.params.update(params)
        self._gbdt.cfg.update(params)
        self._gbdt.reset_split_params()
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        """reference: Booster.set_train_data_name (eval printing label)."""
        self._train_data_name = name
        return self

    def shuffle_models(self, start_iteration: int = 0, end_iteration: int = -1) -> "Booster":
        """Shuffle tree order in [start, end) (reference:
        Booster.shuffle_models -> GBDT ShuffleModels)."""
        models = self._gbdt.models
        end = len(models) if end_iteration < 0 else min(end_iteration, len(models))
        seg = models[start_iteration:end]
        np.random.shuffle(seg)
        # mutation + version bump in ONE pack-lock section (round 19): a
        # concurrent serving pack build either completes before this and
        # stays consistent, or observes the bump at insert time and
        # rebuilds — it can never cache a half-shuffled pack
        with self._gbdt._plock():
            self._gbdt.models[start_iteration:end] = seg
            self._gbdt._invalidate_pred_cache("shuffle_models")
        return self

    def _init_score_offset(self) -> float:
        scores = getattr(self._gbdt, "init_scores", None) or [0.0]
        return float(scores[0]) if len(scores) == 1 else 0.0

    def lower_bound(self) -> float:
        """Minimum possible model output (reference: Booster.lower_bound ->
        GBDT::GetLowerBoundValue: sum over trees of min leaf value)."""
        return float(sum(
            float(np.min(t.leaf_value[: t.num_leaves])) for t in self._gbdt.models
        ) + self._init_score_offset())

    def upper_bound(self) -> float:
        """Maximum possible model output (reference: Booster.upper_bound)."""
        return float(sum(
            float(np.max(t.leaf_value[: t.num_leaves])) for t in self._gbdt.models
        ) + self._init_score_offset())

    def trees_to_dataframe(self):
        """Flatten the model into a pandas DataFrame, one row per node/leaf
        (reference: Booster.trees_to_dataframe)."""
        import pandas as pd

        def node_rows(tree_idx, struct, parent, depth, rows):
            if "split_index" in struct:
                idx = f"{tree_idx}-S{struct['split_index']}"
                rows.append({
                    "tree_index": tree_idx,
                    "node_depth": depth,
                    "node_index": idx,
                    "left_child": None,
                    "right_child": None,
                    "parent_index": parent,
                    "split_feature": struct["split_feature"],
                    "split_gain": struct["split_gain"],
                    "threshold": struct["threshold"],
                    "decision_type": struct["decision_type"],
                    "missing_direction": "left" if struct["default_left"] else "right",
                    "missing_type": struct["missing_type"],
                    "value": struct["internal_value"],
                    "weight": struct["internal_weight"],
                    "count": struct["internal_count"],
                })
                me = len(rows) - 1
                rows[me]["left_child"] = node_rows(
                    tree_idx, struct["left_child"], idx, depth + 1, rows)
                rows[me]["right_child"] = node_rows(
                    tree_idx, struct["right_child"], idx, depth + 1, rows)
                return idx
            idx = f"{tree_idx}-L{struct['leaf_index']}"
            rows.append({
                "tree_index": tree_idx,
                "node_depth": depth,
                "node_index": idx,
                "left_child": None,
                "right_child": None,
                "parent_index": parent,
                "split_feature": None,
                "split_gain": None,
                "threshold": None,
                "decision_type": None,
                "missing_direction": None,
                "missing_type": None,
                "value": struct["leaf_value"],
                "weight": struct.get("leaf_weight"),
                "count": struct.get("leaf_count"),
            })
            return idx

        model = self.dump_model()
        feature_names = model["feature_names"]
        rows: List[Dict[str, Any]] = []
        for t in model["tree_info"]:
            node_rows(t["tree_index"], t["tree_structure"], None, 1, rows)
        df = pd.DataFrame(rows)
        df["split_feature"] = df["split_feature"].map(
            lambda v: feature_names[int(v)] if v is not None and not pd.isna(v) else None
        )
        return df

    def current_iteration(self) -> int:
        return self._gbdt.iter_

    def num_trees(self) -> int:
        return len(self._gbdt.models)

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_tree_per_iteration

    def num_feature(self) -> int:
        return len(self._gbdt.feature_names)

    def feature_name(self) -> List[str]:
        return list(self._gbdt.feature_names)

    # -- eval -------------------------------------------------------------
    def eval_train(self, feval=None):
        return self._eval(0, self._gbdt.train_name, feval)

    def eval_valid(self, feval=None):
        out = []
        for i in range(len(self._gbdt.valid_sets)):
            out.extend(self._eval(i + 1, self._gbdt.valid_names[i], feval))
        return out

    def eval(self, data: Dataset, name: str, feval=None):
        for i, vs in enumerate(self._gbdt.valid_sets):
            if vs is data:
                return self._eval(i + 1, name, feval)
        self.add_valid(data, name)
        return self._eval(len(self._gbdt.valid_sets), name, feval)

    def _eval(self, data_idx: int, name: str, feval=None):
        res = [
            (name, mname, val, hib)
            for (_n, mname, val, hib) in self._gbdt.eval_at(data_idx)
        ]
        if feval is not None:
            ds = self._gbdt.train_set if data_idx == 0 else self._gbdt.valid_sets[data_idx - 1]
            score = self._gbdt._score if data_idx == 0 else self._gbdt._valid_scores[data_idx - 1]
            for r in _call_feval(feval, np.asarray(score), ds):
                res.append((name, r[0], r[1], r[2]))
        return res

    # -- prediction -------------------------------------------------------
    def predict(
        self,
        data,
        start_iteration: int = 0,
        num_iteration: Optional[int] = None,
        raw_score: bool = False,
        pred_leaf: bool = False,
        pred_contrib: bool = False,
        **kwargs,
    ) -> np.ndarray:
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        if _is_scipy_sparse(data):
            # bounded-memory sparse prediction: densify per row chunk only
            # (reference: the CSR predict path never materializes the full
            # dense matrix either).  Chunk rows from a byte budget so wide
            # matrices stay bounded too.
            chunk = max(1, int(512e6 // (max(data.shape[1], 1) * 8)))
            if data.shape[0] > chunk:
                csr = data.tocsr()
                outs = []
                for lo in range(0, csr.shape[0], chunk):
                    outs.append(self.predict(
                        csr[lo:lo + chunk], start_iteration=start_iteration,
                        num_iteration=num_iteration, raw_score=raw_score,
                        pred_leaf=pred_leaf, pred_contrib=pred_contrib,
                        **kwargs,
                    ))
                return np.concatenate(outs, axis=0)
        X = _to_2d_float(data)
        n_feat = self.num_feature()
        if n_feat and X.shape[1] != n_feat and not kwargs.get("predict_disable_shape_check", False):
            # reference: LGBM_BoosterPredictForMat raises on feature-count
            # mismatch unless predict_disable_shape_check is set
            raise LightGBMError(
                f"The number of features in data ({X.shape[1]}) is not the same "
                f"as it was in training data ({n_feat}). You can set "
                f"predict_disable_shape_check=true to discard this error."
            )
        return self._gbdt.predict(
            X,
            raw_score=raw_score,
            start_iteration=start_iteration,
            num_iteration=num_iteration,
            pred_leaf=pred_leaf,
            pred_contrib=pred_contrib,
            # giant-batch serving: mesh= shards the traversal over the row
            # axis in ONE SPMD dispatch, bitwise the single-device result
            mesh=kwargs.get("mesh"),
        )

    def predict_sharded(self, data, mesh, **kwargs) -> np.ndarray:
        """Row-sharded giant-batch :meth:`predict`: scores ``data`` as ONE
        SPMD dispatch over the row ("data") axis of ``mesh`` — bitwise the
        single-device result (models/gbdt.py predict_raw_sharded).  A 2-D
        training mesh works directly (rows shard, features replicate)."""
        return self.predict(data, mesh=mesh, **kwargs)

    def refit(self, data, label, decay_rate: float = 0.9, weight=None,
              **kwargs) -> "Booster":
        """Refit leaf values on new data (reference: GBDT::RefitTree via
        LGBM_BoosterRefit): new_leaf = decay * old + (1-decay) * new_optimal.

        Multiclass ensembles renew tree ``t`` against class ``t % k``'s
        gradient column, accumulating into a per-class score plane — the
        reference's iter-major RefitTree order.  ``weight`` optionally
        carries per-row sample weights into the gradient call (reference:
        RefitTree reuses the Dataset's weights)."""
        X = _to_2d_float(data)
        label = np.asarray(label, dtype=np.float64).ravel()
        new_booster = Booster(model_str=self.model_to_string())
        new_booster._gbdt.cfg = self.cfg
        gbdt = new_booster._gbdt
        k = gbdt.num_tree_per_iteration
        score = np.zeros((len(label), k) if k > 1 else len(label),
                         dtype=np.float64)
        w_dev = None
        if weight is not None:
            weight = np.asarray(weight, dtype=np.float64).ravel()
            if len(weight) != len(label):
                raise LightGBMError(
                    f"refit: {len(label)} labels but {len(weight)} weights")
            w_dev = jnp.asarray(weight, jnp.float32)
        from .objectives import create_objective

        obj = create_objective(self.cfg)
        for t_i, tree in enumerate(gbdt.models):
            leaf = tree.predict_leaf(X)
            g, h = obj.get_gradients(jnp.asarray(score, jnp.float32), jnp.asarray(label, jnp.float32), w_dev)
            g, h = np.asarray(g, np.float64), np.asarray(h, np.float64)
            if k > 1:  # tree t renews against its class column c = t % k
                c = t_i % k
                g, h = g[:, c], h[:, c]
            sum_g = np.bincount(leaf, weights=g, minlength=tree.num_leaves)
            sum_h = np.bincount(leaf, weights=h, minlength=tree.num_leaves)
            lam2 = self.cfg.lambda_l2
            new_vals = -sum_g / (sum_h + lam2 + 1e-15) * tree.shrinkage
            tree.leaf_value = decay_rate * tree.leaf_value + (1.0 - decay_rate) * np.where(
                sum_h > 0, new_vals, tree.leaf_value
            )
            if k > 1:
                score[:, t_i % k] += tree.predict(X)
            else:
                score += tree.predict(X)
        gbdt._invalidate_pred_cache("refit")  # leaf values renewed in place
        # (bump-on-mutate: in-flight serving readers keep the old pack)
        return new_booster

    # -- serialization ----------------------------------------------------
    def model_to_string(self, num_iteration: int = -1, start_iteration: int = 0,
                        importance_type: str = None,
                        raw_deltas: bool = False) -> str:
        # None defers to saved_feature_importance_type (reference: config).
        # raw_deltas: snapshot form — pure-delta trees + init_scores header
        # line, the bitwise-resume contract (docs/ROBUSTNESS.md)
        return self._gbdt.save_model_to_string(
            num_iteration, start_iteration, importance_type,
            raw_deltas=raw_deltas)

    def save_model(self, filename, num_iteration: int = -1, start_iteration: int = 0,
                   importance_type: str = None) -> "Booster":
        # atomic (temp + os.replace): a crash mid-write leaves the previous
        # file intact instead of a torn model (docs/ROBUSTNESS.md)
        _checkpoint.atomic_write_text(
            filename,
            self.model_to_string(num_iteration, start_iteration, importance_type))
        return self

    @classmethod
    def model_from_string(cls, model_str: str) -> "Booster":
        return cls(model_str=model_str)

    def dump_model(self, num_iteration: int = -1, start_iteration: int = 0) -> Dict[str, Any]:
        """JSON model dump (reference: GBDT::DumpModel)."""
        g = self._gbdt
        trees = []
        k = g.num_tree_per_iteration
        lo = start_iteration * k
        hi = len(g.models) if num_iteration < 0 else min((start_iteration + num_iteration) * k, len(g.models))
        for idx, t in enumerate(g.models[lo:hi]):
            trees.append({
                "tree_index": idx,
                "num_leaves": t.num_leaves,
                "num_cat": t.num_cat,
                "shrinkage": t.shrinkage,
                "tree_structure": _dump_node(t, 0 if t.num_internal else -1),
            })
        return {
            "name": "tree",
            "version": "v4",
            "num_class": self.cfg.num_class if hasattr(self, "cfg") else 1,
            "num_tree_per_iteration": k,
            "label_index": 0,
            "max_feature_idx": len(g.feature_names) - 1,
            "objective": g._objective_string(),
            "average_output": g.average_output,
            "feature_names": list(g.feature_names),
            "monotone_constraints": [],
            "feature_infos": {},
            "tree_info": trees,
        }

    def feature_importance(self, importance_type: str = "split", iteration=None) -> np.ndarray:
        return self._gbdt.feature_importance(importance_type)

    def get_split_value_histogram(self, feature, bins=None, xgboost_style: bool = False):
        """Histogram of a feature's split thresholds across the model
        (reference: basic.py Booster.get_split_value_histogram)."""
        if isinstance(feature, str):
            names = self.feature_name()
            if feature not in names:
                raise ValueError(f"Unknown feature name {feature!r}")
            feature = names.index(feature)
        values = []
        for tree in self._gbdt.models:
            is_cat = tree.is_categorical_node()
            for node in range(tree.num_internal):
                if int(tree.split_feature[node]) == feature and not is_cat[node]:
                    values.append(float(tree.threshold[node]))
        values = np.array(values, dtype=np.float64)
        if bins is None or (isinstance(bins, int) and bins > len(values)):
            bins = max(len(values), 1)
        hist, bin_edges = np.histogram(values, bins=bins)
        if xgboost_style:
            ret = np.column_stack((bin_edges[1:], hist))
            ret = ret[ret[:, 1] > 0]
            try:
                import pandas as pd

                return pd.DataFrame(ret, columns=["SplitValue", "Count"])
            except ImportError:
                return ret
        return hist, bin_edges

    # network API compatibility (collectives are XLA's job on TPU)
    def set_network(self, *args, **kwargs) -> "Booster":
        return self

    def free_network(self) -> "Booster":
        return self

    def free_dataset(self) -> "Booster":
        self._train_set = None
        return self

    def set_leaf_output(self, tree_id: int, leaf_id: int, value: float) -> "Booster":
        # in-place edit + version bump atomically under the pack lock
        # (round 19): in-flight serving readers keep the old pack, and a
        # pack build racing this edit retries instead of caching a torn one
        with self._gbdt._plock():
            self._gbdt.models[tree_id].leaf_value[leaf_id] = value
            self._gbdt._invalidate_pred_cache("set_leaf_output")
        return self

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        return float(self._gbdt.models[tree_id].leaf_value[leaf_id])


def _dump_node(tree: Tree, node: int) -> Dict[str, Any]:
    if node < 0 or tree.num_internal == 0:
        leaf = -node - 1 if node < 0 else 0
        return {
            "leaf_index": leaf,
            "leaf_value": float(tree.leaf_value[leaf]),
            "leaf_weight": float(tree.leaf_weight[leaf]) if len(tree.leaf_weight) > leaf else 0.0,
            "leaf_count": int(tree.leaf_count[leaf]) if len(tree.leaf_count) > leaf else 0,
        }
    return {
        "split_index": node,
        "split_feature": int(tree.split_feature[node]),
        "split_gain": float(tree.split_gain[node]),
        "threshold": float(tree.threshold[node]),
        "decision_type": "<=",
        "default_left": bool(tree.default_left()[node]),
        "missing_type": ["None", "Zero", "NaN"][(int(tree.decision_type[node]) >> 2) & 3],
        "internal_value": float(tree.internal_value[node]),
        "internal_weight": float(tree.internal_weight[node]),
        "internal_count": int(tree.internal_count[node]),
        "left_child": _dump_node(tree, tree.left_child[node]),
        "right_child": _dump_node(tree, tree.right_child[node]),
    }


def _call_feval(feval, score: np.ndarray, ds: Dataset):
    ret = feval(score, ds)
    if ret is None:
        return []
    if isinstance(ret, list):
        return ret
    return [ret]
