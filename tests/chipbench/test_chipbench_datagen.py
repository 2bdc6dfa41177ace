"""The bins drawn from the seed stand for a raw matrix: the file route gives
the same ``Dataset`` as ``lgb.Dataset(raw)`` and the same trees."""

import numpy as np

import lightgbm_tpu as lgb
from chipbench.datagen import quantile_bins
from chipbench.harness import stages


def _small_cell(toy_cell, rows=20000):
    toy_cell["config"]["rows"] = rows
    return toy_cell


def test_file_route_equals_dataset_of_raw(toy_cell, tmp_path):
    cell = _small_cell(toy_cell)
    params = cell["config"]["params"]
    data = stages.make_data(cell, 7)
    raw = quantile_bins.raw_matrix(data)
    assert raw.shape == (20000, 6)
    cache = tmp_path / "cache"
    from_file = stages.build_dataset(cell, data, str(cache), 7)
    from_raw = lgb.Dataset(raw, label=np.asarray(data["label"], np.float64),
                           params={"max_bin": params["max_bin"]})
    from_raw.construct()
    np.testing.assert_array_equal(from_file.bins, from_raw.bins)
    for a, b in zip(from_file.binner.mappers, from_raw.binner.mappers):
        np.testing.assert_array_equal(a.upper_bounds, b.upper_bounds)
        assert a.missing_type == b.missing_type
    np.testing.assert_array_equal(from_file.label, from_raw.label)
    assert not list(cache.iterdir())  # the cache file is removed

    models = []
    for ds in (from_file, from_raw):
        bst = lgb.Booster(params, ds)
        for _ in range(3):
            bst.update()
        models.append(bst.model_to_string())
    assert models[0] == models[1]


def test_bins_are_near_uniform_and_labels_follow_the_teacher(toy_cell):
    cell = _small_cell(toy_cell, rows=200000)
    data = stages.make_data(cell, 3)
    counts = np.bincount(data["bins"][:, 0], minlength=255)
    assert counts.min() > 0.8 * 200000 / 255 < counts.max() < 1.2 * 200000 / 255
    raw = quantile_bins.raw_matrix(data)
    w = quantile_bins.teacher(cell["config"])
    agree = ((raw[:, :len(w)] @ w > 0) == (data["label"] > 0.5)).mean()
    assert 0.75 < agree < 0.99  # the noise flips some, not most


def test_same_seed_same_data_whatever_the_threads(toy_cell, monkeypatch):
    cell = _small_cell(toy_cell)
    big = 2**31 + 12345  # more than 32 signed bits hold
    a = stages.make_data(cell, big)
    monkeypatch.setattr(quantile_bins, "THREADS", 1)
    monkeypatch.setattr(quantile_bins, "CHUNK_VALUES", 6 * 4096)
    b = stages.make_data(cell, big)
    c = stages.make_data(cell, big + 1)
    np.testing.assert_array_equal(a["label"][:4096], b["label"][:4096])
    np.testing.assert_array_equal(a["bins"][:4096], b["bins"][:4096])
    assert (a["bins"] != c["bins"]).mean() > 0.9
