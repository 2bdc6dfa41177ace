"""CLI driver + text parsers + native loader tests.

Reference test-strategy analogue: tests/python_package_test/test_consistency.py
(CLI-vs-Python parity via train.conf scenarios) and tests/distributed/'s
CLI-subprocess pattern (SURVEY.md §5.2).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.io import load_data_file, parse_text
from lightgbm_tpu.native import get_lib, parse_file_native

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def csv_files(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("cli")
    rng = np.random.RandomState(0)
    n, f = 1200, 6
    X = rng.randn(n, f)
    w = rng.randn(f)
    y = ((X @ w + 0.5 * rng.randn(n)) > 0).astype(np.float64)
    train_p = tmp / "train.csv"
    valid_p = tmp / "valid.csv"

    def write(path, Xa, ya):
        with open(path, "w") as fh:
            for i in range(len(Xa)):
                fh.write(",".join([f"{ya[i]:g}"] + [f"{v:.8g}" for v in Xa[i]]) + "\n")

    write(train_p, X[:1000], y[:1000])
    write(valid_p, X[1000:], y[1000:])
    return dict(tmp=tmp, train=str(train_p), valid=str(valid_p),
                X=X, y=y)

pytestmark = pytest.mark.slow


def test_native_loader_builds():
    lib = get_lib()
    assert lib is not None, "native loader failed to build (g++ present per env)"


def test_native_csv_matches_numpy(csv_files):
    native = parse_file_native(csv_files["train"], "csv", False, 0)
    assert native is not None
    data_n, label_n = native
    with open(csv_files["train"]) as fh:
        data_p, _, fmt = parse_text(fh.read(), "csv")
    assert fmt == "csv"
    # label_idx=0: column 0 becomes the label and is excluded from data
    np.testing.assert_allclose(data_n, data_p[:, 1:], rtol=0, atol=0)
    np.testing.assert_allclose(label_n, data_p[:, 0])


def test_native_libsvm(tmp_path):
    path = tmp_path / "t.svm"
    path.write_text("1 0:1.5 3:2.5\n0 1:1.0\n1 2:-3.0 3:0.25\n")
    out = parse_file_native(str(path), "libsvm", False, 0)
    assert out is not None
    data, label = out
    np.testing.assert_array_equal(label, [1, 0, 1])
    expect = np.array(
        [[1.5, 0, 0, 2.5], [0, 1.0, 0, 0], [0, 0, -3.0, 0.25]]
    )
    np.testing.assert_allclose(data, expect)


def test_load_data_file_weight_group_columns(tmp_path):
    path = tmp_path / "t.csv"
    # label, f0, weight, f1
    path.write_text("1,0.5,2.0,9\n0,1.5,1.0,8\n1,2.5,0.5,7\n")
    out = load_data_file(str(path), label_column="0", weight_column="2")
    np.testing.assert_array_equal(out["label"], [1, 0, 1])
    np.testing.assert_array_equal(out["weight"], [2.0, 1.0, 0.5])
    np.testing.assert_allclose(out["data"], [[0.5, 9], [1.5, 8], [2.5, 7]])


def test_cli_train_predict_roundtrip(csv_files):
    tmp = csv_files["tmp"]
    conf = tmp / "train.conf"
    model_p = tmp / "model.txt"
    conf.write_text(
        f"task = train\n"
        f"objective = binary\n"
        f"data = {csv_files['train']}\n"
        f"valid = {csv_files['valid']}\n"
        f"num_iterations = 10   # comment\n"
        f"num_leaves = 15\n"
        f"verbosity = -1\n"
        f"output_model = {model_p}\n"
    )
    env = dict(os.environ, PYTHONPATH=_REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu", f"config={conf}"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert model_p.exists()

    # predict via CLI and compare against the Python API
    out_p = tmp / "preds.txt"
    r = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu", "task=predict",
         f"data={csv_files['valid']}", f"input_model={model_p}",
         f"output_result={out_p}"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    cli_preds = np.loadtxt(out_p)
    bst = lgb.Booster(model_file=str(model_p))
    api_preds = bst.predict(csv_files["X"][1000:])
    np.testing.assert_allclose(cli_preds, api_preds, rtol=1e-12, atol=1e-12)
    # the model must actually classify
    acc = ((api_preds > 0.5) == (csv_files["y"][1000:] > 0.5)).mean()
    assert acc > 0.85, acc


def test_cli_convert_model_compiles_and_matches(csv_files, tmp_path):
    """task=convert_model: generated C++ compiles with g++ and predicts
    identically to the framework (reference: Tree::ToIfElse contract)."""
    import ctypes

    X, y = csv_files["X"], csv_files["y"]
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 7, "verbosity": -1},
        lgb.Dataset(X, label=y), num_boost_round=3,
    )
    model_p = tmp_path / "m.txt"
    bst.save_model(str(model_p))
    cpp_p = tmp_path / "pred.cpp"
    from lightgbm_tpu.cli import run

    rc = run([f"task=convert_model", f"input_model={model_p}",
              f"convert_model={cpp_p}"])
    assert rc == 0 and cpp_p.exists()
    so_p = tmp_path / "pred.so"
    r = subprocess.run(
        ["g++", "-O2", "-fPIC", "-shared", "-o", str(so_p), str(cpp_p)],
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    lib = ctypes.CDLL(str(so_p))
    lib.Predict.restype = ctypes.c_double
    lib.Predict.argtypes = [ctypes.POINTER(ctypes.c_double)]
    # Contract (see GBDT.to_if_else): generated C++ is float64 and must
    # bit-match the host f64 tree walk; the f32 device predict path agrees
    # only to float32 roundoff.
    trees = bst._gbdt._trees_for_export(0, -1)
    raw64 = np.sum([t.predict(X[:64]) for t in trees], axis=0)
    host64 = 1.0 / (1.0 + np.exp(-raw64))
    api32 = bst.predict(X[:64])
    for i in range(64):
        row = np.ascontiguousarray(X[i], dtype=np.float64)
        got = lib.Predict(row.ctypes.data_as(ctypes.POINTER(ctypes.c_double)))
        assert abs(got - host64[i]) < 1e-12, (i, got, host64[i])
        assert abs(got - api32[i]) < 1e-5, (i, got, api32[i])


def test_cli_refit(csv_files):
    tmp = csv_files["tmp"]
    model_p = tmp / "m_refit_src.txt"
    X, y = csv_files["X"], csv_files["y"]
    bst = lgb.train(
        {"objective": "binary", "num_leaves": 7, "verbosity": -1},
        lgb.Dataset(X[:1000], label=y[:1000]), num_boost_round=3,
    )
    bst.save_model(str(model_p))
    out_p = tmp / "m_refit.txt"
    env = dict(os.environ, PYTHONPATH=_REPO, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, "-m", "lightgbm_tpu", "task=refit",
         f"data={csv_files['train']}", f"input_model={model_p}",
         f"output_model={out_p}", "verbosity=-1"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert out_p.exists()
    refitted = lgb.Booster(model_file=str(out_p))
    assert np.isfinite(refitted.predict(X[:10])).all()


def test_native_trailing_empty_fields_are_nan(tmp_path):
    """Trailing empty delimited fields must parse as NaN (missing), matching
    the numpy fallback's np.full(..., nan) init."""
    path = tmp_path / "trail.csv"
    path.write_text("1,2.5,\n0,,4.5\n1,5.5,6.5\n")
    out = parse_file_native(str(path), "csv", False, 0)
    assert out is not None
    data, label = out
    np.testing.assert_array_equal(label, [1, 0, 1])
    assert np.isnan(data[0, 1]) and np.isnan(data[1, 0])
    np.testing.assert_allclose(data[2], [5.5, 6.5])
