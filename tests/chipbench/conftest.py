"""Fixtures of the chipbench tests: a toy configuration and a second traffic
mix, added as files under a temporary root, with no edit to ``chipbench/``;
and the program's counters and spans emptied around a test that reads them."""

import copy
import json
import pathlib
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from chipbench.harness import loader  # noqa: E402

# what sound runs read here (CPU, toy size): about 5e-7 on every number; the
# reference with one bfloat16 term in the program's place reads 1e-3 or more
TOY_LIMITS = {"loss_gap": 2e-5, "update1_gap": 2e-5, "updateK_gap": 2e-5,
              "gain_gap": 2e-5, "root_gain_gap": 2e-5, "root_hess_gap": 2e-5,
              "rows_gap": 0.0, "leaves_gap": 0.0}


def toy_config() -> dict:
    cfg = copy.deepcopy(loader.load_json(
        loader.CHIPBENCH / "configs" / "higgs.json"))
    cfg.update(name="toy", rows=6000, features=6)
    cfg["params"].update(num_leaves=15, min_sum_hessian_in_leaf=5.0)
    cfg["limits"] = dict(TOY_LIMITS)
    return cfg


@pytest.fixture
def toy_roots(tmp_path):
    """A root of added files, searched before ``chipbench/`` itself."""
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "configs" / "toy.json").write_text(json.dumps(toy_config()))
    traffic = loader.load_json(
        loader.CHIPBENCH / "traffic" / "steady-train.json")
    traffic.update(name="short-train", warm_trees=2, reference_trees=2)
    (tmp_path / "traffic" / "short-train.json").write_text(
        json.dumps(traffic))
    return [tmp_path, loader.CHIPBENCH]


@pytest.fixture
def toy_cell(toy_roots):
    return loader.load_cell({"name": "toy-train", "config": "toy",
                             "traffic": "short-train", "chips": 1}, toy_roots)


@pytest.fixture
def program_state():
    """The process-wide registry and span ring, emptied around a test and
    left switched on as every other test finds them."""
    from lightgbm_tpu.obs import metrics as obs
    from lightgbm_tpu.obs import trace as obs_trace

    def empty():
        obs.set_enabled(obs.DEFAULT_ENABLED)
        obs.reset()
        obs_trace.reset_trace()

    empty()
    yield
    empty()
