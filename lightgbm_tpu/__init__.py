"""lightgbm_tpu: a TPU-native gradient-boosting framework.

A from-scratch re-design of the LightGBM capability surface
(reference: xiangyu/LightGBM, fork of microsoft/LightGBM) for TPU hardware:
histogram construction, split search, partitioning and prediction are
JAX/XLA/Pallas programs; distributed training is SPMD over a
jax.sharding.Mesh with XLA collectives instead of the reference's
socket/MPI Network layer.

Public API mirrors python-package/lightgbm/__init__.py.
"""

from .basic import Booster, CorruptModelError, Dataset, LightGBMError, Sequence_ as Sequence
from .callback import EarlyStopException, early_stopping, log_evaluation, record_evaluation, reset_parameter
from . import serve as _serve_pkg
from .continual import ContinualError, ContinualRunner
from .serve import Overloaded, ServingRuntime
from .serve import runtime as _serve_runtime_mod

# NOTE: imported AFTER the serve package so the package attribute
# `lightgbm_tpu.serve` resolves to the entry-point FUNCTION (engine.serve);
# the module itself stays importable as `from lightgbm_tpu.serve import ...`
# (sys.modules resolution is unaffected by the attribute shadowing).
from .engine import CVBooster, continual_train, cv, serve, train
from .utils.guards import NonFiniteError
from .utils.log import register_logger

# graft EVERY public name of the subpackage onto the shadowing function —
# driven by its __all__, so a name added there can never be missed here —
# making `import lightgbm_tpu; lightgbm_tpu.serve.ServingRuntime` work
# alongside `lgb.serve(booster)` and `from lightgbm_tpu.serve import ...`
# (both spellings pinned in tests/test_serve.py)
for _name in _serve_pkg.__all__:
    setattr(serve, _name, getattr(_serve_pkg, _name))
serve.runtime = _serve_runtime_mod
del _name, _serve_pkg, _serve_runtime_mod

__all__ = [
    "Dataset",
    "Sequence",
    "Booster",
    "CVBooster",
    "LightGBMError",
    "CorruptModelError",
    "NonFiniteError",
    "register_logger",
    "train",
    "cv",
    "serve",
    "ServingRuntime",
    "Overloaded",
    "continual_train",
    "ContinualRunner",
    "ContinualError",
    "early_stopping",
    "log_evaluation",
    "record_evaluation",
    "reset_parameter",
    "EarlyStopException",
]

__version__ = "0.1.0"

try:  # sklearn wrappers are optional at import time (mirrors compat.py)
    from .sklearn import LGBMClassifier, LGBMModel, LGBMRanker, LGBMRegressor  # noqa: F401

    __all__ += ["LGBMModel", "LGBMClassifier", "LGBMRegressor", "LGBMRanker"]
except ImportError:  # pragma: no cover
    pass

try:  # distributed estimators (reference: lightgbm.dask exposes DaskLGBM*)
    from .dask import (  # noqa: F401
        DaskLGBMClassifier,
        DaskLGBMRanker,
        DaskLGBMRegressor,
    )

    __all__ += ["DaskLGBMClassifier", "DaskLGBMRegressor", "DaskLGBMRanker"]
except ImportError:  # pragma: no cover
    pass

# plotting imports matplotlib/graphviz only at call time, so the module
# itself is always importable
from .plotting import (  # noqa: F401
    create_tree_digraph,
    plot_importance,
    plot_metric,
    plot_split_value_histogram,
    plot_tree,
)

__all__ += [
    "plot_importance",
    "plot_split_value_histogram",
    "plot_metric",
    "plot_tree",
    "create_tree_digraph",
]
