"""lightgbm_tpu: a TPU-native gradient-boosted decision tree framework.

Brand-new implementation with the capabilities of LightGBM (reference studied
at /root/reference, surveyed in SURVEY.md): histogram-based leaf-wise GBDT on
JAX/XLA/Pallas. The binned feature matrix lives in HBM; histogram
construction, best-split search, and data partitioning run on-chip; the
data-parallel mode reduces histograms with XLA collectives over ICI/DCN.

Public API mirrors the reference python package:

    import lightgbm_tpu as lgb
    bst = lgb.train(params, lgb.Dataset(X, y), num_boost_round=100)
    pred = bst.predict(X_test)
"""

from .basic import Booster, Dataset, Sequence
from .callback import (EarlyStopException, early_stopping, log_evaluation,
                       record_evaluation, record_profile, reset_parameter)
from .config import Config, resolve_params
from .engine import CVBooster, cv, train
from .runtime.device import configure_compile_cache
from .utils.log import register_logger

configure_compile_cache()

__version__ = "0.1.0"

__all__ = [
    "Dataset", "Booster", "Sequence", "train", "cv", "CVBooster",
    "Config", "resolve_params",
    "early_stopping", "log_evaluation", "record_evaluation",
    "record_profile", "reset_parameter", "EarlyStopException",
    "register_logger",
    "LGBMModel", "LGBMClassifier", "LGBMRegressor", "LGBMRanker",
]


def __getattr__(name):
    # lazy sklearn wrappers (avoid importing sklearn at package import)
    if name in ("LGBMModel", "LGBMClassifier", "LGBMRegressor", "LGBMRanker"):
        from . import sklearn as _sk
        return getattr(_sk, name)
    if name == "plot_importance" or name == "plot_metric" \
            or name == "plot_tree" or name == "create_tree_digraph":
        from . import plotting as _pl
        return getattr(_pl, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
