"""LightGBM-TPU: a TPU-native gradient boosting framework.

A from-scratch reimplementation of the LightGBM feature set (reference:
shiyu1994/LightGBM) designed for TPU execution: JAX/XLA/Pallas compute kernels,
`jax.sharding` meshes + XLA collectives for distributed training, and a
lightgbm-compatible Python API.
"""
from .config import Config
from .models.tree import Tree
from .models.serialize import GBDTModel
from .tracing import install_compile_listener
from .utils.backend import configure_compile_cache
from .utils.log import register_log_callback, LightGBMError

# the one place every entry point passes through (lgb.train, the CLI,
# bench.py, the serving layer): see utils/backend.py for the rule
configure_compile_cache()
# every compile and cache load of the process becomes a `compile` flight note
install_compile_listener()

__version__ = "0.1.0"

__all__ = [
    "Config",
    "Tree",
    "GBDTModel",
    "register_log_callback",
    "LightGBMError",
    "__version__",
]


def __getattr__(name):
    # Lazy imports: keep `import lightgbm_tpu` cheap and initialize no JAX
    # backend until a training/inference entry point is touched.
    if name in ("Dataset", "Booster"):
        from . import basic

        return getattr(basic, name)
    if name in ("train", "cv", "CVBooster"):
        from . import engine

        return getattr(engine, name)
    if name in ("early_stopping", "log_evaluation", "record_evaluation", "reset_parameter"):
        from . import callback

        return getattr(callback, name)
    if name in ("LGBMModel", "LGBMClassifier", "LGBMRegressor", "LGBMRanker"):
        from . import sklearn

        return getattr(sklearn, name)
    if name in ("plot_importance", "plot_metric", "plot_tree", "plot_split_value_histogram"):
        from . import plotting

        return getattr(plotting, name)
    if name in ("RowBlockStore", "ContinuousTrainer"):
        from . import streaming

        return getattr(streaming, name)
    raise AttributeError(f"module 'lightgbm_tpu' has no attribute {name!r}")
