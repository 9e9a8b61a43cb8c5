"""Objective function interface + factory.

Counterpart of ObjectiveFunction (include/LightGBM/objective_function.h:19-90)
and its factory (src/objective/objective_function.cpp:71-119). Objectives are
per-row gradient/hessian producers; on TPU they are pure jitted elementwise
functions over the device score/label arrays (the analog of the CUDA objective
kernels in src/objective/cuda/).
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple, Type

from ..config import Config
from ..io.metadata import Metadata
from ..utils.log import Log

OBJECTIVE_REGISTRY: Dict[str, Type] = {}


def register_objective(*names: str):
    def deco(cls):
        for n in names:
            OBJECTIVE_REGISTRY[n] = cls
        cls.names = names
        return cls

    return deco


class ObjectiveFunction:
    """Base interface (objective_function.h:29-90)."""

    is_constant_hessian = False
    need_accurate_gradients = False
    # whether get_gradients is a pure traceable function safe to wrap in an
    # outer jit (stateful objectives like rank_xendcg manage their own jits)
    jit_gradients = True
    # names of the per-row device constants get_gradients reads besides the
    # score (labels, weights; an attribute may hold None), declared by an
    # objective whose gradient of a row depends on that row alone: a driver
    # that keeps its rows in a sharded learner's layout places them there
    # once (models/resident.py). Empty: not known to be per-row
    row_constants: Tuple[str, ...] = ()

    def __init__(self, config: Config) -> None:
        self.config = config
        self.metadata: Optional[Metadata] = None
        self.num_data = 0

    def init(self, metadata: Metadata, num_data: int) -> None:
        self.metadata = metadata
        self.num_data = num_data

    # device: score [N, C] -> (grad [N, C], hess [N, C])
    def get_gradients(self, score):
        raise NotImplementedError

    def boost_from_score(self, class_id: int = 0) -> float:
        """Initial raw score (BoostFromScore, objective_function.h:65)."""
        return 0.0

    def convert_output(self, raw):
        """Raw score -> output space (sigmoid/exp/identity)."""
        return raw

    def renew_tree_output(self, tree, score, partition) -> None:
        """Leaf-value refitting hook (RenewTreeOutput) for percentile-style
        objectives (L1/quantile/MAPE); default no-op."""
        return None

    @property
    def num_model_per_iteration(self) -> int:
        return 1

    @property
    def num_class(self) -> int:
        return 1

    def to_string(self) -> str:
        return self.names[0]


def create_objective(name: str, config: Config) -> Optional[ObjectiveFunction]:
    from . import regression, binary, multiclass, rank, xentropy  # noqa: F401

    if name in ("custom", "none", "null", "na") or not name:
        return None
    cls = OBJECTIVE_REGISTRY.get(name)
    if cls is None:
        Log.fatal("Unknown objective type name: %s", name)
    return cls(config)
