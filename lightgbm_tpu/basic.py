"""lightgbm-compatible Dataset and Booster.

Counterpart of python-package/lightgbm/basic.py (Dataset :1773, Booster :3581):
the user-facing objects with lazy Dataset construction, reference alignment
for validation data, and the Booster train/predict/save surface. Where the
reference binds to the C API through ctypes, this implementation drives the
in-process training engine (models/gbdt.py) directly — the C-API-shaped
boundary is preserved in naming and behavior so code written against lightgbm
ports over unchanged.
"""
from __future__ import annotations

import copy
import json
from pathlib import Path
from typing import Any, Dict, Iterable, List, Optional, Sequence, Union

import numpy as np

from .config import (Config, key_alias_transform, param_bool,
                     parse_objective_alias)
from .io.dataset import Dataset as _CoreDataset
from .io.parser import (load_positions, load_query_boundaries, load_weights,
                        parse_file)
from .models.gbdt import GBDT, create_boosting
from .models.serialize import GBDTModel
from .objectives import create_objective
from .utils.log import Log, LightGBMError

_NUMERIC_TYPES = (int, float, bool)


def _is_dataframe(data) -> bool:
    return hasattr(data, "dtypes") and hasattr(data, "columns")


def _pandas_to_matrix(df, pandas_categorical=None):
    """DataFrame -> (float64 matrix, categorical column indices,
    per-column category lists). Category-dtype columns become integer codes
    (NaN for missing/unseen); with `pandas_categorical` supplied (predict
    time), values map through the TRAINING categories — the python-package
    _data_from_pandas / pandas_categorical protocol."""
    cat_cols = [i for i, dt in enumerate(df.dtypes)
                if str(dt) == "category"]
    def _numeric(dt) -> bool:
        import pandas as pd

        # pd.api covers numpy dtypes AND nullable extension dtypes
        # (Int64/Float64/boolean), which np.asarray converts cleanly;
        # object/string/datetime columns are the ones to reject loudly
        return bool(pd.api.types.is_numeric_dtype(dt)
                    or pd.api.types.is_bool_dtype(dt))

    bad = [str(df.columns[i]) for i, dt in enumerate(df.dtypes)
           if i not in cat_cols and not _numeric(dt)]
    if bad:  # the python-package's explicit bad-dtype message (basic.py
        # _data_from_pandas), not an opaque numpy cast error
        raise ValueError(
            "DataFrame.dtypes for data must be int, float or bool. Did not "
            "expect the data types in the following fields: "
            + ", ".join(bad))
    if pandas_categorical is not None and \
            len(cat_cols) != len(pandas_categorical):
        raise ValueError(
            "train and valid dataset categorical_feature do not match")
    def _to_float(frame) -> np.ndarray:
        # to_numpy(na_value=...) maps pd.NA in nullable extension columns
        # to NaN; np.asarray would crash on NAType
        return frame.to_numpy(dtype=np.float64, na_value=np.nan)

    if not cat_cols:
        return _to_float(df), [], None
    df = df.copy(deep=False)
    cats_out = []
    for k, i in enumerate(cat_cols):
        col = df.iloc[:, i]
        if pandas_categorical is not None:
            cats = list(pandas_categorical[k])
            col = col.cat.set_categories(cats)
        else:
            cats = list(col.cat.categories)
        cats_out.append(cats)
        codes = col.cat.codes.to_numpy(dtype=np.float64, copy=True)
        codes[codes < 0] = np.nan  # missing / unseen categories
        df.isetitem(i, codes)
    return _to_float(df), cat_cols, cats_out


def _to_2d_float(data) -> np.ndarray:
    if hasattr(data, "toarray"):  # scipy sparse
        data = data.toarray()
    if _is_dataframe(data):
        data = _pandas_to_matrix(data)[0]
    elif hasattr(data, "values") and not isinstance(data, np.ndarray):
        data = data.values
    arr = np.asarray(data, dtype=np.float64)
    if arr.ndim == 1:
        arr = arr.reshape(1, -1)
    return arr


class Dataset:
    """Lazy-constructed training dataset (basic.py:1773)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, List[str]] = "auto",
                 categorical_feature: Union[str, List] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True, position=None) -> None:
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.position = position
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = copy.deepcopy(params) if params else {}
        self.free_raw_data = free_raw_data
        self._handle: Optional[_CoreDataset] = None
        self._raw: Optional[np.ndarray] = None
        self.used_indices: Optional[np.ndarray] = None
        self._predictor = None
        self.pandas_categorical = None

    # ------------------------------------------------------------ construction

    def construct(self) -> "Dataset":
        if self._handle is not None:
            return self
        params = dict(self.params)
        config = Config(params)
        data = self.data
        label = self.label
        feature_names: Optional[List[str]] = None

        if isinstance(data, (str, Path)) and _is_binary_cache(str(data)):
            self._handle = _CoreDataset.load_binary(str(data), config)
            self._raw = self._handle._loaded_raw
            # constructor args override the cached metadata, matching the
            # python-package's set_* after a binary load
            md = self._handle.metadata
            if self.label is not None:
                md.set_label(np.asarray(self.label))
            if self.weight is not None:
                md.set_weights(self.weight)
            if self.group is not None:
                md.set_query(self.group)
            if self.init_score is not None:
                md.set_init_score(self.init_score)
            if self.position is not None:
                md.set_positions(self.position)
            return self

        if isinstance(data, (str, Path)):
            X, y, names = parse_file(
                str(data), header=config.header,
                label_column=config.label_column or "0")
            if label is None:
                label = y
            feature_names = names
            w = load_weights(str(data))
            if w is not None and self.weight is None:
                self.weight = w
            q = load_query_boundaries(str(data))
            if q is not None and self.group is None:
                self.group = q
            p = load_positions(str(data))
            if p is not None and self.position is None:
                self.position = p
        elif _is_dataframe(data):
            # validation frames must encode through the TRAINING set's
            # category lists, not their own inferred order
            ref_pc = None
            if self.reference is not None:
                self.reference.construct()
                ref_pc = self.reference.pandas_categorical
            X, pd_cat_cols, pd_cats = _pandas_to_matrix(data, ref_pc)
            self.pandas_categorical = ref_pc if ref_pc is not None else pd_cats
            if self.feature_name == "auto":
                feature_names = [str(c) for c in data.columns]
            if pd_cat_cols and self.categorical_feature == "auto":
                self.categorical_feature = pd_cat_cols
        elif hasattr(data, "tocsc") and hasattr(data, "nnz"):
            # scipy sparse: stays sparse into the core Dataset, which bins
            # column-by-column (io/dataset.py) — never densified whole
            X = data
            if params.get("linear_tree"):
                Log.fatal("linear_tree requires dense input "
                          "(raw feature values per leaf)")
        else:
            X = _to_2d_float(data)

        if isinstance(self.feature_name, (list, tuple)):
            feature_names = list(self.feature_name)

        cats: List[int] = []
        if isinstance(self.categorical_feature, (list, tuple)):
            for c in self.categorical_feature:
                if isinstance(c, str) and feature_names and c in feature_names:
                    cats.append(feature_names.index(c))
                elif isinstance(c, _NUMERIC_TYPES):
                    cats.append(int(c))
        if "categorical_feature" in params or "categorical_column" in params:
            raw = params.get("categorical_feature", params.get("categorical_column"))
            if isinstance(raw, str):
                for tok in raw.split(","):
                    tok = tok.strip()
                    if tok.startswith("name:") and feature_names:
                        for nm in tok[5:].split(","):
                            if nm in feature_names:
                                cats.append(feature_names.index(nm))
                    elif tok:
                        cats.append(int(tok))

        ref_handle = None
        if self.reference is not None:
            self.reference.construct()
            ref_handle = self.reference._handle

        if self.used_indices is not None:
            if hasattr(X, "tocsr"):
                X = X.tocsr()[self.used_indices]
            else:
                X = X[self.used_indices]
            label = (np.asarray(label)[self.used_indices]
                     if label is not None else None)

        self._handle = _CoreDataset.from_matrix(
            X, label=label, weight=self.weight, group=self.group,
            init_score=self.init_score, position=self.position,
            config=config, categorical_feature=cats,
            feature_names=feature_names, reference=ref_handle)
        if config.monotone_constraints:
            self._handle.monotone_constraints = list(config.monotone_constraints)
        # raw values back linear trees / refit; a sparse X stays un-densified
        # (linear_tree was rejected above; refit/valid-eval densify on demand)
        if hasattr(X, "tocsc"):
            self._raw = None
            self._sparse_raw = X
        else:
            self._raw = np.asarray(X, dtype=np.float32)
        if self.free_raw_data:
            self.data = None
        return self

    # ---------------------------------------------------------------- helpers

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None, position=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       params=params or self.params, position=position)

    def subset(self, used_indices: Sequence[int], params=None) -> "Dataset":
        self.construct()
        sub = Dataset(None, params=params or self.params,
                      free_raw_data=self.free_raw_data)
        sub._handle = self._handle.subset(np.asarray(used_indices))
        sub._raw = self._raw[np.asarray(used_indices)] if self._raw is not None else None
        if self._raw is None and getattr(self, "_sparse_raw", None) is not None:
            # keep the sliced rows sparse too (cv folds of a sparse train set)
            sub._sparse_raw = self._sparse_raw.tocsr()[np.asarray(used_indices)]
        sub.reference = self
        return sub

    def set_label(self, label) -> "Dataset":
        self.label = label
        if self._handle is not None and label is not None:
            self._handle.metadata.set_label(label)
        return self

    def set_weight(self, weight) -> "Dataset":
        self.weight = weight
        if self._handle is not None:
            self._handle.metadata.set_weights(weight)
        return self

    def set_group(self, group) -> "Dataset":
        self.group = group
        if self._handle is not None and group is not None:
            self._handle.metadata.set_query(group)
        return self

    def set_init_score(self, init_score) -> "Dataset":
        self.init_score = init_score
        if self._handle is not None:
            self._handle.metadata.set_init_score(init_score)
        return self

    def set_position(self, position) -> "Dataset":
        self.position = position
        if self._handle is not None:
            self._handle.metadata.set_positions(position)
        return self

    def get_label(self):
        if self._handle is not None and self._handle.metadata.label is not None:
            return self._handle.metadata.label
        return self.label

    def get_weight(self):
        return self.weight

    def get_group(self):
        return self.group

    def get_init_score(self):
        return self.init_score

    def get_data(self):
        return self.data if self.data is not None else self._raw

    def get_feature_name(self) -> List[str]:
        self.construct()
        return list(self._handle.feature_names)

    def num_data(self) -> int:
        self.construct()
        return self._handle.num_data

    def num_feature(self) -> int:
        self.construct()
        return self._handle.num_total_features

    def save_binary(self, filename: str) -> "Dataset":
        """Binary dataset cache (Dataset::SaveBinaryFile analog, npz-based);
        `Dataset(filename)` loads it back, skipping parse + bin-finding."""
        self.construct()
        h = self._handle
        md = h.metadata
        from .checkpoint import atomic_open

        with atomic_open(filename, "wb") as fh:  # file object: numpy must
            np.savez_compressed(  # not append .npz to the requested name
                fh, bins=h.bins,
                label=md.label if md.label is not None else [],
                weight=md.weights if md.weights is not None else [],
                init_score=md.init_score if md.init_score is not None else [],
                query_boundaries=(md.query_boundaries
                                  if md.query_boundaries is not None else []),
                positions=md.positions if md.positions is not None else [],
                position_ids=(md.position_ids
                              if md.position_ids is not None else []),
                mappers=json.dumps([m.to_dict() for m in h.mappers]),
                feature_names=json.dumps(h.feature_names),
                group_lists=json.dumps(
                    [g.feature_indices for g in h.groups]),
                group_is_multi=json.dumps([g.is_multi for g in h.groups]),
                used_features=json.dumps(h.used_features),
                num_total_features=h.num_total_features,
                monotone=json.dumps(h.monotone_constraints),
                raw=self._raw if self._raw is not None else [])
        if self._raw is None and getattr(self, "_sparse_raw", None) is not None:
            Log.warning("save_binary: raw feature values of a sparse-built "
                        "Dataset are not cached; the reloaded Dataset can "
                        "train but cannot serve as a validation set")
        return self


def _is_binary_cache(path: str) -> bool:
    """A save_binary cache is an npz (zip) file: check the PK magic."""
    try:
        with open(path, "rb") as fh:
            return fh.read(2) == b"PK"
    except OSError:
        return False


class Booster:
    """Training/prediction handle (basic.py:3581)."""

    def __init__(self, params: Optional[Dict[str, Any]] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None) -> None:
        self.params = dict(params) if params else {}
        self.best_iteration = -1
        self.best_score: Dict[str, Dict[str, float]] = {}
        self._train_data_name = "training"
        self.name_valid_sets: List[str] = []

        if train_set is not None:
            if not isinstance(train_set, Dataset):
                raise TypeError(f"Training data should be Dataset instance, "
                                f"met {type(train_set).__name__}")
            train_set.params = {**train_set.params, **self.params}
            train_set.construct()
            self.config = Config(self.params)
            objective = create_objective(self.config.objective, self.config)
            self._gbdt = create_boosting(self.config, train_set._handle,
                                         objective, train_raw=train_set._raw)
            self.train_set = train_set
            self.pandas_categorical = train_set.pandas_categorical
            self._model: Optional[GBDTModel] = None
        elif model_file is not None or model_str is not None:
            model = (GBDTModel.from_file(model_file) if model_file
                     else GBDTModel.from_string(model_str))
            self._model = model
            self.config = Config(self.params)
            self._gbdt = GBDT(self.config, None, None)
            self._gbdt.models = model.trees
            self._gbdt._predictor.invalidate()
            self._gbdt.num_class = model.num_class
            self._gbdt.num_tree_per_iteration = model.num_tree_per_iteration
            # restore the iteration counter (GBDT::LoadModelFromString sets
            # iter_ from the loaded tree count) so current_iteration() and
            # the C API's out_num_iterations are right after a file load
            self._gbdt.iter_ = (len(model.trees)
                                // max(model.num_tree_per_iteration, 1))
            self._gbdt.objective = _objective_from_string(model.objective_str, self.config)
            self._gbdt.average_output = model.average_output
            self.train_set = None
            self.pandas_categorical = model.pandas_categorical
        else:
            raise TypeError("Need at least one training dataset or model "
                            "file or model string to create Booster instance")

    # ------------------------------------------------------------------ train

    def add_valid(self, data: Dataset, name: str) -> "Booster":
        if not isinstance(data, Dataset):
            raise TypeError(f"Validation data should be Dataset instance, "
                            f"met {type(data).__name__}")
        data.construct()
        raw = data._raw
        if raw is None and getattr(data, "_sparse_raw", None) is None:
            Log.fatal("Validation-set evaluation needs raw feature values; "
                      "this Dataset has none (e.g. reloaded from a binary "
                      "cache of sparse input)")
        if raw is None:
            # a sparse set stays sparse: the booster makes it dense only if
            # a tree needs the one-tree pack over raw values
            # (models/gbdt.py _ValidData.raw); a set binned with the
            # training mappers of a bundled or categorical run never does
            raw = data._sparse_raw
        self._gbdt.add_valid(data._handle, raw, name)
        self.name_valid_sets.append(name)
        return self

    def update(self, train_set: Optional[Dataset] = None, fobj=None) -> bool:
        """One boosting iteration; returns True if training finished
        (basic.py:4091 / LGBM_BoosterUpdateOneIter)."""
        if fobj is not None:
            if self._gbdt.objective is not None:
                raise LightGBMError("Cannot use fobj with a built-in objective; "
                                    "set objective='none'")
            grad, hess = fobj(self.__pred_for_fobj(), self.train_set)
            return self.__boost(grad, hess)
        return self._gbdt.train_one_iter()

    def __pred_for_fobj(self):
        # DART must drop trees BEFORE custom gradients read the score
        # (GetTrainingScore triggers DroppingTrees, dart.hpp:78-88)
        self._gbdt.prepare_training_score()
        score = np.asarray(self._gbdt.score)
        return score.ravel() if score.shape[0] == 1 else score.T

    def __boost(self, grad, hess) -> bool:
        grad = np.asarray(grad, dtype=np.float32)
        hess = np.asarray(hess, dtype=np.float32)
        return self._gbdt.train_one_iter(grad, hess)

    def refit(self, data, label, decay_rate: float = 0.9,
              **kwargs: Any) -> "Booster":
        """Refit the existing tree structures on new data
        (python-package Booster.refit / LGBM_BoosterRefit)."""
        if hasattr(data, "toarray"):  # scipy sparse: refit needs raw values
            data = data.toarray()
        data = np.asarray(data, dtype=np.float64)
        pred_leaf = self.predict(data, pred_leaf=True)
        new_params = {**self.params, "refit_decay_rate": decay_rate}
        train_set = Dataset(data, label=label, **kwargs)
        new_booster = Booster(new_params, train_set)
        new_booster._gbdt.models = GBDTModel.from_string(
            self.model_to_string()).trees
        new_booster._gbdt._predictor.invalidate()
        new_booster._gbdt.iter_ = (len(new_booster._gbdt.models)
                                   // new_booster._gbdt.num_tree_per_iteration)
        new_booster._gbdt.refit(
            np.asarray(pred_leaf, dtype=np.int32).reshape(data.shape[0], -1))
        return new_booster

    def rollback_one_iter(self) -> "Booster":
        self._gbdt.rollback_one_iter()
        return self

    def current_iteration(self) -> int:
        return self._gbdt.iter_

    def num_trees(self) -> int:
        return len(self._gbdt.models)

    def num_model_per_iteration(self) -> int:
        return self._gbdt.num_tree_per_iteration

    def num_feature(self) -> int:
        if self.train_set is not None:
            return self.train_set.num_feature()
        return self._model.max_feature_idx + 1 if self._model else 0

    def feature_name(self) -> List[str]:
        if self.train_set is not None:
            return self.train_set.get_feature_name()
        return list(self._model.feature_names) if self._model else []

    # ------------------------------------------------------------------- eval

    def eval_train(self, feval=None) -> List:
        return self.__format_eval(self._gbdt.eval_train(), feval, "train")

    def eval_valid(self, feval=None) -> List:
        return self.__format_eval(self._gbdt.eval_valid(), feval, "valid")

    def __format_eval(self, results, feval, which) -> List:
        out = [(dname, mname, val, bigger) for dname, mname, val, bigger in results]
        if feval is not None:
            fevals = feval if isinstance(feval, (list, tuple)) else [feval]
            for fe in fevals:
                if which == "train" and self.train_set is not None:
                    res = fe(self.__pred_for_feval(self.train_set), self.train_set)
                    name, val, bigger = res
                    out.append((self._train_data_name, name, val, bigger))
        return out

    def __pred_for_feval(self, dataset):
        score = np.asarray(self._gbdt.score)
        return score.ravel() if score.shape[0] == 1 else score.T

    # ---------------------------------------------------------------- predict

    def predict(self, data, start_iteration: int = 0, num_iteration: Optional[int] = None,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False, validate_features: bool = False,
                **kwargs) -> np.ndarray:
        if validate_features and _is_dataframe(data):
            trained = self.feature_name()
            given = [str(c) for c in data.columns]
            if trained and given != trained:
                raise LightGBMError(
                    f"The features names of the data to predict {given} do "
                    f"not match the ones used in training {trained}")
        if _is_dataframe(data) and self.pandas_categorical:
            data = _pandas_to_matrix(data, self.pandas_categorical)[0]
        # keep the caller's f32/f64 values: models/gbdt.py routes the device
        # dtype (f64 stays f64 under jax x64; otherwise the pack-time
        # round-toward--inf threshold downcast keeps f32 bit-exact)
        if isinstance(data, np.ndarray) and data.dtype == np.float32 \
                and data.ndim == 2:
            X = data
        else:
            X = _to_2d_float(data)
        if validate_features:
            expected = self.num_feature()
            if expected > 0 and X.shape[1] != expected:
                raise LightGBMError(
                    f"The number of features in data ({X.shape[1]}) is not "
                    f"the same as it was in training data ({expected})")
        if num_iteration is None:
            # best-iteration truncation applies to whole-model predicts only;
            # an explicit start_iteration means "this slice onward"
            num_iteration = (self.best_iteration
                             if self.best_iteration > 0
                             and start_iteration <= 0 else 0)
        if pred_leaf:
            return self._gbdt.predict_leaf_index(X, num_iteration,
                                                 start_iteration)
        if pred_contrib:
            from .shap import predict_contrib

            C = self._gbdt.num_tree_per_iteration
            trees = self._gbdt.models[max(start_iteration, 0) * C:]
            return predict_contrib(trees, X, C, num_iteration)
        chunk_kw = kwargs.get("pred_chunk_rows",
                              self.params.get("pred_chunk_rows"))
        chunk_rows = int(chunk_kw) if chunk_kw is not None else None
        # pred_shard_rows: row-shard this predict across the data mesh once
        # the batch reaches the given row count (parallel/predict.py policy;
        # inert on single-device platforms)
        shard_kw = kwargs.get("pred_shard_rows",
                              self.params.get("pred_shard_rows"))
        shard_rows = int(shard_kw) if shard_kw is not None else None
        if param_bool(kwargs.get("pred_early_stop",
                                 self.params.get("pred_early_stop"))):
            return self._gbdt.predict(
                X, raw_score=raw_score, num_iteration=num_iteration,
                start_iteration=start_iteration,
                early_stop=(
                    int(kwargs.get("pred_early_stop_freq",
                                   self.params.get("pred_early_stop_freq", 10))),
                    float(kwargs.get(
                        "pred_early_stop_margin",
                        self.params.get("pred_early_stop_margin", 10.0)))))
        return self._gbdt.predict(X, raw_score=raw_score,
                                  num_iteration=num_iteration,
                                  start_iteration=start_iteration,
                                  chunk_rows=chunk_rows,
                                  shard_rows=shard_rows)

    # ------------------------------------------------------------------ model

    def save_model(self, filename: str, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> "Booster":
        model = self.__get_model()
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        model.save_to_file(str(filename), start_iteration, num_iteration or -1,
                           importance_type)
        return self

    def model_to_string(self, num_iteration: Optional[int] = None,
                        start_iteration: int = 0,
                        importance_type: str = "split") -> str:
        model = self.__get_model()
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        return model.to_string(start_iteration, num_iteration or -1, importance_type)

    def dump_model(self, num_iteration: Optional[int] = None,
                   start_iteration: int = 0,
                   importance_type: str = "split") -> Dict:
        model = self.__get_model()
        if num_iteration is None:
            num_iteration = self.best_iteration if self.best_iteration > 0 else -1
        return json.loads(model.dump_json(start_iteration, num_iteration or -1,
                                          importance_type))

    def __get_model(self) -> GBDTModel:
        if self.train_set is not None:
            model = self._gbdt.to_model()
            model.best_iteration = self.best_iteration
            model.pandas_categorical = self.pandas_categorical
            return model
        return self._model

    def feature_importance(self, importance_type: str = "split",
                           iteration: Optional[int] = None) -> np.ndarray:
        model = self.__get_model()
        imp = model.feature_importance(importance_type, iteration or 0)
        return imp if importance_type == "gain" else imp.astype(np.int64)

    def lower_bound(self):
        vals = [t.leaf_value[: t.num_leaves].min() for t in self._gbdt.models]
        return float(np.sum(vals)) if vals else 0.0

    def upper_bound(self):
        vals = [t.leaf_value[: t.num_leaves].max() for t in self._gbdt.models]
        return float(np.sum(vals)) if vals else 0.0

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        self.params.update(params)
        self.config.set(params)
        if self.train_set is not None:
            self._gbdt.shrinkage_rate = self.config.learning_rate
            self._gbdt.tree_learner.config = self.config
            self._gbdt.tree_learner.params_dev = _learner_params(self.config)
        return self


def _learner_params(config: Config):
    import jax.numpy as jnp

    return jnp.asarray([
        config.lambda_l1, config.lambda_l2, float(config.min_data_in_leaf),
        config.min_sum_hessian_in_leaf, config.min_gain_to_split,
        config.max_delta_step], dtype=jnp.float32)


def _objective_from_string(objective_str: Optional[str], config: Config):
    """Rebuild an objective from a model file's `objective=` line
    (e.g. 'binary sigmoid:1', 'multiclass num_class:3')."""
    if not objective_str:
        return None
    parts = objective_str.split()
    name = parse_objective_alias(parts[0])
    for tok in parts[1:]:
        if ":" in tok:
            k, v = tok.split(":", 1)
            try:
                config.set({k: v})
            except Exception:
                pass
    try:
        return create_objective(name, config)
    except LightGBMError:
        return None
