"""Training entry points: train() and cv().

Counterpart of python-package/lightgbm/engine.py (train :109, cv :627):
parameter normalization, validation wiring, the before/after-iteration
callback loop, early stopping, and stratified/grouped CV folds.
"""
from __future__ import annotations

import collections
import copy
import time
from typing import Any, Callable, Dict, Iterable, List, Optional, Union

import numpy as np

from . import callback as callback_mod
from . import checkpoint as checkpoint_mod
from . import telemetry, tracing
from .basic import Booster, Dataset
from .callback import CallbackEnv, EarlyStopException
from .config import key_alias_transform
from .utils.log import Log, LightGBMError
from .utils.timer import SPAN_ITERATION, global_timer


_INIT_SCORE_CHUNK = 262_144  # rows densified at a time for sparse inputs


def _init_score_predict(model: Booster, raw) -> np.ndarray:
    """Raw-score predict for continued-training init scores. Sparse inputs
    above the chunk size densify one row-chunk at a time (the full
    `.toarray()` of a big sparse train matrix is exactly the transient the
    streamed predict path exists to avoid)."""
    if hasattr(raw, "toarray") and raw.shape[0] > _INIT_SCORE_CHUNK:
        parts = []
        for s in range(0, raw.shape[0], _INIT_SCORE_CHUNK):
            dense = raw[s:s + _INIT_SCORE_CHUNK].toarray()
            parts.append(np.atleast_1d(model.predict(dense, raw_score=True)))
        return np.concatenate(parts, axis=0)
    return model.predict(raw, raw_score=True)


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[List[Dataset]] = None,
          valid_names: Optional[List[str]] = None,
          feval: Optional[Union[Callable, List[Callable]]] = None,
          init_model: Optional[Union[str, Booster]] = None,
          keep_training_booster: bool = False,
          callbacks: Optional[List[Callable]] = None) -> Booster:
    params = key_alias_transform(params or {})
    # fresh wall-clock window per run: back-to-back train() calls in one
    # process stop conflating totals (work counters survive — see timer.py)
    global_timer.new_epoch()
    # telemetry session from the `telemetry_dir` param / $LGBM_TPU_TELEMETRY;
    # a session already active (e.g. bench.py's) is left alone and reused
    own_tel = None
    tel_dir = telemetry.resolve_dir(params)
    if tel_dir and telemetry.session() is None:
        own_tel = telemetry.start(tel_dir, label="train")
    try:
        return _train_impl(params, train_set, num_boost_round, valid_sets,
                           valid_names, feval, init_model,
                           keep_training_booster, callbacks)
    except Exception as exc:
        # black box for the postmortem: whatever the ring saw right up to
        # the unhandled failure (recorder works with telemetry off too)
        tracing.note("train_exception", error=repr(exc)[:400])
        tracing.dump_flight("train_exception")
        raise
    finally:
        # beats stop legitimately now — the collective watchdog must not
        # convert post-training silence into a worker loss
        from .parallel import elastic

        elastic.notify_train_end()
        if own_tel is not None:
            telemetry.stop()


def _train_impl(params, train_set, num_boost_round, valid_sets, valid_names,
                feval, init_model, keep_training_booster,
                callbacks) -> Booster:
    # num_boost_round param aliases override the argument (engine.py:158-170)
    if "num_iterations" in params:
        num_boost_round = int(params.pop("num_iterations"))
    params["num_iterations"] = num_boost_round
    fobj = None
    if callable(params.get("objective")):
        fobj = params["objective"]
        params["objective"] = "none"

    first_metric_only = bool(params.get("first_metric_only", False))

    if num_boost_round <= 0:
        raise ValueError("num_boost_round should be greater than zero.")
    predictor_model = None
    ckpt_state = None
    if isinstance(init_model, (str,)):
        # a full-state checkpoint sidecar next to the model file means
        # bit-identical resume: trainer state is reinstated onto the fresh
        # booster below and the predict-seeded init_score path is skipped
        ckpt_state = checkpoint_mod.load_checkpoint(init_model)
        if ckpt_state is None:
            predictor_model = Booster(model_file=init_model)
    elif isinstance(init_model, Booster):
        predictor_model = init_model
    if ckpt_state is not None:
        # checkpoint resume finishes the ORIGINAL run: re-running the same
        # command (same num_boost_round) after a crash reproduces the
        # uninterrupted run bit for bit, parameters echo included. Plain
        # init_model (no sidecar) keeps continued-training semantics below:
        # num_boost_round MORE iterations on top of the loaded model.
        init_iteration = ckpt_state.iteration
        num_boost_round = max(num_boost_round - init_iteration, 0)
    else:
        init_iteration = predictor_model.current_iteration() if predictor_model else 0

    train_set.params = {**train_set.params, **params}
    if predictor_model is not None:
        # continued training: raw scores of the loaded model seed init_score
        train_set.construct()
        raw = train_set._raw
        if raw is None:  # sparse train set: predict densifies per matrix
            raw = getattr(train_set, "_sparse_raw", None)
        init_score = _init_score_predict(predictor_model, raw)
        train_set.set_init_score(np.asarray(init_score, dtype=np.float64).ravel(order="F"))

    booster = Booster(params=params, train_set=train_set)
    if valid_sets is not None:
        if isinstance(valid_sets, Dataset):
            valid_sets = [valid_sets]
        for i, valid_data in enumerate(valid_sets):
            if valid_data is train_set:
                name = "training"
                booster._train_data_name = name
                continue
            name = (valid_names[i] if valid_names and i < len(valid_names)
                    else f"valid_{i}")
            if valid_data.reference is None:
                valid_data.reference = train_set
            valid_data.params = {**valid_data.params, **params}
            if predictor_model is not None:
                valid_data.construct()
                vraw = valid_data._raw
                if vraw is None:
                    vraw = getattr(valid_data, "_sparse_raw", None)
                vi = _init_score_predict(predictor_model, vraw)
                valid_data.set_init_score(np.asarray(vi, dtype=np.float64).ravel(order="F"))
            booster.add_valid(valid_data, name)

    cbs = set(callbacks or [])
    verbosity = int(params.get("verbosity", 1))  # CLI conf values arrive as str
    if params.get("early_stopping_round") and int(params["early_stopping_round"]) > 0:
        cbs.add(callback_mod.early_stopping(int(params["early_stopping_round"]),
                                            first_metric_only,
                                            verbose=verbosity >= 1))
    callbacks_before = sorted((cb for cb in cbs if getattr(cb, "before_iteration", False)),
                              key=lambda cb: getattr(cb, "order", 0))
    callbacks_after = sorted((cb for cb in cbs if not getattr(cb, "before_iteration", False)),
                             key=lambda cb: getattr(cb, "order", 0))

    if ckpt_state is not None:
        checkpoint_mod.restore_trainer_state(booster, ckpt_state,
                                             callbacks_after)

    booster.best_iteration = -1
    is_finished = False
    # §5 tracing: _train_loop wraps the boosting loop in a jax.profiler
    # trace when LGBM_TPU_PROFILE is set (utils/profile.maybe_trace),
    # composing with LGBM_TPU_TIMETAG per-scope TraceAnnotations
    try:
        is_finished = _train_loop(
            booster, params, feval, fobj, init_iteration, num_boost_round,
            callbacks_before, callbacks_after)
    finally:
        if global_timer.enabled:
            Log.info("%s", global_timer.report())
    if booster.best_iteration <= 0:
        booster.best_iteration = booster.current_iteration()
    return booster


def _train_loop(booster, params, feval, fobj, init_iteration, num_boost_round,
                callbacks_before, callbacks_after) -> bool:
    from .utils.profile import maybe_trace

    with maybe_trace():  # device trace when LGBM_TPU_PROFILE=<dir> is set
        return _train_loop_inner(booster, params, feval, fobj,
                                 init_iteration, num_boost_round,
                                 callbacks_before, callbacks_after)


def _train_loop_inner(booster, params, feval, fobj, init_iteration,
                      num_boost_round, callbacks_before,
                      callbacks_after) -> bool:
    is_finished = False
    evaluation_result_list = None
    if telemetry.enabled():
        telemetry.emit("train_begin", begin_iteration=init_iteration,
                       end_iteration=init_iteration + num_boost_round,
                       objective=str(params.get("objective", "")))
    for i in range(init_iteration, init_iteration + num_boost_round):
        if is_finished:
            break
        it_t0 = time.perf_counter()
        # iteration span: same API as the serving request spans, so the
        # Chrome-trace export and the flight recorder speak one format
        it_span = tracing.start_span("train_iteration")
        it_span.attrs["iteration"] = int(i)
        counters_before = (dict(global_timer.counters)
                           if telemetry.enabled() else None)
        # the Span's interval is the `iteration` host span too: the one root
        # `boosting`, `bagging`, `tree_train` and `update_score` nest under,
        # whatever the boosting type (GBDT, DART, RF; sync or async path)
        with global_timer.scope(SPAN_ITERATION):
            for cb in callbacks_before:
                cb(CallbackEnv(model=booster, params=params, iteration=i,
                               begin_iteration=init_iteration,
                               end_iteration=init_iteration + num_boost_round,
                               evaluation_result_list=None))
            is_finished = booster.update(fobj=fobj)
            t_boost_end = time.perf_counter()
            it_span.add_stage("boost", t_boost_end - it_t0)

            evaluation_result_list = []
            if booster._gbdt.valid_sets or booster._gbdt.train_metrics:
                if booster._train_data_name == "training" and _wants_train_metric(params):
                    evaluation_result_list.extend(booster.eval_train(feval))
                evaluation_result_list.extend(booster.eval_valid(feval))
            try:
                for cb in callbacks_after:
                    cb(CallbackEnv(model=booster, params=params, iteration=i,
                                   begin_iteration=init_iteration,
                                   end_iteration=init_iteration + num_boost_round,
                                   evaluation_result_list=evaluation_result_list))
            except EarlyStopException as earlyStopException:
                booster.best_iteration = earlyStopException.best_iteration + 1
                evaluation_result_list = earlyStopException.best_score
                is_finished = True
            it_span.add_stage("eval", time.perf_counter() - t_boost_end)
        it_span.finish()
        if counters_before is not None:
            _emit_iteration_record(booster, i, evaluation_result_list,
                                   time.perf_counter() - it_t0,
                                   counters_before)
    booster.best_score = collections.defaultdict(collections.OrderedDict)
    for item in evaluation_result_list or []:
        booster.best_score[item[0]][item[1]] = item[2]
    return is_finished


def _emit_iteration_record(booster, iteration, evals, wall_s,
                           counters_before) -> None:
    """One structured record per boosting iteration: eval results, tree
    stats, work-counter deltas, wall time — plus an HBM gauge sample."""
    gbdt = getattr(booster, "_gbdt", None)
    models = getattr(gbdt, "models", None) or []
    last = models[-1] if models else None
    deltas = {}
    for k, v in global_timer.counters.items():
        d = int(v) - int(counters_before.get(k, 0))
        if d:
            deltas[k] = d
    telemetry.emit(
        "iteration", iteration=int(iteration), wall_s=round(wall_s, 6),
        num_trees=len(models),
        tree_leaves=int(getattr(last, "num_leaves", 0) or 0),
        evals=[[e[0], e[1], float(e[2])] for e in (evals or [])],
        counters=deltas)
    telemetry.sample_hbm()


def _wants_train_metric(params) -> bool:
    for key in ("is_provide_training_metric", "training_metric",
                "is_training_metric", "train_metric"):
        if params.get(key):
            return True
    return False


class CVBooster:
    """Ensemble of per-fold boosters (engine.py CVBooster)."""

    def __init__(self) -> None:
        self.boosters: List[Booster] = []
        self.best_iteration = -1

    def append(self, booster: Booster) -> None:
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler_function(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs) for b in self.boosters]

        return handler_function


def _make_n_folds(full_data: Dataset, folds, nfold: int, params, seed: int,
                  stratified: bool, shuffle: bool):
    full_data.construct()
    num_data = full_data.num_data()
    label = np.asarray(full_data.get_label())
    rng = np.random.RandomState(seed)
    if folds is not None:
        if hasattr(folds, "split"):
            group = full_data.get_group()
            group_info = (np.asarray(group, dtype=np.int64)
                          if group is not None else None)
            folds = folds.split(X=np.empty(num_data), y=label, groups=group_info)
        yield from folds
        return
    if stratified:
        # stratify by label classes
        classes = np.unique(label)
        idx_by_class = [np.where(label == c)[0] for c in classes]
        if shuffle:
            for a in idx_by_class:
                rng.shuffle(a)
        fold_members: List[List[int]] = [[] for _ in range(nfold)]
        for a in idx_by_class:
            for i, ix in enumerate(a):
                fold_members[i % nfold].append(ix)
        for k in range(nfold):
            test_idx = np.array(sorted(fold_members[k]), dtype=np.int64)
            train_idx = np.setdiff1d(np.arange(num_data), test_idx)
            yield train_idx, test_idx
    else:
        perm = rng.permutation(num_data) if shuffle else np.arange(num_data)
        kstep = int(num_data / nfold)
        for k in range(nfold):
            test_idx = perm[k * kstep: (k + 1) * kstep if k < nfold - 1 else num_data]
            train_idx = np.setdiff1d(np.arange(num_data), test_idx)
            yield train_idx, test_idx


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, feval=None, init_model=None,
       fpreproc=None, seed: int = 0, callbacks=None, eval_train_metric: bool = False,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    """K-fold cross-validation (engine.py:627)."""
    params = key_alias_transform(params or {})
    if "num_iterations" in params:
        num_boost_round = int(params.pop("num_iterations"))
    if metrics is not None:
        params["metric"] = metrics
    if stratified and params.get("objective") not in (
            None, "binary", "multiclass", "multiclassova", "softmax"):
        stratified = False

    results = collections.defaultdict(list)
    cvbooster = CVBooster()
    fold_data = []
    for train_idx, test_idx in _make_n_folds(train_set, folds, nfold, params,
                                             seed, stratified, shuffle):
        tr = train_set.subset(train_idx)
        te = train_set.subset(test_idx)
        if fpreproc is not None:
            tr, te, params = fpreproc(tr, te, params.copy())
        fold_data.append((tr, te))

    boosters = []
    for tr, te in fold_data:
        te.reference = tr
        bst = Booster(params=params, train_set=tr)
        bst.add_valid(te, "valid")
        boosters.append(bst)
        cvbooster.append(bst)

    cbs = set(callbacks or [])
    es_cb = None
    if params.get("early_stopping_round") and int(params["early_stopping_round"]) > 0:
        es_cb = callback_mod.early_stopping(int(params["early_stopping_round"]))
        cbs.add(es_cb)
    callbacks_after = sorted((cb for cb in cbs if not getattr(cb, "before_iteration", False)),
                             key=lambda cb: getattr(cb, "order", 0))

    is_finished = False
    for i in range(num_boost_round):
        if is_finished:
            break
        merged: Dict = collections.defaultdict(list)
        for bst in boosters:
            bst.update()
            for dname, mname, val, bigger in bst.eval_valid(feval):
                merged[(dname, mname, bigger)].append(val)
        agg = []
        for (dname, mname, bigger), vals in merged.items():
            mean, std = float(np.mean(vals)), float(np.std(vals))
            results[f"{dname} {mname}-mean"].append(mean)
            results[f"{dname} {mname}-stdv"].append(std)
            agg.append((dname, mname, mean, bigger, std))
        try:
            for cb in callbacks_after:
                cb(CallbackEnv(model=cvbooster, params=params, iteration=i,
                               begin_iteration=0, end_iteration=num_boost_round,
                               evaluation_result_list=agg))
        except EarlyStopException as e:
            cvbooster.best_iteration = e.best_iteration + 1
            for key in list(results.keys()):
                results[key] = results[key][: cvbooster.best_iteration]
            is_finished = True
    out = dict(results)
    if return_cvbooster:
        out["cvbooster"] = cvbooster
    return out
