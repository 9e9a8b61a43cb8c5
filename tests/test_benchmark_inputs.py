"""What the chip benchmark reads of the package, held to the package.

Every ledger metric is a file of `benchmark/metrics/`: a `reader` and what
it reads. Those that read something the package itself produces are held
here, one case a metric, each to a tiny CPU run of the metric's kind (its
name's prefix):

  * flight notes (`flight_notes`, `notes_ratio`, `ops_per_note`): the run
    leaves a `tracing.recorder` note of the file's `kind` carrying its
    `field` (and `over`), summed as `benchmark/readers/flight_notes.py`
    sums them, to a number the reader can divide by where it divides;
  * host spans (`trace_idle_in_span`): the run opens the named span;
  * device scopes (`trace_scope`, `scope_roofline`): every scope name the
    regex lists is one utils/timer.py defines and the package uses
    (tests/test_scopes.py holds the constants to the lowered programs).

CPU, test size, the one-chip and four-chip device learners reached as
tests/test_scopes.py reaches them; nothing here reads a time. Nothing under
`benchmark/` is changed.
"""
import importlib.util
import json
import pathlib
import re

import numpy as np
import pytest
import scipy.sparse as sp

import lightgbm_tpu as lgb
from lightgbm_tpu import tracing
from lightgbm_tpu.ops import predict as predict_mod
from lightgbm_tpu.treelearner import serial as serial_mod
from lightgbm_tpu.treelearner.device import DeviceTreeLearner
from lightgbm_tpu.utils import timer
from lightgbm_tpu.utils.timer import global_timer

PACKAGE = pathlib.Path(lgb.__file__).parent
BENCH = PACKAGE.parent / "benchmark"
METRICS = {path.stem: json.loads(path.read_text())
           for path in sorted((BENCH / "metrics").glob("*.json"))}
NOTE_READERS = ("flight_notes", "notes_ratio", "ops_per_note")
SCOPE_READERS = ("trace_scope", "scope_roofline")
PARAMS = {"objective": "binary", "num_leaves": 15, "verbosity": -1}
TREES = 3


def _metrics(readers, key=None) -> list:
    return [name for name, spec in METRICS.items()
            if spec["reader"] in readers and (key is None or key in spec)]


def _notes_sum():
    """The benchmark's own sum of a field over a kind's notes."""
    spec = importlib.util.spec_from_file_location(
        "bench_flight_notes", BENCH / "readers" / "flight_notes.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.notes_sum


def _data(n, f=8, seed=3):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    return X, (X[:, 0] - 0.7 * X[:, 1] + 0.3 * rng.randn(n) > 0).astype(float)


def _device_run(mp, params, n, interpret=True):
    """lgb.train on the device learner, as a cell trains."""
    if interpret:
        mp.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    mp.setattr(serial_mod, "on_tpu", lambda: True)
    X, y = _data(n)
    bst = lgb.train(dict(PARAMS, **params), lgb.Dataset(X, label=y),
                    num_boost_round=TREES)
    assert isinstance(bst._gbdt.tree_learner, DeviceTreeLearner)


def _train(mp):
    _device_run(mp, {}, 1531)


def _train_quant(mp):
    _device_run(mp, {"use_quantized_grad": True}, 1529, interpret=False)


def _train_rank(mp):
    """Ragged queries of 1 to 60 documents and a validation set."""
    mp.setattr(serial_mod, "on_tpu", lambda: True)
    rng = np.random.RandomState(5)
    sizes = rng.randint(1, 61, size=30)
    X = rng.randn(int(sizes.sum()), 8)
    grades = np.clip(np.round(X[:, 0] + 0.5 * rng.randn(len(X)) + 1), 0, 4)
    ds = lgb.Dataset(X, label=grades, group=sizes)
    valid = lgb.Dataset(X[:int(sizes[:10].sum())],
                        label=grades[:int(sizes[:10].sum())],
                        group=sizes[:10], reference=ds)
    lgb.train({"objective": "lambdarank", "metric": "ndcg", "eval_at": [3],
               "num_leaves": 7, "min_data_in_leaf": 1, "verbosity": -1},
              ds, num_boost_round=TREES, valid_sets=[valid])


def _train_cat(mp):
    """Two integer-coded categorical columns that carry the label."""
    mp.setattr(serial_mod, "on_tpu", lambda: True)
    rng = np.random.RandomState(11)
    n = 1523
    X = rng.randn(n, 6)
    X[:, 0] = rng.randint(0, 12, n)
    X[:, 1] = rng.randint(0, 5, n)
    y = (np.isin(X[:, 0], [1, 4, 7, 9]) ^ (X[:, 2] > 0.8)).astype(float)
    ds = lgb.Dataset(X, label=y, categorical_feature=[0, 1])
    valid = lgb.Dataset(X[:300], label=y[:300], reference=ds)
    bst = lgb.train(dict(PARAMS, metric="auc"), ds, num_boost_round=TREES,
                    valid_sets=[valid])
    assert type(bst._gbdt.tree_learner) is DeviceTreeLearner


def _train_efb(mp):
    """Scipy CSR: three numerical columns and two one-hot blocks, which
    the data set bundles."""
    mp.setattr(serial_mod, "on_tpu", lambda: True)
    rng = np.random.RandomState(13)
    n = 1521
    a, b = rng.randint(0, 16, n), rng.randint(0, 6, n)
    dense = np.zeros((n, 3 + 16 + 6))
    dense[:, :3] = rng.randn(n, 3)
    dense[np.arange(n), 3 + a] = 1.0
    dense[np.arange(n), 19 + b] = 1.0
    y = (np.isin(a, [2, 5, 11]) ^ (dense[:, 0] > 0.5)).astype(float)
    ds = lgb.Dataset(sp.csr_matrix(dense), label=y,
                     params={"max_conflict_rate": 0.0})
    bst = lgb.train(dict(PARAMS, max_conflict_rate=0.0), ds,
                    num_boost_round=TREES)
    assert any(g.is_multi for g in ds._handle.groups)
    assert type(bst._gbdt.tree_learner) is DeviceTreeLearner


def _train_4chip(mp):
    """tree_learner=data num_machines=4 on the conftest's fake devices:
    the rows stay sharded on the mesh."""
    _device_run(mp, {"tree_learner": "data", "num_machines": 4}, 1527)


def _predict_batch(mp):
    """A forest loaded from its text and scored in one call, packed as a
    TPU packs it (the gather-free program)."""
    X, y = _data(617, seed=4)
    text = lgb.train(dict(PARAMS, num_leaves=7), lgb.Dataset(X, label=y),
                     num_boost_round=TREES).model_to_string()
    mp.setattr(predict_mod, "on_tpu", lambda: True)
    bst = lgb.Booster(model_str=text)
    tracing.recorder().reset()
    bst.predict(X)


RUNS = {"train": _train, "train_quant": _train_quant,
        "train_rank": _train_rank, "train_cat": _train_cat,
        "train_efb": _train_efb, "train_4chip": _train_4chip,
        "predict_batch": _predict_batch}


class _Runs:
    """Each kind's run made once, on first use: its notes and the labels
    of the host spans it opened."""

    def __init__(self):
        self._done = {}

    def __call__(self, metric: str):
        kind = metric.split(".")[0]
        if kind not in self._done:
            self._done[kind] = self._run(RUNS[kind])
        return self._done[kind]

    @staticmethod
    def _run(run):
        opened = []
        mp = pytest.MonkeyPatch()
        mp.setattr(global_timer, "enabled", True)
        mp.setattr(global_timer, "span_hook",
                   lambda label, t0, t1: opened.append(label))
        tracing.recorder().reset()
        try:
            run(mp)
            recorder = tracing.recorder()
            assert not recorder.dropped
            return recorder.snapshot(), set(opened)
        finally:
            mp.undo()
            global_timer.new_epoch()


@pytest.fixture(scope="module")
def runs():
    return _Runs()


def test_every_metric_has_a_known_kind():
    assert {name.split(".")[0] for name in METRICS} == set(RUNS)


@pytest.mark.parametrize("metric", _metrics(NOTE_READERS))
def test_a_run_of_the_kind_leaves_the_notes_the_metric_reads(runs, metric):
    spec = METRICS[metric]
    notes, _ = runs(metric)
    notes_sum = _notes_sum()
    field = notes_sum(notes, spec["kind"], spec["field"],
                      float("-inf"), float("inf"))
    assert field is not None, (spec["kind"], spec["field"],
                               sorted({n["kind"] for n in notes}))
    if spec["reader"] == "notes_ratio":
        over = notes_sum(notes, spec["kind"], spec["over"],
                         float("-inf"), float("inf"))
        assert over, (spec["kind"], spec["over"])
    if spec["reader"] == "ops_per_note":
        assert field > 0


@pytest.mark.parametrize("metric", _metrics(("trace_idle_in_span",)))
def test_a_run_of_the_kind_opens_the_span_the_metric_reads(runs, metric):
    _, opened = runs(metric)
    assert METRICS[metric]["span"] in opened, sorted(opened)


@pytest.fixture(scope="module")
def used_scopes() -> set:
    """The values of the SCOPE_* constants that a module of the package
    other than utils/timer.py names."""
    source = "\n".join(path.read_text() for path in PACKAGE.rglob("*.py")
                       if path != PACKAGE / "utils" / "timer.py")
    return {value for name, value in vars(timer).items()
            if name.startswith("SCOPE_") and name != "SCOPE_PREFIX"
            and re.search(rf"\b{name}\b", source)}


@pytest.mark.parametrize("metric", _metrics(SCOPE_READERS, key="scope"))
def test_every_scope_the_metric_reads_is_one_the_package_uses(used_scopes,
                                                              metric):
    pattern = METRICS[metric]["scope"]
    inner = re.fullmatch(r"\^lgbm\\\.\(?([a-z_|]+)\)?\$", pattern)
    assert inner, pattern
    for name in inner.group(1).split("|"):
        scope = timer.SCOPE_PREFIX + name
        assert re.search(pattern, scope)
        assert scope in used_scopes, scope
