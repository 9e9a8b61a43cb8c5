"""ICI-sharded whole-tree learner: bit-identity with the single-chip wave
learner on the 8 fake CPU devices conftest forces.

Bit-identity strategy per variant:

* plain / bagged — gh is GRID-SNAPPED (multiples of 2^-10, |v| <= 1, ~1k
  rows), so every f32 partial sum is exact in ANY summation order: the
  per-shard-then-psum reduction produces the same bits as the single-device
  full-N reduction, and the whole split log must match exactly.
* quantized — gradients are int8 and the histogram pool int32; integer
  addition commutes exactly, so the FULL GBDT driver (same PRNG stream,
  renewal densified to one device) is bit-identical end to end.

The only tolerance anywhere is on pure DIAGNOSTIC scalars: the recorded
split gain (XLA fuses its arithmetic differently in the two compiled
programs) and the tree's hessian-weight display fields (f32 sums whose
row order differs across shards). Thresholds, chosen features, child
sums/counts, leaf outputs and predictions are compared bit for bit.

Plus the ICI gauge: `device_ici_bytes_per_wave` is O(K*F_pad*Bmax*CH) —
independent of the row count — which is the whole point of data-parallel
sharding (docs/PERF_NOTES.md round-6 comm model).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Dataset as CoreDataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.parallel.learners import DeviceDataParallelTreeLearner
from lightgbm_tpu.treelearner.device import DeviceTreeLearner
from lightgbm_tpu.utils.timer import global_timer


def _snap(v):
    """Snap to the 2^-10 grid: f32 sums of ~1k such values are exact in
    any association order (integers < 2^24 in units of 2^-10)."""
    return np.round(np.clip(v, -1.0, 1.0) * 1024.0) / 1024.0


def _snapped_gh(rng, n):
    g = _snap(rng.uniform(-1.0, 1.0, n)).astype(np.float32)
    h = _snap(rng.uniform(0.25, 1.0, n)).astype(np.float32)
    gh = np.stack([g, h, np.ones(n, np.float32)], axis=1)
    return jnp.asarray(np.concatenate([gh, np.zeros((1, 3), np.float32)]))


def _learner(cls, X, y, params):
    cfg = Config(params)
    ds = CoreDataset.from_matrix(X, label=y, config=cfg)
    return cls(cfg, ds)


# Diagnostic scalars that ride on f32 rounding, not on the split decision:
# split_gain picks up XLA fusion differences between the two compiled
# programs, and the *_weight fields are per-leaf f32 hessian sums whose
# row order differs across shards. Everything else must match bit for bit.
_ULP_FIELDS = {"split_gain", "internal_weight", "leaf_weight"}


def _assert_same_trees(a, b):
    assert len(a) == len(b)
    for ta, tb in zip(a, b):
        for k, va in ta.__dict__.items():
            vb = tb.__dict__[k]
            if k in _ULP_FIELDS:
                np.testing.assert_allclose(va, vb, rtol=1e-6, err_msg=k)
            elif isinstance(va, np.ndarray):
                np.testing.assert_array_equal(va, vb, err_msg=k)
            else:
                assert va == vb, k


@pytest.mark.parametrize("bagged", [False, True])
def test_sharded_split_log_bit_identical(rng, bagged):
    """One tree, grid-snapped gh: the device split log (rec_store) and the
    final per-row leaf ids of the sharded learner must match the
    single-device wave learner bit for bit."""
    n = 1100
    X = rng.randn(n, 7)
    y = (X[:, 0] - 0.5 * X[:, 1] > 0).astype(float)
    gh_ext = _snapped_gh(rng, n)
    params = {"objective": "binary", "num_leaves": 15,
              "min_data_in_leaf": 5, "verbosity": -1}
    bag = (np.sort(np.random.RandomState(3).choice(n, 800, replace=False))
           .astype(np.int32) if bagged else None)

    logs, trees, ids = [], [], []
    for cls in (DeviceTreeLearner, DeviceDataParallelTreeLearner):
        learner = _learner(cls, X, y, params)
        pending = learner.train_async(gh_ext, bag)
        logs.append(np.asarray(pending.rec_store))
        trees.append(learner.finalize(pending))
        ids.append(np.asarray(learner.partition.ids_host))
    # col 4 is the packed SplitInfo gain scalar: its arithmetic picks up
    # XLA fusion differences between the two programs (1-ulp wobble); every
    # decision-bearing column — feature, threshold, sums, counts, outputs —
    # must be exact.
    gain_col = 4
    np.testing.assert_allclose(logs[0][:, gain_col], logs[1][:, gain_col],
                               rtol=1e-6)
    mask = np.ones(logs[0].shape[1], bool)
    mask[gain_col] = False
    np.testing.assert_array_equal(logs[0][:, mask], logs[1][:, mask])
    np.testing.assert_array_equal(ids[0], ids[1])
    _assert_same_trees(trees[:1], trees[1:])
    assert trees[0].num_leaves > 2  # the comparison saw a real tree


def test_sharded_quantized_driver_bit_identical(rng):
    """Quantized path through the FULL driver: int32 histogram reduction is
    exact under any order, the PRNG rounding stream is shared, and leaf
    renewal densifies — tree decisions, leaf values and predictions match
    exactly (weight diagnostics to 1 ulp, see module docstring)."""
    n = 1200
    X = rng.randn(n, 6)
    y = (X[:, 0] - 0.6 * X[:, 1] + rng.randn(n) * 0.3 > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
              "use_quantized_grad": True, "quant_train_renew_leaf": True}
    out = []
    for cls in (DeviceTreeLearner, DeviceDataParallelTreeLearner):
        cfg = Config(params)
        ds = CoreDataset.from_matrix(X, label=y, config=cfg)
        bst = GBDT(cfg, ds, create_objective("binary", cfg))
        bst.tree_learner = cls(cfg, ds)
        for _ in range(4):
            if bst.train_one_iter():
                break
        bst.to_model()
        out.append(bst)
    single, sharded = out
    _assert_same_trees(single.models, sharded.models)
    np.testing.assert_array_equal(
        np.asarray(single.predict(X, raw_score=True)),
        np.asarray(sharded.predict(X, raw_score=True)))


def test_sharded_learner_is_actually_sharded(rng):
    """The carry really spans the mesh: the bin plane and the program's
    leaf ids are laid out over all 8 fake devices, the split log is
    replicated, and growth commits the same tree everywhere. What the
    learner hands on (`gather_leaf_ids`) is the ids without the padding on
    the mesh's first device, where the score update reads them."""
    n = 900
    X = rng.randn(n, 6)
    y = (X[:, 0] > 0).astype(float)
    learner = _learner(DeviceDataParallelTreeLearner, X, y,
                       {"objective": "binary", "num_leaves": 7,
                        "verbosity": -1})
    assert learner.D == 8
    assert len(learner.bins_dev.sharding.device_set) == 8
    program_ids, gather = [], learner._gather_leaf_ids
    learner._gather_leaf_ids = lambda ids: program_ids.append(ids) or gather(
        ids)
    pending = learner.train_async(_snapped_gh(rng, n))
    assert len(program_ids[0].sharding.device_set) == 8
    assert program_ids[0].shape == (learner.n_pad,)
    assert pending.leaf_id.sharding.device_set == {
        learner.mesh.devices.flat[0]}
    assert pending.leaf_id.shape == (n,)
    tree = learner.finalize(pending)
    assert tree.num_leaves > 1
    assert learner.partition.ids_host.shape == (n,)


def test_ici_bytes_gauge_independent_of_rows(rng):
    """The comm-volume claim the docs make: per-wave ICI traffic is
    O(K * F_pad * Bmax * CH) and does NOT scale with N. max_bin=16 so both
    datasets saturate the bin budget and differ ONLY in row count."""
    params = {"objective": "binary", "num_leaves": 7, "max_bin": 16,
              "verbosity": -1}
    gauges = []
    for n in (600, 2400):
        X = rng.randn(n, 6)
        y = (X[:, 0] > 0).astype(float)
        learner = _learner(DeviceDataParallelTreeLearner, X, y, params)
        global_timer.counters.pop("device_ici_bytes_per_wave", None)
        learner.finalize(learner.train_async(_snapped_gh(rng, n)))
        gauges.append(global_timer.counters["device_ici_bytes_per_wave"])
    assert gauges[0] == gauges[1], gauges
    assert gauges[0] > 0


def test_factory_routes_data_to_host_learner_on_cpu(rng):
    """On the CPU backend device growth never applies, so tree_learner=data
    keeps selecting the host-driven data-parallel learner (the fallback
    path the sharded learner is documented to leave intact)."""
    from lightgbm_tpu.parallel.learners import (DataParallelTreeLearner,
                                                create_parallel_learner)

    X = rng.randn(300, 5)
    y = (X[:, 0] > 0).astype(float)
    cfg = Config({"objective": "binary", "num_leaves": 7,
                  "num_machines": 8, "verbosity": -1})
    ds = CoreDataset.from_matrix(X, label=y, config=cfg)
    learner = create_parallel_learner("data", cfg, ds)
    assert isinstance(learner, DataParallelTreeLearner)
    assert not isinstance(learner, DeviceDataParallelTreeLearner)


# ------------------------------------------------- rows resident on the mesh
#
# A run whose learner offers a row layout keeps its scores, gradients and
# leaf ids there between trees (models/resident.py). The fallback on the
# same mesh is the same run with the offer withdrawn: the code every run
# took until PR 37.

ROUNDS = 5
CROSS_CHIP = ("all-gather", "all-to-all", "collective-permute", "all-reduce")


def _device_growth_on_cpu(monkeypatch):
    from lightgbm_tpu.treelearner import serial as serial_mod

    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    monkeypatch.setattr(serial_mod, "on_tpu", lambda: True)


def _no_row_layout(monkeypatch):
    monkeypatch.setattr(DeviceDataParallelTreeLearner, "row_layout",
                        lambda self: None)


def _wave_notes():
    from lightgbm_tpu import tracing

    return [n for n in tracing.recorder().snapshot()
            if n["kind"] == "tree_wave"]


def _train(params, X, y, rounds=ROUNDS, **dataset):
    import lightgbm_tpu as lgb
    from lightgbm_tpu import tracing

    tracing.recorder().reset()
    bst = lgb.train(dict(params, verbosity=-1),
                    lgb.Dataset(X, label=y, **dataset),
                    num_boost_round=rounds)
    # the parameters' echo names the learner; the trees are what is compared
    text = bst.model_to_string().split("\nparameters:")[0]
    return bst._gbdt, text, _wave_notes()


def _labelled(rng, objective, n=1300):
    X = rng.randn(n, 7)
    z = X[:, 0] - 0.5 * X[:, 1] + 0.3 * rng.randn(n)
    return X, (z > 0).astype(float) if objective == "binary" else z


@pytest.mark.parametrize("objective", ["binary", "regression"])
def test_resident_rows_grow_the_fallbacks_and_one_devices_trees(
        rng, monkeypatch, objective):
    """Five trees over 1,300 rows, which no four tile-aligned shards hold
    without a pad: model text and final scores bit for bit those of the
    fallback on the same mesh, and of the one-device learner where the
    mesh's own float32 sums let them be (binary here; the regression run's
    histograms associate differently over four shards, with or without
    the layout, so there the splits are the same and the values close)."""
    _device_growth_on_cpu(monkeypatch)
    X, y = _labelled(rng, objective)
    params = {"objective": objective, "num_leaves": 15,
              "min_data_in_leaf": 5}
    mesh = dict(params, tree_learner="data", num_machines=4)
    g, text, notes = _train(mesh, X, y)
    assert type(g.tree_learner) is DeviceDataParallelTreeLearner
    assert g.tree_learner.n_pad > len(y) and g._rows is not None
    assert [n["rows_resident"] for n in notes] == [1] * ROUNDS
    assert g.score.shape == (1, len(y))
    assert g.tree_learner.partition.ids_host.shape == (len(y),)
    score = np.asarray(g.score)

    one, one_text, one_notes = _train(params, X, y)
    assert type(one.tree_learner) is DeviceTreeLearner
    assert [n["rows_resident"] for n in one_notes] == [0] * ROUNDS
    if objective == "binary":
        assert one_text == text
        np.testing.assert_array_equal(np.asarray(one.score), score)
    else:
        for a, b in zip(one.models, g.models):
            inner = a.num_leaves - 1
            np.testing.assert_array_equal(a.split_feature[:inner],
                                          b.split_feature[:inner])
            np.testing.assert_array_equal(a.threshold_in_bin[:inner],
                                          b.threshold_in_bin[:inner])
        np.testing.assert_allclose(np.asarray(one.score), score, rtol=1e-5,
                                   atol=1e-6)

    _no_row_layout(monkeypatch)
    back, back_text, back_notes = _train(mesh, X, y)
    assert back._rows is None
    assert [n["rows_resident"] for n in back_notes] == [0] * ROUNDS
    assert back_text == text
    np.testing.assert_array_equal(np.asarray(back.score), score)


def test_resident_rows_never_leave_the_learners_layout(rng, monkeypatch):
    """After an iteration the score, the gradients handed to the tree and
    the tree's leaf ids carry the learner's row sharding; the compiled
    gradient, pack, leaf-id and update programs hold no collective, each
    chip working on its own rows; readers outside the iteration get N
    rows, and the training metric reads the view."""
    from lightgbm_tpu.models import gbdt as gbdt_mod
    from lightgbm_tpu.parallel import learners as learners_mod

    _device_growth_on_cpu(monkeypatch)
    X, y = _labelled(rng, "binary")
    handed = []
    grow = DeviceDataParallelTreeLearner._grow
    monkeypatch.setattr(
        DeviceDataParallelTreeLearner, "_grow",
        lambda self, gh, ids, *a, **k: handed.append((gh.sharding,
                                                      ids.sharding))
        or grow(self, gh, ids, *a, **k))
    g, _, _ = _train({"objective": "binary", "num_leaves": 15,
                      "min_data_in_leaf": 5, "tree_learner": "data",
                      "num_machines": 4, "metric": "auc"}, X, y, rounds=2)
    learner, rows = g.tree_learner, g._rows
    layout = learner.row_layout()
    n_pad = layout.n_pad
    assert rows.score.shape == (n_pad,)
    assert rows.score.sharding.is_equivalent_to(layout.rows, 1)
    ids = learner.partition.leaf_ids_dev()
    assert ids.shape == (n_pad,)
    assert ids.sharding.is_equivalent_to(layout.rows, 1)
    assert np.asarray(ids)[len(y):].tolist() == [-1] * (n_pad - len(y))
    for gh, leaf in handed:
        assert gh.is_equivalent_to(
            jax.sharding.NamedSharding(learner.mesh,
                                       jax.sharding.PartitionSpec("data")), 2)
        assert leaf.is_equivalent_to(layout.rows, 1)
    grad, hess = rows.gradients()
    assert grad.sharding.is_equivalent_to(layout.rows, 1)
    pack = np.asarray(rows.programs.pack(grad, hess))
    assert (pack[len(y):] == 0).all() and (pack[:len(y), 2] == 1).all()
    lv = jnp.zeros(15, jnp.float32)
    programs = {
        "gradients": rows.programs.gradients.lower(rows.score,
                                                   rows._constants),
        "pack": rows.programs.pack.lower(grad, hess),
        "leaf ids": learners_mod._root_leaf_ids.lower(
            layout.num_data, n_pad, layout.rows),
        "update": gbdt_mod._add_leaf_values_to_score.lower(rows.score, ids,
                                                           lv),
        "update from the log": gbdt_mod._apply_split_log_to_score.lower(
            rows.score, jnp.zeros((14, 16), jnp.float32), ids,
            jnp.float32(0.5), num_leaves=15),
    }
    for name, lowered in programs.items():
        text = lowered.compile().as_text()
        assert [op for op in CROSS_CHIP if f" {op}(" in text] == [], name
    (_, _, auc, _), = g.eval_train()
    assert 0.9 < auc <= 1.0


def _ranking(rng, n=1200, per_query=20):
    X = rng.randn(n, 7)
    grades = np.clip(np.round(X[:, 0] + 0.5 * rng.randn(n) + 1.5), 0, 4)
    return X, grades, {"group": [per_query] * (n // per_query)}


FALLBACKS = {
    "bagging": {"tree_learner": "data", "bagging_fraction": 0.7,
                "bagging_freq": 1},
    "goss": {"tree_learner": "data", "data_sample_strategy": "goss"},
    "lambdarank": {"tree_learner": "data", "objective": "lambdarank"},
    "feature": {"tree_learner": "feature"},
    "linear_tree": {"tree_learner": "data", "linear_tree": True},
}


@pytest.mark.parametrize("case", sorted(FALLBACKS))
def test_a_run_outside_the_row_layout_grows_the_trees_it_grew(
        rng, monkeypatch, case):
    """What the layout cannot serve keeps the score on one device, says
    `rows_resident` 0 in every note and, where its learner has a layout to
    offer, grows the trees it grows with the offer withdrawn. A bag's tree
    is grown through the learner: the driver's out-of-bag update refuses a
    plane on the mesh beside a score on one chip, as it did before the
    layout (ROADMAP S8)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu import tracing
    from lightgbm_tpu.models.gbdt import _pack_gh

    _device_growth_on_cpu(monkeypatch)
    rounds = 4
    params = dict({"objective": "binary", "num_leaves": 7,
                   "min_data_in_leaf": 5, "num_machines": 4},
                  **FALLBACKS[case])
    if case == "lambdarank":
        X, y, dataset = _ranking(rng)
    else:
        (X, y), dataset = _labelled(rng, "binary", n=1200), {}
    if case in ("bagging", "goss"):
        g = lgb.Booster(dict(params, verbosity=-1),
                        lgb.Dataset(X, label=y))._gbdt
        assert type(g.tree_learner) is DeviceDataParallelTreeLearner
        assert g.tree_learner.row_layout() is not None and g._rows is None
        tracing.recorder().reset()
        bag = np.sort(rng.choice(len(y), 800, replace=False)).astype(np.int32)
        tree = g.tree_learner.train(_pack_gh(*g._grad_fn(g.score[0])), bag)
        assert tree.num_leaves > 1
        assert [n["rows_resident"] for n in _wave_notes()] == [0]
        assert g.tree_learner.partition.ids_host.shape == (len(y),)
        return
    g, text, notes = _train(params, X, y, rounds, **dataset)
    assert g._rows is None and g.score.shape == (1, len(y))
    assert len(g.models) == rounds
    assert all(n["rows_resident"] == 0 for n in notes)
    offer = getattr(g.tree_learner, "row_layout", None)
    if offer is None or offer() is None:
        # the host-driven learner (linear leaves), replicated rows
        assert case in ("linear_tree", "feature")
        return
    assert len(notes) == rounds
    _no_row_layout(monkeypatch)
    _, withdrawn, _ = _train(params, X, y, rounds, **dataset)
    assert withdrawn == text


def test_voting_takes_the_row_layout_and_elects_the_same_trees(
        rng, monkeypatch):
    """The voting learner shares the data learner's rows and its dispatch:
    with the rows resident its trees are those of its fallback."""
    from lightgbm_tpu.parallel.learners import VotingDataParallelTreeLearner

    _device_growth_on_cpu(monkeypatch)
    X, y = _labelled(rng, "binary", n=1200)
    params = {"objective": "binary", "num_leaves": 7, "min_data_in_leaf": 5,
              "tree_learner": "voting", "num_machines": 4, "top_k": 3}
    g, text, notes = _train(params, X, y, 4)
    assert type(g.tree_learner) is VotingDataParallelTreeLearner
    assert [n["rows_resident"] for n in notes] == [1] * 4
    monkeypatch.setattr(VotingDataParallelTreeLearner, "row_layout",
                        lambda self: None)
    back, withdrawn, notes = _train(params, X, y, 4)
    assert [n["rows_resident"] for n in notes] == [0] * 4
    assert withdrawn == text
    np.testing.assert_array_equal(np.asarray(back.score),
                                  np.asarray(g.score))
