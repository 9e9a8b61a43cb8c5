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
