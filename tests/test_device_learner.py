"""Whole-tree-on-device learner: parity with the host-driven serial learner.

The factory only selects DeviceTreeLearner on accelerators (its masked
full-N histograms are MXU-cheap but CPU-slow), so these tests instantiate it
directly on small data.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Dataset as CoreDataset
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.treelearner.device import WORK_FIELDS, DeviceTreeLearner
from lightgbm_tpu.treelearner.serial import SerialTreeLearner


def _boosters(X, y, params, n_iters):
    cfg = Config(params)
    ds = CoreDataset.from_matrix(X, label=y, config=cfg)
    out = []
    for cls in (SerialTreeLearner, DeviceTreeLearner):
        obj = create_objective(cfg.objective, cfg)
        bst = GBDT(cfg, ds, obj)
        bst.tree_learner = cls(cfg, ds)
        for _ in range(n_iters):
            if bst.train_one_iter():
                break
        out.append(bst)
    return out


@pytest.mark.parametrize("params", [
    {"objective": "binary", "num_leaves": 15, "verbosity": -1},
    {"objective": "binary", "num_leaves": 7, "max_depth": 3,
     "min_data_in_leaf": 40, "verbosity": -1},
    {"objective": "regression", "num_leaves": 15, "lambda_l1": 0.5,
     "lambda_l2": 2.0, "verbosity": -1},
])
def test_device_matches_serial(rng, params):
    X = rng.randn(1500, 8)
    if params["objective"] == "binary":
        y = (X[:, 0] - 0.7 * X[:, 1] + rng.randn(1500) * 0.3 > 0).astype(float)
    else:
        y = 2 * X[:, 0] - X[:, 1] + 0.2 * rng.randn(1500)
    serial, device = _boosters(X, y, params, n_iters=6)
    np.testing.assert_allclose(serial.predict(X, raw_score=True),
                               device.predict(X, raw_score=True),
                               rtol=1e-4, atol=1e-5)


def test_device_with_bagging(rng):
    X = rng.randn(1200, 8)
    y = (X[:, 0] + rng.randn(1200) * 0.3 > 0).astype(float)
    cfg = Config({"objective": "binary", "num_leaves": 7, "verbosity": -1})
    ds = CoreDataset.from_matrix(X, label=y, config=cfg)
    obj = create_objective("binary", cfg)
    bst = GBDT(cfg, ds, obj)
    bst.tree_learner = DeviceTreeLearner(cfg, ds)
    import jax.numpy as jnp

    grads, hesses = bst._grad_fn(bst.score[0])
    gh = jnp.concatenate([jnp.stack([grads, hesses,
                                     jnp.ones_like(grads)], axis=1),
                          jnp.zeros((1, 3), jnp.float32)])
    bag = np.sort(np.random.RandomState(0).choice(1200, 800, replace=False))
    tree = bst.tree_learner.train(gh, bag)
    assert tree.num_leaves > 1
    part = bst.tree_learner.partition
    total = sum(part.count(i) for i in range(tree.num_leaves))
    assert total == 800
    # out-of-bag rows keep leaf -1
    assert (part.ids_host == -1).sum() == 400


def test_device_stops_on_no_gain(rng):
    # constant labels -> no positive gain -> single-leaf tree
    X = rng.randn(400, 4)
    y = np.ones(400)
    cfg = Config({"objective": "regression", "num_leaves": 31,
                  "boost_from_average": False, "verbosity": -1})
    ds = CoreDataset.from_matrix(X, label=y, config=cfg)
    obj = create_objective("regression", cfg)
    bst = GBDT(cfg, ds, obj)
    bst.tree_learner = DeviceTreeLearner(cfg, ds)
    stop = bst.train_one_iter()
    # first tree fits the mean; second should find nothing
    stop2 = bst.train_one_iter()
    assert stop or stop2


def test_device_hist_rows_counter(rng):
    """Rows histogrammed per tree must be O(rows in selected leaves):
    root N + sum of smaller-child rows <= ~2N for a full leaf-wise tree,
    NOT O(N * waves). Narrow waves force many waves so the old full-N
    formulation would blow far past the bound."""
    from lightgbm_tpu.utils.timer import global_timer

    n = 2000
    X = rng.randn(n, 8)
    y = 2 * X[:, 0] - X[:, 1] + np.sin(3 * X[:, 2]) + 0.1 * rng.randn(n)
    cfg = Config({"objective": "regression", "num_leaves": 31,
                  "min_data_in_leaf": 5, "verbosity": -1})
    ds = CoreDataset.from_matrix(X, label=y, config=cfg)
    obj = create_objective("regression", cfg)
    bst = GBDT(cfg, ds, obj)
    learner = DeviceTreeLearner(cfg, ds)
    learner.wave_k = 4  # many waves: the O(N * waves) failure mode is loud
    bst.tree_learner = learner
    global_timer.counters.pop("device_hist_rows", None)
    bst.train_one_iter()
    assert learner.last_hist_rows > 0
    # root pass = N rows; each of the <=30 splits histograms the SMALLER
    # child (<= half its parent), summing to <= N per depth level of work;
    # 4N is a generous ceiling that O(N*waves) (>= 8N here) cannot meet
    assert learner.last_hist_rows <= 4 * n, learner.last_hist_rows
    assert global_timer.counters["device_hist_rows"] == learner.last_hist_rows
    assert "device_hist_rows" in global_timer.report()
    # the XLA bodies (no kernel here) walk no row tile and move no pair
    assert set(learner.last_work) == set(WORK_FIELDS)
    assert not any(learner.last_work.values())


@pytest.mark.slow  # tier-1 budget triage: heavy full-training driver, runs in the slow tier
def test_device_pallas_interpret_matches_serial(rng, monkeypatch):
    """End-to-end coverage of the Pallas ragged-histogram + compaction wave
    path on CPU via interpret mode (on TPU this is the production path)."""
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    # f32 operands: parity with the serial learner to float tolerance (the
    # TPU-default bf16 operands round gh to 8 mantissa bits by design)
    monkeypatch.setenv("LGBM_TPU_HIST_F32", "1")
    from lightgbm_tpu.treelearner import device as device_mod

    device_mod.grow_tree_on_device.clear_cache()
    try:
        X = rng.randn(1200, 6)
        y = (X[:, 0] - 0.6 * X[:, 1] + rng.randn(1200) * 0.3 > 0).astype(float)
        params = {"objective": "binary", "num_leaves": 7, "verbosity": -1}
        serial, device = _boosters(X, y, params, n_iters=2)
        np.testing.assert_allclose(serial.predict(X, raw_score=True),
                                   device.predict(X, raw_score=True),
                                   rtol=1e-4, atol=1e-5)
    finally:
        device_mod.grow_tree_on_device.clear_cache()


def _device_booster(X, y, params, n_iters, probe=None):
    cfg = Config(params)
    ds = CoreDataset.from_matrix(X, label=y, config=cfg)
    obj = create_objective(cfg.objective, cfg)
    bst = GBDT(cfg, ds, obj)
    bst.tree_learner = DeviceTreeLearner(cfg, ds)
    stopped_at = None
    for it in range(n_iters):
        if bst.train_one_iter():
            stopped_at = it
            break
        if probe is not None:
            probe(bst, it)
    bst.to_model()  # flushes any in-flight async tree
    return bst, stopped_at


def _assert_same_models(a, b):
    assert len(a.models) == len(b.models)
    for ta, tb in zip(a.models, b.models):
        for k, va in ta.__dict__.items():
            vb = tb.__dict__[k]
            if isinstance(va, np.ndarray):
                np.testing.assert_array_equal(va, vb, err_msg=k)
            else:
                assert va == vb, k


def test_async_pipeline_bit_identical(rng, monkeypatch):
    """The async per-tree pipeline (device growth of tree t overlapped with
    host replay of t-1, score updated from the device split log) must be
    BIT-identical to the sync path, not merely close."""
    X = rng.randn(900, 8)
    y = (X[:, 0] - 0.7 * X[:, 1] + rng.randn(900) * 0.3 > 0).astype(float)
    # 0.5 is f32-exact, so device f32 (leaf * rate) == host f64-shrink + cast
    params = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.5,
              "min_data_in_leaf": 5, "verbosity": -1}
    monkeypatch.setenv("LGBM_TPU_ASYNC", "0")
    sync, _ = _device_booster(X, y, params, 6)
    monkeypatch.setenv("LGBM_TPU_ASYNC", "1")
    # mid-stream predict forces a flush while a tree is in flight
    asy, _ = _device_booster(
        X, y, params, 6,
        probe=lambda b, it: b.predict(X[:64], raw_score=True) if it == 2 else None)
    _assert_same_models(sync, asy)
    np.testing.assert_array_equal(np.asarray(sync.score[0]),
                                  np.asarray(asy.score[0]))
    np.testing.assert_array_equal(
        np.asarray(sync.predict(X, raw_score=True)),
        np.asarray(asy.predict(X, raw_score=True)))


def test_async_auto_gate(rng, monkeypatch):
    """Without LGBM_TPU_ASYNC the pipeline self-enables only when the
    learning rate is exactly representable in f32 (bit-identity proof
    holds); 0.1 is not f32-exact so it must stay sync."""
    monkeypatch.delenv("LGBM_TPU_ASYNC", raising=False)
    X = rng.randn(200, 4)
    y = (X[:, 0] > 0).astype(float)
    for rate, want in ((0.5, True), (0.1, False)):
        cfg = Config({"objective": "binary", "num_leaves": 7,
                      "learning_rate": rate, "verbosity": -1})
        ds = CoreDataset.from_matrix(X, label=y, config=cfg)
        bst = GBDT(cfg, ds, create_objective("binary", cfg))
        bst.tree_learner = DeviceTreeLearner(cfg, ds)
        assert bst._async_enabled() is want, rate
        monkeypatch.setenv("LGBM_TPU_ASYNC", "0")
        assert bst._async_enabled() is False
        monkeypatch.delenv("LGBM_TPU_ASYNC", raising=False)


def test_async_stops_on_no_gain(rng, monkeypatch):
    """A no-split tree is discovered one iteration late in the pipeline
    (at flush); the stub and its zero-delta duplicate are both unwound so
    the surviving model list matches the sync run exactly."""
    monkeypatch.setenv("LGBM_TPU_ASYNC", "0")
    X = rng.randn(400, 4)
    y = np.ones(400)
    params = {"objective": "regression", "num_leaves": 31,
              "learning_rate": 0.5, "boost_from_average": False,
              "verbosity": -1}
    sync, stop_sync = _device_booster(X, y, params, 6)
    monkeypatch.setenv("LGBM_TPU_ASYNC", "1")
    asy, stop_async = _device_booster(X, y, params, 6)
    assert stop_sync is not None and stop_async is not None
    # the pipeline may report the stop at most one iteration later
    assert stop_async <= stop_sync + 1
    _assert_same_models(sync, asy)
    assert sync.iter_ == asy.iter_


_PLANE_VARIANTS = {
    "plain": {},
    "bagged": {"bagging_fraction": 0.7, "bagging_freq": 1, "seed": 7},
    "quantized": {"use_quantized_grad": True, "quant_train_renew_leaf": True},
}


@pytest.mark.parametrize("variant,interpret", [
    ("plain", False), ("bagged", False), ("quantized", False),
    # interpret-mode legs pay Python per wave: slow tier (budget triage)
    pytest.param("plain", True, marks=pytest.mark.slow),
    pytest.param("quantized", True, marks=pytest.mark.slow),
])
def test_device_uint8_vs_i32_bit_identical(rng, monkeypatch, variant,
                                           interpret):
    """The narrow uint8 bin plane is a pure transport change: forcing the
    int32 escape hatch (LGBM_TPU_BINS_I32=1) must reproduce the same trees,
    predictions and hist-rows counter BIT for bit — on the XLA fallback and
    through the Pallas kernels in interpret mode."""
    import jax.numpy as jnp
    from lightgbm_tpu.treelearner import device as device_mod

    if interpret:
        monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    device_mod.grow_tree_on_device.clear_cache()
    try:
        n = 600 if interpret else 1000
        n_iters = 2 if interpret else 4
        X = rng.randn(n, 6)
        y = (X[:, 0] - 0.6 * X[:, 1] + rng.randn(n) * 0.3 > 0).astype(float)
        params = {"objective": "binary", "num_leaves": 7, "verbosity": -1,
                  **_PLANE_VARIANTS[variant]}
        monkeypatch.delenv("LGBM_TPU_BINS_I32", raising=False)
        b8, _ = _device_booster(X, y, params, n_iters)
        assert b8.tree_learner.bins_dev.dtype == jnp.uint8
        rows8 = b8.tree_learner.last_hist_rows
        monkeypatch.setenv("LGBM_TPU_BINS_I32", "1")
        b32, _ = _device_booster(X, y, params, n_iters)
        assert b32.tree_learner.bins_dev.dtype == jnp.int32
        _assert_same_models(b8, b32)
        np.testing.assert_array_equal(
            np.asarray(b8.predict(X, raw_score=True)),
            np.asarray(b32.predict(X, raw_score=True)))
        assert rows8 == b32.tree_learner.last_hist_rows
    finally:
        device_mod.grow_tree_on_device.clear_cache()


def test_device_learner_quantized_matches_serial_quantized(rng):
    """Quantized int8/int32 path in the fori_loop learner: identical int
    gradients (same PRNG seed + call order) must reproduce the serial
    quantized learner's trees exactly."""
    n = 1500
    X = rng.randn(n, 6)
    y = (X[:, 0] - 0.5 * X[:, 1] + rng.randn(n) * 0.3 > 0).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "use_quantized_grad": True, "quant_train_renew_leaf": True}
    serial_b, device_b = _boosters(X, y, params, 8)
    p_serial = serial_b.predict(X)
    p_device = device_b.predict(X)
    np.testing.assert_allclose(p_device, p_serial, rtol=1e-4, atol=1e-5)
    acc = np.mean((p_device > 0.5) == y)
    assert acc > 0.9, acc


# -- the wave width changes the work, never the trees ----------------------

def test_quantized_trees_identical_at_any_wave_width(rng):
    """A wave partitions and histograms K candidate splits; the replay then
    commits them in exact best-first order from the same records. So K
    decides how much speculative work a tree costs and nothing else: with
    use_quantized_grad (integer histogram sums, exact in any order) the
    trees grown at K = 21 and at K = 8 are the same byte for byte. (In
    float the XLA body's one-hot contraction is 3*K columns wide and
    XLA:CPU sums the rows in another order at another width, so there they
    agree to rounding only.)"""
    from lightgbm_tpu.utils.timer import global_timer

    n = 1200
    X = rng.randn(n, 8)
    y = 2 * X[:, 0] - X[:, 1] + np.sin(3 * X[:, 2]) + 0.1 * rng.randn(n)
    params = {"objective": "regression", "num_leaves": 31,
              "min_data_in_leaf": 5, "verbosity": -1,
              "use_quantized_grad": True}

    def grow(wave_k):
        global_timer.counters.pop("device_hist_rows", None)
        cfg = Config(params)
        ds = CoreDataset.from_matrix(X, label=y, config=cfg)
        bst = GBDT(cfg, ds, create_objective(cfg.objective, cfg))
        bst.tree_learner = DeviceTreeLearner(cfg, ds)
        assert bst.tree_learner.wave_k == 21
        bst.tree_learner.wave_k = wave_k
        for _ in range(3):
            assert not bst.train_one_iter()
        bst.to_model()  # flushes any in-flight async tree
        assert global_timer.counters["wave_k"] == wave_k
        return bst, int(global_timer.counters["device_hist_rows"])

    wide, rows_wide = grow(21)
    narrow, rows_narrow = grow(8)
    _assert_same_models(wide, narrow)
    np.testing.assert_array_equal(
        np.asarray(wide.predict(X, raw_score=True)),
        np.asarray(narrow.predict(X, raw_score=True)))
    # and the width did change the work: fewer speculative leaves a wave
    assert rows_narrow < rows_wide, (rows_narrow, rows_wide)


@pytest.mark.parametrize("num_leaves, want", [(255, 21), (31, 21), (7, 7),
                                              (2, 2)])
def test_wave_width_is_the_constant_capped_by_the_leaves(rng, num_leaves,
                                                         want):
    """`wave` is WAVE_K for every learner; `wave_k`, the width a wave can
    use and every gauge and note reports, is capped by num_leaves, on one
    chip and sharded alike (the sharded learners had a method of their own
    for it while a controller could move the one-chip learner's)."""
    from lightgbm_tpu.parallel.learners import DeviceDataParallelTreeLearner
    from lightgbm_tpu.treelearner.device import WAVE_K

    X = rng.randn(300, 4)
    cfg = Config({"objective": "binary", "num_leaves": num_leaves,
                  "verbosity": -1})
    ds = CoreDataset.from_matrix(X, label=(X[:, 0] > 0).astype(float),
                                 config=cfg)
    for cls in (DeviceTreeLearner, DeviceDataParallelTreeLearner):
        learner = cls(cfg, ds)
        assert (learner.wave, learner.wave_k) == (WAVE_K, want) == (21, want)


def test_learner_state_with_a_wave_width_still_restores(rng):
    """A learner state written while the width was a controller's variable
    may hold a `wave_k`: restoring ignores it and keeps the constant."""
    X = rng.randn(300, 4)
    cfg = Config({"objective": "binary", "num_leaves": 15, "verbosity": -1,
                  "feature_fraction": 0.5})
    ds = CoreDataset.from_matrix(X, label=(X[:, 0] > 0).astype(float),
                                 config=cfg)
    old = DeviceTreeLearner(cfg, ds)
    old.col_sampler.reset_by_tree()  # move the sampler's stream
    state = dict(old.snapshot_state(), wave_k=8)
    new = DeviceTreeLearner(cfg, ds)
    new.restore_snapshot_state(state)
    assert new.wave_k == 15
    np.testing.assert_array_equal(new.col_sampler.reset_by_tree(),
                                  old.col_sampler.reset_by_tree())


# -- device-resident GOSS (round 8) ---------------------------------------

_GOSS_PARAMS = {"objective": "binary", "num_leaves": 15,
                "learning_rate": 0.5, "data_sample_strategy": "goss",
                "top_rate": 0.2, "other_rate": 0.1,
                "min_data_in_leaf": 5, "verbosity": -1}


def _goss_booster(X, y, mode, monkeypatch, cls=DeviceTreeLearner,
                  params=None):
    monkeypatch.setenv("LGBM_TPU_GOSS_DEVICE", mode)
    cfg = Config(params or _GOSS_PARAMS)
    ds = CoreDataset.from_matrix(X, label=y, config=cfg)
    bst = GBDT(cfg, ds, create_objective(cfg.objective, cfg))
    bst.tree_learner = cls(cfg, ds)
    for _ in range(8):  # warm-up ends at iter 2 (1/0.5); GOSS active after
        if bst.train_one_iter():
            break
    bst.to_model()
    return bst


@pytest.mark.parametrize("cls", [DeviceTreeLearner, SerialTreeLearner])
def test_goss_device_bit_identical_to_host(rng, monkeypatch, cls):
    """The device-resident GOSS selection consumes the MT19937 stream
    exactly like the host path (both reduce to permutation(n_rest)[:k])
    and scores with the same f32 value chain, so the bags — and therefore
    the trained models — must match BIT for bit on both learners (the
    serial learner exercises DeviceBag's lazy host-index materialization
    and the OOB score path)."""
    n = 900
    X = rng.randn(n, 8)
    y = (X[:, 0] - 0.7 * X[:, 1] + rng.randn(n) * 0.3 > 0).astype(float)
    b_dev = _goss_booster(X, y, "1", monkeypatch, cls)
    b_host = _goss_booster(X, y, "0", monkeypatch, cls)
    _assert_same_models(b_dev, b_host)
    np.testing.assert_array_equal(np.asarray(b_dev.score[0]),
                                  np.asarray(b_host.score[0]))
    np.testing.assert_array_equal(
        np.asarray(b_dev.predict(X, raw_score=True)),
        np.asarray(b_host.predict(X, raw_score=True)))


def test_goss_device_multiclass_bit_identical(rng, monkeypatch):
    """Multiclass gradients are [C, N]: the per-class |g·h| terms must be
    added in the same fixed class order on both paths or the f32 sort keys
    — and the bags — drift."""
    n = 900
    X = rng.randn(n, 6)
    y = (rng.rand(n) * 3).astype(int).astype(float)
    params = {**_GOSS_PARAMS, "objective": "multiclass", "num_class": 3}
    b_dev = _goss_booster(X, y, "1", monkeypatch, SerialTreeLearner,
                          params=params)
    b_host = _goss_booster(X, y, "0", monkeypatch, SerialTreeLearner,
                           params=params)
    _assert_same_models(b_dev, b_host)
    np.testing.assert_array_equal(
        np.asarray(b_dev.predict(X, raw_score=True)),
        np.asarray(b_host.predict(X, raw_score=True)))


def test_goss_device_selection_is_sync_free(rng, monkeypatch):
    """ISSUE round-8 acceptance: zero per-iteration host gathers on the
    sampling path. The sanitizer asserts no countable device sync happens
    inside the goss_device_select scope while the bag is drawn on device
    (SyncInScopeError would fail the run)."""
    from lightgbm_tpu.utils import sanitize

    sanitize.enable()
    sanitize.reset()
    try:
        n = 900
        X = rng.randn(n, 8)
        y = (X[:, 0] - 0.7 * X[:, 1] + rng.randn(n) * 0.3 > 0).astype(float)
        b = _goss_booster(X, y, "1", monkeypatch)
        assert len(b.models) > 0
        # the device select actually ran (its jit was built) ...
        assert b.sample_strategy._select_jit is not None
        # ... and recorded no syncs under its scope (enforced live by
        # _note_sync, but assert the ledger agrees)
        counts = sanitize.sync_counts()
        assert not counts.get("goss_device_select"), counts
    finally:
        sanitize.clear_override()
        sanitize.reset()


# ---------------------------------------------------------------- rows on the lanes
# A custom call's operand layout is fixed, and XLA carries it back into the
# glue that makes the operand: one [Np, k<128] operand of a kernel pads k to
# 128 lanes in HBM (2.15 GB for 4M int32) and drags the wave's routing
# arithmetic onto 8 useful values a vector register (PERF.md, PR 29). What
# guards the orientation is a shape property of the traced program.

class _Traced(Exception):
    """Carries a program's jaxpr out of the learner's dispatch."""


def _trace_instead_of_running(fn, *_):
    def dispatch(*args, **kwargs):
        raise _Traced(fn.trace(*args, **kwargs).jaxpr)

    return dispatch


def _eqns(jaxpr):
    """Every equation of a jaxpr, nested programs included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (tuple, list))
                        else (value,)):
                inner = getattr(sub, "jaxpr", sub)  # ClosedJaxpr -> Jaxpr
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _whole_tree_program(rng, monkeypatch, learner, params=None, env=None):
    """The jaxpr of the whole-tree program `learner` would dispatch for one
    iteration (kernels on the interpreted Pallas path), caught untraced."""
    from lightgbm_tpu.parallel import learners
    from lightgbm_tpu.treelearner import device as device_mod

    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    for name, value in (env or {}).items():
        monkeypatch.setenv(name, value)
    monkeypatch.setattr(device_mod.sanitize, "guard",
                        _trace_instead_of_running)
    X = rng.randn(1500, 8)
    y = (X[:, 0] - 0.7 * X[:, 1] + 0.3 * rng.randn(1500) > 0).astype(float)
    cfg = Config({"objective": "binary", "num_leaves": 15, "verbosity": -1,
                  **(params or {})})
    ds = CoreDataset.from_matrix(X, label=y, config=cfg)
    bst = GBDT(cfg, ds, create_objective(cfg.objective, cfg))
    # the switches are read while the program is traced: no trace of another
    # case may answer for this one
    device_mod.grow_tree_on_device.clear_cache()
    try:
        bst.tree_learner = getattr(learners, learner)(cfg, ds)
        with pytest.raises(_Traced) as caught:
            bst.train_one_iter()
    finally:
        device_mod.grow_tree_on_device.clear_cache()
    return caught.value.args[0].jaxpr


@pytest.mark.parametrize("learner,params,env,kernels", [
    ("DeviceTreeLearner", {}, {}, 3),
    # the bagged tree compacts the in-bag rows to the front first
    ("DeviceTreeLearner", {"bagging_fraction": 0.5, "bagging_freq": 1}, {},
     4),
    ("DeviceTreeLearner", {"use_quantized_grad": True}, {}, 3),
    ("DeviceTreeLearner", {}, {"LGBM_TPU_BINS_I32": "1"}, 3),
    ("DeviceDataParallelTreeLearner", {}, {}, 3),
    ("VotingDataParallelTreeLearner", {}, {}, 3),
    ("DeviceFeatureParallelTreeLearner", {}, {}, 3),
], ids=["plain", "bagged", "quantized", "int32_plane", "data_parallel",
        "voting", "feature"])
def test_no_kernel_operand_has_rows_on_the_sublanes(
        rng, monkeypatch, learner, params, env, kernels):
    """Every per-row operand and result of the whole-tree program's
    pallas_calls is [k, Np], rows on the minor axis, never [Np, k<128]."""
    program = _whole_tree_program(rng, monkeypatch, learner, params, env)
    calls = [e for e in _eqns(program) if e.primitive.name == "pallas_call"]
    assert len(calls) == kernels
    for eqn in calls:
        shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
        n_rows = max(s[-1] for s in shapes if len(s) == 2)  # the bin plane's
        assert n_rows % 1024 == 0 and n_rows >= 1024
        per_row = [s for s in shapes if n_rows in s]
        assert len(per_row) >= 3  # plane, payload, slot or dst (+ results)
        for shape in per_row:
            assert len(shape) == 2 and shape[-1] == n_rows, (
                eqn.params.get("name"), shapes)


# ---------------------------------------------------------------- one scan a wave
# Compaction's glue reads the rows once (PR 33): a wave takes ONE cumulative
# sum over the rows (the left mask's) and derives the pair tables from its
# samples, [K, T] arithmetic. A second scan, or a per-tile min/max of the
# destinations under a class mask ([T, COMPACT_TILE] operands, 2K of them a
# wave), is the glue this replaced: 300 of 1,894 device-ms a tree at
# 10.5 M rows (PERF.md, PR 33).

@pytest.mark.parametrize("learner", ["DeviceTreeLearner",
                                     "DeviceDataParallelTreeLearner"])
def test_a_wave_scans_the_rows_once(rng, monkeypatch, learner):
    from lightgbm_tpu.ops.compact_pallas import COMPACT_TILE

    program = list(_eqns(_whole_tree_program(rng, monkeypatch, learner)))
    # the wave is the body of the program's one while loop over the rows
    waves = [e for e in program if e.primitive.name == "while"
             and any(q.primitive.name == "pallas_call"
                     for q in _eqns(e.params["body_jaxpr"].jaxpr))]
    assert len(waves) == 1
    wave = list(_eqns(waves[0].params["body_jaxpr"].jaxpr))
    compact = [e for e in wave if e.primitive.name == "pallas_call"
               and "compact" in str(e.params.get("name"))]
    assert len(compact) == 1
    n_rows = compact[0].invars[-1].aval.shape[-1]  # dst [1, Np]
    assert n_rows % COMPACT_TILE == 0
    scans = [e for e in wave if e.primitive.name.startswith("cum")
             and n_rows in e.invars[0].aval.shape]
    assert [e.primitive.name for e in scans] == ["cumsum"]
    per_tile = (n_rows // COMPACT_TILE, COMPACT_TILE)
    assert not [e for e in wave
                if e.primitive.name in ("reduce_min", "reduce_max")
                and e.invars[0].aval.shape == per_tile]
