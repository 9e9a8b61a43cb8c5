"""Pod-scale sharded streaming: the ISSUE acceptance suite.

Locks the gang-sharded composition of the out-of-core stack
(streaming/sharded.py, docs/STREAMING.md "Pod-scale streaming"):

  * sharded-vs-single bit-identity — tree_learner=data + a budget 4x
    smaller than the plane on the 8-virtual-device mesh trains byte-
    identical models to the single-shard streamed learner, across
    plain / bagged / quantized (the quantized leg exercises the real
    psum merge; float legs exercise the canonical-fold fallback);
  * global-sketch binning — the rank-merged sketch fit reproduces the
    raw-prefix fit (cut points, EFB groups, the whole plane) byte-for-
    byte independent of shard count / block placement;
  * elastic survival — a worker lost mid-refit surfaces the typed
    WorkerLostError, and an 8-shard flywheel resumed over 4 surviving
    shards trains byte-identical to the undisturbed run;
  * ragged kernel equality — the per-block ragged Pallas histogram in
    interpret mode matches the XLA scatter fold (bit-exact end-to-end
    for quantized; bit-exact at the histogram level for float when the
    gh values are snapped to an exactly-summable grid);
  * the two rider regressions — the _BlockCache eviction race under
    threads, and merge_ranked's arrival-order invariance.
"""
import threading

import jax
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.engine import train
from lightgbm_tpu.io.dataset import Dataset as CoreDataset
from lightgbm_tpu.parallel import elastic
from lightgbm_tpu.parallel.elastic import WorkerLostError
from lightgbm_tpu.streaming import (ContinuousTrainer, PodDriftMonitor,
                                    RowBlockStore, ShardedRowBlockStore,
                                    ShardedStreamedTreeLearner, merge_ranked)
from lightgbm_tpu.streaming.drift import QuantileSketch
from lightgbm_tpu.streaming.learner import (BLOCK_ROWS_ENV, BUDGET_ENV,
                                            RAGGED_ENV, _BlockCache,
                                            StreamedTreeLearner)
from lightgbm_tpu.utils import faults
from lightgbm_tpu.utils.log import LightGBMError
from lightgbm_tpu.utils.timer import global_timer

BASE = {"objective": "binary", "num_leaves": 15, "learning_rate": 0.1,
        "verbosity": -1, "min_data_in_leaf": 5}
MESH_ENV = "LGBM_TPU_FORCE_MESH_DEVICES"


@pytest.fixture(autouse=True)
def _disarm():
    yield
    faults.clear()
    elastic.clear()


def _data(seed=3, n=2048, f=12):
    rng = np.random.RandomState(seed)
    X = rng.standard_normal((n, f)).astype(np.float32)
    y = (X[:, 0] - 0.5 * X[:, 1] + rng.standard_normal(n) * 0.3 > 0)
    return X, y.astype(np.float64)


def _model(params, X, y, rounds=5):
    return train(dict(params), lgb.Dataset(X, label=y),
                 num_boost_round=rounds)


def _plane_bytes(params, X, y):
    core = CoreDataset.from_matrix(X, label=y, config=Config(dict(params)))
    return core.bins.size * core.bins.dtype.itemsize, core.bins.shape[0]


def _need_mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device virtual mesh")


# ------------------------------------------- sharded-vs-single bit-identity

@pytest.mark.parametrize("extra", [
    pytest.param({}, id="plain", marks=pytest.mark.slow),
    pytest.param({"bagging_fraction": 0.7, "bagging_freq": 1}, id="bagged",
                 marks=pytest.mark.slow),
    pytest.param({"use_quantized_grad": True}, id="quantized"),
])
def test_sharded_streamed_bit_identical_starved_budget(monkeypatch, extra):
    """THE tentpole bound: the gang-sharded streamed learner at a budget
    4x smaller than the plane trains byte-identical to the single-shard
    streamed learner (which is itself bit-identical to resident)."""
    _need_mesh()
    X, y = _data()
    params = {**BASE, "tree_learner": "data", **extra}
    plane, groups = _plane_bytes(params, X, y)
    block_bytes = groups * 256  # uint8 plane
    monkeypatch.setenv(BLOCK_ROWS_ENV, "256")
    monkeypatch.setenv(BUDGET_ENV, str(2 * block_bytes))
    assert plane >= 4 * (2 * block_bytes)

    # a forced 1-device mesh makes the sharded learner the parent
    # streamed learner exactly (no cache wrap, canonical fold)
    monkeypatch.setenv(MESH_ENV, "1")
    single = _model(params, X, y)
    monkeypatch.setenv(MESH_ENV, "8")
    sharded = _model(params, X, y)

    assert global_timer.counters["stream_shards"] == 8
    assert single.model_to_string() == sharded.model_to_string()
    np.testing.assert_array_equal(
        np.asarray(single.predict(X, raw_score=True)),
        np.asarray(sharded.predict(X, raw_score=True)))


def test_sharded_wire_cost_is_n_independent(monkeypatch):
    """Quantized gang merge moves one [G, B, 3] int32 histogram per rank
    per wave — the gauge must equal that closed form and not move with
    the row count."""
    _need_mesh()
    params = {**BASE, "tree_learner": "data", "use_quantized_grad": True}
    monkeypatch.setenv(BLOCK_ROWS_ENV, "256")
    monkeypatch.setenv(BUDGET_ENV, "64k")
    monkeypatch.setenv(MESH_ENV, "8")

    def wire(n):
        X, y = _data(n=n)
        bst = _model(params, X, y, rounds=2)
        learner = bst._gbdt.tree_learner
        assert isinstance(learner, ShardedStreamedTreeLearner)
        expect = (len(learner.dataset.groups)
                  * learner.group_bin_padded * 3 * 4)
        got = global_timer.counters["stream_ici_bytes_per_wave"]
        assert got == expect
        assert global_timer.counters["device_ici_bytes_per_wave"] == expect
        return got

    assert wire(1024) == wire(2048)


def test_streaming_factory_routes_data_to_sharded(monkeypatch):
    X, y = _data(n=512)
    monkeypatch.setenv(BUDGET_ENV, "64k")
    bst = lgb.Booster(params={**BASE, "tree_learner": "data"},
                      train_set=lgb.Dataset(X, label=y))
    learner = bst._gbdt.tree_learner
    assert isinstance(learner, ShardedStreamedTreeLearner)
    assert isinstance(learner, StreamedTreeLearner)
    assert learner.bins_dev is None  # the plane never uploads whole


@pytest.mark.parametrize("kind", ["feature", "voting"])
def test_streaming_rejects_plane_resident_learners(monkeypatch, kind):
    X, y = _data(n=512)
    monkeypatch.setenv(BUDGET_ENV, "64k")
    with pytest.raises(LightGBMError, match="serial or data only"):
        train({**BASE, "tree_learner": kind}, lgb.Dataset(X, label=y),
              num_boost_round=1)


# --------------------------------------------- global-sketch binning fit

def _sparse_chunks(seed=11, n=1500, f=8):
    """float64 rows with a sparse tail (EFB-eligible zeros) and planted
    NaNs so the surrogate's NaN-tail scatter is exercised."""
    rng = np.random.RandomState(seed)
    X = rng.standard_normal((n, f))
    X[X < -1.2] = 0.0
    nan_pos = rng.rand(n, f) < 0.01
    X[nan_pos] = np.nan
    y = (np.nan_to_num(X[:, 0]) + 0.3 * np.nan_to_num(X[:, 1]) > 0)
    return X, y.astype(np.float64)


@pytest.mark.parametrize("shards", [4, 7])
def test_sharded_fit_matches_raw_prefix_fit(shards):
    """The rank-merged sketch fit must reproduce the raw-prefix one-shot
    fit byte-for-byte — cut points, EFB group lists, the whole binned
    plane — for ANY shard count / block placement."""
    X, y = _sparse_chunks()
    params = dict(BASE)

    def fill(store):
        for lo in range(0, 1500, 256):
            hi = min(1500, lo + 256)
            store.push_rows(X[lo:hi], label=y[lo:hi])
        return store

    base = fill(RowBlockStore(params=params, bin_sample_rows=1024))
    sh = fill(ShardedRowBlockStore(params=params, bin_sample_rows=1024,
                                   num_shards=shards))
    assert base._layout is not None and sh._layout is not None
    assert len(sh._layout.mappers) == len(base._layout.mappers)
    for ma, mb in zip(base._layout.mappers, sh._layout.mappers):
        assert ma.num_bin == mb.num_bin
        assert np.array_equal(np.asarray(ma.bin_upper_bound, dtype=float),
                              np.asarray(mb.bin_upper_bound, dtype=float),
                              equal_nan=True)
    assert sh._group_lists == base._group_lists  # EFB bundles byte-equal
    a, b = base.finalize(), sh.finalize()
    assert np.array_equal(a.bins, b.bins)
    np.testing.assert_array_equal(np.asarray(a.metadata.label),
                                  np.asarray(b.metadata.label))

    # the sketch merge actually ran (and was timed)
    assert global_timer.counters["stream_sketch_merges"] >= 1
    assert "stream_sketch_merge_us" in global_timer.counters

    pushed = train(dict(params), sh.to_basic_dataset(params=params),
                   num_boost_round=4)
    direct = train(dict(params), base.to_basic_dataset(params=params),
                   num_boost_round=4)
    assert pushed.model_to_string() == direct.model_to_string()


def test_shard_watermarks_pin_round_robin_placement():
    X, y = _data(n=900, f=6)
    store = ShardedRowBlockStore(params=dict(BASE), num_shards=4)
    sizes = [256, 256, 256, 132]
    lo = 0
    for sz in sizes:
        store.push_rows(X[lo:lo + sz], label=y[lo:lo + sz])
        lo += sz
    # placement pinned at push: block i -> shard i % 4
    assert store._block_owner == [0, 1, 2, 3]
    assert [store.shard_rows(r) for r in range(4)] == sizes
    assert sum(store.shard_rows(r) for r in range(4)) == 900
    # reshard re-takes placements round-robin over the surviving world
    store.reshard(2)
    assert store.num_shards == 2
    assert store._block_owner == [0, 1, 0, 1]
    assert store.shard_rows(0) == 256 + 256
    assert store.shard_rows(1) == 256 + 132


def test_pod_drift_alarm_refresh_deterministic(monkeypatch):
    """Gang-merged drift: the planted shift trips the pod alarm, the
    sketch-driven refresh lands, and both — plus the refreshed cut
    points — replay byte-identically (the merged state is a pure
    function of the pushed stream)."""
    monkeypatch.setenv("LGBM_TPU_DRIFT", "1")
    monkeypatch.setenv("LGBM_TPU_DRIFT_CHECK_ROWS", "512")

    def run():
        faults.clear()
        faults.install("drift_shift@1024:0")
        rng = np.random.RandomState(3)
        X = rng.standard_normal((3072, 8))
        y = (X[:, 1] + 0.3 * X[:, 2] > 0).astype(np.float64)
        store = ShardedRowBlockStore(params=dict(BASE),
                                     bin_sample_rows=1024, num_shards=4)
        for lo in range(0, 3072, 256):
            store.push_rows(X[lo:lo + 256], label=y[lo:lo + 256])
        mon = store._drift
        assert isinstance(mon, PodDriftMonitor)
        assert mon.alarmed and mon.alarm_feature == 0
        assert store.maybe_refresh_bins() is True
        assert store.layout_generation == 1
        cuts = [tuple(m.bin_upper_bound) for m in store._layout.mappers]
        return cuts, store.finalize().bins

    cuts1, bins1 = run()
    cuts2, bins2 = run()
    assert cuts1 == cuts2
    assert np.array_equal(bins1, bins2)


# ------------------------------------------------------ elastic survival

def test_sharded_stream_worker_lost_is_typed(monkeypatch):
    """A gang peer lost mid-train under the sharded streamed learner
    surfaces the typed WorkerLostError — rank + last-good iteration —
    within the watchdog timeout."""
    _need_mesh()
    monkeypatch.setenv(BUDGET_ENV, "64k")
    monkeypatch.setenv(BLOCK_ROWS_ENV, "256")
    monkeypatch.setenv(MESH_ENV, "8")
    X, y = _data(n=600)
    params = {**BASE, "tree_learner": "data", "use_quantized_grad": True}
    # warm the jit caches before the watchdog is armed: the deadline must
    # measure the planted hang, not a compile. Every iteration the armed run
    # reaches (0, 1 and 2, where the hang is planted) compiles programs of
    # its own (a leaf's padded row bucket is a shape), so the warm-up runs
    # the same three on the same data; one round left iterations 1 and 2 to
    # compile against the 2 s deadline, and under six loaded workers lost
    warm = 3
    train(dict(params), lgb.Dataset(X, label=y), num_boost_round=warm)
    elastic.install(timeout_s=2.0)
    faults.install("worker_hang@0:2")
    with pytest.raises(WorkerLostError) as ei:
        train(dict(params), lgb.Dataset(X, label=y), num_boost_round=6)
    assert ei.value.rank == 0
    assert ei.value.last_good_iteration == 2


@pytest.mark.slow  # heavy full-training driver: tier-1 keeps the quantized starved-budget bound
def test_worker_lost_mid_refit_shrinks_8_to_4_bit_identical(tmp_path,
                                                            monkeypatch):
    """THE shrink-to-fit contract at pod scale: a worker lost mid-refit
    on the 8-shard flywheel rolls the generation back (watermark stays
    pinned), the store re-shards over the 4 survivors, and the resumed
    refit is byte-identical to the undisturbed 8-shard run."""
    _need_mesh()
    monkeypatch.setenv(BUDGET_ENV, "64k")
    monkeypatch.setenv(BLOCK_ROWS_ENV, "256")
    X, y = _data(seed=42, n=1200, f=10)
    params = {**BASE, "tree_learner": "data", "use_quantized_grad": True}

    def filled():
        s = ShardedRowBlockStore(params=params)
        for lo in range(0, 1200, 300):
            s.push_rows(X[lo:lo + 300], label=y[lo:lo + 300])
        return s

    monkeypatch.setenv(MESH_ENV, "8")
    clean = ContinuousTrainer(params, filled(), num_boost_round=4,
                              checkpoint_dir=str(tmp_path / "clean"))
    straight = clean.step()
    assert straight is not None

    store = filled()
    assert store.num_shards == 8
    tr = ContinuousTrainer(params, store, num_boost_round=4,
                           checkpoint_dir=str(tmp_path / "crashy"))
    elastic.install(timeout_s=2.0)
    faults.install("worker_hang@0:2")
    assert tr.step() is None          # worker lost mid-refit: no publish
    faults.clear()
    elastic.clear()
    assert tr.generation == 0         # generation did NOT advance
    assert tr._inflight_rows == 1200  # watermark stays pinned

    # the gang shrank to 4 survivors: re-shard the block store and the
    # mesh, then resume — the plane and merged drift state are
    # placement-independent, so the retry reproduces the 8-shard bits
    store.reshard(4)
    assert store.num_shards == 4
    monkeypatch.setenv(MESH_ENV, "4")
    resumed = tr.step()
    assert resumed is not None
    assert tr.generation == 1
    assert resumed.model_to_string() == straight.model_to_string()


# ------------------------------------------------- ragged kernel equality

@pytest.mark.slow  # heavy full-training driver: tier-1 keeps the quantized starved-budget bound
def test_ragged_interpret_bit_identical_quantized(monkeypatch):
    """End-to-end: the ragged per-block kernel (interpret mode) and the
    XLA scatter fold train byte-identical quantized models — int32
    accumulation is exact under any block order."""
    X, y = _data(n=1024)
    params = {**BASE, "use_quantized_grad": True}
    plane, groups = _plane_bytes(params, X, y)
    monkeypatch.setenv(BLOCK_ROWS_ENV, "256")
    monkeypatch.setenv(BUDGET_ENV, str(2 * groups * 256))

    monkeypatch.setenv(RAGGED_ENV, "0")
    scatter = _model(params, X, y)
    before = global_timer.counters.get("stream_ragged_leaves", 0)
    monkeypatch.setenv(RAGGED_ENV, "interpret")
    ragged = _model(params, X, y)
    assert global_timer.counters["stream_ragged_leaves"] > before
    assert scatter.model_to_string() == ragged.model_to_string()


@pytest.mark.slow  # heavy full-training driver: tier-1 keeps the quantized starved-budget bound
def test_ragged_interpret_matches_scatter_float_snapped(monkeypatch):
    """Histogram-level float equality: with gh snapped to the 2^-10 grid
    (partial sums exact in f32 under ANY association) and f32 kernel
    operands forced, the ragged kernel must reproduce the scatter fold
    bit-for-bit over every index-set shape."""
    monkeypatch.setenv(BLOCK_ROWS_ENV, "256")
    monkeypatch.setenv(BUDGET_ENV, "64k")
    X, y = _data(n=1500, f=6)
    bst = _model(BASE, X, y, rounds=1)
    learner = bst._gbdt.tree_learner
    assert isinstance(learner, StreamedTreeLearner)

    import jax.numpy as jnp
    gh = np.asarray(learner._gh)
    snapped = np.round(np.clip(gh, -1.0, 1.0) * 1024.0) / 1024.0
    learner._gh = jnp.asarray(snapped.astype(np.float32))
    monkeypatch.setenv("LGBM_TPU_HIST_F32", "1")

    n = learner.num_data
    for idx in (np.arange(0, n, 2),            # strided across all blocks
                np.arange(300, 520),           # straddles a block boundary
                np.asarray([7, 263, 519, 1033, 1499])):  # sparse tiles
        a = np.asarray(learner._hist_over_indices(idx.astype(np.int64)))
        b = np.asarray(learner._ragged_over_indices(idx.astype(np.int64),
                                                    interpret=True))
        np.testing.assert_array_equal(a, b)


# ------------------------------------------------------ rider regressions

def test_block_cache_concurrent_get_prefetch_evict():
    """The LRU race regression: concurrent get/prefetch across threads
    with a 2-slot cache (eviction on almost every access) must neither
    corrupt the maps nor serve wrong block contents."""
    rng = np.random.RandomState(0)
    plane = rng.randint(0, 255, size=(4, 4096)).astype(np.uint8)
    cache = _BlockCache(plane, 256, capacity=2, upload_dtype=None)
    errors = []

    def worker(seed):
        r = np.random.RandomState(seed)
        try:
            for _ in range(300):
                b = int(r.randint(cache.n_blocks))
                if r.rand() < 0.5:
                    cache.prefetch((b + 1) % cache.n_blocks)
                lo, hi = cache.block_range(b)
                if not np.array_equal(np.asarray(cache.get(b)),
                                      plane[:, lo:hi]):
                    errors.append(("wrong-bytes", b))
        except Exception as e:  # noqa: BLE001 - the assertion target
            errors.append(("raised", repr(e)))

    threads = [threading.Thread(target=worker, args=(s,)) for s in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    assert len(cache._resident) <= cache.capacity


def test_merge_ranked_is_arrival_order_invariant():
    """The sketch-merge canonicalization regression: merging the same
    shard sketches in ANY arrival order yields byte-identical merged
    state (rank order is the merge order, not arrival)."""
    rng = np.random.RandomState(1)
    shards = []
    for _ in range(5):
        sk = QuantileSketch(64)
        for _ in range(6):
            sk.update(rng.standard_normal(200))  # forces compaction
        shards.append(sk)

    ref = merge_ranked([(r, sk.copy()) for r, sk in enumerate(shards)])
    ref_sample = ref.quantile_sample(256)
    assert ref.nonzero_n == sum(sk.nonzero_n for sk in shards)

    for seed in range(5):
        order = np.random.RandomState(seed).permutation(5)
        merged = merge_ranked([(int(r), shards[int(r)].copy())
                               for r in order])
        np.testing.assert_array_equal(merged.quantile_sample(256),
                                      ref_sample)

    with pytest.raises(ValueError, match="distinct ranks"):
        merge_ranked([(0, shards[0].copy()), (0, shards[1].copy())])
