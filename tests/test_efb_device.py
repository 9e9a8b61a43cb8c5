"""A sparse table with EFB bundles on the whole-tree device program, held
to the benchmark's plain EFB reference (benchmark/reference/gbdt_efb.py:
numpy float64 from the raw CSC columns, nothing of the program): what the
chip cell `allstate_efb.train` decides `correct` by, at test size; and the
two mechanisms under it: Dataset construction from CSR in O(entries), and
the split scan in width classes (ops/split.py `WidthClasses`: a bundle's
one-hot members gathered at their own 2 bins, not at the widest
feature's).

The table (benchmark/data_allstate.py's law, 30,000 training rows, 5,000
test rows): three numerical columns and four categorical columns one-hot
encoded into 348 columns, as scipy CSR, so that most one-hot columns are
bundled, a few levels keep groups of their own, and rare levels bundled
across columns overwrite each other on some rows. 15 leaves, three trees
through lgb.train with the test set evaluated (AUC) every tree.

The limits, each between the sound runs' largest reading at this size and
the controls' smallest (the fixture's: benchmark/tests/fixtures/efb):

  bin_bound_mismatch      0, exact;
  conflict_rows_mismatch  0, exact;
  count_mismatch          0, exact;
  leaf_value_gap          2e-4;  split_gain_gap 3e-4;  split_shortfall 1e-3;
  loss_gap                1e-5;  valid_score_gap 1e-4;  auc_gap 1e-5.
"""
import dataclasses
import importlib.util
import inspect
import json
import pathlib
import sys
import textwrap
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import lightgbm_tpu as lgb
from lightgbm_tpu import tracing
from lightgbm_tpu.config import Config
from lightgbm_tpu.io import dataset as dataset_mod
from lightgbm_tpu.metrics import binary as binary_mod
from lightgbm_tpu.io.dataset import Dataset as CoreDataset
from lightgbm_tpu.ops import split as split_mod
from lightgbm_tpu.treelearner import device as device_mod
from lightgbm_tpu.treelearner import serial as serial_mod
from lightgbm_tpu.utils.timer import global_timer

BENCH = pathlib.Path(__file__).resolve().parent.parent / "benchmark"
FIXTURE = BENCH / "tests" / "fixtures" / "efb" / "bench"
TRAFFIC = json.loads((FIXTURE / "traffic" /
                      "tiny_train_window_efb.json").read_text())
LIMITS = TRAFFIC["limits"]
TREES = 3


def _load(name: str, path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def ref():
    """benchmark/reference/gbdt_efb.py, loaded by file (benchmark/ holds a
    `trace.py` and a `data.py`, so it is never put on the path)."""
    names = ("modeltext", "reference", "reference.gbdt",
             "reference.gbdt_quant", "reference.gbdt_cat",
             "reference.gbdt_efb")
    had = {name: sys.modules.get(name) for name in names}
    _load("modeltext", BENCH / "modeltext.py")
    package = types.ModuleType("reference")
    package.__path__ = [str(BENCH / "reference")]
    sys.modules["reference"] = package
    for name in names[2:]:
        _load(name, BENCH / "reference" / (name.split(".")[1] + ".py"))
    yield sys.modules["reference.gbdt_efb"]
    for name, module in had.items():
        if module is None:
            sys.modules.pop(name, None)
        else:
            sys.modules[name] = module


@pytest.fixture(scope="module")
def cfg() -> dict:
    return json.loads((FIXTURE / "configs" / "tiny_efb.json").read_text())


@pytest.fixture(scope="module")
def sets(cfg) -> dict:
    data = _load("data_allstate", BENCH / "data_allstate.py")
    return data.make_allstate_data(cfg, 3640000101)


def _params(cfg) -> dict:
    return dict(cfg["params"], verbosity=-1)


def _train(monkeypatch, cfg, sets, trees=TREES):
    """lgb.train on the device learner (XLA bodies) with the test set
    evaluated every tree; what the benchmark's kind hands the reference,
    taken as it takes it; the booster and the layout handed over."""
    monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    monkeypatch.setenv("LGBM_TPU_HIST_F32", "1")
    monkeypatch.setattr(serial_mod, "on_tpu", lambda: True)
    X, y = sets["train"]
    ds = lgb.Dataset(X, label=y, params=_params(cfg)).construct()
    core = ds._handle
    layout = {
        "bounds": [np.asarray(m.bin_upper_bound) if f in core.used_features
                   else None for f, m in enumerate(core.mappers)],
        "bundles": [list(g.feature_indices) for g in core.groups
                    if g.is_multi],
        "conflict_rows": core.conflict_rows}
    test = lgb.Dataset(sets["test"][0], label=sets["test"][1], reference=ds)
    handed = {"train_scores": [], "test_scores": [], "auc": []}

    def after(env):
        g = env.model._gbdt
        handed["train_scores"].append(np.asarray(g.score[0]))
        handed["test_scores"].append(np.asarray(g.valid_sets[0].score[0]))
        handed["auc"].append(float(env.evaluation_result_list[0][2]))
    after.order = 100

    bst = lgb.train(_params(cfg), ds, num_boost_round=trees,
                    valid_sets=[test], valid_names=["test"],
                    callbacks=[after])
    assert type(bst._gbdt.tree_learner).__name__ == "DeviceTreeLearner"
    return handed, bst, layout


def _readings(ref, cfg, sets, handed, bst, layout, trees=TREES) -> dict:
    parsed = sys.modules["modeltext"].parse_model(bst.model_to_string())
    return ref.follow(sets, parsed, layout, cfg["params"], handed, trees)


def _failed(readings: dict) -> list:
    return sorted(k for k, v in readings.items() if not v <= LIMITS[k])


@pytest.fixture(scope="module")
def sound(cfg, sets):
    mp = pytest.MonkeyPatch()
    try:
        yield _train(mp, cfg, sets)
    finally:
        mp.undo()


# ------------------------------------------------------------ construction


def _tables(seed: int) -> list:
    """Dense float64 tables with bundles, conflicts, NaN, an all-zero
    column, a sparse numerical column with negative values (its default
    bin above 0) and explicit zeros among the stored entries."""
    rng = np.random.default_rng(seed)
    n = 4000
    X = np.zeros((n, 42))
    X[:, 0] = rng.normal(size=n)
    X[:, 1] = np.where(rng.random(n) < 0.3, rng.normal(size=n), 0)
    lev = rng.choice(30, n, p=np.r_[0.4, np.full(29, 0.6 / 29)])
    X[np.arange(n), 2 + lev] = 1.0
    X[:, 32] = np.where((lev == 0) & (rng.random(n) < 0.2),
                        rng.normal(size=n), 0)
    X[:, 33] = np.where(rng.random(n) < 0.03, -rng.random(n) - 1, 0)
    X[rng.random(n) < 0.02, 33] = np.nan
    X[rng.random(n) < 0.01, 34] = np.nan
    X[:, 36] = np.where(rng.random(n) < 0.01, 5.0, 0)   # 35 stays all-zero
    X[:, 37] = np.where(rng.random(n) < 0.002, 2.0, 0)  # conflicts in full
    X[:, 38:42] = np.where(rng.random((n, 4)) < 0.05,
                           rng.integers(1, 4, (n, 4)), 0)
    return [X]


def _same_layout(a: CoreDataset, b: CoreDataset) -> None:
    assert [g.feature_indices for g in a.groups] == \
        [g.feature_indices for g in b.groups]
    assert a.feature_to_group == b.feature_to_group
    assert a.used_features == b.used_features
    for ma, mb in zip(a.mappers, b.mappers):
        da, db = ma.to_dict(), mb.to_dict()
        assert da.keys() == db.keys()
        for key in da:
            assert np.array_equal(np.asarray(da[key]), np.asarray(db[key]),
                                  equal_nan=np.asarray(da[key]).dtype.kind
                                  == "f"), key
    assert a.bins.dtype == b.bins.dtype
    np.testing.assert_array_equal(a.bins, b.bins)
    assert a.conflict_rows == b.conflict_rows


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_sparse_construction_is_the_dense_construction(seed):
    """From CSR (float64 and float32 entries, and CSC) the mappers, the
    bundles, the plane and the conflict count are the dense matrix's, for
    the training set and for a set built with reference=."""
    X = _tables(seed)[0]
    Xv = _tables(seed + 10)[0][:1500]
    cfg = Config({"verbosity": -1, "min_data_in_bin": 1,
                  "bin_construct_sample_cnt": 2500})
    dense = CoreDataset.from_matrix(X, config=cfg)
    assert any(g.is_multi for g in dense.groups)
    assert dense.conflict_rows > 0
    dense_v = CoreDataset.from_matrix(Xv, config=cfg, reference=dense)
    for make in (sp.csr_matrix, sp.csc_matrix,
                 lambda a: sp.csr_matrix(a.astype(np.float32))):
        Xs = X.astype(np.float32) if make not in (sp.csr_matrix,
                                                  sp.csc_matrix) else X
        want = CoreDataset.from_matrix(Xs, config=cfg) if Xs is not X \
            else dense
        got = CoreDataset.from_matrix(make(X), config=cfg)
        _same_layout(want, got)
        got_v = CoreDataset.from_matrix(make(Xv), config=cfg, reference=got)
        want_v = (CoreDataset.from_matrix(Xv.astype(np.float32), config=cfg,
                                          reference=want)
                  if Xs is not X else dense_v)
        np.testing.assert_array_equal(want_v.bins, got_v.bins)


def test_sparse_construction_sums_duplicate_entries_as_toarray_does():
    X = _tables(3)[0]
    csr = sp.csr_matrix(X)
    rows = np.repeat(np.arange(X.shape[0]), np.diff(csr.indptr))
    half = csr.data / 2
    order = np.argsort(np.r_[rows, rows], kind="stable")
    dup = sp.csr_matrix((np.r_[half, half][order],
                         np.r_[csr.indices, csr.indices][order],
                         2 * csr.indptr), shape=X.shape)
    assert not dup.has_canonical_format
    cfg = Config({"verbosity": -1, "min_data_in_bin": 1})
    _same_layout(CoreDataset.from_matrix(X, config=cfg),
                 CoreDataset.from_matrix(dup, config=cfg))
    assert not dup.has_canonical_format  # the caller's matrix untouched


def test_construction_never_densifies_a_column_of_sparse_input(monkeypatch):
    """Every read of sparse input is through its entries: the dense column
    helper and every toarray refuse sparse data, and construction of the
    training set and of a reference= set still completes."""
    real = dataset_mod._column

    def dense_only(data, j):
        assert not dataset_mod._is_sparse(data), "a sparse column densified"
        return real(data, j)

    def refuse(self, *a, **k):
        raise AssertionError("a sparse matrix made dense")

    monkeypatch.setattr(dataset_mod, "_column", dense_only)
    for cls in (sp.csr_matrix, sp.csc_matrix):
        monkeypatch.setattr(cls, "toarray", refuse)
        monkeypatch.setattr(cls, "todense", refuse)
    X = _tables(4)[0]
    cfg = Config({"verbosity": -1, "min_data_in_bin": 1})
    ds = CoreDataset.from_matrix(sp.csr_matrix(X), config=cfg)
    CoreDataset.from_matrix(sp.csr_matrix(X[:500]), config=cfg, reference=ds)
    assert any(g.is_multi for g in ds.groups)


def test_construction_notes_the_layout_under_its_spans(monkeypatch):
    monkeypatch.setattr(global_timer, "enabled", True)
    X = _tables(5)[0]
    cfg = Config({"verbosity": -1, "min_data_in_bin": 1})
    n0 = tracing.recorder().total
    was = global_timer.counters["efb_conflict_rows"]
    spans = dict(global_timer.counts)
    ds = CoreDataset.from_matrix(sp.csr_matrix(X), config=cfg)
    notes = [r for r in tracing.recorder().snapshot()
             if r["kind"] == "efb_layout" and r["seq"] >= n0]
    assert len(notes) == 1
    note = notes[0]
    bundles = [g for g in ds.groups if g.is_multi]
    assert note["used_features"] == len(ds.used_features)
    assert note["groups"] == ds.num_groups
    assert note["bundles"] == len(bundles) > 0
    assert note["bundled_features"] == sum(len(g.feature_indices)
                                           for g in bundles)
    assert note["max_group_bins"] == max(g.num_total_bin for g in ds.groups)
    assert note["nnz"] == sp.csr_matrix(X).nnz
    assert note["conflict_rows"] == ds.conflict_rows > 0
    assert note["sample_conflicts"] == 0 and note["aligned"] == 0
    assert global_timer.counters["efb_conflict_rows"] - was == \
        ds.conflict_rows
    for span in ("construct_fit", "construct_bin"):
        assert global_timer.counts[span] == spans.get(span, 0) + 1


def test_max_conflict_rate_lets_conflicting_features_share_a_bundle():
    """Two sparse columns that are both set on 2 % of the rows: at the
    default rate 0 each keeps a group of its own; at 0.05 they share one
    bundle, the later column keeping the rows where both are set."""
    rng = np.random.default_rng(9)
    n = 5000
    a = np.where(rng.random(n) < 0.08, 1.0, 0.0)
    b = np.where(rng.random(n) < 0.08, 2.0, 0.0)
    both = rng.random(n) < 0.02
    a[both], b[both] = 1.0, 2.0
    X = np.column_stack([rng.normal(size=n), a, b])
    apart = CoreDataset.from_matrix(X, config=Config({"verbosity": -1}))
    assert not any(g.is_multi for g in apart.groups)
    cfg = Config({"verbosity": -1, "max_conflict_rate": 0.05})
    assert cfg.max_conflict_rate == 0.05
    shared = CoreDataset.from_matrix(X, config=cfg)
    bundle = [g for g in shared.groups if g.is_multi]
    assert [g.feature_indices for g in bundle] == [[1, 2]]
    assert shared.conflict_rows == int(np.sum((a != 0) & (b != 0)))
    with pytest.raises(Exception):
        Config({"max_conflict_rate": 1.0})


# ------------------------------------------------------- the bundle scan


def _scan_case(seed: int, dyadic: bool, monotone=None):
    """A bundled data set (one-hot members, multi-bin sparse members with
    NaN and negative values, unbundled numerical columns), a leaf's group
    histogram over some of its rows, and the totals."""
    rng = np.random.default_rng(seed)
    n = 3000
    lev = rng.choice(30, n, p=np.r_[0.5, np.full(29, 0.5 / 29)])
    cols = [rng.normal(size=n), rng.integers(0, 5, n).astype(float)]
    cols += [(lev == j).astype(float) for j in range(30)]
    r = rng.random(n)
    cols.append(np.where((lev == 0) & (r < 0.1), rng.normal(size=n), 0.0))
    cols.append(np.where((lev == 0) & (r > 0.9), -rng.random(n) - 0.1, 0.0))
    X = np.column_stack(cols)
    X[rng.random(n) < 0.02, 0] = np.nan
    X[rng.random(n) < 0.01, -2] = np.nan
    params = {"verbosity": -1, "min_data_in_bin": 1, "max_bin": 63}
    if seed % 3 == 2:
        params["zero_as_missing"] = True
    ds = CoreDataset.from_matrix(X, config=Config(params))
    if monotone is not None:
        ds.monotone_constraints = list(monotone) + [0] * (X.shape[1]
                                                          - len(monotone))
    B = int(ds.group_bin_counts().max())
    meta = split_mod.make_feature_meta(ds, B)
    g = rng.normal(size=n)
    h = rng.uniform(0.1, 1, n)
    if dyadic:  # every sum exact in float32: any order, the same bits
        g, h = np.round(g * 8) / 8, np.round(h * 8) / 8 + 0.125
    rows = rng.choice(n, 900, replace=False)
    hist = np.zeros((ds.num_groups, B, 3), np.float32)
    for gi in range(ds.num_groups):
        one = ds.construct_histogram_np(gi, g, h, rows)
        hist[gi, :one.shape[0]] = one
    hist = jnp.asarray(hist)
    return ds, meta, hist, hist[0].sum(axis=0)


_PARAMS = jnp.asarray([0.0, 1.0, 5.0, 1e-3, 0.0, 0.0, 4, 32, 10, 10, 100],
                      jnp.float32)


@jax.jit
def _gather_records(hist, tot, meta, mask=None, constraint=None,
                    penalty=None):
    """The one [F, Bmax] gather's records (a data set without a bundle)."""
    fh = split_mod.gather_feature_hist(hist, meta, tot)
    return split_mod.per_feature_best(fh, tot, meta, _PARAMS, mask,
                                      constraint, penalty)


@jax.jit
def _class_records(hist, tot, meta, mask=None, constraint=None,
                   penalty=None):
    """The width classes' records, laid back in feature order."""
    wcs = meta.width_classes
    return jnp.concatenate([
        split_mod.class_records(hist, tot, wc, _PARAMS, mask, constraint,
                                penalty) for wc in wcs.classes])[wcs.order]


def _same_records(got, want):
    """Every field but the gain to the last bit: the same feature, bin,
    lane, sums and counts, and the outputs computed from them; the gain
    within its last bits (XLA's CPU code may round one fused expression
    over arrays of another shape another way)."""
    got, want = np.asarray(got), np.asarray(want)
    np.testing.assert_array_equal(got[:, 1:], want[:, 1:])
    np.testing.assert_array_equal(np.isfinite(got[:, 0]),
                                  np.isfinite(want[:, 0]))
    ok = np.isfinite(want[:, 0])
    # a gain is the candidate's less the leaf's own: a few units in the
    # last place of the larger term
    ulps = 4 * np.spacing(np.abs(want[ok, 0]).max(initial=1.0))
    np.testing.assert_allclose(got[ok, 0], want[ok, 0], rtol=0, atol=ulps)


@pytest.mark.parametrize("seed,dyadic", [(0, False), (1, False), (2, False),
                                         (3, True), (4, True), (5, True)])
def test_the_bundle_scan_s_records_are_the_gather_scan_s(seed, dyadic):
    """The width classes against the one [F, Bmax] gather: the one-hot
    members at 2 bins, the rest (numerical columns, multi-bin members with
    NaN, zero as missing) at the widest, the same record a feature, and
    find_best_split's pick the same split."""
    ds, meta, hist, tot = _scan_case(seed, dyadic)
    wcs = meta.width_classes
    assert wcs is not None
    assert [wc.meta.max_bins for wc in wcs.classes] == [2, meta.max_bins]
    assert sum(int(wc.ids.shape[0]) for wc in wcs.classes) == \
        len(meta.real_feature)
    full = dataclasses.replace(meta, width_classes=None)
    _same_records(_class_records(hist, tot, meta),
                  _gather_records(hist, tot, full))
    got = np.asarray(split_mod.find_best_split(hist, tot, meta, _PARAMS))
    want = np.asarray(split_mod.find_best_split(hist, tot, full, _PARAMS))
    np.testing.assert_array_equal(got[1:], want[1:])


def test_the_bundle_scan_honours_masks_constraints_and_penalties():
    ds, meta, hist, tot = _scan_case(3, True, monotone=[1, -1])
    F = len(meta.real_feature)
    assert np.asarray(meta.width_classes.classes[1].meta.monotone)[:2]\
        .tolist() == [1, -1]
    rng = np.random.default_rng(1)
    mask = jnp.asarray(rng.random(F) < 0.7)
    penalty = jnp.asarray(rng.random(F).astype(np.float32))
    constraint = jnp.asarray([-0.5, 0.5], jnp.float32)
    full = dataclasses.replace(meta, width_classes=None)
    _same_records(_class_records(hist, tot, meta, mask, constraint,
                                 penalty),
                  _gather_records(hist, tot, full, mask, constraint,
                                  penalty))


def _lowered(learner, n: int) -> str:
    return device_mod.grow_tree_on_device.lower(
        jnp.zeros(learner.bins_dev.shape, learner.bins_dev.dtype),
        jnp.zeros((n, 3), jnp.float32), jnp.zeros(n, jnp.int32),
        learner.meta, learner.tables, learner.params_dev,
        jnp.ones(len(learner.meta.real_feature), bool),
        learner.config.num_leaves, learner.group_bin_padded,
        learner.config.max_depth, quantized=False, scale_vec=None,
        batch=learner.wave_k, bagged=False).as_text(debug_info=True)


def test_no_bundle_scan_lowers_without_a_bundle(cfg, sets):
    """The five numerical cells' program keeps the gather scan: a data set
    with no bundle lowers no lgbm.bundle_scan (nor its width classes);
    the same table with its bundles lowers it, and no [F, Bmax] gather."""
    X, y = sets["train"]
    params = dict(_params(cfg))
    apart = device_mod.DeviceTreeLearner(
        Config(dict(params, enable_bundle=False)),
        CoreDataset.from_matrix(X, label=y,
                                config=Config(dict(params,
                                                   enable_bundle=False))))
    assert apart.meta.width_classes is None
    text = _lowered(apart, X.shape[0])
    assert "lgbm.bundle_scan" not in text
    bundled = device_mod.DeviceTreeLearner(
        Config(params), CoreDataset.from_matrix(X, label=y,
                                                config=Config(params)))
    assert bundled.meta.width_classes is not None
    text = _lowered(bundled, X.shape[0])
    assert "lgbm.bundle_scan" in text
    F, Bmax = bundled.meta.gather_index.shape
    assert f"tensor<{2 * bundled.wave_k}x{F}x{Bmax}x3xf32>" not in text


# ------------------------------------------------- against the reference


def test_the_data_bundles_the_one_hot_columns(sound):
    _, bst, layout = sound
    core = bst._gbdt.train_set
    bundled = sum(len(b) for b in layout["bundles"])
    assert bundled >= 0.9 * len(core.used_features)
    assert any(not g.is_multi and core.mappers[g.feature_indices[0]].num_bin
               == 2 for g in core.groups)  # a dense level alone
    assert layout["conflict_rows"] > 0
    vd = bst._gbdt.valid_sets[0]
    assert vd.plane is not None and vd._raw is None


def test_the_reference_fits_the_bins_itself(ref, cfg, sets):
    """The reference's own bounds from the binning's sampled rows are the
    program's mappers' (bin_bound_mismatch 0); the mappers of a sample of
    other rows (another data_random_seed) read as a mismatch."""
    X, _ = sets["train"]
    own = ref.own_bounds(X, cfg["params"])
    assert sum(b is not None for b in own) > X.shape[1] // 2
    for seed, same in ((1, True), (2, False)):
        core = CoreDataset.from_matrix(
            X, config=Config(dict(_params(cfg), data_random_seed=seed)))
        theirs = [m.bin_upper_bound if f in core.used_features else None
                  for f, m in enumerate(core.mappers)]
        assert (ref.bound_mismatch(own, theirs) == 0) == same, seed


def test_device_trees_follow_the_plain_efb_reference(ref, cfg, sets, sound):
    handed, bst, layout = sound
    readings = _readings(ref, cfg, sets, handed, bst, layout)
    assert _failed(readings) == [], readings


def test_the_tree_notes_count_splits_on_bundle_members(sound):
    _, bst, _ = sound
    notes = [n for n in tracing.recorder().snapshot()
             if n["kind"] == "tree_wave" and "efb_splits" in n]
    assert notes and all(n["efb_splits"] <= n["committed"] for n in notes)
    assert any(n["efb_splits"] for n in notes)
    assert global_timer.counters.get("device_efb_splits", 0) > 0


def _rewritten(module, owner, name: str, replacements: list):
    """`owner.name` recompiled with lines of its source replaced: a fault
    planted in the program for a control, nothing else changed."""
    src = textwrap.dedent(inspect.getsource(getattr(owner, name)))
    for old, new in replacements:
        assert old in src, old
        src = src.replace(old, new)
    namespace = dict(vars(module))
    exec(compile(src, module.__file__, "exec"), namespace)
    return namespace[name]


def _controls() -> dict:
    """chip_controls_efb.py's plants, read from the file itself."""
    plants = _load("chip_controls_efb_plants",
                   BENCH / "tests" / "chip_controls_efb.py").PLANTS
    modules = {"lightgbm_tpu.ops.split": split_mod,
               "lightgbm_tpu.io.dataset": dataset_mod,
               "lightgbm_tpu.metrics.binary": binary_mod}
    out = {}
    for variant, (modname, name, replacements) in plants.items():
        module = modules[modname]
        owner = module
        if "." in name:
            cls, name = name.split(".")
            owner = getattr(module, cls)
        out[variant] = (module, owner, name, replacements)
    return out


@pytest.mark.parametrize("control", ["shifted", "no_default",
                                     "conflict_first", "auc_ties"])
def test_every_control_fails_a_limit(ref, cfg, sets, monkeypatch, control):
    module, owner, name, replacements = _controls()[control]
    monkeypatch.setattr(owner, name,
                        _rewritten(module, owner, name, replacements))
    jax.clear_caches()  # no program of the sound runs is reused
    try:
        handed, bst, layout = _train(monkeypatch, cfg, sets)
        failed = _failed(_readings(ref, cfg, sets, handed, bst, layout))
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert failed, control


def test_the_bfloat16_control_fails_a_limit(ref, cfg, sets, monkeypatch):
    """(a) the program's default operand, one bfloat16 limb, through the
    interpreted kernels (the XLA bodies take float32 whatever the switch
    says): one followed tree."""
    jax.clear_caches()
    try:
        handed, bst, layout = _train(monkeypatch, cfg, sets, trees=1)
        monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
        monkeypatch.setenv("LGBM_TPU_HIST_F32", "0")
        jax.clear_caches()
        X, y = sets["train"]
        ds = lgb.Dataset(X, label=y, params=_params(cfg)).construct()
        test = lgb.Dataset(sets["test"][0], label=sets["test"][1],
                           reference=ds)
        got = {"train_scores": [], "test_scores": [], "auc": []}

        def after(env):
            g = env.model._gbdt
            got["train_scores"].append(np.asarray(g.score[0]))
            got["test_scores"].append(np.asarray(g.valid_sets[0].score[0]))
            got["auc"].append(float(env.evaluation_result_list[0][2]))
        after.order = 100
        low = lgb.train(_params(cfg), ds, num_boost_round=1,
                        valid_sets=[test], valid_names=["test"],
                        callbacks=[after])
        assert low._gbdt.tree_learner.hist_operand == "bf16"
        assert _failed(_readings(ref, cfg, sets, got, low, layout,
                                 trees=1))
        assert _failed(_readings(ref, cfg, sets, handed, bst, layout,
                                 trees=1)) == []
    finally:
        jax.clear_caches()
