"""Fused split-scan kernel oracle: ops/scan_pallas.py vs the XLA body.

The contract is JIT-vs-JIT bit identity (ISSUE round 8): the fused kernel
in interpret mode must reproduce the jitted XLA `per_feature_best` BIT for
bit — same gains, same thresholds, same lane picks, same -inf/-0.0
patterns — across plain, regularized, masked/penalized and missing-heavy
histograms, and end-to-end through the device learner on the plain,
bagged and quantized planes. `LGBM_TPU_SCAN_PALLAS=0` must restore the
XLA path byte-for-byte (the escape-hatch acceptance criterion).

Eager XLA is NOT the oracle: outside jit the gain expression fuses
differently and drifts 1 ULP, so every comparison here jits both sides
(fresh `jax.jit` wrappers re-read the env gate at trace time; the public
`find_best_split` entry is cleared between env flips instead).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Dataset as CoreDS
from lightgbm_tpu.models.gbdt import GBDT
from lightgbm_tpu.objectives import create_objective
from lightgbm_tpu.ops import scan_pallas
from lightgbm_tpu.ops import split as split_mod
from lightgbm_tpu.ops.histogram import build_histogram
from lightgbm_tpu.ops.split import (SPLIT_FIELDS, find_best_split,
                                    gather_feature_hist, make_feature_meta,
                                    per_feature_best)
from lightgbm_tpu.treelearner.device import DeviceTreeLearner


def _clear_dispatch_caches():
    """The SCAN_PALLAS gate is read at trace time; jitted entries that
    captured one routing must be re-traced after an env flip."""
    from lightgbm_tpu.treelearner import device as device_mod

    find_best_split.clear_cache()
    device_mod.grow_tree_on_device.clear_cache()


@pytest.fixture(autouse=True)
def _interpret_and_clean(monkeypatch):
    """Every test in this file runs the kernel in interpret mode (CPU) and
    leaves no routing decision cached behind for other test files."""
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    _clear_dispatch_caches()
    yield
    _clear_dispatch_caches()


@pytest.fixture(scope="module")
def leaf():
    """One leaf's split-scan inputs over a feature set that exercises all
    scan lanes: dense numerics, a zero-sparse feature (MissingType::Zero,
    missing bin == default bin) and a NaN feature (MissingType::NaN,
    missing bin == last)."""
    rng = np.random.RandomState(31)
    N, F = 4000, 7
    X = rng.normal(size=(N, F))
    X[:, 2] = rng.binomial(1, 0.25, N) * rng.normal(size=N)  # zero-sparse
    X[rng.rand(N) < 0.15, 4] = np.nan                        # NaN-missing
    X[:, 5] = rng.randint(0, 3, N).astype(float)             # few bins
    grad = rng.normal(size=N).astype(np.float32)
    hess = (np.abs(rng.normal(size=N)) + 0.1).astype(np.float32)
    ds = CoreDS.from_matrix(X, label=grad, config=Config({"verbosity": -1}))
    B = int(ds.group_bin_counts().max())
    gh = np.stack([grad, hess, np.ones(N, np.float32)], 1)
    hist = build_histogram(jnp.asarray(ds.bins), jnp.asarray(gh), B)
    meta = make_feature_meta(ds, B)
    totals = hist[0].sum(axis=0).astype(jnp.float32)
    return hist, totals, meta


def _run_per_feature(monkeypatch, scan_env, hist, totals, meta, params,
                     mask=None, penalty=None, constraint=None):
    """Jitted [F, len(SPLIT_FIELDS)] scan under one SCAN_PALLAS setting.
    A fresh jax.jit wrapper per call re-reads the env gate at trace time."""
    monkeypatch.setenv("LGBM_TPU_SCAN_PALLAS", scan_env)

    @jax.jit
    def run(h, t, p):
        fh = gather_feature_hist(h, meta, t)
        return per_feature_best(fh, t, meta, p, mask, constraint, penalty)

    return np.asarray(run(hist, totals, jnp.asarray(params, jnp.float32)))


# params vector layout: [lambda_l1, lambda_l2, min_data_in_leaf,
#                        min_sum_hessian_in_leaf, min_gain_to_split,
#                        max_delta_step]
_PARAM_CASES = {
    "plain": [0, 0, 20, 1e-3, 0, 0],
    "l1_l2": [0.5, 1.0, 20, 1e-3, 0, 0],
    "max_delta": [0, 0, 20, 1e-3, 0, 0.3],
    "min_gain": [0, 0, 20, 1e-3, 0.05, 0],
    "tight_floors": [0, 0, 600, 5.0, 0, 0],
    "everything": [0.2, 0.7, 50, 0.5, 0.02, 0.4],
}


@pytest.mark.parametrize("case", sorted(_PARAM_CASES))
def test_fused_bit_identical_per_feature(leaf, monkeypatch, case):
    """Kernel (interpret) vs XLA on the full per-feature record tensor —
    exact equality, including -inf rows for gated-off candidates."""
    hist, totals, meta = leaf
    params = _PARAM_CASES[case]
    fused = _run_per_feature(monkeypatch, "1", hist, totals, meta, params)
    xla = _run_per_feature(monkeypatch, "0", hist, totals, meta, params)
    np.testing.assert_array_equal(fused, xla, err_msg=case)
    # the scan found at least one real split (the test isn't vacuous)
    if case in ("plain", "l1_l2"):
        assert np.isfinite(fused[:, 0]).any(), case


def test_fused_bit_identical_mask_and_penalty(leaf, monkeypatch):
    """ColSampler mask + CEGB penalty lanes flow through the meta columns."""
    hist, totals, meta = leaf
    F = int(meta.gather_index.shape[0])
    mask = jnp.asarray(np.arange(F) % 2 == 0)
    penalty = jnp.asarray(np.linspace(0.0, 0.5, F), jnp.float32)
    params = _PARAM_CASES["plain"]
    fused = _run_per_feature(monkeypatch, "1", hist, totals, meta, params,
                             mask=mask, penalty=penalty)
    xla = _run_per_feature(monkeypatch, "0", hist, totals, meta, params,
                           mask=mask, penalty=penalty)
    np.testing.assert_array_equal(fused, xla)
    # masked-off features must be invalid in both
    assert (fused[1::2, 1] == -1.0).all()


def test_monotone_constraint_stays_on_xla(leaf, monkeypatch):
    """Constrained scans never route to the kernel: flipping the env flag
    must be a no-op byte-for-byte when a constraint vector is present."""
    hist, totals, meta = leaf
    params = _PARAM_CASES["plain"]
    con = jnp.asarray([-0.2, 0.2], jnp.float32)
    on = _run_per_feature(monkeypatch, "1", hist, totals, meta, params,
                          constraint=con)
    off = _run_per_feature(monkeypatch, "0", hist, totals, meta, params,
                           constraint=con)
    np.testing.assert_array_equal(on, off)


def test_find_best_split_escape_hatch(leaf, monkeypatch):
    """The public jitted entry: LGBM_TPU_SCAN_PALLAS=0 restores the XLA
    reduction byte-for-byte (acceptance criterion), cache-cleared between
    flips because the routing is baked in at trace time."""
    hist, totals, meta = leaf
    params = jnp.asarray(_PARAM_CASES["everything"], jnp.float32)
    monkeypatch.setenv("LGBM_TPU_SCAN_PALLAS", "1")
    find_best_split.clear_cache()
    fused = np.asarray(find_best_split(hist, totals, meta, params))
    monkeypatch.setenv("LGBM_TPU_SCAN_PALLAS", "0")
    find_best_split.clear_cache()
    xla = np.asarray(find_best_split(hist, totals, meta, params))
    np.testing.assert_array_equal(fused, xla)
    assert np.isfinite(fused[0])  # a real split was picked


def test_constants_pinned_to_split_module():
    """The kernel re-states two contracts from ops/split.py; drift between
    the twins would silently break bit identity."""
    assert scan_pallas.K_EPSILON == split_mod.K_EPSILON
    assert scan_pallas.N_REC == len(SPLIT_FIELDS)
    assert scan_pallas.REC_PAD >= scan_pallas.N_REC
    # tile width must stay a power of two (BlockSpec grid arithmetic)
    t = scan_pallas.SCAN_TILE_FEATURES
    assert t > 0 and (t & (t - 1)) == 0


def test_use_scan_pallas_env_gate(monkeypatch):
    for val, want in (("0", False), ("off", False), ("false", False),
                      ("xla", False), ("1", True), ("on", True),
                      ("true", True), ("pallas", True)):
        monkeypatch.setenv("LGBM_TPU_SCAN_PALLAS", val)
        assert scan_pallas.use_scan_pallas() is want, val
    # anything else, and unset, is the XLA body — on every backend: the
    # gate no longer asks which one (Mosaic refuses the kernel on a v5e)
    monkeypatch.setenv("LGBM_TPU_SCAN_PALLAS", "auto")
    assert scan_pallas.use_scan_pallas() is False
    monkeypatch.delenv("LGBM_TPU_SCAN_PALLAS")
    assert scan_pallas.use_scan_pallas() is False


def _train_device(X, y, params, n_iters):
    cfg = Config(params)
    ds = CoreDS.from_matrix(X, label=y, config=cfg)
    bst = GBDT(cfg, ds, create_objective(cfg.objective, cfg))
    bst.tree_learner = DeviceTreeLearner(cfg, ds)
    for _ in range(n_iters):
        if bst.train_one_iter():
            break
    bst.to_model()  # flush any in-flight async tree
    return bst


def _assert_same_models(a, b, hess_rtol=0.0):
    """Byte-equality on every tree field except the stored `split_gain`
    metadata, which may drift by one upstream rounding between the fused
    and XLA paths when the scan is embedded in the big grow_tree_on_device
    jit: XLA refuses a fixed op order for its OWN body across fusion
    contexts (the big-jit XLA gain drifts from its standalone-jit self,
    which is the value the kernel reproduces), and the final
    `best_gain - gain_shift` cancellation amplifies that single rounding
    to a few ULP of the result. Decisions, thresholds, counts and leaf
    outputs — everything that feeds predictions — must match bit for
    bit, and so must the stored hessian sums (`internal_weight`,
    `leaf_weight`) unless the caller passes `hess_rtol`: only the
    quantized plane does (see the test below for why)."""
    assert len(a.models) == len(b.models)
    for ta, tb in zip(a.models, b.models):
        for k, va in ta.__dict__.items():
            vb = tb.__dict__[k]
            if k == "split_gain":
                np.testing.assert_allclose(np.asarray(va), np.asarray(vb),
                                           rtol=1e-4, atol=1e-5, err_msg=k)
            elif hess_rtol and k in ("internal_weight", "leaf_weight"):
                np.testing.assert_allclose(np.asarray(va), np.asarray(vb),
                                           rtol=hess_rtol, err_msg=k)
            elif isinstance(va, np.ndarray):
                np.testing.assert_array_equal(va, vb, err_msg=k)
            else:
                assert va == vb, k


_VARIANTS = {
    "plain": {},
    "bagged": {"bagging_fraction": 0.7, "bagging_freq": 1, "seed": 7},
    "quantized": {"use_quantized_grad": True, "quant_train_renew_leaf": True},
}


@pytest.mark.slow  # ~2 min/variant: interpret mode pays Python per wave.
@pytest.mark.parametrize("variant", sorted(_VARIANTS))
def test_train_bit_identical_fused_vs_xla(rng, monkeypatch, variant):
    """End-to-end through the device learner: the fused scan (interpret)
    grows trees identical to the XLA scan on every training plane — same
    structure, thresholds, counts and leaf values bit for bit; the stored
    split_gain metadata is allowed the 1-ULP big-jit context drift (see
    _assert_same_models).

    The quantized plane holds the stored hessian sums to one f32 ULP (the
    1e-6 of tests/test_sharded_device.py) instead of bit for bit. Its
    histograms are int32 and re-enter float space as `int * scale` right
    before the scan, so the kernel does run there. XLA:CPU (jax 0.9)
    duplicates the cheap `totals_int * scale` producer into the XLA body's
    fusions, where LLVM contracts it with `total - left` into one fma:
    the body's right-child sums come from the UNROUNDED product, while the
    kernel reads the rounded totals through its operand boundary (an
    optimization_barrier on the product does not stop it). Left sums,
    thresholds, counts, renewed leaf values and predictions stay exact."""
    n = 900
    X = rng.randn(n, 6)
    y = (X[:, 0] - 0.6 * X[:, 1] + rng.randn(n) * 0.3 > 0).astype(float)
    params = {"objective": "binary", "num_leaves": 15, "verbosity": -1,
              "min_data_in_leaf": 5, **_VARIANTS[variant]}
    monkeypatch.setenv("LGBM_TPU_SCAN_PALLAS", "1")
    _clear_dispatch_caches()
    fused = _train_device(X, y, params, 3)
    monkeypatch.setenv("LGBM_TPU_SCAN_PALLAS", "0")
    _clear_dispatch_caches()
    xla = _train_device(X, y, params, 3)
    _assert_same_models(fused, xla,
                        hess_rtol=1e-6 if variant == "quantized" else 0.0)
    np.testing.assert_array_equal(
        np.asarray(fused.predict(X, raw_score=True)),
        np.asarray(xla.predict(X, raw_score=True)))
