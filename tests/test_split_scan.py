"""The split scan against a plain float64 search written here.

`ops/split.py:per_feature_best` is the only numerical split scan there is:
the serial learner, the whole-tree program and every sharded learner call
it. It is held to a numpy float64 search over the same histogram that does
what the reference's FindBestThresholdSequentially does, one threshold at a
time with no vector tricks: left sums by cumulative sum with the missing
bin taken out, both missing directions, lambda_l1 / lambda_l2 /
max_delta_step in the leaf output and gain, min_gain_to_split in the
shift, the data and hessian floors, the feature mask and the CEGB penalty.

What must agree: which features have a split at all, the threshold bin and
the default direction exactly, the counts exactly (integers below 2**24),
the sums and outputs to float32 rounding, and the gain within GAIN_TOL
(below). The jitted scan is the one under test; eager XLA fuses the gain
expression differently and is not what any learner runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.common import MISSING_NAN, MISSING_NONE, MISSING_ZERO
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Dataset as CoreDS
from lightgbm_tpu.ops.histogram import build_histogram
from lightgbm_tpu.ops.split import (K_EPSILON, SPLIT_FIELDS,
                                    gather_feature_hist, make_feature_meta,
                                    per_feature_best)

FIELD = {name: i for i, name in enumerate(SPLIT_FIELDS)}

# The scan's left sums are float32 cumulative sums over up to B = 255 bins:
# B roundings of 2**-24 each, which add up like a random walk to about
# sqrt(B) * 2**-24 of the sum (B * 2**-24 = 1.5e-5 at the very worst, which
# SUM_RTOL allows). The stored gain is the small difference of two terms of
# the parent gain's size, best_gain - gain_shift, each made of two such
# sums, so its error is held to 4 * sqrt(B) * 2**-24 = 3.8e-6 of
# |best_gain| + |gain_shift|, whatever the gain itself is. The largest
# error read on these fixtures is 0.037 of that (XLA:CPU, jax 0.9).
GAIN_TOL = 4 * 255 ** 0.5 * 2.0 ** -24
SUM_RTOL = 255 * 2.0 ** -24


def _leaf(zero_as_missing):
    """One leaf's split-scan inputs over a feature set that exercises all
    scan lanes: dense numerics, a zero-sparse feature, a NaN feature and a
    feature of three bins. With default settings the NaN feature is
    MissingType::NaN (missing bin == last) and no other has a missing bin;
    with zero_as_missing the zero-sparse one is MissingType::Zero (missing
    bin == default bin, in the middle of the scan). The gradients lean on
    the missing rows, so the best split of either sends them LEFT."""
    rng = np.random.RandomState(31)
    N, F = 4000, 7
    X = rng.normal(size=(N, F))
    X[:, 2] = rng.binomial(1, 0.25, N) * rng.normal(size=N)  # zero-sparse
    X[rng.rand(N) < 0.15, 4] = np.nan                        # NaN-missing
    X[:, 5] = rng.randint(0, 3, N).astype(float)             # few bins
    grad = rng.normal(size=N).astype(np.float32)
    hess = (np.abs(rng.normal(size=N)) + 0.1).astype(np.float32)
    grad[np.isnan(X[:, 4]) | (X[:, 4] < -0.5)] -= 0.5
    grad[(X[:, 2] == 0) | (X[:, 2] < -0.3)] += 0.4
    ds = CoreDS.from_matrix(X, label=grad, config=Config(
        {"verbosity": -1, "zero_as_missing": zero_as_missing}))
    B = int(ds.group_bin_counts().max())
    gh = np.stack([grad, hess, np.ones(N, np.float32)], 1)
    hist = build_histogram(jnp.asarray(ds.bins), jnp.asarray(gh), B)
    meta = make_feature_meta(ds, B)
    totals = hist[0].sum(axis=0).astype(jnp.float32)
    return hist, totals, meta


@pytest.fixture(scope="module")
def leaves():
    nan, zero = _leaf(False), _leaf(True)
    assert MISSING_NAN in np.asarray(nan[2].missing_type)
    assert MISSING_ZERO in np.asarray(zero[2].missing_type)
    return {"nan_missing": nan, "zero_missing": zero}


def _xla_scan(hist, totals, meta, params, mask=None, penalty=None):
    """The jitted [F, len(SPLIT_FIELDS)] scan and the feature histogram it
    read (float32, as every learner hands it over)."""

    @jax.jit
    def run(h, t, p):
        fh = gather_feature_hist(h, meta, t)
        return per_feature_best(fh, t, meta, p, mask, None, penalty), fh

    recs, fh = run(hist, totals, jnp.asarray(params, jnp.float32))
    return np.asarray(recs), np.asarray(fh)


def _leaf_output64(g, h, l1, l2, max_delta):
    g1 = np.sign(g) * max(abs(g) - l1, 0.0)
    out = -g1 / max(h + l2, K_EPSILON)
    if max_delta > 0:
        out = min(max(out, -max_delta), max_delta)
    return out


def _leaf_gain64(g, h, l1, l2, max_delta):
    g1 = np.sign(g) * max(abs(g) - l1, 0.0)
    out = _leaf_output64(g, h, l1, l2, max_delta)
    return -(2.0 * g1 * out + (h + l2) * out * out)


def _float64_search(fh, totals, meta, params, mask=None, penalty=None):
    """One record per feature, or None where no threshold qualifies: the
    best (gain, threshold, default_left, left sums, right sums, outputs)
    over every threshold and both missing directions, first of equals in
    (direction, threshold) order as the reference's sequential scan keeps
    the first best it meets."""
    l1, l2, min_data, min_hess, min_gain, max_delta = map(float, params)
    tot = np.asarray(totals, np.float64)
    shift = _leaf_gain64(tot[0], tot[1], l1, l2, max_delta) + min_gain
    missing_type = np.asarray(meta.missing_type)
    default_bin = np.asarray(meta.default_bin)
    nbins = np.asarray(meta.nbins)
    out = []
    for f in range(fh.shape[0]):
        if mask is not None and not bool(mask[f]):
            out.append(None)
            continue
        h = fh[f, :nbins[f]].astype(np.float64)
        has_missing = missing_type[f] != MISSING_NONE
        miss = np.zeros(3)
        if has_missing:
            at = nbins[f] - 1 if missing_type[f] == MISSING_NAN \
                else default_bin[f]
            miss = h[at].copy()
            h[at] = 0.0
        cum = np.cumsum(h, axis=0)
        best = None
        for default_left in ((False, True) if has_missing else (False,)):
            for t in range(nbins[f] - 1):  # a real bin stays on the right
                left = cum[t] + (miss if default_left else 0.0)
                right = tot - left
                if (left[2] < min_data or right[2] < min_data
                        or left[1] < min_hess or right[1] < min_hess):
                    continue
                gain = (_leaf_gain64(left[0], left[1], l1, l2, max_delta)
                        + _leaf_gain64(right[0], right[1], l1, l2, max_delta))
                if best is None or gain > best["raw"]:
                    best = dict(raw=gain, t=t, default_left=default_left,
                                left=left, right=right)
        if best is None or not best["raw"] > shift:
            out.append(None)
            continue
        best["gain"] = best["raw"] - shift - (
            float(penalty[f]) if penalty is not None else 0.0)
        best["scale"] = abs(best["raw"]) + abs(shift)
        best["lout"] = _leaf_output64(*best["left"][:2], l1, l2, max_delta)
        best["rout"] = _leaf_output64(*best["right"][:2], l1, l2, max_delta)
        out.append(best)
    return out


def _assert_scan_is_the_search(recs, want):
    for f, (rec, w) in enumerate(zip(recs, want)):
        if w is None:
            assert rec[FIELD["gain"]] == -np.inf, f
            assert rec[FIELD["feature"]] == -1.0, f
            continue
        assert rec[FIELD["feature"]] == f
        assert rec[FIELD["threshold_bin"]] == w["t"], f
        assert bool(rec[FIELD["default_left"]] > 0.5) == w["default_left"], f
        assert rec[FIELD["left_count"]] == w["left"][2], f
        assert rec[FIELD["right_count"]] == w["right"][2], f
        err = abs(rec[FIELD["gain"]] - w["gain"]) / w["scale"]
        assert err <= GAIN_TOL, (f, rec[FIELD["gain"]], w["gain"])
        for name, val in (("left_sum_g", w["left"][0]),
                          ("left_sum_h", w["left"][1]),
                          ("right_sum_g", w["right"][0]),
                          ("right_sum_h", w["right"][1]),
                          ("left_output", w["lout"]),
                          ("right_output", w["rout"])):
            # a sum of gradients may cancel: its error scales with the sum
            # of hessians' size (every |grad| <= ~4 here), not with itself
            np.testing.assert_allclose(
                rec[FIELD[name]], val, rtol=SUM_RTOL,
                atol=SUM_RTOL * abs(w["left"][1] + w["right"][1]),
                err_msg=f"{name} of feature {f}")


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
def test_xla_scan_is_the_float64_search_per_feature(leaves, case):
    """Every feature's record: the threshold, the direction and the counts
    exactly, the gain within GAIN_TOL, including the -inf rows of features
    with no admissible threshold."""
    params = _PARAM_CASES[case]
    for name, (hist, totals, meta) in leaves.items():
        recs, fh = _xla_scan(hist, totals, meta, params)
        want = _float64_search(fh, np.asarray(totals), meta, params)
        _assert_scan_is_the_search(recs, want)
        # real splits, and the missing-left lane wins one: not vacuous
        found = [w for w in want if w is not None]
        assert len(found) >= 3, (case, name)
        assert any(w["default_left"] for w in found), (case, name)


def test_xla_scan_is_the_float64_search_under_mask_and_penalty(leaves):
    """The column sampler's mask removes a feature whole; the CEGB penalty
    comes off the stored gain and changes no choice inside a feature."""
    params = _PARAM_CASES["plain"]
    for hist, totals, meta in leaves.values():
        F = int(meta.gather_index.shape[0])
        mask = np.arange(F) % 2 == 0
        penalty = np.linspace(0.0, 0.5, F).astype(np.float32)
        recs, fh = _xla_scan(hist, totals, meta, params,
                             mask=jnp.asarray(mask),
                             penalty=jnp.asarray(penalty))
        want = _float64_search(fh, np.asarray(totals), meta, params,
                               mask=mask, penalty=penalty)
        _assert_scan_is_the_search(recs, want)
        assert all(w is None for w in want[1::2])
        assert (recs[1::2, FIELD["feature"]] == -1.0).all()
        assert sum(w is not None for w in want[0::2]) >= 3
        assert any(w and w["default_left"] for w in want)
