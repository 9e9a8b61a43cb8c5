"""The distinct-value pass of BinMapper.find_bin (bin.cpp:339-361), taken
over the whole sorted sample at once, against the source's loop written
out value by value; and the sparse plane binned on one thread and on
several."""
import math

import numpy as np
import pytest
import scipy.sparse as sp

from lightgbm_tpu.config import Config
from lightgbm_tpu.io import binning as binning_mod
from lightgbm_tpu.io import dataset as dataset_mod
from lightgbm_tpu.io.binning import BinMapper, _distinct
from lightgbm_tpu.io.dataset import Dataset


def _loop(sorted_vals: np.ndarray, zero_cnt: int) -> tuple:
    """The source's loop: a value within one float64 step above the one
    before it joins its run; zeros before, between or after."""
    values, counts = [], []
    if len(sorted_vals) == 0 or (sorted_vals[0] > 0.0 and zero_cnt > 0):
        values.append(0.0)
        counts.append(zero_cnt)
    if len(sorted_vals) > 0:
        values.append(float(sorted_vals[0]))
        counts.append(1)
    for i in range(1, len(sorted_vals)):
        prev, cur = float(sorted_vals[i - 1]), float(sorted_vals[i])
        if not cur <= math.nextafter(prev, math.inf):
            if prev < 0.0 and cur > 0.0:
                values.append(0.0)
                counts.append(zero_cnt)
            values.append(cur)
            counts.append(1)
        else:
            values[-1] = cur
            counts[-1] += 1
    if len(sorted_vals) > 0 and sorted_vals[-1] < 0.0 and zero_cnt > 0:
        values.append(0.0)
        counts.append(zero_cnt)
    return values, counts


def _steps(base: np.ndarray, k: int) -> np.ndarray:
    out = [base]
    for _ in range(k):
        out.append(np.nextafter(out[-1], np.inf))
    return np.concatenate(out)


RNG = np.random.default_rng(40)
CASES = {
    "empty": (np.array([]), 7),
    "ones": (np.ones(500), 1000),
    "ones_no_zeros": (np.ones(500), 0),
    "negative_only": (np.array([-3.0, -3.0, -1.0]), 4),
    "both_signs_no_zeros": (np.array([-2.0, -1.0, 3.0]), 0),
    "smallest_steps": (np.array([-5e-324, 5e-324, 1.0]), 2),
    "chains_of_steps": (_steps(RNG.normal(size=300), 3), 11),
    "rounded_normal": (np.round(RNG.normal(size=5000), 2), 300),
    "normal": (RNG.normal(size=5000), 0),
    "integers": (RNG.integers(1, 9, 2000).astype(np.float64), 50),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_runs_are_the_loops(case):
    vals, zeros = CASES[case]
    s = np.sort(vals, kind="stable")
    got = _distinct(s, zeros)
    want = _loop(s, zeros)
    assert got == want
    assert [type(v) for v in got[0]] == [float] * len(want[0])
    assert [type(c) for c in got[1]] == [int] * len(want[1])


@pytest.mark.parametrize("max_bin", [255, 16])
def test_a_mapper_is_the_loops_mapper(monkeypatch, max_bin):
    """find_bin on a sample whose values come in one-step chains, cross
    zero and hold NaN: every field of the mapper is what the loop's runs
    give."""
    vals = np.concatenate([_steps(RNG.normal(size=400), 2), [np.nan] * 3])
    got = BinMapper()
    got.find_bin(vals, vals.size + 120, max_bin, min_data_in_bin=3)
    monkeypatch.setattr(binning_mod, "_distinct", _loop)
    want = BinMapper()
    want.find_bin(vals, vals.size + 120, max_bin, min_data_in_bin=3)
    assert got.__dict__.keys() == want.__dict__.keys()
    for key, value in want.__dict__.items():
        if isinstance(value, np.ndarray):
            assert np.array_equal(got.__dict__[key], value, equal_nan=True)
        else:
            assert got.__dict__[key] == value, key


def test_the_sparse_plane_is_the_same_on_one_thread(monkeypatch):
    """Groups binned on threads, conflicts summed: the plane and the count
    of one thread's construction."""
    rng = np.random.default_rng(3)
    n, f = 4000, 60
    X = sp.random(n, f, density=0.03, random_state=rng, format="csr",
                  dtype=np.float64)
    X.data = np.round(X.data * 4) + 1.0
    cfg = Config({"max_bin": 63, "min_data_in_leaf": 1,
                  "min_data_in_bin": 1, "max_conflict_rate": 0.05,
                  "verbosity": -1})
    many = Dataset.from_matrix(X, config=cfg)
    monkeypatch.setattr(dataset_mod, "BIN_THREADS", 1)
    one = Dataset.from_matrix(X, config=cfg)
    assert any(g.is_multi for g in many.groups)
    assert np.array_equal(many.bins, one.bins)
    assert many.bins.dtype == one.bins.dtype
    assert many.conflict_rows == one.conflict_rows > 0
