"""Native C++ parser tests: parity with the pure-python path and a build
sanity check (lightgbm_tpu/native/parser.cpp)."""
import os

import numpy as np
import pytest

from lightgbm_tpu.native import get_parser
from lightgbm_tpu.io.parser import parse_file


def test_native_parser_builds():
    assert get_parser() is not None, "native parser failed to build"


def _parity(path, header=False, label_column="0"):
    Xn, yn, nn = parse_file(path, header=header, label_column=label_column)
    os.environ["LIGHTGBM_TPU_NO_NATIVE"] = "1"
    try:
        import lightgbm_tpu.native as nat
        saved, nat._cached = nat._cached, False
        Xp, yp, np_names = parse_file(path, header=header,
                                      label_column=label_column)
        nat._cached = saved
    finally:
        del os.environ["LIGHTGBM_TPU_NO_NATIVE"]
    np.testing.assert_array_equal(np.isnan(Xn), np.isnan(Xp))
    np.testing.assert_allclose(np.nan_to_num(Xn), np.nan_to_num(Xp))
    np.testing.assert_allclose(yn, yp)
    assert nn == np_names


def test_tsv_parity(rng, tmp_path):
    X = rng.randn(200, 4)
    X[5, 1] = np.nan
    y = rng.randint(0, 2, 200)
    p = str(tmp_path / "d.tsv")
    with open(p, "w") as fh:
        for i in range(200):
            row = [str(y[i])] + ["nan" if np.isnan(v) else repr(v)
                                 for v in X[i]]
            fh.write("\t".join(row) + "\n")
    _parity(p)


def test_csv_with_header_parity(rng, tmp_path):
    X = rng.randn(100, 3)
    y = rng.randint(0, 2, 100)
    p = str(tmp_path / "d.csv")
    with open(p, "w") as fh:
        fh.write("target,a,b,c\n")
        for i in range(100):
            fh.write(",".join([str(y[i])] + [repr(v) for v in X[i]]) + "\n")
    _parity(p, header=True, label_column="name:target")


def test_libsvm_parity(examples):
    """The native parser equals the Python one on a LibSVM file in the
    reference's rank.train style (1-based ids, zeros left out)."""
    path = str(examples / "rank.train")
    X, y, _ = parse_file(path)
    assert X.shape[1] == 21 and (X[:, 0] == 0).all()  # ids start at 1
    assert set(np.unique(y)) == {0, 1, 2, 3, 4}
    _parity(path)


def test_reference_example_parses_identically(examples):
    path = str(examples / "binary.train")
    X, y, _ = parse_file(path)
    assert X.shape == (7000, 28) and set(np.unique(y)) == {0, 1}
    _parity(path)


def test_native_parse_dense_multithreaded(tmp_path):
    """Files past the shard threshold take the pipelined multi-shard path;
    results must be byte-identical to the single-shard/numpy parse."""
    native = pytest.importorskip("lightgbm_tpu.native").get_parser()
    if native is None:
        pytest.skip("native parser unavailable")
    rng = np.random.RandomState(3)
    rows, cols = 70_000, 10  # ~5.5 MB > the 4 MB sharding threshold
    M = rng.randn(rows, cols).round(6)
    path = tmp_path / "big.csv"
    np.savetxt(path, M, delimiter=",", fmt="%.6f")
    assert path.stat().st_size > (4 << 20)
    buf, nr, nc = native.parse_dense(str(path), ord(","), 0)
    assert (nr, nc) == (rows, cols)
    out = np.frombuffer(buf, dtype=np.float64).reshape(rows, cols)
    np.testing.assert_allclose(out, M, atol=1e-9)
