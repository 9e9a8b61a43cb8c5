"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors the reference's DistributedMockup strategy (tests/distributed/
_test_distributed.py) of exercising the real collective path on one machine:
here `xla_force_host_platform_device_count=8` gives 8 XLA CPU devices so
shard_map/pjit collective code paths run exactly as they would across a TPU
slice.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# The package points JAX's persistent compilation cache at
# <checkout>/.jax_cache (lightgbm_tpu/utils/backend.py). Tests — and the CLI
# and gang children they spawn, which inherit this — compile everything
# fresh: a test must not pass or fail by what an earlier run left on disk.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture(autouse=True)
def _log_state_isolated():
    """Log verbosity and callback are process globals (the CLI sets them);
    restore them so a `verbosity=-1` run can't mute a later test's
    warning assertions."""
    from lightgbm_tpu.utils import log as _log

    verbosity, callback = _log._verbosity, _log._callback
    yield
    _log._verbosity, _log._callback = verbosity, callback


# The reference's examples/ directory is not mounted here (ROADMAP R1), so
# the tests that read its files get files of the same formats, written once
# a session from a fixed seed: the formats are the subject (a label-first
# TSV, a LibSVM file with its .query side file, a train.conf), not HIGGS's
# or MSLR's rows.
TRAIN_CONF = """\
# task type, support train and predict
task = train

# boosting type, support gbdt for now, alias: boosting, boost
boosting_type = gbdt

# application type, support following application
# regression , regression task
# binary , binary classification task
# lambdarank , lambdarank task
# alias: application, app
objective = binary

# eval metrics, support multi metric, delimited by ',' , support following metrics
# l1
# l2 , default metric for regression
# ndcg , default metric for lambdarank
# auc
# binary_logloss , default metric for binary
# binary_error
metric = binary_logloss,auc

# frequency for metric output
metric_freq = 1

# true if need output metric for training data, alias: tranining_metric, train_metric
is_training_metric = true

# number of bins for feature bucket, 255 is a recommend setting, it can save memories, and also has good accuracy.
max_bin = 255

# training data
# if existing weight file, should name to "binary.train.weight"
# alias: train_data, train
data = binary.train

# validation data, support multi validation data, separated by ','
# if existing weight file, should name to "binary.test.weight"
# alias: valid, test, test_data,
valid_data = binary.test

# number of trees(iterations), alias: num_tree, num_iteration, num_iterations, num_round, num_rounds
num_trees = 100

# shrinkage rate , alias: shrinkage_rate
learning_rate = 0.1

# number of leaves for one tree, alias: num_leaf
num_leaves = 63

# type of tree learner, support following types:
# serial , single machine version
# feature , use feature parallel to train
# data , use data parallel to train
# voting , use voting based parallel to train
# alias: tree
tree_learner = serial

# number of threads for multi-threading. One thread will use one CPU, default is setted to #cpu.
# num_threads = 8

# feature sub-sample, will random select 80% feature to train on each iteration
# alias: sub_feature
feature_fraction = 0.8

# Support bagging (data sub-sample), will perform bagging every 5 iterations
bagging_freq = 5

# Bagging farction, will random select 80% data on bagging
# alias: sub_row
bagging_fraction = 0.8

# minimal number data for one leaf, use this to deal with over-fit
# alias : min_data_per_leaf, min_data
min_data_in_leaf = 50

# minimal sum hessians for one leaf, use this to deal with over-fit
min_sum_hessian_in_leaf = 5.0

# save memory and faster speed for sparse feature, alias: is_sparse
is_enable_sparse = true

# when data is bigger than memory size, set this to true. otherwise set false will have faster speed
# alias: two_round_loading, two_round
use_two_round_loading = false

# true if need to save data to binary file and application will auto load data from binary file next time
# alias: is_save_binary, save_binary
is_save_binary_file = false

# output model file
output_model = LightGBM_model.txt
"""


def _write_binary_example(path, rng, rows, weights):
    """`rows` x 28 standard-normal features, the label first, tab-separated
    at the reference file's three decimals: a logit of six features, one
    product and one square under unit logistic noise."""
    X = rng.normal(size=(rows, 28))
    logit = (X[:, :6] @ weights + 0.8 * X[:, 6] * X[:, 7]
             + 0.5 * (X[:, 8] ** 2 - 1.0))
    y = (logit + rng.logistic(size=rows) > 0).astype(int)
    np.savetxt(path, np.column_stack([y, X]), delimiter="\t",
               fmt=["%d"] + ["%.3f"] * 28)


def _write_rank_example(path, rng, queries, weights):
    """LibSVM rows `grade 1:v 2:v ...` (1-based ids, zeros left out, as the
    reference's rank.train) with `<path>.query` holding the documents a
    query: 5 to 40 each, 20 features of which a third are zero, graded 0-4
    by the fixed cuts of a linear score under noise."""
    sizes = rng.randint(5, 41, size=queries)
    n = int(sizes.sum())
    X = rng.normal(size=(n, 20)) * (rng.rand(n, 20) > 1 / 3)
    score = X @ weights + 0.7 * rng.normal(size=n)
    grade = np.digitize(score, [0.3, 1.2, 2.0, 2.8])
    with open(path, "w") as fh:
        for g, row in zip(grade, X):
            fh.write(" ".join([str(g)] + [f"{k + 1}:{v:.4f}"
                                          for k, v in enumerate(row) if v])
                     + "\n")
    np.savetxt(str(path) + ".query", sizes, fmt="%d")


@pytest.fixture(scope="session")
def examples(tmp_path_factory):
    """The directory of example files: binary.train (7,000 x 28) and
    binary.test (500), rank.train (200 queries) and rank.test (50) with
    their .query files, train.conf."""
    root = tmp_path_factory.mktemp("examples")
    rng = np.random.RandomState(20261002)
    w_bin = rng.normal(size=6)
    w_rank = rng.normal(size=20) * (rng.rand(20) > 0.5)
    _write_binary_example(root / "binary.train", rng, 7000, w_bin)
    _write_binary_example(root / "binary.test", rng, 500, w_bin)
    _write_rank_example(root / "rank.train", rng, 200, w_rank)
    _write_rank_example(root / "rank.test", rng, 50, w_rank)
    (root / "train.conf").write_text(TRAIN_CONF)
    return root
