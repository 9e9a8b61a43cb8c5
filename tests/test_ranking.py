"""Ranking tests on files in the format of the reference's
examples/lambdarank: LibSVM rows graded 0-4 with a .query side file
(tests/conftest.py `examples`: 200 train queries of 5-40 documents, 4,323
rows, 50 test queries; grades from a noisy linear score).

What the thresholds stand against, on the seeded files: scores drawn at
random read NDCG@5 0.29-0.37 on rank.test (five draws), one tree 0.73-0.75.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb


@pytest.fixture
def rank_files(examples):
    return str(examples / "rank.train"), str(examples / "rank.test")


def test_lambdarank_reference_example(rank_files):
    RANK_TRAIN, RANK_TEST = rank_files
    ds = lgb.Dataset(RANK_TRAIN)
    dv = lgb.Dataset(RANK_TEST, reference=ds)
    rec = {}
    bst = lgb.train({"objective": "lambdarank", "metric": "ndcg",
                     "eval_at": "1,3,5", "num_leaves": 31, "learning_rate": 0.1,
                     "verbosity": -1, "min_data_in_leaf": 50, "min_sum_hessian_in_leaf": 5.0},
                    ds, num_boost_round=30, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(rec)])
    ndcg5 = rec["valid_0"]["ndcg@5"]
    # read 0.734 after one tree and 0.947 after 30: 0.85 is far above one
    # tree's worth and 0.097 under the reading
    assert ndcg5[-1] > 0.85, f"ndcg@5 too low: {ndcg5[-1]}"
    assert ndcg5[-1] > ndcg5[0] + 0.1  # rising: learning, not diverging


def test_rank_xendcg(rank_files):
    RANK_TRAIN, RANK_TEST = rank_files
    ds = lgb.Dataset(RANK_TRAIN)
    rec = {}
    dv = lgb.Dataset(RANK_TEST, reference=ds)
    bst = lgb.train({"objective": "rank_xendcg", "metric": "ndcg", "eval_at": "5",
                     "num_leaves": 31, "verbosity": -1, "min_data_in_leaf": 1,
                     "min_sum_hessian_in_leaf": 1e-3},
                    ds, num_boost_round=20, valid_sets=[dv],
                    callbacks=[lgb.record_evaluation(rec)])
    ndcg5 = rec["valid_0"]["ndcg@5"]
    # read 0.749 after one tree and 0.930 after 20
    assert ndcg5[-1] > 0.85, f"ndcg@5 too low: {ndcg5[-1]}"
    assert ndcg5[-1] > ndcg5[0] + 0.1


def test_ndcg_metric_values():
    # hand-computable case: one query, 4 docs
    from lightgbm_tpu.metrics import create_metric
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.metadata import Metadata
    import jax.numpy as jnp

    md = Metadata(4)
    md.set_label(np.array([3.0, 2.0, 1.0, 0.0]))
    md.set_query(np.array([4]))
    cfg = Config({"eval_at": "2,4"})
    m = create_metric("ndcg", cfg)
    m.init(md, 4)
    # perfect ranking
    perfect = m.eval(jnp.asarray([4.0, 3.0, 2.0, 1.0]), None)
    assert perfect[0] == pytest.approx(1.0, abs=1e-6)
    assert perfect[1] == pytest.approx(1.0, abs=1e-6)
    # reversed ranking
    rev = m.eval(jnp.asarray([1.0, 2.0, 3.0, 4.0]), None)
    assert rev[0] < 0.3
    g = [0, 1, 3, 7]
    disc = 1.0 / np.log2(np.arange(4) + 2.0)
    dcg_rev = np.sum(np.array([g[0], g[1], g[2], g[3]]) * disc)
    max_dcg = np.sum(np.array([g[3], g[2], g[1], g[0]]) * disc)
    assert rev[1] == pytest.approx(dcg_rev / max_dcg, abs=1e-5)


def test_map_metric():
    from lightgbm_tpu.metrics import create_metric
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.io.metadata import Metadata
    import jax.numpy as jnp

    md = Metadata(4)
    md.set_label(np.array([1.0, 0.0, 1.0, 0.0]))
    md.set_query(np.array([4]))
    m = create_metric("map", Config({"eval_at": "4"}))
    m.init(md, 4)
    # ranking: rel, not, rel, not -> AP = (1/1 + 2/3)/2
    val = m.eval(jnp.asarray([4.0, 3.0, 2.0, 1.0]), None)
    assert val[0] == pytest.approx((1.0 + 2.0 / 3.0) / 2.0, abs=1e-6)


def test_lambdarank_position_debias(rank_files):
    """Position-debiased lambdarank (rank_objective.hpp:43-90,296-340):
    positions accepted via Dataset, bias factors iteratively estimated,
    NDCG no worse on unbiased data."""
    RANK_TRAIN, RANK_TEST = rank_files
    rng = np.random.RandomState(5)

    def ndcg(params, position=None):
        ds = lgb.Dataset(RANK_TRAIN, position=position)
        dv = lgb.Dataset(RANK_TEST, reference=ds)
        rec = {}
        lgb.train(params, ds, num_boost_round=20, valid_sets=[dv],
                  callbacks=[lgb.record_evaluation(rec)])
        return rec["valid_0"]["ndcg@5"][-1]

    params = {"objective": "lambdarank", "metric": "ndcg", "eval_at": "5",
              "num_leaves": 31, "learning_rate": 0.1, "verbosity": -1,
              "min_data_in_leaf": 50, "min_sum_hessian_in_leaf": 5.0,
              "lambdarank_position_bias_regularization": 0.1}
    base = ndcg(params)
    # unbiased data with random positions: debias must not hurt
    n = lgb.Dataset(RANK_TRAIN)
    n.construct()
    num_rows = n._handle.num_data
    positions = rng.randint(0, 10, size=num_rows)
    debiased = ndcg(params, position=positions)
    # read 0.9284 without positions and 0.9279 with random ones
    assert base > 0.85, base
    assert debiased > base - 0.02, (debiased, base)


def test_position_bias_factors_move(rank_files):
    """The per-position bias factors are actually updated during training."""
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.objectives import create_objective

    RANK_TRAIN, _ = rank_files
    rng = np.random.RandomState(3)
    ds = lgb.Dataset(RANK_TRAIN)
    ds.construct()
    core = ds._handle
    positions = rng.randint(0, 6, size=core.num_data)
    core.metadata.set_positions(positions)
    cfg = Config({"objective": "lambdarank", "verbosity": -1})
    obj = create_objective("lambdarank", cfg)
    obj.init(core.metadata, core.num_data)
    import jax.numpy as jnp

    score = jnp.zeros(core.num_data, dtype=jnp.float32)
    obj.get_gradients(score)
    b1 = np.asarray(obj._pos_biases).copy()
    obj.get_gradients(score)
    b2 = np.asarray(obj._pos_biases)
    assert np.any(b1 != 0.0) or np.any(b2 != b1)
