import pytest

from lightgbm_tpu.config import Config, key_alias_transform, kv2map, load_config_file, parse_objective_alias
from lightgbm_tpu.utils.log import LightGBMError


def test_defaults():
    c = Config()
    assert c.num_iterations == 100
    assert c.learning_rate == 0.1
    assert c.num_leaves == 31
    assert c.max_bin == 255
    assert c.min_data_in_leaf == 20
    assert c.boosting == "gbdt"
    assert c.tree_learner == "serial"


def test_alias_resolution():
    out = key_alias_transform({"n_estimators": 50, "eta": 0.3, "num_leaf": 63})
    assert out == {"num_iterations": 50, "learning_rate": 0.3, "num_leaves": 63}


def test_canonical_wins_over_alias():
    c = Config({"num_boost_round": 10, "num_iterations": 20})
    assert c.num_iterations == 20


def test_objective_aliases():
    assert parse_objective_alias("mse") == "regression"
    assert parse_objective_alias("mae") == "regression_l1"
    assert parse_objective_alias("softmax") == "multiclass"
    assert parse_objective_alias("none") == "custom"
    c = Config({"objective": "l2"})
    assert c.objective == "regression"
    assert c.metric == ["l2"]


def test_metric_parsing():
    c = Config({"objective": "binary", "metric": "auc,binary_logloss"})
    assert c.metric == ["auc", "binary_logloss"]
    c2 = Config({"objective": "binary"})
    assert c2.metric == ["binary_logloss"]


def test_type_coercion_and_checks():
    c = Config({"learning_rate": "0.05", "feature_fraction": "0.8", "is_unbalance": "true"})
    assert c.learning_rate == 0.05
    assert c.is_unbalance is True
    with pytest.raises(LightGBMError):
        Config({"feature_fraction": 1.5})


def test_goss_legacy_boosting():
    c = Config({"boosting": "goss"})
    assert c.boosting == "gbdt"
    assert c.data_sample_strategy == "goss"


def test_max_depth_caps_num_leaves():
    c = Config({"max_depth": 3})
    assert c.num_leaves == 8


def test_kv2map_and_config_file(tmp_path):
    assert kv2map(["a=1", "# comment", "b = 2 # trailing"]) == {"a": "1", "b": "2"}
    p = tmp_path / "train.conf"
    p.write_text("task = train\nobjective = binary\nnum_trees = 5\n# c\n")
    kvs = load_config_file(str(p))
    assert kvs["objective"] == "binary"
    c = Config(kvs)
    assert c.num_iterations == 5


def test_reference_train_conf_parses(examples):
    """A train.conf in the reference's own style: comment lines, blank
    lines, `key = value` with spaces, commented-out keys, a list value."""
    kvs = load_config_file(str(examples / "train.conf"))
    assert "num_threads" not in kvs  # commented out in the file
    c = Config(kvs)
    assert c.objective == "binary"
    assert c.num_iterations == 100  # the file says num_trees: an alias
    assert c.metric == ["binary_logloss", "auc"]
    assert (c.num_leaves, c.learning_rate, c.min_sum_hessian_in_leaf) == (
        63, 0.1, 5.0)


def test_to_string_roundtrip_keys():
    c = Config({"num_leaves": 63})
    s = c.to_string()
    assert "[num_leaves: 63]" in s
    assert "[learning_rate: 0.1]" in s
    # boosting is [no-save] in the reference spec (stored as submodel name)
    assert "[boosting:" not in s


def test_uninitialized_reference_params_present():
    c = Config({"monotone_constraints": "1,-1,0", "eval_at": "1,3,5"})
    assert c.monotone_constraints == [1, -1, 0]
    assert c.eval_at == [1, 3, 5]
    assert not hasattr(Config(), "value")  # no bogus extraction artifacts


def test_no_save_params_excluded_from_to_string():
    s = Config().to_string()
    assert "[config:" not in s
    assert "[output_model:" not in s
    assert "[task:" not in s
    assert "[num_leaves: 31]" in s


def test_explicit_num_leaves_not_clamped():
    c = Config({"num_leaves": 31, "max_depth": 3})
    assert c.num_leaves == 31
    assert Config({"max_depth": 3}).num_leaves == 8


def test_verbosity_duplicate_takes_min():
    assert kv2map(["verbosity=1", "verbosity=-1"]) == {"verbosity": "-1"}
    out = key_alias_transform({"verbosity": 1, "verbose": -1})
    assert out == {"verbosity": -1}


def test_unimplemented_gain_params_warn_loudly(capsys):
    """path_smooth / monotone_penalty must never be silent no-ops: the
    config emits a loud warning naming the ignored parameter."""
    Config({"path_smooth": 0.5, "monotone_penalty": 2.0})
    out = capsys.readouterr().out
    assert "path_smooth" in out and "IGNORED" in out
    assert "monotone_penalty" in out
    # defaults stay quiet
    Config()
    assert "path_smooth" not in capsys.readouterr().out
