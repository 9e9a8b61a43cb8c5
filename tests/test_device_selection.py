"""Nothing on the main path hides the device it runs on.

An explicit device_type=tpu without a TPU is an error, `auto` says why it
chose the host loop, a backend that fails to start is not read as "use the
CPU path", a Pallas kernel is interpreted only when asked by name, and the
compile cache sits where the operator — or else the checkout — says.
"""
import os
import pathlib
import re

import jax
import numpy as np
import pytest

import lightgbm_tpu  # noqa: F401 - import places the compile cache
from lightgbm_tpu.config import Config
from lightgbm_tpu.io.dataset import Dataset as CoreDataset
from lightgbm_tpu.models import sample_strategy
from lightgbm_tpu.ops import histogram
from lightgbm_tpu.treelearner import serial
from lightgbm_tpu.treelearner.device import DeviceTreeLearner
from lightgbm_tpu.utils import backend
from lightgbm_tpu.utils.log import LightGBMError, register_log_callback

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _learner(params, categorical=False):
    rng = np.random.RandomState(0)
    X = rng.randn(300, 4)
    if categorical:
        X[:, 3] = rng.randint(0, 5, 300)
    cfg = Config(dict(params, objective="binary", num_leaves=7))
    ds = CoreDataset.from_matrix(X, label=(X[:, 0] > 0).astype(float),
                                 config=cfg,
                                 categorical_feature=[3] if categorical else ())
    return serial.create_tree_learner("serial", cfg.device_type, cfg, ds)


@pytest.fixture
def log_lines():
    lines = []
    register_log_callback(lines.append)
    yield lines
    register_log_callback(None)


def test_explicit_tpu_without_a_tpu_is_fatal():
    with pytest.raises(LightGBMError, match="device_type=tpu"):
        _learner({"device_type": "tpu"})


def test_auto_without_a_tpu_grows_on_the_host_and_says_so(log_lines):
    learner = _learner({})
    assert type(learner) is serial.SerialTreeLearner
    assert any("device_type=auto: no TPU attached" in ln for ln in log_lines)


def test_explicit_cpu_never_asks_the_backend(monkeypatch):
    monkeypatch.setattr(serial, "on_tpu",
                        lambda: pytest.fail("device_type=cpu asked on_tpu"))
    assert type(_learner({"device_type": "cpu"})) is serial.SerialTreeLearner


@pytest.mark.parametrize("device_type, level", [("auto", "[Info]"),
                                                ("tpu", "[Warning]")])
def test_on_a_tpu_a_config_reason_is_named(monkeypatch, log_lines,
                                           device_type, level):
    monkeypatch.setattr(serial, "on_tpu", lambda: True)
    params = {} if device_type == "auto" else {"device_type": device_type}
    learner = _learner(params, categorical=True)
    assert type(learner) is serial.SerialTreeLearner
    said = [ln for ln in log_lines if "categorical features" in ln]
    assert said and level in said[0]
    # and with nothing in the way, a TPU means the device learner
    assert type(_learner(params)) is DeviceTreeLearner


def _no_backend():
    raise RuntimeError("Unable to initialize backend 'tpu'")


@pytest.mark.parametrize("ask", [
    lambda mp: (mp.setattr(serial, "on_tpu", _no_backend), _learner({})),
    lambda mp: (mp.setattr(histogram, "on_tpu", _no_backend),
                histogram.build_histogram(np.zeros((1, 8), np.uint8),
                                          np.zeros((8, 3), np.float32), 4)),
    lambda mp: (mp.setattr(backend, "on_tpu", _no_backend),
                mp.delenv("LGBM_TPU_GOSS_DEVICE", raising=False),
                sample_strategy.use_device_goss()),
], ids=["learner-factory", "histogram-dispatch", "device-goss"])
def test_backend_init_error_propagates(monkeypatch, ask):
    with pytest.raises(RuntimeError, match="Unable to initialize backend"):
        ask(monkeypatch)


def test_on_tpu_reads_the_default_device(monkeypatch):
    assert backend.on_tpu() is False  # the suite runs on the CPU

    class Chip:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    assert backend.on_tpu() is True


def test_interpret_only_when_asked_by_name(monkeypatch):
    monkeypatch.delenv("LGBM_TPU_PALLAS_INTERPRET", raising=False)
    assert backend.pallas_interpret() is False  # even here, on the CPU
    monkeypatch.setenv("LGBM_TPU_PALLAS_INTERPRET", "1")
    assert backend.pallas_interpret() is True


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def _only_the_key_rule(name, value):
    """What configure_compile_cache may set where the directory was chosen
    elsewhere: that names are part of the key, never where the cache is."""
    if name != "jax_compilation_cache_include_metadata_in_key":
        pytest.fail(f"set a cache option in code: {name}={value}")


def test_compile_cache_dir_is_the_operators_when_set(cache_dir_restored,
                                                     monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/chosen")
    monkeypatch.setattr(jax.config, "update", _only_the_key_rule)
    backend.configure_compile_cache()


def test_compile_cache_dir_is_the_applications_when_set(cache_dir_restored,
                                                        monkeypatch):
    """An application that configured JAX before importing the package
    keeps its directory: importing a library does not redirect it."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", "/the/applications/own")
    monkeypatch.setattr(jax.config, "update", _only_the_key_rule)
    assert backend.configure_compile_cache() == "/the/applications/own"


def test_compile_cache_dir_defaults_into_the_checkout(cache_dir_restored,
                                                      monkeypatch):
    # importing the package already placed it (or left the operator's)
    assert jax.config.jax_compilation_cache_dir == (
        os.environ.get("JAX_COMPILATION_CACHE_DIR")
        or os.path.join(REPO, ".jax_cache"))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax.config.update("jax_compilation_cache_dir", None)
    assert backend.configure_compile_cache() == os.path.join(REPO,
                                                             ".jax_cache")
    assert backend.COMPILE_CACHE_DIR == os.path.join(REPO, ".jax_cache")


# ------------------------------------------------- the LGBM_TPU_* switches

SWITCH = re.compile(r"LGBM_TPU_[A-Z0-9_]+")
# Chosen by the chip and deleted with their losing paths (CHANGES.md, PR
# 30). A name comes back only with a cell that measures it.
DELETED_SWITCHES = {"LGBM_TPU_" + tail for tail in (
    "SCAN_PALLAS", "PREDICT_PALLAS", "HIST_SLOTS", "HIST", "GH_BF16",
    "ADAPTIVE_WAVE", "WAVE", "COMPACT_ALIAS")}


def _switches_under(*dirs) -> dict:
    """name -> the files under `dirs` that spell it (text files whole:
    a comment that names a switch keeps it alive in a reader's mind)."""
    found = {}
    for top in dirs:
        for path in sorted(pathlib.Path(REPO, top).rglob("*")):
            if (not path.is_file() or "__pycache__" in path.parts
                    or path.suffix in (".pyc", ".so")):
                continue
            for name in SWITCH.findall(path.read_text(errors="ignore")):
                found.setdefault(name, set()).add(
                    str(path.relative_to(REPO)))
    return found


def test_every_switch_the_package_reads_is_in_the_one_table():
    """docs/SWITCHES.md has one row per LGBM_TPU_* name the package reads,
    and no row for a name it does not: a switch cannot arrive, or outlive
    its code, without the table saying what it selects and why it stays."""
    rows = re.findall(r"^\| `(LGBM_TPU_[A-Z0-9_]+)` \| ([a-z ]+) \|",
                      pathlib.Path(REPO, "docs", "SWITCHES.md").read_text(),
                      flags=re.M)
    documented = [name for name, _ in rows]
    assert len(documented) == len(set(documented))
    assert {kind for _, kind in rows} == {"deployment", "instrument",
                                          "path choice"}
    read = _switches_under("lightgbm_tpu")
    assert set(read) - set(documented) == set(), "read, not documented"
    assert set(documented) - set(read) == set(), "documented, never read"


def test_no_deleted_switch_is_spelled_anywhere():
    spelled = _switches_under("lightgbm_tpu", "tools", "tests")
    here = str(pathlib.Path(__file__).relative_to(REPO))
    left = {name: sorted(files - {here})
            for name, files in spelled.items()
            if name in DELETED_SWITCHES and files - {here}}
    assert left == {}
