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

# The gain-adaptive wave controller (opt-in since PR 29) walks wave_k down
# a bucket_size rung per tree, and every rung is a fresh static shape for
# grow_tree_on_device — extra XLA compiles that triple the wall time of
# every 3-iteration device test here. Keep it off for the suite whatever
# the caller's environment says; the controller's own tests opt back in
# with monkeypatch.setenv("LGBM_TPU_ADAPTIVE_WAVE", "1").
os.environ.setdefault("LGBM_TPU_ADAPTIVE_WAVE", "0")

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
