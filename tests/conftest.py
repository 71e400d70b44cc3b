"""Test config: run on a virtual 8-device CPU mesh so sharding paths are
exercised without TPU hardware (SURVEY §4 implication — the multi-process
trick maps to XLA_FLAGS=--xla_force_host_platform_device_count=N).

The mesh is pinned before jax's first device query (xla_env); the env
var is set too so subprocesses the tests spawn stay on the CPU.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from xla_env import use_host_mesh  # noqa: E402

os.environ["JAX_PLATFORMS"] = "cpu"
jax = use_host_mesh(8)
# The suite runs with jax's persistent compilation cache off (the cache
# tests turn it on for themselves). XLA:CPU's loader logs an error-level
# line for every executable it reads back from the cache; with ~1,000
# tests sharing one cache those lines land inside pytest's progress
# lines, which the tier-1 driver counts.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np
import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running end-to-end legs, excluded from the tier-1 "
        "run (-m 'not slow'); scripts/ci.sh online/bench stages run "
        "them explicitly")


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs, scope and name counters."""
    import paddle_tpu as fluid
    from paddle_tpu import framework, unique_name
    from paddle_tpu.core import scope as scope_mod

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    old_gen = unique_name.switch()
    scope_mod._scope_stack[:] = [scope_mod.Scope()]
    np.random.seed(42)
    yield
    unique_name.switch(old_gen)


def assert_devices():
    assert len(jax.devices()) == 8, jax.devices()
