"""Test configuration: run on a virtual 8-device CPU mesh.

Must set the env vars BEFORE jax is imported anywhere (the platform and
device count are fixed at backend init).
"""

import os

# tests run on the virtual 8-device CPU mesh whatever the ambient
# environment names (the chip is reached only through chip_smoke.py)
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np
import pytest

# persistent compile cache: the grower's while_loop compiles are 10-40s
# each on CPU; cache them across test runs. The location is decided in
# ONE place (lightgbm_tpu/_cache.py)
from lightgbm_tpu._cache import ensure_compile_cache

ensure_compile_cache()


# NOTE on the historical mid-suite segfaults (exit 139 under a
# fused_dispatch frame): root-caused to XLA:CPU buffer donation on the
# fused step — glibc malloc-internal crashes from a freed-buffer write,
# drifting between tests as allocation patterns changed. boosting._build_fused now disables donation on the cpu
# backend; the per-module cache-clearing workarounds are superseded.


@pytest.fixture
def rng():
    return np.random.RandomState(42)


# trace-safety fixtures (retrace_guard, jaxpr_audit) from the analysis
# suite's pytest plugin — imported rather than duplicated so the
# in-repo suite and external suites (opt-in via
# `pytest -p lightgbm_tpu.analysis.pytest_plugin`) share one definition
from lightgbm_tpu.analysis.pytest_plugin import (  # noqa: E402,F401
    concurrency_lint,
    cost_audit,
    jaxpr_audit,
    retrace_guard,
    scale_audit,
)


@pytest.fixture
def mesh4(monkeypatch):
    """tree_learner=data over FOUR of the eight virtual devices (the
    four chips of one host): the Booster asks data_parallel.make_mesh
    for its mesh, so steering that is steering the path, with no option
    of the program. Returns the four devices."""
    import jax

    from lightgbm_tpu.parallel import data_parallel

    devices = jax.devices()[:4]
    real = data_parallel.make_mesh
    monkeypatch.setattr(
        data_parallel, "make_mesh",
        lambda devs=None, axis_name="data": real(
            devices if devs is None else devs, axis_name))
    return devices


def make_synthetic_regression(n=1000, n_features=10, seed=42):
    """Small regression fixture (reference tests utils.py pattern)."""
    rs = np.random.RandomState(seed)
    X = rs.randn(n, n_features)
    w = rs.randn(n_features)
    y = X @ w + 0.1 * rs.randn(n)
    return X, y


def make_synthetic_binary(n=1000, n_features=10, seed=42):
    rs = np.random.RandomState(seed)
    X = rs.randn(n, n_features)
    w = rs.randn(n_features)
    logits = X @ w
    y = (logits + 0.5 * rs.randn(n) > 0).astype(np.float64)
    return X, y
