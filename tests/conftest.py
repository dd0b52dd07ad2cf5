"""Test harness: force an 8-device virtual CPU mesh before jax initializes.

Multi-chip sharding tests run on CPU with
``--xla_force_host_platform_device_count=8`` (SURVEY §4's implication:
multi-chip tests must be runnable without TPU hardware).
"""

import os

# Force, don't setdefault: tests run on the CPU (the virtual 8-device mesh),
# whatever the ambient environment names.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# tests run on the CPU even when jax was imported before this file
jax.config.update("jax_platforms", "cpu")

# hermetic runs: nothing is read from or written to the checkout's
# persistent compilation cache (the CLI entry points enable it)
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def small_dataset():
    from real_time_fraud_detection_system_tpu.config import DataConfig
    from real_time_fraud_detection_system_tpu.data import generate_dataset

    cfg = DataConfig(n_customers=120, n_terminals=240, n_days=45, seed=7)
    customers, terminals, txs = generate_dataset(cfg)
    return cfg, customers, terminals, txs


@pytest.fixture
def rng():
    """A fresh generator per test: what a test draws never depends on
    which tests its worker ran before it."""
    return np.random.default_rng(0)
