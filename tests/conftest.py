"""Test bootstrap: force an 8-device virtual CPU mesh before JAX imports.

Mirrors the reference's test strategy (SURVEY.md §4): multi-"node" behavior is
tested without real hardware — here via jax_num_cpu_devices, the analog of
InternalTestCluster booting N nodes in one JVM.
"""

import os

# both are read when jax is first imported, so they are set before it
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_NUM_CPU_DEVICES"] = "8"

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)
