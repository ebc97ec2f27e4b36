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


@pytest.fixture(autouse=True, scope="session")
def _no_test_reads_the_hosts_disk():
    """`ClusterNode._disk_usage` falls through to `shutil.disk_usage` unless
    a test sets `disk_usage_pct`; on a host whose volume is over the 85% low
    watermark no replica is ever assigned and a cluster never turns green.
    Every probe of the session reads a fixed 40% instead (session scope: a
    module-scoped cluster fixture is set up before any function-scoped
    one). Tests that set `disk_usage_pct` themselves are unaffected."""
    import collections
    import shutil

    usage = collections.namedtuple("usage", "total used free")
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(shutil, "disk_usage",
                   lambda path: usage(100 << 30, 40 << 30, 60 << 30))
        yield
