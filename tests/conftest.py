"""Test harness config: run the suite on a virtual 8-device CPU mesh.

Analog of the reference's DistributedQueryRunner approach
(presto-tests/.../DistributedQueryRunner.java:114): multi-node semantics
in a single process. Here, multi-chip semantics come from XLA's
host-platform device partitioning, so sharding/collective code paths are
exercised without TPU hardware.
"""

import os
import sys

# The one shared CPU-forcing armor (env + 8 virtual devices) lives in
# scripts/_cpu.py so ad-hoc scripts and the suite can't drift apart.
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts"))
import _cpu  # noqa: E402,F401

import jax  # noqa: E402  (import after env setup)

import numpy as np
import pytest


@pytest.fixture(scope="session")
def devices():
    return jax.devices()


@pytest.fixture(scope="session")
def mesh8():
    from presto_tpu.parallel.mesh import make_mesh
    return make_mesh(8)


@pytest.fixture
def rng():
    return np.random.default_rng(42)
