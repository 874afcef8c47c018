"""Test harness config.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip shardings
(dp/tp/sp over jax.sharding.Mesh) are exercised without TPU hardware, per
the driver contract.  Must run before jax initializes its backends, hence
the env mutation at import time.
"""

import os
import sys

# Tests run on the CPU: this sandbox has no accelerator, and on a machine
# that has one the chip belongs to a single process (the sidecar under
# chip_smoke.py), never to a test worker.  Pin it hard, before jax picks
# its backends, whatever the environment says.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

import jax  # noqa: E402  (after env mutation, before backend init)

jax.config.update("jax_platforms", "cpu")

# The suite is compile-dominated (many bucket shapes); persist compiled
# executables across runs, where every other entry point keeps them.
from fastdfs_tpu import compile_cache  # noqa: E402

compile_cache.configure()


import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def native_tree():
    """The C++ tree is built once, before the first test of a session.
    Half the suite runs its binaries, and many tests reach for
    ``fdfs_codec`` or ``fdfs_load`` without asking for a build: on a fresh
    checkout they used to race the worker that was still linking them.
    Under xdist every worker comes through here; one builds (a file lock,
    ~25 s), the rest wait for it."""
    from harness import ensure_native_built

    ensure_native_built()
