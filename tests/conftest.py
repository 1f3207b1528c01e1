"""Test harness setup.

Tests run on a virtual 8-device CPU mesh (the analogue of the reference's
IPC-on-one-box multi-node rig, `scripts/run_experiments.py:67` /
`transport/transport.cpp:132` — SURVEY §4.4): sharding and collective code
paths execute for real without TPU hardware.

Tests never ask for an accelerator: the platform is pinned to the CPU
through jax.config before any backend is initialised, whatever
``JAX_PLATFORMS`` the shell carries.  The persistent compile cache stays
off (JAX's own ``JAX_ENABLE_COMPILATION_CACHE``, inherited by the node
processes the tests spawn): tests must not fill the checkout's
``.jax_cache`` — least of all with compiles for a described chip, which
no process could read back.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process integration tests (cluster boots)")
