"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Multi-device logic (sharding, collectives, global-vs-local NT-Xent) is tested
without TPU hardware via XLA's host-platform device-count flag, per the test
strategy in SURVEY.md §4. Both variables are set before jax is imported, and
subprocess tests inherit them. The chip is never used from here: what runs
there is ``chip_smoke.py``, through the chip tool (README, "Tests").
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
    # the CPU suite is compile-bound (every shard_map train step is a fresh
    # LLVM build on one core); level 0 trades executable speed — irrelevant
    # for tiny test models — for ~30% less compile time. Subprocess e2e
    # tests inherit this via the environment.
    + " --xla_backend_optimization_level=0"
)
os.environ.setdefault("JAX_ENABLE_X64", "0")
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

assert jax.default_backend() == "cpu", (
    f"tests must run on the virtual CPU mesh, got {jax.default_backend()}"
)
assert jax.device_count() == 8, (
    f"expected 8 virtual CPU devices, got {jax.device_count()}"
)
