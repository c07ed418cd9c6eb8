"""Test bootstrap: distributed-without-a-cluster (SURVEY.md §4.3-4.4).

The reference runs its whole distributed stack on Spark local[8] + Ray local
(pyzoo/test/zoo/orca/learn/ray/pytorch/conftest.py:22-40).  The TPU-native
analog: 8 virtual CPU devices via --xla_force_host_platform_device_count, so
every test exercises real mesh sharding and XLA collectives with no TPU.

Must run before jax is imported anywhere.
"""

import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# force CPU: the tests run on the host's virtual devices whatever the
# machine has attached
os.environ["JAX_PLATFORMS"] = "cpu"
# persistent compile cache (same rule as __graft_entry__.py/
# chip_smoke.py: the environment's directory when it names one, else a
# fixed path in the checkout): the suite is dominated by XLA CPU
# compiles of conv/transformer train steps; warm reruns skip them
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".jax_cache_tests"))
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# keep the 5s floor: lowering it to 1s was tried (r6) and REVERTED —
# it persists the many tiny train-step executables, and XLA:CPU compile
# variants differ slightly in float accumulation, so a frozen unlucky
# variant flips margin tests (test_finetune_beats_scratch 0.695 vs
# >0.9, chronos mtnet/tcmf NaNs).  The >5s compiles (ring/flash/
# shard_map suites) are what the 870s budget needs cached anyway.
jax.config.update("jax_persistent_cache_min_compile_time_secs", 5.0)

import pytest  # noqa: E402


@pytest.fixture()
def orca_context_local():
    """Fresh local context per test that needs explicit init."""
    from analytics_zoo_tpu import init_orca_context, stop_orca_context
    stop_orca_context()
    mesh = init_orca_context(cluster_mode="local")
    yield mesh
    stop_orca_context()
