"""Shared fixtures.

Mirrors the reference's test strategy (SURVEY §4): ``rtpu_init`` boots a
real single-node runtime per test (reference: ``ray_start_regular``,
``python/ray/tests/conftest.py:410``); ``rtpu_cluster`` runs a real
multi-node cluster in one process (reference: ``ray_start_cluster`` :491).

JAX tests run on a virtual 8-device CPU mesh: the env vars below must be
set before jax is imported anywhere in the process.
"""

import os

# Lock-order sanitizer (ISSUE 7): every tier-1 test doubles as a
# sanitizer run — locksan wraps every declared runtime lock, checks the
# DESIGN.md hierarchy, and detects cross-thread A->B/B->A inversions
# online. setdefault so perf-sensitive runs can opt out with
# RTPU_LOCKSAN=0; must be set BEFORE ray_tpu (and any spawned worker,
# which inherits the env) imports locksan.
os.environ.setdefault("RTPU_LOCKSAN", "1")

# Guarded-by field sanitizer (ISSUE 15): beside the lock-order checks,
# every tier-1 test also verifies that threads touching declared shared
# fields (locksan.FIELDS) hold the declared guard — cross-thread
# read-write/write-write pairs with an unguarded write side are
# reported with both stacks. setdefault so perf runs can opt out with
# RTPU_FIELDSAN=0; must be set BEFORE ray_tpu imports fieldsan.
os.environ.setdefault("RTPU_FIELDSAN", "1")

# Tests run on a virtual 8-device CPU mesh, also on a machine that has a
# chip: the env override reaches every spawned worker (a TPU-granted
# worker inherits the platform its node was started with), the config
# update below covers a jax that was imported before this file.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

import ray_tpu  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: left out of the tier-1 run (-m 'not slow')")


def pytest_sessionfinish(session, exitstatus):
    # surface driver-process sanitizer reports in the summary (worker
    # processes print theirs to worker logs, forwarded to stdout live)
    from ray_tpu._private import fieldsan, locksan

    v = locksan.violations()
    if v:
        print(f"\n[locksan] {len(v)} lock-order violation(s) observed "
              "in the driver process — see [locksan] stderr reports "
              "above")
    fv = fieldsan.violations()
    if fv:
        fields = sorted({r["field"] for r in fv})
        print(f"\n[fieldsan] {len(fv)} guarded-by violation(s) observed "
              f"in the driver process across {len(fields)} field(s) "
              f"({', '.join(fields[:8])}"
              f"{', ...' if len(fields) > 8 else ''}) — see [fieldsan] "
              "stderr reports above")


@pytest.fixture
def rtpu_init():
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


@pytest.fixture
def rtpu_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True,
                      head_node_args={"num_cpus": 2})
    ray_tpu.init(address=cluster)
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()
