"""The rule that decides who sees the chip (`_private/accelerators.py`), and
the worker pool that follows it.

- a worker's environment is a pure function of (parent environment, grant,
  detected-or-declared): only a TPU-granted process is left off the CPU pin;
- whole slots buy a process of their own, a killed gang's chip comes back
  before the next grant, and a granted worker's compile cache goes where
  `JAX_COMPILATION_CACHE_DIR` says;
- `ScalingConfig(use_tpu=True)` asks for what the nodes advertise;
- telemetry's device sampling never opens a JAX backend.
"""

import os
import subprocess
import sys

import pytest

import ray_tpu
from ray_tpu._private import accelerators

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------- rule A: who sees the chip

FIXED_CACHE = os.path.join(REPO, ".jax_cache")


@pytest.mark.parametrize("parent, grant, host, detected, want", [
    # no grant: pinned to the CPU, whatever the parent says
    ({}, None, 1, True, {"JAX_PLATFORMS": "cpu"}),
    ({"JAX_PLATFORMS": "tpu"}, None, 4, True, {"JAX_PLATFORMS": "cpu"}),
    ({}, [], 4, False, {"JAX_PLATFORMS": "cpu"}),
    # declared chips, parent names nothing: not forced anywhere
    ({}, [0], 1, False,
     {"JAX_PLATFORMS": None, "JAX_COMPILATION_CACHE_DIR": FIXED_CACHE}),
    # detected chips, parent names nothing: the TPU is named, so that a
    # chip that cannot be opened raises instead of falling back
    ({}, [0], 1, True,
     {"JAX_PLATFORMS": "tpu", "JAX_COMPILATION_CACHE_DIR": FIXED_CACHE}),
    # the parent's platform is inherited (the tier-1 tests export cpu)
    ({"JAX_PLATFORMS": "cpu"}, [0], 1, True,
     {"JAX_PLATFORMS": "cpu", "JAX_COMPILATION_CACHE_DIR": FIXED_CACHE}),
    # the cache goes where the variable says
    ({"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}, [0, 1, 2, 3], 4, True,
     {"JAX_PLATFORMS": "tpu", "JAX_COMPILATION_CACHE_DIR": "/elsewhere"}),
    # one chip of a larger host: restricted to it
    ({}, [2], 4, True,
     {"JAX_PLATFORMS": "tpu", "JAX_COMPILATION_CACHE_DIR": FIXED_CACHE,
      "TPU_VISIBLE_CHIPS": "2", "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
      "TPU_PROCESS_BOUNDS": "1,1,1", "TPU_CHIPS_PER_HOST_BOUNDS": "1,1,1",
      "TPU_HOST_BOUNDS": "1,1,1"}),
])
def test_worker_env_is_a_pure_function(parent, grant, host, detected, want):
    before = dict(parent)
    assert accelerators.worker_env(parent, grant, host, detected) == want
    assert parent == before


def test_compile_cache_dir_is_fixed_and_inside_the_checkout():
    a = accelerators.compile_cache_dir({})
    assert a == accelerators.compile_cache_dir({"TMPDIR": "/x"}) == FIXED_CACHE
    assert accelerators.compile_cache_dir(
        {"JAX_COMPILATION_CACHE_DIR": "/given"}) == "/given"
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_pool_key_and_grant_error():
    key = accelerators.pool_key
    assert key("", None) == "" and key("env", []) == "env"
    assert len({key("", None), key("", [0]), key("", [1]),
                key("", [1, 0]), key("e", [0])}) == 5
    assert key("", [1, 0]) == key("", [0, 1])
    err = accelerators.grant_error
    assert err(1, 4, True) is None and err(4, 4, True) is None
    assert err(1, 1, True) is None and err(2, 4, False) is None
    assert "2 of this host's 4" in err(2, 4, True)


@pytest.mark.parametrize("dev, want", [
    ({"/dev/accel[0-9]*": ["/dev/accel0", "/dev/accel1", "/dev/accel2",
                           "/dev/accel3"]}, 4),
    ({"/dev/vfio/*": ["/dev/vfio/vfio", "/dev/vfio/0"]}, 1),
    ({}, 0),
])
def test_detect_tpus_reads_dev_without_jax(dev, want, monkeypatch):
    monkeypatch.setattr(accelerators.glob, "glob",
                        lambda pat: dev.get(pat, []))
    assert accelerators.detect_tpus() == want
    assert ray_tpu._detect_tpus is accelerators.detect_tpus


def test_granted_actor_gets_its_own_uncpu_pinned_process(monkeypatch):
    """Parent environment names no platform, chips declared: the plain
    task's worker is CPU-pinned, the num_tpus=1 actor's process is not —
    and it is not one of the pooled CPU workers."""
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    ray_tpu.init(num_cpus=2, num_tpus=1)
    try:
        def env():
            return (os.getpid(), os.environ.get("JAX_PLATFORMS"),
                    os.environ.get("JAX_COMPILATION_CACHE_DIR"))

        plain = ray_tpu.remote(env)

        @ray_tpu.remote(num_tpus=1)
        class Granted:
            def env(self):
                return env()

        pooled = ray_tpu.get([plain.remote() for _ in range(6)], timeout=60)
        assert {p[1] for p in pooled} == {"cpu"}
        assert {p[2] for p in pooled} == {None}
        pid, platforms, cache = ray_tpu.get(Granted.remote().env.remote(),
                                            timeout=60)
        assert platforms is None and cache == FIXED_CACHE
        assert pid not in {p[0] for p in pooled} and pid != os.getpid()
        # and a CPU task never lands on the process that holds the chip
        after = ray_tpu.get([plain.remote() for _ in range(6)], timeout=60)
        assert pid not in {p[0] for p in after}
    finally:
        ray_tpu.shutdown()


def test_killed_gang_returns_its_chip_before_the_next_grant():
    """Elastic restart: kill the granted actor, drop its placement group,
    ask again at once. The next actor must hold the slot — not run without
    one while the dying process still owns the chip."""
    from ray_tpu.util.placement_group import (placement_group,
                                              remove_placement_group)
    from ray_tpu.util.scheduling_strategies import (
        PlacementGroupSchedulingStrategy)

    ray_tpu.init(num_cpus=2, num_tpus=1)
    try:
        @ray_tpu.remote
        class Member:
            def slots(self):
                return (os.getpid(), ray_tpu.get_runtime_context()
                        .get_accelerator_ids()["TPU"])

        pids = set()
        for _ in range(3):
            pg = placement_group([{"CPU": 1, "TPU": 1}])
            pg.ready(timeout=30)
            a = Member.options(
                num_cpus=1, resources={"TPU": 1},
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    placement_group=pg,
                    placement_group_bundle_index=0)).remote()
            pid, slots = ray_tpu.get(a.slots.remote(), timeout=60)
            assert slots == [0]
            pids.add(pid)
            ray_tpu.kill(a)
            remove_placement_group(pg)
        assert len(pids) == 3
    finally:
        ray_tpu.shutdown()


def test_granted_worker_compiles_into_the_given_cache_dir(tmp_path,
                                                         monkeypatch):
    """With JAX_COMPILATION_CACHE_DIR set, what a granted worker compiles
    lands there and the fixed directory inside the checkout is not
    touched."""
    def listing(path):
        return sorted(os.listdir(path)) if os.path.isdir(path) else None

    cache = tmp_path / "cache"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache))
    # a tiny program compiles too quickly to be cached by default
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    fixed_before = listing(FIXED_CACHE)
    ray_tpu.init(num_cpus=2, num_tpus=1)
    try:
        @ray_tpu.remote(num_tpus=1)
        class Granted:
            def compile(self):
                import jax
                import jax.numpy as jnp
                out = jax.jit(lambda x: jnp.tanh(x @ x).sum())(
                    jnp.ones((64, 64)))
                return (float(out),
                        os.environ.get("JAX_COMPILATION_CACHE_DIR"))

        _, given = ray_tpu.get(Granted.remote().compile.remote(),
                               timeout=120)
        assert given == str(cache)
        assert os.listdir(cache)
        assert listing(FIXED_CACHE) == fixed_before
    finally:
        ray_tpu.shutdown()


# ------------------------------------------------------- B: use_tpu=True

@pytest.mark.parametrize("chips", [1, 4])
def test_use_tpu_asks_for_what_the_node_advertises(chips):
    from ray_tpu.train import ScalingConfig

    ray_tpu.init(num_cpus=2, num_tpus=chips)
    try:
        assert ScalingConfig(use_tpu=True).bundle() == {
            "CPU": 1.0, "TPU": float(chips)}
        assert ScalingConfig(
            use_tpu=True, resources_per_worker={"TPU": 2.0}
        ).bundle()["TPU"] == 2.0
        assert "TPU" not in ScalingConfig().bundle()
    finally:
        ray_tpu.shutdown()


# ------------------------------------------- telemetry never opens a backend

def test_sample_devices_does_not_initialise_a_backend():
    code = (
        "import jax\n"
        "from jax._src import xla_bridge\n"
        "from ray_tpu._private import accelerators, telemetry\n"
        "assert telemetry.sample_devices() == 0\n"
        "telemetry.sample_once()\n"
        "assert not xla_bridge.backends_are_initialized()\n"
        "assert not accelerators.jax_backend_initialized()\n"
        "jax.devices()\n"
        "assert accelerators.jax_backend_initialized()\n"
        "assert telemetry.sample_devices() == 0   # CPU: no memory stats\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
