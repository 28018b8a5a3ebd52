"""chip_smoke.py on the CPU, and the rule that decides who sees the chip.

The smoke's phases run here at `llama_tiny` size with the expected
platform passed as a function argument (the script itself takes no size
or platform option). What these tests pin:

- the LAST line of stdout is the contract's JSON object, also while a
  worker writes to stdout and stderr until the runtime kills it;
- plain `python chip_smoke.py` on a machine without a chip fails in
  seconds, in the same shape, with `"ok": false`;
- a worker's environment is a pure function of (parent environment, grant,
  detected-or-declared): only a TPU-granted process is left off the CPU pin;
- `ScalingConfig(use_tpu=True)` asks for what the nodes advertise;
- telemetry's device sampling never opens a JAX backend.
"""

import json
import os
import subprocess
import sys
import time

import pytest

import ray_tpu
from ray_tpu._private import accelerators

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PHASES = """
import sys, time
sys.path.insert(0, {repo!r})
import chip_smoke, ray_tpu

ray_tpu.init(num_cpus=4, num_tpus={chips})

@ray_tpu.remote(num_cpus=0)
class Chatty:
    def run(self):
        while True:      # until the runtime kills this process
            print("chatter on stdout", flush=True)
            print("chatter on stderr", file=sys.stderr, flush=True)
            time.sleep(0.005)

class SlowForwarding:
    # stdout on which every forwarded chunk takes its time: a forwarder
    # that is still running when the summary is printed writes after it
    def __init__(self, out):
        self._out = out
    def write(self, text):
        if text.startswith("(worker"):
            time.sleep(0.7)
        return self._out.write(text)
    def __getattr__(self, name):
        return getattr(self._out, name)

chatty = Chatty.remote()
chatty.run.remote()
summary = chip_smoke.run_phases({chips}, "cpu", chip_smoke.TINY)
sys.stdout = SlowForwarding(sys.stdout)
time.sleep(1.0)          # by now a chunk is in flight all of the time
code = chip_smoke.finish(summary)
time.sleep(1.5)          # room for a forwarder that outlived shutdown()
sys.exit(code)
"""


def _run(args, tmp_path, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    t0 = time.monotonic()
    proc = subprocess.run([sys.executable, *args], cwd=tmp_path, env=full,
                          capture_output=True, text=True, timeout=300)
    return proc, time.monotonic() - t0


def _last_line(proc) -> dict:
    """The contract: the last line of stdout is one JSON object with
    exactly these keys."""
    lines = proc.stdout.splitlines()
    assert lines, proc.stderr[-2000:]
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"}, lines[-1]
    assert set(last["device"]) == {"platform", "kind", "count"}, lines[-1]
    assert isinstance(last["ok"], bool)
    return last


def _listing(path):
    return sorted(os.listdir(path)) if os.path.isdir(path) else None


@pytest.fixture(scope="module", autouse=True)
def phase_runs(tmp_path_factory):
    """Both tiny runs, started together before this module's first test and
    read by its last two: each is mostly process starts and compiles, and
    the suite's time limit is real. Each has its own cluster, cache and run
    directory."""
    fixed_before = _listing(os.path.join(REPO, ".jax_cache"))
    runs = {}
    for chips in (1, 4):
        tmp = tmp_path_factory.mktemp(f"phases{chips}")
        env = dict(
            os.environ, JAX_PLATFORMS="cpu",
            JAX_COMPILATION_CACHE_DIR=str(tmp / "cache"),
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={chips}")
        with open(tmp / "out", "w") as out, open(tmp / "err", "w") as err:
            runs[chips] = subprocess.Popen(
                [sys.executable, "-c",
                 _PHASES.format(repo=REPO, chips=chips)],
                cwd=tmp, env=env, stdout=out, stderr=err), tmp
    yield runs, fixed_before
    for proc, _ in runs.values():
        if proc.poll() is None:
            proc.kill()


@pytest.mark.skipif(accelerators.detect_tpus() > 0,
                    reason="this machine has a chip")
def test_plain_smoke_without_a_chip_fails_fast_in_the_same_shape(tmp_path):
    proc, took = _run([os.path.join(REPO, "chip_smoke.py")], tmp_path)
    last = _last_line(proc)
    assert proc.returncode != 0 and last["ok"] is False
    assert last["device"]["platform"] != "tpu"
    assert '"platform": "tpu"' not in proc.stdout
    assert took < 30, f"took {took:.0f}s: a placement wait crept in"


def test_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=60)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


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


# ------------------------------------------ the phases, at tiny size (last:
# the two runs started with the module and have had the tests above to finish)

@pytest.mark.parametrize("chips", [1, 4])
def test_phases_end_on_the_contract_line_despite_a_chatty_worker(
        chips, phase_runs):
    """Both fits of the one-chip run (and the sharded phase on four virtual
    devices) at tiny size; a worker chatters throughout. Also: with
    JAX_COMPILATION_CACHE_DIR set, that directory fills and the fixed
    one inside the checkout is not touched."""
    runs, fixed_before = phase_runs
    proc, tmp = runs[chips]
    proc.wait(timeout=300)
    proc.stdout, proc.stderr = ((tmp / "out").read_text(),
                                (tmp / "err").read_text())
    cache = tmp / "cache"
    last = _last_line(proc)
    assert proc.returncode == 0 and last["ok"] is True, (
        proc.stdout[-3000:] + proc.stderr[-3000:])
    assert last["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips}
    # the forwarder was on and busy, and still nothing followed the summary
    assert "chatter on stdout" in proc.stdout
    assert "chatter on stderr" in proc.stdout
    assert os.listdir(cache)
    assert _listing(os.path.join(REPO, ".jax_cache")) == fixed_before
    claims = [json.loads(l) for l in proc.stdout.splitlines()
              if l.startswith("{") and '"claim"' in l]
    assert claims and all(c["claim"] is None for c in claims)
