"""Worker log streaming to the driver.

Reference: ``python/ray/_private/log_monitor.py:103`` — worker
stdout/stderr is tailed per node and surfaced on the driver.
"""

import sys
import time

import ray_tpu


def _wait_for(capsys, needle: str, timeout: float = 20.0) -> str:
    deadline = time.monotonic() + timeout
    seen = ""
    while time.monotonic() < deadline:
        seen += capsys.readouterr().out
        if needle in seen:
            return seen
        time.sleep(0.2)
    raise AssertionError(f"{needle!r} never reached the driver; saw:\n{seen}")


def test_remote_print_reaches_driver(rtpu_init, capsys):
    @ray_tpu.remote
    def chatty():
        print("hello-from-rtpu-task")
        sys.stderr.write("stderr-from-rtpu-task\n")
        return 1

    assert ray_tpu.get(chatty.remote(), timeout=60) == 1
    out = _wait_for(capsys, "hello-from-rtpu-task")
    # stderr is merged into the worker log stream too (may land in the
    # same batch the first wait already consumed)
    if "stderr-from-rtpu-task" not in out:
        out += _wait_for(capsys, "stderr-from-rtpu-task")
    # lines carry a worker/node prefix for attribution
    line = next(ln for ln in out.splitlines()
                if "hello-from-rtpu-task" in ln)
    assert line.startswith("(worker ")


def test_actor_print_reaches_driver(rtpu_init, capsys):
    @ray_tpu.remote
    class A:
        def speak(self):
            print("actor-says-moo")
            return "ok"

    a = A.remote()
    assert ray_tpu.get(a.speak.remote(), timeout=60) == "ok"
    _wait_for(capsys, "actor-says-moo")


def test_serve_replica_log_attribution(rtpu_init, capsys):
    """Lines printed inside a serve replica carry the deployment name
    (deployment#tag) in the ``(worker ...)`` prefix instead of a bare
    worker id, so driver output / `rtpu logs` is greppable by
    deployment (ISSUE 13 satellite)."""
    from ray_tpu import serve

    @serve.deployment
    def chatty_dep(x):
        print("hello-from-serve-replica")
        return x

    try:
        handle = serve.run(chatty_dep.bind())
        assert handle.remote(1).result(timeout=60) == 1
        out = _wait_for(capsys, "hello-from-serve-replica")
        line = next(ln for ln in out.splitlines()
                    if "hello-from-serve-replica" in ln)
        assert line.startswith("(worker chatty_dep#0 "), line
    finally:
        serve.shutdown()


def test_multinode_logs_reach_driver(rtpu_cluster, capsys):
    cluster = rtpu_cluster
    cluster.add_node(num_cpus=2, resources={"side": 2.0})

    @ray_tpu.remote(resources={"side": 1.0})
    def far_away():
        print("printed-on-the-other-node")
        return True

    assert ray_tpu.get(far_away.remote(), timeout=60)
    _wait_for(capsys, "printed-on-the-other-node")


class _SlowForwarding:
    """stdout on which every forwarded chunk takes its time, and is noted
    with the time it landed: a forwarder that is still running when
    shutdown() returns writes after it."""

    def __init__(self, out):
        self._out = out
        self.forwarded = []         # (landed at, text)

    def write(self, text):
        if text.startswith("(worker"):
            time.sleep(0.3)
            self.forwarded.append((time.monotonic(), text))
        return self._out.write(text)

    def __getattr__(self, name):
        return getattr(self._out, name)


def test_nothing_is_forwarded_after_shutdown_returns(monkeypatch):
    """An actor chatters on stdout and stderr until the runtime kills it.
    Its chatter reaches the driver, and shutdown() stops the forwarder
    before it returns: a script's last line stays its last line."""
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote(num_cpus=0)
        class Chatty:
            def run(self):
                while True:
                    print("chatter on stdout", flush=True)
                    print("chatter on stderr", file=sys.stderr, flush=True)
                    time.sleep(0.005)

        chatty = Chatty.remote()
        chatty.run.remote()
        out = _SlowForwarding(sys.stdout)
        monkeypatch.setattr(sys, "stdout", out)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            seen = "".join(text for _, text in out.forwarded)
            if "chatter on stdout" in seen and "chatter on stderr" in seen:
                break
            time.sleep(0.1)
        else:
            raise AssertionError(f"chatter never reached the driver:\n{seen}")
        time.sleep(0.5)             # by now a chunk is in flight all the time
    finally:
        ray_tpu.shutdown()
    returned = time.monotonic()
    time.sleep(1.5)                 # room for a forwarder that outlived it
    assert not [text for at, text in out.forwarded if at > returned]
