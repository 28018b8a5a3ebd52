"""Network plane: OS-isolated node processes joined over TCP.

Reference analogue: multi-node tests against ``ray start --head`` /
``--address`` clusters (``python/ray/tests/test_multinode_failures.py``
and the gRPC topology of ``gcs_service.proto`` / ``node_manager.proto``).
Every node here is a real subprocess with its own GCS connection; the
driver attaches by ``host:port``.
"""

import json
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster


@pytest.fixture
def tcp_cluster():
    cluster = Cluster(initialize_head=True, process_isolated=True,
                      head_node_args={"num_cpus": 2})
    ray_tpu.init(address=cluster)
    yield cluster
    ray_tpu.shutdown()
    cluster.shutdown()


def _wait_for_nodes(n, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        alive = [x for x in ray_tpu.nodes() if x["alive"]]
        if len(alive) >= n:
            return alive
        time.sleep(0.2)
    raise TimeoutError(f"never saw {n} alive nodes")


def test_driver_attach_and_tasks(tcp_cluster):
    @ray_tpu.remote
    def add(a, b):
        return a + b

    assert ray_tpu.get(add.remote(2, 3), timeout=60) == 5
    assert ray_tpu.get([add.remote(i, i) for i in range(10)],
                       timeout=60) == [2 * i for i in range(10)]


def test_large_objects_over_shm(tcp_cluster):
    arr = np.random.rand(200_000)
    ref = ray_tpu.put(arr)
    out = ray_tpu.get(ref, timeout=60)
    assert np.array_equal(out, arr)

    @ray_tpu.remote
    def total(x):
        return float(x.sum())

    assert abs(ray_tpu.get(total.remote(ref), timeout=60)
               - float(arr.sum())) < 1e-6


def test_second_node_joins_and_runs_tasks(tcp_cluster):
    tcp_cluster.add_node(num_cpus=2, resources={"side": 2.0})
    _wait_for_nodes(2)

    @ray_tpu.remote(resources={"side": 1.0})
    def where():
        import os
        return os.getpid()

    # tasks requiring the custom resource must run on the second process
    pids = ray_tpu.get([where.remote() for _ in range(4)], timeout=60)
    assert all(p > 0 for p in pids)

    # cross-node object flow: produce on node 2, consume anywhere
    @ray_tpu.remote(resources={"side": 1.0})
    def produce():
        return np.arange(150_000, dtype=np.float64)

    @ray_tpu.remote
    def consume(x):
        return float(x[-1])

    assert ray_tpu.get(consume.remote(produce.remote()),
                       timeout=60) == 149999.0


def test_actors_across_processes(tcp_cluster):
    tcp_cluster.add_node(num_cpus=2, resources={"side": 2.0})
    _wait_for_nodes(2)

    @ray_tpu.remote(resources={"side": 1.0})
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self, k=1):
            self.n += k
            return self.n

    c = Counter.options(name="net_counter").remote()
    assert ray_tpu.get(c.incr.remote(), timeout=60) == 1
    assert ray_tpu.get(c.incr.remote(5), timeout=60) == 6
    again = ray_tpu.get_actor("net_counter")
    assert ray_tpu.get(again.incr.remote(), timeout=60) == 7


def test_node_kill_chaos_retriable_tasks(tcp_cluster):
    """SIGKILL a node mid-flight: heartbeat/connection failure detection
    must mark it dead and retriable tasks must finish elsewhere."""
    victim = tcp_cluster.add_node(num_cpus=2, resources={"side": 2.0})
    _wait_for_nodes(2)

    @ray_tpu.remote(max_retries=3)
    def slow(i):
        time.sleep(1.0)
        return i

    # bias toward the victim via its custom resource for half the work
    @ray_tpu.remote(max_retries=3, resources={"side": 0.5})
    def slow_side(i):
        time.sleep(1.0)
        return i

    refs = [slow.remote(i) for i in range(4)]
    refs += [slow_side.remote(i) for i in range(4, 8)]
    time.sleep(0.5)
    tcp_cluster.remove_node(victim)          # hard SIGKILL

    # side-resource tasks can never rerun (resource gone) — only wait on
    # the portable half; they must all complete despite the kill
    out = ray_tpu.get(refs[:4], timeout=90)
    assert out == [0, 1, 2, 3]
    alive = [x for x in ray_tpu.nodes() if x["alive"]]
    assert len(alive) == 1


def test_named_actor_on_dead_node_reports_dead(tcp_cluster):
    victim = tcp_cluster.add_node(num_cpus=2, resources={"side": 2.0})
    _wait_for_nodes(2)

    @ray_tpu.remote(resources={"side": 1.0})
    class A:
        def ping(self):
            return "pong"

    a = A.options(name="doomed").remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
    tcp_cluster.remove_node(victim)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            ray_tpu.get(a.ping.remote(), timeout=10)
        except Exception:
            break
        time.sleep(0.5)
    else:
        pytest.fail("calls to an actor on a SIGKILLed node never failed")


def test_cross_host_object_pull(tcp_cluster):
    """A node claiming a different OS host can't attach the owner's shm;
    objects must be pulled as payload bytes and adopted locally
    (reference: ``object_manager.h:117`` chunked Push/Pull)."""
    tcp_cluster.add_node(num_cpus=2, resources={"far": 2.0},
                         env={"RTPU_NODE_HOST": "simulated-other-host"})
    _wait_for_nodes(2)

    # produce on the "remote host" node, consume on the head's workers —
    # the dependency must cross via OBJ_PULL, not shm
    @ray_tpu.remote(resources={"far": 1.0})
    def produce():
        return np.arange(150_000, dtype=np.float64)

    @ray_tpu.remote
    def consume(x):
        return float(x.sum())

    expect = float(np.arange(150_000, dtype=np.float64).sum())
    assert abs(ray_tpu.get(consume.remote(produce.remote()), timeout=60)
               - expect) < 1e-6

    # and the reverse direction: head-owned arg into a far-host task
    big = np.random.rand(120_000)
    ref = ray_tpu.put(big)

    @ray_tpu.remote(resources={"far": 1.0})
    def consume_far(x):
        return float(x[0])

    assert ray_tpu.get(consume_far.remote(ref),
                       timeout=60) == pytest.approx(float(big[0]))


def test_chaos_under_load_actors_and_objects(tcp_cluster):
    """Sustained load across 3 nodes while one is SIGKILLed: retriable
    tasks finish elsewhere, a restartable actor comes back, and a lost
    object is rebuilt from lineage (reference: chaos node-killer,
    ``_private/test_utils.py:1391``, under real load)."""
    n1 = tcp_cluster.add_node(num_cpus=2, resources={"churn": 4.0})
    tcp_cluster.add_node(num_cpus=2)
    _wait_for_nodes(3)

    @ray_tpu.remote(max_retries=5)
    def work(i):
        time.sleep(0.3)
        return i * i

    @ray_tpu.remote(max_retries=5, resources={"churn": 1.0})
    def churn_work(i):
        time.sleep(0.3)
        return i

    @ray_tpu.remote(max_restarts=3, num_cpus=0)
    class Survivor:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

    # lineage-tracked object created ON the victim node
    seed = churn_work.remote(123)
    assert ray_tpu.get(seed, timeout=60) == 123

    survivor = Survivor.remote()
    assert ray_tpu.get(survivor.bump.remote(), timeout=60) == 1

    # continuous load, half biased onto the victim via its resource
    refs = [work.remote(i) for i in range(12)]
    refs += [churn_work.remote(i) for i in range(4)]
    time.sleep(0.6)
    tcp_cluster.remove_node(n1)              # hard SIGKILL mid-flight

    # portable tasks all complete despite the kill
    assert ray_tpu.get(refs[:12], timeout=120) == [i * i for i in range(12)]

    # the actor keeps serving (restarted if it lived on the victim)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        try:
            out = ray_tpu.get(survivor.bump.remote(), timeout=10)
            break
        except Exception:
            time.sleep(0.5)
    else:
        pytest.fail("actor never came back after node kill")
    assert out >= 1

    # the seed object is servable: either its copy survived or lineage
    # reconstruction reruns churn_work — but its resource died with the
    # node, so accept reconstruction failure, not a hang
    try:
        val = ray_tpu.get(seed, timeout=30)
    except Exception:
        pass        # reconstruction may fail (resource died) — just no hang
    else:
        assert val == 123
    alive = [x for x in ray_tpu.nodes() if x["alive"]]
    assert len(alive) == 2


def test_cross_host_chunked_pull_large_object():
    """A pull larger than the transfer chunk streams in bounded frames
    (reference: chunked Push/Pull, ``object_manager.h:117``). Chunk size
    is shrunk to 256KB so a ~4MB array crosses in ~16 chunks."""
    chunk_env = {"RTPU_OBJECT_TRANSFER_CHUNK_BYTES": str(256 * 1024)}
    cluster = Cluster(initialize_head=True, process_isolated=True,
                      head_node_args={"num_cpus": 2, "env": chunk_env})
    try:
        ray_tpu.init(address=cluster)
        cluster.add_node(num_cpus=2, resources={"far": 2.0},
                         env={**chunk_env,
                              "RTPU_NODE_HOST": "simulated-other-host"})
        _wait_for_nodes(2)

        @ray_tpu.remote(resources={"far": 1.0})
        def produce():
            return np.arange(500_000, dtype=np.float64)   # ~4MB

        @ray_tpu.remote
        def consume(x):
            return float(x.sum()), x.shape[0]

        total, n = ray_tpu.get(consume.remote(produce.remote()),
                               timeout=120)
        assert n == 500_000
        assert total == pytest.approx(
            float(np.arange(500_000, dtype=np.float64).sum()))

        # reverse direction too: head-owned 4MB arg into a far task
        big = np.random.rand(500_000)
        ref = ray_tpu.put(big)

        @ray_tpu.remote(resources={"far": 1.0})
        def consume_far(x):
            return float(x.sum())

        assert ray_tpu.get(consume_far.remote(ref), timeout=120) == \
            pytest.approx(float(big.sum()))
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_actor_restarts_on_surviving_node_after_node_death(tcp_cluster):
    """A restartable actor whose NODE is SIGKILLed is re-created on a
    surviving node (reference: GcsActorManager::OnNodeDead actor
    rescheduling) — deterministic placement via soft node affinity."""
    from ray_tpu._private.ids import NodeID
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    victim = tcp_cluster.add_node(num_cpus=2)
    _wait_for_nodes(2)
    victim_id = NodeID.from_hex(victim.node_id_hex)

    @ray_tpu.remote(max_restarts=2, num_cpus=1)
    class Phoenix:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

        def where(self):
            import ray_tpu as rt
            return rt.get_runtime_context().node_id.hex()

    p = Phoenix.options(scheduling_strategy=NodeAffinitySchedulingStrategy(
        node_id=victim_id, soft=True)).remote()
    assert ray_tpu.get(p.bump.remote(), timeout=60) == 1
    assert ray_tpu.get(p.where.remote(), timeout=60) == victim.node_id_hex

    tcp_cluster.remove_node(victim)          # SIGKILL the actor's node

    deadline = time.monotonic() + 90
    while time.monotonic() < deadline:
        try:
            out = ray_tpu.get(p.bump.remote(), timeout=10)
            break
        except Exception:
            time.sleep(0.5)
    else:
        pytest.fail("actor never restarted after its node was killed")
    assert out >= 1                          # fresh state, restarted
    new_home = ray_tpu.get(p.where.remote(), timeout=30)
    assert new_home != victim.node_id_hex


def test_spillback_rescues_starved_task():
    """A task queued behind a long occupant must re-route once capacity
    opens on another node (reference: lease spillback,
    ``cluster_task_manager.cc``) instead of starving while the rest of
    the cluster idles."""
    cluster = Cluster(initialize_head=True, process_isolated=True,
                      head_node_args={"num_cpus": 1})
    cluster.add_node(num_cpus=1)
    ray_tpu.init(address=cluster)
    try:
        @ray_tpu.remote
        def busy(t):
            time.sleep(t)
            return time.time()

        t0 = time.time()
        busy.remote(12.0)                 # fills one node for a long time
        short = busy.remote(2.0)          # fills the other briefly
        time.sleep(0.5)                   # both running: cluster is full
        third = busy.remote(0.0)          # queued behind one of them
        done = ray_tpu.get(third, timeout=30) - t0
        # without spillback there is a ~50% chance third waits 12s on the
        # long node; with it, it must run soon after the short task frees
        assert done < 7.0, f"queued task starved {done:.1f}s"
        ray_tpu.get(short)
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_burst_does_not_pile_on_one_node():
    """Route-time debits: a burst routed within one heartbeat must fan
    out across nodes instead of herding onto the node the stale view
    says is free (RaySyncer-staleness bridge).

    De-flaked (flaky at the PR-14 seed): the old assertion bounded the
    burst's wall clock at 5s measured from SUBMIT, but that window had
    to absorb 6 COLD worker spawns across 3 process-isolated nodes on
    this 2-core box — routinely > the 3s of headroom the 2s spin left,
    so the bound tripped even when routing behaved. Root cause: the
    timing assumption conflated worker-spawn cost (and mid-wave-stale
    availability gossip) with routing quality. Now (poll-then-assert,
    like the PR-8 autoscaler de-flakes): poll a warm-up burst until
    every CPU slot holds a warm worker, poll the gossiped availability
    back to full (a heartbeat snapshotted mid-warm-wave makes peers
    look busy for up to a beat), THEN submit the measured burst and
    assert the routing property directly: every task STARTS within 2s
    of submit — balanced routing (or a promptly-spilled straggler)
    starts in well under a wave, while herding's serialized waves put
    the last start at 4s+. The routing half was also fixed this PR:
    the router now counts queued-but-undispatched demand against a
    node's availability, so a deferred-dispatch SUBMIT_BATCH no longer
    reads its own node as free 6 times in a row."""
    cluster = Cluster(initialize_head=True, process_isolated=True,
                      head_node_args={"num_cpus": 2})
    try:
        cluster.add_node(num_cpus=2)
        cluster.add_node(num_cpus=2)
        ray_tpu.init(address=cluster)

        @ray_tpu.remote
        def spin(t):
            start = time.time()
            time.sleep(t)
            return start

        _wait_for_nodes(3)
        # poll-then-assert: warm ALL 6 CPU slots' workers first, so the
        # measured burst pays routing + dispatch only, never cold spawn
        deadline = time.monotonic() + 90
        while True:
            t0 = time.time()
            ray_tpu.get([spin.remote(0.5) for _ in range(6)],
                        timeout=60)
            if time.time() - t0 < 2.0:  # one concurrent 0.5s wave: warm
                break
            if time.monotonic() > deadline:
                raise TimeoutError("worker pool never warmed up")
        # ...and poll until every node's GOSSIPED view — the exact view
        # the router consumes — has settled back to idle: full
        # availability AND no queued shapes. A heartbeat snapshotted
        # mid-warm-wave (queued-but-undispatched tasks, busy workers)
        # makes a peer look full for up to a beat and would re-herd
        # the measured burst through no fault of the router.
        while True:
            rows = [n for n in ray_tpu.nodes() if n["alive"]]
            settled = all(
                n["resources_available"].get("CPU", 0.0) >= 2.0
                and not n["pending_shapes"] for n in rows)
            if len(rows) == 3 and settled:
                break
            if time.monotonic() > deadline:
                raise TimeoutError("gossiped availability never settled")
            time.sleep(0.1)
        # 6 tasks == exactly the cluster's CPU capacity, submitted as
        # one burst: with warm workers every task must START promptly —
        # directly routed (one per CPU slot) or spilled within
        # scheduler_spillback_delay_s. Serialized waves (herding without
        # rescue) put the last start at 4s+.
        t_submit = time.time()
        refs = [spin.remote(2.0) for _ in range(6)]
        starts = ray_tpu.get(refs, timeout=60)
        latest = max(starts) - t_submit
        assert latest < 2.0, f"burst serialized: last start {latest:.1f}s"
    finally:
        ray_tpu.shutdown()
        cluster.shutdown()


def test_versioned_heartbeat_drops_stale():
    """RaySyncer-equivalent property: a delayed heartbeat frame with an
    older version refreshes liveness but cannot roll the availability
    view back (reference: ray_syncer.h:86)."""
    from ray_tpu._private.gcs import GlobalControlPlane, NodeInfo
    from ray_tpu._private.ids import NodeID

    gcs = GlobalControlPlane()
    nid = NodeID.from_random()
    gcs.register_node(NodeInfo(node_id=nid, address="sock",
                               resources_total={"CPU": 4.0}))
    gcs.heartbeat(nid, {"CPU": 4.0}, version=10)
    gcs.heartbeat(nid, {"CPU": 1.0}, version=12)
    # delayed duplicate from the past: must not overwrite
    gcs.heartbeat(nid, {"CPU": 4.0}, version=11)
    info = gcs.get_node(nid)
    assert info.resources_available == {"CPU": 1.0}
    assert info.resource_version == 12
    # delta ping (no payload) advances the version, keeps the view
    gcs.heartbeat(nid, None, version=13)
    info = gcs.get_node(nid)
    assert info.resources_available == {"CPU": 1.0}
    assert info.resource_version == 13
    # newer payload applies
    gcs.heartbeat(nid, {"CPU": 3.0}, version=14)
    assert gcs.get_node(nid).resources_available == {"CPU": 3.0}


def test_scheduling_with_delayed_heartbeats(tcp_cluster):
    """Chaos: one node syncs its resource view 5x slower than the
    default; a burst needing both nodes still completes, and the slow
    node is never declared dead."""
    tcp_cluster.add_node(num_cpus=2,
                         env={"RTPU_HEARTBEAT_PERIOD_MS": "5000"})
    _wait_for_nodes(2)

    @ray_tpu.remote
    def work(i):
        time.sleep(0.05)
        return i

    # 3 waves: routing decisions against the stale view must not wedge
    for wave in range(3):
        got = ray_tpu.get([work.remote(i) for i in range(12)],
                          timeout=90)
        assert sorted(got) == list(range(12))
    alive = [x for x in ray_tpu.nodes() if x["alive"]]
    assert len(alive) == 2          # slow heartbeats != dead


def test_cross_node_hierarchical_collective(tcp_cluster):
    """Hierarchical two-level allreduce across OS-isolated nodes: two
    co-located ranks per node, so auto-selection picks the hierarchical
    schedule, only the leaders' ring crosses the TCP wire, and the
    measured inter-node bytes are LOWER than the flat ring's on the
    same group; int8-blockscale then halves them again (>= 2x) at a
    bounded max-abs error."""
    from ray_tpu._private import coll_transport
    from ray_tpu.comm import collective as col

    tcp_cluster.add_node(num_cpus=2, resources={"side": 2.0})
    _wait_for_nodes(2)

    @ray_tpu.remote(num_cpus=0)
    class Rank(col.CollectiveActorMixin):
        def configure(self, algo="auto", wire="exact"):
            from ray_tpu._private.config import CONFIG
            CONFIG._values["collective_algo"] = algo
            CONFIG._values["collective_wire_dtype"] = wire
            return True

        def n_nodes(self):
            return col._groups()["default"].n_nodes

        def big_allreduce(self, n):
            rank = col.get_rank()
            x = ((np.arange(n) % 13) + 1 + rank).astype(np.float32)
            before = coll_transport.stats()["sent_remote_bytes"]
            out = col.allreduce(x)
            remote = (coll_transport.stats()["sent_remote_bytes"]
                      - before)
            return out[:8], float(np.abs(out).max()), remote

    n = 1_048_576                       # 4 MB of float32
    members = ([Rank.remote() for _ in range(2)]
               + [Rank.options(resources={"side": 1.0}).remote()
                  for _ in range(2)])
    col.create_collective_group(members, 4, [0, 1, 2, 3])
    assert ray_tpu.get(members[0].n_nodes.remote()) == 2

    want = sum(((np.arange(n) % 13) + 1 + r).astype(np.float32)
               for r in range(4))
    remotes = {}
    for algo, wire in (("auto", "exact"), ("ring", "exact"),
                       ("auto", "int8-blockscale")):
        ray_tpu.get([m.configure.remote(algo, wire) for m in members])
        outs = ray_tpu.get([m.big_allreduce.remote(n) for m in members],
                           timeout=120)
        for head, peak, _r in outs:
            if wire == "exact":
                np.testing.assert_array_equal(head, want[:8])
                assert peak == float(np.abs(want).max())
            else:
                # int8-blockscale: bounded error, not bit equality
                assert np.abs(head - want[:8]).max() <= \
                    float(np.abs(want).max()) / 254 * 4
        remotes[(algo, wire)] = sum(r for _, _, r in outs)
    hier, ring = remotes[("auto", "exact")], remotes[("ring", "exact")]
    q8 = remotes[("auto", "int8-blockscale")]
    assert 0 < hier < ring, (hier, ring)
    assert q8 * 2 <= hier, (q8, hier)


def test_cross_node_hang_diagnosis_names_dead_rank(tcp_cluster):
    """ISSUE 10 acceptance across OS-isolated nodes: SIGKILL one rank
    mid-allreduce and, within the collective timeout,
    ``state.collective_health()`` (the ``rtpu doctor``/``coll-debug``
    backend) must name the dead rank and the op — and the TimeoutError
    on every survivor must carry the verdict."""
    from ray_tpu import state as rstate
    from ray_tpu.comm import collective as col

    tcp_cluster.add_node(num_cpus=2, resources={"side": 2.0})
    _wait_for_nodes(2)

    @ray_tpu.remote(num_cpus=0)
    class Rank(col.CollectiveActorMixin):
        def guarded_allreduce(self, n, timeout):
            x = np.ones(n, np.float32)
            try:
                col.allreduce(x, timeout=timeout)
                return ("ok", "")
            except Exception as exc:       # noqa: BLE001
                return ("err", str(exc))

    members = ([Rank.remote() for _ in range(2)]
               + [Rank.options(resources={"side": 1.0}).remote()
                  for _ in range(2)])
    col.create_collective_group(members, 4, [0, 1, 2, 3])
    # ranks 0-2 enter a 4 MB allreduce; rank 3 (on the second OS node)
    # never joins it and is SIGKILLed while the others are mid-op
    refs = [m.guarded_allreduce.remote(1_048_576, 12.0)
            for m in members[:3]]
    time.sleep(0.5)
    ray_tpu.kill(members[3])
    verdict = None
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        rep = rstate.collective_health(2.0)
        dead = [v for v in rep.get("verdicts", ())
                if v.get("verdict") == "dead_rank"]
        if dead:
            verdict = dead[0]
            break
        time.sleep(0.3)
    assert verdict is not None, "diagnosis never named the dead rank"
    assert verdict["rank"] == 3
    assert verdict["op"] == "allreduce"
    for status, msg in ray_tpu.get(refs, timeout=90):
        assert status == "err"
        assert "dead rank 3" in msg and "allreduce" in msg, msg


def test_recursive_lineage_reconstruction_chain(tcp_cluster):
    """A depth-2 produce -> transform -> consume chain whose
    intermediate AND leaf objects die with their node is rebuilt by
    ``_maybe_reconstruct`` recursing through the lost creating-task
    args — and the claim gate admits exactly ONE reconstruction per
    object (counter-audited) despite multiple observers of the loss."""
    from ray_tpu._private.ids import NodeID
    from ray_tpu.util.scheduling_strategies import (
        NodeAffinitySchedulingStrategy)

    victim = tcp_cluster.add_node(num_cpus=2)
    _wait_for_nodes(2)
    affinity = NodeAffinitySchedulingStrategy(
        node_id=NodeID.from_hex(victim.node_id_hex), soft=True)

    @ray_tpu.remote(max_retries=3, scheduling_strategy=affinity)
    def produce():
        return np.arange(60_000, dtype=np.float64)        # ~480 KB

    @ray_tpu.remote(max_retries=3, scheduling_strategy=affinity)
    def transform(x):
        return x * 2.0

    a = produce.remote()
    b = transform.remote(a)
    # materialize BOTH links on the victim (sealed -> reconstructable)
    out = ray_tpu.get(b, timeout=60)
    assert float(out[-1]) == (60_000 - 1) * 2.0

    tcp_cluster.remove_node(victim)          # hard SIGKILL: a AND b lost

    @ray_tpu.remote
    def consume(x):
        return float(x.sum())

    want = float((np.arange(60_000, dtype=np.float64) * 2.0).sum())
    got = ray_tpu.get(consume.remote(b), timeout=120)
    assert got == pytest.approx(want)
    # the whole chain was rebuilt exactly once per lost object: the
    # claim gate admitted one reconstruction of b AND one of a (the
    # recursion through the transform spec's lost arg)
    client = ray_tpu._ctx.require_client()
    stats = client.state_query("reconstruct_stats") or {}
    a_hex = a.id.hex()
    b_hex = b.id.hex()
    assert stats.get(b_hex) == 1, stats
    assert stats.get(a_hex) == 1, stats


def test_chaos_training_loop_survives_rank_kill_mid_allreduce(tcp_cluster):
    """ISSUE-12 acceptance, 2 OS-isolated nodes: a training-style loop
    (checkpointable actor ranks, allreduce per step) survives a SIGKILL
    of one rank mid-allreduce — the group reforms under a fresh epoch
    (metric + COLLECTIVE_REFORM event observed), the restarted rank
    resumes from its last checkpoint, the loop reaches step N with
    bit-correct results, and no stale-epoch chunk survives into the new
    epoch (fence assertion on every rank)."""
    from ray_tpu import state as rstate
    from ray_tpu.comm import collective as col

    tcp_cluster.add_node(num_cpus=2, resources={"side": 2.0})
    _wait_for_nodes(2)

    @ray_tpu.remote(num_cpus=0, max_restarts=2)
    class TrainRank(col.CollectiveActorMixin):
        def __init__(self, world, rank):
            from ray_tpu._private.config import CONFIG
            CONFIG._values["actor_checkpoint_interval_calls"] = 1
            CONFIG._values["collective_reform_timeout_s"] = 45.0
            self.world, self.rank = world, rank
            self.step = 0
            self.acc = None
            self.restored_at = None
            self.epochs = []

        def save_checkpoint(self):
            return {"step": self.step, "acc": self.acc}

        def restore_checkpoint(self, state):
            self.step = state["step"]
            self.acc = state["acc"]
            self.restored_at = state["step"]

        def arm(self, spec):
            from ray_tpu._private import failpoints
            failpoints.activate(spec)
            return True

        def train_step(self, i):
            col.ensure_collective_group(self.world, self.rank, "chaos")
            if self.step > i:
                return self.step
            ep = col._groups()["chaos"].epoch
            if ep not in self.epochs:
                self.epochs.append(ep)
            # 1.5 MB float32: >= the hierarchical threshold on the
            # 2-node x 2-rank topology AND two pipeline chunks, so the
            # armed chunk=1 failpoint fires with chunk 0 already in
            # flight — a genuine mid-op death
            grad = np.full(393_216, float((i + 1) * (self.rank + 1)),
                           np.float32)
            out = col.ft_allreduce(grad, group_name="chaos", timeout=6.0)
            self.acc = out if self.acc is None else self.acc + out
            self.step = i + 1
            return self.step

        def report(self):
            import hashlib
            from ray_tpu._private import coll_transport
            stale = [k for k in coll_transport.pending_keys()
                     if len(k) >= 2 and k[0] == "chaos"
                     and k[1] in self.epochs[:-1]]
            digest = (hashlib.sha256(self.acc.tobytes()).hexdigest()
                      if self.acc is not None else None)
            return {"step": self.step, "digest": digest,
                    "restored_at": self.restored_at,
                    "epochs": list(self.epochs), "stale": stale,
                    "fenced": [e for e in self.epochs[:-1]
                               if e in coll_transport.fenced_epochs(
                                   "chaos")]}

    members = ([TrainRank.remote(4, r) for r in range(2)]
               + [TrainRank.options(resources={"side": 1.0}).remote(4, r)
                  for r in (2, 3)])
    # rank 3 (second OS node, a non-leader) dies MID-allreduce of step
    # 2 (seq=2): chunk 0 of its phase-1 contribution is already in
    # flight up the local tree, chunk 1 never leaves — survivors wedge
    # inside the same op with rank 3's partial traffic in the air (the
    # fence's job), and the whole step retries aligned after the reform
    ray_tpu.get(members[3].arm.remote(
        "coll.hier.phase=kill@phase=up&chunk=1&seq=2"), timeout=60)

    def drive(i):
        pending = {idx: m.train_step.remote(i)
                   for idx, m in enumerate(members)}
        results = {}
        deadline = time.monotonic() + 150
        while pending:
            assert time.monotonic() < deadline, (
                f"step {i} wedged; pending {sorted(pending)}")
            for idx, ref in list(pending.items()):
                ready, _ = ray_tpu.wait([ref], timeout=0.5)
                if not ready:
                    continue
                try:
                    results[idx] = ray_tpu.get(ready[0])
                    del pending[idx]
                except Exception:        # killed rank: re-issue, the
                    pending[idx] = (     # restarted actor resumes
                        members[idx].train_step.remote(i))
        return results

    N = 4
    for i in range(N):
        assert set(drive(i).values()) == {i + 1}

    reports = ray_tpu.get([m.report.remote() for m in members],
                          timeout=60)
    # bit-correct on every rank: one shared digest, steps complete
    digests = {r["digest"] for r in reports}
    assert len(digests) == 1 and None not in digests
    acc = None
    for i in range(N):
        out = np.full(393_216, 0.0, np.float32)
        for rank in range(4):
            out = out + np.full(393_216, float((i + 1) * (rank + 1)),
                                np.float32)
        acc = out if acc is None else acc + out
    import hashlib
    assert digests == {hashlib.sha256(acc.tobytes()).hexdigest()}
    for r in reports:
        assert r["step"] == N
    # the killed rank resumed FROM ITS CHECKPOINT at step 2
    assert reports[3]["restored_at"] == 2
    assert all(r["restored_at"] is None for r in reports[:3])
    # the group reformed under ONE fresh epoch: survivors saw exactly
    # [old, new] (old fenced), the restarted rank only ever saw the new
    # one, and NO stale-epoch chunk survives in anyone's mailbox
    new_epochs = {r["epochs"][-1] for r in reports}
    assert len(new_epochs) == 1
    for r in reports:
        assert r["stale"] == []
    for r in reports[:3]:                # survivors fenced the old epoch
        assert len(r["epochs"]) == 2, r["epochs"]
        assert r["fenced"] == [r["epochs"][0]]
    assert reports[3]["epochs"] == [reports[0]["epochs"][1]]

    # observability: reform metric + COLLECTIVE_REFORM event crossed
    # the cluster into the merged table / event ring
    deadline = time.monotonic() + 20
    reforms = 0
    while time.monotonic() < deadline:
        s = rstate.summarize_metrics()
        reforms = (s.get("rtpu_collective_reforms_total") or {}).get(
            "total", 0)
        restores = (s.get("rtpu_actor_restores_total") or {}).get(
            "total", 0)
        if reforms >= 3 and restores >= 1:
            break
        time.sleep(0.3)
    assert reforms >= 3 and restores >= 1
    evs = [e for e in rstate.list_cluster_events()
           if e.get("label") == "COLLECTIVE_REFORM"]
    assert evs and evs[-1].get("group") == "chaos"
    assert evs[-1].get("mode") == "replace"


def test_cross_node_ring_collective(tcp_cluster):
    """Ring collective whose chunks actually cross the wire: one rank
    per OS-isolated node, payload above the tree threshold, so every
    ring step routes COLL_FWD frames across the node plane (out-of-band
    iovecs end to end)."""
    import hashlib

    from ray_tpu._private import coll_transport
    from ray_tpu.comm import collective as col

    tcp_cluster.add_node(num_cpus=2, resources={"side": 2.0})
    _wait_for_nodes(2)

    @ray_tpu.remote(num_cpus=0)
    class Rank(col.CollectiveActorMixin):
        def big_allreduce(self, n):
            rank = col.get_rank()
            x = ((np.arange(n) % 13) + 1 + rank).astype(np.float32)
            before = coll_transport.stats()["sent_bytes"]
            out = col.allreduce(x)
            sent = coll_transport.stats()["sent_bytes"] - before
            return (hashlib.sha256(out.tobytes()).hexdigest(), sent)

    n = 1_048_576                       # 4 MB of float32 -> ring at w=2
    members = [Rank.remote(),
               Rank.options(resources={"side": 1.0}).remote()]
    col.create_collective_group(members, 2, [0, 1])
    outs = ray_tpu.get([m.big_allreduce.remote(n) for m in members],
                       timeout=120)
    parts = [((np.arange(n) % 13) + 1 + r).astype(np.float32)
             for r in range(2)]
    want = hashlib.sha256((parts[0] + parts[1]).tobytes()).hexdigest()
    size = n * 4
    for digest, sent in outs:
        assert digest == want
        # w=2 ring: each rank ships ~half the tensor twice (rs + ag)
        assert size * 0.9 <= sent <= size * 1.3


def test_cross_node_request_trace_stitches(tcp_cluster):
    """ISSUE 13 satellite: one HTTP request whose ingress runs in the
    driver (attached to node A) and whose replica is pinned to node B
    stitches into a single request trace — ingress, queue-wait and
    replica-execute spans share the request id and render as one
    ``cat: "request"`` lane in state.timeline(), with the replica-side
    spans coming from a different process than the ingress."""
    import json as _json
    import urllib.request

    from ray_tpu import serve
    from ray_tpu import state as rstate

    tcp_cluster.add_node(num_cpus=2, resources={"srv": 2.0})
    _wait_for_nodes(2)

    @serve.deployment(ray_actor_options={"resources": {"srv": 1.0}})
    def far_echo(x):
        return {"ok": x}

    rid = "ba5eba1100000042"
    try:
        serve.run(far_echo.bind())
        url = serve.start_http(port=0)          # ingress: driver, node A
        req = urllib.request.Request(
            f"{url}/far_echo", data=_json.dumps({"v": 1}).encode(),
            headers={"Content-Type": "application/json",
                     "X-Request-ID": rid})
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert _json.loads(resp.read())["result"] == {"ok": {"v": 1}}
            assert resp.headers.get("X-RTPU-Request-ID") == rid

        # replica spans arrive over the TCP plane after the call's task
        # boundary — poll the lane together
        want = {"request::ingress", "request::queue_wait",
                "request::replica_execute"}
        events = []
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            events = [e for e in rstate.timeline()
                      if e.get("cat") == "request"
                      and e["pid"] == f"request:{rid}"]
            if want <= {e["name"] for e in events}:
                break
            time.sleep(0.4)
        names = {e["name"] for e in events}
        assert want <= names, f"lane never stitched: {names}"
        # single trace id across the whole lane
        assert len({e["args"]["trace_id"] for e in events}) == 1
        # the ingress span ran in THIS driver process; the replica
        # spans ran in a different one (the node-B worker — the srv
        # resource exists only there)
        import os as _os
        ingress = next(e for e in events
                       if e["name"] == "request::ingress")
        execute = next(e for e in events
                       if e["name"] == "request::replica_execute")
        assert ingress["tid"] == f"pid:{_os.getpid()}"
        assert execute["tid"] != ingress["tid"]
        # and the access-log row (fetched from the node-B replica)
        # carries the same request id
        rows = rstate.serve_requests()
        assert any(r["request_id"] == rid for r in rows), rows
    finally:
        serve.shutdown()


def test_bundle_autopsy_after_node_death_chaos(tcp_cluster, tmp_path):
    """ISSUE 14 acceptance: 2 OS-isolated nodes under queue-building
    load; node B (hosting collective rank 1) is SIGKILLed; the driver's
    ft_allreduce exhausts its reform budget (retries=0) on the
    dead-rank verdict and AUTO-CAPTURES a black-box bundle. `rtpu
    autopsy` — run offline against the tar, no session flag — then
    reproduces the dead-node + dead-rank verdict AND the rising
    queue-depth trend with no live cluster."""
    import subprocess
    import sys as _sys

    from ray_tpu._private import debug_bundle
    from ray_tpu._private.config import CONFIG
    from ray_tpu.comm import collective as col

    CONFIG._values["debug_bundle_dir"] = str(tmp_path)
    CONFIG._values["collective_timeout_s"] = 6.0
    debug_bundle._auto_captured.discard("collective_reform_exhausted")
    victim = tcp_cluster.add_node(num_cpus=2, resources={"b": 2.0})
    _wait_for_nodes(2)

    @ray_tpu.remote(num_cpus=0, resources={"b": 1.0})
    class Rank(col.CollectiveActorMixin):
        def step(self, group):
            col.allreduce(np.ones(4096, np.float32), group_name=group)
            return True

    m = Rank.remote()
    join = m._rtpu_init_collective.remote(2, 1, "chaos14")
    col.init_collective_group(2, 0, group_name="chaos14")
    ray_tpu.get(join, timeout=60)

    @ray_tpu.remote
    def hog(i):
        time.sleep(90)
        return i

    # queue-building load while a healthy collective loop runs: submit
    # long tasks in waves so rtpu_scheduler_pending_tasks RISES across
    # the retained window (the trend the autopsy must find offline)
    hogs = [hog.remote(i) for i in range(4)]       # fill 4 CPUs
    for wave in range(8):
        hogs.extend(hog.remote(100 + wave * 10 + j) for j in range(4))
        step_ref = m.step.remote("chaos14")
        col.allreduce(np.ones(4096, np.float32), group_name="chaos14")
        ray_tpu.get(step_ref, timeout=30)
        time.sleep(1.0)

    # SIGKILL node B: rank 1 dies with its whole node
    tcp_cluster.remove_node(victim)
    with pytest.raises(TimeoutError):
        col.ft_allreduce(np.ones(4096, np.float32),
                         group_name="chaos14", timeout=6.0, retries=0)

    bundles = [f for f in os.listdir(tmp_path)
               if f.startswith("rtpu_bundle_collective_reform_exhausted")]
    assert bundles, ("reform-budget exhaustion did not auto-capture "
                     f"a bundle in {tmp_path}")
    bundle_path = os.path.join(tmp_path, bundles[0])

    # OFFLINE autopsy: a fresh process, no --session, only the tar
    out = subprocess.run(
        [_sys.executable, "-m", "ray_tpu.scripts.cli", "autopsy",
         bundle_path, "--format", "json"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    rep = json.loads(out.stdout)
    # the dead node is named
    assert rep["doctor"]["nodes"]["dead"] >= 1
    dead_line = next(p for p in rep["doctor"]["problems"]
                     if "node(s) dead" in p)
    dead_rows = [n for n in ray_tpu.nodes() if not n["alive"]]
    assert dead_rows
    dead_hex = (dead_rows[0]["node_id"].hex()
                if hasattr(dead_rows[0]["node_id"], "hex")
                else str(dead_rows[0]["node_id"]))
    assert dead_hex[:12] in dead_line
    # the dead-rank verdict the survivors saw rides the capture trigger
    assert rep["trigger"]["reason"] == "collective_reform_exhausted"
    assert "dead rank 1" in rep["trigger"]["verdict"]
    # the queue-depth trend is reproduced offline: pending tasks rose
    # across the retained window
    trend = [t for t in rep["doctor"]["trends"]
             if t["metric"] == "rtpu_scheduler_pending_tasks"]
    assert trend, rep["doctor"]["trends"]
    assert trend[0]["tail"] > trend[0]["head"]
    # and the raw history series is in the bundle for ad-hoc queries
    hist_series = {s["name"] for s in rep["history"]["series"]}
    assert "rtpu_scheduler_pending_tasks" in hist_series
