"""Follower-served reads (ISSUE 11): raft read-index/lease protocol,
linearizable store views, watch resume tokens across members, dispatcher
follower mode, agent session failover, and the three new sim invariants
(each proven LIVE by a checker-sensitivity test)."""

import logging

import pytest

from swarmkit_tpu.models import (
    Annotations, Node, NodeDescription, NodeSpec, NodeState, NodeStatus,
    Resources, Service, ServiceSpec, Task, TaskSpec, TaskState,
    TaskStatus, Version,
)
from swarmkit_tpu.state.raft.core import LEADER, Message, RaftCore
from swarmkit_tpu.state.raft.node import ReadUnavailable
from swarmkit_tpu.state.store import MemoryStore

logging.disable(logging.CRITICAL)


# --------------------------------------------------------------- helpers

def mk_cluster(n=3):
    """N connected RaftCores with a synchronous message pump."""
    ids = [f"n{i}" for i in range(n)]
    import random
    cores = {i: RaftCore(i, ids, rng=random.Random(hash(i) & 0xFFFF))
             for i in ids}

    def pump():
        for _ in range(200):
            moved = False
            for c in cores.values():
                msgs, c._msgs = c._msgs, []
                for m in msgs:
                    moved = True
                    if m.dst in cores:
                        cores[m.dst].step(m)
            if not moved:
                return

    def elect(i):
        c = cores[i]
        while c.role != LEADER:
            c.tick()
            pump()
        # drain ready so the no-op applies (leader_ready)
        rd = c.ready()
        c.advance(rd)
        c.applied_index = c.commit_index
        return c

    return cores, pump, elect


def mk_task(i, sid="svc"):
    return Task(id=f"t{i:03d}", service_id=sid, slot=i,
                desired_state=TaskState.RUNNING, spec=TaskSpec(),
                spec_version=Version(index=1),
                status=TaskStatus(state=TaskState.PENDING, timestamp=1.0))


# ------------------------------------------------------- core read-index

def test_read_index_quorum_round_on_leader():
    cores, pump, elect = mk_cluster()
    leader = elect("n0")
    leader.lease_duration = None    # force the quorum round
    seq = leader.request_read()
    assert seq is not None and seq not in leader.read_results
    pump()   # heartbeat round + echoes
    index, ok, lease = leader.read_results.pop(seq)
    assert ok and not lease
    assert index == leader.commit_index


def test_follower_read_index_round_trip():
    cores, pump, elect = mk_cluster()
    leader = elect("n0")
    leader.lease_duration = None
    follower = cores["n1"]
    # the follower learns the leader via a heartbeat
    leader._broadcast_append(heartbeat=True)
    pump()
    assert follower.leader_id == "n0"
    seq = follower.request_read()
    assert seq is not None
    pump()
    index, ok, lease = follower.read_results.pop(seq)
    assert ok and not lease
    assert index == leader.commit_index


def test_lease_fast_path_and_expiry(monkeypatch):
    from swarmkit_tpu.models import types as mtypes
    t = [100.0]
    mtypes.set_time_source(lambda: t[0])
    try:
        cores, pump, elect = mk_cluster()
        leader = elect("n0")
        leader.lease_duration = 1.0
        # earn the lease: one quorum-acked heartbeat round
        leader._broadcast_append(heartbeat=True)
        pump()
        assert leader.lease_valid()
        seq = leader.request_read()
        index, ok, lease = leader.read_results.pop(seq)
        assert ok and lease and index == leader.commit_index
        assert leader.read_stats["lease_served"] == 1
        # past the (margin-shaved) expiry the lease must NOT serve
        t[0] += 1.0
        assert not leader.lease_valid()
        seq = leader.request_read()
        assert seq not in leader.read_results   # quorum round in flight
        pump()
        index, ok, lease = leader.read_results.pop(seq)
        assert ok and not lease
    finally:
        mtypes.set_time_source(None)


def test_lease_gate_vetoes_fast_path(monkeypatch):
    from swarmkit_tpu.models import types as mtypes
    t = [50.0]
    mtypes.set_time_source(lambda: t[0])
    try:
        cores, pump, elect = mk_cluster()
        leader = elect("n0")
        leader.lease_duration = 5.0
        leader._broadcast_append(heartbeat=True)
        pump()
        assert leader.lease_valid()
        leader.lease_gate = lambda: False   # clock-skew fault active
        seq = leader.request_read()
        assert seq not in leader.read_results   # forced quorum round
        pump()
        index, ok, lease = leader.read_results.pop(seq)
        assert ok and not lease
        assert leader.read_stats["lease_refused_gate"] == 1
    finally:
        mtypes.set_time_source(None)


def test_deposed_leader_fails_pending_reads():
    cores, pump, elect = mk_cluster()
    leader = elect("n0")
    leader.lease_duration = None
    seq = leader.request_read()
    assert seq not in leader.read_results
    # a higher-term message deposes the leader before the round confirms
    leader.step(Message(type="vote", term=leader.term + 5, src="n1",
                        dst="n0", last_log_index=99, last_log_term=99))
    index, ok, lease = leader.read_results.pop(seq)
    assert not ok


def test_single_member_reads_immediately():
    import random
    c = RaftCore("solo", ["solo"], rng=random.Random(1))
    while c.role != LEADER:
        c.tick()
    c.applied_index = c.commit_index
    seq = c.request_read()
    index, ok, lease = c.read_results.pop(seq)
    assert ok and index == c.commit_index


# --------------------------------------------------- store read_view seam

class _BarrierProposer:
    """Fake proposer exposing the read_barrier capability."""

    leadership_epoch = None

    def __init__(self):
        self.barriers = 0

    def propose(self, actions, commit_cb=None, epoch=None):
        commit_cb()

    def read_barrier(self, timeout=None):
        self.barriers += 1


def test_read_view_runs_barrier_only_when_linearizable():
    p = _BarrierProposer()
    store = MemoryStore(proposer=p)
    store.update(lambda tx: tx.create(mk_task(1)))
    n = store.read_view(lambda tx: len(tx.find(Task)))
    assert n == 1 and p.barriers == 0
    n = store.read_view(lambda tx: len(tx.find(Task)),
                        linearizable=True)
    assert n == 1 and p.barriers == 1
    # plain proposers without the capability serve directly
    store2 = MemoryStore()
    store2.update(lambda tx: tx.create(mk_task(2)))
    assert store2.read_view(lambda tx: len(tx.find(Task)),
                            linearizable=True) == 1


# ----------------------------------------------------- watch resume tokens

def test_watch_events_carry_resume_tokens_including_deletes():
    from swarmkit_tpu.manager.watchapi import WatchRequest, WatchServer
    store = MemoryStore()
    server = WatchServer(store)
    stream = server.watch(WatchRequest(kinds=[Task]))
    store.update(lambda tx: tx.create(mk_task(1)))
    store.update(lambda tx: tx.delete(Task, "t001"))
    ev1 = stream.get(timeout=1)
    ev2 = stream.get(timeout=1)
    assert ev1.action == "create" and ev1.version > 0
    assert ev2.action == "delete" and ev2.version == ev1.version + 1
    assert stream.poll() is None
    stream.close()


def test_resume_token_continues_without_gap_or_dup():
    from swarmkit_tpu.manager.watchapi import WatchRequest, WatchServer
    store = MemoryStore()
    server = WatchServer(store)
    stream = server.watch(WatchRequest(kinds=[Task]))
    for i in range(1, 4):
        store.update(lambda tx, i=i: tx.create(mk_task(i)))
    seen = [stream.get(timeout=1) for _ in range(2)]
    token = seen[-1].version
    stream.close()
    # more commits while detached
    for i in range(4, 6):
        store.update(lambda tx, i=i: tx.create(mk_task(i)))
    resumed = server.watch(WatchRequest(kinds=[Task],
                                        resume_from_version=token))
    got = []
    while True:
        ev = resumed.poll()
        if ev is None:
            break
        got.append(ev)
    ids = [e.obj.id for e in got]
    assert ids == ["t003", "t004", "t005"]
    versions = [e.version for e in got]
    assert versions == sorted(versions) and versions[0] == token + 1
    resumed.close()


def test_resume_token_is_member_portable():
    """A token minted on the leader store resumes on a follower replica
    (identical version stamping through apply_store_actions)."""
    from swarmkit_tpu.manager.watchapi import WatchRequest, WatchServer
    from swarmkit_tpu.state.store import StoreAction
    leader = MemoryStore()
    follower = MemoryStore()

    class Replicator:
        leadership_epoch = None

        def propose(self, actions, commit_cb=None, epoch=None):
            commit_cb()
            follower.apply_store_actions(
                [StoreAction(a.action, a.obj.copy()) for a in actions])

    leader._proposer = Replicator()
    stream = WatchServer(leader).watch(WatchRequest(kinds=[Task]))
    for i in range(1, 5):
        leader.update(lambda tx, i=i: tx.create(mk_task(i)))
    token = None
    for _ in range(2):
        token = stream.get(timeout=1).version
    stream.close()
    assert follower.version == leader.version
    resumed = WatchServer(follower).watch(
        WatchRequest(kinds=[Task], resume_from_version=token))
    ids = []
    while True:
        ev = resumed.poll()
        if ev is None:
            break
        ids.append(ev.obj.id)
    assert ids == ["t003", "t004"]
    resumed.close()


def test_resume_compacted_raises():
    from swarmkit_tpu.manager.watchapi import (
        ResumeCompacted, WatchRequest, WatchServer,
    )
    store = MemoryStore()
    store.changelog_limit = 4
    for i in range(1, 10):
        store.update(lambda tx, i=i: tx.create(mk_task(i)))
    with pytest.raises(ResumeCompacted):
        WatchServer(store).watch(
            WatchRequest(kinds=[Task], resume_from_version=1))


# ------------------------------------------------- watch filter parity

def _filter_events(request, events):
    from swarmkit_tpu.manager.watchapi import compile_filter
    pred = compile_filter(request)
    return [ev for ev in events if pred(ev)]


def test_watch_field_filters_and_custom_indices():
    from swarmkit_tpu.manager.watchapi import WatchRequest
    from swarmkit_tpu.state.events import Event
    t1 = mk_task(1, sid="a")
    t2 = mk_task(2, sid="b")
    t2.desired_state = TaskState.SHUTDOWN
    svc = Service(id="s1", spec=ServiceSpec(
        annotations=Annotations(name="Web",
                                indices={"tier": "frontend"})),
        spec_version=Version(index=1))
    events = [Event("create", t1), Event("create", t2),
              Event("create", svc)]
    # slot selector
    got = _filter_events(WatchRequest(slots=[("a", 1)]), events)
    assert [e.obj.id for e in got] == ["t001"]
    # desired-state selector
    got = _filter_events(
        WatchRequest(desired_states=[int(TaskState.SHUTDOWN)]), events)
    assert [e.obj.id for e in got] == ["t002"]
    # exact-name selector (case-insensitive, like the store index)
    got = _filter_events(WatchRequest(names=["web"]), events)
    assert [e.obj.id for e in got] == ["s1"]
    # custom index exact + prefix
    got = _filter_events(
        WatchRequest(custom_indices=[("tier", "frontend")]), events)
    assert [e.obj.id for e in got] == ["s1"]
    got = _filter_events(
        WatchRequest(custom_index_prefixes=[("tier", "front")]), events)
    assert [e.obj.id for e in got] == ["s1"]
    got = _filter_events(
        WatchRequest(custom_indices=[("tier", "backend")]), events)
    assert got == []


def test_watch_filters_member_agnostic():
    """The same compiled filter applied to the leader's and a follower's
    event payloads selects the same stream (shared by both serve paths
    and by the sim's continuity ledger)."""
    from swarmkit_tpu.manager.watchapi import WatchRequest, compile_filter
    from swarmkit_tpu.state.store import StoreAction
    leader, follower = MemoryStore(), MemoryStore()
    req = WatchRequest(kinds=[Task], service_ids=["a"])
    pred = compile_filter(req)
    lsub = leader.queue.subscribe(pred)
    fsub = follower.queue.subscribe(pred)

    class Replicator:
        leadership_epoch = None

        def propose(self, actions, commit_cb=None, epoch=None):
            commit_cb()
            follower.apply_store_actions(
                [StoreAction(a.action, a.obj.copy()) for a in actions])

    leader._proposer = Replicator()
    for i, sid in ((1, "a"), (2, "b"), (3, "a")):
        leader.update(lambda tx, i=i, sid=sid: tx.create(mk_task(i, sid)))
    from swarmkit_tpu.state.events import event_version
    lgot = []
    while True:
        ev = lsub.poll()
        if ev is None:
            break
        lgot.append((event_version(ev), ev.obj.id))
    fgot = []
    while True:
        ev = fsub.poll()
        if ev is None:
            break
        fgot.append((event_version(ev), ev.obj.id))
    assert lgot == fgot == [(1, "t001"), (3, "t003")]


# ------------------------------------------------ dispatcher follower mode

def _mk_node(nid):
    return Node(id=nid, spec=NodeSpec(annotations=Annotations(name=nid)),
                status=NodeStatus(state=NodeState.UNKNOWN),
                description=NodeDescription(
                    hostname=nid,
                    resources=Resources(nano_cpus=10 ** 9,
                                        memory_bytes=1 << 30)))


def test_follower_dispatcher_routes_writes_to_write_store():
    from swarmkit_tpu.manager.dispatcher import Config_, Dispatcher
    local = MemoryStore()      # the follower's replicated store (reads)
    leader = MemoryStore()     # write target
    for s in (local, leader):
        s.update(lambda tx: tx.create(_mk_node("w0")))
    d = Dispatcher(local, Config_(rate_limit_period=0.0),
                   write_store=leader)
    d.run(start_worker=False)
    session, _ = d.register("w0")
    d._flush_updates()
    # the READY write landed on the leader store, not the local one
    assert leader.raw_get(Node, "w0").status.state == NodeState.READY
    assert local.raw_get(Node, "w0").status.state == NodeState.UNKNOWN
    d.stop()


def test_follower_dispatcher_requeues_on_forward_failure():
    from swarmkit_tpu.manager.dispatcher import (
        Config_, Dispatcher, DispatcherError,
    )
    local = MemoryStore()
    local.update(lambda tx: tx.create(_mk_node("w0")))

    class GappyStore:
        def __init__(self):
            self.fail = True

        def batch(self, cb):
            if self.fail:
                raise DispatcherError("no leader to forward the write to")
            return local.batch(cb)

    gap = GappyStore()
    d = Dispatcher(local, Config_(rate_limit_period=0.0),
                   write_store=gap)
    d.run(start_worker=False)
    d.register("w0")
    d._flush_updates()   # forward fails: re-queued, not lost
    assert local.raw_get(Node, "w0").status.state == NodeState.UNKNOWN
    gap.fail = False
    d._flush_updates()
    assert local.raw_get(Node, "w0").status.state == NodeState.READY
    d.stop()


def test_shard_filter_and_release_session():
    from swarmkit_tpu.manager.dispatcher import Config_, Dispatcher
    store = MemoryStore()
    for nid in ("w0", "w1"):
        store.update(lambda tx, nid=nid: tx.create(_mk_node(nid)))
    d = Dispatcher(store, Config_(rate_limit_period=0.0),
                   shard_filter=lambda nid: nid == "w0")
    d.run(start_worker=False)
    # only the shard's node got a registration-grace deadline
    kinds = [(k, n) for (_, _, k, n) in d._heap if k == "reg"]
    assert kinds == [("reg", "w0")]
    session, _ = d.register("w0")
    d.release_session("w0", session)
    d._flush_updates()
    # released WITHOUT a DOWN write (graceful handoff)
    assert store.raw_get(Node, "w0").status.state == NodeState.READY
    with pytest.raises(Exception):
        d.heartbeat("w0", session)
    d.stop()


def test_reg_grace_check_vetoes_down_for_foreign_sessions():
    from swarmkit_tpu.manager.dispatcher import Config_, Dispatcher
    from swarmkit_tpu.models import types as mtypes
    t = [1000.0]
    mtypes.set_time_source(lambda: t[0])
    try:
        store = MemoryStore()
        store.update(lambda tx: tx.create(_mk_node("w0")))
        owned_elsewhere = {"w0"}
        d = Dispatcher(store, Config_(rate_limit_period=0.0))
        d.reg_grace_check = lambda nid: nid not in owned_elsewhere
        d.run(start_worker=False)
        t[0] += 3600.0
        d.process_deadlines()
        assert store.raw_get(Node, "w0").status.state \
            == NodeState.UNKNOWN   # vetoed: session lives elsewhere
        owned_elsewhere.clear()
        d.adopt_registration_grace(["w0"])
        t[0] += 3600.0
        d.process_deadlines()
        assert store.raw_get(Node, "w0").status.state == NodeState.DOWN
        d.stop()
    finally:
        mtypes.set_time_source(None)


# ------------------------------------------------- agent session failover

def test_failover_client_rotates_on_session_invalid():
    from swarmkit_tpu.net.client import SessionInvalid
    from swarmkit_tpu.remotes import (
        ConnectionBroker, FailoverDispatcherClient, Remotes,
    )
    import random

    calls = []

    class FakeClient:
        def __init__(self, addr):
            self.addr = addr

        def heartbeat(self, node_id, session_id):
            calls.append(self.addr)
            if len(calls) == 1:
                raise SessionInvalid("session gone")
            return 1.0

        def close(self):
            pass

    remotes = Remotes(("a", 1), ("b", 2), rng=random.Random(0))
    broker = ConnectionBroker(remotes)
    fc = FailoverDispatcherClient(broker, None,
                                  client_factory=FakeClient)
    with pytest.raises(SessionInvalid):
        fc.heartbeat("w0", "s1")
    fc.heartbeat("w0", "s1")
    assert len(calls) == 2
    assert calls[0] != calls[1], \
        "session-invalid must re-resolve to a DIFFERENT manager"
    # the healthy link never shifted weights
    w = remotes.weights()
    assert w[calls[1]] >= w[calls[0]]


def test_agent_counts_reconnects_by_reason():
    from swarmkit_tpu.utils.metrics import registry
    from swarmkit_tpu.remotes import count_reconnect
    base = registry.get_counter(
        'swarm_agent_reconnects{reason="session_invalid"}')
    count_reconnect("session_invalid")
    assert registry.get_counter(
        'swarm_agent_reconnects{reason="session_invalid"}') == base + 1


# ----------------------------------------------------- health: stale reads

def test_stale_read_risk_transitions():
    from swarmkit_tpu.obs.health import stale_read_risk_value
    from swarmkit_tpu.utils.metrics import Registry
    reg = Registry()
    val = stale_read_risk_value(read_index_p99_bound=0.5)
    assert val(reg) is None                      # no read plane yet
    reg.gauge("swarm_lease_enabled", 1.0)
    assert val(reg) == 0.0                       # lease on, no staleness
    reg.gauge("swarm_lease_enabled", 0.0)
    t = reg.timer("swarm_read_index_latency")
    for _ in range(20):
        t.observe(2.0)                           # slow quorum rounds
    assert val(reg) == 1.0                       # warn: degraded
    reg.counter("swarm_stale_reads")
    assert val(reg) == 2.0                       # fail: stale serve


# ---------------------------------------------------------- sim scenarios

def _quiet():
    logging.disable(logging.CRITICAL)


def test_follower_read_failover_green_and_deterministic():
    from swarmkit_tpu.sim.scenario import run_scenario
    _quiet()
    r1 = run_scenario("follower-read-failover", 0, keep_trace=True)
    assert r1.ok, r1.violations
    r2 = run_scenario("follower-read-failover", 0)
    assert r2.trace_hash == r1.trace_hash
    assert r2.obs_trace_sha256 == r1.obs_trace_sha256
    reads = r1.stats["reads"]
    # consumers stayed off the coordinator...
    assert reads["leader_share"] <= 0.05, reads
    # ...while the plane actually carried traffic and failed over
    assert reads["watch_events"] > 0
    assert reads["watch_hops"] >= 1, \
        "a watcher must have resumed on a different member"
    assert reads["agent_reconnects"] >= 1
    assert reads["lease"] > 0 and reads["read_index"] > 0
    # the stranded ex-leader was probed and refused to serve stale
    assert any("fault stale-read-probe" in line for line in r1.trace)
    assert reads["stale_probe_refused"] >= 1


def test_read_storm_degraded_green():
    from swarmkit_tpu.sim.scenario import run_scenario
    _quiet()
    r = run_scenario("read-storm-degraded", 0)
    assert r.ok, r.violations
    reads = r.stats["reads"]
    assert reads["probe_ok"] > 10
    assert reads["probe_unavailable"] == 0
    assert reads["leader_share"] <= 0.05, reads


# ------------------------------------------ checker-sensitivity (3 new)

def _sim_with_leader(seed=3):
    """A raft_cp sim pumped until a leader control plane is attached and
    bootstrapped."""
    from swarmkit_tpu.sim.cluster import Sim
    sim = Sim(seed, raft_cp=True)
    eng = sim.engine
    while (sim.cp.active is None or not sim.cp._bootstrapped) \
            and eng.clock.elapsed() < 30.0:
        eng.run_until(eng.clock.elapsed() + 0.5)
    assert sim.cp.active is not None
    return sim


@pytest.fixture
def restore_stale_counter():
    """The stale-serve counter latches the stale_read_risk health check
    to FAIL (by design — production never increments it); a sensitivity
    test that deliberately forces a stale serve must put the global
    registry back or every later health assertion in the process
    inherits the failure."""
    from swarmkit_tpu.utils.metrics import registry
    before = registry.get_counter("swarm_stale_reads")
    yield
    delta = registry.get_counter("swarm_stale_reads") - before
    if delta:
        registry.counter("swarm_stale_reads", -delta)


def test_checker_fires_when_read_barrier_skipped(restore_stale_counter):
    """Serve a follower view WITHOUT waiting for the read barrier while
    the follower is partitioned behind committed writes:
    follower-reads-never-uncommitted must fire."""
    _quiet()
    with _sim_with_leader() as sim:
        eng = sim.engine
        cp = sim.cp
        leader = sim.leader()
        follower = next(m for m in sim.managers if m is not leader)
        sim.net.isolate(follower.id)
        cp.scale(4)
        eng.run_until(eng.clock.elapsed() + 5.0)
        assert follower.store.version < cp.read_inv.committed_version()
        # control: enforcement ON -> the read refuses rather than serve
        with pytest.raises(ReadUnavailable):
            follower.store.read_view(lambda tx: len(tx.find(Task)),
                                     linearizable=True, timeout=3.0)
        assert not any("follower-reads-never-uncommitted" in v
                       for v in sim.violations.items)
        # seam off: the stale view IS served -> checker must fire
        cp.proposers[follower.id].enforce_read_barrier = False
        follower.store.read_view(lambda tx: len(tx.find(Task)),
                                 linearizable=True, timeout=3.0)
        assert any("follower-reads-never-uncommitted" in v
                   for v in sim.violations.items)


def test_checker_fires_on_lease_read_under_skew():
    """Widen the lease past the drift margin (gate removed) under an
    injected clock-skew fault: lease-read-safe-under-skew must fire."""
    _quiet()
    with _sim_with_leader() as sim:
        eng = sim.engine
        cp = sim.cp
        leader = sim.leader()
        # control: with the gate live, skew degrades to read-index
        other = next(m for m in sim.managers if m is not leader)
        other.tick_scale = 2.0
        cp.linearizable_read(leader, lambda tx: len(tx.find(Task)))
        assert not any("lease-read-safe-under-skew" in v
                       for v in sim.violations.items)
        # seam: widen the lease and drop the skew gate entirely
        from swarmkit_tpu.models.types import now as vnow
        leader.core.lease_gate = None
        leader.core.lease_duration = 1e6
        leader.core._lease_expiry = vnow() + 1e6
        res = leader.store._proposer.read_barrier()
        assert res["lease"], "seam must force the lease fast path"
        assert any("lease-read-safe-under-skew" in v
                   for v in sim.violations.items)


def test_checker_fires_on_dropped_resume_token():
    """Drop a resume-token increment on reattach (resume_skew=-1 re-
    delivers the last event): watch-resume-no-gap-no-dup must fire."""
    _quiet()
    from swarmkit_tpu.sim.cluster import SimWatcher
    with _sim_with_leader() as sim:
        eng = sim.engine
        cp = sim.cp
        cp.add_watchers(1)
        w = cp.watchers[0]
        w.resume_skew = -1
        cp.scale(4)
        eng.run_until(eng.clock.elapsed() + 8.0)
        assert w.events_seen > 0
        # force a reattach mid-stream (member hop with a skewed token)
        m = w.member
        assert m is not None
        m.crash()
        eng.run_until(eng.clock.elapsed() + 4.0)
        m.restart()
        eng.run_until(eng.clock.elapsed() + 8.0)
        w.drain()
        w.continuity.ensure()
        w.continuity.drain()
        w.continuity.judge(w)
        assert any("watch-resume-no-gap-no-dup" in v
                   for v in sim.violations.items), \
            "a dropped token increment must be caught as dup/gap"


# ----------------------------------------------------------- slow sweeps

@pytest.mark.slow
def test_follower_planes_get_batched_fanout_by_default():
    """The follower-served dispatcher planes come up with the assignment
    fan-out, the one path there is, each over its own store, and a
    session gap through a plane rebuilds a COMPLETE set with nothing
    lost or duplicated."""
    _quiet()
    from swarmkit_tpu.models import TaskState, TaskStatus
    from swarmkit_tpu.state.store import ByNode

    def _mk_assigned(sim, nid, start, n):
        """Assigned tasks for ``nid`` written through the LEADER store
        (they replicate to every member's plane store)."""
        leader_store = sim.leader().store

        def cb(tx):
            for i in range(start, start + n):
                t = mk_task(i, sid="fan-svc")
                t.node_id = nid
                t.status = TaskStatus(state=TaskState.ASSIGNED)
                tx.create(t)
        leader_store.update(cb)

    def _agentless_sim(seed=3):
        # no sim agents: the test drives the plane's session itself, and
        # a main-thread leader write must never race an agent's
        # leader-forwarded write (that shape deadlocks by design — the
        # scenarios route all traffic through the engine)
        from swarmkit_tpu.sim.cluster import Sim
        sim = Sim(seed, raft_cp=True, n_agents=0)
        eng = sim.engine
        while (sim.cp.active is None or not sim.cp._bootstrapped) \
                and eng.clock.elapsed() < 30.0:
            eng.run_until(eng.clock.elapsed() + 0.5)
        assert sim.cp.active is not None
        return sim

    with _agentless_sim() as sim:
        cp = sim.cp
        cp.enable_follower_reads()
        leader = sim.leader()
        follower = next(m for m in sim.managers if m is not leader)
        plane = cp.plane_for(follower)
        assert plane is not None
        assert plane.fanout is not None, \
            "follower plane must default to the batched fan-out"
        # a session + stream through the PLANE (reads local, writes
        # forwarded to the leader), then a gap and a rebuild
        nid = "fanout-w0"
        leader.store.update(lambda tx: tx.create(_mk_node(nid)))
        cp.session_owner[nid] = follower.id
        eng = sim.engine
        eng.run_until(eng.clock.elapsed() + 2.0)
        session, _ = plane.register(nid)
        stream = plane.open_assignments(nid, session)
        assert stream.get(timeout=0).type == "complete"
        # assignments land via replication; the flush pass batches them
        _mk_assigned(sim, nid, 0, 5)
        eng.run_until(eng.clock.elapsed() + 4.0)
        plane.process_deadlines()
        inc = []
        while True:
            try:
                inc.append(stream.get(timeout=0))
            except TimeoutError:
                break
        assert inc and all(m.type == "incremental" for m in inc)
        # the gap: session released mid-flow, more assignments land,
        # plane flushes with the stream down (no crash, nothing lost)
        plane.release_session(nid, session)
        assert stream.closed
        _mk_assigned(sim, nid, 5, 3)
        eng.run_until(eng.clock.elapsed() + 4.0)
        plane.process_deadlines()
        session2, _ = plane.register(nid)
        stream2 = plane.open_assignments(nid, session2)
        rebuilt = stream2.get(timeout=0)
        assert rebuilt.type == "complete"
        want = sorted(
            t.id for t in follower.store.view(
                lambda tx: tx.find(Task, ByNode(nid))))
        got = sorted(obj.id for _a, kind, obj in rebuilt.changes
                     if kind == "task")
        assert got == want and len(want) == 8
        assert len(got) == len(set(got))
    # no opt-out: every plane's fan-out subscribes to its own member's
    # store, and the accessor hands back that one fan-out
    with _agentless_sim(seed=5) as sim2:
        cp2 = sim2.cp
        cp2.enable_follower_reads()
        leader2 = sim2.leader()
        follower2 = next(m for m in sim2.managers if m is not leader2)
        plane2 = cp2.plane_for(follower2)
        assert plane2 is not None and plane2.fanout is not None
        assert plane2.enable_batched_fanout() is plane2.fanout
        assert plane2.store is follower2.store
        assert plane2.fanout._sub in follower2.store.queue._subs
        assert plane2.fanout._sub not in leader2.store.queue._subs


def test_read_scenarios_20_seed_sweep_byte_identical():
    from swarmkit_tpu.sim.scenario import run_scenario
    _quiet()
    hashes = {}
    for name in ("follower-read-failover", "read-storm-degraded"):
        for seed in range(20):
            r = run_scenario(name, seed)
            assert r.ok, (name, seed, r.violations)
            hashes[(name, seed)] = (r.trace_hash, r.obs_trace_sha256)
    # byte-identity: re-running a seed reproduces the exact trace
    for name, seed in (("follower-read-failover", 7),
                       ("read-storm-degraded", 3)):
        r = run_scenario(name, seed)
        assert (r.trace_hash, r.obs_trace_sha256) == hashes[(name, seed)]
