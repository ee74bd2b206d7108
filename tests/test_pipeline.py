"""Differential tests for the pipelined scheduler.

The pipelined tick (SWARM_PIPELINE_DEPTH > 1) overlaps group i+1's
device plan with group i's host commit; the contract is that pipelining
changes ONLY wall-clock interleaving — placements, store state, and the
watch-event stream must be byte-identical to the serial path (depth 1)
for the same workload.  These tests build seeded workloads under a
frozen time source and compare depth 1 vs 2 vs 4 end to end, including
the host-fallback and conflict/rollback routes, standalone and with a
real raft proposer (chunk-pipelined block proposals).
"""

import random
import shutil
import tempfile
import time

import pytest

from swarmkit_tpu.models import (
    Annotations, Node, NodeDescription, NodeSpec, NodeState, NodeStatus,
    Placement, PlacementPreference, Platform, ReplicatedService, Resources,
    ResourceRequirements, Service, ServiceMode, ServiceSpec, SpreadOver,
    Task, TaskSpec, TaskState, TaskStatus, Version,
)
from swarmkit_tpu.models import types as model_types
from swarmkit_tpu.ops import TPUPlanner
from swarmkit_tpu.scheduler import Scheduler
from swarmkit_tpu.state import MemoryStore
from swarmkit_tpu.state.events import Event, EventCommit, EventTaskBlock


@pytest.fixture
def frozen_clock():
    """Pin models.types.now() so snapshots/events carry identical
    timestamps across the runs being diffed."""
    model_types.set_time_source(lambda: 1_700_000_000.0)
    try:
        yield
    finally:
        model_types.set_time_source(None)


def _mk_nodes(n):
    return [Node(
        id=f"n{i:04d}",
        spec=NodeSpec(annotations=Annotations(
            name=f"node-{i:04d}", labels={"rack": f"r{i % 5}",
                                          "row": f"w{i % 3}",
                                          "hall": f"h{i % 2}",
                                          "site": f"s{i % 2}",
                                          "zone": f"z{i % 4}"})),
        status=NodeStatus(state=NodeState.READY),
        description=NodeDescription(
            hostname=f"node-{i:04d}",
            platform=Platform(os="linux", architecture="amd64"),
            resources=Resources(nano_cpus=16 * 10**9,
                                memory_bytes=64 << 30)))
        for i in range(n)]


def _mk_service(sid, n_tasks, spec=None, spec_version=1):
    svc = Service(
        id=sid,
        spec=ServiceSpec(annotations=Annotations(name=f"svc-{sid}"),
                         mode=ServiceMode.REPLICATED,
                         replicated=ReplicatedService(replicas=n_tasks),
                         task=spec or TaskSpec()),
        spec_version=Version(index=spec_version))
    tasks = [Task(id=f"{sid}-t{k:04d}", service_id=sid, slot=k + 1,
                  desired_state=TaskState.RUNNING, spec=svc.spec.task,
                  spec_version=Version(index=spec_version),
                  status=TaskStatus(state=TaskState.PENDING))
             for k in range(n_tasks)]
    return svc, tasks


def _build_workload(seed):
    """Seeded multi-group workload covering the device route, the
    host-fallback route (node.ip constraint -> unsupported; 5-level
    spread -> host placement), and one-off (no spec-version) groups."""
    rng = random.Random(seed)
    store = MemoryStore()
    nodes = _mk_nodes(48)

    def mk(tx):
        for n in nodes:
            tx.create(n)

    store.update(mk)

    device_spec = TaskSpec(resources=ResourceRequirements(
        reservations=Resources(nano_cpus=10**8, memory_bytes=64 << 20)))
    spread_spec = TaskSpec(placement=Placement(preferences=[
        PlacementPreference(spread=SpreadOver(
            spread_descriptor=f"node.labels.{k}"))
        for k in ("rack", "row", "hall", "site", "zone")]))  # 5 levels
    ip_spec = TaskSpec(placement=Placement(
        constraints=["node.ip!=192.168.0.1"]))

    batches = [
        _mk_service("svca", 200 + rng.randrange(50), device_spec),
        _mk_service("svcb", 150 + rng.randrange(50), device_spec),
        _mk_service("svcc", 100 + rng.randrange(30), spread_spec),
        _mk_service("svcd", 20, ip_spec),
        _mk_service("svce", 120 + rng.randrange(40), device_spec),
    ]

    def mk2(tx):
        for svc, tasks in batches:
            tx.create(svc)
            for t in tasks:
                tx.create(t)
        # one-off tasks: no spec_version -> scheduled as single groups
        for j in range(3):
            tx.create(Task(id=f"oneoff-{j}", service_id="svca",
                           slot=900 + j, desired_state=TaskState.RUNNING,
                           spec=device_spec,
                           status=TaskStatus(state=TaskState.PENDING)))

    store.update(mk2)
    return store


def _event_key(ev):
    if isinstance(ev, EventTaskBlock):
        return ("block", tuple(o.id for o in ev.olds),
                tuple(ev.node_ids), ev.base_version, ev.state, ev.message)
    if isinstance(ev, EventCommit):
        return ("commit", ev.version)
    if isinstance(ev, Event):
        obj = ev.obj
        return (ev.action, obj.id, getattr(obj, "node_id", None),
                int(obj.status.state) if hasattr(obj, "status") else None,
                obj.meta.version.index)
    return ("other", repr(ev))


def _run_tick(store, depth, pre_tick=None, ticks=1):
    sub = store.queue.subscribe(accepts_blocks=True)
    planner = TPUPlanner()
    planner.enable_small_group_routing = False  # deterministic routing
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=depth)
    store.view(sched._setup_tasks_list)
    if pre_tick is not None:
        pre_tick(store, sched)
    decisions = 0
    for _ in range(ticks):
        decisions += sched.tick()
    events = [_event_key(e) for e in sub.drain()]
    store.queue.unsubscribe(sub)
    tasks = store.view(lambda tx: tx.find(Task))
    state = sorted((t.id, t.node_id, int(t.status.state),
                    t.status.message, t.meta.version.index)
                   for t in tasks)
    return decisions, state, events, sched, planner


@pytest.mark.parametrize("depth", [2, 4])
@pytest.mark.parametrize("seed", [7, 23])
def test_pipelined_tick_byte_identical_to_serial(frozen_clock, depth,
                                                 seed):
    """Depth-N placements, store snapshot bytes, and watch-event streams
    must equal the serial path's, across multi-group workloads that also
    exercise host-fallback routes."""
    d1, s1, e1, sched1, _ = _run_tick(_build_workload(seed), 1)
    dn, sn, en, schedn, _ = _run_tick(_build_workload(seed), depth)
    assert dn == d1
    assert sn == s1
    assert en == e1
    # mirror state converged identically too (requeues, all_tasks)
    assert sorted(schedn.unassigned_tasks) == sorted(
        sched1.unassigned_tasks)
    # snapshot bytes: the strongest store-state equality
    b1 = _run_tick(_build_workload(seed), 1)[3].store.save_bytes()
    bn = _run_tick(_build_workload(seed), depth)[3].store.save_bytes()
    assert b1 == bn


def test_pipelined_tick_hides_plan_behind_commit_and_the_serial_does_not():
    """The one thing pipelining is for, read off the tick's own spans:
    at depth 2 group i's ``sched.commit`` runs inside group i+1's
    ``plan.inflight`` window, so the phase table's plan/commit overlap
    is above the serial tick's, which has none to show.  No frozen
    clock: under an installed time source spans have no duration."""
    from swarmkit_tpu.obs import phase_table, tracer

    def overlap_s(depth):
        tracer.reset()
        tracer.enable()
        try:
            _run_tick(_build_workload(7), depth)
            table = phase_table(tracer.to_chrome())
        finally:
            tracer.disable()
            tracer.reset()
        assert table["commit_wall_s"] > 0 and table["plan_wall_s"] > 0
        return table["plan_commit_overlap_s"]

    assert overlap_s(2) > overlap_s(1)


def test_pipelined_conflict_rollback_matches_serial(frozen_clock):
    """A mid-flight concurrent assignment (stale mirror version) must
    fail the block item, roll back mirrors, and requeue — identically in
    serial and pipelined mode, across two ticks."""
    def conflict(store, sched):
        def cb(tx):
            for tid in ("svca-t0000", "svcb-t0001"):
                cur = tx.get(Task, tid).copy()
                cur.node_id = "n0000"
                cur.status = TaskStatus(state=TaskState.ASSIGNED,
                                        timestamp=1.0,
                                        message="concurrent writer")
                tx.update(cur)
        store.update(cb)

    d1, s1, e1, sched1, _ = _run_tick(_build_workload(5), 1,
                                      pre_tick=conflict, ticks=2)
    d2, s2, e2, sched2, _ = _run_tick(_build_workload(5), 2,
                                      pre_tick=conflict, ticks=2)
    assert (d1, s1, e1) == (d2, s2, e2)
    # the conflicting tasks were requeued rather than lost or committed
    assert "svca-t0000" in sched1.unassigned_tasks
    assert sorted(sched2.unassigned_tasks) == sorted(
        sched1.unassigned_tasks)


def test_pipelined_raft_chunked_proposals_match_serial(frozen_clock,
                                                      tmp_path):
    """With a real single-voter raft proposer, chunk-pipelined block
    proposals (depth 4, small chunks) must produce the same task states
    and event stream as serial propose-per-chunk."""
    from swarmkit_tpu.state.raft import LocalNetwork, RaftLogger, RaftNode

    def run(depth, sub_dir):
        store = _build_workload(11)
        rn = RaftNode("b0", ["b0"], store,
                      RaftLogger(str(tmp_path / sub_dir)), LocalNetwork())
        store._proposer = rn
        store.pipeline_depth = depth
        store.BLOCK_PROPOSAL_MAX_ITEMS = 64   # force several chunks
        rn.start()
        deadline = time.time() + 15
        while not (rn.is_leader and rn.core.leader_ready):
            assert time.time() < deadline, "raft leader not ready"
            time.sleep(0.01)
        try:
            return _run_tick(store, depth)
        finally:
            rn.stop()

    d1, s1, e1, *_ = run(1, "d1")
    d4, s4, e4, *_ = run(4, "d4")
    assert d4 == d1
    assert s4 == s1
    assert e4 == e1


def test_propose_async_preserves_order(tmp_path):
    """propose_async submissions from one thread commit and run their
    apply-path callbacks in submission order."""
    from swarmkit_tpu.state.raft import LocalNetwork, RaftLogger, RaftNode
    from swarmkit_tpu.state.store import StoreAction

    store = MemoryStore()
    rn = RaftNode("a0", ["a0"], store, RaftLogger(str(tmp_path / "a0")),
                  LocalNetwork())
    rn.start()
    deadline = time.time() + 15
    while not (rn.is_leader and rn.core.leader_ready):
        assert time.time() < deadline
        time.sleep(0.01)
    try:
        applied = []
        node = _mk_nodes(1)[0]
        waiters = [
            rn.propose_async([StoreAction("create", node)],
                             lambda i=i: applied.append(i))
            for i in range(6)]
        for w in waiters:
            rn.wait_proposal(w)
        assert applied == list(range(6))
    finally:
        rn.stop()


def test_pipeline_depth_escape_hatch(monkeypatch):
    """SWARM_PIPELINE_DEPTH=1 reverts every consumer to serial."""
    from swarmkit_tpu.utils.pipeline import default_pipeline_depth

    monkeypatch.setenv("SWARM_PIPELINE_DEPTH", "1")
    assert default_pipeline_depth() == 1
    assert Scheduler(MemoryStore()).pipeline_depth == 1
    assert MemoryStore().pipeline_depth == 1
    monkeypatch.setenv("SWARM_PIPELINE_DEPTH", "4")
    assert Scheduler(MemoryStore()).pipeline_depth == 4
    monkeypatch.setenv("SWARM_PIPELINE_DEPTH", "bogus")
    assert default_pipeline_depth() == 2
    monkeypatch.delenv("SWARM_PIPELINE_DEPTH")
    assert default_pipeline_depth() == 2
    # explicit constructor depth wins over the env
    monkeypatch.setenv("SWARM_PIPELINE_DEPTH", "8")
    assert Scheduler(MemoryStore(), pipeline_depth=1).pipeline_depth == 1


def test_planner_inflight_queue_discipline(frozen_clock):
    """dispatch/fetch must run FIFO, and dispatching over an unfetched
    plan is rejected (its apply feeds the next group's columns)."""
    store = _build_workload(3)
    planner = TPUPlanner()
    planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=1)
    store.view(sched._setup_tasks_list)
    groups = dict(sched.unassigned_groups)
    sched.unassigned_groups = {}
    sched.unassigned_tasks.clear()
    (k1, g1), (k2, g2) = list(groups.items())[:2]
    decisions = {}
    planner.begin_tick(sched)
    h1 = planner.dispatch_group(sched, dict(g1), decisions)
    assert h1 is not None
    with pytest.raises(RuntimeError):
        planner.dispatch_group(sched, dict(g2), decisions)
    assert planner.fetch_group(h1) is True
    planner.discard_inflight()
    planner.end_tick()

