"""Gang scheduling & pipeline workflows (ISSUE 16): spec serde +
forward compatibility, control-API validation (gang shape, DAG cycles),
the gang_fit device kernel vs its numpy host oracle (differential fuzz,
per-group AND fused routes), atomic admission (single-commit placement,
rollback on shortfall, deterministic two-gang ordering), the
preemption-entitlement bugfix (starved priority-0 gangs acquire victims
under tenant quota), the scheduler's pipeline gate, the
PipelineSupervisor release/halt FSM, non-gang byte-identity, and
checker sensitivity for the two new sim invariants.
"""

import dataclasses

import numpy as np
import pytest

from swarmkit_tpu.models import (
    Annotations, ContainerSpec, GangConfig, Node, NodeDescription,
    NodeSpec, NodeState, NodeStatus, PipelineStatus, Placement,
    ReplicatedJob, ReplicatedService, Resources, ResourceRequirements,
    Service, ServiceMode, ServiceSpec, Task, TaskSpec, TaskState,
    TaskStatus, Version,
)
from swarmkit_tpu.models.objects import Cluster
from swarmkit_tpu.models.specs import ClusterSpec
from swarmkit_tpu.models.types import TenantQuota, now
from swarmkit_tpu.manager.controlapi import ControlAPI, InvalidArgument
from swarmkit_tpu.ops.kernel import (
    GroupInputs, NodeInputs, gang_fit_fused_jit, gang_fit_jit,
)
from swarmkit_tpu.orchestrator.pipeline import (
    POISON_FAILURES, PipelineSupervisor,
)
from swarmkit_tpu.scheduler import Scheduler
from swarmkit_tpu.scheduler import gang as gang_mod
from swarmkit_tpu.scheduler.quota import TENANT_LABEL
from swarmkit_tpu.sim.cluster import Sim
from swarmkit_tpu.sim.faults import NetConfig
from swarmkit_tpu.state import serde
from swarmkit_tpu.state.store import MemoryStore
from swarmkit_tpu.utils import new_id

CPU = 2 * 10 ** 9
GB = 1 << 30


# ---------------------------------------------------------------------------
# serde: round-trip + forward compatibility
# ---------------------------------------------------------------------------

def _gang_task():
    return Task(
        id=new_id(), service_id="svc1", slot=1,
        desired_state=TaskState.RUNNING,
        spec=TaskSpec(
            placement=Placement(gang=GangConfig(min_size=8)),
            gang_id="ring-0",
            resources=ResourceRequirements(
                reservations=Resources(nano_cpus=CPU))),
        spec_version=Version(index=1),
        status=TaskStatus(state=TaskState.PENDING))


def _pipeline_service():
    return Service(
        id=new_id(),
        spec=ServiceSpec(
            annotations=Annotations(name="stage-b"),
            mode=ServiceMode.REPLICATED,
            replicated=ReplicatedService(replicas=3),
            task=TaskSpec(),
            depends_on=["stage-a"],
            on_upstream_failure="rollback"),
        spec_version=Version(index=1),
        pipeline_status=PipelineStatus(
            state="released", reason="", updated_at=5.0))


@pytest.mark.parametrize("obj", [_gang_task(), _pipeline_service()],
                         ids=["gang-task", "pipeline-service"])
def test_gang_fields_roundtrip_serde(obj):
    data = serde.dumps(obj)
    back = serde.loads(type(obj), data)
    assert dataclasses.asdict(back) == dataclasses.asdict(obj)
    assert serde.dumps(back) == data


def test_old_records_decode_to_gang_off_defaults():
    """Forward compatibility: records written before this PR (no gang /
    pipeline keys) decode to the gang-off defaults, and the copy()
    paths preserve the new fields."""
    t = _gang_task()
    d = serde.to_dict(t)
    del d["spec"]["gang_id"]
    del d["spec"]["placement"]["gang"]
    back = serde.from_dict(Task, d)
    assert back.spec.gang_id == ""
    assert back.spec.placement.gang is None
    assert not gang_mod.is_gang(back)

    s = _pipeline_service()
    d = serde.to_dict(s)
    del d["spec"]["depends_on"]
    del d["spec"]["on_upstream_failure"]
    del d["pipeline_status"]
    back = serde.from_dict(Service, d)
    assert back.spec.depends_on == []
    assert back.spec.on_upstream_failure == ""
    assert back.pipeline_status is None

    # deep-copy keeps the opt-in fields intact
    t2 = t.copy()
    assert t2.spec.gang_id == "ring-0"
    assert t2.spec.placement.gang.min_size == 8
    s2 = s.copy()
    assert s2.spec.depends_on == ["stage-a"]
    assert s2.pipeline_status.state == "released"
    # and is a real copy, not an alias
    s2.spec.depends_on.append("x")
    assert s.spec.depends_on == ["stage-a"]


def test_gang_unit_key_resolution():
    t = _gang_task()
    assert gang_mod.gang_unit(t) == "ring-0"
    t.spec.gang_id = ""
    assert gang_mod.gang_unit(t) == "svc1"


# ---------------------------------------------------------------------------
# control API: gang shape + DAG validation, exact error strings
# ---------------------------------------------------------------------------

def _svc_spec(name, depends_on=(), on_upstream_failure="",
              gang_min=None):
    placement = Placement()
    if gang_min is not None:
        placement = Placement(gang=GangConfig(min_size=gang_min))
    return ServiceSpec(
        annotations=Annotations(name=name),
        mode=ServiceMode.REPLICATED,
        replicated=ReplicatedService(replicas=2),
        task=TaskSpec(container=ContainerSpec(image="nginx"),
                      placement=placement),
        depends_on=list(depends_on),
        on_upstream_failure=on_upstream_failure)


def test_controlapi_validates_gang_and_pipeline_fields():
    api = ControlAPI(MemoryStore())

    with pytest.raises(InvalidArgument) as e:
        api.create_service(_svc_spec("g", gang_min=-1))
    assert str(e.value) == \
        "Placement: gang min_size must be a non-negative integer"

    with pytest.raises(InvalidArgument) as e:
        api.create_service(_svc_spec("p", depends_on=[""]))
    assert str(e.value) == ("ServiceSpec: depends_on entries must be "
                            "non-empty service names")

    with pytest.raises(InvalidArgument) as e:
        api.create_service(_svc_spec("p", depends_on=["p"]))
    assert str(e.value) == \
        'ServiceSpec: service "p" cannot depend on itself'

    with pytest.raises(InvalidArgument) as e:
        api.create_service(_svc_spec("p", on_upstream_failure="retry"))
    assert str(e.value) == ("ServiceSpec: unknown on_upstream_failure "
                            "'retry' (known: halt, rollback)")

    # valid opt-ins are accepted (forward reference to a not-yet-created
    # upstream is legal: the gate fails safe while it is absent)
    api.create_service(_svc_spec("ok-gang", gang_min=4))
    api.create_service(_svc_spec("ok-stage", depends_on=["upstream"],
                                 on_upstream_failure="rollback"))


def test_controlapi_rejects_dependency_cycles():
    api = ControlAPI(MemoryStore())
    api.create_service(_svc_spec("a", depends_on=["b"]))

    # closing the 2-cycle through the existing edge set is rejected
    with pytest.raises(InvalidArgument) as e:
        api.create_service(_svc_spec("b", depends_on=["a"]))
    assert str(e.value) == "ServiceSpec: depends_on cycle: b -> a -> b"

    # a longer cycle through an intermediate stage too
    api.create_service(_svc_spec("b", depends_on=["c"]))
    with pytest.raises(InvalidArgument) as e:
        api.create_service(_svc_spec("c", depends_on=["a"]))
    assert str(e.value) == \
        "ServiceSpec: depends_on cycle: c -> a -> b -> c"

    # update_service runs the same walk
    b = api.store.view(lambda tx: next(
        s for s in tx.find(Service) if s.spec.annotations.name == "b"))
    with pytest.raises(InvalidArgument):
        api.update_service(b.id, b.meta.version.index,
                           _svc_spec("b", depends_on=["b"]))


# ---------------------------------------------------------------------------
# gang_fit: device kernel vs numpy host oracle (differential fuzz)
# ---------------------------------------------------------------------------

def _random_gang_inputs(rng, nb, L=None):
    """One random densified (NodeInputs, GroupInputs) pair covering
    every filter column gang_fit folds: readiness, reservations,
    plugin masks, constraints (== / != / disabled), platforms, ports,
    max-replicas, and the optional tenant-quota column.  ``L`` pins
    the constraint-row count (the fused route stacks same-shape
    gangs)."""
    n = int(rng.integers(1, nb))
    valid = np.zeros(nb, bool)
    valid[:n] = True
    L = int(rng.integers(1, 3)) if L is None else L
    con_hash = rng.integers(0, 3, (L, 2, nb)).astype(np.int32)
    con_exp = rng.integers(0, 3, (L, 2)).astype(np.int32)
    con_op = rng.integers(0, 3, L).astype(np.int32)
    plat = np.full((2, 4), -1, np.int32)
    if rng.random() < 0.5:
        plat[0] = rng.integers(0, 2, 4).astype(np.int32)
    os_hash = rng.integers(0, 2, (2, nb)).astype(np.int32)
    nodes = NodeInputs(
        valid=valid,
        ready=valid & (rng.random(nb) < 0.9),
        res_ok=valid & (rng.random(nb) < 0.9),
        res_cap=np.where(valid, rng.integers(0, 12, nb), 0).astype(
            np.int32),
        svc_tasks=rng.integers(0, 6, nb).astype(np.int32),
        total_tasks=rng.integers(0, 40, nb).astype(np.int32),
        failures=rng.integers(0, 4, nb).astype(np.int32),
        leaf=np.zeros(nb, np.int32),
        os_hash=os_hash,
        arch_hash=rng.integers(0, 2, (2, nb)).astype(np.int32),
        port_conflict=rng.random(nb) < 0.2,
        extra_mask=rng.random(nb) < 0.95,
        quota_ok=(rng.random(nb) < 0.8) if rng.random() < 0.5
        else None)
    group = GroupInputs(
        k=np.int32(rng.integers(1, 40)),
        con_hash=con_hash, con_op=con_op, con_exp=con_exp,
        plat=plat,
        maxrep=np.int32(rng.choice([0, 0, 2, 4])),
        port_limited=np.bool_(rng.random() < 0.3))
    return nodes, group


def test_gang_fit_device_matches_host_oracle_fuzz():
    """Per-group route: (fit, fail_counts) bit-equal to the numpy
    oracle over random clusters — the contract the planner breaker's
    host demotion stands on."""
    for seed in range(12):
        rng = np.random.default_rng(seed)
        nb = int(rng.choice([64, 128]))
        nodes, group = _random_gang_inputs(rng, nb)
        fit_d, fc_d = gang_fit_jit(nodes, group)
        fit_h, fc_h = gang_mod.gang_fit_host(nodes, group)
        assert bool(fit_d) == fit_h, seed
        assert (np.asarray(fc_d) == fc_h).all(), seed


def test_gang_fit_fused_matches_host_oracle_fuzz():
    """Fused route: G gangs stacked on a leading axis, every verdict
    bit-equal to the per-gang oracle."""
    for seed in range(6):
        rng = np.random.default_rng(100 + seed)
        nb = 64
        rows = [_random_gang_inputs(rng, nb, L=2) for _ in range(3)]
        # quota presence must be uniform across the stack (the fused
        # caller buckets by it); strip it for the stacked run
        rows = [(n._replace(quota_ok=None), g) for n, g in rows]
        stacked_nodes = NodeInputs(*[
            None if f == "quota_ok"
            else np.stack([getattr(n, f) for n, _ in rows])
            for f in NodeInputs._fields])
        stacked_groups = GroupInputs(*[
            np.stack([getattr(g, f) for _, g in rows])
            for f in GroupInputs._fields])
        fits, fcs = gang_fit_fused_jit(stacked_nodes, stacked_groups)
        for i, (n, g) in enumerate(rows):
            fit_h, fc_h = gang_mod.gang_fit_host(n, g)
            assert bool(fits[i]) == fit_h, (seed, i)
            assert (np.asarray(fcs[i]) == fc_h).all(), (seed, i)


def test_gang_fit_boundary_exact_fit():
    """sum(cap) == k is feasible; one less is not — the f32 capacity
    comparison decides the boundary exactly (docstring contract)."""
    rng = np.random.default_rng(0)
    nodes, group = _random_gang_inputs(rng, 64)
    nodes = nodes._replace(
        valid=np.arange(64) < 4, ready=np.arange(64) < 4,
        res_ok=np.arange(64) < 4, extra_mask=np.ones(64, bool),
        port_conflict=np.zeros(64, bool),
        res_cap=np.where(np.arange(64) < 4, 3, 0).astype(np.int32),
        quota_ok=None)
    group = group._replace(
        con_op=np.full(group.con_op.shape, 2, np.int32),
        plat=np.full_like(group.plat, -1),
        maxrep=np.int32(0), port_limited=np.bool_(False))
    for k, want in ((12, True), (13, False)):
        g = group._replace(k=np.int32(k))
        assert bool(gang_fit_jit(nodes, g)[0]) is want
        assert gang_mod.gang_fit_host(nodes, g)[0] is want


# ---------------------------------------------------------------------------
# atomic admission: single commit, rollback, deterministic ordering
# ---------------------------------------------------------------------------

def _mk_store(n_nodes, services, node_cpu=4 * 10 ** 9, cluster=None):
    """services: (sid, priority, n_pending, n_running, gang_min,
    gang_id, depends_on, tenant) tuples; running tasks round-robin."""
    store = MemoryStore()
    if cluster is not None:
        store.update(lambda tx: tx.create(cluster))

    def mk(tx):
        for i in range(n_nodes):
            tx.create(Node(
                id=f"n{i:03d}",
                spec=NodeSpec(annotations=Annotations(name=f"n{i:03d}")),
                status=NodeStatus(state=NodeState.READY),
                description=NodeDescription(
                    hostname=f"n{i:03d}",
                    resources=Resources(nano_cpus=node_cpu,
                                        memory_bytes=16 * GB))))
        for (sid, prio, n_pending, n_running, gang_min, gang_id,
                depends_on, tenant) in services:
            placement = (Placement(gang=GangConfig(min_size=gang_min))
                         if gang_min else Placement())
            spec = TaskSpec(
                priority=prio, placement=placement, gang_id=gang_id,
                resources=ResourceRequirements(reservations=Resources(
                    nano_cpus=CPU, memory_bytes=GB)))
            ann = Annotations(
                name=sid,
                labels={TENANT_LABEL: tenant} if tenant else {})
            tx.create(Service(
                id=sid,
                spec=ServiceSpec(
                    annotations=ann, mode=ServiceMode.REPLICATED,
                    replicated=ReplicatedService(
                        replicas=n_pending + n_running),
                    task=spec, depends_on=list(depends_on)),
                spec_version=Version(index=1)))
            for s in range(n_running):
                tx.create(Task(
                    id=f"{sid}-r{s:03d}", service_id=sid, slot=s + 1,
                    desired_state=TaskState.RUNNING, spec=spec,
                    spec_version=Version(index=1),
                    service_annotations=ann,
                    node_id=f"n{s % n_nodes:03d}",
                    status=TaskStatus(state=TaskState.RUNNING,
                                      timestamp=now())))
            for s in range(n_pending):
                tx.create(Task(
                    id=f"{sid}-p{s:03d}", service_id=sid,
                    slot=n_running + s + 1,
                    desired_state=TaskState.RUNNING, spec=spec,
                    spec_version=Version(index=1),
                    service_annotations=ann,
                    status=TaskStatus(state=TaskState.PENDING,
                                      timestamp=now())))
    store.update(mk)
    return store


def _tick(store, planner=None):
    sched = Scheduler(store, batch_planner=planner)
    store.view(sched._setup_tasks_list)
    sched.tick()
    return sched


def test_gang_places_whole_unit_in_one_commit():
    from swarmkit_tpu.state.events import Event, commit_or
    # 3 nodes x 2 slots = 6; a 6-member gang fits exactly
    store = _mk_store(3, [("svc-g", 0, 6, 0, 6, "", (), "")])
    sub = store.queue.subscribe(commit_or(
        lambda ev: isinstance(ev, Event) and isinstance(ev.obj, Task)))
    sched = _tick(store)
    tasks = [t for t in store.view(lambda tx: tx.find(Task))]
    assert all(t.node_id and t.status.state == TaskState.ASSIGNED
               for t in tasks)
    # one transaction: every assignment event lands before a single
    # commit boundary — no commit interleaves a strict subset
    stream, assigned = [], 0
    ev = sub.poll()
    while ev is not None:
        if isinstance(ev, Event) and isinstance(ev.obj, Task) \
                and ev.obj.node_id:
            assigned += 1
            stream.append("assign")
        elif not isinstance(ev, Event):
            stream.append("commit")
        ev = sub.poll()
    assert assigned == 6
    first = stream.index("assign")
    last = len(stream) - 1 - stream[::-1].index("assign")
    assert "commit" not in stream[first:last], stream
    assert sched.gang.stats["gangs_admitted"] == 1
    assert sched.gang.stats["gang_tasks_placed"] == 6
    assert not sched.gang.blocked


def test_gang_rolls_back_entirely_on_shortfall():
    # 2 nodes x 2 slots = 4 < 6 members: nothing may commit, and the
    # scratch reservations must roll back (mirrors stay clean)
    store = _mk_store(2, [("svc-g", 0, 6, 0, 6, "", (), "")])
    sched = _tick(store)
    tasks = store.view(lambda tx: tx.find(Task))
    assert not any(t.node_id for t in tasks)
    errs = {t.status.err for t in tasks}
    assert errs == {'gang "svc-g" deferred: all-or-nothing placement '
                    'infeasible'}, errs
    assert "svc-g" in sched.gang.blocked
    assert sched.gang.stats["gangs_admitted"] == 0
    # node mirrors untouched: full capacity still available
    free = [info.available_resources.nano_cpus
            for info in sched.node_set.nodes.values()]
    assert free == [4 * 10 ** 9] * 2, free


def test_two_gangs_admit_in_deterministic_order():
    # capacity for ONE 6-gang; the key-ordered admission places
    # svc-a whole and defers svc-b whole — no interleaved livelock
    store = _mk_store(3, [("svc-a", 0, 6, 0, 6, "", (), ""),
                          ("svc-b", 0, 6, 0, 6, "", (), "")])
    _tick(store)
    tasks = store.view(lambda tx: tx.find(Task))
    a = [t for t in tasks if t.service_id == "svc-a"]
    b = [t for t in tasks if t.service_id == "svc-b"]
    assert all(t.node_id for t in a)
    assert not any(t.node_id for t in b)
    # priority outranks key order
    store2 = _mk_store(3, [("svc-a", 0, 6, 0, 6, "", (), ""),
                           ("svc-z", 5, 6, 0, 6, "", (), "")])
    _tick(store2)
    tasks2 = store2.view(lambda tx: tx.find(Task))
    assert all(t.node_id for t in tasks2 if t.service_id == "svc-z")
    assert not any(t.node_id for t in tasks2
                   if t.service_id == "svc-a")


def test_cross_service_gang_is_one_atomic_unit():
    # two 3-replica services share gang_id (min_size 6); capacity 4
    # defers BOTH services entirely
    svcs = [("svc-h1", 0, 3, 0, 6, "ring", (), ""),
            ("svc-h2", 0, 3, 0, 6, "ring", (), "")]
    store = _mk_store(2, svcs)
    _tick(store)
    assert not any(t.node_id
                   for t in store.view(lambda tx: tx.find(Task)))
    # with capacity they admit together
    store2 = _mk_store(3, svcs)
    _tick(store2)
    assert all(t.node_id and t.status.state == TaskState.ASSIGNED
               for t in store2.view(lambda tx: tx.find(Task)))


def test_gangs_and_a_gated_stage_through_the_device_planner():
    """Gang admission with the device planner behind it: every gang is
    judged by ``gang_fit`` on the device, per group and fused (none by
    the host oracle), admitted whole with no deferral, the downstream
    stage is held by the DAG gate, and the same tick again, warm,
    compiles nothing."""
    from swarmkit_tpu.ops import TPUPlanner
    from test_scheduler import cold_then_warm
    svcs = [("svc-a", 0, 6, 0, 6, "", (), ""),
            ("svc-b", 0, 6, 0, 6, "", (), ""),
            ("svc-c", 0, 4, 0, 4, "", (), ""),
            ("svc-h1", 0, 3, 0, 6, "ring", (), ""),
            ("svc-h2", 0, 3, 0, 6, "ring", (), ""),
            ("stage-b", 0, 2, 0, 0, "", ("svc-a",), "")]
    def tick():
        planner = TPUPlanner()
        planner.enable_small_group_routing = False
        store = _mk_store(12, svcs)
        sched = _tick(store, planner)
        tasks = store.view(lambda tx: tx.find(Task))
        gang = [t for t in tasks if t.service_id != "stage-b"]
        assert all(t.node_id and t.status.state == TaskState.ASSIGNED
                   for t in gang)
        assert sched.gang.stats["gangs_admitted"] == 4
        assert sched.gang.stats["gangs_deferred"] == 0
        assert not sched.gang.blocked
        st = planner.stats
        # three single-service gangs, one verdict each; the two member
        # groups of the cross-service unit in one fused call
        assert st.get("gang_fit_device", 0) == 3, st
        assert st.get("gang_fit_fused", 0) == 2, st
        assert not st.get("gang_fit_host", 0), st
        assert not st.get("gang_device_error", 0), st
        assert {t.status.err for t in tasks
                if t.service_id == "stage-b"} == \
            {"awaiting upstream pipeline stage"}
    cold_then_warm(tick)


def test_incomplete_gang_waits_for_materialization():
    # only 4 of min_size 6 pending (orchestrator still materializing):
    # defer with the incomplete stamp, not a placement attempt
    store = _mk_store(3, [("svc-g", 0, 4, 0, 6, "", (), "")])
    sched = _tick(store)
    tasks = store.view(lambda tx: tx.find(Task))
    assert not any(t.node_id for t in tasks)
    errs = {t.status.err for t in tasks}
    assert errs == {'gang "svc-g" incomplete (4/6 members pending)'}
    assert "svc-g" not in sched.gang.blocked


def test_gang_over_quota_defers_atomically_and_uncharges():
    cluster = Cluster(
        id="cluster-default",
        spec=ClusterSpec(
            annotations=Annotations(name="default"),
            tenants={"lo": TenantQuota(nano_cpus=2 * CPU)}))
    store = _mk_store(4, [("svc-g", 0, 4, 0, 4, "", (), "lo")],
                      cluster=cluster)
    sched = _tick(store)
    tasks = store.view(lambda tx: tx.find(Task))
    assert not any(t.node_id for t in tasks)
    errs = {t.status.err for t in tasks}
    assert errs == {'gang "svc-g" over tenant quota (tenant "lo")'}
    # the all-or-nothing charge rolled back: the ledger shows zero use
    assert sched.quota.used.get("lo", [0, 0, 0])[2] == 0


# ---------------------------------------------------------------------------
# preemption entitlement (ROADMAP item 7 residual)
# ---------------------------------------------------------------------------

def test_starved_gang_acquires_victims_under_tenant_quota():
    """A priority-0 gang blocked on capacity held by strictly-lower
    work must enter the preemption pass (the old trigger required
    priority > 0 and starved it forever) — evict-only, then place
    atomically once the capacity frees."""
    cluster = Cluster(
        id="cluster-default",
        spec=ClusterSpec(
            annotations=Annotations(name="default"),
            tenants={"lo": TenantQuota(nano_cpus=8 * CPU)}))
    store = _mk_store(
        3, [("svc-victim", -5, 0, 6, 0, "", (), ""),
            ("svc-g", 0, 4, 0, 4, "", (), "lo")],
        cluster=cluster)
    sched = Scheduler(store)
    store.view(sched._setup_tasks_list)
    sched.tick()
    tasks = store.view(lambda tx: tx.find(Task))
    gang_tasks = [t for t in tasks if t.service_id == "svc-g"]
    victims = [t for t in tasks
               if "swarm.preempted.at" in t.annotations.labels]
    # tick 1: capacity-blocked gang is entitled — victims evicted,
    # but the gang itself did NOT place (evict-only keeps atomicity)
    assert "svc-g" in sched.gang.blocked
    assert len(victims) == 4
    assert all(v.desired_state == TaskState.SHUTDOWN for v in victims)
    assert not any(t.node_id for t in gang_tasks)

    # agents shut the victims down; the next tick places the gang whole
    def down(tx):
        for v in victims:
            cur = tx.get(Task, v.id).copy()
            cur.status = TaskStatus(state=TaskState.SHUTDOWN,
                                    timestamp=now())
            tx.update(cur)
    store.update(down)
    # production drains these watch events on the scheduler thread;
    # the threadless harness feeds them through the same handler
    for v in store.view(lambda tx: [tx.get(Task, v.id)
                                    for v in victims]):
        sched._update_task(v)
    sched.tick()
    gang_tasks = [t for t in store.view(lambda tx: tx.find(Task))
                  if t.service_id == "svc-g"]
    assert all(t.node_id and t.status.state == TaskState.ASSIGNED
               for t in gang_tasks)
    assert sched.gang.stats["gangs_admitted"] == 1


def test_aged_gang_is_preempt_entitled(monkeypatch):
    monkeypatch.setenv("SWARM_PREEMPT_AGE", "5")
    store = _mk_store(2, [("svc-g", 0, 6, 0, 6, "", (), "")])
    sched = _tick(store)
    t0 = next(t for t in store.view(lambda tx: tx.find(Task)))
    # capacity-blocked: entitled through the blocked set
    assert gang_mod.preempt_entitled(sched, t0)
    # age path: a unit pending past SWARM_PREEMPT_AGE stays entitled
    # even once the capacity-blocked marker is gone
    sched.gang.blocked.clear()
    sched.gang.first_pending["svc-g"] = now() - 6.0
    assert gang_mod.preempt_entitled(sched, t0)
    sched.gang.first_pending["svc-g"] = now() - 1.0
    assert not gang_mod.preempt_entitled(sched, t0)


# ---------------------------------------------------------------------------
# the scheduler's pipeline gate
# ---------------------------------------------------------------------------

def test_pipeline_gate_defers_until_released():
    store = _mk_store(3, [("stage-b", 0, 2, 0, 0, "", ("stage-a",),
                           "")])
    _tick(store)
    tasks = store.view(lambda tx: tx.find(Task))
    assert not any(t.node_id for t in tasks)
    assert {t.status.err for t in tasks} == \
        {"awaiting upstream pipeline stage"}

    # the supervisor's released verdict opens the gate
    def rel(tx):
        cur = tx.get(Service, "stage-b").copy()
        cur.pipeline_status = PipelineStatus(state="released")
        tx.update(cur)
    store.update(rel)
    _tick(store)
    assert all(t.node_id
               for t in store.view(lambda tx: tx.find(Task)))


def test_pipeline_gate_reports_halt_reason():
    store = _mk_store(3, [("stage-b", 0, 2, 0, 0, "", ("stage-a",),
                           "")])

    def halt(tx):
        cur = tx.get(Service, "stage-b").copy()
        cur.pipeline_status = PipelineStatus(
            state="halted", reason='upstream "stage-a" halted')
        tx.update(cur)
    store.update(halt)
    _tick(store)
    errs = {t.status.err
            for t in store.view(lambda tx: tx.find(Task))}
    assert errs == {'pipeline halted (upstream "stage-a" halted)'}


# ---------------------------------------------------------------------------
# PipelineSupervisor: release bars, stickiness, failure cascades
# ---------------------------------------------------------------------------

def _mk_service(store, sid, mode=ServiceMode.REPLICATED, replicas=2,
                depends_on=(), on_upstream_failure="",
                total_completions=0):
    spec = ServiceSpec(
        annotations=Annotations(name=sid), mode=mode,
        replicated=(ReplicatedService(replicas=replicas)
                    if mode == ServiceMode.REPLICATED else None),
        replicated_job=(ReplicatedJob(
            total_completions=total_completions)
            if mode == ServiceMode.REPLICATED_JOB else None),
        task=TaskSpec(),
        depends_on=list(depends_on),
        on_upstream_failure=on_upstream_failure)
    store.update(lambda tx: tx.create(Service(
        id=sid, spec=spec, spec_version=Version(index=1))))


def _set_tasks(store, sid, states):
    def cb(tx):
        for t in tx.find(Task):
            if t.service_id == sid:
                tx.delete(Task, t.id)
        for i, st in enumerate(states):
            tx.create(Task(
                id=f"{sid}-t{i:03d}-{new_id()[:6]}", service_id=sid,
                slot=i + 1, desired_state=TaskState.RUNNING,
                spec=TaskSpec(), spec_version=Version(index=1),
                node_id="n000",
                status=TaskStatus(state=st, timestamp=now())))
    store.update(cb)


def _status(store, sid):
    return store.view(lambda tx: tx.get(Service, sid)).pipeline_status


def test_supervisor_releases_when_upstream_running_and_sticky():
    store = MemoryStore()
    _mk_service(store, "a", replicas=2)
    _mk_service(store, "b", depends_on=("a",))
    sup = PipelineSupervisor(store, start_worker=False)
    sup.drive()
    assert _status(store, "b") is None    # 0/2 upstream running
    _set_tasks(store, "a", [TaskState.RUNNING])
    sup.drive()
    assert _status(store, "b") is None    # 1/2: bar not met
    _set_tasks(store, "a", [TaskState.RUNNING, TaskState.RUNNING])
    sup.drive()
    assert _status(store, "b").state == "released"
    # sticky: upstream churn never re-gates
    _set_tasks(store, "a", [])
    sup.drive()
    assert _status(store, "b").state == "released"
    assert sup.stats["released"] == 1


def test_supervisor_job_upstream_releases_on_completions():
    store = MemoryStore()
    _mk_service(store, "job", mode=ServiceMode.REPLICATED_JOB,
                total_completions=2)
    _mk_service(store, "b", depends_on=("job",))
    sup = PipelineSupervisor(store, start_worker=False)
    _set_tasks(store, "job", [TaskState.COMPLETE, TaskState.RUNNING])
    sup.drive()
    assert _status(store, "b") is None
    _set_tasks(store, "job", [TaskState.COMPLETE, TaskState.COMPLETE])
    sup.drive()
    assert _status(store, "b").state == "released"


def test_supervisor_poison_halts_and_rolls_back_downstream():
    store = MemoryStore()
    _mk_service(store, "a", replicas=2)
    _mk_service(store, "b", depends_on=("a",),
                on_upstream_failure="halt")
    _mk_service(store, "c", replicas=3, depends_on=("a",),
                on_upstream_failure="rollback")
    sup = PipelineSupervisor(store, start_worker=False)
    # three distinct failed task ids push "a" over the threshold
    _set_tasks(store, "a", [TaskState.FAILED] * POISON_FAILURES)
    sup.drive()
    st_b = _status(store, "b")
    assert st_b.state == "halted"
    assert st_b.reason == (f'upstream "a" poisoned '
                           f'({POISON_FAILURES} task failures)')
    st_c = _status(store, "c")
    assert st_c.state == "halted"
    svc_c = store.view(lambda tx: tx.get(Service, "c"))
    assert svc_c.spec.replicated.replicas == 0    # rolled back
    assert sup.stats["rollbacks"] == 1
    # halt is sticky even after the upstream heals
    _set_tasks(store, "a", [TaskState.RUNNING, TaskState.RUNNING])
    sup.drive()
    assert _status(store, "b").state == "halted"


def test_supervisor_poison_count_survives_leader_crash():
    """ISSUE 16 residual: failure observations replicate via
    ``PipelineStatus.failed_ids``.  A leader crashing at 2/3
    observations must NOT reset the poison count — the successor's
    supervisor (fresh ``_failed_seen``) trips the threshold on its
    first new observation."""
    store = MemoryStore()
    _mk_service(store, "a", replicas=2)
    _mk_service(store, "b", depends_on=("a",))
    sup1 = PipelineSupervisor(store, start_worker=False)
    # 2/3: below the threshold, but the observations must commit
    _set_tasks(store, "a", [TaskState.FAILED, TaskState.FAILED])
    sup1.drive()
    assert _status(store, "b") is None or \
        _status(store, "b").state != "halted"
    st_a = _status(store, "a")
    assert st_a is not None and len(st_a.failed_ids) == 2
    # leader crash: the successor's supervisor has no local memory and
    # the old tasks are gone (reaped) — only the replicated row remains
    sup2 = PipelineSupervisor(store, start_worker=False)
    _set_tasks(store, "a", [TaskState.FAILED])    # 3rd distinct id
    sup2.drive()
    st_b = _status(store, "b")
    assert st_b is not None and st_b.state == "halted"
    assert "poisoned" in st_b.reason
    # all three observations are on the replicated row now
    assert len(_status(store, "a").failed_ids) == POISON_FAILURES


def test_supervisor_verdict_preserves_failed_ids():
    """Release/halt verdict writes must carry ``failed_ids`` forward —
    a stage that is both a downstream (gets verdicts) and an upstream
    (accrues observations) must not lose its count to a verdict."""
    store = MemoryStore()
    _mk_service(store, "a", replicas=1)
    _mk_service(store, "b", replicas=2, depends_on=("a",))
    _mk_service(store, "c", depends_on=("b",))
    sup = PipelineSupervisor(store, start_worker=False)
    # b accrues one failure observation (below threshold), then its
    # upstream readies and b gets a released verdict
    _set_tasks(store, "b", [TaskState.FAILED])
    sup.drive()
    assert len(_status(store, "b").failed_ids) == 1
    _set_tasks(store, "a", [TaskState.RUNNING])
    sup.drive()
    st_b = _status(store, "b")
    assert st_b.state == "released"
    assert len(st_b.failed_ids) == 1


def test_supervisor_halted_upstream_cascades():
    store = MemoryStore()
    _mk_service(store, "a", replicas=1)
    _mk_service(store, "b", depends_on=("a",))
    _mk_service(store, "d", depends_on=("b",))
    sup = PipelineSupervisor(store, start_worker=False)

    def halt_b(tx):
        cur = tx.get(Service, "b").copy()
        cur.pipeline_status = PipelineStatus(state="halted",
                                             reason="injected")
        tx.update(cur)
    store.update(halt_b)
    sup.drive()
    st = _status(store, "d")
    assert st.state == "halted"
    assert st.reason == 'upstream "b" halted'


def test_supervisor_threadless_reraises_store_failures(monkeypatch):
    store = MemoryStore()
    _mk_service(store, "a", replicas=0)
    _mk_service(store, "b", depends_on=("a",))
    sup = PipelineSupervisor(store, start_worker=False)

    def boom(cb):
        raise RuntimeError("deposed")
    monkeypatch.setattr(store, "update", boom)
    with pytest.raises(RuntimeError):
        sup.drive()


# ---------------------------------------------------------------------------
# non-gang byte-identity: the subsystem is a pure no-op without opt-in
# ---------------------------------------------------------------------------

def _placements(store):
    return sorted(
        (t.id, t.node_id or "", int(t.status.state),
         t.status.err or "")
        for t in store.view(lambda tx: tx.find(Task)))


def test_non_gang_workload_byte_identical(monkeypatch):
    """A workload with no gang/pipeline opt-in never reaches
    admit_gangs, and its placements are byte-identical to a run where
    the gang path is poisoned — the extraction is a pure no-op."""
    svcs = [("svc-a", 0, 5, 0, 0, "", (), ""),
            ("svc-b", 3, 4, 1, 0, "", (), "")]
    store1 = _mk_store(4, svcs)
    _tick(store1)

    def never(*a, **kw):
        raise AssertionError("admit_gangs reached without gang tasks")
    monkeypatch.setattr(gang_mod, "admit_gangs", never)
    store2 = _mk_store(4, svcs)
    _tick(store2)
    assert _placements(store1) == _placements(store2)


# ---------------------------------------------------------------------------
# checker sensitivity: the two new invariants must FIRE when their
# enforcement seam is off (house rule since PR 1)
# ---------------------------------------------------------------------------

def _gang_mini(seed, gang=12, duration=50.0):
    """Capacity-starved gang sim: 3 of 5 workers die (8 slots left), a
    12-member gang arrives after node-down detection has settled —
    atomic admission must hold it back whole until the heal at
    finish.  (Arriving before detection would let the first commit
    place all 12, 4 of them onto dying nodes — a full commit, which
    is not the strict-subset shape the seam-off test needs.)"""
    sim = Sim(seed=seed, n_managers=3, n_agents=5,
              net_config=NetConfig(), raft_cp=True)
    with sim:
        eng = sim.engine
        cp = sim.cp
        sim.start_raft_workload(interval=0.8)
        a = cp.agents
        eng.at(eng.clock.start + 4.0, "node death w0", a[0].crash)
        eng.at(eng.clock.start + 5.0, "node death w1", a[1].crash)
        eng.at(eng.clock.start + 6.0, "node death w2", a[2].crash)
        eng.at(eng.clock.start + 20.0, "gang arrives",
               lambda: cp.add_service("svc-gang", gang, gang_min=gang,
                                      nano_cpus=CPU))
        sim.run(duration)
        sim.finish(grace=20.0)
    return sim


def test_sensitivity_gang_atomicity_fires_when_seam_off(monkeypatch):
    """Disable atomic enforcement: the shortfall tick commits a strict
    subset and the left-behind members stay pending past the checker's
    grace — gang-atomicity must fire."""
    monkeypatch.setattr(gang_mod, "ATOMIC_ENFORCED", False)
    sim = _gang_mini(21)
    assert any("gang-atomicity" in v
               for v in sim.violations.items), sim.violations.items


def _pipeline_mini(seed, duration=40.0):
    """Unplaceable upstream (no node fits its reservation) + placeable
    downstream: with the gate enforced the downstream never runs; with
    the seam off it runs before its upstream ever did."""
    sim = Sim(seed=seed, n_managers=3, n_agents=5,
              net_config=NetConfig(), raft_cp=True)
    with sim:
        eng = sim.engine
        cp = sim.cp
        sim.start_raft_workload(interval=0.8)
        eng.at(eng.clock.start + 4.0, "upstream (unplaceable)",
               lambda: cp.add_service("svc-up", 2,
                                      nano_cpus=100 * CPU))
        eng.at(eng.clock.start + 6.0, "downstream",
               lambda: cp.add_service("svc-down", 2, nano_cpus=CPU,
                                      depends_on=["svc-up"]))
        sim.run(duration)
        sim.finish(grace=15.0)
    return sim


def test_sensitivity_pipeline_order_fires_when_gate_off(monkeypatch):
    monkeypatch.setattr(gang_mod, "GATE_ENFORCED", False)
    sim = _pipeline_mini(22)
    assert any("pipeline-order" in v
               for v in sim.violations.items), sim.violations.items


def test_gang_mini_green_with_enforcement_on():
    """The sensitivity harness itself is green with the seams on —
    the tests above fail for the injected reason, nothing else."""
    sim = _gang_mini(23)
    assert not sim.violations.items, sim.violations.items


# ---------------------------------------------------------------------------
# scenarios: green runs + registry wiring (slow sweep lives in tier 2)
# ---------------------------------------------------------------------------

def test_gang_scenarios_registered():
    from scripts import chaos_sweep
    from swarmkit_tpu.sim.scenario import (
        FUZZ_POOL, GANG_SCENARIOS, SCENARIOS,
    )
    assert GANG_SCENARIOS == ("gang-deadlock", "pipeline-chaos")
    for name in GANG_SCENARIOS:
        assert name in SCENARIOS
        assert name in FUZZ_POOL
    assert chaos_sweep.SUITES["gang"] == GANG_SCENARIOS
    assert set(GANG_SCENARIOS) <= set(chaos_sweep.SUITES["default"])
    for name in GANG_SCENARIOS:
        assert name in chaos_sweep.REQUIRED_CELLS


def test_gang_deadlock_scenario_green():
    from swarmkit_tpu.sim.scenario import run_scenario
    r = run_scenario("gang-deadlock", seed=0)
    assert r.ok, r.violations


def test_pipeline_chaos_scenario_green():
    from swarmkit_tpu.sim.scenario import run_scenario
    r = run_scenario("pipeline-chaos", seed=3)
    assert r.ok, r.violations


@pytest.mark.slow
def test_gang_scenarios_seed_sweep():
    """20-seed slow sweep: both gang scenarios hold their invariants
    and expectations across the fuzzed fault schedule."""
    from swarmkit_tpu.sim.scenario import run_scenario
    for name in ("gang-deadlock", "pipeline-chaos"):
        for seed in range(10):
            r = run_scenario(name, seed=seed)
            assert r.ok, (name, seed, r.violations)
