"""Host scheduler oracle tests.

Pattern mirrors the reference's scheduler_test.go: real MemoryStore (nil
proposer), scheduler running in a thread, nodes/tasks injected through store
transactions, assertions via watch events.
"""

import time

import pytest

from swarmkit_tpu.models import (
    Annotations, Endpoint, EndpointSpec, EngineDescription, Node,
    NodeAvailability, NodeDescription, NodeSpec, NodeState, NodeStatus,
    Placement, PlacementPreference, Platform, PortConfig, PublishMode,
    ReplicatedService, Resources, ResourceRequirements, Service, ServiceMode,
    ServiceSpec, SpreadOver, Task, TaskSpec, TaskState, TaskStatus, Version,
)
from swarmkit_tpu.models.types import PortProtocol
from swarmkit_tpu.scheduler import Scheduler, node_matches, parse
from swarmkit_tpu.scheduler.constraint import InvalidConstraint
from swarmkit_tpu.state import ByService, MemoryStore, match
from swarmkit_tpu.utils import new_id


def poll(fn, timeout=5.0, interval=0.01):
    deadline = time.time() + timeout
    while time.time() < deadline:
        result = fn()
        if result:
            return result
        time.sleep(interval)
    raise AssertionError("poll timed out")


def make_ready_node(name, cpus=4, mem=32 << 30, labels=None,
                    engine_labels=None, os="linux", arch="amd64",
                    availability=NodeAvailability.ACTIVE):
    n = Node(
        id=new_id(),
        spec=NodeSpec(annotations=Annotations(name=name),
                      availability=availability),
        status=NodeStatus(state=NodeState.READY),
        description=NodeDescription(
            hostname=name,
            platform=Platform(architecture=arch, os=os),
            resources=Resources(nano_cpus=cpus * 10**9, memory_bytes=mem),
            engine=EngineDescription(labels=engine_labels or {}),
        ),
    )
    if labels:
        n.spec.annotations.labels.update(labels)
    return n


def make_service_with_tasks(n_tasks, reservations=None, constraints=None,
                            prefs=None, max_replicas=0, ports=None,
                            platforms=None):
    svc = Service(
        id=new_id(),
        spec=ServiceSpec(
            annotations=Annotations(name="svc-" + new_id()[:6]),
            mode=ServiceMode.REPLICATED,
            replicated=ReplicatedService(replicas=n_tasks),
        ),
        spec_version=Version(index=1),
    )
    placement = Placement(constraints=constraints or [],
                          preferences=prefs or [],
                          platforms=platforms or [],
                          max_replicas=max_replicas)
    tasks = []
    for slot in range(1, n_tasks + 1):
        t = Task(
            id=new_id(), service_id=svc.id, slot=slot,
            desired_state=TaskState.RUNNING,
            spec=TaskSpec(
                placement=placement,
                resources=ResourceRequirements(reservations=reservations),
            ),
            spec_version=Version(index=1),
            status=TaskStatus(state=TaskState.PENDING),
        )
        if ports:
            t.endpoint = Endpoint(spec=EndpointSpec(ports=list(ports)),
                                  ports=list(ports))
        tasks.append(t)
    return svc, tasks


def tick_one_service(planner, n_nodes=64, n_tasks=256):
    """One synchronous ``Scheduler.tick()`` of one ``n_tasks`` service
    through ``planner`` on ``n_nodes`` fresh 64-CPU nodes; returns the
    scheduler.  Shared by the tests that need a device tick and no more
    (``test_obs.py``, ``test_flightrec.py``)."""
    store = MemoryStore()
    nodes = [make_ready_node(f"node-{i:03d}", cpus=64, mem=256 << 30)
             for i in range(n_nodes)]
    svc, tasks = make_service_with_tasks(
        n_tasks, reservations=Resources(nano_cpus=10 ** 8,
                                        memory_bytes=64 << 20))
    store.update(lambda tx: [tx.create(obj)
                             for obj in (*nodes, svc, *tasks)])
    sched = Scheduler(store, batch_planner=planner)
    store.view(sched._setup_tasks_list)
    assert sched.tick() == n_tasks
    return sched


def cold_then_warm(tick):
    """Run ``tick()`` twice: whatever it compiles is the first run's to
    compile, and the second, warm, adds no ``swarm_planner_compiles``."""
    from swarmkit_tpu.utils.metrics import registry
    tick()
    before = registry.counters_snapshot("swarm_planner_compiles")
    tick()
    assert registry.counters_snapshot("swarm_planner_compiles") == before


@pytest.fixture
def cluster():
    store = MemoryStore()
    sched = Scheduler(store)
    sched.start()
    yield store, sched
    sched.stop()


def wait_assigned(store, service_id, count, timeout=5.0):
    def check():
        tasks = store.view(lambda tx: tx.find(Task, ByService(service_id)))
        assigned = [t for t in tasks
                    if t.status.state == TaskState.ASSIGNED and t.node_id]
        return assigned if len(assigned) == count else None
    return poll(check, timeout=timeout)


def test_basic_assignment(cluster):
    store, sched = cluster
    nodes = [make_ready_node(f"n{i}") for i in range(3)]
    svc, tasks = make_service_with_tasks(3)

    def setup(tx):
        for n in nodes:
            tx.create(n)
        tx.create(svc)
        for t in tasks:
            tx.create(t)

    store.update(setup)
    assigned = wait_assigned(store, svc.id, 3)
    # spread: one task per node
    assert len({t.node_id for t in assigned}) == 3
    for t in assigned:
        assert t.status.message == "scheduler assigned task to node"


def test_spread_balances_totals(cluster):
    store, sched = cluster
    nodes = [make_ready_node(f"n{i}") for i in range(4)]
    store.update(lambda tx: [tx.create(n) for n in nodes])

    svc1, tasks1 = make_service_with_tasks(8)
    store.update(lambda tx: (tx.create(svc1),
                             [tx.create(t) for t in tasks1]))
    a1 = wait_assigned(store, svc1.id, 8)
    by_node = {}
    for t in a1:
        by_node[t.node_id] = by_node.get(t.node_id, 0) + 1
    assert all(v == 2 for v in by_node.values())


def test_resource_filter_and_explain(cluster):
    store, sched = cluster
    small = make_ready_node("small", cpus=1, mem=1 << 30)
    store.update(lambda tx: tx.create(small))

    svc, tasks = make_service_with_tasks(
        2, reservations=Resources(nano_cpus=10**9, memory_bytes=512 << 20))
    store.update(lambda tx: (tx.create(svc),
                             [tx.create(t) for t in tasks]))

    # only one task fits (1 CPU node, each task wants 1 CPU)
    def check():
        ts = store.view(lambda tx: tx.find(Task, ByService(svc.id)))
        assigned = [t for t in ts if t.status.state == TaskState.ASSIGNED]
        unassigned = [t for t in ts if not t.node_id and t.status.err]
        return (assigned, unassigned) if assigned and unassigned else None

    assigned, unassigned = poll(check)
    assert len(assigned) == 1
    assert "insufficient resources" in unassigned[0].status.err
    assert unassigned[0].status.err.startswith("no suitable node")

    # free resources -> pending task gets scheduled
    t = assigned[0]
    t2 = store.view(lambda tx: tx.get(Task, t.id)).copy()
    t2.status.state = TaskState.FAILED
    t2.desired_state = TaskState.SHUTDOWN
    store.update(lambda tx: tx.update(t2))
    poll(lambda: any(
        t.status.state == TaskState.ASSIGNED and t.id != assigned[0].id
        for t in store.view(lambda tx: tx.find(Task, ByService(svc.id)))))


def test_constraint_filter(cluster):
    store, sched = cluster
    n_ssd = make_ready_node("ssd-node", labels={"disk": "ssd"})
    n_hdd = make_ready_node("hdd-node", labels={"disk": "hdd"})
    store.update(lambda tx: (tx.create(n_ssd), tx.create(n_hdd)))

    svc, tasks = make_service_with_tasks(
        2, constraints=["node.labels.disk == ssd"])
    store.update(lambda tx: (tx.create(svc),
                             [tx.create(t) for t in tasks]))
    assigned = wait_assigned(store, svc.id, 2)
    assert all(t.node_id == n_ssd.id for t in assigned)


def test_platform_filter(cluster):
    store, sched = cluster
    linux = make_ready_node("linux-n", os="linux", arch="amd64")
    windows = make_ready_node("win-n", os="windows", arch="amd64")
    store.update(lambda tx: (tx.create(linux), tx.create(windows)))

    svc, tasks = make_service_with_tasks(
        2, platforms=[Platform(architecture="x86_64", os="linux")])
    store.update(lambda tx: (tx.create(svc),
                             [tx.create(t) for t in tasks]))
    assigned = wait_assigned(store, svc.id, 2)
    assert all(t.node_id == linux.id for t in assigned)


def test_host_port_conflict(cluster):
    store, sched = cluster
    nodes = [make_ready_node(f"n{i}") for i in range(2)]
    store.update(lambda tx: [tx.create(n) for n in nodes])

    port = PortConfig(protocol=PortProtocol.TCP, target_port=80,
                      published_port=8080, publish_mode=PublishMode.HOST)
    svc, tasks = make_service_with_tasks(3, ports=[port])
    store.update(lambda tx: (tx.create(svc),
                             [tx.create(t) for t in tasks]))

    def check():
        ts = store.view(lambda tx: tx.find(Task, ByService(svc.id)))
        assigned = [t for t in ts if t.status.state == TaskState.ASSIGNED]
        blocked = [t for t in ts if not t.node_id and t.status.err]
        return (assigned, blocked) if len(assigned) == 2 and blocked else None

    assigned, blocked = poll(check)
    assert {t.node_id for t in assigned} == {nodes[0].id, nodes[1].id}
    assert "host-mode port already in use" in blocked[0].status.err


def test_max_replicas_filter(cluster):
    store, sched = cluster
    nodes = [make_ready_node(f"n{i}") for i in range(2)]
    store.update(lambda tx: [tx.create(n) for n in nodes])

    svc, tasks = make_service_with_tasks(4, max_replicas=1)
    store.update(lambda tx: (tx.create(svc),
                             [tx.create(t) for t in tasks]))

    def check():
        ts = store.view(lambda tx: tx.find(Task, ByService(svc.id)))
        assigned = [t for t in ts if t.status.state == TaskState.ASSIGNED]
        blocked = [t for t in ts if not t.node_id and t.status.err]
        return (assigned, blocked) \
            if len(assigned) == 2 and len(blocked) == 2 else None

    assigned, blocked = poll(check)
    assert len({t.node_id for t in assigned}) == 2
    assert "max replicas per node limit exceed" in blocked[0].status.err


def test_drained_node_not_used(cluster):
    store, sched = cluster
    active = make_ready_node("active")
    drained = make_ready_node("drained",
                              availability=NodeAvailability.DRAIN)
    store.update(lambda tx: (tx.create(active), tx.create(drained)))

    svc, tasks = make_service_with_tasks(2)
    store.update(lambda tx: (tx.create(svc),
                             [tx.create(t) for t in tasks]))
    assigned = wait_assigned(store, svc.id, 2)
    assert all(t.node_id == active.id for t in assigned)


def test_preassigned_task_validation(cluster):
    store, sched = cluster
    node = make_ready_node("n0", cpus=2)
    store.update(lambda tx: tx.create(node))

    svc, tasks = make_service_with_tasks(
        1, reservations=Resources(nano_cpus=10**9))
    # preassign (global-service style): node_id already set
    tasks[0].node_id = node.id
    store.update(lambda tx: (tx.create(svc), tx.create(tasks[0])))

    def check():
        t = store.view(lambda tx: tx.get(Task, tasks[0].id))
        return t if t.status.state == TaskState.ASSIGNED else None

    t = poll(check)
    assert "preassigned" in t.status.message


def test_preassigned_task_insufficient_resources(cluster):
    store, sched = cluster
    node = make_ready_node("n0", cpus=1)
    store.update(lambda tx: tx.create(node))

    svc, tasks = make_service_with_tasks(
        1, reservations=Resources(nano_cpus=8 * 10**9))
    tasks[0].node_id = node.id
    store.update(lambda tx: (tx.create(svc), tx.create(tasks[0])))

    def check():
        t = store.view(lambda tx: tx.get(Task, tasks[0].id))
        return t if t.status.err else None

    t = poll(check)
    assert "insufficient resources" in t.status.err
    assert t.status.state == TaskState.PENDING


def test_spread_preference_tree(cluster):
    store, sched = cluster
    nodes = []
    for dc in ("east", "west"):
        for i in range(2):
            nodes.append(make_ready_node(f"{dc}-{i}",
                                         labels={"datacenter": dc}))
    store.update(lambda tx: [tx.create(n) for n in nodes])

    prefs = [PlacementPreference(
        spread=SpreadOver(spread_descriptor="node.labels.datacenter"))]
    svc, tasks = make_service_with_tasks(8, prefs=prefs)
    store.update(lambda tx: (tx.create(svc),
                             [tx.create(t) for t in tasks]))
    assigned = wait_assigned(store, svc.id, 8)
    per_dc = {"east": 0, "west": 0}
    node_by_id = {n.id: n for n in nodes}
    for t in assigned:
        per_dc[node_by_id[t.node_id].spec.annotations.labels["datacenter"]] += 1
    assert per_dc["east"] == 4 and per_dc["west"] == 4


def test_scheduler_picks_emptier_node_on_join(cluster):
    store, sched = cluster
    n0 = make_ready_node("n0")
    store.update(lambda tx: tx.create(n0))
    svc, tasks = make_service_with_tasks(4)
    store.update(lambda tx: (tx.create(svc),
                             [tx.create(t) for t in tasks]))
    wait_assigned(store, svc.id, 4)

    # new empty node joins; the first task of a new service lands there,
    # the second spreads to the other node (service count dominates total
    # count in the comparator — reference scheduler.go:708-735)
    n1 = make_ready_node("n1")
    store.update(lambda tx: tx.create(n1))
    svc2, tasks2 = make_service_with_tasks(2)
    store.update(lambda tx: (tx.create(svc2),
                             [tx.create(t) for t in tasks2]))
    assigned = wait_assigned(store, svc2.id, 2)
    assert {t.node_id for t in assigned} == {n0.id, n1.id}
    # a single-task service does prefer the emptier node outright
    svc3, tasks3 = make_service_with_tasks(1)
    store.update(lambda tx: (tx.create(svc3),
                             [tx.create(t) for t in tasks3]))
    assigned3 = wait_assigned(store, svc3.id, 1)
    assert assigned3[0].node_id == n1.id


# ---------------------------------------------------------------- constraint

def test_constraint_parse_and_match():
    cs = parse(["node.labels.disk==ssd", "node.role != manager"])
    assert cs[0].key == "node.labels.disk"
    assert cs[0].match("SSD")
    assert not cs[0].match("hdd")
    assert cs[1].match("worker")  # != manager

    with pytest.raises(InvalidConstraint):
        parse(["no-operator-here"])
    with pytest.raises(InvalidConstraint):
        parse(["~bad~ == x"])


def test_constraint_node_matches_ip_and_platform():
    n = make_ready_node("host1")
    n.status.addr = "10.0.8.4"
    assert node_matches(parse(["node.ip == 10.0.8.0/24"]), n)
    assert not node_matches(parse(["node.ip != 10.0.8.0/24"]), n)
    assert node_matches(parse(["node.ip == 10.0.8.4"]), n)
    assert node_matches(parse(["node.platform.os == linux"]), n)
    assert node_matches(parse(["node.hostname == host1"]), n)
    assert not node_matches(parse(["node.hostname != host1"]), n)
    assert node_matches(parse(["unknown.key != whatever"]), n) is False


def test_concurrent_update_not_overwritten_by_stale_decision():
    """A write that lands between the scheduler's mirror and its commit must
    fail the decision via SequenceConflict, not be overwritten (reference:
    scheduler.go:607-611 relies on UpdateTask's version check)."""
    store = MemoryStore()
    node = make_ready_node("n1")
    svc, tasks = make_service_with_tasks(1)
    t = tasks[0]

    def setup(tx):
        tx.create(node)
        tx.create(svc)
        tx.create(t)

    store.update(setup)
    sched = Scheduler(store)
    store.view(sched._setup_tasks_list)

    # concurrent orchestrator write during the debounce window: the
    # scheduler's mirror has NOT seen this event yet
    def shutdown(tx):
        cur = tx.get(Task, t.id).copy()
        cur.desired_state = TaskState.SHUTDOWN
        tx.update(cur)

    store.update(shutdown)

    sched.tick()

    cur = store.view(lambda tx: tx.get(Task, t.id))
    assert cur.desired_state == TaskState.SHUTDOWN, \
        "stale scheduler decision overwrote a concurrent desired_state change"
    assert cur.status.state == TaskState.PENDING
    # the failed decision was rolled back in the mirror and re-enqueued
    assert t.id in sched.unassigned_tasks
