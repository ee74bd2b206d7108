"""Dispatcher + agent + exec FSM tests: the SURVEY §7.5 end-to-end slice.

One process: store → orchestrator → scheduler → dispatcher → agent(fake
executor) → RUNNING status written back; heartbeat expiry → node DOWN →
restart elsewhere (mirrors manager/dispatcher/dispatcher_test.go and
integration/integration_test.go behaviors).
"""

import threading
import time

import pytest

from swarmkit_tpu.agent import Agent
from swarmkit_tpu.agent.testutils import TestExecutor
from swarmkit_tpu.manager import Allocator, Dispatcher
from swarmkit_tpu.manager.dispatcher import (
    Config_, ErrNodeNotFound, ErrSessionInvalid,
)
from swarmkit_tpu.models import (
    Annotations, Cluster, Node, NodeState, Task, TaskState, TaskStatus,
)
from swarmkit_tpu.models.specs import ClusterSpec
from swarmkit_tpu.models.types import now
from swarmkit_tpu.orchestrator import ReplicatedOrchestrator
from swarmkit_tpu.scheduler import Scheduler
from swarmkit_tpu.state import ByService, MemoryStore
from swarmkit_tpu.utils import new_id

from test_orchestrator import make_node, make_replicated, poll
from test_scheduler import make_ready_node


@pytest.fixture
def store():
    s = MemoryStore()
    cluster = Cluster(id=new_id(), spec=ClusterSpec(
        annotations=Annotations(name="default")))
    s.update(lambda tx: tx.create(cluster))
    yield s
    s.close()


def fast_config(**kw):
    defaults = dict(heartbeat_period=0.3, heartbeat_epsilon=0.02,
                    grace_multiplier=3, process_updates_interval=0.02,
                    orphan_timeout=2.0)
    defaults.update(kw)
    return Config_(**defaults)


def test_register_requires_known_node(store):
    d = Dispatcher(store, fast_config())
    d.run()
    try:
        with pytest.raises(ErrNodeNotFound):
            d.register("nope")
    finally:
        d.stop()


def test_register_rate_limit(store):
    """Re-registration is rate limited per node (reference: nodes.go:90
    CheckRateLimit — RATE_LIMIT_COUNT re-registrations per period, reset
    once the last registration ages past the period)."""
    from swarmkit_tpu.manager.dispatcher import ErrRateLimited

    d = Dispatcher(store, fast_config(rate_limit_period=0.5))
    d.run()
    node = make_ready_node("n1")
    store.update(lambda tx: tx.create(node))
    try:
        d.register(node.id)
        for _ in range(3):        # three rapid re-registrations pass
            d.register(node.id)
        with pytest.raises(ErrRateLimited):
            d.register(node.id)   # the fourth within the period fails
        time.sleep(0.6)           # ...and ages out
        d.register(node.id)

        # disabled limit (period 0): unlimited re-registration
        d2 = Dispatcher(store, fast_config(rate_limit_period=0.0))
        d2.run()
        try:
            for _ in range(10):
                d2.register(node.id)
        finally:
            d2.stop()
    finally:
        d.stop()


def test_heartbeat_session_validation(store):
    d = Dispatcher(store, fast_config())
    d.run()
    node = make_ready_node("n1")
    store.update(lambda tx: tx.create(node))
    try:
        session, period = d.register(node.id)
        assert period > 0
        assert d.heartbeat(node.id, session) > 0
        with pytest.raises(ErrSessionInvalid):
            d.heartbeat(node.id, "bogus")
    finally:
        d.stop()


def test_heartbeat_expiry_marks_node_down(store):
    d = Dispatcher(store, fast_config())
    d.run()
    node = make_ready_node("n1")
    store.update(lambda tx: tx.create(node))
    try:
        d.register(node.id)
        poll(lambda: store.view(
            lambda tx: tx.get(Node, node.id)).status.state
            == NodeState.READY)
        # no heartbeats: after period * grace the node must go DOWN
        poll(lambda: store.view(
            lambda tx: tx.get(Node, node.id)).status.state
            == NodeState.DOWN,
            timeout=5, msg="node should go DOWN after heartbeat expiry")
    finally:
        d.stop()


def test_orphan_timeout_moves_tasks_to_orphaned(store):
    d = Dispatcher(store, fast_config(orphan_timeout=0.5))
    d.run()
    node = make_ready_node("n1")
    t = Task(id=new_id(), service_id=new_id(), slot=1, node_id=node.id,
             desired_state=TaskState.RUNNING,
             status=TaskStatus(state=TaskState.RUNNING))

    def setup(tx):
        tx.create(node)
        tx.create(t)
    store.update(setup)
    try:
        d.register(node.id)
        poll(lambda: store.view(
            lambda tx: tx.get(Node, node.id)).status.state
            == NodeState.DOWN, timeout=5)
        poll(lambda: store.view(
            lambda tx: tx.get(Task, t.id)).status.state
            == TaskState.ORPHANED,
            timeout=5, msg="tasks on long-dead node become ORPHANED")
    finally:
        d.stop()


def test_assignments_stream_complete_and_incremental(store):
    d = Dispatcher(store, fast_config())
    d.run()
    node = make_ready_node("n1")
    t1 = Task(id=new_id(), service_id="svc", slot=1, node_id=node.id,
              desired_state=TaskState.RUNNING,
              status=TaskStatus(state=TaskState.ASSIGNED))

    def setup(tx):
        tx.create(node)
        tx.create(t1)
    store.update(setup)
    try:
        session, _ = d.register(node.id)
        stream = d.open_assignments(node.id, session)
        # served by the dispatcher's one fan-out, not a thread of its own
        assert d.fanout._streams[node.id] is stream
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("assignments-")]
        msg = stream.get(timeout=2)
        assert msg.type == "complete"
        assert [obj.id for _, kind, obj in msg.changes
                if kind == "task"] == [t1.id]

        # a new assignment arrives incrementally
        t2 = Task(id=new_id(), service_id="svc", slot=2, node_id=node.id,
                  desired_state=TaskState.RUNNING,
                  status=TaskStatus(state=TaskState.ASSIGNED))
        store.update(lambda tx: tx.create(t2))
        # create events don't reach agents (tasks are never created
        # ASSIGNED by the real pipeline); an update does
        t2b = store.view(lambda tx: tx.get(Task, t2.id)).copy()
        t2b.status = TaskStatus(state=TaskState.ASSIGNED, timestamp=now())
        t2b.desired_state = TaskState.RUNNING
        store.update(lambda tx: tx.update(t2b))

        msg = stream.get(timeout=2)
        assert msg.type == "incremental"
        assert {obj.id for _, kind, obj in msg.changes} >= {t2.id}
        assert msg.applies_to == "1"
    finally:
        d.stop()


def test_assignments_stream_from_block_commit_with_raft(store, tmp_path):
    """Columnar block commits with a LIVE raft proposer still produce
    correct per-session assignment diffs: each session receives exactly
    its node's slice of the block (as materialized ASSIGNED tasks), and
    the block rides consensus as a compact TaskBlockAction (VERDICT r3
    item 1 'done' criterion)."""
    import os as _os

    from swarmkit_tpu.state.raft import LocalNetwork, RaftLogger, RaftNode

    rn = RaftNode("m0", ["m0"], store,
                  RaftLogger(_os.path.join(str(tmp_path), "m0")),
                  LocalNetwork())
    store._proposer = rn
    rn.start()
    poll(lambda: rn.is_leader and rn.core.leader_ready, timeout=10)

    d = Dispatcher(store, fast_config())
    d.run()
    n1, n2 = make_ready_node("n1"), make_ready_node("n2")
    tasks = [Task(id=new_id(), service_id="svc", slot=i,
                  desired_state=TaskState.RUNNING,
                  status=TaskStatus(state=TaskState.PENDING))
             for i in range(6)]

    def setup(tx):
        tx.create(n1)
        tx.create(n2)
        for t in tasks:
            tx.create(t)
    store.update(setup)

    try:
        s1, _ = d.register(n1.id)
        s2, _ = d.register(n2.id)
        st1 = d.open_assignments(n1.id, s1)
        st2 = d.open_assignments(n2.id, s2)
        assert d.fanout._streams == {n1.id: st1, n2.id: st2}
        assert st1.get(timeout=2).type == "complete"
        assert st2.get(timeout=2).type == "complete"

        # columnar commit: evens to n1, odds to n2, one block
        stored = [store.raw_get(Task, t.id) for t in tasks]
        nids = [n1.id if i % 2 == 0 else n2.id for i in range(6)]
        committed, failed = store.commit_task_block(
            stored, nids, int(TaskState.ASSIGNED), "assigned",
            lambda t, n: None, lambda t, n: False)
        assert committed == list(range(6)) and failed == []

        msg1 = st1.get(timeout=2)
        assert msg1.type == "incremental"
        got1 = {obj.id for _, kind, obj in msg1.changes if kind == "task"}
        assert got1 == {tasks[i].id for i in (0, 2, 4)}
        for _, kind, obj in msg1.changes:
            if kind == "task":
                assert obj.node_id == n1.id
                assert obj.status.state == TaskState.ASSIGNED

        msg2 = st2.get(timeout=2)
        got2 = {obj.id for _, kind, obj in msg2.changes if kind == "task"}
        assert got2 == {tasks[i].id for i in (1, 3, 5)}
    finally:
        d.stop()
        rn.stop()


def test_update_task_status_rejects_foreign_node(store):
    d = Dispatcher(store, fast_config())
    d.run()
    n1, n2 = make_ready_node("n1"), make_ready_node("n2")
    t = Task(id=new_id(), service_id="svc", slot=1, node_id=n2.id,
             desired_state=TaskState.RUNNING,
             status=TaskStatus(state=TaskState.ASSIGNED))

    def setup(tx):
        tx.create(n1)
        tx.create(n2)
        tx.create(t)
    store.update(setup)
    try:
        session, _ = d.register(n1.id)
        with pytest.raises(Exception):
            d.update_task_status(
                n1.id, session,
                [(t.id, TaskStatus(state=TaskState.RUNNING))])
    finally:
        d.stop()


def test_e2e_service_to_running_via_dispatcher_and_agent(store):
    """The minimum end-to-end slice (SURVEY §7.5): service create →
    orchestrator → scheduler → dispatcher → agent → fake executor →
    RUNNING status written back through the dispatcher."""
    d = Dispatcher(store, fast_config())
    d.run()
    alloc = Allocator(store)
    sched = Scheduler(store)
    orch = ReplicatedOrchestrator(store)

    node = make_ready_node("n1", cpus=8)
    store.update(lambda tx: tx.create(node))

    agent = Agent(node.id, TestExecutor(hostname="n1"), d)
    alloc.start()
    sched.start()
    orch.start()
    agent.start()
    try:
        svc = make_replicated("web", 3)
        store.update(lambda tx: tx.create(svc))

        def all_running():
            got = [t for t in store.view(
                lambda tx: tx.find(Task, ByService(svc.id)))
                if t.desired_state == TaskState.RUNNING]
            return (len(got) == 3
                    and all(t.status.state == TaskState.RUNNING
                            for t in got)
                    and all(t.node_id == node.id for t in got))

        poll(all_running, timeout=20,
             msg="3 replicas should reach RUNNING through the full pipeline")

        # the worker runs exactly the assigned tasks
        poll(lambda: len(agent.worker.task_managers) == 3)

        # scale down: agent must stop the removed tasks
        cur = store.view(lambda tx: tx.get(Service, svc.id)).copy()
        from swarmkit_tpu.models import ReplicatedService, Service as _S
        cur.spec.replicated = ReplicatedService(replicas=1)
        store.update(lambda tx: tx.update(cur))

        def scaled():
            got = store.view(lambda tx: tx.find(Task, ByService(svc.id)))
            live = [t for t in got
                    if t.desired_state == TaskState.RUNNING]
            shut = [t for t in got
                    if t.desired_state >= TaskState.SHUTDOWN]
            return (len(live) == 1
                    and all(t.status.state >= TaskState.SHUTDOWN
                            for t in shut))
        poll(scaled, timeout=20,
             msg="scaled-down tasks should be shut down by the agent")
    finally:
        agent.stop()
        orch.stop()
        sched.stop()
        alloc.stop()
        d.stop()


from swarmkit_tpu.models import Service  # noqa: E402  (used in poll closures)


def test_e2e_agent_death_reschedules_tasks(store):
    """Kill the agent (stop heartbeating) → node DOWN → orchestrator
    replaces tasks → scheduler assigns to the surviving node → its agent
    runs them."""
    d = Dispatcher(store, fast_config())
    d.run()
    alloc = Allocator(store)
    alloc.start()
    sched = Scheduler(store)
    orch = ReplicatedOrchestrator(store)

    n1, n2 = make_ready_node("n1", cpus=8), make_ready_node("n2", cpus=8)
    store.update(lambda tx: (tx.create(n1), tx.create(n2)))

    agent1 = Agent(n1.id, TestExecutor(hostname="n1"), d)
    agent2 = Agent(n2.id, TestExecutor(hostname="n2"), d)
    sched.start()
    orch.start()
    agent1.start()
    agent2.start()
    try:
        svc = make_replicated("web", 2)
        store.update(lambda tx: tx.create(svc))

        def all_running():
            got = [t for t in store.view(
                lambda tx: tx.find(Task, ByService(svc.id)))
                if t.desired_state == TaskState.RUNNING]
            return (len(got) == 2
                    and all(t.status.state == TaskState.RUNNING
                            for t in got))
        poll(all_running, timeout=20)

        # kill agent1: heartbeats stop, node n1 goes DOWN, tasks restarted
        agent1.stop()

        def healed():
            got = [t for t in store.view(
                lambda tx: tx.find(Task, ByService(svc.id)))
                if t.desired_state == TaskState.RUNNING]
            return (len(got) == 2
                    and all(t.status.state == TaskState.RUNNING
                            for t in got)
                    and all(t.node_id == n2.id for t in got))
        poll(healed, timeout=20,
             msg="tasks should be rescheduled onto the surviving node")
    finally:
        agent2.stop()
        orch.stop()
        sched.stop()
        alloc.stop()
        d.stop()


def test_driver_backed_secrets_fetch_per_task_values(store):
    """Secrets with spec.driver fetch their value from a provider plugin
    at assignment time; DoNotReuse providers yield task-specific secret
    ids/values (reference: manager/drivers/secrets.go + assignments.go
    assignSecret)."""
    from swarmkit_tpu.manager.drivers import DriverProvider
    from swarmkit_tpu.models import Secret
    from swarmkit_tpu.models.specs import ContainerSpec, SecretSpec, TaskSpec
    from swarmkit_tpu.models.types import Driver, SecretReference

    calls = []

    def plugin(req):
        calls.append(req)
        import base64
        value = f"v-for-{req['TaskID']}".encode()
        return {"Value": base64.b64encode(value).decode(),
                "DoNotReuse": True}

    provider = DriverProvider({"vault": plugin})
    d = Dispatcher(store, fast_config(), driver_provider=provider)
    d.run()
    node = make_ready_node("n1")
    secret = Secret(id=new_id(), spec=SecretSpec(
        annotations=Annotations(name="db-pass"),
        driver=Driver(name="vault")))

    def mk_task(slot):
        return Task(id=new_id(), service_id="svc", slot=slot,
                    node_id=node.id, desired_state=TaskState.RUNNING,
                    status=TaskStatus(state=TaskState.ASSIGNED),
                    spec=TaskSpec(container=ContainerSpec(
                        image="img", secrets=[SecretReference(
                            secret_id=secret.id, secret_name="db-pass")])))

    t1, t2 = mk_task(1), mk_task(2)

    def setup(tx):
        tx.create(node)
        tx.create(secret)
        tx.create(t1)
        tx.create(t2)
    store.update(setup)
    try:
        session, _ = d.register(node.id)
        stream = d.open_assignments(node.id, session)
        msg = stream.get(timeout=2)
        assert msg.type == "complete"
        secrets = {obj.id: obj for _, kind, obj in msg.changes
                   if kind == "secret"}
        assert set(secrets) == {f"{secret.id}.{t1.id}",
                                f"{secret.id}.{t2.id}"}, \
            "DoNotReuse secrets must get task-specific ids"
        assert secrets[f"{secret.id}.{t1.id}"].spec.data == \
            f"v-for-{t1.id}".encode()
        assert secrets[f"{secret.id}.{t2.id}"].spec.data == \
            f"v-for-{t2.id}".encode()
        assert all(s.internal for s in secrets.values())
        assert len(calls) == 2
        assert calls[0]["SecretName"] == "db-pass"
        assert calls[0]["NodeID"] == node.id
    finally:
        d.stop()


def test_driver_secret_fetch_error_skips_assignment(store):
    """Provider failures leave the secret unassigned rather than shipping
    an empty value (reference: assignments.go fetch-error path)."""
    from swarmkit_tpu.manager.drivers import DriverProvider
    from swarmkit_tpu.models import Secret
    from swarmkit_tpu.models.specs import ContainerSpec, SecretSpec, TaskSpec
    from swarmkit_tpu.models.types import Driver, SecretReference

    def bad_plugin(req):
        return {"Err": "vault is sealed"}

    provider = DriverProvider({"vault": bad_plugin})
    d = Dispatcher(store, fast_config(), driver_provider=provider)
    d.run()
    node = make_ready_node("n1")
    secret = Secret(id=new_id(), spec=SecretSpec(
        annotations=Annotations(name="db-pass"),
        driver=Driver(name="vault")))
    t1 = Task(id=new_id(), service_id="svc", slot=1, node_id=node.id,
              desired_state=TaskState.RUNNING,
              status=TaskStatus(state=TaskState.ASSIGNED),
              spec=TaskSpec(container=ContainerSpec(
                  image="img", secrets=[SecretReference(
                      secret_id=secret.id, secret_name="db-pass")])))

    def setup(tx):
        tx.create(node)
        tx.create(secret)
        tx.create(t1)
    store.update(setup)
    try:
        session, _ = d.register(node.id)
        stream = d.open_assignments(node.id, session)
        msg = stream.get(timeout=2)
        assert msg.type == "complete"
        assert [obj.id for _, kind, obj in msg.changes
                if kind == "secret"] == [], \
            "failed driver fetch must not ship a secret"
        assert [obj.id for _, kind, obj in msg.changes
                if kind == "task"] == [t1.id], "the task still ships"
    finally:
        d.stop()


def test_driver_secret_retries_until_provider_recovers(store):
    """A transient provider outage heals: the fan-out's flush retries
    failed fetches and ships the secret once the provider answers."""
    from swarmkit_tpu.manager.drivers import DriverProvider
    from swarmkit_tpu.models import Secret
    from swarmkit_tpu.models.specs import ContainerSpec, SecretSpec, TaskSpec
    from swarmkit_tpu.models.types import Driver, SecretReference

    state = {"n": 0}

    def flaky_plugin(req):
        state["n"] += 1
        if state["n"] <= 2:
            return {"Err": "vault sealed"}
        import base64
        return {"Value": base64.b64encode(b"recovered").decode()}

    provider = DriverProvider({"vault": flaky_plugin})
    d = Dispatcher(store, fast_config(), driver_provider=provider)
    d.run()
    node = make_ready_node("n1")
    secret = Secret(id=new_id(), spec=SecretSpec(
        annotations=Annotations(name="db-pass"),
        driver=Driver(name="vault")))
    t1 = Task(id=new_id(), service_id="svc", slot=1, node_id=node.id,
              desired_state=TaskState.RUNNING,
              status=TaskStatus(state=TaskState.ASSIGNED),
              spec=TaskSpec(container=ContainerSpec(
                  image="img", secrets=[SecretReference(
                      secret_id=secret.id, secret_name="db-pass")])))

    def setup(tx):
        tx.create(node)
        tx.create(secret)
        tx.create(t1)
    store.update(setup)
    try:
        session, _ = d.register(node.id)
        stream = d.open_assignments(node.id, session)
        assert d.fanout._streams[node.id] is stream
        msg = stream.get(timeout=2)
        assert msg.type == "complete"
        assert not [o for _, k, o in msg.changes if k == "secret"]

        # a later flush's retry ships it
        deadline = time.time() + 10
        got = None
        while time.time() < deadline:
            d.heartbeat(node.id, session)   # keep the session alive
            try:
                msg = stream.get(timeout=0.25)
            except TimeoutError:
                continue
            secrets = [o for _, k, o in msg.changes if k == "secret"]
            if secrets:
                got = secrets[0]
                break
        assert got is not None, "secret never shipped after recovery"
        assert got.spec.data == b"recovered"
        assert state["n"] >= 3
    finally:
        d.stop()
