"""Task FSM safety properties, inspired by the reference's TLA+ specs
(design/tla/{Tasks,WorkerSpec}.tla, model-checked with TLC there):

  P1. observed task state is monotonically non-decreasing;
  P2. desired state never moves backwards;
  P3. terminal tasks are never resurrected (state stays terminal);
  P4. a task only carries a node once ASSIGNED or preassigned.

The checker subscribes to the store and validates every committed task
transition while a full cluster scenario (create / scale / fail / drain /
job completion) churns through the real components."""

import threading
import time

from swarmkit_tpu.manager import Allocator, Dispatcher
from swarmkit_tpu.manager.dispatcher import Config_
from swarmkit_tpu.models import (
    Annotations, Cluster, NodeAvailability, Node, ReplicatedService,
    Service, Task, TaskState, TaskStatus,
)
from swarmkit_tpu.models.specs import ClusterSpec
from swarmkit_tpu.models.types import TERMINAL_STATES, now
from swarmkit_tpu.agent import Agent
from swarmkit_tpu.agent.testutils import TestExecutor
from swarmkit_tpu.orchestrator import ReplicatedOrchestrator, TaskReaper
from swarmkit_tpu.scheduler import Scheduler
from swarmkit_tpu.state import ByService, MemoryStore
from swarmkit_tpu.state.events import Event
from swarmkit_tpu.utils import new_id

from test_orchestrator import make_node, make_replicated, poll


class FSMInvariantChecker:
    def __init__(self, store):
        self.store = store
        self.violations = []
        self._last = {}
        self._sub = store.queue.subscribe(
            lambda ev: isinstance(ev, Event) and isinstance(ev.obj, Task))
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        from swarmkit_tpu.state.watch import Closed
        while not self._stop.is_set():
            try:
                ev = self._sub.get(timeout=0.1)
            except TimeoutError:
                continue
            except Closed:
                return
            t = ev.obj
            if ev.action == "delete":
                self._last.pop(t.id, None)
                continue
            prev = self._last.get(t.id)
            if prev is not None:
                prev_state, prev_desired = prev
                if t.status.state < prev_state:
                    self.violations.append(
                        f"P1: task {t.id[:8]} state went backwards "
                        f"{prev_state.name} -> {t.status.state.name}")
                if t.desired_state < prev_desired:
                    self.violations.append(
                        f"P2: task {t.id[:8]} desired went backwards "
                        f"{prev_desired.name} -> {t.desired_state.name}")
                if prev_state in TERMINAL_STATES and \
                        t.status.state != prev_state and \
                        t.status.state not in TERMINAL_STATES:
                    self.violations.append(
                        f"P3: terminal task {t.id[:8]} resurrected to "
                        f"{t.status.state.name}")
            if t.status.state >= TaskState.ASSIGNED and not t.node_id \
                    and t.status.state <= TaskState.RUNNING:
                self.violations.append(
                    f"P4: task {t.id[:8]} in {t.status.state.name} "
                    "without a node")
            self._last[t.id] = (t.status.state, t.desired_state)

    def stop(self):
        self._stop.set()
        self.store.queue.unsubscribe(self._sub)
        self._thread.join(timeout=2)


def test_fsm_invariants_under_cluster_churn():
    store = MemoryStore()
    store.update(lambda tx: tx.create(Cluster(
        id=new_id(),
        spec=ClusterSpec(annotations=Annotations(name="default")))))
    checker = FSMInvariantChecker(store)

    d = Dispatcher(store, Config_(heartbeat_period=0.3,
                                  heartbeat_epsilon=0.02,
                                  process_updates_interval=0.02))
    d.run()
    alloc = Allocator(store)
    sched = Scheduler(store)
    orch = ReplicatedOrchestrator(store)
    reaper = TaskReaper(store)
    nodes = [make_node(f"n{i}") for i in range(3)]
    for n in nodes:
        n.description.resources.nano_cpus = 8 * 10**9
        n.description.resources.memory_bytes = 32 << 30
        store.update(lambda tx, n=n: tx.create(n))
    agents = [Agent(n.id, TestExecutor(), d) for n in nodes]
    alloc.start()
    sched.start()
    orch.start()
    reaper.start()
    for a in agents:
        a.start()
    try:
        svc = make_replicated("churn", 6)
        store.update(lambda tx: tx.create(svc))

        def n_running(k):
            got = [t for t in store.view(
                lambda tx: tx.find(Task, ByService(svc.id)))
                if t.desired_state == TaskState.RUNNING
                and t.status.state == TaskState.RUNNING]
            return len(got) == k
        poll(lambda: n_running(6), timeout=30)

        # fail a task
        victim = store.view(
            lambda tx: tx.find(Task, ByService(svc.id)))[0]

        def fail(tx):
            t = tx.get(Task, victim.id)
            if t is not None and t.status.state <= TaskState.RUNNING:
                t = t.copy()
                t.status = TaskStatus(state=TaskState.FAILED,
                                      timestamp=now(), err="churn")
                tx.update(t)
        store.update(fail)
        poll(lambda: n_running(6), timeout=30)

        # drain a node
        def drain(tx):
            n = tx.get(Node, nodes[0].id).copy()
            n.spec.availability = NodeAvailability.DRAIN
            tx.update(n)
        store.update(drain)
        poll(lambda: n_running(6), timeout=30)

        # scale down, then delete
        cur = store.view(lambda tx: tx.get(Service, svc.id)).copy()
        cur.spec.replicated = ReplicatedService(replicas=2)
        store.update(lambda tx: tx.update(cur))
        poll(lambda: n_running(2), timeout=30)
        store.update(lambda tx: tx.delete(Service, svc.id))
        time.sleep(1.0)

        assert not checker.violations, "\n".join(checker.violations[:10])
    finally:
        for a in agents:
            a.stop()
        orch.stop()
        reaper.stop()
        sched.stop()
        alloc.stop()
        d.stop()
        checker.stop()


def test_resourceapi_attach_detach():
    from swarmkit_tpu.manager import ResourceAPI
    from swarmkit_tpu.manager.controlapi import InvalidArgument, NotFound
    from swarmkit_tpu.models import Network
    from swarmkit_tpu.models.specs import NetworkSpec
    import pytest

    store = MemoryStore()
    node = make_node("n1")
    net = Network(id=new_id(), spec=NetworkSpec(
        annotations=Annotations(name="overlay1"), attachable=True))
    sealed = Network(id=new_id(), spec=NetworkSpec(
        annotations=Annotations(name="internal1")))
    store.update(lambda tx: (tx.create(node), tx.create(net),
                             tx.create(sealed)))
    api = ResourceAPI(store)

    with pytest.raises(NotFound):
        api.attach_network("nope", net.id)
    with pytest.raises(InvalidArgument, match="not attachable"):
        api.attach_network(node.id, sealed.id)

    attachment_id = api.attach_network(node.id, net.id,
                                       container_id="c1")
    t = store.view(lambda tx: tx.get(Task, attachment_id))
    assert t.spec.attachment.container_id == "c1"
    assert t.node_id == node.id
    assert t.networks[0].network_id == net.id

    with pytest.raises(InvalidArgument):
        api.detach_network("other-node", attachment_id)
    api.detach_network(node.id, attachment_id)
    assert store.view(lambda tx: tx.get(Task, attachment_id)) is None
