"""Every agent's assignments come from the dispatcher's one fan-out
(``BatchedAssignmentFanout``): one store subscription and no thread per
session, whatever the session count; each node's stream, applied in
order, equals the node's assignment set computed from the store (the
oracle the per-session streams are held to) through creates, scheduler
block commits, updates, deletes, secret / config / volume dependencies
and a re-open across a session gap; a committed change reaches its
node's stream within ``process_updates_interval`` while the worker runs;
and a traced tick shows the fan-out at work in the two spans that the
per-layer metrics ``publish_subscribers`` and ``fanout_flush_ms`` read.

Tier-1, on the forced CPU: counts, contents and an upper bound on a
delay; never a speed."""

import json
import os
import sys
import threading
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, REPO)

from benchmark import readers  # noqa: E402
from swarmkit_tpu.manager.dispatcher import (  # noqa: E402
    AssignmentsMessage, Config_, Dispatcher, DispatcherError,
    _AssignmentSet,
)
from swarmkit_tpu.models import (  # noqa: E402
    Annotations, Node, NodeDescription, NodeSpec, NodeState, NodeStatus,
    Task, TaskState, TaskStatus,
)
from swarmkit_tpu.models.objects import Config, Secret, Volume  # noqa: E402
from swarmkit_tpu.models.specs import (  # noqa: E402
    ConfigSpec, ContainerSpec, SecretSpec, TaskSpec, VolumeSpec,
)
from swarmkit_tpu.models.types import (  # noqa: E402
    ConfigReference, SecretReference, Version, VolumeAttachment, now,
)
from swarmkit_tpu.obs import tracer  # noqa: E402
from swarmkit_tpu.scheduler import Scheduler  # noqa: E402
from swarmkit_tpu.state import MemoryStore  # noqa: E402
from swarmkit_tpu.state.store import ByNode  # noqa: E402

from test_scheduler import make_ready_node, make_service_with_tasks  # noqa: E402,E501

#: the cells the two metrics are listed for
CELLS = ("swarm-10k.deploys", "swarm-1k.deploys-1k", "harness-100k.sparse",
         "harness-100k-ha.prefs")


def _node(nid):
    return Node(id=nid, spec=NodeSpec(annotations=Annotations(name=nid)),
                status=NodeStatus(state=NodeState.UNKNOWN),
                description=NodeDescription(hostname=nid))


def _task(tid, node_id="", state=TaskState.ASSIGNED, spec=None):
    return Task(id=tid, service_id="s", slot=1, node_id=node_id,
                desired_state=TaskState.RUNNING,
                spec=spec if spec is not None else TaskSpec(),
                spec_version=Version(index=1),
                status=TaskStatus(state=state, timestamp=now()))


def _store(*node_ids):
    store = MemoryStore()
    store.update(lambda tx: [tx.create(_node(n)) for n in node_ids])
    return store


def _drain(stream):
    msgs = []
    while True:
        try:
            msgs.append(stream.get(timeout=0))
        except TimeoutError:
            return msgs


def _assignment_threads():
    return [t.name for t in threading.enumerate()
            if t.name.startswith("assignments-")]


# ----------------------------------------------------- one subscription


@pytest.mark.parametrize("sessions", [1, 16, 64])
def test_n_sessions_hold_one_subscription_and_no_thread(sessions):
    nids = [f"w{i:02d}" for i in range(sessions)]
    store = _store(*nids)
    before = len(store.queue._subs)
    d = Dispatcher(store, Config_(rate_limit_period=0.0))
    d.run()
    try:
        # the dispatcher's two: cluster-spec changes and the fan-out
        assert len(store.queue._subs) == before + 2
        assert d.fanout is not None and d.fanout._sub in store.queue._subs
        threads = threading.active_count()
        streams = []
        for nid in nids:
            session, _ = d.register(nid)
            streams.append(d.open_assignments(nid, session))
        assert [s.get(timeout=2).type for s in streams] == \
            [AssignmentsMessage.COMPLETE] * sessions
        assert len(store.queue._subs) == before + 2
        assert threading.active_count() == threads
        assert _assignment_threads() == []
        assert sorted(d.fanout._streams) == nids
        # the accessor hands back the one fan-out
        assert d.enable_batched_fanout() is d.fanout
    finally:
        d.stop()
    assert len(store.queue._subs) == before
    assert all(s.closed for s in streams)
    assert d.fanout is None
    with pytest.raises(DispatcherError):
        d.open_assignments(nids[0], "any")
    store.close()


def test_a_commit_for_no_open_stream_is_not_buffered():
    """The predicate keeps a Task event only for a node with an open
    stream: a commit for any other node leaves the subscription empty."""
    store = _store("a", "c")
    d = Dispatcher(store, Config_(rate_limit_period=0.0))
    d.run(start_worker=False)
    try:
        session, _ = d.register("a")
        stream = d.open_assignments("a", session)
        _drain(stream)
        d.process_deadlines()
        store.update(lambda tx: tx.create(_task("tc", "c")))
        store.update(lambda tx: tx.create(_task("tn", "",
                                                TaskState.PENDING)))
        assert d.fanout._sub.backlog() == 0
        store.update(lambda tx: tx.create(_task("ta", "a")))
        assert d.fanout._sub.backlog() == 1
        d.process_deadlines()
        (msg,) = _drain(stream)
        assert [obj.id for _a, _k, obj in msg.changes] == ["ta"]
    finally:
        d.stop(flush=False)
        store.close()


# ------------------------------------------------------------- parity


def _digest(objs):
    """What the agent acts on, by (kind, id).  A task's state above
    ASSIGNED is the agent's own report, which is not sent back to it."""
    out = {}
    for (kind, oid), obj in objs.items():
        if kind == "task":
            out[(kind, oid)] = (min(int(obj.status.state),
                                    int(TaskState.ASSIGNED)),
                                int(obj.desired_state), obj.node_id)
        elif kind == "volume":
            out[(kind, oid)] = obj.meta.version.index
        else:
            out[(kind, oid)] = None
    return out


def _oracle(store, node_id):
    """The node's assignment set computed afresh from the store."""
    aset = _AssignmentSet(node_id)
    tx = store.view()
    for t in store.view(lambda vx: list(vx.find(Task, ByNode(node_id)))):
        aset.add_or_update_task(tx, t)
    return _digest({key: obj for key, (_a, _kind, obj)
                    in aset.changes.items()})


class _Agent:
    """A node's view, built from its stream's messages in order."""

    def __init__(self, stream):
        self.stream = stream
        self.view = {}
        self.results_in = ""

    def take(self):
        for msg in _drain(self.stream):
            assert msg.applies_to == self.results_in
            self.results_in = msg.results_in
            if msg.type == AssignmentsMessage.COMPLETE:
                keys = [(kind, obj.id) for _a, kind, obj in msg.changes]
                assert len(keys) == len(set(keys)), "duplicated in COMPLETE"
                self.view = {}
            for action, kind, obj in msg.changes:
                if action == "update":
                    self.view[(kind, obj.id)] = obj
                else:
                    self.view.pop((kind, obj.id), None)
        return _digest(self.view)


def _update(store, tid, **fields):
    def cb(tx):
        t = tx.get(Task, tid).copy()
        for k, v in fields.items():
            setattr(t, k, v)
        tx.update(t)
    store.update(cb)


def test_each_stream_applied_in_order_equals_its_node_s_set_from_the_store():
    store = _store("a", "b", "c")
    secret = Secret(id="sec", spec=SecretSpec(
        annotations=Annotations(name="sec"), data=b"x"))
    config = Config(id="cfg", spec=ConfigSpec(
        annotations=Annotations(name="cfg"), data=b"y"))
    volume = Volume(id="vol", spec=VolumeSpec(
        annotations=Annotations(name="vol")))
    with_deps = TaskSpec(container=ContainerSpec(
        image="img",
        secrets=[SecretReference(secret_id="sec", secret_name="sec")],
        configs=[ConfigReference(config_id="cfg", config_name="cfg")]))

    def setup(tx):
        for obj in (secret, config, volume):
            tx.create(obj)
        tx.create(_task("t0", "a", spec=with_deps))
        tx.create(_task("t1", "c", TaskState.RUNNING))
    store.update(setup)

    d = Dispatcher(store, Config_(rate_limit_period=0.0,
                                  modification_batch_limit=4))
    d.run(start_worker=False)
    agents, sessions = {}, {}

    def step():
        d.process_deadlines()
        for nid, agent in agents.items():
            assert agent.take() == _oracle(store, nid), nid

    try:
        for nid in ("a", "b"):
            sessions[nid], _ = d.register(nid)
            agents[nid] = _Agent(d.open_assignments(nid, sessions[nid]))
        step()
        assert ("secret", "sec") in agents["a"].view
        assert ("config", "cfg") in agents["a"].view

        # creates: tasks the scheduler has not placed, and one placed on b
        # that mounts the volume
        def creates(tx):
            for i in range(10):
                tx.create(_task(f"p{i}", "", TaskState.PENDING))
            t2 = _task("t2", "b")
            t2.volumes = [VolumeAttachment(id="vol", target="/v")]
            tx.create(t2)
        store.update(creates)
        step()
        assert ("volume", "vol") in agents["b"].view

        # a scheduler block: agent and non-agent nodes, more changes for
        # a than one message holds
        pending = [store.raw_get(Task, f"p{i}") for i in range(10)]
        nids = ["a", "b", "c", "a", "a", "a", "b", "c", "a", "a"]
        committed, failed = store.commit_task_block(
            pending, nids, int(TaskState.ASSIGNED), "assigned",
            lambda t, n: None, lambda t, n: False)
        assert committed == list(range(10)) and failed == []
        step()
        assert sum(1 for k in agents["a"].view if k[0] == "task") == 7

        # updates: a task runs, one is told to shut down, one ends, the
        # volume changes
        _update(store, "t0", status=TaskStatus(state=TaskState.RUNNING,
                                               timestamp=now()))
        _update(store, "p0", desired_state=TaskState.SHUTDOWN)
        _update(store, "p3", status=TaskStatus(state=TaskState.COMPLETE,
                                               timestamp=now()))

        def touch_volume(tx):
            v = tx.get(Volume, "vol").copy()
            v.spec.annotations.labels["touched"] = "1"
            tx.update(v)
        store.update(touch_volume)
        step()

        # deletes: one of b's, and the task that held a's secret
        store.update(lambda tx: tx.delete(Task, "p1"))
        store.update(lambda tx: tx.delete(Task, "t0"))
        step()
        assert ("secret", "sec") not in agents["a"].view

        # a gap: b's session goes, b's tasks change while its stream is
        # down, and b comes back to a COMPLETE set
        old = agents.pop("b")
        d.release_session("b", sessions["b"])
        assert old.stream.closed
        store.update(lambda tx: tx.create(_task("t3", "b")))
        _update(store, "t2", status=TaskStatus(state=TaskState.RUNNING,
                                               timestamp=now()))
        store.update(lambda tx: tx.delete(Task, "p6"))
        step()
        session, _ = d.register("b")
        agents["b"] = _Agent(d.open_assignments("b", session))
        step()
        assert ("task", "t3") in agents["b"].view
        assert ("task", "p6") not in agents["b"].view
        # and a's stream carried on through b's gap
        store.update(lambda tx: tx.create(_task("t4", "a")))
        step()
        assert ("task", "t4") in agents["a"].view
    finally:
        d.stop(flush=False)
        store.close()


def test_streams_opened_while_writers_commit_lose_nothing():
    """Sessions open while twelve writers commit to their nodes and the
    worker flushes every 5 ms: each stream still ends equal to its
    node's set from the store.  A commit that lands between a stream's
    COMPLETE snapshot and its registration with the fan-out would be
    lost here."""
    nids = [f"r{i}" for i in range(8)]
    store = _store(*nids)
    d = Dispatcher(store, Config_(rate_limit_period=0.0,
                                  process_updates_interval=0.005))
    d.run()
    agents = {}

    def writer(w):
        for i in range(150):
            nid = nids[(w + i) % len(nids)]
            store.update(lambda tx, tid=f"x{w}-{i}", nid=nid:
                         tx.create(_task(tid, nid)))

    def opener(nid):
        session, _ = d.register(nid)
        agents[nid] = _Agent(d.open_assignments(nid, session))

    threads = [threading.Thread(target=writer, args=(w,), daemon=True)
               for w in range(12)]
    threads[6:6] = [threading.Thread(target=opener, args=(n,), daemon=True)
                    for n in nids]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(interval)
    try:
        d.process_deadlines()    # waits out the worker's flush, then its own
        assert sorted(agents) == nids
        for nid, agent in agents.items():
            assert agent.take() == _oracle(store, nid), nid
    finally:
        d.stop()
        store.close()


# -------------------------------------------------------------- timing


def test_a_commit_reaches_its_stream_within_the_update_interval():
    interval = Config_().process_updates_interval
    slack = 0.5        # a loaded runner's scheduling, not the design's
    store = _store("a")
    d = Dispatcher(store, Config_(rate_limit_period=0.0))
    d.run()
    try:
        session, _ = d.register("a")
        stream = d.open_assignments("a", session)
        assert stream.get(timeout=2).type == AssignmentsMessage.COMPLETE
        waits = []
        for i in range(5):
            t0 = time.monotonic()
            store.update(lambda tx, i=i: tx.create(_task(f"t{i}", "a")))
            got = set()
            while f"t{i}" not in got:
                msg = stream.get(timeout=interval + slack)
                got |= {obj.id for _a, _k, obj in msg.changes}
            waits.append(time.monotonic() - t0)
            time.sleep(0.03 * i)   # land at another point of the cycle
        assert max(waits) <= interval + slack, waits
    finally:
        d.stop()
        store.close()


# ------------------------------------------------------------- tracing


@pytest.fixture(scope="module")
def traced():
    """A traced scheduler tick with four sessions open, and the fan-out's
    flush after it; the tracer is left off and empty."""
    store = MemoryStore()
    nodes = [make_ready_node(f"n{i}", cpus=64, mem=256 << 30)
             for i in range(8)]
    svc, tasks = make_service_with_tasks(48)
    store.update(lambda tx: [tx.create(o) for o in (*nodes, svc, *tasks)])
    d = Dispatcher(store, Config_(rate_limit_period=0.0,
                                  heartbeat_period=600.0))
    d.run(start_worker=False)
    streams = {}
    for n in nodes[:4]:
        session, _ = d.register(n.id)
        streams[n.id] = d.open_assignments(n.id, session)
        _drain(streams[n.id])
    d.process_deadlines()
    sched = Scheduler(store)
    store.view(sched._setup_tasks_list)
    subs = len(store.queue._subs)
    tracer.reset()
    t0 = time.time()
    tracer.enable()
    try:
        assert sched.tick() == 48
        d.process_deadlines()
    finally:
        tracer.disable()
        t1 = time.time()
        spans = tracer.spans()
        tracer.reset()
        sent = {nid: sum(1 for m in _drain(s) for c in m.changes
                         if c[1] == "task")
                for nid, s in streams.items()}
        assigned = {nid: len(store.view(
            lambda tx, nid=nid: tx.find(Task, ByNode(nid))))
            for nid in streams}
        d.stop(flush=False)
        store.close()
    return {"spans": spans, "wall": (t0, t1), "subs": subs, "sent": sent,
            "assigned": assigned}


def test_publish_carries_the_queue_s_subscriber_count(traced):
    publishes = [s for s in traced["spans"] if s.name == "commit.publish"]
    assert publishes
    assert {s.args["subscribers"] for s in publishes} == {traced["subs"]}


def test_the_flush_span_carries_events_streams_and_sends(traced):
    (flush,) = [s for s in traced["spans"]
                if s.name == "dispatcher.fanout_flush"]
    assert set(flush.args) == {"events", "streams", "sends"}
    assert flush.args["streams"] == 4
    # the block lands in one event; each open stream got one message
    assert flush.args["events"] >= 1
    assert flush.args["sends"] == sum(1 for n in traced["sent"].values() if n)
    for nid, n in traced["sent"].items():
        assert n == traced["assigned"][nid]
    sends = [s for s in traced["spans"]
             if s.name == "dispatcher.assignments_send"]
    assert len(sends) == flush.args["sends"]
    assert all(s.parent_id == flush.span_id for s in sends)


@pytest.mark.parametrize("cell", CELLS)
def test_the_two_metrics_read_a_number_through_the_readers(traced, cell):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    obs = readers.Observations()
    t0, t1 = traced["wall"]
    obs.window_s = t1 - t0
    obs.window_wall = (t0, t1)
    obs.spans = [(s.thread, s.name, s.start, s.end, s.args)
                 for s in traced["spans"] if t0 <= s.start < t1]
    line = readers.read_all(cell, obs, bench["per_layer"])
    assert line["publish_subscribers"] == {
        "value": float(traced["subs"]), "unit": "subscribers"}
    assert line["fanout_flush_ms"]["unit"] == "ms"
    assert line["fanout_flush_ms"]["value"] > 0.0
