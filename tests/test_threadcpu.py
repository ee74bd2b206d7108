"""Who holds the interpreter while a span's thread waits: the thread-CPU
accountant (``obs/threadcpu.py``) maps every thread the program starts to
a family by its name, and ``sched.tick`` and ``orchestrator.reconcile``
say what each family used while they ran; ``commit.publish`` says how
many subscriptions its events reached.  With the tracer off nothing is
read (``thread_cpu_snapshots`` stays).

Tier-1, on the forced CPU: names, counts and CPU seconds added up against
the process's own clock; never a speed."""

import ast
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
from swarmkit_tpu.manager.allocator import Allocator  # noqa: E402
from swarmkit_tpu.models import Node, Service, Task, TaskState  # noqa: E402
from swarmkit_tpu.obs import threadcpu, tracer  # noqa: E402
from swarmkit_tpu.orchestrator import replicated  # noqa: E402
from swarmkit_tpu.orchestrator.replicated import Orchestrator  # noqa: E402
from swarmkit_tpu.scheduler import Scheduler  # noqa: E402
from swarmkit_tpu.sim.clock import VirtualClock  # noqa: E402
from swarmkit_tpu.state import MemoryStore  # noqa: E402
from swarmkit_tpu.state.events import Event  # noqa: E402

from test_scheduler import make_ready_node, make_service_with_tasks  # noqa: E402,E501

import servedpath_deploy  # noqa: E402

#: the cells the six metrics are listed for
CELLS = ("swarm-10k.deploys", "swarm-1k.deploys-1k", "harness-100k.sparse",
         "harness-100k-ha.prefs")
#: metric -> (span, argument) it reads as ``arg_mean``
METRICS = {
    "tick_others_cpu_ms": ("sched.tick", "others_cpu_ms"),
    "tick_agents_cpu_ms": ("sched.tick", "agents_cpu_ms"),
    "tick_background_cpu_ms": ("sched.tick", "background_cpu_ms"),
    "tick_control_cpu_ms": ("sched.tick", "control_cpu_ms"),
    "reconcile_others_cpu_ms": ("orchestrator.reconcile", "others_cpu_ms"),
    "publish_delivered": ("commit.publish", "delivered"),
}
#: a busy thread under a name of each family, and one the program would
#: not give (a client's)
SPINNERS = {"agent": "agents", "metrics": "background",
            "dispatcher": "control", "bench-client-0": "other"}
REPLICAS = 600


# ------------------------------------------------------------- the families


def _thread_names_in_source():
    """[(file, line, the ``name=`` of a ``threading.Thread(...)`` call as
    an ast node, or None)] over the package."""
    root = os.path.join(REPO, "swarmkit_tpu")
    out = []
    for folder, _dirs, files in os.walk(root):
        for fname in files:
            if not fname.endswith(".py"):
                continue
            path = os.path.join(folder, fname)
            with open(path) as f:
                tree = ast.parse(f.read(), path)
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) and (
                        getattr(node.func, "attr", None) == "Thread"
                        or getattr(node.func, "id", None) == "Thread"):
                    name = next((k.value for k in node.keywords
                                 if k.arg == "name"), None)
                    out.append((os.path.relpath(path, REPO), node.lineno,
                                name))
    return out


def test_every_thread_the_source_names_has_a_family():
    """Every ``threading.Thread`` the package makes is given a name, and
    a name written in the call (or an f-string's fixed start) maps to a
    family other than ``other``; the two calls that take the name from a
    variable are the live test's below."""
    sites = _thread_names_in_source()
    assert len(sites) >= 30
    from_variable = []
    for path, line, name in sites:
        assert name is not None, f"{path}:{line}: a thread without a name"
        if isinstance(name, ast.Constant):
            text = name.value
        elif isinstance(name, ast.JoinedStr) and isinstance(
                name.values[0], ast.Constant):
            text = name.values[0].value + "x"
        else:
            from_variable.append(path)
            continue
        assert threadcpu.family(text) != threadcpu.OTHER, \
            f"{path}:{line}: thread {text!r} has no family"
    assert sorted(from_variable) == ["swarmkit_tpu/manager/csi.py",
                                     "swarmkit_tpu/orchestrator/enforcers.py"]


def test_every_thread_a_served_manager_with_agents_starts_has_a_family():
    from benchmark.executor import make_executor
    from swarmkit_tpu.agent import Agent
    from swarmkit_tpu.manager import Manager
    from swarmkit_tpu.manager.dispatcher import Config_
    before = {t.ident for t in threading.enumerate()}
    mgr = Manager(dispatcher_config=Config_(heartbeat_period=30.0))
    mgr.run()
    agents = []
    try:
        nodes = [make_ready_node(f"fam-{i}", cpus=64, mem=256 << 30)
                 for i in range(3)]
        mgr.store.update(lambda tx: [tx.create(n) for n in nodes])
        for n in nodes:
            agent = Agent(n.id, make_executor(n.description.hostname),
                          mgr.dispatcher, description=n.description)
            agent.start()
            agents.append(agent)
        sid = mgr.control_api.create_service(
            servedpath_deploy.spec("families", 6)).id
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and sum(
                1 for t in threading.enumerate()
                if t.name.startswith("taskmanager-")) < 6:
            time.sleep(0.05)
        servedpath_deploy.wait_assigned(mgr, sid, 6)
        started = {t.name for t in threading.enumerate()
                   if t.ident not in before}
    finally:
        for agent in agents:
            agent.stop()
        mgr.stop()
    by_family = {}
    for name in started:
        by_family.setdefault(threadcpu.family(name), set()).add(name)
    assert threadcpu.OTHER not in by_family, by_family[threadcpu.OTHER]
    assert {"agent", "agent-reporter", "agent-logs", "agent-heartbeat"} \
        <= by_family["agents"]
    assert any(n.startswith("taskmanager-") for n in by_family["agents"])
    assert {"metrics", "taskreaper", "lifecycle", "jobs", "global",
            "deallocator", "constraint-enforcer", "volume-enforcer",
            "csi-watch", "csi-work"} <= by_family["background"]
    assert {"scheduler", "replicated", "allocator", "dispatcher"} \
        <= by_family["control"]


@pytest.mark.parametrize("name,family", [
    ("agent", "agents"), ("taskmanager-1a2b3c4d", "agents"),
    ("updater-1a2b3c4d", "background"), ("raft-manager-1", "control"),
    ("raft-send-3", "control"), ("sched-commit", "control"),
    ("MainThread", "other"), ("Thread-7 (process_request_thread)", "other"),
    ("agents", "other"), ("scheduler-2", "other")])
def test_a_name_maps_to_its_family(name, family):
    assert threadcpu.family(name) == family


def test_a_thread_that_ended_drops_out_and_one_that_began_counts_whole():
    before = {1: ("agent", 1.0), 2: ("metrics", 2.0), 3: ("dispatcher", 5.0)}
    after = {1: ("agent", 1.25),            # ran 250 ms
             # 2 ended: its clock went with it
             3: ("allocator", 0.5),         # the ident again, a new thread
             4: ("MainThread", 0.125)}      # began in between
    spent = threadcpu.spent_ms(before, after)
    assert list(spent) == list(threadcpu.ARGS)
    assert spent == {"others_cpu_ms": 875.0, "agents_cpu_ms": 250.0,
                     "background_cpu_ms": 0.0, "control_cpu_ms": 500.0,
                     "other_cpu_ms": 125.0}


# --------------------------------------------- the tick's and the pass's account


class Spinners:
    """A busy thread under each name of ``SPINNERS``."""

    def __enter__(self):
        self._stop = threading.Event()
        self._threads = [threading.Thread(target=self._spin, name=name,
                                          daemon=True)
                         for name in SPINNERS]
        for t in self._threads:
            t.start()
        return self

    def _spin(self):
        while not self._stop.is_set():
            sum(range(300))

    def __exit__(self, *exc):
        self._stop.set()
        for t in self._threads:
            t.join(5.0)
        return False


def _cluster():
    """A store with 40 nodes and a service of ``REPLICAS``, and the
    passes of its deploy, each driven by hand: the orchestrator's, the
    allocator's, the scheduler's taking up the PENDING tasks and its
    tick.  Two subscriptions: one takes every
    event, one takes the blocks and wants nodes alone."""
    store = MemoryStore()
    nodes = [make_ready_node(f"n{i}", cpus=64, mem=256 << 30)
             for i in range(40)]
    store.update(lambda tx: [tx.create(n) for n in nodes])
    subs = [store.queue.subscribe(),
            store.queue.subscribe(
                lambda e: isinstance(getattr(e, "obj", None), Node),
                accepts_blocks=True)]
    orch = Orchestrator(store)
    alloc = Allocator(store)
    service = servedpath_deploy.spec("accounted", REPLICAS)
    store.update(lambda tx: tx.create(Service(id="s1", spec=service)))
    orch._handle_event(Event("create", store.raw_get(Service, "s1")))

    def reconcile():
        orch._tick_services()

    def allocate():
        for t in store.view(lambda tx: tx.find(Task)):
            alloc._handle_event(Event("create", t))
        alloc._tick()

    sched = Scheduler(store)

    def take_up():
        store.view(sched._setup_tasks_list)

    def schedule():
        assert sched.tick() == REPLICAS

    return store, subs, (reconcile, allocate, take_up, schedule)


def _clocked(step):
    """Run ``step`` on this thread; (process CPU, this thread's CPU)
    over it, in seconds."""
    p0, t0 = time.process_time(), time.thread_time()
    step()
    return time.process_time() - p0, time.thread_time() - t0


@pytest.fixture(scope="module")
def traced():
    """The deploy's three passes traced beside the spinners: the spans,
    each pass's clocks, and the window."""
    store, subs, (reconcile, allocate, take_up, schedule) = _cluster()
    tracer.reset()
    tracer.enable()
    try:
        with tracer.span("warm"):      # the profiler's import, once
            pass
        t0 = time.time()
        with Spinners():
            clocks = {"orchestrator.reconcile": _clocked(reconcile)}
            allocate()
            take_up()
            clocks["sched.tick"] = _clocked(schedule)
        t1 = time.time()
    finally:
        tracer.disable()
        spans = tracer.spans()
        tracer.reset()
        for sub in subs:
            store.queue.unsubscribe(sub)
    return {"spans": spans, "clocks": clocks, "wall": (t0, t1)}


@pytest.mark.parametrize("name", ["sched.tick", "orchestrator.reconcile"])
def test_the_span_says_what_each_family_used_while_it_ran(traced, name):
    """The families plus the span's own ``cpu`` are the process's CPU
    over the span, less what the accountant's two snapshots took on the
    span's thread: to 10 % or 5 ms."""
    (sp,) = [s for s in traced["spans"] if s.name == name]
    args = sp.args
    assert set(threadcpu.ARGS) <= set(args)
    families = [args[f"{f}_cpu_ms"] for f in (*threadcpu.FAMILIES,
                                               threadcpu.OTHER)]
    assert abs(args["others_cpu_ms"] - sum(families)) < 0.01
    # every spinner ran while the span's thread waited for the interpreter
    for family in SPINNERS.values():
        assert args[f"{family}_cpu_ms"] > 0, (family, args)
    # the two readings of a thread bracket the span and the first
    # snapshot
    assert args["accounted_ms"] >= 1e3 * sp.duration - 0.5, args
    process, own = traced["clocks"][name]
    accountant = own - sp.cpu
    assert 0 <= accountant < own
    expect = 1e3 * (process - accountant)
    got = sum(families) + 1e3 * sp.cpu
    assert abs(got - expect) <= max(0.1 * expect, 5.0), (got, expect, args)


def test_one_reconcile_pass_in_eight_carries_the_families():
    """Every pass is a span; the first and each ``ACCOUNT_EVERY_PASSES``-th
    after it carry the families, and only those take snapshots."""
    every = replicated.ACCOUNT_EVERY_PASSES
    store = MemoryStore()
    svc = Service(id="s1", spec=servedpath_deploy.spec("sampled", 1))
    store.update(lambda tx: tx.create(svc))
    orch = Orchestrator(store)
    tracer.reset()
    tracer.enable()
    try:
        taken = threadcpu.stats["thread_cpu_snapshots"]
        for _ in range(2 * every + 1):
            orch.reconcile_services = {"s1": store.raw_get(Service, "s1")}
            orch._tick_services()
        taken = threadcpu.stats["thread_cpu_snapshots"] - taken
        spans = [s for s in tracer.spans()
                 if s.name == "orchestrator.reconcile"]
    finally:
        tracer.disable()
        tracer.reset()
    assert len(spans) == 2 * every + 1
    accounted = [i for i, sp in enumerate(spans)
                 if "others_cpu_ms" in sp.args]
    assert accounted == [0, every, 2 * every]
    assert taken == 2 * len(accounted)


def test_with_the_tracer_off_no_clock_is_read():
    store, subs, passes = _cluster()
    assert not tracer.enabled
    taken = threadcpu.stats["thread_cpu_snapshots"]
    assert threadcpu.begin() is None
    with Spinners():
        for step in passes:
            step()
    assert threadcpu.stats["thread_cpu_snapshots"] == taken
    for sub in subs:
        store.queue.unsubscribe(sub)


def test_under_a_time_source_the_span_carries_no_family():
    with VirtualClock(1000.0):
        store, subs, passes = _cluster()
        tracer.reset()
        tracer.enable()
        try:
            taken = threadcpu.stats["thread_cpu_snapshots"]
            assert threadcpu.begin() is None
            for step in passes:
                step()
            assert threadcpu.stats["thread_cpu_snapshots"] == taken
            spans = tracer.spans()
        finally:
            tracer.disable()
            tracer.reset()
    for name in ("sched.tick", "orchestrator.reconcile"):
        (sp,) = [s for s in spans if s.name == name]
        assert not {*threadcpu.ARGS, "accounted_ms"} & set(sp.args), \
            sp.args


# ------------------------------------------------------- the publish's reach


def _three_subscriptions(store, blocks):
    """Every event; task events alone; node events alone (the last takes
    blocks as they are where ``blocks``)."""
    return [store.queue.subscribe(),
            store.queue.subscribe(
                lambda e: isinstance(getattr(e, "obj", None), Task)),
            store.queue.subscribe(
                lambda e: isinstance(getattr(e, "obj", None), Node),
                accepts_blocks=blocks)]


@pytest.mark.parametrize("how,blocks,delivered", [
    ("per_task", False, 2),
    # the block is buffered whole for a per-item consumer: its thread
    # wakes, whatever its predicate will keep
    ("block", False, 3),
    ("block", True, 2)])
def test_delivered_counts_the_subscriptions_the_commit_wakes(how, blocks,
                                                             delivered):
    store = MemoryStore()
    nodes = [make_ready_node(f"n{i}") for i in range(4)]
    svc, tasks = make_service_with_tasks(8)
    store.update(lambda tx: [tx.create(o) for o in (*nodes, svc, *tasks)])
    subs = _three_subscriptions(store, blocks)
    stored = store.view(lambda tx: tx.find(Task))
    tracer.reset()
    tracer.enable()
    try:
        if how == "block":
            committed, failed = store.commit_task_block(
                stored, [nodes[i % 4].id for i in range(len(stored))],
                int(TaskState.ASSIGNED), "assigned", lambda t, nid: None,
                lambda t, nid: False)
        else:
            new = []
            for i, t in enumerate(stored):
                t = t.copy()
                t.node_id = nodes[i % 4].id
                t.status.state = TaskState.ASSIGNED
                new.append(t)
            committed, failed = store.bulk_update_tasks(
                new, lambda t: None, lambda t: False)
        assert len(committed) == len(stored) and not failed
        spans = [s for s in tracer.spans() if s.name == "commit.publish"]
    finally:
        tracer.disable()
        tracer.reset()
    assert spans
    assert {s.args["subscribers"] for s in spans} == {3}
    assert {s.args["delivered"] for s in spans} == {delivered}
    # and each of them has something buffered to wake for
    assert sum(1 for sub in subs if sub.backlog()) == delivered


def test_with_the_tracer_off_the_publish_counts_nothing():
    store = MemoryStore()
    subs = _three_subscriptions(store, blocks=False)
    node = make_ready_node("n0")
    store.update(lambda tx: tx.create(node))
    assert not tracer.enabled
    with store._publishing() as delivered:
        assert delivered is None
    assert [sub.poll() is not None for sub in subs] == [True, False, True]


# ------------------------------------------------------- the six metrics


@pytest.mark.parametrize("cell", CELLS)
def test_the_six_metrics_read_a_number_through_the_readers(traced, cell):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    obs = readers.Observations()
    t0, t1 = traced["wall"]
    obs.window_s = t1 - t0
    obs.window_wall = (t0, t1)
    obs.spans = [(s.thread, s.name, s.start, s.end, s.args)
                 for s in traced["spans"] if t0 <= s.start < t1]
    line = readers.read_all(cell, obs, bench["per_layer"])
    for name, (span, arg) in METRICS.items():
        with open(os.path.join(REPO, "benchmark", "layer_metrics",
                               name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == {"kind": "span", "reduce": "arg_mean",
                                  "span": span, "arg": arg}
        assert entries[name]["source"] == "program_span"
        assert entries[name]["moves"] == spec["moves"] == "assign_p50_ms"
        assert cell in entries[name]["workloads"]
        rows = [s.args[arg] for s in traced["spans"] if s.name == span
                and s.args and arg in s.args]
        assert line[name] == {"value": sum(rows) / len(rows),
                              "unit": spec["unit"]}, name
    assert line["tick_others_cpu_ms"]["value"] > 0
    assert line["reconcile_others_cpu_ms"]["value"] > 0
    # the every-event subscription; the other wants nodes alone
    assert line["publish_delivered"]["value"] == 1.0
