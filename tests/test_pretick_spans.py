"""The wait before the tick accounts for itself (ISSUE 38): a deploy's
path from ``create_service`` to PENDING is timed leg by leg on the
threads that do the work.  ``api.create_service`` and
``orchestrator.service`` say what their thread waited for the update lock
and what it spent off the CPU, ``orchestrator.service`` how old the
commit was that queued it, and the allocator has its first spans, one a
batch: what it took up, how long that had waited since it was created,
and what became of it.

Tier-1, on the forced CPU, through a live ``Manager()``: counts, names,
ages against the store's own stamps, and that off means off; never a
speed."""

import bisect
import os
import sys
import threading
import time

import pytest

pytest.importorskip("cryptography")   # the manager's CA bootstrap

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from swarmkit_tpu.manager import Manager, allocator as allocator_mod  # noqa: E402
from swarmkit_tpu.manager.allocator import (  # noqa: E402
    ALLOCATED_STATUS_MESSAGE, Allocator,
)
from swarmkit_tpu.manager.dispatcher import Config_  # noqa: E402
from swarmkit_tpu.models import Service, Task, TaskState  # noqa: E402
from swarmkit_tpu.obs import tracer  # noqa: E402
from swarmkit_tpu.obs.report import (  # noqa: E402
    follow_service, validate_chrome_trace,
)
from swarmkit_tpu.orchestrator.replicated import Orchestrator  # noqa: E402
from swarmkit_tpu.sim.clock import VirtualClock  # noqa: E402
from swarmkit_tpu.state import store as store_mod  # noqa: E402
from swarmkit_tpu.state.events import Event  # noqa: E402
from swarmkit_tpu.state.store import MemoryStore, lock_waited_s  # noqa: E402
from swarmkit_tpu.state.watch import Closed  # noqa: E402

import servedpath_deploy  # noqa: E402
from test_scheduler import make_ready_node  # noqa: E402

#: the traced deploys, in the order they are created: one whose tasks
#: pass ``MAX_CHANGES_PER_TX`` (several store transactions, so several
#: batches of the allocator), one small, and two back to back
TRACED = (("big", 450), ("small", 5), ("pair-a", 60), ("pair-b", 60))
#: the deploy made first, with the tracer off
OFF_REPLICAS = 30
#: a span of the path -> the arguments it always carries on this machine
ARGS = {
    "api.create_service": {"service", "lock_wait_ms", "offcpu_ms"},
    "orchestrator.reconcile": {"kind", "services", "created",
                               "wait_max_ms"},
    "orchestrator.service": {"service", "wait_ms", "created", "batches",
                             "lock_wait_ms", "offcpu_ms"},
    "allocator.tasks": {"tasks", "allocated", "deferred", "flushes",
                        "service", "services", "wait_mean_ms",
                        "wait_max_ms", "lock_wait_ms", "offcpu_ms"},
}
#: read off the machine: left out under an installed time source
MACHINE = {"lock_wait_ms", "offcpu_ms"}


class PendingWatch:
    """Every task's NEW -> PENDING event as the store published it: the
    stamps the spans' ages are held to."""

    def __init__(self, store):
        _, self.sub = store.view_and_watch(lambda tx: None)
        #: task id -> (service id, meta.created_at, the PENDING stamp)
        self.pending = {}
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="pending-watch")
        self._thread.start()

    def _run(self):
        while True:
            try:
                ev = self.sub.get(timeout=0.2)
            except TimeoutError:
                continue
            except Closed:
                return
            t = getattr(ev, "obj", None)
            if isinstance(t, Task) and ev.action == "update" \
                    and t.status.state == TaskState.PENDING \
                    and t.status.message == ALLOCATED_STATUS_MESSAGE \
                    and t.id not in self.pending:
                self.pending[t.id] = (t.service_id, t.meta.created_at,
                                      t.status.timestamp)

    def wait(self, n: int, timeout: float = 60.0) -> None:
        deadline = time.monotonic() + timeout
        while len(self.pending) < n and time.monotonic() < deadline:
            time.sleep(0.02)
        assert len(self.pending) >= n, (len(self.pending), n)


@pytest.fixture(scope="module")
def deploy():
    """One manager, a deploy with the tracer off and four with it on.
    {"off": what the untraced deploy left behind, "spans", "doc",
    "wall": the enabled interval on the wall clock, "services": name ->
    id, "pending": the watch's table, "contended": the service created
    while another thread held the update lock, "taken_up": the calls of
    the allocator's helper that computes a batch's ages}."""
    mgr = Manager(dispatcher_config=Config_(heartbeat_period=600.0))
    mgr.run()
    calls = []
    taken_up = allocator_mod._taken_up
    allocator_mod._taken_up = lambda tasks: (calls.append(len(tasks)),
                                             taken_up(tasks))[1]
    try:
        # every group rides the host: nothing compiles in this module
        mgr.scheduler.batch_planner._launch_overhead = 10.0
        nodes = [make_ready_node(f"n{i}", cpus=640, mem=1024 << 30)
                 for i in range(4)]
        mgr.store.update(lambda tx: [tx.create(n) for n in nodes])
        api = mgr.control_api
        watch = PendingWatch(mgr.store)

        # ---- off
        tracer.disable()
        tracer.reset()
        waited0 = lock_waited_s()
        off_id = api.create_service(
            servedpath_deploy.spec("untraced", OFF_REPLICAS)).id
        servedpath_deploy.wait_assigned(mgr, off_id, OFF_REPLICAS)
        servedpath_deploy.contend(mgr.store)
        off = {"spans": len(tracer.spans()), "taken_up": list(calls),
               "waited": lock_waited_s() - waited0,
               "queued_at": dict(mgr.replicated._queued_at),
               "pending": len(watch.pending)}

        # ---- on
        tracer.reset()
        t0 = time.time()
        tracer.enable()
        ids = {}
        for name, k in TRACED:
            ids[name] = api.create_service(
                servedpath_deploy.spec(name, k)).id
            if name != "pair-a":      # pair-b follows it at once
                servedpath_deploy.wait_assigned(mgr, ids[name], k)
        servedpath_deploy.wait_assigned(mgr, ids["pair-a"],
                                        dict(TRACED)["pair-a"])
        # a writer made to wait: another thread holds the lock for 50 ms
        # while this one creates a service
        holding = threading.Event()

        def hold(tx):
            holding.set()
            time.sleep(0.05)
        holder = threading.Thread(target=lambda: mgr.store.update(hold),
                                  name="the-holder", daemon=True)
        holder.start()
        assert holding.wait(5.0)
        contended = api.create_service(
            servedpath_deploy.spec("contended", 3)).id
        holder.join(5.0)
        servedpath_deploy.wait_assigned(mgr, contended, 3)
        watch.wait(OFF_REPLICAS + sum(k for _, k in TRACED) + 3)
        time.sleep(0.1)
        tracer.disable()
        t1 = time.time()
        doc = tracer.to_chrome()
        spans = tracer.spans()
    finally:
        allocator_mod._taken_up = taken_up
        tracer.disable()
        tracer.reset()
        mgr.stop()
    return {"off": off, "spans": spans, "doc": doc, "wall": (t0, t1),
            "services": ids, "pending": dict(watch.pending),
            "contended": contended, "taken_up": len(calls)}


def _named(deploy, name):
    return sorted((s for s in deploy["spans"] if s.name == name),
                  key=lambda s: s.start)


def _batches(deploy):
    """[(span, [(created_at, PENDING stamp) of the tasks whose stamp
    lies in it])]: each task goes to the last batch that began before
    its stamp."""
    spans = _named(deploy, "allocator.tasks")
    starts = [s.start for s in spans]
    rows = [(s, []) for s in spans]
    for _sid, created, stamp in deploy["pending"].values():
        if stamp >= deploy["wall"][0]:
            i = bisect.bisect_right(starts, stamp) - 1
            assert i >= 0, "a task went PENDING before any batch began"
            rows[i][1].append((created, stamp))
    return rows


# ------------------------------------------------- the allocator's batches

def test_every_task_lies_in_the_one_batch_that_counts_it(deploy):
    rows = _batches(deploy)
    traced = sum(k for _, k in TRACED) + 3
    assert sum(len(tasks) for _s, tasks in rows) == traced
    for sp, tasks in rows:
        # the stamp lies inside the span that began last before it, so in
        # that one alone: the allocator's spans follow one another
        assert all(stamp <= sp.end + 1e-4 for _c, stamp in tasks)
        assert sp.args["allocated"] == len(tasks)
        assert sp.args["tasks"] == sp.args["allocated"] \
            + sp.args["deferred"]
        assert sp.args["deferred"] == 0 and sp.args["flushes"] >= 1
        assert sp.thread == "allocator" and sp.cat == "allocator"
    assert sum(sp.args["allocated"] for sp, _ in rows) == traced
    # the large deploy came to the allocator in more than one commit
    big = [sp for sp, _ in rows
           if sp.args["service"] == deploy["services"]["big"]]
    assert len(big) >= 2
    assert sum(sp.args["allocated"] for sp in big) == dict(TRACED)["big"]
    # nothing to allocate, no span: the services carry no endpoint
    assert not _named(deploy, "allocator.services")
    assert not _named(deploy, "allocator.networks")


def test_a_batch_s_wait_is_the_age_of_its_tasks_creation(deploy):
    rows = _batches(deploy)
    assert rows
    for sp, tasks in rows:
        mean = 1e3 * sum(stamp - created for created, stamp in tasks) \
            / len(tasks)
        args = sp.args
        # taken up after the span began, stamped before it ended
        assert args["wait_mean_ms"] - 0.05 <= mean \
            <= args["wait_mean_ms"] + 1e3 * sp.duration + 0.05, args
        assert 0 <= args["wait_mean_ms"] <= args["wait_max_ms"]
        assert args["wait_max_ms"] < 1e3 * (deploy["wall"][1]
                                            - deploy["wall"][0])


# ------------------------------------------------------ every span's account

@pytest.mark.parametrize("name", sorted(ARGS))
def test_span_carries_its_arguments_inside_the_enabled_interval(deploy,
                                                                name):
    spans = _named(deploy, name)
    assert len(spans) >= len(TRACED)
    t0, t1 = deploy["wall"]
    for sp in spans:
        assert ARGS[name] <= set(sp.args), (name, sp.args)
        # the tracer's one clock: the wall clock the window is cut on
        assert t0 - 1e-3 <= sp.start <= sp.end <= t1 + 1e-3
        for key in ARGS[name] - {"service", "kind", "offcpu_ms"}:
            assert sp.args[key] >= 0, (name, key, sp.args)
        if "offcpu_ms" in ARGS[name]:
            # wall less thread CPU; a thread clock that ticks in steps
            # may charge a short span a whole step
            assert -10.5 <= sp.args["offcpu_ms"] <= 1e3 * sp.duration + 1e-3
            assert sp.args["lock_wait_ms"] <= 1e3 * sp.duration + 1e-3
    assert validate_chrome_trace(deploy["doc"]) == []


def test_a_service_s_wait_is_the_age_of_the_commit_that_queued_it(deploy):
    wall_ms = 1e3 * (deploy["wall"][1] - deploy["wall"][0])
    by_service = {}
    for sp in _named(deploy, "orchestrator.service"):
        assert 0 <= sp.args["wait_ms"] < wall_ms
        by_service.setdefault(sp.args["service"], sp)
    for name, k in TRACED:
        sp = by_service[deploy["services"][name]]
        assert sp.args["created"] == k
        # the reconcile began after the RPC's commit and before it was old
        rpc = [s for s in _named(deploy, "api.create_service")
               if s.args["service"] == sp.args["service"]][0]
        assert sp.start >= rpc.start
        assert sp.args["wait_ms"] <= 1e3 * (sp.start - rpc.start) + 0.05
    for batch in _named(deploy, "orchestrator.reconcile"):
        inside = [s.args["wait_ms"]
                  for s in _named(deploy, "orchestrator.service")
                  if batch.start <= s.start and s.end <= batch.end]
        assert batch.args["wait_max_ms"] == max(inside)
        assert batch.args["services"] == len(inside)


def test_one_service_id_from_the_rpc_through_the_allocator_to_the_commit(
        deploy):
    sid = deploy["services"]["big"]
    rows = follow_service(deploy["doc"], sid)
    names = [r["name"] for r in rows]
    assert names[0] == "api.create_service"
    assert names.index("api.create_service") \
        < names.index("orchestrator.service") \
        < names.index("allocator.tasks")
    commits = [i for i, n in enumerate(names)
               if n in ("sched.commit", "sched.apply_decisions")]
    assert commits and names.index("allocator.tasks") < commits[0]
    leg = [r for r in rows if r["name"] == "allocator.tasks"]
    assert all(r["thread"] == "allocator" for r in leg)
    assert sum(r["args"]["allocated"] for r in leg) == dict(TRACED)["big"]


# --------------------------------------------------------- who waited for it

def test_a_held_lock_shows_in_the_waiter_s_account_and_in_no_other(deploy):
    sid = deploy["contended"]
    rpc = [s for s in _named(deploy, "api.create_service")
           if s.args["service"] == sid][0]
    assert rpc.args["lock_wait_ms"] >= 30.0
    assert rpc.args["offcpu_ms"] >= rpc.args["lock_wait_ms"] - 10.0
    # the threads that took the deploy on came to a free lock
    for name in ("orchestrator.service", "allocator.tasks"):
        leg = [s for s in _named(deploy, name)
               if s.args["service"] == sid]
        assert leg and all(s.args["lock_wait_ms"] < 25.0 for s in leg)
    waits = [s for s in _named(deploy, "store.lock_wait")
             if s.args["holder"] == "the-holder"]
    assert len(waits) == 1 and waits[0].thread == rpc.thread
    assert abs(1e3 * waits[0].duration - rpc.args["lock_wait_ms"]) < 5.0


@pytest.mark.parametrize("on", [True, False])
def test_the_account_is_per_thread_and_kept_only_while_the_tracer_is_on(on):
    store = MemoryStore()
    grown = {}

    def measured(name, fn):
        def run():
            before = lock_waited_s()
            fn()
            grown[name] = lock_waited_s() - before
        return threading.Thread(target=run, name=name, daemon=True)

    holding = threading.Event()

    def hold(tx):
        holding.set()
        time.sleep(0.05)
    tracer.reset()
    if on:
        tracer.enable()
    try:
        holder = measured("holder", lambda: store.update(hold))
        holder.start()
        assert holding.wait(5.0)
        waiter = measured("waiter", lambda: store.update(lambda tx: None))
        waiter.start()
        waiter.join(5.0)
        holder.join(5.0)
        late = measured("late", lambda: store.update(lambda tx: None))
        late.start()
        late.join(5.0)
    finally:
        tracer.disable()
        tracer.reset()
    if on:
        assert grown["waiter"] >= 0.03
        assert grown["holder"] < 0.01 and grown["late"] < 0.01
    else:
        assert grown == {"holder": 0.0, "waiter": 0.0, "late": 0.0}


# ------------------------------------------------------------- off means off

def test_with_the_tracer_off_the_deploy_leaves_nothing_behind(deploy):
    off = deploy["off"]
    assert off["pending"] == OFF_REPLICAS      # the deploy did happen
    assert off["spans"] == 0
    assert off["taken_up"] == []               # no age was computed
    assert off["queued_at"] == {}              # no stamp was kept
    assert off["waited"] == 0.0                # a 20 ms wait, not summed
    # and with it on, one call of the helper a batch
    assert deploy["taken_up"] == len(_named(deploy, "allocator.tasks")) > 0


def test_a_disabled_tracer_costs_the_lock_one_test():
    """``_TimedLock.acquire`` with the tracer off: no account, no span,
    whatever the wait."""
    assert not tracer.enabled
    before = lock_waited_s()
    n0 = len(tracer.spans())
    servedpath_deploy.contend(MemoryStore(), hold_s=0.02)
    assert lock_waited_s() == before and len(tracer.spans()) == n0
    assert store_mod.LOCK_WAIT_SPAN_S == 0.001


# ------------------------------------------------ an installed time source

class _Stamped:
    """A task whose creation stamp says when it is read."""

    def __init__(self, task, reads):
        self._task, self._reads = task, reads

    def __getattr__(self, name):
        if name == "meta":
            self._reads.append(self._task.id)
        return getattr(self._task, name)


def _virtual_legs():
    """The allocator and the orchestrator driven by hand under a virtual
    clock: {span name: args}."""
    with VirtualClock(1000.0) as clk:
        store = MemoryStore()
        orch = Orchestrator(store)
        alloc = Allocator(store)
        tracer.reset()
        tracer.enable()
        try:
            spec = servedpath_deploy.spec("virtual", 4)
            service = Service(id="s1", spec=spec)
            store.update(lambda tx: tx.create(service))
            stored = store.raw_get(Service, "s1")
            orch._handle_event(Event("create", stored))
            clk.advance_to(1000.5)
            orch._tick_services()
            tasks = store.view(lambda tx: tx.find(Task))
            assert len(tasks) == 4
            for t in tasks:
                alloc._handle_event(Event("create", t))
            clk.advance_to(1000.75)
            alloc._tick()
            tracer.disable()
            return {s.name: s.args for s in tracer.spans()}, \
                tracer.to_json()
        finally:
            tracer.disable()
            tracer.reset()


def test_under_a_time_source_the_ages_stay_and_the_machine_is_left_out():
    legs, doc = _virtual_legs()
    assert legs["orchestrator.service"]["wait_ms"] == 500.0
    assert legs["orchestrator.reconcile"]["wait_max_ms"] == 500.0
    tasks = legs["allocator.tasks"]
    assert tasks["wait_mean_ms"] == tasks["wait_max_ms"] == 250.0
    assert (tasks["tasks"], tasks["allocated"], tasks["deferred"]) \
        == (4, 4, 0)
    for name in ("orchestrator.service", "allocator.tasks"):
        assert not MACHINE & set(legs[name]), legs[name]
        assert ARGS[name] - MACHINE <= set(legs[name])
    # a pure function of the clock: byte for byte the same again
    assert _virtual_legs()[1] == doc


def test_with_the_tracer_off_no_task_s_creation_stamp_is_read():
    assert not tracer.enabled
    store = MemoryStore()
    alloc = Allocator(store)
    service = Service(id="s1", spec=servedpath_deploy.spec("plain", 3))
    store.update(lambda tx: tx.create(service))
    orch = Orchestrator(store)
    orch._handle_event(Event("create", store.raw_get(Service, "s1")))
    assert orch._queued_at == {}
    orch._tick_services()
    reads = []
    for t in store.view(lambda tx: tx.find(Task)):
        alloc._pending_tasks[t.id] = _Stamped(t, reads)
    alloc._tick()
    assert reads == []
    assert all(t.status.state == TaskState.PENDING
               for t in store.view(lambda tx: tx.find(Task)))
    # the same batch with the tracer on reads every one
    store.update(lambda tx: [tx.create(t) for t in [
        Task(id=f"x{i}", service_id="s1", slot=10 + i) for i in range(3)]])
    tracer.reset()
    tracer.enable()
    try:
        for t in store.view(lambda tx: tx.find(Task)):
            if t.status.state == TaskState.NEW:
                alloc._pending_tasks[t.id] = _Stamped(t, reads)
        alloc._tick()
        spans = [s for s in tracer.spans() if s.name == "allocator.tasks"]
    finally:
        tracer.disable()
        tracer.reset()
    assert set(reads) == {"x0", "x1", "x2"}
    assert len(spans) == 1 and spans[0].args["allocated"] == 3


# ------------------------------------------------------- the scripts' reading

def test_the_served_path_s_summary_gives_the_legs_and_the_waiters(deploy):
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "servedpath_trace.py")
    spec = importlib.util.spec_from_file_location("servedpath_trace", path)
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    out = script.summary(deploy["doc"])
    legs = out["before_the_tick_ms"]
    assert set(legs) == {"api", "orchestrator", "allocator"}
    traced = sum(k for _, k in TRACED) + 3
    assert legs["api"]["spans"] == len(TRACED) + 1
    assert legs["orchestrator"]["tasks"] == legs["allocator"]["tasks"] \
        == traced
    for leg in legs.values():
        assert leg["ms"] > 0 and leg["lock_wait_ms"] >= 0
        assert leg["offcpu_ms"] <= leg["ms"] + 1e-3
    for name in ("orchestrator", "allocator"):
        leg = legs[name]
        assert 0 <= leg["wait_ms"] <= leg["wait_max_ms"]
        assert 0 <= leg["wait_task_ms"] <= leg["wait_max_ms"]
        assert leg["task_ms"] > 0
    # the 50 ms hold: by the thread that held and by the one that waited
    assert out["lock_wait_s_by_holder"]["the-holder"] >= 0.03
    assert out["lock_wait_s_by_waiter"]["MainThread"] >= 0.03
    assert sum(out["lock_wait_s_by_waiter"].values()) == pytest.approx(
        sum(out["lock_wait_s_by_holder"].values()))


# ------------------------------------------- the allocator's other two passes

def test_the_other_two_passes_have_a_span_where_they_have_work():
    """A network without a subnet, a service with a port to publish on
    it, and the service's tasks, which wait behind both: one span a pass
    that had work, none for a pass that had none."""
    from swarmkit_tpu.models import Annotations, Network
    from swarmkit_tpu.models.specs import NetworkSpec
    from swarmkit_tpu.models.types import (
        EndpointSpec, NetworkAttachmentConfig, PortConfig)
    store = MemoryStore()
    alloc = Allocator(store)
    net = Network(id="net1", spec=NetworkSpec(
        annotations=Annotations(name="overlay")))
    spec = servedpath_deploy.spec("published", 2)
    spec.endpoint = EndpointSpec(ports=[PortConfig(target_port=80)])
    spec.task.networks = [NetworkAttachmentConfig(target="net1")]
    service = Service(id="s1", spec=spec)
    tasks = [Task(id=f"t{i}", service_id="s1", slot=i + 1,
                  spec=spec.task.copy()) for i in range(2)]
    tracer.reset()
    tracer.enable()
    try:
        store.update(lambda tx: [tx.create(net), tx.create(service)]
                     + [tx.create(t) for t in tasks])
        for obj in (net, service, *tasks):
            alloc._handle_event(Event(
                "create", store.raw_get(type(obj), obj.id)))
        alloc._tick()       # all three passes, in their order
        alloc._tick()       # nothing left: no span
        spans = [s for s in tracer.spans() if s.cat == "allocator"]
    finally:
        tracer.disable()
        tracer.reset()
    assert [s.name for s in spans] == [
        "allocator.networks", "allocator.services", "allocator.tasks"]
    assert spans[0].args == {"networks": 1}
    assert spans[1].args == {"services": 1, "service": "s1"}
    assert (spans[2].args["tasks"], spans[2].args["allocated"],
            spans[2].args["deferred"], spans[2].args["service"]) \
        == (2, 2, 0, "s1")
    stored = store.view(lambda tx: tx.find(Task))
    assert all(t.status.state == TaskState.PENDING and t.networks
               and t.endpoint.ports[0].published_port for t in stored)


def test_tasks_behind_an_unallocated_service_are_counted_deferred():
    from swarmkit_tpu.models.types import NetworkAttachmentConfig
    store = MemoryStore()
    alloc = Allocator(store)
    spec = servedpath_deploy.spec("waiting", 2)
    spec.task.networks = [NetworkAttachmentConfig(target="no-such-net")]
    service = Service(id="s1", spec=spec)
    tasks = [Task(id=f"t{i}", service_id="s1", slot=i + 1,
                  spec=spec.task.copy()) for i in range(2)]
    store.update(lambda tx: [tx.create(service)]
                 + [tx.create(t) for t in tasks])
    tracer.reset()
    tracer.enable()
    try:
        for t in tasks:
            alloc._handle_event(Event("create", store.raw_get(Task, t.id)))
        alloc._tick()
        batch, = [s for s in tracer.spans() if s.name == "allocator.tasks"]
    finally:
        tracer.disable()
        tracer.reset()
    assert (batch.args["tasks"], batch.args["allocated"],
            batch.args["deferred"]) == (2, 0, 2)
    assert set(alloc._pending_tasks) == {"t0", "t1"}
