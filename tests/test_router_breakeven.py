"""The break-even router (``ops/planner.py _below_break_even``): a group
rides the host route only while a scan of every mirrored node plus the
host oracle's per-task work is estimated under four fifths of a device
launch.  The launch overhead is pinned (5 ms, as ``servedpath_deploy``
pins it), and so is the cost of the scan (3 us a node) wherever a route
depends on it: what the planner times on a loaded runner is no test's
to assert, only that it times, shares and shows it."""

import time
from types import SimpleNamespace

import pytest

from swarmkit_tpu.manager import Manager
from swarmkit_tpu.manager.dispatcher import Config_
from swarmkit_tpu.models import Resources, Task, TaskState
from swarmkit_tpu.obs import tracer
from swarmkit_tpu.ops import TPUPlanner
from swarmkit_tpu.scheduler import Scheduler
from swarmkit_tpu.scheduler.nodeinfo import NodeInfo
from swarmkit_tpu.state import MemoryStore
from swarmkit_tpu.state.store import ByService

from servedpath_deploy import spec, wait_assigned
from test_scheduler import make_ready_node, make_service_with_tasks

OVERHEAD_S = 0.005
PER_NODE_S = 3e-6
RESERVE = Resources(nano_cpus=10 ** 8, memory_bytes=8 << 20)


def mirror(n: int):
    """A scheduler stand-in whose node mirror counts ``n`` nodes: up to
    1,024 NodeInfos of their own (what the planner's timing samples),
    the rest of the keys laid over them."""
    infos = []
    for i in range(min(n, 1024)):
        node = make_ready_node(f"m{i:04d}", cpus=64, mem=256 << 30)
        infos.append(NodeInfo(node, None, node.description.resources))
    nodes = {i: infos[i % len(infos)] for i in range(n)}
    return SimpleNamespace(node_set=SimpleNamespace(nodes=nodes))


def per_node_counts(tasks) -> list:
    counts = {}
    for t in tasks:
        counts[t.node_id] = counts.get(t.node_id, 0) + 1
    return sorted(counts.values())


def a_task() -> Task:
    return make_service_with_tasks(1, reservations=RESERVE)[1][0]


def sized(n: int):
    """A mirror of ``n`` nodes for a planner whose scan cost is pinned:
    the router reads its size alone."""
    return SimpleNamespace(node_set=SimpleNamespace(nodes=range(n)))


def fresh_planner(monkeypatch) -> TPUPlanner:
    # the scan's cost is shared by the process: each case measures anew
    monkeypatch.setattr(TPUPlanner, "_host_cost_per_node_shared", None)
    planner = TPUPlanner()
    planner._launch_overhead = OVERHEAD_S
    return planner


def to_host(planner, sched, n_tasks: int, scan: bool = True) -> bool:
    return planner._below_break_even(sched, a_task(), n_tasks, scan)


def real_scheduler(planner, n_nodes: int, groups):
    """A scheduler over a store of ``n_nodes`` nodes, its mirror built,
    with one pending service of each size in ``groups``; returns it and
    the task groups."""
    store = MemoryStore()
    nodes = [make_ready_node(f"n{i:03d}", cpus=64, mem=256 << 30)
             for i in range(n_nodes)]
    made = [make_service_with_tasks(k, reservations=RESERVE)
            for k in groups]

    def fill(tx):
        for n in nodes:
            tx.create(n)
        for svc, tasks in made:
            tx.create(svc)
            for t in tasks:
                tx.create(t)
    store.update(fill)
    sched = Scheduler(store, batch_planner=planner)
    store.view(sched._setup_tasks_list)
    return sched, [{t.id: t for t in tasks} for _svc, tasks in made]


def case_48_nodes_break_even_70_to_80(planner):
    planner.host_cost_per_node = PER_NODE_S
    sched = sized(48)
    assert to_host(planner, sched, 6)
    assert not to_host(planner, sched, 160)
    first_on_device = next(k for k in range(1, 200)
                           if not to_host(planner, sched, k))
    assert 70 <= first_on_device <= 80, first_on_device


def case_scan_is_timed_once_shared_and_shown(planner):
    assert planner.host_cost_per_node is None   # until it routes
    to_host(planner, mirror(48), 6)
    assert planner.host_cost_per_node > 0
    assert TPUPlanner._host_cost_per_node_shared \
        == planner.host_cost_per_node \
        == planner.stats["host_cost_per_node"]
    assert not planner.stats.get("host_cost_probe_failures")
    # the next planner of the process takes the figure, not a timing
    other = TPUPlanner()
    other._launch_overhead = OVERHEAD_S
    to_host(other, sized(48), 6)   # nothing here a timing could scan
    assert other.host_cost_per_node == planner.host_cost_per_node


def case_10k_nodes_one_task_rides_the_device(planner):
    planner.host_cost_per_node = PER_NODE_S
    assert not to_host(planner, sized(10_000), 1)


def case_100k_nodes_one_task_rides_the_device(planner):
    planner.host_cost_per_node = PER_NODE_S
    assert not to_host(planner, sized(100_000), 1)


def case_monotone_in_tasks_and_in_nodes(planner):
    planner.host_cost_per_node = PER_NODE_S
    sizes = (0, 1, 48, 300, 1_000, 1_300, 2_000, 10_000)

    def host(k, n):
        return to_host(planner, sized(n), k)
    for n in sizes:
        answers = [host(k, n) for k in range(1, 200)]
        # once a size goes to the device every larger one does
        assert answers == sorted(answers, reverse=True), n
    for k in (1, 6, 40, 79, 80, 160):
        answers = [host(k, n) for n in sizes]
        assert answers == sorted(answers, reverse=True), k
    # the crossover for a one-task group: where a scan meets a launch
    assert host(1, 1_300) and not host(1, 2_000)
    # no scan (the pre-validate loop): the old break-even, 80 tasks,
    # whatever the mirror's size
    for n in (0, 10_000):
        assert to_host(planner, sized(n), 79, scan=False)
        assert not to_host(planner, sized(n), 80, scan=False)


def case_three_sites_share_the_predicate(planner):
    planner.host_cost_per_node = PER_NODE_S
    asked = []
    predicate = planner._below_break_even

    def spy(sched, t, n_tasks, scan=True, sp=None):
        answer = predicate(sched, t, n_tasks, scan, sp)
        asked.append((n_tasks, scan, answer))
        return answer
    planner._below_break_even = spy
    sched, (small, large, large2) = real_scheduler(planner, 48,
                                                   (6, 160, 160))
    # dispatch_group: None is the host route
    assert planner.dispatch_group(sched, dict(small), {}) is None
    assert planner.stats["groups_small_to_host"] == 1
    handle = planner.dispatch_group(sched, dict(large), {})
    assert handle is not None and planner.fetch_group(handle)
    # probe_fused_run: the run ends at the first group for the host
    assert planner.probe_fused_run(sched, [small, large], 0) == []
    assert len(planner.probe_fused_run(sched, [large, large2, small],
                                       0)) == 2
    # the pre-validate site: its host loop scans no node
    few = list(small.values())
    assert planner.validate_preassigned(sched, few, {}) == few
    assert asked == [(6, True, True), (160, True, False),
                     (6, True, True),
                     (160, True, False), (160, True, False),
                     (6, True, True),
                     (6, False, True)]
    # and the predicate is a pure function of (n_tasks, nodes)
    for n_tasks, scan, answer in asked:
        assert predicate(sched, few[0], n_tasks, scan) == answer
    assert planner._route_costs(sched, few[0], 6)[2] == 48
    assert planner._route_costs(sched, few[0], 6, scan=False)[2] == 0


def case_failed_timing_falls_back_to_the_constant(planner):
    broken = SimpleNamespace(node_set=SimpleNamespace(
        nodes={i: object() for i in range(10_000)}))   # no NodeInfo
    assert not to_host(planner, broken, 1)
    assert planner.host_cost_per_node == TPUPlanner.HOST_COST_PER_NODE_FALLBACK
    assert planner.stats["host_cost_probe_failures"] == 1
    # a failed timing is not shared: the next planner measures again
    assert TPUPlanner._host_cost_per_node_shared is None
    assert to_host(planner, mirror(48), 6)


def case_routing_off_forces_the_device(planner):
    planner.enable_small_group_routing = False
    for n in (0, 48, 10_000):
        assert not to_host(planner, mirror(n), 1)
        assert not to_host(planner, mirror(n), 1, scan=False)
    # and asks for neither probe
    assert planner.host_cost_per_node is None


CASES = [case_48_nodes_break_even_70_to_80,
         case_scan_is_timed_once_shared_and_shown,
         case_10k_nodes_one_task_rides_the_device,
         case_100k_nodes_one_task_rides_the_device,
         case_monotone_in_tasks_and_in_nodes,
         case_three_sites_share_the_predicate,
         case_failed_timing_falls_back_to_the_constant,
         case_routing_off_forces_the_device]


@pytest.mark.parametrize("case", CASES,
                         ids=[c.__name__[5:] for c in CASES])
def test_router(case, monkeypatch):
    case(fresh_planner(monkeypatch))


def test_served_small_deploy_on_a_large_cluster_rides_the_device():
    """4,096 nodes behind a live ``Manager()``: a deploy of 3 replicas
    takes ``plan.route`` ``device``, the span says why, and the tasks
    land as the host oracle levels them."""
    n_nodes, replicas = 4096, 3
    mgr = Manager(dispatcher_config=Config_(heartbeat_period=600.0))
    mgr.run()
    try:
        planner = mgr.scheduler.batch_planner
        planner._launch_overhead = OVERHEAD_S
        planner.host_cost_per_node = PER_NODE_S
        nodes = [make_ready_node(f"n{i:04d}", cpus=64, mem=256 << 30)
                 for i in range(n_nodes)]
        mgr.store.update(lambda tx: [tx.create(n) for n in nodes])
        deadline = time.monotonic() + 30.0
        while len(mgr.scheduler.node_set.nodes) < n_nodes:
            assert time.monotonic() < deadline, "mirror not built"
            time.sleep(0.02)
        api = mgr.control_api
        # warm-up, tracer off: the device program compiles here
        wait_assigned(mgr, api.create_service(spec("warm", replicas)).id,
                      replicas)
        tracer.reset()
        tracer.enable()
        sid = api.create_service(spec("small", replicas)).id
        wait_assigned(mgr, sid, replicas)
        tracer.disable()
        routes = [s.args for s in tracer.spans()
                  if s.name == "plan.route" and s.args.get("service") == sid]
        host_spans = [s for s in tracer.spans()
                      if s.name in ("sched.host_fallback",
                                    "sched.strategy_host")]
        placed = mgr.store.view(lambda tx: tx.find(Task))
        stats = dict(planner.stats)
    finally:
        tracer.disable()
        tracer.reset()
        mgr.stop()
    assert [r["route"] for r in routes] == ["device"]
    route = routes[0]
    assert route["tasks"] == replicas and route["nodes"] == n_nodes
    assert route["device_est_ms"] == pytest.approx(0.8 * OVERHEAD_S * 1e3)
    assert route["host_est_ms"] > route["device_est_ms"]
    assert route["host_est_ms"] == pytest.approx(
        1e3 * (n_nodes * PER_NODE_S
               + replicas * planner.host_cost_per_task), abs=1e-3)
    assert not host_spans
    assert stats["groups_small_to_host"] == 0
    assert not stats.get("groups_fallback") \
        and not stats.get("groups_spill_to_host")
    # the host oracle, no planner at all, on the same two deploys
    host_sched, _groups = real_scheduler(None, n_nodes,
                                         (replicas, replicas))
    assert host_sched.tick() == 2 * replicas
    by_host = host_sched.store.view(lambda tx: tx.find(Task))
    assert all(t.status.state >= TaskState.ASSIGNED and t.node_id
               for t in placed + by_host)
    assert per_node_counts(placed) == per_node_counts(by_host) \
        == [1] * (2 * replicas)
