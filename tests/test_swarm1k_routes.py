"""``swarm-1k``: upstream's 1,000-node harness cluster, where the
break-even router's two routes meet inside one tick.

The cluster is the configuration's own (``benchmark/configs/swarm-1k.json``
dealt by ``benchmark/cluster.plain_nodes`` from a seed), the four shapes
the configuration's, and the router's two probes are pinned (plain
attributes: what a launch and a scan cost on a loaded runner is no test's
to assert) so that the break-even falls at a known group size: groups of
up to 9 tasks ride the host, of 10 and more the device.  One tick holds
groups of all four shapes on both sides of it and is driven three ways:
routed as pinned (mixed), everything on the host, everything on the
device.  Each outcome is held to the plain reference's comparison
(``benchmark/reference.py compare``: the guarantees the configuration
states); for the three flat shapes the three outcomes place every task on
the same node, which is the strategy seam's bit-parity across a route
switch inside one tick.

The topology shape is held to the guarantee alone.  The host route is
upstream's ``scheduleNTasksOnSubtree`` line for line and the device
program levels a preference tree branch by branch; both level a group to
within one task between sibling branches and between the nodes of a
rack, but which of two equally loaded racks takes the odd task is each
walk's own order, so the two routes put a topology group on other nodes
of the same levels.  A flat group placed after it then meets another
load on those nodes and follows it, on either route: the tick therefore
walks its flat groups first (switching routes among them) and its
topology groups last, so that what the flat comparison shows is the
routes' own parity and not the echo of a topology tie.

"The same node" is said of a group, not of a task id: the tasks of a
group share one spec and are interchangeable, the device's apply deals
them to the chosen nodes in node order and the host's in its round-robin
order, so the comparison is of each service's per-node counts.

Tier-1, on the forced CPU: placements, counters and spans, never a
speed."""

import collections
import functools
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import cluster, readers, reference  # noqa: E402
from swarmkit_tpu.models import (  # noqa: E402
    Service, Task, TaskState, TaskStatus, Version,
)
from swarmkit_tpu.obs import tracer  # noqa: E402
from swarmkit_tpu.ops import TPUPlanner  # noqa: E402
from swarmkit_tpu.ops.fusedbatch import n_bucket  # noqa: E402
from swarmkit_tpu.scheduler import Scheduler  # noqa: E402
from swarmkit_tpu.state import MemoryStore  # noqa: E402

CONFIG = cluster.load_config("swarm-1k")
SEEDS = (3, 30, 2 ** 31 + 30)
#: the pinned probes: a launch of 5 ms (the router takes four fifths: 4
#: ms) against a scan of 3.52 us a node (3.52 ms on 1,000 nodes) and the
#: host oracle's 50 us a task: 9 tasks ride the host, 10 the device
LAUNCH_S, PER_NODE_S, BREAK_EVEN = 0.005, 3.52e-6, 10
#: one tick, in the order the scheduler walks it: (shape, replicas)
TICK = [("spread", 40), ("constrained", 30), ("binpack", 4),
        ("spread", 5), ("constrained", 24), ("binpack", 50),
        ("constrained", 3), ("spread", 25), ("binpack", 7),
        ("topology", 60), ("topology", 6), ("topology", 33)]
FLAT = ("spread", "constrained", "binpack")
MODES = ("mixed", "all_host", "all_device")
ON_HOST = [k for _shape, k in TICK if k < BREAK_EVEN]
#: a device-routed group straight after a host-routed one: it rebuilds
#: the columns the host route dropped
SWITCHES = sum(1 for (_, a), (_, b) in zip(TICK, TICK[1:])
               if a < BREAK_EVEN <= b)


def _services(tick):
    """The tick's services and tasks under ids that sort in the tick's
    order, whatever order the store lists them in."""
    out = []
    for i, (shape, k) in enumerate(tick):
        spec = cluster.service_spec(f"s{i:02d}-{shape}",
                                    CONFIG["shapes"][shape], k)
        svc = Service(id=f"svc{i:02d}", spec=spec,
                      spec_version=Version(index=1))
        tasks = [Task(id=f"t{i:02d}-{slot:04d}", service_id=svc.id,
                      slot=slot, desired_state=TaskState.RUNNING,
                      spec=spec.task, spec_version=Version(index=1),
                      status=TaskStatus(state=TaskState.PENDING))
                 for slot in range(1, k + 1)]
        out.append((svc, tasks))
    return out


@functools.lru_cache(maxsize=None)
def outcome(seed: int, mode: str) -> dict:
    """One tick of ``TICK``, driven once a seed and mode and read by
    every test."""
    return drive(seed, mode, TICK)


def drive(seed: int, mode: str, tick, before=None) -> dict:
    """One tick of ``tick`` on the cluster ``seed`` deals, routed as
    ``mode`` says; what it placed, counted and traced.  ``before`` is
    handed the planner ahead of the tick."""
    # no dispatcher runs here, so no node is agent-served: the comparison
    # asks RUNNING of no task
    nodes = [dict(n, agent=False)
             for n in cluster.plain_nodes(CONFIG["cluster"], seed)]
    store = MemoryStore()
    made = _services(tick)

    def fill(tx):
        for n in cluster.store_nodes(nodes):
            tx.create(n)
        for svc, tasks in made:
            tx.create(svc)
            for t in tasks:
                tx.create(t)
    store.update(fill)
    planner = TPUPlanner()
    planner._launch_overhead = LAUNCH_S
    planner.host_cost_per_node = PER_NODE_S
    if mode == "all_host":
        # the router's own choice still: a launch no group amortises
        planner._launch_overhead = 10.0
    elif mode == "all_device":
        planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner)
    store.view(sched._setup_tasks_list)
    if before is not None:
        before(planner)
    walked = [len(g) for g in sched.unassigned_groups.values()]
    tracer.reset()
    tracer.enable()
    try:
        decided = sched.tick()
    finally:
        tracer.disable()
    spans = [(s.name, dict(s.args or {})) for s in tracer.spans()]
    tracer.reset()
    placed = store.view(lambda tx: tx.find(Task))
    return {"nodes": nodes, "decided": decided, "walked": walked,
            "spans": spans, "stats": dict(planner.stats),
            "sched_stats": dict(sched.stats),
            "tasks": [{"id": t.id, "service_id": t.service_id,
                       "node_id": t.node_id or "",
                       "state": "assigned"
                       if t.status.state >= TaskState.ASSIGNED
                       else "pending"} for t in placed]}


def test_the_cluster_is_upstream_s_and_sits_in_the_smallest_bucket():
    c = CONFIG["cluster"]
    assert c["nodes"] == 1000 and CONFIG["reduced"] == []
    assert n_bucket(c["nodes"]) == 1024 == n_bucket(1)
    nodes = cluster.plain_nodes(c, SEEDS[0])
    racks = {n["labels"]["rack"] for n in nodes}
    assert len(racks) == 100 and sum(n["agent"] for n in nodes) == 64
    ten = cluster.load_config("swarm-10k")
    assert CONFIG["shapes"] == ten["shapes"]
    assert CONFIG["manager"] == ten["manager"]
    assert set(CONFIG["guarantees"]) == set(ten["guarantees"])
    # the tick below holds every shape on both sides of the break-even
    for shape in CONFIG["shapes"]:
        sizes = [k for s, k in TICK if s == shape]
        assert min(sizes) < BREAK_EVEN <= max(sizes), shape
    assert SWITCHES == 4 and len(ON_HOST) == 5


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", SEEDS)
def test_one_tick_is_held_to_the_reference_on_every_route(seed, mode):
    run = outcome(seed, mode)
    assert run["walked"] == [k for _shape, k in TICK]
    assert run["decided"] == sum(k for _shape, k in TICK)
    services = [{"id": f"svc{i:02d}", "shape": CONFIG["shapes"][shape],
                 "replicas": k, "read_back": True}
                for i, (shape, k) in enumerate(TICK)]
    result = reference.compare(run["nodes"], services, run["tasks"])
    numbers = result["numbers"]
    assert result["correct"], (numbers, result["notes"])
    for name in ("lost_services", "missing_tasks", "unassigned",
                 "not_running", "unacked_seen", "overcommitted_nodes",
                 "ineligible_tasks", "retreats"):
        assert numbers[name] == 0, name
    assert numbers["spread_skew"] == 1
    assert numbers["binpack_open_nodes"] == 1
    assert numbers["topology_leaf_skew"] == 1


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_routes_are_counted_and_the_host_route_is_one_span(seed, mode):
    run = outcome(seed, mode)
    stats = run["stats"]
    host = [args for name, args in run["spans"]
            if name == "sched.host_route"]
    inside = [name for name, _ in run["spans"]
              if name in ("sched.host_fallback", "sched.strategy_host")]
    want = {"mixed": ON_HOST, "all_host": [k for _s, k in TICK],
            "all_device": []}[mode]
    assert [a["tasks"] for a in host] == want
    assert all(a["nodes"] == 1000 and a["reason"] == "host_small"
               for a in host)
    # the two older spans stay, inside it: one a group
    assert len(inside) == len(want)
    assert stats["groups_small_to_host"] == len(want)
    assert stats.get("groups_planned", 0) + stats.get("groups_fused", 0) \
        == len(TICK) - len(want)
    assert stats["route_switches"] == (SWITCHES if mode == "mixed" else 0)
    # the scheduler counts the same groups with the tracer off too: what
    # ``host_route_ms`` reads, 0 and not nothing where none rode the host
    counted = run["sched_stats"]
    assert counted["host_route_groups"] == len(want)
    assert (counted["host_route_s"] > 0) == bool(want)
    for key in ("groups_fallback", "groups_spill_to_host",
                "groups_breaker_to_host", "groups_device_error",
                "groups_strategy_host", "fused_overflows"):
        assert not stats.get(key), key
    if mode == "mixed":
        # both kinds of launch follow a host-routed group: a fused run
        # (constrained 24 + binpack 50) and launches of their own
        assert stats["groups_fused"] == 4 and stats["groups_planned"] == 3


@pytest.mark.parametrize("mode", MODES)
def test_host_route_ms_reads_the_counter_and_nought_where_no_group_rode(mode):
    """The check wants every metric a cell lists on every traced line, and
    nine processes of ten route no group to the host: the metric reads
    the scheduler's counter, which is there at 0; a tree without the
    counter (the parent) has nothing to read and the line leaves it out."""
    spec = readers.load_layer_metrics()["host_route_ms"]["reader"]
    counted = outcome(SEEDS[0], mode)["sched_stats"]
    obs = readers.Observations()
    obs.counters = {"scheduler.stats": dict(counted)}
    value = readers.KINDS[spec["kind"]](spec, obs)
    if mode == "all_device":
        assert value == 0.0
    else:
        assert value == pytest.approx(
            1e3 * counted["host_route_s"] / counted["ticks"]) and value > 0
    obs.counters = {"scheduler.stats": {"ticks": counted["ticks"]}}
    assert readers.KINDS[spec["kind"]](spec, obs) is None


@pytest.mark.parametrize("seed", SEEDS)
def test_the_router_s_estimates_are_on_both_probes_spans(seed):
    spans = outcome(seed, "mixed")["spans"]
    routes = [a for name, a in spans if name == "plan.route"]
    probes = [a for name, a in spans if name == "plan.fused_probe"]
    assert routes and probes
    for args in routes + probes:
        assert args["nodes"] == 1000
        assert args["device_est_ms"] == pytest.approx(0.8 * LAUNCH_S * 1e3)
    for args in routes:
        assert (args["route"] == "host_small") \
            == (args["host_est_ms"] < args["device_est_ms"]) \
            == (args["tasks"] < BREAK_EVEN)
    # one span a probe, with the estimates of the group that ended it:
    # the first probe takes the two fusable groups and stops at the
    # 4-task binpack group, which rides the host
    assert probes[0]["groups"] == 2
    assert probes[0]["host_est_ms"] == pytest.approx(
        1e3 * (1000 * PER_NODE_S + 4 * 50e-6), abs=1e-3)


@pytest.mark.parametrize("seed", SEEDS)
def test_flat_shapes_land_on_the_same_nodes_whatever_the_route(seed):
    shape_of = {f"svc{i:02d}": shape for i, (shape, _k) in enumerate(TICK)}

    def flat(mode):
        """{service: {node: its tasks there}} of the flat services."""
        counts = {}
        for t in outcome(seed, mode)["tasks"]:
            if shape_of[t["service_id"]] in FLAT:
                assert t["node_id"], t
                counts.setdefault(t["service_id"], collections.Counter())[
                    t["node_id"]] += 1
        return counts
    mixed, host, device = (flat(mode) for mode in MODES)
    assert sum(sum(c.values()) for c in mixed.values()) \
        == sum(k for shape, k in TICK if shape in FLAT)
    assert mixed == host
    assert mixed == device


@pytest.mark.parametrize("between", ["topology", "spread"])
def test_a_host_routed_group_between_two_topology_groups_keeps_the_tree_true(
        between):
    """Inside one tick a host-routed group drops the cached columns
    (``_to_host``) and mutates NodeInfos behind marks; the topology
    group after it is handed the resident tree, which has absorbed
    those rows and was not walked again, and its every array is what
    the walk over the same mirror gives there and then."""
    from swarmkit_tpu.ops import fusedbatch
    seen = []

    def spy(planner):
        build = planner._build_device_inputs

        def spied(sched, t, k, flat=False):
            built = build(sched, t, k, flat=flat)
            prefs = [p.spread.spread_descriptor
                     for p in t.spec.placement.preferences]
            if len(prefs) > 1:
                assert planner._resident_for(planner._cache) is not None
                seen.append(((built[7].leaf, built[9], built[10]),
                             fusedbatch.spread_tree(built[0], built[2],
                                                    prefs)))
            return built
        planner._build_device_inputs = spied
    run = drive(SEEDS[0], "mixed",
                [("topology", 60), (between, 6), ("topology", 33)], spy)
    assert run["decided"] == 99
    stats = run["stats"]
    assert stats["groups_small_to_host"] == 1 == stats["route_switches"]
    assert stats["groups_planned"] == 2 == len(seen)
    assert (stats["tree_cols_builds"], stats["tree_cols_hits"],
            stats["tree_cols_invalidations"]) == (1, 1, 0)
    for (leaf, L, (upper, leaf_parent)), \
            (w_leaf, w_L, (w_upper, w_leaf_parent)) in seen:
        assert L == w_L == 256 and len(upper) == len(w_upper) == 1
        for got, want in ((leaf, w_leaf), (leaf_parent, w_leaf_parent),
                          (upper[0][0], w_upper[0][0]),
                          (upper[0][1], w_upper[0][1])):
            assert got.dtype == want.dtype
            assert got.tolist() == want.tolist()
