"""``harness-100k-ha``'s placement path at a test's size: services under
*one* spread preference over more than 256 racks, the form upstream's
API defines (``PlacementPreference { SpreadOver spread }``) and Docker
documents for high availability (``--placement-pref
spread=node.labels.rack``).

The cluster is the configuration's own (``benchmark/configs/
harness-100k-ha.json``) under the cut its cell brings for the CPU
(``tests/benchmark/shrink/harness-100k-ha.prefs.json``: 1,300 nodes, 65
racks a zone), dealt by ``benchmark/cluster.plain_nodes``.  260 racks are
more than 256 values, so a one-preference group's leaf bucket is 4,096
(``..._L4096_h0``): the resident *flat* leaf column
(``ResidentState.flat_leaf``), which has no ``LeafLayout``, so the leaf
level's search takes the scatter form, of its own and for every group of
a fused run that holds one (``fused_..._L4096_...``: a run's static ``L``
is its widest group's).

Two ticks are driven on a ``Scheduler`` over a ``MemoryStore`` three
ways: as the planner routes them (fused runs of three and of two, groups
of their own between the topology groups that break the runs), with the
fused path off (every group a launch of its own), and on the host (a
launch no group amortises).  The second tick brings later partial groups
of services that already hold tasks.  Each outcome is held to the plain
reference's comparison, with the rack level held to 1 on the device;
fused and one by one agree node for node; the device agrees with
``benchmark/reference.py place`` and with the host oracle on every
service's per-rack counts (as a multiset: which of equally loaded racks
takes the odd task is each walk's own order; rack by rack where the
service divides evenly).

Tier-1, on the forced CPU: placements, counters and spans, never a
speed."""

import collections
import functools
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import cluster, readers, reference  # noqa: E402
from swarmkit_tpu.models import (  # noqa: E402
    Node, Service, Task, TaskState, TaskStatus, Version,
)
from swarmkit_tpu.obs import tracer  # noqa: E402
from swarmkit_tpu.ops import TPUPlanner, fusedbatch  # noqa: E402
from swarmkit_tpu.ops import kernel as kernel_mod  # noqa: E402
from swarmkit_tpu.ops import planner as planner_mod  # noqa: E402
from swarmkit_tpu.scheduler import Scheduler  # noqa: E402
from swarmkit_tpu.state import MemoryStore  # noqa: E402

CELL = "harness-100k-ha.prefs"
CONFIG = cluster.load_config("harness-100k-ha")
with open(os.path.join(REPO, "tests", "benchmark", "shrink",
                       f"{CELL}.json")) as f:
    CUT = json.load(f)["cluster"]
FULL_NODES = CONFIG["cluster"]["nodes"]
CONFIG["cluster"].update(CUT)
RACKS = CONFIG["cluster"]["zones"] * CONFIG["cluster"]["racks_per_zone"]
SEED = 2 ** 31 + 36
#: the pinned probes: a launch of 5 ms against a scan of 3.5 us a node
#: over the configuration's 100,000 nodes, priced on this cut, so the
#: router sends every group to the device, as in the cell
LAUNCH_S = 0.005
PER_NODE_S = 3.5e-6 * FULL_NODES / CONFIG["cluster"]["nodes"]
PREF = ("rack-spread", "rack-constrained")
#: the first tick, in the order the scheduler walks it: a fused run of
#: three (mixed strategies), a fused run of two (spread only), and a
#: group of each fusable shape alone between topology groups
TICK = [("rack-spread", 2 * RACKS), ("rack-constrained", 30),
        ("binpack", 50),
        ("topology", 33),
        ("rack-spread", 33), ("rack-constrained", 250),
        ("topology", 1),
        ("rack-spread", 7),
        ("topology", 5),
        ("binpack", 4),
        ("topology", 2),
        ("rack-constrained", 3)]
#: the second tick: (index of the service in ``TICK``, more replicas);
#: later partial groups of services that hold tasks: a fused run of two,
#: a topology group, a rack-spread group of its own
LATER = [(0, 100), (5, 41), (3, 7), (7, 300)]
RUNS = [3, 2]                       # the first tick's fused runs
ALONE = [7, 9, 11]                  # fusable groups launched alone
MODES = ("fused", "single", "host")
FLAT_LABEL = "nb2048_cc1_p1_L4096_h0"
FUSED_LABELS = {"fused_g2_nb2048_cc1_p1_L4096_s4_mx1",
                "fused_g1_nb2048_cc1_p1_L4096_s4_mx1",
                "fused_g1_nb2048_cc1_p1_L4096_s2"}


def _service(i: int):
    shape, _k = TICK[i]
    spec = cluster.service_spec(f"s{i:02d}-{shape}",
                                CONFIG["shapes"][shape], 1)
    return Service(id=f"svc{i:02d}", spec=spec,
                   spec_version=Version(index=1))


def _tasks(svc, first: int, k: int, tick: int):
    """``k`` more PENDING tasks of ``svc`` from slot ``first``, under
    ids that sort in the tick's order."""
    return [Task(id=f"t{tick}-{svc.id}-{slot:04d}", service_id=svc.id,
                 slot=slot, desired_state=TaskState.RUNNING,
                 spec=svc.spec.task, spec_version=Version(index=1),
                 status=TaskStatus(state=TaskState.PENDING))
            for slot in range(first, first + k)]


def _numbers(table: dict) -> dict:
    return {k: v for k, v in table.items() if isinstance(v, (int, float))}


def _grown(before: dict, after: dict) -> dict:
    return {k: v - before.get(k, 0) for k, v in _numbers(after).items()}


def _read_back(store) -> list:
    return [{"id": t.id, "service_id": t.service_id,
             "node_id": t.node_id or "",
             "state": "assigned" if t.status.state >= TaskState.ASSIGNED
             else "pending"}
            for t in store.view(lambda tx: tx.find(Task))]


def _planner(mode: str) -> TPUPlanner:
    planner = TPUPlanner()
    planner._launch_overhead = 10.0 if mode == "host" else LAUNCH_S
    planner.host_cost_per_node = PER_NODE_S
    if mode == "single":
        planner.fused_enabled = False
    return planner


def _arrive(store, sched, group: list) -> None:
    """Create the tasks and hand the scheduler their create events, as
    its event loop would."""
    store.update(lambda tx: [tx.create(t) for t in group])
    for t in group:
        sched._create_task(store.view(lambda tx: tx.get(Task, t.id)))


@functools.lru_cache(maxsize=None)
def outcome(mode: str) -> dict:
    """The two ticks, routed as ``mode`` says: what each placed, counted
    and traced."""
    nodes = [dict(n, agent=False)
             for n in cluster.plain_nodes(CONFIG["cluster"], SEED)]
    store = MemoryStore()
    services = [_service(i) for i in range(len(TICK))]
    first = [_tasks(svc, 1, k, 1)
             for svc, (_shape, k) in zip(services, TICK)]

    def fill(tx):
        for n in cluster.store_nodes(nodes):
            tx.create(n)
        for svc, tasks in zip(services, first):
            tx.create(svc)
            for t in tasks:
                tx.create(t)
    store.update(fill)
    planner = _planner(mode)
    sched = Scheduler(store, batch_planner=planner)
    store.view(sched._setup_tasks_list)
    ticks = []
    tracer.reset()
    tracer.enable()
    try:
        walked = [len(g) for g in sched.unassigned_groups.values()]
        before = _numbers(planner.stats)
        decided = sched.tick()
        ticks.append({"walked": walked, "decided": decided,
                      "stats": _grown(before, planner.stats),
                      "spans": len(tracer.spans()),
                      "tasks": _read_back(store)})
        for i, more in LATER:
            _arrive(store, sched,
                    _tasks(services[i], TICK[i][1] + 1, more, 2))
        walked = [len(g) for g in sched.unassigned_groups.values()]
        before = _numbers(planner.stats)
        decided = sched.tick()
        ticks.append({"walked": walked, "decided": decided,
                      "stats": _grown(before, planner.stats),
                      "tasks": _read_back(store)})
    finally:
        tracer.disable()
    spans = [(s.name, dict(s.args or {})) for s in tracer.spans()]
    tracer.reset()
    return {"nodes": nodes, "ticks": ticks,
            "spans": [spans[:ticks[0]["spans"]], spans[ticks[0]["spans"]:]],
            "stats": _numbers(planner.stats)}


def _counts(tasks: list) -> dict:
    """{service: {node: its tasks there}}."""
    out = collections.defaultdict(collections.Counter)
    for t in tasks:
        assert t["node_id"], t
        out[t["service_id"]][t["node_id"]] += 1
    return out


def _by_rack(nodes: list, counts: collections.Counter) -> dict:
    of = {n["id"]: n["labels"]["rack"] for n in nodes}
    out = collections.Counter()
    for node_id, k in counts.items():
        out[of[node_id]] += k
    return dict(out)


def _replicas(upto: int) -> list:
    """Every service's replicas after tick ``upto`` (0 or 1)."""
    total = [k for _shape, k in TICK]
    if upto:
        for i, more in LATER:
            total[i] += more
    return total


def _services(upto: int) -> list:
    return [{"id": f"svc{i:02d}", "shape": CONFIG["shapes"][shape],
             "replicas": k, "read_back": True}
            for i, ((shape, _k), k) in enumerate(zip(TICK,
                                                     _replicas(upto)))]


def test_the_cut_keeps_more_than_256_racks_and_every_group_on_the_device():
    assert FULL_NODES == 100000 and CONFIG["reduced"] == ["tasks"]
    assert RACKS == 260 > planner_mod.WIDE_TREE_LEAVES \
        == kernel_mod.MASK_FORM_MAX_L
    assert fusedbatch.l_bucket(RACKS) == 4096 == fusedbatch.l_bucket(1000)
    nodes = cluster.plain_nodes(CONFIG["cluster"], SEED)
    assert len({n["labels"]["rack"] for n in nodes}) == RACKS
    assert fusedbatch.n_bucket(len(nodes)) == 2048
    assert PER_NODE_S * len(nodes) > 0.8 * LAUNCH_S
    for name in PREF:
        assert CONFIG["shapes"][name]["spread_over"] == ["node.labels.rack"]
        # no layout comes with a flat column: the scatter form
        assert kernel_mod.search_form(fusedbatch.l_bucket(RACKS)) \
            == "scatter"
    # the constrained shape's tree has fewer racks than the label has
    # values (whole racks are windows or arm64 at this cut), and still
    # rides the label's bucket
    eligible = {n["labels"]["rack"] for n in nodes
                if reference.eligible(n, CONFIG["shapes"][PREF[1]])}
    assert 1 < len(eligible) < RACKS


@pytest.mark.parametrize("mode", MODES)
def test_both_ticks_are_held_to_the_reference_and_the_racks_to_one(mode):
    run = outcome(mode)
    assert run["ticks"][0]["walked"] == [k for _shape, k in TICK]
    assert run["ticks"][1]["walked"] == [more for _i, more in LATER]
    for upto, tick in enumerate(run["ticks"]):
        result = reference.compare(run["nodes"], _services(upto),
                                   tick["tasks"])
        numbers = result["numbers"]
        assert result["correct"], (numbers, result["notes"])
        for name in ("lost_services", "missing_tasks", "unassigned",
                     "not_running", "unacked_seen", "overcommitted_nodes",
                     "ineligible_tasks", "retreats"):
            assert numbers[name] == 0, name
        assert numbers["binpack_open_nodes"] == 1
        assert numbers["topology_leaf_skew"] == 1
        if mode != "host":
            # racks of a service differ by at most 1, after the later
            # partial groups too (the host route is upstream's subtree
            # walk, held to the limit the configuration states)
            assert numbers["topology_skew"] == 1
    assert run["ticks"][0]["decided"] == sum(k for _shape, k in TICK)
    assert run["ticks"][1]["decided"] == sum(more for _i, more in LATER)


def test_a_fused_run_places_what_its_groups_place_one_by_one():
    fused, single = outcome("fused"), outcome("single")
    for a, b in zip(fused["ticks"], single["ticks"]):
        assert _counts(a["tasks"]) == _counts(b["tasks"])
        assert {t["id"]: t["node_id"] for t in a["tasks"]} \
            == {t["id"]: t["node_id"] for t in b["tasks"]}


@pytest.mark.parametrize("mode", ["fused", "single"])
def test_every_rack_s_count_is_the_reference_s_and_the_host_oracle_s(mode):
    run, host = outcome(mode), outcome("host")
    nodes = run["nodes"]
    for upto in (0, 1):
        placed = reference.place(nodes, _services(upto))
        want = _counts(placed)
        got = _counts(run["ticks"][upto]["tasks"])
        oracle = _counts(host["ticks"][upto]["tasks"])
        for i, ((shape, _k), k) in enumerate(zip(TICK, _replicas(upto))):
            sid = f"svc{i:02d}"
            assert sum(got[sid].values()) == k
            if shape == "binpack":
                if upto == 0:
                    assert got[sid] == oracle[sid]
                continue
            if shape == "topology":
                continue    # tests/test_harness100k_path.py holds it
            racks = _by_rack(nodes, got[sid])
            assert sorted(racks.values()) \
                == sorted(_by_rack(nodes, want[sid]).values()), sid
            eligible = {n["labels"]["rack"] for n in nodes
                        if reference.eligible(n, CONFIG["shapes"][shape])}
            assert set(racks) <= eligible
            assert max(racks.values()) - (
                min(racks.values()) if len(racks) == len(eligible)
                else 0) <= 1
            if upto == 0:
                # the first group of a service on the host is levelled
                # too (upstream's quirk needs tasks already held)
                assert sorted(racks.values()) == sorted(
                    _by_rack(nodes, oracle[sid]).values()), sid
            if k % len(eligible) == 0:
                assert racks == _by_rack(nodes, want[sid])
                assert set(racks.values()) == {k // len(eligible)}
    # the nodes of a rack differ by at most 1: ``topology_leaf_skew``,
    # held at 1 above


def test_the_new_counters_count_what_they_say():
    first, later = (t["stats"] for t in outcome("fused")["ticks"])
    pref = [shape in PREF for shape, _k in TICK]
    own = [i for i in ALONE if pref[i]]
    trees = sum(shape == "topology" for shape, _k in TICK)
    assert first["groups_fused"] == sum(RUNS)
    assert first["groups_planned"] == len(TICK) - sum(RUNS)
    assert first["pref_groups"] == sum(pref) == first["pref_wide_groups"]
    # both runs hold a rack preference, so every group in them rides
    # L4096, the binpack group of the first too
    assert first["fused_wide_runs"] == len(RUNS)
    assert first["fused_wide_groups"] == sum(RUNS) \
        > sum(pref) - len(own)
    assert first["fused_wide_s"] > 0
    # a one-preference group of its own is a wide group by its leaf
    # bucket, and no layout came with it
    assert first["wide_tree_groups"] == trees + len(own)
    assert first["dense_tree_groups"] == trees
    # one walk of every NodeInfo for the rack label, then the column
    assert first["leaf_cols_builds"] == 1
    assert first["leaf_cols_hits"] == sum(pref) - 1
    assert first["tree_cols_builds"] == 1
    assert first["tree_cols_hits"] == trees - 1
    # the second tick: a run of two preference groups, a tree, one alone
    assert later["groups_fused"] == 2 and later["groups_planned"] == 2
    assert later["pref_groups"] == 3 == later["pref_wide_groups"]
    assert (later["fused_wide_runs"], later["fused_wide_groups"]) == (1, 2)
    assert (later["leaf_cols_builds"], later["leaf_cols_hits"]) == (0, 3)
    for key in ("groups_small_to_host", "groups_fallback",
                "groups_spill_to_host", "groups_device_error",
                "fused_overflows"):
        assert not first.get(key) and not later.get(key), key
    single = outcome("single")["ticks"][0]["stats"]
    assert single["groups_planned"] == len(TICK)
    assert single["pref_groups"] == sum(pref) == single["pref_wide_groups"]
    assert single.get("groups_fused", 0) == 0 == single["fused_wide_runs"]
    assert single["fused_wide_groups"] == 0 == single["fused_wide_s"]
    assert single["wide_tree_groups"] == trees + sum(pref)
    host = outcome("host")["ticks"][0]["stats"]
    assert host["groups_small_to_host"] == len(TICK)
    for key in ("pref_groups", "pref_wide_groups", "fused_wide_runs",
                "fused_wide_groups", "fused_wide_s", "leaf_cols_hits",
                "leaf_cols_builds"):
        assert host[key] == 0, key


def test_the_spans_name_the_form_and_the_leaf_bucket():
    first, _later = outcome("fused")["spans"]
    dispatch = [a for name, a in first if name == "plan.dispatch"]
    chunks = [a for a in dispatch if a["route"] == "fused"]
    # a run of three is two chunks (2 + 1), a run of two is two (1 + 1)
    assert [a["fused_groups"] for a in chunks] == [2, 1, 1, 1]
    assert {a["label"] for a in chunks} == FUSED_LABELS
    assert all(a["form"] == "scatter" and a["L"] == 4096 for a in chunks)
    own = [a for a in dispatch if a["route"] == "group"]
    flat = [a for a in own if a["label"] == FLAT_LABEL]
    assert len(flat) == sum(TICK[i][0] in PREF for i in ALONE)
    assert all(a["form"] == "scatter" for a in flat)
    assert all(a["form"] == "dense" for a in own
               if a["label"].endswith("_h2"))
    strategy = [a for a in dispatch if a["route"] == "strategy"]
    assert [a["label"] for a in strategy] == ["nb2048_cc1_p1_L1_h0_st1"]


@pytest.mark.parametrize("name, want", [
    ("pref_groups_pct", lambda s: 100.0 * s["pref_groups"]
     / (s["groups_planned"] + s["groups_fused"])),
    ("fused_wide_run_ms", lambda s: 1e3 * s["fused_wide_s"]
     / s["fused_wide_runs"]),
    ("fused_wide_groups_pct", lambda s: 100.0 * s["fused_wide_groups"]
     / (s["groups_planned"] + s["groups_fused"])),
    ("leaf_cols_hit_pct", lambda s: 100.0 * s["leaf_cols_hits"]
     / (s["leaf_cols_hits"] + s["leaf_cols_builds"])),
])
def test_the_layer_metrics_read_the_counters(name, want):
    spec = readers.load_layer_metrics()[name]["reader"]
    stats = outcome("fused")["stats"]
    obs = readers.Observations()
    obs.counters = {"planner.stats": dict(stats)}
    value = readers.KINDS[spec["kind"]](spec, obs)
    assert value == pytest.approx(want(stats)) and value > 0
    # a tree without the counter (the parent) has nothing to read and
    # the line leaves the metric out
    bare = {k: v for k, v in stats.items()
            if k in ("groups_planned", "groups_fused")}
    obs.counters = {"planner.stats": bare}
    assert readers.KINDS[spec["kind"]](spec, obs) is None


def test_a_label_change_on_a_node_rebuilds_the_flat_column():
    """A node moved to another rack can renumber other rows' leaves
    (ids are first-appearance ordered in row order), so the resident
    column is dropped and walked again for the next group, which is
    placed over the racks as they now are."""
    nodes = [dict(n, agent=False, labels=dict(n["labels"]))
             for n in cluster.plain_nodes(CONFIG["cluster"], SEED)]
    store = MemoryStore()
    shape = CONFIG["shapes"]["rack-spread"]
    made = []
    for i in range(3):
        spec = cluster.service_spec(f"m{i}", shape, 1)
        made.append(Service(id=f"mv{i}", spec=spec,
                            spec_version=Version(index=1)))

    def fill(tx):
        for n in cluster.store_nodes(nodes):
            tx.create(n)
        for svc in made:
            tx.create(svc)
        for t in _tasks(made[0], 1, 40, 1):
            tx.create(t)
    store.update(fill)
    planner = _planner("fused")
    sched = Scheduler(store, batch_planner=planner)
    store.view(sched._setup_tasks_list)
    assert sched.tick() == 40
    assert (planner.stats["leaf_cols_builds"],
            planner.stats["leaf_cols_hits"]) == (1, 0)

    def place(svc, k, tick):
        _arrive(store, sched, _tasks(svc, 1, k, tick))
        return sched.tick()
    assert place(made[1], RACKS, 2) == RACKS
    assert (planner.stats["leaf_cols_builds"],
            planner.stats["leaf_cols_hits"]) == (1, 1)
    # move one node that holds no task from its rack to the first
    # node's rack
    held = {t["node_id"] for t in _read_back(store)}
    mover = next(n for n in nodes if n["id"] not in held
                 and n["labels"]["rack"] != nodes[0]["labels"]["rack"])
    mover["labels"]["rack"] = nodes[0]["labels"]["rack"]
    moved = cluster.store_nodes([mover])[0]

    def relabel(tx):
        node = tx.get(Node, moved.id).copy()
        node.spec = moved.spec
        tx.update(node)
        return node
    sched._create_or_update_node(store.update(relabel))
    assert place(made[2], 2 * RACKS, 3) == 2 * RACKS
    assert (planner.stats["leaf_cols_builds"],
            planner.stats["leaf_cols_hits"]) == (2, 1)
    tasks = _read_back(store)
    result = reference.compare(
        nodes, [{"id": made[2].id, "shape": shape, "replicas": 2 * RACKS,
                 "read_back": True}],
        [t for t in tasks if t["service_id"] == made[2].id])
    assert result["correct"], (result["numbers"], result["notes"])
    assert result["numbers"]["topology_skew"] == 0 \
        and result["numbers"]["topology_leaf_skew"] == 1
    racks = _by_rack(nodes, _counts(tasks)[made[2].id])
    assert set(racks.values()) == {2} and len(racks) == RACKS
    assert not planner.stats.get("groups_fallback")
