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
(``ResidentState.flat_leaf``), which since PR 37 brings its
``LeafLayout``, so the leaf level's search takes the dense form, of its
own and for every spread group of a fused run that holds one
(``fused_..._L4096_...``: a run's static ``L`` is its widest group's,
and the run ships its groups' slot rows in place of their leaf rows).
The scatter form is what stands where no layout can come: a stub
``plan_fn``, a column over ``DENSE_FORM_MAX_ENTRIES``.

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

import numpy as np
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
        # the form follows the layout: five nodes a rack lie in [4096, 8]
        assert kernel_mod.search_form(fusedbatch.l_bucket(RACKS)) \
            == "scatter"
        assert kernel_mod.search_form(fusedbatch.l_bucket(RACKS), 8) \
            == "dense"
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
    # every one of them searched its racks on the layout
    assert first["dense_pref_groups"] == sum(pref)
    # both runs hold a rack preference, so every group in them rides
    # L4096, the binpack group of the first too
    assert first["fused_wide_runs"] == len(RUNS)
    assert first["fused_wide_groups"] == sum(RUNS) \
        > sum(pref) - len(own)
    assert first["fused_wide_s"] > 0
    # a one-preference group of its own is a wide group by its leaf
    # bucket, and its layout came with it
    assert first["wide_tree_groups"] == trees + len(own) \
        == first["dense_tree_groups"]
    # one walk of every NodeInfo for the rack label, then the column
    assert first["leaf_cols_builds"] == 1
    assert first["leaf_cols_hits"] == sum(pref) - 1
    assert first["tree_cols_builds"] == 1
    assert first["tree_cols_hits"] == trees - 1
    # the second tick: a run of two preference groups, a tree, one alone
    assert later["groups_fused"] == 2 and later["groups_planned"] == 2
    assert later["pref_groups"] == 3 == later["pref_wide_groups"] \
        == later["dense_pref_groups"]
    assert not first["leaf_cols_invalidations"] \
        and not later["leaf_cols_invalidations"]
    assert (later["fused_wide_runs"], later["fused_wide_groups"]) == (1, 2)
    assert (later["leaf_cols_builds"], later["leaf_cols_hits"]) == (0, 3)
    for key in ("groups_small_to_host", "groups_fallback",
                "groups_spill_to_host", "groups_device_error",
                "fused_overflows"):
        assert not first.get(key) and not later.get(key), key
    single = outcome("single")["ticks"][0]["stats"]
    assert single["groups_planned"] == len(TICK)
    assert single["pref_groups"] == sum(pref) == single["pref_wide_groups"] \
        == single["dense_pref_groups"]
    assert single.get("groups_fused", 0) == 0 == single["fused_wide_runs"]
    assert single["fused_wide_groups"] == 0 == single["fused_wide_s"]
    assert single["wide_tree_groups"] == trees + sum(pref) \
        == single["dense_tree_groups"]
    host = outcome("host")["ticks"][0]["stats"]
    assert host["groups_small_to_host"] == len(TICK)
    for key in ("pref_groups", "pref_wide_groups", "dense_pref_groups",
                "fused_wide_runs", "fused_wide_groups", "fused_wide_s",
                "leaf_cols_hits", "leaf_cols_builds"):
        assert host[key] == 0, key


def test_the_spans_name_the_form_and_the_leaf_bucket():
    first, _later = outcome("fused")["spans"]
    dispatch = [a for name, a in first if name == "plan.dispatch"]
    chunks = [a for a in dispatch if a["route"] == "fused"]
    # a run of three is two chunks (2 + 1), a run of two is two (1 + 1)
    assert [a["fused_groups"] for a in chunks] == [2, 1, 1, 1]
    assert {a["label"] for a in chunks} == FUSED_LABELS
    assert all(a["form"] == "dense" and a["L"] == 4096 for a in chunks)
    own = [a for a in dispatch if a["route"] == "group"]
    flat = [a for a in own if a["label"] == FLAT_LABEL]
    assert len(flat) == sum(TICK[i][0] in PREF for i in ALONE)
    assert all(a["form"] == "dense" for a in flat)
    assert all(a["form"] == "dense" for a in own
               if a["label"].endswith("_h2"))
    strategy = [a for a in dispatch if a["route"] == "strategy"]
    assert [a["label"] for a in strategy] == ["nb2048_cc1_p1_L1_h0_st1"]


#: ``dense_pref_groups_pct`` as a ``benchmark`` issue can add it, as data
#: (``benchmark/layer_metrics/dense_pref_groups_pct.json`` and a
#: ``per_layer`` entry listing this cell).  PR 37 could not:
#: ``tests/benchmark/test_harness100k_ha_cell.py`` holds every per-layer
#: list but PR 36's four to the twin cell's, where the counter reads
#: nothing; the counter is on the ``window counters`` line meanwhile
DENSE_PREF_GROUPS_PCT = {
    "layer": "device programs", "unit": "%", "better": "higher",
    "moves": "decisions_per_s",
    "reader": {"kind": "counter",
               "num": {"source": "planner.stats",
                       "key": "dense_pref_groups"},
               "den": {"source": "planner.stats",
                       "key": "pref_wide_groups"},
               "scale": 100.0}}


@pytest.mark.parametrize("name, want", [
    ("pref_groups_pct", lambda s: 100.0 * s["pref_groups"]
     / (s["groups_planned"] + s["groups_fused"])),
    ("fused_wide_run_ms", lambda s: 1e3 * s["fused_wide_s"]
     / s["fused_wide_runs"]),
    ("fused_wide_groups_pct", lambda s: 100.0 * s["fused_wide_groups"]
     / (s["groups_planned"] + s["groups_fused"])),
    ("leaf_cols_hit_pct", lambda s: 100.0 * s["leaf_cols_hits"]
     / (s["leaf_cols_hits"] + s["leaf_cols_builds"])),
    ("dense_pref_groups_pct", lambda s: 100.0 * s["dense_pref_groups"]
     / s["pref_wide_groups"]),
])
def test_the_layer_metrics_read_the_counters(name, want):
    spec = (readers.load_layer_metrics().get(name)
            or DENSE_PREF_GROUPS_PCT)["reader"]
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


# ------------------------------------------------- the dense form (PR 37)
#
# One tick a case, driven three ways as above on the same 260 racks, with
# two more labels on the nodes so that a run can hold preferences of
# other widths: ``cage`` (three nodes a value, 434 values: its own
# layout at W 4, relaid to the racks' 8 inside a run) and the cluster's
# four zones (16 leaves of its own, no layout: laid at the run's 4,096
# leaves, W 512, to which the racks are relaid).

def _shape(base: str, **over) -> dict:
    return dict(CONFIG["shapes"][base], **over)


SHAPES = dict(
    CONFIG["shapes"],
    **{"flat-spread": _shape("rack-spread", spread_over=[]),
       "cage-spread": _shape("rack-spread",
                             spread_over=["node.labels.cage"]),
       "zone-spread": _shape("rack-spread",
                             spread_over=["node.labels.zone"])})
LABEL_OF = {"rack-spread": "rack", "rack-constrained": "rack",
            "cage-spread": "cage", "zone-spread": "zone"}
#: case -> (the tick's groups, the fused chunks' W, the signatures)
CASES = {
    "pref_constrained_binpack": (
        [("rack-spread", 300), ("rack-constrained", 30), ("binpack", 50)],
        8, {"fused_g2_nb2048_cc1_p1_L4096_s4_mx1",
            "fused_g1_nb2048_cc1_p1_L4096_s4_mx1"}),
    "pref_flat_pref": (
        [("rack-spread", 300), ("flat-spread", 77), ("rack-spread", 33)],
        8, {"fused_g2_nb2048_cc1_p1_L4096_s4",
            "fused_g1_nb2048_cc1_p1_L4096_s4"}),
    "two_labels_of_two_widths": (
        [("rack-spread", 300), ("cage-spread", 500)],
        8, {"fused_g1_nb2048_cc1_p1_L4096_s2"}),
    "a_narrow_preference_in_a_wide_run": (
        [("zone-spread", 41), ("rack-spread", 300), ("cage-spread", 100)],
        512, {"fused_g2_nb2048_cc1_p1_L4096_s4",
              "fused_g1_nb2048_cc1_p1_L4096_s4"}),
    "a_padded_slot": (
        [("rack-spread", 30), ("flat-spread", 20), ("rack-constrained", 25),
         ("binpack", 10), ("rack-spread", 7), ("rack-spread", 260),
         ("flat-spread", 3)],
        8, {"fused_g4_nb2048_cc1_p1_L4096_s8_mx1"}),
}


def _labelled_nodes() -> list:
    nodes = [dict(n, agent=False, labels=dict(n["labels"]))
             for n in cluster.plain_nodes(CONFIG["cluster"], SEED)]
    for i, n in enumerate(nodes):
        n["labels"]["cage"] = f"c{i // 3:03d}"
    return nodes


def _compiled() -> int:
    return sum(fn._cache_size() for fn in (
        kernel_mod.plan_group_jit, kernel_mod.plan_strategy_jit,
        kernel_mod.plan_fused_jit))


def _pending(tick: list, nodes: list, planner):
    """(store, scheduler, services): ``nodes`` and one service a group
    of ``tick`` with its tasks pending, ``planner`` behind the
    scheduler."""
    store = MemoryStore()
    services = [Service(id=f"svc{i:02d}", spec_version=Version(index=1),
                        spec=cluster.service_spec(f"s{i:02d}-{shape}",
                                                  SHAPES[shape], 1))
                for i, (shape, _k) in enumerate(tick)]

    def fill(tx):
        for n in cluster.store_nodes(nodes):
            tx.create(n)
        for svc, (_shape, k) in zip(services, tick):
            tx.create(svc)
            for t in _tasks(svc, 1, k, 1):
                tx.create(t)
    store.update(fill)
    sched = Scheduler(store, batch_planner=planner)
    store.view(sched._setup_tasks_list)
    return store, sched, services


def _built_run(tick: list, nodes: list, planner):
    """(scheduler, the fused run ``build_run`` makes of ``tick``)."""
    _store, sched, _services = _pending(tick, nodes, planner)
    planner.begin_tick(sched)
    specs = planner.probe_fused_run(
        sched, list(sched.unassigned_groups.values()), 0)
    assert len(specs) == len(tick)
    return sched, fusedbatch.build_run(planner, sched, specs)


def _one_tick(tick: list, planner, traced=False, repeat=False) -> dict:
    """``tick``'s groups placed in one tick by ``planner`` (and, with
    ``repeat``, the same groups again as later partial ones): the tasks
    read back, the counters, the spans if ``traced`` (the counters do
    not hang on the tracer), what the repeat compiled."""
    nodes = _labelled_nodes()
    store, sched, services = _pending(tick, nodes, planner)
    tracer.reset()
    if traced:
        tracer.enable()
    try:
        decided = sched.tick()
        spans = [(s.name, dict(s.args or {})) for s in tracer.spans()]
    finally:
        tracer.disable()
        tracer.reset()
    out = {"nodes": nodes, "decided": decided, "tasks": _read_back(store),
           "stats": _numbers(planner.stats), "spans": spans}
    if repeat:
        before = _compiled()
        for svc, (_shape, k) in zip(services, tick):
            _arrive(store, sched, _tasks(svc, k + 1, k, 2))
        out["again"] = sched.tick()
        out["compiled_again"] = _compiled() - before
        out["fused_again"] = planner.stats["groups_fused"] \
            - out["stats"]["groups_fused"]
    return out


@functools.lru_cache(maxsize=None)
def case(name: str, mode: str) -> dict:
    return _one_tick(CASES[name][0], _planner(mode),
                     traced=mode == "fused", repeat=mode == "fused")


def _by_value(nodes: list, counts: collections.Counter, label: str) -> dict:
    of = {n["id"]: n["labels"][label] for n in nodes}
    out = collections.Counter()
    for node_id, k in counts.items():
        out[of[node_id]] += k
    return dict(out)


@pytest.mark.parametrize("name", list(CASES))
def test_a_dense_run_places_what_its_groups_place_one_by_one_and_the_host(
        name):
    """Fused, one by one and on the host: the first two task for task;
    the host oracle service for service by what each value of the
    preferred label (each node, for a service without one) was given, as
    a multiset: which of equally loaded racks takes the odd task is each
    walk's own order, and later groups see what earlier ones placed."""
    tick, W, labels = CASES[name]
    fused, single, host = (case(name, mode) for mode in MODES)
    want = sum(k for _shape, k in tick)
    assert fused["decided"] == single["decided"] == host["decided"] == want
    assert {t["id"]: t["node_id"] for t in fused["tasks"]} \
        == {t["id"]: t["node_id"] for t in single["tasks"]}
    got, oracle = _counts(fused["tasks"]), _counts(host["tasks"])
    nodes = fused["nodes"]
    for i, (shape, k) in enumerate(tick):
        sid = f"svc{i:02d}"
        assert sum(got[sid].values()) == k == sum(oracle[sid].values())
        if shape == "binpack":
            continue    # packs onto what the groups before it left open
        label = LABEL_OF.get(shape)
        mine = _by_value(nodes, got[sid], label) if label else got[sid]
        theirs = _by_value(nodes, oracle[sid], label) if label \
            else oracle[sid]
        assert sorted(mine.values()) == sorted(theirs.values()), sid
        assert max(mine.values()) - min(mine.values()) <= 1, sid
    # one run, every chunk in the dense form under the parent's label
    stats = fused["stats"]
    pref = sum(shape in LABEL_OF for shape, _k in tick)
    wide = sum(LABEL_OF.get(shape) in ("rack", "cage") for shape, _k in tick)
    assert stats["groups_fused"] == len(tick) and stats["fused_wide_runs"] == 1
    assert stats["pref_groups"] == pref
    assert stats["pref_wide_groups"] == wide == stats["dense_pref_groups"]
    chunks = [a for span, a in fused["spans"]
              if span == "plan.dispatch" and a["route"] == "fused"]
    assert {a["label"] for a in chunks} == labels
    assert all(a["form"] == "dense" and a["L"] == 4096 for a in chunks)
    # one by one, with the tracer off
    assert single["stats"]["groups_planned"] == len(tick)
    assert single["stats"]["pref_wide_groups"] == wide \
        == single["stats"]["dense_pref_groups"]
    assert not single["spans"] and not host["stats"]["dense_pref_groups"]
    for run in (fused, single):
        for key in ("groups_small_to_host", "groups_fallback",
                    "groups_spill_to_host", "groups_device_error",
                    "fused_overflows"):
            assert not run["stats"].get(key), key


@pytest.mark.parametrize("name", list(CASES))
def test_a_warm_repeat_of_a_dense_run_compiles_nothing(name):
    """The same groups again, as later partial ones of services that
    hold tasks: one label, one pytree structure, one ``W``."""
    run = case(name, "fused")
    assert run["again"] == run["decided"]
    assert run["compiled_again"] == 0
    assert run["fused_again"] == len(CASES[name][0])


@pytest.mark.parametrize("name", list(CASES))
def test_a_dense_run_ships_its_slot_rows_in_place_of_its_leaf_rows(name):
    """``build_run``'s chunks: ``leaf`` is one ``LeafLayout`` of the
    run's ``W`` whose rows are the groups' own layouts relaid, all
    no-slot for a group without a preference or a padded slot (``flat``);
    the chunk's bytes up are the row form's and the ``[G]`` flags."""
    tick, W, _labels = CASES[name]
    nodes = _labelled_nodes()
    planner = _planner("fused")
    _sched, run = _built_run(tick, nodes, planner)
    n, nb, L = len(nodes), 2048, 4096
    assert (run.L, run.W, run.form) == (L, W, "dense")
    gi = 0
    for c in run.chunks:
        layout, flat = c.groups.leaf, c.groups.flat
        assert isinstance(layout, kernel_mod.LeafLayout) and layout.W == W
        assert layout.slot.shape == (c.gb, nb) and flat.shape == (c.gb,)
        assert layout.slot.dtype == "int32" and flat.dtype == bool
        assert layout.nbytes == 4 * c.gb * nb      # the leaf rows' bytes
        for j in range(c.gb):
            shape = tick[gi][0] if j < c.count else None
            label = LABEL_OF.get(shape)
            assert bool(flat[j]) == (label is None)
            if label is None:
                assert (layout.slot[j] == L * W).all()
            else:
                leaf, n_values, _own = fusedbatch.flat_leaf(
                    run.cols[0], nb, f"node.labels.{label}")
                afresh = fusedbatch.leaf_layout(leaf, n, L)
                want = afresh.slot[:n] // afresh.W * W \
                    + afresh.slot[:n] % afresh.W
                assert (layout.slot[j, :n] == want).all()
                assert (layout.slot[j, n:] == L * W).all()
                assert (layout.slot[j, :n] // W == leaf[:n]).all()
            gi += j < c.count
    planner.abort_fused_run(run)


def _stub_planner() -> TPUPlanner:
    """An injected ``plan_fn``: the single-device program behind a
    wrapper, as a mesh's or a test's stub stands."""
    def plan_fn(nodes, group, L, hier):
        assert hier == ()       # no layout for a program that is not ours
        return kernel_mod.plan_group_jit(nodes, group, L, hier)
    planner = TPUPlanner(plan_fn=plan_fn)
    planner._launch_overhead = LAUNCH_S
    planner.host_cost_per_node = PER_NODE_S
    return planner


@pytest.mark.parametrize("why", ["a_stub_plan_fn", "over_the_bound"])
def test_where_no_layout_can_come_the_scatter_form_stands(why, monkeypatch):
    """The choice is read off the input: an injected ``plan_fn`` is
    handed no layout (and fuses nothing), and a column whose ``L * W``
    is over ``DENSE_FORM_MAX_ENTRIES`` has none, of its own or in a
    run.  Both place what the dense form places."""
    name = "pref_constrained_binpack"
    tick = CASES[name][0]
    if why == "over_the_bound":
        monkeypatch.setattr(kernel_mod, "DENSE_FORM_MAX_ENTRIES", 4096 * 4)
        planner = _planner("fused")
    else:
        planner = _stub_planner()
    run = _one_tick(tick, planner, traced=True)
    stats = run["stats"]
    assert stats["pref_wide_groups"] == 2 and stats["dense_pref_groups"] == 0
    assert {t["id"]: t["node_id"] for t in run["tasks"]} \
        == {t["id"]: t["node_id"] for t in case(name, "single")["tasks"]}
    dispatch = [a for span, a in run["spans"] if span == "plan.dispatch"]
    if why == "over_the_bound":
        assert stats["groups_fused"] == len(tick)
        assert [a["form"] for a in dispatch] == ["scatter", "scatter"]
        alone = _one_tick(tick[:1], _planner("single"))
        assert alone["stats"]["pref_wide_groups"] == 1
        assert alone["stats"]["dense_pref_groups"] == 0 \
            == alone["stats"]["dense_tree_groups"]
    else:
        assert not stats.get("groups_fused")
        flat = [a for a in dispatch if a["label"] == FLAT_LABEL]
        assert len(flat) == 2 and all(a["form"] == "scatter" for a in flat)


def test_the_labels_are_the_parents_with_and_without_a_layout():
    nb = 2048
    leaf = np.zeros(nb, np.int32)
    leaf[:1300] = np.arange(1300) % RACKS
    layout = fusedbatch.leaf_layout(leaf, 1300, 4096)
    assert layout.W == 8

    class Cols:
        valid = np.zeros(nb, bool)
        quota_ok = None
        con_hash = np.zeros((1, 2, nb), np.int32)
        plat = np.zeros((1, 4), np.int32)
    for hier in ((), ((), None, layout)):
        assert planner_mod._bucket_label(Cols, Cols, 4096, hier) \
            == FLAT_LABEL
    tree = (((leaf, np.zeros(16, np.int32)),), np.zeros(4096, np.int32))
    for hier in (tree, tree + (layout,)):
        assert planner_mod._bucket_label(Cols, Cols, 4096, hier) \
            == "nb2048_cc1_p1_L4096_h2"
    run = fusedbatch.FusedRun(None, [], None, None, None, [], 4096, nb, 1,
                              1, 4, has_strat=True, W=8)
    chunk = fusedbatch.FusedChunk(0, 2, 2, None, 0)
    assert run.bucket_label(chunk) == "fused_g2_nb2048_cc1_p1_L4096_s4_mx1"
    assert run.form == "dense"
    run.W = 0
    assert run.bucket_label(chunk) == "fused_g2_nb2048_cc1_p1_L4096_s4_mx1"
    assert run.form == "scatter"


#: the fields of a fused chunk that hold arrays in the parent's call
#: (``quota_ok`` None: no leaf of the pytree), in its order
PARENT_FIELDS = ["k", "slot", "maxrep", "cpu_d", "mem_d", "con_hash",
                 "con_op", "con_exp", "plat", "failures", "leaf",
                 "extra_mask"]


@pytest.mark.parametrize("racks_per_zone, L", [(1, 16), (64, 256)])
def test_a_narrow_run_s_call_is_the_parents_field_for_field(racks_per_zone,
                                                            L):
    """At or under 256 leaves a fused chunk is what it was: the leaf
    ids by rows as a plain array, nothing where the dense form's flags
    would be, the same leaves of the same shapes and types in the same
    order, so the narrow signatures and their programs stand."""
    import jax
    cut = dict(CONFIG["cluster"], racks_per_zone=racks_per_zone, nodes=600)
    nodes = [dict(n, agent=False) for n in cluster.plain_nodes(cut, SEED)]
    tick = [("rack-spread", 40), ("flat-spread", 30), ("rack-spread", 9)]
    planner = _planner("fused")
    planner.host_cost_per_node = 3.5e-6 * FULL_NODES / 600
    sched, run = _built_run(tick, nodes, planner)
    assert (run.L, run.W) == (L, 0)
    assert run.form == ("mask" if L > 1 else "sum")
    for c in run.chunks:
        g = c.groups
        assert g.flat is None and g.quota_ok is None
        assert isinstance(g.leaf, np.ndarray) and g.leaf.dtype == np.int32
        assert g.leaf.shape == (c.gb, 1024) and g.leaf.max() < 4 * \
            racks_per_zone
        paths = [jax.tree_util.keystr(path) for path, _leaf
                 in jax.tree_util.tree_flatten_with_path(g)[0]]
        assert paths == [f".{name}" for name in PARENT_FIELDS]
        assert g._fields[:len(PARENT_FIELDS)] == tuple(PARENT_FIELDS)
    planner.abort_fused_run(run)
    assert sched.tick() == sum(k for _shape, k in tick)
    assert planner.stats["pref_groups"] == 2
    assert planner.stats["pref_wide_groups"] == 0 \
        == planner.stats["dense_pref_groups"]


# --- the layout kept by row

def _resident_and_afresh(planner, sched, label="rack"):
    """``flat_leaf``'s triple from the resident tier and from the walk
    over the same NodeInfos."""
    task = _tasks(Service(id="probe", spec=cluster.service_spec(
        "probe", SHAPES[f"{label}-spread"], 1)), 1, 1, 9)[0]
    built = planner._build_device_inputs(sched, task, 1)
    infos, n, nb = built[0], built[1], built[2]
    leaf, L, hier = built[7].leaf, built[9], built[10]
    w_leaf, n_values, w_layout = fusedbatch.flat_leaf(
        infos, nb, f"node.labels.{label}")
    assert L == fusedbatch.l_bucket(n_values)
    assert (leaf == w_leaf).all()
    layout = hier[2] if hier else None
    assert (layout is None) == (w_layout is None)
    if layout is not None:
        assert hier[:2] == ((), None)
        assert layout.W == w_layout.W
        assert (layout.slot == w_layout.slot).all()
        assert (layout.slot[n:] == L * layout.W).all()
    return leaf, L, layout


def _join(store, sched, nodes: list, node: dict) -> None:
    nodes.append(node)
    obj = cluster.store_nodes([node])[0]
    store.update(lambda tx: tx.create(obj))
    sched._create_or_update_node(store.view(lambda tx: tx.get(Node, obj.id)))


def _new_node(like: dict, i: int, rack: str) -> dict:
    return dict(like, id=f"late-{i:03d}", hostname=f"late-{i:03d}",
                labels=dict(like["labels"], rack=rack))


@pytest.mark.parametrize("event", ["appends", "a_leaf_outgrows_W",
                                   "a_value_crosses_a_rung",
                                   "a_label_change"])
def test_the_layout_kept_by_row_is_the_layout_built_afresh(event):
    """The resident flat column's ``LeafLayout`` against
    ``fusedbatch.flat_leaf``'s over the same nodes, through what a
    cluster does to it; what drops it is counted, as the tree's is."""
    racks_per_zone = 64 if event == "a_value_crosses_a_rung" else 65
    cut = dict(CONFIG["cluster"], racks_per_zone=racks_per_zone,
               nodes=4 * racks_per_zone * 3)
    nodes = [dict(n, agent=False, labels=dict(n["labels"]))
             for n in cluster.plain_nodes(cut, SEED)]
    store = MemoryStore()
    store.update(lambda tx: [tx.create(n)
                             for n in cluster.store_nodes(nodes)])
    planner = _planner("fused")
    sched = Scheduler(store, batch_planner=planner)
    store.view(sched._setup_tasks_list)
    stats = planner.stats
    leaf, L, layout = _resident_and_afresh(planner, sched)
    assert (stats["leaf_cols_builds"], stats["leaf_cols_hits"]) == (1, 0)
    full = planner._streaming.stats["full"]
    some_rack = nodes[7]["labels"]["rack"]
    if event == "a_value_crosses_a_rung":
        # 256 racks: the mask form's, no layout; the 257th brings one
        assert (L, layout) == (256, None)
        _join(store, sched, nodes, _new_node(nodes[0], 0, some_rack))
        assert _resident_and_afresh(planner, sched)[1:] == (256, None)
        _join(store, sched, nodes, _new_node(nodes[0], 1, "z9-r99"))
        leaf, L, layout = _resident_and_afresh(planner, sched)
        assert L == 4096 and layout.W == 4 and leaf[len(nodes) - 1] == 256
        assert stats["leaf_cols_invalidations"] == 0   # none stood
    else:
        assert L == 4096 and layout.W == 4      # three nodes a rack
        # a fourth node of a rack takes its next rank, in place
        _join(store, sched, nodes, _new_node(nodes[0], 0, some_rack))
        _leaf, _L, grown = _resident_and_afresh(planner, sched)
        assert grown is layout and grown.slot[len(nodes) - 1] % 4 == 3
        # a rack that is new, on the same rung: a row of the layout
        _join(store, sched, nodes, _new_node(nodes[0], 1, "z9-r99"))
        leaf, _L, grown = _resident_and_afresh(planner, sched)
        assert grown is layout and leaf[len(nodes) - 1] == RACKS
        assert grown.slot[len(nodes) - 1] == RACKS * 4
        assert stats["leaf_cols_invalidations"] == 0
    if event == "a_leaf_outgrows_W":
        # a fifth node there: one more than its four slots
        _join(store, sched, nodes, _new_node(nodes[0], 2, some_rack))
        _leaf, _L, wider = _resident_and_afresh(planner, sched)
        assert wider is not layout and wider.W == 8
        assert stats["leaf_cols_invalidations"] == 1
    if event == "a_label_change":
        # a node moved to the rack that holds four: the column is
        # walked again, and laid at twice the width
        mover = next(n for n in nodes if n["labels"]["rack"] != some_rack)
        mover["labels"]["rack"] = some_rack
        moved = cluster.store_nodes([mover])[0]

        def relabel(tx):
            node = tx.get(Node, moved.id).copy()
            node.spec = moved.spec
            tx.update(node)
            return node
        sched._create_or_update_node(store.update(relabel))
        builds = stats["leaf_cols_builds"]
        _leaf, _L, again = _resident_and_afresh(planner, sched)
        assert again is not layout and again.W == 8
        assert stats["leaf_cols_builds"] == builds + 1
    assert planner._streaming.stats["full"] == full    # appended, not rebuilt
    assert stats["leaf_cols_builds"] + stats["leaf_cols_hits"] >= 3
