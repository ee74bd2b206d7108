"""``harness-100k``'s served path at a test's size: what 100,000 nodes in
1,000 racks bring that the two smaller configurations do not, driven on
the forced CPU through ``Manager()`` and the control API.

The cluster is the configuration's own (``benchmark/configs/
harness-100k.json``) under the cut its cell brings for the CPU
(``tests/benchmark/shrink/harness-100k.sparse.json``: 1,300 nodes, 65
racks a zone), dealt by ``benchmark/cluster.plain_nodes``.  260 racks are
more than 256 leaves, so a two-level topology group is a *wide tree*: its
leaf bucket is 4,096, its label ``..._L4096_h2``, and its searches take
the dense form of ``ops/kernel.py`` (``L > MASK_FORM_MAX_L`` with the
tree's ``LeafLayout``, kept by the resident tier beside the level
columns), the one branch neither smaller configuration runs.

One tick of the four shapes is deployed twice: the router's probes are
pinned to what they read at the configuration's size (a launch of 5 ms
against a scan priced for 100,000 nodes), so the router itself sends
every group to the device, as in the cell.  The outcome is held to the
plain reference's comparison (``benchmark/reference.py compare``), to the
host route (the same tick with a launch no group amortises, every group
placed by the host oracle) and, launch by launch, to the same program
without the layout (the scatter form) and traced with
``MASK_FORM_MAX_L`` raised to the leaf bucket (the mask form): the three
forms of a step must agree at 4,096 leaves as PR 33's cases show two of
them to at 256.

"The same placement" is said of a group's per-node counts: the tasks of a
group are interchangeable.  The flat shapes agree node for node between
the device and the host oracle.  The topology shape agrees level by
level, per zone and per rack where the group divides evenly over the
racks; which of equally loaded nodes or racks takes an odd task is each
walk's own order (``tests/test_swarm1k_routes.py``), so there it is held
to the guarantee; the tick walks its flat groups first, and those of the
first tick, placed on an empty cluster, are the ones compared node for
node.

Tier-1: placements, counters and spans, never a speed."""

import collections
import functools
import json
import os
import sys
import time

import numpy as np
import pytest

pytest.importorskip("cryptography")   # the manager's CA bootstrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import cluster, reference  # noqa: E402
from swarmkit_tpu.obs import devicetelemetry, tracer  # noqa: E402
from swarmkit_tpu.ops import fusedbatch, planner as planner_mod  # noqa: E402
from swarmkit_tpu.ops import kernel as kernel_mod  # noqa: E402

CELL = "harness-100k.sparse"
CONFIG = cluster.load_config("harness-100k")
with open(os.path.join(REPO, "tests", "benchmark", "shrink",
                       f"{CELL}.json")) as f:
    CUT = json.load(f)["cluster"]
FULL_NODES = CONFIG["cluster"]["nodes"]
CONFIG["cluster"].update(CUT)
RACKS = CONFIG["cluster"]["zones"] * CONFIG["cluster"]["racks_per_zone"]
SEED = 2 ** 31 + 34
#: the pinned probes: a launch of 5 ms against a scan of 3.5 us a node
#: over the configuration's 100,000 nodes (0.35 s), priced on this cut
LAUNCH_S = 0.005
PER_NODE_S = 3.5e-6 * FULL_NODES / CONFIG["cluster"]["nodes"]
#: one tick in the order the scheduler walks it, flat groups first: a
#: fused run of four, the last of three tasks, then three wide trees of
#: which the first divides evenly over the 260 racks
TICK = [("spread", 40), ("constrained", 30), ("binpack", 50),
        ("spread", 3), ("topology", 2 * RACKS), ("topology", 33),
        ("topology", 1)]
TREES = [k for shape, k in TICK if shape == "topology"]
TREE_LABEL = "nb2048_cc1_p1_L4096_h2"
#: the tick is deployed three times: the second meets the first's dirty
#: rows (the resident scatter's bucket for a tick of this size compiles
#: there), the third is the warm repeat
ROUNDS = 3


def _deploy(mgr, round_no: int, timeout: float = 120.0) -> list:
    """Create the tick's services back to back and wait until every task
    has a node; the ids in the tick's order."""
    api = mgr.control_api
    ids = [api.create_service(cluster.service_spec(
        f"r{round_no}-{i:02d}-{shape}", CONFIG["shapes"][shape], k)).id
        for i, (shape, k) in enumerate(TICK)]
    want = sum(k for _shape, k in TICK)
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        tasks = [t for t in api.list_tasks() if t.service_id in ids]
        if sum(1 for t in tasks if t.node_id) >= want:
            return ids
        time.sleep(0.05)
    raise AssertionError(f"round {round_no} was not assigned in {timeout}s")


def _numbers(table: dict) -> dict:
    return {k: v for k, v in table.items() if isinstance(v, (int, float))}


def _programs() -> tuple:
    from swarmkit_tpu.ops import streaming
    return (kernel_mod.plan_group_jit, kernel_mod.plan_strategy_jit,
            kernel_mod.plan_fused_jit, streaming._scatter_rows_jit)


def _compiled() -> int:
    """Signatures the plan programs and the resident scatter hold
    compiled, by the jit caches themselves (the compile ledger is part
    of the telemetry the warm repeat switches off)."""
    return sum(fn._cache_size() for fn in _programs())


@functools.lru_cache(maxsize=None)
def outcome(mode: str) -> dict:
    """``ROUNDS`` ticks of ``TICK`` through a live manager, routed by the
    router under the pinned probes (``device``) or with a launch no
    group amortises (``host``); what was placed, counted, traced and
    launched."""
    from swarmkit_tpu.manager import Manager
    from swarmkit_tpu.manager.dispatcher import Config_
    import jax
    # no agent runs here: the comparison asks RUNNING of no task
    nodes = [dict(n, agent=False)
             for n in cluster.plain_nodes(CONFIG["cluster"], SEED)]
    # caches of its own: what the worker's earlier tests compiled and
    # named (``tests/test_ha_prefs_path.py`` meets the wide tree's label
    # on the same cut) is neither found nor counted here, so the first
    # round compiles every signature and the ledger names each once
    for fn in _programs():
        fn.clear_cache()
    devicetelemetry.reset()
    mgr = Manager(dispatcher_config=Config_(heartbeat_period=600.0))
    mgr.run()
    launches, rounds = [], []
    try:
        sched = mgr.scheduler
        planner = sched.batch_planner
        planner._launch_overhead = 10.0 if mode == "host" else LAUNCH_S
        planner.host_cost_per_node = PER_NODE_S
        # hold the tick until the orchestrator has made the whole stack
        sched.debounce_gap, sched.max_latency = 1.0, 60.0
        call = planner._call_plan_fn

        def spy(nodes_in, group_in, L, hier, sp=None):
            operands = jax.tree_util.tree_map(
                np.array, (nodes_in, group_in, hier))
            out = call(nodes_in, group_in, L, hier, sp)
            launches.append({
                "operands": operands, "L": L,
                "label": planner_mod._bucket_label(nodes_in, group_in, L,
                                                   hier),
                "out": tuple(np.asarray(a) for a in out)})
            return out
        planner._call_plan_fn = spy
        objs = cluster.store_nodes(nodes)
        mgr.store.update(lambda tx: [tx.create(n) for n in objs])
        tracer.reset()
        tracer.enable()
        for r in range(ROUNDS):
            if r == ROUNDS - 1:
                # the warm repeat with the telemetry ledger off: the
                # planner's counters do not hang on it
                devicetelemetry.set_enabled(False)
            before = {"planner": _numbers(planner.stats),
                      "sched": _numbers(sched.stats),
                      "ledger": devicetelemetry.transfer_totals(),
                      "compiles": _compiled(),
                      "signatures": set(
                          devicetelemetry.compile_cache_snapshot()),
                      "launches": len(launches)}
            ids = _deploy(mgr, r)
            rounds.append({
                "ids": ids,
                "planner": {k: v - before["planner"].get(k, 0) for k, v
                            in _numbers(planner.stats).items()},
                "ticks": sched.stats["ticks"] - before["sched"]["ticks"],
                "ledger": {k: v - before["ledger"].get(k, 0) for k, v in
                           devicetelemetry.transfer_totals().items()},
                "compiles": _compiled() - before["compiles"],
                "signatures": set(devicetelemetry.compile_cache_snapshot())
                - before["signatures"],
                "launches": launches[before["launches"]:]})
        tracer.disable()
        spans = [(s.name, dict(s.args or {})) for s in tracer.spans()]
        api = mgr.control_api
        listed = {s.id for s in api.list_services()}
        tasks = [{"id": t.id, "service_id": t.service_id,
                  "node_id": t.node_id or "",
                  "state": "assigned" if t.node_id else "pending"}
                 for t in api.list_tasks()]
    finally:
        devicetelemetry.set_enabled(True)
        tracer.disable()
        tracer.reset()
        mgr.stop()
    return {"nodes": nodes, "rounds": rounds, "spans": spans,
            "listed": listed, "tasks": tasks}


def _counts(run: dict, service_id: str) -> collections.Counter:
    return collections.Counter(t["node_id"] for t in run["tasks"]
                               if t["service_id"] == service_id)


def _by_label(run: dict, counts: collections.Counter, label: str) -> dict:
    of = {n["id"]: n["labels"][label] for n in run["nodes"]}
    out = collections.Counter()
    for node_id, k in counts.items():
        out[of[node_id]] += k
    return dict(out)


def test_the_cut_keeps_the_wide_tree_and_the_router_sends_all_to_the_device():
    assert FULL_NODES == 100000 and CONFIG["reduced"] == ["tasks"]
    assert RACKS == 260 > planner_mod.WIDE_TREE_LEAVES \
        == kernel_mod.MASK_FORM_MAX_L
    assert fusedbatch.l_bucket(RACKS) == 4096 == fusedbatch.l_bucket(1000)
    nodes = cluster.plain_nodes(CONFIG["cluster"], SEED)
    assert len({n["labels"]["rack"] for n in nodes}) == RACKS
    assert fusedbatch.n_bucket(len(nodes)) == 2048
    # priced for the configuration's 100,000 nodes, the host scan loses
    # to a launch for a group of one task
    assert PER_NODE_S * len(nodes) > 0.8 * LAUNCH_S


@pytest.mark.parametrize("mode", ["device", "host"])
def test_two_ticks_are_held_to_the_reference_at_every_limit(mode):
    run = outcome(mode)
    assert [r["ticks"] for r in run["rounds"]] == [1] * ROUNDS
    services = [{"id": sid, "shape": CONFIG["shapes"][shape],
                 "replicas": k, "read_back": sid in run["listed"]}
                for r in run["rounds"]
                for sid, (shape, k) in zip(r["ids"], TICK)]
    result = reference.compare(run["nodes"], services, run["tasks"])
    numbers = result["numbers"]
    assert result["correct"], (numbers, result["notes"])
    for name in ("lost_services", "missing_tasks", "unassigned",
                 "not_running", "unacked_seen", "overcommitted_nodes",
                 "ineligible_tasks", "retreats"):
        assert numbers[name] == 0, name
    for name in ("spread_skew", "binpack_open_nodes", "topology_leaf_skew",
                 "topology_skew"):
        assert numbers[name] == 1, name


def test_every_group_rides_the_device_and_the_trees_ride_the_wide_label():
    run = outcome("device")
    for r in run["rounds"]:
        grown = r["planner"]
        assert grown["groups_small_to_host"] == 0
        assert grown.get("groups_fallback", 0) == 0
        assert grown["groups_planned"] + grown["groups_fused"] == len(TICK)
        assert grown["tasks_planned"] == sum(k for _s, k in TICK)
        # the topology groups and only them, every one in the dense form
        assert grown["wide_tree_groups"] == len(TREES) \
            == grown["dense_tree_groups"]
        assert grown["wide_tree_s"] > 0
        wide = [c for c in r["launches"] if c["L"] > 256]
        assert [c["label"] for c in wide] == [TREE_LABEL] * len(TREES)
        assert [int(c["operands"][1].k) for c in wide] == TREES
        for c in wide:
            layout = c["operands"][2][2]
            assert kernel_mod.search_form(c["L"], layout.W) == "dense"
            assert layout.W == fusedbatch.pow2_bucket(int(np.bincount(
                c["operands"][0].leaf[:len(run["nodes"])]).max()))
    # the span of a group launched on its own says the form it took
    # (the flat groups ride fused runs: only the trees launch alone)
    own = [a for name, a in run["spans"]
           if name == "plan.dispatch" and a["route"] == "group"]
    assert [(a["form"], a["label"]) for a in own] \
        == [("dense", TREE_LABEL)] * (ROUNDS * len(TREES))
    host = outcome("host")
    for r in host["rounds"]:
        assert r["planner"]["groups_small_to_host"] == len(TICK)
        assert r["planner"]["wide_tree_groups"] == 0 \
            == r["planner"]["dense_tree_groups"]
        assert r["planner"]["wide_tree_s"] == 0 and not r["launches"]


def test_the_flat_groups_equal_the_host_oracle_node_for_node():
    """In the first tick, which the flat groups open on an empty
    cluster.  Those of the second tick meet the first tick's trees,
    whose odd tasks sit on other nodes of the same levels on each route,
    and follow them: they are held to the guarantee above."""
    device, host = outcome("device"), outcome("host")
    rd, rh = device["rounds"][0], host["rounds"][0]
    for sd, sh, (shape, k) in zip(rd["ids"], rh["ids"], TICK):
        if shape != "topology":
            assert _counts(device, sd) == _counts(host, sh), (shape, k)
            assert sum(_counts(device, sd).values()) == k


def test_the_wide_trees_equal_the_host_oracle_level_by_level():
    device, host = outcome("device"), outcome("host")
    for rd, rh in zip(device["rounds"], host["rounds"]):
        for sd, sh, (shape, k) in zip(rd["ids"], rh["ids"], TICK):
            if shape != "topology":
                continue
            got, want = _counts(device, sd), _counts(host, sh)
            assert sum(got.values()) == sum(want.values()) == k
            # zones: the same totals on both routes, within one
            zones = _by_label(device, got, "zone")
            assert sorted(zones.values()) == sorted(
                _by_label(host, want, "zone").values())
            # racks: the same multiset of per-rack counts, and where the
            # group divides evenly the same count in every rack
            racks = _by_label(device, got, "rack")
            assert sorted(racks.values()) == sorted(
                _by_label(host, want, "rack").values())
            if k % RACKS == 0:
                assert racks == _by_label(host, want, "rack")
                assert set(racks.values()) == {k // RACKS}
            # (the nodes of a rack are levelled too: the reference's
            # ``topology_leaf_skew``, held at 1 above)


@pytest.mark.parametrize("form", ["scatter", "mask"])
def test_the_three_forms_agree_at_4096_leaves(form, monkeypatch):
    """Every wide-tree launch of the served path took the dense form;
    run again through the same program without the layout (the scatter
    form), and traced with the mask form allowed up to the leaf
    bucket."""
    import jax
    run = outcome("device")
    wide = [c for r in run["rounds"] for c in r["launches"] if c["L"] > 256]
    assert len(wide) == ROUNDS * len(TREES)
    assert kernel_mod.MASK_FORM_MAX_L < 4096
    if form == "mask":
        monkeypatch.setattr(kernel_mod, "MASK_FORM_MAX_L", 4096)
    assert kernel_mod.search_form(4096) == form
    again = jax.jit(kernel_mod.plan_group, static_argnames=("L",))
    for c in wide:
        nodes_in, group_in, hier = c["operands"]
        x, fail_counts, spill = again(nodes_in, group_in, L=c["L"],
                                      hier=hier[:2])
        assert (np.asarray(x) == c["out"][0]).all()
        assert (np.asarray(fail_counts) == c["out"][1]).all()
        assert bool(spill) == bool(c["out"][2]) is False
        assert int(c["out"][0].sum()) == int(group_in.k)


def test_the_bytes_counters_grow_by_the_operands_and_the_results():
    run = outcome("device")
    dispatch = [a for name, a in run["spans"] if name == "plan.dispatch"]
    d2h = [a for name, a in run["spans"] if name == "plan.d2h"]
    launches = [c for r in run["rounds"] for c in r["launches"]]
    own = [a for a in dispatch if a["route"] == "group"]
    assert len(own) == len(launches)
    for args, c in zip(own, launches):
        assert args["label"] == c["label"]
        assert args["h2d_bytes"] == devicetelemetry.tree_nbytes(
            c["operands"]) > 0
    # every fetch names its launch and carries its result's bytes
    assert len(d2h) == len(dispatch)
    assert sorted(a["label"] for a in d2h) \
        == sorted(a["label"] for a in dispatch)
    fetched = {c["label"]: devicetelemetry.tree_nbytes(c["out"])
               for c in launches}
    for args in d2h:
        assert args["d2h_bytes"] > 0
        if args["label"] in fetched:
            assert args["d2h_bytes"] == fetched[args["label"]]
    *ledgered, warm = run["rounds"]
    # with the ledger on the counters grow by exactly what it books;
    # with it off they grow all the same
    for r in ledgered:
        assert r["planner"]["h2d_bytes"] == r["ledger"]["h2d"] > 0
        assert r["planner"]["d2h_bytes"] == r["ledger"]["d2h"] > 0
    assert warm["ledger"] == {"d2h": 0, "h2d": 0}
    assert warm["planner"]["d2h_bytes"] == ledgered[-1]["planner"][
        "d2h_bytes"]
    assert warm["planner"]["h2d_bytes"] == ledgered[-1]["planner"][
        "h2d_bytes"]
    # every fetch is a launch's, so the fetched bytes are the spans'; the
    # uploads are the launches' and the resident tier's besides
    grown = {k: sum(r["planner"][k] for r in run["rounds"])
             for k in ("h2d_bytes", "d2h_bytes")}
    assert sum(a["d2h_bytes"] for a in d2h) == grown["d2h_bytes"]
    assert 0 < sum(a["h2d_bytes"] for a in dispatch) < grown["h2d_bytes"]


def test_the_warm_repeat_compiles_nothing():
    """The second tick meets the signatures of the first: the wide
    tree's, the flat group's, the fused run's, the scatter's."""
    first, second, warm = outcome("device")["rounds"]
    # on caches of its own the first round compiles and names them all
    # (the run of four flat groups is two chunks under one label)
    assert first["signatures"] \
        == {TREE_LABEL, "fused_g2_nb2048_cc1_p1_L1_s4_mx1"}
    assert first["compiles"] == len(first["signatures"])
    assert all(label.startswith("stream_nb2048_d")
               for label in second["signatures"])
    assert second["compiles"] == len(second["signatures"])
    assert warm["compiles"] == 0
    # the four flat groups ride one fused run, the trees launch alone
    assert len(warm["launches"]) == len(TREES)
    assert warm["planner"]["groups_fused"] == len(TICK) - len(TREES)
