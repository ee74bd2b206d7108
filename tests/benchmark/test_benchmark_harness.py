"""The benchmark's harness, rehearsed on the forced CPU at a tiny size:
the contract of ``BENCHMARK.json`` and its data files (every such test is
made twice, on the repo's benchmark and on the one ``one_more.py``
assembles with one more cell and its three-manager twin, their
configurations, a traffic file and three more per-layer metrics from new
data only), the generator, the plain reference and its controls, and
whole runs of cell 1, of the added cells and of the cell kept as data
(``rehearse_cells.KEPT``) through ``harness.run_cell``
(``rehearse_cells.py`` makes them in a process of its own; the TPU check
is switched off there, in the tests, and nowhere in the command)."""

import collections
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))

from benchmark import cluster, harness, readers, reference, traffic  # noqa: E402
from benchmark import warmup  # noqa: E402
import contract  # noqa: E402
import one_more  # noqa: E402
from rehearse_cells import KEPT, load_shrinks  # noqa: E402

BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]

# ------------------------------------------------------- the data's contract
#
# Every test that reads ``BENCHMARK.json`` or a metric's file is made on
# two trees (``conftest.py``: fixture ``bench``).

#: what each tree holds, for the tests that take one case a name; the
#: added names are ``one_more``'s constants, and
#: ``test_one_more_of_everything_is_only_additions`` holds the tree to them
METRICS_OF = {"repo": sorted(readers.load_layer_metrics())}
METRICS_OF["one_more"] = sorted(METRICS_OF["repo"]
                                + list(one_more.NEW_METRICS)
                                + list(one_more.MEMBER_METRICS))
CELLS_OF = {"repo": CELLS,
            "one_more": CELLS + [one_more.CELL, one_more.CELL3]}
TREES = one_more.TREES
EVERY_TREE = pytest.mark.parametrize("bench", TREES, indirect=True)


def _per(names_of):
    """One case a tree and a name of it."""
    cases = [(tree, name) for tree in TREES for name in names_of[tree]]
    return pytest.mark.parametrize(
        "bench,name", cases, indirect=["bench"],
        ids=[f"{tree}-{name}" for tree, name in cases])


def _cell(bench, name):
    return {w["name"]: w for w in bench["workloads"] + list(KEPT.values())
            }[name]


@EVERY_TREE
def test_benchmark_json_has_exactly_the_contract_keys(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= bench["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in bench["end_to_end"])
    assert os.path.getsize(
        os.path.join(harness.ROOT, "BENCHMARK.json")) < 64 << 10


@_per(CELLS_OF)
def test_every_cell_resolves_to_a_configuration_and_a_traffic_file(
        bench, name):
    contract.cell_resolves(bench, _cell(bench, name))


@EVERY_TREE
def test_names_and_units_hold_only_the_allowed_characters(bench):
    contract.names_and_units(bench)


@_per(METRICS_OF)
def test_layer_metric_file_is_sound_and_matches_benchmark_json(bench, name):
    contract.layer_metric_is_sound(
        bench, name, readers.load_layer_metrics()[name])


@EVERY_TREE
def test_every_per_layer_entry_has_its_file(bench):
    contract.every_entry_has_its_file(bench, readers.load_layer_metrics())


@EVERY_TREE
def test_benchmark_json_alone_says_which_cells_report_a_metric(bench):
    """One list: the ``per_layer`` entry's.  No metric's file repeats it,
    and ``readers.read_all`` is handed the entries."""
    for name, spec in readers.load_layer_metrics().items():
        assert "workloads" not in spec, name
    obs = readers.Observations()
    obs.series = {"create_rpc_s": [0.25, 0.75]}
    entry = {m["name"]: m for m in bench["per_layer"]}["create_rpc_ms"]
    there = dict(entry, workloads=["some.cell"])
    assert readers.read_all("some.cell", obs, [there]) \
        == {"create_rpc_ms": {"value": 500.0, "unit": "ms"}}
    assert readers.read_all("other.cell", obs, [there]) == {}


@EVERY_TREE
def test_the_routing_counter_reads_the_host_route(bench):
    """``host_route_groups_pct`` (ISSUE 28's; it came when PR 28 left the
    two span-read host-route metrics nothing to read in the one cell that
    listed them): counters, which say 0 where no group rides the host."""
    entry = {m["name"]: m for m in bench["per_layer"]}[
        "host_route_groups_pct"]
    assert "swarm-10k.deploys" in entry["workloads"]
    assert readers.load_layer_metrics()["host_route_groups_pct"] == {
        "layer": "planner routing", "unit": "%", "better": "lower",
        "moves": "decisions_per_s",
        "reader": {"kind": "counter", "scale": 100.0,
                   "num": {"source": "planner.stats",
                           "key": "groups_small_to_host"},
                   "den": {"source": "planner.stats",
                           "keys": ["groups_small_to_host",
                                    "groups_planned", "groups_fused"]}}}


ROUTES = {
    "most_on_the_host": ({"groups_small_to_host": 330, "groups_planned": 20,
                          "groups_fused": 50}, 82.5),
    "none_on_the_host": ({"groups_small_to_host": 0, "groups_planned": 140,
                          "groups_fused": 250}, 0.0),
    "no_group_at_all": ({"groups_small_to_host": 0, "groups_planned": 0},
                        None)}


@_per({tree: list(ROUTES) for tree in TREES})
def test_host_route_groups_pct_reads_nought_and_not_nothing(bench, name):
    routes, value = ROUTES[name]
    obs = readers.Observations()
    obs.counters = {"planner.stats": routes}
    line = readers.read_all("swarm-10k.deploys", obs, bench["per_layer"])
    if value is None:
        assert "host_route_groups_pct" not in line
    else:
        assert line["host_route_groups_pct"] == {"value": value, "unit": "%"}


def _spans(*rows):
    """Spans of one thread inside a window of ten seconds."""
    obs = readers.Observations()
    obs.window_wall = (0.0, 10.0)
    obs.spans = [("scheduler", name, a, b, args) for name, a, b, args in rows]
    return obs


@pytest.mark.parametrize("rows,value", [
    ([("sched.batch_build", 1.0, 1.1, {"wait_mean_ms": 30.0}),
      ("sched.batch_build", 2.0, 2.1, {"wait_mean_ms": 50.0})], 40.0),
    ([("sched.batch_build", 1.0, 1.1, {"wait_mean_ms": 0.0}),
      ("sched.batch_build", 2.0, 2.1, {"tasks": 7})], 0.0),
    ([("sched.batch_build", 1.0, 1.1, {"tasks": 7}),
      ("sched.batch_build", 2.0, 2.1, None)], None),
    ([("sched.batch_build", 9.9, 10.2, {"wait_mean_ms": 30.0})], None),
], ids=["on_every_span", "read_where_it_is", "on_no_span",
        "only_past_the_close"])
def test_arg_mean_leaves_out_what_no_span_of_the_window_carries(rows, value):
    """A tree whose spans lack the argument (PR 26's had neither
    ``wait_mean_ms`` nor ``offcpu_ms``) has nothing to read: the metric is
    left out of the line, not written as a mean of nought."""
    params = readers.load_layer_metrics()["queue_wait_ms"]["reader"]
    assert params["reduce"] == "arg_mean"
    assert readers.read_span(params, _spans(*rows)) == value


# ---------------------------------------------------------- the generator

def test_same_seed_same_schedule_and_every_seed_the_same_work():
    params = traffic.load("deploys")
    a = traffic.open_loop_schedule(params, 30, 7)
    assert a == traffic.open_loop_schedule(params, 30, 7)
    big = traffic.open_loop_schedule(params, 30, 2 ** 31 + 12345)
    assert a != big
    total = int(round(params["tasks_per_s"] * 30))
    for calls in (a, big):
        assert traffic.offered_tasks(calls) == total
        assert all(0 < c.due_s < 30 for c in calls)
        assert all(1 <= c.replicas <= 1000 for c in calls)
    # the same services and the same gaps, from another point of the cycle
    assert collections.Counter((c.name, c.shape, c.replicas) for c in a) \
        == collections.Counter((c.name, c.shape, c.replicas) for c in big)
    assert [c.name for c in a] != [c.name for c in big]

    def gaps(calls):
        dues = sorted({c.due_s for c in calls})
        return sorted(round(b - a, 6) for a, b in zip([0.0] + dues, dues))
    assert gaps(a) == gaps(big)
    # shapes cycle service by service, so a fusable run is at most the
    # stretch between two topology services
    cycle = params["shapes"]
    assert len(a) % len(cycle) == 0
    for calls in (a, big):
        at = cycle.index(calls[0].shape)
        assert [c.shape for c in calls] \
            == [cycle[(at + i) % len(cycle)] for i in range(len(calls))]


@pytest.mark.parametrize("cell", list(KEPT.values()), ids=list(KEPT))
def test_a_cell_kept_as_data_still_resolves(cell):
    """``harness-100k.backlog`` left ``BENCHMARK.json`` (too unsteady for
    the largest bound) and stays as files: one ``configs`` and one
    ``workloads`` entry bring it back."""
    assert cell["name"] not in CELLS
    config = cluster.load_config(cell["config"])
    assert len(config["source"]) <= 200 and config["chips"] == cell["chips"]
    assert config["guarantees"] \
        == cluster.load_config("swarm-10k")["guarantees"]
    params = traffic.load(cell["traffic"])
    assert params["generator"] in traffic.GENERATORS
    clients = traffic.closed_loop_clients(params)
    assert [c["client"] for c in clients] == list(range(len(clients)))
    assert {c["shape"] for c in clients} <= set(config["shapes"])


EVERY_CELL = _per({tree: CELLS_OF[tree] + list(KEPT) for tree in TREES})
#: what the derivation has to give for the two sizes the repo has, checked
#: once against the literals the warm-up test named before it derived them:
#: (the preference tree's label, fused labels, the longest stack)
TODAY = {"swarm-10k.deploys": ("nb16384_cc1_p1_L256_h2", 4, 3),
         "harness-100k.backlog": ("nb131072_cc1_p1_L4096_h2", 4, 3)}


@EVERY_CELL
def test_warmup_enumerates_the_signatures_of_a_cell(bench, name):
    stacks, labels, nb = contract.warmup_enumerates(_cell(bench, name))
    if name in TODAY:
        tree, fused, longest = TODAY[name]
        assert tree in labels and tree.startswith(f"nb{nb}_")
        assert f"nb{nb}_cc1_p1_L1_h0" in labels
        assert f"nb{nb}_cc1_p1_L1_h0_st1" in labels
        assert sum(lb.startswith("fused_") for lb in labels) == fused
        assert ["topology"] in stacks and max(map(len, stacks)) == longest


@EVERY_CELL
def test_every_cell_brings_its_cut_to_a_test_s_size(bench, name):
    contract.cut_to_a_test_s_size(load_shrinks(harness.ROOT),
                                  _cell(bench, name))


@EVERY_TREE
def test_a_cell_without_its_cut_fails_by_name(bench):
    cell = dict(bench["workloads"][0], name="swarm-10k.nightly")
    with pytest.raises(AssertionError, match="swarm-10k.nightly.json"):
        contract.cut_to_a_test_s_size(load_shrinks(harness.ROOT), cell)


def test_warmup_reads_its_ladders_from_the_planner(monkeypatch):
    from swarmkit_tpu.ops import fusedbatch, streaming
    config = cluster.load_config("swarm-10k")
    nodes = cluster.plain_nodes(config["cluster"], seed=3)
    deploys = traffic.load("deploys")
    monkeypatch.setattr(streaming, "D_BUCKETS", (8, 64))
    monkeypatch.setattr(fusedbatch, "CC_BUCKETS", (2, 8))
    _, labels = warmup.plan(config, deploys, nodes)
    assert {"stream_nb16384_d8", "stream_nb16384_d64"} <= set(labels)
    assert "nb16384_cc2_p1_L1_h0" in labels
    # a label of another name needs no edit: leaves are counted on the nodes
    for n in nodes:
        n["labels"]["row"] = n["labels"]["rack"][:4]
    shape = dict(config["shapes"]["topology"],
                 spread_over=["node.labels.zone", "node.labels.row"])
    assert warmup.group_label(shape, nodes) == "nb16384_cc2_p1_L16_h2"


# ------------------------------------------- the reference and its controls

def _tiny_cluster():
    config = cluster.load_config("swarm-10k")
    config["cluster"].update(nodes=160, racks_per_zone=4, agents=4)
    nodes = cluster.plain_nodes(config["cluster"], seed=5)
    services = [{"id": f"s{i}", "shape": config["shapes"][shape],
                 "replicas": k}
                for i, (shape, k) in enumerate(
                    [("spread", 200), ("constrained", 25), ("binpack", 70),
                     ("topology", 83), ("spread", 7), ("topology", 3)])]
    return nodes, services


def test_reference_placer_is_held_correct_by_its_own_comparison():
    nodes, services = _tiny_cluster()
    result = reference.compare(nodes, services,
                               reference.place(nodes, services))
    assert result["correct"], result
    assert result["numbers"]["spread_skew"] <= 1
    assert result["numbers"]["topology_leaf_skew"] <= 1


def test_a_topology_service_piled_on_one_node_of_a_rack_is_not_correct():
    """The leaf level of a preference tree is held to 1, like a plain
    spread service: levelled branches with one node of each rack taking
    the rack's whole share pass ``topology_skew`` and must not pass."""
    nodes, services = _tiny_cluster()
    tasks = reference.place(nodes, services)
    by_id = {n["id"]: n for n in nodes}
    first_of_rack = {}
    for t in tasks:
        if t["service_id"] == "s3":          # the 83-replica topology one
            rack = by_id[t["node_id"]]["labels"]["rack"]
            t["node_id"] = first_of_rack.setdefault(rack, t["node_id"])
            t["state"] = "running" if by_id[t["node_id"]]["agent"] \
                else "assigned"
    result = reference.compare(nodes, services, tasks)
    assert result["numbers"]["topology_skew"] <= 1
    assert result["numbers"]["topology_leaf_skew"] > 1
    assert not result["correct"]


@pytest.mark.parametrize("fault,number", [
    ("overcommit", "overcommitted_nodes"),
    ("constraint", "ineligible_tasks"),
    ("pile", "spread_skew")])
def test_control_breaks_one_guarantee_and_comes_out_not_correct(fault,
                                                                number):
    nodes, services = _tiny_cluster()
    result = reference.compare(
        nodes, services, reference.place(nodes, services, fault=fault))
    assert not result["correct"]
    assert result["numbers"][number] > result["limits"][number]


@pytest.mark.parametrize("spoil,number", [
    (lambda t, s: t.pop(), "missing_tasks"),
    (lambda t, s: t[0].update(node_id="", state="pending"), "unassigned"),
    (lambda t, s: [x.update(state="assigned") for x in t], "not_running"),
    (lambda t, s: s[0].update(read_back=False), "lost_services"),
    (lambda t, s: t.append({"id": "x", "service_id": "ghost",
                            "node_id": "node-00001",
                            "state": "assigned"}), "unacked_seen"),
], ids=["missing", "unassigned", "not_running", "lost", "unacked"])
def test_comparison_catches_each_broken_read_back(spoil, number):
    nodes, services = _tiny_cluster()
    tasks = reference.place(nodes, services)
    spoil(tasks, services)
    result = reference.compare(nodes, services, tasks)
    assert result["numbers"][number] > 0 and not result["correct"]


def test_a_forced_host_route_is_a_retreat_and_not_correct():
    nodes, services = _tiny_cluster()
    result = reference.compare(
        nodes, services, reference.place(nodes, services),
        retreats=["planner groups_breaker_to_host=3"])
    assert result["numbers"]["retreats"] == 1 and not result["correct"]


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(REPO, "benchmark", "reference.py")) as f:
        source = f.read()
    assert "swarmkit_tpu" not in source.split('"""', 2)[2]


# ------------------------------------------------------------ whole runs
#
# ``rehearse_cells.py`` makes them in a process of its own, held to two
# cores and niced, so that the suite's timing-sensitive daemon tests keep
# theirs.  One whole run of cell 1 stays in tier-1; the other runs (cell 1
# traced, the kept cell, one under each planted fault: over a minute together)
# are marked slow: ``pytest tests/benchmark -m slow``.

slow = pytest.mark.slow


def _rehearse(*names):
    done = subprocess.run(
        [sys.executable, os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "rehearse_cells.py"), *names],
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    return {k: tuple(v) for k, v in
            json.loads(done.stdout.strip().splitlines()[-1]).items()}


def _check_line(line):
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert set(line) <= {"correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "compared"}
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(line["device"])
    json.dumps(line)
    for value in line["metrics"].values():
        assert set(value) == {"value", "unit"}
        assert isinstance(value["value"], float)
    for number, limit in line["compared"].values():
        assert number <= limit


@pytest.fixture(scope="module")
def tier1_runs(one_more_tree):
    """The whole runs that stay in tier-1, made in one process on that
    tree (for cell 1 the repo's own files, byte for byte:
    ``test_one_more_of_everything_is_only_additions``): a plain run of
    cell 1, the added cell plain and traced beside cell 1 traced, and the
    three-manager twin traced (its plain run is ``test_managers.py``'s)."""
    tree, _ = one_more_tree
    return _rehearse("--root", tree, "swarm-10k.deploys:plain",
                     f"{one_more.CELL}:plain", f"{one_more.CELL}:traced",
                     "swarm-10k.deploys:traced", f"{one_more.CELL3}:traced")


def test_one_whole_run_of_cell_1_through_run_cell(tier1_runs):
    """``harness.run_cell`` end to end on the forced CPU: warm-up, window,
    drain, read-back, comparison, the contract's result line."""
    code, line = tier1_runs["swarm-10k.deploys:plain"]
    assert code == 0
    _check_line(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if "swarm-10k.deploys" in m.get("workloads", CELLS)}
    assert set(line["metrics"]) == e2e
    assert line["compared"]["window_compiles"] == [0, 0]
    # one manager: the numbers compared are the ones they were before
    # raft members could be asked for (PR 40), in their order
    assert list(line["compared"]) == list(reference.LIMITS) \
        + ["window_compiles", "failed"]


# ------------------------------------------- one more of everything, as data
#
# A later PR adds a deployment's cell with new files, appended entries and
# appended list items, and edits nothing that is there.  ``one_more.py``
# does exactly that in a temporary tree; the tests above are made on that
# tree as on the repo's, and the tests below hold it to "only additions"
# and run the added cell.

def test_one_more_of_everything_is_only_additions(one_more_tree):
    tree, extended = one_more_tree
    contract.only_additions(BENCH, extended)
    brought = {**{name: one_more.CELL for name in one_more.NEW_METRICS},
               **{name: one_more.CELL3 for name in one_more.MEMBER_METRICS}}
    for key, n in (("configs", 2), ("workloads", 2),
                   ("per_layer", len(brought)), ("end_to_end", 0)):
        assert len(extended[key]) == len(BENCH[key]) + n
    was, now = contract.data_files(REPO), contract.data_files(tree)
    assert {rel: now[rel] for rel in was} == was
    assert sorted(set(now) - set(was)) == sorted(
        [f"benchmark/configs/{one_more.CONFIG}.json",
         f"benchmark/configs/{one_more.CONFIG3}.json",
         f"benchmark/traffic/{one_more.TRAFFIC}.json",
         f"tests/benchmark/shrink/{one_more.CELL}.json",
         f"tests/benchmark/shrink/{one_more.CELL3}.json"]
        + [f"benchmark/layer_metrics/{name}.json" for name in brought])
    # each list that was there: the names the fixture says appended, and
    # nothing else; each metric brought: its one cell
    was = {m["name"]: m.get("workloads")
           for m in BENCH["end_to_end"] + BENCH["per_layer"]}
    for m in extended["end_to_end"] + extended["per_layer"]:
        listed = m.get("workloads")
        if m["name"] in brought:
            assert listed == [brought[m["name"]]]
        elif listed is None:
            assert was[m["name"]] is None
        else:
            assert listed[len(was[m["name"]]):] == [
                cell for cell, names in ((one_more.CELL, one_more.LISTED),
                                         (one_more.CELL3, one_more.LISTED3))
                if m["name"] in names]
    # the twin is the configuration's file but for the manager block
    with open(os.path.join(tree, "benchmark", "configs",
                           f"{one_more.CONFIG}.json")) as f:
        config = json.load(f)
    with open(os.path.join(tree, "benchmark", "configs",
                           f"{one_more.CONFIG3}.json")) as f:
        twin = json.load(f)
    assert {k: v for k, v in twin.items() if k not in ("name", "manager")} \
        == {k: v for k, v in config.items() if k not in ("name", "manager")}
    assert {k: v for k, v in twin["manager"].items()
            if k not in one_more.MANAGERS3 and k != "store"} \
        == {k: v for k, v in config["manager"].items()
            if k not in ("managers", "store")}
    assert {k: twin["manager"][k] for k in one_more.MANAGERS3} \
        == one_more.MANAGERS3 == {"managers": 3, "wal_fsync": True,
                                  "raft_link_delay_ms": 1}


@pytest.mark.parametrize("bench", ["one_more"], indirect=True)
def test_warmup_enumerates_the_added_cell_at_a_third_size(bench):
    """1,250 nodes, 10 racks a zone, a cycle of three shapes: the 2,048
    bucket, 40 racks in the 256 leaf bucket, runs of two at the most."""
    stacks, labels, nb = contract.warmup_enumerates(
        _cell(bench, one_more.CELL))
    assert nb == 2048
    assert labels == [
        "fused_g1_nb2048_cc1_p1_L1_s2_mx1", "nb2048_cc1_p1_L1_h0",
        "nb2048_cc1_p1_L1_h0_st1", "nb2048_cc1_p1_L256_h2",
        "stream_nb2048_d16", "stream_nb2048_d256", "stream_nb2048_d4096"]
    assert sorted(stacks) == [["binpack"], ["spread"], ["spread", "binpack"],
                              ["topology"]]


def test_the_added_cell_runs_plain_and_reports_the_end_to_end_metrics(
        tier1_runs):
    code, line = tier1_runs[f"{one_more.CELL}:plain"]
    assert code == 0
    _check_line(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    # ``assign_p50_ms``: the third list the cell joined, on the plain line
    assert set(line["metrics"]) == {"decisions_per_s", "assign_p50_ms",
                                    "setup_s"}


#: what the added cells' traced lines may lack though their lists name it:
#: ``lock_wait_ms`` only where a writer waited a millisecond or longer, at
#: this size not in every run; and where the router's probe, taken in the
#: warm-up of a loaded CPU, sends the small groups to the host, the window
#: holds no fused run and no two-level group on the device
SOMETIMES = {"lock_wait_ms", "fused_run_ms", "fused_build_ms",
             "tree_cols_hit_pct"}


@pytest.mark.parametrize("cell,brought", [
    (one_more.CELL, one_more.NEW_METRICS),
    (one_more.CELL3, one_more.MEMBER_METRICS)], ids=["added", "twin"])
def test_the_added_cell_s_traced_line_holds_what_its_lists_say(
        tier1_runs, one_more_tree, cell, brought):
    """The per-layer metrics whose lists the cell joined and those it
    brought, and none of the others; for the three-manager twin that is a
    counter of the raft member the harness drives.  Cell 1's line in the
    same tree holds what it held and none of the new ones."""
    code, line = tier1_runs[f"{cell}:traced"]
    assert code == 0
    _check_line(line)
    assert line["correct"] is True and line["failed"] == 0
    listed = {m["name"] for m in one_more_tree[1]["per_layer"]
              if cell in m["workloads"]}
    assert set(brought) <= listed
    assert listed - SOMETIMES <= set(line["metrics"]) <= listed
    for name, spec in brought.items():
        assert line["metrics"][name]["unit"] == spec["unit"]
        assert line["metrics"][name]["value"] > 0
    code, old = tier1_runs["swarm-10k.deploys:traced"]
    assert code == 0 and old["correct"] is True
    assert not set(brought) & set(old["metrics"])
    assert {"tick_ms", "tick_tasks", "device_route_pct",
            "host_route_groups_pct"} <= set(old["metrics"])
    assert set(old["metrics"]) <= {m["name"] for m in BENCH["per_layer"]}


@pytest.fixture(scope="module")
def runs():
    """One traced run of cell 1, one plain run of the kept cell and one under
    each planted fault; {name: (exit code, result line)}."""
    return _rehearse("swarm-10k.deploys:traced", "harness-100k.backlog:plain",
                     "host_route", "answer_altered", "group_on_one_node")


@slow
@pytest.mark.parametrize("cell", ["swarm-10k.deploys:traced",
                                  "harness-100k.backlog:plain"])
def test_result_line_has_exactly_the_contract_keys(runs, cell):
    code, line = runs[cell]
    assert code == 0
    _check_line(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0


@slow
def test_untraced_line_reports_the_cell_s_end_to_end_metrics(runs):
    _, line = runs["harness-100k.backlog:plain"]
    assert set(line["metrics"]) == {"decisions_per_s", "setup_s"}
    assert line["metrics"]["decisions_per_s"]["value"] > 0


@slow
def test_traced_line_reports_per_layer_metrics_and_no_window_compile(runs):
    _, line = runs["swarm-10k.deploys:traced"]
    per_layer = {m["name"] for m in BENCH["per_layer"]
                 if "swarm-10k.deploys" in m["workloads"]}
    assert set(line["metrics"]) <= per_layer
    # what a CPU run has no device trace for is left out, never zeroed
    for name in ("device_idle_pct", "plan_kernel_ms", "plan_roofline"):
        assert name not in line["metrics"]
    for name in ("tick_ms", "tick_tasks", "device_route_pct", "commit_ms",
                 "create_rpc_ms", "pending_lag_ms", "materialise_per_s",
                 "generator_late_ms", "window_compiles", "assign_p95_ms"):
        assert name in line["metrics"], name
    assert line["metrics"]["window_compiles"]["value"] == 0
    assert 0 < line["metrics"]["device_route_pct"]["value"] <= 100


@slow
@pytest.mark.parametrize("fault,number", [
    ("host_route", "retreats"),
    ("answer_altered", None),
    ("group_on_one_node", "topology_leaf_skew")])
def test_run_with_the_timed_path_broken_is_not_correct(runs, fault, number):
    code, line = runs[fault]
    assert code == 0 and line["correct"] is False
    over = [k for k, (n, lim) in line["compared"].items() if n > lim]
    assert over and (number is None or number in over)


# ----------------------------------------------------------- the command

def _command(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"),
         "--workload", "swarm-10k.deploys", "--seed", "1", "--seconds",
         "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_anything_but_a_tpu_and_prints_no_result():
    done = _command(REPO)
    assert done.returncode not in (0, None)
    assert done.stdout.splitlines()[0].startswith("device platform=cpu")
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())


def test_command_fails_in_a_directory_with_only_the_benchmark(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    done = _command(str(tmp_path))
    assert done.returncode != 0
    assert not any(ln.startswith("{") for ln in done.stdout.splitlines())
