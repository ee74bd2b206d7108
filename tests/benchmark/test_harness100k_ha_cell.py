"""``harness-100k-ha.prefs``, the 100,000-node cluster whose spread
services carry one preference over 1,000 racks, as the benchmark's data
states it and as a CPU rehearsal runs it.

The configuration is ``harness-100k``'s but for the preference, and the
traffic ``sparse``'s but for the shapes' names and the rate, so that the
two cells differ in one thing; both are compared key by key here.  At its
real size (131,072 rows, 1,000 racks) the warm-up's enumeration is
checked against the literal labels the cell must compile before the
window opens: the one-preference group's ``nb131072_cc1_p1_L4096_h0``
and the fused runs', every one at ``L4096`` because a run's static ``L``
is its widest group's.  At the size its own cut gives (more than 256
racks, so the rehearsal rides ``L4096`` too) one plain and one traced
run go through ``harness.run_cell`` in ``rehearse_cells.py``'s process,
and one control places the cell's services with the rack level ignored,
read by a check of this file's own that holds the racks to 1 where the
reference's limit is 75."""

import copy
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(1, HERE)

from benchmark import cluster, harness, kernel_bytes, reference  # noqa: E402
from benchmark import traffic, warmup  # noqa: E402
import contract  # noqa: E402
import one_more  # noqa: E402
from rehearse_cells import load_shrinks  # noqa: E402

CELL = "harness-100k-ha.prefs"
TWIN = "harness-100k.sparse"
BENCH = harness.load_benchmark()
ENTRY = {w["name"]: w for w in BENCH["workloads"]}[CELL]
CONFIG = cluster.load_config(ENTRY["config"])
TRAFFIC = traffic.load(ENTRY["traffic"])
NODES = cluster.plain_nodes(CONFIG["cluster"], seed=3)
STACKS, LABELS = warmup.plan(CONFIG, TRAFFIC, NODES)
FLAT = "nb131072_cc1_p1_L4096_h0"
TREE = "nb131072_cc1_p1_L4096_h2"
FUSED = {"fused_g1_nb131072_cc1_p1_L4096_s2",
         "fused_g1_nb131072_cc1_p1_L4096_s2_mx1",
         "fused_g1_nb131072_cc1_p1_L4096_s4_mx1",
         "fused_g2_nb131072_cc1_p1_L4096_s4_mx1"}
DEVICE_TRACE = {m["name"] for m in BENCH["per_layer"]
                if m["source"] == "device_trace"}
LISTED = {m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]}
NEW = {"pref_groups_pct": "%", "fused_wide_run_ms": "ms",
       "fused_wide_groups_pct": "%", "leaf_cols_hit_pct": "%"}
#: the twin's own two, which read the wide two-level tree this cell's
#: traffic meets as often as the twin's but does not list
PINNED = {"wide_tree_group_ms", "wide_tree_groups_pct"}


def test_the_configuration_is_harness_100k_s_but_for_the_preference():
    twin = cluster.load_config("harness-100k")
    for key in ("chips", "cluster", "manager", "reduced", "reduced_note"):
        assert CONFIG[key] == twin[key], key
    assert CONFIG["reduced"] == ["tasks"] and ENTRY["chips"] == 1
    assert CONFIG["source"] != twin["source"]
    shapes, was = CONFIG["shapes"], twin["shapes"]
    assert list(shapes) == ["rack-spread", "rack-constrained", "binpack",
                            "topology"]
    assert shapes["binpack"] == was["binpack"]
    assert shapes["topology"] == was["topology"]
    for name, of in (("rack-spread", "spread"),
                     ("rack-constrained", "constrained")):
        assert shapes[name] == dict(
            was[of], spread_over=["node.labels.rack"]), name
    assert CONFIG["assumed"][:len(twin["assumed"])] == twin["assumed"]
    assert len(CONFIG["assumed"]) == len(twin["assumed"]) + 2
    for key, said in twin["guarantees"].items():
        if key != "topology":
            assert CONFIG["guarantees"][key] == said, key
    assert "one-level rack tree" in CONFIG["guarantees"]["topology"]
    assert set(CONFIG["guarantees"]) == set(twin["guarantees"])


def test_the_traffic_is_sparse_s_but_for_the_shapes_and_the_rate():
    sparse = traffic.load("sparse")
    own = {"shapes", "tasks_per_s", "sustained_tasks_per_s", "rate_note"}
    assert set(TRAFFIC) == set(sparse)
    for key in set(sparse) - own:
        assert TRAFFIC[key] == sparse[key], key
    assert TRAFFIC["shapes"] == list(CONFIG["shapes"])
    # the lower of sparse's rate and four fifths of what this cell
    # sustains, rounded down to a multiple of 50, or the fallbacks ISSUE
    # 36 allows (three fifths, a half)
    rate, sustained = TRAFFIC["tasks_per_s"], TRAFFIC["sustained_tasks_per_s"]
    assert rate % 50 == 0 and rate in {
        min(sparse["tasks_per_s"], int(sustained * share) // 50 * 50)
        for share in (0.8, 0.6, 0.5)}
    assert str(sustained) in TRAFFIC["rate_note"]
    # both cells offer the same services and gaps to every seed
    seed = 2 ** 31 + 36
    if rate == sparse["tasks_per_s"]:
        mine = traffic.open_loop_schedule(TRAFFIC, 51, seed)
        theirs = traffic.open_loop_schedule(sparse, 51, seed)
        assert [(c.due_s, c.replicas, c.deploy) for c in mine] \
            == [(c.due_s, c.replicas, c.deploy) for c in theirs]
        renamed = dict(zip(sparse["shapes"], TRAFFIC["shapes"]))
        assert [c.shape for c in mine] == [renamed[c.shape] for c in theirs]


def lists_as_the_twin_does(bench: dict) -> None:
    """The cell is on each list its twin is on and on no other, but for
    its own four (on, the twin off) and the twin's own two (off); where
    on a list the two stand, and what other cells a list holds, is free.
    It reports ``assign_p50_ms``."""
    for m in bench["per_layer"]:
        name, listed = m["name"], m["workloads"]
        if name in NEW:
            assert CELL in listed and TWIN not in listed, name
        elif name in PINNED:
            assert CELL not in listed, name
        else:
            assert (CELL in listed) == (TWIN in listed), name
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["assign_p50_ms"]["workloads"]


@pytest.mark.parametrize("bench", one_more.TREES, indirect=True)
def test_the_cell_lists_what_its_twin_lists_and_its_own_four(bench):
    lists_as_the_twin_does(bench)
    files = contract.readers.load_layer_metrics()
    for name, unit in NEW.items():
        spec = files[name]
        assert spec["unit"] == unit and spec["moves"] == "decisions_per_s"
        assert spec["reader"]["kind"] == "counter"
        assert spec["reader"]["num"]["source"] == "planner.stats"
    assert files["leaf_cols_hit_pct"]["layer"] \
        == files["tree_cols_hit_pct"]["layer"]
    assert files["fused_wide_run_ms"]["layer"] \
        == files["wide_tree_group_ms"]["layer"]


def _spoiled(name, drop=(), add=()):
    bench = copy.deepcopy(BENCH)
    entry = {m["name"]: m for m in bench["end_to_end"]
             + bench["per_layer"]}[name]
    for cell in drop:
        entry["workloads"].remove(cell)
    entry["workloads"].extend(add)
    return bench


@pytest.mark.parametrize("spoiled", [
    _spoiled("tick_ms", drop=[TWIN]),
    _spoiled("h2d_mb_per_tick", drop=[CELL]),
    _spoiled("pref_groups_pct", drop=[CELL]),
    _spoiled("pref_groups_pct", add=[TWIN]),
    _spoiled("wide_tree_group_ms", add=[CELL]),
    _spoiled("host_route_ms", add=[CELL]),
    _spoiled("assign_p50_ms", drop=[CELL])],
    ids=["prefs_without_sparse", "h2d_without_prefs", "own_without_it",
         "own_with_the_twin", "the_twin_s_own", "neither_s",
         "no_assign_p50"])
def test_a_list_the_twin_rule_does_not_hold_is_caught(spoiled):
    lists_as_the_twin_does(BENCH)
    with pytest.raises(AssertionError):
        lists_as_the_twin_does(spoiled)


def test_the_enumeration_at_the_real_size_names_the_flat_leaf_and_the_runs():
    _stacks, labels, nb = contract.warmup_enumerates(ENTRY)
    assert nb == 131072 and labels == LABELS
    assert len({n["labels"]["rack"] for n in NODES}) == 1000
    assert {FLAT, TREE, "nb131072_cc1_p1_L1_h0_st1",
            "stream_nb131072_d16", "stream_nb131072_d256",
            "stream_nb131072_d4096"} | FUSED == set(labels)
    # no group of the cell rides a flat spread launch without a
    # preference, and no fused run is narrower than its widest group
    assert "nb131072_cc1_p1_L1_h0" not in labels
    assert not any("_L1_s" in label for label in labels)
    # the cycle keeps the one shape that cannot fuse, which bounds a run
    # at three groups: what the warm-up drives
    assert ["topology"] in STACKS and ["rack-spread"] in STACKS
    assert max(map(len, STACKS)) == 3
    assert not warmup.fusable(CONFIG["shapes"]["topology"])


@pytest.mark.parametrize("label", LABELS)
def test_bytes_of_label_knows_every_label_the_cell_dispatches(label):
    family = kernel_bytes.family_of_label(label)
    assert family in kernel_bytes.FAMILY_MODULE
    moved = kernel_bytes.bytes_of_label(label)
    assert isinstance(moved, int) and moved > 0
    if family != "scatter":
        assert moved > 131072 * 4 * 2
    if label == FLAT:
        # the leaf operand goes up whatever ``L`` is: the flat label
        # moves what the no-preference one does
        assert moved == kernel_bytes.bytes_of_label(
            "nb131072_cc1_p1_L1_h0")
    if label in FUSED:
        assert moved == kernel_bytes.bytes_of_label(
            label.replace("_L4096_", "_L1_"))


def test_the_cut_keeps_more_than_256_racks():
    cut = load_shrinks(REPO)[CELL]
    contract.cut_to_a_test_s_size({CELL: cut}, ENTRY)
    assert cut == load_shrinks(REPO)[TWIN]
    c = dict(CONFIG["cluster"], **cut["cluster"])
    racks = c["zones"] * c["racks_per_zone"]
    assert 256 < racks <= c["nodes"] // 4
    nodes = cluster.plain_nodes(c, seed=3)
    shapes = CONFIG["shapes"]
    assert warmup.group_label(shapes["rack-spread"], nodes) \
        == warmup.group_label(shapes["rack-constrained"], nodes) \
        == "nb2048_cc1_p1_L4096_h0"
    assert all("_L4096_" in label for label in warmup.fused_labels(
        [shapes[n] for n in ("rack-spread", "rack-constrained", "binpack")],
        nodes))


@pytest.fixture(scope="module")
def rehearsed():
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_cells.py"),
         f"{CELL}:traced", f"{CELL}:plain"],
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    runs = json.loads(done.stdout.strip().splitlines()[-1])
    return {k: tuple(v) for k, v in runs.items()}, done.stderr


def test_the_rehearsal_rides_l4096_and_is_correct(rehearsed):
    runs, log = rehearsed
    for how in ("traced", "plain"):
        code, line = runs[f"{CELL}:{how}"]
        assert code == 0 and line["correct"] is True, line["compared"]
        assert line["failed"] == 0 and line["attempted"] > 0
        for number, limit in line["compared"].values():
            assert number <= limit
        assert line["compared"]["retreats"] == [0, 0]
        assert line["compared"]["window_compiles"] == [0, 0]
        # no group rides the host here, so the racks are held to 1
        assert line["compared"]["topology_skew"][0] <= 1
        assert line["compared"]["topology_leaf_skew"][0] <= 1
    warmed = [ln for ln in log.splitlines()
              if ln.startswith("setup signatures warmed=")]
    assert warmed and all(
        "_L4096_h0" in ln and "fused_g2_nb2048_cc1_p1_L4096_s4_mx1" in ln
        for ln in warmed)
    assert "signatures the warm-up did not reach" not in log
    _code, plain = runs[f"{CELL}:plain"]
    assert set(plain["metrics"]) == {"decisions_per_s", "assign_p50_ms",
                                     "setup_s"}


def test_the_traced_line_holds_every_listed_metric_a_cpu_run_can_read(
        rehearsed):
    runs, _log = rehearsed
    _code, line = runs[f"{CELL}:traced"]
    metrics = line["metrics"]
    assert LISTED - DEVICE_TRACE - {"lock_wait_ms"} <= set(metrics) \
        <= LISTED
    assert set(NEW) <= LISTED and not DEVICE_TRACE & set(metrics)
    assert not PINNED & set(metrics)
    assert metrics["device_route_pct"]["value"] == 100.0
    assert metrics["host_route_groups_pct"]["value"] == 0.0
    assert metrics["window_compiles"]["value"] == 0.0
    # half the services carry the preference; every fused run holds one
    assert 25.0 < metrics["pref_groups_pct"]["value"] < 75.0
    assert metrics["pref_groups_pct"]["value"] \
        <= metrics["fused_wide_groups_pct"]["value"] + 50.0
    assert 0 < metrics["fused_wide_groups_pct"]["value"] <= 75.0
    assert metrics["fused_wide_run_ms"]["value"] > 0
    assert metrics["leaf_cols_hit_pct"]["value"] == 100.0
    assert metrics["tree_cols_hit_pct"]["value"] == 100.0
    for name, unit in NEW.items():
        assert metrics[name]["unit"] == unit


def _racks_ignored(nodes: list, services: list) -> list:
    """The control: every service placed by the reference with its
    preferences taken away, so its tasks are levelled over the nodes and
    the racks are left to chance."""
    blind = [dict(s, shape=dict(s["shape"], spread_over=[]))
             for s in services]
    return reference.place(nodes, blind)


def test_a_placement_that_ignores_the_racks_is_caught_at_one_and_not_at_75():
    """The reference's ``topology_skew`` limit is 75, set for host-routed
    partial groups, of which this cell has none.  A service of fewer
    tasks than racks, levelled over nodes only, lands two tasks in some
    rack while others stay empty: inside the limit, outside the
    guarantee.  Held to 1, as the cell's own runs read, it is caught."""
    cut = load_shrinks(REPO)[CELL]["cluster"]
    nodes = [dict(n, agent=False) for n in cluster.plain_nodes(
        dict(CONFIG["cluster"], **cut), seed=2 ** 31 + 36)]
    calls = traffic.open_loop_schedule(
        dict(TRAFFIC, tasks_per_s=150), 4, seed=2 ** 31 + 36)
    services = [{"id": c.name, "shape": CONFIG["shapes"][c.shape],
                 "replicas": c.replicas, "read_back": True}
                for c in calls]
    assert any(s["shape"]["spread_over"] == ["node.labels.rack"]
               and s["replicas"] > 20 for s in services)
    sound = reference.compare(nodes, services,
                              reference.place(nodes, services))
    assert sound["correct"], sound["numbers"]
    assert sound["numbers"]["topology_skew"] == 1
    assert sound["numbers"]["topology_leaf_skew"] == 1
    blind = reference.compare(nodes, services,
                              _racks_ignored(nodes, services))
    skew = blind["numbers"]["topology_skew"]
    # the reference's own limits let it pass ...
    assert 1 < skew <= reference.LIMITS["topology_skew"]
    assert blind["correct"]
    # ... and the limit this cell's runs keep does not
    held = dict(reference.LIMITS, topology_skew=1)
    assert not all(blind["numbers"][k] <= held[k] for k in held)
    assert all(sound["numbers"][k] <= held[k] for k in held)
