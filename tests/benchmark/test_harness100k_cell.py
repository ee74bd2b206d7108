"""``harness-100k.sparse``, the cell of 100,000 nodes, as the benchmark's
data states it and as a CPU rehearsal runs it.

At its real size (131,072 rows, 1,000 racks) the warm-up's enumeration is
checked against ``kernel_bytes.bytes_of_label`` label by label: the
roofline of the family that takes most device time needs the bytes of
every signature the cell dispatches, the wide tree's
``nb131072_cc1_p1_L4096_h2`` first.  At the size its own cut gives
(``shrink/harness-100k.sparse.json``: more than 256 racks, so the
rehearsal too drives the two-level label at the 4,096 leaf bucket and the
searches' scatter form) one plain and one traced run go through
``harness.run_cell`` in ``rehearse_cells.py``'s process."""

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

from benchmark import cluster, harness, kernel_bytes, traffic, warmup  # noqa: E402
import contract  # noqa: E402
import one_more  # noqa: E402
from rehearse_cells import load_shrinks  # noqa: E402

CELL = "harness-100k.sparse"
BENCH = harness.load_benchmark()
ENTRY = {w["name"]: w for w in BENCH["workloads"]}[CELL]
CONFIG = cluster.load_config(ENTRY["config"])
TRAFFIC = traffic.load(ENTRY["traffic"])
NODES = cluster.plain_nodes(CONFIG["cluster"], seed=3)
STACKS, LABELS = warmup.plan(CONFIG, TRAFFIC, NODES)
TREE = "nb131072_cc1_p1_L4096_h2"
#: what a CPU run has no device trace for
DEVICE_TRACE = {m["name"] for m in BENCH["per_layer"]
                if m["source"] == "device_trace"}
LISTED = {m["name"] for m in BENCH["per_layer"] if CELL in m["workloads"]}
NEW = {"h2d_mb_per_tick", "d2h_mb_per_tick", "wide_tree_group_ms",
       "wide_tree_groups_pct"}


def test_the_cell_is_upstream_s_cluster_under_cell_1_s_mix():
    c = CONFIG["cluster"]
    assert (c["nodes"], c["zones"], c["racks_per_zone"], c["agents"]) \
        == (100000, 4, 250, 64)
    assert CONFIG["reduced"] == ["tasks"] and ENTRY["chips"] == 1
    ten = cluster.load_config("swarm-10k")
    assert CONFIG["shapes"] == ten["shapes"]
    assert CONFIG["manager"] == ten["manager"]
    assert CONFIG["guarantees"] == ten["guarantees"]
    deploys = traffic.load("deploys")
    own = {"tasks_per_s", "sustained_tasks_per_s", "rate_note"}
    assert set(TRAFFIC) == set(deploys)
    for key in set(deploys) - own:
        assert TRAFFIC[key] == deploys[key], key
    # four fifths of the sustained rate, rounded down to a multiple of
    # 50, or the fallbacks ISSUE 34 allows (three fifths, a half)
    rate, sustained = TRAFFIC["tasks_per_s"], TRAFFIC["sustained_tasks_per_s"]
    assert rate % 50 == 0 and rate in {
        int(sustained * share) // 50 * 50 for share in (0.8, 0.6, 0.5)}


#: the ladder's other two cells
OTHERS = {"swarm-10k.deploys", "swarm-1k.deploys-1k"}


def lists_as_the_ladder_does(bench: dict) -> None:
    """The cell is on each list both other cells of the ladder are on,
    and on cell 1's route shares and the router's two sides; the
    transfer counters list the ladder's three cells, and the wide
    tree's two list this cell and neither of the others.  What other
    cells a list holds, and where, is free."""
    lists = {m["name"]: set(m["workloads"]) for m in bench["per_layer"]}
    for name, listed in lists.items():
        if name in NEW:
            continue
        assert (CELL in listed) == (OTHERS <= listed or name in (
            "device_route_pct", "host_route_groups_pct",
            "route_host_est_ms", "route_device_est_ms")), name
    for name in ("h2d_mb_per_tick", "d2h_mb_per_tick"):
        assert lists[name] >= OTHERS | {CELL}, name
    for name in ("wide_tree_group_ms", "wide_tree_groups_pct"):
        assert CELL in lists[name] and not OTHERS & lists[name], name
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["assign_p50_ms"]["workloads"]


@pytest.mark.parametrize("bench", one_more.TREES, indirect=True)
def test_the_cell_lists_what_both_cells_list_and_the_router_s_readings(
        bench):
    """Beside what both cells list: cell 1's route shares (far above the
    crossover) and the break-even's two sides as the router saw them
    (every group launched on its own passes ``plan.route``); not
    ``host_route_ms`` / ``route_switches_per_tick``, which read 0 where
    no group rides the host."""
    lists_as_the_ladder_does(bench)


def _spoiled(name, drop=(), add=()):
    bench = copy.deepcopy(BENCH)
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    for cell in drop:
        entry["workloads"].remove(cell)
    entry["workloads"].extend(add)
    return bench


@pytest.mark.parametrize("spoiled", [
    _spoiled("h2d_mb_per_tick", drop=["swarm-10k.deploys"]),
    _spoiled("d2h_mb_per_tick", drop=[CELL]),
    _spoiled("wide_tree_group_ms", add=["swarm-10k.deploys"]),
    _spoiled("wide_tree_groups_pct", drop=[CELL]),
    _spoiled("tick_ms", drop=[CELL]),
    _spoiled("host_route_ms", add=[CELL])],
    ids=["h2d_without_cell_1", "d2h_without_sparse", "wide_with_cell_1",
         "wide_without_sparse", "tick_without_sparse", "host_route_ms"])
def test_a_list_the_ladder_rule_does_not_hold_is_caught(spoiled):
    lists_as_the_ladder_does(BENCH)
    with pytest.raises(AssertionError):
        lists_as_the_ladder_does(spoiled)


def test_the_enumeration_at_the_real_size_names_the_wide_tree():
    _stacks, labels, nb = contract.warmup_enumerates(ENTRY)
    assert nb == 131072 and labels == LABELS
    assert TREE in labels and ["topology"] in STACKS
    assert len({n["labels"]["rack"] for n in NODES}) == 1000
    assert {"stream_nb131072_d16", "stream_nb131072_d256",
            "stream_nb131072_d4096", "nb131072_cc1_p1_L1_h0",
            "nb131072_cc1_p1_L1_h0_st1"} <= set(labels)
    assert sum(label.startswith("fused_") for label in labels) == 4


@pytest.mark.parametrize("label", LABELS)
def test_bytes_of_label_knows_every_label_the_cell_dispatches(label):
    family = kernel_bytes.family_of_label(label)
    assert family in kernel_bytes.FAMILY_MODULE
    moved = kernel_bytes.bytes_of_label(label)
    assert isinstance(moved, int) and moved > 0
    if family != "scatter":
        # at the least the node columns of 131,072 rows go up and the
        # counts come back
        assert moved > 131072 * 4 * 2
    if label == TREE:
        flat = kernel_bytes.bytes_of_label("nb131072_cc1_p1_L1_h0")
        # one level above the leaves: its segment ids and parents, and
        # the leaves' parents
        assert moved - flat == 131072 * 4 + 256 * 4 + 4096 * 4


def test_the_cut_keeps_more_than_256_racks():
    cut = load_shrinks(REPO)[CELL]
    contract.cut_to_a_test_s_size({CELL: cut}, ENTRY)
    c = dict(CONFIG["cluster"], **cut["cluster"])
    racks = c["zones"] * c["racks_per_zone"]
    assert 256 < racks <= c["nodes"] // 4
    nodes = cluster.plain_nodes(c, seed=3)
    assert warmup.group_label(CONFIG["shapes"]["topology"], nodes) \
        .endswith("_L4096_h2")
    # the other two cells' cuts drive the mask form
    for other in ("swarm-10k.deploys", "swarm-1k.deploys-1k"):
        oc = load_shrinks(REPO)[other]["cluster"]
        assert 4 * oc["racks_per_zone"] <= 256


@pytest.fixture(scope="module")
def rehearsed():
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_cells.py"),
         f"{CELL}:traced", f"{CELL}:plain"],
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    runs = json.loads(done.stdout.strip().splitlines()[-1])
    return {k: tuple(v) for k, v in runs.items()}, done.stderr


def test_the_rehearsal_drives_the_wide_tree_and_is_correct(rehearsed):
    runs, log = rehearsed
    for how in ("traced", "plain"):
        code, line = runs[f"{CELL}:{how}"]
        assert code == 0 and line["correct"] is True, line["compared"]
        assert line["failed"] == 0 and line["attempted"] > 0
        for number, limit in line["compared"].values():
            assert number <= limit
        assert line["compared"]["retreats"] == [0, 0]
        assert line["compared"]["window_compiles"] == [0, 0]
    warmed = [ln for ln in log.splitlines()
              if ln.startswith("setup signatures warmed=")]
    assert warmed and all("_L4096_h2" in ln for ln in warmed)
    assert "signatures the warm-up did not reach" not in log
    _code, plain = runs[f"{CELL}:plain"]
    assert set(plain["metrics"]) == {"decisions_per_s", "assign_p50_ms",
                                     "setup_s"}


def test_the_traced_line_holds_every_listed_metric_a_cpu_run_can_read(
        rehearsed):
    runs, _log = rehearsed
    _code, line = runs[f"{CELL}:traced"]
    metrics = line["metrics"]
    # ``lock_wait_ms`` only where a writer waited a millisecond or more
    assert LISTED - DEVICE_TRACE - {"lock_wait_ms"} <= set(metrics) \
        <= LISTED
    assert NEW <= LISTED and not DEVICE_TRACE & set(metrics)
    assert metrics["device_route_pct"]["value"] == 100.0
    assert metrics["host_route_groups_pct"]["value"] == 0.0
    assert metrics["window_compiles"]["value"] == 0.0
    # a quarter of the services are topology groups, each a wide tree
    assert 0 < metrics["wide_tree_groups_pct"]["value"] <= 50.0
    assert metrics["wide_tree_group_ms"]["value"] > 0
    assert metrics["h2d_mb_per_tick"]["value"] \
        > metrics["d2h_mb_per_tick"]["value"] > 0
    for name in NEW:
        assert metrics[name]["unit"] == {
            "h2d_mb_per_tick": "MB/tick", "d2h_mb_per_tick": "MB/tick",
            "wide_tree_group_ms": "ms", "wide_tree_groups_pct": "%"}[name]
