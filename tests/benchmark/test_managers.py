"""The managers a configuration asks for (``benchmark/managers.py``, PR 40):
the ``manager`` block read or refused by name, the raft members' delayed
links, the read-back from every member's store in ``reference.compare``,
the raft members' counters in ``harness.counter_tables``, and on the
forced CPU at a test's size, in ``rehearse_cells.py``'s process: every
cell of the one-more tree (the repo's, the added one and its three-manager
twin) served by the managers its own configuration asks for, so that a
cell of one manager is pinned to the standalone manager it always was and
a cell of three to raft members, the twin run whole and correct, and its
control, a follower that drops its task writes, not correct on the new
number alone."""

import copy
import json
import os
import re
import subprocess
import sys
import threading
import time
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(1, HERE)

from benchmark import cluster, harness, managers, reference  # noqa: E402
from benchmark.control import Rehearsal  # noqa: E402
import contract  # noqa: E402
import one_more  # noqa: E402

#: the cells of the one-more tree: the repo's, then the two it adds
SERVED = [w["name"] for w in harness.load_benchmark()["workloads"]] \
    + [one_more.CELL, one_more.CELL3]

# ------------------------------------------------------ the manager block

@pytest.mark.parametrize("bench", one_more.TREES, indirect=True)
def test_every_configuration_asks_for_the_one_manager_it_runs(bench):
    """Every configuration's ``manager`` block is one ``managers.plan``
    honours, and plans the managers it states: exactly the one standalone
    manager where it says 1, raft members with their settings where it
    says more."""
    for entry in bench["configs"]:
        block = cluster.load_config(entry["name"])["manager"]
        planned = managers.plan(block)
        assert planned["managers"] == block["managers"]
        if block["managers"] == 1:
            assert planned == {"managers": 1}
        else:
            assert set(planned) == {"managers", *managers.RAFT_KEYS}


@pytest.mark.parametrize("block,plan", [
    ({"managers": 3}, {"managers": 3, "wal_fsync": False,
                       "raft_link_delay_ms": 0.0,
                       "raft_snapshot_interval": None}),
    ({"managers": 5, "wal_fsync": True, "raft_link_delay_ms": 0.5,
      "raft_snapshot_interval": 10000},
     {"managers": 5, "wal_fsync": True, "raft_link_delay_ms": 0.5,
      "raft_snapshot_interval": 10000}),
    (one_more.MANAGERS3, {"managers": 3, "wal_fsync": True,
                          "raft_link_delay_ms": 1.0,
                          "raft_snapshot_interval": None}),
], ids=["three", "five_as_upstream_defaults", "the_twin"])
def test_raft_members_and_their_settings(block, plan):
    assert managers.plan(block) == plan


#: (the manager block's changes to cell 1's, the key the refusal names)
REFUSED = {
    "two": ({"managers": 2}, "manager.managers"),
    "none": ({"managers": 0}, "manager.managers"),
    "four": ({"managers": 4}, "manager.managers"),
    "a_word": ({"managers": "3"}, "manager.managers"),
    "true": ({"managers": True}, "manager.managers"),
    "delay_under_one": ({"raft_link_delay_ms": 1}, "manager.raft_link_delay_ms"),
    "fsync_under_one": ({"wal_fsync": False}, "manager.wal_fsync"),
    "fsync_a_word": ({"managers": 3, "wal_fsync": "yes"}, "manager.wal_fsync"),
    "delay_below_0": ({"managers": 3, "raft_link_delay_ms": -1},
                      "manager.raft_link_delay_ms"),
    "unknown_key": ({"replicas": 3}, "manager.replicas"),
    "snapshots_under_one": ({"raft_snapshot_interval": 10000},
                            "manager.raft_snapshot_interval"),
    "snapshots_at_0": ({"managers": 3, "raft_snapshot_interval": 0},
                       "manager.raft_snapshot_interval"),
}


@pytest.fixture(scope="module")
def refused_tree(tmp_path_factory):
    """The repo's benchmark with one configuration a case, cell 1's but
    for the manager block."""
    tree = str(tmp_path_factory.mktemp("refused"))
    one_more.build(REPO, tree)
    contract.point_at(tree)
    try:
        for case, (changes, _) in REFUSED.items():
            config = cluster.load_config("swarm-10k")
            config["manager"].update(changes)
            config["name"] = f"refused-{case}"
            with open(os.path.join(tree, "benchmark", "configs",
                                   f"refused-{case}.json"), "w") as f:
                json.dump(config, f)
    finally:
        contract.point_at(REPO)
    return tree


@pytest.mark.parametrize("case", list(REFUSED))
def test_a_block_the_harness_cannot_honour_stops_the_run_by_name(
        refused_tree, case, capsys):
    """Exit 2 and no result before anything starts: not even the look
    for a device, let alone a manager or the window."""
    cell = {"name": f"refused-{case}.deploys", "config": f"refused-{case}",
            "traffic": "deploys", "chips": 1, "why": "a refusal"}
    contract.point_at(refused_tree)
    try:
        code, line = harness.run_cell(
            cell["name"], seed=1, seconds=1, trace=False,
            t_start=time.perf_counter(),
            rehearsal=Rehearsal(require_tpu=False, cell=cell))
    finally:
        contract.point_at(REPO)
    out = capsys.readouterr()
    assert (code, line) == (2, None)
    assert REFUSED[case][1] in out.err
    assert "device platform" not in out.out


# ------------------------------------------------------- the delayed links

class _Recorder:
    def __init__(self):
        self.got = []
        self.lock = threading.Lock()

    def register(self, node_id, handler):
        pass

    def unregister(self, node_id):
        pass

    def send(self, msg):
        with self.lock:
            self.got.append((time.perf_counter(), msg))


def test_delayed_links_deliver_each_message_late_and_in_order():
    inner = _Recorder()
    links = managers.DelayedLinks(inner, 0.02)
    sent = {}
    try:
        for i in range(60):
            msg = (("a", "b") if i % 3 else ("b", "a"), i)
            sent[msg] = time.perf_counter()
            links.send(msg)
        deadline = time.perf_counter() + 5
        while len(inner.got) < 60 and time.perf_counter() < deadline:
            time.sleep(0.01)
    finally:
        links.stop()
    assert len(inner.got) == 60
    for at, msg in inner.got:
        assert at - sent[msg] >= 0.02
    for link in (("a", "b"), ("b", "a")):
        order = [i for _, (ln, i) in inner.got if ln == link]
        assert order == sorted(order)


# ------------------------------------------------- the member read-back

NODES = [{"id": f"n{i}", "hostname": f"n{i}", "labels": {}, "os": "linux",
          "arch": "amd64", "nano_cpus": 4, "memory_bytes": 4, "ready": True,
          "agent": False} for i in range(4)]
SHAPE = {"strategy": "spread", "constraints": [], "platforms": [],
         "spread_over": [], "nano_cpus": 1, "memory_bytes": 1}
SERVICES = [{"id": "s1", "shape": SHAPE, "replicas": 4, "read_back": True}]
TASKS = [{"id": f"s1.{i}", "service_id": "s1", "node_id": f"n{i}",
          "state": "assigned"} for i in range(4)]


def _members(spoil=None, elections=0):
    rows = {m: {"services": ["s1"], "tasks": [dict(t) for t in TASKS]}
            for m in ("m0", "m1", "m2")}
    if spoil is not None:
        spoil(rows["m2"])
    return {"rows": rows, "leader": "m0", "elections": elections}


def test_one_manager_compares_the_numbers_it_always_did():
    result = reference.compare(NODES, SERVICES, TASKS)
    assert result["correct"] and result["limits"] == reference.LIMITS
    assert list(reference.compared_line(result)) == list(reference.LIMITS)


def test_members_that_hold_every_acknowledged_write_are_correct():
    result = reference.compare(NODES, SERVICES, TASKS, members=_members())
    assert result["correct"]
    line = reference.compared_line(result)
    assert list(line) == list(reference.LIMITS) \
        + list(reference.MEMBER_LIMITS)
    assert line["member_read_back_missing"] == [0, 0]
    assert line["leader_changes"] == [0, 0]


def _lose_service(rows):
    rows["services"].clear()


def _lose_task(rows):
    rows["tasks"].pop()


def _move_task(rows):
    rows["tasks"][0]["node_id"] = "n3"


def _other_state(rows):
    rows["tasks"][1]["state"] = "running"


def _extra_task(rows):
    rows["tasks"].append(dict(TASKS[0], id="s1.9"))


def _lose_everything(rows):
    rows["services"].clear()
    rows["tasks"].clear()


@pytest.mark.parametrize("spoil,number", [
    (_lose_service, 1), (_lose_task, 1), (_move_task, 1), (_other_state, 1),
    (_extra_task, 1), (_lose_everything, 5)],
    ids=["service", "task", "node", "state", "extra", "never_caught_up"])
def test_a_member_that_differs_from_the_leader_is_not_correct(spoil, number):
    result = reference.compare(NODES, SERVICES, TASKS,
                               members=_members(spoil))
    assert not result["correct"]
    assert result["numbers"]["member_read_back_missing"] == number
    over = {k for k, (n, lim) in reference.compared_line(result).items()
            if n > lim}
    assert over == {"member_read_back_missing"}


def test_members_are_held_to_the_leaders_store_not_the_earlier_read_back():
    """A task that moved on between the control API's read-back and the
    stop of the writers differs from that read-back on every member
    alike: the members agree with the leader's store read with theirs."""
    members = _members()
    for held in members["rows"].values():
        held["tasks"][2]["state"] = "running"
    result = reference.compare(NODES, SERVICES, TASKS, members=members)
    assert result["numbers"]["member_read_back_missing"] == 0
    _other_state(members["rows"]["m0"])
    result = reference.compare(NODES, SERVICES, TASKS, members=members)
    assert result["numbers"]["member_read_back_missing"] == 2


class _Core:
    def __init__(self, commit, applied):
        self.commit_index, self.applied_index = commit, applied


class _Node:
    def __init__(self, commit, applied):
        self.core = _Core(commit, applied)


def _settling(*cores):
    members = managers.Members.__new__(managers.Members)
    members.nodes = [_Node(*c) for c in cores]
    return members


def test_settle_waits_for_the_highest_commit_index_any_member_knows():
    """The member once driven may have been deposed: its commit index is
    stale, and the wait is for the highest any member knows, with the
    whole timeout counted from the call."""
    members = _settling((10, 10), (14, 12), (14, 14))

    def catch_up():
        time.sleep(0.2)
        for node in members.nodes:
            node.core.commit_index = node.core.applied_index = 14
    threading.Thread(target=catch_up).start()
    t = time.perf_counter()
    assert members.settle(5.0) == 14
    assert 0.15 < time.perf_counter() - t < 4.0
    assert all(n.core.applied_index == 14 for n in members.nodes)


def test_settle_gives_up_after_its_timeout_with_a_member_behind():
    members = _settling((14, 14), (14, 9), (14, 14))
    t = time.perf_counter()
    assert members.settle(0.1) == 14
    assert 0.1 <= time.perf_counter() - t < 2.0
    assert members.nodes[1].core.applied_index == 9


def test_a_leader_change_is_not_correct():
    result = reference.compare(NODES, SERVICES, TASKS,
                               members=_members(elections=1))
    assert not result["correct"]
    assert result["numbers"]["leader_changes"] == 1


# ------------------------------------------------------------ whole runs

def _block(bench: dict, tree: str, cell: str) -> dict:
    """The ``manager`` block of the cell's configuration in ``tree``."""
    config = {w["name"]: w for w in bench["workloads"]}[cell]["config"]
    with open(os.path.join(tree, "benchmark", "configs",
                           f"{config}.json")) as f:
        return json.load(f)["manager"]


@pytest.fixture(scope="module")
def runs(one_more_tree):
    """In one process of ``rehearse_cells.py`` on the one-more tree (the
    repo's data files byte for byte, and the two cells it adds):
    ``Served`` of each of its cells at its cut, the twin's plain run and
    its run under the follower fault.  ({name: (code, line)}, the
    process's stderr)."""
    tree, _ = one_more_tree
    names = [f"served:{cell}" for cell in SERVED] + [
        f"{one_more.CELL3}:plain", f"{one_more.CELL3}:follower_skips_tasks"]
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_cells.py"),
         "--root", tree, *names],
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    out = {k: tuple(v) for k, v in
           json.loads(done.stdout.strip().splitlines()[-1]).items()}
    return out, done.stderr


def served_as_configured(made: dict, block: dict) -> None:
    """What ``rehearse_cells.served`` says a cell's ``Served`` was made
    of, against its configuration's ``manager`` block.  ``managers`` 1:
    the standalone ``Manager()``, with no raft node and a store
    with no proposer.  3 or more: that many raft members, the driven
    manager the leader, its store proposing through its raft node."""
    assert made["managers"] == block["managers"]
    assert made["leader"] is True
    assert made["heartbeat_period"] == block["heartbeat_period_s"]
    one = block["managers"] == 1
    assert made["members"] is not one and made["raft"] is not one
    assert made["proposer"] is not one


@pytest.mark.parametrize("cell", SERVED)
def test_the_four_cells_serve_from_one_standalone_manager(runs, cell,
                                                          one_more_tree):
    """Each cell of the tree served as its own configuration says: the
    repo's four and the added cell from the one standalone manager, the
    twin from three raft members."""
    tree, bench = one_more_tree
    code, made = runs[0][f"served:{cell}"]
    assert code == 0
    served_as_configured(made, _block(bench, tree, cell))


#: (the cell whose configuration is held, what is spoiled in cell 1's or
#: the twin's ``Served`` as the rehearsal reported it)
SPOILED = {
    "one_with_members": ("swarm-10k.deploys", dict(
        members=True, managers=3, raft=True, proposer=True)),
    "one_with_a_raft_node": ("swarm-10k.deploys", dict(raft=True)),
    "one_with_a_proposer": ("swarm-10k.deploys", dict(proposer=True)),
    "three_standalone": (one_more.CELL3, dict(
        members=False, managers=1, raft=False, proposer=False)),
    "three_driving_a_follower": (one_more.CELL3, dict(leader=False)),
    "three_with_five": (one_more.CELL3, dict(managers=5)),
}


@pytest.mark.parametrize("case", list(SPOILED))
def test_a_served_that_is_not_its_configuration_s_fails_the_pin(
        runs, one_more_tree, case):
    tree, bench = one_more_tree
    cell, spoil = SPOILED[case]
    made = copy.deepcopy(runs[0][f"served:{cell}"][1])
    block = _block(bench, tree, cell)
    served_as_configured(made, block)
    made.update(spoil)
    with pytest.raises(AssertionError):
        served_as_configured(made, block)


def test_the_three_manager_twin_runs_whole_and_every_member_holds_it(runs):
    code, line = runs[0][f"{one_more.CELL3}:plain"]
    assert code == 0
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["metrics"]) == {"decisions_per_s", "assign_p50_ms",
                                    "setup_s"}
    compared = line["compared"]
    assert list(compared) == list(reference.LIMITS) \
        + list(reference.MEMBER_LIMITS) + ["window_compiles", "failed"]
    assert compared["member_read_back_missing"] == [0, 0]
    assert compared["leader_changes"] == [0, 0]
    err = runs[1]
    assert "setup managers=3" in err and "wal_fsync=True" in err \
        and "raft_link_delay_ms=1.0" in err
    held = re.findall(r"compared note: member (manager-\d): (\d+) of (\d+) "
                      r"services, (\d+) of (\d+) tasks", err)
    # the first three notes are the plain run's: every member, whole
    assert [m for m, *_ in held[:3]] == ["manager-0", "manager-1",
                                         "manager-2"]
    for _, svc, svcs, tasks, want in held[:3]:
        assert svc == svcs and tasks == want and int(want) > 0


def test_a_follower_that_drops_its_task_writes_fails_on_that_number_alone(
        runs):
    code, line = runs[0][f"{one_more.CELL3}:follower_skips_tasks"]
    assert code == 0 and line["correct"] is False
    over = {k for k, (n, lim) in line["compared"].items() if n > lim}
    assert over == {"member_read_back_missing"}


def test_the_runs_leave_no_raft_log_behind(runs, one_more_tree):
    tree, bench = one_more_tree
    wal_dirs = re.findall(r"wal_dir=(\S+)", runs[1])
    # each ``Served`` of raft members, and the twin's two runs
    assert len(wal_dirs) == 2 + sum(
        _block(bench, tree, cell)["managers"] > 1 for cell in SERVED)
    tree = os.path.realpath(tree)
    for path in wal_dirs:
        assert not os.path.exists(path)
        assert not os.path.realpath(path).startswith(tree)
        assert not os.path.realpath(path).startswith(REPO)
    for root, _, files in os.walk(tree):
        assert "wal.jsonl" not in files, root


# --------------------------------------------------- the raft counters

def _served(members=None):
    return types.SimpleNamespace(
        planner=types.SimpleNamespace(stats={
            "groups_planned": 3, "h2d_bytes": 1024, "breaker": "closed",
            "last_error": None, "groups_fused": 0}),
        scheduler=types.SimpleNamespace(stats={
            "ticks": 2, "events_handled": 7, "mode": "streaming"}),
        members=members)


@pytest.fixture
def ledger(monkeypatch):
    from swarmkit_tpu.obs import devicetelemetry
    monkeypatch.setattr(devicetelemetry, "compile_cache_snapshot", lambda: {
        "nb1024_a": {"compiles": 1, "hits": 4, "misses": 1},
        "stream_b": {"compiles": 0, "hits": 2, "misses": 0}})


#: what the parent of the raft sources gave for ``_served()``, key for key
#: and in its order, and the line it printed
ONE_MANAGER = {
    "planner.stats": {"groups_planned": 3, "h2d_bytes": 1024,
                      "groups_fused": 0},
    "scheduler.stats": {"ticks": 2, "events_handled": 7},
    "compile_ledger": {"compiles": 1, "dispatches": 7},
    "compiled": {"nb1024_a": 1, "stream_b": 0}}
ONE_MANAGER_LINE = (
    'window counters {"planner.stats": {"groups_planned": 3, '
    '"h2d_bytes": 1024}, "scheduler.stats": {"ticks": 2, '
    '"events_handled": 7}, "compile_ledger": {"compiles": 1, '
    '"dispatches": 7}}')


def test_one_manager_s_counter_tables_and_line_are_as_before(ledger):
    tables = harness.counter_tables(_served())
    assert tables == ONE_MANAGER and list(tables) == list(ONE_MANAGER)
    for src, table in tables.items():
        assert list(table) == list(ONE_MANAGER[src])
    assert harness.counters_line(tables) == ONE_MANAGER_LINE


def _member(applied, snapshots):
    return types.SimpleNamespace(stats={
        "applied": applied, "snapshots": snapshots,
        "stale_epoch_rejects": 0, "state": "follower"})


def test_raft_members_add_the_driven_member_and_the_followers_summed(
        ledger):
    members = types.SimpleNamespace(
        nodes=[_member(40, 1), _member(50, 2), _member(38, 0)], leader=1)
    tables = harness.counter_tables(_served(members))
    assert list(tables) == list(ONE_MANAGER) + ["raft.leader",
                                                "raft.followers"]
    assert {k: tables[k] for k in ONE_MANAGER} == ONE_MANAGER
    assert tables["raft.leader"] == {"applied": 50, "snapshots": 2,
                                     "stale_epoch_rejects": 0}
    assert tables["raft.followers"] == {"applied": 78, "snapshots": 1,
                                        "stale_epoch_rejects": 0}
    grown = harness.growth(harness.counter_tables(_served(members)), tables)
    assert grown["raft.leader"]["applied"] == 0
    line = harness.counters_line(tables)
    assert line.startswith(ONE_MANAGER_LINE[:-1] + ", ")
    assert line.endswith(
        '"raft.leader": {"applied": 50, "snapshots": 2}, '
        '"raft.followers": {"applied": 78, "snapshots": 1}}')
