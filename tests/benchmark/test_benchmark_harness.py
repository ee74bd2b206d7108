"""The benchmark's harness, rehearsed on the forced CPU at a tiny size:
the contract of ``BENCHMARK.json`` and its data files, the generator, the
plain reference and its controls, and whole runs of cell 1 and of the
cell kept as data (``rehearse_cells.KEPT``) through
``harness.run_cell`` (``rehearse_cells.py`` makes them in a process of its
own; the TPU check is switched off there, in the tests, and nowhere in the
command)."""

import collections
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
sys.path.insert(1, os.path.dirname(os.path.abspath(__file__)))

from benchmark import cluster, harness, readers, reference, traffic  # noqa: E402
from benchmark import warmup  # noqa: E402
from rehearse_cells import KEPT  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
BENCH = harness.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]

# ------------------------------------------------------- the data's contract

def test_benchmark_json_has_exactly_the_contract_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmark", "tests/benchmark"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 << 10


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=CELLS)
def test_every_cell_resolves_to_a_configuration_and_a_traffic_file(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    configs = {c["name"]: c for c in BENCH["configs"]}
    entry = configs[cell["config"]]
    assert entry["file"] == f"benchmark/configs/{cell['config']}.json"
    config = cluster.load_config(cell["config"])
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert config["reduced"] == entry["reduced"]
    assert config["chips"] == cell["chips"] == 1
    params = traffic.load(cell["traffic"])
    assert params["generator"] in traffic.GENERATORS
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]


def test_names_and_units_hold_only_the_allowed_characters():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    names += [w["traffic"] for w in BENCH["workloads"]]
    assert len(set(names[:len(BENCH["end_to_end"])
                         + len(BENCH["per_layer"])])) \
        == len(BENCH["end_to_end"]) + len(BENCH["per_layer"])
    for name in names:
        assert NAME.match(name), name
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


LAYER_METRICS = readers.load_layer_metrics()


@pytest.mark.parametrize("name", sorted(LAYER_METRICS))
def test_layer_metric_file_is_sound_and_matches_benchmark_json(name):
    spec = LAYER_METRICS[name]
    assert set(spec) == {"layer", "unit", "better", "moves", "workloads",
                         "reader"}
    assert spec["reader"]["kind"] in readers.KINDS
    end_to_end = {m["name"]: m for m in BENCH["end_to_end"]}
    moved = end_to_end[spec["moves"]]
    reporting = set(moved.get("workloads", CELLS))
    assert spec["workloads"] and set(spec["workloads"]) <= reporting
    entry = {m["name"]: m for m in BENCH["per_layer"]}[name]
    assert entry == {
        "name": name, "unit": spec["unit"], "better": spec["better"],
        "source": readers.SOURCE_OF_KIND[spec["reader"]["kind"]],
        "layer": spec["layer"], "moves": spec["moves"],
        "workloads": spec["workloads"]}
    if name.endswith("_roofline") or "mfu" in name:
        assert spec["unit"] == "%"


def test_every_per_layer_entry_has_its_file():
    assert sorted(m["name"] for m in BENCH["per_layer"]) \
        == sorted(LAYER_METRICS)


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


@pytest.mark.parametrize("cell", BENCH["workloads"] + list(KEPT.values()),
                         ids=CELLS + list(KEPT))
def test_warmup_enumerates_the_signatures_of_a_cell(cell):
    config = cluster.load_config(cell["config"])
    nodes = cluster.plain_nodes(config["cluster"], seed=3)
    stacks, labels = warmup.plan(config, traffic.load(cell["traffic"]),
                                 nodes)
    nb = 16384 if len(nodes) == 10_000 else 131072
    assert f"nb{nb}_cc1_p1_L1_h0" in labels
    assert f"nb{nb}_cc1_p1_L1_h0_st1" in labels
    leaves = 256 if nb == 16384 else 4096
    assert f"nb{nb}_cc1_p1_L{leaves}_h2" in labels
    assert sum(1 for lb in labels if lb.startswith("fused_")) == 4
    assert ["topology"] in stacks and max(map(len, stacks)) == 3


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


def test_one_whole_run_of_cell_1_through_run_cell():
    """``harness.run_cell`` end to end on the forced CPU: warm-up, window,
    drain, read-back, comparison, the contract's result line."""
    code, line = _rehearse("swarm-10k.deploys:plain")["swarm-10k.deploys:plain"]
    assert code == 0
    _check_line(line)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    e2e = {m["name"] for m in BENCH["end_to_end"]
           if "swarm-10k.deploys" in m.get("workloads", CELLS)}
    assert set(line["metrics"]) == e2e
    assert line["compared"]["window_compiles"] == [0, 0]
    assert list(line["compared"])[-1] == "failed"


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
