"""The eight per-layer metrics that read the wait before the tick and the
fused run (ISSUE 38), in the form of ``test_layer_metrics_inside.py``:
each is one new data file under ``benchmark/layer_metrics/`` with one
``per_layer`` entry appended to ``BENCHMARK.json``, uses a reader kind
and a reduce that ``benchmark/readers.py`` already had, lists the four
cells in the ladder's order, and reads a number from the spans of the
small traced deploy through a live ``Manager()``
(``tests/servedpath_deploy.py``, whose last step is a stack of two
services one tick plans as a fused run; made by ``rehearse_cells.py`` in a
process of its own), handed to ``readers.read_all`` in an
``Observations``.  Made on the repo's benchmark and on the one with one
more of everything (``one_more.py``)."""

import json
import os
import subprocess
import sys

import pytest

pytest.importorskip("cryptography")   # the manager's CA bootstrap

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(1, os.path.dirname(HERE))

from benchmark import harness, readers  # noqa: E402

import contract  # noqa: E402
import one_more  # noqa: E402
import servedpath_deploy  # noqa: E402

CELLS = ["swarm-10k.deploys", "swarm-1k.deploys-1k", "harness-100k.sparse",
         "harness-100k-ha.prefs"]
TREES = one_more.TREES
EVERY_TREE = pytest.mark.parametrize("bench", TREES, indirect=True)
#: name -> (layer, reduce, the span read, its argument or None, the
#: end-to-end metric moved)
PRETICK = {
    "api_create_ms": ("control API", "mean_ms", "api.create_service",
                      None, "assign_p50_ms"),
    "orch_wait_ms": ("orchestrator", "arg_mean", "orchestrator.service",
                     "wait_ms", "assign_p50_ms"),
    "orch_lock_wait_ms": ("orchestrator", "arg_mean",
                          "orchestrator.service", "lock_wait_ms",
                          "assign_p50_ms"),
    "alloc_wait_ms": ("allocator", "arg_mean", "allocator.tasks",
                      "wait_mean_ms", "assign_p50_ms"),
    "alloc_batch_ms": ("allocator", "mean_ms", "allocator.tasks", None,
                       "assign_p50_ms"),
    "alloc_lock_wait_ms": ("allocator", "arg_mean", "allocator.tasks",
                           "lock_wait_ms", "assign_p50_ms"),
    "fused_run_ms": ("scheduler tick", "ms_per_tick", "sched.fused_run",
                     None, "decisions_per_s"),
    "fused_build_ms": ("densify + resident state", "ms_per_tick",
                       "plan.fused_build", None, "decisions_per_s"),
}
#: what readers.py could reduce before this PR (its file was not edited)
SPAN_REDUCES = {"mean_ms", "ms_per_tick", "arg_mean", "arg_share_pct"}
#: the layers ``BENCHMARK.json`` named before this PR; ``allocator`` is new
LAYERS_BEFORE = {"control API", "orchestrator", "scheduler tick",
                 "densify + resident state"}


@pytest.fixture(scope="module")
def observed():
    """The deploy and what a traced run would hand the readers of it
    (made in ``rehearse_cells.py``'s process, as
    ``test_layer_metrics_inside.py`` makes its own)."""
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_cells.py"), "deploy"],
        capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    code, deploy = json.loads(done.stdout.strip().splitlines()[-1])["deploy"]
    assert code == 0
    obs = readers.Observations()
    t0, t1 = deploy["wall"]
    obs.window_s = t1 - t0
    obs.window_wall = (t0, t1)
    obs.spans = [tuple(s) for s in deploy["spans"] if t0 <= s[2] < t1]
    obs.counters = dict(deploy["counters"])
    return obs, deploy


@pytest.fixture
def read(observed, bench):
    """Every per-layer metric of each of the four cells, read from the
    deploy: {cell: {metric: {"value", "unit"}}}."""
    obs, _deploy = observed
    return {cell: readers.read_all(cell, obs, bench["per_layer"])
            for cell in CELLS}


@pytest.mark.parametrize(
    "bench,name", [(tree, name) for tree in TREES
                   for name in sorted(PRETICK)], indirect=["bench"])
def test_metric_is_one_file_one_entry_and_reads_a_number(bench, name, read,
                                                         observed):
    layer, how, span, arg, moves = PRETICK[name]
    with open(os.path.join(harness.ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert (spec["layer"], spec["moves"]) == (layer, moves)
    assert (spec["unit"], spec["better"]) == ("ms", "lower")
    assert "workloads" not in spec
    reader = spec["reader"]
    assert reader["kind"] == "span" and reader["reduce"] == how \
        and how in SPAN_REDUCES
    assert reader["span"] == span and reader.get("arg") == arg
    assert set(reader) == {"kind", "reduce", "span"} | (
        {"arg"} if arg else set())
    contract.layer_metric_is_sound(bench, name, spec)
    entries = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entries) == 1 and entries[0]["source"] == "program_span"
    # the four cells of the ladder, in its order (a later cell may follow)
    assert entries[0]["workloads"][:4] == CELLS
    obs, _deploy = observed
    rows = [s for s in obs.spans if s[1] == span]
    assert rows, f"the deploy has no {span} span"
    if arg:
        # on every span of the name, so ``arg_mean`` never reads nothing
        assert all(arg in s[4] for s in rows)
    for cell in CELLS:
        value = read[cell][name]["value"]
        assert isinstance(value, float) and value >= 0.0
        assert read[cell][name]["unit"] == "ms"
    if not arg or arg.startswith("wait"):
        # a duration or an age: some time did pass
        assert read[CELLS[0]][name]["value"] > 0.0


@EVERY_TREE
def test_the_eight_are_appended_and_the_old_entries_stand(bench, read,
                                                           observed):
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("api_create_ms")
    assert names[at:at + len(PRETICK)] == list(PRETICK)
    # what was there is there, first and in its order (PR 37's last)
    assert at == 40 and names[at - 1] == "leaf_cols_hit_pct"
    layers = {m["layer"] for m in bench["per_layer"][:at]}
    assert LAYERS_BEFORE <= layers and "allocator" not in layers
    assert {m["layer"] for m in bench["per_layer"][at:at + len(PRETICK)]} \
        == LAYERS_BEFORE | {"allocator"}
    # the client's side of the same legs stays until a benchmark issue
    # retires it
    for old in ("create_rpc_ms", "pending_lag_ms", "reconcile_ms",
                "queue_wait_ms", "lock_wait_ms"):
        assert old in names[:at]
    # every cell reads the same line from the same observations
    for cell in CELLS[1:]:
        for name in PRETICK:
            assert read[cell][name] == read[CELLS[0]][name]
    # a cell the entries do not name reads none of them
    assert readers.read_all("other.cell", observed[0],
                            bench["per_layer"]) == {}


@EVERY_TREE
def test_the_legs_are_consistent_with_each_other(bench, read, observed):
    """What the spans say of the deploy agrees with itself: the batch's
    wait is no older than the deploy, a thread waits for the lock no
    longer than its span lasts, and the fused run's build lies inside the
    run."""
    obs, _deploy = observed
    line = read[CELLS[0]]
    window_ms = 1e3 * obs.window_s
    for name in ("orch_wait_ms", "alloc_wait_ms", "alloc_batch_ms",
                 "api_create_ms"):
        assert line[name]["value"] < window_ms
    assert line["orch_lock_wait_ms"]["value"] \
        <= line["reconcile_ms"]["value"] + 1e-6
    assert line["alloc_lock_wait_ms"]["value"] \
        <= line["alloc_batch_ms"]["value"] + 1e-6
    assert 0 < line["fused_build_ms"]["value"] \
        < line["fused_run_ms"]["value"] <= line["tick_ms"]["value"]
    # the stack's two services fused: one run of two groups
    runs = [s[4] for s in obs.spans if s[1] == "sched.fused_run"]
    assert [r["groups"] for r in runs] == [2]
    # of the stack: a service whose RPC came after the first two
    rpcs = sorted((s for s in obs.spans if s[1] == "api.create_service"),
                  key=lambda s: s[2])
    assert runs[0]["service"] in {s[4]["service"] for s in rpcs[2:]}
    batches = [s[4] for s in obs.spans if s[1] == "allocator.tasks"]
    assert sum(b["allocated"] for b in batches) \
        == 3 * servedpath_deploy.DEVICE_REPLICAS \
        + servedpath_deploy.HOST_REPLICAS
