"""The per-layer metrics that read the program's own account of the
served path (ISSUE 27's, of which the ten that still have something to
read in a cell stay; ISSUE 28's routing counter, which came with PR 29):
each is one data file under ``benchmark/layer_metrics/`` with one
``per_layer`` entry, uses a reader kind and a reduce that
``benchmark/readers.py`` already had, and reads a number from the spans
and counters of a small traced deploy through a live ``Manager()``
(``tests/servedpath_deploy.py``, made by ``rehearse_cells.py`` in a
process of its own), handed to ``readers.read_all`` in an
``Observations``.  Like ``test_benchmark_harness.py``'s, the tests are
made on the repo's benchmark and on the one with one more of everything
(``one_more.py``), where the same deploy also has what the two added
metrics read and cell 1's line must hold neither."""

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

CELL = "swarm-10k.deploys"
TREES = one_more.TREES
EVERY_TREE = pytest.mark.parametrize("bench", TREES, indirect=True)
#: name -> (layer, reader kind, reduce or None, the end-to-end metric moved)
INSIDE = {
    "debounce_wait_ms": ("scheduler loop", "span", "mean_ms",
                         "assign_p50_ms"),
    "debounce_max_pct": ("scheduler loop", "counter", None,
                         "assign_p50_ms"),
    "sched_events_ms": ("scheduler loop", "span", "ms_per_tick",
                        "decisions_per_s"),
    "sched_cpu_pct": ("scheduler loop", "counter", None,
                      "decisions_per_s"),
    "queue_wait_ms": ("scheduler tick", "span", "arg_mean",
                      "assign_p50_ms"),
    "tick_offcpu_ms": ("scheduler tick", "span", "arg_mean",
                       "decisions_per_s"),
    "commit_apply_ms": ("commit", "span", "ms_per_tick",
                        "decisions_per_s"),
    "commit_publish_ms": ("commit", "span", "ms_per_tick",
                          "decisions_per_s"),
    "lock_wait_ms": ("store update lock", "span", "ms_per_tick",
                     "assign_p50_ms"),
    "reconcile_ms": ("orchestrator", "span", "mean_ms", "assign_p50_ms"),
}
#: what came after PR 27's, in the same form
LATER = {
    "host_route_groups_pct": ("planner routing", "counter", None,
                              "decisions_per_s"),
}
#: what the deploy's two services grow in ``planner.stats``: the data of
#: ``servedpath_deploy`` holds the scheduler's counters alone, and its
#: file is not the benchmark's to edit
ROUTES = {"groups_planned": 1, "groups_small_to_host": 1}
METRICS = {**INSIDE, **LATER}
#: what readers.py could reduce before PR 27 (its file was not edited)
SPAN_REDUCES = {"mean_ms", "ms_per_tick", "arg_mean", "arg_share_pct"}


@pytest.fixture(scope="module")
def observed():
    """The deploy and what a traced run would hand the readers of it.
    The deploy is made in ``rehearse_cells.py``'s process: made here it
    would leave this worker's planner warm for whatever module comes
    next (``tests/test_obs_servedpath.py`` wants its own deploy to be one
    of a process's first two)."""
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
    obs.counters = dict(deploy["counters"], **{"planner.stats": ROUTES})
    return obs, deploy


@pytest.fixture
def read(observed, bench):
    """Every per-layer metric of the cell, read from the deploy."""
    obs, deploy = observed
    return readers.read_all(CELL, obs, bench["per_layer"]), deploy


@pytest.mark.parametrize(
    "bench,name", [(tree, name) for tree in TREES for name in sorted(METRICS)],
    indirect=["bench"])
def test_metric_is_one_file_one_entry_and_reads_a_number(bench, name, read):
    layer, kind, how, moves = METRICS[name]
    with open(os.path.join(harness.ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["layer"] == layer and spec["moves"] == moves
    assert "workloads" not in spec
    reader = spec["reader"]
    assert reader["kind"] == kind and kind in readers.KINDS
    if kind == "span":
        assert reader["reduce"] == how and how in SPAN_REDUCES
    else:
        assert set(reader) <= {"kind", "num", "den", "scale"}
        assert reader["num"]["source"] in ("scheduler.stats",
                                           "planner.stats")
    contract.layer_metric_is_sound(bench, name, spec)
    entries = [m for m in bench["per_layer"] if m["name"] == name]
    assert len(entries) == 1 and CELL in entries[0]["workloads"]
    metrics, _deploy = read
    value = metrics[name]["value"]
    assert isinstance(value, float) and value >= 0.0
    assert metrics[name]["unit"] == spec["unit"]
    if name.endswith("_pct"):
        assert value <= 100.0 + 1e-9


@EVERY_TREE
def test_the_new_entries_are_appended_and_the_old_ones_still_read(bench,
                                                                  read):
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:16] == [
        "create_rpc_ms", "pending_lag_ms", "materialise_per_s", "tick_ms",
        "tick_tasks", "device_route_pct", "build_inputs_ms",
        "plan_kernel_ms", "plan_roofline", "device_wait_ms", "apply_ms",
        "commit_ms", "device_idle_pct", "window_compiles",
        "generator_late_ms", "assign_p95_ms"]
    # PR 27's follow the sixteen; what comes after them is free
    assert sorted(names[16:16 + len(INSIDE)]) == sorted(INSIDE)
    assert set(LATER) <= set(names[16 + len(INSIDE):])
    metrics, deploy = read
    # the span-read metrics the benchmark already had read the same run
    for old in ("tick_ms", "tick_tasks", "device_route_pct",
                "build_inputs_ms", "device_wait_ms", "apply_ms",
                "commit_ms"):
        assert metrics[old]["value"] > 0, old
    # commit by stage accounts for the commit span it lies under
    stages = sum(metrics[k]["value"]
                 for k in ("commit_apply_ms", "commit_publish_ms"))
    assert 0 < stages <= metrics["commit_ms"]["value"] * 1.05
    grown = deploy["counters"]["scheduler.stats"]
    # every tick fired on one deadline or the other; the deploy's first
    # snapshot may fall inside the warm-up's last tick, which has counted
    # itself (``ticks``) and not yet its deadline (``ticks_by_*``)
    assert 2 <= grown["ticks"] \
        <= grown["ticks_by_gap"] + grown["ticks_by_max_latency"] \
        <= grown["ticks"] + 1
    assert grown["events_handled"] >= \
        servedpath_deploy.DEVICE_REPLICAS and grown["commits_seen"] >= 4


@EVERY_TREE
def test_one_list_reads_what_the_files_lists_read_less_two_plus_one(
        bench, observed, read):
    """The same observations, read the way ``read_all`` read them while
    every metric's file listed its cells (the reader of each file whose
    list has the cell, asked in turn; the list is the entry's now) and
    read by the ``per_layer`` entries: name for name and value for value
    the same line; on the repo's tree that is what it gave before PR 29
    without the two host-route metrics that left and with the routing
    counter that came.  A metric listed for another cell alone (the two
    of ``one_more``, whose span and counters this deploy has too) is in
    neither."""
    obs, deploy = observed
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    by_file = {}
    for name, spec in readers.load_layer_metrics().items():
        if name not in listed:
            continue
        value = readers.KINDS[spec["reader"]["kind"]](spec["reader"], obs)
        if value is not None:
            by_file[name] = {"value": value, "unit": spec["unit"]}
    metrics, _ = read
    assert metrics == by_file and list(metrics) == list(by_file)
    assert metrics["host_route_groups_pct"] == {"value": 50.0, "unit": "%"}
    if one_more.CELL in contract.cell_names(bench):
        added = {m["name"]: m for m in bench["per_layer"]
                 if m["workloads"] == [one_more.CELL]}
        assert set(added) == set(one_more.NEW_METRICS)
        assert not set(added) & set(metrics)
        # not for want of something to read: the cell that lists them
        # reads both from these observations
        assert set(readers.read_all(one_more.CELL, obs, bench["per_layer"])
                   ) >= set(added)
    # a cell the entries do not name reads nothing
    assert readers.read_all("other.cell", obs, bench["per_layer"]) == {}
