"""The per-layer metric that reads what a service's column costs the
resident tier (ISSUE 39), in the form of ``test_pretick_metrics.py``:
``svc_col_rows_per_build`` is one new data file under
``benchmark/layer_metrics/`` with one ``per_layer`` entry appended to
``BENCHMARK.json``, of the reader kind ``counter`` that
``benchmark/readers.py`` already had, lists the four cells in the
ladder's order, and reads a number from the counters of the small traced
deploy through a live ``Manager()`` (``tests/servedpath_deploy.py``, made
by ``rehearse_cells.py`` in a process of its own), handed to
``readers.read_all`` in an ``Observations``.  A program without the two
counters (this PR's parent) leaves the metric off the line and raises
nothing.  Made on the repo's benchmark and on the one with one more of
everything (``one_more.py``)."""

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

NAME = "svc_col_rows_per_build"
CELLS = ["swarm-10k.deploys", "swarm-1k.deploys-1k", "harness-100k.sparse",
         "harness-100k-ha.prefs"]
TREES = one_more.TREES
EVERY_TREE = pytest.mark.parametrize("bench", TREES, indirect=True)
#: the ``per_layer`` entries that were there before this PR, in their order
BEFORE = [
    "create_rpc_ms", "pending_lag_ms", "materialise_per_s", "tick_ms",
    "tick_tasks", "device_route_pct", "build_inputs_ms", "plan_kernel_ms",
    "plan_roofline", "device_wait_ms", "apply_ms", "commit_ms",
    "device_idle_pct", "window_compiles", "generator_late_ms",
    "assign_p95_ms", "debounce_wait_ms", "debounce_max_pct",
    "sched_events_ms", "sched_cpu_pct", "queue_wait_ms", "tick_offcpu_ms",
    "commit_apply_ms", "commit_publish_ms", "lock_wait_ms", "reconcile_ms",
    "host_route_groups_pct", "host_route_ms", "route_host_est_ms",
    "route_device_est_ms", "route_switches_per_tick", "tree_cols_hit_pct",
    "h2d_mb_per_tick", "d2h_mb_per_tick", "wide_tree_group_ms",
    "wide_tree_groups_pct", "pref_groups_pct", "fused_wide_run_ms",
    "fused_wide_groups_pct", "leaf_cols_hit_pct", "api_create_ms",
    "orch_wait_ms", "orch_lock_wait_ms", "alloc_wait_ms", "alloc_batch_ms",
    "alloc_lock_wait_ms", "fused_run_ms", "fused_build_ms"]


@pytest.fixture(scope="module")
def observed():
    """The deploy and what a traced run would hand the readers of it
    (made in ``rehearse_cells.py``'s process, as
    ``test_pretick_metrics.py`` makes its own)."""
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


@EVERY_TREE
def test_metric_is_one_file_one_entry_and_reads_a_number(bench, observed):
    with open(os.path.join(harness.ROOT, "benchmark", "layer_metrics",
                           NAME + ".json")) as f:
        spec = json.load(f)
    assert (spec["layer"], spec["moves"]) \
        == ("densify + resident state", "decisions_per_s")
    assert (spec["unit"], spec["better"]) == ("rows", "lower")
    assert spec["reader"] == {
        "kind": "counter",
        "num": {"source": "planner.stats", "key": "svc_col_rows"},
        "den": {"source": "planner.stats", "key": "svc_cols_builds"}}
    contract.layer_metric_is_sound(bench, NAME, spec)
    entries = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert len(entries) == 1 and entries[0]["source"] == "program_counter"
    # the four cells of the ladder, in its order (a later cell may follow)
    assert entries[0]["workloads"][:4] == CELLS
    obs, deploy = observed
    grown = deploy["counters"]["planner.stats"]
    # the device-routed service's group and the stack's run of two: each
    # a service's first, which holds no task anywhere
    assert grown["svc_cols_builds"] == 3 and grown["svc_col_rows"] == 0
    for cell in CELLS:
        line = readers.read_all(cell, obs, bench["per_layer"])
        assert line[NAME] == {"value": 0.0, "unit": "rows"}
        assert isinstance(line[NAME]["value"], float)
    assert NAME not in readers.read_all("other.cell", obs,
                                        bench["per_layer"])


@EVERY_TREE
def test_it_is_appended_and_the_old_entries_stand(bench, observed):
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:len(BEFORE)] == BEFORE and names[len(BEFORE)] == NAME
    specs = readers.load_layer_metrics()
    contract.every_entry_has_its_file(bench, specs)
    for name in BEFORE:
        contract.layer_metric_is_sound(bench, name, specs[name])
    # the layer's other metrics read the same deploy beside it
    line = readers.read_all(CELLS[0], observed[0], bench["per_layer"])
    for old in ("build_inputs_ms", "fused_build_ms", "h2d_mb_per_tick"):
        assert specs[old]["layer"] == specs[NAME]["layer"]
        assert line[old]["value"] > 0, old


@pytest.mark.parametrize("bench,table,want", [
    (tree, table, want) for tree in TREES for table, want in (
        # the parent: a planner with neither counter, or no table at all
        ({"groups_planned": 7, "tree_cols_hits": 2}, None),
        (None, None),
        # a window in which no column was built: nothing to divide by
        ({"svc_cols_builds": 0, "svc_col_rows": 0}, None),
        # scale-ups of placed services: the rows that hold them, a build
        ({"svc_cols_builds": 4, "svc_col_rows": 10}, 2.5),
        ({"svc_cols_builds": 382, "svc_col_rows": 0}, 0.0))],
    indirect=["bench"])
def test_the_reader_leaves_out_what_it_cannot_read(bench, table, want):
    obs = readers.Observations()
    obs.counters = {"scheduler.stats": {"ticks": 3}}
    if table is not None:
        obs.counters["planner.stats"] = table
    for cell in CELLS:
        line = readers.read_all(cell, obs, bench["per_layer"])
        if want is None:
            assert NAME not in line
        else:
            assert line[NAME] == {"value": want, "unit": "rows"}
