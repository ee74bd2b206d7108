"""The twelve per-layer metrics that read the program's own account of the
served path (ISSUE 27): each is one data file under
``benchmark/layer_metrics/`` with one ``per_layer`` entry, uses a reader
kind and a reduce that ``benchmark/readers.py`` already had, and reads a
number from the spans and counters of a small traced deploy through a live
``Manager()`` (``tests/servedpath_deploy.py``), handed to
``readers.read_all`` in an ``Observations``."""

import json
import os
import sys

import pytest

pytest.importorskip("cryptography")   # the manager's CA bootstrap

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(1, os.path.dirname(HERE))

from benchmark import harness, readers  # noqa: E402

import servedpath_deploy  # noqa: E402

CELL = "swarm-10k.deploys"
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
    "host_fallback_ms": ("planner routing", "span", "ms_per_tick",
                         "decisions_per_s"),
    "strategy_host_ms": ("planner routing", "span", "ms_per_tick",
                         "decisions_per_s"),
    "commit_apply_ms": ("commit", "span", "ms_per_tick",
                        "decisions_per_s"),
    "commit_publish_ms": ("commit", "span", "ms_per_tick",
                          "decisions_per_s"),
    "lock_wait_ms": ("store update lock", "span", "ms_per_tick",
                     "assign_p50_ms"),
    "reconcile_ms": ("orchestrator", "span", "mean_ms", "assign_p50_ms"),
}
#: what readers.py could reduce before this PR (its file is not edited)
SPAN_REDUCES = {"mean_ms", "ms_per_tick", "arg_mean", "arg_share_pct"}


@pytest.fixture(scope="module")
def read():
    """Every per-layer metric of the cell, read from the deploy."""
    deploy = servedpath_deploy.traced_deploy()
    obs = readers.Observations()
    t0, t1 = deploy["wall"]
    obs.window_s = t1 - t0
    obs.window_wall = (t0, t1)
    obs.spans = [s[:5] for s in deploy["spans"] if t0 <= s[2] < t1]
    obs.counters = deploy["counters"]
    return readers.read_all(CELL, obs), deploy


@pytest.mark.parametrize("name", sorted(INSIDE))
def test_metric_is_one_file_one_entry_and_reads_a_number(name, read):
    layer, kind, how, moves = INSIDE[name]
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    assert spec["layer"] == layer and spec["moves"] == moves
    assert spec["workloads"] == [CELL]
    reader = spec["reader"]
    assert reader["kind"] == kind and kind in readers.KINDS
    if kind == "span":
        assert reader["reduce"] == how and how in SPAN_REDUCES
    else:
        assert set(reader) <= {"kind", "num", "den", "scale"}
        assert reader["num"]["source"] == "scheduler.stats"
    entries = [m for m in harness.load_benchmark()["per_layer"]
               if m["name"] == name]
    assert entries == [{
        "name": name, "unit": spec["unit"], "better": spec["better"],
        "source": readers.SOURCE_OF_KIND[kind], "layer": layer,
        "moves": moves, "workloads": [CELL]}]
    metrics, _deploy = read
    if name == "strategy_host_ms":
        # the deploy has no binpack service: nothing to read is left
        # out of the line, not written as nought
        assert name not in metrics
        return
    value = metrics[name]["value"]
    assert isinstance(value, float) and value >= 0.0
    assert metrics[name]["unit"] == spec["unit"]
    if name.endswith("_pct"):
        assert value <= 100.0 + 1e-9


def test_the_new_entries_are_appended_and_the_old_ones_still_read(read):
    names = [m["name"] for m in harness.load_benchmark()["per_layer"]]
    assert names[:16] == [
        "create_rpc_ms", "pending_lag_ms", "materialise_per_s", "tick_ms",
        "tick_tasks", "device_route_pct", "build_inputs_ms",
        "plan_kernel_ms", "plan_roofline", "device_wait_ms", "apply_ms",
        "commit_ms", "device_idle_pct", "window_compiles",
        "generator_late_ms", "assign_p95_ms"]
    assert sorted(names[16:]) == sorted(INSIDE)
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
    assert grown["ticks_by_gap"] + grown["ticks_by_max_latency"] \
        == grown["ticks"] >= 2
    assert grown["events_handled"] >= \
        servedpath_deploy.DEVICE_REPLICAS and grown["commits_seen"] >= 4
