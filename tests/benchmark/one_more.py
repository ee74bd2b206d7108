"""The benchmark with one more of everything, assembled by the tests in a
temporary directory from new data only: a copy of the repo's
``BENCHMARK.json`` and data files, then a configuration of 1,250 nodes, a
traffic file, a cell, its cut to a test's size, one per-layer metric of
reader kind ``span`` and one of kind ``counter`` as new files, entries
appended to ``BENCHMARK.json``'s lists and the new cell's name appended to
the ``workloads`` list of every metric cell 1 reports that a CPU run of
it can read (``LISTED``); and the configuration's three-manager twin
(``"managers": 3`` behind raft, the WAL fsynced, 1 ms between members)
with its cell and cut, appended to ``assign_p50_ms``, ``tick_ms`` and
``commit_ms`` (``LISTED3``), and a per-layer metric of its own, a counter
of the raft member the harness drives (``MEMBER_METRICS``).  That is all
a later PR does to add a deployment's cell; ``contract.only_additions``
and the byte comparison in the tests hold the assembly to it.  None of
this is the repo's benchmark.
"""

import json
import os
import shutil

from benchmark.readers import SOURCE_OF_KIND
from contract import data_files

#: the trees the tests are made on (``conftest.py``): the repo's benchmark
#: and the one ``build`` assembles
TREES = ("repo", "one_more")
#: names no deployment's own files will take: a later PR's
#: ``swarm-1k.json`` must not meet a file of this fixture's in the tree
CONFIG, TRAFFIC = "one-more-1250", "one-more-deploys"
CELL = f"{CONFIG}.{TRAFFIC}"
#: the twin on three raft managers: a configuration and a cell of its own
#: under the same traffic, reporting the end-to-end metrics
CONFIG3 = f"{CONFIG}-m3"
CELL3 = f"{CONFIG3}.{TRAFFIC}"
MANAGERS3 = {"managers": 3, "wal_fsync": True, "raft_link_delay_ms": 1}
#: the metrics the benchmark has that the new cell also reports: every one
#: cell 1 reports that a CPU run can read (its ``device_trace`` metrics
#: aside), named here so that a metric a later PR lists for cell 1 is not
#: handed to this cell unread
LISTED = ("assign_p50_ms", "create_rpc_ms", "pending_lag_ms",
          "materialise_per_s", "tick_ms", "tick_tasks", "device_route_pct",
          "build_inputs_ms", "device_wait_ms", "apply_ms", "commit_ms",
          "window_compiles", "generator_late_ms", "assign_p95_ms",
          "debounce_wait_ms", "debounce_max_pct", "sched_events_ms",
          "sched_cpu_pct", "queue_wait_ms", "tick_offcpu_ms",
          "commit_apply_ms", "commit_publish_ms", "lock_wait_ms",
          "reconcile_ms", "host_route_groups_pct", "tree_cols_hit_pct",
          "h2d_mb_per_tick", "d2h_mb_per_tick", "api_create_ms",
          "orch_wait_ms", "orch_lock_wait_ms", "alloc_wait_ms",
          "alloc_batch_ms", "alloc_lock_wait_ms", "fused_run_ms",
          "fused_build_ms", "svc_col_rows_per_build")
#: ... and those the twin reports
LISTED3 = ("assign_p50_ms", "tick_ms", "commit_ms")
SOURCE = ("moby/swarmkit manager/scheduler/scheduler_test.go:3338 "
          "BenchmarkScheduler1kNodes1kTasks' cluster, at 1,250 nodes, "
          "driven through the control API like cmd/swarm-bench")
NEW_METRICS = {
    "one_more_begin_tick_ms": {
        "layer": "densify + resident state", "unit": "ms",
        "better": "lower", "moves": "decisions_per_s",
        "reader": {"kind": "span", "span": "plan.begin_tick",
                   "reduce": "ms_per_tick"}},
    "one_more_tick_events": {
        "layer": "scheduler loop", "unit": "events/tick",
        "better": "lower", "moves": "decisions_per_s",
        "reader": {"kind": "counter",
                   "num": {"source": "scheduler.stats",
                           "key": "events_handled"},
                   "den": {"source": "scheduler.stats", "key": "ticks"}}},
}
#: the twin's own: a counter the harness reads only where raft members run
MEMBER_METRICS = {
    "one_more_raft_applied": {
        "layer": "raft log", "unit": "entries/tick", "better": "lower",
        "moves": "decisions_per_s",
        "reader": {"kind": "counter",
                   "num": {"source": "raft.leader", "key": "applied"},
                   "den": {"source": "scheduler.stats", "key": "ticks"}}},
}


def _write(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)
        f.write("\n")


def build(repo: str, tree: str) -> dict:
    """Copy the repo's benchmark data into ``tree`` and add one more of
    everything.  Returns the extended ``BENCHMARK.json`` as loaded."""
    for rel, raw in data_files(repo).items():
        os.makedirs(os.path.dirname(os.path.join(tree, rel)), exist_ok=True)
        with open(os.path.join(tree, rel), "wb") as f:
            f.write(raw)
    shutil.copy(os.path.join(repo, "BENCHMARK.json"), tree)
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    data = os.path.join(tree, "benchmark")

    # a configuration: a third cluster size (the 2,048 bucket), 10 racks
    # a zone; shapes, manager and guarantees as the 10k swarm states them
    with open(os.path.join(data, "configs", "swarm-10k.json")) as f:
        config = json.load(f)
    config.update(name=CONFIG, source=SOURCE, assumed=[
        "1,250 nodes, 4 zones of 10 racks; the rest as swarm-10k"])
    config["cluster"].update(nodes=1250, racks_per_zone=10)
    _write(os.path.join(data, "configs", f"{CONFIG}.json"), config)
    bench["configs"].append({
        "name": CONFIG, "source": SOURCE,
        "file": f"benchmark/configs/{CONFIG}.json", "reduced": [],
        "why": "a small swarm: the other side of the break-even router"})

    # a traffic file: the open loop with a cycle of three shapes, so the
    # longest fusable run is two and not three
    with open(os.path.join(data, "traffic", "deploys.json")) as f:
        params = json.load(f)
    params.update(shapes=["spread", "binpack", "topology"], tasks_per_s=200,
                  assumed=["deploys with a three-shape cycle at half the "
                           "rate; no rate was measured"])
    _write(os.path.join(data, "traffic", f"{TRAFFIC}.json"), params)

    # its three-manager twin: the same file but the manager block
    config.update(name=CONFIG3)
    config["manager"] = dict(
        config["manager"], **MANAGERS3,
        store="a MemoryStore a member, each behind a RaftNode with an "
              "encrypted WAL")
    _write(os.path.join(data, "configs", f"{CONFIG3}.json"), config)
    bench["configs"].append(dict(
        bench["configs"][-1], name=CONFIG3, file=f"benchmark/configs/"
        f"{CONFIG3}.json", why="the small swarm on three raft managers"))

    # the cells, and their cuts to a test's size
    cut = {"cluster": {"nodes": 300, "racks_per_zone": 5, "agents": 6},
           "traffic": {"tasks_per_s": 150}}
    bench["workloads"].append({
        "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
        "why": "open loop of small deploys on 1,250 nodes, 3 shapes "
               "cycling: groups near the router's crossover"})
    bench["workloads"].append({
        "name": CELL3, "config": CONFIG3, "traffic": TRAFFIC, "chips": 1,
        "why": "the same deploys committed through three raft members, "
               "every acknowledged write read back from each"})
    for cell in (CELL, CELL3):
        _write(os.path.join(tree, "tests", "benchmark", "shrink",
                            f"{cell}.json"), cut)

    # the cells' names appended to lists that are there
    for entry in bench["end_to_end"] + bench["per_layer"]:
        if entry["name"] in LISTED:
            entry["workloads"].append(CELL)
        if entry["name"] in LISTED3:
            entry["workloads"].append(CELL3)

    # per-layer metrics of reader kinds that are there, each for one of
    # the two cells alone
    for cell, metrics in ((CELL, NEW_METRICS), (CELL3, MEMBER_METRICS)):
        for name, spec in metrics.items():
            _write(os.path.join(data, "layer_metrics", f"{name}.json"), spec)
            bench["per_layer"].append({
                "name": name, "unit": spec["unit"], "better": spec["better"],
                "source": SOURCE_OF_KIND[spec["reader"]["kind"]],
                "layer": spec["layer"],
                "moves": spec["moves"], "workloads": [cell]})
    _write(os.path.join(tree, "BENCHMARK.json"), bench)
    return bench
