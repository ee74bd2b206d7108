"""Whole runs of cell 1, of the cell kept as data and of the planted
faults on the forced CPU at a tiny size, a cell's ``harness.Served`` built
and stopped (``served``), and the small traced deploy the per-layer
metrics' tests read (``deploy``), in a process of their own: a
run leaves the program's process-wide state behind (metrics registry,
flight recorder, tracer, a warm planner), which the tests that share a
worker with ``tests/benchmark`` must not inherit.  Takes the runs to make
as arguments and prints one JSON object: {name: [exit code, line]}.
``--root <tree>`` first makes them on the benchmark under that tree (its
``BENCHMARK.json``, data files and ``tests/benchmark/shrink``) in this
repo's place.

The TPU check is switched off here, in the tests, and nowhere in the
command."""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)


def load_shrinks(root: str) -> dict:
    """{cell: its cut to what a test run can hold}: overrides of the
    configuration's ``cluster`` and of the traffic's parameters, one file
    a cell, ``tests/benchmark/shrink/<cell>.json``, so that a new cell
    brings its own."""
    folder = os.path.join(root, "tests", "benchmark", "shrink")
    out = {}
    for fname in sorted(os.listdir(folder)):
        if fname.endswith(".json"):
            with open(os.path.join(folder, fname)) as f:
                out[fname[:-len(".json")]] = json.load(f)
    return out


#: the faults planted in cell 1 by their bare name
FAULTS = ("host_route", "answer_altered", "group_on_one_node")
#: cells kept as data files that ``BENCHMARK.json`` does not list (PERF.md
#: 7, first row): a rehearsal brings the ``workloads`` entry itself
KEPT = {
    "harness-100k.backlog": {
        "name": "harness-100k.backlog", "config": "harness-100k",
        "traffic": "backlog", "chips": 1,
        "why": "closed loop, 4 clients, one shape each, 5,000-replica "
               "services back to back on 100k nodes"},
}


def deploy() -> dict:
    """``tests/servedpath_deploy.py``'s traced deploy, as the plain data
    an ``Observations`` is filled from."""
    sys.path.insert(1, os.path.dirname(HERE))
    import servedpath_deploy
    made = servedpath_deploy.traced_deploy()
    return {"spans": [s[:5] for s in made["spans"]], "wall": made["wall"],
            "counters": made["counters"]}


def served(cell: str, shrink: dict) -> dict:
    """Build the cell's ``harness.Served`` at its cut, say what it is
    made of (the managers it started among it), and stop it."""
    from benchmark import cluster, harness, traffic
    entry = {w["name"]: w for w in harness.load_benchmark()["workloads"]}[
        cell]
    config = cluster.load_config(entry["config"])
    config["cluster"].update(shrink.get("cluster", {}))
    made = harness.Served(config, traffic.load(entry["traffic"]), seed=5)
    try:
        mgr = made.mgr
        return {"members": made.members is not None,
                "managers": 1 if made.members is None
                else len(made.members.nodes),
                "raft": mgr.raft is not None,
                "proposer": mgr.store._proposer is not None,
                "leader": mgr.is_leader,
                "heartbeat_period": mgr._dispatcher_config.heartbeat_period,
                "nodes": len(made.nodes), "agents": len(made.agents)}
    finally:
        made.stop()


def main(names) -> None:
    """``names``: ``<cell>:plain``, ``<cell>:traced``, ``<cell>:<fault>``
    (a plain run with that fault planted), a fault's name alone (cell 1),
    ``served:<cell>`` (``served``) or ``deploy``."""
    root = REPO
    if names[:1] == ["--root"]:
        root, names = names[1], names[2:]
        sys.path.insert(1, HERE)
        import contract
        contract.point_at(root)
    shrinks = load_shrinks(root)
    # two cores and a low priority: the tier-1 run has timing-sensitive
    # daemon tests beside this process
    try:
        os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[-2:])
        os.nice(10)
    except (AttributeError, OSError):
        pass
    import jax
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_compilation_cache", False)
    from benchmark import harness
    from benchmark.control import PROGRAM_FAULTS, Rehearsal
    out = {}
    real_stdout, sys.stdout = sys.stdout, sys.stderr
    try:
        for name in names:
            if name == "deploy":
                out[name] = 0, deploy()
                continue
            if name.startswith("served:"):
                cell = name.split(":", 1)[1]
                out[name] = 0, served(cell, shrinks[cell])
                continue
            if name in FAULTS:
                cell, trace, seed, seconds = \
                    "swarm-10k.deploys", False, 91, 3
                rehearsal = Rehearsal(name, shrinks[cell], require_tpu=False)
            else:
                cell, how = name.split(":")
                trace, seed, seconds = how == "traced", 2 ** 31 + 77, 4
                fault = how if how in PROGRAM_FAULTS else None
                rehearsal = Rehearsal(fault, shrinks[cell], require_tpu=False,
                                      cell=KEPT.get(cell))
            out[name] = harness.run_cell(
                cell, seed=seed, seconds=seconds, trace=trace,
                t_start=time.perf_counter(), rehearsal=rehearsal)
    finally:
        sys.stdout = real_stdout
    print(json.dumps(out), flush=True)
    os._exit(0)


if __name__ == "__main__":
    main(sys.argv[1:])
