"""Whole runs of cell 1, of the cell kept as data and of the planted
faults on the forced CPU at a tiny size, in a process of their own: a run leaves the program's
process-wide state behind (metrics registry, flight recorder, tracer),
which the tests that share a worker with ``test_benchmark_harness.py``
must not inherit.  Takes the runs to make as arguments and prints one
JSON object: {name: [exit code, line]}.

The TPU check is switched off here, in the tests, and nowhere in the
command."""

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

#: the cells cut to what a test run can hold
SHRINK = {
    "swarm-10k.deploys": {
        "cluster": {"nodes": 300, "racks_per_zone": 5, "agents": 6},
        "traffic": {"tasks_per_s": 150}},
    "harness-100k.backlog": {
        "cluster": {"nodes": 1200, "racks_per_zone": 10, "agents": 6},
        "traffic": {"clients": [
            {"shape": s, "replicas": 120}
            for s in ("spread", "constrained", "binpack", "topology")]}},
}
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


def main(names) -> None:
    """``names``: ``<cell>:plain``, ``<cell>:traced`` or a fault's name
    (a plain run of cell 1 with that fault planted)."""
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
    from benchmark.control import Rehearsal
    out = {}
    real_stdout, sys.stdout = sys.stdout, sys.stderr
    try:
        for name in names:
            if name in FAULTS:
                cell, trace, seed, seconds = \
                    "swarm-10k.deploys", False, 91, 3
                rehearsal = Rehearsal(name, SHRINK[cell], require_tpu=False)
            else:
                cell, how = name.split(":")
                trace, seed, seconds = how == "traced", 2 ** 31 + 77, 4
                rehearsal = Rehearsal(None, SHRINK[cell], require_tpu=False,
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
