#!/usr/bin/env python3
"""One traced run of a benchmark cell, with the program's own trace kept:

    python scripts/servedpath_trace.py --workload swarm-10k.deploys \\
        --seed 7 --seconds 51 --out chiprun_out/deploys-7 [--kept] [--cpu]

Runs ``benchmark.harness.run_cell`` with ``--trace 1`` exactly as
``benchmark/run.py`` does and prints the same result line, then writes
what the harness does not keep: ``<out>.trace.json`` (the tracer's Chrome
trace, with ``otherData.thread_cpu_s``; ``scripts/trace_report.py`` reads
it, ``--service ID`` follows one deploy through it) and prints one
``servedpath`` line: the scheduler loop's cadence (ticks by the deadline
that fired them, mean debounce, tasks a tick), where the thread's time
went (``sched.tick`` / ``sched.debounce`` / ``sched.idle``), the self
time of ``sched.tick`` and its children's sum, the commit stages against
``sched.commit``, the CPU seconds by thread, and the largest service of
the window (to follow).

``--kept`` runs a cell that ``BENCHMARK.json`` does not list, kept as data
files (``tests/benchmark/rehearse_cells.py KEPT``), through
``control.Rehearsal``.  ``--cpu`` is the rehearsal of this script on the
forced CPU at a test's size; its times are not device numbers.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))


def summary(doc: dict) -> dict:
    """The ``servedpath`` line's object, from the tracer's document."""
    from swarmkit_tpu.obs.report import phase_table, x_events
    events = x_events(doc)
    table = phase_table(doc)
    phases = table["phases"]

    def total(name: str) -> float:
        return phases.get(name, {}).get("total_s", 0.0)

    ticks = [e for e in events if e["name"] == "sched.tick"]
    episodes = [e for e in events if e["name"] == "sched.debounce"]
    ticked = [e for e in episodes if e["args"].get("ticked")]
    by_cause = {}
    for e in ticked:
        cause = e["args"]["fired"]
        by_cause[cause] = by_cause.get(cause, 0) + 1
    tick_s = total("sched.tick")
    tick_ids = {e["args"]["span_id"] for e in ticks}
    child_s = {}
    for e in events:
        if e["args"].get("parent_id") in tick_ids:
            child_s[e["name"]] = child_s.get(e["name"], 0.0) \
                + e["dur"] / 1e6
    n = max(1, len(ticks))
    # ``plan.inflight`` is laid over a plan's dispatch->fetch window
    # after the fact: it overlaps its siblings, so it is no part of the
    # children's sum
    children_ms = 1e3 * sum(v for k, v in child_s.items()
                            if k != "plan.inflight") / n
    # one row for the agents' per-task threads and per-node streams
    threads = {}
    for name, used in ((doc.get("otherData") or {}).get("thread_cpu_s")
                       or {}).items():
        key = "taskmanager" if name.startswith("taskmanager-") \
            else re.sub(r"-\d+$", "", name)
        threads[key] = round(threads.get(key, 0.0) + used, 6)
    deploys = {}
    for e in events:
        if e["name"] == "orchestrator.service":
            sid = e["args"].get("service")
            deploys[sid] = max(deploys.get(sid, 0),
                               e["args"].get("created", 0))
    largest = max(deploys, key=deploys.get) if deploys else None
    lock_by_holder = {}
    for e in events:
        if e["name"] == "store.lock_wait":
            who = e["args"].get("holder") or "(free)"
            lock_by_holder[who] = lock_by_holder.get(who, 0.0) \
                + e["dur"] / 1e6
    return {
        "ticks": len(ticks),
        "ticks_by_cause": by_cause,
        "episodes": len(episodes),
        "debounce_mean_ms": 1e3 * total("sched.debounce")
        / max(1, len(episodes)),
        "tasks_per_tick": sum(e["args"].get("decisions", 0)
                              for e in ticks) / n,
        "scheduler_thread_s": {
            "sched.tick": tick_s, **table["loop_s"]},
        "tick_ms": 1e3 * tick_s / n,
        "tick_self_ms": 1e3 * phases.get("sched.tick", {}).get(
            "self_s", 0.0) / n,
        "tick_cpu_ms": 1e3 * phases.get("sched.tick", {}).get(
            "cpu_s", 0.0) / n,
        "tick_children_sum_ms": children_ms,
        "tick_children_ms": {k: round(1e3 * v / n, 3)
                             for k, v in sorted(child_s.items(),
                                                key=lambda kv: -kv[1])},
        "commit_ms": {name: 1e3 * total(name) / n
                      for name in ("sched.commit", "commit.lock_wait",
                                   "commit.apply", "commit.publish")},
        "lock_wait_s_by_holder": lock_by_holder,
        "thread_cpu_s": dict(sorted(threads.items(),
                                    key=lambda kv: -kv[1])),
        "dropped_spans": (doc.get("otherData") or {}).get(
            "dropped_spans"),
        "largest_service": [largest, deploys.get(largest)],
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python scripts/servedpath_trace.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=51.0)
    p.add_argument("--out", required=True,
                   help="path prefix of the files to write")
    p.add_argument("--kept", action="store_true",
                   help="a cell kept as data files, not in BENCHMARK.json")
    p.add_argument("--cpu", action="store_true",
                   help="rehearse on the forced CPU at a test's size")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    rehearsal = None
    if args.cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.config.update("jax_platforms", "cpu")
    if args.kept or args.cpu:
        # the tests' table of kept cells and test sizes; importing it
        # forces the CPU, which only --cpu wants
        platforms = os.environ.get("JAX_PLATFORMS")
        import rehearse_cells
        if not args.cpu:
            if platforms is None:
                del os.environ["JAX_PLATFORMS"]
            else:
                os.environ["JAX_PLATFORMS"] = platforms
        from benchmark.control import Rehearsal
        # a cell's cut to a test's size is its own file, so a new cell
        # is traced here without an edit
        rehearsal = Rehearsal(
            None, rehearse_cells.load_shrinks(ROOT)[args.workload]
            if args.cpu else None, require_tpu=not args.cpu,
            cell=rehearse_cells.KEPT.get(args.workload))
    from benchmark import harness
    from swarmkit_tpu.obs import tracer
    code, line = harness.run_cell(args.workload, args.seed, args.seconds,
                                  True, t_start=T_START,
                                  rehearsal=rehearsal)
    if line is None:
        return code
    print(json.dumps(line), flush=True)
    doc = tracer.to_chrome()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out + ".trace.json", "w") as f:
        json.dump(doc, f, separators=(",", ":"))
    print("servedpath " + json.dumps(summary(doc)), flush=True)
    return code


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the stopped manager and agents must not hold the
    # exit; every loop was told to stop and joined in run_cell
    os._exit(code)
