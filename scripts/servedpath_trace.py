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
``sched.commit``, the update lock's waits by holder and by waiter, the
legs of a deploy before the tick (``before_the_tick_ms``: the RPC, the
orchestrator's and the allocator's wait and work, each with what its
thread waited for the lock and spent off the CPU, as means over spans
and, weighted by the tasks a span carried, as means over tasks; with the
client's side of the same path at the median: the generator's lateness
and the lag to PENDING), the traced window's own ``assign_p50_ms`` (a
``--trace 1`` line has the per-layer metrics alone), the CPU seconds by
thread and by the thread's family (``obs/threadcpu.py``), what the
accountant's snapshots cost (``threadcpu``), and the largest service of
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
import statistics  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tests", "benchmark"))


def _mean(rows: list, weights: list = None):
    """Mean of ``rows``, weighted where ``weights`` are given; None of
    nothing."""
    weights = [1] * len(rows) if weights is None else weights
    total = sum(weights)
    return sum(r * w for r, w in zip(rows, weights)) / total \
        if total else None


def before_the_tick(events: list) -> dict:
    """A deploy's legs from ``create_service`` to PENDING, in ms: for
    each of the three threads its spans' mean duration (``ms``), the age
    of what it took up (``wait_ms``, and the oldest, ``wait_max_ms``),
    what it waited for the update lock and spent off the CPU; means over
    spans, as the per-layer metrics read them, and ``*_task_ms``, the same
    weighted by the tasks a span carried."""
    def leg(name: str, wait: str = None, tasks: str = None) -> dict:
        rows = [e for e in events if e["name"] == name]
        dur = [e["dur"] / 1e3 for e in rows]
        out = {"spans": len(rows), "ms": _mean(dur)}
        for arg in ("lock_wait_ms", "offcpu_ms"):
            out[arg] = _mean([e["args"][arg] for e in rows
                              if arg in e["args"]])
        if wait:
            # a span that several tasks share says how many it carried
            ages = [e["args"].get(wait, 0.0) for e in rows]
            n = [e["args"].get(tasks, 0) for e in rows]
            out.update(
                wait_ms=_mean(ages),
                wait_max_ms=max((e["args"].get("wait_max_ms", a)
                                 for e, a in zip(rows, ages)), default=None),
                tasks=sum(n), task_ms=_mean(dur, n),
                wait_task_ms=_mean(ages, n))
        return out
    return {"api": leg("api.create_service"),
            "orchestrator": leg("orchestrator.service", "wait_ms",
                                "created"),
            "allocator": leg("allocator.tasks", "wait_mean_ms", "tasks")}


def summary(doc: dict, series: dict = None) -> dict:
    """The ``servedpath`` line's object, from the tracer's document;
    ``series``: what the harness's clients recorded of the window
    (``Observations.series``), for the client's side of the legs."""
    from benchmark.readers import percentile
    from swarmkit_tpu.obs.report import phase_table, thread_names, x_events
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
    thread_of = thread_names(doc)
    lock_by_holder, lock_by_waiter = {}, {}
    for e in events:
        if e["name"] == "store.lock_wait":
            who = e["args"].get("holder") or "(free)"
            lock_by_holder[who] = lock_by_holder.get(who, 0.0) \
                + e["dur"] / 1e6
            who = re.sub(r"-\d+$", "", thread_of.get(e["tid"], "?"))
            lock_by_waiter[who] = lock_by_waiter.get(who, 0.0) \
                + e["dur"] / 1e6
    from swarmkit_tpu.obs import threadcpu
    by_family = {}
    for name, used in ((doc.get("otherData") or {}).get("thread_cpu_s")
                       or {}).items():
        fam = threadcpu.family(name)
        by_family[fam] = round(by_family.get(fam, 0.0) + used, 6)
    legs = before_the_tick(events)
    series = series or {}
    for key, name in (("generator_late_p50_ms", "generator_late_s"),
                      ("pending_lag_p50_ms", "pending_lag_s")):
        if series.get(name):
            legs[key] = 1e3 * statistics.median(series[name])
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
        "lock_wait_s_by_waiter": lock_by_waiter,
        "before_the_tick_ms": legs,
        # as the untraced run's line has it (nearest rank)
        "assign_p50_ms": 1e3 * percentile(series["assign_s"], 50)
        if series.get("assign_s") else None,
        "thread_cpu_s": dict(sorted(threads.items(),
                                    key=lambda kv: -kv[1])),
        "thread_cpu_s_by_family": dict(sorted(by_family.items(),
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
    from swarmkit_tpu.obs import threadcpu, tracer
    # what ``run_cell`` hands the readers and does not return: the
    # clients' series of the window
    observed, read_all = [], harness.readers.read_all

    def tapped(cell, obs, per_layer):
        observed.append(obs)
        return read_all(cell, obs, per_layer)
    harness.readers.read_all = tapped
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
    made = summary(doc, observed[-1].series if observed else None)
    # the accountant's own cost over the traced window
    made["threadcpu"] = dict(threadcpu.stats)
    print("servedpath " + json.dumps(made), flush=True)
    return code


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the stopped manager and agents must not hold the
    # exit; every loop was told to stop and joined in run_cell
    os._exit(code)
