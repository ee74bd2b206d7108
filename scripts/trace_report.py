"""Summarize or diff Chrome trace-event JSON as per-phase tables.

Usage:
    python scripts/trace_report.py trace.json
    python scripts/trace_report.py trace.json --validate
    python scripts/trace_report.py sim_trace.json --json
    python scripts/trace_report.py --diff A.json B.json
    python scripts/trace_report.py trace.json --service SERVICE_ID

Works on any trace the obs tracer emits: the ``.trace.json`` of
``scripts/servedpath_trace.py``, ``python -m swarmkit_tpu.sim
--trace-json``, or a ``/debug/trace`` download.  One table covers the
whole trace.
``--validate`` schema-checks the document and exits non-zero on problems
(the tier-1 tests run exactly this check in-process).
``--diff A B`` prints a side-by-side phase table with per-phase total_s
deltas (A = baseline, B = candidate): the ``obs/report.py`` aggregation
the tests assert on.
``--service ID`` follows one deploy: every span that carries the service
id (or nests under one that does), from ``api.create_service`` through
``orchestrator.service`` and the allocator's batches (``allocator.tasks``,
where the service's task is the batch's first) to ``commit.publish``, in
start order with its thread; the first three carry what their thread
waited for the update lock (``lock_wait_ms``), what it spent off the CPU
(``offcpu_ms``) and the age of what it took up.  The plain table
also prints ``self_s`` and ``cpu_s`` per phase, the scheduler loop's time
between ticks (``sched.idle`` / ``sched.debounce`` / ``sched.events``,
which are not tick time) and the trace's ``thread_cpu_s``: the CPU
seconds each thread used while the tracer was on.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from swarmkit_tpu.obs.report import (  # noqa: E402
    diff_phase_tables, follow_service, format_diff, format_table,
    phase_table, validate_chrome_trace, x_events,
)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _run_diff(path_a: str, path_b: str, as_json: bool) -> int:
    diff = diff_phase_tables(phase_table(_load(path_a)),
                             phase_table(_load(path_b)))
    if as_json:
        print(json.dumps(diff, indent=2, sort_keys=True))
        return 0
    print(f"A = {path_a}\nB = {path_b}\n")
    print(format_diff(diff))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python scripts/trace_report.py")
    p.add_argument("trace", nargs="+",
                   help="Chrome trace-event JSON file(s); two with "
                        "--diff")
    p.add_argument("--validate", action="store_true",
                   help="schema-check only; exit 1 on problems")
    p.add_argument("--json", action="store_true",
                   help="emit the phase table as JSON")
    p.add_argument("--diff", action="store_true",
                   help="side-by-side phase diff of two traces (A B)")
    p.add_argument("--service", metavar="ID",
                   help="follow one deploy: the spans that carry this "
                        "service id, in start order")
    args = p.parse_args(argv)

    if args.diff:
        if len(args.trace) != 2:
            p.error("--diff takes exactly two trace files")
        return _run_diff(args.trace[0], args.trace[1], args.json)
    if len(args.trace) != 1:
        p.error("pass one trace file (or two with --diff)")

    doc = _load(args.trace[0])

    problems = validate_chrome_trace(doc)
    if args.validate:
        for pr in problems:
            print(pr, file=sys.stderr)
        print(f"{args.trace[0]}: "
              f"{'INVALID' if problems else 'ok'} "
              f"({len(x_events(doc))} spans)")
        return 1 if problems else 0
    if problems:
        print(f"warning: {len(problems)} schema problems "
              f"(run --validate)", file=sys.stderr)

    if args.service:
        rows = follow_service(doc, args.service)
        if args.json:
            print(json.dumps(rows, indent=2, sort_keys=True))
            return 0 if rows else 1
        for r in rows:
            print(f"{r['ts'] / 1e3:>12.3f}ms {r['dur'] / 1e3:>10.3f}ms "
                  f"{r['thread']:<16} {r['name']:<24} {r['args']}")
        return 0 if rows else 1

    table = phase_table(doc)
    threads = (doc.get("otherData") or {}).get("thread_cpu_s")
    if args.json:
        if threads is not None:
            table = dict(table, thread_cpu_s=threads)
        print(json.dumps(table, indent=2, sort_keys=True))
        return 0
    print(format_table(table))
    print()
    if threads:
        print("thread CPU seconds while the tracer was on:")
        for name, used in sorted(threads.items(), key=lambda kv: -kv[1]):
            print(f"  {name:<28} {used:>10.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
