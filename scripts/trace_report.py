"""Summarize or diff Chrome trace-event JSON as per-phase tables.

Usage:
    python scripts/trace_report.py bench_trace.json
    python scripts/trace_report.py bench_trace.json --validate
    python scripts/trace_report.py sim_trace.json --json
    python scripts/trace_report.py --diff A.json B.json
    python scripts/trace_report.py --critical-path BENCH_ART.json
    python scripts/trace_report.py --device BENCH_ART.json
    python scripts/trace_report.py trace.json --service SERVICE_ID

Works on any trace the obs tracer emits: ``bench.py``'s BENCH_TRACE_OUT,
``python -m swarmkit_tpu.sim --trace-json``, or a ``/debug/trace``
download.  When the trace carries ``bench.config`` marker spans, a table
is printed per config; otherwise one table covers the whole trace.
``--validate`` schema-checks the document and exits non-zero on problems
(the tier-1 smoke test runs exactly this check in-process).
``--diff A B`` prints a side-by-side phase table with per-phase total_s
deltas (A = baseline, B = candidate), matched per config window where
both traces carry the same ``bench.config`` markers — the same
``obs/report.py`` aggregation the bench artifact embeds.
``--critical-path ART`` takes a bench ARTIFACT (not a trace): it joins
the task-journey attribution of time-to-running p99 with the per-plane
saturation windows and prints one row per plane — which plane owns the
slow tail, and whether that plane's occupancy/backlog corroborates it.
Exits 1 when the attribution is missing, empty, or does not account
for ~100% of the tail (the CI wiring keys on that).
``--service ID`` follows one deploy: every span that carries the service
id (or nests under one that does), from ``api.create_service`` to
``commit.publish``, in start order with its thread.  The plain table
also prints ``self_s`` and ``cpu_s`` per phase, the scheduler loop's time
between ticks (``sched.idle`` / ``sched.debounce`` / ``sched.events``,
which are not tick time) and the trace's ``thread_cpu_s``: the CPU
seconds each thread used while the tracer was on.
``--device ART`` also takes a bench artifact: it renders the device
telemetry ledger (kernel rows per compile bucket joined with the device
plane's occupancy window, per-reason transfer bytes, the compile-cache
ledger, memory watermarks, donation balance).  Exits 1 when the
artifact predates the ledger.
"""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from swarmkit_tpu.obs.report import (  # noqa: E402
    config_windows, device_table, diff_phase_tables, follow_service,
    format_device_table, format_diff, format_table, phase_table,
    validate_chrome_trace, x_events,
)


def _load(path):
    with open(path) as f:
        return json.load(f)


def _tables(doc):
    windows = config_windows(doc)
    if not windows:
        windows = [("all", None)]
    return {name: phase_table(doc, window=w) for name, w in windows}


def _run_diff(path_a: str, path_b: str, as_json: bool) -> int:
    doc_a, doc_b = _load(path_a), _load(path_b)
    ta, tb = _tables(doc_a), _tables(doc_b)
    only_a = sorted(set(ta) - set(tb))
    only_b = sorted(set(tb) - set(ta))
    names = [n for n in ta if n in tb]
    matched = {}
    if names:
        matched = {n: (ta[n], tb[n]) for n in names}
    else:
        # no shared config windows: diff whole-trace tables (and still
        # report the disjoint config sets below — that mismatch is the
        # headline when it happens)
        matched = {"all": (phase_table(doc_a), phase_table(doc_b))}
        names = ["all"]
    diffs = {name: diff_phase_tables(a, b)
             for name, (a, b) in matched.items()}
    if as_json:
        print(json.dumps(diffs, indent=2, sort_keys=True))
        return 0
    print(f"A = {path_a}\nB = {path_b}\n")
    for name in names:
        print(f"=== {name} ===")
        print(format_diff(diffs[name]))
        print()
    if only_a:
        print(f"configs only in A: {', '.join(only_a)}")
    if only_b:
        print(f"configs only in B: {', '.join(only_b)}")
    return 0


def _load_artifact(path):
    """A saved bench artifact may carry log noise before the JSON line;
    take the last line that parses (bench_compare discipline)."""
    with open(path) as f:
        text = f.read().strip()
    for line in reversed(text.splitlines()):
        line = line.strip()
        if not line:
            continue
        try:
            return json.loads(line)
        except ValueError:
            continue
    raise SystemExit(f"{path}: no JSON document found")


def _run_critical_path(path: str, as_json: bool) -> int:
    """Join the artifact's journey attribution with its plane windows:
    one row per plane of the time-to-running p99 tail.  Non-zero exit
    on malformed or empty attribution — ci_check.sh runs this against
    the fast bench config as the observability smoke gate."""
    art = _load_artifact(path)
    attr = art.get("journey_attribution")
    planes = art.get("planes") or {}
    problems = []
    e2e = art.get("e2e_time_to_running")
    if not isinstance(attr, dict) and isinstance(e2e, dict) \
            and str(e2e.get("error", "")).startswith("skipped:"):
        # the e2e config self-skipped for an environmental reason (no
        # `cryptography` for the manager's CA bootstrap): there is no
        # attribution to judge, which is not an observability failure
        msg = (f"critical-path: e2e config was skipped "
               f"({e2e['error']}); nothing to attribute")
        if as_json:
            print(json.dumps({"source": path, "skipped": e2e["error"],
                              "attribution": None, "problems": []},
                             indent=2, sort_keys=True))
        else:
            print(msg, file=sys.stderr)
        return 0
    if not isinstance(attr, dict):
        problems.append("artifact carries no journey_attribution "
                        "(bench ran without the e2e config, or "
                        "journeys were disabled)")
        attr = {}
    by_plane = attr.get("planes") or {}
    if not problems and not attr.get("cohort"):
        problems.append("attribution cohort is empty — no complete "
                        "created->running journeys were sampled")
    if not problems and not by_plane:
        problems.append("attribution has a cohort but no per-plane "
                        "rows")
    frac_sum = sum(float(r.get("frac") or 0.0)
                   for r in by_plane.values())
    if not problems and abs(frac_sum - 1.0) > 0.02:
        problems.append(f"per-plane fractions sum to {frac_sum:.4f}, "
                        "not ~1.0 — the edges no longer partition the "
                        "journey interval")
    doc = {"source": path, "attribution": attr,
           "plane_windows": planes, "frac_sum": round(frac_sum, 6),
           "problems": problems}
    if as_json:
        print(json.dumps(doc, indent=2, sort_keys=True))
        return 1 if problems else 0
    if problems:
        for pr in problems:
            print(f"critical-path: {pr}", file=sys.stderr)
        return 1
    print(f"time-to-running p{int(attr['p'] * 100)} critical path "
          f"({attr['cohort']} tail task(s) of {attr['tasks']} "
          f"complete, {attr['total_s']:.4f}s attributed)")
    hdr = (f"{'plane':<12} {'seconds':>10} {'frac':>7} "
           f"{'occupancy':>10} {'depth':>7} {'oldest_s':>9} "
           f"{'drops':>6}")
    print(hdr)
    order = sorted(by_plane, key=lambda pl: -by_plane[pl]["seconds"])
    for pl in order:
        row = by_plane[pl]
        w = planes.get(pl) or {}
        print(f"{pl:<12} {row['seconds']:>10.4f} "
              f"{row['frac'] * 100:>6.1f}% "
              f"{w.get('occupancy', 0.0):>10.4f} "
              f"{w.get('queue_depth', 0.0):>7.0f} "
              f"{w.get('oldest_age_s', 0.0):>9.3f} "
              f"{w.get('drops', 0):>6d}")
    spectators = sorted(set(planes) - set(by_plane))
    if spectators:
        print(f"planes with no tail share: {', '.join(spectators)}")
    return 0


def _run_device(path: str, as_json: bool) -> int:
    """Render a bench artifact's device-telemetry ledger: kernel rows
    joined with the device plane's occupancy window, per-reason
    transfer bytes, compile-cache ledger, watermarks, donation
    balance.  Exits 1 when the artifact predates the ledger."""
    art = _load_artifact(path)
    table = device_table(art)
    if table is None:
        print(f"{path}: artifact carries no device_telemetry (bench "
              "predates the device ledger, or telemetry was disabled)",
              file=sys.stderr)
        return 1
    if as_json:
        print(json.dumps(table, indent=2, sort_keys=True))
        return 0
    print(f"device telemetry ({path})")
    print(format_device_table(table))
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python scripts/trace_report.py")
    p.add_argument("trace", nargs="+",
                   help="Chrome trace-event JSON file(s); two with "
                        "--diff; a bench artifact with --critical-path")
    p.add_argument("--validate", action="store_true",
                   help="schema-check only; exit 1 on problems")
    p.add_argument("--json", action="store_true",
                   help="emit the phase table(s) as JSON")
    p.add_argument("--diff", action="store_true",
                   help="side-by-side phase diff of two traces (A B)")
    p.add_argument("--critical-path", action="store_true",
                   help="per-plane attribution of time-to-running p99 "
                        "from a bench ARTIFACT (exit 1 when empty or "
                        "malformed)")
    p.add_argument("--service", metavar="ID",
                   help="follow one deploy: the spans that carry this "
                        "service id, in start order")
    p.add_argument("--device", action="store_true",
                   help="device-telemetry ledger from a bench ARTIFACT: "
                        "kernel rows per compile bucket + device-plane "
                        "window, per-reason transfer bytes, "
                        "compile-cache ledger (exit 1 when absent)")
    args = p.parse_args(argv)

    if args.device:
        if len(args.trace) != 1:
            p.error("--device takes exactly one bench artifact")
        return _run_device(args.trace[0], args.json)
    if args.critical_path:
        if len(args.trace) != 1:
            p.error("--critical-path takes exactly one bench artifact")
        return _run_critical_path(args.trace[0], args.json)
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

    tables = _tables(doc)
    threads = (doc.get("otherData") or {}).get("thread_cpu_s")
    if args.json:
        if threads is not None:
            tables = dict(tables, thread_cpu_s=threads)
        print(json.dumps(tables, indent=2, sort_keys=True))
        return 0
    for name, table in tables.items():
        print(f"=== {name} ===")
        print(format_table(table))
        print()
    if threads:
        print("thread CPU seconds while the tracer was on:")
        for name, used in sorted(threads.items(), key=lambda kv: -kv[1]):
            print(f"  {name:<28} {used:>10.3f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
