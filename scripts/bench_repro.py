"""Reproducibility half of ROADMAP item 1: run the headline + cfg6
bench three consecutive times and assert the bar holds on EVERY run.

Each repeat is a fresh ``bench.py`` process (clean heap, clean jit
cache) restricted to config 6 — the production-shape pipelined tick —
via BENCH_CONFIGS=6, with the e2e/obs-overhead/host-baseline extras
skipped.  Every run appends its record to BENCH_HISTORY.jsonl exactly
as a full bench run would (bench.py owns the append), so the ledger
carries all three and ``bench_compare.py --history`` can diff them.

Bar (each configurable):
  * cfg6 decisions/sec        >= --min-dps        (default 220_000)
  * cfg6 shape_cost_x         <= --max-shape-cost (default 1.5)
  * artifact plan_hidden_frac >  --min-hidden     (default 0.15; only
    enforced while the pipeline is on, i.e. pipeline_depth > 1 —
    lowered from 0.3 when the columnar commit plane shrank the commit
    wall the plan used to hide behind)
  * cfg6 commit_phase_s       <= --max-commit-s   (default 0.665 =
    0.5x the r06 commit wall, the ISSUE 13 acceptance bar)
  * cfg6 native_commit must not have fallen back to Python

Exit status: 0 when every repeat holds the bar, 1 otherwise.

One process per chip: this parent never imports JAX, and the bench
children run one at a time, each holding the chip alone.  Keep it so —
a parent that touched JAX would hold the chip its children need.

Usage:
    python scripts/bench_repro.py              # 3 repeats, full bar
    python scripts/bench_repro.py --repeat 5
    python scripts/bench_repro.py --min-dps 0  # record-only mode
"""

import argparse
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG6 = "6_live_manager_2x100k_x_10k"


def run_once(extra_env):
    env = dict(os.environ)
    env.update({
        "BENCH_CONFIGS": "6",
        "BENCH_SKIP_E2E": "1",
        "BENCH_SKIP_OBS": "1",
        "BENCH_SKIP_HOST": "1",
    })
    env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        cwd=REPO, env=env, capture_output=True, text=True)
    if proc.returncode != 0:
        raise SystemExit(
            f"bench.py failed (rc={proc.returncode}):\n{proc.stderr[-2000:]}")
    for line in reversed(proc.stdout.strip().splitlines()):
        line = line.strip()
        if not line:
            continue
        try:
            return json.loads(line)
        except ValueError:
            continue
    raise SystemExit("bench.py produced no JSON artifact")


def check(artifact, args):
    """Returns (summary row dict, list of violation strings)."""
    cfg6 = (artifact.get("configs") or {}).get(CFG6) or {}
    dps = cfg6.get("decisions_per_sec") or 0.0
    shape = cfg6.get("shape_cost_x")
    hidden = artifact.get("plan_hidden_frac", 0.0)
    depth = artifact.get("pipeline_depth", 1)
    commit_s = cfg6.get("commit_phase_s")
    native = cfg6.get("native_commit") or {}
    problems = []
    if dps < args.min_dps:
        problems.append(f"cfg6 {dps:,.0f} dec/s < {args.min_dps:,.0f}")
    if shape is not None and shape > args.max_shape_cost:
        problems.append(f"shape_cost_x {shape} > {args.max_shape_cost}")
    if depth > 1 and hidden <= args.min_hidden:
        problems.append(
            f"plan_hidden_frac {hidden} <= {args.min_hidden} with the "
            f"pipeline on (depth {depth})")
    if commit_s is not None and commit_s > args.max_commit_s:
        problems.append(
            f"cfg6 commit_phase_s {commit_s} > {args.max_commit_s}")
    if native.get("enabled") and (not native.get("active")
                                  or native.get("fallbacks")):
        problems.append(
            f"native commit plane fell back to Python ({native})")
    row = {"device": artifact.get("device"),
           "headline": artifact.get("value"), "cfg6_dps": dps,
           "shape_cost_x": shape, "plan_hidden_frac": hidden,
           "pipeline_depth": depth, "commit_phase_s": commit_s,
           "native_commit": native}
    return row, problems


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python scripts/bench_repro.py")
    p.add_argument("--repeat", type=int, default=3,
                   help="consecutive bench runs (default 3)")
    p.add_argument("--min-dps", type=float, default=220_000,
                   help="cfg6 decisions/sec floor (default 220000)")
    p.add_argument("--max-shape-cost", type=float, default=1.5,
                   help="cfg6 shape_cost_x ceiling (default 1.5)")
    p.add_argument("--min-hidden", type=float, default=0.15,
                   help="plan_hidden_frac floor while pipelined "
                        "(default 0.15; was 0.3 before the columnar "
                        "commit plane — a 3x-smaller commit phase "
                        "leaves less wall to hide the plan behind, so "
                        "the overlap fraction legitimately shrank "
                        "while the tick got strictly faster)")
    p.add_argument("--max-commit-s", type=float, default=0.665,
                   help="cfg6 commit_phase_s ceiling (default 0.665 = "
                        "0.5x the r06 commit wall — the ISSUE 13 "
                        "acceptance bar)")
    args = p.parse_args(argv)

    failures = 0
    for i in range(args.repeat):
        artifact = run_once({})
        row, problems = check(artifact, args)
        status = "ok" if not problems else "FAIL"
        print(f"run {i + 1}/{args.repeat}: {status}  "
              f"device={row['device']}  "
              f"cfg6={row['cfg6_dps']:,.0f} dec/s  "
              f"shape_cost_x={row['shape_cost_x']}  "
              f"plan_hidden_frac={row['plan_hidden_frac']}  "
              f"commit_phase_s={row['commit_phase_s']}  "
              f"depth={row['pipeline_depth']}")
        for prob in problems:
            print(f"  - {prob}", file=sys.stderr)
        failures += bool(problems)
    if failures:
        print(f"\n{failures}/{args.repeat} runs failed the bar",
              file=sys.stderr)
        return 1
    print(f"\nok: the bar held on all {args.repeat} consecutive runs")
    return 0


if __name__ == "__main__":
    sys.exit(main())
