#!/usr/bin/env python3
"""The rate sweep that fixes an open-loop cell's offered rate: one run of
the cell at one offered task rate, as the command makes it but with the
traffic file's ``tasks_per_s`` replaced.

    python benchmark/sweep.py --workload <name> --rate <tasks/s> --seconds <s> [--seed <n>]

Run it once per rate, each in a process of its own.  The highest rate at
which ``decisions_per_s`` still equals the offered rate, the backlog at
the close stays small and the second half of the window waits no longer
than the first is the sustained rate; four fifths of it goes into the
traffic file as ``tasks_per_s`` and the sweep into ``PERF.md``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rate", type=float, required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args()
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    from benchmark import harness
    from benchmark.control import Rehearsal
    code, line = harness.run_cell(
        args.workload, args.seed, args.seconds, False, t_start=T_START,
        rehearsal=Rehearsal(
            shrink={"traffic": {"tasks_per_s": args.rate}}))
    if line is not None:
        print("sweep " + json.dumps({
            "rate": args.rate, "correct": line["correct"],
            "attempted": line["attempted"], "failed": line["failed"],
            **{k: v["value"] for k, v in line["metrics"].items()}}),
            flush=True)
    return code


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
