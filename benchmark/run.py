#!/usr/bin/env python3
"""One process, one cell, once:

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints the device first and refuses anything but a TPU; builds the
cluster from the seed, starts a standalone ``Manager()`` and the
configuration's agents, warms up, measures for ``--seconds``, holds what
the window produced to the plain reference, and prints one last line of
JSON: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` in a traced run, and last ``compared``, each number
compared beside its limit.  ``--trace 0`` gives the end-to-end metrics
with the program's tracer and the profiler off; ``--trace 1`` the
per-layer metrics.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python benchmark/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    # the program comes first: in a directory that holds only the
    # benchmark's own files this import fails, and there is no result
    import swarmkit_tpu  # noqa: F401
    from benchmark import harness
    code, line = harness.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), t_start=T_START)
    if line is not None:
        print(json.dumps(line), flush=True)
    return code


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # daemon threads of the stopped manager and agents must not hold the
    # exit; every loop was told to stop and joined above
    os._exit(code)
