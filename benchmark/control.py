#!/usr/bin/env python3
"""The controls of a cell, on the chip at the cell's own size:

    python benchmark/control.py --workload <name> --seeds 1,2,3 [--seconds <s>]

For each seed, in one process (the compiled programs are shared): a sound
run of the cell with a short window, which gives the lower reading of
every number compared; the plain reference put in the program's place
with one stated guarantee broken (``overcommit``, ``constraint``,
``pile``), placing the very services that run acknowledged; and the
program itself with the timed path broken underneath (``host_route``: the
device path switched off, the program's own lower path; ``answer_altered``
and ``group_on_one_node``: the plan altered where it is fetched).  Every
control has to come out not correct.  The benchmark's own runs never run
this; ``tests/benchmark`` keeps the same controls at a test's size.
"""

import argparse
import json
import logging
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

REFERENCE_FAULTS = ("overcommit", "constraint", "pile")
PROGRAM_FAULTS = ("host_route", "answer_altered", "group_on_one_node")


class Rehearsal:
    """What a control, the sweep or a test changes about a run of
    ``harness.run_cell``; the command passes none.

    ``fault``: the timed path broken underneath the run, one of
    ``PROGRAM_FAULTS``.  ``shrink``: overrides of the configuration's
    ``cluster`` and of the traffic's parameters.  ``require_tpu=False``
    skips the harness's look for a chip (the tests' CPU rehearsals).
    ``cell``: a ``workloads`` entry that ``BENCHMARK.json`` does not
    list, for a cell kept as data files until it is steady enough to be
    judged.  ``acked`` is filled with the services the run acknowledged."""

    def __init__(self, fault=None, shrink=None, require_tpu=True,
                 cell=None):
        if fault is not None and fault not in PROGRAM_FAULTS:
            raise ValueError(f"unknown fault {fault!r}")
        self.fault, self.shrink = fault, shrink
        self.require_tpu, self.cell = require_tpu, cell
        self.acked = {}

    def plant(self, served):
        """Break the timed path under ``served`` (the running
        ``harness.Served``) once it is warm.  Returns what undoes it."""
        if self.fault is None:
            return lambda: None
        if self.fault == "host_route":
            # the device path switched off: every group rides the host
            served.planner.breaker._trip()
            return lambda: None
        import numpy as np
        from swarmkit_tpu.ops import kernel as kernel_mod
        from swarmkit_tpu.ops import planner as planner_mod
        inner, fault = kernel_mod.fetch_plan, self.fault

        def altered(arrays):
            out = inner(arrays)
            x = np.array(out[0])
            for row in x.reshape(-1, x.shape[-1]):
                if not row.any():
                    continue
                if fault == "answer_altered":
                    # every node's answer moves to the next node
                    at = np.flatnonzero(row)
                    moved = row[at]
                    row[:] = 0
                    np.add.at(row, np.minimum(at + 1, at.max()), moved)
                else:
                    # the whole group lands on the node that got most
                    total, top = row.sum(), row.argmax()
                    row[:] = 0
                    row[top] = total
            return (x,) + tuple(out[1:])
        planner_mod.fetch_plan = altered
        return lambda: setattr(planner_mod, "fetch_plan", inner)


def over(compared: dict) -> dict:
    return {k: n for k, (n, lim) in compared.items() if n > lim}


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=10.0)
    p.add_argument("--faults", default=",".join(PROGRAM_FAULTS))
    args = p.parse_args()
    logging.basicConfig(level=logging.ERROR, stream=sys.stderr)
    from benchmark import cluster, harness, reference
    bench = harness.load_benchmark()
    cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
    config = cluster.load_config(cell["config"])
    bad = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        sound = Rehearsal()
        code, line = harness.run_cell(args.workload, seed, args.seconds,
                                      False, t_start=time.perf_counter(),
                                      rehearsal=sound)
        acked = sound.acked
        if code:
            return code
        print("control sound " + json.dumps(
            {"seed": seed, "correct": line["correct"],
             "compared": line["compared"]}), flush=True)
        bad += not line["correct"]
        # the reference in the program's place, one guarantee broken,
        # on the services this run acknowledged
        nodes = cluster.plain_nodes(config["cluster"], seed)
        services = [{"id": sid, "shape": config["shapes"][rec["shape"]],
                     "replicas": rec["replicas"]}
                    for sid, rec in acked.items()]
        for fault in (None,) + REFERENCE_FAULTS:
            t = time.perf_counter()
            result = reference.compare(
                nodes, services, reference.place(nodes, services, fault))
            print("control reference " + json.dumps(
                {"seed": seed, "fault": fault, "correct": result["correct"],
                 "over": over(reference.compared_line(result)),
                 "seconds": round(time.perf_counter() - t, 1)}),
                flush=True)
            bad += result["correct"] != (fault is None)
        for fault in [f for f in args.faults.split(",") if f]:
            code, line = harness.run_cell(
                args.workload, seed, args.seconds, False,
                t_start=time.perf_counter(), rehearsal=Rehearsal(fault))
            print("control program " + json.dumps(
                {"seed": seed, "fault": fault,
                 "correct": None if line is None else line["correct"],
                 "over": None if line is None else over(line["compared"])}),
                flush=True)
            bad += line is None or line["correct"]
    print(f"control verdict: {'every control failed and every sound run passed' if not bad else f'{bad} readings on the wrong side'}",
          flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    os._exit(code)
