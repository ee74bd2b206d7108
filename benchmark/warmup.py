"""Warm-up: every jit signature the cell's traffic can meet, enumerated
from the planner's bucket ladders and driven once each through the served
path, so that the measured window compiles nothing.

A signature is named as the planner names it in its compile ledger
(``ops/planner.py _bucket_label``, ``ops/fusedbatch.py
FusedRun.bucket_label``, ``ops/streaming.py``): node bucket, constraint
and platform slots, spread leaf bucket and depth, strategy id; for a
fused chunk its group slots ``g`` and service slots ``s``; for the
resident scatter its dirty-row bucket ``d``.

Which fused runs can form: a run is two or more consecutive fusable
groups in one tick (no multi-level spread tree, each above the device
break-even).  The open-loop mix cycles its shapes service by service, so
a run is a stretch of the cycle between two unfusable shapes; the
closed-loop clients hold one shape each, so a run is any two or more of
the fusable ones.

To put a chosen run into one tick the warm-up widens the scheduler's
debounce (``Scheduler.debounce_gap`` / ``max_latency``, its constructor's
own parameters) while it creates the stack, so the tick waits for the
orchestrator to finish; the configuration's constants are restored
before the window opens.
"""

from __future__ import annotations

import itertools
import time
from typing import Dict, List, Tuple


def _ladders() -> dict:
    """The planner's own ladders and bucket functions, read from the
    program at run time (a change to a ladder there changes what is
    warmed up here).  Only the label formats below are the benchmark's:
    they are the kernel names the compile ledger is read by."""
    from swarmkit_tpu.ops import fusedbatch, streaming
    from swarmkit_tpu.scheduler import strategy
    return {
        "cc": lambda n: fusedbatch.bucket(n, fusedbatch.CC_BUCKETS),
        "p": lambda n: fusedbatch.bucket(n, fusedbatch.P_BUCKETS),
        "nb": fusedbatch.n_bucket, "L": fusedbatch.l_bucket,
        "pow2": fusedbatch.pow2_bucket,
        "chunks": lambda g: fusedbatch.chunk_sizes(
            g, fusedbatch.default_chunk_groups()),
        "d": tuple(streaming.D_BUCKETS),
        "sid": {strategy.SPREAD: strategy.STRAT_SPREAD,
                strategy.BINPACK: strategy.STRAT_BINPACK,
                strategy.WEIGHTED: strategy.STRAT_WEIGHTED,
                strategy.LEARNED: strategy.STRAT_LEARNED},
    }


def _leaves(nodes: List[dict], descriptors: List[str]) -> int:
    """Distinct values of the deepest spread label over the cluster's
    plain nodes (``cluster.plain_nodes``), whatever the label is."""
    key = descriptors[-1]
    if not key.startswith("node.labels."):
        raise ValueError(f"spread descriptor {key!r}: only node labels")
    label = key[len("node.labels."):]
    return len({n["labels"].get(label, "") for n in nodes})


def fusable(shape: dict) -> bool:
    return len(shape["spread_over"]) <= 1


def group_label(shape: dict, nodes: List[dict]) -> str:
    lad = _ladders()
    nb = lad["nb"](len(nodes))
    cc = lad["cc"](len(shape["constraints"]))
    p = lad["p"](max(len(shape["platforms"]), 1))
    sid = lad["sid"][shape["strategy"]]
    if sid:
        return f"nb{nb}_cc{cc}_p{p}_L1_h0_st{sid}"
    prefs = shape["spread_over"]
    if not prefs:
        return f"nb{nb}_cc{cc}_p{p}_L1_h0"
    L = lad["L"](_leaves(nodes, prefs))
    depth = len(prefs) if len(prefs) > 1 else 0
    return f"nb{nb}_cc{cc}_p{p}_L{L}_h{depth}"


def fused_labels(run: List[dict], nodes: List[dict]) -> List[str]:
    lad = _ladders()
    nb = lad["nb"](len(nodes))
    cc = max(lad["cc"](len(s["constraints"])) for s in run)
    p = max(lad["p"](max(len(s["platforms"]), 1)) for s in run)
    L = max([lad["L"](_leaves(nodes, s["spread_over"]))
             for s in run if s["spread_over"]
             and s["strategy"] == "spread"] or [1])
    mx = "_mx1" if any(s["strategy"] != "spread" for s in run) else ""
    sb = lad["pow2"](len(run))
    return sorted({f"fused_g{lad['pow2'](c)}_nb{nb}_cc{cc}_p{p}_L{L}"
                   f"_s{sb}{mx}"
                   for c in lad["chunks"](len(run)) if c})


def plan(config: dict, traffic: dict,
         nodes: List[dict]) -> Tuple[List[List[str]], List[str]]:
    """(stacks of shape names to deploy into one tick each, every
    signature name the window can meet).  ``nodes``: the cluster's
    plain nodes."""
    shapes = config["shapes"]
    names = (traffic["shapes"] if "shapes" in traffic
             else [c["shape"] for c in traffic["clients"]])
    names = list(dict.fromkeys(names))
    fus = [n for n in names if fusable(shapes[n])]
    if traffic["generator"] == "open_loop":
        # stretches of the cycle between unfusable shapes, and every
        # contiguous part of them (a tick may hold the tail or the head)
        cycle = traffic["shapes"]
        runs = set()
        doubled = cycle + cycle
        for i in range(len(cycle)):
            for g in range(2, len(cycle) + 1):
                part = doubled[i:i + g]
                if all(fusable(shapes[n]) for n in part):
                    runs.add(tuple(part))
    else:
        runs = {c for g in range(2, len(fus) + 1)
                for c in itertools.combinations(fus, g)}
    stacks, labels, seen = [], set(), set()
    for run in sorted(runs, key=lambda r: (len(r), r)):
        sig = tuple(fused_labels([shapes[n] for n in run], nodes))
        if sig not in seen:
            seen.add(sig)
            stacks.append(list(run))
            labels.update(sig)
    for n in names:
        label = group_label(shapes[n], nodes)
        if label not in labels:
            labels.add(label)
            stacks.append([n])
    lad = _ladders()
    nb = lad["nb"](len(nodes))
    labels.update(f"stream_nb{nb}_d{d}" for d in lad["d"])
    return stacks, sorted(labels)


def drive(bench, stacks: List[List[str]], hold_gap_s: float = 0.3) -> Dict:
    """Deploy each stack into one held tick.  ``bench`` is the running
    ``harness.Served``; returns what the warm-up cost and made."""
    sched, planner = bench.scheduler, bench.planner
    t0 = time.perf_counter()
    made = 0
    # a first small deploy: runs the planner's launch probe, and leaves
    # a handful of dirty rows for the smallest scatter bucket
    first = bench.traffic["shapes"][0] if "shapes" in bench.traffic \
        else bench.traffic["clients"][0]["shape"]
    made += bench.deploy_and_wait(f"warm-first", [(first, 8)])
    overhead = planner._launch_overhead or 0.0
    break_even = int(0.8 * overhead / planner.host_cost_per_task) + 1
    k = max(256, 2 * break_even)
    from swarmkit_tpu.obs import devicetelemetry
    shapes, nodes = bench.config["shapes"], bench.nodes
    retries = 0
    sched.debounce_gap, sched.max_latency = hold_gap_s, 60.0
    try:
        for i, stack in enumerate(stacks):
            want = (fused_labels([shapes[n] for n in stack], nodes)
                    if len(stack) > 1
                    else [group_label(shapes[stack[0]], nodes)])
            for attempt in range(4):
                made += bench.deploy_and_wait(
                    f"warm-{i:02d}-{attempt}", [(name, k) for name in stack])
                have = devicetelemetry.compile_cache_snapshot()
                if all(label in have for label in want):
                    break
                # the stack did not meet in one tick (a commit of
                # something else let the tick go early): hold longer
                retries += 1
                sched.debounce_gap = min(sched.debounce_gap * 2, 2.0)
            sched.debounce_gap = hold_gap_s
        # dirty-row counts for the middle scatter bucket
        made += bench.deploy_and_wait("warm-d256", [(first, 200)])
        made += bench.deploy_and_wait("warm-last", [(first, 8)])
    finally:
        sched.debounce_gap = bench.config["manager"][
            "scheduler_debounce_gap_s"]
        sched.max_latency = bench.config["manager"][
            "scheduler_max_latency_s"]
    return {"seconds": time.perf_counter() - t0, "tasks": made,
            "retries": retries,
            "replicas_per_service": k, "launch_overhead_s": overhead,
            "break_even_tasks": break_even}
