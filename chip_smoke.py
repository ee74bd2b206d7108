#!/usr/bin/env python3
"""On-chip smoke: the served scheduling path on one TPU, unable to hide
the device.

One process, one chip, from a plain copy of the repo (no git, no
network).  It prints the device JAX found and stops there unless that is
a TPU.  Then:

* programs — every single-device program of the planner is compiled and
  run once at the 10k-node bucket and compared with the oracle the tests
  use for it (the host scheduler, or the program's numpy twin);
* served — ``Manager()`` standalone, 10,000 READY nodes of which a few
  are served by real ``Agent``s, and four services totalling 100,000
  replicas created back to back through ``control_api.create_service``;
  every task must come back ASSIGNED to a node that may hold it, every
  task on an agent node RUNNING;
* no retreat — every production degradation (breaker, host fallbacks,
  resident-state and native-plane fallbacks) that would let the run
  finish without the device is a failure here.

Exit 0 and a last stdout line ``{"ok": true, "device": {"platform":
..., "kind": ..., "count": ...}}`` (those keys and no others; the
``summary:`` line above it carries the rest) only when every phase
passed.  Timings printed along the way are for orientation, not results.
"""

from __future__ import annotations

import argparse
import contextlib
import itertools
import json
import logging
import os
import random
import sys
import threading
import time
import traceback
from collections import Counter

import numpy as np

N_NODES = 10_000
N_AGENTS = 16
REPLICAS = 100_000
NODE_CPU = 64 * 10 ** 9
NODE_MEM = 256 << 30
ZONES, RACKS_PER_ZONE = 4, 25
#: memory-bound: 31 of these fill a 256 GiB node, with or without the
#: other services' 64 MiB tasks beside them
BINPACK = dict(cpu=0.5, mem=(8 << 30) + (200 << 20))

#: loggers of the device path and the native plane: they log an ERROR
#: exactly when they fall back, so any record here is a quiet retreat
RETREAT_LOGGERS = ("tpu-planner", "tpu-streaming", "native")


def device_info() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


class Smoke:
    """Failures, per-phase wall time and per-program results of a run."""

    def __init__(self):
        self.failures = []
        self.phase_s = {}
        self.programs = []

    def fail(self, msg: str) -> None:
        print(f"FAIL {msg}", flush=True)
        self.failures.append(msg)

    def check(self, ok, msg: str) -> bool:
        if not ok:
            self.fail(msg)
        return bool(ok)

    @contextlib.contextmanager
    def phase(self, name: str):
        """A phase that raises has failed; later phases still run so
        one report shows everything that is wrong."""
        t0 = time.perf_counter()
        try:
            yield
        except Exception:
            self.fail(f"{name}: {traceback.format_exc()}")
        finally:
            self.phase_s[name] = round(time.perf_counter() - t0, 3)
            print(f"phase {name}: {self.phase_s[name]}s", flush=True)

    @contextlib.contextmanager
    def program(self, name: str, bucket: str = ""):
        """One device program against its oracle.  The body sets
        ``row["compile_s"]`` itself when it calls the program directly;
        otherwise the planner's compile ledger says what compiling cost
        and must show a dispatch under a ``bucket``-named signature."""
        before = _compile_ledger()
        waited = _device_wait_ns()
        row = {"program": name, "ok": False}
        n_fail = len(self.failures)
        t0 = time.perf_counter()
        try:
            yield row
            row["ok"] = len(self.failures) == n_fail
        except Exception:
            self.fail(f"program {name}: {traceback.format_exc()}")
        row["wall_s"] = round(time.perf_counter() - t0, 3)
        if "compile_s" not in row:
            compiled, row["dispatched"] = _compile_ledger_growth(before)
            row["compile_s"] = round(sum(compiled.values()), 3)
            # how long the host then sat waiting for the results
            row["device_wait_s"] = round(
                (_device_wait_ns() - waited) / 1e9, 4)
            if not any(bucket in b for b in row["dispatched"]):
                self.fail(f"program {name}: no {bucket!r} dispatch "
                          f"reached the device: {row['dispatched']}")
                row["ok"] = False
        self.programs.append(row)
        print(f"program {name}: {'PASS' if row['ok'] else 'FAIL'} "
              f"compile_s={row['compile_s']} wall_s={row['wall_s']} "
              + " ".join(f"{k}={v}" for k, v in row.items()
                         if k not in ("program", "ok", "compile_s",
                                      "wall_s")), flush=True)

    def exit_code(self) -> int:
        return 1 if self.failures else 0


def _device_wait_ns() -> int:
    from swarmkit_tpu.obs import devicetelemetry
    return sum(row["d2h_ns"]
               for row in devicetelemetry.snapshot()["kernel"].values())


def _compile_ledger() -> dict:
    from swarmkit_tpu.obs import devicetelemetry
    return devicetelemetry.compile_cache_snapshot()


def _compile_ledger_growth(before: dict) -> tuple:
    """({bucket: seconds spent compiling it}, buckets dispatched) in the
    planner's compile ledger since ``before``."""
    after = _compile_ledger()

    def grew(bucket, *fields):
        was = before.get(bucket, {})
        return sum(after[bucket][f] - was.get(f, 0) for f in fields)
    return ({b: round(grew(b, "compile_ns") / 1e9, 3)
             for b in after if grew(b, "compiles")},
            sorted(b for b in after if grew(b, "hits", "misses")))


# ------------------------------------------------------------- the cluster

def make_nodes(n: int, seed: int) -> list:
    """``n`` READY nodes, 64 CPU / 256 GiB each (BASELINE.json config 4's
    node), with zone/rack/tier labels and an os/arch description.  The
    seed decides which node gets which attributes; group sizes stay
    even."""
    from swarmkit_tpu.models import (
        Annotations, Node, NodeDescription, NodeSpec, NodeState,
        NodeStatus, Platform, Resources,
    )
    order = list(range(n))
    random.Random(seed).shuffle(order)
    nodes = []
    for i, j in enumerate(order):
        zone = j % ZONES
        rack = (j // ZONES) % RACKS_PER_ZONE
        name = f"node-{i:05d}"
        nodes.append(Node(
            id=name,
            spec=NodeSpec(annotations=Annotations(name=name, labels={
                "zone": f"z{zone}", "rack": f"z{zone}-r{rack:02d}",
                "tier": ("web", "db", "cache")[j % 3]})),
            status=NodeStatus(state=NodeState.READY),
            description=NodeDescription(
                hostname=name,
                platform=Platform(
                    os="windows" if j % 10 == 9 else "linux",
                    architecture="arm64" if j % 5 == 4 else "amd64"),
                resources=Resources(nano_cpus=NODE_CPU,
                                    memory_bytes=NODE_MEM))))
    return nodes


def _labels(node) -> dict:
    return node.spec.annotations.labels


def _is_web_linux_amd64(node) -> bool:
    p = node.description.platform
    return (_labels(node)["tier"] == "web" and p.os == "linux"
            and p.architecture == "amd64")


def _any_node(node) -> bool:
    return True


def task_spec(kind: str, cpu: float = 0.1, mem: int = 64 << 20):
    """A task spec of one of the shapes the smoke places: spread |
    constrained | topology | rack (one-level spread behind a constraint)
    | binpack | weighted | learned."""
    from swarmkit_tpu.models import (
        Placement, PlacementPreference, Platform, Resources,
        ResourceRequirements, SpreadOver, TaskSpec,
    )
    from swarmkit_tpu.models.specs import ContainerSpec

    def spread_over(label):
        return PlacementPreference(spread=SpreadOver(
            spread_descriptor=f"node.labels.{label}"))

    placement = {}
    if kind == "constrained":
        placement.update(
            constraints=["node.labels.tier==web"],
            platforms=[Platform(os="linux", architecture="x86_64")])
    elif kind == "topology":
        placement.update(preferences=[spread_over("zone"),
                                      spread_over("rack")])
    elif kind == "rack":
        placement.update(preferences=[spread_over("rack")],
                         constraints=["node.labels.tier!=cache"])
    elif kind in ("binpack", "weighted", "learned"):
        placement.update(strategy=kind)
        if kind == "weighted":
            placement.update(strategy_weights={"cpu": 3, "spread": 1})
    elif kind != "spread":
        raise ValueError(kind)
    return TaskSpec(
        container=ContainerSpec(image="smoke"),
        resources=ResourceRequirements(reservations=Resources(
            nano_cpus=int(cpu * 10 ** 9), memory_bytes=mem)),
        placement=Placement(**placement))


def within_one(values, slack: int = 0) -> bool:
    values = list(values)
    return not values or max(values) - min(values) <= 1 + slack


# ---------------------------------------------- programs against oracles

def _workload(specs):
    """[(service id, n tasks, TaskSpec)] -> (services, tasks) with fixed
    ids, so twin stores compare task by task."""
    from swarmkit_tpu.models import (
        Annotations, ReplicatedService, Service, ServiceMode, ServiceSpec,
        Task, TaskState, TaskStatus, Version,
    )
    services, tasks = [], []
    for sid, count, spec in specs:
        services.append(Service(
            id=sid,
            spec=ServiceSpec(annotations=Annotations(name=sid),
                             mode=ServiceMode.REPLICATED,
                             replicated=ReplicatedService(replicas=count),
                             task=spec),
            spec_version=Version(index=1)))
        tasks.extend(
            Task(id=f"{sid}-{s:06d}", service_id=sid, slot=s + 1,
                 desired_state=TaskState.RUNNING, spec=spec,
                 spec_version=Version(index=1),
                 status=TaskStatus(state=TaskState.PENDING))
            for s in range(count))
    return services, tasks


def _one_tick(nodes, services, tasks, planner, preassigned=False):
    """A fresh store, one synchronous scheduler pass (the staging of
    tests/test_tpu_kernel.py run_schedulers and bench.one_tick)."""
    from swarmkit_tpu.models import Task, TaskState
    from swarmkit_tpu.scheduler import Scheduler
    from swarmkit_tpu.state import MemoryStore
    store = MemoryStore()

    def create(tx):
        for obj in nodes:
            tx.create(obj.copy())
        for obj in services:
            tx.create(obj)
        for obj in tasks:
            tx.create(obj.copy())
    store.update(create)
    sched = Scheduler(store, batch_planner=planner)
    store.view(sched._setup_tasks_list)
    if preassigned:
        sched._process_preassigned_tasks()
    sched.tick()
    placed = {t.id: t.node_id
              for t in store.view(lambda tx: tx.find(Task))
              if t.node_id and t.status.state >= TaskState.ASSIGNED}
    return sched, placed


def _device_planner():
    from swarmkit_tpu.ops import TPUPlanner
    planner = TPUPlanner()
    # a differential must reach the device whatever the launch costs
    planner.enable_small_group_routing = False
    return planner


def _differential(smoke, nodes, specs, *, exact, expect,
                  preassign=None):
    """Place one workload through the host oracle (no planner) and
    through the device planner; equal per-service distributions (or, for
    ``exact``, equal task by task), and the planner counters must show
    the device did it."""
    services, tasks = _workload(specs)
    if preassign is not None:
        for i, t in enumerate(tasks):
            t.slot = 0
            t.node_id = preassign(i)
    pre = preassign is not None
    _, host = _one_tick(nodes, services, tasks, None, preassigned=pre)
    planner = _device_planner()
    _, dev = _one_tick(nodes, services, tasks, planner, preassigned=pre)
    smoke.check(len(dev) == len(host) and len(dev) > 0,
                f"placed {len(dev)} on device vs {len(host)} on host")
    if exact:
        smoke.check(dev == host, "placements differ task by task")
    for sid, _count, _spec in specs:
        def counts(placed):
            return sorted(Counter(
                nid for tid, nid in placed.items()
                if tid.startswith(sid + "-")).values())
        smoke.check(counts(dev) == counts(host),
                    f"{sid}: per-node distribution differs from host")
    for key, want in expect.items():
        got = planner.stats.get(key, 0)
        smoke.check(got == want, f"planner {key}={got}, want {want}")
    for finding in retreat_findings(planner):
        smoke.fail(finding)
    return planner


def programs_phase(smoke: Smoke, n_nodes: int, k: int, seed: int) -> None:
    """Compile and run each device program once at ``n_nodes``'s bucket
    against its oracle."""
    nodes = make_nodes(n_nodes, seed)

    # the directly called programs first: the scheduler passes below
    # compile the scatter too, and its compile time would go unseen
    _scatter_program(smoke, n_nodes, seed)
    _gang_programs(smoke, nodes, k)
    _preempt_program(smoke, n_nodes, seed)

    with smoke.program("plan_group_jit/flat", "_h0"):
        _differential(smoke, nodes, [("flat", k, task_spec("rack"))],
                      exact=False, expect={"groups_planned": 1})
    with smoke.program("plan_group_jit/hier", "_h2"):
        _differential(smoke, nodes, [("hier", k, task_spec("topology"))],
                      exact=False, expect={"groups_planned": 1})
    for sid, strategy in enumerate(("binpack", "weighted", "learned"), 1):
        with smoke.program(f"plan_strategy_jit/{strategy}", f"_st{sid}"):
            _differential(
                smoke, nodes,
                [(strategy, k, task_spec(strategy, cpu=1.0, mem=1 << 30))],
                exact=True, expect={"groups_planned": 1})
    with smoke.program("plan_fused_jit", "fused_g"):
        _differential(
            smoke, nodes,
            [("f-spread", k // 2, task_spec("spread")),
             ("f-constrained", k // 2, task_spec("constrained")),
             ("f-rack", k // 2, task_spec("rack")),
             ("f-binpack", k // 2, task_spec("binpack", **BINPACK))],
            exact=False, expect={"groups_fused": 4, "groups_planned": 0})
    with smoke.program("feasibility_jit", "feas_"):
        # global-service shape: tasks pinned to nodes, two per node on
        # the first fifth; 40 CPU each, so a node admits one
        n_pre = n_nodes + n_nodes // 5
        _differential(
            smoke, nodes,
            [("pre", n_pre, task_spec("constrained", cpu=40.0))],
            exact=False, expect={},
            preassign=lambda i: nodes[i % n_nodes].id)


def _timed_twice(row, fn):
    """Call a program twice and block on it: the second call is the
    run, the first minus the second is about the compile."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    row["run_s"] = round(time.perf_counter() - t0, 5)
    row["compile_s"] = round(max(first - row["run_s"], 0.0), 3)
    return out


#: (node bucket, nodes in it, racks a zone) of the three cells: the plan
#: programs are timed at each, on a service seen for the first time.
#: ``harness-100k``'s 250 racks a zone make 1,000 leaves, the 4,096 leaf
#: bucket, where the tree's searches take the dense form on the layout
#: ``fusedbatch.tree_inputs`` builds (the scatter form before PR 35)
TIMED_BUCKETS = ((1024, 1000, 25), (16384, 10000, 25),
                 (131072, 100000, 250))
#: group sizes from the cells' mix (60 % of its services have 1-10
#: replicas); the last fused slot is padding, as a short run's is
TIMED_K = {"flat": 10, "hier": 100, "binpack": 30,
           "fused": (10, 100, 3, 0)}


def _timed_inputs(nb: int, n: int, seed: int, racks_per_zone: int):
    """Synthetic columns of a fresh service on ``n`` nodes of the
    ``nb`` bucket: no task of its own anywhere, 0-8 tasks of others a
    node, 64 CPU / 256 GiB free, 4 zones of ``racks_per_zone`` racks
    dealt round-robin.  Returns (NodeInputs for a flat group,
    group_of(k), zone i32[nb], rack i32[nb])."""
    from swarmkit_tpu.ops.kernel import GroupInputs, NodeInputs
    rng = np.random.default_rng([seed, nb])
    i32 = np.int32
    valid = np.arange(nb) < n
    zone = np.where(valid, np.arange(nb) % ZONES, 0).astype(i32)
    rack = np.where(valid, zone * racks_per_zone
                    + (np.arange(nb) // ZONES) % racks_per_zone,
                    0).astype(i32)
    nodes = NodeInputs(
        valid=valid, ready=valid, res_ok=valid,
        res_cap=np.where(valid, 640, 0).astype(i32),
        svc_tasks=np.zeros(nb, i32),
        total_tasks=np.where(valid, rng.integers(0, 9, nb), 0).astype(i32),
        failures=np.zeros(nb, i32), leaf=np.zeros(nb, i32),
        os_hash=np.zeros((2, nb), i32), arch_hash=np.zeros((2, nb), i32),
        port_conflict=np.zeros(nb, bool), extra_mask=np.ones(nb, bool))

    def group_of(k):
        return GroupInputs(
            k=i32(k), con_hash=np.zeros((1, 2, nb), i32),
            con_op=np.full(1, 2, i32), con_exp=np.zeros((1, 2), i32),
            plat=np.full((1, 4), -1, i32), maxrep=i32(0),
            port_limited=np.bool_(False))
    return nodes, group_of, zone, rack


def _tie_key(nodes):
    from swarmkit_tpu.scheduler import strategy as strategy_mod
    return ((nodes.total_tasks.astype(np.int64) << strategy_mod.IDX_BITS)
            | np.arange(len(nodes.valid)))


def plan_program_times(smoke, buckets=TIMED_BUCKETS, seed: int = 0) -> None:
    """Each plan program called directly, twice, on a fresh service at
    each of ``buckets``: ``run_s`` on its PASS line is the program's run
    time with the result fetched, what ``plan.d2h`` waits for.  Held to
    the host mirrors (flat, binpack), to the tree's balance (hier, at
    the leaf bucket the bucket's racks a zone give; its tree comes from
    ``fusedbatch.tree_inputs`` as the planner's does, so the line times
    the form the planner launches, and its node level is held to the
    scatter-form search, whose two trip counts it prints) and to the
    per-group programs applied in order (fused)."""
    import jax
    from swarmkit_tpu.ops import fusedbatch
    from swarmkit_tpu.ops.kernel import (
        MASK_FORM_MAX_L, FusedCarry, FusedGroups, FusedShared,
        FusedStrategy, StrategyInputs, plan_fused_jit, plan_group_jit,
        plan_strategy_jit, search_form, waterfill_search,
    )
    from swarmkit_tpu.scheduler import strategy as strategy_mod
    i32, i64 = np.int32, np.int64
    w1, b1, w2, b2 = (np.asarray(a, i32)
                      for a in strategy_mod.learned_params())
    for nb, n, racks_per_zone in buckets:
        nodes, group_of, zone, rack = _timed_inputs(nb, n, seed,
                                                    racks_per_zone)
        zeros = np.zeros(nb, i32)
        racks_in_all = ZONES * racks_per_zone
        leaves = fusedbatch.l_bucket(racks_in_all)

        with smoke.program(f"plan_group_jit/flat@nb{nb}") as row:
            k = TIMED_K["flat"]
            x, _fc, spill = _timed_twice(
                row, lambda: plan_group_jit(nodes, group_of(k), 1, ()))
            smoke.check(not spill and (np.asarray(x) == (
                strategy_mod.waterfill_host(
                    zeros, np.minimum(nodes.res_cap, k), _tie_key(nodes),
                    k))).all(),
                "placements differ from waterfill_host")

        with smoke.program(f"plan_group_jit/hier@nb{nb}") as row:
            k = TIMED_K["hier"]
            leaf, leaves, hier = fusedbatch.tree_inputs(
                [zone, rack],
                [{(z,): z for z in range(ZONES)},
                 {(r // racks_per_zone, r): r for r in range(racks_in_all)}],
                n)
            x, _fc, _spill = _timed_twice(
                row, lambda: plan_group_jit(
                    nodes._replace(leaf=leaf), group_of(k), leaves, hier))
            row["form"] = search_form(
                leaves, hier[2].W if len(hier) > 2 else 0)
            x = np.asarray(x)
            racks = np.bincount(rack, x, racks_in_all)
            smoke.check(
                x.sum() == k and within_one(racks)
                and within_one(racks.reshape(ZONES, -1).sum(1))
                and x.max() <= 1, f"tree unbalanced: racks {racks}")
            # the node level again, by rows: what each rack was given,
            # water-filled over its nodes by the search's other forms
            x_rows, level_steps, tie_steps = jax.jit(
                waterfill_search, static_argnames="L")(
                zeros, np.minimum(nodes.res_cap, k),
                _tie_key(nodes).astype(i32),
                np.bincount(rack, x, leaves).astype(i32), leaf, L=leaves)
            row["level_steps"], row["tie_steps"] = \
                int(level_steps), int(tie_steps)
            smoke.check((np.asarray(x_rows) == x).all(),
                        "node level differs from the search by rows")

        # one preference over the racks (``harness-100k-ha``): above
        # 256 racks the flat leaf column brings its layout, as
        # ``fusedbatch.flat_leaf`` hands it to the planner, and the
        # searches take the dense form
        wide = leaves > MASK_FORM_MAX_L
        if wide:
            pref_layout = fusedbatch.leaf_layout(rack, n, leaves)
            pref_hier = () if pref_layout is None \
                else ((), None, pref_layout)
            with smoke.program(f"plan_group_jit/pref@nb{nb}") as row:
                k = TIMED_K["hier"]
                x, _fc, spill = _timed_twice(
                    row, lambda: plan_group_jit(
                        nodes._replace(leaf=rack), group_of(k), leaves,
                        pref_hier))
                row["form"] = search_form(
                    leaves, pref_layout.W if pref_layout else 0)
                x = np.asarray(x)
                smoke.check(
                    not spill and x.sum() == k and x.max() <= 1
                    and within_one(np.bincount(rack, x, racks_in_all)),
                    "racks unbalanced under one preference")

        sin = StrategyInputs(hr_cpu=zeros, hr_mem=zeros, hr_gen=zeros,
                             weights=np.zeros(4, i32), w1=w1, b1=b1,
                             w2=w2, b2=b2)
        with smoke.program(f"plan_strategy_jit/binpack@nb{nb}") as row:
            k = TIMED_K["binpack"]
            x, _fc, _spill = _timed_twice(
                row, lambda: plan_strategy_jit(
                    nodes, group_of(k), sin, strategy_mod.STRAT_BINPACK))
            smoke.check((np.asarray(x) == strategy_mod.plan_binpack_host(
                k, np.minimum(nodes.res_cap, k), nodes.res_cap,
                zeros)).all(),
                "placements differ from plan_binpack_host")

        # the fused scan: spread, spread, binpack and a padded slot; no
        # reservations, so each step sees the last one's totals and no
        # other change, and the per-group programs say what it must give.
        # Then, above 256 racks, the same run with its spread groups
        # under the rack preference: the run's static ``L`` is the leaf
        # bucket, and it ships what ``fusedbatch.build_run`` ships (the
        # groups' slot rows under the run's ``W`` where the layout came:
        # the dense form; the padded slot runs the ``L == 1`` program)
        ks = np.asarray(TIMED_K["fused"], i32)
        sids = np.asarray([0, 0, strategy_mod.STRAT_BINPACK, 0], i32)
        runs = [("", 1, zeros, ())] + (
            [(f"_L{leaves}", leaves, rack, pref_hier)] if wide else [])
        wants = {}
        for tag, L, leaf_col, hier in runs:
            want, total = [], nodes.total_tasks
            for k, sid in zip(ks, sids):
                seen = nodes._replace(
                    total_tasks=total, leaf=zeros if sid else leaf_col,
                    res_cap=np.where(nodes.valid, strategy_mod.K_CLAMP,
                                     0).astype(i32))
                x = (plan_strategy_jit(seen, group_of(k), sin, int(sid))
                     if sid else plan_group_jit(seen, group_of(k), L,
                                                hier))[0]
                want.append(np.asarray(x))
                total = total + want[-1]
            wants[tag] = want
        for (tag, L, leaf_col, hier), g in itertools.product(runs, (2, 4)):
            with smoke.program(f"plan_fused_jit/g{g}{tag}@nb{nb}") as row, \
                    fusedbatch.x64():
                shared = FusedShared(
                    valid=nodes.valid, ready=nodes.ready,
                    os_hash=nodes.os_hash, arch_hash=nodes.arch_hash,
                    svc0=np.zeros((g, nb), i32))
                groups = FusedGroups(
                    k=ks[:g], slot=np.arange(g, dtype=i32),
                    maxrep=np.zeros(g, i32), cpu_d=np.zeros(g, i64),
                    mem_d=np.zeros(g, i64),
                    con_hash=np.zeros((g, 1, 2, nb), i32),
                    con_op=np.full((g, 1), 2, i32),
                    con_exp=np.zeros((g, 1, 2), i32),
                    plat=np.full((g, 1, 4), -1, i32),
                    failures=np.zeros((g, nb), i32),
                    leaf=np.where(sids[:g, None] == 0, leaf_col[None, :],
                                  0).astype(i32),
                    extra_mask=np.ones((g, nb), bool))
                dense = fusedbatch.dense_rows(
                    [(rack, racks_in_all, hier[2]) if k and not sid
                     else None for k, sid in zip(ks[:g], sids[:g])],
                    n, L) if hier else None
                if dense:
                    W, rows = dense
                    leaf, flat = fusedbatch.dense_leaf(rows, g, nb, L, W)
                    groups = groups._replace(leaf=leaf, flat=flat)
                carry = FusedCarry(
                    total=nodes.total_tasks,
                    cpu=np.where(nodes.valid, NODE_CPU, 0).astype(i64),
                    mem=np.where(nodes.valid, NODE_MEM, 0).astype(i64),
                    svc_acc=np.zeros((g, nb), i32))
                strat = FusedStrategy(sid=sids[:g],
                                      weights=np.zeros((g, 4), i32),
                                      w1=w1, b1=b1, w2=w2, b2=b2)
                xs = _timed_twice(row, lambda: plan_fused_jit(
                    shared, groups, carry, L, strat))[0]
                row["form"] = search_form(L, dense[0] if dense else 0)
                smoke.check(
                    (np.asarray(xs) == np.stack(wants[tag][:g])).all(),
                    "differs from the per-group programs in order")


#: (form, rows, segments) of a search step timed alone: PR 33's three
#: (the flat group's sum, the mask at the 16,384 bucket's 256 racks, the
#: scatter at ``harness-100k``'s bucket) and the dense form on that
#: bucket's layout, [4096, 128]
STEP_SHAPES = (("sum", 131072, 1), ("mask", 16384, 256),
               ("scatter", 131072, 4096), ("dense", 131072, 4096))
#: a reading is the difference of two programs' run times, of
#: ``STEP_REPEATS[0]`` and of both numbers of steps, over the second
#: number: the call's own 3-5 ms falls out
STEP_REPEATS = (32, 512)


def search_step_times(shapes=STEP_SHAPES, seed: int = 0) -> dict:
    """One step of the level search (``kernel._seg_total`` of the fill
    at a level that moves with the last step's sums, so no step is
    hoisted) in each form, in microseconds.  The readings behind the
    table over ``kernel.MASK_FORM_MAX_L``; orientation, no verdict."""
    import jax
    import jax.numpy as jnp
    from swarmkit_tpu.ops import fusedbatch, kernel
    rng = np.random.default_rng([seed, 35])
    few, more = STEP_REPEATS
    out = {}
    for form, n, L in shapes:
        # 128 rows a segment in use, as ``harness-100k``'s racks hold
        seg = (np.arange(n) % min(L, max(n // 128, 1))).astype(np.int32)
        e = rng.integers(0, 9, n).astype(np.int32)
        cap = rng.integers(0, 9, n).astype(np.int32)
        layout = fusedbatch.leaf_layout(seg, n, L) if form == "dense" \
            else None
        got = kernel.search_form(L, layout.W if layout else 0)
        assert got == form, (form, got)

        @jax.jit
        def steps(e, cap, seg, layout, n_steps):
            if layout is not None:
                e, cap, seg = layout.lay(e, L), layout.lay(cap, L), None

            def body(_, lam):
                total = kernel._seg_total(
                    lambda lam, e, cap: jnp.clip(lam - e, 0, cap),
                    lam, (e, cap), seg, L)
                return lam + (total >= 3.0).astype(jnp.int32)
            return jax.lax.fori_loop(0, n_steps, body,
                                     jnp.zeros(L, jnp.int32))

        def run_s(n_steps):
            rows = [{} for _ in range(3)]
            for row in rows:
                _timed_twice(row, lambda: steps(e, cap, seg, layout,
                                                np.int32(n_steps)))
            return min(row["run_s"] for row in rows)
        short, long_ = run_s(few), run_s(few + more)
        shape = f"[{L}, {layout.W}]" if layout else f"{n} rows, L {L}"
        out[form] = round((long_ - short) / more * 1e6, 2)
        print(f"step {form} ({shape}): {out[form]} us a step ({few} "
              f"steps {short}s, {few + more} steps {long_}s)", flush=True)
    return out


def _gang_programs(smoke, nodes, k):
    """gang_fit verdicts on inputs the production densifier builds from
    a real mirror, against the numpy twin (tests/test_gang.py fuzz)."""
    from swarmkit_tpu.ops.kernel import (
        GroupInputs, NodeInputs, gang_fit_fused_jit, gang_fit_jit,
    )
    from swarmkit_tpu.scheduler import Scheduler, gang as gang_mod
    from swarmkit_tpu.state import MemoryStore
    store = MemoryStore()
    store.update(lambda tx: [tx.create(n.copy()) for n in nodes])
    sched = Scheduler(store)
    store.view(sched._setup_tasks_list)
    planner = _device_planner()
    # one gang that fits, one that cannot (60 CPU per member leaves one
    # slot a node), one behind constraints
    wants = [("spread", 0.1, k), ("spread", 60.0, len(nodes) + 1),
             ("constrained", 0.1, k)]
    rows = []
    for kind, cpu, members in wants:
        _svc, tasks = _workload([("gang", 1, task_spec(kind, cpu=cpu))])
        built = planner._build_device_inputs(sched, tasks[0], members)
        rows.append((built[7], built[8]))

    def same(dev, host):
        return bool(dev[0]) == host[0] \
            and (np.asarray(dev[1]) == host[1]).all()

    hosts = [gang_mod.gang_fit_host(n, g) for n, g in rows]
    smoke.check([h[0] for h in hosts] == [True, False, True],
                f"gang oracle verdicts {[h[0] for h in hosts]}")
    with smoke.program("gang_fit_jit") as row:
        out = _timed_twice(row, lambda: gang_fit_jit(*rows[0]))
        smoke.check(same(out, hosts[0]), "verdict differs from oracle")
        for r, h in zip(rows[1:], hosts[1:]):
            smoke.check(same(gang_fit_jit(*r), h),
                        "verdict differs from oracle")
    with smoke.program("gang_fit_fused_jit") as row:
        stacked_n = NodeInputs(*[
            None if f == "quota_ok"
            else np.stack([getattr(n, f) for n, _ in rows])
            for f in NodeInputs._fields])
        stacked_g = GroupInputs(*[
            np.stack([getattr(g, f) for _, g in rows])
            for f in GroupInputs._fields])
        fits, fcs = _timed_twice(
            row, lambda: gang_fit_fused_jit(stacked_n, stacked_g))
        for i, h in enumerate(hosts):
            smoke.check(same((fits[i], fcs[i]), h),
                        f"fused verdict {i} differs from oracle")


def _preempt_program(smoke, n_nodes, seed):
    """Victim selection on random candidates against the sequential
    host oracle (tests/test_preemption.py fuzz, at cluster width)."""
    from swarmkit_tpu.ops import preempt as device_preempt
    from swarmkit_tpu.scheduler import preempt as host_preempt
    rng = np.random.default_rng(seed)
    n, v, gb = n_nodes, 4, 1 << 30
    cand = host_preempt.CandidateSet(
        infos=None, ok=rng.random(n) < 0.8,
        free_cpu=rng.integers(-4, 9, n).astype(np.int64) * 10 ** 9,
        free_mem=rng.integers(0, 8, n).astype(np.int64) * gb,
        vvalid=rng.random((v, n)) < 0.6,
        vprio=rng.integers(0, 5, (v, n)).astype(np.int32),
        vcpu=rng.integers(0, 5, (v, n)).astype(np.int64) * 10 ** 9,
        vmem=rng.integers(0, 4, (v, n)).astype(np.int64) * gb,
        victims=None, vb=v, n_candidates=1)
    args = (cand, 2 * 10 ** 9, gb, 0, 8, 16)
    with smoke.program("select_victims_jit") as row:
        picks = _timed_twice(
            row, lambda: device_preempt.plan_victims(*args)[0])
        host = host_preempt.select_victims_host(*args)
        smoke.check(picks == host and len(picks) > 0,
                    f"picks {picks} differ from oracle {host}")


def _scatter_program(smoke, n_nodes, seed):
    """The donated dirty-row scatter on int64 resident columns against
    numpy, and whether the donation really happened."""
    import jax.numpy as jnp
    from swarmkit_tpu.ops import fusedbatch
    from swarmkit_tpu.ops.streaming import D_BUCKETS, _scatter_rows_jit
    rng = np.random.default_rng(seed)
    nb = fusedbatch.n_bucket(n_nodes)
    db = max(b for b in D_BUCKETS if b <= nb // 2)
    host = [rng.random(nb) < 0.9, rng.random(nb) < 0.8,
            rng.integers(0, NODE_CPU, nb), rng.integers(0, NODE_MEM, nb),
            rng.integers(0, 500, nb).astype(np.int32)]
    idx = np.full(db, nb, np.int32)            # pad = out of bounds
    rows = rng.choice(nb, db - 10, replace=False)
    idx[:len(rows)] = rows
    upd = [rng.random(db) < 0.5, rng.random(db) < 0.5,
           rng.integers(0, NODE_CPU, db), rng.integers(0, NODE_MEM, db),
           rng.integers(0, 500, db).astype(np.int32)]
    want = [h.copy() for h in host]
    for w, u in zip(want, upd):
        w[rows] = u[:len(rows)]
    with smoke.program("_scatter_rows_jit") as row, fusedbatch.x64():
        fed = []

        def scatter():
            # fresh device columns each call: the program eats them
            fed[:] = [jnp.asarray(a) for a in host]
            return _scatter_rows_jit(*fed, idx, *upd)
        out = _timed_twice(row, scatter)
        row["donated"] = all(a.is_deleted() for a in fed)
        smoke.check(out[2].dtype == np.int64, f"cpu column {out[2].dtype}")
        for name, o, w in zip(("valid", "ready", "cpu", "mem", "total"),
                              out, want):
            smoke.check((np.asarray(o) == w).all(),
                        f"column {name} differs from numpy")


# ------------------------------------------------------------ no retreat

def retreat_findings(planner) -> list:
    """Every way the planner can finish a tick without the device, as
    findings (empty = the device did the work it was given)."""
    from swarmkit_tpu.ops.kernel import plan_group_jit
    from swarmkit_tpu.ops.planner import BREAKER_CLOSED, _jit_cache_size
    out = []
    for key in ("groups_device_error", "groups_breaker_to_host",
                "groups_fallback", "groups_spill_to_host",
                "groups_strategy_host", "launch_probe_failures",
                "fused_overflows", "gang_device_error", "gang_fit_host",
                "preempt_device_error", "preempt_breaker_to_host"):
        if planner.stats.get(key, 0):
            out.append(f"planner {key}={planner.stats[key]}")
    breaker = planner.breaker
    if breaker.stats["trips"] or breaker.stats["failures"] \
            or breaker.state != BREAKER_CLOSED:
        out.append(f"breaker {breaker.state_name} {breaker.stats}")
    if planner._fused_dead:
        out.append("fused path marked dead")
    if _jit_cache_size(plan_group_jit) is None:
        out.append("compile counting is off: jit cache size unreadable")
    return out


def process_findings() -> list:
    """The process-wide half: resident state, donation balance and the
    native commit plane."""
    from swarmkit_tpu import native
    from swarmkit_tpu.utils.metrics import registry
    out = []
    for name in ("swarm_streaming_device_disabled",
                 "swarm_streaming_scatter_failures",
                 "swarm_device_donation_violations",
                 "swarm_native_commit_fallbacks"):
        if registry.get_counter(name, 0):
            out.append(f"{name}={registry.get_counter(name)}")
    if native.get_commit() is None:
        out.append("native commit plane is not loaded")
    return out


# ------------------------------------------------------------ served path

#: kind -> (may this node hold it, task_spec arguments).  In this order
#: the first three fuse into one run; a two-level spread tree does not
#: fuse and goes per group.
SHAPES = {
    "spread": (_any_node, {}),
    "constrained": (_is_web_linux_amd64, {}),
    "binpack": (_any_node, BINPACK),
    "topology": (_any_node, {}),
}


def service_mix(replicas: int) -> list:
    """[(name, kind, replicas)]: one large service per shape with four
    fifths of the replicas, then forty small ones cycling the shapes.
    The orchestrator materialises services one after another, so it is
    the small ones that meet in a scheduler tick and fuse."""
    big, small = replicas // 5, replicas // 200
    mix = [(f"big-{kind}", kind, big) for kind in SHAPES]
    mix += [(f"small-{i:02d}-{kind}", kind, small)
            for i, kind in zip(range(40), list(SHAPES) * 10) if small]
    rest = replicas - sum(count for _, _, count in mix)
    mix[0] = (mix[0][0], mix[0][1], mix[0][2] + rest)
    return mix


def served_phase(smoke: Smoke, n_nodes: int, n_agents: int, replicas: int,
                 seed: int, timeout: float) -> dict:
    """Control API in, agent status out, through the manager's own
    scheduler; returns what the leader's planner reported."""
    from swarmkit_tpu.agent import Agent
    from swarmkit_tpu.agent.testutils import TestExecutor
    from swarmkit_tpu.manager import Manager
    from swarmkit_tpu.manager.dispatcher import Config_
    from swarmkit_tpu.models import (
        Annotations, ReplicatedService, ServiceMode, ServiceSpec, TaskState,
    )

    compiles_before = _compile_ledger()
    # upstream's dispatcher constants, except the heartbeat: nodes
    # without an agent never open a session, and must not be marked
    # DOWN for it while the run lasts
    mgr = Manager(dispatcher_config=Config_(heartbeat_period=timeout))
    mgr.run()
    agents = []
    try:
        nodes = make_nodes(n_nodes, seed)
        mgr.store.update(lambda tx: [tx.create(n) for n in nodes])
        for node in nodes[:n_agents]:
            agent = Agent(node.id,
                          TestExecutor(hostname=node.description.hostname),
                          mgr.dispatcher, description=node.description)
            agent.start()
            agents.append(agent)
        planner = mgr.scheduler.batch_planner

        t_create = time.perf_counter()
        created = [
            (name, kind, count, mgr.control_api.create_service(ServiceSpec(
                annotations=Annotations(name=name),
                task=task_spec(kind, **SHAPES[kind][1]),
                mode=ServiceMode.REPLICATED,
                replicated=ReplicatedService(replicas=count))).id)
            for name, kind, count in service_mix(replicas)]

        agent_ids = {a.node_id for a in agents}
        deadline = time.perf_counter() + timeout
        tasks = []
        while True:
            tasks = mgr.control_api.list_tasks()
            pending = sum(1 for t in tasks if not t.node_id
                          or t.status.state < TaskState.ASSIGNED)
            starting = sum(1 for t in tasks if t.node_id in agent_ids
                           and t.status.state != TaskState.RUNNING)
            if len(tasks) == replicas and not pending and not starting:
                break
            if time.perf_counter() > deadline:
                smoke.fail(f"served: not converged in {timeout}s: "
                           f"{len(tasks)}/{replicas} tasks, {pending} "
                           f"unassigned, {starting} not running on "
                           "agent nodes")
                break
            time.sleep(1.0)
        served_s = round(time.perf_counter() - t_create, 3)

        host_tasks = replicas - planner.stats.get("tasks_planned", 0)
        check_placements(smoke, nodes, created, tasks, agent_ids,
                         host_tasks)
        return report_planner(smoke, planner, replicas, served_s,
                              compiles_before, n_nodes)
    finally:
        for agent in agents:
            agent.stop()
        mgr.stop()


def check_placements(smoke, nodes, created, tasks, agent_ids,
                     host_tasks) -> None:
    """What came out is right: every task on a node that may hold it,
    RUNNING where an agent serves the node, reservations within every
    node's capacity, and each service balanced the way its strategy
    promises (tests/test_tpu_kernel.py per_node_counts).  The host
    oracle's spread *tree* is lumpier than the device's water-fill over
    many partial groups, so the tree levels get the ``host_tasks`` the
    break-even router kept on the host as slack."""
    from swarmkit_tpu.models import TaskState
    by_service = {}
    cpu_used, mem_used = Counter(), Counter()
    for t in tasks:
        by_service.setdefault(t.service_id, []).append(t)
        res = t.spec.resources.reservations
        cpu_used[t.node_id] += res.nano_cpus
        mem_used[t.node_id] += res.memory_bytes
        if t.node_id in agent_ids:
            smoke.check(t.status.state == TaskState.RUNNING
                        and t.desired_state == TaskState.RUNNING,
                        f"task {t.id} on agent node is "
                        f"{t.status.state!r}")
    smoke.check(all(cpu_used[n] <= NODE_CPU and mem_used[n] <= NODE_MEM
                    for n in cpu_used), "a node's reservations exceed it")
    on_agents = sum(1 for t in tasks if t.node_id in agent_ids)
    smoke.check(on_agents > 0, "no task landed on an agent node")
    print(f"served: {len(tasks)} tasks, {on_agents} RUNNING on "
          f"{len(agent_ids)} agent nodes", flush=True)

    rack_nodes = {}
    for n in nodes:
        rack_nodes.setdefault(_labels(n)["rack"], []).append(n.id)
    eligible_ids = {kind: [n.id for n in nodes if may_hold(n)]
                    for kind, (may_hold, _) in SHAPES.items()}
    packed = Counter()
    for name, kind, count, service_id in created:
        counts = Counter(t.node_id for t in by_service.get(service_id, [])
                         if t.node_id)
        smoke.check(sum(counts.values()) == count,
                    f"{name}: {sum(counts.values())}/{count} placed")
        eligible = eligible_ids[kind]
        smoke.check(set(counts) <= set(eligible),
                    f"{name}: task on an ineligible node")
        per_node = [counts.get(nid, 0) for nid in eligible]
        if kind in ("spread", "constrained"):
            smoke.check(within_one(per_node),
                        f"{name}: per-node counts "
                        f"{min(per_node)}..{max(per_node)}")
        elif kind == "topology":
            # level by level down the tree: zones, the racks of each
            # zone, the nodes of each rack
            zones = {}
            for rack, ids in rack_nodes.items():
                in_rack = [counts.get(nid, 0) for nid in ids]
                smoke.check(within_one(in_rack, host_tasks),
                            f"{name}: nodes of {rack} unbalanced")
                zones.setdefault(rack.split("-")[0], []).append(
                    sum(in_rack))
            for zone, in_zone in zones.items():
                smoke.check(within_one(in_zone, host_tasks),
                            f"{name}: racks of {zone} {in_zone}")
            smoke.check(within_one((sum(z) for z in zones.values()),
                                   host_tasks),
                        f"{name}: zones unbalanced")
        elif kind == "binpack":
            packed.update(counts)
        if name.startswith("big-"):
            print(f"served: {name} {count} tasks on {len(counts)} of "
                  f"{len(eligible)} eligible nodes, per-node max "
                  f"{max(per_node)}", flush=True)
    # binpack fills a node before it opens the next, across services:
    # at most one node that holds its tasks has room for one more
    need_cpu, need_mem = int(BINPACK["cpu"] * 10 ** 9), BINPACK["mem"]
    still_open = [n for n in packed if NODE_CPU - cpu_used[n] >= need_cpu
                  and NODE_MEM - mem_used[n] >= need_mem]
    smoke.check(len(still_open) <= 1,
                f"binpack: {len(still_open)} of {len(packed)} nodes it "
                "opened still have room")
    print(f"served: binpack {sum(packed.values())} tasks on "
          f"{len(packed)} nodes, fullest {max(packed.values())}",
          flush=True)


def report_planner(smoke, planner, replicas, served_s,
                   compiles_before, n_nodes) -> dict:
    """Print what the leader's planner did, and fail on any retreat."""
    from swarmkit_tpu import native
    from swarmkit_tpu.ops import TPUPlanner
    stats = planner.stats
    routes = {route: stats.get(key, 0)
              for key, route in TPUPlanner._ROUTE.items()}
    device_tasks = stats.get("tasks_planned", 0)
    streaming = planner.streaming_snapshot()
    compiled, _dispatched = _compile_ledger_growth(compiles_before)
    # the break-even router's two sides as this process measured them,
    # and the smallest group that rides the device at this cluster's size
    overhead = planner._launch_overhead or 0.0
    per_node = planner.host_cost_per_node or 0.0
    report = {
        "router": {
            "launch_overhead_s": overhead,
            "host_cost_per_node_s": per_node,
            "host_cost_per_task_s": planner.host_cost_per_task,
            "nodes": n_nodes,
            "break_even_tasks": round(max(
                0.0, (0.8 * overhead - per_node * n_nodes)
                / planner.host_cost_per_task), 1)},
        "routes_groups": routes,
        "tasks_device": device_tasks,
        "tasks_host": replicas - device_tasks,
        "served_s": served_s,
        "compile_s": round(sum(compiled.values()), 3),
        "compiled": compiled,
        "streaming": streaming,
        # fused runs seeded from the resident (donated-into) columns
        "device_carries": stats.get("streaming_device_carries", 0),
        "breaker": planner.breaker.state_name,
        "native_commit": native.get_commit() is not None,
    }
    print("served: " + json.dumps(report), flush=True)
    for finding in retreat_findings(planner):
        smoke.fail(f"served: {finding}")
    smoke.check(routes["fused"] >= 1, "served: no group took the fused "
                "route")
    smoke.check(device_tasks > 0, "served: no task was placed on the "
                "device")
    smoke.check(streaming.get("incremental_ticks", 0) > 0
                and streaming.get("device_enabled"),
                f"served: resident state never refreshed incrementally "
                f"on the device: {streaming}")
    return report


# ------------------------------------------------------------------ main

class _RetreatLog(logging.Handler):
    def __init__(self):
        super().__init__(logging.ERROR)
        self.records = []

    def emit(self, record):
        if record.name in RETREAT_LOGGERS:
            self.records.append(f"{record.name}: {record.getMessage()}")


def run(device: dict, n_nodes: int = N_NODES, n_agents: int = N_AGENTS,
        replicas: int = REPLICAS, seed: int = 0,
        timeout: float = 600.0, timed_buckets=TIMED_BUCKETS,
        step_shapes=STEP_SHAPES) -> int:
    """Every phase, then the verdict; returns the exit code."""
    from swarmkit_tpu.utils.compilecache import ensure_compile_cache
    smoke = Smoke()
    retreat_log = _RetreatLog()
    logging.getLogger().addHandler(retreat_log)
    thread_errors = []
    prev_hook = threading.excepthook

    def hook(args):
        thread_errors.append(f"{args.thread.name}: {args.exc_value!r}")
        prev_hook(args)
    threading.excepthook = hook

    cache_dir = ensure_compile_cache()
    try:
        cache_entries = len(os.listdir(cache_dir))
    except OSError:
        cache_entries = 0
    print(f"compile_cache dir={cache_dir} entries_at_start="
          f"{cache_entries}", flush=True)

    overhead = None
    served, step_us = {}, {}
    try:
        with smoke.phase("native_build"):
            from swarmkit_tpu import native
            smoke.check(native.get_commit() is not None,
                        "native commit plane did not build")
        with smoke.phase("launch_probe"):
            from swarmkit_tpu.ops import TPUPlanner
            probe = TPUPlanner()
            probe._measure_launch_overhead()
            overhead = TPUPlanner._launch_overhead_shared
            smoke.check(overhead and not probe.stats.get(
                "launch_probe_failures"), "launch-overhead probe failed")
            # the router's other side, the host scan's cost a node, is
            # timed on a node mirror: the served phase prints both
            print(f"launch_overhead_s={overhead}", flush=True)
        with smoke.phase("programs"):
            programs_phase(smoke, n_nodes, max(replicas // 5, 1), seed)
            plan_program_times(smoke, timed_buckets, seed)
            step_us = search_step_times(step_shapes, seed)
        with smoke.phase("served"):
            served = served_phase(smoke, n_nodes, n_agents, replicas,
                                  seed, timeout)
    finally:
        threading.excepthook = prev_hook
        logging.getLogger().removeHandler(retreat_log)
    for finding in process_findings():
        smoke.fail(finding)
    for line in retreat_log.records:
        smoke.fail(f"retreat logged: {line}")
    for line in thread_errors:
        smoke.fail(f"uncaught thread exception: {line}")

    # set-up time, apart from the run: what the planner's ledger saw
    # compile plus the directly called programs
    compiled, _dispatched = _compile_ledger_growth({})
    direct = [r for r in smoke.programs if "dispatched" not in r]
    compile_total = {
        "signatures": len(compiled) + len(direct),
        "seconds": round(sum(compiled.values())
                         + sum(r["compile_s"] for r in direct), 3),
        "cache_dir": cache_dir, "cache_entries_at_start": cache_entries}
    print(f"compile: {json.dumps(compile_total)}", flush=True)
    return verdict(smoke, device, {
        "nodes": n_nodes, "replicas": replicas, "agents": n_agents,
        "launch_overhead_s": overhead,
        "programs": smoke.programs,
        "search_step_us": step_us,
        "served": served,
        "compile": compile_total,
        "phase_s": smoke.phase_s,
        "claim": None})


def verdict(smoke: Smoke, device: dict, summary: dict) -> int:
    """Failures to stderr and exit 1, or the summary and then the line
    the driver reads: ``ok`` and ``device``, and no other key."""
    if smoke.failures:
        print(f"chip_smoke: {len(smoke.failures)} failure(s)",
              file=sys.stderr)
        for msg in smoke.failures:
            lines = msg.strip().splitlines()
            print("  - " + " ... ".join(lines[:1] + lines[1:][-1:]),
                  file=sys.stderr)
        return smoke.exit_code()
    print("summary: " + json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return smoke.exit_code()


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python chip_smoke.py")
    p.add_argument("--seed", type=int, default=0,
                   help="decides which node gets which labels/platform")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.WARNING, stream=sys.stderr)
    device = device_info()
    print(f"device platform={device['platform']} kind={device['kind']} "
          f"count={device['count']}", flush=True)
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU found; this check only means "
              "something on the chip", file=sys.stderr)
        return 2
    return run(device, seed=args.seed)


if __name__ == "__main__":
    sys.exit(main())
