"""One run of one cell: the served path of the managers the configuration
asks for (``manager.managers``: a standalone ``Manager()``, or an odd
number of raft members, ``managers.py``) on a cluster made from the seed,
warmed up, driven for the window by the cell's traffic, and held to the
plain reference.

The entry the window drives is ``mgr.control_api.create_service`` of the
one manager or of the raft leader; every latency is taken on the client's
side, from the benchmark's watch client (``watchclient.py``).  From the
program the harness takes the system under test, its spans
(``obs.tracer``), its counters (``planner.stats``, ``scheduler.stats``,
the metrics registry, the compile ledger) and its kernel names; traffic,
reduction, peaks, byte counts and the comparison that decides
``correct`` are the benchmark's own files.
"""

from __future__ import annotations

import gc
import glob
import json
import logging
import os
import shutil
import sys
import threading
import time
from typing import Dict, List, Optional, Tuple

from . import cluster as cluster_mod
from . import managers, readers, reduce_trace, reference, retreat
from . import traffic as traffic_mod
from . import warmup
from .watchclient import WatchClient

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: the profiled slice of a traced run: where in the window it starts (as
#: a share of the window) and how long it lasts at most
TRACE_SLICE_AT, TRACE_SLICE_S = 0.25, 6.0
#: how long past the close of the window an answer is waited for
DRAIN_S = 60.0
#: nodes a proposal when the cluster enters raft members' stores
NODES_A_PROPOSAL = 200


def log(msg: str) -> None:
    print(msg, flush=True)


def load_benchmark() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def device_info() -> dict:
    import jax
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def load_peaks(kind: str) -> dict:
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"device kind {kind!r} is not in "
                       "benchmark/peaks.json")
    return peaks[kind]


# ---------------------------------------------------------------- served

class Served:
    """The system under test, running: the managers the configuration
    asks for (``mgr``: the standalone ``Manager()``, or the raft leader of
    ``members``), the configuration's cluster and agents, and the
    benchmark's watch client."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        from swarmkit_tpu.agent import Agent
        from .executor import make_executor
        from swarmkit_tpu.manager import Manager
        from swarmkit_tpu.manager.dispatcher import Config_
        self.config, self.traffic = config, traffic
        self.nodes = cluster_mod.plain_nodes(config["cluster"], seed)
        plan = managers.plan(config["manager"])
        #: the raft members, or None for the one standalone manager
        self.members: Optional[managers.Members] = None
        # upstream's dispatcher constants, except the heartbeat: nodes
        # without an agent never open a session, and must not be marked
        # DOWN for it while the run lasts
        dispatcher = Config_(
            heartbeat_period=config["manager"]["heartbeat_period_s"])
        if plan["managers"] == 1:
            self.mgr = Manager(dispatcher_config=dispatcher)
            self.mgr.run()
        else:
            self.members = managers.Members(plan, dispatcher)
            self.mgr = self.members.managers[self.members.leader]
            log(f"setup managers={plan['managers']} leader="
                f"{self.mgr.raft.id} wal_fsync={plan['wal_fsync']} "
                f"raft_link_delay_ms={plan['raft_link_delay_ms']} "
                f"snapshot_interval="
                f"{self.members.nodes[0].snapshot_interval} "
                f"wal_dir={self.members.wal_dir}")
        self.scheduler = self.mgr.scheduler
        self.planner = self.scheduler.batch_planner
        self.scheduler.debounce_gap = \
            config["manager"]["scheduler_debounce_gap_s"]
        self.scheduler.max_latency = \
            config["manager"]["scheduler_max_latency_s"]
        self.watch = WatchClient(self.mgr.watch_server)
        self.watch.start()
        objs = cluster_mod.store_nodes(self.nodes)
        if self.members is None:
            self.mgr.store.update(lambda tx: [tx.create(n) for n in objs])
        else:
            # a proposal of NODES_A_PROPOSAL nodes at a time, as nodes
            # join a swarm: the leader's raft thread applies an entry
            # without a heartbeat, and 1,000 nodes an entry held it past
            # the followers' election timeout in 3 of 10 set-ups at
            # 10,000 nodes (PERF.md 6, PR 40)
            try:
                for i in range(0, len(objs), NODES_A_PROPOSAL):
                    part = objs[i:i + NODES_A_PROPOSAL]
                    self.mgr.store.update(
                        lambda tx, part=part: [tx.create(n) for n in part])
            except BaseException:
                self.watch.stop()
                self.members.stop()
                raise
        self.agent_ids = {n["id"] for n in self.nodes if n["agent"]}
        self.agents = []
        for obj in objs:
            if obj.id not in self.agent_ids:
                continue
            agent = Agent(obj.id, make_executor(obj.description.hostname),
                          self.mgr.dispatcher, description=obj.description)
            agent.start()
            self.agents.append(agent)
        self._clients_stopped = False
        #: every service whose create the control API acknowledged:
        #: id -> {"name", "shape" (name), "replicas"}
        self.acked: Dict[str, dict] = {}

    def spec(self, name: str, shape: str, replicas: int):
        return cluster_mod.service_spec(name, self.config["shapes"][shape],
                                        replicas)

    def create(self, name: str, shape: str, replicas: int, spec=None) -> str:
        service = self.mgr.control_api.create_service(
            spec if spec is not None else self.spec(name, shape, replicas))
        self.acked[service.id] = {"name": name, "shape": shape,
                                  "replicas": replicas}
        return service.id

    def deploy_and_wait(self, prefix: str, stack: List[Tuple[str, int]],
                        timeout: float = 240.0) -> int:
        """Create a stack of services back to back and wait until the
        watch client saw every task ASSIGNED (warm-up)."""
        deadline = time.perf_counter() + timeout
        ids = [(self.create(f"{prefix}-{i}-{shape}", shape, k), k)
               for i, (shape, k) in enumerate(stack)]
        for sid, k in ids:
            if not self.watch.wait_assigned(sid, k, deadline):
                raise RuntimeError(f"warm-up deploy {prefix} was not "
                                   f"assigned within {timeout}s")
        return sum(k for _, k in ids)

    def read_members(self, term0: Optional[int]) -> Optional[dict]:
        """With raft members: stop what writes (the watch client, the
        agents, every manager's loops), then wait, ``DRAIN_S`` at the most
        from there, for each member to apply what has been committed, and
        read every service and task out of each member's own store at
        that one moment, as ``reference.compare`` takes them.  None with
        one manager, which this leaves running."""
        if self.members is None:
            return None
        from swarmkit_tpu.models import Service, Task
        elections = self.members.term() - term0
        self._stop_clients()
        self.members.quiesce()
        t = time.perf_counter()
        index = self.members.settle(DRAIN_S)
        rows = {}
        for node in self.members.nodes:
            services, tasks = node.store.view(
                lambda tx: (tx.find(Service), tx.find(Task)))
            rows[node.id] = {"services": [s.id for s in services],
                             "tasks": [_task_row(t) for t in tasks]}
        applied = {node.id: node.core.applied_index
                   for node in self.members.nodes}
        log(f"readback members={len(rows)} commit_index={index} "
            f"applied={json.dumps(applied)} "
            f"settle_s={time.perf_counter() - t:.3f}")
        return {"rows": rows, "leader": self.mgr.raft.id,
                "elections": elections}

    def _stop_clients(self) -> None:
        if self._clients_stopped:
            return
        self._clients_stopped = True
        self.watch.stop()
        for agent in self.agents:
            agent.stop()

    def stop(self) -> None:
        self._stop_clients()
        if self.members is None:
            self.mgr.stop()
        else:
            self.members.stop()


# --------------------------------------------------------------- traffic

class Window:
    """What the clients recorded of the measured window."""

    def __init__(self, t0: float, seconds: float):
        self.t0, self.seconds = t0, seconds
        #: service id -> perf_counter instant its create was due
        self.due: Dict[str, float] = {}
        self.refused_tasks = 0
        self.create_rpc_s: List[float] = []
        self.late_s: List[float] = []
        #: the closed loop's client threads, for the drain to join
        self.clients: List[threading.Thread] = []


def open_loop(served: Served, plan, window: Window) -> None:
    """Send each call when it is due, however the system is doing.
    ``plan``: [(call, its prebuilt spec)]; the window opened at
    ``window.t0``."""
    t0 = window.t0
    close = t0 + window.seconds
    for i, (call, spec) in enumerate(plan):
        due = t0 + call.due_s
        wait = due - time.perf_counter()
        if wait > 0:
            time.sleep(wait)
        sent = time.perf_counter()
        if sent > close + DRAIN_S:
            # the control API held the client a minute past the close:
            # what was due and still could not be sent is refused work
            unsent = sum(c.replicas for c, _ in plan[i:])
            log(f"window closed with {len(plan) - i} calls unsent "
                f"({unsent} tasks)")
            window.refused_tasks += unsent
            break
        try:
            sid = served.create(call.name, call.shape, call.replicas, spec)
        except Exception as e:   # a refusal counts its tasks as failed
            log(f"create_service refused {call.name}: {e!r}")
            window.refused_tasks += call.replicas
            continue
        window.create_rpc_s.append(time.perf_counter() - sent)
        window.late_s.append(sent - due)
        window.due[sid] = due
    left = close - time.perf_counter()
    if left > 0:
        time.sleep(left)


def closed_loop(served: Served, clients: List[dict],
                window: Window) -> None:
    """Each client creates a service, waits until the watch client has
    seen all of it ASSIGNED, and creates the next, until the close."""
    close = window.t0 + window.seconds
    lock = threading.Lock()
    # the first services are created in the file's order, one client
    # after another: the orchestrator takes services in the order they
    # came, so a race here would deal every run another cycle of shapes
    first_sent = [threading.Event() for _ in clients]

    def client(i: int, c: dict) -> None:
        n = 0
        if i:
            first_sent[i - 1].wait()
        while time.perf_counter() < close:
            name = f"c{c['client']}-{n:04d}-{c['shape']}"
            spec = served.spec(name, c["shape"], c["replicas"])
            sent = time.perf_counter()
            try:
                sid = served.create(name, c["shape"], c["replicas"], spec)
            except Exception as e:
                log(f"create_service refused {name}: {e!r}")
                with lock:
                    window.refused_tasks += c["replicas"]
                return
            finally:
                first_sent[i].set()
            with lock:
                window.create_rpc_s.append(time.perf_counter() - sent)
                window.due[sid] = sent
            n += 1
            served.watch.wait_assigned(sid, c["replicas"],
                                       close + DRAIN_S)
        first_sent[i].set()

    threads = [threading.Thread(target=client, args=(i, c),
                                name=f"bench-client-{c['client']}",
                                daemon=True)
               for i, c in enumerate(clients)]
    for t in threads:
        t.start()
    left = close - time.perf_counter()
    if left > 0:
        time.sleep(left)
    window.clients = threads


def drain(served: Served, window: Window, timeout: float = DRAIN_S) -> float:
    """Wait, a minute past the close if need be, for every answer that is
    due: all tasks of the window's services ASSIGNED, those on agent
    nodes RUNNING.  Returns the seconds it took."""
    t_close = time.perf_counter()
    deadline = t_close + timeout
    watch = served.watch
    for t in window.clients:
        t.join(max(0.0, deadline - time.perf_counter()))
    for sid in list(window.due):
        watch.wait_assigned(sid, served.acked[sid]["replicas"], deadline)
    while time.perf_counter() < deadline:
        tasks = list(watch.tasks.values())
        if not any(t.node_id in served.agent_ids and t.running is None
                   for t in tasks if t.service_id in window.due):
            break
        time.sleep(0.05)
    return time.perf_counter() - t_close


# ------------------------------------------------------------ the tracer

class Profiled:
    """The profiled slice of a traced run, started and stopped from a
    thread of its own so the clients keep sending."""

    def __init__(self, window_s: float):
        self.dir = os.path.join(ROOT, ".bench_trace")
        self.start_after = window_s * TRACE_SLICE_AT
        self.length = min(TRACE_SLICE_S, window_s * 0.5)
        self.slice_wall: Optional[Tuple[float, float]] = None
        self.ledger = ({}, {})
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run,
                                        name="bench-profiler", daemon=True)

    def start(self, t0: float) -> None:
        self.t0 = t0
        shutil.rmtree(self.dir, ignore_errors=True)
        self._thread.start()

    def _run(self) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        from swarmkit_tpu.obs import devicetelemetry
        try:
            wait = self.t0 + self.start_after - time.perf_counter()
            if wait > 0:
                time.sleep(wait)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            a = time.time()
            with TraceAnnotation(reduce_trace.WINDOW_START, wall_s=repr(a)):
                pass
            before = devicetelemetry.compile_cache_snapshot()
            time.sleep(self.length)
            after = devicetelemetry.compile_cache_snapshot()
            b = time.time()
            with TraceAnnotation(reduce_trace.WINDOW_END, wall_s=repr(b)):
                pass
            jax.profiler.stop_trace()
            self.slice_wall = (a, b)
            self.ledger = (before, after)
        except BaseException as e:
            self.error = e

    def finish(self) -> Optional[dict]:
        self._thread.join(timeout=120)
        if self.error is not None:
            raise self.error
        paths = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            return None
        reduced = reduce_trace.reduce(reduce_trace.read(paths[-1]))
        shutil.rmtree(self.dir, ignore_errors=True)
        return reduced

    def dispatches(self) -> Dict[str, int]:
        before, after = self.ledger
        out = {}
        for label, row in after.items():
            was = before.get(label, {})
            n = sum(row.get(k, 0) - was.get(k, 0)
                    for k in ("hits", "misses"))
            if n:
                out[label] = n
        return out


# -------------------------------------------------------------- counters

#: the sources a counter metric may read (``num`` / ``den`` ``source``)
#: and the ``window counters`` line prints; the two ``raft.`` ones only
#: where the configuration runs raft members
COUNTER_SOURCES = ("planner.stats", "scheduler.stats", "compile_ledger",
                   "raft.leader", "raft.followers")


def _numbers(stats: dict) -> Dict[str, float]:
    return {k: v for k, v in stats.items() if isinstance(v, (int, float))}


def counter_tables(served: Served) -> Dict[str, Dict[str, float]]:
    """{source: {key: value}} as the run stands.  With raft members, beside
    the program's planner and scheduler: ``raft.leader``, the
    ``RaftNode.stats`` of the member the harness drives, and
    ``raft.followers``, the same keys summed over the others."""
    from swarmkit_tpu.obs import devicetelemetry
    ledger = devicetelemetry.compile_cache_snapshot()
    tables = {
        "planner.stats": _numbers(served.planner.stats),
        "scheduler.stats": _numbers(served.scheduler.stats),
        "compile_ledger": {
            "compiles": sum(r["compiles"] for r in ledger.values()),
            "dispatches": sum(r["hits"] + r["misses"]
                              for r in ledger.values())},
        "compiled": {b: r["compiles"] for b, r in ledger.items()},
    }
    if served.members is not None:
        driven = served.members.nodes[served.members.leader]
        tables["raft.leader"] = _numbers(driven.stats)
        followers: Dict[str, float] = {}
        for node in served.members.nodes:
            if node is not driven:
                for k, v in _numbers(node.stats).items():
                    followers[k] = followers.get(k, 0) + v
        tables["raft.followers"] = followers
    return tables


def counters_line(grown: Dict[str, Dict[str, float]]) -> str:
    """The ``window counters`` line of stderr: what grew over the window,
    source by source, of those the run has."""
    return "window counters " + json.dumps(
        {src: {k: v for k, v in grown[src].items() if v}
         for src in COUNTER_SOURCES if src in grown})


def growth(before: Dict[str, Dict[str, float]],
           after: Dict[str, Dict[str, float]]) -> Dict[str, Dict[str, float]]:
    return {src: {k: v - before.get(src, {}).get(k, 0)
                  for k, v in table.items()}
            for src, table in after.items()}


# --------------------------------------------------------------- the run

def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, rehearsal=None) -> Tuple[int, Optional[dict]]:
    """One run.  Returns (exit code, the result line's object).

    ``rehearsal`` is a ``control.Rehearsal``: what the controls and the
    tests change about a run (a fault planted under the timed path, a
    cluster cut to a test's size, no look for a chip).  The command
    passes none.
    """
    bench = load_benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if rehearsal is not None and rehearsal.cell is not None:
        cells[rehearsal.cell["name"]] = rehearsal.cell
    if name not in cells:
        print(f"benchmark: no workload {name!r}; there are "
              f"{sorted(cells)}", file=sys.stderr)
        return 2, None
    cell = cells[name]
    config = cluster_mod.load_config(cell["config"])
    traffic = traffic_mod.load(cell["traffic"])
    if rehearsal is not None and rehearsal.shrink:
        config["cluster"].update(rehearsal.shrink.get("cluster", {}))
        traffic.update(rehearsal.shrink.get("traffic", {}))
    try:
        managers.plan(config["manager"])
    except ValueError as e:
        print(f"benchmark: {name}: {e}. No result: the harness runs the "
              "managers a configuration asks for, or none.",
              file=sys.stderr)
        return 2, None

    device = device_info()
    log(f"device platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}")
    require_tpu = rehearsal is None or rehearsal.require_tpu
    if require_tpu and (device["platform"] != "tpu"
                        or device["count"] < cell["chips"]):
        print(f"benchmark: {name} needs {cell['chips']} TPU chip(s); found "
              f"{device['count']} x {device['platform']}. No result: a "
              "time from anything else is not a device number.",
              file=sys.stderr)
        return 2, None
    peaks = load_peaks(device["kind"]) if device["platform"] == "tpu" \
        else None

    import jax
    from swarmkit_tpu.utils.compilecache import ensure_compile_cache
    cache_dir = ensure_compile_cache()
    # persist every compile, the short ones too: a run after the first
    # in a checkout should find every program in the cache
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    try:
        entries = len(os.listdir(cache_dir))
    except OSError:
        entries = 0
    log(f"compile_cache dir={cache_dir} entries_at_start={entries}")

    from swarmkit_tpu import native
    from swarmkit_tpu.obs import tracer
    t = time.perf_counter()
    if native.get_commit() is None:
        print("benchmark: the native commit plane did not build",
              file=sys.stderr)
        return 3, None
    log(f"setup native_build_s={time.perf_counter() - t:.3f}")

    retreat_log = retreat.RetreatLog()
    logging.getLogger().addHandler(retreat_log)
    thread_errors: List[str] = []
    prev_hook = threading.excepthook

    def hook(args):
        thread_errors.append(f"{args.thread.name}: {args.exc_value!r}")
        prev_hook(args)
    threading.excepthook = hook
    process_before = retreat.process_counters()

    t = time.perf_counter()
    served = Served(config, traffic, seed)
    log(f"setup cluster_s={time.perf_counter() - t:.3f} "
        f"nodes={len(served.nodes)} agents={len(served.agents)}")
    profiled = None
    unplant = lambda: None  # noqa: E731
    try:
        stacks, expected = warmup.plan(config, traffic, served.nodes)
        warm = warmup.drive(served, stacks)
        ledger = counter_tables(served)["compiled"]
        missing = [s for s in expected if s not in ledger]
        log("setup warmup " + json.dumps(warm))
        log(f"setup signatures warmed={sorted(ledger)}")
        if missing:
            # not a failure yet: a window that then meets one of them
            # compiles, and ``window_compiles`` fails the run
            print(f"benchmark: signatures the warm-up did not reach: "
                  f"{missing}", file=sys.stderr)
        if rehearsal is not None:
            unplant = rehearsal.plant(served)

        if traffic["generator"] == "open_loop":
            calls = traffic_mod.open_loop_schedule(traffic, seconds, seed)
            driver, arg = open_loop, [
                (c, served.spec(c.name, c.shape, c.replicas))
                for c in calls]
        else:
            driver, arg = closed_loop, \
                traffic_mod.closed_loop_clients(traffic)
        if trace:
            tracer.reset()
            tracer.enable()
            profiled = Profiled(float(seconds))
        before = counter_tables(served)
        term0 = None if served.members is None else served.members.term()
        wall0, t0 = time.time(), time.perf_counter()
        window = Window(t0, float(seconds))
        setup_s = t0 - t_start
        host0 = (time.process_time(), [g["collections"]
                                       for g in gc.get_stats()])
        if profiled is not None:
            profiled.start(t0)
        # ---- the measured window
        driver(served, arg, window)
        after = counter_tables(served)
        wall1 = time.time()
        host_cpu_s = time.process_time() - host0[0]
        collections = [g["collections"] - n
                       for g, n in zip(gc.get_stats(), host0[1])]
        # ---- closed
        if trace:
            tracer.disable()
        drained_s = drain(served, window)
        peak = memory_peak_bytes()
        log(f"window t0_setup_s={setup_s:.3f} drained_s={drained_s:.3f}")

        reduced = profiled.finish() if profiled is not None else None
        spans = [(s.thread, s.name, s.start, s.end, s.args)
                 for s in tracer.spans()] if trace else []

        # ---- read back through the control API, then free the program
        api = served.mgr.control_api
        listed = {s.id for s in api.list_services()}
        tasks = [_task_row(t) for t in api.list_tasks()]
        findings = retreat.planner_findings(served.planner) \
            + retreat.process_findings(process_before) \
            + [f"retreat logged: {r}" for r in retreat_log.records] \
            + [f"uncaught thread exception: {e}" for e in thread_errors]
        if served.watch.error is not None:
            findings.append(f"watch stream broke: {served.watch.error!r}")
        streaming = served.planner.streaming_snapshot()
        routes = {k: v for k, v in served.planner.stats.items()
                  if k.startswith("groups_") and v}
        members = served.read_members(term0)
    finally:
        threading.excepthook = prev_hook
        logging.getLogger().removeHandler(retreat_log)
        unplant()
        served.stop()

    # ---- the comparison, on plain data, once the window has closed
    if rehearsal is not None:
        rehearsal.acked.update(served.acked)
    shapes = config["shapes"]
    services = [{"id": sid, "shape": shapes[rec["shape"]],
                 "replicas": rec["replicas"], "read_back": sid in listed}
                for sid, rec in served.acked.items()]
    seen = {tid: rec.node_id for tid, rec in served.watch.tasks.items()
            if rec.node_id}
    compared = reference.compare(served.nodes, services, tasks, seen=seen,
                                 retreats=findings, members=members)

    # ---- the metrics, from the client's records
    records = served.watch.tasks
    t_close = window.t0 + window.seconds
    t_end = t_close + drained_s
    assign, running = [], []
    failed = window.refused_tasks
    attempted = window.refused_tasks
    seen_of: Dict[str, int] = {}
    for rec in records.values():
        due = window.due.get(rec.service_id)
        if due is None:
            continue
        seen_of[rec.service_id] = seen_of.get(rec.service_id, 0) + 1
        ok = rec.assigned is not None
        assign.append((rec.assigned if ok else t_end) - due)
        if rec.node_id in served.agent_ids:
            done = rec.running is not None
            running.append((rec.running if done else t_end) - due)
            ok = ok and done
        failed += 0 if ok else 1
    for sid, due in window.due.items():
        want = served.acked[sid]["replicas"]
        attempted += want
        never = want - seen_of.get(sid, 0)   # never even created
        failed += never
        assign.extend([t_end - due] * never)
    decided = sum(1 for s in served.watch.assign_stamps
                  if window.t0 <= s < t_close)
    values = {
        "decisions_per_s": decided / window.seconds,
        "assign_p50_ms": _ms(readers.percentile(assign, 50)),
        "setup_s": setup_s,
    }
    # nothing may compile inside the window: the compile ledger's growth
    # between its two snapshots, in every run, traced or not
    grown = growth(before, after)
    window_compiles = int(grown["compile_ledger"]["compiles"])
    compiled = {b: n for b, n in grown["compiled"].items() if n}
    if compiled:
        print(f"benchmark: compiled inside the window: {compiled}",
              file=sys.stderr)
    # did the wait grow through the window?  (the sweep reads this)
    dues = sorted(window.due.values())
    half = dues[len(dues) // 2] if dues else 0.0
    waits = [(window.due[r.service_id] >= half,
              r.assigned - window.due[r.service_id])
             for r in records.values()
             if r.assigned is not None and r.service_id in window.due]
    log("window assign_p50_ms first_half="
        f"{_ms(readers.percentile([w for late, w in waits if not late], 50))}"
        " second_half="
        f"{_ms(readers.percentile([w for late, w in waits if late], 50))}"
        f" unassigned_at_close={attempted - failed - decided}")
    log(f"window attempted={attempted} failed={failed} decided={decided} "
        f"services={len(window.due)} assign_n={len(assign)} "
        f"running_n={len(running)} routes={routes}")
    legs = [r.running - r.assigned for r in records.values()
            if r.service_id in window.due and r.running is not None
            and r.assigned is not None and r.node_id in served.agent_ids]
    # the tail and the agent leg, for the record of every run: neither
    # is steady enough between runs to be held to a bound (PERF.md 2)
    log(f"window assign_p95_ms={_ms(readers.percentile(assign, 95))} "
        f"running_n={len(running)} "
        f"running_p50_ms={_ms(readers.percentile(running, 50))} "
        f"running_p95_ms={_ms(readers.percentile(running, 95))} "
        f"assigned_to_running_p50_ms={_ms(readers.percentile(legs, 50))} "
        f"window_compiles={window_compiles}")
    log("window streaming " + json.dumps(streaming))
    # how the window went: decisions by twelfth of the window, the
    # process's CPU seconds, collections by generation; for a closed loop
    # each service in the order it was due, with the seconds to its last
    # ASSIGNED
    twelfth = window.seconds / 12
    by_twelfth = [0] * 12
    for s in served.watch.assign_stamps:
        if window.t0 <= s < t_close:
            by_twelfth[min(11, int((s - window.t0) / twelfth))] += 1
    log(f"window course decisions_by_twelfth={by_twelfth} "
        f"host_cpu_s={host_cpu_s:.2f} collections={collections}")
    if traffic["generator"] == "closed_loop":
        last_of: Dict[str, float] = {}
        for r in records.values():
            if r.assigned is not None and r.service_id in window.due:
                last_of[r.service_id] = max(last_of.get(r.service_id, 0.0),
                                            r.assigned)
        log("window services " + json.dumps(
            [(served.acked[sid]["shape"], round(due - window.t0, 2),
              round(last_of.get(sid, t_end) - due, 2))
             for sid, due in sorted(window.due.items(),
                                    key=lambda kv: kv[1])]))

    if trace:
        obs = readers.Observations()
        obs.window_s = window.seconds
        obs.series = {
            "assign_s": assign,
            "create_rpc_s": window.create_rpc_s,
            "generator_late_s": window.late_s,
            "pending_lag_s": [
                r.pending - window.due[r.service_id]
                for r in records.values()
                if r.service_id in window.due and r.pending is not None],
            "created_in_window": [
                s for s in served.watch.create_stamps
                if window.t0 <= s < t_close]}
        obs.spans = [s for s in spans if wall0 <= s[2] < wall1]
        obs.window_wall = (wall0, wall1)
        obs.counters = grown
        obs.trace = reduced
        obs.slice_wall = profiled.slice_wall
        obs.slice_dispatches = profiled.dispatches()
        obs.peak_bytes_per_s = peaks["hbm_bytes_per_s"] if peaks else None
        metrics = readers.read_all(name, obs, bench["per_layer"])
        log(counters_line(obs.counters))
        if obs.roofline_of:
            log("trace roofline " + json.dumps(obs.roofline_of))
    else:
        wanted = {m["name"] for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])}
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics = {k: {"value": v, "unit": units[k]}
                   for k, v in values.items()
                   if k in wanted and v is not None}

    device["memory_peak_bytes"] = peak
    line = {"correct": bool(compared["correct"] and not failed
                            and not window_compiles),
            "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if trace and reduced is not None and reduced["devices"]:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        line["breakdown"] = reduce_trace.breakdown(reduced, spans)
        log("trace modules " + json.dumps(reduced["modules"]))
    line["compared"] = reference.compared_line(compared)
    line["compared"]["window_compiles"] = [window_compiles, 0]
    line["compared"]["failed"] = [failed, 0]
    for note in compared["notes"]:
        print(f"compared note: {note}", file=sys.stderr)
    for key, (number, limit) in line["compared"].items():
        print(f"compared {key}={number} limit={limit}", file=sys.stderr)
    sys.stderr.flush()
    return 0, line


def _task_row(task) -> dict:
    """A task as ``reference.compare`` reads it."""
    return {"id": task.id, "service_id": task.service_id,
            "node_id": task.node_id or "",
            "state": _state_name(int(task.status.state))}


def _state_name(state: int) -> str:
    """The reference's words for a task's state: ``running``,
    ``assigned`` (placed, on its way to running), or what it is."""
    from swarmkit_tpu.models import TaskState
    if state == TaskState.RUNNING:
        return "running"
    if TaskState.ASSIGNED <= state < TaskState.RUNNING:
        return "assigned"
    return TaskState(state).name.lower()


def _ms(seconds: Optional[float]) -> Optional[float]:
    return None if seconds is None else 1e3 * seconds
