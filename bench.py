"""Benchmark: all five BASELINE.json configs through the full path —
store → scheduler tick → TPU plan → columnar store commit.

Headline (the driver's one JSON line) is config 4's scale: 100k tasks ×
10k nodes, median of BENCH_TRIALS runs with p50/p99 and plan/commit phase
breakdown.  The other configs run once each and are embedded in the same
JSON line under "configs":

  1. 1k tasks × 100 nodes, no constraints (spread-only baseline)
  2. 10k × 1k with CPU/memory reservations (ResourceFilter bin-packing)
  3. 50k × 5k with node.labels + platform constraints
  4. 100k × 10k mixed replicated+global with spread-by-label preference
  5. reschedule storm: 500k tasks on 10k nodes, drain 1k nodes → re-place
     the displaced tasks in one tick (plus a 500k cold-storm single tick)

Baseline: the Go toolchain is not present in this image, so the reference's
own benches cannot run here.  ``vs_baseline`` compares against the **host
oracle path** (the faithful reimplementation of the reference algorithm on
the same store) measured in this process on a proportionally scaled
workload, normalized per decision.

An end-to-end "phone-home" measurement (reference: cmd/swarm-bench) runs
the full pipeline — control API -> orchestrator -> device scheduler ->
dispatcher -> agents -> RUNNING status writeback — and reports
time-to-RUNNING percentiles per task.

Observability: the obs tracer records per-phase spans (plan dispatch /
D2H / apply, scheduler batch-build / host-fallback / commit) during every
timed trial; the full Chrome trace is written to ``BENCH_TRACE_OUT``
(default bench_trace.json — load in chrome://tracing or Perfetto) and a
per-config phase table derived from that same trace is embedded in the
output JSON, including the plan↔commit overlap fraction ROADMAP item 1
needs.  Tracing overhead is measured directly: alternating tracer-on/off
trials of the headline config, median of each half under "obs".
Planner routing counters are read from the metrics registry (deltas per
trial), not from ad-hoc dict fields.

Env overrides: BENCH_NODES, BENCH_TASKS, BENCH_BASELINE_TASKS,
BENCH_SKIP_HOST, BENCH_TRIALS, BENCH_SKIP_CONFIGS, BENCH_SKIP_E2E,
BENCH_SKIP_OBS, BENCH_TRACE_OUT, BENCH_CFG6_SERVICES,
BENCH_CFG7_SERVICES/NODES/TASKS,
BENCH_CFG10_NODES/BASE_TASKS/WINDOWS/SEED, SWARM_PLANNER_MESH.
"""

import gc
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

N_NODES = int(os.environ.get("BENCH_NODES", 10_000))
N_TASKS = int(os.environ.get("BENCH_TASKS", 100_000))
BASELINE_TASKS = int(os.environ.get("BENCH_BASELINE_TASKS", 5_000))
SKIP_HOST = os.environ.get("BENCH_SKIP_HOST", "") == "1"
SKIP_CONFIGS = os.environ.get("BENCH_SKIP_CONFIGS", "") == "1"
# run only the named configs, e.g. BENCH_CONFIGS="4 6" (empty = all);
# the headline always runs.  scripts/bench_repro.py uses this to repeat
# the cfg6 bar cheaply.
CONFIGS_ONLY = set(
    os.environ.get("BENCH_CONFIGS", "").replace(",", " ").split())
SKIP_E2E = os.environ.get("BENCH_SKIP_E2E", "") == "1"
# skips the alternating on/off overhead pairs (2x TRIALS extra headline
# trials); smoke/CI runs that don't read overhead_pct can turn it off
SKIP_OBS = os.environ.get("BENCH_SKIP_OBS", "") == "1"
TRIALS = int(os.environ.get("BENCH_TRIALS", 3))
# best-of-N per config (r4->r5 showed a 17x swing on identical code from
# one-off XLA recompiles landing inside a single timed trial)
CONFIG_TRIALS = int(os.environ.get("BENCH_CONFIG_TRIALS", 2))
# variance guard: a config whose worst trial is >1.3x its best gets one
# extra trial so a single recompile/GC hiccup cannot own the number
VARIANCE_GUARD_X = float(os.environ.get("BENCH_VARIANCE_GUARD_X", 1.3))
VARIANCE_RETRIES = int(os.environ.get("BENCH_VARIANCE_RETRIES", 1))
TRACE_OUT = os.environ.get("BENCH_TRACE_OUT", "bench_trace.json")
# flight-recorder post-mortem written when a trial trips the variance
# guard — the evidence trail for "why did this config swing"
FLIGHTREC_OUT = os.environ.get("BENCH_FLIGHTREC_OUT",
                               "bench_flightrec.json")
# every run appends its per-config summary here (bench_compare.py diffs
# entries); set to "" to disable
HISTORY_OUT = os.environ.get("BENCH_HISTORY", "BENCH_HISTORY.jsonl")


def _mesh_devices() -> int:
    """Planner mesh size (SWARM_PLANNER_MESH), 1 when unset/garbage —
    same parse rules as parallel.sharded.mesh_from_env."""
    raw = os.environ.get("SWARM_PLANNER_MESH", "").strip()
    try:
        return max(1, int(raw)) if raw else 1
    except ValueError:
        return 1


def _mesh_crossover():
    """The mesh crossover artifact (scripts/mesh_crossover.py), trimmed
    to the headline fields, or None when it has not been measured."""
    path = os.environ.get("BENCH_MESH_CROSSOVER", "MULTICHIP_r07.json")
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    return {
        "winner_by_shape": doc.get("winner_by_shape"),
        "placements_equal_across_mesh":
            doc.get("placements_equal_across_mesh"),
        "strategy_host_fallbacks": doc.get("strategy_host_fallbacks"),
        "skipped": doc.get("skipped"),
        "curves": {nb: s.get("curve")
                   for nb, s in (doc.get("shapes") or {}).items()},
        "decisions_per_sec": {
            nb: s.get("decisions_per_sec")
            for nb, s in (doc.get("shapes") or {}).items()},
    }


def _cfg_enabled(n: int) -> bool:
    if SKIP_CONFIGS:
        return False
    return not CONFIGS_ONLY or str(n) in CONFIGS_ONLY


def _planner_counters():
    """Routing-counter keys, derived from the planner's own route map so
    a label rename there can never silently zero bench's numbers (the
    planner increments stats dict and registry through one helper, and
    bench reports the registry's numbers)."""
    from swarmkit_tpu.ops import TPUPlanner
    keys = {stat_key: f'swarm_planner_groups{{route="{route}"}}'
            for stat_key, route in TPUPlanner._ROUTE.items()}
    keys["tasks_planned"] = "swarm_planner_tasks_planned"
    return keys


def _planner_counter_snapshot():
    from swarmkit_tpu.utils.metrics import registry
    return registry.counters_snapshot("swarm_planner_")


def _planner_counter_delta(snap):
    cur = _planner_counter_snapshot()
    return {stat_key: int(cur.get(reg_key, 0.0) - snap.get(reg_key, 0.0))
            for stat_key, reg_key in _planner_counters().items()}


_COMPILE_PREFIX = 'swarm_planner_compiles{bucket="'


def _compile_delta(snap):
    """Per-bucket XLA compile counts since ``snap`` (zeros included, so
    the artifact names every bucket the run touched — "this bucket
    existed and did NOT recompile" is the common, load-bearing case)."""
    cur = _planner_counter_snapshot()
    out = {}
    for key in set(cur) | set(snap):
        if not key.startswith(_COMPILE_PREFIX):
            continue
        bucket = key[len(_COMPILE_PREFIX):-2]
        out[bucket] = int(cur.get(key, 0.0) - snap.get(key, 0.0))
    return dict(sorted(out.items()))


def build_cluster(n_nodes, n_tasks, node_labels=None, reservations=None,
                  constraints=None, platforms=None, prefs=None,
                  node_platform=None, global_share=0.0, assigned_state=None,
                  n_services=1):
    from swarmkit_tpu.models import (
        Annotations, Node, NodeDescription, NodeSpec, NodeState, NodeStatus,
        Placement, Platform, ReplicatedService, Resources,
        ResourceRequirements, Service, ServiceMode, ServiceSpec, Task,
        TaskSpec, TaskState, TaskStatus, Version,
    )
    from swarmkit_tpu.state import MemoryStore
    from swarmkit_tpu.utils import new_id

    store = MemoryStore()
    nodes = []
    for i in range(n_nodes):
        labels = dict(node_labels(i)) if node_labels else \
            {"rack": f"r{i % 20}"}
        platform = Platform(**node_platform(i)) if node_platform else \
            Platform(os="linux", architecture="amd64")
        nodes.append(Node(
            id=new_id(),
            spec=NodeSpec(annotations=Annotations(
                name=f"node-{i:05d}", labels=labels)),
            status=NodeStatus(state=NodeState.READY),
            description=NodeDescription(
                hostname=f"node-{i:05d}", platform=platform,
                resources=Resources(nano_cpus=64 * 10**9,
                                    memory_bytes=256 << 30))))
    shared_spec = TaskSpec(
        placement=Placement(constraints=constraints or [],
                            platforms=platforms or [],
                            preferences=prefs or []),
        resources=ResourceRequirements(
            reservations=reservations
            or Resources(nano_cpus=10**8, memory_bytes=64 << 20)))

    # n_services > 1 splits the task count over distinct services: each
    # becomes its own (service, spec-version) scheduling group, the unit
    # the pipelined tick overlaps (plan group i+1 while committing i)
    services = []
    tasks = []
    per = n_tasks // n_services
    for si in range(n_services):
        count = per if si < n_services - 1 else n_tasks - per * si
        svc = Service(
            id=new_id(),
            spec=ServiceSpec(annotations=Annotations(name=f"bench-{si}"),
                             mode=ServiceMode.REPLICATED,
                             replicated=ReplicatedService(replicas=count)),
            spec_version=Version(index=1))
        services.append(svc)
        n_global = int(count * global_share)
        for s in range(1, count + 1):
            t = Task(id=new_id(), service_id=svc.id, slot=s,
                     desired_state=TaskState.RUNNING, spec=shared_spec,
                     spec_version=Version(index=1),
                     status=TaskStatus(state=TaskState.PENDING))
            if s <= n_global:
                # global-service style: preassigned to a node
                t.slot = 0
                t.node_id = nodes[s % n_nodes].id
            if assigned_state is not None and s > n_global:
                t.node_id = nodes[s % n_nodes].id
                t.status = TaskStatus(state=assigned_state)
            tasks.append(t)
    svc = services[0]

    def create_nodes(tx):
        for n in nodes:
            tx.create(n)
        for s in services:
            tx.create(s)

    store.update(create_nodes)

    def create_tasks(tx):
        for t in tasks:
            tx.create(t)

    store.update(create_tasks)
    return store, svc, nodes, tasks


def one_tick(store, planner, preassigned=False):
    from swarmkit_tpu.scheduler import Scheduler

    sched = Scheduler(store, batch_planner=planner)
    store.view(sched._setup_tasks_list)
    n_pre = len(sched.pending_preassigned_tasks)
    gc.collect()
    gc.freeze()
    t0 = time.perf_counter()
    if preassigned:
        sched._process_preassigned_tasks()
    n_dec = sched.tick()
    if preassigned:
        # only preassigned tasks that actually confirmed count
        n_dec += n_pre - len(sched.pending_preassigned_tasks)
    dt = time.perf_counter() - t0
    gc.unfreeze()
    return sched, n_dec, dt


def _trim_heap():
    """Release the previous config's multi-GB object graph back to the
    OS between configs: leftover arenas inflate later configs' GC and
    allocator costs (cfg4/storm measured ~2x slower inside the full run
    than in isolation before this)."""
    gc.collect()
    try:
        import ctypes
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except Exception:
        pass


# name -> {"path", "sha256"} of flight-recorder dumps written because a
# config tripped the variance guard (read back into the artifact)
_flightrec_dumps = {}


def _dump_flightrec_on_trip(name):
    """A trial swung past the guard: dump the black box NOW, before the
    retry overwrites the evidence (the recent spans — including any
    plan.compile — and counter samples around the slow trial)."""
    from swarmkit_tpu.obs import flightrec
    base, ext = os.path.splitext(FLIGHTREC_OUT)
    path = f"{base}_{name}{ext}" if name else FLIGHTREC_OUT
    try:
        sha = flightrec.dump(path)
    except OSError:
        return
    _flightrec_dumps[name or "headline"] = {"path": path, "sha256": sha}


def run_with_variance_guard(trial, n_trials=None, name=None):
    """Best-of-N with the variance guard: run ``trial`` (returning a
    tuple whose first element is the timed seconds) n_trials times, then
    keep re-running while the worst trial exceeds VARIANCE_GUARD_X of
    the best (up to VARIANCE_RETRIES extras).  A tripped guard dumps the
    flight recorder so the swing is explainable after the fact.
    Returns (results, retries)."""
    results = [trial() for _ in range(n_trials or CONFIG_TRIALS)]
    retries = 0
    while retries < VARIANCE_RETRIES:
        dts = [r[0] for r in results]
        if max(dts) <= VARIANCE_GUARD_X * min(dts):
            break
        if retries == 0:
            _dump_flightrec_on_trip(name)
        retries += 1
        results.append(trial())
    return results, retries


def _spread_stats(dts):
    """Trial-spread fields shared by every multi-trial config."""
    best = min(dts)
    return {
        "trials": len(dts),
        "tick_s": round(best, 3),                      # headline = best
        "tick_s_median": round(statistics.median(dts), 3),
        "tick_s_stdev": round(statistics.stdev(dts), 4)
        if len(dts) > 1 else 0.0,
        "variance_x": round(max(dts) / best, 2),
    }


def run_config(name, n_nodes, n_tasks, planner_factory, expect=None, **kw):
    """Best-of-CONFIG_TRIALS with a per-config shape warm-up pass and a
    variance guard, so a one-off XLA recompile can never be the headline
    (VERDICT Weak #2)."""
    from swarmkit_tpu.models import Task as _Task, TaskState

    from swarmkit_tpu.utils.metrics import registry

    preassigned = kw.get("global_share", 0.0) > 0

    # per-config warm-up: tiny task count, IDENTICAL node shape and
    # constraint/preference mix, so every jit signature this config hits
    # is compiled before any timed trial.  The tracer is off for the
    # warm-up: its spans (which absorb any XLA compile) must not land in
    # this config's bench.config window and contaminate the phase table.
    from swarmkit_tpu.obs import tracer
    _trim_heap()
    was_tracing = tracer.enabled
    tracer.disable()
    try:
        warm_store, *_ = build_cluster(n_nodes, 64, **kw)
        warm_planner = planner_factory()
        warm_planner.enable_small_group_routing = False
        one_tick(warm_store, warm_planner, preassigned=preassigned)
        del warm_store, warm_planner
    finally:
        tracer.enabled = was_tracing

    # per-config metrics isolation: counters/gauges zeroed, timers reset
    # in place, so this config's quantiles are its own
    registry.reset()

    def trial():
        _trim_heap()
        snap = _planner_counter_snapshot()
        store, svc, nodes, tasks = build_cluster(n_nodes, n_tasks, **kw)
        planner = planner_factory()
        sched, n_dec, dt = one_tick(store, planner,
                                    preassigned=preassigned)
        routed = _planner_counter_delta(snap)
        expected = expect if expect is not None else n_tasks
        n_assigned = sum(
            1 for t in store.view(lambda tx: tx.find(_Task))
            if t.status.state >= TaskState.ASSIGNED and t.node_id)
        assert n_assigned >= expected, \
            f"{name}: only {n_assigned}/{expected} tasks ASSIGNED"
        if routed["tasks_planned"] == 0:
            # legitimate only when the adaptive router sent every group
            # to the host because the device round-trip won't amortize
            assert routed["groups_small_to_host"] > 0 \
                and routed["groups_fallback"] == 0, \
                f"{name}: TPU path did not engage: {routed}"
        return dt, n_dec, planner, sched, routed

    results, retries = run_with_variance_guard(trial, name=name)
    dts = [r[0] for r in results]
    dt, n_dec, planner, sched, routed = min(results, key=lambda r: r[0])
    out = {
        "nodes": n_nodes, "tasks": n_tasks,
        "decisions": n_dec,
        "decisions_per_sec": round(n_dec / dt, 1),
        "plan_s": round(planner.stats["plan_seconds"], 3),
        "commit_s": round(sched.stats["commit_seconds"], 3),
        # routing counters from the metrics registry (per-trial deltas)
        "fallback_groups": routed["groups_fallback"],
        "groups_small_to_host": routed["groups_small_to_host"],
        "groups_device": routed["groups_planned"],
        "variance_reruns": retries,
        "path": "host-routed" if routed["tasks_planned"] == 0
        else "device",
        # per-bucket XLA compiles inside the timed trials (registry was
        # reset post-warm-up, so any nonzero count here is a compile
        # that landed in a timed region — the r4/r5 swing explained)
        "compiles": _compile_delta({}),
    }
    if name in _flightrec_dumps:
        out["flightrec_dump"] = _flightrec_dumps[name]
    out.update(_spread_stats(dts))
    return out


def run_storm(planner_factory):
    """Config 5: 500k tasks running on 10k nodes; 1k nodes are drained and
    the tasks they hosted must be re-placed on the remaining 9k nodes in
    one tick.  The cluster is built post-drain: drained nodes carry
    availability=DRAIN with their old tasks already SHUT DOWN (what the
    orchestrator/enforcer do), and one PENDING replacement per displaced
    task sits in the queue.  Best-of-CONFIG_TRIALS with the same variance
    guard as run_config (this config showed the 17x r4/r5 swing)."""
    from swarmkit_tpu.models import (
        NodeAvailability, Task, TaskState, TaskStatus,
    )
    from swarmkit_tpu.scheduler import Scheduler
    from swarmkit_tpu.utils import new_id

    from swarmkit_tpu.utils.metrics import registry

    n_nodes, n_tasks, n_drained = 10_000, 500_000, 1_000
    registry.reset()   # per-config metrics isolation
    # no per-config warm-up needed (unlike run_config): jit signatures
    # are shape-bucketed and main()'s warm-up pass already compiled this
    # node bucket with no preferences; task count is a traced scalar, so
    # 500k tasks hits the same compiled program and no compile time can
    # land in this config's spans

    def trial():
        _trim_heap()
        snap = _planner_counter_snapshot()
        store, svc, nodes, tasks = build_cluster(
            n_nodes, n_tasks, assigned_state=TaskState.RUNNING)

        drained = set(n.id for n in nodes[:n_drained])

        def drain_nodes(tx):
            for n in nodes[:n_drained]:
                cur = tx.get(type(n), n.id).copy()
                cur.spec.availability = NodeAvailability.DRAIN
                tx.update(cur)

        store.update(drain_nodes)

        displaced = [t for t in tasks if t.node_id in drained]
        replacements = []
        for t in displaced:
            r = t.copy()
            r.id = new_id()
            r.node_id = ""
            r.status = TaskStatus(state=TaskState.PENDING)
            replacements.append(r)

        def shutdown_and_replace(batch):
            for t in displaced:
                def down(tx, t=t):
                    cur = tx.get(Task, t.id).copy()
                    cur.desired_state = TaskState.SHUTDOWN
                    cur.status = TaskStatus(state=TaskState.SHUTDOWN)
                    tx.update(cur)
                batch.update(down)
            for r in replacements:
                batch.update(lambda tx, r=r: tx.create(r))

        store.batch(shutdown_and_replace)

        planner = planner_factory()
        sched = Scheduler(store, batch_planner=planner)
        store.view(sched._setup_tasks_list)

        gc.collect()
        gc.freeze()
        t0 = time.perf_counter()
        n_dec = sched.tick()
        dt = time.perf_counter() - t0
        gc.unfreeze()
        assert n_dec == len(replacements), (n_dec, len(replacements))
        placed = store.view(
            lambda tx: [tx.get(Task, r.id) for r in replacements])
        assert all(t is not None and t.node_id and t.node_id not in drained
                   for t in placed), "replacements must avoid drained nodes"
        return dt, n_dec, len(replacements), planner, sched, \
            _planner_counter_delta(snap)

    results, retries = run_with_variance_guard(trial, name="storm")
    dts = [r[0] for r in results]
    dt, n_dec, n_repl, planner, sched, routed = min(results,
                                                    key=lambda r: r[0])
    out = {
        "nodes": n_nodes, "tasks": n_tasks,
        "drained_nodes": n_drained,
        "replacements": n_repl,
        "decisions_per_sec": round(n_dec / dt, 1),
        "plan_s": round(planner.stats["plan_seconds"], 3),
        "commit_s": round(sched.stats["commit_seconds"], 3),
        "fallback_groups": routed["groups_fallback"],
        "variance_reruns": retries,
        "compiles": _compile_delta({}),
    }
    if "storm" in _flightrec_dumps:
        out["flightrec_dump"] = _flightrec_dumps["storm"]
    out.update(_spread_stats(dts))
    return out


def run_live_manager(planner_factory, external_firehose=False,
                     n_services=None, n_nodes=None, total_tasks=None):
    """Config 6/7: config-4's scale in PRODUCTION shape — a real
    single-voter raft proposer (on-disk WAL, consensus apply path)
    attached to the store, plus the control plane's subscriber mix
    (dispatcher sessions, orchestrator/reaper loops, metrics collector —
    all in their real block-aware subscription shapes, with live
    consumer threads).  Blocks ride one compact TaskBlockAction per
    chunk through raft and publish one coalesced EventTaskBlock.

    ``n_services`` (default 2, env BENCH_CFG6_SERVICES) services
    splitting ``total_tasks`` (default N_TASKS each) schedule in ONE
    tick — the multi-group shape a live manager actually carries.  Runs
    of fusable groups densify into ONE scan-over-groups program per
    chunk (ops/fusedbatch.py), so the tick pays one device round-trip
    ladder regardless of service count; chunk i+1 computes while group
    i's chunks ride raft (``plan_hidden_frac`` is the overlap
    evidence).  Config 7 reuses this harness at 10 services
    (BENCH_CFG7_* env knobs scale it toward the 1M-task x 50k-node
    target shape on hosts that hold it).

    ``external_firehose`` adds a watch-API-style client consuming EVERY
    task as a synthesized per-task event.  Synthesis runs on the
    consumer's own thread (never the commit path), but this benchmark
    host has ONE core, so the firehose's GIL time lands in the tick
    wall-clock anyway; it is off by default because a real manager has
    no all-task external watcher — the cost scales with what external
    clients actually subscribe to."""
    _trim_heap()
    import shutil
    import tempfile
    import threading

    from swarmkit_tpu.models import Task as _Task, TaskState
    from swarmkit_tpu.state import match
    from swarmkit_tpu.state.raft import LocalNetwork, RaftLogger, RaftNode

    if n_services is None:
        n_services = int(os.environ.get("BENCH_CFG6_SERVICES", 2))
    if n_nodes is None:
        n_nodes = N_NODES
    if total_tasks is None:
        total_tasks = N_TASKS * n_services

    # warm-up at this config's exact fused jit signatures: same node
    # bucket, same service count (group-slot/service-slot buckets), tiny
    # task counts — compiles must never land in the timed tick (tracer
    # off so the compile spans stay out of this config's phase window)
    from swarmkit_tpu.obs import tracer as _tracer
    was_tracing = _tracer.enabled
    _tracer.disable()
    try:
        warm_store, *_ = build_cluster(n_nodes, 16 * n_services,
                                       n_services=n_services)
        warm_planner = planner_factory()
        warm_planner.enable_small_group_routing = False
        one_tick(warm_store, warm_planner)
        del warm_store, warm_planner
        _trim_heap()
    finally:
        _tracer.enabled = was_tracing

    store, svc, nodes, tasks = build_cluster(n_nodes, total_tasks,
                                             n_services=n_services)
    tmp = tempfile.mkdtemp(prefix="bench-raft-")
    rn = RaftNode("b0", ["b0"], store,
                  RaftLogger(os.path.join(tmp, "b0")), LocalNetwork())
    store._proposer = rn
    rn.start()
    deadline = time.time() + 15
    while not (rn.is_leader and rn.core.leader_ready):
        if time.time() > deadline:
            raise RuntimeError("bench raft leader not ready")
        time.sleep(0.01)

    from swarmkit_tpu.state.events import EventTaskBlock

    counts = {}
    # the subscriber mix a live manager carries, in each component's real
    # subscription shape: block-aware control loops (orchestrators,
    # reaper, restart — they skip assignment blocks by contract),
    # block-aware dispatcher sessions (per_node membership probes), the
    # metrics collector (cheap per-item histogram shift), and one
    # EXTERNAL watch client in the legacy per-event shape — it pays the
    # per-task synthesis, on its own thread, never the commit path
    subs = {
        # real orchestrator/reaper loops subscribe unfiltered and skip
        # blocks by contract (state<=RUNNING); model that exactly
        "orchestrator": store.queue.subscribe(accepts_blocks=True),
        "reaper": store.queue.subscribe(accepts_blocks=True),
    }
    if external_firehose:
        subs["external_watch"] = store.queue.subscribe(
            match(_Task, actions=("update",)))
    session_nodes = [n.id for n in nodes[:8]]
    for i, nid in enumerate(session_nodes):
        def pred(ev, nid=nid):
            if isinstance(ev, EventTaskBlock):
                return True   # per-node probe runs on the consumer side
            return getattr(getattr(ev, "obj", None), "node_id",
                           None) == nid
        subs[f"session{i}"] = store.queue.subscribe(
            pred, accepts_blocks=True)
    hist = {}
    metrics_sub = store.queue.subscribe(accepts_blocks=True)
    stop = threading.Event()

    # consumers BLOCK on the subscription like the real components do
    # (orchestrator/dispatcher loops wait in Subscription.get, they do
    # not poll) — sleep-polling here both mismodels the components and
    # taxes the tick with periodic GIL wakeups on this 1-core host

    def _blocking_items(sub):
        try:
            head = sub.get(timeout=0.1)
        except TimeoutError:
            return []
        return [head] + sub.drain()

    def consume(name, sub):
        got = 0
        while not stop.is_set():
            for it in _blocking_items(sub):
                if isinstance(it, EventTaskBlock):
                    if name.startswith("session"):
                        nid = session_nodes[int(name[7:])]
                        got += len(it.per_node().get(nid, ()))
                    else:
                        got += len(it)   # control loop: O(1) skip
                else:
                    got += 1
        for it in sub.drain():
            got += len(it) if isinstance(it, EventTaskBlock) else 1
        counts[name] = got

    def consume_metrics(sub):
        got = 0

        def absorb(items):
            nonlocal got
            for it in items:
                if isinstance(it, EventTaskBlock):
                    for old in it.olds:
                        k = int(old.status.state)
                        hist[k] = hist.get(k, 0) - 1
                    hist[it.state] = hist.get(it.state, 0) + len(it)
                    got += len(it)
                else:
                    got += 1

        while not stop.is_set():
            absorb(_blocking_items(sub))
        absorb(sub.drain())   # post-stop tail, like consume()
        counts["metrics"] = got

    threads = [threading.Thread(target=consume, args=(k, s), daemon=True)
               for k, s in subs.items()]
    threads.append(threading.Thread(target=consume_metrics,
                                    args=(metrics_sub,), daemon=True))
    for t in threads:
        t.start()

    try:
        from swarmkit_tpu import native as _native
        from swarmkit_tpu.utils.metrics import registry as _registry
        planner = planner_factory()
        snap = _planner_counter_snapshot()
        fanout_timer = _registry.timer("swarm_watch_fanout_latency")
        fanout0 = fanout_timer.total
        fallbacks0 = _registry.get_counter("swarm_native_commit_fallbacks")
        sched, n_dec, dt = one_tick(store, planner)
        routed = _planner_counter_delta(snap)
        time.sleep(0.2)   # let consumers drain the tail
        stop.set()
        for t in threads:
            t.join(timeout=5)
        n_assigned = sum(
            1 for t in store.view(lambda tx: tx.find(_Task))
            if t.status.state >= TaskState.ASSIGNED and t.node_id)
        assert n_assigned >= total_tasks, \
            f"live-manager: only {n_assigned}/{total_tasks} ASSIGNED"
        # the metrics histogram must balance, and when the firehose
        # client is attached every decision must reach it as a per-task
        # synthesized event
        assert counts["metrics"] >= n_dec, counts
        assert hist.get(int(TaskState.ASSIGNED), 0) >= n_dec, hist
        if external_firehose:
            assert counts["external_watch"] >= n_dec, counts
        return {
            "nodes": n_nodes, "tasks": total_tasks,
            "services": n_services,
            "pipeline_depth": sched.pipeline_depth,
            "decisions": n_dec,
            "decisions_per_sec": round(n_dec / dt, 1),
            "tick_s": round(dt, 3),
            "plan_s": round(planner.stats["plan_seconds"], 3),
            "commit_s": round(sched.stats["commit_seconds"], 3),
            # commit-plane headline fields (ISSUE 13): the commit phase
            # wall, the watch fan-out synthesis cost (consumer side,
            # includes the drain tail), and whether the native commit
            # plane held (a fallback tick inside the timed window means
            # it silently ran Python — bench_compare gates on it)
            "commit_phase_s": round(sched.stats["commit_seconds"], 3),
            "fanout_s": round(fanout_timer.total - fanout0, 3),
            "native_commit": {
                # enabled = the escape hatch (SWARM_NATIVE_COMMIT) was
                # not pulled; active = the C module actually loaded.
                # enabled-but-inactive or any fallback tick inside the
                # timed window fails bench_compare's native-commit gate.
                "enabled": _native.commit_enabled(),
                "active": _native.get() is not None,
                "fallbacks": int(_registry.get_counter(
                    "swarm_native_commit_fallbacks") - fallbacks0),
            },
            "fallback_groups": routed["groups_fallback"],
            "groups_fused": routed["groups_fused"],
            "mesh_devices": (planner.mesh.shape["nodes"]
                             if getattr(planner, "mesh", None) is not None
                             else 1),
            "raft_entries_applied": rn.stats["applied"],
            "events_delivered": dict(counts),
            "path": "device+raft+watchers",
            "compiles": _compile_delta(snap),
        }
    finally:
        stop.set()
        rn.stop()
        shutil.rmtree(tmp, ignore_errors=True)


def run_priority_jobs(planner_factory):
    """Config 8: services + jobs + 3 priority bands on a FULL cluster —
    the priority & preemption subsystem's production shape.  512 nodes
    (8 cpu each, 4 slots at the 2-cpu reservation) run 1800 priority-0
    tasks; a 400-task priority-2 band, a 120-task priority-1 band and a
    64-completion replicated job (priority 1) then arrive in ONE tick.
    Free capacity covers less than half of them, so the tick's
    preemption pass (device victim kernel, ops/preempt.py) must evict
    ~336 low-band tasks to place every arrival — the bench asserts all
    arrivals ASSIGNED and reports the ``swarm_preemptions`` delta,
    which scripts/bench_compare.py gates on appearing with ZERO
    planner-compile growth in the timed window (the warm-up pass below
    covers every (NB, V, PB) victim-kernel signature)."""
    _trim_heap()
    from swarmkit_tpu.models import (
        Annotations, Node, NodeDescription, NodeSpec, NodeState,
        NodeStatus, ReplicatedService, Resources, ResourceRequirements,
        Service, ServiceMode, ServiceSpec, Task, TaskSpec, TaskState,
        TaskStatus, Version,
    )
    from swarmkit_tpu.models.specs import ReplicatedJob
    from swarmkit_tpu.scheduler import Scheduler
    from swarmkit_tpu.state import MemoryStore
    from swarmkit_tpu.utils import new_id
    from swarmkit_tpu.utils.metrics import registry as _reg

    N_N = int(os.environ.get("BENCH_CFG8_NODES", 512))
    CPU = 2 * 10 ** 9
    MEM = 1 << 30
    N_LO, N_HI, N_MID, N_JOB = 1800, 400, 120, 64

    def build():
        store = MemoryStore()
        nodes = []
        for i in range(N_N):
            nodes.append(Node(
                id=new_id(),
                spec=NodeSpec(annotations=Annotations(name=f"p{i:04d}")),
                status=NodeStatus(state=NodeState.READY),
                description=NodeDescription(
                    hostname=f"p{i:04d}",
                    resources=Resources(nano_cpus=8 * 10 ** 9,
                                        memory_bytes=32 << 30))))
        res = ResourceRequirements(
            reservations=Resources(nano_cpus=CPU, memory_bytes=MEM))
        bands = {"lo": (0, N_LO), "hi": (2, N_HI), "mid": (1, N_MID)}
        specs = {name: TaskSpec(resources=res, priority=prio)
                 for name, (prio, _n) in bands.items()}
        tasks = []
        svcs = []
        for name, (prio, count) in bands.items():
            svc = Service(
                id=new_id(),
                spec=ServiceSpec(
                    annotations=Annotations(name=f"band-{name}"),
                    mode=ServiceMode.REPLICATED,
                    replicated=ReplicatedService(replicas=count),
                    task=specs[name]),
                spec_version=Version(index=1))
            svcs.append(svc)
            for s in range(count):
                t = Task(id=new_id(), service_id=svc.id, slot=s + 1,
                         desired_state=TaskState.RUNNING,
                         spec=specs[name], spec_version=Version(index=1),
                         status=TaskStatus(state=TaskState.PENDING))
                if name == "lo":   # the resident band: already RUNNING
                    t.node_id = nodes[s % N_N].id
                    t.status = TaskStatus(state=TaskState.RUNNING)
                tasks.append(t)
        job_spec = TaskSpec(resources=res, priority=1)
        job = Service(
            id=new_id(),
            spec=ServiceSpec(
                annotations=Annotations(name="band-job"),
                mode=ServiceMode.REPLICATED_JOB,
                replicated_job=ReplicatedJob(total_completions=N_JOB),
                task=job_spec),
            spec_version=Version(index=1))
        svcs.append(job)
        for s in range(N_JOB):
            tasks.append(Task(
                id=new_id(), service_id=job.id, slot=s,
                desired_state=TaskState.COMPLETE, spec=job_spec,
                spec_version=Version(index=1),
                job_iteration=Version(index=0),
                status=TaskStatus(state=TaskState.PENDING)))

        def mk(tx):
            for n in nodes:
                tx.create(n)
            for s in svcs:
                tx.create(s)
        store.update(mk)
        store.update(lambda tx: (
            [tx.create(t) for t in tasks] and None))
        return store

    def one_pass(store):
        planner = planner_factory()
        sched = Scheduler(store, batch_planner=planner,
                          preempt_budget=512)
        store.view(sched._setup_tasks_list)
        gc.collect()
        gc.freeze()
        t0 = time.perf_counter()
        n_dec = sched.tick()
        dt = time.perf_counter() - t0
        gc.unfreeze()
        return sched, planner, n_dec, dt

    # warm-up: the identical workload once, tracer off — covers every
    # planner AND victim-kernel jit signature this config touches
    from swarmkit_tpu.obs import tracer as _tracer
    was_tracing = _tracer.enabled
    _tracer.disable()
    try:
        one_pass(build())
        _trim_heap()
    finally:
        _tracer.enabled = was_tracing

    store = build()
    snap = _planner_counter_snapshot()
    pre0 = _reg.get_counter('swarm_preemptions{reason="priority"}')
    sched, planner, n_dec, dt = one_pass(store)
    preemptions = int(
        _reg.get_counter('swarm_preemptions{reason="priority"}') - pre0)
    routed = _planner_counter_delta(snap)

    pending_bands = N_HI + N_MID + N_JOB
    placed = sum(
        1 for t in store.view(lambda tx: tx.find(Task))
        if t.node_id and t.status.state >= TaskState.ASSIGNED
        and t.desired_state <= TaskState.COMPLETE)
    assert placed >= N_LO - preemptions + pending_bands, \
        f"cfg8: only {placed} live placed (preemptions={preemptions})"
    assert preemptions > 0, "cfg8 ran without a single preemption"
    return {
        "nodes": N_N, "tasks": N_LO + pending_bands,
        "pending_arrivals": pending_bands,
        "priority_bands": 3,
        "decisions": n_dec,
        "decisions_per_sec": round(n_dec / dt, 1),
        "tick_s": round(dt, 3),
        "plan_s": round(planner.stats["plan_seconds"], 3),
        "commit_s": round(sched.stats["commit_seconds"], 3),
        "preemptions": preemptions,
        "fallback_groups": routed["groups_fallback"],
        "path": "device+preempt",
        "shape_cost_x": 1.0,
        "compiles": _compile_delta(snap),
    }


def run_autoscale_tenant_storm(planner_factory):
    """Config 9: autoscaler + tenant QoS under a burst (ISSUE 12).  256
    nodes run a high-band tenant (400 tasks, must all place) while a
    quota'd low-band tenant bursts: one service asks 500 tasks against
    a 300-task quota (admission clamps the overflow), a second same-
    tenant service's whole group arrives with the tenant exhausted —
    the DEVICE quota-mask column rejects it end to end.  The timed
    window covers one autoscaler drive (the supervisor's decision
    write) plus the storm tick; scripts/bench_compare.py gates on
    ``quota_clamps`` > 0 with ZERO XLA compiles inside the window (the
    warm-up pass below covers the quota-mask signatures)."""
    _trim_heap()
    from swarmkit_tpu.models import (
        Annotations, Node, NodeDescription, NodeSpec, NodeState,
        NodeStatus, ReplicatedService, Resources, ResourceRequirements,
        Service, ServiceMode, ServiceSpec, Task, TaskSpec, TaskState,
        TaskStatus, Version,
    )
    from swarmkit_tpu.models.specs import AutoscaleConfig
    from swarmkit_tpu.models.objects import Cluster
    from swarmkit_tpu.models.specs import ClusterSpec
    from swarmkit_tpu.models.types import TenantQuota
    from swarmkit_tpu.orchestrator.autoscaler import (
        Supervisor as AutoscaleSupervisor,
    )
    from swarmkit_tpu.scheduler import Scheduler
    from swarmkit_tpu.scheduler.quota import TENANT_LABEL
    from swarmkit_tpu.state import MemoryStore
    from swarmkit_tpu.utils import new_id

    N_N = int(os.environ.get("BENCH_CFG9_NODES", 256))
    CPU = 2 * 10 ** 9
    # band sizes derive from capacity (4 slots per 8-cpu node) so the
    # config scales with BENCH_CFG9_NODES: the high band + the burst
    # tenant's quota together stay ~70% of the cluster — the blocked
    # service must fail on QUOTA, not on capacity
    slots = N_N * 4
    N_HI = slots * 2 // 5
    QUOTA_TASKS = slots * 3 // 10
    N_BURST = QUOTA_TASKS + max(slots // 5, 50)
    N_BLOCKED = max(slots // 8, 16)

    def build():
        store = MemoryStore()
        store.update(lambda tx: tx.create(Cluster(
            id=new_id(),
            spec=ClusterSpec(
                annotations=Annotations(name="default"),
                tenants={
                    "burst": TenantQuota(nano_cpus=QUOTA_TASKS * CPU),
                    "hi": TenantQuota(nano_cpus=1000 * CPU)}))))

        def mk_nodes(tx):
            for i in range(N_N):
                tx.create(Node(
                    id=new_id(),
                    spec=NodeSpec(
                        annotations=Annotations(name=f"q{i:04d}")),
                    status=NodeStatus(state=NodeState.READY),
                    description=NodeDescription(
                        hostname=f"q{i:04d}",
                        resources=Resources(nano_cpus=8 * 10 ** 9,
                                            memory_bytes=32 << 30))))
        store.update(mk_nodes)
        res = ResourceRequirements(
            reservations=Resources(nano_cpus=CPU, memory_bytes=1 << 30))
        plan = (("hi", "hi", 2, N_HI, None),
                ("burst", "burst", 0, N_BURST,
                 AutoscaleConfig(min_replicas=2, max_replicas=N_BURST,
                                 target_utilization=1.0,
                                 stabilization_window=0.0)),
                ("blocked", "burst", 0, N_BLOCKED, None))
        svcs = {}

        def mk_svcs(tx):
            for name, tenant, prio, count, autoscale in plan:
                spec = TaskSpec(resources=res, priority=prio)
                svc = Service(
                    id=new_id(),
                    spec=ServiceSpec(
                        annotations=Annotations(
                            name=f"t-{name}",
                            labels={TENANT_LABEL: tenant}),
                        mode=ServiceMode.REPLICATED,
                        # the burst service starts small so the timed
                        # autoscaler drive commits a real scale-up
                        # decision against the sampled load
                        replicated=ReplicatedService(
                            replicas=2 if autoscale else count),
                        task=spec,
                        autoscale=autoscale),
                    spec_version=Version(index=1))
                svcs[name] = svc
                tx.create(svc)
        store.update(mk_svcs)

        def mk_tasks(tx):
            for name, _tenant, prio, count, _a in plan:
                svc = svcs[name]
                for s in range(count):
                    tx.create(Task(
                        id=new_id(), service_id=svc.id, slot=s + 1,
                        desired_state=TaskState.RUNNING,
                        spec=svc.spec.task,
                        spec_version=Version(index=1),
                        service_annotations=svc.spec.annotations,
                        status=TaskStatus(state=TaskState.PENDING)))
        store.update(mk_tasks)
        return store, svcs

    def one_pass(store, svcs):
        planner = planner_factory()
        sched = Scheduler(store, batch_planner=planner)
        store.view(sched._setup_tasks_list)
        scaler = AutoscaleSupervisor(
            store,
            sampler=lambda sid: {"load": float(N_BURST)}
            if sid == svcs["burst"].id else None,
            start_worker=False)
        gc.collect()
        gc.freeze()
        t0 = time.perf_counter()
        scaler.drive()
        n_dec = sched.tick()
        dt = time.perf_counter() - t0
        gc.unfreeze()
        return sched, planner, scaler, n_dec, dt

    from swarmkit_tpu.obs import tracer as _tracer
    was_tracing = _tracer.enabled
    _tracer.disable()
    try:
        one_pass(*build())   # warm-up: every jit signature incl. quota
        _trim_heap()
    finally:
        _tracer.enabled = was_tracing

    store, svcs = build()
    snap = _planner_counter_snapshot()
    sched, planner, scaler, n_dec, dt = one_pass(store, svcs)
    routed = _planner_counter_delta(snap)
    clamps = sched.stats.get("quota_clamps", 0)
    assert scaler.stats["decisions"] > 0, \
        "cfg9 autoscaler made no decision in the timed window"

    tasks = store.view(lambda tx: tx.find(Task))
    by_svc = {}
    for t in tasks:
        if t.node_id and t.status.state >= TaskState.ASSIGNED:
            by_svc[t.service_id] = by_svc.get(t.service_id, 0) + 1
    placed_hi = by_svc.get(svcs["hi"].id, 0)
    placed_burst = by_svc.get(svcs["burst"].id, 0)
    placed_blocked = by_svc.get(svcs["blocked"].id, 0)
    assert placed_hi == N_HI, \
        f"cfg9: high band placed {placed_hi}/{N_HI}"
    assert placed_burst <= QUOTA_TASKS, \
        f"cfg9: burst tenant exceeded its quota ({placed_burst})"
    assert clamps > 0, "cfg9 ran without a single quota clamp"
    assert placed_blocked == 0, \
        f"cfg9: exhausted tenant still placed {placed_blocked}"
    blocked_err = next(
        (t.status.err for t in tasks
         if t.service_id == svcs["blocked"].id), "")
    assert "over tenant quota" in (blocked_err or ""), blocked_err
    return {
        "nodes": N_N, "tasks": N_HI + N_BURST + N_BLOCKED,
        "tenants": 2,
        "decisions": n_dec,
        "decisions_per_sec": round(n_dec / dt, 1),
        "tick_s": round(dt, 3),
        "plan_s": round(planner.stats["plan_seconds"], 3),
        "commit_s": round(sched.stats["commit_seconds"], 3),
        "quota_clamps": clamps,
        "autoscale_decisions": scaler.stats["decisions"],
        "fallback_groups": routed["groups_fallback"],
        "path": "device+quota-mask",
        "shape_cost_x": 1.0,
        "compiles": _compile_delta(snap),
    }


def run_steady_state_churn(planner_factory):
    """Config 10: SUSTAINED decisions/sec under Poisson churn — the
    streaming scheduler's production shape (ISSUE 14).  A big cluster
    sits in steady state (base tasks RUNNING everywhere) while every
    window brings small Poisson batches of arrivals and exits; each
    window ends in one scheduler tick driven through the real store
    watch feed (the streaming delta source).  The SAME seeded workload
    runs twice: once with the streaming plane on (device-resident node
    state, dirty-row refresh) and once forced to full replans
    (``SWARM_STREAMING_PLANNER=0`` posture) — the headline is the
    sustained-rate ratio, and placements must be byte-identical
    between the two passes.  scripts/bench_compare.py gates on the
    streaming plane being ACTIVE (incremental ticks > 0), zero XLA
    compiles inside the timed windows, and the pending->assigned p99
    not regressing >20% run-over-run (the obs lifecycle timer,
    measured per window from the same watch feed)."""
    _trim_heap()
    import random as _random
    from swarmkit_tpu.models import (
        Annotations, Node, NodeDescription, NodeSpec, NodeState,
        NodeStatus, ReplicatedService, Resources, ResourceRequirements,
        Service, ServiceMode, ServiceSpec, Task, TaskSpec, TaskState,
        TaskStatus, Version,
    )
    from swarmkit_tpu.models.types import now
    from swarmkit_tpu.obs import devicetelemetry as _devtel
    from swarmkit_tpu.obs.lifecycle import LifecycleTracker
    from swarmkit_tpu.utils.sampling import poisson as _poisson
    from swarmkit_tpu.scheduler import Scheduler
    from swarmkit_tpu.state import MemoryStore
    from swarmkit_tpu.state.events import Event, EventSnapshotRestore
    from swarmkit_tpu.utils.metrics import Registry

    from swarmkit_tpu.models import Placement, PlacementPreference, \
        Platform, SpreadOver

    N_N = int(os.environ.get("BENCH_CFG10_NODES", 8192))
    N_BASE = int(os.environ.get("BENCH_CFG10_BASE_TASKS", 12_000))
    WINDOWS = int(os.environ.get("BENCH_CFG10_WINDOWS", 12))
    SEED = int(os.environ.get("BENCH_CFG10_SEED", 1))
    CPU = 10 ** 8
    MEM = 64 << 20
    SVCS = ("ca", "cb", "cc", "cd", "ce", "cf")
    LAM_ARRIVE = 40.0      # for the window's (rotating) arrival service
    LAM_EXIT = 18.0        # per window

    # production spec shapes: constraints, platform requirements and a
    # spread preference — the per-group column builders these demand
    # (constraint/platform hash columns, spread leaves) are exactly the
    # feasibility-mask precursors the resident state keeps, so the
    # full-replan side pays their O(cluster) Python densification per
    # tick while the streaming side refreshes dirty rows
    res = ResourceRequirements(
        reservations=Resources(nano_cpus=CPU, memory_bytes=MEM))
    specs = {
        "ca": TaskSpec(resources=res),
        "cb": TaskSpec(resources=res, placement=Placement(
            constraints=["node.labels.tier==web"],
            platforms=[Platform(os="linux", architecture="amd64")])),
        "cc": TaskSpec(resources=res, placement=Placement(
            preferences=[PlacementPreference(spread=SpreadOver(
                spread_descriptor="node.labels.rack"))])),
        "cd": TaskSpec(resources=res, placement=Placement(
            constraints=["node.labels.rack!=r03"],
            platforms=[Platform(os="linux", architecture="amd64")])),
        "ce": TaskSpec(resources=res, placement=Placement(
            constraints=["node.hostname!=c99999"],
            platforms=[Platform(os="linux", architecture="amd64")],
            preferences=[PlacementPreference(spread=SpreadOver(
                spread_descriptor="node.labels.rack"))])),
        "cf": TaskSpec(resources=res, placement=Placement(
            constraints=["node.labels.rack!=r07"],
            platforms=[Platform(os="linux", architecture="amd64")])),
    }
    # arrivals rotate over the production-shaped services; the plain
    # service stays as base load
    ARRIVE_SVCS = ("cb", "cc", "cd", "ce", "cf")

    def workload_script(windows):
        """Precompute the whole churn (seeded) so both passes replay
        byte-identical arrivals/exits.  Each window's arrivals hit ONE
        (rotating) service — the steady-state shape: small bursts, not
        every service at once, so the full-replan side re-densifies the
        whole cluster for a single group's worth of decisions."""
        rng = _random.Random(SEED)
        script = []
        for w in range(windows):
            sid = ARRIVE_SVCS[w % len(ARRIVE_SVCS)]
            arrivals = {sid: max(1, _poisson(rng, LAM_ARRIVE))}
            script.append((arrivals, _poisson(rng, LAM_EXIT)))
        return script

    def build():
        store = MemoryStore()
        nodes = [Node(
            id=f"c{i:05d}",
            spec=NodeSpec(annotations=Annotations(
                name=f"c{i:05d}",
                labels={"tier": "web" if i % 2 else "db",
                        "rack": f"r{i % 16:02d}"})),
            status=NodeStatus(state=NodeState.READY),
            description=NodeDescription(
                hostname=f"c{i:05d}",
                platform=Platform(os="linux", architecture="amd64"),
                resources=Resources(nano_cpus=8 * 10 ** 9,
                                    memory_bytes=32 << 30)))
            for i in range(N_N)]
        store.update(lambda tx: [tx.create(n) for n in nodes])

        def mk_svcs(tx):
            for sid in SVCS:
                tx.create(Service(
                    id=sid,
                    spec=ServiceSpec(
                        annotations=Annotations(name=sid),
                        mode=ServiceMode.REPLICATED,
                        replicated=ReplicatedService(replicas=0),
                        task=specs[sid]),
                    spec_version=Version(index=1)))
        store.update(mk_svcs)

        def mk_base(tx):
            for k in range(N_BASE):
                sid = SVCS[k % len(SVCS)]
                tx.create(Task(
                    id=f"{sid}-base{k:06d}", service_id=sid,
                    slot=k + 1, desired_state=TaskState.RUNNING,
                    spec=specs[sid], spec_version=Version(index=1),
                    node_id=nodes[k % N_N].id,
                    status=TaskStatus(state=TaskState.RUNNING)))
        store.update(mk_base)
        return store

    def one_pass(streaming, windows):
        store = build()
        planner = planner_factory()
        planner.enable_small_group_routing = False
        planner.streaming_enabled = streaming
        sched = Scheduler(store, batch_planner=planner,
                          pipeline_depth=1)
        _, sub = store.view_and_watch(
            lambda tx: sched._setup_tasks_list(tx), accepts_blocks=True)
        lreg = Registry()
        lt = LifecycleTracker(registry=lreg)
        seqs = {sid: 0 for sid in SVCS}
        script = workload_script(windows)

        def pump():
            while True:
                ev = sub.poll()
                if ev is None:
                    return
                lt.handle_event(ev)
                if isinstance(ev, EventSnapshotRestore):
                    sched._resync()
                elif isinstance(ev, Event):
                    sched._handle_event(ev)

        def add(sid, n):
            spec = specs[sid]
            base = seqs[sid]

            def cb(tx):
                ts = now()
                for k in range(n):
                    tx.create(Task(
                        id=f"{sid}-a{base + k:06d}", service_id=sid,
                        slot=N_BASE + base + k + 1,
                        desired_state=TaskState.RUNNING, spec=spec,
                        spec_version=Version(index=1),
                        status=TaskStatus(state=TaskState.PENDING,
                                          timestamp=ts)))
            store.update(cb)
            seqs[sid] = base + n

        exited = {"n": 0}

        def exit_some(k):
            # deterministic victims: oldest base tasks first — the
            # same ids in both passes
            start = exited["n"]
            victims = [f"{SVCS[j % len(SVCS)]}-base{j:06d}"
                       for j in range(start, min(start + k, N_BASE))]
            exited["n"] = start + len(victims)

            def cb(tx):
                ts = now()
                for tid in victims:
                    cur = tx.get(Task, tid)
                    if cur is None:
                        continue
                    cur = cur.copy()
                    cur.status = TaskStatus(state=TaskState.COMPLETE,
                                            timestamp=ts,
                                            message="churn exit")
                    tx.update(cur)
            store.update(cb)

        sched.tick()   # cold tick outside the timed window
        gc.collect()
        gc.freeze()
        decisions = 0
        # per-reason transfer ledger around the steady-state windows
        # only: the cold tick's full upload stays out, so the delta IS
        # the steady-state churn cost the transfer-regression gate reads
        xfer_before = _devtel.snapshot()["transfers"]
        t0 = time.perf_counter()
        for arrivals, exits in script:
            for sid, n in arrivals.items():
                if n:
                    add(sid, n)
            if exits:
                exit_some(exits)
            pump()
            decisions += sched.tick()
        dt = time.perf_counter() - t0
        xfer_after = _devtel.snapshot()["transfers"]
        xfer = {
            d: {r: {k: row[k] - xfer_before.get(d, {}).get(r, {})
                    .get(k, 0) for k in row}
                for r, row in tbl.items()}
            for d, tbl in xfer_after.items()}
        gc.unfreeze()
        pump()
        store.queue.unsubscribe(sub)
        placements = sorted(
            (t.id, t.node_id) for t in store.view(
                lambda tx: tx.find(Task)))
        import hashlib
        digest = hashlib.sha256(
            repr(placements).encode()).hexdigest()
        edge = lt.summary().get("pending->assigned", {})
        return (sched, planner, decisions, dt, digest,
                edge.get("p99"), xfer)

    # warm-up: both postures once, tracer off — covers every planner
    # jit signature (incl. the streaming scatter buckets) this config
    # touches
    from swarmkit_tpu.obs import tracer as _tracer
    was_tracing = _tracer.enabled
    _tracer.disable()
    try:
        one_pass(True, 3)
        one_pass(False, 2)
        _trim_heap()
    finally:
        _tracer.enabled = was_tracing

    snap = _planner_counter_snapshot()
    (sched_s, planner_s, dec_s, dt_s, digest_s,
     p99_s, xfer_s) = one_pass(True, WINDOWS)
    (_sched_f, planner_f, dec_f, dt_f, digest_f,
     _p99_f, _xfer_f) = one_pass(False, WINDOWS)
    routed = _planner_counter_delta(snap)
    compiles = _compile_delta(snap)

    assert dec_s == dec_f, (dec_s, dec_f)
    assert digest_s == digest_f, \
        "cfg10: streaming placements diverged from full-replan"
    st = planner_s.streaming_snapshot()
    assert st["enabled"] and st["incremental_ticks"] > 0, st
    assert not planner_f.streaming_snapshot()["enabled"]
    dps_s = dec_s / dt_s if dt_s else 0.0
    dps_f = dec_f / dt_f if dt_f else 0.0
    return {
        "nodes": N_N, "base_tasks": N_BASE, "windows": WINDOWS,
        "decisions": dec_s,
        "decisions_per_sec": round(dps_s, 1),
        "full_replan_decisions_per_sec": round(dps_f, 1),
        "streaming_speedup": round(dps_s / dps_f, 2) if dps_f else None,
        "tick_s": round(dt_s, 3),
        "plan_s": round(planner_s.stats["plan_seconds"], 3),
        "commit_s": round(sched_s.stats["commit_seconds"], 3),
        "pending_assigned_p99_s": round(p99_s, 4)
        if p99_s is not None else None,
        "placements_identical": digest_s == digest_f,
        "streaming": st,
        "device_transfers": xfer_s,
        "h2d_bytes_per_tick": round(
            sum(r["bytes"] for r in xfer_s.get("h2d", {}).values())
            / float(WINDOWS), 1),
        # the resident-tier slice of that ledger: dirty-row scatters
        # (single-device and sharded) plus wide re-uploads.  Under a
        # planner mesh this is what the mesh-resident-transfer gate
        # pins at ~0 — churn must ride per-shard donated scatters,
        # not re-uploads
        "planner_mesh": _mesh_devices(),
        "resident_h2d_bytes_per_tick": round(
            sum(r["bytes"] for name, r in xfer_s.get("h2d", {}).items()
                if name in ("dirty_scatter", "shard_scatter",
                            "wide_reupload")) / float(WINDOWS), 1),
        "strategy_host_groups": int(
            planner_s.stats.get("groups_strategy_host", 0)
            + planner_f.stats.get("groups_strategy_host", 0)),
        "fallback_groups": routed["groups_fallback"],
        "path": "device+streaming",
        "shape_cost_x": 1.0,
        "compiles": compiles,
    }


def run_fragmentation(planner_factory):
    """Config 11: placement-strategy fragmentation (ISSUE 15).  400
    uniform nodes (16 cpu) receive mixed-size replicas — 800 small
    (1 cpu), 300 medium (4 cpu), 200 large (8 cpu) plus a 100-task
    node.ip-CIDR-constrained service (the closed device-path waiver:
    ``fallback_groups`` must stay 0) — in ONE tick, twice: every
    service under the ``spread`` strategy, then the identical workload
    under ``binpack``.  Reported per pass: decisions/sec (the spread
    pass is "spread through the strategy seam" — bench_compare gates
    its regression at 10%) and the STRANDED-CAPACITY fraction: the
    share of free cpu sitting on partially-loaded nodes in slices too
    small to hold one more large replica.  bench_compare gates
    binpack < spread on that fraction, zero strategy fallbacks, and
    compile-flat timed windows (the warm-up pass covers the strategy
    kernels' signatures)."""
    _trim_heap()
    from swarmkit_tpu.models import (
        Annotations, Node, NodeDescription, NodeSpec, NodeState,
        NodeStatus, Placement, ReplicatedService, Resources,
        ResourceRequirements, Service, ServiceMode, ServiceSpec, Task,
        TaskSpec, TaskState, TaskStatus, Version,
    )
    from swarmkit_tpu.scheduler import Scheduler
    from swarmkit_tpu.state import MemoryStore
    from swarmkit_tpu.utils import new_id
    from swarmkit_tpu.utils.metrics import registry as _reg

    N_N = int(os.environ.get("BENCH_CFG11_NODES", 400))
    CPU_UNIT = 10 ** 9
    NODE_CPU = 16 * CPU_UNIT
    LARGE_D = 8 * CPU_UNIT
    MIXES = (("small", 1, 800), ("medium", 4, 300), ("large", 8, 200))
    N_IP = 100

    def build(strategy):
        store = MemoryStore()
        nodes = []
        for i in range(N_N):
            # two /16s: the CIDR-constrained service may only use 10.0/16
            addr = f"10.{i % 2}.{(i // 2) // 250}.{(i // 2) % 250 + 1}"
            nodes.append(Node(
                id=new_id(),
                spec=NodeSpec(annotations=Annotations(name=f"f{i:04d}")),
                status=NodeStatus(state=NodeState.READY, addr=addr),
                description=NodeDescription(
                    hostname=f"f{i:04d}",
                    resources=Resources(nano_cpus=NODE_CPU,
                                        memory_bytes=64 << 30))))
        svcs, tasks = [], []

        def add_service(name, cpus, count, constraints=None):
            spec = TaskSpec(
                resources=ResourceRequirements(reservations=Resources(
                    nano_cpus=cpus * CPU_UNIT,
                    memory_bytes=(cpus << 30) // 4)),
                placement=Placement(constraints=constraints or [],
                                    strategy=strategy))
            svc = Service(
                id=new_id(),
                spec=ServiceSpec(annotations=Annotations(name=name),
                                 mode=ServiceMode.REPLICATED,
                                 replicated=ReplicatedService(
                                     replicas=count),
                                 task=spec),
                spec_version=Version(index=1))
            svcs.append(svc)
            for s in range(count):
                tasks.append(Task(
                    id=new_id(), service_id=svc.id, slot=s + 1,
                    desired_state=TaskState.RUNNING, spec=spec,
                    spec_version=Version(index=1),
                    status=TaskStatus(state=TaskState.PENDING)))

        for name, cpus, count in MIXES:
            add_service(f"frag-{name}", cpus, count)
        add_service("frag-ip", 1, N_IP,
                    constraints=["node.ip==10.0.0.0/16"])

        def mk(tx):
            for n in nodes:
                tx.create(n)
            for s in svcs:
                tx.create(s)
        store.update(mk)
        store.update(lambda tx: (
            [tx.create(t) for t in tasks] and None))
        n_tasks = sum(c for _, _, c in MIXES) + N_IP
        return store, n_tasks

    def one_pass(strategy):
        store, n_tasks = build(strategy)
        planner = planner_factory()
        sched = Scheduler(store, batch_planner=planner)
        store.view(sched._setup_tasks_list)
        gc.collect()
        gc.freeze()
        t0 = time.perf_counter()
        n_dec = sched.tick()
        dt = time.perf_counter() - t0
        gc.unfreeze()
        placed = sum(
            1 for t in store.view(lambda tx: tx.find(Task))
            if t.node_id and t.status.state >= TaskState.ASSIGNED)
        assert placed == n_tasks, \
            f"cfg11/{strategy}: {placed}/{n_tasks} placed"
        # stranded capacity: free cpu on PARTIALLY loaded nodes in
        # slices too small for one more large replica, as a fraction
        # of all free cpu
        free = [info.available_resources.nano_cpus
                for info in sched.node_set.nodes.values()]
        total_free = sum(free)
        stranded = sum(f for f in free if 0 < f < LARGE_D)
        frac = stranded / total_free if total_free else 0.0
        ip_nodes = {t.node_id for t in store.view(
            lambda tx: tx.find(Task))
            if t.node_id and t.spec.placement
            and t.spec.placement.constraints}
        addr_of = {n.id: n.status.addr for n in store.view(
            lambda tx: tx.find(Node))}
        assert all(addr_of[nid].startswith("10.0.")
                   for nid in ip_nodes), "cfg11: CIDR constraint leaked"
        return planner, sched, n_dec, dt, frac

    # warm-up: both strategies once, tracer off — covers the spread
    # AND strategy-kernel jit signatures this config touches
    from swarmkit_tpu.obs import tracer as _tracer
    was_tracing = _tracer.enabled
    _tracer.disable()
    try:
        one_pass("spread")
        one_pass("binpack")
        _trim_heap()
    finally:
        _tracer.enabled = was_tracing

    snap = _planner_counter_snapshot()
    fb0 = sum(_reg.get_counter(
        f'swarm_strategy_fallbacks{{strategy="{s}"}}')
        for s in ("spread", "binpack"))
    dev0 = _reg.get_counter(
        'swarm_strategy_groups{route="device",strategy="binpack"}')
    _, _, dec_sp, dt_sp, frac_sp = one_pass("spread")
    planner_bp, _, dec_bp, dt_bp, frac_bp = one_pass("binpack")
    routed = _planner_counter_delta(snap)
    fallbacks = int(sum(_reg.get_counter(
        f'swarm_strategy_fallbacks{{strategy="{s}"}}')
        for s in ("spread", "binpack")) - fb0)
    binpack_device_groups = int(_reg.get_counter(
        'swarm_strategy_groups{route="device",strategy="binpack"}')
        - dev0)
    return {
        "nodes": N_N,
        "tasks": sum(c for _, _, c in MIXES) + N_IP,
        "decisions": dec_sp,
        "decisions_per_sec": round(dec_sp / dt_sp, 1),
        "spread_decisions_per_sec": round(dec_sp / dt_sp, 1),
        "binpack_decisions_per_sec": round(dec_bp / dt_bp, 1),
        "stranded_frac_spread": round(frac_sp, 4),
        "stranded_frac_binpack": round(frac_bp, 4),
        "stranded_improvement_x": round(frac_sp / frac_bp, 2)
        if frac_bp else None,
        "strategy_fallbacks": fallbacks,
        "binpack_device_groups": binpack_device_groups,
        "tick_s": round(dt_sp, 3),
        "fallback_groups": routed["groups_fallback"],
        "path": "device+strategy",
        "shape_cost_x": 1.0,
        "compiles": _compile_delta(snap),
    }


def run_gang_pipeline(planner_factory):
    """Config 12: gang scheduling & pipeline workflows (ISSUE 16).
    400 uniform nodes (16 cpu) receive a mixed gang fleet — 24
    single-service gangs of 8 (4-cpu members), 40 of 4 (2-cpu), and
    8 cross-service gangs of 8 stitched by ``gang_id`` (the fused
    ``gang_fit`` route) — plus a 3-stage pipeline a -> b -> c (120
    replicas each).  Tick 1 admits every gang atomically and places
    stage a while b and c hold at the DAG gate; releasing b then c
    drains the pipeline over two more ticks.  The identical workload
    with gang/pipeline fields stripped runs the plain path in one
    tick for comparison.  bench_compare gates: zero partially-placed
    gangs, zero gang deferrals, the gate actually held (gated
    deferrals > 0) then drained, device gang route (0 host-oracle
    verdicts), compile-flat timed windows, and the gang tick's dec/s
    within 4x of the plain tick's."""
    _trim_heap()
    from swarmkit_tpu.models import (
        Annotations, GangConfig, Node, NodeDescription, NodeSpec,
        NodeState, NodeStatus, PipelineStatus, Placement,
        ReplicatedService, Resources, ResourceRequirements, Service,
        ServiceMode, ServiceSpec, Task, TaskSpec, TaskState,
        TaskStatus, Version,
    )
    from swarmkit_tpu.scheduler import Scheduler
    from swarmkit_tpu.state import MemoryStore
    from swarmkit_tpu.utils import new_id
    from swarmkit_tpu.utils.metrics import registry as _reg

    N_N = int(os.environ.get("BENCH_CFG12_NODES", 400))
    CPU_UNIT = 10 ** 9
    NODE_CPU = 16 * CPU_UNIT
    GANGS = (("gang8", 24, 8, 4), ("gang4", 40, 4, 2))  # name,n,size,cpu
    N_XGANG = 8          # cross-service gangs: 2 services x 4 members
    N_STAGE = 120        # replicas per pipeline stage

    def build(gang):
        store = MemoryStore()
        nodes = [Node(
            id=new_id(),
            spec=NodeSpec(annotations=Annotations(name=f"g{i:04d}")),
            status=NodeStatus(state=NodeState.READY,
                              addr=f"10.{i // 250}.0.{i % 250 + 1}"),
            description=NodeDescription(
                hostname=f"g{i:04d}",
                resources=Resources(nano_cpus=NODE_CPU,
                                    memory_bytes=64 << 30)))
            for i in range(N_N)]
        svcs, tasks = [], []

        def add_service(name, cpus, count, min_size=0, gang_id="",
                        depends_on=()):
            placement = (Placement(gang=GangConfig(min_size=min_size))
                         if gang and min_size else Placement())
            spec = TaskSpec(
                resources=ResourceRequirements(reservations=Resources(
                    nano_cpus=cpus * CPU_UNIT,
                    memory_bytes=(cpus << 30) // 4)),
                placement=placement,
                gang_id=gang_id if gang else "")
            svc = Service(
                id=new_id(),
                spec=ServiceSpec(
                    annotations=Annotations(name=name),
                    mode=ServiceMode.REPLICATED,
                    replicated=ReplicatedService(replicas=count),
                    task=spec,
                    depends_on=list(depends_on) if gang else []),
                spec_version=Version(index=1))
            svcs.append(svc)
            for s in range(count):
                tasks.append(Task(
                    id=new_id(), service_id=svc.id, slot=s + 1,
                    desired_state=TaskState.RUNNING, spec=spec,
                    spec_version=Version(index=1),
                    status=TaskStatus(state=TaskState.PENDING)))

        for prefix, n_gangs, size, cpus in GANGS:
            for g in range(n_gangs):
                add_service(f"{prefix}-{g:02d}", cpus, size,
                            min_size=size)
        for g in range(N_XGANG):
            for half in "ab":
                add_service(f"xgang-{g}{half}", 2, 4, min_size=8,
                            gang_id=f"xg{g}")
        add_service("stage-a", 1, N_STAGE)
        add_service("stage-b", 1, N_STAGE, depends_on=("stage-a",))
        add_service("stage-c", 1, N_STAGE, depends_on=("stage-b",))

        def mk(tx):
            for n in nodes:
                tx.create(n)
            for s in svcs:
                tx.create(s)
        store.update(mk)
        store.update(lambda tx: (
            [tx.create(t) for t in tasks] and None))
        return store, svcs, len(tasks)

    def release(store, svcs, name):
        sid = next(s.id for s in svcs
                   if s.spec.annotations.name == name)

        def cb(tx):
            cur = tx.get(Service, sid).copy()
            cur.pipeline_status = PipelineStatus(state="released")
            tx.update(cur)
        store.update(cb)

    def placed_ids(store):
        return {t.id for t in store.view(lambda tx: tx.find(Task))
                if t.node_id and t.status.state >= TaskState.ASSIGNED}

    def one_pass(gang):
        store, svcs, n_tasks = build(gang)
        planner = planner_factory()
        sched = Scheduler(store, batch_planner=planner)
        store.view(sched._setup_tasks_list)
        gc.collect()
        gc.freeze()
        t0 = time.perf_counter()
        dec1 = sched.tick()
        dt1 = time.perf_counter() - t0
        gc.unfreeze()
        placed1 = placed_ids(store)
        gated = 0
        if gang:
            # gate evidence: b and c held at tick 1, then drain after
            # their releases — the DAG-gated rollout end to end
            by_svc = {s.id: s.spec.annotations.name for s in svcs}
            gated = sum(
                1 for t in store.view(lambda tx: tx.find(Task))
                if t.id not in placed1
                and by_svc[t.service_id] in ("stage-b", "stage-c"))
            release(store, svcs, "stage-b")
            sched.tick()
            release(store, svcs, "stage-c")
            sched.tick()
        n_placed = len(placed_ids(store))
        assert n_placed == n_tasks, \
            f"cfg12/gang={gang}: {n_placed}/{n_tasks} placed"
        # atomicity evidence: every gang unit fully placed or fully
        # pending after tick 1 — a strict subset is a violation
        partial = 0
        if gang:
            from swarmkit_tpu.scheduler.gang import gang_unit, is_gang
            units = {}
            for t in store.view(lambda tx: tx.find(Task)):
                if is_gang(t):
                    units.setdefault(gang_unit(t), []).append(
                        t.id in placed1)
            partial = sum(1 for flags in units.values()
                          if any(flags) and not all(flags))
        return dec1, dt1, gated, partial

    # warm-up: both shapes once, tracer off — covers the gang_fit
    # (_gf/_gfF) and plain-path jit signatures this config touches
    from swarmkit_tpu.obs import tracer as _tracer
    was_tracing = _tracer.enabled
    _tracer.disable()
    try:
        one_pass(True)
        one_pass(False)
        _trim_heap()
    finally:
        _tracer.enabled = was_tracing

    snap = _planner_counter_snapshot()
    base = {k: _reg.get_counter(k) for k in (
        "swarm_gang_admitted", "swarm_gang_deferred",
        "swarm_planner_gang_fit_host",
        "swarm_planner_gang_fit_device",
        "swarm_planner_gang_fit_fused")}
    dec_g, dt_g, gated, partial = one_pass(True)
    dec_p, dt_p, _, _ = one_pass(False)
    delta = {k: int(_reg.get_counter(k) - v) for k, v in base.items()}
    n_gangs = sum(n for _, n, _, _ in GANGS) + N_XGANG
    return {
        "nodes": N_N,
        "tasks": dec_p,
        "decisions": dec_g,
        "decisions_per_sec": round(dec_g / dt_g, 1),
        "gang_decisions_per_sec": round(dec_g / dt_g, 1),
        "plain_decisions_per_sec": round(dec_p / dt_p, 1),
        "gang_vs_plain_x": round((dec_p / dt_p) / (dec_g / dt_g), 2)
        if dec_g else None,
        "gangs": n_gangs,
        "gangs_admitted": delta["swarm_gang_admitted"],
        "gang_deferred": delta["swarm_gang_deferred"],
        "gang_atomicity_violations": partial,
        "gang_fit_host_verdicts": delta["swarm_planner_gang_fit_host"],
        "gang_fit_device_verdicts":
            delta["swarm_planner_gang_fit_device"]
            + delta["swarm_planner_gang_fit_fused"],
        "pipeline_gated_deferrals": gated,
        "pipeline_stages": 3,
        "tick_s": round(dt_g, 3),
        "path": "device+gang",
        "shape_cost_x": 1.0,
        "compiles": _compile_delta(snap),
    }


def run_e2e(n_agents=5,
            n_replicas=int(os.environ.get("BENCH_E2E_REPLICAS", 500))):
    """swarm-bench equivalent: create an N-replica service and measure
    per-task time from service creation to RUNNING status committed
    (reference: cmd/swarm-bench collector.go percentiles)."""
    _trim_heap()
    import time as time_mod

    from swarmkit_tpu.agent import Agent
    from swarmkit_tpu.agent.testutils import TestExecutor
    from swarmkit_tpu.manager import Manager
    from swarmkit_tpu.manager.dispatcher import Config_
    from swarmkit_tpu.models import TaskState

    # a fresh journey ledger for the e2e window: the headline trials
    # above already filled the cap with their (created-less) tasks,
    # which would refuse every e2e task and starve the attribution
    from swarmkit_tpu.obs.journey import journeys
    journeys.reset(sample_rate=1.0)
    mgr = Manager(dispatcher_config=Config_(
        heartbeat_period=2.0, process_updates_interval=0.05,
        assignment_batching_wait=0.05))
    mgr.run()
    agents = []
    try:
        from swarmkit_tpu.models import (
            Annotations, Node, NodeDescription, NodeSpec, NodeState,
            NodeStatus, Resources,
        )
        from swarmkit_tpu.utils import new_id
        for i in range(n_agents):
            node = Node(
                id=new_id(),
                spec=NodeSpec(annotations=Annotations(name=f"bench-w{i}")),
                status=NodeStatus(state=NodeState.READY),
                description=NodeDescription(
                    hostname=f"bench-w{i}",
                    resources=Resources(nano_cpus=64 * 10**9,
                                        memory_bytes=256 << 30)))
            mgr.store.update(lambda tx, node=node: tx.create(node))
            a = Agent(node.id, TestExecutor(hostname=f"bench-w{i}"),
                      mgr.dispatcher)
            a.start()
            agents.append(a)

        from swarmkit_tpu.models import (
            ReplicatedService, ServiceMode, ServiceSpec, TaskSpec,
        )
        from swarmkit_tpu.models.specs import ContainerSpec

        spec = ServiceSpec(
            annotations=Annotations(name="e2e-bench"),
            task=TaskSpec(container=ContainerSpec(image="bench")),
            mode=ServiceMode.REPLICATED,
            replicated=ReplicatedService(replicas=n_replicas))
        t_create = time_mod.time()
        svc = mgr.control_api.create_service(spec)

        deadline = time_mod.time() + 120
        latencies = []
        while time_mod.time() < deadline:
            tasks = mgr.control_api.list_tasks(service_id=svc.id)
            done = [t for t in tasks
                    if t.status.state == TaskState.RUNNING
                    and t.desired_state == TaskState.RUNNING]
            if len(done) >= n_replicas:
                # applied_at is stamped by the dispatcher on status commit
                latencies = sorted(
                    (t.status.applied_at or t.status.timestamp) - t_create
                    for t in done)
                break
            time_mod.sleep(0.1)
        if not latencies:
            return {"error": "did not converge"}

        def pct(p):
            return round(latencies[min(len(latencies) - 1,
                                       int(p * len(latencies)))], 3)
        # fold any still-buffered store events, then join journeys into
        # the per-plane attribution of time-to-running p99 (ISSUE 17):
        # which plane the slow cohort's wall time actually sat in
        from swarmkit_tpu.obs import flightrec as _fr
        _fr.poll_store()
        return {
            "agents": n_agents, "replicas": n_replicas,
            "p50_s": pct(0.50), "p90_s": pct(0.90), "p99_s": pct(0.99),
            "max_s": round(latencies[-1], 3),
            "journey_attribution": journeys.critical_path(0.99),
            "journey_summary": journeys.summary(),
        }
    finally:
        for a in agents:
            a.stop()
        mgr.stop()


def run_million_swarm(planner_factory):
    """Config 13: overload-safe serving at fleet scale — >=1k REAL
    dispatcher sessions over ONE threadless dispatcher (batched
    assignment fan-out, bounded session/update/assignment bookkeeping)
    carrying a ~1M-replica fan-out end to end.  Phases: register the
    fleet (heartbeat stretch engages as the session count passes the
    threshold), open every assignment stream, schedule the full replica
    set in one timed tick (compiles must be zero — same warm-up
    discipline as cfg6/7), deliver assignments through the batched
    fan-out, then absorb the status-writeback storm at the bounded
    admission edge: batches that would overflow the buffer are shed
    WHOLE with ErrOverloaded, counted on both sides of the RPC, and
    re-sent by the client next round until every replica is RUNNING —
    degraded, never silently lossy.  Records time-to-running
    percentiles (tick start -> RUNNING committed), the exact
    shed/recovery ledger, heartbeat-stretch evidence, fan-out traffic,
    and the dispatcher/scheduler plane saturation snapshot.
    BENCH_CFG13_* env knobs scale it; defaults hit the 1k-session x
    1M-replica target shape."""
    _trim_heap()
    import time as time_mod

    from swarmkit_tpu.manager.dispatcher import (
        Config_ as _DCfg, Dispatcher, ErrOverloaded,
    )
    from swarmkit_tpu.models import (
        Resources, Task as _Task, TaskState, TaskStatus,
    )
    from swarmkit_tpu.obs.planes import plane as _plane

    n_agents = int(os.environ.get("BENCH_CFG13_AGENTS", 1000))
    n_replicas = int(os.environ.get("BENCH_CFG13_REPLICAS", 1_000_000))
    n_services = int(os.environ.get("BENCH_CFG13_SERVICES", 10))
    pending_cap = int(os.environ.get("BENCH_CFG13_PENDING_CAP", 65_536))
    report_batch = int(os.environ.get("BENCH_CFG13_REPORT_BATCH", 1024))

    # the default bench reservation (0.1 CPU) caps a 64-CPU node at 640
    # tasks — 1000 nodes would top out at 640k replicas.  This config
    # models the 1000x-agent serving shape: light replicas, ~3200/node
    # CPU headroom so the full 1M fan-out fits with imbalance slack
    _rsv = Resources(nano_cpus=2 * 10**7, memory_bytes=16 << 20)

    # warm-up at this config's exact fused jit signatures (same node
    # bucket, same service count) so no compile lands in the timed tick
    from swarmkit_tpu.obs import tracer as _tracer
    was_tracing = _tracer.enabled
    _tracer.disable()
    try:
        warm_store, *_ = build_cluster(n_agents, 16 * n_services,
                                       reservations=_rsv,
                                       n_services=n_services)
        warm_planner = planner_factory()
        warm_planner.enable_small_group_routing = False
        one_tick(warm_store, warm_planner)
        del warm_store, warm_planner
        # second pass with default routing: small/remainder groups may
        # take the single-group kernel at this shape — warm it too
        warm_store, *_ = build_cluster(n_agents, 16 * n_services,
                                       reservations=_rsv,
                                       n_services=n_services)
        one_tick(warm_store, planner_factory())
        del warm_store
        _trim_heap()
    finally:
        _tracer.enabled = was_tracing

    store, svc, nodes, tasks = build_cluster(n_agents, n_replicas,
                                             reservations=_rsv,
                                             n_services=n_services)
    # overload bounds live: session cap just above the fleet (steady
    # registration stays admitted), stretch threshold well under it
    # (the period MUST stretch), update buffer far under the storm
    # (the writeback MUST shed).  max_batch_items sits above the
    # admission bound so the buffer drains on this harness's explicit
    # flush turns, not behind an implicit mid-round flush.
    d = Dispatcher(store, _DCfg(
        heartbeat_period=30.0,
        max_batch_items=pending_cap * 2,
        max_sessions=n_agents + 64,
        hb_stretch_start=max(8, n_agents // 16),
        hb_stretch_max=4.0,
        max_pending_updates=pending_cap,
        max_terminal_tasks=max(1024, n_replicas // 64)))
    d.run(start_worker=False)   # threadless: this harness is the clock
    fan = d.enable_batched_fanout()
    try:
        t_reg0 = time_mod.perf_counter()
        sessions = {}
        for n in nodes:
            sessions[n.id] = d.register(n.id)[0]
        register_s = time_mod.perf_counter() - t_reg0
        stretch = d._stretch_factor()

        t_open0 = time_mod.perf_counter()
        streams = {n.id: fan.open(n.id, sessions[n.id]) for n in nodes}
        open_s = time_mod.perf_counter() - t_open0

        def drain_streams():
            msgs = changes = 0
            for s in streams.values():
                while True:
                    try:
                        m = s.get(timeout=0)
                    except Exception:   # TimeoutError / Closed: drained
                        break
                    msgs += 1
                    changes += len(m.changes)
            return msgs, changes

        # ---- timed scheduling window (compiles gated to zero)
        planner = planner_factory()
        snap = _planner_counter_snapshot()
        _plane("scheduler").roll()    # open the tick occupancy window
        t_create = time_mod.time()
        sched, n_dec, dt = one_tick(store, planner)
        _plane("scheduler").note_busy(dt)
        compiles = _compile_delta(snap)

        # ---- assignment fan-out: one subscription drains into 1k
        # bounded per-node sets; flush sends the incremental batches
        t_fan0 = time_mod.perf_counter()
        fan_msgs = fan_changes = 0
        while True:
            fan.flush()
            m, c = drain_streams()
            fan_msgs += m
            fan_changes += c
            if not m:
                break
        fanout_s = time_mod.perf_counter() - t_fan0

        # ---- status-writeback storm against the bounded admission
        # edge: every shed is counted on both sides and the batch is
        # re-queued for the next round (recovery is total by exit)
        backlog = {}
        for t in store.view(lambda tx: tx.find(_Task)):
            if t.node_id:
                backlog.setdefault(t.node_id, []).append(t.id)
        node_ids = [n.id for n in nodes]
        client = {"shed_batches": 0, "shed_updates": 0, "rounds": 0,
                  "heartbeats": 0}
        sheds0 = d.stats["sheds"]
        _plane("dispatcher").roll()   # open the writeback window
        peak_depth = 0
        t_wb0 = time_mod.perf_counter()
        rr = 0
        while backlog:
            client["rounds"] += 1
            for nid in node_ids:   # keep the 1k-session TTL wheel hot
                d.heartbeat(nid, sessions[nid])
                client["heartbeats"] += 1
            shed_this_round = 0
            for _ in range(len(node_ids)):
                nid = node_ids[rr % len(node_ids)]
                rr += 1
                ids = backlog.get(nid)
                if not ids:
                    continue
                chunk = ids[:report_batch]
                ts = time_mod.time()
                ups = [(tid, TaskStatus(state=TaskState.RUNNING,
                                        message="started",
                                        timestamp=ts))
                       for tid in chunk]
                try:
                    d.update_task_status(nid, sessions[nid], ups)
                except ErrOverloaded:
                    client["shed_batches"] += 1
                    client["shed_updates"] += len(ups)
                    shed_this_round += 1
                    if shed_this_round >= 4:
                        break   # edge saturated: drain before resending
                    continue
                del ids[:len(chunk)]
                if not ids:
                    del backlog[nid]
            peak_depth = max(peak_depth, len(d._task_updates))
            _plane("dispatcher").set_depth(peak_depth)
            with _plane("dispatcher").busy():
                d._flush_updates()      # the worker's process turn
                d.process_deadlines()   # TTL wheel + fan-out flush
            m, c = drain_streams()
            fan_msgs += m
            fan_changes += c
        writeback_s = time_mod.perf_counter() - t_wb0
        shed_count = d.stats["sheds"] - sheds0

        # the shed ledger must reconcile EXACTLY: every shed the
        # dispatcher counted is one a client observed (and re-sent)
        assert shed_count == client["shed_updates"], \
            (shed_count, client)
        assert d.stats["premature_expirations"] == 0, d.stats

        lat = sorted(
            (t.status.applied_at or t.status.timestamp) - t_create
            for t in store.view(lambda tx: tx.find(_Task))
            if t.status.state == TaskState.RUNNING)
        assert len(lat) >= n_replicas, \
            f"cfg13: only {len(lat)}/{n_replicas} RUNNING"

        def pct(p):
            return round(lat[min(len(lat) - 1, int(p * len(lat)))], 3)
        _plane("dispatcher").roll()
        _plane("scheduler").roll()
        return {
            "agents": n_agents, "replicas": n_replicas,
            "services": n_services, "sessions": len(sessions),
            "decisions": n_dec,
            "decisions_per_sec": round(n_dec / dt, 1),
            "tick_s": round(dt, 3),
            "register_s": round(register_s, 3),
            "stream_open_s": round(open_s, 3),
            "fanout_s": round(fanout_s, 3),
            "fanout_messages": fan_msgs,
            "fanout_changes": fan_changes,
            "fanout_compactions": fan.stats["compactions"],
            "writeback_s": round(writeback_s, 3),
            "writeback_rounds": client["rounds"],
            "peak_update_depth": peak_depth,
            "heartbeats": client["heartbeats"],
            "hb_stretch_factor": round(stretch, 3),
            "hb_stretches": d.stats["hb_stretches"],
            "premature_expirations": d.stats["premature_expirations"],
            "expirations": d.stats["expirations"],
            "sheds": {
                "dispatcher": shed_count,
                "client_observed": client["shed_updates"],
                "shed_batches": client["shed_batches"],
                "uncounted": shed_count - client["shed_updates"],
                "unrecovered": n_replicas - len(lat)},
            "time_to_running": {
                "p50_s": pct(0.50), "p90_s": pct(0.90),
                "p99_s": pct(0.99), "max_s": round(lat[-1], 3),
                "running": len(lat)},
            "planes": {"dispatcher": _plane("dispatcher").report(),
                       "scheduler": _plane("scheduler").report()},
            "path": "dispatcher+fanout+writeback",
            "compiles": compiles,
        }
    finally:
        d.stop()
        _trim_heap()


def main():
    from swarmkit_tpu.models import Platform, PlacementPreference, Resources, SpreadOver
    from swarmkit_tpu.obs import tracer
    from swarmkit_tpu.obs.report import phase_table
    from swarmkit_tpu.ops import TPUPlanner

    tpu = TPUPlanner

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"bench: device {device}", file=sys.stderr)

    # warm the kernel compile cache for each (node-bucket, spread-level)
    # jit signature used below, outside the timed regions
    rack_pref = [PlacementPreference(
        spread=SpreadOver(spread_descriptor="node.labels.rack"))]
    warm = [(N_NODES, None)]
    if _cfg_enabled(1):
        warm += [(100, None)]
    if _cfg_enabled(3):
        warm += [(5_000, None)]
    if _cfg_enabled(4):
        warm += [(N_NODES, rack_pref)]
    for n_nodes, prefs in warm:
        store, svc, nodes, tasks = build_cluster(
            n_nodes, 64, prefs=prefs)
        warm_planner = TPUPlanner()
        warm_planner.enable_small_group_routing = False  # compile shapes
        one_tick(store, warm_planner)
    # the adaptive router's launch-overhead probe compiles its own tiny
    # shape on first use — warm it here or the FIRST headline trial pays
    # a ~1s jit compile and p99 reports compile time, not scheduling
    TPUPlanner()._measure_launch_overhead()
    if _cfg_enabled(4):
        # warm the preassigned-validation kernel (global-service share of
        # config 4) at its node-bucket shape
        store, svc, nodes, tasks = build_cluster(
            N_NODES, 64, prefs=rack_pref, global_share=1.0)
        warm_planner = TPUPlanner()
        warm_planner.enable_small_group_routing = False
        one_tick(store, warm_planner, preassigned=True)

    # spans recorded from here on; the warm-up compiles above stay out
    tracer.reset()
    tracer.enable()
    # black box on: recent spans + registry samples stay dumpable when
    # a variance guard trips (run_with_variance_guard)
    from swarmkit_tpu.obs import flightrec
    flightrec.reset()
    flightrec.enabled = True
    # journeys + plane windows on from here (the shipped posture): the
    # ledger rides the recorder's store taps; plane occupancy windows
    # roll at artifact-assembly time below
    from swarmkit_tpu.obs import planes as planes_mod
    from swarmkit_tpu.obs.journey import journeys
    planes_mod.reset()
    # pre-create the taxonomy and open every occupancy window at the
    # bench epoch (windows open lazily at first roll; without this the
    # single artifact-assembly roll below would read a zero-width
    # window and report occupancy 0 for every plane)
    for _pl in planes_mod.ALL_PLANES:
        planes_mod.plane(_pl)
    planes_mod.roll_all()
    journeys.reset(sample_rate=1.0)
    journeys.enabled = True
    flightrec.journey_sink = journeys.handle_event
    # device-plane ledger on from here (the shipped posture): kernel
    # rows, per-reason transfer bytes, the compile-cache ledger the
    # window sentinel below audits
    from swarmkit_tpu.obs import devicetelemetry
    devicetelemetry.reset()
    devicetelemetry.set_enabled(True)

    # ---- headline: config 4 scale, median of TRIALS (variance-guarded)
    def headline_trial(obs_tap=False):
        store, svc, nodes, tasks = build_cluster(N_NODES, N_TASKS)
        planner = TPUPlanner()
        # obs_tap = the journeys-enabled posture: the store is tapped
        # like a live manager's, so commits pay the real subscription
        # fan-out; the fold itself (poll_store) runs off the timed
        # window, where the production sampler thread runs it
        if obs_tap:
            flightrec.watch_store(store)
        sched, n_dec, dt = one_tick(store, planner)
        if obs_tap:
            flightrec.poll_store()
            flightrec.unwatch_store(store)
        assert n_dec == N_TASKS
        assert planner.stats["tasks_planned"] == N_TASKS, planner.stats
        out = (dt, planner.stats["plan_seconds"],
               sched.stats["commit_seconds"])
        del store, svc, nodes, tasks, planner, sched
        gc.collect()
        return out

    headline_compile_snap = _planner_counter_snapshot()
    with tracer.span("bench.config", "bench", cfg="headline"):
        trials, headline_reruns = run_with_variance_guard(
            headline_trial, n_trials=TRIALS, name="headline")
    # per-bucket compile counts inside the timed headline region — the
    # warm-up above compiled every signature, so nonzero means a compile
    # landed in a timed trial and the numbers carry its cost
    headline_compiles = _compile_delta(headline_compile_snap)
    ticks = sorted(t[0] for t in trials)
    med = statistics.median(ticks)
    rep = min(trials, key=lambda t: abs(t[0] - med))
    tpu_dps = N_TASKS / med

    # ---- tracing overhead: ALTERNATING tracer-off / tracer-on trials
    # of the same headline workload, so machine-state drift (allocator
    # caches, GC) lands evenly in both halves instead of biasing
    # whichever ran later; medians of each half are the pair the ≤3%
    # acceptance bound is judged on.  Registry counters/timers stay on
    # in BOTH halves by design, like the reference's go-metrics — this
    # measures the optional span layer.  The headline number above is
    # the obs-enabled (shipped) posture.
    if SKIP_OBS:
        obs_stats = None
    else:
        # the "on" half is the full shipped posture: spans AND the
        # journey ledger riding a live store tap; "off" is both dark.
        # The ≤3% acceptance bound (bench_compare obs-overhead gate) is
        # judged on these medians, and the window must be compile-free
        # or the number carries XLA cost instead of obs cost.
        obs_compile_snap = _planner_counter_snapshot()
        # compile-cache window sentinel: signatures already compiled
        # before the timed window — a later miss on any of these is a
        # cache-ledger regression (bench_compare compile-cache-hit gate)
        devtel_seen = {
            b: r["compiles"] for b, r
            in devicetelemetry.compile_cache_snapshot().items()
            if r["compiles"] > 0}
        on_ts, off_ts = [], []
        for _ in range(max(1, TRIALS)):
            tracer.disable()
            journeys.enabled = False
            devicetelemetry.set_enabled(False)
            off_ts.append(headline_trial()[0])
            tracer.enable()
            journeys.enabled = True
            devicetelemetry.set_enabled(True)
            on_ts.append(headline_trial(obs_tap=True)[0])
        med_on = statistics.median(on_ts)
        med_off = statistics.median(off_ts)
        devtel_after = devicetelemetry.compile_cache_snapshot()
        window_repeat_misses = sorted(
            b for b, n in devtel_seen.items()
            if devtel_after.get(b, {}).get("compiles", 0) > n)
        obs_stats = {
            "enabled_decisions_per_sec": round(N_TASKS / med_on, 1),
            "disabled_decisions_per_sec": round(N_TASKS / med_off, 1),
            "overhead_pct": round((med_on - med_off) / med_off * 100.0,
                                  2),
            "window_compiles": sum(
                _compile_delta(obs_compile_snap).values()),
            "window_repeat_misses": window_repeat_misses,
            "journey_sampled_tasks": journeys.summary()["sampled_tasks"],
        }

    if SKIP_HOST:
        host_dps, vs = None, 0.0
    else:
        host_ticks = []
        for _ in range(TRIALS):
            store, svc, nodes, tasks = build_cluster(N_NODES, BASELINE_TASKS)
            _, n_dec, dt = one_tick(store, None)
            host_ticks.append(dt)
        host_dps = BASELINE_TASKS / statistics.median(host_ticks)
        vs = tpu_dps / host_dps

    configs = {}
    if _cfg_enabled(1):
        with tracer.span("bench.config", "bench", cfg="cfg1"):
            configs["1_spread_1k_x_100"] = run_config(
                "cfg1", 100, 1_000, tpu,
                reservations=Resources())
    if _cfg_enabled(2):
        with tracer.span("bench.config", "bench", cfg="cfg2"):
            configs["2_binpack_10k_x_1k"] = run_config(
                "cfg2", 1_000, 10_000, tpu,
                reservations=Resources(nano_cpus=2 * 10**9,
                                       memory_bytes=2 << 30))
    if _cfg_enabled(3):
        with tracer.span("bench.config", "bench", cfg="cfg3"):
            configs["3_constraints_50k_x_5k"] = run_config(
                "cfg3", 5_000, 50_000, tpu,
                node_labels=lambda i: {"tier": "web" if i % 2 else "db",
                                       "rack": f"r{i % 40}"},
                node_platform=lambda i: {"os": "linux" if i % 10
                                         else "windows",
                                         "architecture": "amd64"},
                constraints=["node.labels.tier==web"],
                platforms=[Platform(os="linux", architecture="amd64")],
                expect=50_000)
    if _cfg_enabled(4):
        with tracer.span("bench.config", "bench", cfg="cfg4"):
            configs["4_mixed_100k_x_10k"] = run_config(
                "cfg4", N_NODES, N_TASKS, tpu,
                prefs=[PlacementPreference(
                    spread=SpreadOver(
                        spread_descriptor="node.labels.rack"))],
                global_share=0.2)
    if _cfg_enabled(5):
        with tracer.span("bench.config", "bench", cfg="cfg5"):
            configs["5_reschedule_storm"] = run_storm(tpu)
    # shape_cost_x = per-decision cost of a config relative to the
    # lab-shape headline (tpu_dps).  Configs 1-5 run the very harness
    # the headline runs (no proposer, no watchers) — they ARE the lab
    # shape, so their production-shape cost factor is 1.0 by
    # construction; recording it (instead of the old None) keeps the
    # history ledger's per-config shape_cost_x column well-defined.
    for cfg in configs.values():
        cfg.setdefault("shape_cost_x", 1.0)
    if _cfg_enabled(6):
        with tracer.span("bench.config", "bench", cfg="cfg6"):
            configs["6_live_manager_2x100k_x_10k"] = run_live_manager(tpu)
        live = configs["6_live_manager_2x100k_x_10k"]["decisions_per_sec"]
        # production-shape cost factor: per-decision rate of the live
        # multi-service tick vs the lab-shape headline (no
        # proposer/watchers); target <1.5x
        configs["6_live_manager_2x100k_x_10k"]["shape_cost_x"] = round(
            tpu_dps / live, 2) if live else None
    if _cfg_enabled(7):
        # many-service scale-out: 10 services fused into one program
        # ladder per tick.  Defaults fit the dev container; the env
        # knobs scale toward the 1M-task x 50k-node target shape on
        # hosts that hold it (BENCH_CFG7_NODES=50000
        # BENCH_CFG7_TASKS=1000000).
        cfg7_services = int(os.environ.get("BENCH_CFG7_SERVICES", 10))
        cfg7_nodes = int(os.environ.get("BENCH_CFG7_NODES", N_NODES))
        cfg7_tasks = int(os.environ.get("BENCH_CFG7_TASKS", 500_000))
        with tracer.span("bench.config", "bench", cfg="cfg7"):
            configs["7_many_service_10x"] = run_live_manager(
                tpu, n_services=cfg7_services, n_nodes=cfg7_nodes,
                total_tasks=cfg7_tasks)
        live7 = configs["7_many_service_10x"]["decisions_per_sec"]
        configs["7_many_service_10x"]["shape_cost_x"] = round(
            tpu_dps / live7, 2) if live7 else None
    if _cfg_enabled(8):
        # services + jobs + 3 priority bands: the preemption subsystem
        # under load (victim kernel signatures warmed inside the config)
        with tracer.span("bench.config", "bench", cfg="cfg8"):
            configs["8_mixed_priority_jobs"] = run_priority_jobs(tpu)
    if _cfg_enabled(9):
        # autoscaler decision + quota-clamped tenant burst through the
        # device quota-mask column (bench_compare gates clamps > 0 with
        # compile-flat timed windows)
        with tracer.span("bench.config", "bench", cfg="cfg9"):
            configs["9_autoscale_tenant_storm"] = \
                run_autoscale_tenant_storm(tpu)
    if _cfg_enabled(10):
        # sustained decisions/sec under Poisson churn: the streaming
        # scheduler's incremental ticks vs forced full replans, same
        # seeded workload, placements byte-identical (bench_compare
        # gates the plane being active + compile-flat windows + the
        # pending->assigned p99 regression bound)
        with tracer.span("bench.config", "bench", cfg="cfg10"):
            configs["10_steady_state_churn"] = \
                run_steady_state_churn(tpu)
    if _cfg_enabled(11):
        # mixed-size replicas under spread vs binpack through the
        # strategy seam: stranded-capacity fraction + the node.ip-CIDR
        # device column (bench_compare gates binpack < spread, zero
        # strategy fallbacks, fallback_groups 0, compile-flat windows,
        # and spread dec/s regression <= 10%)
        with tracer.span("bench.config", "bench", cfg="cfg11"):
            configs["11_fragmentation_strategies"] = \
                run_fragmentation(tpu)
    if _cfg_enabled(12):
        # mixed gang fleet + 3-stage DAG-gated pipeline through the
        # atomic-admission path (bench_compare gates zero partial
        # gangs, zero gang deferrals, the gate holding then draining,
        # device gang route, compile-flat windows, and the gang
        # tick's dec/s within 4x of the plain tick)
        with tracer.span("bench.config", "bench", cfg="cfg12"):
            configs["12_gang_pipeline"] = run_gang_pipeline(tpu)
    if _cfg_enabled(13):
        # overload-safe serving at fleet scale: >=1k real dispatcher
        # sessions + ~1M-replica fan-out through the batched assignment
        # plane with the admission bounds LIVE (bench_compare gates the
        # time-to-running p99 regression, ledger-exact shed counting
        # with zero unrecovered, and zero timed-window compiles)
        with tracer.span("bench.config", "bench", cfg="cfg13"):
            configs["13_million_swarm"] = run_million_swarm(tpu)
    if SKIP_E2E:
        e2e = None
    else:
        with tracer.span("bench.config", "bench", cfg="e2e"):
            e2e = run_e2e()

    # ---- trace export + phase tables (from the SAME document, so the
    # artifact's table and the loadable trace can never diverge)
    tracer.disable()
    doc = tracer.to_chrome()
    trace_file = None
    try:
        with open(TRACE_OUT, "w") as f:
            json.dump(doc, f)
        trace_file = TRACE_OUT
    except OSError:
        pass
    from swarmkit_tpu.obs.report import config_windows
    tables = {cfg: phase_table(doc, window=w)
              for cfg, w in config_windows(doc)}

    # headline overlap evidence (ROADMAP item 1), promoted from the
    # per-config phase_table: cfg6 — the production-shape pipelined
    # tick — when it ran, else the headline window.  bench_compare
    # fails a run whose overlap regressed to 0 with the pipeline on.
    from swarmkit_tpu.utils.pipeline import default_pipeline_depth
    overlap_src = next((c for c in ("cfg6", "cfg7") if c in tables),
                       "headline")
    overlap_tbl = tables.get(overlap_src, {})

    # close the plane occupancy windows so the saturation gauges (and
    # the health checks reading them) reflect the finished run
    planes_mod.roll_all()
    planes_report = planes_mod.report_all()

    # health plane verdict over the finished run's registry: all-pass is
    # the clean-run baseline the acceptance criteria pin
    from swarmkit_tpu.obs.health import HealthEvaluator
    health_eval = HealthEvaluator()
    health_checks = health_eval.evaluate()
    health = {"status": health_eval.status(), "checks": health_checks}

    artifact = {
        "metric": f"scheduling decisions/sec, {N_TASKS // 1000}k tasks x "
                  f"{N_NODES // 1000}k nodes (single tick, store-committed)",
        "value": round(tpu_dps, 1),
        "unit": "decisions/sec",
        # what every number in this artifact ran on
        "device": device,
        "vs_baseline": round(vs, 2),
        "tick_p50_s": round(med, 3),
        "tick_p99_s": round(ticks[-1], 3),
        "tick_min_s": round(ticks[0], 3),
        "tick_stdev_s": round(statistics.stdev(ticks), 4)
        if len(ticks) > 1 else 0.0,
        "headline_variance_x": round(ticks[-1] / ticks[0], 2),
        "headline_variance_reruns": headline_reruns,
        "plan_phase_s": round(rep[1], 3),
        "commit_phase_s": round(rep[2], 3),
        "plan_phase_decisions_per_sec": round(N_TASKS / rep[1], 1)
        if rep[1] else None,
        "trials": len(trials),
        "baseline": "host-oracle path, same store+commit framework "
                    "(Go toolchain unavailable)",
        "baseline_decisions_per_sec": round(host_dps, 1) if host_dps
        else None,
        "obs": obs_stats,
        "trace_file": trace_file,
        # per-bucket XLA compiles inside the timed headline region
        "planner_compiles": headline_compiles,
        # plan/commit software pipeline: configured depth + the overlap
        # the trace actually measured (see overlap_src above)
        "pipeline_depth": default_pipeline_depth(),
        # planner mesh size (SWARM_PLANNER_MESH; 1 = single device)
        "planner_mesh_devices": _mesh_devices(),
        # N∈{1,2,4,8} fused-chunk crossover curve, when measured
        # (scripts/mesh_crossover.py writes the artifact it embeds)
        "mesh_crossover": _mesh_crossover(),
        "plan_commit_overlap_s": overlap_tbl.get(
            "plan_commit_overlap_s", 0.0),
        "plan_hidden_frac": overlap_tbl.get("plan_hidden_frac", 0.0),
        "plan_overlap_source": overlap_src,
        # commit-plane headline (ISSUE 13): fraction of the commit wall
        # hidden behind the plan, fan-out synthesis cost, and whether
        # the native commit plane held in the live-manager window
        "commit_hidden_frac": overlap_tbl.get("commit_hidden_frac", 0.0),
        "fanout_s": next(
            (configs[c]["fanout_s"] for c in
             ("6_live_manager_2x100k_x_10k", "7_many_service_10x")
             if c in configs and "fanout_s" in configs[c]), None),
        "native_commit": next(
            (configs[c]["native_commit"] for c in
             ("6_live_manager_2x100k_x_10k", "7_many_service_10x")
             if c in configs and "native_commit" in configs[c]), None),
        # streaming scheduler (ISSUE 14): resident-state evidence from
        # the sustained-churn config's streaming pass
        "streaming": (configs.get("10_steady_state_churn") or {}
                      ).get("streaming"),
        "health": health,
        # device-plane ledger for the whole run: kernel rows keyed by
        # compile bucket, per-reason transfer bytes, the per-signature
        # compile-cache ledger, memory watermarks, donation balance
        "device_telemetry": devicetelemetry.snapshot(),
        # per-plane saturation report (occupancy/depth/age/drops) and
        # the journey-join attribution of e2e time-to-running p99 —
        # trace_report --critical-path prints both from this artifact
        "planes": planes_report,
        "journey_attribution": (e2e or {}).get("journey_attribution"),
        "phase_table": tables,
        "configs": configs,
        "e2e_time_to_running": e2e,
    }
    if "headline" in _flightrec_dumps:
        artifact["flightrec_dump"] = _flightrec_dumps["headline"]
    print(json.dumps(artifact))
    _append_history(artifact)


def _append_history(artifact):
    """One compact JSONL record per run — the regression ledger
    ``scripts/bench_compare.py`` diffs.  Best-effort: an unwritable
    history file must not fail the bench."""
    if not HISTORY_OUT:
        return
    record = {
        "t": round(time.time(), 3),
        "metric": artifact["metric"],
        "value": artifact["value"],
        "unit": artifact["unit"],
        "device": artifact["device"],
        "tick_p50_s": artifact["tick_p50_s"],
        "headline_variance_x": artifact["headline_variance_x"],
        "obs_overhead_pct": (artifact["obs"] or {}).get("overhead_pct"),
        "obs_window_compiles": (artifact["obs"] or {}).get(
            "window_compiles"),
        "obs_window_repeat_misses": (artifact["obs"] or {}).get(
            "window_repeat_misses"),
        "device_transfer_bytes": {
            d: sum(r["bytes"] for r in tbl.values())
            for d, tbl in (artifact.get("device_telemetry") or {})
            .get("transfers", {}).items()},
        "device_bytes_avoided": (artifact.get("device_telemetry")
                                 or {}).get("bytes_avoided"),
        "health": artifact["health"]["status"],
        "health_checks": artifact["health"].get("checks"),
        "planner_compiles": sum(artifact["planner_compiles"].values()),
        "pipeline_depth": artifact["pipeline_depth"],
        "planner_mesh_devices": artifact["planner_mesh_devices"],
        "plan_commit_overlap_s": artifact["plan_commit_overlap_s"],
        "plan_hidden_frac": artifact["plan_hidden_frac"],
        "plan_overlap_source": artifact["plan_overlap_source"],
        "commit_phase_s": artifact["commit_phase_s"],
        "commit_hidden_frac": artifact.get("commit_hidden_frac"),
        "fanout_s": artifact.get("fanout_s"),
        "native_commit": artifact.get("native_commit"),
        "streaming": artifact.get("streaming"),
        "configs": {
            name: {
                "decisions_per_sec": cfg.get("decisions_per_sec"),
                "variance_x": cfg.get("variance_x"),
                "fallback_groups": cfg.get("fallback_groups"),
                "compiles": sum(cfg.get("compiles", {}).values()),
                "shape_cost_x": cfg.get("shape_cost_x"),
                "preemptions": cfg.get("preemptions"),
                "quota_clamps": cfg.get("quota_clamps"),
                "commit_phase_s": cfg.get("commit_phase_s"),
                "fanout_s": cfg.get("fanout_s"),
                "native_commit": cfg.get("native_commit"),
                "streaming": cfg.get("streaming"),
                "streaming_speedup": cfg.get("streaming_speedup"),
                "h2d_bytes_per_tick": cfg.get("h2d_bytes_per_tick"),
                "pending_assigned_p99_s": cfg.get(
                    "pending_assigned_p99_s"),
                "spread_decisions_per_sec": cfg.get(
                    "spread_decisions_per_sec"),
                "binpack_decisions_per_sec": cfg.get(
                    "binpack_decisions_per_sec"),
                "stranded_frac_spread": cfg.get("stranded_frac_spread"),
                "stranded_frac_binpack": cfg.get(
                    "stranded_frac_binpack"),
                "strategy_fallbacks": cfg.get("strategy_fallbacks"),
                "gangs_admitted": cfg.get("gangs_admitted"),
                "gang_deferred": cfg.get("gang_deferred"),
                "gang_atomicity_violations": cfg.get(
                    "gang_atomicity_violations"),
                "gang_fit_host_verdicts": cfg.get(
                    "gang_fit_host_verdicts"),
                "pipeline_gated_deferrals": cfg.get(
                    "pipeline_gated_deferrals"),
                "gang_vs_plain_x": cfg.get("gang_vs_plain_x"),
            }
            for name, cfg in artifact["configs"].items()},
    }
    try:
        with open(HISTORY_OUT, "a") as f:
            f.write(json.dumps(record, sort_keys=True) + "\n")
    except OSError:
        pass


if __name__ == "__main__":
    main()
