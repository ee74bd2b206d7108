"""Mesh-native streaming planner differentials (ISSUE 19).

The resident device tier shards over the planner mesh (node-axis
NamedSharding, per-shard donated scatters — parallel/sharded.py
``put_resident``/``scatter_rows_sharded``); fused runs seed their
node-state columns straight from the resident shards; binpack /
weighted / learned groups ride ``ShardedPlanFn.strategy`` and the
strategy-mixed fused kernel instead of falling back to the host.

Every test here is a differential: placements, store state and the
watch-event stream at mesh N must be byte-identical to the N=1 program
(which itself is bit-equal to the numpy host oracles — test_strategy /
test_streaming hold that leg).  conftest.py forces an 8-virtual-device
CPU platform, so the 2- and 4-way meshes run in-process.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from swarmkit_tpu.models import (
    Annotations, Node, NodeAvailability, NodeDescription, NodeSpec,
    NodeState, NodeStatus, Placement, PlacementPreference,
    ReplicatedService, Resources, ResourceRequirements, Service,
    ServiceMode, ServiceSpec, SpreadOver, Task, TaskSpec, TaskState,
    TaskStatus, Version,
)
from swarmkit_tpu.models import types as model_types
from swarmkit_tpu.ops import TPUPlanner
from swarmkit_tpu.ops import fusedbatch
from swarmkit_tpu.ops.kernel import (
    GroupInputs, NodeInputs, StrategyInputs, K_CLAMP, plan_strategy_jit,
)
from swarmkit_tpu.parallel.sharded import make_mesh, plan_strategy_sharded
from swarmkit_tpu.scheduler import Scheduler
from swarmkit_tpu.state import MemoryStore
from swarmkit_tpu.state.events import (
    Event, EventCommit, EventSnapshotRestore, EventTaskBlock,
)
from swarmkit_tpu.utils.metrics import registry as _metrics


@pytest.fixture
def frozen_clock():
    model_types.set_time_source(lambda: 1_700_000_000.0)
    try:
        yield
    finally:
        model_types.set_time_source(None)


_RES = ResourceRequirements(
    reservations=Resources(nano_cpus=10 ** 8, memory_bytes=64 << 20))


def _mk_node(i, cpus=8 * 10 ** 9, mem=32 << 30):
    return Node(
        id=f"n{i:04d}",
        spec=NodeSpec(annotations=Annotations(
            name=f"node-{i:04d}",
            labels={"rack": f"r{i % 3}",
                    "tier": "web" if i % 2 else "db"})),
        status=NodeStatus(state=NodeState.READY),
        description=NodeDescription(
            hostname=f"node-{i:04d}",
            resources=Resources(nano_cpus=cpus, memory_bytes=mem)))


def _mk_service(sid, n_tasks, spec):
    svc = Service(
        id=sid,
        spec=ServiceSpec(annotations=Annotations(name=f"svc-{sid}"),
                         mode=ServiceMode.REPLICATED,
                         replicated=ReplicatedService(replicas=n_tasks),
                         task=spec),
        spec_version=Version(index=1))
    tasks = [Task(id=f"{sid}-t{k:04d}", service_id=sid, slot=k + 1,
                  desired_state=TaskState.RUNNING, spec=spec,
                  spec_version=Version(index=1),
                  status=TaskStatus(state=TaskState.PENDING,
                                    timestamp=model_types.now()))
             for k in range(n_tasks)]
    return svc, tasks


def _build_store(n_nodes=24):
    store = MemoryStore()
    store.update(lambda tx: [tx.create(_mk_node(i))
                             for i in range(n_nodes)])
    specs = {
        "sva": TaskSpec(resources=_RES),
        "svb": TaskSpec(resources=_RES,
                        placement=Placement(
                            constraints=["node.labels.tier==web"])),
        "svc": TaskSpec(resources=_RES,
                        placement=Placement(preferences=[
                            PlacementPreference(spread=SpreadOver(
                                spread_descriptor="node.labels.rack"))])),
    }
    seeded = {"sva": 20, "svb": 12, "svc": 9}

    def mk(tx):
        for sid, spec in specs.items():
            svc, tasks = _mk_service(sid, seeded[sid], spec)
            tx.create(svc)
            for t in tasks:
                tx.create(t)
    store.update(mk)
    return store, specs, dict(seeded)


def _event_key(ev):
    if isinstance(ev, EventTaskBlock):
        return ("block", tuple(o.id for o in ev.olds),
                tuple(ev.node_ids), ev.base_version, ev.state, ev.message)
    if isinstance(ev, EventCommit):
        return ("commit", ev.version)
    if isinstance(ev, Event):
        obj = ev.obj
        return (ev.action, obj.id, getattr(obj, "node_id", None),
                int(obj.status.state) if hasattr(obj, "status") else None,
                obj.meta.version.index)
    return ("other", repr(ev))


def _pump(sched, sub):
    while True:
        ev = sub.poll()
        if ev is None:
            return
        if isinstance(ev, EventSnapshotRestore):
            sched._resync()
        elif isinstance(ev, Event):
            sched._handle_event(ev)


def _churn_run(planner):
    """The test_streaming churn (arrivals, failures, a drain flip, a
    node join, a node leave) driven through the real event feed, with
    an injectable planner — the mesh/no-mesh differential harness."""
    store, specs, seqs = _build_store()
    planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=1)
    _, sub = store.view_and_watch(
        lambda tx: sched._setup_tasks_list(tx), accepts_blocks=True)
    obs = store.queue.subscribe(accepts_blocks=True)

    def add(sid, n):
        spec = specs[sid]
        base = seqs[sid]

        def cb(tx):
            for k in range(n):
                tx.create(Task(
                    id=f"{sid}-t{base + k:04d}", service_id=sid,
                    slot=base + k + 1, desired_state=TaskState.RUNNING,
                    spec=spec, spec_version=Version(index=1),
                    status=TaskStatus(state=TaskState.PENDING)))
        store.update(cb)
        seqs[sid] = base + n

    def fail_some(sid, k):
        victims = sorted(
            (t for t in store.view(lambda tx: tx.find(Task))
             if t.service_id == sid and t.node_id), key=lambda t: t.id
        )[:k]

        def cb(tx):
            for v in victims:
                cur = tx.get(Task, v.id)
                if cur is None:
                    continue
                cur = cur.copy()
                cur.status = TaskStatus(
                    state=TaskState.FAILED,
                    timestamp=model_types.now(), message="churn exit")
                tx.update(cur)
        store.update(cb)

    def flip(nid, avail):
        def cb(tx):
            cur = tx.get(Node, nid).copy()
            cur.spec.availability = avail
            tx.update(cur)
        store.update(cb)

    decisions = sched.tick()                       # tick 1: cold build
    add("sva", 5)
    add("svc", 3)
    fail_some("sva", 2)
    _pump(sched, sub)
    decisions += sched.tick()                      # tick 2: incremental
    add("svb", 4)
    flip("n0002", NodeAvailability.DRAIN)
    _pump(sched, sub)
    decisions += sched.tick()                      # tick 3: incremental
    store.update(lambda tx: tx.create(_mk_node(24)))
    add("sva", 4)
    _pump(sched, sub)
    decisions += sched.tick()                      # tick 4: append row
    store.update(lambda tx: tx.delete(Node, "n0005"))
    add("svc", 4)
    _pump(sched, sub)
    decisions += sched.tick()                      # tick 5: node-remove
    add("svb", 3)
    flip("n0002", NodeAvailability.ACTIVE)
    _pump(sched, sub)
    decisions += sched.tick()                      # tick 6: incremental

    events = [_event_key(e) for e in obs.drain()]
    store.queue.unsubscribe(obs)
    store.queue.unsubscribe(sub)
    tasks = store.view(lambda tx: tx.find(Task))
    state = sorted((t.id, t.node_id, int(t.status.state),
                    t.status.message, t.meta.version.index)
                   for t in tasks)
    return decisions, state, events, sched, planner


def _mesh_planner(monkeypatch, d):
    monkeypatch.setenv("SWARM_PLANNER_MESH", str(d))
    p = TPUPlanner()
    monkeypatch.delenv("SWARM_PLANNER_MESH")
    assert p.mesh is not None and p.mesh.shape["nodes"] == d
    return p


# ------------------------------------------------ kernel-level parity

def test_sharded_strategy_kernel_matches_jit_fuzz():
    """plan_strategy_sharded (4-way node-axis shard_map) vs the
    single-device jit, bit-for-bit over random columns for every
    non-spread strategy.  Combined with test_strategy's jit-vs-oracle
    fuzz this closes the sharded-kernel-vs-host-oracle triangle."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (conftest forces 8 host devices)")
    mesh = make_mesh(jax.devices()[:4])
    rng = np.random.RandomState(19)
    with fusedbatch.x64():
        for trial in range(4):
            nb = 32
            valid = rng.rand(nb) > 0.1
            cpu = rng.randint(0, 200, nb).astype(np.int64)
            mem = rng.randint(0, 200, nb).astype(np.int64)
            cpu_d, mem_d = 7, 5
            res_ok = valid & (cpu >= cpu_d) & (mem >= mem_d)
            res_cap = np.minimum(cpu // cpu_d, mem // mem_d)
            res_cap = res_cap.clip(0, K_CLAMP).astype(np.int32)
            nodes = NodeInputs(
                valid=jnp.asarray(valid),
                ready=jnp.asarray(rng.rand(nb) > 0.05),
                res_ok=jnp.asarray(res_ok),
                res_cap=jnp.asarray(res_cap),
                svc_tasks=jnp.asarray(
                    rng.randint(0, 6, nb).astype(np.int32)),
                total_tasks=jnp.asarray(
                    rng.randint(0, 9, nb).astype(np.int32)),
                failures=jnp.asarray(
                    rng.randint(0, 3, nb).astype(np.int32)),
                leaf=jnp.zeros(nb, jnp.int32),
                os_hash=jnp.zeros((2, nb), jnp.int32),
                arch_hash=jnp.zeros((2, nb), jnp.int32),
                port_conflict=jnp.zeros(nb, bool),
                extra_mask=jnp.ones(nb, bool), quota_ok=None)
            group = GroupInputs(
                k=jnp.asarray(int(rng.randint(1, 40)), jnp.int32),
                con_hash=jnp.zeros((1, 2, nb), jnp.int32),
                con_op=jnp.full((1,), 2, jnp.int32),
                con_exp=jnp.zeros((1, 2), jnp.int32),
                plat=jnp.full((1, 4), -1, jnp.int32),
                maxrep=jnp.asarray(0, jnp.int32),
                port_limited=jnp.asarray(False))
            sin = StrategyInputs(
                hr_cpu=jnp.asarray(
                    np.clip(cpu // cpu_d, 0, 1023).astype(np.int32)),
                hr_mem=jnp.asarray(
                    np.clip(mem // mem_d, 0, 1023).astype(np.int32)),
                hr_gen=jnp.full(nb, 1023, jnp.int32),
                weights=jnp.asarray(
                    rng.randint(0, 8, 4).astype(np.int32)),
                w1=jnp.asarray(rng.randint(-4, 5, (6, 4)).astype(
                    np.int32)),
                b1=jnp.asarray(rng.randint(-4, 5, 4).astype(np.int32)),
                w2=jnp.asarray(rng.randint(-4, 5, 4).astype(np.int32)),
                b2=jnp.asarray(int(rng.randint(-4, 5)), jnp.int32))
            for sid in (1, 2, 3):
                x1, fc1, sp1 = plan_strategy_jit(nodes, group, sin, sid)
                xm, fcm, spm = plan_strategy_sharded(nodes, group, sin,
                                                     sid, mesh)
                np.testing.assert_array_equal(
                    np.asarray(x1), np.asarray(xm),
                    err_msg=f"trial {trial} sid {sid}")
                np.testing.assert_array_equal(
                    np.asarray(fc1), np.asarray(fcm),
                    err_msg=f"trial {trial} sid {sid}")


# ------------------------------------------------- churn differentials

def test_mesh_churn_byte_identical_to_single_device(frozen_clock,
                                                    monkeypatch):
    """The headline differential: the full churn (arrivals, failures,
    drain flip, node join/leave) at mesh N=2 must produce the same
    decisions, final store state and event stream as N=1 — while the
    resident tier actually runs sharded (per-shard scatters counted)."""
    dm, sm, em, _sched, pm = _churn_run(_mesh_planner(monkeypatch, 2))
    d1, s1, e1, _sched1, _p1 = _churn_run(TPUPlanner())
    assert (dm, sm, em) == (d1, s1, e1)
    snap = pm.streaming_snapshot()
    assert snap["mesh_devices"] == 2, snap
    assert snap["shard_syncs"] >= 1, snap
    assert pm.stats.get("groups_fused", 0) >= 2, pm.stats


def test_mesh_resident_shards_match_mirror_and_seed_fused(frozen_clock,
                                                          monkeypatch):
    """Sharded-scatter column equality: after churn the five sharded
    device columns must equal the host mirror row-for-row (the donated
    per-shard scatter applied exactly the dirty rows a rebuild would),
    and the fused run must have seeded from them (device carries
    counted, resident H2D per tick ~ 0)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    _dm, _sm, _em, sched, planner = _churn_run(
        _mesh_planner(monkeypatch, 4))
    st = planner._streaming
    assert st is not None and st._mesh_active
    st.refresh(sched)
    assert st.device_carry() is not None
    d_valid, d_ready, d_cpu, d_mem, d_total = [
        np.asarray(a) for a in st.dev]
    np.testing.assert_array_equal(d_valid, st.valid)
    np.testing.assert_array_equal(d_ready, st.ready)
    np.testing.assert_array_equal(d_cpu, st.cpu)
    np.testing.assert_array_equal(d_mem, st.mem)
    np.testing.assert_array_equal(d_total, st.total)
    assert st.stats["shard_syncs"] >= 2
    assert st.snapshot()["mesh_devices"] == 4
    assert planner.stats.get("streaming_device_carries", 0) >= 1, \
        planner.stats


def _strategy_spec(strategy, cpus=1, weights=None):
    return TaskSpec(
        resources=ResourceRequirements(reservations=Resources(
            nano_cpus=cpus * 10 ** 9, memory_bytes=1 << 30)),
        placement=Placement(strategy=strategy,
                            strategy_weights=weights or {}))


def _strategy_tick(planner):
    """One tick over a mixed-strategy workload (spread + binpack +
    weighted + learned) on heterogeneous nodes; returns placements."""
    store = MemoryStore()
    nodes = [_mk_node(i, cpus=(4 + (i % 5) * 4) * 10 ** 9)
             for i in range(10)]
    batches = [
        _mk_service("pack", 8, _strategy_spec("binpack")),
        _mk_service("wt", 8, _strategy_spec(
            "weighted", weights={"cpu": 3, "spread": 1})),
        _mk_service("ml", 8, _strategy_spec("learned")),
        _mk_service("spr", 8, _strategy_spec("")),
    ]

    def mk(tx):
        for node in nodes:
            tx.create(node)
        for svc, tasks in batches:
            tx.create(svc)
            for t in tasks:
                tx.create(t)
    store.update(mk)
    if planner is not None:
        planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner)
    store.view(sched._setup_tasks_list)
    sched.tick()
    placements = {t.id: t.node_id for t in store.view(
        lambda tx: tx.find(Task))}
    return placements, planner


def _strategy_subset(placements):
    """The binpack/weighted/learned tasks — the services whose host
    oracle carries the task-level bit-parity contract (spread's host
    walk assigns the same per-node counts in a different task order,
    so the spread service only participates in device-vs-device
    comparisons)."""
    return {tid: nid for tid, nid in placements.items()
            if not tid.startswith("spr-")}


def test_mesh_fused_strategies_match_host_oracle(frozen_clock,
                                                 monkeypatch):
    """binpack / weighted / learned at mesh N=2, fused: the whole
    mixed-strategy tick must place byte-identically to the N=1 device
    program, the strategy services must match the numpy host oracle
    task-for-task, every strategy group must ride the device route
    (zero ``route=host`` increments), and the groups fuse instead of
    breaking the run."""
    def host_groups(route):
        return sum(_metrics.get_counter(
            f'swarm_strategy_groups{{route="{route}",'
            f'strategy="{s}"}}')
            for s in ("binpack", "weighted", "learned"))

    host, _ = _strategy_tick(None)
    dev1, _ = _strategy_tick(TPUPlanner())
    h_before = host_groups("host")
    d_before = host_groups("device")
    devm, planner = _strategy_tick(_mesh_planner(monkeypatch, 2))
    assert devm == dev1                       # N=2 == N=1, all services
    assert _strategy_subset(devm) == _strategy_subset(host)
    assert all(nid for nid in devm.values())
    assert host_groups("host") == h_before, "strategy group fell host"
    assert host_groups("device") == d_before + 3
    assert planner.stats.get("groups_strategy_host", 0) == 0
    assert planner.stats.get("groups_fused", 0) >= 4, planner.stats


def test_mesh_per_group_strategy_kernel_routes_on_device(frozen_clock,
                                                         monkeypatch):
    """With fusion off, a non-spread group rides ShardedPlanFn.strategy
    (the per-group sharded kernel) — not the host oracle — and places
    exactly as the N=1 kernel and the host oracle would."""
    host, _ = _strategy_tick(None)
    p1 = TPUPlanner()
    p1.fused_enabled = False
    dev1, _ = _strategy_tick(p1)
    planner = _mesh_planner(monkeypatch, 2)
    planner.fused_enabled = False
    devm, planner = _strategy_tick(planner)
    assert devm == dev1
    assert _strategy_subset(devm) == _strategy_subset(host)
    assert planner.stats.get("groups_strategy_host", 0) == 0
    assert planner.stats.get("groups_planned", 0) >= 4, planner.stats


# --------------------------------------------------- fallback matrix

def test_mesh_epoch_resync(frozen_clock, monkeypatch):
    """Leader-handoff discipline with the sharded tier: an epoch bump
    forces the counted resync, after which the device tier is sharded
    again and mirrors the host columns."""
    store, _specs, _seqs = _build_store(n_nodes=8)
    planner = _mesh_planner(monkeypatch, 2)
    planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=1)
    store.view(sched._setup_tasks_list)
    sched._tick_epoch = 3
    planner.begin_tick(sched)
    planner.end_tick()
    st = planner._streaming
    assert st._mesh_active and st.stats["resyncs"] == 0
    sched._tick_epoch = 4          # the reign changed
    planner.begin_tick(sched)
    planner.end_tick()
    assert st.stats["resyncs"] == 1, st.stats
    st.refresh(sched)
    assert st._mesh_active
    for dev_col, host_col in zip(st.dev, (st.valid, st.ready, st.cpu,
                                          st.mem, st.total)):
        np.testing.assert_array_equal(np.asarray(dev_col), host_col)


def test_mesh_teardown_and_shard_count_resync(frozen_clock,
                                              monkeypatch):
    """The two new fallback-matrix rows: tearing the mesh down demotes
    to single-device residency; a shard-count change re-uploads over
    the new layout.  Both are counted resyncs with their own reason
    labels, and the host mirror survives untouched."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    store, _specs, _seqs = _build_store(n_nodes=8)
    planner = _mesh_planner(monkeypatch, 2)
    planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=1)
    store.view(sched._setup_tasks_list)
    planner.begin_tick(sched)
    planner.end_tick()
    st = planner._streaming
    assert st._mesh_active and st.snapshot()["mesh_devices"] == 2
    host_cols = [np.array(c) for c in (st.valid, st.ready, st.cpu,
                                       st.mem, st.total)]

    before_td = _metrics.get_counter(
        'swarm_streaming_resyncs{reason="mesh-teardown"}')
    st.set_mesh(None)
    assert st.dev is None and not st._mesh_active
    assert _metrics.get_counter(
        'swarm_streaming_resyncs{reason="mesh-teardown"}') \
        == before_td + 1
    st.refresh(sched)
    assert st.device_carry() is not None
    assert st.snapshot()["mesh_devices"] == 0   # single-device tier
    for host_col, now_col in zip(host_cols,
                                 (st.valid, st.ready, st.cpu, st.mem,
                                  st.total)):
        np.testing.assert_array_equal(host_col, now_col)

    before_sc = _metrics.get_counter(
        'swarm_streaming_resyncs{reason="shard-count"}')
    st.set_mesh(make_mesh(jax.devices()[:4]))
    assert st.dev is None
    assert _metrics.get_counter(
        'swarm_streaming_resyncs{reason="shard-count"}') \
        == before_sc + 1
    st.refresh(sched)
    assert st._mesh_active and st.snapshot()["mesh_devices"] == 4
    for dev_col, host_col in zip(st.dev, host_cols):
        np.testing.assert_array_equal(np.asarray(dev_col), host_col)


def test_mesh_divergence_resync_reshards(frozen_clock, monkeypatch):
    """The divergence sentinel is layout-independent: swap a NodeInfo
    object behind the resident row (the mirror now tracks a dead
    object) and the next refresh must count the divergence fallback,
    rebuild the mirror, and re-upload the SHARDED device tier."""
    store, _specs, _seqs = _build_store(n_nodes=8)
    planner = _mesh_planner(monkeypatch, 2)
    planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=1)
    store.view(sched._setup_tasks_list)
    planner.begin_tick(sched)
    planner.end_tick()
    st = planner._streaming
    assert st._mesh_active
    import copy
    ns = sched.node_set.nodes
    ns["n0000"] = copy.copy(ns["n0000"])   # object swap, not mutation
    sched.delta.mark("n0000")
    before = _metrics.get_counter(
        'swarm_streaming_resyncs{reason="divergence"}')
    fb_before = st.stats["fallbacks"]
    st.refresh(sched)
    assert _metrics.get_counter(
        'swarm_streaming_resyncs{reason="divergence"}') == before + 1
    assert st.stats["fallbacks"] == fb_before + 1
    assert st._mesh_active and st.dev is not None
    for dev_col, host_col in zip(st.dev, (st.valid, st.ready, st.cpu,
                                          st.mem, st.total)):
        np.testing.assert_array_equal(np.asarray(dev_col), host_col)


# ------------------------------------------------------ sim differential

def test_mesh_steady_state_churn_sim(monkeypatch):
    """The twin-store steady-state-churn differential with the whole
    plane on a 2-way mesh: streaming+mesh placements must equal the
    forced full-replan twin for the same virtual-time churn."""
    monkeypatch.setenv("SWARM_PLANNER_MESH", "2")
    from swarmkit_tpu.sim import run_scenario
    r = run_scenario("steady-state-churn", seed=7)
    assert r.ok, r.violations

