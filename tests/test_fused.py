"""Differential tests for the fused many-service planner.

The fused path (ops/fusedbatch.py + kernel.plan_fused) packs a run of
consecutive fusable groups into ONE scan-over-groups program per chunk;
the contract is that fusion changes only the number of device
round-trips — placements, store snapshot bytes, and the watch-event
stream must be byte-identical to the per-group path
(SWARM_FUSED_PLANNER=0) for the same workload, in both the pipelined
and the serial (sim-shaped, depth-1) tick.  Degraded routes — bucket
overflow, device errors, spread spill — must fall back group-by-group,
never fail the tick.
"""

import numpy as np
import pytest

from swarmkit_tpu.models import (
    Annotations, Node, NodeDescription, NodeSpec, NodeState, NodeStatus,
    Placement, PlacementPreference, Platform, ReplicatedService, Resources,
    ResourceRequirements, Service, ServiceMode, ServiceSpec, SpreadOver,
    Task, TaskSpec, TaskState, TaskStatus, Version,
)
from swarmkit_tpu.models import types as model_types
from swarmkit_tpu.ops import TPUPlanner
from swarmkit_tpu.ops import fusedbatch
from swarmkit_tpu.ops import planner as planner_mod
from swarmkit_tpu.scheduler import Scheduler
from swarmkit_tpu.state import MemoryStore
from swarmkit_tpu.state.events import Event, EventCommit, EventTaskBlock


@pytest.fixture
def frozen_clock():
    model_types.set_time_source(lambda: 1_700_000_000.0)
    try:
        yield
    finally:
        model_types.set_time_source(None)


def _mk_nodes(n, cpus=16 * 10**9, mem=64 << 30):
    return [Node(
        id=f"n{i:04d}",
        spec=NodeSpec(annotations=Annotations(
            name=f"node-{i:04d}",
            labels={"rack": f"r{i % 5}",
                    "tier": "web" if i % 2 else "db"})),
        status=NodeStatus(state=NodeState.READY),
        description=NodeDescription(
            hostname=f"node-{i:04d}",
            platform=Platform(os="linux", architecture="amd64"),
            resources=Resources(nano_cpus=cpus, memory_bytes=mem)))
        for i in range(n)]


def _mk_service(sid, n_tasks, spec=None):
    svc = Service(
        id=sid,
        spec=ServiceSpec(annotations=Annotations(name=f"svc-{sid}"),
                         mode=ServiceMode.REPLICATED,
                         replicated=ReplicatedService(replicas=n_tasks),
                         task=spec or TaskSpec()),
        spec_version=Version(index=1))
    tasks = [Task(id=f"{sid}-t{k:04d}", service_id=sid, slot=k + 1,
                  desired_state=TaskState.RUNNING, spec=svc.spec.task,
                  spec_version=Version(index=1),
                  status=TaskStatus(state=TaskState.PENDING))
             for k in range(n_tasks)]
    return svc, tasks


_RES = ResourceRequirements(
    reservations=Resources(nano_cpus=10**8, memory_bytes=64 << 20))


def _many_service_store(n_services=6, n_nodes=40, base=40, specs=None):
    """``n_services`` fusable replicated services of varying sizes."""
    store = MemoryStore()
    nodes = _mk_nodes(n_nodes)
    store.update(lambda tx: [tx.create(n) for n in nodes])
    batches = []
    for si in range(n_services):
        spec = (specs[si] if specs is not None
                else TaskSpec(resources=_RES))
        batches.append(_mk_service(f"svc{si}", base + 7 * si, spec))
    def mk(tx):
        for svc, tasks in batches:
            tx.create(svc)
            for t in tasks:
                tx.create(t)
    store.update(mk)
    return store


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


def _run_tick(store, depth, fused=True, planner=None, ticks=1,
              pre_tick=None):
    sub = store.queue.subscribe(accepts_blocks=True)
    if planner is None:
        planner = TPUPlanner()
    planner.enable_small_group_routing = False  # deterministic routing
    planner.fused_enabled = fused
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=depth)
    store.view(sched._setup_tasks_list)
    if pre_tick is not None:
        pre_tick(store, sched)
    decisions = 0
    for _ in range(ticks):
        decisions += sched.tick()
    events = [_event_key(e) for e in sub.drain()]
    store.queue.unsubscribe(sub)
    tasks = store.view(lambda tx: tx.find(Task))
    state = sorted((t.id, t.node_id, int(t.status.state),
                    t.status.message, t.meta.version.index)
                   for t in tasks)
    return decisions, state, events, sched, planner


# --------------------------------------------------------------- parity

@pytest.mark.parametrize("depth", [1, 2])
@pytest.mark.parametrize("n_services", [3, 6])
def test_fused_tick_byte_identical_to_per_group(frozen_clock, depth,
                                                n_services):
    """Fused placements, store snapshot bytes, and watch-event streams
    must equal the per-group path's, pipelined and serial."""
    dn, sn, en, schedn, pn = _run_tick(
        _many_service_store(n_services), depth, fused=True)
    d0, s0, e0, sched0, p0 = _run_tick(
        _many_service_store(n_services), depth, fused=False)
    # the fused path actually engaged, replacing per-group dispatches
    assert pn.stats.get("groups_fused", 0) == n_services
    assert pn.stats.get("groups_planned", 0) == 0
    assert p0.stats.get("groups_fused", 0) == 0
    assert p0.stats["groups_planned"] == n_services
    assert (dn, sn, en) == (d0, s0, e0)
    bn = _run_tick(_many_service_store(n_services), depth,
                   fused=True)[3].store.save_bytes()
    b0 = _run_tick(_many_service_store(n_services), depth,
                   fused=False)[3].store.save_bytes()
    assert bn == b0


def test_fused_fewer_dispatches_than_groups(frozen_clock):
    """The amortization claim itself: a fused run of G groups dispatches
    ceil(G / chunk) programs, not G."""
    _, _, _, _, planner = _run_tick(_many_service_store(8), 2,
                                    fused=True)
    assert planner.stats["groups_fused"] == 8
    assert 0 < planner.stats["fused_chunks"] < 8


def test_fused_mixed_with_unfusable_groups(frozen_clock):
    """Unfusable groups (here: a spread service and a host-path CSI
    volume mount; node.ip constraints ride the device hash/prefix
    columns now, so they no longer qualify) break the run and ride
    their usual routes; surrounding fusable groups still fuse;
    everything matches the per-group path."""
    from swarmkit_tpu.models.specs import ContainerSpec
    from swarmkit_tpu.models.types import Mount, MountType
    specs = [
        TaskSpec(resources=_RES),
        TaskSpec(resources=_RES),
        TaskSpec(container=ContainerSpec(
            image="x", mounts=[Mount(type=MountType.CSI, source="vol",
                                     target="/data")])),  # host fallback
        TaskSpec(placement=Placement(preferences=[
            PlacementPreference(spread=SpreadOver(
                spread_descriptor="node.labels.rack"))]),
            resources=_RES),                          # fusable (flat)
        TaskSpec(resources=_RES),
    ]
    dn, sn, en, _, pn = _run_tick(
        _many_service_store(5, specs=specs), 2, fused=True)
    d0, s0, e0, _, p0 = _run_tick(
        _many_service_store(5, specs=specs), 2, fused=False)
    assert (dn, sn, en) == (d0, s0, e0)
    assert pn.stats["groups_fallback"] == 1
    assert pn.stats.get("groups_fused", 0) >= 2


def test_fused_conflict_rollback_matches_per_group(frozen_clock):
    """A mid-flight concurrent assignment fails the block item, rolls
    back mirrors, and requeues — identically with fusion on and off,
    across two ticks (second tick re-places the rolled-back tasks)."""
    def conflict(store, sched):
        def cb(tx):
            for tid in ("svc0-t0000", "svc1-t0001"):
                cur = tx.get(Task, tid).copy()
                cur.node_id = "n0000"
                cur.status = TaskStatus(state=TaskState.ASSIGNED,
                                        timestamp=1.0,
                                        message="concurrent writer")
                tx.update(cur)
        store.update(cb)

    out1 = _run_tick(_many_service_store(4), 2, fused=True,
                     pre_tick=conflict, ticks=2)
    out0 = _run_tick(_many_service_store(4), 2, fused=False,
                     pre_tick=conflict, ticks=2)
    assert out1[:3] == out0[:3]
    assert sorted(out1[3].unassigned_tasks) == sorted(
        out0[3].unassigned_tasks)


# ------------------------------------------------------ segment masking

def test_segment_masked_constraints_never_cross(frozen_clock):
    """Two groups with conflicting constraints in one fused batch must
    never share placements: each group's constraint rows mask only its
    own scan step."""
    specs = [
        TaskSpec(placement=Placement(
            constraints=["node.labels.tier==web"]), resources=_RES),
        TaskSpec(placement=Placement(
            constraints=["node.labels.tier==db"]), resources=_RES),
    ]
    store = _many_service_store(2, n_nodes=30, base=30, specs=specs)
    _, state, _, _, planner = _run_tick(store, 2, fused=True)
    assert planner.stats.get("groups_fused", 0) == 2
    node_tier = {f"n{i:04d}": ("web" if i % 2 else "db")
                 for i in range(30)}
    placed = {tid: nid for tid, nid, st, _, _ in state if nid}
    assert placed, "nothing placed"
    for tid, nid in placed.items():
        want = "web" if tid.startswith("svc0") else "db"
        assert node_tier[nid] == want, (tid, nid)


def test_fused_kernel_carry_sequencing():
    """Kernel-level: two groups of the SAME service with maxrep=1 — the
    scan carry must feed group 0's placements into group 1's per-node
    service counts, so the two groups land on disjoint nodes; and two
    groups with opposite constraints score disjoint node sets."""
    import jax.numpy as jnp
    from swarmkit_tpu.ops.hashing import str_hash
    from swarmkit_tpu.ops.kernel import (
        FusedCarry, FusedGroups, FusedShared, plan_fused_jit,
    )

    nb, g, cc, sb = 16, 4, 1, 2
    web = np.array([i % 2 == 0 for i in range(nb)])
    with fusedbatch.x64():
        valid = np.ones(nb, bool)
        shared = FusedShared(
            valid=jnp.asarray(valid), ready=jnp.asarray(valid),
            os_hash=jnp.zeros((2, nb), jnp.int32),
            arch_hash=jnp.zeros((2, nb), jnp.int32),
            svc0=jnp.zeros((sb, nb), jnp.int32))
        con_hash = np.zeros((g, cc, 2, nb), np.int32)
        con_op = np.full((g, cc), 2, np.int32)
        con_exp = np.zeros((g, cc, 2), np.int32)
        for i in range(nb):
            hv = fusedbatch.split_hash(
                str_hash("web" if web[i] else "db"))
            con_hash[2, 0, :, i] = hv
            con_hash[3, 0, :, i] = hv
        con_op[2, 0] = 0
        con_exp[2, 0] = fusedbatch.split_hash(str_hash("web"))
        con_op[3, 0] = 0
        con_exp[3, 0] = fusedbatch.split_hash(str_hash("db"))
        groups = FusedGroups(
            # groups 0+1: same service slot, maxrep=1, k=4 each
            # groups 2+3: conflicting tier constraints, k=3 each
            k=jnp.asarray(np.array([4, 4, 3, 3], np.int32)),
            slot=jnp.asarray(np.array([0, 0, 1, 1], np.int32)),
            maxrep=jnp.asarray(np.array([1, 1, 0, 0], np.int32)),
            cpu_d=jnp.zeros(g, jnp.int64),
            mem_d=jnp.zeros(g, jnp.int64),
            con_hash=jnp.asarray(con_hash),
            con_op=jnp.asarray(con_op), con_exp=jnp.asarray(con_exp),
            plat=jnp.full((g, 1, 4), -1, jnp.int32),
            failures=jnp.zeros((g, nb), jnp.int32),
            leaf=jnp.zeros((g, nb), jnp.int32),
            extra_mask=jnp.ones((g, nb), jnp.bool_))
        carry = FusedCarry(
            total=jnp.zeros(nb, jnp.int32),
            cpu=jnp.zeros(nb, jnp.int64), mem=jnp.zeros(nb, jnp.int64),
            svc_acc=jnp.zeros((sb, nb), jnp.int32))
        xs, fcs, spills, out = plan_fused_jit(shared, groups, carry, 1)
        xs = np.asarray(xs)
    # carry sequencing: same-service maxrep=1 groups on disjoint nodes
    assert xs[0].sum() == 4 and xs[1].sum() == 4
    assert np.all(xs[0] * xs[1] == 0), (xs[0], xs[1])
    # segment masking: conflicting constraints score disjoint node sets
    assert xs[2].sum() == 3 and xs[3].sum() == 3
    assert np.all(xs[2][~web] == 0), xs[2]
    assert np.all(xs[3][web] == 0), xs[3]
    # carry accounting matches the placements
    acc = np.asarray(out.svc_acc)
    assert np.array_equal(acc[0], xs[0] + xs[1])
    assert np.array_equal(acc[1], xs[2] + xs[3])


# ------------------------------------------------------ degraded routes

def test_constraint_overflow_breaks_run_at_probe(frozen_clock):
    """A group whose constraint count overflows the shared bucket ladder
    is not fusable; it breaks the run and rides the per-group (-> host
    fallback) path while its neighbors still fuse."""
    many = [f"node.labels.k{i}==v" for i in range(20)]  # > CC max (16)
    specs = [
        TaskSpec(resources=_RES),
        TaskSpec(placement=Placement(constraints=many), resources=_RES),
        TaskSpec(resources=_RES),
    ]
    dn, sn, en, _, pn = _run_tick(
        _many_service_store(3, specs=specs), 2, fused=True)
    d0, s0, e0, _, p0 = _run_tick(
        _many_service_store(3, specs=specs), 2, fused=False)
    assert (dn, sn, en) == (d0, s0, e0)
    assert pn.stats["groups_fallback"] == 1   # the 20-constraint group


def test_fused_build_failure_falls_back_group_by_group(frozen_clock,
                                                       monkeypatch):
    """A fused batch that cannot be built degrades to per-group
    dispatches with identical placements — never a failed tick."""
    ref = _run_tick(_many_service_store(4), 2, fused=False)
    monkeypatch.setattr(fusedbatch, "build_run",
                        lambda planner, sched, specs: None)
    out = _run_tick(_many_service_store(4), 2, fused=True)
    assert out[:3] == ref[:3]
    assert out[4].stats.get("groups_fused", 0) == 0
    assert out[4].stats["groups_planned"] == 4
    assert out[4].stats.get("fused_overflows", 0) >= 1


def test_fused_dispatch_error_falls_back_group_by_group(frozen_clock,
                                                        monkeypatch):
    """A device error inside the fused dispatch marks the fused path
    dead for the tick; every group still places via the per-group path
    and the tick's outputs are unchanged."""
    ref = _run_tick(_many_service_store(4), 2, fused=False)

    def boom(*a, **k):
        raise RuntimeError("injected fused dispatch failure")

    monkeypatch.setattr(planner_mod, "plan_fused_jit", boom)
    out = _run_tick(_many_service_store(4), 2, fused=True)
    assert out[:3] == ref[:3]
    p = out[4]
    assert p.stats.get("groups_fused", 0) == 0
    assert p.stats["groups_planned"] == 4
    assert p.stats.get("groups_device_error", 0) >= 1
    assert p._fused_dead


def test_fused_spill_routes_group_to_host(frozen_clock):
    """A spread branch saturating mid-run aborts the fused run and the
    group takes the host oracle, exactly like the per-group spill route;
    placements match the per-group path."""
    # rack r4 holds a single tiny node (capacity 2); spreading 40 tasks
    # over 5 racks wants 8 there -> the branch saturates -> spill
    store_fused = MemoryStore()
    store_plain = MemoryStore()
    spread = TaskSpec(placement=Placement(preferences=[
        PlacementPreference(spread=SpreadOver(
            spread_descriptor="node.labels.rack"))]),
        resources=ResourceRequirements(reservations=Resources(
            nano_cpus=10**9, memory_bytes=1 << 30)))
    plain = TaskSpec(resources=_RES)
    for store in (store_fused, store_plain):
        nodes = _mk_nodes(16)
        nodes.append(Node(
            id="n9999",
            spec=NodeSpec(annotations=Annotations(
                name="tiny", labels={"rack": "r9", "tier": "web"})),
            status=NodeStatus(state=NodeState.READY),
            description=NodeDescription(
                hostname="tiny",
                platform=Platform(os="linux", architecture="amd64"),
                resources=Resources(nano_cpus=2 * 10**9,
                                    memory_bytes=2 << 30))))
        store.update(lambda tx, nodes=nodes:
                     [tx.create(n) for n in nodes])
        batches = [_mk_service("svc0", 30, plain),
                   _mk_service("svc1", 60, spread),
                   _mk_service("svc2", 30, plain)]
        def mk(tx, batches=batches):
            for svc, tasks in batches:
                tx.create(svc)
                for t in tasks:
                    tx.create(t)
        store.update(mk)
    dn, sn, en, _, pn = _run_tick(store_fused, 2, fused=True)
    d0, s0, e0, _, p0 = _run_tick(store_plain, 2, fused=False)
    assert (dn, sn, en) == (d0, s0, e0)
    assert p0.stats.get("groups_spill_to_host", 0) >= 1, \
        "workload no longer spills; rebuild it so the route is covered"
    assert pn.stats.get("groups_spill_to_host", 0) >= 1


# ------------------------------------------------------------- sharding

def test_fused_mesh_parity(frozen_clock):
    """ShardedPlanFn's fused path (node axis over a 4-device mesh) must
    produce byte-identical state/events to the single-device program."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices (conftest forces 8 host devices)")
    from swarmkit_tpu.parallel import ShardedPlanFn, make_mesh
    mesh_fn = ShardedPlanFn(make_mesh(jax.devices()[:4]))
    dm, sm, em, _, pm = _run_tick(_many_service_store(5), 2, fused=True,
                                  planner=TPUPlanner(plan_fn=mesh_fn))
    d1, s1, e1, _, p1 = _run_tick(_many_service_store(5), 2, fused=True)
    assert pm.stats.get("groups_fused", 0) == 5
    assert (dm, sm, em) == (d1, s1, e1)


# -------------------------------------------------------- sim differential

def test_fused_differential_scenario():
    """The sim's differential scenario: fused placements must equal
    per-service placements per seed under churn (host-fallback, failure
    down-weighting, drains, breaker trip, leadership stepdown)."""
    from swarmkit_tpu.sim import run_scenario
    r = run_scenario("fused-differential-churn", seed=7)
    assert r.ok, r.violations


def test_fused_differential_detects_divergence(monkeypatch):
    """Checker sensitivity: a fused batch that mis-densifies the
    per-service base counts MUST diverge from the per-service oracle,
    and the differential must catch it — a comparison that can't fire
    is a no-op."""
    from swarmkit_tpu.sim import run_scenario
    orig = fusedbatch.build_run

    def broken(planner, sched, specs):
        run = orig(planner, sched, specs)
        if run is not None:
            run.shared = run.shared._replace(
                svc0=np.zeros_like(run.shared.svc0))
        return run

    monkeypatch.setattr(fusedbatch, "build_run", broken)
    r = run_scenario("fused-differential-churn", seed=7)
    assert any("fused-differential" in v and "diverged" in v
               for v in r.violations), r.violations


def test_mesh_env_knob(monkeypatch):
    """SWARM_PLANNER_MESH builds the mesh at planner construction; a
    count beyond the available devices is a loud error."""
    import jax
    monkeypatch.setenv("SWARM_PLANNER_MESH", "2")
    p = TPUPlanner()
    assert p.mesh is not None and p.mesh.shape["nodes"] == 2
    assert p._fused_fn is p._plan_fn
    monkeypatch.setenv("SWARM_PLANNER_MESH", "1")
    assert TPUPlanner().mesh is None
    monkeypatch.setenv("SWARM_PLANNER_MESH",
                       str(len(jax.devices()) + 1))
    with pytest.raises(RuntimeError):
        TPUPlanner()
