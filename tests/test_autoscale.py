"""Autoscaler + multi-tenant QoS (ISSUE 12): the AutoscaleSupervisor's
policy machinery (hysteresis, rate limits, flap breaker, replicated
resume state), the tenant quota plane (admission clamp + the device
quota-mask column, byte-identical to the host oracle), the tenant-storm
scenario under its four new invariants — each proven LIVE by a
checker-sensitivity test — the batched dispatcher fan-out, the
autoscale_flapping health check, and the chaos-sweep wiring.
"""

import json
import os
import subprocess
import sys

import pytest

from swarmkit_tpu.models import (
    Annotations, Node, NodeDescription, NodeSpec, NodeState, NodeStatus,
    ReplicatedService, Resources, ResourceRequirements, Service,
    ServiceMode, ServiceSpec, Task, TaskSpec, TaskState, TaskStatus,
    Version,
)
from swarmkit_tpu.models import types as mtypes
from swarmkit_tpu.models.objects import Cluster
from swarmkit_tpu.models.specs import AutoscaleConfig, ClusterSpec
from swarmkit_tpu.models.types import TenantQuota, now
from swarmkit_tpu.orchestrator.autoscaler import (
    Supervisor as AutoscaleSupervisor,
)
from swarmkit_tpu.scheduler import Scheduler
from swarmkit_tpu.scheduler.quota import TENANT_LABEL, TenantLedger
from swarmkit_tpu.sim.cluster import Sim
from swarmkit_tpu.sim.faults import NetConfig
from swarmkit_tpu.sim.scenario import run_scenario
from swarmkit_tpu.state.store import MemoryStore

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import chaos_sweep  # noqa: E402

CPU = 2 * 10 ** 9
GB = 1 << 30


@pytest.fixture(autouse=True)
def _restore_autoscale_health_gauges():
    """The flap/out-of-bounds sensitivity tests deliberately drive the
    process-global registry's autoscale gauges into warn/fail states;
    park them back at 0 so every later health assertion in the process
    (e.g. test_obs.py's all-pass verdict on clean books) judges its own
    run — the swarm_stale_reads discipline from the follower-reads tests."""
    yield
    from swarmkit_tpu.utils.metrics import registry
    for prefix in ('swarm_autoscale_flapping{service="',
                   'swarm_autoscale_out_of_bounds{service="'):
        for name, v in registry.gauges_snapshot(prefix).items():
            if v:
                registry.gauge(name, 0.0)


# ---------------------------------------------------------------------------
# supervisor policy unit tests (fake clock through the models.types seam)
# ---------------------------------------------------------------------------

def _mk_autoscaled_store(replicas=2, tenant="", **cfg_kwargs):
    store = MemoryStore()
    cfg = AutoscaleConfig(**cfg_kwargs)
    labels = {TENANT_LABEL: tenant} if tenant else {}

    def mk(tx):
        tx.create(Service(
            id="svc-a",
            spec=ServiceSpec(
                annotations=Annotations(name="svc-a", labels=labels),
                mode=ServiceMode.REPLICATED,
                replicated=ReplicatedService(replicas=replicas),
                task=TaskSpec(),
                autoscale=cfg),
            spec_version=Version(index=1)))
    store.update(mk)
    return store


def _replicas(store, sid="svc-a"):
    return store.view(lambda tx: tx.get(Service, sid)) \
        .spec.replicated.replicas


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def test_supervisor_scales_up_bounded_and_rate_limited():
    clock = _Clock()
    mtypes.set_time_source(clock)
    try:
        store = _mk_autoscaled_store(
            replicas=2, min_replicas=2, max_replicas=10,
            target_utilization=1.0, scale_up_step=3,
            stabilization_window=5.0)
        load = {"v": 40.0}
        sup = AutoscaleSupervisor(
            store, sampler=lambda sid: {"load": load["v"]},
            start_worker=False)
        sup.drive()
        assert _replicas(store) == 5          # one step, not the ideal
        sup.drive()
        assert _replicas(store) == 5          # rate-limited
        assert sup.stats["rate_limited"] >= 1
        clock.t += 6.0
        sup.drive()
        assert _replicas(store) == 8
        clock.t += 6.0
        sup.drive()
        clock.t += 6.0
        sup.drive()
        assert _replicas(store) == 10         # clamped at max
        # load removed: walks back down inside bounds
        load["v"] = 0.0
        for _ in range(8):
            clock.t += 6.0
            sup.drive()
        assert _replicas(store) == 2
        svc = store.view(lambda tx: tx.get(Service, "svc-a"))
        assert svc.autoscale_status is not None
        assert svc.autoscale_status.last_decision_at > 0
    finally:
        mtypes.set_time_source(None)


def test_supervisor_hysteresis_deadband_holds():
    clock = _Clock()
    mtypes.set_time_source(clock)
    try:
        store = _mk_autoscaled_store(
            replicas=4, min_replicas=1, max_replicas=10,
            target_utilization=1.0, hysteresis=0.2,
            stabilization_window=1.0)
        # util = 4.4/4 = 1.1 < 1.2: inside the deadband, no decision
        sup = AutoscaleSupervisor(
            store, sampler=lambda sid: {"load": 4.4},
            start_worker=False)
        for _ in range(5):
            clock.t += 2.0
            sup.drive()
        assert _replicas(store) == 4
        assert sup.stats["decisions"] == 0
    finally:
        mtypes.set_time_source(None)


def test_supervisor_flap_breaker_freezes_policy():
    """An oscillating signal reverses direction every window: after
    flap_reversals reversals the policy freezes (no further writes) and
    exports the flapping gauge the health check warns on."""
    from swarmkit_tpu.utils.metrics import registry as reg
    clock = _Clock()
    mtypes.set_time_source(clock)
    try:
        store = _mk_autoscaled_store(
            replicas=5, min_replicas=1, max_replicas=10,
            target_utilization=1.0, scale_up_step=1, scale_down_step=1,
            stabilization_window=2.0, flap_reversals=3, hysteresis=0.1)
        flip = {"hi": True}

        def sampler(sid):
            # alternate far above / far below target per drive
            return {"load": 50.0 if flip["hi"] else 0.0}

        sup = AutoscaleSupervisor(store, sampler=sampler,
                                  start_worker=False)
        writes_before_freeze = []
        for _ in range(12):
            clock.t += 2.0
            sup.drive()
            flip["hi"] = not flip["hi"]
            svc = store.view(lambda tx: tx.get(Service, "svc-a"))
            if svc.autoscale_status is not None \
                    and svc.autoscale_status.frozen_until > clock.t:
                break
            writes_before_freeze.append(_replicas(store))
        svc = store.view(lambda tx: tx.get(Service, "svc-a"))
        assert svc.autoscale_status.frozen_until > clock.t, \
            "flap breaker never engaged"
        frozen_at = _replicas(store)
        assert reg.get_gauge(
            'swarm_autoscale_flapping{service="svc-a"}') == 1.0
        for _ in range(3):
            clock.t += 2.0
            sup.drive()
            flip["hi"] = not flip["hi"]
        assert _replicas(store) == frozen_at, \
            "frozen policy must not write replica changes"
        assert sup.stats["frozen_skips"] >= 1
    finally:
        mtypes.set_time_source(None)


def test_supervisor_resumes_from_replicated_status():
    """Failover shape: a FRESH supervisor (successor leader) over the
    same store respects the previous reign's stabilization window —
    the stamp rides the Service row, not supervisor memory."""
    clock = _Clock()
    mtypes.set_time_source(clock)
    try:
        store = _mk_autoscaled_store(
            replicas=2, min_replicas=2, max_replicas=10,
            target_utilization=1.0, scale_up_step=2,
            stabilization_window=8.0)
        sampler = lambda sid: {"load": 40.0}   # noqa: E731
        sup1 = AutoscaleSupervisor(store, sampler=sampler,
                                   start_worker=False)
        sup1.drive()
        assert _replicas(store) == 4
        # "failover": a brand-new supervisor, 2s later — still inside
        # the window, must NOT step again
        clock.t += 2.0
        sup2 = AutoscaleSupervisor(store, sampler=sampler,
                                   start_worker=False)
        sup2.drive()
        assert _replicas(store) == 4
        assert sup2.stats["rate_limited"] == 1
        clock.t += 8.0
        sup2.drive()
        assert _replicas(store) == 6
    finally:
        mtypes.set_time_source(None)


# ---------------------------------------------------------------------------
# tenant quota plane: ledger arithmetic + host/device parity
# ---------------------------------------------------------------------------

def test_tenant_ledger_admit_and_charge():
    ledger = TenantLedger()
    cluster = Cluster(id="c", spec=ClusterSpec(
        annotations=Annotations(name="default"),
        tenants={"t": TenantQuota(nano_cpus=6 * CPU, max_tasks=5)}))
    ledger.load_cluster(cluster)
    ledger.begin_tick({})
    assert ledger.admit("other", CPU, 0, 10) is None   # unquota'd
    assert ledger.admit("t", CPU, 0, 10) == 5          # max_tasks binds
    assert ledger.admit("t", 2 * CPU, 0, 10) == 3      # cpu binds
    ledger.charge("t", 2 * CPU, 0, 2)
    assert ledger.admit("t", 2 * CPU, 0, 10) == 1
    ledger.charge("t", 2 * CPU, 0, 1)
    assert ledger.admit("t", 2 * CPU, 0, 10) == 0


def _quota_store(n_nodes=6):
    """Cluster with a tight low-tenant quota: svc-part (10 tasks, quota
    admits 4), svc-blocked (same tenant, wholly exhausted), svc-free
    (untenanted).  Multiple services = a fusable run on the device
    path, so the quota column rides the FUSED program too."""
    store = MemoryStore()
    store.update(lambda tx: tx.create(Cluster(
        id="cluster-default",
        spec=ClusterSpec(
            annotations=Annotations(name="default"),
            tenants={"lo": TenantQuota(nano_cpus=4 * CPU)}))))

    def mk_nodes(tx):
        for i in range(n_nodes):
            tx.create(Node(
                id=f"qn{i}", spec=NodeSpec(
                    annotations=Annotations(name=f"qn{i}")),
                status=NodeStatus(state=NodeState.READY),
                description=NodeDescription(
                    hostname=f"qn{i}",
                    resources=Resources(nano_cpus=8 * 10 ** 9,
                                        memory_bytes=32 * GB))))
    store.update(mk_nodes)
    res = ResourceRequirements(
        reservations=Resources(nano_cpus=CPU, memory_bytes=GB))

    def mk(tx):
        for sid, tenant, count in (("svc-part", "lo", 10),
                                   ("svc-blocked", "lo", 5),
                                   ("svc-free", "", 8)):
            labels = {TENANT_LABEL: tenant} if tenant else {}
            ann = Annotations(name=sid, labels=labels)
            svc = Service(
                id=sid,
                spec=ServiceSpec(
                    annotations=ann, mode=ServiceMode.REPLICATED,
                    replicated=ReplicatedService(replicas=count),
                    task=TaskSpec(resources=res)),
                spec_version=Version(index=1))
            tx.create(svc)
            for s in range(count):
                tx.create(Task(
                    id=f"{sid}-{s:03d}", service_id=sid, slot=s + 1,
                    desired_state=TaskState.RUNNING,
                    spec=svc.spec.task, spec_version=Version(index=1),
                    service_annotations=ann,
                    status=TaskStatus(state=TaskState.PENDING,
                                      timestamp=now())))
    store.update(mk)
    return store


def _placement_claim(store):
    """The host/device equivalence claim: per-service per-node
    placement DISTRIBUTIONS plus per-task (state, err) — per-task node
    identity is not part of the contract (the device path fills node
    slots in column order, the host round-robins)."""
    per_node = {}
    per_task = []
    for t in store.view(lambda tx: tx.find(Task)):
        key = (t.service_id, t.node_id)
        if t.node_id:
            per_node[key] = per_node.get(key, 0) + 1
        per_task.append((t.id, bool(t.node_id), int(t.status.state),
                         t.status.err or ""))
    dist = {}
    for (sid, _node), count in per_node.items():
        dist.setdefault(sid, []).append(count)
    return ({sid: sorted(counts) for sid, counts in dist.items()},
            sorted(per_task))


def _run_quota_tick(planner):
    store = _quota_store()
    sched = Scheduler(store, batch_planner=planner)
    if planner is not None:
        planner.enable_small_group_routing = False
    store.view(sched._setup_tasks_list)
    sched.tick()
    dist, per_task = _placement_claim(store)
    return store, sched, (dist, per_task)


def test_quota_clamps_and_blocks_host_path():
    store, sched, (dist, per_task) = _run_quota_tick(None)
    placed = {"svc-part": 0, "svc-blocked": 0, "svc-free": 0}
    for tid, assigned, state, err in per_task:
        sid = tid.rsplit("-", 1)[0]
        if assigned and state >= int(TaskState.ASSIGNED):
            placed[sid] += 1
    # 4-task quota: svc-part admits 4, svc-blocked wholly blocked
    assert placed == {"svc-part": 4, "svc-blocked": 0, "svc-free": 8}, \
        placed
    assert sched.stats["quota_clamps"] == 6
    errs = {err for tid, _n, _s, err in per_task
            if tid.startswith("svc-blocked")}
    assert errs == {"no suitable node (over tenant quota on 6 nodes)"}, \
        errs
    part_errs = {err for tid, n, _s, err in per_task
                 if tid.startswith("svc-part") and not n}
    assert part_errs == {'over tenant quota (tenant "lo")'}, part_errs


def test_quota_device_path_byte_identical_to_host():
    """The quota mask column end to end: the device planner (per-group
    AND fused routes) must place, defer, and explain exactly like the
    host oracle."""
    from swarmkit_tpu.ops import TPUPlanner
    _, _, host_rows = _run_quota_tick(None)
    planner = TPUPlanner()
    _, sched, dev_rows = _run_quota_tick(planner)
    assert dev_rows == host_rows
    assert sched.quota.stats["blocked_groups"] >= 1
    # the multi-service pending queue fused (quota column in the fused
    # program, not just the per-group one)
    assert planner.stats.get("groups_fused", 0) >= 2, planner.stats


def test_quota_clamped_burst_on_the_device_counts_clamps_and_compiles_once():
    """A clamped tenant's burst through the device planner: admission
    control fires (the host path's six clamps), no group leaves the
    device for it, and the same tick again, warm, compiles nothing."""
    from swarmkit_tpu.ops import TPUPlanner
    from test_scheduler import cold_then_warm

    def tick():
        planner = TPUPlanner()
        _, sched, _ = _run_quota_tick(planner)
        assert sched.stats["quota_clamps"] == 6
        assert not planner.stats.get("groups_fallback", 0), planner.stats
    cold_then_warm(tick)


def test_quota_differential_fuzz_random_tenants():
    """Seeded fuzz: random clusters, tenants, quotas and demands —
    device placements (and quota diagnostics) must equal the host
    oracle's byte for byte."""
    import random as _random
    from swarmkit_tpu.ops import TPUPlanner

    for seed in range(6):
        rng = _random.Random(7000 + seed)
        n_nodes = rng.randrange(3, 10)
        tenants = {}
        for ti in range(rng.randrange(1, 4)):
            tenants[f"t{ti}"] = TenantQuota(
                nano_cpus=rng.randrange(0, 8) * CPU,
                max_tasks=rng.randrange(0, 6))
        services = []
        for si in range(rng.randrange(2, 5)):
            services.append((
                f"s{seed}-{si}",
                rng.choice([""] + list(tenants)),
                rng.randrange(1, 8),
                rng.randrange(0, 3) * 10 ** 9))

        def build():
            store = MemoryStore()
            store.update(lambda tx: tx.create(Cluster(
                id="cluster-default",
                spec=ClusterSpec(
                    annotations=Annotations(name="default"),
                    tenants={k: TenantQuota(nano_cpus=q.nano_cpus,
                                            max_tasks=q.max_tasks)
                             for k, q in tenants.items()}))))

            def mk(tx):
                for i in range(n_nodes):
                    tx.create(Node(
                        id=f"fn{i}", spec=NodeSpec(
                            annotations=Annotations(name=f"fn{i}")),
                        status=NodeStatus(state=NodeState.READY),
                        description=NodeDescription(
                            hostname=f"fn{i}",
                            resources=Resources(
                                nano_cpus=8 * 10 ** 9,
                                memory_bytes=32 * GB))))
                for sid, tenant, count, cpu_d in services:
                    labels = {TENANT_LABEL: tenant} if tenant else {}
                    ann = Annotations(name=sid, labels=labels)
                    spec = TaskSpec(resources=ResourceRequirements(
                        reservations=Resources(nano_cpus=cpu_d)))
                    tx.create(Service(
                        id=sid,
                        spec=ServiceSpec(
                            annotations=ann,
                            mode=ServiceMode.REPLICATED,
                            replicated=ReplicatedService(replicas=count),
                            task=spec),
                        spec_version=Version(index=1)))
                    for s in range(count):
                        tx.create(Task(
                            id=f"{sid}-{s:03d}", service_id=sid,
                            slot=s + 1,
                            desired_state=TaskState.RUNNING,
                            spec=spec, spec_version=Version(index=1),
                            service_annotations=ann,
                            status=TaskStatus(
                                state=TaskState.PENDING,
                                timestamp=now())))
            store.update(mk)
            return store

        def run(planner):
            store = build()
            sched = Scheduler(store, batch_planner=planner)
            if planner is not None:
                planner.enable_small_group_routing = False
            store.view(sched._setup_tasks_list)
            sched.tick()
            return _placement_claim(store)

        host = run(None)
        device = run(TPUPlanner())
        assert host == device, (seed, host, device)


def test_quota_clamped_tenant_does_not_preempt():
    """A tenant at its quota must not preempt its way past it: QoS
    clamps at admission, full stop."""
    store = MemoryStore()
    store.update(lambda tx: tx.create(Cluster(
        id="cluster-default",
        spec=ClusterSpec(
            annotations=Annotations(name="default"),
            tenants={"cap": TenantQuota(nano_cpus=2 * CPU)}))))

    def mk(tx):
        tx.create(Node(
            id="n0", spec=NodeSpec(annotations=Annotations(name="n0")),
            status=NodeStatus(state=NodeState.READY),
            description=NodeDescription(
                hostname="n0",
                resources=Resources(nano_cpus=8 * 10 ** 9,
                                    memory_bytes=32 * GB))))
        res = ResourceRequirements(
            reservations=Resources(nano_cpus=CPU))
        lo_ann = Annotations(name="lo")
        lo_spec = TaskSpec(priority=0, resources=res)
        hi_ann = Annotations(name="hi", labels={TENANT_LABEL: "cap"})
        hi_spec = TaskSpec(priority=9, resources=res)
        for sid, ann, spec, n in (("lo", lo_ann, lo_spec, 2),
                                  ("hi", hi_ann, hi_spec, 4)):
            tx.create(Service(
                id=sid, spec=ServiceSpec(
                    annotations=ann, mode=ServiceMode.REPLICATED,
                    replicated=ReplicatedService(replicas=n),
                    task=spec),
                spec_version=Version(index=1)))
        for s in range(2):
            tx.create(Task(
                id=f"lo-r{s}", service_id="lo", slot=s + 1,
                desired_state=TaskState.RUNNING, spec=lo_spec,
                spec_version=Version(index=1), node_id="n0",
                service_annotations=lo_ann,
                status=TaskStatus(state=TaskState.RUNNING,
                                  timestamp=now())))
        for s in range(4):
            tx.create(Task(
                id=f"hi-p{s}", service_id="hi", slot=s + 1,
                desired_state=TaskState.RUNNING, spec=hi_spec,
                spec_version=Version(index=1),
                service_annotations=hi_ann,
                status=TaskStatus(state=TaskState.PENDING,
                                  timestamp=now())))
    store.update(mk)
    sched = Scheduler(store)
    store.view(sched._setup_tasks_list)
    sched.tick()
    tasks = {t.id: t for t in store.view(lambda tx: tx.find(Task))}
    placed_hi = sum(1 for t in tasks.values()
                    if t.service_id == "hi" and t.node_id)
    # quota admits 2 of the 4 high-band tasks; the node has 2 free cpus,
    # so NO preemption is needed for them — and the other 2 must not
    # evict the low band to get in
    assert placed_hi == 2, placed_hi
    assert tasks["lo-r0"].desired_state == TaskState.RUNNING
    assert tasks["lo-r1"].desired_state == TaskState.RUNNING
    assert sched.stats.get("preemptions", 0) == 0


def test_within_quota_tenant_still_preempts():
    """The other half of the quota/preemption contract: a group FULLY
    inside its quota (admitted and charged this tick) keeps its
    preemption entitlement — its own admission charge must not read as
    'no quota left' when the pass computes headroom."""
    store = MemoryStore()
    store.update(lambda tx: tx.create(Cluster(
        id="cluster-default",
        spec=ClusterSpec(
            annotations=Annotations(name="default"),
            tenants={"cap": TenantQuota(nano_cpus=4 * CPU)}))))

    def mk(tx):
        tx.create(Node(
            id="n0", spec=NodeSpec(annotations=Annotations(name="n0")),
            status=NodeStatus(state=NodeState.READY),
            description=NodeDescription(
                hostname="n0",
                resources=Resources(nano_cpus=8 * 10 ** 9,
                                    memory_bytes=32 * GB))))
        res = ResourceRequirements(
            reservations=Resources(nano_cpus=CPU))
        lo_ann = Annotations(name="lo")
        lo_spec = TaskSpec(priority=0, resources=res)
        hi_ann = Annotations(name="hi", labels={TENANT_LABEL: "cap"})
        hi_spec = TaskSpec(priority=9, resources=res)
        for sid, ann, spec, n in (("lo", lo_ann, lo_spec, 4),
                                  ("hi", hi_ann, hi_spec, 2)):
            tx.create(Service(
                id=sid, spec=ServiceSpec(
                    annotations=ann, mode=ServiceMode.REPLICATED,
                    replicated=ReplicatedService(replicas=n),
                    task=spec),
                spec_version=Version(index=1)))
        # the low band FILLS the node: the within-quota high band can
        # only place by evicting
        for s in range(4):
            tx.create(Task(
                id=f"lo-r{s}", service_id="lo", slot=s + 1,
                desired_state=TaskState.RUNNING, spec=lo_spec,
                spec_version=Version(index=1), node_id="n0",
                service_annotations=lo_ann,
                status=TaskStatus(state=TaskState.RUNNING,
                                  timestamp=now())))
        for s in range(2):
            tx.create(Task(
                id=f"hi-p{s}", service_id="hi", slot=s + 1,
                desired_state=TaskState.RUNNING, spec=hi_spec,
                spec_version=Version(index=1),
                service_annotations=hi_ann,
                status=TaskStatus(state=TaskState.PENDING,
                                  timestamp=now())))
    store.update(mk)
    sched = Scheduler(store)
    store.view(sched._setup_tasks_list)
    sched.tick()
    tasks = {t.id: t for t in store.view(lambda tx: tx.find(Task))}
    placed_hi = sum(1 for t in tasks.values()
                    if t.service_id == "hi" and t.node_id)
    assert placed_hi == 2, placed_hi
    evicted = sum(1 for t in tasks.values()
                  if t.service_id == "lo"
                  and t.desired_state == TaskState.SHUTDOWN)
    assert evicted == 2, evicted
    assert sched.stats["preemptions"] == 2


# ---------------------------------------------------------------------------
# the scenario: green, deterministic, clamps + autoscale observed
# ---------------------------------------------------------------------------

def test_tenant_storm_green_and_deterministic():
    # warm run compiles the quota-mask jit signatures; byte-identity is
    # judged on the warm pair (the preemption-storm discipline)
    warm = run_scenario("tenant-storm", seed=0)
    assert warm.ok, warm.violations
    r1 = run_scenario("tenant-storm", seed=0)
    assert r1.ok, r1.violations
    r2 = run_scenario("tenant-storm", seed=0)
    assert r2.trace_hash == r1.trace_hash == warm.trace_hash
    assert r2.obs_trace_sha256 == r1.obs_trace_sha256
    ctl = r1.stats["control"]
    assert ctl["quota_clamps"] > 0, ctl
    assert ctl["autoscale_changes"] >= 4, ctl
    assert ctl["attaches"] >= 2, ctl          # leader crash mid-scale-up
    # end state: burst converged to min(2) + high band 4, all RUNNING
    assert r1.stats["tasks"].get("RUNNING", 0) == 6, r1.stats["tasks"]


def test_tenant_storm_coverage_cells():
    r = run_scenario("tenant-storm", seed=0, keep_trace=True)
    assert r.ok, r.violations
    matrix = chaos_sweep.coverage_matrix([r.trace])
    required = chaos_sweep.required_cells(("tenant-storm",))
    assert ("quota-clamp", "scheduler") in required
    assert chaos_sweep.uncovered(matrix, required) == [], \
        json.dumps(matrix, indent=2)
    assert chaos_sweep.classify("autoscale-burst", "") == "scheduler"
    assert "tenant-storm" in chaos_sweep.SUITES["qos"]
    assert "tenant-storm" in chaos_sweep.SUITES["default"]


# ---------------------------------------------------------------------------
# checker-sensitivity: all four new invariants must FIRE when their
# enforcement seams are disabled (house rule since PR 1)
# ---------------------------------------------------------------------------

def _mini_qos_sim(seed, build, duration=55.0, grace=20.0,
                  quota_enabled=True, preemption=True):
    sim = Sim(seed=seed, n_managers=3, n_agents=5,
              net_config=NetConfig(), raft_cp=True)
    with sim:
        cp = sim.cp
        cp.quota_enabled = quota_enabled
        cp.preemption_enabled = preemption
        sim.start_raft_workload(interval=0.8)
        build(sim, cp)
        sim.run(duration)
        sim.finish(grace=grace)
    return sim


def test_sensitivity_quota_never_exceeded():
    """Disable the scheduler's quota plane: the bursting tenant's
    committed usage runs past its quota and the checker must catch it
    from the event stream alone."""
    def build(sim, cp):
        eng = sim.engine
        eng.at(eng.clock.start + 4.0, "tenants",
               lambda: cp.configure_tenants(
                   {"t-x": TenantQuota(nano_cpus=4 * 10 ** 9)}))
        eng.at(eng.clock.start + 6.0, "over-quota band",
               lambda: cp.add_service("svc-x", 6, nano_cpus=CPU,
                                      tenant="t-x"))
    sim = _mini_qos_sim(11, build, quota_enabled=False)
    assert any("quota-never-exceeded" in v
               for v in sim.violations.items), sim.violations.items


def test_sensitivity_autoscale_within_bounds_and_rate(monkeypatch):
    """Disable the supervisor's clamp + rate limit (the built-in seam):
    the runaway policy writes past max and faster than the window — the
    checker must catch it from the committed spec stream."""
    monkeypatch.setattr(AutoscaleSupervisor, "_enforce_bounds", False)

    def build(sim, cp):
        eng = sim.engine
        eng.at(eng.clock.start + 4.0, "autoscaled svc",
               lambda: cp.add_service(
                   "svc-run", 1, nano_cpus=10 ** 8,
                   autoscale=AutoscaleConfig(
                       min_replicas=1, max_replicas=3,
                       target_utilization=1.0, scale_up_step=1,
                       stabilization_window=5.0)))
        eng.at(eng.clock.start + 6.0, "load",
               lambda: cp.set_load("svc-run", 50.0))
    sim = _mini_qos_sim(12, build, duration=40.0)
    assert any("autoscale-within-bounds-and-rate" in v
               for v in sim.violations.items), sim.violations.items


def test_sensitivity_no_cross_band_p99_violation():
    """Disable the cross-band protections (quota AND preemption): a
    low-band flood fills the cluster before the high band arrives, the
    high band starves, and its windowed p99 must blow the derived
    bound (open-ended pending tasks count — starvation cannot hide
    from a percentile)."""
    def build(sim, cp):
        eng = sim.engine
        eng.at(eng.clock.start + 4.0, "tenants",
               lambda: cp.configure_tenants(
                   {"t-lo": TenantQuota(nano_cpus=8 * 10 ** 9)}))
        # 20 x 2cpu fills 5 workers x 8cpu wholesale (quota disabled)
        eng.at(eng.clock.start + 6.0, "flood",
               lambda: cp.add_service("svc-flood", 20, nano_cpus=CPU,
                                      tenant="t-lo"))
        eng.at(eng.clock.start + 14.0, "high band starves",
               lambda: cp.add_service("svc-vip", 4, priority=10,
                                      nano_cpus=CPU))
        cp.expect_band_p99(5, 10.0, 45.0)
    sim = _mini_qos_sim(13, build, quota_enabled=False,
                        preemption=False)
    assert any("no-cross-band-p99-violation" in v
               for v in sim.violations.items), sim.violations.items


def test_sensitivity_autoscale_converges(monkeypatch):
    """Disable scale-down (the built-in seam): load removal leaves the
    replicas stranded at the burst size — the registered convergence
    expectation must fire at finish."""
    monkeypatch.setattr(AutoscaleSupervisor, "_scale_down_enabled",
                        False)

    def build(sim, cp):
        eng = sim.engine
        eng.at(eng.clock.start + 4.0, "autoscaled svc",
               lambda: cp.add_service(
                   "svc-c", 2, nano_cpus=10 ** 8,
                   autoscale=AutoscaleConfig(
                       min_replicas=2, max_replicas=8,
                       target_utilization=1.0, scale_up_step=2,
                       scale_down_step=3,
                       stabilization_window=2.0)))
        eng.at(eng.clock.start + 6.0, "load up",
               lambda: cp.set_load("svc-c", 16.0))
        eng.at(eng.clock.start + 24.0, "load removed",
               lambda: cp.set_load("svc-c", 0.0))
        cp.expect_autoscale_converge("svc-c", to=2, by=50.0)
    sim = _mini_qos_sim(14, build, duration=50.0)
    assert any("autoscale-converges" in v
               for v in sim.violations.items), sim.violations.items


def test_qos_invariants_green_by_default():
    """The harness itself must be quiet on a healthy run: quotas
    honored, autoscale inside policy, convergence green."""
    def build(sim, cp):
        eng = sim.engine
        eng.at(eng.clock.start + 4.0, "tenants",
               lambda: cp.configure_tenants(
                   {"t-a": TenantQuota(nano_cpus=16 * 10 ** 9)}))
        eng.at(eng.clock.start + 6.0, "autoscaled svc",
               lambda: cp.add_service(
                   "svc-g", 2, nano_cpus=CPU, tenant="t-a",
                   autoscale=AutoscaleConfig(
                       min_replicas=2, max_replicas=6,
                       target_utilization=1.0, scale_up_step=2,
                       scale_down_step=2,
                       stabilization_window=3.0)))
        eng.at(eng.clock.start + 10.0, "load",
               lambda: cp.set_load("svc-g", 6.0))
        eng.at(eng.clock.start + 30.0, "load removed",
               lambda: cp.set_load("svc-g", 0.0))
        cp.expect_autoscale("svc-g", at_least=6, by=30.0)
        cp.expect_autoscale_converge("svc-g", to=2, by=60.0)
    sim = _mini_qos_sim(15, build, duration=55.0)
    assert not sim.violations.items, sim.violations.items


# ---------------------------------------------------------------------------
# batched dispatcher fan-out
# ---------------------------------------------------------------------------

def _fanout_store(n_tasks=0):
    store = MemoryStore()
    store.update(lambda tx: tx.create(Node(
        id="w0", spec=NodeSpec(annotations=Annotations(name="w0")),
        status=NodeStatus(state=NodeState.UNKNOWN),
        description=NodeDescription(hostname="w0"))))
    return store


def _mk_assigned_tasks(store, n, base=0, node_id="w0"):
    def cb(tx):
        for i in range(base, base + n):
            tx.create(Task(
                id=f"ft{i:04d}", service_id="s", slot=i + 1,
                node_id=node_id, desired_state=TaskState.RUNNING,
                spec=TaskSpec(), spec_version=Version(index=1),
                status=TaskStatus(state=TaskState.ASSIGNED,
                                  timestamp=now())))
    store.update(cb)


def _drain_stream(stream):
    msgs = []
    while True:
        try:
            msgs.append(stream.get(timeout=0))
        except TimeoutError:
            return msgs
        except Exception:
            return msgs


def test_batched_fanout_bounds_sends():
    """N task assignments to one node produce <= ceil(N/batch)
    incremental sends, not N."""
    from swarmkit_tpu.manager.dispatcher import Config_, Dispatcher
    store = _fanout_store()
    d = Dispatcher(store, Config_(rate_limit_period=0.0,
                                  modification_batch_limit=100))
    d.run(start_worker=False)
    d.enable_batched_fanout()
    session, _ = d.register("w0")
    stream = d.open_assignments("w0", session)
    complete = _drain_stream(stream)
    assert [m.type for m in complete] == ["complete"]

    N = 250
    _mk_assigned_tasks(store, N)
    d.process_deadlines()
    msgs = _drain_stream(stream)
    assert all(m.type == "incremental" for m in msgs)
    assert len(msgs) <= -(-N // 100), (len(msgs), N)   # ceil(N/batch)
    delivered = [obj.id for m in msgs
                 for _a, kind, obj in m.changes if kind == "task"]
    assert len(delivered) == N
    assert len(set(delivered)) == N, "duplicated assignment"
    d.stop(flush=False)


def test_batched_fanout_no_loss_or_dup_across_leader_gap():
    """A session gap (the node's stream dies mid-burst, e.g. leader
    handoff) must not lose or duplicate assignments: the re-opened
    stream's COMPLETE is exactly the store's current set."""
    from swarmkit_tpu.manager.dispatcher import Config_, Dispatcher
    store = _fanout_store()
    d = Dispatcher(store, Config_(rate_limit_period=0.0,
                                  modification_batch_limit=100))
    d.run(start_worker=False)
    d.enable_batched_fanout()
    session, _ = d.register("w0")
    stream = d.open_assignments("w0", session)
    _drain_stream(stream)
    _mk_assigned_tasks(store, 120)
    d.process_deadlines()
    _drain_stream(stream)
    # the gap: more assignments land while the session dies
    _mk_assigned_tasks(store, 60, base=120)
    d.release_session("w0", session)
    assert stream.closed
    d.process_deadlines()      # flush with the stream down: no crash
    # re-register (the re-learn path) and reopen
    session2, _ = d.register("w0")
    stream2 = d.open_assignments("w0", session2)
    msgs = _drain_stream(stream2)
    assert msgs[0].type == "complete"
    got = sorted(obj.id for m in msgs
                 for _a, kind, obj in m.changes if kind == "task")
    want = sorted(t.id for t in store.view(lambda tx: tx.find(Task)))
    assert got == want, (len(got), len(want))
    assert len(got) == len(set(got)) == 180
    d.stop(flush=False)


# ---------------------------------------------------------------------------
# health plane + metric hygiene
# ---------------------------------------------------------------------------

def test_autoscale_flapping_health_check_transitions():
    from swarmkit_tpu.obs.health import HealthEvaluator, default_checks
    from swarmkit_tpu.utils.metrics import Registry
    reg = Registry()
    checks = [c for c in default_checks()
              if c.name == "autoscale_flapping"]
    ev = HealthEvaluator(registry=reg, checks=checks)
    assert ev.evaluate()["autoscale_flapping"] == "pass"   # no data
    reg.gauge('swarm_autoscale_flapping{service="s1"}', 0.0)
    reg.gauge('swarm_autoscale_out_of_bounds{service="s1"}', 0.0)
    assert ev.evaluate()["autoscale_flapping"] == "pass"
    reg.gauge('swarm_autoscale_flapping{service="s1"}', 1.0)
    assert ev.evaluate()["autoscale_flapping"] == "warn"
    reg.gauge('swarm_autoscale_out_of_bounds{service="s1"}', 1.0)
    assert ev.evaluate()["autoscale_flapping"] == "fail"
    reg.gauge('swarm_autoscale_flapping{service="s1"}', 0.0)
    reg.gauge('swarm_autoscale_out_of_bounds{service="s1"}', 0.0)
    assert ev.evaluate()["autoscale_flapping"] == "pass"


# ---------------------------------------------------------------------------
# slow: wide sweep + PYTHONHASHSEED independence
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_tenant_storm_wide_sweep():
    """Acceptance: 20 seeds of tenant-storm, all green under all four
    invariants, full coverage, byte-identical re-runs for sampled
    seeds."""
    # warm run first: the quota-mask jit signatures compile once per
    # process, and the cold run's one-off plan.compile events would
    # break byte-identity against warm re-runs (preemption-storm
    # discipline)
    run_scenario("tenant-storm", 0)
    reports = chaos_sweep.sweep(("tenant-storm",), n_seeds=20)
    out = chaos_sweep.verdict(reports, ("tenant-storm",), 20, 0)
    assert out["ok"], json.dumps(
        {"failures": out["failures"],
         "uncovered": out["coverage"]["uncovered"]}, indent=2)
    by_seed = {r.seed: r for r in reports}
    for seed in (0, 7, 13):
        r2 = run_scenario("tenant-storm", seed, keep_trace=True)
        assert r2.trace_hash == by_seed[seed].trace_hash, seed
        assert r2.obs_trace_sha256 == by_seed[seed].obs_trace_sha256, \
            seed


@pytest.mark.slow
def test_tenant_storm_hashseed_independent():
    """Byte-identical across PYTHONHASHSEED: hash-ordered containers
    must not leak into placement or event order."""
    code = ("from swarmkit_tpu.sim.scenario import run_scenario;"
            "r = run_scenario('tenant-storm', 0);"
            "print(r.trace_hash, r.obs_trace_sha256, r.ok)")
    outs = []
    for hs in ("0", "31337"):
        env = dict(os.environ, PYTHONHASHSEED=hs, JAX_PLATFORMS="cpu")
        p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                           env=env, capture_output=True, text=True,
                           timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append(p.stdout.strip().splitlines()[-1])
    assert outs[0] == outs[1], outs
    assert outs[0].endswith("True"), outs
