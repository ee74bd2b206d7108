"""Priority classes & device-batched preemption (ISSUE 10): the
priority model end to end, the host victim oracle vs the device kernel
(differential fuzz across buckets/seeds), the scheduler's atomic
preemption pass, the preemption-storm scenario (green + deterministic),
checker-sensitivity for all three new invariants, jobs-under-churn, and
the priority_inversion health check.
"""

import random

import numpy as np
import pytest

from swarmkit_tpu.models import (
    Annotations, Node, NodeDescription, NodeSpec, NodeState, NodeStatus,
    ReplicatedService, Resources, ResourceRequirements, Service,
    ServiceMode, ServiceSpec, Task, TaskSpec, TaskState, TaskStatus,
    Version,
)
from swarmkit_tpu.models.types import now
from swarmkit_tpu.scheduler import Scheduler
from swarmkit_tpu.scheduler import preempt as hp
from swarmkit_tpu.sim.cluster import Sim
from swarmkit_tpu.sim.faults import NetConfig
from swarmkit_tpu.sim.scenario import run_scenario
from swarmkit_tpu.state.store import MemoryStore

CPU = 2 * 10 ** 9
GB = 1 << 30


# ---------------------------------------------------------------------------
# priority model: spec propagation + queue ordering
# ---------------------------------------------------------------------------

def test_service_priority_propagates_into_task_spec():
    from swarmkit_tpu.orchestrator import common
    svc = Service(
        id="s1",
        spec=ServiceSpec(
            annotations=Annotations(name="s1"),
            mode=ServiceMode.REPLICATED,
            replicated=ReplicatedService(replicas=1),
            task=TaskSpec(),
            priority=7),
        spec_version=Version(index=1))
    t = common.new_task(None, svc, 1, "")
    assert t.spec.priority == 7
    assert common.task_priority(t) == 7
    # the propagated priority is NOT spec drift: the task is clean
    assert not common.is_task_dirty(svc, t, None)
    # a task-level priority wins over the service-level one
    svc2 = svc.copy()
    svc2.spec.task = TaskSpec(priority=3)
    t2 = common.new_task(None, svc2, 1, "")
    assert t2.spec.priority == 3


def _mk_store(n_nodes, bands, node_cpu=4 * 10 ** 9):
    """bands: [(service_id, priority, n_pending, n_running)]; running
    tasks round-robin over the nodes."""
    store = MemoryStore()

    def mk(tx):
        for i in range(n_nodes):
            tx.create(Node(
                id=f"n{i:03d}",
                spec=NodeSpec(annotations=Annotations(name=f"n{i:03d}")),
                status=NodeStatus(state=NodeState.READY),
                description=NodeDescription(
                    hostname=f"n{i:03d}",
                    resources=Resources(nano_cpus=node_cpu,
                                        memory_bytes=16 * GB))))
        for sid, prio, n_pending, n_running in bands:
            spec = TaskSpec(
                priority=prio,
                resources=ResourceRequirements(reservations=Resources(
                    nano_cpus=CPU, memory_bytes=GB)))
            tx.create(Service(
                id=sid,
                spec=ServiceSpec(
                    annotations=Annotations(name=sid),
                    mode=ServiceMode.REPLICATED,
                    replicated=ReplicatedService(
                        replicas=n_pending + n_running),
                    task=spec),
                spec_version=Version(index=1)))
            for s in range(n_running):
                tx.create(Task(
                    id=f"{sid}-r{s:03d}", service_id=sid, slot=s + 1,
                    desired_state=TaskState.RUNNING, spec=spec,
                    spec_version=Version(index=1),
                    node_id=f"n{s % n_nodes:03d}",
                    status=TaskStatus(state=TaskState.RUNNING,
                                      timestamp=now())))
            for s in range(n_pending):
                tx.create(Task(
                    id=f"{sid}-p{s:03d}", service_id=sid,
                    slot=n_running + s + 1,
                    desired_state=TaskState.RUNNING, spec=spec,
                    spec_version=Version(index=1),
                    status=TaskStatus(state=TaskState.PENDING,
                                      timestamp=now())))
    store.update(mk)
    return store


def test_priority_ordered_queue_schedules_high_band_first():
    # 2 nodes x 2 slots = 4 slots; lo enqueued BEFORE hi, but hi must
    # win the constrained capacity
    store = _mk_store(2, [("lo", 0, 4, 0), ("hi", 5, 4, 0)])
    sched = Scheduler(store)
    store.view(sched._setup_tasks_list)
    sched.tick()
    tasks = store.view(lambda tx: tx.find(Task))
    hi = [t for t in tasks if t.service_id == "hi"]
    lo = [t for t in tasks if t.service_id == "lo"]
    assert all(t.node_id for t in hi), "high band must place first"
    assert not any(t.node_id for t in lo), "no capacity left for lo"


# ---------------------------------------------------------------------------
# the preemption pass: atomic swap, budget, cooldown, strictly-lower
# ---------------------------------------------------------------------------

def test_preemption_evicts_strictly_lower_and_requeues():
    # full cluster of lo; hi arrives and must preempt exactly its size
    store = _mk_store(3, [("lo", 0, 0, 6), ("hi", 10, 2, 0)])
    sched = Scheduler(store)
    store.view(sched._setup_tasks_list)
    n = sched.tick()
    tasks = store.view(lambda tx: tx.find(Task))
    hi = [t for t in tasks if t.service_id == "hi"]
    victims = [t for t in tasks
               if "swarm.preempted.at" in t.annotations.labels]
    assert all(t.node_id and t.status.state == TaskState.ASSIGNED
               for t in hi)
    assert len(victims) == 2
    assert all(v.desired_state == TaskState.SHUTDOWN for v in victims)
    assert all(v.annotations.labels["swarm.preempted.prio"] == "0"
               and v.annotations.labels["swarm.preempted.by.prio"] == "10"
               for v in victims)
    assert sched.stats["preemptions"] == 2
    assert n >= 2
    # anti-thrash cooldown stamped per victim slot
    assert len(sched.preempt.cooldowns) == 2


def test_preemption_never_touches_equal_or_higher():
    # cluster full of priority-10 work; a priority-10 and a priority-5
    # band arrive: NOTHING may be preempted
    store = _mk_store(3, [("res", 10, 0, 6), ("same", 10, 2, 0),
                          ("below", 5, 2, 0)])
    sched = Scheduler(store)
    store.view(sched._setup_tasks_list)
    sched.tick()
    tasks = store.view(lambda tx: tx.find(Task))
    assert not any("swarm.preempted.at" in t.annotations.labels
                   for t in tasks)
    assert sched.stats["preemptions"] == 0


def test_preemption_budget_bounds_one_tick():
    store = _mk_store(4, [("lo", 0, 0, 8), ("hi", 10, 6, 0)])
    sched = Scheduler(store, preempt_budget=3)
    store.view(sched._setup_tasks_list)
    sched.tick()
    tasks = store.view(lambda tx: tx.find(Task))
    victims = [t for t in tasks
               if "swarm.preempted.at" in t.annotations.labels]
    assert len(victims) == 3, "per-tick budget must cap evictions"
    placed_hi = [t for t in tasks if t.service_id == "hi" and t.node_id]
    assert len(placed_hi) == 3


def test_preemption_cooldown_blocks_rethrash():
    store = _mk_store(2, [("lo", 0, 0, 4), ("hi", 10, 1, 0)])
    sched = Scheduler(store, preempt_cooldown=3600.0)
    store.view(sched._setup_tasks_list)
    sched.tick()
    assert sched.stats["preemptions"] == 1
    victim_slots = set(sched.preempt.cooldowns)
    # a second arrival wanting the SAME slot finds it cooling down; with
    # every other node fully occupied by cooled-down... here remaining
    # nodes still have victims, so it preempts a DIFFERENT slot
    def more(tx):
        svc = tx.get(Service, "hi").copy()
        svc.spec.replicated.replicas += 1
        tx.update(svc)
        tx.create(Task(
            id="hi-p990", service_id="hi", slot=99,
            desired_state=TaskState.RUNNING, spec=svc.spec.task,
            spec_version=Version(index=1),
            status=TaskStatus(state=TaskState.PENDING, timestamp=now())))
    store.update(more)
    sched._resync()
    sched.tick()
    assert sched.stats["preemptions"] == 2
    assert len(sched.preempt.cooldowns) == 2
    assert set(sched.preempt.cooldowns) > victim_slots


def test_unsupported_groups_are_skipped():
    # no resource demand: preemption cannot fix constraint infeasibility
    store = MemoryStore()

    def mk(tx):
        tx.create(Node(
            id="n0", spec=NodeSpec(annotations=Annotations(name="n0")),
            status=NodeStatus(state=NodeState.READY),
            description=NodeDescription(
                hostname="n0", resources=Resources(
                    nano_cpus=4 * 10 ** 9, memory_bytes=16 * GB))))
        spec = TaskSpec(priority=5)
        tx.create(Service(
            id="c", spec=ServiceSpec(
                annotations=Annotations(name="c"),
                mode=ServiceMode.REPLICATED,
                replicated=ReplicatedService(replicas=1), task=spec),
            spec_version=Version(index=1)))
        tx.create(Task(id="c-p0", service_id="c", slot=1,
                       desired_state=TaskState.RUNNING, spec=spec,
                       spec_version=Version(index=1),
                       status=TaskStatus(state=TaskState.PENDING,
                                         timestamp=now())))
    store.update(mk)
    t = store.view(lambda tx: tx.get(Task, "c-p0"))
    assert not hp.preemptable_group(t)


def test_max_replicas_groups_are_waived():
    from swarmkit_tpu.models.types import Placement
    t = Task(spec=TaskSpec(
        priority=5,
        placement=Placement(max_replicas=2),
        resources=ResourceRequirements(reservations=Resources(
            nano_cpus=CPU, memory_bytes=GB))))
    assert not hp.preemptable_group(t), \
        "max_replicas eligibility cannot be held across stacked picks"


def test_one_off_tasks_preempt_as_singletons():
    """The spec-version-less one-off bucket is heterogeneous: each task
    must be judged at its OWN priority/demand — here the priority-8
    one-off may preempt the priority-5 victim, the priority-3 one
    must not."""
    store = MemoryStore()

    def mk(tx):
        tx.create(Node(
            id="n0", spec=NodeSpec(annotations=Annotations(name="n0")),
            status=NodeStatus(state=NodeState.READY),
            description=NodeDescription(
                hostname="n0",
                resources=Resources(nano_cpus=CPU, memory_bytes=16 * GB))))
        res = ResourceRequirements(reservations=Resources(
            nano_cpus=CPU, memory_bytes=GB))
        vic_spec = TaskSpec(priority=5, resources=res)
        for sid, spec in (("vic", vic_spec),
                          ("one-hi", TaskSpec(priority=8, resources=res)),
                          ("one-lo", TaskSpec(priority=3, resources=res))):
            tx.create(Service(
                id=sid, spec=ServiceSpec(
                    annotations=Annotations(name=sid),
                    mode=ServiceMode.REPLICATED,
                    replicated=ReplicatedService(replicas=1), task=spec),
                spec_version=Version(index=1)))
        tx.create(Task(id="vic-r0", service_id="vic", slot=1,
                       desired_state=TaskState.RUNNING, spec=vic_spec,
                       spec_version=Version(index=1), node_id="n0",
                       status=TaskStatus(state=TaskState.RUNNING,
                                         timestamp=now())))
        # spec_version=None: both land in the one-off (None) bucket
        for sid in ("one-lo", "one-hi"):
            svc_spec = TaskSpec(priority=8 if sid == "one-hi" else 3,
                                resources=res)
            tx.create(Task(id=f"{sid}-p0", service_id=sid, slot=1,
                           desired_state=TaskState.RUNNING, spec=svc_spec,
                           status=TaskStatus(state=TaskState.PENDING,
                                             timestamp=now())))
    store.update(mk)
    sched = Scheduler(store)
    store.view(sched._setup_tasks_list)
    sched.tick()
    tasks = {t.id: t for t in store.view(lambda tx: tx.find(Task))}
    assert tasks["one-hi-p0"].node_id == "n0"
    assert tasks["one-hi-p0"].status.state == TaskState.ASSIGNED
    assert not tasks["one-lo-p0"].node_id, \
        "a priority-3 one-off must not ride the priority-8 selection"
    assert tasks["vic-r0"].desired_state == TaskState.SHUTDOWN
    assert sched.stats["preemptions"] == 1


# ---------------------------------------------------------------------------
# differential fuzz: device kernel vs host oracle (mirrors the
# fused-differential discipline — byte-identical picks, every bucket)
# ---------------------------------------------------------------------------

def _random_candidates(rng, n, V, with_gen=False):
    kwargs = {}
    if with_gen:
        # generic-resource victim bucket (ISSUE 12 waiver shrink): the
        # third resource column must stay byte-identical across paths
        kwargs["free_gen"] = np.array(
            [rng.randrange(0, 4) for _ in range(n)], np.int64)
        kwargs["vgen"] = np.array(
            [[rng.randrange(0, 3) for _ in range(n)]
             for _ in range(V)], np.int64)
    return hp.CandidateSet(
        infos=None,
        ok=np.array([rng.random() < 0.8 for _ in range(n)]),
        free_cpu=np.array([rng.randrange(-4, 9) * 10 ** 9
                           for _ in range(n)], np.int64),
        free_mem=np.array([rng.randrange(0, 8) * GB
                           for _ in range(n)], np.int64),
        vvalid=np.array([[rng.random() < 0.6 for _ in range(n)]
                         for _ in range(V)]),
        vprio=np.array([[rng.randrange(0, 5) for _ in range(n)]
                        for _ in range(V)], np.int32),
        vcpu=np.array([[rng.randrange(0, 5) * 10 ** 9
                        for _ in range(n)] for _ in range(V)], np.int64),
        vmem=np.array([[rng.randrange(0, 4) * GB
                        for _ in range(n)] for _ in range(V)], np.int64),
        victims=None, vb=V, n_candidates=1, **kwargs)


@pytest.mark.parametrize("n,V,with_gen",
                         [(7, 4, False), (40, 16, False), (17, 4, False),
                          (11, 4, True), (23, 16, True)])
def test_device_selection_matches_host_oracle(n, V, with_gen):
    from swarmkit_tpu.ops import preempt as dp
    for seed in range(25):
        rng = random.Random(seed * 1000 + n * 7 + V)
        cand = _random_candidates(rng, n, V, with_gen=with_gen)
        cpu_d = rng.randrange(1, 5) * 10 ** 9
        mem_d = rng.randrange(0, 3) * GB
        gen_d = rng.randrange(1, 4) if with_gen else 0
        budget = rng.randrange(1, 20)
        n_picks = min(rng.randrange(1, 12), budget)
        host = hp.select_victims_host(cand, cpu_d, mem_d, gen_d,
                                      n_picks, budget)
        dev, _label, _fn = dp.plan_victims(cand, cpu_d, mem_d, gen_d,
                                           n_picks, budget)
        assert host == dev, (seed, n, V, host, dev)


def test_generic_demand_is_preemptable_and_places():
    """The narrowed waiver end-to-end: a priority band demanding ONE
    discrete generic kind evicts a lower-priority holder of that kind
    (victims free generics too, not just cpu/memory)."""
    from swarmkit_tpu.models.types import (
        GenericResource, GenericResourceKind,
    )
    store = MemoryStore()
    gpu = [GenericResource(kind="gpu", value=2,
                           res_type=GenericResourceKind.DISCRETE)]

    def mk(tx):
        tx.create(Node(
            id="n0", spec=NodeSpec(annotations=Annotations(name="n0")),
            status=NodeStatus(state=NodeState.READY),
            description=NodeDescription(
                hostname="n0",
                resources=Resources(nano_cpus=8 * 10 ** 9,
                                    memory_bytes=16 * GB,
                                    generic=list(gpu)))))
        lo_spec = TaskSpec(priority=0, resources=ResourceRequirements(
            reservations=Resources(nano_cpus=CPU, generic=list(gpu))))
        hi_spec = TaskSpec(priority=9, resources=ResourceRequirements(
            reservations=Resources(nano_cpus=CPU, generic=list(gpu))))
        for sid, spec in (("g-lo", lo_spec), ("g-hi", hi_spec)):
            tx.create(Service(
                id=sid, spec=ServiceSpec(
                    annotations=Annotations(name=sid),
                    mode=ServiceMode.REPLICATED,
                    replicated=ReplicatedService(replicas=1), task=spec),
                spec_version=Version(index=1)))
        assert hp.preemptable_group(Task(spec=hi_spec))
        tx.create(Task(id="g-lo-r0", service_id="g-lo", slot=1,
                       desired_state=TaskState.RUNNING, spec=lo_spec,
                       spec_version=Version(index=1), node_id="n0",
                       status=TaskStatus(state=TaskState.RUNNING,
                                         timestamp=now())))
        tx.create(Task(id="g-hi-p0", service_id="g-hi", slot=1,
                       desired_state=TaskState.RUNNING, spec=hi_spec,
                       spec_version=Version(index=1),
                       status=TaskStatus(state=TaskState.PENDING,
                                         timestamp=now())))
    store.update(mk)
    sched = Scheduler(store)
    store.view(sched._setup_tasks_list)
    sched.tick()
    tasks = {t.id: t for t in store.view(lambda tx: tx.find(Task))}
    assert tasks["g-lo-r0"].desired_state == TaskState.SHUTDOWN
    assert tasks["g-hi-p0"].node_id == "n0"
    assert sched.stats["preemptions"] == 1


def test_multi_kind_generic_demand_still_waived():
    from swarmkit_tpu.models.types import (
        GenericResource, GenericResourceKind,
    )
    t = Task(spec=TaskSpec(
        priority=5,
        resources=ResourceRequirements(reservations=Resources(
            nano_cpus=CPU,
            generic=[GenericResource(kind="gpu", value=1),
                     GenericResource(kind="fpga", value=1)]))))
    assert not hp.preemptable_group(t)
    named = Task(spec=TaskSpec(
        priority=5,
        resources=ResourceRequirements(reservations=Resources(
            generic=[GenericResource(
                kind="gpu", value_str="gpu-0",
                res_type=GenericResourceKind.NAMED)]))))
    assert not hp.preemptable_group(named)


def _three_band_tick(planner):
    """A cluster full of priority-0 work, then a priority-3 and a
    priority-10 arrival, in one tick."""
    store = _mk_store(3, [("lo", 0, 0, 6), ("mid", 3, 1, 0),
                          ("hi", 10, 2, 0)])
    sched = Scheduler(store, batch_planner=planner)
    if planner is not None:
        planner.enable_small_group_routing = False
    store.view(sched._setup_tasks_list)
    sched.tick()
    return sched, sorted(
        (t.id, t.node_id, int(t.status.state), int(t.desired_state))
        for t in store.view(lambda tx: tx.find(Task)))


def test_device_and_host_schedulers_place_identically():
    from swarmkit_tpu.ops import TPUPlanner
    assert _three_band_tick(None)[1] == _three_band_tick(TPUPlanner())[1]


def test_three_bands_preempt_on_the_device_and_compile_once():
    """Three bands on a full cluster through the device planner: the
    preemption pass fires (one victim per arrival), victim selection
    stays on the device, and the same tick again, warm, compiles
    nothing."""
    from swarmkit_tpu.ops import TPUPlanner
    from test_scheduler import cold_then_warm

    def tick():
        planner = TPUPlanner()
        sched, rows = _three_band_tick(planner)
        assert sched.stats["preemptions"] == 3
        assert sum(1 for tid, node, _s, _d in rows
                   if node and tid.startswith(("mid-p", "hi-p"))) == 3
        assert not planner.stats.get("preempt_device_error", 0)
        assert not planner.stats.get("preempt_breaker_to_host", 0)
    cold_then_warm(tick)


def test_breaker_open_routes_selection_to_host():
    from swarmkit_tpu.ops import TPUPlanner
    planner = TPUPlanner()
    for _ in range(planner.breaker.threshold):
        planner.breaker.record_failure()
    assert planner.select_victims(None, CPU, GB, 0, 1, 8) is None
    assert planner.stats.get("preempt_breaker_to_host", 0) >= 1


# ---------------------------------------------------------------------------
# the scenario: green, deterministic, preemptions observed
# ---------------------------------------------------------------------------

def test_preemption_storm_green_and_deterministic():
    # first run warms the victim-kernel jit signatures: its obs trace
    # carries the one-off plan.compile events (zero-duration under the
    # virtual clock, but present), so byte-identity is judged on the
    # warm pair — same discipline as the benchmark's warm-up
    warm = run_scenario("preemption-storm", seed=0)
    assert warm.ok, warm.violations
    r1 = run_scenario("preemption-storm", seed=0)
    assert r1.ok, r1.violations
    r2 = run_scenario("preemption-storm", seed=0)
    assert r2.trace_hash == r1.trace_hash == warm.trace_hash
    assert r2.obs_trace_sha256 == r1.obs_trace_sha256
    # every band converged RUNNING (20 = 12 lo + 4 mid + 4 hi)
    assert r1.stats["tasks"].get("RUNNING", 0) == 20, r1.stats["tasks"]


def test_jobs_survive_failover_churn():
    """Jobs-under-churn: the failover scenario's replicated job must
    show all its completions despite two leadership hand-offs (the
    jobs orchestrator rides the raft-attached control plane now)."""
    r = run_scenario("failover-churn-rollout", seed=0)
    assert r.ok, r.violations
    assert r.stats["tasks"].get("COMPLETE", 0) >= 6, r.stats["tasks"]


# ---------------------------------------------------------------------------
# checker sensitivity: every new invariant must FIRE when its
# enforcement is disabled (house rule since PR 1)
# ---------------------------------------------------------------------------

def _mini_storm(seed, configure=None, duration=45.0):
    """Small contention sim: 16 lo tasks, two workers die (capacity 12),
    a 2-task priority-5 band arrives — preemption must fire; after heal
    the 18 tasks fit the 20 slots again."""
    sim = Sim(seed=seed, n_managers=3, n_agents=5,
              net_config=NetConfig(), raft_cp=True)
    with sim:
        eng = sim.engine
        cp = sim.cp
        if configure is not None:
            configure(cp)
        sim.start_raft_workload(interval=0.8)
        eng.at(eng.clock.start + 5.0, "lo band",
               lambda: cp.add_service("svc-lo", 16, priority=0,
                                      nano_cpus=CPU))
        a = cp.agents
        eng.at(eng.clock.start + 14.0, "node death w0", a[0].crash)
        eng.at(eng.clock.start + 16.0, "node death w1", a[1].crash)
        eng.at(eng.clock.start + 20.0, "hi band",
               lambda: cp.add_service("svc-hi", 2, priority=5,
                                      nano_cpus=CPU))
        eng.at(eng.clock.start + 34.0, "node return w0", a[0].restart)
        eng.at(eng.clock.start + 36.0, "node return w1", a[1].restart)
        sim.run(duration)
        sim.finish(grace=20.0)
    return sim


def test_sensitivity_no_priority_inversion():
    """Disable the preemption pass: the feasible-with-victims high band
    starves past the bound — the checker must catch the inversion."""
    def cfg(cp):
        cp.preemption_enabled = False
        cp.preempt_inversion_bound = 10.0
    sim = _mini_storm(11, cfg)
    assert any("no-priority-inversion" in v
               for v in sim.violations.items), sim.violations.items


def test_sensitivity_preempted_tasks_requeue(monkeypatch):
    """Break the requeue path (the reconciler skips services that show
    a preemption marker, so evicted slots never refill): the checker
    must report the lost work."""
    from swarmkit_tpu.orchestrator import replicated as repl
    from swarmkit_tpu.state.store import ByService
    orig = repl.Orchestrator._reconcile

    def skip_preempted(self, service):
        tasks = self.store.view(
            lambda tx: tx.find(Task, ByService(service.id)))
        if any("swarm.preempted.at" in t.annotations.labels
               for t in tasks):
            return
        orig(self, service)
    monkeypatch.setattr(repl.Orchestrator, "_reconcile", skip_preempted)
    sim = _mini_storm(12)
    assert any("preempted-tasks-requeue" in v
               for v in sim.violations.items), sim.violations.items


def test_sensitivity_preemption_thrash_bound():
    """Tighten the thrash bound below any real preemption (0): a single
    eviction must trip it — proving the rate tracking is live."""
    def cfg(cp):
        cp.preempt_thrash_bound = 0
    sim = _mini_storm(13, cfg)
    assert any("preemption-thrash-bound" in v
               for v in sim.violations.items), sim.violations.items


def test_mini_storm_is_green_by_default():
    """The sensitivity harness itself is green with enforcement on —
    the three tests above fail for the injected reason, nothing else."""
    sim = _mini_storm(14)
    assert not sim.violations.items, sim.violations.items


# ---------------------------------------------------------------------------
# obs: priority_inversion SLO check
# ---------------------------------------------------------------------------

def test_priority_inversion_health_check():
    from swarmkit_tpu.obs.health import HealthEvaluator
    from swarmkit_tpu.utils.metrics import Registry
    reg = Registry()
    ev = HealthEvaluator(registry=reg)
    assert ev.evaluate()["priority_inversion"] == "pass"
    reg.gauge("swarm_priority_inversion", 0.0)
    assert ev.evaluate()["priority_inversion"] == "pass"
    reg.gauge("swarm_priority_inversion", 2.0)
    assert ev.evaluate()["priority_inversion"] == "warn"
    reg.gauge("swarm_priority_inversion", 9.0)
    assert ev.evaluate()["priority_inversion"] == "fail"
    reg.gauge("swarm_priority_inversion", 0.0)
    assert ev.evaluate()["priority_inversion"] == "pass"


def test_preemption_metrics_exported():
    """The pass exports counters + latency edge timers the dashboards
    and the health plane read."""
    from swarmkit_tpu.utils.metrics import registry as reg
    pre0 = reg.get_counter('swarm_preemptions{reason="priority"}')
    store = _mk_store(2, [("lo", 0, 0, 4), ("hi", 10, 1, 0)])
    sched = Scheduler(store)
    store.view(sched._setup_tasks_list)
    sched.tick()
    assert reg.get_counter('swarm_preemptions{reason="priority"}') \
        == pre0 + 1
    commit_t = reg.get_timer('swarm_preempt_latency{edge="commit"}')
    assert commit_t is not None and commit_t.count > 0
    assert reg.get_gauge("swarm_priority_inversion") is not None


# ---------------------------------------------------------------------------
# slow tier: the 20-seed acceptance sweep
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_preemption_storm_wide_sweep():
    """Acceptance: 20 seeds of preemption-storm, all green (which
    includes no-preempt-equal-or-higher holding everywhere), and
    byte-identical reports on re-run for sampled seeds."""
    import sys, os
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    import chaos_sweep
    reports = chaos_sweep.sweep(("preemption-storm",), n_seeds=20)
    out = chaos_sweep.verdict(reports, ("preemption-storm",), 20, 0)
    assert out["ok"], out["failures"] or out["coverage"]["uncovered"]
    by_seed = {r.seed: r for r in reports}
    for seed in (0, 7, 13):
        r2 = run_scenario("preemption-storm", seed, keep_trace=True)
        assert r2.trace_hash == by_seed[seed].trace_hash, seed
        assert r2.violations == by_seed[seed].violations, seed
