"""Placement-scoring strategy seam tests (ISSUE 15).

Covers: the shared numeric envelope (kernel/oracle constant parity),
the placement primitives' host/device bit-parity (waterfill, packfill),
per-strategy device-kernel-vs-host-oracle differentials (unit fuzz AND
end-to-end through the scheduler), spread's byte-identity through the
seam, per-service strategy selection, breaker/fallback routing, the
node.ip hash/prefix constraint column (the closed device-path waiver),
learned-scorer artifact loading, and controlapi validation.  Slow
tier: the seam-identity scenario twin
(explicit "spread" stamped on every spec vs the unset default must be
byte-identical) across seeds and PYTHONHASHSEED.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax.numpy as jnp

from swarmkit_tpu.models import (
    Annotations, Node, NodeDescription, NodeSpec, NodeState, NodeStatus,
    Placement, PlacementPreference, ReplicatedService, Resources,
    ResourceRequirements, Service, ServiceMode, ServiceSpec, SpreadOver,
    Task, TaskSpec, TaskState, TaskStatus, Version,
)
from swarmkit_tpu.models import types as model_types
from swarmkit_tpu.ops import TPUPlanner
from swarmkit_tpu.ops import kernel as kernel_mod
from swarmkit_tpu.ops.kernel import (
    GroupInputs, NodeInputs, StrategyInputs, fetch_plan, plan_strategy_jit,
    seg_packfill, seg_waterfill,
)
from swarmkit_tpu.scheduler import Scheduler
from swarmkit_tpu.scheduler import strategy as strategy_mod
from swarmkit_tpu.state import MemoryStore
from swarmkit_tpu.utils.metrics import registry as _metrics

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def frozen_clock():
    model_types.set_time_source(lambda: 1_700_000_000.0)
    try:
        yield
    finally:
        model_types.set_time_source(None)


# ------------------------------------------------------ shared envelope

def test_constants_mirror_kernel():
    """strategy.py mirrors the kernel's numeric envelope (it cannot
    import ops — layering); this pin is what keeps them from
    drifting."""
    for name in ("K_CLAMP", "F_BIG", "FAILURE_CLAMP", "SVC_CLAMP",
                 "IDX_BITS", "TOTAL_CLAMP"):
        assert getattr(kernel_mod, name) == getattr(strategy_mod, name), \
            name
    # the canonical-here constants are importable from the kernel too
    for name in ("BP_CLAMP", "HR_CLAMP", "FEAT_CLAMP", "SCORE_CLAMP",
                 "MLP_SHIFT"):
        assert getattr(kernel_mod, name) == getattr(strategy_mod, name)


def test_registry_contents():
    assert set(strategy_mod.REGISTRY) == {
        "spread", "binpack", "weighted", "learned"}
    assert strategy_mod.resolve("spread").sid == strategy_mod.STRAT_SPREAD
    assert strategy_mod.resolve("nope") is None


# -------------------------------------------- placement primitive parity

def test_waterfill_host_matches_device_fuzz():
    rng = np.random.default_rng(1)
    for trial in range(25):
        n = int(rng.integers(1, 40))
        e = rng.integers(0, 50, n).astype(np.int64)
        if rng.random() < 0.3:   # failure-band levels
            e[rng.integers(0, n)] += strategy_mod.F_BIG * 5
        cap = rng.integers(0, 9, n).astype(np.int64)
        tie = rng.permutation(n).astype(np.int64)
        k = int(rng.integers(0, int(cap.sum()) + 3))
        xh = strategy_mod.waterfill_host(e, cap, tie, k)
        xd = np.asarray(seg_waterfill(
            jnp.asarray(e, jnp.int32), jnp.asarray(cap, jnp.int32),
            jnp.asarray(tie, jnp.int32), jnp.asarray([k], jnp.int32),
            jnp.zeros(n, jnp.int32), 1))
        assert (xh == xd).all(), (trial, e, cap, tie, k, xh, xd)


def test_packfill_host_matches_device_fuzz():
    rng = np.random.default_rng(2)
    for trial in range(25):
        n = int(rng.integers(1, 40))
        score = rng.integers(0, 1024, n).astype(np.int64)
        key = (score << strategy_mod.IDX_BITS) | np.arange(n)
        cap = rng.integers(0, 9, n).astype(np.int64)
        k = int(rng.integers(0, int(cap.sum()) + 3))
        xh = strategy_mod.packfill_host(key, cap, k)
        xd = np.asarray(seg_packfill(
            jnp.asarray(key, jnp.int32), jnp.asarray(cap, jnp.int32),
            jnp.asarray([k], jnp.int32), jnp.zeros(n, jnp.int32), 1))
        assert (xh == xd).all(), (trial, key, cap, k, xh, xd)
        # sequential-fill property: every node before the marginal one
        # (in key order) is at capacity
        order = np.argsort(key)
        seen = 0
        for i in order:
            if seen >= k:
                assert xh[i] == 0
            elif xh[i] < cap[i]:
                seen += xh[i]
                assert seen >= min(k, cap.sum())
            else:
                seen += xh[i]


def test_packfill_prefers_low_key():
    key = np.array([3 << 20, 1 << 20, 2 << 20]) | np.arange(3)
    x = strategy_mod.packfill_host(key, np.array([5, 5, 5]), 7)
    assert list(x) == [0, 5, 2]


# ------------------------------------------- kernel vs oracle (unit fuzz)

def _random_columns(rng, nb, n):
    valid = np.zeros(nb, bool)
    valid[:n] = True
    ready = valid & (rng.random(nb) < 0.95)
    res_cap = np.where(valid, rng.integers(0, 60, nb), 0).astype(np.int32)
    return {
        "valid": valid, "ready": ready, "res_cap": res_cap,
        "svc": rng.integers(0, 40, nb).astype(np.int32),
        "total": rng.integers(0, 200, nb).astype(np.int32),
        "failures": np.where(rng.random(nb) < 0.15,
                             rng.integers(1, 9, nb), 0).astype(np.int32),
        "hr_cpu": rng.integers(0, 1024, nb).astype(np.int32),
        "hr_mem": rng.integers(0, 1024, nb).astype(np.int32),
        "hr_gen": np.full(nb, strategy_mod.HR_CLAMP, np.int32),
    }


def _nodes_group(c, k, nb):
    nodes = NodeInputs(
        valid=c["valid"], ready=c["ready"], res_ok=c["valid"].copy(),
        res_cap=c["res_cap"], svc_tasks=c["svc"],
        total_tasks=c["total"], failures=c["failures"],
        leaf=np.zeros(nb, np.int32), os_hash=np.zeros((2, nb), np.int32),
        arch_hash=np.zeros((2, nb), np.int32),
        port_conflict=np.zeros(nb, bool), extra_mask=np.ones(nb, bool))
    group = GroupInputs(
        k=np.int32(k), con_hash=np.zeros((1, 2, nb), np.int32),
        con_op=np.full(1, 2, np.int32), con_exp=np.zeros((1, 2), np.int32),
        plat=np.full((1, 4), -1, np.int32), maxrep=np.int32(0),
        port_limited=np.bool_(False))
    return nodes, group


def test_strategy_kernels_match_host_oracle_fuzz():
    """Every strategy's device kernel vs its numpy oracle over random
    clusters: bit-equal placements (the contract breaker routing and
    mid-tick host demotion stand on)."""
    w1, b1, w2, b2 = strategy_mod.learned_params()
    for seed in range(8):
        rng = np.random.default_rng(seed)
        nb = int(rng.choice([64, 128]))
        n = int(rng.integers(1, nb))
        k = int(rng.integers(1, 80))
        c = _random_columns(rng, nb, n)
        nodes, group = _nodes_group(c, k, nb)
        weights = rng.integers(0, strategy_mod.W_CLAMP + 1,
                               4).astype(np.int32)
        sin = StrategyInputs(
            hr_cpu=c["hr_cpu"], hr_mem=c["hr_mem"], hr_gen=c["hr_gen"],
            weights=weights, w1=w1, b1=b1, w2=w2,
            b2=np.asarray(b2, np.int32))
        kk = min(k, strategy_mod.K_CLAMP)
        cap = np.where(c["valid"] & c["ready"],
                       np.minimum(c["res_cap"], kk), 0).astype(np.int32)
        for sid in (strategy_mod.STRAT_BINPACK,
                    strategy_mod.STRAT_WEIGHTED,
                    strategy_mod.STRAT_LEARNED):
            x, fc, spill = fetch_plan(
                plan_strategy_jit(nodes, group, sin, sid))
            if sid == strategy_mod.STRAT_BINPACK:
                xh = strategy_mod.plan_binpack_host(
                    k, cap, c["res_cap"], c["failures"])
            else:
                xh = strategy_mod.plan_arrays_host(
                    sid, k, cap, c["svc"], c["total"], c["failures"],
                    c["hr_cpu"], c["hr_mem"], c["hr_gen"],
                    weights=weights, params=(w1, b1, w2, b2),
                    ready=c["ready"])
            assert (np.asarray(x) == xh).all(), (seed, sid)
            assert not bool(spill)
            assert int(np.asarray(x).sum()) == min(k, int(cap.sum()))


# -------------------------------------------------- end-to-end scheduler

def _mk_nodes(n, cpus=lambda i: 16, addr=None):
    return [Node(
        id=f"n{i:04d}",
        spec=NodeSpec(annotations=Annotations(name=f"node-{i:04d}")),
        status=NodeStatus(state=NodeState.READY,
                          addr=addr(i) if addr else ""),
        description=NodeDescription(
            hostname=f"node-{i:04d}",
            resources=Resources(nano_cpus=cpus(i) * 10 ** 9,
                                memory_bytes=64 << 30)))
        for i in range(n)]


def _mk_workload(specs):
    """specs: list of (sid, n_tasks, TaskSpec).  Fixed ids so twin
    stores are comparable task-by-task."""
    svcs, tasks = [], []
    for sid, count, spec in specs:
        svcs.append(Service(
            id=sid,
            spec=ServiceSpec(annotations=Annotations(name=sid),
                             mode=ServiceMode.REPLICATED,
                             replicated=ReplicatedService(replicas=count),
                             task=spec),
            spec_version=Version(index=1)))
        for s in range(count):
            tasks.append(Task(
                id=f"{sid}-t{s:04d}", service_id=sid, slot=s + 1,
                desired_state=TaskState.RUNNING, spec=spec,
                spec_version=Version(index=1),
                status=TaskStatus(state=TaskState.PENDING)))
    return svcs, tasks


def _run_tick(nodes, svcs, tasks, planner):
    store = MemoryStore()

    def mk(tx):
        for node in nodes:
            tx.create(node)
        for s in svcs:
            tx.create(s)
        for t in tasks:
            tx.create(t)
    store.update(mk)
    sched = Scheduler(store, batch_planner=planner)
    store.view(sched._setup_tasks_list)
    sched.tick()
    placements = {t.id: t.node_id for t in store.view(
        lambda tx: tx.find(Task))}
    return store, sched, placements


def _strategy_spec(strategy, cpus=1, weights=None, constraints=None,
                   prefs=None):
    return TaskSpec(
        resources=ResourceRequirements(reservations=Resources(
            nano_cpus=cpus * 10 ** 9, memory_bytes=1 << 30)),
        placement=Placement(strategy=strategy,
                            strategy_weights=weights or {},
                            constraints=constraints or [],
                            preferences=prefs or []))


def _device_planner(streaming=True):
    p = TPUPlanner()
    p.enable_small_group_routing = False
    # the SWARM_STREAMING_PLANNER={0,1} pair: resident columns feed the
    # strategy kernels when on; per-tick rebuilds when off — the
    # differential must hold on both postures
    p.streaming_enabled = streaming
    return p


@pytest.mark.parametrize("streaming", [True, False],
                         ids=["streaming1", "streaming0"])
@pytest.mark.parametrize("strategy", ["binpack", "weighted", "learned"])
def test_device_matches_host_end_to_end(strategy, streaming,
                                        frozen_clock):
    """Full-stack differential: the device strategy kernel and the host
    oracle (planner=None) place the identical workload identically,
    task by task — with the streaming resident columns on AND off
    (SWARM_STREAMING_PLANNER={1,0})."""
    nodes = _mk_nodes(10, cpus=lambda i: 4 + (i % 5) * 4)
    svcs, tasks = _mk_workload(
        [("svc0", 30, _strategy_spec(strategy,
                                     weights={"cpu": 3, "spread": 1}))])
    _, _, host = _run_tick([n.copy() for n in nodes],
                           svcs, [t.copy() for t in tasks], None)
    planner = _device_planner(streaming)
    _, _, dev = _run_tick([n.copy() for n in nodes],
                          svcs, [t.copy() for t in tasks], planner)
    assert host == dev
    assert all(nid for nid in dev.values())
    assert planner.stats.get("groups_planned", 0) == 1
    assert planner.stats.get("groups_fallback", 0) == 0
    st = planner.streaming_snapshot()
    assert st["enabled"] == streaming


def test_binpack_packs_least_free_first(frozen_clock):
    """Binpack's defining property: nodes fill to capacity in
    least-free-capacity-first order, so large nodes stay whole."""
    nodes = _mk_nodes(4, cpus=lambda i: (2, 4, 8, 16)[i])
    svcs, tasks = _mk_workload([("svc0", 6, _strategy_spec("binpack"))])
    _, sched, placements = _run_tick(nodes, svcs, tasks,
                                     _device_planner())
    counts = {}
    for nid in placements.values():
        counts[nid] = counts.get(nid, 0) + 1
    # 2-cpu node holds 2, 4-cpu node the remaining 4; big nodes unused
    assert counts == {"n0000": 2, "n0001": 4}


def test_binpack_strands_less_capacity_than_spread(frozen_clock):
    """What the policy is shipped for: 64 one-CPU tasks on eight 16-CPU
    nodes leave, under spread, 8 CPUs free on every node and none that
    takes a 12-CPU task; under binpack four nodes whole.  Both ride the
    device with no fallback and no strategy served by another's path,
    and the same ticks again, warm, compile nothing."""
    from swarmkit_tpu.utils.metrics import registry
    from test_scheduler import cold_then_warm

    def stranded(strategy):
        planner = _device_planner()
        svcs, tasks = _mk_workload(
            [("svc0", 64, _strategy_spec(strategy))])
        _, sched, _ = _run_tick(_mk_nodes(8), svcs, tasks, planner)
        assert planner.stats.get("groups_planned", 0) == 1
        assert not planner.stats.get("groups_fallback", 0)
        assert not planner.stats.get("groups_strategy_host", 0)
        free = [info.available_resources.nano_cpus // 10 ** 9
                for info in sched.node_set.nodes.values()]
        assert sum(free) == 64
        return sum(f for f in free if f < 12)

    def tick():
        assert (stranded("binpack"), stranded("")) == (0, 64)
    fallbacks = registry.counters_snapshot("swarm_strategy_fallbacks")
    cold_then_warm(tick)
    assert registry.counters_snapshot(
        "swarm_strategy_fallbacks") == fallbacks


def test_weighted_weights_steer_placement(frozen_clock):
    """cpu-headroom weighting prefers the big nodes; pure spread
    weighting levels per-service counts like spread."""
    nodes = _mk_nodes(4, cpus=lambda i: (2, 2, 32, 32)[i])
    svcs, tasks = _mk_workload(
        [("svc0", 8, _strategy_spec(
            "weighted", weights={"cpu": 8, "spread": 0}))])
    _, _, placements = _run_tick(nodes, svcs, tasks, _device_planner())
    used = {nid for nid in placements.values()}
    assert used == {"n0002", "n0003"}   # high-headroom nodes only


def test_spread_explicit_equals_default_byte_identical(frozen_clock):
    """The seam-identity contract: stamping strategy="spread" routes
    through the seam's resolve path yet places EXACTLY like the unset
    default — device and host alike."""
    def build(strategy):
        spec = TaskSpec(
            resources=ResourceRequirements(reservations=Resources(
                nano_cpus=10 ** 9, memory_bytes=1 << 30)),
            placement=Placement(
                strategy=strategy,
                preferences=[PlacementPreference(spread=SpreadOver(
                    spread_descriptor="node.labels.rack"))]))
        nodes = _mk_nodes(12)
        for i, node in enumerate(nodes):
            node.spec.annotations.labels["rack"] = f"r{i % 3}"
        svcs, tasks = _mk_workload([("svc0", 25, spec),
                                    ("svc1", 13, spec)])
        return nodes, svcs, tasks

    for planner_factory in (lambda: None, _device_planner):
        nodes, svcs, tasks = build("")
        _, _, p_default = _run_tick(nodes, svcs, tasks,
                                    planner_factory())
        nodes, svcs, tasks = build("spread")
        _, _, p_spread = _run_tick(nodes, svcs, tasks,
                                   planner_factory())
        assert p_default == p_spread


def test_strategy_selectable_per_service(frozen_clock):
    """Two services with different strategies schedule in one tick,
    each through its own scorer."""
    nodes = _mk_nodes(6, cpus=lambda i: (2, 4, 8, 8, 16, 16)[i])
    svcs, tasks = _mk_workload([
        ("pack", 4, _strategy_spec("binpack")),
        ("level", 6, _strategy_spec("")),
    ])
    _, _, placements = _run_tick(nodes, svcs, tasks, _device_planner())
    pack_nodes = sorted({placements[t.id] for t in tasks
                         if t.service_id == "pack"})
    level_nodes = {placements[t.id] for t in tasks
                   if t.service_id == "level"}
    assert pack_nodes == ["n0000", "n0001"]   # packed tight
    # spread levels over every node the pack left feasible (n0000 is
    # resource-full after binpack filled it)
    assert level_nodes == {"n0001", "n0002", "n0003", "n0004", "n0005"}


def test_unknown_strategy_degrades_to_spread_and_counts(frozen_clock):
    nodes = _mk_nodes(4)
    svcs, tasks = _mk_workload([("svc0", 8, _strategy_spec("zebra"))])
    before = _metrics.get_counter(
        'swarm_strategy_fallbacks{strategy="zebra"}')
    planner = _device_planner()
    _, _, placements = _run_tick(nodes, svcs, tasks, planner)
    assert all(placements.values())
    assert _metrics.get_counter(
        'swarm_strategy_fallbacks{strategy="zebra"}') == before + 1
    assert planner.stats.get("groups_fallback", 0) == 1


def test_breaker_open_routes_to_host_oracle_bit_equal(frozen_clock):
    """The planner-breaker fallback contract: with the breaker OPEN a
    strategy group rides its host oracle and places exactly as the
    device kernel would."""
    from swarmkit_tpu.ops.planner import BREAKER_OPEN
    nodes = _mk_nodes(8, cpus=lambda i: 2 + i * 2)
    svcs, tasks = _mk_workload([("svc0", 12, _strategy_spec("binpack"))])
    _, _, dev = _run_tick([n.copy() for n in nodes], svcs,
                          [t.copy() for t in tasks], _device_planner())
    planner = _device_planner()
    planner.breaker._state = BREAKER_OPEN
    planner.breaker._open_until = model_types.now() + 3600.0
    _, _, host = _run_tick([n.copy() for n in nodes], svcs,
                           [t.copy() for t in tasks], planner)
    assert host == dev
    assert planner.stats.get("groups_planned", 0) == 0
    assert planner.stats.get("groups_breaker_to_host", 0) == 1


def test_injected_plan_fn_routes_strategy_to_host(frozen_clock):
    """An injected plan_fn owns the device path; strategy groups must
    not bypass it through plan_strategy_jit — they ride the host
    oracle, counted."""
    calls = []

    def stub_plan_fn(nodes_in, group_in, L, hier):
        calls.append(L)
        raise AssertionError("spread stub must not see strategy groups")

    nodes = _mk_nodes(6, cpus=lambda i: 2 + i * 2)
    svcs, tasks = _mk_workload([("svc0", 9, _strategy_spec("binpack"))])
    planner = TPUPlanner(plan_fn=stub_plan_fn)
    planner.enable_small_group_routing = False
    _, _, placements = _run_tick(nodes, svcs, tasks, planner)
    assert all(placements.values())
    assert not calls
    assert planner.stats.get("groups_strategy_host", 0) == 1


# ------------------------------------------------- node.ip device column

def _ip_nodes(n):
    # half the nodes in 10.0/16, half in 10.1/16, one unparsable addr
    def addr(i):
        if i == n - 1:
            return "not-an-ip"
        return f"10.{i % 2}.0.{i + 1}"
    return _mk_nodes(n, addr=addr)


@pytest.mark.parametrize("streaming", [True, False],
                         ids=["streaming1", "streaming0"])
@pytest.mark.parametrize("expr,expect_subset", [
    (["node.ip==10.0.0.0/16"], lambda a: a.startswith("10.0.")),
    (["node.ip!=10.0.0.0/16"], lambda a: not a.startswith("10.0.")),
    (["node.ip==10.0.0.3"], lambda a: a == "10.0.0.3"),
])
def test_node_ip_constraints_on_device(expr, expect_subset, streaming,
                                       frozen_clock):
    """node.ip exact + CIDR matching rides the hash/prefix column:
    device-planned (no fallback), host-parity placements, and the
    unparsable-addr node behaves like the host's None-ip (== rejects,
    != accepts... except it has no valid addr string to accept on)."""
    nodes = _ip_nodes(9)
    svcs, tasks = _mk_workload(
        [("svc0", 6, _strategy_spec("", constraints=expr))])
    _, _, host = _run_tick([n.copy() for n in nodes], svcs,
                           [t.copy() for t in tasks], None)
    planner = _device_planner(streaming)
    _, _, dev = _run_tick([n.copy() for n in nodes], svcs,
                          [t.copy() for t in tasks], planner)
    # spread tie ORDER between equal nodes is a documented waiver
    # (matching the existing host-vs-device spread differentials):
    # compare the per-node count distribution, not the task mapping
    def dist(p):
        counts = {}
        for nid in p.values():
            if nid:
                counts[nid] = counts.get(nid, 0) + 1
        return sorted(counts.values())
    assert dist(host) == dist(dev)
    assert planner.stats.get("groups_fallback", 0) == 0
    assert planner.stats.get("groups_planned", 0) == 1
    addr_of = {n.id: n.status.addr for n in nodes}
    for p in (dev, host):
        for tid, nid in p.items():
            if nid:
                assert expect_subset(addr_of[nid]), (tid, addr_of[nid])
        assert any(nid for nid in p.values())


def test_node_ip_malformed_rejects_everywhere(frozen_clock):
    """A malformed node.ip expression rejects every node on BOTH paths
    (host _match_ip returns False; device rides the sentinel row)."""
    nodes = _ip_nodes(5)
    svcs, tasks = _mk_workload(
        [("svc0", 3, _strategy_spec("", constraints=[
            "node.ip==10.0.0.0/99"]))])
    planner = _device_planner()
    _, _, dev = _run_tick([n.copy() for n in nodes], svcs,
                          [t.copy() for t in tasks], planner)
    _, _, host = _run_tick([n.copy() for n in nodes], svcs,
                           [t.copy() for t in tasks], None)
    assert host == dev
    assert not any(nid for nid in dev.values())
    assert planner.stats.get("groups_fallback", 0) == 0


def test_node_ip_prefix_key_is_not_node_ip(frozen_clock):
    """Review regression: a key merely STARTING with "node.ip"
    (node.iptables) is an UNKNOWN key — the host rejects every node,
    and the device column must encode the same never-match, not hash
    node addresses."""
    nodes = _ip_nodes(5)
    svcs, tasks = _mk_workload(
        [("svc0", 3, _strategy_spec("", constraints=[
            "node.iptables==10.0.0.2"]))])
    planner = _device_planner()
    _, _, dev = _run_tick([n.copy() for n in nodes], svcs,
                          [t.copy() for t in tasks], planner)
    _, _, host = _run_tick([n.copy() for n in nodes], svcs,
                           [t.copy() for t in tasks], None)
    assert not any(nid for nid in dev.values())
    assert not any(nid for nid in host.values())


def test_weights_of_partial_dict_keeps_omitted_terms():
    """Review regression: a partial strategy_weights dict must leave
    omitted terms at the all-ones default — zeroing them silently
    disabled the spread term."""
    t = Task(id="t", service_id="s",
             spec=TaskSpec(placement=Placement(
                 strategy="weighted", strategy_weights={"cpu": 3})))
    assert list(strategy_mod.weights_of(t)) == [1, 3, 1, 1]
    t.spec.placement.strategy_weights = {"spread": 0, "mem": 99}
    assert list(strategy_mod.weights_of(t)) == [
        0, 1, strategy_mod.W_CLAMP, 1]
    t.spec.placement.strategy_weights = {}
    assert list(strategy_mod.weights_of(t)) == [1, 1, 1, 1]


def test_ip_column_spec_forms():
    from swarmkit_tpu.scheduler.constraint import (
        Constraint, EQ, ip_column_spec, ip_node_value,
    )
    key, exp = ip_column_spec(Constraint("node.ip", EQ, "10.1.2.3"))
    assert (key, exp) == ("node.ip", "10.1.2.3")
    key, exp = ip_column_spec(Constraint("node.ip", EQ, "10.1.2.3/24"))
    assert (key, exp) == ("node.ip/24", "10.1.2.0/24")
    assert ip_column_spec(Constraint("node.ip", EQ, "nope")) is None
    assert ip_node_value("10.1.2.9", "node.ip/24") == "10.1.2.0/24"
    assert ip_node_value("10.1.2.9", "node.ip") == "10.1.2.9"
    assert ip_node_value("", "node.ip/24") == ""
    assert ip_node_value("garbage", "node.ip") == ""
    # family mismatch: canonical forms can never collide
    assert ip_node_value("fe80::1", "node.ip/16") != "10.1.0.0/16"


# ---------------------------------------------------- learned artifact

def test_learned_params_load_and_validate(tmp_path):
    w1, b1, w2, b2 = strategy_mod.learned_params()
    f = len(strategy_mod.MLP_FEATURES)
    assert w1.shape[0] == f and w1.shape[1] == len(b1) == len(w2)
    assert np.abs(w1).max() <= strategy_mod.MLP_W_CLAMP

    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"format": "other"}))
    with pytest.raises(ValueError):
        strategy_mod.learned_params(str(bad))
    doc = {"format": "swarm-learned-scorer-v1",
           "features": list(strategy_mod.MLP_FEATURES),
           "hidden": 4, "shift": strategy_mod.MLP_SHIFT,
           "w1": [[1] * 4] * (f - 1),   # wrong row count
           "b1": [0] * 4, "w2": [1] * 4, "b2": 0}
    bad.write_text(json.dumps(doc))
    with pytest.raises(ValueError):
        strategy_mod.learned_params(str(bad))
    with pytest.raises(FileNotFoundError):
        strategy_mod.learned_params(str(tmp_path / "missing.json"))


def test_trainer_reproduces_artifact(tmp_path):
    """The committed artifact is exactly what the seeded trainer
    writes — weights are provenance-pinned, not hand-edited."""
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts"))
    import train_scorer
    out = tmp_path / "artifact.json"
    train_scorer.main(["--out", str(out)])
    committed = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "swarmkit_tpu", "scheduler", "learned_scorer.json")
    assert json.loads(out.read_text()) == json.loads(
        open(committed).read())


# ------------------------------------------------- controlapi validation

def test_controlapi_validates_strategy_fields():
    from swarmkit_tpu.manager.controlapi import (
        InvalidArgument, validate_service_spec,
    )
    from swarmkit_tpu.models.specs import ContainerSpec

    def spec(strategy="", weights=None):
        return ServiceSpec(
            annotations=Annotations(name="svc"),
            mode=ServiceMode.REPLICATED,
            replicated=ReplicatedService(replicas=1),
            task=TaskSpec(container=ContainerSpec(image="img"),
                          placement=Placement(
                              strategy=strategy,
                              strategy_weights=weights or {})))

    validate_service_spec(spec())
    validate_service_spec(spec("binpack"))
    validate_service_spec(spec("weighted", {"cpu": 3, "spread": 1}))
    with pytest.raises(InvalidArgument):
        validate_service_spec(spec("zebra"))
    with pytest.raises(InvalidArgument):
        validate_service_spec(spec("weighted", {"disk": 1}))
    with pytest.raises(InvalidArgument):
        validate_service_spec(spec("weighted", {"cpu": 99}))
    with pytest.raises(InvalidArgument):
        validate_service_spec(spec("weighted", {"cpu": -1}))
    with pytest.raises(InvalidArgument):
        validate_service_spec(spec("weighted", {"cpu": True}))


def test_placement_spec_roundtrips_serde():
    from swarmkit_tpu.state import serde
    p = Placement(strategy="weighted", strategy_weights={"cpu": 3})
    back = serde.from_dict(Placement, serde.to_dict(p))
    assert back.strategy == "weighted"
    assert back.strategy_weights == {"cpu": 3}
    # forward compatibility: old records without the fields decode
    old = serde.to_dict(p)
    del old["strategy"], old["strategy_weights"]
    back = serde.from_dict(Placement, old)
    assert back.strategy == "" and back.strategy_weights == {}


# ------------------------------------------------ seam identity (sim)

SEAM_ENV = "SWARM_DEFAULT_PLACEMENT_STRATEGY"


def _scenario_fingerprint(seed):
    from swarmkit_tpu.sim.scenario import run_scenario
    r = run_scenario("steady-state-churn", seed)
    assert r.ok, r.violations
    return (r.events, r.trace_hash, r.obs_trace_sha256)


def test_seam_identity_one_seed():
    """Fast twin: the steady-state-churn scenario behaves byte-
    identically with every spec explicitly stamped "spread" vs the
    unset default — the seam's resolve/dispatch path adds nothing."""
    _scenario_fingerprint(7)   # warm the jit signatures (compile spans
    #                            are recorded; cold vs warm runs differ)
    base = _scenario_fingerprint(7)
    os.environ[SEAM_ENV] = "spread"
    try:
        stamped = _scenario_fingerprint(7)
    finally:
        del os.environ[SEAM_ENV]
    assert base == stamped


@pytest.mark.slow
def test_seam_identity_seed_sweep():
    """Slow tier: 20-seed twin sweep of the seam-identity differential.
    Each seed warms its own jit signatures first (a seed's cluster
    shape can mint a fresh bucket, whose compile span would land in
    whichever twin ran first)."""
    for seed in range(20):
        _scenario_fingerprint(seed)              # per-seed warm-up
        base = _scenario_fingerprint(seed)
        os.environ[SEAM_ENV] = "spread"
        try:
            stamped = _scenario_fingerprint(seed)
        finally:
            del os.environ[SEAM_ENV]
        assert base == stamped, f"seed {seed} diverged through the seam"


@pytest.mark.slow
def test_seam_identity_hashseed_independent():
    """Byte-identical across PYTHONHASHSEED with the seam stamp on."""
    code = ("from swarmkit_tpu.sim.scenario import run_scenario;"
            "r = run_scenario('steady-state-churn', 0);"
            "print(r.events, r.trace_hash, r.obs_trace_sha256)")
    outs = []
    for hs in ("1", "77"):
        env = dict(os.environ, PYTHONHASHSEED=hs, JAX_PLATFORMS="cpu")
        env[SEAM_ENV] = "spread"
        out = subprocess.run(
            [sys.executable, "-c", code], env=env, cwd=os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))),
            capture_output=True, text=True, check=True)
        outs.append(out.stdout)
    assert outs[0] == outs[1]
