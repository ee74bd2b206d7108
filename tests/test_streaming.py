"""Streaming scheduler (ISSUE 14): device-resident node state +
dirty-row incremental ticks.

The contract is the byte-identity discipline every planner path in this
repo holds: with the streaming plane on, placements, store snapshot
state and the watch-event stream must be identical to the forced
full-replan path (``SWARM_STREAMING_PLANNER=0``) for the same churn —
the refresh only changes HOW the device inputs are maintained, never
what they contain.  Every row of the fallback matrix (cold, epoch
resync, node remove, overflow/divergence) demotes to the counted full
rebuild; the sim's ``steady-state-churn`` twin-store differential
proves the whole plane live, and its checker-sensitivity twin proves a
corrupted resident row cannot hide.
"""

import json
import os
import random
import subprocess
import sys

import numpy as np
import pytest

from swarmkit_tpu.models import (
    Annotations, Node, NodeAvailability, NodeDescription, NodeSpec,
    NodeState, NodeStatus, Placement, PlacementPreference,
    ReplicatedService, Resources, ResourceRequirements, Service,
    ServiceMode, ServiceSpec, SpreadOver, Task, TaskSpec, TaskState,
    TaskStatus, Version,
)
from swarmkit_tpu.models import types as model_types
from swarmkit_tpu.ops import TPUPlanner
from swarmkit_tpu.ops.streaming import ResidentState
from swarmkit_tpu.scheduler import Scheduler
from swarmkit_tpu.scheduler.deltatrack import DeltaTracker
from swarmkit_tpu.scheduler.nodeinfo import NodeInfo
from swarmkit_tpu.scheduler.nodeset import NodeSet
from swarmkit_tpu.sim.scenario import run_scenario
from swarmkit_tpu.state import MemoryStore
from swarmkit_tpu.state.events import (
    Event, EventCommit, EventSnapshotRestore, EventTaskBlock,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "scripts"))

import chaos_sweep  # noqa: E402


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


def _churn_run(streaming: bool, fused: bool = True):
    """Multi-tick churn driven through the scheduler's real event feed:
    arrivals, exits/failures, an availability flip, a node join, a node
    leave — every streaming code path in one run."""
    store, specs, seqs = _build_store()
    planner = TPUPlanner()
    planner.enable_small_group_routing = False
    planner.fused_enabled = fused
    planner.streaming_enabled = streaming
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


# ------------------------------------------------------------- tracker

def test_delta_tracker_basics():
    tr = DeltaTracker()
    assert tr.full_reason == "cold"
    d, a, full = tr.drain()
    assert full == "cold" and not d and not a
    tr.mark("n1")
    tr.mark("n2")
    tr.mark("n1")
    tr.note_add("n3")
    assert tr.pending
    d, a, full = tr.drain()
    assert list(d) == ["n1", "n2"] and a == ["n3"] and full is None
    tr.note_remove("n1")
    tr.mark("n2")
    d, a, full = tr.drain()
    assert full == "node-remove" and list(d) == ["n2"]
    assert not tr.pending


def test_delta_tracker_add_overflow_collapses():
    tr = DeltaTracker()
    tr.drain()
    from swarmkit_tpu.scheduler import deltatrack
    for i in range(deltatrack.MAX_TRACKED_ADDS + 1):
        tr.note_add(f"n{i}")
    _, _, full = tr.drain()
    assert full == "add-overflow"


# --------------------------------------------------------- byte parity

@pytest.mark.parametrize("fused", [True, False])
def test_streaming_churn_byte_identical_to_full_replan(frozen_clock,
                                                       fused):
    """The whole plane: placements, final store state and the
    watch-event stream must be byte-identical between the streaming
    and forced full-replan paths across a churn of arrivals, exits,
    failures, availability flips, a node join and a node leave."""
    ds, ss, es, _sched_s, planner_s = _churn_run(True, fused=fused)
    df, sf, ef, _sched_f, planner_f = _churn_run(False, fused=fused)
    assert (ds, ss, es) == (df, sf, ef)
    snap = planner_s.streaming_snapshot()
    # incremental ticks actually happened (the differential is not
    # vacuous) and the forced-full side never built resident state
    assert snap["enabled"] and snap["incremental_ticks"] >= 3, snap
    assert snap["fallbacks"] >= 1, snap          # the node-remove tick
    assert not planner_f.streaming_snapshot()["enabled"]


def test_a_warm_churn_run_is_incremental_and_compiles_nothing(frozen_clock):
    """The resident tier's own signatures (the row scatter, the plan
    and fused programs it seeds) are the first run's to compile: the
    same six ticks of churn again run their incremental ticks with no
    growth of ``swarm_planner_compiles``."""
    from test_scheduler import cold_then_warm

    def churn():
        snap = _churn_run(True)[4].streaming_snapshot()
        assert snap["enabled"] and snap["incremental_ticks"] >= 3, snap
    cold_then_warm(churn)


def test_resident_columns_match_full_rebuild(frozen_clock):
    """Direct column equality: after churn, every resident host column
    equals a from-scratch ``_build_columns`` densify."""
    _ds, _ss, _es, sched, planner = _churn_run(True)
    st = planner._streaming
    assert st is not None
    cols = planner._build_columns(sched)
    infos, n, nb, valid, ready, cpu, mem, total = cols
    assert st.n == n and st.nb == nb
    assert [i.node.id for i in st.infos] == [i.node.id for i in infos]
    np.testing.assert_array_equal(st.valid, valid)
    np.testing.assert_array_equal(st.ready, ready)
    np.testing.assert_array_equal(st.cpu, cpu)
    np.testing.assert_array_equal(st.mem, mem)
    np.testing.assert_array_equal(st.total, total)
    # per-service columns vs the per-group loop's values
    for sid in ("sva", "svb", "svc"):
        want = np.zeros(nb, np.int32)
        for i, info in enumerate(infos):
            want[i] = info.active_tasks_count_by_service.get(sid, 0)
        np.testing.assert_array_equal(
            st.svc_tasks_col(sched, sid), want, err_msg=sid)
    # platform hashes vs the full pass (the resident tier builds them
    # lazily on first demand, then maintains rows)
    from swarmkit_tpu.ops import fusedbatch
    os_h, arch_h = fusedbatch.node_platform_hashes(infos, nb)
    ros, rarch = st.platform_hashes()
    np.testing.assert_array_equal(ros, os_h)
    np.testing.assert_array_equal(rarch, arch_h)


# ------------------------------------------------- the service index

def _walk_col(infos, nb, sid):
    """``svc_tasks_col``'s oracle: the walk of every ``NodeInfo`` that
    the tracker-less loops of ``_build_device_inputs`` and ``build_run``
    take."""
    want = np.zeros(nb, np.int32)
    for i, info in enumerate(infos):
        c = info.active_tasks_count_by_service.get(sid, 0)
        if c:
            want[i] = c
    return want


def _assert_index_is_the_walk(st, sched):
    """Every service's column from the index is the walk's, byte for
    byte (one never seen too), and the index then holds exactly the live
    (service, row) pairs: no stale pair, no emptied entry."""
    nodes = sched.node_set.nodes
    sids = {"never-seen"}
    for info in nodes.values():
        sids.update(info.active_tasks_count_by_service)
    for sid in sorted(sids):
        got = st.svc_tasks_col(sched, sid)
        # the absorb is the call's: only now is st.infos the mirror's
        assert [info.node.id for info in st.infos] == list(nodes)
        want = _walk_col(st.infos, st.nb, sid)
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes(), sid
    live = {(sid, i) for i, info in enumerate(st.infos)
            for sid, c in info.active_tasks_count_by_service.items() if c}
    held = {(sid, i) for sid, rows in st.svc_rows.items() for i in rows}
    assert held == live
    assert all(st.svc_rows.values()), "an emptied entry was kept"
    assert len(st.row_svcs) == st.n
    assert {(sid, i) for i, row in enumerate(st.row_svcs)
            for sid in row} == live


def _index_sched(n_nodes=24, resident=True):
    """(store, scheduler, event feed, resident tier) after a cold tick
    placed ``_build_store``'s three services; without ``resident`` the
    scheduler has no tracker and the planner walks (no tier: None)."""
    store, _specs, seqs = _build_store(n_nodes)
    planner = TPUPlanner()
    planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=1)
    _, sub = store.view_and_watch(
        lambda tx: sched._setup_tasks_list(tx), accepts_blocks=True)
    if not resident:
        sched.delta = None
    assert sched.tick() == sum(seqs.values())
    return store, sched, sub, planner._streaming


def _probe_task(sid, k=0):
    return Task(id=f"{sid}-x{k:04d}", service_id=sid, slot=1000 + k,
                desired_state=TaskState.RUNNING,
                spec=TaskSpec(resources=_RES),
                spec_version=Version(index=1),
                status=TaskStatus(state=TaskState.PENDING))


def _step_hook_add(store, sched, sub, st, rng):
    """Tasks of a placed service and of a new one land on some nodes
    through ``NodeInfo.add_task`` (its hook marks the row)."""
    infos = list(sched.node_set.nodes.values())
    for k in range(6):
        sid = rng.choice(["sva", "hooked", f"hooked{rng.randrange(3)}"])
        rng.choice(infos).add_task(_probe_task(sid, rng.randrange(10 ** 6)))


def _step_hook_remove(store, sched, sub, st, rng):
    """Some nodes lose a task through ``NodeInfo.remove_task``."""
    infos = [i for i in sched.node_set.nodes.values() if i.tasks]
    for info in rng.sample(infos, min(5, len(infos))):
        info.remove_task(rng.choice(sorted(info.tasks.values(),
                                           key=lambda t: t.id)))


def _step_apply_mid_tick(store, sched, sub, st, rng):
    """Two groups one after the other inside one tick: the second's
    column is built over the rows the first's apply marked (the batched
    mirror arithmetic of ``_apply_assignments``), and every service's
    is checked between the two and before the tick ends."""
    planner = sched.batch_planner
    for sid in ("svb", f"fresh{rng.randrange(10 ** 6)}"):
        store.update(lambda tx: [
            tx.create(_probe_task(sid, rng.randrange(10 ** 6)))
            for _k in range(rng.randrange(3, 9))])
    _pump(sched, sub)
    planner.begin_tick(sched)
    try:
        for group in list(sched.unassigned_groups.values()):
            decisions = {}
            assert planner.schedule_group(sched, group, decisions)
            assert len(decisions) == len(group)
            assert sched.delta.pending, "the apply marked no row"
            _assert_index_is_the_walk(st, sched)
    finally:
        planner.end_tick()
    sched.unassigned_groups.clear()


def _step_node_append(store, sched, sub, st, rng):
    """A node joins (an appended row, no rebuild) and takes tasks."""
    i = 1 + max(int(nid[1:]) for nid in sched.node_set.nodes)
    full = st.stats["full"]
    store.update(lambda tx: tx.create(_mk_node(i)))
    _pump(sched, sub)
    st.absorb(sched)
    assert st.stats["full"] == full and st.node_ids[-1] == f"n{i:04d}"
    info = sched.node_set.nodes[f"n{i:04d}"]
    info.add_task(_probe_task("sva", rng.randrange(10 ** 6)))
    info.add_task(_probe_task("joined", rng.randrange(10 ** 6)))


def _step_node_remove(store, sched, sub, st, rng):
    """A node that holds tasks leaves: later rows shift, a rebuild."""
    held = [i for i, row in enumerate(st.row_svcs[:-1]) if row]
    nid = st.node_ids[rng.choice(held)]
    full = st.stats["full"]
    store.update(lambda tx: tx.delete(Node, nid))
    _pump(sched, sub)
    st.absorb(sched)
    assert st.stats["full"] == full + 1 and nid not in st.row_of


def _step_scale_to_nought(store, sched, sub, st, rng):
    """Every task of one service goes: its entry goes with the last."""
    st.absorb(sched)
    sid = rng.choice(sorted(st.svc_rows))
    for info in sched.node_set.nodes.values():
        for t in [t for t in info.tasks.values() if t.service_id == sid]:
            info.remove_task(t)
    st.absorb(sched)
    assert sid not in st.svc_rows
    assert any(sid in info.active_tasks_count_by_service
               for info in sched.node_set.nodes.values()), \
        "NodeInfo keeps the zero count: the index must not"


_INDEX_STEPS = {
    "hook-add": _step_hook_add, "hook-remove": _step_hook_remove,
    "apply-mid-tick": _step_apply_mid_tick,
    "node-append": _step_node_append, "node-remove": _step_node_remove,
    "scale-to-nought": _step_scale_to_nought}


@pytest.mark.parametrize("step", sorted(_INDEX_STEPS))
def test_the_service_index_column_is_the_walks_after(frozen_clock, step):
    store, sched, sub, st = _index_sched()
    _assert_index_is_the_walk(st, sched)
    _INDEX_STEPS[step](store, sched, sub, st, random.Random(39))
    _assert_index_is_the_walk(st, sched)


@pytest.mark.parametrize("seed", [39, 3900, 390039])
def test_the_service_index_holds_through_a_seeded_sequence(frozen_clock,
                                                           seed):
    """Thirty steps drawn by ``seed``, every kind among them, the walk
    held against every service's column after each."""
    rng = random.Random(seed)
    store, sched, sub, st = _index_sched()
    names = sorted(_INDEX_STEPS)
    drawn = names + [rng.choice(names) for _ in range(30 - len(names))]
    rng.shuffle(drawn)
    for name in drawn:
        _INDEX_STEPS[name](store, sched, sub, st, rng)
        _assert_index_is_the_walk(st, sched)
    assert st.stats["full"] >= 1 + drawn.count("node-remove")


def _rebuild_cold(sched, st):
    st.infos = None


def _rebuild_epoch(sched, st):
    sched._tick_epoch = (sched._tick_epoch or 0) + 1
    sched.delta.mark(st.node_ids[0])


def _rebuild_no_tracker(sched, st):
    sched.delta = None


def _rebuild_tracker_swap(sched, st):
    sched.delta = DeltaTracker()


def _rebuild_divergence(sched, st):
    # a node in the mirror that no note_add announced
    node = _mk_node(999)
    sched.node_set.nodes[node.id] = NodeInfo(node)
    sched.delta.mark(st.node_ids[0])


def _rebuild_swapped_info(sched, st):
    # the row's NodeInfo replaced, not mutated: another object's counts
    nid = st.node_ids[3]
    sched.node_set.nodes[nid] = NodeInfo(sched.node_set.nodes[nid].node)
    sched.delta.mark(nid)


def _rebuild_demanded(reason):
    def demand(sched, st):
        sched.delta.require_full(reason)
    return demand


_REBUILDS = {
    "cold": _rebuild_cold, "epoch": _rebuild_epoch,
    "no-tracker": _rebuild_no_tracker,
    "tracker-swap": _rebuild_tracker_swap,
    "divergence": _rebuild_divergence,
    "swapped-info": _rebuild_swapped_info,
    "node-remove": _rebuild_demanded("node-remove"),
    "add-overflow": _rebuild_demanded("add-overflow"),
    "resync-store": _rebuild_demanded("resync-store")}


@pytest.mark.parametrize("reason", sorted(_REBUILDS))
def test_a_rebuild_lays_the_service_index_again(frozen_clock, reason):
    """A ``_rebuild`` by each reason, over a mirror that moved since the
    index was laid: the index is the new mirror's, nothing of the old."""
    store, sched, sub, st = _index_sched()
    rng = random.Random(39)
    _step_hook_add(store, sched, sub, st, rng)
    _assert_index_is_the_walk(st, sched)
    # moved behind the tier's back: only a rebuild can see these
    for info in list(sched.node_set.nodes.values())[::5]:
        hook, info.on_dirty = info.on_dirty, None
        info.add_task(_probe_task("unseen", rng.randrange(10 ** 6)))
        for t in [t for t in info.tasks.values() if t.service_id == "svc"]:
            info.remove_task(t)
        info.on_dirty = hook
    full = st.stats["full"]
    _REBUILDS[reason](sched, st)
    _assert_index_is_the_walk(st, sched)
    assert st.stats["full"] > full
    assert "unseen" in st.svc_rows


def test_the_node_bucket_overflows_into_a_rebuilt_index(frozen_clock):
    """The 1,025th node outgrows the 1,024 bucket: the columns and the
    index are laid again at 2,048."""
    store, sched, sub, st = _index_sched(n_nodes=1024)
    assert st.nb == 1024 and st.n == 1024
    full = st.stats["full"]
    store.update(lambda tx: tx.create(_mk_node(1024)))
    _pump(sched, sub)
    sched.node_set.nodes["n1024"].add_task(_probe_task("joined"))
    _assert_index_is_the_walk(st, sched)
    assert (st.nb, st.n, st.stats["full"]) == (2048, 1025, full + 1)
    assert list(st.svc_rows["joined"]) == [1024]


class _Mirror:
    """As much of a scheduler as the resident tier reads."""

    def __init__(self, n_nodes):
        self.delta = DeltaTracker()
        self.node_set = NodeSet()
        self.node_set.tracker = self.delta
        for i in range(n_nodes):
            self.node_set.add_or_update_node(NodeInfo(_mk_node(i)))


@pytest.mark.parametrize("n_nodes, nb", [(1000, 1024), (70000, 131072)])
def test_a_service_that_holds_nothing_reads_no_row(n_nodes, nb):
    """The first sight of a service costs the rows that hold it: none,
    at 1,000 nodes and at the 131,072 bucket alike; a placed service's
    costs its own rows and not the cluster's."""
    counted = {}

    def count(key, delta=1):
        counted[key] = counted.get(key, 0) + delta
    sched = _Mirror(n_nodes)
    st = ResidentState(lambda info, key: None, device=False, count=count)
    infos = list(sched.node_set.nodes.values())
    for info in infos[7::n_nodes // 9]:
        info.add_task(_probe_task("placed"))
    col = st.svc_tasks_col(sched, "fresh")
    assert col.shape == (nb,) and col.dtype == np.int32 and not col.any()
    assert counted == {"svc_cols_builds": 1}
    col = st.svc_tasks_col(sched, "placed")
    assert col.tobytes() == _walk_col(st.infos, nb, "placed").tobytes()
    assert counted == {"svc_cols_builds": 2, "svc_col_rows": int(col.sum())}
    assert 9 <= counted["svc_col_rows"] <= 10
    assert st.stats["full"] == 1 and set(st.svc_rows) == {"placed"}


def _second_wave(resident):
    """(scheduler in a tick, planner, the tick's groups): further tasks
    of ``_build_store``'s three placed services and a first group of a
    new one pending, on the resident tier or on the walk."""
    store, sched, sub, st = _index_sched(resident=resident)
    assert (st is not None) == resident
    store.update(lambda tx: [
        tx.create(_probe_task(sid, k))
        for sid in ("sva", "svb", "svc", "svd") for k in range(6)])
    _pump(sched, sub)
    planner = sched.batch_planner
    planner.begin_tick(sched)
    groups = list(sched.unassigned_groups.values())
    assert len(groups) == 4
    return sched, planner, groups


def test_build_device_inputs_svc_tasks_with_the_tier_and_without(
        frozen_clock):
    """``_build_device_inputs``' ``svc_tasks`` from the index and from
    its tracker-less loop: the same bytes for a placed service and for
    a new one, the second group's over the first's applied rows."""
    (sched_r, planner_r, groups_r), (sched_w, planner_w, groups_w) = \
        _second_wave(True), _second_wave(False)
    seen = 0
    for group_r, group_w in zip(groups_r, groups_w):
        t_r, t_w = (next(iter(g.values())) for g in (group_r, group_w))
        assert t_r.id == t_w.id
        got = planner_r._build_device_inputs(sched_r, t_r, len(group_r))
        want = planner_w._build_device_inputs(sched_w, t_w, len(group_w))
        assert got[7].svc_tasks.tobytes() == want[7].svc_tasks.tobytes()
        seen += bool(got[7].svc_tasks.any())
        for sched, planner, group in ((sched_r, planner_r, group_r),
                                     (sched_w, planner_w, group_w)):
            decisions = {}
            assert planner.schedule_group(sched, group, decisions)
            assert len(decisions) == len(group)
    assert seen == 3    # sva, svb, svc hold tasks; svd is a first sight
    assert planner_w.stats["svc_cols_builds"] == 0
    assert planner_r.stats["svc_col_rows"] > 0


def test_build_run_svc0_with_the_tier_and_without(frozen_clock):
    """``build_run``'s ``svc0`` from the index, a slot a service, and
    from its tracker-less loop: the same bytes."""
    from swarmkit_tpu.ops import fusedbatch
    runs = []
    for resident in (True, False):
        sched, planner, groups = _second_wave(resident)
        specs = planner.probe_fused_run(sched, groups, 0)
        assert len(specs) == 4
        before = planner.stats["svc_cols_builds"]
        runs.append((fusedbatch.build_run(planner, sched, specs),
                     planner.stats["svc_cols_builds"] - before))
    (got, builds_r), (want, builds_w) = runs
    assert got.shared.svc0.shape == want.shared.svc0.shape == (4, 1024)
    assert got.shared.svc0.tobytes() == want.shared.svc0.tobytes()
    assert got.shared.svc0[:3].any(axis=1).all()
    assert not got.shared.svc0[3].any()
    assert (builds_r, builds_w) == (4, 0)


# ------------------------------------------- resident spread-tree twin

_TREE_LABELS = ("zone", "rack", "shelf")


def _tree_node(i, rack):
    """Node ``i`` in rack ``rack``; the rack decides the zone, every
    rack has the one shelf: 15 racks give 15 ids at levels 1 and 2."""
    node = _mk_node(i)
    node.spec.annotations.labels = {
        "zone": f"z{rack % 2}", "rack": f"r{rack:02d}", "shelf": "s0"}
    return node


def _tree_spec(depth):
    return TaskSpec(resources=_RES, placement=Placement(preferences=[
        PlacementPreference(spread=SpreadOver(
            spread_descriptor=f"node.labels.{label}"))
        for label in _TREE_LABELS[:depth]]))


def _tree_sched(depth, n_nodes=30, services=(("tree", 7),)):
    """(store, scheduler on the resident tier, event feed) over
    ``n_nodes`` nodes dealt two to a rack, with ``services`` of the
    ``depth``-level spread shape pending."""
    store = MemoryStore()

    def fill(tx):
        for i in range(n_nodes):
            tx.create(_tree_node(i, i % 15))
        for sid, k in services:
            svc, tasks = _mk_service(sid, k, _tree_spec(depth))
            tx.create(svc)
            for t in tasks:
                tx.create(t)
    store.update(fill)
    planner = TPUPlanner()
    planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=1)
    _, sub = store.view_and_watch(
        lambda tx: sched._setup_tasks_list(tx), accepts_blocks=True)
    return store, sched, sub


def _move_to_rack(store, node_id, rack):
    def move(tx):
        node = tx.get(Node, node_id).copy()
        node.spec.annotations.labels = dict(
            node.spec.annotations.labels,
            rack=f"r{rack:02d}", zone=f"z{rack % 2}")
        tx.update(node)
    store.update(move)


def _tree_inputs_of(planner, sched, depth):
    """(leaf, L, hier) as ``_build_device_inputs`` hands them to the
    kernel for a group of the ``depth``-level shape."""
    t = Task(id="probe", service_id="tree", spec=_tree_spec(depth))
    built = planner._build_device_inputs(sched, t, 1)
    return built[7].leaf, built[9], built[10]


def _assert_same_tree(got, want, depth):
    """Every seg array, parent array, leaf_parent and width equal,
    dtypes too: the kernel's inputs byte for byte."""
    (leaf_g, L_g, (upper_g, lp_g)) = got
    (leaf_w, L_w, (upper_w, lp_w)) = want
    assert L_g == L_w and len(upper_g) == len(upper_w) == depth - 1
    pairs = [(leaf_g, leaf_w), (lp_g, lp_w)]
    for (seg_g, par_g), (seg_w, par_w) in zip(upper_g, upper_w):
        pairs += [(seg_g, seg_w), (par_g, par_w)]
    for g, w in pairs:
        assert g.dtype == w.dtype == np.int32
        np.testing.assert_array_equal(g, w)     # shape (each L_d) too
    assert lp_g.shape == (L_g,)


@pytest.mark.parametrize("depth", [2, 3])
def test_resident_tree_columns_match_the_walk(frozen_clock, depth):
    """The resident twin of a multi-level spread tree against the
    per-group walk (a planner off the resident tier, on the same
    mirror), through every way the columns are kept."""
    store, sched, sub = _tree_sched(depth)
    planner = sched.batch_planner
    walk = TPUPlanner()
    walk.streaming_enabled = False
    stats = planner.stats

    def same(builds, hits, invalidations):
        _pump(sched, sub)
        got = _tree_inputs_of(planner, sched, depth)
        assert walk._resident_for(walk._densify(sched, None)) is None
        _assert_same_tree(got, _tree_inputs_of(walk, sched, depth), depth)
        assert (stats["tree_cols_builds"], stats["tree_cols_hits"],
                stats["tree_cols_invalidations"]) \
            == (builds, hits, invalidations), stats
        return got

    # (a) a cold build
    assert same(1, 0, 0)[1] == 16
    assert planner._resident_for(planner._densify(sched, None)) is not None
    # (b) nodes appended: into a rack that is there, opening a 16th
    # rack, opening a 17th: every level with 17 ids crosses its bucket's
    # edge and the parent arrays come at the new width, with no walk
    store.update(lambda tx: [tx.create(_tree_node(30, 4)),
                             tx.create(_tree_node(31, 15))])
    leaf, L, (upper, leaf_parent) = same(1, 1, 0)
    # a 16th leaf: under zone z1 (id 1), or under the 16th rack
    assert L == 16 and leaf[31] == 15
    assert leaf_parent[15] == (1 if depth == 2 else 15)
    store.update(lambda tx: tx.create(_tree_node(32, 16)))
    leaf, L, (upper, leaf_parent) = same(1, 2, 0)
    assert L == 256 and leaf[32] == 16
    assert [len(parent) for _seg, parent in upper] == [16, 256][:depth - 1]
    assert planner._streaming.stats["full"] == 1      # appended, not rebuilt

    # (c) a node changes rack: other rows' ids may move, the entry goes
    # and the next group walks again
    _move_to_rack(store, "n0000", 9)
    leaf, _L, _hier = same(2, 2, 1)
    assert leaf[0] == 0 and leaf[9] == 0 and leaf[1] == 1   # r00 went
    # (d) a node removed: the whole resident state is rebuilt, the tree
    # with it (no invalidation: nothing was held)
    store.update(lambda tx: tx.delete(Node, "n0005"))
    same(3, 2, 1)
    assert planner._streaming.stats["full"] == 2
    # (e) dirty rows whose labels did not change: a node drained, a
    # reservation taken behind a mark; no walk
    def drain(tx):
        node = tx.get(Node, "n0002").copy()
        node.spec.availability = NodeAvailability.DRAIN
        tx.update(node)
    store.update(drain)
    sched.node_set.nodes["n0007"].available_resources.nano_cpus -= 5
    sched.delta.mark("n0007")
    same(3, 3, 1)
    same(3, 4, 1)


def test_resident_tree_invalidation_is_what_keeps_it_true(frozen_clock,
                                                          monkeypatch):
    """The differential above fails on a tree that a label change does
    not drop: with the invalidation taken out the resident columns keep
    the old numbering and differ from the walk's."""
    from swarmkit_tpu.ops import streaming
    store, sched, sub = _tree_sched(2)
    planner = sched.batch_planner
    walk = TPUPlanner()
    walk.streaming_enabled = False
    _tree_inputs_of(planner, sched, 2)
    monkeypatch.setattr(streaming.ResidentState, "_recompute_tree_row",
                        lambda self, descriptors, i, info, append: None)
    _move_to_rack(store, "n0000", 9)
    _pump(sched, sub)
    with pytest.raises(AssertionError):
        _assert_same_tree(_tree_inputs_of(planner, sched, 2),
                          _tree_inputs_of(walk, sched, 2), 2)


def test_resident_tree_cache_is_bounded(frozen_clock):
    """``LEAF_CACHE_CAP``'s discipline: the oldest-built tree goes."""
    from swarmkit_tpu.ops import streaming
    _store, sched, _sub = _tree_sched(2)
    planner = sched.batch_planner
    planner._densify(sched, None)
    st = planner._streaming
    keys = [(f"node.labels.a{i}", "node.labels.rack")
            for i in range(streaming.LEAF_CACHE_CAP + 1)]
    for key in keys:
        st.spread_tree(sched, key)
    assert list(st.tree_cols) == keys[1:]
    assert planner.stats["tree_cols_builds"] == len(keys)


# ---- a wide tree's leaf layout (more than 256 leaves: kernel.LeafLayout)

def _wide_tree_sched(n_nodes=599, racks=300):
    """``_tree_sched`` over ``racks`` racks, nodes dealt round-robin:
    two to a rack but the last, which has one."""
    store = MemoryStore()

    def fill(tx):
        for i in range(n_nodes):
            tx.create(_tree_node(i, i % racks))
        svc, tasks = _mk_service("tree", 7, _tree_spec(2))
        tx.create(svc)
        for t in tasks:
            tx.create(t)
    store.update(fill)
    planner = TPUPlanner()
    planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=1)
    _, sub = store.view_and_watch(
        lambda tx: sched._setup_tasks_list(tx), accepts_blocks=True)
    return store, sched, sub


def _assert_layout(layout, leaf, n, L):
    """Unique slots inside [0, L * W), each in its leaf's row of the
    layout and ranked in row order; ``W`` the power of two at or above
    the fullest leaf; no slot on the bucket's padding rows."""
    from swarmkit_tpu.ops import fusedbatch
    slot, W = layout.slot, layout.W
    assert slot.dtype == np.int32 and slot.shape == leaf.shape
    pop = np.bincount(leaf[:n])
    assert W == fusedbatch.pow2_bucket(int(pop.max()))
    assert len(set(slot[:n].tolist())) == n
    assert slot[:n].min() >= 0 and slot[:n].max() < L * W
    assert (slot[:n] // W == leaf[:n]).all()
    for lf in range(len(pop)):
        ranks = slot[:n][leaf[:n] == lf] % W
        assert ranks.tolist() == list(range(pop[lf]))     # row order
    assert (slot[n:] == L * W).all()
    assert layout.nbytes == slot.nbytes


def test_a_wide_trees_leaf_layout_is_kept_with_its_columns(frozen_clock):
    """The resident tier's layout against the walk's, through a build,
    an appended node that takes its leaf's next rank in place, and one
    that outgrows ``W``: laid again at twice the width and counted."""
    store, sched, sub = _wide_tree_sched()
    planner = sched.batch_planner
    walk = TPUPlanner()
    walk.streaming_enabled = False
    stats = planner.stats

    def same(n, builds, hits, invalidations):
        _pump(sched, sub)
        leaf, L, hier = _tree_inputs_of(planner, sched, 2)
        w_leaf, w_L, w_hier = _tree_inputs_of(walk, sched, 2)
        assert L == w_L == 4096 and len(hier) == len(w_hier) == 3
        _assert_same_tree((leaf, L, hier[:2]), (w_leaf, w_L, w_hier[:2]), 2)
        _assert_layout(hier[2], leaf, n, L)
        assert hier[2].W == w_hier[2].W
        np.testing.assert_array_equal(hier[2].slot, w_hier[2].slot)
        assert (stats["tree_cols_builds"], stats["tree_cols_hits"],
                stats["tree_cols_invalidations"]) \
            == (builds, hits, invalidations), stats
        return leaf, hier[2]

    leaf, layout = same(599, 1, 0, 0)
    assert layout.W == 2 and leaf[598] == 298 and leaf[299] == 299
    # rack 299 has one node: the next one takes rank 1 there, in place
    store.update(lambda tx: tx.create(_tree_node(599, 299)))
    leaf, grown = same(600, 1, 1, 0)
    assert grown is layout and grown.slot[599] == 299 * 2 + 1
    # a third node in rack 5: one more than its two slots
    store.update(lambda tx: tx.create(_tree_node(600, 5)))
    leaf, wider = same(601, 1, 2, 1)
    assert wider is not layout and wider.W == 4
    assert wider.slot[600] == 5 * 4 + 2
    assert planner._streaming.stats["full"] == 1      # appended, not rebuilt
    # a rack that is new: the tree's inputs come again, the layout too
    store.update(lambda tx: tx.create(_tree_node(601, 300)))
    leaf, again = same(602, 1, 3, 1)
    assert again is not wider and again.W == 4 and leaf[601] == 300


@pytest.mark.parametrize("racks,wide", [(256, False), (257, True)])
def test_only_a_tree_past_the_mask_forms_bound_gets_a_layout(racks, wide):
    from swarmkit_tpu.ops import fusedbatch, kernel
    assert kernel.MASK_FORM_MAX_L == 256
    n, nb = 3 * racks, 1024
    rack = np.zeros(nb, np.int32)
    rack[:n] = np.arange(n) % racks
    rack[:5] = 0                                  # the fullest leaf: 7
    segs = [np.where(np.arange(nb) < n, rack % 4, 0).astype(np.int32), rack]
    level_ids = [{(z,): z for z in range(4)},
                 {(r % 4, r): r for r in range(racks)}]
    leaf, L, hier = fusedbatch.tree_inputs(segs, level_ids, n)
    assert L == fusedbatch.l_bucket(racks) == (4096 if wide else 256)
    assert len(hier) == (3 if wide else 2)
    if wide:
        assert hier[2].W == 8
        _assert_layout(hier[2], leaf, n, L)


def test_two_level_group_places_the_same_on_the_resident_tier_and_the_walk(
        frozen_clock):
    """``schedule_group`` on the resident tier and on a tracker-less
    scheduler (the walk): two two-level groups one after the other, the
    second over the first's dirty rows, the same tasks on every node."""
    def counts(resident):
        _store, sched, _sub = _tree_sched(
            2, services=(("tree", 37), ("tree2", 11)))
        planner = sched.batch_planner
        if not resident:
            sched.delta = None
        per_node = {}
        for group in list(sched.unassigned_groups.values()):
            decisions, k = {}, len(group)
            assert planner.schedule_group(sched, group, decisions)
            assert len(decisions) == k
            for d in decisions.values():
                key = (d.new.service_id, d.new.node_id)
                per_node[key] = per_node.get(key, 0) + 1
        return per_node, planner
    got, planner = counts(True)
    want, walker = counts(False)
    assert got == want and sum(got.values()) == 48
    assert planner.stats["tree_cols_builds"] == 1
    assert planner.stats["tree_cols_hits"] == 1
    assert walker._streaming is None
    assert walker.stats["tree_cols_builds"] == 0


def test_epoch_change_forces_resync(frozen_clock):
    """A tick under a different leadership epoch must rebuild the
    resident state (successor-reign discipline) and count a resync."""
    store, _specs, _seqs = _build_store(n_nodes=8)
    planner = TPUPlanner()
    planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=1)
    store.view(sched._setup_tasks_list)
    sched._tick_epoch = 3
    planner.begin_tick(sched)
    planner.end_tick()
    st = planner._streaming
    assert st.stats["resyncs"] == 0
    sched._tick_epoch = 3
    planner.begin_tick(sched)
    planner.end_tick()
    assert st.stats["incremental"] >= 1
    sched._tick_epoch = 4          # the reign changed
    planner.begin_tick(sched)
    planner.end_tick()
    assert st.stats["resyncs"] == 1, st.stats


def test_streaming_env_hatch(monkeypatch):
    monkeypatch.setenv("SWARM_STREAMING_PLANNER", "0")
    assert not TPUPlanner().streaming_enabled
    monkeypatch.delenv("SWARM_STREAMING_PLANNER")
    assert TPUPlanner().streaming_enabled


def test_device_carry_feeds_fused_run(frozen_clock):
    """With the resident device tier fresh, the fused run seeds its
    node-state columns from device (no H2D) — and places exactly what
    the host-seeded run places."""
    ds, ss, es, _sched, planner = _churn_run(True, fused=True)
    assert planner.stats.get("streaming_device_carries", 0) >= 1, \
        planner.stats
    assert planner.stats.get("groups_fused", 0) >= 2
    df, sf, ef, _sched_f, _planner_f = _churn_run(False, fused=True)
    assert (ds, ss, es) == (df, sf, ef)


def test_resident_device_columns_mirror_host(frozen_clock):
    """The donated-scatter device tier tracks the host mirror exactly
    at refresh points (between refreshes the host tier runs ahead and
    ``device_carry`` refuses to serve — asserted below)."""
    _ds, _ss, _es, sched, planner = _churn_run(True)
    st = planner._streaming
    # the last tick's applies marked rows after the final device sync:
    # the device tier must refuse to serve until the next refresh
    assert st._tracker.pending or st._tracker.version != st._dev_version
    assert st.device_carry() is None
    st.refresh(sched)
    assert st.device_carry() is not None
    assert st.dev is not None
    d_valid, d_ready, d_cpu, d_mem, d_total = [
        np.asarray(a) for a in st.dev]
    np.testing.assert_array_equal(d_valid, st.valid)
    np.testing.assert_array_equal(d_ready, st.ready)
    np.testing.assert_array_equal(d_cpu, st.cpu)
    np.testing.assert_array_equal(d_mem, st.mem)
    np.testing.assert_array_equal(d_total, st.total)
    assert st.stats["device_syncs"] >= 2


def test_device_backlog_from_host_only_absorbs(frozen_clock):
    """Review regression (PR 14): a HOST-ONLY absorb (the mid-tick
    accessor path — group A's apply marks drained by group B's column
    build) updates host rows the device tier has not seen.  The next
    refresh must scatter that backlog — not stamp the device tier
    fresh while silently missing those rows' reservation deductions."""
    store, _specs, _seqs = _build_store(n_nodes=8)
    planner = TPUPlanner()
    planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=1)
    store.view(sched._setup_tasks_list)
    planner.begin_tick(sched)
    planner.end_tick()
    st = planner._streaming
    assert st.device_carry() is not None
    # mid-tick-style mutation: mirror changes + mark, then a HOST-ONLY
    # absorb (what svc_tasks_col does between groups)
    info = sched.node_set.nodes["n0000"]
    info.available_resources.nano_cpus -= 12345
    sched.delta.mark("n0000")
    st.absorb(sched)
    assert st.cpu[0] == info.available_resources.nano_cpus
    assert st._pending_dev_rows, "host-only drain left no device backlog"
    # stale device must refuse to serve until synced
    assert st.device_carry() is None
    st.refresh(sched)
    assert not st._pending_dev_rows
    assert st.device_carry() is not None
    assert int(np.asarray(st.dev[2])[0]) == int(st.cpu[0]), \
        "refresh stamped the device tier fresh without the backlog rows"


# ------------------------------------------------------ sim differential

def test_steady_state_churn_scenario():
    """The twin-store differential: streaming placements must equal
    full-replan placements per seed under Poisson churn, membership
    churn and a leader stepdown (which must resync resident state)."""
    r = run_scenario("steady-state-churn", seed=7, keep_trace=True)
    assert r.ok, r.violations
    assert any("streaming-resync scheduler" in line for line in r.trace)


def test_steady_state_churn_detects_corrupt_resident_row(monkeypatch):
    """Checker sensitivity: perturbing a resident row WITHOUT marking
    it dirty must diverge placements, and the
    incremental-equals-full-replan differential must catch it — a
    comparison that can't fire is a no-op."""
    orig = ResidentState.refresh

    def corrupt(self, sched):
        cols = orig(self, sched)
        if self.n:
            self.cpu[: max(1, self.n // 2)] = 0
        return cols

    monkeypatch.setattr(ResidentState, "refresh", corrupt)
    r = run_scenario("steady-state-churn", seed=7)
    assert any("incremental-equals-full-replan" in v and "diverged" in v
               for v in r.violations), r.violations


def test_chaos_sweep_requires_streaming_resync_cell():
    """The sweep's coverage gate carries the streaming-resync x
    scheduler cell for the new scenario, and a trace without it is
    reported uncovered."""
    cells = chaos_sweep.required_cells(("steady-state-churn",))
    assert ("streaming-resync", "scheduler") in cells
    assert chaos_sweep.classify("streaming-resync", "") == "scheduler"
    matrix = chaos_sweep.coverage_matrix(
        [["0.000001 fault stepdown m0"]])
    assert chaos_sweep.uncovered(matrix, cells)
    matrix = chaos_sweep.coverage_matrix(
        [["0.000001 fault streaming-resync scheduler",
          "0.000002 fault stepdown m0"]])
    assert ("streaming-resync", "scheduler") not in \
        chaos_sweep.uncovered(matrix, cells)


# -------------------------------------------- satellite: per-service p99

def test_per_service_lifecycle_timer_and_autoscaler_signal():
    from swarmkit_tpu.obs.lifecycle import (
        SERVICE_TIMER_CAP, LifecycleTracker, service_edge_timer_name,
    )
    from swarmkit_tpu.orchestrator.autoscaler import registry_sampler
    from swarmkit_tpu.utils.metrics import Registry

    reg = Registry()
    lt = LifecycleTracker(registry=reg)

    def observe(sid, tid, dt):
        t0 = Task(id=tid, service_id=sid, spec=TaskSpec(),
                  status=TaskStatus(state=TaskState.PENDING,
                                    timestamp=100.0))
        lt.observe_task(t0)
        t1 = Task(id=tid, service_id=sid, spec=TaskSpec(),
                  status=TaskStatus(state=TaskState.ASSIGNED,
                                    timestamp=100.0 + dt))
        lt.observe_task(t1)

    for k in range(8):
        observe("slow-svc", f"s{k}", 4.0)
        observe("fast-svc", f"f{k}", 0.01)
    slow_t = reg.get_timer(service_edge_timer_name("slow-svc"))
    fast_t = reg.get_timer(service_edge_timer_name("fast-svc"))
    assert slow_t.count == 8 and fast_t.count == 8
    # the global edge timer still aggregates everything
    glob = reg.get_timer(
        'swarm_task_lifecycle{from="pending",to="assigned"}')
    assert glob.count == 16

    # the autoscaler's target_p99 reads the service's OWN signal — a
    # fast service next to a slow neighbor must not see 4s latencies
    sample = registry_sampler(reg)
    assert sample("slow-svc")["p99"] == pytest.approx(4.0)
    assert sample("fast-svc")["p99"] == pytest.approx(0.01)
    # unknown service falls back to the global aggregate
    assert sample("other-svc")["p99"] == pytest.approx(4.0)

    # bounded cardinality: beyond the cap no new per-service timer
    # appears, the overflow counter ticks, the global edge still counts
    for k in range(SERVICE_TIMER_CAP + 4):
        observe(f"many-{k}", f"m{k}", 0.1)
    assert reg.get_counter(
        "swarm_task_lifecycle_service_overflow") >= 1
    n_svc_timers = sum(
        1 for name in reg.timers
        if name.startswith("swarm_task_lifecycle_service{"))
    assert n_svc_timers <= SERVICE_TIMER_CAP


def test_block_commit_feeds_per_service_timer(frozen_clock):
    """The columnar commit path (EventTaskBlock) carries service ids
    through to the per-service timer."""
    from swarmkit_tpu.obs.lifecycle import (
        LifecycleTracker, service_edge_timer_name,
    )
    from swarmkit_tpu.utils.metrics import Registry
    reg = Registry()
    lt = LifecycleTracker(registry=reg)
    store, _specs, _seqs = _build_store(n_nodes=8)
    sub = store.queue.subscribe(accepts_blocks=True)
    planner = TPUPlanner()
    planner.enable_small_group_routing = False
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=1)
    store.view(sched._setup_tasks_list)
    sched.tick()
    while True:
        ev = sub.poll()
        if ev is None:
            break
        lt.handle_event(ev)
    store.queue.unsubscribe(sub)
    t = reg.get_timer(service_edge_timer_name("sva"))
    assert t is not None and t.count > 0


# ------------------------------------- satellite: bulk index batching

def test_bulk_update_tasks_batches_by_node_index(frozen_clock,
                                                 monkeypatch):
    """The non-block bulk path routes by_node writes through
    _batch_index_tasks; buckets keep the insertion-ordered {id: None}
    contract, including around items that take the full reindex route
    (service change) mid-chunk."""
    from swarmkit_tpu import native
    monkeypatch.setattr(native, "get", lambda: None)   # python path
    store = MemoryStore()
    store.update(lambda tx: [tx.create(_mk_node(i)) for i in range(2)])
    spec = TaskSpec(resources=_RES)

    def mk(tx):
        for svc_id in ("ba", "bb"):
            svc, _ = _mk_service(svc_id, 0, spec)
            tx.create(svc)
        for k in range(6):
            tx.create(Task(
                id=f"bt{k}", service_id="ba", slot=k + 1,
                desired_state=TaskState.RUNNING, spec=spec,
                spec_version=Version(index=1),
                status=TaskStatus(state=TaskState.PENDING)))
    store.update(mk)

    calls = []
    orig = MemoryStore._batch_index_tasks

    def spy(by_node, triples):
        triples = list(triples)
        calls.append(triples)
        return orig(by_node, triples)

    monkeypatch.setattr(MemoryStore, "_batch_index_tasks",
                        staticmethod(spy))
    news = []
    for k in range(6):
        t = store.raw_get(Task, f"bt{k}").copy()
        t.node_id = "n0000" if k < 4 else "n0001"
        if k == 2:
            t.service_id = "bb"    # mid-chunk full-reindex item
        t.status = TaskStatus(state=TaskState.ASSIGNED,
                              timestamp=model_types.now(),
                              message="m")
        news.append(t)
    committed, failed = store.bulk_update_tasks(
        news, on_missing=lambda t: None, on_assigned=lambda t: True)
    assert len(committed) == 6 and not failed
    # batching actually happened and the reindex item split the batch
    # (pending triples flushed BEFORE the service-changed item's
    # _unindex/_index, which itself writes by_node per-item)
    assert len(calls) >= 2
    by_node = store._tables["tasks"].by_node
    # per-item commit order preserved inside each bucket — including
    # around the full-reindex item
    assert list(by_node["n0000"]) == ["bt0", "bt1", "bt2", "bt3"]
    assert list(by_node["n0001"]) == ["bt4", "bt5"]
    assert "bt2" in store._tables["tasks"].by_service.get("bb", {})


# ---------------------------------------------------------------- slow

@pytest.mark.slow
def test_steady_state_churn_wide_sweep():
    """Acceptance: 20 seeds of steady-state-churn, all green under the
    incremental-equals-full-replan differential, required coverage
    (incl. streaming-resync x scheduler) present, byte-identical
    re-runs for sampled seeds."""
    run_scenario("steady-state-churn", 0)   # warm the jit signatures
    reports = chaos_sweep.sweep(("steady-state-churn",), n_seeds=20)
    out = chaos_sweep.verdict(reports, ("steady-state-churn",), 20, 0)
    assert out["ok"], json.dumps(
        {"failures": out["failures"],
         "uncovered": out["coverage"]["uncovered"]}, indent=2)
    by_seed = {r.seed: r for r in reports}
    for seed in (0, 7, 13):
        r2 = run_scenario("steady-state-churn", seed, keep_trace=True)
        assert r2.trace_hash == by_seed[seed].trace_hash, seed


@pytest.mark.slow
def test_steady_state_churn_hashseed_independent():
    """Byte-identical across PYTHONHASHSEED: hash-ordered containers
    must not leak into the dirty-set drain order or placements."""
    code = ("from swarmkit_tpu.sim.scenario import run_scenario;"
            "r = run_scenario('steady-state-churn', 0);"
            "print(r.trace_hash, r.ok)")
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
