"""Columnar task-block store: array-shaped scheduler commits with lazy
per-task materialization (reference: memory.go:531 Batch semantics +
scheduler.go:490 applySchedulingDecisions, re-shaped for the TPU path)."""

import pytest

from swarmkit_tpu.models import (
    Annotations, Node, NodeSpec, Service, ServiceSpec, Task, TaskState,
    TaskStatus,
)
from swarmkit_tpu.state import MemoryStore
from swarmkit_tpu.state.store import ByNode, ByService, SequenceConflict
from swarmkit_tpu.utils import new_id

from test_scheduler import make_ready_node, make_service_with_tasks


def _mk_store_with_tasks(n_tasks=10, n_nodes=3):
    store = MemoryStore()
    svc, tasks = make_service_with_tasks(n_tasks)
    nodes = [make_ready_node(f"n{i}") for i in range(n_nodes)]

    def cb(tx):
        tx.create(svc)
        for n in nodes:
            tx.create(n)
        for t in tasks:
            tx.create(t)
    store.update(cb)
    stored = store.view(lambda tx: tx.find(Task, ByService(svc.id)))
    return store, svc, nodes, sorted(stored, key=lambda t: t.slot)


def _noop_missing(t, nid):
    raise AssertionError("on_missing should not fire")


def _no_conflict(t, nid):
    raise AssertionError("on_assigned should not fire")


def test_block_commit_lazy_materialization():
    store, svc, nodes, tasks = _mk_store_with_tasks(6)
    node_ids = [nodes[i % 3].id for i in range(6)]
    v0 = store.version
    committed, failed = store.commit_task_block(
        tasks, node_ids, int(TaskState.ASSIGNED), "assigned",
        _noop_missing, _no_conflict)
    assert committed == list(range(6)) and failed == []
    table = store._tables["tasks"]
    assert len(table.overlay) == 6          # nothing materialized yet
    assert store.version == v0 + 6

    # point read materializes exactly that id, with stamped version
    t0 = store.raw_get(Task, tasks[0].id)
    assert t0.node_id == node_ids[0]
    assert t0.status.state == TaskState.ASSIGNED
    assert t0.status.message == "assigned"
    assert t0.meta.version.index == v0 + 1
    assert len(table.overlay) == 5

    # index-driven find materializes only the touched ids
    on_n1 = store.view(lambda tx: tx.find(Task, ByNode(nodes[1].id)))
    assert {t.id for t in on_n1} == {tasks[1].id, tasks[4].id}
    assert all(t.node_id == nodes[1].id for t in on_n1)
    assert len(table.overlay) == 3

    # scan queries flush the remainder
    all_tasks = store.view(lambda tx: tx.find(Task))
    assert all(t.node_id for t in all_tasks if t.service_id == svc.id)
    assert len(table.overlay) == 0


@pytest.mark.parametrize("reader", ["raw_get", "view_get"])
def test_lock_free_read_during_materialization_is_never_stale(
        monkeypatch, reader):
    """``raw_get`` takes no lock.  While another thread materializes a
    block-committed task (a copy of the task: long enough for the
    interpreter to switch threads), the id must stay in the overlay until
    the assigned object is stored, or the reader gets the pre-assignment
    object: PENDING, no node.  A dispatcher session that reads a block's
    task like that never ships it (seen once on the chip, PR 30: one task
    of 1,975 on agent nodes still ASSIGNED a minute after the close)."""
    import threading
    from swarmkit_tpu.state import store as store_mod
    store, svc, nodes, tasks = _mk_store_with_tasks(2)
    store.commit_task_block(
        tasks, [nodes[0].id, nodes[1].id], int(TaskState.ASSIGNED),
        "assigned", _noop_missing, _no_conflict)
    tid, seen, done = tasks[0].id, [], threading.Event()

    def read():
        if reader == "raw_get":
            seen.append(store.raw_get(Task, tid))
        else:
            seen.append(store.view(lambda tx: tx.get(Task, tid)))
        done.set()
    inner = store_mod._materialize_task

    def mid_copy(old, *args):
        # the other thread reads while this one is inside the copy; a
        # correct reader waits for the store's lock, so give up on it
        # here and let it finish once the materialization has
        threading.Thread(target=read, daemon=True).start()
        done.wait(0.2)
        return inner(old, *args)
    monkeypatch.setattr(store_mod, "_materialize_task", mid_copy)
    found = store.view(lambda tx: tx.find(Task, ByNode(nodes[0].id)))
    assert [t.id for t in found] == [tid]
    assert done.wait(5)
    assert seen[0].node_id == nodes[0].id
    assert seen[0].status.state == TaskState.ASSIGNED
    assert seen[0] is store.raw_get(Task, tid)
    assert tid not in store._tables["tasks"].overlay


def test_block_commit_conflict_semantics():
    store, svc, nodes, tasks = _mk_store_with_tasks(4)
    nid = nodes[0].id

    # stale mirror version -> failed
    stale = tasks[0].copy()
    stale.meta.version.index -= 1
    committed, failed = store.commit_task_block(
        [stale], [nid], int(TaskState.ASSIGNED), "assigned",
        _noop_missing, lambda t, n: False)
    assert committed == [] and failed == [0]

    # missing task -> on_missing, appears in neither list
    ghost = tasks[1].copy()
    ghost.id = new_id()
    seen = []
    committed, failed = store.commit_task_block(
        [ghost], [nid], int(TaskState.ASSIGNED), "assigned",
        lambda t, n: seen.append(t), lambda t, n: False)
    assert committed == [] and failed == [] and seen == [ghost]

    # guard: stored state >= ASSIGNED consults on_assigned
    committed, _ = store.commit_task_block(
        [tasks[2]], [nid], int(TaskState.ASSIGNED), "assigned",
        _noop_missing, _no_conflict)
    assert committed == [0]
    # recommit of the same (still-unmaterialized) task: slow path runs,
    # same state+message -> skipped, no duplicate version burn
    v = store.version
    committed, failed = store.commit_task_block(
        [tasks[2]], [nid], int(TaskState.ASSIGNED), "assigned",
        _noop_missing, lambda t, n: True)
    assert committed == [] and failed == []
    assert store.version == v


def test_block_commit_interops_with_tx_update_and_snapshot():
    store, svc, nodes, tasks = _mk_store_with_tasks(3)
    node_ids = [nodes[0].id] * 3
    store.commit_task_block(
        tasks, node_ids, int(TaskState.ASSIGNED), "assigned",
        _noop_missing, _no_conflict)

    # a transactional update sees the materialized form and its version
    def bump(tx):
        t = tx.get(Task, tasks[0].id)
        assert t.node_id == nodes[0].id
        cur = t.copy()
        cur.status = TaskStatus(state=TaskState.RUNNING)
        tx.update(cur)
    store.update(bump)
    got = store.raw_get(Task, tasks[0].id)
    assert got.status.state == TaskState.RUNNING

    # stale-version updates still conflict
    def stale(tx):
        t = tx.get(Task, tasks[1].id).copy()
        t.meta.version.index -= 1
        tx.update(t)
    with pytest.raises(SequenceConflict):
        store.update(stale)

    # snapshots contain materialized tasks (save flushes the overlay)
    snap = store.save()
    by_id = {t.id: t for t in snap["tables"]["tasks"]}
    assert all(by_id[t.id].node_id == nodes[0].id for t in tasks)

    s2 = MemoryStore()
    s2.restore(snap)
    assert s2.raw_get(Task, tasks[2].id).node_id == nodes[0].id


def test_block_commit_with_watchers_synthesizes_events():
    """Live watchers get the per-task update events the per-object path
    would have published — synthesized lazily from ONE coalesced
    EventTaskBlock; block-aware subscribers get the block itself."""
    from swarmkit_tpu.state import EventCommit
    from swarmkit_tpu.state.events import EventTaskBlock, match

    store, svc, nodes, tasks = _mk_store_with_tasks(4)
    assert store.supports_block_commit   # watchers no longer disable it
    sub = store.watch_queue().subscribe(
        match(Task, actions=("update",)))
    raw = store.watch_queue().subscribe(accepts_blocks=True)
    v0 = store.version
    node_ids = [nodes[i % 3].id for i in range(4)]
    committed, failed = store.commit_task_block(
        tasks, node_ids, int(TaskState.ASSIGNED), "assigned",
        _noop_missing, _no_conflict)
    assert committed == list(range(4)) and failed == []

    evs = [sub.get(timeout=2) for _ in range(4)]
    for i, ev in enumerate(evs):
        assert ev.action == "update"
        assert ev.obj.id == tasks[i].id
        assert ev.obj.node_id == node_ids[i]
        assert ev.obj.status.state == TaskState.ASSIGNED
        assert ev.obj.meta.version.index == v0 + 1 + i
        assert ev.old is tasks[i]          # pre-assignment object
    with pytest.raises(TimeoutError):
        sub.get(timeout=0.05)

    block = raw.get(timeout=2)
    assert isinstance(block, EventTaskBlock)
    assert len(block) == 4 and block.base_version == v0
    assert isinstance(raw.get(timeout=2), EventCommit)
    store.watch_queue().unsubscribe(sub)
    store.watch_queue().unsubscribe(raw)


def test_block_filtered_to_nothing_does_not_break_waiters():
    """A subscriber whose predicate rejects every event a block expands
    to must keep honoring its get() timeout: the block wakes the waiter,
    expansion filters to nothing, and the wait continues to the caller's
    deadline (no premature TimeoutError), then delivers later events."""
    import threading
    import time as _time

    from swarmkit_tpu.models import Node
    from swarmkit_tpu.state.events import match

    store, svc, nodes, tasks = _mk_store_with_tasks(3)
    sub = store.watch_queue().subscribe(match(Node, actions=("update",)))

    def commit_late():
        _time.sleep(0.1)
        store.commit_task_block(
            tasks, [nodes[0].id] * 3, int(TaskState.ASSIGNED),
            "assigned", _noop_missing, _no_conflict)

    th = threading.Thread(target=commit_late, daemon=True)
    t0 = _time.monotonic()
    th.start()
    with pytest.raises(TimeoutError):
        sub.get(timeout=0.6)
    elapsed = _time.monotonic() - t0
    th.join()
    assert elapsed >= 0.55, \
        f"woke after {elapsed:.2f}s — block traffic broke the deadline"

    # matching events still flow after the no-match block
    def touch_node(tx):
        n = tx.get(Node, nodes[1].id).copy()
        tx.update(n)
    store.update(touch_node)
    ev = sub.get(timeout=2)
    assert ev.obj.id == nodes[1].id
    store.watch_queue().unsubscribe(sub)


class _CapturingProposer:
    """Test proposer: records serialized actions, commits via callback
    (the consensus seam contract), optionally replays onto a follower."""

    def __init__(self, follower=None, fail=False):
        self.actions = []
        self.follower = follower
        self.fail = fail

    def propose(self, actions, commit_cb):
        if self.fail:
            raise RuntimeError("leadership lost")
        from swarmkit_tpu.state import serde
        wire = serde.dumps([serde.action_to_dict(a) for a in actions])
        self.actions.extend(actions)
        commit_cb()
        if self.follower is not None:
            decoded = [serde.action_from_dict(d)
                       for d in serde.loads_dict(wire)]
            self.follower.apply_store_actions(decoded)


def test_block_commit_rides_proposer_and_converges_follower():
    """With a proposer the block validates first, then rides a compact
    columnar TaskBlockAction through consensus; a follower replaying the
    serialized action converges bit-for-bit (same versions, node ids,
    lazy overlay shape)."""
    from swarmkit_tpu.state.store import TaskBlockAction

    store, svc, nodes, tasks = _mk_store_with_tasks(6)
    follower = MemoryStore()
    follower.restore(store.save())
    store._proposer = _CapturingProposer(follower=follower)
    assert store.supports_block_commit

    v0 = store.version
    node_ids = [nodes[i % 3].id for i in range(6)]
    committed, failed = store.commit_task_block(
        tasks, node_ids, int(TaskState.ASSIGNED), "assigned",
        _noop_missing, _no_conflict)
    assert committed == list(range(6)) and failed == []
    assert store.version == v0 + 6

    [action] = store._proposer.actions
    assert isinstance(action, TaskBlockAction)
    assert list(action.ids) == [t.id for t in tasks]
    assert list(action.node_ids) == node_ids
    assert action.base_version == v0

    # leader committed lazily (overlay, not materialized objects)
    assert len(store._tables["tasks"].overlay) == 6

    # follower converges: same assignments and version stamps
    assert follower.version == store.version
    for i, t in enumerate(tasks):
        mine = store.raw_get(Task, t.id)
        theirs = follower.raw_get(Task, t.id)
        assert theirs.node_id == mine.node_id == node_ids[i]
        assert theirs.meta.version.index == mine.meta.version.index
        assert theirs.status.state == TaskState.ASSIGNED
    assert {t.id for t in follower.view(
        lambda tx: tx.find(Task, ByNode(nodes[0].id)))} == \
        {t.id for t in store.view(
            lambda tx: tx.find(Task, ByNode(nodes[0].id)))}


def test_block_commit_proposer_validation_and_failure():
    """Validation (stale/ghost/guard) happens before proposing — rejected
    items never reach consensus; a dropped proposal fails every accepted
    item and leaves the store untouched."""
    store, svc, nodes, tasks = _mk_store_with_tasks(5)
    store._proposer = _CapturingProposer()
    nid = nodes[0].id

    stale = tasks[0].copy()
    stale.meta.version.index -= 1
    ghost = tasks[1].copy()
    ghost.id = new_id()
    seen = []
    committed, failed = store.commit_task_block(
        [stale, ghost, tasks[2], tasks[3]], [nid] * 4,
        int(TaskState.ASSIGNED), "assigned",
        lambda t, n: seen.append(t), lambda t, n: False)
    assert committed == [2, 3] and failed == [0] and seen == [ghost]
    [action] = store._proposer.actions
    assert list(action.ids) == [tasks[2].id, tasks[3].id]

    # dropped proposal: accepted items fail, nothing commits
    store2, _, nodes2, tasks2 = _mk_store_with_tasks(3)
    store2._proposer = _CapturingProposer(fail=True)
    v = store2.version
    committed, failed = store2.commit_task_block(
        tasks2, [nodes2[0].id] * 3, int(TaskState.ASSIGNED), "assigned",
        _noop_missing, _no_conflict)
    assert committed == [] and failed == [0, 1, 2]
    assert store2.version == v
    assert not store2._tables["tasks"].overlay
    assert store2.raw_get(Task, tasks2[0].id).node_id == ""


def test_block_commit_native_matches_python(monkeypatch):
    """Differential: the C block_commit fast path and the pure-Python
    loop produce identical overlays, indexes, and results."""
    import swarmkit_tpu.native as native

    def run(force_python):
        store, svc, nodes, tasks = _mk_store_with_tasks(8)
        if force_python:
            monkeypatch.setattr(native, "get", lambda: None)
        else:
            monkeypatch.undo()
        # mix: 5 clean, 1 stale-version, 1 missing, 1 already-assigned
        olds = list(tasks[:5])
        nids = [nodes[i % 3].id for i in range(5)]
        stale = tasks[5].copy()
        stale.meta.version.index -= 1
        olds.append(stale)
        nids.append(nodes[0].id)
        ghost = tasks[6].copy()
        ghost.id = new_id()
        olds.append(ghost)
        nids.append(nodes[0].id)
        store.commit_task_block(
            [tasks[7]], [nodes[2].id], int(TaskState.ASSIGNED),
            "assigned", _noop_missing, _no_conflict)
        olds.append(tasks[7])
        nids.append(nodes[1].id)   # conflicting re-assignment
        missing = []
        committed, failed = store.commit_task_block(
            olds, nids, int(TaskState.ASSIGNED), "assigned",
            lambda t, n: missing.append(t), lambda t, n: False)
        table = store._tables["tasks"]
        names = {nd.id: nd.description.hostname for nd in nodes}
        tasks_by_id = {t.id: t for t in tasks}
        overlay_shape = sorted(
            (tasks_by_id[tid].slot, names[e[0]], int(e[3]))
            for tid, e in table.overlay.items())
        return (sorted(committed), sorted(failed), len(missing),
                overlay_shape)

    a = run(force_python=False)
    b = run(force_python=True)
    assert a == b
    assert a[0] == [0, 1, 2, 3, 4]
    # 5 = stale version -> failed; 7 = same status already committed ->
    # skipped (status-equality short-circuit precedes the guard, matching
    # bulk_update_tasks); 6 = missing -> on_missing only
    assert a[1] == [5] and a[2] == 1


def test_scheduler_block_path_matches_eager_path():
    """Same cluster, same tick through the device planner: block-mode
    assignments equal the eager per-object path's."""
    from swarmkit_tpu.ops import TPUPlanner
    from swarmkit_tpu.scheduler import Scheduler

    def run(block: bool):
        store, svc, nodes, tasks = _mk_store_with_tasks(30, 5)
        sub = None
        if not block:
            # a subscriber forces the eager path
            sub = store.watch_queue().subscribe()
        planner = TPUPlanner()
        planner.enable_small_group_routing = False
        sched = Scheduler(store, batch_planner=planner)
        store.view(sched._setup_tasks_list)
        n = sched.tick()
        assert n == 30
        if block:
            assert planner.stats["tasks_planned"] == 30
            assert sched.block_mode
        placed = store.view(lambda tx: tx.find(Task, ByService(svc.id)))
        if sub is not None:
            store.watch_queue().unsubscribe(sub)
        names = {nd.id: nd.description.hostname for nd in nodes}
        assert all(t.status.state == TaskState.ASSIGNED for t in placed)
        # node ids are random per cluster: compare hostname placements
        return sorted(names[t.node_id] for t in placed)

    assert run(block=True) == run(block=False)
