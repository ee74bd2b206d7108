"""Versioned in-memory cluster store with watches and a consensus seam.

Reference: manager/state/store/memory.go (go-memdb based MemoryStore).

Semantics preserved from the reference:

* ``view(cb)`` / ``update(cb)`` transactions; update collects a changelist,
  (optionally) proposes it through a ``Proposer`` (raft), then commits and
  publishes one event per change plus an ``EventCommit``
  (memory.go:395-470).
* Version sequencing: every committed write stamps ``meta.version.index``
  with a monotonically increasing store index; updates require the caller's
  object version to match the stored version (``SequenceConflict``) — the
  scheduler's node-conflict rollback depends on this (scheduler.go:533-544).
* ``batch(cb)`` splits a large write into transactions of at most
  ``MAX_CHANGES_PER_TX`` changes (memory.go:45-51).
* ``view_and_watch`` atomically snapshots + subscribes so no event is lost
  (memory.go:892).
* ``apply_store_actions`` replays follower-side raft log entries
  (memory.go:280).
* ``save``/``restore`` full-store snapshots for raft snapshot transfer.
* Unique, case-preserved names per collection except tasks (naming conflicts
  return ``NameConflict``).

Implementation differs deliberately: plain dicts + per-store RW mutex instead
of a radix-tree MVCC — the control plane is low-write-rate and the scheduler
hot path reads a private mirror, so simplicity wins.  Objects returned by
reads are the stored instances; callers must not mutate them (writes store
defensive copies via ``obj.copy()``).
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional, Sequence, Tuple, Type

from ..models.objects import (
    Cluster, Config, Extension, Network, Node, Resource, Secret, Service,
    Task, Volume, STORE_OBJECT_TYPES,
)
from ..models.types import now, time_source_installed
from ..obs.trace import tracer
from ..utils.metrics import registry as _metrics
from ..utils.pipeline import default_pipeline_depth
from .events import Event, EventCommit, EventSnapshotRestore, EventTaskBlock
from .watch import Queue, Subscription

MAX_CHANGES_PER_TX = 200  # reference: memory.go:45-51
# a transaction (= one raft proposal) also flushes once its changes reach
# this serialized size, whichever bound trips first (reference:
# memory.go:45-51 MaxTransactionBytes = 1.5MB)
MAX_TX_BYTES = 1_500_000
WEDGE_TIMEOUT = 30.0      # reference: memory.go:79-146 deadlock tripwire
# a wait for the update lock this long or longer gets a span of its own
# (``store.lock_wait``, with the holder's name) while the tracer is on
LOCK_WAIT_SPAN_S = 0.001

log = logging.getLogger("store")

# cached Timer references for the write paths (Registry.reset() resets
# these in place, so holding them is safe)
_UPDATE_TX_TIMER = _metrics.timer("swarm_store_write_tx_latency")
_BATCH_TIMER = _metrics.timer("swarm_store_batch_latency")
_BLOCK_COMMIT_TIMER = _metrics.timer("swarm_store_block_commit_latency")


# the update lock by waiter: seconds each thread has waited for a store's
# update lock while the tracer was on (``_TimedLock.acquire`` adds them)
_waited = threading.local()


def lock_waited_s() -> float:
    """The calling thread's running total of waits for the update lock,
    short ones too, summed while the tracer is on and no time source is
    installed.  A span's owner reads it at the span's start and again at
    its end (``span_waits``)."""
    return getattr(_waited, "s", 0.0)


def span_waits(sp, waited0: float) -> None:
    """The two arguments a span reads off the machine, set once it has
    ended: ``lock_wait_ms``, what its thread waited for the update lock
    since ``waited0`` (``lock_waited_s()`` at its start), and
    ``offcpu_ms``, its wall less its thread's CPU.  Both are left out
    where the span has no ``cpu``: an installed time source."""
    if sp.cpu is None:
        return
    if sp.args is None:
        sp.args = {}
    sp.args["lock_wait_ms"] = round((lock_waited_s() - waited0) * 1e3, 3)
    sp.args["offcpu_ms"] = round((sp.duration - sp.cpu) * 1e3, 3)


class _TimedLock:
    """Update-lock wrapper with a lock-age tripwire and hold-time metric
    (reference: memory.go timedMutex — logs when the store wedges)."""

    __slots__ = ("_lock", "_acquired_at", "_holder", "_wait_timer",
                 "_hold_timer")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._acquired_at = 0.0
        self._holder = ""
        # cached Timer references: this runs on the system's hottest
        # lock, so no per-call registry lookup (Registry.reset() resets
        # timers in place precisely to keep held references valid)
        self._wait_timer = _metrics.timer("swarm_store_lock_wait")
        self._hold_timer = _metrics.timer("swarm_store_lock_hold")

    def acquire(self) -> None:
        t0 = time.monotonic()
        # who held it when this writer arrived: the name on the wait's
        # span (racy by design; "" = it was free)
        holder = self._holder
        while not self._lock.acquire(timeout=WEDGE_TIMEOUT):
            log.error(
                "store update lock wedged: held for %.0fs by %r "
                "(waiter: %r)", time.monotonic() - self._acquired_at,
                self._holder, threading.current_thread().name)
        self._acquired_at = time.monotonic()
        self._holder = threading.current_thread().name
        # reference: memory.go:84-112 lockTimer — contention visibility
        wait = self._acquired_at - t0
        self._wait_timer.observe(wait)
        # a wait is read off the machine's clock: under an installed time
        # source (the sim) a writer preempted for a millisecond on a
        # loaded host would put a span into a seed-pure trace
        if tracer.enabled and not time_source_installed():
            # every wait, the short ones too, on the waiter's own account
            _waited.s = getattr(_waited, "s", 0.0) + wait
            if wait >= LOCK_WAIT_SPAN_S:
                tracer.record_complete("store.lock_wait", "store", wait,
                                       holder=holder)

    def release(self) -> None:
        held = time.monotonic() - self._acquired_at
        self._holder = ""
        self._lock.release()
        # observed after the release so it never extends the hold
        self._hold_timer.observe(held)
        if held > WEDGE_TIMEOUT:
            log.error("store update lock was held for %.0fs", held)

    def __enter__(self) -> "_TimedLock":
        self.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self.release()


class StoreError(Exception):
    pass


class NotFound(StoreError):
    pass


class AlreadyExists(StoreError):
    pass


class NameConflict(StoreError):
    pass


class SequenceConflict(StoreError):
    """Update out of sequence (stale version)."""


class InvalidStoreAction(StoreError):
    pass


@dataclass(frozen=True)
class StoreAction:
    """One replicated mutation (reference: api.StoreAction)."""

    action: str        # "create" | "update" | "delete"
    obj: Any           # a store object snapshot


@dataclass(frozen=True)
class TaskBlockAction:
    """One replicated columnar scheduler block: N task assignments in a
    single compact raft entry (~2 strings/task instead of N serialized
    Task objects).  Followers apply it straight into the task table's
    overlay — the same lazy-materialization shape the leader commits.
    Replaces N per-task StoreActions for scheduler status flips; the
    reference has no counterpart (it proposes per-object actions,
    manager/state/raft/raft.go:1592 ProposeValue)."""

    action: str            # always "task_block"
    ids: Tuple[str, ...]
    node_ids: Tuple[str, ...]
    base_version: int      # versions run base+1 .. base+len(ids)
    state: int
    message: str
    ts: float


class Proposer:
    """Consensus seam (reference: manager/state/proposer.go:17).

    ``propose`` must block until the change list is committed by consensus
    (or raise).  ``commit_cb`` — the store-side commit — must be invoked
    exactly once, synchronously in the consensus apply path, before the
    applied index advances past this entry; this is what keeps snapshots
    consistent with the entries they claim to cover (the reference passes
    the memstore commit as the wait callback run by wait.trigger inside
    processEntry, raft.go:1917).  On failure commit_cb must NOT run and
    propose raises.  Actions arrive with their final version indices
    already stamped (see MemoryStore.update).  A nil proposer (None) keeps
    the store fully functional standalone — the master test fixture of the
    reference.

    Leadership fencing (optional): proposers that expose a non-None
    ``leadership_epoch`` (RaftNode, the sim's member-bound proposer)
    accept an ``epoch=`` keyword on propose/propose_async and reject a
    proposal whose pinned epoch has been fenced — before serialization,
    again pre-WAL, and again at commit-callback delivery.  The store
    pins every chunk of a multi-proposal commit to the epoch it started
    under, so a chunked commit can never straddle a role change.  Plain
    proposers (this base class, test fakes) ignore fencing entirely.
    """

    #: current leadership-epoch fencing token; None = no fencing support
    leadership_epoch: Optional[int] = None

    def propose(self, actions: Sequence[StoreAction],
                commit_cb: Callable[[], None]) -> None:
        raise NotImplementedError


# ---------------------------------------------------------------------------
# Find combinators (reference: manager/state/store/by.go)
# ---------------------------------------------------------------------------

class By:
    """Query selector; subclasses know how to use indexes or fall back to
    a linear filter."""


@dataclass(frozen=True)
class All(By):
    pass


@dataclass(frozen=True)
class ByName(By):
    name: str


@dataclass(frozen=True)
class ByNamePrefix(By):
    prefix: str


@dataclass(frozen=True)
class ByIDPrefix(By):
    prefix: str


@dataclass(frozen=True)
class ByService(By):
    service_id: str


@dataclass(frozen=True)
class ByNode(By):
    node_id: str


@dataclass(frozen=True)
class BySlot(By):
    service_id: str
    slot: int


@dataclass(frozen=True)
class ByDesiredState(By):
    state: int


@dataclass(frozen=True)
class ByTaskState(By):
    state: int


@dataclass(frozen=True)
class ByRole(By):
    role: int


@dataclass(frozen=True)
class ByMembership(By):
    membership: int


@dataclass(frozen=True)
class ByReferencedSecret(By):
    secret_id: str


@dataclass(frozen=True)
class ByReferencedConfig(By):
    config_id: str


@dataclass(frozen=True)
class ByReferencedNetwork(By):
    network_id: str


@dataclass(frozen=True)
class ByVolumeGroup(By):
    group: str


@dataclass(frozen=True)
class ByKind(By):
    kind: str


@dataclass(frozen=True)
class ByCustom(By):
    index: str
    value: str


@dataclass(frozen=True)
class Or(By):
    bys: Tuple[By, ...]

    def __init__(self, *bys: By):
        object.__setattr__(self, "bys", tuple(bys))


@dataclass(frozen=True)
class Where(By):
    """Escape hatch: arbitrary predicate (linear scan)."""

    pred: Callable[[Any], bool]


def _task_secret_ids(t: Task) -> Iterable[str]:
    c = t.spec.container
    if c:
        for ref in c.secrets:
            yield ref.secret_id


def _task_config_ids(t: Task) -> Iterable[str]:
    c = t.spec.container
    if c:
        for ref in c.configs:
            yield ref.config_id


def _task_network_ids(t: Task) -> Iterable[str]:
    for a in t.networks:
        yield a.network_id
    for n in t.spec.networks:
        yield n.target


def _service_network_ids(s: Service) -> Iterable[str]:
    for n in s.spec.networks:
        yield n.target
    for n in s.spec.task.networks:
        yield n.target


def _materialize_task(old: Task, node_id: str, version: int, ts: float,
                      state, message: str) -> Task:
    """Build the assigned form of a block-committed task from its
    pre-assignment object + overlay tuple — single recipe shared by lazy
    materialization and changelog replay."""
    from ..models.types import TaskState, TaskStatus
    new = old.copy()
    new.node_id = node_id
    new.status = TaskStatus(state=TaskState(state), timestamp=ts,
                            message=message)
    new.meta.version.index = version
    new.meta.updated_at = ts
    return new


def _obj_name(obj: Any) -> str:
    spec = getattr(obj, "spec", None)
    ann = getattr(spec, "annotations", None) or getattr(obj, "annotations", None)
    if ann is not None and ann.name:
        return ann.name
    # nodes are named by hostname when they have no explicit name
    desc = getattr(obj, "description", None)
    if desc is not None and desc.hostname:
        return desc.hostname
    return ""


class _Table:
    def __init__(self) -> None:
        self.objects: Dict[str, Any] = {}
        self.by_name: Dict[str, str] = {}            # lower(name) -> id
        # index buckets are insertion-ordered {id: None} dicts, NOT
        # sets: indexed find() results feed placement decisions, and set
        # iteration order varies with hash randomization — per-process
        # nondeterminism the sim's byte-identical-report contract forbids
        self.by_service: Dict[str, Dict[str, None]] = {}   # tasks/volumes
        self.by_node: Dict[str, Dict[str, None]] = {}
        self.by_slot: Dict[Tuple[str, int], Dict[str, None]] = {}
        # columnar task-block overlay: id -> (node_id, version, ts, state,
        # message).  A block commit records assignments here instead of
        # materializing per-task objects; reads materialize lazily (see
        # MemoryStore._materialize_locked).  Indexes are maintained
        # eagerly, so only `objects` values can be stale.
        self.overlay: Dict[str, tuple] = {}

    def snapshot(self) -> Dict[str, Any]:
        return dict(self.objects)


class ReadTx:
    """Consistent read view.  Holds the store lock only during method calls;
    objects are immutable-by-convention so the view stays coherent."""

    def __init__(self, store: "MemoryStore"):
        self._store = store

    def get(self, kind: Type, id: str) -> Optional[Any]:
        return self._store.raw_get(kind, id)

    def find(self, kind: Type, by: By = All()) -> List[Any]:
        with self._store._lock:
            return self._store._find_locked(kind, by)


class WriteTx(ReadTx):
    def __init__(self, store: "MemoryStore"):
        super().__init__(store)
        self._changes: List[StoreAction] = []
        self._events: List[Event] = []
        # staged view: id -> obj (or _TOMBSTONE)
        self._staged: Dict[Tuple[str, str], Any] = {}
        # staged name index: (collection, lower-name) -> id, so name-conflict
        # checks stay O(1) even for 10k-create transactions
        self._staged_names: Dict[Tuple[str, str], str] = {}
        self._staged_name_by_id: Dict[Tuple[str, str], str] = {}
        self.closed = False

    # reads see staged writes
    def get(self, kind: Type, id: str) -> Optional[Any]:
        key = (kind.collection, id)
        if key in self._staged:
            obj = self._staged[key]
            return None if obj is _TOMBSTONE else obj
        return super().get(kind, id)

    def find(self, kind: Type, by: By = All()) -> List[Any]:
        base = super().find(kind, by)
        if not self._staged:
            return base
        staged_ids = {i for (c, i) in self._staged if c == kind.collection}
        if not staged_ids:
            return base
        out = [o for o in base if o.id not in staged_ids]
        pred = self._store._predicate_for(kind, by)
        for (c, i), obj in self._staged.items():
            if c != kind.collection or obj is _TOMBSTONE:
                continue
            if pred(obj):
                out.append(obj)
        return out

    def _check_name(self, kind: Type, obj: Any) -> None:
        if kind.collection == "tasks":
            return
        name = _obj_name(obj)
        if not name:
            return
        lname = name.lower()
        staged_holder = self._staged_names.get((kind.collection, lname))
        if staged_holder is not None and staged_holder != obj.id:
            raise NameConflict(f"name conflict: {name!r}")
        with self._store._lock:
            existing = self._store._tables[kind.collection].by_name.get(lname)
        if existing is not None and existing != obj.id:
            # unless the holder is staged for deletion / rename
            holder = self._staged.get((kind.collection, existing))
            if holder is _TOMBSTONE:
                return
            if holder is not None and _obj_name(holder).lower() != lname:
                return
            raise NameConflict(f"name conflict: {name!r}")

    def _stage_name(self, kind: Type, obj: Any) -> None:
        if kind.collection == "tasks":
            return
        # drop any staged name previously held by this id (rename in-tx)
        old = self._staged_name_by_id.pop((kind.collection, obj.id), None)
        if old is not None:
            self._staged_names.pop((kind.collection, old), None)
        name = _obj_name(obj).lower()
        if name:
            self._staged_names[(kind.collection, name)] = obj.id
            self._staged_name_by_id[(kind.collection, obj.id)] = name

    def create(self, obj: Any) -> None:
        kind = type(obj)
        if self.get(kind, obj.id) is not None:
            raise AlreadyExists(obj.id)
        self._check_name(kind, obj)
        cp = obj.copy()
        ts = now()
        cp.meta.created_at = cp.meta.created_at or ts
        cp.meta.updated_at = ts
        self._staged[(kind.collection, obj.id)] = cp
        self._stage_name(kind, cp)
        self._changes.append(StoreAction("create", cp))
        self._events.append(Event("create", cp))

    def update(self, obj: Any) -> None:
        kind = type(obj)
        existing = self.get(kind, obj.id)
        if existing is None:
            raise NotFound(obj.id)
        if existing.meta.version.index != obj.meta.version.index:
            raise SequenceConflict(
                f"{kind.collection}/{obj.id}: stale version "
                f"{obj.meta.version.index} != {existing.meta.version.index}")
        self._check_name(kind, obj)
        cp = obj.copy()
        cp.meta.created_at = existing.meta.created_at
        cp.meta.updated_at = now()
        self._staged[(kind.collection, obj.id)] = cp
        self._stage_name(kind, cp)
        self._changes.append(StoreAction("update", cp))
        self._events.append(Event("update", cp, existing))

    def delete(self, kind: Type, id: str) -> None:
        existing = self.get(kind, id)
        if existing is None:
            raise NotFound(id)
        self._staged[(kind.collection, id)] = _TOMBSTONE
        old = self._staged_name_by_id.pop((kind.collection, id), None)
        if old is not None:
            self._staged_names.pop((kind.collection, old), None)
        self._changes.append(StoreAction("delete", existing))
        self._events.append(Event("delete", existing))


class _Tombstone:
    def __repr__(self) -> str:
        return "<deleted>"


_TOMBSTONE = _Tombstone()


class MemoryStore:
    def __init__(self, proposer: Optional[Proposer] = None):
        self._lock = threading.RLock()
        self._update_lock = _TimedLock()  # serializes writers; tripwired
        self._tables: Dict[str, _Table] = {
            t.collection: _Table() for t in STORE_OBJECT_TYPES
        }
        self._proposer = proposer
        self._version = 0
        # raft block-chunk pipelining window for commit_task_block: with
        # a proposer exposing propose_async/wait_proposal, up to this
        # many chunk proposals ride consensus at once (serialization and
        # WAL writes of chunk i+1 overlap the apply of chunk i); 1 =
        # strictly serial propose->wait per chunk (SWARM_PIPELINE_DEPTH
        # escape hatch)
        self.pipeline_depth = default_pipeline_depth()
        self.queue = Queue()
        # bounded changelog ring for watch-from-version resume
        # (reference: raft.go:1617 ChangesBetween over the raft log).
        # Entries: ("one", version, action, obj, old) or a columnar
        # ("block", base_version, olds, node_ids, state, message, ts)
        # from commit_task_block, expanded lazily on replay.
        self._changelog: deque = deque()
        self._changelog_total = 0
        self.changelog_limit = 8192   # changes retained for resume

    # ------------------------------------------------------------------ reads

    def raw_get(self, kind: Type, id: str) -> Optional[Any]:
        """Lock-free point read: a single GIL-atomic dict lookup of an
        immutable stored object.  The supported fast-read API for hot-path
        friends (scheduler commit checks); everything else should use
        ``view``.  Block-committed tasks materialize on first access."""
        table = self._tables[kind.collection]
        if table.overlay and id in table.overlay:
            with self._lock:
                return self._materialize_locked(table, id)
        return table.objects.get(id)

    # ------------------------------------------- task-block lazy materialization

    def _materialize_locked(self, table: _Table, tid: str) -> Optional[Any]:
        """Turn an overlay entry into a real stored Task (caller holds
        ``_lock``).  Idempotent: a concurrent reader may have materialized
        the id between the overlay check and lock acquisition.  The
        stored object is written BEFORE the entry leaves the overlay:
        ``raw_get`` reads without the lock, and an id that is in neither
        place for the length of a task copy reads as its pre-assignment
        object (a dispatcher session that reads a block's task so never
        ships it: the task stays ASSIGNED for good)."""
        entry = table.overlay.get(tid)
        old = table.objects.get(tid)
        if entry is None or old is None:
            table.overlay.pop(tid, None)
            return old
        node_id, version, ts, state, message = entry
        new = _materialize_task(old, node_id, version, ts, state, message)
        table.objects[tid] = new
        del table.overlay[tid]
        return new

    def _materialize_all_locked(self, table: _Table) -> None:
        if table.overlay:
            for tid in list(table.overlay):
                self._materialize_locked(table, tid)

    def view(self, cb: Optional[Callable[[ReadTx], Any]] = None) -> Any:
        tx = ReadTx(self)
        if cb is None:
            return tx
        return cb(tx)

    def read_view(self, cb: Optional[Callable[[ReadTx], Any]] = None,
                  linearizable: bool = False,
                  timeout: Optional[float] = None) -> Any:
        """A read transaction with an optional linearizability guarantee.

        ``linearizable=False`` is ``view``: the local replicated state,
        which may trail the leader (serializable, never uncommitted —
        followers only apply committed entries).  ``linearizable=True``
        first runs the proposer's ``read_barrier`` capability (raft
        read-index / leader lease): the barrier returns only once this
        store has applied everything committed cluster-wide at call
        time, so a FOLLOWER store serves linearizable reads without
        touching the leader's store.  Raises the proposer's
        ReadUnavailable when the barrier cannot be confirmed — degraded,
        never stale.  Proposers without the capability (nil/test
        proposers, a standalone store) serve directly: there is no
        replication lag to wait out.

        The barrier deliberately runs OUTSIDE both store locks (it blocks
        on consensus; swarmlint's lock-discipline rule bans it under
        ``_lock``/``_update_lock``)."""
        if linearizable and self._proposer is not None:
            barrier = getattr(self._proposer, "read_barrier", None)
            if barrier is not None:
                if timeout is None:
                    barrier()
                else:
                    barrier(timeout=timeout)
        tx = ReadTx(self)
        if cb is None:
            return tx
        return cb(tx)

    def view_and_watch(self, cb: Callable[[ReadTx], Any],
                       predicate=None, limit: Optional[int] = None,
                       accepts_blocks: bool = False
                       ) -> Tuple[Any, Subscription]:
        """Atomic snapshot + subscribe (reference: memory.go:892)."""
        with self._update_lock:
            sub = (self.queue.subscribe_limited(limit, predicate,
                                                accepts_blocks)
                   if limit else self.queue.subscribe(predicate,
                                                      accepts_blocks))
            result = cb(ReadTx(self))
        return result, sub

    def watch_queue(self) -> Queue:
        return self.queue

    # ----------------------------------------------------------------- writes

    def update(self, cb: Callable[[WriteTx], Any]) -> Any:
        """Run a write transaction; commit via proposer when configured.

        Version indices are stamped *before* proposing so the replicated
        StoreActions carry the exact versions the leader will commit —
        followers replaying them converge bit-for-bit (the reference gets
        this via proposer.GetVersion(); memory.go).
        """
        t0 = time.perf_counter()
        try:
            with self._update_lock:
                tx = WriteTx(self)
                result = cb(tx)  # exceptions roll back (nothing committed)
                self._propose_and_commit(tx)
                return result
        finally:
            _UPDATE_TX_TIMER.observe(time.perf_counter() - t0)

    def _proposer_epoch(self) -> Optional[int]:
        """The proposer's current leadership-epoch fencing token, or None
        when the proposer (or a nil proposer) does not support fencing."""
        return getattr(self._proposer, "leadership_epoch", None)

    @staticmethod
    def _propose_fenced(proposer, actions, commit_cb, epoch):
        """propose() with the epoch pin when fencing is supported; plain
        two-argument propose for legacy/test proposers."""
        if epoch is None:
            proposer.propose(actions, commit_cb)
        else:
            proposer.propose(actions, commit_cb, epoch=epoch)

    def _propose_and_commit(self, tx: "WriteTx") -> None:
        """Stamp versions, run consensus, apply.  Caller holds _update_lock.

        With a proposer, the local commit runs inside the consensus apply
        path (see Proposer.propose) so snapshots taken at an applied index
        always include that index's changes."""
        if tx._changes:
            with self._lock:
                seq = self._version
            for change in tx._changes:
                seq += 1
                if change.action in ("create", "update"):
                    change.obj.meta.version.index = seq
            if self._proposer is not None:
                # the epoch read here travels with the proposal: stamped
                # versions are only valid for the reign they were read
                # under, and the fence makes that a checked invariant
                self._propose_fenced(self._proposer, tx._changes,
                                     lambda: self._commit(tx),
                                     self._proposer_epoch())
                return
        self._commit(tx)

    def batch(self, cb: Callable[["Batch"], Any]) -> Any:
        """Split a large write into transactions bounded by
        MAX_CHANGES_PER_TX *store changes* (reference: memory.go:531).

        Sub-transactions commit incrementally (best-effort): an error midway
        leaves earlier flushes committed, like the reference.
        """
        t0 = time.perf_counter()
        b = Batch(self)
        try:
            result = cb(b)
            b._flush()
            return result
        finally:
            b._abort()
            _BATCH_TIMER.observe(time.perf_counter() - t0)

    def _commit(self, tx: WriteTx) -> None:
        if not tx._changes:
            tx.closed = True
            return
        with self._lock:
            for change, ev in zip(tx._changes, tx._events):
                self._version += 1   # versions pre-stamped in update()
                self._apply_locked(change)
                # stamp the resume token (frozen dataclass: events are
                # immutable to consumers; the store is their minter)
                object.__setattr__(ev, "version", self._version)
                self._log_change_locked(
                    ("one", self._version, ev.action, ev.obj, ev.old), 1)
        tx.closed = True
        for ev in tx._events:
            self.queue.publish(ev)
        self.queue.publish(EventCommit(self._version))

    # -------------------------------------------------- changelog (resume)

    def _log_change_locked(self, entry: tuple, count: int) -> None:
        self._changelog.append(entry)
        self._changelog_total += count
        while self._changelog_total > self.changelog_limit \
                and len(self._changelog) > 1:
            dropped = self._changelog.popleft()
            self._changelog_total -= (1 if dropped[0] == "one"
                                      else len(dropped[2]))

    def _entry_version_range(self, entry: tuple) -> Tuple[int, int]:
        if entry[0] == "one":
            return entry[1], entry[1]
        _, base, olds, *_ = entry
        return base + 1, base + len(olds)

    def changes_between(self, from_version: int) -> List[Event]:
        """Events for every change with version > ``from_version``, in
        commit order (reference: raft.go:1617 ChangesBetween).  Raises
        InvalidStoreAction when that range was compacted out of the
        changelog (snapshot install / ring overflow) — resuming callers
        must re-list instead."""
        with self._lock:
            if from_version > self._version:
                raise InvalidStoreAction(
                    f"version {from_version} is in the future "
                    f"(store at {self._version})")
            if from_version == self._version:
                return []
            entries = list(self._changelog)
        if not entries or \
                self._entry_version_range(entries[0])[0] > from_version + 1:
            raise InvalidStoreAction(
                f"changes since version {from_version} were compacted; "
                "re-list and watch from the current version")
        out: List[Event] = []
        for entry in entries:
            lo, hi = self._entry_version_range(entry)
            if hi <= from_version:
                continue
            if entry[0] == "one":
                out.append(Event(entry[2], entry[3], entry[4],
                                 version=entry[1]))
                continue
            _, base, olds, node_ids, state, message, ts = entry
            for i, old in enumerate(olds):
                ver = base + 1 + i
                if ver <= from_version:
                    continue
                out.append(Event(
                    "update",
                    _materialize_task(old, node_ids[i], ver, ts, state,
                                      message),
                    old, version=ver))
        return out

    def watch_from(self, from_version: int, predicate=None
                   ) -> Tuple[List[Event], "Subscription"]:
        """Atomically: events missed since ``from_version`` plus a live
        subscription from the current version (reference:
        watchapi/watch.go:32 WatchFrom)."""
        with self._update_lock:
            replay = self.changes_between(from_version)
            sub = self.queue.subscribe(predicate)
        return replay, sub

    def _apply_locked(self, change: StoreAction) -> None:
        obj = change.obj
        table = self._tables[obj.collection]
        if table.overlay and obj.id in table.overlay:
            # the unindex below must see the materialized (assigned) form
            self._materialize_locked(table, obj.id)
        old = table.objects.get(obj.id)
        # name index maintenance
        if old is not None:
            oldname = _obj_name(old).lower()
            if oldname and table.by_name.get(oldname) == obj.id:
                del table.by_name[oldname]
        if change.action == "delete":
            table.objects.pop(obj.id, None)
            self._unindex(table, old if old is not None else obj)
            return
        if obj.collection != "tasks":
            name = _obj_name(obj).lower()
            if name:
                table.by_name[name] = obj.id
        if old is not None:
            self._unindex(table, old)
        table.objects[obj.id] = obj
        self._index(table, obj)

    def _index(self, table: _Table, obj: Any) -> None:
        if isinstance(obj, Task):
            if obj.service_id:
                table.by_service.setdefault(obj.service_id, {})[obj.id] = None
                table.by_slot.setdefault((obj.service_id, obj.slot), {})[obj.id] = None
            if obj.node_id:
                table.by_node.setdefault(obj.node_id, {})[obj.id] = None

    def _unindex(self, table: _Table, obj: Any) -> None:
        if isinstance(obj, Task):
            if obj.service_id:
                table.by_service.get(obj.service_id, {}).pop(obj.id, None)
                table.by_slot.get((obj.service_id, obj.slot), {}).pop(obj.id, None)
            if obj.node_id:
                table.by_node.get(obj.node_id, {}).pop(obj.id, None)

    # ------------------------------------------------------- queries (locked)

    def _predicate_for(self, kind: Type, by: By) -> Callable[[Any], bool]:
        if isinstance(by, All):
            return lambda o: True
        if isinstance(by, ByName):
            return lambda o: _obj_name(o).lower() == by.name.lower()
        if isinstance(by, ByNamePrefix):
            return lambda o: _obj_name(o).lower().startswith(by.prefix.lower())
        if isinstance(by, ByIDPrefix):
            return lambda o: o.id.startswith(by.prefix)
        if isinstance(by, ByService):
            return lambda o: getattr(o, "service_id", None) == by.service_id
        if isinstance(by, ByNode):
            return lambda o: getattr(o, "node_id", None) == by.node_id
        if isinstance(by, BySlot):
            return lambda o: (getattr(o, "service_id", None) == by.service_id
                              and getattr(o, "slot", None) == by.slot)
        if isinstance(by, ByDesiredState):
            return lambda o: o.desired_state == by.state
        if isinstance(by, ByTaskState):
            return lambda o: o.status.state == by.state
        if isinstance(by, ByRole):
            return lambda o: o.spec.desired_role == by.role
        if isinstance(by, ByMembership):
            return lambda o: o.spec.membership == by.membership
        if isinstance(by, ByReferencedSecret):
            return lambda o: by.secret_id in set(_task_secret_ids(o)) \
                if isinstance(o, Task) else False
        if isinstance(by, ByReferencedConfig):
            return lambda o: by.config_id in set(_task_config_ids(o)) \
                if isinstance(o, Task) else False
        if isinstance(by, ByReferencedNetwork):
            def net_pred(o):
                if isinstance(o, Task):
                    return by.network_id in set(_task_network_ids(o))
                if isinstance(o, Service):
                    return by.network_id in set(_service_network_ids(o))
                return False
            return net_pred
        if isinstance(by, ByVolumeGroup):
            return lambda o: o.spec.group == by.group
        if isinstance(by, ByKind):
            return lambda o: getattr(o, "kind", None) == by.kind
        if isinstance(by, ByCustom):
            return lambda o: (getattr(o, "annotations", None) or
                              o.spec.annotations).indices.get(by.index) == by.value
        if isinstance(by, Where):
            return by.pred
        if isinstance(by, Or):
            preds = [self._predicate_for(kind, b) for b in by.bys]
            return lambda o: any(p(o) for p in preds)
        raise InvalidStoreAction(f"unsupported selector {by!r}")

    def _find_locked(self, kind: Type, by: By) -> List[Any]:
        table = self._tables[kind.collection]
        # fast paths via indexes
        if kind is Task:
            ids: Optional[Dict[str, None]] = None
            if isinstance(by, ByService):
                ids = table.by_service.get(by.service_id, {})
            elif isinstance(by, ByNode):
                ids = table.by_node.get(by.node_id, {})
            elif isinstance(by, BySlot):
                ids = table.by_slot.get((by.service_id, by.slot), {})
            if ids is not None:
                if table.overlay:
                    # index-driven query: materialize only touched ids
                    return [self._materialize_locked(table, i)
                            if i in table.overlay else table.objects[i]
                            for i in ids if i in table.objects]
                return [table.objects[i] for i in ids
                        if i in table.objects]
            if table.overlay:
                # scan query: the predicate may read node_id/status
                self._materialize_all_locked(table)
        if isinstance(by, All):
            return list(table.objects.values())
        if isinstance(by, ByName) and kind.collection != "tasks":
            oid = table.by_name.get(by.name.lower())
            return [table.objects[oid]] if oid in table.objects else []
        pred = self._predicate_for(kind, by)
        return [o for o in table.objects.values() if pred(o)]

    # --------------------------------------------- columnar scheduler commits

    @contextlib.contextmanager
    def _commit_lock(self):
        """The update lock for a scheduler commit, its wait under a span
        of its own (``commit.lock_wait``): with ``commit.apply`` and
        ``commit.publish`` the three stages of ``sched.commit``."""
        with tracer.span("commit.lock_wait", "commit"):
            self._update_lock.acquire()
        try:
            yield
        finally:
            self._update_lock.release()

    def bulk_update_tasks(self, new_tasks: Sequence[Task], on_missing,
                          on_assigned,
                          guard_state: int = 192,  # TaskState.ASSIGNED
                          epoch: Optional[int] = None,
                          ) -> Tuple[List[int], List[int]]:
        """Columnar commit path for scheduler decisions (the TPU path's
        array-shaped output).  Semantically one ``batch`` of single-task
        updates (reference: memory.go:531 + scheduler.go:490), stripped of
        per-task transaction machinery; the inner loops run in C when the
        native hotpath module is available (see native/hotpath.c), with an
        identical pure-Python fallback below.

        Per-item semantics (scheduler.go:594-611 applySchedulingDecisions):

        * no stored object                -> ``on_missing(new)``, skipped;
        * status (state, message, err) unchanged -> skipped;
        * stored state >= ``guard_state`` -> ``on_assigned(new)`` returning
          False fails the item (node-version conflict path);
        * stale ``new.meta.version.index`` -> failed (SequenceConflict);
        * otherwise version-stamped and committed.

        ``new_tasks`` ownership transfers to the store — no defensive
        copies; callers must treat them as immutable afterwards (the same
        replace-don't-mutate convention stored objects already follow).
        Proposals/commits/events are chunked at MAX_CHANGES_PER_TX so each
        raft proposal stays within bounds.  StoreAction construction is
        elided with a nil proposer, Event construction when nobody is
        subscribed — both are observable only by their consumers.

        Returns (committed_indices, failed_indices); skipped items appear
        in neither.
        """
        from .. import native
        hp = native.get()
        committed_idx: List[int] = []
        failed_idx: List[int] = []
        n = len(new_tasks)
        ts = now()
        if not isinstance(new_tasks, list):
            new_tasks = list(new_tasks)
        with self._commit_lock():
            table = self._tables["tasks"]
            objects = table.objects
            if table.overlay:
                # the C prepare loop reads `objects` directly: flush the
                # lazily-committed ids it may touch
                with self._lock:
                    for t in new_tasks:
                        if t.id in table.overlay:
                            self._materialize_locked(table, t.id)
            want_actions = self._proposer is not None
            want_events = self.queue.has_subscribers()
            if want_actions and epoch is None:
                # pin every chunk of this commit to one reign: a role
                # change mid-commit fails the remaining chunks instead of
                # letting them ride the successor's epoch
                epoch = self._proposer_epoch()
            i = 0
            while i < n:
                stop = min(i + MAX_CHANGES_PER_TX, n)
                with tracer.span("commit.apply", "commit"):
                    with self._lock:
                        seq = self._version
                    if hp is not None:
                        committed, failed, stamped, actions, events = \
                            hp.commit_prepare(
                                new_tasks, i, stop, objects, seq, ts,
                                int(guard_state),
                                StoreAction if want_actions else None,
                                Event if want_events else None,
                                on_missing, on_assigned)
                    else:
                        committed, failed, stamped, actions, events = \
                            self._commit_prepare_py(
                                new_tasks, i, stop, objects, seq, ts,
                                guard_state, want_actions, want_events,
                                on_missing, on_assigned)
                    i = stop
                    failed_idx.extend(failed)
                    if not stamped:
                        continue

                    def apply_chunk(stamped=stamped):
                        with self._lock:
                            if hp is not None:
                                hp.commit_apply(stamped, objects,
                                                table.by_node,
                                                self._reindex_pair)
                            else:
                                self._commit_apply_py(stamped, table)
                            self._version += len(stamped)
                            for t in stamped:
                                # old ref elided on this path (replays
                                # carry old=None)
                                self._log_change_locked(
                                    ("one", t.meta.version.index, "update",
                                     t, None), 1)

                    if want_actions:
                        try:
                            # commit runs inside the consensus apply
                            # path (see Proposer.propose)
                            self._propose_fenced(self._proposer, actions,
                                                 apply_chunk, epoch)
                        except Exception:
                            # per-chunk failure granularity: earlier
                            # chunks are committed and stay committed;
                            # this chunk and all remaining items fail so
                            # the caller rolls back only what the store
                            # did not apply
                            log.exception("bulk task-update proposal failed")
                            failed_idx.extend(committed)
                            failed_idx.extend(range(i, n))
                            break
                    else:
                        apply_chunk()
                    committed_idx.extend(committed)
                with self._publishing() as delivered:
                    if want_events:
                        publish = self.queue.publish
                        for ev in events:
                            publish(ev, delivered)
                    self.queue.publish(EventCommit(self._version), delivered)
        return committed_idx, failed_idx

    @contextlib.contextmanager
    def _publishing(self):
        """The ``commit.publish`` span around a commit's publish.  It
        carries ``subscribers``, the queue's subscriptions, and
        ``delivered``, those that buffered at least one of the events
        published inside it (a block buffered whole for a per-item
        consumer among them): the threads the commit wakes.  Yields the
        set to hand ``Queue.publish``; None with the tracer off."""
        with tracer.span("commit.publish", "commit",
                         subscribers=self.queue.subscriber_count()) as sp:
            delivered = None if sp is None else set()
            yield delivered
            if sp is not None:
                sp.args["delivered"] = len(delivered)

    @property
    def supports_block_commit(self) -> bool:
        """True when scheduler assignments may commit as a columnar block
        (arrays end-to-end, objects materialized lazily on read) — always,
        since round 4: with live watchers the block publishes ONE coalesced
        EventTaskBlock (expanded lazily, shared, per subscriber); with a
        raft proposer it rides a compact columnar TaskBlockAction through
        consensus.  Kept as a property for callers that keyed off the old
        no-watcher/no-proposer restriction."""
        return True

    def commit_task_block(self, *args, **kwargs
                          ) -> Tuple[List[int], List[int]]:
        # timing shell only — signature, defaults, and docs live on the
        # impl so they exist in exactly one place
        with _BLOCK_COMMIT_TIMER.time():
            return self._commit_task_block_impl(*args, **kwargs)

    def _commit_task_block_impl(self, old_tasks: Sequence[Task],
                                node_ids: Sequence[str],
                                state: int, message: str,
                                on_missing, on_assigned,
                                guard_state: int = 192,
                                epoch: Optional[int] = None,
                                ) -> Tuple[List[int], List[int]]:
        """Columnar scheduler commit: assignments stay arrays end-to-end.

        Same per-item semantics as ``bulk_update_tasks`` (scheduler.go:490
        applySchedulingDecisions), but instead of installing pre-built Task
        objects it records (node_id, version, status) in the task table's
        overlay; per-task objects materialize lazily on first read.
        ``old_tasks[i]`` must be the scheduler's mirror of the stored task
        — when it is the stored instance itself (the common case; mirrors
        hold store references), validation is one identity check.

        by_node indexes update eagerly, so index-driven queries stay
        correct without materializing.  Live watchers get one coalesced
        EventTaskBlock per block (expanded to per-task events for
        subscribers that didn't opt into blocks); with a proposer the
        block is validated first, then proposed as chunked columnar
        TaskBlockActions and applied in the consensus apply path
        (reference: raft.go:1592 ProposeValue + wait.trigger).

        Returns (committed_indices, failed_indices); skipped items appear
        in neither.
        """
        from .. import native
        from ..models.types import TaskState
        if int(state) > int(TaskState.RUNNING):
            # contract block-aware consumers rely on: blocks carry
            # scheduler placement transitions only (state<=RUNNING), so
            # restart/reconcile/reaper loops may skip them wholesale —
            # failure and terminal states must go through per-object paths
            raise InvalidStoreAction(
                f"task blocks carry states <= RUNNING, got {state}")
        ts = now()
        committed_idx: List[int] = []
        failed_idx: List[int] = []
        missing: List[Tuple[Task, str]] = []
        if not isinstance(old_tasks, list):
            old_tasks = list(old_tasks)
        if not isinstance(node_ids, list):
            node_ids = list(node_ids)
        if self._proposer is not None:
            return self._commit_task_block_proposed(
                old_tasks, node_ids, int(state), message,
                on_missing, on_assigned, int(guard_state), ts,
                epoch=epoch)
        with self._commit_lock():
            with tracer.span("commit.apply", "commit"):
                table = self._tables["tasks"]
                objects = table.objects
                overlay = table.overlay
                by_node = table.by_node
                hp = native.get()
                with self._lock:
                    seq = self._version
                    # slow-path index updates batch into ONE pass per chunk
                    # (_batch_index_tasks) — runs in the finally so an
                    # overlay entry can never outlive its index update
                    pend_index: List[Tuple[str, str, str]] = []
                    try:
                        slow: Sequence[int] = range(len(old_tasks))
                        if hp is not None:
                            fast, slow, seq = hp.block_commit(
                                old_tasks, node_ids, objects, overlay,
                                by_node, ts, int(state), message, seq,
                                int(guard_state))
                            committed_idx.extend(fast)
                        for i in slow:
                            old = old_tasks[i]
                            tid = old.id
                            cur = objects.get(tid)
                            if cur is not old or tid in overlay:
                                # mirror is not the stored instance: run the
                                # full bulk-path checks against the stored one
                                if cur is not None and tid in overlay:
                                    cur = self._materialize_locked(table, tid)
                                if cur is None:
                                    # callbacks run after the loop: an
                                    # exception here must not strand
                                    # committed versions (see finally)
                                    missing.append((old, node_ids[i]))
                                    continue
                                cs = cur.status
                                if cs.state == state \
                                        and cs.message == message:
                                    continue
                                if cs.state >= guard_state and \
                                        not on_assigned(old, node_ids[i]):
                                    failed_idx.append(i)
                                    continue
                                if cur.meta.version.index != \
                                        old.meta.version.index:
                                    failed_idx.append(i)
                                    continue
                            elif cur.status.state >= guard_state and \
                                    not on_assigned(old, node_ids[i]):
                                failed_idx.append(i)
                                continue
                            seq += 1
                            nid = node_ids[i]
                            overlay[tid] = (nid, seq, ts, state, message)
                            pend_index.append((tid, old.node_id, nid))
                            committed_idx.append(i)
                    finally:
                        self._batch_index_tasks(by_node, pend_index)
                        # already-written overlay entries carry versions up to
                        # seq — the counter must advance past them even if a
                        # callback raised, or the next commit would reissue
                        # duplicate version indices
                        base = self._version
                        self._version = seq
                        olds_c = nids_c = None
                        if committed_idx:
                            # one columnar changelog entry for the whole
                            # block: replay materializes per-task lazily.
                            # Version order within the block matches commit
                            # order (fast-path items first, then slow).
                            olds_c = [old_tasks[i] for i in committed_idx]
                            nids_c = [node_ids[i] for i in committed_idx]
                            self._log_change_locked(
                                ("block", base, olds_c, nids_c,
                                 int(state), message, ts),
                                len(committed_idx))
            with self._publishing() as delivered:
                if olds_c and self.queue.has_subscribers():
                    # one coalesced event for the whole block; per-task
                    # events synthesize lazily, shared across subscribers
                    self.queue.publish(EventTaskBlock(
                        olds_c, nids_c, base, int(state), message, ts),
                        delivered)
                self.queue.publish(EventCommit(self._version), delivered)
        for old, nid in missing:
            on_missing(old, nid)
        return committed_idx, failed_idx

    #: items per columnar raft proposal — ~25B/item serialized (joined
    #: ids + node RLE) keeps each entry under ~1MB, inside the
    #: reference's 1.5MB tx bound (memory.go:45-51)
    BLOCK_PROPOSAL_MAX_ITEMS = 32768

    def _commit_task_block_proposed(self, old_tasks: List[Task],
                                    node_ids: List[str], state: int,
                                    message: str, on_missing, on_assigned,
                                    guard_state: int, ts: float,
                                    epoch: Optional[int] = None,
                                    ) -> Tuple[List[int], List[int]]:
        """Block commit through the consensus seam: validate every item
        against the current store (no writes), stamp versions, then ride
        chunked columnar TaskBlockActions through the proposer — the
        overlay/index writes run inside the consensus apply path, exactly
        like ``update``'s commit callback, so snapshots taken at an
        applied index always include that index's changes.  Chunk failure
        granularity matches ``bulk_update_tasks``: committed chunks stay
        committed, the failing chunk and everything after fail.  All
        chunks are pinned to one leadership epoch (``epoch``, default:
        the proposer's at entry): a role change mid-commit fences the
        remaining chunks at the proposer instead of racing it."""
        from .. import native
        hp = native.get()
        if epoch is None:
            epoch = self._proposer_epoch()
        committed_idx: List[int] = []
        failed_idx: List[int] = []
        missing: List[Tuple[Task, str]] = []
        with self._commit_lock():
            table = self._tables["tasks"]
            objects = table.objects
            overlay = table.overlay
            by_node = table.by_node
            with tracer.span("commit.apply", "commit", stage="validate"):
                with self._lock:
                    base = self._version
                    if hp is not None:
                        fast, slow = hp.block_validate(
                            old_tasks, node_ids, objects, overlay,
                            int(guard_state))
                        # all-fast blocks keep the range lazy (no 100k-int
                        # list); slow leftovers force a mutable list
                        accepted = list(fast) if slow else fast
                    else:
                        accepted = []
                        slow = range(len(old_tasks))
                    for i in slow:
                        old = old_tasks[i]
                        tid = old.id
                        cur = objects.get(tid)
                        if cur is not old or tid in overlay:
                            # mirror is not the stored instance: full checks
                            # against the stored one (bulk-path semantics)
                            if cur is not None and tid in overlay:
                                cur = self._materialize_locked(table, tid)
                            if cur is None:
                                missing.append((old, node_ids[i]))
                                continue
                            cs = cur.status
                            if cs.state == state and cs.message == message:
                                continue
                            if cs.state >= guard_state and \
                                    not on_assigned(old, node_ids[i]):
                                failed_idx.append(i)
                                continue
                            if cur.meta.version.index != \
                                    old.meta.version.index:
                                failed_idx.append(i)
                                continue
                        elif cur.status.state >= guard_state and \
                                not on_assigned(old, node_ids[i]):
                            failed_idx.append(i)
                            continue
                        accepted.append(i)
            # ---- chunked proposals, optionally pipelined.  With a
            # proposer exposing propose_async/wait_proposal and
            # pipeline_depth > 1, up to ``window`` chunk proposals ride
            # consensus at once: chunk i+1 serializes and persists while
            # chunk i is being applied.  Ordering is preserved because
            # same-thread proposals append to the raft log in submission
            # order and apply callbacks run in log order; the caller is
            # only acked (this method returns) after every chunk
            # resolved.  window=1 / missing async API degrades to the
            # strictly serial propose->wait-per-chunk behavior.
            proposer = self._proposer
            window = max(1, self.pipeline_depth)
            can_async = (window > 1
                         and hasattr(proposer, "propose_async")
                         and hasattr(proposer, "wait_proposal"))
            pending: deque = deque()

            def reap(entry) -> bool:
                chunk, olds_c, nids_c, cb_base, waiter = entry
                try:
                    with tracer.span("commit.apply", "commit",
                                     stage="wait"):
                        proposer.wait_proposal(waiter)
                except Exception:
                    log.exception("columnar block proposal failed")
                    failed_idx.extend(chunk)
                    return False
                committed_idx.extend(chunk)
                if self.queue.has_subscribers():
                    with self._publishing() as delivered:
                        self.queue.publish(EventTaskBlock(
                            olds_c, nids_c, cb_base, state, message, ts),
                            delivered)
                return True

            pos = 0
            chunk_base = base
            n_acc = len(accepted)
            # a failed submit/commit fails the chunk and everything
            # after it (committed chunks stay committed) — same
            # granularity as bulk_update_tasks; chunks already in
            # flight when a failure surfaces resolve by their own
            # waiter (a later chunk cannot commit unless every earlier
            # one did, so results stay consistent with the log)
            ok_to_submit = True
            while pos < n_acc:
                chunk = accepted[pos:pos + self.BLOCK_PROPOSAL_MAX_ITEMS]
                pos += len(chunk)
                if not ok_to_submit:
                    failed_idx.extend(chunk)
                    continue
                # one materialization of the chunk's columns, shared by
                # the action, the changelog entry, and the block event
                olds_c = [old_tasks[i] for i in chunk]
                nids_c = [node_ids[i] for i in chunk]
                action = TaskBlockAction(
                    "task_block", tuple(t.id for t in olds_c),
                    tuple(nids_c), chunk_base, state, message, ts)

                def apply_chunk(chunk=chunk, chunk_base=chunk_base,
                                olds_c=olds_c, nids_c=nids_c):
                    with self._lock:
                        if hp is not None:
                            seq = hp.block_apply(
                                old_tasks, node_ids, chunk, overlay,
                                by_node, ts, state, message, chunk_base)
                        else:
                            seq = chunk_base
                            pend_index = []
                            for i in chunk:
                                seq += 1
                                old = old_tasks[i]
                                tid = old.id
                                nid = node_ids[i]
                                overlay[tid] = (nid, seq, ts, state,
                                                message)
                                pend_index.append((tid, old.node_id, nid))
                            # one batched index pass per chunk
                            self._batch_index_tasks(by_node, pend_index)
                        self._version = seq
                        self._log_change_locked(
                            ("block", chunk_base, olds_c, nids_c,
                             state, message, ts),
                            len(chunk))

                if can_async:
                    try:
                        if epoch is None:
                            # legacy 2-arg proposers have no fencing
                            # swarmlint: disable=epoch-fencing
                            waiter = proposer.propose_async([action],
                                                            apply_chunk)
                        else:
                            waiter = proposer.propose_async(
                                [action], apply_chunk, epoch=epoch)
                    except Exception:
                        log.exception("columnar block proposal failed")
                        failed_idx.extend(chunk)
                        ok_to_submit = False
                        continue
                    pending.append((chunk, olds_c, nids_c, chunk_base,
                                    waiter))
                    if len(pending) >= window \
                            and not reap(pending.popleft()):
                        ok_to_submit = False
                else:
                    try:
                        with tracer.span("commit.apply", "commit",
                                         stage="propose"):
                            self._propose_fenced(proposer, [action],
                                                 apply_chunk, epoch)
                    except Exception:
                        log.exception("columnar block proposal failed")
                        failed_idx.extend(chunk)
                        ok_to_submit = False
                        continue
                    committed_idx.extend(chunk)
                    if self.queue.has_subscribers():
                        with self._publishing() as delivered:
                            self.queue.publish(EventTaskBlock(
                                olds_c, nids_c, chunk_base, state,
                                message, ts), delivered)
                chunk_base += len(chunk)
            while pending:
                reap(pending.popleft())
            with self._publishing() as delivered:
                self.queue.publish(EventCommit(self._version), delivered)
        for old, nid in missing:
            on_missing(old, nid)
        return committed_idx, failed_idx

    def _reindex_pair(self, old: Task, new: Task) -> None:
        table = self._tables["tasks"]
        self._unindex(table, old)
        self._index(table, new)

    def _commit_prepare_py(self, new_tasks, start, stop, objects, seq, ts,
                           guard_state, want_actions, want_events,
                           on_missing, on_assigned):
        """Pure-Python mirror of native commit_prepare (and the
        differential-test oracle for it)."""
        committed: List[int] = []
        failed: List[int] = []
        stamped: List[Task] = []
        actions: List[StoreAction] = []
        events: List[Event] = []
        for i in range(start, stop):
            new = new_tasks[i]
            cur = objects.get(new.id)
            if cur is None:
                on_missing(new)
                continue
            cs, ns = cur.status, new.status
            if (cs.state == ns.state and cs.message == ns.message
                    and cs.err == ns.err):
                continue
            if cs.state >= guard_state and not on_assigned(new):
                failed.append(i)
                continue
            if cur.meta.version.index != new.meta.version.index:
                failed.append(i)
                continue
            seq += 1
            m = new.meta
            m.version.index = seq
            m.created_at = cur.meta.created_at
            m.updated_at = ts
            committed.append(i)
            stamped.append(new)
            if want_actions:
                actions.append(StoreAction("update", new))
            if want_events:
                events.append(Event("update", new, cur))
        return committed, failed, stamped, actions, events

    def _commit_apply_py(self, stamped: List[Task], table: _Table) -> None:
        """Pure-Python apply for ``bulk_update_tasks``.  by_node index
        writes batch through ``_batch_index_tasks`` — ONE pass per
        chunk, like the block-commit paths — instead of a dict
        probe-and-pop per task.  Order preservation: the pending batch
        flushes BEFORE any item that takes the full ``_unindex``/
        ``_index`` route (a service/slot change also touches by_node),
        so every bucket still receives ids in exactly per-item commit
        order — the insertion-ordered ``{id: None}`` contract."""
        objects = table.objects
        by_node = table.by_node
        pend_index: List[Tuple[str, str, str]] = []
        for obj in stamped:
            old = objects.get(obj.id)
            objects[obj.id] = obj
            if old is None:
                continue
            if old.service_id != obj.service_id or old.slot != obj.slot:
                if pend_index:
                    self._batch_index_tasks(by_node, pend_index)
                    pend_index = []
                self._unindex(table, old)
                self._index(table, obj)
            elif old.node_id != obj.node_id:
                pend_index.append((obj.id, old.node_id, obj.node_id))
        if pend_index:
            self._batch_index_tasks(by_node, pend_index)

    # --------------------------------------------------- raft follower replay

    def apply_store_actions(self, actions: Sequence[StoreAction]) -> None:
        """Apply replicated actions without re-proposing
        (reference: memory.go:280).  Columnar TaskBlockActions apply
        straight into the task overlay — followers converge on the same
        lazy-materialization shape the leader committed."""
        events: List[Any] = []
        with self._update_lock:
            with self._lock:
                for change in actions:
                    if change.action == "task_block":
                        ev = self._apply_task_block_locked(change)
                        if isinstance(ev, list):
                            events.extend(ev)
                        elif ev is not None:
                            events.append(ev)
                        continue
                    obj = change.obj.copy()
                    old = self._tables[obj.collection].objects.get(obj.id)
                    if change.action == "create":
                        events.append(Event("create", obj))
                    elif change.action == "update":
                        events.append(Event("update", obj, old))
                    else:
                        events.append(Event("delete", old if old is not None else obj))
                    # The leader's _commit advances _version once per change
                    # (including deletes, whose payload carries the *old*
                    # object version) — mirror that exactly so follower
                    # EventCommit indices and post-failover version counters
                    # match the leader's.
                    if change.action == "delete":
                        self._version += 1
                    else:
                        self._version = max(self._version + 1,
                                            obj.meta.version.index)
                    self._apply_locked(StoreAction(change.action, obj))
                    ev = events[-1]
                    # follower-side resume tokens must match the leader's
                    # stamping bit-for-bit (same version counter flow)
                    object.__setattr__(ev, "version", self._version)
                    self._log_change_locked(
                        ("one", self._version, ev.action, ev.obj, ev.old),
                        1)
            for ev in events:
                self.queue.publish(ev)
            self.queue.publish(EventCommit(self._version))

    @staticmethod
    def _batch_index_tasks(by_node: Dict[str, Dict[str, None]],
                           triples) -> None:
        """One by_node index pass per committed chunk: ``triples`` is an
        iterable of (task_id, old_node_id, new_node_id) in commit order.
        Consecutive same-node placements (the planner emits them sorted
        by node) share one bucket lookup; buckets stay insertion-ordered
        ``{id: None}`` dicts and receive ids in exactly the order the
        per-item loops would have inserted them — the PR 8 determinism
        contract."""
        last_nid: Optional[str] = None
        bucket: Optional[Dict[str, None]] = None
        for tid, old_nid, nid in triples:
            if old_nid and old_nid != nid:
                b = by_node.get(old_nid)
                if b is not None:
                    b.pop(tid, None)
            if nid != last_nid:
                last_nid = nid
                if nid:
                    bucket = by_node.get(nid)
                    if bucket is None:
                        bucket = by_node[nid] = {}
                else:
                    bucket = None
            if bucket is not None:
                bucket[tid] = None

    def _apply_task_block_locked(self, action: "TaskBlockAction"):
        """Apply one replicated columnar block (caller holds both locks).
        Uses the leader's version numbering (base+1..base+n) so overlay
        entries converge bit-for-bit.  Returns one event to publish (an
        EventTaskBlock normally, a list of per-item Events if ids were
        skipped), or None when nothing resolved.

        The healthy-log case (every id stored, none overlaid) runs as
        one native pass — overlay writes plus a batched by_node index
        pass per chunk (hotpath.c block_apply_follower); the Python loop
        below is the fallback and the oracle, and the only path that can
        handle diverged/overlaid ids."""
        from .. import native
        table = self._tables["tasks"]
        objects = table.objects
        overlay = table.overlay
        by_node = table.by_node
        state, message, ts = action.state, action.message, action.ts
        hp = native.get_commit()
        if hp is not None:
            olds = hp.block_apply_follower(
                action.ids, action.node_ids, objects, overlay, by_node,
                ts, state, message, action.base_version)
            if olds is not None:
                self._version = max(
                    self._version, action.base_version + len(action.ids))
                if not olds:
                    return None
                nids = list(action.node_ids)
                self._log_change_locked(
                    ("block", action.base_version, olds, nids, state,
                     message, ts), len(olds))
                return EventTaskBlock(olds, nids, action.base_version,
                                      state, message, ts)
        applied: List[Tuple[Task, str, int]] = []
        for j, (tid, nid) in enumerate(zip(action.ids, action.node_ids)):
            cur = objects.get(tid)
            if cur is not None and tid in overlay:
                cur = self._materialize_locked(table, tid)
            if cur is None:
                # diverged follower (should not happen with a healthy
                # log): the leader still burned this version index
                continue
            ver = action.base_version + 1 + j
            overlay[tid] = (nid, ver, ts, state, message)
            applied.append((cur, nid, ver))
        self._batch_index_tasks(
            by_node,
            ((cur.id, cur.node_id, nid) for cur, nid, _v in applied))
        self._version = max(self._version,
                            action.base_version + len(action.ids))
        if not applied:
            return None
        if len(applied) == len(action.ids):
            # versions are contiguous from base: block changelog entry +
            # block event (both stamp versions as base+1+i)
            olds = [a[0] for a in applied]
            nids = [a[1] for a in applied]
            self._log_change_locked(
                ("block", action.base_version, olds, nids, state,
                 message, ts), len(applied))
            return EventTaskBlock(olds, nids, action.base_version,
                                  state, message, ts)
        # skipped ids broke contiguity: log/publish per item with exact
        # versions so changelog replay and events stamp correctly
        events: List[Event] = []
        for old, nid, ver in applied:
            ev = Event("update",
                       _materialize_task(old, nid, ver, ts, state,
                                         message), old, version=ver)
            self._log_change_locked(
                ("one", ver, "update", ev.obj, ev.old), 1)
            events.append(ev)
        return events

    def save(self) -> Dict[str, Any]:
        """Full-store snapshot (reference: snapshot.proto StoreSnapshot)."""
        with self._lock:
            self._materialize_all_locked(self._tables["tasks"])
            return {
                "version": self._version,
                "tables": {
                    coll: [o.copy() for o in t.objects.values()]
                    for coll, t in self._tables.items()
                },
            }

    def restore(self, snapshot: Dict[str, Any]) -> None:
        with self._update_lock:
            with self._lock:
                for coll in self._tables:
                    self._tables[coll] = _Table()
                for coll, objs in snapshot["tables"].items():
                    table = self._tables[coll]
                    for o in objs:
                        cp = o.copy()
                        table.objects[cp.id] = cp
                        self._index(table, cp)
                        if coll != "tasks":
                            name = _obj_name(cp).lower()
                            if name:
                                table.by_name[name] = cp.id
                self._version = snapshot.get("version", 0)
                # resume continuity is lost across a snapshot install:
                # watch-from callers see "compacted" and must re-list
                self._changelog.clear()
                self._changelog_total = 0
            self.queue.publish(EventSnapshotRestore())

    def save_bytes(self) -> bytes:
        """Deterministic snapshot bytes (raft snapshot transfer / disk)."""
        from . import serde
        return serde.snapshot_to_bytes(self.save())

    def restore_bytes(self, data: bytes) -> None:
        from . import serde
        self.restore(serde.snapshot_from_bytes(data))

    @property
    def version(self) -> int:
        with self._lock:
            return self._version

    def close(self) -> None:
        self.queue.close()


class Batch:
    """Accumulates updates in one open transaction, committing whenever the
    staged *change count* reaches MAX_CHANGES_PER_TX — the bound a single
    raft proposal must respect (reference: memory.go:45-51, :531).

    Callbacks run immediately against the open transaction; the writer lock
    is held from the first update until the enclosing ``store.batch`` call
    returns (flush or abort).
    """

    def __init__(self, store: MemoryStore):
        self._store = store
        self._tx: Optional[WriteTx] = None
        self.applied = 0    # callbacks run
        self.committed = 0  # changes committed
        self.flushes = 0    # transactions committed
        self._staged_bytes = 0   # serialized size of staged changes
        self._measured = 0       # changes already size-accounted

    def update(self, cb: Callable[[WriteTx], Any]) -> Any:
        if self._tx is None:
            self._store._update_lock.acquire()
            self._tx = WriteTx(self._store)
        result = cb(self._tx)
        self.applied += 1
        changes = self._tx._changes
        if self._store._proposer is not None:
            # size-account only the changes staged since the last
            # callback; each serializes once here, exactly as it will on
            # the raft wire.  Proposer-less stores skip this — the byte
            # bound exists to cap a single raft proposal, and paying
            # O(serialized bytes) per local batch would tax every
            # orchestrator batch for nothing.
            while self._measured < len(changes):
                from . import serde
                self._staged_bytes += len(serde.dumps(
                    serde.action_to_dict(changes[self._measured])))
                self._measured += 1
        if len(changes) >= MAX_CHANGES_PER_TX \
                or self._staged_bytes >= MAX_TX_BYTES:
            self._flush_tx()
        return result

    def _flush_tx(self) -> None:
        tx, self._tx = self._tx, None
        self._staged_bytes = 0
        self._measured = 0
        try:
            n = len(tx._changes)
            self._store._propose_and_commit(tx)
            self.committed += n
            self.flushes += 1
        finally:
            self._store._update_lock.release()

    def _flush(self) -> None:
        if self._tx is not None:
            self._flush_tx()

    def _abort(self) -> None:
        """Discard any uncommitted tail (after an error) and release."""
        if self._tx is not None:
            self._tx = None
            self._staged_bytes = 0
            self._measured = 0
            self._store._update_lock.release()
