"""Dispatcher: the worker-facing control channel.

Reference: manager/dispatcher/dispatcher.go, assignments.go, nodes.go,
heartbeat/heartbeat.go.

Responsibilities (matching the reference):

* ``register``      — session creation for a known node; marks node READY
  (dispatcher.go:553).
* ``heartbeat``     — TTL refresh with ±epsilon jitter; expiry marks the
  node DOWN (dispatcher.go:1317, :29-34).
* ``open_assignments`` — a stream of COMPLETE + INCREMENTAL assignment
  diffs (tasks >= ASSIGNED on the node, plus referenced secrets/configs),
  at most 100 modifications a message, every node's from one fan-out
  flushed each ``process_updates_interval`` (dispatcher.go:1013,
  assignments.go).
* ``update_task_status`` — validated, batched status writeback; status only
  moves forward (dispatcher.go:607, :726).
* down-node tracking — nodes DOWN longer than ``orphan_timeout`` get their
  tasks moved to ORPHANED so resources free up (dispatcher.go:52, :1209).

Transport: in-process method calls shaped like the gRPC surface (register /
session stream / assignments stream / unary status updates) so a network
transport can wrap this object 1:1.  All timers (heartbeat TTLs, orphan
deadlines, status-update batching) and the assignments fan-out's flushes
run on one worker thread.
"""

from __future__ import annotations

import dataclasses
import heapq
import logging
import random
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Set, Tuple

from ..models.objects import Cluster, Config, Node, Secret, Task, Volume
from ..models.types import NodeState, NodeStatus, TaskState, TaskStatus, now
from ..obs import planes as _planes
from ..obs.journey import journeys as _journeys
from ..obs.trace import tracer
from ..state import serde as _serde
from ..state.events import Event, EventSnapshotRestore, EventTaskBlock
from ..state.store import Batch, ByNode, MemoryStore
from ..state.watch import Closed
from ..utils import new_id
from ..utils.metrics import registry as _metrics

log = logging.getLogger("dispatcher")


@dataclass
class Config_:
    """reference: dispatcher.go:29-53 DefaultConfig."""

    heartbeat_period: float = 5.0
    heartbeat_epsilon: float = 0.5
    grace_multiplier: float = 3.0
    rate_limit_period: float = 8.0
    process_updates_interval: float = 0.100
    max_batch_items: int = 100
    modification_batch_limit: int = 100
    orphan_timeout: float = 24 * 3600.0
    # --- overload protection (backpressure plane).  All defaults keep
    # classic behavior; bounds opt in per deployment.
    #: hard admission bound on concurrent sessions; register() beyond it
    #: sheds with ErrOverloaded (counted, client retries under backoff)
    max_sessions: Optional[int] = None
    #: session count beyond which the heartbeat period stretches
    #: linearly (leader tells agents to slow down); 0 disables
    hb_stretch_start: int = 0
    #: cap on the stretch factor
    hb_stretch_max: float = 4.0
    #: bound on buffered task-status updates; an update batch that would
    #: overflow it is shed with ErrOverloaded (counted, client re-sends)
    max_pending_updates: Optional[int] = None
    #: per-node assignment-set bound on retained TERMINAL tasks; beyond
    #: it the oldest terminal entries are compacted out (counted) as
    #: explicit "remove" changes — memory stays O(assigned tasks)
    max_terminal_tasks: Optional[int] = None


DefaultConfig = Config_


class DispatcherError(Exception):
    #: wire error code (net/server.py passes it through verbatim, so the
    #: agent-side failover client can classify without importing manager)
    code = "dispatcher"


class ErrNodeNotFound(DispatcherError):
    code = "not_found"


class ErrSessionInvalid(DispatcherError):
    code = "session_invalid"


class ErrNodeNotRegistered(DispatcherError):
    code = "node_not_registered"


class ErrRateLimited(DispatcherError):
    """Node re-registered too often (reference: nodes.go:90
    CheckRateLimit — at most RATE_LIMIT_COUNT registrations per
    rate_limit_period)."""


class ErrOverloaded(DispatcherError):
    """Backpressure shed at the RPC edge: the dispatcher is at a
    configured bound (sessions or status buffer).  Degraded, never
    silently lossy — every shed is counted in ``swarm_plane_drops``
    and the client re-queues under its existing jittered backoff."""
    code = "overloaded"


RATE_LIMIT_COUNT = 3   # reference: nodes.go:14


@dataclass
class _RegisteredNode:
    node_id: str
    session_id: str
    deadline: float = 0.0
    #: end of the window PROMISED to the agent (stretched period ×
    #: grace) — an expiry firing before it is a premature expiration,
    #: the bug heartbeat-liveness-under-stretch exists to catch
    promised_until: float = 0.0
    registered_at: float = field(default_factory=now)
    attempts: int = 0
    streams: List["AssignmentStream"] = field(default_factory=list)


class AssignmentsMessage:
    """One batch of assignment changes (reference: api/dispatcher.proto)."""

    COMPLETE = "complete"
    INCREMENTAL = "incremental"

    __slots__ = ("type", "applies_to", "results_in", "changes")

    def __init__(self, type_, applies_to, results_in, changes):
        self.type = type_
        self.applies_to = applies_to
        self.results_in = results_in
        self.changes = changes  # list of (action, kind, obj)


def _note_assignment_ship(msg: "AssignmentsMessage") -> None:
    """Per-shipped-batch observability: the ``assigned_sent`` journey
    milestone for every task update in the batch (the one leader-local
    milestone — delivery is not replicated state) and the serialized
    size of the batch as the assignment-set bytes gauge."""
    nbytes = 0
    for change in msg.changes:
        action, kind, obj = change
        if kind == "task" and action == "update":
            _journeys.note_sent(obj.id)
        try:
            nbytes += len(_serde.dumps(obj))
        except Exception:
            pass   # unserializable stub: size stays an estimate
    _metrics.gauge("swarm_dispatcher_assignment_set_bytes",
                   float(nbytes))


class AssignmentStream:
    """Server-side push stream of AssignmentsMessage, one per Assignments
    call; the dispatcher's fan-out feeds it."""

    def __init__(self, node_id: str):
        self.node_id = node_id
        self._buf: List[AssignmentsMessage] = []
        self._cond = threading.Condition()
        self._closed = False
        self.error: Optional[Exception] = None

    def _push(self, msg: AssignmentsMessage) -> None:
        with self._cond:
            if self._closed:
                return
            self._buf.append(msg)
            self._cond.notify()

    def get(self, timeout: Optional[float] = None) -> AssignmentsMessage:
        with self._cond:
            if not self._buf and not self._closed:
                self._cond.wait(timeout)
            if self._buf:
                return self._buf.pop(0)
            if self._closed:
                raise Closed()
            raise TimeoutError()

    def close(self, error: Optional[Exception] = None) -> None:
        with self._cond:
            self.error = error
            self._closed = True
            self._cond.notify_all()

    @property
    def closed(self) -> bool:
        with self._cond:
            return self._closed


class _AssignmentSet:
    """Tracks what a node currently knows and computes diffs
    (reference: assignments.go newAssignmentSet)."""

    def __init__(self, node_id: str, driver_provider=None,
                 terminal_bound: Optional[int] = None,
                 on_compact: Optional[Callable[[int], None]] = None):
        self.node_id = node_id
        self.driver_provider = driver_provider
        #: bound on retained terminal (> RUNNING) tasks; beyond it the
        #: oldest are compacted out as explicit "remove" changes so the
        #: set stays O(assigned tasks) under churn
        self.terminal_bound = terminal_bound
        self.on_compact = on_compact
        self.compactions = 0
        self._terminal: Dict[str, None] = {}   # insertion-ordered ids
        self.tasks: Dict[str, Task] = {}
        self.deps_use: Dict[Tuple[str, str], Set[str]] = {}  # (kind,id)->task ids
        self.changes: Dict[Tuple[str, str], tuple] = {}
        # driver-backed secrets marked DoNotReuse get task-specific ids
        # (reference: assignments.go assignSecret): (task_id, secret_id)
        # -> combined assignment id
        self._secret_alias: Dict[Tuple[str, str], str] = {}
        # tasks whose driver-secret fetch failed; the fan-out's flush
        # retries them until the provider recovers
        self.pending_secret_retry: Set[str] = set()

    # --- dependencies

    _DEP_TYPES = {"secret": Secret, "config": Config, "volume": Volume}

    def _task_deps(self, t: Task) -> List[Tuple[str, str]]:
        deps = []
        c = t.spec.container
        if c is not None:
            for ref in c.secrets:
                deps.append(("secret", ref.secret_id))
            for ref in c.configs:
                deps.append(("config", ref.config_id))
        # CSI volume attachments are worker dependencies too: the agent's
        # node-volumes manager stages/publishes them before the task runs
        # (reference: assignments.go volumes + agent/csi/volumes.go)
        for va in t.volumes:
            deps.append(("volume", va.id))
        return deps

    def _add_task_deps(self, tx, t: Task) -> None:
        for key in self._task_deps(t):
            kind, obj_id = key
            if kind == "secret":
                self._assign_secret(tx, t, obj_id)
                continue
            users = self.deps_use.setdefault(key, set())
            if not users:
                obj = tx.get(self._DEP_TYPES[kind], obj_id)
                if obj is not None:
                    self.changes[key] = ("update", kind, obj)
            users.add(t.id)

    def _assign_secret(self, tx, t: Task, secret_id: str) -> None:
        """Plain secrets ship the stored object; driver-backed secrets
        fetch their value from the provider plugin, and DoNotReuse values
        get a task-specific id so different tasks can receive different
        values (reference: assignments.go assignSecret + drivers/)."""
        alias = self._secret_alias.get((t.id, secret_id))
        if alias is not None:
            # re-add of a task whose task-specific secret already shipped
            self.deps_use.setdefault(("secret", alias), set()).add(t.id)
            return
        base_key = ("secret", secret_id)
        if self.deps_use.get(base_key):
            # already shipped under its own id (plain, or driver-fetched
            # reusable) — don't re-fetch the value per additional task
            self.deps_use[base_key].add(t.id)
            return
        obj = tx.get(Secret, secret_id)
        key = base_key
        if obj is not None and obj.spec.driver is not None \
                and obj.spec.driver.name:
            if self.driver_provider is None:
                log.warning("secret %s needs driver %r but no provider "
                            "is registered; assignment skipped",
                            secret_id[:8], obj.spec.driver.name)
                return
            try:
                d = self.driver_provider.new_secret_driver(obj.spec.driver)
                value, no_reuse = d.get(obj.spec, t)
            except Exception:
                # fetch errors skip the assignment; the fan-out's flush
                # retries, so the task (shipped without its secret, hence
                # PREPARING) recovers with the provider
                log.exception("fetching driver secret %s failed",
                              secret_id[:8])
                self.pending_secret_retry.add(t.id)
                return
            obj = obj.copy()
            obj.spec.data = value
            if no_reuse:
                combined = f"{secret_id}.{t.id}"
                obj.id = combined
                obj.internal = True
                self._secret_alias[(t.id, secret_id)] = combined
                key = ("secret", combined)
        users = self.deps_use.setdefault(key, set())
        if not users and obj is not None:
            self.changes[key] = ("update", "secret", obj)
        users.add(t.id)

    def retry_pending_secrets(self, tx) -> bool:
        """Re-attempt driver-secret fetches that failed earlier; returns
        True when a retry shipped something new."""
        n_before = len(self.changes)
        for tid in list(self.pending_secret_retry):
            self.pending_secret_retry.discard(tid)
            t = self.tasks.get(tid)
            if t is not None:
                self._add_task_deps(tx, t)
        return len(self.changes) > n_before

    def _release_task_deps(self, t: Task) -> bool:
        modified = False
        self.pending_secret_retry.discard(t.id)
        for key in self._task_deps(t):
            kind, obj_id = key
            if kind == "secret":
                alias = self._secret_alias.pop((t.id, obj_id), None)
                if alias is not None:
                    key = ("secret", alias)
            users = self.deps_use.get(key)
            if users is None:
                continue
            users.discard(t.id)
            if not users:
                del self.deps_use[key]
                kind, obj_id = key
                stub = self._DEP_TYPES[kind](id=obj_id)
                self.changes[key] = ("remove", kind, stub)
                modified = True
        return modified

    def update_volume(self, v: Volume) -> bool:
        """Forward updates of a tracked volume (publish context changes
        etc.) to the node (reference: assignments.go addOrUpdateVolume)."""
        if ("volume", v.id) not in self.deps_use:
            return False
        self.changes[("volume", v.id)] = ("update", "volume", v)
        return True

    # --- tasks

    def add_or_update_task(self, tx, t: Task) -> bool:
        # only tasks ASSIGNED or higher concern the agent
        if t.status.state < TaskState.ASSIGNED:
            return False
        old = self.tasks.get(t.id)
        if old is not None:
            # states <= ASSIGNED are manager-set and must always be sent;
            # above that, skip sends when nothing the agent cares about
            # changed (reference: assignments.go:268)
            if (t.status.state > TaskState.ASSIGNED
                    and old.desired_state == t.desired_state
                    and old.spec is t.spec
                    and old.node_id == t.node_id):
                self.tasks[t.id] = t
                if t.status.state > TaskState.RUNNING:
                    modified = self._release_task_deps(t)
                    return self._note_terminal(t) or modified
                return False
        elif t.status.state <= TaskState.RUNNING:
            self._add_task_deps(tx, t)
        self.tasks[t.id] = t
        self.changes[("task", t.id)] = ("update", "task", t)
        self._note_terminal(t)
        return True

    def _note_terminal(self, t: Task) -> bool:
        """Track terminal (> RUNNING) tasks in arrival order and compact
        the oldest beyond ``terminal_bound`` as explicit "remove"
        changes: the agent forgets them a little early (it would on the
        reaper's delete anyway) and set memory stays O(assigned tasks)
        under churn instead of O(task history)."""
        if t.status.state <= TaskState.RUNNING:
            return False
        self._terminal.setdefault(t.id, None)
        bound = self.terminal_bound
        if bound is None or len(self._terminal) <= bound:
            return False
        evicted = 0
        while len(self._terminal) > bound:
            tid = next(iter(self._terminal))
            del self._terminal[tid]
            old = self.tasks.pop(tid, None)
            if old is not None:
                self._release_task_deps(old)
                self.changes[("task", tid)] = ("remove", "task",
                                               Task(id=tid))
            evicted += 1
        self.compactions += evicted
        _metrics.counter("swarm_dispatcher_aset_compactions", evicted)
        if self.on_compact is not None:
            self.on_compact(evicted)
        return True

    def remove_task(self, t: Task) -> bool:
        self._terminal.pop(t.id, None)
        if t.id not in self.tasks:
            return False
        self.changes[("task", t.id)] = ("remove", "task", Task(id=t.id))
        del self.tasks[t.id]
        self._release_task_deps(t)
        return True


class BatchedAssignmentFanout:
    """The dispatcher's one assignments fan-out: every node's stream is
    served from it, whether there is one session or ten thousand.

    ONE store subscription for all of them, and no thread of its own:
    its predicate passes scheduler blocks, volumes, and a Task event only
    for a node with an open stream, so a commit that concerns no agent
    buffers nothing and wakes nobody.  ``flush()`` — driven from
    ``process_deadlines``, the dispatcher's worker thread at least every
    ``process_updates_interval`` in production, the control step in the
    sim — routes what the subscription holds into per-node
    ``_AssignmentSet`` diffs and sends at most
    ceil(pending / modification_batch_limit) INCREMENTAL messages per
    node: N task assignments to one node cost <= ceil(N/batch) sends,
    not N round-trips.

    Leader-gap discipline mirrors the status-flush re-queue machinery:
    diffs accumulate while a stream is down and the re-registered
    node's fresh ``open`` rebuilds a COMPLETE set from the store view —
    nothing lost, nothing duplicated (unit-tested across a gap in
    tests/test_autoscale.py).
    """

    def __init__(self, dispatcher: "Dispatcher"):
        self.d = dispatcher
        self._mu = threading.Lock()
        # serializes open() against flush(): open's COMPLETE snapshot
        # and its registration in _sets must be atomic w.r.t. a flush
        # draining the shared subscription, or an assignment committed
        # between the two is consumed for a node flush doesn't know yet
        # and lost forever
        self._drain_mu = threading.Lock()
        self._sets: Dict[str, _AssignmentSet] = {}
        self._streams: Dict[str, AssignmentStream] = {}
        self._seq: Dict[str, int] = {}
        self._applies: Dict[str, str] = {}
        self.stats = {"sends": 0, "complete_sends": 0, "compactions": 0}
        self._sub = dispatcher.store.queue.subscribe(self._wants,
                                                     accepts_blocks=True)

    def _wants(self, ev) -> bool:
        """The subscription's predicate.  It runs on the committing
        writer's thread, so it stays O(1) whatever the session count."""
        if isinstance(ev, EventTaskBlock):
            return True   # routed per node at flush, off the block's grouping
        if not isinstance(ev, Event):
            return False
        obj = ev.obj
        if isinstance(obj, Task):
            return obj.node_id in self._sets
        return isinstance(obj, Volume)   # matched to tracked deps at flush

    # ------------------------------------------------------------- streams

    def open(self, node_id: str, session_id: str) -> AssignmentStream:
        """Open (or re-open) a node's stream: full COMPLETE set from the
        current store view, then incremental batches via flush()."""
        self.d._check_session(node_id, session_id)
        stream = AssignmentStream(node_id)

        def _on_compact(n):
            self.stats["compactions"] += n

        aset = _AssignmentSet(node_id,
                              driver_provider=self.d.driver_provider,
                              terminal_bound=self.d.config
                              .max_terminal_tasks,
                              on_compact=_on_compact)
        with self._drain_mu:
            # session re-check + stream registration BEFORE any state
            # lands in the maps: a failure here must leak nothing
            with self.d._mu:
                rn = self.d._nodes.get(node_id)
                if rn is None or rn.session_id != session_id:
                    raise ErrSessionInvalid(node_id)
                rn.streams.append(stream)
            # registered before the snapshot: from here on the predicate
            # keeps this node's Task events, so a commit the snapshot
            # misses reaches the next flush (one in both is re-added,
            # which is idempotent)
            with self._mu:
                self._sets[node_id] = aset
                self._streams[node_id] = stream
                self._seq[node_id] = 0
                self._applies[node_id] = ""
            try:
                initial = self.d.store.view(
                    lambda vx: list(vx.find(Task, ByNode(node_id))))
                tx = self.d.store.view()
                for t in initial:
                    aset.add_or_update_task(tx, t)
                self._send(node_id, aset, stream,
                           AssignmentsMessage.COMPLETE)
            except BaseException as e:
                self._drop(node_id)
                stream.close(e)
                raise
            self.stats["complete_sends"] += 1
        return stream

    def _drop(self, node_id: str) -> None:
        with self._mu:
            self._sets.pop(node_id, None)
            self._streams.pop(node_id, None)
            self._seq.pop(node_id, None)
            self._applies.pop(node_id, None)

    def _send(self, node_id: str, aset: _AssignmentSet,
              stream: AssignmentStream, type_) -> None:
        """Send aset's pending changes as <= ceil(n/batch) messages."""
        limit = max(self.d.config.modification_batch_limit, 1)
        while True:
            if type_ == AssignmentsMessage.INCREMENTAL \
                    and not aset.changes:
                return
            # diff build (assignments.go message assembly) + delivery
            t0 = time.perf_counter()
            with tracer.span("dispatcher.assignments_send", "dispatcher",
                             type=type_) as sp:
                chunk: Dict[tuple, tuple] = {}
                for key in list(aset.changes)[:limit]:
                    chunk[key] = aset.changes.pop(key)
                self._seq[node_id] += 1
                results_in = str(self._seq[node_id])
                msg = AssignmentsMessage(type_, self._applies[node_id],
                                         results_in, list(chunk.values()))
                if sp is not None:
                    sp.args["changes"] = len(msg.changes)
                stream._push(msg)
            self.d._build_timer.observe(time.perf_counter() - t0)
            self._applies[node_id] = results_in
            self.stats["sends"] += 1
            _metrics.counter(
                f'swarm_dispatcher_assignments_sent{{type="{type_}"}}')
            _metrics.counter("swarm_dispatcher_assignment_changes",
                             len(msg.changes))
            _note_assignment_ship(msg)
            # a COMPLETE always goes out (even empty); its overflow (a
            # node with more assignments than one batch) continues as
            # incrementals
            type_ = AssignmentsMessage.INCREMENTAL

    # --------------------------------------------------------------- flush

    def flush(self) -> None:
        """Drain the shared subscription into the per-node sets, then
        one batched send pass.  ``_drain_mu`` serializes against
        ``open()`` so events for a node mid-registration are either in
        its COMPLETE snapshot or routed here — never silently consumed
        for an unknown node that registers a moment later."""
        t0 = time.perf_counter()
        with self._drain_mu:
            with tracer.span("dispatcher.fanout_flush", "dispatcher") as sp:
                sends0 = self.stats["sends"]
                events, streams = self._flush_locked()
                if sp is not None:
                    sp.args = {"events": events, "streams": streams,
                               "sends": self.stats["sends"] - sends0}
        _planes.plane(_planes.DISPATCHER).note_busy(
            time.perf_counter() - t0)

    def _flush_locked(self) -> Tuple[int, int]:
        """Route, retry, send; returns (events drained, live sets)."""
        with self._mu:
            live = dict(self._sets)
        tx = None
        drained = 0
        while True:
            ev = self._sub.poll()
            if ev is None:
                break
            drained += 1
            if isinstance(ev, EventTaskBlock):
                per_node = ev.per_node()
                for node_id, aset in live.items():
                    items = per_node.get(node_id)
                    if not items:
                        continue
                    tx = tx if tx is not None else self.d.store.view()
                    for old, _ver in items:
                        t = self.d.store.raw_get(Task, old.id)
                        if t is not None:
                            aset.add_or_update_task(tx, t)
                continue
            obj = ev.obj
            if isinstance(obj, Volume):
                if ev.action != "delete":
                    for aset in live.values():
                        aset.update_volume(obj)
                continue
            aset = live.get(obj.node_id)
            if aset is None:
                continue
            if ev.action == "delete":
                aset.remove_task(obj)
            else:
                tx = tx if tx is not None else self.d.store.view()
                aset.add_or_update_task(tx, obj)
        for node_id, aset in live.items():
            stream = self._streams.get(node_id)
            if stream is None or stream.closed:
                self._drop(node_id)
                continue
            if aset.pending_secret_retry:
                aset.retry_pending_secrets(self.d.store.view())
            if aset.changes:
                self._send(node_id, aset, stream,
                           AssignmentsMessage.INCREMENTAL)
        return drained, len(live)

    def stop(self) -> None:
        with self._mu:
            streams = list(self._streams.values())
            self._sets.clear()
            self._streams.clear()
        for s in streams:
            s.close(DispatcherError("dispatcher stopped"))
        if self._sub is not None:
            try:
                self.d.store.queue.unsubscribe(self._sub)
            except Exception:
                pass
            self._sub = None


class Dispatcher:
    def __init__(self, store: MemoryStore,
                 config: Optional[Config_] = None,
                 driver_provider=None,
                 rng: Optional[random.Random] = None,
                 write_store=None,
                 shard_filter: Optional[Callable[[str], bool]] = None):
        self.store = store
        # FOLLOWER MODE (reads served off the local replicated store):
        # every read — session checks, assignment snapshots/streams,
        # status validation — stays on ``store``; every session-mutating
        # WRITE (node READY/DOWN, task status batches, orphan moves)
        # goes through ``write_store``, which a follower member points at
        # a leader-forwarding proxy.  Default (None) is leader mode:
        # reads and writes share one store, behavior unchanged.
        self.write_store = write_store if write_store is not None \
            else store
        # session sharding: with a filter, this dispatcher owns only the
        # nodes the filter accepts — markNodesUnknown grace deadlines are
        # limited to its shard so a restarted member cannot DOWN nodes
        # that re-registered with a different member
        self.shard_filter = shard_filter
        #: optional veto consulted when a registration-grace deadline
        #: fires for a node with no local session: return False when the
        #: node is known to hold a live session on ANOTHER member (the
        #: control plane tracks ownership), True to proceed marking DOWN
        self.reg_grace_check: Optional[Callable[[str], bool]] = None
        # heartbeat jitter source: injectable so the deterministic
        # simulator can seed it (production uses the module-level RNG)
        self._rng = rng or random
        # resolves SecretSpec.driver to provider plugins
        # (reference: manager/drivers/provider.go)
        self.driver_provider = driver_provider
        # private copy: cluster-spec reloads must not mutate the caller's
        # (e.g. the Manager's) config object, which seeds future
        # dispatchers on later leadership cycles
        self.config = dataclasses.replace(config) if config else Config_()
        # the configured default, restored when the spec unsets its value
        self._default_heartbeat = self.config.heartbeat_period
        self._mu = threading.Lock()
        self._nodes: Dict[str, _RegisteredNode] = {}
        self._down_nodes: Dict[str, float] = {}  # node_id -> down since
        self._task_updates: Dict[str, TaskStatus] = {}
        # (volume_id, node_id) pairs reported node-unpublished by agents
        # (reference: dispatcher.go:682 UpdateVolumeStatus)
        self._unpublished_volumes: Set[Tuple[str, str]] = set()
        self._node_updates: Dict[str, tuple] = {}  # id->(status, description)
        self._updates_lock = threading.Lock()
        self._heap: List = []    # (deadline, seq, kind, node_id)
        self._seq = 0
        self._running = False
        self._stop = threading.Event()
        self._worker: Optional[threading.Thread] = None
        #: the assignments fan-out every stream is served from: one store
        #: subscription + per-node batched flushes driven from
        #: process_deadlines; made by run(), torn down by stop()
        self.fanout: Optional[BatchedAssignmentFanout] = None
        self.stats = {"heartbeats": 0, "expirations": 0,
                      "sheds": 0, "hb_stretches": 0,
                      "premature_expirations": 0}
        #: checker-sensitivity seam: with the seam off, the expiry
        #: deadline forgets the stretch the agent was PROMISED — the
        #: exact bug heartbeat-liveness-under-stretch exists to catch
        self.stretch_extends_deadline = True
        #: checker-sensitivity seam: with the seam off, admission sheds
        #: still happen but are NOT counted — silently lossy degradation,
        #: the exact bug overload-sheds-are-counted-and-recovered catches
        self.count_sheds = True
        # cached Timer references — no per-call registry lookup on the
        # flush/assignments paths (reset() resets these in place)
        self._flush_timer = _metrics.timer(
            "swarm_dispatcher_update_batch_latency")
        self._build_timer = _metrics.timer(
            "swarm_dispatcher_assignments_build")

        # dispatcher-plane saturation probe (obs/planes.py): session
        # count as its own gauge (a bounded per-shard scalar) and the
        # fan-out's pending-change backlog as the plane queue depth.
        # plane() is resolved per call — planes.reset() rebinds the
        # table.  Weakref: the probe must not pin a stopped dispatcher.
        # Co-resident dispatchers (HA tests): last constructed owns it.
        import weakref
        _ref = weakref.ref(self)

        def _disp_probe():
            d = _ref()
            if d is None:
                return {}
            with d._mu:
                sessions = float(len(d._nodes))
            _metrics.gauge("swarm_dispatcher_sessions", sessions)
            with d._updates_lock:
                pending = float(len(d._task_updates))
            _metrics.gauge("swarm_dispatcher_pending_updates", pending)
            depth = pending
            fan = d.fanout
            if fan is not None:
                with fan._mu:
                    depth += float(sum(len(s.changes)
                                       for s in fan._sets.values()))
            return {"depth": depth}
        _planes.plane(_planes.DISPATCHER).set_probe(_disp_probe)

    # ------------------------------------------------------------- lifecycle

    def run(self, start_worker: bool = True) -> None:
        """Start the dispatcher's timer/batching worker.

        ``start_worker=False`` brings the dispatcher fully up but runs no
        thread — the caller (the deterministic simulator) drives
        ``process_deadlines``/``_flush_updates`` itself under its clock."""
        with self._mu:
            if self._running:
                return
            self._running = True
            self._stop.clear()
            # cluster-spec changes (e.g. heartbeat period) take effect
            # live; the current spec applies at startup too (reference:
            # manager.go:801 watchForClusterChanges does an initial read)
            self._cluster_sub = self.store.queue.subscribe(
                lambda ev: isinstance(ev, EventSnapshotRestore)
                or (isinstance(ev, Event) and isinstance(ev.obj, Cluster)
                    and ev.action == "update"),
                accepts_blocks=True)   # blocks are never cluster events
            self._load_cluster_config()
            self._mark_nodes_unknown()
            self.enable_batched_fanout()
            if start_worker:
                self._worker = threading.Thread(
                    target=self._worker_loop, name="dispatcher",
                    daemon=True)
                self._worker.start()

    def _mark_nodes_unknown(self) -> None:
        """A fresh dispatcher (new leader) inherits store nodes that
        registered with the OLD leader's dispatcher: give each a
        registration grace window; whoever doesn't open a session by then
        is marked DOWN so its tasks heal elsewhere (reference:
        dispatcher.go markNodesUnknown on Run)."""
        try:
            nodes = self.store.view(lambda tx: tx.find(Node))
        except Exception:
            log.exception("markNodesUnknown scan failed")
            return
        grace = self._heartbeat_period() * self.config.grace_multiplier
        deadline = now() + grace
        # caller (start) already holds self._mu
        for n in nodes:
            if self.shard_filter is not None \
                    and not self.shard_filter(n.id):
                continue   # another member's session shard
            if n.status.state != NodeState.DOWN:
                self._push_deadline(deadline, "reg", n.id)

    def enable_batched_fanout(self) -> "BatchedAssignmentFanout":
        """The assignments fan-out, made here if it is not there yet
        (``run`` makes it).  Idempotent."""
        if self.fanout is None:
            self.fanout = BatchedAssignmentFanout(self)
        return self.fanout

    def adopt_registration_grace(self, node_ids) -> None:
        """Adopt orphaned sessions (their owning member died): give each
        node a registration-grace window on THIS dispatcher; whoever does
        not re-register anywhere by then is marked DOWN so its tasks heal
        elsewhere (the follower-mode analogue of markNodesUnknown)."""
        grace = self._heartbeat_period() * self.config.grace_multiplier
        deadline = now() + grace
        with self._mu:
            for nid in node_ids:
                if nid not in self._nodes:
                    self._push_deadline(deadline, "reg", nid)

    def release_session(self, node_id: str, session_id: str) -> None:
        """Graceful session handoff: drop the session WITHOUT marking the
        node DOWN — the agent is re-registering with another member (e.g.
        draining consumers off a freshly promoted leader).  An unknown or
        mismatched session is a no-op (the handoff already happened)."""
        with self._mu:
            rn = self._nodes.get(node_id)
            if rn is None or rn.session_id != session_id:
                return
            del self._nodes[node_id]
        for stream in rn.streams:
            stream.close(ErrSessionInvalid("session released"))

    def stop(self, flush: bool = True) -> None:
        """``flush=False`` drops buffered status updates instead of
        writing them out — the deposed-leader teardown path: a fenced
        proposer would reject the flush anyway, and the successor's
        dispatcher re-learns task state from the agents' re-registration
        (fresh COMPLETE assignment sets)."""
        self._stop.set()
        with self._mu:
            self._running = False
            nodes = list(self._nodes.values())
            self._nodes.clear()
        for rn in nodes:
            for stream in rn.streams:
                stream.close(DispatcherError("dispatcher stopped"))
        if self._worker is not None:
            self._worker.join(timeout=5)
            self._worker = None
        if self.fanout is not None:
            self.fanout.stop()
            self.fanout = None
        if getattr(self, "_cluster_sub", None) is not None:
            self.store.queue.unsubscribe(self._cluster_sub)
            self._cluster_sub = None
        if flush:
            self._flush_updates()

    def _load_cluster_config(self) -> None:
        from ..state.store import ByName
        clusters = self.store.view(
            lambda tx: tx.find(Cluster, ByName("default")))
        if clusters:
            self._apply_cluster_config(clusters[0])

    def _apply_cluster_config(self, cluster: Cluster) -> None:
        # spec value 0 means unset -> the configured default applies;
        # this holds on the initial read, live updates, AND snapshot
        # restores, and lets an operator RESET to the default by writing 0
        period = cluster.spec.dispatcher.heartbeat_period
        target = period if period > 0 else self._default_heartbeat
        if target != self.config.heartbeat_period:
            log.info("heartbeat period now %.1fs (cluster spec)", target)
            self.config.heartbeat_period = target

    # -------------------------------------------------------------- register

    def register(self, node_id: str,
                 description=None, addr: str = "") -> Tuple[str, float]:
        """Create a session; returns (session_id, heartbeat_period)
        (reference: dispatcher.go:553)."""
        if not self._running:
            raise DispatcherError("dispatcher is not running")
        node = self.store.raw_get(Node, node_id)
        if node is None:
            raise ErrNodeNotFound(node_id)
        maxs = self.config.max_sessions
        if maxs is not None and node_id not in self._nodes \
                and len(self._nodes) >= maxs:
            self._count_shed(1)
            raise ErrOverloaded(
                f"session bound {maxs} reached; node {node_id} shed")

        session_id = new_id()
        period = self._heartbeat_period()
        window = period if self.stretch_extends_deadline \
            else period / self._stretch_factor()
        with self._mu:
            old = self._nodes.get(node_id)
            attempts = 0
            if old is not None:
                # re-registration rate limit (reference: nodes.go:90
                # CheckRateLimit): attempts reset once the last
                # registration is older than the period, and carry over
                # across accepted re-registrations otherwise; period <= 0
                # disables the limit (reference tests set 0)
                if self.config.rate_limit_period > 0:
                    attempts = old.attempts
                    if now() - old.registered_at > \
                            self.config.rate_limit_period:
                        attempts = 0
                    attempts += 1
                    if attempts > RATE_LIMIT_COUNT:
                        # attempts stick but the window keeps aging from
                        # the last ACCEPTED registration (reference:
                        # nodes.go:94-101 — Registered is only stamped on
                        # success), so steady retries recover after one
                        # quiet period
                        old.attempts = attempts
                        raise ErrRateLimited(
                            f"node {node_id} exceeded rate limit count "
                            "of registrations")
                for stream in old.streams:
                    stream.close(ErrSessionInvalid("node re-registered"))
            rn = _RegisteredNode(node_id=node_id, session_id=session_id,
                                 attempts=attempts)
            rn.deadline = now() + window * self.config.grace_multiplier
            rn.promised_until = now() + period * \
                self.config.grace_multiplier
            self._nodes[node_id] = rn
            self._down_nodes.pop(node_id, None)
            self._push_deadline(rn.deadline, "hb", node_id)

        self._mark_node_ready(node_id, description, addr)
        log.info("worker %s registered", node_id)
        return session_id, period

    def _heartbeat_period(self) -> float:
        base = self.config.heartbeat_period
        jittered = base + self._rng.uniform(
            -self.config.heartbeat_epsilon, self.config.heartbeat_epsilon)
        stretch = self._stretch_factor()
        if stretch > 1.0:
            self.stats["hb_stretches"] += 1
            _metrics.counter("swarm_dispatcher_hb_stretches")
        return jittered * stretch

    def _stretch_factor(self) -> float:
        """Adaptive heartbeat stretching: beyond ``hb_stretch_start``
        sessions the advertised period grows linearly with load (capped
        at ``hb_stretch_max``) — the leader tells agents to slow down,
        so heartbeat arrival rate stays ~flat as sessions multiply.
        Lock-free read of len(_nodes); callers may hold ``_mu``."""
        start = self.config.hb_stretch_start
        if start <= 0:
            return 1.0
        sessions = len(self._nodes)
        if sessions <= start:
            return 1.0
        factor = min(self.config.hb_stretch_max,
                     sessions / float(start))
        _metrics.gauge("swarm_dispatcher_hb_stretch", factor)
        return factor

    def _count_shed(self, n: int) -> None:
        """Every admission shed is COUNTED before it is raised — the
        overload-sheds-are-counted-and-recovered invariant audits the
        client-observed sheds against exactly this ledger."""
        if not self.count_sheds:
            return   # sensitivity seam: shed silently (the bug)
        self.stats["sheds"] += n
        _metrics.counter("swarm_dispatcher_sheds", n)
        _planes.plane(_planes.DISPATCHER).drop(n)

    def publish_logs(self, node_id: str, session_id: str,
                     messages) -> None:
        """Agent-side log publishing passthrough to the log broker
        (reference: logbroker.go PublishLogs; the broker is attached by
        the Manager).  Session-gated like every other agent-facing
        method: expired/orphaned agents must not keep injecting logs."""
        with self._mu:
            rn = self._nodes.get(node_id)
            if rn is None:
                raise ErrNodeNotRegistered(node_id)
            if rn.session_id != session_id:
                raise ErrSessionInvalid(node_id)
        broker = getattr(self, "log_broker", None)
        if broker is None:
            return
        from .logbroker import LogMessage
        broker.publish_logs([
            LogMessage(task_id=m["task_id"], node_id=m["node_id"],
                       stream=m.get("stream", "stdout"),
                       data=m["data"] if isinstance(m["data"], bytes)
                       else m["data"].encode())
            for m in messages])

    def heartbeat(self, node_id: str, session_id: str) -> float:
        """TTL refresh; returns the next period
        (reference: dispatcher.go:1317)."""
        period = self._heartbeat_period()
        window = period if self.stretch_extends_deadline \
            else period / self._stretch_factor()
        with self._mu:
            rn = self._nodes.get(node_id)
            if rn is None:
                raise ErrNodeNotRegistered(node_id)
            if rn.session_id != session_id:
                raise ErrSessionInvalid(node_id)
            rn.deadline = now() + window * self.config.grace_multiplier
            rn.promised_until = now() + period * \
                self.config.grace_multiplier
            self._push_deadline(rn.deadline, "hb", node_id)
        self.stats["heartbeats"] += 1
        _metrics.counter("swarm_dispatcher_heartbeats")
        return period

    def _check_session(self, node_id: str, session_id: str) -> None:
        with self._mu:
            rn = self._nodes.get(node_id)
        if rn is None:
            raise ErrNodeNotRegistered(node_id)
        if rn.session_id != session_id:
            raise ErrSessionInvalid(node_id)

    # ------------------------------------------------------- node up/down

    def _mark_node_ready(self, node_id: str, description, addr: str) -> None:
        with self._updates_lock:
            self._node_updates[node_id] = (
                NodeStatus(state=NodeState.READY, addr=addr), description)
        # readiness must not wait for the batching interval: orchestrators
        # treat DOWN nodes as invalid (reference marks ready synchronously)
        self._flush_updates()

    def _mark_node_not_ready(self, node_id: str, message: str) -> None:
        """Heartbeat expiry or disconnect: node DOWN
        (reference: dispatcher.go:1253)."""
        self.stats["expirations"] += 1
        _metrics.counter("swarm_dispatcher_heartbeat_expirations")
        with self._mu:
            rn = self._nodes.pop(node_id, None)
            self._down_nodes[node_id] = now()
            self._push_deadline(now() + self.config.orphan_timeout,
                                "orphan", node_id)
        if rn is not None:
            for stream in rn.streams:
                stream.close(ErrSessionInvalid(message))
        with self._updates_lock:
            self._node_updates[node_id] = (
                NodeStatus(state=NodeState.DOWN, message=message), None)
        self._flush_updates()

    def _move_tasks_to_orphaned(self, node_id: str) -> None:
        """reference: dispatcher.go:1209."""
        tasks = self.store.view(lambda tx: tx.find(Task, ByNode(node_id)))

        def cb(batch: Batch) -> None:
            for t in tasks:
                if t.status.state >= TaskState.ORPHANED:
                    continue

                def one(tx, t=t):
                    cur = tx.get(Task, t.id)
                    if cur is None or cur.status.state >= TaskState.ORPHANED:
                        return
                    cur = cur.copy()
                    cur.status = TaskStatus(state=TaskState.ORPHANED,
                                            timestamp=now(),
                                            message="node unreachable")
                    tx.update(cur)
                batch.update(one)

        try:
            self.write_store.batch(cb)
        except Exception:
            log.exception("moving tasks to orphaned failed")

    # --------------------------------------------------------- status intake

    def update_task_status(self, node_id: str, session_id: str,
                           updates: List[Tuple[str, TaskStatus]]) -> None:
        """Batched agent status writeback (reference: dispatcher.go:607)."""
        self._check_session(node_id, session_id)
        valid: List[Tuple[str, TaskStatus]] = []
        for task_id, status in updates:
            t = self.store.raw_get(Task, task_id)
            if t is None:
                continue  # task may have been deleted
            if t.node_id != node_id:
                raise DispatcherError(
                    "cannot update a task not assigned this node")
            valid.append((task_id, status))
        bound = self.config.max_pending_updates
        shed = 0
        with self._updates_lock:
            # admission check at the RPC edge: a batch that would
            # overflow the buffer is shed WHOLE (newest-rejected ==
            # oldest-first retention: buffered updates, already
            # admitted, are never dropped to make room).  Updates
            # rewriting an already-buffered task don't grow the buffer
            # and always land.
            if bound is not None and valid:
                growth = sum(1 for task_id, _ in valid
                             if task_id not in self._task_updates)
                if growth and len(self._task_updates) + growth > bound:
                    shed = len(valid)
            if not shed:
                for task_id, status in valid:
                    self._task_updates[task_id] = status
            n = len(self._task_updates)
        if shed:
            self._count_shed(shed)
            raise ErrOverloaded(
                f"status buffer at bound {bound}: shed {shed} updates "
                f"from node {node_id}")
        if n >= self.config.max_batch_items:
            self._flush_updates()

    def update_volume_status(self, node_id: str, session_id: str,
                             updates) -> None:
        """Agents report node-side volume unpublish completion; the next
        batch moves those volumes from PENDING_NODE_UNPUBLISH to
        PENDING_UNPUBLISH so the CSI manager can controller-unpublish
        (reference: dispatcher.go:682 UpdateVolumeStatus).
        ``updates``: iterable of (volume_id, unpublished: bool)."""
        self._check_session(node_id, session_id)
        with self._updates_lock:
            for volume_id, unpublished in updates:
                if unpublished:
                    self._unpublished_volumes.add((volume_id, node_id))

    def _flush_updates(self) -> None:
        """reference: dispatcher.go:726 processUpdates."""
        with self._updates_lock:
            task_updates, self._task_updates = self._task_updates, {}
            node_updates, self._node_updates = self._node_updates, {}
            unpublished = self._unpublished_volumes
            self._unpublished_volumes = set()
        if not task_updates and not node_updates and not unpublished:
            return
        _metrics.counter("swarm_dispatcher_task_status_updates",
                         len(task_updates))
        _flush_t0 = time.perf_counter()

        def cb(batch: Batch) -> None:
            for task_id, status in task_updates.items():
                def one(tx, task_id=task_id, status=status):
                    t = tx.get(Task, task_id)
                    if t is None:
                        return
                    if t.status.state > status.state:
                        return  # invalid transition
                    if (t.status.state == status.state
                            and t.status.message == status.message
                            and t.status.err == status.err):
                        return
                    t = t.copy()
                    status = status.copy()
                    status.applied_at = now()
                    t.status = status
                    tx.update(t)
                batch.update(one)
            for node_id, (status, description) in node_updates.items():
                def one_n(tx, node_id=node_id, status=status,
                          description=description):
                    n = tx.get(Node, node_id)
                    if n is None:
                        return
                    n = n.copy()
                    if status is not None:
                        n.status.state = status.state
                        n.status.message = status.message
                        if status.addr:
                            n.status.addr = status.addr
                    if description is not None:
                        n.description = description
                    tx.update(n)
                batch.update(one_n)
            for volume_id, v_node in unpublished:
                def one_v(tx, volume_id=volume_id, v_node=v_node):
                    from ..models.types import VolumePublishStatus
                    v = tx.get(Volume, volume_id)
                    if v is None:
                        return
                    changed = requeue = False
                    v = v.copy()
                    for ps in v.publish_status:
                        if ps.node_id != v_node:
                            continue
                        if ps.state == (VolumePublishStatus.State
                                        .PENDING_NODE_UNPUBLISH):
                            ps.state = (VolumePublishStatus.State
                                        .PENDING_UNPUBLISH)
                            changed = True
                        elif ps.state == \
                                VolumePublishStatus.State.PUBLISHED:
                            # agent reported before the scheduler freed
                            # the volume: keep the report for a later
                            # flush instead of losing it
                            requeue = True
                    if requeue:
                        with self._updates_lock:
                            self._unpublished_volumes.add(
                                (volume_id, v_node))
                    if changed:
                        tx.update(v)
                batch.update(one_v)

        try:
            self.write_store.batch(cb)
        except Exception as e:
            from ..state.raft.node import NotLeader, ProposalDropped
            if isinstance(e, (DispatcherError, NotLeader,
                              ProposalDropped)):
                # forwarding gap (follower mode during a leaderless
                # window / a deposal mid-write): re-queue so the next
                # flush retries instead of losing the statuses.  Newest
                # wins: an update buffered since the pop supersedes the
                # failed one.
                log.warning("dispatcher update batch deferred "
                            "(no leader): re-queued")
                with self._updates_lock:
                    for task_id, status in task_updates.items():
                        self._task_updates.setdefault(task_id, status)
                    for node_id, pair in node_updates.items():
                        self._node_updates.setdefault(node_id, pair)
                    self._unpublished_volumes |= unpublished
            else:
                # anything else is a poisoned item or a store bug:
                # dropping it (with the trace) beats starving every
                # later batch on an eternal retry
                log.exception("dispatcher update batch failed")
        self._flush_timer.observe(time.perf_counter() - _flush_t0)

    # ------------------------------------------------------------ worker

    def _push_deadline(self, deadline: float, kind: str,
                       node_id: str) -> None:
        self._seq += 1
        heapq.heappush(self._heap, (deadline, self._seq, kind, node_id))

    def _worker_loop(self) -> None:
        last_flush = now()
        while not self._stop.is_set():
            interval = self.config.process_updates_interval
            with self._mu:
                deadline = self._heap[0][0] if self._heap else None
            timeout = interval if deadline is None else \
                max(0.0, min(interval, deadline - now()))
            self._stop.wait(timeout=timeout)
            ts = now()
            self.process_deadlines(ts)
            if ts - last_flush >= interval:
                self._flush_updates()
                last_flush = ts

    def process_deadlines(self, ts: Optional[float] = None) -> None:
        """Fire every deadline (heartbeat TTL, registration grace, orphan
        timeout) due at ``ts``, and apply pending cluster-config events.
        Called by the worker thread each wakeup; the deterministic
        simulator calls it directly under virtual time instead of running
        the worker thread."""
        if ts is None:
            ts = now()
        # apply live cluster-config changes (and resync on restore)
        sub = getattr(self, "_cluster_sub", None)
        while sub is not None:
            ev = sub.poll()
            if ev is None:
                break
            if isinstance(ev, EventSnapshotRestore):
                self._load_cluster_config()
            else:
                self._apply_cluster_config(ev.obj)
        # heartbeat expirations + orphan deadlines
        while True:
            with self._mu:
                if not self._heap or self._heap[0][0] > ts:
                    break
                _, _, kind, node_id = heapq.heappop(self._heap)
                if kind == "hb":
                    rn = self._nodes.get(node_id)
                    expired = rn is not None and rn.deadline <= ts
                    if expired and rn.promised_until > ts:
                        # the node is being DOWNed INSIDE the window the
                        # dispatcher promised it (a stretch the deadline
                        # forgot) — the liveness invariant reads this
                        self.stats["premature_expirations"] += 1
                        _metrics.counter(
                            "swarm_dispatcher_premature_expirations")
                elif kind == "reg":
                    # registration grace after a leadership change; the
                    # ownership veto keeps a sharded dispatcher from
                    # DOWNing a node with a live session elsewhere
                    expired = node_id not in self._nodes \
                        and (self.reg_grace_check is None
                             or self.reg_grace_check(node_id))
                else:
                    down_since = self._down_nodes.get(node_id)
                    expired = (down_since is not None
                               and ts - down_since
                               >= self.config.orphan_timeout)
                    if expired:
                        del self._down_nodes[node_id]
            if kind == "hb" and expired:
                log.info("heartbeat expiration for worker %s", node_id)
                self._mark_node_not_ready(node_id, "heartbeat failure")
            elif kind == "reg" and expired:
                log.info("node %s never registered after leadership "
                         "change", node_id)
                self._mark_node_not_ready(
                    node_id, "node did not re-register after "
                    "leadership change")
            elif kind == "orphan" and expired:
                self._move_tasks_to_orphaned(node_id)
        if self.fanout is not None:
            self.fanout.flush()

    # ---------------------------------------------------------- assignments

    def open_assignments(self, node_id: str,
                         session_id: str) -> AssignmentStream:
        """Start an assignments stream for the node
        (reference: dispatcher.go:1013): a COMPLETE set now, then
        INCREMENTAL diffs from the dispatcher's one fan-out.  No stream
        has a thread or a store subscription of its own."""
        fanout = self.fanout
        if fanout is None:
            raise DispatcherError("dispatcher is not running")
        return fanout.open(node_id, session_id)
