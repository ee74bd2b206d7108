"""The scheduler: assigns PENDING tasks to nodes.

Reference: manager/scheduler/scheduler.go.

Event-loop object over the store: mirrors tasks/nodes in memory, debounces
commit events (50ms gap, 1s max), groups unassigned tasks by (service,
spec-version), builds a spread-preference tree per group, round-robins tasks
over sorted candidate nodes re-filtering after every placement, then commits
ASSIGNED states in batched transactions with node-version conflict rollback.

A pluggable ``batch_planner`` seam lets the TPU path (ops/planner.py) replace
the per-group tree walk with a device-computed placement while event
handling, commit logic, and the host path stay identical — the Filter/
Pipeline gating strategy called for in SURVEY.md §5.8.
"""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Dict, Iterable, List, Optional, Tuple

from ..models.objects import Cluster, Node, Service, Task, Volume
from ..models.types import (
    Resources, TaskState, TaskStatus, now, time_source_installed,
)
from ..obs import planes as _planes
from ..obs import threadcpu
from ..obs.trace import tracer
from ..utils.metrics import registry as _metrics
from ..utils.pipeline import default_pipeline_depth
from ..state.events import Event, EventCommit, EventSnapshotRestore
from ..state.store import Batch, ByName, MemoryStore, ReadTx
from ..state.watch import Closed
from . import gang as gang_mod
from . import genericresource
from . import preempt as preempt_mod
from . import strategy as strategy_mod
from .deltatrack import DeltaTracker
from .filters import Pipeline, VolumesFilter
from .nodeinfo import MAX_FAILURES, NodeInfo, task_reservations
from .nodeset import DecisionTree, NodeSet
from .preempt import PreemptSupervisor, task_priority
from .quota import QuotaFilter, TenantLedger, task_tenant
from .volumes import VolumeSet

log = logging.getLogger("scheduler")

COMMIT_DEBOUNCE_GAP = 0.050   # reference: scheduler.go:149-155
MAX_LATENCY = 1.0

# cached Timer references (Registry.reset() resets in place)
_TICK_TIMER = _metrics.timer("swarm_scheduler_tick_latency")
_COMMIT_TIMER = _metrics.timer("swarm_scheduler_commit_latency")


class SchedulingDecision:
    __slots__ = ("old", "new")

    def __init__(self, old: Task, new: Task):
        self.old = old
        self.new = new


def _services_of(ids: List[str]) -> dict:
    """``service`` (the first) and ``services`` (how many) for the span
    of a commit that several services' decisions share."""
    return {"service": ids[0], "services": len(set(ids))} if ids else {}


class _TickCommitter:
    """One tick's commit pipeline: group drafts commit on a dedicated
    thread, in submission (= planning) order, while the main thread
    builds and dispatches the next group's device plan — the host-commit
    half of the plan/commit overlap (docs/architecture.md "Pipelined
    scheduling").

    The tick is only acked after ``close()``: every submitted draft has
    resolved, commit results aggregated, so conflict rollback and
    re-enqueue run exactly as the serial path's end-of-tick handling.
    Once leadership is observed lost, remaining drafts fail WITHOUT
    touching the store — no in-flight device plan may commit after
    leadership loss (asserted by the sim's pipelined-commit scenario).
    """

    __slots__ = ("_sched", "_q", "_tickets", "_thread", "_resolved")

    def __init__(self, sched: "Scheduler"):
        self._sched = sched
        self._q: "queue.Queue" = queue.Queue()
        self._tickets: List[dict] = []
        self._thread: Optional[threading.Thread] = None
        self._resolved = 0   # tickets resolve strictly FIFO

    def submit(self, draft: List[Tuple[List[Task], List[str], str]]
               ) -> None:
        ticket = {"draft": draft, "done": threading.Event(),
                  "committed": 0, "failed": [], "missing": []}
        self._tickets.append(ticket)
        _metrics.gauge("swarm_scheduler_chunk_inflight",
                       float(len(self._tickets) - self._resolved))
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="sched-commit", daemon=True)
            self._thread.start()
        self._q.put(ticket)

    def throttle(self, max_inflight: int) -> None:
        """Bounded depth: block until at most ``max_inflight`` submitted
        drafts remain unresolved.  Tickets resolve in submission order
        (single FIFO committer), so a monotonic resolved-prefix index
        keeps this O(1) amortized per call."""
        if len(self._tickets) - self._resolved > max_inflight:
            with tracer.span("sched.commit_wait", "sched"):
                while len(self._tickets) - self._resolved > max_inflight:
                    self._tickets[self._resolved]["done"].wait()
                    self._resolved += 1
        _metrics.gauge("swarm_scheduler_chunk_inflight",
                       float(len(self._tickets) - self._resolved))

    @staticmethod
    def _fail_all(ticket: dict) -> None:
        ticket["failed"] = [
            (old, nid) for olds, nids, _ in ticket["draft"]
            for old, nid in zip(olds, nids)]

    def _lost_leadership(self) -> bool:
        """Fail-fast check before touching the store.  The epoch
        comparison is the load-bearing one: the tick's drafts are pinned
        to the leadership epoch captured at tick start, so a deposal —
        even a depose-and-re-elect flap this thread never observes as a
        role change — fences the remaining drafts.  (The proposer
        re-checks the same epoch pre-WAL and at commit delivery, so this
        racy fast-path can only ever fail early, never admit late.)"""
        proposer = self._sched.store._proposer
        if proposer is None:
            return False
        if not getattr(proposer, "is_leader", True):
            return True
        tick_epoch = self._sched._tick_epoch
        return (tick_epoch is not None
                and getattr(proposer, "leadership_epoch", None)
                != tick_epoch)

    def _run(self) -> None:
        while True:
            ticket = self._q.get()
            if ticket is None:
                return
            sched = self._sched
            try:
                if self._lost_leadership():
                    self._fail_all(ticket)
                else:
                    n = sum(len(olds)
                            for olds, _, _ in ticket["draft"])
                    t0 = now()
                    with tracer.span("sched.commit", "sched",
                                     decisions=n) as sp:
                        if sp is not None:
                            # a draft holds one group's block
                            sp.args.update(_services_of(
                                [olds[0].service_id for olds, _, _
                                 in ticket["draft"] if olds]))
                        c, _, f = sched._commit_draft(
                            ticket["draft"], want_ids=False,
                            missing_out=ticket["missing"])
                    dt = now() - t0
                    sched.stats["commit_seconds"] += dt
                    _COMMIT_TIMER.observe(dt)
                    ticket["committed"] = c
                    ticket["failed"] = f
            except Exception:
                log.exception("pipelined block commit failed")
                self._fail_all(ticket)
            finally:
                ticket["done"].set()

    def close(self) -> Tuple[int, List[Tuple[Task, str]]]:
        """Join the committer, then run the deferred vanished-task
        cleanup on the calling (main) thread; returns (committed count,
        failed (mirror task, node_id) pairs) across all drafts."""
        if self._thread is not None:
            self._q.put(None)
            self._thread.join()
        committed = sum(t["committed"] for t in self._tickets)
        failed = [p for t in self._tickets for p in t["failed"]]
        for t in self._tickets:
            for old, nid in t["missing"]:
                self._sched._on_block_missing(old, nid)
        return committed, failed


class _LoopAccount:
    """What ``Scheduler.run`` did with its thread, kept per debounce
    episode and never per event (events run to thousands a second).

    An episode runs from the ``EventCommit`` that opened it to the
    instant a deadline fired and the loop acted.  Spans, all retroactive
    (``record_complete``) and only while the tracer is on:
    ``sched.idle`` from the end of the last episode to this one's first
    ``EventCommit``; ``sched.debounce`` over the episode, with the
    deadline that fired; ``sched.events``, whose duration is the SUMMED
    time inside ``_handle_event`` / ``_resync`` since the last episode
    closed — a total, placed at the episode's end, not an interval in
    which the events ran.  With ``sched.tick`` they cover the thread.
    Counters (``Scheduler.stats``) advance once, when the episode
    closes, tracer or not."""

    __slots__ = ("stats", "events", "commits", "event_s", "idle_from",
                 "wall0", "cpu0")

    def __init__(self, stats: dict):
        self.stats = stats
        self.events = self.commits = 0
        self.event_s = 0.0
        self.idle_from = self.wall0 = now()
        self.cpu0 = time.thread_time()

    def open_episode(self, started: float) -> None:
        if tracer.enabled:
            tracer.record_complete("sched.idle", "sched",
                                   started - self.idle_from)

    def fire(self, started: float, fired: str, queued: int,
             ticked: bool) -> None:
        if tracer.enabled:
            tracer.record_complete(
                "sched.debounce", "sched", now() - started,
                events=self.events, commits=self.commits, fired=fired,
                queued=queued, ticked=ticked)
            tracer.record_complete("sched.events", "sched", self.event_s,
                                   events=self.events)

    def close_episode(self, at: Optional[float] = None) -> None:
        """``at``: the instant the episode's work ended (its tick's
        end), where the idle stretch after it begins."""
        stats = self.stats
        stats["events_handled"] += self.events
        stats["commits_seen"] += self.commits
        self.events = self.commits = 0
        self.event_s = 0.0
        t = now() if at is None else at
        if not time_source_installed():
            cpu = time.thread_time()
            stats["thread_cpu_s"] += cpu - self.cpu0
            stats["loop_wall_s"] += t - self.wall0
            self.cpu0 = cpu
        self.wall0 = self.idle_from = t


class Scheduler:
    def __init__(self, store: MemoryStore,
                 batch_planner=None,
                 debounce_gap: float = COMMIT_DEBOUNCE_GAP,
                 max_latency: float = MAX_LATENCY,
                 pipeline_depth: Optional[int] = None,
                 preempt_budget: Optional[int] = None,
                 preempt_cooldown: Optional[float] = None,
                 tick_budget_s: Optional[float] = None):
        self.store = store
        # bounded-depth plan/commit software pipeline: while group i's
        # draft commits on the committer thread, group i+1's device plan
        # is dispatched and computes.  1 = strictly serial tick
        # (SWARM_PIPELINE_DEPTH escape hatch); placements are
        # byte-identical either way (tests/test_pipeline.py).
        self.pipeline_depth = (pipeline_depth if pipeline_depth is not None
                               else default_pipeline_depth())
        # commit-event debounce windows (reference: scheduler.go:149-155);
        # injectable so tests and the simulator control latency precisely
        self.debounce_gap = debounce_gap
        self.max_latency = max_latency
        self.unassigned_tasks: Dict[str, Task] = {}
        # count of unassigned tasks in a positive priority band: while
        # it is nonzero, a lower-priority task reaching RUNNING is a
        # tick trigger — new preemption capacity just materialized
        # (without this, a starving high-priority group would wait for
        # an unrelated create/delete/node event to retry)
        self._prio_pending = 0
        # incremental (service, spec-version) grouping of the unassigned
        # queue: maintained at enqueue/dequeue time so tick() does not pay
        # a per-task grouping pass (reference groups in tick,
        # scheduler.go:438-462 — same result, amortized differently)
        self.unassigned_groups: Dict[Optional[Tuple[str, int]],
                                     Dict[str, Task]] = {}
        self.pending_preassigned_tasks: Dict[str, Task] = {}
        self.preassigned_tasks: set = set()
        # streaming-scheduler delta feed: node create/update/remove and
        # task commit/exit events (this loop's existing block-aware
        # subscription) fold into per-node dirty bits the planner's
        # resident device-input state refreshes from (ops/streaming.py)
        self.delta = DeltaTracker()
        self.node_set = NodeSet()
        self.node_set.tracker = self.delta
        self.all_tasks: Dict[str, Task] = {}
        self.pipeline = Pipeline()
        self.volumes = VolumeSet()
        self.batch_planner = batch_planner
        # columnar commit draft: one (mirror tasks, node_ids, status
        # message) column triple per planned group, accumulated by the
        # device planner when the store allows block commits
        # (store.commit_task_block); committed as array-shaped calls per
        # tick instead of per-task objects
        self.block_draft: List[Tuple[List[Task], List[str], str]] = []
        self.block_mode = False

        # priority preemption (scheduler/preempt.py): budget, anti-thrash
        # cooldowns, and obs exports.  SWARM_PREEMPTION=0 disables the
        # pass wholesale; with every priority at the default 0 band the
        # pass is a no-op either way (positive priority opts a service
        # into preempting).
        import os as _os
        self.preempt = PreemptSupervisor(budget=preempt_budget,
                                         cooldown=preempt_cooldown)
        self.preempt_enabled = \
            _os.environ.get("SWARM_PREEMPTION", "") != "0"

        # overload protection: per-tick deadline budget (seconds).  A
        # tick that exceeds it mid-walk commits what it planned CLEANLY
        # and re-enqueues the remaining groups for the next tick —
        # backlog converts to bounded per-tick latency instead of one
        # unboundedly long tick that starves heartbeats and fan-out.
        # Virtual-clock sims never trip it (the clock is frozen inside
        # a control step), so sim runs stay byte-deterministic.
        _budget = _os.environ.get("SWARM_TICK_BUDGET_S", "")
        self.tick_budget_s = tick_budget_s if tick_budget_s is not None \
            else (float(_budget) if _budget else None)
        self._tick_deadline: Optional[float] = None

        # multi-tenant quota plane (scheduler/quota.py): admission-side
        # clamp + the host half of the quota mask column.  The filter
        # rides the shared pipeline so the host oracle's short-circuit
        # failure counts (and explanations) match the device kernel's
        # quota row.  SWARM_TENANT_QUOTA=0 disables enforcement
        # wholesale; with no tenants on the ClusterSpec the plane is a
        # no-op either way.
        self.quota = TenantLedger()
        self.quota_enabled = \
            _os.environ.get("SWARM_TENANT_QUOTA", "") != "0"
        self._quota_filter = QuotaFilter(self.quota)
        self.pipeline.add_filter(self._quota_filter)

        # gang scheduling (scheduler/gang.py): all-or-nothing placement
        # units + the pipeline gate.  Pure no-op bookkeeping until a
        # spec opts in via Placement.gang / ServiceSpec.depends_on.
        self.gang = gang_mod.GangState()

        # leadership epoch captured at tick/preassigned-pass start; every
        # commit of that pass is pinned to it (None = unfenced proposer)
        self._tick_epoch: Optional[int] = None
        self._stop = threading.Event()
        self._done = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # counters for benchmarking / tests.  The loop's own
        # (``ticks_by_*`` .. ``loop_wall_s``) advance once per debounce
        # episode of run(): which deadline fired each tick, the events
        # and commits the loop consumed, and the scheduler thread's CPU
        # seconds beside the wall seconds they were spent in (both stay
        # zero under an installed time source).
        self.stats = {"ticks": 0, "decisions": 0, "commit_seconds": 0.0,
                      "ticks_by_gap": 0, "ticks_by_max_latency": 0,
                      "events_handled": 0, "commits_seen": 0,
                      "thread_cpu_s": 0.0, "loop_wall_s": 0.0,
                      "host_route_groups": 0, "host_route_s": 0.0}

        # scheduler-plane saturation probe (obs/planes.py): backlog
        # depth and oldest pending age, read lazily at window-roll time.
        # plane() is resolved per call — planes.reset() rebinds the
        # table and a cached PlaneStats would go stale.  The probe holds
        # a WEAKREF: it must never pin a dead scheduler's task graph
        # (tests build many in one process).  Co-resident schedulers (HA
        # tests): last constructed owns the probe.
        import weakref
        _ref = weakref.ref(self)

        def _sched_probe():
            sched = _ref()
            if sched is None:
                return {}
            tasks = list(sched.unassigned_tasks.values())
            depth = float(len(tasks)
                          + len(sched.pending_preassigned_tasks))
            oldest = 0.0
            stamps = [t.status.timestamp for t in tasks
                      if t.status is not None and t.status.timestamp]
            if stamps:
                oldest = max(0.0, now() - min(stamps))
            return {"depth": depth, "oldest_age": oldest}
        _planes.plane(_planes.SCHEDULER).set_probe(_sched_probe)

    # ------------------------------------------------------------------ setup

    def _setup_tasks_list(self, tx: ReadTx) -> None:
        clusters = tx.find(Cluster, ByName("default"))
        self.quota.load_cluster(clusters[0] if clusters else None)
        for volume in tx.find(Volume):
            if volume.volume_info and volume.volume_info.volume_id:
                self.volumes.add_or_update_volume(volume)

        tasks_by_node: Dict[str, Dict[str, Task]] = {}
        for t in tx.find(Task):
            if (t.status.state < TaskState.PENDING
                    or t.status.state > TaskState.RUNNING):
                continue
            if (t.status.state == TaskState.PENDING
                    and t.desired_state > TaskState.COMPLETE):
                # updated/removed before ever being assigned
                continue
            self.all_tasks[t.id] = t
            if not t.node_id:
                self._enqueue(t)
                continue
            if t.status.state == TaskState.PENDING:
                self.preassigned_tasks.add(t.id)
                self.pending_preassigned_tasks[t.id] = t
                continue
            self.volumes.reserve_task_volumes(t)
            tasks_by_node.setdefault(t.node_id, {})[t.id] = t

        self._build_node_set(tx, tasks_by_node)

    def _build_node_set(self, tx: ReadTx,
                        tasks_by_node: Dict[str, Dict[str, Task]]) -> None:
        for n in tx.find(Node):
            resources = Resources()
            if n.description and n.description.resources:
                resources = n.description.resources
            self.node_set.add_or_update_node(
                NodeInfo(n, tasks_by_node.get(n.id), resources))

    # ------------------------------------------------------------- event loop

    def start(self) -> None:
        self._thread = threading.Thread(target=self.run, name="scheduler",
                                        daemon=True)
        self._thread.start()

    def run(self) -> None:
        try:
            self.pipeline.add_filter(VolumesFilter(self.volumes))
            # accepts_blocks: EventTaskBlocks on this store are this
            # scheduler's OWN commits (it is the only block producer on a
            # leader) — mirrors intentionally keep the pre-assignment
            # objects, so blocks are ignored below instead of being
            # expanded into len(block) synthesized self-echo events
            _, sub = self.store.view_and_watch(
                lambda tx: self._setup_tasks_list(tx),
                accepts_blocks=True)
            try:
                self._process_preassigned_tasks()
                self.tick()

                debounce_started: Optional[float] = None
                tick_required = False
                loop = _LoopAccount(self.stats)

                while not self._stop.is_set():
                    if debounce_started is None:
                        timeout = 0.2
                    else:
                        deadline = min(debounce_started + self.max_latency,
                                       self._last_event + self.debounce_gap)
                        timeout = max(0.0, deadline - now())
                    try:
                        event = sub.get(timeout=timeout) if timeout > 0 else None
                    except TimeoutError:
                        event = None
                    except Closed:
                        return

                    if event is None:
                        if debounce_started is not None:
                            fired = ("max_latency"
                                     if debounce_started + self.max_latency
                                     <= self._last_event + self.debounce_gap
                                     else "gap")
                            loop.fire(debounce_started, fired,
                                      len(self.unassigned_tasks),
                                      tick_required)
                            if len(self.pending_preassigned_tasks) > 0:
                                self._process_preassigned_tasks()
                            tick_end = None
                            if tick_required:
                                self.tick()
                                # before anything allocates: the tick
                                # paused the collector, and the first
                                # allocation after it pays the pause
                                tick_end = now()
                                self.stats["ticks_by_" + fired] += 1
                                tick_required = False
                            debounce_started = None
                            loop.close_episode(tick_end)
                        continue

                    if isinstance(event, EventCommit):
                        self._last_event = now()
                        loop.commits += 1
                        if debounce_started is None:
                            debounce_started = self._last_event
                            loop.open_episode(debounce_started)
                    elif isinstance(event, EventSnapshotRestore):
                        loop.events += 1
                        t_ev = now()
                        self._resync()
                        loop.event_s += now() - t_ev
                        tick_required = True
                    elif isinstance(event, Event):
                        loop.events += 1
                        if tracer.enabled:
                            t_ev = now()
                            tick_required |= self._handle_event(event)
                            loop.event_s += now() - t_ev
                        else:
                            tick_required |= self._handle_event(event)
            finally:
                self.store.queue.unsubscribe(sub)
        finally:
            self._done.set()

    _last_event = 0.0

    def stop(self) -> None:
        self._stop.set()
        self._done.wait(timeout=10)

    def _resync(self) -> None:
        self.unassigned_tasks.clear()
        self.unassigned_groups.clear()
        self._prio_pending = 0
        self.pending_preassigned_tasks.clear()
        self.preassigned_tasks.clear()
        self.all_tasks.clear()
        self.node_set = NodeSet()
        self.node_set.tracker = self.delta
        # a wholesale re-mirror invalidates every resident row at once
        self.delta.require_full("resync-store")
        # clear in place: the pipeline's VolumesFilter holds a reference
        self.volumes.clear()
        self.store.view(lambda tx: self._setup_tasks_list(tx))

    def _handle_event(self, ev: Event) -> bool:
        obj = ev.obj
        if isinstance(obj, Task):
            if ev.action == "create":
                return self._create_task(obj)
            if ev.action == "update":
                return self._update_task(obj)
            return self._delete_task(self.all_tasks.get(obj.id, obj))
        if isinstance(obj, Node):
            if ev.action == "delete":
                self.node_set.remove(obj.id)
                return False
            self._create_or_update_node(obj)
            return True
        if isinstance(obj, Volume) and ev.action == "update":
            if obj.volume_info and obj.volume_info.volume_id:
                self.volumes.add_or_update_volume(obj)
                return True
        if isinstance(obj, Cluster) and ev.action != "delete" \
                and obj.spec.annotations.name == "default":
            # live quota changes (the "default" cluster only — the one
            # _setup_tasks_list reads; any other Cluster object must
            # not wipe the quota table): a raised quota may unblock
            # pending tenant work, so the next tick must run
            self.quota.load_cluster(obj)
            return True
        return False

    # --------------------------------------------------------- state mirror

    def _enqueue(self, t: Task) -> None:
        self.unassigned_tasks[t.id] = t
        if task_priority(t) > 0:
            self._prio_pending += 1
        sv = t.spec_version
        key = (t.service_id, sv.index) if sv is not None else None
        self.unassigned_groups.setdefault(key, {})[t.id] = t

    def _dequeue(self, task_id: str) -> None:
        t = self.unassigned_tasks.pop(task_id, None)
        if t is not None:
            if task_priority(t) > 0:
                self._prio_pending -= 1
            sv = t.spec_version
            key = (t.service_id, sv.index) if sv is not None else None
            group = self.unassigned_groups.get(key)
            if group is not None:
                group.pop(task_id, None)
                if not group:
                    del self.unassigned_groups[key]

    def _create_task(self, t: Task) -> bool:
        if (t.status.state < TaskState.PENDING
                or t.status.state > TaskState.RUNNING):
            return False
        self.all_tasks[t.id] = t
        if not t.node_id:
            self._enqueue(t)
            return True
        if t.status.state == TaskState.PENDING:
            self.preassigned_tasks.add(t.id)
            self.pending_preassigned_tasks[t.id] = t
            return False
        info = self.node_set.node_info(t.node_id)
        if info is not None:
            info.add_task(t)
        return False

    def _update_task(self, t: Task) -> bool:
        if t.status.state < TaskState.PENDING:
            return False
        old = self.all_tasks.get(t.id)
        if t.status.state > TaskState.RUNNING:
            if old is None:
                return False
            if (t.status.state != old.status.state
                    and t.status.state in (TaskState.FAILED,
                                           TaskState.REJECTED)):
                if t.id not in self.preassigned_tasks:
                    info = self.node_set.node_info(t.node_id)
                    if info is not None:
                        info.task_failed(t)
            self._delete_task(old)
            return True
        if not t.node_id:
            if old is not None:
                self._delete_task(old)
            self.all_tasks[t.id] = t
            self._enqueue(t)
            return True
        if t.status.state == TaskState.PENDING:
            if old is not None:
                self._delete_task(old)
            self.preassigned_tasks.add(t.id)
            self.all_tasks[t.id] = t
            self.pending_preassigned_tasks[t.id] = t
            return False
        self.all_tasks[t.id] = t
        info = self.node_set.node_info(t.node_id)
        if info is not None:
            info.add_task(t)
        # a lower-priority task reaching RUNNING while a positive band
        # starves is preemption capacity arriving: tick.  Capacity-
        # blocked gang units (ROADMAP item 7 residual) are starved the
        # same way despite their 0 band, so they extend the trigger.
        return ((self._prio_pending > 0 or bool(self.gang.blocked))
                and t.status.state == TaskState.RUNNING)

    def _delete_task(self, t: Task) -> bool:
        # a preempted victim leaving the mirror (terminal status, or the
        # orchestrator's dead-slot delete) closes its exit-latency window
        self.preempt.observe_task_gone(t.id)
        self.all_tasks.pop(t.id, None)
        self.preassigned_tasks.discard(t.id)
        self.pending_preassigned_tasks.pop(t.id, None)
        self._dequeue(t.id)
        for va in t.volumes:
            self.volumes.release_volume(va.id, t.id)
        info = self.node_set.node_info(t.node_id)
        if info is not None and info.remove_task(t):
            return True
        return False

    def _create_or_update_node(self, n: Node) -> None:
        info = self.node_set.node_info(n.id)
        if n.description and n.description.resources:
            resources = n.description.resources.copy()
            if info is not None:
                for task in info.tasks.values():
                    reservations = task_reservations(task)
                    resources.memory_bytes -= reservations.memory_bytes
                    resources.nano_cpus -= reservations.nano_cpus
                    genericresource.consume(resources.generic,
                                            task.assigned_generic_resources)
        else:
            resources = Resources()
        if info is None:
            self.node_set.add_or_update_node(NodeInfo(n, None, resources))
        else:
            info.node = n
            info.available_resources = resources
            # in-place node swap bypasses the NodeInfo mutation hooks
            self.delta.mark(n.id)

    # -------------------------------------------------------------- decisions

    def _process_preassigned_tasks(self) -> None:
        with tracer.span("sched.preassigned", "sched",
                         pending=len(self.pending_preassigned_tasks)):
            self._process_preassigned_inner()

    def _process_preassigned_inner(self) -> None:
        self._tick_epoch = getattr(self.store._proposer,
                                   "leadership_epoch", None)
        decisions: Dict[str, SchedulingDecision] = {}
        pending = list(self.pending_preassigned_tasks.values())
        planner = self.batch_planner
        self.block_mode = self.store.supports_block_commit
        if planner is not None and hasattr(planner, "validate_preassigned"):
            # large same-spec batches (global services during a storm)
            # validate in one fused device call; whatever the device path
            # can't model (volumes, ports, small batches, rejections
            # needing per-filter explanations) falls through to the host
            # loop below.  Keyed like the group queues (_enqueue): tasks
            # of different spec versions have different constraints and
            # reservations and must not share one densified group
            by_spec: Dict[tuple, list] = {}
            for t in pending:
                key = (t.service_id,
                       t.spec_version.index if t.spec_version else -1)
                by_spec.setdefault(key, []).append(t)
            pending = []
            for group in by_spec.values():
                pending.extend(
                    planner.validate_preassigned(self, group, decisions))
        _, committed_ids, block_failed = self._commit_block_draft()
        for tid in committed_ids:
            self.pending_preassigned_tasks.pop(tid, None)
        for old, nid in block_failed:
            self.all_tasks[old.id] = old
            info = self.node_set.node_info(nid)
            if info is not None:
                info.remove_task(old)
        for t in pending:
            new_t = self._task_fit_node(t, t.node_id)
            if new_t is None:
                continue
            decisions[t.id] = SchedulingDecision(t, new_t)
        successful, failed = self._apply_scheduling_decisions(decisions)
        for d in successful:
            if d.new.status.state == TaskState.ASSIGNED:
                self.pending_preassigned_tasks.pop(d.old.id, None)
        for d in failed:
            self.all_tasks[d.old.id] = d.old
            info = self.node_set.node_info(d.new.node_id)
            if info is not None:
                info.remove_task(d.new)
            for va in d.new.volumes:
                self.volumes.release_volume(va.id, d.new.id)

    def tick(self) -> int:
        """Schedule the unassigned queue; returns number of decisions."""
        from ..utils.gctune import paused_gc
        t0 = now()
        # the other threads' CPU while the tick ran: who held the
        # interpreter while it was off the CPU (nothing read when off)
        threads0 = threadcpu.begin()
        with paused_gc(), tracer.span("sched.tick", "sched") as sp:
            n = self._tick_inner()
            if sp is not None:
                sp.args = {"decisions": n}
        if sp is not None and sp.cpu is not None:
            # wall minus thread CPU: what the tick spent off the CPU
            # (the GIL, the update lock, the device, the committer)
            sp.args["offcpu_ms"] = round((sp.duration - sp.cpu) * 1e3, 3)
            threadcpu.end(sp, threads0)
        _dt = now() - t0
        _TICK_TIMER.observe(_dt)
        _planes.plane(_planes.SCHEDULER).note_busy(_dt)
        return n

    def _tick_inner(self) -> int:
        t0 = now()
        self.stats["ticks"] += 1
        self._tick_deadline = (t0 + self.tick_budget_s
                               if self.tick_budget_s else None)
        # one reign per tick: every draft planned below commits under the
        # epoch read here or not at all (leadership-epoch fencing)
        self._tick_epoch = getattr(self.store._proposer,
                                   "leadership_epoch", None)
        self.block_mode = self.store.supports_block_commit
        # tenant-quota base usage for this tick, recomputed from the
        # fresh mirror; admission charges accumulate on top of it as
        # the priority-ordered queue below is walked
        if self.quota_enabled:
            with tracer.span("sched.quota_begin", "sched"):
                self.quota.begin_tick(self.all_tasks)
                self._ensure_quota_filter_last()
        decisions: Dict[str, SchedulingDecision] = {}

        # groups are maintained incrementally by _enqueue/_dequeue; take
        # them over wholesale — failures re-enqueue into fresh dicts during
        # the scheduling phase below
        with tracer.span("sched.batch_build", "sched") as sp:
            groups = self.unassigned_groups
            self.unassigned_groups = {}
            self.unassigned_tasks.clear()
            self._prio_pending = 0    # failures re-enqueue (re-count)
            one_off_tasks = groups.pop(None, {})
            if sp is not None:
                sp.args = {"groups": len(groups),
                           **self._queue_wait(groups, one_off_tasks, t0)}

        # gang units leave the normal walk and admit atomically first
        # (scheduler/gang.py) — a pure no-op extraction when no task
        # opts in, so non-gang ticks stay byte-identical
        with tracer.span("sched.gangs", "sched"):
            gang_units = gang_mod.take_gangs(groups, one_off_tasks)
            if gang_units or self.gang.blocked or self.gang.first_pending:
                self.gang.prune([k for k, _ in gang_units])
            n_gang = (gang_mod.admit_gangs(self, gang_units, decisions)
                      if gang_units else 0)

        planner = self.batch_planner
        use_pipeline = (self.pipeline_depth > 1 and self.block_mode
                        and planner is not None
                        and hasattr(planner, "dispatch_group"))
        pipe_block = 0       # block decisions already committed in-pipeline
        pipe_committed = 0
        pipe_failed: List[Tuple[Task, str]] = []
        if planner is not None and hasattr(planner, "begin_tick"):
            with tracer.span("plan.begin_tick", "plan"):
                planner.begin_tick(self)
        try:
            if use_pipeline:
                pipe_block, pipe_committed, pipe_failed = \
                    self._run_group_pipeline(groups, one_off_tasks,
                                             decisions)
            else:
                self._run_groups_serial(groups, one_off_tasks, decisions)
        finally:
            if planner is not None and hasattr(planner, "end_tick"):
                with tracer.span("plan.end_tick", "plan"):
                    planner.end_tick()

        n_decisions = n_gang + len(decisions) + pipe_block + sum(
            len(olds) for olds, _, _ in self.block_draft)
        with tracer.span("sched.commit", "sched", decisions=n_decisions):
            t_commit = now()
            n_committed, _, block_failed = self._commit_block_draft(
                want_ids=False)
            residual = n_committed or block_failed
            n_committed += pipe_committed
            block_failed = pipe_failed + block_failed
            for old, nid in block_failed:
                # mirror rollback (remove_task never reads node_id, so the
                # pre-assignment object works) + requeue for the next tick
                self.all_tasks[old.id] = old
                info = self.node_set.node_info(nid)
                if info is not None:
                    info.remove_task(old)
                self._enqueue(old)
            if residual:
                # pipelined drafts were timed on the committer thread;
                # only a residual serial commit lands here
                dt_block = now() - t_commit
                self.stats["commit_seconds"] += dt_block
                # the columnar path commits here, not through
                # _apply_scheduling_decisions — feed the timer both ways
                _COMMIT_TIMER.observe(dt_block)
            _, failed = self._apply_scheduling_decisions(decisions)
        for d in failed:
            self.all_tasks[d.old.id] = d.old
            info = self.node_set.node_info(d.new.node_id)
            if info is not None:
                info.remove_task(d.new)
            for va in d.new.volumes:
                self.volumes.release_volume(va.id, d.new.id)
            self._enqueue(d.old)

        # priority preemption: higher-priority groups the normal pass
        # left infeasible may evict strictly-lower-priority running work
        with tracer.span("sched.preempt_pass", "sched"):
            n_decisions += self._preempt_pass()

        if not decisions and self.volumes.frees_pending:
            # releases without new decisions (task shutdowns) must still
            # queue node-unpublish for now-unused volumes (the decisions
            # path runs free_volumes in its own finally)
            self.volumes.frees_pending = False
            try:
                self.store.batch(self.volumes.free_volumes)
            except Exception:
                log.exception("freeing volumes failed")

        self.stats["decisions"] += n_decisions
        return n_decisions

    def _queue_wait(self, groups, one_off_tasks, ts: float) -> dict:
        """``sched.batch_build``'s arguments about the tick's queue (the
        tracer is on): how many tasks it took and how long they had
        waited since their PENDING stamp."""
        tasks = [t for group in (*groups.values(), one_off_tasks)
                 for t in group.values() if t is not None]
        ages = [ts - t.status.timestamp for t in tasks
                if t.status.timestamp] or [0.0]
        return {"tasks": len(tasks),
                "wait_mean_ms": round(1e3 * sum(ages) / len(ages), 3),
                "wait_max_ms": round(1e3 * max(ages), 3)}

    def _collect_groups(self, groups, one_off_tasks, decisions
                        ) -> List[Dict[str, Task]]:
        """``_tick_groups`` walked to its end, under one span: pruning,
        the priority sort, the pipeline gate and the quota clamp."""
        with tracer.span("sched.groups", "sched") as sp:
            deferred = self.stats.get("deferred_tasks", 0)
            glist = list(self._tick_groups(groups, one_off_tasks,
                                           decisions))
            if sp is not None:
                sp.args = {"groups": len(glist),
                           "deferred": self.stats.get("deferred_tasks", 0)
                           - deferred}
        return glist

    def _tick_groups(self, groups, one_off_tasks, decisions=None
                     ) -> Iterable[Dict[str, Task]]:
        """The tick's task groups in scheduling order, with entries that
        were assigned out-of-band since enqueue dropped — one code path
        shared by the serial loop and the pipeline so group order (and
        therefore commit/event order) is identical in both modes.

        Order is the PRIORITY-ORDERED pending queue: higher priority
        classes schedule first so a constrained tick spends its capacity
        on the important band.  The sort is stable over the insertion-
        ordered group dicts (one-off tasks after service groups, as
        before), so ties — including the all-default-priority case every
        pre-priority workload is — keep the exact historical order and
        placements stay byte-deterministic."""
        entries: List[Tuple[int, Dict[str, Task]]] = []
        for group in groups.values():
            stale = [tid for tid, t in group.items()
                     if t is None or t.node_id]
            for tid in stale:
                del group[tid]
            if group:
                entries.append(
                    (task_priority(next(iter(group.values()))), group))
        for t in one_off_tasks.values():
            if t is not None and not t.node_id:
                entries.append((task_priority(t), {t.id: t}))
        entries.sort(key=lambda e: -e[0])
        yielded = 0
        for i, (_, group) in enumerate(entries):
            # tick deadline budget: once over budget — and with at
            # least one group yielded, so a single huge group still
            # makes progress — the rest of the queue re-enqueues for
            # the next tick and this tick commits partially.  The
            # priority sort above means the deferral always lands on
            # the LOWEST bands of this tick's queue.
            if (self._tick_deadline is not None and yielded > 0
                    and now() >= self._tick_deadline):
                deferred = 0
                for _, g in entries[i:]:
                    for t in g.values():
                        self._enqueue(t)
                        deferred += 1
                self.stats["partial_ticks"] = \
                    self.stats.get("partial_ticks", 0) + 1
                self.stats["deferred_tasks"] = \
                    self.stats.get("deferred_tasks", 0) + deferred
                _metrics.counter("swarm_scheduler_partial_ticks")
                _planes.plane(_planes.SCHEDULER).defer(deferred)
                log.info("tick budget %.3fs exceeded: %d tasks "
                         "deferred to the next tick",
                         self.tick_budget_s, deferred)
                return
            # pipeline gate (scheduler/gang.py): a group whose service
            # awaits an upstream DAG stage defers before admission so
            # gated work never consumes quota or placement capacity
            group = gang_mod.pipeline_gate(self, group, decisions)
            if not group:
                continue
            group = self._quota_admit(group, decisions)
            if group:
                yielded += 1
                yield group

    # -------------------------------------------------------- tenant quota

    def _ensure_quota_filter_last(self) -> None:
        """The QuotaFilter's checklist position is load-bearing: it
        must be LAST so the host pipeline's short-circuit failure
        counts (and the resulting 'no suitable node' explanation) match
        the device kernel's quota row, which is evaluated after every
        other mask.  Filters appended later (VolumesFilter in run()/the
        sim) would otherwise displace it — re-pin it each tick."""
        checklist = self.pipeline._checklist
        if checklist and checklist[-1].f is self._quota_filter:
            return
        for i, entry in enumerate(checklist):
            if entry.f is self._quota_filter:
                checklist.append(checklist.pop(i))
                return

    def _quota_admit(self, group: Dict[str, Task],
                     decisions) -> Dict[str, Task]:
        """Admission clamp for one group (scheduler/quota.py): charge
        fully-admitted groups, split partially-affordable ones (the
        deferred remainder re-queues with a quota message), and stamp a
        frozen BLOCKED verdict on groups whose tenant cannot admit even
        one task — those still flow to placement, where the quota mask
        column / QuotaFilter rejects every node so both paths produce
        identical ``over tenant quota`` diagnostics."""
        ledger = self.quota
        if not self.quota_enabled or not ledger.active:
            return group
        t0 = next(iter(group.values()))
        tenant = task_tenant(t0)
        res = task_reservations(t0)
        cpu_d, mem_d = int(res.nano_cpus), int(res.memory_bytes)
        admit = ledger.admit(tenant, cpu_d, mem_d, len(group))
        if admit is None:
            return group            # untenanted / unlimited
        if admit >= len(group):
            ledger.charge(tenant, cpu_d, mem_d, len(group))
            ledger.note_group_charge(t0, len(group))
            return group
        if admit <= 0:
            # exhausted: nothing charged — the mask/filter rejects the
            # whole group at placement (diagnostics parity by design)
            ledger.block_group(t0)
            return group
        # partial: admit the insertion-order prefix (deterministic),
        # defer the rest
        items = list(group.items())
        admitted = dict(items[:admit])
        ledger.charge(tenant, cpu_d, mem_d, admit)
        ledger.note_group_charge(t0, admit)
        self._quota_defer(tenant, items[admit:], decisions)
        return admitted

    def _quota_defer(self, tenant: str, items, decisions) -> None:
        """Defer clamped tasks: quota message + re-queue for the next
        tick (the _no_suitable_node discipline, with a quota-specific
        error so operators see the clamp, not a capacity problem)."""
        n = len(items)
        self.stats["quota_clamps"] = self.stats.get("quota_clamps", 0) + n
        _metrics.counter(f'swarm_quota_clamps{{tenant="{tenant}"}}', n)
        ts = now()
        for task_id, _t in items:
            self.quota.deferred_tasks.add(task_id)
        for task_id, t in items:
            new_t = t.copy()
            new_t.status.timestamp = ts
            new_t.status.err = f'over tenant quota (tenant "{tenant}")'
            self.all_tasks[task_id] = new_t
            self._enqueue(new_t)
            if decisions is not None:
                decisions[task_id] = SchedulingDecision(t, new_t)

    def _run_group_pipeline(self, groups, one_off_tasks, decisions
                            ) -> Tuple[int, int, List[Tuple[Task, str]]]:
        """Software-pipelined scheduling phase: while group i's draft
        commits on the committer thread (raft propose/apply, store
        overlay writes), group i+1's inputs are densified and its device
        plan dispatched — the device computes during the host commit
        instead of idling.  Placement order, mirror mutation order, and
        commit order all match the serial path exactly (each group's
        plan is fetched and applied before the next group's inputs are
        built), so placements are byte-identical; only the wall-clock
        interleaving changes.  Returns (block decisions drafted,
        committed count, failed pairs); the tick is acked only after the
        last draft resolved.

        Runs of >= 2 consecutive fusable groups take the FUSED
        many-service path (ops/fusedbatch.py): one densify + one
        scan-over-groups program per chunk instead of a round-trip per
        group, with the same per-group drafts flowing to the committer
        in the same order — a fused tick's store/event stream is
        byte-identical to the per-group tick's.
        """
        planner = self.batch_planner
        committer = _TickCommitter(self)
        inflight: Optional[Tuple[object, Dict[str, Task]]] = None
        n_block = 0
        glist = self._collect_groups(groups, one_off_tasks, decisions)
        can_fuse = hasattr(planner, "probe_fused_run")
        i = 0
        try:
            while i < len(glist):
                # probe reads only task specs + planner routing state, so
                # it is safe with a per-group plan still in flight
                specs = (planner.probe_fused_run(self, glist, i)
                         if can_fuse else [])
                if len(specs) >= 2:
                    if inflight is not None:
                        n_block += self._finish_inflight(
                            inflight, decisions, committer)
                        inflight = None
                    consumed, fused_block, spilled = self._run_fused(
                        specs, decisions, committer)
                    n_block += fused_block
                    i += consumed
                    if consumed and not spilled:
                        continue
                    # spilled at glist[i] (re-fusing replans against the
                    # same node state and deterministically spills again)
                    # or the run could not build/dispatch: glist[i]
                    # falls through to the per-group path below
                group = glist[i]
                i += 1
                if inflight is not None:
                    n_block += self._finish_inflight(inflight, decisions,
                                                     committer)
                    inflight = None
                handle = planner.dispatch_group(self, group, decisions)
                if handle is None:
                    # not device-planned: host oracle, synchronously (no
                    # plan is in flight here, so mirror mutation order
                    # matches the serial path)
                    self._schedule_group_host(group, decisions)
                else:
                    inflight = (handle, group)
            if inflight is not None:
                n_block += self._finish_inflight(inflight, decisions,
                                                 committer)
                inflight = None
        finally:
            if inflight is not None and hasattr(planner,
                                                "discard_inflight"):
                planner.discard_inflight()
            with tracer.span("sched.commit_join", "sched"):
                committed, failed = committer.close()
        return n_block, committed, failed

    def _run_groups_serial(self, groups, one_off_tasks, decisions) -> None:
        """Serial scheduling phase (pipeline_depth == 1, or no pipelined
        planner): groups schedule synchronously and drafts commit at
        tick end.  Fusable runs still take the fused many-service path —
        it is thread-free (chunk fetches block inline), so the sim's
        deterministic depth-1 control plane exercises the exact fused
        program production runs."""
        planner = self.batch_planner
        can_fuse = (planner is not None
                    and hasattr(planner, "probe_fused_run"))
        glist = self._collect_groups(groups, one_off_tasks, decisions)
        i = 0
        while i < len(glist):
            specs = (planner.probe_fused_run(self, glist, i)
                     if can_fuse else [])
            if len(specs) >= 2:
                consumed, _, spilled = self._run_fused(specs, decisions,
                                                       committer=None)
                i += consumed
                if consumed and not spilled:
                    continue
                # spilled group (glist[i]) goes per-group below
            self._schedule_task_group(glist[i], decisions)
            i += 1

    def _run_fused(self, specs, decisions,
                   committer: Optional[_TickCommitter]
                   ) -> Tuple[int, int, bool]:
        """Drive one fused run to completion: fetch each chunk (the next
        chunk computes on device meanwhile), apply its groups in order,
        and hand each group's draft to the committer (pipelined mode) or
        leave it on ``block_draft`` for the end-of-tick commit (serial
        mode) — exactly where the per-group path puts it.  Returns
        (groups consumed, block decisions drafted to the committer,
        spilled); a spill or a dead run stops early and the caller
        continues per-group from the first unconsumed group — without
        re-probing a spilled group for fusion, which would replan it
        against identical node state and spill again."""
        with tracer.span("sched.fused_run", "sched", groups=len(specs),
                         service=specs[0].t.service_id):
            planner = self.batch_planner
            run = planner.dispatch_fused_run(self, specs)
            if run is None:
                return 0, 0, False
            n_block = 0
            consumed = 0
            try:
                while True:
                    out = planner.fetch_fused_chunk(run)
                    if out is None:
                        break
                    xs, fcs, spills, start, count = out
                    for j in range(count):
                        gi = start + j
                        if bool(spills[j]):
                            # exact reference parity requires the host
                            # oracle for this group; later groups were
                            # planned against a placement that no longer
                            # happens, so the run aborts here
                            planner.note_fused_spill(run)
                            return consumed, n_block, True
                        planner.apply_fused_group(run, gi, xs[j], fcs[j],
                                                  decisions)
                        group = run.specs[gi].group
                        if group:
                            self._no_suitable_node(
                                group, decisions,
                                explanation=getattr(planner,
                                                    "last_explanation", ""))
                        consumed += 1
                        if committer is not None and self.block_draft:
                            draft, self.block_draft = self.block_draft, []
                            n_block += sum(len(olds)
                                           for olds, _, _ in draft)
                            committer.submit(draft)
                            committer.throttle(max(1,
                                                   self.pipeline_depth - 1))
            finally:
                planner.abort_fused_run(run)
            return consumed, n_block, False

    def _finish_inflight(self, inflight, decisions,
                         committer: _TickCommitter) -> int:
        """Fetch + apply an in-flight device plan, then hand its draft
        to the commit pipeline.  Returns the number of block decisions
        drafted for the group."""
        handle, group = inflight
        with tracer.span("sched.finish_group", "sched",
                         service=handle.t.service_id):
            planner = self.batch_planner
            handled = planner.fetch_group(handle)
            if not handled:
                # spill: exact reference parity requires the host oracle's
                # convergence loop for this group (same as the serial path)
                self._schedule_group_host(group, decisions)
                return 0
            if group:
                self._no_suitable_node(
                    group, decisions,
                    explanation=getattr(planner, "last_explanation", ""))
            if not self.block_draft:
                return 0
            draft, self.block_draft = self.block_draft, []
            n = sum(len(olds) for olds, _, _ in draft)
            committer.submit(draft)
            # bounded depth: one plan in flight on the device + at most
            # depth-1 unacked commits behind it
            committer.throttle(max(1, self.pipeline_depth - 1))
            return n

    # ----------------------------------------------------------- preemption

    def _preempt_pass(self) -> int:
        """Evict strictly-lower-priority running tasks for pending
        groups the normal scheduling pass could not place (the
        priority & preemption subsystem — scheduler/preempt.py hosts
        the oracle and policy state, ops/preempt.py the device kernel).

        Each successful pick commits its victims' shutdown AND the
        preemptor's assignment in one store transaction (the store pins
        the write to the leadership epoch at commit start; the pass
        itself refuses to run once the tick's reign is over), so the
        orchestrators observe an atomic swap and requeue the victims'
        slots at their own — lower — priority.  Returns the number of
        preemptor tasks placed."""
        sup = self.preempt
        if sup is None or not self.preempt_enabled:
            return 0
        entries: List[Tuple[int, Dict[str, Task]]] = []
        for key, group in self.unassigned_groups.items():
            if not group:
                continue
            if key is None:
                # the one-off bucket is heterogeneous (no shared spec):
                # each task is its own singleton group, exactly as the
                # normal pass schedules them (_tick_groups)
                for t in group.values():
                    if task_priority(t) > 0 \
                            or gang_mod.preempt_entitled(self, t):
                        entries.append((task_priority(t), {t.id: t}))
                continue
            t0 = next(iter(group.values()))
            prio = task_priority(t0)
            # positive bands may preempt; so may capacity-blocked or
            # aged gang units in the 0 band (ROADMAP item 7 residual:
            # the trigger predicate used to require priority > 0, so a
            # quota-entitled gang starved forever behind it)
            if prio > 0 or gang_mod.preempt_entitled(self, t0):
                entries.append((prio, group))
        if not entries:
            sup.export_inversions(0)
            return 0
        proposer = self.store._proposer
        if proposer is not None \
                and getattr(proposer, "leadership_epoch", None) \
                != self._tick_epoch:
            # the tick's reign is over: nothing may commit under it
            sup.export_inversions(0)
            return 0
        entries.sort(key=lambda e: -e[0])    # stable: insertion ties
        budget_rem = sup.begin_tick()
        device = getattr(self.batch_planner, "select_victims", None)
        placed_total = 0
        inversions = 0
        t_pass = now()
        for prio, group in entries:
            if budget_rem <= 0:
                sup.note_skipped("budget", len(group))
                inversions += len(group)
                continue
            t0 = next(iter(group.values()))
            if not preempt_mod.preemptable_group(t0):
                sup.note_skipped("unsupported", len(group))
                continue
            if gang_mod.is_gated(self, t0):
                # a pipeline-gated group cannot schedule even with the
                # capacity: evicting victims for it would be pure loss
                sup.note_skipped("gated", len(group))
                continue
            cpu_d, mem_d, gen_d = preempt_mod.demand_of(t0)
            headroom = None
            if self.quota_enabled and self.quota.active:
                # a tenant at (or over) its quota must not preempt its
                # way past it — QoS clamps at admission, full stop.
                # Headroom counts the group's OWN admission charge back
                # in: tasks already admitted (and charged) this tick are
                # entitled to preempt their way to placement.
                headroom = self.quota.preempt_headroom(
                    t0, cpu_d, mem_d, group)
                if headroom is not None and headroom <= 0:
                    sup.note_skipped("quota", len(group))
                    continue
            skipped_cd: List[int] = []
            cand = preempt_mod.build_candidates(
                self, t0, prio, sup.shut_this_tick, sup.cooldowns,
                sup.cooldown, skipped_cd,
                gen_kind=gen_d[0] if gen_d else None)
            if skipped_cd and skipped_cd[0]:
                sup.note_skipped("cooldown", skipped_cd[0])
            if cand is None:
                continue
            # host and device run the SAME capped pick count — the
            # shared-iteration contract the differential fuzz pins.
            # A quota'd tenant's picks are additionally capped at its
            # headroom (remaining quota + the group's own charge).
            n_picks = min(len(group), budget_rem)
            if headroom is not None:
                n_picks = min(n_picks, headroom)
            gen_val = gen_d[1] if gen_d else 0
            picks = None
            if device is not None:
                picks = device(cand, cpu_d, mem_d, gen_val, n_picks,
                               budget_rem)
            if picks is None:
                picks = preempt_mod.select_victims_host(
                    cand, cpu_d, mem_d, gen_val, n_picks, budget_rem)
            if picks:
                # gang groups evict ONLY (assign=False): per-pick
                # assignment would commit a strict subset of the gang;
                # the freed capacity lets the unit place atomically on
                # the next tick instead
                placed, victims_n = self._commit_preemption(
                    group, t0, prio, cand, picks,
                    assign=not gang_mod.is_gang(t0))
                budget_rem -= victims_n
                placed_total += placed
                if placed and self.quota_enabled and self.quota.active:
                    # keep the ledger honest for later same-tenant
                    # groups this pass: placements consume the group's
                    # phantom charge first; only the excess (fresh
                    # quota headroom) is new usage to charge
                    consumed = min(placed, self.quota.group_charge(t0))
                    self.quota.note_group_charge(t0, -consumed)
                    extra = placed - consumed
                    if extra > 0:
                        self.quota.charge(task_tenant(t0), cpu_d,
                                          mem_d, extra)
            # still-pending positive-priority tasks with live lower-
            # priority candidates = the inversion signal the
            # priority_inversion health check judges.  Count against
            # the unassigned queue, not the (possibly temporary
            # singleton) group dict.
            inversions += sum(1 for tid in group
                              if tid in self.unassigned_tasks)
        if placed_total:
            sup.observe_commit_latency(t_pass)
        sup.export_inversions(inversions)
        self.stats["preemptions"] = sup.stats["preemptions"]
        return placed_total

    def _commit_preemption(self, group: Dict[str, Task], t0: Task,
                           prio: int, cand, picks,
                           assign: bool = True
                           ) -> Tuple[int, int]:
        """Commit the selected picks: one atomic transaction per pick
        (victims' desired SHUTDOWN + preemption marker, preemptor's
        ASSIGNED write), each re-validated against the store row so a
        racing agent update skips the pick instead of corrupting it.
        ``assign=False`` (gang groups) commits the victims' shutdown
        WITHOUT placing the preemptor — a gang member may only commit
        with its whole unit (scheduler/gang.py), so the pass frees the
        capacity and the unit places atomically on a later tick.
        Returns (preemptors placed, victims shut down)."""
        from ..models.types import Annotations
        expanded = preempt_mod.replay_pick_victims(cand, picks)
        items = list(group.items())
        sup = self.preempt
        placed = 0
        victims_total = 0
        ts = now()
        for idx, (j, victims) in enumerate(expanded):
            if idx >= len(items):
                break
            tid, _mirror = items[idx]
            node_id = cand.infos[j].id
            result: Dict[str, object] = {}

            def cb(tx, tid=tid, node_id=node_id, victims=victims,
                   result=result):
                cur = None
                if assign:
                    cur = tx.get(Task, tid)
                    if cur is None or cur.node_id \
                            or cur.status.state != TaskState.PENDING \
                            or cur.desired_state > TaskState.COMPLETE:
                        return
                vrows = []
                for vt in victims:
                    vcur = tx.get(Task, vt.id)
                    if vcur is None \
                            or vcur.desired_state > TaskState.COMPLETE \
                            or vcur.status.state != TaskState.RUNNING \
                            or vcur.node_id != vt.node_id:
                        return    # a victim changed under us: skip pick
                    vrows.append(vcur)
                for vcur in vrows:
                    nv = vcur.copy()
                    nv.desired_state = TaskState.SHUTDOWN
                    # replace-don't-mutate: fresh Annotations so the
                    # committed marker never aliases the old object
                    nv.annotations = Annotations(
                        name=nv.annotations.name,
                        labels={**nv.annotations.labels,
                                "swarm.preempted.at": f"{ts:.3f}",
                                "swarm.preempted.by": t0.service_id,
                                "swarm.preempted.by.prio": str(prio),
                                "swarm.preempted.prio": str(
                                    task_priority(vcur))},
                        indices=dict(nv.annotations.indices))
                    tx.update(nv)
                if assign:
                    new_t = cur.copy()
                    new_t.node_id = node_id
                    new_t.status = TaskStatus(
                        state=TaskState.ASSIGNED, timestamp=ts,
                        message="scheduler assigned task to node "
                                "(preempted lower-priority tasks)")
                    tx.update(new_t)
                    result["task"] = new_t
                result["victims"] = victims

            try:
                self.store.update(cb)
            except Exception:
                # leadership loss or store failure: the pass stops; the
                # group's remainder stays pending (counted as inversions)
                log.exception("preemption transaction failed")
                break
            if "victims" not in result:
                # the pick was skipped (preemptor or a victim changed
                # under us): STOP — later picks' feasibility may depend
                # on this pick's evictions (same-node surplus carry),
                # so committing them could overcommit the node.  The
                # group's remainder retries next tick against fresh
                # state.
                break
            if assign:
                new_t = result["task"]
                self._dequeue(tid)
                self.all_tasks[tid] = new_t
                info = self.node_set.node_info(new_t.node_id)
                if info is not None:
                    info.add_task(new_t)
                placed += 1
            sup.note_preemptions(result["victims"], prio)
            victims_total += len(result["victims"])
        return placed, victims_total

    def _commit_block_draft(self, want_ids: bool = True
                            ) -> Tuple[int, Optional[List[str]],
                                       List[Tuple[Task, str]]]:
        """Commit the columnar assignment draft through
        store.commit_task_block — arrays end-to-end, no per-task objects
        (they materialize lazily on read).  Returns (committed count,
        committed task ids or None when ``want_ids`` is False, failed
        (mirror task, node_id) pairs for rollback)."""
        draft = self.block_draft
        if not draft:
            return 0, [] if want_ids else None, []
        self.block_draft = []
        return self._commit_draft(draft, want_ids)

    def _on_block_missing(self, old: Task, nid: str) -> None:
        # the draft already planted the task on the assigned node's
        # mirror (membership + reservations) — clean THAT node, not
        # old.node_id (which is empty pre-assignment)
        info = self.node_set.node_info(nid)
        if info is not None:
            info.remove_task(old)
        self._delete_task(self.all_tasks.get(old.id, old))

    def _commit_draft(self, draft: List[Tuple[List[Task], List[str], str]],
                      want_ids: bool = True,
                      missing_out: Optional[List[Tuple[Task, str]]] = None
                      ) -> Tuple[int, Optional[List[str]],
                                 List[Tuple[Task, str]]]:
        """Commit an explicit draft list (the body of
        ``_commit_block_draft``, callable from the tick committer with
        drafts taken off ``block_draft`` at submit time).

        ``missing_out``: when given (the committer-thread path),
        vanished-task cleanup is DEFERRED — (old, nid) pairs are
        appended for the main thread to process at tick end via
        ``_on_block_missing`` — because it mutates scheduler mirrors,
        which must not happen concurrently with the main thread's
        planning.  The serial path runs it inline (same thread)."""
        node_info = self.node_set.node_info
        raw_get = self.store.raw_get

        def on_missing(old: Task, nid: str) -> None:
            if missing_out is not None:
                missing_out.append((old, nid))
                return
            self._on_block_missing(old, nid)

        def on_assigned(old: Task, nid: str) -> bool:
            # stored task already >= ASSIGNED: commit only if our view of
            # the node is current (node-version conflict check)
            info = node_info(nid)
            if info is None:
                return False
            node = raw_get(Node, nid)
            return (node is not None and node.meta.version.index
                    == info.node.meta.version.index)

        n_committed = 0
        committed_ids: Optional[List[str]] = [] if want_ids else None
        failed: List[Tuple[Task, str]] = []
        for olds, nids, msg in draft:
            try:
                c, f = self.store.commit_task_block(
                    olds, nids, int(TaskState.ASSIGNED), msg,
                    on_missing, on_assigned,
                    guard_state=int(TaskState.ASSIGNED),
                    epoch=self._tick_epoch)
            except Exception:
                log.exception("scheduler block commit failed")
                failed.extend(zip(olds, nids))
                continue
            n_committed += len(c)
            if committed_ids is not None:
                committed_ids.extend(olds[i].id for i in c)
            failed.extend((olds[i], nids[i]) for i in f)
        return n_committed, committed_ids, failed

    def _apply_scheduling_decisions(
            self, decisions: Dict[str, SchedulingDecision]
    ) -> Tuple[List[SchedulingDecision], List[SchedulingDecision]]:
        """Commit ASSIGNED states (reference: scheduler.go:490).

        Decisions without volume attachments take the store's columnar
        bulk-commit path (one validation callback per task, no per-task
        transaction objects or defensive copies); volume-carrying decisions
        keep the transactional path that also stages volume publish updates.
        """
        if not decisions:
            return [], []
        t0 = now()
        try:
            with tracer.span("sched.apply_decisions", "sched",
                             decisions=len(decisions)) as sp:
                if sp is not None:
                    # the host route's decisions commit together, at the
                    # tick's end: the first service and how many
                    sp.args.update(_services_of(
                        [d.old.service_id for d in decisions.values()]))
                return self._apply_decisions_inner(decisions)
        finally:
            dt = now() - t0
            self.stats["commit_seconds"] += dt
            _COMMIT_TIMER.observe(dt)

    def _apply_decisions_inner(self, decisions):
        fast: List[SchedulingDecision] = []
        fast_tasks: List[Task] = []
        slow: Dict[str, SchedulingDecision] = {}
        for tid, d in decisions.items():
            new = d.new
            if new.volumes:
                slow[tid] = d
            else:
                fast.append(d)
                fast_tasks.append(new)

        successful: List[SchedulingDecision] = []
        failed: List[SchedulingDecision] = []
        if fast:
            s, f = self._apply_decisions_bulk(fast, fast_tasks)
            successful.extend(s)
            failed.extend(f)
        if slow:
            s, f = self._apply_decisions_tx(slow)
            successful.extend(s)
            failed.extend(f)
        elif fast:
            # the tx path frees volumes in its finally; mirror that here
            self.store.batch(self.volumes.free_volumes)
        return successful, failed

    def _apply_decisions_bulk(self, fast: List[SchedulingDecision],
                              fast_tasks: List[Task]):
        """Columnar commit via store.bulk_update_tasks; same semantic
        checks as commit_one below."""
        node_info = self.node_set.node_info
        raw_get = self.store.raw_get

        def on_assigned(new: Task) -> bool:
            # stored task already >= ASSIGNED: commit only if our view of
            # the node is current (node-version conflict check)
            info = node_info(new.node_id)
            if info is None:
                return False
            node = raw_get(Node, new.node_id)
            return (node is not None and node.meta.version.index
                    == info.node.meta.version.index)

        try:
            committed, failed_idx = self.store.bulk_update_tasks(
                fast_tasks, on_missing=self._delete_task,
                on_assigned=on_assigned, guard_state=TaskState.ASSIGNED,
                epoch=self._tick_epoch)
            return ([fast[i] for i in committed],
                    [fast[i] for i in failed_idx])
        except Exception:
            log.exception("scheduler bulk commit failed")
            return [], list(fast)

    def _apply_decisions_tx(
            self, decisions: Dict[str, SchedulingDecision]
    ) -> Tuple[List[SchedulingDecision], List[SchedulingDecision]]:
        successful: List[SchedulingDecision] = []
        failed: List[SchedulingDecision] = []
        try:
            if not decisions:
                return successful, failed

            def commit_one(tx, decision: SchedulingDecision) -> None:
                t = tx.get(Task, decision.old.id)
                if t is None:
                    self._delete_task(decision.new)
                    return
                new_status = decision.new.status
                old_status = t.status
                if (old_status.state == new_status.state
                        and old_status.message == new_status.message
                        and old_status.err == new_status.err):
                    return
                if old_status.state >= TaskState.ASSIGNED:
                    # already assigned by someone else; check node version
                    info = self.node_set.node_info(decision.new.node_id)
                    if info is None:
                        failed.append(decision)
                        return
                    node = tx.get(Node, decision.new.node_id)
                    if (node is None or node.meta.version.index
                            != info.node.meta.version.index):
                        failed.append(decision)
                        return
                volumes_to_update = []
                for va in decision.new.volumes:
                    v = tx.get(Volume, va.id)
                    if v is None:
                        failed.append(decision)
                        return
                    if v.spec.availability != 0:  # not ACTIVE
                        failed.append(decision)
                        return
                    if not any(ps.node_id == decision.new.node_id
                               for ps in v.publish_status):
                        v = v.copy()
                        from ..models.types import VolumePublishStatus
                        v.publish_status.append(VolumePublishStatus(
                            node_id=decision.new.node_id,
                            state=VolumePublishStatus.State.PENDING_PUBLISH))
                        volumes_to_update.append(v)
                # decision.new carries the mirror's version: if the task
                # changed in the store after the scheduler mirrored it (e.g.
                # an orchestrator bumped desired_state during the debounce
                # window), tx.update raises SequenceConflict and the
                # decision fails instead of overwriting the concurrent
                # write (reference: scheduler.go:607-611).
                try:
                    tx.update(decision.new)
                except Exception:
                    failed.append(decision)
                    return
                for v in volumes_to_update:
                    tx.update(v)
                successful.append(decision)

            # Batch bounds each transaction/raft proposal by actual change
            # count (decisions may add volume updates beyond one change each)
            def cb(batch: Batch) -> None:
                for decision in decisions.values():
                    batch.update(
                        lambda tx, d=decision: commit_one(tx, d))

            self.store.batch(cb)
            return successful, failed
        except Exception:
            # Reference-parity behavior (scheduler.go:639-644): on a batch
            # error, treat everything as failed so tasks are rolled back in
            # the mirror and re-enqueued.  Earlier sub-transactions may have
            # committed (best-effort batch) — the re-scheduled tasks then
            # hit the status-unchanged early return or node-version check.
            log.exception("scheduler tick transaction failed")
            failed.extend(successful)
            return [], failed
        finally:
            # always release no-longer-used volumes (reference: defer at
            # scheduler.go:501)
            self.store.batch(self.volumes.free_volumes)

    def _task_fit_node(self, t: Task, node_id: str) -> Optional[Task]:
        """Validate a preassigned task against its node
        (reference: scheduler.go:646)."""
        info = self.node_set.node_info(node_id)
        if info is None:
            return None
        self.pipeline.set_task(t)
        if not self.pipeline.process(info):
            new_t = t.copy()
            new_t.status.timestamp = now()
            new_t.status.err = self.pipeline.explain()
            self.all_tasks[t.id] = new_t
            return new_t
        new_t = t.copy()
        try:
            attachments = self.volumes.choose_task_volumes(t, info)
        except ValueError as e:
            new_t.status.timestamp = now()
            new_t.status.err = str(e)
            self.all_tasks[t.id] = new_t
            return new_t
        new_t.volumes = attachments
        new_t.status = TaskStatus(
            state=TaskState.ASSIGNED, timestamp=now(),
            message="scheduler confirmed task can run on preassigned node")
        self.all_tasks[t.id] = new_t
        info.add_task(new_t)
        return new_t

    # --------------------------------------------------------- group schedule

    def _schedule_task_group(self, task_group: Dict[str, Task],
                             decisions: Dict[str, SchedulingDecision]) -> None:
        if self.batch_planner is not None:
            handled = self.batch_planner.schedule_group(
                self, task_group, decisions)
            if handled:
                if task_group:
                    self._no_suitable_node(
                        task_group, decisions,
                        explanation=getattr(self.batch_planner,
                                            "last_explanation", ""))
                return
        self._schedule_group_host(task_group, decisions)

    def _schedule_group_host(self, task_group: Dict[str, Task],
                             decisions: Dict[str, SchedulingDecision],
                             defer_leftover: bool = True,
                             reason: Optional[str] = None) -> None:
        """One group placed on the host, under one ``sched.host_route``
        span that says why it rides there: ``reason`` (the gang scratch
        placement's) or, from the tick's own walk, what the planner's
        router said of the group (``host_small``: its break-even priced
        the host cheaper, which is its choice and no fault; ``breaker``,
        ``fallback``, ``strategy_host``, ``spill``, ``device_error``),
        ``no_planner`` without one.  ``stats["host_route_groups"]`` and
        ``stats["host_route_s"]`` count the same groups and their wall
        time with the tracer off too: a window in which no group rode
        the host reads 0 there, where the span reads nothing."""
        if reason is None:
            planner = self.batch_planner
            reason = "no_planner" if planner is None else (
                getattr(planner, "last_host_reason", None) or "fallback")
        t0 = time.perf_counter()
        with tracer.span("sched.host_route", "sched", tasks=len(task_group),
                         nodes=len(self.node_set.nodes), reason=reason):
            self._place_group_host(task_group, decisions, defer_leftover)
        self.stats["host_route_groups"] += 1
        self.stats["host_route_s"] += time.perf_counter() - t0

    def _place_group_host(self, task_group: Dict[str, Task],
                          decisions: Dict[str, SchedulingDecision],
                          defer_leftover: bool) -> None:
        """The host oracle path: spread tree + sorted round-robin
        (reference: scheduler.go:694 scheduleTaskGroup).  Non-spread
        strategies route to their host oracle (scheduler/strategy.py) —
        bit-equal to the device strategy kernel, so breaker/fallback
        demotions never move a task; an UNKNOWN strategy name degrades
        to the spread tree and counts the strategy fallback."""
        t = next(iter(task_group.values()))
        sname = strategy_mod.strategy_of(t)
        if sname != strategy_mod.SPREAD:
            sinfo = strategy_mod.resolve(sname)
            if sinfo is not None:
                try:
                    with tracer.span("sched.strategy_host", "sched",
                                     tasks=len(task_group),
                                     service=t.service_id):
                        strategy_mod.schedule_group_host(
                            self, task_group, decisions, sinfo)
                except Exception:
                    # a broken strategy (e.g. an unreadable learned-
                    # weights artifact) degrades to the spread tree —
                    # counted, never a failed tick
                    log.exception("strategy %s host oracle failed; "
                                  "spread path serves the group", sname)
                    strategy_mod.count_fallback(sname)
                else:
                    if task_group and defer_leftover:
                        self._no_suitable_node(task_group, decisions)
                    return
            else:
                strategy_mod.count_fallback(sname)
        self.pipeline.set_task(t)
        ts = now()

        def node_less(a: NodeInfo, b: NodeInfo) -> bool:
            fa = a.count_recent_failures(ts, t)
            fb = b.count_recent_failures(ts, t)
            if fa >= MAX_FAILURES or fb >= MAX_FAILURES:
                if fa > fb:
                    return False
                if fb > fa:
                    return True
            sa = a.active_tasks_count_by_service.get(t.service_id, 0)
            sb = b.active_tasks_count_by_service.get(t.service_id, 0)
            if sa != sb:
                return sa < sb
            return a.active_tasks_count < b.active_tasks_count

        prefs = t.spec.placement.preferences if t.spec.placement else []
        with tracer.span("sched.host_fallback", "sched",
                         tasks=len(task_group), service=t.service_id):
            tree = self.node_set.tree(t.service_id, prefs, len(task_group),
                                      self.pipeline.process, node_less)
            self._schedule_n_tasks_on_subtree(len(task_group), task_group,
                                              tree, decisions, node_less)
        if task_group and defer_leftover:
            # gang scratch placement (defer_leftover=False) leaves the
            # shortfall in task_group for the caller's atomic rollback
            self._no_suitable_node(task_group, decisions)

    def _schedule_n_tasks_on_subtree(self, n: int,
                                     task_group: Dict[str, Task],
                                     tree: DecisionTree,
                                     decisions: Dict[str, SchedulingDecision],
                                     node_less) -> int:
        """Recursive branch equalization (reference: scheduler.go:772)."""
        if tree.next is None:
            nodes = tree.ordered_nodes(self.pipeline.process)
            if not nodes:
                return 0
            return self._schedule_n_tasks_on_nodes(n, task_group, nodes,
                                                   decisions, node_less)

        tasks_scheduled = 0
        tasks_in_usable_branches = tree.tasks
        no_room: set = set()

        converging = True
        while (tasks_scheduled != n and len(no_room) != len(tree.next)
               and converging):
            usable = len(tree.next) - len(no_room)
            desired, remainder = divmod(
                tasks_in_usable_branches + n - tasks_scheduled, usable)
            converging = False
            for subtree in tree.next.values():
                if id(subtree) in no_room:
                    continue
                subtree_tasks = subtree.tasks
                if (subtree_tasks < desired
                        or (subtree_tasks == desired and remainder > 0)):
                    converging = True
                    to_assign = desired - subtree_tasks
                    if remainder > 0:
                        to_assign += 1
                    res = self._schedule_n_tasks_on_subtree(
                        to_assign, task_group, subtree, decisions, node_less)
                    if res < to_assign:
                        no_room.add(id(subtree))
                        tasks_in_usable_branches -= subtree_tasks
                    elif remainder > 0:
                        remainder -= 1
                    tasks_scheduled += res
        return tasks_scheduled

    def _schedule_n_tasks_on_nodes(self, n: int,
                                   task_group: Dict[str, Task],
                                   nodes: List[NodeInfo],
                                   decisions: Dict[str, SchedulingDecision],
                                   node_less) -> int:
        """Round-robin assignment over sorted candidates, re-filtering the
        mutated node after each placement (reference: scheduler.go:844)."""
        tasks_scheduled = 0
        failed_constraints: Dict[int, bool] = {}
        node_iter = 0
        node_count = len(nodes)
        for task_id, t in list(task_group.items()):
            if task_id in decisions:
                continue
            node = nodes[node_iter % node_count]
            try:
                attachments = self.volumes.choose_task_volumes(t, node)
            except ValueError:
                attachments = []

            new_t = t.copy()
            new_t.volumes = attachments
            new_t.node_id = node.id
            self.volumes.reserve_task_volumes(new_t)
            new_t.status = TaskStatus(
                state=TaskState.ASSIGNED, timestamp=now(),
                message="scheduler assigned task to node")
            self.all_tasks[t.id] = new_t
            node.add_task(new_t)

            decisions[task_id] = SchedulingDecision(t, new_t)
            del task_group[task_id]
            tasks_scheduled += 1
            if tasks_scheduled == n:
                return tasks_scheduled

            if node_iter + 1 < node_count:
                # first pass: level nodes to equal task counts
                next_node = nodes[(node_iter + 1) % node_count]
                if node_less(next_node, node):
                    node_iter += 1
            else:
                node_iter += 1

            orig_iter = node_iter
            while (failed_constraints.get(node_iter % node_count)
                   or not self.pipeline.process(nodes[node_iter % node_count])):
                failed_constraints[node_iter % node_count] = True
                node_iter += 1
                if node_iter - orig_iter == node_count:
                    return tasks_scheduled
        return tasks_scheduled

    def _no_suitable_node(self, task_group: Dict[str, Task],
                          decisions: Dict[str, SchedulingDecision],
                          explanation: Optional[str] = None) -> None:
        with tracer.span("sched.no_suitable_node", "sched",
                         tasks=len(task_group)) as sp:
            if sp is not None and task_group:
                sp.args["service"] = \
                    next(iter(task_group.values())).service_id
            if explanation is None:
                explanation = self.pipeline.explain()
            # one service lookup per group, not per task: all tasks in a
            # group share (service_id, spec_version)
            services: Dict[str, Optional[Service]] = {}
            for t in task_group.values():
                if t.service_id not in services:
                    services[t.service_id] = self.store.raw_get(
                        Service, t.service_id)
                service = services[t.service_id]
                if service is None:
                    continue
                new_t = t.copy()
                new_t.status.timestamp = now()
                sv = service.spec_version
                tv = new_t.spec_version
                if sv is not None and tv is not None and sv.index > tv.index:
                    if (t.status.state == TaskState.PENDING
                            and t.desired_state >= TaskState.SHUTDOWN):
                        new_t.status.state = TaskState.SHUTDOWN
                        new_t.status.err = ""
                else:
                    if explanation:
                        new_t.status.err = \
                            f"no suitable node ({explanation})"
                    else:
                        new_t.status.err = "no suitable node"
                    self._enqueue(new_t)
                self.all_tasks[t.id] = new_t
                decisions[t.id] = SchedulingDecision(t, new_t)
