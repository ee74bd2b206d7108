"""Store change events + matcher combinators.

The reference generates a typed event per (object kind × action) with
per-field "checks" (api/*.pb.go EventCreateTask etc.).  Here one generic
``Event`` carries (action, object, old_object) and matchers are plain
predicate builders — equally expressive, no codegen.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Optional, Tuple, Type

from ..utils.metrics import registry as _metrics

# cached Timer reference (Registry.reset() resets it in place): total
# wall time spent synthesizing per-task events out of coalesced blocks —
# the watch fan-out cost (``swarm_watch_fanout_latency`` on /metrics)
_FANOUT_TIMER = _metrics.timer("swarm_watch_fanout_latency")


@dataclass(frozen=True)
class Event:
    action: str              # "create" | "update" | "delete"
    obj: Any                 # the (new) object; for delete, the deleted object
    old: Any = None          # previous version on update, else None
    # store version this change committed at (the watch resume token).
    # 0 = unstamped: create/update events fall back to the object's own
    # meta.version.index; the store stamps deletes explicitly (a delete
    # burns a version index the payload cannot carry).
    version: int = 0

    @property
    def collection(self) -> str:
        return self.obj.collection


def event_version(ev: Event) -> int:
    """The change's store version — the resume token a watch client hands
    back to continue exactly after this event, on ANY member's replicated
    store (version stamping is part of the replicated state, so tokens
    survive reconnecting to a different member)."""
    if ev.version:
        return ev.version
    meta = getattr(ev.obj, "meta", None)
    return meta.version.index if meta is not None else 0


class EventTaskBlock:
    """One coalesced event for a columnar scheduler block commit.

    Carries the block arrays (pre-assignment tasks, node ids, version
    base, status columns); ``expand_events()`` lazily synthesizes the
    equivalent per-task update Events ONCE, shared across every
    subscriber — the watch queue expands it for subscribers that have
    not opted into block delivery (``accepts_blocks``), so existing
    consumers observe exactly the per-task stream the per-object commit
    path would have produced.  No reference counterpart: the reference
    publishes one event per task (state/store/memory.go publish); the
    block form is what lets the TPU scheduler's array-shaped commits
    stay legal with live watchers.
    """

    __slots__ = ("olds", "node_ids", "base_version", "state", "message",
                 "ts", "_events", "_per_node")

    def __init__(self, olds, node_ids, base_version, state, message, ts):
        self.olds = olds
        self.node_ids = node_ids
        self.base_version = base_version
        self.state = state
        self.message = message
        self.ts = ts
        self._events = None
        self._per_node = None

    def expand_events(self):
        """Synthesized per-task Events (cached; thread-safe because the
        build is idempotent and the final assignment is atomic).  One
        native pass when the commit plane's hot path is available
        (hotpath.c fanout_expand); the list comprehension below is the
        fallback and its differential oracle.  Runs on CONSUMER threads
        only — never under the store locks (swarmlint lock-discipline
        bans fanout_expand under them)."""
        events = self._events
        if events is None:
            from .. import native
            from .store import _materialize_task
            base = self.base_version
            state, message, ts = self.state, self.message, self.ts
            hp = native.get_commit()
            with _FANOUT_TIMER.time():
                if hp is not None:
                    from ..models.types import TaskState, TaskStatus
                    status = TaskStatus(state=TaskState(state),
                                        timestamp=ts, message=message)
                    events = hp.fanout_expand(self.olds, self.node_ids,
                                              base, ts, status, Event)
                else:
                    events = [
                        Event("update",
                              _materialize_task(old, nid, base + 1 + i,
                                                ts, state, message),
                              old)
                        for i, (old, nid) in enumerate(zip(self.olds,
                                                           self.node_ids))
                    ]
            self._events = events
        return events

    def per_node(self):
        """node_id -> [(old_task, version), ...] grouping (cached,
        shared).  Block-aware per-node consumers (dispatcher sessions)
        use this for an O(1) membership probe instead of filtering the
        synthesized per-task stream — with S agent sessions that turns
        O(tasks x S) predicate work into O(tasks + S).  Native pass when
        available (hotpath.c per_node_group); the loop below is the
        oracle."""
        grouped = self._per_node
        if grouped is None:
            from .. import native
            base = self.base_version
            hp = native.get_commit()
            if hp is not None:
                with _FANOUT_TIMER.time():
                    grouped = hp.per_node_group(self.olds, self.node_ids,
                                                base)
            else:
                grouped = {}
                for i, (old, nid) in enumerate(zip(self.olds,
                                                   self.node_ids)):
                    lst = grouped.get(nid)
                    if lst is None:
                        lst = grouped[nid] = []
                    lst.append((old, base + 1 + i))
            self._per_node = grouped
        return grouped

    def __len__(self) -> int:
        return len(self.olds)


@dataclass(frozen=True)
class EventCommit:
    """Published once per committed transaction — drives debounced loops
    (reference: state/store/memory.go publishes state.EventCommit)."""

    version: int = 0


@dataclass(frozen=True)
class EventSnapshotRestore:
    """Published after a full store restore; watchers must resync."""


Pred = Callable[[Any], bool]


def is_event(ev: Any) -> bool:
    return isinstance(ev, Event)


def match(kind: Optional[Type] = None, actions: Tuple[str, ...] = (),
          where: Optional[Pred] = None) -> Pred:
    """Build an event predicate: object kind, action set, and object filter.

    ``where`` is applied to the new object (or the deleted one).
    """

    def pred(ev: Any) -> bool:
        if not isinstance(ev, Event):
            return False
        if kind is not None and not isinstance(ev.obj, kind):
            return False
        if actions and ev.action not in actions:
            return False
        if where is not None and not where(ev.obj):
            return False
        return True

    return pred


def any_of(*preds: Pred) -> Pred:
    def pred(ev: Any) -> bool:
        return any(p(ev) for p in preds)
    return pred


def commit_or(pred: Pred) -> Pred:
    """Match commit events plus whatever ``pred`` matches."""

    def p(ev: Any) -> bool:
        return isinstance(ev, EventCommit) or pred(ev)
    return p
