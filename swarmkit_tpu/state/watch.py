"""Bounded pub/sub event queue (reference: watch/watch.go:20).

The store publishes every committed change here; control loops subscribe with
a predicate.  Semantics mirror the reference's Queue built on go-events:

* ``subscribe``   — unbounded buffered channel; slow consumers grow the buffer.
* ``subscribe_limited(n)`` — bounded buffer; on overflow the subscription is
  CLOSED (the consumer sees the closure and must resync from a store view),
  matching the reference's close-on-overflow sink behavior.

A subscription is a thread-safe iterator/queue hybrid: ``get(timeout)`` or
iteration; ``close()`` cancels.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Any, Callable, Iterable, Iterator, List, Optional

from ..models.types import now as _now, time_source_installed \
    as _virtual_time

Predicate = Callable[[Any], bool]


class Closed(Exception):
    """The subscription was closed (by cancel or overflow)."""


class Subscription:
    def __init__(self, queue: "Queue", predicate: Optional[Predicate],
                 limit: Optional[int], accepts_blocks: bool = False):
        self._queue = queue
        self._predicate = predicate
        self._limit = limit
        self._buf: deque = deque()
        self._cond = threading.Condition()
        self._closed = False
        self.overflowed = False
        #: opt-in: deliver coalesced block events (objects exposing
        #: ``expand_events``) as-is instead of expanding them into their
        #: per-item events — block-aware consumers read the arrays
        #: directly and skip the per-item synthesis entirely
        self.accepts_blocks = accepts_blocks

    # -- producer side -----------------------------------------------------
    def _publish(self, event: Any) -> bool:
        """Buffer ``event`` if it is for this subscription; True where it
        was buffered, so the consumer's thread will wake."""
        if not self.accepts_blocks \
                and getattr(event, "expand_events", None) is not None:
            # coalesced block for a per-item consumer: buffer the block
            # AS-IS and expand at consumption time, in the CONSUMER's
            # thread — the committing writer pays O(subscribers) per
            # block, never O(items).  The expansion itself is cached on
            # the block, shared across subscribers; each subscriber pays
            # only its own predicate filter.
            with self._cond:
                if self._closed:
                    return False
                if self._limit is not None and \
                        len(self._buf) + len(event) > self._limit:
                    self.overflowed = True
                    self._closed = True
                    self._cond.notify_all()
                    return False
                self._buf.append(event)
                self._cond.notify()
            return True
        if self._predicate is not None:
            try:
                if not self._predicate(event):
                    return False
            except Exception:
                return False
        with self._cond:
            if self._closed:
                return False
            if self._limit is not None and len(self._buf) >= self._limit:
                # close-on-overflow: consumer must resync
                self.overflowed = True
                self._closed = True
                self._cond.notify_all()
                return False
            self._buf.append(event)
            self._cond.notify()
        return True

    # -- consumer side -----------------------------------------------------
    def _needs_expand(self, item: Any) -> bool:
        return not self.accepts_blocks and \
            getattr(item, "expand_events", None) is not None

    def _expand(self, block: Any) -> List[Any]:
        """Synthesize + filter a block's per-item events.  Runs WITHOUT
        _cond held: expansion is O(len(block)) and must never stall the
        publishing (committing) thread, which takes _cond in _publish.
        A predicate exception drops only the offending event, matching
        the per-event publish path's granularity.  The expansion is
        shared across subscribers (cached on the block, native when
        available); the per-subscriber predicate filter runs as one
        native pass too (hotpath.c fanout_filter) with the loop below
        as fallback and oracle."""
        try:
            events = block.expand_events()
        except Exception:
            return []
        pred = self._predicate
        if pred is None:
            return list(events)
        from .. import native
        hp = native.get_commit()
        if hp is not None:
            return hp.fanout_filter(events, pred)
        out = []
        for e in events:
            try:
                if pred(e):
                    out.append(e)
            except Exception:
                continue
        return out

    def _splice_front_locked(self, events: List[Any]) -> None:
        self._buf.extendleft(reversed(events))

    def get(self, timeout: Optional[float] = None) -> Any:
        """Next event; blocks up to ``timeout`` (forever when None).
        Buffered blocks expand on THIS thread, outside the lock — with
        one consumer per subscription (the usage contract) ordering is
        preserved by re-splicing the tail at the buffer front.  The
        deadline reads through models.types.now() — the determinism
        seam — so a simulated consumer's wait window is a function of
        the virtual clock, not the host's.  In production now() is
        wall-clock: like every other deadline in the control plane
        (dispatcher TTLs, scheduler debounce), a clock step moves it —
        the price of one observable time axis end to end.  A generous
        REAL-time backstop bounds the wait when an installed virtual
        clock is frozen (a test forgot to step it): raise TimeoutError,
        never hang the consumer thread."""
        import time as _time
        deadline = None if timeout is None else _now() + timeout
        if timeout is None:
            real_deadline = None
        else:
            # the backstop must read host time by definition
            # swarmlint: disable=determinism-seam
            real_deadline = _time.monotonic() + timeout * 16.0 + 1.0
        while True:
            with self._cond:
                item = self._buf.popleft() if self._buf else None
                if item is None:
                    if self._closed:
                        raise Closed()
                    if deadline is None:
                        self._cond.wait()
                    else:
                        remaining = deadline - _now()
                        if remaining <= 0:
                            raise TimeoutError()
                        # virtual remaining is not real seconds: under
                        # an installed virtual clock wait in short real
                        # slices so a deadline stepped past mid-wait is
                        # observed promptly, not after the full slice
                        self._cond.wait(min(remaining, 0.05)
                                        if _virtual_time()
                                        else remaining)
                    item = self._buf.popleft() if self._buf else None
                    if item is None:
                        if self._closed:
                            raise Closed()
                        if deadline is not None:
                            # backstop read, see above
                            # swarmlint: disable=determinism-seam
                            hung = _time.monotonic() >= real_deadline
                            if hung or _now() >= deadline:
                                raise TimeoutError()
                        continue
            if not self._needs_expand(item):
                return item
            events = self._expand(item)
            if not events:
                continue   # block filtered to nothing: keep waiting
            if len(events) > 1:
                with self._cond:
                    self._splice_front_locked(events[1:])
            return events[0]

    WAKE = object()   # sentinel returned by get() after wake()

    def wake(self) -> None:
        """Make a blocked get() return Subscription.WAKE promptly — lets a
        worker that multiplexes timers with this subscription react to new
        timers without waiting out its poll timeout."""
        with self._cond:
            if self._closed:
                return
            self._buf.append(Subscription.WAKE)
            self._cond.notify()

    def poll(self) -> Optional[Any]:
        while True:
            with self._cond:
                if not self._buf:
                    return None
                item = self._buf.popleft()
            if not self._needs_expand(item):
                return item
            events = self._expand(item)
            if not events:
                continue
            if len(events) > 1:
                with self._cond:
                    self._splice_front_locked(events[1:])
            return events[0]

    def backlog(self) -> int:
        """Buffered, unconsumed entries — coalesced blocks count their
        expansion size, so with one entry per committed version this is
        the subscription's lag in store versions (the watch plane's
        queue-depth probe reads it; observability only, never consumes)."""
        with self._cond:
            items = list(self._buf)
        n = 0
        for it in items:
            if getattr(it, "expand_events", None) is not None:
                try:
                    n += len(it)
                    continue
                except Exception:
                    pass
            n += 1
        return n

    def drain(self) -> List[Any]:
        with self._cond:
            raw = list(self._buf)
            self._buf.clear()
        items: List[Any] = []
        for item in raw:
            if self._needs_expand(item):
                items.extend(self._expand(item))
            else:
                items.append(item)
        return items

    def __iter__(self) -> Iterator[Any]:
        while True:
            try:
                yield self.get()
            except Closed:
                return

    @property
    def closed(self) -> bool:
        with self._cond:
            return self._closed and not self._buf

    def close(self) -> None:
        with self._cond:
            self._closed = True
            self._cond.notify_all()


class Queue:
    """Broadcast queue: every event goes to every matching subscriber."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._subs: List[Subscription] = []

    def publish(self, event: Any, delivered: Optional[set] = None) -> None:
        """Hand ``event`` to every subscription; where ``delivered`` is a
        set, add to it each subscription that buffered the event."""
        with self._lock:
            subs = list(self._subs)
        for sub in subs:
            if sub._publish(event) and delivered is not None:
                delivered.add(sub)

    def has_subscribers(self) -> bool:
        return bool(self._subs)

    def subscriber_count(self) -> int:
        return len(self._subs)

    def publish_all(self, events: Iterable[Any]) -> None:
        for e in events:
            self.publish(e)

    def subscribe(self, predicate: Optional[Predicate] = None,
                  accepts_blocks: bool = False) -> Subscription:
        return self._add(Subscription(self, predicate, None,
                                      accepts_blocks))

    def subscribe_limited(self, limit: int,
                          predicate: Optional[Predicate] = None,
                          accepts_blocks: bool = False) -> Subscription:
        return self._add(Subscription(self, predicate, limit,
                                      accepts_blocks))

    def _add(self, sub: Subscription) -> Subscription:
        with self._lock:
            self._subs.append(sub)
        return sub

    def unsubscribe(self, sub: Subscription) -> None:
        sub.close()
        with self._lock:
            try:
                self._subs.remove(sub)
            except ValueError:
                pass

    def close(self) -> None:
        with self._lock:
            subs = list(self._subs)
            self._subs.clear()
        for sub in subs:
            sub.close()
