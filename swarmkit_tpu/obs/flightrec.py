"""Black-box flight recorder: bounded ring buffers of recent control-
plane activity, dumpable as one post-mortem JSON.

Motivation (round-5 verdict): identical code swung 17x between
benchmark runs and sim invariant failures reported a verdict with no
surrounding state — the noise was *inferred*, never *observed*.  The
recorder keeps the last-N of everything cheap to capture continuously:

* **spans** — tapped from the PR-2 tracer via its ``sink`` hook (every
  ended span lands here even after the tracer's own buffer fills);
* **samples** — periodic registry snapshots recorded by
  ``obs/sampler.py`` (counter/timer-count deltas since ``rebase()``);
* **store events** — a block-aware subscription on a MemoryStore's
  watch queue, summarized to (action, kind, id, state) tuples;
* **raft transitions** — every ``RaftCore`` role change
  (follower/candidate/leader + term), via the core's ``on_transition``
  hook;
* **notes** — free-form marks (invariant violations, health
  transitions, fault injections).

Every record is stamped through ``models.types.now()`` — under the
simulator's VirtualClock a dump is a pure function of (scenario, seed),
byte for byte, which is what makes a post-mortem from a failing seed
*evidence* rather than anecdote (asserted in tests/test_flightrec.py).

Dump triggers: ``/debug/flightrec`` on the DebugServer (on demand),
and ``sim.scenario.run_scenario`` (automatically on invariant violation
or crashed-scenario exit; path + sha land in the report).
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import threading
from collections import deque
from typing import Any, Dict, List, Optional

from ..models import types as _types

log = logging.getLogger("flightrec")


class Ring:
    """Bounded append-only buffer; evictions are counted, not silent."""

    __slots__ = ("_buf", "dropped")

    def __init__(self, maxlen: int):
        self._buf: deque = deque(maxlen=maxlen)
        self.dropped = 0

    def append(self, item: Any) -> None:
        buf = self._buf
        if len(buf) == buf.maxlen:
            # approximate under concurrent appends (no lock on the hot
            # path); exact in the single-threaded simulator
            self.dropped += 1
        buf.append(item)

    def items(self) -> List[Any]:
        return list(self._buf)

    def clear(self) -> None:
        self._buf.clear()
        self.dropped = 0

    def __len__(self) -> int:
        return len(self._buf)


class FlightRecorder:
    """Always-on black box.  Enable/disable is one attribute check on
    every record path, so an idle recorder costs nothing measurable."""

    def __init__(self, max_spans: int = 4096, max_samples: int = 512,
                 max_store_events: int = 4096, max_raft: int = 1024,
                 max_notes: int = 1024):
        self.enabled = False
        #: True while a deterministic capture (the simulator) owns the
        #: recorder: dumps omit anything wall-clock-tainted (live
        #: registry totals) so the sha is a pure function of the seed
        self.deterministic = False
        self._maxlens = (max_spans, max_samples, max_store_events,
                         max_raft, max_notes)
        self._fresh_rings()
        self._lock = threading.Lock()
        # store taps: queue id -> (queue, subscription).  A dict, not a
        # single slot, so two managers in one process (HA tests) can
        # each tap their own store without stealing the other's.
        self._store_subs: Dict[int, tuple] = {}
        #: optional per-raw-event tap (obs.journey.JourneyLedger
        #: .handle_event): the journey ledger rides the SAME store
        #: subscriptions instead of adding its own, so the watch plane
        #: pays one consumer for both
        self.journey_sink = None

    def _fresh_rings(self) -> None:
        (max_spans, max_samples, max_store_events, max_raft,
         max_notes) = self._maxlens
        self.spans = Ring(max_spans)
        self.samples = Ring(max_samples)
        self.store_events = Ring(max_store_events)
        self.raft = Ring(max_raft)
        self.notes = Ring(max_notes)

    # ------------------------------------------------------------- recording

    def record_span(self, sp) -> None:
        """Tracer sink callback (obs.trace.Tracer.sink): one compact row
        per ended span, kept even after the tracer's buffer fills."""
        if not self.enabled:
            return
        self.spans.append((sp.name, sp.cat, sp.start, sp.end,
                           sp.span_id, sp.parent_id))

    def record_sample(self, sample: Dict[str, Any]) -> None:
        if not self.enabled:
            return
        self.samples.append(sample)

    def record_raft(self, member_id: str, role: str, term: int) -> None:
        if not self.enabled:
            return
        self.raft.append((_types.now(), member_id, role, term))

    def note(self, msg: str) -> None:
        if not self.enabled:
            return
        self.notes.append((_types.now(), msg))

    # ---------------------------------------------------------- store events

    def watch_store(self, store) -> None:
        """Subscribe to a MemoryStore's watch queue (block-aware).  The
        subscription buffers until ``poll_store`` drains it — call that
        from the sampler tick (production) or the engine (sim); a dump
        drains implicitly.  Idempotent per store; independent stores can
        be tapped concurrently."""
        q = store.queue
        if id(q) not in self._store_subs:
            self._store_subs[id(q)] = (
                q, q.subscribe(accepts_blocks=True))
        # watch-plane saturation probe: the recorder's taps are the
        # canonical store consumers, so their summed backlog (in store
        # versions — Subscription.backlog counts block expansions) is
        # the consumer plane's lag.  Registered here, not in state/
        # watch.py: the state layer must not import obs (layering rule).
        from . import planes as _planes
        _planes.plane(_planes.WATCH).set_probe(self._watch_backlog)

    def _watch_backlog(self) -> Dict[str, float]:
        depth = 0.0
        for _q, sub in list(self._store_subs.values()):
            try:
                depth += float(sub.backlog())
            except Exception:
                pass
        return {"depth": depth}

    def unwatch_store(self, store=None) -> None:
        """Detach a store tap — only ``store``'s when given (a stopping
        manager must not tear down another manager's tap), every tap
        when called bare."""
        if store is not None:
            entries = [self._store_subs.pop(id(store.queue), None)]
        else:
            entries = list(self._store_subs.values())
            self._store_subs.clear()
        for entry in entries:
            if entry is None:
                continue
            q, sub = entry
            try:
                q.unsubscribe(sub)
            except Exception:
                pass

    def poll_store(self) -> int:
        """Drain every store subscription into the ring; returns how
        many rows were recorded."""
        t = _types.now()
        n = 0
        sink = self.journey_sink
        for q, sub in list(self._store_subs.values()):
            while True:
                ev = sub.poll()
                if ev is None:
                    break
                if sink is not None:
                    try:
                        sink(ev)
                    except Exception:
                        log.exception("journey sink failed")
                row = self._summarize_event(t, ev)
                if row is not None and self.enabled:
                    self.store_events.append(row)
                    n += 1
        return n

    @staticmethod
    def _summarize_event(t: float, ev) -> Optional[tuple]:
        from ..state.events import Event, EventSnapshotRestore, \
            EventTaskBlock
        if isinstance(ev, EventTaskBlock):
            return (t, "task_block", "", int(ev.state), len(ev))
        if isinstance(ev, EventSnapshotRestore):
            return (t, "snapshot_restore", "", 0, 0)
        if isinstance(ev, Event):
            obj = ev.obj
            state = getattr(getattr(obj, "status", None), "state", 0)
            return (t, f"{ev.action} {type(obj).__name__.lower()}",
                    getattr(obj, "id", ""), int(state), 1)
        return None   # EventCommit / WAKE: too chatty to record

    # ------------------------------------------------------------- lifecycle

    def reset(self, deterministic: bool = False) -> None:
        """Start a fresh capture.  Rings are REBOUND, not cleared in
        place, so a state captured by ``save_state`` before the reset
        survives (same contract as Tracer.reset/save_state)."""
        with self._lock:
            self._fresh_rings()
            self.deterministic = deterministic

    def save_state(self):
        """Capture rings + flags + taps so an embedded recording session
        (the sim runner) can restore the embedding process's black box
        afterwards."""
        with self._lock:
            return (self.spans, self.samples, self.store_events,
                    self.raft, self.notes, self.enabled,
                    self.deterministic, dict(self._store_subs),
                    self.journey_sink)

    def restore_state(self, state) -> None:
        with self._lock:
            (self.spans, self.samples, self.store_events, self.raft,
             self.notes, self.enabled, self.deterministic,
             self._store_subs, self.journey_sink) = state

    # ----------------------------------------------------------------- dump

    def snapshot(self) -> Dict[str, Any]:
        """One post-mortem document.  Deterministic captures carry only
        seed-derived content; live captures additionally embed the
        current registry counters so a dump stands alone."""
        self.poll_store()
        with self._lock:
            doc: Dict[str, Any] = {
                "spans": [list(r) for r in self.spans.items()],
                "samples": self.samples.items(),
                "store_events": [list(r) for r in
                                 self.store_events.items()],
                "raft_transitions": [list(r) for r in self.raft.items()],
                "notes": [list(r) for r in self.notes.items()],
                "dropped": {
                    "spans": self.spans.dropped,
                    "samples": self.samples.dropped,
                    "store_events": self.store_events.dropped,
                    "raft_transitions": self.raft.dropped,
                    "notes": self.notes.dropped,
                },
            }
        if not self.deterministic:
            from ..utils.metrics import registry
            doc["counters"] = dict(sorted(
                registry.counters_snapshot().items()))
            # device-telemetry + compile-cache snapshot at dump time: a
            # post-mortem must distinguish a recompile storm from a
            # transfer storm without a second capture.  Omitted from
            # deterministic (sim) captures with the registry counters —
            # its ns fields are wall-clock-tainted.
            from . import devicetelemetry as _devtel
            doc["device_telemetry"] = _devtel.snapshot()
        # full journeys of invariant-implicated tasks: a violation note
        # naming a sampled task id gets that task's complete milestone
        # ledger in the post-mortem, so "task X stuck" arrives WITH
        # where in the pipeline it stuck.  Seed-pure in deterministic
        # captures (notes and milestones both are).
        ledger = getattr(self.journey_sink, "__self__", None)
        if ledger is not None and hasattr(ledger, "journeys"):
            viol = [str(m) for _t, m in doc["notes"]
                    if str(m).startswith("INVARIANT")]
            if viol:
                imp = {tid: ms
                       for tid, ms in ledger.journeys().items()
                       if any(tid in n for n in viol)}
                if imp:
                    doc["implicated_journeys"] = imp
        return doc

    def dump_json(self) -> str:
        return json.dumps(self.snapshot(), sort_keys=True,
                          separators=(",", ":"))

    def dump(self, path: str) -> str:
        """Write the post-mortem JSON; returns its sha256 (the identity
        sim reports record next to the artifact path)."""
        body = self.dump_json()
        with open(path, "w") as f:
            f.write(body)
        return hashlib.sha256(body.encode()).hexdigest()


# the process-wide recorder; obs.trace installs it as the tracer sink
flightrec = FlightRecorder()


# --------------------------------------------------------- crash hook
#
# Control-loop threads (scheduler, orchestrators, dispatcher worker,
# the raft loop...) are daemon threads: an unhandled exception kills the
# thread silently and the manager limps on without it.  The crash hook
# turns that into evidence — the black box is dumped as a post-mortem
# (path + sha logged) BEFORE the thread dies, with the crash itself as
# the final note.  Installed by Manager.run, removed by Manager.stop;
# ref-counted so co-resident managers (HA tests) compose.

_crash_hook_lock = threading.Lock()
_crash_hook_refs = 0
_prev_excepthook = None
_crash_seq = 0


def _crash_dump(thread_name: str, exc_type, exc_value) -> None:
    global _crash_seq
    if not flightrec.enabled:
        return
    flightrec.note(f"thread {thread_name!r} crashed: "
                   f"{exc_type.__name__}: {exc_value}")
    safe = "".join(c if c.isalnum() or c in "-_" else "-"
                   for c in thread_name) or "thread"
    d = os.environ.get("SWARM_FLIGHTREC_DIR") or "."
    with _crash_hook_lock:
        _crash_seq += 1
        seq = _crash_seq
    path = os.path.join(
        d, f"flightrec_crash_{safe}_{os.getpid()}_{seq}.json")
    try:
        sha = flightrec.dump(path)
    except OSError:
        log.exception("crash post-mortem dump failed")
        return
    log.error("thread %r died with %s; flight-recorder post-mortem "
              "dumped to %s (sha256 %s)", thread_name,
              exc_type.__name__, path, sha)


def _crash_excepthook(args) -> None:
    try:
        if args.exc_type is not SystemExit:
            _crash_dump(getattr(args.thread, "name", None) or "unknown",
                        args.exc_type, args.exc_value)
    except Exception:
        log.exception("flightrec crash hook failed")
    finally:
        prev = _prev_excepthook or threading.__excepthook__
        prev(args)


def install_crash_hook() -> None:
    """Route ``threading.excepthook`` through the flight recorder
    (chained: the previous hook still prints the traceback)."""
    global _crash_hook_refs, _prev_excepthook
    with _crash_hook_lock:
        _crash_hook_refs += 1
        if _crash_hook_refs == 1:
            _prev_excepthook = threading.excepthook
            threading.excepthook = _crash_excepthook


def uninstall_crash_hook() -> None:
    global _crash_hook_refs, _prev_excepthook
    with _crash_hook_lock:
        if _crash_hook_refs == 0:
            return
        _crash_hook_refs -= 1
        if _crash_hook_refs == 0 \
                and threading.excepthook is _crash_excepthook:
            threading.excepthook = \
                _prev_excepthook or threading.__excepthook__
            _prev_excepthook = None
