"""Span-based tracing core.

Dapper-style explicit spans (start/end with parent links) recorded into a
bounded in-memory buffer and exported as Chrome trace-event JSON — the
format ``chrome://tracing`` and Perfetto load directly.

Design constraints, in priority order:

* **Near-zero cost when disabled.**  ``tracer.span(...)`` on a disabled
  tracer returns a shared no-op context manager: one attribute load and
  one call, no allocation.  The hot paths (scheduler loop and tick,
  planner group loop, store commit) are instrumented at *phase*
  granularity — per debounce episode / per tick / per group, never per
  task or per event.  What it costs when on is measured on the chip by
  the benchmark's ``--trace 1`` run against its ``--trace 0`` run
  (PERF.md 6).

* **One clock, and it cannot step.**  Timestamps are wall-clock seconds
  (what the benchmark's harness and its idle-gap naming compare them
  with), read as ``epoch_wall + (perf_counter() - epoch_perf)`` with
  both anchors fixed at ``enable()``/``reset()``: an NTP step inside a
  run cannot tear a span from the device trace.

* **Time-source aware.**  While a replacement time source is installed
  (``models.types.set_time_source`` — the deterministic simulator's
  VirtualClock), timestamps come from ``models.types.now()``, span ids
  from a monotonic counter, and everything read off the machine (thread
  CPU, the thread table, the profiler mirror) is left out — so a
  simulation trace is a pure function of its seed, byte for byte
  (asserted in tests/test_obs.py).

* **Thread CPU.**  A span records ``cpu``: ``time.thread_time()`` at its
  end minus at its start.  Wall minus CPU is the time the span's thread
  was off the CPU: waiting for the GIL, a lock, the device, or asleep.
  The tracer also reads the CPU clock of every live thread at
  ``enable()``, ``disable()`` and export, and exports the growth by
  thread name (``otherData.thread_cpu_s``): which thread has the
  interpreter, observers included.

* **Collector pauses.**  While enabled the tracer listens to
  ``gc.callbacks`` and records every collection of 1 ms or more as a
  retroactive ``gc.collect`` span on the thread that ran it: the
  interpreter stands still for all threads meanwhile (the scheduler's
  tick pauses the collector, so the first allocation after it pays).

* **In the profile.**  While enabled, each span is also entered as a
  ``jax.profiler.TraceAnnotation`` of the same name and (start-time)
  arguments, so a captured device profile shows the program's spans on
  the host plane, on the profiler's own clock.  ``jax`` is imported at
  the first enabled span, not with this module.

* **Thread-safe.**  Production components record spans from their own
  threads; the buffer append and id allocation are lock-protected, and
  parent links are tracked per-thread (a span's parent is the innermost
  open span *on the same thread*).
"""

from __future__ import annotations

import gc
import json
import threading
import time
from typing import Any, Dict, List, Optional

from ..models import types as _types
from .threadcpu import read_threads


class Span:
    __slots__ = ("name", "cat", "start", "end", "span_id", "parent_id",
                 "thread", "args", "cpu", "_mirror")

    def __init__(self, name: str, cat: str, start: float, span_id: int,
                 parent_id: int, thread: str,
                 args: Optional[Dict[str, Any]]):
        self.name = name
        self.cat = cat
        self.start = start
        self.end = start
        self.span_id = span_id
        self.parent_id = parent_id   # 0 = root
        self.thread = thread
        self.args = args
        #: seconds of its thread's CPU the span used; None where it was
        #: not measured (a retroactive span, an installed time source).
        #: While the span is open it holds the thread's clock at start.
        self.cpu: Optional[float] = None
        #: the span's twin in the profiler's trace, while it is open
        self._mirror = None

    @property
    def duration(self) -> float:
        return self.end - self.start


class _Noop:
    """Shared do-nothing context manager returned while disabled."""

    __slots__ = ()

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> bool:
        return False


_NOOP = _Noop()


class _SpanCtx:
    __slots__ = ("_tracer", "_name", "_cat", "_args", "span")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[Dict[str, Any]]):
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._args = args

    def __enter__(self) -> Span:
        self.span = self._tracer.start_span(self._name, self._cat,
                                            self._args)
        return self.span

    def __exit__(self, *exc) -> bool:
        self._tracer.end_span(self.span)
        return False


def _thread_cpu_table() -> Dict[str, float]:
    """CPU seconds of every live thread so far, summed by thread name;
    empty where the platform has no per-thread CPU clock."""
    out: Dict[str, float] = {}
    for name, used in read_threads().values():
        out[name] = out.get(name, 0.0) + used
    return out


#: a collector pause this long or longer gets a ``gc.collect`` span
GC_PAUSE_SPAN_S = 0.001

#: jax.profiler.TraceAnnotation, looked up at the first enabled span;
#: False where jax cannot be imported
_annotation = None


def _trace_annotation():
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        except Exception:       # no jax, or one that cannot start here
            _annotation = False
    return _annotation


class Tracer:
    """Bounded span recorder with explicit start/end and parent links."""

    def __init__(self, clock=None, max_spans: int = 262_144):
        # None -> models.types.now (late-bound so a VirtualClock installed
        # later still governs this tracer)
        self._clock = clock
        self.enabled = False
        # optional tap: called with every ended span (even ones the
        # bounded buffer dropped) — the flight recorder's black box
        # installs itself here.  Process-wide, so save/restore_state
        # deliberately leaves it alone.
        self.sink = None
        self.max_spans = max_spans
        self._lock = threading.Lock()
        self._spans: List[Span] = []
        # spans started but not yet ended, by id — exported as
        # "incomplete" so a live /debug/trace snapshot taken mid-tick
        # still contains every referenced parent
        self._open: Dict[int, Span] = {}
        self._next_id = 1
        self._local = threading.local()
        self.epoch = 0.0
        self.dropped = 0
        # the two anchors of the wall clock spans are stamped with
        self._epoch_wall = 0.0
        self._epoch_perf = 0.0
        # thread name -> CPU seconds at enable(), and their growth as
        # read at disable(); None = not read
        self._threads0: Optional[Dict[str, float]] = None
        self._threads: Optional[Dict[str, float]] = None
        # collector pauses seen by _gc_event, as (thread, start, end,
        # generation, collected), until spans()/to_chrome() fold them
        # in.  The callback runs wherever an allocation trips the
        # collector, inside this tracer's own critical sections too, so
        # it takes no lock: it appends, and nothing else.
        self._pauses: List[tuple] = []
        self._gc_t0: Optional[float] = None

    # ------------------------------------------------------------- lifecycle

    def _machine(self) -> bool:
        """True when spans are stamped off this machine's clocks: no
        injected clock and no installed time source (the simulator)."""
        return self._clock is None and not _types.time_source_installed()

    def _now(self) -> float:
        if self._clock is not None:
            return self._clock()
        if _types.time_source_installed():
            return _types.now()
        return self._epoch_wall + (time.perf_counter() - self._epoch_perf)

    def _anchor(self) -> None:
        # the one reading of the wall clock: every stamp after it is
        # this anchor plus a monotonic difference (an installed time
        # source still governs, see _now)
        # swarmlint: disable=determinism-seam
        self._epoch_wall = time.time()
        self._epoch_perf = time.perf_counter()
        self.epoch = self._now()

    def enable(self) -> None:
        if not self._spans:
            self._anchor()
        if self._machine():
            self._threads0, self._threads = _thread_cpu_table(), None
            if self._gc_event not in gc.callbacks:
                gc.callbacks.append(self._gc_event)
        self.enabled = True

    def disable(self) -> None:
        if self.enabled:
            self._threads = self.thread_cpu_s()
        self.enabled = False
        if self._gc_event in gc.callbacks:
            gc.callbacks.remove(self._gc_event)

    def _gc_event(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            dt, self._gc_t0 = time.perf_counter() - self._gc_t0, None
            if dt >= GC_PAUSE_SPAN_S and self.enabled and self._machine():
                end = self._now()
                self._pauses.append(
                    (threading.current_thread().name, end - dt, end,
                     info.get("generation"), info.get("collected")))

    def _fold_pauses(self) -> None:
        """Turn the collector pauses seen so far into ``gc.collect``
        spans (root spans of the thread that ran the collection; the
        sink does not see them).  Caller holds ``_lock``."""
        pauses, self._pauses = self._pauses, []
        for thread, start, end, generation, collected in pauses:
            if start < self.epoch:
                continue
            if len(self._spans) >= self.max_spans:
                self.dropped += 1
                continue
            sp = Span("gc.collect", "gc", start, self._next_id, 0, thread,
                      {"generation": generation, "collected": collected})
            sp.end = end
            self._next_id += 1
            self._spans.append(sp)

    def thread_cpu_s(self) -> Optional[Dict[str, float]]:
        """CPU seconds each thread (by name) used since ``enable()``:
        live while enabled, as read at ``disable()`` afterwards; None
        where it was not read.  A thread that ended in between is
        missing: its clock went with it."""
        if not self.enabled and self._threads is not None:
            return self._threads
        if self._threads0 is None or not self._machine():
            return None
        base = self._threads0
        return {name: round(used - base.get(name, 0.0), 6)
                for name, used in sorted(_thread_cpu_table().items())}

    def reset(self) -> None:
        """Drop all recorded spans and restart ids; the next span's clock
        reading becomes the new epoch (per-run isolation).  Spans still
        open on other threads when reset runs belong to the previous
        recording session — end_span drops them (pre-epoch start)."""
        with self._lock:
            self._spans = []
            self._open = {}
            self._next_id = 1
            self.dropped = 0
            self._pauses = []
            self._anchor()
            self._threads0 = self._threads = None
        self._local = threading.local()

    def save_state(self):
        """Capture the recording state (buffer, ids, epoch, enabled) so
        an embedded recording session — the sim runner resets the shared
        tracer around each scenario — can hand the caller's trace back
        via restore_state afterwards."""
        with self._lock:
            self._fold_pauses()
            return (self._spans, self._open, self._next_id, self.epoch,
                    self.dropped, self.enabled, self._epoch_wall,
                    self._epoch_perf, self._threads0, self._threads)

    def restore_state(self, state) -> None:
        with self._lock:
            (self._spans, self._open, self._next_id, self.epoch,
             self.dropped, enabled, self._epoch_wall, self._epoch_perf,
             self._threads0, self._threads) = state
        self._local = threading.local()
        self.enabled = enabled

    # ------------------------------------------------------------- recording

    def span(self, name: str, cat: str = "", **args):
        """Context manager recording one span; no-op when disabled.
        ``args`` land in the exported event's args dict — counts, names
        and identifiers; a reading of the machine's clocks only where no
        time source is installed (the sim's traces are seed-pure)."""
        if not self.enabled:
            return _NOOP
        return _SpanCtx(self, name, cat, args or None)

    def start_span(self, name: str, cat: str = "",
                   args: Optional[Dict[str, Any]] = None) -> Span:
        machine = self._machine()
        mirror = None
        if machine and self.enabled:
            annotation = _trace_annotation()
            if annotation:
                mirror = annotation(name, **args) if args \
                    else annotation(name)
                mirror.__enter__()
        t = self._now()
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        parent = stack[-1].span_id if stack else 0
        with self._lock:
            sid = self._next_id
            self._next_id += 1
            sp = Span(name, cat, t, sid, parent,
                      threading.current_thread().name, args)
            self._open[sid] = sp
        stack.append(sp)
        sp._mirror = mirror
        if machine:
            sp.cpu = time.thread_time()
        return sp

    def end_span(self, sp: Span) -> None:
        if sp.cpu is not None:
            sp.cpu = time.thread_time() - sp.cpu
        sp.end = self._now()
        if sp._mirror is not None:
            sp._mirror.__exit__(None, None, None)
            sp._mirror = None
        stack = getattr(self._local, "stack", None)
        if stack and stack[-1] is sp:
            stack.pop()
        elif stack and sp in stack:        # mismatched exit order
            stack.remove(sp)
        with self._lock:
            self._open.pop(sp.span_id, None)
            if sp.start < self.epoch:
                # started before the last reset: a leftover of the
                # previous recording session — exporting it would yield
                # a negative timestamp
                self.dropped += 1
                return
            elif len(self._spans) < self.max_spans:
                self._spans.append(sp)
            else:
                self.dropped += 1
        sink = self.sink
        if sink is not None:
            sink(sp)

    def record_complete(self, name: str, cat: str = "",
                        duration: float = 0.0, **args) -> Optional[Span]:
        """Record an already-measured span ending *now* — for events the
        caller only recognizes after timing them (an XLA compile is
        detected by a jit-cache-size delta once the call returns).  The
        span parents under the innermost open span on this thread that
        contains it, so a retroactive ``plan.compile`` nests inside
        ``plan.dispatch``.
        It has no ``cpu`` and no twin in the profiler's trace."""
        if not self.enabled:
            return None
        end = self._now()
        start = max(self.epoch, end - max(0.0, duration))
        # the innermost open span that contains it: what the caller
        # timed may have begun before the span it is reported in (a
        # plan's dispatch->fetch window ends inside the next group)
        parent = 0
        for open_sp in reversed(getattr(self._local, "stack", None) or ()):
            if open_sp.start <= start:
                parent = open_sp.span_id
                break
        with self._lock:
            sid = self._next_id
            self._next_id += 1
            sp = Span(name, cat, start, sid, parent,
                      threading.current_thread().name, args or None)
            sp.end = end
            if len(self._spans) < self.max_spans:
                self._spans.append(sp)
            else:
                self.dropped += 1
        sink = self.sink
        if sink is not None:
            sink(sp)
        return sp

    # --------------------------------------------------------------- export

    def spans(self) -> List[Span]:
        with self._lock:
            self._fold_pauses()
            return list(self._spans)

    def to_chrome(self) -> Dict[str, Any]:
        """Chrome trace-event JSON object (``traceEvents`` array of "X"
        complete events plus thread-name metadata).  Deterministic: events
        appear in end order (the order spans were recorded), thread ids
        are assigned by first appearance, and timestamps are integer
        microseconds relative to the tracer epoch."""
        t_now = self._now()
        with self._lock:
            self._fold_pauses()
            spans = list(self._spans)
            open_spans = sorted(self._open.values(),
                                key=lambda s: s.span_id)
        tids: Dict[str, int] = {}
        events: List[Dict[str, Any]] = []
        for sp in spans:
            tid = tids.setdefault(sp.thread, len(tids) + 1)
            ev: Dict[str, Any] = {
                "name": sp.name, "cat": sp.cat or "default", "ph": "X",
                "ts": int(round((sp.start - self.epoch) * 1e6)),
                # clamped: a backwards wall-clock step (NTP) mid-span
                # must not emit a negative duration the validator and
                # chrome://tracing both reject
                "dur": max(0, int(round((sp.end - sp.start) * 1e6))),
                "pid": 1, "tid": tid,
                "args": dict(sp.args or {},
                             span_id=sp.span_id, parent_id=sp.parent_id),
            }
            if sp.cpu is not None:
                # the format's own thread-clock duration
                ev["tdur"] = max(0, int(round(sp.cpu * 1e6)))
            events.append(ev)
        for sp in open_spans:
            # a live snapshot mid-tick: export in-flight spans too, so
            # every parent_id in the document resolves
            if sp.start < self.epoch:
                continue
            tid = tids.setdefault(sp.thread, len(tids) + 1)
            try:
                # the owning thread may be mutating args concurrently
                # (e.g. the dispatcher filling in a count mid-span)
                args = dict(sp.args) if sp.args else {}
            except RuntimeError:
                args = {}
            args.update(span_id=sp.span_id, parent_id=sp.parent_id,
                        incomplete=True)
            events.append({
                "name": sp.name, "cat": sp.cat or "default", "ph": "X",
                "ts": int(round((sp.start - self.epoch) * 1e6)),
                "dur": max(0, int(round((t_now - sp.start) * 1e6))),
                "pid": 1, "tid": tid,
                "args": args,
            })
        meta = [{"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
                 "args": {"name": tname}}
                for tname, tid in sorted(tids.items(), key=lambda kv: kv[1])]
        other: Dict[str, Any] = {"dropped_spans": self.dropped}
        threads = self.thread_cpu_s()
        if threads is not None:
            other["thread_cpu_s"] = threads
        return {"traceEvents": meta + events, "displayTimeUnit": "ms",
                "otherData": other}

    def to_json(self) -> str:
        return json.dumps(self.to_chrome(), sort_keys=True,
                          separators=(",", ":"))

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            f.write(self.to_json())


# the process-wide tracer every instrumented component records into
tracer = Tracer()

# the flight recorder taps every ended span (cheap: one attribute check
# while the recorder is disabled)
from .flightrec import flightrec as _flightrec  # noqa: E402  (cycle-free)

tracer.sink = _flightrec.record_span
