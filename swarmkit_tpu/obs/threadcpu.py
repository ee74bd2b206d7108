"""Thread-CPU accountant: which families of threads ran while a span's
thread waited.

Every live thread is mapped to a *family* by the name the program gave
it (``FAMILIES``, the one table of them):

* ``agents``: the in-process agents' threads (an ``Agent``'s loop, its
  reporter, log publisher and heartbeat, each task's manager, and a
  remote agent's assignments reader).
* ``background``: the manager's threads outside the served path: the
  store's watchers (metrics, task reaper, lifecycle, sampler, jobs,
  global, deallocator, the constraint and volume enforcers, role
  manager, CSI, restart timer, update watchers) and its other loops
  (key manager, leadership, CA and certificate loops, autoscaler,
  pipeline, the debug and manager servers).
* ``control``: the served path's own loops: the scheduler, the
  replicated orchestrator, the allocator, the dispatcher (the
  assignments fan-out flushes there), the scheduler's committer and the
  raft members' threads.
* ``other``: every thread the program did not name: ``MainThread``, a
  client's threads, a server's per-connection threads.  In a served run
  the control API's ``create_service`` runs on its caller's thread, so
  its work lands here.

A snapshot reads the CPU clock of every live thread but the caller's
(``time.pthread_getcpuclockid`` / ``time.clock_gettime`` over
``threading.enumerate()``).  ``begin`` takes one before a span opens and
``end`` one after it closed, and sets on the span what each family used
in between (``<family>_cpu_ms``) and their sum (``others_cpu_ms``); the
span's own thread is its ``cpu``.  A thread that ended between the two
snapshots drops out, its clock gone with it; one that started in
between counts whole.  Only while the tracer is on and reads this
machine's clocks (no installed time source): otherwise ``begin`` reads
no clock and returns None, and ``stats["thread_cpu_snapshots"]``, the
snapshots taken, stays where it was (``stats`` also sums the threads they
read and the CPU and wall they took: the accountant's own cost).

Each thread's two readings lie ``accounted_ms`` apart, more than the
span: the first snapshot's own wall comes on top, and a snapshot that
waits for the interpreter lets other threads run inside it.  The span
carries ``accounted_ms`` beside the families so that a reading can be
set against the span's duration.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, Optional, Tuple

#: family -> the thread names of it; a name ending in ``-*`` is a prefix
FAMILIES: Dict[str, Tuple[str, ...]] = {
    "agents": ("agent", "agent-reporter", "agent-logs", "agent-heartbeat",
               "taskmanager-*", "assignments-reader"),
    "background": ("metrics", "taskreaper", "lifecycle", "obs-sampler",
                   "jobs", "global", "deallocator", "constraint-enforcer",
                   "volume-enforcer", "rolemanager", "csi-watch",
                   "csi-work", "restart-timer", "updater-*", "keymanager",
                   "leadership", "ca-adoption", "ca-rotation",
                   "cert-renewer", "role-watcher",
                   "manager-identity-renewer", "autoscaler", "pipeline",
                   "debug-http", "manager-server"),
    "control": ("scheduler", "replicated", "allocator", "dispatcher",
                "sched-commit", "raft-*"),
}
#: the family of every name no entry of ``FAMILIES`` matches
OTHER = "other"
#: the span arguments ``end`` sets, in order
ARGS = ("others_cpu_ms",) + tuple(f"{f}_cpu_ms"
                                  for f in (*FAMILIES, OTHER))

_EXACT = {name: fam for fam, names in FAMILIES.items()
          for name in names if not name.endswith("-*")}
#: a prefix entry's part before its ``-*`` -> its family
_PREFIXED = {name[:-2]: fam for fam, names in FAMILIES.items()
             for name in names if name.endswith("-*")}
#: the snapshots taken, the threads they read, and the CPU and wall
#: seconds they took on the threads that took them: what the accountant
#: costs (wall above CPU: the snapshot waited for the interpreter)
stats = {"thread_cpu_snapshots": 0, "threads_read": 0,
         "snapshot_cpu_s": 0.0, "snapshot_wall_s": 0.0}
_stats_lock = threading.Lock()

Snapshot = Dict[int, Tuple[str, float]]


def family(name: str) -> str:
    """The family of a thread by its name."""
    fam = _EXACT.get(name)
    if fam is None:
        head, dash, _ = name.partition("-")
        fam = _PREFIXED.get(head, OTHER) if dash else OTHER
    return fam


def read_threads(skip: Optional[int] = None) -> Snapshot:
    """{thread ident: (name, CPU seconds so far)} of every live thread
    but ``skip``; empty where the platform has no per-thread CPU clock.
    A thread that ends between the two calls is passed over."""
    out: Snapshot = {}
    clock_id = getattr(time, "pthread_getcpuclockid", None)
    if clock_id is None:
        return out
    gettime = time.clock_gettime
    for t in threading.enumerate():
        ident = t.ident
        if ident is None or ident == skip:
            continue
        try:
            out[ident] = (t.name, gettime(clock_id(ident)))
        except (OSError, ValueError, OverflowError):
            continue
    return out


def snapshot() -> Snapshot:
    """``read_threads`` of every live thread but the caller's."""
    cpu0, wall0 = time.thread_time(), time.perf_counter()
    out = read_threads(skip=threading.get_ident())
    with _stats_lock:
        stats["thread_cpu_snapshots"] += 1
        stats["threads_read"] += len(out)
        stats["snapshot_cpu_s"] += time.thread_time() - cpu0
        stats["snapshot_wall_s"] += time.perf_counter() - wall0
    return out


def spent_ms(before: Snapshot, after: Snapshot) -> Dict[str, float]:
    """``ARGS`` -> CPU milliseconds between the two snapshots.  A thread
    in ``after`` alone, or under an ident ``before`` knew by another name
    or with more CPU, started in between and counts whole."""
    by_family = dict.fromkeys((*FAMILIES, OTHER), 0.0)
    was_of = before.get
    for ident, (name, used) in after.items():
        was = was_of(ident)
        if was is not None and was[0] == name and was[1] <= used:
            used -= was[1]
        if used:        # most threads idle between the two: no lookup
            by_family[family(name)] += used
    out = {"others_cpu_ms": round(sum(by_family.values()) * 1e3, 3)}
    out.update((f"{fam}_cpu_ms", round(s * 1e3, 3))
               for fam, s in by_family.items())
    return out


def begin() -> Optional[Tuple[float, Snapshot]]:
    """The wall clock and a snapshot, taken just before a span opens;
    None, and no clock read, while the tracer is off or stamps a time
    source's clock."""
    from .trace import tracer
    if not tracer.enabled or not tracer._machine():
        return None
    return time.perf_counter(), snapshot()


def end(sp, before: Optional[Tuple[float, Snapshot]]) -> None:
    """Set ``ARGS`` on ``sp``, a span that has just closed, from
    ``before`` (``begin()``'s) and a snapshot taken now, and
    ``accounted_ms``, the wall between the two snapshots' starts: what
    each thread's two readings lie apart, the span and the first
    snapshot's own wall; nothing where either is None."""
    if sp is None or before is None:
        return
    t0, threads0 = before
    accounted = time.perf_counter() - t0
    spent = spent_ms(threads0, snapshot())
    spent["accounted_ms"] = round(accounted * 1e3, 3)
    if sp.args is None:
        sp.args = spent
    else:
        sp.args.update(spent)
