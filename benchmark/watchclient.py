"""The benchmark's watch client: the client's side of the served path and
the clock of every latency.  One thread reads
``mgr.watch_server.watch(WatchRequest(kinds=[Task]))`` and stamps each
event with ``time.perf_counter()`` as it receives it."""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional


class TaskTimes:
    """What the client saw of one task."""

    __slots__ = ("service_id", "created", "pending", "assigned", "running",
                 "node_id")

    def __init__(self, service_id: str):
        self.service_id = service_id
        self.created: Optional[float] = None
        self.pending: Optional[float] = None
        self.assigned: Optional[float] = None
        self.running: Optional[float] = None
        self.node_id = ""


class WatchClient:
    def __init__(self, watch_server):
        from swarmkit_tpu.manager.watchapi import WatchRequest
        from swarmkit_tpu.models import Task, TaskState
        self._stream = watch_server.watch(WatchRequest(kinds=[Task]))
        self._states = (int(TaskState.PENDING), int(TaskState.ASSIGNED),
                        int(TaskState.RUNNING))
        self.tasks: Dict[str, TaskTimes] = {}
        #: service id -> number of its tasks seen ASSIGNED with a node
        self.assigned_of: Dict[str, int] = {}
        #: service id -> the count a client is waiting for: the reader
        #: wakes a waiting client once, when its count is reached, not at
        #: every event (a woken thread wants the interpreter, which the
        #: program's threads share)
        self._wanted: Dict[str, int] = {}
        #: receipt stamps, in order: every create, every first ASSIGNED
        self.create_stamps: List[float] = []
        self.assign_stamps: List[float] = []
        self.events = 0
        self._cond = threading.Condition()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run,
                                        name="bench-watch", daemon=True)
        self.error: Optional[BaseException] = None

    def start(self) -> None:
        self._thread.start()

    def _run(self) -> None:
        pending, assigned, running = self._states
        tasks = self.tasks
        try:
            while not self._stop.is_set():
                try:
                    ev = self._stream.get(timeout=0.2)
                except TimeoutError:
                    continue
                now = time.perf_counter()
                self.events += 1
                obj = ev.obj
                rec = tasks.get(obj.id)
                if rec is None:
                    if ev.action == "delete":
                        continue
                    rec = tasks[obj.id] = TaskTimes(obj.service_id)
                    rec.created = now
                    self.create_stamps.append(now)
                state = int(obj.status.state)
                if state >= pending and rec.pending is None:
                    rec.pending = now
                if state >= assigned and obj.node_id \
                        and rec.assigned is None:
                    rec.assigned = now
                    rec.node_id = obj.node_id
                    self.assign_stamps.append(now)
                    sid = obj.service_id
                    seen = self.assigned_of[sid] = \
                        self.assigned_of.get(sid, 0) + 1
                    if seen >= self._wanted.get(sid, seen + 1):
                        with self._cond:
                            self._cond.notify_all()
                if state == running and rec.running is None:
                    rec.running = now
        except BaseException as e:   # the stream closed under us
            if not self._stop.is_set():
                self.error = e
            with self._cond:
                self._cond.notify_all()

    def wait_assigned(self, service_id: str, count: int,
                      deadline: float) -> bool:
        """Block until ``count`` tasks of the service were seen ASSIGNED,
        or ``deadline`` (a ``perf_counter`` reading) passes."""
        with self._cond:
            self._wanted[service_id] = count
            try:
                while self.assigned_of.get(service_id, 0) < count:
                    left = deadline - time.perf_counter()
                    if left <= 0 or self.error is not None:
                        return False
                    self._cond.wait(min(left, 0.5))
            finally:
                self._wanted.pop(service_id, None)
        return True

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=5)
        self._stream.close()
