"""The executor the benchmark's agents run their tasks with.

The agents are the program's real ``Agent``s; the executor stands in for
the container runtime, which is outside the system under test.  The
program's own stand-in (``agent/testutils.py TestExecutor``) is kept but
for one thing: its controller's ``wait`` polls an event fifty times a
second for every running task, so a few hundred tasks on agent nodes are
tens of thousands of interpreter hand-offs a second, and the manager in
the same process starves (seen on the chip and on the CPU, PR 26: the
served path fell under 10 tasks/s).  A container runtime does not bill
the manager for a running container; this controller blocks until it is
stopped or interrupted, and does nothing else differently.
"""

from __future__ import annotations

import threading


def make_executor(hostname: str):
    from swarmkit_tpu.agent.exec import TemporaryError
    from swarmkit_tpu.agent.testutils import TestController, TestExecutor

    class BlockingController(TestController):
        """``TestController`` whose ``wait`` sleeps until woken."""

        def __init__(self, **kwargs):
            super().__init__(**kwargs)
            self._wake = threading.Event()

        def interrupt(self) -> None:
            super().interrupt()
            self._wake.set()

        def shutdown(self) -> None:
            super().shutdown()
            self._wake.set()

        def terminate(self) -> None:
            super().terminate()
            self._wake.set()

        def close(self) -> None:
            super().close()
            self._wake.set()

        def wait(self) -> None:
            while True:
                self._wake.wait()
                self._wake.clear()
                if self.stopped.is_set():
                    return
                if self.interrupted.is_set():
                    self.interrupted.clear()
                    raise TemporaryError("wait interrupted by task update")

    class BlockingExecutor(TestExecutor):
        def controller(self, t):
            ctlr = BlockingController(**self.controller_kwargs)
            ctlr.task = t
            with self._mu:
                self.controllers[t.id] = ctlr
            return ctlr

    return BlockingExecutor(hostname=hostname)
