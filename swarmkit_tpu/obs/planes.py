"""Per-plane saturation signals: one uniform shape for every serving
plane.

USE-style saturation methodology (PAPERS.md): attribute a tail-latency
regression to the saturated *resource*, not the symptom.  Every serving
plane — raft commit, raft apply, scheduler, dispatcher, device, watch —
exports the same four signals through one ``PlaneStats`` per plane:

* **occupancy** — busy_s / wall_s per roll window (how much of the
  window the plane spent doing work), gauge
  ``swarm_plane_occupancy{plane="..."}``;
* **queue depth** — items waiting (proposal inbox, apply lag entries,
  pending backlog, sessions, dispatch queue, watch buffer), gauge
  ``swarm_plane_queue_depth{plane="..."}``;
* **oldest-item age** — seconds the head of that queue has waited,
  gauge ``swarm_plane_oldest_age_s{plane="..."}``;
* **drops / defers** — counters
  ``swarm_plane_drops{plane="..."}`` / ``swarm_plane_defers{plane=...}``.

Busy time is accumulated at the call sites (``note_busy`` / the
``busy()`` context manager); depth and age are either pushed
(``set_depth`` / ``set_oldest_age``) or pulled through a registered
``probe`` at roll time — the probe form keeps hot paths untouched for
signals that are just an attribute read away (raft inbox qsize, apply
lag).  ``roll_all()`` is driven by the sampler tick, so gauge freshness
follows the same cadence as every other sampled signal.

Time flows through ``models.types.now()`` — under the simulator's
VirtualClock occupancy windows are a pure function of the seed.  All
label values here are the fixed plane names below: bounded cardinality
by construction (swarmlint's metric-hygiene cardinality shapes enforce
the same rule tree-wide).
"""

from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Callable, Dict, Optional

from ..models import types as _types
from ..utils.metrics import Registry
from ..utils.metrics import registry as _default_registry

# the fixed plane taxonomy (docs/architecture.md "planes & journeys")
RAFT = "raft"              # proposal inbox + fsync/WAL batch plane
RAFT_APPLY = "raft_apply"  # committed-entry apply plane (lag entries)
SCHEDULER = "scheduler"    # tick occupancy + pending backlog
DISPATCHER = "dispatcher"  # sessions + assignment fan-out flush
DEVICE = "device"          # planner dispatch queue + d2h stalls
WATCH = "watch"            # subscription lag (versions / buffer depth)

ALL_PLANES = (RAFT, RAFT_APPLY, SCHEDULER, DISPATCHER, DEVICE, WATCH)


class PlaneStats:
    """Saturation signals for one plane.  Thread-safe; cheap enough to
    call from hot paths (one lock, a few float adds)."""

    def __init__(self, name: str, registry: Optional[Registry] = None):
        self.name = name
        self.registry = registry or _default_registry
        self._mu = threading.Lock()
        self._busy_s = 0.0
        # opened lazily at the first roll(): constructing a PlaneStats
        # must not consume the time source (lazy plane() creation would
        # otherwise shift frozen-clock byte-identity runs)
        self._window_start: Optional[float] = None
        self._depth = 0.0
        self._oldest_age = 0.0
        self._drops = 0
        self._defers = 0
        self._probe: Optional[Callable[[], Dict[str, float]]] = None
        self.last_occupancy = 0.0

    # ------------------------------------------------------------ recording

    def note_busy(self, dt: float) -> None:
        """Accumulate ``dt`` seconds of busy time into the current
        window (retroactive form — pairs with existing phase timers)."""
        if dt <= 0:
            return
        with self._mu:
            self._busy_s += dt

    @contextmanager
    def busy(self):
        """Context-manager form of ``note_busy`` for inline sections."""
        t0 = _types.now()
        try:
            yield
        finally:
            self.note_busy(_types.now() - t0)

    def set_depth(self, n: float) -> None:
        with self._mu:
            self._depth = float(n)

    def set_oldest_age(self, seconds: float) -> None:
        with self._mu:
            self._oldest_age = max(0.0, float(seconds))

    def drop(self, n: int = 1) -> None:
        with self._mu:
            self._drops += n
        self.registry.counter(
            f'swarm_plane_drops{{plane="{self.name}"}}', n)

    def defer(self, n: int = 1) -> None:
        with self._mu:
            self._defers += n
        self.registry.counter(
            f'swarm_plane_defers{{plane="{self.name}"}}', n)

    def set_probe(self, probe: Optional[Callable[[], Dict[str, float]]]
                  ) -> None:
        """Register a pull-probe run at roll time; it returns any of
        ``{"depth": n, "oldest_age": s, "busy_s": dt}`` — the cheap way
        to sample signals that are an attribute read away (raft inbox
        qsize, commit_index - applied_index) without touching the hot
        path that produces them."""
        self._probe = probe

    # -------------------------------------------------------------- rolling

    def roll(self) -> Dict[str, float]:
        """Close the current occupancy window and export the gauges.
        Returns the rolled snapshot (also kept for ``report()``)."""
        probe = self._probe
        if probe is not None:
            try:
                probed = probe() or {}
            except Exception:
                probed = {}   # a dying component must not take obs down
            if "depth" in probed:
                self.set_depth(probed["depth"])
            if "oldest_age" in probed:
                self.set_oldest_age(probed["oldest_age"])
            if "busy_s" in probed:
                self.note_busy(probed["busy_s"])
        t = _types.now()
        with self._mu:
            start = self._window_start
            wall = t - start if start is not None else 0.0
            occ = min(1.0, self._busy_s / wall) if wall > 0 else 0.0
            self._busy_s = 0.0
            self._window_start = t
            self.last_occupancy = occ
            depth, oldest = self._depth, self._oldest_age
        reg = self.registry
        reg.gauge(f'swarm_plane_occupancy{{plane="{self.name}"}}',
                  round(occ, 6))
        reg.gauge(f'swarm_plane_queue_depth{{plane="{self.name}"}}',
                  depth)
        reg.gauge(f'swarm_plane_oldest_age_s{{plane="{self.name}"}}',
                  round(oldest, 6))
        return {"occupancy": round(occ, 6), "queue_depth": depth,
                "oldest_age_s": round(oldest, 6)}

    def report(self) -> Dict[str, float]:
        with self._mu:
            out = {
                "occupancy": round(self.last_occupancy, 6),
                "queue_depth": self._depth,
                "oldest_age_s": round(self._oldest_age, 6),
                "drops": self._drops,
                "defers": self._defers,
            }
        if self.name == DEVICE:
            # sub-plane rows from the device-telemetry ledger: where the
            # plane's busy time went (dispatch vs d2h vs compile) and
            # what it moved.  Lazy import, device plane only — the
            # ledger imports nothing above utils, so no cycle; an empty
            # ledger contributes nothing (fresh-manager rendering stays
            # byte-identical to PR 17).
            from . import devicetelemetry as _devtel
            sub = _devtel.sub_plane_rows()
            if sub:
                out["sub"] = sub
        return out


# ------------------------------------------------------------- module state

_lock = threading.Lock()
_planes: Dict[str, PlaneStats] = {}


def plane(name: str) -> PlaneStats:
    """The process-wide ``PlaneStats`` singleton for ``name`` (created
    on first use so importing a component never allocates planes it
    does not export)."""
    with _lock:
        p = _planes.get(name)
        if p is None:
            p = _planes[name] = PlaneStats(name)
        return p


def roll_all() -> Dict[str, Dict[str, float]]:
    """Roll every registered plane (the sampler's tick);
    returns {plane: rolled snapshot} in sorted order."""
    with _lock:
        items = sorted(_planes.items())
    return {name: p.roll() for name, p in items}


def report_all() -> Dict[str, Dict[str, float]]:
    """Deterministically ordered report for ``/debug/planes`` and
    ``/debug/device``.  Safe on a fresh process: an empty taxonomy reports
    an empty dict, never raises."""
    with _lock:
        items = sorted(_planes.items())
    return {name: p.report() for name, p in items}


def save_state():
    """Capture the plane table so an embedded capture session (the sim
    runner) can restore the embedding process's planes afterwards —
    same contract as Tracer.save_state/FlightRecorder.save_state."""
    with _lock:
        state = dict(_planes)
    return state


def restore_state(state) -> None:
    global _planes
    with _lock:
        _planes = dict(state)


def reset() -> None:
    """Start fresh (tests, sim scenario entry).  The table is REBOUND,
    not cleared in place, so a ``save_state`` capture survives."""
    global _planes
    with _lock:
        _planes = {}
