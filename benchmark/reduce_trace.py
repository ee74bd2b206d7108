"""From a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to the device's busy and idle time, each XLA
module's device time, the operations that took most of it and the longest
idle gaps.

What a TPU trace holds (looked at by hand, PR 26): a plane
``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one event a
program run, named ``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one
event an HLO operation; a ``while`` spans its body's operations); a plane
``/host:CPU`` whose ``python`` line holds the ``TraceAnnotation``
markers; and a plane ``Task Environment`` whose ``profile_start_time``
stat is the wall clock, in nanoseconds, of every event's ``start_ns`` 0.

Busy is the union of the ``XLA Ops`` intervals (of ``XLA Modules`` where
a plane has no op line) inside the window, averaged over the device
planes; the window runs from the ``bench.window_start`` marker to
``bench.window_end`` (the whole profile where they are missing).
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_START, WINDOW_END = "bench.window_start", "bench.window_end"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_FINGERPRINT = re.compile(r"\(\d+\)$")

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint cover of ``intervals``."""
    out: List[List[float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: float,
         hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def gaps_of(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The idle intervals of ``[lo, hi]`` given its disjoint busy
    cover."""
    out, at = [], lo
    for a, b in busy:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def module_family(name: str) -> str:
    """``jit_plan_group_jit(123)`` -> ``jit_plan_group_jit``."""
    return _FINGERPRINT.sub("", name)


def op_label(name: str) -> str:
    """The HLO text of an op event is long; its result name (``%while.12``)
    with the opcode is enough to find it again."""
    head, _, rest = name.partition(" = ")
    m = re.search(r"\b([a-z][a-z0-9\-_]*)\(", rest)
    return f"{head.strip()} {m.group(1)}" if m else head.strip()[:60]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns * 1e-9,
             (e.start_ns + e.duration_ns) * 1e-9) for e in line.events]


def read(path: str) -> dict:
    """The raw pieces of a trace, times in seconds from the profile's
    start: {"devices": [{"modules": [...], "ops": [...]}], "markers":
    {name: (start_s, wall_s)}, "profile_start_wall_s", "profile_s"}."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, markers = [], {}
    start_wall = stop_wall = None
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "modules": [], "ops": []}
            for line in plane.lines:
                if line.name == "XLA Modules":
                    dev["modules"] = _events(line)
                elif line.name == "XLA Ops":
                    dev["ops"] = _events(line)
            devices.append(dev)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name in (WINDOW_START, WINDOW_END):
                        wall = None
                        for key, value in e.stats:
                            if key == "wall_s":
                                wall = float(value)
                        markers[e.name] = (e.start_ns * 1e-9, wall)
        elif plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats:
                start_wall = stats["profile_start_time"] * 1e-9
            if "profile_stop_time" in stats:
                stop_wall = stats["profile_stop_time"] * 1e-9
    profile_s = (stop_wall - start_wall
                 if start_wall is not None and stop_wall is not None
                 else max((e[2] for d in devices for e in d["modules"]),
                          default=0.0))
    return {"devices": devices, "markers": markers,
            "profile_start_wall_s": start_wall, "profile_s": profile_s}


def reduce(raw: dict) -> dict:
    """Busy/idle share, per-module device time, top operations and the
    idle gaps of the traced window."""
    lo = raw["markers"].get(WINDOW_START, (0.0, None))[0]
    hi = raw["markers"].get(WINDOW_END, (raw["profile_s"], None))[0]
    window = hi - lo
    modules: Dict[str, Dict[str, float]] = {}
    by_fingerprint: Dict[str, Dict[str, float]] = {}
    op_seconds: Dict[str, float] = {}
    busy_total = 0.0
    all_busy: List[Interval] = []
    for dev in raw["devices"]:
        source = dev["ops"] or dev["modules"]
        busy = union(clip(((a, b) for _, a, b in source), lo, hi))
        busy_total += sum(b - a for a, b in busy)
        all_busy.extend(busy)
        mods = sorted(dev["modules"], key=lambda e: e[1])
        starts = [m[1] for m in mods]
        for name, a, b in mods:
            if b <= lo or a >= hi:
                continue
            for table, key in ((modules, module_family(name)),
                               (by_fingerprint, name)):
                row = table.setdefault(key, {"calls": 0, "seconds": 0.0})
                row["calls"] += 1
                row["seconds"] += min(b, hi) - max(a, lo)
        for name, a, b in dev["ops"]:
            if b <= lo or a >= hi:
                continue
            i = bisect.bisect_right(starts, a) - 1
            owner = (module_family(mods[i][0])
                     if i >= 0 and a < mods[i][2] else "?")
            key = f"{owner}/{op_label(name)}"
            op_seconds[key] = op_seconds.get(key, 0.0) + (b - a)
    if not raw["devices"]:
        # no device plane (a CPU rehearsal): nothing to read, and no
        # idle share is made up for it
        return {"window_s": None, "busy_s": None, "idle_pct": None,
                "devices": 0, "modules": {}, "modules_by_fingerprint": {},
                "device_ops": [], "gaps": [], "wall_offset_s": None}
    n_dev = len(raw["devices"])
    busy_s = busy_total / n_dev
    # gaps of the chips together: idle where no chip runs anything
    gaps = gaps_of(union(all_busy), lo, hi) if window > 0 else []
    offset = raw["profile_start_wall_s"]
    mark = raw["markers"].get(WINDOW_START)
    if mark is not None and mark[1] is not None:
        offset = mark[1] - mark[0]      # the marker's own stamp wins
    return {
        "window_s": window,
        "busy_s": busy_s,
        "idle_pct": 100.0 * (1.0 - busy_s / window) if window > 0
        else None,
        "devices": len(raw["devices"]),
        "modules": modules,
        "modules_by_fingerprint": by_fingerprint,
        "device_ops": sorted(([k, v] for k, v in op_seconds.items()),
                             key=lambda kv: -kv[1]),
        "gaps": gaps,
        "wall_offset_s": offset,
    }


def name_gaps(gaps: List[Interval], wall_offset_s: Optional[float],
              spans: List[Tuple[str, str, float, float]],
              top: int = 10) -> List[list]:
    """[[what the host was doing, idle seconds]] for the idle gaps,
    longest total first.  ``spans``: (thread, name, start, end, ...) on the
    wall clock (the program's tracer).  A gap is named by the innermost
    span that covers its middle on each thread that has one, the
    scheduler's thread first; a gap no span covers is ``between spans``,
    and with no offset between the clocks every gap is ``unnamed``."""
    if wall_offset_s is None or not spans:
        total = sum(b - a for a, b in gaps)
        return [["unnamed", total]] if total else []
    by_thread: Dict[str, List[Tuple[float, float, str]]] = {}
    for thread, name, a, b, *_ in spans:
        by_thread.setdefault(thread, []).append((a, b, name))
    for rows in by_thread.values():
        rows.sort()
    order = sorted(by_thread, key=lambda t: (t != "scheduler", t))
    totals: Dict[str, float] = {}
    for a, b in gaps:
        mid = (a + b) / 2 + wall_offset_s
        label = "between spans"
        for thread in order:
            rows = by_thread[thread]
            i = bisect.bisect_right(rows, (mid, float("inf"), ""))
            best = None
            # spans nest: walk back to every span that started before
            # the middle and still covers it; the latest start is the
            # innermost
            for j in range(i - 1, max(i - 64, -1), -1):
                if rows[j][1] >= mid:
                    best = rows[j]
                    break
            if best is not None:
                label = f"{thread}:{best[2]}"
                break
        totals[label] = totals.get(label, 0.0) + (b - a)
    return [[k, v] for k, v in sorted(totals.items(),
                                      key=lambda kv: -kv[1])[:top]]


def breakdown(reduced: dict,
              spans: List[Tuple[str, str, float, float]]) -> dict:
    return {"device_ops": reduced["device_ops"][:10],
            "idle_gaps": name_gaps(reduced["gaps"],
                                   reduced["wall_offset_s"], spans)}
