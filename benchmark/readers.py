"""The reader kinds of the per-layer metrics.

A per-layer metric is one file, ``layer_metrics/<name>.json``: its
``layer``, ``unit``, ``better``, ``moves``, and a ``reader`` of one of
four kinds with that kind's parameters.  Which cells report it is said
once, by the ``workloads`` list of its ``per_layer`` entry in
``BENCHMARK.json``.  A later PR adds a metric that an existing kind can
read as one new file and one appended entry, and a cell to a metric as
one appended item of that list.

``client``        a series the harness's own clients recorded (host clock)
``span``          spans of the program's tracer, by name
``counter``       a counter of the program, as growth over the window
``device_trace``  the reduced profiler trace

Every reader gets the run's ``Observations`` and returns a number or
None; None (nothing to read) leaves the metric out of the line.  No
reader returns 0 for a share of a roofline or of a peak it could not
work out.
"""

from __future__ import annotations

import json
import os
import statistics
from typing import Dict, List, Optional

from . import kernel_bytes

HERE = os.path.dirname(os.path.abspath(__file__))
SOURCE_OF_KIND = {"client": "host_clock", "span": "program_span",
                  "counter": "program_counter",
                  "device_trace": "device_trace"}


class Observations:
    """What a traced run hands the readers."""

    def __init__(self):
        self.window_s = 0.0
        #: name -> list of seconds (or counts), recorded by the clients
        self.series: Dict[str, List[float]] = {}
        #: (thread, name, start wall s, end wall s, args) of the spans that
        #: started inside the window; the window on the same clock
        self.spans: List[tuple] = []
        self.window_wall: tuple = (0.0, 0.0)
        #: source -> {key: growth over the window}
        self.counters: Dict[str, Dict[str, float]] = {}
        #: reduce_trace.reduce() of the profiled slice, or None
        self.trace: Optional[dict] = None
        #: the slice on the wall clock, to count its ticks
        self.slice_wall: Optional[tuple] = None
        #: compile-ledger growth of dispatches inside the slice
        self.slice_dispatches: Dict[str, int] = {}
        self.peak_bytes_per_s: Optional[float] = None
        #: what ``roofline_pct`` was worked out from, once it was read
        self.roofline_of: Optional[dict] = None


def percentile(values: List[float], q: float) -> Optional[float]:
    """The q-th percentile (nearest rank) of all the values."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, -(-len(ordered) * q // 100))
    return ordered[int(rank) - 1]


def read_client(params: dict, obs: Observations) -> Optional[float]:
    values = obs.series.get(params["series"])
    if not values:
        return None
    how = params["reduce"]
    if how == "mean":
        out = statistics.fmean(values)
    elif how == "median":
        out = statistics.median(values)
    elif how == "p95":
        out = percentile(values, 95)
    elif how == "count_per_s":
        out = len(values) / obs.window_s if obs.window_s else None
    else:
        raise ValueError(f"client reduce {how!r}")
    return None if out is None else out * params.get("scale", 1.0)


def _ticks(obs: Observations, lo=None, hi=None) -> int:
    return sum(1 for sp in obs.spans
               if sp[1] == "sched.tick"
               and (lo is None or lo <= sp[2] < hi))


def _arg_sum(obs: Observations, ref: dict, inside=None) -> float:
    """Sum of one argument over the spans of one name; ``inside``: only
    spans that lie within one of these (start, end) intervals."""
    total = 0.0
    for _thread, name, a, b, args in obs.spans:
        if name != ref["span"] or not args:
            continue
        if inside is not None and not any(lo <= a and b <= hi
                                          for lo, hi in inside):
            continue
        total += args.get(ref["arg"], 0)
    return total


def read_span(params: dict, obs: Observations) -> Optional[float]:
    how = params["reduce"]
    if how == "arg_share_pct":
        # both sides from the same whole spans: the numerator's spans
        # are counted only inside a denominator span that ended in the
        # window
        whole = [(a, b) for _t, name, a, b, _args in obs.spans
                 if name == params["den"]["span"]
                 and b <= obs.window_wall[1]]
        den = _arg_sum(obs, params["den"], whole)
        if not whole or not den:
            return None
        return 100.0 * _arg_sum(obs, params["num"], whole) / den
    if how == "arg_mean":
        # over the spans that carry the argument: a tree whose spans
        # lack it has nothing to read, not a mean of nought
        rows = [args[params["arg"]]
                for _t, name, _a, b, args in obs.spans
                if name == params["span"] and args
                and params["arg"] in args and b <= obs.window_wall[1]]
        return sum(rows) / len(rows) if rows else None
    spans = [sp[3] - sp[2] for sp in obs.spans if sp[1] == params["span"]]
    ticks = _ticks(obs)
    if not spans or not ticks:
        return None
    if how == "mean_ms":
        return 1e3 * sum(spans) / len(spans)
    if how == "ms_per_tick":
        return 1e3 * sum(spans) / ticks
    raise ValueError(f"span reduce {how!r}")


def _counter(ref: dict, obs: Observations) -> Optional[float]:
    table = obs.counters.get(ref["source"])
    if table is None:
        return None
    if "keys" in ref:
        return sum(table.get(k, 0) for k in ref["keys"])
    return table.get(ref["key"])


def read_counter(params: dict, obs: Observations) -> Optional[float]:
    num = _counter(params["num"], obs)
    if num is None:
        return None
    if "den" not in params:
        return num * params.get("scale", 1.0)
    den = _counter(params["den"], obs)
    if not den:
        return None
    return num / den * params.get("scale", 1.0)


def read_device_trace(params: dict, obs: Observations) -> Optional[float]:
    trace = obs.trace
    if trace is None or not trace.get("window_s"):
        return None
    what = params["value"]
    if what == "idle_pct":
        return trace["idle_pct"]
    if what == "module_ms_per_tick":
        lo, hi = obs.slice_wall
        ticks = _ticks(obs, lo, hi)
        rows = [trace["modules"][m] for m in params["modules"]
                if m in trace["modules"]]
        if not ticks or not rows:
            return None
        return 1e3 * sum(r["seconds"] for r in rows) / ticks
    if what == "roofline_pct":
        # the family that took most device time in the slice; its calls'
        # least bytes come from the signatures the planner dispatched
        families = {fam: trace["modules"][mod]["seconds"]
                    for fam, mod in kernel_bytes.FAMILY_MODULE.items()
                    if fam in params["families"]
                    and mod in trace["modules"]}
        if not families or not obs.peak_bytes_per_s:
            return None
        top = max(families, key=families.get)
        moved = 0
        for label, n in obs.slice_dispatches.items():
            if kernel_bytes.family_of_label(label) == top:
                moved += n * kernel_bytes.bytes_of_label(label)
        if not moved or not families[top]:
            return None
        obs.roofline_of = {"family": top, "bound": "bytes",
                           "bytes": moved, "seconds": families[top]}
        return 100.0 * (moved / obs.peak_bytes_per_s) / families[top]
    raise ValueError(f"device_trace value {what!r}")


KINDS = {"client": read_client, "span": read_span,
         "counter": read_counter, "device_trace": read_device_trace}


def load_layer_metrics() -> Dict[str, dict]:
    """Every ``layer_metrics/*.json``, by metric name (the file's)."""
    out = {}
    folder = os.path.join(HERE, "layer_metrics")
    for fname in sorted(os.listdir(folder)):
        if fname.endswith(".json"):
            with open(os.path.join(folder, fname)) as f:
                spec = json.load(f)
            if spec["reader"]["kind"] not in KINDS:
                raise ValueError(f"{fname}: reader kind "
                                 f"{spec['reader']['kind']!r}")
            out[fname[:-len(".json")]] = spec
    return out


def read_all(cell: str, obs: Observations,
             per_layer: List[dict]) -> Dict[str, dict]:
    """{name: {"value", "unit"}} for every per-layer metric that found
    something to read, of those whose entry in ``per_layer``
    (``BENCHMARK.json``'s list) names ``cell``."""
    reporting = {entry["name"] for entry in per_layer
                 if cell in entry["workloads"]}
    out = {}
    for name, spec in load_layer_metrics().items():
        if name not in reporting:
            continue
        value = KINDS[spec["reader"]["kind"]](spec["reader"], obs)
        if value is not None:
            out[name] = {"value": value, "unit": spec["unit"]}
    return out
