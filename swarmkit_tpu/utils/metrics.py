"""Minimal metrics registry: counters, gauges, and latency timers with a
Prometheus-style text exposition.

Reference role: docker/go-metrics as used by the reference (store tx/lock
timers memory.go:84-112, dispatcher scheduling-delay timer
dispatcher.go:72-77, object-count collector manager/metrics/collector.go).
"""

from __future__ import annotations

import math
import threading
import time
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_QUANTILES = (0.5, 0.9, 0.99)


class Timer:
    """Latency accumulator with reservoir-free streaming quantiles
    (bounded ring of recent observations)."""

    def __init__(self, maxlen: int = 2048):
        self._lock = threading.Lock()
        self._buf: List[float] = []
        self._maxlen = maxlen
        self._i = 0
        self.count = 0
        self.total = 0.0

    def observe(self, seconds: float) -> None:
        with self._lock:
            self.count += 1
            self.total += seconds
            if len(self._buf) < self._maxlen:
                self._buf.append(seconds)
            else:
                self._buf[self._i % self._maxlen] = seconds
            self._i += 1

    def time(self):
        timer = self

        class _Ctx:
            def __enter__(self):
                self.t0 = time.perf_counter()
                return self

            def __exit__(self, *exc):
                timer.observe(time.perf_counter() - self.t0)

        return _Ctx()

    def quantiles(self) -> Dict[float, float]:
        with self._lock:
            buf = sorted(self._buf)
        if not buf:
            return {q: 0.0 for q in _QUANTILES}
        # nearest-rank: the smallest value with at least q*n observations
        # at or below it.  The previous ``int(q*len)`` indexed one element
        # HIGH for exact multiples (p50 of 10 returned the 6th element)
        # while q*n just under len biased to max-1 — on small buffers the
        # reported p99 was systematically off by one rank.
        n = len(buf)
        return {q: buf[max(0, math.ceil(q * n) - 1)] for q in _QUANTILES}

    def reset(self) -> None:
        """Forget every observation (per-test / per-scenario isolation)."""
        with self._lock:
            self._buf = []
            self._i = 0
            self.count = 0
            self.total = 0.0


class Registry:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.counters: Dict[str, float] = defaultdict(float)
        self.gauges: Dict[str, float] = {}
        self.timers: Dict[str, Timer] = {}

    def counter(self, name: str, delta: float = 1.0) -> None:
        with self._lock:
            self.counters[name] += delta

    def gauge(self, name: str, value: float) -> None:
        with self._lock:
            self.gauges[name] = value

    def timer(self, name: str) -> Timer:
        with self._lock:
            t = self.timers.get(name)
            if t is None:
                t = self.timers[name] = Timer()
            return t

    def get_counter(self, name: str, default: float = 0.0) -> float:
        with self._lock:
            return self.counters.get(name, default)

    def get_gauge(self, name: str, default: Optional[float] = None
                  ) -> Optional[float]:
        """Point read of one gauge; default (None) distinguishes
        never-set from 0.0 — health checks treat no-data as pass."""
        with self._lock:
            return self.gauges.get(name, default)

    def counters_snapshot(self, prefix: str = "") -> Dict[str, float]:
        """Copy of the counter map (optionally prefix-filtered); the
        benchmark diffs two snapshots to attribute counts to its window."""
        with self._lock:
            return {k: v for k, v in self.counters.items()
                    if k.startswith(prefix)}

    def gauges_snapshot(self, prefix: str = "") -> Dict[str, float]:
        with self._lock:
            return {k: v for k, v in self.gauges.items()
                    if k.startswith(prefix)}

    def timers_snapshot(self, prefix: str = "") -> Dict[str, Timer]:
        """Name -> live Timer references (the objects are stable across
        ``reset()``); consumers read .count/.total/.quantiles() without
        touching this registry's lock protocol."""
        with self._lock:
            return {k: t for k, t in self.timers.items()
                    if k.startswith(prefix)}

    def get_timer(self, name: str) -> Optional[Timer]:
        with self._lock:
            return self.timers.get(name)

    def reset(self) -> None:
        """Zero all counters/gauges and reset timers IN PLACE — components
        hold Timer references from ``timer(name)``, so the objects must
        survive a reset (per-test / per-scenario isolation)."""
        with self._lock:
            self.counters.clear()
            self.gauges.clear()
            timers = list(self.timers.values())
        for t in timers:
            t.reset()

    def expose(self) -> str:
        """Prometheus-style text format."""
        lines: List[str] = []
        with self._lock:
            for name, v in sorted(self.counters.items()):
                if "{" in name:
                    # labeled counter: the _total suffix belongs on the
                    # metric NAME, before the label braces
                    base, labels = name.split("{", 1)
                    lines.append(f"{base}_total{{{labels} {v:g}")
                else:
                    lines.append(f"{name}_total {v:g}")
            for name, v in sorted(self.gauges.items()):
                lines.append(f"{name} {v:g}")
            timers = list(self.timers.items())
        for name, t in sorted(timers):
            if "{" in name:
                # labeled timer: merge the quantile label into the
                # existing label set, suffix on the metric name
                base, labels = name.split("{", 1)
                labels = labels[:-1]  # strip closing brace
                for q, v in t.quantiles().items():
                    lines.append(f'{base}_seconds{{{labels},'
                                 f'quantile="{q}"}} {v:.6f}')
                lines.append(f"{base}_seconds_count{{{labels}}} {t.count}")
                lines.append(f"{base}_seconds_sum{{{labels}}} "
                             f"{t.total:.6f}")
                continue
            for q, v in t.quantiles().items():
                lines.append(f'{name}_seconds{{quantile="{q}"}} {v:.6f}')
            lines.append(f"{name}_seconds_count {t.count}")
            lines.append(f"{name}_seconds_sum {t.total:.6f}")
        return "\n".join(lines) + "\n"


registry = Registry()
