"""Every way a run can finish without the device, as findings (a copy of
``chip_smoke.py``'s ``retreat_findings`` / ``process_findings``; the
smoke stays as it is).  Counters are process-wide, so a run reads them
against the snapshot it took when it started."""

from __future__ import annotations

import logging
from typing import Dict, List

#: loggers of the device path and the native plane: they log an ERROR
#: exactly when they fall back
RETREAT_LOGGERS = ("tpu-planner", "tpu-streaming", "native")

PLANNER_KEYS = ("groups_device_error", "groups_breaker_to_host",
                "groups_fallback", "groups_spill_to_host",
                "groups_strategy_host", "launch_probe_failures",
                "fused_overflows", "gang_device_error", "gang_fit_host",
                "preempt_device_error", "preempt_breaker_to_host")

PROCESS_COUNTERS = ("swarm_streaming_device_disabled",
                    "swarm_streaming_scatter_failures",
                    "swarm_device_donation_violations",
                    "swarm_native_commit_fallbacks")


class RetreatLog(logging.Handler):
    def __init__(self):
        super().__init__(logging.ERROR)
        self.records: List[str] = []

    def emit(self, record):
        if record.name in RETREAT_LOGGERS:
            self.records.append(f"{record.name}: {record.getMessage()}")


def process_counters() -> Dict[str, float]:
    from swarmkit_tpu.utils.metrics import registry
    return {name: registry.get_counter(name, 0)
            for name in PROCESS_COUNTERS}


def planner_findings(planner) -> List[str]:
    from swarmkit_tpu.ops.kernel import plan_group_jit
    from swarmkit_tpu.ops.planner import BREAKER_CLOSED, _jit_cache_size
    out = []
    for key in PLANNER_KEYS:
        if planner.stats.get(key, 0):
            out.append(f"planner {key}={planner.stats[key]}")
    breaker = planner.breaker
    if breaker.stats["trips"] or breaker.stats["failures"] \
            or breaker.state != BREAKER_CLOSED:
        out.append(f"breaker {breaker.state_name} {breaker.stats}")
    if planner._fused_dead:
        out.append("fused path marked dead")
    if _jit_cache_size(plan_group_jit) is None:
        out.append("compile counting is off: jit cache size unreadable")
    streaming = planner.streaming_snapshot()
    if not streaming.get("device_enabled", False):
        out.append(f"resident device tier is off: {streaming}")
    return out


def process_findings(before: Dict[str, float]) -> List[str]:
    from swarmkit_tpu import native
    out = [f"{name}=+{now - before.get(name, 0)}"
           for name, now in process_counters().items()
           if now - before.get(name, 0)]
    if native.get_commit() is None:
        out.append("native commit plane is not loaded")
    return out
