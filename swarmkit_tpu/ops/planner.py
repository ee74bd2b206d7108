"""TPU batch planner: plugs the device kernel into the scheduler seam.

Implements the ``batch_planner`` protocol consumed by
scheduler.Scheduler._schedule_task_group: given a task group, either place
the whole group on device and return True, or return False to fall back to
the host (oracle) path.

Falls back for features the device path does not model yet (documented
parity waivers, ``_supported``): CSI volume mounts, named (non-discrete)
generic resources, and spread-preference trees deeper than 4 levels.
Multi-level spread (up to 4 levels) runs on device via the kernel's
hierarchical stage-A water-fill; node.ip constraints ride the hash/prefix
columns (``constraint.ip_column_spec``).

Small groups on small clusters route to the host path: a device launch
costs a fixed round-trip (measured once per process, see
_measure_launch_overhead) while the host oracle costs a scan of every
mirrored node per group (measured once per process too, see
_measure_host_cost_per_node) plus tens of microseconds per task, so
below the measured break-even the pipeline seam simply keeps the group
on the host.  Large groups — where the kernel's margin is 30x+ per
decision — and every group of a cluster whose scan alone outweighs a
launch go to the device.

Densification builds SoA arrays from the scheduler's NodeSet mirror.  The
group-independent node columns are built once per tick (begin_tick), kept
in sync by the apply phase's batched per-node updates, and invalidated
whenever a host-path fallback (which mutates NodeInfos directly) occurs —
so a tick of many small groups pays O(N) once, not O(N x groups).
"""

from __future__ import annotations

import logging
from collections import deque
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..models.objects import Meta, Task
from ..models.types import (
    GenericResourceKind, MountType, NodeAvailability, NodeState, PublishMode,
    Version, now,
)
from ..scheduler import constraint as constraint_mod
from ..scheduler import strategy as strategy_mod
from ..scheduler.filters import (
    Pipeline, normalize_arch, _references_volume_plugin,
)
from ..scheduler.nodeinfo import NodeInfo
from ..models.types import TaskState, TaskStatus
from ..obs import devicetelemetry as _devtel
from ..obs import planes as _planes
from ..obs.trace import tracer
from ..utils.compilecache import ensure_compile_cache
from ..utils.metrics import registry as _metrics
from . import fusedbatch
from .fusedbatch import (
    CC_BUCKETS as _CC_BUCKETS, P_BUCKETS as _P_BUCKETS,
    SENTINEL as _SENTINEL, bucket as _bucket, l_bucket as _l_bucket,
    n_bucket as _n_bucket, split_hash as _split_hash,
)
from .hashing import str_hash
from .kernel import (
    GroupInputs, K_CLAMP, NodeInputs, StrategyInputs, fetch_plan,
    gang_fit_fused_jit, gang_fit_jit, plan_fused_jit, plan_group_jit,
    plan_strategy_jit, search_form,
)

log = logging.getLogger("tpu-planner")

#: a spread tree with more leaves at its last level than this is a wide
#: tree (``stats["wide_tree_groups"]`` / ``["wide_tree_s"]``): a property
#: of the service's preferences and the cluster's labels.  A rung of the
#: leaf ladder (``fusedbatch.l_bucket``), so the leaf bucket tells it
WIDE_TREE_LEAVES = 256


def _spread_prefs(t: Task) -> int:
    """How many spread preferences the task's placement carries."""
    placement = t.spec.placement
    return sum(1 for p in (placement.preferences if placement else ())
               if p.spread)


# cached Timer references (Registry.reset() resets in place)
_PLAN_TIMER = _metrics.timer("swarm_planner_plan_latency")
_COMPILE_TIMER = _metrics.timer("swarm_planner_compile_latency")


def _jit_cache_size(fn) -> Optional[int]:
    """Compiled-signature count of a jitted callable, or None when the
    runtime does not expose it (then compile detection is off rather
    than guessed — the whole point is observation, not inference)."""
    cache_size = getattr(fn, "_cache_size", None)
    if cache_size is None:
        return None
    try:
        return cache_size()
    except Exception:
        return None


def _bucket_label(nodes_in, group_in, L: int, hier) -> str:
    """Stable name for one static jit signature: node bucket, constraint
    slots, platform slots, spread leaf bucket, spread depth.  Bounded
    cardinality — every component comes from a fixed bucket ladder."""
    # a one-preference spread's hier, ((), None, layout), is no tree
    depth = len(hier[0]) + 1 if hier and hier[1] is not None else 0
    q = "_q1" if nodes_in.quota_ok is not None else ""
    return (f"nb{nodes_in.valid.shape[0]}_cc{group_in.con_hash.shape[0]}"
            f"_p{group_in.plat.shape[0]}_L{L}_h{depth}{q}")


def _observe_compile(fn, bucket: str, cache_before: Optional[int],
                     dt: float) -> float:
    """Count an XLA cache miss when the jit cache grew across one call:
    a ``swarm_planner_compiles{bucket=...}`` counter tick, a compile
    timer observation, and a retroactive ``plan.compile`` span — the
    explanation trail for a slow tick in a trace.

    Doubles as THE compile-cache ledger feed: every dispatch lands in
    the per-signature hit/miss registry (obs/devicetelemetry.py), so
    "compiles 0 in the timed window" is auditable per-bucket.  Returns
    the retro-measured compile seconds (0.0 on a hit) for the caller's
    kernel-ledger row."""
    after = _jit_cache_size(fn)
    if cache_before is None or after is None:
        return 0.0
    if after <= cache_before:
        _devtel.note_cache_hit(bucket)
        return 0.0
    _metrics.counter(f'swarm_planner_compiles{{bucket="{bucket}"}}',
                     after - cache_before)
    _COMPILE_TIMER.observe(dt)
    _devtel.note_compile(bucket, dt, after - cache_before)
    # under a virtual clock (the simulator) the wall-clock compile
    # duration would be the ONLY nondeterministic bytes in an otherwise
    # seed-pure span trace: keep the event, zero the duration
    from ..models.types import time_source_installed
    tracer.record_complete("plan.compile", "plan",
                           0.0 if time_source_installed() else dt,
                           bucket=bucket)
    return dt


# shape-bucket helpers live in ops/fusedbatch.py (single source for the
# per-group and fused paths); the module-private names above are aliases


def _fast_assign(task: Task, node_id: str, status) -> Task:
    """Minimal assignment clone for the columnar commit hot path.

    Equivalent to ``task.copy()`` + set node_id/status, minus the wasted
    copy of the status we immediately replace.  ``status`` may be shared
    across the whole group: stored/mirrored objects follow the
    replace-don't-mutate convention (Task.copy always copies status before
    any mutation), so structural sharing is safe.
    """
    new = object.__new__(Task)
    d = new.__dict__
    d.update(task.__dict__)
    m = task.meta
    new.meta = Meta(Version(m.version.index), m.created_at, m.updated_at)
    new.status = status
    new.node_id = node_id
    new.networks = list(task.networks)
    new.assigned_generic_resources = []
    new.volumes = list(task.volumes)
    return new


def _probe_inputs():
    nb = 1024
    valid = np.ones(nb, bool)
    nodes = NodeInputs(
        valid=valid, ready=valid.copy(),
        res_ok=valid.copy(), res_cap=np.full(nb, 8, np.int32),
        svc_tasks=np.zeros(nb, np.int32), total_tasks=np.zeros(nb, np.int32),
        failures=np.zeros(nb, np.int32), leaf=np.zeros(nb, np.int32),
        os_hash=np.zeros((2, nb), np.int32),
        arch_hash=np.zeros((2, nb), np.int32),
        port_conflict=np.zeros(nb, bool), extra_mask=np.ones(nb, bool))
    group = GroupInputs(
        k=np.int32(8), con_hash=np.zeros((1, 2, nb), np.int32),
        con_op=np.full(1, 2, np.int32), con_exp=np.zeros((1, 2), np.int32),
        plat=np.full((1, 4), -1, np.int32), maxrep=np.int32(0),
        port_limited=np.bool_(False))
    return nodes, group


BREAKER_CLOSED, BREAKER_HALF_OPEN, BREAKER_OPEN = 0, 1, 2
_BREAKER_NAMES = {BREAKER_CLOSED: "closed", BREAKER_HALF_OPEN: "half-open",
                  BREAKER_OPEN: "open"}


class PlannerBreaker:
    """Degraded-mode circuit breaker for the device path.

    N consecutive device dispatch/fetch failures trip the breaker OPEN:
    every group routes to the host oracle (placements stay valid, the
    tick never fails) until the cooldown elapses.  The breaker then goes
    HALF-OPEN and admits a single probe group; a successful probe closes
    it, a failed probe re-opens it with a doubled (capped) cooldown.
    Successful closes decay the accumulated cooldown back toward the
    base, so a device that recovers cleanly is re-trusted quickly while
    a flapping one backs off geometrically.

    State is exported as the ``swarm_planner_breaker_state`` gauge
    (0=closed, 1=half-open, 2=open) — judged by the ``planner_breaker``
    SLO check in obs/health — and every trip lands in the flight
    recorder.  Time is read through ``models.types.now()`` so the sim
    drives the cooldown deterministically.
    """

    def __init__(self, threshold: int = 3, cooldown: float = 30.0,
                 max_cooldown: float = 480.0):
        self.threshold = max(1, threshold)
        self.base_cooldown = cooldown
        self.max_cooldown = max_cooldown
        self._state = BREAKER_CLOSED
        self._failures = 0          # consecutive, resets on success
        self._cooldown = cooldown
        self._open_until = 0.0
        self._probe_inflight = False
        self.stats = {"trips": 0, "probes": 0, "failures": 0}
        self._export()

    def _export(self) -> None:
        _metrics.gauge("swarm_planner_breaker_state", self._state)

    @property
    def state(self) -> int:
        return self._state

    @property
    def state_name(self) -> str:
        return _BREAKER_NAMES[self._state]

    def allow_device(self) -> bool:
        """Gate one group's device dispatch.  OPEN past its cooldown
        flips to HALF-OPEN and admits exactly one probe; every other
        caller stays on the host until the probe resolves."""
        if self._state == BREAKER_CLOSED:
            return True
        if self._state == BREAKER_OPEN:
            if now() < self._open_until:
                return False
            self._state = BREAKER_HALF_OPEN
            self._probe_inflight = False
            self._export()
        # HALF_OPEN: single probe at a time
        if self._probe_inflight:
            return False
        self._probe_inflight = True
        self.stats["probes"] += 1
        _metrics.counter("swarm_planner_breaker_probes")
        return True

    def abort_probe(self) -> None:
        """The admitted group never reached the device (routed to host
        for an unrelated reason): release the probe slot unchanged."""
        self._probe_inflight = False

    def record_success(self) -> None:
        self._failures = 0
        if self._state == BREAKER_HALF_OPEN:
            self._probe_inflight = False
            self._state = BREAKER_CLOSED
            # decay the accumulated backoff toward the base: a clean
            # recovery is re-trusted, a flapper keeps most of its penalty
            self._cooldown = max(self.base_cooldown, self._cooldown / 2.0)
            self._export()
            log.info("planner breaker closed (device recovered)")

    def record_failure(self) -> None:
        self.stats["failures"] += 1
        if self._state == BREAKER_HALF_OPEN:
            # failed probe: back off harder
            self._cooldown = min(self._cooldown * 2.0, self.max_cooldown)
            self._trip()
            return
        self._failures += 1
        if self._state == BREAKER_CLOSED \
                and self._failures >= self.threshold:
            self._trip()

    def _trip(self) -> None:
        self._state = BREAKER_OPEN
        self._probe_inflight = False
        self._failures = 0
        self._open_until = now() + self._cooldown
        self.stats["trips"] += 1
        _metrics.counter("swarm_planner_breaker_trips")
        self._export()
        log.warning("planner breaker OPEN for %.1fs: device path "
                    "degraded to host fallback", self._cooldown)
        from ..obs.flightrec import flightrec
        flightrec.note(f"planner breaker tripped open "
                       f"(cooldown {self._cooldown:.1f}s)")


class _InFlightPlan:
    """One dispatched-but-unfetched device plan: everything fetch_group
    needs to finish the group once the device triple lands."""

    __slots__ = ("sched", "t", "task_group", "decisions", "built",
                 "plan_t0", "arrays", "bucket", "route", "pref_L",
                 "form")

    def __init__(self, sched, t, task_group, decisions, built, plan_t0,
                 arrays, bucket="", route="group", pref_L=0, form=""):
        self.sched = sched
        self.t = t
        self.task_group = task_group
        self.decisions = decisions
        self.built = built
        self.plan_t0 = plan_t0
        self.arrays = arrays
        # kernel-ledger attribution for the fetch stage (the dispatch
        # stage noted its half under the same key)
        self.bucket = bucket
        self.route = route
        # the leaf bucket of a group under exactly one spread preference
        # (0 for every other group): ``stats["pref_groups"]``
        self.pref_L = pref_L
        # the form the program's searches take at the leaf level
        self.form = form


class TPUPlanner:
    def __init__(self, plan_fn=None, fused_plan_fn=None, mesh=None):
        # plan_fn(nodes: NodeInputs, group: GroupInputs, L: int, hier)
        # -> (x i32[N], fail_counts i32[7], spill bool); hier carries
        # multi-level
        # spread segments (() for flat).  Defaults to the single-device jit
        # kernel; parallel/sharded.py provides a mesh-sharded
        # implementation with the same signature.
        #
        # SWARM_PLANNER_MESH=<D> shards the node axis over the first D
        # devices (parallel/sharded.py ShardedPlanFn drives both the
        # per-group and fused kernels); explicit plan_fn/mesh args win
        # over the env knob.
        import os as _os
        # every entry point that plans on the device builds a planner
        # first, so this is the one place the compile cache is placed
        ensure_compile_cache()
        if plan_fn is None and fused_plan_fn is None and mesh is None:
            from ..parallel.sharded import mesh_from_env
            mesh = mesh_from_env()
        if mesh is not None:
            from ..parallel.sharded import ShardedPlanFn
            sharded = ShardedPlanFn(mesh)
            plan_fn = plan_fn or sharded
            fused_plan_fn = fused_plan_fn or sharded
        self.mesh = mesh
        self._plan_fn = plan_fn or plan_group_jit
        # fused entry: an object exposing .fused(shared, groups, carry,
        # L) (+ optional .prepare_fused) — a ShardedPlanFn, or None for
        # the single-device kernel.  A ShardedPlanFn passed as plan_fn
        # serves both paths so the mesh is used consistently.
        if fused_plan_fn is None and hasattr(self._plan_fn, "fused"):
            fused_plan_fn = self._plan_fn
        self._fused_fn = fused_plan_fn
        # fused many-service batching (the one-program-per-tick path);
        # SWARM_FUSED_PLANNER=0 reverts to per-group dispatches.  An
        # injected plan_fn WITHOUT a fused twin owns the device path
        # entirely: fusing around it with the default kernel would
        # bypass the injected implementation (mesh fns, test stubs)
        self.fused_enabled = \
            _os.environ.get("SWARM_FUSED_PLANNER", "") != "0" \
            and (plan_fn is None or self._fused_fn is not None)
        self._fused_dead = False     # set on fused errors: rest of the
        #                              tick rides the per-group path
        self._fused_active = None    # in-flight FusedRun (tick aborts)
        self._tick_ts = None         # failure-window ts frozen per tick
        self.last_explanation = ""
        self.stats = {"groups_planned": 0, "groups_fallback": 0,
                      "groups_small_to_host": 0, "route_switches": 0,
                      "tree_cols_hits": 0, "tree_cols_builds": 0,
                      "tree_cols_invalidations": 0,
                      "h2d_bytes": 0, "d2h_bytes": 0,
                      "wide_tree_groups": 0, "wide_tree_s": 0.0,
                      "dense_tree_groups": 0,
                      "pref_groups": 0, "pref_wide_groups": 0,
                      "dense_pref_groups": 0,
                      "fused_wide_runs": 0, "fused_wide_groups": 0,
                      "fused_wide_s": 0.0,
                      "leaf_cols_hits": 0, "leaf_cols_builds": 0,
                      "leaf_cols_invalidations": 0,
                      "svc_cols_builds": 0, "svc_col_rows": 0,
                      "tasks_planned": 0, "plan_seconds": 0.0}
        # the break-even router's two sides (_route_costs): the measured
        # fixed launch overhead (dispatch + D2H round-trip on a minimal
        # workload) vs. what the host route costs, a scan of every
        # mirrored node per group (measured, _measure_host_cost_per_node)
        # plus the host oracle's marginal cost of a task.  Groups too
        # small to amortize a device round-trip stay on the host path
        self._launch_overhead = None
        self.host_cost_per_node = None
        self.host_cost_per_task = 50e-6
        # set False to force every supported group onto the device
        # (chip_smoke.py, differential tests)
        self.enable_small_group_routing = True
        # per-tick cache of group-independent node columns; built on
        # begin_tick, updated incrementally by the apply phase, invalidated
        # by host-path fallbacks (which mutate NodeInfos behind our back)
        self._cache = None
        # why the group routed last rides the host (None: it rides the
        # device), for the scheduler's ``sched.host_route`` span; and
        # whether a host-routed group of this tick dropped the columns,
        # so that the device-routed group that rebuilds them counts one
        # ``route_switches``
        self.last_host_reason: Optional[str] = None
        self._host_routed = False
        # streaming scheduler (ops/streaming.py): the node columns above
        # — and their device copies — stay RESIDENT across ticks and
        # refresh from the scheduler's dirty-set tracker in O(churn);
        # the full O(cluster) rebuild demotes to the counted fallbacks.
        # SWARM_STREAMING_PLANNER=0 reverts to per-tick rebuilds.
        self.streaming_enabled = \
            _os.environ.get("SWARM_STREAMING_PLANNER", "") != "0"
        self._streaming = None
        # degraded-mode circuit breaker: consecutive device failures trip
        # the whole planner to host fallback instead of failing ticks
        self.breaker = PlannerBreaker()
        # FIFO in-flight queue for the dispatch/fetch pipeline split:
        # plans dispatched via dispatch_group wait here until fetch_group
        # blocks on their D2H.  At most ONE plan may be in flight (the
        # dispatch_group guard): group i+1's input columns depend on
        # group i's apply, so the pipelined scheduler overlaps the
        # in-flight plan with group COMMITS (bounded by the scheduler's
        # pipeline_depth), never with another plan.
        self._inflight: deque = deque()

        # device-plane saturation probe (obs/planes.py): dispatch-queue
        # depth read lazily at window-roll time.  plane() resolved per
        # call — planes.reset() rebinds the table; weakref so the probe
        # never pins a dead planner; last-constructed planner owns it
        # (same discipline as raft/scheduler).
        import weakref
        _ref = weakref.ref(self)
        _planes.plane(_planes.DEVICE).set_probe(
            lambda: ({"depth": float(len(_ref()._inflight))}
                     if _ref() is not None else {}))

    # ------------------------------------------------------------- accounting

    # routing-counter keys -> the route label exported on
    # swarm_planner_groups{route=...}; every increment goes through
    # _count so the stats dict and the metrics registry can never
    # disagree (benchmark/retreat.py reads the dict, /metrics the registry)
    _ROUTE = {"groups_planned": "device",
              "groups_fused": "fused",
              "groups_fallback": "fallback",
              "groups_small_to_host": "host_small",
              "groups_spill_to_host": "spill",
              "groups_breaker_to_host": "breaker",
              "groups_strategy_host": "strategy_host"}

    def _count(self, key: str, delta: int = 1) -> None:
        self.stats[key] = self.stats.get(key, 0) + delta
        route = self._ROUTE.get(key)
        if route is not None:
            _metrics.counter(f'swarm_planner_groups{{route="{route}"}}',
                             delta)
        else:
            _metrics.counter(f"swarm_planner_{key}", delta)

    def _count_pref_group(self, pref_L: int, form: str) -> None:
        """A device-planned group, of its own or in a fused run, whose
        service carries exactly one spread preference (``pref_L``: that
        preference's leaf bucket; 0 for any other group), planned by a
        program whose leaf level searched in ``form``."""
        if pref_L:
            self._count("pref_groups")
            if pref_L > WIDE_TREE_LEAVES:
                self._count("pref_wide_groups")
                if form == "dense":
                    self._count("dense_pref_groups")

    def _observe_plan(self, dt: float) -> None:
        self.stats["plan_seconds"] += dt
        _PLAN_TIMER.observe(dt)

    def _note_h2d(self, reason: str, operands, sp=None) -> int:
        """Host arrays handed to a device program: their bytes into
        ``stats["h2d_bytes"]`` (whether or not the telemetry ledger is
        enabled), into the ledger under ``reason`` and onto the
        ``plan.dispatch`` span ``sp``."""
        nbytes = _devtel.tree_nbytes(operands)
        self._count("h2d_bytes", nbytes)
        _devtel.note_h2d(reason, nbytes)
        if sp is not None:
            sp.args["h2d_bytes"] = nbytes
        return nbytes

    def _note_d2h(self, reason: str, fetched) -> None:
        """Results fetched outside ``fetch_plan``: their bytes into
        ``stats["d2h_bytes"]`` and into the ledger under ``reason``."""
        nbytes = _devtel.tree_nbytes(fetched)
        self._count("d2h_bytes", nbytes)
        _devtel.note_d2h(reason, nbytes)

    def _fetch(self, arrays, sp=None):
        """``fetch_plan`` with the fetched bytes counted into
        ``stats["d2h_bytes"]`` and put on the ``plan.d2h`` span ``sp``.
        The seam counts the ledger's share itself and hands back only
        the arrays (``benchmark/control.py`` stands in for it under that
        signature), so the three results' ``nbytes`` are read again."""
        out = fetch_plan(arrays)
        nbytes = _devtel.tree_nbytes(out)
        self._count("d2h_bytes", nbytes)
        if sp is not None:
            sp.args["d2h_bytes"] = nbytes
        return out

    @staticmethod
    def _note_inflight(dt: float) -> None:
        """Retroactive ``plan.inflight`` span covering one plan's whole
        dispatch→fetch window.  The d2h span alone under-reports hidden
        work: compute that finished WHILE the host applied/committed an
        earlier group leaves a near-zero d2h wait, which would read as
        "no overlap" exactly when overlap worked best.  The in-flight
        window is what the commit spans genuinely ran inside of —
        obs/report.py counts it toward plan_hidden_frac, which
        scripts/trace_report.py prints under the phase table.
        Zero-duration under a virtual clock, like plan.compile
        (seed-pure sim traces).
        """
        from ..models.types import time_source_installed
        tracer.record_complete("plan.inflight", "plan",
                               0.0 if time_source_installed() else dt)

    def _call_plan_fn(self, nodes_in, group_in, L, hier, sp=None):
        """Every device-plan dispatch goes through here so XLA cache
        misses are *observed* per static shape bucket (jit cache-size
        delta around the call), not inferred from timing swings.  The
        dispatch also lands in the device kernel ledger with its input
        columns' H2D bytes (host-side nbytes — the implicit
        numpy->device transfer at the jit boundary)."""
        import time as _time
        bucket = _bucket_label(nodes_in, group_in, L, hier)
        self._note_h2d("group_inputs", (nodes_in, group_in, hier), sp)
        before = _jit_cache_size(self._plan_fn)
        t0 = _time.perf_counter()
        out = self._plan_fn(nodes_in, group_in, L, hier)
        dt = _time.perf_counter() - t0
        comp = _observe_compile(self._plan_fn, bucket, before, dt)
        _devtel.note_kernel(bucket, "group", dispatch_s=dt,
                            compile_s=comp, task_rows=int(group_in.k),
                            node_rows=nodes_in.valid.shape[0])
        return out

    def _call_strategy_fn(self, nodes_in, group_in, sin, sinfo, sp=None):
        """Strategy-kernel dispatch twin of ``_call_plan_fn``: same
        compile observation, per-strategy bucket suffix (each static
        strategy id is its own jit signature)."""
        import time as _time
        bucket = (_bucket_label(nodes_in, group_in, 1, ())
                  + f"_st{sinfo.sid}")
        self._note_h2d("group_inputs", (nodes_in, group_in, sin), sp)
        sfn = getattr(self._plan_fn, "strategy", None)
        probe = self._strategy_jit_probe()
        before = _jit_cache_size(probe)
        t0 = _time.perf_counter()
        if sfn is not None:
            out = sfn(nodes_in, group_in, sin, sinfo.sid)
        else:
            out = plan_strategy_jit(nodes_in, group_in, sin, sinfo.sid)
        dt = _time.perf_counter() - t0
        comp = _observe_compile(probe, bucket, before, dt)
        _devtel.note_kernel(bucket, "strategy", dispatch_s=dt,
                            compile_s=comp, task_rows=int(group_in.k),
                            node_rows=nodes_in.valid.shape[0],
                            strategy_id=sinfo.sid)
        return out

    def _build_strategy_inputs(self, built, t, sinfo) -> StrategyInputs:
        """Densify the strategy-seam columns for one group: per-resource
        headroom in demand units (exact int64 floor divisions — the
        host oracle's build_host_columns applies the identical per-row
        formula), the per-service weight vector, and the learned
        scorer's fixed artifact weights.  Unused members ship as zeros;
        the static strategy id keeps signatures apart."""
        (infos, n, nb, valid, cpu, mem, total, _nodes_in, _group_in,
         _L, _hier, cpu_d, mem_d, gen_wanted, _port_limited) = built
        HR = strategy_mod.HR_CLAMP
        if cpu_d > 0:
            hr_cpu = np.clip(cpu // cpu_d, 0, HR).astype(np.int32)
        else:
            hr_cpu = np.full(nb, HR, np.int32)
        if mem_d > 0:
            hr_mem = np.clip(mem // mem_d, 0, HR).astype(np.int32)
        else:
            hr_mem = np.full(nb, HR, np.int32)
        hr_gen = np.full(nb, HR, np.int32)
        if gen_wanted:
            for i, info in enumerate(infos):
                gen_min = HR
                for g in gen_wanted:
                    avail = 0
                    for r in info.available_resources.generic:
                        if r.kind == g.kind:
                            avail += (1 if r.res_type
                                      == GenericResourceKind.NAMED
                                      else r.value)
                    gen_min = min(gen_min,
                                  int(min(max(avail // g.value, 0), HR)))
                hr_gen[i] = gen_min
        if sinfo.uses_weights:
            weights = strategy_mod.weights_of(t)
        else:
            weights = np.zeros(4, np.int32)
        if sinfo.uses_learned:
            w1, b1, w2, b2 = strategy_mod.learned_params()
        else:
            f = len(strategy_mod.MLP_FEATURES)
            w1 = np.zeros((f, 1), np.int32)
            b1 = np.zeros(1, np.int32)
            w2 = np.zeros(1, np.int32)
            b2 = np.zeros((), np.int32)
        return StrategyInputs(hr_cpu=hr_cpu, hr_mem=hr_mem,
                              hr_gen=hr_gen, weights=weights,
                              w1=w1, b1=b1, w2=w2,
                              b2=np.asarray(b2, np.int32))

    # ------------------------------------------------------- per-tick caching

    def begin_tick(self, sched) -> None:
        self._in_tick = True
        self._fused_dead = False
        # one failure-window timestamp for the whole tick: the fused run
        # stamps its down-weights once, so the per-group path must read
        # the same instant or a failure aging out mid-tick breaks the
        # placement parity contract under a wall clock
        self._tick_ts = now()
        self._host_routed = False
        st = self._streaming_for(sched)
        if st is not None:
            self._cache = st.refresh(sched)
        else:
            self._cache = self._build_columns(sched)

    def _streaming_for(self, sched):
        """The resident-state plane when it may serve this scheduler:
        hatch on AND the scheduler carries the dirty-set delta feed
        (scheduler/deltatrack.py).  Lazily constructed — planners that
        only ever see tracker-less harnesses never pay for it."""
        if not self.streaming_enabled \
                or getattr(sched, "delta", None) is None:
            return None
        mesh = self.mesh \
            or getattr(self._plan_fn, "mesh", None) \
            or getattr(self._fused_fn, "mesh", None)
        if self._streaming is None:
            from .streaming import ResidentState
            self._streaming = ResidentState(self._node_value, mesh=mesh,
                                            count=self._count)
        else:
            # mesh teardown / shard-count change between ticks resyncs
            # the device tier (set_mesh is a no-op on identity)
            self._streaming.set_mesh(mesh)
        return self._streaming

    def _resident_for(self, cols):
        """The resident state iff ``cols`` came from it (identity on
        the infos list) — the guard every streaming fast path sits
        behind, so a planner fed foreign columns can never read stale
        resident caches."""
        st = self._streaming
        if st is not None and cols and cols[0] is st.infos:
            return st
        return None

    def streaming_snapshot(self):
        """The resident tier's counters (``ResidentState.snapshot``), or
        the all-off shape; benchmark/retreat.py and chip_smoke.py read
        it to tell a run with the tier off from one with it on."""
        st = self._streaming
        if st is None or not self.streaming_enabled:
            return {"enabled": False, "dirty_frac": None, "resyncs": 0,
                    "fallbacks": 0, "incremental_ticks": 0,
                    "full_ticks": 0, "rows": 0, "device_syncs": 0}
        return st.snapshot()

    def end_tick(self) -> None:
        self._in_tick = False
        self._tick_ts = None
        if self._fused_active is not None:   # abandoned run (aborted tick)
            self.abort_fused_run(self._fused_active)
        self._cache = None

    def fail_ts(self):
        """Failure-window timestamp: frozen per tick so the fused and
        per-group paths count the same recent failures (see
        begin_tick); falls back to now() for out-of-tick densifies."""
        ts = self._tick_ts
        return ts if ts is not None else now()

    def _build_columns(self, sched):
        node_set = sched.node_set
        infos: List[NodeInfo] = list(node_set.nodes.values())
        n = len(infos)
        nb = _n_bucket(max(n, 1))
        valid = np.zeros(nb, bool)
        ready = np.zeros(nb, bool)
        cpu = np.zeros(nb, np.int64)
        mem = np.zeros(nb, np.int64)
        total = np.zeros(nb, np.int32)
        valid[:n] = True
        for i, info in enumerate(infos):
            node = info.node
            ready[i] = (node.status.state == NodeState.READY
                        and node.spec.availability == NodeAvailability.ACTIVE)
            cpu[i] = info.available_resources.nano_cpus
            mem[i] = info.available_resources.memory_bytes
            total[i] = info.active_tasks_count
        return [infos, n, nb, valid, ready, cpu, mem, total]

    # explanation builders, pipeline order (matches kernel fail_counts rows
    # and the host filters' Explain strings — filter.go)
    _EXPLAINERS = (
        lambda n: (f"{n} nodes not available for new tasks" if n != 1
                   else "1 node not available for new tasks"),
        lambda n: (f"insufficient resources on {n} nodes" if n != 1
                   else "insufficient resources on 1 node"),
        lambda n: (f"missing plugin on {n} nodes" if n != 1
                   else "missing plugin on 1 node"),
        lambda n: (f"scheduling constraints not satisfied on {n} nodes"
                   if n != 1
                   else "scheduling constraints not satisfied on 1 node"),
        lambda n: (f"unsupported platform on {n} nodes" if n != 1
                   else "unsupported platform on 1 node"),
        lambda n: (f"host-mode port already in use on {n} nodes" if n != 1
                   else "host-mode port already in use on 1 node"),
        lambda n: "max replicas per node limit exceed",
        # the quota mask column (scheduler/quota.py): must produce the
        # exact string the host QuotaFilter.explain does — err-string
        # parity between the paths is part of the differential contract
        lambda n: (f"over tenant quota on {n} nodes" if n != 1
                   else "over tenant quota on 1 node"),
    )

    def _explain(self, fail_counts: np.ndarray) -> str:
        pairs = [(int(c), ex) for c, ex in zip(fail_counts, self._EXPLAINERS)]
        pairs.sort(key=lambda p: -p[0])
        return "; ".join(ex(c) for c, ex in pairs if c > 0)

    # ------------------------------------------------------------ suitability

    def _supported(self, t: Task) -> bool:
        c = t.spec.container
        if c is not None:
            for m in c.mounts:
                if m.type == MountType.CSI:
                    return False  # volume scheduling stays on host
        placement = t.spec.placement
        if placement:
            prefs = [p for p in placement.preferences if p.spread]
            if len(prefs) > 4:
                return False  # absurdly deep spread tree: host path
            # node.ip constraints (exact AND CIDR) ride the hash/prefix
            # columns (constraint.ip_column_spec) — no longer a waiver
        res = t.spec.resources.reservations if t.spec.resources else None
        if res:
            for g in res.generic:
                if g.res_type != GenericResourceKind.DISCRETE:
                    return False
        return True

    # ---------------------------------------------------------- densification

    def _densify(self, sched, t: Task):
        """Build (or reuse) the per-tick SoA arrays from the NodeSet mirror.

        The node-level arrays (ready/cpu/mem/total, int64 for exact
        resource math) are group-independent and cached across the groups
        of one tick (begin_tick); per-service arrays (svc_tasks/failures)
        and constraint/platform/port columns are group-dependent and built
        per group.
        """
        if self._cache is not None:
            return self._cache
        st = self._streaming_for(sched)
        if st is not None:
            # O(churn): host-path mutations were hook-marked dirty, so
            # the resident columns refresh row-wise instead of rebuilding
            cols = st.refresh(sched)
        else:
            cols = self._build_columns(sched)
        if getattr(self, "_in_tick", False):
            # re-cache after an invalidation: the fresh columns already
            # reflect any host-path mutations
            self._cache = cols
            if self._host_routed:
                # a device-routed group (a launch of its own or a fused
                # run) follows a host-routed one: it paid this rebuild
                self._host_routed = False
                self._count("route_switches")
        return cols

    _launch_overhead_shared: Optional[float] = None  # per-process link cost

    def _measure_launch_overhead(self) -> None:
        """Time a minimal warm launch: dispatch + compute-epsilon + D2H
        round-trip — the fixed cost a group must amortize to be worth
        the device.  The
        result is a property of the process's device link, so it is
        measured once and shared across planner instances — re-measuring
        per instance would spend two round-trips inside every tick that
        builds a fresh planner."""
        import time as _time
        import jax as _jax
        cls = type(self)
        if cls._launch_overhead_shared is not None:
            self._launch_overhead = cls._launch_overhead_shared
            return
        nodes_in, group_in = _probe_inputs()
        try:
            _jax.device_get(self._call_plan_fn(nodes_in, group_in, 1, ()))
            t0 = _time.perf_counter()
            probe_out = _jax.device_get(
                self._call_plan_fn(nodes_in, group_in, 1, ()))
            self._launch_overhead = _time.perf_counter() - t0
            self._note_d2h("probe", (probe_out, probe_out))
            # only successful measurements are shared: caching a failed
            # probe (0.0) would poison every future planner's break-even
            cls._launch_overhead_shared = self._launch_overhead
        except Exception:
            # overhead 0.0 routes every group to the device, where the
            # breaker judges it; counted so the probe's loss is visible
            log.exception("launch-overhead probe failed")
            self._count("launch_probe_failures")
            self._launch_overhead = 0.0

    _host_cost_per_node_shared: Optional[float] = None  # per-process
    #: what the scan is taken to cost a node when its timing fails
    HOST_COST_PER_NODE_FALLBACK = 3e-6

    def _measure_host_cost_per_node(self, sched, t: Task) -> None:
        """Time what the host route pays per mirrored node, whatever
        the group's size: the host oracle's densify (the filter
        pipeline and the per-node resource and count reads,
        ``strategy.build_host_columns``) over up to 1,024 of the
        mirror's NodeInfos, after one warm pass, as the launch probe.
        A small mirror is scanned as often as makes 1,024 visits, so
        one preempted pass cannot set the figure.  A property of the
        process's CPU, so it is measured once and shared across planner
        instances; it reads the mirror and mutates nothing (its filter
        pipeline is its own, not the scheduler's)."""
        import itertools
        import time as _time
        from types import SimpleNamespace
        cls = type(self)
        if cls._host_cost_per_node_shared is None:
            try:
                infos = list(itertools.islice(
                    sched.node_set.nodes.values(), 1024))
                # all build_host_columns asks of a scheduler
                scan = SimpleNamespace(pipeline=Pipeline())
                ts = self.fail_ts()
                passes = -(-1024 // len(infos))
                strategy_mod.build_host_columns(scan, t, 1, infos, ts)
                t0 = _time.perf_counter()
                for _ in range(passes):
                    strategy_mod.build_host_columns(scan, t, 1, infos, ts)
                # only successful timings are shared, as the launch probe's
                cls._host_cost_per_node_shared = \
                    (_time.perf_counter() - t0) / (passes * len(infos))
            except Exception:
                log.exception("host-scan timing failed; constant used")
                self._count("host_cost_probe_failures")
        shared = cls._host_cost_per_node_shared
        self.host_cost_per_node = self.HOST_COST_PER_NODE_FALLBACK \
            if shared is None else shared
        self.stats["host_cost_per_node"] = self.host_cost_per_node

    def _route_costs(self, sched, t: Task, n_tasks: int, scan: bool = True):
        """The two sides of the break-even, in seconds, and the mirror's
        size: (what the host route would cost a group of ``n_tasks``
        like ``t``, what a device launch must beat, the ``nodes`` the
        host route scans for it: every mirrored node, or none where
        ``scan`` is False).  A pure function of (n_tasks, nodes) once
        the two probes ran."""
        nodes = len(sched.node_set.nodes) if scan else 0
        if self._launch_overhead is None:
            self._measure_launch_overhead()
        if self.host_cost_per_node is None and nodes:
            self._measure_host_cost_per_node(sched, t)
        host = (nodes * (self.host_cost_per_node or 0.0)
                + n_tasks * self.host_cost_per_task)
        return host, 0.8 * self._launch_overhead, nodes

    def _below_break_even(self, sched, t: Task, n_tasks: int,
                          scan: bool = True, sp=None) -> bool:
        """True when a group of ``n_tasks`` like ``t`` is too small to
        amortize the device launch overhead: the host route, which
        scans the scheduler's node mirror for it (``scan``), is
        estimated cheaper.  The single predicate every routing site
        shares — dispatch_group, the host pre-validate path, and the
        fused-run probe must agree on it, or fused and per-group routing
        drift apart silently.  A span ``sp`` is given the comparison
        that decided, to hold against sched.host_fallback's and
        plan.inflight's readings."""
        if not self.enable_small_group_routing:
            return False
        host, device, nodes = self._route_costs(sched, t, n_tasks, scan)
        if sp is not None:
            sp.args = dict(sp.args or {},
                           host_est_ms=round(host * 1e3, 3),
                           device_est_ms=round(device * 1e3, 3),
                           nodes=nodes)
        return host < device

    def _to_host(self, reason: str) -> str:
        """The group rides the host route, which mutates NodeInfos the
        cached columns mirror: they are dropped, the next device-routed
        group of the tick rebuilds them (``route_switches``), and the
        scheduler's ``sched.host_route`` span is told why."""
        self._cache = None
        self._host_routed = True
        self.last_host_reason = reason
        return reason

    def _fallback(self) -> bool:
        self._count("groups_fallback")
        self._to_host("fallback")
        return False

    def _node_value(self, info: NodeInfo, key: str) -> str:
        node = info.node
        lk = key.lower()
        if lk == "node.id":
            return node.id
        if lk == "node.ip" or lk.startswith("node.ip/"):
            # hash/prefix column keys minted by constraint.ip_column_spec:
            # "node.ip" = canonical address, "node.ip/<p>" = canonical
            # containing network at prefix length p
            return constraint_mod.ip_node_value(
                node.status.addr if node.status else "", lk)
        if lk == "node.hostname":
            return node.description.hostname if node.description else ""
        if lk == "node.role":
            return "MANAGER" if node.spec.desired_role == 1 else "WORKER"
        if lk == "node.platform.os":
            return (node.description.platform.os
                    if node.description and node.description.platform else "")
        if lk == "node.platform.arch":
            return (node.description.platform.architecture
                    if node.description and node.description.platform else "")
        if lk.startswith(constraint_mod.NODE_LABEL_PREFIX):
            return node.spec.annotations.labels.get(
                key[len(constraint_mod.NODE_LABEL_PREFIX):], "")
        if lk.startswith(constraint_mod.ENGINE_LABEL_PREFIX):
            if node.description and node.description.engine:
                return node.description.engine.labels.get(
                    key[len(constraint_mod.ENGINE_LABEL_PREFIX):], "")
            return ""
        return None  # unknown key

    # ----------------------------------------------------------- entry point

    def schedule_group(self, sched, task_group: Dict[str, Task],
                       decisions) -> bool:
        """Serial entry point: dispatch + immediate fetch.  The pipelined
        scheduler calls the two stages separately (commit work runs
        between them); both paths share exactly this code, so pipelining
        cannot change placements."""
        handle = self.dispatch_group(sched, task_group, decisions)
        if handle is None:
            return False
        return self.fetch_group(handle)

    def _route_to_host(self, sched, t: Task, k: int, sp=None):
        """The router's decision for one group of ``k`` tasks like ``t``:
        (the reason it rides the host path, or None for the device; its
        resolved strategy).  Routing counters, breaker bookkeeping and
        column-cache invalidation are applied here, exactly once; the
        ``plan.route`` span ``sp`` gets the break-even's two sides."""
        if not self._supported(t):
            self._fallback()
            return "fallback", None
        sinfo = strategy_mod.resolve(strategy_mod.strategy_of(t))
        if sinfo is None:
            # unknown strategy name (written behind the API): the host
            # path serves it through the spread tree and counts the
            # strategy fallback
            self._fallback()
            return "fallback", None
        if sinfo.sid != strategy_mod.STRAT_SPREAD \
                and self._plan_fn is not plan_group_jit \
                and not hasattr(self._plan_fn, "strategy"):
            # an injected plan_fn (test stubs) owns the device path and
            # has no strategy twin: the group rides its HOST ORACLE —
            # identical placements by the seam's bit-parity contract,
            # one densify on the host instead.  Mesh ShardedPlanFn
            # exposes .strategy and keeps non-spread groups on device.
            self._count("groups_strategy_host")
            return self._to_host("strategy_host"), sinfo
        if not self.breaker.allow_device():
            # degraded mode: a sick device routes every group to the
            # host oracle until the breaker's cooldown/probe admits it
            self._count("groups_breaker_to_host")
            return self._to_host("breaker"), sinfo
        if self._below_break_even(sched, t, k, sp=sp):
            self._count("groups_small_to_host")
            self.breaker.abort_probe()   # never reached the device
            return self._to_host("host_small"), sinfo
        self.last_host_reason = None
        return None, sinfo

    def dispatch_group(self, sched, task_group: Dict[str, Task],
                       decisions) -> Optional[_InFlightPlan]:
        """Pipeline stage 1: route, densify, and async-dispatch one
        group's device plan.  Returns an in-flight handle to finish with
        ``fetch_group``, or None when the group is not device-planned
        (the caller must run the host path; routing counters and column-
        cache invalidation have already been applied exactly as the
        serial path would).

        The handle's plan was built from the CURRENT mirror state: the
        caller must fetch-and-apply it before mutating mirrors or
        building another group's inputs (enforced below), otherwise the
        dispatched placement would be read against stale columns.
        """
        t = next(iter(task_group.values()))
        with tracer.span("plan.route", "plan", tasks=len(task_group),
                         service=t.service_id) as sp:
            host, sinfo = self._route_to_host(sched, t, len(task_group),
                                              sp)
            if sp is not None:
                sp.args["route"] = host or "device"
        if host is not None:
            return None

        import time as _time
        _plan_t0 = _time.perf_counter()
        k = len(task_group)
        if k > K_CLAMP:  # beyond the kernel's 32-bit budget (see kernel.py)
            self.breaker.abort_probe()
            self._fallback()
            return None
        if self._inflight:
            self.breaker.abort_probe()
            raise RuntimeError(
                "dispatch_group with a plan already in flight: fetch it "
                "first (its apply feeds this group's input columns)")
        flat = sinfo.sid != strategy_mod.STRAT_SPREAD
        with tracer.span("plan.build_inputs", "plan", tasks=k,
                         service=t.service_id):
            built = self._build_device_inputs(sched, t, k, flat=flat)
        if built is None:
            self.breaker.abort_probe()
            self._fallback()
            return None
        if built[1] == 0:   # no valid nodes densified
            self.breaker.abort_probe()
            return None
        nodes_in, group_in, L, hier = built[7], built[8], built[9], \
            built[10]
        # the launch's name in the compile ledger, and on its span (so
        # it stands in a captured profile beside the launch)
        bucket = _bucket_label(nodes_in, group_in, L, hier)
        if flat:
            bucket += f"_st{sinfo.sid}"
        route = "strategy" if flat else "group"
        # the form the program's searches take at the leaf level
        form = search_form(L, hier[2].W if len(hier) > 2 else 0)
        try:
            with tracer.span("plan.dispatch", "plan", tasks=k,
                             service=t.service_id, label=bucket,
                             route=route, form=form) as sp:
                if flat:
                    sin = self._build_strategy_inputs(built, t, sinfo)
                    arrays = self._call_strategy_fn(nodes_in, group_in,
                                                    sin, sinfo, sp)
                else:
                    arrays = self._call_plan_fn(nodes_in, group_in, L,
                                                hier, sp)
        except Exception:
            # device dispatch failure degrades THIS group to the host
            # path and feeds the breaker — a sick device trips to
            # wholesale host fallback instead of failing the tick
            # (strategy groups land on their host oracle: bit-equal)
            log.exception("device dispatch failed; group routed to host")
            self._count("groups_device_error")
            self.breaker.record_failure()
            self._to_host("device_error")
            return None
        if flat:
            strategy_mod.count_group(sinfo.name, "device")
        if L > WIDE_TREE_LEAVES:
            # the scheduler thread's wall on the group so far: the
            # densify and the launch (fetch_group adds the wait)
            self._count("wide_tree_groups")
            if form == "dense":
                self._count("dense_tree_groups")
            self.stats["wide_tree_s"] += _time.perf_counter() - _plan_t0
        handle = _InFlightPlan(
            sched, t, task_group, decisions, built, _plan_t0, arrays,
            bucket=bucket, route=route,
            pref_L=L if not flat and _spread_prefs(t) == 1 else 0,
            form=form)
        self._inflight.append(handle)
        return handle

    def _build_device_inputs(self, sched, t, k, flat=False):
        """Densify the cluster + one task-group spec into kernel inputs.
        Shared by group planning and preassigned validation.  Returns None
        when a static bucket overflows (caller falls back to the host
        path).  ``flat``: skip the spread-preference tree (non-spread
        strategies own the scoring stage — one flat segment)."""
        cols = self._densify(sched, t)
        infos, n, nb, valid, ready, cpu, mem, total = cols
        if n == 0:
            return (infos, 0, nb, valid, cpu, mem, total, None, None, 1,
                    (), 0, 0, [], False)
        # resident fast paths (ops/streaming.py): per-service counts,
        # failure rows, platform hashes, constraint hash columns, flat
        # spread leaves and the level columns of a multi-level spread
        # tree come from row-wise-maintained caches —
        # O(touched rows) instead of an O(cluster) Python loop per
        # group.  Values are byte-identical to the loops below by
        # construction (same per-row formulas); the loops remain as the
        # tracker-less/hatch-off path AND the differential oracle.
        st = self._resident_for(cols)

        # ---- per-service arrays.  NOTE: every input keeps its full node
        # shape even when it carries no signal — shrinking no-signal
        # arrays to broadcastable stand-ins was tried (less H2D per
        # tick) and reverted: each narrow/wide
        # combination is a distinct jit signature, so cluster-state flips
        # (first failure, first active task) and new spec shapes trigger
        # 20-40s XLA recompiles at runtime — a far worse trade.
        ts = self.fail_ts()
        sid = t.service_id
        failures = np.zeros(nb, np.int32)
        if st is not None:
            svc_tasks = st.svc_tasks_col(sched, sid)
            if st.fail_rows:
                st.fill_failures(failures, ts, t)
        else:
            svc_tasks = np.zeros(nb, np.int32)
            for i, info in enumerate(infos):
                c = info.active_tasks_count_by_service.get(sid, 0)
                if c:
                    svc_tasks[i] = c
                if info.recent_failures:
                    failures[i] = info.count_recent_failures(ts, t)

        # ---- constraints
        placement = t.spec.placement
        constraints = []
        if placement and placement.constraints:
            try:
                constraints = constraint_mod.parse(placement.constraints)
            except constraint_mod.InvalidConstraint:
                constraints = []
        cc = _bucket(len(constraints), _CC_BUCKETS)
        if cc is None:
            return None
        con_hash = np.zeros((cc, 2, nb), np.int32)
        con_op = np.full(cc, 2, np.int32)     # 2 = disabled
        con_exp = np.zeros((cc, 2), np.int32)
        if constraints:
            if st is not None:
                st.fill_constraints(sched, constraints, con_hash,
                                    con_op, con_exp)
            else:
                fusedbatch.fill_constraints(self._node_value, infos, n,
                                            constraints, con_hash,
                                            con_op, con_exp)

        # ---- platforms
        platforms = placement.platforms if placement else []
        pb = _bucket(max(len(platforms), 1), _P_BUCKETS)
        if pb is None:
            return None
        plat = np.full((pb, 4), -1, np.int32)
        fusedbatch.fill_platforms(platforms, plat)
        if platforms:
            if st is not None:
                os_hash, arch_hash = st.platform_hashes()
            else:
                os_hash, arch_hash = fusedbatch.node_platform_hashes(
                    infos, nb)
        else:
            os_hash = np.zeros((2, nb), np.int32)
            arch_hash = np.zeros((2, nb), np.int32)

        # ---- resources: exact int64 mask + capacity, computed host-side so
        # device decisions match the host oracle's integer comparisons
        res = t.spec.resources.reservations if t.spec.resources else None
        cpu_d = int(res.nano_cpus) if res else 0
        mem_d = int(res.memory_bytes) if res else 0
        gen_wanted = [g for g in (res.generic if res else [])]
        res_ok = valid.copy()
        res_cap = np.full(nb, K_CLAMP, np.int64)
        for avail, demand in ((cpu, cpu_d), (mem, mem_d)):
            if demand > 0:
                res_ok &= avail >= demand
                np.minimum(res_cap, avail // demand, out=res_cap)
        for g in gen_wanted:
            if g.value <= 0:
                continue
            gen_avail = np.zeros(nb, np.int64)
            for i, info in enumerate(infos):
                avail = 0
                for r in info.available_resources.generic:
                    if r.kind == g.kind:
                        avail += (1 if r.res_type == GenericResourceKind.NAMED
                                  else r.value)
                gen_avail[i] = avail
            res_ok &= gen_avail >= g.value
            np.minimum(res_cap, gen_avail // g.value, out=res_cap)
        res_cap = np.clip(res_cap, 0, K_CLAMP).astype(np.int32)

        # ---- host ports
        port_conflict = np.zeros(nb, bool)
        port_limited = False
        if t.endpoint:
            wanted = [(p.protocol, p.published_port)
                      for p in t.endpoint.ports
                      if p.publish_mode == PublishMode.HOST
                      and p.published_port]
            if wanted:
                port_limited = True
                for i, info in enumerate(infos):
                    if info.used_host_ports:
                        port_conflict[i] = any(
                            w in info.used_host_ports for w in wanted)

        # ---- plugins (volume/network/log drivers): host-side mask
        if fusedbatch.needs_plugins(t):
            extra_mask = fusedbatch.plugin_mask(t, infos, nb)
        else:
            extra_mask = np.ones(nb, bool)

        # ---- tenant quota mask column: materialized (all-False) only
        # for groups the ledger BLOCKED at admission — the frozen
        # verdict, never recomputed here (the group's own in-tick
        # charge must not flip it).  Unblocked groups ship None so the
        # quota-free jit signatures stay untouched.
        quota_ok = None
        if fusedbatch.group_quota_blocked(sched, t):
            quota_ok = np.zeros(nb, bool)

        # ---- spread preferences -> hierarchical branch ids.  Each level's
        # segment id identifies the node's branch path prefix; the kernel's
        # stage A equalizes allocations level by level (nodeset.go:50 tree)
        leaf = np.zeros(nb, np.int32)
        L = 1
        hier = ()
        prefs = [] if flat else \
            [p for p in (placement.preferences if placement else [])
             if p.spread]
        if len(prefs) == 1:
            # the common flat case: one pass keyed by the raw value
            # (resident leaf column when the streaming plane holds one)
            descriptor = prefs[0].spread.spread_descriptor
            if st is not None:
                leaf, n_values, layout = st.flat_leaf(sched, descriptor)
            else:
                leaf, n_values, layout = fusedbatch.flat_leaf(
                    infos, nb, descriptor)
            L = _l_bucket(n_values)
            if layout is not None:
                hier = ((), None, layout)
        elif prefs:
            # two or more levels: the resident twin of the tree (its
            # level columns kept row-wise, as the flat leaf's) or the walk
            descriptors = tuple(p.spread.spread_descriptor for p in prefs)
            if st is not None:
                leaf, L, hier = st.spread_tree(sched, descriptors)
            else:
                leaf, L, hier = fusedbatch.spread_tree(infos, nb,
                                                       descriptors)
        if len(hier) > 2 and self._plan_fn is not plan_group_jit:
            # a wide level's layout is the single-device program's: an
            # injected plan_fn (the mesh's, a stub) takes the two, or
            # under one preference none
            hier = hier[:2] if hier[1] is not None else ()

        nodes_in = NodeInputs(
            valid=valid, ready=ready, res_ok=res_ok, res_cap=res_cap,
            svc_tasks=svc_tasks, total_tasks=total, failures=failures,
            leaf=leaf, os_hash=os_hash, arch_hash=arch_hash,
            port_conflict=port_conflict, extra_mask=extra_mask,
            quota_ok=quota_ok)
        group_in = GroupInputs(
            k=np.int32(k), con_hash=con_hash, con_op=con_op, con_exp=con_exp,
            plat=plat, maxrep=np.int32(
                placement.max_replicas if placement else 0),
            port_limited=np.bool_(port_limited))
        return (infos, n, nb, valid, cpu, mem, total, nodes_in, group_in,
                L, hier, cpu_d, mem_d, gen_wanted, port_limited)

    def _apply_assignments(self, sched, t, items, slots, infos,
                           decisions, cpu_d, mem_d, counts,
                           cpu, mem, total,
                           message="scheduler assigned task to node"
                           ) -> None:
        """Shared apply: clone+register the assigned tasks (C hot path
        when available) and do the per-NODE mirror arithmetic in batch.
        ``counts``: i32[nb] tasks placed per node column."""
        from ..scheduler.scheduler import SchedulingDecision

        from .. import native
        hp = native.get()
        all_tasks = sched.all_tasks
        # resident row lists when the streaming plane owns these infos
        # (identity-guarded) — kills two O(cluster) list builds per group
        st = self._streaming
        if st is not None and st.infos is not infos:
            st = None
        if getattr(sched, "block_mode", False):
            # columnar end-to-end: no per-task object materialization —
            # each group stages one (olds, nids, message) column triple and
            # commits as one array-shaped store call
            # (store.commit_task_block); mirrors keep the pre-assignment
            # object (membership + reservations are what they serve)
            node_id_by_i = st.node_ids if st is not None \
                else [info.node.id for info in infos]
            if hp is not None:
                task_dict_by_i = st.task_dicts if st is not None \
                    else [info.tasks for info in infos]
                olds, nids = hp.block_stage(items, slots, node_id_by_i,
                                            task_dict_by_i)
            else:
                olds, nids = [], []
                for (task_id, task), i in zip(items, slots):
                    olds.append(task)
                    nids.append(node_id_by_i[i])
                    infos[i].tasks[task_id] = task
            if olds:
                sched.block_draft.append((olds, nids, message))
        elif hp is not None:
            shared_status = TaskStatus(
                state=TaskState.ASSIGNED, timestamp=now(), message=message)
            node_id_by_i = st.node_ids if st is not None \
                else [info.node.id for info in infos]
            task_dict_by_i = st.task_dicts if st is not None \
                else [info.tasks for info in infos]
            hp.plan_apply(items, slots, node_id_by_i, task_dict_by_i,
                          shared_status, all_tasks, decisions,
                          SchedulingDecision)
        else:
            shared_status = TaskStatus(
                state=TaskState.ASSIGNED, timestamp=now(), message=message)
            for (task_id, task), i in zip(items, slots):
                info = infos[i]
                new_t = _fast_assign(task, info.id, shared_status)
                all_tasks[task_id] = new_t
                info.tasks[task_id] = new_t
                decisions[task_id] = SchedulingDecision(task, new_t)
        service_id = t.service_id
        idx = np.nonzero(counts)[0]
        if self._cache is not None and len(idx):
            # column-cache arithmetic stays vectorized; only the per-node
            # NodeInfo mirror below needs a Python loop
            hit = counts[idx]
            total[idx] += hit
            cpu[idx] -= hit.astype(np.int64) * cpu_d
            mem[idx] -= hit.astype(np.int64) * mem_d
        # the batched mirror arithmetic below bypasses the NodeInfo
        # mutation hooks: mark the touched rows dirty directly so the
        # resident device-input state refreshes them next absorb
        delta = getattr(sched, "delta", None)
        mark = delta.mark if delta is not None else None
        for i in idx.tolist():
            cnt = int(counts[i])
            info = infos[i]
            info.active_tasks_count += cnt
            svc_map = info.active_tasks_count_by_service
            svc_map[service_id] = svc_map.get(service_id, 0) + cnt
            ar = info.available_resources
            ar.nano_cpus -= cnt * cpu_d
            ar.memory_bytes -= cnt * mem_d
            if mark is not None:
                mark(info.node.id)

    def validate_preassigned(self, sched, tasks, decisions) -> list:
        """Validate preassigned tasks (same service) against their FIXED
        nodes in one fused device call (reference: scheduler.go:646
        taskFitNode, which walks the same filter pipeline per task).

        Admits each task iff its node passes the feasibility mask and has
        remaining capacity after earlier tasks in this batch claimed it.
        Admitted tasks are written into ``decisions`` (mirrors updated,
        ASSIGNED status); the remaining tasks are returned for the host
        path to handle (rejections need its per-filter explanations).
        """
        from ..scheduler.scheduler import SchedulingDecision
        from .kernel import feasibility_jit

        t = tasks[0]
        if not self._supported(t):
            return tasks
        c = t.spec.container
        if c is not None and (c.mounts or getattr(c, "volumes", None)):
            return tasks   # volume selection is host-path logic
        if any(tk.desired_state > TaskState.COMPLETE for tk in tasks):
            # batched mirror counting assumes every admitted task counts
            # toward active totals (nodeinfo.py:132 addTask guard) —
            # shutdown-marked stragglers take the host path
            return tasks
        if not self.breaker.allow_device():
            # breaker open: host loop validates; counted like
            # dispatch_group so route breakdowns stay honest
            self._count("groups_breaker_to_host")
            return tasks
        # the host loop checks each task against its own node
        # (_task_fit_node): it scans none, so no node term here
        if self._below_break_even(sched, t, len(tasks), scan=False):
            self.breaker.abort_probe()
            return tasks   # below device break-even: host loop
        import time as _time
        _plan_t0 = _time.perf_counter()
        with tracer.span("plan.build_inputs", "plan", tasks=len(tasks),
                         service=t.service_id):
            built = self._build_device_inputs(sched, t, len(tasks))
        if built is None or built[1] == 0:
            self.breaker.abort_probe()
            return tasks
        (infos, n, nb, valid, cpu, mem, total, nodes_in, group_in, L,
         hier, cpu_d, mem_d, gen_wanted, port_limited) = built
        if gen_wanted or port_limited:
            self.breaker.abort_probe()
            return tasks   # per-task claim bookkeeping: host path

        import jax as _jax
        _feas_bucket = "feas_" + _bucket_label(nodes_in, group_in, 1, ())
        try:
            with tracer.span("plan.feasibility", "plan", tasks=len(tasks),
                             service=t.service_id, label=_feas_bucket,
                             route="feasibility"):
                _cache_before = _jit_cache_size(feasibility_jit)
                self._note_h2d("group_inputs", (nodes_in, group_in))
                _feas_t0 = _time.perf_counter()
                _fetched = _jax.device_get(
                    feasibility_jit(nodes_in, group_in))
                _feas_dt = _time.perf_counter() - _feas_t0
                mask, cap, _ = _fetched
                self._note_d2h("feasibility", _fetched)
                _comp = _observe_compile(feasibility_jit, _feas_bucket,
                                         _cache_before, _feas_dt)
                _devtel.note_kernel(_feas_bucket, "feasibility",
                                    dispatch_s=_feas_dt, compile_s=_comp,
                                    task_rows=len(tasks), node_rows=nb)
        except Exception:
            log.exception("device feasibility failed; host validates")
            self._count("groups_device_error")
            self.breaker.record_failure()
            self._cache = None
            return tasks
        self.breaker.record_success()
        col = {info.node.id: i for i, info in enumerate(infos)}

        items = []      # (task_id, task) admitted
        slots = []      # node column per admitted task
        remaining = []
        used = np.zeros(nb, np.int32)
        for task in tasks:
            i = col.get(task.node_id)
            if i is None or not mask[i] or used[i] >= cap[i]:
                remaining.append(task)
                continue
            used[i] += 1
            items.append((task.id, task))
            slots.append(i)
        self._observe_plan(_time.perf_counter() - _plan_t0)
        if not items:
            return remaining

        with tracer.span("plan.apply", "plan", tasks=len(items),
                         service=t.service_id):
            self._apply_assignments(
                sched, t, items, slots, infos, decisions, cpu_d, mem_d,
                used, cpu, mem, total,
                message="scheduler confirmed task can run on preassigned "
                        "node")
        self._count("tasks_planned", len(items))
        return remaining

    def discard_inflight(self) -> None:
        """Drop dispatched-but-unfetched plans (aborted tick): their
        results are never applied, and the column cache is invalidated
        since mirrors may no longer match what was densified.  A
        discarded plan may have been the breaker's half-open probe —
        release the slot (no outcome observed) or the breaker would
        stay wedged in half-open with no path back to the device."""
        self.breaker.abort_probe()
        if self._inflight:
            self._inflight.clear()
            self._cache = None
        if self._fused_active is not None:
            self.abort_fused_run(self._fused_active)
            self._cache = None

    def fetch_group(self, handle: _InFlightPlan) -> bool:
        """Pipeline stage 2: block on the dispatched plan's D2H, then
        apply it to the scheduler mirrors / decision draft.  Returns True
        when the device handled the group (``task_group`` retains any
        unplaceable leftovers), False when the plan spilled and the
        caller must re-run the group through the host oracle (counters
        and cache invalidation already applied, as in the serial path).

        Handles must be fetched oldest-first (FIFO) — each plan's apply
        feeds the next plan's input columns.
        """
        import time as _time

        if not self._inflight or self._inflight[0] is not handle:
            raise RuntimeError("fetch_group out of dispatch order")
        self._inflight.popleft()
        sched, t = handle.sched, handle.t
        task_group, decisions = handle.task_group, handle.decisions
        _plan_t0 = handle.plan_t0
        (infos, n, nb, valid, cpu, mem, total, nodes_in, group_in, L,
         hier, cpu_d, mem_d, gen_wanted, port_limited) = handle.built
        k = len(task_group)
        # one round-trip for all outputs: each fetch is a host sync
        _d2h_t0 = _time.perf_counter()
        try:
            with tracer.span("plan.d2h", "plan", service=t.service_id,
                             label=handle.bucket) as sp:
                x, fail_counts, spill = self._fetch(handle.arrays, sp)
        except Exception:
            # fetch failure: the plan is lost but the group is not — it
            # re-runs through the host oracle (return False), and the
            # breaker counts the device failure
            log.exception("device fetch failed; group routed to host")
            handle.arrays = None
            self._observe_plan(_time.perf_counter() - _plan_t0)
            self._count("groups_device_error")
            self.breaker.record_failure()
            self._to_host("device_error")
            return False
        handle.arrays = None
        # the d2h wait IS the device plane's busy window: the host is
        # stalled on the accelerator, which is what saturation means here
        _d2h_dt = _time.perf_counter() - _d2h_t0
        if L > WIDE_TREE_LEAVES:
            self.stats["wide_tree_s"] += _d2h_dt
        _planes.plane(_planes.DEVICE).note_busy(_d2h_dt)
        if handle.bucket:
            # the fetch half of this plan's kernel-ledger row (bytes
            # were counted inside the fetch_plan seam)
            _devtel.note_kernel(handle.bucket, handle.route,
                                d2h_s=_d2h_dt)
        self.breaker.record_success()
        self._note_inflight(_time.perf_counter() - _plan_t0)
        if bool(spill):
            # a spread branch saturated: the host oracle's convergence
            # loop redistributes differently than the water-fill in that
            # regime (see kernel.py) — keep exact reference parity by
            # letting the host place this group
            self._observe_plan(_time.perf_counter() - _plan_t0)
            self._count("groups_spill_to_host")
            self._to_host("spill")
            return False
        self.last_explanation = self._explain(fail_counts)
        self._observe_plan(_time.perf_counter() - _plan_t0)

        # ---- apply: expand per-node counts into per-task decisions
        from ..scheduler.scheduler import SchedulingDecision
        slots = np.repeat(np.arange(x.shape[0]), x).tolist()
        items = list(task_group.items())
        ts_now = now()
        shared_status = TaskStatus(
            state=TaskState.ASSIGNED, timestamp=ts_now,
            message="scheduler assigned task to node")
        all_tasks = sched.all_tasks
        placed = 0
        # batched per-node counting below assumes every placed task counts
        # toward active-task totals, which holds only for desired_state <=
        # COMPLETE (reference: nodeinfo.go:132 addTask guard) — tasks
        # already marked for shutdown take the per-task path
        simple = (not gen_wanted and not port_limited
                  and not any(tk.desired_state > TaskState.COMPLETE
                              for _, tk in items))
        if simple:
            # batched mirror update: per-task dict entries, per-*node*
            # counter/resource arithmetic (NodeInfo.add_task is O(1) but
            # its Python cost dominates large groups when run per task)
            placed = min(len(items), len(slots))
            counts = np.asarray(x)
            with tracer.span("plan.apply", "plan", tasks=placed,
                             service=t.service_id):
                self._apply_assignments(sched, t, items[:placed],
                                        slots[:placed], infos, decisions,
                                        cpu_d, mem_d, counts, cpu, mem,
                                        total)
            if placed == len(task_group):
                task_group.clear()
            else:
                for task_id, _ in items[:placed]:
                    del task_group[task_id]
        else:
            # generic resources / host ports need per-task claim bookkeeping
            self._cache = None   # add_task mutates behind the columns
            with tracer.span("plan.apply", "plan", tasks=len(slots),
                             service=t.service_id):
                for (task_id, task), node_i in zip(items, slots):
                    info = infos[node_i]
                    new_t = _fast_assign(task, info.id, shared_status)
                    all_tasks[task_id] = new_t
                    info.add_task(new_t)
                    decisions[task_id] = SchedulingDecision(task, new_t)
                    del task_group[task_id]
                    placed += 1

        self._count("groups_planned")
        self._count_pref_group(handle.pref_L, handle.form)
        self._count("tasks_planned", placed)
        return True

    # --------------------------------------------------- victim selection

    def select_victims(self, cand, cpu_d: int, mem_d: int, gen_d: int,
                       n_picks: int, budget: int):
        """Device preemption: the victims×nodes selection kernel
        (ops/preempt.py), byte-identical to the host oracle — including
        the single-kind generic-resource column (``gen_d``; 0 = none).
        Routed through the SAME breaker seam as planning: an open
        breaker or any device failure returns None and the scheduler's
        supervisor runs the host oracle instead — selection never fails
        a tick."""
        import time as _time
        from . import preempt as _preempt
        if not self.breaker.allow_device():
            self._count("preempt_breaker_to_host")
            return None
        try:
            before = _jit_cache_size(_preempt.select_victims_jit)
            t0 = _time.perf_counter()
            with tracer.span("plan.preempt", "plan", picks=n_picks,
                             route="preempt") as sp:
                picks, bucket, fn = _preempt.plan_victims(
                    cand, cpu_d, mem_d, gen_d, n_picks, budget)
                if sp is not None:     # the label is the plan's to give
                    sp.args["label"] = bucket
            dt = _time.perf_counter() - t0
            comp = _observe_compile(fn, bucket, before, dt)
            _devtel.note_kernel(bucket, "preempt", dispatch_s=dt,
                                compile_s=comp, task_rows=n_picks,
                                node_rows=int(cand.ok.shape[0]))
        except Exception:
            log.exception("device victim selection failed; host oracle")
            self._count("preempt_device_error")
            self.breaker.record_failure()
            return None
        self.breaker.record_success()
        return picks

    # -------------------------------------------- gang feasibility check

    def gang_feasible(self, sched, t: Task, k: int) -> Optional[bool]:
        """Group-level all-members-feasible verdict for a gang member
        group (ops/kernel.py ``gang_fit``): True/False when a verdict
        was computed, None when no verdict is available (static bucket
        overflow) and the caller should decide by placement attempt +
        rollback instead.  Device behind the planner breaker with the
        bit-equal numpy host oracle (scheduler/gang.py) serving
        demotions — a breaker flip never changes an admission verdict.
        """
        built = self._build_device_inputs(sched, t, k)
        if built is None:
            return None
        (infos, n, nb, valid, cpu, mem, total, nodes_in, group_in,
         L, hier, cpu_d, mem_d, gen_wanted, port_limited) = built
        if n == 0:
            return False
        bucket = _bucket_label(nodes_in, group_in, L, hier) + "_gf"
        return self._gang_fit_one(nodes_in, group_in, bucket)

    def _gang_fit_one(self, nodes_in, group_in, bucket: str) -> bool:
        """One gang_fit verdict: device kernel behind the breaker, the
        numpy host oracle on open breaker or device failure."""
        import time as _time
        if self.breaker.allow_device():
            try:
                before = _jit_cache_size(gang_fit_jit)
                self._note_h2d("gang_inputs", (nodes_in, group_in))
                t0 = _time.perf_counter()
                with tracer.span("plan.gang_fit", "plan",
                                 k=int(group_in.k), label=bucket,
                                 route="gang"):
                    fit, _fc = gang_fit_jit(nodes_in, group_in)
                    fit = bool(fit)
                dt = _time.perf_counter() - t0
                comp = _observe_compile(gang_fit_jit, bucket, before, dt)
                _devtel.note_kernel(bucket, "gang", dispatch_s=dt,
                                    compile_s=comp,
                                    task_rows=int(group_in.k),
                                    node_rows=nodes_in.valid.shape[0])
            except Exception:
                log.exception("device gang_fit failed; host oracle")
                self._count("gang_device_error")
                self.breaker.record_failure()
            else:
                self.breaker.record_success()
                self._count("gang_fit_device")
                return fit
        from ..scheduler import gang as gang_mod
        self._count("gang_fit_host")
        fit, _fc = gang_mod.gang_fit_host(nodes_in, group_in)
        return fit

    def gang_feasible_many(self, sched, wants) -> list:
        """Fused gang route: verdicts for ``wants`` = [(t, k), ...].
        Same-signature groups (identical bucket label, same quota-mask
        presence) stack on a leading gang axis and judge in ONE
        ``gang_fit_fused_jit`` call (bucket suffix ``_gfF``);
        singletons and breaker demotions take the per-group route.
        Returns [Optional[bool]] aligned with ``wants``."""
        import time as _time
        results: list = [None] * len(wants)
        by_bucket: Dict[str, list] = {}
        for i, (t, k) in enumerate(wants):
            built = self._build_device_inputs(sched, t, k)
            if built is None:
                continue
            (infos, n, nb, valid, cpu, mem, total, nodes_in, group_in,
             L, hier, cpu_d, mem_d, gen_wanted, port_limited) = built
            if n == 0:
                results[i] = False
                continue
            label = _bucket_label(nodes_in, group_in, L, hier)
            by_bucket.setdefault(label, []).append(
                (i, nodes_in, group_in))
        for label, rows in by_bucket.items():
            if len(rows) < 2 or not self.breaker.allow_device():
                for i, nodes_in, group_in in rows:
                    results[i] = self._gang_fit_one(
                        nodes_in, group_in, label + "_gf")
                continue
            try:
                stacked_nodes = NodeInputs(*[
                    None if f == "quota_ok"
                    and rows[0][1].quota_ok is None
                    else np.stack([getattr(r[1], f) for r in rows])
                    for f in NodeInputs._fields])
                stacked_groups = GroupInputs(*[
                    np.stack([getattr(r[2], f) for r in rows])
                    for f in GroupInputs._fields])
                before = _jit_cache_size(gang_fit_fused_jit)
                self._note_h2d("gang_inputs",
                               (stacked_nodes, stacked_groups))
                t0 = _time.perf_counter()
                with tracer.span("plan.gang_fit_fused", "plan",
                                 gangs=len(rows), label=label + "_gfF",
                                 route="gang_fused"):
                    fits, _fcs = gang_fit_fused_jit(stacked_nodes,
                                                    stacked_groups)
                    fits = [bool(f) for f in fits]
                dt = _time.perf_counter() - t0
                comp = _observe_compile(gang_fit_fused_jit,
                                        label + "_gfF", before, dt)
                _devtel.note_kernel(label + "_gfF", "gang_fused",
                                    dispatch_s=dt, compile_s=comp,
                                    groups=len(rows))
            except Exception:
                log.exception("fused gang_fit failed; host oracle")
                self._count("gang_device_error")
                self.breaker.record_failure()
                from ..scheduler import gang as gang_mod
                for i, nodes_in, group_in in rows:
                    self._count("gang_fit_host")
                    fit, _fc = gang_mod.gang_fit_host(nodes_in,
                                                      group_in)
                    results[i] = fit
            else:
                self.breaker.record_success()
                self._count("gang_fit_fused", len(rows))
                for (i, _n, _g), fit in zip(rows, fits):
                    results[i] = fit
        return results

    # ----------------------------------------------- fused many-service

    def probe_fused_run(self, sched, glist, start: int) -> list:
        """Maximal run of consecutive fusable groups from ``glist``
        [start:], as parsed GroupSpecs.  Empty when fusion is off, the
        breaker is not closed (per-group routing owns probe accounting),
        or the first group is not fusable — the scheduler then takes the
        per-group path for exactly the groups a per-group tick would
        route the same way."""
        if not self.fused_enabled or self._fused_dead:
            return []
        if self.breaker.state != BREAKER_CLOSED:
            return []
        specs = []
        with tracer.span("plan.fused_probe", "plan") as sp:
            for group in glist[start:]:
                # the span keeps the break-even's two sides as the last
                # group judged had them: the one that ended the run
                if self._below_break_even(
                        sched, next(iter(group.values())), len(group),
                        sp=sp):
                    break   # below device break-even: host path
                spec = fusedbatch.probe_group(self, sched, group)
                if spec is None:
                    break
                specs.append(spec)
            if sp is not None:
                sp.args = dict(sp.args or {}, groups=len(specs))
        return specs

    def dispatch_fused_run(self, sched, specs):
        """Densify + dispatch one fused run (>= 2 groups).  Returns a
        FusedRun handle or None when the batch cannot be built or the
        first dispatch fails — the caller falls back group-by-group
        (identical placements; no mirror state was touched here)."""
        import time as _time
        _run_t0 = _time.perf_counter()
        try:
            with tracer.span("plan.fused_build", "plan",
                             services=len(specs),
                             service=specs[0].t.service_id):
                run = fusedbatch.build_run(self, sched, specs)
        except Exception:
            log.exception("fused batch build failed; per-group path")
            self._fused_dead = True
            return None
        if run is None:
            self._count("fused_overflows")
            return None
        try:
            with tracer.span("plan.fused_prepare", "plan"), \
                    fusedbatch.x64():
                run.shared, run.carry = self._prepare_fused(run.shared,
                                                            run.carry)
            self._dispatch_fused_chunks(run)
        except Exception:
            log.exception("fused dispatch failed; per-group path")
            self._count("groups_device_error")
            self.breaker.record_failure()
            self._fused_dead = True
            return None
        if run.dispatch_dead and run.next_dispatch == 0:
            return None
        if run.L > WIDE_TREE_LEAVES:
            # the scheduler thread's wall on the run so far: its densify,
            # its node state's placement and the first launches
            # (fetch_fused_chunk adds each wait and the launch after it)
            self._count("fused_wide_runs")
            self.stats["fused_wide_s"] += _time.perf_counter() - _run_t0
        self._fused_active = run
        return run

    def _prepare_fused(self, shared, carry):
        """Device placement of a run's node state (called under the x64
        guard): mesh plan fns shard it with NamedShardings; the
        single-device path is a plain transfer.  Either way the arrays
        stay device-resident across every chunk of the run.

        With the streaming plane fresh (no mirror mutation since the
        resident device sync), the five node-state columns are ALREADY
        on device — the run seeds its FusedShared/FusedCarry from the
        resident arrays and skips their H2D transfer entirely.  Values
        equal the host mirrors bit-for-bit (the donated scatter applies
        the same per-row updates), so placements cannot change."""
        fn = self._fused_fn
        if fn is not None and hasattr(fn, "prepare_fused"):
            # mesh path: when the streaming plane's device tier is
            # sharded over THIS plan fn's mesh, the run seeds node state
            # from the resident shards — zero cross-device reshuffle,
            # only the small per-run extras transfer (sharded by the
            # plan fn).  Same identity guard as the single-device path.
            st = self._streaming
            if st is not None and (not self.streaming_enabled
                                   or shared.valid is not st.valid):
                st = None
            dev = st.device_carry() if st is not None else None
            if dev is not None and getattr(st, "_mesh_active", False) \
                    and st.mesh is getattr(fn, "mesh", None):
                self._count("streaming_device_carries")
                _devtel.note_bytes_avoided(_devtel.tree_nbytes(
                    (shared.valid, shared.ready, carry.total, carry.cpu,
                     carry.mem)))
                return fn.prepare_fused(shared, carry, resident=dev)
            return fn.prepare_fused(shared, carry)
        import jax.numpy as jnp
        from .kernel import FusedCarry, FusedShared
        # identity guard, like every other streaming fast path: the
        # run's shared.valid IS the resident host column iff build_run
        # densified from the resident state — a run built from foreign
        # columns (hatch off, tracker-less sched) must never be seeded
        # from another scheduler's resident device arrays
        st = self._streaming
        if st is not None and (not self.streaming_enabled
                               or shared.valid is not st.valid):
            st = None
        dev = st.device_carry() if st is not None else None
        if dev is not None and getattr(st, "_mesh_active", False):
            # resident tier sharded but no mesh plan fn to consume it:
            # the single-device fused path re-uploads from the host
            # mirror rather than gathering shards through the host
            dev = None
        if dev is not None:
            d_valid, d_ready, d_cpu, d_mem, d_total = dev
            self._count("streaming_device_carries")
            # the resident carry spares this run the five node-state
            # column uploads; only the small per-run extras transfer
            _devtel.note_bytes_avoided(_devtel.tree_nbytes(
                (shared.valid, shared.ready, carry.total, carry.cpu,
                 carry.mem)))
            self._note_h2d("cold_build", (shared.os_hash, shared.arch_hash,
                                          shared.svc0, carry.svc_acc))
            return (FusedShared(valid=d_valid, ready=d_ready,
                                os_hash=jnp.asarray(shared.os_hash),
                                arch_hash=jnp.asarray(shared.arch_hash),
                                svc0=jnp.asarray(shared.svc0)),
                    FusedCarry(total=d_total, cpu=d_cpu, mem=d_mem,
                               svc_acc=jnp.asarray(carry.svc_acc)))
        self._note_h2d("cold_build", (tuple(shared), tuple(carry)))
        return (FusedShared(*(jnp.asarray(a) for a in shared)),
                FusedCarry(*(jnp.asarray(a) for a in carry)))

    def _fused_jit_probe(self):
        """The underlying jit callable whose cache growth is observed
        for compile accounting (None when the plan fn hides it)."""
        if self._fused_fn is None:
            return plan_fused_jit
        from ..parallel.sharded import plan_fused_sharded
        return plan_fused_sharded

    def _strategy_jit_probe(self):
        """Strategy-kernel twin of ``_fused_jit_probe``."""
        if not hasattr(self._plan_fn, "strategy"):
            return plan_strategy_jit
        from ..parallel.sharded import plan_strategy_sharded
        return plan_strategy_sharded

    def _dispatch_fused_chunks(self, run) -> None:
        """Dispatch chunks until two are in flight (or the run is fully
        dispatched).  Two in flight = the device computes chunk i+1
        while the host fetches/applies/commits chunk i; deeper would
        only hold H2D buffers longer.  A dispatch failure marks the run
        dispatch-dead: already-dispatched chunks still apply, the rest
        of the tick rides the per-group path."""
        import time as _time
        while (not run.dispatch_dead and not run.aborted
               and run.next_dispatch < len(run.chunks)
               and run.next_dispatch - run.next_fetch < 2):
            c = run.chunks[run.next_dispatch]
            bucket = run.bucket_label(c)
            probe = self._fused_jit_probe()
            before = _jit_cache_size(probe)
            h2d = self._note_h2d("fused_inputs", (c.groups, c.strat))
            c.t0 = _time.perf_counter()
            try:
                with tracer.span("plan.dispatch", "plan", tasks=c.tasks,
                                 fused_groups=c.count,
                                 service=run.specs[c.start].t.service_id,
                                 label=bucket, route="fused",
                                 form=run.form, L=run.L,
                                 h2d_bytes=h2d):
                    with fusedbatch.x64():
                        fn = (self._fused_fn.fused
                              if self._fused_fn is not None
                              else plan_fused_jit)
                        if c.strat is not None:
                            xs, fcs, spills, carry = fn(
                                run.shared, c.groups, run.carry, run.L,
                                c.strat)
                        else:
                            xs, fcs, spills, carry = fn(
                                run.shared, c.groups, run.carry, run.L)
            except Exception:
                log.exception("fused chunk dispatch failed; remaining "
                              "groups ride the per-group path")
                self._count("groups_device_error")
                self.breaker.record_failure()
                self._fused_dead = True
                run.dispatch_dead = True
                return
            dt = _time.perf_counter() - c.t0
            comp = _observe_compile(probe, bucket, before, dt)
            _devtel.note_kernel(bucket, "fused", dispatch_s=dt,
                                compile_s=comp, groups=c.count,
                                task_rows=c.tasks)
            c.arrays = (xs, fcs, spills)
            c.groups = None   # release the np staging buffers
            c.strat = None
            run.carry = carry   # device-resident; never fetched
            run.next_dispatch += 1
            self._count("fused_chunks")

    def fetch_fused_chunk(self, run):
        """Block on the next chunk's D2H and prime the following
        dispatch.  Returns (x [G, N], fail_counts [G, 7], spill [G],
        start, count) as numpy, or None when the run is exhausted or
        died (remaining groups take the per-group path)."""
        import time as _time
        if run.aborted or run.next_fetch >= run.next_dispatch:
            return None
        c = run.chunks[run.next_fetch]
        _d2h_t0 = _time.perf_counter()
        try:
            with tracer.span("plan.d2h", "plan", fused_groups=c.count,
                             service=run.specs[c.start].t.service_id,
                             label=run.bucket_label(c)) as sp:
                xs, fcs, spills = self._fetch(c.arrays, sp)
        except Exception:
            log.exception("fused fetch failed; remaining groups ride "
                          "the per-group path")
            self._count("groups_device_error")
            self.breaker.record_failure()
            self._fused_dead = True
            self._cache = None
            self.abort_fused_run(run)
            return None
        c.arrays = None
        run.next_fetch += 1
        self.breaker.record_success()
        end = _time.perf_counter()
        _planes.plane(_planes.DEVICE).note_busy(end - _d2h_t0)
        _devtel.note_kernel(run.bucket_label(c), "fused",
                            d2h_s=end - _d2h_t0)
        # chunk windows overlap (two dispatches in flight): charge
        # plan_seconds only the wall time this chunk ADDED beyond the
        # previous fetch, or summed plan_s would exceed the tick wall
        self._observe_plan(end - max(c.t0, run.last_fetch_end))
        run.last_fetch_end = end
        self._note_inflight(end - c.t0)
        self._dispatch_fused_chunks(run)   # keep the pipeline primed
        if run.L > WIDE_TREE_LEAVES:
            self.stats["fused_wide_s"] += _time.perf_counter() - _d2h_t0
        return (np.asarray(xs), np.asarray(fcs), np.asarray(spills),
                c.start, c.count)

    def apply_fused_group(self, run, gi: int, x_row, fail_row,
                          decisions) -> int:
        """Apply one fused group's placements to the scheduler mirrors /
        decision draft — the same simple-path apply as ``fetch_group``
        (fusability guarantees no generics/ports/shutdown stragglers).
        Returns the number of tasks placed; the group dict retains any
        unplaceable leftovers and ``last_explanation`` is set for the
        caller's no-suitable-node pass."""
        spec = run.specs[gi]
        sched, t, task_group = run.sched, spec.t, spec.group
        infos, n, nb, valid, ready, cpu, mem, total = run.cols
        self.last_explanation = self._explain(fail_row)
        x = np.asarray(x_row)
        slots = np.repeat(np.arange(x.shape[0]), x).tolist()
        items = list(task_group.items())
        placed = min(len(items), len(slots))
        with tracer.span("plan.apply", "plan", tasks=placed,
                         service=t.service_id):
            self._apply_assignments(sched, t, items[:placed],
                                    slots[:placed], infos, decisions,
                                    spec.cpu_d, spec.mem_d, x, cpu, mem,
                                    total)
        if placed == len(task_group):
            task_group.clear()
        else:
            for task_id, _ in items[:placed]:
                del task_group[task_id]
        run.applied = gi + 1
        self._count("groups_fused")
        self._count_pref_group(spec.pref_L, run.form)
        if run.L > WIDE_TREE_LEAVES:
            self._count("fused_wide_groups")
        if spec.sid:
            # non-spread group served by the fused device path: same
            # per-strategy route accounting as the per-group kernel
            strategy_mod.count_group(spec.sname, "device")
        self._count("tasks_planned", placed)
        return placed

    def note_fused_spill(self, run) -> None:
        """A fused group's spread branches saturated: the group goes to
        the host oracle for exact reference parity (same flag as the
        per-group path), which invalidates the column cache and aborts
        the rest of the run — later groups were planned against this
        group's device placement, which no longer happens."""
        self._count("groups_spill_to_host")
        self._cache = None
        self.abort_fused_run(run)

    def abort_fused_run(self, run) -> None:
        """Release a fused run (normal completion or abort): drop
        undispatched staging buffers and unfetched device arrays."""
        run.aborted = True
        for c in run.chunks:
            c.arrays = None
            c.groups = None
            c.strat = None
        run.carry = None
        run.shared = None
        if self._fused_active is run:
            self._fused_active = None
