"""Pluggable placement-scoring strategies: the host half of the seam.

The scorer used to be spread-only: the host oracle's ``node_less``
comparator (scheduler.py) and the device kernel's effective-level column
(ops/kernel.py ``plan_group``) both hard-coded the reference's
per-service-count spread semantics.  This module factors the *scoring
stage* into a registry of strategies that share everything else — the
bucket ladder, the feasibility masks, and the water-fill/pack-fill
placement primitives:

* ``spread``  (default): the reference semantics, untouched — spread
  groups keep riding the exact pre-seam code paths (tree walk on the
  host, ``plan_group``/``plan_fused`` on device), so placements are
  byte-identical to the pre-seam scheduler by construction.
* ``binpack``: least-free-capacity-first (capacity measured in units of
  the group's own demand).  Reduces stranded capacity under mixed-size
  replicas — the policy latent in the reference's scheduler design.
* ``weighted``: linear multi-criteria score over cpu/mem/generic
  headroom and the spread term, with per-service integer weights
  (PAPERS.md 0706.4009 multi-criteria scheduling).
* ``learned`` (experimental): a tiny fixed-weight integer MLP over
  per-node features, evaluated as just another vmap'd tasks×nodes
  kernel; weights load from a checked-in artifact trained offline
  against ``sim/scenario.py``-shaped traces (scripts/train_scorer.py;
  GFlowNet-style robust scheduling is the stretch goal, PAPERS.md
  2302.05446).

Every non-spread strategy has BOTH a host oracle (this module — pure
numpy, exact integer math) and a device kernel
(``ops/kernel.plan_strategy``).  The two consume identical integer
columns and apply identical integer formulas, so placements agree
bit-for-bit; the planner's breaker/fallback routing can therefore hand
any strategy group to the host oracle mid-tick without changing the
outcome.  All score arithmetic is integer (fixed-point for the MLP):
no float can round a host decision away from the device's.

Strategy is selected per service via the ``placement_strategy`` spec
field (``Placement.strategy``); weights ride ``strategy_weights``.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, NamedTuple, Optional

import numpy as np

from ..models.objects import Task
from ..models.types import GenericResourceKind, PublishMode, now
from ..utils.metrics import registry as _metrics
from .nodeinfo import MAX_FAILURES, NodeInfo

# ---------------------------------------------------------------- constants
#
# Shared numeric envelope.  The first block MIRRORS ops/kernel.py (the
# kernel cannot be imported from here — ops imports scheduler, never the
# reverse); tests/test_strategy.py pins the pairs equal so they cannot
# drift.  The second block is canonical HERE and imported by the kernel.

K_CLAMP = 1 << 22          # mirrors ops.kernel.K_CLAMP
F_BIG = 1 << 22            # mirrors ops.kernel.F_BIG
FAILURE_CLAMP = 63         # mirrors ops.kernel.FAILURE_CLAMP
SVC_CLAMP = (1 << 20) - 1  # mirrors ops.kernel.SVC_CLAMP
IDX_BITS = 20              # mirrors ops.kernel.IDX_BITS
TOTAL_CLAMP = (1 << 10) - 1  # mirrors ops.kernel.TOTAL_CLAMP

#: weighted-strategy term weights clamp (ints; 0 disables a term)
W_CLAMP = 15
#: headroom columns clamp (units of the group's per-task demand)
HR_CLAMP = 1023
#: binpack freeness clamp: scores occupy 10 bits of the packed fill key
#: ([0, BP_CLAMP] capacity band + [BP_CLAMP+1, 1023] failure band), so
#: key = score << IDX_BITS | idx stays under 2^30 — the same search
#: range as the spread tie keys
BP_CLAMP = 959
#: learned-scorer output clamp (leaves room under the failure band)
SCORE_CLAMP = (1 << 24) - 1
#: MLP feature clamp (10-bit features keep int32 accumulators exact)
FEAT_CLAMP = 1023
#: fixed-point shift applied after each MLP layer
MLP_SHIFT = 7
#: MLP weight magnitude clamp (int8 envelope: F*FEAT_CLAMP*127 < 2^31)
MLP_W_CLAMP = 127
#: feature order the artifact's w1 rows are trained against
MLP_FEATURES = ("svc", "total", "failures", "hr_cpu", "hr_mem", "ready")

SPREAD, BINPACK, WEIGHTED, LEARNED = \
    "spread", "binpack", "weighted", "learned"
STRAT_SPREAD, STRAT_BINPACK, STRAT_WEIGHTED, STRAT_LEARNED = 0, 1, 2, 3

#: weighted term order in the weights vector
WEIGHT_KEYS = ("spread", "cpu", "mem", "generic")


class StrategyInfo(NamedTuple):
    """One registered scoring strategy."""

    name: str
    sid: int                # static id the device kernel branches on
    uses_weights: bool      # ships the per-service weight vector
    uses_learned: bool      # ships the MLP parameter arrays


#: name -> StrategyInfo.  "" aliases spread (the unset spec default).
REGISTRY: Dict[str, StrategyInfo] = {}


def register(info: StrategyInfo) -> None:
    REGISTRY[info.name] = info


register(StrategyInfo(SPREAD, STRAT_SPREAD, False, False))
register(StrategyInfo(BINPACK, STRAT_BINPACK, False, False))
register(StrategyInfo(WEIGHTED, STRAT_WEIGHTED, True, False))
register(StrategyInfo(LEARNED, STRAT_LEARNED, False, True))


def strategy_of(t: Task) -> str:
    """The task's selected strategy name ("" normalizes to spread; an
    UNKNOWN name is returned verbatim — the scheduler serves it through
    the spread path and counts the fallback)."""
    p = t.spec.placement
    name = (p.strategy if p is not None else "") or SPREAD
    return name.lower()


def resolve(name: str) -> Optional[StrategyInfo]:
    return REGISTRY.get(name)


def count_fallback(name: str) -> None:
    """A non-spread strategy group was served by the spread path (the
    strategy could not be honored — unknown name).
    tests/test_strategy.py holds it at 0 for spread/binpack groups."""
    _metrics.counter(f'swarm_strategy_fallbacks{{strategy="{name}"}}')


def count_group(name: str, route: str) -> None:
    """Per-group routing counter: route is "device" (strategy kernel)
    or "host" (this module's oracle)."""
    _metrics.counter(
        f'swarm_strategy_groups{{route="{route}",strategy="{name}"}}')


def weights_of(t: Task) -> np.ndarray:
    """The weighted strategy's i32[4] term vector [spread, cpu, mem,
    generic], clamped to [0, W_CLAMP].  Unset/empty -> all ones, and a
    PARTIAL dict leaves the omitted terms at 1 too — writing
    {"cpu": 3} boosts cpu without silently disabling the spread term
    (a 0 must be explicit)."""
    p = t.spec.placement
    raw = (p.strategy_weights if p is not None else None) or {}
    out = np.ones(len(WEIGHT_KEYS), np.int32)
    for i, key in enumerate(WEIGHT_KEYS):
        if key not in raw:
            continue
        try:
            out[i] = min(max(int(raw[key]), 0), W_CLAMP)
        except (TypeError, ValueError):
            out[i] = 1
    return out


# ------------------------------------------------------- learned scorer

_LEARNED_PATH = os.path.join(os.path.dirname(__file__),
                             "learned_scorer.json")
_learned_cache: Optional[tuple] = None


def learned_params(path: Optional[str] = None) -> tuple:
    """The checked-in MLP artifact as (w1 i32[F,H], b1 i32[H],
    w2 i32[H], b2 i32[]) — fixed weights, loaded once, deterministic
    (NO randomness may enter here: a missing artifact is an error, not
    a random init — the determinism lint pins this).  Weights clamp to
    the int8 envelope so every accumulator below stays exact in
    int32."""
    global _learned_cache
    if path is None and _learned_cache is not None:
        return _learned_cache
    with open(path or _LEARNED_PATH) as f:
        doc = json.load(f)
    if doc.get("format") != "swarm-learned-scorer-v1":
        raise ValueError("unknown learned-scorer artifact format")
    if tuple(doc.get("features", ())) != MLP_FEATURES:
        raise ValueError("learned-scorer artifact feature order mismatch")
    if int(doc.get("shift", -1)) != MLP_SHIFT:
        raise ValueError("learned-scorer artifact shift mismatch")

    def arr(key, shape):
        a = np.clip(np.asarray(doc[key], np.int64),
                    -MLP_W_CLAMP, MLP_W_CLAMP).astype(np.int32)
        if a.shape != shape:
            raise ValueError(f"learned-scorer {key} shape {a.shape} != "
                             f"{shape}")
        return a

    hidden = int(doc["hidden"])
    f = len(MLP_FEATURES)
    params = (arr("w1", (f, hidden)), arr("b1", (hidden,)),
              arr("w2", (hidden,)), arr("b2", ()))
    if path is None:
        _learned_cache = params
    return params


def learned_features(svc, total, failures, hr_cpu, hr_mem,
                     ready) -> np.ndarray:
    """Per-node feature matrix i32[N, F] in MLP_FEATURES order, every
    column clamped into the 10-bit envelope.  The SAME formula runs on
    device (ops/kernel.py _learned_score) — integer, so bit-exact."""
    cols = (np.clip(svc, 0, FEAT_CLAMP),
            np.clip(total, 0, FEAT_CLAMP),
            np.clip(failures, 0, FEAT_CLAMP),
            np.clip(hr_cpu, 0, FEAT_CLAMP),
            np.clip(hr_mem, 0, FEAT_CLAMP),
            np.asarray(ready).astype(np.int32) * FEAT_CLAMP)
    return np.stack([np.asarray(c, np.int32) for c in cols], axis=-1)


def learned_score_host(features: np.ndarray, params: tuple) -> np.ndarray:
    """Fixed-point MLP forward pass, numpy.  h = relu((f·w1 + b1) >>
    SHIFT) clamped to the feature envelope; out = (h·w2 + b2) >> SHIFT
    clamped to [0, SCORE_CLAMP].  All int32, accumulators bounded by
    the clamps — exact, and identical to the device kernel."""
    w1, b1, w2, b2 = params
    f = features.astype(np.int32)
    h = np.right_shift(f @ w1 + b1, MLP_SHIFT)
    h = np.clip(h, 0, FEAT_CLAMP)
    out = np.right_shift(h @ w2 + b2, MLP_SHIFT)
    return np.clip(out, 0, SCORE_CLAMP).astype(np.int32)


# ------------------------------------------------------ scoring (host)

def failure_downweight(failures: np.ndarray) -> np.ndarray:
    """The spread kernel's failure penalty, shared verbatim by the
    waterfill strategies: nodes at/over MAX_FAILURES sink below every
    healthy node."""
    failures = np.asarray(failures, np.int64)
    return np.where(failures >= MAX_FAILURES,
                    np.clip(failures, 0, FAILURE_CLAMP), 0)


def binpack_key(res_cap, failures, idx) -> np.ndarray:
    """Packed fill-order key, lower = fill first: freeness (tasks of
    this group the node can still absorb, clamped to BP_CLAMP) in the
    top 10 bits, node index below; failure-heavy nodes ride the band
    above every healthy score."""
    res_cap = np.asarray(res_cap, np.int64)
    failures = np.asarray(failures, np.int64)
    score = np.where(failures >= MAX_FAILURES,
                     BP_CLAMP + 1 + np.clip(failures, 0, FAILURE_CLAMP),
                     np.clip(res_cap, 0, BP_CLAMP))
    return (score << IDX_BITS) | np.asarray(idx, np.int64)


def weighted_score(svc, hr_cpu, hr_mem, hr_gen, failures,
                   weights) -> np.ndarray:
    """Linear multi-criteria effective level, lower = preferred:
    spread term + inverted headroom terms (more headroom = lower
    score), failure penalty on top.  Bounded well under the 2^30
    water-level search range (15·2^20 + 3·15·1023 + 63·F_BIG)."""
    w = np.asarray(weights, np.int64)
    e = (w[0] * np.clip(np.asarray(svc, np.int64), 0, SVC_CLAMP)
         + w[1] * (HR_CLAMP - np.asarray(hr_cpu, np.int64))
         + w[2] * (HR_CLAMP - np.asarray(hr_mem, np.int64))
         + w[3] * (HR_CLAMP - np.asarray(hr_gen, np.int64))
         + failure_downweight(failures) * F_BIG)
    return e.astype(np.int32)


# -------------------------------------------- placement primitives (host)

def waterfill_host(e, cap, tie, k: int) -> np.ndarray:
    """Exact numpy mirror of ops/kernel.seg_waterfill (single segment):
    minimal level λ with fill(λ) >= k, base fill at λ-1, remainder
    granted to marginal nodes in tie order.  Device placements equal
    this bit-for-bit on equal inputs (the kernel's f32 segment sums are
    exact for every comparison that matters — see its docstring)."""
    e = np.asarray(e, np.int64)
    cap = np.asarray(cap, np.int64)
    tie = np.asarray(tie, np.int64)
    lo, hi = 0, 1 << 30
    while lo < hi:
        mid = (lo + hi) // 2
        if int(np.clip(mid - e, 0, cap).sum()) >= k:
            hi = mid
        else:
            lo = mid + 1
    lam = hi
    x = np.clip(lam - 1 - e, 0, cap)
    r = k - int(x.sum())
    if r > 0:
        marginal = (e <= lam - 1) & (x < cap)
        mt = np.sort(tie[marginal])
        if len(mt):
            thr = mt[min(r, len(mt)) - 1]
            x = x + (marginal & (tie <= thr)).astype(np.int64)
    return x.astype(np.int32)


def packfill_host(key, cap, k: int) -> np.ndarray:
    """Sequential fill in ascending key order (keys unique): each node
    takes its full capacity before the next starts — binpack.  Mirrors
    the kernel's threshold-search fill exactly."""
    key = np.asarray(key, np.int64)
    cap = np.asarray(cap, np.int64)
    order = np.argsort(key, kind="stable")
    c = cap[order]
    before = np.cumsum(c) - c
    x_o = np.clip(k - before, 0, c)
    x = np.zeros_like(cap)
    x[order] = x_o
    return x.astype(np.int32)


def plan_arrays_host(sid: int, k: int, cap, svc, total, failures,
                     hr_cpu, hr_mem, hr_gen, weights=None,
                     params=None, ready=None,
                     idx_offset: int = 0) -> np.ndarray:
    """The strategy seam's host oracle core: one group's per-node
    placement counts from densified integer columns.  ``cap`` is the
    EFFECTIVE capacity (feasibility-masked, k/maxrep/port-clamped —
    exactly what ops/kernel.feasibility_and_capacity emits); scores
    come from the strategy's formula above.  The device kernel
    (ops/kernel.plan_strategy) computes the same function."""
    n = len(cap)
    idx = np.arange(n, dtype=np.int64) + idx_offset
    kk = min(int(k), K_CLAMP)
    if sid == STRAT_WEIGHTED:
        e = weighted_score(svc, hr_cpu, hr_mem, hr_gen, failures,
                           weights if weights is not None
                           else np.ones(4, np.int32))
    elif sid == STRAT_LEARNED:
        feats = learned_features(svc, total, failures, hr_cpu, hr_mem,
                                 ready if ready is not None
                                 else np.ones(n, bool))
        score = learned_score_host(feats, params or learned_params())
        e = (score.astype(np.int64)
             + failure_downweight(failures) * F_BIG).astype(np.int32)
    else:
        raise ValueError(f"no host oracle for strategy id {sid}")
    tie = ((np.clip(np.asarray(total, np.int64), 0, TOTAL_CLAMP)
            << IDX_BITS) | idx)
    return waterfill_host(e, cap, tie, kk)


def plan_binpack_host(k: int, cap, res_cap, failures,
                      idx_offset: int = 0) -> np.ndarray:
    """Binpack host oracle: pack-fill by (freeness, index).  ``cap`` is
    the effective capacity, ``res_cap`` the raw absorbable count the
    freeness score reads (the kernel uses nodes.res_cap the same
    way)."""
    n = len(cap)
    idx = np.arange(n, dtype=np.int64) + idx_offset
    key = binpack_key(res_cap, failures, idx)
    return packfill_host(key, cap, min(int(k), K_CLAMP))


# ----------------------------------------- host column builders + entry

class HostColumns(NamedTuple):
    """Densified per-node integer columns for one group, built from the
    scheduler's NodeInfo mirror — the host twin of the planner's device
    inputs, sharing its formulas (exact int64 resource math)."""

    mask: np.ndarray      # bool[N] pipeline feasibility
    cap: np.ndarray       # i32[N] effective capacity
    res_cap: np.ndarray   # i32[N] raw absorbable count (binpack score)
    svc: np.ndarray       # i32[N]
    total: np.ndarray     # i32[N]
    failures: np.ndarray  # i32[N]
    hr_cpu: np.ndarray    # i32[N] headroom in demand units
    hr_mem: np.ndarray    # i32[N]
    hr_gen: np.ndarray    # i32[N]
    ready: np.ndarray     # bool[N]


def _headroom(avail: int, demand: int) -> int:
    if demand <= 0:
        return HR_CLAMP
    return int(min(max(avail // demand, 0), HR_CLAMP))


def build_host_columns(sched, t: Task, k: int,
                       infos: List[NodeInfo], ts: float) -> HostColumns:
    """One group's columns, mirroring ops/planner._build_device_inputs
    row formulas (res_cap = min over demanded resources of
    avail // demand in exact int64; effective cap additionally clamped
    by k, max_replicas and host-port exclusivity, zeroed off-mask)."""
    from ..models.types import NodeAvailability, NodeState

    n = len(infos)
    pipeline = sched.pipeline
    pipeline.set_task(t)
    mask = np.zeros(n, bool)
    ready = np.zeros(n, bool)
    res_cap = np.full(n, K_CLAMP, np.int64)
    svc = np.zeros(n, np.int32)
    total = np.zeros(n, np.int32)
    failures = np.zeros(n, np.int32)
    hr_cpu = np.zeros(n, np.int32)
    hr_mem = np.zeros(n, np.int32)
    hr_gen = np.zeros(n, np.int32)

    res = t.spec.resources.reservations if t.spec.resources else None
    cpu_d = int(res.nano_cpus) if res else 0
    mem_d = int(res.memory_bytes) if res else 0
    gen_wanted = [g for g in (res.generic if res else []) if g.value > 0]
    placement = t.spec.placement
    maxrep = placement.max_replicas if placement else 0
    port_limited = bool(t.endpoint and any(
        p.publish_mode == PublishMode.HOST and p.published_port
        for p in t.endpoint.ports))
    sid = t.service_id

    for i, info in enumerate(infos):
        node = info.node
        mask[i] = pipeline.process(info)
        ready[i] = (node.status.state == NodeState.READY
                    and node.spec.availability == NodeAvailability.ACTIVE)
        ar = info.available_resources
        cap_i = K_CLAMP
        if cpu_d > 0:
            cap_i = min(cap_i, int(ar.nano_cpus) // cpu_d)
        if mem_d > 0:
            cap_i = min(cap_i, int(ar.memory_bytes) // mem_d)
        gen_min = HR_CLAMP
        for g in gen_wanted:
            avail = 0
            for r in ar.generic:
                if r.kind == g.kind:
                    avail += (1 if r.res_type == GenericResourceKind.NAMED
                              else r.value)
            cap_i = min(cap_i, avail // g.value)
            gen_min = min(gen_min, _headroom(avail, g.value))
        res_cap[i] = cap_i
        svc[i] = info.active_tasks_count_by_service.get(sid, 0)
        total[i] = info.active_tasks_count
        if info.recent_failures:
            failures[i] = info.count_recent_failures(ts, t)
        hr_cpu[i] = _headroom(int(ar.nano_cpus), cpu_d)
        hr_mem[i] = _headroom(int(ar.memory_bytes), mem_d)
        hr_gen[i] = gen_min if gen_wanted else HR_CLAMP

    res_cap = np.clip(res_cap, 0, K_CLAMP).astype(np.int32)
    kk = min(int(k), K_CLAMP)
    cap = np.minimum(res_cap, kk)
    if maxrep > 0:
        cap = np.minimum(cap, np.maximum(maxrep - svc, 0))
    if port_limited:
        cap = np.minimum(cap, 1)
    cap = np.where(mask, np.maximum(cap, 0), 0).astype(np.int32)
    return HostColumns(mask, cap, res_cap, svc, total, failures,
                       hr_cpu, hr_mem, hr_gen, ready)


def plan_host(info: StrategyInfo, t: Task, cols: HostColumns,
              k: int) -> np.ndarray:
    """Placement counts for one group via ``info``'s host oracle."""
    if info.sid == STRAT_BINPACK:
        return plan_binpack_host(k, cols.cap, cols.res_cap,
                                 cols.failures)
    return plan_arrays_host(
        info.sid, k, cols.cap, cols.svc, cols.total, cols.failures,
        cols.hr_cpu, cols.hr_mem, cols.hr_gen,
        weights=weights_of(t) if info.uses_weights else None,
        params=learned_params() if info.uses_learned else None,
        ready=cols.ready)


def schedule_group_host(sched, task_group: Dict[str, Task], decisions,
                        info: StrategyInfo) -> None:
    """The scheduler's host path for a non-spread strategy group: build
    columns from the NodeInfo mirror, run the strategy's host oracle,
    and assign tasks with exactly the per-task mechanics of the spread
    tree path (volume choice, mirror add_task, decision rows).
    Leftover tasks stay in ``task_group`` for the caller's
    no-suitable-node pass."""
    from ..models.types import TaskState, TaskStatus
    from .scheduler import SchedulingDecision

    t = next(iter(task_group.values()))
    infos = list(sched.node_set.nodes.values())
    if not infos:
        return
    count_group(info.name, "host")
    ts = now()
    cols = build_host_columns(sched, t, len(task_group), infos, ts)
    x = plan_host(info, t, cols, len(task_group))
    slots = np.repeat(np.arange(len(infos)), x).tolist()
    items = list(task_group.items())
    placed = min(len(items), len(slots))
    for (task_id, task), i in zip(items[:placed], slots):
        node = infos[i]
        try:
            attachments = sched.volumes.choose_task_volumes(task, node)
        except ValueError:
            attachments = []
        new_t = task.copy()
        new_t.volumes = attachments
        new_t.node_id = node.id
        sched.volumes.reserve_task_volumes(new_t)
        new_t.status = TaskStatus(
            state=TaskState.ASSIGNED, timestamp=now(),
            message="scheduler assigned task to node")
        sched.all_tasks[task_id] = new_t
        node.add_task(new_t)
        decisions[task_id] = SchedulingDecision(task, new_t)
        del task_group[task_id]
