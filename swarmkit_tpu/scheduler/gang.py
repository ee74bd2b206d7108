"""Gang scheduling: all-or-nothing placement units + the pipeline gate.

A task is a *gang member* when its spec opts in
(``Placement.gang`` — models/types.py).  Members sharing a gang unit
key (``TaskSpec.gang_id``, defaulting to the service id, so one gang
can span services) place **atomically**: either every pending member
of the unit commits in a single epoch-pinned store transaction, or
none does and the whole unit defers to the next tick.  A commit
containing a strict subset of a gang is a bug — the sim's
``gang-atomicity`` invariant (sim/invariants.py) fails the run on one.

The admission flow per unit (``admit_gangs``, driven from the tick):

1. **Pipeline gate** — a unit whose service declares ``depends_on``
   only schedules once the PipelineSupervisor released its stage.
2. **Completeness** — fewer pending members than the largest
   ``min_size`` across the unit defers it (members are still
   materializing in the orchestrator).
3. **Quota, all-or-nothing** — every member group must be admitted in
   full by the TenantLedger; any shortfall rolls back the charges
   already taken (``TenantLedger.uncharge``) and defers the unit.
4. **Device precheck** — ``planner.gang_feasible`` (ops/planner.py)
   runs the ``kernel.gang_fit`` reduction behind the planner breaker;
   the numpy ``gang_fit_host`` oracle below is bit-equal on the same
   densified inputs (the PR 14/15 oracle/kernel discipline), so a
   breaker demotion never changes an admission verdict.
5. **Scratch placement + single-tx commit** — members place through
   the ordinary host group path into a scratch decision set; a
   shortfall rolls every scratch placement back (mirror, volumes,
   quota).  A full placement commits all members in ONE store
   transaction with per-row re-validation — any row changed under us
   aborts the transaction and the unit defers.

Two half-placeable gangs cannot livelock: units admit in a
deterministic (-priority, first-pending age, key) order, so one gang
always wins the capacity race and the other defers intact.

Starvation (satellite of ROADMAP item 7): the preemption pass used to
trigger only for priority > 0 pending work.  Gang units that were
deferred for capacity (``GangState.blocked``) or that have waited
longer than ``SWARM_PREEMPT_AGE`` seconds are *entitled* too
(``preempt_entitled``) — they may evict strictly-lower-priority
victims (evict-only: the gang still places atomically on a later
tick, never one preemptor at a time).

``ATOMIC_ENFORCED`` / ``GATE_ENFORCED`` are checker-sensitivity
seams: tests flip them off to prove the sim's ``gang-atomicity`` and
``pipeline-order`` invariants actually fire (never touch them in
production code).
"""

from __future__ import annotations

import logging
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..models.objects import Service, Task, Volume
from ..models.types import TaskState, VolumePublishStatus, now
from ..utils.metrics import registry as _metrics
from .preempt import task_priority
from .quota import task_tenant
from .nodeinfo import task_reservations

log = logging.getLogger("gang")

#: checker-sensitivity seams (see module docstring) — tests only
ATOMIC_ENFORCED = True
GATE_ENFORCED = True

#: kernel group-size clamp (ops/kernel.py contract) — duplicated here
#: so the host oracle does not import the jax-heavy ops package
K_CLAMP = 1 << 22

#: age (seconds) after which a still-pending gang unit becomes
#: preemption-entitled even without a recorded capacity deferral
DEFAULT_PREEMPT_AGE = 30.0


def _preempt_age() -> float:
    raw = os.environ.get("SWARM_PREEMPT_AGE", "").strip()
    try:
        return float(raw) if raw else DEFAULT_PREEMPT_AGE
    except ValueError:
        return DEFAULT_PREEMPT_AGE


def gang_cfg(t: Task):
    """The task's GangConfig, or None for ordinary tasks."""
    p = t.spec.placement if t.spec is not None else None
    return p.gang if p is not None else None


def is_gang(t: Task) -> bool:
    return gang_cfg(t) is not None


def gang_unit(t: Task) -> str:
    """Gang unit key: explicit ``gang_id`` or the owning service —
    a shared gang_id joins several services into one atomic unit."""
    gid = getattr(t.spec, "gang_id", "") if t.spec is not None else ""
    return gid or t.service_id


class GangState:
    """Per-scheduler gang bookkeeping (leader-local; rebuilt from the
    pending queue after failover — ages restart, verdicts do not)."""

    def __init__(self) -> None:
        #: unit key -> first time this unit was seen pending-deferred
        self.first_pending: Dict[str, float] = {}
        #: unit keys deferred for capacity/quota (preemption-entitled)
        self.blocked: set = set()
        self.stats = {"gangs_admitted": 0, "gangs_deferred": 0,
                      "gang_tasks_placed": 0, "rollbacks": 0}

    def prune(self, live_keys) -> None:
        """Drop bookkeeping for units no longer pending (placed,
        deleted, or drained) so stale entries cannot keep the
        preemption trigger hot forever."""
        self.blocked &= set(live_keys)
        for key in list(self.first_pending):
            if key not in live_keys:
                del self.first_pending[key]


# --------------------------------------------------------- host oracle


def gang_fit_host(nodes_in, group_in) -> Tuple[bool, np.ndarray]:
    """Numpy replica of ``kernel.gang_fit`` (ops/kernel.py) on the SAME
    densified inputs: (fit, fail_counts i32[8]).

    Bit-equality argument: the masks and the capacity formula are
    integer/boolean, identical term for term; the only float is the
    final f32 capacity sum, whose >= k comparison is decided
    identically despite summation-order differences — totals < 2^24
    are exact in f32 (all addends non-negative), and totals >= 2^24
    are far above k <= K_CLAMP = 2^22 under any rounding."""
    valid = np.asarray(nodes_in.valid, bool)
    ready_m = np.asarray(nodes_in.ready, bool)
    res_m = np.asarray(nodes_in.res_ok, bool)
    plugin_m = np.asarray(nodes_in.extra_mask, bool)

    con_hash = np.asarray(group_in.con_hash)
    con_op = np.asarray(group_in.con_op)
    con_exp = np.asarray(group_in.con_exp)
    con_m = np.ones_like(ready_m)
    for i in range(con_op.shape[0]):
        eq = ((con_hash[i, 0] == con_exp[i, 0])
              & (con_hash[i, 1] == con_exp[i, 1]))
        op = int(con_op[i])
        if op == 0:
            con_m &= eq
        elif op == 1:
            con_m &= ~eq

    plat = np.asarray(group_in.plat)
    os_hash = np.asarray(nodes_in.os_hash)
    arch_hash = np.asarray(nodes_in.arch_hash)
    matched = np.zeros_like(ready_m)
    any_used = False
    for i in range(plat.shape[0]):
        row = plat[i]
        if row[0] == -1:
            continue
        any_used = True
        os_ok = ((row[0] == 0) & (row[1] == 0)) | (
            (os_hash[0] == row[0]) & (os_hash[1] == row[1]))
        arch_ok = ((row[2] == 0) & (row[3] == 0)) | (
            (arch_hash[0] == row[2]) & (arch_hash[1] == row[3]))
        matched |= os_ok & arch_ok
    plat_m = matched if any_used else np.ones_like(ready_m)

    port_limited = bool(group_in.port_limited)
    port_m = ~(port_limited & np.asarray(nodes_in.port_conflict, bool))
    maxrep = int(group_in.maxrep)
    svc_tasks = np.asarray(nodes_in.svc_tasks)
    rep_m = np.ones_like(ready_m) if maxrep == 0 else svc_tasks < maxrep
    quota_m = (np.asarray(nodes_in.quota_ok, bool)
               if nodes_in.quota_ok is not None
               else np.ones_like(ready_m))

    fail_counts = np.zeros(8, np.int32)
    mask = valid
    for fi, m in enumerate((ready_m, res_m, plugin_m, con_m, plat_m,
                            port_m, rep_m, quota_m)):
        fails = mask & ~m
        fail_counts[fi] = int(np.sum(fails))
        mask = mask & m

    k = min(int(group_in.k), K_CLAMP)
    cap = np.minimum(np.asarray(nodes_in.res_cap, np.int32),
                     np.int32(k))
    if maxrep > 0:
        cap = np.minimum(cap, np.maximum(
            np.int32(maxrep) - svc_tasks, 0).astype(np.int32))
    if port_limited:
        cap = np.minimum(cap, 1)
    cap = np.where(mask, np.maximum(cap, 0), 0).astype(np.int32)
    total = np.sum(cap.astype(np.float32))
    return bool(total >= np.float32(k)), fail_counts


# ----------------------------------------------------- queue extraction


def take_gangs(groups: Dict, one_off_tasks: Dict
               ) -> "List[Tuple[str, List[Dict[str, Task]]]]":
    """Pull every gang member out of the tick's taken queue (service
    groups AND the one-off bucket) and fold them into units.  Pure
    no-op when no task opts in — non-gang ticks stay byte-identical.
    Returns [(unit key, [member group dict, ...])] with deterministic
    member-group order (queue insertion order, one-offs last)."""
    units: Dict[str, List[Dict[str, Task]]] = {}
    for key in list(groups):
        group = groups[key]
        t0 = next((t for t in group.values() if t is not None), None)
        if t0 is None or not is_gang(t0):
            continue
        members = {tid: t for tid, t in group.items()
                   if t is not None and not t.node_id}
        del groups[key]
        if members:
            units.setdefault(gang_unit(t0), []).append(members)
    gone: List[str] = []
    for tid, t in one_off_tasks.items():
        if t is None or t.node_id or not is_gang(t):
            continue
        units.setdefault(gang_unit(t), []).append({tid: t})
        gone.append(tid)
    for tid in gone:
        del one_off_tasks[tid]
    return list(units.items())


# ------------------------------------------------------- pipeline gate


def _gate_err(service: Service) -> Optional[str]:
    """Deferral message when ``service``'s pipeline stage is not
    released, or None when the stage may schedule.  Fail-safe: a
    dependent service with no supervisor verdict yet is gated."""
    if not service.spec.depends_on:
        return None
    st = service.pipeline_status
    if st is None:
        return "awaiting upstream pipeline stage"
    if st.state == "released":
        return None
    if st.state == "halted":
        return (f"pipeline halted ({st.reason})" if st.reason
                else "pipeline halted")
    return "awaiting upstream pipeline stage"


def gate_err_for(sched, t: Task) -> Optional[str]:
    """Gate verdict for a task, from the replicated Service row."""
    if not GATE_ENFORCED or not t.service_id:
        return None
    service = sched.store.raw_get(Service, t.service_id)
    if service is None:
        return None
    return _gate_err(service)


def is_gated(sched, t: Task) -> bool:
    return gate_err_for(sched, t) is not None


def pipeline_gate(sched, group: Dict[str, Task],
                  decisions) -> Dict[str, Task]:
    """Tick-side gate for ordinary (non-gang) groups: a group whose
    service awaits an upstream pipeline stage defers wholesale with a
    pipeline message instead of flowing to placement (gang units run
    the same check inside ``admit_gangs``)."""
    t0 = next(iter(group.values()))
    err = gate_err_for(sched, t0)
    if err is None:
        return group
    defer_tasks(sched, list(group.values()), err, decisions)
    return {}


def defer_tasks(sched, tasks: List[Task], err: str, decisions) -> None:
    """The quota-defer discipline (scheduler._quota_defer): stamp the
    reason, re-enqueue for the next tick, and record a decision so the
    status write commits this tick.  Deferred tasks carry no quota
    charge (preemption headroom must not count them)."""
    from .scheduler import SchedulingDecision
    ts = now()
    for t in tasks:
        sched.quota.deferred_tasks.add(t.id)
    for t in tasks:
        new_t = t.copy()
        new_t.status.timestamp = ts
        new_t.status.err = err
        sched.all_tasks[t.id] = new_t
        sched._enqueue(new_t)
        if decisions is not None:
            decisions[t.id] = SchedulingDecision(t, new_t)


# --------------------------------------------------- atomic admission


def _unit_sort_key(sched, key: str, member_groups) -> Tuple:
    """Deterministic admission order — the livelock breaker: priority
    first, then how long the unit has been waiting (older first), then
    the key itself.  Two half-placeable gangs always race in the same
    order, so one places and the other defers intact."""
    prio = max(task_priority(next(iter(g.values())))
               for g in member_groups)
    age = sched.gang.first_pending.get(key, float("inf"))
    return (-prio, age, key)


def _rollback_scratch(sched, scratch) -> None:
    """Undo scratch placements' mirror mutations (the tick's standard
    failed-decision rollback, minus the re-enqueue — deferral stamps
    handle that)."""
    for d in scratch.values():
        sched.all_tasks[d.old.id] = d.old
        info = sched.node_set.node_info(d.new.node_id)
        if info is not None:
            info.remove_task(d.new)
        for va in d.new.volumes:
            sched.volumes.release_volume(va.id, d.new.id)


def _commit_unit(sched, scratch) -> bool:
    """Commit every member's assignment in ONE store transaction,
    re-validating each row in-tx (the _commit_preemption discipline):
    a member that changed under us — assigned elsewhere, shut down,
    version bumped — aborts the whole transaction, so the store never
    observes a partial gang.  Volume publish staging matches
    scheduler._apply_decisions_tx."""
    proposer = sched.store._proposer
    if proposer is not None \
            and getattr(proposer, "leadership_epoch", None) \
            != sched._tick_epoch:
        return False    # the tick's reign is over: nothing may commit
    result: Dict[str, bool] = {}

    def cb(tx) -> None:
        rows = []
        vols: Dict[str, Volume] = {}
        for d in scratch.values():
            cur = tx.get(Task, d.old.id)
            if (cur is None or cur.node_id
                    or cur.status.state != TaskState.PENDING
                    or cur.desired_state > TaskState.COMPLETE
                    or cur.meta.version.index
                    != d.old.meta.version.index):
                return    # write nothing: the unit defers intact
            for va in d.new.volumes:
                v = vols.get(va.id)
                if v is None:
                    v = tx.get(Volume, va.id)
                if v is None or v.spec.availability != 0:
                    return
                if not any(ps.node_id == d.new.node_id
                           for ps in v.publish_status):
                    v = v.copy()
                    v.publish_status.append(VolumePublishStatus(
                        node_id=d.new.node_id,
                        state=VolumePublishStatus.State.PENDING_PUBLISH))
                vols[va.id] = v
            rows.append(d.new)
        for r in rows:
            tx.update(r)
        for v in vols.values():
            tx.update(v)
        result["ok"] = True

    try:
        sched.store.update(cb)
    except Exception:
        log.exception("gang commit transaction failed")
        return False
    return result.get("ok", False)


def admit_gangs(sched, units, decisions) -> int:
    """Admit gang units atomically (see module docstring for the
    five-step flow).  Returns gang tasks placed this tick; deferral
    stamps ride the OUTER ``decisions`` dict (committed with the
    tick's other status writes), placed members commit here in their
    own single transactions and never enter ``decisions``."""
    state: GangState = sched.gang
    ledger = sched.quota
    quota_on = sched.quota_enabled and ledger.active
    planner = sched.batch_planner
    placed_total = 0
    units = sorted(units, key=lambda u: _unit_sort_key(sched, u[0], u[1]))

    for key, member_groups in units:
        members = [t for g in member_groups for t in g.values()]

        def deferred(err: str, blocked: bool) -> None:
            defer_tasks(sched, members, err, decisions)
            state.stats["gangs_deferred"] += 1
            _metrics.counter("swarm_gang_deferred", 1)
            if blocked:
                state.blocked.add(key)
                state.first_pending.setdefault(key, now())

        # 1. pipeline gate (any gated member service gates the unit)
        err = None
        for g in member_groups:
            err = gate_err_for(sched, next(iter(g.values())))
            if err is not None:
                break
        if err is not None:
            deferred(err, blocked=False)
            continue

        # 2. completeness: wait for the orchestrator to materialize
        # the whole gang before attempting placement.  Members already
        # placed and live count toward min_size — a gang that lost one
        # member to node churn only needs its REPLACEMENT pending, not
        # a whole new gang (else churn deadlocks the unit forever).
        need = max((gang_cfg(t).min_size for t in members
                    if gang_cfg(t) is not None), default=0)
        placed_live = sum(
            1 for t in sched.all_tasks.values()
            if t.node_id and is_gang(t) and gang_unit(t) == key
            and t.desired_state <= TaskState.COMPLETE
            and t.status.state <= int(TaskState.RUNNING))
        if len(members) + placed_live < need:
            deferred(f'gang "{key}" incomplete '
                     f'({len(members)}/{max(need - placed_live, 0)} '
                     f'members pending)', blocked=False)
            continue

        # 3. quota: all member groups admit in full or none do
        charges: List[Tuple[str, int, int, int, Task]] = []
        short_tenant: Optional[str] = None
        if quota_on:
            for g in member_groups:
                t0 = next(iter(g.values()))
                tenant = task_tenant(t0)
                res = task_reservations(t0)
                cpu_d = int(res.nano_cpus)
                mem_d = int(res.memory_bytes)
                admit = ledger.admit(tenant, cpu_d, mem_d, len(g))
                if admit is not None and admit < len(g):
                    short_tenant = tenant
                    break
                if admit is not None:
                    ledger.charge(tenant, cpu_d, mem_d, len(g))
                    ledger.note_group_charge(t0, len(g))
                    charges.append((tenant, cpu_d, mem_d, len(g), t0))

        def uncharge_all() -> None:
            for tenant, cpu_d, mem_d, n, t0 in charges:
                ledger.uncharge(tenant, cpu_d, mem_d, n)
                ledger.note_group_charge(t0, -n)

        if short_tenant is not None:
            uncharge_all()
            deferred(f'gang "{key}" over tenant quota '
                     f'(tenant "{short_tenant}")', blocked=True)
            continue

        # 4. device feasibility precheck (breaker-routed; the host
        # oracle serves demotions bit-identically).  None = no verdict
        # (planner absent / bucket overflow): the placement attempt +
        # rollback below decides instead.  The seam disables the whole
        # all-or-nothing apparatus, precheck included, so the partial
        # commit the sensitivity test needs can actually happen.
        feasible: Optional[bool] = None
        if planner is not None and ATOMIC_ENFORCED:
            wants = [(next(iter(g.values())), len(g))
                     for g in member_groups]
            if len(wants) >= 2 \
                    and hasattr(planner, "gang_feasible_many"):
                # multi-service unit: the fused gang route judges all
                # member groups in one device call
                verdicts = planner.gang_feasible_many(sched, wants)
            elif hasattr(planner, "gang_feasible"):
                verdicts = [planner.gang_feasible(sched, tg, k)
                            for tg, k in wants]
            else:
                verdicts = []
            if any(v is False for v in verdicts):
                feasible = False
        if feasible is False:
            uncharge_all()
            deferred(f'gang "{key}" deferred: all-or-nothing '
                     f'placement infeasible', blocked=True)
            continue

        # 5. scratch placement through the ordinary host group path,
        # then the single-transaction commit
        scratch: Dict[str, object] = {}
        leftover: List[Task] = []
        for g in member_groups:
            work = dict(g)
            sched._schedule_group_host(work, scratch,
                                       defer_leftover=False, reason="gang")
            if work:
                leftover.extend(work.values())
        if leftover and ATOMIC_ENFORCED:
            state.stats["rollbacks"] += 1
            _rollback_scratch(sched, scratch)
            uncharge_all()
            deferred(f'gang "{key}" deferred: all-or-nothing '
                     f'placement infeasible', blocked=True)
            continue
        if leftover:
            # seam OFF (tests only): commit the partial subset so the
            # sim's gang-atomicity checker proves it fires
            defer_tasks(sched, leftover,
                        f'gang "{key}" partially placed', decisions)
        if not scratch:
            uncharge_all()
            deferred(f'gang "{key}" deferred: all-or-nothing '
                     f'placement infeasible', blocked=True)
            continue
        if not _commit_unit(sched, scratch):
            state.stats["rollbacks"] += 1
            _rollback_scratch(sched, scratch)
            uncharge_all()
            deferred(f'gang "{key}" deferred: atomic commit failed',
                     blocked=False)
            continue

        placed = len(scratch)
        placed_total += placed
        state.stats["gangs_admitted"] += 1
        state.stats["gang_tasks_placed"] += placed
        _metrics.counter("swarm_gang_admitted", 1)
        _metrics.counter("swarm_gang_tasks_placed", placed)
        state.blocked.discard(key)
        state.first_pending.pop(key, None)

    return placed_total


# -------------------------------------------------- preemption triggers


def preempt_entitled(sched, t: Task) -> bool:
    """Whether a priority-0 gang group may enter the preemption pass
    (satellite of ROADMAP item 7): deferred-for-capacity units and
    units pending longer than SWARM_PREEMPT_AGE are entitled to evict
    strictly-lower-priority victims (evict-only — the gang itself
    still places atomically on a later tick)."""
    if not is_gang(t):
        return False
    key = gang_unit(t)
    if key in sched.gang.blocked:
        return True
    first = sched.gang.first_pending.get(key)
    return first is not None and now() - first >= _preempt_age()
