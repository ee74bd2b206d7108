"""Fused many-service batch builder: one program per tick.

The per-service planner pays one densify + one XLA dispatch + one D2H
round-trip per (service, spec-version) group, so a tick of G services
costs G device round-trips and G Python column builds — the
``shape_cost_x`` scaling wall (ROADMAP direction 1).  This module packs
an ordered run of *fusable* groups into ONE padded, shape-bucketed
tasks×nodes program (``ops.kernel.plan_fused``): shared node columns are
densified once, per-group columns land in bucketed group slots, and the
groups' sequential semantics (group g sees groups 0..g-1 applied) ride
the program's scan carry instead of host round-trips.  Placements are
byte-identical to the per-group path by construction — the carry updates
are exactly the per-group apply, restricted to the signals the kernel
reads.

A run is split into CHUNKS (``SWARM_FUSED_CHUNK`` groups each, always
>= 2 chunks per run) so the pipelined scheduler can overlap chunk i+1's
device compute with chunk i's host apply/commit; the carry is threaded
chunk-to-chunk as device arrays and never fetched.

Fusability is stricter than device-ability: a group that densifies fine
per-group but carries signals the fused carry does not model (generic
resources, host-published ports, multi-level spread trees,
shutdown-marked stragglers) simply breaks the run and takes the
per-group path — identical placements, one extra round-trip.  Any
builder/bucket overflow degrades the same way: group-by-group, never a
failed tick.

Resource arithmetic is exact: the carry holds int64 nano-cpus/bytes and
the kernel's floor-divisions match the host densifier bit-for-bit, so
the fused program traces and dispatches under ``enable_x64`` (scoped —
the rest of the process stays in default 32-bit mode).
"""

from __future__ import annotations

import os
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..models.objects import Task
from ..models.types import PublishMode, TaskState
from ..scheduler import constraint as constraint_mod
from ..scheduler import strategy as strategy_mod
from ..scheduler.filters import normalize_arch
from .hashing import str_hash
from .kernel import (
    FusedCarry, FusedGroups, FusedShared, FusedStrategy, K_CLAMP,
    LeafLayout, search_form,
)

# static shape buckets to bound recompiles (shared with the per-group
# planner — ops/planner.py imports these so both paths use one ladder)
CC_BUCKETS = (1, 4, 16)      # constraint slots
P_BUCKETS = (1, 4)           # platform slots

SENTINEL = (-1, -1)  # never matches any real hash column value


def bucket(n: int, buckets) -> Optional[int]:
    for b in buckets:
        if n <= b:
            return b
    return None


def n_bucket(n: int) -> int:
    b = 1024
    while b < n:
        b *= 2
    return b


def l_bucket(n: int) -> int:
    for b in (1, 16, 256, 4096):
        if n <= b:
            return b
    return 1 << (n - 1).bit_length()


def pow2_bucket(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def split_hash(h: int) -> Tuple[int, int]:
    # two non-negative int32 halves (62 effective bits)
    return (h >> 31) & 0x7FFFFFFF, h & 0x7FFFFFFF


def x64():
    """The scoped-x64 guard every fused trace/dispatch/transfer runs
    under (int64 resource carry — see module docstring)."""
    return jax.enable_x64(True)


def default_chunk_groups() -> int:
    """Groups per fused chunk (SWARM_FUSED_CHUNK, default 4)."""
    raw = os.environ.get("SWARM_FUSED_CHUNK", "")
    try:
        v = int(raw)
    except ValueError:
        return 4
    return v if v > 0 else 4


def chunk_sizes(g: int, chunk: int) -> List[int]:
    """Split ``g`` groups into chunk sizes.  A run always yields >= 2
    chunks (when it has >= 2 groups) so the pipelined tick has a chunk
    of commits to overlap the next chunk's device compute with."""
    chunk = max(1, chunk)
    if g <= 1:
        return [g] if g else []
    if g <= chunk:
        first = (g + 1) // 2
        return [first, g - first]
    out = []
    rest = g
    while rest > 0:
        take = min(chunk, rest)
        out.append(take)
        rest -= take
    return out


# ------------------------------------------------- shared column builders
#
# Single source for the host-side densification the per-group planner
# (ops/planner.py _build_device_inputs) and the fused builder both use —
# placement parity between the two paths is load-bearing, so the column
# semantics live in exactly one place.

def con_column_key(con) -> "Tuple[Optional[str], Optional[str]]":
    """(column_key, expected_value) for one constraint's hash column.
    Plain keys compare the raw node value against the raw expression;
    node.ip compiles through constraint.ip_column_spec (canonical
    address / containing-network-at-prefix values — the hash/prefix
    column).  (None, None) = the constraint can never match (malformed
    node.ip): callers encode an op-== row against the sentinel, which
    rejects every node regardless of the written operator — exactly
    the host ``_match_ip`` malformed behavior."""
    if con.key.lower() == "node.ip":   # exact: "node.iptables" is an
        #                                UNKNOWN key (host rejects all)
        spec = constraint_mod.ip_column_spec(con)
        if spec is None:
            return None, None
        return spec
    return con.key, con.exp


def fill_constraints(node_value: Callable, infos, n: int, constraints,
                     con_hash: np.ndarray, con_op: np.ndarray,
                     con_exp: np.ndarray) -> None:
    """Fill one group's constraint columns: ``con_hash`` [Cc, 2, nb]
    zeroed, ``con_op`` [Cc] pre-filled 2 (disabled), ``con_exp``
    [Cc, 2] zeroed."""
    for ci, con in enumerate(constraints):
        col_key, expected = con_column_key(con)
        if col_key is None:
            con_op[ci] = 0
            con_exp[ci] = SENTINEL
            continue
        values = [node_value(info, col_key) for info in infos]
        if any(v is None for v in values):
            # unknown key: node never matches, regardless of op
            con_op[ci] = 0
            con_exp[ci] = SENTINEL
            continue
        hi_lo = [split_hash(str_hash(v)) for v in values]
        arr = np.array(hi_lo, np.int64).T  # [2, n]
        con_hash[ci, :, :n] = arr
        con_op[ci] = con.operator
        con_exp[ci] = split_hash(str_hash(expected))


def fill_platforms(platforms, plat: np.ndarray) -> None:
    """Fill one group's platform rows (``plat`` [P, 4] pre-filled -1)."""
    for pi, p in enumerate(platforms):
        os_h = split_hash(str_hash(p.os)) if p.os else (0, 0)
        arch = normalize_arch(p.architecture)
        arch_h = (split_hash(str_hash(arch)) if arch else (0, 0))
        plat[pi] = (*os_h, *arch_h)


def node_platform_hashes(infos, nb: int) -> Tuple[np.ndarray, np.ndarray]:
    """Node platform.os / normalized-arch hash columns ([2, nb] each).
    Nodes without a description get the sentinel (PlatformFilter
    rejects them)."""
    os_hash = np.zeros((2, nb), np.int32)
    arch_hash = np.zeros((2, nb), np.int32)
    for i, info in enumerate(infos):
        desc = info.node.description
        if desc and desc.platform:
            os_hash[:, i] = split_hash(str_hash(desc.platform.os))
            arch_hash[:, i] = split_hash(
                str_hash(normalize_arch(desc.platform.architecture)))
        else:
            os_hash[:, i] = SENTINEL
            arch_hash[:, i] = SENTINEL
    return os_hash, arch_hash


def group_quota_blocked(sched, t: Task) -> bool:
    """The frozen admission verdict for ``t``'s scheduling group: True
    when the scheduler's tenant ledger blocked it this tick (the quota
    mask column must reject every node).  Schedulers without the quota
    plane (or with it disabled) never block."""
    ledger = getattr(sched, "quota", None)
    if ledger is None or not getattr(sched, "quota_enabled", False):
        return False
    return ledger.group_blocked(t)


def fused_strategies_ok(planner) -> bool:
    """Whether the planner's fused entry can serve non-spread strategy
    groups: the default kernel (plan_fused's in-scan strategy switch)
    or an injected fn that declares ``supports_strategies``
    (parallel.sharded.ShardedPlanFn).  Stubs without the flag keep the
    pre-strategy contract: non-spread groups break the run."""
    fn = getattr(planner, "_fused_fn", None)
    return fn is None or bool(getattr(fn, "supports_strategies", False))


def needs_plugins(t: Task) -> bool:
    from ..scheduler.filters import _references_volume_plugin
    c = t.spec.container
    if c is not None and any(_references_volume_plugin(m)
                             for m in c.mounts):
        return True
    return (t.spec.log_driver is not None
            and t.spec.log_driver.name not in ("", "none"))


def plugin_mask(t: Task, infos, nb: int) -> np.ndarray:
    """Plugin/volume-driver feasibility column for one group."""
    from ..scheduler.filters import PluginFilter
    extra_mask = np.ones(nb, bool)
    pf = PluginFilter()
    if pf.set_task(t):
        for i, info in enumerate(infos):
            extra_mask[i] = pf.check(info)
    return extra_mask


def flat_leaf(infos, nb: int, descriptor: str
              ) -> Tuple[np.ndarray, int, Optional[LeafLayout]]:
    """Flat (single-level) spread leaf ids keyed by the raw preference
    value, first-appearance order.  Returns (leaf [nb], value count,
    the column's ``leaf_layout`` at the count's ``l_bucket``)."""
    from ..scheduler.nodeset import _pref_value
    leaf = np.zeros(nb, np.int32)
    values: Dict[str, int] = {}
    for i, info in enumerate(infos):
        v = _pref_value(info, descriptor) or ""
        leaf[i] = values.setdefault(v, len(values))
    n_values = max(len(values), 1)
    return leaf, n_values, leaf_layout(leaf, len(infos), l_bucket(n_values))


def leaf_layout(leaf: np.ndarray, n: int, L: int) -> Optional[LeafLayout]:
    """The leaf-major dense layout (``kernel.LeafLayout``) of a wide
    leaf level, a tree's or a flat column's, over the first ``n`` rows
    of ``leaf``, the real ones: a row's rank is its place among its
    leaf's rows in row order, so an appended row takes its leaf's next
    rank.  None where
    the kernel would not take the dense form (``L`` of at most
    ``MASK_FORM_MAX_L``, or a leaf so full that ``L * W`` is over
    ``DENSE_FORM_MAX_ENTRIES``)."""
    rows = leaf[:n]
    pop = np.bincount(rows, minlength=1)
    W = pow2_bucket(int(pop.max()))
    if search_form(L, W) != "dense":
        return None
    order = np.argsort(rows, kind="stable")
    rank = np.empty(n, np.int64)
    rank[order] = np.arange(n) - (np.cumsum(pop) - pop)[rows[order]]
    slot = np.full(len(leaf), L * W, np.int32)
    slot[:n] = rows * W + rank
    return LeafLayout(slot, W)


def dense_rows(cols, n: int, L: int):
    """What a fused run of the dense form ships in place of its groups'
    leaf rows: ``(W, rows)``, ``rows[g]`` group g's slot row i32[nb]
    under the run's shared ``L`` and its widest ``W`` (None for a group
    without a preference), from ``cols[g]``, the group's ``flat_leaf``
    triple or None.  A column laid at another width, or narrower than
    the run (a preference over 256 values or fewer has no layout of its
    own: it is laid here, at ``L``), is relaid to the one ``[L, W]``:
    leaf and rank stay, ``W`` and the padding rows' no-slot move.
    None where the run keeps the row forms: ``L`` of at most
    ``MASK_FORM_MAX_L``, or some column over
    ``DENSE_FORM_MAX_ENTRIES`` at ``L`` times the widest ``W``."""
    if search_form(L, 1) != "dense":
        return None
    laid = {}     # id(leaf) -> (layout, the L its no-slot was set for)
    for col in cols:
        if col is None or id(col[0]) in laid:
            continue
        leaf, n_values, layout = col
        own_L = l_bucket(n_values)
        if layout is None:
            layout, own_L = leaf_layout(leaf, n, L), L
            if layout is None:
                return None
        laid[id(leaf)] = layout, own_L
    W = max(layout.W for layout, _own_L in laid.values())
    if search_form(L, W) != "dense":
        return None
    rows = {}
    for key, (layout, own_L) in laid.items():
        if (layout.W, own_L) == (W, L):
            rows[key] = layout.slot
            continue
        slot = np.full(len(layout.slot), L * W, np.int32)
        own = layout.slot[:n]
        slot[:n] = own // layout.W * W + own % layout.W
        rows[key] = slot
    return W, [None if col is None else rows[id(col[0])] for col in cols]


def dense_leaf(rows, gb: int, nb: int, L: int, W: int):
    """A chunk's ``FusedGroups.leaf`` and ``.flat`` in the dense form
    from its groups' ``dense_rows``: one `LeafLayout`, slot
    i32[gb, nb]; a group without a row (no preference, another
    strategy, a padded slot) has no slot anywhere and is ``flat``."""
    slot = np.full((gb, nb), L * W, np.int32)
    flat = np.ones(gb, bool)
    for j, row in enumerate(rows):
        if row is not None:
            slot[j] = row
            flat[j] = False
    return LeafLayout(slot, W), flat


def tree_inputs(segs, level_ids, n: int):
    """The kernel's view of a numbered spread tree over ``n`` rows:
    ``(leaf, L, hier)`` from the per-level segment columns and
    path-prefix -> id maps.  ``hier`` = ((seg, parent) per upper level,
    leaf_parent), every parent array at its level's ``l_bucket`` width,
    and for a wide tree a third element, ``leaf_layout``'s."""
    depth = len(segs)
    L = l_bucket(max(len(level_ids[-1]), 1))
    upper = []
    for di in range(depth - 1):
        parent = np.zeros(l_bucket(max(len(level_ids[di]), 1)), np.int32)
        if di > 0:
            for path, cid in level_ids[di].items():
                parent[cid] = level_ids[di - 1][path[:di]]
        upper.append((segs[di], parent))
    leaf_parent = np.zeros(L, np.int32)
    for path, cid in level_ids[-1].items():
        leaf_parent[cid] = level_ids[-2][path[:depth - 1]]
    layout = leaf_layout(segs[-1], n, L)
    hier = (tuple(upper), leaf_parent)
    return segs[-1], L, hier if layout is None else hier + (layout,)


def spread_path(info, descriptors) -> tuple:
    """A node's branch path down a multi-level spread tree: its value
    under each descriptor, "" where it has none."""
    from ..scheduler.nodeset import _pref_value
    return tuple(_pref_value(info, d) or "" for d in descriptors)


def spread_tree(infos, nb: int, descriptors):
    """Multi-level spread tree (two or more preferences): each level's
    segment id identifies the node's branch path prefix, numbered in
    first-appearance order in row order (a tie-break the kernel reads).
    Returns ``tree_inputs``' (leaf [nb], L, hier)."""
    paths = [spread_path(info, descriptors) for info in infos]
    level_ids: List[Dict[tuple, int]] = []
    segs: List[np.ndarray] = []
    for di in range(len(descriptors)):
        ids: Dict[tuple, int] = {}
        seg = np.zeros(nb, np.int32)
        for i, path in enumerate(paths):
            seg[i] = ids.setdefault(path[:di + 1], len(ids))
        level_ids.append(ids)
        segs.append(seg)
    return tree_inputs(segs, level_ids, len(paths))


# ----------------------------------------------------------- fusability

class GroupSpec:
    """One fusable group's parsed routing facts, captured at probe time
    and reused by the builder and the apply phase."""

    __slots__ = ("group", "t", "k", "constraints", "platforms",
                 "pref_descriptor", "wants_plugins", "cpu_d", "mem_d",
                 "maxrep", "slot", "pref_L", "quota_blocked", "sid",
                 "sname", "weights")

    def __init__(self, group: Dict[str, Task], t: Task, k: int,
                 constraints, platforms, pref_descriptor, wants_plugins,
                 cpu_d: int, mem_d: int, maxrep: int,
                 quota_blocked: bool = False, sid: int = 0,
                 sname: str = "", weights=None):
        self.group = group
        self.t = t
        self.k = k
        self.constraints = constraints
        self.platforms = platforms
        self.pref_descriptor = pref_descriptor
        self.wants_plugins = wants_plugins
        self.cpu_d = cpu_d
        self.mem_d = mem_d
        self.maxrep = maxrep
        self.slot = 0    # service slot, assigned at build time
        self.pref_L = 0  # its own preference's leaf bucket, at build time
        # frozen tenant-quota admission verdict (group_quota_blocked):
        # True builds an all-False quota mask row for this group
        self.quota_blocked = quota_blocked
        # strategy routing facts: sid 0 = spread; non-spread groups
        # ride the fused in-scan strategy switch (FusedStrategy)
        self.sid = sid
        self.sname = sname
        self.weights = weights   # i32[4] (weighted strategy) or None


def probe_group(planner, sched,
                group: Dict[str, Task]) -> Optional[GroupSpec]:
    """Fusability check for one group: everything ``dispatch_group``
    would device-plan MINUS the signals the fused carry does not model
    (generic resources, host-published ports, multi-level spread,
    shutdown-marked stragglers).  None = the group breaks the run and
    takes the per-group path."""
    t = next(iter(group.values()))
    if not planner._supported(t):
        return None
    sinfo = strategy_mod.resolve(strategy_mod.strategy_of(t))
    if sinfo is None:
        # unknown strategy name: the host path serves it through the
        # spread tree and counts the strategy fallback
        return None
    flat = sinfo.sid != strategy_mod.STRAT_SPREAD
    if flat and not fused_strategies_ok(planner):
        # an injected fused fn without the strategy switch (test stubs,
        # older mesh fns): non-spread groups break the run and ride the
        # per-group strategy kernel instead
        return None
    k = len(group)
    if k == 0 or k > K_CLAMP:
        return None
    placement = t.spec.placement
    # non-spread strategies own the scoring stage and ignore spread
    # preferences entirely (the per-group route plans them flat too)
    prefs = [] if flat else \
        [p for p in (placement.preferences if placement else [])
         if p.spread]
    if len(prefs) > 1:
        return None    # multi-level spread: per-group hier path
    res = t.spec.resources.reservations if t.spec.resources else None
    if res and res.generic:
        return None    # per-task claim bookkeeping: per-group path
    if t.endpoint and any(p.publish_mode == PublishMode.HOST
                          and p.published_port
                          for p in t.endpoint.ports):
        return None    # cross-group port claims: per-group path
    if any(tk.desired_state > TaskState.COMPLETE
           for tk in group.values()):
        return None    # batched mirror counting needs active totals
    constraints = []
    if placement and placement.constraints:
        try:
            constraints = constraint_mod.parse(placement.constraints)
        except constraint_mod.InvalidConstraint:
            constraints = []
    if bucket(len(constraints), CC_BUCKETS) is None:
        return None    # constraint-slot overflow: per-group -> host
    platforms = placement.platforms if placement else []
    if bucket(max(len(platforms), 1), P_BUCKETS) is None:
        return None
    return GroupSpec(
        group, t, k, constraints, platforms,
        prefs[0].spread.spread_descriptor if prefs else None,
        needs_plugins(t),
        int(res.nano_cpus) if res else 0,
        int(res.memory_bytes) if res else 0,
        placement.max_replicas if placement else 0,
        quota_blocked=group_quota_blocked(sched, t),
        sid=sinfo.sid, sname=sinfo.name,
        weights=(strategy_mod.weights_of(t)
                 if sinfo.uses_weights else None))


# ------------------------------------------------------------ run builder

class FusedChunk:
    """One dispatch unit of a fused run."""

    __slots__ = ("start", "count", "gb", "groups", "strat", "arrays",
                 "tasks", "t0")

    def __init__(self, start: int, count: int, gb: int,
                 groups: FusedGroups, tasks: int, strat=None):
        self.start = start
        self.count = count
        self.gb = gb
        self.groups = groups   # np-backed FusedGroups; dropped at dispatch
        self.strat = strat     # np-backed FusedStrategy or None (spread)
        self.arrays = None     # dispatched (x, fail_counts, spill) triple
        self.tasks = tasks
        self.t0 = 0.0


class FusedRun:
    """A dispatched fused batch: chunks, device carry, and everything
    the apply phase needs."""

    __slots__ = ("sched", "specs", "cols", "shared", "carry", "chunks",
                 "next_dispatch", "next_fetch", "last_fetch_end", "L",
                 "W", "nb", "cc", "pb", "sb", "has_quota", "has_strat",
                 "aborted", "dispatch_dead", "applied")

    def __init__(self, sched, specs, cols, shared, carry, chunks,
                 L, nb, cc, pb, sb, has_quota=False, has_strat=False,
                 W=0):
        self.sched = sched
        self.specs = specs
        self.cols = cols
        self.shared = shared
        self.carry = carry
        self.chunks = chunks
        self.next_dispatch = 0
        self.next_fetch = 0
        self.last_fetch_end = 0.0   # perf_counter of the last fetch
        self.L = L
        # the width of the run's one `LeafLayout`; 0 where it ships
        # none and its searches go by rows
        self.W = W
        self.nb = nb
        self.cc = cc
        self.pb = pb
        self.sb = sb
        self.has_quota = has_quota
        self.has_strat = has_strat
        self.aborted = False
        self.dispatch_dead = False
        self.applied = 0

    @property
    def n_groups(self) -> int:
        return len(self.specs)

    @property
    def form(self) -> str:
        """The form the run's spread groups search their leaf level in
        (``kernel.search_form``)."""
        return search_form(self.L, self.W)

    def bucket_label(self, chunk: FusedChunk) -> str:
        """Stable jit-signature name for one fused chunk shape."""
        q = "_q1" if self.has_quota else ""
        m = "_mx1" if self.has_strat else ""
        return (f"fused_g{chunk.gb}_nb{self.nb}_cc{self.cc}"
                f"_p{self.pb}_L{self.L}_s{self.sb}{q}{m}")


def build_run(planner, sched, specs: List[GroupSpec]
              ) -> Optional[FusedRun]:
    """Densify an ordered run of fusable groups into one fused batch.

    Returns None when the cluster has no valid nodes or a shared bucket
    cannot hold the run — the caller falls back to the per-group path
    (same placements, amortization lost)."""
    t0 = specs[0].t
    cols = planner._densify(sched, t0)
    infos, n, nb, valid, ready, cpu, mem, total = cols
    if n == 0:
        return None
    # resident fast paths (ops/streaming.py): per-service base counts,
    # platform hashes, failure rows and flat leaves come from the
    # planner's row-wise-maintained resident caches when these ARE the
    # resident columns (identity-guarded); the loops below remain the
    # tracker-less path and the differential oracle
    st = planner._resident_for(cols) \
        if hasattr(planner, "_resident_for") else None

    # ---- shared buckets across the run
    cc = max(bucket(len(sp.constraints), CC_BUCKETS) for sp in specs)
    pb = max(bucket(max(len(sp.platforms), 1), P_BUCKETS)
             for sp in specs)

    # ---- service slots (groups of one service share a slot so the
    # carry's per-service accumulator levels them together)
    slot_map: Dict[str, int] = {}
    for sp in specs:
        sp.slot = slot_map.setdefault(sp.t.service_id, len(slot_map))
    sb = pow2_bucket(len(slot_map))

    svc0 = np.zeros((sb, nb), np.int32)
    if st is not None:
        for sid, s in slot_map.items():
            svc0[s] = st.svc_tasks_col(sched, sid)
    else:
        for i, info in enumerate(infos):
            by_svc = info.active_tasks_count_by_service
            if not by_svc:
                continue
            for sid, c in by_svc.items():
                s = slot_map.get(sid)
                if s is not None and c:
                    svc0[s, i] = c

    if any(sp.platforms for sp in specs):
        if st is not None:
            os_hash, arch_hash = st.platform_hashes()
        else:
            os_hash, arch_hash = node_platform_hashes(infos, nb)
    else:
        os_hash = np.zeros((2, nb), np.int32)
        arch_hash = np.zeros((2, nb), np.int32)

    # ---- spread leaves (flat; multi-level trees never fuse) + shared L
    ts = planner.fail_ts()   # tick-frozen: parity with the per-group path
    fail_idx = list(st.fail_rows) if st is not None else \
        [i for i, info in enumerate(infos) if info.recent_failures]
    leaves: List[Optional[tuple]] = []   # flat_leaf's triple a group
    L = 1
    for sp in specs:
        if sp.pref_descriptor is not None:
            if st is not None:
                col = st.flat_leaf(sched, sp.pref_descriptor)
            else:
                col = flat_leaf(infos, nb, sp.pref_descriptor)
            leaves.append(col)
            sp.pref_L = l_bucket(col[1])
            L = max(L, sp.pref_L)
        else:
            leaves.append(None)
    # the run's form, read off its input (kernel.plan_fused): above 256
    # leaves the dense one where every preference's column has a layout
    # within the bound at the run's L and widest W; the layout is the
    # single-device program's, so an injected fused fn (the mesh's, a
    # stub) keeps the rows
    dense = dense_rows(leaves, n, L) \
        if getattr(planner, "_fused_fn", None) is None else None
    W, rows = dense or (0, [None if col is None else col[0]
                            for col in leaves])

    shared = FusedShared(valid=valid, ready=ready, os_hash=os_hash,
                         arch_hash=arch_hash, svc0=svc0)
    # carry snapshot: int64 resource columns (exact math on device),
    # int32 totals; svc placements accumulate from zero within the run
    carry = FusedCarry(
        total=total.copy(), cpu=cpu.copy(), mem=mem.copy(),
        svc_acc=np.zeros((sb, nb), np.int32))

    # ---- chunk assembly.  Quota mask rows are built for the WHOLE run
    # when ANY group in it is quota-blocked (one shape per run); a run
    # with no blocked group ships quota_ok=None — the quota-free jit
    # signature, untouched.
    has_quota = any(sp.quota_blocked for sp in specs)
    # Strategy-mixed runs carry per-group strategy ids + weighted terms
    # and ONE run-wide learned-scorer parameter set (all groups share the
    # deployed scorer).  Spread-only runs ship strat=None — the
    # strategy-free jit signature, untouched.
    has_strat = any(sp.sid for sp in specs)
    if has_strat:
        if any(sp.sid == strategy_mod.STRAT_LEARNED for sp in specs):
            lw1, lb1, lw2, lb2 = strategy_mod.learned_params()
            lw1 = np.asarray(lw1, np.int32)
            lb1 = np.asarray(lb1, np.int32)
            lw2 = np.asarray(lw2, np.int32)
            lb2 = np.asarray(lb2, np.int32)
        else:
            f = len(strategy_mod.MLP_FEATURES)
            lw1 = np.zeros((f, 1), np.int32)
            lb1 = np.zeros(1, np.int32)
            lw2 = np.zeros(1, np.int32)
            lb2 = np.zeros((), np.int32)
    chunks: List[FusedChunk] = []
    start = 0
    for count in chunk_sizes(len(specs), default_chunk_groups()):
        gb = pow2_bucket(count)
        k = np.zeros(gb, np.int32)
        slot = np.zeros(gb, np.int32)
        maxrep = np.zeros(gb, np.int32)
        cpu_d = np.zeros(gb, np.int64)
        mem_d = np.zeros(gb, np.int64)
        con_hash = np.zeros((gb, cc, 2, nb), np.int32)
        con_op = np.full((gb, cc), 2, np.int32)
        con_exp = np.zeros((gb, cc, 2), np.int32)
        plat = np.full((gb, pb, 4), -1, np.int32)
        failures = np.zeros((gb, nb), np.int32)
        if dense:
            leaf, flat = dense_leaf(rows[start:start + count], gb, nb, L, W)
        else:
            leaf, flat = np.zeros((gb, nb), np.int32), None
        extra = np.ones((gb, nb), bool)
        quota = np.ones((gb, nb), bool) if has_quota else None
        sid = np.zeros(gb, np.int32) if has_strat else None
        weights = np.zeros((gb, 4), np.int32) if has_strat else None
        tasks = 0
        for j in range(count):
            sp = specs[start + j]
            if quota is not None and sp.quota_blocked:
                quota[j] = False
            if sid is not None:
                sid[j] = sp.sid
                if sp.weights is not None:
                    weights[j] = sp.weights
            k[j] = sp.k
            slot[j] = sp.slot
            maxrep[j] = sp.maxrep
            cpu_d[j] = sp.cpu_d
            mem_d[j] = sp.mem_d
            tasks += sp.k
            if sp.constraints:
                if st is not None:
                    st.fill_constraints(sched, sp.constraints,
                                        con_hash[j], con_op[j],
                                        con_exp[j])
                else:
                    fill_constraints(planner._node_value, infos, n,
                                     sp.constraints, con_hash[j],
                                     con_op[j], con_exp[j])
            if sp.platforms:
                fill_platforms(sp.platforms, plat[j])
            for i in fail_idx:
                failures[j, i] = infos[i].count_recent_failures(ts, sp.t)
            if not dense and rows[start + j] is not None:
                leaf[j] = rows[start + j]
            if sp.wants_plugins:
                extra[j] = plugin_mask(sp.t, infos, nb)
        chunks.append(FusedChunk(
            start, count, gb,
            FusedGroups(k=k, slot=slot, maxrep=maxrep, cpu_d=cpu_d,
                        mem_d=mem_d, con_hash=con_hash, con_op=con_op,
                        con_exp=con_exp, plat=plat, failures=failures,
                        leaf=leaf, extra_mask=extra, quota_ok=quota,
                        flat=flat),
            tasks,
            strat=(FusedStrategy(sid=sid, weights=weights, w1=lw1,
                                 b1=lb1, w2=lw2, b2=lb2)
                   if has_strat else None)))
        start += count

    return FusedRun(sched, specs, cols, shared, carry, chunks,
                    L, nb, cc, pb, sb, has_quota=has_quota,
                    has_strat=has_strat, W=W)
