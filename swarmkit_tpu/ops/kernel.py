"""The TPU scheduling kernel: batched group placement as array programs.

This is the device-side replacement for the reference's hot loops
(manager/scheduler/scheduler.go:694 scheduleTaskGroup, :772
scheduleNTasksOnSubtree, :844 scheduleNTasksOnNodes, nodeset.go:50 tree):

* The filter pipeline (Ready/Resource/Constraint/Platform/Plugin/HostPort/
  MaxReplicas — filter.go) becomes a fused boolean feasibility mask over all
  nodes at once.
* The spread comparator (scheduler.go:708 nodeLess) becomes an integer
  "effective level" per node: per-service task count, down-weighted by
  recent failures.
* The sorted round-robin placement loop becomes **hierarchical
  water-filling**: raise a per-branch water level λ until the group's k
  tasks fit (respecting per-node capacity), then break ties among marginal
  nodes with a threshold search on (total-tasks, node-index).  This
  reproduces the reference's "level per-service counts first, then total
  counts, capacity-bounded" semantics without any sequential loop.

Everything is fixed-shape, 32-bit, and built exclusively from ops that XLA
maps well to TPU (reductions, elementwise selects).  The searches are
bisections in ``lax.while_loop``s whose brackets come from the inputs and
which leave when every segment has converged (see "The searches" below):
the trip count is a function of the data, at most SEARCH_STEPS_MAX, and
the result does not depend on it.  The identical code runs under plain
`jit` (single chip) and under `shard_map` with the node axis sharded over
a mesh — the only difference is the `reduce` callback, which becomes a
`psum` over the node-axis (see parallel/sharded.py).

Numeric ranges (32-bit budget):
  per-service counts clamped to 2^20; failure down-weight factor 2^22
  (dominates any real count); water levels lie in [0, 2^30]; node index
  packed in 20 bits -> supports up to 2^20 (~1M) nodes per shard; group size
  k clamped to 2^22 (the planner falls back to the host path above that).

The searches (`_bisect`, `waterfill_search`, `packfill_search`).  A search
starts from the bracket its inputs give, per segment, and stops at
``lo == hi``.  The reference is the plain form: 34 steps over [0, 2^30]
(levels) or [-1, 2^30] (keys), whatever the inputs, kept verbatim in
tests/test_waterfill_search.py.  The placements are bit-identical to it,
because the bracket always holds what that form finds or something that
places the same:
  (a) feasible segment (sum of cap >= k).  At λ = max e + k every row
      with room fills min(k + (max e - e_i), cap_i) >= min(k, cap_i), so
      the fill reaches k and the minimal λ lies in [min e, max e + k]
      (min, max over the rows with room: the others fill nothing).
      Below min e + 1 nothing fills, so for k >= 1 the lower end is safe.
  (b) infeasible segment (sum of cap < k).  The 34-step form ends at
      λ = 2^30 with x_base = cap and no marginal row, so x = cap.  The
      bracket's top gives λ = max e + k: λ - 1 - e_i >= k - 1 >= cap_i
      (every cap_i <= sum of cap < k), so x_base = cap again, no row is
      marginal and no grant is made.
  (c) k = 0 (a fused run's padded slot) or no row with room.  The bracket
      is closed at λ = 0, where the 34-step form ends too when k = 0;
      with no room x = 0 at any λ.  No step is taken.
  (d) the tie threshold.  r > 0 implies at least r marginal rows (the
      fill grows by one per marginal row from λ - 1 to λ and reaches k
      there), so the r-th smallest of their keys is the threshold and
      [min, max] of the marginal rows' keys holds it; with r = 0 or no
      marginal row no grant is made whatever the threshold.  The
      pack-fill's threshold is likewise the key of a row with room, or
      one past the largest of them where the segment cannot hold k, which
      places what 2^30 placed: every row below it takes its cap.
  (e) int32.  For spread, max e + k < 2^28 + 2^20 + 2^22 < 2^30, also
      under the fused path's `enable_x64` (e, cap, k and the brackets are
      int32 throughout).  The weighted and learned scores have their own
      clamps; the top is cut at 2^30 as before and formed as
      min(max e, 2^30 - k) + k, which cannot wrap.
A caller that hands in a ``reduce`` (the sharded twins) sees only its
shard's rows, and a bracket from them would differ between shards: it
keeps the static brackets, closed where k = 0 or r = 0 (both replicated),
and gains the convergence exit alone; ``lo`` and ``hi`` follow from
reduced sums, so the loop's predicate is the same on every shard.
  (f) the dense form's padding.  Above MASK_FORM_MAX_L a caller that
      brings the leaf level's `LeafLayout` has the columns laid
      leaf-major [L, W]; a slot no row fills reads e = cap = tie = 0.
      With cap = 0 it is no row with room, so no bracket sees it (a);
      it fills clip(λ - 0, 0, 0) = 0 at every λ and adds 0.0 to every
      sum; x_base = 0 is not < cap, so it is never marginal, counts
      for no threshold and is granted nothing (d); the pack-fill gives
      it min(cap, r) = 0.  The bucket's padding rows, which have no
      slot, have cap = 0 and no tasks: the row forms give them 0 too.
      So brackets, trip counts and placements are the scatter form's,
      and the f32 sums, taken along a row of the layout, stay inside
      the argument at the foot of this docstring.
What a step costs follows the static L and, above MASK_FORM_MAX_L,
whether the layout came (`search_form`; the table over
`MASK_FORM_MAX_L`): a reduction in three of the four forms, a walk of
every row (a scatter-add and a gather) only where a leaf level of more
than 256 segments, a tree's or a one-preference spread's flat column,
is over the layout's bound (one giant leaf among thousands), or the
caller reduces across shards.  The dense form walks the rows five
times a program (four columns in, x out), not twice a step.

Resource accounting is **exact**: the host densifier compares int64
nano-cpus/bytes and floor-divides in int64 (matching the reference's integer
comparisons, api/types.proto:68), shipping the kernel a boolean ``res_ok``
mask and an int32 per-node capacity ``res_cap`` — no float rounding can
admit/reject a node the host oracle would decide differently.

Segment sums that can exceed int32 (fill volumes up to N*k ~ 2^42) are
computed in float32, which is safe *for comparisons against k <= K_CLAMP*:
all addends are non-negative, so every partial sum <= the true total; totals
< 2^24 are therefore exact at every step, and totals >= 2^24 keep enough
relative accuracy (error ~ N*eps) to stay far above K_CLAMP = 2^22 — either
way the `sum >= k` comparison is decided correctly, in whatever order the
addends are taken (a scatter-add, a plain reduction, a reduction over the
membership mask, a reduction along a row of the dense layout).
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..scheduler.nodeinfo import MAX_FAILURES  # single source of truth
from ..scheduler.strategy import (  # strategy-seam shared envelope
    BP_CLAMP, FEAT_CLAMP, HR_CLAMP, MLP_SHIFT, SCORE_CLAMP,
    STRAT_BINPACK, STRAT_LEARNED, STRAT_SPREAD, STRAT_WEIGHTED,
)

F_BIG = 1 << 22          # failure down-weight step (dominates svc counts)
FAILURE_CLAMP = 63       # keeps e = svc + failures*F_BIG inside int32
SVC_CLAMP = (1 << 20) - 1
K_CLAMP = 1 << 22        # max group size the kernel accepts (see docstring)
LOAD_CLAMP = (1 << 24) - 1   # branch-load clamp: the f32 segment sums are
                             # exact below 2^24, so clamping there keeps
                             # stage-A branch ordering exact; branches with
                             # >16.7M tasks of one service are equi-preferred
LEVEL_TOP = 1 << 30      # ceiling of the water-level search's bracket
KEY_TOP = 1 << 30        # tie / pack keys are < 2^30: above every key
SEARCH_STEPS_MAX = 31    # ceil(log2(2^30 + 2)): the widest bracket a
                         # search can start from; most take far fewer
_I32_MAX = (1 << 31) - 1
_I32_MIN = -(1 << 31)
IDX_BITS = 20
TOTAL_CLAMP = (1 << 10) - 1   # total-tasks clamp: tie keys stay < 2^30 so
                              # the threshold search range fits in int32

Reduce = Callable[[jnp.ndarray], jnp.ndarray]


def _identity(x: jnp.ndarray) -> jnp.ndarray:
    return x


class GroupInputs(NamedTuple):
    """Per-(service, spec-version) task-group inputs, densified host-side."""

    k: jnp.ndarray              # i32 scalar: number of tasks to place
    con_hash: jnp.ndarray       # i32[Cc, 2, N]: node hash (hi,lo) per constraint
    con_op: jnp.ndarray         # i32[Cc]: 0 ==, 1 !=, 2 disabled
    con_exp: jnp.ndarray        # i32[Cc, 2]: expected (hi,lo)
    plat: jnp.ndarray           # i32[P, 4]: (os_hi, os_lo, arch_hi, arch_lo);
                                #   row -1 sentinel in col 0 = unused
    maxrep: jnp.ndarray         # i32 scalar: max replicas per node (0 = off)
    port_limited: jnp.ndarray   # bool scalar: group publishes host ports


class NodeInputs(NamedTuple):
    """Cluster-wide node state (SoA), maintained incrementally host-side."""

    valid: jnp.ndarray          # bool[N] (padding mask)
    ready: jnp.ndarray          # bool[N] READY && ACTIVE
    res_ok: jnp.ndarray         # bool[N] node meets this group's reservations
                                #   (exact int64 comparison, host-side)
    res_cap: jnp.ndarray        # i32[N] tasks of this group the node's
                                #   resources can absorb (exact int64 floor
                                #   division host-side, clipped to K_CLAMP)
    svc_tasks: jnp.ndarray      # i32[N] active tasks of this service
    total_tasks: jnp.ndarray    # i32[N] active tasks total
    failures: jnp.ndarray       # i32[N] recent failures for this service
    leaf: jnp.ndarray           # i32[N] spread-preference leaf id (0 if none)
    os_hash: jnp.ndarray        # i32[2, N] node platform.os hash (hi, lo)
    arch_hash: jnp.ndarray      # i32[2, N] normalized arch hash (hi, lo)
    port_conflict: jnp.ndarray  # bool[N] a requested host port is taken
    extra_mask: jnp.ndarray     # bool[N] plugin/volume masks ANDed host-side
    # tenant-quota mask column (scheduler/quota.py): all-False when the
    # group's tenant was exhausted at admission.  None (the default)
    # keeps the quota-free jit signatures unchanged — the column is
    # only materialized for blocked groups.
    quota_ok: Optional[jnp.ndarray] = None   # bool[N] or None


def _member(seg: jnp.ndarray, L: int) -> jnp.ndarray:
    """bool[N, L]: row i lies in segment l.  Never stored: the compiler
    fuses the compare into the reduction that reads it."""
    return seg[:, None] == jnp.arange(L, dtype=seg.dtype)[None, :]


# How a per-segment quantity is taken over the rows follows the static
# L and, above MASK_FORM_MAX_L, whether the caller brought the leaf
# level's layout (`search_form`).  A step of the level search alone, on
# one TPU v5 lite (`chip_smoke.py search_step_times`, PERF.md §6 PRs 33
# and 35; a scatter-add or a gather over N rows is a serial walk of
# them, 9-17 ns a row):
#   sum      L == 1: one segment holds every row, a plain reduction and
#            a scalar broadcast: 2.5-3.0 us at 131,072 rows
#   mask     L <= MASK_FORM_MAX_L: reduce over the [N, L] membership
#            mask, 7 ps an entry: 30.4 us at 16,384 rows and L = 256,
#            9x faster than the scatter there
#   dense    above, with a `LeafLayout` of at most
#            DENSE_FORM_MAX_ENTRIES slots, which both builders of a
#            leaf level bring (a tree's, a flat column's, the latter
#            of its own and inside a fused run): the columns are laid
#            leaf-major [L, W] once a program (a walk each, 0.7-0.8 ms
#            at 131,072 rows, and 1.1 ms for x back) and a step reduces
#            along axis 1 and broadcasts back: 3.8 us at [4096, 128],
#            7 ps a slot
#   scatter  above, where the level is over that bound (no layout is
#            built: `fusedbatch.leaf_layout`) or the caller has a
#            `reduce` (the sharded twins get none): N * L mask entries
#            cost more than the walk, so scatter and gather: 2,084 us
#            at 131,072 rows and L = 4,096
MASK_FORM_MAX_L = 256
# a dense column is 4 * L * W bytes and a program holds some eight of
# them (four laid, the step's temporaries, x): 2^24 slots are 64 MiB a
# column, half a GiB a program, a thirtieth of one chip's memory.  The
# step is not what limits it: at the bound, [4096, 4096], it takes
# 180 us (11 ps a slot from HBM; 46 us at [4096, 1024]), an eleventh of
# one scatter-form step at 131,072 rows.  A tree with one giant leaf
# and thousands of tiny ones is over the bound and keeps the scatter.
DENSE_FORM_MAX_ENTRIES = 1 << 24


@jax.tree_util.register_pytree_node_class
class LeafLayout:
    """Where each row of a wide leaf level (a multi-level tree's, or the
    flat column of a one-preference spread) sits in the leaf-major
    dense layout ``[L, W]`` (row ``l`` holds the nodes of leaf ``l``):
    ``slot`` i32[N] = ``leaf * W + rank``, the rank a row's place among
    its leaf's rows in row order, and ``L * W`` (no slot: dropped on the
    way in, read as 0 on the way out) on the bucket's padding rows,
    which have no room and no tasks.  ``W`` is static, a power of two at
    or above the fullest leaf's population: it rides as the pytree's
    aux data, so a leaf that outgrows it is a new jit signature, as a
    node bucket that grows is.  Built by ``fusedbatch.leaf_layout`` for
    ``fusedbatch.tree_inputs`` (the tree) and ``fusedbatch.flat_leaf``
    (the flat column), kept by row in the resident tier
    (``ops/streaming.py``) and carried as the third element of
    ``hier``; a fused run of the dense form carries its groups' slot
    rows as one layout, ``slot`` i32[G, N] (``FusedGroups.leaf``)."""

    def __init__(self, slot, W: int):
        self.slot = slot
        self.W = W

    @property
    def nbytes(self) -> int:
        return self.slot.nbytes     # what a launch sends up

    def tree_flatten(self):
        return (self.slot,), self.W

    @classmethod
    def tree_unflatten(cls, W, children):
        return cls(children[0], W)

    def lay(self, col: jnp.ndarray, L: int) -> jnp.ndarray:
        """``col`` [N] in the layout, [L, W]; the slots no row fills
        read 0, which with ``cap = 0`` changes nothing (module
        docstring, "The searches" (f))."""
        flat = jnp.zeros(L * self.W, col.dtype)
        return flat.at[self.slot].set(col, mode="drop").reshape(L, self.W)

    def rows(self, dense: jnp.ndarray) -> jnp.ndarray:
        """Back to row order, [N] from [L, W]."""
        return dense.reshape(-1).at[self.slot].get(mode="fill",
                                                   fill_value=0)


def search_form(L: int, W: int = 0, reduce: Reduce = _identity) -> str:
    """The form a search over ``L`` segments takes (the table above);
    ``W`` the width of the caller's `LeafLayout`, 0 without one."""
    if L == 1:
        return "sum"
    if L <= MASK_FORM_MAX_L:
        return "mask"
    if 0 < L * W <= DENSE_FORM_MAX_ENTRIES and reduce is _identity:
        return "dense"
    return "scatter"


# In the primitives below ``seg is None`` says the columns are dense
# [L, W]; else they are [N] with ``seg`` i32[N] the row's segment.

def _seg_reduce(x: jnp.ndarray, seg: Optional[jnp.ndarray], L: int, *,
                over, scatter, fill) -> jnp.ndarray:
    """[L] reduction (``over``: jnp.sum / min / max) of x i32|f32 by
    segment; ``fill`` is the reduction's identity, ``scatter`` its
    jax.ops form."""
    if seg is None:
        return over(x, axis=1)
    if L == 1:
        return over(x).reshape(1)
    if L <= MASK_FORM_MAX_L:
        return over(jnp.where(_member(seg, L), x[:, None], fill), axis=0)
    return scatter(x, seg, num_segments=L)


def _seg_sum_f32(x: jnp.ndarray, seg: Optional[jnp.ndarray],
                 L: int) -> jnp.ndarray:
    """int32 segment sum carried in f32 so totals up to N*k (~2^42) cannot
    wrap.  Safe for comparisons against bounds <= K_CLAMP — see module
    docstring for the exactness argument, which holds in any order of
    summation."""
    return _seg_reduce(x.astype(jnp.float32), seg, L, over=jnp.sum,
                       scatter=jax.ops.segment_sum, fill=0.0)


def _of_row(v_seg: jnp.ndarray, seg: Optional[jnp.ndarray],
            L: int) -> jnp.ndarray:
    """Each row's entry of a per-segment vector ([N] from [L], or
    broadcastable to [L, W]); a row whose segment id is no segment
    reads 0 in the mask form."""
    if seg is None:
        return v_seg[:, None]
    if L == 1:
        return v_seg[0]
    if L <= MASK_FORM_MAX_L:
        # one entry of each row of the mask is set: the sum is exact
        return jnp.sum(jnp.where(_member(seg, L), v_seg[None, :], 0),
                       axis=1, dtype=v_seg.dtype)
    return v_seg[seg]


def _seg_total(fn: Callable, v_seg: jnp.ndarray, rows: Tuple,
               seg: Optional[jnp.ndarray], L: int) -> jnp.ndarray:
    """What a search step needs, f32[L]: per segment, the sum over its
    rows of ``fn(v, *rows)`` with v the segment's entry of ``v_seg`` —
    ``_seg_sum_f32(fn(_of_row(v_seg), *rows))``, in one pass over the
    mask in the mask form and over the layout in the dense one, so a
    step of either neither gathers nor scatters.  ``fn`` is elementwise
    and non-negative."""
    if seg is None or L == 1 or L > MASK_FORM_MAX_L:
        return _seg_sum_f32(fn(_of_row(v_seg, seg, L), *rows), seg, L)
    vals = fn(v_seg[None, :], *(r[:, None] for r in rows))
    return jnp.sum(jnp.where(_member(seg, L), vals, 0).astype(jnp.float32),
                   axis=0)


def _key_bracket(live: jnp.ndarray, key: jnp.ndarray,
                 seg: Optional[jnp.ndarray], L: int):
    """Per segment (has, min, max) of ``key`` over the ``live`` rows;
    min and max mean nothing where ``has`` is False."""
    lo = _seg_reduce(jnp.where(live, key, _I32_MAX), seg, L, over=jnp.min,
                     scatter=jax.ops.segment_min, fill=_I32_MAX)
    hi = _seg_reduce(jnp.where(live, key, _I32_MIN), seg, L, over=jnp.max,
                     scatter=jax.ops.segment_max, fill=_I32_MIN)
    return lo <= hi, lo, hi


def _bisect(reaches: Callable[[jnp.ndarray], jnp.ndarray],
            lo: jnp.ndarray, hi: jnp.ndarray):
    """Per segment, the least t in [lo, hi) with ``reaches(t)``
    (monotone in t), or hi where no such t has it.  Returns (t i32[L],
    steps i32 scalar).  The loop leaves when every segment has
    ``lo == hi`` — ceil(log2(hi - lo + 1)) steps of the widest segment
    — and a segment that got there does not move again."""
    def step(state):
        lo, hi, n = state
        mid = lo + (hi - lo) // 2   # avoids int32 overflow of lo + hi
        ge = reaches(mid)
        open_ = lo < hi
        return (jnp.where(open_ & ~ge, mid + 1, lo),
                jnp.where(open_ & ge, mid, hi), n + 1)

    _, hi, n = jax.lax.while_loop(
        lambda state: jnp.any(state[0] < state[1]), step,
        (lo, hi, jnp.zeros((), jnp.int32)))
    return hi, n


def waterfill_search(e: jnp.ndarray, cap: jnp.ndarray, tie: jnp.ndarray,
                     k_seg: jnp.ndarray, seg: Optional[jnp.ndarray], L: int,
                     reduce: Reduce = _identity):
    """``seg_waterfill`` with its two trip counts: (x, level steps, tie
    steps), x shaped as the columns are.  The counts are what the tests
    pin; the jitted programs return x alone and the compiler drops the
    counters."""
    e = e.astype(jnp.int32)
    cap = cap.astype(jnp.int32)
    k_seg = k_seg.astype(jnp.int32)
    kf = k_seg.astype(jnp.float32)
    from_data = reduce is _identity

    def fill(lam, e_rows, cap_rows):
        return jnp.clip(lam - e_rows, 0, cap_rows)

    # level bracket (module docstring, "The searches"): [min e, max e +
    # k] over the rows with room; closed at 0 where nothing is asked or
    # nothing has room
    want = k_seg > 0
    if from_data:
        has, e_min, e_max = _key_bracket(cap > 0, e, seg, L)
        want = want & has
        top = jnp.minimum(e_max, LEVEL_TOP - k_seg) + k_seg  # no wrap
        hi = jnp.where(want, top, 0)
        lo = jnp.where(want, jnp.clip(e_min, 0, hi), 0)
    else:
        hi = jnp.where(want, LEVEL_TOP, 0).astype(jnp.int32)
        lo = jnp.zeros_like(hi)
    # minimal λ with fill ≥ k (or the bracket's top if infeasible)
    lam, level_steps = _bisect(
        lambda mid: reduce(_seg_total(fill, mid, (e, cap), seg, L)) >= kf,
        lo, hi)

    lam_row = _of_row(lam, seg, L)
    x_base = fill(lam_row - 1, e, cap)
    f_base = reduce(_seg_sum_f32(x_base, seg, L))
    # remainder is exact: whenever r > 0, f_base < k <= K_CLAMP < 2^24
    r = jnp.maximum(kf - f_base, 0.0)

    marginal = (e <= lam_row - 1) & (x_base < cap)

    # threshold search: per segment, the r-th smallest tie key among
    # marginals — one of their keys, so their range is the bracket
    want = r > 0
    if from_data:
        has, t_min, t_max = _key_bracket(marginal, tie, seg, L)
        want = want & has
        tlo = jnp.where(want, t_min, 0)
        thi = jnp.where(want, t_max, 0)
    else:
        tlo = jnp.where(want, -1, 0).astype(jnp.int32)
        thi = jnp.where(want, KEY_TOP, 0).astype(jnp.int32)
    thr, tie_steps = _bisect(
        lambda mid: reduce(_seg_total(
            lambda t, m, tie: (m & (tie <= t)).astype(jnp.int32),
            mid, (marginal, tie), seg, L)) >= r,
        tlo, thi)
    grant = marginal & (tie <= _of_row(thr, seg, L)) \
        & (_of_row(r, seg, L) > 0)

    return x_base + grant.astype(jnp.int32), level_steps, tie_steps


def seg_waterfill(e: jnp.ndarray, cap: jnp.ndarray, tie: jnp.ndarray,
                  k_seg: jnp.ndarray, seg: Optional[jnp.ndarray], L: int,
                  reduce: Reduce = _identity) -> jnp.ndarray:
    """Capacity-bounded water-filling within each segment.

    Finds per-segment level λ, assigns x_i = clip(λ-1 - e_i, 0, cap_i), then
    grants the remainder one-by-one to marginal nodes in ``tie`` order.

    e:    i32[N] current level per element (lower = preferred)
    cap:  i32[N] max units this element can take (>= 0)
    tie:  i32[N] tie-break key in [0, 2^30), unique per element (lower =
          preferred)
    k_seg:i32[L] units to place per segment (each <= K_CLAMP)
    seg:  i32[N] segment id per element (all 0 where L == 1); None
          where e, cap and tie come in a `LeafLayout`'s dense [L, W],
          as x then does
    reduce: cross-shard sum for [L]-shaped partials (psum under shard_map)
    """
    return waterfill_search(e, cap, tie, k_seg, seg, L, reduce)[0]


def _hash_eq(node_hash: jnp.ndarray, exp: jnp.ndarray) -> jnp.ndarray:
    """node_hash: i32[2, N], exp: i32[2] -> bool[N]."""
    return (node_hash[0] == exp[0]) & (node_hash[1] == exp[1])


def feasibility_and_capacity(nodes: NodeInputs, group: GroupInputs,
                             reduce: Reduce = _identity):
    """Fused filter pipeline: mask[N], per-node capacity[N], and per-filter
    failure counts (for user-visible ``no suitable node (...)`` diagnostics,
    matching pipeline.go's short-circuit failure accounting).

    Mirrors filter.go's checklist; a False anywhere is a node the host
    pipeline would also reject (modulo documented waivers).
    """
    # --- individual filter masks
    ready_m = nodes.ready
    res_m = nodes.res_ok       # exact int64 comparison done host-side
    plugin_m = nodes.extra_mask

    def apply_constraint(i, m):
        eq = _hash_eq(group.con_hash[i], group.con_exp[i])
        op = group.con_op[i]
        ok = jnp.where(op == 0, eq, jnp.where(op == 1, ~eq, True))
        return m & ok

    con_m = jax.lax.fori_loop(0, group.con_op.shape[0], apply_constraint,
                              jnp.ones_like(ready_m))

    def apply_platform(i, acc):
        row = group.plat[i]
        used = row[0] != -1
        os_ok = ((row[0] == 0) & (row[1] == 0)) | (
            (nodes.os_hash[0] == row[0]) & (nodes.os_hash[1] == row[1]))
        arch_ok = ((row[2] == 0) & (row[3] == 0)) | (
            (nodes.arch_hash[0] == row[2]) & (nodes.arch_hash[1] == row[3]))
        matched, any_used = acc
        return matched | (used & os_ok & arch_ok), any_used | used

    matched, any_used = jax.lax.fori_loop(
        0, group.plat.shape[0], apply_platform,
        (jnp.zeros_like(ready_m), jnp.zeros((), jnp.bool_)))
    plat_m = matched | ~any_used

    port_m = ~(group.port_limited & nodes.port_conflict)
    rep_m = (group.maxrep == 0) | (nodes.svc_tasks < group.maxrep)
    # tenant-quota mask column: last in the checklist, mirroring the
    # host pipeline's QuotaFilter position so short-circuit failure
    # counts (and therefore explanations) agree between the paths
    quota_m = nodes.quota_ok if nodes.quota_ok is not None \
        else jnp.ones_like(ready_m)

    # --- short-circuit failure counts in pipeline order (pipeline.go:10-20)
    prior = nodes.valid
    fail_counts = []
    mask = prior
    for m in (ready_m, res_m, plugin_m, con_m, plat_m, port_m, rep_m,
              quota_m):
        fails = mask & ~m
        fail_counts.append(jnp.sum(fails.astype(jnp.int32)))
        mask = mask & m
    fail_counts = reduce(jnp.stack(fail_counts))

    # capacity: how many tasks of this group each node can absorb
    cap = jnp.minimum(nodes.res_cap, jnp.minimum(group.k, K_CLAMP))
    cap = jnp.where(group.maxrep > 0,
                    jnp.minimum(cap, jnp.maximum(
                        group.maxrep - nodes.svc_tasks, 0)), cap)
    cap = jnp.where(group.port_limited, jnp.minimum(cap, 1), cap)
    cap = jnp.where(mask, jnp.maximum(cap, 0), 0)
    return mask, cap, fail_counts


def plan_group(nodes: NodeInputs, group: GroupInputs, L: int,
               reduce: Reduce = _identity,
               idx_offset: Optional[jnp.ndarray] = None,
               hier: Tuple = ()) -> jnp.ndarray:
    """Place a task group: returns x i32[N] = tasks assigned per node.

    Multi-stage hierarchical water-fill (reference semantics:
    scheduleNTasksOnSubtree equalizes branch totals level by level,
    scheduleNTasksOnNodes levels per-service counts):

      stage A: walk the spread-preference tree top-down; at each level the
               parent's allocation is water-filled over its child branches
               (loads = branch service-task totals, capacity = branch
               feasible capacity).  ``hier`` carries the upper levels as
               (seg_nodes i32[N], parent i32[L_d]) pairs, top level first;
               ``nodes.leaf`` is the deepest level with L segments.
      stage B: nodes within each leaf — level per-service counts
               (failure-down-weighted), tie-broken by total tasks.

    Where ``hier`` brings the leaf level's `LeafLayout` and
    ``search_form`` says "dense", what the leaf level reads (e, cap, the
    tie key, the valid rows' service tasks) is laid out once, the leaf
    level's branch sums and stage B run on the layout, and x is read
    back to row order: five walks of the rows a program, none a step.

    Returns (x i32[N] tasks per node, fail_counts i32[7] per-filter
    failure counts in pipeline order, spill bool scalar — True when a
    spread branch saturated and the caller should use the host path for
    exact reference parity).
    """
    mask, cap, fail_counts = feasibility_and_capacity(nodes, group, reduce)
    n = nodes.ready.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    if idx_offset is not None:
        idx = idx + idx_offset

    svc = jnp.clip(nodes.svc_tasks, 0, SVC_CLAMP)
    # The waterfill needs a true per-node e.  broadcast_to is a no-op for
    # today's full-width inputs; it future-proofs against callers shipping
    # broadcastable length-1 stand-ins for no-signal arrays (tried for H2D
    # savings and currently off — see the recompile trade-off note in
    # planner._build_device_inputs before re-enabling).
    e = jnp.broadcast_to(spread_score(nodes),
                         nodes.ready.shape).astype(jnp.int32)

    # ---- stage A: allocation down the branch hierarchy
    # branch load counts every valid node's service tasks (feasible or not),
    # matching nodeset.go:88-105 where tree.tasks accumulates per walked
    # node.  Sums ride f32 (overflow-safe, see docstring) and are clamped
    # back into the int32 search ranges: loads above LOAD_CLAMP are
    # equi-preferred, caps above k are equivalent to k.
    kk = jnp.minimum(group.k, K_CLAMP)
    svc_valid = jnp.where(nodes.valid, svc, 0)
    tie = (jnp.clip(nodes.total_tasks, 0, TOTAL_CLAMP) << IDX_BITS) | idx

    def branch_arrays(seg, n_segs, svc_valid=svc_valid, cap=cap):
        load = jnp.minimum(
            reduce(_seg_sum_f32(svc_valid, seg, n_segs)),
            float(LOAD_CLAMP)).astype(jnp.int32)
        raw_cap = reduce(_seg_sum_f32(cap, seg, n_segs))  # true capacity
        bcap = jnp.minimum(raw_cap,
                           kk.astype(jnp.float32)).astype(jnp.int32)
        return load, bcap, raw_cap

    # hier = (upper_levels, leaf_parent[, layout]):
    #   upper_levels — tuple of (seg_nodes i32[N], parent i32[L_d]) pairs,
    #   top level first, for every level ABOVE the leaves;
    #   leaf_parent  — i32[L] mapping each leaf to its upper-level branch
    #   (None under one preference: the root is every leaf's parent);
    #   layout       — the leaf level's `LeafLayout`, of a wide level only
    #   (a one-preference spread's hier is ``((), None, layout)``).
    upper_levels, leaf_parent, *layout = hier if hier else ((), None)
    dense = bool(layout) and search_form(L, layout[0].W, reduce) == "dense"
    # what the leaf level reads, by rows under nodes.leaf or laid dense
    leaf_seg, leaf_cols = nodes.leaf, (svc_valid, cap, e, tie)
    if dense:
        (layout,) = layout
        leaf_seg = None
        leaf_cols = tuple(layout.lay(col, L) for col in leaf_cols)
    svc_leaf, cap_leaf, e_leaf, tie_leaf = leaf_cols

    k_parent = kk.reshape(1)   # the root's allocation
    parent_count = 1
    # branch-capacity binding detector: when a spread branch saturates
    # (allocation == capacity with capacity > 0 at a multi-branch level),
    # the host oracle's convergence loop (scheduler.py:738, mirroring
    # reference scheduler.go:772) redistributes with STALE branch counts
    # and order-biased remainders, producing lumpier distributions than
    # this water-fill's globally-even answer.  Rather than replicate that
    # sequential quirk on device, flag it: the planner routes flagged
    # groups to the host path, preserving exact reference parity.
    spill = jnp.zeros((), jnp.bool_)

    def level_spill(alloc, raw_cap):
        # a level diverges from the host only when SOME usable branch
        # truly saturates (allocation == its UNclamped capacity) while
        # ANOTHER usable branch does not — that is when the host loop's
        # stale-count redistribution kicks in.  Compare against the raw
        # capacity, not the k-clamped bcap: a lone branch absorbing the
        # whole group, or a fully saturated level (host and device agree
        # there), must not flag.
        af = alloc.astype(jnp.float32)
        usable = raw_cap > 0
        sat = usable & (af >= raw_cap)
        return jnp.any(sat) & jnp.any(usable & ~sat)

    for seg_nodes, parent in upper_levels:
        L_d = parent.shape[0]
        load, bcap, raw_cap = branch_arrays(seg_nodes, L_d)
        # stage-A waterfills run on [L_d]-shaped, fully-replicated arrays
        # (the reduce already happened in branch_arrays), so no cross-shard
        # reduce is needed even under shard_map
        k_parent = seg_waterfill(
            e=load, cap=bcap, tie=jnp.arange(L_d, dtype=jnp.int32),
            k_seg=k_parent, seg=parent, L=parent_count)
        if L_d > 1:
            spill = spill | level_spill(k_parent, raw_cap)
        parent_count = L_d

    if L == 1 and not upper_levels:
        _, branch_cap, _raw = branch_arrays(nodes.leaf, 1)
        k_branch = jnp.minimum(kk, branch_cap)
    else:
        load, bcap, raw_cap = branch_arrays(leaf_seg, L, svc_leaf, cap_leaf)
        seg = leaf_parent if leaf_parent is not None \
            else jnp.zeros((L,), jnp.int32)
        k_branch = seg_waterfill(
            e=load, cap=bcap, tie=jnp.arange(L, dtype=jnp.int32),
            k_seg=k_parent, seg=seg, L=parent_count)
        if L > 1:
            spill = spill | level_spill(k_branch, raw_cap)

    # ---- stage B: nodes within each leaf branch
    x = seg_waterfill(e=e_leaf, cap=cap_leaf, tie=tie_leaf, k_seg=k_branch,
                      seg=leaf_seg, L=L, reduce=reduce)
    if dense:
        x = layout.rows(x)
    return x, fail_counts, spill


@functools.partial(jax.jit, static_argnames=("L",))
def plan_group_jit(nodes: NodeInputs, group: GroupInputs, L: int,
                   hier: Tuple = ()) -> jnp.ndarray:
    return plan_group(nodes, group, L, hier=hier)


# ------------------------------------------------------- strategy seam
#
# The scoring stage is pluggable (scheduler/strategy.py registry):
# every strategy shares the SAME feasibility masks, bucket ladder and
# placement primitives (seg_waterfill / seg_packfill below); only the
# per-node score column differs.  Spread keeps riding plan_group /
# plan_fused untouched (its score is `spread_score` — the factored
# pre-seam computation, byte-identical by construction); the
# alternative strategies run through `plan_strategy_jit`, a separate
# jitted entry so spread's jit signatures cannot change.  Each device
# strategy's host oracle lives in scheduler/strategy.py: identical
# integer columns, identical integer formulas, bit-equal placements —
# the planner's breaker can demote any strategy group to the host
# oracle mid-tick without moving a single task.

def _downweight(failures: jnp.ndarray) -> jnp.ndarray:
    return jnp.where(failures >= MAX_FAILURES,
                     jnp.clip(failures, 0, FAILURE_CLAMP), 0)


def spread_score(nodes: NodeInputs) -> jnp.ndarray:
    """The spread strategy's effective level: per-service count,
    failure-down-weighted (scheduler.go:708 nodeLess) — exactly the
    pre-seam inline computation, now the seam's default scorer."""
    svc = jnp.clip(nodes.svc_tasks, 0, SVC_CLAMP)
    return svc + _downweight(nodes.failures) * F_BIG


class StrategyInputs(NamedTuple):
    """Per-group strategy columns/parameters, densified host-side
    (exact int64 headroom divisions, mirrored by the host oracle).
    Unused members ship as zeros — the static ``strategy`` argument
    already separates jit signatures, so no Optional-field games."""

    hr_cpu: jnp.ndarray   # i32[N] cpu headroom in demand units
    hr_mem: jnp.ndarray   # i32[N] memory headroom in demand units
    hr_gen: jnp.ndarray   # i32[N] generic-resource headroom (min kind)
    weights: jnp.ndarray  # i32[4] weighted terms [spread,cpu,mem,gen]
    w1: jnp.ndarray       # i32[F, H] learned-scorer layer 1
    b1: jnp.ndarray       # i32[H]
    w2: jnp.ndarray       # i32[H]
    b2: jnp.ndarray       # i32[] scalar


def packfill_search(key: jnp.ndarray, cap: jnp.ndarray,
                    k_seg: jnp.ndarray, seg: Optional[jnp.ndarray], L: int,
                    reduce: Reduce = _identity):
    """``seg_packfill`` with its trip count: (x, steps); dense columns
    and ``seg`` None as in ``seg_waterfill``."""
    cap = cap.astype(jnp.int32)
    k_seg = k_seg.astype(jnp.int32)
    kf = k_seg.astype(jnp.float32)

    # the threshold is the key of a row with room, or one past the last
    # of them where the segment cannot hold k; closed at -1, under every
    # key, where nothing is asked or nothing has room
    want = k_seg > 0
    if reduce is _identity:
        has, k_min, k_max = _key_bracket(cap > 0, key, seg, L)
        want = want & has
        lo = jnp.where(want, k_min, -1)
        hi = jnp.where(want, k_max + 1, -1)
    else:
        lo = jnp.full_like(k_seg, -1)
        hi = jnp.where(want, KEY_TOP, -1).astype(jnp.int32)
    # minimal key threshold with fill >= k (the bracket's top: infeasible)
    thr, steps = _bisect(
        lambda mid: reduce(_seg_total(
            lambda t, key, cap: jnp.where(key <= t, cap, 0),
            mid, (key, cap), seg, L)) >= kf,
        lo, hi)

    thr_row = _of_row(thr, seg, L)
    x = jnp.where(key < thr_row, cap, 0)
    f = reduce(_seg_sum_f32(x, seg, L))
    # remainder is exact: whenever r > 0, f < k <= K_CLAMP < 2^24
    r_row = _of_row(jnp.maximum(kf - f, 0.0), seg, L)
    # keys are unique, so at most one element per segment sits AT the
    # threshold; by minimality of thr its capacity covers r
    grant = (key == thr_row) & (r_row > 0.0)
    return x + jnp.where(grant, jnp.minimum(
        cap, r_row.astype(jnp.int32)), 0), steps


def seg_packfill(key: jnp.ndarray, cap: jnp.ndarray,
                 k_seg: jnp.ndarray, seg: Optional[jnp.ndarray], L: int,
                 reduce: Reduce = _identity) -> jnp.ndarray:
    """Sequential (pack) fill within each segment: nodes take their
    full capacity in ascending ``key`` order until k is placed — the
    binpack placement primitive.  Keys lie in [0, 2^30) and must be
    unique per segment (callers pack the node index into the low
    bits).  Same threshold search as seg_waterfill's tie stage, so it
    runs under shard_map with the identical ``reduce`` contract."""
    return packfill_search(key, cap, k_seg, seg, L, reduce)[0]


def _learned_score(nodes: NodeInputs, sin: StrategyInputs
                   ) -> jnp.ndarray:
    """Fixed-point MLP score — the device twin of
    scheduler/strategy.learned_score_host (identical int32 ops)."""
    f = jnp.stack([
        jnp.clip(nodes.svc_tasks, 0, FEAT_CLAMP),
        jnp.clip(nodes.total_tasks, 0, FEAT_CLAMP),
        jnp.clip(nodes.failures, 0, FEAT_CLAMP),
        jnp.clip(sin.hr_cpu, 0, FEAT_CLAMP),
        jnp.clip(sin.hr_mem, 0, FEAT_CLAMP),
        nodes.ready.astype(jnp.int32) * FEAT_CLAMP,
    ], axis=-1).astype(jnp.int32)                       # [N, F]
    # explicit multiply-add contractions (not jnp.dot): integer, exact,
    # and XLA maps the broadcast+reduce well on TPU
    h = jnp.sum(f[:, :, None] * sin.w1[None, :, :], axis=1) + sin.b1
    h = jnp.clip(jnp.right_shift(h, MLP_SHIFT), 0, FEAT_CLAMP)
    out = jnp.sum(h * sin.w2[None, :], axis=1) + sin.b2
    return jnp.clip(jnp.right_shift(out, MLP_SHIFT), 0, SCORE_CLAMP)


def strategy_score(nodes: NodeInputs, sin: StrategyInputs,
                   strategy: int) -> jnp.ndarray:
    """The pluggable scoring stage: per-node effective level (lower =
    preferred) for the waterfill strategies.  Formulas mirror
    scheduler/strategy.py's numpy oracles term for term."""
    if strategy == STRAT_WEIGHTED:
        w = sin.weights
        return (w[0] * jnp.clip(nodes.svc_tasks, 0, SVC_CLAMP)
                + w[1] * (HR_CLAMP - sin.hr_cpu)
                + w[2] * (HR_CLAMP - sin.hr_mem)
                + w[3] * (HR_CLAMP - sin.hr_gen)
                + _downweight(nodes.failures) * F_BIG)
    if strategy == STRAT_LEARNED:
        return (_learned_score(nodes, sin)
                + _downweight(nodes.failures) * F_BIG)
    return spread_score(nodes)


def plan_strategy(nodes: NodeInputs, group: GroupInputs,
                  sin: StrategyInputs, strategy: int,
                  reduce: Reduce = _identity,
                  idx_offset: Optional[jnp.ndarray] = None):
    """Place one task group under a non-spread strategy.  Shares the
    fused feasibility/capacity stage (and therefore the fail-count
    diagnostics) with plan_group; strategies ignore spread-preference
    trees (the strategy owns the scoring stage), so placement is one
    flat segment.  Returns the same (x, fail_counts, spill) triple as
    plan_group — spill is constantly False (no spread branches to
    saturate), so the planner's fetch path is shared unchanged."""
    mask, cap, fail_counts = feasibility_and_capacity(nodes, group,
                                                      reduce)
    n = nodes.ready.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    if idx_offset is not None:
        idx = idx + idx_offset
    seg = jnp.zeros(n, jnp.int32)
    kk = jnp.minimum(group.k, K_CLAMP).reshape(1)
    if strategy == STRAT_BINPACK:
        score = jnp.where(
            nodes.failures >= MAX_FAILURES,
            BP_CLAMP + 1 + jnp.clip(nodes.failures, 0, FAILURE_CLAMP),
            jnp.clip(nodes.res_cap, 0, BP_CLAMP))
        key = (score << IDX_BITS) | idx
        x = seg_packfill(key, cap, kk, seg, 1, reduce=reduce)
    else:
        e = jnp.broadcast_to(
            strategy_score(nodes, sin, strategy),
            nodes.ready.shape).astype(jnp.int32)
        tie = (jnp.clip(nodes.total_tasks, 0, TOTAL_CLAMP)
               << IDX_BITS) | idx
        x = seg_waterfill(e=e, cap=cap, tie=tie, k_seg=kk, seg=seg,
                          L=1, reduce=reduce)
    return x, fail_counts, jnp.zeros((), jnp.bool_)


@functools.partial(jax.jit, static_argnames=("strategy",))
def plan_strategy_jit(nodes: NodeInputs, group: GroupInputs,
                      sin: StrategyInputs, strategy: int):
    return plan_strategy(nodes, group, sin, strategy)


# ------------------------------------------------------- fused many-service
#
# One program for the WHOLE tick: every pending (service, spec-version)
# group is packed into shared static buckets (group slots G, constraint
# slots Cc, platform slots P, spread-leaf slots L, service slots S) and
# planned by a single XLA dispatch.  The groups are not independent — a
# group's placements feed the next group's per-service counts, total
# loads and remaining resources — so the fused program is a
# `lax.scan` over group slots carrying the cluster state (FusedCarry),
# which makes the sequential per-service semantics exact by
# construction: scan step g computes precisely what a standalone
# `plan_group` dispatch would see after groups 0..g-1 applied.
#
# Segment masking: each scan step scores ONLY its own group's inputs
# (constraints, spread leaves, failure down-weights are per group-slot
# rows; per-service counts live in `svc_acc[slot]` segments), so two
# groups in one batch can never cross-contaminate each other's
# feasibility or spread scoring — asserted by tests/test_fused.py.
#
# Resource accounting rides int64 (the host densifier's exact integer
# comparisons, see module docstring): callers trace/dispatch under
# `jax.enable_x64` (ops/fusedbatch.py x64()) so avail//demand
# floor-divisions match numpy bit-for-bit.

class FusedShared(NamedTuple):
    """Run-wide node state, densified once per fused run."""

    valid: jnp.ndarray        # bool[N] padding mask
    ready: jnp.ndarray        # bool[N] READY && ACTIVE
    os_hash: jnp.ndarray      # i32[2, N] platform.os hash (hi, lo)
    arch_hash: jnp.ndarray    # i32[2, N] normalized arch hash (hi, lo)
    svc0: jnp.ndarray         # i32[S, N] base active tasks per service slot


class FusedGroups(NamedTuple):
    """Per-group inputs, stacked over the group axis G (scan xs).
    Padded slots carry k=0 (they place nothing and leave the carry
    untouched)."""

    k: jnp.ndarray            # i32[G] tasks to place (0 = padding slot)
    slot: jnp.ndarray         # i32[G] service slot into svc0/svc_acc
    maxrep: jnp.ndarray       # i32[G] max replicas per node (0 = off)
    cpu_d: jnp.ndarray        # i64[G] per-task nano-cpu reservation
    mem_d: jnp.ndarray        # i64[G] per-task memory reservation
    con_hash: jnp.ndarray     # i32[G, Cc, 2, N]
    con_op: jnp.ndarray       # i32[G, Cc] 0 ==, 1 !=, 2 disabled
    con_exp: jnp.ndarray      # i32[G, Cc, 2]
    plat: jnp.ndarray         # i32[G, P, 4] (-1 row sentinel = unused)
    failures: jnp.ndarray     # i32[G, N] recent failures for the group
    leaf: jnp.ndarray         # i32[G, N] spread leaf id (0 when no prefs);
    #                           in a run of the dense form (`plan_fused`)
    #                           the groups' `LeafLayout` in its place,
    #                           slot i32[G, N] under the run's one W
    extra_mask: jnp.ndarray   # bool[G, N] plugin/volume masks
    # tenant-quota mask rows: all-False rows for groups whose tenant
    # was exhausted at admission; None when no group in the run is
    # quota-blocked (signature stability for quota-free workloads)
    quota_ok: Optional[jnp.ndarray] = None   # bool[G, N] or None
    # a run of the dense form only (None in every other, whose
    # signatures it leaves alone): the group has no preference of its
    # own, so no row of the layout, and runs the L == 1 program
    flat: Optional[jnp.ndarray] = None       # bool[G] or None


class FusedCarry(NamedTuple):
    """Cluster state threaded through the scan — and, across chunked
    dispatches of one run, kept device-resident between calls (the
    planner never fetches it; chunk i+1 consumes chunk i's carry as
    device arrays)."""

    total: jnp.ndarray        # i32[N] active tasks total
    cpu: jnp.ndarray          # i64[N] available nano-cpus
    mem: jnp.ndarray          # i64[N] available memory bytes
    svc_acc: jnp.ndarray      # i32[S, N] tasks placed per service slot
    #                           within this fused run


class FusedStrategy(NamedTuple):
    """Per-group strategy columns for a mixed-strategy fused run.
    ``sid``/``weights`` ride the scan xs next to FusedGroups; the
    learned-scorer parameters are run-wide and stay outside the scan
    (closed over).  Spread-only runs ship ``strat=None`` — the
    pre-strategy jit signatures, untouched."""

    sid: jnp.ndarray          # i32[G] strategy id (0 = spread)
    weights: jnp.ndarray      # i32[G, 4] weighted terms per group
    w1: jnp.ndarray           # i32[F, H] learned-scorer layer 1
    b1: jnp.ndarray           # i32[H]
    w2: jnp.ndarray           # i32[H]
    b2: jnp.ndarray           # i32[] scalar


def _fused_headroom(avail: jnp.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    """In-scan headroom column in demand units: the exact int64 floor
    division planner._build_strategy_inputs applies host-side (callers
    dispatch under enable_x64), so a fused strategy group scores the
    same headrooms a per-group dispatch would densify after the
    preceding groups applied."""
    hr = jnp.clip(avail // jnp.maximum(d, 1), 0, HR_CLAMP)
    return jnp.where(d > 0, hr, HR_CLAMP).astype(jnp.int32)


def plan_fused(shared: FusedShared, groups: FusedGroups,
               carry: FusedCarry, L: int, reduce: Reduce = _identity,
               idx_offset: Optional[jnp.ndarray] = None,
               strat: Optional[FusedStrategy] = None):
    """Plan a fused batch of task groups in one program.

    Returns (x i32[G, N] tasks per node per group, fail_counts
    i32[G, 7], spill bool[G], carry' FusedCarry).  Placements are
    byte-identical to dispatching `plan_group` per group in order and
    applying each result before densifying the next — the scan carry
    IS that apply, restricted to the signals the kernel reads.

    ``strat`` (mixed-strategy runs): per-group strategy ids select the
    scoring stage in-scan via lax.switch over the four static-strategy
    programs — binpack/weighted/learned groups fuse alongside spread
    ones instead of breaking the run.  Headroom columns are computed
    from the carry (the same int64 divisions the host densifier runs),
    and hr_gen is the neutral HR_CLAMP because groups demanding
    generic resources never fuse (probe_group rejects them).

    The run's form.  ``L`` is static and shared: the widest leaf bucket
    of the run's preferences, and up to MASK_FORM_MAX_L every spread
    group of the scan searches under it by rows (``groups.leaf`` the
    leaf ids, all 0 for a group without a preference: one usable leaf).
    Above, ``fusedbatch.build_run`` reads the choice off its input:
    where every preference group's column has a `LeafLayout` and the
    run's widest ``W`` keeps ``L * W`` within DENSE_FORM_MAX_ENTRIES,
    ``groups.leaf`` is that layout (the groups' slot rows in place of
    their leaf rows, relaid to the one ``W``) and a step hands
    ``plan_group`` its group's row of it: the dense form.  A spread
    group without a preference has no row in any ``[L, W]`` layout
    (``groups.flat``); a ``lax.cond`` runs the ``L == 1`` program for
    it, which places what the shared-``L`` program places on one usable
    leaf (``k_branch = min(k, cap)``, no spill) at the flat search's
    price.  Else (no layout, a run over the bound, a caller with a
    ``reduce``) the run keeps the scatter form, row for row as before."""
    no_ports = jnp.zeros_like(shared.valid)
    dense = isinstance(groups.leaf, LeafLayout)
    if dense:
        assert search_form(L, groups.leaf.W, reduce) == "dense", \
            (L, groups.leaf.W)
        # the dense form reads the layout and the L == 1 program no
        # leaf at all
        no_leaf = jnp.zeros(shared.valid.shape, jnp.int32)

    def step(state: FusedCarry, xs):
        if strat is None:
            g = xs
        else:
            g, g_sid, g_weights = xs
        # exact int64 resource math, matching the host densifier:
        # res_ok &= avail >= demand and cap = min(cap, avail // demand)
        # for each demanded resource, then clip to [0, K_CLAMP] in i32
        res_ok = shared.valid
        cap = jnp.full(state.cpu.shape, K_CLAMP, state.cpu.dtype)
        for avail, d in ((state.cpu, g.cpu_d), (state.mem, g.mem_d)):
            have = d > 0
            res_ok = res_ok & (~have | (avail >= d))
            cap = jnp.where(
                have, jnp.minimum(cap, avail // jnp.maximum(d, 1)), cap)
        res_cap = jnp.clip(cap, 0, K_CLAMP).astype(jnp.int32)
        svc = shared.svc0[g.slot] + state.svc_acc[g.slot]
        nodes = NodeInputs(
            valid=shared.valid, ready=shared.ready, res_ok=res_ok,
            res_cap=res_cap, svc_tasks=svc, total_tasks=state.total,
            failures=g.failures, leaf=no_leaf if dense else g.leaf,
            os_hash=shared.os_hash, arch_hash=shared.arch_hash,
            port_conflict=no_ports, extra_mask=g.extra_mask,
            quota_ok=g.quota_ok if groups.quota_ok is not None else None)
        grp = GroupInputs(
            k=g.k, con_hash=g.con_hash, con_op=g.con_op,
            con_exp=g.con_exp, plat=g.plat, maxrep=g.maxrep,
            port_limited=jnp.zeros((), jnp.bool_))

        def _spread():
            if not dense:
                return plan_group(nodes, grp, L, reduce=reduce,
                                  idx_offset=idx_offset)
            return jax.lax.cond(
                g.flat,
                lambda: plan_group(nodes, grp, 1, reduce=reduce,
                                   idx_offset=idx_offset),
                lambda: plan_group(nodes, grp, L, reduce=reduce,
                                   idx_offset=idx_offset,
                                   hier=((), None, g.leaf)))

        if strat is None:
            x, fail_counts, spill = _spread()
        else:
            sin = StrategyInputs(
                hr_cpu=_fused_headroom(state.cpu, g.cpu_d),
                hr_mem=_fused_headroom(state.mem, g.mem_d),
                hr_gen=jnp.full(res_cap.shape, HR_CLAMP, jnp.int32),
                weights=g_weights, w1=strat.w1, b1=strat.b1,
                w2=strat.w2, b2=strat.b2)

            def _strategy(sid_static):
                return plan_strategy(nodes, grp, sin, sid_static,
                                     reduce=reduce,
                                     idx_offset=idx_offset)

            x, fail_counts, spill = jax.lax.switch(
                jnp.clip(g_sid, 0, 3),
                [_spread,
                 lambda: _strategy(STRAT_BINPACK),
                 lambda: _strategy(STRAT_WEIGHTED),
                 lambda: _strategy(STRAT_LEARNED)])
        nxt = FusedCarry(
            total=state.total + x,
            cpu=state.cpu - x.astype(state.cpu.dtype) * g.cpu_d,
            mem=state.mem - x.astype(state.mem.dtype) * g.mem_d,
            svc_acc=state.svc_acc.at[g.slot].add(x))
        return nxt, (x, fail_counts, spill)

    xs_in = groups if strat is None \
        else (groups, strat.sid, strat.weights)
    carry_out, (xs, fcs, spills) = jax.lax.scan(step, carry, xs_in)
    return xs, fcs, spills, carry_out


@functools.partial(jax.jit, static_argnames=("L",))
def plan_fused_jit(shared: FusedShared, groups: FusedGroups,
                   carry: FusedCarry, L: int,
                   strat: Optional[FusedStrategy] = None):
    return plan_fused(shared, groups, carry, L, strat=strat)


# --------------------------------------------------------- pipeline stages
#
# The jitted entry above is ASYNC-DISPATCHED: calling it (stage 1)
# enqueues the XLA program and returns device arrays immediately; the
# host blocks only when it reads their values.  The pipelined scheduler
# exploits exactly this split — dispatch group i+1's plan (any plan_fn
# with plan_group_jit's signature, incl. the mesh-sharded one), run
# group i's host commit while the device computes, then fetch — with the
# two stages wrapped in the ``plan.dispatch`` / ``plan.d2h`` spans the
# overlap metrics are built from (ops/planner.py dispatch_group /
# fetch_group).

def fetch_plan(arrays):
    """Stage 2: one blocking D2H round-trip for a dispatched plan's
    outputs.  Fetch everything in one call — each fetch is a host sync.
    Works for single-device and mesh-sharded (shard_map) outputs
    alike.

    This is THE accounted D2H seam: every fetched byte lands in the
    device-telemetry transfer ledger (host-side nbytes of the numpy
    results — no device introspection, so accounting cannot change
    placements)."""
    out = jax.device_get(arrays)
    from ..obs import devicetelemetry as _devtel
    _devtel.note_d2h("fetch", _devtel.tree_nbytes(out))
    return out


@jax.jit
def feasibility_jit(nodes: NodeInputs, group: GroupInputs):
    """Mask + capacity only — validates preassigned (global-service)
    tasks against their fixed nodes in one fused call instead of a
    per-task host filter walk (reference: scheduler.go:646
    taskFitNode runs the same pipeline the planner does)."""
    mask, cap, fail_counts = feasibility_and_capacity(
        nodes, group, lambda v: v)
    return mask, cap, fail_counts


# ----------------------------------------------------------- gang admission
#
# Gang scheduling (scheduler/gang.py) needs ONE device answer per gang:
# can the cluster absorb all k members simultaneously?  That is the
# fused filter pipeline's capacity column reduced to a single
# comparison — sum(cap) >= k — so the kernel reuses
# feasibility_and_capacity verbatim and inherits its numeric contract:
# per-node cap <= K_CLAMP, and the f32 total is exact below 2^24 while
# anything above keeps enough relative accuracy to stay far beyond
# K_CLAMP, so the comparison is always decided correctly (see module
# docstring).

def gang_fit(nodes: NodeInputs, group: GroupInputs,
             reduce: Reduce = _identity):
    """All-members-feasible reduction: (fit bool scalar, fail_counts
    i32[8]).  ``fit`` is True iff the summed per-node capacity covers
    the whole gang; the per-filter failure counts feed the same
    ``no suitable node (...)`` deferral diagnostics the plan path
    emits."""
    mask, cap, fail_counts = feasibility_and_capacity(nodes, group, reduce)
    total = reduce(jnp.sum(cap.astype(jnp.float32)))
    kf = jnp.minimum(group.k, K_CLAMP).astype(jnp.float32)
    return total >= kf, fail_counts


@jax.jit
def gang_fit_jit(nodes: NodeInputs, group: GroupInputs):
    return gang_fit(nodes, group, lambda v: v)


@jax.jit
def gang_fit_fused_jit(nodes: NodeInputs, groups: GroupInputs):
    """Fused gang route: every array in ``nodes``/``groups`` carries a
    leading gang axis G (host-side stack of the same per-gang
    densifications the per-gang route uses; ``quota_ok`` must be
    stacked for all gangs or None for all).  Each gang is judged
    against the same base cluster state — atomic admission re-walks
    gangs in deterministic order and re-validates in the commit
    transaction, so the precheck is deliberately independent per
    gang."""
    return jax.vmap(lambda n, g: gang_fit(n, g, lambda v: v))(
        nodes, groups)
