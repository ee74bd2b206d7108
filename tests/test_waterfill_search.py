"""The water-fill's and the pack-fill's searches (ops/kernel.py): the
brackets come from the data and the loops leave on convergence, and the
placements are those of the fixed 34-step searches they replaced, bit
for bit.  That older form is kept here verbatim as the reference; the
host mirrors (scheduler/strategy.py) are the second one.  CPU: counts
and equality, no speed."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from swarmkit_tpu.ops import fusedbatch
from swarmkit_tpu.ops import kernel as kernel_mod
from swarmkit_tpu.ops.kernel import (
    F_BIG, FAILURE_CLAMP, IDX_BITS, K_CLAMP, SEARCH_STEPS_MAX, TOTAL_CLAMP,
    packfill_search, seg_packfill, seg_waterfill, waterfill_search,
)
from swarmkit_tpu.scheduler import strategy as strategy_mod

# ------------------------------------------------- the 34-step reference
#
# ops/kernel.py seg_waterfill / seg_packfill as they stood before the
# searches took their steps from the data, line for line.

LEVEL_ITERS = 34
TIE_ITERS = 34


def _identity(x):
    return x


def _seg_sum_f32(x, seg, L):
    return jax.ops.segment_sum(x.astype(jnp.float32), seg, num_segments=L)


def ref_waterfill(e, cap, tie, k_seg, seg, L, reduce=_identity):
    e = e.astype(jnp.int32)
    cap = cap.astype(jnp.int32)
    kf = k_seg.astype(jnp.float32)

    def fill_at(lam_seg):
        return jnp.clip(lam_seg[seg] - e, 0, cap)

    def level_body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2   # avoids int32 overflow of lo + hi
        f = reduce(_seg_sum_f32(fill_at(mid), seg, L))
        ge = f >= kf
        return jnp.where(ge, lo, mid + 1), jnp.where(ge, mid, hi)

    lo = jnp.zeros((L,), jnp.int32)
    hi = jnp.full((L,), 1 << 30, jnp.int32)
    lo, hi = jax.lax.fori_loop(0, LEVEL_ITERS, level_body, (lo, hi))
    lam = hi  # minimal λ with fill ≥ k (or 2^30 if capacity-infeasible)

    x_base = fill_at(lam - 1)
    f_base = reduce(_seg_sum_f32(x_base, seg, L))
    r = jnp.maximum(kf - f_base, 0.0)

    marginal = (e <= lam[seg] - 1) & (x_base < cap)

    def tie_body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2   # avoids int32 overflow of lo + hi
        cnt = reduce(_seg_sum_f32(
            (marginal & (tie <= mid[seg])).astype(jnp.int32), seg, L))
        ge = cnt >= r
        return jnp.where(ge, lo, mid + 1), jnp.where(ge, mid, hi)

    tlo = jnp.full((L,), -1, jnp.int32)
    thi = jnp.full((L,), 1 << 30, jnp.int32)  # tie keys are < 2^30
    tlo, thi = jax.lax.fori_loop(0, TIE_ITERS, tie_body, (tlo, thi))
    grant = marginal & (tie <= thi[seg]) & (r[seg] > 0)

    return x_base + grant.astype(jnp.int32)


def ref_packfill(key, cap, k_seg, seg, L, reduce=_identity):
    cap = cap.astype(jnp.int32)
    kf = k_seg.astype(jnp.float32)

    def body(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2   # avoids int32 overflow of lo + hi
        cnt = reduce(_seg_sum_f32(
            jnp.where(key <= mid[seg], cap, 0), seg, L))
        ge = cnt >= kf
        return jnp.where(ge, lo, mid + 1), jnp.where(ge, mid, hi)

    lo = jnp.full((L,), -1, jnp.int32)
    hi = jnp.full((L,), 1 << 30, jnp.int32)  # keys are < 2^30
    lo, hi = jax.lax.fori_loop(0, TIE_ITERS, body, (lo, hi))
    thr = hi   # minimal key threshold with fill >= k (2^30 infeasible)

    x = jnp.where(key < thr[seg], cap, 0)
    f = reduce(_seg_sum_f32(x, seg, L))
    r = jnp.maximum(kf - f, 0.0)
    grant = (key == thr[seg]) & (r[seg] > 0.0)
    return x + jnp.where(grant, jnp.minimum(
        cap, r[seg].astype(jnp.int32)), 0)


@functools.partial(jax.jit, static_argnames=("L", "sums_only"))
def both_waterfills(e, cap, tie, k_seg, seg, L, sums_only=False):
    # ``sums_only``: a caller that hands in a reduce (the sharded twins)
    reduce = (lambda v: v) if sums_only else kernel_mod._identity
    return (ref_waterfill(e, cap, tie, k_seg, seg, L),
            *waterfill_search(e, cap, tie, k_seg, seg, L, reduce))


@functools.partial(jax.jit, static_argnames=("L", "sums_only"))
def both_packfills(key, cap, k_seg, seg, L, sums_only=False):
    reduce = (lambda v: v) if sums_only else kernel_mod._identity
    return (ref_packfill(key, cap, k_seg, seg, L),
            *packfill_search(key, cap, k_seg, seg, L, reduce))


# ----------------------------------------------------------------- cases

#: rows by segment count: the flat groups' one segment, the tree's rack
#: level (racks over 16 zones) and its node level (nodes over 256 racks)
ROWS = {1: 512, 16: 256, 256: 1024}
#: a leaf bucket past MASK_FORM_MAX_L, where the step scatters and gathers
WIDE_L, WIDE_ROWS = 4096, 2048
KS = (0, 1, 10, 1000, K_CLAMP)
KINDS = ("plain", "infeasible", "empty_segments", "padding_rows",
         "failure_levels", "total_clamp", "idx_offset", "one_row_each")


def make_case(kind: str, L: int, k: int, seed: int = 0) -> dict:
    """One search input as numpy int32 columns: levels ``e``, room
    ``cap``, the tie key as plan_group packs it, segments, and the units
    asked of every segment."""
    rng = np.random.default_rng([seed, L, KINDS.index(kind), k % 9973])
    n = ROWS.get(L, WIDE_ROWS)
    seg = rng.integers(0, L, n) if L > 1 else np.zeros(n, np.int64)
    e = rng.integers(0, 9, n)
    cap = rng.integers(0, max(2, min(k, 600) + 1), n)
    total = rng.integers(0, 9, n)
    idx = np.arange(n)
    k_seg = np.full(L, k)
    if L > 1:
        # segments ask for different amounts, some for nothing
        k_seg = np.minimum(rng.integers(0, 2 * k + 1, L), K_CLAMP)
        k_seg[rng.random(L) < 0.1] = 0
    if kind == "infeasible":
        cap = rng.integers(0, 2, n)
        k_seg = np.minimum(k_seg + n, K_CLAMP)
    elif kind == "empty_segments":
        if L > 1:
            seg = seg % max(L // 2, 1) * 2     # odd segments hold no row
        else:
            cap[:] = 0                         # the one segment has no room
    elif kind == "padding_rows":
        cap[n // 2:] = 0
        e[n // 2:] = 0
        total[n // 2:] = 0
    elif kind == "failure_levels":
        hit = rng.random(n) < 0.3
        e = e + np.where(hit, rng.integers(5, FAILURE_CLAMP + 1, n), 0) \
            * F_BIG
        e[0] = kernel_mod.SVC_CLAMP + FAILURE_CLAMP * F_BIG
    elif kind == "total_clamp":
        total = np.where(rng.random(n) < 0.5, TOTAL_CLAMP,
                         rng.integers(0, TOTAL_CLAMP + 1, n))
    elif kind == "idx_offset":
        idx = idx + 7 * n                      # a later shard's rows
    elif kind == "one_row_each":
        e = rng.integers(0, 1 << 20, n)        # wide levels, narrow room
        cap = np.ones(n, np.int64)
    tie = (np.clip(total, 0, TOTAL_CLAMP) << IDX_BITS) | idx
    i32 = np.int32
    return dict(e=e.astype(i32), cap=cap.astype(i32), tie=tie.astype(i32),
                k_seg=k_seg.astype(i32), seg=seg.astype(i32), L=L)


def pack_key(case: dict) -> np.ndarray:
    """A binpack key over the case's rows: a 10-bit score above the row
    index the tie key already carries."""
    score = (case["e"].astype(np.int64) * 37 + case["cap"]) % 1024
    return ((score << IDX_BITS)
            | (case["tie"] & ((1 << IDX_BITS) - 1))).astype(np.int32)


def host_by_segment(case: dict, fill) -> np.ndarray:
    """The single-segment host mirror, segment by segment."""
    out = np.zeros(len(case["seg"]), np.int32)
    for s in range(case["L"]):
        rows = case["seg"] == s
        if rows.any():
            out[rows] = fill(rows, int(case["k_seg"][s]))
    return out


def check_waterfill(case: dict, sums_only: bool = False,
                    host: bool = True) -> tuple:
    ref, new, level_steps, tie_steps = both_waterfills(
        case["e"], case["cap"], case["tie"], case["k_seg"], case["seg"],
        case["L"], sums_only)
    assert new.dtype == level_steps.dtype == tie_steps.dtype == jnp.int32
    ref, new = np.asarray(ref), np.asarray(new)
    assert (new == ref).all(), np.flatnonzero(new != ref)[:8]
    if host:
        want = host_by_segment(
            case, lambda rows, k: strategy_mod.waterfill_host(
                case["e"][rows], case["cap"][rows], case["tie"][rows], k))
        assert (new == want).all(), np.flatnonzero(new != want)[:8]
    assert int(level_steps) <= SEARCH_STEPS_MAX
    assert int(tie_steps) <= SEARCH_STEPS_MAX
    return int(level_steps), int(tie_steps)


def check_packfill(case: dict, sums_only: bool = False,
                   host: bool = True) -> int:
    key = pack_key(case)
    ref, new, steps = both_packfills(
        key, case["cap"], case["k_seg"], case["seg"], case["L"], sums_only)
    assert new.dtype == steps.dtype == jnp.int32
    ref, new = np.asarray(ref), np.asarray(new)
    assert (new == ref).all(), np.flatnonzero(new != ref)[:8]
    if host:
        want = host_by_segment(
            case, lambda rows, k: strategy_mod.packfill_host(
                key[rows], case["cap"][rows], k))
        assert (new == want).all(), np.flatnonzero(new != want)[:8]
    assert int(steps) <= SEARCH_STEPS_MAX
    return int(steps)


# ----------------------------------------------------------------- tests

def test_no_search_takes_more_steps_than_the_loops_it_replaced():
    assert SEARCH_STEPS_MAX <= min(LEVEL_ITERS, TIE_ITERS)
    # the widest brackets: [0, 2^30] and [-1, 2^30]
    assert SEARCH_STEPS_MAX == int(np.ceil(np.log2((1 << 30) + 2)))


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("L", sorted(ROWS))
@pytest.mark.parametrize("kind", KINDS)
def test_waterfill_equals_the_34_step_form_and_the_host_mirror(kind, L, k):
    check_waterfill(make_case(kind, L, k))


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("L", sorted(ROWS))
@pytest.mark.parametrize("kind", KINDS)
def test_packfill_equals_the_34_step_form_and_the_host_mirror(kind, L, k):
    check_packfill(make_case(kind, L, k))


@pytest.mark.parametrize("kind", KINDS)
def test_a_leaf_bucket_too_wide_for_the_mask_form(kind):
    assert WIDE_L > kernel_mod.MASK_FORM_MAX_L >= max(ROWS)
    for k in (0, 1, 1000):
        case = make_case(kind, WIDE_L, k, seed=3)
        # the host mirror on a few segments only: 4,096 calls a case
        check_waterfill(case, host=False)
        check_packfill(case, host=False)
    few = case["seg"] < 8
    small = dict(case, L=8, k_seg=case["k_seg"][:8],
                 **{c: case[c][few] for c in ("e", "cap", "tie", "seg")})
    want = host_by_segment(small, lambda rows, k: strategy_mod.waterfill_host(
        small["e"][rows], small["cap"][rows], small["tie"][rows], k))
    got = np.asarray(seg_waterfill(
        case["e"], case["cap"], case["tie"], case["k_seg"], case["seg"],
        WIDE_L))[few]
    assert (got == want).all()


@pytest.mark.parametrize("L", sorted(ROWS))
@pytest.mark.parametrize("kind", ("plain", "infeasible", "empty_segments",
                                  "failure_levels"))
def test_a_caller_with_a_reduce_keeps_the_static_bracket(kind, L):
    """The sharded twins hand in a psum: a bracket from the shard's own
    rows would differ between shards, so they keep [0, 2^30] and gain
    the convergence exit alone."""
    for k in KS:
        case = make_case(kind, L, k, seed=1)
        check_waterfill(case, sums_only=True)
        check_packfill(case, sums_only=True)


@pytest.mark.parametrize("L", sorted(ROWS))
def test_the_searches_under_the_fused_paths_x64(L):
    with fusedbatch.x64():
        for kind in KINDS:
            for k in (0, 10, K_CLAMP):
                case = make_case(kind, L, k, seed=2)
                out = seg_waterfill(case["e"], case["cap"], case["tie"],
                                    case["k_seg"], case["seg"], L)
                assert out.dtype == jnp.int32
                assert seg_packfill(pack_key(case), case["cap"],
                                    case["k_seg"], case["seg"],
                                    L).dtype == jnp.int32
                for sums_only in (False, True):
                    check_waterfill(case, sums_only)
                    check_packfill(case, sums_only)


@pytest.mark.parametrize("seed", range(8))
def test_random_inputs_equal_the_34_step_form(seed):
    rng = np.random.default_rng(100 + seed)
    for _ in range(12):
        L = int(rng.choice(sorted(ROWS)))
        kind = KINDS[int(rng.integers(len(KINDS)))]
        k = int(rng.choice([0, 1, 2, 3, 7, 50, 333, 4096, 1 << 17,
                            K_CLAMP - 1, K_CLAMP]))
        case = make_case(kind, L, k, seed=int(rng.integers(1 << 30)))
        check_waterfill(case)
        check_packfill(case)


def test_a_segment_that_cannot_be_filled_does_not_move_its_neighbours():
    """Two segments: one converges at once and cannot hold its k (its
    lower bound would step past the top of its bracket if it kept
    moving), the other needs the whole search."""
    n = 64
    seg = (np.arange(n) >= 8).astype(np.int32)
    e = np.where(seg == 0, 3, np.arange(n) * 1000).astype(np.int32)
    cap = np.where(seg == 0, 1, 5).astype(np.int32)
    tie = np.arange(n, dtype=np.int32)
    case = dict(e=e, cap=cap, tie=tie, seg=seg, L=2,
                k_seg=np.array([500, 97], np.int32))
    # rows by segment for the jit's static shapes
    ref, new, level_steps, _ = both_waterfills(
        e, cap, tie, case["k_seg"], seg, 2)
    assert (np.asarray(new) == np.asarray(ref)).all()
    assert np.asarray(new)[:8].tolist() == [1] * 8     # all it has
    assert int(np.asarray(new)[8:].sum()) == 97
    assert int(level_steps) > 8


# ----------------------------------------------------- the cells' shapes

def fresh_service(nb: int, n: int, k: int, seed: int) -> dict:
    """A service seen for the first time on ``n`` nodes of the ``nb``
    bucket, as both cells' deploys are: no task of its own anywhere,
    0-8 tasks of others on a node, room for the whole group on every
    valid row."""
    rng = np.random.default_rng([seed, nb, k])
    valid = np.arange(nb) < n
    total = np.where(valid, rng.integers(0, 9, nb), 0)
    tie = (total << IDX_BITS) | np.arange(nb)
    i32 = np.int32
    return dict(e=np.zeros(nb, i32), cap=np.where(valid, k, 0).astype(i32),
                tie=tie.astype(i32), k_seg=np.array([k], i32),
                seg=np.zeros(nb, i32), L=1)


@pytest.mark.parametrize("k", (1, 3, 10, 30, 100, 300, 1000))
@pytest.mark.parametrize("nb,n", [(1024, 1000), (16384, 10000)])
def test_trip_counts_of_a_fresh_service_at_the_cells_buckets(nb, n, k):
    level_steps, tie_steps = check_waterfill(fresh_service(nb, n, k, 3))
    # the level lies in [0, k]: log2 of that, where the old loop took 34
    assert level_steps <= 12
    assert level_steps <= int(np.ceil(np.log2(k + 1)))
    # the threshold is a packed key (total << 20 | row): its steps are
    # the width of the marginal rows' key range, 24 bits at totals 0-8
    assert tie_steps <= 24
    assert check_packfill(fresh_service(nb, n, k, 4)) <= 24


def test_a_search_that_has_nothing_to_find_takes_no_step():
    """k = 0 (a fused run's padded slot) and a segment with no room:
    both brackets start closed."""
    for case in (fresh_service(1024, 1000, 0, 5),
                 dict(fresh_service(1024, 1000, 7, 5),
                      cap=np.zeros(1024, np.int32))):
        assert check_waterfill(case) == (0, 0)
        assert check_packfill(case) == 0


# ------------------------------------------- the dense form, above 256 leaves
#
# A wide tree's leaf level laid leaf-major [L, W] once (kernel.LeafLayout,
# built by fusedbatch.leaf_layout): the fourth form of the same searches.

@functools.partial(jax.jit, static_argnames=("L",))
def dense_searches(e, cap, tie, key, k_seg, layout, L):
    """The water-fill and the pack-fill on the layout, back by rows:
    (x, level steps, tie steps, x of the pack-fill, its steps)."""
    e, cap, tie, key = (layout.lay(col, L) for col in (e, cap, tie, key))
    x, level_steps, tie_steps = waterfill_search(e, cap, tie, k_seg, None, L)
    xp, steps = packfill_search(key, cap, k_seg, None, L)
    return layout.rows(x), level_steps, tie_steps, layout.rows(xp), steps


def check_dense(case: dict, n: int = None) -> kernel_mod.LeafLayout:
    """``case`` through the dense form against the scatter form and the
    34-step reference: placements bit for bit, trip counts equal.
    ``n``: the real rows (the rest is the bucket's padding)."""
    L = case["L"]
    n = len(case["seg"]) if n is None else n
    layout = fusedbatch.leaf_layout(case["seg"], n, L)
    assert kernel_mod.search_form(L, layout.W) == "dense"
    key = pack_key(case)
    x, level_steps, tie_steps, xp, steps = dense_searches(
        case["e"], case["cap"], case["tie"], key, case["k_seg"], layout, L)
    assert x.dtype == xp.dtype == level_steps.dtype == jnp.int32
    ref, scatter, s_level, s_tie = both_waterfills(
        case["e"], case["cap"], case["tie"], case["k_seg"], case["seg"], L)
    assert (np.asarray(x) == np.asarray(scatter)).all()
    assert (np.asarray(x) == np.asarray(ref)).all()
    assert (int(level_steps), int(tie_steps)) == (int(s_level), int(s_tie))
    pref, pscatter, p_steps = both_packfills(
        key, case["cap"], case["k_seg"], case["seg"], L)
    assert (np.asarray(xp) == np.asarray(pscatter)).all()
    assert (np.asarray(xp) == np.asarray(pref)).all()
    assert int(steps) == int(p_steps)
    return layout


@pytest.mark.parametrize("k", (0, 1, 1000, K_CLAMP))
@pytest.mark.parametrize("kind", KINDS)
def test_the_dense_form_places_what_the_scatter_form_places_in_as_many_steps(
        kind, k):
    case = make_case(kind, WIDE_L, k, seed=4)
    layout = check_dense(case)
    # the layout holds every row once, in its own leaf's row
    slot = layout.slot
    assert len(np.unique(slot)) == len(slot) and slot.max() < WIDE_L * layout.W
    assert (slot // layout.W == case["seg"]).all()
    # the host mirror on a few segments: 4,096 calls a case otherwise
    few = case["seg"] < 8
    small = dict(case, L=8, k_seg=case["k_seg"][:8],
                 **{c: case[c][few] for c in ("e", "cap", "tie", "seg")})
    want = host_by_segment(small, lambda rows, k: strategy_mod.waterfill_host(
        small["e"][rows], small["cap"][rows], small["tie"][rows], k))
    got = np.asarray(dense_searches(
        case["e"], case["cap"], case["tie"], pack_key(case), case["k_seg"],
        layout, WIDE_L)[0])[few]
    assert (got == want).all()


def _shaped_case(shape: str) -> tuple:
    """(case, real rows) for the layout's own edges."""
    case = make_case("plain", WIDE_L, 50, seed=5)
    n = rows = len(case["seg"])
    if shape == "a_leaf_filled_to_exactly_W":
        case["seg"][:64] = 7          # with the leaf's own rows: 64 or more
        extra = int((case["seg"] == 7).sum())
        case["seg"][64:][case["seg"][64:] == 7] = 8
        assert (case["seg"] == 7).sum() == 64 <= extra
        case["k_seg"][7] = 3 * 64     # and asked for more than it has rows
    elif shape == "empty_leaves":
        case["seg"] = (case["seg"] % 300 * 13).astype(np.int32)
    elif shape == "padding_rows":
        # a bucket's tail: segment 0, no room, no tasks, and no slot
        n = rows * 3 // 4
        for c, v in (("seg", 0), ("cap", 0), ("e", 0)):
            case[c][n:] = v
        case["tie"][n:] = np.arange(n, rows)
    return case, n


@pytest.mark.parametrize("shape", ("a_leaf_filled_to_exactly_W",
                                   "empty_leaves", "padding_rows"))
def test_the_layouts_edges_place_what_the_scatter_form_places(shape):
    case, n = _shaped_case(shape)
    layout = check_dense(case, n)
    pop = np.bincount(case["seg"][:n], minlength=WIDE_L)
    assert layout.W == fusedbatch.pow2_bucket(int(pop.max()))
    if shape == "a_leaf_filled_to_exactly_W":
        assert layout.W == 64 == pop[7]
        assert sorted(layout.slot[case["seg"] == 7]) \
            == list(range(7 * 64, 8 * 64))
    elif shape == "empty_leaves":
        assert (pop == 0).sum() >= WIDE_L - 300
    else:
        assert (layout.slot[n:] == WIDE_L * layout.W).all()
        assert layout.slot[:n].max() < WIDE_L * layout.W


@pytest.mark.parametrize("kind", KINDS)
def test_the_dense_form_under_the_fused_paths_x64(kind):
    with fusedbatch.x64():
        for k in (0, 10, K_CLAMP):
            check_dense(make_case(kind, WIDE_L, k, seed=6))


# ---- the three ways back to the scatter form, through plan_group

WIDE_NB, WIDE_N, WIDE_RACKS = 1024, 900, 300


def wide_tree_inputs(seed: int = 0, k: int = 77):
    """(NodeInputs, GroupInputs, L, hier) of a two-level tree of 300
    racks in 4 zones over 900 nodes of the 1,024 bucket, built as the
    cold path builds it."""
    from swarmkit_tpu.ops.kernel import GroupInputs, NodeInputs
    rng = np.random.default_rng([seed, 35])
    nb, n, i32 = WIDE_NB, WIDE_N, np.int32
    valid = np.arange(nb) < n
    rack = np.where(valid, rng.integers(0, WIDE_RACKS, nb), 0)
    zone = rack % 4
    segs, level_ids = [], []
    for col in ([(z,) for z in zone], list(zip(zone, rack))):
        ids = {}
        segs.append(np.array([ids.setdefault(p, len(ids)) if v else 0
                              for p, v in zip(col, valid)], i32))
        level_ids.append(ids)
    leaf, L, hier = fusedbatch.tree_inputs(segs, level_ids, n)
    nodes = NodeInputs(
        valid=valid, ready=valid & (rng.random(nb) < 0.95), res_ok=valid,
        res_cap=np.where(valid, rng.integers(0, 4, nb), 0).astype(i32),
        svc_tasks=np.where(valid, rng.integers(0, 3, nb), 0).astype(i32),
        total_tasks=np.where(valid, rng.integers(0, 9, nb), 0).astype(i32),
        failures=np.zeros(nb, i32), leaf=leaf,
        os_hash=np.zeros((2, nb), i32), arch_hash=np.zeros((2, nb), i32),
        port_conflict=np.zeros(nb, bool), extra_mask=np.ones(nb, bool))
    group = GroupInputs(
        k=i32(k), con_hash=np.zeros((1, 2, nb), i32),
        con_op=np.full(1, 2, i32), con_exp=np.zeros((1, 2), i32),
        plat=np.full((1, 4), -1, i32), maxrep=i32(0),
        port_limited=np.bool_(False))
    return nodes, group, L, hier


def _plan(nodes, group, L, hier, reduce=kernel_mod._identity):
    """(outputs, whether the traced program scatter-adds: the scatter
    form's segment sums; the dense form's scatters only set)."""
    fn = functools.partial(kernel_mod.plan_group, L=L, reduce=reduce)
    text = str(jax.make_jaxpr(fn)(nodes, group, hier=hier))
    out = jax.jit(fn)(nodes, group, hier=hier)
    return [np.asarray(a) for a in out], "scatter-add" in text


@pytest.mark.parametrize("way", ("no_layout", "over_the_bound",
                                 "a_caller_with_a_reduce"))
def test_the_ways_back_to_the_scatter_form(way, monkeypatch):
    nodes, group, L, hier = wide_tree_inputs()
    layout = hier[2]
    assert L == WIDE_L and len(hier) == 3
    assert kernel_mod.search_form(L, layout.W) == "dense"
    dense, adds = _plan(nodes, group, L, hier)
    assert not adds and dense[0].sum() == int(group.k)
    reduce = kernel_mod._identity
    if way == "no_layout":
        hier = hier[:2]
        assert kernel_mod.search_form(L) == "scatter"
    elif way == "over_the_bound":
        monkeypatch.setattr(kernel_mod, "DENSE_FORM_MAX_ENTRIES",
                            L * layout.W - 1)
        assert kernel_mod.search_form(L, layout.W) == "scatter"
        # and the tree's builder lays none the kernel would not take
        assert fusedbatch.leaf_layout(nodes.leaf, WIDE_N, L) is None
    else:
        reduce = lambda v: v                                # noqa: E731
        assert kernel_mod.search_form(L, layout.W, reduce) == "scatter"
    back, adds = _plan(nodes, group, L, hier, reduce)
    assert adds
    for got, want in zip(back, dense):
        assert (got == want).all()


def test_the_form_follows_the_static_inputs_alone():
    form = kernel_mod.search_form
    assert [form(L) for L in (1, 16, 256, 257, 4096)] \
        == ["sum", "mask", "mask", "scatter", "scatter"]
    # a layout changes nothing at or under the mask form's bound
    assert [form(L, 128) for L in (1, 256, 257, 4096)] \
        == ["sum", "mask", "dense", "dense"]
    top = kernel_mod.DENSE_FORM_MAX_ENTRIES
    assert form(4096, top // 4096) == "dense"
    assert form(4096, top // 4096 * 2) == "scatter"


def _walks(jaxpr, inside_loop=False, out=None) -> dict:
    """Scatters and gathers of a traced program, those inside a
    ``while`` apart: {(primitive, inside a loop): count}."""
    out = {} if out is None else out
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name.startswith(("scatter", "gather")):
            out[name, inside_loop] = out.get((name, inside_loop), 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _walks(sub, inside_loop or name == "while", out)
    return out


def test_the_dense_program_walks_the_rows_five_times_and_never_in_a_step():
    """Four columns into the layout, x back; the scatter form of the
    same program gathers and scatter-adds inside both searches."""
    nodes, group, L, hier = wide_tree_inputs()
    fn = functools.partial(kernel_mod.plan_group, L=L)
    dense = _walks(jax.make_jaxpr(fn)(nodes, group, hier=hier).jaxpr)
    assert dense == {("scatter", False): 4, ("gather", False): 1}
    scatter = _walks(jax.make_jaxpr(fn)(nodes, group, hier=hier[:2]).jaxpr)
    assert scatter["scatter-add", True] == 2 == scatter["gather", True]
    assert sum(scatter.values()) == 14     # each a walk; ten of them once
