"""The trace reduction on a small recorded trace, and the byte counts
against the programs' own signatures.

The fixture is one scheduler tick on a TPU v5 lite at the 1,024-node
bucket (two fused chunks, then a topology group and a flat group),
recorded in PR 26 and pruned to the two module lines' worth a test can
work by hand: the four ``XLA Modules`` events, the ten longest ``XLA
Ops`` events, the two window markers and the profile's start time."""

import os
import sys

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import kernel_bytes as kb  # noqa: E402
from benchmark import reduce_trace as rt  # noqa: E402

FIXTURE = os.path.join(REPO, "benchmark", "fixtures",
                       "plan_tick_v5e.xplane.pb")

# the fixture's device events, in nanoseconds from the profile's start
MODULES = {"jit_plan_fused_jit": [(203861224, 205342501),
                                  (206449728, 206836609)],
           "jit_plan_group_jit": [(222919283, 224944230),
                                  (234207356, 234922884)]}
# the op events overlap (a while spans its body); their union is four
# stretches
OPS_UNION = [(203864269, 205341047), (206461528, 206834039),
             (223685349, 224292551), (224318949, 224926182)]
WINDOW = (46821625, 308267770)


@pytest.fixture(scope="module")
def reduced():
    return rt.reduce(rt.read(FIXTURE))


def test_window_runs_from_marker_to_marker(reduced):
    assert reduced["window_s"] == pytest.approx(
        (WINDOW[1] - WINDOW[0]) * 1e-9, abs=1e-9)
    assert reduced["devices"] == 1


def test_busy_share_is_the_hand_worked_union_of_the_ops(reduced):
    busy = sum(b - a for a, b in OPS_UNION) * 1e-9
    assert busy == pytest.approx(0.003063724, abs=1e-12)
    assert reduced["busy_s"] == pytest.approx(busy, abs=1e-9)
    assert reduced["idle_pct"] == pytest.approx(
        100 * (1 - busy / reduced["window_s"]), abs=1e-6)
    assert 98.8 < reduced["idle_pct"] < 98.9


def test_per_module_time_is_the_sum_of_its_runs(reduced):
    for family, runs in MODULES.items():
        row = reduced["modules"][family]
        assert row["calls"] == 2
        assert row["seconds"] == pytest.approx(
            sum(b - a for a, b in runs) * 1e-9, abs=1e-9)
    assert len(reduced["modules_by_fingerprint"]) == 4


def test_idle_gaps_are_what_lies_between_the_busy_stretches(reduced):
    edges = [WINDOW[0]] + [x for ab in OPS_UNION for x in ab] + [WINDOW[1]]
    want = [(edges[i] * 1e-9, edges[i + 1] * 1e-9)
            for i in range(0, len(edges), 2)]
    assert len(reduced["gaps"]) == 5
    for got, exp in zip(reduced["gaps"], want):
        assert got == pytest.approx(exp, abs=1e-9)
    assert sum(b - a for a, b in reduced["gaps"]) + reduced["busy_s"] \
        == pytest.approx(reduced["window_s"], abs=1e-9)


def test_top_operations_name_their_module(reduced):
    top = reduced["device_ops"][0]
    assert top[0] == "jit_plan_fused_jit/%while.121"
    assert top[1] == pytest.approx(1476778e-9, abs=1e-9)
    assert all(name.split("/")[0] in MODULES
               for name, _ in reduced["device_ops"])


def test_marker_puts_the_trace_on_the_wall_clock(reduced):
    raw = rt.read(FIXTURE)
    assert reduced["wall_offset_s"] == pytest.approx(
        raw["profile_start_wall_s"], abs=1e-3)


def test_gaps_are_named_by_the_innermost_host_span(reduced):
    off = reduced["wall_offset_s"]
    spans = [("scheduler", "sched.tick", off + 0.20, off + 0.24, None),
             ("scheduler", "plan.d2h", off + 0.2052, off + 0.2066, None),
             ("other", "dispatcher.flush", off + 0.0, off + 0.4, None)]
    named = dict(rt.name_gaps(reduced["gaps"], off, spans))
    # the gap between the two fused chunks lies inside plan.d2h
    assert named["scheduler:plan.d2h"] == pytest.approx(
        (206461528 - 205341047) * 1e-9, abs=1e-9)
    assert "scheduler:sched.tick" in named
    # before and after the tick only the other thread has a span
    assert named["other:dispatcher.flush"] > 0.2
    assert rt.name_gaps(reduced["gaps"], None, spans) \
        == [["unnamed", pytest.approx(sum(b - a
                                          for a, b in reduced["gaps"]))]]
    bd = rt.breakdown(reduced, spans)
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_union_and_gaps_on_plain_intervals():
    assert rt.union([(3, 4), (0, 2), (1, 2.5), (4, 5)]) \
        == [(0, 2.5), (3, 5)]
    assert rt.gaps_of([(0, 2.5), (3, 5)], -1, 6) \
        == [(-1, 0), (2.5, 3), (5, 6)]
    assert rt.clip([(0, 10)], 2, 3) == [(2, 3)]
    assert rt.module_family("jit_plan_group_jit(123)") \
        == "jit_plan_group_jit"


def test_a_trace_with_no_device_plane_reads_nothing():
    empty = rt.reduce({"devices": [], "markers": {}, "profile_s": 1.0,
                       "profile_start_wall_s": None})
    assert empty["idle_pct"] is None and empty["busy_s"] is None


# ------------------------------------------------------------ byte counts

def _tree_bytes(tree) -> int:
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in jax.tree_util.tree_leaves(tree))


def _group_inputs(nb, cc, p):
    from swarmkit_tpu.ops.kernel import GroupInputs, NodeInputs
    i32 = np.int32
    nodes = NodeInputs(
        valid=np.ones(nb, bool), ready=np.ones(nb, bool),
        res_ok=np.ones(nb, bool), res_cap=np.ones(nb, i32),
        svc_tasks=np.zeros(nb, i32), total_tasks=np.zeros(nb, i32),
        failures=np.zeros(nb, i32), leaf=np.zeros(nb, i32),
        os_hash=np.zeros((2, nb), i32), arch_hash=np.zeros((2, nb), i32),
        port_conflict=np.zeros(nb, bool), extra_mask=np.ones(nb, bool))
    group = GroupInputs(
        k=i32(5), con_hash=np.zeros((cc, 2, nb), i32),
        con_op=np.full(cc, 2, i32), con_exp=np.zeros((cc, 2), i32),
        plat=np.full((p, 4), -1, i32), maxrep=i32(0),
        port_limited=np.bool_(False))
    return nodes, group


@pytest.mark.parametrize("cc,p", [(1, 1), (4, 4)])
def test_plan_group_bytes_equal_the_lowered_program_s(cc, p):
    from swarmkit_tpu.ops.kernel import plan_group_jit
    nb = 1024
    nodes, group = _group_inputs(nb, cc, p)
    lowered = plan_group_jit.lower(nodes, group, 1, ())
    out = jax.eval_shape(lambda n, g: plan_group_jit(n, g, 1, ()),
                         nodes, group)
    assert _tree_bytes((nodes, group)) + _tree_bytes(out) \
        == kb.plan_group_bytes(nb, cc, p, 1, 0)
    assert _tree_bytes(lowered.out_info) == kb.plan_results(nb)
    assert kb.bytes_of_label(f"nb{nb}_cc{cc}_p{p}_L1_h0") \
        == kb.plan_group_bytes(nb, cc, p, 1, 0)


def test_two_level_tree_bytes_equal_the_program_s():
    from swarmkit_tpu.ops.kernel import plan_group_jit
    nb = 1024
    nodes, group = _group_inputs(nb, 1, 1)
    hier = (((np.zeros(nb, np.int32), np.zeros(16, np.int32)),),
            np.zeros(256, np.int32))
    out = jax.eval_shape(lambda n, g, h: plan_group_jit(n, g, 256, h),
                         nodes, group, hier)
    assert _tree_bytes((nodes, group, hier)) + _tree_bytes(out) \
        == kb.bytes_of_label(f"nb{nb}_cc1_p1_L256_h2")


def test_strategy_and_fused_bytes_equal_the_programs():
    from swarmkit_tpu.ops.kernel import (
        FusedCarry, FusedGroups, FusedShared, FusedStrategy,
        StrategyInputs, plan_fused_jit, plan_strategy_jit)
    from swarmkit_tpu.scheduler import strategy as strategy_mod
    i32, i64 = np.int32, np.int64
    nb, g, s = 1024, 2, 4
    nodes, group = _group_inputs(nb, 1, 1)
    w1, b1, w2, b2 = (np.asarray(a, i32)
                      for a in strategy_mod.learned_params())
    sin = StrategyInputs(hr_cpu=np.zeros(nb, i32), hr_mem=np.zeros(nb, i32),
                         hr_gen=np.zeros(nb, i32), weights=np.zeros(4, i32),
                         w1=w1, b1=b1, w2=w2, b2=b2)
    out = jax.eval_shape(lambda n, gr, si: plan_strategy_jit(n, gr, si, 1),
                         nodes, group, sin)
    assert _tree_bytes((nodes, group, sin)) + _tree_bytes(out) \
        == kb.bytes_of_label(f"nb{nb}_cc1_p1_L1_h0_st1")

    shared = FusedShared(valid=np.ones(nb, bool), ready=np.ones(nb, bool),
                         os_hash=np.zeros((2, nb), i32),
                         arch_hash=np.zeros((2, nb), i32),
                         svc0=np.zeros((s, nb), i32))
    groups = FusedGroups(
        k=np.zeros(g, i32), slot=np.zeros(g, i32), maxrep=np.zeros(g, i32),
        cpu_d=np.zeros(g, i64), mem_d=np.zeros(g, i64),
        con_hash=np.zeros((g, 1, 2, nb), i32),
        con_op=np.full((g, 1), 2, i32), con_exp=np.zeros((g, 1, 2), i32),
        plat=np.full((g, 1, 4), -1, i32), failures=np.zeros((g, nb), i32),
        leaf=np.zeros((g, nb), i32), extra_mask=np.ones((g, nb), bool))
    carry = FusedCarry(total=np.zeros(nb, i32), cpu=np.zeros(nb, i64),
                       mem=np.zeros(nb, i64), svc_acc=np.zeros((s, nb), i32))
    strat = FusedStrategy(sid=np.zeros(g, i32), weights=np.zeros((g, 4), i32),
                          w1=w1, b1=b1, w2=w2, b2=b2)
    with jax.enable_x64(True):
        out = jax.eval_shape(
            lambda a, b, c, d: plan_fused_jit(a, b, c, 1, d),
            shared, groups, carry, strat)
        got = _tree_bytes((shared, groups, carry, strat)) + _tree_bytes(out)
    assert got == kb.bytes_of_label(
        f"fused_g{g}_nb{nb}_cc1_p1_L1_s{s}_mx1")


def test_labels_that_are_no_plan_program_have_no_bytes():
    assert kb.bytes_of_label("probe") is None
    assert kb.bytes_of_label("feas_nb1024_cc1_p1_L1_h0") is None
    assert kb.family_of_label("stream_nb16384_d256") == "scatter"
    assert kb.bytes_of_label("stream_nb16384_d256") \
        == kb.scatter_bytes(16384, 256) == 256 * 4 + 2 * 256 * 22
