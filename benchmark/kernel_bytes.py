"""The least bytes a call of each plan program must move: its operands
and its results at the call's static shape, from the signatures in
``swarmkit_tpu/ops/kernel.py`` (``NodeInputs``, ``GroupInputs``,
``StrategyInputs``, ``FusedShared/Groups/Carry/Strategy``) and
``ops/streaming.py`` (``_scatter_rows_jit``).  It counts the call, not an
implementation: whatever later implements a program has to read these
operands and write these results once.

The programs are integer and boolean selects, segment sums and searches;
none has a floating-point operation count worth a roofline, so the bound
is bytes over the chip's memory bandwidth.

``bytes_of_label`` reads the planner's own signature names (its compile
and kernel ledgers are keyed by them): ``nb16384_cc1_p1_L256_h2``,
``..._st1``, ``fused_g2_nb16384_cc1_p1_L1_s4_mx1``,
``stream_nb16384_d256``.
"""

from __future__ import annotations

import re
from typing import Optional

I32, I64, BOOL = 4, 8, 1
#: entries of a plan's ``fail_counts`` result (one per filter)
FAIL_COUNTS = 8
#: learned-scorer parameter shapes (scheduler/strategy.py MLP_FEATURES x
#: hidden); the spread/binpack strategies ship the same shapes as zeros
MLP_F, MLP_H = 6, 8


def _upper_buckets(L: int, depth: int) -> list:
    """Leaf buckets of the levels above the leaves of a spread tree,
    top down: each level's bucket is the next rung down the planner's
    ladder (1, 16, 256, 4096), never below 16 for a real level."""
    ladder = [1, 16, 256, 4096]
    out = []
    b = L
    for _ in range(max(depth - 1, 0)):
        lower = [x for x in ladder if x < b]
        b = max(lower[-1], 16) if lower else 16
        out.append(b)
    return list(reversed(out))


def node_inputs(nb: int, quota: bool = False) -> int:
    return nb * (5 * BOOL + 5 * I32 + 2 * 2 * I32 + (BOOL if quota else 0))


def group_inputs(nb: int, cc: int, p: int) -> int:
    return (I32 + cc * 2 * nb * I32 + cc * I32 + cc * 2 * I32
            + p * 4 * I32 + I32 + BOOL)


def plan_results(nb: int) -> int:
    return nb * I32 + FAIL_COUNTS * I32 + BOOL


def plan_group_bytes(nb: int, cc: int, p: int, L: int, depth: int,
                     quota: bool = False,
                     upper: Optional[list] = None) -> int:
    """``plan_group_jit(nodes, group, L, hier)``; ``depth`` is the
    label's ``h``: 0 flat, else the number of spread levels."""
    hier = 0
    if depth > 1:
        for l_d in (upper if upper is not None
                    else _upper_buckets(L, depth)):
            hier += nb * I32 + l_d * I32        # (segment ids, parent)
        hier += L * I32                         # leaf_parent
    return (node_inputs(nb, quota) + group_inputs(nb, cc, p) + hier
            + plan_results(nb))


def strategy_inputs(nb: int) -> int:
    return (3 * nb * I32 + 4 * I32 + MLP_F * MLP_H * I32 + MLP_H * I32
            + MLP_H * I32 + I32)


def plan_strategy_bytes(nb: int, cc: int, p: int,
                        quota: bool = False) -> int:
    return (node_inputs(nb, quota) + group_inputs(nb, cc, p)
            + strategy_inputs(nb) + plan_results(nb))


def plan_fused_bytes(g: int, nb: int, cc: int, p: int, s: int,
                     quota: bool = False, strat: bool = False) -> int:
    """``plan_fused_jit(shared, groups, carry, L, strat)`` for one chunk
    of ``g`` group slots and ``s`` service slots."""
    shared = nb * (2 * BOOL + 2 * 2 * I32) + s * nb * I32
    groups = (3 * g * I32 + 2 * g * I64 + g * cc * 2 * nb * I32
              + g * cc * I32 + g * cc * 2 * I32 + g * p * 4 * I32
              + 2 * g * nb * I32 + g * nb * BOOL
              + (g * nb * BOOL if quota else 0))
    carry = nb * (I32 + 2 * I64) + s * nb * I32
    strategy = (g * I32 + g * 4 * I32 + MLP_F * MLP_H * I32
                + 2 * MLP_H * I32 + I32) if strat else 0
    # the fused program runs under x64: its fail counts come back int64
    results = g * nb * I32 + g * FAIL_COUNTS * I64 + g * BOOL + carry
    return shared + groups + carry + strategy + results


def scatter_bytes(nb: int, d: int) -> int:
    """``_scatter_rows_jit``: the five resident columns are donated and
    updated in place, so the least the call moves is the ``d`` indices,
    the ``d`` update rows read and the same rows written."""
    row = 2 * BOOL + 2 * I64 + I32
    return d * I32 + 2 * d * row


_GROUP = re.compile(
    r"^nb(\d+)_cc(\d+)_p(\d+)_L(\d+)_h(\d+)(_q1)?(?:_st(\d+))?$")
_FUSED = re.compile(
    r"^fused_g(\d+)_nb(\d+)_cc(\d+)_p(\d+)_L(\d+)_s(\d+)(_q1)?(_mx1)?$")
_STREAM = re.compile(r"^stream_nb(\d+)_d(\d+)$")

#: XLA module name of each program family
FAMILY_MODULE = {"group": "jit_plan_group_jit",
                 "strategy": "jit_plan_strategy_jit",
                 "fused": "jit_plan_fused_jit",
                 "scatter": "jit__scatter_rows_jit"}


def family_of_label(label: str) -> Optional[str]:
    m = _GROUP.match(label)
    if m:
        return "strategy" if m.group(7) else "group"
    if _FUSED.match(label):
        return "fused"
    if _STREAM.match(label):
        return "scatter"
    return None


def bytes_of_label(label: str) -> Optional[int]:
    """Bytes of one call under a planner signature name; None for a name
    that is none of the plan programs (probe, feasibility, gang...)."""
    m = _GROUP.match(label)
    if m:
        nb, cc, p, L, h = (int(x) for x in m.group(1, 2, 3, 4, 5))
        quota = bool(m.group(6))
        if m.group(7):
            return plan_strategy_bytes(nb, cc, p, quota)
        return plan_group_bytes(nb, cc, p, L, h, quota)
    m = _FUSED.match(label)
    if m:
        g, nb, cc, p, _L, s = (int(x) for x in m.group(1, 2, 3, 4, 5, 6))
        return plan_fused_bytes(g, nb, cc, p, s, bool(m.group(7)),
                                bool(m.group(8)))
    m = _STREAM.match(label)
    if m:
        return scatter_bytes(int(m.group(1)), int(m.group(2)))
    return None
