"""Node-axis sharding of the scheduling kernel over a TPU mesh.

Scaling axis (SURVEY.md §5.7): the tasks×nodes problem is sharded over the
**node dimension** — each device owns N/D nodes' SoA arrays.  The kernel's
only cross-node dependencies are the water-level and tie-threshold binary
searches, whose per-iteration state is an [L]-vector of partial sums — so
the sharded kernel is the *same code* as the single-chip kernel with the
segment-sum reductions wrapped in a `psum` over the mesh axis.  Collective
traffic per group: at most 2 x 31 psums of an [L]-vector (L = spread-branch
count, usually 1) for the two searches, plus a handful for branch sums and
fail counts — a few KB over ICI, independent of node count.  A shard sees
only its own rows, so under a ``reduce`` the searches keep their static
brackets (ops/kernel.py, "The searches") and take from the single-chip
form its exit on convergence: ``lo`` and ``hi`` follow from reduced sums,
so every shard leaves the loop on the same step.

Design notes vs the reference: SwarmKit scales its scheduler by heap bounds
and batching in one Go process (design/scheduler.md); there is no
distributed scheduler to mirror.  This module is the TPU-native scaling
story: pjit/shard_map over a Mesh, XLA collectives over ICI, zero host
coordination inside a tick.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.kernel import (
    FusedCarry, FusedGroups, FusedShared, FusedStrategy, GroupInputs,
    NodeInputs, StrategyInputs, plan_fused, plan_group, plan_strategy,
)

NODE_AXIS = "nodes"


def make_mesh(devices=None, axis: str = NODE_AXIS) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    return Mesh(np.asarray(devices), (axis,))


def mesh_from_env() -> Optional[Mesh]:
    """Build the planner mesh the SWARM_PLANNER_MESH knob asks for:
    an integer device count >1 selects the first D devices (D must be
    available — on CPU images use XLA_FLAGS
    --xla_force_host_platform_device_count).  Unset/1/garbage means
    single-device (no mesh); asking for more devices than exist is a
    loud no (misconfiguration must not silently run slower)."""
    import os
    raw = os.environ.get("SWARM_PLANNER_MESH", "").strip()
    if not raw:
        return None
    try:
        d = int(raw)
    except ValueError:
        return None
    if d <= 1:
        return None
    devices = jax.devices()
    if len(devices) < d:
        raise RuntimeError(
            f"SWARM_PLANNER_MESH={d} but only {len(devices)} device(s) "
            "available")
    return make_mesh(devices[:d])


# PartitionSpecs: node-dimension sharded, everything else replicated.
# quota_ok defaults to None here — specs must match the input pytree
# STRUCTURE, and the quota mask column is only materialized for
# quota-blocked groups (_node_specs switches the spec in per call).
_NODE_SPECS = NodeInputs(
    valid=P(NODE_AXIS), ready=P(NODE_AXIS), res_ok=P(NODE_AXIS),
    res_cap=P(NODE_AXIS), svc_tasks=P(NODE_AXIS),
    total_tasks=P(NODE_AXIS), failures=P(NODE_AXIS), leaf=P(NODE_AXIS),
    os_hash=P(None, NODE_AXIS), arch_hash=P(None, NODE_AXIS),
    port_conflict=P(NODE_AXIS), extra_mask=P(NODE_AXIS))


def _node_specs(nodes: NodeInputs) -> NodeInputs:
    if nodes.quota_ok is None:
        return _NODE_SPECS
    return _NODE_SPECS._replace(quota_ok=P(NODE_AXIS))

_GROUP_SPECS = GroupInputs(
    k=P(), con_hash=P(None, None, NODE_AXIS),
    con_op=P(), con_exp=P(), plat=P(), maxrep=P(), port_limited=P())


@functools.partial(jax.jit, static_argnames=("L", "mesh"))
def plan_group_sharded(nodes: NodeInputs, group: GroupInputs, L: int,
                       mesh: Mesh, hier=()):
    """Sharded group placement:
    (x i32[N] sharded, fail_counts i32[7], spill bool)."""

    n_devices = mesh.shape[NODE_AXIS]
    local_n = nodes.ready.shape[0] // n_devices

    def kernel(nodes_l: NodeInputs, group_l: GroupInputs, hier_l):
        reduce = lambda v: jax.lax.psum(v, NODE_AXIS)  # noqa: E731
        offset = jax.lax.axis_index(NODE_AXIS).astype(jnp.int32) * local_n
        return plan_group(nodes_l, group_l, L, reduce=reduce,
                          idx_offset=offset, hier=hier_l)

    if hier:
        upper, leaf_parent = hier
        # node-dim segment columns shard with the nodes; the small
        # branch-level parent maps are replicated
        hier_specs = (tuple((P(NODE_AXIS), P()) for _ in upper), P())
    else:
        hier_specs = ()
    # check_vma=False: the kernel body is shared with the single-device
    # program, whose loop carries start from replicated constants and
    # pick up shard-varying values; typing that needs pvary casts the
    # single-device trace has no axis for
    fn = shard_map(kernel, mesh=mesh,
                   in_specs=(_node_specs(nodes), _GROUP_SPECS,
                             hier_specs),
                   out_specs=(P(NODE_AXIS), P(), P()),
                   check_vma=False)
    return fn(nodes, group, hier)


# Strategy-kernel PartitionSpecs: the headroom columns shard with the
# nodes; the per-group weight vector and the learned-scorer parameter
# arrays are tiny and replicate.
_STRATEGY_SPECS = StrategyInputs(
    hr_cpu=P(NODE_AXIS), hr_mem=P(NODE_AXIS), hr_gen=P(NODE_AXIS),
    weights=P(), w1=P(), b1=P(), w2=P(), b2=P())


@functools.partial(jax.jit, static_argnames=("strategy", "mesh"))
def plan_strategy_sharded(nodes: NodeInputs, group: GroupInputs,
                          sin: StrategyInputs, strategy: int,
                          mesh: Mesh):
    """Sharded non-spread strategy placement: the same score + packfill
    / waterfill program as ops.kernel.plan_strategy with the node axis
    split over the mesh (psum reduce, per-shard index offset) —
    (x i32[N] sharded, fail_counts i32[8], spill bool=False)."""

    n_devices = mesh.shape[NODE_AXIS]
    local_n = nodes.ready.shape[0] // n_devices

    def kernel(nodes_l: NodeInputs, group_l: GroupInputs,
               sin_l: StrategyInputs):
        reduce = lambda v: jax.lax.psum(v, NODE_AXIS)  # noqa: E731
        offset = jax.lax.axis_index(NODE_AXIS).astype(jnp.int32) * local_n
        return plan_strategy(nodes_l, group_l, sin_l, strategy,
                             reduce=reduce, idx_offset=offset)

    # check_vma=False: as in plan_group_sharded
    fn = shard_map(kernel, mesh=mesh,
                   in_specs=(_node_specs(nodes), _GROUP_SPECS,
                             _STRATEGY_SPECS),
                   out_specs=(P(NODE_AXIS), P(), P()),
                   check_vma=False)
    return fn(nodes, group, sin)


# Fused-batch PartitionSpecs: node-dimension sharded, group/service
# axes replicated (G and S are small; the node axis is the scale axis).
_FUSED_SHARED_SPECS = FusedShared(
    valid=P(NODE_AXIS), ready=P(NODE_AXIS), os_hash=P(None, NODE_AXIS),
    arch_hash=P(None, NODE_AXIS), svc0=P(None, NODE_AXIS))

_FUSED_GROUP_SPECS = FusedGroups(
    k=P(), slot=P(), maxrep=P(), cpu_d=P(), mem_d=P(),
    con_hash=P(None, None, None, NODE_AXIS), con_op=P(), con_exp=P(),
    plat=P(), failures=P(None, NODE_AXIS), leaf=P(None, NODE_AXIS),
    extra_mask=P(None, NODE_AXIS))


def _fused_group_specs(groups: FusedGroups) -> FusedGroups:
    if groups.quota_ok is None:
        return _FUSED_GROUP_SPECS
    return _FUSED_GROUP_SPECS._replace(quota_ok=P(None, NODE_AXIS))

_FUSED_CARRY_SPECS = FusedCarry(
    total=P(NODE_AXIS), cpu=P(NODE_AXIS), mem=P(NODE_AXIS),
    svc_acc=P(None, NODE_AXIS))

# Mixed-strategy fused runs: the per-group ids/weights and the
# run-wide learned parameters are all node-independent — replicated.
_FUSED_STRAT_SPECS = FusedStrategy(
    sid=P(), weights=P(), w1=P(), b1=P(), w2=P(), b2=P())


@functools.partial(jax.jit, static_argnames=("L", "mesh"))
def plan_fused_sharded(shared: FusedShared, groups: FusedGroups,
                       carry: FusedCarry, L: int, mesh: Mesh,
                       strat: Optional[FusedStrategy] = None):
    """Sharded fused batch: the same scan-over-groups program as
    ops.kernel.plan_fused with the node axis split over the mesh.
    Cross-shard traffic per group is unchanged from the per-group
    sharded kernel (at most 2 x 31 psums of an [L]-vector per scan
    step for the node level's two searches, which keep their static
    brackets under a ``reduce`` and leave on convergence, plus a
    handful for branch sums and fail counts); the
    carry stays sharded across chunked calls, so chunk i+1 consumes
    chunk i's device-resident state with zero host round-trips.
    ``strat`` fuses binpack/weighted/learned groups into the same
    sharded scan (ops.kernel.plan_fused's in-scan strategy switch);
    None keeps the spread-only signature untouched."""

    n_devices = mesh.shape[NODE_AXIS]
    local_n = shared.valid.shape[0] // n_devices

    def kernel(shared_l, groups_l, carry_l, strat_l):
        reduce = lambda v: jax.lax.psum(v, NODE_AXIS)  # noqa: E731
        offset = jax.lax.axis_index(NODE_AXIS).astype(jnp.int32) * local_n
        return plan_fused(shared_l, groups_l, carry_l, L, reduce=reduce,
                          idx_offset=offset, strat=strat_l)

    # check_vma=False: as in plan_group_sharded
    fn = shard_map(kernel, mesh=mesh,
                   in_specs=(_FUSED_SHARED_SPECS,
                             _fused_group_specs(groups),
                             _FUSED_CARRY_SPECS,
                             _FUSED_STRAT_SPECS if strat is not None
                             else None),
                   out_specs=(P(None, NODE_AXIS), P(), P(),
                              _FUSED_CARRY_SPECS),
                   check_vma=False)
    return fn(shared, groups, carry, strat)


# ------------------------------------------------ sharded resident tier
#
# The streaming planner's device tier (ops/streaming.ResidentState) on a
# mesh: the five node-state columns live as node-axis-sharded arrays,
# dirty rows are bucketed by owning shard host-side and scattered by a
# per-shard donated program, and the wide-delta re-upload stages each
# device's slice directly via NamedSharding device_put.  The node bucket
# must divide evenly over the mesh (pow2 buckets/mesh sizes guarantee
# it); ResidentState falls back to the single-device tier otherwise.

#: resident node-state column layout (each of the five 1-D columns)
RESIDENT_SPEC = P(NODE_AXIS)
#: staged scatter-buffer layout: leading shard axis [D, db]
SCATTER_SPEC = P(NODE_AXIS, None)


def put_resident(cols, mesh: Mesh) -> tuple:
    """Mesh placement of resident columns: ``device_put`` with a
    node-axis NamedSharding ships each device its own slice (per-shard
    staging — no replicate-then-slice round trip)."""
    s = NamedSharding(mesh, RESIDENT_SPEC)
    # placement shim: the caller (streaming._device_upload) notes these
    # bytes under its resync-reason label — noting here too would
    # double-count the ledger
    # swarmlint: disable=device-path-purity
    return tuple(jax.device_put(a, s) for a in cols)


def put_scatter_updates(bufs, mesh: Mesh) -> tuple:
    """Mesh placement of the staged [D, db] dirty-row buffers: the
    leading axis is the shard axis, so each device receives only its
    own update rows."""
    s = NamedSharding(mesh, SCATTER_SPEC)
    # placement shim: the caller (streaming._device_sync) notes the
    # staged bytes under the shard_scatter label — noting here too
    # would double-count the ledger
    # swarmlint: disable=device-path-purity
    return tuple(jax.device_put(a, s) for a in bufs)


@functools.partial(jax.jit, static_argnames=("mesh",),
                   donate_argnums=(0, 1, 2, 3, 4))
def scatter_rows_sharded(valid, ready, cpu, mem, total, idx,
                         u_valid, u_ready, u_cpu, u_mem, u_total,
                         mesh: Mesh):
    """Per-shard donated dirty-row scatter — the mesh twin of
    ops.streaming._scatter_rows_jit.  The five resident columns are
    DONATED (XLA updates each shard's buffer in place); ``idx`` and the
    update buffers carry a leading shard axis [D, db] with LOCAL row
    indices (row % local_n, bucketed host-side by row // local_n; pad
    slots carry local_n, out of bounds, and drop).  Each device touches
    only rows it owns: zero cross-device traffic per sync."""

    def kernel(valid_l, ready_l, cpu_l, mem_l, total_l, idx_l,
               uv, ur, uc, um, ut):
        kw = dict(mode="drop")
        i = idx_l[0]
        return (valid_l.at[i].set(uv[0], **kw),
                ready_l.at[i].set(ur[0], **kw),
                cpu_l.at[i].set(uc[0], **kw),
                mem_l.at[i].set(um[0], **kw),
                total_l.at[i].set(ut[0], **kw))

    fn = shard_map(kernel, mesh=mesh,
                   in_specs=(RESIDENT_SPEC,) * 5 + (SCATTER_SPEC,) * 6,
                   out_specs=(RESIDENT_SPEC,) * 5,
                   check_vma=False)
    return fn(valid, ready, cpu, mem, total, idx,
              u_valid, u_ready, u_cpu, u_mem, u_total)


class ShardedPlanFn:
    """Drop-in ``plan_fn`` for ops.planner.TPUPlanner running on a mesh.

    Pads the node axis to a multiple of the mesh size and places inputs with
    NamedShardings so XLA keeps arrays device-resident between calls.
    """

    #: the fused path may route non-spread strategy groups through
    #: ``fused(..., strat=...)`` (ops.fusedbatch.probe_group checks)
    supports_strategies = True

    def __init__(self, mesh: Optional[Mesh] = None):
        self.mesh = mesh or make_mesh()

    def __call__(self, nodes: NodeInputs, group: GroupInputs, L: int,
                 hier=()):
        d = self.mesh.shape[NODE_AXIS]
        n = nodes.ready.shape[0]
        if n % d:
            pad = d - n % d

            def pad_last(a):
                if a is None:   # absent quota mask column
                    return None
                width = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
                return np.pad(np.asarray(a), width)

            nodes = NodeInputs(*[pad_last(a) for a in nodes])
            group = group._replace(con_hash=pad_last(group.con_hash))
            if hier:
                upper, leaf_parent = hier
                hier = (tuple((pad_last(seg), parent)
                              for seg, parent in upper), leaf_parent)
        return plan_group_sharded(nodes, group, L, self.mesh, hier)

    def strategy(self, nodes: NodeInputs, group: GroupInputs,
                 sin: StrategyInputs, sid: int):
        """Sharded non-spread strategy dispatch (the planner's
        ``plan_strategy_jit`` twin).  Node-axis padding mirrors
        ``__call__``: padded rows carry valid=False, so their capacity
        is zero and their (arbitrary) strategy scores never place."""
        d = self.mesh.shape[NODE_AXIS]
        n = nodes.ready.shape[0]
        if n % d:
            pad = d - n % d

            def pad_last(a):
                if a is None:
                    return None
                width = [(0, 0)] * (a.ndim - 1) + [(0, pad)]
                return np.pad(np.asarray(a), width)

            nodes = NodeInputs(*[pad_last(a) for a in nodes])
            group = group._replace(con_hash=pad_last(group.con_hash))
            sin = sin._replace(hr_cpu=pad_last(sin.hr_cpu),
                               hr_mem=pad_last(sin.hr_mem),
                               hr_gen=pad_last(sin.hr_gen))
        return plan_strategy_sharded(nodes, group, sin, sid, self.mesh)

    # ------------------------------------------------------- fused batch

    def _shard(self, value, specs):
        from ..obs import devicetelemetry as _devtel
        put = jax.device_put
        staged = [np.asarray(a) for a in value]
        _devtel.note_h2d("mesh_reshard", _devtel.tree_nbytes(staged))
        return type(value)(*(
            put(a, NamedSharding(self.mesh, spec))
            for a, spec in zip(staged, specs)))

    def prepare_fused(self, shared: FusedShared, carry: FusedCarry,
                      resident=None):
        """Place a fused run's node state on the mesh once, so every
        chunked dispatch reads device-resident shards instead of
        re-transferring the resource matrices per call.  The node
        bucket must divide evenly over the mesh (power-of-two buckets
        and mesh sizes guarantee it — asserted, not padded, because
        fused idx tie-keys must match the single-device program).

        ``resident`` (streaming fast path): the five node-state columns
        as ALREADY-mesh-sharded device arrays (ResidentState's sharded
        tier, node-axis layout).  The run seeds valid/ready and the
        resource carry from them with zero cross-device reshuffle —
        only the small per-run extras (platform hashes, service bases,
        the svc accumulator) transfer."""
        n = shared.valid.shape[0]
        d = self.mesh.shape[NODE_AXIS]
        if n % d:
            raise ValueError(
                f"fused node bucket {n} not divisible by mesh size {d}")
        if resident is not None:
            from ..obs import devicetelemetry as _devtel
            d_valid, d_ready, d_cpu, d_mem, d_total = resident
            put = jax.device_put
            extras = [np.asarray(a) for a in
                      (shared.os_hash, shared.arch_hash, shared.svc0,
                       carry.svc_acc)]
            _devtel.note_h2d("mesh_reshard", _devtel.tree_nbytes(extras))
            row_spec = NamedSharding(self.mesh, P(None, NODE_AXIS))
            os_h, arch_h, svc0, svc_acc = (put(a, row_spec)
                                           for a in extras)
            return (FusedShared(valid=d_valid, ready=d_ready,
                                os_hash=os_h, arch_hash=arch_h,
                                svc0=svc0),
                    FusedCarry(total=d_total, cpu=d_cpu, mem=d_mem,
                               svc_acc=svc_acc))
        return (self._shard(shared, _FUSED_SHARED_SPECS),
                self._shard(carry, _FUSED_CARRY_SPECS))

    def fused(self, shared: FusedShared, groups: FusedGroups,
              carry: FusedCarry, L: int,
              strat: Optional[FusedStrategy] = None):
        return plan_fused_sharded(shared, groups, carry, L, self.mesh,
                                  strat)
