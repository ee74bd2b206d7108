"""Device-resident node state for the streaming scheduler (ISSUE 14).

``TPUPlanner._build_columns`` re-densifies the whole NodeSet mirror into
SoA columns every tick — O(cluster) Python work per tick, even when the
tick's churn touched three nodes.  ``ResidentState`` keeps those columns
(and the per-group column *precursors*: which rows hold an active task
of which service, node platform hashes, constraint hash columns, spread
leaves, the level columns of multi-level spread trees, failure rows)
alive across ticks and refreshes only the rows the scheduler's ``DeltaTracker`` marked dirty — the hardware-task-scheduler move of
amortizing decision cost across a persistent structure (PAPERS.md: HTS
1907.00271, DaphneSched 2308.01607).

Two tiers of residency:

* **host mirror** — numpy columns updated row-wise from the NodeInfo
  ground truth.  These feed the per-group kernel inputs and the exact
  int64 resource math, so incremental refresh is byte-identical to a
  full rebuild by construction (same per-row formulas, same row order —
  appends match the NodeSet dict's insertion order; removals demand a
  full rebuild because row index is a placement tie-break key).
* **device arrays** — jnp copies of the five node-state columns
  (valid/ready/cpu/mem/total), updated in place by a **donated** scatter
  program (``_scatter_rows_jit``: ``donate_argnums`` lets XLA reuse the
  resident buffers instead of allocating per delta — the pjit/donation
  idiom in SNIPPETS.md [1]/[2]).  The fused planner seeds its
  ``FusedShared``/``FusedCarry`` node columns from them when fresh,
  skipping the per-run H2D of the big columns.  The resident arrays are
  never read back to host mid-program — D2H belongs to the fetch stage
  (swarmlint device-path-purity).

On a planner mesh (``SWARM_PLANNER_MESH``) the device tier is
**node-axis sharded** (parallel/sharded.py): each device owns nb/D
rows, uploads stage per shard (``device_put`` with a NamedSharding
ships each device its own slice), and the dirty-row scatter becomes a
per-shard donated program — rows are bucketed by owning shard
host-side, so a streaming tick moves O(churn) bytes and zero
cross-device traffic, and the fused run seeds sharded
``FusedShared``/``FusedCarry`` columns with no reshuffle.

Fallback matrix (every full rebuild is counted; the escape hatch
``SWARM_STREAMING_PLANNER=0`` turns the whole plane off):

=====================  =======================================
cold start             first refresh ever (counted ``cold``)
leader handoff         tick epoch != resident epoch → resync —
                       a successor must rebuild from its own
                       replicated store before trusting rows
node removal / store   row order shifts → full rebuild
resync
node-bucket overflow   cluster outgrew ``nb`` → rebuild into
                       the next pow2 bucket
tracker divergence     mirror count != resident count (a missed
                       hook) → rebuild, never trust drifted rows
mesh shard-count       planner mesh resized → the resident
change                 shards have the wrong layout; device tier
                       re-uploads (host mirror stays valid)
mesh teardown          planner mesh removed → device tier
                       demotes to single-device residency
=====================  =======================================
"""

from __future__ import annotations

import functools
import logging
import warnings
from typing import Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ..models.types import NodeAvailability, NodeState
from ..obs import devicetelemetry as _devtel
from ..utils.metrics import registry as _metrics
from . import fusedbatch
from .fusedbatch import SENTINEL, l_bucket, n_bucket, split_hash
from .hashing import str_hash

log = logging.getLogger("tpu-streaming")

_REFRESH_TIMER = _metrics.timer("swarm_streaming_refresh_latency")

#: dirty-row scatter buckets (jit signatures stay bounded); a refresh
#: dirtier than the top bucket re-uploads the columns wholesale
D_BUCKETS = (16, 256, 4096)

#: column cache bounds (FIFO eviction — oldest-built goes first;
#: deterministic): an evicted column simply rebuilds on next demand
CON_CACHE_CAP = 32
LEAF_CACHE_CAP = 16

_UNSET = object()


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3, 4))
def _scatter_rows_jit(valid, ready, cpu, mem, total, idx,
                      u_valid, u_ready, u_cpu, u_mem, u_total):
    """In-place dirty-row update of the resident device columns.  The
    five resident arrays are DONATED: XLA writes the updates into the
    existing buffers instead of allocating a cluster-sized copy per
    delta batch.  Padded index slots carry ``nb`` (out of bounds) and
    drop."""
    kw = dict(mode="drop")
    return (valid.at[idx].set(u_valid, **kw),
            ready.at[idx].set(u_ready, **kw),
            cpu.at[idx].set(u_cpu, **kw),
            mem.at[idx].set(u_mem, **kw),
            total.at[idx].set(u_total, **kw))


def _d_bucket(d: int) -> Optional[int]:
    for b in D_BUCKETS:
        if d <= b:
            return b
    return None


class _ConColumn:
    """One cached constraint-key hash column: per-node value hashes
    (hi/lo int32) plus whether ANY node's value was unknown (the whole
    constraint then disables with the sentinel, matching
    ``fusedbatch.fill_constraints``)."""

    __slots__ = ("hash", "none_count")

    def __init__(self, nb: int):
        self.hash = np.zeros((2, nb), np.int32)
        self.none_count = 0


class _TreeColumns:
    """One cached multi-level spread tree, as the per-group walk
    (``fusedbatch.spread_tree``) numbers it: per level the segment-id
    column and the path-prefix -> id map, each row's path, and the
    kernel inputs derived from the numbering (``fusedbatch.tree_inputs``:
    parent arrays at their bucket widths, ``leaf_parent``, ``L``, a wide
    tree's ``LeafLayout``), derived again once an appended row opened a
    new branch or outgrew the layout's ``W``."""

    __slots__ = ("segs", "level_ids", "paths", "inputs")

    def __init__(self, depth: int, nb: int):
        self.segs = [np.zeros(nb, np.int32) for _ in range(depth)]
        self.level_ids: List[Dict[tuple, int]] = [
            {} for _ in range(depth)]
        self.paths: List[tuple] = []
        self.inputs = None

    def append(self, path: tuple) -> bool:
        """Row ``len(paths)`` joins with ``path``.  True where it was
        one more than its leaf's ``W`` slots hold: the layout is laid
        again at twice the width, a new jit signature."""
        i = len(self.paths)
        self.paths.append(path)
        for di, ids in enumerate(self.level_ids):
            known = len(ids)
            self.segs[di][i] = ids.setdefault(path[:di + 1], known)
            if len(ids) != known:
                self.inputs = None
        if self.inputs is None or len(self.inputs[2]) < 3:
            return False
        # the kept layout: the row takes its leaf's next rank
        layout, leaf = self.inputs[2][2], self.segs[-1]
        rank = int(np.count_nonzero(leaf[:i] == leaf[i]))
        if rank < layout.W:
            layout.slot[i] = leaf[i] * layout.W + rank
            return False
        self.inputs = None
        return True


class _LeafColumn:
    """One cached flat spread column, as ``fusedbatch.flat_leaf`` numbers
    it: the leaf-id column, the value -> id map, each row's value, and
    the column's ``LeafLayout`` (None where it has none: 256 values or
    fewer, or over the bound), kept by row as ``_TreeColumns`` keeps the
    tree's and laid again (``laid`` False) once a new value moved the
    leaf bucket or a leaf outgrew the layout's ``W``."""

    __slots__ = ("leaf", "ids", "values", "layout", "laid")

    def __init__(self, nb: int):
        self.leaf = np.zeros(nb, np.int32)
        self.ids: Dict[str, int] = {}
        self.values: List[str] = []
        self.layout = None
        self.laid = False

    def append(self, v: str) -> bool:
        """Row ``len(values)`` joins with value ``v``.  True where a
        layout that stood was dropped for it: a new jit signature."""
        i = len(self.values)
        self.values.append(v)
        known = len(self.ids)
        self.leaf[i] = self.ids.setdefault(v, known)
        if not self.laid:
            return False
        if l_bucket(len(self.ids)) != l_bucket(max(known, 1)):
            # a rung of the leaf ladder: another L, so another layout
            # (or a first one, past 256 values)
            self.laid = False
        elif self.layout is not None:
            # the kept layout: the row takes its leaf's next rank
            layout, leaf = self.layout, self.leaf
            rank = int(np.count_nonzero(leaf[:i] == leaf[i]))
            if rank < layout.W:
                layout.slot[i] = leaf[i] * layout.W + rank
            else:
                self.laid = False
        return not self.laid and self.layout is not None

    def inputs(self):
        """``fusedbatch.flat_leaf``'s triple."""
        n_values = max(len(self.ids), 1)
        if not self.laid:
            self.layout = fusedbatch.leaf_layout(
                self.leaf, len(self.values), l_bucket(n_values))
            self.laid = True
        return self.leaf, n_values, self.layout


class ResidentState:
    """Persistent densified node state, refreshed O(churn) per tick."""

    def __init__(self, node_value: Callable, device: bool = True,
                 mesh=None, count: Optional[Callable] = None):
        #: planner._node_value — constraint-key lookup per NodeInfo
        self._node_value = node_value
        #: planner._count — the resident tier's events that the planner
        #: accounts for (``tree_cols_*``, ``leaf_cols_*``, ``svc_cols_builds``
        #: and ``svc_col_rows``, the ``h2d_bytes`` of the device tier's
        #: uploads and scatters) go through its one counter sink
        self._count = count or (lambda key, delta=1: None)
        #: planner mesh (parallel/sharded.py) — when set and the node
        #: bucket divides evenly over it, the device tier lives as
        #: node-axis-sharded arrays with per-shard donated scatters
        self.mesh = mesh
        self._mesh_active = False
        self.infos: Optional[List] = None
        self.row_of: Dict[str, int] = {}
        self.n = 0
        self.nb = 0
        self.valid = self.ready = None
        self.cpu = self.mem = self.total = None
        self.os_hash = self.arch_hash = None
        # platform hashes are maintained LAZILY: workloads without
        # platform requirements never pay the 2x str_hash per row
        self._want_platforms = False
        self.node_ids: List[str] = []
        self.task_dicts: List[dict] = []
        #: rows whose NodeInfo has a (possibly expired) failure record —
        #: mirrors the ``if info.recent_failures`` guard of the per-group
        #: failure loop, so the fill visits the same rows it would
        self.fail_rows: Dict[int, None] = {}
        #: service id -> the rows that hold an active task of it, and per
        #: row the services it is entered under: exactly the live
        #: (service, row) pairs, so a service's column costs its non-zero
        #: rows and a service that holds nothing costs no row at all
        self.svc_rows: Dict[str, Dict[int, None]] = {}
        self.row_svcs: List[tuple] = []
        self.con_cols: Dict[str, _ConColumn] = {}
        self.leaf_cols: Dict[str, _LeafColumn] = {}
        self.tree_cols: Dict[Tuple[str, ...], _TreeColumns] = {}
        self.epoch = _UNSET
        self._tracker = None
        # device tier
        self.device_enabled = device
        self.dev: Optional[tuple] = None     # (valid, ready, cpu, mem, total)
        self._dev_version = -1
        # rows recomputed by a HOST-ONLY absorb (mid-tick accessors):
        # the device tier has not seen them yet — the next device sync
        # must scatter them too, or it would stamp itself fresh while
        # silently missing those rows' updates
        self._pending_dev_rows: Dict[int, None] = {}
        self.stats = {"colds": 0, "resyncs": 0, "fallbacks": 0,
                      "incremental": 0, "full": 0, "rows": 0,
                      "dirty_frac": 0.0, "device_syncs": 0,
                      "bytes_avoided": 0,
                      "shard_syncs": 0, "scatter_failures": 0}

    # --------------------------------------------------------- mesh tier

    def set_mesh(self, mesh) -> None:
        """(Re)wire the planner mesh.  A layout change while device
        arrays exist — mesh resized ("shard-count") or removed
        ("mesh-teardown") — drops the device tier for a counted
        re-upload on the next sync; the host mirror stays valid, so no
        host rebuild happens."""
        if mesh is self.mesh:
            return
        old, self.mesh = self.mesh, mesh
        if self.dev is None and not self._mesh_active:
            return
        reason = "mesh-teardown" if mesh is None else "shard-count"
        self.stats["resyncs"] += 1
        _metrics.counter(
            f'swarm_streaming_resyncs{{reason="{reason}"}}')
        log.info("resident device tier reset (%s): mesh %s -> %s",
                 reason, old, mesh)
        self.dev = None
        self._mesh_active = False
        self._dev_version = -1

    def _mesh_for(self):
        """The usable mesh for the device tier: set, >1 device, and
        evenly dividing the node bucket (pow2 buckets and mesh sizes
        make that the norm; a non-pow2 mesh demotes to the
        single-device tier)."""
        mesh = self.mesh
        if mesh is None or not self.nb:
            return None
        from ..parallel.sharded import NODE_AXIS
        d = mesh.shape[NODE_AXIS]
        if d <= 1 or self.nb % d:
            return None
        return mesh

    # ------------------------------------------------------------- refresh

    def refresh(self, sched) -> list:
        """Bring the resident columns up to date with the scheduler's
        mirror and sync the device tier; returns the planner cols list
        ``[infos, n, nb, valid, ready, cpu, mem, total]``.  O(dirty)
        when incremental, O(cluster) on the counted fallbacks."""
        import time as _time
        t0 = _time.perf_counter()
        rows = self._absorb(sched, device=True, tick=True)
        _REFRESH_TIMER.observe(_time.perf_counter() - t0)
        if rows is not None and self.n:
            frac = len(rows) / float(self.n)
            self.stats["dirty_frac"] = frac
            _metrics.gauge("swarm_streaming_dirty_frac", frac)
        return self.cols()

    def absorb(self, sched) -> None:
        """Host-only incremental catch-up (mid-tick accessors call this
        before reading cached columns).  Cheap no-op when the tracker
        has nothing pending."""
        self._absorb(sched, device=False)

    def cols(self) -> list:
        return [self.infos, self.n, self.nb, self.valid, self.ready,
                self.cpu, self.mem, self.total]

    def _absorb(self, sched, device: bool,
                tick: bool = False) -> Optional[list]:
        tracker = getattr(sched, "delta", None)
        if tracker is None:
            # no delta feed: behave like the non-streaming planner
            self._rebuild(sched, "no-tracker", count="fallbacks")
            return None
        if self._tracker is not None and tracker is not self._tracker:
            # a different scheduler's mirror: its mutations were never
            # observed here — never trust the resident rows
            self._tracker = tracker
            tracker.drain()
            self._rebuild(sched, "tracker-swap", count="fallbacks")
            self.epoch = getattr(sched, "_tick_epoch", None)
            if device:
                self._device_upload()
            return None
        self._tracker = tracker
        epoch = getattr(sched, "_tick_epoch", None)
        if not tracker.pending and self.infos is not None \
                and epoch == self.epoch:
            if tick:
                self.stats["incremental"] += 1
                self.stats["dirty_frac"] = 0.0
                _metrics.counter(
                    'swarm_streaming_ticks{mode="incremental"}')
            if device:
                self._device_sync([])   # flushes any host-only backlog
            return []
        dirty, added, full_reason = tracker.drain()
        if self.infos is None:
            full_reason = full_reason or "cold"
        if full_reason is not None:
            self._rebuild(sched, full_reason)
            self.epoch = epoch
            if device:
                self._device_upload()
            return None
        if self.epoch is not _UNSET and epoch != self.epoch:
            # leader handoff (or the first fenced tick after an unfenced
            # one): the resident state was built under another reign —
            # rebuild from the replicated store before trusting it
            self._rebuild(sched, "epoch", count="resyncs")
            self.epoch = epoch
            if device:
                self._device_upload()
            return None
        node_set = sched.node_set
        rows: List[int] = []
        for nid in added:
            if nid in self.row_of:
                self._rebuild(sched, "divergence", count="fallbacks")
                self.epoch = epoch
                if device:
                    self._device_upload()
                return None
            info = node_set.nodes.get(nid)
            if info is None or self.n >= self.nb:
                reason = "overflow" if info is not None else "divergence"
                self._rebuild(sched, reason, count="fallbacks")
                self.epoch = epoch
                if device:
                    self._device_upload()
                return None
            i = self.n
            self.n += 1
            self.row_of[nid] = i
            self.infos.append(info)
            self.node_ids.append(nid)
            self.task_dicts.append(info.tasks)
            self.row_svcs.append(())
            self.valid[i] = True
            self._recompute_row(i, info, append=True)
            rows.append(i)
        if self.n != len(node_set.nodes):
            self._rebuild(sched, "divergence", count="fallbacks")
            self.epoch = epoch
            if device:
                self._device_upload()
            return None
        for nid in dirty:
            i = self.row_of.get(nid)
            if i is None:
                continue   # marked after removal was already demanded
            info = node_set.nodes.get(nid)
            if info is not self.infos[i]:
                # the NodeInfo OBJECT was swapped (not mutated in
                # place): the resident row mirrors a dead object
                self._rebuild(sched, "divergence", count="fallbacks")
                self.epoch = epoch
                if device:
                    self._device_upload()
                return None
            self._recompute_row(i, info)
            rows.append(i)
        if tick:
            self.stats["incremental"] += 1
            _metrics.counter('swarm_streaming_ticks{mode="incremental"}')
        self.stats["rows"] += len(rows)
        if rows:
            _metrics.counter("swarm_streaming_rows", len(rows))
        if device:
            self._device_sync(rows)
        else:
            # host-only drain: the device tier is now behind for these
            # rows — queue them for the next device sync
            for i in rows:
                self._pending_dev_rows[i] = None
        return rows

    # ------------------------------------------------------------ row math

    def _recompute_row(self, i: int, info, append: bool = False) -> None:
        """One row from the NodeInfo ground truth — the exact per-row
        formulas ``_build_columns`` / ``node_platform_hashes`` apply, so
        an incremental row equals its full-rebuild value bit-for-bit."""
        node = info.node
        self.ready[i] = (
            node.status.state == NodeState.READY
            and node.spec.availability == NodeAvailability.ACTIVE)
        self.cpu[i] = info.available_resources.nano_cpus
        self.mem[i] = info.available_resources.memory_bytes
        self.total[i] = info.active_tasks_count
        if self._want_platforms:
            self._recompute_platform_row(i, info)
        if info.recent_failures:
            self.fail_rows[i] = None
        else:
            self.fail_rows.pop(i, None)
        self._recompute_svc_row(i, info)
        for key in list(self.con_cols):
            self._recompute_con_row(key, i, info)
        for desc_key in list(self.leaf_cols):
            self._recompute_leaf_row(desc_key, i, info, append)
        for descriptors in list(self.tree_cols):
            self._recompute_tree_row(descriptors, i, info, append)

    def _recompute_svc_row(self, i: int, info) -> None:
        """Row ``i``'s entries of the service index, from its live counts
        (``NodeInfo`` keeps a service it no longer holds at 0; the index
        does not, and drops an entry that empties)."""
        live = tuple([sid for sid, c
                      in info.active_tasks_count_by_service.items() if c])
        held = self.row_svcs[i]
        if live == held:
            return
        self.row_svcs[i] = live
        svc_rows = self.svc_rows
        for sid in set(held).difference(live):
            rows = svc_rows[sid]
            del rows[i]
            if not rows:
                del svc_rows[sid]
        for sid in set(live).difference(held):
            svc_rows.setdefault(sid, {})[i] = None

    def _recompute_platform_row(self, i: int, info) -> None:
        desc = info.node.description
        if desc and desc.platform:
            from ..scheduler.filters import normalize_arch
            self.os_hash[:, i] = split_hash(str_hash(desc.platform.os))
            self.arch_hash[:, i] = split_hash(
                str_hash(normalize_arch(desc.platform.architecture)))
        else:
            self.os_hash[:, i] = SENTINEL
            self.arch_hash[:, i] = SENTINEL

    def _recompute_con_row(self, key: str, i: int, info) -> None:
        entry = self.con_cols[key]
        v = self._node_value(info, key)
        # real value hashes are split into non-negative halves, so the
        # (-1, -1) sentinel doubles as the per-row "was unknown" flag
        was_none = bool(entry.hash[0, i] == SENTINEL[0]
                        and entry.hash[1, i] == SENTINEL[1])
        if v is None:
            entry.hash[:, i] = SENTINEL
            if not was_none:
                entry.none_count += 1
        else:
            entry.hash[:, i] = split_hash(str_hash(v))
            if was_none:
                entry.none_count -= 1

    def _recompute_leaf_row(self, desc_key: str, i: int, info,
                            append: bool) -> None:
        from ..scheduler.nodeset import _pref_value
        entry = self.leaf_cols.get(desc_key)
        if entry is None:
            return   # already invalidated earlier in this absorb pass
        v = _pref_value(info, desc_key) or ""
        if append:
            if entry.append(v):
                self._count("leaf_cols_invalidations")
            return
        if entry.values[i] == v:
            return
        # a value change can renumber OTHER rows (leaf ids are
        # first-appearance ordered in row order, and branch index is a
        # spread tie-break the kernel reads): drop the cached column —
        # it rebuilds lazily, exactly as a full rebuild would number it
        del self.leaf_cols[desc_key]

    def _recompute_tree_row(self, descriptors: Tuple[str, ...], i: int,
                            info, append: bool) -> None:
        entry = self.tree_cols[descriptors]
        path = fusedbatch.spread_path(info, descriptors)
        if append:
            if entry.append(path):
                self._count("tree_cols_invalidations")
        elif entry.paths[i] != path:
            # as a flat leaf's value change: ids at every level are
            # first-appearance ordered in row order, so a moved row can
            # renumber others — drop the tree, it rebuilds lazily
            del self.tree_cols[descriptors]
            self._count("tree_cols_invalidations")

    # ------------------------------------------------------- full rebuild

    def _rebuild(self, sched, reason: str, count: Optional[str] = None
                 ) -> None:
        if count is None:
            count = ("colds" if reason == "cold"
                     else "resyncs" if reason == "epoch"
                     else "fallbacks")
        self.stats[count] += 1
        self.stats["full"] += 1
        self.stats["dirty_frac"] = 1.0
        _metrics.counter('swarm_streaming_ticks{mode="full"}')
        _metrics.counter(
            f'swarm_streaming_resyncs{{reason="{reason}"}}')
        node_set = sched.node_set
        infos = list(node_set.nodes.values())
        n = len(infos)
        nb = n_bucket(max(n, 1))
        self.infos = infos
        self.n = n
        self.nb = nb
        self.row_of = {info.node.id: i for i, info in enumerate(infos)}
        self.node_ids = [info.node.id for info in infos]
        self.task_dicts = [info.tasks for info in infos]
        self.valid = np.zeros(nb, bool)
        self.valid[:n] = True
        self.ready = np.zeros(nb, bool)
        self.cpu = np.zeros(nb, np.int64)
        self.mem = np.zeros(nb, np.int64)
        self.total = np.zeros(nb, np.int32)
        self.os_hash = np.zeros((2, nb), np.int32)
        self.arch_hash = np.zeros((2, nb), np.int32)
        self.fail_rows = {}
        # column caches rebuild lazily at their new width; a full
        # device upload covers every row, so the host-only backlog dies
        self.svc_rows = {}
        self.row_svcs = [()] * n
        self.con_cols = {}
        self.leaf_cols = {}
        self.tree_cols = {}
        self._pending_dev_rows = {}
        for i, info in enumerate(infos):
            self._recompute_row(i, info)
        _devtel.set_watermark("host_mirror", _devtel.tree_nbytes(
            (self.valid, self.ready, self.cpu, self.mem, self.total,
             self.os_hash, self.arch_hash)))

    # -------------------------------------------------- cached precursors

    def svc_tasks_col(self, sched, service_id: str) -> np.ndarray:
        """Per-service active-task column, built from the rows the
        service index names: O(rows that hold the service), each count
        read from the row's ``NodeInfo``."""
        self.absorb(sched)
        col = np.zeros(self.nb, np.int32)
        self._count("svc_cols_builds")
        rows = self.svc_rows.get(service_id)
        if rows:
            infos = self.infos
            for i in rows:
                col[i] = infos[i].active_tasks_count_by_service[service_id]
            self._count("svc_col_rows", len(rows))
        return col

    def fill_failures(self, failures: np.ndarray, ts: float, t) -> None:
        """Failure down-weights for one group (rows with failure
        records only — the same rows the O(N) guard loop would visit)."""
        infos = self.infos
        for i in self.fail_rows:
            failures[i] = infos[i].count_recent_failures(ts, t)

    def platform_hashes(self) -> Tuple[np.ndarray, np.ndarray]:
        """Resident node platform hash columns; built in full on first
        demand (a platform-requiring group appeared), row-maintained
        from then on."""
        if not self._want_platforms:
            self._want_platforms = True
            for i, info in enumerate(self.infos):
                self._recompute_platform_row(i, info)
        return self.os_hash, self.arch_hash

    def fill_constraints(self, sched, constraints, con_hash, con_op,
                         con_exp) -> None:
        """Streaming twin of ``fusedbatch.fill_constraints``: per-key
        node-value hash columns are resident and refreshed per dirty
        row, so a group build is a vectorized copy instead of an O(N)
        Python hashing loop."""
        from .fusedbatch import con_column_key
        self.absorb(sched)
        n = self.n
        for ci, con in enumerate(constraints):
            # node.ip constraints resolve to prefix-specific column
            # keys ("node.ip/<p>") whose per-node values _node_value
            # computes — the resident row maintenance below them is
            # key-agnostic (fill_constraints parity)
            col_key, expected = con_column_key(con)
            if col_key is None:
                # malformed node.ip: never matches, regardless of op
                con_op[ci] = 0
                con_exp[ci] = SENTINEL
                continue
            entry = self.con_cols.get(col_key)
            if entry is None:
                if len(self.con_cols) >= CON_CACHE_CAP:
                    del self.con_cols[next(iter(self.con_cols))]
                entry = _ConColumn(self.nb)
                self.con_cols[col_key] = entry
                for i, info in enumerate(self.infos):
                    self._recompute_con_row(col_key, i, info)
            if entry.none_count > 0:
                # unknown key on some node: node never matches,
                # regardless of op (fill_constraints parity)
                con_op[ci] = 0
                con_exp[ci] = SENTINEL
                continue
            con_hash[ci, :, :n] = entry.hash[:, :n]
            con_op[ci] = con.operator
            con_exp[ci] = split_hash(str_hash(expected))

    def flat_leaf(self, sched, descriptor: str):
        """Streaming twin of ``fusedbatch.flat_leaf`` (its triple,
        read-only to callers) — leaf ids stay first-appearance ordered
        in ROW order (a tie-break the kernel reads), so value changes
        that would renumber rebuild the column.  An appended row
        extends the column and its ``LeafLayout``, which is laid again
        here where the row dropped it (``_LeafColumn.append``)."""
        self.absorb(sched)
        entry = self.leaf_cols.get(descriptor)
        if entry is None:
            from ..scheduler.nodeset import _pref_value
            if len(self.leaf_cols) >= LEAF_CACHE_CAP:
                self.leaf_cols.pop(next(iter(self.leaf_cols)))
            entry = _LeafColumn(self.nb)
            for info in self.infos:
                entry.append(_pref_value(info, descriptor) or "")
            self.leaf_cols[descriptor] = entry
            self._count("leaf_cols_builds")
        else:
            self._count("leaf_cols_hits")
        return entry.inputs()

    def spread_tree(self, sched, descriptors: Tuple[str, ...]):
        """Streaming twin of ``fusedbatch.spread_tree`` for two or more
        spread preferences: ``(leaf, L, hier)`` from the resident level
        columns (read-only to callers).  An appended row extends each
        level; a row whose path changed dropped the tree
        (``_recompute_tree_row``), and it is walked again here."""
        self.absorb(sched)
        entry = self.tree_cols.get(descriptors)
        if entry is None:
            if len(self.tree_cols) >= LEAF_CACHE_CAP:
                self.tree_cols.pop(next(iter(self.tree_cols)))
            entry = _TreeColumns(len(descriptors), self.nb)
            for info in self.infos:
                entry.append(fusedbatch.spread_path(info, descriptors))
            self.tree_cols[descriptors] = entry
            self._count("tree_cols_builds")
        else:
            self._count("tree_cols_hits")
        if entry.inputs is None:
            entry.inputs = fusedbatch.tree_inputs(
                entry.segs, entry.level_ids, len(entry.paths))
        return entry.inputs

    # --------------------------------------------------------- device tier

    def _device_upload(self, reason: str = "cold_build") -> None:
        """Fresh device placement of the five node-state columns (full
        rebuild, or a delta too wide for the scatter buckets).  Covers
        every row, so the host-only backlog is consumed by definition.
        On a mesh the wide-delta re-upload is STAGED PER SHARD:
        ``device_put`` with a node-axis NamedSharding ships each device
        its own nb/D slice directly."""
        if not self.device_enabled:
            return
        self._pending_dev_rows = {}
        mesh = self._mesh_for()
        try:
            with fusedbatch.x64():
                if mesh is not None:
                    from ..parallel.sharded import put_resident
                    self.dev = put_resident(
                        (self.valid, self.ready, self.cpu, self.mem,
                         self.total), mesh)
                    self._mesh_active = True
                else:
                    import jax.numpy as jnp
                    self.dev = tuple(jnp.asarray(a) for a in (
                        self.valid, self.ready, self.cpu, self.mem,
                        self.total))
                    self._mesh_active = False
        except Exception:
            log.exception("resident device upload failed; host tier only")
            self.device_enabled = False
            self.dev = None
            self._mesh_active = False
            _metrics.counter("swarm_streaming_device_disabled")
            return
        # host nbytes == device nbytes here (jnp.asarray copies the
        # host columns wholesale under the x64 guard)
        self._note_h2d(reason, _devtel.tree_nbytes(
            (self.valid, self.ready, self.cpu, self.mem, self.total)))
        _devtel.set_watermark("device_resident",
                              _devtel.tree_nbytes(self.dev))
        self.stats["device_syncs"] += 1
        self._dev_version = self._tracker.version \
            if self._tracker is not None else -1

    def _note_h2d(self, reason: str, nbytes: int) -> None:
        """An upload of the device tier: into the planner's
        ``h2d_bytes`` and into the telemetry ledger."""
        self._count("h2d_bytes", nbytes)
        _devtel.note_h2d(reason, nbytes)

    def _device_sync(self, rows: List[int]) -> None:
        """Scatter dirty rows — plus any host-only backlog — into the
        resident device arrays via the donated update program; wide
        deltas re-upload wholesale.  On a mesh the dirty rows are
        bucketed by owning shard (row // local_n) and scattered by the
        per-shard donated program — each device updates only rows it
        owns, zero cross-device traffic."""
        if not self.device_enabled:
            return
        if self.dev is None:
            self._pending_dev_rows = {}
            self._device_upload()
            return
        mesh = self._mesh_for() if self._mesh_active else None
        if self._mesh_active and mesh is None:
            # the mesh became unusable under live device arrays (bucket
            # regrew to a non-dividing width): re-place
            self.dev = None
            self._device_upload()
            return
        if self._pending_dev_rows:
            backlog = self._pending_dev_rows
            self._pending_dev_rows = {}
            for i in rows:
                backlog[i] = None
            rows = list(backlog)
        if not rows:
            self._dev_version = self._tracker.version \
                if self._tracker is not None else -1
            return
        db = _d_bucket(len(rows))
        if db is None:
            self._device_upload(reason="wide_reupload")
            return
        from .planner import _jit_cache_size, _observe_compile
        import time as _time
        if mesh is not None:
            from ..parallel.sharded import NODE_AXIS
            nd = mesh.shape[NODE_AXIS]
            local_n = self.nb // nd
            # pad slot = local_n: out of bounds for the shard, drops
            idx = np.full((nd, db), local_n, np.int32)
            u_valid = np.zeros((nd, db), bool)
            u_ready = np.zeros((nd, db), bool)
            u_cpu = np.zeros((nd, db), np.int64)
            u_mem = np.zeros((nd, db), np.int64)
            u_total = np.zeros((nd, db), np.int32)
            fill = [0] * nd
            for i in rows:
                s, r = divmod(i, local_n)
                j = fill[s]
                fill[s] += 1
                idx[s, j] = r
                u_valid[s, j] = self.valid[i]
                u_ready[s, j] = self.ready[i]
                u_cpu[s, j] = self.cpu[i]
                u_mem[s, j] = self.mem[i]
                u_total[s, j] = self.total[i]
            bucket = f"stream_nb{self.nb}_d{db}_x{nd}"
            reason = "shard_scatter"
            probe = None   # resolved below (import-order safety)
        else:
            idx = np.full(db, self.nb, np.int32)   # pad = oob, drops
            idx[:len(rows)] = rows
            u_valid = np.zeros(db, bool)
            u_ready = np.zeros(db, bool)
            u_cpu = np.zeros(db, np.int64)
            u_mem = np.zeros(db, np.int64)
            u_total = np.zeros(db, np.int32)
            for j, i in enumerate(rows):
                u_valid[j] = self.valid[i]
                u_ready[j] = self.ready[i]
                u_cpu[j] = self.cpu[i]
                u_mem[j] = self.mem[i]
                u_total[j] = self.total[i]
            bucket = f"stream_nb{self.nb}_d{db}"
            reason = "dirty_scatter"
            probe = _scatter_rows_jit
        if probe is None:
            from ..parallel.sharded import scatter_rows_sharded
            probe = scatter_rows_sharded
        before = _jit_cache_size(probe)
        staged = _devtel.tree_nbytes(
            (idx, u_valid, u_ready, u_cpu, u_mem, u_total))
        self._note_h2d(reason, staged)
        # what a non-streaming tick would have shipped instead: the
        # full five-column upload, minus what the scatter staged
        full = _devtel.tree_nbytes(
            (self.valid, self.ready, self.cpu, self.mem, self.total))
        avoided = max(0, full - staged)
        _devtel.note_bytes_avoided(avoided)
        self.stats["bytes_avoided"] += avoided
        # the resident buffers are DONATED to the scatter program: the
        # old array objects are dead after this call, and the donation
        # balance catches anyone who kept a reference and reads them
        old_ids = [id(a) for a in self.dev]
        _devtel.note_donated(old_ids)
        t0 = _time.perf_counter()
        try:
            with warnings.catch_warnings():
                # CPU backends may decline donation with a warning; the
                # program is correct either way (donation is the TPU win)
                warnings.filterwarnings("ignore", message=".*onat.*")
                with fusedbatch.x64():
                    if mesh is not None:
                        from ..parallel.sharded import (
                            put_scatter_updates, scatter_rows_sharded)
                        bufs = put_scatter_updates(
                            (idx, u_valid, u_ready, u_cpu, u_mem,
                             u_total), mesh)
                        self.dev = scatter_rows_sharded(
                            *self.dev, *bufs, mesh=mesh)
                        self.stats["shard_syncs"] += 1
                    else:
                        self.dev = _scatter_rows_jit(
                            *self.dev, idx, u_valid, u_ready, u_cpu,
                            u_mem, u_total)
        except Exception:
            log.exception("resident device scatter failed; re-uploading")
            self.stats["scatter_failures"] += 1
            _metrics.counter("swarm_streaming_scatter_failures")
            _devtel.note_retired(old_ids)   # buffers gone either way
            self.dev = None
            self._device_upload()
            return
        dt = _time.perf_counter() - t0
        _devtel.note_retired(old_ids)
        comp = _observe_compile(probe, bucket, before, dt)
        _devtel.note_kernel(bucket, "scatter", dispatch_s=dt,
                            compile_s=comp, node_rows=len(rows))
        _devtel.set_watermark("device_resident",
                              _devtel.tree_nbytes(self.dev))
        self.stats["device_syncs"] += 1
        self._dev_version = self._tracker.version \
            if self._tracker is not None else -1

    def device_carry(self):
        """The resident device columns (valid, ready, cpu, mem, total)
        — only when they provably mirror the host columns (no marks
        since the last sync); None otherwise.  Consumers treat them as
        immutable snapshots (jax arrays are)."""
        if self.dev is None or self._tracker is None:
            return None
        if self._tracker.version != self._dev_version \
                or self._tracker.pending:
            return None
        # donation-balance runtime check: a consumer is about to read
        # these arrays — if any was donated to a scatter and never
        # rebound, that read would be use-after-donation
        _devtel.check_live([id(a) for a in self.dev])
        return self.dev

    # ------------------------------------------------------------ counters

    def snapshot(self) -> Dict[str, object]:
        """The tier's counters as one dict: benchmark/retreat.py (a
        run with no incremental tick is a retreat), benchmark/harness.py
        and chip_smoke.py read it through
        ``TPUPlanner.streaming_snapshot``."""
        return {
            "enabled": True,
            "dirty_frac": round(self.stats["dirty_frac"], 4),
            "resyncs": self.stats["resyncs"],
            "fallbacks": self.stats["fallbacks"],
            "incremental_ticks": self.stats["incremental"],
            "full_ticks": self.stats["full"],
            "rows": self.stats["rows"],
            "device_syncs": self.stats["device_syncs"],
            "bytes_avoided": self.stats["bytes_avoided"],
            "shard_syncs": self.stats["shard_syncs"],
            "scatter_failures": self.stats["scatter_failures"],
            "device_enabled": self.device_enabled,
            "mesh_devices": self._mesh_devices(),
        }

    def _mesh_devices(self) -> int:
        if not self._mesh_active or self.mesh is None:
            return 0
        from ..parallel.sharded import NODE_AXIS
        return int(self.mesh.shape[NODE_AXIS])
