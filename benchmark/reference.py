"""The plain reference: upstream's placement semantics in straightforward
Python over plain data, and the comparison that decides ``correct``.

Nothing here imports the program.  Nodes, services and tasks arrive as
plain dicts (``benchmark/cluster.py`` makes them from the seed; the
harness reads tasks back through the control API), so this file can be
read, and run, without ``swarmkit_tpu``.

Two halves:

* ``place`` — a sequential scheduler of the semantics the configuration
  states (moby/swarmkit scheduler.go:694 scheduleTaskGroup, :708
  nodeLess, nodeset.go:50 tree, filter.go): filters, then per-service
  levelling with total load as the tie, a preference tree levelled branch
  by branch, or fill-first for ``binpack``.  It is the reference put in
  the program's place: sound, its placements pass ``compare``; with
  ``fault=`` it breaks one stated guarantee and is the control that has
  to fail.
* ``compare`` — the numbers a run is held to, each beside its limit.
  Placement is not unique (ties are broken by node order, and what a tick
  holds depends on timing), so a run is held to what every correct
  placement has in common, the guarantees of the configuration's file,
  and not to one placement.
"""

from __future__ import annotations

from collections import Counter, defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

ARCH_ALIASES = {"x86_64": "amd64", "aarch64": "arm64"}

#: the limit of each number compared: 0 is exact; ``spread_skew``,
#: ``topology_leaf_skew`` and ``binpack_open_nodes`` are what the
#: configuration's ``guarantees`` state ("differ by at most 1", "at most
#: one node ... has room"): inside the last branch of a preference tree
#: the nodes are levelled like any spread service's.
#: ``topology_skew`` holds the branch levels alone and is set from
#: readings (PERF.md section 2): upstream's scheduleNTasksOnSubtree,
#: which the host route ports faithfully, over-assigns a branch that
#: already holds tasks of the service when a later partial group reaches
#: it, by at most the size of a host-routed group (under the device
#: break-even, 74-160 tasks on the chip); sound runs read 1, 14 and
#: once 24, the weakest control 232; 75 is their geometric middle
LIMITS = {
    "lost_services": 0,
    "missing_tasks": 0,
    "unassigned": 0,
    "not_running": 0,
    "unacked_seen": 0,
    "node_mismatch": 0,
    "overcommitted_nodes": 0,
    "ineligible_tasks": 0,
    "spread_skew": 1,
    "topology_skew": 75,
    "topology_leaf_skew": 1,
    "binpack_open_nodes": 1,
    "retreats": 0,
}


# ------------------------------------------------------------ eligibility

def _node_value(node: dict, key: str) -> Optional[str]:
    lk = key.lower()
    if lk == "node.id":
        return node["id"]
    if lk == "node.hostname":
        return node["hostname"]
    if lk == "node.platform.os":
        return node["os"]
    if lk == "node.platform.arch":
        return node["arch"]
    if lk.startswith("node.labels."):
        return node["labels"].get(key[len("node.labels."):], "")
    return None


def parse_constraint(expr: str) -> Tuple[str, str, str]:
    for op in ("==", "!="):
        if op in expr:
            key, value = expr.split(op, 1)
            return key.strip(), op, value.strip()
    raise ValueError(f"constraint {expr!r}: only == and != are known")


def eligible(node: dict, shape: dict) -> bool:
    """May this node hold a task of this shape, resources apart."""
    if not node.get("ready", True):
        return False
    for expr in shape.get("constraints", ()):
        key, op, want = parse_constraint(expr)
        got = _node_value(node, key)
        if got is None or (got.lower() == want.lower()) != (op == "=="):
            return False
    platforms = shape.get("platforms", ())
    if platforms:
        arch = ARCH_ALIASES.get(node["arch"], node["arch"])
        for p in platforms:
            want_arch = ARCH_ALIASES.get(p.get("architecture", ""),
                                        p.get("architecture", ""))
            if (not want_arch or want_arch == arch) \
                    and (not p.get("os") or p["os"] == node["os"]):
                break
        else:
            return False
    return True


def _branch_of(node: dict, descriptors: List[str], depth: int) -> tuple:
    return tuple(_node_value(node, d) or "" for d in descriptors[:depth])


# ----------------------------------------------------------------- placer

class Placer:
    """Sequential placement of whole services, one task at a time, each
    on the best node by upstream's order: fewest tasks of the service,
    then fewest tasks in all, then node order; under spread preferences
    the least-loaded branch first, level by level; ``binpack`` the node
    with the least room that still fits.  Heaps keep a service's next
    pick O(log nodes), so the cell's own size runs in seconds."""

    def __init__(self, nodes: List[dict], fault: Optional[str] = None):
        if fault not in (None, "overcommit", "constraint", "pile"):
            raise ValueError(fault)
        self.nodes = nodes
        self.fault = fault
        self.cpu = {n["id"]: n["nano_cpus"] for n in nodes}
        self.mem = {n["id"]: n["memory_bytes"] for n in nodes}
        self.total = Counter()
        self.order = {n["id"]: i for i, n in enumerate(nodes)}

    def _room(self, nid: str, shape: dict) -> int:
        if self.fault == "overcommit":
            return 1 << 30
        return min(self.cpu[nid] // shape["nano_cpus"],
                   self.mem[nid] // shape["memory_bytes"])

    def _take(self, nid: str, shape: dict) -> None:
        self.total[nid] += 1
        self.cpu[nid] -= shape["nano_cpus"]
        self.mem[nid] -= shape["memory_bytes"]

    def place_service(self, service_id: str, shape: dict,
                      replicas: int) -> List[Tuple[str, str]]:
        """[(task id, node id)] for as many replicas as fit."""
        import heapq
        if self.fault == "constraint":
            cand = [n for n in self.nodes if n.get("ready", True)]
        else:
            cand = [n for n in self.nodes if eligible(n, shape)]
        cand = [n for n in cand if self._room(n["id"], shape) > 0]
        out: List[Tuple[str, str]] = []

        def emit(nid: str) -> None:
            self._take(nid, shape)
            out.append((f"{service_id}.{len(out) + 1}", nid))

        if shape.get("strategy") == "binpack":
            # fill first: the node that can absorb the fewest more; a
            # node being filled stays the one with the least room
            for n in sorted(cand, key=lambda n: (
                    self._room(n["id"], shape), self.order[n["id"]])):
                while len(out) < replicas \
                        and self._room(n["id"], shape) > 0:
                    emit(n["id"])
            return out
        if self.fault == "pile":
            while cand and len(out) < replicas:
                if self._room(cand[0]["id"], shape) > 0:
                    emit(cand[0]["id"])
                else:
                    cand.pop(0)
            return out
        descriptors = list(shape.get("spread_over", ()))
        depth = len(descriptors)
        heaps: Dict[tuple, list] = defaultdict(list)
        for n in cand:
            nid = n["id"]
            heaps[_branch_of(n, descriptors, depth)].append(
                (0, self.total[nid], self.order[nid], nid))
        for heap in heaps.values():
            heapq.heapify(heap)
        load = Counter()        # tasks of this service under each branch
        while len(out) < replicas:
            live = [leaf for leaf, heap in heaps.items() if heap]
            if not live:
                break
            # down the tree: at each level the least-loaded branch that
            # still has an open node below it
            prefix: tuple = ()
            for d in range(1, depth + 1):
                options = sorted({leaf[:d] for leaf in live
                                  if leaf[:d - 1] == prefix})
                prefix = min(options, key=lambda b: load[b])
            heap = heaps[prefix]
            mine, _total, order, nid = heapq.heappop(heap)
            emit(nid)
            for d in range(1, depth + 1):
                load[prefix[:d]] += 1
            if self._room(nid, shape) > 0:
                heapq.heappush(heap, (mine + 1, self.total[nid], order,
                                      nid))
        return out


def place(nodes: List[dict], services: List[dict],
          fault: Optional[str] = None) -> List[dict]:
    """Place ``services`` (dicts with id, shape, replicas) in order;
    returns task dicts as ``compare`` reads them."""
    placer = Placer(nodes, fault)
    agents = {n["id"] for n in nodes if n.get("agent")}
    tasks = []
    for svc in services:
        for tid, nid in placer.place_service(svc["id"], svc["shape"],
                                             svc["replicas"]):
            tasks.append({"id": tid, "service_id": svc["id"],
                          "node_id": nid,
                          "state": "running" if nid in agents
                          else "assigned"})
    return tasks


# ------------------------------------------------------------- comparison

def _skew(counts: Iterable[int]) -> int:
    counts = list(counts)
    return max(counts) - min(counts) if counts else 0


def compare(nodes: List[dict], services: List[dict], tasks: List[dict],
            seen: Optional[Dict[str, str]] = None,
            retreats: Iterable[str] = ()) -> dict:
    """Hold what a run produced to the configuration's guarantees.

    ``services``: every service whose ``create_service`` was
    acknowledged, as {id, shape, replicas, read_back}; ``read_back`` is
    False when the control API no longer returns it.  ``tasks``: every
    task read back through the control API when the drain ended, as
    {id, service_id, node_id, state}.  ``seen``: task id -> node id as
    the watch client saw it ASSIGNED (None: not compared).  ``retreats``:
    the no-retreat findings.

    Returns {"numbers": {name: value}, "limits": {name: limit},
    "correct": bool, "notes": [...]}.
    """
    by_id = {n["id"]: n for n in nodes}
    agents = {n["id"] for n in nodes if n.get("agent")}
    shape_of = {s["id"]: s["shape"] for s in services}
    by_service: Dict[str, List[dict]] = defaultdict(list)
    cpu_used, mem_used = Counter(), Counter()
    numbers = {name: 0 for name in LIMITS}
    notes: List[str] = []

    acked_ids = set(shape_of)
    for t in tasks:
        if t["service_id"] not in acked_ids:
            numbers["unacked_seen"] += 1
            continue
        by_service[t["service_id"]].append(t)
        nid = t["node_id"]
        if not nid or t["state"] not in ("assigned", "running"):
            numbers["unassigned"] += 1
            continue
        shape = shape_of[t["service_id"]]
        cpu_used[nid] += shape["nano_cpus"]
        mem_used[nid] += shape["memory_bytes"]
        node = by_id.get(nid)
        if node is None or not eligible(node, shape):
            numbers["ineligible_tasks"] += 1
        if nid in agents and t["state"] != "running":
            numbers["not_running"] += 1
        if seen is not None and seen.get(t["id"]) not in (None, nid):
            numbers["node_mismatch"] += 1

    for nid in cpu_used:
        node = by_id.get(nid)
        if node is None or cpu_used[nid] > node["nano_cpus"] \
                or mem_used[nid] > node["memory_bytes"]:
            numbers["overcommitted_nodes"] += 1

    room_cache: Dict[tuple, set] = {}

    def room_for(shape: dict) -> set:
        """Ids of the nodes that could take one more task of ``shape``."""
        key = (shape["nano_cpus"], shape["memory_bytes"])
        if key not in room_cache:
            room_cache[key] = {
                n["id"] for n in nodes
                if n["nano_cpus"] - cpu_used[n["id"]] >= key[0]
                and n["memory_bytes"] - mem_used[n["id"]] >= key[1]}
        return room_cache[key]

    def sibling_skew(children: set, counts: Counter, room: set) -> int:
        """Most-loaded child minus least-loaded child that could still
        take a task (a full node is excused from levelling)."""
        top = max(counts.get(c, 0) for c in children)
        open_ = children & room if room is not None else children
        if len(open_) > sum(1 for c in open_ if counts.get(c, 0)):
            return top                      # an open child holds none
        return top - min((counts[c] for c in open_ if counts.get(c, 0)),
                         default=top)

    tree_cache: Dict[str, tuple] = {}

    def tree_for(shape: dict) -> tuple:
        """(eligible ids, [children by parent at each level]) of the
        shape's preference tree; the last level's children are nodes."""
        descriptors = list(shape.get("spread_over", ()))
        key = repr((shape.get("constraints"), shape.get("platforms"),
                    descriptors))
        if key not in tree_cache:
            ids = {n["id"] for n in nodes if eligible(n, shape)}
            levels = [defaultdict(set)
                      for _ in range(len(descriptors) + 1)]
            for nid in ids:
                path = _branch_of(by_id[nid], descriptors,
                                  len(descriptors))
                for depth in range(len(descriptors)):
                    levels[depth][path[:depth]].add(path[:depth + 1])
                levels[-1][path].add(nid)
            tree_cache[key] = (ids, levels, descriptors)
        return tree_cache[key]

    packed = Counter()
    pack_shapes = {}
    for svc in services:
        sid, shape = svc["id"], svc["shape"]
        if not svc.get("read_back", True):
            numbers["lost_services"] += 1
        placed = [t for t in by_service.get(sid, ()) if t["node_id"]]
        numbers["missing_tasks"] += max(
            0, svc["replicas"] - len(by_service.get(sid, ())))
        counts = Counter(t["node_id"] for t in placed)
        if not counts:
            continue
        if shape.get("strategy") == "binpack":
            packed.update(counts)
            pack_shapes[(shape["nano_cpus"], shape["memory_bytes"])] = \
                shape
            continue
        ids, levels, descriptors = tree_for(shape)
        room = room_for(shape)
        for depth, level in enumerate(levels):
            if depth < len(descriptors):
                name = "topology_skew"
                at = Counter()
                for nid, c in counts.items():
                    if nid in ids:
                        at[_branch_of(by_id[nid], descriptors,
                                      depth + 1)] += c
                parents = {b[:-1] for b in at}
                level_room = None
            else:
                name = "topology_leaf_skew" if descriptors \
                    else "spread_skew"
                at = counts
                parents = {_branch_of(by_id[nid], descriptors,
                                      len(descriptors))
                           for nid in counts if nid in ids}
                level_room = room
            for parent in parents:
                skew = sibling_skew(level[parent], at, level_room)
                if skew > numbers[name]:
                    numbers[name] = skew
                    if skew > LIMITS[name]:
                        held = sorted(at.get(c, 0) for c in level[parent])
                        notes.append(
                            f"{sid} ({svc['replicas']} replicas): under "
                            f"{parent or 'root'} siblings differ by {skew}: "
                            f"{held[:6]}..{held[-6:]}")

    for shape in pack_shapes.values():
        open_nodes = len(room_for(shape) & set(packed))
        numbers["binpack_open_nodes"] = max(
            numbers["binpack_open_nodes"], open_nodes)

    retreats = list(retreats)
    numbers["retreats"] = len(retreats)
    notes.extend(f"retreat: {r}" for r in retreats)
    correct = all(numbers[name] <= LIMITS[name] for name in LIMITS)
    return {"numbers": numbers, "limits": dict(LIMITS),
            "correct": correct, "notes": notes[:40]}


def compared_line(result: dict) -> dict:
    """{name: [number, limit]} in a fixed order, for the result line and
    the last lines of standard error."""
    return {name: [result["numbers"][name], result["limits"][name]]
            for name in LIMITS}
