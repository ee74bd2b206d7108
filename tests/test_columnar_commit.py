"""Columnar zero-copy commit plane + native watch fan-out (ISSUE 13).

Differential suites: every native fast path (binary block entry codec,
follower-side block apply, watch fan-out expansion / per-subscriber
filtering / per-node grouping) is pitted against its pure-Python oracle,
and the whole plane must be byte-identical — snapshot bytes, watch
streams, resume replays — across SWARM_NATIVE_COMMIT={0,1} and both raft
routes (proposer-less store and a real single-voter RaftNode)."""

import json
import os
import random
import shutil
import string
import subprocess
import sys
import tempfile

import pytest

from swarmkit_tpu import native
from swarmkit_tpu.models import (
    Annotations, Node, NodeSpec, Task, TaskState, TaskStatus,
)
from swarmkit_tpu.models import types as mtypes
from swarmkit_tpu.state import MemoryStore, serde
from swarmkit_tpu.state.events import Event, EventCommit, EventTaskBlock
from swarmkit_tpu.state.store import TaskBlockAction
from swarmkit_tpu.utils import new_id

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def frozen_clock():
    """Deterministic model clock: byte-identity comparisons span runs,
    so every store-stamped timestamp must be a pure function of the
    workload, not the host."""
    t = [1_000_000.0]

    def tick():
        t[0] += 0.001
        return t[0]

    mtypes.set_time_source(tick)
    yield
    mtypes.set_time_source(None)


def _require_native():
    if native.get() is None:
        pytest.skip("native hotpath did not build on this image")


# ---------------------------------------------------------------------------
# binary block entry codec
# ---------------------------------------------------------------------------

def _random_block(rng, n=None):
    n = rng.randrange(0, 60) if n is None else n
    alphabet = string.hexdigits + ",:{}~é"
    ids = tuple("".join(rng.choices(alphabet, k=rng.randrange(1, 24)))
                for _ in range(n))
    nodes = [f"node-{i}" for i in range(rng.randrange(1, 6))]
    nids = tuple(rng.choice(nodes) if rng.random() > 0.05 else ""
                 for _ in range(n))
    return TaskBlockAction(
        "task_block", ids, nids, rng.randrange(0, 1 << 40),
        rng.randrange(0, int(TaskState.RUNNING) + 1),
        "scheduler assigned task to node"[:rng.randrange(0, 31)],
        rng.random() * 1e9)


def test_block_codec_native_matches_python_oracle():
    """Random blocks x seeds: serde.block_to_bytes must round-trip
    identically through the native block_decode and the pure-Python
    block_from_bytes oracle."""
    _require_native()
    hp = native.get()
    for seed in range(5):
        rng = random.Random(seed)
        for _ in range(60):
            action = _random_block(rng)
            data = serde.block_to_bytes(action)
            assert data is not None
            assert data[:4] == serde.BLOCK_ENTRY_MAGIC
            assert serde.block_from_bytes(data) == action
            assert hp.block_decode(data, TaskBlockAction) == action


def test_block_codec_rejects_corruption():
    """Truncated, padded, and structurally-corrupt entries must raise
    ValueError on BOTH decoders — native and oracle must agree on every
    byte string, or members running different planes diverge on
    identical replicated bytes."""
    import struct
    _require_native()
    hp = native.get()
    data = serde.block_to_bytes(_random_block(random.Random(1), n=12))
    corrupt = [data[:cut] for cut in (0, 3, 17, len(data) - 1)]
    corrupt.append(data + b"x")
    # extra NUL separators: n=2 but three id segments
    hdr = struct.pack("<4sIqidI", b"SKB1", 2, 5, 2, 1.0, 1) + b"m"
    blob = b"a\x00b\x00c"
    corrupt.append(hdr + struct.pack("<I", len(blob)) + blob
                   + struct.pack("<II", 1, 2)
                   + struct.pack("<I", 2) + b"n1")
    # n=0 with a dangling non-empty ids blob
    hdr0 = struct.pack("<4sIqidI", b"SKB1", 0, 5, 2, 1.0, 0)
    corrupt.append(hdr0 + struct.pack("<I", 3) + b"xyz"
                   + struct.pack("<I", 0) + struct.pack("<I", 0))
    for bad in corrupt:
        with pytest.raises(ValueError):
            serde.block_from_bytes(bad)
        with pytest.raises(ValueError):
            hp.block_decode(bad, TaskBlockAction)


def test_entry_codec_fallbacks():
    """NUL in an id forces the JSON change-list form; the escape hatch
    forces it too; decode always accepts BOTH wire forms (replicated
    bytes must apply regardless of the local hatch)."""
    odd = TaskBlockAction("task_block", ("a\x00b",), ("n1",), 1, 2,
                          "m", 3.0)
    assert serde.block_to_bytes(odd) is None
    data = serde.actions_to_entry_data([odd])
    assert data[:1] == b"[" and serde.entry_to_actions(data) == [odd]

    plain = _random_block(random.Random(2), n=8)
    binary = serde.actions_to_entry_data([plain])
    assert binary[:4] == serde.BLOCK_ENTRY_MAGIC
    os.environ["SWARM_NATIVE_COMMIT"] = "0"
    try:
        hatched = serde.actions_to_entry_data([plain])
        assert hatched[:1] == b"["
        # decode side is hatch-agnostic: binary bytes still apply
        assert serde.entry_to_actions(binary) == [plain]
        assert serde.entry_to_actions(hatched) == [plain]
    finally:
        del os.environ["SWARM_NATIVE_COMMIT"]
    assert serde.entry_to_actions(binary) == [plain]


def test_native_commit_fallback_counter(monkeypatch):
    """Native requested but unavailable counts fallback ticks (what
    benchmark/retreat.py reads as a retreat); the explicit escape hatch
    does not."""
    from swarmkit_tpu.utils.metrics import registry
    monkeypatch.setenv("SWARMKIT_TPU_NO_NATIVE", "1")
    base = registry.get_counter("swarm_native_commit_fallbacks")
    assert native.get_commit() is None
    assert registry.get_counter("swarm_native_commit_fallbacks") \
        == base + 1
    monkeypatch.setenv("SWARM_NATIVE_COMMIT", "0")
    assert native.get_commit() is None
    assert registry.get_counter("swarm_native_commit_fallbacks") \
        == base + 1   # hatch pulled: intentional, not a fallback


def test_native_plane_serves_a_block_workload_with_no_fallback(
        monkeypatch):
    """With the plane built and not switched off, the commit path asks
    for it, gets it every time, and counts no fallback: block commits,
    a delete burst and the watch fan-out of both ran native."""
    _require_native()
    from swarmkit_tpu.utils.metrics import registry
    monkeypatch.delenv("SWARM_NATIVE_COMMIT", raising=False)
    served = []
    get_commit = native.get_commit

    def recording():
        served.append(get_commit())
        return served[-1]
    monkeypatch.setattr(native, "get_commit", recording)
    base = registry.get_counter("swarm_native_commit_fallbacks")
    store = MemoryStore()
    sub = store.queue.subscribe()
    _drive_workload(store)
    assert len(sub.drain()) > 37
    assert served and all(hp is not None for hp in served)
    assert registry.get_counter("swarm_native_commit_fallbacks") == base


# ---------------------------------------------------------------------------
# native watch fan-out vs the Python oracle
# ---------------------------------------------------------------------------

def _mk_block_tasks(n, rng):
    out = []
    for i in range(n):
        t = Task(id=f"t{i:04d}", service_id="svc", slot=i + 1,
                 status=TaskStatus(state=TaskState.PENDING, message="p"),
                 desired_state=TaskState.RUNNING)
        t.meta.version.index = rng.randrange(50)
        t.meta.created_at = 5.0
        out.append(t)
    return out


def _event_key(ev):
    if isinstance(ev, EventCommit):
        return ("commit", ev.version)
    if isinstance(ev, Event):
        return (ev.action, ev.version, serde.to_dict(ev.obj),
                serde.to_dict(ev.old) if ev.old is not None else None)
    return ("block", serde.to_dict(ev.expand_events()[0].obj)
            if len(ev) else None, len(ev))


def test_fanout_expand_matches_oracle(monkeypatch):
    _require_native()
    rng = random.Random(3)
    for n in (0, 1, 17, 50):
        olds = _mk_block_tasks(n, rng)
        nids = [f"n{rng.randrange(3)}" for _ in range(n)]
        args = (olds, nids, 700, int(TaskState.ASSIGNED), "assigned",
                42.5)
        ev_native = EventTaskBlock(*args).expand_events()
        monkeypatch.setenv("SWARM_NATIVE_COMMIT", "0")
        ev_python = EventTaskBlock(*args).expand_events()
        monkeypatch.delenv("SWARM_NATIVE_COMMIT")
        assert [_event_key(e) for e in ev_native] \
            == [_event_key(e) for e in ev_python]
        for a, b in zip(ev_native, ev_python):
            assert a.old is b.old   # both reference the stored mirror


def test_per_node_group_matches_oracle(monkeypatch):
    _require_native()
    rng = random.Random(4)
    olds = _mk_block_tasks(40, rng)
    nids = [f"n{rng.randrange(4)}" for _ in range(40)]
    args = (olds, nids, 100, int(TaskState.ASSIGNED), "m", 1.0)
    g_native = EventTaskBlock(*args).per_node()
    monkeypatch.setenv("SWARM_NATIVE_COMMIT", "0")
    g_python = EventTaskBlock(*args).per_node()
    monkeypatch.delenv("SWARM_NATIVE_COMMIT")
    assert list(g_native) == list(g_python)   # insertion order too
    for k in g_native:
        assert [(o.id, v) for o, v in g_native[k]] \
            == [(o.id, v) for o, v in g_python[k]]


def test_fanout_filter_matches_oracle_with_raising_predicate():
    _require_native()
    hp = native.get()
    rng = random.Random(5)
    olds = _mk_block_tasks(20, rng)
    events = EventTaskBlock(olds, ["n1"] * 20, 0,
                            int(TaskState.ASSIGNED), "m",
                            1.0).expand_events()

    def pred(ev):
        if ev.obj.slot % 7 == 0:
            raise RuntimeError("predicate boom")
        return ev.obj.slot % 2 == 0

    oracle = []
    for e in events:
        try:
            if pred(e):
                oracle.append(e)
        except Exception:
            continue
    assert hp.fanout_filter(events, pred) == oracle
    assert len(oracle) > 0


# ---------------------------------------------------------------------------
# byte-identity across SWARM_NATIVE_COMMIT={0,1} and both raft routes
# ---------------------------------------------------------------------------

def _mk_node(name):
    return Node(id=f"node-{name}",
                spec=NodeSpec(annotations=Annotations(name=name)))


def _drive_workload(store, n_tasks=37):
    """Deterministic mixed workload: block commits (two blocks), a
    delete burst, and a per-object update — the stream shapes satellite
    3 pins (blocks, deletes, resume-token stamping)."""
    nodes = [_mk_node(f"n{i}") for i in range(4)]
    tasks = [Task(id=f"task-{i:04d}", service_id="svc", slot=i + 1,
                  desired_state=TaskState.RUNNING,
                  status=TaskStatus(state=TaskState.PENDING))
             for i in range(n_tasks)]

    def setup(tx):
        for n in nodes:
            tx.create(n)
        for t in tasks:
            tx.create(t)
    store.update(setup)
    stored = sorted(store.view(lambda tx: tx.find(Task)),
                    key=lambda t: t.slot)

    def boom(*a):
        raise AssertionError("unexpected callback")

    half = n_tasks // 2
    c1, f1 = store.commit_task_block(
        stored[:half], [nodes[i % 4].id for i in range(half)],
        int(TaskState.ASSIGNED), "assigned", boom, boom)
    assert len(c1) == half and not f1
    # delete events interleave the block stream
    def deletes(tx):
        for t in stored[half:half + 3]:
            tx.delete(Task, t.id)
    store.update(deletes)
    rest = stored[half + 3:]
    c2, f2 = store.commit_task_block(
        rest, [nodes[(i + 1) % 4].id for i in range(len(rest))],
        int(TaskState.ASSIGNED), "assigned", boom, boom)
    assert len(c2) == len(rest) and not f2
    # a per-object update rides the JSON form alongside the blocks
    n0 = store.view(lambda tx: tx.get(Node, nodes[0].id)).copy()
    n0.spec.annotations.labels["zone"] = "z1"
    store.update(lambda tx: tx.update(n0))


def _run_plane(native_on, route, monkeypatch):
    """One full run: returns (snapshot bytes, per-item subscriber
    stream, block-aware subscriber stream, resume replay) fingerprints."""
    if native_on:
        monkeypatch.delenv("SWARM_NATIVE_COMMIT", raising=False)
    else:
        monkeypatch.setenv("SWARM_NATIVE_COMMIT", "0")
    store = MemoryStore()
    tmp = rn = None
    if route == "raft":
        from swarmkit_tpu.state.raft import (
            LocalNetwork, RaftLogger, RaftNode,
        )
        import time as _time
        tmp = tempfile.mkdtemp(prefix="colcommit-")
        rn = RaftNode("m0", ["m0"], store,
                      RaftLogger(os.path.join(tmp, "m0")), LocalNetwork(),
                      tick_interval=0.005)
        store._proposer = rn
        rn.start()
        deadline = _time.monotonic() + 15
        while not (rn.is_leader and rn.core.leader_ready):
            assert _time.monotonic() < deadline, "no leader"
            _time.sleep(0.005)
    per_item = store.queue.subscribe()
    block_aware = store.queue.subscribe(accepts_blocks=True)
    try:
        _drive_workload(store)
        snap = store.save_bytes()
        items = [_event_key(e) for e in per_item.drain()]
        blocks = [_event_key(e) for e in block_aware.drain()]
        replay = [_event_key(e) for e in store.changes_between(0)]
        return snap, items, blocks, replay
    finally:
        if rn is not None:
            rn.stop()
        if tmp is not None:
            shutil.rmtree(tmp, ignore_errors=True)


@pytest.mark.parametrize("route", ["standalone", "raft"])
def test_byte_identity_across_native_modes(route, frozen_clock,
                                           monkeypatch):
    """Snapshot bytes, per-subscriber watch streams (per-item AND
    block-aware), and resume replays must be byte-identical between the
    native commit plane and the pure-Python oracle, on both raft
    routes."""
    _require_native()
    snap_n, items_n, blocks_n, replay_n = _run_plane(
        True, route, monkeypatch)
    mtypes.set_time_source(None)   # re-freeze identically for run 2

    t = [1_000_000.0]

    def tick():
        t[0] += 0.001
        return t[0]
    mtypes.set_time_source(tick)
    snap_p, items_p, blocks_p, replay_p = _run_plane(
        False, route, monkeypatch)
    assert snap_n == snap_p
    assert items_n == items_p
    assert blocks_n == blocks_p
    assert replay_n == replay_p
    assert any(k[0] == "delete" for k in items_n)
    # resume tokens: every replayed event carries an exact version stamp
    versions = [k[1] for k in replay_n if k[0] == "update"]
    assert versions == sorted(versions) and versions


def test_follower_apply_differential(frozen_clock, monkeypatch):
    """apply_store_actions over binary-decoded blocks: the native
    follower apply and the Python loop must converge followers
    bit-for-bit (snapshot bytes, streams, by_node bucket order)."""
    _require_native()

    def build_leaderish():
        store = MemoryStore()
        nodes = [_mk_node(f"n{i}") for i in range(3)]
        tasks = [Task(id=f"task-{i:04d}", service_id="svc", slot=i + 1,
                      desired_state=TaskState.RUNNING,
                      status=TaskStatus(state=TaskState.PENDING))
                 for i in range(25)]

        def setup(tx):
            for n in nodes:
                tx.create(n)
            for t in tasks:
                tx.create(t)
        store.update(setup)
        return store, nodes, tasks

    # one canonical entry stream produced by a "leader"
    leader, nodes, tasks = build_leaderish()
    action = TaskBlockAction(
        "task_block", tuple(t.id for t in tasks),
        tuple(nodes[i % 3].id for i in range(len(tasks))),
        leader.version, int(TaskState.ASSIGNED), "assigned", 123.25)
    entry = serde.actions_to_entry_data([action])
    assert entry[:4] == serde.BLOCK_ENTRY_MAGIC

    def follower_state(native_on):
        if native_on:
            monkeypatch.delenv("SWARM_NATIVE_COMMIT", raising=False)
        else:
            monkeypatch.setenv("SWARM_NATIVE_COMMIT", "0")
        store, _nodes, _tasks = build_leaderish()
        sub = store.queue.subscribe()
        store.apply_store_actions(serde.entry_to_actions(entry))
        stream = [_event_key(e) for e in sub.drain()]
        buckets = {nid: list(b)
                   for nid, b in store._tables["tasks"].by_node.items()}
        return store.save_bytes(), stream, buckets, store.version

    mtypes.set_time_source(None)
    t = [2_000_000.0]
    mtypes.set_time_source(lambda: (t.__setitem__(0, t[0] + 0.001)
                                    or t[0]))
    sn, st_n, bk_n, vn = follower_state(True)
    mtypes.set_time_source(None)
    t = [2_000_000.0]
    mtypes.set_time_source(lambda: (t.__setitem__(0, t[0] + 0.001)
                                    or t[0]))
    sp, st_p, bk_p, vp = follower_state(False)
    assert sn == sp and st_n == st_p and vn == vp
    assert bk_n == bk_p
    for nid in bk_n:
        assert bk_n[nid] == bk_p[nid]   # insertion order preserved


def test_follower_apply_diverged_falls_back(frozen_clock):
    """A block naming an unknown id (diverged follower) must take the
    Python slow path: skipped ids burn their version indices and the
    applied remainder publishes per-item events with exact stamps."""
    _require_native()
    store = MemoryStore()
    store.update(lambda tx: tx.create(_mk_node("n0")))
    tasks = [Task(id=f"task-{i}", service_id="svc", slot=i + 1,
                  status=TaskStatus(state=TaskState.PENDING))
             for i in range(3)]
    store.update(lambda tx: [tx.create(t) for t in tasks] and None)
    base = store.version
    action = TaskBlockAction(
        "task_block", (tasks[0].id, "ghost", tasks[2].id),
        ("node-n0", "node-n0", "node-n0"), base,
        int(TaskState.ASSIGNED), "assigned", 1.0)
    sub = store.queue.subscribe()
    store.apply_store_actions([action])
    events = [e for e in sub.drain() if isinstance(e, Event)]
    assert [e.version for e in events] == [base + 1, base + 3]
    assert store.version == base + 3


# ---------------------------------------------------------------------------
# sim: the raft_cp plane rides the columnar commit end to end
# ---------------------------------------------------------------------------

def test_sim_scenario_deterministic_with_native_commit_plane():
    """fused-differential-churn under the native columnar commit plane:
    green, re-run byte-identical, and the coverage line proving a binary
    block rode consensus with native decode active is in the trace."""
    _require_native()
    import logging
    logging.disable(logging.CRITICAL)
    from swarmkit_tpu.sim.scenario import run_scenario
    # warm run: jit signatures compile once per process; a cold run's
    # one-off plan.compile spans would break byte-identity against the
    # warm re-run (preemption-storm discipline)
    run_scenario("fused-differential-churn", seed=11)
    r1 = run_scenario("fused-differential-churn", seed=11,
                      keep_trace=True)
    assert r1.ok, r1.violations
    assert any("fault native-commit-plane store" in line
               for line in r1.trace), \
        "the native columnar commit plane never carried a block"
    r2 = run_scenario("fused-differential-churn", seed=11)
    assert r2.trace_hash == r1.trace_hash
    assert r2.obs_trace_sha256 == r1.obs_trace_sha256


@pytest.mark.slow
def test_sim_columnar_commit_wide_sweep():
    """Acceptance sweep (satellite 4): 20 seeds of the raft_cp
    differential scenario under the columnar commit plane, all green
    with the native-commit coverage cell filled, byte-identical re-runs
    for sampled seeds."""
    _require_native()
    import logging
    logging.disable(logging.CRITICAL)
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import chaos_sweep
    from swarmkit_tpu.sim.scenario import run_scenario
    run_scenario("fused-differential-churn", 0)   # warm jit signatures
    reports = chaos_sweep.sweep(("fused-differential-churn",),
                                n_seeds=20)
    out = chaos_sweep.verdict(reports, ("fused-differential-churn",),
                              20, 0)
    assert out["ok"], json.dumps(
        {"failures": out["failures"],
         "uncovered": out["coverage"]["uncovered"]}, indent=2)
    assert out["coverage"]["matrix"]["native-commit-plane"]["store"] > 0
    by_seed = {r.seed: r for r in reports}
    for seed in (0, 7, 13):
        r2 = run_scenario("fused-differential-churn", seed,
                          keep_trace=True)
        assert r2.trace_hash == by_seed[seed].trace_hash, seed
        assert r2.obs_trace_sha256 == by_seed[seed].obs_trace_sha256, \
            seed


@pytest.mark.slow
def test_sim_columnar_commit_hashseed_independent():
    """Byte-identical across PYTHONHASHSEED with the native commit
    plane on: hash-ordered containers must not leak into the columnar
    encode/decode/fan-out order."""
    code = ("from swarmkit_tpu.sim.scenario import run_scenario;"
            "r = run_scenario('fused-differential-churn', 0);"
            "print(r.trace_hash, r.obs_trace_sha256, r.ok)")
    outs = []
    for hs in ("0", "31337"):
        env = dict(os.environ, PYTHONHASHSEED=hs, JAX_PLATFORMS="cpu")
        env.pop("SWARM_NATIVE_COMMIT", None)
        p = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                           env=env, capture_output=True, text=True,
                           timeout=600)
        assert p.returncode == 0, p.stderr[-2000:]
        outs.append(p.stdout.strip().splitlines()[-1])
    assert outs[0] == outs[1], outs
    assert outs[0].endswith("True"), outs
