"""Flight recorder, health/SLO plane, and compile observability.

Covers this PR's acceptance surface:
* ring-buffer eviction bounds (the black box stays bounded, evictions
  are counted);
* post-mortem determinism — a sim scenario with an injected invariant
  violation dumps a flight-recorder JSON whose sha256 is identical
  across two runs of the same seed, with the violation visible in
  context (spans + store events + raft transitions around it);
* health-check state machine: pass -> warn -> fail -> recover, with
  transitions logged and ``swarm_health{check=...}`` gauges exported;
* DebugServer: ``/`` serves an endpoint index, ``/debug/health``
  returns 503 (not 200) while any check fails, ``/debug/flightrec``
  serves the dump;
* compile counters: a second same-bucket planner call records zero new
  compiles (cache misses are observed via jit cache size, not timing);
* metric hygiene: every live registry name matches the exposition
  grammar with sorted, bounded-cardinality labels.
"""

import functools
import json
import os
import re
import sys
import urllib.request

from swarmkit_tpu.obs import Check, HealthEvaluator, flightrec
from swarmkit_tpu.obs.flightrec import FlightRecorder, Ring
from swarmkit_tpu.obs.health import FAIL, PASS, WARN, timer_p99
from swarmkit_tpu.utils.metrics import Registry

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


# ---------------------------------------------------------------- ring buffer

def test_ring_eviction_bounds():
    ring = Ring(maxlen=8)
    for i in range(20):
        ring.append(i)
    assert len(ring) == 8
    assert ring.items() == list(range(12, 20))   # oldest evicted first
    assert ring.dropped == 12
    ring.clear()
    assert len(ring) == 0 and ring.dropped == 0

    # the recorder's rings honor their configured bounds end to end
    rec = FlightRecorder(max_notes=4, max_raft=2)
    rec.enabled = True
    for i in range(10):
        rec.note(f"n{i}")
        rec.record_raft("m0", "leader", i)
    assert len(rec.notes) == 4 and rec.notes.dropped == 6
    assert len(rec.raft) == 2
    doc = json.loads(rec.dump_json())
    assert len(doc["notes"]) == 4
    assert doc["dropped"]["notes"] == 6

    # disabled recorder records nothing
    rec2 = FlightRecorder()
    rec2.note("ghost")
    rec2.record_raft("m0", "leader", 1)
    assert len(rec2.notes) == 0 and len(rec2.raft) == 0


def test_save_restore_survives_reset():
    """An embedded capture (the sim runner) must not destroy the
    embedder's black box: reset() rebinds fresh rings, so the state
    captured by save_state survives and restore_state brings the
    original history back."""
    rec = FlightRecorder()
    rec.enabled = True
    rec.note("embedder history")
    saved = rec.save_state()
    rec.reset(deterministic=True)
    rec.note("sim capture")
    assert [m for _, m in rec.notes.items()] == ["sim capture"]
    rec.restore_state(saved)
    assert [m for _, m in rec.notes.items()] == ["embedder history"]
    assert rec.deterministic is False


# ----------------------------------------------------- post-mortem determinism

def _durability_bug_scenario(sim):
    """A seeded invariant violation: a follower crashes losing acked WAL
    records (the missing-fsync bug), then a flipped partition lets the
    amnesiac half commit divergent entries at the lost indices — the
    committed-ledger checker must fire (same recipe as
    tests/test_sim.py::test_checker_detects_seeded_durability_bug, as a
    runner scenario so the post-mortem path engages)."""
    eng = sim.engine
    sim.start_raft_workload(interval=0.5)
    sim.cp.create_tasks(4)

    def strike():
        lead = sim.leader()
        if lead is None:
            eng.after(1.0, "await leader", strike)
            return
        iso, keeper = [m for m in sim.managers if m is not lead]
        sim.net.split([iso.id], [lead.id, keeper.id])

        def burst():
            for i in range(12):
                sim.propose(f"critical-{i:02d}".encode())

            def bug():
                keeper.crash(truncate_wal=10)
                keeper.restart()
                sim.net.split([lead.id], [iso.id, keeper.id])

            eng.after(2.0, "durability bug", bug)

        eng.after(2.0, "burst", burst)

    eng.at(eng.clock.start + 5.0, "strike", strike)
    return 30.0


def test_flightrec_dump_deterministic_per_seed(tmp_path):
    from swarmkit_tpu.sim.scenario import SCENARIOS, run_scenario

    SCENARIOS["_durability-bug"] = _durability_bug_scenario
    try:
        d1, d2 = tmp_path / "a", tmp_path / "b"
        d1.mkdir(), d2.mkdir()
        r1 = run_scenario("_durability-bug", seed=5, flightrec_dir=str(d1))
        r2 = run_scenario("_durability-bug", seed=5, flightrec_dir=str(d2))
    finally:
        del SCENARIOS["_durability-bug"]

    # the violation fired and the post-mortem was written automatically
    assert not r1.ok
    assert any("no-committed-entry-loss" in v for v in r1.violations)
    assert r1.flightrec_path and os.path.exists(r1.flightrec_path)
    assert "flightrec_path" in r1.to_dict()

    # identity: same seed => same sha, byte for byte
    assert r1.flightrec_sha256 == r2.flightrec_sha256
    with open(r1.flightrec_path) as fa, open(r2.flightrec_path) as fb:
        assert fa.read() == fb.read()

    # the dump is evidence, not a verdict: the violation note sits next
    # to surrounding state — spans, store events, raft role history,
    # and delta-based metric samples, all under virtual time
    doc = json.load(open(r1.flightrec_path))
    assert any("INVARIANT no-committed-entry-loss" in msg
               for _, msg in doc["notes"])
    assert doc["spans"], "recent spans must be captured"
    assert doc["store_events"], "store events must be captured"
    roles = {role for _, _, role, _ in doc["raft_transitions"]}
    assert "leader" in roles and "candidate" in roles
    assert doc["samples"], "periodic metric samples must be captured"
    # deterministic captures never embed live wall-clock registry totals
    assert "counters" not in doc

    # a clean run of a clean scenario writes no post-mortem
    r3 = run_scenario("crash-leader-mid-commit", seed=7,
                      flightrec_dir=str(tmp_path))
    assert r3.ok and r3.flightrec_path == ""


# --------------------------------------------------------------- health plane

def test_health_state_transitions():
    reg = Registry()
    rec = FlightRecorder()
    rec.enabled = True
    check = Check("latency_p99", timer_p99("swarm_x_latency"),
                  warn=1.0, fail=5.0, unit="s",
                  window_prefixes=("swarm_x_",))
    hev = HealthEvaluator(registry=reg, recorder=rec, checks=[check])

    # no data => pass (a fresh process is healthy, not unknown)
    assert hev.evaluate() == {"latency_p99": PASS}
    t = reg.timer("swarm_x_latency")
    t.observe(0.1)
    assert hev.evaluate() == {"latency_p99": PASS}
    assert reg.gauges['swarm_health{check="latency_p99"}'] == 0

    t.observe(2.0)          # p99 -> 2.0 >= warn
    assert hev.evaluate() == {"latency_p99": WARN}
    assert reg.gauges['swarm_health{check="latency_p99"}'] == 1

    t.observe(10.0)         # p99 -> 10.0 >= fail
    assert hev.evaluate() == {"latency_p99": FAIL}
    assert hev.failing() and hev.status() == FAIL
    assert reg.gauges['swarm_health{check="latency_p99"}'] == 2

    t.reset()
    t.observe(0.1)          # recovered
    assert hev.evaluate() == {"latency_p99": PASS}
    assert not hev.failing() and hev.status() == PASS
    assert reg.gauges['swarm_health{check="latency_p99"}'] == 0

    # the full transition history was tracked and noted to the recorder
    edges = [(a, b) for _, _, a, b in hev.transitions]
    assert edges == [(PASS, WARN), (WARN, FAIL), (FAIL, PASS)]
    notes = [msg for _, msg in rec.notes.items()]
    assert any("warn -> fail" in n for n in notes)

    # report carries the offending window for non-pass checks
    t.observe(10.0)
    rec.record_sample({"t": 1.0,
                       "counters": {"swarm_x_latency_seen": 1},
                       "timer_counts": {"swarm_x_latency": 3}})
    report = hev.report()
    assert report["status"] == FAIL
    entry = report["checks"]["latency_p99"]
    assert entry["state"] == FAIL and entry["value"] == 10.0
    assert entry["window"], "failing check must carry its sample window"
    assert report["transitions"][-1]["to"] == FAIL


# ----------------------------------------------------------------- debug http

def _get(addr, path):
    url = f"http://{addr[0]}:{addr[1]}{path}"
    try:
        with urllib.request.urlopen(url) as resp:
            return resp.status, resp.read().decode()
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode()


def test_debug_server_index_health_and_flightrec():
    from swarmkit_tpu.utils.httpdebug import DebugServer

    reg = Registry()
    check = Check("latency_p99", timer_p99("swarm_x_latency"),
                  warn=1.0, fail=5.0)
    hev = HealthEvaluator(registry=reg, recorder=FlightRecorder(),
                          checks=[check])
    srv = DebugServer(health_evaluator=hev)
    srv.start()
    try:
        # index page lists every registered endpoint
        code, body = _get(srv.addr, "/")
        assert code == 200
        for path in ("/metrics", "/healthz", "/debug/stacks",
                     "/debug/trace", "/debug/health",
                     "/debug/flightrec"):
            assert path in body, body

        # healthy: 200 with a JSON report
        code, body = _get(srv.addr, "/debug/health")
        assert code == 200
        report = json.loads(body)
        assert report["status"] == PASS
        assert report["checks"]["latency_p99"]["state"] == PASS

        # failing: 503 so probes need no JSON parsing
        reg.timer("swarm_x_latency").observe(30.0)
        code, body = _get(srv.addr, "/debug/health")
        assert code == 503
        assert json.loads(body)["status"] == FAIL

        # recovery flips it back
        reg.timer("swarm_x_latency").reset()
        code, _ = _get(srv.addr, "/debug/health")
        assert code == 200

        # the flight recorder dump is served as JSON
        code, body = _get(srv.addr, "/debug/flightrec")
        assert code == 200
        doc = json.loads(body)
        assert {"spans", "samples", "store_events", "raft_transitions",
                "notes", "dropped"} <= set(doc)

        # unknown paths still 404
        code, _ = _get(srv.addr, "/debug/nope")
        assert code == 404
    finally:
        srv.stop()


# ----------------------------------------------------------- compile counters

def test_compile_counter_zero_on_second_same_bucket_call():
    """A planner call through a FRESH jit records exactly the compiles
    the XLA cache reports; a second call on the same static shape bucket
    records zero — so the per-bucket counts separate "compiled in the
    measured window" from "ran warm", which timing alone cannot."""
    import jax

    from swarmkit_tpu.ops import TPUPlanner
    from swarmkit_tpu.ops.kernel import plan_group
    from swarmkit_tpu.utils.metrics import registry
    from test_scheduler import tick_one_service

    @functools.partial(jax.jit, static_argnames=("L",))
    def fresh_plan_fn(nodes, group, L, hier=()):
        return plan_group(nodes, group, L, hier=hier)

    def run_once():
        planner = TPUPlanner(plan_fn=fresh_plan_fn)
        planner.enable_small_group_routing = False
        tick_one_service(planner, n_nodes=64, n_tasks=256)

    def compile_counts():
        return registry.counters_snapshot("swarm_planner_compiles")

    snap0 = compile_counts()
    run_once()
    snap1 = compile_counts()
    first = {k: v - snap0.get(k, 0.0) for k, v in snap1.items()}
    first = {k: v for k, v in first.items() if v}
    assert first, "first call on a fresh jit must record a compile"
    (bucket_key,) = first
    assert re.match(
        r'^swarm_planner_compiles\{bucket="nb\d+_cc\d+_p\d+_L\d+_h\d+"\}$',
        bucket_key), bucket_key

    run_once()
    snap2 = compile_counts()
    second = {k: v - snap1.get(k, 0.0) for k, v in snap2.items()}
    assert not any(second.values()), \
        f"second same-bucket call must record zero new compiles: {second}"


# ------------------------------------------------------------- metric hygiene
#
# The name-grammar / sorted-labels / static-labelset lint moved to
# swarmlint (swarmkit_tpu/analysis/rules/metrics.py, rule
# `metric-hygiene`): it now checks every registry call site in SOURCE,
# including names only emitted on rare error paths, instead of whatever
# a test run happened to populate.  What stays here is the part only a
# live process can check: runtime-interpolated label VALUES — their
# cardinality fan-out (the static rule sees one placeholder labelset
# per f-string) and that they parse back out of the exposition.

_MAX_LABEL_CARDINALITY = 64


def test_live_exposition_parses_and_cardinality_bounded():
    """After a sim run, the exposition built from the live registry —
    real interpolated label values included — must parse line by line,
    and no base name may fan out past the cardinality bound (an
    unbounded label value bloats exposition and flight-recorder dumps;
    the static grammar lint cannot see runtime values)."""
    from swarmkit_tpu.sim.scenario import run_scenario
    from swarmkit_tpu.utils.metrics import registry

    r = run_scenario("crash-leader-mid-commit", seed=3)
    assert r.ok, r.violations

    names = (list(registry.counters_snapshot())
             + list(registry.gauges_snapshot())
             + list(registry.timers_snapshot()))
    assert names, "the run must have populated the registry"
    cardinality = {}
    for name in names:
        base, _, rest = name.partition("{")
        if rest:
            cardinality.setdefault(base, set()).add(rest)
    for base, labelsets in cardinality.items():
        assert len(labelsets) <= _MAX_LABEL_CARDINALITY, \
            f"{base} has {len(labelsets)} label combinations " \
            f"(> {_MAX_LABEL_CARDINALITY}): unbounded label value?"
    expo = registry.expose()
    line_re = re.compile(
        r'^[a-z0-9_]+(\{[a-z0-9_]+="[^"]*"(,[a-z0-9_]+="[^"]*")*\})? '
        r"-?[0-9.e+-]+$")
    for line in expo.strip().split("\n"):
        assert line_re.match(line), f"unparseable exposition line: {line}"
