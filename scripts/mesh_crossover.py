"""Mesh crossover curve for the fused planner: N ∈ {1,2,4,8} devices.

Measures the steady-state cost of ONE fused chunk (dispatch + compute +
D2H) at the production tick shape — the cfg6/cfg7 node bucket with a
4-group-slot chunk — on a 1-device program (``plan_fused_jit``) and on
``plan_fused_sharded`` meshes of 2/4/8 devices, each in a fresh
subprocess so XLA_FLAGS / device count / jit caches cannot leak between
points.  The carry round-trips device-resident exactly as the planner
drives it (``ShardedPlanFn.prepare_fused`` NamedShardings for meshes).

Output: one JSON artifact (default MULTICHIP_r07.json) with the
seconds-per-chunk / decisions-per-second curve, the winning N, and
per-point parity checks (every mesh must produce byte-identical
placements to the 1-device program — for the plain chunk AND for a
strategy-mixed chunk cycling spread/binpack/weighted/learned group
strategy ids).  Each point also records the device-ledger H2D bytes
moved during the timed window (~0 once the carry is resident) and the
host-route strategy-group counter delta (must stay 0: no sharded
strategy kernel may fall back to the numpy oracle).  ``bench.py``
embeds the artifact under ``mesh_crossover`` when the file is
present, which is how the curve reaches the bench ledger.

The whole --devices list is validated up front against every node
bucket (n >= 1, bucket divisible by n); infeasible points are recorded
under ``skipped`` with a reason.  A child that fails — too few devices
for its N included — is recorded under ``failed`` and fails the sweep
(exit 1) once the remaining points have run.

One process per chip: this parent never imports JAX, and the children
run one at a time, each holding the devices alone.  Keep it so — a
parent that touched JAX would hold the chip its children need.

Children run on whatever platform JAX finds (the TPU, on a machine
that has one).  Each point records the platform it measured on and the
artifact sets ``host_forced_devices`` when every point saw the cpu, so
a curve from forced host devices cannot pass for a silicon curve.  For
a placement-parity run without a chip, export ``JAX_PLATFORMS=cpu``:
the host-device force flag is injected only then, and the timings of
such a run say nothing about a mesh (forced host devices are slices of
the same cores).  The cost model lives in docs/architecture.md ("Fused
many-service planning & mesh sharding").

Usage:
    python scripts/mesh_crossover.py                 # full curve
    python scripts/mesh_crossover.py --nodes 65536 --repeats 5
    python scripts/mesh_crossover.py --child 4       # (internal)
"""

import argparse
import hashlib
import json
import os
import statistics
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_OUT = os.path.join(REPO, "MULTICHIP_r07.json")


def _child(n_devices: int, nb: int, groups: int, k: int,
           repeats: int) -> None:
    """One measurement point, in an isolated process."""
    sys.path.insert(0, REPO)
    import time

    import jax
    import numpy as np

    from swarmkit_tpu.obs import devicetelemetry as _devtel
    from swarmkit_tpu.ops import fusedbatch
    from swarmkit_tpu.ops.kernel import (
        FusedCarry, FusedGroups, FusedShared, FusedStrategy, fetch_plan,
        plan_fused_jit,
    )
    from swarmkit_tpu.ops.planner import _jit_cache_size
    from swarmkit_tpu.scheduler import strategy as strategy_mod
    from swarmkit_tpu.utils.metrics import registry

    def _host_routed_groups() -> int:
        return sum(v for key, v in registry.counters_snapshot(
            "swarm_strategy_groups").items() if 'route="host"' in key)

    devices = jax.devices()
    if len(devices) < n_devices:
        raise SystemExit(f"need {n_devices} devices, have {len(devices)}")

    rng = np.random.RandomState(0)
    gb = fusedbatch.pow2_bucket(groups)
    sb = fusedbatch.pow2_bucket(groups)   # one service slot per group
    shared = FusedShared(
        valid=np.ones(nb, bool), ready=np.ones(nb, bool),
        os_hash=np.zeros((2, nb), np.int32),
        arch_hash=np.zeros((2, nb), np.int32),
        svc0=rng.randint(0, 4, (sb, nb)).astype(np.int32))
    g = FusedGroups(
        k=np.array([k] * groups + [0] * (gb - groups), np.int32),
        slot=np.arange(gb, dtype=np.int32) % sb,
        maxrep=np.zeros(gb, np.int32),
        cpu_d=np.full(gb, 10 ** 8, np.int64),
        mem_d=np.full(gb, 64 << 20, np.int64),
        con_hash=np.zeros((gb, 1, 2, nb), np.int32),
        con_op=np.full((gb, 1), 2, np.int32),
        con_exp=np.zeros((gb, 1, 2), np.int32),
        plat=np.full((gb, 1, 4), -1, np.int32),
        failures=np.zeros((gb, nb), np.int32),
        leaf=np.zeros((gb, nb), np.int32),
        extra_mask=np.ones((gb, nb), bool))
    carry = FusedCarry(
        total=rng.randint(0, 8, nb).astype(np.int32),
        cpu=np.full(nb, 64 * 10 ** 9, np.int64),
        mem=np.full(nb, 256 << 30, np.int64),
        svc_acc=np.zeros((sb, nb), np.int32))

    # strategy-mixed chunk: group strategy ids cycle spread / binpack /
    # weighted / learned with fixed weighted terms and zero learned
    # params — deterministic, so its placements digest must agree at
    # every N (the ShardedPlanFn.fused route the planner takes)
    f_dim = len(strategy_mod.MLP_FEATURES)
    strat = FusedStrategy(
        sid=(np.arange(gb, dtype=np.int32) % 4),
        weights=np.tile(np.array([3, 1, 0, 0], np.int32), (gb, 1)),
        w1=np.zeros((f_dim, 1), np.int32), b1=np.zeros(1, np.int32),
        w2=np.zeros(1, np.int32), b2=np.zeros((), np.int32))

    with fusedbatch.x64():
        if n_devices == 1:
            import jax.numpy as jnp
            # the device ledger accounts this point's staging the same
            # way the planner's _prepare_fused cold path does
            _devtel.note_h2d("cold_build", _devtel.tree_nbytes(
                (tuple(shared), tuple(carry))))
            sh = FusedShared(*(jnp.asarray(a) for a in shared))
            ca = FusedCarry(*(jnp.asarray(a) for a in carry))
            probe = plan_fused_jit

            def run(ca, strat=None):
                xs, fcs, spills, ca = plan_fused_jit(sh, g, ca, 1,
                                                     strat)
                return fetch_plan((xs, fcs, spills)), ca
        else:
            from swarmkit_tpu.parallel.sharded import (
                ShardedPlanFn, make_mesh, plan_fused_sharded,
            )
            fn = ShardedPlanFn(make_mesh(devices[:n_devices]))
            # ShardedPlanFn._shard accounts the mesh_reshard H2D itself
            sh, ca = fn.prepare_fused(shared, carry)
            probe = plan_fused_sharded

            def run(ca, strat=None):
                xs, fcs, spills, ca = fn.fused(sh, g, ca, 1, strat)
                return fetch_plan((xs, fcs, spills)), ca

        (x0, _, _), _ = run(ca)            # compile + parity sample
        warm_compiles = _jit_cache_size(probe) or 0
        tt0 = _devtel.transfer_totals()
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            _, _ = run(ca)                 # fresh carry each repeat
            times.append(time.perf_counter() - t0)
        tt1 = _devtel.transfer_totals()
        timed_compiles = (_jit_cache_size(probe) or 0) - warm_compiles

        # untimed strategy-mixed dispatch: digest parity across N plus
        # proof no strategy group fell back to the numpy host oracle
        host_before = _host_routed_groups()
        (xs_s, _, _), _ = run(ca, strat)
        strat_fallbacks = _host_routed_groups() - host_before

    def _digest(x):
        return hashlib.sha256(np.ascontiguousarray(
            np.asarray(x).astype(np.int64)).tobytes()).hexdigest()

    med = statistics.median(times)
    print(json.dumps({
        "n_devices": n_devices,
        "chunk_seconds": round(med, 6),
        "chunk_seconds_min": round(min(times), 6),
        "decisions_per_sec": round(groups * k / med),
        "placements_digest": _digest(x0),
        "strategy_placements_digest": _digest(xs_s),
        "strategy_host_fallbacks": strat_fallbacks,
        "placed": int(np.asarray(x0).sum()),
        # per-point device-ledger evidence: bytes moved during the
        # timed repeats (steady-state D2H; H2D must be ~0 — the
        # carry stays device-resident) and the jit signatures this
        # point compiled, with timed-window growth pinned at 0
        "transfer_bytes": {d: tt1[d] - tt0.get(d, 0) for d in tt1},
        "resident_h2d_bytes_timed": tt1["h2d"] - tt0.get("h2d", 0),
        "compiles": warm_compiles,
        "timed_window_compiles": timed_compiles,
        "platform": devices[0].platform,
    }))


def _validate_devices(devices, nodes_list):
    """Whole-sweep feasibility check BEFORE any child runs: every
    requested N must be >= 1 and divide every node bucket (fused
    shards are unpadded so idx tie-keys match the 1-device program).
    Infeasible Ns land in the returned ``skipped`` map with a reason
    and the sweep proceeds over the rest — never dies mid-sweep."""
    valid, skipped = [], {}
    for n in devices:
        if n < 1:
            skipped[str(n)] = "n_devices must be >= 1"
        elif any(nb % n for nb in nodes_list):
            bad = [nb for nb in nodes_list if nb % n]
            skipped[str(n)] = (f"node buckets {bad} not divisible "
                               f"by {n}")
        else:
            valid.append(n)
    return valid, skipped


def _measure_shape(nodes, groups, k, repeats, devices, skipped):
    points = {n: {"skipped": reason} for n, reason in skipped.items()}
    for n in devices:
        env = dict(os.environ)
        # force host devices only when the caller asked for the cpu
        # backend — an accelerator supplies its own device inventory
        flags = env.get("XLA_FLAGS", "")
        if (env.get("JAX_PLATFORMS") == "cpu"
                and "xla_force_host_platform_device_count" not in flags):
            env["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                f"{max(8, n)}").strip()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__),
             "--child", str(n), "--nodes", str(nodes),
             "--groups", str(groups), "--k", str(k),
             "--repeats", str(repeats)],
            cwd=REPO, env=env, capture_output=True, text=True)
        if proc.returncode != 0:
            points[str(n)] = {"failed": proc.stderr[-500:]}
            print(f"nb={nodes} N={n}: child failed: "
                  f"{proc.stderr[-500:]}", file=sys.stderr)
            continue
        points[str(n)] = json.loads(proc.stdout.strip().splitlines()[-1])
        print(f"nb={nodes} N={n}: {points[str(n)]}", file=sys.stderr)

    ok = {n: pt for n, pt in points.items() if "chunk_seconds" in pt}
    digests = {pt["placements_digest"] for pt in ok.values()}
    strat_digests = {pt["strategy_placements_digest"]
                     for pt in ok.values()}
    winner = min(ok, key=lambda n: ok[n]["chunk_seconds"]) if ok else None
    base = ok.get("1", {}).get("chunk_seconds")
    return {
        "shape": {"nodes": nodes, "groups_per_chunk": groups,
                  "tasks_per_group": k},
        "curve": {n: pt.get("chunk_seconds") for n, pt in points.items()},
        "decisions_per_sec": {n: pt.get("decisions_per_sec")
                              for n, pt in points.items()},
        "overhead_x": {n: round(pt["chunk_seconds"] / base, 3)
                       for n, pt in ok.items()} if base else {},
        "placements_equal_across_mesh": len(digests) <= 1,
        "strategy_placements_equal_across_mesh": len(strat_digests) <= 1,
        "strategy_host_fallbacks": sum(
            pt.get("strategy_host_fallbacks", 0) for pt in ok.values()),
        "max_timed_h2d_bytes": max(
            (pt.get("resident_h2d_bytes_timed", 0)
             for pt in ok.values()), default=0),
        "winner_devices": int(winner) if winner else None,
        "points": points,
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="python scripts/mesh_crossover.py")
    p.add_argument("--nodes", type=int, nargs="*",
                   default=[16384, 65536, 131072],
                   help="node buckets to sweep (default: 16384 = the "
                        "cfg6/cfg7 10k-node shape, 65536 = the "
                        "50k-node target shape, 131072 = the 100k+ "
                        "regime where per-shard working sets drop "
                        "back into cache and the mesh crosses over)")
    p.add_argument("--groups", type=int, default=4,
                   help="groups per fused chunk (default 4)")
    p.add_argument("--k", type=int, default=50_000,
                   help="tasks per group (default 50000)")
    p.add_argument("--repeats", type=int, default=9)
    p.add_argument("--devices", type=int, nargs="*",
                   default=[1, 2, 4, 8])
    p.add_argument("--out", default=DEFAULT_OUT)
    p.add_argument("--child", type=int, help=argparse.SUPPRESS)
    args = p.parse_args(argv)

    if args.child is not None:
        _child(args.child, args.nodes[0], args.groups, args.k,
               args.repeats)
        return 0

    valid_devices, skipped = _validate_devices(args.devices, args.nodes)
    for n, reason in skipped.items():
        print(f"skipping N={n}: {reason}", file=sys.stderr)
    shapes = {str(nb): _measure_shape(nb, args.groups, args.k,
                                      args.repeats, valid_devices,
                                      skipped)
              for nb in args.nodes}
    all_parity = all(s["placements_equal_across_mesh"]
                     and s["strategy_placements_equal_across_mesh"]
                     for s in shapes.values())
    platforms = sorted({pt["platform"]
                        for s in shapes.values()
                        for pt in s["points"].values()
                        if "platform" in pt})
    artifact = {
        "metric": "fused planner chunk seconds vs mesh size N",
        "devices_swept": args.devices,
        "skipped": skipped,
        "failed": {nb: failed for nb, s in shapes.items()
                   if (failed := sorted(n for n, pt in s["points"].items()
                                        if "failed" in pt))},
        "shapes": shapes,
        "winner_by_shape": {nb: s["winner_devices"]
                            for nb, s in shapes.items()},
        "placements_equal_across_mesh": all_parity,
        "strategy_host_fallbacks": sum(
            s["strategy_host_fallbacks"] for s in shapes.values()),
        # honest provenance: True only when every point actually ran
        # on forced host-cpu devices — a silicon curve says so
        "platforms": platforms,
        "host_forced_devices": platforms == ["cpu"],
    }
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2, sort_keys=True)
        f.write("\n")
    print(json.dumps(artifact))
    return 0 if all_parity and shapes and valid_devices \
        and not artifact["failed"] else 1


if __name__ == "__main__":
    sys.exit(main())
