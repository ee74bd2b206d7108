#!/usr/bin/env bash
# CI gate: static analysis first (cheap, catches convention drift with
# exact file:line messages), then the tier-1 test suite from ROADMAP.md.
# Exit nonzero on new swarmlint findings, stale/unjustified baseline
# entries, or any tier-1 failure.
set -o pipefail

cd "$(dirname "$0")/.."

echo "== native hotpath freshness (hash check + rebuild) =="
# the committed .so must match the committed hotpath.c: rebuild when the
# source hash stamp disagrees, and FAIL if it still disagrees afterwards
# (a stale .so silently serving old semantics is a correctness bug, not
# a perf nit — the commit plane's fallback counter would hide it)
SRC_SHA=$(sha256sum swarmkit_tpu/native/hotpath.c | cut -d' ' -f1)
STAMP_FILE=swarmkit_tpu/native/_hotpath.src.sha256
if [ "$(cat "$STAMP_FILE" 2>/dev/null | tr -d '[:space:]')" != "$SRC_SHA" ]; then
    echo "stale or missing native stamp; rebuilding _hotpath"
    (cd swarmkit_tpu/native && python build.py) >/dev/null 2>&1
fi
if [ "$(cat "$STAMP_FILE" 2>/dev/null | tr -d '[:space:]')" != "$SRC_SHA" ]; then
    echo "FAIL: _hotpath .so is stale vs hotpath.c and rebuild did not fix it"
    exit 1
fi

echo
echo "== swarmlint (scripts/swarmlint.py) =="
python scripts/swarmlint.py || exit 1

echo
echo "== chaos sweep, fast subset (scripts/chaos_sweep.py --fast) =="
# 3 seeds x (rolling-upgrade-chaos + preemption-storm): real rolling
# updates (pause / rollback / failover handoff) and priority preemption
# under partition+churn, invariants + coverage gate.  The 20-seed
# default-suite sweep and long-soak run in the slow tier
# (tests/test_update_chaos.py / test_preemption.py -m slow).
timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python scripts/chaos_sweep.py --fast --quiet > /tmp/_chaos_fast.json \
    || { cat /tmp/_chaos_fast.json; exit 1; }

echo
echo "== tier-1 tests (ROADMAP.md) =="
rm -f /tmp/_t1.log
timeout -k 10 870 env JAX_PLATFORMS=cpu python -m pytest tests/ -q \
    -m 'not slow' --continue-on-collection-errors -p no:cacheprovider \
    -p no:xdist -p no:randomly 2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log \
    | tr -cd . | wc -c)
exit $rc
