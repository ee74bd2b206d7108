"""swarmlint: the linter lints the tree, and the linter itself is linted.

Four layers of protection:

* **tree run** — the full rule suite over the real tree must be clean
  (only baselined/suppressed findings), i.e. exactly what
  ``scripts/swarmlint.py`` enforces in CI;
* **checker sensitivity** — for every rule, a fixture snippet that MUST
  fire and a corrected twin that MUST pass (same philosophy as the
  sim's invariant-sensitivity tests: an invariant you've never seen
  fire is a no-op);
* **baseline ratchet** — the committed grandfather list may only
  shrink: a hard entry cap (lower it when you fix one, never raise it),
  a justification on every entry, and stale-entry rejection;
* **suppression audit** — every ``# swarmlint: disable=`` comment in
  the tree names a real rule (typos must fail, not silently disable).
"""

import json
import os
import subprocess
import sys

import pytest

from swarmkit_tpu.analysis import (
    Baseline, BaselineEntry, DEFAULT_BASELINE, DEFAULT_ROOTS, ModuleInfo,
    checker_names, lint_tree, make_checkers)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(REPO, "tests", "fixtures", "swarmlint")

# The baseline RATCHET: this number may only go DOWN (to the new entry
# count) when a grandfathered finding is fixed.  Raising it to admit a
# new violation is exactly what this test exists to block — add a
# justified per-line suppression or fix the code instead.
MAX_BASELINE_ENTRIES = 4

#: rule -> (bad fixture, good fixture, relpath the harness lints them as)
FIXTURES = {
    "determinism-seam": ("determinism_bad.py", "determinism_good.py",
                         "swarmkit_tpu/state/fixture.py"),
    "epoch-fencing": ("fencing_bad.py", "fencing_good.py",
                      "swarmkit_tpu/manager/fixture.py"),
    "lock-discipline": ("locking_bad.py", "locking_good.py",
                        "swarmkit_tpu/state/fixture.py"),
    "layering": ("layering_bad.py", "layering_good.py",
                 "swarmkit_tpu/ops/fixture.py"),
    "device-path-purity": ("device_bad.py", "device_good.py",
                           "swarmkit_tpu/ops/fixture.py"),
    "metric-hygiene": ("metrics_bad.py", "metrics_good.py",
                       "swarmkit_tpu/obs/fixture.py"),
    "backpressure-discipline": ("backpressure_bad.py",
                                "backpressure_good.py",
                                "swarmkit_tpu/manager/fixture.py"),
}


def _run_rule(rule, fixture, relpath):
    with open(os.path.join(FIXDIR, fixture), encoding="utf-8") as f:
        source = f.read()
    checker = make_checkers([rule])[0]
    mod = ModuleInfo.from_source(source, relpath)
    findings = list(checker.check(mod)) + list(checker.finalize())
    return [f for f in findings if not mod.suppressed(f)]


def test_every_rule_has_a_fixture_pair():
    assert set(FIXTURES) == set(checker_names()), \
        "each rule needs a firing fixture and a clean twin"


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_fires_on_bad_fixture(rule):
    bad, _good, relpath = FIXTURES[rule]
    findings = _run_rule(rule, bad, relpath)
    assert findings, f"{rule} did not fire on {bad}: dead checker"
    assert all(f.rule == rule for f in findings)


@pytest.mark.parametrize("rule", sorted(FIXTURES))
def test_rule_passes_clean_twin(rule):
    _bad, good, relpath = FIXTURES[rule]
    findings = _run_rule(rule, good, relpath)
    assert not findings, \
        f"{rule} false-positives on its clean twin {good}:\n" \
        + "\n".join(f.render() for f in findings)


# Per-rule sensitivity floors: the bad fixtures each pack several
# distinct violation shapes; a refactor that quietly narrows a rule to
# one shape must fail here, not in review.
@pytest.mark.parametrize("rule,min_findings", [
    ("determinism-seam", 10),  # time.time/monotonic/uuid4/urandom/Random/
    #                            random.random + the threaded-supervisor
    #                            shape (2 bare wall-clock reads pacing a
    #                            rollout monitor window — ISSUE 8) + the
    #                            learned-scorer weight-loading shapes
    #                            (ISSUE 15): unseeded
    #                            numpy.random.default_rng() + a global
    #                            numpy RNG draw random-initing weights
    ("epoch-fencing", 4),      # 3 unfenced calls + 1 fencing-blind def
    ("lock-discipline", 5),    # order cycle + 2 blocking-under-lock +
    #                            read_barrier under the view lock
    #                            (ISSUE 11 follower-read shape) +
    #                            GIL-released native fan-out under the
    #                            writer lock (ISSUE 13 commit plane)
    ("layering", 4),           # state/manager/sim/orchestrator imports
    ("device-path-purity", 20),  # float()/np./jax.debug/.item() + the
    #                              fused shapes: np/.item() in a scan
    #                              step, mid-program device_get,
    #                              block_until_ready in a mesh kernel +
    #                              the preempt-kernel shapes (ISSUE 10):
    #                              np.cumsum/int() in the pick scan,
    #                              picks fetched mid-program + the
    #                              donation shapes (ISSUE 14): host
    #                              read of a resident array inside the
    #                              donated update program, 2x reuse of
    #                              a donated buffer after dispatch + the
    #                              strategy-kernel shapes (ISSUE 15):
    #                              numpy sort in the score stage, D2H
    #                              float() cast on a traced score + the
    #                              unaccounted-transfer shapes (ISSUE
    #                              18): host device_put with no ledger
    #                              call, host block_until_ready fetch
    #                              with no ledger call + the
    #                              cross-shard shapes (ISSUE 19):
    #                              mid-chunk device_get of a carry that
    #                              feeds a later dispatch, re-put of an
    #                              already-resident sharded array
    ("metric-hygiene", 7),     # bad chars/unsorted/duplicate/upper key
    #                            + the metric-cardinality shapes
    #                            (ISSUE 17): per-entity task= / node_id=
    #                            / session= label keys, one series per
    #                            entity
    ("backpressure-discipline", 4),  # ISSUE 20 overload plane: RPC-edge
    #                            list.append, heartbeat residue into an
    #                            unbounded deque, heappush admission
    #                            wheel, scheduler _enqueue batch extend
    #                            — each without a declared bound or a
    #                            counted shed
])
def test_rule_sensitivity_floor(rule, min_findings):
    bad, _good, relpath = FIXTURES[rule]
    findings = _run_rule(rule, bad, relpath)
    assert len(findings) >= min_findings, \
        f"{rule} found {len(findings)} < {min_findings} on {bad}: " \
        "the checker lost coverage\n" \
        + "\n".join(f.render() for f in findings)


# ------------------------------------------------------------- tree run

def test_tree_is_clean():
    """The full rule suite over the real tree: no new findings, no
    stale or unjustified baseline entries, no parse errors."""
    result = lint_tree(REPO)
    assert set(result.rules) == set(checker_names())
    assert len(result.modules) > 100, "tree walk lost most of the repo?"
    assert result.ok, "swarmlint found new violations:\n" \
        + "\n".join(f.render() for f in result.new) \
        + "".join(f"\nstale baseline: {e.to_dict()}" for e in result.stale) \
        + "".join(f"\nunjustified: {e.to_dict()}"
                  for e in result.unjustified)


# ------------------------------------------------------- baseline ratchet

def test_baseline_only_shrinks():
    bl = Baseline.load(os.path.join(REPO, DEFAULT_BASELINE))
    assert len(bl.entries) <= MAX_BASELINE_ENTRIES, \
        f"baseline grew to {len(bl.entries)} entries " \
        f"(cap {MAX_BASELINE_ENTRIES}): the grandfather list only " \
        "shrinks — fix the code or add a justified per-line suppression"
    for e in bl.entries:
        assert e.justification.strip(), \
            f"baseline entry {e.key()} has no justification"
        assert e.rule in checker_names(), \
            f"baseline entry names unknown rule {e.rule!r}"


def test_stale_baseline_entry_is_an_error():
    """Fixing a violation must force its baseline entry out: a synthetic
    entry matching nothing shows up as stale and fails the run."""
    bl = Baseline([BaselineEntry(
        rule="determinism-seam", path="swarmkit_tpu/nonexistent.py",
        code="t = time.time()", justification="synthetic")])
    new, old, stale = bl.split([])
    assert stale and stale[0].path == "swarmkit_tpu/nonexistent.py"


def test_baseline_matching_is_count_aware():
    """One entry absorbs exactly ONE occurrence: pasting a textually
    identical violation elsewhere in the file is a NEW finding, not a
    free ride on the grandfathered line."""
    from swarmkit_tpu.analysis.core import Finding

    entry = BaselineEntry(rule="determinism-seam",
                          path="swarmkit_tpu/state/store.py",
                          code="t0 = time.monotonic()",
                          justification="grandfathered")
    bl = Baseline([entry])
    f = lambda line: Finding(rule="determinism-seam",
                             path="swarmkit_tpu/state/store.py",
                             line=line, col=0, message="m",
                             code="t0 = time.monotonic()")
    new, old, stale = bl.split([f(85), f(900)])   # second: fresh paste
    assert len(old) == 1 and len(new) == 1 and not stale


def test_layering_catches_from_package_import_form():
    """`from swarmkit_tpu import sim` must be flagged exactly like
    `import swarmkit_tpu.sim` — the from-form names the package in the
    imported members, not the module."""
    checker = make_checkers(["layering"])[0]
    mod = ModuleInfo.from_source(
        "from swarmkit_tpu import sim\n"
        "from swarmkit_tpu import manager\n",
        "swarmkit_tpu/ops/fixture.py")
    findings = list(checker.check(mod))
    assert len(findings) == 2, [f.render() for f in findings]


def test_locking_multi_item_with_and_context_expr():
    """`with a, b:` acquires in order (edges between items), and calls
    inside a with's context expression run under the already-held
    locks."""
    checker = make_checkers(["lock-discipline"])[0]
    mod = ModuleInfo.from_source(
        "class MemoryStore:\n"
        "    def one(self):\n"
        "        with self._update_lock, self._lock:\n"
        "            self.apply()\n"
        "    def two(self):\n"
        "        with self._lock:\n"
        "            with self._update_lock:\n"
        "                self.apply()\n"
        "    def three(self, planner, h):\n"
        "        with self._lock, planner.fetch_group(h):\n"
        "            pass\n",
        "swarmkit_tpu/state/fixture.py")
    findings = list(checker.check(mod)) + list(checker.finalize())
    assert any("cycle" in f.message for f in findings), \
        [f.render() for f in findings]
    assert any("fetch_group" in f.message for f in findings), \
        [f.render() for f in findings]


def test_write_baseline_placeholder_still_fails_the_gate(tmp_path):
    """--write-baseline's TODO placeholder must not produce a green
    run: regenerated entries stay failing until a human justifies."""
    from swarmkit_tpu.analysis import write_baseline

    scratch = str(tmp_path / "bl.json")
    r = lint_tree(REPO, roots=("tests/fixtures/swarmlint",),
                  rules=["determinism-seam"], baseline_path=None)
    assert r.new, "fixtures should produce findings to grandfather"
    write_baseline(REPO, r, scratch)
    bl = Baseline.load(scratch)
    assert bl.entries and bl.unjustified() == bl.entries


def test_missing_lint_root_is_an_error():
    """A typo'd root must fail loudly, never lint nothing and pass."""
    from swarmkit_tpu.analysis import iter_source_files

    with pytest.raises(FileNotFoundError):
        iter_source_files(REPO, ("swarmkit_tpu/sate",))


def test_directive_in_string_literal_is_inert():
    """A string literal MENTIONING the directive is neither a
    suppression nor a bad-suppression — only real comments count."""
    from swarmkit_tpu.analysis.runner import run_checkers

    mod = ModuleInfo.from_source(
        "import time\n"
        "MSG = \"add '# swarmlint: disable=bogus-rule' above the line\"\n"
        "t = time.time()  "
        "# a real string: '# swarmlint: disable=determinism-seam'\n",
        "swarmkit_tpu/state/fixture.py")
    assert not mod.suppressions.get(2)
    findings, suppressed, bad = run_checkers(make_checkers(), [mod])
    assert not bad, [f.render() for f in bad]
    # ...but the directive inside a REAL comment (line 3) does suppress
    assert suppressed == 1 and \
        not any(f.rule == "determinism-seam" for f in findings)


def test_metric_hygiene_leading_placeholder_is_unverifiable():
    """f'{prefix}_total' on the registry: the prefix cannot be judged
    statically — must NOT be flagged as outside the namespace."""
    checker = make_checkers(["metric-hygiene"])[0]
    mod = ModuleInfo.from_source(
        "def f(registry, prefix):\n"
        "    registry.counter(f'{prefix}_total')\n",
        "swarmkit_tpu/obs/fixture.py")
    assert not list(checker.check(mod))


def test_metric_hygiene_catches_misprefixed_name_on_registry():
    """A name outside the swarm_ namespace passed to the REAL registry
    is a violation (the namespace contract the old live test enforced);
    the same method name on an unrelated receiver is not."""
    checker = make_checkers(["metric-hygiene"])[0]
    mod = ModuleInfo.from_source(
        "def f(registry, stopwatch):\n"
        "    registry.counter('tasks_total')\n"
        "    registry.counter('Swarm_Bad')\n"
        "    stopwatch.timer('laps')\n",
        "swarmkit_tpu/obs/fixture.py")
    findings = list(checker.check(mod))
    assert len(findings) == 2, [f.render() for f in findings]
    assert all("swarm_ namespace" in f.message
               or "violates" in f.message for f in findings)


# ----------------------------------------------------- suppression audit


def test_suppressions_name_existing_rules():
    """Every directive the LINTER ITSELF parses out of the tree names a
    real rule — using the same parser as enforcement, so the audit and
    the linter can never disagree on a comment's grammar."""
    from swarmkit_tpu.analysis import iter_source_files

    known = set(checker_names()) | {"all"}
    seen = 0
    for rel in iter_source_files(REPO, DEFAULT_ROOTS):
        with open(os.path.join(REPO, rel), encoding="utf-8") as f:
            source = f.read()
        try:
            mod = ModuleInfo.from_source(source, rel)
        except SyntaxError:
            continue
        for lineno, rules in sorted(mod.suppressions.items()):
            for rule in rules:
                seen += 1
                assert rule in known, \
                    f"{rel}:{lineno}: suppression names unknown " \
                    f"rule {rule!r}"
    assert seen >= 1, "expected at least the store/crypto suppressions"


def test_unknown_suppression_is_a_finding():
    """A typo'd suppression is an error in the lint result itself."""
    from swarmkit_tpu.analysis.runner import run_checkers

    mod = ModuleInfo.from_source(
        "import time\n"
        "t = time.time()  # swarmlint: disable=determinsm-seam\n",
        "swarmkit_tpu/state/fixture.py")
    findings, suppressed, bad = run_checkers(make_checkers(), [mod])
    assert any(f.rule == "bad-suppression" for f in bad)
    # and the misspelled suppression did NOT silence the real finding
    assert any(f.rule == "determinism-seam" for f in findings)


def test_subset_runs_ignore_out_of_scope_baseline():
    """A subtree or rule-subset run must not report the out-of-scope
    grandfather entries (store.py determinism-seam) as stale."""
    r = lint_tree(REPO, roots=("swarmkit_tpu/obs",))
    assert r.ok, [f.render() for f in r.new] + \
        [e.to_dict() for e in r.stale]
    r = lint_tree(REPO, rules=["layering"])
    assert r.ok and not r.stale, [e.to_dict() for e in r.stale]


def test_write_baseline_preserves_out_of_scope_entries(tmp_path):
    """--write-baseline on a subtree must keep (not delete) the entries
    for files outside that subtree, justifications included."""
    import shutil

    from swarmkit_tpu.analysis import write_baseline

    scratch = tmp_path / "bl.json"
    shutil.copy(os.path.join(REPO, DEFAULT_BASELINE), scratch)
    before = Baseline.load(str(scratch))
    r = lint_tree(REPO, roots=("swarmkit_tpu/obs",),
                  baseline_path=str(scratch))
    n = write_baseline(REPO, r, str(scratch))
    after = Baseline.load(str(scratch))
    assert n == len(before.entries)
    assert sorted((e.key(), e.justification) for e in after.entries) \
        == sorted((e.key(), e.justification) for e in before.entries)


def test_file_roots_are_normalized():
    """'./scripts/chaos_sweep.py' and 'scripts/chaos_sweep.py' must lint
    identically — whitelists (here ``scripts/``: the file imports the
    simulator) and baseline entries match on the canonical repo-relative
    path."""
    from swarmkit_tpu.analysis import iter_source_files

    root = "./scripts/chaos_sweep.py"
    assert iter_source_files(REPO, (root,)) == ["scripts/chaos_sweep.py"]
    r = lint_tree(REPO, roots=(root,), baseline_path=None)
    assert r.ok, [f.render() for f in r.new]


# ------------------------------------------------------------- CLI smoke

def test_cli_json_smoke():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "swarmlint.py"),
         "--format", "json"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    payload = json.loads(out.stdout)
    assert payload["ok"] is True
    assert payload["findings"] == []
    assert set(payload["rules"]) == set(checker_names())


def test_cli_rule_subset_and_paths():
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "swarmlint.py"),
         "--rules", "layering", "--baseline", "none", "swarmkit_tpu"],
        capture_output=True, text=True, cwd=REPO, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
