"""The bring-up pieces, on the forced CPU at tiny sizes: where the
compile cache goes, the resident scatter's failure counter, and
chip_smoke.py turning every quiet retreat into a non-zero exit."""

import json
import logging
import os
import re
import sys

import jax
import numpy as np
import pytest

from swarmkit_tpu.ops import TPUPlanner, planner as planner_mod
from swarmkit_tpu.ops import streaming
from swarmkit_tpu.scheduler import Scheduler
from swarmkit_tpu.state import MemoryStore
from swarmkit_tpu.utils import compilecache
from swarmkit_tpu.utils.metrics import registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


# ------------------------------------------------------------ compile cache

@pytest.fixture
def cache_dir_config():
    prev = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_left_alone_when_placed_from_outside(
        monkeypatch, tmp_path, cache_dir_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compilecache.ENV_VAR, str(tmp_path))
    assert compilecache.ensure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_defaults_into_the_checkout_whatever_the_cwd(
        monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.delenv(compilecache.ENV_VAR, raising=False)
    first = compilecache.ensure_compile_cache()
    monkeypatch.chdir(tmp_path)
    second = compilecache.ensure_compile_cache()
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


# ------------------------------------------------- scatter failure counter

def test_failed_scatter_is_counted_and_reuploaded(monkeypatch):
    store = MemoryStore()
    store.update(lambda tx: [tx.create(n)
                             for n in chip_smoke.make_nodes(8, seed=0)])
    planner = TPUPlanner()
    sched = Scheduler(store, batch_planner=planner, pipeline_depth=1)
    store.view(sched._setup_tasks_list)
    planner.begin_tick(sched)
    planner.end_tick()
    st = planner._streaming
    info = sched.node_set.nodes["node-00000"]
    info.available_resources.nano_cpus -= 12345
    sched.delta.mark("node-00000")

    def boom(*args):
        raise RuntimeError("scatter refused")
    monkeypatch.setattr(streaming, "_scatter_rows_jit", boom)
    before = registry.get_counter("swarm_streaming_scatter_failures", 0)
    st.refresh(sched)
    assert st.stats["scatter_failures"] == 1
    assert st.snapshot()["scatter_failures"] == 1
    assert registry.get_counter(
        "swarm_streaming_scatter_failures", 0) == before + 1
    # the production guarantee stays: the tier re-uploaded and is right
    row = st.row_of["node-00000"]
    assert int(np.asarray(st.dev[2])[row]) == \
        info.available_resources.nano_cpus


# ------------------------------------------------------------- chip_smoke

def test_smoke_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr().out.strip().splitlines()
    assert out == ["device platform=cpu kind=cpu count=8"]


def test_last_line_is_ok_and_device_and_nothing_else(capsys):
    """What the driver parses: exactly ``ok`` and ``device``; the rest
    of the report rides the ``summary:`` line above it."""
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    smoke = chip_smoke.Smoke()
    assert chip_smoke.verdict(smoke, tpu, {"nodes": 4, "claim": None}) == 0
    *_, summary, last = capsys.readouterr().out.splitlines()
    assert json.loads(last) == {"ok": True, "device": tpu}
    assert summary.startswith("summary: ")
    assert summary.endswith('"claim": null}')

    smoke.fail("served: planner groups_device_error=1")
    capsys.readouterr()
    assert chip_smoke.verdict(smoke, tpu, {"claim": None}) != 0
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "groups_device_error=1" in captured.err


def test_every_program_matches_its_oracle_on_the_cpu():
    smoke = chip_smoke.Smoke()
    chip_smoke.programs_phase(smoke, n_nodes=64, k=512, seed=3)
    assert smoke.failures == []
    assert [row["program"].split("/")[0] for row in smoke.programs] == [
        "_scatter_rows_jit", "gang_fit_jit", "gang_fit_fused_jit",
        "select_victims_jit", "plan_group_jit", "plan_group_jit",
        "plan_strategy_jit", "plan_strategy_jit", "plan_strategy_jit",
        "plan_fused_jit", "feasibility_jit"]
    assert all(row["ok"] for row in smoke.programs)
    assert smoke.exit_code() == 0


def test_forced_retreats_become_a_nonzero_exit(monkeypatch, capsys):
    """A device path that raises (groups_device_error, then a tripped
    breaker) and a disabled native plane: the production path carries
    on and places every task — the smoke must not."""
    def boom(*args, **kwargs):
        raise RuntimeError("device refused")
    monkeypatch.setattr(TPUPlanner, "_call_plan_fn", boom)
    monkeypatch.setattr(TPUPlanner, "_call_strategy_fn", boom)
    monkeypatch.setattr(planner_mod, "plan_fused_jit", boom)
    # every group is worth the device, whatever this host's probe says
    monkeypatch.setattr(TPUPlanner, "_launch_overhead_shared", 1e-9)
    monkeypatch.setenv("SWARM_NATIVE_COMMIT", "0")
    # other test modules switch logging off process-wide at import
    monkeypatch.setattr(logging.root.manager, "disable", logging.NOTSET)
    # and a logger that was asked while it was off remembers the answer
    logging.root.manager._clear_cache()
    monkeypatch.setattr(chip_smoke, "programs_phase",
                        lambda *args: None)
    rc = chip_smoke.run(CPU, n_nodes=64, n_agents=2, replicas=2000,
                        timeout=120.0, timed_buckets=((128, 100, 25),),
                        step_shapes=(("sum", 128, 1),))
    captured = capsys.readouterr()
    assert rc != 0
    assert '"ok"' not in captured.out
    failures = captured.err
    assert "groups_device_error=" in failures
    assert re.search(r"breaker (open|half-open) .*'trips': [1-9]", failures)
    assert "native commit plane" in failures
    assert "retreat logged: tpu-planner" in failures


def test_plan_programs_are_timed_at_each_bucket_beside_their_pass_line(
        capsys):
    """``run_s`` of every plan program, per bucket: the before and after
    of a change to the device programs (tiny buckets here: a count and
    the oracles, no speed; the second has 260 racks, so its tree takes
    the 4,096 leaf bucket as ``harness-100k``'s does)."""
    smoke = chip_smoke.Smoke()
    chip_smoke.plan_program_times(
        smoke, buckets=((128, 100, 25), (2048, 1300, 65)), seed=5)
    assert smoke.failures == []
    # above 256 racks the one-preference program and the fused run at
    # the leaf bucket are timed too (``harness-100k-ha``, PR 36)
    assert [row["program"] for row in smoke.programs] == [
        f"{name}@nb128" for name in (
            "plan_group_jit/flat", "plan_group_jit/hier",
            "plan_strategy_jit/binpack", "plan_fused_jit/g2",
            "plan_fused_jit/g4")] + [
        f"{name}@nb2048" for name in (
            "plan_group_jit/flat", "plan_group_jit/hier",
            "plan_group_jit/pref", "plan_strategy_jit/binpack",
            "plan_fused_jit/g2", "plan_fused_jit/g4",
            "plan_fused_jit/g2_L4096", "plan_fused_jit/g4_L4096")]
    assert all(row["ok"] and row["run_s"] >= 0 for row in smoke.programs)
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("program plan_")]
    assert len(lines) == 13
    # the three lines at the leaf bucket print the form their program
    # took: the flat column's layout came, of its own and in the run
    pref = [row for row in smoke.programs if "/pref@" in row["program"]
            or "_L4096@" in row["program"]]
    assert [row["form"] for row in pref] == ["dense"] * 3
    assert [row["form"] for row in smoke.programs
            if re.search(r"fused_jit/g\d@", row["program"])] == ["sum"] * 4
    assert all(re.search(r" form=dense\b", line) for line in lines
               if "/pref@" in line or "_L4096@" in line)
    assert all(re.search(r": PASS compile_s=\S+ wall_s=\S+ run_s=\S+", line)
               for line in lines)
    # the tree's line says the form its searches took and their steps:
    # built through fusedbatch.tree_inputs, 260 racks get the layout
    hier = [row for row in smoke.programs if "hier" in row["program"]]
    assert [row["form"] for row in hier] == ["mask", "dense"]
    assert all(0 < row["level_steps"] <= 7 and 0 <= row["tie_steps"] <= 24
               for row in hier)
    json.dumps(smoke.programs)      # the summary line carries every row
    assert all(re.search(r" form=\w+ level_steps=\d+ tie_steps=\d+$", line)
               for line in lines if "/hier@" in line)
    # the three cells' buckets, the last with harness-100k's wide tree
    assert chip_smoke.TIMED_BUCKETS == (
        (1024, 1000, 25), (16384, 10000, 25), (131072, 100000, 250))


def test_a_search_step_is_timed_in_each_of_its_four_forms(capsys):
    """Tiny shapes here: that each shape takes the form it names and a
    line is printed for it, no speed."""
    shapes = (("sum", 512, 1), ("mask", 512, 16), ("scatter", 2048, 300),
              ("dense", 2048, 300))
    out = chip_smoke.search_step_times(shapes, seed=1)
    assert list(out) == ["sum", "mask", "scatter", "dense"]
    lines = [line for line in capsys.readouterr().out.splitlines()
             if line.startswith("step ")]
    assert len(lines) == 4 and "step dense ([300, 128])" in lines[3]
    assert all(re.search(r": \S+ us a step ", line) for line in lines)
    # on the chip: PR 33's three and the dense form at [4096, 128]
    assert [(form, L) for form, _n, L in chip_smoke.STEP_SHAPES] == [
        ("sum", 1), ("mask", 256), ("scatter", 4096), ("dense", 4096)]
