"""The six metrics that say who held the interpreter, on the traced lines
of the cells that list them and have no traced rehearsal of their own
(``harness-100k.sparse`` and ``harness-100k-ha.prefs`` are held to every
listed metric by their own files): the families' CPU while the tick and
the orchestrator's pass ran, and the subscriptions a publish woke.  The
runs are made at each cell's cut in ``rehearse_cells.py``'s process."""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)

from benchmark import harness  # noqa: E402

BENCH = harness.load_benchmark()
WHO_HOLDS = ("tick_others_cpu_ms", "tick_agents_cpu_ms",
             "tick_background_cpu_ms", "tick_control_cpu_ms",
             "reconcile_others_cpu_ms", "publish_delivered")
CELLS = ("swarm-10k.deploys", "swarm-1k.deploys-1k")


@pytest.fixture(scope="module")
def traced():
    """{cell: (exit code, result line)} of one traced run of each."""
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "rehearse_cells.py"),
         *(f"{cell}:traced" for cell in CELLS)],
        capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    runs = json.loads(done.stdout.strip().splitlines()[-1])
    return {name.split(":")[0]: tuple(v) for name, v in runs.items()}


@pytest.mark.parametrize("cell", CELLS, ids=["cell_1", "small"])
def test_the_traced_line_says_who_held_the_interpreter(traced, cell):
    """Each of the six the cell's lists name is on its traced line as a
    number; the tick's others are never nothing, and the benchmark's own
    watch client takes every event, so a publish wakes at least one."""
    code, line = traced[cell]
    assert code == 0 and line["correct"] is True
    assert line["metrics"]["window_compiles"]["value"] == 0
    listed = {m["name"] for m in BENCH["per_layer"]
              if cell in m.get("workloads", ())}
    assert set(WHO_HOLDS) <= listed
    for name in WHO_HOLDS:
        assert line["metrics"][name]["value"] >= 0.0, name
    assert line["metrics"]["tick_others_cpu_ms"]["unit"] == "ms"
    assert line["metrics"]["tick_others_cpu_ms"]["value"] > 0.0
    assert line["metrics"]["publish_delivered"]["unit"] == "subscribers"
    assert line["metrics"]["publish_delivered"]["value"] >= 1.0
