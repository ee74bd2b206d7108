"""What the tests hold a benchmark to, as functions of a loaded
``BENCHMARK.json``: the repo's own (``test_benchmark_harness.py``) and the
one the tests assemble with one more of everything (``one_more.py``) pass
through the same checks.  Every function reads the data files through the
harness's own loaders, so it checks whatever tree those point at.

What a check expects of a cell (node bucket, leaf bucket, fused labels,
longest stack) is derived from the cell's configuration, its traffic and
the planner's ladders, never from the cell's name or size."""

import os
import re

from benchmark import cluster, harness, readers, traffic, warmup

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def point_at(root: str) -> None:
    """From here on this process's loaders (``harness.load_benchmark``,
    ``cluster.load_config``, ``traffic.load``,
    ``readers.load_layer_metrics``) read the benchmark under ``root``.
    The one way the tests move them: ``rehearse_cells.py --root`` in its
    own process, the ``bench`` fixtures in theirs (which point back at the
    repo when the test is over).  The command has no such switch."""
    harness.ROOT = root
    cluster.HERE = traffic.HERE = readers.HERE = \
        os.path.join(root, "benchmark")


def cell_names(bench: dict) -> list:
    return [w["name"] for w in bench["workloads"]]


def cell_resolves(bench: dict, cell: dict) -> None:
    """The ``workloads`` entry names a configuration and a traffic file
    that are there and say the same as their entries."""
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    configs = {c["name"]: c for c in bench["configs"]}
    entry = configs[cell["config"]]
    assert entry["file"] == f"benchmark/configs/{cell['config']}.json"
    config = cluster.load_config(cell["config"])
    assert config["source"] == entry["source"] and len(entry["source"]) <= 200
    assert config["reduced"] == entry["reduced"]
    assert config["chips"] == cell["chips"] and cell["chips"] in (1, 4)
    params = traffic.load(cell["traffic"])
    assert params["generator"] in traffic.GENERATORS
    assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]


def names_and_units(bench: dict) -> None:
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    names += cell_names(bench) + [c["name"] for c in bench["configs"]]
    names += [w["traffic"] for w in bench["workloads"]]
    for name in names:
        assert NAME.match(name), name
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25


def layer_metric_is_sound(bench: dict, name: str, spec: dict) -> None:
    """The metric's file and its ``per_layer`` entry agree on the keys
    both have; the entry alone says which cells report it, and they are
    cells that report the end-to-end metric it moves."""
    assert set(spec) == {"layer", "unit", "better", "moves", "reader"}
    assert spec["reader"]["kind"] in readers.KINDS
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    listed = entry["workloads"]
    assert entry == {
        "name": name, "unit": spec["unit"], "better": spec["better"],
        "source": readers.SOURCE_OF_KIND[spec["reader"]["kind"]],
        "layer": spec["layer"], "moves": spec["moves"],
        "workloads": listed}
    moved = {m["name"]: m for m in bench["end_to_end"]}[spec["moves"]]
    reporting = set(moved.get("workloads", cell_names(bench)))
    assert listed and len(set(listed)) == len(listed)
    assert set(listed) <= reporting
    if name.endswith("_roofline") or "mfu" in name:
        assert spec["unit"] == "%"


def every_entry_has_its_file(bench: dict, specs: dict) -> None:
    assert sorted(m["name"] for m in bench["per_layer"]) == sorted(specs)


def cut_to_a_test_s_size(shrinks: dict, cell: dict) -> None:
    """A cell brings its own cut (``tests/benchmark/shrink/<cell>.json``)
    and the cut overrides only what the configuration's cluster and the
    traffic file have."""
    assert cell["name"] in shrinks, (
        f"{cell['name']}: no tests/benchmark/shrink/{cell['name']}.json; "
        "a cell is rehearsed on the CPU at a size its own file gives")
    cut = shrinks[cell["name"]]
    assert cut and set(cut) <= {"cluster", "traffic"}
    config = cluster.load_config(cell["config"])
    assert set(cut.get("cluster", {})) <= set(config["cluster"])
    assert set(cut.get("traffic", {})) <= set(traffic.load(cell["traffic"]))


def _longest_fusable_stretch(cycle: list, shapes: dict) -> list:
    """The most consecutive fusable shapes a tick can hold of a cycle
    that repeats service by service (a whole cycle at the most: what the
    warm-up drives)."""
    best, run = [], []
    for name in cycle + cycle:
        run = run + [name] if warmup.fusable(shapes[name]) else []
        if len(best) < len(run) <= len(cycle):
            best = run
    return best


def warmup_enumerates(cell: dict):
    """``warmup.plan`` on the cell's configuration at its full size
    names every signature the cell's traffic can meet.  Returns (stacks,
    labels, the cluster's node bucket).

    Taken here from the planner and the data, not from ``warmup``: the
    node bucket, each preference tree's leaf bucket and depth, the scatter
    buckets, and how long a stack can be (the traffic's shapes).  The
    format of a fused label and which slots it names are ``warmup``'s own
    (``fused_labels``), so for those this checks that the plan is whole,
    not that a label is right: that is held by the literal labels the
    tests name for the three sizes they know, and on the chip by
    ``window_compiles`` 0."""
    from swarmkit_tpu.ops import fusedbatch, streaming
    config = cluster.load_config(cell["config"])
    params = traffic.load(cell["traffic"])
    nodes = cluster.plain_nodes(config["cluster"], seed=3)
    shapes = config["shapes"]
    stacks, labels = warmup.plan(config, params, nodes)
    nb = fusedbatch.n_bucket(len(nodes))
    sent = (params["shapes"] if "shapes" in params
            else [c["shape"] for c in params["clients"]])
    # every shape the traffic sends: its own group's signature, at the
    # node bucket the planner gives a cluster of this size
    own = {warmup.group_label(shapes[n], nodes) for n in sent}
    assert all(label.startswith(f"nb{nb}_") for label in own)
    # a preference tree: the deepest label's distinct values over the
    # nodes, in the planner's leaf ladder, and the tree's depth
    for n in sent:
        over = shapes[n]["spread_over"]
        if len(over) > 1 and shapes[n]["strategy"] == "spread":
            leaf = over[-1][len("node.labels."):]
            L = fusedbatch.l_bucket(len({x["labels"][leaf] for x in nodes}))
            assert any(label.startswith(f"nb{nb}_") and label.endswith(
                f"_L{L}_h{len(over)}") for label in labels), (n, L)
    # a shape that cannot fuse is warmed by a stack of its own
    for n in sent:
        if not warmup.fusable(shapes[n]):
            assert [n] in stacks
    # the longest run a tick can hold, from the traffic's shapes: a
    # stretch of the open loop's cycle, all the closed loop's fusable
    # clients together; no stack is longer and its signatures are warmed
    fusables = [n for n in dict.fromkeys(sent) if warmup.fusable(shapes[n])]
    longest = (_longest_fusable_stretch(sent, shapes)
               if params["generator"] == "open_loop" else fusables)
    assert max(map(len, stacks)) <= max(len(longest), 1)
    if len(longest) > 1:
        assert set(warmup.fused_labels([shapes[n] for n in longest],
                                       nodes)) <= set(labels)
    fused = set()
    for stack in stacks:
        assert set(stack) <= set(sent)
        if len(stack) > 1:
            assert set(stack) <= set(fusables)
            fused.update(warmup.fused_labels([shapes[n] for n in stack],
                                             nodes))
    assert all(f"_nb{nb}_" in label for label in fused)
    scatter = {f"stream_nb{nb}_d{d}" for d in streaming.D_BUCKETS}
    assert set(labels) == own | fused | scatter
    return stacks, labels, nb


def only_additions(original: dict, extended: dict) -> None:
    """``extended`` is ``original`` (both a loaded ``BENCHMARK.json``)
    with entries appended and items appended to ``workloads`` lists, and
    nothing else."""
    assert list(extended) == list(original)
    for key in ("command", "paths", "run_seconds"):
        assert extended[key] == original[key]
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        was, now = original[key], extended[key]
        assert len(now) >= len(was)
        for old, new in zip(was, now):
            assert list(new) == list(old), (key, old["name"])
            for field in old:
                if field == "workloads" and key in ("end_to_end",
                                                    "per_layer"):
                    assert new[field][:len(old[field])] == old[field]
                else:
                    assert new[field] == old[field], (key, old["name"])


def data_files(root: str) -> dict:
    """{relative path: bytes} of every data file of the benchmark under
    ``root``: ``BENCHMARK.json`` aside, what a later PR may add to and
    may not edit."""
    out = {}
    for folder in ("benchmark/configs", "benchmark/traffic",
                   "benchmark/layer_metrics", "tests/benchmark/shrink"):
        for fname in sorted(os.listdir(os.path.join(root, folder))):
            rel = f"{folder}/{fname}"
            with open(os.path.join(root, rel), "rb") as f:
                out[rel] = f.read()
    with open(os.path.join(root, "benchmark", "peaks.json"), "rb") as f:
        out["benchmark/peaks.json"] = f.read()
    return out
