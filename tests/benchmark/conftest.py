"""The two trees every test of ``BENCHMARK.json`` and of a metric's file is
made on: the repo's benchmark, and the repo's with one more of everything
(``one_more.py``: a configuration and its three-manager twin, a traffic
file, a cell of each with its cut, three per-layer metrics, the added
cell's name on every list cell 1 reports that a CPU run of it can read and
the twin's on three, from new data only), which is what a later PR that
may edit nothing here hands in.  A test that passes on the first tree and
fails on the second is a pin: a test of a list or of a cell's managers
holds the cells it names to what their own files say, never a list to a
fixed set of cells or a position."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, REPO)
sys.path.insert(1, HERE)

from benchmark import harness  # noqa: E402
import contract  # noqa: E402
import one_more  # noqa: E402


@pytest.fixture(scope="session")
def one_more_tree(tmp_path_factory):
    """(the tree, its ``BENCHMARK.json`` as loaded)."""
    tree = str(tmp_path_factory.mktemp("one_more"))
    return tree, one_more.build(REPO, tree)


@pytest.fixture
def bench(request, one_more_tree):
    """``BENCHMARK.json`` of the tree the test is made on (parametrised
    indirectly with a name of ``one_more.TREES``), through
    ``harness.load_benchmark``, with this process's loaders reading that
    tree while the test runs."""
    if request.param == "one_more":
        contract.point_at(one_more_tree[0])
    try:
        yield harness.load_benchmark()
    finally:
        contract.point_at(REPO)
