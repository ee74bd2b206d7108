"""Test configuration.

Force JAX onto a virtual 8-device CPU mesh so sharding tests (shard_map over
the node axis) run without TPU hardware.  The environment is set before jax
is imported; jax.config.update covers a jax that something imported earlier
(backend initialization is lazy, so it still takes effect as long as no test
touched a device before conftest import, which pytest guarantees).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# the planner places a persistent compile cache in the checkout
# (utils/compilecache.py); a test must not depend on what an earlier
# run left there
jax.config.update("jax_enable_compilation_cache", False)


# ---------------------------------------------------------------------------
# Daemon-level tests drive real multi-process-style clusters (threads, TCP,
# heartbeat TTLs, raft elections) on whatever CPU the runner gives us; under
# heavy load a timing assumption can miss once even though the behavior is
# correct (each of these passes consistently in isolation).  Mirror the
# reference CI's flaky-retry pragma: rerun a FAILED test from the known
# timing-sensitive daemon files once before declaring failure.  Genuine
# regressions still fail — twice in a row.

_TIMING_SENSITIVE_FILES = {"test_remotes_swarmd.py", "test_integration.py",
                           "test_ca_rotation.py", "test_external_ca.py",
                           # real threaded elections on a loaded 1-core
                           # runner: a leadership blip mid-test fails a
                           # proposal (by design — epoch fencing rejects
                           # flap-window proposals); correct on retry
                           "test_raft.py"}


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: wide sweeps excluded from the tier-1 run (-m 'not slow')")


def pytest_runtest_protocol(item, nextitem):
    from _pytest.runner import runtestprotocol

    if item.fspath.basename not in _TIMING_SENSITIVE_FILES:
        return None
    item.ihook.pytest_runtest_logstart(nodeid=item.nodeid,
                                       location=item.location)
    reports = runtestprotocol(item, nextitem=nextitem, log=False)
    if any(r.failed for r in reports):
        import warnings
        warnings.warn(f"retrying timing-sensitive test {item.nodeid} "
                      "after a failure under load")
        # one retry, freshly set-up; only its outcome is reported
        reports = runtestprotocol(item, nextitem=nextitem, log=False)
    for r in reports:
        item.ihook.pytest_runtest_logreport(report=r)
    item.ihook.pytest_runtest_logfinish(nodeid=item.nodeid,
                                        location=item.location)
    return True
