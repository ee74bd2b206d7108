"""A small traced deploy through a live ``Manager()``, for the tests of the
served path's own accounting (``test_obs_servedpath.py``) and of the
per-layer metrics that read it (``benchmark/test_layer_metrics_inside.py``).

One standalone manager with the device scheduler, 48 nodes, a warm-up
round with the tracer off, then with the tracer on two deploys through the
control API: one over the device break-even, one under it, and then a
stack of two created back to back, which one tick plans as a fused run.
Returns plain data; the manager is stopped and the process-wide tracer left
off and empty."""

import threading
import time

from swarmkit_tpu.manager import Manager
from swarmkit_tpu.manager.dispatcher import Config_
from swarmkit_tpu.models import (
    Annotations, ReplicatedService, Resources, ResourceRequirements,
    ServiceMode, ServiceSpec, Task, TaskSpec, TaskState,
)
from swarmkit_tpu.models.specs import ContainerSpec
from swarmkit_tpu.obs import tracer
from swarmkit_tpu.state.store import ByService

from test_scheduler import make_ready_node

#: replicas of the deploy the device plans, and of the one the host does
DEVICE_REPLICAS, HOST_REPLICAS = 160, 6
#: the debounce gap while a stack's two services are created: both are
#: PENDING before the tick that takes them, on a loaded runner too
STACK_GAP_S = 0.3


def spec(name: str, replicas: int) -> ServiceSpec:
    return ServiceSpec(
        annotations=Annotations(name=name),
        task=TaskSpec(
            container=ContainerSpec(image="img"),
            resources=ResourceRequirements(reservations=Resources(
                nano_cpus=10 ** 8, memory_bytes=8 << 20))),
        mode=ServiceMode.REPLICATED,
        replicated=ReplicatedService(replicas=replicas))


def wait_assigned(mgr, service_id: str, replicas: int,
                  timeout: float = 120.0) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        tasks = mgr.store.view(
            lambda tx: tx.find(Task, ByService(service_id)))
        if sum(1 for t in tasks if t.node_id
               and t.status.state >= TaskState.ASSIGNED) >= replicas:
            return
        time.sleep(0.02)
    raise AssertionError(f"{service_id}: not assigned within {timeout}s")


def deploy_stack(mgr, prefix: str) -> list:
    """Two services over the break-even, created back to back under a
    debounce gap wide enough that one tick holds both groups: a fused
    run.  Returns their ids, every task assigned."""
    gap, mgr.scheduler.debounce_gap = mgr.scheduler.debounce_gap, STACK_GAP_S
    try:
        ids = [mgr.control_api.create_service(
            spec(f"{prefix}-{i}", DEVICE_REPLICAS)).id for i in range(2)]
        for sid in ids:
            wait_assigned(mgr, sid, DEVICE_REPLICAS)
    finally:
        mgr.scheduler.debounce_gap = gap
    return ids


def contend(store, holder: str = "lock-holder",
            hold_s: float = 0.02) -> None:
    """A second thread, named ``holder``, holds the store's update lock
    for ``hold_s`` while this one asks for it."""
    holding = threading.Event()

    def hold(tx):
        holding.set()
        time.sleep(hold_s)
    t = threading.Thread(target=lambda: store.update(hold), name=holder,
                         daemon=True)
    t.start()
    assert holding.wait(5.0)
    store.update(lambda tx: None)
    t.join(5.0)
    assert not t.is_alive()


def _numbers(stats: dict) -> dict:
    return {k: v for k, v in stats.items() if isinstance(v, (int, float))}


def traced_deploy() -> dict:
    """{"spans": [(thread, name, start, end, args, span_id, parent_id,
    cpu)], "services": {"device": id, "host": id, "stack": [id, id]},
    "counters":
    {"scheduler.stats": growth, "planner.stats": growth}, "wall": (t0, t1),
    "doc": chrome trace,
    "stats": the scheduler's counters at the end}."""
    # nodes without an agent must not be marked DOWN while the test runs
    mgr = Manager(dispatcher_config=Config_(heartbeat_period=600.0))
    mgr.run()
    try:
        planner = mgr.scheduler.batch_planner
        # a fixed break-even (80 tasks at 50 us a task), not one this
        # machine's launch probe happens to measure
        planner._launch_overhead = 0.005
        nodes = [make_ready_node(f"n{i:02d}", cpus=64, mem=256 << 30)
                 for i in range(48)]
        mgr.store.update(lambda tx: [tx.create(n) for n in nodes])
        api = mgr.control_api
        # warm-up, tracer off: the device program compiles here
        for name, k in (("warm-d", DEVICE_REPLICAS), ("warm-h", 3)):
            wait_assigned(mgr, api.create_service(spec(name, k)).id, k)
        deploy_stack(mgr, "warm-stack")
        tracer.reset()
        tracer.enable()
        before = _numbers(mgr.scheduler.stats)
        planned0 = _numbers(planner.stats)
        t0 = time.time()
        ids = {}
        for key, k in (("device", DEVICE_REPLICAS), ("host", HOST_REPLICAS)):
            ids[key] = api.create_service(spec(f"traced-{key}", k)).id
            wait_assigned(mgr, ids[key], k)
        ids["stack"] = deploy_stack(mgr, "traced-stack")
        contend(mgr.store)
        # let the loop close its last episode (the counters advance there)
        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline and \
                mgr.scheduler.stats["loop_wall_s"] == before["loop_wall_s"]:
            time.sleep(0.02)
        time.sleep(0.15)
        t1 = time.time()
        after = _numbers(mgr.scheduler.stats)
        planned = _numbers(planner.stats)
        tracer.disable()
        doc = tracer.to_chrome()
        spans = [(s.thread, s.name, s.start, s.end, s.args, s.span_id,
                  s.parent_id, s.cpu) for s in tracer.spans()]
    finally:
        tracer.disable()
        tracer.reset()
        mgr.stop()
    return {"spans": spans, "services": ids, "wall": (t0, t1), "doc": doc,
            "counters": {"scheduler.stats": {
                k: after[k] - before.get(k, 0) for k in after},
                "planner.stats": {
                k: planned[k] - planned0.get(k, 0) for k in planned}},
            "stats": after}
