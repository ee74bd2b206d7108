"""Process executor: tasks run as real OS processes through the full
manager/dispatcher/agent pipeline."""

import os
import tempfile
import time

import pytest

pytest.importorskip(
    "cryptography", reason="CA/TLS tests require the cryptography package")

from swarmkit_tpu.agent import ProcessExecutor
from swarmkit_tpu.manager import Manager
from swarmkit_tpu.manager.dispatcher import Config_
from swarmkit_tpu.models import (
    Annotations, ContainerSpec, ReplicatedService, RestartCondition,
    RestartPolicy, Service, ServiceMode, ServiceSpec, TaskSpec, TaskState,
)
from swarmkit_tpu.node import Node as ClusterNode
from swarmkit_tpu.utils import new_id

from test_orchestrator import poll


def fast_cfg():
    return Config_(heartbeat_period=0.3, heartbeat_epsilon=0.02,
                   process_updates_interval=0.02)


def proc_service(name, replicas, command, restart=None):
    return ServiceSpec(
        annotations=Annotations(name=name),
        task=TaskSpec(container=ContainerSpec(
            image="process", command=command),
            restart=restart or RestartPolicy(
                condition=RestartCondition.NONE)),
        mode=ServiceMode.REPLICATED,
        replicated=ReplicatedService(replicas=replicas))


@pytest.fixture
def cluster():
    manager = Manager(dispatcher_config=fast_cfg(),
                      use_device_scheduler=False)
    manager.run()
    log_dir = tempfile.mkdtemp()
    executor = ProcessExecutor(hostname="proc1", log_dir=log_dir,
                               stop_grace=2.0)
    node = ClusterNode(executor, tempfile.mkdtemp())
    from swarmkit_tpu.models import Cluster
    from swarmkit_tpu.state.store import ByName
    cl = manager.store.view(
        lambda tx: tx.find(Cluster, ByName("default")))[0]
    node.load_or_join(manager.ca_server, cl.root_ca.join_tokens.worker)
    node.start(manager.dispatcher, store=manager.store, hostname="proc1")
    yield manager, node, executor
    node.stop()
    manager.stop()


def test_process_tasks_run_and_complete(cluster):
    manager, node, executor = cluster
    api = manager.control_api
    marker = os.path.join(tempfile.mkdtemp(), "ran")
    svc = api.create_service(proc_service(
        "toucher", 2, ["sh", "-c", f"echo done >> {marker}"]))
    poll(lambda: len([t for t in api.list_tasks(service_id=svc.id)
                      if t.status.state == TaskState.COMPLETE]) == 2,
         timeout=20, msg="both process replicas should COMPLETE")
    with open(marker) as f:
        assert f.read().count("done") == 2
    # stdout captured per task
    svc2 = api.create_service(proc_service(
        "talker", 1, ["sh", "-c", "echo captured-output"]))
    poll(lambda: [t for t in api.list_tasks(service_id=svc2.id)
                  if t.status.state == TaskState.COMPLETE] or None,
         timeout=20)
    t = [t for t in api.list_tasks(service_id=svc2.id)][0]
    ctlr = executor.controllers[t.id]
    assert b"captured-output" in ctlr.read_logs()


def test_process_failure_surfaces_exit_code(cluster):
    manager, node, executor = cluster
    api = manager.control_api
    svc = api.create_service(proc_service(
        "failer", 1, ["sh", "-c", "echo boom >&2; exit 3"]))

    def failed():
        ts = api.list_tasks(service_id=svc.id)
        return [t for t in ts if t.status.state == TaskState.FAILED]
    got = poll(lambda: failed() or None, timeout=20,
               msg="failing process should reach FAILED")
    assert "exited with 3" in got[0].status.err
    assert "boom" in got[0].status.err


def test_health_check_fails_unhealthy_task(cluster):
    """A failing healthcheck stops the task with a diagnostic err and the
    restart policy replaces it (reference: dockerapi controller health
    monitoring; api/types.proto HealthConfig)."""
    from swarmkit_tpu.models.specs import HealthConfig

    manager, node, executor = cluster
    api = manager.control_api

    # healthy-then-unhealthy: the probe passes while the flag file
    # exists, then we delete it and the task must fail within ~2 probes
    flag = os.path.join(tempfile.mkdtemp(), "healthy")
    open(flag, "w").close()
    spec = proc_service("webish", 1, ["sh", "-c", "sleep 60"])
    spec.task.container.healthcheck = HealthConfig(
        test=["CMD", "test", "-e", flag],
        interval=0.2, timeout=1.0, retries=2, start_period=0.2)
    svc = api.create_service(spec)
    poll(lambda: [t for t in api.list_tasks(service_id=svc.id)
                  if t.status.state == TaskState.RUNNING] or None,
         timeout=20, msg="task should start healthy")
    time.sleep(0.6)   # at least one passing probe
    running = [t for t in api.list_tasks(service_id=svc.id)
               if t.status.state == TaskState.RUNNING]
    assert running, "passing health checks must not kill the task"

    os.unlink(flag)
    got = poll(lambda: [t for t in api.list_tasks(service_id=svc.id)
                        if t.status.state == TaskState.FAILED] or None,
               timeout=20, msg="unhealthy task should FAIL")
    assert "health check" in got[0].status.err

    # CMD-SHELL form + restart policy: always-unhealthy task cycles
    # through replacements (the orchestrator heals unhealthy tasks)
    spec2 = proc_service(
        "sickly", 1, ["sh", "-c", "sleep 60"],
        restart=RestartPolicy(condition=RestartCondition.ON_FAILURE,
                              delay=0.05))
    spec2.task.container.healthcheck = HealthConfig(
        test=["CMD-SHELL", "exit 1"],
        interval=0.1, timeout=1.0, retries=2)
    svc2 = api.create_service(spec2)

    def replaced():
        ts = api.list_tasks(service_id=svc2.id)
        return len([t for t in ts
                    if t.status.state == TaskState.FAILED]) >= 2
    poll(replaced, timeout=25,
         msg="restart policy should replace unhealthy tasks")


def test_process_shutdown_terminates_group(cluster):
    manager, node, executor = cluster
    api = manager.control_api
    svc = api.create_service(proc_service(
        "sleeper", 1, ["sh", "-c", "sleep 300 & wait"]))
    poll(lambda: [t for t in api.list_tasks(service_id=svc.id)
                  if t.status.state == TaskState.RUNNING] or None,
         timeout=20, msg="long-running process should reach RUNNING")
    tasks = api.list_tasks(service_id=svc.id)
    pid = executor.controllers[tasks[0].id].proc.pid
    api.remove_service(svc.id)
    poll(lambda: not any(t.status.state == TaskState.RUNNING
                         for t in api.list_tasks(service_id=svc.id)),
         timeout=20, msg="removal should stop the process task")

    def proc_gone():
        try:
            os.kill(pid, 0)
            return False
        except ProcessLookupError:
            return True
    poll(proc_gone, timeout=15, msg="the OS process group must die")


def test_task_logs_ship_to_broker(cluster):
    """Process stdout flows agent → dispatcher → log broker subscribers
    (reference: agent log publisher + logbroker.PublishLogs)."""
    from swarmkit_tpu.manager.logbroker import LogSelector

    manager, node, executor = cluster
    node.agent.log_ship_interval = 0.1
    api = manager.control_api
    svc = api.create_service(proc_service(
        "chatty", 1,
        ["sh", "-c", "echo hello-from-task; echo second-line"]))
    sub = manager.logbroker.subscribe_logs(
        LogSelector(service_ids=[svc.id]))
    poll(lambda: [t for t in api.list_tasks(service_id=svc.id)
                  if t.status.state == TaskState.COMPLETE] or None,
         timeout=20)
    got = b""
    deadline = time.time() + 10
    while time.time() < deadline and b"second-line" not in got:
        try:
            got += sub.get(timeout=1.0).data
        except TimeoutError:
            pass
    assert b"hello-from-task" in got and b"second-line" in got
    sub.close()


def test_task_logs_ship_over_tcp():
    """Same flow over the wire: remote agent publishes log bytes through
    the TCP dispatcher surface."""
    from swarmkit_tpu.agent import Agent
    from swarmkit_tpu.manager.logbroker import LogSelector
    from swarmkit_tpu.models import Cluster
    from swarmkit_tpu.net import ManagerServer, RemoteDispatcherClient, \
        issue_certificate
    from swarmkit_tpu.state.store import ByName

    manager = Manager(dispatcher_config=fast_cfg(),
                      use_device_scheduler=False)
    manager.run()
    server = ManagerServer(manager)
    server.start()
    agent = None
    try:
        cl = manager.store.view(
            lambda tx: tx.find(Cluster, ByName("default")))[0]
        node_id = new_id()
        cert = issue_certificate(server.addr, node_id,
                                 cl.root_ca.join_tokens.worker)
        client = RemoteDispatcherClient(server.addr, cert)
        executor = ProcessExecutor(hostname="tcp-proc",
                                   log_dir=tempfile.mkdtemp())
        agent = Agent(node_id, executor, client)
        agent.log_ship_interval = 0.1
        agent.start()

        api = manager.control_api
        svc = api.create_service(proc_service(
            "tcp-chatty", 1, ["sh", "-c", "echo over-the-wire"]))
        sub = manager.logbroker.subscribe_logs(
            LogSelector(service_ids=[svc.id]))
        got = b""
        deadline = time.time() + 15
        while time.time() < deadline and b"over-the-wire" not in got:
            try:
                got += sub.get(timeout=1.0).data
            except TimeoutError:
                pass
        assert b"over-the-wire" in got
        sub.close()
    finally:
        if agent is not None:
            agent.stop()
        server.stop()
        manager.stop()


def test_cli_service_logs(cluster):
    """`swarmctl service logs` collects live output via the broker."""
    from swarmkit_tpu.cli import run_command

    manager, node, executor = cluster
    node.agent.log_ship_interval = 0.1
    api = manager.control_api
    svc = api.create_service(proc_service(
        "logger", 1,
        ["sh", "-c", "for i in 1 2 3; do echo line-$i; sleep 0.4; done"]))
    poll(lambda: [t for t in api.list_tasks(service_id=svc.id)
                  if t.status.state >= TaskState.RUNNING] or None,
         timeout=20, msg="logger task should start")
    out = run_command(["service", "logs", "logger", "--duration", "4"],
                      api)
    # live-only stream: line-1 may print before collection subscribes,
    # but the tail of the output must land inside the window
    assert "line-" in out and "line-3" in out

    # history replay: --no-follow returns instantly from the broker's
    # ring — including output that predates this subscription — and
    # --tail bounds it (reference: LogSubscriptionOptions)
    out = run_command(["service", "logs", "logger", "--no-follow"], api)
    assert "line-1" in out and "line-3" in out
    # tail bounds the replay to the last message(s) per task
    msgs = api.collect_logs(svc.id, tail=1, follow=False)
    assert len(msgs) == 1 and b"line-3" in msgs[0]["data"]
    assert "logger." in out and "@" in out


def test_process_task_receives_secret_and_config_files(cluster):
    """Secrets/configs materialize as per-task files with their paths in
    SWARM_SECRET_* / SWARM_CONFIG_* env vars (the process equivalent of
    the reference's /run/secrets mounts)."""
    from swarmkit_tpu.models.specs import ConfigSpec, SecretSpec
    from swarmkit_tpu.models.types import ConfigReference, SecretReference

    manager, node, executor = cluster
    api = manager.control_api
    secret = api.create_secret(SecretSpec(
        annotations=Annotations(name="db-pass"), data=b"hunter2"))
    config = api.create_config(ConfigSpec(
        annotations=Annotations(name="app-conf"), data=b"mode=fast"))
    out = os.path.join(tempfile.mkdtemp(), "out")
    spec = proc_service(
        "secretuser", 1,
        ["sh", "-c",
         f'cat "$SWARM_SECRET_DB_PASS" "$SWARM_CONFIG_APP_CONF" > {out}'])
    spec.task.container.secrets = [SecretReference(
        secret_id=secret.id, secret_name="db-pass", target="db-pass")]
    spec.task.container.configs = [ConfigReference(
        config_id=config.id, config_name="app-conf",
        target="app-conf")]
    svc = api.create_service(spec)
    poll(lambda: [t for t in api.list_tasks(service_id=svc.id)
                  if t.status.state == TaskState.COMPLETE] or None,
         timeout=20, msg="secret-using task completes")
    with open(out, "rb") as f:
        assert f.read() == b"hunter2mode=fast"
    # secret file mode is owner-only
    t = api.list_tasks(service_id=svc.id)[0]
    ctlr = executor.controllers[t.id]
    spath = os.path.join(ctlr.deps_dir, "secrets", "db-pass")
    # the file may already be cleaned with the task; check only if present
    if os.path.exists(spath):
        assert (os.stat(spath).st_mode & 0o777) == 0o600
    # controller close must shred the plaintext material
    ctlr.close()
    assert not os.path.exists(ctlr.deps_dir)
