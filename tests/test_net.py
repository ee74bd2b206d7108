"""Network transport tests: remote agents + remote control over TCP, and
raft consensus over the TCP transport (the distributed communication
backend, SURVEY §5.8)."""

import os
import time

import pytest

from swarmkit_tpu.agent import Agent
from swarmkit_tpu.agent.testutils import TestExecutor
from swarmkit_tpu.manager import Manager
from swarmkit_tpu.manager.dispatcher import Config_
from swarmkit_tpu.models import (
    Annotations, Cluster, NodeState, ReplicatedService, Task, TaskState,
)
from swarmkit_tpu.models.types import NodeRole
from swarmkit_tpu.net import (
    ManagerServer, RemoteControlClient, RemoteDispatcherClient,
    TCPRaftTransport, issue_certificate,
)
from swarmkit_tpu.state import MemoryStore
from swarmkit_tpu.state.store import ByName
from swarmkit_tpu.utils import new_id

from test_orchestrator import make_replicated, poll

from swarmkit_tpu.security.ca import HAVE_CRYPTOGRAPHY

requires_crypto = pytest.mark.skipif(
    not HAVE_CRYPTOGRAPHY,
    reason="requires the 'cryptography' package")



def fast_cfg():
    return Config_(heartbeat_period=0.3, heartbeat_epsilon=0.02,
                   process_updates_interval=0.02)


@requires_crypto
def test_remote_agent_and_control_over_tcp():
    """Full E2E over real sockets: join via token -> cert; agent sessions,
    heartbeats, assignment stream, status writeback; control client drives
    service lifecycle."""
    manager = Manager(dispatcher_config=fast_cfg(),
                      use_device_scheduler=False)
    manager.run()
    server = ManagerServer(manager)
    server.start()
    agent = None
    try:
        cluster = manager.store.view(
            lambda tx: tx.find(Cluster, ByName("default")))[0]
        token = cluster.root_ca.join_tokens.worker

        # join over the network: token -> certificate
        node_id = new_id()
        cert = issue_certificate(server.addr, node_id, token)
        assert cert.node_id == node_id
        assert NodeRole(cert.role) == NodeRole.WORKER

        # bad token rejected
        with pytest.raises(Exception):
            issue_certificate(server.addr, new_id(), "SWMTKN-1-bad-bad")

        client = RemoteDispatcherClient(server.addr, cert)
        agent = Agent(node_id, TestExecutor(hostname="remote1"), client)
        agent.start()

        poll(lambda: manager.store.view(
            lambda tx: tx.get(
                __import__("swarmkit_tpu.models",
                           fromlist=["Node"]).Node, node_id)) is not None,
            msg="remote node should self-register")

        # the control surface is manager-role gated: a worker cert is
        # rejected, an operator needs a manager-token-issued cert
        with pytest.raises(PermissionError):
            RemoteControlClient(server.addr, cert).list_nodes()
        op_cert = issue_certificate(server.addr, new_id(),
                                    cluster.root_ca.join_tokens.manager)
        control = RemoteControlClient(server.addr, op_cert)
        svc = control.create_service(make_replicated("web", 3).spec)

        def running():
            tasks = control.list_tasks(service_id=svc.id)
            live = [t for t in tasks
                    if t.desired_state == TaskState.RUNNING]
            return (len(live) == 3
                    and all(t.status.state == TaskState.RUNNING
                            and t.node_id == node_id for t in live))
        poll(running, timeout=30,
             msg="remote agent should run all replicas via TCP")

        # scale down over the network
        cur = control.get_service(svc.id)
        spec = cur.spec.copy()
        spec.replicated = ReplicatedService(replicas=1)
        control.update_service(svc.id, cur.meta.version.index, spec)
        poll(lambda: len([t for t in control.list_tasks(service_id=svc.id)
                          if t.desired_state == TaskState.RUNNING]) == 1,
             timeout=30)
        control.close()
    finally:
        if agent is not None:
            agent.stop()
        server.stop()
        manager.stop()


@requires_crypto
def test_unauthenticated_connection_rejected():
    manager = Manager(dispatcher_config=fast_cfg(),
                      use_device_scheduler=False)
    manager.run()
    server = ManagerServer(manager)
    server.start()
    try:
        from swarmkit_tpu.security import RootCA
        foreign = RootCA().issue("evil", NodeRole.WORKER)
        with pytest.raises(PermissionError):
            RemoteControlClient(server.addr, foreign).list_nodes()
    finally:
        server.stop()
        manager.stop()


def test_raft_over_tcp(tmp_path):
    """3-member consensus over real TCP links."""
    from swarmkit_tpu.models import Node, NodeSpec
    from swarmkit_tpu.state.raft import RaftLogger, RaftNode

    ids = ["m0", "m1", "m2"]
    transports = {i: TCPRaftTransport(i) for i in ids}
    for i in ids:
        for j in ids:
            if i != j:
                transports[i].set_peer(j, transports[j].addr)
    members = {}
    for i in ids:
        store = MemoryStore()
        rn = RaftNode(i, ids, store,
                      RaftLogger(os.path.join(tmp_path, i)),
                      transports[i])
        store._proposer = rn
        members[i] = rn
        rn.start()
    try:
        # leader_ready: proposals before the election no-op applies are
        # dropped by design; wait for a proposal-ready leader
        leader = poll(
            lambda: next((m for m in members.values()
                          if m.is_leader and m.core.leader_ready), None)
            if sum(1 for m in members.values() if m.is_leader) == 1
            else None,
            timeout=20, msg="leader over TCP")
        for name in ("a", "b"):
            leader.store.update(lambda tx, name=name: tx.create(Node(
                id=new_id(),
                spec=NodeSpec(annotations=Annotations(name=name)))))

        def converged():
            for m in members.values():
                names = {n.spec.annotations.name
                         for n in m.store.view(lambda tx: tx.find(Node))}
                if names != {"a", "b"}:
                    return False
            return True
        poll(converged, timeout=20,
             msg="stores should converge over TCP links")
    finally:
        for m in members.values():
            m.stop()


@requires_crypto
def test_manager_raft_join_rpc(tmp_path):
    """A promoted node's manager joins the raft group over the network:
    manager-cert gated, returns peer addresses, membership grows."""
    import os

    from swarmkit_tpu.models.types import NodeRole
    from swarmkit_tpu.net import join_raft
    from swarmkit_tpu.state.raft import LocalNetwork, RaftLogger, RaftNode

    net = LocalNetwork()
    store = MemoryStore()
    rn = RaftNode("m0", ["m0"], store,
                  RaftLogger(os.path.join(tmp_path, "m0")), net)
    store._proposer = rn
    rn.start()
    poll(lambda: rn.is_leader, timeout=10)

    manager = Manager(store=store, raft_node=rn,
                      dispatcher_config=fast_cfg(),
                      use_device_scheduler=False)
    manager.raft_peer_addrs["m0"] = ("127.0.0.1", 12345)
    manager.run()
    server = ManagerServer(manager)
    server.start()
    try:
        poll(lambda: manager.is_leader, timeout=10)
        worker_cert = manager.root_ca.issue("joiner", NodeRole.WORKER)
        with pytest.raises(Exception):
            join_raft(server.addr, worker_cert, "joiner")

        mgr_cert = manager.root_ca.issue("m1", NodeRole.MANAGER)
        # the join wedges quorum until the member starts, so start it
        # right after the RPC
        import threading

        def start_member():
            store2 = MemoryStore()
            rn2 = RaftNode("m1", ["m0", "m1"], store2,
                           RaftLogger(os.path.join(tmp_path, "m1")), net)
            store2._proposer = rn2
            rn2.start()
            return rn2

        result = join_raft(server.addr, mgr_cert, "m1",
                           raft_addr=("127.0.0.1", 23456))
        assert "m0" in result["members"]
        assert "m1" in rn.core.peers
        rn2 = start_member()
        try:
            poll(lambda: rn2.core.commit_index > 0, timeout=15,
                 msg="joined manager should replicate")
        finally:
            rn2.stop()
    finally:
        server.stop()
        manager.stop()
        rn.stop()


@requires_crypto
def test_collect_logs_over_tcp():
    """service logs work through the remote control client too."""
    import tempfile as _tf

    from swarmkit_tpu.agent import Agent, ProcessExecutor

    manager = Manager(dispatcher_config=fast_cfg(),
                      use_device_scheduler=False)
    manager.run()
    server = ManagerServer(manager)
    server.start()
    agent = None
    try:
        cluster = manager.store.view(
            lambda tx: tx.find(Cluster, ByName("default")))[0]
        node_id = new_id()
        cert = issue_certificate(server.addr, node_id,
                                 cluster.root_ca.join_tokens.worker)
        client = RemoteDispatcherClient(server.addr, cert)
        agent = Agent(node_id, ProcessExecutor(
            hostname="tcp-logger", log_dir=_tf.mkdtemp()), client)
        agent.log_ship_interval = 0.1
        agent.start()

        op_cert = issue_certificate(server.addr, new_id(),
                                    cluster.root_ca.join_tokens.manager)
        control = RemoteControlClient(server.addr, op_cert)
        from swarmkit_tpu.models import (
            Annotations, ContainerSpec, ReplicatedService,
            RestartCondition, RestartPolicy, ServiceMode, ServiceSpec,
            TaskSpec,
        )
        svc = control.create_service(ServiceSpec(
            annotations=Annotations(name="wire-logger"),
            task=TaskSpec(container=ContainerSpec(
                image="process",
                command=["sh", "-c",
                         "for i in 1 2 3; do echo wire-$i; "
                         "sleep 0.4; done"]),
                restart=RestartPolicy(
                    condition=RestartCondition.NONE)),
            mode=ServiceMode.REPLICATED,
            replicated=ReplicatedService(replicas=1)))
        poll(lambda: [t for t in control.list_tasks(service_id=svc.id)
                      if t.status.state >= TaskState.RUNNING] or None,
             timeout=20)
        msgs = control.collect_logs(svc.id, duration=4.0)
        data = b"".join(m["data"] for m in msgs)
        # live-only stream: lines published before the subscription are
        # not replayed, so any tick from the overlap window suffices
        assert b"wire-" in data, data
        control.close()
    finally:
        if agent is not None:
            agent.stop()
        server.stop()
        manager.stop()
