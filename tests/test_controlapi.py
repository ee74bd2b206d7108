"""Control API tests: validated CRUD with reference-parity error messages
(mirrors manager/controlapi/*_test.go assertions)."""

import pytest

from swarmkit_tpu.manager import ControlAPI
from swarmkit_tpu.manager.controlapi import (
    AlreadyExists, FailedPrecondition, InvalidArgument, NotFound,
)
from swarmkit_tpu.models import (
    Annotations, EndpointSpec, NodeState, PortConfig, PublishMode,
    ReplicatedService, Resources, ResourceRequirements, ServiceMode,
    TaskSpec, UpdateConfig,
)
from swarmkit_tpu.models.specs import (
    ConfigSpec, ContainerSpec, NodeSpec, SecretSpec, ServiceSpec,
)
from swarmkit_tpu.models.types import NodeRole, SecretReference
from swarmkit_tpu.state import MemoryStore

from test_orchestrator import make_node

from swarmkit_tpu.security.ca import HAVE_CRYPTOGRAPHY

requires_crypto = pytest.mark.skipif(
    not HAVE_CRYPTOGRAPHY,
    reason="requires the 'cryptography' package")



def spec(name="web", replicas=1, image="nginx", **kw):
    return ServiceSpec(
        annotations=Annotations(name=name),
        task=TaskSpec(container=ContainerSpec(image=image)),
        mode=ServiceMode.REPLICATED,
        replicated=ReplicatedService(replicas=replicas),
        **kw,
    )


@pytest.fixture
def api():
    return ControlAPI(MemoryStore())


def test_create_service_validates_name(api):
    with pytest.raises(InvalidArgument, match="meta: name must be provided"):
        api.create_service(spec(name=""))
    with pytest.raises(InvalidArgument,
                       match="name must be valid as a DNS name component"):
        api.create_service(spec(name="not valid!"))
    with pytest.raises(InvalidArgument,
                       match="name must be 63 characters or fewer"):
        api.create_service(spec(name="x" * 64))


def test_create_service_validates_runtime_and_resources(api):
    s = spec()
    s.task.container = None
    with pytest.raises(InvalidArgument, match="TaskSpec: missing runtime"):
        api.create_service(s)

    s = spec()
    s.task.container.image = ""
    with pytest.raises(InvalidArgument,
                       match="image reference must be provided"):
        api.create_service(s)

    s = spec()
    s.task.resources = ResourceRequirements(
        reservations=Resources(memory_bytes=1024))
    with pytest.raises(InvalidArgument, match="Must be at least 4MiB"):
        api.create_service(s)


def test_create_service_name_conflict(api):
    api.create_service(spec(name="web"))
    with pytest.raises(AlreadyExists):
        api.create_service(spec(name="web"))


def test_create_service_missing_secret(api):
    s = spec()
    s.task.container.secrets = [
        SecretReference(secret_id="nope", secret_name="missing",
                        target="cert")]
    with pytest.raises(InvalidArgument, match="secret not found: missing"):
        api.create_service(s)


def test_create_service_with_existing_secret(api):
    secret = api.create_secret(SecretSpec(
        annotations=Annotations(name="tls-cert"), data=b"shh"))
    s = spec()
    s.task.container.secrets = [
        SecretReference(secret_id=secret.id, secret_name="tls-cert",
                        target="cert")]
    created = api.create_service(s)
    assert created.spec.task.container.secrets[0].secret_id == secret.id


def test_update_service_rules(api):
    created = api.create_service(spec(name="web", replicas=2))
    new_spec = spec(name="web", replicas=5)
    updated = api.update_service(created.id, created.meta.version.index,
                                 new_spec)
    assert updated.spec.replicated.replicas == 5
    assert updated.previous_spec is not None
    assert updated.spec_version.index > created.spec_version.index

    with pytest.raises(InvalidArgument,
                       match="renaming services is not supported"):
        api.update_service(updated.id, updated.meta.version.index,
                           spec(name="web2", replicas=5))

    bad = spec(name="web", replicas=5)
    bad.mode = ServiceMode.GLOBAL
    bad.replicated = None
    with pytest.raises(InvalidArgument,
                       match="service mode change is not allowed"):
        api.update_service(updated.id, updated.meta.version.index, bad)

    # stale version -> FailedPrecondition
    with pytest.raises(FailedPrecondition):
        api.update_service(updated.id, updated.meta.version.index - 1,
                           spec(name="web", replicas=7))


def test_ingress_port_conflict(api):
    s1 = spec(name="a")
    s1.endpoint = EndpointSpec(ports=[PortConfig(
        target_port=80, published_port=8080,
        publish_mode=PublishMode.INGRESS)])
    api.create_service(s1)
    s2 = spec(name="b")
    s2.endpoint = EndpointSpec(ports=[PortConfig(
        target_port=80, published_port=8080,
        publish_mode=PublishMode.INGRESS)])
    with pytest.raises(InvalidArgument,
                       match="already in use by service 'a'"):
        api.create_service(s2)


def test_remove_service(api):
    created = api.create_service(spec())
    api.remove_service(created.id)
    with pytest.raises(NotFound):
        api.get_service(created.id)
    with pytest.raises(NotFound):
        api.remove_service(created.id)


def test_node_remove_rules(api):
    node = make_node("n1")
    api.store.update(lambda tx: tx.create(node))
    with pytest.raises(FailedPrecondition,
                       match="is not down and can't be removed"):
        api.remove_node(node.id)
    api.remove_node(node.id, force=True)
    with pytest.raises(NotFound):
        api.get_node(node.id)


def test_demote_last_manager_fails(api):
    node = make_node("m1")
    node.spec.desired_role = NodeRole.MANAGER
    api.store.update(lambda tx: tx.create(node))
    demote = NodeSpec(annotations=Annotations(name="m1"),
                      desired_role=NodeRole.WORKER)
    with pytest.raises(FailedPrecondition,
                       match="attempting to demote the last manager"):
        api.update_node(node.id, node.meta.version.index, demote)


def test_secret_lifecycle(api):
    with pytest.raises(InvalidArgument):
        api.create_secret(SecretSpec(annotations=Annotations(name="s"),
                                     data=b""))
    secret = api.create_secret(SecretSpec(
        annotations=Annotations(name="s"), data=b"data"))
    with pytest.raises(AlreadyExists):
        api.create_secret(SecretSpec(annotations=Annotations(name="s"),
                                     data=b"x"))

    # the payload never leaves the manager — list AND get strip it
    # (reference: secret.go:44,143); the stored object keeps it
    listed = api.list_secrets()
    assert listed[0].spec.data == b""
    assert api.get_secret(secret.id).spec.data == b""
    from swarmkit_tpu.models import Secret as _Secret
    assert api.store.view(
        lambda tx: tx.get(_Secret, secret.id)).spec.data == b"data"

    with pytest.raises(InvalidArgument,
                       match="only updates to Labels are allowed"):
        api.update_secret(secret.id, secret.meta.version.index,
                          SecretSpec(annotations=Annotations(name="s"),
                                     data=b"different"))
    updated = api.update_secret(
        secret.id, secret.meta.version.index,
        SecretSpec(annotations=Annotations(name="s",
                                           labels={"env": "prod"})))
    assert updated.spec.annotations.labels == {"env": "prod"}
    assert updated.spec.data == b""   # responses stay stripped
    assert api.store.view(
        lambda tx: tx.get(_Secret, secret.id)).spec.data == b"data"

    api.remove_secret(secret.id)
    with pytest.raises(NotFound):
        api.get_secret(secret.id)


def test_remove_secret_in_use(api):
    secret = api.create_secret(SecretSpec(
        annotations=Annotations(name="tls"), data=b"shh"))
    s = spec(name="web")
    s.task.container.secrets = [
        SecretReference(secret_id=secret.id, secret_name="tls",
                        target="cert")]
    svc = api.create_service(s)
    # materialize a task referencing the secret (orchestrator would)
    from swarmkit_tpu.orchestrator.common import new_task
    t = new_task(None, api.store.view(
        lambda tx: tx.get(type(svc), svc.id)), 1, "")
    api.store.update(lambda tx: tx.create(t))
    with pytest.raises(InvalidArgument,
                       match="is in use by the following service: web"):
        api.remove_secret(secret.id)


def test_update_config_validation(api):
    s = spec()
    s.update = UpdateConfig(max_failure_ratio=1.5)
    with pytest.raises(InvalidArgument, match="maxfailureratio"):
        api.create_service(s)


def test_network_ipam_allocation():
    """Networks get subnets carved from the default pool; services on
    them get VIPs; tasks get per-network addresses (reference:
    manager/allocator network allocation)."""
    import time

    from swarmkit_tpu.manager.allocator import Allocator
    from swarmkit_tpu.models import (
        Annotations, Network, NetworkAttachmentConfig, Task, TaskState,
    )
    from swarmkit_tpu.models.specs import NetworkSpec
    from swarmkit_tpu.state import ByService

    from test_orchestrator import poll

    store = MemoryStore()
    api = ControlAPI(store)
    alloc = Allocator(store)
    alloc.start()
    try:
        n1 = api.create_network(NetworkSpec(
            annotations=Annotations(name="backend")))
        n2 = api.create_network(NetworkSpec(
            annotations=Annotations(name="frontend")))
        poll(lambda: store.view(
            lambda tx: all(tx.get(Network, i).ipam is not None
                           for i in (n1.id, n2.id))),
            msg="subnets allocated")
        nets = store.view(lambda tx: [tx.get(Network, i)
                                      for i in (n1.id, n2.id)])
        subnets = [n.ipam.configs[0].subnet for n in nets]
        assert len(set(subnets)) == 2, "distinct subnets"
        assert all(s.endswith("/24") for s in subnets), subnets
        gws = [n.ipam.configs[0].gateway for n in nets]
        assert all(g.endswith(".1") for g in gws), gws

        # service attached to both networks: VIP per network
        svc_spec = spec("webnet", replicas=2)
        svc_spec.task.networks = [
            NetworkAttachmentConfig(target="backend"),
            NetworkAttachmentConfig(target=n2.id)]
        svc = api.create_service(svc_spec)
        poll(lambda: (api.get_service(svc.id).endpoint is not None
                      and len(api.get_service(svc.id)
                              .endpoint.virtual_ips) == 2),
             msg="VIPs on both networks")
        vips = api.get_service(svc.id).endpoint.virtual_ips
        assert {v.network_id for v in vips} == {n1.id, n2.id}
        assert all(v.addr for v in vips)

        # tasks carry per-network addresses, all distinct (created
        # directly: no orchestrator runs in this test)
        from swarmkit_tpu.models.types import TaskStatus
        from swarmkit_tpu.utils import new_id

        def mk(tx):
            for slot in (1, 2):
                tx.create(Task(
                    id=new_id(), service_id=svc.id, slot=slot,
                    spec=svc_spec.task.copy(),
                    status=TaskStatus(state=TaskState.NEW),
                    desired_state=TaskState.RUNNING))
        store.update(mk)

        def task_addrs():
            ts = store.view(lambda tx: tx.find(Task, ByService(svc.id)))
            if len(ts) < 2 or any(
                    t.status.state < TaskState.PENDING for t in ts):
                return None
            return [a for t in ts for att in t.networks
                    for a in att.addresses]
        addrs = poll(task_addrs, msg="task addresses allocated")
        assert len(addrs) == 4                     # 2 tasks x 2 networks
        assert len(set(addrs)) == 4, "addresses must be unique"
        vip_addrs = {v.addr for v in vips}
        assert not vip_addrs & set(addrs), "VIPs never reused for tasks"
    finally:
        alloc.stop()


# ------------------------------------------------- volumes (volume.go parity)

def _vol_spec(name="vol1", driver="csi.example", group="", sharing=None,
              secrets=None):
    from swarmkit_tpu.models.specs import VolumeSpec
    from swarmkit_tpu.models.types import Driver, VolumeAccessMode

    return VolumeSpec(
        annotations=Annotations(name=name), group=group,
        driver=Driver(name=driver),
        access_mode=VolumeAccessMode(sharing=sharing or 0),
        secrets=dict(secrets or {}))


def test_volume_crud_lifecycle(api):
    from swarmkit_tpu.models.types import VolumeAvailability

    with pytest.raises(InvalidArgument, match="driver must be specified"):
        api.create_volume(_vol_spec(driver=""))
    with pytest.raises(InvalidArgument, match="name must be provided"):
        api.create_volume(_vol_spec(name=""))

    v = api.create_volume(_vol_spec())
    assert api.get_volume(v.id).spec.annotations.name == "vol1"
    with pytest.raises(AlreadyExists):
        api.create_volume(_vol_spec())

    # only labels + availability are mutable
    spec2 = v.spec.copy()
    spec2.group = "changed"
    with pytest.raises(InvalidArgument, match="Group cannot be updated"):
        api.update_volume(v.id, v.meta.version.index, spec2)
    spec3 = v.spec.copy()
    spec3.annotations.labels["tier"] = "fast"
    spec3.availability = int(VolumeAvailability.DRAIN)
    updated = api.update_volume(v.id, v.meta.version.index, spec3)
    assert updated.spec.annotations.labels == {"tier": "fast"}
    assert updated.spec.availability == int(VolumeAvailability.DRAIN)

    assert [x.id for x in api.list_volumes()] == [v.id]
    api.remove_volume(v.id)           # unused -> marked pending delete
    assert api.get_volume(v.id).pending_delete
    api.remove_volume(v.id, force=True)
    with pytest.raises(NotFound):
        api.get_volume(v.id)


def test_volume_create_reports_all_missing_secrets(api):
    with pytest.raises(InvalidArgument, match="secrets not found"):
        api.create_volume(_vol_spec(secrets={"a": "sec-a", "b": "sec-b"}))


def test_volume_in_use_refuses_remove(api):
    from swarmkit_tpu.models.objects import Volume
    from swarmkit_tpu.models.types import VolumePublishStatus

    v = api.create_volume(_vol_spec())

    def publish(tx):
        cur = tx.get(Volume, v.id).copy()
        cur.publish_status.append(VolumePublishStatus(node_id="n1"))
        tx.update(cur)
    api.store.update(publish)
    with pytest.raises(FailedPrecondition, match="still in use"):
        api.remove_volume(v.id)


# -------------------------------- extensions + resources (extension.go parity)

def test_extension_and_resource_lifecycle(api):
    with pytest.raises(InvalidArgument, match="name must be provided"):
        api.create_extension(Annotations(name=""))
    ext = api.create_extension(Annotations(name="widgets"),
                               "custom widget type")
    with pytest.raises(AlreadyExists):
        api.create_extension(Annotations(name="widgets"))

    with pytest.raises(InvalidArgument, match="not registered"):
        api.create_resource(Annotations(name="w1"), "gadgets")
    r = api.create_resource(Annotations(name="w1"), "widgets",
                            b"payload-1")
    assert api.get_resource(r.id).payload == b"payload-1"
    assert [x.id for x in api.list_resources(kind="widgets")] == [r.id]

    # extension removal is refused while resources of its kind exist
    with pytest.raises(InvalidArgument, match="in use by resources"):
        api.remove_extension(ext.id)

    # payload + labels mutable; renames rejected
    ann = r.annotations.copy()
    ann.name = "renamed"
    with pytest.raises(InvalidArgument, match="Name cannot be updated"):
        api.update_resource(r.id, r.meta.version.index, annotations=ann)
    r2 = api.update_resource(r.id, r.meta.version.index,
                             payload=b"payload-2")
    assert r2.payload == b"payload-2"

    api.remove_resource(r.id)
    api.remove_extension(ext.id)
    with pytest.raises(NotFound):
        api.get_extension(ext.id)


# ------------------------------------------------------------- join tokens

@requires_crypto
def test_rotate_join_token_via_api():
    from swarmkit_tpu.manager import Manager
    from swarmkit_tpu.models import Cluster
    from swarmkit_tpu.state.store import ByName

    m = Manager(use_device_scheduler=False)
    m.run()
    try:
        cluster = m.store.view(
            lambda tx: tx.find(Cluster, ByName("default")))[0]
        old = cluster.root_ca.join_tokens.worker
        new = m.control_api.rotate_join_token(NodeRole.WORKER)
        assert new != old
        assert m.root_ca.join_token(NodeRole.WORKER) == new
        cluster = m.store.view(
            lambda tx: tx.find(Cluster, ByName("default")))[0]
        assert cluster.root_ca.join_tokens.worker == new
        with pytest.raises(Exception):
            m.root_ca.role_for_token(old)
    finally:
        m.stop()


# ------------------------------------------------------------------ CLI nouns

@requires_crypto
def test_cli_volume_network_cluster_nouns():
    from swarmkit_tpu.cli import run_command
    from swarmkit_tpu.manager import Manager

    m = Manager(use_device_scheduler=False)
    m.run()
    api2 = m.control_api
    try:
        vid = run_command(["volume", "create", "data1",
                           "--driver", "csi.example",
                           "--group", "fast"], api2)
        out = run_command(["volume", "ls"], api2)
        assert "data1" in out and "fast" in out
        out = run_command(["volume", "inspect", "data1"], api2)
        assert vid in out
        run_command(["volume", "drain", "data1"], api2)
        run_command(["volume", "rm", "data1", "--force"], api2)
        assert "data1" not in run_command(["volume", "ls"], api2)

        nid = run_command(["network", "create", "backend",
                           "--subnet", "10.99.0.0/24"], api2)
        assert "backend" in run_command(["network", "ls"], api2)
        assert "10.99.0.0/24" in run_command(
            ["network", "inspect", "backend"], api2)
        run_command(["network", "rm", "backend"], api2)

        out = run_command(["cluster", "inspect"], api2)
        assert "SWMTKN-1-" in out
        ls = run_command(["cluster", "ls"], api2)
        assert "default" in ls and "AUTOLOCK" in ls
        token = run_command(["cluster", "rotate-token", "worker"], api2)
        assert token.startswith("SWMTKN-1-")
        assert token in run_command(["cluster", "inspect"], api2)

        run_command(["extension", "create", "widgets"], api2)
        run_command(["resource", "create", "w1", "widgets"], api2)
        assert "w1" in run_command(["resource", "ls"], api2)
        run_command(["resource", "rm", "w1"], api2)
        run_command(["extension", "rm", "widgets"], api2)
    finally:
        m.stop()


@requires_crypto
def test_list_service_statuses():
    """Desired/running counts per service — the `service ls` helper
    (reference: manager/controlapi/service.go:1047 ListServiceStatuses:
    replicated desired = replicas; global desired counts live tasks;
    unknown ids return zeroed statuses)."""
    from swarmkit_tpu.cli import run_command
    from swarmkit_tpu.manager import Manager
    from swarmkit_tpu.models import (
        Annotations, ContainerSpec, ServiceMode, ServiceSpec, TaskSpec,
    )

    from test_orchestrator import poll

    m = Manager(use_device_scheduler=False)
    m.run()
    api = m.control_api
    try:
        run_command(["service", "create", "--name", "web",
                     "--image", "nginx", "--replicas", "3"], api)
        svc = api.list_services("web")[0]
        gsvc = api.create_service(ServiceSpec(
            annotations=Annotations(name="agent-everywhere"),
            task=TaskSpec(container=ContainerSpec(image="agent")),
            mode=ServiceMode.GLOBAL))
        # no agents: replicated tasks never RUN, but desired is 3 now
        sts = {st["service_id"]: st for st in api.list_service_statuses(
            [svc.id, gsvc.id, "no-such-service"])}
        assert sts[svc.id]["desired_tasks"] == 3
        assert sts["no-such-service"] == {
            "service_id": "no-such-service", "desired_tasks": 0,
            "running_tasks": 0, "completed_tasks": 0}

        # a node joins: global desired becomes 1, and once tasks run the
        # running counts follow
        from swarmkit_tpu.agent.testutils import TestExecutor
        from swarmkit_tpu.node import Node as ClusterNode
        import tempfile
        node = ClusterNode(TestExecutor(hostname="w1"), tempfile.mkdtemp())
        cluster = api.get_default_cluster()
        node.load_or_join(m.ca_server, cluster.root_ca.join_tokens.worker)
        node.start(m.dispatcher, store=m.store, hostname="w1")
        try:
            def counts():
                sts = {st["service_id"]: st
                       for st in api.list_service_statuses(
                           [svc.id, gsvc.id])}
                return (sts[svc.id]["running_tasks"] == 3
                        and sts[gsvc.id]["desired_tasks"] == 1
                        and sts[gsvc.id]["running_tasks"] == 1)
            poll(counts, timeout=20,
                 msg="statuses should reach 3/3 and 1/1")
            ls = run_command(["service", "ls"], api)
            assert "3/3" in ls and "1/1" in ls
        finally:
            node.stop()
    finally:
        m.stop()


@requires_crypto
def test_cli_nouns_over_remote_control_client():
    """The same CLI nouns drive a remote manager through the mTLS control
    client (reference: swarmctl against a live manager)."""
    from swarmkit_tpu.cli import run_command
    from swarmkit_tpu.manager import Manager
    from swarmkit_tpu.models import Cluster
    from swarmkit_tpu.net import ManagerServer, RemoteControlClient, issue_certificate
    from swarmkit_tpu.state.store import ByName
    from swarmkit_tpu.utils import new_id

    m = Manager(use_device_scheduler=False)
    m.run()
    srv = ManagerServer(m)
    srv.start()
    try:
        cluster = m.store.view(
            lambda tx: tx.find(Cluster, ByName("default")))[0]
        op = issue_certificate(srv.addr, new_id(),
                               cluster.root_ca.join_tokens.manager)
        ctl = RemoteControlClient(srv.addr, op)
        run_command(["volume", "create", "rv", "--driver", "csi.x"], ctl)
        assert "rv" in run_command(["volume", "ls"], ctl)
        run_command(["volume", "rm", "rv", "--force"], ctl)
        run_command(["network", "create", "rnet"], ctl)
        assert "rnet" in run_command(["network", "ls"], ctl)
        run_command(["network", "rm", "rnet"], ctl)
        tok = run_command(["cluster", "rotate-token", "worker"], ctl)
        assert tok.startswith("SWMTKN-1-")
        run_command(["extension", "create", "kinds"], ctl)
        run_command(["resource", "create", "k1", "kinds"], ctl)
        assert "k1" in run_command(["resource", "ls"], ctl)
        run_command(["resource", "rm", "k1"], ctl)
        run_command(["secret", "create", "rs", "payload"], ctl)
        insp = run_command(["secret", "inspect", "rs"], ctl)
        assert "Name: rs" in insp and "payload" not in insp
        run_command(["secret", "rm", "rs"], ctl)
        run_command(["config", "create", "rc", "k=v"], ctl)
        assert "Data: k=v" in run_command(["config", "inspect", "rc"], ctl)
        run_command(["config", "rm", "rc"], ctl)
        run_command(["extension", "rm", "kinds"], ctl)
        # service ls pulls running/desired through the wire statuses RPC
        run_command(["service", "create", "--name", "rweb",
                     "--image", "nginx", "--replicas", "2"], ctl)
        assert "0/2" in run_command(["service", "ls"], ctl)
        run_command(["service", "rm", "rweb"], ctl)
        ctl.close()
    finally:
        srv.stop()
        m.stop()


@requires_crypto
def test_csi_volume_lifecycle_e2e_from_cli():
    """VERDICT r2 item 3 done-criterion: volume create -> schedule a task
    using it -> publish -> drain -> unpublish, all driven from the CLI
    (reference: volume.go + csi manager + VolumesFilter together)."""
    import time

    from swarmkit_tpu.agent import Agent
    from swarmkit_tpu.agent.testutils import TestExecutor
    from swarmkit_tpu.cli import run_command
    from swarmkit_tpu.manager import Manager
    from swarmkit_tpu.manager.dispatcher import Config_
    from swarmkit_tpu.models import Task, TaskState
    from swarmkit_tpu.models.types import VolumePublishStatus

    from test_orchestrator import poll
    from test_scheduler import make_ready_node

    m = Manager(dispatcher_config=Config_(
        heartbeat_period=0.3, heartbeat_epsilon=0.02,
        process_updates_interval=0.02, assignment_batching_wait=0.02),
        use_device_scheduler=False)
    m.run()
    api2 = m.control_api
    n = make_ready_node("csi-n1")
    m.store.update(lambda tx, n=n: tx.create(n))
    agent = Agent(n.id, TestExecutor(hostname="csi-n1"), m.dispatcher)
    agent.start()
    try:
        vid = run_command(["volume", "create", "data1",
                           "--driver", "inmem"], api2)
        # csi manager creates it plugin-side
        poll(lambda: api2.get_volume(vid).volume_info is not None
             and api2.get_volume(vid).volume_info.volume_id,
             timeout=10, msg="csi manager should create the volume")

        run_command(["service", "create", "--name", "dbsvc",
                     "--image", "db", "--replicas", "1",
                     "--csi-volume", "data1:/data"], api2)

        def task_running_with_volume():
            ts = [t for t in api2.list_tasks()
                  if t.service_annotations.name == "dbsvc"
                  and t.desired_state == TaskState.RUNNING]
            return (ts and ts[0].status.state == TaskState.RUNNING
                    and any(va.id == vid for va in ts[0].volumes))
        poll(task_running_with_volume, timeout=20,
             msg="task should run with the volume attached")

        def published():
            v = api2.get_volume(vid)
            return any(p.node_id == n.id and p.state ==
                       VolumePublishStatus.State.PUBLISHED
                       for p in v.publish_status)
        poll(published, timeout=10,
             msg="csi manager should controller-publish on the node")
        assert "published" in run_command(
            ["volume", "inspect", "data1"], api2)

        # drain: the volume enforcer evicts the task, the csi manager
        # unpublishes once unused
        run_command(["volume", "drain", "data1"], api2)

        def unpublished():
            v = api2.get_volume(vid)
            return not v.publish_status
        poll(unpublished, timeout=20,
             msg="drained volume should unpublish after eviction")

        # and now removable without force
        run_command(["service", "rm", "dbsvc"], api2)
        run_command(["volume", "rm", "data1"], api2)
        poll(lambda: not [v for v in api2.list_volumes()
                          if v.spec.annotations.name == "data1"],
             timeout=10, msg="pending-delete volume should be deleted")
    finally:
        agent.stop()
        m.stop()


@requires_crypto
def test_node_side_csi_staging_with_process_executor(tmp_path):
    """Worker-side CSI (reference: agent/csi/volumes.go): the agent
    stages/publishes the volume to a local path before the process task
    starts, exposes it via env, and unstages after shutdown."""
    import os

    from swarmkit_tpu.agent import Agent
    from swarmkit_tpu.agent.procexec import ProcessExecutor
    from swarmkit_tpu.cli import run_command
    from swarmkit_tpu.manager import Manager
    from swarmkit_tpu.manager.dispatcher import Config_
    from swarmkit_tpu.models import TaskState
    from swarmkit_tpu.models.specs import (
        ContainerSpec, ServiceSpec,
    )
    from swarmkit_tpu.models import (
        ReplicatedService, ServiceMode, TaskSpec,
    )
    from swarmkit_tpu.models.types import Mount, MountType

    from test_orchestrator import poll
    from test_scheduler import make_ready_node

    m = Manager(dispatcher_config=Config_(
        heartbeat_period=0.3, heartbeat_epsilon=0.02,
        process_updates_interval=0.02, assignment_batching_wait=0.02),
        use_device_scheduler=False)
    m.run()
    api2 = m.control_api
    n = make_ready_node("csi-p1")
    m.store.update(lambda tx, n=n: tx.create(n))
    agent = Agent(n.id, ProcessExecutor(
        hostname="csi-p1", log_dir=str(tmp_path / "logs")), m.dispatcher,
        task_db_path=str(tmp_path / "node" / "tasks.db"))
    agent.start()
    try:
        vid = run_command(["volume", "create", "pdata",
                           "--driver", "inmem"], api2)
        poll(lambda: api2.get_volume(vid).volume_info is not None
             and api2.get_volume(vid).volume_info.volume_id, timeout=10)

        marker = tmp_path / "proof"
        svc = api2.create_service(ServiceSpec(
            annotations=Annotations(name="vol-writer"),
            task=TaskSpec(container=ContainerSpec(
                image="process",
                command=["sh", "-c",
                         f'echo "$SWARM_VOLUME_DATA" > {marker}; '
                         'touch "$SWARM_VOLUME_DATA/wrote"; sleep 30'],
                mounts=[Mount(type=MountType.CSI, source="pdata",
                              target="/data")])),
            mode=ServiceMode.REPLICATED,
            replicated=ReplicatedService(replicas=1)))

        def running():
            ts = [t for t in api2.list_tasks(service_id=svc.id)
                  if t.desired_state == TaskState.RUNNING]
            return ts and ts[0].status.state == TaskState.RUNNING
        poll(running, timeout=20, msg="volume task should run")

        poll(lambda: marker.exists() and marker.read_text().strip(),
             timeout=10, msg="task should see the volume path env")
        vol_path = marker.read_text().strip()
        assert os.path.isdir(vol_path), vol_path
        # the task's shell writes the marker first and touches this next
        poll(lambda: os.path.exists(os.path.join(vol_path, "wrote")),
             timeout=10, msg="task should write into the volume")
        assert agent.volumes.ready(vid)

        # removal: task goes away, node unstages, path is gone
        api2.remove_service(svc.id)
        poll(lambda: not agent.volumes.ready(vid), timeout=20,
             msg="volume should unstage after the task is removed")
        poll(lambda: not os.path.exists(vol_path), timeout=10,
             msg="published path should be cleaned up")
        poll(lambda: not api2.get_volume(vid).publish_status, timeout=20,
             msg="controller-unpublish should complete")
    finally:
        agent.stop()
        m.stop()


def test_cli_cluster_update_live_settings():
    """swarmctl cluster update flags flow into the watched ClusterSpec
    (reference: swarmctl cluster update)."""
    from swarmkit_tpu.cli import run_command
    from swarmkit_tpu.manager.controlapi import ControlAPI
    from swarmkit_tpu.models import Cluster
    from swarmkit_tpu.models.specs import ClusterSpec
    from swarmkit_tpu.models.types import Annotations
    from swarmkit_tpu.state import MemoryStore

    store = MemoryStore()
    store.update(lambda tx: tx.create(Cluster(
        id="c1", spec=ClusterSpec(annotations=Annotations(name="default")))))
    api = ControlAPI(store)
    out = run_command(["cluster", "update", "--heartbeat-period", "2.5",
                       "--cert-expiry", "3600",
                       "--task-history-limit", "9"], api)
    assert "heartbeat-period=2.5s" in out
    c = api.get_default_cluster()
    assert c.spec.dispatcher.heartbeat_period == 2.5
    assert c.spec.ca_config.node_cert_expiry == 3600
    assert c.spec.orchestration.task_history_retention_limit == 9
    assert run_command(["cluster", "update"], api) == "nothing to update"


def test_cluster_responses_redact_key_material():
    """get/list/get_default cluster strip signing + unlock keys but keep
    join tokens, and a redacted inspect→update round trip preserves the
    stored signing CA material (reference: controlapi/cluster.go:252
    redactClusters)."""
    from swarmkit_tpu.manager.controlapi import ControlAPI
    from swarmkit_tpu.models import Cluster
    from swarmkit_tpu.models.objects import RootCAState
    from swarmkit_tpu.models.specs import ClusterSpec
    from swarmkit_tpu.models.types import (
        Annotations, EncryptionKey, JoinTokens,
    )
    from swarmkit_tpu.state import MemoryStore

    store = MemoryStore()
    spec = ClusterSpec(annotations=Annotations(name="default"))
    spec.ca_config.signing_ca_key = b"SIGNKEY"
    spec.ca_config.signing_ca_cert = b"SIGNCERT"
    store.update(lambda tx: tx.create(Cluster(
        id="c1", spec=spec,
        root_ca=RootCAState(
            ca_key=b"CAKEY", ca_cert=b"CACERT",
            rotation_ca_key=b"ROTKEY",
            join_tokens=JoinTokens(worker="SWMTKN-w", manager="SWMTKN-m")),
        unlock_keys=[EncryptionKey(subsystem="manager", key=b"UNLOCK")],
        network_bootstrap_keys=[
            EncryptionKey(subsystem="networking", key=b"GOSSIP")])))
    api = ControlAPI(store)

    for c in (api.get_cluster("c1"), api.get_default_cluster(),
              *api.list_clusters()):
        assert c.spec.ca_config.signing_ca_key == b""
        assert c.spec.ca_config.signing_ca_cert == b""
        assert c.root_ca.ca_key == b""
        assert c.root_ca.rotation_ca_key == b""
        assert c.unlock_keys == []
        assert c.network_bootstrap_keys == []
        # public material survives redaction
        assert c.root_ca.ca_cert == b"CACERT"
        assert c.root_ca.join_tokens.worker == "SWMTKN-w"

    # in-process raw reads still see the key material (autolock path)
    assert api._default_cluster_raw().unlock_keys[0].key == b"UNLOCK"

    # redacted round trip: update with a blanked spec keeps signing keys
    c = api.get_default_cluster()
    new_spec = c.spec.copy()
    new_spec.dispatcher.heartbeat_period = 7.0
    api.update_cluster(c.id, c.meta.version.index, new_spec)
    stored = api._default_cluster_raw()
    assert stored.spec.dispatcher.heartbeat_period == 7.0
    assert stored.spec.ca_config.signing_ca_key == b"SIGNKEY"
    assert stored.spec.ca_config.signing_ca_cert == b"SIGNCERT"
