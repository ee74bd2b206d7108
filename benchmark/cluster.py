"""The cluster and the service specs of a configuration, made from its
file and the seed.

``plain_nodes`` is the one source: plain dicts, dealt by the seed exactly
as ``chip_smoke.py make_nodes`` deals them (a copy; the smoke stays as it
is).  The reference reads the dicts; ``store_nodes`` turns the same dicts
into the program's ``Node`` objects, so both sides see one cluster.
"""

from __future__ import annotations

import json
import os
import random
from typing import List

HERE = os.path.dirname(os.path.abspath(__file__))


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


def plain_nodes(cluster: dict, seed: int) -> List[dict]:
    """``cluster["nodes"]`` READY nodes.  The seed decides which node
    gets which labels and platform; group sizes stay even.  The seed
    also deals the ``cluster["agents"]`` agent-served nodes: the
    scheduler breaks ties by node order, so the first nodes would take
    every small service and the agents far more than their share."""
    n = cluster["nodes"]
    zones, racks = cluster["zones"], cluster["racks_per_zone"]
    tiers = cluster["tiers"]
    order = list(range(n))
    deal = random.Random(seed)
    deal.shuffle(order)
    served = set(deal.sample(range(n), cluster["agents"]))
    width = max(5, len(str(n - 1)))
    rack_width = max(2, len(str(racks - 1)))
    nodes = []
    for i, j in enumerate(order):
        zone = j % zones
        rack = (j // zones) % racks
        name = f"node-{i:0{width}d}"
        nodes.append({
            "id": name, "hostname": name,
            "labels": {"zone": f"z{zone}",
                       "rack": f"z{zone}-r{rack:0{rack_width}d}",
                       "tier": tiers[j % len(tiers)]},
            "os": "windows" if j % cluster["windows_every"]
            == cluster["windows_every"] - 1 else "linux",
            "arch": "arm64" if j % cluster["arm64_every"]
            == cluster["arm64_every"] - 1 else "amd64",
            "nano_cpus": cluster["node_nano_cpus"],
            "memory_bytes": cluster["node_memory_bytes"],
            "ready": True,
            "agent": i in served})
    return nodes


def store_nodes(nodes: List[dict]) -> list:
    """The program's ``Node`` objects for the plain dicts."""
    from swarmkit_tpu.models import (
        Annotations, Node, NodeDescription, NodeSpec, NodeState,
        NodeStatus, Platform, Resources,
    )
    return [Node(
        id=n["id"],
        spec=NodeSpec(annotations=Annotations(name=n["id"],
                                              labels=dict(n["labels"]))),
        status=NodeStatus(state=NodeState.READY),
        description=NodeDescription(
            hostname=n["hostname"],
            platform=Platform(os=n["os"], architecture=n["arch"]),
            resources=Resources(nano_cpus=n["nano_cpus"],
                                memory_bytes=n["memory_bytes"])))
        for n in nodes]


def service_spec(name: str, shape: dict, replicas: int):
    """A replicated ``ServiceSpec`` of one of the configuration's
    shapes."""
    from swarmkit_tpu.models import (
        Annotations, Placement, PlacementPreference, Platform,
        ReplicatedService, Resources, ResourceRequirements, ServiceMode,
        ServiceSpec, SpreadOver, TaskSpec,
    )
    from swarmkit_tpu.models.specs import ContainerSpec
    placement = {}
    if shape["constraints"]:
        placement["constraints"] = list(shape["constraints"])
    if shape["platforms"]:
        placement["platforms"] = [Platform(**p) for p in shape["platforms"]]
    if shape["spread_over"]:
        placement["preferences"] = [
            PlacementPreference(spread=SpreadOver(spread_descriptor=d))
            for d in shape["spread_over"]]
    if shape["strategy"] != "spread":
        placement["strategy"] = shape["strategy"]
    return ServiceSpec(
        annotations=Annotations(name=name),
        task=TaskSpec(
            container=ContainerSpec(image="bench"),
            resources=ResourceRequirements(reservations=Resources(
                nano_cpus=shape["nano_cpus"],
                memory_bytes=shape["memory_bytes"])),
            placement=Placement(**placement)),
        mode=ServiceMode.REPLICATED,
        replicated=ReplicatedService(replicas=replicas))
