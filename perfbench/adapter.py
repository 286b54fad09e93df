"""Plain records (`cluster.py`) -> the program's structs and wire form.

The one file of the benchmark, beside the launcher, that imports the
program. The shapes are `nomad_tpu/synth.py`'s (`synth_node`,
`synth_service_job`, `synth_alloc`); the values come from the records.
"""
from __future__ import annotations

import json

from nomad_tpu.mock import alloc_resources
from nomad_tpu.structs import (JOB_TYPE_SERVICE, Allocation, EphemeralDisk,
                               Job, NetworkResource, Node,
                               NodeReservedResources, NodeResources,
                               RequestedDevice, Resources, Task, TaskGroup)
from nomad_tpu.structs.codec import to_json_tree, to_wire
from nomad_tpu.structs.job import Affinity, Constraint, Spread, SpreadTarget


def to_node(rec: dict) -> Node:
    i, reserved = rec["i"], rec["reserved"]
    node = Node(
        id=rec["id"], name=rec["name"], datacenter=rec["datacenter"],
        node_class=rec["class"],
        attributes={"kernel.name": "linux", "arch": "amd64",
                    "cpu.numcores": rec["cores"], "driver.exec": "1",
                    "driver.docker": "1", "rack": rec["rack"]},
        node_resources=NodeResources(
            cpu=rec["cpu"], memory_mb=rec["memory"], disk_mb=rec["disk"],
            networks=[NetworkResource(
                device="eth0",
                ip=f"10.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}",
                cidr="10.0.0.0/8", mbits=1000)]),
        reserved_resources=NodeReservedResources(
            cpu=reserved["cpu"], memory_mb=reserved["memory"],
            disk_mb=reserved["disk"], reserved_ports="22"),
    )
    node.meta["cell"] = rec["cell"]
    if rec["gpus"]:
        from nomad_tpu.structs.resources import (NodeDeviceInstance,
                                                 NodeDeviceResource)

        node.node_resources.devices = [NodeDeviceResource(
            vendor="nvidia", type="gpu", name="1080ti",
            instances=[NodeDeviceInstance(id=f"gpu-{i}-{k}", healthy=True)
                       for k in range(rec["gpus"])],
            attributes={"memory": 11, "cuda_cores": 3584})]
    node.compute_class()
    return node


def to_job(spec: dict) -> Job:
    constraints = [Constraint(ltarget=lt, rtarget=rv, operand=op)
                   for lt, op, rv in spec["constraints"]]
    if spec["distinct_hosts"]:
        constraints.append(Constraint(operand="distinct_hosts"))
    if spec["distinct_property"]:
        target, allowed = spec["distinct_property"]
        constraints.append(Constraint(ltarget=target, rtarget=str(allowed),
                                      operand="distinct_property"))
    spreads = []
    if spec["spread"]:
        sp = spec["spread"]
        spreads = [Spread(attribute=sp["attribute"], weight=sp["weight"],
                          spread_target=[SpreadTarget(value=v, percent=p)
                                         for v, p in sp["targets"]])]
    return Job(
        id=spec["id"], name=spec["id"], type=JOB_TYPE_SERVICE, priority=50,
        datacenters=list(spec["datacenters"]),
        constraints=constraints,
        affinities=[Affinity(ltarget=lt, rtarget=rv, operand=op, weight=w)
                    for lt, op, rv, w in spec["affinities"]],
        spreads=spreads,
        task_groups=[TaskGroup(
            name="web", count=spec["count"],
            ephemeral_disk=EphemeralDisk(size_mb=spec["disk"]),
            tasks=[Task(name="web", driver="exec", resources=Resources(
                cpu=spec["cpu"], memory_mb=spec["memory"],
                devices=([RequestedDevice(name="nvidia/gpu",
                                          count=spec["gpus"])]
                         if spec["gpus"] else [])))])],
    )


def job_payload(spec: dict) -> bytes:
    """The body of `PUT /v1/jobs`, as `NomadClient.register_job` sends it."""
    return json.dumps(to_json_tree({"job": to_wire(to_job(spec))})).encode()


def to_filler_alloc(rec: dict, node: Node, job: Job) -> Allocation:
    return Allocation(
        id=rec["id"], eval_id="synth", namespace="default",
        name=f"{job.id}.web[0]", node_id=node.id, job_id=job.id, job=job,
        task_group="web",
        allocated_resources=alloc_resources(
            cpu=rec["cpu"], memory_mb=rec["memory"], disk_mb=rec["disk"]),
        desired_status="run", client_status="running")
