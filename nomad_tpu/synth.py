"""Synthetic cluster generator for benchmarks and the multi-chip dry run.

Produces the BASELINE.json workload shapes (10K-node / 100K-pending-alloc
synthetic cluster; service bin-pack, batch constraint+affinity, spread across
3 DCs, system + preemption, device asks) without the per-object overhead of
the full mock fixtures: nodes/allocs are built once and fed through the
normal `InMemState`/`ClusterTensors` ingestion path.
"""
from __future__ import annotations

import random
import uuid
from typing import List, Optional, Tuple

from .mock import alloc_resources
from .structs import (
    Allocation,
    Job,
    NetworkResource,
    Node,
    NodeReservedResources,
    NodeResources,
    RequestedDevice,
    Resources,
    Task,
    TaskGroup,
    EphemeralDisk,
    JOB_TYPE_SERVICE,
)
from .structs.job import Affinity, Constraint, Spread, SpreadTarget

DATACENTERS = ("dc1", "dc2", "dc3")
NODE_CLASSES = ("linux-small", "linux-medium", "linux-large")


def synth_node(rng: random.Random, i: int) -> Node:
    """One synthetic node: 3 size classes over 3 DCs, linux attrs, exec+docker
    drivers (mirrors the mock.Node shape, nomad/mock/mock.go:13)."""
    cls = NODE_CLASSES[i % 3]
    mult = {"linux-small": 1, "linux-medium": 2, "linux-large": 4}[cls]
    node = Node(
        id=str(uuid.UUID(int=rng.getrandbits(128), version=4)),
        name=f"node-{i}",
        datacenter=DATACENTERS[i % len(DATACENTERS)],
        node_class=cls,
        attributes={
            "kernel.name": "linux",
            "arch": "amd64",
            "cpu.numcores": str(4 * mult),
            "driver.exec": "1",
            "driver.docker": "1",
            "rack": f"r{i % 20}",
        },
        node_resources=NodeResources(
            cpu=4000 * mult,
            memory_mb=8192 * mult,
            disk_mb=100 * 1024,
            networks=[
                NetworkResource(
                    device="eth0", ip=f"10.{(i >> 16) & 255}.{(i >> 8) & 255}.{i & 255}",
                    cidr="10.0.0.0/8", mbits=1000,
                )
            ],
        ),
        reserved_resources=NodeReservedResources(
            cpu=100, memory_mb=256, disk_mb=4 * 1024, reserved_ports="22"
        ),
    )
    if i % 4 == 0:
        # Every 4th node carries GPUs (BASELINE config 5: device-plugin
        # nvidia/gpu requests + per-node reserved resources)
        from .structs.resources import NodeDeviceInstance, NodeDeviceResource

        node.node_resources.devices = [NodeDeviceResource(
            vendor="nvidia", type="gpu", name="1080ti",
            instances=[NodeDeviceInstance(id=f"gpu-{i}-{k}", healthy=True)
                       for k in range(4)],
            attributes={"memory": 11, "cuda_cores": 3584},
        )]
    node.compute_class()
    return node


def synth_service_job(rng: random.Random, count: int = 8,
                      with_affinity: bool = False,
                      with_spread: bool = False,
                      distinct_hosts: bool = False,
                      with_devices: bool = False,
                      distinct_property: bool = False,
                      datacenter: Optional[str] = None) -> Job:
    """One service job: 1 task group, CPU+MiB bin-pack ask (BASELINE config 1),
    optionally the batch/spread/distinct_hosts/device/distinct_property
    stanzas (configs 2-5). `datacenter` pins the job to ONE dc — jobs
    pinned to different dcs have disjoint node footprints, the shape the
    wave-dispatch partition (ISSUE 12) parallelizes."""
    jid = f"svc-{rng.getrandbits(48):012x}"
    constraints = [Constraint(ltarget="${attr.kernel.name}", rtarget="linux",
                              operand="=")]
    if distinct_hosts:
        constraints.append(Constraint(operand="distinct_hosts"))
    if distinct_property:
        constraints.append(Constraint(ltarget="${attr.rack}", rtarget="2",
                                      operand="distinct_property"))
    affinities = []
    if with_affinity:
        affinities.append(
            Affinity(ltarget="${node.class}", rtarget="linux-large",
                     operand="=", weight=50)
        )
    spreads = []
    if with_spread:
        spreads.append(
            Spread(attribute="${node.datacenter}", weight=100,
                   spread_target=[
                       SpreadTarget(value="dc1", percent=50),
                       SpreadTarget(value="dc2", percent=30),
                       SpreadTarget(value="dc3", percent=20),
                   ])
        )
    return Job(
        id=jid,
        name=jid,
        type=JOB_TYPE_SERVICE,
        priority=50,
        datacenters=[datacenter] if datacenter else list(DATACENTERS),
        constraints=constraints,
        affinities=affinities,
        spreads=spreads,
        task_groups=[
            TaskGroup(
                name="web",
                count=count,
                ephemeral_disk=EphemeralDisk(size_mb=150),
                tasks=[
                    Task(
                        name="web",
                        driver="exec",
                        resources=Resources(
                            cpu=rng.choice((250, 500, 1000)),
                            memory_mb=rng.choice((128, 256, 512)),
                            devices=([RequestedDevice(name="nvidia/gpu",
                                                      count=1)]
                                     if with_devices else []),
                        ),
                    )
                ],
            )
        ],
    )


def synth_system_job(rng: random.Random, priority: int = 80) -> Job:
    """One system job (BASELINE config 4): one alloc per eligible node,
    priority above the synthetic filler allocs so priority-based preemption
    (system_sched.go:268) can evict on full nodes."""
    jid = f"sys-{rng.getrandbits(48):012x}"
    return Job(
        id=jid,
        name=jid,
        type="system",
        priority=priority,
        datacenters=list(DATACENTERS),
        constraints=[Constraint(ltarget="${attr.kernel.name}",
                                rtarget="linux", operand="=")],
        task_groups=[
            TaskGroup(
                name="mon",
                count=1,
                ephemeral_disk=EphemeralDisk(size_mb=50),
                tasks=[
                    Task(
                        name="mon",
                        driver="exec",
                        resources=Resources(
                            cpu=rng.choice((500, 1000)),
                            memory_mb=rng.choice((128, 256)),
                        ),
                    )
                ],
            )
        ],
    )


def synth_alloc(rng: random.Random, node: Node, shared_job: Job) -> Allocation:
    """A pre-existing (running) alloc occupying capacity on `node`."""
    return Allocation(
        id=f"{rng.getrandbits(128):032x}",
        eval_id="synth",
        namespace="default",
        name=f"{shared_job.id}.web[0]",
        node_id=node.id,
        job_id=shared_job.id,
        job=shared_job,
        task_group="web",
        allocated_resources=alloc_resources(
            cpu=rng.choice((100, 200, 400)),
            memory_mb=rng.choice((64, 128, 256)),
            disk_mb=100,
        ),
        desired_status="run",
        client_status="running",
    )


def build_synthetic_state(
    n_nodes: int,
    n_allocs: int,
    seed: int = 0,
):
    """Build an InMemState with n_nodes nodes and n_allocs running allocs
    (the 10K-node / 100K-alloc synthetic of BASELINE.json at full size)."""
    from .scheduler.harness import InMemState

    rng = random.Random(seed)
    state = InMemState()
    nodes: List[Node] = []
    for i in range(n_nodes):
        node = synth_node(rng, i)
        nodes.append(node)
        state.upsert_node(node)
    filler_jobs = [synth_service_job(rng) for _ in range(max(n_allocs // 200, 1))]
    for j in filler_jobs:
        state.upsert_job(j)
    for i in range(n_allocs):
        node = nodes[rng.randrange(n_nodes)]
        job = filler_jobs[i % len(filler_jobs)]
        state.upsert_alloc(synth_alloc(rng, node, job))
    return state, nodes
