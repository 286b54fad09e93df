"""The benchmark's own cluster, fillers and jobs, as plain data from a seed.

Nothing here imports the program or JAX: the launcher turns these records
into the program's structs (`adapter.py`), the plain reference
(`reference.py`) reads them as they are. They are copies of the smoke's
generators (`chip_smoke.py`, `nomad_tpu/synth.py`) and differ in exactly
this: datacenter = (i // 3) % 3, so every datacenter holds every class;
fillers are dealt to nodes in proportion to the class multiplier, so every
node starts at about the same share and none over; asks are small enough
that a window cannot fill the cluster; the share of device jobs is set by
the GPUs there are. Sizes, choices, the mix and the shape of each job kind
(`kinds`: a record a kind) come from the configuration's file.
"""
from __future__ import annotations

import copy
import random
import uuid
from typing import Dict, List

import numpy as np

DIMS = ("cpu", "memory", "disk")


def datacenters(cfg: dict) -> List[str]:
    return [f"dc{d + 1}" for d in range(int(cfg["datacenters"]))]


def _rng(seed: int, stream: str) -> random.Random:
    """One independent stream per purpose, so that asking for more jobs
    never changes the cluster."""
    return random.Random(f"{int(seed)}/{stream}")


def make_nodes(cfg: dict, seed: int) -> List[dict]:
    """The node shapes are the configuration's: `classes` (name ->
    multiplier of the `node_*` sizes, dealt in turn), `datacenters`,
    `racks`, `cells`, `gpus_per_node` on every `gpu_every`-th node,
    `reserved` off every node."""
    rng = _rng(seed, "nodes")
    classes = list(cfg["classes"].items())
    dcs = datacenters(cfg)
    racks, cells = int(cfg["racks"]), int(cfg["cells"])
    gpu_every, gpus = int(cfg["gpu_every"]), int(cfg["gpus_per_node"])
    out = []
    for i in range(int(cfg["nodes"])):
        name, mult = classes[i % len(classes)]
        out.append({
            "i": i,
            "id": str(uuid.UUID(int=rng.getrandbits(128), version=4)),
            "name": f"node-{i}",
            # every datacenter holds every class
            "datacenter": dcs[(i // len(classes)) % len(dcs)],
            "class": name,
            "mult": int(mult),
            "cores": str(4 * int(mult)),
            "rack": f"r{i % racks}",
            "cell": f"c{i % cells}",
            "gpus": gpus if i % gpu_every == 0 else 0,
            "cpu": int(cfg["node_cpu_mhz"]) * int(mult),
            "memory": int(cfg["node_memory_mib"]) * int(mult),
            "disk": int(cfg["node_disk_mib"]),
            "reserved": cfg["reserved"],
        })
    return out


def make_fillers(cfg: dict, seed: int, nodes: List[dict]) -> List[dict]:
    """`allocs` running allocations, dealt over a seeded shuffle of one slot
    per unit of class multiplier: a node of multiplier m gets m shares."""
    rng = _rng(seed, "fillers")
    slots = [n["i"] for n in nodes for _ in range(n["mult"])]
    rng.shuffle(slots)
    f = cfg["filler"]
    n_jobs = max(int(cfg["allocs"]) // 200, 1)
    out = []
    for k in range(int(cfg["allocs"])):
        out.append({
            "id": f"{rng.getrandbits(128):032x}",
            "node": slots[k % len(slots)],
            "job": k % n_jobs,
            "cpu": rng.choice(f["cpu"]),
            "memory": rng.choice(f["memory"]),
            "disk": int(f["disk"]),
        })
    return out


def filler_job_ids(cfg: dict, seed: int) -> List[str]:
    rng = _rng(seed, "filler-jobs")
    n_jobs = max(int(cfg["allocs"]) // 200, 1)
    return [f"fill-{rng.getrandbits(48):012x}" for _ in range(n_jobs)]


def kinds_sequence(cfg: dict, seed: int, n: int) -> List[str]:
    """The kinds of `n` jobs: whole copies of the mix's multiset, each copy
    shuffled from the seed, so every seed sends exactly the same shares in
    another order, and any prefix of whole copies holds them too."""
    base = [k for k, c in cfg["mix"].items() for _ in range(int(c))]
    out: List[str] = []
    b = 0
    while len(out) < n:
        block = list(base)
        _rng(seed, f"kinds/{b}").shuffle(block)
        out.extend(block)
        b += 1
    return out[:n]


def make_job(cfg: dict, seed: int, k: int, kind: str, count: int) -> dict:
    """Job number `k` of a run, as a plain spec. The asks walk the nine
    (cpu, memory) pairs in an order shuffled per block of nine, so every
    seed sends the same totals. The job's shape is the default job's
    (`binpack`: every datacenter, the `linux` constraint, nothing else; the
    launcher's fillers are made of it) under the kind's record in the
    configuration's `kinds`: the shape keys that differ, as literal values.
    A kind without a record, or a record with a key that is no key of the
    shape — one that `reference.py` and `adapter.to_job` would not read —
    is an error: no cell is judged by a reference that ignores part of its
    job."""
    j = cfg["job"]
    pairs = [(c, m) for c in j["cpu"] for m in j["memory"]]
    _rng(seed, f"asks/{k // len(pairs)}").shuffle(pairs)
    cpu, mem = pairs[k % len(pairs)]
    rng = _rng(seed, f"job/{k}")
    shape = {
        "datacenters": datacenters(cfg),
        "constraints": [["${attr.kernel.name}", "=", "linux"]],
        "affinities": [],
        "spread": None,
        "distinct_hosts": False,
        "distinct_property": None,
        "gpus": 0,
    }
    record = (cfg.get("kinds") or {}).get(kind)
    if kind == "binpack":
        if record:
            raise ValueError("`binpack` is the default job: it takes no "
                             "record")
    elif record is None:
        raise ValueError(f"job kind {kind!r} has no record in the "
                         f"configuration's `kinds`")
    else:
        unknown = sorted(set(record) - set(shape))
        if unknown:
            raise ValueError(f"job kind {kind!r}: {unknown} are no keys of "
                             f"a job's shape {sorted(shape)}")
        shape.update(copy.deepcopy(record))
    return {
        "k": k,
        "id": f"svc-{rng.getrandbits(48):012x}",
        "kind": kind,
        "count": int(count),
        "cpu": cpu, "memory": mem, "disk": int(j["disk"]),
        **shape,
    }


class Cluster:
    """The node table as arrays, for the reference and the sizing checks."""

    def __init__(self, cfg: dict, seed: int) -> None:
        self.cfg = cfg
        self.seed = int(seed)
        self.nodes = make_nodes(cfg, seed)
        self.fillers = make_fillers(cfg, seed, self.nodes)
        n = len(self.nodes)
        self.raw = np.array([[nd[d] for d in DIMS] for nd in self.nodes],
                            dtype=np.float64)
        #: usable = node - reserved, per dimension (MHz, MiB, MiB)
        self.cap = self.raw - np.array(
            [[nd["reserved"][d] for d in DIMS] for nd in self.nodes],
            dtype=np.float64)
        self.used = np.zeros((n, 3), dtype=np.float64)
        for f in self.fillers:
            self.used[f["node"]] += (f["cpu"], f["memory"], f["disk"])
        self.gpus = np.array([nd["gpus"] for nd in self.nodes],
                             dtype=np.int64)
        self.index_of: Dict[str, int] = {nd["id"]: nd["i"]
                                         for nd in self.nodes}
        #: attribute columns as the constraint targets name them
        self.columns = {
            "${attr.kernel.name}": np.array(["linux"] * n),
            "${attr.arch}": np.array(["amd64"] * n),
            "${attr.cpu.numcores}": np.array([nd["cores"]
                                              for nd in self.nodes]),
            "${attr.rack}": np.array([nd["rack"] for nd in self.nodes]),
            "${meta.cell}": np.array([nd["cell"] for nd in self.nodes]),
            "${node.class}": np.array([nd["class"] for nd in self.nodes]),
            "${node.datacenter}": np.array([nd["datacenter"]
                                            for nd in self.nodes]),
            "${node.unique.name}": np.array([nd["name"]
                                             for nd in self.nodes]),
        }

    def fill(self, used: np.ndarray) -> dict:
        """Share of the cluster's raw capacity in use, per dimension."""
        tot = self.raw.sum(axis=0)
        u = used.sum(axis=0)
        return {"cpu": float(u[0] / tot[0]), "memory": float(u[1] / tot[1]),
                "disk": float(u[2] / tot[2])}
