"""The yardstick of `placement_roofline`: the chip's peaks, and the least
work the placement ALGORITHM needs, counted from shapes.

The count is of what any kernel that implements Nomad's Select over a dense
node table must do, whatever its form, so it reads the same before and after
a kernel is rewritten; it takes nothing from XLA's `cost_analysis()` (which
counts the implementation). Per program (one task group of one eval):

- bytes: one pass over the node-axis view it must read — capacity and used
  f32[N, R], node_ok u8[N], dyn_free f32[N] and the attribute columns its
  stanzas name i32[N, columns] — with N the node ROW BUCKET the table is
  padded to (the padding is the view's, not the kernel's);
- operations: per allocation and candidate node, the Select arithmetic
  itemised in `OPS_PER_CANDIDATE`.

Padding programs and speculative re-dispatches are no work. The least time
is the larger of bytes over the memory peak and operations over the compute
peak; the share is that over the measured device time of the placement
programs.
"""
from __future__ import annotations

#: published peaks of one chip, keyed by `jax.devices()[0].device_kind`.
#: Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
#: 819 GB/s HBM); the benchmark's own table, the program keeps none.
PEAKS = {
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
    "TPU v5e": {"flops_per_s": 197e12, "bytes_per_s": 819e9},
}

RESOURCES = 4  # cpu, memory, disk, network: the view's R

#: operations per allocation and candidate node
OPS_PER_CANDIDATE = {
    "fit: used + ask, compare, and-reduce (R dims)": 3 * RESOURCES,
    "free share of cpu and memory: divide, subtract": 4,
    "10^free, twice (one transcendental each)": 2,
    "fitness: add, subtract, clamp, normalise": 5,
    "anti-affinity, affinity, spread: add and count": 6,
    "mean of the parts: divide": 1,
    "feasibility mask and argmax compare": 3,
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak is recorded for device kind "
                       f"{device_kind!r}: add it to work.PEAKS with its "
                       f"source") from None


def placement_work(node_bucket: int, programs: float,
                   allocs_per_program: float, columns: float) -> dict:
    """Bytes and operations the algorithm needs for `programs` programs of
    `allocs_per_program` allocations over a `node_bucket`-row view."""
    per_node_bytes = (2 * RESOURCES * 4) + 1 + 4 + columns * 4
    ops = sum(OPS_PER_CANDIDATE.values())
    return {"bytes": programs * node_bucket * per_node_bytes,
            "ops": programs * allocs_per_program * node_bucket * ops}


def least_seconds(work: dict, device_kind: str) -> dict:
    pk = peaks(device_kind)
    t_mem = work["bytes"] / pk["bytes_per_s"]
    t_ops = work["ops"] / pk["flops_per_s"]
    return {"seconds": max(t_mem, t_ops),
            "bound": "memory" if t_mem >= t_ops else "compute"}
