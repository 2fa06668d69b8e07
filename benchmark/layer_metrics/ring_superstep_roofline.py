"""Superstep, kernels: the least time the chip could take for one fused
ring superstep as a share of the device time one superstep took, in
percent. The least time is the bytes ``kernel_costs.ring_superstep_bytes``
says a superstep must move over HBM (every state byte read once and
written once) over the published HBM bandwidth: the superstep is
HBM-bound, a few integer operations a byte.

The time is the device-busy time of the whole traced superstep (the
busy time ``device_idle_share`` and ``superstep_us`` read, over the
supersteps the traced jobs ran), not the kernel event's alone: XLA stages the kernel's operand by a copy of
its own (PERF.md, PR 23), so the kernel's event moves only half of the
bytes over HBM and would read 180 % against all of them."""

import trace_reduce


def read(trace, run):
    facts = run["facts"]
    if not facts.get("kernel_bytes") or not run["peaks"]:
        return None
    steps = sum(j["supersteps"] for j in run["jobs"])
    if not steps:
        return None
    busy_us = trace_reduce.busy_and_window(trace)[0] / steps / 1e3
    least_us = facts["kernel_bytes"] / (run["peaks"]["hbm_gbps"] * 1e3)
    return 100.0 * least_us / busy_us
