"""Superstep, kernels: mean device microseconds of one execution of the
fused superstep kernel (the events the configuration names by
``kernel_event``). Nothing to read where the cell runs no such kernel."""

import trace_reduce


def read(trace, run):
    part = run["facts"].get("kernel_event")
    evs = trace_reduce.named(trace.ops[0], part) if part else []
    if not evs:
        return None
    return sum(d for _, d, _ in evs) / len(evs) / 1e3
