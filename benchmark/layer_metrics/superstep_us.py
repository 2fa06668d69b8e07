"""Superstep, XLA: device-busy microseconds (leaf operations, copies in
flight beside them counted once) over the supersteps the traced jobs
executed (the engine's own count)."""

import trace_reduce


def read(trace, run):
    steps = sum(j["supersteps"] for j in run["jobs"])
    if not steps:
        return None
    return trace_reduce.busy_and_window(trace)[0] / steps / 1e3
