"""Sharding: messages delivered across a shard boundary a superstep,
from the engine's ``last_run_stats`` ``boundary_msgs`` of the traced
jobs' calls (counted on each shard beside the state, summed in the
call's one readback). The dense ring on four shards reads 4: anything
else says the ring is not where the mesh says. Nothing to read from a
program that does not count them."""

import span_reduce


def read(trace, run):
    steps = span_reduce.supersteps(run)
    crossed = [j.get("boundary_msgs") for j in run["jobs"]]
    if not steps or None in crossed:
        return None
    return sum(crossed) / steps
