"""Sharding: messages a superstep whose destination lives on another
shard than their sender, from the engine's ``last_run_stats``
``remote_msgs`` of the traced jobs' calls (counted on each shard beside
the state, summed in the call's one readback). Uniform peers over four
shards read three in four of the pushes: 786 432 of 2^20. Nothing to
read from a program that does not count them."""

import span_reduce
import steady_x4_reduce


def read(trace, run):
    steps = span_reduce.supersteps(run)
    crossed = steady_x4_reduce.counted(run, "remote_msgs")
    if not steps or crossed is None:
        return None
    return sum(crossed) / steps
