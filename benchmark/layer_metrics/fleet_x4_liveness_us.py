"""Sharding: microseconds an iteration in which the ``tw.liveness``
all-reduce (the ``psum`` in the quiet loop's condition,
``ShardedBatchedEngine._any_world``) ran or was in flight on a chip:
``x4_reduce.collective_ns``'s reading of that one collective, averaged
over the chips. It holds the wait for the slowest chip: a chip that
took a narrower rung reaches the reduction early and sits in it.
``None`` from a program that does not name the scope."""

import fleet_x4_reduce
import x4_reduce


def read(trace, run):
    return fleet_x4_reduce.liveness_us(trace, run, x4_reduce.collective_ns)
