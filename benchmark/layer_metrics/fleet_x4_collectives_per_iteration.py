"""Sharding: collective operations the first chip executed over the
iterations of the loop the traced jobs ran (an async collective counts
once): the world-sharded fleet's superstep holds none, its loop's
condition one, evaluated once more than the loop iterates. ``None``
from a trace that holds no collective."""

import span_reduce
import x4_reduce


def read(trace, run):
    steps = span_reduce.supersteps(run)
    count = x4_reduce.executed(trace.ops[0])
    if not count or not steps:
        return None
    return count / steps
