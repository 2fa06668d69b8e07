"""Sharding: collective operations the first chip executed over the
supersteps the traced jobs ran (an async collective counts once). What
``MeshComm`` asks of the mesh a superstep: a ``ppermute`` a plane in
``roll``, an ``all_gather`` in ``all_min``, a ``psum`` a counter.
``None`` from a trace that holds no collective."""

import span_reduce
import x4_reduce


def read(trace, run):
    steps = span_reduce.supersteps(run)
    count = x4_reduce.executed(trace.ops[0])
    if not count or not steps:
        return None
    return count / steps
