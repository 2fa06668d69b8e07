"""Sharding: collective operations the first chip executed over the
supersteps the traced jobs ran (an async collective counts once): an
``all_to_all`` a plane of the exchange (``6 + payload_width``), an
``all_gather`` in ``all_min``, a ``psum`` a counter, as the chip's
compiler left them. ``None`` from a trace that holds no collective."""

import span_reduce
import steady_x4_reduce


def read(trace, run):
    steps = span_reduce.supersteps(run)
    count = steady_x4_reduce.executed(trace.ops[0])
    if not count or not steps:
        return None
    return count / steps
