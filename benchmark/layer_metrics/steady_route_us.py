"""Superstep, XLA: device microseconds a superstep under the scope
``tw.route``, whole (stage 6 in the eager regime: the flattened
outboxes, the link's draw on every slot, the one variadic sort by
destination, insertion). Nothing to read where the builder brought no
``op_name``s."""

import steady_reduce


def read(trace, run):
    return steady_reduce.scope_us(trace, run, "tw.route")
