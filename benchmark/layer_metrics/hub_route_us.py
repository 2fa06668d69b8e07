"""Superstep, XLA: device microseconds a superstep under the scope
``tw.route``, whole (the sender compaction, the ladder's switch, the
rung's gather, sort by destination and link draw, the ranked
insertion). Nothing to read where the builder brought no ``op_name``s."""

import steady_reduce


def read(trace, run):
    return steady_reduce.scope_us(trace, run, "tw.route")
