"""Superstep, XLA: device microseconds a superstep under the scope
``tw.route``, whole (the adaptive regime: sender compaction, the
conditional of eleven rungs with the link's draw, the sorts and the
staging scatters of the rung taken, the fill of the holes). Nothing to
read where the builder brought no ``op_name``s."""

import steady_reduce


def read(trace, run):
    return steady_reduce.scope_us(trace, run, "tw.route")
