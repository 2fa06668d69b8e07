"""Superstep, XLA: device microseconds a superstep under the scope
``tw.route/insert`` (``_insert_sorted``'s ranked branch: the rank in the
destination's group, append after the kept messages, the flat scatters
of deliver time, sender and both payload words, the overflow count and
the largest fan-in)."""

import steady_reduce


def read(trace, run):
    return steady_reduce.scope_us(trace, run, "tw.route/insert")
