"""Superstep, XLA: device microseconds a superstep under the scope
``tw.route/insert`` (``_insert_sorted`` on every lane: the rank in the
destination's group, the r-th hole by bit select, the flat scatters of
deliver time and payload)."""

import steady_reduce


def read(trace, run):
    return steady_reduce.scope_us(trace, run, "tw.route/insert")
