"""Superstep, XLA: device microseconds a superstep under the scope
``tw.route/sort``, averaged over the chips: the eager path's one
variadic sort by (destination, sender-major rank) of the lanes a
device received, ``shards * bucket_cap`` of them
(``steady_sort_us``'s reading on four planes)."""

import steady_x4_costs
import x4_reduce


def read(trace, run):
    return x4_reduce.scope_us(trace, run, steady_x4_costs.SORT_SCOPE)
